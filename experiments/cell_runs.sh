#!/bin/bash
# Several runs of ONE benchmark cell in one chip call, each from a tree of its own, so that
# two commits are measured on one machine, or a cell is seen cold and then warm (PR 54):
#
#   chiprun --timeout 3000 -- bash experiments/cell_runs.sh <cell> <step> [<step> ...]
#
# A step is "dir:tag:seed:trace[:cold[:seconds]]": the tree to run from ("." or a git-ignored copy such
# as .chipbench_tree/parent, made with `git archive <commit> | tar -x -C ...`), a tag for the
# output files (chiprun_out/runs/<cell>.<tag>.out / .err), the seed, --trace 0|1, and "cold"
# to keep the compile cache, from this step on, in a directory that this call makes and finds
# empty (mktemp -d under TMPDIR, or under the checkout's git-ignored .chipbench_tree/ where
# TMPDIR is not set; removed when the call ends), so the cell is seen cold, then warm, where
# the machine's own cache would otherwise be used ("" keeps that), and the window's seconds
# (51, the benchmark's; set-up does not depend on it).
# Prints each run's setup / measured / setup_anatomy lines and the head of its result.
W=$1; shift
ROOT=$PWD
OUT=$ROOT/chiprun_out/runs; mkdir -p $OUT
mkdir -p "${TMPDIR:-$ROOT/.chipbench_tree}"
COLD=$(mktemp -d "${TMPDIR:-$ROOT/.chipbench_tree}/cell_runs_cache.XXXX") || exit 1
trap 'rm -rf "$COLD"' EXIT
echo "== cold cache directory $COLD holds $(ls -A "$COLD" | wc -l) files before the first step"
for step in "$@"; do
  IFS=: read dir tag seed trace cache seconds <<< "$step"
  t0=$(date +%s)
  if [ "$cache" = "cold" ]; then export JAX_COMPILATION_CACHE_DIR=$COLD; fi
  (cd $ROOT/$dir && python3 -m chipbench.run --workload $W --seed $seed --seconds ${seconds:-51} --trace $trace) > $OUT/$W.$tag.out 2> $OUT/$W.$tag.err
  echo "== $W $tag rc=$? $(( $(date +%s) - t0 ))s cold_cache_files=$(ls -A "$COLD" | wc -l)"
  grep -E '"event": "(setup|measured|setup_anatomy)"' $OUT/$W.$tag.out | cut -c1-2500
  tail -1 $OUT/$W.$tag.out | cut -c1-400
done
