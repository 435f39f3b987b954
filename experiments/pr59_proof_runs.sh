#!/bin/bash
# PR 59: the runs that prove the committed files are enough and that this PR's benchmark
# files work on the parent's program, as one chip call (PERF.md section 6 cites its numbers).
#
#   bash experiments/pr59_proof_runs.sh prepare      # here, where .git is: makes the two trees
#   chiprun --timeout 2400 -- bash experiments/pr59_proof_runs.sh run
#
# prepare: .chipbench_tree/tracked = `git archive $(git write-tree)` (run `git add -A` first),
# .chipbench_tree/parent = HEAD's files with BENCHMARK.json and chipbench/ of the index laid
# over them, as the driver lays them (.chipbench_tree/ is git-ignored and goes with the copy).
# run: the parent at the new cell (it has to fail at once, cleanly), the new cell traced from
# the tracked tree, one old cell traced on the parent under this PR's benchmark files.
set -u
W=mellum2-12b-a2.5b-L8.serve-repoctx
T=.chipbench_tree
case "${1:-}" in
prepare)
  rm -rf $T/tracked $T/parent && mkdir -p $T/tracked $T/parent
  git archive "$(git write-tree)" | tar -x -C $T/tracked
  git archive HEAD | tar -x -C $T/parent
  git archive "$(git write-tree)" BENCHMARK.json chipbench | tar -x -C $T/parent
  du -sh $T/tracked $T/parent
  ;;
run)
  bash experiments/cell_runs.sh $W $T/parent:pparent:${SEED_PARENT:-5900002001}:0 \
    $T/tracked:ptracked:${SEED_TRACKED:-5900002777}:1 | grep -v '"event": "setup_anatomy"' | cut -c1-1200
  bash experiments/cell_runs.sh ${OLD_CELL:-bloom-1b7.serve-doc} \
    $T/parent:pparent:${SEED_OLD:-5900002888}:1 | grep -v '"event": "setup_anatomy"' | cut -c1-900
  ;;
*) echo "usage: $0 prepare | run"; exit 2 ;;
esac
