#!/usr/bin/env python3
"""Checks of the benchmark's own yardstick; runs on the CPU in under a minute.

    python3 -m chipbench.selftest            # all checks, exit 0 if they hold
    python3 -m chipbench.selftest --record D # (on a machine with >= 2 TPU chips) record
                                             # a tiny trace and its reduction into D

What is checked: the interval arithmetic and the reductions of ``reduce.py``
on a hand-made trace whose numbers are worked out by hand, and on a small
trace recorded on the chip (``recorded/``: a few steps of a tiny sharded
program on two devices' planes) against the reduction pinned when it was
recorded; ``flops.py`` and the references' counts against hand counts for
both configurations; that each traffic generator gives identical requests for
one seed and different ones for another; where a traced serving run starts and
stops the profiler, the host window that leaves and every cell file against its
own stop rate (``test_trace_window.py``, on a virtual clock), and the readers that
are shares of the device's time on a hand-made trace (``test_device_shares.py``,
through the same table); that ``BENCHMARK.json``, the
directories and the readers agree (every metric has its reader, every cell its
files, and ``run.py`` names none of them); and the references
(``check_references``, run as part of ``check_files``): every configuration
names one that is there, exports the protocol and covers its program, a
configuration that names none or a missing one is refused, a program with a
key the reference does not implement is refused by that key's name, and the
system's float32 model agrees with each configuration's reference at the
rehearsal size (``parity.py``), within a tolerance that bfloat16 fails. The
last part compiles small programs on the CPU: about 20 s, most of the whole.
"""

from __future__ import annotations

import argparse
import glob
import importlib
import json
import math
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def close(a: float, b: float, rel: float = 1e-9) -> bool:
    return math.isclose(a, b, rel_tol=rel, abs_tol=1e-12)


def check_intervals() -> None:
    from . import reduce as R

    assert R.merge([(0, 1), (0.5, 2), (3, 4), (4, 4)]) == [(0, 2), (3, 4)]
    assert R.subtract([(0, 10)], [(1, 2), (3, 4), (9, 12)]) == [(0, 1), (2, 3), (4, 9)]
    assert R.subtract([(0, 1), (2, 3)], [(0.5, 2.5)]) == [(0, 0.5), (2.5, 3)]
    assert R.clip([(0, 2), (3, 5)], 1, 4) == [(1, 2), (3, 4)]


def check_handmade_trace() -> None:
    """Two devices over a 10 s window.
    dev0: fusion.1 [0,4), all-reduce.1 [4,5) (synchronous: exposed),
          all-gather-start.1 [5,5.1), fusion.2 [5.1,7), all-gather-done.1 [7,7.5), idle to 10.
      busy 7.5; collective in flight [4,5) + [5,7.5) = 3.5; compute covers [5.1,7) of it,
      so exposed = 1 + 0.1 + 0.5 = 1.6; gaps: [7.5,10) = 2.5 under span "fetch".
    dev1: while.9 [0,9) whose body is fusion.1 [0,8) and all-gather.7 [8,9) (the trace
      nests a loop's body in the loop): busy 9; the loop itself is charged nothing, the
      all-gather is in flight for 1 and exposed for 1, although the loop "runs" meanwhile.
    means: busy 8.25, collective 2.25, exposed 1.3; worst (most idle) device dev0."""
    from . import reduce as R

    tr = R.Trace(
        devices={
            "/device:TPU:0": [("fusion.1", 0, 4), ("all-reduce.1", 4, 5),
                              ("all-gather-start.1", 5, 5.1), ("fusion.2", 5.1, 7),
                              ("all-gather-done.1", 7, 7.5)],
            "/device:TPU:1": [("while.9", 0, 9), ("fusion.1", 0, 8), ("all-gather.7", 8, 9)],
        },
        host=[(R.WINDOW_SPAN, 0, 10), ("train_batch", 0, 0.2), ("fetch", 0.3, 10),
              ("python noise", 8, 9)])
    r = R.reduce(tr, ("train_batch", "fetch"))
    assert close(r["window_s"], 10) and r["devices"] == 2
    assert close(r["busy_s_mean"], 8.25) and close(r["busy_s_worst"], 7.5)
    assert close(r["collective_s_mean"], 2.25), r["collective_s_mean"]
    assert close(r["collective_exposed_s_mean"], 1.3), r["collective_exposed_s_mean"]
    assert close(r["op_seconds"]["fusion.1"], (4 + 8) / 2) and "while.9" not in r["op_seconds"]
    assert close(sum(r["op_seconds"].values()), r["busy_s_mean"])  # self times tile busy time
    assert close(r["idle_by_span"]["fetch"], 2.5) and len(r["idle_by_span"]) == 1
    assert close(R.op_seconds_matching(r, r"^fusion"), (4 + 1.9 + 8) / 2)
    b = R.breakdown(r)
    assert b["device_ops"][0][0] == "fusion.1" and b["idle_gaps"] == [["fetch", 2.5]]
    segs = R.leaf_segments([("w", 0, 10), ("a", 1, 3), ("b", 2, 2.5), ("c", 6, 12)], 0, 10)
    assert segs == [("w", 0, 1), ("a", 1, 2), ("b", 2, 2.5), ("a", 2.5, 3), ("w", 3, 6),
                    ("c", 6, 10)], segs


def check_recorded_trace() -> None:
    from . import reduce as R

    files = sorted(glob.glob(os.path.join(HERE, "recorded", "*.xplane.pb")))
    assert files, "no recorded trace under chipbench/recorded/"
    for path in files:
        with open(path.replace(".xplane.pb", ".expected.json")) as f:
            want = json.load(f)
        tr = R.load(path)
        assert len(tr.devices) == want["devices"], (len(tr.devices), want["devices"])
        got = R.reduce(tr, tuple(want["span_names"]))
        for key in ("window_s", "busy_s_mean", "busy_s_worst", "collective_s_mean",
                    "collective_exposed_s_mean"):
            assert close(got[key], want[key], 1e-6), (key, got[key], want[key])
        for name, seconds in want["op_seconds_top"]:
            assert close(got["op_seconds"][name], seconds, 1e-6), name
        for name, seconds in want["idle_by_span"].items():
            assert close(got["idle_by_span"][name], seconds, 1e-6), name
        # the invariants any trace must satisfy
        assert 0 < got["busy_s_worst"] <= got["busy_s_mean"] <= got["window_s"]
        assert 0 <= got["collective_exposed_s_mean"] <= got["collective_s_mean"]
        assert got["collective_s_mean"] > 0, "the recorded program has collectives"


def check_flops() -> None:
    from . import flops
    from .references import program_of

    def program(name):
        with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
            return program_of(json.load(f))

    # pythia-1.4b by hand: a layer has 4*2048^2 + 2*2048*8192 = 50,331,648 matmul
    # parameters; 24 layers + the 2048 x 50304 head = 1,310,982,144 on a token's path;
    # all parameters (two embeddings-sized matrices, biases, LayerNorms): 1,414,647,808,
    # the count the model card gives.
    p = flops.param_counts(program("pythia-1.4b"))
    assert p["matmul_per_layer"] == 50_331_648
    assert p["matmul_on_token_path"] == 24 * 50_331_648 + 2048 * 50304 == 1_310_982_144
    assert p["total"] == 1_414_647_808
    # 6 x 1,310,982,144 + 3 x 24 x 2 x 2048 x 2048 (causal attention, fwd + bwd)
    assert flops.train_flops_per_token(program("pythia-1.4b"), 2048) == (
        6 * 1_310_982_144 + 3 * 24 * 2 * 2048 * 2048) == 8_469_872_640
    # bloom-1b7: same layers, tied 250,880-wide head: 1,722,408,960 parameters
    b = flops.param_counts(program("bloom-1b7"))
    assert b["matmul_on_token_path"] == 24 * 50_331_648 + 2048 * 250_880
    assert b["total"] == 1_722_408_960
    # flash on [8, 2048, 16, 128] causal: scores 8*16*2048^2/2 = 268,435,456 pairs;
    # forward 2 matmuls x 2 x 128 per pair; backward 5; q,k,v,o of 67,108,864 B each
    f = flops.flash_cost(8, 2048, 16, 128)
    assert f == {"flops": 4 * 268_435_456 * 128, "bytes": 4 * 67_108_864}
    assert flops.flash_cost(8, 2048, 16, 128, backward=True) == {
        "flops": 10 * 268_435_456 * 128, "bytes": 8 * 67_108_864}
    # decode attention over 10,000 live tokens, 24 layers of 16 x 128 in bf16
    d = flops.decode_attention_cost(10_000, 16, 128, 24)
    assert d == {"flops": 24 * 4 * 10_000 * 2048, "bytes": 24 * 2 * 10_000 * 2048 * 2}
    peak = {"bf16_flops_per_s": 200e12, "hbm_bytes_per_s": 800e9}
    r = flops.roofline({"flops": 2e12, "bytes": 4e9}, 0.02, peak)  # 10 ms vs 5 ms -> compute
    assert r["bound"] == "compute" and close(r["pct"], 50.0)
    r = flops.roofline({"flops": 2e9, "bytes": 8e9}, 0.02, peak)
    assert r["bound"] == "memory" and close(r["pct"], 50.0)


def check_traffic() -> None:
    """Every generator under ``traffic/``, on the parameters it documents as
    its ``EXAMPLE``, and every cell's own ``traffic`` block; then the loop that
    drives serving traffic (``check_trace_window``)."""
    import numpy as np

    blocks = {}
    for path in sorted(glob.glob(os.path.join(HERE, "traffic", "*.py"))):
        gen = importlib.import_module(f"chipbench.traffic.{os.path.basename(path)[:-3]}")
        if hasattr(gen, "generate"):
            blocks[f"traffic/{os.path.basename(path)}"] = gen.EXAMPLE
    for path in sorted(glob.glob(os.path.join(HERE, "workloads", "*.json"))):
        with open(path) as f:
            blocks[os.path.basename(path)] = json.load(f)["traffic"]
    assert {b["kind"] for b in blocks.values()} >= {"open_loop", "closed_loop", "zipf_packed"}
    for name, params in blocks.items():
        gen = importlib.import_module(f"chipbench.traffic.{params['kind']}")
        make = lambda seed: gen.generate(params, seed=seed, seconds=10.0, vocab_size=1000,
                                         n_slots=8)
        a, b, c = make(3), make(3), make(4)
        if "requests" in a:
            flat = lambda t: [(r["uid"], r["prompt"].tolist(), r["max_new_tokens"],
                               r["temperature"], r["top_p"], r["arrival_time"])
                              for r in t["requests"]]
            assert flat(a) == flat(b), f"{name}: one seed, two request lists"
            assert flat(a) != flat(c), f"{name}: two seeds, one request list"
            # the work offered does not depend on the seed
            lo, hi = a["window"]
            whole = len(a["requests"]) // 64 * 64  # closed loop: whole blocks of the grid
            work = lambda t: sorted(len(r["prompt"]) for r in t["requests"][:whole]
                                    if lo <= r["arrival_time"] < hi or t["loop"] == "closed")
            assert work(a) == work(c), f"{name}: the seed changed the work"
        else:
            x, y, z = next(a["batches"]), next(b["batches"]), next(c["batches"])
            assert np.array_equal(x, y) and not np.array_equal(x, z)
            assert x.min() >= 0 and x.max() < 1000
    # part of this check and not one of its own: the repository's test of this
    # file counts its six "ok" lines (tests/test_tracing_spans.py)
    check_trace_window()


def check_files() -> None:
    from .references import available

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for group, package in (("end_to_end", "end_to_end"), ("per_layer", "layer_metrics")):
        for m in bench[group]:
            reader = importlib.import_module(f"chipbench.{package}.{m['name'].split('.')[0]}")
            assert reader.NAME == m["name"].split(".")[0] and reader.UNIT == m["unit"], m["name"]
            if group == "per_layer":
                assert reader.LAYER == m["layer"], m["name"]
                cells = set(m.get("workloads", [w["name"] for w in bench["workloads"]]))
                moved = e2e[m["moves"]]
                assert cells <= set(moved.get("workloads", cells)), (
                    f"{m['name']} is reported where {m['moves']} is not")
    for w in bench["workloads"]:
        with open(os.path.join(HERE, "workloads", f"{w['name']}.json")) as f:
            cell = json.load(f)
        assert (cell["config"], cell["chips"]) == (w["config"], w["chips"]), w["name"]
        importlib.import_module(f"chipbench.drivers.{cell['driver']}")
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"])), c["file"]
    with open(os.path.join(HERE, "run.py")) as f:
        text = f.read()
    names = ([w["name"] for w in bench["workloads"]] + [c["name"] for c in bench["configs"]]
             + [m["name"].split(".")[0] for g in ("end_to_end", "per_layer") for m in bench[g]])
    named = [n for n in names + available() if n in text]
    assert not named, f"run.py names {named}"
    # one more kind of file found by name; not a line of its own in main()'s list because
    # tests/test_tracing_spans.py pins the number of "ok" lines and no benchmark PR may edit it
    check_references(bench)


def _refused(call, *words) -> None:
    """``call`` must end in a refusal whose message has every one of ``words``."""
    from .references import NotCovered

    try:
        call()
    except (SystemExit, NotCovered) as e:
        missing = [w for w in words if w not in str(e)]
        assert not missing, f"the refusal {str(e)!r} does not say {missing}"
    else:
        raise AssertionError(f"not refused (expected a refusal naming {words})")


def check_references(bench: dict) -> None:
    from . import parity
    from .references import Program, available, load_reference, named_reference, program_of

    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            config = json.load(f)
        for block in ("program", "rehearse_program"):
            program = program_of(config, block)
            load_reference(program).param_counts(program)  # there, whole, and covers the block
    there = available()
    _refused(lambda: named_reference({"name": "x"}), "names no reference", *there)
    _refused(lambda: named_reference({"name": "x", "reference": "no_such"}),
             "no references/no_such.py", *there)
    with open(os.path.join(HERE, "configs", "pythia-1.4b.json")) as f:
        covered = json.load(f)["rehearse_program"]
    for key, value in (("moe_every", 2), ("use_bias", False), ("rotary_interleaved", True),
                       ("activation", "relu"), ("norm_style", "post"), ("num_kv_heads", 4)):
        odd = Program({**covered, key: value}, "gpt_family")
        _refused(lambda: load_reference(odd), repr(key))
    # the system against each configuration's reference, and the tolerance against bfloat16
    for name, which in parity.cases():
        parity.check(name, which)
        err = parity.error(name, which, bf16=True)
        assert err > parity.TOL[which], (
            f"{name} {which}: bfloat16 compute passes the tolerance ({err:.3g})")


def record(out_dir: str) -> int:
    """A few steps of a tiny sharded program under the profiler, on the first
    two devices: matmuls, an all-gather and a psum per step."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from . import reduce as R

    devs = jax.devices()[:2]
    if len(devs) < 2 or devs[0].platform != "tpu":
        print("selftest --record needs two TPU chips", file=sys.stderr)
        return 3
    mesh = Mesh(np.asarray(devs), ("x",))
    w = jax.device_put(jnp.ones((1024, 1024), jnp.bfloat16), NamedSharding(mesh, P("x", None)))
    x = jax.device_put(jnp.ones((512, 1024), jnp.bfloat16), NamedSharding(mesh, P("x", None)))

    @jax.jit
    def step(w, x):
        full = jax.lax.with_sharding_constraint(w, NamedSharding(mesh, P(None, None)))
        y = jnp.tanh(x @ full) @ full.T
        return jnp.sum(y.astype(jnp.float32))  # all-gather in, all-reduce out

    step(w, x).block_until_ready()
    os.makedirs(out_dir, exist_ok=True)
    tmp = os.path.join(out_dir, "_trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level, opts.host_tracer_level = 0, 1
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation(R.WINDOW_SPAN):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("train_batch"):
                out = step(w, x)
            with jax.profiler.TraceAnnotation("fetch"):
                out.block_until_ready()
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb"))[0]
    dst = os.path.join(out_dir, "tiny_two_chips.xplane.pb")
    os.replace(src, dst)
    spans = ("train_batch", "fetch")
    tr = R.load(dst)
    got = R.reduce(tr, spans)
    top = sorted(got["op_seconds"].items(), key=lambda kv: -kv[1])[:5]
    with open(dst.replace(".xplane.pb", ".expected.json"), "w") as f:
        json.dump({"devices": len(tr.devices), "span_names": spans,
                   "device_kind": devs[0].device_kind,
                   **{k: got[k] for k in ("window_s", "busy_s_mean", "busy_s_worst",
                                          "collective_s_mean", "collective_exposed_s_mean",
                                          "idle_by_span")},
                   "op_seconds_top": top, "all_ops": sorted(got["op_seconds"])}, f, indent=1)
    print(json.dumps({"recorded": dst, "bytes": os.path.getsize(dst), "reduced": {
        k: v for k, v in got.items() if k != "op_seconds"}, "ops": sorted(got["op_seconds"])}))
    return 0


def check_trace_window() -> None:
    """Where a traced serving run starts and stops the profiler and what host
    window that leaves: ``test_trace_window.py``'s cases, on a virtual clock."""
    from . import test_trace_window

    for name, case in test_trace_window.CASES.items():
        try:
            case()
        except AssertionError as e:
            raise AssertionError(f"{name}: {e}") from e


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--record", metavar="DIR")
    args = ap.parse_args(argv)
    if args.record:
        return record(args.record)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    checks = [check_intervals, check_handmade_trace, check_recorded_trace, check_flops,
              check_traffic, check_files]
    failed = 0
    for check in checks:
        try:
            check()
            print(f"ok    {check.__name__}")
        except AssertionError as e:
            failed += 1
            print(f"FAIL  {check.__name__}: {e}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
