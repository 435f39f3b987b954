"""What starting up was made of (telemetry/tracing.py, PR 54) and what reads it.

Contracts under test:
  * a span opened as kept, and every span opened inside it, outlives the ring:
    ``spans(since)`` returns it once after ``RING_CAPACITY + 1`` later spans; the
    kept list stops at its cap and counts what it dropped; ``keep`` can be set
    on an open span; a steady span is not kept;
  * spans nest by thread, not by tracer, and the module has a span of its own;
  * every outermost trace, lowering and compile of the process is a kept
    ``xla/*`` span under whatever span is open on the thread, with jax's
    ``program`` name, the inner jits counted and, on a compile, what the
    persistent cache did (``written`` in one process is ``hit`` in the next);
  * the watchdog's ``compile`` event says what the first call was made of;
  * ``build_serving_engine`` and ``deepspeed_tpu.initialize`` leave
    ``startup/build`` with the engine's phases under it, a call that compiled
    is kept, and ``Telemetry.snapshot()`` has the ``startup`` table;
  * each ``setup_*`` reader under chipbench/layer_metrics/ returns the
    hand-computed value on a hand-made list and None without the spans.
"""

import importlib
import json
import os
import subprocess
import sys
import time
from types import SimpleNamespace

import numpy as np
import pytest

from deepspeed_tpu.telemetry import SpanTracer, Telemetry, tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# kept spans, one stack a thread
# ---------------------------------------------------------------------------

def _kept_outlive_the_ring():
    tr = SpanTracer()
    tracing.clear_spans()
    with tr.span("startup/build", keep=True, role="both") as build:
        with tr.span("draw") as draw:
            pass
    with tr.span("steady") as steady:
        pass
    assert build.keep and draw.keep and not steady.keep  # the parent's flag, nothing else
    assert [sp.path for sp in tracing.spans()] == ["startup/build/draw", "startup/build", "steady"]
    for i in range(tracing.RING_CAPACITY + 1):
        with tr.span("s", i=i):
            pass
    got = tracing.spans()
    # the ring turned over: the steady span fell out, the kept ones are still
    # there, once each, in the order they ended
    assert len(got) == tracing.RING_CAPACITY + 2 and len({sp.id for sp in got}) == len(got)
    assert got[0] is draw and got[1] is build and got[2].attrs == {"i": 1}
    assert [sp.path for sp in tracing.spans(build.t1)][:1] == ["s"]  # t0 >= since, as before
    assert tracing.kept_stats() == {"kept": 2, "dropped": 0, "capacity": tracing.KEPT_CAPACITY}
    assert tracing.startup_table()["phases"] == {"startup/build/draw": draw.dur_s,
                                                 "startup/build": build.dur_s}
    tracing.clear_spans()
    assert tracing.spans() == [] and tracing.kept_stats()["kept"] == 0


def _kept_list_stops_at_its_cap(monkeypatch):
    tr = SpanTracer()
    tracing.clear_spans()
    monkeypatch.setattr(tracing, "KEPT_CAPACITY", 3)
    for i in range(5):  # a recompile storm cannot grow it
        with tr.span("k", keep=True, i=i):
            pass
    stats = tracing.kept_stats()
    assert (stats["kept"], stats["dropped"]) == (3, 2)
    assert [sp.attrs["i"] for sp in tracing.spans()] == [0, 1, 2, 3, 4]  # the ring has them all
    tracing.clear_spans()
    assert tracing.kept_stats()["dropped"] == 0


def _keep_is_settable_on_an_open_span():
    tr = SpanTracer()
    since = time.perf_counter()
    with tr.span("decode") as call:
        with tr.span("dispatch") as dispatch:
            pass
        call.keep = True  # what a worker call does once it knows that it compiled
        with tr.span("fetch") as fetch:
            pass
    assert call.keep and not fetch.keep and not dispatch.keep  # the call alone: no half subtree
    kept = tracing.startup_table(since)["kept"]["kept"]
    with tr.span("decode"):  # a steady call adds nothing to the kept list
        pass
    assert tracing.startup_table(since)["kept"]["kept"] == kept


def _two_tracers_nest_on_one_thread():
    a, b = SpanTracer(), SpanTracer()
    since = time.perf_counter()
    with tracing.span("startup/build", keep=True, replica_id=3) as build:  # the module's own
        with a.span("mesh") as mesh:
            with b.span("cache") as cache:
                pass
    assert (mesh.parent, cache.parent) == (build.id, mesh.id)
    assert cache.path == "startup/build/mesh/cache" and cache.depth == 2
    assert cache.replica_id == 3 and cache.keep
    assert [sp.path for sp in tracing.spans(since)] == [
        "startup/build/mesh/cache", "startup/build/mesh", "startup/build"]


@pytest.mark.parametrize("case", [_kept_outlive_the_ring, _kept_list_stops_at_its_cap,
                                  _keep_is_settable_on_an_open_span,
                                  _two_tracers_nest_on_one_thread],
                         ids=lambda f: f.__name__.lstrip("_"))
def test_kept_spans(case, monkeypatch):
    case(*([monkeypatch] if case.__code__.co_argcount else []))


# ---------------------------------------------------------------------------
# every trace, lowering and compile of the process
# ---------------------------------------------------------------------------

def test_first_call_leaves_xla_spans_under_the_open_span():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def inner(x):
        return jnp.sin(x) * 2

    @jax.jit
    def outer_fn(x):
        return jnp.where(x > 0, inner(x), x)

    x = jnp.ones(7)  # its own eager programs end before the span opens
    before = tracing.xla_totals()
    with tracing.span("startup/build", keep=True) as build:
        with tracing.span("draw") as draw:
            jax.block_until_ready(outer_fn(x))
            t_first = time.perf_counter()
            outer_fn(x)  # a second call: no span
    spans = [sp for sp in tracing.spans(build.t0) if sp.name.startswith("xla/")]
    assert [sp.name for sp in spans] == ["xla/trace", "xla/lower", "xla/compile"]
    trace, lower, comp = spans
    assert all(sp.parent == draw.id and sp.path == f"startup/build/draw/{sp.name}" and sp.keep
               for sp in spans)
    assert trace.attrs["program"] == "outer_fn" and comp.attrs["program"] == "jit(outer_fn)"
    # jax traces every inner jit inside the outer's interval: counted, not spans
    assert trace.attrs["inner"] >= 3 and lower.attrs["inner"] == comp.attrs["inner"] == 0
    assert comp.attrs["cache"] in ("hit", "written", "not_kept") and "cache" not in trace.attrs
    if comp.attrs["cache"] == "hit":
        assert comp.attrs["load_s"] > 0 and "saved_s" in comp.attrs
    # disjoint on the thread, inside the span that caused them, on its clock
    assert draw.t0 <= trace.t0 <= trace.t1 <= lower.t0 <= lower.t1 <= comp.t0 <= comp.t1 <= t_first
    grew = [b - a for a, b in zip(before, tracing.xla_totals())]
    np.testing.assert_allclose(grew[:3], [trace.dur_s, lower.dur_s, comp.dur_s])
    assert sum(grew[3:]) == 1
    (row,) = [r for r in tracing.startup_table(build.t0)["programs"] if r["program"] == "outer_fn"]
    assert row[comp.attrs["cache"]] == 1 and row["trace_s"] == trace.dur_s


_CHILD = """
import json, sys, jax, jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from deepspeed_tpu.telemetry import tracing
x = jnp.arange(64.0)
with tracing.span("startup/build", keep=True) as build:
    jax.block_until_ready(jax.jit(lambda v: jnp.tanh(v) @ v, inline=False)(x))
print(json.dumps([sp.attrs for sp in tracing.spans(build.t0) if sp.name == "xla/compile"]))
"""


def test_written_in_one_process_is_hit_in_the_next(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(tmp_path),
           "PYTHONPATH": ROOT}

    def child():
        out = subprocess.run([sys.executable, "-c", _CHILD], env=env, cwd=ROOT, check=True,
                             capture_output=True, text=True, timeout=120).stdout
        (attrs,) = json.loads(out.strip().splitlines()[-1])
        return attrs

    first, second = child(), child()
    assert first["cache"] == "written" and "load_s" not in first
    assert second["cache"] == "hit" and second["load_s"] > 0 and "saved_s" in second
    assert first["program"] == second["program"]


def test_the_listener_can_be_taken_off_and_put_back():
    import jax
    import jax.numpy as jnp

    def first_call(n):
        since = time.perf_counter()
        jax.block_until_ready(jax.jit(lambda x: jnp.tanh(x) + n)(jnp.ones(n)))
        return [sp.name for sp in tracing.spans(since) if sp.name.startswith("xla/")]

    totals = tracing.xla_totals()
    tracing.listen(False)
    try:
        tracing.listen(False)  # taken off once: the second call finds nothing to do
        assert first_call(41) == [] and tracing.xla_totals() == totals
    finally:
        tracing.listen()
    tracing.listen()  # installed once
    assert first_call(43).count("xla/compile") >= 1  # the function's, and ``ones``' own


def test_watchdog_compile_event_says_what_the_first_call_was_made_of():
    import jax
    import jax.numpy as jnp

    tm = Telemetry()
    fn = tm.watch(jax.jit(lambda x: jnp.cumsum(x * 3.0)), "probe/cumsum", stable=True)
    x = jnp.ones(33)
    jax.block_until_ready(fn(x))
    fn(x)  # steady: no event
    (ev,) = tm.watchdog.events
    assert ev["trace_s"] > 0 and ev["lower_s"] > 0 and ev["backend_s"] > 0
    assert ev["trace_s"] + ev["lower_s"] + ev["backend_s"] <= ev["compile_s"]
    assert ev["cache"] in ("hit", "written", "not_kept")
    # the verdict is the event's, the row's and the startup table's: no counter repeats it
    assert not [n for n in tm.registry.snapshot()["counters"] if "cache" in n or "not_kept" in n]
    (row,) = tm.watchdog.compile_table()
    assert row["cache"] == [ev["cache"]] and row["backend_s"] == ev["backend_s"]
    assert row["total_compile_s"] == ev["compile_s"]


# ---------------------------------------------------------------------------
# start-up spans where the work happens
# ---------------------------------------------------------------------------

SPEC = {"model": {"vocab_size": 97, "max_seq_len": 128, "num_layers": 2, "num_heads": 4,
                  "hidden_size": 32, "dtype": "float32", "loss_chunk_size": 0,
                  "decode_attn": "xla", "pos_emb": "rotary"},
        "engine_dtype": "fp32",
        "serving": {"n_slots": 2, "max_seq_len": 128,
                    "prefix_cache": {"enabled": True, "n_slots": 4, "block": 8}}}


def test_build_serving_engine_leaves_startup_build_with_its_phases():
    from deepspeed_tpu.inference.serving import Request
    from deepspeed_tpu.launcher.serving_worker import build_serving_engine

    since = time.perf_counter()
    srv = build_serving_engine(SPEC, replica_id="b-1", role="both")
    spans = tracing.spans(since)
    (build,) = [sp for sp in spans if sp.path == "startup/build"]
    # ``rotary_kinds`` (PR 59): how each layer kind of the program turns its q and k
    assert build.attrs == {"role": "both", "rotary_kinds": "plain(10000)"}
    assert build.replica_id == "b-1" and build.parent is None
    phases = [sp for sp in spans if sp.parent == build.id]
    # the engine's own, whatever tracer opened each (``cache`` is the serving telemetry's)
    assert [sp.name for sp in phases] == ["mesh", "shapes", "draw", "cache"]
    assert all(sp.keep and sp.replica_id == "b-1" and build.t0 <= sp.t0 <= sp.t1 <= build.t1
               for sp in phases)
    by_name = {sp.name: sp for sp in phases}
    xla = [sp for sp in spans if sp.name.startswith("xla/")]
    assert xla and all(sp.path.startswith("startup/build/") for sp in xla)
    # the weights' draw and both allocation programs (slot cache, prefix pool)
    # compiled or were loaded under their phase
    compiles = [sp.parent for sp in xla if sp.name == "xla/compile"]
    assert compiles.count(by_name["draw"].id) >= 1 and compiles.count(by_name["cache"].id) >= 2

    # a call that compiled is kept, with its trace / lower / compile; a steady one is not
    t_serve = time.perf_counter()
    prompt = np.arange(9, dtype=np.int32)
    assert all(r.ok for r in srv.serve([Request(uid=1, prompt=prompt, max_new_tokens=4)]).values())
    calls = [sp for sp in tracing.spans(t_serve) if sp.name in ("prefill", "decode")]
    assert {sp.keep for sp in calls} == {True, False}
    assert all(sp.keep == sp.attrs["compiled"] for sp in calls)
    first = next(sp for sp in calls if sp.name == "decode" and sp.keep)
    under = [sp for sp in tracing.spans(t_serve) if sp.path.startswith(first.path + "/")
             and sp.name.startswith("xla/")]
    assert [sp.name for sp in under] == ["xla/trace", "xla/lower", "xla/compile"]
    assert under[-1].path == "serve/step/decode/dispatch/enqueue/xla/compile"

    table = srv.telemetry_snapshot()["startup"]
    assert table["phases"]["startup/build"] >= build.dur_s  # summed over the process's builds
    assert {"startup/build/draw", "startup/build/cache"} <= set(table["phases"])
    decode = next(r for r in table["programs"] if r["program"] == "decode")
    assert decode["hit"] + decode["written"] + decode["not_kept"] >= 1 and decode["trace_s"] > 0
    assert table["kept"]["dropped"] == 0
    json.dumps(table)  # plain data
    (ev,) = [e for e in srv.telemetry.watchdog.events if e["name"] == "serving/decode"]
    assert 0 < ev["trace_s"] + ev["lower_s"] + ev["backend_s"] <= ev["compile_s"]


def test_initialize_leaves_startup_build_and_keeps_the_step_that_compiled():
    import deepspeed_tpu
    from simple_model import base_config, random_tokens, tiny_transformer

    cfg = base_config()
    cfg["mesh"] = {"data": -1}
    since = time.perf_counter()
    engine, _, _, _ = deepspeed_tpu.initialize(model=tiny_transformer(), config=cfg)
    spans = tracing.spans(since)
    (build,) = [sp for sp in spans if sp.path == "startup/build"]
    assert build.attrs == {"role": "train"}
    # (the specs' ``eval_shape`` is an ``xla/trace`` of the build's own, between the two)
    assert [sp.name for sp in spans if sp.parent == build.id] == ["mesh", "xla/trace", "state"]
    (state,) = [sp for sp in spans if sp.path == "startup/build/state"]
    # each state program a child by the name the code has for it, its compile under it
    kids = [sp for sp in spans if sp.parent == state.id and not sp.name.startswith("xla/")]
    assert [sp.name for sp in kids] == ["init_fn", "opt_init"]
    for kid in kids:
        assert any(sp.parent == kid.id and sp.name == "xla/compile" for sp in spans)
    batch = random_tokens(16)
    t_train = time.perf_counter()
    for _ in range(3):
        engine.train_batch(batch)
    steps = [sp for sp in tracing.spans(t_train) if sp.path == "train/train_batch"]
    assert [sp.keep for sp in steps] == [True, False, False]
    assert any(sp.path == "train/train_batch/dispatch/xla/compile" and sp.parent != steps[0].id
               for sp in tracing.spans(t_train))
    assert "startup/build/state" in engine.telemetry_snapshot()["startup"]["phases"]


def test_state_spans_are_on_the_engines_own_tracer(tmp_path, monkeypatch):
    """``state`` and its children open on ``engine.telemetry``: the engine's JSONL
    sink sees them, and ``device_sync_spans`` makes each child wait for its tree."""
    import jax

    import deepspeed_tpu
    from simple_model import base_config, tiny_transformer

    waited = []
    block = jax.block_until_ready
    monkeypatch.setattr(tracing.jax, "block_until_ready",
                        lambda tree: waited.append(tree) or block(tree))
    cfg = base_config()
    cfg["mesh"] = {"data": -1}
    cfg["telemetry"] = {"enabled": True, "jsonl_path": str(tmp_path / "events.jsonl"),
                        "device_sync_spans": True}
    engine, _, _, _ = deepspeed_tpu.initialize(model=tiny_transformer(), config=cfg)
    assert any(tree is engine.state["params"] for tree in waited)  # ``init_fn``'s sync value
    assert any(tree is engine.state["opt"] for tree in waited)
    with open(tmp_path / "events.jsonl") as f:
        events = [json.loads(line) for line in f]
    paths = [ev["path"] for ev in events if ev.get("type") == "span"]
    assert paths[:3] == ["startup/build/state/init_fn", "startup/build/state/opt_init",
                         "startup/build/state"]


# ---------------------------------------------------------------------------
# the readers (chipbench/layer_metrics/setup_*.py)
# ---------------------------------------------------------------------------

def _sp(id, parent, path, t0, t1, **attrs):
    name = next((n for n in ("startup/build", "train/train_batch", "serve/step", "xla/trace",
                             "xla/lower", "xla/compile") if path.endswith(n)),
                path.rsplit("/", 1)[-1])
    return SimpleNamespace(id=id, parent=parent, name=name, path=path, t0=t0, t1=t1, attrs=attrs,
                           replica_id=0, keep=True)


B, E = "startup/build", "serve/step/admit/prefill/dispatch/enqueue"
# process start 500, set-up 100 s: [500, 600)
HAND = [
    _sp(1, None, B, 512.0, 520.0, role="both"),
    _sp(2, 1, B + "/draw", 513.0, 518.0),
    _sp(3, 2, B + "/draw/xla/trace", 513.0, 513.5, program="<lambda>", inner=40),
    _sp(4, 2, B + "/draw/xla/lower", 513.5, 514.0, program="jit(<lambda>)", inner=0),
    _sp(5, 2, B + "/draw/xla/compile", 514.0, 517.0, program="jit(<lambda>)", inner=0,
        cache="hit", load_s=2.5, saved_s=30.0),
    # the harness's own: an eager float32 operation, compiled anew in every process
    _sp(6, None, "xla/trace", 530.0, 530.25, program="dot_general", inner=0),
    _sp(7, None, "xla/lower", 530.25, 530.5, program="jit(dot_general)", inner=0),
    _sp(8, None, "xla/compile", 530.5, 531.5, program="jit(dot_general)", inner=0,
        cache="not_kept"),
    # a first call: the prefill program, which the cache did not hold
    _sp(9, None, "serve/step/admit/prefill", 540.0, 575.0, compiled=True),
    _sp(10, 9, E + "/xla/trace", 541.0, 545.0, program="prefill", inner=900),
    _sp(11, 9, E + "/xla/lower", 545.0, 547.0, program="jit(prefill)", inner=0),
    _sp(12, 9, E + "/xla/compile", 547.0, 572.0, program="jit(prefill)", inner=0, cache="written"),
    # after set-up (it ended in the window): not counted
    _sp(13, None, "xla/compile", 599.0, 601.0, program="jit(late)", inner=0, cache="written"),
]
READERS = {"setup_build_s": 8.0, "setup_trace_lower_s": 0.5 + 0.5 + 0.25 + 0.25 + 4.0 + 2.0,
           "setup_compile_s": 1.0 + 25.0, "setup_cache_load_s": 3.0, "setup_cache_writes": 1.0}


def _ctx(notes):
    return {"run": SimpleNamespace(t_start=500.0, note=lambda **kw: notes.append(kw)),
            "t_setup": 100.0, "serve": None, "train": None, "trace": None}


def _patch(monkeypatch, records):
    monkeypatch.setattr(tracing, "spans",
                        lambda since=float("-inf"): [sp for sp in records if sp.t0 >= since])


@pytest.mark.parametrize("name", list(READERS))
def test_setup_reader_on_a_hand_made_list(monkeypatch, name):
    reader = importlib.import_module(f"chipbench.layer_metrics.{name}")
    assert (reader.NAME, reader.LAYER) == (name, "start-up")
    _patch(monkeypatch, HAND)
    notes = []
    np.testing.assert_allclose(reader.read(_ctx(notes)), READERS[name], rtol=1e-12)
    if name != "setup_compile_s":
        assert not notes
        return
    (note,) = notes
    assert note["event"] == "setup_anatomy" and note["first_span_s"] == 12.0
    assert note["phases"] == {B: 8.0, B + "/draw": 5.0}
    assert note["first_calls"] == {"prefill": {"n": 1, "s": 35.0}}
    assert note["xla"]["startup"] == {"trace_s": 0.5, "lower_s": 0.5, "compile_s": 0.0,
                                      "load_s": 3.0, "spans": 3}
    assert note["xla"]["harness"]["compile_s"] == 1.0 and note["xla"]["calls"]["compile_s"] == 25.0
    assert note["cache"] == {"hit": {"n": 1, "s": 3.0}, "written": {"n": 1, "s": 25.0},
                             "not_kept": {"n": 1, "s": 1.0}}
    assert note["top"][0] == {"program": "prefill", "under": [E], "s": 31.0, "cache": ["written"]}
    assert note["top"][1] == {"program": "<lambda>", "under": [B + "/draw"], "s": 4.0,
                              "cache": ["hit"]}
    assert note["top"][2]["under"] == [None]
    # set-up less the union of the kept spans: the build 8, the harness's 1.5, the call 35
    np.testing.assert_allclose(note["unaccounted_s"], 100.0 - 8.0 - 1.5 - 35.0)
    assert note["dropped"] == 0


@pytest.mark.parametrize("name", list(READERS))
def test_setup_reader_without_the_spans(monkeypatch, name):
    reader = importlib.import_module(f"chipbench.layer_metrics.{name}")
    notes = []
    # a program before PR 54: steady spans in its ring, no kept ones
    _patch(monkeypatch, [_sp(1, None, "serve/step", 510.0, 510.1),
                         _sp(2, None, "train/train_batch", 520.0, 520.1)])
    assert reader.read(_ctx(notes)) is None and not notes
    _patch(monkeypatch, [])
    assert reader.read(_ctx(notes)) is None and not notes
    monkeypatch.delattr(tracing, "spans")  # the parent of PR 24
    assert reader.read(_ctx(notes)) is None and not notes
    if name != "setup_build_s":
        # a number where there is nothing of the kind (0 is one): compiles, no build span
        monkeypatch.undo()
        _patch(monkeypatch, HAND[5:8])
        assert reader.read(_ctx(notes)) == {"setup_trace_lower_s": 0.5, "setup_compile_s": 1.0,
                                            "setup_cache_load_s": 0.0,
                                            "setup_cache_writes": 0.0}[name]
