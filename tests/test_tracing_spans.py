"""The program's one span system (telemetry/tracing.py) and what reads it.

Contracts under test:
  * every ended span is one record of a bounded process-wide ring: id,
    parent, path, attributes, ``t0 <= t1`` on ``time.perf_counter()``'s clock;
    a span that raises is still recorded and leaves the nesting intact;
  * ``ServingEngine.step`` leaves one ``serve/step`` per iteration whose
    children nest as PERF.md's span table says, one ``dispatch`` + ``fetch``
    under each worker call with the four parts of a call under those
    (``operands`` / ``enqueue``, ``wait`` / ``copy``; no ``key``: the key is
    split inside the call's own program since PR 36, and nothing eager runs
    inside a call) and ``h2d`` / ``d2h`` on the call, a served stream that is
    the eager split's to the token and to the carried key,
    request-labelled prefill/chunk spans, and the latency
    histograms are fed from exactly the spans that did not compile; no span
    feeds a ``span/<path>`` histogram;
  * ``train_batch`` leaves ``train/train_batch`` with ``pre`` / ``dispatch``
    / ``post``;
  * each per-layer reader under chipbench/layer_metrics/ that reads the ring
    returns the hand-computed value on a hand-made ring and None on an empty
    one, and ``python3 -m chipbench.selftest`` holds.

Models stay tiny and reuse the session's serving config, so the compiled
programs are already in tests/.xla_cache.
"""

import importlib
import json
import os
import subprocess
import sys
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench.layer_metrics.span_ring import WORKER_CALLS
from deepspeed_tpu.telemetry import SpanTracer, tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# the ring
# ---------------------------------------------------------------------------

def _ring_fields():
    tr = SpanTracer()
    before = time.perf_counter()
    with tr.span("outer", replica_id=7, kind="x") as outer:
        with tr.span("inner") as inner:
            inner.annotate(n=3)
    after = time.perf_counter()
    got = tracing.spans(before)
    assert [sp.path for sp in got] == ["outer/inner", "outer"]  # the order they ended
    rec_in, rec_out = got
    assert rec_in is inner and rec_out is outer  # the ring holds the span itself
    assert rec_out.parent is None and rec_in.parent == rec_out.id != rec_in.id
    assert rec_out.attrs == {"kind": "x"} and rec_in.attrs == {"n": 3}
    assert rec_in.replica_id == 7  # inherited from the enclosing span
    assert before <= rec_out.t0 <= rec_in.t0 <= rec_in.t1 <= rec_out.t1 <= after
    assert rec_in.dur_s == rec_in.t1 - rec_in.t0
    assert rec_in._sync is None and rec_in._ann is None and rec_in._tracer is None
    assert rec_in.as_dict() == {"id": rec_in.id, "parent": rec_out.id, "path": "outer/inner",
                                "t0": rec_in.t0, "t1": rec_in.t1, "replica_id": 7, "n": 3}


def _ring_bounded():
    tr = SpanTracer()
    tracing.clear_spans()
    assert tracing.spans() == []
    for i in range(tracing.RING_CAPACITY + 5):
        with tr.span("s", i=i):
            pass
    got = tracing.spans()
    assert len(got) == tracing.RING_CAPACITY == 65_536
    assert got[0].attrs["i"] == 5 and got[-1].attrs["i"] == tracing.RING_CAPACITY + 4
    mid = got[1000].t0
    assert len(tracing.spans(mid)) == tracing.RING_CAPACITY - 1000  # t0 >= since
    tracing.clear_spans()
    assert tracing.spans() == []


def _ring_survives_raise():
    tr = SpanTracer()
    since = time.perf_counter()
    with pytest.raises(ValueError):
        with tr.span("outer"):
            with tr.span("boom"):
                raise ValueError("x")
    with tr.span("after"):
        pass
    got = {sp.path: sp for sp in tracing.spans(since)}
    assert set(got) == {"outer/boom", "outer", "after"}  # the stack unwound
    assert got["outer/boom"].attrs["error"] == "ValueError"
    assert got["after"].parent is None and "error" not in got["after"].attrs
    # a span that raised says so in the ring, and so does what it unwound through
    assert [sp.path for sp in tracing.spans(since) if "error" not in sp.attrs] == ["after"]


def _ring_parent_is_per_thread():
    tr = SpanTracer()
    since = time.perf_counter()
    seen = {}

    def other():
        with tr.span("other") as sp:
            seen["other"] = sp

    with tr.span("main"):
        t = threading.Thread(target=other)
        t.start()
        t.join()
    assert seen["other"].parent is None and seen["other"].path == "other"
    assert {sp.path for sp in tracing.spans(since)} == {"main", "other"}


def _ring_threads_lose_nothing():
    """More writers than cores, one reader, a short switch interval: every
    span is recorded once, under an id of its own."""
    tr = SpanTracer()
    workers, each = 2 * (os.cpu_count() or 4), 300
    since = time.perf_counter()
    stop = threading.Event()

    def write(k):
        for i in range(each):
            with tr.span("w", k=k, i=i):
                with tr.span("x"):
                    pass

    def read():
        while not stop.is_set():
            tracing.spans(since)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        reader = threading.Thread(target=read)
        threads = [threading.Thread(target=write, args=(k,)) for k in range(workers)]
        reader.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        stop.set()
        reader.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not reader.is_alive() and not any(t.is_alive() for t in threads)
    got = tracing.spans(since)
    outer = [sp for sp in got if sp.path == "w"]
    assert len(got) == 2 * workers * each and len({sp.id for sp in got}) == len(got)
    assert sorted((sp.attrs["k"], sp.attrs["i"]) for sp in outer) == [
        (k, i) for k in range(workers) for i in range(each)]
    by_id = {sp.id: sp for sp in got}
    assert all(by_id[sp.parent].path == "w" for sp in got if sp.path == "w/x")


@pytest.mark.parametrize("case", [_ring_fields, _ring_bounded, _ring_survives_raise,
                                  _ring_parent_is_per_thread,
                                  _ring_threads_lose_nothing],
                         ids=lambda f: f.__name__.lstrip("_"))
def test_ring(case):
    case()


# ---------------------------------------------------------------------------
# spans where the work happens: serving
# ---------------------------------------------------------------------------

SERVING = {
    "plain": {},
    "chunked": {"chunked_prefill": {"enabled": True, "chunk_size": 16}},
    "prefix": {"prefix_cache": {"enabled": True, "n_slots": 8, "block": 8}},
    "speculation": {"speculation": {"enabled": True, "depth": 4}},
}

# the name of the span each span may sit under (None: the root)
PARENTS = {
    "serve/step": {None},
    "sweep": {"serve/step"}, "admit": {"serve/step"}, "chunks": {"serve/step"},
    "draft": {"serve/step"}, "emit": {"serve/step"},
    "decode": {"serve/step"}, "verify": {"serve/step"},
    # the fetch of a decode step that no later step was enqueued behind (PR 60: a ``decode``
    # span is the DISPATCH of one step and the fetch of the step before)
    "collect": {"serve/step"},
    "prefill": {"admit"}, "chunk": {"chunks", "admit"},
    "dispatch": set(WORKER_CALLS), "fetch": {*WORKER_CALLS, "collect"},
    "operands": {"dispatch"}, "enqueue": {"dispatch"},
    "merge": {"dispatch"},  # a decode step's host tokens into the carried ones
    "wait": {"fetch"}, "copy": {"fetch"},
    # a program's first call traces, lowers and compiles (or loads) inside enqueue;
    # the prefix pool's copies are called from admit and emit, under no span of their own
    **{f"xla/{kind}": {"enqueue", "admit", "emit"} for kind in ("trace", "lower", "compile")},
}


def _serve(engine, mode, **ask):
    from deepspeed_tpu.inference import ServingEngine
    from deepspeed_tpu.inference.serving import Request

    srv = ServingEngine(engine, {"request_trace": {"enabled": True}}, n_slots=2,
                        max_seq_len=128, replica_id=f"t-{mode}", **SERVING[mode])
    rng = np.random.default_rng(3)
    lens = (5, 40, 9, 23, 40)
    prompts = [rng.integers(0, 97, size=n).astype(np.int32) for n in lens]
    if mode == "prefix":
        prompts[4] = prompts[1].copy()  # a whole-prompt repeat: a prefix hit
    if mode == "speculation":
        prompts = [np.tile(p[:4], 10)[:len(p)] for p in prompts]  # n-gram drafts match
    reqs = [Request(uid=100 + i, prompt=p, max_new_tokens=3 + i, **ask)
            for i, p in enumerate(prompts)]
    since = time.perf_counter()
    results = srv.serve(reqs)
    assert all(r.ok for r in results.values())
    mine = [sp for sp in tracing.spans(since) if sp.replica_id == srv.replica_id]
    return srv, reqs, mine


@pytest.mark.parametrize("mode", list(SERVING))
def test_serving_spans(tiny_serving_engine, mode):
    srv, reqs, spans = _serve(tiny_serving_engine, mode)
    by_id = {sp.id: sp for sp in spans}
    kids = {}
    for sp in spans:
        kids.setdefault(sp.parent, []).append(sp)
    hist = srv.telemetry.registry.snapshot()["histograms"]
    counters = srv.telemetry.registry.snapshot()["counters"]
    assert hist and not any(name.startswith("span/") for name in hist)  # the ring is the record

    # every scheduler iteration is one serve/step, numbered, and everything nests
    # as the table says, on one clock, inside its parent
    steps = [sp for sp in spans if sp.path == "serve/step"]
    assert [sp.attrs["step"] for sp in steps] == list(range(1, len(steps) + 1))
    assert all({"n_active", "queue_len"} <= set(sp.attrs) for sp in steps)
    for sp in spans:
        up = by_id.get(sp.parent)
        assert (up.name if up else None) in PARENTS[sp.name], sp.path
        assert sp.t0 <= sp.t1
        if up is not None:
            assert sp.path == f"{up.path}/{sp.name}" and up.t0 <= sp.t0 and sp.t1 <= up.t1
    fetches = lambda sp: any(c.name == "fetch" for c in kids.get(sp.id, []))
    for st in steps:
        below = kids.get(st.id, [])
        names = [c.name for c in below]
        # a step in flight is dealt with first (the next enqueued and it fetched, or it
        # collected) and emitted; then the sweep and the admissions; a step is enqueued last
        at = names.index("sweep")
        assert names[at:at + 2] == ["sweep", "admit"] and names.count("sweep") == 1
        assert names[:at] in ([], ["decode", "emit"], ["collect", "emit"])
        assert names.count("decode") + names.count("verify") <= 1
        # host bookkeeping follows every fetch of a device step, and only that
        assert names.count("emit") == sum(fetches(c) for c in below)
        assert all(b.name == "emit" for a, b in zip(below, below[1:]) if fetches(a))

    # a call that compiled is kept with its xla/* spans (they outlive the ring);
    # no steady span is
    xla = [sp for sp in spans if sp.name.startswith("xla/")]
    assert {sp.id for sp in spans if sp.keep} >= {sp.id for sp in xla}
    for sp in spans:
        if sp.name in WORKER_CALLS:
            assert sp.keep == sp.attrs["compiled"], sp.path
        else:  # a kept call is kept alone: nothing it opened, before or after it knew
            assert not sp.keep or sp in xla, sp.path

    # one dispatch + one fetch under each worker call (a chunk left asynchronous
    # has no fetch), in that order
    calls = [sp for sp in spans if sp.name in WORKER_CALLS]
    for sp in calls:
        under = [c.name for c in kids.get(sp.id, [])]
        want = ["dispatch"] if sp.attrs.get("fetch") is False else ["dispatch", "fetch"]
        if sp.name == "decode" and sp.attrs["d2h"] == 0:  # nothing was unfetched behind it
            want = ["dispatch"]
        assert under == want, (sp.path, under)
        assert isinstance(sp.attrs["compiled"], bool)
    # every decode step is fetched once: by the decode call after it, or collected
    collects = [sp for sp in spans if sp.name == "collect"]
    assert all([c.name for c in kids[sp.id]] == ["fetch"] for sp in collects)

    # the latency histograms hold exactly the calls that did not compile
    def timed(kind):
        return [sp for sp in calls if sp.name == kind and not sp.attrs["compiled"]
                and sp.attrs.get("fetch") is not False]

    for kind, name in (("decode", "serving/decode_step_sec"), ("prefill", "serving/prefill_sec"),
                       ("verify", "serving/verify_step_sec"),
                       ("chunk", "serving/chunk_prefill_sec")):
        assert hist.get(name, {"count": 0})["count"] == len(timed(kind)), name
        if timed(kind) and kind != "decode":
            np.testing.assert_allclose(hist[name]["sum"], sum(sp.dur_s for sp in timed(kind)))
    decodes = [sp for sp in calls if sp.name == "decode"]
    assert len(decodes) == counters.get("serving/decode_steps", 0)
    # a decode step's datum runs from when the device could begin it (its enqueue, or the
    # fetch before it) to its own fetch, in a LATER span: no more than from its span's start
    # to the end of the span that fetched it
    fetched_by = sorted([sp for sp in decodes if sp.attrs["d2h"]] + collects, key=lambda sp: sp.t0)
    assert len(fetched_by) == len(decodes)
    if timed("decode"):
        assert 0 < hist["serving/decode_step_sec"]["sum"] <= sum(
            b.t1 - a.t0 for a, b in zip(decodes, fetched_by) if not a.attrs["compiled"])
    assert counters.get("serving/decode_steps_ahead", 0) == sum(sp.attrs["ahead"] for sp in decodes)
    assert (mode == "speculation") == (not any(sp.attrs["ahead"] for sp in decodes))
    assert all(1 <= sp.attrs["n_active"] <= 2 for sp in decodes)
    emitted = sum(sp.attrs["tokens"] for sp in spans if sp.name == "emit")
    finished = sum(sp.attrs["finished"] for sp in spans if sp.name == "emit")
    # the first token of a request comes from its prefill, the rest from emit
    assert emitted == sum(r.max_new_tokens - 1 for r in reqs) and finished == len(reqs)
    assert sum(sp.attrs["admitted"] for sp in spans if sp.name == "admit") == len(reqs)

    # a request's spans carry its uid and meet the request trace on one clock
    prompt_len = {r.uid: len(r.prompt) for r in reqs}
    prefills = [sp for sp in calls if sp.name == "prefill"]
    chunks = [sp for sp in calls if sp.name == "chunk"]
    for sp in prefills:
        n = prompt_len[sp.attrs["uid"]]
        assert sp.attrs["true_len"] == n and sp.attrs["bucket"] == srv._bucket_len(n)
        assert sp.attrs["slot"] in (0, 1)
    for sp in chunks:
        assert sp.attrs["uid"] in prompt_len and 1 <= sp.attrs["live"] <= sp.attrs["width"]
    snap = srv.telemetry_snapshot()
    assert snap["epoch"] == srv._epoch and snap["replica_id"] == srv.replica_id
    assert [d["id"] for d in snap["spans"]] == [sp.id for sp in spans][-len(snap["spans"]):]
    json.dumps(snap["spans"])  # plain data
    events = {(e["uid"], e["event"]): snap["epoch"] + e["t"] for e in snap["request_trace"]}
    last_call = {}
    for sp in prefills + chunks:
        last_call[sp.attrs["uid"]] = sp
    assert set(last_call) == set(prompt_len)
    for uid, sp in last_call.items():
        first_call = min((c for c in prefills + chunks if c.attrs["uid"] == uid),
                         key=lambda c: c.t0)
        assert events[uid, "admitted"] <= first_call.t0
        assert sp.t1 <= events[uid, "first_token"]

    if mode == "plain":
        assert {sp.name for sp in spans} - {sp.name for sp in xla} == {
            "serve/step", "sweep", "admit", "prefill", "decode", "dispatch", "merge", "operands",
            "enqueue", "fetch", "wait", "copy", "emit", "collect"}
        assert len(prefills) == len(reqs) and not chunks
    if mode == "chunked":
        assert not prefills and {by_id[sp.parent].name for sp in chunks} == {"chunks"}
        assert any(sp.attrs["fetch"] is False for sp in chunks)
        assert sum(sp.attrs["live"] for sp in chunks) == sum(prompt_len.values())
    if mode == "prefix":
        # the repeated prompt's suffix runs through the chunk path inside admit
        assert {by_id[sp.parent].name for sp in chunks} == {"admit"}
        assert {sp.attrs["uid"] for sp in chunks} == {104}
    if mode == "speculation":
        verifies = [sp for sp in calls if sp.name == "verify"]
        drafts = [sp for sp in spans if sp.name == "draft"]
        assert verifies and all(sp.attrs["depth"] in (1, 2, 4) for sp in verifies)
        assert len(drafts) == len(decodes) + len(verifies)
        assert sum(sp.attrs["slots"] > 0 for sp in drafts) == len(verifies)


@pytest.mark.parametrize("mode", ["plain", "chunked"])
def test_sampler_attribute_is_the_rule_the_program_branches_on(tiny_serving_engine, mode):
    """``sampler`` on a decode / prefill / chunk span names the form the
    call's program took: the arrays each call handed its program, given to
    ``sampler_form`` TRACED (as ``sample_logits_vector`` evaluates it), name
    the same form. The traffic makes all three occur: greedy requests, one
    that draws, one that filters, one whose filters sit on a greedy row."""
    import jax

    from deepspeed_tpu.inference import ServingEngine
    from deepspeed_tpu.inference.sampling import SAMPLER_FORMS, sampler_form
    from deepspeed_tpu.inference.serving import Request

    srv = ServingEngine(tiny_serving_engine, n_slots=2, max_seq_len=128,
                        replica_id=f"sampler-{mode}", **SERVING[mode])
    traced = jax.jit(sampler_form, static_argnums=3)
    handed = {"decode": [], "prefill": [], "chunk": []}
    for kind in handed:
        call = getattr(srv.worker, kind)

        def recording(*args, _call=call, _kind=kind, **kw):
            # temperature, top_k, top_p close every signature (copied: decode's are live state)
            handed[_kind].append(tuple(np.array(a, dt, ndmin=1) for a, dt in
                                       zip(args[-3:], (np.float32, np.int32, np.float32))))
            return _call(*args, **kw)

        setattr(srv.worker, kind, recording)
    rng = np.random.default_rng(5)
    asks = [dict(), dict(temperature=0.9), dict(temperature=0.7, top_k=5, top_p=0.9),
            dict(top_k=3, top_p=0.5), dict(temperature=1.1, top_p=0.8), dict()]
    reqs = [Request(uid=i, prompt=rng.integers(0, 97, size=5 + 6 * i).astype(np.int32),
                    max_new_tokens=3 + 2 * (i % 3), **ask) for i, ask in enumerate(asks)]
    since = time.perf_counter()
    assert all(r.ok for r in srv.serve(reqs).values())
    spans = [sp for sp in tracing.spans(since) if sp.replica_id == srv.replica_id]
    seen = {}
    for kind, rows in handed.items():
        got = [sp.attrs["sampler"] for sp in spans if sp.name == kind]
        want = [SAMPLER_FORMS[int(traced(*row, 97))] for row in rows]
        assert got == want, kind
        seen[kind] = set(got)
    assert seen["decode"] == set(SAMPLER_FORMS)
    assert seen["chunk" if mode == "chunked" else "prefill"] == set(SAMPLER_FORMS)


def test_worker_call_outside_step_has_a_short_path(tiny_serving_engine):
    from deepspeed_tpu.inference import ServingEngine

    srv = ServingEngine(tiny_serving_engine, n_slots=2, max_seq_len=128,
                        speculation={"enabled": True, "depth": 2})
    since = time.perf_counter()
    srv.warm_verify()
    got = tracing.spans(since)
    # the same tree as every other call: no kind of call has a ``key`` span
    assert {sp.path for sp in got if not sp.name.startswith("xla/")} == {
        "verify", "verify/dispatch", "verify/dispatch/operands", "verify/dispatch/enqueue",
        "verify/fetch", "verify/fetch/wait", "verify/fetch/copy"}
    assert all(sp.attrs["warm"] for sp in got if sp.path == "verify")
    hist = srv.telemetry.registry.snapshot()["histograms"]
    assert "serving/verify_step_sec" not in hist  # a warm call is no latency datum


# ---------------------------------------------------------------------------
# the anatomy of a worker call (SlotWorker._run): four parts, two counters
# ---------------------------------------------------------------------------

# call kind -> (serving mode that makes it, request keywords, host operands, device operands,
# arrays fetched): what the call hands its program behind params and cache, by where it lives
# when handed over (the one device operand is the carried key: no upload, so not in ``h2d``;
# a decode step's tokens are carried too since PR 60, and where the host has some of them a
# merge program takes two uploads of its own first: ``merged``, ``h2d`` 8)
ANATOMY = {
    "decode": ("plain", {}, 6, 2, 2),
    "prefill": ("plain", {}, 6, 1, 2),  # prompt, slot, true_len ride the program's own upload
    "chunk": ("chunked", {}, 7, 1, 2),
    "verify": ("speculation", {}, 4, 0, 1),  # greedy: no key, no sampler rows, ONE packed array
    "verify-sampled": ("speculation", {"temperature": 0.8, "top_k": 1}, 7, 1, 4),  # the argmax, drawn
}


class _Recorder:
    """A watched program that notes the operands of each call behind params and
    cache, and is otherwise the program."""

    def __init__(self, prog, seen):
        self._prog, self._seen = prog, seen

    def __call__(self, params, cache, *operands):
        self._seen.append(operands)
        return self._prog(params, cache, *operands)

    def __getattr__(self, name):
        return getattr(self._prog, name)


def _by_parent(spans):
    kids = {}
    for sp in spans:
        kids.setdefault(sp.parent, []).append(sp)
    return {up: sorted(below, key=lambda sp: sp.t0) for up, below in kids.items()}


@pytest.mark.parametrize("kind", list(ANATOMY))
def test_worker_call_has_four_parts_in_order(tiny_serving_engine, kind):
    """``operands`` / ``enqueue`` under ``dispatch`` and ``wait`` / ``copy``
    under ``fetch``: in that order, disjoint, inside their parent, and covering
    it but for the program look-up between ``operands`` and ``enqueue``. No kind
    of call has a ``key`` span, and the ring holds none; a chunk left
    asynchronous has no ``fetch`` and so neither of its parts."""
    mode, ask, *_ = ANATOMY[kind]
    _, _, spans = _serve(tiny_serving_engine, mode, **ask)
    kids = _by_parent(spans)
    name = kind.split("-")[0]
    calls = [sp for sp in spans if sp.name == name]
    assert calls
    uncovered = []
    for call in calls:
        dispatch, *rest = kids[call.id]
        parts = kids[dispatch.id]
        if call.attrs.get("merged"):  # a decode step's host tokens go in first
            assert parts[0].name == "merge" and parts[0].path == f"{dispatch.path}/merge"
            assert dispatch.t0 <= parts[0].t0 and parts[0].t1 <= parts[1].t0
            parts = parts[1:]
        assert [sp.name for sp in parts] == ["operands", "enqueue"], call.path
        groups = [(dispatch, parts)]
        if call.attrs.get("fetch") is False or (name == "decode" and not rest):
            # a chunk left asynchronous; a decode step with no step unfetched before it
            assert not rest and call.attrs["d2h"] == 0
        else:
            (fetch,) = rest
            assert fetch.name == "fetch" and dispatch.t1 <= fetch.t0
            assert [sp.name for sp in kids[fetch.id]] == ["wait", "copy"]
            groups.append((fetch, kids[fetch.id]))
        for up, below in groups:
            assert up.t0 <= below[0].t0 and below[-1].t1 <= up.t1
            assert all(a.t1 <= b.t0 for a, b in zip(below, below[1:]))
            assert all(sp.path == f"{up.path}/{sp.name}" for sp in below)
            lookup = below[1].t0 - below[0].t1 if up is dispatch else 0.0
            merge = below[0].t0 - up.t0 if up is dispatch and call.attrs.get("merged") else 0.0
            uncovered.append(up.dur_s - sum(sp.dur_s for sp in below) - lookup - merge)
    # what no part covers is a few Python statements: microseconds, on any machine
    assert np.median(uncovered) < 2e-4, np.median(uncovered)
    assert not any(sp.name == "key" for sp in spans)
    if kind == "chunk":
        assert any(c.attrs["fetch"] is False for c in calls)
    if kind == "decode":  # its fetch is the step BEFORE: all but the first have one
        assert [bool(c.attrs["d2h"]) for c in calls] == [False] + [True] * (len(calls) - 1)
        assert any(c.attrs.get("merged") for c in calls) and not all(
            c.attrs.get("merged") for c in calls)


@pytest.mark.parametrize("kind", list(ANATOMY))
def test_h2d_and_d2h_are_what_the_call_hands_over_and_fetches(tiny_serving_engine, monkeypatch,
                                                              kind):
    """``h2d`` = the host arrays given to the program (the carried key is a device
    operand, and no upload, as are a decode step's carried tokens; the two uploads of
    a decode step's token merge count with the step); ``d2h`` = the arrays
    ``device_get`` is handed: counted here from outside, at the program's call and
    at ``jax.device_get``. A decode call fetches the step BEFORE it (none: 0), and
    the last step's fetch is a ``collect`` span's."""
    import jax

    from deepspeed_tpu.inference import ServingEngine
    from deepspeed_tpu.inference.serving import Request

    mode, ask, n_host, n_device, n_fetched = ANATOMY[kind]
    name = kind.split("-")[0]
    srv = ServingEngine(tiny_serving_engine, n_slots=2, max_seq_len=128,
                        replica_id=f"count-{kind}", **SERVING[mode])
    handed, fetched = [], []
    getter = f"_{name}_prog"
    get = getattr(srv.worker, getter)
    monkeypatch.setattr(srv.worker, getter, lambda *a: _Recorder(get(*a), handed))
    device_get = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda out: (fetched.append(len(out)), device_get(out))[1])
    rng = np.random.default_rng(11)
    prompts = [np.tile(rng.integers(0, 97, size=4), 10)[:n].astype(np.int32) for n in (9, 40)]
    reqs = [Request(uid=i, prompt=p, max_new_tokens=6, **ask) for i, p in enumerate(prompts)]
    since = time.perf_counter()
    assert all(r.ok for r in srv.serve(reqs).values())
    monkeypatch.undo()
    spans = [sp for sp in tracing.spans(since) if sp.replica_id == srv.replica_id]
    calls = [sp for sp in spans if sp.name == name
             and (name != "verify" or (sp.attrs["h2d"] == 4) == (kind == "verify"))]
    others = [sp for sp in spans if sp.name in WORKER_CALLS and sp.name != name]
    assert calls and len(handed) == sum(sp.name == name for sp in spans)
    mine = [ops for ops in handed if len(ops) == n_host + n_device]
    assert len(mine) == len(calls)
    for ops in mine:
        assert sum(isinstance(x, jax.Array) for x in ops) == n_device
        assert sum(isinstance(x, (np.ndarray, np.generic)) for x in ops) == n_host
    assert {sp.attrs["h2d"] - 2 * bool(sp.attrs.get("merged")) for sp in calls} == {n_host}
    fetching = [sp for sp in calls if sp.attrs.get("fetch") is not False and sp.attrs["d2h"]]
    assert {sp.attrs["d2h"] for sp in fetching} == {n_fetched}
    if name == "decode":
        assert [sp for sp in calls if not sp.attrs["d2h"]] == calls[:1]
    # every fetch of the run is some call's: none is made beside the scaffold
    others += [sp for sp in spans if sp.name == "collect"]
    assert sorted(fetched) == sorted(sp.attrs["d2h"] for sp in calls + others
                                     if sp.attrs.get("fetch") is not False and sp.attrs["d2h"])


@pytest.mark.parametrize("kind", list(ANATOMY))
def test_worker_call_goes_into_the_runtime_once(tiny_serving_engine, monkeypatch, kind):
    """Between the edges of a call span nothing eager runs: no key split, no
    ``jnp`` conversion, no upload of its own. The call's one trip into the
    runtime is its own program's. Checked on warm programs (a first call
    traces, and the traced body splits the key), with the eager entry points
    made to raise while ``_run`` (a decode step: ``decode``, whose token merge is
    a program of its own and no eager operation) is on the stack."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference import ServingEngine
    from deepspeed_tpu.inference.serving import Request, SlotWorker

    mode, ask, *_ = ANATOMY[kind]
    name = kind.split("-")[0]
    srv = ServingEngine(tiny_serving_engine, n_slots=2, max_seq_len=128,
                        replica_id=f"once-{kind}", **SERVING[mode])
    rng = np.random.default_rng(13)
    prompts = [np.tile(rng.integers(0, 97, size=4), 10)[:n].astype(np.int32) for n in (9, 40)]

    def served(first_uid):
        reqs = [Request(uid=first_uid + i, prompt=p, max_new_tokens=6, **ask)
                for i, p in enumerate(prompts)]
        results = srv.serve(reqs)
        return [list(map(int, results[r.uid].tokens)) for r in reqs if results[r.uid].ok]

    warm = served(0)  # every program of the run compiles here
    inside, eager = [], []
    entry = "decode" if name == "decode" else "_run"
    run = getattr(SlotWorker, entry)

    def watched(self, *args, **kwargs):
        inside.append(name)
        try:
            return run(self, *args, **kwargs)
        finally:
            inside.pop()

    def refuse(what, fn):
        def guarded(*args, **kwargs):
            if inside:
                eager.append((inside[-1], what))
                raise AssertionError(f"{what} inside a {inside[-1]} call")
            return fn(*args, **kwargs)
        return guarded

    monkeypatch.setattr(SlotWorker, entry, watched)
    for owner, attr in ((jax.random, "split"), (jax, "device_put"), (jnp, "asarray"),
                        (jnp, "array"), (type(jnp.int32), "__call__")):
        monkeypatch.setattr(owner, attr, refuse(f"{owner.__name__}.{attr}", getattr(owner, attr)))
    since = time.perf_counter()
    again = served(10)
    monkeypatch.undo()
    assert not eager and len(again) == len(prompts)
    calls = [sp for sp in tracing.spans(since) if sp.replica_id == srv.replica_id
             and sp.name == name]
    assert calls and not any(sp.attrs["compiled"] for sp in calls)
    if not ask:
        assert again == warm  # greedy rows: the same prompts give the same tokens


def test_a_routed_model_fetches_one_array_more():
    """The expert load comes back in the fetch that brings the tokens: ``d2h``
    3 where a dense model's call has 2; ``h2d`` is the dense model's. A decode call's
    fetch, and so the load on its span, is the step's BEFORE it."""
    from chipbench.references import program_of
    from deepspeed_tpu.inference.serving import Request
    from deepspeed_tpu.launcher.serving_worker import build_serving_engine

    with open(os.path.join(ROOT, "chipbench", "configs", "olmoe-1b-7b-L4.json")) as f:
        program = program_of(json.load(f), "rehearse_program")
    srv = build_serving_engine({"model": {**program, "dtype": "float32"}, "engine_dtype": "fp32",
                                "serving": {"n_slots": 4, "max_seq_len": 256, "seed": 0}})
    rng = np.random.default_rng(2)
    reqs = [Request(uid=i, prompt=rng.integers(0, program["vocab_size"], size=n).astype(np.int32),
                    max_new_tokens=4) for i, n in enumerate((40, 77))]
    since = time.perf_counter()
    assert all(r.ok for r in srv.serve(reqs).values())
    calls = {sp.name: sp for sp in tracing.spans(since) if sp.name in ("prefill", "decode")}
    assert not calls["decode"].attrs.get("merged")  # the last one: no slot changed hands
    assert {k: (sp.attrs["h2d"], sp.attrs["d2h"]) for k, sp in calls.items()} == {
        "prefill": (6, 3), "decode": (6, 3)}
    assert "expert_load_max_over_mean" in calls["decode"].attrs


STREAM_SEED = 5


def _stream_requests():
    from deepspeed_tpu.inference.serving import Request

    rng = np.random.default_rng(9)
    return [Request(uid=i, prompt=np.tile(rng.integers(0, 97, size=4), 8)[:n].astype(np.int32),
                    max_new_tokens=8, temperature=0.9 if i % 2 else 0.0,
                    top_p=0.8 if i == 3 else 1.0) for i, n in enumerate((7, 30, 12, 21))]


def test_wait_then_copy_changes_no_token(tiny_serving_engine, monkeypatch):
    """All outputs of one program become ready together, so waiting for them
    and then copying them fetches what one ``device_get`` fetched: a sampled
    stream is the same, token for token, with the wait taken out again (the
    form the calls had before their ``fetch`` was split)."""
    import jax

    from deepspeed_tpu.inference import ServingEngine

    def stream(mode):
        srv = ServingEngine(tiny_serving_engine, n_slots=2, max_seq_len=128, seed=STREAM_SEED,
                            **SERVING[mode])
        reqs = _stream_requests()
        results = srv.serve(reqs)
        assert all(r.ok for r in results.values())
        return [list(map(int, results[r.uid].tokens)) for r in reqs]

    for mode in SERVING:
        with_wait = stream(mode)
        with monkeypatch.context() as m:
            m.setattr(jax, "block_until_ready", lambda out: out)
            assert stream(mode) == with_wait, mode


# ---------------------------------------------------------------------------
# the key: split inside the programs, carried on the device (PR 36)
# ---------------------------------------------------------------------------

def _eager_split_form(worker, monkeypatch):
    """Make ``worker`` draw as it did before PR 36, written out: the HOST holds
    the key and splits it eagerly before every call that takes one, and the
    program draws with the half it is handed. The programs are this tree's, traced
    with their inner split turned into "carry and draw with the operand"; the
    chain of keys is kept here, by hand, from the same seed."""
    import jax
    import jax.numpy as jnp

    split = jax.random.split
    chain = [jax.random.PRNGKey(STREAM_SEED)]
    monkeypatch.setattr(jax.random, "split", lambda key, num=2: (
        jnp.stack([key, key]) if num == 2 else split(key, num)))
    dispatch = worker._dispatch  # every call's first half, a decode step's too

    def eager(*args, key=True, **kwargs):
        if key:
            chain[0], k = split(chain[0])
            worker._rng = jax.device_put(k, worker._key_sharding())
        return dispatch(*args, key=key, **kwargs)

    monkeypatch.setattr(worker, "_dispatch", eager)
    return chain


@pytest.mark.parametrize("mode", list(SERVING))
def test_stream_and_carried_key_are_the_eager_splits(tiny_serving_engine, monkeypatch, mode):
    """Greedy and sampled rows mixed: the tokens are those of a stream whose
    keys the host draws by hand with the eager split from the same seed; the
    key the worker carries after *k* keyed calls is *k* eager splits of
    ``PRNGKey(seed)``; and every program compiled once, warm-up included."""
    import jax

    from deepspeed_tpu.inference import ServingEngine

    def engine(tag):
        return ServingEngine(tiny_serving_engine, n_slots=2, max_seq_len=128, seed=STREAM_SEED,
                             replica_id=f"key-{tag}-{mode}", **SERVING[mode])

    def stream(srv):
        if mode == "speculation":
            srv.warm_verify()  # greedy family: takes no key, moves none
        reqs = _stream_requests()
        since = time.perf_counter()
        results = srv.serve(reqs)
        assert all(r.ok for r in results.values())
        keyed = [sp for sp in tracing.spans(since) if sp.replica_id == srv.replica_id
                 and sp.name in WORKER_CALLS and not (sp.name == "verify" and sp.attrs["h2d"] == 4)]
        return [list(map(int, results[r.uid].tokens)) for r in reqs], len(keyed)

    srv = engine("inside")
    tokens, keyed = stream(srv)
    key = jax.random.PRNGKey(STREAM_SEED)
    for _ in range(keyed):
        key, _ = jax.random.split(key)
    assert keyed and np.array_equal(np.asarray(srv.worker._rng), np.asarray(key))
    counts = srv.compile_counts()
    assert counts["decode"] == 1
    for family in ("prefill", "chunk_prefill"):
        assert set(counts.get(family, {}).values()) <= {1}, (family, counts)
    # a depth's count folds its two families (all-greedy, mixed): one program each
    assert set(counts.get("verify", {}).values()) <= {1, 2}, counts
    assert counts["chunk_prefill" if mode == "chunked" else "prefill"]

    with monkeypatch.context() as m:
        by_hand = engine("by-hand")
        chain = _eager_split_form(by_hand.worker, m)
        assert stream(by_hand) == (tokens, keyed), mode
        assert np.array_equal(np.asarray(chain[0]), np.asarray(key))


def test_a_chunk_left_unfetched_advances_the_key_once(tiny_serving_engine):
    """``fetch=False`` leaves the call asynchronous and the key a future like the
    cache: it still moves by exactly one split, and a greedy ``verify`` by none."""
    import jax

    from deepspeed_tpu.inference import ServingEngine

    srv = ServingEngine(tiny_serving_engine, n_slots=2, max_seq_len=128, seed=STREAM_SEED,
                        chunked_prefill={"enabled": True, "chunk_size": 16},
                        speculation={"enabled": True, "depth": 2})
    w = srv.worker
    want = jax.random.PRNGKey(STREAM_SEED)
    assert np.array_equal(np.asarray(w._rng), np.asarray(want))
    toks = np.arange(16, dtype=np.int32)[None, :]
    assert w.chunk(16, toks, 0, 0, 16, 0.7, 0, 1.0, fetch=False) is None
    want, _ = jax.random.split(want)
    assert np.array_equal(np.asarray(w._rng), np.asarray(want))
    tok, bad = w.chunk(16, toks, 0, 16, 5, 0.7, 0, 1.0, fetch=True)
    want, k = jax.random.split(want)
    assert np.array_equal(np.asarray(w._rng), np.asarray(want)) and not bad
    srv.warm_verify()  # the greedy family: no key operand, the key stays
    assert np.array_equal(np.asarray(w._rng), np.asarray(want))
    assert w._rng.committed and w._rng.sharding == w._key_sharding()


# ---------------------------------------------------------------------------
# spans where the work happens: training
# ---------------------------------------------------------------------------

def test_train_batch_spans():
    import deepspeed_tpu
    from simple_model import base_config, random_tokens, tiny_transformer

    cfg = base_config()
    cfg["mesh"] = {"data": -1}
    engine, _, _, _ = deepspeed_tpu.initialize(model=tiny_transformer(), config=cfg)
    batch = random_tokens(16)
    since = time.perf_counter()
    for _ in range(3):
        engine.train_batch(batch)
    spans = tracing.spans(since)
    steps = [sp for sp in spans if sp.path == "train/train_batch"]
    assert [sp.attrs["step"] for sp in steps] == [1, 2, 3] and all(s.parent is None for s in steps)
    for st in steps:
        kids = sorted((sp for sp in spans if sp.parent == st.id), key=lambda sp: sp.t0)
        assert [k.path for k in kids] == [f"train/train_batch/{n}"
                                          for n in ("pre", "dispatch", "post")]
        assert st.t0 <= kids[0].t0 and kids[-1].t1 <= st.t1
        assert all(a.t1 <= b.t0 for a, b in zip(kids, kids[1:]))
    hist = engine.telemetry.registry.snapshot()["histograms"]
    assert hist["train/step_time_sec"]["count"] == 3
    np.testing.assert_allclose(hist["train/step_time_sec"]["sum"], sum(s.dur_s for s in steps))
    assert sum(sp.path == "train/train_batch/dispatch" for sp in spans) == 3
    assert not any(name.startswith("span/") for name in hist)


# ---------------------------------------------------------------------------
# the per-layer readers of the ring (chipbench/layer_metrics/)
# ---------------------------------------------------------------------------

def _sp(id, parent, path, t0, t1, **attrs):
    return SimpleNamespace(id=id, parent=parent, name=path.rsplit("/", 1)[-1] if path not in (
        "serve/step", "train/train_batch") else path, path=path, t0=t0, t1=t1, attrs=attrs,
        replica_id=0)


def _call(id, parent, path, t0, t_disp, t1, **attrs):
    """A worker call with its dispatch [t0, t_disp] and fetch [t_disp, t1]."""
    return [_sp(id, parent, path, t0, t1, compiled=False, **attrs),
            _sp(id + 1, id, path + "/dispatch", t0, t_disp),
            _sp(id + 2, id, path + "/fetch", t_disp, t1)]


E = 1000.0  # the serving loop's epoch; its window is [10, 20) on the loop's clock
S = "serve/step"
HAND_SERVE = [
    # a warm-up decode outside step(), before the window: its fetch ends 5 ms
    # before the window opens, so 4 ms of the gap to the next call lie inside
    *_call(90, None, "decode", E + 9.940, E + 9.942, E + 9.995, n_active=8),
    # step A, 60 ms: decode 54 ms (dispatch 2); self 6
    _sp(1, None, S, E + 10.000, E + 10.060, step=1),
    _sp(2, 1, S + "/sweep", E + 10.000, E + 10.001),
    _sp(3, 1, S + "/admit", E + 10.001, E + 10.002, admitted=0),
    *_call(4, 1, S + "/decode", E + 10.002, E + 10.004, E + 10.056, n_active=8),
    _sp(7, 1, S + "/emit", E + 10.056, E + 10.058, tokens=8, finished=1),
    # step B, 168 ms: prefill 102 ms (dispatch 3) under admit, decode 52 ms
    # (dispatch 3); self 14
    _sp(8, None, S, E + 10.062, E + 10.230, step=2),
    _sp(9, 8, S + "/sweep", E + 10.062, E + 10.063),
    _sp(10, 8, S + "/admit", E + 10.063, E + 10.167, admitted=1),
    *_call(11, 10, S + "/admit/prefill", E + 10.064, E + 10.067, E + 10.166,
           uid=5, slot=0, bucket=2048, true_len=1024),
    *_call(14, 8, S + "/decode", E + 10.168, E + 10.171, E + 10.220, n_active=8),
    _sp(17, 8, S + "/emit", E + 10.220, E + 10.224, tokens=8, finished=0),
    # step D, 110 ms: prefill 102 ms (dispatch 2), no decode; self 8
    _sp(22, None, S, E + 10.232, E + 10.342, step=3),
    _sp(23, 22, S + "/admit", E + 10.232, E + 10.337, admitted=1),
    *_call(24, 23, S + "/admit/prefill", E + 10.233, E + 10.235, E + 10.335,
           uid=6, slot=1, bucket=2048, true_len=1536),
    # step C: its decode compiled (498 ms): no median sees it, nor its step
    _sp(18, None, S, E + 10.350, E + 10.850, step=4),
    _sp(19, 18, S + "/decode", E + 10.351, E + 10.849, n_active=8, compiled=True),
    _sp(20, 19, S + "/decode/dispatch", E + 10.351, E + 10.353),
    _sp(21, 19, S + "/decode/fetch", E + 10.353, E + 10.849),
    # after the window: not counted
    *_call(40, None, "decode", E + 20.500, E + 20.501, E + 20.510, n_active=1),
]
T = "train/train_batch"
HAND_TRAIN = [  # t_start 500 + t_setup 100: the counted steps are [600.0, 602.8]
    _sp(1, None, T, 598.0, 598.9, step=1),  # warm-up (it compiled): before the window
    _sp(2, None, T, 600.001, 600.004, step=3),
    _sp(3, 2, T + "/dispatch", 600.002, 600.003),
    _sp(4, None, T, 601.401, 601.406, step=4),
    _sp(5, None, T, 602.801, 602.803, step=5),  # begun after the last counted step
]
# gaps (ms): 4 (the lead-in call's, clipped) + 11 (A->prefill) + 5 (prefill->decode B)
# + 15 (decode B->prefill D) + 18 (prefill D->compiled decode); window 10 s
READERS = {
    "decode_prog_ms_p50": 53.0, "prefill_prog_ms_p50": 102.0, "decode_dispatch_ms_p50": 2.5,
    "prefill_padding_pct": 37.5, "sched_host_ms_p50": 8.0,
    "serve_host_gap_pct": 100.0 * 0.053 / 10.0, "train_host_ms_p50": 4.0,
}


def _ctx(notes):
    run = SimpleNamespace(t_start=500.0, note=lambda **kw: notes.append(kw))
    return {"serve": {"window": (10.0, 20.0), "epoch": E, "traced": (10.0, 10.1)},
            "train": {"steps": [(0.0, 1.4), (1.4, 2.8)]}, "t_setup": 100.0, "trace": None,
            "run": run}


def _patch_ring(monkeypatch, records):
    monkeypatch.setattr(tracing, "spans",
                        lambda since=float("-inf"): [sp for sp in records if sp.t0 >= since])


@pytest.mark.parametrize("name", list(READERS))
def test_reader_on_a_hand_made_ring(monkeypatch, name):
    reader = importlib.import_module(f"chipbench.layer_metrics.{name}")
    _patch_ring(monkeypatch, HAND_SERVE + HAND_TRAIN)
    notes = []
    np.testing.assert_allclose(reader.read(_ctx(notes)), READERS[name], rtol=1e-9)
    if name == "serve_host_gap_pct":
        (note,) = notes
        assert note["event"] == "host_gaps"
        by = note["window"]["by_span"]
        np.testing.assert_allclose(sum(by.values()), note["window"]["gap_s"])
        np.testing.assert_allclose(note["window"]["gap_s"], 0.053)
        # A's emit 2 + B's 4 (the gap to D begins at B's fetch end); between steps 2 + 2 + 8
        np.testing.assert_allclose([by["emit"], by["outside"], by["sweep"]],
                                   [0.006, 0.012, 0.002], atol=1e-9)
        np.testing.assert_allclose(by["dispatch"], 0.002 + 0.003 + 0.003 + 0.002 + 0.002)
        # the traced sub-window [10.0, 10.1): the lead-in's 4 ms + the 11 ms gap
        np.testing.assert_allclose(note["traced"]["pct"], 15.0)
        assert note["traced"]["device_idle_pct"] is None  # no trace in this ctx


@pytest.mark.parametrize("name", list(READERS))
def test_reader_on_an_empty_ring(monkeypatch, name):
    reader = importlib.import_module(f"chipbench.layer_metrics.{name}")
    notes = []
    _patch_ring(monkeypatch, [])
    assert reader.read(_ctx(notes)) is None and not notes
    # a program without the ring (the parent of PR 24): nothing to read either
    monkeypatch.delattr(tracing, "spans")
    assert reader.read(_ctx(notes)) is None
    # and the other kind of cell has no such block
    monkeypatch.undo()
    _patch_ring(monkeypatch, HAND_SERVE + HAND_TRAIN)
    ctx = _ctx(notes)
    ctx["serve" if name != "train_host_ms_p50" else "train"] = None
    assert reader.read(ctx) is None


# -- the anatomy readers (PR 35): the ring's five parts beside the trace's programs ----------

def _anatomy_call(id, parent, path, t0, operands, key, enqueue, wait, copy, **attrs):
    """A worker call from t0 (s) with its five parts back to back (ms each)."""
    t = [t0]
    for ms in (operands, key, enqueue, wait, copy):
        t.append(t[-1] + ms / 1e3)
    return [_sp(id, parent, path, t[0], t[5], **{"compiled": False, **attrs}),
            _sp(id + 1, id, path + "/dispatch", t[0], t[3]),
            _sp(id + 2, id + 1, path + "/dispatch/operands", t[0], t[1]),
            _sp(id + 3, id + 1, path + "/dispatch/key", t[1], t[2]),
            _sp(id + 4, id + 1, path + "/dispatch/enqueue", t[2], t[3]),
            _sp(id + 5, id, path + "/fetch", t[3], t[5]),
            _sp(id + 6, id + 5, path + "/fetch/wait", t[3], t[4]),
            _sp(id + 7, id + 5, path + "/fetch/copy", t[4], t[5])]


# the traced window is [10.0, 10.2) of the loop's clock, the host window [10, 20)
HAND_ANATOMY = [
    # 20 ms, half of it before the traced window: counts as half a run
    *_anatomy_call(100, None, S + "/decode", E + 9.990, .5, .5, 1, 17, 1, h2d=8, d2h=2),
    *_anatomy_call(110, None, S + "/decode", E + 10.020, .5, .5, 2, 16, 1, h2d=8, d2h=2),
    *_anatomy_call(120, None, S + "/admit/prefill", E + 10.050, 3, 1, 2, 93, 1, h2d=7, d2h=2,
                   bucket=2048, true_len=1500),
    # it compiled: no reader sees it
    *_anatomy_call(130, None, S + "/decode", E + 10.150, 1, 1, 5, 22, 1, h2d=8, d2h=2,
                   compiled=True),
    # 40 ms, 10 of them inside the traced window: a quarter of a run
    *_anatomy_call(140, None, S + "/decode", E + 10.190, 1, 1, 3, 33.5, 1.5, h2d=8, d2h=2),
    # after the traced window, in the host window: a call that packed its operands and results
    *_anatomy_call(150, None, S + "/decode", E + 12.000, 1, 1, 4, 22, 2, h2d=6, d2h=1),
]
HAND_OPS = {"jit_decode/fusion.1": 0.014, "jit_decode/fusion.2": 0.007,  # 21 ms over 1.75 runs
            "jit_prefill/ragged-dot": 0.080,
            "jit_decoder/fusion.9": 0.5, "jit__threefry_split/fusion": 0.001}  # foreign programs
DECODE_RUNS = 0.5 + 1 + 0.25
ANATOMY_READERS = {
    "decode_device_ms_mean": 21.0 / DECODE_RUNS, "prefill_device_ms_mean": 80.0,
    # the weighted calls: (0.5 x 20 + 20 + 0.25 x 40) ms over 1.75 runs
    "decode_call_overhead_ms": 40.0 / DECODE_RUNS - 21.0 / DECODE_RUNS,
    "prefill_call_overhead_ms": 20.0,
    # the host window's calls that did not compile: those at 10.020, 10.190, 12.000
    "decode_enqueue_ms_p50": 3.0, "decode_copy_ms_p50": 1.5, "decode_host_transfers": 10.0,
}
TRACE_READ = ("decode_device_ms_mean", "prefill_device_ms_mean", "decode_call_overhead_ms",
              "prefill_call_overhead_ms")


def _anatomy_ctx(notes, ops=HAND_OPS):
    ctx = _ctx(notes)
    ctx["serve"]["traced"] = (10.0, 10.2)
    ctx["trace"] = {"op_seconds": dict(ops), "busy_s_worst": sum(ops.values()), "window_s": 0.2}
    return ctx


@pytest.mark.parametrize("name", list(ANATOMY_READERS))
def test_anatomy_reader_on_a_hand_made_ring_and_trace(monkeypatch, name):
    reader = importlib.import_module(f"chipbench.layer_metrics.{name}")
    _patch_ring(monkeypatch, HAND_ANATOMY)
    notes = []
    np.testing.assert_allclose(reader.read(_anatomy_ctx(notes)), ANATOMY_READERS[name], rtol=1e-9)
    if name != "decode_call_overhead_ms":
        assert not notes
        return
    (note,) = notes
    assert note["event"] == "call_anatomy"
    traced, host = note["decode"]["traced"], note["decode"]["host"]
    np.testing.assert_allclose(
        [traced["runs"], traced["device_ms"], traced["call"]["mean"], traced["call"]["p50"]],
        [1.75, 12.0, 40.0 / 1.75, 20.0])
    # enqueue (0.5 x 1 + 2 + 0.25 x 3) / 1.75 + wait (0.5 x 17 + 16 + 0.25 x 33.5) / 1.75 - 12
    np.testing.assert_allclose(traced["runtime_ms"], (3.25 + 32.875) / 1.75 - 12.0)
    np.testing.assert_allclose([traced["overhead_ms"], host["overhead_ms"]],
                               [ANATOMY_READERS[name], 30.0 - 12.0])
    np.testing.assert_allclose([host["runs"], host["call"]["mean"], host["enqueue"]["p50"],
                                host["copy"]["mean"], host["h2d"], host["d2h"]],
                               [3, 30.0, 3.0, 1.5, 8, 2])
    for table in (traced, host):  # the parts cover their parents: this ring has no look-up
        for up, parts in (("dispatch", ("operands", "key", "enqueue")), ("fetch", ("wait", "copy"))):
            np.testing.assert_allclose(sum(table[p]["mean"] for p in parts), table[up]["mean"])
    np.testing.assert_allclose([note["prefill"]["traced"]["device_ms"],
                                note["prefill"]["traced"]["operands"]["mean"]], [80.0, 3.0])
    # runs x device time of the two programs + the others tile the busy seconds
    programs = note["programs_s"]
    np.testing.assert_allclose(
        1e-3 * (traced["runs"] * traced["device_ms"] + 80.0) + programs["other"], note["busy_s"])
    np.testing.assert_allclose([programs["jit_decode"], programs["other"]], [0.021, 0.501])
    assert programs["other_top"][0] == ("jit_decoder", 0.5)


def test_anatomy_readers_on_a_ring_without_key_spans(monkeypatch):
    """The program since PR 36: a call has no ``key`` part. Every reader reads what
    it read (none takes ``key``), and the note's table says ``key`` None with the
    other parts as they were."""
    records = [r for r in HAND_ANATOMY if not r.path.endswith("/dispatch/key")]
    assert len(records) < len(HAND_ANATOMY)
    _patch_ring(monkeypatch, records)
    notes = []
    for name, want in ANATOMY_READERS.items():
        reader = importlib.import_module(f"chipbench.layer_metrics.{name}")
        np.testing.assert_allclose(reader.read(_anatomy_ctx(notes)), want, rtol=1e-9)
    (note,) = notes
    for kind, operands in (("decode", (0.5 * .5 + .5 + 0.25 * 1) / DECODE_RUNS), ("prefill", 3.0)):
        traced = note[kind]["traced"]
        assert traced["key"] is None and note[kind]["host"]["key"] is None
        np.testing.assert_allclose(traced["operands"]["mean"], operands)
        assert traced["enqueue"] and traced["wait"] and traced["copy"] and traced["dispatch"]


@pytest.mark.parametrize("name", list(ANATOMY_READERS))
def test_anatomy_reader_with_nothing_to_read(monkeypatch, name):
    reader = importlib.import_module(f"chipbench.layer_metrics.{name}")
    notes = []
    # a trace that names no program (the CPU rehearsal's): the span-read ones still read
    _patch_ring(monkeypatch, HAND_ANATOMY)
    got = reader.read(_anatomy_ctx(notes, {"fusion.1": 0.1, "dot.2": 0.05}))
    if name in TRACE_READ:
        assert got is None
    else:
        np.testing.assert_allclose(got, ANATOMY_READERS[name])
    # a ring whose calls have no parts and no counters (the parent of PR 35): the device's
    # time a run and the overhead read from what it has; step A's decode, 54 ms, is the one
    # call of _ctx's traced window [10.0, 10.1)
    _patch_ring(monkeypatch, HAND_SERVE)
    ctx = _anatomy_ctx(notes, {"jit_decode/fusion.1": 0.040})
    ctx["serve"]["traced"] = (10.0, 10.1)
    want = {"decode_device_ms_mean": 40.0, "decode_call_overhead_ms": 14.0}.get(name)
    if want is None:
        assert reader.read(ctx) is None
    else:
        np.testing.assert_allclose(reader.read(ctx), want)
    # a window that holds no call, a program without the ring, a cell that does not serve
    notes.clear()
    _patch_ring(monkeypatch, [])
    assert reader.read(_anatomy_ctx(notes)) is None and not notes
    monkeypatch.delattr(tracing, "spans")
    assert reader.read(_anatomy_ctx(notes)) is None
    monkeypatch.undo()
    _patch_ring(monkeypatch, HAND_ANATOMY)
    ctx = _anatomy_ctx(notes)
    ctx["serve"] = None
    assert reader.read(ctx) is None and not notes


_SELFTEST_PARTS = ["check_intervals", "check_handmade_trace", "check_recorded_trace", "check_flops",
                   "check_traffic", "check_files", "check_trace_window", "check_references"]


@pytest.mark.parametrize("part", _SELFTEST_PARTS)
def test_chipbench_selftest(part, monkeypatch):
    """The yardstick's own checks, part by part and in this process: every per_layer
    entry of BENCHMARK.json against its reader file, the trace reduction, flops,
    traffic, the trace window, and what the references refuse. ``check_files`` ends
    in ``check_references``, whose second half is ``tests/test_reference_parity.py``
    case for case (each configuration against its reference, and bfloat16 failing
    the tolerance): here the first half alone (every block has a reference that
    covers it, and ``COVERS`` refuses by name), once. ``python3 -m chipbench.selftest``
    still runs all of it (by hand, before a chip call: ``chipbench/README.md``)."""
    from chipbench import parity, selftest

    if part == "check_references":
        monkeypatch.setattr(parity, "cases", lambda: [])  # that half: test_reference_parity.py
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            selftest.check_references(json.load(f))
    else:
        monkeypatch.setattr(selftest, "check_references", lambda bench: None)  # check_files' tail
        getattr(selftest, part)()
