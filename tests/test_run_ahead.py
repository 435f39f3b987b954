"""The scheduler runs ONE decode step ahead of the host's copy of the tokens (PR 60).

``ServingEngine._step`` hands the device step k + 1 before it fetches step k; the
tokens step k sampled stay on the device as step k + 1's operand. What must hold:
the output of an engine driven to the end is, token for token, what the engine gives
when it fetches first (an engine with a fault injector ARMED and nothing to inject
runs that order: it is the fallback), dense, routed and with an exit gate; a row
enqueued for a request that ended meanwhile (EOS, ``bad``, cancel, deadline) is
dropped, exactly that row, and the slot's next request is right; ``drain()`` and
``serve()`` leave nothing unfetched; a drafter's step and an armed injector fall
back; the decode program compiles once; the ``decode`` spans keep their shape."""

import json
import os
import re
import time

import numpy as np
import pytest

from deepspeed_tpu.inference import Request, ServingEngine
from deepspeed_tpu.launcher.serving_worker import build_serving_engine
from deepspeed_tpu.telemetry import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FETCH_FIRST = {"fault_injection": {"enabled": True}}  # armed, nothing listed, rate 0


@pytest.fixture(scope="module")
def engine(tiny_serving_engine):
    return tiny_serving_engine


def _prompts(sizes, seed=0, vocab=97):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=s).astype(np.int32) for s in sizes]


def _twin(name: str, **serving):
    """The rehearsal twin of a benchmark configuration behind a serving engine."""
    with open(os.path.join(ROOT, "chipbench", "configs", f"{name}.json")) as f:
        program = json.load(f)["rehearse_program"]
    return build_serving_engine({
        "model": {**program, "dtype": "float32"}, "engine_dtype": "fp32",
        "serving": {"n_slots": 3, "max_seq_len": 256, "seed": 0, **serving}}), program


def _decodes(t0):
    return [sp for sp in tracing.spans(t0) if sp.name == "decode"]


def _children(t0):
    kids = {}
    for sp in tracing.spans(t0):
        kids.setdefault(sp.parent, set()).add(sp.name)
    return kids


# -- the same tokens as fetching first ---------------------------------------------------------


def _mixed(sampled: bool):
    """More requests than slots, ragged lengths, an EOS among them: slots are freed
    by length and by EOS and refilled while steps are in flight."""
    prompts = _prompts([5, 11, 23, 8, 17, 6, 9], seed=11)
    return [Request(uid=i, prompt=p, max_new_tokens=3 + 2 * i,
                    temperature=0.8 if sampled and i % 2 else 0.0,
                    top_k=5 if sampled and i % 3 == 0 else 0,
                    top_p=0.9 if sampled and i % 2 else 1.0)
            for i, p in enumerate(prompts)]


def test_greedy_output_is_the_fetch_first_engines_and_the_models(engine):
    ahead = ServingEngine(engine, n_slots=3, max_seq_len=128)
    first = ServingEngine(engine, n_slots=3, max_seq_len=128, **FETCH_FIRST)
    for srv in (ahead, first):
        for r in _mixed(False):
            srv.submit(r)
    got, want = ahead.drain(), first.drain()
    for r in _mixed(False):
        np.testing.assert_array_equal(got[r.uid].tokens, want[r.uid].tokens)
        np.testing.assert_array_equal(
            got[r.uid].tokens, engine.generate(r.prompt[None], r.max_new_tokens)[0])
    counts = ahead.compile_counts()
    assert counts["decode_steps_ahead"] > 0.7 * counts["decode_steps"]
    assert first.compile_counts()["decode_steps_ahead"] == 0


def test_sampled_output_repeats_the_fetch_first_engines_at_one_seed(engine):
    """The key is split once a call in the same order where every request is
    admitted before the first decode step: as many requests as slots."""
    reqs = _mixed(True)[:3]
    out = []
    for extra in ({}, FETCH_FIRST, {}):
        srv = ServingEngine(engine, n_slots=3, max_seq_len=128, seed=5, **extra)
        res = srv.serve(reqs)
        out.append([res[r.uid].tokens for r in reqs])
    for a, b, c in zip(*out):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)
    assert any(len(set(t.tolist())) > 1 for t in out[0])


@pytest.mark.parametrize("name", ["olmoe-1b-7b-L4", "ouro-2.6b-L12"], ids=["routed", "exit_gate"])
def test_routed_and_gated_twins_match_fetch_first_and_pair_what_they_note(name):
    """A routed model's ``routing_log`` pairs the experts chosen with the rows of the
    step that PRODUCED them, and an exit gate's distribution is noted a step late,
    not lost: both orders log the same (as many requests as slots, so that both run
    the same steps: a freed slot is refilled one step later when steps run ahead)."""
    logs, tokens, exits = [], [], []
    for extra in ({}, FETCH_FIRST):
        srv, program = _twin(name, **extra)
        srv.worker.routing_log = []
        rng = np.random.default_rng(3)
        reqs = [Request(uid=i, prompt=rng.integers(0, program["vocab_size"], size=n).astype(np.int32),
                        max_new_tokens=4 + 2 * i) for i, n in enumerate((40, 77, 21))]
        t0 = time.perf_counter()
        for r in reqs:
            srv.submit(r)
        res = srv.drain()
        tokens.append([res[r.uid].tokens for r in reqs])
        logs.append(srv.worker.routing_log)
        # on the span that FETCHED the step: a later decode call's, or a ``collect``
        exits.append([sp.attrs["exit_pass_mean"] for sp in tracing.spans(t0)
                      if sp.name in ("decode", "collect") and "exit_pass_mean" in sp.attrs])
        assert srv.compile_counts()["decode"] == 1
    for a, b in zip(*tokens):
        np.testing.assert_array_equal(a, b)
    assert exits[0] == exits[1]
    assert [e["span"] for e in logs[0]] == [e["span"] for e in logs[1]]
    for a, b in zip(*logs):
        assert a.keys() == b.keys()
        for key in a:
            np.testing.assert_array_equal(a[key], b[key])
    if name.startswith("olmoe"):
        steps = [e for e in logs[0] if e["span"] == "decode"]
        assert steps and all(e["chosen"].shape[1] == len(e["active"]) == len(e["pos"])
                             for e in steps)
    else:
        assert exits[0]


# -- a row that ran for nothing ----------------------------------------------------------------


def _stop_token(ref):
    at = next(i for i in range(2, len(ref) - 2) if ref[i] not in ref[:i])
    return at, int(ref[at])


def test_an_eos_with_a_row_in_flight_drops_that_row_and_the_next_request_is_right(engine):
    srv = ServingEngine(engine, n_slots=1, max_seq_len=128)
    pa, pb = _prompts([8, 13], seed=3)
    ref = engine.generate(pa[None], max_new_tokens=10)[0]
    at, stop = _stop_token(ref)
    t0 = time.perf_counter()
    srv.submit(Request(uid=0, prompt=pa, max_new_tokens=10, eos_token=stop))
    srv.submit(Request(uid=1, prompt=pb, max_new_tokens=6))
    res = srv.drain()
    np.testing.assert_array_equal(res[0].tokens, ref[:at + 1])  # the EOS included, nothing after
    np.testing.assert_array_equal(res[1].tokens, engine.generate(pb[None], 6)[0])
    assert res[0].slot == res[1].slot == 0
    dropped = [sp.attrs.get("rows_discarded", 0) for sp in _decodes(t0)]
    assert sum(dropped) == 1 and max(dropped) == 1
    counters = srv.telemetry.registry.snapshot()["counters"]
    assert counters["serving/decode_rows_discarded"] == 1
    assert counters["serving/decode_steps_ahead"] == srv.compile_counts()["decode_steps_ahead"] > 0


def _in_flight(engine):
    """An engine two steps into a request of 12 tokens: a row of it is in flight."""
    srv = ServingEngine(engine, n_slots=2, max_seq_len=128)
    pa, pb = _prompts([9, 14], seed=8)
    srv.submit(Request(uid=0, prompt=pa, max_new_tokens=12))
    srv.step(now=0.0)
    srv.step(now=0.0)
    assert srv._flight is not None and srv._flight.active[0]
    return srv, pa, pb


def test_cancel_with_a_row_in_flight(engine):
    srv, pa, pb = _in_flight(engine)
    before = list(srv.live_progress()[0])
    assert srv.cancel(0)
    srv.submit(Request(uid=1, prompt=pb, max_new_tokens=5))
    res = srv.drain()
    assert res[0].status == "cancelled"
    np.testing.assert_array_equal(res[0].tokens, before)  # the row in flight added nothing
    np.testing.assert_array_equal(res[0].tokens, engine.generate(pa[None], 12)[0][:len(before)])
    np.testing.assert_array_equal(res[1].tokens, engine.generate(pb[None], 5)[0])
    assert srv.telemetry.registry.snapshot()["counters"]["serving/decode_rows_discarded"] == 1
    assert srv._flight is None and srv.n_active == 0 and srv.n_free == 2


def test_a_deadline_eviction_with_a_row_in_flight(engine):
    srv = ServingEngine(engine, n_slots=2, max_seq_len=128)
    pa, pb = _prompts([9, 14], seed=8)
    srv.submit(Request(uid=0, prompt=pa, max_new_tokens=12, deadline_s=5.0))
    srv.submit(Request(uid=1, prompt=pb, max_new_tokens=6))
    srv.step(now=0.0)
    srv.step(now=1.0)
    assert srv._flight.active.all()
    done = srv.step(now=6.0)  # enqueues a row for uid 0, then the sweep evicts it
    assert 0 in done and srv.result(0).status == "deadline_exceeded"
    n = len(srv.result(0).tokens)
    res = srv.drain()
    assert len(res[0].tokens) == n
    np.testing.assert_array_equal(res[0].tokens, engine.generate(pa[None], 12)[0][:n])
    np.testing.assert_array_equal(res[1].tokens, engine.generate(pb[None], 6)[0])
    assert srv.telemetry.registry.snapshot()["counters"]["serving/decode_rows_discarded"] == 1


def test_a_bad_sentinel_with_a_row_in_flight(engine, monkeypatch):
    """The fetched step says ``bad`` for a slot whose next row is queued already: the
    request is quarantined and replayed, the queued row dropped, and the replay
    (same uid, perhaps the same slot) is the model's tokens."""
    srv, pa, pb = _in_flight(engine)
    fetch = srv.worker._fetch_results
    state = {"armed": True}

    def poisoned(sp, out, chosen, n_out, rows):
        nxt, bad = fetch(sp, out, chosen, n_out, rows)
        if state["armed"] and rows["active"][0]:
            state["armed"] = False
            bad = bad.copy()
            bad[0] = True
        return nxt, bad

    monkeypatch.setattr(srv.worker, "_fetch_results", poisoned)
    srv.submit(Request(uid=1, prompt=pb, max_new_tokens=5))
    res = srv.drain()
    assert res[0].status == "ok" and res[0].requeues == 1
    np.testing.assert_array_equal(res[0].tokens, engine.generate(pa[None], 12)[0])
    np.testing.assert_array_equal(res[1].tokens, engine.generate(pb[None], 5)[0])
    counters = srv.telemetry.registry.snapshot()["counters"]
    assert counters["resilience/quarantines"] == 1
    assert counters["serving/decode_rows_discarded"] == 1


# -- nothing stays unfetched; the fallbacks ----------------------------------------------------


def test_drain_and_serve_return_with_nothing_unfetched(engine):
    srv = ServingEngine(engine, n_slots=2, max_seq_len=128)
    pa, pb, pc = _prompts([7, 12, 9], seed=4)
    ref = engine.generate(pa[None], max_new_tokens=9)[0]
    _, stop = _stop_token(ref)
    # an EOS leaves a row in flight behind the last request: drain collects it
    srv.submit(Request(uid=0, prompt=pa, max_new_tokens=9, eos_token=stop))
    srv.drain()
    assert srv._flight is None and srv.worker._pending is None and srv.idle
    # serve() returns its own requests; another's step in flight is collected, its
    # tokens kept, and the request stays in flight
    srv.submit(Request(uid=1, prompt=pb, max_new_tokens=30))
    res = srv.serve([Request(uid=2, prompt=pc, max_new_tokens=4)])
    np.testing.assert_array_equal(res[2].tokens, engine.generate(pc[None], 4)[0])
    assert srv._flight is None and srv.worker._pending is None
    assert srv.n_active == 1 and srv.result(1) is None
    np.testing.assert_array_equal(srv.drain()[1].tokens, engine.generate(pb[None], 30)[0])


def test_a_token_reaches_live_progress_one_step_after_its_step_was_enqueued(engine):
    srv = ServingEngine(engine, n_slots=2, max_seq_len=128)
    (p,) = _prompts([10], seed=6)
    srv.submit(Request(uid=0, prompt=p, max_new_tokens=4))
    seen, finished = [], []
    for _ in range(4):
        finished.append(srv.step(now=0.0))
        seen.append(len(srv.live_progress().get(0, ())))
    # step 1: the prefill's token, and a decode step enqueued; each later step fetches
    # one; the step that would exhaust the request is planned WITHOUT it (no wasted row)
    assert seen == [1, 2, 3, 0] and finished == [[], [], [], [0]]
    assert srv.compile_counts()["decode_steps"] == 3 and srv._flight is None
    np.testing.assert_array_equal(srv.result(0).tokens, engine.generate(p[None], 4)[0])


def test_an_armed_injector_fetches_first_and_still_injects(engine):
    srv = ServingEngine(engine, n_slots=2, max_seq_len=128, fault_injection={
        "enabled": True, "garbage_logits_uids": [0], "garbage_logits_phase": "decode",
        "garbage_logits_decode_step": 2})
    pa, pb = _prompts([9, 14], seed=8)
    t0 = time.perf_counter()
    res = srv.serve([Request(uid=0, prompt=pa, max_new_tokens=8),
                     Request(uid=1, prompt=pb, max_new_tokens=6)])
    assert res[0].status == "ok" and res[0].requeues == 1
    np.testing.assert_array_equal(res[0].tokens, engine.generate(pa[None], 8)[0])
    np.testing.assert_array_equal(res[1].tokens, engine.generate(pb[None], 6)[0])
    assert srv.compile_counts()["decode_steps_ahead"] == 0
    assert not any(sp.attrs["ahead"] for sp in _decodes(t0))
    assert "serving/decode_rows_discarded" not in srv.telemetry.registry.snapshot()["counters"]


def test_a_drafters_engine_fetches_first(engine):
    spec = {"enabled": True, "depth": 4, "ngram_min_match": 1}
    srv = ServingEngine(engine, n_slots=2, max_seq_len=128, speculation=spec)
    p = np.tile(np.asarray([3, 9, 4], np.int32), 6)  # repetitive: the drafter proposes
    res = srv.serve([Request(uid=0, prompt=p, max_new_tokens=16)])
    np.testing.assert_array_equal(res[0].tokens, engine.generate(p[None], 16)[0])
    assert srv.spec_stats()["verify_steps"] > 0
    assert srv.compile_counts()["decode_steps_ahead"] == 0 and srv._flight is None


# -- one program, and the spans' shape ---------------------------------------------------------


def test_the_decode_program_and_the_merge_compile_once(engine):
    srv = ServingEngine(engine, {"watchdog_mode": "raise"}, n_slots=3, max_seq_len=128)
    for r in _mixed(True):
        srv.submit(r)
    srv.step(now=float("inf"))
    srv.cancel(1)
    res = srv.drain()
    assert res[1].status == "cancelled" and all(
        res[r.uid].status == "ok" for r in _mixed(True) if r.uid != 1)
    counts = srv.compile_counts()
    assert counts["decode"] == 1 and counts["token_merge"] == 1
    table = {row["name"]: row for row in srv.telemetry.watchdog.compile_table()}
    for name in ("serving/decode", "serving/token_merge"):
        (row,) = [r for n, r in table.items() if n.startswith(name)]
        assert row["stable"] and row["compiles"] == 1 and row["refusals"] == 0
    # a token vector and a key typed by hand lower to the served call's own module
    w = srv.worker
    import jax
    import jax.numpy as jnp
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
    vec = lambda d: jax.ShapeDtypeStruct((w.n_slots,), d)
    by_hand = w._decode.lower(
        jax.tree.map(sds, w.params), jax.tree.map(sds, w._cache), vec(jnp.int32), vec(jnp.int32),
        vec(jnp.int32), vec(jnp.bool_), jax.random.PRNGKey(0), vec(jnp.float32), vec(jnp.int32),
        vec(jnp.float32))
    served = w._decode.lower(
        w.params, w._cache, w._toks, np.zeros(w.n_slots, np.int32), np.zeros(w.n_slots, np.int32),
        np.zeros(w.n_slots, bool), w._rng, np.zeros(w.n_slots, np.float32),
        np.zeros(w.n_slots, np.int32), np.ones(w.n_slots, np.float32))
    # (private functions are numbered by a counter of the process: @_where_132)
    text = lambda lowered: re.sub(r"@(\w+?)_\d+\b", r"@\1", lowered.as_text())
    assert text(by_hand) == text(served)


def test_every_steady_decode_span_has_both_halves_and_says_what_it_did(engine):
    srv = ServingEngine(engine, n_slots=2, max_seq_len=128)
    pa, pb, pc = _prompts([6, 15, 9], seed=9)
    t0 = time.perf_counter()
    srv.submit(Request(uid=0, prompt=pa, max_new_tokens=9))
    srv.submit(Request(uid=1, prompt=pb, max_new_tokens=5))
    srv.submit(Request(uid=2, prompt=pc, max_new_tokens=4))  # admitted into uid 1's slot
    srv.drain()
    decodes, kids = _decodes(t0), _children(t0)
    assert len(decodes) == srv.compile_counts()["decode_steps"]  # a span a device step
    first, *steady = decodes
    assert kids[first.id] == {"dispatch"} and not first.attrs["ahead"] and first.attrs["d2h"] == 0
    (last,) = [sp for sp in tracing.spans(t0) if sp.name == "collect"]  # the last step's fetch
    assert kids[last.id] == {"fetch"} and last.attrs["d2h"] == 2 and last.t0 > steady[-1].t1
    for sp in steady:
        assert kids[sp.id] == {"dispatch", "fetch"}
        assert sp.attrs["ahead"] is True and sp.attrs["d2h"] == 2 and not sp.attrs["compiled"]
        # six host operands (pos, wpos, active, the sampler's three); the merge's two more on
        # a step that follows an activation or an end: the tokens themselves are no upload
        assert sp.attrs["h2d"] == (8 if sp.attrs.get("merged") else 6)
        assert "rows_discarded" not in sp.attrs
    assert any(sp.attrs.get("merged") for sp in steady)
    assert not all(sp.attrs.get("merged") for sp in steady)
    paths = {sp.path for sp in tracing.spans(t0)}
    assert "serve/step/decode/dispatch/merge" in paths
    assert {"serve/step/decode/dispatch/operands", "serve/step/decode/dispatch/enqueue",
            "serve/step/decode/fetch/wait", "serve/step/decode/fetch/copy"} <= paths
    # one decode span a device step: each enqueued step's rows are on exactly one span
    assert sum(sp.attrs.get("n_active", 0) for sp in decodes) == (9 - 1) + (5 - 1) + (4 - 1)
