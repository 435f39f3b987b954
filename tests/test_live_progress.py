"""``ServingEngine.live_progress()`` — the per-step progress surface a worker
process piggybacks on its step reply (launcher/serving_worker.py) and the
benchmark's serving loop reads after every step.

The contract under test: ``{uid: tokens so far}`` for every decoding slot, each
value a read-only window on the slot's Python ``int``s that a later ``step()``
neither grows nor changes, whatever path wrote the tokens (a decode step, a
speculative burst, a handoff import), at the cost of one small object a slot —
no work per token already generated, not even a copy.

Speed: every engine here is built on the session-scoped ``tiny_serving_engine``
with the shapes of test_serving / test_speculative / test_disagg, so this
module adds no XLA program of its own.
"""

import json
import time
from collections.abc import Sequence

import numpy as np
import pytest

from deepspeed_tpu.inference import Request, ServingEngine
from deepspeed_tpu.launcher.serving_worker import WorkerHost

SPEC = {"enabled": True, "depth": 4, "ngram_min_match": 2}
KV_WINDOW = 64  # test_disagg's handoff width: the one export / import program
MAX_NEW = 40


@pytest.fixture(scope="module")
def engine(tiny_serving_engine):
    return tiny_serving_engine


def _requests(sizes, seed=0, vocab=97):
    rng = np.random.default_rng(seed)
    return [Request(uid=100 + i, prompt=rng.integers(1, vocab, size=s).astype(np.int32),
                    max_new_tokens=MAX_NEW) for i, s in enumerate(sizes)]


def _numpy_first_token(srv):
    """Make the worker's prefill hand its first token over as a NumPy scalar:
    the engine converts where it WRITES ``st.tokens``, never where it reads."""
    prefill = srv.worker.prefill

    def as_numpy(*args, **kwargs):
        first, bad = prefill(*args, **kwargs)
        return np.int32(first), bad

    srv.worker.prefill = as_numpy


def _loaded(engine, **features):
    srv = ServingEngine(engine, n_slots=4, max_seq_len=128,
                        config={"watchdog_mode": "raise"}, **features)
    _numpy_first_token(srv)
    for r in _requests([5, 11, 23]):
        srv.submit(r)
    return srv


def _decoding(engine):
    srv = _loaded(engine)
    for _ in range(6):
        srv.step(now=float("inf"))
    return srv


def _after_a_burst(engine):
    srv = _loaded(engine, speculation=SPEC)
    # the tiny model falls into repetition, the n-gram drafter fires, and a
    # verify step appends an accepted draft and its bonus token in one burst
    for _ in range(MAX_NEW):
        srv.step(now=float("inf"))
        if srv.spec_stats()["accepted"] > 0:
            break
    assert srv.spec_stats()["accepted"] > 0 and srv.n_active > 0
    return srv


def _imported(engine):
    """A decode-role engine whose slots were filled by handoff imports, the
    Router's pump done by hand (router.py ``_pump_handoffs``)."""
    common = {"n_slots": 2, "max_seq_len": 128, "watchdog_mode": "raise"}
    pre = ServingEngine(engine, config=dict(common), role="prefill")
    dec = ServingEngine(engine, config=dict(common), role="decode")
    reqs = {r.uid: r for r in _requests([7, 19], seed=3)}
    for r in reqs.values():
        pre.submit(r)
    while len(pre.handoff_ready()) < len(reqs):
        pre.step(now=float("inf"))
    for h in pre.handoff_ready():
        uid, pos = h["uid"], h["pos"]
        dec.kv_import_begin(reqs[uid], pos, np.int32(h["first"]))
        for start in range(0, -(-pos // KV_WINDOW) * KV_WINDOW, KV_WINDOW):
            k, v = pre.kv_export_window(uid, start, KV_WINDOW)
            dec.kv_import_window(uid, start, KV_WINDOW, k, v)
        assert dec.kv_import_commit(uid) and pre.handoff_release(uid)
    for _ in range(4):
        dec.step(now=float("inf"))
    return dec


WRITERS = {"decode": _decoding, "speculative": _after_a_burst, "handoff": _imported}


@pytest.fixture(params=list(WRITERS))
def srv(request, engine):
    srv = WRITERS[request.param](engine)
    assert srv.n_active >= 2
    return srv


def test_progress_is_partial_tokens_and_a_prefix_of_the_result(srv):
    progress = srv.live_progress()
    assert len(progress) == srv.n_active
    for uid, toks in progress.items():
        assert len(toks) > 1
        assert list(toks) == list(srv.partial_tokens(uid))
    srv.drain()
    for uid, toks in progress.items():
        assert list(srv.result(uid).tokens[:len(toks)]) == list(toks)


def test_every_token_is_a_python_int(srv):
    for _ in range(2):  # what was written before, and what the next step writes
        for toks in srv.live_progress().values():
            assert isinstance(toks, Sequence)
            assert all(type(t) is int for t in toks)
            assert type(toks[0]) is int and toks[-1] == toks[len(toks) - 1]
            assert toks[1:] == list(toks)[1:] and toks[:] == list(toks)
            with pytest.raises(IndexError):
                toks[len(toks)]
        srv.step(now=float("inf"))


def test_the_workers_progress_block_serialises(srv):
    reply = WorkerHost(srv).step(now=float("inf"), progress=True)
    block = json.loads(json.dumps({"progress": reply["progress"]}))["progress"]
    assert block == {str(u): list(t) for u, t in srv.live_progress().items()}
    assert block and all(len(t) > 1 for t in block.values())


def test_a_returned_window_does_not_change_under_its_holder(srv):
    """The contract ``live_progress()``'s docstring states: a later ``step()``
    neither grows nor changes what an earlier call returned — not while the
    request decodes on, and not after it finished and its slot was reused —
    and the holder cannot write through it."""
    held = srv.live_progress()
    snapshot = {u: list(t) for u, t in held.items()}
    srv.step(now=float("inf"))
    assert {u: list(t) for u, t in held.items()} == snapshot
    after = srv.live_progress()
    assert any(len(after[u]) > len(t) for u, t in held.items() if u in after)
    for toks in held.values():
        with pytest.raises(TypeError):
            toks[0] = -1
        assert not hasattr(toks, "append")
    srv.drain()
    for r in _requests([9, 9, 9, 9], seed=5):  # new occupants for every slot
        r.uid += 1000
        srv.submit(r)
    for _ in range(3):
        srv.step(now=float("inf"))
    assert {u: list(t) for u, t in held.items()} == snapshot


def test_progress_costs_no_call_and_no_copy_a_token(engine):
    """No absolute clock: on 128 slots of 4,096 tokens the surface is at least
    four times as fast as the per-token conversion it replaced, timed here on
    the same lists (the old body reads 1x, a slice copy ~14x faster)."""
    srv = ServingEngine(engine, n_slots=128, max_seq_len=128)
    rng = np.random.default_rng(0)
    for uid, st in enumerate(srv._slots):
        st.uid = uid
        st.tokens = rng.integers(0, 97, size=4096).tolist()
    srv._active[:] = True
    lists = {st.uid: st.tokens for st in srv._slots}

    def best_of_five(fn):
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            out = fn()
            best = min(best, time.perf_counter() - t0)
        return best, out

    t_new, got = best_of_five(srv.live_progress)
    t_old, want = best_of_five(lambda: {u: list(map(int, t)) for u, t in lists.items()})
    assert {u: list(t) for u, t in got.items()} == want and len(got) == 128
    assert t_new <= t_old / 4, (t_new, t_old)
