"""K-EXAONE-236B-A23B's twin behind the serving engine (split from ``test_k_exaone.py``,
PR 47): what the engine refuses at build, its tokens, spans and pools, a prefill through
a flash+window bucket, and the cell's rehearsal."""

import json
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from k_exaone_cases import (  # noqa: F401,I001 -- first: puts the repo's root on sys.path; fixtures
    ROOT, WHOLE, TOL, CONFIG, WINDOW, program, reference, cfg, params, _tokens, _prefill)

from chipbench import kinds_cost  # noqa: E402
from deepspeed_tpu.inference.serving import Request  # noqa: E402
from deepspeed_tpu.launcher.serving_worker import build_serving_engine  # noqa: E402
from deepspeed_tpu.models import transformer as tfm  # noqa: E402
from deepspeed_tpu.telemetry import tracing  # noqa: E402


def _spec(program, **serving):
    return {"model": {**program, "dtype": "float32"}, "engine_dtype": "fp32",
            "serving": {"n_slots": 3, "max_seq_len": 128, "seed": 0, "watchdog_mode": "off",
                        **serving}}


@pytest.mark.parametrize("what,block", [
    ("prefix_cache", {"prefix_cache": {"enabled": True, "n_slots": 2}}),
    ("speculation", {"speculation": {"enabled": True}}),
    ("role", {"role": "prefill"}),
])
def test_the_engine_refuses_at_build_what_moves_the_cache_by_position(program, what, block):
    with pytest.raises(NotImplementedError, match="window layers"):
        build_serving_engine(_spec(program, **block))


# -- the serving engine ----------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served(program):
    srv = build_serving_engine(_spec(program))
    cfg = srv.engine.cfg
    prompts = [_tokens(cfg, (n,), n) for n in (40, 9, 70)]
    mark = tracing.spans(0.0)[-1].t1 if tracing.spans(0.0) else 0.0
    results = srv.serve([Request(uid=i, prompt=p, max_new_tokens=12)
                         for i, p in enumerate(prompts)])
    return srv, prompts, results, [sp for sp in tracing.spans(0.0) if sp.t0 >= mark]


def test_serving_engine_serves_the_models_tokens(served):
    """Through ``build_serving_engine`` / ``ServingEngine.step`` / ``SlotWorker``
    like any other model: three requests of three buckets share the slots; every
    token is the argmax of ``apply`` on what came before it."""
    srv, prompts, results, _ = served
    cfg, params = srv.engine.cfg, srv.engine.params
    for i, p in enumerate(prompts):
        got = np.asarray(results[i].tokens)
        assert results[i].status == "ok" and len(got) == 12
        logits = np.asarray(tfm.apply(cfg, params, np.concatenate([p, got])[None]))[0]
        want = logits[len(p) - 1:len(p) + 11]
        gap = want.max(axis=-1) - want[np.arange(12), got]
        assert gap.max() <= 1e-4, gap
    assert srv.compile_counts()["decode"] == 1


def test_spans_and_pools_say_what_was_read(served):
    srv, prompts, _, spans = served
    pools = srv.worker.hbm_pools()
    assert pools["slot_kv_cache"] == 1 * 3 * 128 * tfm.cache_bytes_per_token(srv.engine.cfg)
    assert pools["slot_kv_ring"] == 3 * tfm.cache_ring_bytes(srv.engine.cfg)
    prefills = [sp for sp in spans if sp.name == "prefill"]
    decodes = [sp for sp in spans if sp.name == "decode"]
    assert {sp.attrs["attn"] for sp in prefills} == {"dense+window"}
    assert {sp.attrs["attn"] for sp in decodes} == {"dense+ring"}
    assert all(sp.attrs["window_layers"] == 5 for sp in prefills + decodes)
    # a decode call fetches the step BEFORE it (PR 60): what comes with a fetch is on all but a burst's first
    fetched = [sp for sp in decodes if sp.attrs["d2h"]]
    assert len(fetched) > len(decodes) / 2
    for sp in prefills + fetched:
        assert sp.attrs["experts_held"] == 4
        assert 0 < sp.attrs["experts_touched"] <= 4 and sp.attrs["expert_rows_held"] > 0
    by_len = {sp.attrs["true_len"]: sp for sp in prefills}
    for p in prompts:  # a prefill's queries each read min(position + 1, window) of a ring
        n = len(p)
        assert by_len[n].attrs["ring_tokens"] == sum(min(i + 1, WINDOW) for i in range(n))
        assert by_len[n].attrs["ring_tokens"] == kinds_cost.window_pairs(n, WINDOW)
    full = [sp for sp in decodes if sp.attrs["n_active"] == 3]
    assert full and all(sp.attrs["ring_tokens"] <= 3 * WINDOW < sp.attrs["cached_tokens"]
                        for sp in full[2:])
    with pytest.raises(NotImplementedError, match="window layers"):
        srv.worker.kv_export(16, 0, 0)


# -- a prefill through a flash+window bucket (PR 40: the banded forward) --------------------------

LONG = 1024  # a bucket whose band is narrower than its causal grid at the band's blocks (512, 128)


@pytest.fixture
def flash_from_1024_rows(monkeypatch, cfg):
    """``cache_attention_form``'s rule met at 1,024 rows of the twin's 4 heads (the
    cell meets it from 1,024 rows at 64): the bucket attends through the flash
    kernel, its window layers through the band."""
    monkeypatch.setattr(tfm, "DENSE_SCORE_BYTES", 4 * cfg.num_heads * LONG ** 2 - 1)
    assert tfm.cache_block_form(cfg, LONG) == "flash+window"
    assert tfm.cache_block_form(cfg, LONG // 2) == "dense+window"


def test_a_flash_window_prefill_is_the_reference_and_writes_the_parents_rings(
        cfg, params, program, reference, flash_from_1024_rows, monkeypatch):
    """A 700-token prompt padded to 1,024 rows: the window layers take the banded
    forward (the window a constant of the trace). Its logits are the reference's,
    and logits and rings are those of the parent's form (the whole causal grid
    under the window as a runtime operand: ``static_window`` made to see no
    constant) and of the dense form under a [T, T] bias."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    prompt = _tokens(cfg, (700,), 40)
    band_logits, band_cache = _prefill(cfg, params, tfm.init_cache(cfg, 2, LONG), 1, prompt)
    ref = reference.routed_pass(program, params, prompt, np.arange(699, 700), fetch=WHOLE)
    assert np.max(np.abs(band_logits - ref["logits"][0])) <= TOL

    def rings(cache):
        return [np.asarray(cache[tfm.RING][name]) for name in ("k", "v")]

    assert all(r[:, 1].any() and not r[:, 0].any() for r in rings(band_cache))
    with monkeypatch.context() as parent:
        parent.setattr(fa, "static_window", lambda window, *shape: (window, 0))
        whole_logits, whole_cache = _prefill(cfg, params, tfm.init_cache(cfg, 2, LONG), 1, prompt)
    with monkeypatch.context() as dense:
        dense.setattr(tfm, "DENSE_SCORE_BYTES", 2 ** 40)
        dense_logits, dense_cache = _prefill(cfg, params, tfm.init_cache(cfg, 2, LONG), 1, prompt)
    for logits, cache in ((whole_logits, whole_cache), (dense_logits, dense_cache)):
        assert np.max(np.abs(band_logits - logits)) <= TOL
        for got, want in zip(rings(band_cache), rings(cache)):
            np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)


def test_the_engine_serves_a_flash_window_bucket_and_says_which_grid(program,
                                                                     flash_from_1024_rows):
    """Through ``build_serving_engine`` with a 1,024-long slot cache: a 700-token
    request's prefill goes through the banded forward and its tokens are the
    argmax of ``apply``; the prefill span says which grid the window layers took
    and how much of the causal grid it computes; a short request's bucket attends
    densely and says nothing of a grid."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    spec = _spec({**program, "max_seq_len": LONG})
    spec["serving"]["max_seq_len"] = LONG
    srv = build_serving_engine(spec)
    cfg = srv.engine.cfg
    prompts = [_tokens(cfg, (700,), 41), _tokens(cfg, (30,), 42)]
    mark = tracing.spans(0.0)[-1].t1 if tracing.spans(0.0) else 0.0
    results = srv.serve([Request(uid=i, prompt=p, max_new_tokens=6) for i, p in enumerate(prompts)])
    for i, p in enumerate(prompts):
        res = results[i]
        got = np.asarray(res.tokens)
        assert res.status == "ok" and len(got) == 6
        logits = np.asarray(tfm.apply(cfg, srv.engine.params, np.concatenate([p, got])[None]))[0]
        want = logits[len(p) - 1:len(p) + 5]
        assert (want.max(axis=-1) - want[np.arange(6), got]).max() <= 1e-4
    by_bucket = {sp.attrs["bucket"]: sp.attrs for sp in tracing.spans(0.0)
                 if sp.name == "prefill" and sp.t0 >= mark}
    long, short = by_bucket[LONG], by_bucket[32]
    assert (long["attn"], long["window_grid"]) == ("flash+window", "band")
    blocks_pct = fa.window_grid(LONG, WINDOW, cfg.num_heads, cfg.head_dim, cfg.head_dim,
                                jnp.dtype(cfg.dtype).itemsize)[1]
    assert long["window_blocks_pct"] == round(blocks_pct, 2) < 100
    # its whole-context layers take the causal grid, and say what it computes of the triangle
    tiles_pct = fa.causal_tiles_pct(LONG, cfg.head_dim, jnp.dtype(cfg.dtype).itemsize)
    assert long["causal_tiles_pct"] == round(tiles_pct, 2) and "causal_tiles_pct" not in short
    assert short["attn"] == "dense+window" and "window_grid" not in short


# -- the cell's rehearsal ---------------------------------------------------------------------------


def test_the_cells_rehearsal_passes_and_lists_its_metrics():
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", f"{CONFIG}.serve-mixedlen",
         "--rehearse", "--trace", "1"], cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] == "passed" and last["correct"] and last["failed"] == 0
    for name in ("slot_cache_bytes_per_slot", "kinds_decode_hbm_floor_pct", "kinds_prefill_mfu_pct",
                 "moe_load_max_over_mean", "compiles_in_window.doc", "decode_host_transfers"):
        assert name in last["would_report"], last["would_report"]
