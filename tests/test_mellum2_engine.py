"""Mellum2-12B-A2.5B's twin behind the serving engine: ``chunked_prefill`` with window
layers builds (the refusal went with PR 59) and emits the tokens of the engine that
prefills whole prompts; what a chunk span says; what stays refused over rings by name; the
cell's own check (``drivers/serve_chunked_kinds.py``) on a float32 engine, sound and with a
fault planted in its probe; the cell's rehearsal."""

import json
import subprocess
import sys
from unittest import mock

import numpy as np
import pytest

from mellum2_cases import (  # noqa: F401,I001 -- first: puts the repo's root on sys.path; fixtures
    ROOT, WHOLE, TOL, CONFIG, WINDOW, program, reference, cfg, params, _tokens, planted,
    segments, judge_float8_reference)

from chipbench import chunk_cost, flops, kinds_cost  # noqa: E402
from chipbench.drivers import serve, serve_chunked_kinds, serve_latent  # noqa: E402
from deepspeed_tpu.inference.serving import Request  # noqa: E402
from deepspeed_tpu.launcher.serving_worker import build_serving_engine  # noqa: E402
from deepspeed_tpu.models import transformer as tfm  # noqa: E402
from deepspeed_tpu.telemetry import tracing  # noqa: E402

CHUNK = 32
LENS = (90, 9, 70, 33)  # two chunks and a tail; a lone tail; ...; one row behind a whole chunk


def _spec(program, **serving):
    return {"model": {**program, "dtype": "float32"}, "engine_dtype": "fp32",
            "serving": {"n_slots": 3, "max_seq_len": 128, "seed": 0, "watchdog_mode": "off",
                        "min_prefill_bucket": 8, **serving}}


def _chunks(size=CHUNK):
    return {"chunked_prefill": {"enabled": True, "chunk_size": size, "chunks_per_step": 1}}


@pytest.mark.parametrize("what,block", [
    ("prefix_cache", {"prefix_cache": {"enabled": True, "n_slots": 2}}),
    ("speculation", {"speculation": {"enabled": True}}),
    ("role", {"role": "prefill"}),
])
def test_what_moves_a_ring_by_position_is_still_refused_at_build(program, what, block):
    with pytest.raises(NotImplementedError, match="window layers"):
        build_serving_engine(_spec(program, **_chunks(), **block))


def _serve(program, **serving):
    srv = build_serving_engine(_spec(program, **serving))
    prompts = [_tokens(srv.engine.cfg, (n,), n) for n in LENS]
    mark = tracing.spans(0.0)[-1].t1 if tracing.spans(0.0) else 0.0
    results = srv.serve([Request(uid=i, prompt=p, max_new_tokens=10)
                         for i, p in enumerate(prompts)])
    return srv, prompts, results, [sp for sp in tracing.spans(0.0) if sp.t0 >= mark]


@pytest.fixture(scope="module")
def whole(program):
    return _serve(program)


@pytest.fixture(scope="module")
def served(program):
    return _serve(program, **_chunks())


def test_chunked_admission_emits_the_whole_prompt_engines_tokens(whole, served):
    """Greedy, four requests of one to three chunks sharing three slots: token for token
    what the engine emits with ``chunked_prefill`` off, and each the argmax of ``apply``."""
    srv, prompts, results, spans = served
    for i, p in enumerate(prompts):
        got = np.asarray(results[i].tokens)
        assert results[i].status == "ok" and len(got) == 10
        assert np.array_equal(got, np.asarray(whole[2][i].tokens)), i
        logits = np.asarray(tfm.apply(srv.engine.cfg, srv.engine.params,
                                      np.concatenate([p, got])[None]))[0]
        want = logits[len(p) - 1:len(p) + 9]
        assert (want.max(axis=-1) - want[np.arange(10), got]).max() <= 1e-4
    assert not [sp for sp in spans if sp.name == "prefill"]
    assert [sp for sp in whole[3] if sp.name == "prefill"] and not [
        sp for sp in whole[3] if sp.name == "chunk"]
    counts = srv.compile_counts()
    assert counts["decode"] == 1 and set(counts["chunk_prefill"]) == {8, 16, 32}


@pytest.mark.parametrize("size", [8, 16])  # below and equal to the window
def test_other_chunk_sizes_emit_the_same_tokens(program, whole, size):
    _, _, results, _ = _serve(program, **_chunks(size))
    for i in range(len(LENS)):
        assert np.array_equal(np.asarray(results[i].tokens), np.asarray(whole[2][i].tokens))


def test_a_chunk_span_says_what_its_queries_required(served):
    srv, prompts, _, spans = served
    chunks = [sp for sp in spans if sp.name == "chunk"]
    by_uid = {}
    for sp in chunks:
        by_uid.setdefault(sp.attrs["uid"], []).append(sp)
    p = srv.engine.cfg
    for uid, n in enumerate(LENS):
        cut = sorted((sp.attrs["start"], sp.attrs["width"], sp.attrs["live"])
                     for sp in by_uid[uid])
        assert cut == segments(n, CHUNK)
        for sp in by_uid[uid]:
            a = sp.attrs
            seen = np.arange(a["start"], a["start"] + a["live"])
            assert a["whole_keys"] == int(np.sum(seen + 1))
            assert a["ring_tokens"] == int(np.sum(np.minimum(seen + 1, WINDOW)))
            assert a["window_layers"] == 6 and a["attn"] == "dense+ring"
            assert a["attn_chunk"] == "dense"  # 4 heads x 32 x 128 scores: far under the threshold
            assert a["expert_rows_held"] == a["width"] * p.moe_top_k * p.num_layers
            assert a["expert_bank"] in ("in_place", "sliced") and a["cached_tokens"] == seen[-1] + 1
            assert a["fetch"] == (a["start"] + a["live"] == n)  # the last chunk alone is fetched
    decodes = [sp for sp in spans if sp.name == "decode"]
    assert decodes and all("ring_tokens" in sp.attrs and sp.attrs["window_layers"] == 6
                           for sp in decodes)
    tm = srv.telemetry
    assert tm.counter("serving/chunk_bucket[32]").value == sum(
        width == CHUNK for n in LENS for _, width, _ in segments(n, CHUNK))
    admits = tm.histogram("serving/chunks_per_admit")
    assert admits.count == len(LENS) and admits.sum == sum(len(segments(n, CHUNK)) for n in LENS)
    built = [sp for sp in tracing.spans(0.0) if sp.path == tracing.STARTUP]
    assert built[-1].attrs["rotary_kinds"] == "whole=yarn(10000, x4, 32) window=plain(10000)"


def test_the_cost_functions_count_what_the_spans_state(served, program):
    """``chunk_cost`` on a span's own counts: attention at what the model requires, the
    matrix products over the chunk's width, the head for one row."""
    _, _, _, spans = served
    sp = max((s for s in spans if s.name == "chunk"), key=lambda s: s.attrs["start"])
    a = sp.attrs
    whole_layers, window_layers = kinds_cost.layers_by_kind(program)
    assert (whole_layers, window_layers) == (2, 6)
    pairs = 2 * a["whole_keys"] + 6 * a["ring_tokens"]
    assert chunk_cost.attention_flops(program, a["whole_keys"], a["ring_tokens"]) == (
        4.0 * program["qk_head_dim"] * program["num_heads"] * pairs)
    counts = flops.param_counts(program)
    head = program["hidden_size"] * program["vocab_size"]
    total = chunk_cost.chunk_flops(program, a["width"], a["expert_rows_held"], a["whole_keys"],
                                   a["ring_tokens"])
    assert total == pytest.approx(
        2.0 * (counts["matmul_outside_experts"] - head) * a["width"] + 2.0 * head
        + 2.0 * a["expert_rows_held"] * counts["matmul_per_expert"]
        + 4.0 * program["qk_head_dim"] * program["num_heads"] * pairs)
    cost = chunk_cost.attention_cost(program, a["start"], a["width"], a["whole_keys"],
                                     a["ring_tokens"])
    kv = kinds_cost.kv_bytes_per_token(program)
    assert cost["bytes"] == (8 * a["width"] * 2 * 4 * 24 * 2
                             + (2 * (a["start"] + a["width"])
                                + 6 * (min(a["start"], WINDOW) + a["width"])) * kv)


def test_the_walks_operations_are_found_by_their_text(program):
    """``chunk_attn_roofline_pct.walk_seconds`` on a hand-made reduced trace: of the chunk
    programs' operations those whose result is one of the walk's own arrays (the running
    maximum and sum [1, K/V heads, group, rows], the accumulator [..., head width]) count,
    the loop's ``while`` among them; the same shapes in another program, the query heads
    laid otherwise and the band's kernel do not."""
    from chipbench.layer_metrics import chunk_attn_roofline_pct as reader

    kv, g, d = program["num_kv_heads"], program["num_heads"] // program["num_kv_heads"], 24
    text = {
        "jit_chunk/fusion.751": f"%fusion.751 = (f32[{kv},{g},32]{{2,1,0}}, f32[1,{kv},{g},32]{{3,2,1,0}}) fusion(%a)",
        "jit_chunk/bitcast_add_fusion.15": f"%bitcast_add_fusion.15 = f32[1,{kv},{g},32,{d}]{{4,3,2,1,0}} fusion(%a)",
        "jit_chunk/divide_convert_fusion.2": f"%divide_convert_fusion.2 = bf16[1,{kv},{g},32,{d}]{{4,3,2,1,0}} fusion(%a)",
        "jit_chunk/while.74": f"%while.74 = (s32[], f32[1,{kv},{g},32]{{3,2,1,0}}) while(%t), condition=%c, body=%b",
        "jit_chunk/flash_fwd_band.3": "%flash_fwd_band.3 = bf16[4,48,24]{2,1,0} custom-call(%q)",
        "jit_chunk/fusion.9": f"%fusion.9 = f32[1,32,{kv},{g},{d}]{{4,3,2,1,0}} fusion(%a)",
        "jit_decode/fusion.1": f"%fusion.1 = f32[1,{kv},{g},32]{{3,2,1,0}} fusion(%a)",
    }
    tr = {"op_seconds": {name: 1.0 for name in text}, "op_text": text}
    assert reader.walk_seconds(tr, program) == 4.0


class _Run:
    """What ``serve_latent._check`` reads of the harness's ``Run``."""

    def __init__(self, program, seed):
        self.program, self.seed = program, seed

    def sized(self, block):
        return {"deployment": {"max_seq_len": 128, "n_slots": 3}}[block]


@pytest.fixture(scope="module")
def checked(program):
    srv = build_serving_engine(_spec(program, **_chunks()))
    serve_chunked_kinds.probe_as(srv)
    return srv


def test_the_cells_check_holds_a_sound_engine_and_names_a_wrong_probe(checked, program):
    """The driver's own check on a float32 engine under float32's limits: the engine's
    chunks are logged (the unfetched ones too), the probe takes the engine's cut, and both
    agree with the reference; the probe with the window off by one does not."""
    with serve_chunked_kinds.as_this_cell(LOGIT_TOL=TOL, ROUTING_TOL=1e-3):
        out = serve_latent._check(_Run(program, 7), checked, Request)
        assert out["ok"] and out["logit_max_abs_err"] <= TOL, out
        assert out["engine_and_probe_chose_alike"]
        assert [serve_chunked_kinds.tail_width(checked, n) for n in (118, 90, 33)] == [32, 32, 8]
        with planted("the window off by one"), \
                mock.patch.object(serve_latent, "WARM_UID", serve.WARM_UID + 1000):
            bad = serve_latent._check(_Run(program, 8), checked, Request)
        assert not bad["ok"] and bad["logit_max_abs_err"] > 10 * TOL


def test_a_fault_in_the_engines_own_chunk_programs_fails_the_check(program):
    """The fault in the TIMED engine's chunk programs ALONE: they are traced where first
    called, inside the plant; the probe, traced behind it, is sound and passes its own
    limit. The engine's tokens and choices are held to the reference too, and fail."""
    srv = build_serving_engine(_spec(program, **_chunks()))
    serve_chunked_kinds.probe_as(srv)
    rng = np.random.default_rng(5)
    with planted("a ring that a chunk overwrote before its queries read it"):
        srv.serve([Request(uid=900 + i, max_new_tokens=2,
                           prompt=rng.integers(0, program["vocab_size"], size=n).astype(np.int32))
                   for i, n in enumerate((118, 90, 33))])
    with serve_chunked_kinds.as_this_cell(LOGIT_TOL=TOL, ROUTING_TOL=1e-3):
        bad = serve_latent._check(_Run(program, 9), srv, Request)
    assert not bad["ok"] and bad["logit_max_abs_err"] <= TOL  # the probe is sound
    assert bad["routing_slack"] > 1e-2 and bad["probe_routing_slack"] <= 1e-3, bad


def test_cell_rehearsal_lists_its_metrics():
    out = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", f"{CONFIG}.serve-repoctx",
         "--rehearse", "--trace", "1"], cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] == "passed" and last["correct"] and last["failed"] == 0
    for name in ("chunk_prefill_mfu_pct", "chunk_share_of_loop_pct", "decode_gap_ms_p99",
                 "kinds_decode_hbm_floor_pct", "slot_cache_bytes_per_slot"):
        assert name in last["would_report"], last["would_report"]
