"""SDAR-30B-A3B-Chat's twin behind the serving engine: requests of several lengths in slots
at different passes receive, a block at a time, the tokens of the reference's ``generate``;
the static schedule runs a step ahead of the fetch and the dynamic one fetches first, with
the same operands and tokens; an EOS inside a block; sampled requests under the
reference's eyes; a NaN in one slot's block; what a block step's span says; what is refused
by name; the cell's own check (``drivers/serve_blocks.py``) on a float32 engine, sound and
with each fault planted; the cell's rehearsal."""

import json
import subprocess
import sys
import time
from unittest import mock

import numpy as np
import pytest

from sdar_cases import (  # noqa: F401,I001 -- first: puts the repo's root on sys.path; fixtures
    ROOT, WHOLE, TOL, BLOCK, CONFIG, program, reference, cfg, params, _tokens,
    causal_inside_a_block, commit_skipped, written_one_off)

from chipbench import block_cost  # noqa: E402
from chipbench.drivers import serve, serve_blocks  # noqa: E402
from deepspeed_tpu.inference.serving import Request  # noqa: E402
from deepspeed_tpu.launcher.serving_worker import build_serving_engine  # noqa: E402
from deepspeed_tpu.telemetry import tracing  # noqa: E402

FETCH_FIRST = {"fault_injection": {"enabled": True}}  # armed, nothing listed, rate 0
# (prompt length, max_new_tokens): P mod B = 1, 0, 2, 3 (under a block); four on three slots
LENS = [(17, 11), (8, 6), (30, 9), (3, 5)]


def _spec(program, **serving):
    return {"model": {**program, "dtype": "float32"}, "engine_dtype": "fp32",
            "serving": {"n_slots": 3, "max_seq_len": 128, "seed": 0, "watchdog_mode": "raise",
                        "min_prefill_bucket": 8, **serving}}


def _blocks(steps=4, strategy="low_confidence_static", threshold=0.02):
    return {"block_generation": {"denoising_steps": steps, "strategy": strategy,
                                 "threshold": threshold}}


def _requests(program, lens=LENS, seed=5, **fields):
    rng = np.random.default_rng(seed)
    return [Request(uid=i, prompt=rng.integers(0, program["vocab_size"], size=n).astype(np.int32),
                    max_new_tokens=m, **fields) for i, (n, m) in enumerate(lens)]


@pytest.fixture(scope="module")
def checked(program):
    return build_serving_engine(_spec(program, **_blocks()))


@pytest.mark.parametrize("steps,strategy", [
    (4, "low_confidence_static"), (2, "low_confidence_static"), (4, "low_confidence_dynamic")])
def test_every_request_receives_generates_tokens(program, reference, steps, strategy):
    """Four requests on three slots: slots at different passes, admissions while others are
    mid-block, ``max_new_tokens`` that end inside a block, a prompt shorter than a block."""
    srv = build_serving_engine(_spec(program, **_blocks(steps, strategy)))
    reqs = _requests(program)
    results = srv.serve(reqs)
    for r in reqs:
        want = reference.generate(program, srv.engine.params, r.prompt, r.max_new_tokens,
                                  fetch=WHOLE, denoising_steps=steps, strategy=strategy,
                                  threshold=0.02)
        assert results[r.uid].status == "ok"
        np.testing.assert_array_equal(results[r.uid].tokens, want["tokens"])
        assert results[r.uid].first_token_time > results[r.uid].admitted_time
    counts = srv.compile_counts()
    assert counts["block_step"] == 1 and counts["decode"] == 0
    static = strategy == "low_confidence_static"
    assert (counts["block_steps_ahead"] > 0.8 * counts["block_steps"]) == static
    assert static or counts["block_steps_ahead"] == 0


def test_enqueue_ahead_and_fetch_first_hand_over_the_same_operands(program):
    """As many requests as slots, so that both orders run the same steps: every block step's
    rows (``pos``, ``active``, ``opened``, ``count``) and what it left (``toks``, ``mask``)
    are the same to the bit, and so are the tokens."""
    logs, tokens = [], []
    for extra in ({}, FETCH_FIRST):
        srv = build_serving_engine(_spec(program, **_blocks(), **extra))
        srv.worker.block_log = []
        reqs = _requests(program, LENS[:3])
        for r in reqs:
            srv.submit(r)
        res = srv.drain()
        logs.append(srv.worker.block_log)
        tokens.append([res[r.uid].tokens for r in reqs])
        assert (srv.compile_counts()["block_steps_ahead"] > 0) == (not extra)
    assert len(logs[0]) == len(logs[1]) > 10
    for a, b in zip(*logs):
        assert a.keys() == b.keys()
        for key in a:
            np.testing.assert_array_equal(a[key], b[key], err_msg=key)
    for a, b in zip(*tokens):
        np.testing.assert_array_equal(a, b)


def test_an_eos_inside_a_block_ends_the_request_there(program, reference, checked):
    srv = checked
    req = _requests(program, [(13, 14)])[0]
    free = reference.generate(program, srv.engine.params, req.prompt, 14, fetch=WHOLE,
                              denoising_steps=4)["tokens"]
    eos = int(free[5])  # inside the second generated block
    cut = int(np.flatnonzero(free == eos)[0]) + 1
    got = srv.serve([Request(uid=707, prompt=req.prompt, max_new_tokens=14, eos_token=eos),
                     Request(uid=708, prompt=req.prompt, max_new_tokens=14)])
    np.testing.assert_array_equal(got[707].tokens, free[:cut])
    np.testing.assert_array_equal(got[708].tokens, free)
    want = reference.generate(program, srv.engine.params, req.prompt, 14, fetch=WHOLE,
                              denoising_steps=4, eos=eos)["tokens"]
    np.testing.assert_array_equal(got[707].tokens, want)


def test_sampled_requests_reveal_what_the_reference_would(program, reference, checked):
    """Temperature and top-k: the draws are the engine's own, so the reference is teacher
    forced with them (``generate(reveals=...)``): every token revealed lies among the
    reference's top-k at its row, and the row revealed is the reference's most confident
    masked row for that token."""
    srv = checked
    srv.worker.block_log, srv.worker.routing_log = [], []
    reqs = [Request(uid=800 + r.uid, prompt=r.prompt, max_new_tokens=r.max_new_tokens,
                    temperature=0.9, top_k=5) for r in _requests(program, [(17, 10), (12, 7)])]
    try:
        results = srv.serve(reqs)
        logs = srv.worker.block_log, srv.worker.routing_log
    finally:
        srv.worker.block_log = srv.worker.routing_log = None
    played = serve_blocks.replay(*logs, [r.uid for r in reqs], [r.prompt for r in reqs], BLOCK,
                                 program["mask_token_id"])
    greedy = reference.generate(program, srv.engine.params, reqs[0].prompt, 10, fetch=WHOLE,
                                denoising_steps=4)["tokens"]
    assert not np.array_equal(results[800].tokens, greedy)
    for r, req in zip(reqs, played):
        reveals = [p["revealed"] for p in req["passes"] if not p["commit"]]
        out = reference.generate(program, srv.engine.params, r.prompt, r.max_new_tokens,
                                 fetch=WHOLE, denoising_steps=4, reveals=reveals)
        np.testing.assert_array_equal(out["tokens"], results[r.uid].tokens)
        for p in (p for p in out["passes"] if not p["commit"]):
            for pos in p["revealed"]:
                row = pos - p["start"]
                top = np.argsort(-p["logits"][row])[:5]
                assert p["sequence"][pos] == program["mask_token_id"] and p["x0"][row] in top


def test_a_nan_in_one_slots_block_quarantines_that_request_alone(program, reference):
    srv = build_serving_engine(_spec(program, **_blocks()))
    reqs = _requests(program, LENS[:3])
    for r in reqs:
        srv.submit(r)
    for _ in range(4):
        srv.step(now=float("inf"), enforce_deadlines=False)
    victim = next(s for s in range(3) if srv._active[s])
    uid = srv._slots[victim].uid
    srv.worker.fill_slot(victim, float("nan"))
    results = srv.drain()
    assert results[uid].status == "ok" and results[uid].requeues == 1
    assert [results[r.uid].requeues for r in reqs if r.uid != uid] == [0, 0]
    for r in reqs:
        want = reference.generate(program, srv.engine.params, r.prompt, r.max_new_tokens,
                                  fetch=WHOLE, denoising_steps=4)["tokens"]
        np.testing.assert_array_equal(results[r.uid].tokens, want)
    snap = srv.telemetry.registry.snapshot()["counters"]
    assert snap["resilience/nan_logit_faults"] == 1 and snap["resilience/requeues"] == 1


def test_what_a_block_steps_span_and_the_counters_say(program):
    srv = build_serving_engine(_spec(program, **_blocks()))
    t0 = time.perf_counter()
    reqs = _requests(program, [(16, 8), (20, 8)])
    srv.serve(reqs)
    spans = [sp for sp in tracing.spans(t0) if sp.name == "block_step"]
    kids = {}
    for sp in tracing.spans(t0):
        kids.setdefault(sp.parent, set()).add(sp.name)
    assert len(spans) == 9 and spans[0].attrs["compiled"]  # two blocks: 5 passes + 4 (no commit)
    for sp in spans:
        a = sp.attrs
        assert a["rows"] == 3 * BLOCK and a["slots_active"] == 2 and "dispatch" in kids[sp.id]
        assert a["expert_rows_held"] == 3 * BLOCK * program["moe_top_k"] * program["num_layers"]
        assert a["h2d"] == 11 and a["d2h"] in (0, 4) and a["attn"] == "dense"
    assert [sp.attrs["masked_rows"] for sp in spans] == [8, 6, 4, 2, 0, 8, 6, 4, 2]
    assert [sp.attrs["revealed"] for sp in spans] == [2, 2, 2, 2, 0, 2, 2, 2, 2]
    assert [sp.attrs["commits"] for sp in spans] == [0, 0, 0, 0, 2, 0, 0, 0, 0]
    assert spans[0].attrs["live_keys"] == (16 + 4) * 4 + (20 + 4) * 4
    assert spans[5].attrs["live_keys"] == (20 + 4) * 4 + (24 + 4) * 4
    assert any("expert_load_max_over_mean" in sp.attrs for sp in spans[1:])
    snap = srv.telemetry.registry.snapshot()["counters"]
    assert snap["serving/block_steps"] == 9 and snap["serving/block_commits"] == 2
    assert snap["serving/tokens_revealed"] == 16 and snap["serving/tokens_out"] == 16
    cost = block_cost.attention_cost(program, 12, spans[0].attrs["live_keys"])
    kv = 2 * program["num_kv_heads"] * program["qk_head_dim"] * 2
    assert cost["bytes"] == program["num_layers"] * (
        2 * 12 * program["num_heads"] * program["qk_head_dim"] * 2 + (20 + 24) * kv)
    assert block_cost.step_min_bytes(program, 176, 8) > block_cost.step_min_bytes(program, 176, 4)
    # the routed experts: the pairs of the ACTIVE slots' rows, the banks those rows chose
    per_expert = 3 * program["hidden_size"] * program["intermediate_size"]
    gemm = block_cost.expert_gemm_cost(program, 2 * BLOCK, 6)
    pairs = 2 * BLOCK * program["moe_top_k"]
    assert gemm["flops"] == program["num_layers"] * 2.0 * pairs * per_expert
    assert gemm["bytes"] > program["num_layers"] * 6 * per_expert * 2
    assert gemm["bytes"] - block_cost.expert_gemm_cost(program, 2 * BLOCK, 5)["bytes"] == \
        program["num_layers"] * per_expert * 2


def test_the_scheduler_readers_of_a_block_model_know_the_block_step(program):
    """``block_host_gap_pct`` and ``block_sched_host_ms_p50`` count the block step among the
    worker's calls without touching ``span_ring.WORKER_CALLS``, which the accepted readers
    read; ``block_moe_load_max_over_mean`` reads the block steps' own load."""
    from chipbench.layer_metrics import block_host_gap_pct, block_moe_load_max_over_mean, \
        block_sched_host_ms_p50, sched_host_ms_p50, span_ring

    srv = build_serving_engine(_spec(program, **_blocks()))
    srv.serve(_requests(program, [(16, 8)]))  # (the programs compile here)
    t0 = time.perf_counter()
    later = _requests(program, [(16, 12), (20, 12)], seed=6)
    for i, r in enumerate(later):
        r.uid = 10 + i
    srv.serve(later)
    t1 = time.perf_counter()
    ctx = {"serve": {"epoch": 0.0, "window": (t0, t1), "traced": (None, None)},
           "program": program, "trace": None, "run": mock.Mock()}
    assert span_ring.WORKER_CALLS == ("prefill", "chunk", "decode", "verify")
    gap = block_host_gap_pct.read(ctx)
    assert 0.0 < gap < 100.0
    note = ctx["run"].note.call_args.kwargs
    assert note["event"] == "block_host_gaps" and note["traced"] is None
    assert abs(sum(note["window"]["by_span"].values()) - note["window"]["gap_s"]) < 1e-9
    mine, theirs = block_sched_host_ms_p50.read(ctx), sched_host_ms_p50.read(ctx)
    assert 0.0 < mine < theirs  # the accepted reader charges the scheduler the block steps
    assert block_moe_load_max_over_mean.read(ctx) >= 1.0
    # a program with no block step gives each nothing to read
    none = {**ctx, "serve": {**ctx["serve"], "window": (t1 + 100.0, t1 + 101.0)}}
    for reader in (block_host_gap_pct, block_sched_host_ms_p50, block_moe_load_max_over_mean):
        assert reader.read(none) is None


def test_the_expert_operations_are_found_by_their_text(program):
    from chipbench.layer_metrics import block_expert_gemm_roofline_pct as reader

    E, d, f = program["num_experts"], program["hidden_size"], program["intermediate_size"]
    text = {
        "jit_block_step/fusion.1": f"%fusion.1 = bf16[{E},12,{f}]{{2,1,0}} fusion(bf16[2,{E},{d},{f}]{{3,2,1,0}} %w, s32[] %i)",
        "jit_block_step/fusion.2": f"%fusion.2 = bf16[12,{d}]{{1,0}} fusion(bf16[{E},12,{f}] %h, bf16[{E},{f},{d}]{{2,1,0}} %w)",
        "jit_block_step/fusion.3": f"%fusion.3 = bf16[{E},12,{f}]{{2,1,0}} fusion(bf16[{E},12,{f}] %a)",
        "jit_block_step/while.1": f"%while.1 = (bf16[2,{E},{d},{f}]{{3,2,1,0}}) while(%t)",
        "jit_prefill/fusion.1": f"%fusion.1 = bf16[8,{d}] fusion(bf16[2,{E},{d},{f}]{{3,2,1,0}} %w)",
    }
    tr = {"op_seconds": {name: 1.0 for name in text}, "op_text": text}
    assert sorted(reader.bank_ops(tr, program)) == [
        "jit_block_step/fusion.1", "jit_block_step/fusion.2"]


def test_the_score_operations_are_found_by_their_text(program):
    from chipbench.layer_metrics import block_attn_roofline_pct as reader

    h, b = program["num_heads"], BLOCK
    text = {
        "jit_block_step/fusion.1": f"%fusion.1 = bf16[3,{h * b},128]{{2,1,0}} fusion(%a)",
        "jit_block_step/fusion.2": f"%fusion.2 = f32[3,{h},{b},128]{{3,2,1,0}} fusion(%a)",
        "jit_block_step/fusion.3": f"%fusion.3 = bf16[3,{h * b},48]{{2,1,0}} fusion(%a)",
        "jit_prefill/fusion.1": f"%fusion.1 = bf16[3,{h * b},128]{{2,1,0}} fusion(%a)",
    }
    tr = {"op_seconds": {name: 1.0 for name in text}, "op_text": text}
    assert sorted(reader.score_ops(tr, program, 3, 128)) == [
        "jit_block_step/fusion.1", "jit_block_step/fusion.2"]


@pytest.mark.parametrize("what,block", [
    ("prefix_cache", {"prefix_cache": {"enabled": True, "n_slots": 2}}),
    ("chunked_prefill", {"chunked_prefill": {"enabled": True, "chunk_size": 32}}),
    ("speculation", {"speculation": {"enabled": True}}),
    ("serving role", {"role": "prefill"}),
])
def test_what_assumes_a_token_a_step_is_refused_at_build(program, what, block):
    with pytest.raises(NotImplementedError, match=f"{what}.*diffusion over blocks"):
        build_serving_engine(_spec(program, **block))


@pytest.mark.parametrize("serving,words", [
    ({"block_generation": {"denoising_steps": 5}}, "denoising_steps"),
    ({"max_seq_len": 126}, "multiples of the block length"),
    ({"min_prefill_bucket": 2}, "multiples of the block length"),
])
def test_a_schedule_or_a_slot_that_cuts_a_block_is_refused(program, serving, words):
    with pytest.raises(ValueError, match=words):
        build_serving_engine(_spec(program, **serving))


def test_the_one_token_generate_loop_refuses_a_block_model(program):
    srv = build_serving_engine(_spec(program))
    with pytest.raises(NotImplementedError, match="diffusion over blocks"):
        srv.engine.generate(np.zeros((1, 8), np.int32), 4)


def test_an_unknown_strategy_is_refused_by_the_configuration():
    from deepspeed_tpu.runtime.config import BlockGenerationConfig, DeepSpeedConfigError

    with pytest.raises(DeepSpeedConfigError, match="strategy"):
        BlockGenerationConfig(strategy="entropy")


# -- the cell's own check ------------------------------------------------------------------

class _Run:
    """What ``serve_blocks._check`` reads of the harness's ``Run``."""

    rehearse = False

    def __init__(self, program, seed):
        self.program, self.seed = program, seed

    def sized(self, block):
        return {"deployment": {"max_seq_len": 128, "n_slots": 3}}[block]


FLOAT32 = {"LOGIT_TOL": TOL, "CONF_TOL": TOL, "ROUTING_TOL": 1e-3, "REVEAL_TOL": 2e-4}
_uid = iter(range(2000, 10 ** 6, 100))


def _check(program, srv, seed=7):
    with mock.patch.object(serve_blocks, "limits_of", lambda run: FLOAT32), \
            mock.patch.object(serve_blocks, "WARM_UID", serve.WARM_UID + next(_uid)), \
            mock.patch.object(serve_blocks, "CHECK_PROMPT_LENS", (80, 43)):
        return serve_blocks._check(_Run(program, seed), srv, Request)


def test_the_cells_check_holds_a_sound_engine(checked, program):
    out = _check(program, checked)
    assert out["ok"] and out["logit_max_abs_err"] <= TOL, out
    assert out["passes_checked"] == (5 + 5 + 1) + (2 + 5 + 1)
    assert out["token_gap_to_reference_top"] <= TOL and out["reveal_gap_log_conf"] <= 2e-4
    assert out["engine_log_conf_err"] <= TOL  # the timed program's own confidences
    # the engine serves on behind the check: the probe left its cache in place
    res = checked.serve(_requests(program, [(9, 5)], seed=3)[:1])
    assert res[0].status == "ok" and len(res[0].tokens) == 5


@pytest.mark.parametrize("fault", ["skip_commit", "write_off"])
def test_a_fault_in_the_probe_fails_the_check_on_its_logits(checked, program, fault):
    real = serve_blocks.probe_passes
    kw = {"skip_commit": True} if fault == "skip_commit" else {"write_off": 1}
    with mock.patch.object(serve_blocks, "probe_passes",
                           lambda srv, reqs, prompts: real(srv, reqs, prompts, **kw)):
        bad = _check(program, checked, seed=8)
    assert not bad["ok"] and bad["logit_max_abs_err"] > 100 * TOL
    assert bad["token_gap_to_reference_top"] <= TOL  # the engine is sound


@pytest.mark.parametrize("plant", [commit_skipped, causal_inside_a_block],
                         ids=lambda f: f.__name__)
def test_a_fault_in_the_timed_engine_fails_the_check_on_its_tokens(program, plant):
    """The fault in the TIMED engine alone (its block-step program is traced where first
    called, inside the plant; the commit and the write position are its scheduler's
    operands): the probe's own steps, run behind the plant, are sound and pass their limit
    (the prompt's K/V it reads is the engine's prefill programs', so the mask planted there
    shows in it too); the engine's tokens, its reveals or its routing do not."""
    srv = build_serving_engine(_spec(program, **_blocks()))
    real = serve_blocks.served

    def served_planted(*args):
        with plant():
            return real(*args)

    with mock.patch.object(serve_blocks, "served", served_planted):
        bad = _check(program, srv, seed=9)
    assert not bad["ok"], bad
    if plant is not causal_inside_a_block:  # (whose prefill programs the probe calls too)
        assert bad["logit_max_abs_err"] <= TOL, bad
    assert (bad["token_gap_to_reference_top"] > 100 * TOL or bad["routing_slack"] > 1e-2
            or bad["reveal_gap_log_conf"] > 1e-2), bad
    assert bad["engine_log_conf_err"] > 100 * TOL, bad  # the timed program's own arithmetic


def test_a_pass_that_reveals_the_least_confident_row_fails_the_reveal_limit(program):
    """What ``REVEAL_TOL`` is for: the engine's block step ranks a block's masked rows the
    wrong way round (traced inside the plant). Every token it reveals is still its row's
    arg-max, so the token gap stays under its limit; the order does not."""
    from deepspeed_tpu.inference import serving

    srv = build_serving_engine(_spec(program, **_blocks()))
    real_served, real_rows = serve_blocks.served, serving.reveal_rows

    def served_planted(*args):
        with mock.patch.object(serving, "reveal_rows",
                               lambda conf, *rest: real_rows(-conf, *rest)):
            return real_served(*args)

    with mock.patch.object(serve_blocks, "served", served_planted):
        bad = _check(program, srv, seed=11)
    assert not bad["ok"] and bad["reveal_gap_log_conf"] > 0.05, bad
    assert bad["token_gap_to_reference_top"] <= TOL and bad["logit_max_abs_err"] <= TOL


def test_float8_expert_matrices_in_the_timed_engine_fail_on_its_own_confidences(program):
    """The nearest precision below the configuration's, IN the timed engine (its weights
    rounded to float8 e4m3 where they lie; the reference keeps what they were): the number
    read from the timed block-step program's own output, ``engine_log_conf_err``, fails its
    limit, as does the probe's (it runs on the engine's weights). On the chip, at the cell's
    size: 0.065 and 0.079 against 0.024 sound (``serve_blocks.CONF_TOL``)."""
    import jax
    import jax.numpy as jnp

    srv = build_serving_engine(_spec(program, **_blocks()))
    kept = jax.device_get(srv.engine.params)
    experts = srv.engine.params["moe"]["experts"]
    for k in list(experts):
        experts[k] = experts[k].astype(jnp.float8_e4m3fn).astype(experts[k].dtype)
    srv.worker.params = srv.engine.params
    real = serve_blocks.judge
    with mock.patch.object(serve_blocks, "judge",
                           lambda ref, prog, _, reqs, lim: real(ref, prog, kept, reqs, lim)):
        bad = _check(program, srv, seed=12)
    assert not bad["ok"], bad
    assert bad["engine_log_conf_err"] > 100 * TOL and bad["logit_max_abs_err"] > 100 * TOL, bad


def test_cell_rehearsal_lists_its_metrics():
    out = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", f"{CONFIG}.serve-blockgen",
         "--rehearse", "--trace", "1"], cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] == "passed" and last["correct"] and last["failed"] == 0
    for name in ("tokens_per_block_step", "block_commit_share_pct", "slot_occupancy_mean",
                 "block_host_gap_pct", "block_sched_host_ms_p50",
                 "block_moe_load_max_over_mean", "prefill_padding_pct"):
        assert name in last["would_report"], last["would_report"]
