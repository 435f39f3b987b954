"""Falcon-H1-34B's twin behind the serving engine (split from ``test_falcon_h1.py``, PR 47):
the engine's tokens and what it holds, chunked prefill, what it refuses, the chip's check
and what it must catch, the prefill reader, and the cell's rehearsal."""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from falcon_h1_cases import (  # noqa: F401,I001 -- first: puts the repo's root on sys.path; fixtures
    ROOT, WHOLE, TOL, SMAX, program, reference, cfg, params)

from chipbench import ssm_cost  # noqa: E402
from chipbench.drivers import serve, serve_recurrent  # noqa: E402
from chipbench.layer_metrics import recurrent_state_bytes_per_slot, ssm_prefill_mfu_pct  # noqa: E402
from deepspeed_tpu.inference import serving  # noqa: E402
from deepspeed_tpu.launcher.serving_worker import build_serving_engine  # noqa: E402
from deepspeed_tpu.models import transformer as tfm  # noqa: E402
from deepspeed_tpu.telemetry import tracing  # noqa: E402


CELL = "falcon-h1-34b-L4.serve-shortchat"


# -- the engine: the same entry points, scheduler, slot cache and sampler ------------------------


def _engine(program, dtype, **serving_block):
    return build_serving_engine({
        "model": {**program, "dtype": dtype},
        "engine_dtype": {"float32": "fp32", "bfloat16": "bf16"}[dtype],
        "serving": {"n_slots": 3, "max_seq_len": SMAX, **serving_block}})


def _served_against_reference(srv, program, reference, lens, new=5):
    """The engine's greedy tokens lie on the reference's top logit at every step."""
    rng = np.random.default_rng(8)
    reqs = [serving.Request(uid=i, prompt=rng.integers(0, program["vocab_size"], size=n).astype(
        np.int32), max_new_tokens=new) for i, n in enumerate(lens)]
    results = srv.serve(reqs)
    params = srv.engine.params
    for r in reqs:
        got = np.asarray(results[r.uid].tokens, np.int32)
        assert results[r.uid].status == "ok" and len(got) == new
        ref = reference.logits_at(program, params, np.concatenate([r.prompt, got[:-1]]),
                                  np.arange(len(r.prompt) - 1, len(r.prompt) + new - 1),
                                  fetch=WHOLE)
        assert float(np.max(ref.max(axis=-1) - ref[np.arange(new), got])) < TOL, len(r.prompt)


def test_the_serving_engine_serves_it_and_says_what_it_holds(program, reference):
    """More requests than slots (every slot is reused), through ``ServingEngine``
    and ``SlotWorker`` as any model; the spans and the HBM ledger say what the
    recurrent state costs."""
    srv = _engine(program, "float32")
    t0 = time.perf_counter()
    _served_against_reference(srv, program, reference, [1, 3, 40, 97, 128, 129, 200])
    assert srv.compile_counts()["decode"] == 1
    per_slot = 3 * (4 * 16 * 32 * 4 + 3 * 192 * 4)  # layers x (float32 state + float32 tail)
    pools = srv.worker.hbm_pools()
    assert pools["slot_state"] == 3 * per_slot and srv.worker.state_bytes_per_slot == per_slot
    assert pools["slot_kv_cache"] == 3 * 3 * SMAX * tfm.cache_bytes_per_token(srv.engine.cfg)
    assert tfm.cache_bytes_per_token(srv.engine.cfg) == 2 * 2 * 16 * 4  # 2 K/V heads, not 4
    assert recurrent_state_bytes_per_slot.read({"worker": srv.worker}) == per_slot
    spans = tracing.spans(t0)
    decodes = [sp for sp in spans if sp.name == "decode"]
    assert decodes and all(sp.attrs["state_rows"] == sp.attrs["n_active"] for sp in decodes)
    assert all(sp.attrs["state_bytes"] == 2 * sp.attrs["state_rows"] * per_slot
               for sp in decodes)
    prefills = {sp.attrs["true_len"]: sp.attrs for sp in spans if sp.name == "prefill"}
    assert prefills[200]["scan_chunks"] == 2
    assert prefills[200]["state_rows"] == 200 and prefills[40]["scan_chunks"] == 1
    # a model without the mixer says nothing of a state
    plain = _engine({"vocab_size": 64, "num_layers": 1, "num_heads": 2, "hidden_size": 16,
                     "max_seq_len": SMAX, "decode_attn": "xla"}, "float32")
    assert "slot_state" not in plain.worker.hbm_pools()
    assert recurrent_state_bytes_per_slot.read({"worker": plain.worker}) is None


def test_chunked_prefill_carries_the_state(program, reference):
    """Chunks of 128 (the slot's state sliced out, advanced from what the last
    chunk left, written back; decode steps of other rows in between) serve what
    the one-shot prefill serves: the reference's tokens."""
    srv = _engine(program, "float32", chunked_prefill={"enabled": True, "chunk_size": 128})
    _served_against_reference(srv, program, reference, [300, 97, 129, 260, 2])
    assert srv.compile_counts()["chunk_prefill"]


_ENGINE_REFUSED = {
    "prefix cache": (dict(prefix_cache={"enabled": True, "n_slots": 2}), "prefix_cache"),
    "speculation": (dict(speculation={"enabled": True}), "speculation"),
    "prefill role": (dict(role="prefill"), "serving role 'prefill'"),
    "decode role": (dict(role="decode"), "serving role 'decode'"),
}


@pytest.mark.parametrize("case", list(_ENGINE_REFUSED))
def test_what_moves_the_cache_by_position_is_refused_at_engine_build(program, case):
    block, named = _ENGINE_REFUSED[case]
    with pytest.raises(NotImplementedError, match=named):
        _engine(program, "float32", **block)


def test_kv_export_and_import_refuse_the_recurrent_state(program):
    srv = _engine(program, "float32")
    with pytest.raises(NotImplementedError, match="kv_export"):
        srv.worker.kv_export(16, 0, 0)
    with pytest.raises(NotImplementedError, match="kv_import"):
        srv.worker.kv_import(16, None, None, 0, 0)


# -- the chip's check: small for bfloat16 compute, large for what it must catch ------------------


class _Run:
    """What ``serve_recurrent._check`` reads of the harness's run."""

    cell = {"serving": {}}

    def __init__(self, program, seed):
        self.program, self.seed = program, seed

    def sized(self, block):
        return {"deployment": {"max_seq_len": SMAX, "n_slots": 4}}[block]


def _check(program, served_program=None, dtype="bfloat16"):
    """The driver's check: an engine built from ``served_program`` (the program
    itself unless a fault is planted) judged against the reference of ``program``."""
    srv = build_serving_engine({
        "model": {**(served_program or program), "dtype": dtype},
        "engine_dtype": {"float32": "fp32", "bfloat16": "bf16"}[dtype],
        "serving": {"n_slots": 4, "max_seq_len": SMAX, "seed": 1}})
    return serve_recurrent._check(_Run(program, 7), srv, serving.Request)


def test_the_chips_check_passes_bfloat16_compute(program):
    out = _check(program)
    assert out["ok"], out
    assert out["check_buckets"] == [64, 128, 256, SMAX]
    assert serve_recurrent.LOGIT_STD[0] < out["reference_logit_std"] < serve_recurrent.LOGIT_STD[1]


@pytest.mark.parametrize("left_out", list(tfm.MULTIPLIERS))
def test_a_multiplier_left_out_of_the_program_fails_the_chips_check(program, left_out):
    """One multiplier at a time dropped from the SERVED program (the weights and
    the reference keep it): the seeded draw makes its factor show in the logits,
    over the serving check's tolerance, in float32 and so in any precision."""
    kept = {k: v for k, v in program["multipliers"].items() if k != left_out}
    out = _check(program, {**program, "multipliers": kept}, dtype="float32")
    assert not out["ok"]
    assert out["logit_max_abs_err"] > 2 * serve.LOGIT_TOL, out


def _float8_weights(leaves):
    """A ``fetch`` that rounds every matrix to float8 (e4m3: three bits of
    mantissa), scaled so its largest entry sits at the format's largest, as an
    8-bit deployment holds its weights; vectors (norm scales, the mixer's
    per-head values) stay as they are. The activations stay float32: the least an
    8-bit computation would lose."""
    def rounded(x):
        x = jnp.asarray(x, jnp.float32)
        if x.ndim < 2:
            return x
        scale = float(jnp.finfo(jnp.float8_e4m3fn).max) / jnp.max(jnp.abs(x))
        return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    return {k: rounded(v) for k, v in leaves.items()}


def test_the_reference_in_float8_fails_the_chips_check(program, reference, cfg):
    """The nearest precision below the configuration's bfloat16: the reference
    itself with float8 weights, judged as a probe's logits are (the tokens are
    the float32 reference's own greedy ones, so their gap is 0 and the logit
    error alone decides). ``LOGIT_TOL`` lies between bfloat16 compute (passes,
    above) and this."""
    weights = tfm.init(cfg, jax.random.PRNGKey(1))  # the seeded draw, as the cell serves it
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32) for n in (40, 97, 200)]
    steps = serve.DECODE_STEPS
    seqs = [p for p in prompts]
    for _ in range(steps + 1):  # the float32 reference's greedy continuation
        nxt = reference.logits_of(program, weights, seqs, [[len(q) - 1] for q in seqs],
                                  fetch=WHOLE)
        seqs = [np.append(q, np.argmax(x[0])).astype(np.int32) for q, x in zip(seqs, nxt)]
    got = [q[len(p):] for q, p in zip(seqs, prompts)]
    rows = [np.arange(len(p) - 1, len(p) + steps) for p in prompts]
    probe = reference.logits_of(program, weights, [q[:len(p) + steps] for q, p in zip(seqs, prompts)],
                                rows, fetch=_float8_weights)
    out = serve_recurrent.judge(reference, program, weights, prompts, got, probe)
    assert not out["ok"], out
    assert out["token_gap_to_reference_top"] <= serve.LOGIT_TOL  # not by the tokens
    assert serve_recurrent.LOGIT_STD[0] < out["reference_logit_std"] < serve_recurrent.LOGIT_STD[1]
    assert out["logit_max_abs_err"] > 1.5 * serve.LOGIT_TOL, out


def test_prefill_mfu_is_the_windows_operations_over_its_prefill_time(monkeypatch, program):
    """Two prefills of different buckets on a hand-made ring: the share is the sum
    of their operations over the sum of their durations, not a median of shares
    (which would sit on one bucket or the other)."""
    from types import SimpleNamespace

    def call(id, bucket, t0, t1, **attrs):
        sp = lambda i, parent, name, a, b, **kw: SimpleNamespace(  # noqa: E731
            id=i, parent=parent, name=name, path="serve/step/admit/" + name, t0=a, t1=b, attrs=kw)
        return [sp(id, None, "prefill", t0, t1, bucket=bucket, compiled=False, **attrs),
                sp(id + 1, id, "prefill/dispatch", t0, t0 + 1e-3),
                sp(id + 2, id, "prefill/fetch", t0 + 1e-3, t1)]

    ring = (call(1, 64, 100.0, 100.010, scan_chunks=1, state_rows=40)
            + call(4, 1024, 100.020, 100.060, scan_chunks=8, state_rows=900)
            + call(7, 256, 100.070, 100.080))  # a program without the scan: not counted
    for sp in ring:
        sp.name = sp.name.rsplit("/", 1)[-1]
    monkeypatch.setattr(tracing, "spans", lambda since=float("-inf"): [
        sp for sp in ring if sp.t0 >= since])
    ctx = {"serve": {"window": (0.0, 1.0), "epoch": 100.0}, "program": program,
           "peak": {"bf16_flops_per_s": 1e12}}
    want = 100.0 * (ssm_cost.prefill_flops(program, 64)
                    + ssm_cost.prefill_flops(program, 1024)) / 1e12 / 0.050
    np.testing.assert_allclose(ssm_prefill_mfu_pct.read(ctx), want, rtol=1e-9)
    assert ssm_prefill_mfu_pct.read({**ctx, "program": {"vocab_size": 64}}) is None


def test_the_cell_rehearses_on_the_recurrent_twin():
    """``chipbench.run --rehearse --trace 1`` of the cell as committed, in a
    process of its own as the command line runs it (behind this file's other
    tests the profiler's stop outlasts a rehearsal's window): the driver takes
    the twin WITH the mixer (``serve_recurrent.run``), so the rehearsal drives
    the state path, the traffic and the readers. Every metric the cell lists is
    one a run would report."""
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELL, "--rehearse", "--trace", "1"],
        cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True,
        timeout=900)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    lines = [json.loads(x) for x in proc.stdout.strip().splitlines() if x.startswith("{")]
    last = lines[-1]
    assert last["correct"] and last["failed"] == 0 and last["attempted"] > 0, lines[-2:]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"] for m in json.load(f)["per_layer"] if CELL in m.get("workloads", [])}
    assert {"ssm_decode_hbm_floor_pct", "ssm_prefill_mfu_pct",
            "recurrent_state_bytes_per_slot"} <= listed
    # no memory_stats on the CPU, and its trace names no program (``jit_decode/...``), so the
    # device's time a run and what a call costs beyond it come from a chip run alone
    assert listed == set(last["would_report"]) | {
        "hbm_peak_gb.doc", "decode_device_ms_mean", "prefill_device_ms_mean",
        "decode_call_overhead_ms", "prefill_call_overhead_ms"}
