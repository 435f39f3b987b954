"""Qwen3-Next's delta twin behind the serving engine (PR 52): the chip's check and what
it must catch, the engine's tokens against the reference, spans and pools, chunked
prefill from a carried state, what the engine refuses at build, the readers, and the
cell's rehearsal."""

import json
import subprocess
import sys
import time

import numpy as np
import pytest

from qwen3_next_cases import (  # noqa: F401,I001 -- first: puts the repo's root on sys.path; fixtures
    ROOT, WHOLE, TOL, CONFIG, _config, program, reference, cfg, params, _tokens, _PLANTED,
    _plant)

from chipbench import delta_cost  # noqa: E402
from chipbench.drivers import serve_delta, serve_latent  # noqa: E402
from chipbench.layer_metrics import (  # noqa: E402
    conv_decode_hbm_floor_pct, delta_decode_hbm_floor_pct, delta_prefill_mfu_pct,
    kinds_flash_roofline_pct, kv_bytes_per_token_model, recurrent_state_bytes_per_slot)
from chipbench.references import program_of  # noqa: E402
from deepspeed_tpu.inference.serving import Request  # noqa: E402
from deepspeed_tpu.launcher.serving_worker import build_serving_engine  # noqa: E402
from deepspeed_tpu.models import transformer as tfm  # noqa: E402
from deepspeed_tpu.telemetry import tracing  # noqa: E402


# -- the chip's check: small for float32 compute, large for what it must catch --------------------


class _Run:
    """What ``serve_latent._check`` reads of the harness's run."""

    cell = {"serving": {}}

    def __init__(self, program, seed):
        self.program, self.seed = program, seed

    def sized(self, block):
        return {"deployment": {"max_seq_len": 256, "n_slots": 4}}[block]


def _check(program, dtype="bfloat16", seed=7):
    """The driver's check (``serve_delta.run``'s prompts, limits and probe in
    ``serve_latent._check``) on an engine built as the cell builds it."""
    srv = build_serving_engine({
        "model": {**program, "dtype": dtype},
        "engine_dtype": {"float32": "fp32", "bfloat16": "bf16"}[dtype],
        "serving": {"n_slots": 4, "max_seq_len": 256, "seed": 1, "watchdog_mode": "off"}})
    with serve_delta.as_this_cell():
        return serve_latent._check(_Run(program, seed), srv, Request)


def test_the_chips_check_passes_float32_compute_by_far(program):
    out = _check(program, "float32")
    assert out["ok"] and out["check_buckets"] == [128, 256, 256, 256], out
    assert out["logit_max_abs_err"] < 2e-3 and out["routing_slack"] < 1e-2
    assert (out["logit_tol"], out["routing_tol"]) == (serve_delta.LOGIT_TOL,
                                                      serve_delta.ROUTING_TOL)


@pytest.mark.parametrize("fault", ["beta skipped", "the attention gate dropped",
                                   "the state taken from the padding"])
def test_a_planted_fault_fails_the_chips_check(program, monkeypatch, fault):
    """By ``serve_latent``'s two-part rule at the cell's own limits, in float32 (and so
    in any precision): the engine serves the faulty program, the probe runs it too,
    and the reference keeps the architecture. One fault of the rule, one of attention
    and one of the cache (``tests/test_qwen3_next.py`` plants all six against the
    float32 tolerance; an engine and three reference passes a case here)."""
    _plant(monkeypatch, fault)
    out = _check(program, "float32")
    assert not out["ok"], out
    assert max(out["logit_max_abs_err"], out["token_gap_to_reference_top"]) \
        > 1.5 * serve_delta.LOGIT_TOL or out["routing_slack"] > 1.5 * serve_delta.ROUTING_TOL, out


def _spec(program, dtype="float32", **serving):
    return {"model": {**program, "dtype": dtype},
            "engine_dtype": {"float32": "fp32", "bfloat16": "bf16"}[dtype],
            "serving": {"n_slots": 3, "max_seq_len": 256, "seed": 0, "watchdog_mode": "off",
                        **serving}}


@pytest.mark.parametrize("what,block", [
    ("prefix_cache", {"prefix_cache": {"enabled": True, "n_slots": 2}}),
    ("speculation", {"speculation": {"enabled": True}}),
    ("serving role 'prefill'", {"role": "prefill"}),
    ("serving role 'decode'", {"role": "decode"}),
])
def test_the_engine_refuses_at_build_what_moves_the_cache_by_position(program, what, block):
    with pytest.raises(NotImplementedError, match=what):
        build_serving_engine(_spec(program, **block))


# -- the serving engine ----------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served(program):
    srv = build_serving_engine(_spec(program))
    cfg = srv.engine.cfg
    # five requests, three slots: prompts that pad their bucket (130 of 256, 70 of 128), of one
    # row and of two (fewer than the filter's tail), requests of different lengths side by side
    prompts = [_tokens(cfg, (n,), n) for n in (130, 1, 70, 2, 9)]
    t0 = time.perf_counter()
    results = srv.serve([Request(uid=i, prompt=p, max_new_tokens=12)
                         for i, p in enumerate(prompts)])
    return srv, prompts, results, tracing.spans(t0)


def test_serving_engine_serves_the_references_tokens(served, program, reference):
    """Through ``build_serving_engine`` / ``ServingEngine.step`` / ``SlotWorker`` like
    any other model: five requests share three slots (two are reused, by a shorter
    and by a longer request); every token lies at the REFERENCE's top logit (its full
    forward pass, the recurrence token by token from nothing) within tolerance."""
    srv, prompts, results, _ = served
    params = srv.engine.params
    for i, p in enumerate(prompts):
        got = np.asarray(results[i].tokens)
        assert results[i].status == "ok" and len(got) == 12
        ref = reference.logits_at(program, params, np.concatenate([p, got[:-1]]),
                                  np.arange(len(p) - 1, len(p) + 11), fetch=WHOLE)
        gap = ref.max(axis=-1) - ref[np.arange(12), got]
        assert gap.max() <= TOL, (i, gap)
    assert srv.compile_counts()["decode"] == 1


def test_spans_and_pools_say_what_was_kept_and_read(served):
    srv, prompts, _, spans = served
    w, cfg = srv.worker, srv.engine.cfg
    per_slot = 6 * (4 * 16 * 16 * 4 + 3 * 128 * 4)  # delta layers x (the matrix + the tail), float32
    pools = w.hbm_pools()
    assert pools["slot_state"] == 3 * per_slot and w.state_bytes_per_slot == per_slot
    assert w.state_layers == 6 and "slot_kv_ring" not in pools
    assert pools["slot_kv_cache"] == 2 * 3 * 256 * tfm.cache_bytes_per_token(cfg)  # TWO layers'
    assert srv.telemetry.gauge("serving/slot_state_bytes").value == 3 * per_slot
    twin = program_of(_config(), serve_delta.TWIN)
    ctx = {"worker": w, "program": twin}
    assert recurrent_state_bytes_per_slot.read(ctx) == per_slot
    assert kv_bytes_per_token_model.read(ctx) == 2 * tfm.cache_bytes_per_token(cfg)
    prefills = [sp for sp in spans if sp.name == "prefill"]
    decodes = [sp for sp in spans if sp.name == "decode"]
    assert len(prefills) == 5 and decodes
    for sp in prefills + decodes:
        assert sp.attrs["delta_layers"] == 6 and sp.attrs["attn_layers"] == 2
        assert "conv_layers" not in sp.attrs and sp.attrs["attn"] == "dense"
    # a decode call fetches the step BEFORE it (PR 60): what comes with a fetch is on all but a burst's first
    fetched = [sp for sp in decodes if sp.attrs["d2h"]]
    assert len(fetched) > len(decodes) / 2
    for sp in prefills + fetched:
        assert 0 < sp.attrs["experts_touched"] <= 4  # of 4 held
        assert sp.attrs["expert_load_max_over_mean"] >= 1 and sp.attrs["experts_held"] == 4
    for sp in decodes:
        assert sp.attrs["state_rows"] == sp.attrs["n_active"]
        assert sp.attrs["state_bytes"] == 2 * sp.attrs["n_active"] * per_slot
    by_len = {sp.attrs["true_len"]: sp.attrs for sp in prefills}
    assert sorted(by_len) == [1, 2, 9, 70, 130]
    assert all(a["state_rows"] == n and a["state_bytes"] == per_slot and
               a["scan_chunks"] == -(-a["bucket"] // 64) and "expert_rows_held" in a
               and a["delta_block"] == "xla"  # the CPU, 16-wide heads: not the kernel's (PR 55)
               for n, a in by_len.items())
    assert by_len[130]["scan_chunks"] == 4 and by_len[9]["scan_chunks"] == 1
    with pytest.raises(NotImplementedError, match="kv_export"):
        w.kv_export(16, 0, 0)
    with pytest.raises(NotImplementedError, match="kv_import"):
        w.kv_import(16, None, None, 0, 0)


def test_chunked_prefill_carries_the_state(program, reference):
    """Chunks of 32 rows (half a chunk of the rule: every other one enters mid-way) through the ``chunk``
    programs: each starts from the matrix and the tail the last one left (from nothing
    at position 0, whatever the slot held) and moves them on its live rows only: the
    block form from a state given. The tokens are the reference's."""
    srv = build_serving_engine(_spec(program, chunked_prefill={"enabled": True, "chunk_size": 32}))
    rng = np.random.default_rng(8)
    reqs = [Request(uid=i, prompt=rng.integers(0, program["vocab_size"], size=n).astype(np.int32),
                    max_new_tokens=5) for i, n in enumerate([100, 17, 49, 2, 150])]
    results = srv.serve(reqs)
    assert srv.compile_counts()["chunk_prefill"]
    for r in reqs:
        got = np.asarray(results[r.uid].tokens, np.int32)
        assert results[r.uid].status == "ok" and len(got) == 5
        ref = reference.logits_at(program, srv.engine.params, np.concatenate([r.prompt, got[:-1]]),
                                  np.arange(len(r.prompt) - 1, len(r.prompt) + 4), fetch=WHOLE)
        assert float(np.max(ref.max(axis=-1) - ref[np.arange(5), got])) < TOL, len(r.prompt)


# -- the readers -----------------------------------------------------------------------------------


def test_the_readers_count_by_operator(monkeypatch):
    """Two prefills and three decode steps on a hand-made ring at the published widths:
    the floor counts K/V in the TWO attention layers and the state moved, the MFU the
    LIVE rows (attention in two layers, the rule at its recurrent cost in six); spans
    without the operator's attributes give nothing, and neither reader can pass 100%:
    what they count is under what the chip's peaks allow in the spans' own time."""
    from types import SimpleNamespace

    program = program_of(_config())

    def call(i, name, t0, t1, **attrs):
        sp = lambda j, parent, n, a, b, **kw: SimpleNamespace(  # noqa: E731
            id=j, parent=parent, name=n, path="serve/step/" + n, t0=a, t1=b, attrs=kw)
        return [sp(i, None, name, t0, t1, compiled=False, **attrs),
                sp(i + 1, i, "dispatch", t0, t0 + 1e-4), sp(i + 2, i, "fetch", t0 + 1e-4, t1)]

    ops = dict(delta_layers=6, attn_layers=2)
    state = 2 * 64 * 12_877_824
    step = dict(cached_tokens=64 * 5500, state_bytes=state, state_rows=64, experts_touched=64.0, **ops)
    ring = (call(1, "prefill", 100.0, 100.080, bucket=4096, state_rows=3500, scan_chunks=64, **ops)
            + call(4, "prefill", 100.1, 100.25, bucket=8192, state_rows=7000, scan_chunks=128, **ops)
            + call(7, "prefill", 100.3, 100.31, bucket=512)  # a program without the operator
            + call(10, "decode", 100.40, 100.415, **step) + call(13, "decode", 100.42, 100.435, **step)
            + call(16, "decode", 100.44, 100.45, cached_tokens=5))
    monkeypatch.setattr(tracing, "spans", lambda since=float("-inf"): [
        sp for sp in ring if sp.t1 >= since])
    notes = []
    ctx = {"serve": {"epoch": 0.0, "window": (99.0, 101.0)}, "program": program,
           "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "run": SimpleNamespace(note=lambda **kw: notes.append(kw))}
    flops = delta_cost.prefill_flops(program, 3500) + delta_cost.prefill_flops(program, 7000)
    assert delta_prefill_mfu_pct.read(ctx) == pytest.approx(100 * flops / 197e12 / 0.230)
    need = delta_cost.decode_min_bytes(program, 64 * 5500, state, 64.0)
    assert delta_decode_hbm_floor_pct.read(ctx) == pytest.approx(100 * need / 819e9 / 0.015)
    assert delta_prefill_mfu_pct.read(ctx) < 100 and delta_decode_hbm_floor_pct.read(ctx) < 100
    assert {n["program"] for n in notes} == {"prefill", "decode"}
    plain = {**ctx, "program": program_of(_config(), "rehearse_program")}
    assert delta_prefill_mfu_pct.read(plain) is None and delta_decode_hbm_floor_pct.read(plain) is None
    # the accepted readers the cell is left out of return nothing for this program, as they stand
    assert conv_decode_hbm_floor_pct.read(ctx) is None
    assert kinds_flash_roofline_pct.read({**ctx, "trace": {"ops": []}}) is None


def test_the_cell_file_is_kananas_traffic_on_more_slots():
    with open(f"{ROOT}/chipbench/workloads/{CONFIG}.serve-longdoc.json") as f:
        cell = json.load(f)
    with open(f"{ROOT}/chipbench/workloads/kanana-2-30b-a3b-L7.serve-longdoc.json") as f:
        kanana = json.load(f)
    assert cell["deployment"]["n_slots"] == 64 and cell["deployment"]["max_seq_len"] == 8192
    assert cell["driver"] == "serve_delta" and cell["chips"] == 1
    for key in ("kind", "clients_per_slot", "prompt", "output", "max_total", "lead_in_s"):
        assert cell["traffic"][key] == kanana["traffic"][key], key
    assert cell["traffic"]["grace_s"] >= 60 and cell["serving"] == kanana["serving"]
    assert cell["trace"] == kanana["trace"]


# -- the cell's rehearsal ---------------------------------------------------------------------------


def test_the_cells_rehearsal_passes_and_lists_its_metrics():
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", f"{CONFIG}.serve-longdoc",
         "--rehearse", "--trace", "1"], cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] == "passed" and last["correct"] and last["failed"] == 0
    for name in ("kv_bytes_per_token_model", "delta_decode_hbm_floor_pct", "delta_prefill_mfu_pct",
                 "recurrent_state_bytes_per_slot", "moe_load_max_over_mean",
                 "compiles_in_window.doc", "decode_host_transfers"):
        assert name in last["would_report"], last["would_report"]
