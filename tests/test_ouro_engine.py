"""Ouro's tiny twin behind the serving engine (PR 56): the chip's check and what it must
catch, the engine's tokens against the reference, spans, gauges and pools of a model
with passes, and chunked prefill, the prefix cache, export / import of a slot and
n-gram speculation each serving it to the plain engine's tokens: the first
configuration in five whose cache is plain per-head K/V at ``Smax``, which is all
those four ask, however many (pass, layer)s deep it is."""

import json
import subprocess
import sys
import time
from types import SimpleNamespace

import numpy as np
import pytest

from ouro_cases import (  # noqa: F401,I001 -- first: puts the repo's root on sys.path; fixtures
    ROOT, WHOLE, TOL, CONFIG, _config, program, reference, _tokens, planted,
    judge_float8_reference)

from chipbench import loop_cost  # noqa: E402
from chipbench.drivers import serve_looped, serve_recurrent  # noqa: E402
from chipbench.layer_metrics import (  # noqa: E402
    decode_hbm_floor_pct, kv_bytes_per_token_model, loop_cache_bytes_per_token,
    loop_decode_hbm_floor_pct, loop_prefill_mfu_pct)
from chipbench.references import program_of  # noqa: E402
from deepspeed_tpu.inference import Router  # noqa: E402
from deepspeed_tpu.inference.serving import Request  # noqa: E402
from deepspeed_tpu.launcher.serving_worker import build_serving_engine  # noqa: E402
from deepspeed_tpu.models import transformer as tfm  # noqa: E402
from deepspeed_tpu.telemetry import tracing  # noqa: E402


def _spec(program, dtype="float32", **serving):
    return {"model": {**program, "dtype": dtype},
            "engine_dtype": {"float32": "fp32", "bfloat16": "bf16"}[dtype],
            "serving": {"n_slots": 3, "max_seq_len": 256, "seed": 0, "watchdog_mode": "off",
                        **serving}}


# -- the chip's check: small for float32 compute, large for what it must catch --------------------


class _Run:
    """What ``serve_recurrent._check`` reads of the harness's run."""

    cell = {"serving": {}}

    def __init__(self, program, seed):
        self.program, self.seed = program, seed

    def sized(self, block):
        return {"deployment": {"max_seq_len": 256, "n_slots": 3}}[block]


def _check(program, seed=7):
    srv = build_serving_engine(_spec(program))
    with serve_looped.as_this_cell():
        return serve_recurrent._check(_Run(program, seed), srv, Request)


def test_the_chips_check_passes_float32_compute_by_far(program):
    out = _check(program)
    assert out["ok"] and out["check_buckets"] == [128, 256, 256], out
    assert out["logit_max_abs_err"] < TOL and out["logit_tol"] == serve_looped.LOGIT_TOL
    assert len(out["logit_err_by_prompt"]) == len(serve_looped.CHECK_PROMPT_LENS) == 3


@pytest.mark.parametrize("fault", ["one pass too few", "a decode step reads the pass before"])
def test_a_planted_fault_fails_the_chips_check(program, fault):
    """At the cell's own limit, in float32 (and so in any precision): the engine serves
    the faulty program, the probe runs it too, and the reference keeps the architecture."""
    with planted(fault):
        out = _check(program)
    assert not out["ok"] and out["logit_max_abs_err"] > 5 * serve_looped.LOGIT_TOL, out


def test_the_float8_reference_fails_the_chips_check(program, reference):
    """The control of the limit from below, through the harness's own comparison: the
    REFERENCE on matrices rounded to float8 (e4m3) stands in the probe's place and
    ``judge`` holds it to the reference on the tree as it is. Not correct, and by ONE of
    the limits: the logits are too far (0.59 to 0.73 at the twin's widths, 2.5 at the
    cell's), the tokens are the sound engine's own."""
    srv = build_serving_engine(_spec(program))
    prompts = [_tokens(srv.engine.cfg, (n,), n) for n in (100, 200, 240)]
    reqs = [Request(uid=i, prompt=p, max_new_tokens=serve_recurrent.DECODE_STEPS + 1)
            for i, p in enumerate(prompts)]
    results = srv.serve(reqs)
    got = [np.asarray(results[r.uid].tokens, np.int32) for r in reqs]
    with serve_looped.as_this_cell():
        out = judge_float8_reference(reference, program, srv.engine.params, prompts, got)
    assert not out["ok"] and out["logit_max_abs_err"] > 1.25 * serve_looped.LOGIT_TOL, out
    assert out["token_gap_to_reference_top"] < TOL and out["logit_tol"] == serve_looped.LOGIT_TOL


# -- the serving engine ----------------------------------------------------------------------------


PROMPT_LENS = (130, 1, 70, 2, 9)  # pad their buckets (130 of 256, 70 of 128); five on three slots


def _requests(cfg, new=12, first_uid=0):
    return [Request(uid=first_uid + i, prompt=_tokens(cfg, (n,), n), max_new_tokens=new)
            for i, n in enumerate(PROMPT_LENS)]


@pytest.fixture(scope="module")
def served(program):
    srv = build_serving_engine(_spec(program))
    reqs = _requests(srv.engine.cfg)
    t0 = time.perf_counter()
    results = srv.serve(reqs)
    return srv, reqs, results, tracing.spans(t0)


def test_serving_engine_serves_the_references_tokens(served, program, reference):
    """Through ``build_serving_engine`` / ``ServingEngine.step`` / ``SlotWorker`` like any
    other model: five requests share three slots (two are reused, by a shorter and by a
    longer request), requests of different lengths decode side by side; every token
    lies at the REFERENCE's top logit (its full forward pass of three passes) within
    tolerance."""
    srv, reqs, results, _ = served
    for r in reqs:
        got = np.asarray(results[r.uid].tokens)
        assert results[r.uid].status == "ok" and len(got) == 12
        ref = reference.logits_at(program, srv.engine.params, np.concatenate([r.prompt, got[:-1]]),
                                  np.arange(len(r.prompt) - 1, len(r.prompt) + 11), fetch=WHOLE)
        assert (ref.max(axis=-1) - ref[np.arange(12), got]).max() <= TOL, r.uid
    assert srv.compile_counts()["decode"] == 1


def test_spans_gauges_and_pools_say_the_passes(served, program):
    srv, _, _, spans = served
    w, cfg = srv.worker, srv.engine.cfg
    per_token = 6 * tfm.cache_bytes_per_token(cfg)  # three passes x two layers
    assert w.hbm_pools()["slot_kv_cache"] == 3 * 256 * per_token
    assert set(w.hbm_pools()) == {"params", "slot_kv_cache"}
    assert srv.telemetry.gauge("serving/cache_layers").value == 6
    assert 1.0 <= srv.telemetry.gauge("serving/exit_pass_mean").value <= 3.0
    ctx = {"worker": w, "program": program}
    assert loop_cache_bytes_per_token.read(ctx) == per_token == loop_cost.kv_bytes_per_token(
        program, 4)
    assert kv_bytes_per_token_model.read(ctx) is None  # the accepted reader: nothing to divide by
    assert loop_cache_bytes_per_token.read({"worker": w, "program": {"num_layers": 2}}) is None
    prefills = [sp for sp in spans if sp.name == "prefill"]
    decodes = [sp for sp in spans if sp.name == "decode"]
    assert len(prefills) == 5 and decodes
    # a decode call fetches the step BEFORE it (PR 60): what comes with a fetch is on all but a burst's first
    assert sum(not sp.attrs["d2h"] for sp in decodes) < len(decodes) / 2
    for sp in prefills + decodes:
        assert sp.attrs["layer_passes"] == 3 and sp.attrs["cache_layers"] == 6
        if sp.name == "decode" and not sp.attrs["d2h"]:
            continue
        assert 1.0 <= sp.attrs["exit_pass_mean"] <= 3.0 and sp.attrs["d2h"] == 3
        cdf = sp.attrs["exit_cdf"]
        assert len(cdf) == 2 and 0 <= cdf[0] <= cdf[1] <= 1
    assert {sp.attrs["attn"] for sp in decodes} == {"dense"}


def test_the_exit_statistic_is_the_live_rows_mean(program, reference):
    """The prefill span's ``exit_pass_mean`` is the mean over the prompt's OWN rows (not
    the bucket's padding) of sum r p_r, the reference's."""
    srv = build_serving_engine(_spec(program))
    prompt = _tokens(srv.engine.cfg, (70,), 3)
    t0 = time.perf_counter()
    srv.serve([Request(uid=0, prompt=prompt, max_new_tokens=1)])
    sp, = [sp for sp in tracing.spans(t0) if sp.name == "prefill"]
    p = reference.exit_distribution(program, srv.engine.params, prompt, np.arange(70), fetch=WHOLE)
    assert sp.attrs["exit_pass_mean"] == pytest.approx(float((p * [1, 2, 3]).sum(-1).mean()),
                                                       abs=2e-4)
    np.testing.assert_allclose(sp.attrs["exit_cdf"], np.cumsum(p.mean(0))[:2], atol=2e-4)


def _same_tokens(results, served, offset=0):
    _, reqs, plain, _ = served
    for r in reqs:
        got = results[r.uid + offset]
        assert got.status == "ok"
        np.testing.assert_array_equal(np.asarray(got.tokens), np.asarray(plain[r.uid].tokens))


def test_chunked_prefill_serves_the_plain_engines_tokens(served, program):
    """Chunks of 32 rows through the ``chunk`` programs: a chunk entering past position 0
    attends, in EVERY pass, to what that pass of that layer wrote for the chunks before."""
    srv = build_serving_engine(_spec(program, chunked_prefill={"enabled": True, "chunk_size": 32}))
    _same_tokens(srv.serve(_requests(srv.engine.cfg)), served)
    assert srv.compile_counts()["chunk_prefill"]


def test_the_prefix_cache_stores_and_fetches_every_pass(served, program):
    """A stored prefix's window is ``layer_passes`` x L layers deep; a request that shares
    it is served from the pool to the plain engine's tokens."""
    block = {"enabled": True, "n_slots": 2, "block": 16, "max_prefix_len": 128,
             "insert_policy": "always"}
    srv = build_serving_engine(_spec(program, prefix_cache=block))
    cfg = srv.engine.cfg
    assert srv.worker._pool["k"].shape == (6, 2, 128, 4, 16)
    shared = _tokens(cfg, (130,), 130)  # the plain engine's first prompt
    first = srv.serve([Request(uid=0, prompt=shared, max_new_tokens=12)])
    again = srv.serve([Request(uid=1, prompt=shared, max_new_tokens=12),
                       Request(uid=2, prompt=np.concatenate([shared, _tokens(cfg, (9,), 1)]),
                               max_new_tokens=4)])
    _, _, plain, _ = served
    for res in (first[0], again[1]):
        np.testing.assert_array_equal(np.asarray(res.tokens), np.asarray(plain[0].tokens))
    assert again[1].prefix_hit_tokens >= 64 and again[2].prefix_hit_tokens >= 64
    assert again[2].status == "ok" and srv.prefix_cache_stats()["hits"] >= 2


def test_a_slot_exported_and_imported_decodes_to_the_same_tokens(served, program):
    """Disaggregated serving: one prefill and one decode replica; every request's K/V
    crosses the wire in windows [6, 1, width, 4, 16], all passes of all layers."""
    srv, _, _, _ = served
    router = Router(srv.engine, config={
        "n_slots": 3, "max_seq_len": 256, "watchdog_mode": "off",
        "router": {"disagg": {"enabled": True, "prefill_replicas": 1, "decode_replicas": 1}}})
    for r in _requests(srv.engine.cfg, first_uid=100):
        router.submit(r)
    _same_tokens(router.drain(), served, offset=100)
    k, v = srv.worker.kv_export(16, 0, 0)
    assert k.shape == v.shape == (6, 1, 16, 4, 16)


def test_ngram_speculation_serves_the_plain_engines_tokens(served, program):
    """Verify blocks of several tokens write their drafts' K/V in every (pass, layer) and
    roll back by not advancing: greedy tokens are the plain engine's."""
    srv = build_serving_engine(_spec(program, speculation={"enabled": True, "depth": 4,
                                                           "ngram_min_match": 2}))
    _same_tokens(srv.serve(_requests(srv.engine.cfg)), served)
    cfg = srv.engine.cfg
    loop = np.tile(_tokens(cfg, (6,), 2), 12)  # a prompt that repeats: drafts to verify
    out = srv.serve([Request(uid=50, prompt=loop, max_new_tokens=24)])
    plain = build_serving_engine(_spec(program)).serve(
        [Request(uid=50, prompt=loop, max_new_tokens=24)])
    np.testing.assert_array_equal(np.asarray(out[50].tokens), np.asarray(plain[50].tokens))
    assert srv.compile_counts().get("verify") and srv.spec_stats()["verify_steps"] > 0


# -- the readers -----------------------------------------------------------------------------------


def test_the_readers_count_the_passes(monkeypatch):
    """Two prefills and three decode steps on a hand-made ring at the published widths:
    the floor counts the layers' weights once a pass and K/V in 48 cache layers, the MFU
    every pass's matrices; spans without ``layer_passes`` give nothing; neither can pass
    100%: what they count is under what the chip's peaks allow in the spans' own time."""
    program = program_of(_config())

    def call(i, name, t0, t1, **attrs):
        sp = lambda j, parent, n, a, b, **kw: SimpleNamespace(  # noqa: E731
            id=j, parent=parent, name=n, path="serve/step/" + n, t0=a, t1=b, attrs=kw)
        return [sp(i, None, name, t0, t1, compiled=False, **attrs),
                sp(i + 1, i, "dispatch", t0, t0 + 1e-4), sp(i + 2, i, "fetch", t0 + 1e-4, t1)]

    looped = dict(layer_passes=4, cache_layers=48, exit_pass_mean=2.1, exit_cdf=[0.3, 0.6, 0.8])
    ring = (call(1, "prefill", 100.0, 100.030, bucket=512, **looped)
            + call(4, "prefill", 100.1, 100.116, bucket=256, **looped)
            + call(7, "prefill", 100.2, 100.21, bucket=128)  # a program without the passes
            + call(10, "decode", 100.40, 100.42, **looped) + call(13, "decode", 100.43, 100.45, **looped)
            + call(16, "decode", 100.46, 100.47))
    monkeypatch.setattr(tracing, "spans", lambda since=float("-inf"): [
        sp for sp in ring if sp.t1 >= since])
    notes = []
    ctx = {"serve": {"epoch": 0.0, "window": (99.0, 101.0),
                     "steps": [(100.40, 100.42, 24, 9800), (100.43, 100.45, 24, 9824)]},
           "program": program, "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "run": SimpleNamespace(note=lambda **kw: notes.append(kw))}
    mfu = [100 * loop_cost.prefill_flops(program, b) / 197e12 / s for b, s in ((512, 0.030),
                                                                              (256, 0.016))]
    assert loop_prefill_mfu_pct.read(ctx) == pytest.approx(np.median(mfu))
    need = loop_cost.decode_min_bytes(program, 9812.0)
    assert need == 2 * 2_566_914_048 + 9812.0 * 393_216
    assert loop_decode_hbm_floor_pct.read(ctx) == pytest.approx(100 * need / 819e9 / 0.020)
    assert loop_prefill_mfu_pct.read(ctx) < 100 and loop_decode_hbm_floor_pct.read(ctx) < 100
    assert {n["program"] for n in notes} == {"prefill", "decode"}
    assert loop_cost.prefill_flops(program, 512) == pytest.approx(
        2 * 4 * 12 * 51_380_224 * 512 + 2 * 2048 * 49152 + 48 * 2 * 512 * 512 * 2048)
    plain = {**ctx, "program": program_of(_config("bloom-1b7"))}
    assert loop_prefill_mfu_pct.read(plain) is None and loop_decode_hbm_floor_pct.read(plain) is None
    # the accepted floor reader, which knows no passes, would count K/V in 12 cache layers of
    # the 48 (a quarter of 3.9 GB a step): the cell is in no list of its
    assert decode_hbm_floor_pct.read(ctx) < 0.7 * loop_decode_hbm_floor_pct.read(ctx)


def test_the_cell_file_states_the_issues_traffic():
    with open(f"{ROOT}/chipbench/workloads/{CONFIG}.serve-reason.json") as f:
        cell = json.load(f)
    with open(f"{ROOT}/BENCHMARK.json") as f:
        bench = json.load(f)
    assert cell["deployment"]["n_slots"] == 24 and cell["deployment"]["max_seq_len"] == 1024
    assert cell["driver"] == "serve_looped" and cell["chips"] == 1
    t = cell["traffic"]
    assert (t["kind"], t["clients_per_slot"], t["max_total"]) == ("closed_loop", 2, 1024)
    assert t["prompt"] == {"dist": "lognormal", "median": 192, "sigma": 0.6, "min": 96, "max": 512}
    # ISSUE 56's step 3 lets two things move, each one way: lead_in_s 25 up to 35, and the
    # outputs 256-512 down to 192-384; grace_s is only ever raised; the trace block stands
    assert t["output"] in ({"dist": "uniform", "min": 256, "max": 512},
                           {"dist": "uniform", "min": 192, "max": 384})
    assert t["grace_s"] >= 60 and 25 <= t["lead_in_s"] <= 35 and t["max_rps"] == 16
    assert cell["trace"] == {"seconds": 6.0, "settle_s": 5.0}
    assert cell["serving"]["max_queue_len"] == 0
    assert not any(cell["serving"][k]["enabled"]
                   for k in ("prefix_cache", "chunked_prefill", "speculation"))
    name = f"{CONFIG}.serve-reason"
    lists = {m["name"]: m.get("workloads", []) for m in bench["per_layer"] + bench["end_to_end"]}
    assert name in lists["serve_tokens_per_s"] and name in lists["decode_host_transfers"]
    for reader in ("loop_decode_hbm_floor_pct", "loop_prefill_mfu_pct",
                   "loop_cache_bytes_per_token"):
        assert name in lists[reader]
    for reader in ("decode_hbm_floor_pct", "prefill_mfu_pct", "kv_bytes_per_token_model",
                   "moe_load_max_over_mean", "recurrent_state_bytes_per_slot"):
        assert name not in lists[reader]
    config = _config()
    assert config["total_ut_steps"] == 4 and config["early_exit_threshold"] == 1.0
    assert config["reduced"] == ["num_hidden_layers"] and config["published"] == {
        "num_hidden_layers": 48}
    assert (config["hidden_size"], config["intermediate_size"], config["num_attention_heads"],
            config["head_dim"], config["vocab_size"]) == (2048, 5632, 16, 128, 49152)
    assert config["program"]["layer_passes"] == 4 and config["program"]["num_layers"] == 12


# -- the cell's rehearsal ---------------------------------------------------------------------------


def test_the_cells_rehearsal_passes_and_lists_its_metrics():
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", f"{CONFIG}.serve-reason",
         "--rehearse", "--trace", "1"], cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] == "passed" and last["correct"] and last["failed"] == 0
    for name in ("loop_cache_bytes_per_token", "loop_decode_hbm_floor_pct", "loop_prefill_mfu_pct",
                 "compiles_in_window.doc", "decode_host_transfers", "serve_host_gap_pct"):
        assert name in last["would_report"], name
