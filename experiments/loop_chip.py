"""Ouro's cell by hand on the chip (PR 56): one 512-row prefill and one 24-row decode
step of the cell's own engine timed by hand, then the driver's check over several
seeds on that ONE engine (how far the probe of the serving path is from the float32
reference, seed by seed), what the harness's ``judge`` says of the REFERENCE with its
matrices rounded to float8 (e4m3), the nearest precision below the configuration's,
put in the probe's place (``tests/ouro_cases.py::judge_float8_reference``), and what
it says of the probe with each planted fault (``tests/ouro_cases.py::PLANTED``: a
pass too few, a decode step that reads the pass before's K/V, the norm between the
passes in bfloat16, ...).

    chiprun --timeout 1800 -- python3 experiments/loop_chip.py [--seeds 4]

``--tiny`` rehearses the control flow on the CPU with the configuration's tiny twin."""
import argparse
import json
import os
import sys
import time
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tests"))

CHIP_FAULTS = ("one pass too few", "a decode step reads the pass before",
               "the norm between passes dropped", "a branch norm dropped",
               "the norm between passes in the compute dtype")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=4)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    if args.tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import numpy as np

    from deepspeed_tpu.utils.jax_env import use_compile_cache

    use_compile_cache()
    from ouro_cases import judge_float8_reference, planted

    from chipbench.drivers import serve, serve_looped, serve_recurrent
    from chipbench.references import load_reference, program_of
    from deepspeed_tpu.inference.serving import Request
    from deepspeed_tpu.launcher.serving_worker import build_serving_engine

    say = lambda **kw: print(json.dumps(kw, default=float), flush=True)
    with open(os.path.join(ROOT, "chipbench", "configs", "ouro-2.6b-L12.json")) as f:
        config = json.load(f)
    program = program_of(config, "rehearse_program" if args.tiny else "program")
    reference = load_reference(program)
    n_slots, budget = (4, 256) if args.tiny else (24, 1024)
    t0 = time.perf_counter()
    srv = build_serving_engine({
        "model": {**program, "dtype": "bfloat16"}, "engine_dtype": "bf16",
        "serving": {"n_slots": n_slots, "max_seq_len": budget, "seed": 0, "watchdog_mode": "warn"}})
    say(event="built", s=time.perf_counter() - t0, device=jax.devices()[0].device_kind)
    w, vocab = srv.worker, program["vocab_size"]
    rng = np.random.default_rng(0)

    # -- one prefill of each bucket and one full step, by hand --------------------------------
    for bucket in (128, 256, 512) if not args.tiny else (128,):
        padded = np.zeros((1, bucket), np.int32)
        n = bucket - 20
        padded[0, :n] = rng.integers(0, vocab, size=n)
        for i in range(4):
            t = time.perf_counter()
            w.prefill(bucket, padded, i % n_slots, n, 0.0, 0, 1.0)
            say(event="prefill", bucket=bucket, call=i, ms=1e3 * (time.perf_counter() - t))
    pos = np.full((n_slots,), 400 if not args.tiny else 100, np.int32)
    active = np.ones((n_slots,), bool)
    tok = rng.integers(0, vocab, size=n_slots).astype(np.int32)
    zeros = np.zeros((n_slots,), np.float32)
    for i in range(8):
        t = time.perf_counter()
        w.decode(tok, pos, pos, active, zeros, np.zeros((n_slots,), np.int32),
                 np.ones((n_slots,), np.float32))
        say(event="decode", call=i, live_tokens=int(pos.sum()), ms=1e3 * (time.perf_counter() - t))
        pos = pos + 1
    say(event="pools", **w.hbm_pools(), peak=(jax.devices()[0].memory_stats() or {}).get(
        "peak_bytes_in_use"))

    # -- the check, seed by seed, on this engine ------------------------------------------------
    class Run:
        cell = {"serving": {}}

        def __init__(self, seed):
            self.program, self.seed = program, seed

        def sized(self, block):
            return {"deployment": {"max_seq_len": budget, "n_slots": n_slots}}[block]

    with serve_looped.as_this_cell():
        for seed in range(args.seeds):
            t = time.perf_counter()
            with mock.patch.object(serve_recurrent, "WARM_UID", serve.WARM_UID + 1000 * (seed + 1)):
                out = serve_recurrent._check(Run(1000003 * seed + 17), srv, Request)
            say(event="check", seed=seed, s=time.perf_counter() - t, **out)

        # the last seed's prompts again, by hand: the tokens, the sound probe, the reference
        seed = 1000003 * (args.seeds - 1) + 17
        prng = np.random.default_rng([seed, 0xC4EC])
        lens = [min(n, budget - serve.DECODE_STEPS - 2) for n in serve_looped.CHECK_PROMPT_LENS]
        prompts = [prng.integers(0, vocab, size=n).astype(np.int32) for n in lens]
        reqs = [Request(uid=serve.WARM_UID + 50 + i, prompt=p,
                        max_new_tokens=serve.DECODE_STEPS + 1) for i, p in enumerate(prompts)]
        results = srv.serve(reqs)
        got = [np.asarray(results[r.uid].tokens, np.int32) for r in reqs]
        params, cfg = srv.engine.params, srv.engine.cfg
        buckets = [serve._bucket(srv, len(p)) for p in prompts]
        forced = np.stack([g[:serve.DECODE_STEPS] for g in got])
        probe = serve_recurrent.probe_logits(cfg, params, prompts, buckets, forced)
        sound = serve_recurrent.judge(reference, program, params, prompts, got, probe)
        say(event="sound", **sound)

        # the reference through float8 matrices, the nearest precision below, IN THE PROBE'S
        # PLACE and through the harness's own comparison: ``judge`` holds it to the float32
        # reference under the cell's limits, and it has to come out not correct
        out8 = judge_float8_reference(reference, program, params, prompts, got)
        say(event="float8_reference", ok=out8["ok"], logit_max_abs_err=out8["logit_max_abs_err"],
            token_gap_to_reference_top=out8["token_gap_to_reference_top"],
            logit_tol=out8["logit_tol"], over_tol=out8["logit_max_abs_err"] / out8["logit_tol"],
            by_prompt=out8["logit_err_by_prompt"])

        # the probe with each planted fault against the sound reference
        for fault in CHIP_FAULTS:
            with planted(fault):
                bad = serve_recurrent.probe_logits(cfg, params, prompts, buckets, forced)
            out = serve_recurrent.judge(reference, program, params, prompts, got, bad)
            say(event="fault", fault=fault, ok=out["ok"],
                logit_max_abs_err=out["logit_max_abs_err"],
                over_tol=out["logit_max_abs_err"] / serve_looped.LOGIT_TOL,
                by_prompt=out["logit_err_by_prompt"])


if __name__ == "__main__":
    main()
