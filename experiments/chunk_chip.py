"""Mellum2's cell by hand on the chip (PR 59): one call of each chunk program and one
32-row decode step of the cell's own engine timed by hand (the chunk at several offsets:
its whole-context layers walk the prefix), then the driver's check
(``drivers/serve_chunked_kinds.py``) over several seeds on that ONE engine (how far the
probe of the chunked serving path is from the float32 reference, seed by seed), what the
harness's ``judge`` says of the REFERENCE with its matrices rounded to float8 (e4m3), the
nearest precision below the configuration's, put in the probe's place
(``tests/mellum2_cases.py::judge_float8_reference``), and what it says of the probe with
each planted fault (``tests/mellum2_cases.py::CHIP_FAULTS``: plain rotary on the full
layers, ``attention_factor`` dropped, the window off by one, a ring that a chunk overwrote
before its queries read it). ``--engine-fault`` plants a fault in the TIMED engine's own
chunk programs alone (they are traced inside the plant; the probe, traced behind it, is
sound) and runs the check: the engine's tokens and choices have to fail it. (In the
review's session ``--walks`` timed the chunk programs a second time with the XLA walk in
the place of a Pallas kernel of it; the two tied, the kernel went, and the option with it.)

    chiprun --timeout 3000 -- python3 experiments/chunk_chip.py [--seeds 4] [--engine-fault]

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


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=4)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--skip-faults", action="store_true")
    ap.add_argument("--skip-timing", action="store_true")
    ap.add_argument("--engine-fault", action="store_true")
    args = ap.parse_args()
    if args.tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import numpy as np

    from deepspeed_tpu.utils.jax_env import use_compile_cache

    use_compile_cache()
    from mellum2_cases import CHIP_FAULTS, judge_float8_reference, planted

    from chipbench.drivers import serve, serve_chunked_kinds as drv, serve_latent
    from chipbench.references import load_reference, program_of
    from deepspeed_tpu.inference.serving import Request
    from deepspeed_tpu.launcher.serving_worker import build_serving_engine

    say = lambda **kw: print(json.dumps(kw, default=float), flush=True)
    with open(os.path.join(ROOT, "chipbench", "configs", "mellum2-12b-a2.5b-L8.json")) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "chipbench", "workloads",
                           "mellum2-12b-a2.5b-L8.serve-repoctx.json")) as f:
        cell = json.load(f)
    program = program_of(config, "rehearse_kinds_program" if args.tiny else "program")
    reference = load_reference(program)
    n_slots, budget = (4, 256) if args.tiny else (32, 32768)
    serving = {**cell["serving"], **(cell["rehearse"]["serving"] if args.tiny else {})}
    t0 = time.perf_counter()
    srv = build_serving_engine({
        "model": {**program, "dtype": "bfloat16"}, "engine_dtype": "bf16",
        "serving": {**serving, "n_slots": n_slots, "max_seq_len": budget, "seed": 0}})
    say(event="built", s=time.perf_counter() - t0, device=jax.devices()[0].device_kind)
    drv.probe_as(srv)
    w, vocab = srv.worker, program["vocab_size"]
    rng = np.random.default_rng(0)
    chunk = srv.chunk_cfg.chunk_size

    # -- one chunk of each width at several offsets and one full step, by hand ---------------
    widths = [] if args.skip_timing else [chunk] if args.tiny else [chunk, chunk // 2, chunk // 8]
    for width in widths:
        toks = rng.integers(0, vocab, size=(1, width)).astype(np.int32)
        for start in ((0, chunk) if args.tiny else (0, 0, 4096, 4096, 14336, 14336, 28672, 28672)):
            t = time.perf_counter()
            w.chunk(width, toks, 0, start, width, 0.0, 0, 1.0, fetch=True)
            say(event="chunk", width=width, start=start, ms=1e3 * (time.perf_counter() - t))
    for live in (() if args.skip_timing else (100,) if args.tiny else (2048, 10000, 30000)):
        pos = np.full((n_slots,), live, np.int32)
        active = np.ones((n_slots,), bool)
        tok = rng.integers(0, vocab, size=n_slots).astype(np.int32)
        zeros = np.zeros((n_slots,), np.float32)
        for i in range(4):
            t = time.perf_counter()
            w.decode(tok, pos, pos, active, zeros, np.zeros((n_slots,), np.int32),
                     np.ones((n_slots,), np.float32))
            say(event="decode", call=i, live_tokens=int(pos.sum()),
                ms=1e3 * (time.perf_counter() - t))
            pos = pos + 1
    say(event="pools", **w.hbm_pools(), peak=(jax.devices()[0].memory_stats() or {}).get(
        "peak_bytes_in_use"))

    # -- the check, seed by seed, on this engine ------------------------------------------------
    class Run:
        def __init__(self, seed):
            self.program, self.seed = program, seed

        def sized(self, block):
            return {"deployment": {"max_seq_len": budget, "n_slots": n_slots}}[block]

    with drv.as_this_cell(args.tiny):
        for seed in range(args.seeds):
            t = time.perf_counter()
            with mock.patch.object(serve_latent, "WARM_UID", serve.WARM_UID + 1000 * (seed + 1)):
                out = serve_latent._check(Run(1000003 * seed + 17), srv, Request)
            say(event="check", seed=seed, s=time.perf_counter() - t, **out)
        if args.engine_fault:
            fault = "a ring that a chunk overwrote before its queries read it"
            lens = [min(n, budget - serve.DECODE_STEPS - 2) for n in drv.CHECK_PROMPT_LENS]
            w._chunk_progs.clear()
            with planted(fault):  # the engine's chunk programs are traced where first called
                srv.serve([Request(uid=serve.WARM_UID + 900 + i, max_new_tokens=2,
                                   prompt=rng.integers(0, vocab, size=n).astype(np.int32))
                           for i, n in enumerate(lens)])
            t = time.perf_counter()
            with mock.patch.object(serve_latent, "WARM_UID", serve.WARM_UID + 5000):
                out = serve_latent._check(Run(1000003 * args.seeds + 17), srv, Request)
            say(event="engine_fault", fault=fault, s=time.perf_counter() - t, **out)
            w._chunk_progs.clear()  # the sound programs again, traced where next called
        if args.skip_faults:
            return

        # the last seed's prompts again, by hand: the tokens, the engine's choices, the probes
        seed = 1000003 * (args.seeds - 1) + 17
        prng = np.random.default_rng([seed, 0xC4EC])
        lens = [min(n, budget - serve.DECODE_STEPS - 2) for n in drv.CHECK_PROMPT_LENS]
        prompts = [prng.integers(0, vocab, size=n).astype(np.int32) for n in lens]
        reqs = [Request(uid=serve.WARM_UID + 50 + i, prompt=p,
                        max_new_tokens=serve.DECODE_STEPS + 1) for i, p in enumerate(prompts)]
        w.routing_log = log = []
        results = srv.serve(reqs)
        w.routing_log = None
        got = [np.asarray(results[r.uid].tokens, np.int32) for r in reqs]
        engine_chosen = drv.served_choices(log, [r.uid for r in reqs], lens)
        del log[:]
        params, cfg = srv.engine.params, srv.engine.cfg
        forced = np.stack([g[:serve.DECODE_STEPS] for g in got])

        def judged(event, **more):
            probe, chosen = drv.probe_logits(cfg, params, prompts, None, forced)
            out = serve_latent.judge(reference, program, params, prompts, got, probe, chosen,
                                     engine_chosen)
            say(event=event, **more, ok=out["ok"], logit_max_abs_err=out["logit_max_abs_err"],
                over_tol=out["logit_max_abs_err"] / out["logit_tol"],
                token_gap=out["token_gap_to_reference_top"],
                routing_slack=out["routing_slack"], probe_routing_slack=out["probe_routing_slack"],
                free_err=out["logit_max_abs_err_free_routing"],
                by_prompt=[float(np.max(np.abs(p))) for p in probe])

        judged("sound")
        # the reference through float8 matrices, the nearest precision below, IN THE PROBE'S
        # PLACE and through the harness's own comparison: it has to come out not correct
        out8 = judge_float8_reference(reference, program, params, prompts, got, engine_chosen)
        say(event="float8_reference", ok=out8["ok"], logit_max_abs_err=out8["logit_max_abs_err"],
            over_tol=out8["logit_max_abs_err"] / out8["logit_tol"],
            token_gap=out8["token_gap_to_reference_top"],
            probe_routing_slack=out8["probe_routing_slack"])
        for fault in CHIP_FAULTS:
            with planted(fault):
                judged("fault", fault=fault)


if __name__ == "__main__":
    main()
