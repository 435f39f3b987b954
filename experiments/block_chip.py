"""SDAR's cell by hand on the chip (PR 63): the driver's check (``drivers/serve_blocks.py``)
over several seeds on ONE engine at the cell's own size (how far the probe of the block step
is from the float32 reference, and the timed engine's tokens and reveals from its top, seed
by seed), then what ``judge`` says of each planted fault:

* in the PROBE's steps: the commit skipped, a block's K/V written one position off, the
  causal mask inside a block (the probe's step is traced inside the plant);
* the REFERENCE with the experts' matrices rounded to float8 (e4m3), the nearest precision
  below the configuration's, put in the probe's place (a lower-precision expert GEMM);
* in the TIMED engine: the commit skipped and the K/V one position off (its scheduler's
  operands), and with ``--engine-mask`` the causal mask inside a block and the masked rows
  ranked the wrong way round, each in its own block-step program, traced again inside the
  plant (one more compile each);
* last, the TIMED engine on float8 (e4m3) expert matrices: its own weights rounded in place
  on the device (every program of the engine reads them), the reference on a host copy of
  the weights as they were. Nothing is run behind it.

The faults are read on the first ``--fault-seeds`` seeds.

Each line says the reading beside the cell's limit. ``--steps N`` also times N block steps
of the engine by hand at a full house of idle rows.

    chiprun --timeout 3000 -- python3 experiments/block_chip.py [--seeds 6] [--engine-mask]

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

CELL = "sdar-30b-a3b-L7.serve-blockgen"


class Run:
    """What ``serve_blocks._check`` reads of the harness's ``Run``."""

    def __init__(self, program, seed, deployment, rehearse):
        self.program, self.seed, self.rehearse, self._deployment = program, seed, rehearse, deployment

    def sized(self, block):
        return {"deployment": self._deployment}[block]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--first-seed", type=int, default=6300000100)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--skip-faults", action="store_true")
    ap.add_argument("--engine-mask", action="store_true")
    ap.add_argument("--steps", type=int, default=0)
    ap.add_argument("--fault-seeds", type=int, default=2)
    ap.add_argument("--skip-probe-faults", action="store_true")
    ap.add_argument("--only-float8", action="store_true",
                    help="the sound seeds, then the timed engine on float8 experts, nothing else")
    ap.add_argument("--all-probe-faults", action="store_true",
                    help="the probe's faults on every fault seed (default: the last alone)")
    args = ap.parse_args()
    if args.tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.utils.jax_env import use_compile_cache

    use_compile_cache()
    from sdar_cases import causal_inside_a_block, commit_skipped, written_one_off

    from chipbench.drivers import serve, serve_blocks as drv
    from chipbench.references import load_reference, program_of
    from deepspeed_tpu.inference.serving import Request
    from deepspeed_tpu.launcher.serving_worker import build_serving_engine

    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    sink = open(os.path.join(out_dir, "block_chip.jsonl"), "a")

    def say(**kw):
        line = json.dumps(kw, default=float)
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()

    with open(os.path.join(ROOT, "chipbench", "configs", "sdar-30b-a3b-L7.json")) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "chipbench", "workloads", f"{CELL}.json")) as f:
        cell = json.load(f)
    program = program_of(config, "rehearse_blocks_program" if args.tiny else "program")
    dep = dict(cell["deployment"], **(cell["rehearse"]["deployment"] if args.tiny else {}))
    serving = dict(cell["serving"], **(cell["rehearse"]["serving"] if args.tiny else {}))
    reference = load_reference(program)
    t0 = time.perf_counter()
    srv = build_serving_engine({
        "model": {**program, "dtype": "bfloat16"}, "engine_dtype": "bf16",
        "serving": {**serving, "n_slots": dep["n_slots"], "max_seq_len": dep["max_seq_len"],
                    "seed": args.first_seed}})
    say(event="built", s=time.perf_counter() - t0, device=jax.devices()[0].device_kind,
        n_slots=dep["n_slots"], max_seq_len=dep["max_seq_len"])
    uid = iter(range(1000, 10 ** 6, 100))
    keys = ("ok", "logit_max_abs_err", "engine_log_conf_err", "token_gap_to_reference_top",
            "reveal_gap_log_conf", "routing_slack", "probe_routing_slack", "passes_checked", "why")

    def check(seed, **patches):
        run = Run(program, seed, dep, args.tiny)
        t = time.perf_counter()
        with mock.patch.multiple(drv, WARM_UID=serve.WARM_UID + next(uid), **patches):
            out = drv._check(run, srv, Request)
        return {k: out[k] for k in keys if k in out}, time.perf_counter() - t

    limits = drv.limits_of(Run(program, 0, dep, args.tiny))
    seeds = [args.first_seed + 1000 * i for i in range(args.seeds)]
    for seed in seeds:
        out, took = check(seed)
        say(event="sound", seed=seed, s=took, limits=limits, **out)
    if args.steps:
        w, n, B = srv.worker, dep["n_slots"], program["attn_block_length"]
        idle = dict(opened=np.zeros(n, bool), new_toks=np.zeros((n, B), np.int32),
                    new_mask=np.zeros((n, B), bool), pos=np.zeros(n, np.int32),
                    wpos=np.full(n, w.Smax, np.int32), active=np.zeros(n, bool),
                    count=np.zeros(n, np.int32), threshold=np.full(n, np.inf, np.float32),
                    temp=np.zeros(n, np.float32), top_k=np.zeros(n, np.int32),
                    top_p=np.ones(n, np.float32))
        w.block_step(*idle.values(), masked_rows=0, commits=0)
        w.collect()
        t = time.perf_counter()
        for _ in range(args.steps):
            w.block_step(*idle.values(), masked_rows=0, commits=0)
        w.collect()
        say(event="steps_by_hand", steps=args.steps, idle_rows=True,
            ms_a_step=1e3 * (time.perf_counter() - t) / args.steps)
    if args.skip_faults:
        return 0
    real_probe, real_served, real_judge = drv.probe_passes, drv.served, drv.judge

    def probe_masked(s, r, p):
        with causal_inside_a_block():
            return real_probe(s, r, p)

    # a lower-precision expert GEMM in the probe's place: the reference on float8 experts
    f8 = lambda leaves: jax.tree.map(  # noqa: E731
        lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype) if x.ndim == 2 and "wg" in leaves
        else x, leaves)

    def probe_float8(s, requests, prompts):
        real_probe(s, requests, prompts)
        B = program["attn_block_length"]
        flat = [(req, i) for req in requests for i in range(len(req["passes"]))]
        seqs = [req["passes"][i]["sequence"] for req, i in flat]
        rows = [np.arange(len(q) - B, len(q)) for q in seqs]
        routing = [drv.routing_of(dict(req, prefill=req["probe_prefill"]), i,
                                  lambda p: p["probe_chosen"]) for req, i in flat]
        ref = reference.routed_passes(program, s.engine.params, seqs, rows, fetch=f8,
                                      routing=routing)
        for (req, i), logits in zip(flat, ref["logits"]):
            req["passes"][i]["probe_logits"] = logits

    def planted_in_engine(plant):
        def served(*a):
            with plant():
                return real_served(*a)
        return served

    def ranked_the_wrong_way_round():
        from deepspeed_tpu.inference import serving as live
        real_rows = live.reveal_rows
        return mock.patch.object(live, "reveal_rows", lambda conf, *rest: real_rows(-conf, *rest))

    fault_seeds = seeds[:args.fault_seeds]
    for seed in () if args.only_float8 else fault_seeds:
        for name, probe in (
                ("probe: commit skipped", lambda s, r, p: real_probe(s, r, p, skip_commit=True)),
                ("probe: K/V one position off", lambda s, r, p: real_probe(s, r, p, write_off=1)),
                ("probe: the causal mask inside a block", probe_masked),
                ("reference with float8 (e4m3) expert matrices in the probe's place",
                 probe_float8)):
            out, took = check(seed, probe_passes=probe)
            say(event="fault", fault=name, seed=seed, s=took, **out)
        for name, plant in (("engine: commit skipped", commit_skipped),
                            ("engine: K/V one position off", written_one_off)):
            out, took = check(seed, served=planted_in_engine(plant))
            say(event="fault", fault=name, seed=seed, s=took, **out)
    if args.engine_mask and not args.only_float8:
        for name, plant in (
                ("engine: the causal mask inside a block (its own block step)", causal_inside_a_block),
                ("engine: the masked rows ranked the wrong way round (its own block step)",
                 ranked_the_wrong_way_round)):
            for seed in fault_seeds:
                srv.worker._block = None  # traced again, inside the plant
                out, took = check(seed, served=planted_in_engine(plant))
                say(event="fault", fault=name, seed=seed, s=took, **out)
        srv.worker._block = None

    # -- last: the TIMED engine on float8 expert matrices, the reference on what they were ----
    # (two dispatches a matrix and no jit round them: inside ONE program the chip's compiler
    # drops a narrowing convert that is widened again at once, and the weights stay as they
    # were: call 229 read the sound numbers to the digit)
    kept = jax.device_get(srv.engine.params)
    experts = srv.engine.params["moe"]["experts"]
    for k in list(experts):
        was, experts[k] = experts[k], None
        narrow = was.astype(jnp.float8_e4m3fn)
        narrow.block_until_ready()
        was.delete()  # (a program that still held the old matrix would fail here, loudly)
        experts[k] = narrow.astype(was.dtype)
        experts[k].block_until_ready()
        narrow.delete()
    changed = max(float(np.max(np.abs(np.asarray(experts[k][0, 0], np.float32)
                                      - np.asarray(kept["moe"]["experts"][k][0, 0], np.float32))))
                  for k in experts)
    say(event="rounded", leaves=sorted(experts), max_change_in_one_matrix=changed)
    srv.worker.params = srv.engine.params
    for seed in fault_seeds:
        out, took = check(seed, judge=lambda ref, prog, _, reqs, lim: real_judge(
            ref, prog, kept, reqs, lim))
        say(event="fault", fault="engine: float8 (e4m3) expert matrices in the TIMED engine",
            seed=seed, s=took, **out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
