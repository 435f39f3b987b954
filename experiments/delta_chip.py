"""Qwen3-Next's cell by hand on the chip (PR 52): the driver's check over several seeds
on ONE engine (how far the timed engine and the probe are from the float32 reference,
seed by seed), then one traced stretch of the cell's own programs (8,192- and 4,096-row
prefills, 64-row decode steps) with every device operation's time a call; with
``--float8`` also what the check reads when the REFERENCE's matrices are rounded to
float8 (e4m3), the nearest precision below the configuration's.

    chiprun --timeout 2400 -- python3 experiments/delta_chip.py [--seeds 3] [--top 45]

``--tiny`` rehearses the control flow on the CPU with the configuration's delta twin."""
import argparse
import json
import os
import sys
import tempfile
import time
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--top", type=int, default=45)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--float8", action="store_true")
    ap.add_argument("--set", default="{}", help="JSON of program keys to override")
    args = ap.parse_args()
    if args.tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import glob

    import jax
    import numpy as np

    from chipbench import reduce
    from chipbench.drivers import serve_delta, serve_latent
    from chipbench.references import program_of
    from deepspeed_tpu.inference.serving import Request
    from deepspeed_tpu.launcher.serving_worker import build_serving_engine
    from deepspeed_tpu.telemetry import tracing
    from deepspeed_tpu.utils.jax_env import use_compile_cache

    use_compile_cache()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs", "qwen3-next-80b-a3b-L8.json")) as f:
        config = json.load(f)
    program = program_of(config, serve_delta.TWIN if args.tiny else "program")
    program.update(json.loads(args.set))
    n_slots, smax = (4, 256) if args.tiny else (64, 8192)
    srv = build_serving_engine({"model": {**program, "dtype": "bfloat16"}, "engine_dtype": "bf16",
                                "serving": {"n_slots": n_slots, "max_seq_len": smax, "seed": 1,
                                            "watchdog_mode": "warn", "max_queue_len": 0}})

    class Run:
        cell = {"serving": {}}

        def __init__(self, seed):
            self.program, self.seed = program, seed

        def sized(self, block):
            return {"deployment": {"max_seq_len": smax, "n_slots": n_slots}}[block]

    def float8(leaves):
        """A ``fetch`` that rounds every matrix to float8 (e4m3), scaled a leaf."""
        import jax.numpy as jnp

        def one(x):
            x = jnp.asarray(x, jnp.float32)
            if x.ndim < 2:
                return x
            scale = float(jnp.finfo(jnp.float8_e4m3fn).max) / jnp.max(jnp.abs(x))
            return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale

        return jax.tree.map(one, leaves)

    if args.float8:  # the sound engine and probe against a reference computed in float8
        import chipbench.references.qwen3_next as ref

        real = ref.routed_passes
        with mock.patch.object(ref, "routed_passes", lambda *a, fetch, **kw: real(
                *a, fetch=float8, **kw)), serve_delta.as_this_cell(), mock.patch.object(
                    serve_latent, "WARM_UID", serve_latent.WARM_UID + 999_000):
            out = serve_latent._check(Run(5200000200), srv, Request)
        print(json.dumps({"event": "check", "reference": "float8 (e4m3) matrices", **out}),
              flush=True)

    for seed in range(args.seeds):
        t0 = time.perf_counter()
        with serve_delta.as_this_cell(), mock.patch.object(  # a uid is one engine's once
                serve_latent, "WARM_UID", serve_latent.WARM_UID + 1000 * seed):
            out = serve_latent._check(Run(5200000200 + 7 * seed), srv, Request)
        print(json.dumps({"event": "check", "seed": seed, "s": round(time.perf_counter() - t0, 1),
                          **out}), flush=True)

    rng = np.random.default_rng(3)
    lens = [100, 200] * 8 if args.tiny else [7000, 3500] * 8
    reqs = [Request(uid=i, prompt=rng.integers(0, program["vocab_size"], size=n).astype(np.int32),
                    max_new_tokens=24 if args.tiny else 64) for i, n in enumerate(lens)]
    srv.serve(reqs[:8])  # untraced: whatever is left to compile or to page in
    trace_dir = tempfile.mkdtemp(prefix="delta-chip-")
    jax.profiler.start_trace(trace_dir)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(reduce.WINDOW_SPAN):
        srv.serve(reqs[8:])  # eight prefills and some 70 steps of the 64-row decode program
    jax.profiler.stop_trace()
    spans = tracing.spans(t0)
    for name in ("prefill", "decode"):
        took = [1e3 * (sp.t1 - sp.t0) for sp in spans if sp.name == name]
        print(json.dumps({"event": "spans", "program": name, "n": len(took),
                          "ms": [round(t, 2) for t in sorted(took)][:12]}), flush=True)
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    red = reduce.reduce(reduce.load(files[0]), ())
    calls = {"jit_prefill": max(1, sum(sp.name == "prefill" for sp in spans)),
             "jit_decode": max(1, sum(sp.name == "decode" for sp in spans))}
    for prog, n in calls.items():
        ops = sorted(((k, v) for k, v in red["op_seconds"].items() if k.startswith(prog + "/")),
                     key=lambda kv: -kv[1])
        total = sum(v for _, v in ops)
        print(json.dumps({"event": "program", "program": prog, "calls": n,
                          "device_ms_a_call": round(1e3 * total / n, 2)}), flush=True)
        for k, v in ops[:args.top]:
            text = red["op_text"].get(k, "")
            shape = text.split(" = ", 1)[1].split("{", 1)[0].strip()[:60] if " = " in text else ""
            print(f"  {1e3 * v / n:8.3f} ms  {k}  {shape}", flush=True)


if __name__ == "__main__":
    main()
