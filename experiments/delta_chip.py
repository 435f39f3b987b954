"""Qwen3-Next's cell by hand on the chip (PR 52): the driver's check over several seeds
on ONE engine (how far the timed engine and the probe are from the float32 reference,
seed by seed), then one traced stretch of the cell's own programs (8,192- and 4,096-row
prefills, 64-row decode steps) with every device operation's time a call; with
``--float8`` also what the check reads when the REFERENCE's matrices are rounded to
float8 (e4m3), the nearest precision below the configuration's.

    chiprun --timeout 2400 -- python3 experiments/delta_chip.py [--seeds 3] [--top 45]

``--block`` (PR 55) times the rule's block form ALONE at the cell's widths (16 key / 32 value
heads of 128; 8,192 and 4,096 rows of one sequence, bfloat16, a drawn state): the XLA form
beside the Pallas kernel (``transformer._delta_chunks(form=...)``), operands passed in, and how
far the kernel's o and S_T lie from the XLA form's (~1 min on the chip).

``--tiny`` rehearses the control flow on the CPU with the configuration's delta twin (the
block mode at 256 and 128 rows of 2 / 4 heads, the kernel through the interpreter)."""
import argparse
import json
import os
import sys
import tempfile
import time
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def block_operands(rows, key_heads, value_heads, width=128, seed=0):
    """q, k (unit rows; q scaled), v in bfloat16, g (decays a token from ~1 to ~e^-3: heads that
    remember thousands of rows and heads that forget in one), beta, a drawn float32 state."""
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)
    q = unit(jax.random.normal(ks[0], (1, rows, key_heads, width))) * width ** -0.5
    k = unit(jax.random.normal(ks[1], (1, rows, key_heads, width)))
    v = jax.random.normal(ks[2], (1, rows, value_heads, width))
    rate = jnp.exp(jnp.linspace(-9.0, 1.1, value_heads))  # a head's own: log-uniform steps
    g = -rate * jax.random.uniform(ks[3], (1, rows, value_heads), minval=0.5, maxval=1.5)
    beta = jax.random.uniform(ks[4], (1, rows, value_heads))
    S0 = 0.1 * jax.random.normal(ks[5], (1, value_heads, width, width))
    return tuple(x.astype(jnp.bfloat16) for x in (q, k, v)) + (g, beta, S0)


def time_block_forms(forms, shapes, heads, repeats):
    """Each form of ``forms`` (name -> f(q, k, v, g, beta, S0) -> (o, S_T)) jitted and timed at
    each row count of ``shapes``: ms a call by the host's clock round ``repeats`` calls in
    flight (the device runs them back to back), and the distance from the FIRST form."""
    import jax
    import jax.numpy as jnp

    for rows in shapes:
        operands = block_operands(rows, *heads)
        want = None
        for name, form in forms.items():
            fn = jax.jit(form)
            t0 = time.perf_counter()
            got = jax.block_until_ready(fn(*operands))
            first = time.perf_counter() - t0
            t0 = time.perf_counter()
            for _ in range(repeats):
                out = fn(*operands)
            jax.block_until_ready(out)
            ms = 1e3 * (time.perf_counter() - t0) / repeats
            want = want or got
            far = [float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))) for a, b in zip(got, want)]
            print(json.dumps({"event": "block", "rows": rows, "form": name, "ms": round(ms, 3),
                              "first_call_s": round(first, 2), "o_rel": far[0], "S_rel": far[1]}),
                  flush=True)


def block_mode(tiny):
    from functools import partial

    from deepspeed_tpu.models import transformer as tfm

    forms = {form: partial(tfm._delta_chunks, form=form) for form in ("xla", "kernel")}
    if tiny:
        return time_block_forms(forms, (256, 128), (2, 4), 2)
    return time_block_forms(forms, (8192, 4096), (16, 32), 20)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--block", action="store_true")
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--top", type=int, default=45)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--float8", action="store_true")
    ap.add_argument("--set", default="{}", help="JSON of program keys to override")
    args = ap.parse_args()
    if args.tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"
    if args.block:
        return block_mode(args.tiny)
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
