"""Perf localization probe for the GPT-2 125M bench (task: >=64 TFLOPS/chip).

Times, on the real chip:
  1. flash-attention kernel standalone vs XLA attention at bench shapes
  2. forward-only loss, fwd+bwd, and the full train step
  3. variants: remat policy, attn impl, batch size

Run:  python experiments/perf_probe.py [variant ...]
"""

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.models.transformer import Model, TransformerConfig

L, H, D, V, S = 12, 12, 768, 50304, 1024


def _sync(out):
    """Wait for the device by fetching one scalar of the output."""
    leaf = jax.tree.leaves(out)[0]
    np.asarray(jax.device_get(leaf.ravel()[0]))


def timeit(fn, *args, n=10, warmup=2):
    for _ in range(warmup):
        out = fn(*args)
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    _sync(out)
    return (time.perf_counter() - t0) / n


def flops_per_token():
    n_params = L * (12 * D * D) + V * D + S * D
    return 6 * n_params + L * 12 * S * D


def _repeat_in_jit(op, reps):
    """Wrap op(q,k,v)->array into a jitted fn running it `reps` times serially
    (carry-dependent so XLA can't elide), amortizing dispatch overhead."""

    def f(q, k, v):
        def body(carry, _):
            out = op(q + carry, k, v)
            return out.ravel()[0].astype(q.dtype) * 1e-9, None

        carry, _ = jax.lax.scan(body, jnp.zeros((), q.dtype), None, length=reps)
        return carry

    return jax.jit(f)


def dispatch_probe():
    x = jnp.zeros((8, 128))
    f = jax.jit(lambda x: x + 1)
    t = timeit(f, x, n=20)
    print(f"dispatch overhead (tiny op): {t*1e3:.2f} ms")


def attn_probe(B=64, reps=10):
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    from deepspeed_tpu.models.transformer import xla_attention

    rng = jax.random.PRNGKey(0)
    q = jax.random.normal(rng, (B, S, H, 64), jnp.bfloat16)
    k = jax.random.normal(rng, (B, S, H, 64), jnp.bfloat16)
    v = jax.random.normal(rng, (B, S, H, 64), jnp.bfloat16)

    # attention FLOPs (fwd): 2 matmuls of [S,S]x[S,D]-ish: 2*2*B*H*S*S*Dh/2 causal
    fwd_flops = 4 * B * H * S * S * 64 / 2

    for name, op in [
        ("flash fwd", lambda q, k, v: flash_attention(q, k, v, causal=True)),
        ("xla   fwd", lambda q, k, v: xla_attention(q, k, v)),
    ]:
        t = timeit(_repeat_in_jit(op, reps), q, k, v, n=3) / reps
        print(f"{name} B={B}: {t*1e3:.2f} ms  ({fwd_flops/t/1e12:.1f} TFLOPS)")

    for name, op in [
        ("flash fwd+bwd", lambda q, k, v: flash_attention(q, k, v, causal=True)),
        ("xla   fwd+bwd", lambda q, k, v: xla_attention(q, k, v)),
    ]:
        gop = jax.grad(lambda q, k, v: jnp.sum(op(q, k, v).astype(jnp.float32)))
        t = timeit(_repeat_in_jit(lambda q, k, v: gop(q, k, v), reps), q, k, v, n=3) / reps
        print(f"{name} B={B}: {t*1e3:.2f} ms  ({3.5*fwd_flops/t/1e12:.1f} TFLOPS)")


def make_engine(B, attn, remat, policy="nothing_saveable", zero=1, chunk=512):
    cfg = TransformerConfig(
        vocab_size=V, max_seq_len=S, num_layers=L, num_heads=H, hidden_size=D,
        pos_emb="learned", dtype=jnp.bfloat16, remat=remat, remat_policy=policy,
        attn_impl=attn, loss_chunk_size=chunk,
    )
    model = Model(cfg)
    ds_cfg = {
        "train_batch_size": B,
        "train_micro_batch_size_per_gpu": B,
        "optimizer": {"type": "AdamW", "params": {"lr": 6e-4, "weight_decay": 0.1}},
        "zero_optimization": {"stage": zero},
        "bf16": {"enabled": True},
        "gradient_clipping": 1.0,
        "steps_per_print": 1000000,
        "mesh": {"data": -1},
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=ds_cfg)
    tokens = np.random.default_rng(0).integers(0, V, size=(B, S + 1)).astype(np.int32)
    return engine, {"tokens": tokens}


def step_probe(name, B, attn, remat, policy="nothing_saveable", n=8, chunk=512):
    engine, batch = make_engine(B, attn, remat, policy, chunk=chunk)
    try:
        engine.train_batch(batch)  # compile
        jax.block_until_ready(engine.state["params"]["wte"])
        t0 = time.perf_counter()
        for _ in range(n):
            engine.train_batch(batch)
        jax.block_until_ready(engine.state["params"]["wte"])
        dt = (time.perf_counter() - t0) / n
        tok_s = B * S / dt
        tf = tok_s * flops_per_token() / 1e12
        print(f"[{name}] B={B} attn={attn} remat={remat}/{policy}: "
              f"{dt*1e3:.0f} ms/step, {tok_s:,.0f} tok/s, {tf:.1f} TFLOPS")
    except Exception as e:
        print(f"[{name}] FAILED: {type(e).__name__}: {str(e)[:300]}")


def fwd_bwd_probe(B=64, attn="flash", remat=True, policy="nothing_saveable"):
    """Forward-only vs grad: how much of step time is bwd vs optimizer."""
    engine, batch = make_engine(B, attn, remat, policy)
    model = engine.model
    cd = jnp.bfloat16

    def loss_of(params, batch):
        cast = jax.tree.map(lambda p: p.astype(cd) if p.dtype == jnp.float32 else p, params)
        return model.loss(cast, batch)

    f = jax.jit(loss_of)
    t = timeit(f, engine.state["params"], batch, n=5)
    tok = B * S
    print(f"fwd-only: {t*1e3:.0f} ms  ({tok/t:,.0f} tok/s; fwd≈{tok/t*2*flops_per_token()/6/1e12:.1f} TFLOPS eff)")
    g = jax.jit(jax.grad(loss_of))
    t = timeit(g, engine.state["params"], batch, n=5)
    print(f"fwd+bwd:  {t*1e3:.0f} ms  ({tok/t:,.0f} tok/s, {tok/t*flops_per_token()/1e12:.1f} TFLOPS)")


if __name__ == "__main__":
    which = sys.argv[1:] or ["attn"]
    print(f"devices: {jax.devices()}")
    for w in which:
        if w == "attn":
            attn_probe()
        elif w == "fwdbwd":
            fwd_bwd_probe()
        elif w == "base":
            step_probe("base", 64, "flash", True, "nothing_saveable")
        elif w == "saveflash":
            step_probe("saveflash", 64, "flash", True, "save_flash")
        elif w == "dotsflash64":
            step_probe("dotsflash64", 64, "flash", True, "dots_and_flash")
        elif w == "dotsflash32":
            step_probe("dotsflash32", 32, "flash", True, "dots_and_flash")
        elif w == "noremat32":
            step_probe("noremat32", 32, "flash", False)
        elif w == "noremat16":
            step_probe("noremat16", 16, "flash", False)
        elif w == "xla":
            step_probe("xla", 64, "xla", True)
        elif w == "nochunk":
            step_probe("nochunk", 64, "flash", True, chunk=0)
        else:
            print(f"unknown variant {w}")
