"""Roofline table for the bench geometry (VERDICT r4 #2's alternative bar).

Measures, on the real chip, the achieved TFLOPS of each compute component of
the GPT-2 125M train step AT ITS EXACT SHAPES (micro 16, seq 1024, bf16):

  - layer matmuls: qkv/proj [16384,768]x[768,768], mlp [16384,768]x[768,3072]
    and [16384,3072]x[3072,768] (fwd and the two bwd GEMM shapes each)
  - flash attention fwd+bwd (ops/pallas/flash_attention) at B=16,H=12,S=1024
  - LayerNorm fwd+bwd (fp32 round trip) at [16,1024,768]
  - chunked vocab projection + softmax-xent fwd+bwd at chunk 256

From these it assembles the per-step time budget the matmul ceiling implies
and compares with the measured end-to-end step, so the residual gap is
attributable: if sum(component times at measured component TFLOPS) ~= step
time, the bench number IS the matmul ceiling at these shapes and further MFU
asks for bigger shapes, not better scheduling.

Usage: python experiments/roofline_r5.py  (writes experiments/roofline_r5.json)
"""

import json
import os
import sys
import time

sys.path.insert(0, "/root/repo")

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

if os.environ.get("DSTPU_ROOFLINE_TINY"):  # CPU self-check: trace every
    # component at toy shapes so a script bug never wastes a chip window
    MICRO, S, D, H, F, V, L = 2, 256, 128, 4, 512, 1024, 2
else:
    MICRO, S, D, H, F, V, L = 16, 1024, 768, 12, 3072, 50304, 12
N = MICRO * S
CHUNK = 256 if S >= 1024 else 128


def timed_scan(make_step, reps=30):
    """Amortized timing: ``reps`` iterations of make_step(i) -> fp32 scalar
    run inside ONE compiled lax.scan, so per-dispatch overhead (~3 ms then —
    enough to make a 19-GFLOP GEMM read as 5 TFLOPS when timed per-call,
    which is exactly what the first cut of this script recorded) is paid
    once, not per rep. The loop index feeds each step so XLA cannot hoist
    the work out of the loop; the carried sum defeats DCE."""

    def body(acc, i):
        return acc + make_step(i), None

    f = jax.jit(
        lambda: jax.lax.scan(body, jnp.zeros((), jnp.float32),
                             jnp.arange(reps))[0])
    np.asarray(jax.device_get(f()))  # compile + warm
    t0 = time.perf_counter()
    r = f()
    np.asarray(jax.device_get(r))
    return (time.perf_counter() - t0) / reps


def matmul_tflops(m, k, n, reps=30):
    a = jnp.ones((m, k), jnp.bfloat16)
    b = jnp.ones((k, n), jnp.bfloat16)

    def step(i):
        a2 = a.at[0, 0].add(i.astype(jnp.bfloat16))  # loop-variant: no hoisting
        # reduce the FULL product: slicing one element lets XLA reorder the
        # slice above the dot and time a k-length dot instead of the GEMM
        return jnp.sum((a2 @ b).astype(jnp.float32))

    dt = timed_scan(step, reps=reps)
    return 2 * m * k * n / dt / 1e12, dt


def main():
    rows = []
    plat = jax.devices()[0].platform
    # --- pure matmul ceiling at the six GEMM shapes of one layer step ---
    # fwd: x@Wqkv-ish (768x768 x4 as one 768x2304 + proj), x@Wi, h@Wo
    # bwd per matmul: dY@W^T (same flop) and X^T@dY (reduction over N)
    shapes = {
        "attn_fwd_768x768": (N, D, D),
        "attn_bwd_dW_768": (D, N, D),      # X^T @ dY: [768,16384]x[16384,768]
        "mlp_fwd_768x3072": (N, D, F),
        "mlp_fwd_3072x768": (N, F, D),
        "mlp_bwd_dW_3072": (D, N, F),
        "vocab_chunk_fwd": (MICRO * CHUNK, D, V),
    }
    for name, (m, k, n) in shapes.items():
        tf, dt = matmul_tflops(m, k, n)
        rows.append({"component": name, "shape": [m, k, n],
                     "tflops": round(tf, 1), "ms": round(dt * 1e3, 3)})

    # --- flash attention fwd+bwd at bench shapes ---
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    q = jnp.ones((MICRO, S, H, D // H), jnp.bfloat16)  # kernel layout [B,S,H,Dh]

    def attn_step(i):
        def loss(q):
            o = flash_attention(q, q, q, causal=True,
                                block_q=1024, block_k=1024)
            return jnp.sum(o.astype(jnp.float32))
        q2 = q.at[0, 0, 0, 0].add(i.astype(jnp.bfloat16))
        return jnp.sum(jax.grad(loss)(q2).astype(jnp.float32))

    dt = timed_scan(attn_step, reps=20)
    # fwd 4*S*S*Dh MACs per head (QK^T+AV) /2 causal, bwd ~2.5x fwd
    attn_flops = MICRO * H * (2 * 2 * S * S * (D // H)) / 2 * 3.5
    rows.append({"component": "flash_attn_fwd+bwd", "shape": [MICRO, S, H, D // H],
                 "tflops": round(attn_flops / dt / 1e12, 1), "ms": round(dt * 1e3, 3)})

    # --- LayerNorm fwd+bwd (the fp32 round trip) ---
    from deepspeed_tpu.models.transformer import layer_norm

    x = jnp.ones((MICRO, S, D), jnp.bfloat16)
    sc = jnp.ones((D,), jnp.float32)
    bi = jnp.zeros((D,), jnp.float32)

    def ln_step(i):
        x2 = x.at[0, 0, 0].add(i.astype(jnp.bfloat16))
        return jnp.sum(jax.grad(
            lambda x: jnp.sum(layer_norm(x, sc, bi, 1e-5).astype(jnp.float32))
        )(x2).astype(jnp.float32))

    dt = timed_scan(ln_step, reps=30)
    rows.append({"component": "layernorm_fwd+bwd", "shape": [MICRO, S, D],
                 "tflops": None, "ms": round(dt * 1e3, 3),
                 "gbps": round(2 * 2 * x.size * 2 / dt / 1e9, 1)})

    # --- assemble the budget ---
    per = {r["component"]: r["ms"] for r in rows}
    # per micro-step (fwd+bwd, dots_and_flash = no matmul recompute):
    # attn block: qkv+proj = 4 fwd GEMMs [N,768,768]; bwd = 4 dX (same shape)
    #             + 4 dW (reduction shape)
    # mlp block: fwd 2 GEMMs; bwd 2 dX + 2 dW
    layer_ms = (
        4 * per["attn_fwd_768x768"] * 2       # fwd + dX
        + 4 * per["attn_bwd_dW_768"]
        + (per["mlp_fwd_768x3072"] + per["mlp_fwd_3072x768"]) * 2
        + 2 * per["mlp_bwd_dW_3072"]
        + per["flash_attn_fwd+bwd"]
        + 2 * per["layernorm_fwd+bwd"]
    )
    vocab_ms = (S // CHUNK) * per["vocab_chunk_fwd"] * 3  # fwd + dX + dW
    micro_ms = L * layer_ms + vocab_ms
    gas = 4
    predicted_step_ms = gas * micro_ms

    # --- measured end-to-end step at the sweep-winning config ---
    import deepspeed_tpu
    from deepspeed_tpu.models.transformer import Model, TransformerConfig

    B_total = MICRO * gas
    cfg = TransformerConfig(
        vocab_size=V, max_seq_len=S, num_layers=L, num_heads=H, hidden_size=D,
        pos_emb="learned", dtype=jnp.bfloat16, remat=True,
        remat_policy="dots_and_flash", attn_impl="flash",
        flash_block_q=min(1024, S), flash_block_k=min(1024, S),
        loss_chunk_size=CHUNK)
    engine, _, _, _ = deepspeed_tpu.initialize(model=Model(cfg), config={
        "train_batch_size": B_total, "train_micro_batch_size_per_gpu": MICRO,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "AdamW", "params": {"lr": 6e-4, "weight_decay": 0.1}},
        "zero_optimization": {"stage": 1}, "bf16": {"enabled": True},
        "gradient_clipping": 1.0, "steps_per_print": 10**9, "mesh": {"data": -1}})
    toks = np.random.default_rng(0).integers(0, V, (B_total, S + 1)).astype(np.int32)
    batch = {"tokens": toks}
    m = engine.train_batch(batch)
    np.asarray(jax.device_get(m["loss"]))
    for _ in range(3):
        m = engine.train_batch(batch)
    np.asarray(jax.device_get(m["loss"]))
    t0 = time.perf_counter()
    for _ in range(10):
        m = engine.train_batch(batch)
    np.asarray(jax.device_get(m["loss"]))
    step_ms = (time.perf_counter() - t0) / 10 * 1e3

    out = {
        "platform": plat,
        "components": rows,
        "budget_ms": {"per_layer": round(layer_ms, 2),
                      "vocab_loss": round(vocab_ms, 2),
                      "predicted_step": round(predicted_step_ms, 1),
                      "measured_step": round(step_ms, 1),
                      "residual_pct": round(
                          100 * (step_ms - predicted_step_ms) / step_ms, 1)},
        "tok_s": round(B_total * S / step_ms * 1e3, 1),
    }
    name = ("roofline_r5_tiny.json" if os.environ.get("DSTPU_ROOFLINE_TINY")
            else "roofline_r5.json")  # self-check must never clobber the chip artifact
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out, indent=1), flush=True)
    os._exit(0)


if __name__ == "__main__":
    main()
