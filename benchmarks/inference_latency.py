"""Generative-inference latency harness: p50/p90 per-token decode latency.

The reference's inference north-star is DS-Inference p50 latency (BASELINE.md:
2.3x lower vs PyTorch at MP=4, docs/_posts/2021-05-05-inference-kernel-
optimization.md). This harness measures, on the current backend:

  * prefill latency (one compiled call over the prompt)
  * per-token decode latency p50/p90 — each decode step dispatched separately
    so the distribution is observable (generation normally runs as one fused
    scan; that path is strictly faster)

Usage:  python benchmarks/inference_latency.py [--model gpt2|bloom7b-class]
                                               [--batch 1] [--prompt 128]
                                               [--tokens 64]
Prints one JSON line.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np


def _sync(x):
    leaf = jax.tree.leaves(x)[0]
    np.asarray(jax.device_get(leaf.ravel()[0]))


MODELS = {
    # flagship bench model
    "gpt2": dict(vocab_size=50304, num_layers=12, num_heads=12, hidden_size=768,
                 max_seq_len=1024, pos_emb="learned"),
    # BLOOM-7B-class geometry (alibi): 30L x 4096h x 32 heads
    "bloom7b-class": dict(vocab_size=250880, num_layers=30, num_heads=32,
                          hidden_size=4096, max_seq_len=2048, pos_emb="alibi"),
    # small CPU smoke model
    "smoke": dict(vocab_size=1024, num_layers=2, num_heads=4, hidden_size=64,
                  max_seq_len=256, pos_emb="rotary"),
}


def _random_quantized_params(cfg, seed: int = 0):
    """Build int8 weight-only params DIRECTLY in quantized storage — a
    multi-billion model's fp32 init (4 bytes/param) would OOM a 16 GB chip
    before quantization could run. Random weights are statistically shaped
    (int8 codes + fan-in-scaled group scales), which is all a latency
    measurement needs (VERDICT r4 #5: 'random-init fine'). lm_head is
    omitted so the output projection ties to wte (half the embedding HBM)."""
    import jax.numpy as jnp
    from deepspeed_tpu.models import transformer as tfm
    from deepspeed_tpu.models.transformer import quantizable_layer_leaves

    shapes = jax.eval_shape(lambda k: tfm.init(cfg, k), jax.random.PRNGKey(0))
    g = cfg.weight_group_size
    rng = np.random.default_rng(seed)

    layer_shapes = shapes["layers"]
    targets = quantizable_layer_leaves(
        {k: v for k, v in layer_shapes.items()}, g)

    def build(name, sd):
        shp = tuple(sd.shape)
        if name in targets:
            gs = targets[name]
            fan_in = shp[-2] if len(shp) >= 2 else shp[-1]
            q = jnp.asarray(rng.integers(-127, 128, size=shp, dtype=np.int8))
            s_shape = shp[:-1] + (shp[-1] // gs,)
            # scale so dequantized weights ~ N(0, 1/fan_in): std(int8)≈73
            scale = np.full(s_shape, 1.0 / (73.0 * np.sqrt(fan_in)), np.float32)
            return {"q": q, "s": jnp.asarray(scale)}
        if "scale" in name:
            return jnp.ones(shp, jnp.bfloat16)
        if "bias" in name or name.startswith("b"):
            return jnp.zeros(shp, jnp.bfloat16)
        return jnp.asarray(
            rng.standard_normal(shp, np.float32) * 0.02, jnp.bfloat16)

    params = {}
    for k, v in shapes.items():
        if k == "lm_head":
            continue  # tie to wte
        if k == "layers":
            params["layers"] = {lk: build(lk, lv) for lk, lv in v.items()}
        else:
            params[k] = build(k, v)
    return params


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default=None, choices=list(MODELS))
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--prompt", type=int, default=128)
    ap.add_argument("--tokens", type=int, default=64)
    ap.add_argument("--decode-attn", default="kernel", choices=["kernel", "xla"])
    ap.add_argument("--int8", action="store_true",
                    help="int8 weight-only storage, random-init in quantized "
                         "form (multi-billion models on one 16 GB chip)")
    ap.add_argument("--dry-trace", action="store_true",
                    help="trace the prefill/decode/generate programs at the "
                         "requested shapes without compiling or executing — "
                         "CPU-side de-risk before burning a chip window")
    args = ap.parse_args()

    platform = jax.default_backend()
    on_tpu = platform == "tpu"
    name = args.model or ("gpt2" if on_tpu else "smoke")
    print(f"[inference_latency] platform={platform} model={name}" + (
        "" if on_tpu else " — not a TPU: float32, and the toy 'smoke' model "
        "unless --model is given; times below are not device numbers"),
        flush=True)

    import sys, os
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.models.transformer import Model, TransformerConfig

    spec = MODELS[name]
    prompt_len = min(args.prompt, spec["max_seq_len"] // 2)
    cfg = TransformerConfig(
        dtype=jnp.bfloat16 if on_tpu else jnp.float32,
        decode_attn=args.decode_attn,
        **({"weight_bits": 8, "weight_group_size": 64} if args.int8 else {}),
        **spec,
    )
    model = Model(cfg)
    if args.int8:
        qparams = _random_quantized_params(cfg)
        eng = InferenceEngine(model=model, config={"dtype": "bf16" if on_tpu else "fp32"},
                              params=qparams)
    else:
        eng = InferenceEngine(model=model, config={"dtype": "bf16" if on_tpu else "fp32"})

    B = args.batch
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, spec["vocab_size"], size=(B, prompt_len)).astype(np.int32)

    from deepspeed_tpu.models import transformer as tfm

    Smax = -(-(prompt_len + args.tokens) // 128) * 128
    params = eng.params

    prefill = jax.jit(
        lambda p, t, c: tfm.apply_with_cache(cfg, p, t, c, 0, last_only=True)
    )
    decode = jax.jit(
        lambda p, t, c, pos: tfm.apply_with_cache(cfg, p, t, c, pos)
    )

    cache = tfm.init_cache(cfg, B, Smax, dtype=cfg.dtype)

    if args.dry_trace:
        abstract = lambda t: jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
        ap_, cp_ = abstract(params), abstract(cache)
        tp_ = jax.ShapeDtypeStruct((B, prompt_len), jnp.int32)
        t1_ = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        n1 = len(prefill.lower(ap_, tp_, cp_).as_text())
        n2 = len(decode.lower(ap_, t1_, cp_, prompt_len).as_text())
        print(json.dumps({"metric": f"{name} dry-trace", "batch": B,
                          "prefill_hlo_kchars": n1 // 1000,
                          "decode_hlo_kchars": n2 // 1000, "ok": True,
                          "platform": platform}),
              flush=True)
        return

    logits, cache = prefill(params, jnp.asarray(prompt), cache)  # compile
    _sync(logits)
    # median of several calls — a single timed call right after compilation
    # can catch residual backend work and report seconds for a ~10ms program
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        logits, cache2 = prefill(params, jnp.asarray(prompt), cache)
        _sync(logits)
        times.append((time.perf_counter() - t0) * 1e3)
    prefill_ms = float(np.median(times))

    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    logits1, cache2 = decode(params, tok, cache2, prompt_len)  # compile
    _sync(logits1)

    lat = []
    pos = prompt_len
    for i in range(args.tokens):
        t0 = time.perf_counter()
        logits1, cache2 = decode(params, tok, cache2, pos)
        _sync(logits1)
        lat.append((time.perf_counter() - t0) * 1e3)
        tok = jnp.argmax(logits1[:, -1], axis=-1).astype(jnp.int32)[:, None]
        pos += 1

    lat = np.asarray(lat)

    # chained decode: steps dispatched back-to-back, one sync at the end.
    # Still two host dispatches per token (decode + argmax) riding the
    # dispatch queue — an intermediate between the per-step-sync numbers
    # above (which also pay a round-trip per token) and the fused generate
    # below (the actual serving path).
    tok_c = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    cache_c = cache2
    t0 = time.perf_counter()
    pos = prompt_len
    for _ in range(args.tokens):
        logits1, cache_c = decode(params, tok_c, cache_c, pos)
        tok_c = jnp.argmax(logits1[:, -1], axis=-1).astype(jnp.int32)[:, None]
        pos += 1
    _sync(logits1)
    chained_ms = (time.perf_counter() - t0) * 1e3 / args.tokens

    # the serving path: the ENTIRE prefill + decode loop as one compiled
    # program (InferenceEngine.generate lowers decode to a lax.scan) — one
    # dispatch for the whole generation, so host round-trips are out
    # of the measurement. Differencing two generation lengths cancels the
    # prefill + dispatch constant so the metric is per DECODE token, the
    # same definition chained_ms uses.
    t_half = args.tokens // 2 or 1
    eng.generate(prompt, max_new_tokens=args.tokens)   # compile T
    eng.generate(prompt, max_new_tokens=t_half)        # compile T/2
    t0 = time.perf_counter()
    toks_out = eng.generate(prompt, max_new_tokens=args.tokens)
    t_full = time.perf_counter() - t0
    t0 = time.perf_counter()
    eng.generate(prompt, max_new_tokens=t_half)
    t_short = time.perf_counter() - t0
    fused_ms = (t_full - t_short) * 1e3 / (args.tokens - t_half)
    assert toks_out.shape == (B, args.tokens)

    n_params = sum(
        leaf.size * (2 if leaf.dtype == jnp.uint8 else 1)  # packed int4: 2/byte
        for leaf in jax.tree.leaves(params)
    )
    wq = "-int8" if args.int8 else ""
    out = {
        "metric": f"{name}{wq} decode latency p50 (batch {B}, prompt {prompt_len})",
        "n_params": int(n_params),
        "value": round(float(np.percentile(lat, 50)), 2),
        "unit": "ms/token",
        "p90_ms": round(float(np.percentile(lat, 90)), 2),
        "chained_ms_per_token": round(chained_ms, 2),
        "fused_generate_ms_per_token": round(fused_ms, 2),
        "prefill_ms": round(prefill_ms, 2),
        "decode_attn": args.decode_attn,
        "platform": platform,
        "tokens_per_sec": round(1000.0 / fused_ms * B, 1),
    }
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
