"""MoE layer glue (reference: moe/layer.py:15 ``MoE`` wraps gate + experts +
MOELayer). Used by models/transformer.py when ``moe_every > 0``."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .experts import apply_experts, experts_logical_axes, init_experts
from .sharded_moe import moe_dispatch_combine


def init_moe_params(rng, num_routed: int, num_experts: int, d_model: int, d_ff: int):
    """Stacked MoE params with leading [num_routed] dim."""
    keys = jax.random.split(rng, num_routed + 1)
    gates = jnp.stack(
        [jax.random.normal(k, (d_model, num_experts)) * (1.0 / math.sqrt(d_model)) for k in keys[:num_routed]]
    )
    banks = [init_experts(jax.random.fold_in(keys[-1], i), num_experts, d_model, d_ff) for i in range(num_routed)]
    all_experts = jax.tree.map(lambda *xs: jnp.stack(xs), *banks)
    return {"gate": gates, "experts": all_experts}


def moe_logical_axes():
    ex = experts_logical_axes()
    return {
        "gate": (None, "embed", None),
        "experts": {k: (None,) + v for k, v in ex.items()},
    }


def moe_ffn_apply(cfg, moe_params, h: jnp.ndarray, mesh=None):
    """h [B, S, M] -> (out [B, S, M], aux_loss). One transformer MoE-FFN."""
    B, S, M = h.shape
    x = h.reshape(B * S, M)
    out, aux = moe_dispatch_combine(
        x,
        moe_params["gate"],
        lambda ei: apply_experts(moe_params["experts"], ei),
        capacity_factor=cfg.moe_capacity_factor,
        top_k=cfg.moe_top_k,
        mesh=mesh,
    )
    return out.reshape(B, S, M), aux


def moe_ffn_dense(cfg, moe_params, h: jnp.ndarray):
    """Capacity-free MoE for DECODE: every token gets its exact top-k expert
    mix, no dropping. With a handful of tokens per step the capacity
    heuristic (tokens * factor / experts) degenerates to ~1 slot and drops
    colliding tokens; computing all experts densely costs E small GEMMs —
    negligible at decode batch sizes and bitwise-stable (the reference's
    inference MoE routes without capacity drops, moe_inference.py)."""
    B, S, M = h.shape
    x = h.reshape(B * S, M)
    logits = x @ moe_params["gate"].astype(x.dtype)  # [T, E]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    if cfg.moe_top_k < probs.shape[-1]:
        vals, _ = jax.lax.top_k(probs, cfg.moe_top_k)
        thresh = vals[..., -1:]
        probs = jnp.where(probs >= thresh, probs, 0.0)
        if cfg.moe_top_k >= 2:
            # GShard renormalizes only multi-expert mixes (top2_gating:92);
            # top-1 keeps the raw gate prob as the scale (top1_gating:56)
            probs = probs / jnp.maximum(probs.sum(-1, keepdims=True), 1e-9)
    # every expert on every token: [E, T, M]
    E = probs.shape[-1]
    xe = jnp.broadcast_to(x[None], (E,) + x.shape)
    ye = apply_experts(moe_params["experts"], xe)  # [E, T, M]
    out = jnp.einsum("te,etm->tm", probs.astype(x.dtype), ye)
    return out.reshape(B, S, M)
