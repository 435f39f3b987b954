"""Fused single-token decode attention over a KV cache (Pallas).

The reference's generative-inference hot kernel is ``softmax_context`` —
attention of one new token against the incremental KV cache, fused with the
causal mask over the valid prefix (csrc/transformer/inference/csrc/
pt_binding.cpp:1237-1283). The TPU failure mode it prevents is different from
CUDA's: a dense XLA attention over the whole [Smax] cache re-reads the entire
allocation every decoded token, so decode becomes O(Smax) HBM traffic no
matter how short the sequence actually is.

This kernel:
  * processes one batch row per outer grid step, all H heads together (the
    per-head work is a [H, D] x [D, Bk] matvec batch — decode attention is
    HBM-bandwidth-bound, so the job is streaming k/v, not MXU utilization);
  * streams the cache in ``block_k`` chunks along the innermost grid dim with
    online softmax in VMEM scratch (same machinery as flash_attention);
  * is length-aware via scalar prefetch: the per-row ``pos`` feeds the
    BlockSpec index maps, which CLAMP out-of-range block indices to the last
    valid block — Mosaic's pipeline emitter skips re-fetching a block whose
    indices equal the previous step's, so blocks past ``pos`` cost neither
    HBM bandwidth nor compute (``pl.when`` guards the FLOPs).

Layout: q [B, H, D] (the new token, post-rotary), k/v cache [B, Smax, H, D],
pos [B] int32 = index of the newest valid entry (keys [0, pos] attended).
With ``layer`` the caches are a model's whole [L, B, Smax, H, D] stacks and the
layer index is a second scalar-prefetch operand of the K/V index maps: the
kernel streams layer ``layer`` where it lies, and the caller slices nothing
out (XLA cannot fuse a slice into a ``pallas_call`` operand).

The per-row ``pos`` vector is what makes the kernel continuous-batching
ready: the serving engine's single compiled decode step
(inference/serving.py) feeds one slot per batch row, each at its own
absolute position — rows are never in lock-step, and a freshly admitted
slot (small pos) streams only its own short prefix while a long-running
neighbour streams its full one.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_default

NEG_INF = -1e30


def _decode_kernel(pos_ref, layer_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                   *, sm_scale, block_k, num_kb, slope_ref=None):
    # layer_ref is read by the K/V index maps only
    # All-elementwise formulation: decode attention at T=1 is a matvec per
    # head — pure HBM streaming, so the MXU buys nothing and the VPU does the
    # whole block in consistent (kk, H, D)-shaped broadcasts/reductions.
    # (A head-batched dot_general fails Mosaic's attr parser on hardware, and
    # per-head 2D-dot blocks violate the (sublane, lane) tiling rules for the
    # [B, S, H, D] cache layout — this shape avoids dots entirely.)
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = pos_ref[b]
    jmax = pos // block_k

    @pl.when(j <= jmax)
    def _compute():
        q3 = q_ref[...].astype(jnp.float32)       # [1, H, D]
        k3 = k_ref[0].astype(jnp.float32)         # [Bk, H, D]
        v3 = v_ref[0].astype(jnp.float32)
        # s[kk, h] = sum_d q[h, d] * k[kk, h, d], kept as [Bk, H, 1]
        s3 = sm_scale * jnp.sum(k3 * q3, axis=2, keepdims=True)
        k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s3.shape, 0)
        if slope_ref is not None:
            # fused alibi (BLOOM): bias = slope_h * (k_pos - q_pos), computed
            # from positions — the reference's softmax_context alibi path
            # (pt_binding.cpp:1231-1283); q_pos == pos for the new token
            s3 = s3 + slope_ref[...] * (k_pos - pos).astype(jnp.float32)
        s3 = jnp.where(k_pos <= pos, s3, NEG_INF)
        m_prev = m_scr[:, :, 0:1]                 # [1, H, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s3, axis=0, keepdims=True))
        p3 = jnp.exp(s3 - m_new)                  # [Bk, H, 1]
        alpha = jnp.exp(m_prev - m_new)           # [1, H, 1]
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = l_scr[...] * alpha + jnp.broadcast_to(
            jnp.sum(p3, axis=0, keepdims=True), l_scr.shape)
        pv = jnp.sum(p3 * v3, axis=0, keepdims=True)  # [1, H, D]
        acc_scr[...] = acc_scr[...] * alpha + pv

    @pl.when(j == num_kb - 1)
    def _finalize():
        l = l_scr[:, :, 0:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_scr[...] / l_safe).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, pos, *, layer=None, sm_scale=None,
                     block_k: int = 512, interpret: bool | None = None, alibi_slopes=None):
    """q [B, H, D], k/v_cache [B, Smax, H, D], pos [B] or scalar int32 (index
    of the newest valid cache entry) -> attention output [B, H, D]. With
    ``layer`` (int32 scalar, may be traced) k/v_cache are the stacked
    [L, B, Smax, H, D] caches and layer ``layer`` of them is read in place.

    Equivalent to ``xla_attention(q[:, None], k_cache, v_cache,
    causal_offset=pos)[:, 0]`` but reads only the valid cache prefix.
    ``alibi_slopes`` [H] fuses the BLOOM alibi bias in-kernel (computed from
    cache positions, nothing streamed).
    """
    B, H, D = q.shape
    if layer is None:  # one layer's cache is a stack of one: a free reshape
        k_cache, v_cache, layer = k_cache[None], v_cache[None], 0
    Smax = k_cache.shape[2]
    if sm_scale is None:
        sm_scale = 1.0 / (D ** 0.5)
    block_k = min(block_k, Smax)
    while block_k > 1 and Smax % block_k:
        block_k //= 2
    if Smax % block_k:
        raise ValueError(
            f"cache length {Smax} has no power-of-two block divisor; allocate "
            f"the KV cache rounded up to a multiple of 128 (inference engine "
            f"does this automatically)"
        )
    num_kb = Smax // block_k
    if interpret is None:
        interpret = interpret_default()
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))

    def kv_block(b, j, p_ref, l_ref):
        return (l_ref[0], b, jnp.minimum(j, p_ref[b] // block_k), 0, 0)

    in_specs = [
        pl.BlockSpec((1, H, D), lambda b, j, p, l: (b, 0, 0)),
        pl.BlockSpec((None, 1, block_k, H, D), kv_block),
        pl.BlockSpec((None, 1, block_k, H, D), kv_block),
    ]
    operands = [q, k_cache, v_cache]
    base = functools.partial(
        _decode_kernel, sm_scale=sm_scale, block_k=block_k, num_kb=num_kb
    )
    if alibi_slopes is None:
        kernel = base
    else:
        slopes_arr = jnp.asarray(alibi_slopes, jnp.float32).reshape(1, H, 1)
        in_specs.append(pl.BlockSpec((1, H, 1), lambda b, j, p, l: (0, 0, 0)))
        operands.append(slopes_arr)

        def kernel(pos_ref, layer_ref, q_ref, k_ref, v_ref, s_ref, o_ref, m_scr, l_scr, acc_scr):
            return base(pos_ref, layer_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                        acc_scr, slope_ref=s_ref)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, num_kb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, H, D), lambda b, j, p, l: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, H, 1), jnp.float32),
            pltpu.VMEM((1, H, 1), jnp.float32),
            pltpu.VMEM((1, H, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        interpret=interpret,
        name="decode_attention",
    )(pos, layer, *operands)
    return out
