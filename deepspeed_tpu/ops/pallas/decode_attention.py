"""Fused single-token decode attention over a KV cache (Pallas).

The reference's generative-inference hot kernel is ``softmax_context`` —
attention of one new token against the incremental KV cache, fused with the
causal mask over the valid prefix (csrc/transformer/inference/csrc/
pt_binding.cpp:1237-1283). The TPU failure mode it prevents is different from
CUDA's: a dense XLA attention over the whole [Smax] cache re-reads the entire
allocation every decoded token, so decode becomes O(Smax) HBM traffic no
matter how short the sequence actually is.

The kernel's time is set by the K/V bytes that are live (PR 58):

  * **it walks live blocks only, rows back to back.** The grid is flat, one
    step a (row, block) entry of a work list that ``decode_walk`` builds from
    ``pos`` (a row at ``pos`` has ``pos // block + 1`` live blocks; a model
    builds the list once a decode step, outside its layer loop) and that the
    index maps read by scalar prefetch. A row's last block is followed by the
    next row's first, so the pipeline's fetch of the next entry always hides
    under a step that computes, and the grid's LENGTH is the list's (a dynamic
    grid bound): no step fetches nothing or computes nothing;
  * **the block comes from the shape** (``block_rows``): as many cached
    positions as make a fetch long enough to cover a grid step's fixed cost,
    and no more than a quarter of the cache's length, so that a row fetches
    little past what it holds;
  * **a block's arithmetic is two matrix products.** The block is read as
    [block * Hkv, D] (a free view of [block, Hkv, D]: a position's heads are
    one tile's sublanes): scores = q [Hq, D] x that, transposed -> [Hq,
    block * Hkv], of which a query head keeps the columns of ITS K/V head (a
    mask beside the causal one); probabilities x the V view -> [Hq, D], the
    accumulator's own shape. The statistics are [Hq, 1] over lane-dense
    [Hq, block * Hkv] scores. Arithmetic: scores, maximum, exponentials, sum
    and accumulator in float32 (bfloat16 x bfloat16 products accumulated in
    float32 are exact); the probabilities enter the second product as TWO
    terms of the cache's dtype, p = hi + lo (16 bits of mantissa for a
    bfloat16 cache where the definition, ``xla_attention``, rounds to 8), or
    as they are for a float32 cache.

Layout: q [B, Hq, D] (the new token, post-rotary), k/v cache [B, Smax, Hkv, D],
pos [B] int32 = index of the newest valid entry (keys [0, pos] attended).
With ``layer`` the caches are a model's whole [L, B, Smax, Hkv, D] stacks and
the layer index is a scalar-prefetch operand of the K/V index maps: the kernel
streams layer ``layer`` where it lies, and the caller slices nothing out (XLA
cannot fuse a slice into a ``pallas_call`` operand). Query heads and K/V heads
are separate names throughout (query head i reads K/V head i // (Hq // Hkv));
only Hq == Hkv has a caller and tests today.

The per-row ``pos`` vector is what makes the kernel continuous-batching
ready: the serving engine's single compiled decode step
(inference/serving.py) feeds one slot per batch row, each at its own
absolute position — rows are never in lock-step, and a freshly admitted
slot (small pos) streams only its own short prefix while a long-running
neighbour streams its full one.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_default

NEG_INF = -1e30

# One v5e chip, as ``models/transformer.cache_attention_form`` keeps its own: HBM
# gives 819 GB/s, so a block whose K and V together are FETCH_BYTES is 1.28 us of
# fetch, which still covers a grid step's fixed cost (PR 58, the kernel alone on the
# chip at 16 heads of 128 in bfloat16: 1.39 us a step at 1 MiB, 2.77 at 2 MiB, but
# 0.83 at 0.5 MiB, where the step and not the bytes sets the time).
FETCH_BYTES = 1 << 20
MIN_BLOCK = 128   # positions: below it a step's fixed cost is the block's time
LENGTH_SHARE = 4  # a block is at most Smax / 4: a row fetches <= ~1.25 x what it holds


def block_rows(smax: int, row_bytes: int) -> int:
    """Cached positions a grid step fetches, from the cache's length and the
    bytes of ONE position of K (= of V): the power of two whose K and V make
    FETCH_BYTES, at most ``smax / LENGTH_SHARE`` (not under MIN_BLOCK), halved
    until it divides ``smax`` (not under 8 positions, a tile's sublanes)."""
    pow2_floor = lambda n: 1 << (max(n, 1).bit_length() - 1)
    block = min(pow2_floor(FETCH_BYTES // (2 * row_bytes)),
                max(MIN_BLOCK, pow2_floor(smax // LENGTH_SHARE)))
    if block >= smax:
        return smax
    while block > 8 and smax % block:
        block //= 2
    if smax % block:
        raise ValueError(
            f"cache length {smax} has no power-of-two block divisor; allocate "
            f"the KV cache rounded up to a multiple of 128 (inference engine "
            f"does this automatically)"
        )
    return block


class Walk(NamedTuple):
    """``decode_walk``'s work list: entry g < ``n_live`` is the g-th live block in row order."""
    rows: jax.Array    # int32 [batch * smax / block]: the entry's row ...
    blocks: jax.Array  # ... and its block in that row
    n_live: jax.Array  # int32 scalar: the grid's length
    pos: jax.Array     # int32 [batch], inside the cache: what the kernel masks by
    block: int         # cached positions a block


def decode_walk(pos, batch: int, smax: int, block: int) -> Walk:
    """The kernel's work list for rows at ``pos`` ([batch] or scalar) over a cache
    ``smax`` long in blocks of ``block`` positions (``block_rows``)."""
    pos = jnp.clip(jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (batch,)), 0, smax - 1)
    per_row = pos // block + 1
    ends = jnp.cumsum(per_row)
    # room for every row full; past the list's end its last entry again, so that
    # whatever reads an entry ahead of the grid's last step stays inside the cache
    g = jnp.minimum(jnp.arange(batch * (smax // block), dtype=jnp.int32), ends[-1] - 1)
    rows = jnp.sum((g[:, None] >= ends[None, :]).astype(jnp.int32), axis=1)
    return Walk(rows, g - (ends - per_row)[rows], ends[-1], pos, block)


def _decode_kernel(rows_ref, blocks_ref, layer_ref, pos_ref, q_ref, k_ref, v_ref,
                   *rest, sm_scale, block, alibi):
    # layer_ref is read by the K/V index maps only
    slope_ref = rest[0] if alibi else None
    o_ref, m_scr, l_scr, acc_scr = rest[-4:]
    g = pl.program_id(0)
    j = blocks_ref[g]
    pos = pos_ref[rows_ref[g]]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_heads, width = q_ref.shape[1:]
    kv_heads = k_ref.shape[2]
    # a position's heads are the sublanes of one tile: the view is free
    k2 = k_ref[0].reshape(block * kv_heads, width)
    v2 = v_ref[0].reshape(block * kv_heads, width)
    wide = jnp.promote_types(q_ref.dtype, k2.dtype)
    # s[h, kk * Hkv + g] = q[h] . k[kk, g]; query head h keeps g == h // group
    s = sm_scale * jax.lax.dot_general(
        q_ref[0].astype(wide), k2.astype(wide), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    q_head = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    newest = pos - j * block  # in this block's own positions
    if alibi:
        # fused alibi (BLOOM): bias = slope_h * (k_pos - q_pos), computed
        # from positions — the reference's softmax_context alibi path
        # (pt_binding.cpp:1231-1283); q_pos == pos for the new token
        s = s + slope_ref[...] * (col // kv_heads - newest).astype(jnp.float32)
    own = col % kv_heads == q_head // (q_heads // kv_heads)
    s = jnp.where(own & (col < (newest + 1) * kv_heads), s, NEG_INF)
    m_prev = m_scr[...]                          # [Hq, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)                       # [Hq, block * Hkv]
    alpha = jnp.exp(m_prev - m_new)
    m_scr[...] = m_new
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    if v2.dtype == jnp.float32:
        pv = jnp.dot(p, v2, preferred_element_type=jnp.float32)
    else:  # p = hi + lo in the cache's dtype: both products exact, summed in float32
        hi = p.astype(v2.dtype)
        lo = (p - hi.astype(jnp.float32)).astype(v2.dtype)
        pv = jnp.dot(jnp.concatenate([hi, lo], axis=0), v2,
                     preferred_element_type=jnp.float32)
        pv = pv[:q_heads] + pv[q_heads:]
    acc_scr[...] = acc_scr[...] * alpha + pv     # [Hq, D]

    @pl.when(j == pos // block)
    def _finalize():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, pos, *, layer=None, walk=None, sm_scale=None,
                     interpret: bool | None = None, alibi_slopes=None):
    """q [B, Hq, D], k/v_cache [B, Smax, Hkv, D], pos [B] or scalar int32 (index
    of the newest valid cache entry) -> attention output [B, Hq, D]. With
    ``layer`` (int32 scalar, may be traced) k/v_cache are the stacked
    [L, B, Smax, Hkv, D] caches and layer ``layer`` of them is read in place.
    ``walk`` is ``decode_walk(pos, B, Smax, block)`` where the caller has built
    it already (one list for every layer of a step); its block is then the kernel's.

    Equivalent to ``xla_attention(q[:, None], k_cache, v_cache,
    causal_offset=pos)[:, 0]`` but reads only the valid cache prefix.
    ``alibi_slopes`` [Hq] fuses the BLOOM alibi bias in-kernel (computed from
    cache positions, nothing streamed).
    """
    B, q_heads, D = q.shape
    if layer is None:  # one layer's cache is a stack of one: a free reshape
        k_cache, v_cache, layer = k_cache[None], v_cache[None], 0
    Smax, kv_heads = k_cache.shape[2:4]
    if kv_heads != q_heads:
        raise NotImplementedError(
            f"{q_heads} query heads over {kv_heads} K/V heads: the kernel's mask takes "
            "grouped heads, no caller or test does yet (a grouped model states decode_attn 'xla')")
    if sm_scale is None:
        sm_scale = 1.0 / (D ** 0.5)
    if walk is None:
        walk = decode_walk(pos, B, Smax, block_rows(Smax, kv_heads * D * k_cache.dtype.itemsize))
    rows, blocks, n_live, pos, block = walk
    if interpret is None:
        interpret = interpret_default()
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))

    by_row = lambda g, rows, blocks, l, p: (rows[g], 0, 0)
    kv_block = lambda g, rows, blocks, l, p: (l[0], rows[g], blocks[g], 0, 0)
    in_specs = [
        pl.BlockSpec((1, q_heads, D), by_row),
        pl.BlockSpec((None, 1, block, kv_heads, D), kv_block),
        pl.BlockSpec((None, 1, block, kv_heads, D), kv_block),
    ]
    operands = [q, k_cache, v_cache]
    if alibi_slopes is not None:
        in_specs.append(pl.BlockSpec((q_heads, 1), lambda g, *_: (0, 0)))
        operands.append(jnp.asarray(alibi_slopes, jnp.float32).reshape(q_heads, 1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_live,),  # the walk's own length: a dynamic bound
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, q_heads, D), by_row),
        scratch_shapes=[
            pltpu.VMEM((q_heads, 1), jnp.float32),
            pltpu.VMEM((q_heads, 1), jnp.float32),
            pltpu.VMEM((q_heads, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel, sm_scale=sm_scale, block=block,
                          alibi=alibi_slopes is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, q_heads, D), q.dtype),
        interpret=interpret,
        name="decode_attention",
    )(rows, blocks, layer, pos, *operands)
