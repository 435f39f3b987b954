"""Ring attention — context parallelism over the 'context' mesh axis.

Beyond-reference feature (SURVEY.md §5: sequence/context parallelism is absent
in DeepSpeed v0.7.1; the north-star adds it as a first-class axis). Sequence
is sharded over 'context'; K/V blocks rotate around the ring via ``ppermute``
while each device accumulates its queries' attention with numerically-stable
online-softmax merging (flash-attention style running max/denominator), so
peak memory is O(S_local²) instead of O(S²) and the S axis scales with the
ring size.

Causality across blocks: with sequence laid out contiguously, ring rank r owns
queries [r·S_loc, (r+1)·S_loc). After j rotations a device holds K/V from rank
(r - j) mod R: those keys are fully in the past iff src < r, fully in the
future iff src > r, and need the local causal mask iff src == r.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ..comm.collectives import ppermute

NEG_INF = -1e30


def _block_attn(q, k, v, mask):
    """One block: returns (unnormalized out [B,Sq,H,D], row max m [B,H,Sq],
    row denom l [B,H,Sq])."""
    Dh = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / math.sqrt(Dh)
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1)  # [B,H,Sq]
    p = jnp.exp(s - m[..., None])
    # rows with no visible keys: m == NEG_INF → force p to 0
    p = jnp.where(jnp.isfinite(m)[..., None], p, 0.0)
    m = jnp.where(jnp.isfinite(m), m, NEG_INF)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v).astype(jnp.float32)
    return o, m, l


def ring_attention(q, k, v, axis_name: str = "context"):
    """Causal ring attention for [B, S_local, H, Dh] inputs inside
    shard_map/jit over a mesh with ``axis_name``. Returns [B, S_local, H, Dh].
    """
    R = lax.axis_size(axis_name)
    rank = lax.axis_index(axis_name)
    B, Sq, H, Dh = q.shape

    q_pos = jnp.arange(Sq)[:, None]
    k_pos = jnp.arange(Sq)[None, :]
    local_mask = (q_pos >= k_pos)[None, None]  # [1,1,Sq,Sk]
    full_mask = jnp.ones((1, 1, Sq, Sq), bool)
    none_mask = jnp.zeros((1, 1, Sq, Sq), bool)

    perm = [(i, (i + 1) % R) for i in range(R)]

    def step(carry, j):
        o_acc, m_acc, l_acc, kj, vj = carry
        src = (rank - j) % R
        mask = jnp.where(
            src < rank, full_mask, jnp.where(src == rank, local_mask, none_mask)
        )
        o_b, m_b, l_b = _block_attn(q, kj, vj, mask)
        # online-softmax merge of (o_acc, m_acc, l_acc) with the new block
        m_new = jnp.maximum(m_acc, m_b)
        a = jnp.exp(m_acc - m_new)
        b = jnp.exp(m_b - m_new)
        o_acc = o_acc * a[..., None].swapaxes(1, 2) + o_b * b[..., None].swapaxes(1, 2)
        l_acc = l_acc * a + l_b * b
        # comm/ wrapper (not bare lax): the collective X-ray's byte
        # accounting must see the ring's per-hop KV traffic
        kj = ppermute(kj, axis_name, perm)
        vj = ppermute(vj, axis_name, perm)
        return (o_acc, m_new, l_acc, kj, vj), None

    o0 = jnp.zeros((B, Sq, H, Dh), jnp.float32)
    m0 = jnp.full((B, H, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    (o, m, l, _, _), _ = lax.scan(step, (o0, m0, l0, k, v), jnp.arange(R))
    l = jnp.maximum(l, 1e-20)
    out = o / l[..., None].swapaxes(1, 2)
    return out.astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh, axis_name: str = "context"):
    """shard_map wrapper for calling from un-shard_mapped (pjit) code:
    [B, S_global, H, Dh] arrays sharded on S over ``axis_name``."""
    from jax.sharding import PartitionSpec as P

    spec = P(("data", "fsdp"), axis_name, None, None)
    fn = jax.shard_map(
        partial(ring_attention, axis_name=axis_name),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
