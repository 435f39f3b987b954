"""Ulysses-style all-to-all sequence parallelism.

Beyond-reference target (SURVEY §7: the long-context story must exceed
DeepSpeed v0.7.1, whose answer was block-sparse attention only). Two
sequence-parallel attention strategies ship here:

- ring attention (parallel/ring_attention.py): K/V blocks rotate around the
  ``context`` axis via ppermute — O(S/N) memory, N steps of neighbor traffic.
- Ulysses (this file, after DeepSpeed-Ulysses): two ``all_to_all``s re-shard
  the activations from sequence-sharded to HEAD-sharded and back, so each
  device runs ordinary full-sequence attention over H/N heads. Comm volume
  is O(B·S·D/N) per all-to-all (constant in N per device), latency two
  collectives instead of N permutes — the better trade on all-to-all-capable
  ICI when H is divisible by the axis.

Per-device view (inside shard_map over ``context``):
    [B, S/N, H, Dh] --all_to_all(split H, concat S)--> [B, S, H/N, Dh]
    full causal attention on the local heads
    [B, S, H/N, Dh] --all_to_all(split S, concat H)--> [B, S/N, H, Dh]
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..comm.collectives import all_to_all


def _local_attention(q, k, v, causal: bool):
    """Plain full-sequence attention on the local head group (fp32 softmax),
    shared math with models.transformer.xla_attention."""
    from ..models.transformer import xla_attention

    return xla_attention(q, k, v, causal=causal)


def ulysses_attention(q, k, v, axis_name: str = "context", causal: bool = True):
    """Per-device function (inside shard_map): q/k/v [B, S_local, H, Dh]
    sharded on S over ``axis_name``; returns the same layout."""
    n = jax.lax.axis_size(axis_name)
    H = q.shape[2]
    if H % n:
        raise ValueError(
            f"Ulysses needs heads ({H}) divisible by the {axis_name} axis ({n}); "
            "use ring attention for head counts that do not divide")

    def seq_to_heads(x):
        # split the head dim across the axis, gather the full sequence
        # (comm/ wrapper so the collective X-ray's byte accounting sees it)
        return all_to_all(x, axis_name, split_axis=2, concat_axis=1)

    def heads_to_seq(x):
        return all_to_all(x, axis_name, split_axis=1, concat_axis=2)

    qg, kg, vg = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    out = _local_attention(qg, kg, vg, causal)
    return heads_to_seq(out.astype(q.dtype))


def ulysses_attention_sharded(q, k, v, mesh, axis_name: str = "context",
                              causal: bool = True):
    """shard_map wrapper for pjit callers: [B, S_global, H, Dh] arrays sharded
    on S over ``axis_name`` (same contract as ring_attention_sharded)."""
    from jax.sharding import PartitionSpec as P

    spec = P(("data", "fsdp"), axis_name, None, None)
    fn = jax.shard_map(
        partial(ulysses_attention, axis_name=axis_name, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
