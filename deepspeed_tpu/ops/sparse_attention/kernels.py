"""Block-sparse flash attention (Pallas) over a static block layout.

Reference: the Triton block-sparse matmul/softmax kernels
(``deepspeed/ops/sparse_attention/matmul.py:11``, ``softmax.py``) behind
``SparseSelfAttention``. TPU-native design: the [nq, nk] block layout is
STATIC (from a SparsityConfig), so each query-block row is compressed to its
list of active key blocks at trace time. The kernel grid is
(B*H, nq, max_active): the scalar-prefetch active-list feeds the BlockSpec
index map, so a skipped block is never fetched from HBM (Mosaic elides
re-fetch when the clamped index repeats) and ``pl.when`` skips its FLOPs —
the same length-aware machinery as ops/pallas/decode_attention.py.

Backward reuses the same compression: dq iterates each q-block's active k
list; dk/dv iterate the TRANSPOSED lists (per k-block active q blocks).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..pallas import interpret_default

NEG_INF = -1e30
LANES = 128  # lane-broadcast tiling for row statistics (same as flash kernel)


def layout_to_lists(layout: np.ndarray, causal: bool) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """[nq, nk] 0/1 block layout -> (k_lists [nq, A], k_counts [nq],
    q_lists [nk, Aq], q_counts [nk]); lists padded with the row's last valid
    entry (so clamped re-fetches hit a hot block). Causal masks the upper
    block triangle first."""
    layout = np.asarray(layout, dtype=bool)
    nq, nk = layout.shape
    if causal:
        layout = np.tril(layout)
    if not layout.any(axis=1).all():
        raise ValueError("sparsity layout leaves some query block with no keys")
    counts_k = layout.sum(axis=1)
    A = int(counts_k.max())
    k_lists = np.zeros((nq, A), np.int32)
    for q in range(nq):
        idx = np.nonzero(layout[q])[0]
        k_lists[q, : len(idx)] = idx
        k_lists[q, len(idx):] = idx[-1]
    counts_q = layout.sum(axis=0)
    Aq = int(max(1, counts_q.max()))
    q_lists = np.zeros((nk, Aq), np.int32)
    for k in range(nk):
        idx = np.nonzero(layout[:, k])[0]
        if len(idx) == 0:
            continue  # key block never attended; grid step masked out
        q_lists[k, : len(idx)] = idx
        q_lists[k, len(idx):] = idx[-1]
    return k_lists, counts_k.astype(np.int32), q_lists, counts_q.astype(np.int32)


def _causal_mask(s, qi, kj, block: int):
    q_pos = qi * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = kj * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(q_pos >= k_pos, s, NEG_INF)


def _fwd_kernel(k_list_ref, k_count_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, sm_scale, causal, block, max_a):
    qi = pl.program_id(1)
    a = pl.program_id(2)

    @pl.when(a == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(a < k_count_ref[qi])
    def _compute():
        kj = k_list_ref[qi, a]
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = sm_scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if causal:
            s = _causal_mask(s, qi, kj, block)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev[:, 0:1] - m_new[:, 0:1])
        m_scr[...] = jnp.broadcast_to(m_new[:, 0:1], m_scr.shape)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(a == max_a - 1)
    def _finalize():
        l = l_scr[:, 0:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(
            m_scr[:, 0:1] + jnp.log(l_safe), lse_ref.shape[1:])


def _sparse_forward(q, k, v, k_lists, k_counts, sm_scale, causal, block, interpret):
    BH, S, D = q.shape
    nq, max_a = k_lists.shape
    grid = (BH, nq, max_a)
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, block=block, max_a=max_a
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # k_lists, k_counts
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block, D), lambda bh, qi, a, kl, kc: (bh, qi, 0)),
            pl.BlockSpec((1, block, D), lambda bh, qi, a, kl, kc: (bh, kl[qi, a], 0)),
            pl.BlockSpec((1, block, D), lambda bh, qi, a, kl, kc: (bh, kl[qi, a], 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block, D), lambda bh, qi, a, kl, kc: (bh, qi, 0)),
            pl.BlockSpec((1, block, LANES), lambda bh, qi, a, kl, kc: (bh, qi, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block, block), jnp.float32),
            pltpu.VMEM((block, block), jnp.float32),
            pltpu.VMEM((block, D), jnp.float32),
        ],
    )
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), q.dtype),
            jax.ShapeDtypeStruct((BH, S, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(k_lists, k_counts, q, k, v)
    return out, lse[..., 0]  # de-broadcast the lane-tiled row statistic


def _dq_kernel(k_list_ref, k_count_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
               delta_ref, dq_ref, dq_scr, *, sm_scale, causal, block, max_a):
    qi = pl.program_id(1)
    a = pl.program_id(2)

    @pl.when(a == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(a < k_count_ref[qi])
    def _compute():
        kj = k_list_ref[qi, a]
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, 0:1]     # lane-tiled [block, LANES] -> [block, 1]
        delta = delta_ref[0][:, 0:1]
        s = sm_scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if causal:
            s = _causal_mask(s, qi, kj, block)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta)
        dq_scr[...] += sm_scale * jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(a == max_a - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _dkdv_kernel(q_list_ref, q_count_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                 delta_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                 *, sm_scale, causal, block, max_a):
    kj = pl.program_id(1)
    a = pl.program_id(2)

    @pl.when(a == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(a < q_count_ref[kj])
    def _compute():
        qi = q_list_ref[kj, a]
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, 0:1]
        delta = delta_ref[0][:, 0:1]
        s = sm_scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if causal:
            s = _causal_mask(s, qi, kj, block)
        p = jnp.exp(s - lse)
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta)
        dk_scr[...] += sm_scale * jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(a == max_a - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _sparse_backward(res, g, lists, sm_scale, causal, block, interpret):
    q, k, v, out, lse = res
    k_lists, k_counts, q_lists, q_counts = lists
    BH, S, D = q.shape
    nq, max_a = k_lists.shape
    nk, max_aq = q_lists.shape
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)  # [BH,S]
    # lane-tile the row statistics for the kernels (saved de-broadcast)
    lse = jnp.broadcast_to(lse[..., None], lse.shape + (LANES,))
    delta = jnp.broadcast_to(delta[..., None], delta.shape + (LANES,))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, sm_scale=sm_scale, causal=causal, block=block, max_a=max_a),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(BH, nq, max_a),
            in_specs=[
                pl.BlockSpec((1, block, D), lambda bh, qi, a, kl, kc: (bh, qi, 0)),
                pl.BlockSpec((1, block, D), lambda bh, qi, a, kl, kc: (bh, kl[qi, a], 0)),
                pl.BlockSpec((1, block, D), lambda bh, qi, a, kl, kc: (bh, kl[qi, a], 0)),
                pl.BlockSpec((1, block, D), lambda bh, qi, a, kl, kc: (bh, qi, 0)),
                pl.BlockSpec((1, block, LANES), lambda bh, qi, a, kl, kc: (bh, qi, 0)),
                pl.BlockSpec((1, block, LANES), lambda bh, qi, a, kl, kc: (bh, qi, 0)),
            ],
            out_specs=pl.BlockSpec((1, block, D), lambda bh, qi, a, kl, kc: (bh, qi, 0)),
            scratch_shapes=[pltpu.VMEM((block, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        interpret=interpret,
    )(k_lists, k_counts, q, k, v, g, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkdv_kernel, sm_scale=sm_scale, causal=causal, block=block, max_a=max_aq),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(BH, nk, max_aq),
            in_specs=[
                pl.BlockSpec((1, block, D), lambda bh, kj, a, ql, qc: (bh, ql[kj, a], 0)),
                pl.BlockSpec((1, block, D), lambda bh, kj, a, ql, qc: (bh, kj, 0)),
                pl.BlockSpec((1, block, D), lambda bh, kj, a, ql, qc: (bh, kj, 0)),
                pl.BlockSpec((1, block, D), lambda bh, kj, a, ql, qc: (bh, ql[kj, a], 0)),
                pl.BlockSpec((1, block, LANES), lambda bh, kj, a, ql, qc: (bh, ql[kj, a], 0)),
                pl.BlockSpec((1, block, LANES), lambda bh, kj, a, ql, qc: (bh, ql[kj, a], 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block, D), lambda bh, kj, a, ql, qc: (bh, kj, 0)),
                pl.BlockSpec((1, block, D), lambda bh, kj, a, ql, qc: (bh, kj, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block, D), jnp.float32),
                pltpu.VMEM((block, D), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), k.dtype),
            jax.ShapeDtypeStruct((BH, S, D), v.dtype),
        ],
        interpret=interpret,
    )(q_lists, q_counts, q, k, v, g, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _sparse_bhsd(q, k, v, lists, sm_scale, causal, block, interpret):
    out, _ = _sparse_forward(q, k, v, np.asarray(lists[0]), np.asarray(lists[1]),
                             sm_scale, causal, block, interpret)
    return out


def _sparse_bhsd_fwd(q, k, v, lists, sm_scale, causal, block, interpret):
    out, lse = _sparse_forward(q, k, v, np.asarray(lists[0]), np.asarray(lists[1]),
                               sm_scale, causal, block, interpret)
    return out, (q, k, v, out, lse)


def _sparse_bhsd_bwd(lists, sm_scale, causal, block, interpret, res, g):
    lists = tuple(np.asarray(a) for a in lists)
    return _sparse_backward(res, g, lists, sm_scale, causal, block, interpret)


_sparse_bhsd.defvjp(_sparse_bhsd_fwd, _sparse_bhsd_bwd)


def sparse_flash_attention(q, k, v, layout: np.ndarray, causal: bool = True,
                           sm_scale: float | None = None, block: int | None = None,
                           interpret: bool | None = None):
    """Block-sparse attention. q/k/v [B, S, H, D]; ``layout`` is a [nq, nk]
    (or [1, nq, nk]) 0/1 block mask from a SparsityConfig with block size
    S // nq. Shared layout across heads (the config default)."""
    B, S, H, D = q.shape
    layout = np.asarray(layout)
    if layout.ndim == 3:
        if layout.shape[0] != 1 and not (layout == layout[0]).all():
            raise NotImplementedError("per-head layouts not supported; use a shared layout")
        layout = layout[0]
    nq, nk = layout.shape
    if S % nq or S % nk:
        raise ValueError(f"seq {S} not divisible by layout blocks {layout.shape}")
    blk = S // nq
    if block is not None and block != blk:
        raise ValueError(f"block {block} inconsistent with layout ({blk})")
    if sm_scale is None:
        sm_scale = 1.0 / (D ** 0.5)
    if interpret is None:
        interpret = interpret_default()
    # lists stay NUMPY (static): they ride custom_vjp's nondiff_argnums and
    # feed the kernels' scalar-prefetch inputs at call time
    lists = layout_to_lists(layout, causal)

    def to_bhsd(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, S, D)

    out = _sparse_bhsd(to_bhsd(q), to_bhsd(k), to_bhsd(v), lists, sm_scale, causal, blk, interpret)
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)
