"""Fused vocab-projection + cross-entropy Pallas kernels (training fwd+bwd).

The LM loss tail — logits = H @ W then softmax-xent — is the single largest
HBM consumer of a small-vocab-model train step after attention: at the bench
shapes ([16384, 768] hidden, 50304 vocab) each sequence chunk materializes a
multi-hundred-MB logits tensor, reads it back twice for logsumexp, and the
rematerialized backward does it all again before two more passes for dlogits.
The reference pays the same cost eagerly (its loss is plain torch
cross-entropy over materialized logits; the fused CUDA work in
csrc/transformer targets the layers, not the loss). TPU-native we can do
better: treat the vocab axis exactly like flash attention treats the key
axis —

  * forward streams W vocab-blocks down the innermost grid dim, computes the
    [Br, Bv] logits tile on the MXU into VMEM, folds it into a running
    row-max / row-sum (online logsumexp) and a gold-logit accumulator
    (label hit found by iota==label compare — no gather, Mosaic-friendly),
    and never writes a logit to HBM. Saves per-row lse as the residual.
  * backward recomputes the logits tile blockwise (FlashAttention-2 style)
    and forms ds = (softmax − onehot) · g_row in VMEM: one kernel accumulates
    dH = ds @ W_blk^T over vocab blocks, one accumulates dW = H_blk^T @ ds
    over row blocks. ds never exists in HBM either.

Net HBM traffic is one read of H and ~num_row_blocks re-reads of W per pass,
vs write+2·read of the logits tensor per pass for the chunked XLA path —
at bench shapes roughly a 3x reduction on the loss tail (W re-reads shrink
as the row block grows; 512-row blocks re-read W 32x = 2.5 GB vs ~5 GB of
logits traffic per micro-batch forward).

Public entry: ``fused_linear_xent(hidden, head, labels)`` -> per-row nll
[N] fp32 with a custom VJP. The caller applies masking/mean outside (XLA's
vjp then feeds the right per-row cotangents to the backward kernels).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from . import interpret_default
from .flash_attention import (
    LANES,
    NEG_INF,
    _compiler_params,
    _lanes,
    _scratch,
    _vmem_spec,
    _widen,
)

# Block-size policy (same grain logic as flash_attention: big blocks amortize
# grid-step overhead; VMEM per program stays < ~8 MB with double-buffered
# W blocks). Row blocks want to be LARGE — W is re-read once per row block.
MAX_BLOCK_ROWS = 512
MAX_BLOCK_V = 512


def _auto_block(n: int, cap: int) -> int:
    b = cap
    while b > 128 and n % b:
        b //= 2
    return min(b, n)


# ---------------------------------------------------------------------------
# Forward: online logsumexp + gold-logit pick over streamed vocab blocks
# ---------------------------------------------------------------------------

def _fwd_kernel(h_ref, w_ref, y_ref, lse_ref, gold_ref, m_scr, l_scr, g_scr,
                *, num_v, vocab):
    vj = pl.program_id(1)
    block_v = w_ref.shape[1]

    @pl.when(vj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        g_scr[...] = jnp.zeros_like(g_scr)

    h = h_ref[0]          # [Br, D] native dtype
    w_blk = w_ref[...]    # [D, Bv]
    logits = jax.lax.dot_general(
        h, w_blk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # [Br, Bv] fp32 on the MXU accumulator
    block_rows = logits.shape[0]
    col = vj * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (block_rows, block_v), 1
    )
    # mask vocab padding (W is zero-padded up to a block multiple)
    logits = jnp.where(col < vocab, logits, NEG_INF)
    y = y_ref[0][:, 0:1]  # [Br, 1] int32 labels (lane-broadcast input)
    hit = col == y        # [Br, Bv] — one column at most; negatives never hit
    g_scr[...] += _lanes(jnp.sum(jnp.where(hit, logits, 0.0), axis=1))

    m_prev = m_scr[...]                      # [Br, LANES] lane-broadcast
    m_new = jnp.maximum(m_prev, _lanes(jnp.max(logits, axis=1)))
    p = jnp.exp(logits - _widen(m_new, block_v))
    p = jnp.where(col < vocab, p, 0.0)       # exp(NEG_INF - m) underflows to 0 anyway; be explicit
    m_scr[...] = m_new
    l_scr[...] = l_scr[...] * jnp.exp(m_prev - m_new) + _lanes(jnp.sum(p, axis=1))

    @pl.when(vj == num_v - 1)
    def _finalize():
        l = l_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        lse_ref[0] = m_scr[...] + jnp.log(l_safe)
        gold_ref[0] = g_scr[...]


def _fused_forward(h, w, y_l, block_rows, block_v, vocab, interpret):
    N, D = h.shape
    Vp = w.shape[1]
    num_v = Vp // block_v
    grid = (N // block_rows, num_v)
    lse, gold = pl.pallas_call(
        functools.partial(_fwd_kernel, num_v=num_v, vocab=vocab),
        grid=grid,
        in_specs=[
            _vmem_spec((1, block_rows, D), lambda ri, vj: (ri, 0, 0)),
            _vmem_spec((D, block_v), lambda ri, vj: (0, vj)),
            _vmem_spec((1, block_rows, LANES), lambda ri, vj: (ri, 0, 0)),
        ],
        out_specs=[
            _vmem_spec((1, block_rows, LANES), lambda ri, vj: (ri, 0, 0)),
            _vmem_spec((1, block_rows, LANES), lambda ri, vj: (ri, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N // block_rows, block_rows, LANES), jnp.float32),
            jax.ShapeDtypeStruct((N // block_rows, block_rows, LANES), jnp.float32),
        ],
        scratch_shapes=[
            _scratch((block_rows, LANES)),  # running row-max m
            _scratch((block_rows, LANES)),  # running row-sum l
            _scratch((block_rows, LANES)),  # gold-logit accumulator
        ],
        interpret=interpret,
        compiler_params=_compiler_params(len(grid)),
    )(h.reshape(N // block_rows, block_rows, D), w, y_l)
    return lse.reshape(N, LANES), gold.reshape(N, LANES)


# ---------------------------------------------------------------------------
# Backward. ds = (softmax(logits) − onehot(y)) · g_row is recomputed
# blockwise in both kernels and never materialized.
# ---------------------------------------------------------------------------

def _block_ds(h, w_blk, y, g, lse, vj, vocab):
    """[Br, Bv] fp32 ds tile from recomputed logits."""
    block_v = w_blk.shape[1]
    logits = jax.lax.dot_general(
        h, w_blk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    block_rows = logits.shape[0]
    col = vj * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (block_rows, block_v), 1
    )
    p = jnp.exp(logits - _widen(lse, block_v))
    p = jnp.where(col < vocab, p, 0.0)
    hit = col == y[:, 0:1]
    return (p - jnp.where(hit, 1.0, 0.0)) * g[:, 0:1]


def _bwd_dh_kernel(h_ref, w_ref, y_ref, lse_ref, g_ref, dh_ref, dh_scr,
                   *, num_v, vocab):
    vj = pl.program_id(1)

    @pl.when(vj == 0)
    def _init():
        dh_scr[...] = jnp.zeros_like(dh_scr)

    h = h_ref[0]
    w_blk = w_ref[...]
    ds = _block_ds(h, w_blk, y_ref[0], g_ref[0], lse_ref[0], vj, vocab)
    # dH += ds @ W_blk^T  (contract vocab)
    dh_scr[...] += jax.lax.dot_general(
        ds.astype(w_blk.dtype), w_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(vj == num_v - 1)
    def _finalize():
        dh_ref[0] = dh_scr[...].astype(dh_ref.dtype)


def _bwd_dw_kernel(h_ref, w_ref, y_ref, lse_ref, g_ref, dw_ref, dw_scr,
                   *, num_r, vocab):
    vj = pl.program_id(1)
    ri = pl.program_id(2)

    @pl.when(ri == 0)
    def _init():
        dw_scr[...] = jnp.zeros_like(dw_scr)

    h = h_ref[0]
    w_blk = w_ref[...]
    ds = _block_ds(h, w_blk, y_ref[0], g_ref[0], lse_ref[0], vj, vocab)
    # dW += H_blk^T @ ds  (contract rows)
    dw_scr[...] += jax.lax.dot_general(
        h, ds.astype(h.dtype), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ri == num_r - 1)
    def _finalize():
        dw_ref[...] = dw_scr[...].astype(dw_ref.dtype)


def _fused_backward(h, w, y_l, lse_l, g_l, block_rows, block_v, vocab,
                    interpret):
    N, D = h.shape
    Vp = w.shape[1]
    num_v = Vp // block_v
    num_r = N // block_rows
    h_b = h.reshape(num_r, block_rows, D)

    row_specs = [
        _vmem_spec((1, block_rows, D), lambda ri, vj: (ri, 0, 0)),
        _vmem_spec((D, block_v), lambda ri, vj: (0, vj)),
        _vmem_spec((1, block_rows, LANES), lambda ri, vj: (ri, 0, 0)),
        _vmem_spec((1, block_rows, LANES), lambda ri, vj: (ri, 0, 0)),
        _vmem_spec((1, block_rows, LANES), lambda ri, vj: (ri, 0, 0)),
    ]
    dh = pl.pallas_call(
        functools.partial(_bwd_dh_kernel, num_v=num_v, vocab=vocab),
        grid=(num_r, num_v),
        in_specs=row_specs,
        out_specs=_vmem_spec((1, block_rows, D), lambda ri, vj: (ri, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((num_r, block_rows, D), h.dtype),
        scratch_shapes=[_scratch((block_rows, D))],
        interpret=interpret,
        compiler_params=_compiler_params(2),
    )(h_b, w, y_l, lse_l, g_l).reshape(N, D)

    dw = pl.pallas_call(
        functools.partial(_bwd_dw_kernel, num_r=num_r, vocab=vocab),
        grid=(1, num_v, num_r),  # rows innermost: dW accumulates over them
        in_specs=[
            _vmem_spec((1, block_rows, D), lambda _, vj, ri: (ri, 0, 0)),
            _vmem_spec((D, block_v), lambda _, vj, ri: (0, vj)),
            _vmem_spec((1, block_rows, LANES), lambda _, vj, ri: (ri, 0, 0)),
            _vmem_spec((1, block_rows, LANES), lambda _, vj, ri: (ri, 0, 0)),
            _vmem_spec((1, block_rows, LANES), lambda _, vj, ri: (ri, 0, 0)),
        ],
        out_specs=_vmem_spec((D, block_v), lambda _, vj, ri: (0, vj)),
        out_shape=jax.ShapeDtypeStruct((D, Vp), w.dtype),
        scratch_shapes=[_scratch((D, block_v))],
        interpret=interpret,
        compiler_params=_compiler_params(3),
    )(h_b, w, y_l, lse_l, g_l)
    return dh, dw


# ---------------------------------------------------------------------------
# custom_vjp plumbing
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _fused_xent(h, w, y_l, block_rows, block_v, vocab, interpret):
    lse, gold = _fused_forward(h, w, y_l, block_rows, block_v, vocab, interpret)
    return lse[:, 0] - gold[:, 0]


def _fused_xent_fwd(h, w, y_l, block_rows, block_v, vocab, interpret):
    lse, gold = _fused_forward(h, w, y_l, block_rows, block_v, vocab, interpret)
    # lse (de-broadcast, [N]) is the only residual beyond the inputs — the
    # backward kernels recompute everything else blockwise. Named so remat
    # policies can save it (models/transformer._remat_policy).
    lse_row = checkpoint_name(lse[:, 0], "xent_lse")
    return lse[:, 0] - gold[:, 0], (h, w, y_l, lse_row)


def _fused_xent_bwd(block_rows, block_v, vocab, interpret, res, g):
    h, w, y_l, lse_row = res
    lse_l = jnp.broadcast_to(lse_row[:, None], (lse_row.shape[0], LANES))
    g_l = jnp.broadcast_to(
        g.astype(jnp.float32)[:, None], (g.shape[0], LANES))
    N = h.shape[0]
    num_r = N // block_rows
    dh, dw = _fused_backward(
        h, w,
        y_l.reshape(num_r, block_rows, LANES),
        lse_l.reshape(num_r, block_rows, LANES),
        g_l.reshape(num_r, block_rows, LANES),
        block_rows, block_v, vocab, interpret,
    )
    return dh, dw, None


_fused_xent.defvjp(_fused_xent_fwd, _fused_xent_bwd)


def fused_linear_xent(
    hidden,
    head,
    labels,
    block_rows: int | None = None,
    block_v: int | None = None,
    interpret: bool | None = None,
):
    """Per-row next-token NLL without materializing logits.

    hidden: [N, D] (any float dtype — the matmuls run in it, softmax math in
    fp32), head: [D, V], labels: [N] int32 (< 0 = ignored row: the gold
    accumulator never fires and the backward's onehot never hits, so such a
    row contributes exactly zero gradient as long as the caller masks its nll
    out of the reduction, which also zeroes its cotangent).

    Returns nll [N] fp32 = logsumexp_v(hidden @ head) − (hidden @ head)[label].
    Differentiable in (hidden, head) via the blockwise-recompute kernels.
    """
    N, D = hidden.shape
    V = head.shape[1]
    if interpret is None:
        interpret = interpret_default()

    block_rows = block_rows or _auto_block(N, MAX_BLOCK_ROWS)
    if N % block_rows:
        raise ValueError(f"rows ({N}) must be divisible by block_rows ({block_rows})")
    if block_rows % 8:
        # TPU sublane tiling: a non-8-aligned row block fails Mosaic lowering
        # on hardware with an obscure error — reject it here instead
        raise ValueError(
            f"block_rows ({block_rows}) must be a multiple of 8 (TPU sublane "
            f"tile); pad rows to a multiple of 8 or pass an aligned block_rows"
        )
    block_v = block_v or MAX_BLOCK_V
    if block_v % LANES:
        raise ValueError(f"block_v ({block_v}) must be a multiple of {LANES}")
    pad_v = (-V) % block_v
    if pad_v:
        head = jnp.pad(head, ((0, 0), (0, pad_v)))

    y_l = jnp.broadcast_to(
        labels.astype(jnp.int32)[:, None], (N, LANES)
    ).reshape(N // block_rows, block_rows, LANES)
    nll = _fused_xent(hidden, head, y_l, block_rows, block_v, V, interpret)
    return nll
