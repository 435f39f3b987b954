"""The gated delta rule's block form as ONE Pallas kernel: what
``models/transformer._delta_chunks`` computes (its docstring has the algebra),
with everything ``[chunk, chunk]`` of a chunk, and the ``[head width, head
width]`` float32 state it is swept through, in VMEM.

A grid step is one (sequence, key head, a few chunks of ``Q`` rows) and the ``r =
H / Hk`` value heads of that key head. The chunk axis is the grid's LAST and runs
in order: the ``[r, D, D]`` state is the kernel's own output block, whose index
does not move with the chunk, so it is fetched once a (sequence, key head),
carried from chunk to chunk where it lies and written back once. Per step the
kernel reads ``[rows, D]`` of q and k, ``[rows, r D]`` of v and ``[rows, H]`` of g
and beta WHERE THEY LIE (``[B, T, Hk D]`` / ``[B, T, H D]`` / ``[B, T, H]`` through
the index maps: no transposed copy is made for it) and writes ``[rows, r D]`` of o
straight into ``[B, T, H D]``. The pair decays, ``A``, its inverse, ``U``, ``W``,
the chunk's inner products and the scaled q and k (``_delta_chunks``' operands
of its scan over chunks: ~0.5 GB a layer at 8,192 rows, written and read again
through half-filled tiles) never reach HBM.

The roundings are the XLA form's: the decays from each pair's OWN sum of g (g's
three bfloat16 pieces against a 0 / 1 mask: the whole float32 product) and to the
chunk's end summed from the end; ``k k^T``, ``q k^T`` and every product with the
state in the compute dtype, accumulated in float32; ``A`` and ``(I + A)^-1``
float32 at the precision of ``lax.Precision.HIGHEST`` (``_full_precision``: the same
six bfloat16 partial products, as one accumulation in the MXU instead of six
matmuls); the state float32. A row with g = beta = 0 (a bucket's padding) passes
the state through exactly.

Where the time went decided the form (PERF.md section 6, PR 55, has the table): a
chunk's inverse is a chain of ten small products that wait on each other, and the
compiler schedules a kernel's text in the order it is written, so the chains of a
step's (chunk, value head) pairs are written stage by stage, side by side.

Forward only (no ``custom_vjp``: ``transformer.delta_block_form`` hands the
kernel programs that carry a state, which run no backward pass). On the CPU
platform the kernel runs in the Pallas interpreter.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_default

LANES = 128  # a head's width must fill whole lane tiles: the blocks cut [T, heads x D] by head
# The instruction's name in the compiled program and the operation's in a device trace.
KERNEL_NAME = "delta_chunks"

# Chunks a grid step (where the rows make as many; a power of two): their chains side by
# side in the kernel's text. By the chip, ms a layer at 8,192 / 4,096 rows of the cell's
# heads: 4.82 / 2.44 at one, 3.62 / 1.83 at two, 3.28 / 1.66 at four, where the registers
# spilled bind (PERF.md section 6, PR 55).
CHUNKS_A_STEP = 4

_NT = (((1,), (1,)), ((), ()))  # x y^T
_TN = (((0,), (0,)), ((), ()))  # x^T y


def _pieces(x):
    """x float32 -> three float32 values of 8 significant bits each whose sum is x, bit
    for bit (the top 8 bits of x, of what is left, and the last 8): what a float32
    operand is to the MXU, which multiplies bfloat16; each converts to bfloat16
    exactly. Cut by a mask on the bits, not by a rounding conversion and back: two
    operations a cut where the conversions make four."""
    top = lambda y: lax.bitcast_convert_type(
        lax.bitcast_convert_type(y, jnp.int32) & jnp.int32(-65536), jnp.float32)
    hi = top(x)
    rest = x - hi
    mid = top(rest)
    return hi, mid, rest - mid


def _full_precision(a, b):
    """a @ b for two [Q, Q] float32 matrices, each held TWICE side by side ([Q, 2 Q]: a
    float32 tile is 128 lanes wide, so the second copy costs no register and no
    operation) and given as its ``_pieces`` -> the product, held twice. The precision
    is ``lax.Precision.HIGHEST``'s: the same six partial products a1 b1 + a1 b2 + a2 b1
    + a1 b3 + a2 b2 + a3 b1 of bfloat16 pieces accumulated in float32. But they are ONE
    trip into the MXU: the pieces lie side by side along the contraction (three
    128-lane tiles [a1 | a1], [a2 | a1], [a2 | a3], which the second copy makes a lane
    select and no shift), so the six products are steps of one accumulation. Asked for
    by ``precision=HIGHEST`` the compiler makes six matmuls of float32 operands: 223
    pushes and pops of the MXU a 64 x 64 product where this makes 86, and the chunk's
    inverse was two thirds of the kernel's time (PERF.md section 6, PR 55)."""
    (a1, a2, a3), (b1, b2, b3) = a, b
    Q, bf16 = a1.shape[0], jnp.bfloat16
    left = lax.broadcasted_iota(jnp.int32, a1.shape, 1) < Q
    b1, b2, b3 = b1.astype(bf16), b2.astype(bf16), b3.astype(bf16)
    return jnp.dot(
        jnp.concatenate([a1, jnp.where(left, a2, a1), jnp.where(left, a2, a3)], axis=1).astype(bf16),
        jnp.concatenate([b1, b2, b1, b3, b2, b1], axis=0), preferred_element_type=jnp.float32)


def _unit_lower_inverses(As):
    """``transformer._unit_lower_inverse`` on each of several strictly lower-triangular
    [Q, Q] float32 matrices held twice ([Q, 2 Q], ``_full_precision``) -> their (I +
    A)^-1, held twice: the six factors (I - A)(I + A^2)(I + A^4) ... of the ended
    series, every product at full precision. The matrices go through the factors
    TOGETHER (a factor of all of them, then the next): each one's products wait on the
    last, and written one matrix after the other the compiler's schedule is the sum of
    the chains (2,575 bundles a chunk of two heads, and twice that for two chunks)."""
    Q = As[0].shape[0]
    eye = (lax.broadcasted_iota(jnp.int32, As[0].shape, 0)
           == lax.broadcasted_iota(jnp.int32, As[0].shape, 1) % Q).astype(As[0].dtype)
    invs, powers, upto = [eye - A for A in As], [_pieces(A) for A in As], 1
    while 2 * upto < Q:
        powers, upto = [_pieces(_full_precision(p, p)) for p in powers], 2 * upto
        invs = [inv + _full_precision(_pieces(inv), p) for inv, p in zip(invs, powers)]
    return invs


def _chunk_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s0_ref, o_ref, s_ref, *,
                  Q: int, r: int, D: int):
    """``chunks`` chunks of one key head: q_ref, k_ref [chunks Q, D]; v_ref [chunks Q,
    r D]; g_ref, b_ref [chunks Q, H] (every value head's: a head's column is picked
    here); s0_ref, s_ref [r, D, D] float32, key dimension first; o_ref [chunks Q, r D]
    float32. What a chunk needs before the state is computed for ALL the step's
    (chunk, value head) pairs, stage by stage, before the state is swept through the
    chunks: the pairs' chains are independent, and side by side in the kernel's text
    one's trips into the MXU fill the waits of the others. The [Q, Q] float32 matrices
    on the way to the inverse are held twice, [Q, 2 Q] (``_full_precision``)."""
    f32, dt = jnp.float32, q_ref.dtype
    H = g_ref.shape[1]
    chunks = q_ref.shape[0] // Q
    head, step = pl.program_id(1), pl.program_id(2)

    @pl.when(step == 0)
    def _():
        s_ref[...] = s0_ref[...]

    row = lax.broadcasted_iota(jnp.int32, (Q, 2 * Q), 0)
    lane = lax.broadcasted_iota(jnp.int32, (Q, 2 * Q), 1)
    col = lane % Q
    lower, strictly, eye = row >= col, row > col, row == col
    # [m, j]: m lies after j, held twice; three times down the contraction for g's three
    # pieces, and a tile of zeros that fills the fourth half tile
    after = jnp.concatenate([strictly.astype(jnp.bfloat16)] * 3
                            + [jnp.zeros((Q, 2 * Q), jnp.bfloat16)], axis=0)
    heads = lax.broadcasted_iota(jnp.int32, (Q, H), 1)
    pairs = [(c, j) for c in range(chunks) for j in range(r)]

    def decays(c, j):
        """-> g's running sum, the sum to the chunk's end, beta [Q, 1]; the pair decays
        [Q, 2 Q] (held twice)."""
        at = pl.ds(c * Q, Q)
        pick = lambda ref: jnp.sum(jnp.where(heads == head * r + j, ref[at, :], 0.0), axis=1,
                                   keepdims=True)  # [Q, 1]
        g, b = pick(g_ref), pick(b_ref)
        g_row = jnp.sum(jnp.where(eye, g, 0.0), axis=0, keepdims=True)  # [1, 2 Q]: g along lanes
        upto = jnp.where(lower, g_row, 0.0)  # [i, m]: g_m where m <= i
        c_i = jnp.sum(upto[:, :Q], axis=1, keepdims=True)
        to_end = jnp.sum(jnp.where(col > row, g_row, 0.0)[:, :Q], axis=1, keepdims=True)
        # c_i - c_j as the sum of the g between them (j < m <= i), each pair's own sum: the
        # 0 / 1 mask is exact in bfloat16, so g's three pieces against it are the whole product
        u1, u2, u3 = _pieces(upto)
        gaps = jnp.dot(jnp.concatenate([jnp.where(lane < Q, u1, u2), jnp.where(lane < Q, u3, 0.0)],
                                       axis=1).astype(jnp.bfloat16), after, preferred_element_type=f32)
        return c_i, to_end, b, jnp.where(lower, jnp.exp(gaps), 0.0)

    q = [q_ref[pl.ds(c * Q, Q), :] for c in range(chunks)]
    k = [k_ref[pl.ds(c * Q, Q), :] for c in range(chunks)]
    kk = [lax.dot_general(x, jnp.concatenate([x, x], axis=0), _NT, preferred_element_type=f32)
          for x in k]  # [Q, 2 Q]: held twice
    qk = [lax.dot_general(x, y, _NT, preferred_element_type=f32) for x, y in zip(q, k)]
    gates = [decays(c, j) for c, j in pairs]
    Tm = [inv[:, :Q].astype(dt) for inv in _unit_lower_inverses(
        [jnp.where(strictly, kk[c] * decay * b, 0.0) for (c, _), (_, _, b, decay) in zip(pairs, gates)])]
    ready = {}
    for (c, j), (c_i, to_end, b, decay), T in zip(pairs, gates, Tm):
        v, from_start = v_ref[pl.ds(c * Q, Q), j * D:(j + 1) * D], jnp.exp(c_i)
        ready[c, j] = (
            jnp.dot(T, (v * b).astype(dt), preferred_element_type=f32),  # U
            jnp.dot(T, (k[c] * (b * from_start)).astype(dt), preferred_element_type=f32).astype(dt),
            (qk[c] * decay[:, :Q]).astype(dt), (q[c] * from_start).astype(dt),
            (k[c] * jnp.exp(to_end)).astype(dt), from_start[Q - 1:, :])  # the chunk's whole decay
    S = [s_ref[j] for j in range(r)]
    for c, j in pairs:
        U, W, inner, q_in, k_out, whole = ready[c, j]
        held = S[j].astype(dt)  # the state ENTERING the chunk is what it reads
        moved = (U - jnp.dot(W, held, preferred_element_type=f32)).astype(dt)
        o_ref[pl.ds(c * Q, Q), j * D:(j + 1) * D] = (
            jnp.dot(q_in, held, preferred_element_type=f32)
            + jnp.dot(inner, moved, preferred_element_type=f32))
        S[j] = whole * S[j] + lax.dot_general(k_out, moved, _TN, preferred_element_type=f32)
    for j in range(r):
        s_ref[j] = S[j]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _delta_chunks(q, k, v, g, beta, S0, chunk, interpret):
    B, T, H, D = v.shape
    Hk = q.shape[2]
    r, Q = H // Hk, chunk
    chunks = math.gcd(T // Q, CHUNKS_A_STEP)
    rows = lambda heads: pl.BlockSpec((None, chunks * Q, heads * D), lambda b, h, c: (b, c, h))
    gates = pl.BlockSpec((None, chunks * Q, H), lambda b, h, c: (b, c, 0))
    state = pl.BlockSpec((None, None, r, D, D), lambda b, h, c: (b, h, 0, 0, 0))
    flops = 2 * B * (T // Q) * (Hk * 2 * Q * Q * D + H * (11 * Q * Q * Q + 3 * Q * Q * D
                                                         + 3 * Q * D * D))
    o, S = pl.pallas_call(
        functools.partial(_chunk_kernel, Q=Q, r=r, D=D),
        out_shape=(jax.ShapeDtypeStruct((B, T, H * D), jnp.float32),
                   jax.ShapeDtypeStruct((B, Hk, r, D, D), jnp.float32)),
        grid=(B, Hk, T // (chunks * Q)),
        in_specs=[rows(1), rows(1), rows(r), gates, gates, state],
        out_specs=(rows(r), state),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=flops, transcendentals=B * (T // Q) * H * (Q * Q + 3 * Q),
            bytes_accessed=(B * T * (2 * Hk + H) * D * q.dtype.itemsize + B * T * H * D * 4
                            + 2 * B * T * H * Hk * 4 + 2 * B * H * D * D * 4)),
        interpret=interpret,
        name=KERNEL_NAME,
    )(q.reshape(B, T, Hk * D), k.reshape(B, T, Hk * D), v.reshape(B, T, H * D), g, beta,
      S0.astype(jnp.float32).reshape(B, Hk, r, D, D))
    return o.reshape(B, T, H, D), S.reshape(B, H, D, D)


def tiles(head_dim: int) -> bool:
    """Whether the kernel takes heads of this width: whole lane tiles, so that a
    block of ``[T, heads x D]`` cut at a head is a block the chip can address."""
    return head_dim > 0 and head_dim % LANES == 0


def delta_chunks(q, k, v, g, beta, S0, chunk: int, interpret: bool | None = None):
    """``transformer._delta_chunks`` on rows that are a whole number of chunks: q, k
    [B, T, Hk, D] and v [B, T, H, D] in the compute dtype, g and beta [B, T, H]
    float32, S0 [B, H, D, D] float32 -> (o [B, T, H, D] float32, S_T)."""
    T, D = v.shape[1], v.shape[3]
    if T % chunk or not tiles(D):
        raise ValueError(f"{T} rows of {D}-wide heads are not whole chunks of {chunk} rows "
                         f"of whole {LANES}-lane heads")
    if interpret is None:
        interpret = interpret_default()
    return _delta_chunks(q, k, v, g.astype(jnp.float32), beta.astype(jnp.float32), S0,
                         chunk, interpret)
