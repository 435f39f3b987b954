"""Fused attention Pallas kernels (training fwd + bwd).

The reference's training-side attention lives in the fused CUDA transformer
layer (csrc/transformer/ds_transformer_cuda.cpp: softmax_kernels.cu +
strided-batch GEMMs in cublas_wrappers.cu, bound via `forward_fp16`/
`backward_fp16` :1029-1047). The TPU-native equivalent is a blockwise
online-softmax ("flash") attention pair of kernels:

  * forward never materializes the [S, S] score matrix: per q-block it
    streams k/v blocks, keeping a running row-max / row-sum (online softmax)
    and a [Bq, D] accumulator in VMEM; saves the per-row logsumexp for the
    backward pass as ONE float32 a row ([BH, 1, Sq], laid along the lanes:
    PR 65; a lane-broadcast [BH, Sq, 128] array before, 134 MB a layer at the
    train cell's shape).
  * backward recomputes P = exp(QK^T·scale − L) blockwise (FlashAttention-2
    decomposition) in ONE kernel where a head's dQ fits VMEM (``flash_bwd``,
    PR 64: grid (head, key block, query block); a tile's P and dS are computed
    once and feed dV and dK of its key block and dQ of its query block, dQ of
    the whole head held in float32 scratch across its key blocks: 5 matmuls and
    one exponential a tile), else in two (``backward_form``: one kernel
    accumulates dK/dV over q-blocks, one dQ over k-blocks, each recomputing
    the scores: 7 matmuls and two exponentials). The backward's tile is
    TRANSPOSED (S^T = K Q^T, keys down the sublanes, rows along the lanes: PR 65),
    so a row's logsumexp is read as it was written, dV and dK are products as
    they lie and only dQ contracts over a transposed operand; the softmax
    Jacobian term D_i = rowsum(dO ∘ O) is made inside the step from the dO block
    it holds and the O block streamed beside it (``_delta_row``: XLA's own pass
    over dO and O, handing the kernel a row, cost the train cell's 1,195 ms step
    4.3 ms more; a value head narrower than the lanes keeps that pass).

VMEM residency is O(block) not O(sequence): the streamed operand rides the
*innermost grid dimension* (its BlockSpec indexes that dim), so Pallas
double-buffers one block at a time from HBM while the online-softmax /
gradient state lives in VMEM scratch accumulators that persist across the
sequential innermost grid steps (output blocks are revisited, written once
when the stream finishes). This keeps per-program VMEM bounded at any
sequence length — whole-sequence BlockSpecs would blow the VMEM budget at
8-16k tokens, which is why the fused backward, whose dQ IS a whole-sequence
block, is taken by a rule on the shapes and falls back to the pair.

Causal masking keeps the grid and its coarse blocks and cuts the work INSIDE a
step (the block analogue of the reference's triangular softmax kernels): a step
runs the key sub-tiles of its block that hold a key at or under its last row,
and a step wholly above the diagonal runs nothing and fetches nothing (its
index map stays on the last block its row needed). On
the CPU platform the kernels run in Pallas interpreter mode so tests exercise
the same code.

Layout: public API takes [B, S, H, D] (the model family's layout) and maps
over fused batch×head programs internally.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_default

LANES = 128  # TPU lane width; a step's running max / sum are held lane-broadcast in VMEM
NEG_INF = -1e30

# Block-size policy. A grid step costs about a microsecond whatever it computes
# (PERF.md section 6, PR 51 has the table: at [128 heads, 2048 rows, 128] the
# three kernels take 7.13 ms at 512 x 1024 blocks run whole, 5.88 with a step's
# work cut to the keys its rows see, 5.05 at 512 x 2048 and 7.37 at 512 x 512),
# so the OUTER blocks are as coarse as VMEM lets them be, and a causal step
# cuts its work to what the diagonal leaves of its block INSIDE the step. The
# streamed operand still rides the innermost grid dim, so VMEM stays bounded
# at any sequence length.
MAX_BLOCK_Q = 512
MAX_BLOCK_K = 2048
# What one key-side block (a k or a v block: each is double-buffered, and dK/dV
# hold a float32 accumulator apiece beside them) may take of the 16 MiB of VMEM
# the chip's compiler gives a kernel: 2048 keys of 128-wide float32 heads or
# 256-wide bfloat16 ones compile, 2048 of 256-wide float32 are refused by 4 MiB
# (tests/test_chip_compile_kernels.py).
KEY_BLOCK_BYTES = 2 ** 20
# The fused backward (``backward_form``): a head's dQ stays in VMEM over its key
# blocks, so a step grows with the rows. ``FUSED_VMEM_BYTES`` is what its blocks and
# accumulators may take by ``_fused_step_bytes`` (past it the backward is the pair of
# kernels, whose steps do not grow), ``FUSED_VMEM_LIMIT`` what the kernel asks the
# compiler for in place of the 16 MiB it gives by default: the compiler's own
# temporaries beside those buffers (the tile's scores in flight, spilled registers)
# came to 3 - 6 MiB and moved by 3 MiB with nothing but the count of heads in the
# grid, which the second 16 MiB absorb (a v5e core has 128 MiB of VMEM).
FUSED_VMEM_BYTES = 16 * 2 ** 20
FUSED_VMEM_LIMIT = 32 * 2 ** 20
# The width of the key sub-tiles a causal step's block is cut in where its rows
# are not cut too (``diag_sub`` 0): a step runs only the sub-tiles that hold a
# key at or under its last row (``_keys_seen``, ``_step_pieces``).
SUB_K = 512
# The edge of the sub-tiles the DIAGONAL's tile is cut in (PR 65: two MXU tiles;
# ``diag_sub``): a step's rows go in pieces of this many, each against the keys
# at or under ITS last row, so of a 512 x 512 tile on the diagonal the quarter
# above it is neither multiplied nor exponentiated.
DIAG_SUB = 256


def _auto_block(s: int, cap: int) -> int:
    """Largest power-of-two block <= cap that divides s (s is pre-padded to a
    multiple of 128 by the public wrapper)."""
    b = cap
    while b > 128 and s % b:
        b //= 2
    return min(b, s)


def _key_block(s: int, width: int, itemsize: int) -> int:
    """The key block over ``s`` keys (a multiple of 128) of heads ``width``
    wide: ``_auto_block`` under ``MAX_BLOCK_K``, halved while one block would
    pass ``KEY_BLOCK_BYTES``."""
    cap = MAX_BLOCK_K
    while cap > 128 and cap * width * itemsize > KEY_BLOCK_BYTES:
        cap //= 2
    return _auto_block(s, cap)


def _outer_blocks(rows: int, keys: int, width: int, itemsize: int, block_q, block_k):
    """(block_q, block_k) of the whole grid over ``rows`` x ``keys`` (multiples
    of 128) at heads ``width`` wide (the wider of q/k and v): the caller's, held
    to the lengths, or ``_auto_block`` / ``_key_block``'s."""
    return (min(block_q, rows) if block_q else _auto_block(rows, MAX_BLOCK_Q),
            min(block_k, keys) if block_k else _key_block(keys, width, itemsize))


# Under a window that is a constant of the trace the forward runs over a band of
# key blocks (``_band_forward``), and the triangle's blocks are the wrong size for
# it: a 1024-wide key block is 8 x a window of 128. The key block is the smallest
# multiple of 128 that holds the window; the query block was timed on the chip
# (PERF.md §6, PR 40: the kernel alone at [64 heads, rows, 128], window 128).
BAND_BLOCK_Q = 256
BAND_MAX_BLOCK_K = 1024  # the band's own cap: a wider window keeps the whole grid (``band_plan``)
# What a banded step may hold of the 16 MiB of VMEM the chip's compiler gives a
# kernel (``_band_step_bytes``, an upper estimate fitted to what the compiler
# reported for 150 shapes: tests/test_chip_compile_kernels.py compiles a sweep).
BAND_VMEM_BYTES = 14 * 2 ** 20
# Heads of a banded step written out inline in one iteration of its loop over
# them: 1 / 2 / 4 / 8 of 8 read 4.06 / 3.17 / 2.91 / 2.89 ms at 16,384 rows
# (PERF.md §6, PR 40), and the kernel's trace grows with it.
BAND_UNROLL = 2


def static_window(window, sq: int, sk: int, causal: bool):
    """-> (window, band): what a caller's ``window`` is to the kernels, read
    from what the trace can see of it. A traced window (GPT-Neo's scanned layers
    choose theirs by the layer index) stays the runtime operand it was, band 0.
    A Python number is a constant of the trace: one that masks nothing (<= 0:
    global; >= the rows) is no window at all, and for causal self-attention any
    other gives ``band`` = W > 0, a query row seeing the keys q - W + 1 .. q
    (distances are whole, so d < w is d < ceil(w))."""
    if window is None or isinstance(window, jax.Array):
        return window, 0
    w = math.ceil(float(window))
    if w <= 0 or w >= sq:
        return None, 0
    return window, w if causal and sq == sk else 0


def _band_blocks(s: int, band: int) -> tuple[int, int]:
    """(block_q, block_k) of the banded forward over ``s`` rows (a multiple of
    128) where the caller names none."""
    bk = min(-(-band // 128) * 128, BAND_MAX_BLOCK_K)
    while s % bk:
        bk -= 128
    return _auto_block(s, BAND_BLOCK_Q), bk


def _band_lo(qi, block_q, block_k, band, maximum=max):
    """The first key block the band of query block ``qi`` reaches (its first row
    sees back to position qi * block_q - band + 1); ``maximum=jnp.maximum`` for
    a traced ``qi``."""
    return maximum(qi * block_q - band + 1, 0) // block_k


def _diag(qi, block_q, block_k):
    """The last key block at or under the diagonal of query block ``qi``."""
    return (qi * block_q + block_q - 1) // block_k


def _band_steps(num_q, block_q, block_k, band):
    """Key blocks the band of each query block reaches, a list over query
    blocks: its max is how many the banded forward hands a step (``block_q /
    block_k + ceil((band - 1) / block_k)`` for ``block_q`` a multiple of
    ``block_k``), its sum the blocks it computes."""
    return [_diag(qi, block_q, block_k) - _band_lo(qi, block_q, block_k, band) + 1
            for qi in range(num_q)]


def _band_step_bytes(heads, block_q, block_k, views, d, dv, itemsize):
    """VMEM one banded step holds, from above: its blocks twice (they are
    double-buffered: q, o and a float32 lse row block, ``views`` key and value
    blocks; a head narrower than the 128 lanes is padded to them) and the float32
    scores and probabilities of the heads in flight at once (the compiler
    reported 1 to 3 heads' worth with every head written out inline; the loop
    now holds ``BAND_UNROLL``)."""
    wide = lambda w: -(-w // LANES) * LANES
    blocks = (itemsize * (block_q + views * block_k) * (wide(d) + wide(dv))
              + 4 * LANES * block_q)
    scores = views * block_q * wide(block_k) * (4 + 4 + itemsize)
    return 2 * heads * blocks + min(heads, 3) * scores


def band_plan(rows, band, bh, d, dv, itemsize, block_q=None, block_k=None):
    """-> (block_q, block_k, views, heads) of the banded forward over ``rows`` (a
    multiple of 128) x ``bh`` fused batch x head programs under the static
    window ``band``, or None where even one head's step would overflow
    ``BAND_VMEM_BYTES`` (a window of thousands of keys: the whole grid, which
    streams one key block a step, takes it under the window as an operand).
    ``views``: the key blocks a step is handed; ``heads``: the programs that
    ride one step, the most of 8, 4, 2, 1 that divides ``bh`` and fits (a
    step's work is small, its fixed cost is not)."""
    auto_q, auto_k = _band_blocks(rows, band)
    block_q, block_k = min(block_q or auto_q, rows), min(block_k or auto_k, rows)
    if rows % block_q or rows % block_k:
        return None
    views = max(_band_steps(rows // block_q, block_q, block_k, band))
    for heads in (8, 4, 2, 1):
        if bh % heads == 0 and _band_step_bytes(
                heads, block_q, block_k, views, d, dv, itemsize) <= BAND_VMEM_BYTES:
            return block_q, block_k, views, heads
    return None


@functools.lru_cache(maxsize=None)  # a serving worker asks on every prefill call
def window_grid(rows: int, window, bh: int, d: int, dv: int, itemsize: int,
                block_q: int | None = None, block_k: int | None = None):
    """-> (``"band"`` | ``"causal"``, the key blocks it computes as a % of those
    at or under the diagonal): the forward that ``flash_attention(causal=True,
    window=window)`` takes for self-attention over ``rows`` x ``bh`` batch x head
    programs of width ``d`` (values ``dv``), from the shapes and the window (a
    Python number or None) alone (``SlotWorker.prefill`` labels its span by it)."""
    rows += (-rows) % 128
    _, band = static_window(window, rows, rows, True)
    plan = band and band_plan(rows, band, bh, d, dv, itemsize, block_q, block_k)
    if not plan:
        return "causal", 100.0
    bq, bk = plan[:2]
    num_q = rows // bq
    under = sum(_diag(qi, bq, bk) + 1 for qi in range(num_q))
    return "band", 100.0 * sum(_band_steps(num_q, bq, bk, band)) / under


# ---------------------------------------------------------------------------
# The causal schedule inside a grid step (PR 51)
# ---------------------------------------------------------------------------

def _sub_tile(block_k: int) -> int:
    """Width of the key sub-tiles a causal step's ``block_k`` keys are cut in."""
    return block_k if block_k <= SUB_K else math.gcd(block_k, SUB_K)


def _clip(x, n):
    """``x`` held to 0 .. n, for a Python number or a traced one."""
    return min(max(x, 0), n) if isinstance(x, int) else jnp.clip(x, 0, n)


def _keys_seen(r0, rows, k0, sub, n):
    """How many of the ``n`` key sub-tiles ``sub`` wide from key ``k0`` on hold a
    key at or under the last of the query rows ``r0 .. r0 + rows - 1``: from
    there on every key lies after the last row and is not computed."""
    return _clip((r0 + rows - k0 + sub - 1) // sub, n)


def _diag_cut(block_q: int, block_k: int, backward: bool = False) -> int:
    """The edge of the sub-tiles a causal step of ``block_q`` rows against
    ``block_k`` keys cuts the diagonal's tile in, 0 where the tile runs whole.
    ``DIAG_SUB`` where the query block holds two or more of them (the halves of a
    256-row block would fall to the MXU's own 128 rows, where a product's fixed
    cost is what the cut saves). The backward, which is bound by its products,
    cuts wherever it can (3 - 8% of the kernel at 1,024 to 4,096 rows). The
    FORWARD is bound by its stores: it gains 0.6 - 4% of the kernel where the key
    block holds four query blocks or more (2,048 rows and over) and LOST 3 - 4%
    under that (1,024 rows: a key block of two; 1,536: of one), so it cuts only
    there. The head's width does not enter: at 192 / 128 and at 256-wide heads
    the cut compiles inside the same VMEM and saves as large a share of the
    schedule (PERF.md section 6, PR 65)."""
    if block_q < 2 * DIAG_SUB or block_q % DIAG_SUB or not (backward or block_k >= 4 * block_q):
        return 0
    return DIAG_SUB


def _step_pieces(ahead: int, block_q: int, block_k: int, cut: int) -> tuple:
    """What a causal step computes whose first row lies ``ahead`` keys past its
    key block's first key: ((row0, key0, key1), ...), the query block's rows from
    ``row0`` on against the key block's keys ``key0 .. key1 - 1``, every piece
    under the causal mask (the compare and select cost nothing that a chip run
    could find; sparing the keys under the diagonal them took a product of their
    own and cost more: PERF.md section 6, PR 51). Nothing: the block lies above
    the diagonal. With the rows whole (``cut`` 0) one piece, the key sub-tiles
    ``_sub_tile`` wide that hold a key at or under the LAST row. Cut in row
    pieces of ``cut`` (``_diag_cut``), the first piece's keys for every row, and
    for the rows from each later piece on the ``cut`` keys more that this piece
    sees: the stairs under the diagonal, so the sub-tiles above it are not
    computed and the keys under it stay ONE product with all the block's rows."""
    if not cut:
        sub = _sub_tile(block_k)
        keys = _keys_seen(ahead, block_q, 0, sub, block_k // sub) * sub
        return ((0, 0, keys),) if keys else ()
    stairs = ((row0, _clip(ahead + row0, block_k) if row0 else 0,
               _clip(ahead + row0 + cut, block_k)) for row0 in range(0, block_q, cut))
    return tuple(piece for piece in stairs if piece[2] > piece[1])


def _step_cases(num_q: int, num_k: int, block_q: int, block_k: int, causal: bool, cut: int):
    """What the steps of a ``num_q`` x ``num_k`` grid of ``block_q`` x ``block_k``
    blocks can be asked to compute, each case a piece of straight-line code under
    its own ``pl.when`` on static slices of the blocks (a loop of a traced trip
    count over the sub-tiles lost more between them than the diagonal saves:
    PERF.md section 6, PR 51): -> [(lo, hi, pieces)], the steps whose first row
    lies ``lo .. hi`` keys past their key block's first run ``_step_pieces``'s
    ``pieces`` (more keys the further under the diagonal, so a case is a range).
    Only what this grid meets is listed (one key block over 2,048 rows never
    sees a block wholly under the diagonal). Without a diagonal a step is its
    whole block."""
    if not causal:
        return [(None, None, ((0, 0, block_k),))]
    met = {}
    for ahead in sorted({qi * block_q - kj * block_k for qi in range(num_q) for kj in range(num_k)}):
        pieces = _step_pieces(ahead, block_q, block_k, cut)
        if pieces:
            met[pieces] = (met.get(pieces, (ahead,))[0], ahead)
    return [(lo, hi, pieces) for pieces, (lo, hi) in met.items()]


def _for_step_case(qi, kj, block_q, block_k, cases, body):
    """Run ``body(pieces)`` for the one case of ``cases`` (``_step_cases``) that
    query block ``qi`` meets in key block ``kj`` (none: the block lies above the
    diagonal)."""
    ahead = qi * block_q - kj * block_k
    for lo, hi, pieces in cases:
        if lo is None:
            body(pieces)
        else:
            met = ahead == lo if hi == lo else (ahead >= lo) & (ahead <= hi)
            pl.when(met)(functools.partial(body, pieces))


def diag_sub(rows: int, width: int, itemsize: int, block_q: int | None = None,
             block_k: int | None = None, backward: bool = False) -> int:
    """The edge of the sub-tiles the causal forward (``backward``: the backward)
    cuts the diagonal's tile in over ``rows`` rows of self-attention at heads
    ``width`` wide (the wider of q/k and v), 0 where it runs whole (``_diag_cut``
    at the call's own blocks): what a span or a program's ledger row says of the
    schedule beside ``causal_tiles_pct``."""
    rows += (-rows) % 128
    return _diag_cut(*_outer_blocks(rows, rows, width, itemsize, block_q, block_k), backward)


@functools.lru_cache(maxsize=None)  # a serving worker asks on every prefill call
def causal_tiles_pct(rows: int, width: int, itemsize: int, block_q: int | None = None,
                     block_k: int | None = None) -> float:
    """The score elements the causal forward (``flash_fwd``) computes over
    ``rows`` rows of self-attention at heads ``width`` wide (the wider of q/k
    and v) as a % of those at or under the diagonal, from the shapes alone, as
    the kernel's steps read their own schedule (100: the triangle and nothing
    else; the whole square would read 200)."""
    rows += (-rows) % 128
    block_q, block_k = _outer_blocks(rows, rows, width, itemsize, block_q, block_k)
    cut = _diag_cut(block_q, block_k)
    tiles = sum((block_q - row0) * (key1 - key0)
                for r0 in range(0, rows, block_q) for k0 in range(0, rows, block_k)
                for row0, key0, key1 in _step_pieces(r0 - k0, block_q, block_k, cut))
    return 100.0 * tiles / (rows * (rows + 1) / 2)


def _vmem_spec(shape, index_map):
    return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)


def _scratch(shape):
    return pltpu.VMEM(shape, jnp.float32)


def _compiler_params(grid_len, sequential=1, vmem_limit_bytes=None):
    """Mark every grid dim except the ``sequential`` innermost (the stream over
    which scratch accumulates) as parallel; ``vmem_limit_bytes``: what the kernel
    may take of VMEM where the compiler's 16 MiB are too few."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * (grid_len - sequential) + ("arbitrary",) * sequential,
        vmem_limit_bytes=vmem_limit_bytes)


def _widen(lane_tile, width):
    """[rows, LANES] lane-broadcast tile -> [rows, width] (all lanes equal)."""
    if width == LANES:
        return lane_tile
    if width % LANES == 0:
        return jnp.tile(lane_tile, (1, width // LANES))
    return lane_tile[:, :width]


def _lanes(col, lanes=LANES):
    """[rows] -> [rows, lanes] broadcast."""
    return jnp.broadcast_to(col[:, None], (col.shape[0], lanes))


def _row(lane_tile):
    """[rows, LANES] lane-broadcast tile -> [1, rows]: a row's statistic laid
    along the lanes, as it leaves the forward and enters the backward (one
    float32 a row; a lane-broadcast array in HBM is 128)."""
    return lane_tile.T[0:1]


# ---------------------------------------------------------------------------
# In-kernel scores (shared by forward + both backward kernels)
# ---------------------------------------------------------------------------

def _block_scores(q, k_blk, r0, k0, *, sm_scale, causal, slope_ref, w_ref, mask_block=1,
                  transposed=False):
    """[rows, keys] fp32 scores of the query rows from ``r0`` on against the
    keys from ``k0`` on, with alibi / local-window / causal fused;
    ``transposed``: [keys, rows], K Q^T (the backward's tile: a row's logsumexp
    and delta lie along the lanes there, ``_row``).

    ``slope_ref`` (or None): [1, 1, LANES] block of the per-program alibi slope
    (one lane-broadcast row per fused batch×head program, read as a vector:
    a [1, LANES] block of a [BH, LANES] array is no legal TPU tile, and a
    scalar is not read from VMEM) — the bias is COMPUTED from block
    positions, never streamed from HBM (the reference threads alibi through
    softmax_context_* the same way, pt_binding.cpp:1231-1283). ``w_ref`` (or
    None): [1, LANES] runtime local-attention window; w <= 0 means global
    (lets the scanned GPT-Neo layers alternate locality with one compiled
    kernel). ``mask_block`` > 1 (with ``causal``): causal BETWEEN blocks of that many
    positions, a query seeing its own block whole (key j iff j // B <= i // B); ``r0``
    is a multiple of it (a tile starts at a multiple of 128), so a row's place in its
    block is its place in the tile's."""
    lhs, rhs = (k_blk, q) if transposed else (q, k_blk)
    s = sm_scale * jax.lax.dot_general(
        lhs, rhs, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # fp32 accumulator
    along_rows, along_keys = (1, 0) if transposed else (0, 1)
    if causal or slope_ref is not None or w_ref is not None:
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, along_rows)
        dist = (r0 - k0) + (row - jax.lax.broadcasted_iota(jnp.int32, s.shape, along_keys))  # q_pos - k_pos
    if slope_ref is not None:
        s = s - _widen(slope_ref[0], s.shape[1]) * dist.astype(jnp.float32)
    if w_ref is not None:
        w = w_ref[0, 0]  # fp32 runtime window; w <= 0 means global
        s = jnp.where((w <= 0) | (dist.astype(jnp.float32) < w), s, NEG_INF)
    if causal and mask_block > 1:  # the keys behind a query that its own block still holds
        s = jnp.where(dist + (mask_block - 1) - (row & (mask_block - 1)) >= 0, s, NEG_INF)
    elif causal:
        s = jnp.where(dist >= 0, s, NEG_INF)
    return s


def _wrap_extras(base, n_in, has_slopes, has_window):
    """Adapt a kernel so the optional slope/window operands (appended after
    the regular inputs, in that order) reach it as keyword refs."""
    if not has_slopes and not has_window:
        return base

    def wrapped(*refs):
        ins = list(refs[:n_in])
        i = n_in
        kw = {}
        if has_slopes:
            kw["slope_ref"] = refs[i]
            i += 1
        if has_window:
            kw["w_ref"] = refs[i]
            i += 1
        return base(*ins, *refs[i:], **kw)

    return wrapped


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, *rest,
    sm_scale, causal, num_k, cases, with_lse, slope_ref=None, w_ref=None, mask_block=1,
):
    """``rest``: the row of logsumexps where the call writes one (``with_lse``),
    then the scratch: the running max, the running sum, the accumulator."""
    lse_ref = rest[0] if with_lse else None
    m_scr, l_scr, acc_scr = rest[-3:]
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _compute(pieces):
        # every piece's scores first: a row's max runs over all the keys it sees
        scores = [_block_scores(
            q_ref[0, row0:, :],      # native dtype: the MXU runs at full rate in bf16
            k_ref[0, key0:key1, :], qi * block_q + row0, kj * block_k + key0, sm_scale=sm_scale,
            causal=causal, slope_ref=slope_ref, w_ref=w_ref, mask_block=mask_block)
            for row0, key0, key1 in pieces]
        m_prev = m_scr[...]                     # [Bq, LANES] lane-broadcast
        for (row0, _, _), s in zip(pieces, scores):
            m_scr[row0:, :] = jnp.maximum(m_scr[row0:, :], _lanes(jnp.max(s, axis=1)))
        m_new = m_scr[...]
        alpha = jnp.exp(m_prev - m_new)         # [Bq, LANES]
        l_scr[...] = l_scr[...] * alpha
        acc_scr[...] = acc_scr[...] * alpha[:, 0:1]
        for (row0, key0, key1), s in zip(pieces, scores):
            v_blk = v_ref[0, key0:key1, :]
            p = jnp.exp(s - _widen(m_new[row0:], key1 - key0))
            l_scr[row0:, :] += _lanes(jnp.sum(p, axis=1))
            acc_scr[row0:, :] += jax.lax.dot_general(
                p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    # a block strictly above the diagonal meets no case (under ``mask_block`` too: the
    # pieces a step computes end at multiples of 128, where a block of the mask ends)
    _for_step_case(qi, kj, block_q, block_k, cases, _compute)

    @pl.when(kj == num_k - 1)
    def _finalize():
        l = l_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l_safe[:, 0:1]).astype(o_ref.dtype)
        if with_lse:
            lse_ref[0] = _row(m_scr[...] + jnp.log(l_safe))


def _band_kernel(q_ref, *refs, sm_scale, band, views, has_slopes, with_lse, unroll):
    """One query block of ``heads`` fused batch x head programs against the
    ``views`` key blocks its band reaches, all in this ONE grid step (refs: k x
    views, v x views[, slopes], o[, lse]): the band is a few blocks wide
    whatever the sequence's length, so the softmax is taken over all of it at
    once: no running max, no rescaled accumulator, no scratch. View ``j`` holds
    key block ``lo(qi) + j`` (``_band_forward``'s index map holds it at the
    diagonal's block once past it; masked at the index it stands for, such a
    view lies above the diagonal and is empty). The mask is one per view for
    every head of the step; a row's max and sum are taken across the views
    elementwise first, so each costs one cross-lane reduction a row, not one a
    view. The heads go ``unroll`` at a time through a ``fori_loop``: written
    out in Python all eight overlap best on the chip, but the kernel is traced
    and lowered again in every process for every program that holds it, and
    eight copies of the body made that 1 s a prefill program (PERF.md §6,
    PR 40)."""
    k_refs, v_refs = refs[:views], refs[views:2 * views]
    slope_ref = refs[2 * views] if has_slopes else None
    o_ref = refs[2 * views + has_slopes]
    qi = pl.program_id(1)
    heads, block_q, _ = q_ref.shape
    block_k = k_refs[0].shape[1]
    lo = _band_lo(qi, block_q, block_k, band, jnp.maximum)
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    # q_pos - k_pos of view j: a key is seen from 0 (causal) up to band - 1 behind
    dist = [rows - cols + (qi * block_q - (lo + j) * block_k) for j in range(views)]
    seen = [(d >= 0) & (d < band) for d in dist]

    def head(h):
        q = q_ref[h]
        s = []
        for j in range(views):
            sj = sm_scale * jax.lax.dot_general(
                q, k_refs[j][h], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            if has_slopes:
                sj = sj - _widen(slope_ref[h], block_k) * dist[j].astype(jnp.float32)
            s.append(jnp.where(seen[j], sj, NEG_INF))
        # a row's own position is always in its band: m is a real score's
        m = jnp.max(functools.reduce(jnp.maximum, s), axis=1, keepdims=True)
        p = [jnp.exp(sj - m) for sj in s]
        l = jnp.sum(functools.reduce(jnp.add, p), axis=1, keepdims=True)
        acc = functools.reduce(jnp.add, [
            jax.lax.dot_general(pj.astype(v_refs[j].dtype), v_refs[j][h],
                                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            for j, pj in enumerate(p)])
        o_ref[h] = (acc * (1.0 / l)).astype(o_ref.dtype)
        if with_lse:
            refs[-1][h] = jnp.broadcast_to(m + jnp.log(l), (block_q, LANES))

    unroll = min(unroll, heads)

    def some(i, carry):
        for u in range(unroll):
            head(i * unroll + u)
        return carry

    jax.lax.fori_loop(0, heads // unroll, some, None)


@functools.partial(jax.jit, static_argnames=("sm_scale", "block_q", "block_k", "band", "interpret",
                                             "with_lse", "heads", "unroll"))
def _band_forward(q, k, v, slopes_bh, sm_scale, block_q, block_k, band, interpret,
                  with_lse=True, heads=None, unroll=BAND_UNROLL):
    """The forward of causal self-attention under a window known to the trace
    (``static_window``): grid ``(BH / heads, Sq / block_q)``, and a query block
    gets the key blocks its band reaches as SEPARATE operands of one step
    (``_band_kernel``), where the whole grid streams every key block through an
    innermost dimension. View ``j`` of query block ``qi`` is key block
    ``min(lo(qi) + j, diag(qi))``. ``heads`` programs ride one step
    (``band_plan``'s, unless the caller times its own). Without ``with_lse``
    (nothing will differentiate the call) the logsumexp, a float32 row of 128
    lanes a query, twice the output's bytes, is not written:
    -> (out, lse | None). Under ``jit``, so that the layers of a program that
    call it at one shape share one trace of the kernel."""
    BH, Sq, D = q.shape
    Dv = v.shape[2]
    _, _, views, planned = band_plan(Sq, band, BH, D, Dv, q.dtype.itemsize, block_q, block_k)
    heads = heads or planned

    def kv_view(j):
        def index(g, qi):
            lo = _band_lo(qi, block_q, block_k, band, jnp.maximum)
            return (g, jnp.minimum(lo + j, _diag(qi, block_q, block_k)), 0)
        return index

    in_specs = ([_vmem_spec((heads, block_q, D), lambda g, qi: (g, qi, 0))]
                + [_vmem_spec((heads, block_k, D), kv_view(j)) for j in range(views)]
                + [_vmem_spec((heads, block_k, Dv), kv_view(j)) for j in range(views)])
    operands = [q] + [k] * views + [v] * views
    if slopes_bh is not None:
        in_specs.append(_vmem_spec((heads, 1, LANES), lambda g, qi: (g, 0, 0)))
        operands.append(slopes_bh)
    outs = [(Dv, q.dtype)] + ([(LANES, jnp.float32)] if with_lse else [])
    out = pl.pallas_call(
        functools.partial(_band_kernel, sm_scale=sm_scale, band=band, views=views,
                          has_slopes=slopes_bh is not None, with_lse=with_lse, unroll=unroll),
        grid=(BH // heads, Sq // block_q),
        in_specs=in_specs,
        out_specs=[_vmem_spec((heads, block_q, width), lambda g, qi: (g, qi, 0))
                   for width, _ in outs],
        out_shape=[jax.ShapeDtypeStruct((BH, Sq, width), dtype) for width, dtype in outs],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="flash_fwd_band",
    )(*operands)
    return out[0], out[1] if with_lse else None


def _streamed_keys(causal, block_q, block_k):
    """Index map of the key-side operand streamed past query block ``qi`` (grid
    ``(bh, qi, kj)``): a causal step past the diagonal's block computes nothing,
    and stays on that block so that nothing is fetched for it either (an
    unchanged block index starts no copy)."""
    if not causal:
        return lambda bh, qi, kj: (bh, kj, 0)
    return lambda bh, qi, kj: (bh, jnp.minimum(kj, _diag(qi, block_q, block_k)), 0)


def _streamed_rows(causal, block_q, block_k, num_q):
    """The same for the query-side operands streamed past key block ``kj`` (grid
    ``(bh, kj, qi)``): the steps before the first query block with a row at or
    under the key block's first key stay on that one."""
    if not causal:
        return lambda bh, kj, qi: (bh, qi, 0)
    return lambda bh, kj, qi: (
        bh, jnp.minimum(jnp.maximum(qi, kj * block_k // block_q), num_q - 1), 0)


def _flash_forward(q, k, v, slopes_bh, w_arr, sm_scale, causal, block_q,
                   block_k, interpret, band=0, with_lse=True, mask_block=1):
    """-> (out, lse [BH, 1, Sq] float32: a row's logsumexp along the lanes, or
    None without ``with_lse``: nothing will differentiate the call, a serving
    prefill, and the row, with the transpose that lays it, is not made).
    ``band`` (``static_window``): ``_band_forward`` (which writes the logsumexp
    lane-broadcast: its first lane is taken); ``w_arr`` is then the backward's
    alone."""
    if band:
        out, lse = _band_forward(q, k, v, slopes_bh, sm_scale, block_q, block_k, band, interpret,
                                 with_lse)
        return out, lse if lse is None else lse[:, None, :, 0]
    BH, Sq, D = q.shape
    Dv = v.shape[2]  # a value head may be narrower than a q/k head (latent attention)
    Sk = k.shape[1]
    num_k = Sk // block_k
    grid = (BH, Sq // block_q, num_k)
    base = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, num_k=num_k, mask_block=mask_block,
        with_lse=with_lse,
        cases=_step_cases(Sq // block_q, num_k, block_q, block_k, causal,
                          _diag_cut(block_q, block_k)),
    )
    kv_block = _streamed_keys(causal, block_q, block_k)
    in_specs = [
        _vmem_spec((1, block_q, D), lambda bh, qi, kj: (bh, qi, 0)),
        _vmem_spec((1, block_k, D), kv_block),
        _vmem_spec((1, block_k, Dv), kv_block),
    ]
    operands = [q, k, v]
    if slopes_bh is not None:
        in_specs.append(_vmem_spec((1, 1, LANES), lambda bh, qi, kj: (bh, 0, 0)))
        operands.append(slopes_bh)
    if w_arr is not None:
        in_specs.append(_vmem_spec((1, LANES), lambda bh, qi, kj: (0, 0)))
        operands.append(w_arr)
    kernel = _wrap_extras(base, 3, slopes_bh is not None, w_arr is not None)
    out_specs = [_vmem_spec((1, block_q, Dv), lambda bh, qi, kj: (bh, qi, 0))]
    out_shape = [jax.ShapeDtypeStruct((BH, Sq, Dv), q.dtype)]
    if with_lse:  # one float32 a row (``_row``)
        out_specs.append(_vmem_spec((1, 1, block_q), lambda bh, qi, kj: (bh, 0, qi)))
        out_shape.append(jax.ShapeDtypeStruct((BH, 1, Sq), jnp.float32))
    out, *lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            _scratch((block_q, LANES)),   # running row-max m
            _scratch((block_q, LANES)),   # running row-sum l
            _scratch((block_q, Dv)),      # output accumulator
        ],
        compiler_params=_compiler_params(len(grid)),
        interpret=interpret,
        name="flash_fwd",
    )(*operands)
    return out, lse[0] if with_lse else None


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _delta_row(do_ref, o_ref):
    """The softmax Jacobian's D_i = rowsum(dO ∘ O) of the step's query block as
    float32 rows along the lanes (``_row``): -> ``from_row``, the [1, rows -
    row0] of it from a row on. ``o_ref`` is the O block streamed beside the dO
    block the step holds anyway, and the row is made here, no array of it in
    HBM; or, where ``_flash_backward`` made the row itself (a head narrower than
    the lanes), the [1, 1, rows] block of it, read where a piece starts."""
    if o_ref.shape[1] == 1:
        return lambda row0: o_ref[0, :, row0:]
    prod = do_ref[0].astype(jnp.float32) * o_ref[0].astype(jnp.float32)
    row = _row(_lanes(jnp.sum(prod, axis=1)))
    return lambda row0: row[:, row0:]


def _tile_grads(q_ref, k_ref, v_ref, do_ref, lse_ref, delta, qi, kj, piece, *, sm_scale, causal,
                slope_ref, w_ref):
    """One piece of a step of the backward (``_step_pieces``), the tile
    TRANSPOSED, keys down the sublanes and rows along the lanes, where a row's
    logsumexp and delta (``lse_ref`` [1, 1, Bq], ``delta``: ``_delta_row``'s) lie as they
    are stored: S^T = K Q^T, P^T = exp(S^T - lse), dP^T = V dO^T, dS^T = P^T *
    (dP^T - delta) -> (q, do, k, P^T and dS^T in the operands' dtype): what
    dV += P^T dO, dK += dS^T Q (both as they lie) and dQ += dS K are taken from."""
    row0, key0, key1 = piece
    block_q, block_k = q_ref.shape[1], k_ref.shape[1]
    k_blk = k_ref[0, key0:key1, :]
    q_blk = q_ref[0, row0:, :]    # [rows, D]
    do_blk = do_ref[0, row0:, :]  # [rows, Dv]
    st = _block_scores(q_blk, k_blk, qi * block_q + row0, kj * block_k + key0, sm_scale=sm_scale,
                       causal=causal, slope_ref=slope_ref, w_ref=w_ref, transposed=True)
    pt = jnp.exp(st - lse_ref[0, :, row0:])  # [keys, rows]
    dpt = jax.lax.dot_general(
        v_ref[0, key0:key1, :], do_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    dst = pt * (dpt - delta(row0))
    return q_blk, do_blk, k_blk, pt.astype(do_blk.dtype), dst.astype(q_blk.dtype)


def _dkdv_add(dk_scr, dv_scr, piece, q_blk, do_blk, pt, dst, sm_scale):
    """dV += P^T dO and dK += dS^T Q . scale on the piece's keys of the key
    block's float32 accumulators."""
    _, key0, key1 = piece
    dv_scr[key0:key1, :] += jax.lax.dot_general(
        pt, do_blk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    dk_scr[key0:key1, :] += sm_scale * jax.lax.dot_general(
        dst, q_blk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def _dq_of(dst, k_blk, sm_scale):
    """dS K . scale of a piece, [rows, D] float32, from the transposed dS^T."""
    return sm_scale * jax.lax.dot_general(
        dst, k_blk, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def _bwd_fused_kernel(
    q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dq_ref, dk_ref, dv_ref,
    dq_scr, dk_scr, dv_scr, *, sm_scale, causal, num_q, num_k, cases, slope_ref=None, w_ref=None,
):
    """The whole backward in one pass over the scores: grid ``(bh, kj, qi)``, the
    query blocks the inner walk. A tile's P and dS are computed ONCE
    (``_tile_grads``) and feed dV and dK of its key block (float32 scratch,
    written when the key block's walk ends) and dQ of its query block: dQ of the
    whole head stays in VMEM (``dq_scr``, [Sq, D] float32) across the head's key
    blocks and is written once, in the output's dtype, when the last one ends."""
    kj = pl.program_id(1)
    qi = pl.program_id(2)
    block_q, block_k = q_ref.shape[1], k_ref.shape[1]

    @pl.when((kj == 0) & (qi == 0))
    def _init_head():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(qi == 0)
    def _init_keys():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _compute(pieces):
        delta = _delta_row(do_ref, o_ref)
        for piece in pieces:
            q_blk, do_blk, k_blk, pt, dst = _tile_grads(
                q_ref, k_ref, v_ref, do_ref, lse_ref, delta, qi, kj, piece, sm_scale=sm_scale,
                causal=causal, slope_ref=slope_ref, w_ref=w_ref)
            _dkdv_add(dk_scr, dv_scr, piece, q_blk, do_blk, pt, dst, sm_scale)
            rows = pl.ds(pl.multiple_of(qi * block_q + piece[0], LANES), block_q - piece[0])
            dq_scr[rows, :] += _dq_of(dst, k_blk, sm_scale)

    # q-blocks entirely above the diagonal contribute nothing to this k-block,
    # and the others only to the keys at or under their rows
    _for_step_case(qi, kj, block_q, block_k, cases, _compute)

    @pl.when(qi == num_q - 1)
    def _finalize_keys():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)

    @pl.when((kj == num_k - 1) & (qi == num_q - 1))
    def _finalize_head():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkdv_kernel(
    q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dk_ref, dv_ref,
    dk_scr, dv_scr, *, sm_scale, causal, num_q, cases, slope_ref=None, w_ref=None,
):
    kj = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _compute(pieces):
        delta = _delta_row(do_ref, o_ref)
        for piece in pieces:
            q_blk, do_blk, _, pt, dst = _tile_grads(
                q_ref, k_ref, v_ref, do_ref, lse_ref, delta, qi, kj, piece, sm_scale=sm_scale,
                causal=causal, slope_ref=slope_ref, w_ref=w_ref)
            _dkdv_add(dk_scr, dv_scr, piece, q_blk, do_blk, pt, dst, sm_scale)

    _for_step_case(qi, kj, q_ref.shape[1], k_ref.shape[1], cases, _compute)

    @pl.when(qi == num_q - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dq_ref, dq_scr,
    *, sm_scale, causal, num_k, cases, slope_ref=None, w_ref=None,
):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def _compute(pieces):
        delta = _delta_row(do_ref, o_ref)
        for piece in pieces:
            _, _, k_blk, _, dst = _tile_grads(
                q_ref, k_ref, v_ref, do_ref, lse_ref, delta, qi, kj, piece, sm_scale=sm_scale,
                causal=causal, slope_ref=slope_ref, w_ref=w_ref)
            dq_scr[piece[0]:, :] += _dq_of(dst, k_blk, sm_scale)

    _for_step_case(qi, kj, q_ref.shape[1], k_ref.shape[1], cases, _compute)

    @pl.when(kj == num_k - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _fused_step_bytes(rows, block_q, block_k, d, dv, itemsize):
    """VMEM a step of the fused backward holds, by the count of its own buffers:
    the streamed blocks twice (q, dO, O and the float32 row of logsumexps, eight
    sublanes tall in VMEM, of a query block; k, v and the dK, dV it writes of a
    key block), the key block's two float32 accumulators and the head's dQ
    (float32 scratch and the block written out, twice), with 3 bytes a score
    element for the tile in flight (the compiler's own temporaries came to 0.9 -
    6 of them: ``FUSED_VMEM_LIMIT`` holds what is over); a head narrower than
    the 128 lanes is padded to them."""
    both = max(d, LANES) + max(dv, LANES)
    streamed = (itemsize * ((block_q + 2 * block_k) * both + block_q * max(dv, LANES))
                + 4 * 8 * block_q)
    held = 4 * block_k * both + rows * max(d, LANES) * (4 + 2 * itemsize)
    return 2 * streamed + held + 3 * block_q * block_k


def backward_form(rows: int, d: int, dv: int, itemsize: int, block_q: int | None = None,
                  block_k: int | None = None, keys: int | None = None) -> str:
    """``"fused"`` | ``"split"``: the backward the kernels take over ``rows``
    query rows (``keys`` keys: the rows', for self-attention) of heads ``d``
    wide (values ``dv``), from the shapes and the blocks alone (None: the
    call's own). One kernel (``flash_bwd``) where a head's dQ fits VMEM beside a
    step's blocks (``_fused_step_bytes`` within ``FUSED_VMEM_BYTES``: up to 6,144
    rows of 128-wide bfloat16 heads at the call's own blocks, 2,048 of latent
    attention's 192 / 128), else the pair that recomputes the scores
    (``flash_bwd_dkdv`` + ``flash_bwd_dq``), whose steps do not grow with the
    rows."""
    rows += (-rows) % 128
    keys = rows if keys is None else keys + (-keys) % 128
    block_q, block_k = _outer_blocks(rows, keys, max(d, dv), itemsize, block_q, block_k)
    fits = _fused_step_bytes(rows, block_q, block_k, d, dv, itemsize) <= FUSED_VMEM_BYTES
    return "fused" if fits else "split"


def _flash_backward(res, g, sm_scale, causal, block_q, block_k, interpret):
    q, k, v, slopes_bh, w_arr, out, lse = res  # lse [BH, 1, Sq]: the forward's row, as it was written
    BH, Sq, D = q.shape
    Sk, Dv = v.shape[1:]  # a value head may be narrower than a q/k head (latent attention)
    num_q = Sq // block_q
    num_k = Sk // block_k
    cases = _step_cases(num_q, num_k, block_q, block_k, causal,
                        _diag_cut(block_q, block_k, backward=True))

    kv_block = _streamed_keys(causal, block_q, block_k)
    q_block = _streamed_rows(causal, block_q, block_k, num_q)
    key_block = lambda bh, kj, qi: (bh, kj, 0)
    lse_block = lambda bh, kj, qi: (bh, 0, q_block(bh, kj, qi)[1])  # the row [BH, 1, Sq] of the same block

    has_slopes = slopes_bh is not None
    has_window = w_arr is not None
    extra_specs = []
    extra_ops = []
    if has_slopes:
        extra_specs.append(_vmem_spec((1, 1, LANES), lambda bh, a, b: (bh, 0, 0)))
        extra_ops.append(slopes_bh)
    if has_window:
        extra_specs.append(_vmem_spec((1, LANES), lambda bh, a, b: (0, 0)))
        extra_ops.append(w_arr)
    # O rides beside dO and a step takes delta = rowsum(dO ∘ O) from the two blocks
    # (``_delta_row``) where its heads fill whole lane tiles: XLA's own pass over dO and
    # O is 0.27 ms a call at [128, 2048, 128] where the kernel grows by 0.03, and with it
    # the train cell's step read 1,199.4 ms for 1,195.2 (PERF.md section 6, PR 65). A
    # narrower head's O is kept by XLA with the rows along the lanes (the saved
    # ``flash_out`` stack of 64-wide heads in half the bytes): an operand of the kernel
    # would pin it to padded tiles, so there XLA's pass makes the row, as it made the
    # array before
    inside = Dv % LANES == 0
    if not inside:
        out = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)[:, None, :]
    o_spec = lambda rows, row: _vmem_spec((1, block_q, Dv), rows) if inside else _vmem_spec((1, 1, block_q), row)
    operands = (q, k, v, g, out, lse, *extra_ops)
    dslopes = jnp.zeros_like(slopes_bh) if has_slopes else None
    dw = jnp.zeros_like(w_arr) if has_window else None

    # the query blocks streamed past a key block: dK/dV's walk, and the fused kernel's
    by_key_block = [
        _vmem_spec((1, block_q, D), q_block),
        _vmem_spec((1, block_k, D), key_block),
        _vmem_spec((1, block_k, Dv), key_block),
        _vmem_spec((1, block_q, Dv), q_block),
        o_spec(q_block, lse_block),
        _vmem_spec((1, 1, block_q), lse_block),
    ] + extra_specs
    dkdv_specs = [_vmem_spec((1, block_k, D), key_block), _vmem_spec((1, block_k, Dv), key_block)]
    dkdv_shapes = [jax.ShapeDtypeStruct((BH, Sk, D), k.dtype),
                   jax.ShapeDtypeStruct((BH, Sk, Dv), v.dtype)]
    dkdv_scratch = [_scratch((block_k, D)), _scratch((block_k, Dv))]

    if backward_form(Sq, D, Dv, q.dtype.itemsize, block_q, block_k, Sk) == "fused":
        base = functools.partial(_bwd_fused_kernel, sm_scale=sm_scale, causal=causal,
                                 num_q=num_q, num_k=num_k, cases=cases)
        dq, dk, dv = pl.pallas_call(
            _wrap_extras(base, 6, has_slopes, has_window),
            grid=(BH, num_k, num_q),
            in_specs=by_key_block,
            out_specs=[_vmem_spec((1, Sq, D), lambda bh, kj, qi: (bh, 0, 0))] + dkdv_specs,
            out_shape=[jax.ShapeDtypeStruct((BH, Sq, D), q.dtype)] + dkdv_shapes,
            scratch_shapes=[_scratch((Sq, D))] + dkdv_scratch,
            interpret=interpret,
            # dQ accumulates over both walks: only the heads are independent
            compiler_params=_compiler_params(3, 2, FUSED_VMEM_LIMIT),
            name="flash_bwd",
        )(*operands)
        return dq, dk, dv, dslopes, dw

    # the pair holds a whole [block_q, block_k] tile as the fused kernel does, and the
    # compiler's temporaries for dK/dV's moved past the default 16 MiB with the count of
    # heads alone (256-wide bfloat16 heads at 2,048 keys: fits at 8 heads, 19.8 MiB at 128)
    cp = _compiler_params(3, vmem_limit_bytes=FUSED_VMEM_LIMIT)
    base_dkdv = functools.partial(
        _bwd_dkdv_kernel, sm_scale=sm_scale, causal=causal, num_q=num_q, cases=cases,
    )
    dk, dv = pl.pallas_call(
        _wrap_extras(base_dkdv, 6, has_slopes, has_window),
        grid=(BH, num_k, num_q),
        in_specs=by_key_block,
        out_specs=dkdv_specs,
        out_shape=dkdv_shapes,
        scratch_shapes=dkdv_scratch,
        interpret=interpret,
        compiler_params=cp,
        name="flash_bwd_dkdv",
    )(*operands)

    base_dq = functools.partial(
        _bwd_dq_kernel, sm_scale=sm_scale, causal=causal, num_k=num_k, cases=cases,
    )
    query_block = lambda bh, qi, kj: (bh, qi, 0)
    query_row = lambda bh, qi, kj: (bh, 0, qi)
    dq = pl.pallas_call(
        _wrap_extras(base_dq, 6, has_slopes, has_window),
        grid=(BH, num_q, num_k),
        in_specs=[
            _vmem_spec((1, block_q, D), query_block),
            _vmem_spec((1, block_k, D), kv_block),
            _vmem_spec((1, block_k, Dv), kv_block),
            _vmem_spec((1, block_q, Dv), query_block),
            o_spec(query_block, query_row),
            _vmem_spec((1, 1, block_q), query_row),
        ] + extra_specs,
        out_specs=_vmem_spec((1, block_q, D), query_block),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
        scratch_shapes=[_scratch((block_q, D))],
        interpret=interpret,
        compiler_params=cp,
        name="flash_bwd_dq",
    )(*operands)
    return dq, dk, dv, dslopes, dw


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash_bhsd(q, k, v, slopes_bh, w_arr, sm_scale, causal, block_q, block_k,
                interpret, band, mask_block=1):
    out, _ = _flash_forward(q, k, v, slopes_bh, w_arr, sm_scale, causal,
                            block_q, block_k, interpret, band, with_lse=False,
                            mask_block=mask_block)
    return out


def _flash_bhsd_fwd(q, k, v, slopes_bh, w_arr, sm_scale, causal, block_q,
                    block_k, interpret, band, mask_block=1):
    if mask_block > 1:
        raise NotImplementedError(
            "flash_attention backward under mask_block > 1 has no code: the backward kernels "
            "attend under the causal mask (a model that generates by diffusion over blocks "
            "has no training objective here)")
    out, lse = _flash_forward(q, k, v, slopes_bh, w_arr, sm_scale, causal,
                              block_q, block_k, interpret, band)
    # Under jax.checkpoint, out/lse are the residuals the backward kernels
    # need; naming them lets a remat policy (models/transformer.py
    # _remat_policy 'flash' names) save them so the forward kernel is NOT
    # re-run inside the backward pass. lse is the kernel's own [BH, 1, Sq] row,
    # one float32 a query, saved and handed to the backward as it was written.
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, (q, k, v, slopes_bh, w_arr, out, lse)


def _flash_bhsd_bwd(sm_scale, causal, block_q, block_k, interpret, band, mask_block, res, g):
    # the backward kernels keep the whole grid: a band's window reaches them as
    # the array a traced one would be (``w_arr`` among the residuals), and they
    # take the triangle's blocks, not the band's
    if band:
        q, v = res[0], res[2]
        block_q, block_k = _outer_blocks(q.shape[1], q.shape[1], max(q.shape[2], v.shape[2]),
                                         q.dtype.itemsize, None, None)
    return _flash_backward(res, g, sm_scale, causal, block_q, block_k, interpret)


_flash_bhsd.defvjp(_flash_bhsd_fwd, _flash_bhsd_bwd)


def flash_attention(
    q,
    k,
    v,
    causal: bool = True,
    bias=None,
    sm_scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    alibi_slopes=None,
    window=None,
    mask_block: int = 1,
):
    """Fused blockwise attention. q/k/v: [B, S, H, D] -> [B, S, H, D]. The
    kernels take value heads of a width of their own (v [B, S, H, Dv]
    -> [B, S, H, Dv]: latent attention's 192-wide q/k beside 128-wide v;
    dV is then as wide as v, dQ and dK as q and k); the softmax scale defaults
    to 1/sqrt(D) of the q/k heads as given.

    Structured biases are FUSED (computed from block positions in-kernel, no
    HBM bias tensor — the reference threads alibi through its inference
    kernels the same way, pt_binding.cpp:1231-1283):
      * ``alibi_slopes``: per-head slopes [H] (BLOOM). Bias added to the
        scores is slope_h * (k_pos - q_pos).
      * ``window``: local-attention window (<= 0 means global). A traced
        scalar is a runtime operand — GPT-Neo's alternating local layers run
        one compiled kernel. A Python number is a constant of the trace
        (``static_window``): for causal self-attention the FORWARD grid is then
        a band of the key blocks the window reaches, at blocks sized to it
        (``band_plan``; a caller's ``block_q`` / ``block_k`` still win), and
        the backward kernels run their whole grid under the same window.
    A general dense ``bias`` tensor is not fused; those callers use the XLA
    path (models/transformer._attention_dispatch falls back).

    ``mask_block`` > 1 (causal self-attention, forward only): the mask is causal
    between blocks of that many positions and a query sees its own block whole (key
    j iff j // B <= i // B: generation by diffusion over blocks). A power of two up
    to 128 that divides the rows: the grid, the blocks streamed and the sub-tiles a
    step computes are the causal ones (all end at multiples of 128, where a block of
    the mask ends), and only the compare inside a diagonal tile differs. 1 traces
    what it always has.

    Sequence lengths need not be block-aligned when ``causal``: q/k/v are
    zero-padded up to a 128 multiple — padded key positions sit *after* every
    real query position, so the causal mask already excludes them, and padded
    query rows are sliced off the output (curriculum-truncated odd lengths
    train fine under attn_impl='flash').
    """
    if bias is not None:
        raise NotImplementedError("flash_attention: dense additive bias not fused; use attn_impl='xla'")
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    slopes_bh = None
    if alibi_slopes is not None:
        sl = jnp.asarray(alibi_slopes, jnp.float32)
        assert sl.shape == (H,), (sl.shape, H)
        # one [1, LANES] row per fused batch×head program
        slopes_bh = jnp.broadcast_to(
            jnp.tile(sl, B)[:, None, None], (B * H, 1, LANES))
    if mask_block > 1:
        ok = causal and Sq == Sk and window is None and alibi_slopes is None
        if not ok or mask_block & (mask_block - 1) or mask_block > 128 or Sq % mask_block:
            raise NotImplementedError(
                f"flash_attention(mask_block={mask_block}) is causal self-attention with no "
                "window and no alibi, under blocks of a power of two up to 128 that divides "
                f"the rows (got causal={causal}, rows ({Sq}, {Sk}), window={window!r}, "
                f"alibi={alibi_slopes is not None})")
    window, band = static_window(window, Sq, Sk, causal)
    w_arr = None
    if window is not None:
        w_arr = jnp.full((1, LANES), 0.0, jnp.float32) + jnp.asarray(
            window, jnp.float32)

    pad_q = (-Sq) % 128
    pad_k = (-Sk) % 128
    if pad_q or pad_k:
        if not causal:
            raise ValueError(
                f"non-causal flash_attention needs 128-aligned lengths, got ({Sq}, {Sk})"
            )
        if Sq == Sk:  # keep self-attention's diagonal alignment
            q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
            k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        else:
            raise ValueError(
                f"cross-attention lengths ({Sq}, {Sk}) must be 128-aligned"
            )
    Sq_p, Sk_p = q.shape[1], k.shape[1]
    # a band whose step fits VMEM takes the blocks ``band_plan`` sized to it
    plan = band and band_plan(Sq_p, band, B * H, D, v.shape[3], q.dtype.itemsize,
                              block_q, block_k)
    if plan:
        block_q, block_k = plan[:2]
    else:
        band = 0
        block_q, block_k = _outer_blocks(Sq_p, Sk_p, max(D, v.shape[3]), q.dtype.itemsize,
                                         block_q, block_k)
    if Sq_p % block_q or Sk_p % block_k:
        raise ValueError(
            f"sequence lengths ({Sq_p}, {Sk_p}) must be divisible by blocks ({block_q}, {block_k})"
        )
    if interpret is None:
        interpret = interpret_default()

    def to_bhsd(x):
        return x.transpose(0, 2, 1, 3).reshape(x.shape[0] * x.shape[2], x.shape[1], x.shape[3])

    out = _flash_bhsd(
        to_bhsd(q), to_bhsd(k), to_bhsd(v), slopes_bh, w_arr, sm_scale, causal,
        block_q, block_k, interpret, band, mask_block
    )
    out = out.reshape(B, H, Sq_p, v.shape[3]).transpose(0, 2, 1, 3)
    if pad_q:
        out = out[:, :Sq]
    return out


def flash_attention_sharded(q, k, v, *, mesh, alibi_slopes=None, window=None, **kw):
    """``flash_attention`` for un-shard_mapped (pjit) callers on ``mesh``.

    The SPMD partitioner cannot split a Mosaic kernel ("Mosaic kernels cannot
    be automatically partitioned"), so on a mesh of several devices the call
    is wrapped in ``shard_map``: each device runs the kernel on its own batch
    rows (data/fsdp axes) and heads (model axis) — the slot cache's layout
    rule, parallel/sharding.batch_and_head_axes. Inside another shard_map
    (the pipeline's stage program) the caller's manual axes already own the
    layout and the kernel is called as is. A window that is a constant of the
    trace is closed over, not passed: as an operand of ``shard_map`` it would
    arrive traced and the kernel would keep the whole grid."""
    from jax.sharding import PartitionSpec as P

    from ...parallel.sharding import batch_and_head_axes

    traced_window = isinstance(window, jax.Array)

    def call(q, k, v, slopes, w=None):
        return flash_attention(q, k, v, alibi_slopes=slopes,
                               window=w if traced_window else window, **kw)

    if mesh is None or mesh.size == 1 or jax.sharding.get_abstract_mesh().manual_axes:
        return call(q, k, v, alibi_slopes, window)
    batch_axes, head_axis = batch_and_head_axes(mesh, q.shape[0], q.shape[2])
    qkv = P(batch_axes, None, head_axis, None)
    # absent operands ride through as None leaves, which match any spec
    return jax.shard_map(
        call, mesh=mesh, in_specs=(qkv, qkv, qkv, P(head_axis), P()),
        out_specs=qkv, check_vma=False,
    )(q, k, v, alibi_slopes, window if traced_window else None)
