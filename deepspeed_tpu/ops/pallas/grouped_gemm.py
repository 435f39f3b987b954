"""Grouped matmul Pallas kernel: ``lhs [m, K]`` whose rows lie sorted by group
times ``rhs [G, K, N]``, row r through the matrix of the group it lies in
(``group_sizes [G]``) -> ``[m, N]``. What ``jax.lax.ragged_dot`` computes, and
written from the kernel the TPU compiler lowers that to
(``jax.experimental.pallas.ops.tpu.megablox.gmm``), with the one thing the
compiler keeps to itself an argument here: the ROW TILE.

A grid step is one visit of a ``tm``-row tile by one group: the whole tile goes
through the group's matrix and the rows that are the group's are stored. A tile
a group boundary falls in is visited once by each group that has rows in it, so
``m / tm + (groups - 1)`` visits at most, and every visit costs ``tm`` rows of
the MXU whatever it stores. The compiler's tile is 512 rows; the routed
feed-forward's groups are 64 to 384 rows in the mean (``moe/dropless.py``), so
most of its visits were of rows another group owns (PERF.md §6, PR 46 has the
chip's timings at every tile).

The grid is ``(N / tn, visits)``; a visit multiplies ``[tm, K]`` rows by the
group's ``[K, tn]`` column block of the bank, all of ``K`` at once (a block cut
along ``K`` would be fetched again by every visit: three times the bank's bytes
at these groups, and slower at every shape on the chip). The rows and the output
are blocks of the grid's own pipeline. The bank is not: that pipeline fetches a
step's blocks during the step before, so a group's 3 to 12 MB block would have
the LAST visit of the group before it to arrive in, 2.7 us of multiplying at 128
rows against 5 us of copy, and every group would start with a wait (0.78 ms a
projection at OLMoE's shapes, against ``ragged_dot``'s 1.28). The kernel keeps
two blocks of its own in VMEM and starts the FOLLOWING group's copy at a group's
first visit, so the copy has all of the group's visits to arrive in. A
bank is read once a column tile however short the row tile, and the rows once a
column tile. An empty group gets no visit and its matrix is never read: the held
``[L * E, K, N]`` stacks with one layer's groups filled cost that layer's bytes.
Rows past the last group's are no group's: no visit stores them, and what the
output holds there is unspecified (``ragged_dot`` writes zeros).

Forward only (no ``custom_vjp``: the callers that differentiate keep
``ragged_dot``). On the CPU platform the kernel runs in the Pallas interpreter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_default

LANES = 128  # the MXU's width, and the least a block's last dimension may be
# The row tile, at every number of rows: the MXU's width (``gmm_tiling`` has the
# prefill shapes' table). At the 144 to 512 pairs of a decode step, where a group
# is one visit of a row or two, tiles of 16, 32 and 64 rows read within 0.5% of
# it at every shape (PERF.md §6, PR 62): a visit then lasts as long as its
# block's copy, not as its rows' multiplying.
ROW_TILE = LANES
# What ONE of the kernel's blocks of the bank may take of VMEM (the chip has 128
# MiB; the compiler gives a kernel 16 MiB unless told otherwise, and is told
# here what the blocks add up to: ``_vmem_limit``).
BANK_BLOCK_BYTES = 12 * 2 ** 20
# The rows of a call up to which the kernel keeps THREE blocks of the bank and
# not two (``bank_blocks``): 16 row tiles, over the routed layers' 64 to 128
# experts a row or two a group.
FEW_ROWS = 2048
# The instruction's name in the compiled program and the operation's in a device
# trace: the grouped matmul of the routed feed-forward under whichever kernel, so
# what finds ``lax.ragged_dot``'s seconds there (``ragged-dot``) finds these.
KERNEL_NAME = "ragged-dot-gmm"


def visits(group_sizes, m: int, tm: int):
    """The grid's visits axis, from the groups' sizes: -> (offsets [G + 1], the
    row each group starts at; group [m / tm + G - 1] and tile [same], what visit
    i multiplies; following [G + 1], the next group after g that visits a tile
    (G: none, and none follows it) and nth [G], g's place among those that do;
    how many visits there are). A group visits the tiles from the one its first
    row lies in to the one its last row lies in; an empty group visits none.
    Visits past the count repeat the last one and are not run."""
    G = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = starts // tm
    n_tiles = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    upto = jnp.cumsum(n_tiles)  # visits of groups 0..g
    i = jnp.arange(m // tm + G - 1, dtype=jnp.int32)
    i = jnp.minimum(i, upto[-1] - 1)
    group = jnp.minimum(jnp.sum(upto[None, :] <= i[:, None], axis=1, dtype=jnp.int32), G - 1)
    tile = first[group] + i - (upto[group] - n_tiles[group])
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends.astype(jnp.int32)])
    ids = jnp.arange(G, dtype=jnp.int32)
    later = lax.cummin(jnp.where(n_tiles > 0, ids, G), reverse=True)  # first visiting group >= g
    following = jnp.concatenate([later[1:], jnp.full((2,), G, jnp.int32)])  # and none follows none
    nth = jnp.cumsum(n_tiles > 0, dtype=jnp.int32) - 1
    return (offsets, group, tile.astype(jnp.int32), following, nth), upto[-1].astype(jnp.int32)


def _gmm_kernel(offsets, group, tile, following, nth, lhs, rhs, out, bank, arrived, *,
                tm: int, tn: int):
    """One visit: ``lhs`` [tm, K] and ``out`` [tm, tn] are the tile's blocks, brought
    and taken by the grid's own pipeline; ``rhs`` is the whole bank where it lies
    in HBM, and ``bank`` [buffers, K, tn] the column blocks of it this kernel keeps:
    the visiting group's and those of the ``buffers - 1`` groups that visit after
    it, the last of whose copies starts at the group's first visit and is waited
    for at its own, so it has all of the visits in between to arrive in (the
    grid's pipeline would start it at the group's LAST visit: a 4 MB block
    against one 128-row visit's 2.7 us of multiplying)."""
    n, i = pl.program_id(0), pl.program_id(1)
    g = group[i]
    buffers, G = bank.shape[0], rhs.shape[0]
    slot = nth[g] % buffers

    def block(of, into):
        return pltpu.make_async_copy(rhs.at[of, :, pl.ds(n * tn, tn)], bank.at[into],
                                     arrived.at[into])

    def start(of, into):
        @pl.when(of < G)
        def _():
            block(of, into).start()

    @pl.when(i == 0)
    def _():
        of = g  # the first group that visits: nth 0
        for into in range(buffers - 1):
            start(of, into)
            of = following[of]

    @pl.when((i == 0) | (group[jnp.maximum(i - 1, 0)] != g))
    def _():
        block(g, slot).wait()
        of = g
        for _ in range(buffers - 1):
            of = following[of]
        start(of, (nth[g] + buffers - 1) % buffers)  # the block of the group before g: done with

    product = jnp.dot(lhs[...], bank[slot], preferred_element_type=jnp.float32)
    rows = tile[i] * tm + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    mine = (rows >= offsets[g]) & (rows < offsets[g + 1])
    out[...] = jnp.where(mine, product.astype(out.dtype), out[...])


def _vmem_limit(tm, K, tn, itemsize, buffers):
    """The bank's blocks, the rows' and the output's twice (the grid's
    pipeline double-buffers them), the float32 product of a visit, and a
    quarter over for what the compiler adds."""
    held = (buffers * K * tn + 2 * (tm * K + tm * tn)) * itemsize + tm * tn * 4
    return max(16 * 2 ** 20, 5 * held // 4)


@functools.partial(jax.jit, static_argnames=("tm", "tn", "buffers", "interpret"))
def _gmm(lhs, rhs, group_sizes, tm, tn, buffers, interpret):
    m, K = lhs.shape
    G, _, N = rhs.shape
    scalars, count = visits(group_sizes, m, tm)
    itemsize = jnp.dtype(lhs.dtype).itemsize
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, tn=tn),
        out_shape=jax.ShapeDtypeStruct((m, N), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(N // tn, count),
            in_specs=[pl.BlockSpec((tm, K), lambda n, i, offsets, group, tile, *_: (tile[i], 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tm, tn), lambda n, i, offsets, group, tile, *_: (tile[i], n)),
            scratch_shapes=[pltpu.VMEM((buffers, K, tn), rhs.dtype),
                            pltpu.SemaphoreType.DMA((buffers,))],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(tm, K, tn, itemsize, buffers)),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * K * N, transcendentals=0,
            bytes_accessed=(m * K * (N // tn) + K * N * min(G, m) + m * N) * itemsize),
        interpret=interpret,
        name=KERNEL_NAME,
    )(*scalars, lhs, rhs)


def bank_blocks(m: int) -> int:
    """How many blocks of the bank the kernel keeps over ``m`` rows: the visiting
    group's and the next one's, or the next two's. With two, a group's copy
    starts when the group before it begins: early enough where a group is several
    visits (a prefill's 64 to 384 rows a group), but where a group is ONE visit (a
    decode step's pairs: 24 rows x 6 choices touch 87 experts, a row or two each)
    one copy is in flight at a time, with a gap at every wait. On the chip
    (PERF.md §6, PR 62), one layer's routed block at the four decode shapes, two
    blocks / three (ms): 1.26 / 1.15, 1.08 / 1.01, 1.16 / 1.08, 1.78 / 1.69; one
    projection at 2,048 to 8,192 rows 4 to 8% faster with three at 32 to 128 rows
    a group, at 16,384 rows over OLMoE's 64 experts (256 a group) 5 to 7% SLOWER,
    at kanana's 49,152 over 128 within 0.5%. No program the benchmark ran before
    PR 62 hands the kernel ``FEW_ROWS`` rows or fewer: they keep two."""
    return 3 if m <= FEW_ROWS else 2


def whole_tiles(m: int) -> int:
    """``m`` rows up to whole row tiles: what a caller that may pad hands over."""
    return -(-m // ROW_TILE) * ROW_TILE


def gmm_tiling(m: int, K: int, N: int, itemsize: int = 2):
    """(tm, tn) of ``grouped_matmul`` over ``m`` rows, or None where the kernel
    does not take the shapes (rows that are no whole number of tiles). From the
    traced shapes alone, by the table on the chip (PERF.md §6, PR 46: nine calls
    of the four routed cells, groups of 64 to 384 rows in the mean). Rows: the
    MXU's width; 256 read 6 to 30% over it and 512 45 to 105% at every shape,
    64 within 6% under it at eight and 10% over at one. Columns: all of them
    where a block of the bank fits ``BANK_BLOCK_BYTES`` (the rows are then
    read once), else halved until it does; at 512 every shape read 8 to 27%
    more. How many blocks of the bank the kernel keeps is ``bank_blocks``'s,
    from the rows too."""
    tm = ROW_TILE
    if m % tm:
        return None
    tn = N
    while K * tn * itemsize > BANK_BLOCK_BYTES and tn % (2 * LANES) == 0:
        tn //= 2
    return tm, tn


def grouped_matmul(lhs, rhs, group_sizes, tiling, interpret: bool | None = None):
    """lhs [m, K] (rows sorted by group) x rhs [G, K, N] by ``group_sizes`` [G]
    int32 -> [m, N] in ``lhs``'s dtype, accumulated in float32. ``tiling``:
    (tm, tn), each dividing its dimension (``gmm_tiling`` gives one)."""
    tm, tn = tiling
    if lhs.shape[0] % tm or rhs.shape[2] % tn:
        raise ValueError(f"tiling {tiling} does not divide {lhs.shape} x {rhs.shape}")
    if interpret is None:
        interpret = interpret_default()
    return _gmm(lhs, rhs, group_sizes.astype(jnp.int32), tm, tn, bank_blocks(lhs.shape[0]),
                interpret)
