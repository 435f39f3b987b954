"""Dropless top-k routing over a bank of gated experts (``moe_routing:
"dropless"``): the mixture-of-experts block of OLMoE / Mixtral-style models,
which are trained without capacity and so have no token to drop.

Beside the GShard path (``sharded_moe.py``: top-1 / top-2, a capacity, one-hot
``[T, E, C]`` dispatch and combine einsums, tokens over capacity dropped) this
one has no capacity and no ``[T, E, C]`` tensor at any skew:

* ``route``: router logits in float32; an expert's score the softmax over every
  expert or the sigmoid of its own logit (``moe_score_fn``); top-k for any k,
  taken of the scores or of the scores plus a held per-expert bias that selects
  and does not weigh (``moe_select_bias``: DeepSeek-V3's
  ``e_score_correction_bias``); the k weights renormalised or left as the raw
  scores (``moe_norm_topk_prob``, a model's ``norm_topk_prob``) and multiplied by
  ``moe_routed_scale``; the one router of training, prefill and decode, its
  forms data of the configuration.
* ``experts_sorted``: the token-expert pairs sorted by expert, one grouped
  matmul per projection over the contiguous groups, unsorted, and summed with
  the weights. The grouped matmul is ``jax.lax.ragged_dot`` (on a TPU the
  compiler's grouped-GEMM kernel, whose row tile is 512: a tile a group
  boundary falls in is multiplied once by each group in it, and the groups
  here are 64 to 384 rows in the mean) or, in a serving program on the chip,
  ``ops/pallas/grouped_gemm.py`` at a row tile of 128 with the next group's
  bank block fetched while this one's rows multiply (``expert_gemm_form``
  says which; PERF.md §6, PR 46 has the chip's timings of both at every
  cell's shapes: 1.28 -> 0.6 ms a projection at OLMoE's).
* ``experts_dense``: every expert on every row, each expert's weights read
  once; for the few rows of a decode step where they touch about every expert,
  so that the sorted form reads the same bytes and pays a sort, two gathers
  and near-empty tiles on top (PERF.md §6, PR 27 has the chip's timings of
  both, taken against the compiler's 512-row tile; PR 46's table has the
  sorted form's grouped matmuls alone at 256 and 512 rows a call under both
  kernels). Where a step's rows leave a share of a whole bank untouched (24
  rows x 6 choices of 128 experts touch two thirds), a serving program on the
  chip takes the sorted form through the kernel instead, the pairs padded to
  its row tile: an expert no row chose is no visit and its matrices are never
  read (``expert_gemm_form``, ``sorted_ahead``; PERF.md §6, PR 62).

* the shared expert (``moe_shared_size``): one more gated MLP that every row
  goes through, a plain matmul beside the routed sum and not a group of the
  grouped GEMM (its rows are all of them: nothing to sort); with
  ``moe_shared_gate`` its output is multiplied by sigmoid(x . w_gate), a row.

Experts are ``down(silu(gate(x)) * up(x))``, stacked ``[layers, E, ...]`` under
the ``expert`` logical axis so a mesh shards them like the GShard bank.

Where the bank is read from (PR 34). Both forms take the three banks either as
ONE layer's ``[E, K, N]`` leaves (training, evaluation, the pipeline stages: the
layer loop scans the stacks and hands over the slice, whose cotangent a backward
pass needs and which ``param_offload`` streams) or as the HELD stacks
``[L, E, K, N]`` with ``layer``, the routed layer's traced position in them (the
serving programs: ``transformer.expert_bank_form`` says when). In the second case
``experts_sorted`` views each stack as ``[L * E, K, N]`` (a reshape of a contiguous
array: nothing moves) and counts the pairs into groups ``layer * E + expert``, so
the grouped-GEMM kernel reads layer ``layer`` through its own group index and
every other layer's groups are empty (an empty group gets no tile): no
``[E, K, N]`` copy of the layer is made for the kernel's operand, which was a
fifth of a routed prefill (PERF.md §6, PR 34). ``experts_dense`` takes the layer
inside its einsums' operands, where XLA fuses the slice as it does a scanned one.

A held share (``moe_experts_held`` = (first, count); PR 38). The program of one
chip of a deployment that spreads a layer's experts over several holds ``count``
of the ``E`` experts: the banks are ``[L, count, ...]``, the router stays ``E``
wide and chooses its k of E, and only the pairs whose expert is held are
dispatched; the layer's output is the shared expert plus the held experts' part.
``experts_dense`` mixes the held experts' outputs alone. ``experts_sorted_held``
sorts the pairs with the held ones first, by expert, and takes THOSE in chunks
of ``held_chunk_rows`` under a loop whose trip count the data decides: about
k x count / E of the pairs are held, so the buffers are that share of what every
pair would take (at 16,384 rows x 8 choices of 128, 16 held: 20,480 gathered rows
where all pairs are 131,072, 1.6 GB in bfloat16), and a routing as uneven as it
likes still drops no pair: it takes more trips. Forward only: a loop whose trip
count is data has no reverse-mode derivative.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .experts import experts_logical_axes

# Rows (tokens of one call) up to which every expert is computed on every row
# instead of sorting the pairs: a decode or verify step, a short chunk. From
# the chip's timings of one layer's block at OLMoE's widths (PERF.md §6, PR 27;
# ms, dense / sorted): 3.0 / 5.7 at 16 rows, 3.0 / 7.3 at 256, 3.7 / 7.5 at 512,
# 6.3 / 8.1 at 1024, 16.0 / 8.9 at 2048 with the weights held in float32 as
# then, and 1.9 / 2.0, 2.0 / 3.6, 3.4 / 3.7, 6.1 / 4.3, 13.2 / 5.2 held in
# bfloat16: up to 512 the dense form won either way. Those sorted timings were
# of ``lax.ragged_dot``'s 512-row tile, the bank sliced out a layer at a time.
# Which form each row count takes today (``expert_gemm_form``): over 512 rows
# the sorted form, whose grouped matmuls are the Pallas kernel's in a serving
# program on the chip and ``ragged_dot``'s elsewhere; up to 512 the dense form,
# on any platform, EXCEPT where a serving program on the chip holds a whole
# bank of which the call's rows leave enough untouched (``sorted_ahead``, PR
# 62): there the sorted form through the kernel, which reads the banks the rows
# chose and no other. The 256- to 512-row end is still this constant's: under
# the kernel the three grouped matmuls of such a call are about half what they
# were (PERF.md §6, PR 46: the table's last four rows), so the sorted form may
# overtake the dense one there too, where every expert is touched and the
# question is the MXU's, not the bytes'; not timed as whole blocks (ROADMAP
# S2(i)), and ``chipbench/kinds_cost.py::SORTED_FORM_ROWS`` mirrors the constant.
DENSE_ROWS = 512

# The few-rows end, from the chip's timings of one layer's block whole (the
# route excluded) on held stacks at the four whole-bank decode shapes of the
# benchmark (PERF.md §6, PR 62; ms, dense / sorted through the kernel at a
# 128-row tile and three bank blocks, and the experts touched): kanana 24 rows x
# 6 of 128: 1.72 / 1.15, 87 touched; OLMoE 16 x 8 of 64: 1.27 / 1.01, 57;
# Mellum2 32 x 8 of 64: 1.17 / 1.08, 62; LFM2 128 x 4 of 64: 1.76 / 1.69, 64.
# At every shape the sorted block took what its touched banks take at 711 to
# 714 GB/s, the sort, the count, the gathers and the combine (0.03 ms at 144 to
# 256 pairs, 0.07 at 512) included; the dense form streams all of them at 637
# to 701 GB/s alone and about 5% faster inside a decode program (kanana's cell:
# 1.63 ms a layer). So the forms are compared by their bytes.
# ``SORTED_FIXED_BYTES``: what the sorted form's fixed work is worth in bytes
# streamed (0.045 ms: the 0.03 to 0.07 above and the first block's exposed wait
# of each of the three kernel calls). ``SORTED_AHEAD``: the sorted form is taken
# where its bytes are at most this share of the dense form's; where no bank is
# skipped the two are within the 4 to 8% that the block alone and the block in
# its program differ by, and the form stays the one every cell was timed in.
SORTED_FIXED_BYTES = 32 * 2 ** 20
SORTED_AHEAD = 0.95

# The standard deviation the selection bias is DRAWN with. The published model
# starts it at zero and moves it by the experts' load, never by the loss; at
# zero, choosing by ``score + bias`` is choosing by ``score`` and the mechanism
# is tested by nothing. Sigmoid scores of unit-variance logits lie about 0.011
# apart round the 6th of 128 (0.03 round the 2nd of 8), so a bias of this spread
# changes the chosen set for most tokens and leaves most of each set in place
# (tests/test_kanana.py counts both).
SELECT_BIAS_STD = 0.02

# Rows from which ``experts_dense`` hands its first two matmuls the rows once an
# EXPERT (a batched product over e) and not once for all of them. ``tm,emf->etf``
# leaves e a free dimension of the bank; XLA then either runs it as a convolution
# over e on the bank as it lies (what it does at a decode step's 16 to 32 rows: the
# timings above) or folds e into f, which wants the bank as [E, F, M]: compiled for
# the chip at 128 rows inside a serving program (a decode step of 128 slots, a
# 128-row prefill) it took the second and copied BOTH whole [L, E, M, F] stacks into
# that layout in every call (2 x 3 GB at LFM2's widths: neither program fit; on the
# chip, PR 42, and ``tests/test_chip_compile_caches.py``). With e a batch dimension each
# expert's [M, F] is read where it lies, whatever the rows. Every decode step the
# benchmark had before (16 to 32 rows) keeps the form it was timed in; of its
# prefill programs one takes this form (a held share's 512-row bucket).
BATCHED_ROWS = 64


def init_dropless(rng, n_layers: int, num_experts: int, d_model: int, d_ff: int,
                  shared: int = 0, select_bias: bool = False, held: int | None = None,
                  shared_gate: bool = False):
    """Router and gated expert bank of ``n_layers`` routed layers; every stack
    is drawn whole, in one call (no per-layer list to restack). ``shared``: the
    width of the shared expert (0: none); ``select_bias``: the router's held
    selection bias, drawn non-zero (``SELECT_BIAS_STD``); ``held``: how many of
    the ``num_experts`` the router chooses among this program holds a bank of
    (None: all); ``shared_gate``: the shared expert's gate vector ``w_gate``."""
    keys = jax.random.split(rng, 4)  # the gate and the bank draw as they always have
    keys = list(keys) + list(jax.random.split(jax.random.fold_in(rng, 1), 4))

    def draw(key, shape, fan_in):
        return jax.random.normal(key, shape, jnp.float32) * (1.0 / math.sqrt(fan_in))

    routed, bank = (n_layers, num_experts), (n_layers, held or num_experts)
    out = {
        "gate": draw(keys[0], (n_layers, d_model, num_experts), d_model),
        "experts": {
            "wg": draw(keys[1], bank + (d_model, d_ff), d_model),
            "wi": draw(keys[2], bank + (d_model, d_ff), d_model),
            "wo": draw(keys[3], bank + (d_ff, d_model), d_ff),
        },
    }
    if select_bias:
        out["bias"] = SELECT_BIAS_STD * jax.random.normal(keys[4], routed, jnp.float32)
    if shared:
        out["shared"] = {"wg": draw(keys[5], (n_layers, d_model, shared), d_model),
                         "wi": draw(keys[6], (n_layers, d_model, shared), d_model),
                         "wo": draw(keys[7], (n_layers, shared, d_model), shared)}
        if shared_gate:
            out["shared"]["w_gate"] = draw(jax.random.fold_in(rng, 2), (n_layers, d_model), d_model)
    return out


def dropless_logical_axes(shared: bool = False, select_bias: bool = False,
                          shared_gate: bool = False):
    ex = experts_logical_axes()
    ex["wg"] = ex["wi"]
    axes = {"gate": (None, "embed", None), "experts": {k: (None,) + v for k, v in ex.items()}}
    if select_bias:
        axes["bias"] = (None, None)
    if shared:
        axes["shared"] = {"wg": (None, "embed", "mlp"), "wi": (None, "embed", "mlp"),
                          "wo": (None, "mlp", "embed")}
        if shared_gate:
            axes["shared"]["w_gate"] = (None, "embed")
    return axes


def route(x, gate_w, top_k: int, renormalize: bool, *, score_fn: str = "softmax",
          select_bias=None, scale: float = 1.0):
    """x [T, M] -> (weights [T, k] float32, experts [T, k] int32, scores [T, E]
    float32). The logits are a float32 product at full precision whatever the
    compute dtype: the gap between the k-th and the (k+1)-th expert is a few
    hundredths of the logits' spread, and a bf16 product would move it.
    ``select_bias`` [E] is added to the scores for the choice alone: the weights
    are the chosen experts' own scores, renormalised (over their sum + 1e-20, as
    the published code has it) and scaled as asked."""
    logits = jnp.dot(x.astype(jnp.float32), gate_w.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits) if score_fn == "sigmoid" else jax.nn.softmax(logits, axis=-1)
    if select_bias is None:
        weights, experts = lax.top_k(scores, top_k)
    else:
        _, experts = lax.top_k(scores + select_bias.astype(jnp.float32), top_k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
    if renormalize:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    if scale != 1.0:
        weights = weights * scale
    return weights, experts.astype(jnp.int32), scores


def load_balance_loss(probs, experts):
    """E x sum_e(share of the token-expert pairs sent to e x mean router
    probability of e): 1 when both are uniform (the GShard / Switch term, with
    the share taken over all k choices)."""
    E = probs.shape[-1]
    share = jnp.mean(jax.nn.one_hot(experts, E, dtype=jnp.float32), axis=(0, 1))
    return E * jnp.sum(share * jnp.mean(probs, axis=0))


def _gated(gate, up):
    return jax.nn.silu(gate) * up


def sorted_ahead(rows: int, top_k: int, experts: int, bank_bytes: int) -> bool:
    """Whether the sorted form through the kernel is ahead of the dense one at
    ``rows`` rows of a decode or verify step (up to ``DENSE_ROWS``), by what each
    must stream: the dense form every expert's three matrices (``bank_bytes``, a
    layer's), the sorted form those of the experts an even router's ``rows`` x
    ``top_k`` choices touch, u = 1 - (1 - k / E) ** rows of them (a trained or a
    seeded router is more uneven and touches fewer), and ``SORTED_FIXED_BYTES``
    on top."""
    touched = 1.0 - (1.0 - top_k / experts) ** rows
    return touched * bank_bytes + SORTED_FIXED_BYTES <= SORTED_AHEAD * bank_bytes


def expert_gemm_form(cfg, bank, rows: int, in_place: bool) -> str:
    """What multiplies a call of ``rows`` tokens through the routed experts:
    ``"dense"`` (``experts_dense``), ``"ragged_dot"`` (the sorted forms over the
    compiler's grouped GEMM) or ``"gmm<tm>"`` (the sorted forms over
    ``ops/pallas/grouped_gemm.py`` at a row tile of ``tm``). The kernel is taken
    where the code can see it may: on the ``tpu`` platform (on the CPU every
    routed test would pay the Pallas interpreter), by a program that only runs
    forward (``in_place``: the held stacks, which ``transformer.expert_bank_form``
    grants to serving programs alone; the kernel has no backward pass), at
    shapes it tiles (``gmm_tiling``, from the rows of a call and the bank's
    widths). Over ``DENSE_ROWS`` rows the form is a sorted one. Up to them it is
    the dense one, but for a whole bank that may go through the kernel and whose
    rows leave enough of its experts untouched (``sorted_ahead``, from the rows,
    ``moe_top_k`` and the bank's shape): the kernel reads no matrix of an
    expert no row chose, the dense form reads them all. A held share keeps the
    dense form at few rows (its sorted form is a loop of trips).
    ``moe_ffn_dropless`` traces by this and ``SlotWorker`` labels its spans by
    it (``expert_gemm``)."""
    few = rows <= DENSE_ROWS
    if in_place and jax.default_backend() == "tpu":
        from ..ops.pallas.grouped_gemm import gmm_tiling, whole_tiles

        count, M, F = bank["wi"].shape[-3:]
        whole = count == cfg.num_experts
        m = rows * cfg.moe_top_k
        if not whole:
            m = held_chunk_rows(m, count, cfg.num_experts)
        elif few:  # ``experts_sorted`` pads a step's pairs
            m = whole_tiles(m)
        tiles = gmm_tiling(m, M, F), gmm_tiling(m, F, M)
        bank_bytes = 3 * count * M * F * jnp.dtype(bank["wi"].dtype).itemsize
        if all(tiles) and (not few or (whole and sorted_ahead(rows, cfg.moe_top_k, count,
                                                              bank_bytes))):
            return f"gmm{tiles[0][0]}"
    return "dense" if few else "ragged_dot"


def _grouped_dot(xs, w, sizes, kernel: bool):
    """xs [m, K], sorted by group, x w [G, K, N] by ``sizes`` [G]."""
    if not kernel:
        return lax.ragged_dot(xs, w, sizes)
    from ..ops.pallas.grouped_gemm import gmm_tiling, grouped_matmul

    return grouped_matmul(xs, w, sizes, gmm_tiling(xs.shape[0], *w.shape[1:]))


def experts_sorted(bank, x, weights, experts, layer=None, kernel: bool = False):
    """x [T, M] through the chosen experts: pairs sorted by expert, grouped
    matmuls over the groups as they are (no capacity, no padding to one).
    ``bank``: one layer's ``[E, K, N]`` leaves or, with ``layer`` (a traced
    index), the held stacks ``[L, E, K, N]``, read in place as ``L * E`` groups
    of which only layer ``layer``'s hold a pair. ``kernel``: the grouped matmuls
    through the Pallas kernel (``expert_gemm_form``)."""
    T, M = x.shape
    k = experts.shape[1]
    flat = experts.reshape(T * k)
    order = jnp.argsort(flat)  # pairs by expert; pair p is token p // k
    if layer is not None:  # [L, E, K, N] as L * E groups: layer ``layer``'s alone are filled
        flat = layer * bank["wi"].shape[1] + flat
        bank = {name: leaf.reshape((-1,) + leaf.shape[2:]) for name, leaf in bank.items()}
    sizes = jnp.zeros((bank["wi"].shape[0],), jnp.int32).at[flat].add(1)
    pad = 0
    if kernel:  # whole row tiles (a decode step's pairs: 144 -> 256)
        from ..ops.pallas.grouped_gemm import whole_tiles

        pad = whole_tiles(T * k) - T * k
    # the rows past the pairs are no group's: no visit stores them, no token gathers them
    xs = x[(jnp.concatenate([order, jnp.zeros((pad,), order.dtype)]) if pad else order) // k]
    w = {name: leaf.astype(x.dtype) for name, leaf in bank.items()}
    dot = partial(_grouped_dot, sizes=sizes, kernel=kernel)
    h = _gated(dot(xs, w["wg"]), dot(xs, w["wi"]))
    ys = dot(h, w["wo"])  # [T * k, M], still sorted
    ys = ys[jnp.argsort(order)].reshape(T, k, M)  # pair p's row: none past the pairs
    return jnp.einsum("tkm,tk->tm", ys.astype(jnp.float32), weights).astype(x.dtype)


def held_chunk_rows(pairs: int, count: int, num_experts: int) -> int:
    """The gathered rows one trip of ``experts_sorted_held`` takes: a quarter over
    the held share of ``pairs`` (an even router fills one trip), a multiple of
    256, never more than the pairs there are."""
    want = -(-5 * pairs * count // (4 * num_experts))
    return min(-(-max(want, 1) // 256) * 256, pairs)


def experts_sorted_held(bank, x, weights, experts, first: int, num_experts: int, layer=None,
                        kernel: bool = False):
    """``experts_sorted`` for a bank that holds experts [first, first + count) of
    the ``num_experts`` the router chose among: x [T, M], weights and experts
    [T, k] over ALL the router's experts -> the held experts' part of the routed
    sum [T, M]. The pairs are sorted held first, by expert; ceil(held pairs / C)
    trips each take C sorted rows (``held_chunk_rows``) through the grouped
    matmuls, and every token gathers its own pairs' rows back out of the trip's
    output, one choice at a time, weighted. No pair is dropped, and none that is
    not held is gathered or multiplied. ``layer`` and ``kernel``: as
    ``experts_sorted`` takes them (the kernel leaves a trip's rows past the held
    pairs unwritten: no token gathers them)."""
    T, M = x.shape
    k = experts.shape[1]
    count = bank["wi"].shape[0 if layer is None else 1]
    if layer is not None:  # [L, count, K, N] as L * count groups: this layer's alone are filled
        bank = {name: leaf.reshape((-1,) + leaf.shape[2:]) for name, leaf in bank.items()}
    w = {name: leaf.astype(x.dtype) for name, leaf in bank.items()}
    flat = experts.reshape(T * k) - first
    key = jnp.where((flat >= 0) & (flat < count), flat, count)  # the others behind the held
    order = jnp.argsort(key)  # pairs by held expert; pair p is token p // k
    place = jnp.argsort(order).reshape(T, k)  # where each token's pairs lie in that order
    ends = jnp.cumsum(jnp.zeros((count + 1,), jnp.int32).at[key].add(1)[:count])
    begins = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends[:-1]])
    n_held = ends[-1]
    C = held_chunk_rows(T * k, count, num_experts)
    weights = weights.astype(jnp.float32)

    def trip(carry):
        lo, out = carry
        rows = jnp.minimum(lo + jnp.arange(C), T * k - 1)
        xs = x[order[rows] // k]
        sizes = jnp.clip(ends, lo, lo + C) - jnp.clip(begins, lo, lo + C)
        if layer is not None:
            sizes = lax.dynamic_update_slice(jnp.zeros((w["wi"].shape[0],), jnp.int32), sizes,
                                             (layer * count,))
        dot = partial(_grouped_dot, sizes=sizes, kernel=kernel)
        h = _gated(dot(xs, w["wg"]), dot(xs, w["wi"]))
        ys = dot(h, w["wo"])  # [C, M], sorted; rows past the held: no group's

        def choice(j, out):
            at = place[:, j]
            mine = (at >= lo) & (at < jnp.minimum(lo + C, n_held))
            got = ys[jnp.clip(at - lo, 0, C - 1)].astype(jnp.float32)
            return out + jnp.where(mine[:, None], got * weights[:, j, None], 0.0)

        return lo + C, lax.fori_loop(0, k, choice, out)

    _, out = lax.while_loop(lambda carry: carry[0] < n_held, trip,
                            (jnp.int32(0), jnp.zeros((T, M), jnp.float32)))
    return out.astype(x.dtype)


def experts_dense(bank, x, weights, experts, layer=None, first: int = 0):
    """Every expert of the bank on every row of x [T, M]; the weights of those
    not chosen are zero. ``bank`` and ``layer`` as ``experts_sorted`` takes them;
    ``first``: the bank holds the experts from this one on (a choice outside it
    is mixed in by no one here). From ``BATCHED_ROWS`` rows the first two matmuls
    take the rows once an expert."""
    T = x.shape[0]
    if layer is not None:
        bank = {name: lax.dynamic_index_in_dim(leaf, layer, 0, keepdims=False)
                for name, leaf in bank.items()}
    E = bank["wi"].shape[0]
    w = {name: leaf.astype(x.dtype) for name, leaf in bank.items()}
    if T >= BATCHED_ROWS:  # e a batch dimension: no layout of the bank but its own
        xe = jnp.broadcast_to(x[None], (E,) + x.shape)
        h = _gated(jnp.einsum("etm,emf->etf", xe, w["wg"]), jnp.einsum("etm,emf->etf", xe, w["wi"]))
    else:
        h = _gated(jnp.einsum("tm,emf->etf", x, w["wg"]), jnp.einsum("tm,emf->etf", x, w["wi"]))
    ys = jnp.einsum("etf,efm->etm", h, w["wo"])
    at = experts - first
    at = jnp.where((at >= 0) & (at < E), at, E)  # not held: dropped (a negative index would wrap)
    mix = jnp.zeros((T, E), jnp.float32).at[jnp.arange(T)[:, None], at].set(weights, mode="drop")
    return jnp.einsum("etm,te->tm", ys.astype(jnp.float32), mix).astype(x.dtype)


def shared_expert(w, x):
    """The gated MLP every row of x [T, M] goes through; with a gate vector
    (``w_gate`` [M]: ``moe_shared_gate``) its output times sigmoid(x . w_gate), a row."""
    w = {name: leaf.astype(x.dtype) for name, leaf in w.items()}
    out = _gated(x @ w["wg"], x @ w["wi"]) @ w["wo"]
    if "w_gate" not in w:
        return out
    logit = jnp.einsum("tm,m->t", x, w["w_gate"], preferred_element_type=jnp.float32)
    return out * jax.nn.sigmoid(logit)[:, None].astype(x.dtype)


def moe_ffn_dropless(cfg, moe_p, h, layer=None):
    """h [B, S, M] -> (out [B, S, M], load-balancing loss, experts [B, S, k]).
    ``moe_p``: one routed layer's leaves; with ``layer`` its ``experts`` are the
    held stacks of every routed layer and ``layer`` this one's position in them."""
    B, S, M = h.shape
    x = h.reshape(B * S, M)
    weights, experts, probs = route(
        x, moe_p["gate"], cfg.moe_top_k, cfg.moe_norm_topk_prob, score_fn=cfg.moe_score_fn,
        select_bias=moe_p.get("bias"), scale=cfg.moe_routed_scale)
    first, count = cfg.experts_held
    form = expert_gemm_form(cfg, moe_p["experts"], B * S, layer is not None)
    kernel = form.startswith("gmm")
    if form == "dense":
        out = experts_dense(moe_p["experts"], x, weights, experts, layer, first)
    elif count < cfg.num_experts:  # one chip's share of the experts: the held pairs alone
        out = experts_sorted_held(moe_p["experts"], x, weights, experts, first, cfg.num_experts,
                                  layer, kernel)
    else:
        out = experts_sorted(moe_p["experts"], x, weights, experts, layer, kernel)
    if "shared" in moe_p:
        out = out + shared_expert(moe_p["shared"], x)
    return (out.reshape(B, S, M), load_balance_loss(probs, experts),
            experts.reshape(B, S, cfg.moe_top_k))


def expert_load(experts, live, num_experts: int, held=None):
    """experts [layers, B, T, k], live [B, T] bool -> int32 [layers, E]: the
    rows that count (not bucket padding, not an idle slot) sent to each expert.
    ``held`` (first, count): the load of the experts this program holds alone,
    [layers, count] (a choice outside them is another chip's row)."""
    first, count = held or (0, num_experts)
    hot = jax.nn.one_hot(experts - first, count, dtype=jnp.int32)  # [layers, B, T, k, count]
    return jnp.sum(hot * live[None, :, :, None, None].astype(jnp.int32), axis=(1, 2, 3))


def load_summary(load) -> dict:
    """Host side, load [layers, E] as fetched: how uneven the routing of one
    call was (the worst layer's busiest expert over its mean expert) and how
    many experts a layer touched on average."""
    load = np.asarray(load, np.float64)
    mean = np.maximum(load.mean(axis=1), 1e-9)
    return {"expert_load_max_over_mean": float(np.max(load.max(axis=1) / mean)),
            "experts_touched": float(np.mean(np.count_nonzero(load, axis=1)))}
