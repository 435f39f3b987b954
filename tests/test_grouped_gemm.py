"""``ops/pallas/grouped_gemm.py`` through the Pallas interpreter on the CPU: the
kernel against ``lax.ragged_dot`` over groups laid out every way the routed
feed-forward lays them, the visits it plans, the tiles its rule picks, and
``moe_ffn_dropless`` with the kernel and without at the rehearsal widths of the
four routed configurations. No other routed test runs the kernel: on the CPU
``dropless.expert_gemm_form`` keeps ``ragged_dot``."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from deepspeed_tpu.models import transformer as tfm
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.ops.pallas import grouped_gemm
from deepspeed_tpu.ops.pallas.grouped_gemm import gmm_tiling, grouped_matmul, visits

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "chipbench", "configs")

# name: (rows, group sizes). Tiles of 128 rows unless the case says otherwise.
LAYOUTS = {
    "a_boundary_inside_a_tile": (256, [100, 156]),
    "boundaries_on_the_tiles": (384, [128, 256]),
    "an_empty_group_between": (256, [70, 0, 0, 186]),
    "empty_groups_first_and_last": (256, [0, 200, 56, 0]),
    "a_group_of_several_tiles": (640, [30, 520, 90]),
    "many_groups_in_one_tile": (256, [5, 9, 1, 40, 73, 128]),
    "one_layer_of_four_filled": (384, [0] * 8 + [60, 0, 200, 124] + [0] * 4),
    "the_first_layer_of_three_filled": (256, [100, 156, 0, 0, 0, 0]),
    "rows_past_the_held_pairs": (512, [90, 0, 130, 41]),
    "rows_past_one_layer_of_two": (384, [0, 0, 0, 17, 200, 3]),
    "no_row_past_the_first_tile": (384, [100, 0]),
}


def _operands(rows, sizes, K=128, N=256, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    lhs = jax.random.normal(ks[0], (rows, K), jnp.bfloat16)
    rhs = jax.random.normal(ks[1], (len(sizes), K, N), jnp.bfloat16) * K ** -0.5
    return lhs, rhs, jnp.asarray(sizes, jnp.int32)


def _close(out, ref, live):
    """The rows that lie in a group agree to bfloat16 rounding (both accumulate
    in float32; the order differs); what lies past them the kernel leaves
    unwritten, and ``ragged_dot`` zero."""
    out, ref = (np.asarray(a[:live], np.float32) for a in (out, ref))
    np.testing.assert_allclose(out, ref, rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("name", sorted(LAYOUTS))
def test_kernel_agrees_with_ragged_dot(name):
    rows, sizes = LAYOUTS[name]
    lhs, rhs, group_sizes = _operands(rows, sizes)
    out = grouped_matmul(lhs, rhs, group_sizes, (128, 256), interpret=True)
    assert out.shape == (rows, 256) and out.dtype == jnp.bfloat16
    _close(out, lax.ragged_dot(lhs, rhs, group_sizes), sum(sizes))


@pytest.mark.parametrize("tm,tn", [(128, 256), (128, 128), (256, 256), (512, 128), (64, 256)])
def test_kernel_agrees_at_every_tile(tm, tn):
    """Every row tile the rule can return (128 today) and those the chip's table
    was taken at, with the columns whole and split (a split bank is fetched a
    column block at a time, once a sweep of the visits)."""
    sizes = [100, 0, 300, 28, 0, 468, 128, 0]
    lhs, rhs, group_sizes = _operands(1024, sizes, seed=1)
    out = grouped_matmul(lhs, rhs, group_sizes, (tm, tn), interpret=True)
    _close(out, lax.ragged_dot(lhs, rhs, group_sizes), 1024)


@pytest.mark.parametrize("name", sorted(LAYOUTS))
def test_visits_cover_every_group_once_a_tile(name):
    """A group visits exactly the tiles its rows lie in, in order; the visits
    are sorted by group and, within one, by tile (so a tile's visits are
    consecutive and its output block is written back once); an empty group has
    none; ``following`` and ``nth`` chain the groups that visit."""
    rows, sizes = LAYOUTS[name]
    tm = 128
    (offsets, group, tile, following, nth), count = visits(jnp.asarray(sizes, jnp.int32), rows, tm)
    count = int(count)
    assert group.shape == tile.shape == (rows // tm + len(sizes) - 1,) and count <= group.shape[0]
    ends = np.cumsum(sizes)
    want = [(g, t) for g, (lo, hi) in enumerate(zip(ends - sizes, ends)) if hi > lo
            for t in range(lo // tm, (hi - 1) // tm + 1)]
    assert list(zip(np.asarray(group)[:count], np.asarray(tile)[:count])) == want
    assert list(np.asarray(offsets)) == [0] + list(ends)
    filled = [g for g, n in enumerate(sizes) if n]
    assert [int(following[g]) for g in filled] == filled[1:] + [len(sizes)]
    assert [int(nth[g]) for g in filled] == list(range(len(filled)))


@pytest.mark.parametrize("m,K,N,want", [
    (16384, 2048, 1024, (128, 1024)), (16384, 1024, 2048, (128, 2048)),  # OLMoE
    (49152, 2048, 768, (128, 768)), (49152, 768, 2048, (128, 2048)),  # kanana
    (2560, 6144, 2048, (128, 1024)), (2560, 2048, 6144, (128, 3072)),  # K-EXAONE's held chunk
    (8192, 2048, 1536, (128, 1536)), (4096, 1536, 2048, (128, 2048)),  # lfm2
    (4096, 64, 32, (128, 32)),  # a rehearsal's widths: whole, whatever they are
    (1000, 2048, 1024, None),  # rows that are no whole number of tiles: not the kernel's
])
def test_the_rule_picks_the_tile_from_the_shapes(m, K, N, want):
    assert gmm_tiling(m, K, N) == want
    if want:
        tm, tn = want
        assert m % tm == 0 and N % tn == 0
        assert K * tn * 2 <= grouped_gemm.BANK_BLOCK_BYTES


@pytest.mark.parametrize("buffers", [2, 3, 4])
def test_kernel_agrees_at_every_number_of_bank_blocks(buffers, monkeypatch):
    """The kernel keeps the visiting group's block of the bank and those of the
    groups that visit next (``bank_blocks``: two, or three at few rows); with one visit a group, as
    a decode step's pairs lie (a row or two an expert, most experts of a layer,
    the other layers' groups empty), every visit waits for its own block and
    starts the one ``buffers - 1`` groups on, into the block of the group before."""
    monkeypatch.setattr(grouped_gemm, "bank_blocks", lambda m: buffers)
    rng = np.random.default_rng(buffers)
    sizes = [0] * 24 + [int(n) for n in rng.integers(0, 4, 24)]  # the second layer of two
    sizes[30] += 150 - sum(sizes)  # ... and one group that crosses the tile's edge
    lhs, rhs, group_sizes = _operands(256, sizes, seed=2)
    out = grouped_matmul(lhs, rhs, group_sizes, (128, 256), interpret=True)
    _close(out, lax.ragged_dot(lhs, rhs, group_sizes), 150)


@pytest.mark.parametrize("m,blocks", [(128, 3), (256, 3), (2048, 3), (2560, 2), (4096, 2),
                                      (16384, 2), (49152, 2)])
def test_three_blocks_of_the_bank_at_few_rows_alone(m, blocks):
    """A decode step's padded pairs (128 to 512 rows) keep three blocks; every
    call the prefill and chunk programs make (2,560 rows, K-EXAONE's held chunk,
    and up) keeps the two it was timed with."""
    assert grouped_gemm.bank_blocks(m) == blocks


def test_a_tiling_that_does_not_divide_is_refused():
    lhs, rhs, sizes = _operands(256, [100, 156])
    with pytest.raises(ValueError, match="does not divide"):
        grouped_matmul(lhs, rhs, sizes, (96, 256), interpret=True)
    with pytest.raises(ValueError, match="does not divide"):
        grouped_matmul(lhs, rhs, sizes, (128, 96), interpret=True)


def _rehearsal(config):
    with open(os.path.join(CONFIGS, config + ".json")) as f:
        program = json.load(f)["rehearse_program"]
    return tfm.TransformerConfig(dtype=jnp.bfloat16, **{**program, "max_seq_len": 1024})


@pytest.fixture
def kernel_on_cpu(monkeypatch):
    """``expert_gemm_form`` takes the kernel on the ``tpu`` platform alone, and
    the kernel compiles there; steer both, in the test, so that the routed block
    runs the kernel through the interpreter."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(grouped_gemm, "interpret_default", lambda: True)


@pytest.mark.parametrize("config", ["olmoe-1b-7b-L4", "kanana-2-30b-a3b-L7",
                                    "k-exaone-236b-a23b-L5", "lfm2-24b-a2b-L9"])
def test_routed_block_is_the_same_with_the_kernel_and_without(config, kernel_on_cpu, monkeypatch):
    """One routed layer of each configuration's rehearsal twin (bfloat16, 1,024
    rows: the sorted form; K-EXAONE's through ``experts_sorted_held``, whose last
    trip has rows past the held pairs), the banks handed over as the held stacks
    with the layer's index: the same output to bfloat16 rounding, the same
    experts chosen, and the form each was traced by."""
    cfg = _rehearsal(config)
    moe = tfm.hold_for_compute(cfg, tfm.Model(cfg).init(jax.random.PRNGKey(3)))["moe"]
    layer = jnp.int32(jax.tree.leaves(moe["experts"])[0].shape[0] - 1)
    moe_l = {name: (leaf if name == "experts" else jax.tree.map(lambda a: a[layer], leaf))
             for name, leaf in moe.items()}
    h = jax.random.normal(jax.random.PRNGKey(4), (1, 1024, cfg.hidden_size), jnp.bfloat16)
    assert dropless.expert_gemm_form(cfg, moe["experts"], 1024, True) == "gmm128"
    assert dropless.expert_gemm_form(cfg, moe["experts"], 1024, False) == "ragged_dot"
    assert dropless.expert_gemm_form(cfg, moe["experts"], 512, True) == "dense"
    with_kernel, _, chosen = dropless.moe_ffn_dropless(cfg, moe_l, h, layer)
    monkeypatch.undo()  # the CPU platform again: the compiler's ragged_dot
    assert dropless.expert_gemm_form(cfg, moe["experts"], 1024, True) == "ragged_dot"
    without, _, chosen_without = dropless.moe_ffn_dropless(cfg, moe_l, h, layer)
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(chosen_without))
    with_kernel, without = (np.asarray(a, np.float32) for a in (with_kernel, without))
    assert np.isfinite(with_kernel).all()
    np.testing.assert_allclose(with_kernel, without, rtol=2e-2,
                               atol=2e-2 * float(np.abs(without).max()))


# configuration: (its cell, what a decode step of the cell's slots takes on the chip in place)
DECODE_FORMS = {
    "olmoe-1b-7b-L4": ("serve-doc", "gmm128"),  # 16 rows x 8 of 64: 88% touched by an even router
    "kanana-2-30b-a3b-L7": ("serve-longdoc", "gmm128"),  # 24 x 6 of 128: 68%
    "mellum2-12b-a2.5b-L8": ("serve-repoctx", "dense"),  # 32 x 8 of 64: 99%
    "lfm2-24b-a2b-L9": ("serve-longgen", "dense"),  # 128 x 4 of 64: every expert
    "k-exaone-236b-a23b-L5": ("serve-mixedlen", "dense"),  # a held share: 16 of 128
    "qwen3-next-80b-a3b-L8": ("serve-longdoc", "dense"),  # a held share: 64 of 512
}


@pytest.mark.parametrize("config", sorted(DECODE_FORMS))
def test_the_rule_at_few_rows_is_from_rows_choices_and_the_bank(config, monkeypatch):
    """``expert_gemm_form`` at the six routed configurations' published widths and
    their cells' slots (a decode step's rows): the sorted form through the kernel
    where a whole bank is read in place on the ``tpu`` platform and the rows leave
    enough of it untouched; the dense form on the CPU, out of a scanned slice, for
    a held share at any few rows, and at 512 rows; over 512 what it was."""
    cell, on_chip = DECODE_FORMS[config]
    with open(os.path.join(CONFIGS, config + ".json")) as f:
        program = json.load(f)["program"]
    with open(os.path.join(os.path.dirname(CONFIGS), "workloads", f"{config}.{cell}.json")) as f:
        rows = json.load(f)["deployment"]["n_slots"]
    cfg = tfm.TransformerConfig(dtype=jnp.bfloat16, **{**program, "vocab_size": 1024})
    count = cfg.experts_held[1]
    bank = {"wi": jax.ShapeDtypeStruct((2, count, cfg.hidden_size, cfg.intermediate_size),
                                       jnp.bfloat16)}
    form = lambda rows, in_place: dropless.expert_gemm_form(cfg, bank, rows, in_place)
    assert {form(n, in_place) for n in (1, rows, 512) for in_place in (True, False)} == {"dense"}
    assert form(1024, True) == form(1024, False) == "ragged_dot"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert form(rows, True) == on_chip
    assert form(rows, False) == form(512, True) == form(512, False) == "dense"
    assert (form(1024, True), form(1024, False)) == ("gmm128", "ragged_dot")
    if count < cfg.num_experts:  # a held share: no few rows take the kernel
        assert {form(n, True) for n in (1, 2, 4, 8, 16, 64, 256)} == {"dense"}
    else:  # fewer rows touch fewer experts; enough rows touch them all
        assert form(4, True) == "gmm128" and form(256, True) == "dense"


@pytest.mark.parametrize("rows,top_k,experts,mb,ahead", [
    (24, 6, 128, 1208, True), (48, 6, 128, 1208, True), (64, 6, 128, 1208, False),  # kanana
    (16, 8, 64, 805, True), (32, 8, 64, 805, False),  # OLMoE
    (32, 8, 64, 793, False), (128, 4, 64, 1208, False),  # Mellum2, LFM2
    (16, 8, 256, 11274, True), (64, 8, 256, 11274, True),  # DeepSeek-V3's layer at a small batch
    (4, 2, 16, 1, False),  # a rehearsal's bank: the fixed work is all there is
])
def test_sorted_ahead_by_the_bytes_each_form_streams(rows, top_k, experts, mb, ahead):
    assert dropless.sorted_ahead(rows, top_k, experts, mb * 10 ** 6) == ahead


@pytest.mark.parametrize("config,rows", [("kanana-2-30b-a3b-L7", 24), ("olmoe-1b-7b-L4", 16)])
def test_decode_rows_through_the_kernel_equal_the_dense_form(config, rows, kernel_on_cpu,
                                                             monkeypatch):
    """A decode step's rows of the two rehearsal twins whose cells take the
    kernel at few rows (the rule steered to say so at the twins' widths, where the
    fixed work is all there is): the pairs padded to the row tile (96 and 64 ->
    128), the held stacks read in place, against ``experts_dense`` out of the
    same stacks: the same experts chosen, the same output to bfloat16 rounding."""
    cfg = _rehearsal(config)
    moe = tfm.hold_for_compute(cfg, tfm.Model(cfg).init(jax.random.PRNGKey(5)))["moe"]
    layer = jnp.int32(jax.tree.leaves(moe["experts"])[0].shape[0] - 1)
    moe_l = {name: (leaf if name == "experts" else jax.tree.map(lambda a: a[layer], leaf))
             for name, leaf in moe.items()}
    h = jax.random.normal(jax.random.PRNGKey(6), (rows, 1, cfg.hidden_size), jnp.bfloat16)
    assert dropless.expert_gemm_form(cfg, moe["experts"], rows, True) == "dense"
    monkeypatch.setattr(dropless, "sorted_ahead", lambda *a: True)
    assert dropless.expert_gemm_form(cfg, moe["experts"], rows, True) == "gmm128"
    assert dropless.expert_gemm_form(cfg, moe["experts"], rows, False) == "dense"
    sorted_, _, chosen = dropless.moe_ffn_dropless(cfg, moe_l, h, layer)
    monkeypatch.undo()
    assert dropless.expert_gemm_form(cfg, moe["experts"], rows, True) == "dense"
    dense, _, chosen_dense = dropless.moe_ffn_dropless(cfg, moe_l, h, layer)
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(chosen_dense))
    sorted_, dense = (np.asarray(a, np.float32) for a in (sorted_, dense))
    assert np.isfinite(sorted_).all()
    np.testing.assert_allclose(sorted_, dense, rtol=2e-2, atol=2e-2 * float(np.abs(dense).max()))
