"""The dense and the routed families' serving programs compiled for a described TPU v5e
(``tests/chip_compile_cases.py``): the decode program at BLOOM's, Pythia's and OLMoE's
widths, cut to four layers and a small vocabulary (the guard that the slot K/V cache
stays one buffer through the layer loop), Falcon-H1's recurrent state beside it, the
2048-row prefill that attends through the flash kernel and holds no score matrix, the
sampler's sort behind its conditional, and the routed feed-forward block alone at the
routed cells' widths. Tier 1.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from chip_compile_cases import (  # noqa: F401 -- the fixtures are used by name
    GMM_CALL, RAGGED_DOT, _bare_slot_worker, _compile_decode, _compile_prefill, _computations,
    _operations_writing, _reach, _walk_built_once, v5e, no_persistent_cache, as_tpu)


_FAMILIES = {
    "bloom_dense_alibi": dict(pos_emb="alibi", embed_ln=True, activation="gelu"),
    "pythia_pallas_kernel": dict(pos_emb="rotary", rotary_pct=0.25, parallel_residual=True,
                                 tie_embeddings=False, activation="gelu_exact"),
    "olmoe_dropless": dict(pos_emb="rotary", norm_kind="rms", qk_norm=True, use_bias=False,
                           tie_embeddings=False, activation="swiglu", moe_routing="dropless",
                           moe_every=1, num_experts=64, moe_top_k=8, intermediate_size=1024),
}


def _family_cfg(family, L, Smax, H=16, Dh=128):
    """A benchmark configuration's block at its published widths, ``L`` layers
    and a vocabulary of 1024."""
    from deepspeed_tpu.models.transformer import TransformerConfig

    fields = dict(intermediate_size=4 * H * Dh, use_bias=True)
    fields.update(_FAMILIES[family])
    return TransformerConfig(vocab_size=1024, max_seq_len=Smax, num_layers=L, num_heads=H,
                             hidden_size=H * Dh, dtype=jnp.bfloat16, **fields)


# ---------------------------------------------------------------------------
# the decode program keeps the slot cache in place (tier 1)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", ["bloom_dense_alibi", "pythia_pallas_kernel", "olmoe_dropless"])
def test_decode_program_keeps_the_slot_cache_in_place(family, v5e, no_persistent_cache, as_tpu):
    """``SlotWorker``'s decode step at the widths of the benchmark's two dense
    configurations and of its first routed one, the other cell whose steps run the
    Pallas decode kernel (4 layers, 8 slots x 512, vocabulary 1024): the stacked
    cache is the layer loop's carry, donated in and aliased out. Put it back
    into the scan's xs/ys and the compiler slices a layer out and restacks it
    in every iteration and copies the whole cache twice to reconcile the
    buffers (both assertions then fail, as they do on the code before PR 25)."""
    L, n, Smax, H, Dh = 4, 8, 512, 16, 128
    cfg = _family_cfg(family, L, Smax, H, Dh)
    worker, params, cache, sds = _bare_slot_worker(cfg, n, Smax, SingleDeviceSharding(v5e[0]))
    compiled = _compile_decode(worker, params, cache, n, sds)

    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == (family != "bloom_dense_alibi")
    if family != "bloom_dense_alibi":
        # OLMoE's 8 rows x 8 choices of 64 leave a third of a layer's experts untouched: its
        # step's routed layers take the grouped-matmul kernel (PR 62), three calls a layer
        _walk_built_once(worker, params, cache, n, sds, loops=1,
                         grouped=3 if family == "olmoe_dropless" else 0)
    whole = re.escape(f"bf16[{L},{n},{Smax},{H},{Dh}]")
    copies = re.findall(rf"^\s*%?[\w.-]+ = {whole}\S* copy\(", text, re.M)
    assert not copies, f"the decode step copies the whole slot cache: {copies}"
    ma = compiled.memory_analysis()
    layer_bytes = n * Smax * H * Dh * 2  # one layer of K (or of V)
    assert ma.alias_size_in_bytes >= 2 * L * layer_bytes  # K and V: donated in, aliased out
    # nothing cache-sized beside the cache: not a copy of the stack, not one
    # layer sliced out of it (for the Pallas kernel, which takes the stack)
    assert ma.temp_size_in_bytes < layer_bytes, (ma.temp_size_in_bytes, layer_bytes)


def _falcon_h1_cfg(L, Smax):
    """Falcon-H1-34B's block at its published widths (a Mamba-2 mixer of 32 heads x
    128 with a state of 256 in 2 groups beside 20 / 4 grouped heads of 128, a gated
    MLP of 21504), ``L`` layers and a vocabulary of 1024."""
    from deepspeed_tpu.models.transformer import TransformerConfig

    return TransformerConfig(
        vocab_size=1024, max_seq_len=Smax, num_layers=L, num_heads=20, num_kv_heads=4,
        qk_head_dim=128, hidden_size=5120, intermediate_size=21504, pos_emb="rotary",
        rotary_base=1e11, tie_embeddings=False, use_bias=False, norm_kind="rms",
        activation="swiglu", decode_attn="xla", ssm_state_size=256, ssm_heads=32,
        ssm_head_dim=128, ssm_groups=2, ssm_conv_kernel=4, ssm_chunk_size=128,
        multipliers={"key_multiplier": 0.011, "ssm_multipliers": [0.35, 0.25, 0.18, 0.5, 0.35],
                     "mlp_multipliers": [0.18, 0.011]}, dtype=jnp.bfloat16)


def test_decode_program_keeps_the_recurrent_state_in_place(v5e, no_persistent_cache, as_tpu):
    """``SlotWorker``'s decode step at Falcon-H1-34B's widths (4 layers, 16 slots x
    512): the float32 state stack [L, slots, 32, 128, 256] (268 MB here, 1.07 GB
    in the cell) rides in the layer loop's carry with K/V, donated in and aliased
    out; layer l of it is read, advanced by the one-step recurrence and written
    back where it lies. The compiled program holds no second copy of the stack
    and nothing of one layer's size beside it. K/V: the 4 K/V heads of 128 (not the
    20 query heads) side by side as one row of 512 (``cache_heads_merged``: grouped
    heads), which the step contracts in place (``_rows_attention``): NO operation
    of the program yields one layer's K or V (as [L, slots, Smax, 4, 128] the
    grouped form had the compiler slice each layer's out as an operation of its
    own, 2 x 134 MB a layer a step in the cell: PR 45), and the temporaries are
    under a quarter of one layer's K."""
    L, n, Smax = 4, 16, 512
    cfg = _falcon_h1_cfg(L, Smax)
    worker, params, cache, sds = _bare_slot_worker(cfg, n, Smax, SingleDeviceSharding(v5e[0]))
    assert cache["k"].shape == cache["v"].shape == (L, n, Smax, 1, 4 * 128)
    compiled = _compile_decode(worker, params, cache, n, sds)
    text = compiled.as_text()
    assert "tpu_custom_call" not in text  # decode_attn "xla": grouped heads
    whole = re.escape(f"f32[{L},{n},32,128,256]")
    copies = re.findall(rf"^\s*%?[\w.-]+ = {whole}\S* copy\(", text, re.M)
    assert not copies, f"the decode step copies the whole state stack: {copies}"
    layer = _operations_writing(text, n * Smax * 4 * 128)
    assert not layer, f"the decode step slices or copies one layer's K/V: {layer}"
    ma = compiled.memory_analysis()
    layer_state = n * 32 * 128 * 256 * 4
    layer_bytes = n * Smax * 4 * 128 * 2  # one layer's K (or V)
    assert ma.alias_size_in_bytes >= L * layer_state + 2 * L * layer_bytes  # donated, aliased
    assert ma.temp_size_in_bytes < layer_bytes // 4, (ma.temp_size_in_bytes, layer_bytes)


def test_prefill_scan_forms_no_pairs_by_state_temporary(v5e, no_persistent_cache, as_tpu):
    """The 1024-row prefill at Falcon-H1-34B's widths (4 layers): the chunked scan
    keeps its pairs as [chunks, heads, 128, 128] and its states as [chunks, heads,
    128, 256]; the form that multiplies them out (``transformers``' fallback:
    [chunks, 128, 128, heads, 256] float32 = 4.3 GB at 1024 rows) would not fit
    beside the model, and a tenth of it would fail here. B and C stay at their 2
    groups: nothing [rows, 32 heads, 256] is made of them."""
    L, n, Smax, rows = 4, 8, 2048, 1024
    cfg = _falcon_h1_cfg(L, Smax)
    worker, params, cache, sds = _bare_slot_worker(cfg, n, Smax, SingleDeviceSharding(v5e[0]))
    compiled = _compile_prefill(worker, params, cache, rows, sds)
    multiplied_out = (rows // 128) * 128 * 128 * 32 * 256 * 4
    assert compiled.memory_analysis().temp_size_in_bytes < multiplied_out // 10
    assert not re.findall(rf"(?:f32|bf16)\[1,{rows},32,256\]", compiled.as_text())


@pytest.mark.parametrize("family,L", [("bloom_dense_alibi", 24), ("pythia_pallas_kernel", 24),
                                      ("olmoe_dropless", 4)])
def test_serving_programs_cast_no_stacked_weight(family, L, v5e, no_persistent_cache, as_tpu):
    """``SlotWorker``'s decode step and its 2048-row prefill at the widths and
    depths of the benchmark's three configurations (8 slots x 2048, vocabulary
    1024), on operands typed as ``InferenceEngine`` holds them
    (``hold_for_compute``): no ``convert`` yields a whole stacked weight; the
    decode step's temporaries are under the bf16 bytes of the largest stacked
    leaf, and the prefill's (its local K/V; no score matrix since PR 30) under
    a third of those of all the stacks together. OLMoE's prefill reads layer l
    of each expert bank IN PLACE, out of the held ``[L, 64, ...]`` stack through
    the grouped-GEMM kernel's group index (PR 34: ``expert_bank_form``,
    ``moe/dropless.py``): it defines no value of one layer's bank, and its
    temporaries are its activations alone (315 MB: the local K/V, 67 MB, and the
    16,384 pairs' gathered rows, gate, up and down products at 67 MB each),
    held under 1.25 x ONE layer of ONE bank (268 MB), where the slice copied out
    for the kernel's operand made them 633 MB; its decode step takes the layer
    inside the dense form's GEMM fusions, as the scanned slice was taken. On ``model.init``'s
    float32 operands, which the engine held before PR 28, all of it fails: every
    program casts every stack (twelve converts for BLOOM, the three expert
    banks for OLMoE) and carries the bf16 copies as temporaries on top of the
    rest (2.42 GB of the decode step's 2.42 and of the prefill's 3.79 for
    BLOOM, 3.26 GB of 3.26 and of 3.85 for OLMoE-L4)."""
    n, Smax = 8, 2048
    cfg = _family_cfg(family, L, Smax)
    worker, params, cache, sds = _bare_slot_worker(cfg, n, Smax, SingleDeviceSharding(v5e[0]))
    stacks = [x.shape for x in jax.tree.leaves(params)  # the matrices, stacked [L, ...]
              if x.ndim >= 3 and x.shape[0] == L and x.dtype == jnp.bfloat16]
    nbytes = [int(np.prod(shape)) * 2 for shape in stacks]
    shapes = "|".join(re.escape(",".join(map(str, shape))) for shape in sorted(set(stacks)))
    routed = family == "olmoe_dropless"
    bank_layer = 64 * 2048 * 1024 * 2  # one layer of one expert bank, bf16
    for name, compiled, bound in (
            ("decode", _compile_decode(worker, params, cache, n, sds), max(nbytes)),
            ("prefill", _compile_prefill(worker, params, cache, Smax, sds),
             bank_layer * 5 // 4 if routed else sum(nbytes) // 3)):
        text = compiled.as_text()
        casts = re.findall(rf"^\s*%?[\w.-]+ = \w+\[(?:{shapes})\]\S* convert\(", text, re.M)
        assert not casts, f"the {name} program casts a whole stacked weight: {casts}"
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < bound, (name, temp, bound)
        if routed and name == "prefill":
            sliced = re.findall(r"^\s*%?[\w.-]+ = bf16\[64,(?:2048,1024|1024,2048)\]", text, re.M)
            assert not sliced, f"the prefill copies a layer of an expert bank out: {sliced}"
            # under ``as_tpu`` the three grouped matmuls are the Pallas kernel's (PR 46)
            assert len(re.findall(GMM_CALL + r" = bf16\[16384,", text, re.M)) == 3
            assert not re.findall(RAGGED_DOT, text, re.M)


@pytest.mark.parametrize("family,L", [("bloom_dense_alibi", 24), ("pythia_pallas_kernel", 24),
                                      ("olmoe_dropless", 4)])
def test_long_prefill_attends_through_the_flash_kernel(family, L, v5e, no_persistent_cache, as_tpu,
                                                       monkeypatch):
    """The 2048-row prefill of each benchmark configuration (16 heads of 128,
    8 slots x 2048, vocabulary 1024): its block fills its local cache and its
    dense scores would be 256 MiB, so attention is ONE ``flash_fwd`` kernel
    call in the layer loop, no ``[16, 2048, 2048]`` float32 value exists, and
    the temporaries are under the dense form's (the same program with the
    constant steered out of reach) by the score matrix where that was their
    peak (OLMoE's is the sliced-out expert banks, before and after). The
    1024-row bucket, whose 64 MiB of scores XLA keeps in VMEM, has no kernel."""
    from deepspeed_tpu.models import transformer as tfm

    n, Smax = 8, 2048
    cfg = _family_cfg(family, L, Smax)
    worker, params, cache, sds = _bare_slot_worker(cfg, n, Smax, SingleDeviceSharding(v5e[0]))
    scores = rf"f32\[(?:1,)?{cfg.num_heads},{Smax},{Smax}\]"
    flash = _compile_prefill(worker, params, cache, Smax, sds)
    text = flash.as_text()
    assert len(re.findall(r'^\s*%?flash_fwd[\w.]* = .*custom_call_target="tpu_custom_call"',
                          text, re.M)) == 1
    assert not re.search(scores, text)
    assert "flash_fwd" not in _compile_prefill(worker, params, cache, Smax // 2, sds).as_text()

    monkeypatch.setattr(tfm, "DENSE_SCORE_BYTES", 2 ** 40)
    dense = _compile_prefill(worker, params, cache, Smax, sds)
    assert "flash_fwd" not in dense.as_text() and re.search(scores, dense.as_text())
    saved = dense.memory_analysis().temp_size_in_bytes - flash.memory_analysis().temp_size_in_bytes
    assert saved > (0 if family == "olmoe_dropless" else 4 * cfg.num_heads * Smax * Smax), saved


@pytest.mark.parametrize("family", ["bloom_dense_alibi", "pythia_pallas_kernel"])
def test_sampler_sort_stays_behind_the_conditional(family, v5e, no_persistent_cache, as_tpu):
    """``SlotWorker``'s decode step and a prefill at the widths of the two
    ``gpt_family`` configurations (4 layers, 8 slots x 512, vocabulary 1024),
    compiled for the chip: the sampler is ONE three-branch ``conditional`` of
    the entry computation, the ``[rows, vocab]`` sort is reached only through
    its branches, and what runs whatever the operands say (the entry
    computation and all it calls outside those branches) holds no such sort.
    A compiler that flattened the conditional into a select would put the sort
    back into every all-greedy step, and this would say so before a chip call."""
    L, n, Smax, V = 4, 8, 512, 1024
    cfg = _family_cfg(family, L, Smax)
    worker, params, cache, sds = _bare_slot_worker(cfg, n, Smax, SingleDeviceSharding(v5e[0]))
    for name, compiled, rows in (("decode", _compile_decode(worker, params, cache, n, sds), n),
                                 ("prefill", _compile_prefill(worker, params, cache, Smax, sds), 1)):
        comps, entry = _computations(compiled.as_text())
        conds = [line for line in comps[entry][0] if " conditional(" in line]
        assert len(conds) == 1 and len(comps[entry][2]) == 3, (name, conds)
        sort = re.compile(rf"= \(?f32\[{rows},{V}\][^=]* sort\(")
        sorts_in = lambda cs: [line.split(" = ")[0].strip() for c in cs for line in comps[c][0]
                               if sort.search(line)]
        always = _reach(comps, [entry], through_branches=False)
        assert not sorts_in(always), (name, sorts_in(always))
        behind = _reach(comps, comps[entry][2], through_branches=True)
        assert sorts_in(behind), name


@pytest.mark.parametrize("rows,grouped", [(16, False), (2048, True)], ids=["decode", "prefill"])
def test_dropless_expert_block_at_olmoe_widths(rows, grouped, v5e, no_persistent_cache):
    """One routed layer of OLMoE (64 gated experts of 2048 x 1024, top-8, bf16 compute, the
    bank in bf16 and the router in float32 as ``InferenceEngine`` holds them): a 2048-row
    prefill goes through the compiler's grouped-GEMM kernel (``ragged_dot``: three calls and
    their group metadata) and holds nothing of the size of a GShard ``[T, E, C]`` dispatch
    tensor (2048 x 64 x 320 floats = 168 MB) nor a copy of the bank (805 MB, what the cast of
    a float32 bank took before PR 28); a 16-row decode step computes every expert densely,
    with no kernel call."""
    from deepspeed_tpu.models.transformer import TransformerConfig
    from deepspeed_tpu.moe.dropless import moe_ffn_dropless

    cfg = TransformerConfig(hidden_size=2048, intermediate_size=1024, num_experts=64, moe_top_k=8,
                            moe_routing="dropless", activation="swiglu", moe_every=1,
                            dtype=jnp.bfloat16)
    one_chip = SingleDeviceSharding(v5e[0])
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    bank = {"wg": (64, 2048, 1024), "wi": (64, 2048, 1024), "wo": (64, 1024, 2048)}
    moe_p = {"gate": sds((2048, 64), jnp.float32),
             "experts": {k: sds(shape, jnp.bfloat16) for k, shape in bank.items()}}
    compiled = jax.jit(lambda p, h: moe_ffn_dropless(cfg, p, h)).lower(
        moe_p, sds((1, rows, 2048), jnp.bfloat16)).compile()
    text = compiled.as_text()
    assert (text.count('custom_call_target="tpu_custom_call"') >= 3) == grouped
    assert not re.findall(r"= \w+\[64,(?:2048,1024|1024,2048)\]\S* convert\(", text)
    pairs = rows * 8 * 2048 * 2  # the gathered rows of every token-expert pair, bf16
    assert compiled.memory_analysis().temp_size_in_bytes < 6 * pairs + 2 ** 26


def test_dropless_expert_block_reads_the_held_stacks_in_place(v5e, no_persistent_cache):
    """The routed block of four OLMoE layers in one scan, 2048 rows, the three banks
    handed over as the held ``[4, 64, ...]`` stacks with the layer's index
    (``moe_ffn_dropless(..., layer)``): the grouped-GEMM kernel is still called three times
    an iteration, on the stack itself viewed as 256 groups; the loop body defines no value
    of one layer's bank (the scanned slice makes three: the slice copied out for the
    kernel's operand, ``dynamic-slice_bitcast_fusion`` ``bf16[64,2048,1024]``, 3.27 ms each
    on the chip) and its temporaries are the block's activations (the gathered rows of
    the 16,384 pairs, 67 MB), a third of one layer's bank at most and a bank (268 MB)
    less than the scanned form's."""
    from deepspeed_tpu.models.transformer import TransformerConfig
    from deepspeed_tpu.moe.dropless import moe_ffn_dropless

    L, rows = 4, 2048
    cfg = TransformerConfig(hidden_size=2048, intermediate_size=1024, num_experts=64, moe_top_k=8,
                            moe_routing="dropless", activation="swiglu", moe_every=1,
                            dtype=jnp.bfloat16)
    one_chip = SingleDeviceSharding(v5e[0])
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    bank = {"wg": (L, 64, 2048, 1024), "wi": (L, 64, 2048, 1024), "wo": (L, 64, 1024, 2048)}
    moe = {"gate": sds((L, 2048, 64), jnp.float32),
           "experts": {k: sds(shape, jnp.bfloat16) for k, shape in bank.items()}}

    def in_place(moe, h):
        def body(h, xs):
            gate, l = xs
            moe_l = {"gate": gate, "experts": moe["experts"]}
            return h + moe_ffn_dropless(cfg, moe_l, h, l)[0], None
        return jax.lax.scan(body, h, (moe["gate"], jnp.arange(L, dtype=jnp.int32)))[0]

    def scanned(moe, h):
        return jax.lax.scan(lambda h, moe_l: (h + moe_ffn_dropless(cfg, moe_l, h)[0], None),
                            h, moe)[0]

    h = sds((1, rows, 2048), jnp.bfloat16)
    bank_layer = 64 * 2048 * 1024 * 2
    layer_of_a_bank = r"^\s*%?[\w.-]+ = bf16\[64,(?:2048,1024|1024,2048)\]"
    temps = {}
    for fn, slices in ((in_place, 0), (scanned, 3)):
        compiled = jax.jit(fn).lower(moe, h).compile()
        text = compiled.as_text()
        assert len(re.findall(RAGGED_DOT + r"bf16\[16384,", text, re.M)) == 3
        assert len(re.findall(layer_of_a_bank, text, re.M)) == slices, fn.__name__
        temps[fn.__name__] = compiled.memory_analysis().temp_size_in_bytes
    assert temps["in_place"] < bank_layer // 3, temps
    assert temps["scanned"] - temps["in_place"] > 0.9 * bank_layer, temps


@pytest.mark.parametrize("cell,widths,rows,tile", [
    ("olmoe", dict(hidden_size=2048, intermediate_size=1024, num_experts=64, moe_top_k=8), 2048,
     (16384, "gmm128")),
    ("kanana", dict(hidden_size=2048, intermediate_size=768, num_experts=128, moe_top_k=6,
                    moe_score_fn="sigmoid", moe_shared_size=1536), 8192, (49152, "gmm128")),
    ("k-exaone", dict(hidden_size=6144, intermediate_size=2048, num_experts=128, moe_top_k=8,
                      moe_score_fn="sigmoid", moe_shared_size=2048, moe_experts_held=(0, 16)),
     2048, (2560, "gmm128")),
    ("lfm2", dict(hidden_size=2048, intermediate_size=1536, num_experts=64, moe_top_k=4,
                  moe_score_fn="sigmoid"), 1024, (4096, "gmm128")),
    # a decode step's rows (PR 62): the pairs padded to whole row tiles (144 -> 256, 128 -> 128)
    ("kanana-decode", dict(hidden_size=2048, intermediate_size=768, num_experts=128, moe_top_k=6,
                           moe_score_fn="sigmoid", moe_shared_size=1536), 24, (256, "gmm128")),
    ("olmoe-decode", dict(hidden_size=2048, intermediate_size=1024, num_experts=64, moe_top_k=8),
     16, (128, "gmm128")),
], ids=lambda v: v if isinstance(v, str) else None)
def test_dropless_expert_block_takes_the_grouped_matmul_kernel(cell, widths, rows, tile, v5e,
                                                               no_persistent_cache, as_tpu):
    """The routed block of the four routed cells (their widths, experts and
    choices; two layers of held stacks; a prefill bucket of each, and of the two
    whose decode step leaves banks untouched the step's rows, PR 62) in one scan,
    under ``as_tpu``: the sorted forms go through ``ops/pallas/grouped_gemm.py`` at
    the tile the rule picks (PR 46), which the chip's compiler takes (its VMEM,
    its alignment: what the interpreter cannot show). The loop body holds three
    kernel calls over the pairs' rows (K-EXAONE's inside its loop of trips, over
    the held chunk's), no ``ragged-dot`` of the compiler's, and no value of one
    layer's bank."""
    from deepspeed_tpu.models.transformer import TransformerConfig
    from deepspeed_tpu.moe.dropless import expert_gemm_form, moe_ffn_dropless

    L = 2
    cfg = TransformerConfig(moe_routing="dropless", activation="swiglu", moe_every=1,
                            dtype=jnp.bfloat16, **widths)
    M, F, E, count = cfg.hidden_size, cfg.intermediate_size, cfg.num_experts, cfg.experts_held[1]
    one_chip = SingleDeviceSharding(v5e[0])
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    moe = {"gate": sds((L, M, E), jnp.float32),
           "experts": {"wg": sds((L, count, M, F), jnp.bfloat16),
                       "wi": sds((L, count, M, F), jnp.bfloat16),
                       "wo": sds((L, count, F, M), jnp.bfloat16)}}
    if cfg.moe_shared_size:
        S = cfg.moe_shared_size
        moe["shared"] = {"wg": sds((L, M, S), jnp.bfloat16), "wi": sds((L, M, S), jnp.bfloat16),
                         "wo": sds((L, S, M), jnp.bfloat16)}
    pairs, form = tile
    assert expert_gemm_form(cfg, moe["experts"], rows, True) == form
    assert expert_gemm_form(cfg, moe["experts"], rows, False) == ("ragged_dot" if rows > 512
                                                                  else "dense")

    def in_place(moe, h):
        def body(h, xs):
            scanned, l = xs
            return h + moe_ffn_dropless(cfg, {**scanned, "experts": moe["experts"]}, h, l)[0], None
        scanned = {name: leaf for name, leaf in moe.items() if name != "experts"}
        return jax.lax.scan(body, h, (scanned, jnp.arange(L, dtype=jnp.int32)))[0]

    text = jax.jit(in_place).lower(moe, sds((1, rows, M), jnp.bfloat16)).compile().as_text()
    calls = re.findall(GMM_CALL + r" = bf16\[(\d+),(\d+)\]", text, re.M)
    assert sorted(calls) == sorted([(str(pairs), str(F))] * 2 + [(str(pairs), str(M))]), calls
    assert not re.findall(RAGGED_DOT, text, re.M)
    assert not re.findall(rf"^\s*%?[\w.-]+ = bf16\[{count},(?:{M},{F}|{F},{M})\]", text, re.M)


# ---------------------------------------------------------------------------
# the block step keeps the slot cache in place (tier 1)
# ---------------------------------------------------------------------------

def test_block_step_keeps_the_slot_cache_as_the_layer_loops_carry(v5e, no_persistent_cache, as_tpu):
    """``SlotWorker``'s block step (generation by diffusion over blocks) at SDAR-30B-A3B's
    widths (32 / 4 grouped heads of 128, 128 experts of 768 top-8; 4 layers, 8 slots x 512,
    vocabulary 1024): the stacked cache of merged rows [L, n, Smax, 1, 512] is the layer
    loop's carry, donated in and aliased out; the block's 4 x 32 query rows contract the
    rows where they lie (``_rows_attention``), so no layer is sliced out of the stack and
    nothing cache-sized stands beside it (at the cell's size a copy is 2.8 GB)."""
    from deepspeed_tpu.models.transformer import TransformerConfig

    L, n, Smax, B = 4, 8, 512, 4
    cfg = TransformerConfig(
        vocab_size=1024, max_seq_len=Smax, num_layers=L, num_heads=32, num_kv_heads=4,
        qk_head_dim=128, hidden_size=2048, intermediate_size=768, pos_emb="rotary",
        rotary_base=1e6, tie_embeddings=False, use_bias=False, norm_kind="rms",
        activation="swiglu", decode_attn="xla", qk_norm="head", moe_every=1,
        moe_routing="dropless", moe_norm_topk_prob=True, moe_aux_coeff=0.0, num_experts=128,
        moe_top_k=8, attn_block_length=B, mask_token_id=1023, dtype=jnp.bfloat16)
    worker, params, cache, sds = _bare_slot_worker(cfg, n, Smax, SingleDeviceSharding(v5e[0]))
    worker.block_len, worker.n_slots = B, n
    vec, blk = (lambda d: sds((n,), d)), (lambda d: sds((n, B), d))
    compiled = worker._build_block_step().lower(
        params, cache, blk(jnp.int32), blk(jnp.bool_), vec(jnp.bool_), blk(jnp.int32),
        blk(jnp.bool_), vec(jnp.int32), vec(jnp.int32), vec(jnp.bool_), vec(jnp.int32),
        vec(jnp.float32), sds((2,), jnp.uint32), vec(jnp.float32), vec(jnp.int32),
        vec(jnp.float32)).compile()
    text = compiled.as_text()
    assert cache["k"].shape == (L, n, Smax, 1, 512)  # a position's K/V heads as one row
    whole = r"bf16\[" + rf"{L},{n},{Smax},(?:1,)?512" + r"\]"
    copies = re.findall(rf"^\s*%?[\w.-]+ = {whole}\S* copy\(", text, re.M)
    assert not copies, f"the block step copies the whole slot cache: {copies}"
    ma = compiled.memory_analysis()
    layer_bytes = n * Smax * 512 * 2  # one layer of K (or of V)
    assert ma.alias_size_in_bytes >= 2 * L * layer_bytes  # K and V: donated in, aliased out
    # the block's float32 scores [n, 32 x 4, Smax] and the sampler's rows, not a layer of K/V
    # sliced out beside the stack for each of K and V
    assert ma.temp_size_in_bytes < 2 * layer_bytes, (ma.temp_size_in_bytes, layer_bytes)
