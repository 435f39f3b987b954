"""The serving programs of the slot caches that carry more than K/V, compiled for a
described TPU v5e (``tests/chip_compile_cases.py``) at each cell's published widths: the
latent cache (kanana), two cache kinds and chunks into rings (K-EXAONE, Mellum2), the
operators' conv state (LFM2), the delta rule's matrix state (Qwen3-Next) and K/V per
pass of a looped block (Ouro). Each holds its cache in place and fits the chip. Tier 1.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import chip_smoke

from chip_compile_cases import (  # noqa: F401 -- the fixtures are used by name
    HBM_BYTES, _bare_slot_worker, _compile_decode, _compile_prefill, _footprint,
    _operations_writing, _walk_built_once, _whole_copies, v5e, no_persistent_cache, as_tpu)


def _kanana_worker(L, n, Smax, v5e):
    """kanana-2-30b-a3b's block at its published widths (``chipbench/configs/
    kanana-2-30b-a3b-L7.json``'s ``program``), ``L`` layers (the leading dense one
    and ``L - 1`` routed), 16 of its 128 experts and a vocabulary of 1024."""
    import json
    from deepspeed_tpu.models import transformer as tfm

    with open(os.path.join(os.path.dirname(chip_smoke.__file__), "chipbench", "configs",
                           "kanana-2-30b-a3b-L7.json")) as f:
        program = json.load(f)["program"]
    cfg = tfm.TransformerConfig(dtype=jnp.bfloat16, **{
        **program, "num_layers": L, "num_experts": 16, "vocab_size": 1024, "max_seq_len": Smax})
    return (cfg, *_bare_slot_worker(cfg, n, Smax, SingleDeviceSharding(v5e[0])))


def test_latent_decode_program_keeps_the_latent_cache_in_place(v5e, no_persistent_cache, as_tpu):
    """``SlotWorker``'s decode step of the latent-attention block (the cell's 7 layers,
    the cell's 24 slots x 8192): the cache tree is the 512-wide latent and the 64-wide
    rotary key, the layer loop's carry, donated in and aliased out, never copied
    whole; the step attends in the absorbed form, so nothing per-head is made
    of the cache: no value has the cache's rows beside the 32 heads' 128 / 192 /
    256 widths (a step that expanded ``c W_kv_b`` over the cached tokens would
    hold ``[24, 8192, 32, 256]``), and the temporaries are the step's float32
    scores, about one layer's latent in all."""
    L, n, Smax = 7, 24, 8192
    cfg, worker, params, cache, sds = _kanana_worker(L, n, Smax, v5e)
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (L, n, Smax, 1, 64), "v": (L, n, Smax, 1, 512)}
    compiled = _compile_decode(worker, params, cache, n, sds)
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    for width in (64, 512):
        whole = rf"bf16\[{L},{n},{Smax},(?:1,)?{width}\]"
        copies = re.findall(rf"^\s*%?[\w.-]+ = {whole}\S* copy\(", text, re.M)
        assert not copies, f"the decode step copies the whole latent cache: {copies}"
    expanded = re.findall(rf"\w+\[(?:1,)?{n},{Smax},{cfg.num_heads},(?:128|192|256|320)\]", text)
    assert not expanded, f"the decode step expands the cached latent to heads: {expanded[:3]}"
    ma = compiled.memory_analysis()
    layer_bytes = n * Smax * (512 + 64) * 2
    assert ma.alias_size_in_bytes >= L * layer_bytes
    # score-sized float32 temporaries (24 x 32 x 8192), not a copy of the stack nor of a layer
    assert not re.findall(rf"= bf16\[(?:1,)?{n},{Smax},(?:1,)?512\]\S* (?:copy|transpose)\(", text)
    assert ma.temp_size_in_bytes < 1.25 * layer_bytes, (ma.temp_size_in_bytes, layer_bytes)


def _k_exaone_worker(n, Smax, v5e):
    """K-EXAONE-236B-A23B's five layers S S S G S at their published widths
    (``chipbench/configs/k-exaone-236b-a23b-L5.json``'s ``program``: 64 / 8 heads of
    128, a window of 128, 16 of 128 experts held) and a vocabulary of 1024."""
    import json
    from deepspeed_tpu.models import transformer as tfm

    with open(os.path.join(os.path.dirname(chip_smoke.__file__), "chipbench", "configs",
                           "k-exaone-236b-a23b-L5.json")) as f:
        program = json.load(f)["program"]
    cfg = tfm.TransformerConfig(dtype=jnp.bfloat16, **{
        **program, "vocab_size": 1024, "max_seq_len": Smax})
    return (cfg, *_bare_slot_worker(cfg, n, Smax, SingleDeviceSharding(v5e[0])))


def test_kinds_decode_program_keeps_both_cache_kinds_in_place(v5e, no_persistent_cache, as_tpu):
    """``SlotWorker``'s decode step of the block with window and whole-context
    layers (the cell's five layers, 16 slots x 8192): the cache tree is ONE
    whole-context layer ``Smax`` long and four rings of 128, both the layer
    loop's carry, donated in and aliased out, neither stack copied whole; a
    window layer attends over its ring, so exactly ONE matmul of the program
    yields a value ``Smax`` long (the whole-context layer's QK^T; were a window
    layer to attend over ``Smax`` under a mask there would be five). The
    whole-context layer keeps its heads (a model with rings: ``cache_heads_merged``)
    and its stack is ONE layer, which the compiler reads in place, the head-major
    re-layout inside the contraction's own fusion: the only operations that yield
    an array of a layer's K or V are the two in-place writes of the new row, no
    slice, copy or transpose. The temporaries (1.9 x one layer's K here, 0.528 GB at
    the cell's 32 x 16,384 where a layer's K is 1.07 GB) are four per-layer copies
    of the ``wq`` slices (PERF.md §7) and the float32 scores, nothing of K/V."""
    n, Smax = 16, 8192
    cfg, worker, params, cache, sds = _k_exaone_worker(n, Smax, v5e)
    assert jax.tree.map(lambda x: x.shape, cache) == {
        "k": (1, n, Smax, 8, 128), "v": (1, n, Smax, 8, 128),
        "ring": {"k": (4, n, 128, 8, 128), "v": (4, n, 128, 8, 128)}}
    compiled = _compile_decode(worker, params, cache, n, sds)
    text = compiled.as_text()
    assert "tpu_custom_call" not in text  # decode_attn "xla": grouped heads, the ring
    for whole in (rf"bf16\[1,{n},{Smax},8,128\]", rf"bf16\[4,{n},128,8,128\]"):
        copies = re.findall(rf"^\s*%?[\w.-]+ = {whole}\S* copy\(", text, re.M)
        assert not copies, f"the decode step copies a whole cache stack: {copies}"
    long_matmuls = [line for line in text.splitlines()
                    if re.search(r" (?:convolution|dot)\(", line)
                    and re.search(rf"^\s*(?:ROOT )?%?[\w.-]+ = \w+\[[\d,]*\b{Smax}\b", line)]
    assert len(long_matmuls) == 1, long_matmuls
    layer = _operations_writing(text, n * Smax * 8 * 128)
    assert len(layer) == 2 and all(" fusion(%bitcast" in op and "kind=kCustom" in op
                                   for op in layer), layer  # the new row into K, into V
    ma = compiled.memory_analysis()
    layer_bytes = n * Smax * 8 * 128 * 2  # the whole-context layer's K (or V)
    ring_bytes = 4 * n * 128 * 8 * 128 * 2
    assert ma.alias_size_in_bytes >= 2 * (layer_bytes + ring_bytes)  # donated in, aliased out
    wq_copies = 4 * 6144 * 64 * 128 * 2
    assert ma.temp_size_in_bytes < wq_copies + layer_bytes // 2, (ma.temp_size_in_bytes, layer_bytes)


def test_kinds_prefill_takes_the_banded_forward_in_its_window_layers(v5e, no_persistent_cache,
                                                                    as_tpu):
    """The cell's longest prefill (16,384 rows, the five layers S S S G S at 64
    heads of 128, window 128) compiled for the chip: the four window layers go
    through the banded forward (``flash_fwd_band``: a query block of 256 rows
    gets the 3 key blocks of 128 its band reaches as operands of one step, eight
    heads a step, in the VMEM the compiler allows), the whole-context layer
    through the whole causal grid (``flash_fwd``: q, k, v and no window), nothing
    rows x rows is made, and the program fits beside nothing else of the cell."""
    n, Smax = 4, 16384
    cfg, worker, params, cache, sds = _k_exaone_worker(n, Smax, v5e)
    compiled = _compile_prefill(worker, params, cache, Smax, sds)
    text = compiled.as_text()
    calls = re.findall(r'^\s*%?(flash_fwd[a-z_]*)[\d.]* = .*?custom-call\((.*?)\), '
                       r'custom_call_target="tpu_custom_call"', text, re.M)
    operands = {}
    for name, ops in calls:
        operands.setdefault(name, []).append(len(ops.split(", ")))
    assert operands == {"flash_fwd_band": [7] * 4, "flash_fwd": [3]}, operands
    assert not re.search(rf"f32\[(?:1,)?{cfg.num_heads},{Smax},{Smax}\]", text)
    assert _footprint(compiled) < HBM_BYTES


def _mellum_worker(n, Smax, v5e):
    """Mellum2-12B-A2.5B's eight layers S S S G S S S G at their published widths
    (``chipbench/configs/mellum2-12b-a2.5b-L8.json``'s ``program``: 32 / 4 heads of 128, a
    window of 1,024, a rotary per layer kind, 64 experts top-8, the WHOLE vocabulary:
    the head's logits are part of what a chunk makes)."""
    import json
    from deepspeed_tpu.models import transformer as tfm

    with open(os.path.join(os.path.dirname(chip_smoke.__file__), "chipbench", "configs",
                           "mellum2-12b-a2.5b-L8.json")) as f:
        program = json.load(f)["program"]
    cfg = tfm.TransformerConfig(dtype=jnp.bfloat16, **{**program, "max_seq_len": Smax})
    return (cfg, *_bare_slot_worker(cfg, n, Smax, SingleDeviceSharding(v5e[0])))


def _compile_chunk(worker, params, cache, width, sds):
    one = lambda dtype: sds((1,), dtype)
    return worker._build_chunk(width).lower(
        params, cache, sds((1, width), jnp.int32), sds((), jnp.int32), sds((), jnp.int32),
        sds((), jnp.int32), sds((2,), jnp.uint32), one(jnp.float32), one(jnp.int32),
        one(jnp.float32)).compile()


@pytest.mark.parametrize("width", [2048, 256])
def test_chunk_into_rings_attends_through_kernels_and_fits(width, v5e, no_persistent_cache,
                                                           as_tpu):
    """The cell's chunk programs (a whole chunk of 2,048 rows; the shortest tail, 256)
    compiled for the chip at the cell's own size, 32 slots x 32,768 beside 7.6 GB of
    weights: the two whole-context layers walk the slot's cache a key block at a time
    (``_blocks_attention``: nothing [rows, Smax] of scores is made, 8.6 GB a layer if it
    were; a step's are [rows, 512]), the six window layers attend over [ring ; chunk]
    through the banded flash forward (its run is window + width rows), and the program
    fits the chip beside the cache it is handed."""
    n, Smax = 32, 32768
    cfg, worker, params, cache, sds = _mellum_worker(n, Smax, v5e)
    assert jax.tree.map(lambda x: x.shape, cache) == {  # rows beside rings of heads
        "k": (2, n, Smax, 1, 512), "v": (2, n, Smax, 1, 512),
        "ring": {"k": (6, n, 1024, 4, 128), "v": (6, n, 1024, 4, 128)}}
    compiled = _compile_chunk(worker, params, cache, width, sds)
    text = compiled.as_text()
    calls = re.findall(r'^\s*%?(flash_fwd[a-z_]*)[\d.]* = .*?custom-call\(', text, re.M)
    assert sorted(set(calls)) == ["flash_fwd_band"], calls
    assert not re.search(rf"f32\[(?:1,)?{cfg.num_heads},{width},{Smax}\]", text)
    assert re.search(rf"f32\[1,4,8,{width},512\]", text)  # the walk's scores, a key block's
    assert _footprint(compiled) < HBM_BYTES, _footprint(compiled) / 1e9
    ma = compiled.memory_analysis()
    print({"width": width, "temp_gb": ma.temp_size_in_bytes / 1e9,
           "argument_gb": ma.argument_size_in_bytes / 1e9, "alias_gb": ma.alias_size_in_bytes / 1e9,
           "footprint_gb": _footprint(compiled) / 1e9})


def test_mellum_decode_program_fits_beside_32_slots_of_32768(v5e, no_persistent_cache, as_tpu):
    """The cell's decode step (32 rows over two whole-context layers 32,768 long and six
    rings of 1,024, ``decode_attn: xla``) compiled for the chip: both cache kinds are
    donated in and aliased out, neither stack is copied whole, the step fits, and the
    two whole-context layers are contracted where they lie in their stack of ROWS
    (``cache_heads_merged``): as heads, [2, 32, 32768, 4, 128], each layer's K and V left
    the stack as a 268 MB copy every step (1.07 GB of temporaries, 12.2 of a 31.5 ms
    step on the chip, PERF.md section 6 PR 59); the temporaries left are a layer's
    float32 scores [32, 32, 32768] and their exponentials."""
    n, Smax = 32, 32768
    cfg, worker, params, cache, sds = _mellum_worker(n, Smax, v5e)
    compiled = _compile_decode(worker, params, cache, n, sds)
    text = compiled.as_text()
    for whole in (rf"bf16\[2,{n},{Smax},1,512\]", rf"bf16\[6,{n},1024,4,128\]"):
        copies = re.findall(rf"^\s*%?[\w.-]+ = {whole}\S* copy\(", text, re.M)
        assert not copies, f"the decode step copies a whole cache stack: {copies}"
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < 2 * n * Smax * 512 * 2, ma.temp_size_in_bytes / 1e9  # < a layer's K
    cache_bytes = 2 * (2 * n * Smax + 6 * n * 1024) * 4 * 128 * 2
    assert ma.alias_size_in_bytes >= cache_bytes
    assert _footprint(compiled) < HBM_BYTES, _footprint(compiled) / 1e9
    print({"decode_temp_gb": ma.temp_size_in_bytes / 1e9,
           "footprint_gb": _footprint(compiled) / 1e9})


def _lfm2_worker(n, Smax, v5e):
    """LFM2-24B-A2B's nine layers C A C C C A C C C at their published widths
    (``chipbench/configs/lfm2-24b-a2b-L9.json``'s ``program``: a gated short
    convolution of 3 taps in seven layers, 32 / 8 heads of 64 in two, 64 experts of
    1536 top-4) and a vocabulary of 1024."""
    import json
    from deepspeed_tpu.models import transformer as tfm

    with open(os.path.join(os.path.dirname(chip_smoke.__file__), "chipbench", "configs",
                           "lfm2-24b-a2b-L9.json")) as f:
        program = json.load(f)["program"]
    cfg = tfm.TransformerConfig(dtype=jnp.bfloat16, **{
        **program, "vocab_size": 1024, "max_seq_len": Smax})
    return (cfg, *_bare_slot_worker(cfg, n, Smax, SingleDeviceSharding(v5e[0])))


def test_operators_decode_program_keeps_kv_and_conv_state_in_place(v5e, no_persistent_cache,
                                                                   as_tpu):
    """``SlotWorker``'s decode step of the block with attention and short-convolution
    layers at the cell's own size (nine layers, 128 slots x 3,072): the cache tree
    is K/V of the TWO attention layers (a token's 8 heads of 64 side by side as one
    row of 512: ``cache_heads_merged``; as [..., 8, 64] the program copied the WHOLE
    cache into a lane-padded form at entry and back at exit, 2 x 1.5 GB of
    temporaries) and two rows of state a sequence of the SEVEN conv layers, both the
    layer loop's carry, donated in and aliased out,
    neither stack copied whole; a conv layer attends to nothing, so exactly TWO
    matmuls of the program yield a value ``Smax`` long (the attention layers'
    QK^T: one in the period's scanned body, which runs twice, would read as one;
    here the lead is inline and the two periods are one scan, so one line). The
    step contracts each attention layer's rows where they lie
    (``_rows_attention``): NO operation of the program yields one layer's K or V,
    as [1, 128, 3072, 512] or viewed as [..., 8, 64] (the grouped form had the
    compiler slice each out of its stack and copy it head-major: four operations of
    403 MB, 9.8 ms of the cell's 26.7 ms step, PR 45), and the temporaries are the
    float32 scores and the step's own activations: 0.02 GB, under a quarter of one
    layer's K, where they were 0.85 GB."""
    n, Smax = 128, 3072
    cfg, worker, params, cache, sds = _lfm2_worker(n, Smax, v5e)
    assert jax.tree.map(lambda x: x.shape, cache) == {
        "k": (2, n, Smax, 1, 512), "v": (2, n, Smax, 1, 512), "state": {"conv": (7, n, 2, 2048)}}
    assert jax.tree.map(lambda x: x.shape, params["layers"]["conv"]) == {
        "conv_in": (7, 2048, 6144), "conv_w": (7, 3, 2048), "conv_out": (7, 2048, 2048)}
    assert params["layers"]["attn"]["wq"].shape == (2, 2048, 32, 64)
    compiled = _compile_decode(worker, params, cache, n, sds)
    text = compiled.as_text()
    assert "tpu_custom_call" not in text  # decode_attn "xla": grouped heads
    # (the 7.3 MB state stack is donated and aliased like K/V; the compiler moves it whole
    # into its faster memory for the loop and back, 9 us of bandwidth: not held to this)
    for whole in (rf"bf16\[2,{n},{Smax},(?:1,512|8,64)\]", r"bf16\[[67],2048,6144\]",
                  r"bf16\[[67],2048,2048\]", r"bf16\[8,64,2048,1536\]"):
        copies = re.findall(rf"^\s*%?[\w.-]+ = {whole}\S* copy\(", text, re.M)
        assert not copies, f"the decode step copies a whole stack: {copies}"
    long_matmuls = [line for line in text.splitlines()
                    if re.search(r" (?:convolution|dot)\(", line)
                    and re.search(rf"^\s*(?:ROOT )?%?[\w.-]+ = \w+\[[\d,]*\b{Smax}\b", line)]
    assert len(long_matmuls) == 1, long_matmuls  # the scanned period's ONE attention layer
    layer = _operations_writing(text, n * Smax * 8 * 64)
    assert not layer, f"the decode step slices or copies one layer's K/V: {layer}"
    ma = compiled.memory_analysis()
    layer_bytes = n * Smax * 8 * 64 * 2  # one attention layer's K (or V)
    state_bytes = 7 * n * 2 * 2048 * 2
    assert ma.alias_size_in_bytes >= 4 * layer_bytes + state_bytes  # donated in, aliased out
    assert ma.temp_size_in_bytes < layer_bytes // 4, (ma.temp_size_in_bytes, layer_bytes)


def _qwen3_next_worker(n, Smax, v5e):
    """Qwen3-Next-80B-A3B's eight layers D D D A D D D A at their published widths and
    the cell's share (``chipbench/configs/qwen3-next-80b-a3b-L8.json``'s ``program``: a
    gated delta rule of 16 key / 32 value heads of 128 and 4 taps in six layers, gated
    attention at 16 / 2 heads of 256 in two, a 512-wide router top-10 with 64 experts of
    512 held, the sliced vocabulary)."""
    import json
    from deepspeed_tpu.models import transformer as tfm

    with open(os.path.join(os.path.dirname(chip_smoke.__file__), "chipbench", "configs",
                           "qwen3-next-80b-a3b-L8.json")) as f:
        program = json.load(f)["program"]
    cfg = tfm.TransformerConfig(dtype=jnp.bfloat16, **{**program, "max_seq_len": Smax})
    return (cfg, *_bare_slot_worker(cfg, n, Smax, SingleDeviceSharding(v5e[0])))


_DELTA_STACKS = (r"bf16\[6,2048,12288\]", r"bf16\[6,4096,2048\]", r"bf16\[8,64,2048,512\]",
                 r"bf16\[8,64,512,2048\]", r"bf16\[2,2048,16,512\]")  # delta_in / _out, the banks, wq


def test_delta_decode_program_keeps_the_matrix_state_in_place(v5e, no_persistent_cache, as_tpu):
    """``SlotWorker``'s decode step of the block with attention and gated-delta-rule
    layers at the cell's own size (eight layers, 64 slots x 8,192): the cache tree is
    K/V of the TWO attention layers (a token's 2 heads of 256 side by side as one row
    of 512), and of the SIX delta layers a float32 matrix [32, 128, 128] a slot (0.81
    GB) and three rows of the filter's input, all the layer loop's carry, donated in and
    aliased out. No stack is copied whole (the state, K/V, the delta layers' two large
    projections, the expert banks, ``wq``); a layer's state is read where it lies (a
    dynamic slice INSIDE the fusions that take its two products and its update) and
    written back by an in-place update; the temporaries are the step's own
    activations and the attention layers' float32 scores (74 MB: under two thirds of
    ONE layer's state, 134 MB), and the program's footprint is what it is handed."""
    n, Smax = 64, 8192
    cfg, worker, params, cache, sds = _qwen3_next_worker(n, Smax, v5e)
    assert jax.tree.map(lambda x: (x.shape, str(x.dtype)), cache) == {
        "k": ((2, n, Smax, 1, 512), "bfloat16"), "v": ((2, n, Smax, 1, 512), "bfloat16"),
        "state": {"delta": ((6, n, 32, 128, 128), "float32"), "conv": ((6, n, 3, 8192), "bfloat16")}}
    compiled = _compile_decode(worker, params, cache, n, sds)
    text = compiled.as_text()
    assert "tpu_custom_call" not in text  # decode_attn "xla": grouped heads, the rows form
    for whole in (rf"f32\[6,{n},32,128,128\]", rf"bf16\[2,{n},{Smax},1,512\]") + _DELTA_STACKS:
        copies = re.findall(rf"^\s*%?[\w.-]+ = {whole}\S* copy\(", text, re.M)
        assert not copies, f"the decode step copies a whole stack: {copies}"
    # one layer's state as a value of its own (sliced out, or an update not in place)
    layer_state = re.findall(rf"^\s*(?:ROOT )?%?[\w.-]+ = f32\[1,{n},32,128,128\]\S* "
                             r"(?:copy|dynamic-slice|fusion)\(", text, re.M)
    in_fusion = re.findall(rf"^\s*%?dynamic[_-]slice[\w.-]* = f32\[1,{n},32,128,128\]", text, re.M)
    assert in_fusion and len(layer_state) <= len(in_fusion), (layer_state, in_fusion)
    ma = compiled.memory_analysis()
    state_bytes, kv_bytes = 6 * n * 32 * 128 * 128 * 4, 4 * n * Smax * 512 * 2
    assert ma.alias_size_in_bytes >= state_bytes + kv_bytes  # donated in, aliased out
    assert ma.temp_size_in_bytes < state_bytes // 6 * 2 // 3, ma.temp_size_in_bytes  # 74 MB
    assert _footprint(compiled) < 0.5 * HBM_BYTES


@pytest.mark.parametrize("rows", [8192, 4096])
def test_delta_prefill_loops_over_chunks_and_over_no_row(rows, v5e, no_persistent_cache, as_tpu):
    """The cell's two prefill buckets (8,192 and 4,096 rows) compiled for the chip: the
    two attention layers go through the flash forward kernel at a 256-wide head, the
    held experts' pairs through the grouped-matmul kernel, and each delta layer's block
    form through ``ops/pallas/delta_rule.py`` (PR 55), which sweeps the [16, 2, 128, 128]
    float32 state through the chunks INSIDE the kernel: no loop of the program carries
    it (the XLA form's three chunk scans of the scanned period are gone), nothing
    [chunks, 16, 2, 64, 64] reaches HBM (the decays, ``A``, its inverse: 38 operations of
    0.6 to 1 ms a layer at 8,192 rows before), nothing rows x rows is made, no stack is
    copied whole, and the program fits in about half the chip."""
    from deepspeed_tpu.ops.pallas.delta_rule import KERNEL_NAME

    n, Smax = 64, 8192
    cfg, worker, params, cache, sds = _qwen3_next_worker(n, Smax, v5e)
    compiled = _compile_prefill(worker, params, cache, rows, sds)
    text = compiled.as_text()
    kernels = set(re.findall(r'^\s*%?([a-z_-]+?)[\d.]* = .*custom_call_target="tpu_custom_call"',
                             text, re.M))
    assert kernels == {"flash_fwd", "ragged-dot-gmm", KERNEL_NAME}, kernels
    calls = re.findall(rf'^\s*%?{KERNEL_NAME}[\d.]* = \((\S+), (\S+)\) custom-call\(', text, re.M)
    assert len(calls) == 3  # one a delta layer of the scanned period
    assert all(o.startswith(f"f32[1,{rows},4096]") and S.startswith("f32[1,16,2,128,128]")
               for o, S in calls), calls
    loops = re.findall(r"^\s*%?while[\w.-]* = \((.*?)\) while\(", text, re.M)
    assert not [carry for carry in loops if "f32[1,16,2,128,128]" in carry] and len(loops) <= 12
    assert not re.search(rf"\[(?:1,)?{rows // 64},(?:1,)?16,2,64,(?:64|128)\]", text)  # a chunk's matrices, the scan's operands
    assert not re.search(rf"\[(?:1,)?(?:16|32),{rows},{rows}\]", text)
    for whole in _DELTA_STACKS:
        copies = re.findall(rf"^\s*%?[\w.-]+ = {whole}\S* copy\(", text, re.M)
        assert not copies, f"the prefill copies a whole stack: {copies}"
    # 1.02 GB at 8,192 rows and 0.51 at 4,096 (2.2 GB with the XLA form's chunk operands)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.3e9 * rows / Smax
    assert _footprint(compiled) < 0.6 * HBM_BYTES


def _ouro_worker(n, Smax, v5e, **fields):
    """Ouro-2.6B's twelve layers at their published widths and the cell's passes
    (``chipbench/configs/ouro-2.6b-L12.json``'s ``program``: 16 heads of 128, a gated
    feed-forward of 5632, sandwich norms, the exit gate, the whole 49,152-row head, the
    stack run four times)."""
    import json
    from deepspeed_tpu.models import transformer as tfm

    with open(os.path.join(os.path.dirname(chip_smoke.__file__), "chipbench", "configs",
                           "ouro-2.6b-L12.json")) as f:
        program = json.load(f)["program"]
    cfg = tfm.TransformerConfig(dtype=jnp.bfloat16, **{**program, "max_seq_len": Smax, **fields})
    return (cfg, *_bare_slot_worker(cfg, n, Smax, SingleDeviceSharding(v5e[0])))


# a layer stack of Ouro's that no program may copy: wo, the three feed-forward matrices
_OURO_STACKS = (r"bf16\[12,16,128,2048\]", r"bf16\[12,2048,5632\]", r"bf16\[12,5632,2048\]")
_OURO_QKV = r"bf16\[12,2048,16,128\]"


def test_passes_decode_program_loops_over_the_passes_and_keeps_the_cache_in_place(
        v5e, no_persistent_cache, as_tpu):
    """``SlotWorker``'s decode step of the looped block at the cell's own size (twelve
    layers run four times, 24 slots x 1,024): the cache tree is K/V of 48 (pass, layer)s,
    [48, 24, 1024, 16, 128] twice (9.66 GB), the carry of BOTH loops, donated in and
    aliased out, never copied. The passes are a LOOP: the program's text is that of the
    one-pass program within a fifth (it would be fourfold unrolled), the Pallas decode
    kernel stands in it ONCE (12 x 4 = 48 calls a step), inside two nested loops. No
    feed-forward or output-projection stack is copied. The compiler does re-lay the
    q / k / v stacks head-major (the layout its projection wants; the one-pass program
    re-lays a layer's slice of them inside the loop, every layer): with the stacks
    constants of TWO loops it hoists that out of both, ONCE a call (3 x 100 MB, from
    the entry's own parameters), which is a quarter of what the slices would cost
    over four passes; nothing is copied a pass. The footprint is what the cell's
    ``why`` says: under three quarters of the chip."""
    n, Smax = 24, 1024
    texts = {}
    for passes in (1, 4):
        cfg, worker, params, cache, sds = _ouro_worker(n, Smax, v5e, layer_passes=passes,
                                                       exit_gate=passes > 1)
        compiled = _compile_decode(worker, params, cache, n, sds)
        texts[passes] = compiled.as_text()
    text = texts[4]
    assert jax.tree.map(lambda x: x.shape, cache) == {"k": (48, n, Smax, 16, 128),
                                                      "v": (48, n, Smax, 16, 128)}
    assert len(text) < 1.2 * len(texts[1]), (len(text), len(texts[1]))
    assert len(re.findall(r'custom_call_target="tpu_custom_call"', text)) == 1
    _walk_built_once(worker, params, cache, n, sds, loops=2)  # once a step, not 48 times
    loops = re.findall(r"^\s*%?while[\w.-]* = ", text, re.M)
    assert len(loops) == len(re.findall(r"^\s*%?while[\w.-]* = ", texts[1], re.M)) + 1
    assert not _whole_copies(text, rf"bf16\[48,{n},{Smax},16,128\]")
    for stack in _OURO_STACKS:
        assert not _whole_copies(text, stack), stack
    relaid = _whole_copies(text, _OURO_QKV)  # in the entry computation: outside both loops
    assert len(relaid) <= 3 and relaid == _whole_copies(text[text.index("\nENTRY "):], _OURO_QKV)
    ma = compiled.memory_analysis()
    kv_bytes = 2 * 48 * n * Smax * 16 * 128 * 2
    assert ma.alias_size_in_bytes >= kv_bytes  # donated in, aliased out
    assert ma.temp_size_in_bytes < 0.35e9, ma.temp_size_in_bytes  # 0.20 GB: the re-laid stacks
    assert _footprint(compiled) < 0.75 * HBM_BYTES  # 11.5 GB


def test_passes_prefill_attends_densely_in_every_pass_and_copies_no_stack(
        v5e, no_persistent_cache, as_tpu):
    """The cell's longest prefill bucket (512 rows) compiled for the chip: dense
    attention (16 x 512 x 512 float32 scores a layer: no kernel), the block attending to
    itself in every pass, its local cache [48, 1, 512, 16, 128] written whole into the
    slot (re-laid ONCE a prefill on its way there: twice 100 MB, k and v), the slot cache
    never copied, no feed-forward or output-projection stack copied, the q / k / v stacks
    re-laid once a call as in the decode program; temporaries 0.5 GB."""
    n, Smax, rows = 24, 1024, 512
    cfg, worker, params, cache, sds = _ouro_worker(n, Smax, v5e)
    compiled = _compile_prefill(worker, params, cache, rows, sds)
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    assert not _whole_copies(text, rf"bf16\[48,{n},{Smax},16,128\]")
    assert len(_whole_copies(text, rf"bf16\[48,1,{rows},16,128\]")) <= 2
    for stack in _OURO_STACKS:
        assert not _whole_copies(text, stack), stack
    relaid = _whole_copies(text, _OURO_QKV)  # in the entry computation: outside both loops
    assert len(relaid) <= 3 and relaid == _whole_copies(text[text.index("\nENTRY "):], _OURO_QKV)
    assert not re.search(rf"\[(?:1,)?16,{rows},{Smax}\]", text)  # scores against the SLOT's length
    assert compiled.memory_analysis().temp_size_in_bytes < 0.7e9
    assert _footprint(compiled) < 0.78 * HBM_BYTES  # 11.8 GB


def test_latent_prefill_attends_through_the_flash_kernel(v5e, no_persistent_cache, as_tpu):
    """The 1024-row prefill of the same block at 32 heads: its dense scores would
    be 128 MiB, so the expanded form goes through the flash forward kernel at q/k
    heads of 192 and value heads of 128: ONE ``flash_fwd`` call in each of the two
    layer loops (the leading dense layer's and the routed layers'), and no
    ``f32[32, 1024, 1024]`` value. The 512-row bucket attends densely."""
    L, n, Smax = 3, 8, 2048
    cfg, worker, params, cache, sds = _kanana_worker(L, n, Smax, v5e)
    text = _compile_prefill(worker, params, cache, 1024, sds).as_text()
    calls = re.findall(r'^\s*%?flash_fwd[\w.]* = .*custom_call_target="tpu_custom_call"', text, re.M)
    assert len(calls) == 2
    # the kernel's output is as wide as a value head, its q and k operands as a q/k head
    assert all(f"bf16[{cfg.num_heads},1024,128]" in c for c in calls), calls
    assert f"bf16[{cfg.num_heads},1024,192]" in text and f"[{cfg.num_heads},1024,256]" not in text
    assert not re.search(rf"f32\[(?:1,)?{cfg.num_heads},1024,1024\]", text)
    assert "flash_fwd" not in _compile_prefill(worker, params, cache, 512, sds).as_text()
