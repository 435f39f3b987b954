"""The main path compiled for a TPU v5e that is described, not attached.

The TPU compiler is installed in the sandbox: ``jax.experimental.topologies``
describes a ``v5e:2x2`` host and ``lower(...).compile()`` then raises what the
chip's compiler would raise — a misaligned kernel slice, too much VMEM, a
program over HBM — which interpret mode cannot show. Nothing runs, so nothing
here is a chip run or a time.

Tier 1 compiles the main path's kernels at real widths with
``interpret=False`` (about two seconds each) and the serving decode program
at BLOOM's and Pythia's widths, cut to four layers and a small vocabulary: the
guard that the slot KV cache stays one buffer through the layer loop; and the
2048-row prefill of the three benchmark configurations, which attends through
the flash kernel and holds no score matrix. The slow tier compiles whole
programs at GPT-2 125M: the train step as ``chip_smoke.py`` / ``bench.py``
configure it, the serving programs of ``chip_smoke.py``'s serve phase, and the
ZeRO-3 fsdp=4 step over the four described chips (run them with ``-m slow``
before spending chip time on ``chip_smoke.py``).
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

import chip_smoke

HBM_BYTES = 16e9  # one v5e chip


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    # dstpu: allow[broad-except] -- no TPU compiler in this installation: whatever it raises, the answer is skip
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    return topo.devices


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip: the next run would warn and compile
    again, so the cache is off around these."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def as_tpu(monkeypatch):
    """Code that asks ``jax.default_backend()`` still sees the CPU here;
    steer it onto its TPU branch (compiled kernels, donation) for the
    compile, in the test and not through an option of the program."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


# The routed feed-forward's grouped matmul in a compiled program's text: the
# compiler's own (``lax.ragged_dot``) and the Pallas kernel's, which carries the
# other's name as the head of its own (``ops/pallas/grouped_gemm.py::KERNEL_NAME``).
RAGGED_DOT = r"^\s*%?ragged-dot(?!-gmm)[\w.-]* = "
GMM_CALL = r"^\s*%?ragged-dot-gmm[\w.-]*"


def _footprint(compiled) -> float:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


# ---------------------------------------------------------------------------
# kernels at real widths (tier 1)
# ---------------------------------------------------------------------------

def _flash_fwd_bwd(sds):
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    x = sds((16, 1024, 12, 64), jnp.bfloat16)  # bench.py / chip_smoke micro-batch

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block_q=1024,
                                       block_k=1024, interpret=False).astype(jnp.float32))

    return jax.grad(loss, argnums=(0, 1, 2)), (x, x, x)


def _flash_fwd_bwd_alibi(sds):
    """The kernel with BLOOM's alibi computed from block positions, at the
    serving models' heads (16 of 128, 2048 rows): the slopes ride in as a
    [BH, 1, 128] array, a [BH, 128] one in (1, 128) blocks is refused by the
    chip's lowering and passed every interpret-mode test (PR 30)."""
    from deepspeed_tpu.models.transformer import alibi_slopes
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    x = sds((1, 2048, 16, 128), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, alibi_slopes=alibi_slopes(16),
                                       interpret=False).astype(jnp.float32))

    return jax.grad(loss, argnums=(0, 1, 2)), (x, x, x)


def _decode_kernel(sds):
    from deepspeed_tpu.ops.pallas.decode_attention import decode_attention

    sz = chip_smoke.REAL  # the serve phase's slot cache: [n_slots, Smax, H, Dh]
    n, Dh = sz["n_slots"], sz["D"] // sz["H"]
    cache = sds((n, sz["S"], sz["H"], Dh), jnp.bfloat16)
    return (lambda q, k, v, p: decode_attention(q, k, v, p, interpret=False),
            (sds((n, sz["H"], Dh), jnp.bfloat16), cache, cache, sds((n,), jnp.int32)))


def _fused_xent_fwd_bwd(sds):
    from deepspeed_tpu.ops.pallas.fused_xent import fused_linear_xent

    N, D, V = 16384, 768, 50304

    def loss(h, w, y):
        return jnp.sum(fused_linear_xent(h, w, y, interpret=False))

    return jax.grad(loss, argnums=(0, 1)), (
        sds((N, D), jnp.bfloat16), sds((D, V), jnp.bfloat16), sds((N,), jnp.int32))


@pytest.mark.parametrize("build", [_flash_fwd_bwd, _flash_fwd_bwd_alibi, _decode_kernel,
                                   _fused_xent_fwd_bwd],
                         ids=["flash_fwd_bwd", "flash_fwd_bwd_alibi", "decode_attention",
                              "fused_linear_xent"])
def test_kernel_compiles_for_v5e(build, v5e, no_persistent_cache):
    one_chip = SingleDeviceSharding(v5e[0])
    fn, args = build(lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _footprint(compiled) < HBM_BYTES


ALL_FLASH = ("flash_fwd", "flash_bwd_dkdv", "flash_bwd_dq")


@pytest.mark.parametrize("cell,stack,alibi", [
    ("ouro-2.6b-L12.serve-reason", (48, 24, 1024, 16, 128), False),
    ("olmoe-1b-7b-L4.serve-doc", (4, 16, 2048, 16, 128), False),
    # no cell's program: the model routes alibi round the kernel (ROADMAP S2(e)); the kernel takes it
    ("bloom-1b7.serve-doc, alibi in the kernel", (24, 8, 2048, 16, 128), True)])
def test_decode_kernel_compiles_at_the_cells_shapes(cell, stack, alibi, v5e, no_persistent_cache):
    """The decode kernel over a traced layer of the two cells' whole cache stacks, the
    work list handed in as a model hands it: a block by the rule (128 positions: 1 MiB
    of K and V), its two operands double-buffered well under the kernel's 16 MiB of
    VMEM (past it the compiler refuses, which interpret mode cannot show), the grid's
    length the list's own (a dynamic bound), and no copy of a stack beside the call."""
    from deepspeed_tpu.models.transformer import alibi_slopes
    from deepspeed_tpu.ops.pallas import decode_attention as da

    L, n, Smax, H, Dh = stack
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=SingleDeviceSharding(v5e[0]))
    block = da.block_rows(Smax, H * Dh * 2)
    assert block == 128 and 2 * 2 * block * H * Dh * 2 < 16 * 2 ** 20 / 4

    def step(q, k, v, pos, layer):
        walk = da.decode_walk(pos, n, Smax, block)
        return da.decode_attention(q, k, v, pos, layer=layer, walk=walk, interpret=False,
                                   alibi_slopes=alibi_slopes(H) if alibi else None)

    cache = sds(stack, jnp.bfloat16)
    compiled = jax.jit(step).lower(sds((n, H, Dh), jnp.bfloat16), cache, cache,
                                   sds((n,), jnp.int32), sds((), jnp.int32)).compile()
    text = compiled.as_text()
    assert len(re.findall(r'custom_call_target="tpu_custom_call"', text)) == 1
    assert not _whole_copies(text, re.escape(f"bf16[{L},{n},{Smax},{H},{Dh}]"))
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


@pytest.mark.parametrize("bh,rows,widths,dtype,alibi,kernels,block_k", [
    (128, 2048, (128, 128), jnp.bfloat16, False, ALL_FLASH, 2048),
    (32, 8192, (192, 128), jnp.bfloat16, False, ("flash_fwd",), 2048),
    (16, 2048, (128, 128), jnp.bfloat16, True, ("flash_fwd",), 2048),
    (16, 2048, (128, 128), jnp.bfloat16, True, ALL_FLASH, 2048),
    (8, 4096, (128, 128), jnp.float32, True, ALL_FLASH, 2048),
    (8, 4096, (256, 256), jnp.bfloat16, False, ALL_FLASH, 2048),
    (8, 4096, (256, 256), jnp.float32, False, ALL_FLASH, 1024),
    (8, 4096, (64, 64), jnp.bfloat16, False, ALL_FLASH, 2048),
], ids=["train-128x2048x128", "latent-32x8192x192-128", "alibi-16x2048x128", "alibi-fwd-bwd",
        "f32-128-alibi", "bf16-256", "f32-256-halved", "bf16-64"])
def test_causal_schedule_compiles_for_v5e(bh, rows, widths, dtype, alibi, kernels, block_k, v5e,
                                          no_persistent_cache):
    """The causal kernels with their work cut inside the step (PR 51: a case a
    count of key sub-tiles, each on a static slice of the key block, index maps
    that stay on the last block a row needs) at the cells' shapes: the train
    cell's three kernels at [128 heads x 2048 x 128], kanana's forward at q/k
    heads of 192 beside value heads of 128 over 8,192 rows, BLOOM's with alibi;
    and at the widths and dtypes that decide the key block (``_key_block``: 2,048
    keys where a block holds ``KEY_BLOCK_BYTES`` or less; 256-wide float32
    heads at 2,048 are refused by 4 MiB and take 1,024). Each compiles for the
    described chip inside the 16 MiB of VMEM its compiler gives a kernel (it
    refuses more)."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    one_chip = SingleDeviceSharding(v5e[0])
    d, dv = widths
    qk = jax.ShapeDtypeStruct((bh, rows, d), dtype, sharding=one_chip)
    v = jax.ShapeDtypeStruct((bh, rows, dv), dtype, sharding=one_chip)
    slopes = jnp.full((bh, 1, fa.LANES), 0.25, jnp.float32) if alibi else None
    blocks = fa._auto_block(rows, fa.MAX_BLOCK_Q), fa._key_block(rows, d, jnp.dtype(dtype).itemsize)
    assert blocks == (512, block_k) and fa._sub_tile(blocks[1]) == fa.SUB_K

    def attend(q, k, v):
        return fa._flash_bhsd(q, k, v, slopes, None, d ** -0.5, True, *blocks, False, 0)

    if len(kernels) == 1:
        fn = attend
    else:
        fn = jax.grad(lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32)),
                      argnums=(0, 1, 2))
    text = jax.jit(fn).lower(qk, qk, v).compile().as_text()
    found = set(re.findall(r'^\s*%?[a-z_]*?(flash_[a-z]+(?:_[a-z]+)?)_*[\d.]* = .*'
                           r'custom_call_target="tpu_custom_call"', text, re.M))
    assert found == set(kernels), found


@pytest.mark.parametrize("window", [100, 128, 512, 1024, 2048, 4096])
@pytest.mark.parametrize("widths,dtype", [((64, 64), jnp.bfloat16), ((128, 128), jnp.bfloat16),
                                          ((192, 128), jnp.bfloat16), ((256, 256), jnp.bfloat16),
                                          ((128, 128), jnp.float32), ((256, 256), jnp.float32)],
                         ids=lambda x: "x".join(map(str, x)) if isinstance(x, tuple) else x.__name__)
def test_a_static_window_compiles_as_a_band_or_keeps_the_whole_grid(widths, dtype, window, v5e,
                                                                    no_persistent_cache):
    """``flash_attention(window=<a Python number>)`` at 16,384 rows x 8 heads for
    the chip, forward with the logsumexp (the most VMEM a step takes): a band
    whose step ``band_plan`` sized (``flash_fwd_band``: heads and key blocks a
    step by its estimate, inside the 16 MiB the compiler gives a kernel) or,
    where one head's step would not fit (a window of thousands of keys at wide
    heads), the whole grid under the window as an operand. Neither is refused."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    rows, heads, (d, dv) = 16384, 8, widths
    one_chip = SingleDeviceSharding(v5e[0])
    qk = jax.ShapeDtypeStruct((heads, rows, d), dtype, sharding=one_chip)
    v = jax.ShapeDtypeStruct((heads, rows, dv), dtype, sharding=one_chip)
    plan = fa.band_plan(rows, window, heads, d, dv, jnp.dtype(dtype).itemsize)
    w_arr = jnp.full((1, fa.LANES), float(window), jnp.float32)
    blocks = plan[:2] if plan else (fa.MAX_BLOCK_Q, fa._key_block(rows, d, jnp.dtype(dtype).itemsize))
    compiled = jax.jit(lambda q, k, v: fa._flash_forward(
        q, k, v, None, w_arr, d ** -0.5, True, *blocks, False,
        band=window if plan else 0)).lower(qk, qk, v).compile()
    assert ("flash_fwd_band" in compiled.as_text()) == bool(plan)
    # the cell's shape keeps the step that was timed; only a wide band is given up
    if (widths, dtype, window) == ((128, 128), jnp.bfloat16, 128):
        assert plan == (256, 128, 3, 8)
    assert plan or window >= 1024


def _bare_slot_worker(cfg, n, Smax, one_chip):
    """A ``SlotWorker`` with just what its program builders read, and the
    shapes of its operands on the described chip, the weights typed as
    ``InferenceEngine`` holds them: (worker, params, cache, sds)."""
    from deepspeed_tpu.inference.serving import SlotWorker
    from deepspeed_tpu.models import transformer as tfm
    from deepspeed_tpu.models.transformer import Model, hold_for_compute

    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    params = jax.tree.map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(lambda r: hold_for_compute(cfg, Model(cfg).init(r)), jax.random.PRNGKey(0)))
    cache = jax.tree.map(  # the tree the model's attention caches: K/V per head, or a latent
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(lambda: tfm.init_cache(cfg, n, Smax, dtype=jnp.bfloat16)))
    worker = SlotWorker.__new__(SlotWorker)
    worker.cfg, worker.Smax = cfg, Smax
    tfm._ACTIVE_MESH[0] = None  # the engine's own (one chip) in a process; an earlier test's here
    worker._cache_shardings = {name: one_chip for name in cache}
    return worker, params, cache, sds


def _decode_operands(params, cache, n, sds):
    vec = lambda dtype: sds((n,), dtype)
    return (params, cache, vec(jnp.int32), vec(jnp.int32), vec(jnp.int32), vec(jnp.bool_),
            sds((2,), jnp.uint32), vec(jnp.float32), vec(jnp.int32), vec(jnp.float32))


def _compile_decode(worker, params, cache, n, sds):
    return worker._build_decode().lower(*_decode_operands(params, cache, n, sds)).compile()


def _loop_depths(jaxpr, primitive, depth=0):
    """How deep in loops (``scan`` / ``while``) each ``primitive`` equation of a jaxpr lies."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive:
            found.append(depth)
        inner = depth + (eqn.primitive.name in ("scan", "while"))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _loop_depths(sub, primitive, inner)
    return found


def _walk_built_once(worker, params, cache, n, sds, loops):
    """The decode kernel's work list (a ``cumsum`` over the rows' blocks; the sampler's
    nucleus has the program's other one) is built outside the layer loop(s), once a
    step, and the kernel stands ``loops`` loops deep."""
    jaxpr = worker._build_decode().trace(*_decode_operands(params, cache, n, sds)).jaxpr.jaxpr
    assert set(_loop_depths(jaxpr, "cumsum")) == {0}
    assert _loop_depths(jaxpr, "pallas_call") == [loops]


def _compile_prefill(worker, params, cache, bucket, sds):
    one = lambda dtype: sds((1,), dtype)
    return worker._build_prefill(bucket).lower(
        params, cache, sds((1, bucket), jnp.int32), sds((), jnp.int32), sds((), jnp.int32),
        sds((2,), jnp.uint32), one(jnp.float32), one(jnp.int32), one(jnp.float32)).compile()


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
        _walk_built_once(worker, params, cache, n, sds, loops=1)
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


def _computations(text):
    """An optimised HLO module's text as {computation: (its lines, the
    computations it calls outside a conditional's branches, those it calls as
    a conditional's branches)}, and the entry computation's name."""
    comps, entry, name = {}, None, None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%?([\w.-]+) \(.*\) -> .* \{$", line)
        if head:
            name = head.group(2)
            comps[name] = ([], set(), set())
            entry = name if head.group(1) else entry
        elif name is not None and line != "}":
            body, calls, branches = comps[name]
            body.append(line)
            calls.update(re.findall(r"(?:calls|to_apply|body|condition)=%?([\w.-]+)", line))
            for group in re.findall(r"branch_computations=\{([^}]*)\}", line):
                branches.update(c.strip().lstrip("%") for c in group.split(","))
            branches.update(re.findall(r"(?:true|false)_computation=%?([\w.-]+)", line))
    return comps, entry


def _reach(comps, roots, through_branches):
    seen, todo = set(), list(roots)
    while todo:
        c = todo.pop()
        if c not in seen and c in comps:
            seen.add(c)
            todo += comps[c][1] | (comps[c][2] if through_branches else set())
    return seen


def _operations_writing(text, elements, dtype="bf16"):
    """The instructions of an optimised module that run as operations of their own
    (the lines of the entry computation and of every loop body, loop condition and
    conditional branch under it; not those inside a fusion, which make no array)
    and yield a ``dtype`` array of exactly ``elements`` elements in any order of
    dimensions: one layer's K (or V), sliced out of its stack, copied or re-laid.
    Views (a bitcast, an element of a tuple, a parameter) write nothing."""
    comps, entry = _computations(text)
    seen, todo, found = set(), [entry], []
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        todo += comps[name][2]
        for line in comps[name][0]:
            todo += re.findall(r"(?:body|condition)=%?([\w.-]+)", line)
            todo += re.findall(r" call\(.*to_apply=%?([\w.-]+)", line)
            op = re.match(rf"\s*(?:ROOT )?%?[\w.-]+ = {dtype}\[([\d,]+)\]\S* ([\w-]+)\(", line)
            if (op and op.group(2) not in ("bitcast", "get-tuple-element", "parameter")
                    and np.prod([int(d) for d in op.group(1).split(",")]) == elements):
                found.append(line.strip()[:160])
    return found


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


def _whole_copies(text, shape):
    return re.findall(rf"^\s*%?[\w.-]+ = {shape}\S* copy\((\S+?)\)", text, re.M)


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
], ids=lambda v: v if isinstance(v, str) else None)
def test_dropless_expert_block_takes_the_grouped_matmul_kernel(cell, widths, rows, tile, v5e,
                                                               no_persistent_cache, as_tpu):
    """The routed block of the four routed cells (their widths, experts and
    choices; two layers of held stacks; a prefill bucket of each) in one scan,
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
    assert expert_gemm_form(cfg, moe["experts"], rows, False) == "ragged_dot"

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
# whole programs at GPT-2 125M (slow tier)
# ---------------------------------------------------------------------------

def _retarget(engine, devices) -> None:
    """Point a CPU-built engine's mesh and state shardings at described
    devices, so that the step it builds next lowers for them."""
    mesh = Mesh(np.asarray(devices).reshape(engine.mesh.devices.shape),
                engine.mesh.axis_names)
    engine.mesh = mesh
    engine.model.set_mesh(mesh)
    engine._state_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s.spec, memory_kind=s.memory_kind),
        engine._state_shardings, is_leaf=lambda x: isinstance(x, NamedSharding))


def _compile_train_step(engine, sz):
    state = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        engine.state, engine._state_shardings)
    batch = {"tokens": jax.ShapeDtypeStruct(
        (sz["B"], sz["S"] + 1), jnp.int32,
        sharding=NamedSharding(engine.mesh, engine.batch_spec))}
    return engine._build_train_step().lower(state, batch).compile()


@pytest.mark.slow
def test_train_step_125m_compiles_for_one_v5e(v5e, no_persistent_cache, as_tpu):
    import deepspeed_tpu
    from deepspeed_tpu.comm.mesh import MeshConfig, build_mesh

    sz = chip_smoke.REAL
    cfg = chip_smoke._ds_config(sz, zero_stage=1, micro=sz["micro"],
                                gas=sz["B"] // sz["micro"], mesh={"data": 1})
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=chip_smoke._train_model(sz), config=cfg,
        mesh=build_mesh(MeshConfig(data=1), devices=jax.devices()[:1]))
    _retarget(engine, v5e[:1])
    compiled = _compile_train_step(engine, sz)
    assert "tpu_custom_call" in compiled.as_text()  # the flash kernel is in it
    print("train step 125M on one v5e:", compiled.memory_analysis())
    assert _footprint(compiled) < HBM_BYTES


@pytest.mark.slow
def test_fsdp4_train_step_125m_compiles_for_v5e_2x2(v5e, no_persistent_cache, as_tpu):
    import deepspeed_tpu
    from deepspeed_tpu.comm.mesh import MeshConfig, build_mesh

    sz = chip_smoke.REAL
    cfg = chip_smoke._ds_config(sz, zero_stage=3, micro=sz["micro"], gas=1,
                                mesh={"data": 1, "fsdp": 4})
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=chip_smoke._train_model(sz), config=cfg,
        mesh=build_mesh(MeshConfig(data=1, fsdp=4), devices=jax.devices()[:4]))
    _retarget(engine, v5e)
    compiled = _compile_train_step(engine, sz)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" in text or "all-reduce" in text  # really partitioned
    print("fsdp=4 train step 125M, per device:", compiled.memory_analysis())
    assert _footprint(compiled) < HBM_BYTES


def _save_flash_twin(mesh_sizes: dict, n_devices: int):
    """The 125M twin cut to eight layers and a small vocabulary (a scanned
    layer is traced once whatever the depth), under ``save_flash``: the policy
    whose checkpoints the engine may add to (the file's own twin states
    ``dots_and_flash``, an explicit choice)."""
    import deepspeed_tpu
    from deepspeed_tpu.comm.mesh import MeshConfig, build_mesh
    from deepspeed_tpu.models.transformer import Model

    sz = {**chip_smoke.REAL, "L": 8, "V": 2048, "B": 16, "micro": 16 // n_devices}
    model = Model(chip_smoke._train_model(sz).config.replace(remat_policy="save_flash"))
    cfg = chip_smoke._ds_config(sz, zero_stage=3 if n_devices > 1 else 1, micro=sz["micro"],
                                gas=1, mesh=mesh_sizes)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, config=cfg,
        mesh=build_mesh(MeshConfig(**mesh_sizes), devices=jax.devices()[:n_devices]))
    return engine, sz


def _compile_with_limit(engine, sz, **kw):
    state = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        engine.state, engine._state_shardings)
    batch = {"tokens": jax.ShapeDtypeStruct(
        (sz["B"], sz["S"] + 1), jnp.int32,
        sharding=NamedSharding(engine.mesh, engine.batch_spec))}
    return engine._build_train_step(**kw).lower(state, batch).compile()


@pytest.mark.parametrize("mesh_sizes, n_devices", [({"data": 1}, 1), ({"data": 1, "fsdp": 4}, 4)],
                         ids=["one_v5e", "fsdp4_on_the_2x2"])
def test_train_step_keeps_the_matmul_output_a_handed_in_limit_holds(
        v5e, no_persistent_cache, as_tpu, mesh_sizes, n_devices):
    """``_saving_what_fits`` for the described chip: with no limit handed in
    (a described device has no ``memory_stats()``) the step is the floor
    program; with a limit that holds the candidate the backward pass has one
    product fewer (the up projection's) and the compiler's temporaries grow by
    about the candidate's bytes, never by twice them; with a limit that does
    not, the floor program again."""
    from deepspeed_tpu.models.transformer import FFN_NAMES
    from deepspeed_tpu.runtime.remat_plan import HEADROOM
    from deepspeed_tpu.utils.memory import device_bytes_held

    engine, sz = _save_flash_twin(mesh_sizes, n_devices)
    _retarget(engine, v5e[:n_devices])
    products = lambda c: len(re.findall(r" convolution\(", c.as_text()))
    temporaries = lambda c: c.memory_analysis().temp_size_in_bytes
    floor = _compile_with_limit(engine, sz)
    assert engine._remat_plans == {}
    rows = sz["B"] // n_devices
    names, ffn, residuals, working = engine.model.remat_offer(
        {"tokens": jax.ShapeDtypeStruct((rows, sz["S"] + 1), jnp.int32)})
    # bfloat16 over eight layers; the twin's 64-wide heads lie in 128-lane tiles in the
    # kernel's [heads, rows, width] arrays: flash_out takes twice its values
    per_width = rows * sz["S"] * 2 * sz["L"]
    assert (names, ffn, residuals) == (
        FFN_NAMES[:1], per_width * 4 * sz["D"], per_width * (sz["D"] + 2 * sz["D"]))
    state = jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
                         engine.state, engine._state_shardings)
    grads = device_bytes_held(state["params"]) // 2  # float32 parameters, bfloat16 gradients
    limit_for = lambda room: int(
        device_bytes_held(state) + residuals + HEADROOM * (grads + working) + room) + 1
    kept = _compile_with_limit(engine, sz, remat_limit=limit_for(ffn), remat_state=state)
    (plan,) = engine._remat_plans.values()
    engine._remat_plans.clear()
    assert plan.names == FFN_NAMES[:1] and plan.saved_bytes == ffn
    assert products(floor) - products(kept) == 1
    # the saved stack and no second copy of it. How close depends on where the step's
    # memory peaks: 1.02 of the candidate's bytes on the 2x2 at 4 rows a chip, 1.76 on one
    # chip at 16 rows; the cell's own step grows by 1.02 (experiments/remat_fit.py)
    assert 0.98 * ffn <= temporaries(kept) - temporaries(floor) < 2 * ffn
    # and the count of the floor program's temporaries covers what the compiler needed
    peak = floor.memory_analysis().peak_memory_in_bytes
    assert peak - device_bytes_held(state) - residuals <= HEADROOM * (grads + working)
    again = _compile_with_limit(engine, sz, remat_limit=limit_for(ffn - 2), remat_state=state)
    (plan,) = engine._remat_plans.values()
    assert plan.names == ()
    assert (products(again), temporaries(again)) == (products(floor), temporaries(floor))


@pytest.mark.slow
def test_serving_programs_125m_compile_for_one_v5e(v5e, no_persistent_cache, as_tpu):
    """The programs ``SlotWorker`` builds for chip_smoke's serve phase: the
    one decode step, and the smallest and largest prefill bucket its prompts
    fall into (each program takes ~25 s here: the vocab-wide sampler sort)."""
    from deepspeed_tpu.inference.serving import _next_pow2
    from deepspeed_tpu.models.transformer import TransformerConfig

    sz = chip_smoke.REAL
    cfg = TransformerConfig(
        vocab_size=sz["V"], max_seq_len=sz["S"], num_layers=sz["L"],
        num_heads=sz["H"], hidden_size=sz["D"], pos_emb="learned", dtype=jnp.bfloat16)
    n, Smax = sz["n_slots"], sz["S"]
    worker, params, cache, sds = _bare_slot_worker(cfg, n, Smax, SingleDeviceSharding(v5e[0]))
    decode = _compile_decode(worker, params, cache, n, sds)
    assert "tpu_custom_call" in decode.as_text()  # the Pallas decode kernel
    worst = _footprint(decode)
    buckets = sorted({max(16, _next_pow2(p)) for p in sz["prompt_lens"]})
    for bucket in (buckets[0], buckets[-1]):
        worst = max(worst, _footprint(_compile_prefill(worker, params, cache, bucket, sds)))
    print(f"serving programs 125M on one v5e: worst footprint {worst / 1e9:.2f} GB")
    assert worst < HBM_BYTES
