"""The main path's kernels compiled for a TPU v5e that is described, not attached
(``tests/chip_compile_cases.py``): at real widths with ``interpret=False`` (about two
seconds each), at the cells' own shapes, and the causal and banded schedules over a
sweep of widths. Tier 1.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import chip_smoke

from chip_compile_cases import (  # noqa: F401 -- the fixtures are used by name
    HBM_BYTES, _footprint, _whole_copies, v5e, no_persistent_cache, as_tpu)


# ---------------------------------------------------------------------------
# kernels at real widths (tier 1)
# ---------------------------------------------------------------------------

def _flash_fwd_bwd(sds):
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    x = sds((16, 1024, 12, 64), jnp.bfloat16)  # chip_smoke micro-batch

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


FUSED = ("flash_fwd", "flash_bwd")  # PR 64: forward + backward are TWO Pallas calls where dQ fits VMEM
SPLIT = ("flash_fwd", "flash_bwd_dkdv", "flash_bwd_dq")


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


@pytest.mark.parametrize("bh,rows,widths,dtype,alibi,kernels,block_k,mask_block", [
    (128, 2048, (128, 128), jnp.bfloat16, False, FUSED, 2048, 1),
    (32, 8192, (192, 128), jnp.bfloat16, False, ("flash_fwd",), 2048, 1),
    (32, 2048, (192, 128), jnp.bfloat16, False, FUSED, 2048, 1),
    (16, 2048, (128, 128), jnp.bfloat16, True, ("flash_fwd",), 2048, 1),
    (16, 2048, (128, 128), jnp.bfloat16, True, FUSED, 2048, 1),
    (8, 4096, (128, 128), jnp.bfloat16, True, FUSED, 2048, 1),
    (8, 4096, (192, 128), jnp.bfloat16, False, SPLIT, 2048, 1),
    (8, 8192, (128, 128), jnp.bfloat16, False, SPLIT, 2048, 1),
    (8, 4096, (128, 128), jnp.float32, True, SPLIT, 2048, 1),
    (8, 4096, (256, 256), jnp.bfloat16, False, SPLIT, 2048, 1),
    (8, 4096, (256, 256), jnp.float32, False, SPLIT, 1024, 1),
    (8, 4096, (64, 64), jnp.bfloat16, False, FUSED, 2048, 1),
    (16, 8192, (256, 256), jnp.bfloat16, False, ("flash_fwd",), 2048, 1),
    (128, 2048, (256, 256), jnp.bfloat16, False, SPLIT, 2048, 1),
    (32, 6144, (128, 128), jnp.bfloat16, False, FUSED, 2048, 1),
    (32, 2048, (128, 128), jnp.bfloat16, False, ("flash_fwd",), 2048, 4),
    (32, 1024, (128, 128), jnp.bfloat16, False, ("flash_fwd",), 1024, 4),
    (32, 1024, (64, 64), jnp.bfloat16, False, ("flash_fwd",), 1024, 1),
    (128, 1536, (128, 128), jnp.bfloat16, False, FUSED, 512, 1),
], ids=["train-128x2048x128", "latent-32x8192x192-128", "latent-train-32x2048x192-128",
        "alibi-16x2048x128", "alibi-fwd-bwd", "bf16-128-4096-alibi", "latent-4096-split",
        "bf16-128-8192-split", "f32-128-alibi", "bf16-256", "f32-256-halved", "bf16-64",
        "qwen3-next-16x8192x256", "bf16-256-128-heads", "bf16-128-6144-fused",
        "sdar-32x2048x128-blocks-of-4", "sdar-32x1024x128-blocks-of-4", "lfm2-32x1024x64",
        "train-128x1536x128"])
def test_causal_schedule_compiles_for_v5e(bh, rows, widths, dtype, alibi, kernels, block_k, mask_block,
                                          v5e, no_persistent_cache):
    """The causal kernels with their work cut inside the step (PR 51: a case a
    count of key sub-tiles, each on a static slice of the key block, index maps
    that stay on the last block a row needs) at the cells' shapes: the train
    cell's three kernels at [128 heads x 2048 x 128], kanana's forward at q/k
    heads of 192 beside value heads of 128 over 8,192 rows, BLOOM's with alibi;
    and at the widths and dtypes that decide the key block (``_key_block``: 2,048
    keys where a block holds ``KEY_BLOCK_BYTES`` or less; 256-wide float32
    heads at 2,048 are refused by 4 MiB and take 1,024). Each compiles for the
    described chip inside the 16 MiB of VMEM its compiler gives a kernel (it
    refuses more). Since PR 64 the backward is ONE kernel (``flash_bwd``, once:
    the train step's forward + backward hold TWO Pallas calls, were three) where
    ``backward_form`` says a head's dQ fits VMEM beside a step's blocks (the
    train cell's shape, latent attention's 192 / 128 heads at 2,048 rows, 4,096
    rows of 128-wide bfloat16 heads under alibi), and the pair on the other side
    of the line, which the compiler takes at any length. Since PR 65 the
    backward cuts the diagonal's tile in 256 x 256 sub-tiles (``diag_sub``: a
    case is two pieces, the keys the first 256 rows see against all 512 and the
    256 more against the last 256), and so does the forward where the key block
    holds four query blocks (2,048 rows and over); the backward's tile is
    transposed, the logsumexp is a [BH, 1, rows] row, delta is made in the kernel,
    and a forward that nothing differentiates writes no logsumexp: the train cell's shape,
    kanana's 192 / 128 heads over 8,192 rows, qwen3-next's 256-wide heads over
    8,192 rows (forward) and at 128 heads through the pair, 6,144 rows of
    128-wide heads, which the one kernel's own count of its buffers now holds
    (15.8 MiB; 16.5 with two lane-broadcast blocks where O's and a row are), and
    on the forward's other side: SDAR's prefill under the mask between blocks of
    4 positions at 2,048 rows (cut) and 1,024 (whole), LFM2's 64-wide heads at
    1,024 rows, and 1,536 rows in 512 x 512 blocks, where the forward runs the
    diagonal whole and the backward cuts it."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    one_chip = SingleDeviceSharding(v5e[0])
    d, dv = widths
    qk = jax.ShapeDtypeStruct((bh, rows, d), dtype, sharding=one_chip)
    v = jax.ShapeDtypeStruct((bh, rows, dv), dtype, sharding=one_chip)
    slopes = jnp.full((bh, 1, fa.LANES), 0.25, jnp.float32) if alibi else None
    blocks = fa._auto_block(rows, fa.MAX_BLOCK_Q), fa._key_block(rows, d, jnp.dtype(dtype).itemsize)
    assert blocks == (512, block_k) and fa._sub_tile(blocks[1]) == fa.SUB_K
    item = jnp.dtype(dtype).itemsize
    assert fa.diag_sub(rows, max(d, dv), item, backward=True) == fa.DIAG_SUB == 256
    assert fa.diag_sub(rows, max(d, dv), item) == (256 if block_k == 2048 else 0)
    form = fa.backward_form(rows, d, dv, item)
    assert len(kernels) == 1 or kernels == {"fused": FUSED, "split": SPLIT}[form]

    def attend(q, k, v):
        return fa._flash_bhsd(q, k, v, slopes, None, d ** -0.5, True, *blocks, False, 0, mask_block)

    if len(kernels) == 1:
        fn = attend
    else:
        fn = jax.grad(lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32)),
                      argnums=(0, 1, 2))
    text = jax.jit(fn).lower(qk, qk, v).compile().as_text()
    found = re.findall(r'^\s*(?:ROOT )?%?[a-z_]*?(flash_[a-z]+(?:_[a-z]+)?)_*[\d.]* = .*'
                       r'custom_call_target="tpu_custom_call"', text, re.M)
    assert sorted(found) == sorted(kernels), found  # each once


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
