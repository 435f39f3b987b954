"""Flash-attention kernel numerics vs the pure-XLA reference attention.

Mirrors the reference's kernel-test strategy (tests/unit/test_cuda_forward.py
/ test_cuda_backward.py: fused kernel vs vendored framework implementation
within tolerance). On CPU the Pallas kernels run in interpreter mode, so the
same kernel code paths are exercised as on TPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.transformer import Model, TransformerConfig, xla_attention
from deepspeed_tpu.ops.pallas.flash_attention import backward_form, flash_attention


def _qkv(B=2, S=256, H=4, D=32, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (B, S, H, D)
    return tuple(jax.random.normal(k, shape, dtype) * 0.5 for k in ks)


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_xla(causal):
    q, k, v = _qkv()
    ref = (
        xla_attention(q, k, v)
        if causal
        else _dense_nocausal(q, k, v)
    )
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def _dense_nocausal(q, k, v):
    import math

    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def test_forward_uneven_blocks():
    q, k, v = _qkv(S=384)
    ref = xla_attention(q, k, v)
    out = flash_attention(q, k, v, block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_gradients_match_xla():
    q, k, v = _qkv(B=1, S=256, H=2, D=32)

    def loss_flash(q, k, v):
        return jnp.sum(jnp.square(flash_attention(q, k, v)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.square(xla_attention(q, k, v)))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5, err_msg=f"d{name}"
        )


def test_unaligned_seq_len_pads():
    # curriculum-truncated odd lengths (VERDICT r02 weak #10): causal padding
    # path — padded keys are causally masked, padded query rows sliced off
    q, k, v = _qkv(S=200)
    out = flash_attention(q, k, v, block_q=128, block_k=128)
    ref = xla_attention(q, k, v)
    assert out.shape == ref.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-2, atol=2e-3)


def test_bias_not_supported():
    q, k, v = _qkv(S=128)
    with pytest.raises(NotImplementedError):
        flash_attention(q, k, v, bias=jnp.zeros((1, 4, 128, 128)))


def test_model_with_flash_attention_matches_xla():
    cfg_x = TransformerConfig(
        vocab_size=101, max_seq_len=128, num_layers=2, num_heads=4,
        hidden_size=32, dtype=jnp.float32, loss_chunk_size=0, attn_impl="xla",
    )
    cfg_f = cfg_x.replace(attn_impl="flash")
    mx, mf = Model(cfg_x), Model(cfg_f)
    params = mx.init(jax.random.PRNGKey(0))
    toks = np.random.default_rng(0).integers(0, 101, size=(2, 129)).astype(np.int32)
    lx = mx.loss(params, {"tokens": toks})
    lf = mf.loss(params, {"tokens": toks})
    np.testing.assert_allclose(np.asarray(lx), np.asarray(lf), rtol=1e-5)


@pytest.mark.parametrize("mesh_cfg", [{"data": 2, "fsdp": 2, "model": 2}, {"data": 8}],
                         ids=["dp2-fsdp2-tp2", "dp8-batch-indivisible"])
def test_sharded_wrapper_matches_unsharded(mesh_cfg):
    """flash_attention_sharded (what the model calls on a mesh of several
    devices, because a Mosaic kernel cannot be partitioned by GSPMD) gives
    the unsharded kernel's output and gradients: batch over data x fsdp,
    heads over model, and an axis that does not divide its dim left out."""
    from deepspeed_tpu.comm.mesh import MeshConfig, build_mesh
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention_sharded

    mesh = build_mesh(MeshConfig(**mesh_cfg))
    q, k, v = _qkv(B=4, S=128)
    slopes = jnp.asarray([0.5, 0.25, 0.125, 0.0625], jnp.float32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(jnp.square(fn(q, k, v)))

    plain = lambda q, k, v: flash_attention(q, k, v, alibi_slopes=slopes)
    sharded = lambda q, k, v: flash_attention_sharded(
        q, k, v, mesh=mesh, alibi_slopes=slopes)
    ref, ref_g = jax.value_and_grad(loss(plain), argnums=(0, 1, 2))(q, k, v)
    out, out_g = jax.jit(jax.value_and_grad(loss(sharded), argnums=(0, 1, 2)))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5)
    for a, b in zip(out_g, ref_g):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# A window that is a constant of the trace: the banded forward grid (PR 40)
# ---------------------------------------------------------------------------

def _dense_window(q, k, v, window):
    """[BH, S, D] causal attention under ``window`` (a query sees the keys
    q - window + 1 .. q), with each row's logsumexp."""
    import math

    S = q.shape[1]
    s = jnp.einsum("bqd,bkd->bqk", q, k) / math.sqrt(q.shape[-1])
    i = jnp.arange(S)
    seen = (i[:, None] >= i[None, :]) & (i[:, None] - i[None, :] < window)
    s = jnp.where(seen[None], s, -jnp.inf)
    return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, axis=-1), v), jax.nn.logsumexp(s, axis=-1)


def _pallas_calls(fn, *args):
    """name -> (grid, block shapes, operands) of every ``pallas_call`` ``fn``
    traces to, nested jaxprs (the custom_vjp's, a shard_map's) among them."""
    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                gm = eqn.params["grid_mapping"]
                blocks = [tuple(getattr(b, "block_size", b) for b in bm.block_shape)
                          for bm in gm.block_mappings]
                found[eqn.params["name"]] = (tuple(gm.grid), blocks, len(eqn.invars))
            for value in eqn.params.values():
                for sub in value if isinstance(value, (list, tuple)) else [value]:
                    inner = getattr(sub, "jaxpr", sub)
                    inner = getattr(inner, "jaxpr", inner)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.mark.parametrize("blocks", [(128, 128), (256, 128), (512, 128), (128, 256), (256, 256)],
                         ids=lambda b: f"bq{b[0]}-bk{b[1]}")
@pytest.mark.parametrize("window", [1, 100, 128, 256])
@pytest.mark.parametrize("rows", [256, 1024, 2048])
def test_banded_forward_matches_the_whole_grid_and_a_dense_reference(rows, window, blocks):
    """The forward over the band of key blocks a static window reaches (one step
    a query block, the band's blocks its operands) gives the whole grid's outputs
    and logsumexp under the same window as a runtime operand, and a dense
    reference's: at a window of one position, one that is no multiple of a
    block, one block, two blocks; query blocks narrower than, equal to and wider
    than the key blocks."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    block_q, block_k = (min(b, rows) for b in blocks)
    ks = jax.random.split(jax.random.PRNGKey(rows + window), 3)
    q, k, v = (jax.random.normal(kk, (2, rows, 32), jnp.float32) * 0.5 for kk in ks)
    w_arr = jnp.full((1, fa.LANES), float(window), jnp.float32)
    args = (q, k, v, None, w_arr, 32 ** -0.5, True, block_q, block_k, True)
    out_w, lse_w = fa._flash_forward(*args)            # whole grid, runtime window
    out_b, lse_b = fa._flash_forward(*args, band=window)
    ref, ref_lse = _dense_window(q, k, v, window)
    for got, lse in ((out_b, lse_b), (out_w, lse_w)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)
        assert lse.shape == (2, 1, rows)  # one float32 a row, along the lanes (PR 65)
        np.testing.assert_allclose(np.asarray(lse[:, 0]), np.asarray(ref_lse),
                                   rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(out_b), np.asarray(out_w), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(lse_b), np.asarray(lse_w), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("window", [1, 100, 128, 256, 512, 5000, 0, -1.0])
def test_static_window_through_the_public_call(window):
    """``flash_attention(window=<a Python number>)`` at its own choice of blocks:
    the dense reference's outputs, whether the window makes a band, covers every
    row (>= the rows: no window at all) or is global (<= 0)."""
    S = 512
    q, k, v = _qkv(B=1, S=S, H=2, D=32, seed=3)
    to_bhsd = lambda x: x.transpose(0, 2, 1, 3).reshape(2, S, 32)
    ref, _ = _dense_window(to_bhsd(q), to_bhsd(k), to_bhsd(v), window if window > 0 else S)
    out = flash_attention(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(to_bhsd(out)), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [100, 128])
def test_static_window_with_alibi_matches_the_traced_window(window):
    """BLOOM's slopes beside a static window: the banded forward computes the
    bias from the same distances as the whole grid does under a traced one."""
    q, k, v = _qkv(B=1, S=1024, H=4, D=32, seed=7)
    slopes = jnp.asarray([0.5, 0.25, 0.125, 0.0625], jnp.float32)
    band = flash_attention(q, k, v, alibi_slopes=slopes, window=window)
    whole = flash_attention(q, k, v, alibi_slopes=slopes, window=jnp.float32(window))
    np.testing.assert_allclose(np.asarray(band), np.asarray(whole), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window,rows", [(100, 256), (128, 512), (256, 512), (1, 256), (128, 2048),
                                         (600, 2048)])
def test_gradients_through_a_static_window_match_the_dense_reference(window, rows):
    """Forward only: under ``jax.grad`` the banded forward's residuals go to the
    backward kernels, which run their whole grid under the same window."""
    q, k, v = _qkv(B=1, S=rows, H=2, D=32, seed=5)
    to_bhsd = lambda x: x.transpose(0, 2, 1, 3).reshape(2, rows, 32)
    tgt = jax.random.normal(jax.random.PRNGKey(9), (2, rows, 32))

    def loss_flash(q, k, v):
        return jnp.sum(to_bhsd(flash_attention(q, k, v, window=window)) * tgt)

    def loss_ref(q, k, v):
        return jnp.sum(_dense_window(to_bhsd(q), to_bhsd(k), to_bhsd(v), window)[0] * tgt)

    # float32 heads: the one kernel up to 1,024 rows, the pair at 2,048; each takes the window
    kernels = BACKWARD_KERNELS[backward_form(rows, 32, 32, 4)]
    assert kernels == BACKWARD_KERNELS["split" if rows == 2048 else "fused"]
    calls = _pallas_calls(jax.grad(loss_flash), q, k, v)
    assert all(calls[name][2] == 7 for name in kernels)
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("rows,window,blocks,grid", [
    (2048, 128, (None, None), "band"), (2048, 128, (256, 128), "band"),
    (2048, 128, (512, 128), "band"), (2048, 128, (128, 256), "band"),
    (16384, 128, (None, None), "band"), (2048, 100, (None, None), "band"),
    (2048, 300, (None, None), "band"),
    (2048, None, (None, None), "whole"), (2048, "traced", (None, None), "whole"),
    (2048, 0, (None, None), "whole"), (2048, 2048, (None, None), "whole"),
    (2048, 128, "not causal", "whole"),
], ids=lambda x: str(x).replace(" ", ""))
def test_lowered_forward_grid(rows, window, blocks, grid):
    """The grid the forward kernel is traced with. A static window: NO dimension
    over key blocks; a query block's step gets the key blocks its band reaches
    as operands, ``block_q / block_k + ceil((W - 1) / block_k)`` of K and as
    many of V, at blocks sized to the window, several heads a step, no window
    operand. No window, a window that masks nothing, a TRACED window (GPT-Neo's
    scanned layers) and a call that is not causal: ``Sk / block_k`` steps at the
    triangle's blocks, as before PR 40, the traced window one more operand.
    Either kernel's name begins ``flash_fwd``."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    x = jax.ShapeDtypeStruct((1, rows, 8, 32), jnp.float32)
    causal = blocks != "not causal"
    bq, bk = blocks if causal else (None, None)
    if window == "traced":
        fn = lambda q, k, v, w: flash_attention(q, k, v, window=w)
        args = (x, x, x, jax.ShapeDtypeStruct((), jnp.float32))
    else:
        fn = lambda q, k, v: flash_attention(q, k, v, causal=causal, window=window,
                                             block_q=bq, block_k=bk)
        args = (x, x, x)
    calls = _pallas_calls(fn, *args)
    (name, (grid_dims, block_shapes, operands)), = calls.items()
    assert name.startswith("flash_fwd")
    block_q, block_k = block_shapes[0][1], block_shapes[1][1]
    shape = (8, 32, 32, 4)  # batch x heads, the head widths and the itemsize of ``x``
    if grid == "band":
        auto_q, auto_k = fa._band_blocks(rows, window)
        assert (block_q, block_k) == (bq or auto_q, bk or auto_k)
        assert auto_k == min(-(-window // 128) * 128, 256)  # 300 -> 384 does not divide 2048
        views = max(fa._band_steps(rows // block_q, block_q, block_k, window))
        if block_q % block_k == 0:
            assert views == block_q // block_k + -(-(window - 1) // block_k)
        heads = block_shapes[0][0]
        assert (block_q, block_k, views, heads) == fa.band_plan(rows, window, *shape, bq, bk)
        assert heads > 1 and grid_dims == (8 // heads, rows // block_q)
        assert operands == 1 + 2 * views and views < rows // block_k
        form, pct = fa.window_grid(rows, window, *shape, bq, bk)
        assert form == "band" and 0 < pct < 100
    else:
        assert name == "flash_fwd"
        assert (block_q, block_k) == (fa.MAX_BLOCK_Q, fa.MAX_BLOCK_K) == (512, 2048)
        assert grid_dims == (8, rows // block_q, rows // block_k)
        # a traced window, and a static one the band cannot take, ride as an operand
        assert operands == 3 + (window == "traced" or not causal)
        if causal and window != "traced":
            assert fa.window_grid(rows, window, *shape) == ("causal", 100.0)


def test_band_visits_the_share_of_the_causal_grid_it_says():
    """``window_blocks_pct``'s arithmetic: 16,384 rows under a window of 128 at
    (512, 128) blocks are 32 query blocks of 5 key blocks each but the first
    (4), against 4 + 8 + ... + 128 at or under the diagonal."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    form, pct = fa.window_grid(16384, 128.0, 64, 128, 128, 2, 512, 128)
    assert form == "band" and pct == pytest.approx(100 * (32 * 5 - 1) / (4 * 32 * 33 / 2))


@pytest.mark.parametrize("window", [128.0, "traced"])
def test_sharded_wrapper_keeps_a_static_window_static(window):
    """On a mesh of several devices the call is wrapped in ``shard_map``: a
    static window is closed over (the banded forward inside, no window operand),
    a traced one stays the operand it was; outputs equal the unsharded call's."""
    from deepspeed_tpu.comm.mesh import MeshConfig, build_mesh
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention_sharded

    mesh = build_mesh(MeshConfig(data=2, fsdp=2, model=2))
    q, k, v = _qkv(B=4, S=512)
    if window == "traced":
        fn = jax.jit(lambda q, k, v, w: flash_attention_sharded(q, k, v, mesh=mesh, window=w))
        args = (q, k, v, jnp.float32(128))
    else:
        fn = jax.jit(lambda q, k, v: flash_attention_sharded(q, k, v, mesh=mesh, window=window))
        args = (q, k, v)
    (name, (grid, _, operands)), = _pallas_calls(fn, *args).items()
    if window == "traced":  # the whole grid (512 rows: one key block), the window an operand
        assert (name, grid[2], operands) == ("flash_fwd", 1, 4)
    else:  # per device 2 batch rows x 2 heads; no dimension over key blocks, no window operand
        assert name == "flash_fwd_band" and len(grid) == 2 and operands % 2 == 1
    ref = flash_attention(q, k, v, window=128.0)
    np.testing.assert_allclose(np.asarray(fn(*args)), np.asarray(ref), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# The causal schedule inside a grid step (PR 51): a step runs the key sub-tiles
# of its block that hold a key at or under its last row, and a step that runs
# nothing fetches nothing
# ---------------------------------------------------------------------------

def _dense(q, k, v, causal=True, slopes=None, window=None):
    """[B, S, H, D] attention in float32 with every score written out: causal,
    alibi (slope_h * (k_pos - q_pos)) and a window (a query sees the keys
    q - window + 1 .. q) as masks and biases on the [S, S] matrix."""
    S, Sk = q.shape[1], k.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") / np.sqrt(q.shape[-1])
    dist = jnp.arange(S)[:, None] - jnp.arange(Sk)[None, :]  # q_pos - k_pos
    if slopes is not None:
        s = s - slopes[None, :, None, None] * dist[None, None]
    seen = jnp.ones((S, Sk), bool)
    if causal:
        seen &= dist >= 0
    if window is not None:
        seen &= dist < window
    s = jnp.where(seen[None, None], s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, precision="highest")


# rows, (block_q, block_k) handed to the call (None: its own), and what the case is for. Since
# PR 65 a query block of 512 rows or more cuts the diagonal's tile in 256 x 256 sub-tiles
# (``diag_sub`` 256): in the backward always, in the forward where the key block holds four
# query blocks; the 128- and 256-row blocks of the last cases run it whole (0)
SCHEDULE_CASES = {
    "key-block-4x-query-block": (2048, (None, None)),    # 512 x 2048: the train cell's grid
    "key-block-2x-query-block": (2048, (512, 1024)),     # PR 50's blocks: skipped steps
    "two-key-blocks-of-2048": (4096, (None, None)),
    "key-block-1x-query-block": (1536, (None, None)),    # 512 x 512: a sub-tile is the block
    "one-block": (512, (None, None)),                    # a grid of one block
    "one-key-block": (1024, (None, None)),               # 512 x 1024 in a (2, 1) grid
    "padded-to-2048": (2000, (None, None)),              # rows not a multiple of the block
    "padded-to-1152": (1100, (None, None)),              # 128 x 128 blocks after padding
    "query-block-2x-sub-tile": (2048, (1024, 1024)),     # two row sub-tiles a step
    "odd-block": (1536, (768, 768)),                     # sub-tiles of gcd(768, 512) = 256
    "small-blocks": (512, (128, 256)),
}


def _schedule_call(rows, blocks, **kw):
    bq, bk = blocks
    return lambda q, k, v: flash_attention(q, k, v, block_q=bq, block_k=bk, **kw)


BACKWARD_KERNELS = {"fused": {"flash_bwd"}, "split": {"flash_bwd_dkdv", "flash_bwd_dq"}}


@pytest.fixture(params=["fused", "split"])
def form(request, monkeypatch):
    """The backward's two forms (PR 64), each forced for a test at shapes whose
    own rule (``backward_form``) would take the fused one: the budget of the
    rule is the one thing that chooses."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    monkeypatch.setattr(fa, "FUSED_VMEM_BYTES", {"fused": 1 << 40, "split": 0}[request.param])
    return request.param


def _backward_kernels(loss, *args):
    return {name for name in _pallas_calls(jax.grad(loss, argnums=(0, 1, 2)), *args)
            if name.startswith("flash_bwd")}


@pytest.mark.parametrize("case", SCHEDULE_CASES)
def test_causal_schedule_forward_matches_the_dense_reference(case):
    rows, blocks = SCHEDULE_CASES[case]
    q, k, v = _qkv(B=1, S=rows, H=2, D=32, seed=rows)
    out = _schedule_call(rows, blocks)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_dense(q, k, v)), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("case", SCHEDULE_CASES)
def test_causal_schedule_gradients_match_the_dense_reference(case, form):
    """Under the one kernel (dQ of the head held in VMEM over its key blocks: one
    block, several, rows that no power of two over 512 divides, padded rows) and
    under the pair."""
    rows, blocks = SCHEDULE_CASES[case]
    q, k, v = _qkv(B=1, S=rows, H=1, D=32, seed=rows + 1)
    tgt = jax.random.normal(jax.random.PRNGKey(rows), q.shape)
    attend = _schedule_call(rows, blocks)
    assert _backward_kernels(lambda *a: jnp.sum(attend(*a) * tgt), q, k, v) == BACKWARD_KERNELS[form]
    gf = jax.grad(lambda *a: jnp.sum(attend(*a) * tgt), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(_dense(*a) * tgt), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5,
                                   err_msg=f"d{name}")


def _dense_lse(q, k):
    """[B, S, H, D] -> [B * H, S]: each causal row's logsumexp in float32, what
    the forward hands the backward (the first lane of the lane-broadcast array
    it wrote before PR 65)."""
    S = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32),
                   precision="highest") / np.sqrt(q.shape[-1])
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None], s, -jnp.inf)
    return jax.scipy.special.logsumexp(s, axis=-1).reshape(-1, S)


# rows, dtype, the backward form forced (None: the rule's own, named beside it), diag_sub of
# the forward and of the backward
CELL_CLASS_CASES = {
    # the train cell's shape class: 2,048 rows of 128-wide heads in 512 x 2048 blocks
    "train-cell-bfloat16": (2048, jnp.bfloat16, None, "fused", (256, 256)),
    "train-cell-float32-one-kernel": (2048, jnp.float32, "fused", "fused", (256, 256)),
    "past-the-limit-float32-the-pair": (2048, jnp.float32, None, "split", (256, 256)),
    # 512 x 512 blocks: the store-bound forward runs the diagonal whole under a key block
    # of four query blocks, the backward cuts it
    "rows-no-power-of-two-divides": (1536, jnp.float32, None, "fused", (0, 256)),
    "whole-diagonal": (1280, jnp.float32, None, "fused", (0, 0)),            # 256 x 256 blocks
}


@pytest.mark.parametrize("case", CELL_CLASS_CASES)
def test_the_cut_diagonal_and_the_row_statistics_match_the_float32_reference(case, monkeypatch):
    """PR 65 at the train cell's shape class and on both sides of each rule: the
    diagonal's tile in 256 x 256 sub-tiles (``diag_sub`` 256) and whole (0, a
    256-row query block; in the forward a key block under four query blocks too),
    the one backward kernel and the pair past ``backward_form``'s limit, rows no
    power-of-two block over 512 divides. The forward, the logsumexp it hands on
    (ONE float32 a row, ``[BH, 1, Sq]``: what the first lane of the old
    ``[BH, Sq, 128]`` array held; not written where nothing will read it, a
    serving prefill) and all three gradients against the float32 dense reference."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    rows, dtype, forced, form, sub = CELL_CLASS_CASES[case]
    if forced:
        monkeypatch.setattr(fa, "FUSED_VMEM_BYTES", {"fused": 1 << 40, "split": 0}[forced])
    q, k, v = _qkv(B=1, S=rows, H=1, D=128, dtype=dtype, seed=rows)
    tgt = jax.random.normal(jax.random.PRNGKey(rows), q.shape, dtype)
    wide = lambda *xs: tuple(x.astype(jnp.float32) for x in xs)
    item = jnp.dtype(dtype).itemsize
    assert (fa.diag_sub(rows, 128, item), fa.diag_sub(rows, 128, item, backward=True)) == sub
    assert fa.backward_form(rows, 128, 128, item) == form
    loss = lambda fn: lambda *a: jnp.sum((fn(*a) * tgt).astype(jnp.float32))
    assert _backward_kernels(loss(flash_attention), q, k, v) == BACKWARD_KERNELS[form]
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=5e-4, atol=5e-5)

    blocks = fa._outer_blocks(rows, rows, 128, item, None, None)
    bhsd = lambda x: x.transpose(0, 2, 1, 3).reshape(-1, rows, 128)
    forward = lambda **kw: fa._flash_forward(bhsd(q), bhsd(k), bhsd(v), None, None, 128 ** -0.5, True,
                                             *blocks, True, **kw)
    out, lse = forward()
    served, nothing = forward(with_lse=False)
    assert nothing is None and np.array_equal(np.asarray(served), np.asarray(out))
    assert lse.shape == (1, 1, rows) and lse.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(lse[:, 0]), np.asarray(_dense_lse(q, k)), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(out.astype(jnp.float32)),
                               np.asarray(bhsd(_dense(*wide(q, k, v)))), **tol)
    gf = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(_dense(*a) * tgt.astype(jnp.float32)),
                  argnums=(0, 1, 2))(*wide(q, k, v))
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a.astype(jnp.float32)), np.asarray(b),
                                   err_msg=f"d{name}", **tol)


@pytest.mark.parametrize("block_k", [None, 1024])
@pytest.mark.parametrize("kind", ["alibi", "traced-window", "traced-window-global",
                                  "alibi+traced-window", "static-window", "not-causal",
                                  "not-causal-window"])
def test_causal_schedule_under_alibi_and_windows(kind, block_k, form):
    """What a step cut to its keys has to keep, in the one backward kernel and in
    the pair: alibi's distances, counted from the block's own origin; a TRACED window (only the operand says where it
    lies; w <= 0 is global); a static window's backward, which runs the whole
    grid under the window; ``causal=False`` has no diagonal and runs its block
    whole. Forward and gradients at 2,048 rows, at the call's own blocks (512 x
    2048: steps of one to four sub-tiles) and at 512 x 1024 (skipped steps, whose
    index maps stay behind), against the dense reference."""
    S, H = 2048, 2
    q, k, v = _qkv(B=1, S=S, H=H, D=32, seed=11)
    tgt = jax.random.normal(jax.random.PRNGKey(12), q.shape)
    slopes = jnp.asarray([0.25, 0.0625], jnp.float32) if "alibi" in kind else None
    causal = not kind.startswith("not-causal")
    window = {"traced-window": 300.0, "alibi+traced-window": 300.0, "traced-window-global": -1.0,
              "static-window": 300, "not-causal-window": 300.0}.get(kind)
    traced = window is not None and kind != "static-window"
    dense_window = window if window and window > 0 else None

    def flash(q, k, v, w):
        return flash_attention(q, k, v, causal=causal, alibi_slopes=slopes,
                               window=w if traced else window, block_k=block_k)

    def loss(fn):
        return lambda q, k, v, w: jnp.sum(fn(q, k, v, w) * tgt)

    ref = lambda q, k, v, w: _dense(q, k, v, causal, slopes, dense_window)
    w = jnp.float32(window if traced else 0.0)
    out, gf = jax.value_and_grad(loss(flash), argnums=(0, 1, 2))(q, k, v, w)
    want, gr = jax.value_and_grad(loss(ref), argnums=(0, 1, 2))(q, k, v, w)
    np.testing.assert_allclose(np.asarray(flash(q, k, v, w)), np.asarray(ref(q, k, v, w)),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(float(out), float(want), rtol=1e-4)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("rows", [1024, 2048])
def test_causal_schedule_with_narrower_value_heads(rows, form):
    """Latent attention's shape: value heads narrower than the q/k heads, forward
    and (since PR 64: dV and dO have the value heads' width, dQ and dK the q/k
    heads') the gradients of both backward forms."""
    ks = jax.random.split(jax.random.PRNGKey(rows), 4)
    q, k = (jax.random.normal(kk, (1, rows, 2, 48)) * 0.5 for kk in ks[:2])
    v = jax.random.normal(ks[2], (1, rows, 2, 32)) * 0.5
    tgt = jax.random.normal(ks[3], v.shape)
    np.testing.assert_allclose(np.asarray(flash_attention(q, k, v)), np.asarray(_dense(q, k, v)),
                               rtol=2e-5, atol=2e-5)
    gf = jax.grad(lambda *a: jnp.sum(flash_attention(*a) * tgt), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(_dense(*a) * tgt), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5,
                                   err_msg=f"d{name}")


@pytest.fixture
def counted_tiles(monkeypatch):
    """Every block of scores the kernels RUN (interpret mode: the kernel's jaxpr
    is evaluated, so a callback in a case's body fires once an execution):
    ``[(rows, keys), ...]``."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    ran, scores = [], fa._block_scores

    def counting(q, k_blk, *a, **kw):
        jax.debug.callback(lambda: ran.append((q.shape[0], k_blk.shape[0])))
        return scores(q, k_blk, *a, **kw)

    monkeypatch.setattr(fa, "_block_scores", counting)
    return ran


@pytest.mark.parametrize("rows,pct,parent_pct", [
    (1024, 150.0, 200.0), (2048, 112.5, 150.0), (4096, 106.25, 125.0), (8192, 103.125, 112.5),
    (1536, 133.33, 133.33)])
def test_causal_tiles_pct_is_what_an_instrumented_run_counts(rows, pct, parent_pct, counted_tiles):
    """``causal_tiles_pct`` (the pure function the prefill span and the train
    step's ledger row quote) against the score elements an instrumented
    interpret run of the forward computes, as a % of those at or under the
    diagonal; ``parent_pct``: what whole blocks (the schedule before PR 51) ran
    there. Since PR 65 the diagonal's 512 x 512 tile is three 256 x 256 sub-tiles
    of its four where the key block holds four query blocks: 112.5% at 2,048 rows
    where PR 51 left 125; under that (1,024 and 1,536 rows) the forward runs it
    whole, as PR 51 left it."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    q, k, v = _qkv(B=1, S=rows, H=1, D=8, seed=rows)
    jax.block_until_ready(flash_attention(q, k, v))
    jax.effects_barrier()
    computed = sum(r * c for r, c in counted_tiles)
    tiles_pct = fa.causal_tiles_pct(rows, 8, 4)
    assert tiles_pct == pytest.approx(100 * computed / (rows * (rows + 1) / 2))
    assert tiles_pct == pytest.approx(pct, rel=2e-3)
    blocks = fa._auto_block(rows, 512), fa._auto_block(rows, 1024)  # PR 50's, run whole
    whole = sum(fa._diag(qi, *blocks) + 1 for qi in range(rows // blocks[0])) * blocks[0] * blocks[1]
    assert 100 * whole / (rows * (rows + 1) / 2) == pytest.approx(parent_pct, rel=2e-3)
    assert tiles_pct <= 100 * whole / (rows * (rows + 1) / 2)


@pytest.mark.parametrize("width,itemsize,block_k", [(128, 2, 2048), (256, 2, 2048), (128, 4, 2048),
                                                    (192, 4, 1024), (256, 4, 1024), (64, 2, 2048)])
def test_key_block_is_as_coarse_as_its_bytes_allow(width, itemsize, block_k):
    """The key block is 2,048 keys where one block of the widest head holds
    ``KEY_BLOCK_BYTES`` or less (what the chip's compiler took:
    tests/test_chip_compile_kernels.py), halved otherwise; the key sub-tiles of the
    causal schedule do not depend on it, and the forward cuts the diagonal's tile
    where it holds four query blocks (PR 65)."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    assert fa._key_block(8192, width, itemsize) == block_k
    assert fa._key_block(1536, width, itemsize) == 512 and fa._key_block(1024, width, itemsize) == 1024
    assert fa.causal_tiles_pct(8192, width, itemsize) == pytest.approx(
        103.125 if block_k == 2048 else 106.25, rel=2e-3)


@pytest.mark.parametrize("window", [None, 700.0], ids=["no-window", "traced-window"])
def test_backward_kernels_run_the_forward_s_tiles(window, counted_tiles, form):
    """The backward cuts its steps by the same rule: at 2,048 rows (one key
    block) a kernel runs the forward's 9 sub-tiles' worth of scores (10 before
    PR 65, 12 before PR 51) in four steps of two pieces each, all 512 rows
    against the keys the first 256 see and the last 256 rows against the 256
    keys more that they see; a traced window changes no step. The one kernel
    passes over the scores ONCE where dK/dV and dQ each do (PR 64: the forward's
    tiles twice in all, not three times)."""
    q, k, v = _qkv(B=1, S=2048, H=1, D=8, seed=2)
    w = None if window is None else jnp.float32(window)
    jax.block_until_ready(jax.grad(lambda q, k, v: jnp.sum(flash_attention(q, k, v, window=w)),
                                   argnums=(0, 1, 2))(q, k, v))
    jax.effects_barrier()
    passes = {"fused": 2, "split": 3}[form]
    assert sorted(counted_tiles) == sorted(passes * (
        [(512, 256), (512, 768), (512, 1280), (512, 1792)] + 4 * [(256, 256)]))


def STAIRS(ahead, rows=512):
    """A diagonal step's pieces at 256-row sub-tiles, ``ahead`` keys past its key block's first."""
    return tuple((row0, ahead + row0 if row0 else 0, ahead + row0 + 256)
                 for row0 in range(0, rows, 256))


@pytest.mark.parametrize("blocks,cases", [
    ((512, 1024), [(0, 0, STAIRS(0)), (512, 512, STAIRS(512)), (1024, 7680, ((0, 0, 1024),))]),
    ((512, 512), [(0, 0, STAIRS(0)), (512, 3584, ((0, 0, 512),))]),
    ((1024, 1024), [(0, 0, STAIRS(0, 1024)), (1024, 7168, ((0, 0, 1024),))]),
    ((768, 768), [(0, 0, STAIRS(0, 768)), (768, 5376, ((0, 0, 768),))]),
    ((128, 256), [(0, 1920, ((0, 0, 256),))]),
    ((256, 2048), [(0, 256, ((0, 0, 512),)), (512, 768, ((0, 0, 1024),)),
                   (1024, 1280, ((0, 0, 1536),)), (1536, 16128, ((0, 0, 2048),))]),
    ((1024, 2048), [(0, 0, STAIRS(0, 1024)), (1024, 1024, STAIRS(1024, 1024)),
                    (2048, 15360, ((0, 0, 2048),))]),
], ids=lambda x: "x".join(map(str, x)) if isinstance(x[0], int) else None)
def test_step_cases_are_every_case_a_grid_meets_and_no_other(blocks, cases):
    """``_step_cases``, the pieces of straight-line code a kernel holds, from
    the grid and the block shapes alone: [(lo, hi, pieces)], a case the steps
    whose first row lies lo .. hi keys past their key block's first. Against every
    (query block, key block) pair of a long grid counted by hand: each step that
    computes meets exactly one case; its pieces hold every key at or under each
    row, no 256 x 256 sub-tile wholly above the diagonal where the rows are cut
    (``_diag_cut``: 512-row blocks and over) and no 512-key sub-tile beyond the
    last row where they are not; a grid of ONE key block over 2,048 rows lists
    no block wholly under the diagonal; without a diagonal one case, the whole
    block."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    bq, bk = blocks
    cut = fa._diag_cut(bq, bk, backward=True)
    assert cut == (256 if bq >= 512 else 0)
    assert fa._diag_cut(bq, bk) == (cut if bk >= 4 * bq else 0)  # the forward's rule
    num_q, num_k = 8 * bk // bq, 8
    assert fa._step_cases(num_q, num_k, bq, bk, True, cut) == cases
    edge = cut or fa._sub_tile(bk)
    met = set()
    for qi in range(num_q):
        for kj in range(num_k):
            ahead = qi * bq - kj * bk
            mine = [pieces for lo, hi, pieces in cases if lo <= ahead <= hi]
            if kj * bk > qi * bq + bq - 1:  # the block lies above the diagonal
                assert not mine
                continue
            (pieces,) = mine
            met.add(pieces)
            seen = np.zeros((bq, bk), bool)
            for row0, key0, key1 in pieces:
                assert not seen[row0:, key0:key1].any()  # no score twice
                seen[row0:, key0:key1] = True
            under = np.arange(bq)[:, None] + ahead >= np.arange(bk)[None, :]
            assert seen[under].all()
            # what is computed beyond the triangle lies in sub-tiles the diagonal crosses
            for r in range(0, bq, cut or bq):
                for c in range(0, bk, edge):
                    tile = np.s_[r:r + (cut or bq), c:c + edge]
                    assert under[tile].any() or not seen[tile].any()
    assert met == {pieces for _, _, pieces in cases}
    assert fa._step_cases(num_q, num_k, bq, bk, False, cut) == [(None, None, ((0, 0, bk),))]
    if blocks == (512, 1024):  # one key block: the case wholly under the diagonal is not traced
        assert fa._step_cases(2, 1, bq, bk, True, cut) == cases[:2]


@pytest.mark.parametrize("blocks,rows", [((512, 1024), 2048), ((512, 512), 1536), ((1024, 512), 2048),
                                         ((128, 256), 512)])
def test_a_step_that_computes_nothing_fetches_nothing(blocks, rows):
    """The streamed operand's index map stays on the last block its row needs
    (forward, dQ: the diagonal's key block; dK/dV: the first query block with a
    row at or under the key block's first key), so a step strictly above the
    diagonal starts no copy; every block a step COMPUTES on is still the block
    of its own index."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    bq, bk = blocks
    num_q, num_k = rows // bq, rows // bk
    keys, qs = fa._streamed_keys(True, bq, bk), fa._streamed_rows(True, bq, bk, num_q)
    skipped = 0
    for qi in range(num_q):
        for kj in range(num_k):
            computes = kj * bk <= qi * bq + bq - 1
            got_k, got_q = int(keys(0, qi, kj)[1]), int(qs(0, kj, qi)[1])
            if computes:
                assert (got_k, got_q) == (kj, qi)
            else:
                skipped += 1
                assert got_k == fa._diag(qi, bq, bk) < kj and 0 <= got_q < num_q
                assert got_q * bq + bq - 1 >= kj * bk > (got_q - 1) * bq + bq - 1
    assert skipped == sum(kj * bk > qi * bq + bq - 1 for qi in range(num_q) for kj in range(num_k))
    assert fa._streamed_keys(False, bq, bk)(0, 0, num_k - 1) == (0, num_k - 1, 0)
    assert fa._streamed_rows(False, bq, bk, num_q)(0, num_k - 1, 0) == (0, 0, 0)


@pytest.mark.parametrize("rows,widths,itemsize,blocks,want", [
    (2048, (128, 128), 2, (None, None), "fused"),   # the train cell's heads: 12.5 of the 16 MiB
    (2048, (192, 128), 2, (None, None), "fused"),   # latent attention's
    (2048, (64, 64), 2, (None, None), "fused"),     # narrow heads are padded to the lanes
    (1536, (128, 128), 2, (None, None), "fused"),   # three key blocks of 512
    (2000, (128, 128), 2, (None, None), "fused"),   # padded to 2,048
    (4096, (128, 128), 2, (None, None), "fused"),   # the longest rows at 128-wide bfloat16 heads
    (4096, (128, 128), 2, (1024, 1024), "fused"),
    (4096, (192, 128), 2, (None, None), "split"),
    (6144, (128, 128), 2, (None, None), "fused"),   # PR 65: the O block and a row of lse for two lane-broadcast blocks
    (8192, (128, 128), 2, (None, None), "split"),   # dQ alone: 4 MiB of float32 and 4 written out
    (8192, (128, 128), 2, (128, 128), "fused"),     # a caller's small blocks leave dQ the room
    (2048, (128, 128), 4, (None, None), "split"),   # float32: the blocks are twice the bytes
    (2048, (256, 256), 2, (None, None), "split"),
    (1024, (256, 256), 4, (None, None), "split"),
])
def test_backward_form_is_read_from_the_shapes(rows, widths, itemsize, blocks, want):
    """``backward_form``: the one kernel where a head's dQ fits VMEM beside a
    step's blocks, the pair where it does not, from rows, widths, itemsize and
    blocks alone (tests/test_chip_compile_kernels.py compiles both sides of the
    line for the chip); and the backward a call TRACES is the form it names."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    d, dv = widths
    assert fa.backward_form(rows, d, dv, itemsize, *blocks) == want
    dtype = {2: jnp.bfloat16, 4: jnp.float32}[itemsize]
    qk, v = jax.ShapeDtypeStruct((1, rows, 1, d), dtype), jax.ShapeDtypeStruct((1, rows, 1, dv), dtype)
    loss = lambda q, k, v: jnp.sum(flash_attention(q, k, v, block_q=blocks[0], block_k=blocks[1]))
    assert _backward_kernels(loss, qk, qk, v) == BACKWARD_KERNELS[want]


@pytest.mark.parametrize("attn,blocks,pct", [("flash", (0, 0), 199.22), ("flash", (128, 128), 149.42),
                                             ("xla", (0, 0), None)])
def test_train_step_ledger_row_says_what_the_causal_kernels_compute(attn, blocks, pct):
    """The schedule is a constant of the trace: as the train step is traced the
    engine annotates its program-ledger row with ``causal_tiles_pct`` at the
    micro-batch's sequence length and the configured OUTER blocks (256 rows: one
    block computes the square, 128 x 128 blocks three of four); a model that does
    not attend through the kernels says nothing."""
    import deepspeed_tpu
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    cfg = TransformerConfig(vocab_size=128, max_seq_len=256, num_layers=1, num_heads=2,
                            hidden_size=32, dtype=jnp.float32, attn_impl=attn,
                            flash_block_q=blocks[0], flash_block_k=blocks[1])
    engine, _, _, _ = deepspeed_tpu.initialize(model=Model(cfg), config={
        "train_batch_size": 8, "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}})
    toks = np.random.default_rng(0).integers(0, 128, size=(8, 257)).astype(np.int32)
    engine.train_batch({"tokens": toks})
    row = next(r for r in engine.telemetry_snapshot()["program_ledger"]
               if r["name"].startswith("train/train_step"))
    assert row.get("causal_tiles_pct") == pct
    assert row.get("flash_bwd_form") == ("fused" if pct else None)  # PR 64: which backward it traced
    # PR 65: 256-row blocks run the diagonal whole, in the forward and in the backward
    assert (row.get("diag_sub"), row.get("flash_bwd_diag_sub")) == ((0, 0) if pct else (None, None))
    if pct:
        assert pct == round(fa.causal_tiles_pct(256, 16, 4, blocks[0] or None, blocks[1] or None), 2)
