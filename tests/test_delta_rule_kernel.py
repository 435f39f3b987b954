"""The gated delta rule's block form as a Pallas kernel (PR 55), through the
interpreter on the CPU at 128-wide heads: against ``_delta_chunks``' XLA form (the
definition), the rule that picks one or the other from the call, and the engine twin
serving the reference's tokens with the kernel forced."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from qwen3_next_cases import (  # noqa: F401,I001 -- first: puts the repo's root on sys.path; fixtures
    WHOLE, TOL, _config, _tokens)

from chipbench.drivers import serve_delta  # noqa: E402
from chipbench.references import Program, load_reference, program_of  # noqa: E402
from deepspeed_tpu.comm.mesh import build_mesh  # noqa: E402
from deepspeed_tpu.inference import engine as inference_engine  # noqa: E402
from deepspeed_tpu.inference.serving import Request  # noqa: E402
from deepspeed_tpu.launcher.serving_worker import build_serving_engine  # noqa: E402
from deepspeed_tpu.models import transformer as tfm  # noqa: E402
from deepspeed_tpu.ops.pallas import delta_rule  # noqa: E402
from deepspeed_tpu.telemetry import tracing  # noqa: E402

D = 128  # the published head width, and the narrowest the kernel tiles


def _operands(T, Hk, r, dtype=jnp.float32, drawn=True, rate=0.3, seed=0):
    """q, k unit rows (q scaled), v, g = -rate * U(0, 1) a row, beta in (0, 1), S0
    drawn or zero: what ``_gated_delta`` hands the block form."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (1, T, Hk, D))) * D ** -0.5
    k = unit(jax.random.normal(ks[1], (1, T, Hk, D)))
    v = jax.random.normal(ks[2], (1, T, Hk * r, D))
    g = -rate * jax.random.uniform(ks[3], (1, T, Hk * r))
    beta = jax.random.uniform(ks[4], (1, T, Hk * r))
    S0 = 0.1 * jax.random.normal(ks[5], (1, Hk * r, D, D)) * float(drawn)
    return tuple(x.astype(dtype) for x in (q, k, v)) + (g, beta, S0)


def _far(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("T,Hk,r,dtype,drawn,tol", [
    (256, 2, 2, jnp.float32, False, 1e-5),   # four chunks a grid step, two value heads a key head, from nothing
    (100, 1, 1, jnp.float32, True, 1e-5),    # a chunk and a part (two a step), one value head, a state given
    (192, 2, 2, jnp.bfloat16, True, 5e-3),   # the cell's compute dtype, float32 accumulation; one chunk a step
], ids=["whole_chunks_r2_zero", "part_chunk_r1_drawn", "bfloat16_r2_drawn"])
def test_the_kernel_is_the_xla_form(T, Hk, r, dtype, drawn, tol):
    args = _operands(T, Hk, r, dtype, drawn)
    o, S = tfm._delta_chunks(*args)
    o_k, S_k = tfm._delta_chunks(*args, form="kernel")
    assert o_k.shape == o.shape == (1, T, Hk * r, D) and o_k.dtype == S_k.dtype == jnp.float32
    assert float(jnp.abs(o).max()) > 1e-2 and float(jnp.abs(S).max()) > 1e-1
    assert _far(o_k, o) <= tol and _far(S_k, S) <= tol


def test_a_fast_forgetting_head_is_the_xla_forms():
    """|c| in the hundreds inside a chunk (g to -8 a row): each pair's decay is
    ``exp`` of the pair's OWN sum and the decay to the chunk's end is summed from the
    end, in the kernel as in the XLA form, so neither subtracts two large sums."""
    args = _operands(128, 1, 2, rate=8.0, seed=3)
    assert float(jnp.min(jnp.sum(args[3][0, :64], axis=0))) < -200
    o, S = tfm._delta_chunks(*args)
    o_k, S_k = tfm._delta_chunks(*args, form="kernel")
    assert bool(jnp.isfinite(o_k).all()) and _far(o_k, o) <= 1e-5 and _far(S_k, S) <= 1e-5


def test_a_padded_tail_leaves_the_state_of_the_live_rows_bit_for_bit():
    """100 live rows in a 128-row block: g = beta = 0 on the padding passes the state
    through EXACTLY whatever q, k, v hold there, and the live rows' outputs with it."""
    q, k, v, g, beta, S0 = _operands(128, 1, 2, seed=5)
    live = (jnp.arange(128) < 100)[None, :, None]
    g, beta = jnp.where(live, g, 0.0), jnp.where(live, beta, 0.0)
    o_noisy, S_noisy = tfm._delta_chunks(q, k, v, g, beta, S0, form="kernel")
    zeroed = [jnp.where(live[..., None], x, 0.0) for x in (q, k, v)]
    o_zero, S_zero = tfm._delta_chunks(*zeroed, g, beta, S0, form="kernel")
    np.testing.assert_array_equal(np.asarray(S_noisy), np.asarray(S_zero))
    np.testing.assert_array_equal(np.asarray(o_noisy[:, :100]), np.asarray(o_zero[:, :100]))
    # the block form's own padding of 100 rows to two chunks is the same padding
    o_cut, S_cut = tfm._delta_chunks(*(x[:, :100] for x in (q, k, v, g, beta)), S0, form="kernel")
    np.testing.assert_array_equal(np.asarray(S_cut), np.asarray(S_zero))
    np.testing.assert_array_equal(np.asarray(o_cut), np.asarray(o_zero[:, :100]))


def test_one_block_and_two_blocks_of_the_same_rows_leave_the_same_state():
    """150 rows in ONE call and in TWO (100 rows, then 50 from the carried state: the
    second enters mid-chunk of the first's chunking), under PR 52's bound of 4.9e-5."""
    q, k, v, g, beta, S0 = _operands(150, 2, 2, rate=2.0, seed=7)
    o, S = tfm._delta_chunks(q, k, v, g, beta, S0, form="kernel")
    first = tfm._delta_chunks(*(x[:, :100] for x in (q, k, v, g, beta)), S0, form="kernel")
    second = tfm._delta_chunks(*(x[:, 100:] for x in (q, k, v, g, beta)), first[1], form="kernel")
    assert _far(jnp.concatenate([first[0], second[0]], axis=1), o) <= 4.9e-5
    assert _far(second[1], S) <= 4.9e-5


def test_the_kernels_inverse_is_exact():
    """The kernel's own ``(I + A)^-1`` (plain ``jnp`` on [64, 64] matrices held twice,
    two of them through the factors together) against float64, to the bound
    ``_unit_lower_inverse`` of the XLA form is held to; every product in it is six
    bfloat16 partial products in ONE accumulation (``_full_precision``), which agrees
    with float64 as a float32 matmul at ``Precision.HIGHEST`` does."""
    twice = lambda x: jnp.concatenate([x, x], axis=1)
    As = [0.3 * jnp.tril(jax.random.normal(jax.random.PRNGKey(h), (64, 64)), -1) for h in (0, 1)]
    for A, got in zip(As, delta_rule._unit_lower_inverses([twice(A) for A in As])):
        want = np.linalg.inv(np.eye(64) + np.asarray(A, np.float64))
        np.testing.assert_array_equal(np.asarray(got[:, :64]), np.asarray(got[:, 64:]))
        assert np.abs(np.asarray(got[:, :64], np.float64) - want).max() / np.abs(want).max() <= 1e-5
    a, b = jax.random.normal(jax.random.PRNGKey(7), (2, 64, 64))
    exact = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    ours = np.asarray(delta_rule._full_precision(delta_rule._pieces(twice(a)),
                                                 delta_rule._pieces(twice(b))))[:, :64]
    highest = np.asarray(jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST))
    assert np.abs(ours - exact).max() <= 2 * np.abs(highest - exact).max() + 1e-6
    assert np.abs(ours - exact).max() / np.abs(exact).max() <= 2e-6
    one_pass = np.asarray(a.astype(jnp.bfloat16).astype(jnp.float32) @ b.astype(jnp.bfloat16).astype(jnp.float32))
    assert np.abs(one_pass - exact).max() / np.abs(exact).max() > 1e-3  # what one bfloat16 pass reads


def test_rows_or_heads_the_kernel_does_not_tile_are_refused():
    q, k, v, g, beta, S0 = _operands(100, 1, 1)
    with pytest.raises(ValueError, match="whole chunks"):
        delta_rule.delta_chunks(q, k, v, g, beta, S0, 64, interpret=True)
    narrow = [x[..., :16] for x in (q, k, v)] + [g, beta, S0[..., :16, :16]]
    with pytest.raises(ValueError, match="whole chunks"):
        delta_rule.delta_chunks(*(x[:, :64] for x in narrow[:5]), narrow[5], 64, interpret=True)


def _one_device():
    return build_mesh(devices=jax.devices()[:1])


@pytest.mark.parametrize("platform,state,width,rows,devices,want", [
    ("tpu", True, 128, 8192, 1, "kernel"),   # a serving program on the chip at the published width
    ("tpu", True, 256, 64, 1, "kernel"),     # any whole number of lane tiles, any block
    ("cpu", True, 128, 8192, 1, "xla"),      # the CPU would pay the interpreter
    ("tpu", False, 128, 8192, 1, "xla"),     # ``apply`` / the loss: a backward pass may follow
    ("tpu", True, 16, 8192, 1, "xla"),       # the rehearsal twin's heads: not a lane tile
    ("tpu", True, 192, 8192, 1, "xla"),      # a tile and a half
    ("tpu", True, 128, 1, 1, "xla"),         # one row is the recurrence itself
    ("tpu", True, 128, 8192, 8, "xla"),      # a mesh of several devices cannot split the kernel
])
def test_the_rule_picks_the_form_from_the_call(monkeypatch, platform, state, width, rows, devices,
                                               want):
    program = {**program_of(_config(), serve_delta.TWIN), "delta_head_dim": width}
    cfg = tfm.TransformerConfig(dtype=jnp.bfloat16, **program)
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    mesh = build_mesh(devices=jax.devices()[:devices])
    cache = {tfm.STATE: {}} if state else None
    assert tfm.delta_block_form(cfg, rows, cache, mesh) == want
    if devices == 1:  # no mesh at all is one device
        monkeypatch.setattr(tfm, "_ACTIVE_MESH", [None])
        assert tfm.delta_block_form(cfg, rows, cache) == want


# -- the engine twin at the kernel's head width -----------------------------------------------------


@pytest.fixture
def kernel_on_cpu(monkeypatch):
    """``delta_block_form`` takes the kernel on the ``tpu`` platform alone, on one
    device, and the kernel compiles there; steer all three, in the test, so that the
    engine's programs run the kernel through the interpreter."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(delta_rule, "interpret_default", lambda: True)
    monkeypatch.setattr(inference_engine, "build_mesh", lambda config: _one_device())


def _wide_twin():
    """The delta twin cut to one period (D D D A) with heads of 128."""
    program = program_of(_config(), serve_delta.TWIN)
    return Program({**program, "num_layers": 4, "delta_head_dim": D,
                    "layer_operators": program["layer_operators"][:4]}, program.reference)


def _serve(program, prompts, **serving):
    srv = build_serving_engine({
        "model": {**program, "dtype": "float32"}, "engine_dtype": "fp32",
        "serving": {"n_slots": 2, "max_seq_len": 256, "seed": 0, "watchdog_mode": "off",
                    **serving}})
    t0 = time.perf_counter()
    results = srv.serve([Request(uid=i, prompt=p, max_new_tokens=6) for i, p in enumerate(prompts)])
    return srv, results, tracing.spans(t0)


@pytest.mark.parametrize("chunked", [False, True], ids=["prefill", "chunk"])
def test_the_engine_serves_the_references_tokens_through_the_kernel(kernel_on_cpu, chunked):
    """Three requests on two slots (a prompt that pads its bucket, one of two rows, one
    that reuses a slot), prefilled whole or in chunks of 32 rows (half a chunk of
    the rule's: every second one enters mid-way): every token lies at the reference's top
    logit, and every ``prefill`` / ``chunk`` span says the kernel ran."""
    program = _wide_twin()
    reference = load_reference(program)
    cfg = tfm.TransformerConfig(dtype=jnp.float32, **program)
    prompts = [_tokens(cfg, (n,), n) for n in (70, 2, 100)]  # buckets 128, 16, 128
    serving = {"chunked_prefill": {"enabled": True, "chunk_size": 32}} if chunked else {}
    srv, results, spans = _serve(program, prompts, **serving)
    for i, p in enumerate(prompts):
        got = np.asarray(results[i].tokens)
        assert results[i].status == "ok" and len(got) == 6
        ref = reference.logits_at(program, srv.engine.params, np.concatenate([p, got[:-1]]),
                                  np.arange(len(p) - 1, len(p) + 5), fetch=WHOLE)
        assert float(np.max(ref.max(axis=-1) - ref[np.arange(6), got])) <= TOL, i
    blocks = [sp for sp in spans if sp.name in ("prefill", "chunk")]
    assert {sp.name for sp in blocks} == {"chunk" if chunked else "prefill"}
    for sp in blocks:  # beside what the MFU reader needs, not in its place
        assert sp.attrs["delta_block"] == "kernel" and sp.attrs["delta_layers"] == 3
        assert sp.attrs["scan_chunks"] >= 1 and sp.attrs["state_rows"] >= 1
    assert not any("delta_block" in sp.attrs for sp in spans if sp.name == "decode")


def test_apply_takes_the_xla_form_where_the_platform_would_grant_the_kernel(monkeypatch):
    """A traced ``apply`` of the wide twin (no state: a backward pass may follow) holds
    no kernel even on the ``tpu`` platform; ``apply_with_cache`` of the same model does.
    (A CPU engine's spans say ``xla``: ``tests/test_qwen3_next_engine.py``.)"""
    program = _wide_twin()
    cfg = tfm.TransformerConfig(dtype=jnp.float32, **program)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(tfm, "_ACTIVE_MESH", [None])
    params = jax.eval_shape(lambda: tfm.Model(cfg).init(jax.random.PRNGKey(0)))
    tokens = _tokens(cfg, (1, 128))
    assert "pallas_call" not in str(jax.make_jaxpr(lambda p: tfm.apply(cfg, p, tokens))(params))
    served = jax.make_jaxpr(lambda p: tfm.apply_with_cache(
        cfg, p, tokens, tfm.init_cache(cfg, 1, 128), 0)[0])(params)
    assert str(served).count("pallas_call") >= 1
