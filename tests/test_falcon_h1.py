"""Falcon-H1 on the normal path (PR 33): a Mamba-2 state-space mixer beside
grouped-query attention in every layer, a gated feed-forward, maximal-update
multipliers, and a slot cache that holds per-token K/V and per-sequence recurrent
state side by side — against the plain reference
``chipbench/references/falcon_h1.py`` (itself held to ``transformers``'
``FalconH1ForCausalLM``), at the configuration's recurrent rehearsal twin on the
CPU, seeded weights, float32 unless a test says bfloat16."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from falcon_h1_cases import (  # noqa: F401,I001 -- first: puts the repo's root on sys.path; fixtures
    WHOLE, TOL, SMAX, _config, program, reference, cfg, params)

import deepspeed_tpu  # noqa: E402
from chipbench import parity, ssm_cost  # noqa: E402
from chipbench.drivers import serve, serve_recurrent  # noqa: E402
from chipbench.references import load_reference, program_of  # noqa: E402
from deepspeed_tpu.models import transformer as tfm  # noqa: E402


def _tokens(cfg, shape, seed):
    return np.random.default_rng(seed).integers(0, cfg.vocab_size, size=shape).astype(np.int32)


def _bucket(n):
    return min(max(16, 1 << (n - 1).bit_length()), SMAX)  # ``ServingEngine._bucket_len``


def _prefill(cfg, params, cache, prompt, slot):
    """What ``SlotWorker._build_prefill`` does: the prompt padded to its bucket,
    prefilled with the live-row mask into a local cache, written into ``slot``."""
    bucket = _bucket(len(prompt))
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :len(prompt)] = prompt
    local = tfm.init_cache(cfg, 1, bucket)
    logits, local = tfm.apply_with_cache(
        cfg, params, jnp.asarray(padded), local, 0, last_index=len(prompt) - 1,
        live=jnp.arange(bucket)[None, :] < len(prompt))
    return logits[0, 0], tfm.update_cache_slot(cache, local, slot)


def _decode(cfg, params, cache, tokens, pos, active):
    """What ``SlotWorker._build_decode`` does: one token a slot, inactive rows
    writing at Smax (dropped) and attending at 0."""
    active = jnp.asarray(active)
    at = jnp.where(active, jnp.asarray(pos, jnp.int32), 0)
    wpos = jnp.where(active, jnp.asarray(pos, jnp.int32), tfm.cache_len(cache))
    logits, cache = tfm.apply_with_cache(cfg, params, jnp.asarray(tokens)[:, None], cache, at,
                                         write_pos=wpos, live=active[:, None])
    return logits[:, 0], cache


_decode_jit = jax.jit(_decode, static_argnums=0)


# -- the configuration ---------------------------------------------------------------------------


def test_the_published_configuration_builds_and_counts():
    config = _config()
    real = tfm.TransformerConfig(dtype=jnp.bfloat16, **config["program"])
    shapes = jax.eval_shape(lambda r: tfm.init(real, r), jax.random.PRNGKey(0))
    held = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    program = program_of(config)
    counts = load_reference(program).param_counts(program)
    assert held == counts["total"] == 4_394_354_048
    assert counts["matmul_attention_per_layer"] == 31_457_280
    # the cache: 4 K/V heads of 128 a token, a float32 [32, 128, 256] state and a 3 x 5120 tail
    assert tfm.cache_bytes_per_token(real) == ssm_cost.kv_bytes_per_token(program) == 2048
    assert tfm.cache_state_bytes(real) == 4_194_304 + 30_720
    assert ssm_cost.state_bytes_per_slot(program) == 16_900_096
    tfm._ACTIVE_MESH[0] = None  # an earlier test's engine: a tensor axis would keep the heads
    layout = tfm.cache_layout(real)
    assert layout["k"] == layout["v"] == (1, 512)  # grouped heads: a token's 4 x 128 as one row
    assert layout[tfm.STATE]["ssm"] == ((32, 128, 256), jnp.float32)
    cache = jax.eval_shape(lambda: tfm.init_cache(real, 2, 256))
    assert cache["k"].shape == (4, 2, 256, 1, 512) and cache["k"].dtype == jnp.bfloat16
    assert cache[tfm.STATE]["ssm"].shape == (4, 2, 32, 128, 256)
    assert cache[tfm.STATE]["ssm"].dtype == jnp.float32
    assert cache[tfm.STATE]["conv"].shape == (4, 2, 3, 5120)
    # every multiplier the source publishes is in the program as published
    for name, value in config["program"]["multipliers"].items():
        assert config[name] == value
    assert set(config["program"]["multipliers"]) == set(tfm.MULTIPLIERS)


def test_the_seeded_draw_compensates_the_multipliers(cfg, program):
    """Logits of standard deviation about 1 and K of the size of Q under the
    multipliers as stated (with 1 / sqrt(fan in) everywhere this twin's logits
    would have standard deviation 1/8 and its keys a quarter of the queries')."""
    params = tfm.init(cfg, jax.random.PRNGKey(5))
    logits = tfm.apply(cfg, params, _tokens(cfg, (2, 64), 1))
    assert 0.5 < float(jnp.std(logits)) < 2.0
    lay, m = params["layers"], program["multipliers"]
    k_to_q = float(jnp.std(lay["wk"]) / jnp.std(lay["wq"]))
    assert abs(k_to_q * m["key_multiplier"] - 1.0) < 0.1
    assert abs(float(jnp.std(params["lm_head"])) * m["lm_head_multiplier"]
               * np.sqrt(cfg.hidden_size) - 1.0) < 0.1
    a = -np.exp(np.asarray(lay["ssm_a_log"][0]))
    assert a.tolist() == [-1.0, -2.0, -3.0, -4.0]  # heads that remember, heads that forget
    steps = np.log1p(np.exp(np.asarray(lay["ssm_dt_bias"])))  # softplus: the drawn steps
    assert 1e-3 <= steps.min() and steps.max() <= 1e-1


_REFUSED = {
    "bidirectional": (dict(causal=False), "causal=False"),
    "post norm": (dict(norm_style="post"), "norm_style='post'"),
    "parallel residual": (dict(parallel_residual=True), "parallel_residual"),
    "local attention": (dict(local_attn_layers=(0, 1, 0), local_attn_window=8),
                        "local_attn_layers"),
    "int8 weights": (dict(weight_bits=8), "weight_bits"),
    "ring attention": (dict(attn_impl="ring"), "attn_impl='ring'"),
    "sparse attention": (dict(attn_impl="sparse"), "attn_impl='sparse'"),
    "flash training": (dict(attn_impl="flash"), "attn_impl='flash'"),
    "decode kernel with grouped heads": (dict(decode_attn="kernel"), "decode_attn='kernel'"),
    "biases": (dict(use_bias=True), "use_bias"),
    "a routed feed-forward": (dict(moe_every=1, moe_routing="dropless", num_experts=4,
                                   moe_top_k=2), "routed feed-forward"),
    "two head widths without a latent": (dict(v_head_dim=8), "without latent attention"),
}


@pytest.mark.parametrize("case", list(_REFUSED))
def test_combinations_without_code_are_refused_by_name(program, case):
    fields, named = _REFUSED[case]
    with pytest.raises(NotImplementedError, match=named):
        tfm.TransformerConfig(**{**program, **fields})


def test_a_padded_prefill_without_the_live_rows_is_refused(cfg, params):
    """``last_index`` says the block is padded past its live last token; with no
    ``live`` the recurrence would run on over the padding."""
    with pytest.raises(ValueError, match="needs `live`"):
        tfm.apply_with_cache(cfg, params, jnp.zeros((1, 16), jnp.int32),
                             tfm.init_cache(cfg, 1, 16), 0, last_index=4)


def test_pipeline_schedules_refuse_the_mixer(cfg):
    from deepspeed_tpu.pipe import PipelinedTransformer

    with pytest.raises(NotImplementedError, match="pipeline"):
        PipelinedTransformer(cfg, num_stages=3, num_micro_batches=1)


# -- the reference against the published code ----------------------------------------------------


def test_reference_agrees_with_transformers(program, reference, cfg, params):
    """``FalconH1ForCausalLM`` at the twin's size with this model's switches on
    the SAME seeded weights: its logits are the reference's, so the reference is
    the published forward pass (its scan is the recurrence, theirs chunked)."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    p = program
    hf = transformers.FalconH1Config(
        vocab_size=p["vocab_size"], hidden_size=p["hidden_size"],
        intermediate_size=p["intermediate_size"], num_hidden_layers=p["num_layers"],
        num_attention_heads=p["num_heads"], num_key_value_heads=p["num_kv_heads"],
        head_dim=p["qk_head_dim"], hidden_act="silu", rms_norm_eps=p["layernorm_epsilon"],
        tie_word_embeddings=False, rope_theta=p["rotary_base"], rope_scaling=None,
        max_position_embeddings=p["max_seq_len"], attention_bias=False, mlp_bias=False,
        projectors_bias=False, mamba_d_ssm=p["ssm_heads"] * p["ssm_head_dim"],
        mamba_n_heads=p["ssm_heads"], mamba_d_head=p["ssm_head_dim"],
        mamba_n_groups=p["ssm_groups"], mamba_d_state=p["ssm_state_size"],
        mamba_d_conv=p["ssm_conv_kernel"], mamba_chunk_size=16, mamba_conv_bias=True,
        mamba_proj_bias=False, mamba_norm_before_gate=False, mamba_rms_norm=True,
        pad_token_id=0, **p["multipliers"])
    hf._attn_implementation = "eager"
    model = transformers.FalconH1ForCausalLM(hf).eval()
    t = lambda x: torch.from_numpy(np.array(x, np.float32))  # noqa: E731
    lay, d = params["layers"], p["hidden_size"]
    state = {"model.embed_tokens.weight": t(params["wte"]),
             "model.final_layernorm.weight": t(params["lnf_scale"]),
             "lm_head.weight": t(params["lm_head"].T)}
    for i in range(p["num_layers"]):
        pre = f"model.layers.{i}."
        state.update({
            pre + "input_layernorm.weight": t(lay["ln1_scale"][i]),
            pre + "pre_ff_layernorm.weight": t(lay["ln2_scale"][i]),
            pre + "self_attn.q_proj.weight": t(lay["wq"][i].reshape(d, -1).T),
            pre + "self_attn.k_proj.weight": t(lay["wk"][i].reshape(d, -1).T),
            pre + "self_attn.v_proj.weight": t(lay["wv"][i].reshape(d, -1).T),
            pre + "self_attn.o_proj.weight": t(lay["wo"][i].reshape(-1, d).T),
            pre + "mamba.in_proj.weight": t(lay["ssm_in"][i].T),
            pre + "mamba.conv1d.weight": t(lay["ssm_conv"][i].T[:, None, :]),
            pre + "mamba.conv1d.bias": t(lay["ssm_conv_bias"][i]),
            pre + "mamba.dt_bias": t(lay["ssm_dt_bias"][i]),
            pre + "mamba.A_log": t(lay["ssm_a_log"][i]),
            pre + "mamba.D": t(lay["ssm_d"][i]),
            pre + "mamba.norm.weight": t(lay["ssm_norm_scale"][i]),
            pre + "mamba.out_proj.weight": t(lay["ssm_out"][i].T),
            pre + "feed_forward.gate_proj.weight": t(lay["wg"][i].T),
            pre + "feed_forward.up_proj.weight": t(lay["wi"][i].T),
            pre + "feed_forward.down_proj.weight": t(lay["wo_mlp"][i].T)})
    missing, unexpected = model.load_state_dict(state, strict=False)
    assert not unexpected and not missing, (missing, unexpected)
    tokens = _tokens(cfg, (60,), 9)
    with torch.no_grad():
        theirs = model(torch.from_numpy(tokens[None].astype(np.int64))).logits[0].numpy()
    ours = reference.logits_at(program, params, tokens, np.arange(60), fetch=WHOLE)
    assert float(np.max(np.abs(theirs - ours))) < TOL
    assert 0.5 < float(np.std(ours)) < 2.0


# -- the system against the reference: apply, the loss, its gradients ----------------------------


@pytest.mark.parametrize("rows", [128, 200, 7], ids=["one_chunk", "not_a_multiple", "short"])
def test_apply_matches_the_reference(cfg, params, program, reference, rows):
    """``apply``'s chunked scan (chunks of 128: one whole, one and a padded part,
    less than one) against the reference's recurrence over time."""
    tokens = _tokens(cfg, (2, rows), rows)
    got = np.asarray(tfm.apply(cfg, params, tokens))
    for row, mine in zip(tokens, got):
        want = reference.logits_at(program, params, row, np.arange(rows), fetch=WHOLE)
        assert float(np.max(np.abs(mine - want))) < TOL


def test_the_chunk_size_changes_nothing(cfg, params):
    tokens = _tokens(cfg, (1, 100), 4)
    a = tfm.apply(cfg, params, tokens)
    b = tfm.apply(cfg.replace(ssm_chunk_size=16), params, tokens)
    assert float(jnp.max(jnp.abs(a - b))) < TOL


def test_loss_and_its_gradients_match_the_reference(cfg, params, program, reference):
    """The loss to parity.py's tolerance; gradients against central differences
    of the REFERENCE's loss along a seeded direction mixed with the gradient's own."""
    batch = {"tokens": _tokens(cfg, (2, 49), 3)}
    loss, grads = jax.value_and_grad(lambda p: tfm.causal_lm_loss(cfg, p, batch))(params)
    ref_loss = reference.lm_loss(program, params, batch["tokens"], fetch=WHOLE)
    assert abs(float(loss) - ref_loss) <= parity.TOL["loss"]
    for name in ("ssm_in", "ssm_conv", "ssm_conv_bias", "ssm_a_log", "ssm_dt_bias", "ssm_d",
                 "ssm_norm_scale", "ssm_out", "wk", "wg"):
        leaf, g = params["layers"][name], grads["layers"][name]
        direction = jax.random.normal(jax.random.PRNGKey(len(name)), leaf.shape)
        direction = direction / jnp.linalg.norm(direction) + g / jnp.linalg.norm(g)
        direction = direction / jnp.linalg.norm(direction)

        def moved(eps):
            layers = {**params["layers"], name: leaf + eps * direction}
            return reference.lm_loss(program, {**params, "layers": layers}, batch["tokens"],
                                     fetch=WHOLE)

        want = (moved(2e-2) - moved(-2e-2)) / 4e-2
        got = float(jnp.sum(g * direction))
        assert abs(got - want) <= 0.05 * abs(want) + 5e-5, (name, got, want)
        assert abs(want) > 1e-4, (name, want)  # the direction moves the loss


def test_train_batch_takes_two_steps_under_zero1(program):
    model = tfm.Model(tfm.TransformerConfig(dtype=jnp.float32, **program))
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_batch_size": 8, "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": 1}, "mesh": {"data": -1}})
    batch = {"tokens": np.random.default_rng(0).integers(0, 768, size=(8, 33)).astype(np.int32)}
    losses = [float(engine.train_batch(batch)["loss"]) for _ in range(2)]
    assert np.isfinite(losses).all() and losses[1] < losses[0], losses
    assert engine._train_step._cache_size() == 1


# -- parity.py's three surfaces on the twin WITH the mixer ---------------------------------------


def _parity_error(program, reference, which, bf16=False):
    """``parity.error`` with ``serve_recurrent``'s probe where parity.py takes
    ``serve.py``'s (which drops the recurrent state: the configuration's notes)."""
    cfg = tfm.TransformerConfig(dtype=jnp.bfloat16 if bf16 else jnp.float32, **program)
    params = parity._seeded_params(tfm, cfg)
    rng = np.random.default_rng([parity.SEED, parity.CHECKS.index(which)])
    if which == "apply":
        tokens = rng.integers(0, cfg.vocab_size, size=(2, parity.SEQ)).astype(np.int32)
        got = np.asarray(tfm.apply(cfg, params, tokens), np.float32)
        want = np.stack(reference.logits_of(program, params, list(tokens),
                                            [np.arange(parity.SEQ)] * 2, fetch=WHOLE))
    elif which == "cache":
        prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
                   for n, _ in parity.PROMPTS]
        forced = rng.integers(0, cfg.vocab_size, size=(2, serve.DECODE_STEPS)).astype(np.int32)
        got = serve_recurrent.probe_logits(cfg, params, prompts,
                                           [b for _, b in parity.PROMPTS], forced)
        want = np.stack(reference.logits_of(
            program, params, [np.concatenate([p, f]) for p, f in zip(prompts, forced)],
            [np.arange(len(p) - 1, len(p) + serve.DECODE_STEPS) for p in prompts], fetch=WHOLE))
    else:
        tokens = rng.integers(0, cfg.vocab_size, size=(2, parity.SEQ + 1)).astype(np.int32)
        got = np.float32(tfm.causal_lm_loss(cfg, params, {"tokens": tokens}))
        want = np.float32(reference.lm_loss(program, params, tokens, fetch=WHOLE))
    return float(np.max(np.abs(got - want)))


@pytest.mark.parametrize("which", parity.CHECKS)
def test_the_recurrent_twin_holds_parity_and_bfloat16_fails_it(program, reference, which):
    """The three parity cases (tests/test_reference_parity.py counts those of the
    twin without the mixer) and their control: the tolerance that passes float32
    must catch bfloat16 compute."""
    assert _parity_error(program, reference, which) <= parity.TOL[which]
    assert _parity_error(program, reference, which, bf16=True) > 10 * parity.TOL[which]


# -- serving: bucketed prefill, then decode, through the slot cache ------------------------------


@pytest.mark.parametrize("reused", [False, True], ids=["fresh_slot", "reused_slot"])
@pytest.mark.parametrize("true_len", [1, 2, 3, 97, 127, 128, 129, 200])
def test_prefill_then_decode_equals_the_reference(cfg, params, program, reference, true_len,
                                                  reused):
    """A prompt padded to its bucket and prefilled into a slot, then decode
    steps through the slot cache, are the reference's full forward pass: the
    padded rows moved no state (a recurrence has no causality to hide behind),
    and the convolution's tail is that of the prompt's last rows (zeros where it
    is shorter than 3). ``reused``: the slot held another, longer request
    before, which leaves nothing behind. 64 decode steps on one length."""
    steps = 64 if true_len == 200 else 6
    seq = _tokens(cfg, (true_len + steps,), 11 * true_len)
    cache = tfm.init_cache(cfg, 3, SMAX)
    if reused:
        other = _tokens(cfg, (260,), 5)
        _, cache = _prefill(cfg, params, cache, other, 1)
        _, cache = _decode_jit(cfg, params, cache, np.array([0, 7, 0], np.int32),
                               np.array([0, 260, 0], np.int32), np.array([False, True, False]))
    first, cache = _prefill(cfg, params, cache, seq[:true_len], 1)
    got = [first]
    for i in range(steps):
        tokens = np.zeros((3,), np.int32)
        tokens[1] = seq[true_len + i]
        logits, cache = _decode_jit(cfg, params, cache, tokens,
                                    np.array([0, true_len + i, 0], np.int32),
                                    np.array([False, True, False]))
        got.append(logits[1])
    want = reference.logits_at(program, params, seq, np.arange(true_len - 1, true_len + steps),
                               fetch=WHOLE)
    assert float(np.max(np.abs(np.stack(got) - want))) < TOL


def test_a_decode_step_leaves_inactive_rows_state_bit_equal(cfg, params):
    """Inactive and prefilling rows ride along in every decode step: their K/V
    write is dropped, and their recurrent state and convolution tail must come
    out as they went in, bit for bit (dt = 0: exp(0) = 1 and 0 x anything = 0)."""
    cache = tfm.init_cache(cfg, 3, SMAX)
    for slot, n in enumerate((40, 97, 130)):
        _, cache = _prefill(cfg, params, cache, _tokens(cfg, (n,), slot), slot)
    before = jax.tree.map(np.asarray, cache[tfm.STATE])
    assert all(np.abs(leaf).max() > 0 for leaf in jax.tree.leaves(before))
    _, cache = _decode_jit(cfg, params, cache, np.array([3, 4, 5], np.int32),
                           np.array([40, 97, 130], np.int32), np.array([False, True, False]))
    after = jax.tree.map(np.asarray, cache[tfm.STATE])
    for name in before:
        for row in (0, 2):
            assert np.array_equal(before[name][:, row], after[name][:, row]), (name, row)
        assert not np.array_equal(before[name][:, 1], after[name][:, 1]), name


def test_grouped_attention_is_attention_on_repeated_keys_and_values():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 9, 10, 8)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(2, 12, 2, 8)), jnp.float32) for _ in range(2))
    pos = jnp.asarray([3, 0])
    got = tfm.xla_attention(q, k, v, causal_offset=pos)
    want = tfm.xla_attention(q, jnp.repeat(k, 5, axis=2), jnp.repeat(v, 5, axis=2),
                             causal_offset=pos)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-6
