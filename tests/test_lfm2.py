"""LFM2-24B-A2B on the normal path (PR 42): a gated short convolution in the
attention sublayer's place in five layers of the twin's seven (C A C C C A C), the
parameters in stacks BY OPERATOR, a slot cache that keeps K/V for the attention
layers alone and two rows of state a sequence for the others, a sigmoid router with
a selection bias and no shared expert behind one leading dense layer — against the
plain reference ``chipbench/references/lfm2_moe.py`` (itself held to
``transformers``' ``Lfm2ForCausalLM`` and ``DeepseekV3TopkRouter``), at the
configuration's rehearsal sizes on the CPU, seeded weights, float32 unless a test
says bfloat16."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lfm2_cases import (  # noqa: F401,I001 -- first: puts the repo's root on sys.path; fixtures
    WHOLE, TOL, _config, program, reference, cfg, params, _tokens, _PLANTED, _plant)

from chipbench import conv_cost, parity  # noqa: E402
from chipbench.drivers import serve_shortconv  # noqa: E402
from chipbench.references import Program, load_reference, program_of  # noqa: E402
from deepspeed_tpu.models import transformer as tfm  # noqa: E402


OPS = ["conv", "attn", "conv", "conv", "conv", "attn", "conv"]  # the twin's


# -- the layout -----------------------------------------------------------------------------------


def test_layout_is_stacks_by_operator_and_a_cache_by_layer_kind(cfg, params, program):
    assert program["layer_operators"] == OPS and cfg.conv_kernel == 3
    C, A = (0, True, "conv"), (0, True, "attn")  # (window, rotary, operator)
    assert cfg.layer_kinds == (C, A, C, C, C, A, C) and cfg.conv_layers == (0, 2, 3, 4, 6)
    assert tfm._index_in_kind(cfg) == (0, 0, 1, 2, 3, 1, 4)
    lay, moe = params["layers"], params["moe"]
    assert lay["ln1_scale"].shape == lay["ln2_scale"].shape == (7, 64)  # norms: every layer's
    assert {k: v.shape for k, v in lay["conv"].items()} == {
        "conv_in": (5, 64, 192), "conv_w": (5, 3, 64), "conv_out": (5, 64, 64)}
    assert {k: v.shape for k, v in lay["attn"].items()} == {
        "wq": (2, 64, 4, 64), "wk": (2, 64, 2, 64), "wv": (2, 64, 2, 64), "wo": (2, 4, 64, 64),
        "q_norm_scale": (2, 64), "k_norm_scale": (2, 64)}
    assert set(lay) == {"ln1_scale", "ln2_scale", "attn", "conv"}
    assert moe["gate"].shape == (6, 64, 16) and moe["bias"].shape == (6, 16)  # behind ONE lead
    assert "shared" not in moe and params["dense_ffn"]["wi"].shape == (1, 64, 160)
    assert "lm_head" not in params  # the head is the embedding's transpose
    # the cache: K/V of the attention layers alone, a token's heads side by side as one row
    # (2 x 64 = one whole lane row of a head narrower than the lanes); 2 rows of state a conv layer
    assert tfm.cache_heads_merged(cfg)
    cache = tfm.init_cache(cfg, 3, 256)
    assert jax.tree.map(lambda x: x.shape, cache) == {
        "k": (2, 3, 256, 1, 128), "v": (2, 3, 256, 1, 128), tfm.STATE: {"conv": (5, 3, 2, 64)}}
    assert tfm.cache_layers(cfg) == {"tokens": 2, tfm.RING: 0, tfm.STATE: 5}
    assert tfm.cache_bytes_per_token(cfg) == 2 * 2 * 64 * 4 and tfm.cache_state_bytes(cfg) == 512
    axes = jax.tree.structure(tfm.logical_axes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    assert axes == jax.tree.structure(params)
    held = tfm.hold_for_compute(cfg.replace(dtype=jnp.bfloat16), params)["layers"]
    assert {v.dtype for v in held["conv"].values()} == {jnp.dtype(jnp.bfloat16)}
    assert held["ln1_scale"].dtype == held["attn"]["q_norm_scale"].dtype == jnp.float32


def test_the_parent_keywords_are_new():
    """What ``TransformerConfig(**program)`` raised on before this PR: the keys."""
    new = {"layer_operators", "conv_kernel"}
    assert new <= set(program_of(_config())) and new <= set(tfm.TransformerConfig.__dataclass_fields__)
    assert new.isdisjoint(program_of(_config(), "rehearse_program"))  # parity.py's twin: none


def test_a_model_with_one_stack_keeps_its_heads_and_its_layout():
    """Heads of the lanes' width (every other configuration of the benchmark), the
    Pallas decode kernel, or heads that fill no whole lane row together: the cache
    is [L, B, Smax, heads, width] as it was."""
    base = dict(vocab_size=64, max_seq_len=64, num_layers=2, num_heads=4, hidden_size=256)
    for extra, merged in ((dict(decode_attn="xla"), True), (dict(decode_attn="kernel"), False),
                          (dict(decode_attn="xla", num_heads=2), False),  # heads of 128
                          (dict(decode_attn="xla", num_heads=8, qk_head_dim=24), False)):
        c = tfm.TransformerConfig(**{**base, **extra})
        assert tfm.cache_heads_merged(c) == merged, extra
        heads = (1, c.kv_heads * c.head_dim) if merged else (c.kv_heads, c.head_dim)
        assert tfm.init_cache(c, 1, 8)["k"].shape == (2, 1, 8) + heads


# -- the three surfaces against the reference -----------------------------------------------------


def test_apply_matches_the_reference_and_returns_its_choices(cfg, params, program, reference):
    tokens = _tokens(cfg, (2, 70))
    got, chosen = tfm.apply(cfg, params, tokens, return_routing=True)
    assert chosen.shape == (6, 2, 70, 4)
    for row in range(2):
        ref = reference.routed_pass(program, params, tokens[row], np.arange(70), fetch=WHOLE)
        assert np.std(ref["logits"]) > 0.1
        assert np.max(np.abs(np.asarray(got[row]) - ref["logits"])) <= TOL
        np.testing.assert_array_equal(np.sort(np.asarray(chosen)[:, row]), np.sort(ref["own"]))


def test_loss_matches_the_reference_and_has_a_gradient_in_every_stack(cfg, params, program,
                                                                      reference):
    tokens = _tokens(cfg, (2, 97), 4)
    loss, grads = jax.value_and_grad(lambda p: tfm.causal_lm_loss(cfg, p, {"tokens": tokens}))(
        params)
    assert abs(float(loss) - reference.lm_loss(program, params, tokens, fetch=WHOLE)) \
        <= parity.TOL["loss"]
    sizes = jax.tree.map(lambda g: float(jnp.abs(g).max()), grads)
    assert min(jax.tree.leaves(sizes["layers"])) > 0 and sizes["dense_ffn"]["wi"] > 0
    assert sizes["moe"]["bias"] == 0 and sizes["moe"]["gate"] > 0  # the bias selects only


# -- the controls: what the tolerance must catch --------------------------------------------------


def _errors(cfg, params, program, reference):
    """max |system - reference| on apply, the cache path and the loss."""
    tokens = _tokens(cfg, (60,), 6)
    ref = reference.logits_at(program, params, tokens, np.arange(60), fetch=WHOLE)
    apply_err = float(np.max(np.abs(np.asarray(tfm.apply(cfg, params, tokens[None]), np.float32)[0]
                                    - ref)))
    got, _ = serve_shortconv.probe_logits(cfg, params, [tokens[:50]], [64], tokens[None, 50:58])
    cache_err = float(np.max(np.abs(got[0] - ref[49:58])))
    batch = _tokens(cfg, (2, 97), 4)
    loss_err = abs(float(tfm.causal_lm_loss(cfg, params, {"tokens": batch}))
                   - reference.lm_loss(program, params, batch, fetch=WHOLE))
    return {"apply": apply_err, "cache": cache_err, "loss": loss_err}


@pytest.fixture(scope="module")
def bf16_errors(program, reference, params):
    return _errors(tfm.TransformerConfig(dtype=jnp.bfloat16, **program), params, program, reference)


@pytest.mark.parametrize("surface", ["apply", "cache", "loss"])
def test_bfloat16_compute_fails_the_float32_tolerance(bf16_errors, surface):
    assert bf16_errors[surface] > 5 * parity.TOL[surface], bf16_errors


def test_float32_passes_where_the_controls_fail(cfg, params, program, reference):
    errs = _errors(cfg, params, program, reference)
    assert all(errs[k] <= parity.TOL[k] for k in errs), errs


@pytest.mark.parametrize("fault", list(_PLANTED))
def test_a_planted_fault_fails_the_tolerance(cfg, params, program, reference, monkeypatch, fault):
    """Apply and the cache path both miss by far (a state from the padding: the
    cache path alone, ``apply`` pads nothing)."""
    _plant(monkeypatch, fault)
    errs = _errors(cfg, params, program, reference)
    assert errs["cache"] > 30 * TOL, errs
    assert (errs["apply"] > 30 * TOL) == (fault != "the state taken from the padding"), errs


# -- the reference against the published code -----------------------------------------------------


def test_reference_agrees_with_transformers_lfm2(program, reference, params):
    """``Lfm2ForCausalLM`` (the dense LFM2 that is installed: ``Lfm2ShortConv``,
    ``Lfm2Attention``, ``Lfm2DecoderLayer``, ``embedding_norm``, the tied head) on
    copied weights, the routed feed-forward swapped for the published gated MLP on
    both sides (``moe_first_dense`` = every layer on the reference's): the logits
    are the reference's."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")

    p, L, d, f = program, program["num_layers"], program["hidden_size"], 96
    dense = Program({**p, "moe_first_dense": L}, "lfm2_moe")
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    mlp = {"wg": jax.random.normal(keys[0], (L, d, f)) / 8, "wi": jax.random.normal(keys[1], (L, d, f)) / 8,
           "wo_mlp": jax.random.normal(keys[2], (L, f, d)) / 10}
    ours = {**{k: v for k, v in params.items() if k != "moe"}, "dense_ffn": mlp}
    config = transformers.Lfm2Config(
        vocab_size=p["vocab_size"], hidden_size=d, intermediate_size=f, num_hidden_layers=L,
        num_attention_heads=p["num_heads"], num_key_value_heads=p["num_kv_heads"],
        max_position_embeddings=p["max_seq_len"], norm_eps=p["layernorm_epsilon"],
        rope_theta=p["rotary_base"], conv_bias=False, conv_L_cache=p["conv_kernel"],
        block_auto_adjust_ff_dim=False, tie_word_embeddings=True,
        layer_types=["conv" if op == "conv" else "full_attention" for op in OPS])
    config.head_dim = p["qk_head_dim"]  # the twin states its head width; published: d // heads
    config._attn_implementation = "eager"
    model = transformers.Lfm2ForCausalLM(config).eval()
    t = lambda x: torch.from_numpy(np.array(x, np.float32))  # noqa: E731
    lay = params["layers"]
    state = {"model.embed_tokens.weight": t(params["wte"]), "lm_head.weight": t(params["wte"]),
             "model.embedding_norm.weight": t(params["lnf_scale"])}
    for i, op in enumerate(OPS):
        pre, at = f"model.layers.{i}.", OPS[:i].count(op)
        state.update({pre + "operator_norm.weight": t(lay["ln1_scale"][i]),
                      pre + "ffn_norm.weight": t(lay["ln2_scale"][i]),
                      pre + "feed_forward.w1.weight": t(mlp["wg"][i].T),
                      pre + "feed_forward.w3.weight": t(mlp["wi"][i].T),
                      pre + "feed_forward.w2.weight": t(mlp["wo_mlp"][i].T)})
        if op == "conv":
            c = {k: np.asarray(v[at]) for k, v in lay["conv"].items()}
            state.update({pre + "conv.in_proj.weight": t(c["conv_in"].T),
                          pre + "conv.out_proj.weight": t(c["conv_out"].T),
                          pre + "conv.conv.weight": t(c["conv_w"].T[:, None, :])})  # [d, 1, K]
        else:
            a = {k: np.asarray(v[at]) for k, v in lay["attn"].items()}
            state.update({pre + "self_attn.q_proj.weight": t(a["wq"].reshape(d, -1).T),
                          pre + "self_attn.k_proj.weight": t(a["wk"].reshape(d, -1).T),
                          pre + "self_attn.v_proj.weight": t(a["wv"].reshape(d, -1).T),
                          pre + "self_attn.out_proj.weight": t(a["wo"].reshape(-1, d).T),
                          pre + "self_attn.q_layernorm.weight": t(a["q_norm_scale"]),
                          pre + "self_attn.k_layernorm.weight": t(a["k_norm_scale"])})
    missing, unexpected = model.load_state_dict(state, strict=False)
    assert not unexpected and all("rotary" in k or "inv_freq" in k for k in missing), (
        missing, unexpected)
    tokens = _tokens(tfm.TransformerConfig(**p), (60,), 9)
    with torch.no_grad():
        theirs = model(torch.from_numpy(tokens[None].astype(np.int64))).logits[0].numpy()
    mine = reference.logits_at(dense, ours, tokens, np.arange(60), fetch=WHOLE)
    assert np.std(theirs) > 0.1 and np.max(np.abs(theirs - mine)) <= TOL


def test_reference_router_is_the_published_form(program, reference, params):
    """``DeepseekV3TopkRouter`` at one group (sigmoid scores, a bias that selects and
    does not weigh, the chosen scores renormalised and scaled by 1) on copied
    weights chooses the reference's experts and gives its weights (the published
    LFM2 denominator is + 1e-6 where this module's is + 1e-20: 5e-7 relative); and
    the weights are the form written out by hand."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    from transformers.models.deepseek_v3 import modeling_deepseek_v3 as hf

    p, r = program, 2
    config = transformers.DeepseekV3Config(
        hidden_size=p["hidden_size"], n_routed_experts=p["num_experts"], n_group=1, topk_group=1,
        routed_scaling_factor=p["moe_routed_scale"], num_experts_per_tok=p["moe_top_k"],
        norm_topk_prob=True)
    router = hf.DeepseekV3TopkRouter(config).eval()
    gate, bias = np.asarray(params["moe"]["gate"][r]), np.asarray(params["moe"]["bias"][r])
    router.load_state_dict({"weight": torch.from_numpy(gate.T.copy()),
                            "e_score_correction_bias": torch.from_numpy(bias.copy())})
    h = np.asarray(jax.random.normal(jax.random.PRNGKey(5), (80, p["hidden_size"])), np.float32)
    with torch.no_grad():
        experts, weights = router(torch.from_numpy(h.copy())[None])
    with jax.default_matmul_precision("highest"):
        mine = reference._route(p, jnp.asarray(h) @ jnp.asarray(gate), jnp.asarray(bias), None)
    np.testing.assert_array_equal(np.sort(experts.numpy()), np.sort(mine["own"]))
    mix = np.zeros((80, p["num_experts"]), np.float32)
    np.put_along_axis(mix, experts.numpy(), weights.numpy(), axis=-1)
    np.testing.assert_allclose(np.asarray(mine["mix"]), mix, atol=2e-6)
    s = 1 / (1 + np.exp(-(h.astype(np.float64) @ gate)))
    top = np.argsort(-(s + bias), axis=-1)[:, :4]
    np.testing.assert_array_equal(np.sort(top), np.sort(mine["own"]))
    chosen = np.take_along_axis(s, top, axis=-1)
    by_hand = np.zeros_like(s)
    np.put_along_axis(by_hand, top, chosen / (chosen.sum(-1, keepdims=True) + 1e-6), axis=-1)
    np.testing.assert_allclose(np.asarray(mine["mix"]), by_hand, atol=1e-6)
    assert abs(float(np.asarray(mine["mix"]).sum(-1).mean()) - 1) < 1e-5  # scale 1, no shared


def test_reference_attention_in_query_blocks_is_the_whole_matrix(program, reference, params):
    from unittest import mock

    tokens = np.random.default_rng(1).integers(0, program["vocab_size"], size=70)
    one = reference.logits_at(program, params, tokens, np.arange(70), fetch=WHOLE)
    reference._attend.clear_cache()
    with mock.patch.multiple(reference, QUERY_BLOCK=16, ROW_BLOCK=32, HEAD_BLOCK=100):
        many = reference.logits_at(program, params, tokens, np.arange(70), fetch=WHOLE)
    reference._attend.clear_cache()
    np.testing.assert_allclose(many, one, atol=2e-5)


@pytest.mark.parametrize("key,value", [("moe_shared_size", 32), ("tie_embeddings", False),
                                       ("local_attn_layers", [0] * 7), ("ssm_state_size", 16),
                                       ("moe_norm_topk_prob", False), ("qk_norm", True)])
def test_the_reference_refuses_what_it_does_not_implement(program, key, value):
    with pytest.raises(NotImplementedError, match=key):
        load_reference(Program({**program, key: value}, "lfm2_moe"))


# -- the shared filter: Falcon-H1's mixer through the function it became ---------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("masked", [False, True], ids=["all_live", "padded"])
def test_the_shared_filter_is_the_mixers_convolution_bit_for_bit(dtype, masked):
    """``_causal_filter`` / ``_filter_tail`` against the lines they replaced in
    ``_ssm_mixer`` (the parent's, written out), K = 4 taps as Falcon-H1's."""
    B_, T, K, C = 3, 9, 4, 32
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    tail = jax.random.normal(keys[0], (B_, K - 1, C)).astype(dtype)
    xBC = jax.random.normal(keys[1], (B_, T, C)).astype(dtype)
    leaf = jax.random.normal(keys[2], (K, C))
    live = (jnp.arange(T)[None, :] < jnp.asarray([9, 4, 0])[:, None]) if masked else None
    f32 = jnp.float32
    rows = jnp.concatenate([tail.astype(dtype), xBC], axis=1)
    taps = leaf.astype(dtype).astype(f32)
    conv = sum(rows[:, j:j + T].astype(f32) * taps[j] for j in range(K))
    n_live = jnp.full((B_,), T, jnp.int32) if live is None else jnp.sum(live, axis=1)
    kept = n_live.astype(jnp.int32)[:, None, None] + jnp.arange(K - 1)[None, :, None]
    want_tail = jnp.take_along_axis(rows, kept, axis=1)
    got_rows, got = tfm._causal_filter(tail, xBC, leaf)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(conv))
    np.testing.assert_array_equal(np.asarray(got_rows, f32), np.asarray(rows, f32))
    np.testing.assert_array_equal(np.asarray(tfm._filter_tail(got_rows, K, live), f32),
                                  np.asarray(want_tail, f32))
    if masked:  # a row with no live token keeps the tail it had
        np.testing.assert_array_equal(np.asarray(want_tail[2], f32), np.asarray(tail[2], f32))


def test_falcon_h1s_twin_still_matches_its_reference():
    """The mixer's apply and cache path through the shared filter: ``parity``'s own
    cases of the configuration, float32."""
    for surface in ("apply", "loss"):
        assert parity.error("falcon-h1-34b-L4", surface) <= parity.TOL[surface]


# -- refusals, by name ------------------------------------------------------------------------------

_REFUSED = {
    "latent attention": (dict(kv_lora_rank=16, qk_rope_head_dim=8, v_head_dim=16, qk_norm=False),
                         "layer kinds|layer_operators"),
    "the mixer": (dict(ssm_state_size=8, ssm_heads=2, ssm_head_dim=8, qk_norm=False,
                       moe_every=0, moe_routing="gshard", moe_score_fn="softmax",
                       moe_select_bias=False, moe_norm_topk_prob=False, moe_first_dense=0,
                       dense_intermediate_size=None), "layer kinds|mixer"),
    "alibi": (dict(pos_emb="alibi"), "alibi"),
    "window layers": (dict(local_attn_layers=[0, 1, 0, 0, 0, 1, 0], local_attn_window=8),
                      "window layers"),
    "biases": (dict(use_bias=True), "use_bias"),
    "post norm": (dict(norm_style="post"), "norm_style='post'"),
    "parallel residual": (dict(parallel_residual=True), "parallel_residual"),
    "bidirectional": (dict(causal=False), "causal=False"),
    "int8 weights": (dict(weight_bits=8), "weight_bits"),
    "activation quantisation": (dict(act_quant_bits=8), "act_quant_bits"),
    "param offload": (dict(param_offload=True), "param_offload"),
    "a prediction module": (dict(mtp_layers=1), "mtp_layers"),
    "flash training": (dict(attn_impl="flash"), "attn_impl='flash'"),
    "one operator for every layer": (dict(layer_operators=["attn"] * 7), "BOTH operators"),
    "an operator of no kind": (dict(layer_operators=["conv", "mamba"] + OPS[2:]), "layer_operators"),
    "too few operators": (dict(layer_operators=OPS[:6]), "layer_operators"),
    "no taps": (dict(conv_kernel=0), "conv_kernel"),
    "taps without a layer to filter": (dict(layer_operators=None), "conv_kernel"),
}


@pytest.mark.parametrize("case", list(_REFUSED))
def test_combinations_without_code_are_refused_by_name(program, case):
    extra, word = _REFUSED[case]
    with pytest.raises((NotImplementedError, ValueError), match=word):
        tfm.TransformerConfig(**{**program, **extra})


def test_the_loops_that_know_one_stack_refuse_the_operators(cfg, params):
    from deepspeed_tpu.pipe import PipelinedTransformer

    with pytest.raises(NotImplementedError, match="pipeline"):
        PipelinedTransformer(cfg, num_stages=7, num_micro_batches=1)
    with pytest.raises(ValueError, match="live"):  # a padded block with no live-row mask
        tfm.apply_with_cache(cfg, params, _tokens(cfg, (1, 8)), tfm.init_cache(cfg, 1, 8), 0,
                             last_index=4)


# -- what the by-kind loop refused and the ONE loop runs (PR 47): wrap and per-layer gates ----------


def _loss_and_grads(cfg, params, rng=None, grads=True):
    loss = lambda p: tfm.causal_lm_loss(cfg, p, {"tokens": _tokens(cfg, (2, 49), 4)}, rng=rng)  # noqa: E731
    return jax.jit(jax.value_and_grad(loss) if grads else loss)(params)


@pytest.fixture(scope="module")
def plain(cfg, params):
    return _loss_and_grads(cfg, params)


def test_remat_changes_neither_the_loss_nor_a_gradient(cfg, params, plain):
    """Activation checkpointing round every body the loop scans (the lead, a whole
    period A C C C, the tail): the same loss and the same gradient in every stack."""
    loss, grads = _loss_and_grads(cfg.replace(remat=True), params)
    assert abs(float(loss) - float(plain[0])) <= 1e-5
    worst = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))), grads, plain[1])
    assert max(jax.tree.leaves(worst)) <= 1e-5, worst


@pytest.mark.parametrize("case", ["every layer kept", "dropout"])
def test_the_per_layer_gates_reach_every_layer_of_both_operators(cfg, params, plain, case):
    """``rng`` / ``pld_keep`` are cut like every other stack. Layer drop at keep
    probability 1 draws a gate for every layer and keeps them all: the loss without
    it. Dropout (on the conv sublayer's output as on the attention's) moves the loss
    and leaves a gradient in every stack."""
    rng = jax.random.PRNGKey(3)
    if case == "every layer kept":
        kept = cfg.replace(pld_enabled=True, pld_theta=1.0)
        assert abs(float(_loss_and_grads(kept, params, rng, grads=False)) - float(plain[0])) <= 1e-5
        return
    dropped = cfg.replace(hidden_dropout=0.2, attn_dropout=0.2)
    assert abs(float(_loss_and_grads(dropped, params)[0]) - float(plain[0])) <= 1e-5  # no rng
    loss, grads = _loss_and_grads(dropped, params, rng)
    assert np.isfinite(float(loss)) and abs(float(loss) - float(plain[0])) > 1e-3
    sizes = jax.tree.map(lambda g: float(jnp.abs(g).max()), grads)
    assert min(jax.tree.leaves(sizes["layers"])) > 0 and sizes["dense_ffn"]["wi"] > 0
    assert sizes["moe"]["gate"] > 0


def test_counts_at_the_published_widths():
    config = _config()
    program = program_of(config)
    counts = load_reference(program).param_counts(program)
    assert counts["total"] == 5_177_950_976  # 10.36 GB in bf16
    assert counts["matmul_conv_per_layer"] == 16_783_360 - 3 * 2048  # without the taps
    assert counts["matmul_attention_per_layer"] == 10_485_888 - 128  # without the head norms
    assert counts["matmul_per_expert"] == 9_437_184 and counts["routed_layers"] == 8
    assert (counts["conv_layers"], counts["attn_layers"]) == (7, 2)
    assert counts["matmul_on_token_path"] - 2048 * 65536 == 513_802_240  # 0.51 G a row
    real = tfm.TransformerConfig(dtype=jnp.bfloat16, **program)
    shapes = jax.eval_shape(lambda: tfm.init(real, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) == counts["total"]
    # the cache: 4,096 B a token over the model where K/V in every layer is 18,432;
    # 57,344 B a sequence of state
    assert conv_cost.kv_bytes_per_token_model(program) == 4096
    assert 2 * tfm.cache_bytes_per_token(real) == 4096 and 9 * tfm.cache_bytes_per_token(real) == 18432
    assert conv_cost.state_bytes_per_slot(program) == 57344
    assert tfm.cache_layers(real)[tfm.STATE] * tfm.cache_state_bytes(real) == 57344
    cache = jax.eval_shape(lambda: tfm.init_cache(real, 128, 3072))
    assert jax.tree.map(lambda x: x.shape, cache) == {
        "k": (2, 128, 3072, 1, 512), "v": (2, 128, 3072, 1, 512),
        tfm.STATE: {"conv": (7, 128, 2, 2048)}}
    assert sum(int(np.prod(x.shape)) * 2 for x in jax.tree.leaves(cache)) \
        == 128 * (3072 * 4096 + 57344) == 1_617_952_768
    # a prefill: attention in TWO layers, the filter in seven
    rows = 2048
    body = 2.0 * 513_802_240 * rows + 2.0 * 2048 * 65536
    assert conv_cost.prefill_flops(program, rows) == \
        body + 2 * rows * rows * 32 * 2 * 64 + 7 * rows * 2 * 3 * 2048
    # a decode step at 128 rows past 800 tokens, every expert touched
    need = conv_cost.decode_min_bytes(program, 128 * 800, 2 * 128 * 57344, 64.0)
    weights = (counts["matmul_outside_experts"] + 8 * 64 * 9_437_184) * 2
    assert need == weights + 2 * 128 * 800 * 2048 + 2 * 128 * 57344
    assert abs(weights / need - 0.95) < 0.02  # the weights: nearly all of a step
    # every published number of the catalog row is in the file under its own key
    published = {"hidden_size": 2048, "intermediate_size": 11776, "moe_intermediate_size": 1536,
                 "num_attention_heads": 32, "num_key_value_heads": 8, "num_experts": 64,
                 "num_experts_per_tok": 4, "conv_L_cache": 3, "vocab_size": 65536,
                 "norm_eps": 1e-05, "routed_scaling_factor": 1, "max_position_embeddings": 128000}
    assert {k: config[k] for k in published} == published and len(config["layer_types"]) == 40
    assert config["num_hidden_layers"] == 9 and config["num_dense_layers"] == 1
    assert config["reduced"] == ["num_hidden_layers", "num_dense_layers"]
    ran = [config["layer_types"][i] for i in (0, *range(2, 10))]
    assert ["conv" if t == "conv" else "attn" for t in ran] == program["layer_operators"]
