"""Qwen3-Next-80B-A3B on the normal path (PR 52): a gated delta rule in the attention
sublayer's place in six layers of the twin's eight (D D D A D D D A), the parameters in
stacks BY OPERATOR, an output gate on attention, rotary on a quarter of a head, a
softmax router that holds a share of its experts and a gated shared expert — against
the plain reference ``chipbench/references/qwen3_next.py`` (itself held to
``transformers``' ``Qwen3NextForCausalLM``), at the configuration's rehearsal sizes on
the CPU, seeded weights, float32 unless a test says bfloat16."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from qwen3_next_cases import (  # noqa: F401,I001 -- first: puts the repo's root on sys.path; fixtures
    WHOLE, TOL, LOSS_TOL, OPS, _config, program, reference, cfg, params, _tokens, _PLANTED,
    _plant)

from chipbench import delta_cost, parity  # noqa: E402
from chipbench.drivers import serve_delta  # noqa: E402
from chipbench.references import Program, load_reference, program_of  # noqa: E402
from deepspeed_tpu.models import transformer as tfm  # noqa: E402


# -- the layout -----------------------------------------------------------------------------------


def test_layout_is_stacks_by_operator_and_a_cache_by_layer_kind(cfg, params, program):
    assert program["layer_operators"] == OPS and cfg.conv_kernel == 4
    D, A = (0, True, "delta"), (0, True, "attn")  # (window, rotary, operator)
    assert cfg.layer_kinds == (D, D, D, A) * 2 and cfg.delta_layers == (0, 1, 2, 4, 5, 6)
    assert cfg.stateful_layers == cfg.delta_layers and cfg.conv_layers == ()
    assert tfm._index_in_kind(cfg) == (0, 1, 2, 0, 3, 4, 5, 1)
    lay, moe = params["layers"], params["moe"]
    assert lay["ln1_scale"].shape == lay["ln2_scale"].shape == (8, 64)  # norms: every layer's
    assert {k: v.shape for k, v in lay["delta"].items()} == {
        "delta_in": (6, 64, 192), "delta_ba": (6, 64, 8), "delta_conv": (6, 4, 128),
        "delta_a_log": (6, 4), "delta_dt_bias": (6, 4), "delta_norm_scale": (6, 16),
        "delta_out": (6, 64, 64)}
    assert {k: v.shape for k, v in lay["attn"].items()} == {  # wq: query | gate, a head
        "wq": (2, 64, 4, 128), "wk": (2, 64, 2, 64), "wv": (2, 64, 2, 64), "wo": (2, 4, 64, 64),
        "q_norm_scale": (2, 64), "k_norm_scale": (2, 64)}
    assert set(lay) == {"ln1_scale", "ln2_scale", "attn", "delta"}
    assert moe["gate"].shape == (8, 64, 16) and moe["experts"]["wi"].shape == (8, 4, 64, 32)
    assert {k: v.shape for k, v in moe["shared"].items()} == {
        "wg": (8, 64, 32), "wi": (8, 64, 32), "wo": (8, 32, 64), "w_gate": (8, 64)}
    assert params["lm_head"].shape == (64, 768) and "bias" not in moe
    drawn = tfm.init(cfg, jax.random.PRNGKey(3))["layers"]["delta"]
    a = np.exp(np.asarray(drawn["delta_a_log"]))
    assert 0 < a.min() and a.max() <= 16 and a.std() > 2  # log U(0, 16), as published
    dt = np.asarray(jax.nn.softplus(drawn["delta_dt_bias"]))  # a step in [0.001, 0.1]
    assert 1e-3 <= dt.min() and dt.max() <= 1e-1 + 1e-6 and np.exp(-a * dt).min() > 0.15
    # the cache: K/V of the attention layers alone, a token's grouped heads side by side as one
    # row; a float32 matrix a value head and 3 rows of the filter's input a delta layer
    assert tfm.cache_heads_merged(cfg) and tfm.cache_rows_step(cfg)
    cache = tfm.init_cache(cfg, 3, 256)
    assert jax.tree.map(lambda x: (x.shape, str(x.dtype)), cache) == {
        "k": ((2, 3, 256, 1, 128), "float32"), "v": ((2, 3, 256, 1, 128), "float32"),
        tfm.STATE: {"delta": ((6, 3, 4, 16, 16), "float32"), "conv": ((6, 3, 3, 128), "float32")}}
    held = tfm.init_cache(cfg.replace(dtype=jnp.bfloat16), 1, 8)[tfm.STATE]
    assert held["delta"].dtype == jnp.float32 and held["conv"].dtype == jnp.bfloat16
    assert tfm.cache_layers(cfg) == {"tokens": 2, tfm.RING: 0, tfm.STATE: 6}
    assert tfm.cache_state_bytes(cfg) == 4 * 16 * 16 * 4 + 3 * 128 * 4
    axes = jax.tree.structure(tfm.logical_axes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    assert axes == jax.tree.structure(params)
    held = tfm.hold_for_compute(cfg.replace(dtype=jnp.bfloat16), params)
    lay16 = held["layers"]["delta"]
    assert {k for k, v in lay16.items() if v.dtype == jnp.bfloat16} == {
        "delta_in", "delta_ba", "delta_conv", "delta_out"}
    assert held["moe"]["shared"]["w_gate"].dtype == jnp.bfloat16
    assert held["moe"]["gate"].dtype == jnp.float32


def test_the_parent_keywords_are_new():
    """What ``TransformerConfig(**program)`` raised on before this PR: the keys."""
    new = {"delta_key_heads", "delta_value_heads", "delta_head_dim", "attn_output_gate",
           "moe_shared_gate"}
    fields = set(tfm.TransformerConfig.__dataclass_fields__)
    assert new <= set(program_of(_config())) and new <= fields
    twin = program_of(_config(), "rehearse_program")  # parity.py's twin: every layer attends
    assert "layer_operators" not in twin and {"attn_output_gate", "moe_shared_gate"} <= set(twin)


def test_a_model_without_the_new_fields_draws_and_computes_what_it_did():
    """The seeded draw and the logits of a gated-attention-free, gate-free model are
    those of the same configuration written before the fields existed."""
    base = dict(vocab_size=64, max_seq_len=32, num_layers=2, num_heads=2, hidden_size=32,
                activation="swiglu", use_bias=False, moe_every=1, moe_routing="dropless",
                num_experts=4, moe_top_k=2, moe_shared_size=16)
    plain, gated = tfm.TransformerConfig(**base), tfm.TransformerConfig(
        **base, attn_output_gate=True, moe_shared_gate=True)
    p0, p1 = (tfm.init(c, jax.random.PRNGKey(0)) for c in (plain, gated))
    assert "w_gate" not in p0["moe"]["shared"] and p0["layers"]["wq"].shape == (2, 32, 2, 16)
    assert p1["layers"]["wq"].shape == (2, 32, 2, 32)
    for name in ("wg", "wi", "wo"):
        np.testing.assert_array_equal(p0["moe"]["shared"][name], p1["moe"]["shared"][name])
    np.testing.assert_array_equal(p0["moe"]["experts"]["wi"], p1["moe"]["experts"]["wi"])
    tokens = _tokens(plain, (1, 12))
    assert float(jnp.abs(tfm.apply(plain, p0, tokens) - tfm.apply(gated, p1, tokens)).max()) > 1e-3


# -- the three surfaces against the reference -----------------------------------------------------


def test_apply_matches_the_reference_and_returns_its_choices(cfg, params, program, reference):
    tokens = _tokens(cfg, (2, 150))  # two chunks and 22 rows of a third
    got, chosen = tfm.apply(cfg, params, tokens, return_routing=True)
    assert chosen.shape == (8, 2, 150, 4)
    for row in range(2):
        ref = reference.routed_pass(program, params, tokens[row], np.arange(150), fetch=WHOLE)
        assert np.std(ref["logits"]) > 0.5
        assert np.max(np.abs(np.asarray(got[row]) - ref["logits"])) <= TOL
        np.testing.assert_array_equal(np.sort(np.asarray(chosen)[:, row]), np.sort(ref["own"]))


def test_loss_matches_the_reference_and_has_a_gradient_in_every_stack(cfg, params, program,
                                                                      reference):
    tokens = _tokens(cfg, (2, 97), 4)
    loss, grads = jax.value_and_grad(lambda p: tfm.causal_lm_loss(cfg, p, {"tokens": tokens}))(
        params)
    assert abs(float(loss) - reference.lm_loss(program, params, tokens, fetch=WHOLE)) <= LOSS_TOL
    sizes = jax.tree.map(lambda g: float(jnp.abs(g).max()), grads)
    assert min(jax.tree.leaves(sizes["layers"])) > 0  # both operators' stacks, every leaf
    assert sizes["moe"]["shared"]["w_gate"] > 0 and sizes["moe"]["gate"] > 0
    assert np.isfinite(jax.tree.leaves(jax.tree.map(lambda g: float(jnp.abs(g).sum()), grads))).all()


# -- the controls: what the tolerance must catch --------------------------------------------------


def _errors(cfg, params, program, reference, loss=True):
    """max |system - reference| on apply, the cache path and (unless told not) the loss."""
    tokens = _tokens(cfg, (110,), 6)
    ref = reference.logits_at(program, params, tokens, np.arange(110), fetch=WHOLE)
    apply_err = float(np.max(np.abs(np.asarray(tfm.apply(cfg, params, tokens[None]), np.float32)[0]
                                    - ref)))
    got, _ = serve_delta.probe_logits(cfg, params, [tokens[:100]], [128], tokens[None, 100:108])
    cache_err = float(np.max(np.abs(got[0] - ref[99:108])))
    if not loss:
        return {"apply": apply_err, "cache": cache_err}
    batch = _tokens(cfg, (2, 97), 4)
    loss_err = abs(float(tfm.causal_lm_loss(cfg, params, {"tokens": batch}))
                   - reference.lm_loss(program, params, batch, fetch=WHOLE))
    return {"apply": apply_err, "cache": cache_err, "loss": loss_err}


def test_float32_passes_and_bfloat16_compute_fails_the_tolerance(cfg, params, program, reference):
    errs = _errors(cfg, params, program, reference)
    assert errs["apply"] <= TOL and errs["cache"] <= TOL and errs["loss"] <= LOSS_TOL, errs
    bf16 = _errors(tfm.TransformerConfig(dtype=jnp.bfloat16, **program), params, program, reference)
    assert bf16["apply"] > 5 * TOL and bf16["cache"] > 5 * TOL and bf16["loss"] > 5 * LOSS_TOL, bf16


@pytest.mark.parametrize("fault", list(_PLANTED))
def test_a_planted_fault_fails_the_tolerance(cfg, params, program, reference, monkeypatch, fault):
    """Apply and the cache path both miss by far (a state from the padding: the cache
    path alone, ``apply`` pads nothing)."""
    _plant(monkeypatch, fault)
    errs = _errors(cfg, params, program, reference, loss=False)
    assert errs["cache"] > 10 * TOL, errs
    assert (errs["apply"] > 10 * TOL) == (fault != "the state taken from the padding"), errs


# -- the reference against the published code -----------------------------------------------------


def _published_state(torch, p, params):
    """The twin's parameter tree as ``Qwen3NextForCausalLM``'s state dict: every
    RMSNorm's weight is the scale - 1 (not the gated norm's), ``in_proj_qkvz`` and
    ``in_proj_ba`` are interleaved by key head."""
    t = lambda x: torch.from_numpy(np.array(x, np.float32))  # noqa: E731
    d, lay, moe = p["hidden_size"], params["layers"], params["moe"]
    Hk, Hv, D = p["delta_key_heads"], p["delta_value_heads"], p["delta_head_dim"]
    r = Hv // Hk
    state = {"model.embed_tokens.weight": t(params["wte"]),
             "model.norm.weight": t(params["lnf_scale"] - 1), "lm_head.weight": t(params["lm_head"].T)}
    for i, op in enumerate(p["layer_operators"]):
        pre, at = f"model.layers.{i}.", OPS[:i].count(op)
        state.update({pre + "input_layernorm.weight": t(lay["ln1_scale"][i] - 1),
                      pre + "post_attention_layernorm.weight": t(lay["ln2_scale"][i] - 1)})
        if op == "delta":
            c = {k: np.asarray(v[at]) for k, v in lay["delta"].items()}
            q, k, v, z = np.split(c["delta_in"], [Hk * D, 2 * Hk * D, 2 * Hk * D + Hv * D], axis=1)
            by_head = np.concatenate([q.reshape(d, Hk, D), k.reshape(d, Hk, D),
                                      v.reshape(d, Hk, r * D), z.reshape(d, Hk, r * D)], axis=2)
            b, a = c["delta_ba"][:, :Hv], c["delta_ba"][:, Hv:]
            ba = np.concatenate([b.reshape(d, Hk, r), a.reshape(d, Hk, r)], axis=2)
            pre += "linear_attn."
            state.update({pre + "in_proj_qkvz.weight": t(by_head.reshape(d, -1).T),
                          pre + "in_proj_ba.weight": t(ba.reshape(d, -1).T),
                          pre + "conv1d.weight": t(c["delta_conv"].T[:, None, :]),  # [C, 1, K]
                          pre + "A_log": t(c["delta_a_log"]), pre + "dt_bias": t(c["delta_dt_bias"]),
                          pre + "norm.weight": t(c["delta_norm_scale"]),
                          pre + "out_proj.weight": t(c["delta_out"].T)})
        else:
            a = {k: np.asarray(v[at]) for k, v in lay["attn"].items()}
            pre += "self_attn."
            state.update({pre + "q_proj.weight": t(a["wq"].reshape(d, -1).T),
                          pre + "k_proj.weight": t(a["wk"].reshape(d, -1).T),
                          pre + "v_proj.weight": t(a["wv"].reshape(d, -1).T),
                          pre + "o_proj.weight": t(a["wo"].reshape(-1, d).T),
                          pre + "q_norm.weight": t(a["q_norm_scale"] - 1),
                          pre + "k_norm.weight": t(a["k_norm_scale"] - 1)})
        pre = f"model.layers.{i}.mlp."
        state[pre + "gate.weight"] = t(moe["gate"][i].T)
        state[pre + "shared_expert_gate.weight"] = t(moe["shared"]["w_gate"][i][None])
        mlps = {"shared_expert.": {k: moe["shared"][k][i] for k in ("wg", "wi", "wo")}}
        for e in range(p["num_experts"]):
            mlps[f"experts.{e}."] = {k: v[i, e] for k, v in moe["experts"].items()}
        for name, w in mlps.items():
            state.update({pre + name + "gate_proj.weight": t(w["wg"].T),
                          pre + name + "up_proj.weight": t(w["wi"].T),
                          pre + name + "down_proj.weight": t(w["wo"].T)})
    return state


def test_reference_agrees_with_transformers(program, reference):
    """``Qwen3NextForCausalLM`` at the delta twin's sizes, one period D D D A, ALL
    sixteen experts held, on the same seeded weights (the norms' + 1 and the column
    permutation undone on the way in): its logits are the reference's, so the
    reference is the published forward pass (its chunked delta rule against the
    reference's recurrence). One period and not two: a delta layer passes on 3e-6 to
    1e-5 of float32 rounding where an attention layer passes 1e-6 (the same reading
    with the published RECURRENT function in the chunked one's place), and behind
    eight layers the two float32 passes are 1e-4 to 5e-4 apart. Even so 1e-4 stands
    near float32's own floor here: over twelve seeds of 48 tokens the two read 4.1e-5
    to 1.5e-4 apart, and ``transformers``' own float32 pass is 4e-5 to 3e-4 from its
    float64 one (the residual stream of this seeded draw reaches 9); the seed below
    reads 4.1e-5."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    p = Program({**{k: v for k, v in program.items() if k != "moe_experts_held"},
                 "num_layers": 4, "layer_operators": OPS[:4]}, "qwen3_next")
    uncut = tfm.TransformerConfig(dtype=jnp.float32, **p)
    params = parity._seeded_params(tfm, uncut)
    hf = transformers.Qwen3NextConfig(
        vocab_size=p["vocab_size"], hidden_size=p["hidden_size"], intermediate_size=96,
        num_hidden_layers=p["num_layers"], num_attention_heads=p["num_heads"],
        num_key_value_heads=p["num_kv_heads"], head_dim=p["qk_head_dim"],
        partial_rotary_factor=p["rotary_pct"], rope_theta=p["rotary_base"], rope_scaling=None,
        max_position_embeddings=p["max_seq_len"], rms_norm_eps=p["layernorm_epsilon"],
        tie_word_embeddings=False, attention_bias=False, attention_dropout=0.0,
        linear_conv_kernel_dim=p["conv_kernel"], linear_key_head_dim=p["delta_head_dim"],
        linear_value_head_dim=p["delta_head_dim"], linear_num_key_heads=p["delta_key_heads"],
        linear_num_value_heads=p["delta_value_heads"], decoder_sparse_step=1, mlp_only_layers=[],
        moe_intermediate_size=p["intermediate_size"],
        shared_expert_intermediate_size=p["moe_shared_size"], num_experts=p["num_experts"],
        num_experts_per_tok=p["moe_top_k"], norm_topk_prob=True, hidden_act="silu",
        layer_types=["linear_attention" if op == "delta" else "full_attention"
                     for op in p["layer_operators"]])
    hf._attn_implementation = "eager"
    model = transformers.Qwen3NextForCausalLM(hf).eval()
    missing, unexpected = model.load_state_dict(_published_state(torch, p, params), strict=False)
    assert not unexpected and all("rotary" in k or "inv_freq" in k for k in missing), (
        missing, unexpected)
    tokens = _tokens(uncut, (48,), 13)
    with torch.no_grad():
        theirs = model(torch.from_numpy(tokens[None].astype(np.int64))).logits[0].numpy()
    ours = reference.logits_at(p, params, tokens, np.arange(48), fetch=WHOLE)
    err = float(np.max(np.abs(theirs - ours)))
    assert np.std(theirs) > 0.5 and err <= 1e-4, err


def test_reference_attention_in_query_blocks_is_the_whole_matrix(program, reference, params):
    from unittest import mock

    tokens = np.random.default_rng(1).integers(0, program["vocab_size"], size=70)
    one = reference.logits_at(program, params, tokens, np.arange(70), fetch=WHOLE)
    reference._attend.clear_cache()
    with mock.patch.multiple(reference, QUERY_BLOCK=16, ROW_BLOCK=32, HEAD_BLOCK=100):
        many = reference.logits_at(program, params, tokens, np.arange(70), fetch=WHOLE)
    reference._attend.clear_cache()
    np.testing.assert_allclose(many, one, atol=5e-4)  # summation order, through eight layers


@pytest.mark.parametrize("key,value", [("moe_score_fn", "sigmoid"), ("tie_embeddings", True),
                                       ("attn_output_gate", False), ("moe_first_dense", 1),
                                       ("ssm_state_size", 16)])
def test_the_reference_refuses_what_it_does_not_implement(program, key, value):
    from chipbench.references import NotCovered

    with pytest.raises(NotCovered, match=key):
        load_reference(Program({**program, key: value}, "qwen3_next"))


# -- the share ------------------------------------------------------------------------------------


def test_the_four_shares_of_a_layer_add_up_to_the_uncut_layer(program, reference):
    """Sixteen experts in shares of four: each share's program holds its own banks
    and every other leaf alike; the four shares' routed parts plus the shared expert
    counted once are the uncut reference's layer, and the system's share is the
    reference's share."""
    from deepspeed_tpu.moe.dropless import moe_ffn_dropless

    uncut = Program({k: v for k, v in program.items() if k != "moe_experts_held"}, "qwen3_next")
    cfg_all = tfm.TransformerConfig(dtype=jnp.float32, **uncut)
    moe = jax.tree.map(lambda a: a[0], parity._seeded_params(tfm, cfg_all)["moe"])
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 40, 64))
    whole, _, chosen = moe_ffn_dropless(cfg_all, moe, h)
    shared = moe_ffn_dropless(cfg_all.replace(moe_top_k=1), {
        **moe, "experts": jax.tree.map(jnp.zeros_like, moe["experts"])}, h)[0]
    parts = []
    for first in (0, 4, 8, 12):
        cfg_share = cfg_all.replace(moe_experts_held=(first, 4))
        bank = jax.tree.map(lambda a: a[first:first + 4], moe["experts"])
        out, _, own = moe_ffn_dropless(cfg_share, {**moe, "experts": bank}, h)
        np.testing.assert_array_equal(own, chosen)  # the router is every share's, whole
        parts.append(out - shared)
    assert float(jnp.abs(shared).max()) > 1e-2 and all(float(jnp.abs(p).max()) > 1e-2 for p in parts)
    np.testing.assert_allclose(np.asarray(sum(parts) + shared), np.asarray(whole), atol=2e-5)
    # and against the reference's layer, uncut: its router, its experts one at a time, its gate
    with jax.default_matmul_precision("highest"):
        route = reference._route(uncut, h[0] @ moe["gate"], None)
        want = sum(reference._gated_mlp(h[0], *(moe["experts"][k][e] for k in ("wg", "wi", "wo")),
                                        route["mix"][:, e]) for e in range(16))
        want = want + reference._gated_mlp(h[0], *(moe["shared"][k] for k in ("wg", "wi", "wo")),
                                           jax.nn.sigmoid(h[0] @ moe["shared"]["w_gate"]))
    np.testing.assert_allclose(np.asarray(whole[0]), np.asarray(want), atol=2e-5)


# -- what has no code is refused by name ----------------------------------------------------------


_REFUSED = {  # case: (what to state beside the twin's program, the error, words of its message)
    "pipeline": None,
    "latent attention": (dict(kv_lora_rank=8, qk_rope_head_dim=8, v_head_dim=16, qk_norm=False,
                              attn_output_gate=False),
                         NotImplementedError, r"latent attention"),
    "a mixer beside": (dict(ssm_state_size=8, ssm_heads=2, ssm_head_dim=8), NotImplementedError,
                       r"state-space mixer"),
    "window layers": (dict(local_attn_window=8, local_attn_layers=[0, 0, 0, 1] * 2),
                      NotImplementedError, r"a 'delta' layer\) with window layers"),
    "biases": (dict(use_bias=True), NotImplementedError, r"use_bias"),
    "weight_bits": (dict(weight_bits=8), NotImplementedError, r"a 'delta' layer\) with weight_bits"),
    "param_offload": (dict(param_offload=True), NotImplementedError,
                      r"a 'delta' layer\) with param_offload"),
    "mtp_layers": (dict(mtp_layers=1), NotImplementedError, r"a 'delta' layer\) with mtp_layers"),
    "flash training": (dict(attn_impl="flash"), NotImplementedError, r"attn_impl='flash'"),
    "conv and delta layers": (dict(layer_operators=["delta", "conv", "delta", "attn"] * 2),
                              NotImplementedError, r"both 'conv' and 'delta'"),
    "no attention layer": (dict(layer_operators=["delta"] * 8), ValueError, r"BOTH operators"),
    "no taps": (dict(conv_kernel=0), ValueError, r"a 'delta' layer states conv_kernel"),
    "no sizes": (dict(delta_head_dim=0), ValueError, r"a 'delta' layer states its sizes"),
    "value heads not a multiple": (dict(delta_value_heads=3), ValueError, r"a multiple of them"),
    "sizes without the operator": (dict(layer_operators=None, conv_kernel=0), ValueError,
                                   r"without layer_operators"),
    "delta sizes beside conv layers": (dict(layer_operators=["conv", "conv", "conv", "attn"] * 2),
                                       ValueError, r"without a 'delta' layer"),
    "a conv layer names itself": (dict(layer_operators=["conv", "conv", "conv", "attn"] * 2,
                                       delta_key_heads=0, delta_value_heads=0, delta_head_dim=0,
                                       weight_bits=8),
                                  NotImplementedError, r"a 'conv' layer\) with weight_bits"),
    "a gate without a shared expert": (dict(moe_shared_size=0), ValueError,
                                       r"moe_shared_gate without a shared expert"),
    "the attention gate with biases": (dict(layer_operators=None, conv_kernel=0, delta_key_heads=0,
                                            delta_value_heads=0, delta_head_dim=0, use_bias=True,
                                            activation="gelu", moe_every=0, moe_routing="gshard",
                                            moe_shared_size=0, moe_shared_gate=False,
                                            moe_experts_held=None, moe_norm_topk_prob=False,
                                            num_experts=1, moe_top_k=1),
                                       NotImplementedError, r"attn_output_gate with use_bias"),
}


@pytest.mark.parametrize("case", list(_REFUSED))
def test_combinations_without_code_are_refused_by_name(program, cfg, case):
    """Every row of ``_refuse_uncoded_operators``' table, and the pipeline's: the
    message names the operator it refuses (a 'delta' layer, or a 'conv' layer)."""
    if case == "pipeline":
        with pytest.raises(NotImplementedError, match="layer_operators under a pipeline"):
            tfm.refuse_in_pipeline(cfg)
        return
    extra, error, words = _REFUSED[case]
    with pytest.raises(error, match=words):
        tfm.TransformerConfig(**{**program, **extra})


# -- training's surface ---------------------------------------------------------------------------


def test_remat_changes_neither_the_loss_nor_a_gradient(cfg, params):
    tokens = _tokens(cfg, (2, 70), 5)
    loss = lambda c: jax.value_and_grad(  # noqa: E731
        lambda p: tfm.causal_lm_loss(c, p, {"tokens": tokens}))(params)
    (l0, g0), (l1, g1) = loss(cfg), loss(cfg.replace(remat=True))
    assert abs(float(l0) - float(l1)) <= 1e-6
    worst = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max() / jnp.abs(a).max()), g0, g1)
    assert max(jax.tree.leaves(worst)) <= 1e-3, worst  # of the leaf's largest gradient


# -- the counts at the published widths ------------------------------------------------------------


def test_counts_at_the_published_widths():
    config = _config()
    program = program_of(config)
    counts = load_reference(program).param_counts(program)
    assert counts["total"] == 1_978_847_360  # ISSUE 52's reckoning from the published config
    assert counts["matmul_delta_per_layer"] == 33_718_464 - 32_768 - 192  # without taps and vectors
    assert counts["matmul_attention_per_layer"] == 27_263_488 - 512  # without the head norms
    assert counts["matmul_per_expert"] == 3_145_728 and counts["routed_layers"] == 8
    assert counts["experts_held"] == 64 and counts["held_pairs_per_token_per_layer"] == 1.25
    assert (counts["delta_layers"], counts["attn_layers"]) == (6, 2)
    real = tfm.TransformerConfig(dtype=jnp.bfloat16, **program)
    shapes = jax.eval_shape(lambda: tfm.init(real, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) == counts["total"]
    assert delta_cost.state_bytes_per_slot(program) == 12_877_824 == 6 * tfm.cache_state_bytes(real)
    assert delta_cost.kv_bytes_per_token(program) == 4096 == 2 * tfm.cache_bytes_per_token(real)
    assert tfm.cache_heads_merged(real) and tfm.cache_rows_step(real)
    for key, value in config["published"].items():  # the cuts, stated beside what was published
        assert key in config["reduced"] and config[key] < value
    assert config["num_experts"] == program["moe_experts_held"][1] and program["num_experts"] == 512
    # an 8,192-row prefill: 6.8 TFLOP as ISSUE 52 sized it (GEMMs, the two attention layers'
    # causal half, the rule at its recurrent cost)
    assert 6.0e12 < delta_cost.prefill_flops(program, 8192) < 7.5e12
    step = delta_cost.decode_min_bytes(program, 64 * 5500, 2 * 64 * 12_877_824, 64.0)
    assert 6.0e9 < step < 8.0e9  # about 7 GB a step: 3.96 of weights, 1.65 of state, 1.4 of K/V
