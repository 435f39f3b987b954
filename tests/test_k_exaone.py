"""K-EXAONE-236B-A23B on the normal path (PR 38): sliding-window and whole-context
layers in one stack (a ring of ``local_attn_window`` positions a window layer, ``Smax``
a whole one), no rotary on the whole-context layers, per-head q/k RMSNorm, a held
SHARE of the routed experts and the multi-token-prediction module — against the
plain reference ``chipbench/references/exaone_moe.py`` (itself held to
``transformers``' ``Exaone4Attention`` and ``DeepseekV3MoE``), at the
configuration's rehearsal sizes on the CPU, seeded weights, float32 unless a test
says bfloat16."""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k_exaone_cases import (  # noqa: F401,I001 -- first: puts the repo's root on sys.path; fixtures
    WHOLE, TOL, WINDOW, _config, program, reference, cfg, params, _tokens)

from chipbench import kinds_cost, parity  # noqa: E402
from chipbench.drivers import serve_kinds  # noqa: E402
from chipbench.references import Program, load_reference, program_of  # noqa: E402
from deepspeed_tpu.models import transformer as tfm  # noqa: E402
from deepspeed_tpu.moe import dropless  # noqa: E402


# -- the layout -----------------------------------------------------------------------------------


def test_layout_is_kinds_as_data_over_a_held_share(cfg, params):
    S, G = (16, True, "attn"), (0, False, "attn")  # (window, rotary, operator): every layer attends
    assert cfg.layer_kinds == (S, S, S, G, S, S)
    assert cfg.window_layers == (0, 1, 2, 4, 5) and cfg.experts_held == (4, 4)
    lay, moe = params["layers"], params["moe"]
    assert lay["q_norm_scale"].shape == lay["k_norm_scale"].shape == (6, 24)  # one [D] a layer
    assert moe["gate"].shape == (5, 64, 16) and moe["bias"].shape == (5, 16)  # the router: all 16
    assert moe["experts"]["wi"].shape == (5, 4, 64, 32)  # the banks: the 4 held
    cache = tfm.init_cache(cfg, 3, 256)
    assert cache["k"].shape == (1, 3, 256, 2, 24)  # ONE whole-context layer, Smax long
    assert cache[tfm.RING]["k"].shape == cache[tfm.RING]["v"].shape == (5, 3, WINDOW, 2, 24)
    assert tfm.cache_bytes_per_token(cfg) == 2 * 2 * 24 * 4
    assert tfm.cache_ring_bytes(cfg) == 5 * WINDOW * tfm.cache_bytes_per_token(cfg)
    axes = jax.tree.structure(tfm.logical_axes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    assert axes == jax.tree.structure(params)


def test_the_parent_keywords_are_new():
    """What ``TransformerConfig(**program)`` raised on before this PR: the keys."""
    new = {"rotary_layers", "moe_experts_held", "mtp_layers"}
    assert new <= set(program_of(_config())) and new <= set(tfm.TransformerConfig.__dataclass_fields__)


# -- the three surfaces against the reference -----------------------------------------------------


def test_apply_matches_the_reference_and_returns_its_choices(cfg, params, program, reference):
    tokens = _tokens(cfg, (2, 70))
    got, chosen = tfm.apply(cfg, params, tokens, return_routing=True)
    for row in range(2):
        ref = reference.routed_pass(program, params, tokens[row], np.arange(70), fetch=WHOLE)
        assert np.std(ref["logits"]) > 0.3
        assert np.max(np.abs(np.asarray(got[row]) - ref["logits"])) <= TOL
        np.testing.assert_array_equal(np.sort(np.asarray(chosen)[:, row]), np.sort(ref["own"]))


def test_loss_matches_the_reference(cfg, params, program, reference):
    tokens = _tokens(cfg, (2, 97), 4)
    got = float(tfm.causal_lm_loss(cfg, params, {"tokens": tokens}))
    assert abs(got - reference.lm_loss(program, params, tokens, fetch=WHOLE)) <= parity.TOL["loss"]


def test_mtp_logits_match_the_reference():
    program = program_of(_config(), "rehearse_mtp_program")
    reference = load_reference(program)
    cfg = tfm.TransformerConfig(dtype=jnp.float32, **program)
    params = parity._seeded_params(tfm, cfg)
    assert params["mtp"]["eh_proj"].shape == (128, 64)
    assert params["mtp"]["moe"]["experts"]["wi"].shape == (1, 4, 64, 32)
    tokens = _tokens(cfg, (61,), 5)
    logits, mtp = tfm.apply(cfg, params, tokens[None, :-1], mtp_tokens=tokens[None, 1:])
    want = reference.mtp_logits_at(program, params, tokens, np.arange(60), fetch=WHOLE)
    assert np.std(want) > 0.3 and np.max(np.abs(np.asarray(mtp[0]) - want)) <= TOL
    main = reference.logits_at(program, params, tokens[:-1], np.arange(60), fetch=WHOLE)
    assert np.max(np.abs(np.asarray(logits[0]) - main)) <= TOL  # the model's own are untouched
    assert np.max(np.abs(want - main)) > 0.1  # and the module's are another prediction


# -- the controls: what the tolerance must catch --------------------------------------------------


def _errors(cfg, params, program, reference):
    """max |system - reference| on apply, the cache path and the loss."""
    tokens = _tokens(cfg, (60,), 6)
    ref = reference.logits_at(program, params, tokens, np.arange(60), fetch=WHOLE)
    apply_err = float(np.max(np.abs(np.asarray(tfm.apply(cfg, params, tokens[None]), np.float32)[0]
                                    - ref)))
    got, _ = serve_kinds.probe_logits(cfg, params, [tokens[:50]], [64], tokens[None, 50:58])
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


@pytest.mark.parametrize("fault", ["every layer whole-context", "rotary on every layer",
                                   "window one short", "norm over the whole projection's width"])
def test_a_dropped_kind_fails_the_tolerance(cfg, params, program, reference, fault):
    """The system run with one flag of the layers' kinds wrong, against the
    reference of the configuration as stated: apply and the cache path both miss."""
    wrong = {"every layer whole-context": dict(local_attn_layers=None),
             "rotary on every layer": dict(rotary_layers=None),
             "window one short": dict(local_attn_window=WINDOW - 1),
             "norm over the whole projection's width": dict(layernorm_epsilon=1e-2)}[fault]
    errs = _errors(cfg.replace(**wrong), params, program, reference)
    assert errs["apply"] > 30 * TOL and errs["cache"] > 30 * TOL, errs


def test_float32_passes_where_the_controls_fail(cfg, params, program, reference):
    errs = _errors(cfg, params, program, reference)
    assert all(errs[k] <= parity.TOL[k] for k in errs), errs


# -- the share ------------------------------------------------------------------------------------


@pytest.mark.parametrize("rows", [64, 600], ids=["dense_form", "sorted_form"])
def test_the_eight_shares_add_up_to_the_uncut_layer(rows):
    """A routed layer of 16 experts cut into eight shares of 2: every share's
    output minus the shared expert (each chip computes it alike: counted once),
    summed, plus the shared expert, is the uncut layer's output; and the
    reference given the same share reads the same part."""
    base = dict(_config()["rehearse_program"], moe_experts_held=None)
    whole = tfm.TransformerConfig(dtype=jnp.float32, **base)
    params = parity._seeded_params(tfm, whole)
    layer = jax.tree.map(lambda a: a[1], params["moe"])
    h = jax.random.normal(jax.random.PRNGKey(rows), (1, rows, whole.hidden_size))
    want, _, chosen = dropless.moe_ffn_dropless(whole, layer, h)
    shared = dropless.shared_expert(layer["shared"], h[0])
    total = jnp.zeros_like(want)
    for s in range(8):
        cut = whole.replace(moe_experts_held=(2 * s, 2))
        part = {**layer, "experts": {k: v[2 * s:2 * s + 2] for k, v in layer["experts"].items()}}
        got, _, chose = dropless.moe_ffn_dropless(cut, part, h)
        np.testing.assert_array_equal(np.asarray(chose), np.asarray(chosen))  # the router is whole
        total = total + (got - shared[None])
    assert np.std(np.asarray(want - shared[None])) > 0.05
    np.testing.assert_allclose(np.asarray(total + shared[None]), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("layer", [None, 2], ids=["one_layers_slice", "held_stacks_in_place"])
def test_uneven_routing_takes_more_trips_and_drops_no_pair(layer):
    """Every token routed to held experts alone (8x the even share): the chunked
    loop takes as many trips as the pairs need and agrees with the dense form."""
    T, M, F, E, k, first, count = 640, 32, 16, 16, 4, 4, 4
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    shape = (3, count) if layer is not None else (count,)
    bank = {"wg": jax.random.normal(keys[0], shape + (M, F)) / 6,
            "wi": jax.random.normal(keys[1], shape + (M, F)) / 6,
            "wo": jax.random.normal(keys[2], shape + (F, M)) / 4}
    x = jax.random.normal(keys[3], (T, M))
    weights = jax.random.uniform(keys[4], (T, k))
    held_only = first + jnp.argsort(jax.random.uniform(keys[5], (T, count)), axis=-1)[:, :k]
    mixed = jnp.where(jnp.arange(T)[:, None] % 3 == 0, held_only, held_only + 6)  # some outside
    assert dropless.held_chunk_rows(T * k, count, E) == 1024 < T * k
    for experts in (held_only.astype(jnp.int32), mixed.astype(jnp.int32)):
        want = dropless.experts_dense(bank, x, weights, experts, layer, first)
        got = dropless.experts_sorted_held(bank, x, weights, experts, first, E, layer)
        assert float(jnp.std(want)) > 0.01
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_load_counts_the_held_experts_alone(cfg, params):
    tokens = _tokens(cfg, (1, 40), 7)
    _, chosen = tfm.apply(cfg, params, tokens, return_routing=True)
    live = jnp.ones((1, 40), bool)
    everyone = np.asarray(dropless.expert_load(chosen, live, cfg.num_experts))
    held = np.asarray(dropless.expert_load(chosen, live, cfg.num_experts, cfg.experts_held))
    assert everyone.shape == (5, 16) and everyone.sum() == 5 * 40 * 4
    np.testing.assert_array_equal(held, everyone[:, 4:8])


# -- the reference against the published code -----------------------------------------------------


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_reference_attention_agrees_with_transformers(program, reference, params, kind):
    """``Exaone4Attention`` (per-head q/k RMSNorm, rotary on sliding layers ONLY,
    the window handed to sliding layers alone) on the reference's own weights and
    ``transformers``' own masks: the attention sublayer's output is the
    reference's."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    from transformers import masking_utils
    from transformers.models.exaone4 import modeling_exaone4 as hf

    p, S = program, 50
    layer = {"sliding_attention": 1, "full_attention": 3}[kind]
    types = ["sliding_attention" if on else "full_attention" for on in p["local_attn_layers"]]
    config = transformers.Exaone4Config(
        vocab_size=p["vocab_size"], hidden_size=p["hidden_size"], num_hidden_layers=p["num_layers"],
        num_attention_heads=p["num_heads"], num_key_value_heads=p["num_kv_heads"],
        head_dim=p["qk_head_dim"], rms_norm_eps=p["layernorm_epsilon"], rope_theta=p["rotary_base"],
        sliding_window=p["local_attn_window"], sliding_window_pattern=4, layer_types=types,
        max_position_embeddings=p["max_seq_len"], attention_dropout=0.0)
    config._attn_implementation = "eager"
    attn = hf.Exaone4Attention(config, layer).eval()
    assert attn.is_sliding == (kind == "sliding_attention")
    t = lambda x: torch.from_numpy(np.array(x, np.float32))  # noqa: E731
    lp = {k: np.asarray(params["layers"][k][layer]) for k in reference.ATTENTION}
    d = p["hidden_size"]
    attn.load_state_dict({
        "q_proj.weight": t(lp["wq"].reshape(d, -1).T), "k_proj.weight": t(lp["wk"].reshape(d, -1).T),
        "v_proj.weight": t(lp["wv"].reshape(d, -1).T), "o_proj.weight": t(lp["wo"].reshape(-1, d).T),
        "q_norm.weight": t(lp["q_norm_scale"]), "k_norm.weight": t(lp["k_norm_scale"])})
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (S, d)), np.float32)
    with jax.default_matmul_precision("highest"):
        after, _ = reference._attend(
            jnp.asarray(x), {k: jnp.asarray(v) for k, v in lp.items()},
            eps=p["layernorm_epsilon"], base=p["rotary_base"],
            window=p["local_attn_window"] if attn.is_sliding else 0, rotary=attn.is_sliding)
        h = np.asarray(reference._rms(jnp.asarray(x), jnp.asarray(lp["ln1_scale"]),
                                      p["layernorm_epsilon"]))
    hidden = t(h)[None]
    position = torch.arange(S)[None]
    make = (masking_utils.create_sliding_window_causal_mask if attn.is_sliding
            else masking_utils.create_causal_mask)
    mask = make(config=config, input_embeds=hidden, attention_mask=None,
                cache_position=torch.arange(S), past_key_values=None, position_ids=position)
    with torch.no_grad():
        theirs, _ = attn(hidden, hf.Exaone4RotaryEmbedding(config)(hidden, position), mask)
    ours = np.asarray(after) - x
    assert np.std(ours) > 0.05 and np.max(np.abs(theirs[0].numpy() - ours)) <= TOL


def test_reference_routed_layer_agrees_with_transformers(reference):
    """``DeepseekV3MoE`` (sigmoid scores, selection bias, one group, normalised and
    scaled weights, a shared expert) on the reference's own weights: the UNCUT
    routed layer's output is the reference's."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    from transformers.models.deepseek_v3 import modeling_deepseek_v3 as hf

    p = Program(dict(_config()["rehearse_program"], moe_experts_held=None), "exaone_moe")
    cfg = tfm.TransformerConfig(dtype=jnp.float32, **p)
    moe = parity._seeded_params(tfm, cfg)["moe"]
    config = transformers.DeepseekV3Config(
        hidden_size=p["hidden_size"], moe_intermediate_size=p["intermediate_size"],
        n_shared_experts=1, n_routed_experts=p["num_experts"], n_group=1, topk_group=1,
        routed_scaling_factor=p["moe_routed_scale"], num_experts_per_tok=p["moe_top_k"],
        norm_topk_prob=p["moe_norm_topk_prob"], hidden_act="silu")
    assert p["moe_shared_size"] == p["intermediate_size"]  # num_shared_experts 1
    block = hf.DeepseekV3MoE(config).eval()
    t = lambda x: torch.from_numpy(np.array(x, np.float32))  # noqa: E731
    r = 1
    state = {"gate.weight": t(moe["gate"][r].T), "gate.e_score_correction_bias": t(moe["bias"][r])}
    mlps = {"shared_experts.": tuple(moe["shared"][k][r] for k in ("wg", "wi", "wo"))}
    for e in range(p["num_experts"]):
        mlps[f"experts.{e}."] = tuple(moe["experts"][k][r, e] for k in ("wg", "wi", "wo"))
    for name, (wg, wi, wo) in mlps.items():
        state.update({name + "gate_proj.weight": t(wg.T), name + "up_proj.weight": t(wi.T),
                      name + "down_proj.weight": t(wo.T)})
    block.load_state_dict(state)
    h = jax.random.normal(jax.random.PRNGKey(5), (40, p["hidden_size"]))
    xs = [jnp.zeros_like(h)]
    log = {"own": [[]], "slack": -np.inf, "differ": 0, "pairs": 0}
    with jax.default_matmul_precision("highest"):
        reference._routed_ffn(p, moe, r, xs, [h], None, log, WHOLE)
    with torch.no_grad():
        theirs = block(t(h)[None])[0].numpy()
    assert np.std(theirs) > 0.05 and np.max(np.abs(theirs - np.asarray(xs[0]))) <= TOL


def test_reference_attention_in_query_blocks_is_the_whole_matrix(program, reference, params):
    tokens = np.random.default_rng(1).integers(0, program["vocab_size"], size=70)
    one = reference.logits_at(program, params, tokens, np.arange(70), fetch=WHOLE)
    reference._attend.clear_cache()
    with mock.patch.object(reference, "QUERY_BLOCK", 16), mock.patch.object(reference,
                                                                           "ROW_BLOCK", 32):
        many = reference.logits_at(program, params, tokens, np.arange(70), fetch=WHOLE)
    reference._attend.clear_cache()
    np.testing.assert_allclose(many, one, atol=2e-5)


# -- what has no code is refused by name -----------------------------------------------------------

_REFUSED = {
    "kinds with latent attention": (dict(kv_lora_rank=32, qk_rope_head_dim=8, v_head_dim=16,
                                         num_kv_heads=0, qk_norm=False), "layer kinds"),
    "kinds with the mixer": (dict(ssm_state_size=16, ssm_heads=4, ssm_head_dim=16,
                                  moe_routing="gshard", moe_every=0, moe_experts_held=None,
                                  moe_score_fn="softmax", moe_select_bias=False,
                                  moe_routed_scale=1.0, moe_shared_size=0, moe_first_dense=0,
                                  dense_intermediate_size=None), "layer kinds"),
    "rotary_layers without rotary": (dict(pos_emb="none"), "rotary_layers"),
    "a flag a layer": (dict(local_attn_layers=(1, 0)), "one 0/1 flag a layer"),
    "flags without a window": (dict(local_attn_window=0), "local_attn_window"),
    "a share outside the router": (dict(moe_experts_held=(12, 8)), "moe_experts_held"),
    "a share without dropless routing": (dict(moe_routing="gshard", moe_score_fn="softmax",
                                              moe_select_bias=False, moe_routed_scale=1.0,
                                              moe_shared_size=0, moe_first_dense=0,
                                              dense_intermediate_size=None), "moe_experts_held"),
    "two modules": (dict(mtp_layers=2), "mtp_layers"),
    "a q/k norm of no kind": (dict(qk_norm="group"), "qk_norm"),
}


@pytest.mark.parametrize("case", list(_REFUSED))
def test_combinations_without_code_are_refused_by_name(program, case):
    extra, word = _REFUSED[case]
    with pytest.raises((NotImplementedError, ValueError), match=word):
        tfm.TransformerConfig(**{**program, **extra})


# -- the counts at the published widths ------------------------------------------------------------


def test_counts_at_the_published_widths():
    config = _config()
    program = program_of(config)
    counts = load_reference(program).param_counts(program)
    assert counts["total"] == 3_712_028_416  # ISSUE 38's reckoning from the published config
    assert counts["matmul_attention_per_layer"] == 113_246_464 - 256  # without the head norms
    assert counts["matmul_per_expert"] == 37_748_736 and counts["routed_layers"] == 4
    assert counts["experts_held"] == 16 and counts["held_pairs_per_token_per_layer"] == 1.0
    assert counts["matmul_on_token_path"] - 6144 * 19200 == 1_211_105_280  # 1.21 G a row
    real = tfm.TransformerConfig(dtype=jnp.bfloat16, **program)
    shapes = jax.eval_shape(lambda: tfm.init(real, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) == counts["total"]
    module = dict(program, mtp_layers=1)
    with_module = jax.eval_shape(lambda: tfm.init(tfm.TransformerConfig(**module),
                                                  jax.random.PRNGKey(0)))
    assert (sum(int(np.prod(x.shape)) for x in jax.tree.leaves(with_module))
            == load_reference(Program(module, "exaone_moe")).param_counts(module)["total"])
    # the cache: 4,096 B a position a layer; 69.2 MB a slot where one length would be 335.5
    assert kinds_cost.kv_bytes_per_token(program) == tfm.cache_bytes_per_token(real) == 4096
    assert kinds_cost.slot_cache_bytes(program, 16384) == 69_206_016
    assert 16384 * tfm.cache_bytes_per_token(real) + tfm.cache_ring_bytes(real) == 69_206_016
    assert 5 * 16384 * 4096 == 335_544_320
    # attention at what the model requires: one causal layer and four windows of 128
    rows = 4096
    pairs = rows * rows / 2 + 4 * (128 * 129 / 2 + (rows - 128) * 128)
    assert kinds_cost.attention_flops(program, rows) == 4 * 128 * 64 * pairs
    assert kinds_cost.flash_cost(program, rows)["bytes"] == 5 * rows * 144 * 128 * 2
    # a prefill whose router is even dispatches one pair a row a layer
    even = kinds_cost.prefill_flops(program, rows, 4 * rows)
    assert even == 2.0 * 1_211_105_280 * rows + 2.0 * 6144 * 19200 + 4 * 128 * 64 * pairs
    gemm = kinds_cost.grouped_gemm_cost(program, 4 * rows)
    assert gemm["flops"] == 2.0 * 4 * rows * 37_748_736
    assert gemm["bytes"] == (4 * 16 * 37_748_736 + 4 * rows * (3 * 6144 + 3 * 2048)) * 2
    # a decode step at 32 rows past 4,500 tokens, every held expert touched
    need = kinds_cost.decode_min_bytes(program, 32 * 4500, 32 * 128, 16.0)
    outside = counts["matmul_outside_experts"]
    assert need == (outside + 4 * 16 * 37_748_736) * 2 + (32 * 4500 + 4 * 32 * 128) * 4096
    assert abs(4 * 16 * 37_748_736 * 2 / need - 0.60) < 0.02  # the held experts: most of a step
