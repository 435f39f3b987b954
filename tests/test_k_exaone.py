"""K-EXAONE-236B-A23B on the normal path (PR 38): sliding-window and whole-context
layers in one stack (a ring of ``local_attn_window`` positions a window layer, ``Smax``
a whole one), no rotary on the whole-context layers, per-head q/k RMSNorm, a held
SHARE of the routed experts and the multi-token-prediction module — against the
plain reference ``chipbench/references/exaone_moe.py`` (itself held to
``transformers``' ``Exaone4Attention`` and ``DeepseekV3MoE``), at the
configuration's rehearsal sizes on the CPU, seeded weights, float32 unless a test
says bfloat16."""

import json
import os
import subprocess
import sys
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import deepspeed_tpu  # noqa: E402,F401
from chipbench import kinds_cost, parity  # noqa: E402
from chipbench.drivers import serve_kinds  # noqa: E402
from chipbench.references import Program, load_reference, program_of  # noqa: E402
from deepspeed_tpu.inference.serving import Request  # noqa: E402
from deepspeed_tpu.launcher.serving_worker import build_serving_engine  # noqa: E402
from deepspeed_tpu.models import transformer as tfm  # noqa: E402
from deepspeed_tpu.moe import dropless  # noqa: E402
from deepspeed_tpu.telemetry import tracing  # noqa: E402

WHOLE = lambda leaves: leaves  # noqa: E731
TOL = parity.TOL["apply"]  # float32 on both sides, summation order alone
CONFIG = "k-exaone-236b-a23b-L5"
WINDOW = 16  # the kinds twin's


def _config():
    with open(os.path.join(ROOT, "chipbench", "configs", f"{CONFIG}.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def program():
    return program_of(_config(), "rehearse_kinds_program")


@pytest.fixture(scope="module")
def reference(program):
    return load_reference(program)


@pytest.fixture(scope="module")
def cfg(program):
    return tfm.TransformerConfig(dtype=jnp.float32, **program)


@pytest.fixture(scope="module")
def params(cfg):
    return parity._seeded_params(tfm, cfg)  # noise on every leaf: norm scales count too


def _tokens(cfg, shape, seed=0):
    return np.random.default_rng(seed).integers(0, cfg.vocab_size, size=shape).astype(np.int32)


def _bucket(n: int) -> int:
    return max(16, 1 << (n - 1).bit_length())


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


# shorter than the window, the window exactly, one more, several windows; each padded to a bucket
@pytest.mark.parametrize("n", [5, 16, 17, 50, 200])
def test_every_step_through_the_kinds_cache_matches_the_reference(cfg, params, program,
                                                                   reference, n):
    """The probe of the chip's check (bucket-padded prefill under the live-row
    mask into a local cache, ``update_cache_slot``, 8 decode steps at per-row
    positions): the ring holds the last 16 LIVE rows, not the bucket's last, and
    the steps wrap it."""
    prompts = [_tokens(cfg, (n,), n), _tokens(cfg, (max(n - 3, 1),), n + 1)]
    forced = _tokens(cfg, (2, serve_kinds.DECODE_STEPS), n + 2)
    got, chosen = serve_kinds.probe_logits(cfg, params, prompts, [_bucket(n)] * 2, forced)
    for j, (p, f) in enumerate(zip(prompts, forced)):
        rows = np.arange(len(p) - 1, len(p) + serve_kinds.DECODE_STEPS)
        ref = reference.routed_pass(program, params, np.concatenate([p, f]), rows, fetch=WHOLE,
                                    routing=chosen[j])
        assert np.max(np.abs(got[j] - ref["logits"])) <= TOL and ref["slack"] <= 1e-4


def _decode(cfg, params, cache, slot, start, tokens, n_rows=3):
    """Greedy-free decode of ``tokens`` at row ``slot`` from position ``start``,
    the other rows idle (position 0, their write dropped) -> logits per step."""
    out = []
    for i, t in enumerate(tokens):
        toks = np.zeros((n_rows,), np.int32)
        pos = np.zeros((n_rows,), np.int32)
        wpos = np.full((n_rows,), tfm.cache_len(cache), np.int32)
        toks[slot], pos[slot], wpos[slot] = t, start + i, start + i
        logits, cache = tfm.apply_with_cache(cfg, params, toks[:, None], cache,
                                             jnp.asarray(pos), write_pos=jnp.asarray(wpos))
        out.append(np.asarray(logits[slot, 0]))
    return np.stack(out), cache


def _prefill(cfg, params, cache, slot, prompt):
    n, bucket = len(prompt), _bucket(len(prompt))
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n] = prompt
    local = tfm.init_cache(cfg, 1, bucket)
    logits, local = tfm.apply_with_cache(cfg, params, padded, local, 0, last_index=n - 1,
                                         live=jnp.arange(bucket)[None, :] < n)
    return np.asarray(logits[0, 0]), tfm.update_cache_slot(cache, local, slot)


def test_a_slot_reused_by_a_shorter_request_reads_nothing_of_the_last(cfg, params):
    """A 90-token request, then a 7-token one in the same slot, idle rows riding
    along: every logit is ``apply``'s of the second sequence alone (the ring's
    stale entries hold positions the mask counts as never written)."""
    long, short = _tokens(cfg, (90,), 1), _tokens(cfg, (40,), 2)
    cache = tfm.init_cache(cfg, 3, 128)
    _, cache = _prefill(cfg, params, cache, 1, long)
    _, cache = _decode(cfg, params, cache, 1, 90, _tokens(cfg, (5,), 3))
    first, cache = _prefill(cfg, params, cache, 1, short[:7])
    steps, cache = _decode(cfg, params, cache, 1, 7, short[7:])  # past two wraps of the ring
    want = np.asarray(tfm.apply(cfg, params, short[None]))[0]
    assert np.max(np.abs(first - want[6])) <= TOL
    assert np.max(np.abs(steps - want[7:])) <= TOL
    # the idle rows' writes were dropped: their rings are as they were made
    assert not np.asarray(cache[tfm.RING]["k"])[:, [0, 2]].any()


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


# -- GPT-Neo's local layers are the same thing -----------------------------------------------------


@pytest.mark.parametrize("decode_attn", ["xla", "kernel"])
def test_gpt_neo_local_layers_are_served_through_the_same_rings(decode_attn):
    """GPT-Neo's alternating local attention (learned positions, multi-head, a
    LayerNorm block) through ``apply_with_cache``, which refused it before this
    PR: a ring for the local layers, ``Smax`` (and, where asked, the Pallas decode
    kernel) for the global ones; every logit is ``apply``'s."""
    cfg = tfm.TransformerConfig(vocab_size=211, max_seq_len=128, num_layers=4, num_heads=4,
                                hidden_size=64, local_attn_window=8,
                                local_attn_layers=(0, 1, 0, 1), decode_attn=decode_attn)
    params = parity._seeded_params(tfm, cfg)
    tokens = _tokens(cfg, (45,), 8)
    want = np.asarray(tfm.apply(cfg, params, tokens[None]))[0]
    cache = tfm.init_cache(cfg, 3, 128)
    assert cache["k"].shape[0] == 2 and cache[tfm.RING]["k"].shape[:3] == (2, 3, 8)
    first, cache = _prefill(cfg, params, cache, 1, tokens[:21])
    steps, _ = _decode(cfg, params, cache, 1, 21, tokens[21:])
    assert np.max(np.abs(first - want[20])) <= 2e-5 and np.max(np.abs(steps - want[21:])) <= 2e-5


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


def test_the_cache_path_refuses_what_a_ring_cannot_carry(cfg, params):
    cache = tfm.init_cache(cfg, 1, 64)
    block = _tokens(cfg, (1, 8))
    with pytest.raises(NotImplementedError, match="past position 0"):  # a chunk, a verify block
        tfm.apply_with_cache(cfg, params, block, cache, jnp.asarray([20]))
    with pytest.raises(ValueError, match="live"):  # a padded block with no live-row mask
        tfm.apply_with_cache(cfg, params, block, tfm.init_cache(cfg, 1, 8), 0, last_index=4)
    alibi = tfm.TransformerConfig(vocab_size=64, max_seq_len=64, num_layers=2, num_heads=2,
                                  hidden_size=32, pos_emb="alibi", local_attn_window=4,
                                  local_attn_layers=(1, 0), decode_attn="xla")
    with pytest.raises(NotImplementedError, match="alibi"):
        tfm.apply_with_cache(alibi, tfm.init(alibi, jax.random.PRNGKey(0)),
                             np.zeros((1, 4), np.int32), tfm.init_cache(alibi, 1, 4), 0)


def _spec(program, **serving):
    return {"model": {**program, "dtype": "float32"}, "engine_dtype": "fp32",
            "serving": {"n_slots": 3, "max_seq_len": 128, "seed": 0, "watchdog_mode": "off",
                        **serving}}


@pytest.mark.parametrize("what,block", [
    ("prefix_cache", {"prefix_cache": {"enabled": True, "n_slots": 2}}),
    ("chunked_prefill", {"chunked_prefill": {"enabled": True, "chunk_size": 16}}),
    ("speculation", {"speculation": {"enabled": True}}),
    ("role", {"role": "prefill"}),
])
def test_the_engine_refuses_at_build_what_moves_the_cache_by_position(program, what, block):
    with pytest.raises(NotImplementedError, match="window layers"):
        build_serving_engine(_spec(program, **block))


# -- the serving engine ----------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served(program):
    srv = build_serving_engine(_spec(program))
    cfg = srv.engine.cfg
    prompts = [_tokens(cfg, (n,), n) for n in (40, 9, 70)]
    mark = tracing.spans(0.0)[-1].t1 if tracing.spans(0.0) else 0.0
    results = srv.serve([Request(uid=i, prompt=p, max_new_tokens=12)
                         for i, p in enumerate(prompts)])
    return srv, prompts, results, [sp for sp in tracing.spans(0.0) if sp.t0 >= mark]


def test_serving_engine_serves_the_models_tokens(served):
    """Through ``build_serving_engine`` / ``ServingEngine.step`` / ``SlotWorker``
    like any other model: three requests of three buckets share the slots; every
    token is the argmax of ``apply`` on what came before it."""
    srv, prompts, results, _ = served
    cfg, params = srv.engine.cfg, srv.engine.params
    for i, p in enumerate(prompts):
        got = np.asarray(results[i].tokens)
        assert results[i].status == "ok" and len(got) == 12
        logits = np.asarray(tfm.apply(cfg, params, np.concatenate([p, got])[None]))[0]
        want = logits[len(p) - 1:len(p) + 11]
        gap = want.max(axis=-1) - want[np.arange(12), got]
        assert gap.max() <= 1e-4, gap
    assert srv.compile_counts()["decode"] == 1


def test_spans_and_pools_say_what_was_read(served):
    srv, prompts, _, spans = served
    pools = srv.worker.hbm_pools()
    assert pools["slot_kv_cache"] == 1 * 3 * 128 * tfm.cache_bytes_per_token(srv.engine.cfg)
    assert pools["slot_kv_ring"] == 3 * tfm.cache_ring_bytes(srv.engine.cfg)
    prefills = [sp for sp in spans if sp.name == "prefill"]
    decodes = [sp for sp in spans if sp.name == "decode"]
    assert {sp.attrs["attn"] for sp in prefills} == {"dense+window"}
    assert {sp.attrs["attn"] for sp in decodes} == {"dense+ring"}
    for sp in prefills + decodes:
        assert sp.attrs["window_layers"] == 5 and sp.attrs["experts_held"] == 4
        assert 0 < sp.attrs["experts_touched"] <= 4 and sp.attrs["expert_rows_held"] > 0
    by_len = {sp.attrs["true_len"]: sp for sp in prefills}
    for p in prompts:  # a prefill's queries each read min(position + 1, window) of a ring
        n = len(p)
        assert by_len[n].attrs["ring_tokens"] == sum(min(i + 1, WINDOW) for i in range(n))
        assert by_len[n].attrs["ring_tokens"] == kinds_cost.window_pairs(n, WINDOW)
    full = [sp for sp in decodes if sp.attrs["n_active"] == 3]
    assert full and all(sp.attrs["ring_tokens"] <= 3 * WINDOW < sp.attrs["cached_tokens"]
                        for sp in full[2:])
    with pytest.raises(NotImplementedError, match="window layers"):
        srv.worker.kv_export(16, 0, 0)


# -- a prefill through a flash+window bucket (PR 40: the banded forward) --------------------------

LONG = 1024  # a bucket whose band is narrower than its causal grid at the band's blocks (512, 128)


@pytest.fixture
def flash_from_1024_rows(monkeypatch, cfg):
    """``cache_attention_form``'s rule met at 1,024 rows of the twin's 4 heads (the
    cell meets it from 1,024 rows at 64): the bucket attends through the flash
    kernel, its window layers through the band."""
    monkeypatch.setattr(tfm, "DENSE_SCORE_BYTES", 4 * cfg.num_heads * LONG ** 2 - 1)
    assert tfm.cache_block_form(cfg, LONG) == "flash+window"
    assert tfm.cache_block_form(cfg, LONG // 2) == "dense+window"


def test_a_flash_window_prefill_is_the_reference_and_writes_the_parents_rings(
        cfg, params, program, reference, flash_from_1024_rows, monkeypatch):
    """A 700-token prompt padded to 1,024 rows: the window layers take the banded
    forward (the window a constant of the trace). Its logits are the reference's,
    and logits and rings are those of the parent's form (the whole causal grid
    under the window as a runtime operand: ``static_window`` made to see no
    constant) and of the dense form under a [T, T] bias."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    prompt = _tokens(cfg, (700,), 40)
    band_logits, band_cache = _prefill(cfg, params, tfm.init_cache(cfg, 2, LONG), 1, prompt)
    ref = reference.routed_pass(program, params, prompt, np.arange(699, 700), fetch=WHOLE)
    assert np.max(np.abs(band_logits - ref["logits"][0])) <= TOL

    def rings(cache):
        return [np.asarray(cache[tfm.RING][name]) for name in ("k", "v")]

    assert all(r[:, 1].any() and not r[:, 0].any() for r in rings(band_cache))
    with monkeypatch.context() as parent:
        parent.setattr(fa, "static_window", lambda window, *shape: (window, 0))
        whole_logits, whole_cache = _prefill(cfg, params, tfm.init_cache(cfg, 2, LONG), 1, prompt)
    with monkeypatch.context() as dense:
        dense.setattr(tfm, "DENSE_SCORE_BYTES", 2 ** 40)
        dense_logits, dense_cache = _prefill(cfg, params, tfm.init_cache(cfg, 2, LONG), 1, prompt)
    for logits, cache in ((whole_logits, whole_cache), (dense_logits, dense_cache)):
        assert np.max(np.abs(band_logits - logits)) <= TOL
        for got, want in zip(rings(band_cache), rings(cache)):
            np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)


def test_the_engine_serves_a_flash_window_bucket_and_says_which_grid(program,
                                                                     flash_from_1024_rows):
    """Through ``build_serving_engine`` with a 1,024-long slot cache: a 700-token
    request's prefill goes through the banded forward and its tokens are the
    argmax of ``apply``; the prefill span says which grid the window layers took
    and how much of the causal grid it computes; a short request's bucket attends
    densely and says nothing of a grid."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    spec = _spec({**program, "max_seq_len": LONG})
    spec["serving"]["max_seq_len"] = LONG
    srv = build_serving_engine(spec)
    cfg = srv.engine.cfg
    prompts = [_tokens(cfg, (700,), 41), _tokens(cfg, (30,), 42)]
    mark = tracing.spans(0.0)[-1].t1 if tracing.spans(0.0) else 0.0
    results = srv.serve([Request(uid=i, prompt=p, max_new_tokens=6) for i, p in enumerate(prompts)])
    for i, p in enumerate(prompts):
        res = results[i]
        got = np.asarray(res.tokens)
        assert res.status == "ok" and len(got) == 6
        logits = np.asarray(tfm.apply(cfg, srv.engine.params, np.concatenate([p, got])[None]))[0]
        want = logits[len(p) - 1:len(p) + 5]
        assert (want.max(axis=-1) - want[np.arange(6), got]).max() <= 1e-4
    by_bucket = {sp.attrs["bucket"]: sp.attrs for sp in tracing.spans(0.0)
                 if sp.name == "prefill" and sp.t0 >= mark}
    long, short = by_bucket[LONG], by_bucket[32]
    assert (long["attn"], long["window_grid"]) == ("flash+window", "band")
    blocks_pct = fa.window_grid(LONG, WINDOW, cfg.num_heads, cfg.head_dim, cfg.head_dim,
                                jnp.dtype(cfg.dtype).itemsize)[1]
    assert long["window_blocks_pct"] == round(blocks_pct, 2) < 100
    assert short["attn"] == "dense+window" and "window_grid" not in short


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


# -- the cell's rehearsal ---------------------------------------------------------------------------


def test_the_cells_rehearsal_passes_and_lists_its_metrics():
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", f"{CONFIG}.serve-mixedlen",
         "--rehearse", "--trace", "1"], cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] == "passed" and last["correct"] and last["failed"] == 0
    for name in ("slot_cache_bytes_per_slot", "kinds_decode_hbm_floor_pct", "kinds_prefill_mfu_pct",
                 "moe_load_max_over_mean", "compiles_in_window.doc", "decode_host_transfers"):
        assert name in last["would_report"], last["would_report"]
