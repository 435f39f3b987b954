"""LFM2-24B-A2B on the normal path (PR 42): a gated short convolution in the
attention sublayer's place in five layers of the twin's seven (C A C C C A C), the
parameters in stacks BY OPERATOR, a slot cache that keeps K/V for the attention
layers alone and two rows of state a sequence for the others, a sigmoid router with
a selection bias and no shared expert behind one leading dense layer — against the
plain reference ``chipbench/references/lfm2_moe.py`` (itself held to
``transformers``' ``Lfm2ForCausalLM`` and ``DeepseekV3TopkRouter``), at the
configuration's rehearsal sizes on the CPU, seeded weights, float32 unless a test
says bfloat16."""

import inspect
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import deepspeed_tpu  # noqa: E402,F401
from chipbench import conv_cost, parity  # noqa: E402
from chipbench.drivers import serve_latent, serve_shortconv  # noqa: E402
from chipbench.layer_metrics import (  # noqa: E402
    conv_decode_hbm_floor_pct, conv_prefill_mfu_pct, kv_bytes_per_token_model,
    recurrent_state_bytes_per_slot)
from chipbench.references import Program, load_reference, program_of  # noqa: E402
from deepspeed_tpu.inference.serving import Request  # noqa: E402
from deepspeed_tpu.launcher.serving_worker import build_serving_engine  # noqa: E402
from deepspeed_tpu.models import transformer as tfm  # noqa: E402
from deepspeed_tpu.moe import dropless  # noqa: E402
from deepspeed_tpu.telemetry import tracing  # noqa: E402

WHOLE = lambda leaves: leaves  # noqa: E731
TOL = parity.TOL["apply"]  # float32 on both sides, summation order alone
CONFIG = "lfm2-24b-a2b-L9"
OPS = ["conv", "attn", "conv", "conv", "conv", "attn", "conv"]  # the twin's
STEPS = serve_shortconv.serve_latent.DECODE_STEPS


def _config():
    with open(os.path.join(ROOT, "chipbench", "configs", f"{CONFIG}.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def program():
    return program_of(_config(), serve_shortconv.TWIN)


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


def test_layout_is_stacks_by_operator_and_a_cache_by_kind(cfg, params, program):
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


# prompts of 1, 2 and 3 rows (fewer than, as many as, one more than the state's rows), a
# bucket's worth, and past one; each padded to its bucket (the second prompt 3 shorter)
@pytest.mark.parametrize("n", [1, 2, 3, 4, 16, 17, 50, 200])
def test_every_step_through_the_two_kind_cache_matches_the_reference(cfg, params, program,
                                                                      reference, n):
    """The probe of the chip's check (bucket-padded prefill under the live-row mask
    into a local cache, ``update_cache_slot``, 8 decode steps at per-row
    positions): the state a conv layer hands the steps is that of the last two
    LIVE rows, not the bucket's last two."""
    prompts = [_tokens(cfg, (n,), n), _tokens(cfg, (max(n - 3, 1),), n + 1)]
    forced = _tokens(cfg, (2, STEPS), n + 2)
    got, chosen = serve_shortconv.probe_logits(cfg, params, prompts, [_bucket(n)] * 2, forced)
    for j, (p, f) in enumerate(zip(prompts, forced)):
        rows = np.arange(len(p) - 1, len(p) + STEPS)
        ref = reference.routed_pass(program, params, np.concatenate([p, f]), rows, fetch=WHOLE,
                                    routing=chosen[j])
        assert np.max(np.abs(got[j] - ref["logits"])) <= TOL and ref["slack"] <= 1e-4


def _decode(cfg, params, cache, slot, start, tokens, n_rows=3):
    """Decode ``tokens`` at row ``slot`` from position ``start``, the other rows idle
    as ``SlotWorker`` rides them (position 0, their write dropped, not live) ->
    logits per step."""
    out = []
    for i, t in enumerate(tokens):
        toks = np.zeros((n_rows,), np.int32)
        pos = np.zeros((n_rows,), np.int32)
        wpos = np.full((n_rows,), tfm.cache_len(cache), np.int32)
        toks[slot], pos[slot], wpos[slot] = t, start + i, start + i
        logits, cache = tfm.apply_with_cache(
            cfg, params, toks[:, None], cache, jnp.asarray(pos), write_pos=jnp.asarray(wpos),
            live=jnp.asarray(np.arange(n_rows) == slot)[:, None])
        out.append(np.asarray(logits[slot, 0]))
    return np.stack(out), cache


def _prefill(cfg, params, cache, slot, prompt, bucket=None):
    n, bucket = len(prompt), bucket or _bucket(len(prompt))
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n] = prompt
    local = tfm.init_cache(cfg, 1, bucket)
    logits, local = tfm.apply_with_cache(cfg, params, padded, local, 0, last_index=n - 1,
                                         live=jnp.arange(bucket)[None, :] < n)
    return np.asarray(logits[0, 0]), tfm.update_cache_slot(cache, local, slot), local


@pytest.mark.parametrize("n", [1, 2, 3, 9])
def test_the_state_is_the_last_two_live_rows_of_the_filters_input(cfg, params, n):
    """A prompt of ``n`` rows padded to 16 leaves each conv layer the state an
    unpadded block of exactly ``n`` rows leaves (zero rows in front where the
    prompt is shorter than two), and padding drawn from other tokens changes
    nothing of it."""
    prompt = _tokens(cfg, (n,), n)
    _, _, padded = _prefill(cfg, params, tfm.init_cache(cfg, 1, 32), 0, prompt, bucket=16)
    exact = tfm.init_cache(cfg, 1, n)
    _, exact = tfm.apply_with_cache(cfg, params, prompt[None], exact, 0)
    state = np.asarray(padded[tfm.STATE]["conv"])
    assert state.shape == (5, 1, 2, 64)
    np.testing.assert_allclose(state, np.asarray(exact[tfm.STATE]["conv"]), atol=2e-5)
    assert np.abs(state[:, 0, -1]).min() > 0  # the last live row's input
    assert (n >= 2) == bool(np.abs(state[:, 0, 0]).max() > 0)  # before the start: zero
    noisy = np.full((1, 16), 7, np.int32)
    noisy[0, :n] = prompt
    local = tfm.init_cache(cfg, 1, 16)
    _, local = tfm.apply_with_cache(cfg, params, noisy, local, 0, last_index=n - 1,
                                    live=jnp.arange(16)[None, :] < n)
    np.testing.assert_array_equal(np.asarray(local[tfm.STATE]["conv"]), state)


def test_a_slot_reused_by_a_shorter_request_and_idle_rows_riding_the_steps(cfg, params):
    """A 90-token request, then a 1-token one in the same slot, while another slot
    holds a prefilled sequence that only rides along (idle: not live): every logit
    of the second request is ``apply``'s of it alone, the riding slot's state is
    untouched by the eight steps, and its own steps afterwards are ``apply``'s."""
    long, short, other = _tokens(cfg, (90,), 1), _tokens(cfg, (12,), 2), _tokens(cfg, (30,), 3)
    cache = tfm.init_cache(cfg, 3, 128)
    _, cache, _ = _prefill(cfg, params, cache, 1, long)
    _, cache, _ = _prefill(cfg, params, cache, 2, other[:20])
    parked = np.asarray(cache[tfm.STATE]["conv"])[:, 2].copy()
    _, cache = _decode(cfg, params, cache, 1, 90, _tokens(cfg, (5,), 4))
    first, cache, _ = _prefill(cfg, params, cache, 1, short[:1])  # a prompt of ONE row
    steps, cache = _decode(cfg, params, cache, 1, 1, short[1:9])  # eight steps
    want = np.asarray(tfm.apply(cfg, params, short[None]))[0]
    assert np.max(np.abs(first - want[0])) <= TOL
    assert np.max(np.abs(steps - want[1:9])) <= TOL
    state = np.asarray(cache[tfm.STATE]["conv"])
    np.testing.assert_array_equal(state[:, 2], parked)  # rode thirteen steps: moved by none
    assert not state[:, 0].any() and not np.asarray(cache["k"])[:, 0].any()  # never used
    rest, cache = _decode(cfg, params, cache, 2, 20, other[20:28])
    want = np.asarray(tfm.apply(cfg, params, other[None]))[0]
    assert np.max(np.abs(rest - want[20:28])) <= TOL


def test_a_row_not_marked_idle_moves_its_state(cfg, params):
    """The control of the test above: without ``live`` the riding row's state moves."""
    cache = tfm.init_cache(cfg, 2, 64)
    _, cache, _ = _prefill(cfg, params, cache, 1, _tokens(cfg, (20,), 3))
    parked = np.asarray(cache[tfm.STATE]["conv"])[:, 1].copy()
    pos = jnp.asarray([0, 0])
    _, cache = tfm.apply_with_cache(cfg, params, np.asarray([[5], [9]], np.int32), cache, pos,
                                    write_pos=jnp.asarray([0, 64]))
    assert np.abs(np.asarray(cache[tfm.STATE]["conv"])[:, 1] - parked).max() > 1e-3


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


_PLANTED = {  # one line of ``_short_conv`` / ``_filter_tail`` / the configuration wrong
    "the input gate dropped": ("_short_conv", "u = gate_in * z", "u = z"),
    "the output gate dropped": ("_short_conv", "gate_out * c.astype(h.dtype)",
                                "c.astype(h.dtype)"),
    "the taps out of order": ("_causal_filter", "* taps[j] for j", "* taps[K - 1 - j] for j"),
    "the state taken from the padding": ("_filter_tail", "if live is None else",
                                         "if True else"),
}


def _plant(monkeypatch, fault):
    """``tfm``'s function with one line replaced, as the module would have it."""
    name, old, new = _PLANTED[fault]
    source = inspect.getsource(getattr(tfm, name))
    assert source.count(old) == 1, (name, old)
    scope = dict(vars(tfm))
    exec(source.replace(old, new), scope)  # noqa: S102 -- the module's own source, one line changed
    monkeypatch.setattr(tfm, name, scope[name])


@pytest.mark.parametrize("fault", list(_PLANTED))
def test_a_planted_fault_fails_the_tolerance(cfg, params, program, reference, monkeypatch, fault):
    """Apply and the cache path both miss by far (a state from the padding: the
    cache path alone, ``apply`` pads nothing)."""
    _plant(monkeypatch, fault)
    errs = _errors(cfg, params, program, reference)
    assert errs["cache"] > 30 * TOL, errs
    assert (errs["apply"] > 30 * TOL) == (fault != "the state taken from the padding"), errs


# -- the chip's check: small for bfloat16 compute, large for what it must catch ------------------


class _Run:
    """What ``serve_latent._check`` reads of the harness's run."""

    cell = {"serving": {}}

    def __init__(self, program, seed):
        self.program, self.seed = program, seed

    def sized(self, block):
        return {"deployment": {"max_seq_len": 256, "n_slots": 4}}[block]


def _check(program, dtype="bfloat16", seed=7):
    """The driver's check (``serve_shortconv.run``'s prompts and probe in
    ``serve_latent._check``) on an engine built as the cell builds it."""
    srv = build_serving_engine({
        "model": {**program, "dtype": dtype},
        "engine_dtype": {"float32": "fp32", "bfloat16": "bf16"}[dtype],
        "serving": {"n_slots": 4, "max_seq_len": 256, "seed": 1, "watchdog_mode": "off"}})
    with serve_shortconv.as_this_cell():
        return serve_latent._check(_Run(program, seed), srv, Request)


def test_the_chips_check_passes_float32_compute_by_far(program):
    out = _check(program, "float32")
    assert out["ok"] and out["check_buckets"] == [128, 256, 256, 256], out
    assert out["logit_max_abs_err"] < 1e-4 and out["routing_slack"] < 1e-3


@pytest.mark.parametrize("fault", list(_PLANTED))
def test_a_planted_fault_fails_the_chips_check(program, monkeypatch, fault):
    """By ``serve_latent``'s two-part rule at the cell's own limits, in float32
    (and so in any precision): the engine serves the faulty program, the probe
    runs it too, and the reference keeps the architecture."""
    _plant(monkeypatch, fault)
    out = _check(program, "float32")
    assert not out["ok"], out
    assert (out["logit_tol"], out["routing_tol"]) == (serve_shortconv.LOGIT_TOL,
                                                      serve_shortconv.ROUTING_TOL)
    assert max(out["logit_max_abs_err"], out["token_gap_to_reference_top"]) \
        > 1.5 * serve_shortconv.LOGIT_TOL or out["routing_slack"] > 1.5 * serve_shortconv.ROUTING_TOL, out


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


# -- the lead shifts the routed position (PR 34's fault, planted) ----------------------------------


@pytest.mark.parametrize("rows", [48, 520], ids=["dense_form", "sorted_form"])
def test_the_stack_index_is_the_routed_layers_not_the_models(cfg, params, rows, monkeypatch):
    """Behind ONE leading dense layer the model's layer 1 is routed stack 0, inline
    and in the scanned periods alike: the in-place programs (one chip) give the
    sliced programs' logits, and with the model's layer number in the index's
    place they do not."""
    tokens = _tokens(cfg, (1, rows), rows)

    def run():
        cache = tfm.init_cache(cfg, 1, rows)
        return tfm.apply_with_cache(cfg, params, tokens, cache, 0, last_only=True)[0]

    sliced = run()
    seen = []
    real = dropless.moe_ffn_dropless

    def spy(c, p, h, layer=None):
        seen.append(layer is not None)
        return real(c, p, h, layer)

    monkeypatch.setattr(tfm, "_ACTIVE_MESH", [None])  # one chip: the banks read in place
    monkeypatch.setattr(tfm, "expert_bank_form", lambda *a, **k: "in_place")
    with monkeypatch.context() as m:
        m.setattr(dropless, "moe_ffn_dropless", spy)
        in_place = run()
    assert seen and all(seen)
    np.testing.assert_allclose(np.asarray(in_place), np.asarray(sliced), atol=2e-5)
    with monkeypatch.context() as m:
        m.setattr(dropless, "moe_ffn_dropless",
                  lambda c, p, h, layer=None: real(c, p, h, None if layer is None
                                                   else jnp.minimum(layer + 1, 5)))
        off_by_the_lead = run()
    assert float(jnp.max(jnp.abs(off_by_the_lead - sliced))) > 1e-2


def test_the_forward_only_loop_reads_the_operator_stacks_where_they_lie(cfg, params):
    """The cache path (forward only) hands its scanned periods the layers' indices
    and reads the held stacks at them; ``apply`` (a backward pass may follow) scans
    the periods' slices. Same logits."""
    def stacks_scanned(fn, *args):
        text = str(jax.make_jaxpr(fn)(*args))
        scans = [line for line in text.splitlines() if " scan[" in line]
        return text.count("f32[1,3,64,192]"), len(scans)  # a period's share of conv_in: [G, n, ...]

    tokens = _tokens(cfg, (1, 24))
    fwd_bwd = stacks_scanned(lambda p: tfm.apply(cfg, p, tokens), params)
    cache = tfm.init_cache(cfg, 1, 24)
    fwd = stacks_scanned(lambda p: tfm.apply_with_cache(cfg, p, tokens, cache, 0)[0], params)
    assert fwd_bwd[0] > 0 and fwd[0] == 0
    got = tfm.apply_with_cache(cfg, params, tokens, cache, 0)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(tfm.apply(cfg, params, tokens)), atol=2e-5)


def test_the_dense_form_batches_over_experts_from_64_rows(cfg, params):
    """``experts_dense`` at 63 rows (the rows handed over once for all experts) and
    at 64 (once an expert: a batched product, which reads each expert's bank where
    it lies): the same numbers, and the jaxpr says which form each took."""
    bank = jax.tree.map(lambda a: a[1], params["moe"]["experts"])
    n = dropless.BATCHED_ROWS
    x = jax.random.normal(jax.random.PRNGKey(0), (n, 64))
    weights = jax.random.uniform(jax.random.PRNGKey(1), (n, 4))
    experts = jnp.argsort(jax.random.uniform(jax.random.PRNGKey(2), (n, 16)))[:, :4].astype(jnp.int32)
    whole = dropless.experts_dense(bank, x, weights, experts)
    fewer = dropless.experts_dense(bank, x[:-1], weights[:-1], experts[:-1])
    assert float(jnp.std(whole)) > 0.01
    np.testing.assert_allclose(np.asarray(whole[:-1]), np.asarray(fewer), atol=2e-5)
    batched = lambda *a: str(jax.make_jaxpr(dropless.experts_dense)(bank, *a)).count(  # noqa: E731
        "dimension_numbers=(([2], [1]), ([0], [0]))")  # e a batch dimension of both operands
    assert batched(x, weights, experts) == 3 and batched(x[:-1], weights[:-1], experts[:-1]) == 1


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
    "remat": (dict(remat=True), "remat"),
    "dropout": (dict(hidden_dropout=0.1), "dropout"),
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


def _spec(program, dtype="float32", **serving):
    return {"model": {**program, "dtype": dtype},
            "engine_dtype": {"float32": "fp32", "bfloat16": "bf16"}[dtype],
            "serving": {"n_slots": 3, "max_seq_len": 128, "seed": 0, "watchdog_mode": "off",
                        **serving}}


@pytest.mark.parametrize("what,block", [
    ("prefix_cache", {"prefix_cache": {"enabled": True, "n_slots": 2}}),
    ("speculation", {"speculation": {"enabled": True}}),
    ("serving role 'prefill'", {"role": "prefill"}),
    ("serving role 'decode'", {"role": "decode"}),
])
def test_the_engine_refuses_at_build_what_moves_the_cache_by_position(program, what, block):
    with pytest.raises(NotImplementedError, match=what):
        build_serving_engine(_spec(program, **block))


# -- the serving engine ----------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served(program):
    srv = build_serving_engine(_spec(program))
    cfg = srv.engine.cfg
    prompts = [_tokens(cfg, (n,), n) for n in (40, 1, 70, 2, 9)]  # five requests, three slots
    t0 = time.perf_counter()
    results = srv.serve([Request(uid=i, prompt=p, max_new_tokens=12)
                         for i, p in enumerate(prompts)])
    return srv, prompts, results, tracing.spans(t0)


def test_serving_engine_serves_the_models_tokens(served):
    """Through ``build_serving_engine`` / ``ServingEngine.step`` / ``SlotWorker`` like
    any other model: five requests share three slots (two are reused, by a
    shorter and by a longer request); every token is the argmax of ``apply`` on
    what came before it."""
    srv, prompts, results, _ = served
    cfg, params = srv.engine.cfg, srv.engine.params
    for i, p in enumerate(prompts):
        got = np.asarray(results[i].tokens)
        assert results[i].status == "ok" and len(got) == 12
        logits = np.asarray(tfm.apply(cfg, params, np.concatenate([p, got])[None]))[0]
        want = logits[len(p) - 1:len(p) + 11]
        gap = want.max(axis=-1) - want[np.arange(12), got]
        assert gap.max() <= 1e-4, (i, gap)
    assert srv.compile_counts()["decode"] == 1


def test_spans_and_pools_say_what_was_kept_and_read(served):
    srv, prompts, _, spans = served
    w, cfg = srv.worker, srv.engine.cfg
    per_slot = 5 * 2 * 64 * 4  # conv layers x rows x channels x float32
    pools = w.hbm_pools()
    assert pools["slot_state"] == 3 * per_slot and w.state_bytes_per_slot == per_slot
    assert w.state_layers == 5 and "slot_kv_ring" not in pools
    assert pools["slot_kv_cache"] == 2 * 3 * 128 * tfm.cache_bytes_per_token(cfg)  # TWO layers'
    ctx = {"worker": w, "program": program_of(_config(), serve_shortconv.TWIN)}
    assert recurrent_state_bytes_per_slot.read(ctx) == per_slot
    assert kv_bytes_per_token_model.read(ctx) == 2 * tfm.cache_bytes_per_token(cfg)
    assert kv_bytes_per_token_model.read({**ctx, "program": {"num_layers": 2}}) is None
    prefills = [sp for sp in spans if sp.name == "prefill"]
    decodes = [sp for sp in spans if sp.name == "decode"]
    assert len(prefills) == 5 and decodes
    for sp in prefills + decodes:
        assert sp.attrs["conv_layers"] == 5 and sp.attrs["attn_layers"] == 2
        assert sp.attrs["attn"] == "dense" and 0 < sp.attrs["experts_touched"] <= 16
        assert sp.attrs["expert_load_max_over_mean"] >= 1
    for sp in decodes:
        assert sp.attrs["state_rows"] == sp.attrs["n_active"]
        assert sp.attrs["state_bytes"] == 2 * sp.attrs["n_active"] * per_slot
    by_len = {sp.attrs["true_len"]: sp.attrs for sp in prefills}
    assert sorted(by_len) == [1, 2, 9, 40, 70]
    assert all(a["state_rows"] == n and a["state_bytes"] == per_slot and
               a["expert_bank"] in ("in_place", "sliced") for n, a in by_len.items())
    full = [sp for sp in decodes if sp.attrs["n_active"] == 3]
    assert full and all(sp.attrs["cached_tokens"] >= 3 for sp in full)  # ONE attention layer's
    with pytest.raises(NotImplementedError, match="kv_export"):
        w.kv_export(16, 0, 0)
    with pytest.raises(NotImplementedError, match="kv_import"):
        w.kv_import(16, None, None, 0, 0)


def test_chunked_prefill_carries_the_state(program, reference):
    """Chunks of 16 rows through the ``chunk`` programs: each starts from the state
    the last one left (from nothing at position 0, whatever the slot held) and
    moves it on its live rows only; the tokens are the reference's."""
    srv = build_serving_engine(_spec(program, chunked_prefill={"enabled": True, "chunk_size": 16}))
    rng = np.random.default_rng(8)
    reqs = [Request(uid=i, prompt=rng.integers(0, program["vocab_size"], size=n).astype(np.int32),
                    max_new_tokens=5) for i, n in enumerate([50, 17, 33, 2, 90])]
    results = srv.serve(reqs)
    assert srv.compile_counts()["chunk_prefill"]
    for r in reqs:
        got = np.asarray(results[r.uid].tokens, np.int32)
        assert results[r.uid].status == "ok" and len(got) == 5
        ref = reference.logits_at(program, srv.engine.params, np.concatenate([r.prompt, got[:-1]]),
                                  np.arange(len(r.prompt) - 1, len(r.prompt) + 4), fetch=WHOLE)
        assert float(np.max(ref.max(axis=-1) - ref[np.arange(5), got])) < TOL, len(r.prompt)


# -- the readers and the counts at the published widths --------------------------------------------


def test_the_readers_count_by_operator(monkeypatch):
    """Two prefills and three decode steps on a hand-made ring at the published
    widths: the floor counts K/V in the TWO attention layers and the state moved,
    the MFU attention in two layers and the filter in seven; spans without the
    operators' attributes give nothing."""
    from types import SimpleNamespace

    program = program_of(_config())

    def call(i, name, t0, t1, **attrs):
        sp = lambda j, parent, n, a, b, **kw: SimpleNamespace(  # noqa: E731
            id=j, parent=parent, name=n, path="serve/step/" + n, t0=a, t1=b, attrs=kw)
        return [sp(i, None, name, t0, t1, compiled=False, **attrs),
                sp(i + 1, i, "dispatch", t0, t0 + 1e-4), sp(i + 2, i, "fetch", t0 + 1e-4, t1)]

    ops = dict(conv_layers=7, attn_layers=2)
    step = dict(cached_tokens=128 * 800, state_bytes=2 * 128 * 57344, experts_touched=64.0, **ops)
    ring = (call(1, "prefill", 100.0, 100.010, bucket=256, **ops)
            + call(4, "prefill", 100.02, 100.05, bucket=2048, **ops)
            + call(7, "prefill", 100.06, 100.07, bucket=512)  # a program without the operator
            + call(10, "decode", 100.10, 100.13, **step) + call(13, "decode", 100.14, 100.17, **step)
            + call(16, "decode", 100.18, 100.20, cached_tokens=5))
    monkeypatch.setattr(tracing, "spans", lambda since=float("-inf"): [
        sp for sp in ring if sp.t1 >= since])
    notes = []
    ctx = {"serve": {"epoch": 0.0, "window": (99.0, 101.0)}, "program": program,
           "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "run": SimpleNamespace(note=lambda **kw: notes.append(kw))}
    flops = conv_cost.prefill_flops(program, 256) + conv_cost.prefill_flops(program, 2048)
    assert conv_prefill_mfu_pct.read(ctx) == pytest.approx(100 * flops / 197e12 / 0.040)
    need = conv_cost.decode_min_bytes(program, 128 * 800, 2 * 128 * 57344, 64.0)
    assert conv_decode_hbm_floor_pct.read(ctx) == pytest.approx(100 * need / 819e9 / 0.030)
    assert {n["program"] for n in notes} == {"prefill", "decode"}
    plain = {**ctx, "program": program_of(_config(), "rehearse_program")}
    assert conv_prefill_mfu_pct.read(plain) is None and conv_decode_hbm_floor_pct.read(plain) is None


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


# -- the cell's rehearsal ---------------------------------------------------------------------------


def test_the_cells_rehearsal_passes_and_lists_its_metrics():
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", f"{CONFIG}.serve-longgen",
         "--rehearse", "--trace", "1"], cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] == "passed" and last["correct"] and last["failed"] == 0
    for name in ("kv_bytes_per_token_model", "conv_decode_hbm_floor_pct", "conv_prefill_mfu_pct",
                 "recurrent_state_bytes_per_slot", "moe_load_max_over_mean",
                 "compiles_in_window.doc", "decode_host_transfers"):
        assert name in last["would_report"], last["would_report"]
