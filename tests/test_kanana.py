"""kanana-2-30b-a3b (the DeepSeek-V3 block) on the normal path (PR 31): latent
attention with a latent slot cache in its two forms, a sigmoid router with a
selection bias, normalised and scaled weights, a shared expert and a leading
dense gated layer — against the plain reference
``chipbench/references/deepseek_v3.py`` (itself held to ``transformers``'
``DeepseekV3ForCausalLM``), at the configuration's rehearsal size on the CPU,
seeded weights, float32 unless a test says bfloat16."""

import json
import os
import sys
import time
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import deepspeed_tpu  # noqa: E402
from chipbench import mla_cost, parity  # noqa: E402
from chipbench.drivers import serve_latent, serve_routed  # noqa: E402
from chipbench.references import load_reference, program_of  # noqa: E402
from deepspeed_tpu.inference import serving  # noqa: E402
from deepspeed_tpu.launcher.serving_worker import build_serving_engine  # noqa: E402
from deepspeed_tpu.models import transformer as tfm  # noqa: E402
from deepspeed_tpu.moe import dropless  # noqa: E402
from deepspeed_tpu.telemetry import tracing  # noqa: E402

WHOLE = lambda leaves: leaves  # noqa: E731
TOL = parity.TOL["apply"]  # float32 on both sides, summation order alone
CONFIG = "kanana-2-30b-a3b-L7"


def _config():
    with open(os.path.join(ROOT, "chipbench", "configs", f"{CONFIG}.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def program():
    return program_of(_config(), "rehearse_program")


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


def test_layout_is_the_latent_block_over_a_leading_dense_layer(cfg):
    params = tfm.init(cfg, jax.random.PRNGKey(0))
    L, d, H, E = cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.num_experts
    R, Dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    shapes = {k: v.shape for k, v in params["layers"].items()}
    assert shapes == {
        "ln1_scale": (L, d), "ln2_scale": (L, d), "wq": (L, d, H, cfg.head_dim),
        "wkv_a": (L, d, R + Dr), "kv_norm_scale": (L, R),
        "wkv_b": (L, R, H, cfg.head_dim - Dr + cfg.value_head_dim),
        "wo": (L, H, cfg.value_head_dim, d)}
    lead = cfg.moe_first_dense
    assert {k: v.shape for k, v in params["dense_ffn"].items()} == {
        "wg": (lead, d, cfg.dense_ffn_size), "wi": (lead, d, cfg.dense_ffn_size),
        "wo_mlp": (lead, cfg.dense_ffn_size, d)}
    moe = params["moe"]
    assert moe["gate"].shape == (L - lead, d, E) and moe["bias"].shape == (L - lead, E)
    assert moe["experts"]["wg"].shape == (L - lead, E, d, cfg.ffn_size)
    assert moe["shared"]["wo"].shape == (L - lead, cfg.moe_shared_size, d)
    # the selection bias is DRAWN, so that choosing by score + bias is not choosing by score
    assert 0.5 * dropless.SELECT_BIAS_STD < float(jnp.std(moe["bias"])) < 2 * dropless.SELECT_BIAS_STD
    axes = tfm.logical_axes(cfg)
    assert jax.tree.structure(axes, is_leaf=lambda x: isinstance(x, tuple)) == \
        jax.tree.structure(params)
    # what an engine holds: the router, its bias and every norm scale float32, the rest bf16
    held = tfm.hold_for_compute(cfg.replace(dtype=jnp.bfloat16), params)
    f32 = {jax.tree_util.keystr(p) for p, x in jax.tree_util.tree_flatten_with_path(held)[0]
           if x.dtype == jnp.float32}
    assert f32 == {"['layers']['kv_norm_scale']", "['layers']['ln1_scale']",
                   "['layers']['ln2_scale']", "['lnf_scale']", "['moe']['bias']",
                   "['moe']['gate']"}


_REFUSED = {
    "alibi": (dict(pos_emb="alibi"), "pos_emb='alibi'"),
    "qk_norm": (dict(qk_norm=True), "qk_norm"),
    "decode kernel": (dict(decode_attn="kernel"), "decode_attn='kernel'"),
    "ring training": (dict(attn_impl="ring"), "attn_impl='ring'"),
    "biases": (dict(use_bias=True), "use_bias"),
    "int8 weights": (dict(weight_bits=8), "weight_bits"),
    "head sizes without a latent": (dict(kv_lora_rank=0), "without latent attention"),
    "router forms without the dropless block": (
        dict(moe_routing="gshard", activation="gelu", kv_lora_rank=0, qk_head_dim=0, v_head_dim=0,
             qk_rope_head_dim=0), "only moe_routing='dropless'"),
    "every layer dense": (dict(moe_first_dense=3), "moe_first_dense"),
}


@pytest.mark.parametrize("case", list(_REFUSED))
def test_combinations_without_code_are_refused_by_name(program, case):
    fields, named = _REFUSED[case]
    with pytest.raises(NotImplementedError, match=named):
        tfm.TransformerConfig(**{**program, **fields})


def test_disaggregated_roles_refuse_the_latent_cache(program):
    with pytest.raises(NotImplementedError, match="latent"):
        build_serving_engine({"model": {**program, "dtype": "float32"}, "engine_dtype": "fp32",
                              "serving": {"n_slots": 2, "max_seq_len": 128, "role": "prefill"}})


def test_flash_backward_takes_value_heads_of_their_own_width():
    """Since PR 64 (it refused them by name before): dV and dO are as wide as the
    value heads, dQ and dK as the q/k heads."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k = (jax.random.normal(kk, (1, 128, 2, 48)) * 0.5 for kk in ks[:2])
    v = jax.random.normal(ks[2], (1, 128, 2, 32)) * 0.5
    loss = lambda fn: lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)
    got = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: tfm.xla_attention(q, k, v, causal=True)),
                    argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5)


def test_training_through_the_flash_kernels_matches_the_xla_form(cfg, params):
    """``attn_impl="flash"`` with latent attention (refused until PR 64: the backward
    kernels took one head size): the expanded heads, 48-wide q/k beside 32-wide v
    here, go through the forward kernel and the one backward kernel, and the loss
    and every leaf's gradient are the XLA form's."""
    tokens = _tokens(cfg, (2, 129), seed=4)

    def loss_and_grads(c):
        model = tfm.Model(c)
        return jax.value_and_grad(lambda p: model.loss(p, {"tokens": tokens}))(params)

    want, g_want = loss_and_grads(cfg)
    got, g_got = loss_and_grads(cfg.replace(attn_impl="flash"))
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    worst = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-12)),
                         g_got, g_want)
    assert max(jax.tree.leaves(worst)) < 1e-4, worst


def test_apply_matches_the_reference_and_returns_its_choices(cfg, params, program, reference):
    tokens = _tokens(cfg, (2, 80))
    logits, chosen = tfm.apply(cfg, params, tokens, return_routing=True)
    routed = cfg.num_layers - cfg.moe_first_dense
    assert chosen.shape == (routed, 2, 80, cfg.moe_top_k) and chosen.dtype == jnp.int32
    for j, row in enumerate(tokens):
        ref = reference.routed_pass(program, params, row, np.arange(80), fetch=WHOLE)
        assert np.max(np.abs(np.asarray(logits[j]) - ref["logits"])) <= TOL
        assert np.array_equal(np.sort(np.asarray(chosen[:, j]), axis=-1),
                              np.sort(ref["own"], axis=-1))


@pytest.mark.parametrize("prefill", ["dense", "flash"])
def test_every_step_through_the_latent_cache_matches_the_reference(cfg, params, program,
                                                                   reference, prefill, monkeypatch):
    """What the serving programs run: a bucket-padded prefill that fills its
    local cache (the expanded form: densely, or through the flash kernel in
    interpret mode with q/k heads of 48 and value heads of 32), written into a
    slot; then decode steps, a verify block and a prompt chunk at per-row
    positions, all in the absorbed form over the cached latent."""
    bucket, n = 128, 97
    if prefill == "flash":
        monkeypatch.setattr(tfm, "DENSE_SCORE_BYTES", 4 * cfg.num_heads * bucket ** 2 - 1)
    assert tfm.cache_attention_form(cfg.num_heads, 1, bucket, bucket) == prefill
    tokens = _tokens(cfg, (n + 2 + 3 + 16,), 1)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n] = tokens[:n]
    first, local = tfm.apply_with_cache(cfg, params, padded, tfm.init_cache(cfg, 1, bucket), 0,
                                        last_index=n - 1)
    assert {k: v.shape[3:] for k, v in local.items()} == {
        "k": (1, cfg.qk_rope_head_dim), "v": (1, cfg.kv_lora_rank)}
    cache = tfm.update_cache_slot(tfm.init_cache(cfg, 2, 256), local, 1)  # row 0 stays idle
    got, at = [np.asarray(first[0, 0])], n

    def step(block):
        nonlocal cache, at
        toks = np.zeros((2, len(block)), np.int32)
        toks[1] = block
        pos = jnp.asarray([0, at], jnp.int32)
        logits, cache = tfm.apply_with_cache(cfg, params, toks, cache, pos,
                                             write_pos=jnp.asarray([256, at], jnp.int32))
        got.extend(np.asarray(logits[1]))
        at += len(block)

    for tok in tokens[n:n + 2]:  # decode
        step([tok])
    step(tokens[n + 2:n + 5])  # a verify block of three
    # a chunk of 16 through the slot's window, as SlotWorker._build_chunk does
    window = tfm.slice_cache_slot(cache, 1, 256)
    logits, window = tfm.apply_with_cache(cfg, params, tokens[None, n + 5:], window,
                                          jnp.asarray([at], jnp.int32))
    got.extend(np.asarray(logits[0]))
    ref = reference.logits_at(program, params, tokens, np.arange(n - 1, len(tokens)), fetch=WHOLE)
    assert np.max(np.abs(np.stack(got) - ref)) <= TOL


def test_the_absorbed_form_equals_the_expanded_one_on_the_same_cache(cfg, params):
    """One layer's attention both ways on the same projected q, rotary keys and
    latents; and the whole model: a lock-step prompt SHORTER than its cache
    attends in the absorbed form, one as long as its cache in the expanded."""
    lp = jax.tree.map(lambda x: x[1], params["layers"])
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    B, S = 2, 40
    q = jax.random.normal(keys[0], (B, S, cfg.num_heads, cfg.head_dim))
    k_pe = jax.random.normal(keys[1], (B, S, 1, cfg.qk_rope_head_dim))
    c = jax.random.normal(keys[2], (B, S, 1, cfg.kv_lora_rank))
    expanded = tfm.xla_attention(q, *tfm._latent_expand(cfg, lp, k_pe, c))
    absorbed = tfm._latent_attention(cfg, lp, q, k_pe, c, 0)
    assert expanded.shape == absorbed.shape == (B, S, cfg.num_heads, cfg.value_head_dim)
    assert float(jnp.max(jnp.abs(expanded - absorbed))) < 2e-5
    tokens = _tokens(cfg, (2, 64), 2)
    short, _ = tfm.apply_with_cache(cfg, params, tokens, tfm.init_cache(cfg, 2, 128), 0)
    whole, _ = tfm.apply_with_cache(cfg, params, tokens, tfm.init_cache(cfg, 2, 64), 0)
    assert float(jnp.max(jnp.abs(short - whole))) < 1e-4


def test_loss_and_its_gradients_match_the_reference(cfg, params, program, reference):
    """The loss to parity.py's tolerance; gradients against central differences
    of the REFERENCE's loss along a seeded direction, the experts held at the
    choices of the point differentiated at (tests/test_olmoe.py has the why)."""
    batch = {"tokens": _tokens(cfg, (2, 49), 3)}
    loss, grads = jax.value_and_grad(lambda p: tfm.causal_lm_loss(cfg, p, batch))(params)
    ref_loss = reference.lm_loss(program, params, batch["tokens"], fetch=WHOLE)
    assert abs(float(loss) - ref_loss) <= parity.TOL["loss"]
    # held and not trained by the loss: the selection bias has no gradient
    assert float(jnp.max(jnp.abs(grads["moe"]["bias"]))) == 0.0
    held = [reference.routed_pass(program, params, row[:-1], [0], fetch=WHOLE)["own"]
            for row in batch["tokens"]]
    leaves = {"router": ("moe", "gate"), "shared expert": ("moe", "shared", "wi"),
              "latent norm": ("layers", "kv_norm_scale"), "expansion": ("layers", "wkv_b"),
              "latent projection": ("layers", "wkv_a"), "leading dense": ("dense_ffn", "wg")}
    for name, path in leaves.items():
        leaf = params
        for key in path:
            leaf = leaf[key]
        g = grads
        for key in path:
            g = g[key]
        # half a seeded direction, half the gradient's own: a random direction alone moves
        # the loss by less than float32 differences resolve for the leaves deep in a sum
        direction = jax.random.normal(jax.random.PRNGKey(len(name)), leaf.shape)
        direction = direction / jnp.linalg.norm(direction) + g / jnp.linalg.norm(g)
        direction = direction / jnp.linalg.norm(direction)

        def moved(eps):
            out = jax.tree.map(lambda x: x, params)
            node = out
            for key in path[:-1]:
                node = node[key]
            node[path[-1]] = leaf + eps * direction
            return reference.lm_loss(program, out, batch["tokens"], fetch=WHOLE, routing=held)

        want = (moved(2e-2) - moved(-2e-2)) / 4e-2
        got = float(jnp.sum(g * direction))
        assert abs(got - want) <= 0.05 * abs(want) + 5e-5, (name, got, want)
        assert abs(want) > 1e-4, (name, want)  # the direction moves the loss


def test_router_hand_written_cases():
    """Eight experts, two a token, logits given (the gate is the identity)."""
    eye = jnp.eye(8)
    logits = jnp.asarray([[2.0, 1.9, 1.0, 0.0, -1.0, -1.0, -2.0, -3.0]])
    s = np.asarray(jax.nn.sigmoid(logits[0]))
    route = lambda **kw: dropless.route(logits, eye, 2, True, score_fn="sigmoid", **kw)  # noqa: E731
    # a zero bias reproduces the top-k of the scores
    w0, e0, scores = route(select_bias=jnp.zeros(8), scale=2.448)
    assert sorted(np.asarray(e0[0])) == [0, 1] and np.allclose(np.asarray(scores[0]), s)
    assert np.isclose(float(w0.sum()), 2.448)  # normalised, then scaled
    assert np.allclose(np.asarray(w0[0]), 2.448 * s[[0, 1]] / s[[0, 1]].sum())
    # a bias that changes the choice selects and does not weigh: the weights are those of s
    bias = jnp.zeros(8).at[2].set(0.5)
    w1, e1, _ = route(select_bias=bias, scale=2.448)
    assert list(np.asarray(e1[0])) == [2, 0]  # s[2] + 0.5 = 1.23 > s[0] = 0.88 > s[1]
    assert np.allclose(np.asarray(w1[0]), 2.448 * s[[2, 0]] / s[[2, 0]].sum())
    # the softmax form, as OLMoE runs it, is what it was
    w2, e2, probs = dropless.route(logits, eye, 2, False)
    assert np.allclose(np.asarray(probs[0]), np.asarray(jax.nn.softmax(logits[0])))
    assert np.allclose(np.asarray(w2[0]), np.asarray(probs[0])[[0, 1]])


def test_the_drawn_selection_bias_changes_a_visible_share_of_the_choices(cfg):
    """SELECT_BIAS_STD's promise, at init's own draw: with the bias most tokens
    choose another SET than by the scores alone, and most of each set stays."""
    params = tfm.init(cfg, jax.random.PRNGKey(2))
    tokens = _tokens(cfg, (4, 64), 4)
    _, with_bias = tfm.apply(cfg, params, tokens, return_routing=True)
    no_bias = dict(params, moe=dict(params["moe"], bias=jnp.zeros_like(params["moe"]["bias"])))
    _, without = tfm.apply(cfg, no_bias, tokens, return_routing=True)
    a, b = np.sort(np.asarray(with_bias), -1), np.sort(np.asarray(without), -1)
    changed = float(np.mean(np.any(a != b, axis=-1)))
    kept = float(np.mean([len(np.intersect1d(x, y)) for x, y in
                          zip(a.reshape(-1, a.shape[-1]), b.reshape(-1, b.shape[-1]))]))
    assert 0.15 < changed < 0.95, changed
    assert kept > 0.6 * cfg.moe_top_k, kept


# -- the expert banks read in place (PR 34): tests/test_olmoe.py's cases at this twin, whose
# routed layers are the model's layers 1 and 2 and the ``moe`` stacks' 0 and 1 ---------------

import test_olmoe as olmoe_cases  # noqa: E402


@pytest.mark.parametrize("pairing", olmoe_cases._PAIRINGS)
@pytest.mark.parametrize("form", [dropless.experts_sorted, dropless.experts_dense],
                         ids=["sorted", "dense"])
def test_the_held_stacks_read_in_place_are_the_layers_slice(cfg, params, form, pairing):
    olmoe_cases.bank_in_place_is_the_slice(cfg, params["moe"], form, pairing)


@pytest.mark.parametrize("rows", [64, 520], ids=["dense_form", "sorted_form"])
def test_the_stack_index_is_the_routed_layers_not_the_models(cfg, params, rows, monkeypatch):
    """Behind ONE leading dense layer the model's layer 1 is routed stack 0: the in-place
    programs give the sliced programs' logits (``same_as``), and with the model's layer
    number in the index's place they do not (the last routed layer would read past the
    stacks, the first the second's banks)."""
    assert cfg.moe_first_dense == 1 and params["moe"]["gate"].shape[0] == cfg.num_layers - 1
    run = olmoe_cases.cache_pass(cfg, params, rows, monkeypatch)
    real = dropless.moe_ffn_dropless
    with monkeypatch.context() as m:
        m.setattr(dropless, "moe_ffn_dropless",
                  lambda c, p, h, layer=None: real(c, p, h, None if layer is None else layer + 1))
        off_by_the_lead = run()[0]
    logits_s = olmoe_cases.in_place_is_the_sliced_pass(run, rows, monkeypatch)
    assert float(jnp.max(jnp.abs(off_by_the_lead - logits_s))) > 1e-2


def test_training_scans_the_slice_and_the_cache_path_reads_the_stacks(cfg, params, monkeypatch):
    E, M, F = cfg.num_experts, cfg.hidden_size, cfg.ffn_size
    routed = cfg.num_layers - cfg.moe_first_dense
    train, serve = olmoe_cases.banks_by_caller(cfg, params, monkeypatch)
    assert sorted(train) == [(E, F, M), (E, M, F), (E, M, F)]
    assert sorted(serve) == [(routed * E, F, M), (routed * E, M, F), (routed * E, M, F)]


def test_one_chip_engine_reads_the_banks_in_place_and_serves_the_models_tokens(program,
                                                                              monkeypatch):
    olmoe_cases.serves_a_long_prompt_in_place(olmoe_cases.one_chip_engine(program, monkeypatch),
                                              program["vocab_size"])


def _engine(program, dtype, seed=0, n_slots=4, **serving_block):
    return build_serving_engine({
        "model": {**program, "dtype": dtype},
        "engine_dtype": {"float32": "fp32", "bfloat16": "bf16"}[dtype],
        "serving": {"n_slots": n_slots, "max_seq_len": 256, "seed": seed, **serving_block}})


def test_serving_engine_serves_and_its_spans_say_what_was_read(cfg, program):
    srv = _engine(program, "bfloat16",
                  chunked_prefill={"enabled": True, "chunk_size": 64})
    L = cfg.num_layers
    # 64 + 16 values a token a layer in bf16; the worker's account is the metric's numerator
    assert tfm.cache_bytes_per_token(srv.engine.cfg) == 160
    assert srv.worker.hbm_pools()["slot_kv_cache"] == 160 * 4 * 256 * L
    reqs = [serving.Request(uid=i, prompt=_tokens(cfg, (n,), i), max_new_tokens=5)
            for i, n in enumerate((40, 77, 150))]
    t0 = time.perf_counter()
    results = srv.serve(reqs)
    assert all(results[r.uid].status == "ok" and len(results[r.uid].tokens) == 5 for r in reqs)
    assert srv.compile_counts()["decode"] == 1
    spans = {name: [sp for sp in tracing.spans(t0) if sp.name == name]
             for name in ("prefill", "decode", "chunk")}
    assert spans["decode"] and spans["chunk"]
    assert {sp.attrs["attn"] for sp in spans["prefill"]} <= {"dense"}  # a bucket under the rule
    for sp in spans["decode"] + spans["chunk"]:
        assert sp.attrs["attn"] == "latent" and sp.attrs["cached_tokens"] > 0
    # a decode step at n_active rows each past a 40-token prompt reads more than that
    assert all(sp.attrs["cached_tokens"] >= 40 * sp.attrs["n_active"] for sp in spans["decode"])
    # the 150-token prompt's chunks: each reads what lay before it and its own live rows
    assert sorted(sp.attrs["cached_tokens"] for sp in spans["chunk"])[-1] == 150
    # a decode call fetches the step BEFORE it (PR 60): what comes with a fetch is on all but a burst's first
    fetched = [sp for sp in spans["decode"] if sp.attrs["d2h"]]
    assert len(fetched) > len(spans["decode"]) / 2
    for sp in spans["prefill"] + fetched:  # the routed attributes, for this router too
        assert sp.attrs["expert_load_max_over_mean"] >= 1.0
        assert 0 < sp.attrs["experts_touched"] <= cfg.num_experts
    # every routed call states the form its rows went through the experts in (PR 62: decode too);
    # on the CPU no call takes the kernel, and these few rows take every expert
    for sp in spans["prefill"] + spans["decode"] + spans["chunk"]:
        assert sp.attrs["expert_gemm"] == "dense"


def test_a_plain_model_spans_say_dense():
    dense = {"vocab_size": 64, "num_layers": 1, "num_heads": 2, "hidden_size": 16,
             "max_seq_len": 256, "decode_attn": "xla"}
    srv = _engine(dense, "float32", n_slots=2)
    t0 = time.perf_counter()
    srv.serve([serving.Request(uid=0, prompt=np.arange(9, dtype=np.int32), max_new_tokens=3)])
    decodes = [sp for sp in tracing.spans(t0) if sp.name == "decode"]
    assert decodes and all(sp.attrs["attn"] == "dense" for sp in decodes)
    assert [sp.attrs["cached_tokens"] for sp in decodes] == [10, 11]


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


def test_bfloat16_compute_fails_the_float32_tolerance():
    """The control of the three parity cases (tests/test_reference_parity.py
    counts them): the tolerance that passes float32 must catch bfloat16."""
    assert parity.error(CONFIG, "cache", bf16=True) > 10 * parity.TOL["cache"]


# -- the chip's check: small for bfloat16 compute, large for what it must catch ----------------


class _Run:
    """What ``serve_latent._check`` reads of the harness's run."""

    cell = {"serving": {}}

    def __init__(self, program, seed):
        self.program, self.seed = program, seed

    def sized(self, block):
        return {"deployment": {"max_seq_len": 256, "n_slots": 4}}[block]


@pytest.fixture(scope="module")
def bf16_check(program):
    """The chip's check at the rehearsal size with bfloat16 compute: the
    engine's own tokens and choices, the probe and its choices, and the verdict."""
    srv = _engine(program, "bfloat16", seed=3)
    seen = {}
    judge = serve_latent.judge

    def keep(reference, program, params, prompts, got, probe, probe_chosen, engine_chosen):
        seen.update(reference=reference, params=params, prompts=prompts, got=got, probe=probe,
                    probe_chosen=probe_chosen, engine_chosen=engine_chosen, srv=srv)
        return judge(reference, program, params, prompts, got, probe, probe_chosen,
                     engine_chosen)

    serve_latent.judge = keep
    try:
        seen["verdict"] = serve_latent._check(_Run(program, 3), srv, serving.Request)
    finally:
        serve_latent.judge = judge
    return seen


def test_routed_check_reads_small_for_bfloat16_compute(bf16_check):
    c, v = bf16_check, bf16_check["verdict"]
    assert len(c["prompts"]) == 4 and v["check_buckets"] == [256, 128, 256, 256]
    assert v["routing_tol"] == serve_latent.ROUTING_TOL
    assert max(v["routing_slack"], v["probe_routing_slack"]) <= serve_latent.ROUTING_TOL, v
    assert 0 < v["routing_differs_share"] < 0.1
    # what is judged of the engine is the ENGINE's: its routing log gave a choice for every row
    # of every check request, the log is off again, and its tokens sit at the top of the
    # reference under those choices
    k = c["srv"].engine.cfg.moe_top_k
    assert [x.shape for x in c["engine_chosen"]] == [
        (2, len(p) + serve_latent.DECODE_STEPS, k) for p in c["prompts"]]  # two routed layers
    assert c["srv"].worker.routing_log is None
    assert v["token_gap_to_reference_top"] <= serve_latent.LOGIT_TOL
    # a row behind a flipped choice is far over the tolerance; under the system's own routing
    # the rehearsal's 128-wide model reads near it (the chip's, at 2048, is PERF.md's)
    assert v["logit_max_abs_err_free_routing"] > 3 * v["logit_max_abs_err"]
    assert v["logit_max_abs_err"] <= serve_latent.LOGIT_TOL and v["ok"]


def test_routing_log_is_the_programs_own_choice_and_costs_no_fetch_when_off(program):
    """The worker's log holds what the serving programs chose, row for row what
    ``apply_with_cache`` chooses on the same cache, and nothing is kept while it
    is None (the default)."""
    srv = _engine(program, "float32", seed=5)
    prompt = np.random.default_rng(5).integers(0, program["vocab_size"], size=70).astype(np.int32)
    req = lambda uid: serving.Request(uid=uid, prompt=prompt, max_new_tokens=3)  # noqa: E731
    assert srv.worker.routing_log is None
    quiet = srv.serve([req(1)])[1].tokens
    srv.worker.routing_log = log = []
    logged = srv.serve([req(2)])[2].tokens
    srv.worker.routing_log = None
    assert list(quiet) == list(logged)
    assert [r["span"] for r in log] == ["prefill", "decode", "decode"]
    chosen = serve_latent_choices(log, prompt, logged, srv)
    cfg, params = srv.engine.cfg, srv.engine.params
    tokens = np.concatenate([prompt, logged[:2]])[None]
    _, _, own = tfm.apply_with_cache(cfg, params, jnp.asarray(tokens),
                                     tfm.init_cache(cfg, 1, 128), 0, return_routing=True)
    assert np.array_equal(np.sort(chosen, -1), np.sort(np.asarray(own)[:, 0], -1))


def serve_latent_choices(log, prompt, tokens, srv):
    slot = log[0]["slot"]
    assert log[0]["true_len"] == len(prompt)
    steps = [r["chosen"][:, slot] for r in log[1:]]
    assert [int(r["pos"][slot]) for r in log[1:]] == [len(prompt), len(prompt) + 1]
    assert all(r["active"][slot] for r in log[1:])
    return np.concatenate([log[0]["chosen"][:, 0, :len(prompt)], *steps], axis=1)


def _wrong(kind, moe):
    if kind == "an 8-bit router":  # float8 (e4m3) weights, three bits of mantissa
        return dict(moe, gate=moe["gate"].astype(jnp.float8_e4m3fn).astype(moe["gate"].dtype))
    return dict(moe, bias=jnp.zeros_like(moe["bias"]))  # the selection bias dropped


@pytest.mark.parametrize("kind,times_the_tolerance", [
    ("one expert replaced at random", 3), ("an 8-bit router", 1.25),
    ("the selection bias dropped", 3)])
def test_a_wrong_router_fails_the_routing_slack(bf16_check, program, kind, times_the_tolerance):
    """The ENGINE's choices, as its routing log gives them, held to the right
    router: a fault in the engine's programs or weights alone fails the check."""
    c = bf16_check
    if kind == "one expert replaced at random":
        rng = np.random.default_rng(0)
        chosen = [x.copy() for x in c["engine_chosen"]]
        layer, token = rng.integers(chosen[0].shape[0]), rng.integers(chosen[0].shape[1])
        left_out = np.setdiff1d(np.arange(program["num_experts"]), chosen[0][layer, token])
        chosen[0][layer, token, rng.integers(program["moe_top_k"])] = rng.choice(left_out)
    else:
        worker, right = c["srv"].worker, c["params"]
        worker.params = dict(right, moe=_wrong(kind, right["moe"]))  # the same programs
        worker.routing_log = log = []
        try:
            first = 900 if kind == "an 8-bit router" else 950  # a uid serves once an engine
            reqs = [serving.Request(uid=first + j, prompt=p,
                                    max_new_tokens=serve_latent.DECODE_STEPS + 1)
                    for j, p in enumerate(c["prompts"])]
            results = c["srv"].serve(reqs)
        finally:
            worker.params, worker.routing_log = right, None
        got = [np.asarray(results[r.uid].tokens, np.int32) for r in reqs]
        chosen = serve_latent.served_choices(log, [r.uid for r in reqs],
                                             [len(p) for p in c["prompts"]])
        # a prompt's own rows are chosen before any token is emitted, so they can be held
        # against the right router's tokens; a decode row only while the tokens still agree
        same = [int(np.argmin(np.append(a == b, False))) for a, b in zip(got, c["got"])]
        chosen = [np.concatenate([x[:, :len(p) + n], y[:, len(p) + n:]], axis=1)
                  for x, y, p, n in zip(chosen, c["engine_chosen"], c["prompts"], same)]
    v = serve_latent.judge(c["reference"], program, c["params"], c["prompts"], c["got"],
                           c["probe"], c["probe_chosen"], chosen)
    assert not v["ok"] and not v["engine_and_probe_chose_alike"]
    assert v["routing_slack"] > times_the_tolerance * serve_latent.ROUTING_TOL, v["routing_slack"]
    assert v["routing_slack"] > 3 * c["verdict"]["routing_slack"]
    assert v["probe_routing_slack"] == c["verdict"]["probe_routing_slack"]


def test_reference_attention_in_query_blocks_is_the_whole_matrix(program, reference, params):
    """The reference takes its causal scores ``QUERY_BLOCK`` queries at a time;
    with blocks of 16 a 70-token sequence reads what one block reads."""
    tokens = np.random.default_rng(1).integers(0, program["vocab_size"], size=70)
    rows, whole = np.arange(70), lambda leaves: leaves
    one = reference.logits_at(program, params, tokens, rows, fetch=whole)
    reference._attend.clear_cache()
    with mock.patch.object(reference, "QUERY_BLOCK", 16):
        many = reference.logits_at(program, params, tokens, rows, fetch=whole)
    reference._attend.clear_cache()
    np.testing.assert_allclose(many, one, atol=2e-5)


# -- the reference against the published code ---------------------------------------------------


def test_reference_agrees_with_transformers(program, reference, cfg, params):
    """``DeepseekV3ForCausalLM`` at the rehearsal size with this model's switches
    (no query compression, interleaved rotary, one group, a leading dense layer,
    the selection bias non-zero) on the SAME seeded weights: its logits are the
    reference's, so the reference is the published forward pass."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    p = program
    hf = transformers.DeepseekV3Config(
        vocab_size=p["vocab_size"], hidden_size=p["hidden_size"],
        intermediate_size=p["dense_intermediate_size"], moe_intermediate_size=p["intermediate_size"],
        num_hidden_layers=p["num_layers"], num_attention_heads=p["num_heads"],
        num_key_value_heads=p["num_heads"], n_shared_experts=2, n_routed_experts=p["num_experts"],
        routed_scaling_factor=p["moe_routed_scale"], kv_lora_rank=p["kv_lora_rank"],
        q_lora_rank=None, qk_rope_head_dim=p["qk_rope_head_dim"], v_head_dim=p["v_head_dim"],
        qk_nope_head_dim=p["qk_head_dim"] - p["qk_rope_head_dim"], n_group=1, topk_group=1,
        num_experts_per_tok=p["moe_top_k"], first_k_dense_replace=p["moe_first_dense"],
        norm_topk_prob=p["moe_norm_topk_prob"], hidden_act="silu",
        max_position_embeddings=p["max_seq_len"], rms_norm_eps=p["layernorm_epsilon"],
        tie_word_embeddings=False, rope_theta=p["rotary_base"], rope_scaling=None,
        rope_interleave=True, attention_bias=False, attention_dropout=0.0)
    assert p["moe_shared_size"] == 2 * p["intermediate_size"]  # n_shared_experts x the expert width
    hf._attn_implementation = "eager"
    model = transformers.DeepseekV3ForCausalLM(hf).eval()
    t = lambda x: torch.from_numpy(np.array(x, np.float32))  # noqa: E731
    lay, moe, lead = params["layers"], params["moe"], p["moe_first_dense"]
    state = {"model.embed_tokens.weight": t(params["wte"]), "model.norm.weight": t(params["lnf_scale"]),
             "lm_head.weight": t(params["lm_head"].T)}
    for i in range(p["num_layers"]):
        pre, d = f"model.layers.{i}.", p["hidden_size"]
        state.update({
            pre + "input_layernorm.weight": t(lay["ln1_scale"][i]),
            pre + "post_attention_layernorm.weight": t(lay["ln2_scale"][i]),
            pre + "self_attn.q_proj.weight": t(lay["wq"][i].reshape(d, -1).T),
            pre + "self_attn.kv_a_proj_with_mqa.weight": t(lay["wkv_a"][i].T),
            pre + "self_attn.kv_a_layernorm.weight": t(lay["kv_norm_scale"][i]),
            pre + "self_attn.kv_b_proj.weight": t(lay["wkv_b"][i].reshape(p["kv_lora_rank"], -1).T),
            pre + "self_attn.o_proj.weight": t(lay["wo"][i].reshape(-1, d).T)})
        mlps = {}
        if i < lead:
            f = params["dense_ffn"]
            mlps["mlp."] = (f["wg"][i], f["wi"][i], f["wo_mlp"][i])
        else:
            r = i - lead
            state[pre + "mlp.gate.weight"] = t(moe["gate"][r].T)
            state[pre + "mlp.gate.e_score_correction_bias"] = t(moe["bias"][r])
            ex, sh = moe["experts"], moe["shared"]
            mlps["mlp.shared_experts."] = (sh["wg"][r], sh["wi"][r], sh["wo"][r])
            for e in range(p["num_experts"]):
                mlps[f"mlp.experts.{e}."] = (ex["wg"][r, e], ex["wi"][r, e], ex["wo"][r, e])
        for name, (wg, wi, wo) in mlps.items():
            state.update({pre + name + "gate_proj.weight": t(wg.T),
                          pre + name + "up_proj.weight": t(wi.T),
                          pre + name + "down_proj.weight": t(wo.T)})
    missing, unexpected = model.load_state_dict(state, strict=False)
    assert not unexpected and all("rotary" in k or "inv_freq" in k for k in missing), (
        missing, unexpected)
    tokens = _tokens(cfg, (60,), 9)
    with torch.no_grad():
        theirs = model(torch.from_numpy(tokens[None].astype(np.int64))).logits[0].numpy()
    ours = reference.logits_at(program, params, tokens, np.arange(60), fetch=WHOLE)
    assert np.std(theirs) > 0.5 and np.max(np.abs(theirs - ours)) <= TOL


# -- the counts at the published widths ----------------------------------------------------------


def test_counts_at_the_published_widths():
    config = _config()
    program = program_of(config)
    counts = load_reference(program).param_counts(program)
    assert counts["total"] == 4_429_613_312  # ISSUE 31's reckoning from the published config
    assert counts["matmul_attention_per_layer"] == 26_345_984 - 512  # without the latent's norm
    assert counts["matmul_per_expert"] == 4_718_592 and counts["routed_layers"] == 6
    real = tfm.TransformerConfig(dtype=jnp.bfloat16, **program)
    shapes = jax.eval_shape(lambda: tfm.init(real, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) == counts["total"]
    # the cache: 576 values = 1,152 B a token a layer where per-head K/V would be 20,480
    assert mla_cost.cache_values_per_token(program) == 576
    assert tfm.cache_bytes_per_token(real) == 1152
    assert 2 * program["num_heads"] * (program["qk_head_dim"] + program["v_head_dim"]) == 20480
    # attention as this model computes it: 10,240 x rows^2 a layer at its causal half
    rows = 4096
    body = counts["matmul_on_token_path"] - program["hidden_size"] * program["vocab_size"]
    attention = mla_cost.prefill_flops(program, rows) - 2.0 * body * rows \
        - 2.0 * program["hidden_size"] * program["vocab_size"]
    assert attention == program["num_layers"] * 10240 * rows ** 2
    flash = mla_cost.flash_cost(program, rows)
    assert flash["flops"] == attention  # the kernel does all of it in a flash bucket
    # a decode step at 24 rows past 5,500 tokens, 87 experts touched a layer
    need = mla_cost.decode_min_bytes(program, 24 * 5500, 87.0)
    cache = 7 * 24 * 5500 * 1152
    assert need == 2 * (counts["matmul_outside_experts"] + 6 * 87 * 4_718_592) + cache
    # every published number of the catalog row is in the file under its own key
    published = {"hidden_size": 2048, "kv_lora_rank": 512, "qk_rope_head_dim": 64,
                 "qk_nope_head_dim": 128, "v_head_dim": 128, "n_routed_experts": 128,
                 "num_experts_per_tok": 6, "moe_intermediate_size": 768, "intermediate_size": 6144,
                 "n_shared_experts": 2, "first_k_dense_replace": 1, "vocab_size": 128256,
                 "routed_scaling_factor": 2.448, "rope_theta": 1000000, "num_attention_heads": 32}
    assert {k: config[k] for k in published} == published
    assert config["num_hidden_layers"] == 7 and config["reduced"] == ["num_hidden_layers"]
