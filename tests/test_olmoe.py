"""OLMoE on the normal path (PR 27): RMSNorm, QK-norm, a float32 top-k router,
dropless dispatch over gated experts — against the plain reference
``chipbench/references/olmoe.py``, at the configuration's rehearsal size on
the CPU, seeded weights, float32 unless a test says bfloat16."""

import json
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import deepspeed_tpu  # noqa: E402
from chipbench import parity  # noqa: E402
from chipbench.drivers import serve_routed  # noqa: E402
from chipbench.references import load_reference, program_of  # noqa: E402
from deepspeed_tpu.inference import serving  # noqa: E402
from deepspeed_tpu.launcher.serving_worker import build_serving_engine  # noqa: E402
from deepspeed_tpu.models import transformer as tfm  # noqa: E402
from deepspeed_tpu.moe import dropless  # noqa: E402
from deepspeed_tpu.telemetry import tracing  # noqa: E402

WHOLE = lambda leaves: leaves  # noqa: E731
# float32 on both sides, summation order alone: parity.py's tolerance
TOL = parity.TOL["apply"]


@pytest.fixture(scope="module")
def program():
    with open(os.path.join(ROOT, "chipbench", "configs", "olmoe-1b-7b-L4.json")) as f:
        return program_of(json.load(f), "rehearse_program")


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


def test_layout_has_what_the_block_needs_and_no_more(cfg, params):
    layers = params["layers"]
    assert {"q_norm_scale", "k_norm_scale", "ln1_scale", "ln2_scale"} <= set(layers)
    # RMSNorm has no bias, no projection has one, and no layer has a dense feed-forward
    assert not [k for k in layers if k.startswith("b") or k.endswith("_bias") or "mlp" in k]
    assert "wi" not in layers and "lnf_bias" not in params and "lm_head" in params
    E, d, f, L = cfg.num_experts, cfg.hidden_size, cfg.ffn_size, cfg.num_layers
    assert params["moe"]["gate"].shape == (L, d, E)
    assert {k: v.shape for k, v in params["moe"]["experts"].items()} == {
        "wg": (L, E, d, f), "wi": (L, E, d, f), "wo": (L, E, f, d)}
    axes = tfm.logical_axes(cfg)
    assert jax.tree.structure(axes, is_leaf=lambda x: isinstance(x, tuple)) == \
        jax.tree.structure(params)
    assert all(a[1] == "expert" for a in axes["moe"]["experts"].values())


@pytest.mark.parametrize("keywords", [
    {"moe_routing": "gshard"}, {"activation": "gelu"}, {"moe_every": 2}])
def test_half_of_the_block_is_refused(program, keywords):
    with pytest.raises(NotImplementedError, match="dropless"):
        tfm.init(tfm.TransformerConfig(**{**program, **keywords}), jax.random.PRNGKey(0))


def test_apply_matches_the_reference_and_returns_its_choices(cfg, params, program, reference):
    tokens = _tokens(cfg, (2, 80))
    logits, chosen = tfm.apply(cfg, params, tokens, return_routing=True)
    assert chosen.shape == (cfg.num_layers, 2, 80, cfg.moe_top_k) and chosen.dtype == jnp.int32
    for j, row in enumerate(tokens):
        ref = reference.routed_pass(program, params, row, np.arange(80), fetch=WHOLE)
        assert np.max(np.abs(np.asarray(logits[j]) - ref["logits"])) <= TOL
        # float32 on both sides: the same sets, in whatever order
        assert np.array_equal(np.sort(np.asarray(chosen[:, j]), axis=-1),
                              np.sort(ref["own"], axis=-1))
    # the default return is what it was, and a dense or GShard model has no choices to give
    assert tfm.apply(cfg, params, tokens).shape == logits.shape
    with pytest.raises(NotImplementedError, match="dropless"):
        tfm.apply(tfm.TransformerConfig(num_layers=1), {}, tokens, return_routing=True)


def test_prefill_and_decode_through_the_cache_match_the_reference(cfg, params, program,
                                                                  reference):
    """A bucket-padded prefill, then decode steps at per-row positions: the
    serving programs' two forms of ``apply_with_cache``."""
    prompt, steps, bucket = _tokens(cfg, (45,), 1), _tokens(cfg, (6,), 2), 64
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :45] = prompt
    cache = tfm.init_cache(cfg, 1, 128)
    first, cache, chosen = tfm.apply_with_cache(
        cfg, params, padded, cache, 0, last_index=44, return_routing=True)
    assert chosen.shape == (cfg.num_layers, 1, bucket, cfg.moe_top_k)
    got, sets = [np.asarray(first[0, 0])], [np.asarray(chosen[:, 0, :45])]
    for i, tok in enumerate(steps):
        pos = jnp.asarray([45 + i], jnp.int32)
        logits, cache, chosen = tfm.apply_with_cache(
            cfg, params, np.asarray([[tok]], np.int32), cache, pos, write_pos=pos,
            return_routing=True)
        got.append(np.asarray(logits[0, 0]))
        sets.append(np.asarray(chosen[:, 0]))
    ref = reference.routed_pass(program, params, np.concatenate([prompt, steps]),
                                np.arange(44, 51), fetch=WHOLE)
    assert np.max(np.abs(np.stack(got) - ref["logits"])) <= TOL
    assert np.array_equal(np.sort(np.concatenate(sets, axis=1), axis=-1),
                          np.sort(ref["own"], axis=-1))
    # the default return stays (logits, cache)
    assert len(tfm.apply_with_cache(cfg, params, padded, tfm.init_cache(cfg, 1, 64), 0)) == 2


def test_loss_and_its_gradients_match_the_reference(cfg, params, program, reference):
    """The loss to parity.py's tolerance; the gradient of a router, an expert
    and a QK-norm scale against central differences of the REFERENCE's loss
    along a seeded direction (the reference is plain Python loops, so it has
    no gradient of its own), the experts held at the choices of the point
    differentiated at: a choice has no derivative, and one flipped by the
    step would move the loss by more than the slope does."""
    batch = {"tokens": _tokens(cfg, (2, 49), 3)}
    loss, grads = jax.value_and_grad(lambda p: tfm.causal_lm_loss(cfg, p, batch))(params)
    ref_loss = reference.lm_loss(program, params, batch["tokens"], fetch=WHOLE)
    assert abs(float(loss) - ref_loss) <= parity.TOL["loss"]
    held = [reference.routed_pass(program, params, row[:-1], [0], fetch=WHOLE)["own"]
            for row in batch["tokens"]]
    leaves = {"router": ("moe", "gate"), "expert": ("moe", "experts", "wo"),
              "qk_norm": ("layers", "q_norm_scale")}
    for name, path in leaves.items():
        leaf = params
        for key in path:
            leaf = leaf[key]
        direction = jax.random.normal(jax.random.PRNGKey(len(name)), leaf.shape)
        if name == "expert":  # ONE expert of one layer
            direction = direction * (jnp.arange(cfg.num_experts) == 3)[None, :, None, None]
        direction = direction / jnp.linalg.norm(direction)

        def moved(eps):
            out = jax.tree.map(lambda x: x, params)
            node = out
            for key in path[:-1]:
                node = node[key]
            node[path[-1]] = leaf + eps * direction
            return reference.lm_loss(program, out, batch["tokens"], fetch=WHOLE, routing=held)

        g = grads
        for key in path:
            g = g[key]
        want = (moved(2e-2) - moved(-2e-2)) / 4e-2
        got = float(jnp.sum(g * direction))
        # float32 differences of a loss near 6.6 resolve 5e-7 / 4e-2 = 1.2e-5
        assert abs(got - want) <= 0.05 * abs(want) + 5e-5, (name, got, want)
        assert abs(want) > 2e-4, (name, want)  # the direction moves the loss


def test_no_token_is_dropped_at_the_worst_skew(cfg, params, program, reference):
    """A sequence of one repeated token gives every position the same hidden
    state, so ALL of them choose the same k experts: the load GShard's capacity
    would cut to a quarter. Nothing is dropped: every pair is counted and the
    logits are still the reference's."""
    tokens = np.full((1, 72), 7, np.int32)
    logits, chosen = tfm.apply(cfg, params, tokens, return_routing=True)
    assert all(len(np.unique(np.sort(np.asarray(layer[0]), axis=-1), axis=0)) == 1
               for layer in chosen)
    load = np.asarray(dropless.expert_load(chosen, jnp.ones((1, 72), bool), cfg.num_experts))
    assert np.all(np.sort(load, axis=1)[:, -cfg.moe_top_k:] == 72)
    assert np.all(load.sum(axis=1) == 72 * cfg.moe_top_k)
    assert dropless.load_summary(load) == {
        "expert_load_max_over_mean": cfg.num_experts / cfg.moe_top_k,
        "experts_touched": float(cfg.moe_top_k)}
    ref = reference.logits_at(program, params, tokens[0], np.arange(72), fetch=WHOLE)
    assert np.max(np.abs(np.asarray(logits[0]) - ref)) <= TOL


@pytest.mark.parametrize("renormalize", [False, True])
def test_the_two_forms_of_the_expert_block_agree(cfg, params, renormalize):
    """Sorted pairs through grouped matmuls (prefill) and every expert on every
    row (decode), on the same rows under one router."""
    moe_p = jax.tree.map(lambda a: a[1], params["moe"])
    x = jax.random.normal(jax.random.PRNGKey(5), (40, cfg.hidden_size))
    weights, experts, probs = dropless.route(x, moe_p["gate"], cfg.moe_top_k, renormalize)
    assert experts.shape == (40, cfg.moe_top_k) and probs.shape == (40, cfg.num_experts)
    assert np.allclose(np.asarray(weights.sum(-1)), 1.0) == renormalize
    a = dropless.experts_sorted(moe_p["experts"], x, weights, experts)
    b = dropless.experts_dense(moe_p["experts"], x, weights, experts)
    assert np.max(np.abs(np.asarray(a) - np.asarray(b))) <= 1e-5
    # which form a call takes is decided by its rows alone
    few, many = x[None, :dropless.DENSE_ROWS], jnp.tile(x, (2, 1))[None, :dropless.DENSE_ROWS + 1]
    for h in (few, many):
        out, aux, chosen = dropless.moe_ffn_dropless(
            cfg.replace(moe_norm_topk_prob=renormalize), moe_p, h)
        assert out.shape == h.shape and chosen.shape == h.shape[:2] + (cfg.moe_top_k,)
        assert float(aux) > 0


_PAIRINGS = ("as routed", "an expert no pair chose", "all pairs on one expert")


def _paired(cfg, moe_l, x, pairing):
    """(weights, experts) of ``x`` under layer ``moe_l``'s router, bent to the pairing."""
    weights, experts, _ = dropless.route(x, moe_l["gate"], cfg.moe_top_k, cfg.moe_norm_topk_prob,
                                         score_fn=cfg.moe_score_fn, select_bias=moe_l.get("bias"),
                                         scale=cfg.moe_routed_scale)
    if pairing == "an expert no pair chose":
        spare = (experts + 1) % cfg.num_experts  # expert 3's pairs go to 4 (a row may hold 4 twice)
        experts = jnp.where(experts == 3, spare, experts)
    elif pairing == "all pairs on one expert":
        experts = jnp.full_like(experts, 5)
    return weights, experts


def same_as(to_the_bit, got, want, what=None):
    """The dense form indexes the layer inside its einsums: the same bits anywhere. The
    sorted form's grouped matmul is the same kernel on the same tiles ON THE CHIP (bit for
    bit there at OLMoE's and kanana's shapes: PERF.md section 6, PR 34); XLA's CPU lowering
    of ``ragged_dot`` sums over all the groups it is handed, L * E or E, in another order,
    so here the two agree to float32 rounding (values are O(1); a wrong layer is O(1) off)."""
    got, want = np.asarray(got), np.asarray(want)
    if to_the_bit:
        assert np.array_equal(got, want), what
    else:
        assert np.max(np.abs(got - want)) <= 2e-6 * max(1.0, float(np.max(np.abs(want)))), what


def bank_in_place_is_the_slice(cfg, moe, form, pairing):
    """``form`` on the held stacks with the routed layer's index (traced) against ``form``
    on that layer's slice, for every layer (``same_as``). Shared with tests/test_kanana.py."""
    x = jax.random.normal(jax.random.PRNGKey(7), (40, cfg.hidden_size))
    stacks = moe["experts"]
    n_routed = stacks["wi"].shape[0]
    assert n_routed >= 2
    in_place = jax.jit(lambda l, w, e: form(stacks, x, w, e, l))
    of_slice = jax.jit(lambda bank, w, e: form(bank, x, w, e))  # both compiled: one summation order
    outs = []
    for l in range(n_routed):
        moe_l = jax.tree.map(lambda a: a[l], moe)
        weights, experts = _paired(cfg, moe_l, x, pairing)
        if pairing != "as routed":
            counts = np.bincount(np.asarray(experts).ravel(), minlength=cfg.num_experts)
            assert (counts[3] == 0) if pairing == _PAIRINGS[1] else (counts[5] == experts.size)
        sliced = of_slice(moe_l["experts"], weights, experts)
        got = in_place(jnp.int32(l), weights, experts)
        same_as(form is dropless.experts_dense, got, sliced, (l, pairing))
        outs.append(np.asarray(sliced))
    assert not np.array_equal(outs[0], outs[1])  # the layers differ: an index off by one would show


@pytest.mark.parametrize("pairing", _PAIRINGS)
@pytest.mark.parametrize("form", [dropless.experts_sorted, dropless.experts_dense],
                         ids=["sorted", "dense"])
def test_the_held_stacks_read_in_place_are_the_layers_slice(cfg, params, form, pairing):
    bank_in_place_is_the_slice(cfg, params["moe"], form, pairing)


def _ragged_rhs_shapes(jaxpr, out=None):
    """The shapes of the bank operand of every ``ragged_dot`` of a jaxpr, nested ones too."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name.startswith("ragged_dot"):
            out.append(tuple(eqn.invars[1].aval.shape))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _ragged_rhs_shapes(sub, out)
    return out


def on_one_chip(monkeypatch):
    """No active mesh for the test's own traces, as on the chip's one device: an engine built
    earlier in the process over the eight virtual devices leaves its mesh active
    (``Model.set_mesh``), and it shards the bank, which ``expert_bank_form`` answers with
    the slice."""
    monkeypatch.setattr(tfm, "_ACTIVE_MESH", [None])


def banks_by_caller(cfg, params, monkeypatch, rows=520):
    """(bank operands of ``apply``'s grouped GEMMs, of ``apply_with_cache``'s) over
    ``rows`` > DENSE_ROWS tokens. Shared with tests/test_kanana.py."""
    on_one_chip(monkeypatch)
    tokens = _tokens(cfg, (1, rows), 3)
    long = cfg.replace(max_seq_len=1024)
    train = jax.make_jaxpr(lambda p: tfm.apply(long, p, tokens))(params)
    serve = jax.make_jaxpr(lambda p: tfm.apply_with_cache(
        long, p, tokens, tfm.init_cache(long, 1, rows), 0)[0])(params)
    return _ragged_rhs_shapes(train.jaxpr), _ragged_rhs_shapes(serve.jaxpr)


def test_training_scans_the_slice_and_the_cache_path_reads_the_stacks(cfg, params, monkeypatch):
    """``apply`` (training, evaluation: a backward pass wants one layer's cotangent) hands the
    grouped GEMMs layer l's ``[E, K, N]`` slice, as it always has; ``apply_with_cache`` hands
    them the held stacks as ``[L * E, K, N]``. Bit for bit the same logits either way."""
    E, M, F, L = cfg.num_experts, cfg.hidden_size, cfg.ffn_size, cfg.num_layers
    train, serve = banks_by_caller(cfg, params, monkeypatch)
    assert sorted(train) == [(E, F, M), (E, M, F), (E, M, F)]  # one scanned body
    assert sorted(serve) == [(L * E, F, M), (L * E, M, F), (L * E, M, F)]


def cache_pass(cfg, params, rows, monkeypatch):
    """-> a call that runs ``rows`` tokens a row through ``apply_with_cache`` from an empty
    cache (520 rows: the sorted form; 64: the dense) and returns (logits, cache, chosen)."""
    on_one_chip(monkeypatch)
    long = cfg.replace(max_seq_len=1024)
    tokens = _tokens(cfg, (2, rows), 9)
    assert tfm.expert_bank_form(long, params["moe"]) == "in_place"
    return lambda: tfm.apply_with_cache(long, params, tokens, tfm.init_cache(long, 2, rows), 0,
                                        return_routing=True)


def in_place_is_the_sliced_pass(run, rows, monkeypatch):
    """``run`` as it is (the banks in place) against ``run`` with the rule saying ``sliced``:
    the same logits and cache (``same_as``), the same experts chosen. -> the sliced logits.
    Both shared with tests/test_kanana.py."""
    logits, cache, chosen = run()
    monkeypatch.setattr(tfm, "expert_bank_form", lambda *a, **k: "sliced")
    logits_s, cache_s, chosen_s = run()
    to_the_bit = rows <= dropless.DENSE_ROWS
    same_as(to_the_bit, logits, logits_s)
    assert np.array_equal(np.asarray(chosen), np.asarray(chosen_s))
    for name in cache:
        same_as(to_the_bit, cache[name], cache_s[name], name)
    return logits_s


@pytest.mark.parametrize("rows", [64, 520], ids=["dense_form", "sorted_form"])
def test_in_place_banks_give_the_sliced_programs_logits(cfg, params, rows, monkeypatch):
    in_place_is_the_sliced_pass(cache_pass(cfg, params, rows, monkeypatch), rows, monkeypatch)


def _mesh(n_devices):
    """The first ``n_devices`` along ``data`` (``build_mesh`` would also make it the process's
    current mesh, for the tests that follow)."""
    from deepspeed_tpu.comm.mesh import AXIS_ORDER

    shape = tuple(n_devices if axis == "data" else 1 for axis in AXIS_ORDER)
    return jax.sharding.Mesh(np.asarray(jax.devices()[:n_devices]).reshape(shape), AXIS_ORDER)


_NOT_IN_PLACE = {
    "a float32-held bank under bfloat16 compute": lambda cfg, moe: (
        cfg.replace(dtype=jnp.bfloat16), moe, None),
    "a quantised bank leaf": lambda cfg, moe: (
        cfg, {**moe, "experts": {**moe["experts"], "wi": {
            "q": moe["experts"]["wi"].astype(jnp.int8), "s": jnp.ones((2, 16, 1, 1))}}}, None),
    "stacks that param_offload streams": lambda cfg, moe: (
        cfg.replace(param_offload=True), moe, None),
    "stacks sharded over the mesh": lambda cfg, moe: (cfg, moe, "eight devices"),
}


@pytest.mark.parametrize("case", list(_NOT_IN_PLACE))
def test_the_bank_is_sliced_where_in_place_would_cost_more(cfg, params, case, monkeypatch):
    """``expert_bank_form`` on what the program is handed; a float32-held bank is then cast a
    LAYER at a time inside the scan, never the whole stack."""
    assert tfm.expert_bank_form(cfg, params["moe"], _mesh(1)) == "in_place"
    assert tfm.expert_bank_form(cfg.replace(moe_every=0, moe_routing="top1"), None) is None
    cfg2, moe, mesh = _NOT_IN_PLACE[case](cfg, params["moe"])
    assert tfm.expert_bank_form(cfg2, moe, _mesh(8 if mesh else 1)) == "sliced"
    if cfg2.dtype == jnp.bfloat16:
        on_one_chip(monkeypatch)
        long = cfg2.replace(max_seq_len=1024)
        tokens = _tokens(cfg, (1, 520), 3)
        jaxpr = jax.make_jaxpr(lambda p: tfm.apply_with_cache(
            long, p, tokens, tfm.init_cache(long, 1, 520, dtype=jnp.bfloat16), 0)[0])(params)
        E, M, F = cfg.num_experts, cfg.hidden_size, cfg.ffn_size
        assert sorted(_ragged_rhs_shapes(jaxpr.jaxpr)) == [(E, F, M), (E, M, F), (E, M, F)]
        whole = re.findall(r"bf16\[%d,%d,\d+,\d+\] = convert_element_type" % (cfg.num_layers, E),
                           str(jaxpr))
        assert not whole, whole


def test_live_row_counters_ignore_padding_and_idle_slots(cfg, params):
    k, E, L = cfg.moe_top_k, cfg.num_experts, cfg.num_layers
    # prefill: 45 live rows of a 64-row bucket, whatever the padding holds
    loads = []
    for pad in (0, 5):
        padded = np.full((1, 64), pad, np.int32)
        padded[0, :45] = _tokens(cfg, (45,), 1)
        _, _, (load, chosen) = serving._forward(cfg, params, padded, tfm.init_cache(cfg, 1, 64),
                                                0, jnp.arange(64)[None, :] < 45, last_index=44)
        loads.append(np.asarray(load))
        assert load.shape == (L, E) and np.all(load.sum(axis=1) == 45 * k)
        # beside the load, the choices it was counted from: every row's, padding included
        assert chosen.shape == (L, 1, 64, k)
        assert np.array_equal(load, [np.bincount(np.asarray(c)[0, :45].ravel(), minlength=E)
                                     for c in chosen])
    assert np.array_equal(*loads)
    # decode: 2 active slots of 4
    active = jnp.asarray([True, False, True, False])
    pos = jnp.asarray([3, 0, 9, 0], jnp.int32)
    _, _, (load, _) = serving._forward(cfg, params, _tokens(cfg, (4, 1), 4),
                                       tfm.init_cache(cfg, 4, 128), pos, active[:, None],
                                       write_pos=pos)
    assert np.all(np.asarray(load).sum(axis=1) == 2 * k)
    # a dense model's programs gain nothing
    dense = tfm.TransformerConfig(vocab_size=64, num_layers=1, num_heads=2, hidden_size=16)
    out = serving._forward(dense, tfm.init(dense, jax.random.PRNGKey(0)),
                           np.zeros((1, 8), np.int32), tfm.init_cache(dense, 1, 8), 0, None)
    assert out[2] == ()


def _engine(program, dtype, seed=0, n_slots=4):
    return build_serving_engine({
        "model": {**program, "dtype": dtype},
        "engine_dtype": {"float32": "fp32", "bfloat16": "bf16"}[dtype],
        "serving": {"n_slots": n_slots, "max_seq_len": 256, "seed": seed}})


def test_serving_engine_serves_and_its_spans_carry_the_load(cfg, program):
    srv = _engine(program, "float32")
    reqs = [serving.Request(uid=i, prompt=_tokens(cfg, (n,), i), max_new_tokens=5)
            for i, n in enumerate((40, 77))]
    t0 = time.perf_counter()
    results = srv.serve(reqs)
    assert all(results[r.uid].status == "ok" and len(results[r.uid].tokens) == 5 for r in reqs)
    assert srv.compile_counts()["decode"] == 1
    calls = [sp for sp in tracing.spans(t0) if sp.name in ("prefill", "decode")]
    assert {sp.name for sp in calls} == {"prefill", "decode"}
    # a decode call fetches the step BEFORE it (PR 60): what comes with a fetch is on all but a burst's first
    assert [sp.attrs["d2h"] for sp in calls if sp.name == "decode"] == [0, 3, 3, 3]
    for sp in calls:
        # the form the call's rows went through the experts in (PR 62: on decode spans too)
        assert sp.attrs["expert_gemm"] == "dense"
        if not sp.attrs["d2h"]:
            continue
        assert 1.0 <= sp.attrs["expert_load_max_over_mean"] <= cfg.num_experts
        assert 0 < sp.attrs["experts_touched"] <= cfg.num_experts
    gauges = srv.telemetry.registry.snapshot()["gauges"]
    assert {"serving/expert_load_max_over_mean", "serving/experts_touched"} <= set(gauges)
    # the engine's greedy tokens are the model's own
    prompt = reqs[0].prompt
    logits = tfm.apply(cfg, srv.engine.params, prompt[None])
    assert int(jnp.argmax(logits[0, -1])) == int(results[0].tokens[0])


def one_chip_engine(program, monkeypatch, dtype=jnp.float32, n_slots=2, max_seq_len=1024):
    """A serving engine on a ONE-device mesh, as the chip's is (``_engine``'s spans the eight
    virtual devices, which shard the bank over ``data``); the mesh it makes active is put
    back when the test ends. Shared with tests/test_kanana.py."""
    from deepspeed_tpu.inference import InferenceEngine

    monkeypatch.setattr(tfm, "_ACTIVE_MESH", [None])  # ``set_mesh`` writes into this one
    cfg = tfm.TransformerConfig(dtype=dtype, **{**program, "max_seq_len": max_seq_len})
    engine = InferenceEngine(model=tfm.Model(cfg), mesh=_mesh(1), config={
        "dtype": {jnp.float32: "fp32", jnp.bfloat16: "bf16"}[dtype]})
    return serving.ServingEngine(engine, config={"n_slots": n_slots, "max_seq_len": max_seq_len,
                                                 "seed": 0})


def serves_a_long_prompt_in_place(srv, vocab):
    """A 600-token prompt (the 1024-row bucket: the sorted form) then four decode steps (the
    dense form) through ``srv``: every prefill span says ``expert_bank: in_place``, no decode
    span carries the attribute, and the greedy tokens are ``apply``'s own, which scans the
    slice. Shared with tests/test_kanana.py."""
    cfg, held = srv.engine.cfg, srv.engine.params
    assert srv.worker.expert_bank == "in_place"
    prompt = np.random.default_rng(11).integers(0, vocab, size=600).astype(np.int32)
    t0 = time.perf_counter()
    tokens = srv.serve([serving.Request(uid=0, prompt=prompt, max_new_tokens=5)])[0].tokens
    spans = [sp for sp in tracing.spans(t0) if sp.name in ("prefill", "decode")]
    prefills = [sp for sp in spans if sp.name == "prefill"]
    assert [sp.attrs["bucket"] for sp in prefills] == [1024]
    assert all(sp.attrs["expert_bank"] == "in_place" for sp in prefills)
    # the sorted form, through the compiler's grouped GEMM: the Pallas kernel is the chip's
    assert all(sp.attrs["expert_gemm"] == "ragged_dot" for sp in prefills)
    assert len(spans) > 1 and not any("expert_bank" in sp.attrs for sp in spans
                                      if sp.name == "decode")
    seen = list(prompt)
    for tok in tokens:
        logits = tfm.apply(cfg, held, np.asarray(seen, np.int32)[None])
        assert int(jnp.argmax(logits[0, -1])) == int(tok)
        seen.append(int(tok))


def test_one_chip_engine_reads_the_banks_in_place_and_serves_the_models_tokens(program,
                                                                              monkeypatch):
    serves_a_long_prompt_in_place(one_chip_engine(program, monkeypatch), program["vocab_size"])


def test_an_engine_over_a_mesh_that_shards_the_bank_keeps_the_slice(cfg, program):
    srv = _engine(program, "float32")  # the eight virtual devices: 16 experts over ``data``
    assert srv.worker.expert_bank == "sliced"
    t0 = time.perf_counter()
    srv.serve([serving.Request(uid=0, prompt=_tokens(cfg, (40,), 2), max_new_tokens=2)])
    prefills = [sp for sp in tracing.spans(t0) if sp.name == "prefill"]
    assert prefills and all(sp.attrs["expert_bank"] == "sliced" for sp in prefills)
    assert all(sp.attrs["expert_gemm"] == "dense" for sp in prefills)  # 40 rows: every expert


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
    # training names no bank form: its one form is the scanned slice
    assert not any("expert_bank" in sp.attrs for sp in tracing.spans(0.0)
                   if sp.path.startswith("train/"))


class _Run:
    """What ``serve_routed._check`` reads of the harness's run."""

    def __init__(self, program, seed):
        self.program, self.seed = program, seed

    def sized(self, block):
        return {"deployment": {"max_seq_len": 256}}[block]


@pytest.fixture(scope="module")
def bf16_check(program):
    """The chip's check at the rehearsal size with bfloat16 compute: the
    engine's own tokens, the probe and its choices, and the verdict."""
    srv = _engine(program, "bfloat16", seed=3)
    seen = {}
    judge = serve_routed.judge

    def keep(reference, program, params, prompts, got, probe, chosen):
        seen.update(reference=reference, params=params, prompts=prompts, got=got,
                    probe=probe, chosen=chosen, cfg=srv.engine.cfg)
        return judge(reference, program, params, prompts, got, probe, chosen)

    serve_routed.judge = keep
    try:
        seen["verdict"] = serve_routed._check(_Run(program, 3), srv, serving.Request)
    finally:
        serve_routed.judge = judge
    return seen


def test_routed_check_passes_where_the_free_comparison_fails(bf16_check):
    v = bf16_check["verdict"]
    assert v["ok"], v
    assert v["logit_max_abs_err"] <= serve_routed.LOGIT_TOL
    assert v["routing_slack"] <= serve_routed.ROUTING_TOL
    # bfloat16 flips a few near ties, and a row behind a flip is far over the tolerance
    assert 0 < v["routing_differs_share"] < 0.1
    assert v["logit_max_abs_err_free_routing"] > serve_routed.LOGIT_TOL


def _wrong_gate(kind, g):
    if kind == "top-k of the wrong quantity":  # the k SMALLEST probabilities
        return -g
    # an 8-bit router: float8 (e4m3) weights, three bits of mantissa
    return g.astype(jnp.float8_e4m3fn).astype(g.dtype)


@pytest.mark.parametrize("kind,times_the_tolerance", [
    ("top-k of the wrong quantity", 20), ("one expert replaced at random", 3),
    # float8 WEIGHTS alone (the activations stay bfloat16: the system has no hook for them)
    # already read four times what bfloat16 compute does
    ("an 8-bit router", 1.5)])
def test_a_wrong_router_fails_the_routing_slack(bf16_check, program, kind, times_the_tolerance):
    c = bf16_check
    chosen = c["chosen"]
    if kind == "one expert replaced at random":
        rng = np.random.default_rng(0)
        chosen = [x.copy() for x in chosen]
        layer, token = rng.integers(chosen[0].shape[0]), rng.integers(chosen[0].shape[1])
        left_out = np.setdiff1d(np.arange(program["num_experts"]), chosen[0][layer, token])
        chosen[0][layer, token, rng.integers(program["moe_top_k"])] = rng.choice(left_out)
    else:
        wrong = dict(c["params"], moe=dict(c["params"]["moe"],
                                           gate=_wrong_gate(kind, c["params"]["moe"]["gate"])))
        buckets = [64 if len(p) <= 64 else 128 if len(p) <= 128 else 256 for p in c["prompts"]]
        _, chosen = serve_routed.probe_logits(
            c["cfg"], wrong, c["prompts"], buckets,
            np.stack([g[:serve_routed.DECODE_STEPS] for g in c["got"]]))
    v = serve_routed.judge(c["reference"], program, c["params"], c["prompts"], c["got"],
                           c["probe"], chosen)
    assert not v["ok"], kind
    assert v["routing_slack"] > times_the_tolerance * serve_routed.ROUTING_TOL, v["routing_slack"]
    assert v["routing_slack"] > 3 * c["verdict"]["routing_slack"]


# -- the benchmark's counting functions and the readers of the new span attributes --------


def _published(name):
    with open(os.path.join(ROOT, "chipbench", "configs", f"{name}.json")) as f:
        return program_of(json.load(f))


def test_counts_at_the_published_widths():
    """By hand: a layer has 4 x 2048^2 attention + 2048 x 64 router = 16,908,288 matmul
    parameters outside its experts and 64 experts of 3 x 2048 x 1024 = 6,291,456; the head
    2048 x 50,304 = 103,022,592."""
    from chipbench import flops, moe_cost

    p = _published("olmoe-1b-7b-L4")
    c = flops.param_counts(p)
    assert c["matmul_per_expert"] == 6_291_456 and c["routed_layers"] == 4
    assert c["matmul_outside_experts"] == 4 * 16_908_288 + 103_022_592 == 170_655_744
    assert c["matmul_on_token_path"] == 4 * (16_908_288 + 8 * 6_291_456) + 103_022_592
    assert c["total"] == 4 * 419_569_664 + 2 * 103_022_592 + 2048 == 1_884_325_888
    # 16 layers: the published 6.92 B
    assert load_reference(p).param_counts({**p, "num_layers": 16})["total"] == 6_919_161_856
    # a decode step that touched 56 experts a layer over 24,000 live tokens, bf16
    assert moe_cost.decode_min_bytes(p, 24_000, 56.0) == (
        2 * (170_655_744 + 4 * 56 * 6_291_456) + 4 * 2 * 24_000 * 2048 * 2) == 3_946_315_776
    with pytest.raises(ValueError, match="touched"):
        moe_cost.decode_min_bytes(p, 24_000)
    # a 2048-row prefill: the body for every row, the head for one, causal attention
    assert moe_cost.prefill_flops(p, 2048) == (
        2 * 4 * 67_239_936 * 2048 + 2 * 103_022_592 + 4 * 2 * 2048 ** 3) == 1_170_584_633_344
    assert moe_cost.grouped_gemm_cost(p, 2048) == {
        "flops": 4 * 2 * 16_384 * 6_291_456,
        "bytes": 4 * 2 * (64 * 6_291_456 + 16_384 * (2 * 2048 + 2 * 1024 + 1024 + 2048))}
    # a dense model reads every matmul parameter on the token's path, and has no experts
    b = _published("bloom-1b7")
    assert moe_cost.decode_min_bytes(b, 10_000) == (
        2 * flops.param_counts(b)["matmul_on_token_path"] + 24 * 2 * 10_000 * 2048 * 2)


def _worker_call(i, kind, t0, t1, **attrs):
    from types import SimpleNamespace as NS

    path = f"serve/step/{kind}"
    return [NS(id=3 * i, parent=None, name=kind, path=path, t0=t0, t1=t1,
               attrs={"compiled": False, **attrs}),
            NS(id=3 * i + 1, parent=3 * i, name="dispatch", path=path + "/dispatch", t0=t0,
               t1=t0 + 0.002, attrs={}),
            NS(id=3 * i + 2, parent=3 * i, name="fetch", path=path + "/fetch", t0=t1 - 0.001,
               t1=t1, attrs={})]


@pytest.mark.parametrize("routed", [True, False])
def test_readers_of_the_new_span_attributes(monkeypatch, routed):
    """Three decode steps of 25 ms and two 2048-row prefills of 40 ms on a hand-made ring;
    the numbers are those of test_counts_at_the_published_widths. A program whose spans
    lack the attributes (the parent of PR 27) gives a routed model's readers nothing."""
    import importlib
    from types import SimpleNamespace as NS

    load = {"expert_load_max_over_mean": 3.0, "experts_touched": 56.0} if routed else {}
    ring = []
    for i, t0 in enumerate((1011.0, 1012.0, 1013.0)):
        ring += _worker_call(i, "decode", t0, t0 + 0.025, n_active=16,
                             **{k: v + i - 1 for k, v in load.items()})
    for i, t0 in enumerate((1014.0, 1015.0)):
        ring += _worker_call(10 + i, "prefill", t0, t0 + 0.040, bucket=2048, true_len=1500)
    monkeypatch.setattr(tracing, "spans",
                        lambda since=float("-inf"): [sp for sp in ring if sp.t0 >= since])
    notes = []
    ctx = {"serve": {"window": (10.0, 20.0), "epoch": 1000.0, "traced": (10.0, 20.0),
                     "steps": [(11.0, 11.1, 16, 23_000), (12.0, 12.1, 16, 24_000),
                               (13.0, 13.1, 16, 25_000)]},
           "program": _published("olmoe-1b-7b-L4"), "trace": None,
           "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "run": NS(note=lambda **kw: notes.append(kw))}
    read = lambda name: importlib.import_module(f"chipbench.layer_metrics.{name}").read(ctx)
    np.testing.assert_allclose(read("prefill_mfu_pct"),
                               100 * 1_170_584_633_344 / 197e12 / 0.040)  # 14.86
    assert read("moe_gemm_roofline_pct") is None  # no device trace in this ctx
    if routed:
        assert read("moe_load_max_over_mean") == 3.0
        np.testing.assert_allclose(read("decode_hbm_floor_pct"),
                                   100 * 3_946_315_776 / 819e9 / 0.025)  # 19.27
        assert notes[-1]["program"] == "decode" and notes[-1]["experts_touched"] == 56.0
    else:
        assert read("moe_load_max_over_mean") is None
        assert read("decode_hbm_floor_pct") is None and not notes
    # a dense configuration reads its floor without the attribute
    ctx["program"] = _published("bloom-1b7")
    assert 0 < read("decode_hbm_floor_pct") < 100
    # no serving block, or an empty ring: nothing, and no error
    for name in ("moe_load_max_over_mean", "decode_hbm_floor_pct", "prefill_mfu_pct",
                 "moe_gemm_roofline_pct"):
        assert importlib.import_module(f"chipbench.layer_metrics.{name}").read(
            {**ctx, "serve": None}) is None
    ring.clear()
    assert read("decode_hbm_floor_pct") is None and read("prefill_mfu_pct") is None
