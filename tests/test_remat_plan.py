"""What a checkpointed layer keeps beside ``save_flash``'s floor (PR 50):
``runtime/remat_plan.plan_saved`` as arithmetic, the names the model offers
(``remat_candidates``), what saving them does to the program (the backward
pass's products; the loss and the gradients to the bit), and the engine's side
(nothing on the CPU; a handed-in limit; the log, the gauge and the ledger row;
the floor program after a compile that fails for memory)."""

import logging
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import transformer as tfm
from deepspeed_tpu.models.transformer import Model, TransformerConfig
from deepspeed_tpu.runtime.remat_plan import HEADROOM, RematPlan, plan_saved
from deepspeed_tpu.utils.memory import mesh_memory_limit
from simple_model import base_config, random_tokens

GB = 10 ** 9
V5E_LIMIT = int(15.75 * 2 ** 30)  # memory_stats()["bytes_limit"] of one v5e chip
# the train cell a device: Pythia-1.4B's widths, ZeRO-3 over fsdp=4, 8 x 2,048 tokens
PYTHIA = dict(vocab_size=50304, max_seq_len=2048, num_layers=24, num_heads=16, hidden_size=2048,
              intermediate_size=8192, pos_emb="rotary", rotary_pct=0.25, parallel_residual=True,
              use_bias=True, activation="gelu_exact", attn_impl="flash", remat=True,
              dtype=jnp.bfloat16)
STATE = 4_243_943_444  # 12 bytes a parameter of a quarter of the model
FFN = tfm.FFN_NAMES
QKV_BYTES = 3 * 16_384 * 2048 * 2 * 24  # what keeping q, k and v beside would take: 4.83 GB


def _cell_plan(limit, more=0, **over):
    """The planner's arithmetic as the engine does it, at the cell's numbers."""
    cfg = TransformerConfig(**{**PYTHIA, **over})
    floor, names, values = tfm.remat_candidates(cfg)
    working = STATE // 12 * 2 + tfm.step_working_bytes(cfg, 8, 16_384)
    return plan_saved(limit, STATE + 16_384 * floor * 2, working, names,
                      16_384 * values * 2 + more), working


def test_the_cells_candidate_is_the_issues_bytes():
    floor, names, values = tfm.remat_candidates(TransformerConfig(**PYTHIA))
    assert (names, 16_384 * values * 2) == (FFN[:1], 16_384 * 8192 * 2 * 24)  # 6.44 GB
    assert 16_384 * floor * 2 == 16_384 * 2 * 24 * 2048 * 2  # 3.22 GB


@pytest.mark.parametrize("limit, names", [
    (None, ()), (0, ()),  # no memory figures (the CPU): nothing is added
    (12 * GB, ()), (15 * GB, ()),  # 7.46 GB held and 2.59 GB to work in leave 1.9 and 4.9 GB
    (V5E_LIMIT, FFN[:1]),  # 6.86 GB of room for the candidate's 6.44
    (23 * GB, FFN[:1]),
])
def test_plan_keeps_the_candidate_where_it_fits(limit, names):
    plan, working = _cell_plan(limit)
    assert plan.names == names
    assert plan.saved_bytes == (16_384 * 8192 * 2 * 24 if names else 0)
    if limit:
        assert plan.room == int(limit - STATE - 16_384 * 2 * 24 * 4096 - HEADROOM * working)
        assert plan.saved_bytes <= max(plan.room, 0)
    else:
        assert plan == RematPlan()


def test_never_q_k_v_beside_it_on_a_v5e():
    """Both of the issue's products (11.3 GB) do not fit a v5e beside the cell's
    state, by the planner's own arithmetic, even with nothing kept for headroom;
    they would from 21.4 GB."""
    assert _cell_plan(V5E_LIMIT, more=QKV_BYTES)[0].names == ()
    assert _cell_plan(V5E_LIMIT + int((HEADROOM - 1) * 2.36 * GB), more=QKV_BYTES)[0].names == ()
    assert _cell_plan(int(21.4 * GB), more=QKV_BYTES)[0].names == FFN[:1]
    assert plan_saved(V5E_LIMIT, 0, 0, FFN, 0) == RematPlan()  # a model with nothing to offer


@pytest.mark.parametrize("over, names, values", [
    # a dense feed-forward: one product; a gated one: two, both named
    (dict(), FFN[:1], 24 * 8192),
    (dict(activation="swiglu", use_bias=False), FFN, 24 * 2 * 8192),
    # whatever attends: grouped-query heads through the XLA form (k, v narrower than q)
    (dict(num_kv_heads=4, attn_impl="xla", decode_attn="xla"), FFN[:1], 24 * 8192),
    (dict(intermediate_size=None), FFN[:1], 24 * 4 * 2048),
    # routed layers' experts are not offered; the leading dense layers are, at their width
    (dict(activation="swiglu", use_bias=False, moe_routing="dropless", moe_every=1, num_experts=8,
          moe_top_k=2, intermediate_size=1024, moe_first_dense=2, dense_intermediate_size=4096),
     FFN, 2 * 2 * 4096),
    (dict(moe_every=2, num_experts=8), FFN[:1], 12 * 8192),
    (dict(moe_every=1, num_experts=8), FFN[:1], 0),  # every layer routed: nothing to offer
])
def test_candidates_follow_the_models_shapes(over, names, values):
    cfg = TransformerConfig(**{**PYTHIA, **over})
    floor, *candidate = tfm.remat_candidates(cfg)
    assert tuple(candidate) == (names, values)
    # the floor: every layer's input, and flash_out where the flash kernel attends
    assert floor == 24 * 2048 + (cfg.attn_impl == "flash") * 24 * 2048


def test_a_head_narrower_than_the_lanes_takes_a_whole_tile_in_the_floor():
    """``flash_out`` lies [heads, rows, head width] on the device in 128-lane
    tiles: GPT-2 125M's 64-wide heads take a 128-wide head's room (the twin of
    tests/test_chip_compile_whole.py showed it: twice the values)."""
    cfg = TransformerConfig(**{**PYTHIA, "hidden_size": 768, "num_heads": 12, "num_layers": 12,
                               "intermediate_size": None})
    assert tfm.remat_candidates(cfg) == (12 * 768 + 12 * 12 * 128, FFN[:1], 12 * 4 * 768)


# The compiled floor program's temporaries a device (``memory_analysis()`` peak - the
# state - save_flash's residuals) of the ZeRO-3 step of the cell's model for a described
# v5e 2x2, as experiments/remat_fit.py printed them (PR 50): layers, fsdp, sequences a
# chip, sequence length, sizes other than the cell's, the state's bytes, the peak.
FIT = [
    (24, 4, 8, 2048, {}, 4_243_943_444, 9_776_644_608),  # the cell
    (24, 4, 4, 2048, {}, 4_243_943_444, 7_532_267_520),
    (12, 4, 8, 2048, {}, 2_431_045_652, 6_037_881_344),
    (24, 4, 8, 1024, {}, 4_243_943_444, 8_086_044_160),
    (12, 2, 8, 2048, {}, 4_862_091_284, 8_874_348_544),
    (24, 4, 8, 2048, dict(loss_chunk_size=1024), 4_243_943_444, 11_012_931_072),
    (24, 4, 12, 2048, {}, 4_243_943_444, 12_085_654_016),
    (24, 4, 2, 8192, {}, 4_243_943_444, 9_601_357_312),
    (24, 4, 8, 2048, dict(loss_chunk_size=64), 4_243_943_444, 9_601_363_968),
    (24, 4, 4, 2048, dict(loss_chunk_size=64), 4_243_943_444, 7_532_390_912),
    (24, 4, 8, 2048, dict(loss_chunk_size=64, intermediate_size=4096), 3_035_688_980,
     7_981_961_216),
    (24, 4, 8, 2048, dict(loss_chunk_size=64, intermediate_size=5632, activation="swiglu",
                          use_bias=False), 4_318_113_812, 9_698_767_872),
    (24, 4, 9, 2048, {}, 4_243_943_444, 10_354_196_992),
    (24, 4, 16, 1024, {}, 4_243_943_444, 11_012_935_168),
    (24, 4, 6, 2048, dict(intermediate_size=5632, activation="swiglu", use_bias=False),
     4_318_113_812, 8_707_089_920),
    (16, 4, 10, 2048, {}, 3_035_344_916, 8_168_055_296),
    (12, 4, 12, 2048, {}, 2_431_045_652, 7_535_292_928),
]


@pytest.mark.parametrize("layers, fsdp, sequences, length, over, state, peak", FIT)
def test_the_count_of_a_steps_temporaries_holds_the_compiled_programs(
        layers, fsdp, sequences, length, over, state, peak):
    """``step_working_bytes`` and the gradients against what the chip's compiler
    needed: never more than 2% under (``HEADROOM`` covers that five times) and
    never a tenth over (room given away)."""
    cfg = TransformerConfig(**{**PYTHIA, "num_layers": layers, "max_seq_len": length, **over})
    tokens = sequences * length
    floor, _, _ = tfm.remat_candidates(cfg)
    compiled = peak - state - tokens * floor * 2
    counted = state // 12 * 2 + tfm.step_working_bytes(cfg, sequences, tokens)
    assert 0.98 <= counted / compiled <= 1.10


def test_an_unchunked_loss_counts_every_position():
    cfg = TransformerConfig(**PYTHIA)
    chunked = tfm.step_working_bytes(cfg, 8, 16_384)
    for unchunked in (cfg.replace(loss_chunk_size=0), cfg.replace(loss_chunk_size=768)):
        assert tfm.step_working_bytes(unchunked, 8, 16_384) - chunked == (
            16_384 - 8 * 512) * 50304 * 6


def test_model_offers_nothing_under_an_explicit_policy():
    batch = lambda **kw: {k: jax.ShapeDtypeStruct(v, jnp.int32) for k, v in kw.items()}
    cfg = TransformerConfig(**PYTHIA)
    offer = Model(cfg).remat_offer(batch(tokens=(8, 2049)))  # [B, S + 1] holds S inputs
    assert offer == (FFN[:1], 16_384 * 24 * 8192 * 2, 16_384 * 24 * 4096 * 2,
                     tfm.step_working_bytes(cfg, 8, 16_384))
    assert Model(cfg).remat_offer(batch(input_ids=(8, 2048), labels=(8, 2048))) == offer
    assert Model(cfg).remat_offer(batch(pixels=(8, 2048))) is None  # not a batch it can read
    for over in (dict(remat=False), dict(remat_policy="dots_and_flash"),
                 dict(remat_policy="nothing_saveable"), dict(remat_policy="dots_saveable")):
        assert Model(cfg.replace(**over)).remat_offer(batch(tokens=(8, 2049))) is None


# ---------------------------------------------------------------------------
# the limit: one number on every process
# ---------------------------------------------------------------------------

class _Device:
    """A device as PjRt shows it to process 0 of a run: its own answer
    ``memory_stats()``, another process's raise."""

    def __init__(self, process_index, kind="TPU v5 lite", limit=V5E_LIMIT):
        self.process_index, self.device_kind, self.limit = process_index, kind, limit

    def memory_stats(self):
        if self.process_index != _Device.this_process:
            raise jax.errors.JaxRuntimeError(
                "INVALID_ARGUMENT: MemoryStats is only supported for addressable PjRt devices.")
        return self.limit and {"bytes_limit": self.limit}


@pytest.mark.parametrize("devices, limit", [
    # two hosts of four chips: the mesh's first device is the other host's on host 1
    ([_Device(p) for p in (0, 0, 0, 0, 1, 1, 1, 1)], V5E_LIMIT),
    ([_Device(p) for p in (1, 0, 1, 0)], V5E_LIMIT),
    # chips of two kinds: no limit, on every process
    ([_Device(0), _Device(0), _Device(1, kind="TPU v4"), _Device(1, kind="TPU v4")], None),
    ([_Device(0, kind="cpu", limit=None), _Device(1, kind="cpu", limit=None)], None),  # no figures
    # described and not attached (a compile for a chip this host has not got)
    ([_Device(7), _Device(7)], None),
])
def test_every_process_reads_the_same_limit(monkeypatch, devices, limit):
    mesh = types.SimpleNamespace(devices=np.array(devices, dtype=object))
    for process in (0, 1):
        monkeypatch.setattr(_Device, "this_process", process, raising=False)
        monkeypatch.setattr(jax, "local_devices",
                            lambda *a, **k: [d for d in devices if d.process_index == process])
        assert mesh_memory_limit(mesh) == limit


@pytest.mark.parametrize("offload", [False, True])
def test_policy_saves_the_added_names(offload):
    from jax._src.ad_checkpoint import name_p
    from jax._src.interpreters import partial_eval as pe

    saved = lambda policy, name: policy(name_p, name=name) in (True, pe.Saveable)
    floor = tfm._remat_policy("save_flash", offload=offload)
    more = tfm._remat_policy("save_flash", offload=offload, also=FFN[:1])
    for name in FFN:
        assert not saved(floor, name)
        assert saved(more, name) == (name == FFN[0])
    for name in ("flash_out", "flash_lse", "xent_lse"):
        assert saved(floor, name) and saved(more, name)
    if offload:  # the boundary still goes to the host, and is not held on the device too
        assert isinstance(more(name_p, name="layer_in"), pe.Offloadable)
    # a policy the user wrote out is taken as written
    written = tfm._remat_policy("nothing_saveable", also=FFN)
    assert not any(saved(written, n) for n in FFN)


# ---------------------------------------------------------------------------
# the program: fewer products in the backward pass, the same numbers
# ---------------------------------------------------------------------------

def _two_layers(gated: bool, **over) -> Model:
    return Model(TransformerConfig(**{**dict(
        vocab_size=256, max_seq_len=128, num_layers=2, num_heads=4, hidden_size=64,
        intermediate_size=256, pos_emb="rotary", rotary_pct=0.25, parallel_residual=not gated,
        use_bias=not gated, activation="swiglu" if gated else "gelu_exact", attn_impl="flash",
        remat=True, dtype=jnp.float32), **over}))


def _loss_and_grads(model, names):
    params = model.init(jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 129), 0, 256)}

    def step(p, b):
        with tfm.remat_also_saving(names):  # read as the step is traced
            return jax.value_and_grad(model.loss)(p, b)

    lowered = jax.jit(step).lower(params, batch)
    return lowered.compile()(params, batch), lowered.as_text().count("stablehlo.dot_general")


@pytest.mark.parametrize("gated, names, fewer", [
    (False, FFN[:1], 1), (False, FFN, 1), (True, FFN, 2), (True, FFN[:1], 1), (True, FFN[1:], 1),
])
def test_saved_products_leave_the_backward_pass_and_nothing_else_changes(gated, names, fewer):
    """Two scanned layers are ONE traced body: a product saved is one
    ``dot_general`` fewer in the step (the recompute's), and the loss and every
    gradient are the floor program's to the bit."""
    model = _two_layers(gated)
    (loss0, grads0), dots0 = _loss_and_grads(model, ())
    (loss, grads), dots = _loss_and_grads(model, names)
    assert dots0 - dots == fewer
    assert np.array_equal(loss, loss0)
    assert all(jax.tree.leaves(jax.tree.map(np.array_equal, grads, grads0)))


def test_names_are_ignored_outside_save_flash_and_outside_a_checkpoint():
    for over in (dict(remat_policy="nothing_saveable"), dict(remat=False)):
        model = _two_layers(False, **over)
        assert _loss_and_grads(model, FFN)[1] == _loss_and_grads(model, ())[1]
    # a forward program holds the tags as the identity: the same text with them
    model = _two_layers(False)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 128), jnp.int32)
    text = jax.jit(model.apply).lower(params, tokens).as_text()
    assert "ffn_up" not in text


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

def _engine(**model_over):
    model = _two_layers(False, **model_over)
    cfg = base_config(train_batch_size=8, gradient_accumulation_steps=1)
    cfg["zero_optimization"] = {"stage": 3}
    cfg["mesh"] = {"data": 2, "fsdp": 4}
    return deepspeed_tpu.initialize(model=model, config=cfg)[0]


def _step_text(engine, **kw):
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
    tokens = jax.ShapeDtypeStruct((8, 129), jnp.int32, sharding=jax.sharding.NamedSharding(
        engine.mesh, engine.batch_spec))
    return engine._build_train_step(**kw).lower(
        jax.tree.map(sds, engine.state), {"tokens": tokens}).as_text()


@pytest.fixture
def engine_log():
    """The engine's log lines (its logger does not propagate to pytest's)."""
    from deepspeed_tpu.utils.logging import logger

    lines = []
    handler = logging.Handler()
    handler.emit = lambda record: lines.append(record.getMessage())
    logger.addHandler(handler)
    yield lines
    logger.removeHandler(handler)


def _limit_with_room(engine, room):
    """The limit that leaves ``room`` bytes for a candidate, by the engine's own
    count: a device's 128 tokens, float32, two layers; the model's 16-wide heads
    take 128 lanes."""
    from deepspeed_tpu.utils.memory import device_bytes_held

    names, candidate, floor, working = engine.model.remat_offer(
        {"tokens": jax.ShapeDtypeStruct((1, 129), jnp.int32)})
    assert (names, candidate, floor) == (FFN[:1], 128 * 4 * 2 * 256, 128 * 4 * 2 * (64 + 4 * 128))
    held = device_bytes_held(engine.state)
    assert held == sum(int(np.prod(x.sharding.shard_shape(x.shape))) * x.dtype.itemsize
                       for x in jax.tree.leaves(engine.state))  # from the shardings: exact
    grads = device_bytes_held(engine.state["params"])  # float32, as the parameters are
    return int(held + floor + HEADROOM * (grads + working) + room) + 1


def test_engine_adds_nothing_without_memory_figures(engine_log):
    """The CPU platform has no ``memory_stats()``: the step is the floor program."""
    engine = _engine()
    floor_dots = _step_text(engine).count("stablehlo.dot_general")
    engine.train_batch(random_tokens(8, seq=129, vocab=256))
    assert engine._remat_plans == {}
    assert not [l for l in engine_log if "remat:" in l]
    snap = engine.telemetry_snapshot()
    assert "train/remat_saved_bytes" not in snap["metrics"]["gauges"]
    # and a limit that leaves no room says so and adds nothing
    assert _step_text(engine, remat_limit=1000).count("stablehlo.dot_general") == floor_dots
    (plan,) = engine._remat_plans.values()
    assert plan.names == () and plan.room < 0
    assert "also keeps nothing of ['ffn_up']" in engine_log[-1]


def test_engine_plans_from_a_handed_in_limit_and_says_so(engine_log):
    engine = _engine()
    floor = _step_text(engine)
    ffn = 128 * 4 * 2 * 256
    # just room for the feed-forward's candidate, stated twice: ONE program, said once
    texts = [_step_text(engine, remat_limit=_limit_with_room(engine, ffn)) for _ in range(2)]
    assert texts[0] == texts[1]
    assert floor.count("stablehlo.dot_general") - texts[0].count("stablehlo.dot_general") == 1
    said = [l for l in engine_log if "remat:" in l]
    assert len(said) == 1 and "also keeps ['ffn_up']" in said[0]
    (plan,) = engine._remat_plans.values()
    assert plan == RematPlan(names=FFN[:1], saved_bytes=ffn, room=plan.room)
    assert ffn <= plan.room < ffn + 8
    engine.train_batch(random_tokens(8, seq=129, vocab=256))  # the CPU's own step: the floor
    snap = engine.telemetry_snapshot()
    assert snap["metrics"]["gauges"]["train/remat_saved_bytes"] == ffn
    row = next(r for r in snap["program_ledger"] if r["name"].startswith("train/train_step"))
    assert row["remat_saved"] == ["ffn_up"] and row["remat_saved_bytes"] == ffn
    # one byte less of room: the floor program
    engine._remat_plans.clear()
    assert _step_text(engine, remat_limit=_limit_with_room(engine, ffn - 2)) == floor
    # a state handed in (a compile for a chip that is not attached) is counted instead
    engine._remat_plans.clear()
    twice = jax.tree.map(lambda x: jax.ShapeDtypeStruct((2,) + x.shape, x.dtype, sharding=(
        jax.sharding.NamedSharding(engine.mesh, jax.sharding.PartitionSpec()))), engine.state)
    _step_text(engine, remat_limit=_limit_with_room(engine, ffn), remat_state=twice)
    (plan,) = engine._remat_plans.values()
    assert plan.names == () and plan.room < ffn
    # an explicit policy is taken as written, whatever the limit
    written = _engine(remat_policy="nothing_saveable")
    assert _step_text(written, remat_limit=10 ** 9) == _step_text(written)
    assert written._remat_plans == {}


def test_engine_reads_the_limit_from_a_device_of_its_own_process(monkeypatch, engine_log):
    """A multi-process run: the mesh's first devices are another host's, which
    answer no ``memory_stats()`` here. The engine reads its own first device's
    limit, the number every other process reads from its own, and plans the
    same program; the error is nowhere swallowed into 'no limit'."""
    from deepspeed_tpu.utils import memory

    engine = _engine()
    floor = _step_text(engine)
    mine = list(engine.mesh.devices.flat)[4:]
    limit = _limit_with_room(engine, 128 * 4 * 2 * 256)

    def stats(device=None):
        if device not in mine:
            raise jax.errors.JaxRuntimeError(
                "INVALID_ARGUMENT: MemoryStats is only supported for addressable PjRt devices.")
        return {"bytes_limit": limit}

    monkeypatch.setattr(memory, "device_memory_stats", stats)
    monkeypatch.setattr(jax, "local_devices", lambda *a, **k: mine)
    assert memory.mesh_memory_limit(engine.mesh) == limit
    kept = _step_text(engine)
    assert floor.count("stablehlo.dot_general") - kept.count("stablehlo.dot_general") == 1
    assert "also keeps ['ffn_up']" in engine_log[-1]
    # a process that could read no device of the mesh at all has no limit: the floor
    monkeypatch.setattr(jax, "local_devices", lambda *a, **k: [])
    engine._remat_plans.clear()
    assert _step_text(engine) == floor and engine._remat_plans == {}


def test_engine_builds_the_floor_program_when_the_chosen_one_does_not_fit(engine_log):
    engine = _engine()
    batch = random_tokens(8, seq=129, vocab=256)
    engine._train_step = engine._build_train_step()
    engine._remat_plans["a shape"] = RematPlan(names=FFN[:1], saved_bytes=1)

    def refused(state, batch):
        raise jax.errors.JaxRuntimeError(
            "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of memory in memory "
            "space hbm. Used 19.70G of 15.75G hbm.")

    chosen, engine._train_step = engine._train_step, refused
    loss = float(engine.train_batch(batch)["loss"])
    assert np.isfinite(loss) and engine._remat_floor_only and engine._remat_plans == {}
    assert engine._train_step not in (refused, chosen)
    assert len([l for l in engine_log if "did not fit the device" in l]) == 1
    assert _step_text(engine, remat_limit=10 ** 9) == _step_text(engine)  # the floor from now on
    # any other failure is the caller's
    other = _engine()
    other._train_step = refused
    with pytest.raises(jax.errors.JaxRuntimeError):
        other.train_batch(batch)
