"""Whole programs at GPT-2 125M compiled for a described TPU v5e
(``tests/chip_compile_cases.py``): the train step as ``chip_smoke.py`` configures it, the
ZeRO-3 fsdp=4 step over the four described chips, the step under a handed-in memory
limit, and the serving programs of ``chip_smoke.py``'s serve phase. Slow tier but for
the handed-in limit: run them with ``-m slow`` before spending chip time on
``chip_smoke.py``.
"""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

import chip_smoke

from chip_compile_cases import (  # noqa: F401 -- the fixtures are used by name
    HBM_BYTES, _bare_slot_worker, _compile_decode, _compile_prefill, _footprint, v5e,
    no_persistent_cache, as_tpu)


# ---------------------------------------------------------------------------
# whole programs at GPT-2 125M (slow tier)
# ---------------------------------------------------------------------------

def _retarget(engine, devices) -> None:
    """Point a CPU-built engine's mesh and state shardings at described
    devices, so that the step it builds next lowers for them."""
    mesh = Mesh(np.asarray(devices).reshape(engine.mesh.devices.shape),
                engine.mesh.axis_names)
    engine.mesh = mesh
    engine.model.set_mesh(mesh)
    engine._state_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s.spec, memory_kind=s.memory_kind),
        engine._state_shardings, is_leaf=lambda x: isinstance(x, NamedSharding))


def _compile_train_step(engine, sz):
    state = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        engine.state, engine._state_shardings)
    batch = {"tokens": jax.ShapeDtypeStruct(
        (sz["B"], sz["S"] + 1), jnp.int32,
        sharding=NamedSharding(engine.mesh, engine.batch_spec))}
    return engine._build_train_step().lower(state, batch).compile()


@pytest.mark.slow
def test_train_step_125m_compiles_for_one_v5e(v5e, no_persistent_cache, as_tpu):
    import deepspeed_tpu
    from deepspeed_tpu.comm.mesh import MeshConfig, build_mesh

    sz = chip_smoke.REAL
    cfg = chip_smoke._ds_config(sz, zero_stage=1, micro=sz["micro"],
                                gas=sz["B"] // sz["micro"], mesh={"data": 1})
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=chip_smoke._train_model(sz), config=cfg,
        mesh=build_mesh(MeshConfig(data=1), devices=jax.devices()[:1]))
    _retarget(engine, v5e[:1])
    compiled = _compile_train_step(engine, sz)
    assert "tpu_custom_call" in compiled.as_text()  # the flash kernel is in it
    print("train step 125M on one v5e:", compiled.memory_analysis())
    assert _footprint(compiled) < HBM_BYTES


@pytest.mark.slow
def test_fsdp4_train_step_125m_compiles_for_v5e_2x2(v5e, no_persistent_cache, as_tpu):
    import deepspeed_tpu
    from deepspeed_tpu.comm.mesh import MeshConfig, build_mesh

    sz = chip_smoke.REAL
    cfg = chip_smoke._ds_config(sz, zero_stage=3, micro=sz["micro"], gas=1,
                                mesh={"data": 1, "fsdp": 4})
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=chip_smoke._train_model(sz), config=cfg,
        mesh=build_mesh(MeshConfig(data=1, fsdp=4), devices=jax.devices()[:4]))
    _retarget(engine, v5e)
    compiled = _compile_train_step(engine, sz)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" in text or "all-reduce" in text  # really partitioned
    print("fsdp=4 train step 125M, per device:", compiled.memory_analysis())
    assert _footprint(compiled) < HBM_BYTES


def _save_flash_twin(mesh_sizes: dict, n_devices: int):
    """The 125M twin cut to eight layers and a small vocabulary (a scanned
    layer is traced once whatever the depth), under ``save_flash``: the policy
    whose checkpoints the engine may add to (the file's own twin states
    ``dots_and_flash``, an explicit choice)."""
    import deepspeed_tpu
    from deepspeed_tpu.comm.mesh import MeshConfig, build_mesh
    from deepspeed_tpu.models.transformer import Model

    sz = {**chip_smoke.REAL, "L": 8, "V": 2048, "B": 16, "micro": 16 // n_devices}
    model = Model(chip_smoke._train_model(sz).config.replace(remat_policy="save_flash"))
    cfg = chip_smoke._ds_config(sz, zero_stage=3 if n_devices > 1 else 1, micro=sz["micro"],
                                gas=1, mesh=mesh_sizes)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, config=cfg,
        mesh=build_mesh(MeshConfig(**mesh_sizes), devices=jax.devices()[:n_devices]))
    return engine, sz


def _compile_with_limit(engine, sz, **kw):
    state = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        engine.state, engine._state_shardings)
    batch = {"tokens": jax.ShapeDtypeStruct(
        (sz["B"], sz["S"] + 1), jnp.int32,
        sharding=NamedSharding(engine.mesh, engine.batch_spec))}
    return engine._build_train_step(**kw).lower(state, batch).compile()


@pytest.mark.parametrize("mesh_sizes, n_devices", [({"data": 1}, 1), ({"data": 1, "fsdp": 4}, 4)],
                         ids=["one_v5e", "fsdp4_on_the_2x2"])
def test_train_step_keeps_the_matmul_output_a_handed_in_limit_holds(
        v5e, no_persistent_cache, as_tpu, mesh_sizes, n_devices):
    """``_saving_what_fits`` for the described chip: with no limit handed in
    (a described device has no ``memory_stats()``) the step is the floor
    program; with a limit that holds the candidate the backward pass has one
    product fewer (the up projection's) and the compiler's temporaries grow by
    about the candidate's bytes, never by twice them; with a limit that does
    not, the floor program again."""
    from deepspeed_tpu.models.transformer import FFN_NAMES
    from deepspeed_tpu.runtime.remat_plan import HEADROOM
    from deepspeed_tpu.utils.memory import device_bytes_held

    engine, sz = _save_flash_twin(mesh_sizes, n_devices)
    _retarget(engine, v5e[:n_devices])
    products = lambda c: len(re.findall(r" convolution\(", c.as_text()))
    temporaries = lambda c: c.memory_analysis().temp_size_in_bytes
    floor = _compile_with_limit(engine, sz)
    assert engine._remat_plans == {}
    rows = sz["B"] // n_devices
    names, ffn, residuals, working = engine.model.remat_offer(
        {"tokens": jax.ShapeDtypeStruct((rows, sz["S"] + 1), jnp.int32)})
    # bfloat16 over eight layers; the twin's 64-wide heads lie in 128-lane tiles in the
    # kernel's [heads, rows, width] arrays: flash_out takes twice its values
    per_width = rows * sz["S"] * 2 * sz["L"]
    assert (names, ffn, residuals) == (
        FFN_NAMES[:1], per_width * 4 * sz["D"], per_width * (sz["D"] + 2 * sz["D"]))
    state = jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
                         engine.state, engine._state_shardings)
    grads = device_bytes_held(state["params"]) // 2  # float32 parameters, bfloat16 gradients
    limit_for = lambda room: int(
        device_bytes_held(state) + residuals + HEADROOM * (grads + working) + room) + 1
    kept = _compile_with_limit(engine, sz, remat_limit=limit_for(ffn), remat_state=state)
    (plan,) = engine._remat_plans.values()
    engine._remat_plans.clear()
    assert plan.names == FFN_NAMES[:1] and plan.saved_bytes == ffn
    assert products(floor) - products(kept) == 1
    # the saved stack ITSELF, [layers, rows, sequence, 4 x hidden] in bfloat16 = the candidate's
    # bytes, is in the kept program and not in the floor's, and no second copy of it: the
    # temporaries grow by under twice its bytes. By how much is where each program's memory
    # peaks: 0.95 of the candidate's bytes on the 2x2 at 4 rows a chip, 1.50 on one chip at 16
    # rows (1.26 and 1.65 before PR 65, whose backward pass no longer holds two lane-broadcast
    # [heads, rows, 128] float32 arrays a layer; the kept program's peak lay there, the floor's
    # only in part: 100 MB and 37 MB less on the 2x2)
    stack = sz["L"], rows, sz["S"], 4 * sz["D"]
    shape = rf"bf16\[{','.join(map(str, stack))}\]"
    assert math.prod(stack) * 2 == ffn
    assert re.search(shape, kept.as_text()) and not re.search(shape, floor.as_text())
    assert temporaries(kept) - temporaries(floor) < 2 * ffn
    # and the count of the floor program's temporaries covers what the compiler needed
    peak = floor.memory_analysis().peak_memory_in_bytes
    assert peak - device_bytes_held(state) - residuals <= HEADROOM * (grads + working)
    again = _compile_with_limit(engine, sz, remat_limit=limit_for(ffn - 2), remat_state=state)
    (plan,) = engine._remat_plans.values()
    assert plan.names == ()
    assert (products(again), temporaries(again)) == (products(floor), temporaries(floor))


@pytest.mark.slow
def test_serving_programs_125m_compile_for_one_v5e(v5e, no_persistent_cache, as_tpu):
    """The programs ``SlotWorker`` builds for chip_smoke's serve phase: the
    one decode step, and the smallest and largest prefill bucket its prompts
    fall into (each program takes ~25 s here: the vocab-wide sampler sort)."""
    from deepspeed_tpu.inference.serving import _next_pow2
    from deepspeed_tpu.models.transformer import TransformerConfig

    sz = chip_smoke.REAL
    cfg = TransformerConfig(
        vocab_size=sz["V"], max_seq_len=sz["S"], num_layers=sz["L"],
        num_heads=sz["H"], hidden_size=sz["D"], pos_emb="learned", dtype=jnp.bfloat16)
    n, Smax = sz["n_slots"], sz["S"]
    worker, params, cache, sds = _bare_slot_worker(cfg, n, Smax, SingleDeviceSharding(v5e[0]))
    decode = _compile_decode(worker, params, cache, n, sds)
    assert "tpu_custom_call" in decode.as_text()  # the Pallas decode kernel
    worst = _footprint(decode)
    buckets = sorted({max(16, _next_pow2(p)) for p in sz["prompt_lens"]})
    for bucket in (buckets[0], buckets[-1]):
        worst = max(worst, _footprint(_compile_prefill(worker, params, cache, bucket, sds)))
    print(f"serving programs 125M on one v5e: worst footprint {worst / 1e9:.2f} GB")
    assert worst < HBM_BYTES
