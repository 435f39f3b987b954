"""What the ``test_chip_compile_*`` files share (PR 61 split ``test_chip_compile.py``
by program family): the described chip and the two fixtures every compile runs
under, a bare ``SlotWorker`` with the shapes of its operands, and the readers of a
compiled program's text that more than one of the files call.

The TPU compiler is installed in the sandbox: ``jax.experimental.topologies``
describes a ``v5e:2x2`` host and ``lower(...).compile()`` then raises what the
chip's compiler would raise — a misaligned kernel slice, too much VMEM, a
program over HBM — which interpret mode cannot show. Nothing runs, so nothing
in those files is a chip run or a time.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HBM_BYTES = 16e9  # one v5e chip


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    # dstpu: allow[broad-except] -- no TPU compiler in this installation: whatever it raises, the answer is skip
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    return topo.devices


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip: the next run would warn and compile
    again, so the cache is off around these."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def as_tpu(monkeypatch):
    """Code that asks ``jax.default_backend()`` still sees the CPU here;
    steer it onto its TPU branch (compiled kernels, donation) for the
    compile, in the test and not through an option of the program."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


# The routed feed-forward's grouped matmul in a compiled program's text: the
# compiler's own (``lax.ragged_dot``) and the Pallas kernel's, which carries the
# other's name as the head of its own (``ops/pallas/grouped_gemm.py::KERNEL_NAME``).
RAGGED_DOT = r"^\s*%?ragged-dot(?!-gmm)[\w.-]* = "
GMM_CALL = r"^\s*%?ragged-dot-gmm[\w.-]*"


def _footprint(compiled) -> float:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def _bare_slot_worker(cfg, n, Smax, one_chip):
    """A ``SlotWorker`` with just what its program builders read, and the
    shapes of its operands on the described chip, the weights typed as
    ``InferenceEngine`` holds them: (worker, params, cache, sds)."""
    from deepspeed_tpu.inference.serving import SlotWorker
    from deepspeed_tpu.models import transformer as tfm
    from deepspeed_tpu.models.transformer import Model, hold_for_compute

    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    params = jax.tree.map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(lambda r: hold_for_compute(cfg, Model(cfg).init(r)), jax.random.PRNGKey(0)))
    cache = jax.tree.map(  # the tree the model's attention caches: K/V per head, or a latent
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(lambda: tfm.init_cache(cfg, n, Smax, dtype=jnp.bfloat16)))
    worker = SlotWorker.__new__(SlotWorker)
    worker.cfg, worker.Smax = cfg, Smax
    tfm._ACTIVE_MESH[0] = None  # the engine's own (one chip) in a process; an earlier test's here
    worker._cache_shardings = {name: one_chip for name in cache}
    return worker, params, cache, sds


def _decode_operands(params, cache, n, sds):
    vec = lambda dtype: sds((n,), dtype)
    return (params, cache, vec(jnp.int32), vec(jnp.int32), vec(jnp.int32), vec(jnp.bool_),
            sds((2,), jnp.uint32), vec(jnp.float32), vec(jnp.int32), vec(jnp.float32))


def _compile_decode(worker, params, cache, n, sds):
    return worker._build_decode().lower(*_decode_operands(params, cache, n, sds)).compile()


def _loop_depths(jaxpr, primitive, depth=0):
    """How deep in loops (``scan`` / ``while``) each ``primitive`` equation of a jaxpr lies."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive:
            found.append(depth)
        inner = depth + (eqn.primitive.name in ("scan", "while"))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _loop_depths(sub, primitive, inner)
    return found


def _walk_built_once(worker, params, cache, n, sds, loops, grouped=0):
    """The decode kernel's work list (a ``cumsum`` over the rows' blocks; the sampler's
    nucleus has the program's other one) is built outside the layer loop(s), once a
    step, and the kernel stands ``loops`` loops deep. ``grouped``: the grouped-matmul
    kernel calls beside it in the layer loop (three where a routed step's few rows take
    the sorted form, PR 62), whose visits are counted (``cumsum``) per layer, by nature."""
    jaxpr = worker._build_decode().trace(*_decode_operands(params, cache, n, sds)).jaxpr.jaxpr
    assert set(_loop_depths(jaxpr, "cumsum")) == ({0, loops} if grouped else {0})
    assert _loop_depths(jaxpr, "pallas_call") == [loops] * (1 + grouped)


def _compile_prefill(worker, params, cache, bucket, sds):
    one = lambda dtype: sds((1,), dtype)
    return worker._build_prefill(bucket).lower(
        params, cache, sds((1, bucket), jnp.int32), sds((), jnp.int32), sds((), jnp.int32),
        sds((2,), jnp.uint32), one(jnp.float32), one(jnp.int32), one(jnp.float32)).compile()


def _computations(text):
    """An optimised HLO module's text as {computation: (its lines, the
    computations it calls outside a conditional's branches, those it calls as
    a conditional's branches)}, and the entry computation's name."""
    comps, entry, name = {}, None, None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%?([\w.-]+) \(.*\) -> .* \{$", line)
        if head:
            name = head.group(2)
            comps[name] = ([], set(), set())
            entry = name if head.group(1) else entry
        elif name is not None and line != "}":
            body, calls, branches = comps[name]
            body.append(line)
            calls.update(re.findall(r"(?:calls|to_apply|body|condition)=%?([\w.-]+)", line))
            for group in re.findall(r"branch_computations=\{([^}]*)\}", line):
                branches.update(c.strip().lstrip("%") for c in group.split(","))
            branches.update(re.findall(r"(?:true|false)_computation=%?([\w.-]+)", line))
    return comps, entry


def _reach(comps, roots, through_branches):
    seen, todo = set(), list(roots)
    while todo:
        c = todo.pop()
        if c not in seen and c in comps:
            seen.add(c)
            todo += comps[c][1] | (comps[c][2] if through_branches else set())
    return seen


def _operations_writing(text, elements, dtype="bf16"):
    """The instructions of an optimised module that run as operations of their own
    (the lines of the entry computation and of every loop body, loop condition and
    conditional branch under it; not those inside a fusion, which make no array)
    and yield a ``dtype`` array of exactly ``elements`` elements in any order of
    dimensions: one layer's K (or V), sliced out of its stack, copied or re-laid.
    Views (a bitcast, an element of a tuple, a parameter) write nothing."""
    comps, entry = _computations(text)
    seen, todo, found = set(), [entry], []
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        todo += comps[name][2]
        for line in comps[name][0]:
            todo += re.findall(r"(?:body|condition)=%?([\w.-]+)", line)
            todo += re.findall(r" call\(.*to_apply=%?([\w.-]+)", line)
            op = re.match(rf"\s*(?:ROOT )?%?[\w.-]+ = {dtype}\[([\d,]+)\]\S* ([\w-]+)\(", line)
            if (op and op.group(2) not in ("bitcast", "get-tuple-element", "parameter")
                    and np.prod([int(d) for d in op.group(1).split(",")]) == elements):
                found.append(line.strip()[:160])
    return found


def _whole_copies(text, shape):
    return re.findall(rf"^\s*%?[\w.-]+ = {shape}\S* copy\((\S+?)\)", text, re.M)
