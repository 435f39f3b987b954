"""Driver for a training cell: ``deepspeed_tpu.initialize(...).train_batch``
on fresh seeded batches, every step ended by ``block_until_ready``.

The cell file gives ``job`` (what is trained: optimizer, batch, sequence
length, ZeRO stage, mesh), ``traffic`` (the batch generator and its
parameters) and ``tuning`` (what the program could choose itself and a user
must set today).

Correctness goes through the measured program. The first ``train_batch``
call (a warm-up step, in set-up) is given the first ``reference_sequences``
sequences of the stream repeated to a full batch, so the loss it returns, on
the engine's initial parameters, is the mean over exactly those sequences; it
agrees with the plain float32 reference the configuration names
(``references/``) on the same sequences and parameters within ``LOSS_TOL``,
the same for every configuration. And in the window the loss falls, nothing
is non-finite and no overflow flag is set.
"""

from __future__ import annotations

import time

import numpy as np

from ..references import load_reference

# |first train_batch loss - float32 reference loss| on the same 4 x 2048 tokens
# and parameters, bf16 compute against float32. The engine's forward alone
# (eval_batch) measured 0.0000 to 0.0014 from the reference on the chip at the
# real size (nine runs, PR 23); 0.01 leaves room for another seed and none for
# 8-bit floats (2^-3 to 2^-4 relative against bf16's 2^-8: an order of
# magnitude and more above what was measured). The measured difference is
# printed on the "measured" line of every run.
LOSS_TOL = 0.01

SPANS = ("train_batch", "generator", "fetch")


def ds_config(job: dict, micro: int, replicas: int) -> dict:
    """The engine's configuration for a cell's ``job`` at a micro-batch."""
    return {
        "train_batch_size": job["sequences_per_step"],
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": job["sequences_per_step"] // (micro * replicas),
        "optimizer": job["optimizer"],
        "zero_optimization": {"stage": job["zero_stage"]},
        "bf16": {"enabled": True},
        "gradient_clipping": job["gradient_clipping"],
        "steps_per_print": 10 ** 9,
        "mesh": job["mesh"],
    }


def _build(run):
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.comm.mesh import MeshConfig, build_mesh
    from deepspeed_tpu.models.transformer import Model, TransformerConfig

    job, tuning = run.sized("job"), run.sized("tuning")
    cfg = TransformerConfig(dtype=jnp.bfloat16, **{**run.program, **tuning["model"],
                                                   "max_seq_len": job["sequence_length"]})
    mesh = build_mesh(MeshConfig(**job["mesh"]), devices=jax.devices()[:run.chips])
    replicas = 1
    for axis in ("data", "fsdp"):
        replicas *= mesh.shape.get(axis, 1)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=Model(cfg), config=ds_config(job, tuning["micro_batch_per_chip"], replicas),
        rng=jax.random.PRNGKey(run.seed), mesh=mesh)
    jax.block_until_ready(engine.state)
    return engine, job


def _reference_loss(run, engine, sequences) -> float:
    """Float32 reference loss on the engine's initial parameters: the
    reference is handed the sharded tree whole, and each slice it takes (the
    top-level leaves, then one layer at a time) is brought to one device as
    it is used, so the state is never gathered at once."""
    import jax

    dev = jax.devices()[0]
    return load_reference(run.program).lm_loss(
        run.program, engine.state["params"], sequences,
        fetch=lambda leaves: jax.device_put(leaves, dev))


def _memory_analysis(run, engine, batch):
    """The compiler's own account of the train step (a cache hit: the step is
    compiled already). Reads the engine's private builder: a rename fails
    here, loudly."""
    import jax
    from jax.sharding import NamedSharding

    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
    state = jax.tree.map(sds, engine.state)
    tokens = jax.ShapeDtypeStruct(batch.shape, batch.dtype,
                                  sharding=NamedSharding(engine.mesh, engine.batch_spec))
    step = engine._build_train_step()
    return run.memory_dict(step.lower(state, {"tokens": tokens}).compile())


def run(run) -> dict:
    import jax

    engine, job = _build(run)
    t_built = time.perf_counter()
    traffic = run.traffic()
    batches = traffic["batches"]
    tokens_per_step = traffic["tokens_per_step"]
    head = next(batches)

    # the reference's loss on the initial parameters, before the first step
    # consumes them; that step's batch is the same sequences, repeated
    n_ref = job["reference_sequences"]
    first = np.tile(head[:n_ref], (len(head) // n_ref, 1))
    assert first.shape == head.shape, "reference_sequences must divide sequences_per_step"
    ref_loss = _reference_loss(run, engine, head[:n_ref])
    t_checked = time.perf_counter()

    def step(batch):
        """One synchronised step; the next batch is made while it runs."""
        t0 = time.perf_counter()
        with run.span("train_batch"):
            m = engine.train_batch({"tokens": batch})
        with run.span("generator"):
            nxt = next(batches)
        with run.span("fetch"):
            jax.block_until_ready(m["loss"])
        return nxt, (t0, time.perf_counter(), m["loss"], m["overflow"])

    # warm-up: the one train step (the first call compiles it), twice
    batch, rec0 = step(first)
    batch, _ = step(batch)
    first_loss = float(np.asarray(rec0[2]))
    memory_analysis = _memory_analysis(run, engine, first) if run.trace else None
    t_window = time.perf_counter()
    setup_s = t_window - run.t_start
    run.note(event="setup", build_s=t_built - run.t_start, check_s=t_checked - t_built,
             warm_s=t_window - t_checked, reference_loss=ref_loss, first_step_loss=first_loss,
             loss_tol=LOSS_TOL, memory_analysis=memory_analysis,
             tokens_per_step=tokens_per_step)

    # the window: whole steps. With --trace 1 the profiler runs over the last
    # steps of it (two whole steps at the least).
    deadline = t_window + run.seconds
    trace_lead = run.sized("trace")["seconds"]
    records, traced_steps, tracing = [], 0, False
    while time.perf_counter() < deadline or (tracing and traced_steps < 2):
        if run.trace and not tracing and time.perf_counter() >= deadline - trace_lead:
            run.trace_start()
            tracing = True
        batch, rec = step(batch)
        records.append(rec)
        traced_steps += tracing
    if tracing:
        run.trace_stop()
        run.trace_reduce(SPANS)

    # only steps that ended inside the window count, and the window ends with
    # the last of them: a fixed number of whole steps, not a cut-off step
    counted = [r for r in records if r[1] <= deadline] or records[:1]
    window_s = counted[-1][1] - t_window
    losses = [float(np.asarray(r[2])) for r in counted]
    overflow = [bool(np.asarray(r[3])) for r in counted]
    bad = [i for i, (l, o) in enumerate(zip(losses, overflow)) if o or not np.isfinite(l)]
    k = max(1, len(losses) // 4)
    falls = float(np.mean(losses[-k:])) < float(np.mean(losses[:k])) if len(losses) > 1 else True
    correct = abs(first_loss - ref_loss) <= LOSS_TOL and falls and not bad
    return {
        "correct": correct, "attempted": len(counted), "failed": len(bad),
        "t_setup": setup_s, "window_s": window_s,
        "n_compiles": run.compiles_between(t_window, counted[-1][1]),
        "train": {"steps": [(r[0] - t_window, r[1] - t_window) for r in counted],
                  "tokens_per_step": tokens_per_step,
                  "sequences_per_step": job["sequences_per_step"],
                  "sequence_length": job["sequence_length"], "losses": losses,
                  "traced_steps": traced_steps},
        "serve": None,
        "memory_analysis": memory_analysis,
        "notes": {"steps": len(counted), "loss_first": losses[0], "loss_last": losses[-1],
                  "loss_falls": falls, "first_step_vs_reference": abs(first_loss - ref_loss)},
    }
