"""Compiled pipeline execution.

Reference: ``runtime/pipe/engine.py`` — ``PipelineEngine`` (:36),
``train_batch`` (:294), ``_exec_schedule`` (:1359) interpreting the
instruction stream, p2p transport ``runtime/pipe/p2p.py``.

TPU-native inversion: instead of an eager interpreter issuing sends/recvs per
instruction, the WHOLE pipeline — warmup bubble, steady state, drain — is one
``lax.scan`` over clock ticks inside the engine's single compiled train step:

  * per-stage activations live in a buffer with a leading stage axis sharded
    over the mesh ``pipe`` axis;
  * every tick vmaps the stage function over that axis (GSPMD places stage
    i's compute on pipe-rank i) and rolls the buffer by one stage —
    ``jnp.roll`` on a sharded axis compiles to `CollectivePermute` over ICI,
    the reference's Send/RecvActivation pair;
  * the backward pass is jax.grad through the scan: XLA replays the permutes
    reversed, which is exactly Send/RecvGrad — no hand-written schedule.

Scheduling note: autodiff of the scan yields a GPipe-profile schedule (all
forwards, then all backwards) rather than interleaved 1F1B; with the stage
body rematerialized the live set is the scan carry (one activation per stage)
plus collected last-stage outputs — the same O(M + S) activation budget the
reference's TrainSchedule targets (pipe/schedule.py num_pipe_buffers). XLA's
latency-hiding scheduler overlaps the collective-permutes with stage compute
(the reference overlaps p2p on side streams by hand).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..comm.collectives import all_reduce, ppermute
from ..runtime.engine import DeepSpeedEngine
from ..utils.logging import log_dist


def pipeline_apply(stage_fn, stage_params, x_mb, num_stages: int, mesh: Optional[Mesh],
                   collect_aux: bool = False):
    """Stream M microbatches through S stages; returns last-stage outputs.

    stage_fn:     (per-stage params, h[mb, ...]) -> h[mb, ...], or with
                  ``collect_aux`` -> (h, aux_scalar) (e.g. MoE load-balancing
                  losses); aux is summed over VALID (stage, tick) pairs only —
                  bubble/drain re-feeds contribute nothing.
    stage_params: pytree with leading axis [S, ...] (sharded over 'pipe')
    x_mb:         [M, mb, ...] stage-0 inputs (already embedded)
    returns:      [M, mb, ...] outputs of the last stage
                  (with collect_aux: (outputs, aux_sum))

    Clock t of the scan computes, in parallel across pipe ranks, stage s's
    work on microbatch t - s (where valid) — the diagonal wavefront of the
    1F1B/GPipe diagrams. Total ticks = M + S - 1; the S - 1 fill/drain ticks
    are the pipeline bubble (same bubble fraction as the reference's
    schedule; reference schedule.py:182).
    """
    M = x_mb.shape[0]
    S = num_stages
    mb_shape = x_mb.shape[1:]
    dtype = x_mb.dtype

    def _batch_axes(dim: int):
        """('data','fsdp') if they divide the microbatch dim, else None."""
        if mesh is None:
            return None
        n = mesh.shape.get("data", 1) * mesh.shape.get("fsdp", 1)
        return ("data", "fsdp") if n > 1 and dim % n == 0 else None

    # Non-batch dims stay UNCONSTRAINED: pinning seq/hidden to replicated
    # here while context-parallel attention shards seq inside stage_fn made
    # the partitioner bounce the clock-loop buffers between incompatible
    # device orders — an '[SPMD] Involuntary full rematerialization' (a
    # whole-tensor replicate) every tick (VERDICT r4 #6).
    # Leaving them open lets one consistent layout flow through the loop.
    U = PartitionSpec.UNCONSTRAINED

    def constrain_stage(t):
        if mesh is None or mesh.shape.get("pipe", 1) == 1:
            return t
        spec = PartitionSpec("pipe", _batch_axes(t.shape[1]), *([U] * (t.ndim - 2)))
        return lax.with_sharding_constraint(t, NamedSharding(mesh, spec))

    def constrain_mb(t):
        if mesh is None:
            return t
        spec = PartitionSpec(None, _batch_axes(t.shape[1]), *([U] * (t.ndim - 2)))
        return lax.with_sharding_constraint(t, NamedSharding(mesh, spec))

    buf = jnp.zeros((S,) + mb_shape, dtype)  # activation entering each stage
    outs = jnp.zeros((M,) + mb_shape, dtype)

    def tick(carry, t):
        buf, outs, aux_sum = carry
        # stage 0 ingests microbatch t (dummy re-feed of the last mb during drain)
        x0 = lax.dynamic_index_in_dim(x_mb, jnp.clip(t, 0, M - 1), axis=0, keepdims=False)
        buf = buf.at[0].set(jnp.where(t < M, x0, buf[0]))
        buf = constrain_stage(buf)
        if collect_aux:
            y, aux = jax.vmap(stage_fn)(stage_params, buf)  # aux [S]
            stage_mb = t - jnp.arange(S)  # microbatch at each stage this tick
            valid = (stage_mb >= 0) & (stage_mb < M)
            aux_sum = aux_sum + jnp.sum(jnp.where(valid, aux, 0.0))
        else:
            y = jax.vmap(stage_fn)(stage_params, buf)  # all stages, one program
        y = constrain_stage(y)
        # collect last stage's result for microbatch t - (S-1)
        idx = t - (S - 1)
        upd = lax.dynamic_update_index_in_dim(outs, y[-1], jnp.clip(idx, 0, M - 1), axis=0)
        outs = jnp.where(idx >= 0, upd, outs)
        # hand stage s's output to stage s+1  (CollectivePermute over 'pipe')
        buf = jnp.roll(y, 1, axis=0)
        return (buf, outs, aux_sum), None

    (_, outs, aux_sum), _ = lax.scan(
        tick, (buf, outs, jnp.zeros((), jnp.float32)), jnp.arange(M + S - 1))
    outs = constrain_mb(outs)
    return (outs, aux_sum) if collect_aux else outs


def pipeline_train_1f1b(
    stage_fn,
    loss_head,
    stage_params,
    head_params,
    x_mb,
    labels_mb,
    loss_scale,
    num_stages: int,
    mesh: Mesh,
):
    """Execute the clocked 1F1B TrainSchedule (pipe/schedule.py:144) as a
    compiled shard_map program over the 'pipe' axis — the executed form of the
    reference's ``_exec_schedule`` interpreter (runtime/pipe/engine.py:1359).

    Per clock tick t, stage s runs ForwardPass of microbatch (t - s)/2 and/or
    BackwardPass of microbatch (t - (2S-1-s))/2 — exactly the schedule's
    closed-form clocks — with activations/gradients exchanged by ppermute
    (Send/Recv{Activation,Grad}). Each stage stashes only the INPUTS of its
    in-flight microbatches (<= S buffers — the 1F1B memory bound; GPipe's
    autodiff-of-scan stores M + S - 1) and rebuilds the stage VJP at backward
    time (activation recomputation, one extra forward per microbatch — the
    same trade the engine's remat policy makes).

    Args:
      stage_fn:    (stage param slice [K, ...], h [mb, ...]) -> h
      loss_head:   (head_params, h [mb, ...], labels [mb, ...]) -> scalar loss
      stage_params: [S, K, ...] pytree sharded over 'pipe'
      x_mb:        [M, mb, ...] embedded microbatch inputs
      loss_scale:  scalar multiplied into the backward seed (fp16)
    Returns (loss_mean, grads_stage [S,K,...], grads_head, grads_x [M,mb,...],
    trace) where trace = (is_fwd, fwd_mb, is_bwd, bwd_mb) each [S, ticks] for
    execution-order conformance tests against TrainSchedule.
    """
    M = x_mb.shape[0]
    S = num_stages
    P = PartitionSpec
    dp = ("data", "fsdp")
    ticks = 2 * M + 2 * S - 2

    stage_P = jax.tree.map(lambda _: P("pipe"), stage_params)
    head_P = jax.tree.map(lambda _: P(), head_params)

    def body(stage_p, head_p, x_mb, labels_mb, loss_scale):
        s = lax.axis_index("pipe")
        sp = jax.tree.map(lambda a: a[0], stage_p)  # local [K, ...]
        mb_shape = x_mb.shape[1:]
        msg0 = jnp.zeros(mb_shape, x_mb.dtype)
        stash0 = jnp.zeros((S,) + mb_shape, x_mb.dtype)
        gstage0 = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), sp)
        ghead0 = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), head_p)
        gx0 = jnp.zeros(x_mb.shape, jnp.float32)

        def tick(carry, t):
            fwd_msg, bwd_msg, stash, gstage, ghead, gx_all, loss_sum = carry
            tf = t - s
            is_fwd = (tf >= 0) & (tf % 2 == 0) & (tf // 2 < M)
            mF = jnp.clip(tf // 2, 0, M - 1)
            tb = t - (2 * S - 1 - s)
            is_bwd = (tb >= 0) & (tb % 2 == 0) & (tb // 2 < M)
            mB = jnp.clip(tb // 2, 0, M - 1)

            x_first = lax.dynamic_index_in_dim(x_mb, mF, 0, keepdims=False)
            x_in = jnp.where(s == 0, x_first, fwd_msg)

            def do_fwd(stash):
                y = stage_fn(sp, x_in)
                return y, stash.at[mF % S].set(x_in)

            y_f, stash = lax.cond(
                is_fwd, do_fwd, lambda st: (jnp.zeros_like(msg0), st), stash
            )

            labels_b = lax.dynamic_index_in_dim(labels_mb, mB, 0, keepdims=False)

            def do_bwd(op):
                stash, gstage, ghead, gx_all, loss_sum = op
                x_b = stash[mB % S]
                y, pull = jax.vjp(lambda p, x: stage_fn(p, x), sp, x_b)

                def last_seed(y):
                    lv, pull2 = jax.vjp(
                        lambda hp, yy: loss_head(hp, yy, labels_b), head_p, y
                    )
                    gh, gy = pull2(jnp.asarray(loss_scale, lv.dtype))
                    return gy.astype(x_mb.dtype), gh, lv

                def mid_seed(y):
                    zh = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), head_p)
                    return bwd_msg, zh, jnp.zeros((), jnp.float32)

                gy, gh, lv = lax.cond(s == S - 1, last_seed, mid_seed, y)
                gp, gx = pull(gy)
                gstage = jax.tree.map(jnp.add, gstage, gp)
                ghead = jax.tree.map(jnp.add, ghead, gh)
                loss_sum = loss_sum + lv
                # stage 0's input grad is the embedding cotangent; other
                # stages write a no-op (their own current slice back)
                gx_all = gx_all.at[mB].set(
                    jnp.where(s == 0, gx.astype(jnp.float32), gx_all[mB])
                )
                return gx, (stash, gstage, ghead, gx_all, loss_sum)

            gx_out, (stash, gstage, ghead, gx_all, loss_sum) = lax.cond(
                is_bwd,
                do_bwd,
                lambda op: (jnp.zeros_like(msg0), op),
                (stash, gstage, ghead, gx_all, loss_sum),
            )

            # comm/ wrappers, not bare lax: the collective X-ray reconciles
            # HLO collectives against this byte accounting
            fwd_msg = ppermute(y_f, "pipe", [(i, i + 1) for i in range(S - 1)])
            bwd_msg = ppermute(gx_out, "pipe", [(i, i - 1) for i in range(1, S)])
            trace = (
                is_fwd.astype(jnp.int32), mF.astype(jnp.int32),
                is_bwd.astype(jnp.int32), mB.astype(jnp.int32),
            )
            return (fwd_msg, bwd_msg, stash, gstage, ghead, gx_all, loss_sum), trace

        carry0 = (msg0, msg0, stash0, gstage0, ghead0, gx0, jnp.zeros((), jnp.float32))
        (_, _, _, gstage, ghead, gx_all, loss_sum), trace = lax.scan(
            tick, carry0, jnp.arange(ticks)
        )
        # reductions: 'pipe' collects the stage-local pieces (loss/head grads
        # live on the last stage, embedding cotangents on stage 0); the dp
        # axes average what pjit's implicit psum does in the autodiff path —
        # each dp shard saw only its slice of every microbatch.
        n_dp = mesh.shape.get("data", 1) * mesh.shape.get("fsdp", 1)
        loss = all_reduce(all_reduce(loss_sum, "pipe"), dp, op="mean") / M
        # grads of the MEAN loss over microbatches (matching autodiff of the
        # model's batch-mean loss): divide the per-mb accumulation by M
        ghead = jax.tree.map(
            lambda a: a / M, all_reduce(all_reduce(ghead, "pipe"), dp, op="mean"))
        gstage = jax.tree.map(lambda a: all_reduce(a, dp, op="mean") / M, gstage)
        gx_all = all_reduce(gx_all, "pipe") / (n_dp * M)
        gstage_out = jax.tree.map(lambda a: a[None], gstage)  # [1, K, ...]
        trace = tuple(tr[None, :] for tr in trace)  # [1, ticks] per stage
        return loss, gstage_out, ghead, gx_all, trace

    sm = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(stage_P, head_P, P(None, dp), P(None, dp), P()),
        out_specs=(
            P(),
            stage_P,
            head_P,
            P(None, dp),
            (P("pipe"), P("pipe"), P("pipe"), P("pipe")),
        ),
        check_vma=False,
    )
    return sm(stage_params, head_params, x_mb, labels_mb, jnp.asarray(loss_scale, jnp.float32))


class PipelineEngine(DeepSpeedEngine):
    """Engine for pipelined models (reference PipelineEngine,
    runtime/pipe/engine.py:36).

    ``gradient_accumulation_steps`` from the config becomes the number of
    in-flight microbatches streamed through the pipeline (the reference's
    identical reinterpretation: pipe/engine.py:83 micro_batches =
    gradient_accumulation_steps); the base engine's sequential accumulation
    loop is disabled (gas=1) since accumulation happens inside the pipeline.
    """

    def __init__(self, model, config, **kwargs):
        required = ("num_micro_batches", "num_stages", "layers_per_stage")
        missing = [a for a in required if not hasattr(model, a)]
        if missing:
            raise TypeError(
                "PipelineEngine requires a pipelined model "
                f"(pipe.module.PipelinedTransformer or equivalent with {required}); "
                f"missing attributes: {missing}"
            )
        raw = config if isinstance(config, dict) else getattr(config, "raw", {})
        self._pipe_schedule = (
            (raw.get("pipeline", {}) or {}).get("schedule", "gpipe").lower()
        )
        if self._pipe_schedule not in ("gpipe", "1f1b"):
            raise ValueError(f"pipeline.schedule must be gpipe|1f1b, got {self._pipe_schedule}")
        if (self._pipe_schedule == "1f1b"
                and getattr(getattr(model, "config", None), "moe_every", 0) > 0):
            raise NotImplementedError(
                "MoE under the executed 1F1B schedule is not wired up (the "
                "clocked program has no aux-loss channel); use "
                "pipeline.schedule='gpipe' for PPxEP")
        super().__init__(model=model, config=config, **kwargs)
        # Config gas IS the microbatch count (reference pipe/engine.py:83).
        # A model left at the default adopts it; an explicit conflicting value
        # is an error rather than a silent override.
        gas = self.gradient_accumulation_steps
        if model.num_micro_batches in (1, gas):
            model.num_micro_batches = gas
        elif gas == 1:
            # config left gas at its default: adopt the model's microbatch
            # count (the reference treats gas as the sole source but never
            # errors when only the module specifies it)
            pass
        else:
            raise ValueError(
                f"gradient_accumulation_steps={gas} in the config conflicts with "
                f"num_micro_batches={model.num_micro_batches} on the model; set one of them"
            )
        self.micro_batches = model.num_micro_batches
        self.num_stages = model.num_stages
        pipe_axis = self.mesh.shape.get("pipe", 1)
        if pipe_axis != self.num_stages:
            raise ValueError(
                f"mesh 'pipe' axis is {pipe_axis} but the model has "
                f"{self.num_stages} stages; build the mesh with "
                f"MeshConfig(pipe={self.num_stages}, ...) or stages execute replicated"
            )
        # accumulation happens inside the pipeline scan
        self.gradient_accumulation_steps = 1
        # 1F1B/GPipe bubble accounting for the step anatomy: the clocked
        # schedule runs M + S - 1 ticks of which S - 1 are fill/drain
        # (pipeline_apply docstring) — published as a gauge and attached to
        # the train-step anatomy rows (telemetry/collective_ledger.py)
        from ..telemetry.collective_ledger import pipeline_bubble_fraction

        self.telemetry.ledger.set_pipeline(
            self.num_stages, self.micro_batches, self._pipe_schedule)
        self.telemetry.registry.gauge("train/pipe/bubble_fraction").set(
            pipeline_bubble_fraction(self.num_stages, self.micro_batches))
        log_dist(
            f"pipeline engine: {self.num_stages} stages × "
            f"{model.layers_per_stage} layers, {self.micro_batches} microbatches",
            ranks=[0],
        )

    def _make_micro_grad(self, compute_dtype):
        """Under pipeline.schedule='1f1b' the gradients come from the executed
        1F1B program (pipeline_train_1f1b) instead of autodiff-of-scan: embed
        runs outside with its own VJP, stage grads flow through the clocked
        schedule, and the head/embedding cotangents are stitched back in."""
        if self._pipe_schedule != "1f1b":
            return super()._make_micro_grad(compute_dtype)

        from ..models import transformer as tfm

        model = self.model
        cfg = model.config
        mesh = self.mesh
        S = self.num_stages
        M = self.micro_batches

        def micro_grad(params, batch, loss_scale, rng=None, step=None):
            # dropout/PLD are rejected at PipelinedTransformer construction
            cast = jax.tree.map(
                lambda p: p.astype(compute_dtype) if p.dtype == jnp.float32 else p, params
            )
            p_stages = cast["layers"]
            p_rest = {k: v for k, v in cast.items() if k != "layers"}
            inputs, labels = tfm.split_batch(batch)
            B, Sq = inputs.shape
            mb = B // M
            n_dp = mesh.shape.get("data", 1) * mesh.shape.get("fsdp", 1)
            if mb % n_dp:
                raise ValueError(
                    f"1f1b: microbatch size {mb} (batch {B} / {M} microbatches) "
                    f"must be divisible by the dp axes product {n_dp}"
                )
            # stage_fn runs INSIDE the executor's shard_map, where the batch
            # dim is the per-dp-shard slice (all rows share the same arange)
            positions = jnp.broadcast_to(jnp.arange(Sq)[None, :], (mb // n_dp, Sq))
            attend = tfm._stateless_attention(cfg, Sq)
            # every mesh axis is manual inside the executor's shard_map: the saved
            # boundary cannot be given a sharding there (partition_activations)
            wrap = tfm._remat_wrapper(cfg.replace(remat_partition_axis=""))

            def embed_fn(p_rest):
                x, _ = tfm.embed(cfg, p_rest, inputs)
                return x.reshape((M, mb) + x.shape[1:])

            x_mb, pull_embed = jax.vjp(embed_fn, p_rest)
            labels_mb = labels.reshape((M, mb, Sq))

            def stage_fn(sp, h):  # MoE is refused for this schedule at construction
                return tfm._layer_loop(
                    cfg, sp, None, h, None, positions=positions, attend=attend, wrap=wrap)[0]

            def loss_head(hp, y, labels_b):
                return tfm.lm_loss_from_hidden(cfg, hp, tfm._final_norm(cfg, hp, y), labels_b)

            loss, g_stage, g_head, gx, _trace = pipeline_train_1f1b(
                stage_fn, loss_head, p_stages, p_rest, x_mb, labels_mb,
                loss_scale, S, mesh,
            )
            (g_embed,) = pull_embed(gx.astype(x_mb.dtype))
            g_rest = jax.tree.map(lambda a, b: a + b, g_head, g_embed)
            grads = dict(g_rest)
            grads["layers"] = g_stage
            return loss, grads

        return micro_grad

    def train_batch(self, batch=None, data_iter=None):
        """Reference signature accepts an iterator (pipe/engine.py:294)."""
        if batch is None:
            assert data_iter is not None, "train_batch needs a batch or data_iter"
            batch = next(data_iter)
        return super().train_batch(batch)

    def eval_batch(self, batch=None, data_iter=None):
        if batch is None:
            assert data_iter is not None, "eval_batch needs a batch or data_iter"
            batch = next(data_iter)
        return super().eval_batch(batch)
