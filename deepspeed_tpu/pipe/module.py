"""Pipeline modules: layer partitioning + the stage-stacked transformer.

Reference: ``runtime/pipe/module.py`` — ``LayerSpec`` (:23),
``TiedLayerSpec`` (:71), ``PipelineModule`` (:85), layer partitioning
``_partition_layers`` (:361, uniform / parameters / type-regex).

TPU-native design: a pipeline stage is NOT a rank running different code —
it is one slice of a stage-stacked parameter pytree sharded over the mesh's
``pipe`` axis. All stages execute the same compiled stage function (vmapped
over the stage axis, so GSPMD places stage i's compute on pipe-rank i), and
activations move between stages as a roll over the stage axis, which XLA
lowers to a `CollectivePermute` over ICI — the compiled analogue of the
reference's p2p send/recv (runtime/pipe/p2p.py:48/:69).

Tied layers (reference TiedLayerSpec + tied-weight allreduce,
pipe/module.py:417) need no special machinery here: tied weights (e.g. the
embedding used in stage 0 and the LM head) live OUTSIDE the pipelined stack as
ordinary replicated-over-pipe params, and XLA sums their gradient
contributions automatically.
"""

from __future__ import annotations

import re
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from ..models import transformer as tfm
from ..models.transformer import Model, TransformerConfig


# ---------------------------------------------------------------------------
# Balanced partitioning (reference: _partition_layers module.py:361 +
# deepspeed/runtime/utils partition_balanced)
# ---------------------------------------------------------------------------

def partition_uniform(num_items: int, num_parts: int) -> list[int]:
    """Boundaries [p0..p_num_parts]; part i = [b[i], b[i+1])."""
    base = num_items // num_parts
    rem = num_items % num_parts
    bounds = [0]
    for i in range(num_parts):
        bounds.append(bounds[-1] + base + (1 if i < rem else 0))
    return bounds

def partition_balanced(weights: Sequence[float], num_parts: int) -> list[int]:
    """Contiguous partition minimizing the max part weight (binary search over
    the bottleneck + greedy feasibility check)."""
    n = len(weights)
    assert n >= num_parts, f"cannot split {n} items into {num_parts} parts"
    prefix = [0.0]
    for w in weights:
        prefix.append(prefix[-1] + w)

    def feasible(cap: float) -> Optional[list[int]]:
        bounds = [0]
        start = 0
        for _ in range(num_parts):
            # furthest end with sum(start:end) <= cap, at least one item,
            # leaving enough items for the remaining parts
            end = start + 1
            while end < n and prefix[end + 1] - prefix[start] <= cap:
                end += 1
            remaining_parts = num_parts - len(bounds)
            end = min(end, n - remaining_parts)
            if prefix[end] - prefix[start] > cap:
                return None
            bounds.append(end)
            start = end
        return bounds if bounds[-1] == n else None

    lo = max(weights) if weights else 0.0
    hi = prefix[-1]
    best = feasible(hi)
    for _ in range(50):
        mid = (lo + hi) / 2
        b = feasible(mid)
        if b is not None:
            best, hi = b, mid
        else:
            lo = mid
    assert best is not None
    return best


# ---------------------------------------------------------------------------
# LayerSpec machinery (generic models)
# ---------------------------------------------------------------------------

class LayerSpec:
    """Deferred layer: builder called lazily so a stage only materializes its
    own layers (the reference's motivation, module.py:23-55)."""

    def __init__(self, typename: Callable, *args, **kwargs):
        self.typename = typename
        self.args = args
        self.kwargs = kwargs

    def build(self):
        return self.typename(*self.args, **self.kwargs)

    def __repr__(self):
        return f"LayerSpec({getattr(self.typename, '__name__', self.typename)})"


class TiedLayerSpec(LayerSpec):
    """Layer whose parameters are shared with every other layer of the same
    ``key`` (reference module.py:71). Under pjit, tying = the layers index the
    same entry of a shared-params dict; gradient summation is automatic."""

    def __init__(self, key: str, typename: Callable, *args, forward_fn=None, **kwargs):
        super().__init__(typename, *args, **kwargs)
        self.key = key
        self.forward_fn = forward_fn


class PipelineModule:
    """Container that partitions a layer list into ``num_stages`` contiguous
    stages (reference PipelineModule, module.py:85).

    Layers are functional: each built layer must expose
    ``init(rng) -> params`` and ``__call__(params, x) -> x``; tied layers
    share one params entry keyed by ``TiedLayerSpec.key``.
    """

    def __init__(
        self,
        layers: Sequence,
        num_stages: int,
        partition_method: str = "parameters",
        loss_fn: Optional[Callable] = None,
    ):
        self.specs = [l if isinstance(l, LayerSpec) else LayerSpec(lambda f=l: f) for l in layers]
        self.num_stages = num_stages
        self.partition_method = partition_method
        self.loss_fn = loss_fn
        self.built = [s.build() for s in self.specs]
        self.parts = self._partition_layers(partition_method)

    # -- partitioning -------------------------------------------------------
    def _layer_weight(self, layer, method: str) -> float:
        if method == "uniform":
            return 1.0
        if method == "parameters":
            try:
                shapes = jax.eval_shape(layer.init, jax.random.PRNGKey(0))
                return float(sum(int(jnp.prod(jnp.asarray(s.shape))) for s in jax.tree.leaves(shapes))) or 1.0
            # dstpu: allow[broad-except] -- partition weighting is a load-balance heuristic: eval_shape over arbitrary user layer inits can raise anything, and degrading to uniform weights only costs balance, never correctness
            except Exception:
                return 1.0
        raise ValueError(method)

    def _partition_layers(self, method: str) -> list[int]:
        m = method.lower()
        if m == "uniform":
            return partition_uniform(len(self.built), self.num_stages)
        if m == "parameters":
            w = [self._layer_weight(l, "parameters") for l in self.built]
            return partition_balanced(w, self.num_stages)
        if m.startswith("type:"):
            regex = m.split(":", 1)[1]
            w = [
                1.0 if re.search(regex, type(l).__name__, re.IGNORECASE) else 0.0
                for l in self.built
            ]
            if sum(w) == 0:
                raise ValueError(f"partition regex {regex!r} matched no layers")
            return partition_balanced(w, self.num_stages)
        raise ValueError(f"unknown partition_method {method!r}")

    def stage_layers(self, stage_id: int) -> list:
        return self.built[self.parts[stage_id] : self.parts[stage_id + 1]]

    # -- functional API -----------------------------------------------------
    def init(self, rng) -> dict:
        params: dict[str, Any] = {"layers": [], "tied": {}}
        keys = jax.random.split(rng, len(self.built))
        for spec, layer, k in zip(self.specs, self.built, keys):
            if isinstance(spec, TiedLayerSpec):
                if spec.key not in params["tied"]:
                    params["tied"][spec.key] = layer.init(k)
                params["layers"].append(None)
            else:
                params["layers"].append(layer.init(k))
        return params

    def apply(self, params: dict, x):
        """Sequential reference execution (used for numerics tests; the
        compiled pipeline path is PipelinedTransformer / pipe.engine)."""
        for spec, layer, p in zip(self.specs, self.built, params["layers"]):
            if isinstance(spec, TiedLayerSpec):
                tied_p = params["tied"][spec.key]
                fwd = spec.forward_fn or layer
                x = fwd(tied_p, x)
            else:
                x = layer(p, x)
        return x


# ---------------------------------------------------------------------------
# Stage-stacked pipelined transformer (the compiled PP path)
# ---------------------------------------------------------------------------

class PipelinedTransformer(Model):
    """Flagship transformer with its layer stack pipelined over the ``pipe``
    mesh axis.

    The base model stores layers as one stacked pytree [L, ...] scanned by
    ``lax.scan`` (models/transformer.py). Here the stack is reshaped to
    [S, L/S, ...]; axis 0 ('stage') shards over the mesh 'pipe' axis, and the
    loss runs the microbatch-streamed pipeline (see ``pipeline_apply`` in
    pipe/engine.py). ``num_micro_batches`` plays the role of gradient
    accumulation steps — the reference's ``train_batch`` semantics
    (runtime/pipe/engine.py:294: one call = micro_batches × micro_bs × dp).
    """

    def __init__(self, cfg: TransformerConfig, num_stages: int, num_micro_batches: int = 1):
        assert cfg.num_layers % num_stages == 0, (
            f"num_layers={cfg.num_layers} must divide evenly into {num_stages} stages"
        )
        if cfg.hidden_dropout > 0 or cfg.attn_dropout > 0 or cfg.pld_enabled:
            raise NotImplementedError(
                "dropout/progressive-layer-drop under pipeline parallelism is "
                "not wired up (per-stage rng routing); disable them"
            )
        if cfg.local_attn_layers is not None:
            raise NotImplementedError(
                "local-attention layers under pipeline parallelism are not wired up: a stage "
                "sees its own layer indices, not the model's")
        if cfg.moe_first_dense:
            raise NotImplementedError(
                "moe_first_dense under pipeline parallelism is not wired up: the leading dense "
                "layers' stacks (params['dense_ffn']) belong to the first stage alone, and "
                "every stage is handed an equal slice of 'layers' and 'moe'")
        tfm.refuse_in_pipeline(cfg)  # both schedules run this model's stages
        super().__init__(cfg, loss_fn=None)
        self.num_stages = num_stages
        self.num_micro_batches = num_micro_batches
        self.layers_per_stage = cfg.num_layers // num_stages
        # MoE under PP (PP x EP composition — reference topology claims
        # runtime/pipe/topology.py:243): every stage must hold a whole number
        # of (moe_every)-layer groups so the expert stacks split evenly into
        # a [S, n_moe/S, ...] stage axis.
        if cfg.moe_every > 0 and self.layers_per_stage % cfg.moe_every != 0:
            raise ValueError(
                f"MoE+PP needs layers_per_stage ({self.layers_per_stage}) "
                f"divisible by moe_every ({cfg.moe_every})")

    # -- params: reshape [L, ...] -> [S, L/S, ...] --------------------------
    def init(self, rng):
        flat = tfm.init(self.config, rng)
        S, K = self.num_stages, self.layers_per_stage
        flat["layers"] = jax.tree.map(
            lambda a: a.reshape((S, K) + a.shape[1:]), flat["layers"]
        )
        if "moe" in flat:
            flat["moe"] = jax.tree.map(
                lambda a: a.reshape((S, a.shape[0] // S) + a.shape[1:]), flat["moe"]
            )
        return flat

    def logical_axes(self):
        axes = tfm.logical_axes(self.config)
        axes["layers"] = jax.tree.map(
            lambda ax: ("stage",) + ax,
            axes["layers"],
            is_leaf=lambda x: isinstance(x, tuple),
        )
        if "moe" in axes:
            axes["moe"] = jax.tree.map(
                lambda ax: ("stage",) + ax,
                axes["moe"],
                is_leaf=lambda x: isinstance(x, tuple),
            )
        return axes

    # -- compiled pipeline loss --------------------------------------------
    def loss(self, params, batch):
        from .engine import pipeline_apply

        cfg = self.config
        inputs, labels = tfm.split_batch(batch)
        B, Sq = inputs.shape
        M = self.num_micro_batches
        assert B % M == 0, f"batch {B} not divisible by {M} microbatches"
        x, full_positions = tfm.embed(cfg, params, inputs)
        positions = full_positions[: B // M]  # identical rows; per-microbatch view
        attend = tfm._stateless_attention(cfg, Sq)
        wrap = tfm._remat_wrapper(cfg)

        def stage_fn(stage_params, h):
            # the aux (load-balancing) losses of a stage's routed layers (zero for a dense
            # model) stream back through pipeline_apply's validity-gated side channel
            layers, moe = stage_params
            h, _, aux, _, _ = tfm._layer_loop(
                cfg, layers, moe, h, None, positions=positions, attend=attend, wrap=wrap)
            return h, aux

        x_mb = x.reshape((M, B // M) + x.shape[1:])  # [M, mb, Sq, d]
        out_mb, aux = pipeline_apply(
            stage_fn, (params["layers"], params.get("moe")), x_mb, self.num_stages, self.mesh,
            collect_aux=True)
        hidden = out_mb.reshape((B,) + out_mb.shape[2:])
        nll = tfm.lm_loss_from_hidden(cfg, params, tfm._final_norm(cfg, params, hidden), labels)
        # aux accumulated once per microbatch per group: average over M to
        # match the base model's per-batch group sum
        return nll + cfg.moe_aux_coeff * aux / M
