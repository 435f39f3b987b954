"""Autotuner — search over ZeRO stage / micro-batch / remat / kernel blocks.

Reference: ``deepspeed/autotuning/autotuner.py:26`` (Autotuner) +
``scheduler.py:27`` (ResourceManager) + tuner strategies. The reference
launches each candidate as a separate training job on the resource pool and
reads metrics files back. TPU-native inversion: a candidate is a COMPILED
train step in this process — XLA's AOT path gives compile-time memory
analysis for free (OOM candidates are pruned before running), the jit cache
makes repeated geometry cheap, and one process owns the chips, so the
resource-manager layer collapses into a sequential trial loop.

Strategies (reference tuner/{grid,random,model}_sort):
  * grid        — exhaustive over the space
  * random      — shuffled subset
  * model_based — rank by a cost model (the flops profiler's FLOPs estimate /
                  peak-bound step time) and try the most promising first

Usage:
    tuner = Autotuner(model_factory, base_config, batch_factory)
    best = tuner.tune(space={...}, max_trials=8)
    # best.config is a full DeepSpeed-style config dict

CLI: ``dstpu_bench --autotune`` (bin/dstpu_bench).
"""

from __future__ import annotations

import itertools
import json
import random as pyrandom
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import numpy as np

from ..utils.logging import log_dist, logger

DEFAULT_SPACE = {
    "zero_stage": [1, 2, 3],
    "micro_batch_divisor": [1, 2, 4],  # micro = train_batch / (dp * divisor)
    "remat_policy": ["none", "save_flash", "dots_and_flash"],
}


@dataclass
class Trial:
    overrides: dict
    tokens_per_sec: float = 0.0
    step_ms: float = 0.0
    status: str = "pending"  # ok | failed | pruned
    error: str = ""
    cost_rank: float = 0.0


@dataclass
class TuneResult:
    best: Optional[Trial]
    trials: list = field(default_factory=list)

    @property
    def config(self) -> Optional[dict]:
        return None if self.best is None else self.best.overrides

    def save(self, path: str):
        with open(path, "w") as f:
            json.dump(
                {
                    "best": None if self.best is None else self.best.__dict__,
                    "trials": [t.__dict__ for t in self.trials],
                },
                f,
                indent=1,
            )


class Autotuner:
    """``model_factory(overrides) -> model`` builds a fresh model per trial
    (remat/attention overrides are model-config-level);
    ``batch_factory() -> dict`` yields one synthetic global batch."""

    def __init__(
        self,
        model_factory: Callable[[dict], Any],
        base_config: dict,
        batch_factory: Callable[[], dict],
        steps: int = 5,
        warmup: int = 2,
        world_size: Optional[int] = None,
        hbm_gb: Optional[float] = None,
    ):
        """``world_size``/``hbm_gb``: supply both to keep the tuner from
        touching ``jax.devices()`` at all — REQUIRED when driving isolated
        subprocess trials on an accelerator: a parent that initializes the
        backend holds the chip, and ``ExperimentScheduler.run_trial`` then
        refuses to start the trial (utils/jax_env.require_chip_free)."""
        self.model_factory = model_factory
        self.base_config = dict(base_config)
        self.batch_factory = batch_factory
        self.steps = steps
        self.warmup = warmup
        self.world_size = world_size
        self.hbm_gb = hbm_gb

    # -- candidate enumeration ---------------------------------------------
    def _expand(self, space: dict) -> list[dict]:
        keys = list(space)
        out = []
        for combo in itertools.product(*(space[k] for k in keys)):
            out.append(dict(zip(keys, combo)))
        return out

    def _apply_overrides(self, overrides: dict) -> dict:
        cfg = json.loads(json.dumps(self.base_config))  # deep copy
        if "zero_stage" in overrides:
            cfg.setdefault("zero_optimization", {})["stage"] = overrides["zero_stage"]
        if "micro_batch_divisor" in overrides:
            train = cfg["train_batch_size"]
            dp = self._dp_size(cfg)
            micro = max(1, train // (dp * overrides["micro_batch_divisor"]))
            cfg["train_micro_batch_size_per_gpu"] = micro
            cfg["gradient_accumulation_steps"] = train // (micro * dp)
        if "micro_batch" in overrides:
            train = cfg["train_batch_size"]
            dp = self._dp_size(cfg)
            micro = overrides["micro_batch"]
            cfg["train_micro_batch_size_per_gpu"] = micro
            cfg["gradient_accumulation_steps"] = train // (micro * dp)
        return cfg

    def _dp_size(self, cfg) -> int:
        """data x fsdp product with any single -1 wildcard axis resolved the
        way MeshConfig.sizes does (remaining devices)."""
        mesh = cfg.get("mesh", {})
        n = self.world_size if self.world_size is not None else len(jax.devices())
        sizes = {k: mesh.get(k, -1 if k == "data" else 1)
                 for k in ("pipe", "data", "fsdp", "context", "model")}
        unknown = [k for k, v in sizes.items() if v == -1]
        fixed = int(np.prod([v for v in sizes.values() if v != -1]))
        if unknown:
            sizes[unknown[0]] = max(1, n // fixed)
        return sizes["data"] * sizes["fsdp"]

    # -- cost model (reference: model-based tuner; here the flops profiler
    # estimate ranks candidates before any compilation) ---------------------
    def _model_config_for(self, overrides: dict):
        """Model config for a candidate, cached — ranking should not build a
        throwaway model per candidate per sort key."""
        key = tuple(sorted((k, str(v)) for k, v in overrides.items()))
        if not hasattr(self, "_mc_cache"):
            self._mc_cache = {}
        if key not in self._mc_cache:
            self._mc_cache[key] = getattr(self.model_factory(overrides), "config", None)
        return self._mc_cache[key]

    def _device_mem_gb(self) -> float:
        if self.hbm_gb is not None:
            return self.hbm_gb
        stats = getattr(jax.local_devices()[0], "memory_stats", lambda: None)() or {}
        limit = stats.get("bytes_limit", 0)
        return limit / 1e9 if limit else 16.0  # v5e-class default

    def _estimate_mem_gb(self, overrides: dict) -> Optional[float]:
        """Rough HBM high-water estimate (activations + model/opt states) so
        the ranking never spends its trial budget compiling candidates that
        cannot fit — the first real sweep burned every trial on remat=none at
        full micro-batch (compile-time OOM)."""
        mc = self._model_config_for(overrides)
        if mc is None or not hasattr(mc, "num_layers"):
            return None
        cfg = self._apply_overrides(overrides)
        dp = self._dp_size(cfg)
        micro = cfg.get("train_micro_batch_size_per_gpu",
                        cfg["train_batch_size"] // dp)
        L, S, D = mc.num_layers, mc.max_seq_len, mc.hidden_size
        F = getattr(mc, "intermediate_size", None) or 4 * D
        policy = overrides.get("remat_policy",
                               mc.remat_policy if getattr(mc, "remat", False) else "none")
        # live activation tensors per layer, in units of the bf16 residual
        # stream [B, S, D]: none keeps every matmul output AND their incoming
        # gradients at the backward peak (hence the 2x — the chip sweep showed
        # remat=none OOMs exactly where the un-doubled estimate said it fit);
        # dots keeps matmul outs but recomputes elementwise; save_flash keeps
        # only the boundary + flash out/lse
        k = {"none": 2 * (10 + 2 * F / D), "dots_and_flash": 5 + 2 * F / D,
             "save_flash": 3.0}.get(policy, 3.0)
        act_gb = L * micro * S * D * 2 * k / 1e9
        n_params = L * (4 * D * D + 2 * D * F) + getattr(mc, "vocab_size", 0) * D
        stage = overrides.get("zero_stage", 1)
        opt_shard = max(1, dp) if stage >= 1 else 1
        par_shard = max(1, dp) if stage >= 3 else 1
        states_gb = n_params * (2 / par_shard + 16 / opt_shard) / 1e9
        return act_gb + states_gb

    def _cost_rank(self, overrides: dict) -> float:
        """Lower = more promising. Heuristics: less remat recompute and
        bigger micro-batches are faster; higher zero stages add collectives
        on multi-device meshes (free on one chip). Candidates whose memory
        estimate exceeds HBM sink to the back of the ranking."""
        rank = 0.0
        policy = overrides.get("remat_policy", "save_flash")
        rank += {"none": 0.0, "dots_and_flash": 0.5, "save_flash": 1.0}.get(policy, 1.5)
        rank += overrides.get("micro_batch_divisor", 1) * 0.25
        n_dev = self.world_size if self.world_size is not None else len(jax.devices())
        if n_dev > 1:
            rank += {1: 0.0, 2: 0.1, 3: 0.3, 0: 0.0}.get(overrides.get("zero_stage", 1), 0)
        try:
            est = self._estimate_mem_gb(overrides)
            hbm = self._device_mem_gb()
        # dstpu: allow[broad-except] -- the memory estimate only orders the trial queue: any estimator failure must degrade to 'unranked', never abort the tuning sweep it is trying to speed up
        except Exception:  # noqa: BLE001 — estimation must never kill tuning
            est = hbm = None
        if est is not None and est > hbm:
            logger.info(
                f"autotune: {overrides} estimated {est:.1f} GB > HBM "
                f"{hbm:.1f} GB; deprioritized")
            rank += 100.0 + est
        return rank

    # -- measurement --------------------------------------------------------
    def _measure(self, overrides: dict) -> Trial:
        import deepspeed_tpu

        trial = Trial(overrides=overrides)
        try:
            cfg = self._apply_overrides(overrides)
            model = self.model_factory(overrides)
            engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=cfg)
            batch = self.batch_factory()
            m = engine.train_batch(batch)  # compile
            np.asarray(jax.device_get(m["loss"]))
            for _ in range(self.warmup):
                m = engine.train_batch(batch)
            np.asarray(jax.device_get(m["loss"]))
            t0 = time.perf_counter()
            for _ in range(self.steps):
                m = engine.train_batch(batch)
            np.asarray(jax.device_get(m["loss"]))
            dt = (time.perf_counter() - t0) / self.steps
            leaf = next(iter(batch.values()))
            # causal-LM batches carry S+1 columns (inputs + shifted labels);
            # count the S positions actually trained
            seq = leaf.shape[1] - 1 if "tokens" in batch else leaf.shape[1]
            tokens = int(leaf.shape[0] * seq)
            trial.step_ms = dt * 1e3
            trial.tokens_per_sec = tokens / dt
            trial.status = "ok"
        # dstpu: allow[broad-except] -- a tuning trial exists to discover HOW a candidate config fails (OOM, compile error, shape mismatch, ...); every failure kind is the trial's RESULT, recorded with its type name
        except Exception as e:  # noqa: BLE001 — a failing candidate is data
            trial.status = "failed"
            trial.error = f"{type(e).__name__}: {str(e)[:300]}"
            logger.warning(f"autotune trial failed {overrides}: {trial.error}")
        return trial

    # -- main loop ----------------------------------------------------------
    def tune(
        self,
        space: Optional[dict] = None,
        strategy: str = "model_based",
        max_trials: int = 12,
        results_path: Optional[str] = None,
        seed: int = 0,
    ) -> TuneResult:
        space = space or DEFAULT_SPACE
        candidates = [(0.0, c) for c in self._expand(space)]
        if strategy == "random":
            pyrandom.Random(seed).shuffle(candidates)
        elif strategy == "model_based":
            candidates = sorted(
                ((self._cost_rank(c), c) for _, c in candidates), key=lambda rc: rc[0]
            )
        elif strategy != "grid":
            raise ValueError(f"unknown strategy {strategy!r} (grid|random|model_based)")
        candidates = candidates[:max_trials]

        result = TuneResult(best=None)
        for i, (rank, overrides) in enumerate(candidates):
            log_dist(f"autotune trial {i + 1}/{len(candidates)}: {overrides}", ranks=[0])
            trial = self._measure(overrides)
            trial.cost_rank = rank
            result.trials.append(trial)
            if trial.status == "ok" and (
                result.best is None or trial.tokens_per_sec > result.best.tokens_per_sec
            ):
                result.best = trial
        if result.best is not None:
            log_dist(
                f"autotune best: {result.best.overrides} -> "
                f"{result.best.tokens_per_sec:,.0f} tok/s ({result.best.step_ms:.0f} ms/step)",
                ranks=[0],
            )
        if results_path:
            result.save(results_path)
        return result

    # -- isolated (subprocess) experiments ---------------------------------
    def _spec_for(self, overrides: dict, model_cfg: dict, batch: dict) -> dict:
        mc = dict(model_cfg)
        policy = overrides.get("remat_policy")
        if policy is not None:
            if policy == "none":
                mc["remat"] = False
            else:
                mc["remat"] = True
                mc["remat_policy"] = policy
        for k, v in overrides.items():
            # 'model.loss_chunk_size': 256 → TransformerConfig override
            if k.startswith("model."):
                mc[k[len("model."):]] = v
        return {
            "model_cfg": mc,
            "ds_config": self._apply_overrides(overrides),
            "batch": dict(batch),
            "steps": self.steps,
            "warmup": self.warmup,
        }

    def _surrogate_sort(self, candidates: list[dict], observed: list[Trial]) -> list[dict]:
        """Model-based tuner (reference tuner/model_based_tuner.py:14): fit a
        regressor on measured trials and explore the best PREDICTED next.
        One-hot features + ridge least-squares replace the reference's
        XGBoost cost model — same shape, no dependency. Failed trials train
        the model at 0 tok/s, steering the search away from their region."""
        keys = sorted({k for t in observed for k in t.overrides} |
                      {k for c in candidates for k in c})
        vocab = {k: sorted({str(t.overrides.get(k)) for t in observed} |
                           {str(c.get(k)) for c in candidates}) for k in keys}

        def feat(ov):
            v = [1.0]
            for k in keys:
                for val in vocab[k]:
                    v.append(1.0 if str(ov.get(k)) == val else 0.0)
            return v

        X = np.array([feat(t.overrides) for t in observed])
        y = np.array([t.tokens_per_sec if t.status == "ok" else 0.0 for t in observed])
        lam = 1e-3
        A = X.T @ X + lam * np.eye(X.shape[1])
        w = np.linalg.solve(A, X.T @ y)
        scored = [(float(np.array(feat(c)) @ w), c) for c in candidates]
        return [c for _, c in sorted(scored, key=lambda sc: -sc[0])]

    def tune_isolated(
        self,
        model_cfg: dict,
        batch: dict,
        scheduler,
        space: Optional[dict] = None,
        strategy: str = "surrogate",
        max_trials: int = 12,
        results_path: Optional[str] = None,
        seed: int = 0,
    ) -> TuneResult:
        """Experiment-scheduler sweep: every trial is a fresh SUBPROCESS with
        a hard timeout (scheduler.ExperimentScheduler — the reference
        ResourceManager's job isolation), so an OOM/hang candidate is a
        recorded failure, not a dead tuner, and a restarted sweep resumes
        from the experiment log.

        ``model_cfg``: TransformerConfig kwargs (dtype as 'bfloat16'/'float32'
        string); ``batch``: {'size': B, 'seq': S, 'vocab': V}.
        ``strategy``: 'surrogate' bootstraps with the analytic cost model,
        then re-ranks remaining candidates after every observation with the
        fitted surrogate; 'model_based'/'grid'/'random' order once, up front.
        """
        space = space or DEFAULT_SPACE
        candidates = self._expand(space)
        if strategy == "random":
            pyrandom.Random(seed).shuffle(candidates)
        elif strategy in ("model_based", "surrogate"):
            candidates = [c for _, c in sorted(
                ((self._cost_rank(c), c) for c in candidates), key=lambda rc: rc[0])]
        elif strategy != "grid":
            raise ValueError(f"unknown strategy {strategy!r}")

        result = TuneResult(best=None)
        bootstrap = 3  # observations before the surrogate takes over
        while candidates and len(result.trials) < max_trials:
            ok_seen = [t for t in result.trials if t.status == "ok"]
            if strategy == "surrogate" and len(ok_seen) >= bootstrap:
                candidates = self._surrogate_sort(candidates, result.trials)
            overrides = candidates.pop(0)
            log_dist(
                f"autotune[isolated] trial {len(result.trials) + 1}/{max_trials}: "
                f"{overrides}", ranks=[0])
            rec = scheduler.run_trial(self._spec_for(overrides, model_cfg, batch))
            trial = Trial(
                overrides=overrides,
                tokens_per_sec=float(rec.get("tokens_per_sec", 0.0)),
                step_ms=float(rec.get("step_ms", 0.0)),
                status="ok" if rec.get("status") == "ok" else "failed",
            )
            if rec.get("status") != "ok":
                trial.error = f"[{rec.get('status')}] {rec.get('error', '')}"[:400]
            result.trials.append(trial)
            if trial.status == "ok" and (
                result.best is None
                or trial.tokens_per_sec > result.best.tokens_per_sec
            ):
                result.best = trial
        if result.best is not None:
            log_dist(
                f"autotune[isolated] best: {result.best.overrides} -> "
                f"{result.best.tokens_per_sec:,.0f} tok/s", ranks=[0])
        if results_path:
            result.save(results_path)
        return result
