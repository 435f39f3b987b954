"""Generative inference engine.

Reference: ``deepspeed/inference/engine.py`` — ``InferenceEngine`` (:28):
builds a TP group (:168), applies the injection policy (:319), converts
dtypes, optionally captures CUDA graphs (:474), and serves ``forward``
(:503) over fused kernels with an incremental KV cache.

TPU-native design:
  * TP group            -> the mesh's ``model`` axis; weights are device_put
                           with the sharding rules in parallel/sharding.py
                           and XLA inserts the row-parallel all-reduces the
                           reference codes as LinearAllreduce.
  * kernel injection    -> module_inject.replace_module converts the HF
                           checkpoint into the compiled transformer family.
  * CUDA graphs         -> jit: prefill and decode are each ONE XLA program
                           (the generate loop is lax.scan'd inside jit, so a
                           whole generation is a single device call).
  * KV cache            -> static [L, B, Smax, H, Dh] arrays, donated between
                           steps (models/transformer.apply_with_cache).
  * module.half()       -> the engine holds every leaf in the dtype the
                           forward pass reads it in, cast once when it is
                           built (models/transformer.hold_for_compute): with
                           dtype bf16 the matrices, embeddings and
                           activation-dtype biases are bf16; norm scales and
                           biases, lm_head_bias and a router's gate stay
                           float32. The same rule whether the weights are
                           drawn here, passed as ``params`` or converted from
                           an HF checkpoint; ``engine.params`` (and so
                           ``SlotWorker.hbm_pools()["params"]``) is that tree,
                           and no program casts a weight.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..comm.mesh import MeshConfig, build_mesh
from ..models import transformer as tfm
from ..models.transformer import Model, TransformerConfig
from ..parallel import sharding as shd
from ..telemetry import tracing
from ..utils.logging import log_dist


class InferenceEngine:
    def __init__(
        self,
        model=None,
        config: dict | None = None,
        mesh: Optional[Mesh] = None,
        params=None,
        hf_model=None,
        hf_config=None,
        state_dict=None,
    ):
        config = dict(config or {})
        tp = config.get("tensor_parallel", {})
        tp_size = tp.get("tp_size", config.get("mp_size", 1))
        dtype = config.get("dtype", jnp.bfloat16)
        if isinstance(dtype, str):
            table = {
                "fp16": jnp.bfloat16,  # fp16 maps to bf16 on TPU
                "half": jnp.bfloat16,
                "bf16": jnp.bfloat16,
                "bfloat16": jnp.bfloat16,
                "fp32": jnp.float32,
                "float32": jnp.float32,
            }
            if dtype not in table:
                raise ValueError(f"unsupported dtype {dtype!r}; one of {sorted(table)}")
            if dtype in ("fp16", "half"):
                log_dist(
                    "inference dtype fp16 requested: TPU has no fp16 matmul path, "
                    "using bfloat16 (same memory, wider exponent)",
                    ranks=[0],
                )
            dtype = table[dtype]

        if hf_model is not None or state_dict is not None:
            from ..module_inject import replace_module

            model, converted = replace_module(
                hf_model=hf_model, hf_config=hf_config, state_dict=state_dict, dtype=dtype
            )
            params = params if params is not None else converted
        assert model is not None, "InferenceEngine needs a model or an HF checkpoint"
        if model.config.dtype != dtype:
            model = Model(model.config.replace(dtype=dtype), loss_fn=model._loss)

        # the build's phases are kept spans (telemetry/tracing.py): under
        # ``startup/build`` where ``build_serving_engine`` opened it
        self.model = model
        self.cfg: TransformerConfig = model.config
        with tracing.span("mesh", keep=True):
            self.mesh = mesh or build_mesh(MeshConfig(data=-1, model=tp_size))
            model.set_mesh(self.mesh)
        self.dtype = dtype
        self.max_out_tokens = config.get("max_out_tokens", self.cfg.max_seq_len)

        # --- parameters onto the mesh (TP slicing = sharding specs) --------
        with tracing.span("shapes", keep=True):
            axes_tree = model.logical_axes()
            shapes = jax.eval_shape(lambda r: model.init(r), jax.random.PRNGKey(0))
            shape_tree = jax.tree.map(lambda s: s.shape, shapes)
            self.param_specs = shd.make_param_specs(
                axes_tree, shape_tree, shd.DEFAULT_TP_RULES, self.mesh
            )
            shardings = shd.tree_shardings(self.mesh, self.param_specs)
        # One rule for what the engine holds, however the weights arrive
        # (tfm.hold_for_compute: the reference's module.half(), but for the
        # leaves the forward pass reads in float32). No program casts a weight.
        if params is None:
            # drawn and rounded in one program: the float32 tree is never resident
            with tracing.span("draw", keep=True):
                params = jax.jit(lambda r: tfm.hold_for_compute(self.cfg, model.init(r)),
                                 out_shardings=shardings)(jax.random.PRNGKey(0))
        else:
            with tracing.span("load", keep=True):
                params = jax.device_put(
                    tfm.hold_for_compute(self.cfg, jax.tree.map(np.asarray, params)), shardings)
        self.params = params

        # --- weight-only int8/int4 quantization (reference: MoQ injection +
        # int8 inference kernels, pt_binding int8 variants). Weights stay
        # quantized in HBM; each scanned layer dequantizes its own slice.
        qcfg = config.get("quantize", config.get("quant", {}))
        if isinstance(qcfg, dict) and qcfg.get("enabled"):
            bits = int(qcfg.get("bits", 8))
            group_size = int(qcfg.get("group_size", 64))
            if tp_size > 1:
                raise NotImplementedError(
                    "weight-only quantization with tensor parallelism is not "
                    "supported yet; use tp_size=1"
                )
            self.cfg = self.cfg.replace(weight_bits=bits, weight_group_size=group_size)
            with tracing.span("quantize", keep=True):
                self.params = jax.jit(
                    partial(tfm.quantize_weights, self.cfg, bits=bits, group_size=group_size)
                )(self.params)
            self.model = Model(self.cfg, loss_fn=self.model._loss)
            self.model.set_mesh(self.mesh)
            log_dist(f"weight-only quantization: int{bits}, group {group_size}", ranks=[0])

        self._fwd = None
        self._generate = {}
        n_params = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
        held: dict[str, int] = {}
        for leaf in jax.tree.leaves(self.params):
            held[leaf.dtype.name] = held.get(leaf.dtype.name, 0) + int(leaf.nbytes)
        log_dist(
            f"inference engine: {n_params/1e6:.1f}M params, tp={tp_size}, "
            f"mesh={dict(self.mesh.shape)}, dtype={jnp.dtype(dtype).name}, held "
            + ", ".join(f"{n / 1e9:.3f} GB {d}" for d, n in sorted(held.items())),
            ranks=[0],
        )

    # ------------------------------------------------------------------
    def forward(self, tokens) -> jax.Array:
        """Full (non-incremental) forward: tokens [B, S] -> logits [B, S, V]."""
        if self._fwd is None:
            self._fwd = jax.jit(lambda p, t: self.model.apply(p, t))
        return self._fwd(self.params, jnp.asarray(tokens))

    __call__ = forward

    # ------------------------------------------------------------------
    def _cache_spec(self):
        # [L, B, Smax, H, Dh]: batch over data axes, heads over model axis
        return PartitionSpec(None, ("data", "fsdp"), None, "model", None)

    def _build_generate(self, B: int, prompt_len: int, max_new: int, sampler_static: tuple):
        from .sampling import SamplerConfig, sample_logits, update_seen

        cfg = self.cfg
        mesh = self.mesh
        # cache rounded up to a 128 multiple: the Pallas decode kernel streams
        # it in power-of-two blocks; positions past the live prefix are masked
        Smax = -(-(prompt_len + max_new) // 128) * 128
        cache_sharding = NamedSharding(mesh, self._cache_spec())
        top_k, top_p, rep_penalty = sampler_static

        use_seen = rep_penalty != 1.0  # skip the [B, V] history carry otherwise

        def gen(params, prompt, rng, temperature):
            scfg = SamplerConfig(
                temperature=temperature, top_k=top_k, top_p=top_p,
                repetition_penalty=rep_penalty,
            )
            cache = tfm.init_cache(cfg, B, Smax, dtype=cfg.dtype)
            state = cache.pop(tfm.STATE, None)  # per-sequence leaves have no head axis to pin
            cache = jax.tree.map(
                lambda x: jax.lax.with_sharding_constraint(x, cache_sharding), cache
            )
            if state is not None:
                cache[tfm.STATE] = state
            seen0 = (
                update_seen(jnp.zeros((B, cfg.vocab_size), jnp.bool_), prompt)
                if use_seen
                else jnp.zeros((B, 1), jnp.bool_)  # dummy carry
            )
            logits, cache = tfm.apply_with_cache(cfg, params, prompt, cache, 0, last_only=True)
            rng, k0 = jax.random.split(rng)
            tok = sample_logits(logits[:, -1], k0, scfg, seen=seen0 if use_seen else None)
            seen = update_seen(seen0, tok[:, None]) if use_seen else seen0

            def step(carry, _):
                tok, cache, pos, rng, seen = carry
                logits, cache = tfm.apply_with_cache(cfg, params, tok[:, None], cache, pos)
                rng, k = jax.random.split(rng)
                nxt = sample_logits(logits[:, 0], k, scfg, seen=seen if use_seen else None)
                if use_seen:
                    seen = update_seen(seen, nxt[:, None])
                return (nxt, cache, pos + 1, rng, seen), tok

            (last, _, _, _, _), toks = jax.lax.scan(
                step, (tok, cache, prompt_len, rng, seen), None, length=max_new - 1
            )
            # toks = tokens emitted before each step; append the final one
            return jnp.concatenate([toks.T, last[:, None]], axis=1)  # [B, max_new]

        return jax.jit(gen)

    def generate(
        self,
        prompt_tokens,
        max_new_tokens: int = 32,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 1.0,
        repetition_penalty: float = 1.0,
        rng: Optional[jax.Array] = None,
    ) -> np.ndarray:
        """prompt [B, S] int32 -> generated [B, max_new_tokens] int32.

        Sampling: temperature (<=0 greedy), top-k, top-p (nucleus), and
        repetition penalty (CTRL-style over prompt + generated history). The
        whole loop (prefill + scan'd decode with the Pallas decode-attention
        kernel) is one compiled program per (B, prompt_len, max_new_tokens)
        bucket."""
        if self.cfg.attn_block_length > 1:
            raise NotImplementedError(
                "generate() decodes one token a step under the causal mask; a model that "
                f"generates by diffusion over blocks (attn_block_length = "
                f"{self.cfg.attn_block_length}) is served by ServingEngine (its block step)")
        prompt = jnp.asarray(prompt_tokens, jnp.int32)
        B, S = prompt.shape
        budget = min(self.cfg.max_seq_len, self.max_out_tokens)
        if S + max_new_tokens > budget:
            raise ValueError(
                f"prompt ({S}) + max_new_tokens ({max_new_tokens}) exceeds the "
                f"sequence budget {budget} (min of model max_seq_len "
                f"{self.cfg.max_seq_len} and max_out_tokens {self.max_out_tokens})"
            )
        sampler_static = (int(top_k), float(top_p), float(repetition_penalty))
        key = (B, S, max_new_tokens, sampler_static)
        if key not in self._generate:
            self._generate[key] = self._build_generate(B, S, max_new_tokens, sampler_static)
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        out = self._generate[key](self.params, prompt, rng, jnp.float32(temperature))
        return np.asarray(jax.device_get(out))
