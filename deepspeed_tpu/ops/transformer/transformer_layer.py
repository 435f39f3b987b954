"""Standalone fused transformer layers — the public ``ops.transformer`` API.

Reference surface:
- Training layer: ``DeepSpeedTransformerLayer`` + ``DeepSpeedTransformerConfig``
  (csrc/transformer/ds_transformer_cuda.cpp:1029 ``create_transformer_layer_*``
  / ``forward_fp16`` / ``backward_fp16``) — a fused BERT-style block (QKV gemm,
  softmax, dropout, gelu, layernorm) with a stochastic_transformer variant.
- Inference layer: ``DeepSpeedTransformerInference`` + ``DeepSpeedInferenceConfig``
  (ops/transformer/inference/transformer_inference.py:738) — fused decode block
  with incremental KV cache.

TPU-native: there are no per-layer stateful C++ objects or hand-scheduled
cuBLAS batches — a layer is (params pytree, pure apply fn) and the fusion the
reference hand-writes (bias+gelu, bias+dropout+residual, strided-batch gemms)
is what XLA emits for the jitted body; attention runs the Pallas flash kernel
when enabled. The *stochastic* variant maps to per-call dropout keys derived
from a step counter (the reference trades exact replay for speed; here replay
is controlled by whether the caller fixes the rng).

Both layers ARE the model family's block
(models/transformer.py:_block) so numerics, dropout semantics, and remat
behavior are identical to what the training engine compiles.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from ...models import transformer as mt


@dataclass
class DeepSpeedTransformerConfig:
    """Training-layer config (reference ds_transformer_cuda.cpp binding args;
    field spelling follows the reference Python-side config)."""

    batch_size: int = 1
    hidden_size: int = 768
    intermediate_size: Optional[int] = None
    heads: int = 12
    attn_dropout_ratio: float = 0.0
    hidden_dropout_ratio: float = 0.0
    num_hidden_layers: int = 1
    initializer_range: float = 0.02
    layer_norm_eps: float = 1e-12
    seed: int = 0
    fp16: bool = False
    pre_layer_norm: bool = True
    normalize_invertible: bool = False  # memory trick; XLA-managed (no-op)
    gelu_checkpoint: bool = False  # remat of gelu; folded into remat policy
    adjust_init_range: bool = True
    attn_dropout_checkpoint: bool = False  # XLA-managed (no-op)
    stochastic_mode: bool = False
    return_tuple: bool = False
    training: bool = True

    def _model_cfg(self) -> mt.TransformerConfig:
        return mt.TransformerConfig(
            vocab_size=1,  # layer-only: no embedding table used
            max_seq_len=1,
            num_layers=1,
            num_heads=self.heads,
            hidden_size=self.hidden_size,
            intermediate_size=self.intermediate_size,
            pos_emb="none",
            causal=False,
            norm_style="pre" if self.pre_layer_norm else "post",
            layernorm_epsilon=self.layer_norm_eps,
            activation="gelu",
            dtype=jnp.bfloat16 if self.fp16 else jnp.float32,
            hidden_dropout=self.hidden_dropout_ratio,
            attn_dropout=self.attn_dropout_ratio,
        )


class DeepSpeedTransformerLayer:
    """One fused transformer training layer: ``init(rng)`` -> params,
    ``apply(params, hidden_states, attention_mask=None, rng=None)``.

    ``attention_mask`` is additive, broadcastable to [B, H, S, S] (the
    reference takes the same additive mask its kernels add pre-softmax).
    Dropout is active when ``rng`` is passed (or in stochastic mode, where
    keys derive from an internal counter)."""

    def __init__(self, config: DeepSpeedTransformerConfig):
        self.config = config
        self._cfg = config._model_cfg()
        self._counter = 0

    def init(self, rng=None) -> dict:
        rng = rng if rng is not None else jax.random.PRNGKey(self.config.seed)
        full = mt.init(self._cfg, rng)
        # strip the scan's leading L=1 layer axis -> single-layer leaves
        return {k: v[0] for k, v in full["layers"].items()}

    def logical_axes(self) -> dict:
        axes = mt.logical_axes(self._cfg)["layers"]
        return {k: tuple(a for a in v[1:]) for k, v in axes.items()}

    def apply(self, params: dict, hidden_states, attention_mask=None, rng=None):
        cfg = self._cfg
        if rng is None and self.config.stochastic_mode and self.config.training:
            # stochastic mode: fresh dropout mask per call, no replay contract
            rng = jax.random.fold_in(jax.random.PRNGKey(self.config.seed), self._counter)
            self._counter += 1
        bias = None
        if attention_mask is not None:
            bias = jnp.asarray(attention_mask, jnp.float32)
            while bias.ndim < 4:
                bias = bias[:, None]
        (out, _), _ = mt._block(
            cfg, (hidden_states.astype(cfg.dtype), None), params, 0, positions=None, rng=rng,
            attend=lambda q, k, v, state, l, lp: (
                mt.xla_attention(q, k, v, bias=bias, causal=False), state),
            ffn=partial(mt._dense_ffn, cfg))
        return (out,) if self.config.return_tuple else out

    __call__ = apply


def DeepSpeedStochasticTransformerLayer(config: DeepSpeedTransformerConfig):
    """Stochastic variant (reference ``stochastic_transformer`` op): same
    layer with stochastic_mode forced on."""
    import dataclasses

    return DeepSpeedTransformerLayer(dataclasses.replace(config, stochastic_mode=True))


# ---------------------------------------------------------------------------
@dataclass
class DeepSpeedInferenceConfig:
    """Inference-layer config (reference transformer_inference.py:738 ctor
    args that matter on TPU; CUDA-graph/stream knobs have no analogue)."""

    hidden_size: int = 768
    intermediate_size: Optional[int] = None
    heads: int = 12
    layer_norm_eps: float = 1e-12
    pre_layer_norm: bool = True
    fp16: bool = False
    rotary_dim: int = 0  # >0: rotary positions applied to q/k
    triangular_masking: bool = True
    max_out_tokens: int = 1024  # KV-cache allocation length

    def _model_cfg(self) -> mt.TransformerConfig:
        return mt.TransformerConfig(
            vocab_size=1,
            max_seq_len=self.max_out_tokens,
            num_layers=1,
            num_heads=self.heads,
            hidden_size=self.hidden_size,
            intermediate_size=self.intermediate_size,
            pos_emb="rotary" if self.rotary_dim > 0 else "none",
            rotary_pct=(self.rotary_dim * self.heads / self.hidden_size
                        if self.rotary_dim > 0 else 1.0),
            causal=self.triangular_masking,
            norm_style="pre" if self.pre_layer_norm else "post",
            layernorm_epsilon=self.layer_norm_eps,
            dtype=jnp.bfloat16 if self.fp16 else jnp.float32,
            decode_attn="xla",
        )


class DeepSpeedTransformerInference:
    """Single fused inference layer with incremental KV cache.

    ``init_cache(batch)`` allocates [B, max_out_tokens, H, Dh] K/V;
    ``apply(params, hidden_states, cache, pos)`` consumes T new positions
    starting at ``pos`` and returns (out, updated_cache). Cache layout and
    attention math are the model family's (models/transformer.py:init_cache /
    cached_attention), i.e. what InferenceEngine compiles — the reference's
    ``softmax_context`` kernel role."""

    def __init__(self, config: DeepSpeedInferenceConfig):
        self.config = config
        self._cfg = config._model_cfg()

    def init(self, rng=None) -> dict:
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        full = mt.init(self._cfg, rng)
        return {k: v[0] for k, v in full["layers"].items()}

    def init_cache(self, batch: int, dtype=None) -> dict:
        c = mt.init_cache(self._cfg, batch, self.config.max_out_tokens, dtype)
        return {"k": c["k"][0], "v": c["v"][0]}

    def apply(self, params: dict, hidden_states, cache: dict, pos):
        cfg = self._cfg
        x = hidden_states.astype(cfg.dtype)
        B, T = x.shape[:2]
        # the model's cache attention over a one-layer stack
        positions, attend = mt._cache_attention(cfg, B, T, cache["k"].shape[1], pos)
        (x, stacks), _ = mt._block(
            cfg, (x, {"k": cache["k"][None], "v": cache["v"][None]}), params, 0,
            positions=positions, attend=attend, ffn=partial(mt._dense_ffn, cfg))
        return x, {"k": stacks["k"][0], "v": stacks["v"][0]}

    __call__ = apply
