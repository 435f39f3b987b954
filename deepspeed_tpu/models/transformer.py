"""Decoder-only transformer — the framework's flagship model family.

Covers GPT-2 (learned pos-emb), GPT-NeoX (rotary, parallel residual) and
BLOOM-style (alibi) decoders with one configurable implementation — the same
architectures the reference's inference policies target
(module_inject/replace_policy.py:129/:219/:381/:435).

TPU-first design choices:
  * functional: ``init(rng) -> params`` pytree + ``apply(params, tokens)``;
    no module objects, so the engine can shard/donate freely.
  * layer stack is a SINGLE stacked pytree scanned with ``lax.scan`` — one
    compiled layer body regardless of depth (XLA-friendly; contrast with the
    reference's per-layer C++ objects, csrc/transformer/ds_transformer_cuda.cpp).
  * every parameter carries logical axis names so parallel/sharding.py can map
    ZeRO/TP/EP placements onto it.
  * attention implementation is pluggable ("xla" einsum, "flash" Pallas,
    "ring" context-parallel) — see ops/ and parallel/ring_attention.py.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

Params = Any

# Mesh handle for MoE sharding constraints inside traced code (set by
# Model.set_mesh via the engine; [None] = no constraint, single-mesh apps only).
_ACTIVE_MESH: list = [None]


class _Stated(dict):
    """A mapping a configuration states (``multipliers``), hashable by its items
    so that the configuration stays what it was: a jit's static argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple)) else v)
                                 for k, v in self.items())))


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 50257
    max_seq_len: int = 1024
    num_layers: int = 12
    # The layer stack runs this many times over the SAME weights (a looped /
    # universal transformer: Ouro's ``total_ut_steps``), the final norm after EVERY
    # pass and its output the next pass's input. Through the cache every (pass,
    # layer) keeps K/V of its own, pass-major (``cache_layout``)
    layer_passes: int = 1
    # An exit gate (``layer_passes`` > 1): one [hidden_size] -> 1 linear map with a
    # bias, read on every pass's normed output; its sigmoids give a distribution over
    # the pass at which a token would stop (``exit_distribution``), handed out with
    # ``return_exit``. It decides nothing: every row runs every pass
    exit_gate: bool = False
    num_heads: int = 12
    hidden_size: int = 768
    intermediate_size: Optional[int] = None  # default 4*hidden
    pos_emb: str = "learned"  # learned | rotary | alibi | none
    rotary_pct: float = 1.0
    rotary_interleaved: bool = False  # GPT-J rotate-every-two convention
    parallel_residual: bool = False  # GPT-NeoX style
    causal: bool = True  # False = bidirectional (BERT-style encoders)
    # pre (GPT) | post (BERT) layernorm placement | sandwich: a norm BEFORE and a second
    # norm AFTER each sublayer, inside the residual branch (x += norm2(f(norm1(x))):
    # Ouro's), two more scale leaves a layer (``ln1_post`` / ``ln2_post``)
    norm_style: str = "pre"
    norm_kind: str = "layer"  # layer (scale + bias) | rms (RMSNorm: scale only)
    # RMSNorm with a learned scale on the queries and the keys, before the rotary.
    # True: ONE norm over the whole projection, heads and head width together
    # (OLMoE's q_norm / k_norm). "head": a norm of width ``head_dim`` on every head
    # after the head split, one [head_dim] scale shared by the heads (EXAONE 4's)
    qk_norm: Any = False
    # Grouped-query attention: this many key / value heads (0: num_heads), query
    # head i attending key / value head i // (num_heads // num_kv_heads); the
    # cache holds the key / value heads, never the repeated ones
    num_kv_heads: int = 0
    # Explicit head sizes: the width of a query / key head and of a value head
    # (0: hidden_size // num_heads for both). Plain attention takes ``qk_head_dim``
    # for both (num_heads x qk_head_dim need not be hidden_size); two different
    # widths and a rotary part are latent attention's.
    qk_head_dim: int = 0
    v_head_dim: int = 0
    # Latent attention (MLA, DeepSeek-V2 / V3; no query compression): the keys
    # and values of all heads are expanded from ONE normed latent of
    # ``kv_lora_rank`` values a token, and the last ``qk_rope_head_dim`` of each
    # q/k head are a rotary part whose key is shared by the heads. The cache
    # holds the latent and that key (``cache_layout``), never per-head K/V.
    kv_lora_rank: int = 0
    qk_rope_head_dim: int = 0
    rotary_base: float = 10000.0
    # A Mamba-2 state-space mixer beside the attention in EVERY layer, on the same
    # normed input, both added to the residual before a sequential feed-forward
    # (Falcon-H1; ``_ssm_mixer`` has the equations). ``ssm_state_size`` > 0 turns
    # it on: ``ssm_heads`` heads of ``ssm_head_dim`` channels, each with a state of
    # ``ssm_state_size``; B and C in ``ssm_groups`` groups of heads; a depthwise
    # causal convolution of ``ssm_conv_kernel`` taps; many rows go through the
    # chunked scan in chunks of ``ssm_chunk_size``. The slot cache holds, beside
    # K/V, a float32 state and the convolution's tail per SEQUENCE (``cache_layout``).
    ssm_state_size: int = 0
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 1
    ssm_conv_kernel: int = 4
    ssm_chunk_size: int = 128
    # Maximal-update multipliers, as configuration data: constants the forward
    # pass multiplies by at the places ``MULTIPLIERS`` lists (a place left out
    # is 1). ``init`` draws each matrix whose output one scales at 1 / (sqrt(fan
    # in) x the multiplier), so that a multiplier dropped from the program shows.
    multipliers: Optional[dict] = None
    # A layer's KIND, as data (``layer_kinds``). ``local_attn_layers``: per-layer
    # 0/1 flags, 1 = the layer attends inside a sliding window of
    # ``local_attn_window`` positions (key j visible to query i iff j <= i and
    # i - j < window), 0 = over the whole context; None = every layer whole.
    # GPT-Neo's alternating local layers and EXAONE's three sliding layers in
    # four are this one thing. ``rotary_layers`` (``pos_emb="rotary"`` only):
    # per-layer 0/1 flags, 0 = no rotary on that layer's q and k (EXAONE's full
    # layers carry no position at all); None = every layer. HOW a rotated layer
    # turns is its kind's to state (``rotary_by_kind`` below; ``rotary_base`` where
    # it states nothing). Through the cache a window layer keeps a RING of
    # ``local_attn_window`` positions a sequence and a whole layer ``Smax``
    # (``cache_layout``); a prompt may enter both in chunks (``_cache_attention``)
    local_attn_window: int = 0
    local_attn_layers: Optional[tuple] = None
    rotary_layers: Optional[tuple] = None
    # Generation by diffusion over blocks (SDAR): the mask is causal BETWEEN blocks of
    # ``attn_block_length`` positions, aligned from position 0, and every position of a
    # block sees its whole block: key j is visible to query i iff j // B <= i // B
    # (``block_visible``). 1 is the causal mask, and every program is what it was.
    # ``mask_token_id``: the id whose embedding stands at a position of a block that is
    # not revealed yet (which positions those are is the caller's to know: the id may
    # occur in a prompt). A block is denoised through the cache by ``apply_with_cache``
    # with T = ``attn_block_length`` rows a sequence at a per-row ``pos``; the serving
    # engine's block step drives it (inference/serving.py)
    attn_block_length: int = 1
    mask_token_id: int = -1
    # A layer kind's ROTARY, as data (``rotary_spec``): ``{"window": spec, "whole":
    # spec}``, the rotary of the layers that attend inside the window and of those
    # that attend over the whole context (a kind left out turns by ``rotary_base``).
    # A spec is plain, ``{"base": b}``: inv_freq_i = b^(-2i/d); or YaRN's blend (Peng
    # et al. 2023, as ``transformers`` computes it), ``{"type": "yarn", "base",
    # "factor", "original_max_position_embeddings", "beta_fast" (32), "beta_slow" (1),
    # "attention_factor" (0.1 ln(factor) + 1), "truncate" (True)}``: the dimensions
    # that turn more than ``beta_fast`` times over the original context keep their
    # frequency, those that turn less than ``beta_slow`` times are divided by
    # ``factor``, a linear ramp between, and cos and sin are multiplied by
    # ``attention_factor`` (``rotary_table`` has the equations). Mellum2's full layers
    # state YaRN and its window layers a plain base. ``rotary_layers``' 0 still means
    # no rotary at all. None: every rotated layer turns by ``rotary_base``, as ever
    rotary_by_kind: Optional[dict] = None
    # A layer's OPERATOR, the third part of its kind: per layer "attn" (the
    # attention sublayer above) or "conv", a gated short convolution in its place
    # (LFM2's; ``_short_conv`` has the equations): no q / k / v, no rotary, no
    # attention, a depthwise causal filter of ``conv_kernel`` taps whose last
    # ``conv_kernel - 1`` inputs are ALL a sequence keeps between steps. None:
    # every layer attends. The two operators' leaves differ, so the parameters
    # lie in stacks BY OPERATOR (``init``) and the cache keeps K/V for the
    # attention layers alone and the filter's tail for the others (``cache_layout``)
    # or "delta", a gated delta rule in its place (Qwen3-Next's; ``_gated_delta`` has
    # the equations): ``delta_key_heads`` key heads and ``delta_value_heads`` value
    # heads (a multiple of them) of ``delta_head_dim``, a filter of ``conv_kernel``
    # taps over its q | k | v, and a float32 MATRIX [head width, head width] a value
    # head that a sequence keeps between steps beside the filter's tail
    layer_operators: Optional[tuple] = None
    conv_kernel: int = 0
    delta_key_heads: int = 0
    delta_value_heads: int = 0
    delta_head_dim: int = 0
    # An output gate on attention (Qwen3-Next's): the query projection is twice as
    # wide, a head's second half gates the head's attention output through a sigmoid
    attn_output_gate: bool = False
    layernorm_epsilon: float = 1e-5
    tie_embeddings: bool = True
    use_bias: bool = True
    final_ln: bool = True  # False: no final LayerNorm (BERT encoders)
    # gelu | gelu_exact | relu | swiglu (down(silu(gate(x)) * up(x)), no biases:
    # the feed-forward of a model with no routed layer, and the gated experts of
    # moe_routing="dropless", its shared expert and its leading dense layers)
    activation: str = "gelu"
    embed_ln: bool = False  # LayerNorm after embedding (BLOOM)
    attn_impl: str = "xla"  # xla | flash | ring | sparse
    flash_block_q: int = 0  # 0 = auto (ops/pallas/flash_attention._auto_block / _key_block)
    flash_block_k: int = 0
    # attn_impl="sparse": block-sparse attention config (reference
    # ops/sparse_attention/sparsity_config.py). {"mode": "fixed"|"bigbird"|
    # "bslongformer"|"variable"|"dense", "block": 128, ...mode kwargs}
    sparsity: Optional[dict] = None
    decode_attn: str = "kernel"  # kernel (Pallas length-aware) | xla (dense)
    # weight-only quantization (inference): 0 = off; 8/4 = int bits. Weights
    # stay quantized in HBM; each scanned layer dequantizes only its own
    # slice (see quantize_weights / _dequant_layer).
    weight_bits: int = 0
    weight_group_size: int = 64
    # activation quantization (compression: reference basic_layer.py:12
    # QuantAct): fake-quantize the inputs of the layer's linear projections
    # (qkv, attn-out, ffn up/down) with a straight-through gradient. 0 = off.
    act_quant_bits: int = 0
    act_quant_symmetric: bool = True
    remat: bool = False  # activation checkpointing over the layer scan
    # Remat policy names: any jax.checkpoint_policies attr, plus
    #   "save_flash"      — the LEAST that is saved: the flash kernel's out/lse
    #                       residuals, so the Pallas forward never re-runs in
    #                       backward. A training engine whose device has room
    #                       adds a dense feed-forward's pre-activation as it
    #                       traces its step (remat_candidates; from shapes and
    #                       the device's memory limit, runtime/remat_plan.py)
    #   "dots_and_flash"  — dots_saveable + the flash residuals: no matmul or
    #                       attention recompute, memory = all matmul outputs
    # Any value but the default is an explicit choice and is taken as written.
    remat_policy: str = "save_flash"
    # Activation-checkpointing extensions (reference configure() knobs,
    # runtime/activation_checkpointing/checkpointing.py:825):
    #   remat_offload        — cpu_checkpointing: saved layer-boundary
    #                          activations live in pinned host memory
    #   remat_partition_axis — partition_activations: saved boundaries are
    #                          sharded over this mesh axis (e.g. "model");
    #                          recompute all-gathers them (memory↔comm trade)
    #   remat_group          — layers per checkpoint group; number_checkpoints
    #                          = num_layers // remat_group. >1 saves
    #                          boundaries only at group edges.
    remat_offload: bool = False
    remat_partition_axis: str = ""
    remat_group: int = 0
    # lax.scan unroll over the layer stack. >1 puts that many layers in one
    # loop body so XLA's latency-hiding scheduler can start layer i+1's
    # host->HBM parameter copy while layer i computes — the double-buffering
    # the ZeRO-Infinity param tier (runtime/zero/param_offload.py) needs to
    # stop serializing on the stream (the reference's prefetch coordinator
    # plays this role, runtime/zero/parameter_offload.py). Costs one extra
    # layer's params resident per unroll step; no effect on math.
    scan_unroll: int = 1
    # compute dtype. init() draws float32 leaves and training keeps them as its
    # masters; hold_for_compute() gives the tree an inference engine holds
    dtype: Any = jnp.float32
    moe_every: int = 0  # >0: every Nth layer is an MoE FFN (see moe/)
    num_experts: int = 1
    moe_top_k: int = 1
    # "gshard": top-1 / top-2, capacity, tokens over it dropped (moe/sharded_moe.py).
    # "dropless": any top-k, no capacity and no dropped token, gated experts,
    # every layer routed (moe/dropless.py).
    moe_routing: str = "gshard"
    # dropless only: the k weights renormalised to sum to 1 (a model's
    # norm_topk_prob), or left as the raw softmax probabilities
    moe_norm_topk_prob: bool = False
    # dropless only, the router's form (moe/dropless.py::route): the score of an
    # expert is the softmax over all of them or a sigmoid of its own logit; a
    # held (not trained by the loss) per-expert bias may be added to the scores
    # for the CHOICE alone; the k weights are multiplied by a constant
    moe_score_fn: str = "softmax"  # softmax | sigmoid
    moe_select_bias: bool = False
    moe_routed_scale: float = 1.0
    # dropless only: one gated expert of this width that every token goes
    # through beside its routed ones (0: none)
    moe_shared_size: int = 0
    # dropless only: the shared expert's output is multiplied by sigmoid(x w_g), one
    # learned [hidden_size] vector a layer (Qwen3-Next's ``shared_expert_gate``)
    moe_shared_gate: bool = False
    # dropless only: this many leading layers have a dense gated feed-forward
    # of width ``dense_intermediate_size`` instead of the routed block
    # (``intermediate_size`` stays the width of one expert)
    moe_first_dense: int = 0
    dense_intermediate_size: Optional[int] = None
    # dropless only: (first, count), the experts THIS program holds of the
    # ``num_experts`` the router chooses among: one chip's share of a layer that
    # expert parallelism spreads over several. The router keeps its width and its
    # ``moe_top_k`` a token; only the pairs whose expert is held are dispatched, and
    # the layer's output is the shared expert plus the held experts' part (what
    # the other chips would add is theirs). None: every expert
    moe_experts_held: Optional[tuple] = None
    # Multi-token-prediction modules behind the last layer (DeepSeek-V3 section
    # 2.2; 0 or 1): [RMSNorm(Emb(t_{i+1})) ; RMSNorm(x_i before the final norm)]
    # . W_eh, one block of the whole-context kind with no rotary and the model's
    # last layer's feed-forward, a final norm of its own, the SHARED embedding
    # and head -> logits for t_{i+2} (``apply(..., mtp_tokens=...)``)
    mtp_layers: int = 0
    moe_capacity_factor: float = 1.25  # gshard only
    moe_aux_coeff: float = 0.01  # load-balancing loss weight
    loss_chunk_size: int = 512  # chunk the vocab projection in the loss; 0 = off
    # "chunked": lax.scan over sequence chunks (logits chunk materialized,
    #   recomputed in backward — see lm_loss_from_hidden). "fused_xent":
    #   Pallas fused projection+xent (ops/pallas/fused_xent.py) — logits
    #   never reach HBM in either pass. Single-device / per-shard path;
    #   vocab-sharded TP keeps "chunked" (XLA partitions the einsum).
    loss_impl: str = "chunked"
    loss_fused_block_rows: int = 0  # 0 = auto (fused_xent._auto_block)
    loss_fused_block_v: int = 0
    # Dropout (reference fused layer: csrc/transformer/dropout_kernels.cu —
    # attn_output_dropout_ratio / hidden_dropout_ratio). Applied on the
    # attention output projection (attn) and on embeddings + FFN output
    # (hidden); active only when the caller passes an rng (training).
    hidden_dropout: float = 0.0
    attn_dropout: float = 0.0
    # Progressive layer drop (reference runtime/progressive_layer_drop.py:5):
    # theta(t) = pld_theta + (1 - pld_theta) * exp(-pld_gamma * t); layer i's
    # residual branches are kept with prob 1 - i/L * (1 - theta(t)).
    pld_enabled: bool = False
    pld_theta: float = 0.5
    pld_gamma: float = 0.001
    # ZeRO-Infinity parameter tier (engine offload_param, see
    # runtime/zero/param_offload.py): parameters live in pinned HOST memory
    # and each scanned layer streams its slice into HBM just-in-time;
    # gradients are pinned straight back to host. HBM then holds activations
    # plus one layer's working set — models whose parameters exceed device
    # memory train on one chip (reference: 13B on one 16 GB V100,
    # partition_parameters.py:537 remote_device='cpu').
    param_offload: bool = False

    def __post_init__(self):
        if self.multipliers is not None:
            object.__setattr__(self, "multipliers", _Stated(self.multipliers))
        for name in ("local_attn_layers", "rotary_layers", "moe_experts_held"):
            if isinstance(getattr(self, name), list):  # a JSON file's list: hashable
                object.__setattr__(self, name, tuple(int(v) for v in getattr(self, name)))
        if isinstance(self.layer_operators, list):
            object.__setattr__(self, "layer_operators", tuple(self.layer_operators))
        if self.rotary_by_kind is not None:
            object.__setattr__(self, "rotary_by_kind", _Stated(
                {kind: _Stated(spec) if isinstance(spec, dict) else spec
                 for kind, spec in self.rotary_by_kind.items()}))
        _refuse_uncoded(self)

    @property
    def layer_kinds(self):
        """Per layer (window, rotary, operator): the positions a query sees behind
        it (0: the whole context), whether its q and k are rotated, and what stands
        in the attention sublayer's place (``OPERATORS``: "attn" itself, "conv" or "delta").
        None where every layer is of one kind and the loop need not tell them apart."""
        if (self.local_attn_layers is None and self.rotary_layers is None
                and self.layer_operators is None):
            return None
        L = self.num_layers
        windows = [self.local_attn_window if on else 0
                   for on in (self.local_attn_layers or (0,) * L)]
        rotary = [bool(on) for on in (self.rotary_layers or (1,) * L)]
        return tuple(zip(windows, rotary, self.layer_operators or ("attn",) * L))

    def layers_of(self, operator: str) -> tuple:
        """The layers (model indices) whose operator is ``operator``."""
        return tuple(l for l, op in enumerate(self.layer_operators or ()) if op == operator)

    @property
    def conv_layers(self) -> tuple:
        """The layers (model indices) whose operator is the gated short convolution."""
        return self.layers_of("conv")

    @property
    def delta_layers(self) -> tuple:
        """The layers (model indices) whose operator is the gated delta rule."""
        return self.layers_of("delta")

    @property
    def stateful_layers(self) -> tuple:
        """The layers whose operator is not attention: each keeps per-sequence state
        (``cache_layout``) and nothing by position."""
        return tuple(l for l, op in enumerate(self.layer_operators or ()) if op != "attn")

    @property
    def delta_key_dim(self) -> int:
        """The delta rule's q (and k) channels: key heads x head width."""
        return self.delta_key_heads * self.delta_head_dim

    @property
    def delta_value_dim(self) -> int:
        """The delta rule's v (and z) channels: value heads x head width."""
        return self.delta_value_heads * self.delta_head_dim

    @property
    def delta_conv_dim(self) -> int:
        """The channels the delta rule's filter runs over: q | k | v."""
        return 2 * self.delta_key_dim + self.delta_value_dim

    @property
    def window_layers(self) -> tuple:
        """The layers (model indices) that attend inside a sliding window."""
        return tuple(l for l, on in enumerate(self.local_attn_layers or ()) if on)

    def rotary_spec(self, window) -> dict:
        """The rotary a layer of one KIND states (``rotary_by_kind``): ``window``
        truthy for a layer that attends inside the sliding window, falsy for one
        over the whole context. A kind that states none turns by ``rotary_base``."""
        stated = (self.rotary_by_kind or {}).get("window" if window else "whole")
        return stated if stated is not None else {"base": self.rotary_base}

    @property
    def experts_held(self) -> tuple:
        """(first, count) of the experts this program holds."""
        return tuple(self.moe_experts_held or (0, self.num_experts))

    @property
    def head_dim(self) -> int:
        """The width of a query / key head."""
        return self.qk_head_dim or self.hidden_size // self.num_heads

    @property
    def value_head_dim(self) -> int:
        return self.v_head_dim or self.head_dim

    @property
    def kv_heads(self) -> int:
        """The key / value heads plain attention projects and caches."""
        return self.num_kv_heads or self.num_heads

    @property
    def ssm_inner(self) -> int:
        """The mixer's channels: heads x head width."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """The channels the mixer convolves: x, then B and C of every group."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state_size

    def multiplier(self, name: str):
        """The constant the forward pass multiplies by at the place ``name``
        (``MULTIPLIERS``): a float, or a tuple of floats; 1 where not stated."""
        width = MULTIPLIERS[name]
        value = (self.multipliers or {}).get(name, 1.0 if width == 1 else (1.0,) * width)
        return float(value) if width == 1 else tuple(float(v) for v in value)

    @property
    def dense_ffn_size(self) -> int:
        return self.dense_intermediate_size or self.ffn_size

    @property
    def ffn_size(self) -> int:
        return self.intermediate_size or 4 * self.hidden_size

    def replace(self, **kw) -> "TransformerConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Parameter init + logical axes
# ---------------------------------------------------------------------------

def _dense_init(key, shape, fan_in):
    return (jax.random.normal(key, shape) * (1.0 / math.sqrt(fan_in))).astype(jnp.float32)


def _refuse_uncoded(cfg: "TransformerConfig") -> None:
    """Called when a configuration is built: the combinations of the latent
    attention and of the dropless router's forms that have no code are refused
    by name, never computed as something else."""
    if cfg.kv_lora_rank > 0:
        no_code = {
            f"pos_emb={cfg.pos_emb!r} (its positions are the rotary part of q and k)":
                cfg.pos_emb != "rotary",
            "qk_norm": bool(cfg.qk_norm), "use_bias": cfg.use_bias,
            "causal=False": not cfg.causal,
            "layer kinds (local_attn_layers / rotary_layers)": cfg.layer_kinds is not None,
            "mtp_layers": cfg.mtp_layers != 0,
            "weight_bits": cfg.weight_bits != 0,
            f"attn_impl={cfg.attn_impl!r} (training attends through the XLA form or the flash "
            "kernels, which take value heads of their own width)":
                cfg.attn_impl not in ("xla", "flash"),
            "decode_attn='kernel' (the Pallas decode kernel reads per-head K/V; state "
            "decode_attn='xla')": cfg.decode_attn == "kernel",
        }
        for what, refused in no_code.items():
            if refused:
                raise NotImplementedError(f"latent attention (kv_lora_rank > 0) with {what} "
                                          "has no code")
        rope = cfg.qk_rope_head_dim
        if not (0 < rope < cfg.qk_head_dim and rope % 2 == 0 and cfg.v_head_dim > 0):
            raise ValueError(
                "latent attention states its head sizes: qk_head_dim > qk_rope_head_dim > 0 "
                f"(even) and v_head_dim > 0 (got {cfg.qk_head_dim}, {rope}, {cfg.v_head_dim})")
    elif cfg.qk_rope_head_dim or cfg.v_head_dim not in (0, cfg.qk_head_dim):
        raise NotImplementedError(
            "v_head_dim other than qk_head_dim, or qk_rope_head_dim, without latent attention "
            "(kv_lora_rank > 0) have no code: a plain head has ONE width, qk_head_dim or "
            "hidden_size // num_heads")
    _refuse_uncoded_heads_and_mixer(cfg)
    _refuse_uncoded_kinds_and_share(cfg)
    _refuse_uncoded_passes(cfg)
    _refuse_uncoded_blocks(cfg)
    if cfg.moe_score_fn not in ("softmax", "sigmoid"):
        raise ValueError(f"moe_score_fn is 'softmax' or 'sigmoid', not {cfg.moe_score_fn!r}")
    forms = {"moe_score_fn": cfg.moe_score_fn != "softmax", "moe_select_bias": cfg.moe_select_bias,
             "moe_routed_scale": cfg.moe_routed_scale != 1.0,
             "moe_shared_size": cfg.moe_shared_size != 0,
             "moe_first_dense": cfg.moe_first_dense != 0,
             "dense_intermediate_size": cfg.dense_intermediate_size is not None}
    stated = [name for name, is_stated in forms.items() if is_stated]
    if stated and cfg.moe_routing != "dropless":
        raise NotImplementedError(
            f"{', '.join(stated)}: only moe_routing='dropless' has these forms "
            f"(got moe_routing={cfg.moe_routing!r})")
    if cfg.moe_first_dense and (cfg.use_bias or not 0 < cfg.moe_first_dense < cfg.num_layers):
        raise NotImplementedError(
            "moe_first_dense: the leading dense gated layers have no biases (use_bias=False) "
            f"and leave a routed layer behind them (got {cfg.moe_first_dense} of "
            f"{cfg.num_layers} layers, use_bias={cfg.use_bias})")


# The places the forward pass multiplies by a stated constant
# (``TransformerConfig.multipliers``), with how many constants each takes:
# ``ssm_multipliers`` scale the mixer's projection segment by segment (z, x, B,
# C, dt), ``mlp_multipliers`` the gate's pre-activation and the feed-forward's
# output. Falcon-H1's names.
MULTIPLIERS = {"embedding_multiplier": 1, "attention_in_multiplier": 1, "key_multiplier": 1,
               "attention_out_multiplier": 1, "ssm_in_multiplier": 1, "ssm_multipliers": 5,
               "ssm_out_multiplier": 1, "mlp_multipliers": 2, "lm_head_multiplier": 1}


def _refuse_uncoded_heads_and_mixer(cfg: "TransformerConfig") -> None:
    """``_refuse_uncoded`` for grouped-query heads, the state-space mixer and the
    multipliers: what has no code is refused by name."""
    grouped = cfg.num_kv_heads not in (0, cfg.num_heads)
    if grouped and cfg.num_heads % cfg.num_kv_heads:
        raise ValueError(f"num_kv_heads ({cfg.num_kv_heads}) does not divide num_heads "
                         f"({cfg.num_heads})")
    for name, value in (cfg.multipliers or {}).items():
        if name not in MULTIPLIERS:
            raise ValueError(f"multipliers: no place is called {name!r}; there are: "
                             f"{', '.join(MULTIPLIERS)}")
        if MULTIPLIERS[name] > 1 and len(value) != MULTIPLIERS[name]:
            raise ValueError(f"multipliers[{name!r}] takes {MULTIPLIERS[name]} values")
    mixer = cfg.ssm_state_size > 0
    for feature, on, no_code in (
        ("grouped-query heads (num_kv_heads < num_heads)", grouped, {
            "latent attention (kv_lora_rank > 0: its heads share ONE latent)":
                cfg.kv_lora_rank > 0,
            f"attn_impl={cfg.attn_impl!r} (training attends through the XLA form: the flash, "
            "ring and sparse kernels take as many K/V heads as query heads)":
                cfg.attn_impl != "xla",
            "decode_attn='kernel' (the Pallas decode kernel reads one K/V head per query "
            "head; state decode_attn='xla')": cfg.decode_attn == "kernel",
        }),
        ("the state-space mixer (ssm_state_size > 0)", mixer, {
            "causal=False (a recurrence runs one way)": not cfg.causal,
            "norm_style='post'": cfg.norm_style == "post",
            "parallel_residual (its feed-forward is sequential)": cfg.parallel_residual,
            "layer kinds (local_attn_layers / rotary_layers)": cfg.layer_kinds is not None,
            "mtp_layers": cfg.mtp_layers != 0,
            "weight_bits": cfg.weight_bits != 0, "act_quant_bits": cfg.act_quant_bits != 0,
            "use_bias (only its convolution has a bias)": cfg.use_bias,
            "latent attention (kv_lora_rank > 0)": cfg.kv_lora_rank > 0,
            "a routed feed-forward (moe_every > 0)": cfg.moe_every > 0,
            f"attn_impl={cfg.attn_impl!r}": cfg.attn_impl != "xla",
            "dropout or progressive layer drop":
                bool(cfg.hidden_dropout or cfg.attn_dropout or cfg.pld_enabled),
            "param_offload": cfg.param_offload,
        }),
    ):
        for what, refused in no_code.items():
            if on and refused:
                raise NotImplementedError(f"{feature} with {what} has no code")
    if mixer:
        sizes = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_conv_kernel,
                 cfg.ssm_chunk_size)
        if min(sizes) < 1 or cfg.ssm_conv_kernel < 2 or cfg.ssm_heads % cfg.ssm_groups:
            raise ValueError(
                "the state-space mixer states its sizes: ssm_heads, ssm_head_dim, ssm_groups "
                "(dividing ssm_heads), ssm_conv_kernel >= 2 and ssm_chunk_size, all > 0 "
                f"(got {sizes})")
    elif cfg.ssm_heads or cfg.ssm_head_dim:
        raise ValueError("ssm_heads / ssm_head_dim without ssm_state_size > 0: no mixer to size")
    stated = cfg.multipliers or {}
    if not mixer and any(name.startswith("ssm_") for name in stated):
        raise ValueError("multipliers of the state-space mixer without one (ssm_state_size = 0)")
    if "mlp_multipliers" in stated and (cfg.activation != "swiglu" or cfg.moe_every > 0):
        raise NotImplementedError(
            "mlp_multipliers scale a dense gated feed-forward's gate and output: "
            "activation='swiglu' with no routed layer")
    if "key_multiplier" in stated and cfg.kv_lora_rank:
        raise NotImplementedError("key_multiplier with latent attention has no code")


def _refuse_uncoded_kinds_and_share(cfg: "TransformerConfig") -> None:
    """``_refuse_uncoded`` for the layer kinds, the per-head q/k norm, the held
    share of the experts and the multi-token-prediction module. (Kinds with
    latent attention or the state-space mixer are refused beside those; a window
    layer through the CACHE with alibi, a verify block into a ring and a padded
    block without ``live`` are refused where the cache path is traced,
    ``_cache_attention``: ``apply`` has all of them.)"""
    if cfg.qk_norm not in (False, True, "head"):
        raise ValueError(f"qk_norm is False, True (one norm over the whole projection) or "
                         f"'head' (one of width head_dim on every head), not {cfg.qk_norm!r}")
    L = cfg.num_layers
    for name, flags in (("local_attn_layers", cfg.local_attn_layers),
                        ("rotary_layers", cfg.rotary_layers)):
        if flags is not None and (len(flags) != L or any(f not in (0, 1) for f in flags)):
            raise ValueError(f"{name} is one 0/1 flag a layer ({L} layers), got {flags!r}")
    if cfg.local_attn_layers is not None and any(cfg.local_attn_layers) \
            and cfg.local_attn_window <= 0:
        raise ValueError("local_attn_layers without a local_attn_window > 0: no window to slide")
    if cfg.rotary_layers is not None and cfg.pos_emb != "rotary":
        raise ValueError(f"rotary_layers with pos_emb={cfg.pos_emb!r}: no rotary to switch off")
    _refuse_uncoded_rotary_kinds(cfg)
    if cfg.moe_experts_held is not None:
        first, count = (tuple(cfg.moe_experts_held) + (0, 0))[:2]
        if cfg.moe_routing != "dropless":
            raise NotImplementedError(
                "moe_experts_held: only moe_routing='dropless' dispatches to a held share of "
                f"the experts (got moe_routing={cfg.moe_routing!r})")
        if len(cfg.moe_experts_held) != 2 or count < 1 or first < 0 \
                or first + count > cfg.num_experts:
            raise ValueError("moe_experts_held is (first, count) inside the num_experts "
                             f"({cfg.num_experts}) the router chooses among, got "
                             f"{cfg.moe_experts_held!r}")
    _refuse_uncoded_operators(cfg)
    if cfg.mtp_layers not in (0, 1):
        raise NotImplementedError(
            f"mtp_layers={cfg.mtp_layers}: one multi-token-prediction module has code (a chain "
            "of several, each fed the one before it, has none)")
    if cfg.mtp_layers:
        no_code = {"norm_kind other than 'rms' (its two joining norms are RMSNorms)":
                       cfg.norm_kind != "rms",
                   "norm_style='post'": cfg.norm_style == "post",
                   "causal=False": not cfg.causal, "param_offload": cfg.param_offload,
                   "weight_bits": cfg.weight_bits != 0,
                   "dropout or progressive layer drop":
                       bool(cfg.hidden_dropout or cfg.attn_dropout or cfg.pld_enabled)}
        for what, refused in no_code.items():
            if refused:
                raise NotImplementedError(f"mtp_layers with {what} has no code")


# A YaRN spec's keys (``rotary_by_kind``): those it must state, and those with a default.
_YARN_STATED = ("base", "factor", "original_max_position_embeddings")
_YARN_KEYS = _YARN_STATED + ("type", "beta_fast", "beta_slow", "attention_factor", "truncate")


def _refuse_uncoded_rotary_kinds(cfg: "TransformerConfig") -> None:
    """``_refuse_uncoded`` for ``rotary_by_kind``: a spec that is neither plain nor
    YaRN, or misses a key, and the combinations no code turns by a kind's table."""
    if cfg.rotary_by_kind is None:
        return
    if cfg.pos_emb != "rotary":
        raise ValueError(f"rotary_by_kind with pos_emb={cfg.pos_emb!r}: no rotary to state")
    for kind, spec in cfg.rotary_by_kind.items():
        if kind not in ("window", "whole"):
            raise ValueError(f"rotary_by_kind states the kinds 'window' and 'whole', not {kind!r}")
        if not isinstance(spec, dict) or float(spec.get("base", 0)) <= 0:
            raise ValueError(f"rotary_by_kind[{kind!r}] is a spec with a base > 0, got {spec!r}")
        form = spec.get("type", "plain")
        if form == "plain":
            known = ("type", "base")
        elif form == "yarn":
            known = _YARN_KEYS
            missing = [k for k in _YARN_STATED if k not in spec]
            if missing or float(spec["factor"]) <= 0:
                raise ValueError(f"rotary_by_kind[{kind!r}]: a YaRN spec states "
                                 f"{', '.join(_YARN_STATED)} (factor > 0), got {dict(spec)!r}")
        else:
            raise NotImplementedError(
                f"rotary_by_kind[{kind!r}]: a rotary of type {form!r} has no code ('plain' and "
                "'yarn' have; linear, dynamic, longrope and llama3 scaling have none)")
        unknown = sorted(set(spec) - set(known))
        if unknown:
            raise NotImplementedError(
                f"rotary_by_kind[{kind!r}]: the key(s) {', '.join(unknown)} of a {form} rotary "
                "have no code (mscale / mscale_all_dim among them: state attention_factor)")
    if "window" in cfg.rotary_by_kind and not cfg.window_layers:
        raise ValueError("rotary_by_kind states a 'window' kind and no layer attends inside a "
                         "window (local_attn_layers)")
    no_code = {"latent attention (kv_lora_rank > 0: its rotary part is one shared key's)":
                   cfg.kv_lora_rank > 0,
               "rotary_pct < 1": cfg.rotary_pct != 1.0,
               "rotary_interleaved": cfg.rotary_interleaved}
    for what, refused in no_code.items():
        if refused:
            raise NotImplementedError(f"rotary_by_kind with {what} has no code")


# What may stand in a layer's attention sublayer (``layer_operators``); also the keys
# of the operators' stacks under ``params["layers"]``.
OPERATORS = ("attn", "conv", "delta")


def _refuse_uncoded_operators(cfg: "TransformerConfig") -> None:
    """``_refuse_uncoded`` for ``layer_operators``, the attention gate and the shared
    expert's gate: what the stacks by operator, the layer loop's static kinds, the
    short convolution and the delta rule have no code for, each refusal naming the
    operator it refuses."""
    ops, L = cfg.layer_operators, cfg.num_layers
    delta_sizes = (cfg.delta_key_heads, cfg.delta_value_heads, cfg.delta_head_dim)
    if cfg.moe_shared_gate and not (cfg.moe_routing == "dropless" and cfg.moe_shared_size):
        raise ValueError("moe_shared_gate without a shared expert (moe_routing='dropless', "
                         "moe_shared_size > 0): nothing to gate")
    if cfg.attn_output_gate:
        no_code = {"latent attention (kv_lora_rank > 0)": cfg.kv_lora_rank > 0,
                   "use_bias (the gate's half of the projection is drawn with none)": cfg.use_bias}
        for what, refused in no_code.items():
            if refused:
                raise NotImplementedError(f"attn_output_gate with {what} has no code")
    if ops is None:
        if cfg.conv_kernel or any(delta_sizes):
            raise ValueError("conv_kernel / delta_key_heads / delta_value_heads / delta_head_dim "
                             "without layer_operators: no layer to filter")
        return
    if len(ops) != L or any(op not in OPERATORS for op in ops):
        raise ValueError(f"layer_operators is one of {OPERATORS} a layer ({L} layers), got {ops!r}")
    others = sorted(set(ops) - {"attn"})
    if "attn" not in ops or not others:
        raise ValueError("layer_operators states layers of BOTH operators, attention and one "
                         "other (every layer attending is layer_operators=None; a model with no "
                         f"attention layer has no per-token cache to size), got {ops!r}")
    if len(others) > 1:
        raise NotImplementedError(
            f"layer_operators with both {others[0]!r} and {others[1]!r} layers has no code: the "
            "cache keeps ONE operator's per-sequence leaves beside the attention layers' K/V")
    op = others[0]
    if cfg.conv_kernel < 2:
        raise ValueError(f"layer_operators with a {op!r} layer states conv_kernel >= 2 taps "
                         f"(got {cfg.conv_kernel})")
    if op == "delta":
        kh, vh, width = delta_sizes
        if min(delta_sizes) < 1 or vh % kh:
            raise ValueError("layer_operators with a 'delta' layer states its sizes: "
                             "delta_key_heads, delta_value_heads (a multiple of them) and "
                             f"delta_head_dim, all > 0 (got {delta_sizes})")
    elif any(delta_sizes):
        raise ValueError("delta_key_heads / delta_value_heads / delta_head_dim without a 'delta' "
                         "layer: no rule to size")
    no_code = {
        "latent attention (kv_lora_rank > 0)": cfg.kv_lora_rank > 0,
        "the state-space mixer (ssm_state_size > 0: a mixer beside every layer's attention)":
            cfg.ssm_state_size > 0,
        "pos_emb='alibi' (its bias is every layer's)": cfg.pos_emb == "alibi",
        "window layers (local_attn_layers)": bool(cfg.window_layers),
        "use_bias (neither operator's leaves are drawn with one)": cfg.use_bias,
        "norm_style='post'": cfg.norm_style == "post",
        "parallel_residual": cfg.parallel_residual,
        "causal=False (the filter runs one way)": not cfg.causal,
        "weight_bits": cfg.weight_bits != 0, "act_quant_bits": cfg.act_quant_bits != 0,
        "param_offload (the operators' stacks are not streamed)": cfg.param_offload,
        "mtp_layers": cfg.mtp_layers != 0,
        "GShard routing (moe_every > 0 without moe_routing='dropless')":
            cfg.moe_every > 0 and cfg.moe_routing != "dropless",
        f"attn_impl={cfg.attn_impl!r}": cfg.attn_impl != "xla",
    }
    for what, refused in no_code.items():
        if refused:
            raise NotImplementedError(f"layer_operators (a {op!r} layer) with {what} has no code")


def _refuse_uncoded_passes(cfg: "TransformerConfig") -> None:
    """``_refuse_uncoded`` for the sandwich norms, the passes over the layer stack and
    the exit gate: what the outer loop, the cache's (pass, layer) index and the two
    branch norms have no code for (or no test against a reference), by name."""
    if cfg.norm_style not in ("pre", "post", "sandwich"):
        raise ValueError(f"norm_style is 'pre', 'post' or 'sandwich', not {cfg.norm_style!r}")
    if cfg.layer_passes < 1:
        raise ValueError(f"layer_passes is the times the stack runs, >= 1 (got {cfg.layer_passes})")
    if cfg.exit_gate and cfg.layer_passes == 1:
        raise ValueError("exit_gate without layer_passes > 1: no pass to stop before")
    for feature, on, no_code in (
        ("norm_style='sandwich'", cfg.norm_style == "sandwich", {
            "parallel_residual (the second norm is on ONE sublayer's branch)":
                cfg.parallel_residual,
            "the state-space mixer (ssm_state_size > 0: two operators share the branch)":
                cfg.ssm_state_size > 0,
            "layer_operators": cfg.layer_operators is not None,
            "mtp_layers": cfg.mtp_layers != 0,
        }),
        ("layer_passes > 1", cfg.layer_passes > 1, {
            "layer kinds (local_attn_layers / rotary_layers / layer_operators: the blocks read "
            "a layer's kind at the index the loop hands them, which counts the passes)":
                cfg.layer_kinds is not None,
            "the state-space mixer (ssm_state_size > 0: its state is a layer's, not a pass's)":
                cfg.ssm_state_size > 0,
            "latent attention (kv_lora_rank > 0)": cfg.kv_lora_rank > 0,
            "a routed feed-forward (moe_every > 0: the experts chosen are stacked by layer)":
                cfg.moe_every > 0,
            "mtp_layers (the module reads the stream BEFORE the final norm)": cfg.mtp_layers != 0,
            "dropout or progressive layer drop (one key a layer, not a pass)":
                bool(cfg.hidden_dropout or cfg.attn_dropout or cfg.pld_enabled),
            "param_offload (a streamed slice's host-pinned cotangents would meet over the "
            "passes)": cfg.param_offload,
        }),
    ):
        for what, refused in no_code.items():
            if on and refused:
                raise NotImplementedError(f"{feature} with {what} has no code")


def _refuse_uncoded_blocks(cfg: "TransformerConfig") -> None:
    """``_refuse_uncoded`` for the block-causal mask (``attn_block_length`` > 1): what
    no code attends under it, by name. (Its training objective is refused where the
    loss is asked for, ``causal_lm_loss``; what the serving engine has not built for it
    where the engine is built.)"""
    B = cfg.attn_block_length
    if B < 1 or B & (B - 1) or B > 128:
        raise ValueError(
            "attn_block_length is a power of two from 1 (the causal mask) to 128: the flash "
            f"kernel's tiles start at multiples of 128, which a block must not straddle (got {B})")
    if B == 1:
        return
    if not 0 <= cfg.mask_token_id < cfg.vocab_size:
        raise ValueError(
            f"attn_block_length={B} states its mask_token_id, an id of the vocabulary "
            f"(got {cfg.mask_token_id} of {cfg.vocab_size})")
    no_code = {
        "causal=False (the mask is causal between blocks)": not cfg.causal,
        "window layers, rotary_layers or layer_operators (a ring keeps positions, not "
        "blocks; a conv's or a delta rule's state has no block to reopen)":
            cfg.layer_kinds is not None,
        "the state-space mixer (ssm_state_size > 0: a state moved on by a pass cannot be "
        "moved back for the next pass over the same block)": cfg.ssm_state_size > 0,
        "latent attention (kv_lora_rank > 0)": cfg.kv_lora_rank > 0,
        "layer_passes > 1": cfg.layer_passes > 1,
        "mtp_layers": cfg.mtp_layers != 0,
        "pos_emb='alibi' (its bias is written for the causal mask)": cfg.pos_emb == "alibi",
        f"attn_impl={cfg.attn_impl!r} (the XLA form and the flash kernel take the mask)":
            cfg.attn_impl not in ("xla", "flash"),
        "decode_attn='kernel' (the Pallas decode kernel walks the keys at or under ONE "
        "query's position; state decode_attn='xla')": cfg.decode_attn == "kernel",
    }
    for what, refused in no_code.items():
        if refused:
            raise NotImplementedError(f"attn_block_length > 1 with {what} has no code")


def refuse_in_pipeline(cfg: "TransformerConfig") -> None:
    """What the pipeline schedules (pipe/) have not carried yet, by name."""
    if cfg.attn_block_length > 1:
        raise NotImplementedError(
            "attn_block_length > 1 under a pipeline schedule has no code: the stages attend "
            "under the causal mask")
    if cfg.layer_passes > 1:
        raise NotImplementedError(
            "layer_passes > 1 under a pipeline schedule has no code: a stage would be visited "
            "once a pass, and the schedules send a micro-batch through the stages once")
    if cfg.layer_operators is not None:
        raise NotImplementedError(
            "layer_operators under a pipeline schedule has no code: the stages slice ONE "
            "stack of identical layers, and these lie in stacks by operator")
    if cfg.rotary_layers is not None or cfg.rotary_by_kind is not None or cfg.mtp_layers:
        raise NotImplementedError(
            "rotary_layers / rotary_by_kind / mtp_layers under a pipeline schedule have no "
            "code: a stage sees its own layer indices, not the model's, and no stage owns the "
            "module behind the last layer")
    if cfg.ssm_state_size > 0:
        raise NotImplementedError(
            "the state-space mixer (ssm_state_size > 0) under a pipeline schedule has no "
            "code: the stages' stacks and logical axes do not name its leaves")


def _dropless(cfg: TransformerConfig) -> bool:
    """Whether the model's feed-forward is the dropless routed block
    (moe/dropless.py) in every layer after the ``moe_first_dense`` leading ones;
    refuses the combinations that block, and a gated dense feed-forward, do not
    have."""
    dropless, gated = cfg.moe_routing == "dropless", cfg.activation == "swiglu"
    if dropless and (not gated or cfg.moe_every != 1):
        raise NotImplementedError(
            "moe_routing='dropless', activation='swiglu' and moe_every=1 come together: the "
            "dropless block has gated experts in every layer (after moe_first_dense leading "
            f"dense gated ones) (got activation={cfg.activation!r}, moe_every={cfg.moe_every})")
    if gated and not dropless and (cfg.moe_every > 0 or cfg.use_bias):
        raise NotImplementedError(
            "activation='swiglu' without moe_routing='dropless' is a dense gated feed-forward "
            "in every layer, with no biases: GShard's experts are not gated (got moe_every="
            f"{cfg.moe_every}, use_bias={cfg.use_bias})")
    return dropless


def init(cfg: TransformerConfig, rng: jax.Array) -> Params:
    """The seeded draw. Every matrix at 1 / sqrt(fan in); one whose OUTPUT a
    stated multiplier scales (``MULTIPLIERS``) at 1 / (sqrt(fan in) x that
    multiplier), the mixer's input projection segment by segment, so that the
    multiplied activations have the size they have in a model without
    multipliers and a multiplier dropped from the forward pass changes the
    logits by its factor, not by nothing."""
    keys = jax.random.split(rng, 16)
    more = jax.random.split(jax.random.fold_in(rng, 16), 8)  # the leaves newer than the 16
    d, f, L = cfg.hidden_size, cfg.ffn_size, cfg.num_layers
    H, Hkv, Dh, Dv = cfg.num_heads, cfg.kv_heads, cfg.head_dim, cfg.value_head_dim
    dropless = _dropless(cfg)
    mult = cfg.multiplier
    attn_in = mult("attention_in_multiplier")
    La = L - len(cfg.stateful_layers)  # the layers that attend: as long as the attention stacks are

    def stack(key, shape, fan_in, n=L, scale=1.0):
        ks = jax.random.split(key, n)
        return jnp.stack([_dense_init(k, shape, fan_in) for k in ks]) / scale

    layers = {
        "ln1_scale": jnp.ones((L, d)),
        "ln2_scale": jnp.ones((L, d)),
        # with an output gate a head's projection is query | gate, each Dh wide
        "wq": stack(keys[0], (d, H, Dh * (1 + cfg.attn_output_gate)), d, La, scale=attn_in),
        # a head width that is not hidden_size // num_heads: the fan-in is the heads'
        "wo": stack(keys[3], (H, Dv, d), d if cfg.kv_lora_rank else H * Dv, La,
                    scale=mult("attention_out_multiplier")),
    }
    if cfg.kv_lora_rank:  # the latent and the shared rotary key; then every head's k_nope | v
        R, Dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
        layers.update({"wkv_a": stack(keys[1], (d, R + Dr), d),
                       "kv_norm_scale": jnp.ones((L, R)),
                       "wkv_b": stack(keys[2], (R, H, Dh - Dr + Dv), R)})
    else:
        layers.update({
            "wk": stack(keys[1], (d, Hkv, Dh), d, La, scale=attn_in * mult("key_multiplier")),
            "wv": stack(keys[2], (d, Hkv, Dh), d, La, scale=attn_in)})
    if cfg.norm_kind != "rms":
        layers.update({"ln1_bias": jnp.zeros((L, d)), "ln2_bias": jnp.zeros((L, d))})
    if cfg.norm_style == "sandwich":  # the second norm of each sublayer, on its branch
        for name in ("ln1_post", "ln2_post"):
            layers[name + "_scale"] = jnp.ones((L, d))
            if cfg.norm_kind != "rms":
                layers[name + "_bias"] = jnp.zeros((L, d))
    if cfg.qk_norm == "head":  # one [head_dim] scale, every head's
        layers.update({"q_norm_scale": jnp.ones((La, Dh)), "k_norm_scale": jnp.ones((La, Dh))})
    elif cfg.qk_norm:
        layers.update({"q_norm_scale": jnp.ones((La, H, Dh)),
                       "k_norm_scale": jnp.ones((La, Hkv, Dh))})
    if not dropless:  # no layer has a dense feed-forward there
        gate_m, down_m = mult("mlp_multipliers")
        layers.update({"wi": stack(keys[4], (d, f), d),
                       "wo_mlp": stack(keys[5], (f, d), f, scale=down_m)})
        if cfg.activation == "swiglu":
            layers["wg"] = stack(more[0], (d, f), d, scale=gate_m)
    if cfg.ssm_state_size:
        layers.update(_init_mixer(cfg, more[1:6], stack))
    if cfg.use_bias:
        layers.update(
            {
                "bq": jnp.zeros((L, H, Dh)),
                "bk": jnp.zeros((L, Hkv, Dh)),
                "bv": jnp.zeros((L, Hkv, Dh)),
                "bo": jnp.zeros((L, d)),
            }
        )
        if not dropless:
            layers.update({"bi": jnp.zeros((L, f)), "bo_mlp": jnp.zeros((L, d))})
    if cfg.delta_layers:
        layers = _stacks_by_operator(layers, "delta", _init_delta(cfg, more[7], stack))
    elif cfg.layer_operators is not None:
        layers = _stacks_by_operator(layers, "conv", _init_conv(cfg, more[7], stack))
    stated = cfg.multipliers or {}
    params = {
        # with a stated embedding multiplier the residual stream enters at the size of
        # a layer's output (1), so that the multiplier left out would show in the logits
        "wte": jax.random.normal(keys[6], (cfg.vocab_size, d)) * (
            1.0 / mult("embedding_multiplier") if "embedding_multiplier" in stated else 0.02),
        "layers": layers,
        "lnf_scale": jnp.ones((d,)),
    }
    if cfg.norm_kind != "rms":
        params["lnf_bias"] = jnp.zeros((d,))
    if cfg.pos_emb == "learned":
        params["wpe"] = jax.random.normal(keys[7], (cfg.max_seq_len, d)) * 0.01
    if cfg.embed_ln:
        params["emb_ln_scale"] = jnp.ones((d,))
        if cfg.norm_kind != "rms":
            params["emb_ln_bias"] = jnp.zeros((d,))
    if not cfg.tie_embeddings:
        params["lm_head"] = (_dense_init(keys[8], (d, cfg.vocab_size), d)
                             / mult("lm_head_multiplier"))
    elif "lm_head_multiplier" in stated:
        raise NotImplementedError("lm_head_multiplier with tie_embeddings has no seeded draw: "
                                  "the embedding cannot be drawn for both multipliers")
    if dropless:
        from ..moe.dropless import init_dropless

        lead, fd = cfg.moe_first_dense, cfg.dense_ffn_size
        params["moe"] = init_dropless(keys[9], L - lead, cfg.num_experts, d, f,
                                      shared=cfg.moe_shared_size, select_bias=cfg.moe_select_bias,
                                      held=cfg.experts_held[1], shared_gate=cfg.moe_shared_gate)
        if lead:
            params["dense_ffn"] = {"wg": stack(keys[10], (d, fd), d, lead),
                                   "wi": stack(keys[11], (d, fd), d, lead),
                                   "wo_mlp": stack(keys[12], (fd, d), fd, lead)}
    elif cfg.moe_every > 0:
        from ..moe.layer import init_moe_params

        n_moe = cfg.num_layers // cfg.moe_every
        params["moe"] = init_moe_params(keys[9], n_moe, cfg.num_experts, d, f)
    if cfg.mtp_layers:
        params["mtp"] = _init_mtp(cfg, more[6])
    if cfg.exit_gate:  # drawn as any [d, 1] matrix; float32 and replicated wherever it is held
        params["exit_gate"] = {"w": _dense_init(jax.random.fold_in(rng, 17), (d, 1), d),
                               "b": jnp.zeros((1,))}
    return params


def _mtp_block_cfg(cfg: TransformerConfig) -> TransformerConfig:
    """The configuration of the multi-token-prediction module's ONE block: the
    model's own, of the whole-context kind with no rotary, its feed-forward the
    model's last layer's (routed where the model routes)."""
    return cfg.replace(num_layers=1, mtp_layers=0, moe_first_dense=0,
                       dense_intermediate_size=None, local_attn_layers=None, rotary_by_kind=None,
                       rotary_layers=(0,) if cfg.pos_emb == "rotary" else None)


def _init_mtp(cfg: TransformerConfig, key) -> dict:
    """The module's leaves: the two joining norms, ``eh_proj`` [2d, d] (the
    embedding's half first), one block drawn as the model's are, its final norm.
    The embedding and the head are the model's own."""
    d = cfg.hidden_size
    k_proj, k_block = jax.random.split(key)
    block = init(_mtp_block_cfg(cfg).replace(vocab_size=1, tie_embeddings=True,
                                             pos_emb="none" if cfg.pos_emb == "learned"
                                             else cfg.pos_emb), k_block)
    out = {"enorm_scale": jnp.ones((d,)), "hnorm_scale": jnp.ones((d,)),
           "eh_proj": _dense_init(k_proj, (2 * d, d), 2 * d),
           "layers": block["layers"], "lnf_scale": jnp.ones((d,))}
    if "moe" in block:
        out["moe"] = block["moe"]
    return out


# The leaves of a layer that belong to its attention sublayer (no biases: a model
# with ``layer_operators`` has none): those that lie in the "attn" stack there.
_ATTENTION_LEAVES = ("wq", "wk", "wv", "wo", "q_norm_scale", "k_norm_scale")


def _stacks_by_operator(layers: dict, operator: str, own: dict) -> dict:
    """``init``'s (or ``logical_axes``') one dict of a layer's leaves -> the same
    with the STACKS BY OPERATOR: the norms and a dense feed-forward, which every
    layer has, stay [L, ...] at the top; the attention sublayer's leaves go under
    ``"attn"`` ([attention layers, ...]) and the other operator's (``own``: the short
    convolution's or the delta rule's) under its name (``"conv"`` / ``"delta"``),
    each stack as long as its layers are many."""
    shared = {k: v for k, v in layers.items() if k not in _ATTENTION_LEAVES}
    return {**shared, "attn": {k: layers[k] for k in _ATTENTION_LEAVES if k in layers},
            operator: own}


def _init_conv(cfg: TransformerConfig, key, stack) -> dict:
    """The short convolution's leaves, [conv layers]-stacked: ``conv_in`` maps the
    hidden state to B | C | z (three chunks of ``hidden_size``), ``conv_w`` the
    filter's taps [taps, channels] (uniform within 1 / sqrt(taps), as a
    depthwise ``Conv1d`` draws them; tap j multiplies u_{t - taps + 1 + j}),
    ``conv_out`` the output projection."""
    d, K, n = cfg.hidden_size, cfg.conv_kernel, len(cfg.conv_layers)
    k_in, k_w, k_out = jax.random.split(key, 3)
    bound = 1.0 / math.sqrt(K)
    return {"conv_in": stack(k_in, (d, 3 * d), d, n),
            "conv_w": jax.random.uniform(k_w, (n, K, d), minval=-bound, maxval=bound),
            "conv_out": stack(k_out, (d, d), d, n)}


def _init_delta(cfg: TransformerConfig, key, stack) -> dict:
    """The gated delta rule's leaves, [delta layers]-stacked: ``delta_in`` maps the
    hidden state to q | k | v | z (key, key, value, value channels, in that order,
    heads side by side within each), ``delta_ba`` to b | a (one of each a value
    head), ``delta_conv`` the filter's taps [taps, q | k | v channels] (uniform
    within 1 / sqrt(taps), as a depthwise ``Conv1d`` draws them), ``delta_a_log`` =
    log U(0, 16) as the published initialiser has it (the draw's floor keeps the log
    finite) and ``delta_dt_bias`` the inverse softplus of a log-uniform step in
    [0.001, 0.1] (Gated DeltaNet's own initialiser and ``_init_mixer``'s: heads that
    forget in a few tokens and heads that remember thousands), the gated norm's
    [head width] scale, ``delta_out`` the projection. The published model class
    fills ``dt_bias`` with 1 instead: with A in (0, 16) that is exp(g) < 0.01 a token
    for four heads in five, whose output is then sign(q_t . k_t) x the normed v_t, a
    value no 16-bit residual stream holds to a float32 reference (on the chip at
    the published widths: max logit error 0.34 and a routing slack of 1.4 standard
    deviations from heads whose q . k rounds across 0; PERF.md section 6, PR 52), and
    whose state no step ever reads."""
    d, K, n = cfg.hidden_size, cfg.conv_kernel, len(cfg.delta_layers)
    Hv, Kd, Vd = cfg.delta_value_heads, cfg.delta_key_dim, cfg.delta_value_dim
    k_in, k_ba, k_w, k_a, k_dt, k_out = jax.random.split(key, 6)
    bound = 1.0 / math.sqrt(K)
    dt = jnp.exp(jax.random.uniform(k_dt, (n, Hv), minval=math.log(1e-3), maxval=math.log(1e-1)))
    return {"delta_in": stack(k_in, (d, 2 * Kd + 2 * Vd), d, n),
            "delta_ba": stack(k_ba, (d, 2 * Hv), d, n),
            "delta_conv": jax.random.uniform(k_w, (n, K, cfg.delta_conv_dim),
                                             minval=-bound, maxval=bound),
            "delta_a_log": jnp.log(jax.random.uniform(k_a, (n, Hv), minval=1e-3, maxval=16.0)),
            "delta_dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1
            "delta_norm_scale": jnp.ones((n, cfg.delta_head_dim)),
            "delta_out": stack(k_out, (Vd, d), Vd, n)}


def _init_mixer(cfg: TransformerConfig, keys, stack) -> dict:
    """The mixer's leaves, [L]-stacked. ``ssm_in`` maps the hidden state to
    z | x | B | C | dt (``ssm_inner``, ``ssm_inner``, G x N, G x N, heads), each
    segment drawn for its own multiplier; the rest as Mamba-2 initialises them:
    ``A_log`` = log(1 ... heads) (heads that forget in a few tokens and heads
    that remember hundreds), ``dt_bias`` the inverse softplus of a log-uniform
    step in [0.001, 0.1], ``D`` = 1, the convolution uniform within 1 / sqrt(taps)."""
    d, L, H, K = cfg.hidden_size, cfg.num_layers, cfg.ssm_heads, cfg.ssm_conv_kernel
    inner, gn, conv_dim = cfg.ssm_inner, cfg.ssm_groups * cfg.ssm_state_size, cfg.ssm_conv_dim
    in_m = cfg.multiplier("ssm_in_multiplier")
    segments = zip(jax.random.split(keys[0], 5), (inner, inner, gn, gn, H),
                   cfg.multiplier("ssm_multipliers"))
    dt = jnp.exp(jax.random.uniform(keys[1], (L, H), minval=math.log(1e-3), maxval=math.log(1e-1)))
    bound = 1.0 / math.sqrt(K)
    return {
        "ssm_in": jnp.concatenate([stack(k, (d, width), d, scale=in_m * m)
                                   for k, width, m in segments], axis=-1),
        "ssm_conv": jax.random.uniform(keys[2], (L, K, conv_dim), minval=-bound, maxval=bound),
        "ssm_conv_bias": jax.random.uniform(keys[3], (L, conv_dim), minval=-bound, maxval=bound),
        "ssm_dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1
        "ssm_a_log": jnp.broadcast_to(jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)), (L, H)),
        "ssm_d": jnp.ones((L, H)),
        "ssm_norm_scale": jnp.ones((L, inner)),
        "ssm_out": stack(keys[4], (inner, d), inner, scale=cfg.multiplier("ssm_out_multiplier")),
    }


def logical_axes(cfg: TransformerConfig) -> Params:
    """Pytree of logical-axis tuples matching ``init``'s output; consumed by
    parallel/sharding.spec_from_logical."""
    dropless = _dropless(cfg)
    layers = {
        "ln1_scale": ("layers", "embed"),
        "ln2_scale": ("layers", "embed"),
        "wq": ("layers", "embed", "heads", "kv"),
        "wo": ("layers", "heads", "kv", "embed"),
    }
    if cfg.kv_lora_rank:  # the latent is every head's: not split over them
        layers.update({"wkv_a": ("layers", "embed", None), "kv_norm_scale": ("layers", None),
                       "wkv_b": ("layers", None, "heads", "kv")})
    else:
        layers.update({"wk": ("layers", "embed", "heads", "kv"),
                       "wv": ("layers", "embed", "heads", "kv")})
    if cfg.norm_kind != "rms":
        layers.update({"ln1_bias": ("layers", "embed"), "ln2_bias": ("layers", "embed")})
    if cfg.norm_style == "sandwich":
        for name in ("ln1_post", "ln2_post"):
            layers[name + "_scale"] = ("layers", "embed")
            if cfg.norm_kind != "rms":
                layers[name + "_bias"] = ("layers", "embed")
    if cfg.qk_norm == "head":
        layers.update({"q_norm_scale": ("layers", "kv"), "k_norm_scale": ("layers", "kv")})
    elif cfg.qk_norm:
        layers.update({"q_norm_scale": ("layers", "heads", "kv"),
                       "k_norm_scale": ("layers", "heads", "kv")})
    if not dropless:
        layers.update({"wi": ("layers", "embed", "mlp"), "wo_mlp": ("layers", "mlp", "embed")})
        if cfg.activation == "swiglu":
            layers["wg"] = ("layers", "embed", "mlp")
    if cfg.ssm_state_size:  # z | x | B | C | dt lie side by side in one axis: not split
        layers.update({"ssm_in": ("layers", "embed", None), "ssm_conv": ("layers", None, None),
                       "ssm_conv_bias": ("layers", None), "ssm_dt_bias": ("layers", None),
                       "ssm_a_log": ("layers", None), "ssm_d": ("layers", None),
                       "ssm_norm_scale": ("layers", None), "ssm_out": ("layers", None, "embed")})
    if cfg.use_bias:
        layers.update(
            {
                "bq": ("layers", "heads", "kv"),
                "bk": ("layers", "heads", "kv"),
                "bv": ("layers", "heads", "kv"),
                "bo": ("layers", "embed"),
            }
        )
        if not dropless:
            layers.update({"bi": ("layers", "mlp"), "bo_mlp": ("layers", "embed")})
    if cfg.delta_layers:  # q | k | v | z lie side by side in one axis: not split
        layers = _stacks_by_operator(layers, "delta", {
            "delta_in": ("layers", "embed", None), "delta_ba": ("layers", "embed", None),
            "delta_conv": ("layers", None, None), "delta_a_log": ("layers", None),
            "delta_dt_bias": ("layers", None), "delta_norm_scale": ("layers", None),
            "delta_out": ("layers", None, "embed")})
    elif cfg.layer_operators is not None:  # B | C | z lie side by side in one axis: not split
        layers = _stacks_by_operator(layers, "conv", {
            "conv_in": ("layers", "embed", None), "conv_w": ("layers", None, None),
            "conv_out": ("layers", None, "embed")})
    axes = {
        "wte": ("vocab", "embed"),
        "layers": layers,
        "lnf_scale": ("embed",),
    }
    if cfg.norm_kind != "rms":
        axes["lnf_bias"] = ("embed",)
    if cfg.pos_emb == "learned":
        axes["wpe"] = (None, "embed")
    if cfg.embed_ln:
        axes["emb_ln_scale"] = ("embed",)
        if cfg.norm_kind != "rms":
            axes["emb_ln_bias"] = ("embed",)
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    if dropless:
        from ..moe.dropless import dropless_logical_axes

        axes["moe"] = dropless_logical_axes(shared=bool(cfg.moe_shared_size),
                                            select_bias=cfg.moe_select_bias,
                                            shared_gate=cfg.moe_shared_gate)
        if cfg.moe_first_dense:
            axes["dense_ffn"] = {"wg": ("layers", "embed", "mlp"), "wi": ("layers", "embed", "mlp"),
                                 "wo_mlp": ("layers", "mlp", "embed")}
    elif cfg.moe_every > 0:
        from ..moe.layer import moe_logical_axes

        axes["moe"] = moe_logical_axes()
    if cfg.mtp_layers:
        block = logical_axes(_mtp_block_cfg(cfg))
        axes["mtp"] = {"enorm_scale": ("embed",), "hnorm_scale": ("embed",),
                       "eh_proj": (None, "embed"), "layers": block["layers"],
                       "lnf_scale": ("embed",), **({"moe": block["moe"]} if "moe" in block else {})}
    if cfg.exit_gate:
        axes["exit_gate"] = {"w": (None, None), "b": (None,)}
    return axes


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

# The leaves the forward pass below casts to ``cfg.dtype`` before it uses them
# (``.astype(h.dtype)`` and its like): the embeddings (``embed``), the
# vocabulary projection, the attention and feed-forward matrices and the biases
# added in the activations' dtype (``_qkv_proj``, ``_attn_out_proj``, ``_ffn``),
# and the expert banks (``wg`` / ``wi`` / ``wo`` of moe/dropless.py and
# moe/experts.py; the shared expert and the leading dense layers have the same
# names), and latent attention's ``wkv_a`` / ``wkv_b``. Every other floating leaf
# is read in float32: the norm scales and biases (``layer_norm`` / ``rms_norm``
# multiply in float32), the q/k norm scales and the latent's ``kv_norm_scale``,
# ``lm_head_bias`` (added to float32 logits) and the router's ``gate`` and its
# selection ``bias`` (``dropless.route`` is a float32 product at full precision,
# and the GShard path's ``moe_dispatch_combine`` likewise). Of the state-space
# mixer the two projections and the convolution (its tail is cached in the
# compute dtype) are cast; ``ssm_dt_bias``, ``ssm_a_log``, ``ssm_d`` and the gated
# norm's scale enter float32 arithmetic and are read in float32. The gated short
# convolution's two projections and its taps are cast likewise, and the gated delta
# rule's three projections and its taps (``delta_a_log``, ``delta_dt_bias`` and its
# norm's scale are read in float32); the shared expert's gate vector (``w_gate``) too.
_READ_IN_COMPUTE_DTYPE = frozenset({
    "wte", "wpe", "lm_head", "eh_proj",
    "wq", "wk", "wv", "wo", "bq", "bk", "bv", "bo", "wkv_a", "wkv_b",
    "wi", "wo_mlp", "bi", "bo_mlp", "wg",
    "ssm_in", "ssm_out", "ssm_conv", "ssm_conv_bias",
    "conv_in", "conv_out", "conv_w",
    "delta_in", "delta_ba", "delta_conv", "delta_out", "w_gate",
})


def hold_for_compute(cfg: TransformerConfig, params: Params) -> Params:
    """``params`` with every floating leaf in the dtype the forward pass reads
    it in: ``cfg.dtype`` for the leaves of ``_READ_IN_COMPUTE_DTYPE``, float32
    for the rest. The forward pass's own casts are then no-ops, and its outputs
    are those it computes from the float32 tree, bit for bit: a leaf rounded
    once is the same number as that leaf rounded in every call. What an engine
    that only runs the forward pass should hold (``InferenceEngine``); training
    keeps float32 masters and calls the same forward pass on them. Integer
    leaves and quantised storage (``{"q", "s"}`` under a matrix's name) pass
    through; with ``cfg.dtype`` float32 nothing changes. Takes numpy leaves on
    the host and traced ones inside ``jit`` alike."""
    held = jnp.dtype(cfg.dtype)

    def hold(path, leaf):
        if not jnp.issubdtype(leaf.dtype, jnp.floating):
            return leaf
        name = getattr(path[-1], "key", None)
        return leaf.astype(held if name in _READ_IN_COMPUTE_DTYPE else jnp.float32)

    return jax.tree_util.tree_map_with_path(hold, params)


def layer_norm(x, scale, bias, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    out = (x32 - mu) * lax.rsqrt(var + eps) * scale + bias
    return out.astype(x.dtype)


def rms_norm(x, scale, eps, axes=-1):
    """RMSNorm over ``axes`` (scale only, no mean taken off), in float32."""
    x32 = x.astype(jnp.float32)
    out = x32 * lax.rsqrt(jnp.mean(jnp.square(x32), axis=axes, keepdims=True) + eps) * scale
    return out.astype(x.dtype)


def norm(cfg: "TransformerConfig", x, p, name: str):
    """The normalisation called ``name`` (ln1, ln2, lnf, emb_ln) of the
    parameter dict ``p``, of the kind the model has."""
    if cfg.norm_kind == "rms":
        return rms_norm(x, p[name + "_scale"], cfg.layernorm_epsilon)
    return layer_norm(x, p[name + "_scale"], p[name + "_bias"], cfg.layernorm_epsilon)


def yarn_ramp(spec: dict, rotary_dims: int) -> np.ndarray:
    """YaRN's blend r_i [rotary_dims // 2] float32 for a spec of ``rotary_by_kind``: 0
    where dimension pair i keeps its frequency, 1 where it is divided by ``factor``.
    With d(n) = rotary_dims ln(original_max_position_embeddings / (2 pi n)) / (2 ln
    base), the pair that turns n times over the original context: low = d(beta_fast),
    high = d(beta_slow), floored and ceiled where ``truncate`` (the published default),
    clipped to 0 .. rotary_dims - 1; r_i = clip((i - low) / (high - low), 0, 1)."""
    base, span = float(spec["base"]), float(spec["original_max_position_embeddings"])
    turns = lambda n: rotary_dims * math.log(span / (n * 2 * math.pi)) / (2 * math.log(base))
    low, high = turns(float(spec.get("beta_fast", 32))), turns(float(spec.get("beta_slow", 1)))
    if spec.get("truncate", True):
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, rotary_dims - 1)
    if low == high:
        high += 0.001  # the published guard against a ramp of no width
    i = np.arange(rotary_dims // 2, dtype=np.float32)
    return np.clip((i - np.float32(low)) / np.float32(high - low), 0, 1).astype(np.float32)


def yarn_attention_factor(spec: dict) -> float:
    """What a YaRN spec multiplies cos and sin by: ``attention_factor`` as stated, or
    0.1 ln(factor) + 1 (1 for a factor <= 1)."""
    stated = spec.get("attention_factor")
    if stated is not None:
        return float(stated)
    factor = float(spec["factor"])
    return 0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0


def rotary_table(spec: dict, rotary_dims: int):
    """A rotary spec (``TransformerConfig.rotary_spec``) -> (inv_freq [rotary_dims //
    2] float32, the constant cos and sin are multiplied by): THE place a frequency is
    computed. Plain: inv_freq_i = base^(-2i / rotary_dims), and 1. YaRN: (1 - r_i) x
    that + r_i x that / ``factor`` (``yarn_ramp``), and ``yarn_attention_factor``."""
    half = rotary_dims // 2
    plain = jnp.exp(-math.log(float(spec["base"])) * jnp.arange(0, half, dtype=jnp.float32) / half)
    if spec.get("type", "plain") == "plain":
        return plain, 1.0
    r = jnp.asarray(yarn_ramp(spec, rotary_dims))
    return plain / float(spec["factor"]) * r + plain * (1 - r), yarn_attention_factor(spec)


def rotary_tables(cfg: "TransformerConfig"):
    """The tables of a model whose layer kinds state their rotary (``rotary_by_kind``),
    built ONCE a forward pass (``_layer_loop``): (inv_freq [2, half], factor [2]), row 0
    the whole-context kind's and row 1 the window kind's; a block takes its kind's row,
    at a Python index where the loop knows the kind and at a traced one where it scans
    layers of both. None for a model that states one rotary for all: ``rotary_embed``
    then computes ``rotary_base``'s frequencies where it always has."""
    if cfg.rotary_by_kind is None:
        return None
    rd = int(cfg.head_dim * cfg.rotary_pct)
    freqs, factors = zip(*(rotary_table(cfg.rotary_spec(window), rd) for window in (False, True)))
    return jnp.stack(freqs), jnp.asarray(factors, jnp.float32)


def rotary_kinds_fact(cfg: "TransformerConfig"):
    """What the engine's build span says of the program's rotary: None for a model
    without, ``"plain(<base>)"`` where every rotated layer turns by ``rotary_base``, and
    with ``rotary_by_kind`` each kind's own, ``"whole=yarn(<base>, x<factor>, <original
    context>) window=plain(<base>)"``."""
    if cfg.pos_emb != "rotary":
        return None

    def fact(spec):
        if spec.get("type", "plain") == "plain":
            return f"plain({float(spec['base']):g})"
        return (f"yarn({float(spec['base']):g}, x{float(spec['factor']):g}, "
                f"{int(spec['original_max_position_embeddings'])})")

    if cfg.rotary_by_kind is None:
        return fact({"base": cfg.rotary_base})
    kinds = [("whole", False)] + ([("window", True)] if cfg.window_layers else [])
    return " ".join(f"{name}={fact(cfg.rotary_spec(window))}" for name, window in kinds)


def rotary_embed(x, positions, rotary_dims, interleaved: bool = False, base: float = 10000.0,
                 table=None):
    """Apply rotary position embedding to the first ``rotary_dims`` of x
    [B, S, H, Dh] (reference inference kernel: apply_rotary_pos_emb,
    csrc/transformer/inference/csrc/pt_binding.cpp:1268). ``interleaved``
    selects GPT-J's rotate-every-two pairing ((x0,x1),(x2,x3),...) instead of
    the NeoX half-split ((x0,x_half),...). ``table`` (a layer kind's row of
    ``rotary_tables``): (inv_freq [rotary_dims // 2], the factor on cos and sin)
    in the place of ``base``'s plain frequencies."""
    rd = rotary_dims
    x_rot, x_pass = x[..., :rd], x[..., rd:]
    half = rd // 2
    freqs, factor = rotary_table({"base": base}, rd) if table is None else table
    angles = positions[:, :, None].astype(jnp.float32) * freqs[None, None, :]  # [B,S,half]
    # YaRN's attention factor (1 for a plain kind) multiplies in float32
    scaled = (lambda t: t) if table is None else (lambda t: t * factor)
    cos = scaled(jnp.cos(angles)[:, :, None, :]).astype(x.dtype)
    sin = scaled(jnp.sin(angles)[:, :, None, :]).astype(x.dtype)
    if interleaved:
        x1, x2 = x_rot[..., 0::2], x_rot[..., 1::2]
        r1 = x1 * cos - x2 * sin
        r2 = x2 * cos + x1 * sin
        rotated = jnp.stack([r1, r2], axis=-1).reshape(x_rot.shape)
    else:
        x1, x2 = x_rot[..., :half], x_rot[..., half:]
        rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([rotated, x_pass], axis=-1)


def alibi_slopes(num_heads: int) -> jnp.ndarray:
    """BLOOM alibi slopes (reference builds these for the BLOOM policy path)."""
    closest = 2 ** math.floor(math.log2(num_heads))
    base = jnp.asarray([2 ** (-8.0 * (i + 1) / closest) for i in range(closest)])
    if closest < num_heads:
        extra = jnp.asarray(
            [2 ** (-4.0 * (i + 1) / closest) for i in range(num_heads - closest)]
        )
        base = jnp.concatenate([base, extra])
    return base


def block_visible(q_pos, block: int):
    """The last key position a query at ``q_pos`` sees under the mask that is causal
    between blocks of ``block`` positions (``attn_block_length``): the last position of
    its own block. The query's own position where ``block`` is 1 (nothing is traced)."""
    return q_pos if block == 1 else q_pos - q_pos % block + (block - 1)


def xla_attention(q, k, v, *, causal_offset=0, bias=None, causal=True, dtype=jnp.float32,
                  block: int = 1):
    """Plain einsum attention [B,S,H,Dh] — the baseline the Pallas flash
    kernel is validated against (mirrors tests vs vendored BERT in the
    reference's test_cuda_forward.py strategy). ``causal=False`` gives the
    bidirectional encoder form (BERT). ``causal_offset`` may be a scalar or a
    per-row [B] vector — continuous batching decodes every cache slot at its
    own absolute position. ``block`` > 1: causal between blocks of that many
    positions (``block_visible``). v's heads may be another width than q's and k's.
    k and v may have FEWER heads than q (grouped-query attention): query head i
    attends key / value head i // (H // Hkv), the group contracted against its
    one K/V head where it lies, never against repeated copies. That contraction's
    batch dimensions are (row, K/V head): right for a block's own K/V and for a
    ring of 128 positions; over layer l of a cache stack ``Smax`` long the
    compiler slices the layer out first (and re-lays narrow heads head-major),
    which is why a step that reads a cache of rows takes ``_rows_attention``."""
    B, Sq, H, Dh = q.shape
    Hkv = k.shape[2]
    if Hkv == H:
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / math.sqrt(Dh)
        probs = _masked_softmax(scores, causal_offset, bias, causal, block).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    qg = q.reshape(B, Sq, Hkv, H // Hkv, Dh)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32) / math.sqrt(Dh)
    scores = scores.reshape(B, H, Sq, k.shape[1])  # the mask and a bias are per query head
    probs = _masked_softmax(scores, causal_offset, bias, causal, block).astype(q.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.reshape(B, Hkv, H // Hkv, Sq, k.shape[1]), v)
    return out.reshape(B, Sq, H, v.shape[-1])


def _masked_softmax(scores, causal_offset=0, bias=None, causal=True, block: int = 1):
    """float32 scores [B, H, Sq, Sk] -> probabilities, under ``xla_attention``'s
    additive bias and causal mask (``causal_offset``: scalar or [B]; ``block``:
    ``block_visible``)."""
    Sq, Sk = scores.shape[-2:]
    if bias is not None:
        scores = scores + bias
    if causal:
        off = jnp.asarray(causal_offset)
        if off.ndim == 0:
            q_pos = block_visible(jnp.arange(Sq)[:, None] + off, block)
            k_pos = jnp.arange(Sk)[None, :]
            mask = q_pos >= k_pos  # [Sq, Sk]
            scores = jnp.where(mask[None, None], scores, jnp.finfo(jnp.float32).min)
        else:
            q_pos = block_visible(off[:, None, None] + jnp.arange(Sq)[None, :, None], block)
            k_pos = jnp.arange(Sk)[None, None, :]
            mask = q_pos >= k_pos  # [B, Sq, Sk]
            scores = jnp.where(mask[:, None], scores, jnp.finfo(jnp.float32).min)
    return jax.nn.softmax(scores, axis=-1)


def _latent_expand(cfg: "TransformerConfig", lp, k_pe, c):
    """Latent attention's EXPANDED form: the rotary key k_pe [B, S, 1, Dr] and
    the normed latent c [B, S, 1, R] of a block -> its per-head keys
    [B, S, H, Dn + Dr] (``k_nope`` from the latent, then the key every head
    shares) and values [B, S, H, Dv]; plain attention follows."""
    Dn = cfg.head_dim - cfg.qk_rope_head_dim
    kv = jnp.einsum("bsr,rhk->bshk", c[:, :, 0], lp["wkv_b"].astype(c.dtype))
    k_pe = jnp.broadcast_to(k_pe, kv.shape[:3] + k_pe.shape[3:])
    return jnp.concatenate([kv[..., :Dn], k_pe], axis=-1), kv[..., Dn:]


def _latent_attention(cfg: "TransformerConfig", lp, q, k_pe, c, pos):
    """Latent attention's ABSORBED form, for a step that reads the cache:
    q [B, T, H, Dn + Dr] against the cached rotary keys k_pe [B, Smax, 1, Dr]
    and latents c [B, Smax, 1, R] whose valid rows are [0, pos + T) (``pos``
    scalar or [B]). With ``wkv_b`` split a head into W_uk, W_uv [R, D]:
    score_h(s) = ((q_nope_h W_uk_h^T) c_s + q_pe_h k_pe_s) / sqrt(Dn + Dr) and
    out_h = (sum_s p_s c_s) W_uv_h, equal to the expanded form in exact
    arithmetic; the cache is read as it lies and never expanded to heads."""
    Dn = cfg.head_dim - cfg.qk_rope_head_dim
    w = lp["wkv_b"].astype(q.dtype)
    k_pe, c = k_pe[:, :, 0], c[:, :, 0]
    q_lat = jnp.einsum("bthn,rhn->bthr", q[..., :Dn], w[..., :Dn])
    scores = (jnp.einsum("bthr,bsr->bhts", q_lat, c).astype(jnp.float32)
              + jnp.einsum("bthp,bsp->bhts", q[..., Dn:], k_pe).astype(jnp.float32))
    probs = _masked_softmax(scores / math.sqrt(cfg.head_dim), pos).astype(q.dtype)
    out_lat = jnp.einsum("bhts,bsr->bthr", probs, c)
    return jnp.einsum("bthr,rhv->bthv", out_lat, w[..., Dn:])


def _rows_attention(q, k_rows, v_rows, pos, bias=None, block: int = 1):
    """Grouped-query attention of a short block q [B, T, H, Dh] over a cache layer
    that holds a token's K/V heads side by side as ONE row (``cache_heads_merged``):
    k_rows [B, Smax, Hkv x Dh], v_rows [B, Smax, Hkv x Dv], valid rows
    [0, pos + T) (``pos`` scalar or [B]). The row is contracted WHOLE, the cache's
    row index the only batch dimension, as ``_latent_attention`` contracts its
    latent: query head h is laid into block h // (H // Hkv) of a row of zeros, so
    ``q_rows . k_row`` is q_h . k_{its head} plus exact zeros in the float32
    accumulation, the softmax is per query head as in ``xla_attention``, and block
    h // (H // Hkv) of ``probs . v_row`` is the head's output: the same products,
    the same sums, the same rounding points. It multiplies Hkv x what the grouped
    form does (H x T operations a byte of K/V read: ``ROWS_OPS_PER_BYTE``) and the
    compiler reads layer l of the stack inside the contraction. The grouped form's
    batch dimensions (row, K/V head) are not the stack's leading ones: for it the
    compiler slices the layer out of the stack and re-lays it head-major, four
    passes over a layer's K and V where the step needs one (LFM2, 128 slots x
    3,072: 9.8 ms of a 26.7 ms step, PERF.md §6 PR 45)."""
    B, T, H, Dh = q.shape
    Smax, Hkv = k_rows.shape[1], k_rows.shape[2] // Dh
    # [H, 1, Hkv, 1]: whether block j of a row is query head h's K/V head
    own = (jnp.arange(H)[:, None] // (H // Hkv) == jnp.arange(Hkv))[:, None, :, None]
    q_rows = jnp.where(own, q.transpose(0, 2, 1, 3)[:, :, :, None, :], 0)  # [B, H, T, Hkv, Dh]
    scores = jnp.einsum("bmr,bsr->bms", q_rows.reshape(B, H * T, Hkv * Dh), k_rows)
    scores = scores.astype(jnp.float32).reshape(B, H, T, Smax) / math.sqrt(Dh)
    probs = _masked_softmax(scores, pos, bias, block=block).astype(q.dtype)
    out_rows = jnp.einsum("bms,bsr->bmr", probs.reshape(B, H * T, Smax), v_rows)
    out = jnp.where(own, out_rows.reshape(B, H, T, Hkv, -1), 0)  # one block is the head's
    return jnp.sum(out, axis=3).transpose(0, 2, 1, 3)


def _param_streamer(cfg: TransformerConfig):
    """Per-layer host→device streaming hook for the scan bodies (identity
    when param_offload is off). See runtime/zero/param_offload.py."""
    if not cfg.param_offload:
        return lambda t: t
    from ..runtime.zero.param_offload import stream_to_device

    return stream_to_device


# Per-layer host slicing is worth it (and DMA-legal) only for the big matmul
# stacks; leaves below this slice size are streamed whole at entry instead —
# the role the reference's param_persistence_threshold plays
# (stage3.py: small params stay resident), and it also keeps XLA's async
# host dynamic-slice emitter away from sub-sublane slices it cannot tile.
_PER_LAYER_STREAM_MIN_BYTES = 1 << 18


def _per_layer_streamable(stacked) -> bool:
    if getattr(stacked, "ndim", 0) < 3:
        return False
    import numpy as _np

    elems = int(_np.prod(stacked.shape[1:]))
    return elems * stacked.dtype.itemsize >= _PER_LAYER_STREAM_MIN_BYTES


def _make_stack_loader(cfg: TransformerConfig, tree):
    """(xs, load) for a stacked parameter tree under param_offload.

    Big matmul stacks stay host-resident in ``xs``; ``load`` streams their
    slices inside the scan body. Small stacks are streamed WHOLE at entry
    (device-resident in ``xs``) and ``load`` passes them through untouched —
    re-streaming an already-device slice would pin its tiny per-layer
    cotangent to host inside the loop, which XLA's async host-DMA emitter
    cannot tile (sub-sublane slices) and the per-slice transfers would be
    wasteful anyway. Identity when param_offload is off."""
    if not cfg.param_offload:
        return tree, lambda t: t
    from ..runtime.zero.param_offload import stream_to_device

    big = jax.tree.map(_per_layer_streamable, tree)
    xs = jax.tree.map(lambda v, b: v if b else stream_to_device(v), tree, big)

    def load(sliced):
        return jax.tree.map(lambda v, b: stream_to_device(v) if b else v, sliced, big)

    return xs, load


def _stream_top_level(cfg: TransformerConfig, params: Params) -> Params:
    """Stream the non-stacked leaves (embeddings, final LN, head) to device
    once at entry; ``layers``/``moe`` stacks stay host-resident for the scan
    bodies to stream slice-by-slice. No-op when param_offload is off."""
    if not cfg.param_offload:
        return params
    from ..runtime.zero.param_offload import stream_to_device

    out = dict(params)
    for k, v in params.items():
        if k not in ("layers", "moe"):
            out[k] = stream_to_device(v)
    return out


_SAVED_NAMES = {"save_flash": ("flash_out", "flash_lse", "xent_lse"),
                "nothing_saveable": ()}
# checkpoint names of a dense feed-forward's pre-activation (``_ffn``): the up
# projection after its bias, and a gated form's gate product
FFN_NAMES = ("ffn_up", "ffn_gate")
# Names that ``save_flash`` saves beside its own while a training engine traces
# its step (``remat_also_saving``): never a user's to set, and the tracing thread's own
_ALSO_SAVED: contextvars.ContextVar = contextvars.ContextVar("remat_also_saved", default=())


@contextlib.contextmanager
def remat_also_saving(names):
    """While a step is TRACED inside this scope, ``_remat_wrapper`` of a model whose
    policy is ``save_flash`` saves ``names`` too (names ``remat_candidates`` offers:
    a matmul's output that the backward pass then reads instead of recomputing).
    The training engine enters it with what ``runtime/remat_plan.plan_saved``
    found room for; any other policy is an explicit choice and is left alone."""
    token = _ALSO_SAVED.set(tuple(names))
    try:
        yield
    finally:
        _ALSO_SAVED.reset(token)


def _named(x, name: str):
    """``x`` under its checkpoint name while a step that saves it is traced
    (``remat_also_saving``), and ``x`` itself otherwise: every other program, a
    serving one among them, is what it was to the symbol."""
    return checkpoint_name(x, name) if name in _ALSO_SAVED.get() else x


def remat_candidates(cfg: TransformerConfig) -> tuple:
    """What a ``save_flash`` checkpoint of ``cfg``'s layers could keep beside its
    floor, for ``plan_saved``: (floor, names, values). ``floor`` is the values a
    token that the floor policy saves over all layers: each layer's input and
    ``flash_out`` (``flash_lse`` is one float a head), the latter [heads, rows,
    head width] on the device, whose tiles are 128 lanes wide. ``names`` are the
    one candidate's checkpoint names and ``values`` what they hold a token over
    all layers (0: nothing to offer): the dense feed-forwards' pre-activations
    (``FFN_NAMES``; a gated form has two), which remove 2 x hidden_size
    operations a saved value from the backward pass. A
    routed layer's experts are not offered. Nor are q, k and v: kept as the
    flash kernel's residuals they took the three projections out of the
    backward pass and left the step as long as it was, because the rotary's and
    the relayout's backward, fused into the recomputed products before, ran as
    passes of their own (PERF.md section 6, PR 50)."""
    L, lead = cfg.num_layers, cfg.moe_first_dense
    flash = cfg.num_layers - len(cfg.stateful_layers) if cfg.attn_impl == "flash" else 0
    floor = L * cfg.hidden_size + flash * cfg.num_heads * (-(-cfg.value_head_dim // 128) * 128)
    routed = (L - lead) // cfg.moe_every if cfg.moe_every > 0 else 0
    gated = cfg.activation == "swiglu"
    ffn = 2 * lead * cfg.dense_ffn_size + (L - lead - routed) * cfg.ffn_size * (1 + gated)
    # a checkpoint is one (pass, layer)'s: what the layers keep, ``layer_passes`` times
    return cfg.layer_passes * floor, FFN_NAMES[:1 + gated], cfg.layer_passes * ffn


def step_working_bytes(cfg: TransformerConfig, sequences: int, tokens: int) -> int:
    """Bytes of a training step's temporaries on one device, beside the state,
    the gradients and what the checkpoints save: the room ``plan_saved`` leaves
    them. From shapes, as the compiled floor program of the ZeRO-3 step showed
    them at seventeen shapes (``experiments/remat_fit.py``; PERF.md section 6,
    PR 50: within - 2 and + 9% of ``memory_analysis()`` there, before the
    planner's headroom):

    - weights gathered for use: two layers' (this one's and the next, fetched
      ahead) and the vocabulary's matrix;
    - the larger of the two moments a step's memory peaks at: the loss over one
      chunk of logits, held in float32 and in the compute dtype (every
      ``sequences`` row x ``loss_chunk_size`` positions x the vocabulary; all
      positions where the loss is not chunked), or one layer's backward pass,
      which holds about two rows as wide as the feed-forward and ten as wide
      as the residual for each of the device's ``tokens``.

    ``layer_passes`` changes none of these: a pass gathers the same two layers and
    its backward pass is one layer's; what the passes multiply is what the
    checkpoints keep (``remat_candidates``)."""
    item = jnp.dtype(cfg.dtype).itemsize
    d, f = cfg.hidden_size, max(cfg.ffn_size, cfg.dense_ffn_size)
    layer_weights = d * (cfg.num_heads + 2 * cfg.kv_heads) * cfg.head_dim + d * d + (
        2 + (cfg.activation == "swiglu")) * d * f
    gathered = (2 * layer_weights + cfg.vocab_size * d) * item
    chunk, S = cfg.loss_chunk_size, tokens // max(sequences, 1)
    rows = sequences * chunk if 0 < chunk < S and S % chunk == 0 else tokens
    loss = rows * cfg.vocab_size * (4 + item)
    layer = tokens * (2 * f + 10 * d) * item
    return gathered + max(loss, layer)


def _remat_policy(name: str, offload: bool = False, also: tuple = ()):
    """Resolve a remat-policy name (TransformerConfig.remat_policy).

    ``also``: names ``save_flash`` saves beside its own (``remat_also_saving``).
    ``offload=True`` (cpu_checkpointing): the tagged ``layer_in`` boundary
    residual is saved to pinned host memory instead of HBM — the reference
    moves the saved input to CPU at checkpoint:493/:480; here XLA schedules
    the d2h/h2d copies asynchronously around the recompute."""
    cp = jax.checkpoint_policies
    also = tuple(also) if name == "save_flash" else ()
    if offload:
        saved = _SAVED_NAMES.get(name)
        if saved is None:
            raise ValueError(
                f"cpu_checkpointing composes with named-residual remat policies "
                f"{sorted(_SAVED_NAMES)}, not {name!r}")
        return cp.save_and_offload_only_these_names(
            names_which_can_be_saved=list(saved + also),
            names_which_can_be_offloaded=["layer_in"],
            offload_src="device",
            offload_dst="pinned_host",
        )
    # xent_lse: the fused loss kernel's residual (ops/pallas/fused_xent.py) —
    # saved so a remat region spanning the loss never re-runs its forward
    flash_names = cp.save_only_these_names(*_SAVED_NAMES["save_flash"], *also)
    if name == "save_flash":
        return flash_names
    if name == "dots_and_flash":
        return cp.save_from_both_policies(cp.dots_saveable, flash_names)
    return getattr(cp, name, None)


def _boundary_tagger(cfg: TransformerConfig):
    """Per-layer boundary treatment for activation checkpointing.

    Tags the residual-stream carry as ``layer_in`` (so offload policies can
    target it) and, under partition_activations, stores the saved copy sharded
    over ``remat_partition_axis`` — the reference slices the saved input
    across TP ranks (checkpointing.py:367) and all-gathers on recompute; the
    sharding-constraint pair expresses the same trade to XLA."""
    from jax.ad_checkpoint import checkpoint_name

    axis = cfg.remat_partition_axis
    needs_tag = cfg.remat and (cfg.remat_offload or bool(axis))
    if not needs_tag:
        return lambda x: x
    U = jax.sharding.PartitionSpec.UNCONSTRAINED

    def tag(x):
        mesh = _ACTIVE_MESH[0]
        use_axis = (
            axis
            and mesh is not None
            and mesh.shape.get(axis, 1) > 1
            and x.ndim == 3
            and x.shape[1] % mesh.shape[axis] == 0
        )
        if use_axis:
            x = jax.lax.with_sharding_constraint(
                x, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(U, axis, U)))
        x = checkpoint_name(x, "layer_in")
        if use_axis:
            x = jax.lax.with_sharding_constraint(
                x, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(U, None, U)))
        return x

    return tag


def _attention_dispatch(cfg: TransformerConfig):
    if cfg.attn_impl == "flash":
        from ..ops.pallas.flash_attention import flash_attention_sharded

        bq = cfg.flash_block_q or None
        bk = cfg.flash_block_k or None
        slopes = alibi_slopes(cfg.num_heads) if cfg.pos_emb == "alibi" else None

        def flash_fn(q, k, v, bias, window=None):
            if bias is not None:
                # general dense bias (not expressible as alibi/window)
                return xla_attention(q, k, v, bias=bias, causal=cfg.causal)
            return flash_attention_sharded(
                q, k, v, mesh=_ACTIVE_MESH[0], causal=cfg.causal, block_q=bq,
                block_k=bk, alibi_slopes=slopes, window=window,
                mask_block=cfg.attn_block_length,
            )

        # alibi and local windows are fused IN-KERNEL (computed from block
        # positions; no [S,S] bias tensor) — the layer body passes the raw
        # window instead of materializing a dense bias
        flash_fn.handles_fused_bias = True
        return flash_fn
    if cfg.attn_impl == "ring":
        from ..parallel.ring_attention import ring_attention_sharded

        return lambda q, k, v, bias: ring_attention_sharded(q, k, v, mesh=_ACTIVE_MESH[0])
    if cfg.attn_impl == "ulysses":
        from ..parallel.ulysses import ulysses_attention_sharded

        # additive bias (alibi/local windows) is not plumbed through the
        # all-to-all re-sharding — those layers take the dense XLA path,
        # mirroring the flash dispatch above
        return lambda q, k, v, bias: (
            ulysses_attention_sharded(q, k, v, mesh=_ACTIVE_MESH[0], causal=cfg.causal)
            if bias is None
            else xla_attention(q, k, v, bias=bias, causal=cfg.causal)
        )
    if cfg.attn_impl == "sparse":
        from ..ops.sparse_attention import SPARSITY_CONFIGS, sparse_flash_attention

        sp = dict(cfg.sparsity or {})
        mode = sp.pop("mode", "fixed")
        sp.setdefault("num_heads", cfg.num_heads)
        sparsity_cfg = SPARSITY_CONFIGS[mode](**sp)

        def sparse_fn(q, k, v, bias):
            if bias is not None:
                return xla_attention(q, k, v, bias=bias, causal=cfg.causal)  # alibi unfused
            layout = sparsity_cfg.make_layout(q.shape[1])
            return sparse_flash_attention(q, k, v, layout, causal=cfg.causal)

        return sparse_fn
    return lambda q, k, v, bias: xla_attention(q, k, v, bias=bias, causal=cfg.causal,
                                               block=cfg.attn_block_length)


def _times(x, m: float):
    """x scaled by a stated multiplier; x itself where it is 1 (no operation traced)."""
    return x if m == 1.0 else x * m


def _act_q(cfg, x):
    """Activation fake-quant at linear-projection inputs (compression's
    activation_quantization group; reference QuantAct basic_layer.py:12)."""
    if not cfg.act_quant_bits:
        return x
    from ..ops.quantization import fake_quant_act

    return fake_quant_act(x, cfg.act_quant_bits, cfg.act_quant_symmetric)


def _ffn(cfg, lp, h):
    # named_scope feeds the flops profiler's per-module tree (profiling/
    # flops_profiler: reference print_model_profile parity)
    with jax.named_scope("ffn"):
        h = _act_q(cfg, h)
        u = jnp.einsum("bsd,df->bsf", h, lp["wi"].astype(h.dtype))
        if cfg.use_bias:
            u = u + lp["bi"].astype(h.dtype)
        # the pre-activation as the backward pass reads it: a checkpoint that
        # saves FFN_NAMES recomputes no up projection (``remat_candidates``)
        u = _named(u, FFN_NAMES[0])
        gate_m, down_m = cfg.multiplier("mlp_multipliers")
        if cfg.activation == "swiglu":  # a gated feed-forward has no biases
            gate = jnp.einsum("bsd,df->bsf", h, lp["wg"].astype(h.dtype))
            gate = _named(gate, FFN_NAMES[1])
            u = jax.nn.silu(_times(gate, gate_m)) * u
        elif cfg.activation == "relu":
            u = jax.nn.relu(u)
        elif cfg.activation == "gelu_exact":
            u = jax.nn.gelu(u, approximate=False)
        else:
            u = jax.nn.gelu(u, approximate=True)
        u = _act_q(cfg, u)
        out = jnp.einsum("bsf,fd->bsd", u, lp["wo_mlp"].astype(h.dtype))
        if cfg.use_bias:
            out = out + lp["bo_mlp"].astype(h.dtype)
        return _times(out, down_m)


def _dense_ffn(cfg, lp, h):
    """``_ffn`` in the form the block takes a feed-forward: (out, aux loss, experts chosen)."""
    return _ffn(cfg, lp, h), jnp.zeros((), jnp.float32), None


def _qkv_proj(cfg: TransformerConfig, lp, h, positions, rotary=True, table=None):
    """LN'd hidden states -> rotary-embedded q, k, v [B, T, H, Dh]. Latent
    attention gives what it caches in the place of k and v: the rotary key
    every head shares [B, T, 1, Dr] and the normed latent [B, T, 1, R]; the
    block's ``attend`` expands or absorbs them (``_latent_expand`` /
    ``_latent_attention``). ``rotary`` (``rotary_layers``): whether THIS layer
    rotates, a Python bool where the loop knows the layer's kind and a traced
    one where it scans layers of both (the rotated pair is then selected). With
    ``attn_output_gate`` a fourth value comes last: the second half of every
    head's query projection [B, T, H, Dh], which ``_attn_out_proj`` gates the
    heads' output by. ``table`` (``rotary_by_kind``): this layer's kind's row of
    ``rotary_tables``, which ``rotary_embed`` turns by in ``rotary_base``'s place."""
    with jax.named_scope("attn"):
        h = _act_q(cfg, h)
        h = _times(h, cfg.multiplier("attention_in_multiplier"))
        q = jnp.einsum("bsd,dhk->bshk", h, lp["wq"].astype(h.dtype))
        gate = ()
        if cfg.attn_output_gate:  # query | gate, a head at a time
            q, gate = q[..., :cfg.head_dim], (q[..., cfg.head_dim:],)
        if cfg.kv_lora_rank:
            R, Dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
            rope = partial(rotary_embed, positions=positions, rotary_dims=Dr,
                           interleaved=cfg.rotary_interleaved, base=cfg.rotary_base)
            kv = jnp.einsum("bsd,dr->bsr", h, lp["wkv_a"].astype(h.dtype))
            c = rms_norm(kv[..., :R], lp["kv_norm_scale"], cfg.layernorm_epsilon)
            q = jnp.concatenate([q[..., :-Dr], rope(q[..., -Dr:])], axis=-1)
            return q, rope(kv[:, :, None, R:]), c[:, :, None]
        k = jnp.einsum("bsd,dhk->bshk", h, lp["wk"].astype(h.dtype))  # kv_heads of them
        v = jnp.einsum("bsd,dhk->bshk", h, lp["wv"].astype(h.dtype))
        k = _times(k, cfg.multiplier("key_multiplier"))  # before the rotary
        if cfg.use_bias:
            q = q + lp["bq"].astype(h.dtype)
            k = k + lp["bk"].astype(h.dtype)
            v = v + lp["bv"].astype(h.dtype)
        if cfg.qk_norm == "head":  # every head by itself, one [head_dim] scale for all
            q = rms_norm(q, lp["q_norm_scale"], cfg.layernorm_epsilon)
            k = rms_norm(k, lp["k_norm_scale"], cfg.layernorm_epsilon)
        elif cfg.qk_norm:  # over the whole projection: heads and head dimension together
            q = rms_norm(q, lp["q_norm_scale"], cfg.layernorm_epsilon, axes=(-2, -1))
            k = rms_norm(k, lp["k_norm_scale"], cfg.layernorm_epsilon, axes=(-2, -1))
        if cfg.pos_emb == "rotary" and rotary is not False:
            rd = int(cfg.head_dim * cfg.rotary_pct)
            turned = [rotary_embed(x, positions, rd, cfg.rotary_interleaved, cfg.rotary_base,
                                   table) for x in (q, k)]
            q, k = turned if rotary is True else [jnp.where(rotary, t, x)
                                                  for t, x in zip(turned, (q, k))]
        return (q, k, v, *gate)


def _attn_out_proj(cfg: TransformerConfig, lp, attn_out, gate=None):
    """The heads' output [B, T, H, Dv] through ``wo``; ``gate`` (``_qkv_proj``'s, with
    ``attn_output_gate``): each head's output times sigmoid(its gate) first."""
    with jax.named_scope("attn"):
        if gate is not None:
            attn_out = attn_out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(attn_out.dtype)
        attn_out = _act_q(cfg, attn_out)
        out = jnp.einsum("bshk,hkd->bsd", attn_out, lp["wo"].astype(attn_out.dtype))
        if cfg.use_bias:
            out = out + lp["bo"].astype(attn_out.dtype)
        return _times(out, cfg.multiplier("attention_out_multiplier"))


# The cache tree's subtree of per-SEQUENCE leaves ([L, B, ...], no position
# axis: overwritten whole by every step); every leaf of the tree outside it and
# outside ``RING`` is per-TOKEN ([L, B, Smax, heads, width]). ``cache_layout``
# builds the kinds and the helpers below it tell them apart by these keys alone.
STATE = "state"
# The subtree of RINGS ([L_window, B, R, heads, width]: a window layer's last R
# positions, position p at index p mod R), moved whole like state.
RING = "ring"


def _ssm_scan(x, dt, A, Bm, Cm, S0, chunk: int):
    """The selective scan S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t, y_t = S_t C_t
    over many rows, in its chunked form: x [B, T, H, P], dt [B, T, H] float32
    (0 on a row that must not move the state), A [H] float32 (< 0), Bm / Cm
    [B, T, G, N] (head h reads group h // (H // G); never repeated to heads), S0
    [B, H, P, N] float32 -> (y [B, T, H, P] float32, S_T). T is padded to whole
    chunks with dt = 0. Within a chunk of Q rows the pairs are a [Q, Q] matrix a
    head (decay x C.B, as attention's scores are); between chunks one state a
    chunk is carried. Nothing of size rows x rows x heads x N is formed: the
    largest temporaries are [chunks, heads, Q, Q] and [chunks, heads, P, N].
    The decays, their cumulative sums and the states are float32; the four
    contractions take their operands in x's dtype and accumulate in float32."""
    f32 = jnp.float32
    B_, T, H, P = x.shape
    G, N = Bm.shape[2:]
    Hg, Q = H // G, chunk
    pad = (-T) % Q
    if pad:
        x, dt, Bm, Cm = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                         for a in (x, dt, Bm, Cm))
    nc = (T + pad) // Q
    xc = x.reshape(B_, nc, Q, G, Hg, P)
    dtc = dt.reshape(B_, nc, Q, G, Hg)
    Bc, Cc = Bm.reshape(B_, nc, Q, G, N), Cm.reshape(B_, nc, Q, G, N)
    cum = jnp.cumsum(dtc * A.reshape(G, Hg), axis=2)  # [B, nc, Q, G, Hg], falling from 0
    # within a chunk: y_i += sum_{j <= i} exp(cum_i - cum_j) dt_j (C_i . B_j) x_j
    cum_t = jnp.moveaxis(cum, 2, -1)  # [B, nc, G, Hg, Q]
    causal = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]
    decay = jnp.exp(jnp.where(causal, cum_t[..., :, None] - cum_t[..., None, :], -jnp.inf))
    cb = jnp.einsum("bcign,bcjgn->bcgij", Cc, Bc, preferred_element_type=f32)
    pairs = cb[:, :, :, None] * decay * jnp.moveaxis(dtc, 2, -1)[..., None, :]
    y = jnp.einsum("bcghij,bcjghp->bcighp", pairs.astype(x.dtype), xc,
                   preferred_element_type=f32)
    # a chunk's own contribution to the state at its end, and its whole decay
    to_end = jnp.exp(cum[:, :, -1:] - cum) * dtc  # [B, nc, Q, G, Hg]
    local = jnp.einsum("bcjghp,bcjgn->cbghpn", (xc * to_end[..., None]).astype(x.dtype), Bc,
                       preferred_element_type=f32)
    whole = jnp.moveaxis(jnp.exp(cum[:, :, -1]), 1, 0)  # [nc, B, G, Hg]

    def carry_state(S, chunk_):
        own, dec = chunk_
        return dec[..., None, None] * S + own, S  # the state ENTERING the chunk is what it reads

    S_T, entering = lax.scan(carry_state, S0.reshape(B_, G, Hg, P, N), (local, whole))
    y = y + jnp.einsum("bcign,cbghpn->bcighp", Cc, entering.astype(x.dtype),
                       preferred_element_type=f32) * jnp.exp(cum)[..., None]
    return y.reshape(B_, nc * Q, H, P)[:, :T], S_T.reshape(B_, H, P, N)


def _ssm_step(x, dt, A, Bm, Cm, S0):
    """``_ssm_scan`` for ONE row a sequence (a decode step): the recurrence as it
    is written, one update of the state and one read, elementwise in float32 (the
    state is read once and written once; no contraction rounds it)."""
    f32 = jnp.float32
    B_, _, H, P = x.shape
    G, N = Bm.shape[2:]
    Hg = H // G
    dt = dt[:, 0].reshape(B_, G, Hg, 1, 1)
    Bg, Cg = (m[:, 0].astype(f32)[:, :, None, None, :] for m in (Bm, Cm))  # [B, G, 1, 1, N]
    S = (jnp.exp(dt * A.reshape(G, Hg, 1, 1)) * S0.reshape(B_, G, Hg, P, N)
         + dt * x[:, 0].astype(f32).reshape(B_, G, Hg, P, 1) * Bg)
    return jnp.sum(S * Cg, axis=-1).reshape(B_, 1, H, P), S.reshape(B_, H, P, N)


def _causal_filter(tail, u, taps):
    """The depthwise causal convolution the state-space mixer and the gated short
    convolution share: u [B, T, C] behind ``tail`` [B, K - 1, C] (the K - 1 rows
    that came before it: zeros where the sequence starts), ``taps`` [K, C] (the
    leaf: rounded to u's dtype, multiplied in float32) -> (rows [B, K - 1 + T, C],
    the tail and u as they lie, and out [B, T, C] float32, out_t = sum_j taps[j] *
    rows_{t + j}: tap j multiplies u_{t - K + 1 + j})."""
    K, T = taps.shape[0], u.shape[1]
    rows = jnp.concatenate([tail.astype(u.dtype), u], axis=1)
    taps = taps.astype(u.dtype).astype(jnp.float32)
    return rows, sum(rows[:, j:j + T].astype(jnp.float32) * taps[j] for j in range(K))


def _filter_tail(rows, K: int, live):
    """What a sequence keeps of ``_causal_filter``'s ``rows`` [B, K - 1 + T, C]: the
    K - 1 rows behind its last LIVE one (``live`` [B, T] bool, leading each row of
    the batch; None: all T), rows n_live ... n_live + K - 2: with fewer than K - 1
    live rows, what is left of the old tail in front of them, and with none the old
    tail itself, so a row that only rides along keeps what it had."""
    B_, T = rows.shape[0], rows.shape[1] - (K - 1)
    n_live = jnp.full((B_,), T, jnp.int32) if live is None else jnp.sum(live, axis=1)
    kept = n_live.astype(jnp.int32)[:, None, None] + jnp.arange(K - 1)[None, :, None]
    return jnp.take_along_axis(rows, kept, axis=1)  # rows n_live ... n_live + K - 2


def _short_conv(cfg: TransformerConfig, lp, h, state, l, live):
    """The gated short convolution of one layer (LFM2's operator, in the attention
    sublayer's place) on the normed h [B, T, d] -> (its output [B, T, d], state):

        [B | C | z] = h W_in                 three chunks of d, in that order
        u = B * z
        c_t = sum_j w[j] * u_{t-K+1+j}       depthwise, causal, no bias, no activation
        out = (C * c) W_out

    ``state`` is None (``apply``: the sequence starts from nothing and keeps
    nothing) or the cache tree, whose ``STATE`` leaf ``conv`` [conv layers, B, K - 1,
    d] holds per layer and row the last K - 1 rows of u, ALL a sequence keeps of
    such a layer: layer ``l`` (the model's index; the leaf is indexed among the conv
    layers) is read and written back in place. ``live``: ``_filter_tail``'s."""
    d, K = cfg.hidden_size, cfg.conv_kernel
    with jax.named_scope("conv"):
        proj = jnp.einsum("bsd,dz->bsz", _act_q(cfg, h), lp["conv_in"].astype(h.dtype))
        gate_in, gate_out, z = proj[..., :d], proj[..., d:2 * d], proj[..., 2 * d:]
        u = gate_in * z
        if state is None:
            tail = jnp.zeros((h.shape[0], K - 1, d), h.dtype)
        else:
            at = jnp.asarray(_index_in_kind(cfg), jnp.int32)[l]
            tail = lax.dynamic_index_in_dim(state[STATE]["conv"], at, keepdims=False)
        rows, c = _causal_filter(tail, u, lp["conv_w"])
        out = jnp.einsum("bsd,de->bse", gate_out * c.astype(h.dtype),
                         lp["conv_out"].astype(h.dtype))
        if state is None:
            return out, state
        held = state[STATE]["conv"]
        held = lax.dynamic_update_slice(held, _filter_tail(rows, K, live)[None].astype(held.dtype),
                                        (at, 0, 0, 0))
        return out, {**state, STATE: {**state[STATE], "conv": held}}


def _index_in_kind(cfg: TransformerConfig) -> tuple:
    """Per layer, its index among the layers that keep the same kind of cache
    leaves as it does (whole-context K/V, a window layer's ring, a conv layer's
    state): where it lies in those leaves' stacks."""
    seen, out = {}, []
    for window, _, operator in cfg.layer_kinds:
        kind = (operator, bool(window))
        out.append(seen.get(kind, 0))
        seen[kind] = out[-1] + 1
    return tuple(out)


# Rows a chunk of the gated delta rule's block form (``_delta_chunks``): the
# published code's (``torch_chunk_gated_delta_rule``), half a lane tile, so that
# the [chunk, chunk] matrices a head stay small beside the [head width, head width] state.
DELTA_CHUNK = 64


def _unit_lower_inverse(A):
    """(I + A)^-1 of strictly lower-triangular A [..., Q, Q] (Q a power of two),
    float32, with no loop over a chunk's rows: A is nilpotent (A^Q = 0), so the
    series sum_n (-A)^n ends and factors as (I - A)(I + A^2)(I + A^4) ... (I +
    A^(Q/2)), exactly: log2(Q) - 1 squarings and as many products, each a batch of
    [Q, Q] float32 matmuls at full precision (the inverse multiplies every value
    row of the chunk: an error in it is every later row's)."""
    Q = A.shape[-1]
    mm = partial(jnp.matmul, precision=lax.Precision.HIGHEST)
    inv, power, n = jnp.eye(Q, dtype=A.dtype) - A, A, 1
    while 2 * n < Q:
        power, n = mm(power, power), 2 * n
        inv = inv + mm(inv, power)
    return inv


def delta_block_form(cfg: TransformerConfig, rows: int, state, mesh=None) -> str:
    """What runs the gated delta rule's block form over a call of ``rows`` rows:
    ``"kernel"`` (``ops/pallas/delta_rule.py``: a chunk's [chunk, chunk] matrices and
    the state it is swept through stay in VMEM) or ``"xla"`` (``_delta_chunks``' own
    form, the definition the kernel is tested against). The kernel is taken where
    the code can see it may: on the ``tpu`` platform (on the CPU every delta test
    would pay the Pallas interpreter), on one device (the partitioner cannot split
    a Mosaic kernel), by a program that carries a state (``state`` is not None: the
    serving programs, which only run forward; the kernel has no backward pass, so
    ``apply`` and the loss keep the XLA form), over a block (one row is
    ``_delta_step``'s) of heads it tiles (``delta_head_dim`` whole lane tiles).
    ``_gated_delta`` traces by this and ``SlotWorker`` labels its ``prefill`` and
    ``chunk`` spans by it (``delta_block``)."""
    from ..ops.pallas.delta_rule import tiles

    mesh = mesh if mesh is not None else _ACTIVE_MESH[0]
    one_device = mesh is None or mesh.size == 1
    if (state is not None and rows > 1 and one_device and tiles(cfg.delta_head_dim)
            and jax.default_backend() == "tpu"):
        return "kernel"
    return "xla"


def _delta_chunks(q, k, v, g, beta, S0, form: str = "xla"):
    """The gated delta rule S_t = exp(g_t) S_{t-1} + k_t (x) beta_t (v_t - (exp(g_t)
    S_{t-1})^T k_t), o_t = S_t^T q_t over many rows, in its chunked form (Gated
    DeltaNet, arXiv:2412.06464; ``torch_chunk_gated_delta_rule``): q, k [B, T, Hk, D]
    (normalised; q scaled), v [B, T, H, D], g (<= 0) and beta [B, T, H] float32 (both
    0 on a row that must not move the state), S0 [B, H, D, D] float32 (key dimension
    first) -> (o [B, T, H, D] float32, S_T). Value head h reads key head h // (H //
    Hk); q and k are never repeated to the value heads. T is padded to whole chunks
    with g = beta = 0. With c_i the running sum of g inside a chunk of Q rows:

        A = strictly-lower((beta k) k^T * exp(c_i - c_j));  Tm = (I + A)^-1
        U = Tm (beta v);  W = Tm (beta k * exp(c))
        chunk after chunk:  v' = U - W S
                            o  = (q * exp(c)) S + lower(q k^T * exp(c_i - c_j)) v'
                            S <- exp(c_last) S + (k * exp(c_last - c))^T v'

    Everything above the last three lines is computed for all chunks at once; the
    ``lax.scan`` over chunks carries ONE state and runs four batched matmuls a chunk.
    Nothing rows x rows is formed: the largest temporaries are [chunks, heads, Q, Q]
    and [rows, heads, D]. The decays, ``Tm`` and the states are float32; the
    contractions take their operands in q's dtype and accumulate in float32.
    ``form`` (``delta_block_form``): ``"kernel"`` hands the padded rows to
    ``ops/pallas/delta_rule.py``, which computes the same to the same roundings with
    every chunk's temporaries in VMEM; what follows the padding here is the definition."""
    f32, dt = jnp.float32, q.dtype
    B_, T, H, D = v.shape
    Hk, Q = q.shape[2], DELTA_CHUNK
    r = H // Hk
    pad = (-T) % Q
    if pad:
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                            for a in (q, k, v, g, beta))
    if form == "kernel":
        from ..ops.pallas.delta_rule import delta_chunks

        o, S_T = delta_chunks(q, k, v, g, beta, S0, Q)
        return o[:, :T], S_T
    nc = (T + pad) // Q
    qc, kc = q.reshape(B_, nc, Q, Hk, D), k.reshape(B_, nc, Q, Hk, D)
    vc = v.reshape(B_, nc, Q, Hk, r, D)
    bc = beta.reshape(B_, nc, Q, Hk, r)
    g_t, b_t = (jnp.moveaxis(x.reshape(B_, nc, Q, Hk, r), 2, -1) for x in (g, beta))
    c_t = jnp.cumsum(g_t, axis=-1)  # [B, nc, Hk, r, Q]: falling from 0 inside each chunk
    to_end = jnp.flip(jnp.cumsum(jnp.flip(g_t, -1), axis=-1), -1) - g_t  # c_last - c_i, summed
    # from the END: a head that forgets fast has |c| in the hundreds, and a difference of two
    # such sums is good to 1e-4 where exp() of it is multiplied into a neighbouring row
    at = jnp.arange(Q)
    lower = at[:, None] >= at[None, :]
    between = (at[None, None, :] > at[None, :, None]) & (at[None, None, :] <= at[:, None, None])
    # c_i - c_j as the sum of the g between them (j < m <= i), each pair's own sum
    gaps = jnp.einsum("bcgrm,ijm->bcgrij", g_t, between.astype(f32),
                      precision=lax.Precision.HIGHEST)
    decay = jnp.exp(jnp.where(lower, gaps, -jnp.inf))
    kk = jnp.einsum("bcigd,bcjgd->bcgij", kc, kc, preferred_element_type=f32)
    qk = jnp.einsum("bcigd,bcjgd->bcgij", qc, kc, preferred_element_type=f32)
    A = jnp.where(at[:, None] > at[None, :], kk[:, :, :, None] * decay * b_t[..., :, None], 0.0)
    Tm = _unit_lower_inverse(A).astype(dt)  # [B, nc, Hk, r, Q, Q]
    # what the scan over chunks reads, chunk first and heads before rows ([nc, B, Hk, r, Q,
    # ...]): every contraction in its body is then a plain batched matmul over (B, Hk, r)
    rows = lambda x, scale: (jnp.moveaxis(x, 2, -2)[:, :, :, None]  # a key head's rows, for
                             * scale[..., None]).astype(dt)         # each of its value heads
    chunks_first = lambda x: jnp.moveaxis(x, 1, 0)
    U = jnp.einsum("bcgrij,bcjgrd->cbgrid", Tm, (vc * bc[..., None]).astype(dt),
                   preferred_element_type=f32)
    W = jnp.einsum("bcgrij,bcgrjd->cbgrid", Tm, rows(kc, b_t * jnp.exp(c_t)),
                   preferred_element_type=f32).astype(dt)
    intra = chunks_first((qk[:, :, :, None] * decay).astype(dt))  # [nc, B, Hk, r, Q, Q]
    q_in = chunks_first(rows(qc, jnp.exp(c_t)))  # [nc, B, Hk, r, Q, D]
    k_out = chunks_first(rows(kc, jnp.exp(to_end)))
    whole = chunks_first(jnp.exp(c_t[..., -1]))  # [nc, B, Hk, r]: a chunk's whole decay

    def chunk(S, xs):
        U_c, W_c, intra_c, q_c, k_c, whole_c = xs
        held = S.astype(dt)  # the state ENTERING the chunk is what it reads
        moved = U_c - jnp.einsum("bgrik,bgrkv->bgriv", W_c, held, preferred_element_type=f32)
        o = (jnp.einsum("bgrik,bgrkv->bgriv", q_c, held, preferred_element_type=f32)
             + jnp.einsum("bgrij,bgrjv->bgriv", intra_c, moved.astype(dt),
                          preferred_element_type=f32))
        S = whole_c[..., None, None] * S + jnp.einsum(
            "bgrjk,bgrjv->bgrkv", k_c, moved.astype(dt), preferred_element_type=f32)
        return S, o

    S_T, o = lax.scan(chunk, S0.astype(f32).reshape(B_, Hk, r, D, D),
                      (U, W, intra, q_in, k_out, whole))
    o = o.transpose(1, 0, 4, 2, 3, 5).reshape(B_, nc * Q, H, D)[:, :T]  # [nc, B, Hk, r, Q, D] ->
    return o, S_T.reshape(B_, H, D, D)


def _delta_step(q, k, v, g, beta, S0):
    """``_delta_chunks`` for ONE row a sequence (a decode step): the recurrence
    itself, elementwise in float32 (q, k [B, 1, Hk, D] float32, the rest as there).
    With a = exp(g) and S the state read, d = beta (v - a S^T k), S' = a S + k (x) d
    and o = S'^T q = a S^T q + (k . q) d: S is read for its two products with k and q
    and once more for the update, and written once; no contraction rounds it."""
    f32 = jnp.float32
    B_, _, H, D = v.shape
    Hk = q.shape[2]
    r = H // Hk
    S = S0.astype(f32).reshape(B_, Hk, r, D, D)
    a, b = (x[:, 0].reshape(B_, Hk, r, 1) for x in (jnp.exp(g), beta))
    qf, kf = (x[:, 0].astype(f32)[:, :, None, :, None] for x in (q, k))  # [B, Hk, 1, D, 1]
    Sk, Sq = jnp.sum(S * kf, axis=-2), jnp.sum(S * qf, axis=-2)  # [B, Hk, r, D]: S^T k, S^T q
    d = b * (v[:, 0].astype(f32).reshape(B_, Hk, r, D) - a * Sk)
    o = a * Sq + jnp.sum(qf * kf, axis=-2) * d
    S = a[..., None] * S + kf * d[..., None, :]
    return o.reshape(B_, 1, H, D), S.reshape(B_, H, D, D)


def _gated_delta(cfg: TransformerConfig, lp, h, state, l, live):
    """The gated delta rule of one layer (Qwen3-Next's ``Qwen3NextGatedDeltaNet``, in
    the attention sublayer's place) on the normed h [B, T, d] -> (its output [B, T,
    d], state). With Hk key heads and Hv value heads of D (``delta_key_heads``,
    ``delta_value_heads``, ``delta_head_dim``):

        [q | k | v | z] = h W_in      Hk D, Hk D, Hv D, Hv D channels, in that order
        [b | a]         = h W_ba      Hv + Hv
        [q | k | v]     = silu(filter([q | k | v]))   depthwise, causal, no bias
        q, k            -> Hk heads, each x * rsqrt(sum x^2 + 1e-6); q * D^-1/2
        beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)      float32, a value head
        S_t = exp(g_t) S_{t-1};  d_t = beta_t (v_t - S_t^T k_t);  S_t += k_t (x) d_t;  o_t = S_t^T q_t
        out = (w * rmsnorm_D(o) * silu(z)) W_out                       the norm BEFORE the gate

    Value head i reads key head i // (Hv / Hk). ``state`` is None (``apply``: the
    sequence starts from nothing and keeps nothing) or the cache tree, whose
    ``STATE`` leaves hold per delta layer and row the matrix S [Hv, D, D] in float32
    (key dimension first) and ``conv``, the last ``conv_kernel - 1`` rows of the
    filter's input q | k | v: layer ``l`` (the model's index; the leaves are indexed
    among the delta layers) is read and written back in place. ``live`` [B, T] bool
    or None (all): the rows that are a sequence's own. On any other row g = 0 and
    beta = 0, so the state passes through exactly, and the tail kept is that of the
    last LIVE rows (``_filter_tail``). T = 1 takes the recurrence itself
    (``_delta_step``), more rows the chunked form from the state given
    (``_delta_chunks``)."""
    f32 = jnp.float32
    Hk, Hv, D, K = cfg.delta_key_heads, cfg.delta_value_heads, cfg.delta_head_dim, cfg.conv_kernel
    Kd, conv_dim = cfg.delta_key_dim, cfg.delta_conv_dim
    B_, T, _ = h.shape
    with jax.named_scope("delta"):
        h = _act_q(cfg, h)
        proj = jnp.einsum("bsd,dz->bsz", h, lp["delta_in"].astype(h.dtype))
        ba = jnp.einsum("bsd,dz->bsz", h, lp["delta_ba"].astype(h.dtype),
                        preferred_element_type=f32)
        beta = jax.nn.sigmoid(ba[..., :Hv])
        g = -jnp.exp(lp["delta_a_log"].astype(f32)) * jax.nn.softplus(
            ba[..., Hv:] + lp["delta_dt_bias"].astype(f32))
        if live is not None:
            g, beta = (jnp.where(live[..., None], x, 0.0) for x in (g, beta))
        if state is None:
            tail, S0 = jnp.zeros((B_, K - 1, conv_dim), h.dtype), jnp.zeros((B_, Hv, D, D), f32)
        else:
            at = jnp.asarray(_index_in_kind(cfg), jnp.int32)[l]
            tail, S0 = (lax.dynamic_index_in_dim(state[STATE][name], at, keepdims=False)
                        for name in ("conv", "delta"))
        rows, conv = _causal_filter(tail, proj[..., :conv_dim], lp["delta_conv"])
        qkv = jax.nn.silu(conv)
        unit = lambda x: x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)
        q = unit(qkv[..., :Kd].reshape(B_, T, Hk, D)) * D ** -0.5
        k = unit(qkv[..., Kd:2 * Kd].reshape(B_, T, Hk, D))
        v = qkv[..., 2 * Kd:].reshape(B_, T, Hv, D)
        if T == 1:
            o, S = _delta_step(q, k, v, g, beta, S0)
        else:
            o, S = _delta_chunks(*(x.astype(h.dtype) for x in (q, k, v)), g, beta, S0,
                                 delta_block_form(cfg, T, state))
        o = o * lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + cfg.layernorm_epsilon)
        z = proj[..., conv_dim:].astype(f32).reshape(B_, T, Hv, D)
        gated = (o * lp["delta_norm_scale"].astype(f32) * jax.nn.silu(z)).astype(h.dtype)
        out = jnp.einsum("bsi,id->bsd", gated.reshape(B_, T, Hv * D),
                         lp["delta_out"].astype(h.dtype))
        if state is None:
            return out, state
        held = state[STATE]
        held = {"conv": lax.dynamic_update_slice(
                    held["conv"], _filter_tail(rows, K, live)[None].astype(held["conv"].dtype),
                    (at, 0, 0, 0)),
                "delta": lax.dynamic_update_slice(
                    held["delta"], S[None].astype(held["delta"].dtype), (at, 0, 0, 0, 0))}
        return out, {**state, STATE: held}


def _ssm_mixer(cfg: TransformerConfig, lp, h, state, l, live):
    """The Mamba-2 mixer of one layer on the normed h [B, T, d] -> (its output
    [B, T, d], state). As published (Falcon-H1; every multiplier is
    ``cfg.multiplier``'s, z | xBC | dt are ``ssm_in``'s segments):

        [z | xBC | dt] = (W_in (h in_mult)) * ssm_multipliers, segment by segment
        xBC_t = silu(sum_j w_conv[j] * xBC_{t-K+1+j} + b_conv)     depthwise, causal
        [x | B | C] = xBC;  dt_t = softplus(dt_t + dt_bias);  A = -exp(A_log)
        S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t;   y_t = S_t C_t + D x_t
        out = W_out(grouped_rmsnorm(y * silu(z))) out_mult         gate BEFORE the norm

    ``state`` is None (training, ``apply``: the sequence starts from nothing and
    keeps nothing) or the cache tree the block's ``attend`` carries, whose
    ``STATE`` leaves hold per layer and row the state S [H, P, N] in float32 and
    the convolution's tail, the last K - 1 rows of xBC BEFORE the convolution:
    layer ``l`` of both is read, and written back in place. ``live`` [B, T] bool
    or None (all): the rows that are a sequence's own, leading each row of the
    batch. On any other row (a prefill bucket's padding, a slot that is idle or
    still prefilling in a decode step) dt is 0, so exp(dt A) = 1 and dt x (x) B =
    0 and the state passes through exactly, and the tail kept is that of the
    last K - 1 LIVE rows. T = 1 takes the recurrence itself, more rows the
    chunked scan from the state given."""
    f32 = jnp.float32
    H, P, G, N, K = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state_size,
                     cfg.ssm_conv_kernel)
    inner, gn, conv_dim = cfg.ssm_inner, cfg.ssm_groups * cfg.ssm_state_size, cfg.ssm_conv_dim
    B_, T, _ = h.shape
    with jax.named_scope("ssm"):
        segments = np.repeat(np.asarray(cfg.multiplier("ssm_multipliers"), np.float32),
                             (inner, inner, gn, gn, H))
        proj = jnp.einsum("bsd,dz->bsz", _times(h, cfg.multiplier("ssm_in_multiplier")),
                          lp["ssm_in"].astype(h.dtype), preferred_element_type=f32) * segments
        z, xBC, dt = (proj[..., :inner], proj[..., inner:inner + conv_dim].astype(h.dtype),
                      proj[..., inner + conv_dim:])
        if state is None:
            tail, S0 = jnp.zeros((B_, K - 1, conv_dim), h.dtype), jnp.zeros((B_, H, P, N), f32)
        else:
            tail, S0 = (lax.dynamic_index_in_dim(state[STATE][name], l, keepdims=False)
                        for name in ("conv", "ssm"))
        rows, conv = _causal_filter(tail, xBC, lp["ssm_conv"])
        xBC = jax.nn.silu(conv + lp["ssm_conv_bias"].astype(h.dtype).astype(f32)).astype(h.dtype)
        xs = xBC[..., :inner].reshape(B_, T, H, P)
        Bm = xBC[..., inner:inner + gn].reshape(B_, T, G, N)
        Cm = xBC[..., inner + gn:].reshape(B_, T, G, N)
        dt = jax.nn.softplus(dt + lp["ssm_dt_bias"].astype(f32))
        if live is not None:
            dt = jnp.where(live[..., None], dt, 0.0)
        A = -jnp.exp(lp["ssm_a_log"].astype(f32))
        if T == 1:
            y, S = _ssm_step(xs, dt, A, Bm, Cm, S0.astype(f32))
        else:
            y, S = _ssm_scan(xs, dt, A, Bm, Cm, S0.astype(f32), cfg.ssm_chunk_size)
        y = y + lp["ssm_d"].astype(f32)[:, None] * xs.astype(f32)
        gated = (y.reshape(B_, T, inner) * jax.nn.silu(z)).reshape(B_, T, G, inner // G)
        gated = gated * lax.rsqrt(jnp.mean(jnp.square(gated), axis=-1, keepdims=True)
                                  + cfg.layernorm_epsilon)
        gated = (gated.reshape(B_, T, inner) * lp["ssm_norm_scale"].astype(f32)).astype(h.dtype)
        out = _times(jnp.einsum("bsi,id->bsd", gated, lp["ssm_out"].astype(h.dtype)),
                     cfg.multiplier("ssm_out_multiplier"))
        if state is None:
            return out, state
        tail = _filter_tail(rows, K, live)
        held = state[STATE]
        held = {"conv": lax.dynamic_update_slice(held["conv"], tail[None].astype(
                    held["conv"].dtype), (l, 0, 0, 0)),
                "ssm": lax.dynamic_update_slice(held["ssm"], S[None].astype(held["ssm"].dtype),
                                                (l, 0, 0, 0, 0))}
        return out, {**state, STATE: held}


def quantizable_layer_leaves(layers: dict, group_size: int) -> dict[str, int]:
    """{leaf name: effective group size} for the layer weights that weight
    quantization (inference) and QAT fake-quant (engine MoQ hook) BOTH cover —
    one predicate so the two paths can never diverge."""
    out = {}
    for k, w in layers.items():
        if isinstance(w, dict):
            continue  # already quantized
        if k.startswith("w") and getattr(w, "ndim", 0) >= 3:
            out[k] = group_size if w.shape[-1] % group_size == 0 else w.shape[-1]
    return out


def quantize_weights(cfg: TransformerConfig, params: Params, bits: int = 8, group_size: int = 64) -> Params:
    """Convert the stacked layer weight matrices to grouped int8/int4 storage
    (weight-only quantization — the reference's int8 inference path,
    csrc/transformer/inference pt_binding int8 variants + MoQ module_quantize).
    Quantized leaves become {'q': int8 [L, ...], 's': fp32 scales}; LayerNorm
    params and biases stay fp. Use with cfg.replace(weight_bits=bits)."""
    from ..ops.quantization import quantize

    from ..ops.quantization import pack_int4

    targets = quantizable_layer_leaves(params["layers"], group_size)
    new_layers = {}
    for k, w in params["layers"].items():
        if k in targets:
            qt = quantize(w, bits=bits, group_size=targets[k])
            if bits == 4 and w.shape[-1] % 2 == 0:
                # two int4 values per byte — int4 actually halves HBM
                new_layers[k] = {"q4": pack_int4(qt.values), "s": qt.scale}
            else:
                new_layers[k] = {"q": qt.values, "s": qt.scale}
        else:
            new_layers[k] = w
    out = dict(params)
    out["layers"] = new_layers
    return out


def _dequant_layer(cfg: TransformerConfig, lp):
    """Per-layer slice of quantized storage -> compute-dtype weights; no-op
    for unquantized models."""
    if not cfg.weight_bits:
        return lp
    from ..ops.quantization import QuantizedTensor, dequantize

    from ..ops.quantization import unpack_int4

    out = {}
    for k, v in lp.items():
        if isinstance(v, dict) and ("q" in v or "q4" in v):
            values = unpack_int4(v["q4"]) if "q4" in v else v["q"]
            # group size is recoverable from the shapes (quantize_weights may
            # have fallen back to per-leaf grouping on non-divisible dims)
            g = values.shape[-1] // v["s"].shape[-1]
            qt = QuantizedTensor(
                values=values, scale=v["s"], zero_point=None,
                bits=cfg.weight_bits, group_size=g, shape=values.shape,
            )
            out[k] = dequantize(qt, dtype=cfg.dtype)
        else:
            out[k] = v
    return out


def _dropout(x, rate: float, rng):
    """Inverted dropout; identity when rate == 0 or no rng (inference).
    Seeding via jax.random replaces the reference's curand state per layer
    (csrc/transformer/dropout_kernels.cu)."""
    if rate <= 0.0 or rng is None:
        return x
    keep = jax.random.bernoulli(rng, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), jnp.zeros((), x.dtype))


def _local_attn_bias(cfg: TransformerConfig, S: int):
    """Additive [S, S] window mask for GPT-Neo-style local attention."""
    w = cfg.local_attn_window
    dist = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]
    return jnp.where((dist >= 0) & (dist < w), 0.0, NEG_BIAS).astype(jnp.float32)


NEG_BIAS = -1e30


def _block(cfg: TransformerConfig, carry, lp, l, *, positions, attend, ffn,
           rng=None, pld_keep=None, live=None, kind=None, tables=None):
    """THE transformer layer: every caller's layer is this function.

    carry = (x [B, T, d] in the compute dtype, state); ``lp`` one layer's leaves;
    ``l`` its index in the stacks it came from (with ``layer_passes`` > 1: pass x
    layers + that index, the (pass, layer)'s place in the CACHE's stacks, which is
    all such a model reads ``l`` for: ``_layer_loop``). What differs between callers
    comes in as arguments and nothing else does:

    - ``attend(q, k, v, state, l, lp) -> (attention output, state)``: how
      attention is computed from the projected q, k, v and what state it carries
      (training: ``_stateless_attention``, no state; serving:
      ``_cache_attention``, state = the stacked cache tree); ``lp`` is there
      for latent attention, whose form (expanded or absorbed) is ``attend``'s;
    - ``ffn(lp, h) -> (out, aux_loss, experts chosen or None)``: this layer's
      feed-forward, dense or routed (``_layer_loop`` picks);
    - ``rng`` / ``pld_keep``: the training-only stochastic gates (dropout;
      progressive layer drop, one coin for BOTH residual branches);
    - ``live`` [B, T] bool or None: the rows that are a sequence's own, for a
      model whose state-space mixer must not move on the others (``_ssm_mixer``);
    - ``kind`` (window, rotary, operator) or None: this layer's entry of
      ``cfg.layer_kinds`` where ``_layer_loop`` tells the kinds apart as it is
      traced (the cache path, whose window layers keep another cache than its
      whole ones, and every path of a model with ``layer_operators``, whose conv
      layers have other leaves); it is handed on to ``attend``. None: a model of
      one kind, or one whose layers of every kind run in ONE scan (``apply`` with
      window / rotary flags), read here at the traced ``l``. A layer whose operator is
      "conv" runs ``_short_conv`` in the attention sublayer's place and one whose
      operator is "delta" ``_gated_delta``: no q / k / v of attention's, no rotary,
      no ``attend``; its state rides in ``state`` as a mixer's does.
    - ``tables``: ``rotary_tables(cfg)`` where the layer kinds state their rotary
      (``rotary_by_kind``; built here where a caller hands none): the block turns q
      and k by its kind's row, the window kind's or the whole-context kind's.

    Norm kind and placement and the residual form are what ``cfg`` says, for a
    dense layer and a routed one alike (``norm_style="post"`` is the BERT
    layout, sublayer -> residual add -> norm, and is sequential; ``"sandwich"`` is
    the pre-norm layout with a second norm on each sublayer's OUTPUT, before the
    residual add: x + norm(f(norm(x))), sequential too). Three residual
    forms: sequential (x + attn, then + ffn of the new x), parallel (x + attn +
    ffn, both of the same x: GPT-NeoX), and, with a state-space mixer, the
    attention and the mixer in parallel on the SAME normed input followed by a
    sequential feed-forward (Falcon-H1); the mixer's state rides in ``state``.
    Returns (carry, (aux_loss, experts)): a ``lax.scan`` body."""
    x, state = carry
    lp = _dequant_layer(cfg, lp)
    k_attn = k_hidden = gate = None
    if rng is not None:
        k_attn, k_hidden, k_pld = jax.random.split(rng, 3)
        if pld_keep is not None:
            gate = jax.random.bernoulli(k_pld, pld_keep).astype(cfg.dtype)

    def branch(out, rate, key):
        out = _dropout(out, rate, key)
        return out if gate is None else gate * out

    pre = cfg.norm_style != "post"
    # the sandwich's second norm, on a sublayer's branch; any other placement has none
    post = (lambda y, name: norm(cfg, y, lp, name)) if cfg.norm_style == "sandwich" \
        else (lambda y, name: y)
    h = norm(cfg, x, lp, "ln1") if pre else x
    rotary = True
    if cfg.rotary_layers is not None:
        rotary = kind[1] if kind is not None else jnp.asarray(cfg.rotary_layers, bool)[l]
    table = None
    if cfg.rotary_by_kind is not None:
        tables = tables if tables is not None else rotary_tables(cfg)
        windowed = int(bool(kind[0])) if kind is not None else jnp.asarray(
            cfg.local_attn_layers or (0,) * cfg.num_layers, jnp.int32)[l]
        table = (tables[0][windowed], tables[1][windowed])
    if kind is not None and kind[2] != "attn":
        operator = _short_conv if kind[2] == "conv" else _gated_delta
        op_out, state = operator(cfg, lp, h, state, l, live)
        attn_out = branch(op_out, cfg.attn_dropout, k_attn)
    else:
        q, k, v, *out_gate = _qkv_proj(cfg, lp, h, positions, rotary, table)
        attn, state = attend(q, k, v, state, l, lp, **({} if kind is None else {"kind": kind}))
        attn_out = branch(post(_attn_out_proj(cfg, lp, attn, *out_gate), "ln1_post"),
                          cfg.attn_dropout, k_attn)
    if cfg.ssm_state_size:
        mixed, state = _ssm_mixer(cfg, lp, h, state, l, live)
        attn_out = attn_out + mixed
    if pre and cfg.parallel_residual:
        f, aux, experts = ffn(lp, norm(cfg, x, lp, "ln2"))
        x = x + attn_out + branch(f, cfg.hidden_dropout, k_hidden)
    else:
        x = x + attn_out
        if not pre:
            x = norm(cfg, x, lp, "ln1")
        f, aux, experts = ffn(lp, norm(cfg, x, lp, "ln2") if pre else x)
        x = x + branch(post(f, "ln2_post"), cfg.hidden_dropout, k_hidden)
        if not pre:
            x = norm(cfg, x, lp, "ln2")
    return (x, state), (aux, experts)


def _stateless_attention(cfg: TransformerConfig, S: int):
    """The block's ``attend`` for a whole sequence of length ``S`` (training,
    the pipeline stages): ``_attention_dispatch`` with alibi and GPT-Neo's local
    window, fused in-kernel where the dispatch computes them from positions,
    otherwise as a dense [S, S] bias. Carries no state."""
    attn_fn = _attention_dispatch(cfg)
    fused = getattr(attn_fn, "handles_fused_bias", False)
    bias = None if fused else attn_bias(cfg, S)
    if cfg.kv_lora_rank:  # a whole sequence fills its own cache: the expanded form
        return lambda q, k, v, state, l, lp: (
            attn_fn(q, *_latent_expand(cfg, lp, k, v), bias), state)
    if cfg.local_attn_window <= 0 or cfg.local_attn_layers is None:
        # ``kind``: what the loop hands a model with ``layer_operators``; its
        # attention layers are all of the whole-context kind
        return lambda q, k, v, state, l, lp, kind=None: (attn_fn(q, k, v, bias), state)
    is_local = jnp.asarray(cfg.local_attn_layers, bool)  # per layer
    local_bias = None if fused else _local_attn_bias(cfg, S)

    def attend(q, k, v, state, l, lp):
        if fused:  # the raw window (0 = global) instead of a dense bias
            w = jnp.where(is_local[l], jnp.float32(cfg.local_attn_window), jnp.float32(0))
            return attn_fn(q, k, v, bias, window=w), state
        lb = jnp.where(is_local[l], local_bias, 0.0)[None, None]
        return attn_fn(q, k, v, lb if bias is None else bias + lb), state

    return attend


def _remat_wrapper(cfg: TransformerConfig):
    """What a training caller hands ``_layer_loop`` as ``wrap``: activation
    checkpointing round one scanned body, whose entering residual stream is
    tagged ``layer_in`` (``_boundary_tagger``). None when ``cfg.remat`` is off."""
    if not cfg.remat:
        return None
    policy = _remat_policy(cfg.remat_policy, offload=cfg.remat_offload, also=_ALSO_SAVED.get())
    tag = _boundary_tagger(cfg)

    def wrap(body):
        def tagged(carry, xs):
            x, state = carry
            return body((tag(x), state), xs)

        return jax.checkpoint(tagged, policy=policy, prevent_cse=False)

    return wrap


def _layer_loop(cfg: TransformerConfig, layers, moe, x, state, *, positions, attend,
                per_layer=None, wrap=None, lead=None, live=None, forward_only: bool = False,
                after_pass=None):
    """THE layer loop: ``_block`` over the stacked ``layers`` [L, ...] (the
    whole model's, or one pipeline stage's slice), ``moe`` the routed layers'
    stacks or None -> (x, state, summed aux loss, experts chosen or None, what
    ``after_pass`` handed out a pass or None).

    Owns the stack loaders, the layer index, ``scan_unroll`` and the period. It
    first says, as Python values, WHAT every layer IS: its kind and its
    feed-forward. The kind is the layer's entry of ``cfg.layer_kinds`` where the
    blocks must be told apart as the program is traced (a model with
    ``layer_operators``, whose conv layers have other leaves; a ``forward_only``
    caller, whose window layers keep another cache), and None otherwise: a model
    of one kind, or window / rotary flags that ``_block`` reads at the traced
    layer index. The feed-forward is "lead" (the ``moe_first_dense`` leading
    layers, whose gated stacks are ``lead`` = ``params["dense_ffn"]``; ``moe``
    then holds the stacks of the layers after them), "routed" (the last of every
    ``moe_every`` layers after the lead, where ``moe`` is given) or "dense".
    Behind the lead the loop finds the shortest period of that list, scans over
    the whole periods (compile time flat in depth) and runs the layers that fill
    no period behind them. A dense model of one kind under ``wrap`` has
    ``remat_group`` layers a period. ONE rule runs every stretch of layers, the
    lead, the inside of a period and the tail alike: a run of layers that are the
    same thing is a ``lax.scan`` of the block over their slices (a scan of one
    trip is the block itself once XLA has simplified it). So a model of one kind
    is one scan over its stacks as they are; ``moe_every`` = 4 is a scan of (a
    scan of three dense layers, one routed block); a leading dense layer is one
    block before the scan; A C C C under a backward pass is a scan of (an
    attention block, a scan of three conv layers).

    ``wrap`` (the caller's ``_remat_wrapper`` or None) goes round the body of
    every run outside the periods and round a whole period's; ``per_layer`` holds
    [L]-leading ``rng`` / ``pld_keep`` for the block; ``live`` is the block's
    (the rows a state-space mixer may move on).

    Stacks by operator (``cfg.layer_operators``): ``layers`` then holds, beside
    the [L]-stacked leaves every layer has, one sub-dict an operator
    (``OPERATORS``) whose stacks are as long as that operator's layers are many.
    Every stack is cut the same way: a run's (or a period's) share of a stack
    begins behind the layers before it that USE the stack, so a block gets its
    layer's slice of the first and ITS operator's slice of the second, a leading
    layer its slice of ``lead`` and a routed one its slice of ``moe`` (NOT at the
    model's layer number: a leading dense layer or a period of several shifts it).

    ``forward_only`` (the cache path's word that no backward pass follows; a
    backward pass wants the scanned slice, whose cotangent is one layer's, so
    training and the pipeline stages leave it off) changes what is scanned, not
    what is computed, because on the chip a slice that does not begin its stack
    is a copy of all of it in every call (192 MB a decode step at LFM2's
    widths). Its runs beside and inside the periods are ONE layer long, so their
    slices are constants of the trace that the block's matmuls read in place (S S
    S G is a scan of four blocks). The operators' stacks are not sliced at all:
    the scans carry the layers' INDICES in them and the block reads the held
    stack there (a period's layers need not begin the stack). Where
    ``expert_bank_form`` says "in_place" the three expert banks of dropless
    routing are read the same way, through the grouped GEMM's own group index
    (``moe/dropless.py``). And a one-token block is a decode step to the routed
    feed-forward (``_moe_ffn``).

    The PASSES (``cfg.layer_passes`` > 1): everything above is then the body of ONE
    outer ``lax.scan`` over the pass r, which carries (x, state); the stacks are
    its constants (compile time flat in the passes as in the depth; under a backward
    pass their cotangents add up over the passes). ``after_pass(x) -> (x, y)`` (the
    caller's: the final norm, and the exit gate's reading where asked for) runs
    behind every pass, the last one too, and the y's come back stacked by pass. The
    blocks of pass r are handed r x L + l where those of a model of one pass are
    handed l: the (pass, layer)'s place in the cache's stacks (``cache_layout``),
    where ``attend`` writes and reads, while the block's weights are layer l's
    slice as in any pass. ``forward_only``'s rule holds under it: no stack is
    sliced but from its beginning, none is copied a pass."""
    by_op = {op: layers[op] for op in OPERATORS if op in layers}  # none: ONE stack of layers
    layers_xs, load_layer = _make_stack_loader(
        cfg, {k: v for k, v in layers.items() if k not in by_op} if by_op else layers)
    L = jax.tree.leaves(layers_xs)[0].shape[0]
    routed_model = cfg.moe_every > 0 and moe is not None
    every = max(cfg.moe_every, 1)
    n_lead = 0 if lead is None else jax.tree.leaves(lead)[0].shape[0]
    decode = forward_only and x.shape[1] == 1
    # every stack the blocks read, by the name of what uses it (``uses``)
    index = jnp.arange(L, dtype=jnp.int32)
    stacks = {"layers": (layers_xs, index, per_layer or {})}
    load_lead = load_moe = banks = None
    if lead is not None:
        stacks["lead"], load_lead = _make_stack_loader(cfg, lead)
    if routed_model:
        stacks["routed"], load_moe = _make_stack_loader(cfg, moe)
        if forward_only and expert_bank_form(cfg, moe) == "in_place":
            banks = moe["experts"]  # the scans carry the routed layer's position in their place
            n_routed = jax.tree.leaves(banks)[0].shape[0]
            stacks["routed"] = {**stacks["routed"],
                                "experts": jnp.arange(n_routed, dtype=jnp.int32)}
    for op, stack in by_op.items():
        stacks[op] = (jnp.arange(jax.tree.leaves(stack)[0].shape[0], dtype=jnp.int32)
                      if forward_only else stack)

    kinds = cfg.layer_kinds if by_op or forward_only else None

    def feed(i):
        if i < n_lead:
            return "lead"
        return "routed" if routed_model and (i - n_lead) % every == every - 1 else "dense"

    what = [(kinds[i] if kinds else None, feed(i)) for i in range(L)]
    uses = lambda name, w: name in ("layers", w[1]) or (w[0] is not None and name == w[0][2])
    n = L - n_lead
    period = next((p for p in range(1, n) if what[n_lead:L - p] == what[n_lead + p:]), n)
    if period == 1 and not routed_model and wrap is not None and cfg.remat_group > 1:
        # number_checkpoints analogue (reference checkpoint():743): boundaries
        # saved only every remat_group layers; a whole group recomputes in backward
        if n % cfg.remat_group:
            import warnings

            warnings.warn(
                f"remat_group={cfg.remat_group} does not divide num_layers={n}; "
                "falling back to per-layer activation checkpointing")
        else:
            period = cfg.remat_group
    G, tail = divmod(n, period) if period else (0, 0)
    unroll = max(1, cfg.scan_unroll)
    block = partial(_block, cfg, positions=positions, attend=attend, live=live,
                    tables=rotary_tables(cfg))
    unwrapped = lambda body: body  # noqa: E731
    wrap = wrap or unwrapped

    def one(w, carry, xs):
        """The block of a layer that is ``w``, on its slices ``xs`` of the stacks it uses."""
        kind, fed = w
        lp, l, gates = xs["layers"]
        lp = load_layer(lp)
        if by_op:
            mine = xs[kind[2]]
            lp = {**lp, **(mine if not forward_only else jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, mine, 0, keepdims=False), by_op[kind[2]]))}
        if fed == "lead":
            lp = {**lp, **load_lead(xs["lead"])}
        ffn = partial(_dense_ffn, cfg)
        if fed == "routed":
            moe_l, bank_layer = load_moe(xs["routed"]), None
            if banks is not None:  # this layer's position came where its banks would be
                moe_l, bank_layer = {**moe_l, "experts": banks}, moe_l["experts"]
            ffn = lambda lp, h: _moe_ffn(cfg, moe_l, h, decode, bank_layer)  # noqa: E731
        carry, y = block(carry, lp, l, **gates, ffn=ffn, kind=kind)
        return carry, (y if fed == "routed" else None)

    def share(held, first, lo, hi, groups=None):
        """Of stacks ``held``, whose first layer is the model's ``first``: the slices
        of layers lo..hi, each stack cut behind the layers before ``lo`` that use it;
        with ``groups`` as [groups, a period's share, ...] of as many periods."""
        out = {}
        for name, tree in held.items():
            before, count = (sum(uses(name, w) for w in what[a:b])
                             for a, b in ((first, lo), (lo, hi)))
            if not count:
                continue
            count *= groups or 1

            def cut(a):
                a = a if (before, count) == (0, a.shape[0]) else a[before:before + count]
                return a if groups is None else a.reshape((groups, count // groups) + a.shape[1:])

            out[name] = jax.tree.map(cut, tree)
        return out

    def run(carry, held, first, lo, hi, wrap):
        """Layers lo..hi, all the same thing: ONE scan of the block over their slices
        -> (carry, [what they chose] where they are routed)."""
        carry, y = lax.scan(wrap(partial(one, what[lo])), carry, share(held, first, lo, hi),
                            unroll=unroll)
        return carry, [y] if what[lo][1] == "routed" else []

    def stretch(carry, held, first, lo, hi, wrap=unwrapped):
        """Layers lo..hi beside the scan over the periods, or inside one, run by run."""
        ys = []
        while lo < hi:
            end = lo + 1 if forward_only else next(
                (i for i in range(lo, hi) if what[i] != what[lo]), hi)
            carry, got = run(carry, held, first, lo, end, wrap)
            ys, lo = ys + got, end
        return carry, ys

    join = lambda ys: ys[0] if len(ys) == 1 else jax.tree.map(lambda *a: jnp.concatenate(a), *ys)
    lo, hi = n_lead, L - tail

    def once(carry, stacks):
        """Every layer once, ``stacks`` as above -> (carry, summed aux loss, experts chosen)."""
        carry, ys = stretch(carry, stacks, 0, 0, lo, wrap)
        if period == 1:  # a period of one layer is the layer: one run over the stacks as they are
            carry, got = run(carry, stacks, 0, lo, hi, wrap)
            ys += got
        elif G:
            def group(carry, held):
                carry, ys = stretch(carry, held, lo, lo, lo + period)
                return carry, (join(ys) if ys else None)

            carry, got = lax.scan(wrap(group), carry, share(stacks, 0, lo, lo + period, G),
                                  unroll=unroll)
            if got is not None:
                ys.append(jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), got))
        carry, got = stretch(carry, stacks, 0, hi, L, wrap)
        ys += got
        if not ys:
            return carry, jnp.zeros((), jnp.float32), None
        aux, chosen = join(ys)
        return carry, jnp.sum(aux), chosen

    if cfg.layer_passes == 1:
        carry, aux, chosen = once((x, state), stacks)
        return (*carry, aux, chosen, None)

    def one_pass(carry, r):
        with jax.named_scope("pass"):
            carry, aux, _ = once(carry, {**stacks, "layers": (layers_xs, index + r * L, {})})
            x, handed = after_pass(carry[0])
        return (x, carry[1]), (aux, handed)

    carry, (aux, handed) = lax.scan(one_pass, (x, state),
                                    jnp.arange(cfg.layer_passes, dtype=jnp.int32))
    return (*carry, jnp.sum(aux), None, handed)


def _head_matrix(params: Params, load=lambda t: t):
    """[d, vocab]: the untied head, or the embedding table transposed."""
    head = params.get("lm_head", None)
    return load(params["wte"]).T if head is None else load(head)


def _final_norm(cfg: TransformerConfig, params: Params, x):
    return norm(cfg, x, params, "lnf") if cfg.final_ln else x


def _after_pass(cfg: TransformerConfig, params: Params, return_exit: bool):
    """What ``apply`` / ``apply_with_cache`` hand ``_layer_loop`` of a model with
    ``layer_passes`` > 1 (None for any other): x -> (the final norm of it, which the
    next pass starts from and the head reads, so that a caller that handed one in does
    not norm the last pass's output a second time; the exit gate's
    lambda = sigmoid(h . w_g + b_g) on that normed h, float32 [B, T], where
    ``return_exit`` asks for it: without the flag the gate is not in the program)."""
    if return_exit and not cfg.exit_gate:
        raise ValueError("return_exit: the configuration states no exit_gate")
    if cfg.layer_passes == 1:
        return None

    def after(x):
        h = _final_norm(cfg, params, x)
        if not return_exit:
            return h, None
        gate = params["exit_gate"]
        with jax.named_scope("exit_gate"):
            score = jnp.einsum("btd,d->bt", h.astype(jnp.float32), gate["w"][:, 0].astype(
                jnp.float32), precision=lax.Precision.HIGHEST) + gate["b"].astype(jnp.float32)
        return h, jax.nn.sigmoid(score)

    return after


def exit_distribution(gates):
    """The exit gate's lambdas [passes, B, T] -> p [B, T, passes] float32, the
    distribution over the pass at which a token would stop: p_r = lambda_r x
    prod_{s<r} (1 - lambda_s) before the last pass, which takes the rest (its own
    lambda is read and decides nothing). Sums to 1 over the passes."""
    stay = jnp.cumprod(1.0 - gates[:-1], axis=0)  # the share still running BEHIND pass r
    before = jnp.concatenate([jnp.ones_like(gates[:1]), stay[:-1]])  # ... BEFORE pass r
    p = jnp.concatenate([gates[:-1] * before, stay[-1:]])
    return jnp.moveaxis(p, 0, -1)


def _lm_head(cfg: TransformerConfig, params: Params, x, normed: bool = False):
    """THE output head: final norm (if ``cfg.final_ln``; not where ``normed`` says
    the layer loop applied it already), tied or untied projection, ``lm_head_bias`` ->
    float32 logits. The losses read ``_final_norm`` and ``_head_matrix`` through
    ``lm_loss_from_hidden``."""
    if not normed:
        x = _final_norm(cfg, params, x)
    with jax.named_scope("lm_head"):
        logits = jnp.einsum("bsd,dv->bsv", x, _head_matrix(params).astype(x.dtype))
        logits = logits.astype(jnp.float32)
        logits = _times(logits, cfg.multiplier("lm_head_multiplier"))
        if "lm_head_bias" in params:
            logits = logits + params["lm_head_bias"].astype(jnp.float32)
        return logits


def embed(cfg: TransformerConfig, params: Params, tokens, positions=None):
    """Token (+ learned position) embedding -> (x [B,S,d], positions [B,S])."""
    with jax.named_scope("embed"):
        B, S = tokens.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
        x = params["wte"][tokens].astype(cfg.dtype)
        x = _times(x, cfg.multiplier("embedding_multiplier"))
        if cfg.pos_emb == "learned":
            x = x + params["wpe"][positions].astype(cfg.dtype)
        if cfg.embed_ln:
            x = norm(cfg, x, params, "emb_ln")
        return x, positions


def attn_bias(cfg: TransformerConfig, S: int):
    """Additive attention bias [1,H,S,S] (alibi) or None."""
    if cfg.pos_emb != "alibi":
        return None
    slopes = alibi_slopes(cfg.num_heads)
    dist = jnp.arange(S)[None, :] - jnp.arange(S)[:, None]
    return (slopes[:, None, None] * dist[None]).astype(jnp.float32)[None]


def apply(
    cfg: TransformerConfig,
    params: Params,
    tokens: jnp.ndarray,
    positions=None,
    return_hidden: bool = False,
    with_aux: bool = False,
    rng: Optional[jax.Array] = None,
    step=None,
    _top_streamed: bool = False,
    return_routing: bool = False,
    mtp_tokens=None,
    return_exit: bool = False,
) -> jnp.ndarray:
    """tokens [B, S] int32 -> logits [B, S, vocab] (fp32), or the final hidden
    states [B, S, d] when ``return_hidden`` (used by the chunked LM loss).
    With ``with_aux`` returns (out, aux_loss) — MoE load-balancing loss.
    With ``return_routing`` (dropless routing only) the experts chosen for
    every token in every routed layer come last: int32 [layers, B, S, k].
    With ``mtp_tokens`` [B, S] (``mtp_layers`` > 0: row i holds t_{i+1}, the token
    AFTER the one at i) the multi-token-prediction module's logits for t_{i+2}
    come last of all, [B, S, vocab] (``mtp_logits``), and the experts its block
    chose are the last row of the routing.
    With ``return_exit`` (``exit_gate`` only) the exit distribution comes last of
    all: float32 [B, S, layer_passes] (``exit_distribution``); the logits are the
    last pass's either way.
    ``rng`` enables dropout / progressive layer drop (training); ``step``
    drives the PLD theta schedule. ``_top_streamed``: the caller already
    streamed the top-level leaves (param_offload) — a shared leaf (tied wte)
    must be streamed exactly ONCE per differentiated function, or its two
    host-pinned cotangents meet in an ``add`` XLA's host-offload legalizer
    rejects."""
    B, S = tokens.shape
    L = cfg.num_layers
    _routing_asked(cfg, return_routing)
    if not _top_streamed:
        params = _stream_top_level(cfg, params)
    x, positions = embed(cfg, params, tokens, positions)
    if rng is not None:
        rng, k_emb = jax.random.split(rng)
        x = _dropout(x, cfg.hidden_dropout, k_emb)
    per_layer = None
    if rng is not None and (cfg.hidden_dropout > 0 or cfg.attn_dropout > 0 or cfg.pld_enabled):
        per_layer = {"rng": jax.random.split(rng, L)}
        if cfg.pld_enabled:
            t = jnp.asarray(0 if step is None else step, jnp.float32)
            theta_t = cfg.pld_theta + (1.0 - cfg.pld_theta) * jnp.exp(-cfg.pld_gamma * t)
            depth_frac = jnp.arange(L, dtype=jnp.float32) / max(1, L)
            per_layer["pld_keep"] = 1.0 - depth_frac * (1.0 - theta_t)  # [L]
    after_pass = _after_pass(cfg, params, return_exit)
    x, _, aux_total, chosen, gates = _layer_loop(
        cfg, params["layers"], params.get("moe"), x, None, positions=positions,
        attend=_stateless_attention(cfg, S), per_layer=per_layer, wrap=_remat_wrapper(cfg),
        lead=params.get("dense_ffn"), after_pass=after_pass)
    mtp = ()
    if mtp_tokens is not None:
        mtp, module_chosen = mtp_logits(cfg, params, x, mtp_tokens, positions)
        mtp = (mtp,)
        if chosen is not None and module_chosen is not None:  # the module's choices last
            chosen = jnp.concatenate([chosen, module_chosen])
    normed = after_pass is not None  # the loop normed every pass's output, the last one's too
    if return_hidden:
        x = x if normed else _final_norm(cfg, params, x)
    else:
        x = _lm_head(cfg, params, x, normed)
    out = ((x,) + ((aux_total,) if with_aux else ()) + ((chosen,) if return_routing else ())
           + mtp + ((exit_distribution(gates),) if return_exit else ()))
    return out if len(out) > 1 else x


def mtp_logits(cfg: TransformerConfig, params: Params, hidden, next_tokens, positions=None):
    """The multi-token-prediction module (``mtp_layers``; DeepSeek-V3 section 2.2).
    ``hidden`` [B, S, d]: the residual stream behind the model's last layer,
    BEFORE its final norm; ``next_tokens`` [B, S]: t_{i+1} at row i. h' =
    [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(hidden_i)] . W_eh, one block of the
    whole-context kind (no window, no rotary, the model's last layer's
    feed-forward), the module's own final norm, the model's head -> (float32
    logits [B, S, vocab] for t_{i+2}, the experts the module's block chose
    [1, B, S, k] or None)."""
    if not cfg.mtp_layers:
        raise ValueError("mtp_logits: the configuration states no mtp_layers")
    p, eps = params["mtp"], cfg.layernorm_epsilon
    emb, positions = embed(cfg, params, next_tokens, positions)
    joined = jnp.concatenate([rms_norm(emb, p["enorm_scale"], eps),
                              rms_norm(hidden, p["hnorm_scale"], eps)], axis=-1)
    x = jnp.einsum("bsk,kd->bsd", joined, p["eh_proj"].astype(joined.dtype))
    block = _mtp_block_cfg(cfg)
    x, _, _, chosen, _ = _layer_loop(block, p["layers"], p.get("moe"), x, None,
                                     positions=positions,
                                     attend=_stateless_attention(block, x.shape[1]))
    return _lm_head(cfg, {**params, "lnf_scale": p["lnf_scale"]}, x), chosen


def _routing_asked(cfg, return_routing: bool) -> None:
    if return_routing and cfg.moe_routing != "dropless":
        raise NotImplementedError(
            "return_routing needs moe_routing='dropless': the GShard path keeps its "
            "choices as [T, E, C] one-hot tensors, and a dense model has none")


def _moe_ffn(cfg, moe_p, h, decode: bool = False, bank_layer=None):
    """The routed feed-forward of one layer on h [B, T, d] -> (out, load-
    balancing loss, experts chosen [B, T, k] or None). GShard routing keeps
    its capacity semantics except at a single-token ``decode`` step, where
    the capacity heuristic degenerates to ~1 slot and drops colliding tokens;
    dropless routing is one function everywhere. ``bank_layer`` (dropless
    routing with the banks read in place, ``_layer_loop(forward_only=True)``):
    ``moe_p["experts"]`` are the held stacks and this the layer's position in them."""
    with jax.named_scope("moe"):
        if cfg.moe_routing == "dropless":
            from ..moe.dropless import moe_ffn_dropless

            return moe_ffn_dropless(cfg, moe_p, h, bank_layer)
        from ..moe.layer import moe_ffn_apply, moe_ffn_dense

        if decode:
            return moe_ffn_dense(cfg, moe_p, h), jnp.zeros((), jnp.float32), None
        return moe_ffn_apply(cfg, moe_p, h, mesh=_ACTIVE_MESH[0]) + (None,)


# ---------------------------------------------------------------------------
# KV-cache decoding (generative inference)
# ---------------------------------------------------------------------------
#
# The reference's decode path is the fused `softmax_context` CUDA kernel with
# an incremental KV cache (csrc/transformer/inference/csrc/pt_binding.cpp:
# softmax_context_* :1237, attention-with-cache). TPU-native: the cache is a
# tree of static-shape leaves (``cache_layout``: [L, B, Smax, heads, width] a
# token, [L, B, ...] a sequence for a state-space mixer's state)
# that stays ONE set of buffers through the layer scan (its carry: layer l
# writes its new rows at [l, row, pos] and attends to layer l of the stack
# where it lies); one `apply_with_cache` function serves both prefill
# (T = prompt len, pos = 0) and decode (T = 1) so XLA compiles exactly two
# programs per sequence budget.

def cache_layout(cfg: TransformerConfig) -> dict:
    """What the model keeps of a sequence between steps, as the tree every helper
    below, and the serving engine, work on (on no leaf's name or trailing shape).

    Per-TOKEN leaves, ``{leaf: (heads, width)}``: what attention caches a token a
    layer, [L, B, Smax, heads, width] in the cache. Plain attention: the keys and
    the values of every K/V head (``kv_heads``: grouped-query attention caches
    its few, not the query heads' many), or, where ``cache_heads_merged`` says so
    (narrow heads; grouped heads a step contracts in place), those heads side by
    side as ONE 'head' ``kv_heads x head_dim`` wide. Latent attention: ``k`` is the rotary
    key the heads share, ``v`` the normed latent, which is the absorbed form's
    value and the rest of its key; the 'one head' is every head's. With layers of
    several kinds (``layer_kinds``) L counts the WHOLE-context ATTENTION layers
    alone (``cache_layers``): a window layer keeps a ring, a conv layer no token.
    With ``layer_passes`` > 1 a pass attends to the keys and values that the SAME
    pass wrote at earlier positions (they are functions of the pass's own input), so
    every (pass, layer) keeps K/V of its own and the leading axis is ``layer_passes``
    x L, PASS-MAJOR: pass r of layer l lies at r x L + l (ONE rule: ``_layer_loop``
    hands the blocks that index and ``attend`` writes and reads there; the weights
    have L layers, the cache more). Every helper below moves the leading axis as it
    finds it.

    RINGS, under ``RING``, ``{leaf: (positions, heads, width)}``: what a WINDOW
    layer keeps, [L_window, B, R, heads, width]: the last R = ``local_attn_window``
    positions of a sequence, position p at index p mod R, whatever ``Smax`` is.
    R is the window itself: a step at position p reads p - R + 1 ... p, exactly
    the R entries the ring holds once p is written, so no entry is read that a
    mask must hide for being too old, and R = 128 is one lane tile. A block of
    T > 1 tokens entering at a position past 0 (a CHUNK of a prompt) would need R
    >= window + T - 1 to still hold what its first query sees once it is written;
    the ring stays R = window all the same, because the block attends over [ring ;
    block] BEFORE the write and the ring then takes its last R LIVE rows
    (``_cache_attention``): rings of window + chunk would cost a slot chunk x
    window layers more positions (Mellum2: 5.50 GB where 4.70 are held) and every
    decode step a mask over them. A ring cannot be sliced at an old position nor
    rolled back (a verify block is refused for it): the helpers move it WHOLE, as
    they do state. Absent for a model without window layers.

    Per-SEQUENCE leaves, under ``STATE``, ``{leaf: (shape, dtype or None for the
    cache's)}``: state with no position axis, [L, B, *shape] in the cache, that
    every step overwrites whole and nothing can slice by position. A
    state-space mixer's float32 state [heads, head width, state size] (it
    accumulates over thousands of steps) and the convolution's tail, the last
    ``ssm_conv_kernel - 1`` rows of its input, in EVERY layer; or, for a model
    with ``layer_operators``, the gated short convolution's tail alone, the last
    ``conv_kernel - 1`` rows of its filter's input [conv_kernel - 1, hidden_size],
    in the CONV layers alone (``cache_layers`` says how many keep it); or the gated
    delta rule's float32 matrix [value heads, head width, head width] and its
    filter's tail [conv_kernel - 1, q | k | v channels], in the DELTA layers alone.
    Absent for a model without."""
    if cfg.kv_lora_rank:
        return {"k": (1, cfg.qk_rope_head_dim), "v": (1, cfg.kv_lora_rank)}
    layout = {"k": (cfg.kv_heads, cfg.head_dim), "v": (cfg.kv_heads, cfg.value_head_dim)}
    rings = {name: (cfg.local_attn_window,) + tail for name, tail in layout.items()}
    if cache_heads_merged(cfg):
        layout = {name: (1, heads * width) for name, (heads, width) in layout.items()}
    if cfg.window_layers:
        layout[RING] = rings  # a ring keeps its heads: its step reads R positions, not Smax
    if cfg.ssm_state_size:
        layout[STATE] = {
            "ssm": ((cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state_size), jnp.float32),
            "conv": ((cfg.ssm_conv_kernel - 1, cfg.ssm_conv_dim), None)}
    elif cfg.conv_layers:
        layout[STATE] = {"conv": ((cfg.conv_kernel - 1, cfg.hidden_size), None)}
    elif cfg.delta_layers:
        width = cfg.delta_head_dim
        layout[STATE] = {
            "delta": ((cfg.delta_value_heads, width, width), jnp.float32),
            "conv": ((cfg.conv_kernel - 1, cfg.delta_conv_dim), None)}
    return layout


LANES = 128  # the chip's vector lanes: the minor dimension an array is tiled by

# Operations a byte of K/V read up to which a block contracts a cache of rows where
# they lie (``_rows_attention``): it does (query heads x block rows) of them, and 240
# is the v5e's ridge (197 TFLOP/s over 819 GB/s), so up to there the block costs the
# read of the rows and nothing more. Only a step was timed on the chip (PERF.md §6,
# PR 45); verify blocks of 4 and 7 rows were compiled for it at LFM2's cell (no slice
# or copy of a layer; the temporaries are the block's float32 scores, 0.27 and 0.55
# GB); nothing is taken past the ridge.
ROWS_OPS_PER_BYTE = 240


def cache_heads_merged(cfg: TransformerConfig) -> bool:
    """Whether the cache keeps a token's K/V heads side by side as ONE row
    ``kv_heads x head_dim`` wide ([L, B, Smax, 1, heads x width]) and not as
    [..., heads, width]. Two reasons, either is enough, and both need the heads
    together to fill whole lanes:

    a head NARROWER than the chip's 128 lanes. A [..., 8, 64] leaf is tiled by its
    last two dimensions and half of every tile is padding; the decode program then
    copies the WHOLE cache into the padded form at entry and back at exit (2 x 1.5 GB
    of temporaries and some 8 ms a step at 128 slots x 3,072, compiled for the chip
    at LFM2's widths). Merged, the leaf is as wide as its heads together and lies
    compact;

    GROUPED heads (``kv_heads < num_heads``) of any width. A step's grouped
    contraction over [B, Smax, Hkv, Dh] has the K/V head as a batch dimension, which
    is not a leading dimension of the stack: the compiler slices the layer out as an
    operation of its own (Falcon-H1, [1, 64, 2048, 4, 128]: 2 x 134 MB written and
    read again a layer a step). Over a row the step contracts in place
    (``_rows_attention``, by ``cache_rows_step``'s rule; a model with more query heads
    than ``ROWS_OPS_PER_BYTE`` would never take it and keeps its heads), and a
    [..., Hkv, Dh] leaf VIEWED as rows is a materialised reshape of the layer (the
    tiles differ), so the cache itself holds the rows.

    Either way the leaf is the layer loop's carry as it is held; a block that fills
    its cache attends to itself and never reads it, and a block too long for the rows
    form (a chunk) views the layer as heads where it reads it. Plain XLA attention
    alone: the Pallas decode kernel takes [L, B, Smax, H, Dh] stacks; a latent is one
    'head' already; a window layer's ring keeps its heads whatever the whole-context
    layers do, and so does ONE whole-context layer beside rings (K-EXAONE: the compiler
    reads it in place with the re-layout inside the contraction's own fusion; no K/V
    slice or copy stands in its program). SEVERAL whole-context layers beside rings are
    a stack again and grouped heads merge there as anywhere (Mellum2, [2, 32, 32768, 4,
    128]: as heads each layer's K and V left the stack as a 268 MB copy every step,
    12.2 of a 31.5 ms step, PERF.md §6 PR 59). Multi-head attention at the lanes' width
    (BLOOM, OLMoE, Pythia) has nothing to gain. A row is one 'head' and replicates over a
    mesh's tensor axis (``kv_slot_cache_spec``): grouped heads that the process's
    active mesh (``Model.set_mesh``, as ``expert_bank_form`` reads it) would shard
    over that axis stay heads, each shard holding its own; narrow heads merge there
    too, as they have since PR 42 (the padded copy of the whole cache costs more)."""
    together = cfg.kv_heads * cfg.head_dim
    narrow = cfg.head_dim % LANES != 0
    grouped = cfg.kv_heads < cfg.num_heads <= ROWS_OPS_PER_BYTE
    if grouped and _ACTIVE_MESH[0] is not None:
        from ..parallel.sharding import batch_and_head_axes

        grouped = batch_and_head_axes(_ACTIVE_MESH[0], 1, cfg.kv_heads)[1] is None
    if cfg.window_layers:  # beside rings: grouped heads of a STACK of whole-context layers
        narrow, grouped = False, grouped and cache_layers(cfg)["tokens"] > 1
    return (not cfg.kv_lora_rank and cfg.decode_attn == "xla"
            and (narrow or grouped) and together % LANES == 0
            and cfg.value_head_dim == cfg.head_dim)


def cache_rows_step(cfg: TransformerConfig, T: int = 1) -> bool:
    """Whether a block of ``T`` tokens a row that READS the cache contracts its rows
    where they lie (``_rows_attention``): the cache holds rows of grouped heads and
    the block is a step, or short enough (a verify block of a few drafts) that its
    H x T operations a byte stay under the chip's ridge. A longer block (a chunk of
    a prompt: ONE row of the batch) views the layer as heads and pays the grouped
    form's slice and copy once for many tokens."""
    return (cache_heads_merged(cfg) and cfg.kv_heads < cfg.num_heads
            and T * cfg.num_heads <= ROWS_OPS_PER_BYTE)


def cache_layers(cfg: TransformerConfig) -> dict:
    """How many layers keep each kind of ``cache_layout``'s leaves, as the leading
    axis of those leaves has it: ``"tokens"`` the whole-context attention layers
    (x ``layer_passes``: a (pass, layer) keeps its own K/V, ``cache_layout``),
    ``RING`` the window layers, ``STATE`` the layers with per-sequence state (every
    layer of a model with a state-space mixer; the conv or delta layers of one with
    ``layer_operators``; 0 for any other)."""
    n_window, n_op = len(cfg.window_layers), len(cfg.stateful_layers)
    return {"tokens": cfg.layer_passes * (cfg.num_layers - n_window - n_op), RING: n_window,
            STATE: n_op or (cfg.num_layers if cfg.ssm_state_size else 0)}


def token_leaves(tree: dict) -> dict:
    """The per-token leaves of a cache tree or of a layout: those with a position
    axis ``Smax`` long that a window can be cut out of."""
    return {name: leaf for name, leaf in tree.items() if name not in (STATE, RING)}


def _whole_leaves(tree: dict) -> dict:
    """The subtrees the helpers move WHOLE, a sequence at a time: the rings and
    the per-sequence state, each [L, B, ...] with the sequence on axis 1."""
    return {name: tree[name] for name in (RING, STATE) if name in tree}


def cache_bytes_per_token(cfg: TransformerConfig, dtype=None) -> int:
    """Bytes one token takes in ONE layer of the cache that keeps every token (a
    whole-context layer's per-token leaves; a window layer keeps
    ``local_attn_window`` positions of as many bytes each, ``cache_ring_bytes``)."""
    values = sum(heads * width for heads, width in token_leaves(cache_layout(cfg)).values())
    return values * jnp.dtype(dtype or cfg.dtype).itemsize


def cache_ring_bytes(cfg: TransformerConfig, dtype=None) -> int:
    """Bytes one SEQUENCE's rings take over ALL the window layers (0 for a model
    with none): constant in the sequence's length."""
    values = sum(math.prod(shape) for shape in cache_layout(cfg).get(RING, {}).values())
    return len(cfg.window_layers) * values * jnp.dtype(dtype or cfg.dtype).itemsize


def cache_state_bytes(cfg: TransformerConfig, dtype=None) -> int:
    """Bytes one SEQUENCE's per-sequence leaves take in ONE layer of the cache that
    keeps them (0 for a model that keeps none; ``cache_layers(cfg)[STATE]`` layers do)."""
    return sum(math.prod(shape) * jnp.dtype(own or dtype or cfg.dtype).itemsize
               for shape, own in cache_layout(cfg).get(STATE, {}).values())


def init_cache(cfg: TransformerConfig, batch: int, max_len: int, dtype=None):
    """Allocate an empty cache for ``batch`` sequences of up to ``max_len``."""
    dtype = dtype or cfg.dtype
    layout, n = cache_layout(cfg), cache_layers(cfg)
    cache = {name: jnp.zeros((n["tokens"], batch, max_len) + tail, dtype)
             for name, tail in token_leaves(layout).items()}
    if RING in layout:
        cache[RING] = {name: jnp.zeros((n[RING], batch) + shape, dtype)
                       for name, shape in layout[RING].items()}
    if STATE in layout:
        cache[STATE] = {name: jnp.zeros((n[STATE], batch) + shape, own or dtype)
                        for name, (shape, own) in layout[STATE].items()}
    return cache


def cache_len(cache) -> int:
    """Smax of a cache tree: the position axis of its per-token leaves
    [L, B, Smax, heads, width]."""
    return jax.tree.leaves(token_leaves(cache))[0].shape[2]


def cache_dtype(cache):
    """The dtype a cache tree's per-token leaves are held in."""
    return jax.tree.leaves(token_leaves(cache))[0].dtype


def _slot_whole(tree: dict, slot):
    """Row ``slot`` of leaves [L, B, ...] -> [L, 1, ...], whole."""
    return jax.tree.map(lambda c: lax.dynamic_slice(
        c, (0, slot) + (0,) * (c.ndim - 2), (c.shape[0], 1) + c.shape[2:]), tree)


def slice_cache_slot(cache, slot, length: int, start=0):
    """Read one sequence's window out of a slot cache: every per-token leaf
    [L, B, Smax, heads, width] -> [L, 1, length, heads, width] at row ``slot``,
    positions [start, start+length); every ring and per-sequence leaf [L, B, ...]
    -> [L, 1, ...], whole (they have no positions to cut at: the sequence's
    rings and state are those after the last step that wrote them, whatever
    window is asked for). ``slot`` and ``start`` may be traced int32 scalars —
    the caller's program stays compile-stable across slots/offsets; ``length`` is
    static: it picks the compiled program.

    The serving engine's chunked prefill and prefix-cache copies both run on
    these windows: chunk programs slice a slot out, extend it through
    ``apply_with_cache`` at the chunk's offset, and write back only the
    chunk's region; prefix fetch/store move windows between the slot cache
    and the prefix pool."""
    if length > cache_len(cache):
        raise ValueError(f"cache window ({length}) exceeds cache length {cache_len(cache)}")
    slot = jnp.asarray(slot, jnp.int32)
    start = jnp.asarray(start, jnp.int32)
    out = jax.tree.map(
        lambda c: lax.dynamic_slice(c, (0, slot, start, 0, 0),
                                    (c.shape[0], 1, length) + c.shape[3:]), token_leaves(cache))
    out.update(_slot_whole(_whole_leaves(cache), slot))
    return out


def update_cache_slot(cache, window, slot, start=0):
    """Write a window into row ``slot`` of a slot cache — the inverse of
    ``slice_cache_slot``: every per-token leaf [L, 1, W, heads, width] at
    positions [start, start+W) (one ``dynamic_update_slice`` per leaf), every
    ring and per-sequence leaf [L, 1, ...] over the row's WHOLE (a prefill leaves
    nothing of the slot's previous request behind). ``slot``/``start`` are traced
    scalars: one compiled program regardless of which slot/offset is written."""
    slot = jnp.asarray(slot, jnp.int32)
    start = jnp.asarray(start, jnp.int32)
    out = jax.tree.map(
        lambda c, w: lax.dynamic_update_slice(c, w.astype(c.dtype), (0, slot, start, 0, 0)),
        token_leaves(cache), token_leaves(window))
    out.update(jax.tree.map(
        lambda c, w: lax.dynamic_update_slice(c, w.astype(c.dtype),
                                              (0, slot) + (0,) * (c.ndim - 2)),
        _whole_leaves(cache), _whole_leaves(window)))
    return out


def fresh_cache_state(cache, fresh):
    """``cache`` with its per-sequence leaves zeroed where ``fresh`` (a traced
    bool scalar): a sequence's first chunk starts from nothing, whatever the
    slot's last request left. A tree without such leaves comes back as it is."""
    if STATE not in cache:
        return cache
    return {**cache, STATE: jax.tree.map(
        lambda c: jnp.where(fresh, jnp.zeros((), c.dtype), c), cache[STATE])}


def cached_attention(q, k_cache, v_cache, pos, *, bias=None, block: int = 1):
    """Attention of q [B,T,H,Dh] against a [B,Smax,H,Dh] cache whose valid
    keys are [0, pos+T): the causal mask with offset ``pos`` covers the
    prefix, the new block's internal causality, and the padding tail.
    ``pos`` may be a scalar (lock-step batch) or a per-row [B] vector
    (continuous batching: each slot at its own position). ``block`` > 1: the mask is
    causal between blocks of that many positions (``block_visible``): a block of T =
    ``block`` rows entering at a multiple of it sees [0, pos + T) from every row."""
    return xla_attention(q, k_cache, v_cache, causal_offset=pos, bias=bias, block=block)


# The float32 score matrix [B, H, T, Smax] of ONE layer, in bytes, up to which
# a block that fills its cache attends densely; above it the block goes
# through the flash forward kernel. Measured, not configured (PERF.md §6,
# PR 30: ``SlotWorker.prefill`` timed both ways on one v5e at BLOOM-1b7's
# widths, 16 heads): while XLA keeps the scores in VMEM the dense form is 3-7%
# ahead (128 to 1280 rows, 100 MiB: 33.3 ms against 35.7), and once they spill
# to HBM it is behind by up to half (1536 rows, 144 MiB: 44.5 against 37.8;
# 2048 rows, 256 MiB: 89.5 against 46.3). Compiled for other head counts the
# spill follows the bytes, not the rows (98 MiB stays, 121 MiB does not).
DENSE_SCORE_BYTES = 100 * 2 ** 20


def cache_attention_form(num_heads: int, B: int, T: int, Smax: int, lock_step: bool = True) -> str:
    """``"flash"`` or ``"dense"``: how ``T`` new tokens a row attend in a cache
    ``Smax`` long, read from the shapes alone. A lock-step block as long as its
    cache (it can only enter at position 0) is plain causal self-attention, and
    the kernel is the cheaper form of it once the dense form's score matrix is
    over ``DENSE_SCORE_BYTES``: serving's long prefill buckets. Decode steps,
    per-row positions (verify, chunk) and a prompt shorter than its cache are
    dense. ``_cache_attention`` traces by this and ``SlotWorker.prefill``
    labels its span by it."""
    fills_cache = lock_step and T == Smax
    return "flash" if fills_cache and 4 * B * num_heads * T * Smax > DENSE_SCORE_BYTES else "dense"


# Cached positions a step of ``_blocks_attention``'s walk holds: 512 keys of 4 K/V heads
# against 2,048 rows of 32 query heads are 134 MB of float32 scores, the most a step makes.
CHUNK_KEY_BLOCK = 512


def cache_chunk_form(cfg: TransformerConfig, B: int, T: int, Smax: int) -> str:
    """``"blocks"`` or ``"dense"``: how a block of ``T`` > 1 tokens a row that READS a
    whole-context layer's cache ``Smax`` long (a chunk entering at its own position, a
    verify block) attends there, from what the code can see. Densely over all ``Smax``
    positions under the causal mask while those scores fit ``DENSE_SCORE_BYTES``;
    beyond, over the key blocks up to the newest position and no further
    (``_blocks_attention``): a 2,048-row chunk in a slot of 32,768 would make 8.6 GB of
    scores a layer where its queries see ``start + i`` keys. ONE walk, in XLA, on every
    platform and mesh: a Pallas kernel of the same walk tied with it in the chunk
    program it was written for (Mellum2's, 2,048 rows over 32,768: 41.3 / 42.1 / 49.0 /
    60.0 ms with the kernel at prefixes of 0 / 4,096 / 14,336 / 28,672 against 39.3 /
    41.9 / 50.4 / 61.8, PERF.md §6 PR 59) and went; a ``perf_opt`` has to earn it.
    Latent attention (its absorbed form), alibi (its bias is [T, Smax]), a block short
    enough for the rows form and a cache no key block divides stay dense.
    ``_cache_attention`` traces by this and ``SlotWorker.chunk`` labels its span by it
    (``attn_chunk``)."""
    over = 4 * B * cfg.num_heads * T * Smax > DENSE_SCORE_BYTES
    plain = not cfg.kv_lora_rank and cfg.pos_emb != "alibi" and not cache_rows_step(cfg, T)
    return "blocks" if T > 1 and over and plain and Smax % CHUNK_KEY_BLOCK == 0 else "dense"


def _blocks_attention(q, k_l, v_l, positions, mask_block: int = 1):
    """Attention of a block q [B, T, H, D] at ``positions`` [B, T] over ONE layer of a
    cache k_l / v_l [B, Smax, Hkv, D] that already holds the block's own keys: the
    softmax taken online over key blocks of ``CHUNK_KEY_BLOCK`` positions, from the
    first to the one that holds the newest position and no further (a loop of a traced
    trip count), each under the causal mask of the absolute positions; a group of
    query heads against its one K/V head where it lies. The work follows the keys the
    block's queries see, not ``Smax``. ``mask_block`` > 1: each query sees to the end of
    its own block of that many positions (``block_visible``), and the walk goes as far."""
    B, T, H, D = q.shape
    positions = block_visible(positions, mask_block)
    Hkv, block = k_l.shape[2], CHUNK_KEY_BLOCK
    qg = q.reshape(B, T, Hkv, H // Hkv, D)
    scale = 1.0 / math.sqrt(D)
    newest = jnp.minimum(jnp.max(positions), k_l.shape[1] - 1)

    def step(j, carry):
        m, norm, acc = carry
        k_b, v_b = (lax.dynamic_slice_in_dim(c, j * block, block, axis=1) for c in (k_l, v_l))
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_b).astype(jnp.float32) * scale
        seen = (j * block + jnp.arange(block))[None, None, :] <= positions[:, :, None]
        s = jnp.where(seen[:, None, None], s, NEG_BIAS)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        fade = jnp.exp(m - m_new)
        acc = acc * fade[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p.astype(q.dtype), v_b).astype(jnp.float32)
        return m_new, norm * fade + jnp.sum(p, axis=-1), acc

    lead = (B, Hkv, H // Hkv, T)
    m, norm, acc = lax.fori_loop(
        0, newest // block + 1, step,
        (jnp.full(lead, NEG_BIAS, jnp.float32), jnp.zeros(lead, jnp.float32),
         jnp.zeros(lead + (v_l.shape[-1],), jnp.float32)))
    out = (acc / norm[..., None]).astype(q.dtype)  # block 0 holds key 0: every row saw a key
    return out.transpose(0, 3, 1, 2, 4).reshape(B, T, H, v_l.shape[-1])


def cache_step_form(cfg: TransformerConfig) -> str:
    """``"latent"``, ``"dense"``, ``"dense+ring"`` or ``"ring"``: how a step that
    READS the slot cache (decode, verify, chunk) attends, for the spans' ``attn``:
    latent attention in its absorbed form over the cached latent, plain attention
    densely (or through the Pallas decode kernel) over per-head K/V, a window
    layer over its ring of ``local_attn_window`` positions (``+``: the model has
    layers of both kinds)."""
    if cfg.kv_lora_rank:
        return "latent"
    n_window = len(cfg.window_layers)
    return "dense" if not n_window else "ring" if n_window == cfg.num_layers else "dense+ring"


def decode_kernel_block(cfg: TransformerConfig, smax: int, kv_dtype=None):
    """Cached positions a block of the Pallas decode kernel's walk holds, over a
    cache ``smax`` long held in ``kv_dtype`` (the model's own where None): the
    kernel's one rule, ``ops/pallas/decode_attention.block_rows``. None where a
    one-token step does not attend through the kernel (``decode_attn: "xla"``;
    alibi, whose bias stays unfused)."""
    if cfg.decode_attn != "kernel" or cfg.pos_emb == "alibi":
        return None
    from ..ops.pallas.decode_attention import block_rows

    return block_rows(smax, cfg.kv_heads * cfg.head_dim
                      * jnp.dtype(kv_dtype or cfg.dtype).itemsize)


def kv_rows_fetched(live_pos, block: int) -> int:
    """Cache positions of ONE layer the decode kernel's walk fetches for rows whose
    newest positions are ``live_pos`` (a host array): whole blocks of ``block``
    positions (``decode_kernel_block``), ``pos // block + 1`` a row."""
    return int(np.sum(np.asarray(live_pos) // block + 1)) * block


def cache_block_form(cfg: TransformerConfig, bucket: int) -> str:
    """How a prefill block ``bucket`` long that fills its own cache attends, for
    the span's ``attn``: ``cache_attention_form``'s ``"flash"`` / ``"dense"``, with
    ``"+window"`` where the model has window layers (they take the same form by
    the same rule, under the window's mask: the kernel's banded grid,
    ``window_grid_form``, or a [T, T] bias on the dense scores)."""
    form = cache_attention_form(cfg.num_heads, 1, bucket, bucket)
    return form + "+window" if cfg.window_layers else form


def window_grid_form(cfg: TransformerConfig, bucket: int) -> dict:
    """What a prefill span says of the flash forward grid its window layers took
    (nothing where no window layer goes through the kernel): ``window_grid``,
    ``"band"`` where the grid runs over the key blocks the window reaches (the
    window is a constant of the trace: ``_cache_attention`` hands the kernel the
    layer's own) or ``"causal"`` where it ran every block at or under the
    diagonal, and ``window_blocks_pct``, the key blocks it computes as a share of
    those, from the bucket's shapes: ``flash_attention.window_grid``, the rule
    the kernel itself is traced by."""
    if not cfg.window_layers or cache_attention_form(cfg.num_heads, 1, bucket, bucket) != "flash":
        return {}
    from ..ops.pallas.flash_attention import window_grid

    grid, pct = window_grid(bucket, cfg.local_attn_window, cfg.num_heads, cfg.head_dim,
                            cfg.head_dim, jnp.dtype(cfg.dtype).itemsize)
    return {"window_grid": grid, "window_blocks_pct": round(pct, 2)}


def causal_tiles_fact(cfg: TransformerConfig, rows: int, block_q=None, block_k=None) -> dict:
    """``causal_tiles_pct``: the score elements the causal flash kernels compute
    over ``rows`` rows of the model's heads as a % of those at or under the
    diagonal (100: the triangle alone), a constant of the trace from the shapes:
    ``flash_attention.causal_tiles_pct``, the rule the kernel's steps read their
    own schedule by; and ``diag_sub`` (PR 65): the edge of the sub-tiles the
    forward cut the diagonal's tile in, 0 where it ran whole."""
    from ..ops.pallas.flash_attention import causal_tiles_pct, diag_sub

    shape = rows, max(cfg.head_dim, cfg.value_head_dim), jnp.dtype(cfg.dtype).itemsize
    return {"causal_tiles_pct": round(causal_tiles_pct(*shape, block_q, block_k), 2),
            "diag_sub": diag_sub(*shape, block_q, block_k)}


def causal_grid_form(cfg: TransformerConfig, bucket: int) -> dict:
    """What a prefill span says of the flash forward grid its whole-context
    layers took (nothing where none goes through the kernel):
    ``causal_tiles_fact`` at the bucket's rows."""
    whole = cfg.num_layers - len(cfg.stateful_layers) - len(cfg.window_layers)
    if not whole or cache_attention_form(cfg.num_heads, 1, bucket, bucket) != "flash":
        return {}
    return causal_tiles_fact(cfg, bucket)


def expert_bank_form(cfg: TransformerConfig, moe, mesh=None):
    """``"in_place"`` or ``"sliced"`` (None for a model without dropless
    routing): where a program that only runs the forward pass through the cache
    reads a routed layer's three expert banks from, decided from what it is
    handed. In place (``_layer_loop(forward_only=True)`` asks here: layer ``l`` read
    through the grouped GEMM's own group index out of the held ``[L, E, K, N]``
    stacks, no per-layer copy) unless that would cost more than the copy it
    saves: a bank leaf that is not a plain array of the compute dtype (a
    float32-held or quantised ``{"q" | "q4", "s"}`` leaf would be cast or
    dequantised WHOLE, L times one layer's), stacks that ``param_offload``
    streams a slice at a time, or stacks sharded over a mesh axis by the rules
    ``InferenceEngine`` places them by (``[L, E]`` viewed as ``[L * E]`` keeps no
    ``expert`` sharding). ``apply_with_cache`` traces by this on the process's
    active mesh (``Model.set_mesh``, as the sharded attention paths read it) and
    ``SlotWorker`` labels its ``prefill`` and ``chunk`` spans by it on its
    engine's (``expert_bank``)."""
    if cfg.moe_routing != "dropless" or moe is None:
        return None
    bank = moe["experts"]
    plain = all(not isinstance(leaf, dict) and leaf.dtype == jnp.dtype(cfg.dtype)
                for leaf in bank.values())
    if cfg.param_offload or not plain:
        return "sliced"
    mesh = mesh if mesh is not None else _ACTIVE_MESH[0]
    if mesh is not None and mesh.size > 1:
        from ..moe.dropless import dropless_logical_axes
        from ..parallel.sharding import DEFAULT_TP_RULES, spec_from_logical

        axes = dropless_logical_axes()["experts"]
        if any(tuple(spec_from_logical(axes[name], leaf.shape, DEFAULT_TP_RULES, mesh))
               for name, leaf in bank.items()):
            return "sliced"
    return "in_place"


def _cache_attention(cfg: TransformerConfig, B: int, T: int, Smax: int, pos, write_pos=None,
                     live=None, kv_dtype=None):
    """-> (positions [B, T], the block's ``attend``) for T new tokens entering a
    stacked [L, B, Smax, H, Dh] cache tree (held in ``kv_dtype``; the model's own
    where None) at ``pos`` (scalar, or [B] with ``write_pos``: see ``apply_with_cache``).

    ``attend`` writes the new rows into layer ``l`` of the stacks and attends to
    that layer where it lies. Latent attention has its two forms here, chosen
    from the same shapes: a block that fills its cache expands its own latent
    and attends as plain causal attention (``_latent_expand``; the flash kernel
    by ``cache_attention_form``'s rule, at q/k and v heads of two widths);
    every other block attends in the absorbed form over the cached latent
    (``_latent_attention``) and nothing per-head is ever made of the cache. Grouped
    heads over a cache of merged rows (``cache_heads_merged``) have two forms the
    same way: a step, or a block short enough (``cache_rows_step``), contracts the
    rows where they lie (``_rows_attention``); a longer block that reads the cache
    (a chunk) views the layer as heads for ``xla_attention``'s grouped form. The
    stacks are its state, so they stay the layer loop's CARRY: with the cache
    donated the loop's input and output are one buffer; as the scan's xs/ys they
    would be sliced out and restacked layer by layer and copied whole
    (tests/test_chip_compile_serving.py guards it).

    Layers of several kinds (``cfg.layer_kinds``; the loop hands ``attend`` the
    layer's ``kind`` as a Python value). A whole-context layer is the above, at
    its index among the whole-context layers. A WINDOW layer keeps a ring
    (``cache_layout``) and has two forms: a block that STARTS its sequences
    (``pos`` the Python int 0: a prefill) attends to itself under the window's
    mask (flash kernel over the band of blocks the window reaches, the window a
    constant of the trace, or densely with a [T, T] bias, by
    ``cache_attention_form``'s rule on the block's own score matrix) and writes
    its last R LIVE rows (``live``: not a bucket's padding) into the ring, row p
    at p mod R; a one-token step writes at ``pos mod R`` and attends over the
    ring's R entries, each masked by the absolute position it holds (entry r at a
    query position p holds p - ((p - r) mod R); negative: never written by this
    sequence, whatever an earlier one left there). Nothing ``Smax`` long is made
    for a window layer. A block of several tokens entering past position 0 (a
    chunk: ``pos`` traced) would overwrite ring entries its own first queries still
    see, so it attends over [ring ; block] BEFORE the write: the window's positions
    before the block, out of the ring in position order, and the block's own keys
    are one run of consecutive positions, R + T long, on which causal attention
    under the window's mask is what the model requires (the same two forms, by the
    same rule on R + T rows); the ring then takes the block's last R LIVE rows. A
    block written apart from where it attends (``write_pos``: a verify block, whose
    rejected tail would have to be rolled back) is refused by name.

    A block that READS a whole-context layer's cache (a chunk, a verify block)
    attends densely over ``Smax`` while those scores are small and over the key
    blocks up to its newest position beyond (``cache_chunk_form``,
    ``_blocks_attention``).

    ``attn_block_length`` > 1 (generation by diffusion over blocks): every form
    above takes the mask that is causal between blocks (``block_visible``) from the
    ABSOLUTE positions, which stay what turns the rotary: row i of a block entering
    at ``pos`` embeds and turns at ``pos + i`` and sees every key up to its block's
    last position, the block's own rows among them, written before it attends."""
    starts = isinstance(pos, (int, np.integer)) and int(pos) == 0
    pos = jnp.asarray(pos, jnp.int32)
    vector_pos = pos.ndim >= 1
    steps = jnp.arange(T)
    if vector_pos:
        positions = pos[:, None] + steps[None, :]  # [B, T]
    else:
        positions = pos + jnp.broadcast_to(steps[None, :], (B, T))

    # A lock-step block as long as the cache (serving prefill: a local cache
    # of the bucket's length) IS the layer's cache once written, so attention
    # reads the block itself: through the flash kernel where the dense scores
    # would spill (no score matrix, alibi from block positions), otherwise
    # densely with QK^T and the softmax in one fusion; read back through the
    # stack, Pythia's 2048-token prefill took 103 ms on the chip instead of 73
    # (PERF.md §6, PR 25).
    lock_step = not vector_pos
    fills_cache = lock_step and T == Smax
    use_flash = cache_attention_form(cfg.num_heads, B, T, Smax, lock_step) == "flash"
    windowed = set(cfg.window_layers)  # a window layer's block attends to itself: [T, T] scores
    # ... or, entering past position 0, to the ring's positions before it and itself
    ring_rows = T if starts or T == 1 else cfg.local_attn_window + T
    window_flash = bool(windowed) and cache_attention_form(
        cfg.num_heads, B, ring_rows, ring_rows) == "flash"
    # a block that reads a whole-context layer's cache: over the live key blocks, or densely
    chunk_form = "dense" if fills_cache else cache_chunk_form(cfg, B, T, Smax)
    if use_flash or window_flash:
        from ..ops.pallas.flash_attention import flash_attention_sharded

    slopes = alibi_slopes(cfg.num_heads) if cfg.pos_emb == "alibi" else None
    bias = None
    if slopes is not None and not use_flash:
        # alibi distances vs absolute key positions, rows = new tokens
        if vector_pos:
            dist = jnp.arange(Smax)[None, None, :] - positions[:, :, None]  # [B,T,Smax]
            bias = (slopes[None, :, None, None] * dist[:, None]).astype(jnp.float32)
        else:
            dist = jnp.arange(Smax)[None, :] - (pos + steps[:, None])
            bias = (slopes[:, None, None] * dist[None]).astype(jnp.float32)[None]

    # a layer's index among the layers of ITS kind: where it lies in that kind's stacks
    if windowed:
        if slopes is not None:
            raise NotImplementedError(
                "window layers (local_attn_layers) through the cache with pos_emb='alibi' have "
                "no code: the ring's bias from the positions its entries hold is not written")
        if T > 1 and write_pos is not None:
            raise NotImplementedError(
                f"a block of {T} tokens written apart from where it attends (write_pos: "
                "speculative verification) into a window layer's ring has no code: the ring "
                f"keeps local_attn_window = {cfg.local_attn_window} positions, and a rejected "
                "tail cannot be rolled back out of it")
    in_kind = None
    if windowed or cfg.stateful_layers:  # not every layer keeps per-token K/V
        in_kind = jnp.asarray(_index_in_kind(cfg), jnp.int32)

    # Single-token decode steps route through the Pallas length-aware kernel
    # (ops/pallas/decode_attention.py — the reference's softmax_context,
    # pt_binding.cpp:1237): it reads only cache blocks up to ``pos`` instead
    # of the dense O(Smax) recompute. Alibi keeps the XLA path (bias unfused).
    # Its work list (the live blocks in row order) is the same for every cache
    # layer of a step, so it is built here, once, and not in the layer loop.
    kernel_block = decode_kernel_block(cfg, Smax, kv_dtype) if T == 1 else None
    use_decode_kernel = kernel_block is not None
    if use_decode_kernel:
        from ..ops.pallas.decode_attention import decode_attention, decode_walk

        walk = decode_walk(pos, B, Smax, kernel_block)

    if vector_pos:
        rows = jnp.arange(B)[:, None]
        write_positions = positions
        if write_pos is not None:
            write_positions = jnp.asarray(write_pos, jnp.int32)[:, None] + steps[None, :]

        def write(c, l, new):
            # per-row scatter into layer l of the stack: row b's block lands
            # at [write_pos[b], +T). mode="drop" is load-bearing: the serving
            # engine passes write_pos=Smax for inactive/prefilling slots so
            # their garbage write is DISCARDED here — a mid-admission slot
            # already holds prefix KV at the low positions, so no in-range
            # parking spot is safe
            return c.at[l, rows, write_positions].set(new.astype(c.dtype), mode="drop")
    else:
        if write_pos is not None:
            raise ValueError("write_pos requires a per-row pos vector")
        write_positions = positions

        def write(c, l, new):
            return lax.dynamic_update_slice(c, new[None].astype(c.dtype), (l, 0, pos, 0, 0))

    def repeat_groups(q, k_l, v_l):
        """Grouped heads for the flash kernel, which takes one K/V head a query
        head: the BLOCK's own keys and values repeated, never the cache's."""
        group = q.shape[2] // k_l.shape[2]
        return (k_l, v_l) if group == 1 else (jnp.repeat(k_l, group, axis=2),
                                              jnp.repeat(v_l, group, axis=2))

    def ring_attend(q, k, v, stacks, l, window):
        ring = stacks[RING]
        R = ring["k"].shape[2]
        slots = jnp.arange(R)
        # the position ring entry r holds for a sequence whose newest position is p
        held = lambda p: p[:, None] - ((p[:, None] - slots[None, :]) % R)  # [B, R]
        if T == 1:  # a step: into the ring at pos mod R (an idle row's write is dropped)
            at = write_positions[:, 0]
            at = jnp.where(at < Smax, at % R, R)
            ring = {name: ring[name].at[l, jnp.arange(B), at].set(
                new[:, 0].astype(ring[name].dtype), mode="drop")
                for name, new in (("k", k), ("v", v))}
            k_l, v_l = (lax.dynamic_index_in_dim(ring[name], l, keepdims=False)
                        for name in ("k", "v"))
            q_pos = positions[:, 0]
            k_pos = held(q_pos)
            seen = (k_pos >= 0) & (q_pos[:, None] - k_pos < window)
            mask = jnp.where(seen, 0.0, NEG_BIAS).astype(jnp.float32)[:, None, None, :]
            attn = xla_attention(q, k_l, v_l, bias=mask, causal=False)
            return attn, {**stacks, RING: ring}
        k_l, v_l = k.astype(ring["k"].dtype), v.astype(ring["v"].dtype)
        # a block's live rows a sequence, counted where each form wants them (a block that
        # starts its sequences behind its attention, as its program has always been traced)
        live_rows = lambda: (jnp.full((B,), T, jnp.int32) if live is None  # noqa: E731
                             else jnp.sum(live.astype(jnp.int32), axis=1))
        if not starts:
            # A block entering at its own position (a chunk): attention over [ring ;
            # block] BEFORE the write. Per row, the R positions before ``start`` (those
            # of them that exist: from position 0) out of the ring in position order,
            # then the block's own keys, are ONE run of consecutive positions from
            # ``base`` on, R + T long; the queries lie in it at ``start - base``, behind
            # rows of zeros. Plain causal self-attention under the window's mask over
            # that run is what the model requires (what lies behind the block is hidden
            # by causality, as a bucket's padding is), so it takes the forms a block that
            # starts its sequences takes, by the same rule on its R + T rows.
            start = positions[:, 0]
            base = jnp.maximum(start - R, 0)
            at = start - base  # [B], 0 .. R
            order = ((base[:, None] + slots[None, :]) % R)[:, :, None, None]
            place = jax.vmap(lambda run, new, i: lax.dynamic_update_slice(run, new, (i, 0, 0)))
            ring_l = {name: lax.dynamic_index_in_dim(ring[name], l, keepdims=False)
                      for name in ("k", "v")}
            # what the ring holds BEHIND the block: the last R LIVE positions, entry r the
            # newest position p = r (mod R) at or under the block's last live one, the
            # block's row p - start where that is in the block, what it held (that same
            # p) where not. Attention reads the ring as it was: the block's first queries
            # still see entries its last rows overwrite
            p = held(start + live_rows() - 1)
            src = jnp.clip(p - start[:, None], 0, T - 1)[:, :, None, None]
            fresh = (p >= start[:, None])[:, :, None, None]
            written = {name: jnp.where(fresh, jnp.take_along_axis(new, src, axis=1), ring_l[name])
                       for name, new in (("k", k_l), ("v", v_l))}
            k_run, v_run = (place(jnp.concatenate(
                [jnp.take_along_axis(ring_l[name], order, axis=1), jnp.zeros_like(new)], axis=1),
                new, at) for name, new in (("k", k_l), ("v", v_l)))
            q_run = place(jnp.zeros((B, R + T) + q.shape[2:], q.dtype), q, at)
            if window_flash:
                attn = flash_attention_sharded(
                    q_run, *repeat_groups(q_run, k_run, v_run), mesh=_ACTIVE_MESH[0],
                    causal=True, window=float(window))
            else:
                dist = jnp.arange(R + T)[:, None] - jnp.arange(R + T)[None, :]
                inside = jnp.where(dist < window, 0.0, NEG_BIAS).astype(jnp.float32)
                attn = xla_attention(q_run, k_run, v_run, bias=inside[None, None])
            attn = jax.vmap(lambda a, i: lax.dynamic_slice_in_dim(a, i, T, axis=0))(attn, at)
            ring = {name: lax.dynamic_update_slice(ring[name], written[name][None],
                                                   (l, 0, 0, 0, 0)) for name in ("k", "v")}
            return attn, {**stacks, RING: ring}
        # a block that starts its sequences: attends to itself under the window's mask
        if window_flash:
            attn = flash_attention_sharded(q, *repeat_groups(q, k_l, v_l), mesh=_ACTIVE_MESH[0],
                                           causal=True, window=float(window))
        else:
            dist = steps[:, None] - steps[None, :]
            inside = jnp.where(dist < window, 0.0, NEG_BIAS).astype(jnp.float32)
            attn = xla_attention(q, k_l, v_l, bias=inside[None, None])
        src = jnp.clip(held(live_rows() - 1), 0, T - 1)[:, :, None, None]  # the last R LIVE rows
        ring = {name: lax.dynamic_update_slice(
            ring[name], jnp.take_along_axis(new, src, axis=1)[None], (l, 0, 0, 0, 0))
            for name, new in (("k", k_l), ("v", v_l))}
        return attn, {**stacks, RING: ring}

    merged = cache_heads_merged(cfg)  # the cache's row is every head's, side by side
    as_row = lambda x: x.reshape(x.shape[:2] + (1, -1)) if merged else x
    as_heads = lambda c: c.reshape(c.shape[:2] + (cfg.kv_heads, -1)) if merged else c
    rows_step = cache_rows_step(cfg, T)  # a short block contracts the rows where they lie

    def attend(q, k, v, stacks, l, lp, kind=None):
        if in_kind is not None:
            if kind is None:
                raise ValueError("a cache with rings, or of the attention layers alone, needs "
                                 "the layer's kind: _layer_loop(forward_only=True)")
            l = in_kind[l]
            if kind[0]:
                return ring_attend(q, k, v, stacks, l, kind[0])
        k_stack, v_stack = write(stacks["k"], l, as_row(k)), write(stacks["v"], l, as_row(v))
        stacks = {**stacks, "k": k_stack, "v": v_stack}  # rings and a mixer's state ride along
        if use_decode_kernel:
            attn = decode_attention(q[:, 0], k_stack, v_stack, pos, layer=l, walk=walk)[:, None]
            return attn, stacks
        if fills_cache:
            k_l, v_l = k.astype(k_stack.dtype), v.astype(v_stack.dtype)
            if cfg.kv_lora_rank:
                k_l, v_l = _latent_expand(cfg, lp, k_l, v_l)
        else:
            k_l, v_l = (lax.dynamic_index_in_dim(c, l, keepdims=False)
                        for c in (k_stack, v_stack))
            if cfg.kv_lora_rank:
                return _latent_attention(cfg, lp, q, k_l, v_l, pos), stacks
            if rows_step:
                return _rows_attention(q, k_l[:, :, 0], v_l[:, :, 0], pos, bias,
                                       cfg.attn_block_length), stacks
            k_l, v_l = as_heads(k_l), as_heads(v_l)
            if chunk_form == "blocks":
                return _blocks_attention(q, k_l, v_l, positions, cfg.attn_block_length), stacks
        if use_flash:
            attn = flash_attention_sharded(q, *repeat_groups(q, k_l, v_l), mesh=_ACTIVE_MESH[0],
                                           causal=True, alibi_slopes=slopes,
                                           mask_block=cfg.attn_block_length)
        else:
            attn = cached_attention(q, k_l, v_l, pos, bias=bias, block=cfg.attn_block_length)
        return attn, stacks

    return positions, attend


def apply_with_cache(
    cfg: TransformerConfig, params: Params, tokens, cache, pos,
    last_only: bool = False, last_index=None, write_pos=None,
    return_routing: bool = False, live=None, return_exit: bool = False,
):
    """tokens [B, T] entering at absolute position ``pos`` -> (logits, updated
    cache). Serves prefill (T=prompt) and decode (T=1). With ``last_only``
    only the final position is projected to the vocab (prefill never
    materializes [B, S, V] — same motivation as the chunked LM loss);
    ``last_index`` (traced scalar) projects only position ``last_index``
    instead — bucketed prefill pads the prompt to the bucket length, so the
    live last token sits mid-sequence, not at T-1.

    ``pos`` may be a scalar (all rows in lock-step — the one-shot generate
    path) or a per-row [B] int32 vector (continuous batching: every cache
    slot decodes at its own absolute position; cache writes become per-row
    scatters and the causal mask is per-row).

    ``write_pos`` (vector-``pos`` path only) decouples where a row's KV is
    WRITTEN from where it attends/embeds: the serving engine passes
    ``write_pos = Smax`` for inactive/prefilling slots so their garbage
    write is dropped by the scatter while their attention position stays 0
    — the length-aware decode kernel then streams one block for an idle
    row instead of the whole cache. None = write at ``pos`` (every other
    caller).

    The layers are training's (``_layer_loop`` over ``_block``), with the cache
    as the loop's carry; a routed model's expert banks are read in place out of
    the held stacks where ``expert_bank_form`` allows (no backward pass here
    wants a layer's slice). With ``return_routing`` (dropless routing only) a third
    value comes back: the experts chosen for each of the tokens given, in every
    routed layer, int32 [layers, B, T, k] — padded and idle rows are routed like
    any other, so a caller that counts load masks them itself. With ``return_exit``
    (``exit_gate`` only) the exit distribution of each of the tokens given comes
    last: float32 [B, T, layer_passes], padded and idle rows' too.

    ``live`` [B, T] bool (None: every row): the rows that are a sequence's own,
    leading each row of the batch — not a bucket's padding, not an idle slot's
    ride-along token. Attention needs no such mask (causality hides the padded
    tail and ``write_pos`` drops an idle row's write); a state-space mixer does:
    a recurrence has no causality to hide behind, so its state moves on live
    rows only (``_ssm_mixer``), and a padded block (``last_index``) without the
    mask is refused for it; so does a window layer's ring, which keeps a
    block's last LIVE rows (``_cache_attention``). ``pos`` the Python int 0 says
    that the block STARTS its sequences, which a window layer must know."""
    _routing_asked(cfg, return_routing)
    if not cfg.causal:
        raise NotImplementedError("KV-cache decoding is causal-only (encoders use apply())")
    if cfg.attn_impl == "sparse":
        raise NotImplementedError(
            "block-sparse decode is not wired up — dense cache attention would "
            "silently change the attention pattern the model trained with"
        )
    B, T = tokens.shape
    if live is None and last_index is not None and (STATE in cache or RING in cache):
        raise ValueError(
            "apply_with_cache(last_index=...) pads the block past its live last token: "
            "per-sequence state (a state-space mixer's, a short convolution's tail) needs "
            "`live` (the rows that are the sequence's own), or it runs on over the padding; so "
            "does a window layer's ring, or it keeps the padding's rows")
    params = _stream_top_level(cfg, params)
    positions, attend = _cache_attention(cfg, B, T, cache_len(cache), pos, write_pos, live,
                                         cache_dtype(cache))
    x, _ = embed(cfg, params, tokens, positions)
    after_pass = _after_pass(cfg, params, return_exit)
    x, cache, _, chosen, gates = _layer_loop(
        cfg, params["layers"], params.get("moe"), x, dict(cache),
        positions=positions, attend=attend, lead=params.get("dense_ffn"), live=live,
        forward_only=True, after_pass=after_pass)
    if last_index is not None:
        # bucketed prefill: the live last token sits at ``last_index``
        # (prompt_len - 1), not at T-1 — project only that position
        x = lax.dynamic_slice_in_dim(x, jnp.asarray(last_index, jnp.int32), 1, axis=1)
    elif last_only:
        x = x[:, -1:]
    logits = _lm_head(cfg, params, x, normed=after_pass is not None)
    return ((logits, cache) + ((chosen,) if return_routing else ())
            + ((exit_distribution(gates),) if return_exit else ()))


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def effective_loss_impl(cfg: TransformerConfig, mesh=None, n_rows=None):
    """Resolve the loss implementation that will ACTUALLY run -> (impl, reason).

    One predicate shared by ``lm_loss_from_hidden`` (trace time) and the
    engines (init time, via log_dist) so a silent fused→chunked fallback can
    never diverge from what was reported. ``mesh`` defaults to the active
    mesh; ``n_rows`` (= B*S) enables the shape-alignment check — pass None
    for the shape-independent answer (engine init, before batches exist)."""
    if cfg.loss_impl != "fused_xent":
        return "chunked", "configured"
    mesh = mesh if mesh is not None else _ACTIVE_MESH[0]
    if mesh is not None and mesh.shape.get("model", 1) > 1:
        # a vocab-sharded head under TP: pallas_call over the sharded head
        # would force replication/all-gather of the full [D, V] head, silently
        # defeating the kernel's HBM savings — keep the chunked einsum, which
        # XLA partitions over the vocab shards
        return "chunked", (
            "tensor-parallel mesh (model axis > 1) shards the vocab head; "
            "the fused_xent Pallas kernel cannot partition it — using the "
            "chunked loss, which XLA partitions over the vocab shards"
        )
    if n_rows is not None:
        br = cfg.loss_fused_block_rows or 128
        bv = cfg.loss_fused_block_v or 128
        if not (n_rows % 128 == 0 and n_rows % br == 0
                and br % 128 == 0 and bv % 128 == 0):
            return "chunked", (
                f"rows (B*S={n_rows}) must be divisible by 128 and by "
                f"loss_fused_block_rows ({cfg.loss_fused_block_rows or 'auto'}), "
                f"with 128-aligned block_rows/block_v"
            )
    return "fused_xent", "configured"


def lm_loss_from_hidden(cfg: TransformerConfig, params: Params, hidden, labels,
                        _top_streamed: bool = False) -> jnp.ndarray:
    """Token-mean next-token cross-entropy from final hidden states [B,S,d],
    with the vocab projection chunked over the sequence so [B,S,V] logits are
    never materialized (see ``causal_lm_loss``). Shared by the plain and
    pipelined model families."""
    head = _head_matrix(params, (lambda t: t) if _top_streamed else _param_streamer(cfg))
    # the logits' multiplier, on the narrow side
    hidden = _times(hidden, cfg.multiplier("lm_head_multiplier"))

    _n_rows = hidden.shape[0] * hidden.shape[1]
    _impl, _reason = effective_loss_impl(cfg, n_rows=_n_rows)
    if cfg.loss_impl == "fused_xent" and _impl != "fused_xent":
        import warnings

        warnings.warn(
            f"loss_impl='fused_xent' falling back to the chunked loss "
            f"({_reason}) — the fused kernel's HBM savings do NOT apply",
            stacklevel=2,
        )
    if _impl == "fused_xent":
        from ..ops.pallas.fused_xent import fused_linear_xent

        B, S, D = hidden.shape
        nll = fused_linear_xent(
            hidden.reshape(B * S, D),
            head.astype(hidden.dtype),
            labels.reshape(B * S),
            block_rows=cfg.loss_fused_block_rows or None,
            block_v=cfg.loss_fused_block_v or None,
        )
        mask = (labels.reshape(B * S) >= 0).astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)

    chunk = cfg.loss_chunk_size
    S = hidden.shape[1]
    if chunk <= 0 or S % chunk != 0 or S <= chunk:
        logits = jnp.einsum("bsd,dv->bsv", hidden, head.astype(hidden.dtype)).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        mask = (labels >= 0).astype(jnp.float32)
        return jnp.sum((logz - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)

    n_chunks = S // chunk
    h_c = hidden.reshape(hidden.shape[0], n_chunks, chunk, hidden.shape[-1]).swapaxes(0, 1)
    l_c = labels.reshape(labels.shape[0], n_chunks, chunk).swapaxes(0, 1)

    @jax.checkpoint  # recompute chunk logits in backward — never keep [B,S,V]
    def chunk_loss(carry, hl):
        h, lab = hl
        logits = jnp.einsum("bsd,dv->bsv", h, head.astype(h.dtype)).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
        mask = (lab >= 0).astype(jnp.float32)
        nll_sum, tok_sum = carry
        return (nll_sum + jnp.sum((logz - gold) * mask), tok_sum + jnp.sum(mask)), None

    (nll_sum, tok_sum), _ = lax.scan(chunk_loss, (jnp.zeros(()), jnp.zeros(())), (h_c, l_c))
    return nll_sum / jnp.maximum(tok_sum, 1.0)


def split_batch(batch: dict) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Normalize {'tokens'} / {'input_ids','labels'} batches to (inputs, labels)."""
    tokens = batch.get("tokens", batch.get("input_ids"))
    labels = batch.get("labels")
    if labels is None:
        return tokens[:, :-1], tokens[:, 1:]
    return tokens, labels


def causal_lm_loss(
    cfg: TransformerConfig,
    params: Params,
    batch: dict,
    rng: Optional[jax.Array] = None,
    step=None,
) -> jnp.ndarray:
    """Next-token cross-entropy. batch: {'tokens': [B,S]} or
    {'input_ids': ..., 'labels': ...} (HF spelling accepted). ``rng`` enables
    dropout for this step (training); None = deterministic.

    The vocab projection is chunked over the sequence (``loss_chunk_size``)
    so the [B, S, vocab] logits tensor is never materialized — on a 16 GB
    v5e this is what lets 125M-class models train at batch 64+.
    """
    if cfg.attn_block_length > 1:
        raise NotImplementedError(
            "causal_lm_loss with attn_block_length > 1 has no code: a model that generates "
            "by diffusion over blocks is trained to denoise masked blocks under a noise "
            "schedule, which its configuration does not give; next-token cross-entropy "
            "under the block mask would let a position see its own label")
    inputs, labels = split_batch(batch)
    # stream top-level leaves ONCE for both the embedding and the (tied)
    # head use — see apply()'s _top_streamed note
    params = _stream_top_level(cfg, params)
    hidden, aux = apply(
        cfg, params, inputs, return_hidden=True, with_aux=True, rng=rng, step=step,
        _top_streamed=True,
    )  # [B, S, d]
    return lm_loss_from_hidden(
        cfg, params, hidden, labels, _top_streamed=True) + cfg.moe_aux_coeff * aux


class Model:
    """Thin bundle handed to ``deepspeed_tpu.initialize``: init/apply/loss +
    logical axes (the engine's contract; see runtime/engine.py)."""

    def __init__(self, cfg: TransformerConfig, loss_fn: Optional[Callable] = None):
        self.config = cfg
        self._loss = loss_fn or causal_lm_loss
        import inspect

        try:
            sig = inspect.signature(self._loss).parameters
            self._loss_takes_rng = "rng" in sig
            self._loss_takes_step = "step" in sig
        except (TypeError, ValueError):
            self._loss_takes_rng = False
            self._loss_takes_step = False
        self.mesh = None  # set by the engine for MoE sharding constraints

    def set_mesh(self, mesh):
        self.mesh = mesh
        _ACTIVE_MESH[0] = mesh

    def init(self, rng):
        return init(self.config, rng)

    def apply(self, params, *args, **kw):
        return apply(self.config, params, *args, **kw)

    def loss(self, params, batch, rng=None, step=None):
        kw = {}
        if rng is not None and self._loss_takes_rng:
            kw["rng"] = rng
        if step is not None and self.config.pld_enabled and self._loss_takes_step:
            kw["step"] = step
        return self._loss(self.config, params, batch, **kw)

    def logical_axes(self):
        return logical_axes(self.config)

    def remat_offer(self, micro_batch):
        """For the training engine's plan of what a checkpointed layer keeps
        (``runtime/remat_plan.plan_saved``), in bytes on one device, whose share
        of a micro-batch ``micro_batch`` is (shapes: ``ShapeDtypeStruct``s):
        (names, what saving them takes, what the floor policy saves, the step's
        temporaries: ``remat_candidates``, ``step_working_bytes``). None where
        nothing may be added: no remat, a policy other than ``save_flash``, which
        is an explicit choice and is taken as written, or a batch that is not
        ``split_batch``'s. The engine traces the loss inside
        ``remat_also_saving(names)``."""
        c = self.config
        if not c.remat or c.remat_policy != "save_flash" or not (
                isinstance(micro_batch, dict) and {"tokens", "input_ids"} & set(micro_batch)):
            return None
        sequences, length = jax.eval_shape(split_batch, micro_batch)[0].shape[:2]
        tokens, item = sequences * length, jnp.dtype(c.dtype).itemsize
        floor, names, values = remat_candidates(c)
        return (names, tokens * values * item, tokens * floor * item,
                step_working_bytes(c, sequences, tokens))

    remat_also_saving = staticmethod(remat_also_saving)

    def flash_schedule(self, micro_batch) -> dict:
        """For the train step's program-ledger row: ``causal_tiles_fact`` at the
        sequence length of this micro-batch and the configured outer blocks
        (``diag_sub`` beside it: the edge the forward cut the diagonal's tile in,
        0: whole), ``flash_bwd_form``: whether that backward is the one kernel or
        the pair (``flash_attention.backward_form``: ``fused`` / ``split``), and
        ``flash_bwd_diag_sub``: the edge the backward cut the diagonal's tile in
        (it cuts under 2,048 rows too, where the forward does not:
        ``flash_attention._diag_cut``). Nothing where training does not attend
        through those kernels."""
        c = self.config
        if c.attn_impl != "flash" or not c.causal or not (
                isinstance(micro_batch, dict) and {"tokens", "input_ids"} & set(micro_batch)):
            return {}
        from ..ops.pallas.flash_attention import backward_form, diag_sub

        length = jax.eval_shape(split_batch, micro_batch)[0].shape[1]
        blocks = c.flash_block_q or None, c.flash_block_k or None
        item = jnp.dtype(c.dtype).itemsize
        return {**causal_tiles_fact(c, length, *blocks),
                "flash_bwd_form": backward_form(length, c.head_dim, c.value_head_dim, item, *blocks),
                "flash_bwd_diag_sub": diag_sub(length, max(c.head_dim, c.value_head_dim), item,
                                               *blocks, backward=True)}

    def flops_per_token(self) -> float:
        """Approximate training FLOPs/token (fwd+bwd ≈ 6 * n_params matmul
        + attention term) — used by the throughput reports (reference:
        ThroughputTimer TFLOPS estimate utils/timer.py:135)."""
        c = self.config
        # a conv layer's operator (d x 3d in, d x d out) is as many parameters as
        # four d x d attention projections, and attends to nothing. A gated
        # feed-forward has three matrices; a token multiplies through a layer's
        # matrices once a PASS and through the head once
        ffn_matrices = 3 if c.activation == "swiglu" else 2
        n_params = (
            c.layer_passes * c.num_layers * (4 * c.hidden_size * c.hidden_size
                                             + ffn_matrices * c.hidden_size * c.ffn_size)
            + c.vocab_size * c.hidden_size
        )
        attn = (c.layer_passes * (c.num_layers - len(c.stateful_layers))
                * 2 * c.max_seq_len * c.hidden_size)  # qk+av
        return 6.0 * (n_params + attn)
