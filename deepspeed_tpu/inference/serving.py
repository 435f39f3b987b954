"""Continuous-batching serving engine: slot-based KV cache, ONE compiled
decode step, bucketed prefill, prefix-cache KV reuse, chunked prefill.

The reference's inference pillar (deepspeed/inference/engine.py) serves a
single static batch per call; heavy multi-tenant traffic needs Orca-style
continuous batching (requests join/leave mid-decode) and vLLM-style slot
management of the KV cache. On TPU both reduce to what this codebase is
built around — a small number of long-lived, statically-shaped compiled
programs over sharded state:

  * persistent slot cache  — one sharded [L, n_slots, Smax, H, Dh] k/v pair
                             lives across the whole serving session (slots
                             over the data/fsdp axes, heads over the TP axis;
                             parallel/sharding.kv_slot_cache_spec). A request
                             occupies one slot from admission to eviction.
  * ONE decode program     — ``decode_step`` advances EVERY slot by one token
                             per device call. Per-slot position is a [n]
                             vector (models/transformer.apply_with_cache),
                             per-slot sampler state is arrays (temperature /
                             top-k / top-p — inference/sampling.
                             sample_logits_vector), so admitting a request
                             with a new prompt length, sampling params, or
                             arrival time NEVER recompiles: the program
                             compiles exactly once per engine lifetime.
  * bucketed prefill       — prompts are padded to power-of-two length
                             buckets; one compiled program per bucket writes
                             the prompt's KV into a free slot via
                             ``dynamic_update_slice`` and samples the first
                             token at the live prompt position
                             (``last_index`` — never materializing the
                             padded tail's logits).
  * prefix cache           — RadixAttention-style prompt KV reuse (SGLang,
                             Zheng et al. 2023): a host-side trie
                             (inference/prefix_cache.py) maps prompt token
                             prefixes to slots of a sharded device pool
                             [L, n_prefix_slots, Pmax, H, Dh] (same layout
                             rule as the slot cache). On admit the longest
                             cached prefix is copied into the request's slot
                             by ONE compiled ``prefix_fetch`` program (slot
                             indices are array operands) and only the suffix
                             is prefilled; after prefill ONE ``prefix_store``
                             program caches the new prompt's prefix per the
                             insertion policy. Ref-counted LRU eviction.
  * chunked prefill        — Sarathi-Serve-style admission (Agrawal et al.
                             2024): prompt suffixes are split into fixed-size
                             chunks plus ONE power-of-two-bucketed padded
                             tail (one compiled program per width, so the
                             program set is {C, C/2, ...} — a handful of
                             STABLE programs, never one per prompt length).
                             Each chunk slices the request's slot window out
                             of the cache, extends it through
                             ``apply_with_cache`` at the chunk's offset
                             (per-row positions + causal offset: chunk i
                             attends to KV written by chunks < i and the
                             fetched prefix), and writes back only the
                             chunk's region. ``step()`` interleaves chunks
                             with decode steps, so active slots never stall
                             behind a long prompt for more than one chunk.
                             Admission is a state machine:
                             queued -> prefilling(k chunks done) -> decoding.
  * host scheduler         — admission picks the earliest ARRIVED request
                             (a future-dated queue head never blocks later
                             traffic), slot eviction on EOS / max-tokens,
                             request→response bookkeeping, and a wall-clock
                             ``serve`` driver. It runs ONE decode step ahead of
                             the host's copy of the tokens
                             (``ServingEngine._step``): step k + 1 is enqueued
                             before step k is fetched, the tokens between them
                             staying on the device, so the host's side of a
                             step runs under the device's.

  * block steps           — a model that generates by diffusion over blocks
                             (``attn_block_length`` B > 1) has no decode step: ONE
                             ``block_step`` program runs a pass over every slot's open
                             block of B positions (denoise, commit and open are all
                             operands), the blocks stay on the device between steps,
                             the scheduler plans a step ahead of the fetch under the
                             static schedule, and a request receives its tokens a
                             block at a time (docs/serving.md "Generation by diffusion
                             over blocks").

  * degradation          — production traffic includes requests that must be
                             refused or abandoned (docs/resilience.md):
                             per-request deadlines (queued past deadline →
                             shed; in-flight → cancelled/evicted with the
                             partial output), a bounded arrival queue with
                             typed load-shedding, and a per-slot NaN-logit
                             sentinel computed INSIDE the decode/prefill
                             programs — a poisoned request is quarantined
                             (requeued once for a clean replay, then failed)
                             without touching the rest of the batch, its KV
                             is never offered to the prefix cache, and a
                             slot that faults repeatedly is pulled from
                             rotation. Every transition is a host-side state
                             change on the existing per-slot arrays: the
                             ONE-compiled-decode-program contract survives.

Inactive and mid-prefill slots still flow through the decode program
(static shapes are the whole point); they WRITE at position Smax — the
cache scatter's ``mode="drop"`` discards the garbage KV — while attending
at position 0, and their sampled tokens are discarded by the host.
Repetition penalty is NOT supported here: its [n_slots, vocab]
"seen" carry would dominate the cache HBM for large vocabs — use
``InferenceEngine.generate`` for penalty-constrained decoding.
"""

from __future__ import annotations

import math
import os
import time
from collections import deque
from collections.abc import Sequence
from dataclasses import dataclass, field
from functools import partial
from itertools import islice
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from ..models import transformer as tfm
from ..moe.dropless import expert_gemm_form, expert_load, load_summary
from ..parallel.sharding import kv_prefix_pool_spec, kv_slot_cache_spec
from ..resilience import FaultInjector, RequestRejected
from ..runtime.config import (BlockGenerationConfig, ChunkedPrefillConfig,
                              FaultInjectionConfig, IncidentConfig, LedgerConfig,
                              PrefixCacheConfig,
                              RequestTraceConfig, SLOConfig,
                              SpeculationConfig, TenantConfig,
                              TimeSeriesConfig)
from ..telemetry import (IncidentRecorder, RequestTracer, Telemetry,
                         TimeSeriesStore, classify_terminal, hbm_snapshot,
                         tree_bytes)
from ..telemetry.tracing import spans as ended_spans
from ..utils.donation import donated_jit
from ..utils.logging import log_dist
from .engine import InferenceEngine
from .prefix_cache import PrefixIndex
from .sampling import (SAMPLER_FORMS, reveal_rows, sample_logits_vector,
                       sample_with_confidence, sampler_form, verify_logits_vector)
from .speculation import make_drafter


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


# consecutive zero-acceptance verify steps before a slot's drafting is
# suppressed outright (acceptance-aware speculation scheduling); each
# further failed re-probe doubles the wait before the next one, capped at
# 2^_SPEC_PROBE_WAIT_MAX_LOG2 decode steps
_SPEC_SUPPRESS_AFTER = 3
_SPEC_PROBE_WAIT_MAX_LOG2 = 6
# ended spans a telemetry_snapshot() carries (about fifty steps): snapshots
# are scraped and travel over the fleet's rpc, the ring's 65,536 do not
_SNAPSHOT_SPANS = 512


@dataclass
class Request:
    """One generation request. ``arrival_time`` is seconds relative to the
    engine epoch (0.0 = already arrived). step() admits once its clock —
    wall time by default, or the caller's ``now`` — has passed it; drain()
    ignores it entirely. ``deadline_s`` (seconds after arrival; 0 = the
    engine's ``default_deadline_s``, which may itself be 0 = none) bounds
    the request's total latency: past it a queued request is shed
    (``expired``) and an in-flight one is cancelled/evicted
    (``deadline_exceeded``) with whatever it produced so far. ``priority``
    orders overload shedding only (higher = kept longer): when a browned-
    out Router's global queue bound is hit, the lowest-priority newest
    queued request is shed first (docs/serving.md "Elastic fleet &
    brownout"); it never affects admission or decode order. ``tenant`` is
    the caller's identity for fair scheduling, quota accounting, and
    idempotency scoping (docs/serving.md "Multi-tenant isolation") — a
    HOST-SIDE label only: it never becomes a traced operand, so an
    arbitrary tenant mix admits with zero new XLA programs."""

    uid: int
    prompt: np.ndarray  # [S] int32
    max_new_tokens: int = 32
    temperature: float = 0.0  # <= 0 greedy
    top_k: int = 0  # 0 = disabled
    top_p: float = 1.0  # 1.0 = disabled
    eos_token: Optional[int] = None
    arrival_time: float = 0.0
    deadline_s: float = 0.0
    priority: int = 0
    tenant: str = ""


@dataclass
class RequestResult:
    uid: int
    tokens: np.ndarray  # [n_generated] int32 (includes eos if emitted)
    prompt_len: int
    arrival_time: float
    admitted_time: float = 0.0
    first_token_time: float = 0.0  # TTFT reference point
    finish_time: float = 0.0
    slot: int = -1
    prefix_hit_tokens: int = 0  # prompt tokens reused from the prefix cache
    # degradation outcome (docs/resilience.md): ok | deadline_exceeded |
    # cancelled | shed_queue_full | expired | failed_nan
    status: str = "ok"
    requeues: int = 0  # NaN-quarantine replays this request went through

    @property
    def ok(self) -> bool:
        return self.status == "ok"

    @property
    def ttft(self) -> float:
        return self.first_token_time - self.arrival_time

    @property
    def time_per_output_token(self) -> float:
        n = len(self.tokens)
        if n <= 1:
            return 0.0
        return (self.finish_time - self.first_token_time) / (n - 1)


@dataclass
class _Slot:
    uid: int = -1
    remaining: int = 0
    eos: int = -1  # -1 = never matches
    result: Optional[RequestResult] = None
    # Python ints, converted where they are written, and append-only while
    # the request lives: live_progress() hands out windows on this list
    tokens: list[int] = field(default_factory=list)
    prefix_entry: object = None  # acquired PrefixEntry released on finish
    request: Optional[Request] = None  # kept for quarantine requeue/deadline


class _TokensSoFar(Sequence):
    """The first ``n`` tokens of a slot's list, ``n`` fixed when the window
    is made. Never changes under its holder: the list only grows by
    ``append`` while its request lives, and a released slot gets a NEW
    ``_Slot`` — the old list is dropped, never cleared."""

    __slots__ = ("_tokens", "_n")

    def __init__(self, tokens: list[int]):
        self._tokens = tokens
        self._n = len(tokens)

    def __len__(self):
        return self._n

    def __getitem__(self, i):
        if isinstance(i, slice):
            return self._tokens[:self._n][i]
        if i < 0:
            i += self._n
        if not 0 <= i < self._n:
            raise IndexError(i)
        return self._tokens[i]

    def __iter__(self):
        return islice(self._tokens, self._n)


@dataclass
class _Prefill:
    """A slot mid-admission: prefilling(idx of len(segments) chunks done).
    The slot is occupied (not in ``_free``) but not yet decoding
    (``_active`` false) — decode steps run alongside untouched."""

    req: Request
    slot: int
    prompt: np.ndarray  # [S] int32
    segments: list  # [(start, width, live_len)] covering [prefix_len, S)
    idx: int = 0
    entry: object = None  # PrefixEntry backing the fetched prefix (acquired)
    t_admit: float = 0.0  # epoch-relative admission time


@dataclass
class _Handoff:
    """A prefill-role slot PARKED after admission: the prompt KV and first
    token are resident, but a prefill worker never decodes — the slot waits
    for the Router to stream its KV window into a decode replica
    (``kv_export_window``) and release it (``handoff_release``). Occupied
    (not in ``_free``), never ``_active``."""

    req: Request
    slot: int
    first: int  # the sampled first token (travels with the handoff)
    pos: int  # prompt length: KV resident in [0, pos)
    prefix_hit_tokens: int
    t_admit: float
    t_first: float
    entry: object = None  # acquired PrefixEntry, released on handoff_release


@dataclass
class _Flight:
    """The decode step that is enqueued and not fetched yet, as the scheduler
    planned it: the rows it ran and who held each. Its tokens are emitted against
    THESE rows, not the slots' state at the fetch: by then a slot may have been
    freed (cancelled, evicted, quarantined, ended by an EOS the step before) or
    freed AND refilled, and such a row ran for nothing."""

    active: np.ndarray  # [n_slots] bool: the rows the step ran
    # [n_slots]: each slot's count of occupants at the enqueue. A uid would not do:
    # a quarantined request is requeued under its uid and may come back to its slot
    epoch: np.ndarray
    # a BLOCK step's rows (generation by diffusion over blocks; None for a decode step),
    # all [n_slots]: which rows ran a denoising pass (the others a commit), how many
    # positions of the slot's block were masked at its entry, and the first position of
    # the block that is the request's to receive (behind its prompt's last tokens)
    denoise: Optional[np.ndarray] = None
    masked: Optional[np.ndarray] = None
    gen_from: Optional[np.ndarray] = None


def _forward(cfg, params, toks, cache, pos, live, **kw):
    """``apply_with_cache`` for a serving program -> (logits, cache, extra
    outputs). A dense model has no extra output and its program is what it
    was. A model with dropless routing adds TWO. int32 [routed layers, E]: how
    many of the ``live`` rows (toks-shaped bool: not bucket padding, not an
    idle slot) each layer sent to each expert (with ``moe_experts_held``: to each
    HELD expert, [routed layers, count + 1], the last column every row's pairs to
    a held expert, live or not: what the program dispatched); it comes back in the fetch
    that brings the tokens and ``SlotWorker._note_load`` puts it on the span.
    Then the experts chosen themselves, int32 [routed layers, B, T, k], which
    the load was counted from: they stay on the device unless
    ``SlotWorker.routing_log`` asks for them. The model is handed ``live`` too:
    a state-space mixer's state may move on those rows alone. A model with an exit
    gate (``exit_gate``: several passes over its layers) adds ONE: float32
    [layer_passes], the ``live`` rows' mean exit distribution, reduced here on the
    device; it comes back in the same fetch and ``SlotWorker._note_exit`` puts it
    on the span."""
    if cfg.exit_gate:
        logits, cache, p = tfm.apply_with_cache(cfg, params, toks, cache, pos, live=live,
                                                return_exit=True, **kw)
        on = live.astype(jnp.float32)[..., None]
        return logits, cache, (jnp.sum(p * on, axis=(0, 1)) / jnp.maximum(jnp.sum(on), 1.0),)
    if cfg.moe_routing != "dropless":
        return (*tfm.apply_with_cache(cfg, params, toks, cache, pos, live=live, **kw), ())
    logits, cache, chosen = tfm.apply_with_cache(
        cfg, params, toks, cache, pos, return_routing=True, live=live, **kw)
    held = cfg.moe_experts_held
    load = expert_load(chosen, live, cfg.num_experts, held)
    if held:  # one more column: every row's pairs to a held expert, padding and idle rows too
        first, count = held
        rows = jnp.sum((chosen >= first) & (chosen < first + count), axis=(1, 2, 3))
        load = jnp.concatenate([load, rows[:, None].astype(load.dtype)], axis=-1)
    return logits, cache, (load, chosen)


class SlotWorker:
    """The compiled-program driver half of the serving engine.

    The serving engine is really two machines. The HOST SCHEDULER
    (``ServingEngine``) owns requests: queues, admission, deadlines,
    shedding, quarantine — pure host state transitions. This worker owns
    the DEVICE: the slot KV cache, the prefix pool, the sampler PRNG, and
    the small inventory of long-lived compiled programs that touch them.
    Every public method here is exactly one host→device dispatch (``decode``
    a second, small one on a step that takes tokens from the host: the merge;
    ``collect`` none, it fetches); nothing
    in this class knows about requests, arrival times, or health.

    The boundary is what makes fleet serving possible as pure host code:
    a ``Router`` (inference/router.py) drives N schedulers — and therefore
    N workers — from one process, and replica management (liveness,
    failover, draining) never introduces a new XLA program shape, because
    it only ever talks to schedulers.
    """

    def __init__(self, engine: InferenceEngine, telemetry: Telemetry,
                 n_slots: int, budget: int, seed: int,
                 prefix_cfg: PrefixCacheConfig):
        self.engine = engine
        self.cfg = engine.cfg
        self.mesh = engine.mesh
        self.params = engine.params
        self.telemetry = telemetry
        self.n_slots = int(n_slots)
        # only the cache ALLOCATION rounds up to the 128 multiple the decode
        # kernel's block streaming needs — the scheduler's admission budget
        # stays at the model's limit, so those tail positions are never
        # admitted into
        self.Smax = -(-int(budget) // 128) * 128

        # the cache is the tree the model's attention says (per-head K/V, or
        # latent attention's shared rotary key + latent: ``tfm.cache_layout``);
        # everything below works on that tree. Its head axis shards over the
        # TP axis where every leaf's heads divide (a latent is every head's:
        # it has one, and replicates)
        layout = tfm.cache_layout(self.cfg)
        per_token = tfm.token_leaves(layout)
        cache_heads = min(heads for heads, _ in per_token.values())
        self.spec = kv_slot_cache_spec(self.mesh, self.n_slots, cache_heads)
        self._cache_sharding = NamedSharding(self.mesh, self.spec)

        def shardings(token_sharding):
            """The sharding tree of a cache: its per-token leaves, and a window
            layer's rings, as given; its per-sequence leaves [L, slots, ...] (a
            state-space mixer's state) over the slots' axis alone."""
            tree = {name: token_sharding for name in per_token}
            if tfm.RING in layout:  # [L, slots, R, heads, width]: the axes of a per-token leaf
                tree[tfm.RING] = {name: token_sharding for name in layout[tfm.RING]}
            if tfm.STATE in layout:
                per_seq = NamedSharding(self.mesh, PartitionSpec(*token_sharding.spec[:2]))
                tree[tfm.STATE] = {name: per_seq for name in layout[tfm.STATE]}
            return tree

        # every program pins the cache OUTPUT to this sharding too — an
        # inferred output sharding that differs from the input's would give
        # the next call a differently-sharded operand and silently recompile
        self._cache_shardings = shardings(self._cache_sharding)
        # the key lives on the device from here on: every program that draws
        # takes it, splits it INSIDE and hands the carried half back (``_run``
        # stores that and never fetches it). Placed where the programs return
        # it, so a program's first call and its later ones hand it the same
        # kind of operand: one compile
        self._rng = jax.device_put(jax.random.PRNGKey(seed), self._key_sharding())
        # the tokens the last decode step sampled stay on the device as well: they
        # are the NEXT step's token operand, which the scheduler enqueues before it
        # has fetched them (``decode``). Placed like the key, and the decode program
        # takes and returns them there: the same kind of operand from its first
        # call on, one compile. Rows that were idle in that step hold 0
        self._toks = jax.device_put(np.zeros((self.n_slots,), np.int32), self._key_sharding())
        # a model that generates by diffusion over blocks (``attn_block_length`` B > 1)
        # carries every slot's OPEN BLOCK there instead: its B tokens and which of them are
        # still masked, [n_slots, B] each, the block step's first two operands and first
        # two host-bound values (``block_step``), pinned like the tokens above
        self.block_len = int(self.cfg.attn_block_length)
        self._btoks = self._bmask = None
        if self.block_len > 1:
            open_block = (self.n_slots, self.block_len)
            self._btoks = jax.device_put(np.zeros(open_block, np.int32), self._key_sharding())
            self._bmask = jax.device_put(np.zeros(open_block, np.bool_), self._key_sharding())
        # the decode (or block) step that is enqueued and not fetched (``decode`` /
        # ``block_step`` / ``collect``): its outputs on the device, a routed model's
        # choices, the rows it ran, when it was handed over and whether that call compiled
        self._pending = None
        self._t_fetched = 0.0  # when the last decode step's results reached the host
        # a kept span (telemetry/tracing.py): the allocation programs' traces and
        # compiles end under it, and it under the build's ``startup/build``
        with telemetry.span("cache", keep=True) as sp:
            self._cache = jax.jit(
                partial(tfm.init_cache, self.cfg, self.n_slots, self.Smax,
                        dtype=self.cfg.dtype),
                out_shardings=self._cache_shardings,
            )()
            # prefix pool: the slot cache's sibling — same [L, slots, len, H, Dh]
            # layout, holding cached prompt prefixes instead of live sequences
            self.pmax = 0
            self._pool = None
            if prefix_cfg.enabled:
                self.pmax = int(prefix_cfg.max_prefix_len) or self.Smax
                if self.pmax > self.Smax:
                    raise ValueError(
                        f"prefix_cache.max_prefix_len ({self.pmax}) exceeds the "
                        f"slot cache length {self.Smax}")
                pool_spec = kv_prefix_pool_spec(self.mesh, prefix_cfg.n_slots, cache_heads)
                self._pool_sharding = NamedSharding(self.mesh, pool_spec)
                self._pool_shardings = shardings(self._pool_sharding)
                self._pool = jax.jit(
                    partial(tfm.init_cache, self.cfg, prefix_cfg.n_slots, self.pmax,
                            dtype=self.cfg.dtype),
                    out_shardings=self._pool_shardings,
                )()
            # the one program beside the four that draw: the host's tokens into the
            # carried ones, row by row (``decode``). Built and run once here, so no
            # serving call compiles it
            wd = telemetry.watchdog
            self._merge = wd.watch(self._build_merge(), wd.unique_name("serving/token_merge"),
                                   stable=True)
            self._toks = self._merge(np.ones((self.n_slots,), np.bool_),
                                     np.zeros((self.n_slots,), np.int32), self._toks)
            sp.set_sync((self._cache, self._pool, self._toks))
        # what ONE decode step must read and write of per-sequence state, a live
        # row: the leaves of every layer that keeps any (the layout's own count:
        # every layer of a model with a mixer, the conv or delta layers of one with
        # layers by operator), once each way (0 for a model without)
        kept = tfm.cache_layers(self.cfg)
        self.state_layers = kept[tfm.STATE]
        self.state_bytes_per_slot = self.state_layers * tfm.cache_state_bytes(self.cfg)
        # how many layers keep a ring of ``local_attn_window`` positions a slot (0: none)
        self.window_layers = kept[tfm.RING]
        # what the programs ran of each operator (a model with ``layer_operators`` only)
        self.operator_attrs = {
            f"{op}_layers": len(self.cfg.layers_of(op))
            for op in sorted(set(self.cfg.layer_operators or ()), reverse=True)}
        # a model whose layer stack runs several times (``layer_passes``): what its
        # spans say of it (nothing for any other). ``cache_layers``: the K/V layers a
        # token keeps, one a (pass, layer)
        self.pass_attrs = {}
        if self.cfg.layer_passes > 1:
            self.pass_attrs = {"layer_passes": self.cfg.layer_passes,
                               "cache_layers": kept["tokens"]}
            telemetry.gauge("serving/cache_layers").set(kept["tokens"])
        # the cached positions a block of the Pallas decode kernel's walk holds (None:
        # a step does not attend through it): what ``kv_rows_fetched`` counts in
        self.kv_block = tfm.decode_kernel_block(self.cfg, self.Smax, tfm.cache_dtype(self._cache))
        # where the programs read a routed layer's expert banks from ("in_place" /
        # "sliced"; None for a model without dropless routing): the rule they trace by
        self.expert_bank = tfm.expert_bank_form(self.cfg, self.params.get("moe"), self.mesh)
        # the routed layers a row goes through (a model with dropless routing only)
        self.routed_layers = (jax.tree.leaves(self.params["moe"]["experts"])[0].shape[0]
                              if self.expert_bank else 0)

        self._decode = None  # jitted lazily (params pytree shapes needed)
        self._block = None  # jitted block step (``attn_block_length`` > 1), lazily too
        self._prefills: dict[int, object] = {}  # bucket len -> jitted prefill
        self._chunk_progs: dict[int, object] = {}  # chunk width -> jitted chunk
        # (spec depth, greedy_only) -> jitted verify: two program families
        # per pow2 bucket — the greedy one skips the filtered-sampling
        # machinery (argmax is the whole acceptance rule), which on small
        # models is most of the verify step's cost
        self._verifies: dict[tuple[int, bool], object] = {}
        self._fetch = None  # jitted prefix pool -> slot copy
        self._store = None  # jitted slot -> prefix pool copy
        self._poison = None  # jitted slot-KV fill (fault injection/scrub)
        # disaggregated serving's KV wire programs (docs/serving.md
        # "Disaggregated prefill/decode"): pow2 width -> jitted window
        # slice / splat — the chunked-prefill width discipline applied to
        # the handoff path, so the program set stays bounded
        self._kv_exports: dict[int, object] = {}
        self._kv_imports: dict[int, object] = {}
        self._decode_steps = 0
        self._decode_steps_ahead = 0  # those enqueued while the step before was unfetched
        self._block_steps = 0
        self._block_steps_ahead = 0
        # True if ANY dispatch since the scheduler last reset it paid a
        # compilation — the Router's step-latency heartbeat exempts such
        # steps (a cold replica's first step compiles for tens of seconds
        # on real hardware; that is not a hang), the same rule the latency
        # histograms already apply via last_call_compiled
        self.step_compiled = False
        # a routed model's programs return the experts they chose beside the
        # load counted from them; set this to a list and every fetched call
        # appends {"span", its rows (decode / verify: pos, active; prefill:
        # uid, slot, true_len; chunk: uid, slot, start, live), "chosen"} (a chunk
        # left asynchronous too: its choices are then waited for). The
        # programs are the same either way: observation, not a path
        self.routing_log: list | None = None
        # the same for a block step's outcome: set this to a list and every FETCHED
        # block step appends the rows it ran (``pos``, ``active``, ``opened``, ``count``)
        # and what it left (``toks``, ``mask`` [n_slots, B], ``bad``), with every row's
        # confidence as the program computed it (``conf`` [n_slots, B])
        self.block_log: list | None = None

    # -- compiled programs ----------------------------------------------

    def _build_decode(self):
        cfg = self.cfg

        def decode(params, cache, toks, pos, wpos, active, rng, temp, top_k, top_p):
            # toks/pos/wpos/active/temp/top_k/top_p are all [n_slots] ARRAYS
            # — nothing about an individual request is baked into the
            # program. wpos decouples the KV write from the attention
            # position: inactive/prefilling rows write at Smax (dropped by
            # the scatter) but ATTEND at pos 0, so the length-aware decode
            # kernel streams one block for an idle row, not the whole cache
            logits, cache, load = _forward(
                cfg, params, toks[:, None], cache, pos, active[:, None], write_pos=wpos)
            # per-slot NaN sentinel: a non-finite logit row means the slot's
            # state is poisoned (bad KV, numeric fault) — the host
            # quarantines the request; the sampled token for such a row is
            # garbage and discarded. Computed in the SAME program: the
            # one-compiled-decode-step contract holds.
            bad = jnp.any(~jnp.isfinite(logits[:, 0]), axis=-1)
            rng, k = jax.random.split(rng)
            nxt = sample_logits_vector(logits[:, 0], k, temp, top_k, top_p)
            return (cache, rng, jnp.where(active, nxt, 0), bad, *load)

        # all serving programs donate the slot KV cache / prefix pool —
        # XLA-created device buffers, never CPU zero-copy host memory, so
        # donation stays on every backend (utils/donation.py is the gate)
        return self._keyed_jit(decode, 4, 2, carried_tokens=True)

    def _build_merge(self):
        """``where(mask, host, carried)`` over the slots: the token operand of a decode
        step in which some rows' token is the HOST's (a prefill's first token, an
        imported or requeued request's, a row that fell idle: 0) and the others' is
        the one the last step sampled, which only the device has yet. It merges
        OUTSIDE the decode program, whose operand list stays what it was, and runs
        only on a step that has such a row. Pinned like the carried key, in and out."""
        rep = self._key_sharding()

        def token_merge(mask, host, carried):
            return jnp.where(mask, host, carried)

        return jax.jit(token_merge, in_shardings=(rep, rep, rep), out_shardings=rep)

    def _build_block_step(self):
        """ONE program for every phase of generation by diffusion over blocks: a pass
        over every slot's open block of B = ``attn_block_length`` positions, [n_slots, B]
        rows. Nothing of a request, a phase, a schedule or a strategy is baked in beyond
        B: all of it is the operands'.

        ``toks`` / ``mask`` [n_slots, B]: the open blocks as the last step left them, on
        the device. ``opened`` [n_slots]: the rows whose block OPENS with this step; they
        take ``new_toks`` / ``new_mask`` from the host (a prompt's last tokens in place
        and the rest masked; B masks behind a committed block). A masked position's input
        is the mask token's embedding. The block is written at ``wpos .. wpos + B - 1``
        BEFORE it attends (idle and prefilling rows at ``Smax``: dropped) and attends at
        ``pos .. pos + B - 1`` under the mask that is causal between blocks, so every row
        sees the whole block and everything before it. Per masked row the token
        (``sample_with_confidence``: arg-max or a draw, by the slot's sampler rows) and
        its probability under the softmax over the vocabulary; ``reveal_rows`` keeps the
        ``count`` most confident of a slot's masked rows and those over ``threshold``.
        A pass whose block holds no mask reveals nothing, and the K/V it wrote is the
        block's final K/V: the COMMIT is this same program on such a row. Behind the
        sentinel the program hands back every row's confidence [n_slots, B] float32, which
        stays on the device unless ``block_log`` asks for it (as the experts chosen do
        for ``routing_log``): what a check compares of THIS program's own arithmetic."""
        cfg, B = self.cfg, self.block_len
        mask_id = jnp.int32(cfg.mask_token_id)

        def block_step(params, cache, toks, mask, opened, new_toks, new_mask, pos, wpos,
                       active, count, threshold, rng, temp, top_k, top_p):
            toks = jnp.where(opened[:, None], new_toks, toks)
            mask = jnp.where(opened[:, None], new_mask, mask)
            on = jnp.broadcast_to(active[:, None], toks.shape)
            logits, cache, load = _forward(
                cfg, params, jnp.where(mask, mask_id, toks), cache, pos, on, write_pos=wpos)
            # the sentinel spans the block: a NaN in any of its rows poisons the pass
            bad = jnp.any(~jnp.isfinite(logits), axis=(1, 2))
            rng, k = jax.random.split(rng)
            rows = lambda a: jnp.repeat(a, B)  # a slot's sampler state on each of its rows
            x0, conf = sample_with_confidence(
                logits.reshape(-1, logits.shape[-1]), k, rows(temp), rows(top_k), rows(top_p))
            reveal = on & reveal_rows(conf.reshape(toks.shape), mask, count, threshold)
            toks = jnp.where(on, jnp.where(reveal, x0.reshape(toks.shape), toks), 0)
            return (cache, rng, toks, mask & ~reveal & on, bad, conf.reshape(toks.shape), *load)

        cache, *rest = self._outs(4)
        key = self._key_sharding()
        return donated_jit(block_step, donate_argnums=(1,),
                           in_shardings=(None, None, key, key) + (None,) * 8
                           + (key, None, None, None),
                           out_shardings=(cache, key, key, key, *rest[2:]))

    def _build_verify(self, depth: int, greedy_only: bool = False):
        cfg = self.cfg

        if greedy_only:
            # every emitted token is an argmax: the rng key and the
            # temp/top_k/top_p vectors are DEAD operands, so the greedy
            # family drops them from its signature — four fewer host
            # uploads per verify step on a path whose whole point is
            # shaving per-step cost
            def verify_greedy(params, cache, toks, pos, wpos, active):
                logits, cache, load = _forward(
                    cfg, params, toks, cache, pos,
                    jnp.broadcast_to(active[:, None], toks.shape), write_pos=wpos)
                bad = jnp.any(~jnp.isfinite(logits), axis=(1, 2))
                # acceptance is draft == argmax and every emitted token IS
                # the argmax — no top-k/top-p sort, no categorical draws,
                # no residual distribution. On small models the filtered-
                # sampling machinery across (depth+1) x n_slots positions
                # is ~3x the whole forward pass, so this family is what
                # makes CPU/greedy speculation pay for itself.
                greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                accept = toks[:, 1:] == greedy[:, :depth]
                on = active[:, None]
                out = jnp.where(on, greedy, 0)
                # ONE packed int32 output [n, 2*depth+2] — accept flags,
                # then the depth+1 argmax tokens, then the bad sentinel —
                # so the host pays a single device fetch per verify step
                # instead of four tiny ones
                packed = jnp.concatenate(
                    [(accept & on).astype(jnp.int32), out,
                     bad.astype(jnp.int32)[:, None]], axis=1)
                return (cache, packed, *load)

            return donated_jit(verify_greedy, donate_argnums=(1,),
                               out_shardings=self._outs(1))

        def verify(params, cache, toks, pos, wpos, active, rng, temp, top_k, top_p):
            # toks [n_slots, depth+1]: column 0 is each slot's last sampled
            # token, columns 1..depth its (padded) draft. The whole block
            # runs ONE forward pass at positions pos..pos+depth — the
            # amortization speculative decoding exists for: one weights
            # read scores depth+1 positions. Draft KV is written at
            # wpos..wpos+depth as it goes (write-before-attend, exactly the
            # chunk path's discipline); rejected tail positions hold stale
            #-but-finite KV that the causal mask hides until later
            # dispatches overwrite them — the per-slot "rollback" is just
            # the host not advancing pos past the accepted prefix.
            # Inactive slots write at Smax.. and beyond: every position of
            # their block lands out of range and the scatter's mode="drop"
            # discards it, the same contract decode relies on.
            logits, cache, load = _forward(
                cfg, params, toks, cache, pos,
                jnp.broadcast_to(active[:, None], toks.shape), write_pos=wpos)
            # the sentinel spans ALL depth+1 positions: a NaN anywhere in
            # the block poisons the accept/bonus math for that slot
            bad = jnp.any(~jnp.isfinite(logits), axis=(1, 2))
            rng, k = jax.random.split(rng)
            accept, resample, clean = verify_logits_vector(
                logits, toks[:, 1:], k, temp, top_k, top_p)
            on = active[:, None]
            return (cache, rng, accept & on, jnp.where(on, resample, 0),
                    jnp.where(on, clean, 0), bad, *load)

        return self._keyed_jit(verify, 4, 4)

    def _build_prefill(self, bucket: int):
        cfg = self.cfg

        def prefill(params, cache, prompt, slot, true_len, rng, temp, top_k, top_p):
            # prompt [1, bucket]. ATTENTION needs no mask for the padded tail:
            # causality hides it (the live tokens never attend to it, and its KV
            # is overwritten by decode steps as the sequence grows into those
            # positions). A RECURRENCE has no causality to hide behind: every
            # padded row would advance the state decode then starts from, so a
            # state-space mixer is handed the live-row mask (``_forward``) and
            # the state written to the slot is the one after row true_len - 1.
            # ``update_cache_slot`` overwrites the slot's per-sequence leaves
            # whole: its previous request leaves nothing behind
            local = tfm.init_cache(cfg, 1, bucket, dtype=tfm.cache_dtype(cache))
            logits, local, load = _forward(
                cfg, params, prompt, local, 0, jnp.arange(bucket)[None, :] < true_len,
                last_index=true_len - 1)
            bad = jnp.any(~jnp.isfinite(logits[:, 0]), axis=-1)
            rng, k = jax.random.split(rng)
            tok = sample_logits_vector(logits[:, 0], k, temp, top_k, top_p)
            return (tfm.update_cache_slot(cache, local, slot), rng, tok, bad, *load)

        return self._keyed_jit(prefill, 3, 2)

    def _build_chunk(self, width: int):
        cfg = self.cfg
        Smax = self.Smax

        def chunk(params, cache, toks, slot, start, true_len, rng, temp, top_k, top_p):
            # toks [1, width] prompt tokens entering at absolute position
            # ``start`` of row ``slot`` (slot/start/true_len are all traced
            # scalars — one program per width, never per slot/offset/length).
            # The slot's window is sliced out, extended through the
            # cache-attention path (the per-row position vector makes this
            # chunk attend to the prefix and every earlier chunk already
            # resident in the window), and splatted back. Only the slot's
            # own row is ever written: other slots' mid-decode KV cannot be
            # perturbed. A final tail chunk may be padded past ``true_len``
            # (bucketed like the one-shot prefill); the pad's garbage KV at
            # positions >= the prompt length is overwritten by decode steps
            # before any query position can attend to it, and ``last_index``
            # projects only the live last token's logits.
            # A state-space mixer's state comes out with the window, whole, and
            # goes back whole: the chunk starts from what the last chunk left
            # (from nothing at ``start`` 0, whatever the slot held) and moves
            # on its live rows only.
            local = tfm.fresh_cache_state(tfm.slice_cache_slot(cache, slot, Smax), start == 0)
            logits, local, load = _forward(
                cfg, params, toks, local, jnp.reshape(start, (1,)),
                jnp.arange(width)[None, :] < true_len, last_index=true_len - 1)
            # NaN mid-prompt propagates through attention to every later
            # chunk, so the final chunk's sentinel covers the whole prefill
            bad = jnp.any(~jnp.isfinite(logits[:, 0]), axis=-1)
            rng, k = jax.random.split(rng)
            tok = sample_logits_vector(logits[:, 0], k, temp, top_k, top_p)
            # write back ONLY the chunk's region [start, start+width) — the
            # rest of the window is unchanged, and splatting all Smax
            # positions per chunk would multiply the cache-write bandwidth
            # by Smax/width on exactly the prompt-side hot path
            new_kv = tfm.slice_cache_slot(local, 0, width, start=start)
            return (tfm.update_cache_slot(cache, new_kv, slot, start=start), rng, tok, bad,
                    *load)

        return self._keyed_jit(chunk, 4, 2)

    def _build_fetch(self):
        pmax = self.pmax

        def fetch(cache, pool, pool_slot, slot):
            # the whole [0, Pmax) window is copied (static width — ONE
            # program); positions past the entry's live length are garbage
            # the suffix prefill / decode writes overwrite before any query
            # position can attend to them
            return tfm.update_cache_slot(
                cache, tfm.slice_cache_slot(pool, pool_slot, pmax), slot)

        return donated_jit(fetch, donate_argnums=(0,),
                           out_shardings=self._cache_shardings)

    def _build_store(self):
        pmax = self.pmax

        def store(pool, cache, slot, pool_slot):
            return tfm.update_cache_slot(
                pool, tfm.slice_cache_slot(cache, slot, pmax), pool_slot)

        return donated_jit(store, donate_argnums=(0,),
                           out_shardings=self._pool_shardings)

    def _build_kv_export(self, width: int):
        def export(cache, slot, start):
            # pure read — the cache is NOT donated (it must survive the
            # export; the prefill slot keeps serving retries until the
            # router releases it). Returns the [L, 1, width, H, Dh] k/v
            # window at [start, start+width) of row ``slot``.
            return tfm.slice_cache_slot(cache, slot, width, start=start)

        return donated_jit(export)

    def _build_kv_import(self, width: int):
        def imp(cache, new_kv, slot, start):
            return tfm.update_cache_slot(cache, new_kv, slot, start=start)

        return donated_jit(imp, donate_argnums=(0,),
                           out_shardings=self._cache_shardings)

    def _chunk_prog(self, width: int):
        if width not in self._chunk_progs:
            wd = self.telemetry.watchdog
            self._chunk_progs[width] = wd.watch(
                self._build_chunk(width),
                wd.unique_name(f"serving/chunk_prefill[{width}]"), stable=True)
        return self._chunk_progs[width]

    def _kv_export_prog(self, width: int):
        if width not in self._kv_exports:
            wd = self.telemetry.watchdog
            self._kv_exports[width] = wd.watch(
                self._build_kv_export(width),
                wd.unique_name(f"serving/kv_export[{width}]"), stable=True)
        return self._kv_exports[width]

    def _kv_import_prog(self, width: int):
        if width not in self._kv_imports:
            wd = self.telemetry.watchdog
            self._kv_imports[width] = wd.watch(
                self._build_kv_import(width),
                wd.unique_name(f"serving/kv_import[{width}]"), stable=True)
        return self._kv_imports[width]

    def _outs(self, n: int) -> tuple:
        """``out_shardings`` of a program that returns the slot cache, ``n``
        host-bound values and ``_forward``'s extra outputs: for a routed model the
        expert load and the experts chosen, for one with an exit gate the mean exit
        distribution."""
        extra = 1 if self.cfg.exit_gate else 2 * (self.cfg.moe_routing == "dropless")
        return (self._cache_shardings,) + (None,) * (n + extra)

    def _key_sharding(self):
        """Where the carried key lives: replicated over the devices of the cache.
        On one device that is said without the mesh, as a key made by hand
        (``jax.random.PRNGKey``) is typed: jit traces by the operands' types, the
        mesh among them, so such a key finds the served program's own trace."""
        on = jax.tree.leaves(self._cache_shardings)[0]
        if len(on.device_set) == 1:
            return SingleDeviceSharding(*on.device_set)
        return NamedSharding(on.mesh, PartitionSpec())

    def _keyed_jit(self, fun, n_head: int, n: int, carried_tokens: bool = False):
        """The jit of a program that draws: ``fun(params, cache, *n_head operands,
        rng, temp, top_k, top_p)`` splits the worker's key ``rng`` and hands the
        carried half back behind the cache, ahead of its ``n`` host-bound values.
        The key's sharding is pinned coming in and going out, as the cache's is
        going out: the key one call returns is the next call's operand, whichever
        program that is, and a key typed by hand (a lowering for the compiler's
        memory account) lowers to the same module as a served call's.
        ``carried_tokens`` (the decode program): its first operand, the tokens, and
        its first host-bound value, the tokens it sampled, are pinned the same way:
        one step's output is the next step's operand (``_toks``), and a token vector
        typed by hand lowers to the same module too."""
        cache, *rest = self._outs(n)
        key = self._key_sharding()
        head = (None,) * n_head
        if carried_tokens:
            head, rest = (key, *head[1:]), (key, *rest[1:])
        return donated_jit(fun, donate_argnums=(1,),
                           in_shardings=(None, None, *head, key, None, None, None),
                           out_shardings=(cache, key, *rest))

    # -- dispatches ------------------------------------------------------

    def _decode_prog(self):
        if self._decode is None:
            wd = self.telemetry.watchdog
            self._decode = wd.watch(
                self._build_decode(), wd.unique_name("serving/decode"), stable=True)
        return self._decode

    def _block_prog(self):
        if self._block is None:
            wd = self.telemetry.watchdog
            self._block = wd.watch(
                self._build_block_step(), wd.unique_name("serving/block_step"), stable=True)
        return self._block

    def _verify_prog(self, depth: int, greedy_only: bool):
        key = (depth, greedy_only)
        if key not in self._verifies:
            wd = self.telemetry.watchdog
            name = f"serving/verify[{depth}{':greedy' if greedy_only else ''}]"
            self._verifies[key] = wd.watch(
                self._build_verify(depth, greedy_only), wd.unique_name(name), stable=True)
        return self._verifies[key]

    def _prefill_prog(self, bucket: int):
        if bucket not in self._prefills:
            # each bucket length is its own compile-stable program: one
            # compile at first use, never again
            wd = self.telemetry.watchdog
            self._prefills[bucket] = wd.watch(
                self._build_prefill(bucket),
                wd.unique_name(f"serving/prefill[{bucket}]"), stable=True)
        return self._prefills[bucket]

    def _note_load(self, sp, load, chosen=None, **rows) -> None:
        """A routed model's call: how uneven the routing of its live rows was,
        on the call's span and the gauges of the same names (``load`` is the
        fetched tail of the program's outputs: empty for a dense model). While
        ``routing_log`` is a list, the call's ``chosen`` experts are fetched
        too and appended with what says whose ``rows`` they are."""
        if not load:
            return
        if self.routing_log is not None:
            self.routing_log.append({"span": sp.name, **rows,
                                     "chosen": np.asarray(chosen)})
        load = load[0]
        if self.cfg.moe_experts_held:  # the share's own: what was held, what was dispatched
            load, rows = load[:, :-1], load[:, -1]
            sp.annotate(experts_held=int(load.shape[1]), expert_rows_held=int(rows.sum()))
        summary = load_summary(load)
        sp.annotate(**summary)
        self.telemetry.gauge("serving/expert_load_max_over_mean").set(
            summary["expert_load_max_over_mean"])
        self.telemetry.gauge("serving/experts_touched").set(summary["experts_touched"])

    def _note_exit(self, sp, mean_p) -> None:
        """A call of a model with an exit gate: its live rows' mean exit distribution
        ``mean_p`` [layer_passes] (the fetched tail of the program's outputs) on the
        call's span as ``exit_pass_mean``, the mean pass at which a row would stop
        (sum of r x p_r, r from 1), and ``exit_cdf``, the share that would have stopped
        after each pass but the last; the first on the gauge of its name too. The gate
        decides nothing: every row ran every pass."""
        p = np.asarray(mean_p, np.float64)
        mean = float(np.sum(p * np.arange(1, len(p) + 1)))
        sp.annotate(exit_pass_mean=round(mean, 4),
                    exit_cdf=[round(float(c), 4) for c in np.cumsum(p)[:-1]])
        self.telemetry.gauge("serving/exit_pass_mean").set(mean)

    def _state_attrs(self, n_active: int) -> dict:
        """What a decode span says of the per-sequence state (nothing
        for a model without): ``state_rows``, the active rows whose state the step
        advanced, and ``state_bytes``, the per-sequence bytes it had to read and
        write for them (2 x rows x the layers that keep state x a layer's leaves:
        a mixer's state + convolution tail, a short convolution's tail, a delta
        rule's matrix + filter tail); and for a model with layers by operator how
        many of each the program ran (``conv_layers`` or ``delta_layers``,
        ``attn_layers``)."""
        if not self.state_bytes_per_slot:
            return {}
        return {"state_rows": n_active,
                "state_bytes": 2 * n_active * self.state_bytes_per_slot,
                **self.operator_attrs}

    def _ring_attrs(self, live_positions) -> dict:
        """What a span says of the window layers' rings (nothing for a model
        without): ``window_layers``, how many layers keep one, and ``ring_tokens``,
        the positions ONE of them read, summed over the call's live query rows
        (a query at position p reads min(p + 1, window) of them);
        ``cached_tokens`` beside it is ONE whole-context layer's."""
        if not self.window_layers:
            return {}
        p = np.asarray(live_positions, np.int64).reshape(-1)
        return {"window_layers": self.window_layers,
                "ring_tokens": int(np.sum(np.minimum(p + 1, self.cfg.local_attn_window)))}

    def _expert_gemm(self, rows: int) -> dict:
        """``expert_gemm`` of a routed model's call of ``rows`` tokens (a decode
        step's: every slot; a verify step's: every slot's block): what multiplies
        them through the experts, by the rule the program was traced by
        (``dropless.expert_gemm_form``). Nothing for a model with no routed layer."""
        if not self.expert_bank:
            return {}
        return {"expert_gemm": expert_gemm_form(self.cfg, self.params["moe"]["experts"], int(rows),
                                                self.expert_bank == "in_place")}

    def _block_attrs(self, rows: int, live: int) -> dict:
        """What a prefill or chunk span says of the block its program was traced
        with. A state-space mixer's scan: the chunks it ran (those of the
        bucket's padding among them) and ``state_rows``, the live rows that moved
        the state. A model with layers by operator: ``conv_layers`` (or
        ``delta_layers``) / ``attn_layers`` it ran, ``state_rows`` and ``state_bytes``,
        the state it wrote for the slot, and with delta layers ``scan_chunks``, the
        chunks of ``DELTA_CHUNK`` rows the rule's block form ran, and ``delta_block``,
        what ran them (``transformer.delta_block_form``, the rule the program was
        traced by: ``"kernel"`` or ``"xla"``). A routed
        model's ``expert_bank``: where the program reads
        layer l of the three banks from (``expert_bank_form``), and
        ``expert_gemm``: what multiplies its rows through the experts
        (``dropless.expert_gemm_form``, the rule the program was traced by).
        Nothing for a model with neither."""
        attrs = {}
        if self.expert_bank:
            attrs = {"expert_bank": self.expert_bank, **self._expert_gemm(rows)}
        if self.state_bytes_per_slot:
            attrs.update(state_rows=int(live), **self.operator_attrs)
            if self.cfg.ssm_state_size:
                attrs.update(scan_chunks=-(-int(rows) // self.cfg.ssm_chunk_size))
            else:  # the state written for the slot: one layer's leaves a layer that keeps them
                attrs.update(state_bytes=self.state_bytes_per_slot)
                if self.cfg.delta_layers:
                    attrs.update(scan_chunks=-(-int(rows) // tfm.DELTA_CHUNK),
                                 delta_block=tfm.delta_block_form(self.cfg, int(rows), self._cache,
                                                                  self.mesh))
        return attrs

    def _sampler_rows(self, sp, temperature, top_k, top_p):
        """The sampler operands of a call as its program gets them ([rows]
        float32 / int32 / float32 host arrays), with the form the program's
        sampler takes on them noted on the call's span as ``sampler``:
        ``sampler_form``, the rule the program itself branches on."""
        rows = (np.atleast_1d(np.asarray(temperature, np.float32)),
                np.atleast_1d(np.asarray(top_k, np.int32)),
                np.atleast_1d(np.asarray(top_p, np.float32)))
        sp.annotate(sampler=SAMPLER_FORMS[int(sampler_form(*rows, self.cfg.vocab_size))])
        return rows

    def _run(self, name: str, attrs: dict, program, operands, n_out: int, *,
             key: bool = True, fetch: bool = True, **rows):
        """The scaffold of three worker calls (``verify``, ``prefill``, ``chunk``):
        ``_dispatch`` then ``_fetch_results`` of the same program's run under one span
        ``name``. (``decode`` is made of the same two halves, of two different
        steps.) A call makes ONE trip into the runtime, the call of its own program:
        nothing eager (no program of its own, no upload of its own) runs before
        it. A call is one span ``name`` with two children and, under those, the
        four parts a call's cost beyond the device's work is made of:

          dispatch            entry until the program's call has returned: before
                              that the device cannot start. Its self time is
                              ``program()``: the look-up, or the first build
            dispatch/operands ``operands(sp)`` -> (the program's operands behind
                              params and cache, its sampler rows): host
                              conversions only, every one a numpy array or scalar
                              (or an array the device holds already)
            dispatch/enqueue  the watched program's call: the proxy's bookkeeping,
                              pjit's argument path over the parameter tree, the
                              batched upload of the host operands, the enqueue
          fetch               until the host holds the results (none with
                              ``fetch=False``: the call stays asynchronous)
            fetch/wait        the copies asked for (``copy_to_host_async``), then
                              ``block_until_ready`` on the host-bound outputs: the
                              runtime's launch latency and the device's run
            fetch/copy        ``device_get`` of the same outputs, as numpy: what
                              is left of the copy back once they are ready

        The key (``key=True``: every call but a greedy ``verify``, whose program
        draws nothing) goes in as the device array ``self._rng`` between the two
        groups of operands; the program splits it and returns the carried half
        first behind the cache, which is stored and never fetched: like the
        cache it is a future until something waits for the call (PR 36; the
        eager split of the key it replaces was a program launch of 0.83 ms
        a call on the host for 3 us of device time). The operand LISTS of the
        four programs are pinned: ``chipbench/drivers/serve.py::_memory_analysis``
        and ``chipbench/rehearse_compile.py`` type them by position.

        All outputs of one program become ready together, and ``wait`` asks for
        the copies before it waits, as ``device_get`` itself does first: the
        transfers follow the program on the device with no host round trip
        between. So ``wait`` then ``copy`` moves the same bytes in the same
        order, and at the same times, as one ``device_get`` of them would: the
        split changes what is timed, not what is fetched (waiting FIRST and
        asking afterwards cost 0.25 ms a call on the chip: PERF.md section 6, PR 35).
        The fetch syncs, so the call span's own duration is device-true, and it
        is what the callers feed the latency histograms from.

        On the call's span: ``compiled`` (a call that compiled is no latency
        datum, and the scheduler's heartbeat exempts its step), ``h2d``, the
        host arrays handed to the program (every operand behind params and
        cache that the device does not hold already: not the carried key, not a
        decode step's carried tokens) and
        ``d2h``, the separate arrays fetched (the experts a ``routing_log`` asks
        for are not among them). Returns ``(span, the n_out fetched arrays or
        None)``; what follows them in the fetch is a routed model's load, noted
        with ``rows``, or an exit gate's mean distribution (``_note_exit``)."""
        with self.telemetry.span(name, **attrs) as sp:
            out, chosen = self._dispatch(sp, program, operands, key=key)
            if not fetch:
                sp.annotate(d2h=0)
                if self.routing_log is not None and chosen is not None:
                    # an unfetched call's choices, for the check alone: the one wait an
                    # asynchronous call is otherwise spared, and only while the log is on
                    self.routing_log.append({"span": sp.name, **rows,
                                             "chosen": np.asarray(chosen)})
                return sp, None
            return sp, self._fetch_results(sp, out, chosen, n_out, rows)

    def _dispatch(self, sp, program, operands, *, key: bool = True, first=None):
        """The first half of a call under its span ``sp``: the ``dispatch`` span and
        its parts, the program's call (``first``: what a call runs inside
        ``dispatch`` ahead of them, a decode step's token merge). Returns ``(the
        outputs behind the cache and the key, on the device, a routed model's choices
        or None)`` and notes ``compiled`` and ``h2d`` on ``sp``."""
        tm = self.telemetry
        with tm.span("dispatch"):
            if first is not None:
                first()
            with tm.span("operands"):
                head, sampler = operands(sp)
            prog = program()
            with tm.span("enqueue"):
                if key:
                    self._cache, self._rng, *out = prog(
                        self.params, self._cache, *head, self._rng, *sampler)
                else:
                    self._cache, *out = prog(self.params, self._cache, *head, *sampler)
        compiled = bool(prog.last_call_compiled)
        self.step_compiled |= compiled
        if compiled:  # a first call outlives the ring: its trace, compile or load, and first run
            sp.keep = True
        chosen = None  # a routed model's choices: a device array no fetch waits for
        if self.cfg.moe_routing == "dropless":
            *out, chosen = out
        sp.annotate(compiled=compiled,
                    h2d=sum(isinstance(x, (np.ndarray, np.generic)) for x in (*head, *sampler)))
        return out, chosen

    def _fetch_results(self, sp, out, chosen, n_out: int, rows: dict):
        """The second half of a call under the span ``sp``: the ``fetch`` span and its
        two parts over ``out``, a dispatch's host-bound outputs. Notes ``d2h`` and what
        follows the ``n_out`` results (a routed model's load with ``rows``, the rows of
        the run that PRODUCED it; an exit gate's distribution) on ``sp`` and returns
        the results as numpy."""
        tm = self.telemetry
        with tm.span("fetch"):
            with tm.span("wait"):
                for x in out:
                    x.copy_to_host_async()
                jax.block_until_ready(out)
            with tm.span("copy"):
                out = tuple(np.asarray(x) for x in jax.device_get(out))
        sp.annotate(d2h=len(out))
        if self.cfg.exit_gate:
            self._note_exit(sp, out[n_out])
        else:
            self._note_load(sp, out[n_out:], chosen, **rows)
        return out[:n_out]

    def decode(self, toks, from_host, pos, wpos, active, temp, top_k, top_p, *,
               rows_discarded: int = 0):
        """ENQUEUE one decode step over every slot, then FETCH the step enqueued
        before it, if one is unfetched: the host's side of a step (operands,
        enqueue, copy back, and whatever the scheduler does between two calls) runs
        while the device works on a step that was queued before it began. THE
        compile-stable path: a second compilation means an operand's
        shape/dtype/sharding drifted and every admission would pay a retrace (the
        watchdog warns or raises per config).

        The token operand stays on the device: ``_toks``, the tokens the last
        enqueued step sampled (0 in its idle rows). ``from_host`` [n_slots] bool
        marks the rows whose token the HOST knows instead, ``toks`` [n_slots]: there
        ``_build_merge``'s program puts them in first (span ``dispatch/merge``); a
        step with no such row takes ``_toks`` as the last step returned it.

        One span ``decode`` a call, as for the other calls, but of TWO device steps:
        its ``dispatch`` (``operands``, ``merge`` where it ran, ``enqueue``) is the
        step handed over, its ``fetch`` (``wait``, ``copy``) the step before. What the
        span says of the rows (``n_active``, ``cached_tokens``, ...) is the enqueued
        step's; a routed model's load and an exit gate's distribution come with the
        fetch and are the fetched step's, as are the ``pos`` / ``active`` rows a
        ``routing_log`` entry pairs the choices with. ``ahead``: a step was unfetched
        when this one was enqueued (the device had work queued throughout);
        ``rows_discarded`` (where not 0): the rows of the fetched step that the
        scheduler drops, their request having ended since they were enqueued. With
        nothing unfetched the span has no ``fetch`` and the call returns None;
        otherwise host ``(next_token, bad_sentinel)`` [n_slots] of the FETCHED
        step."""
        tm = self.telemetry
        # ``cached_tokens``: the cache positions the step attends to, summed
        # over its live rows (row at ``pos`` reads [0, pos]); ``attn``: the form
        # the program was traced with (``prefill`` has flash / dense);
        # ``sampler``: the form its sampler takes on these rows;
        # ``state_rows`` / ``state_bytes`` (a state-space mixer only): the active
        # rows whose per-sequence state the step advanced, and the bytes of it
        # the step had to read AND write; ``kv_rows_fetched`` (a step through the
        # Pallas decode kernel only): the positions of ONE cache layer the
        # kernel's walk fetched for the live rows, whole blocks of ``kv_block``
        n_active = int(np.count_nonzero(active))
        live_pos = np.asarray(pos)[np.asarray(active, bool)]
        prev = self._pending
        attrs = dict(n_active=n_active, cached_tokens=int(np.sum(live_pos + 1)),
                     attn=tfm.cache_step_form(self.cfg), **self._state_attrs(n_active),
                     **self._ring_attrs(live_pos), **self.pass_attrs,
                     **self._expert_gemm(len(active)), ahead=prev is not None)
        if self.kv_block:
            attrs["kv_rows_fetched"] = tfm.kv_rows_fetched(live_pos, self.kv_block)

        def operands(sp):
            # host arrays straight into the jitted call (pjit batches the uploads;
            # ``h2d`` counts them: six here, and the merge's two where it ran), the
            # carried tokens and the carried key the two device operands; dtypes are
            # pinned by the engine's per-slot state arrays. The operand list is
            # pinned too (``_run``)
            return ((self._toks, pos, np.asarray(wpos, np.int32), active),
                    self._sampler_rows(sp, temp, top_k, top_p))

        def merge():
            with tm.span("merge"):
                self._toks = self._merge(np.asarray(from_host, np.bool_),
                                         np.asarray(toks, np.int32), self._toks)

        with tm.span("decode", **attrs) as sp:
            merged = bool(np.any(from_host))
            t_enqueue = time.perf_counter()
            out, chosen = self._dispatch(sp, self._decode_prog, operands,
                                         first=merge if merged else None)
            self._toks = out[0]
            self._pending = (out, chosen,  # ``span``: the log's word for the step, whoever fetches
                             dict(span="decode", pos=np.array(pos), active=np.array(active, bool)),
                             t_enqueue, sp.attrs["compiled"])
            if merged:
                sp.annotate(h2d=sp.attrs["h2d"] + 2, merged=True)
            fetched = self._fetch_pending(sp, prev, rows_discarded)
        self._decode_steps += 1
        tm.counter("serving/decode_steps").inc()
        if prev is not None:
            self._decode_steps_ahead += 1
            tm.counter("serving/decode_steps_ahead").inc()
        return fetched

    def collect(self, *, rows_discarded: int = 0):
        """Fetch the decode step that is enqueued and unfetched, and enqueue nothing:
        the scheduler's call where the next step needs this one's tokens on the host
        first (a drafter, an armed fault injector), where no slot continues, and
        before ``drain()`` / ``serve()`` return. Its span is ``collect``, with a
        ``fetch`` and nothing else (so a ``decode`` span is a device step, one each).
        Returns host ``(next_token, bad_sentinel)``, or None where nothing is
        unfetched (no span then)."""
        prev, self._pending = self._pending, None
        if prev is None:
            return None
        with self.telemetry.span("collect") as sp:
            return self._fetch_pending(sp, prev, rows_discarded)

    def _fetch_pending(self, sp, prev, rows_discarded: int = 0):
        """``_fetch_results`` of ``prev``, a ``_pending`` decode step (None: nothing, and
        ``d2h`` 0), under ``sp``; its time feeds ``serving/decode_step_sec``: from
        when the device could begin it (its enqueue, or the fetch of the step before
        where that came later) until its results were on the host."""
        if prev is None:
            sp.annotate(d2h=0)
            return None
        if rows_discarded:
            sp.annotate(rows_discarded=rows_discarded)
        out, chosen, rows, t_enqueue, compiled = prev
        blocks = self.block_len > 1  # a worker's steps are of one kind: (toks, mask, bad) against (token, bad)
        if blocks:  # the rows' confidences: no fetch waits for them
            *out, conf = out
        fetched = self._fetch_results(sp, out, chosen, 3 if blocks else 2, rows)
        done = time.perf_counter()
        if not compiled:
            self.telemetry.histogram(
                "serving/block_step_sec" if blocks else "serving/decode_step_sec").observe(
                done - max(t_enqueue, self._t_fetched))
        self._t_fetched = done
        if blocks and self.block_log is not None:
            self.block_log.append({**rows, "toks": fetched[0], "mask": fetched[1],
                                   "bad": fetched[2], "conf": np.asarray(conf)})
        return fetched

    def block_step(self, opened, new_toks, new_mask, pos, wpos, active, count, threshold,
                   temp, top_k, top_p, *, masked_rows: int, commits: int,
                   rows_discarded: int = 0):
        """ENQUEUE one block step over every slot (``_build_block_step`` has the
        operands), then FETCH the step enqueued before it, if one is unfetched: ``decode``'s
        two halves, of two device steps, for a model that generates by diffusion over
        blocks. The open blocks stay on the device between steps (``_btoks`` /
        ``_bmask``); the host hands over only the blocks that open.

        One span ``block_step`` a call with ``dispatch`` / ``fetch`` children as the other
        programs have. Of the ENQUEUED step: ``rows`` (n_slots x B), ``slots_active``,
        ``masked_rows`` (masked positions at its entry), ``revealed`` (what ``count``
        reveals; the dynamic strategy may reveal more, and ``serving/tokens_revealed``
        counts what the fetch shows), ``commits`` (the active slots whose pass is a
        commit), ``live_keys`` (the keys ONE layer's block rows are required to read: a
        slot at ``pos`` reads pos + B from each of its B rows), ``expert_rows_held``,
        ``expert_gemm``, ``ahead``. A routed model's load comes with the fetch and is the
        FETCHED step's. Returns host ``(toks, mask, bad)`` of the fetched step ([n_slots,
        B] twice and [n_slots]), or None where nothing was unfetched."""
        tm = self.telemetry
        B, n = self.block_len, self.n_slots
        on = np.asarray(active, bool)
        n_active = int(np.count_nonzero(on))
        prev = self._pending
        attrs = dict(rows=n * B, slots_active=n_active, masked_rows=int(masked_rows),
                     revealed=int(np.sum(np.asarray(count)[on])), commits=int(commits),
                     live_keys=int(np.sum((np.asarray(pos, np.int64)[on] + B) * B)),
                     attn=tfm.cache_step_form(self.cfg), **self._expert_gemm(n * B),
                     ahead=prev is not None)
        if self.expert_bank:
            attrs.update(expert_bank=self.expert_bank,
                         expert_rows_held=n * B * self.cfg.moe_top_k * self.routed_layers)

        def operands(sp):
            # the carried blocks and the carried key are the device's; the rest are this
            # call's own host arrays (``h2d`` counts them)
            return ((self._btoks, self._bmask, np.asarray(opened, np.bool_),
                     np.asarray(new_toks, np.int32), np.asarray(new_mask, np.bool_),
                     np.asarray(pos, np.int32), np.asarray(wpos, np.int32), on,
                     np.asarray(count, np.int32), np.asarray(threshold, np.float32)),
                    self._sampler_rows(sp, temp, top_k, top_p))

        with tm.span("block_step", **attrs) as sp:
            t_enqueue = time.perf_counter()
            out, chosen = self._dispatch(sp, self._block_prog, operands)
            self._btoks, self._bmask = out[0], out[1]
            out.append(out.pop(3))  # (toks, mask, bad, the load, conf): ``_fetch_pending``
            self._pending = (out, chosen,
                             dict(span="block_step", pos=np.array(pos), active=on.copy(),
                                  opened=np.array(opened, bool), count=np.array(count)),
                             t_enqueue, sp.attrs["compiled"])
            fetched = self._fetch_pending(sp, prev, rows_discarded)
        self._block_steps += 1
        tm.counter("serving/block_steps").inc()
        tm.counter("serving/block_commits").inc(int(commits))
        if prev is not None:
            self._block_steps_ahead += 1
            tm.counter("serving/block_steps_ahead").inc()
        return fetched

    def verify(self, depth: int, toks, pos, wpos, active, temp, top_k, top_p,
               greedy_only: bool = False, warm: bool = False):
        """Score every slot's draft block in one forward pass through the
        ``depth`` verify program — compile-stable programs per pow2 depth
        bucket (at most two: the all-greedy fast path and the mixed-
        sampling one), the chunked-prefill discipline applied to decode.
        Returns host ``(accept, resample, clean, bad)`` arrays
        ([n, depth] / [n, depth+1] / [n, depth+1] / [n])."""
        tm = self.telemetry
        attrs = dict(n_active=int(np.count_nonzero(active)), depth=depth,
                     cached_tokens=int(np.sum(
                         (np.asarray(pos) + depth + 1)[np.asarray(active, bool)])),
                     attn=tfm.cache_step_form(self.cfg), **({"warm": True} if warm else {}),
                     **self.pass_attrs, **self._expert_gemm(len(active) * (depth + 1)))
        # host arrays go straight into the jitted call: pjit's C++ argument
        # path uploads them in one batch, and the greedy family's trimmed
        # signature (no rng/temp/top_k/top_p — dead operands there) skips the
        # uploads and leaves the worker's key where it is (``key=False``); the
        # sampled family takes and returns it like ``decode``
        sp, out = self._run(
            "verify", attrs, lambda: self._verify_prog(depth, greedy_only),
            lambda sp: ((toks, pos, np.asarray(wpos, np.int32), active),
                        () if greedy_only else (temp, top_k, top_p)),
            1 if greedy_only else 4, key=not greedy_only,
            pos=np.array(pos), active=np.array(active, bool))
        if greedy_only:
            p, = out  # one packed array: the ONE fetch
            tokens = p[:, depth:2 * depth + 1]
            out = (p[:, :depth].astype(bool), tokens, tokens, p[:, -1].astype(bool))
        if warm:
            # pre-warm dispatch (all slots inactive, writes dropped): it
            # exists to COMPILE, so it is neither a latency datum nor a
            # verify step the acceptance accounting should see
            return out
        if not sp.attrs["compiled"]:
            tm.histogram("serving/verify_step_sec").observe(sp.dur_s)
        tm.counter("serving/verify_steps").inc()
        tm.counter(f"serving/verify_bucket[{depth}]").inc()
        return out

    def prefill(self, bucket: int, padded, slot: int, true_len: int,
                temperature: float, top_k: int, top_p: float, *, uid=None):
        """One-shot bucketed prompt prefill into ``slot``. Returns the host
        ``(first_token, bad)`` pair. ``uid`` only labels the span."""
        tm = self.telemetry
        # ``attn``: the form the bucket's program was traced with (its local
        # cache is the bucket long: ``_build_prefill``), and the grids its
        # whole-context and its window layers took through the flash kernel
        attrs = dict(uid=uid, slot=slot, bucket=bucket, true_len=true_len,
                     attn=tfm.cache_block_form(self.cfg, bucket),
                     **tfm.causal_grid_form(self.cfg, bucket),
                     **tfm.window_grid_form(self.cfg, bucket),
                     **self._block_attrs(bucket, true_len),
                     **self._ring_attrs(np.arange(true_len)), **self.pass_attrs)
        sp, (tok, bad) = self._run(
            "prefill", attrs, lambda: self._prefill_prog(bucket),
            lambda sp: ((np.asarray(padded, np.int32), np.int32(slot), np.int32(true_len)),
                        self._sampler_rows(sp, temperature, top_k, top_p)),
            2, uid=uid, slot=slot, true_len=true_len)
        if not sp.attrs["compiled"]:
            tm.histogram("serving/prefill_sec").observe(sp.dur_s)
        tm.counter(f"serving/prefill_bucket[{bucket}]").inc()
        return int(tok[0]), bool(bad.reshape(-1)[0])

    def chunk(self, width: int, toks, slot: int, start: int, live: int,
              temperature: float, top_k: int, top_p: float, *, fetch: bool,
              uid=None):
        """One prompt chunk through the ``width`` program. ``fetch=False``
        (intermediate chunk) returns None and leaves the dispatch async —
        the sampled token is garbage mid-prompt logits, and the next decode
        step overlaps with the chunk (its span has no ``fetch`` child and is
        no latency datum); the FINAL chunk fetches and returns
        ``(first_token, bad)``. ``uid`` only labels the span."""
        tm = self.telemetry
        # ``whole_keys``: the keys ONE whole-context layer's live queries required
        # (the query at position p sees p + 1), ``ring_tokens`` ONE window layer's
        # (min(p + 1, window)); ``attn_chunk``: how the whole-context layers read the
        # slot's cache (``cache_chunk_form``: over the live key blocks, or all of
        # ``Smax`` densely); ``expert_rows_held``: the (row, expert) pairs the routed
        # layers multiplied, the padding's among them, known before the call where
        # every expert is held (a held share's count comes back with a FETCHED call)
        seen = np.arange(int(start), int(start) + int(live), dtype=np.int64)
        attrs = dict(uid=uid, slot=slot, start=int(start), width=width, live=live, fetch=fetch,
                     cached_tokens=int(start) + int(live), whole_keys=int(np.sum(seen + 1)),
                     attn=tfm.cache_step_form(self.cfg),
                     attn_chunk=tfm.cache_chunk_form(self.cfg, 1, width, self.Smax),
                     **self._block_attrs(width, live), **self._ring_attrs(seen),
                     **self.pass_attrs)
        if self.expert_bank and not self.cfg.moe_experts_held:
            attrs["expert_rows_held"] = int(width) * self.cfg.moe_top_k * self.routed_layers
        sp, out = self._run(
            "chunk", attrs, lambda: self._chunk_prog(width),
            lambda sp: ((np.asarray(toks, np.int32), np.int32(slot), np.int32(start),
                         np.int32(live)),
                        self._sampler_rows(sp, temperature, top_k, top_p)),
            2, fetch=fetch, uid=uid, slot=slot, start=start, live=live)
        tm.counter(f"serving/chunk_bucket[{width}]").inc()
        if not fetch:
            return None
        if not sp.attrs["compiled"]:
            tm.histogram("serving/chunk_prefill_sec").observe(sp.dur_s)
        tok, bad = out
        return int(tok[0]), bool(bad.reshape(-1)[0])

    def prefix_fetch(self, pool_slot: int, slot: int) -> None:
        """Copy a prefix-pool window into ``slot`` (ONE compiled program;
        slot indices are traced operands)."""
        if self._fetch is None:
            wd = self.telemetry.watchdog
            self._fetch = wd.watch(
                self._build_fetch(),
                wd.unique_name("serving/prefix_fetch"), stable=True)
        self._cache = self._fetch(
            self._cache, self._pool, jnp.int32(pool_slot), jnp.int32(slot))
        self.step_compiled |= bool(self._fetch.last_call_compiled)

    def prefix_store(self, slot: int, pool_slot: int) -> None:
        """Copy ``slot``'s leading window into the prefix pool."""
        if self._store is None:
            wd = self.telemetry.watchdog
            self._store = wd.watch(
                self._build_store(),
                wd.unique_name("serving/prefix_store"), stable=True)
        self._pool = self._store(
            self._pool, self._cache, jnp.int32(slot), jnp.int32(pool_slot))
        self.step_compiled |= bool(self._store.last_call_compiled)

    def _refuse_state(self, what: str) -> None:
        if self.state_bytes_per_slot:
            raise NotImplementedError(
                f"{what} with per-sequence state in the cache (a state-space mixer's recurrent "
                "state, a short convolution's tail, a delta rule's matrix): the wire form carries "
                "windows of per-token K/V and no per-sequence state")
        if self.window_layers:
            raise NotImplementedError(
                f"{what} with window layers (local_attn_layers): the wire form carries windows "
                "of per-token K/V and no ring of a window layer's last positions")

    def kv_export(self, width: int, slot: int, start: int):
        """Fetch one [start, start+width) KV window of ``slot`` to the host
        — the disaggregated handoff's wire unit. Pow2 ``width`` keeps the
        program family bounded (one program per width, slot/start traced).
        Returns host ``(k, v)`` arrays [L, 1, width, H, Dh]."""
        self._refuse_state("kv_export")
        prog = self._kv_export_prog(width)
        kv = prog(self._cache, jnp.int32(slot), jnp.int32(start))
        self.step_compiled |= bool(prog.last_call_compiled)
        self.telemetry.counter(f"serving/kv_export_bucket[{width}]").inc()
        k, v = jax.device_get((kv["k"], kv["v"]))
        return np.asarray(k), np.asarray(v)

    def kv_import(self, width: int, k, v, slot: int, start: int) -> None:
        """Splat one host KV window into [start, start+width) of ``slot``
        — the import half of the handoff wire. Idempotent (a replayed
        window writes the same bytes), donation + pinned output sharding
        exactly like the chunk path, so the decode program's cache operand
        never drifts."""
        self._refuse_state("kv_import")
        prog = self._kv_import_prog(width)
        self._cache = prog(
            self._cache,
            {"k": jnp.asarray(k), "v": jnp.asarray(v)},
            jnp.int32(slot), jnp.int32(start))
        self.step_compiled |= bool(prog.last_call_compiled)
        self.telemetry.counter(f"serving/kv_import_bucket[{width}]").inc()

    def fill_slot(self, slot: int, value: float) -> None:
        """Overwrite one slot's whole KV row with ``value`` — ONE compiled
        program (slot and value are traced operands), cache sharding pinned
        so the decode program's operand never drifts (no decode recompile).
        Two callers: fault injection poisons with NaN so the next program
        attending to the slot genuinely computes non-finite logits (the
        device-side sentinel, not host bookkeeping, must catch it), and
        quarantine scrubs with 0 before the slot re-enters rotation.

        The scrub is load-bearing, not hygiene: attention computes scores
        over ALL cache positions and zeros masked ones AFTER the fact, so a
        NaN parked anywhere in the row leaks through ``0 * NaN = NaN`` into
        every later occupant's logits even though the mask "hides" it —
        NaN-faulted KV must never survive into a reused slot."""
        if self._poison is None:
            self.step_compiled = True  # first fill call compiles the program

            def fill(cache, slot, val):
                return jax.tree.map(lambda c: c.at[:, slot].set(val), cache)

            wd = self.telemetry.watchdog
            self._poison = wd.watch(
                donated_jit(fill, donate_argnums=(0,),
                            out_shardings=self._cache_shardings),
                wd.unique_name("serving/fill_slot"), stable=True)
        self._cache = self._poison(
            self._cache, jnp.int32(slot), jnp.asarray(value, tfm.cache_dtype(self._cache)))

    def hbm_pools(self) -> dict:
        """Named device-memory pools this worker holds — the HBM ledger's
        rows (bytes from array metadata, no device sync)."""
        state, ring = self._cache.get(tfm.STATE, {}), self._cache.get(tfm.RING, {})
        pools = {
            "params": tree_bytes(self.params),
            "slot_kv_cache": tree_bytes(self._cache) - tree_bytes(state) - tree_bytes(ring),
        }
        if ring:  # the window layers' rings: constant in the sequence, like state
            pools["slot_kv_ring"] = tree_bytes(ring)
        if state:  # a state-space mixer's per-sequence leaves: constant in the sequence
            pools["slot_state"] = tree_bytes(state)
        if self._pool is not None:
            pools["prefix_pool"] = tree_bytes(self._pool)
        return pools

    def compile_counts(self) -> dict:
        """How many XLA programs this worker traced — the continuous-batching
        invariant is decode == 1 regardless of workload mix, and every chunk
        width / prefix copy is likewise ONE program."""
        out = {
            "decode": int(self._decode._cache_size()) if self._decode is not None else 0,
            "prefill": {b: int(f._cache_size()) for b, f in sorted(self._prefills.items())},
            "decode_steps": self._decode_steps,
            "decode_steps_ahead": self._decode_steps_ahead,
        }
        if self.block_len > 1:
            out.update(block_step=int(self._block._cache_size()) if self._block is not None else 0,
                       block_steps=self._block_steps, block_steps_ahead=self._block_steps_ahead)
        if self._chunk_progs:
            out["chunk_prefill"] = {w: int(f._cache_size())
                                    for w, f in sorted(self._chunk_progs.items())}
        if self._verifies:
            # keyed by depth; the value folds both sampler families (all-
            # greedy + mixed), so the bounded-set contract reads "<= 2 per
            # pow2 bucket"
            ver: dict[int, int] = {}
            for (d, _greedy), f in self._verifies.items():
                ver[d] = ver.get(d, 0) + int(f._cache_size())
            out["verify"] = dict(sorted(ver.items()))
        if self._fetch is not None:
            out["prefix_fetch"] = int(self._fetch._cache_size())
        if self._store is not None:
            out["prefix_store"] = int(self._store._cache_size())
        if self._kv_exports:
            out["kv_export"] = {w: int(f._cache_size())
                                for w, f in sorted(self._kv_exports.items())}
        if self._kv_imports:
            out["kv_import"] = {w: int(f._cache_size())
                                for w, f in sorted(self._kv_imports.items())}
        if self._poison is not None:
            out["fill_slot"] = int(self._poison._cache_size())
        out["token_merge"] = int(self._merge._cache_size())
        return out


class ServingEngine:
    """Continuous batching over an ``InferenceEngine``'s model/params.

    This class is the HOST SCHEDULER half of the serving engine — queues,
    admission, deadlines, shedding, quarantine, the terminal-uid contract.
    All device state and compiled programs live in ``self.worker``
    (``SlotWorker``), and ``inference/router.py`` builds a fleet by putting
    N of these schedulers behind one Router.

    Config keys (``config`` dict or keyword arguments; kwargs win —
    the ``serving`` block of runtime/config.py is this dict's schema;
    a ``router`` sub-block is consumed by ``Router``, not here):
      n_slots             concurrent sequences resident in the slot cache
      max_seq_len         per-slot admission budget (prompt + generated);
                          must not exceed the engine's sequence budget. Only
                          the cache allocation rounds up to a multiple of
                          128 (Pallas decode-kernel block streaming).
                          Default: the engine's sequence budget.
      min_prefill_bucket  smallest prompt bucket (power of two padding floor)
      seed                sampler PRNG seed
      replica_id          engine identity stamped into telemetry_snapshot()
                          (a Router assigns one per replica)
      jsonl_path          telemetry JSONL event log ("" = off)
      watchdog_mode       off|warn|raise when a compile-stable path
                          compiles a second time (default warn)
      prefix_cache        {enabled, n_slots, max_prefix_len, block,
                          insert_policy, min_hits} — prompt-prefix KV reuse
                          (runtime/config.PrefixCacheConfig; docs/serving.md)
      chunked_prefill     {enabled, chunk_size, chunks_per_step} — admission
                          chunks interleaved with decode
                          (runtime/config.ChunkedPrefillConfig)
      speculation         {enabled, depth, ngram_min_match, draft_source} —
                          self-speculative multi-token decoding: host-side
                          n-gram drafts verified by a pow2-bucketed family
                          of compiled verify programs; greedy requests keep
                          bitwise parity with non-speculative decode
                          (runtime/config.SpeculationConfig; docs/serving.md)
      max_queue_len       bound on ARRIVED not-yet-admitted requests; excess
                          arrivals are load-shed with a typed reason
                          (0 = unbounded; docs/resilience.md)
      default_deadline_s  deadline applied to requests without their own
                          (seconds after arrival; 0 = none)
      quarantine_max_requeues   clean replays granted to a request whose
                          logits went non-finite before it is failed
      slot_quarantine_after     consecutive NaN faults in one slot before
                          that slot is pulled from rotation
      fault_injection     {enabled, seed, rate, garbage_logits_*} —
                          deterministic NaN-logit injection
                          (runtime/config.FaultInjectionConfig)

    Telemetry is always on (host-side dict updates per step — decode already
    pays a device call): TTFT/TPOT histograms, queue depth, slot occupancy,
    admissions/evictions, per-bucket prefill counts, prefix-cache hit/reuse
    counters + pool-occupancy gauge, chunks-per-admit histogram, and a
    recompile watchdog over decode (stable: ONE program), each prefill
    bucket, each chunk width, and the prefix fetch/store programs.
    ``telemetry_snapshot()`` reports everything in one call; pass
    ``telemetry=`` to share a bundle across engines.
    """

    def __init__(self, engine: InferenceEngine, config: dict | None = None,
                 *, n_slots: int | None = None, max_seq_len: int | None = None,
                 min_prefill_bucket: int | None = None, seed: int | None = None,
                 telemetry: Telemetry | None = None,
                 replica_id: int | str | None = None,
                 prefix_cache: PrefixCacheConfig | dict | None = None,
                 chunked_prefill: ChunkedPrefillConfig | dict | None = None,
                 speculation: SpeculationConfig | dict | None = None,
                 fault_injection: FaultInjectionConfig | dict | None = None,
                 role: str | None = None):
        config = dict(config or {})
        config.pop("router", None)  # the Router's block, not this engine's
        config.pop("gateway", None)  # the HTTP front door's block
        # disaggregated serving role (docs/serving.md "Disaggregated
        # prefill/decode"): ``both`` (the co-located default), ``prefill``
        # (admission + chunked prefill, then park for KV handoff), or
        # ``decode`` (receives handoffs via kv_import_*, owns decode/
        # speculation/SSE progress). A Router or worker CLI assigns it.
        self.role = role if role is not None else config.pop("role", "both")
        if self.role not in ("both", "prefill", "decode"):
            raise ValueError(
                f"serving role must be both|prefill|decode, got {self.role!r}")
        if self.role != "both" and engine.cfg.kv_lora_rank:
            raise NotImplementedError(
                f"serving role {self.role!r} with latent attention (kv_lora_rank > 0): the "
                "prefill -> decode handoff's wire form (inference/rpc.py: raw or int8 "
                "windows of per-head K/V) has not carried the latent cache tree yet")
        # the cache layout's own word: a model that keeps per-sequence state (a
        # state-space mixer's, a short convolution's tail), whatever field says so
        recurrent = tfm.STATE in tfm.cache_layout(engine.cfg)
        if self.role != "both" and recurrent:
            raise NotImplementedError(
                f"serving role {self.role!r} with per-sequence state in the cache (a state-space "
                "mixer's, a short convolution's tail): the prefill -> decode handoff (kv_export / "
                "kv_import) carries windows of per-token K/V and no per-sequence state")
        windowed = bool(engine.cfg.window_layers)
        if self.role != "both" and windowed:
            raise NotImplementedError(
                f"serving role {self.role!r} with window layers (local_attn_layers): the prefill "
                "-> decode handoff (kv_export / kv_import) carries windows of per-token K/V and "
                "no ring of a window layer's last positions")
        n_slots = n_slots if n_slots is not None else config.get("n_slots", 8)
        max_seq_len = max_seq_len if max_seq_len is not None else config.get(
            "max_seq_len", 0)
        # 0/None = the engine's sequence budget — the typed schema's default
        # (runtime/config.ServingConfig.max_seq_len=0), so a dataclass dump
        # of the `serving` block drops in unchanged
        max_seq_len = max_seq_len or min(engine.cfg.max_seq_len, engine.max_out_tokens)
        min_prefill_bucket = (min_prefill_bucket if min_prefill_bucket is not None
                              else config.get("min_prefill_bucket", 16))
        seed = seed if seed is not None else config.get("seed", 0)
        lc = config.get("ledger", {})
        if isinstance(lc, dict):
            lc = LedgerConfig(**lc)
        self.ledger_cfg: LedgerConfig = lc
        rt = config.get("request_trace", {})
        if isinstance(rt, dict):
            rt = RequestTraceConfig(**rt)
        ts = config.get("timeseries", {})
        if isinstance(ts, dict):
            ts = TimeSeriesConfig(**ts)
        slo = config.get("slo", {})
        if isinstance(slo, dict):
            slo = SLOConfig(**slo)
        inc = config.get("incidents", {})
        if isinstance(inc, dict):
            inc = IncidentConfig(**inc)
        self.timeseries_cfg: TimeSeriesConfig = ts
        self.slo_cfg: SLOConfig = slo
        self.incidents_cfg: IncidentConfig = inc
        self.telemetry = telemetry if telemetry is not None else Telemetry(
            jsonl_path=config.get("jsonl_path", ""),
            watchdog_mode=config.get("watchdog_mode", "warn"),
            ledger=lc.enabled,
            ledger_collectives=lc.collectives.enabled,
            ici_gbps=lc.collectives.ici_gbps,
            jsonl_max_bytes=int(config.get("jsonl_max_bytes", 0)),
            jsonl_keep=int(config.get("jsonl_keep", 3)),
        )
        # program-ledger join rules (telemetry/program_ledger.py): each
        # program family reads its measured wall time from its existing
        # latency histogram; decode — the steady-state path — nominates the
        # engine's headline serving/mfu gauge
        self.telemetry.ledger.bind(
            "serving/decode", wall_hist="serving/decode_step_sec",
            gauge="serving")
        self.telemetry.ledger.bind(
            "serving/prefill[", wall_hist="serving/prefill_sec")
        self.telemetry.ledger.bind(
            "serving/chunk_prefill[", wall_hist="serving/chunk_prefill_sec")
        self.telemetry.ledger.bind(
            "serving/verify[", wall_hist="serving/verify_step_sec")
        self.telemetry.ledger.bind(
            "serving/block_step", wall_hist="serving/block_step_sec")
        # collective X-ray axis mapping reads the inference mesh (a 1-device
        # mesh simply yields no collectives — anatomy rows stay labeled)
        self.telemetry.ledger.set_mesh_shape(dict(engine.mesh.shape))
        pc = prefix_cache if prefix_cache is not None else config.get("prefix_cache", {})
        if isinstance(pc, dict):
            pc = PrefixCacheConfig(**pc)
        cp = (chunked_prefill if chunked_prefill is not None
              else config.get("chunked_prefill", {}))
        if isinstance(cp, dict):
            cp = ChunkedPrefillConfig(**cp)
        self.prefix_cfg: PrefixCacheConfig = pc
        self.chunk_cfg: ChunkedPrefillConfig = cp
        sp = (speculation if speculation is not None
              else config.get("speculation", {}))
        if isinstance(sp, dict):
            sp = SpeculationConfig(**sp)
        self.spec_cfg: SpeculationConfig = sp
        if recurrent:
            # each moves the cache by POSITION, and a recurrent state has none: a
            # prefix's K/V can be copied but not the state after it (that takes a
            # snapshot a prefix), a rejected draft cannot be rolled back out of a
            # state already advanced (that takes a snapshot a verify step)
            for what, on in (("prefix_cache", pc.enabled), ("speculation", sp.enabled)):
                if on:
                    raise NotImplementedError(
                        f"{what} with per-sequence state in the cache (a state-space mixer's, a "
                        "short convolution's tail) has no code: it would run on a stale state; "
                        "serve with it off")
        if windowed:
            # each moves the cache by POSITION, and a ring was overwritten past its
            # window: a prefix's ring is not the ring after the prefix unless it is
            # stored with it, a rejected draft cannot be rolled back out of it. (A
            # chunk entering past position 0 has code: it attends over [ring ; chunk]
            # before the ring is written, ``transformer._cache_attention``.)
            for what, on in (("prefix_cache", pc.enabled), ("speculation", sp.enabled)):
                if on:
                    raise NotImplementedError(
                        f"{what} with window layers (local_attn_layers) has no code: a window "
                        "layer keeps a ring of its last local_attn_window positions, which "
                        "cannot be cut or rolled back at an old position; serve with it off")
        bg = config.get("block_generation", {})
        if isinstance(bg, dict):
            bg = BlockGenerationConfig(**bg)
        self.block_cfg: BlockGenerationConfig = bg
        # generation by diffusion over blocks (docs/serving.md): B positions a slot a
        # device step, T denoising passes and a commit a block
        self.block_len = int(engine.cfg.attn_block_length)
        self.block_passes = int(bg.denoising_steps) or self.block_len
        self._block_static = bg.strategy == "low_confidence_static"
        if self.block_len > 1:
            # each assumes that a step yields one token a row, at the causal mask: a
            # prefix's K/V ends where a block may not, a chunk's rows see their block's
            # later rows only once those are written, a draft is a causal continuation,
            # and a handoff ships a first token that a block model's prefill does not make
            for what, on in (("prefix_cache", pc.enabled), ("chunked_prefill", cp.enabled),
                             ("speculation", sp.enabled),
                             (f"serving role {self.role!r}", self.role != "both")):
                if on:
                    raise NotImplementedError(
                        f"{what} with generation by diffusion over blocks (attn_block_length "
                        f"= {self.block_len}) has no code: serve with it off")
            if not 1 <= self.block_passes <= self.block_len:
                raise ValueError(
                    f"block_generation.denoising_steps is 1 .. the block length "
                    f"{self.block_len} (0: the block length), got {bg.denoising_steps}")
        # the drafter is constructed eagerly so a bad draft_source fails at
        # engine build, not on the first decode step (draft_model needs the
        # model's vocab size to build its host-resident scorer)
        self._drafter = (make_drafter(sp, vocab_size=engine.cfg.vocab_size)
                         if sp.enabled else None)
        # host-side acceptance bookkeeping (spec_stats / the step-reply
        # piggyback): plain ints — no registry read on the hot path
        self._spec_drafted = 0
        self._spec_accepted = 0
        self._spec_steps = 0
        # per-slot ADAPTIVE draft cap (AIMD over the configured depth):
        # doubled on a fully-accepted draft, halved on any rejection. A
        # slot whose output is locally repetitive ramps to full depth in
        # log2(depth) steps; a slot the drafter keeps mispredicting sits
        # at cap 1-2, so its verify dispatches ride the CHEAP small pow2
        # buckets (near decode-step cost) instead of paying the deepest
        # program for drafts that die at position 0
        self._spec_len = np.full((n_slots,), 2, np.int32)
        # acceptance-aware suppression on top of AIMD: consecutive ZERO-
        # acceptance verifies floor the slot's cap at 1, and past
        # _SPEC_SUPPRESS_AFTER of them drafting stops entirely (cap 0 —
        # the slot rides plain decode steps) with a decaying re-probe
        # schedule, so a never-accepting request converges to decode-step
        # dispatch rates instead of paying verify overhead forever
        self._spec_zero_streak = np.zeros((n_slots,), np.int32)
        self._spec_probe_wait = np.zeros((n_slots,), np.int32)
        self._spec_suppressed_steps = 0
        self._spec_probes = 0

        # -- degradation knobs (docs/resilience.md) ---------------------
        self.max_queue_len = int(config.get("max_queue_len", 0))
        self.default_deadline_s = float(config.get("default_deadline_s", 0.0))
        self.quarantine_max_requeues = int(config.get("quarantine_max_requeues", 1))
        self.slot_quarantine_after = int(config.get("slot_quarantine_after", 2))
        # -- multi-tenant isolation (docs/serving.md) -------------------
        # tenant id -> TenantConfig. Purely host-side scheduler state: the
        # tenant axis never reaches a traced operand, so an arbitrary
        # tenant mix admits with ZERO new XLA programs. Empty policy (the
        # default) keeps the legacy single-pool FIFO semantics exactly.
        self._tenants: dict[str, TenantConfig] = {}
        self.set_tenant_policy(config.get("tenants", {}))
        # DWRR scheduler state: per-tenant deficit counters plus a rotation
        # cursor (tenant name, so ring membership churn can't skew it)
        self._dwrr_deficit: dict[str, float] = {}
        self._dwrr_at: str = ""
        fi = (fault_injection if fault_injection is not None
              else config.get("fault_injection", {}))
        if isinstance(fi, dict):
            fi = FaultInjectionConfig(**fi)
        self._inj: Optional[FaultInjector] = (
            FaultInjector(fi) if fi.enabled else None)

        self.engine = engine
        self.cfg = engine.cfg
        # NOTE: no mesh/params here — all device state lives in the worker;
        # this scheduler is pure host code
        self.n_slots = int(n_slots)
        # engine identity for fleet snapshots: every telemetry_snapshot()
        # carries it, so a Router's merged view stays attributable
        self.replica_id = (replica_id if replica_id is not None
                           else config.get("replica_id", 0))
        # admission budget stays at the MODEL's sequence limit (a learned
        # position table indexes out of range past it — jax clamps the gather
        # and the output would be silently wrong); the WORKER's cache
        # allocation rounds up to the 128 multiple the decode kernel needs
        engine_budget = min(engine.cfg.max_seq_len, engine.max_out_tokens)
        self.budget = int(max_seq_len)
        if self.budget > engine_budget:
            raise ValueError(
                f"max_seq_len ({self.budget}) exceeds the engine's sequence "
                f"budget {engine_budget} (min of model max_seq_len "
                f"{engine.cfg.max_seq_len} and max_out_tokens "
                f"{engine.max_out_tokens})")
        self.min_bucket = int(min_prefill_bucket)
        if self.block_len > 1 and (self.budget % self.block_len or self.min_bucket % self.block_len):
            raise ValueError(
                f"max_seq_len ({self.budget}) and min_prefill_bucket ({self.min_bucket}) are "
                f"multiples of the block length {self.block_len}: a slot holds whole blocks")

        # the compiled-program driver: device state + program inventory
        # (this scheduler is pure host code from here on)
        self.worker = SlotWorker(engine, self.telemetry, self.n_slots,
                                 self.budget, seed, pc)
        self.Smax = self.worker.Smax
        if recurrent:  # beside the HBM ledger's ``slot_state`` row (``hbm_pools``)
            self.telemetry.gauge("serving/slot_state_bytes").set(
                self.worker.hbm_pools()["slot_state"])

        # host-side prefix index: the radix trie mapping prompt prefixes to
        # the worker's pool slots (scheduler state — the pool is device)
        self._pfx: Optional[PrefixIndex] = None
        if pc.enabled:
            self._pfx = PrefixIndex(pc.n_slots, pc.block,
                                    insert_policy=pc.insert_policy,
                                    min_hits=pc.min_hits)
            self.telemetry.gauge("serving/prefix_pool_slots").set(pc.n_slots)

        # host-side slot state (device twins are passed per step as arrays)
        n = self.n_slots
        self._slots = [_Slot() for _ in range(n)]
        self._free: deque[int] = deque(range(n))
        self._active = np.zeros((n,), np.bool_)
        self._pos = np.zeros((n,), np.int32)
        self._last_tok = np.zeros((n,), np.int32)
        self._temp = np.zeros((n,), np.float32)
        self._top_k = np.zeros((n,), np.int32)
        self._top_p = np.ones((n,), np.float32)
        # generation by diffusion over blocks: each slot's open block AS PLANNED, i.e.
        # behind every step enqueued (``_pos`` is the block's first position). What the
        # static schedule lets the host know without a fetch (``_enqueue_block_step``)
        B = self.block_len
        self._bleft = np.zeros((n,), np.int32)  # positions still masked
        self._bpass = np.zeros((n,), np.int32)  # denoising passes the block has had
        self._bopen = np.zeros((n,), np.bool_)  # the next step opens the block (host's tokens)
        self._bnew_toks = np.zeros((n, B), np.int32)
        self._bnew_mask = np.zeros((n, B), np.bool_)
        self._bgen_from = np.zeros((n,), np.int32)  # the block's first position to emit
        self._bbudget = np.zeros((n,), np.int64)  # tokens of the request not planned yet
        self._bdone = np.zeros((n,), np.bool_)  # its last step is enqueued

        self._queue: deque[Request] = deque()
        self._prefilling: dict[int, _Prefill] = {}  # slot -> admission state
        # disaggregated-serving state (empty/ignored for role "both"):
        # prefill role parks finished admissions here until the Router
        # streams their KV out; decode role stages in-progress imports here
        # until the Router commits them
        self._handoffs: dict[int, _Handoff] = {}  # uid -> parked handoff
        self._imports: dict[int, dict] = {}  # uid -> staged KV import
        self._rr = 0  # round-robin cursor over prefilling slots
        self._results: dict[int, RequestResult] = {}
        # quarantine bookkeeping: per-uid replay count, per-slot consecutive
        # NaN-fault count, and slots pulled from rotation (suspect hardware)
        self._requeues: dict[int, int] = {}
        # uid -> tenant id for live requests (per-tenant terminal metrics;
        # popped on terminal). Anonymous requests (tenant "") stay out, so
        # single-tenant deployments grow zero extra registry entries.
        self._uid_tenant: dict[int, str] = {}
        self._slot_faults = np.zeros((n,), np.int32)
        self._quarantined_slots: set[int] = set()
        # the decode step the device has and the host has not fetched (``_step``:
        # the scheduler runs ONE step ahead), and how many requests have left each
        # slot (``_release_slot``): what tells a row of that step whose request is
        # still there from one that ran for nothing
        self._flight: Optional[_Flight] = None
        self._slot_epoch = np.zeros((n,), np.int64)
        # uids exempt from queue-bound accounting: a Router's failover /
        # drain requeues were already accepted once — like quarantine
        # replays, they are neither shed nor allowed to displace arrivals
        self._exempt_uids: set[int] = set()
        # uids that reached a terminal state since the last step() returned —
        # step() drains this so callers driving the scheduler directly see
        # EVERY completion (ok, expired, shed, deadline, cancelled, failed),
        # not just EOS/length finishes
        self._terminal_uids: list[int] = []
        # deadline sweeping costs an O(queue + slots) host pass per decode
        # step; skip it entirely until some live request can actually expire
        self._deadlines_armed = self.default_deadline_s > 0
        self._epoch = time.perf_counter()
        self._t_built = self._epoch
        self._steps = 0  # scheduler iterations: the serve/step span's index
        # per-request lifecycle tracing (telemetry/request_trace.py): a
        # bounded ring of host-side timeline events on the engine's clock,
        # stamped with this replica's id for fleet-wide merges
        self.tracer: Optional[RequestTracer] = (
            RequestTracer(rt.capacity, replica_id=self.replica_id,
                          clock=lambda: time.perf_counter() - self._epoch)
            if rt.enabled else None)
        # flight-recorder rings (telemetry/timeseries.py): sampled from the
        # step loop on the engine clock, flushed over the step-reply
        # piggyback. SLO classification and incident capture both read the
        # rings, so enabling either implies them.
        self._rings: Optional[TimeSeriesStore] = (
            TimeSeriesStore(raw_interval_s=ts.interval_s,
                            tiers=tuple(ts.tiers), capacity=ts.capacity,
                            flush_capacity=ts.flush_capacity)
            if (ts.enabled or slo.enabled or inc.enabled) else None)
        self._next_sample_t = 0.0
        # incident recorder (telemetry/incident.py): per-replica bundles
        # under <dir>/replica<rid>/ so a fleet's recorders never collide
        self._incidents: Optional[IncidentRecorder] = None
        if inc.enabled:
            self._incidents = IncidentRecorder(
                os.path.join(inc.dir, f"replica{self.replica_id}"),
                source=f"replica{self.replica_id}",
                max_bundles=inc.max_bundles,
                window_before_s=inc.window_before_s,
                window_after_s=inc.window_after_s,
                registry=self.telemetry.registry)
            self.telemetry.watchdog.on_refusal = self._on_watchdog_refusal
        feat = []
        if pc.enabled:
            feat.append(f"prefix_cache[{pc.n_slots}x{self.worker.pmax}, "
                        f"block {pc.block}, {pc.insert_policy}]")
        if cp.enabled:
            feat.append(f"chunked_prefill[{cp.chunk_size}]")
        if sp.enabled:
            feat.append(f"speculation[depth {sp.depth}, {sp.draft_source}]")
        if self.block_len > 1:
            feat.append(f"block_generation[{self.block_len} positions, {self.block_passes} "
                        f"passes + commit, {bg.strategy}]")
        log_dist(
            f"serving engine: {n} slots x {self.Smax} tokens, cache "
            f"{self.worker.hbm_pools()['slot_kv_cache'] / 1e6:.1f} MB at "
            f"{tfm.cache_bytes_per_token(self.cfg)} B a token a layer "
            f"({tfm.cache_step_form(self.cfg)})"
            + (f" in {self.worker.pass_attrs['cache_layers']} layers, one a (pass, layer) of "
               f"{self.cfg.layer_passes} passes" if self.worker.pass_attrs else "")
            + (f" in {tfm.cache_layers(self.cfg)['tokens']} layers, per-sequence state "
               f"{self.worker.hbm_pools()['slot_state'] / 1e6:.1f} MB at "
               f"{self.worker.state_bytes_per_slot} B a slot over {self.worker.state_layers} "
               "layers" if recurrent else "")
            + (f", rings {self.worker.hbm_pools()['slot_kv_ring'] / 1e6:.1f} MB at "
               f"{tfm.cache_ring_bytes(self.cfg)} B a slot over {self.worker.window_layers} "
               f"window layers of {self.cfg.local_attn_window}" if windowed else "")
            + f", spec={self.worker.spec}" + (", " + ", ".join(feat) if feat else ""),
            ranks=[0],
        )

    def _bucket_len(self, S: int) -> int:
        return min(_next_pow2(max(S, self.min_bucket)), self.Smax)

    def _segments(self, start: int, S: int) -> list[tuple[int, int, int]]:
        """Split [start, S) into (start, width, live_len) chunk segments:
        full ``chunk_size`` chunks, then ONE power-of-two bucketed segment
        for the remainder (padded, exactly like the one-shot prefill — a
        short post-hit suffix reaches its first token in a single step
        instead of dripping through log2(r) sub-chunks). Only when the
        padded bucket would spill past the cache end does the remainder fall
        back to its unpadded binary decomposition. Widths are powers of two
        <= chunk_size, so the compiled-program set stays bounded by
        log2(chunk_size) — never one program per prompt length."""
        C = self.chunk_cfg.chunk_size
        segs = []
        p = start
        while S - p >= C:
            segs.append((p, C, C))
            p += C
        r = S - p
        if r > 0:
            b = min(_next_pow2(max(r, min(self.min_bucket, C))), C)
            if p + b <= self.Smax:
                segs.append((p, b, r))
            else:
                while r > 0:
                    while b > r:
                        b //= 2
                    segs.append((p, b, b))
                    p += b
                    r -= b
        return segs

    # -- scheduler ------------------------------------------------------

    def set_tenant_policy(self, tenants: dict) -> None:
        """Install (or replace) the per-tenant scheduling policy: a mapping
        of tenant id -> ``TenantConfig`` (or an equivalent dict block).
        Hot-swappable between steps — host-side state only, so a policy
        change never invalidates a compiled program. An empty mapping
        restores the legacy single-pool FIFO semantics."""
        pol: dict[str, TenantConfig] = {}
        for tid, block in dict(tenants or {}).items():
            pol[str(tid)] = (block if isinstance(block, TenantConfig)
                             else TenantConfig(**dict(block)))
        self._tenants = pol

    def _tenant_weight(self, tenant: str) -> float:
        tc = self._tenants.get(tenant)
        return tc.weight if tc is not None else 1.0

    def submit(self, request: Request) -> int:
        """Enqueue a request (admitted by the next step()/serve() iteration
        whose clock has passed its arrival_time)."""
        S = int(np.asarray(request.prompt).shape[-1])
        if S + request.max_new_tokens > self.budget:
            raise ValueError(
                f"request {request.uid}: prompt ({S}) + max_new_tokens "
                f"({request.max_new_tokens}) exceeds the slot budget {self.budget}")
        if request.max_new_tokens < 1:
            raise ValueError(
                f"request {request.uid}: max_new_tokens must be >= 1 "
                f"(got {request.max_new_tokens})")
        # a duplicate uid would overwrite its twin's result and leave
        # serve()'s completion count short — spinning forever
        live = ({r.uid for r in self._queue} | set(self._results)
                | {s.uid for s in self._slots if s.uid >= 0}
                | {p.req.uid for p in self._prefilling.values()}
                | set(self._handoffs) | set(self._imports))
        if request.uid in live:
            raise ValueError(f"request uid {request.uid} is already in flight "
                             "or finished; uids must be unique per engine")
        if self.max_queue_len:
            # load shedding: the bound covers requests that have ARRIVED but
            # not been admitted (a future-dated request is scheduled, not
            # queued — it is shed at step() time if the queue is still full
            # when it arrives). Typed rejection instead of unbounded growth.
            now = time.perf_counter() - self._epoch
            if (request.arrival_time <= now
                    and request.uid not in self._exempt_uids):
                # same population as _shed_overflow: quarantine replays and
                # router requeues sit outside the bound accounting, so a
                # transient fault never shrinks admission capacity
                arrived = self.arrived_queue_len(now)
                if arrived >= self.max_queue_len:
                    self.telemetry.counter("resilience/load_shed").inc()
                    raise RequestRejected(
                        request.uid, "queue_full",
                        f"{arrived} arrived requests already queued "
                        f"(max_queue_len={self.max_queue_len})")
        tc = self._tenants.get(request.tenant)
        if tc is not None and tc.max_queued > 0:
            # per-tenant queue-depth quota: enforced even under global
            # headroom, so one tenant's burst is contained by its OWN cap
            # (typed 429 upstream) instead of degrading its neighbors.
            # Same exemption rule as the global bound: requeues/replays
            # were already accepted once and never re-count.
            now = time.perf_counter() - self._epoch
            if (request.arrival_time <= now
                    and request.uid not in self._exempt_uids):
                mine = sum(
                    1 for r in self._queue
                    if r.tenant == request.tenant and r.arrival_time <= now
                    and self._requeues.get(r.uid, 0) == 0
                    and r.uid not in self._exempt_uids)
                if mine >= tc.max_queued:
                    self.telemetry.counter(
                        f"tenant/{request.tenant}/rejected").inc()
                    raise RequestRejected(
                        request.uid, "tenant_quota",
                        f"tenant {request.tenant!r} has {mine} arrived "
                        f"requests queued (max_queued={tc.max_queued})")
        if request.deadline_s > 0:
            self._deadlines_armed = True
        if request.tenant:
            self._uid_tenant[request.uid] = request.tenant
        self._queue.append(request)
        if self.tracer is not None:
            # a future-dated request's timeline starts at its logical
            # arrival instant, matching every other arrival-relative timing
            self.tracer.record(request.uid, "arrived", t=request.arrival_time,
                               prompt_len=int(np.asarray(request.prompt).shape[-1]))
        return request.uid

    # -- router-facing surface (inference/router.py) --------------------

    def requeue(self, request: Request) -> int:
        """Re-admission entry for the Router's failover / drain migration:
        the request was already ACCEPTED once by this process, so it
        re-enters a queue OUTSIDE the queue-bound accounting — the same
        rule quarantine replays follow (docs/resilience.md). It is neither
        shed nor allowed to displace newly-accepted arrivals; the backlog
        may transiently overshoot by the number of in-flight failovers."""
        self._exempt_uids.add(int(request.uid))
        try:
            uid = self.submit(request)
        except BaseException:
            self._exempt_uids.discard(int(request.uid))
            raise
        if self.tracer is not None:
            self.tracer.record(uid, "requeued")
        return uid

    def withdraw(self, uid: int) -> Optional[Request]:
        """Silently remove a still-QUEUED request and hand it back (no
        result is synthesized — unlike ``cancel``, the request is not
        terminal, it is MOVING: the Router's drain path re-queues it on a
        sibling replica). None if the uid is not queued here."""
        for i, r in enumerate(self._queue):
            if r.uid == uid:
                del self._queue[i]
                self._exempt_uids.discard(uid)
                self._uid_tenant.pop(uid, None)
                return r
        return None

    # -- disaggregated prefill/decode surface (docs/serving.md) ----------
    #
    # Prefill role: _activate parks finished admissions in self._handoffs;
    # the Router discovers them (handoff_ready), streams their KV windows
    # out (kv_export_window) and frees the slot once the decode side has
    # committed (handoff_release). Decode role: the Router stages a slot
    # (kv_import_begin), streams windows in (kv_import_window), then flips
    # it to decoding (kv_import_commit) or unwinds (kv_import_abort).
    # Every mutation is replay-tolerant — a retried RPC must not corrupt
    # the handoff state machine.

    def _check_kv_window(self, start: int, width: int) -> None:
        if width < 1 or (width & (width - 1)) != 0 or width > 128:
            raise ValueError(
                f"kv window width must be a power of two <= 128, got {width}")
        if start < 0 or start % width != 0 or start + width > self.Smax:
            raise ValueError(
                f"kv window [{start}, {start + width}) must be width-aligned "
                f"inside the {self.Smax}-token slot cache")

    def handoff_ready(self) -> list[dict]:
        """Parked prefill-role handoffs awaiting KV transfer — the block a
        worker process piggybacks on its step reply so the Router's handoff
        pump discovers finished prefills with zero extra round trips."""
        return [{"uid": int(uid), "pos": int(h.pos), "first": int(h.first),
                 "prefix_hit_tokens": int(h.prefix_hit_tokens),
                 "t_admit": float(h.t_admit), "t_first": float(h.t_first)}
                for uid, h in self._handoffs.items()]

    def kv_export_window(self, uid: int, start: int, width: int):
        """One host KV window of a parked handoff's slot — a pure read
        (replay-safe: a retried export returns the same bytes)."""
        h = self._handoffs.get(int(uid))
        if h is None:
            raise ValueError(f"uid {uid} is not parked for handoff")
        self._check_kv_window(start, width)
        return self.worker.kv_export(width, h.slot, start)

    def handoff_release(self, uid: int) -> bool:
        """Free a parked handoff's slot after the decode side committed —
        the request is MOVING, not terminal, so no result is synthesized
        (the decode replica owns it from here). Replay-tolerant: releasing
        an unknown uid is False, not an error."""
        h = self._handoffs.pop(int(uid), None)
        if h is None:
            return False
        if h.entry is not None:
            self._pfx.release(h.entry)
        # the slot's KV is finite (the prefill sentinel was checked before
        # parking) — stale-but-finite KV is causally masked for the next
        # occupant, the same contract every normal release relies on
        self._free.append(h.slot)
        self._exempt_uids.discard(int(uid))
        self.telemetry.counter("serving/handoffs_released").inc()
        if self.tracer is not None:
            self.tracer.record(int(uid), "handoff_released", slot=h.slot)
        return True

    def kv_import_begin(self, request: Request, pos: int, first: int,
                        prefix_hit_tokens: int = 0, t_admit: float = 0.0,
                        t_first: float = 0.0) -> int:
        """Stage a decode-role slot for an incoming KV handoff; returns the
        slot. Raises a typed ``RequestRejected(reason="no_slot")`` when no
        slot is free (the Router leaves the handoff parked and retries —
        that backlog is the decode pool's scale-up signal). Replay-
        tolerant: a uid already staged returns its existing slot."""
        uid = int(request.uid)
        if uid in self._imports:
            return int(self._imports[uid]["slot"])
        if not self._free:
            raise RequestRejected(uid, "no_slot",
                                  "no free decode slot for KV import")
        if int(pos) + int(request.max_new_tokens) - 1 > self.budget:
            raise ValueError(
                f"kv import for uid {uid}: pos ({pos}) + remaining tokens "
                f"exceed the slot budget {self.budget}")
        slot = self._free.popleft()
        self._imports[uid] = {
            "slot": slot, "req": request, "pos": int(pos),
            "first": int(first), "prefix_hit_tokens": int(prefix_hit_tokens),
            "t_admit": float(t_admit), "t_first": float(t_first),
        }
        if self.tracer is not None:
            self.tracer.record(uid, "kv_import_begin", slot=slot,
                               pos=int(pos))
        return slot

    def kv_import_window(self, uid: int, start: int, width: int, k, v) -> None:
        """Splat one streamed KV window into the staged slot. Idempotent —
        a replayed window rewrites the same bytes."""
        imp = self._imports.get(int(uid))
        if imp is None:
            raise ValueError(f"uid {uid} has no staged KV import")
        self._check_kv_window(start, width)
        self.worker.kv_import(width, k, v, imp["slot"], start)

    def kv_import_commit(self, uid: int) -> bool:
        """Flip a fully-streamed import to DECODING — the decode-role twin
        of ``_activate``. Replay-tolerant: committing a uid that already
        committed (active or terminal here) returns True; an unknown uid
        returns False (the Router treats it as a lost handoff)."""
        uid = int(uid)
        imp = self._imports.pop(uid, None)
        if imp is None:
            return bool(uid in self._results
                        or any(self._active[s] and self._slots[s].uid == uid
                               for s in range(self.n_slots)))
        slot, req = imp["slot"], imp["req"]
        st = self._slots[slot]
        st.uid = uid
        st.remaining = req.max_new_tokens - 1
        st.eos = req.eos_token if req.eos_token is not None else -1
        st.tokens = [imp["first"]]  # an int since kv_import_begin
        st.request = req
        st.result = RequestResult(
            uid=uid, tokens=np.zeros((0,), np.int32),
            prompt_len=imp["pos"], arrival_time=req.arrival_time,
            admitted_time=imp["t_admit"], first_token_time=imp["t_first"],
            slot=slot, prefix_hit_tokens=imp["prefix_hit_tokens"],
        )
        self._active[slot] = True
        self._pos[slot] = imp["pos"]
        self._last_tok[slot] = imp["first"]
        self._spec_len[slot] = 2
        self._spec_zero_streak[slot] = 0
        self._spec_probe_wait[slot] = 0
        self._temp[slot] = req.temperature
        self._top_k[slot] = req.top_k
        self._top_p[slot] = req.top_p
        if req.deadline_s > 0 or self.default_deadline_s > 0:
            self._deadlines_armed = True
        self.telemetry.counter("serving/kv_imports_committed").inc()
        if self.tracer is not None:
            self.tracer.record(uid, "kv_import_commit", slot=slot)
        if imp["first"] == st.eos or st.remaining <= 0:
            self._finish(slot)
        return True

    def kv_import_abort(self, uid: int) -> bool:
        """Unwind a staged import (decode replica lost mid-stream, prefill
        side failed over): free the slot, forget the staging. The partial
        KV is finite garbage the next occupant's prefill masks/overwrites —
        same contract as every slot release. Replay-tolerant."""
        imp = self._imports.pop(int(uid), None)
        if imp is None:
            return False
        self._free.append(imp["slot"])
        self.telemetry.counter("serving/kv_imports_aborted").inc()
        if self.tracer is not None:
            self.tracer.record(int(uid), "kv_import_abort",
                               slot=imp["slot"])
        return True

    def result(self, uid: int) -> Optional[RequestResult]:
        """The terminal result for ``uid``, or None while in flight."""
        return self._results.get(uid)

    def partial_tokens(self, uid: int) -> Optional[np.ndarray]:
        """Tokens generated SO FAR for ``uid`` — the incremental result
        surface an SSE gateway streams from (launcher/http_gateway.py):
        the decoding slot's token list, an empty array for a request still
        queued or mid-prefill, or the terminal result's tokens. None for a
        uid this engine does not hold. Pure host reads — no device work,
        no new programs; tokens already crossed to the host in step()."""
        res = self._results.get(uid)
        if res is not None:
            return np.asarray(res.tokens, np.int32)
        for slot in range(self.n_slots):
            st = self._slots[slot]
            if self._active[slot] and st.uid == uid:
                return np.asarray(st.tokens, np.int32)
        h = self._handoffs.get(uid)
        if h is not None:
            return np.asarray([h.first], np.int32)
        if (any(r.uid == uid for r in self._queue)
                or any(pf.req.uid == uid
                       for pf in self._prefilling.values())
                or uid in self._imports):
            return np.zeros((0,), np.int32)
        return None

    def live_progress(self) -> dict[int, Sequence[int]]:
        """``{uid: tokens-so-far}`` for every ACTIVE (decoding) slot — the
        per-step progress block a worker process piggybacks on its step
        reply so a remote gateway's streams advance with ZERO extra round
        trips (rpc.ReplicaClient caches it like load/idle).

        Contract: each value is a READ-ONLY window (``_TokensSoFar``) on the
        slot's list of Python ``int``s, as long as the list was at the call —
        a later ``step()`` neither grows nor changes it; ``list(v)`` gives a
        list of one's own (``json`` encodes nothing else). A token is here one
        ``step()`` after the one that enqueued its decode step (``_step``). Cost: one small
        object a live slot; no per-token work, not even a copy (on the chip's
        host the copies read 0.33 ms a step at 128 slots x ~300 tokens)."""
        return {st.uid: _TokensSoFar(st.tokens)
                for slot, st in enumerate(self._slots)
                if self._active[slot] and st.uid >= 0}

    def live_requests(self) -> list[Request]:
        """Accepted, non-terminal requests in scheduler order (queued, then
        mid-prefill, then decoding) — the population a Router fails over
        when this replica is declared dead or hung."""
        out = list(self._queue)
        out.extend(pf.req for _, pf in sorted(self._prefilling.items()))
        # parked handoffs are accepted and non-terminal: a dead prefill
        # replica's Router failover must replay them from scratch
        out.extend(h.req for _, h in sorted(self._handoffs.items()))
        out.extend(st.request for slot, st in enumerate(self._slots)
                   if self._active[slot] and st.request is not None)
        return out

    def arrived_queue_len(self, now: float | None = None) -> int:
        """ARRIVED not-yet-admitted requests that count toward the queue
        bound — quarantine replays and router failover/drain requeues sit
        outside the accounting. This is the population ``submit`` and
        ``_shed_overflow`` police, and what a Router sums across replicas
        for its global bound."""
        if now is None:
            now = time.perf_counter() - self._epoch
        return sum(1 for r in self._queue
                   if r.arrival_time <= now
                   and self._requeues.get(r.uid, 0) == 0
                   and r.uid not in self._exempt_uids)

    def prefix_match_len(self, prompt) -> int:
        """Longest cached-prefix match (tokens) for ``prompt`` with NO side
        effects — no hit/miss counters, no LRU bump (``PrefixIndex.peek``).
        The Router's affinity dispatch polls every replica per submit; a
        stats-bumping probe would corrupt hit-rate telemetry and LRU order
        on the replicas that lose the dispatch. 0 when the feature is off."""
        if self._pfx is None:
            return 0
        p = np.asarray(prompt).reshape(-1)
        if p.shape[0] < 2:
            return 0
        return self._pfx.peek(p, min(p.shape[0] - 1, self.worker.pmax))

    @property
    def load(self) -> int:
        """Scheduler load for least-loaded dispatch: queued + mid-prefill +
        decoding requests, plus (disaggregated roles) parked handoffs and
        staged imports — both occupy slots, so they gate dispatch too."""
        return (len(self._queue) + len(self._prefilling) + self.n_active
                + len(self._handoffs) + len(self._imports))

    @property
    def idle(self) -> bool:
        return (not self._queue and not self._prefilling
                and not self._active.any() and self._flight is None
                and not self._handoffs and not self._imports)

    @property
    def queue_len(self) -> int:
        """Requests queued (arrived or future-dated), not yet admitted."""
        return len(self._queue)

    @property
    def occupancy(self) -> float:
        """Fraction of slots held by decoding requests plus staged KV
        imports — the decode pool's saturation signal for per-pool
        autoscaling (a staged import IS a slot: it gates admission)."""
        if not self.n_slots:
            return 0.0
        return (self.n_active + len(self._imports)) / self.n_slots

    def pending_arrival_times(self) -> list[float]:
        """Arrival times of every queued request — the Router's idle-wait
        reads these instead of reaching into the queue representation."""
        return [r.arrival_time for r in self._queue]

    def set_epoch(self, epoch: float) -> None:
        """Align this engine's clock with a Router's (one epoch across the
        fleet keeps queue-wait/TTFT timings and ``step(now=...)`` coherent).
        Call only while idle — in-flight requests' timings are epoch-relative."""
        self._epoch = float(epoch)

    def take_trace_flush(self, limit: int = 256) -> list[dict]:
        """Incremental drain of request-trace events for a Router's mirror:
        events recorded since the last call (bounded, non-destructive — the
        engine's own ring keeps them too). A Router calls this on every
        step so a replica PROCESS that dies between steps has already
        shipped its timeline; the merged ``request_timeline()`` then still
        shows the killed worker's admitted/first_token edges next to the
        router's failover edge. Empty when tracing is off."""
        if self.tracer is None:
            return []
        events, self._trace_cursor = self.tracer.events_since(
            getattr(self, "_trace_cursor", 0), limit)
        return events

    def take_ring_flush(self, limit: int = 256) -> list[dict]:
        """Incremental drain of closed flight-recorder ring cells for a
        Router's per-replica mirror — the ``take_trace_flush`` contract
        (seq-cursor, bounded, non-destructive) over
        ``TimeSeriesStore.cells_since``. Empty when rings are off."""
        if self._rings is None:
            return []
        cells, self._ring_cursor = self._rings.cells_since(
            getattr(self, "_ring_cursor", 0), limit)
        return cells

    def _on_watchdog_refusal(self, name: str, signature: str) -> None:
        """First refusal of a compile-stable path -> incident trigger (the
        watchdog's ``on_refusal`` hook; raise-mode refusals are operational
        events worth an autopsy bundle, not just a counter)."""
        if self._incidents is not None:
            self._incidents.trigger(
                "watchdog_refusal", time.perf_counter() - self._epoch,
                program=name, signature=signature)

    def _maybe_sample_rings(self, now: float) -> None:
        """One flight-recorder sample per configured interval: scheduler
        gauges as-is, registry counters as deltas, histogram percentile
        estimates as ring-only series. Off-interval steps pay one float
        compare; the sampling walk itself is accumulated into the
        ``serving/ring_sample_sec`` counter so the overhead claim in
        docs/observability.md stays measured, not asserted."""
        if self._rings is None or not math.isfinite(now):
            return
        if now < self._next_sample_t:
            return
        t0 = time.perf_counter()
        iv = self._rings.raw_interval_s
        self._next_sample_t = (math.floor(now / iv) + 1.0) * iv
        reg = self.telemetry.registry
        gauges = {
            "serving/queue_depth": float(len(self._queue)),
            "serving/slot_occupancy": (self.n_active / self.n_slots
                                       if self.n_slots else 0.0),
            "serving/prefilling": float(len(self._prefilling)),
        }
        if self._pfx is not None:
            g = reg.get("serving/prefix_pool_used")
            if g is not None:
                gauges["serving/prefix_pool_used"] = g.value
        for hist_name, ring_name, q in (
                ("serving/ttft_sec", "serving/ttft_p90_s", 0.9),
                ("serving/tpot_sec", "serving/tpot_p90_s", 0.9),
                ("serving/decode_step_sec", "serving/decode_step_p50_s", 0.5)):
            h = reg.get(hist_name)
            if h is not None and h.count:
                gauges[ring_name] = h.quantile(q)
        if self._spec_drafted:
            gauges["serving/spec_acceptance"] = (
                self._spec_accepted / self._spec_drafted)
        counters = {}
        for name in ("slo/requests", "slo/failures", "slo/ttft_violations",
                     "slo/tpot_violations", "serving/tokens_out",
                     "resilience/quarantines"):
            c = reg.get(name)
            if c is not None:
                counters[name] = c.value
        self._rings.sample(now, gauges=gauges, counters=counters)
        reg.counter("serving/ring_sample_sec").inc(
            time.perf_counter() - t0)

    def _incident_context(self, st: dict, t0: float, t1: float) -> dict:
        """Engine-side incident capture: the ring window around the trigger,
        the trace events inside it, and a plain registry snapshot. Host
        dict/deque reads only — no device work, no lazy ledger analysis
        (this runs on the step loop mid-incident)."""
        ctx: dict = {"metrics": self.telemetry.registry.snapshot()}
        if self._rings is not None:
            ctx["rings"] = self._rings.window_snapshot(t0, t1)
        if self.tracer is not None:
            ctx["trace_events"] = [
                ev for ev in self.tracer.events()
                if t0 <= float(ev.get("t", 0.0)) <= t1]
        ctx["scheduler"] = {
            "queue_depth": len(self._queue),
            "active": self.n_active,
            "prefilling": self.n_prefilling,
            "quarantined_slots": sorted(self._quarantined_slots),
        }
        return ctx

    @property
    def last_step_compiled(self) -> bool:
        """True if the most recent ``step()`` paid at least one program
        compilation — the Router's liveness heartbeat exempts such steps
        from the hung verdict (compiling is not hanging)."""
        return self.worker.step_compiled

    @property
    def n_active(self) -> int:
        return int(self._active.sum())

    @property
    def n_prefilling(self) -> int:
        return len(self._prefilling)

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def quarantined_slots(self) -> set[int]:
        return set(self._quarantined_slots)

    def _pop_earliest_arrived(self, now: float) -> Optional[Request]:
        """Earliest-arrival request whose arrival_time has passed, removed
        from the queue — NOT the queue head: a future-dated head must never
        block admission of later-submitted requests that have already
        arrived (head-of-line fix)."""
        best_i = -1
        best_t = None
        for i, r in enumerate(self._queue):
            if r.arrival_time <= now and (best_t is None or r.arrival_time < best_t):
                best_i, best_t = i, r.arrival_time
        if best_i < 0:
            return None
        req = self._queue[best_i]
        del self._queue[best_i]
        return req

    def _pop_tenant_fair(self, now: float) -> Optional[Request]:
        """Deficit-weighted round robin over per-tenant arrival queues
        (docs/serving.md "Multi-tenant isolation"). Within a tenant the
        order stays earliest-arrival FIFO; across tenants each admission
        visit pays one unit of deficit, topped up by the tenant's
        configured weight, so long-run admission shares converge to the
        weight ratios regardless of offered load. Pure host code — the
        tenant axis never becomes a traced operand. With at most one
        tenant backlogged this reduces EXACTLY to the legacy
        earliest-arrival pop (including its head-of-line fix)."""
        # earliest arrived candidate per tenant (FIFO within a tenant)
        best: dict[str, int] = {}
        for i, r in enumerate(self._queue):
            if r.arrival_time > now:
                continue
            j = best.get(r.tenant)
            if j is None or r.arrival_time < self._queue[j].arrival_time:
                best[r.tenant] = i
        if not best:
            return None
        if len(best) == 1:
            (i,) = best.values()
            req = self._queue[i]
            del self._queue[i]
            return req
        # idle tenants bank no credit: a deficit persists only while its
        # tenant stays backlogged, so a returning burster starts from zero
        for t in [t for t in self._dwrr_deficit if t not in best]:
            del self._dwrr_deficit[t]
        ring = sorted(best)
        n = len(ring)
        idx = ring.index(self._dwrr_at) if self._dwrr_at in ring else 0
        # config validates weight >= 0.01, so every tenant crosses one
        # unit of deficit within 100 ring passes; the spin bound below is
        # therefore unreachable and exists purely as a defensive fallback
        for _ in range(101 * n):
            t = ring[idx]
            d = self._dwrr_deficit.get(t, 0.0)
            if d < 1.0:
                d += self._tenant_weight(t)  # one top-up per visit
            if d >= 1.0:
                d -= 1.0
                self._dwrr_deficit[t] = d
                # keep serving this tenant while its quantum lasts; once
                # the deficit is spent the cursor moves on BEFORE the next
                # top-up, so a heavyweight tenant cannot re-arm in place
                # and starve the ring
                self._dwrr_at = t if d >= 1.0 else ring[(idx + 1) % n]
                i = best[t]
                req = self._queue[i]
                del self._queue[i]
                return req
            self._dwrr_deficit[t] = d
            idx = (idx + 1) % n
            self._dwrr_at = ring[idx]
        return self._pop_earliest_arrived(now)

    def _admit(self, now: float):
        """Move arrived requests from the queue into free slots. Without
        prefix/chunk features this runs the legacy one-shot bucketed prefill;
        otherwise it fetches the cached prefix and leaves the request in the
        ``prefilling`` state for step() to advance chunk by chunk."""
        tm = self.telemetry
        with tm.span("admit") as sp:
            admitted = self._admit_arrived(now)
            sp.annotate(admitted=admitted)

    def _admit_arrived(self, now: float) -> int:
        tm = self.telemetry
        admitted = 0
        while self._free and self._queue:
            req = self._pop_tenant_fair(now)
            if req is None:
                break
            slot = self._free.popleft()
            admitted += 1
            prompt = np.asarray(req.prompt, np.int32).reshape(-1)
            S = prompt.shape[0]
            t_adm = time.perf_counter() - self._epoch
            tm.counter("serving/admissions").inc()
            tm.histogram("serving/queue_wait_sec").observe(
                max(t_adm - req.arrival_time, 0.0))
            if self.tracer is not None:
                self.tracer.record(req.uid, "admitted", t=t_adm, slot=slot)

            entry = None
            if self._pfx is not None:
                # at most S-1 tokens are reusable: the first sampled token
                # needs the LAST prompt position's logits, so at least one
                # suffix token must run through a prefill program
                entry = self._pfx.lookup(prompt, min(S - 1, self.worker.pmax))
                if entry is not None:
                    self._pfx.acquire(entry)
                    tm.counter("serving/prefix_hits").inc()
                    tm.counter("serving/prefix_tokens_reused").inc(entry.length)
                    self.worker.prefix_fetch(entry.pool_slot, slot)
                    if self.tracer is not None:
                        self.tracer.record(req.uid, "prefix_hit",
                                           tokens=entry.length)
                else:
                    tm.counter("serving/prefix_misses").inc()
            P = entry.length if entry is not None else 0

            if P == 0 and not self.chunk_cfg.enabled:
                # legacy blocking path: whole prompt through one bucketed
                # prefill program (compile-compatible with pre-feature
                # engines — same program, same XLA cache entries)
                tm.histogram("serving/chunks_per_admit").observe(1)
                self._prefill_one_shot(req, slot, prompt, t_adm, entry)
                continue

            segments = self._segments(P, S)
            tm.histogram("serving/chunks_per_admit").observe(len(segments))
            self._prefilling[slot] = _Prefill(
                req=req, slot=slot, prompt=prompt, segments=segments,
                entry=entry, t_admit=t_adm)
            if not self.chunk_cfg.enabled:
                # prefix hit with chunking off: the suffix still runs through
                # the window path (it must attend to the fetched prefix), but
                # all segments run back-to-back — legacy blocking semantics
                while slot in self._prefilling:
                    self._advance_prefill(slot)
        return admitted

    def _prefill_one_shot(self, req: Request, slot: int, prompt: np.ndarray,
                          t_adm: float, entry):
        # a block model prefills the prompt's WHOLE blocks; its last S mod B tokens open
        # the first generated block (``_open_first_block``), and the token the program
        # samples at the last prefilled row is nobody's
        S = prompt.shape[0] - prompt.shape[0] % self.block_len
        first, bad = 0, False
        if S:  # (a prompt shorter than a block has nothing to prefill)
            bucket = self._bucket_len(S)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :S] = prompt[:S]
            first, bad = self.worker.prefill(
                bucket, padded, slot, S, req.temperature, req.top_k, req.top_p,
                uid=req.uid)
        t_first = time.perf_counter() - self._epoch
        self._activate(slot, req, prompt, first, t_adm, t_first, entry, bad=bad)

    def _advance_prefill(self, slot: int):
        """Run ONE chunk of the slot's admission prefill; on the final chunk
        the first token is sampled and the slot flips to decoding."""
        pf = self._prefilling[slot]
        start, width, live = pf.segments[pf.idx]
        toks = np.zeros((1, width), np.int32)
        toks[0, :live] = pf.prompt[start:start + live]
        if self.tracer is not None:
            self.tracer.record(pf.req.uid, "chunk", k=pf.idx, width=width,
                               slot=slot)
        pf.idx += 1
        out = self.worker.chunk(
            width, toks, slot, start, live, pf.req.temperature,
            pf.req.top_k, pf.req.top_p, fetch=pf.idx >= len(pf.segments),
            uid=pf.req.uid)
        if out is None:
            # intermediate chunk: the sampled token is garbage (mid-prompt
            # logits) and deliberately NOT fetched — the chunk stays an
            # async dispatch the next decode step overlaps with. A NaN here
            # propagates through attention to the final chunk, whose fetched
            # sentinel covers the whole prefill.
            return
        first, bad = out
        t_first = time.perf_counter() - self._epoch
        del self._prefilling[slot]
        self._activate(slot, pf.req, pf.prompt, first, pf.t_admit, t_first,
                       pf.entry, bad=bad)

    def _activate(self, slot: int, req: Request, prompt: np.ndarray,
                  first: int, t_adm: float, t_first: float, entry,
                  bad: bool = False):
        """Prompt KV fully resident in the slot + first token sampled:
        flip the slot to decoding and (policy permitting) cache the prompt's
        prefix for future admissions. A ``bad`` (non-finite logits) prefill
        is quarantined instead: the slot is freed, the request requeued for
        a clean replay, and — poison protection — the faulted KV is NEVER
        offered to the prefix cache."""
        if self._inj is not None and self._inj.garbage_logits(req.uid, "prefill"):
            # make the fault REAL: the slot KV is NaN-poisoned, so an engine
            # that ignored the sentinel would store poisoned prefix KV and
            # decode garbage — the parity tests would catch it
            self.worker.fill_slot(slot, float("nan"))
            self.telemetry.counter("resilience/injected_faults").inc()
            bad = True
        if bad:
            self.telemetry.counter("resilience/nan_logit_faults").inc()
            if entry is not None:
                self._pfx.release(entry)  # the POOL entry is clean; our slot isn't
            self._quarantine(slot, req, "prefill")
            self._release_slot(slot)
            return
        if self.block_len > 1:
            self._open_first_block(slot, req, prompt, t_adm)
            return
        S = prompt.shape[0]
        eos = req.eos_token if req.eos_token is not None else -1
        if self.role == "prefill" and first != eos and req.max_new_tokens > 1:
            # prefill role: the decode belongs to the decode pool — park
            # the slot with its KV resident and let the Router stream it
            # out (kv_export_window) and release it (handoff_release).
            # Requests that FINISH at the first token (eos / max_new 1)
            # fall through and complete locally: shipping their KV would
            # buy nothing. The prefix insert still happens here — the
            # prefill pool's cache is what makes failover replays cheap.
            if self._pfx is not None:
                self._insert_prefix(slot, prompt)
            self._handoffs[req.uid] = _Handoff(
                req=req, slot=slot, first=first, pos=S,
                prefix_hit_tokens=entry.length if entry is not None else 0,
                t_admit=t_adm, t_first=t_first, entry=entry)
            self.telemetry.counter("serving/handoffs_parked").inc()
            if self.tracer is not None:
                self.tracer.record(req.uid, "handoff_ready", t=t_first,
                                   slot=slot)
            return
        st = self._slots[slot]
        st.uid = req.uid
        st.remaining = req.max_new_tokens - 1
        st.eos = req.eos_token if req.eos_token is not None else -1
        st.tokens = [int(first)]
        st.prefix_entry = entry
        st.request = req
        st.result = RequestResult(
            uid=req.uid, tokens=np.zeros((0,), np.int32), prompt_len=S,
            arrival_time=req.arrival_time, admitted_time=t_adm,
            first_token_time=t_first, slot=slot,
            prefix_hit_tokens=entry.length if entry is not None else 0,
        )
        self._active[slot] = True
        self._pos[slot] = S
        self._last_tok[slot] = first
        self._spec_len[slot] = 2  # adaptive draft cap re-ramps per request
        self._spec_zero_streak[slot] = 0
        self._spec_probe_wait[slot] = 0
        self._temp[slot] = req.temperature
        self._top_k[slot] = req.top_k
        self._top_p[slot] = req.top_p
        if self.tracer is not None:
            self.tracer.record(req.uid, "first_token", t=t_first, slot=slot)
        if self._pfx is not None:
            self._insert_prefix(slot, prompt)
        if first == st.eos or st.remaining <= 0:
            self._finish(slot)

    def _open_first_block(self, slot: int, req: Request, prompt: np.ndarray, t_adm: float):
        """``_activate`` for a model that generates by diffusion over blocks: the
        prompt's whole blocks are resident, and the block that holds position S opens
        with the prompt's last S mod B tokens in place and the rest masked. Nothing is
        emitted yet: the request receives its tokens a block at a time, each when its
        last masked position is revealed (``_emit_blocks``), and its first-token time is
        that of its first block."""
        B, S = self.block_len, prompt.shape[0]
        r = S % B
        st = self._slots[slot]
        st.uid, st.remaining, st.tokens, st.request = req.uid, req.max_new_tokens, [], req
        st.eos = req.eos_token if req.eos_token is not None else -1
        st.result = RequestResult(
            uid=req.uid, tokens=np.zeros((0,), np.int32), prompt_len=S,
            arrival_time=req.arrival_time, admitted_time=t_adm, slot=slot)
        self._active[slot] = True
        self._pos[slot] = S - r
        self._temp[slot], self._top_k[slot], self._top_p[slot] = (
            req.temperature, req.top_k, req.top_p)
        self._bnew_toks[slot] = 0
        self._bnew_toks[slot, :r] = prompt[S - r:]
        self._bnew_mask[slot] = np.arange(B) >= r
        self._bopen[slot], self._bdone[slot] = True, False
        self._bleft[slot], self._bpass[slot], self._bgen_from[slot] = B - r, 0, r
        self._bbudget[slot] = req.max_new_tokens

    def _insert_prefix(self, slot: int, prompt: np.ndarray):
        """Offer the freshly prefilled prompt to the prefix cache; a created
        entry copies the slot's leading window into the pool with the ONE
        compiled store program."""
        tm = self.telemetry
        skips_before = self._pfx.insert_skips
        res = self._pfx.insert(prompt, min(prompt.shape[0] - 1, self.worker.pmax))
        if res.evicted is not None:
            tm.counter("serving/prefix_evictions").inc()
        if res.created:
            self.worker.prefix_store(slot, res.entry.pool_slot)
            tm.counter("serving/prefix_inserts").inc()
        elif self._pfx.insert_skips > skips_before:
            # the index declined (pool full of in-use prefixes / below the
            # min_hits popularity bar) — distinct from "already cached"
            tm.counter("serving/prefix_insert_skips").inc()
        tm.gauge("serving/prefix_pool_used").set(self._pfx.used_slots)

    def _finish(self, slot: int, status: str = "ok"):
        st = self._slots[slot]
        st.result.tokens = np.asarray(st.tokens, np.int32)
        st.result.finish_time = time.perf_counter() - self._epoch
        st.result.status = status
        st.result.requeues = self._requeues.get(st.uid, 0)
        self._results[st.uid] = st.result
        self._terminal_uids.append(st.uid)
        self._exempt_uids.discard(st.uid)
        res = st.result
        tm = self.telemetry
        tm.counter("serving/evictions").inc()
        tm.counter("serving/tokens_out").inc(len(res.tokens))
        # every _finish caller is a NON-fault path (faults route through
        # _quarantine), and the slot decoded with finite logits throughout —
        # clear suspicion even for cancelled/deadline completions, else two
        # UNRELATED faults weeks apart would read as "consecutive" and
        # permanently quarantine a healthy slot
        self._slot_faults[slot] = 0
        if status == "ok":
            if res.requeues:
                # the quarantine path contained the fault and the replay
                # finished cleanly
                tm.counter("resilience/recovered").inc()
            # latency stats cover completed requests only — a deadline
            # eviction's truncated timings would pollute the percentiles
            tm.histogram("serving/ttft_sec").observe(res.ttft)
            tpot = res.time_per_output_token
            if len(res.tokens) > 1:
                tm.histogram("serving/tpot_sec").observe(tpot)
        else:
            tpot = 0.0
        if self.slo_cfg.enabled:
            classify_terminal(tm.registry, self.slo_cfg, status, res.ttft,
                              tpot if len(res.tokens) > 1 else None)
        self._tenant_terminal(res.uid, status, res.ttft,
                              tpot if len(res.tokens) > 1 else None)
        tm.emit({
            "type": "request", "uid": res.uid, "slot": slot,
            "prompt_len": res.prompt_len, "n_tokens": int(len(res.tokens)),
            "ttft_s": res.ttft, "tpot_s": tpot, "status": status,
            "arrival_s": res.arrival_time, "finish_s": res.finish_time,
            "prefix_hit_tokens": res.prefix_hit_tokens,
        })
        if self.tracer is not None:
            self.tracer.record(res.uid, "terminal", t=res.finish_time,
                               status=status, n_tokens=int(len(res.tokens)))
        self._release_slot(slot)

    def _tenant_terminal(self, uid: int, status: str, ttft: float,
                         tpot: Optional[float]) -> None:
        """Per-tenant terminal accounting (docs/serving.md "Multi-tenant
        isolation"): latency percentiles, shed counters, and SLO attainment
        keyed ``tenant/<id>/...``. No-op for anonymous requests, so the
        single-tenant registry footprint is unchanged."""
        t = self._uid_tenant.pop(uid, "")
        if not t:
            return
        tm = self.telemetry
        tm.counter(f"tenant/{t}/requests").inc()
        if status == "ok":
            tm.histogram(f"tenant/{t}/ttft_sec").observe(ttft)
            if tpot is not None:
                tm.histogram(f"tenant/{t}/tpot_sec").observe(tpot)
        elif status.startswith("shed"):
            tm.counter(f"tenant/{t}/sheds").inc()
        if self.slo_cfg.enabled:
            # same verdict logic as classify_terminal, scoped to the tenant
            ok = (status == "ok"
                  and not (ttft > self.slo_cfg.ttft_s > 0)
                  and not (tpot is not None and tpot > self.slo_cfg.tpot_s > 0))
            if ok:
                tm.counter(f"tenant/{t}/slo_ok").inc()
            else:
                tm.counter(f"tenant/{t}/slo_miss").inc()

    def _release_slot(self, slot: int):
        """Host-side slot teardown shared by every terminal path (finish,
        deadline eviction, cancellation, quarantine). Purely per-slot array
        resets — no device work, no new programs."""
        st = self._slots[slot]
        if st.prefix_entry is not None:
            self._pfx.release(st.prefix_entry)
        self._slots[slot] = _Slot()
        self._active[slot] = False
        self._slot_epoch[slot] += 1  # a row still in flight for it now runs for nothing
        # pos 0 is the freed slot's ATTENTION position only (cheapest for the
        # length-aware decode kernel); its decode WRITE goes to wpos=Smax and
        # is dropped by the scatter — never park the write in range (step())
        self._pos[slot] = 0
        self._last_tok[slot] = 0
        self._temp[slot] = 0.0
        self._top_k[slot] = 0
        self._top_p[slot] = 1.0
        self._bleft[slot] = self._bpass[slot] = self._bgen_from[slot] = self._bbudget[slot] = 0
        self._bopen[slot] = self._bdone[slot] = False
        if slot in self._quarantined_slots:
            self.telemetry.gauge("resilience/quarantined_slots").set(
                len(self._quarantined_slots))
        else:
            self._free.append(slot)

    def _synth_result(self, req: Request, status: str, slot: int = -1):
        """Terminal result for a request that never produced tokens
        (shed/expired/cancelled pre-activation/failed quarantine)."""
        now = time.perf_counter() - self._epoch
        res = RequestResult(
            uid=req.uid, tokens=np.zeros((0,), np.int32),
            prompt_len=int(np.asarray(req.prompt).shape[-1]),
            arrival_time=req.arrival_time, finish_time=now, slot=slot,
            status=status, requeues=self._requeues.get(req.uid, 0))
        self._results[req.uid] = res
        self._terminal_uids.append(req.uid)
        self._exempt_uids.discard(req.uid)
        if self.slo_cfg.enabled:
            classify_terminal(self.telemetry.registry, self.slo_cfg,
                              status, 0.0, None)
        self._tenant_terminal(req.uid, status, 0.0, None)
        self.telemetry.emit({
            "type": "request", "uid": req.uid, "slot": slot,
            "prompt_len": res.prompt_len, "n_tokens": 0, "status": status,
            "arrival_s": req.arrival_time, "finish_s": now,
        })
        if self.tracer is not None:
            self.tracer.record(req.uid, "terminal", t=now, status=status,
                               n_tokens=0)
        return res

    # -- degradation paths (docs/resilience.md) -------------------------

    def _deadline_of(self, req: Request) -> float:
        d = req.deadline_s if req.deadline_s > 0 else self.default_deadline_s
        return req.arrival_time + d if d > 0 else float("inf")

    def cancel(self, uid: int) -> bool:
        """Cancel a request wherever it is: queued (removed), mid-prefill
        (slot freed, fetched prefix released), or mid-decode (evicted with
        its partial output). Host-side state transitions only — in-flight
        device work for the slot completes and is discarded (its KV writes
        target a freed slot, which decode parks at the dropped position).
        Returns False if the uid is unknown/already finished."""
        tm = self.telemetry
        for i, r in enumerate(self._queue):
            if r.uid == uid:
                del self._queue[i]
                self._synth_result(r, "cancelled")
                tm.counter("resilience/cancelled").inc()
                return True
        for slot, pf in list(self._prefilling.items()):
            if pf.req.uid == uid:
                if pf.entry is not None:
                    self._pfx.release(pf.entry)
                del self._prefilling[slot]
                self._synth_result(pf.req, "cancelled", slot=slot)
                # a mid-prefill slot's KV is UNVERIFIED (intermediate-chunk
                # sentinels are never fetched) — scrub before reuse, else an
                # undetected NaN leaks into the next occupant through masked
                # attention (see SlotWorker.fill_slot)
                self.worker.fill_slot(slot, 0.0)
                self._release_slot(slot)
                tm.counter("resilience/cancelled").inc()
                return True
        for slot in range(self.n_slots):
            if self._active[slot] and self._slots[slot].uid == uid:
                self._finish(slot, status="cancelled")
                tm.counter("resilience/cancelled").inc()
                return True
        h = self._handoffs.pop(uid, None)
        if h is not None:
            if h.entry is not None:
                self._pfx.release(h.entry)
            self._free.append(h.slot)
            self._synth_result(h.req, "cancelled", slot=h.slot)
            tm.counter("resilience/cancelled").inc()
            return True
        imp = self._imports.pop(uid, None)
        if imp is not None:
            self._free.append(imp["slot"])
            self._synth_result(imp["req"], "cancelled", slot=imp["slot"])
            tm.counter("resilience/cancelled").inc()
            return True
        return False

    def _sweep_deadlines(self, now: float):
        """Shed queued requests past their deadline; cancel prefilling and
        evict decoding slots past theirs (partial output returned)."""
        tm = self.telemetry
        expired = [r for r in self._queue if now > self._deadline_of(r)]
        for r in expired:
            self._queue.remove(r)
            self._synth_result(r, "expired")
            tm.counter("resilience/deadline_shed").inc()
        for slot, pf in list(self._prefilling.items()):
            if now > self._deadline_of(pf.req):
                if pf.entry is not None:
                    self._pfx.release(pf.entry)
                del self._prefilling[slot]
                self._synth_result(pf.req, "deadline_exceeded", slot=slot)
                # mid-prefill KV is unverified — scrub before reuse (see
                # the same path in cancel())
                self.worker.fill_slot(slot, 0.0)
                self._release_slot(slot)
                tm.counter("resilience/deadline_evictions").inc()
        for slot in range(self.n_slots):
            st = self._slots[slot]
            if (self._active[slot] and st.request is not None
                    and now > self._deadline_of(st.request)):
                self._finish(slot, status="deadline_exceeded")
                tm.counter("resilience/deadline_evictions").inc()
        for uid, h in list(self._handoffs.items()):
            # a parked handoff past its deadline is evicted like a decoding
            # slot: the Router's pump never committed it anywhere else
            if now > self._deadline_of(h.req):
                del self._handoffs[uid]
                if h.entry is not None:
                    self._pfx.release(h.entry)
                self._free.append(h.slot)
                self._synth_result(h.req, "deadline_exceeded", slot=h.slot)
                tm.counter("resilience/deadline_evictions").inc()

    def _shed_overflow(self, now: float):
        """Bounded arrival queue: if more requests have ARRIVED than
        ``max_queue_len``, shed the newest arrivals (admission order is
        earliest-first, so the head of the backlog keeps its place).
        Quarantine-requeued requests sit OUTSIDE the bound accounting — they
        were already admitted once and granted a clean replay, so they are
        neither shed nor allowed to push an already-accepted arrival over
        the bound; the backlog may transiently overshoot by at most the
        number of in-flight faults (<= n_slots)."""
        if not self.max_queue_len:
            return
        # same population as arrived_queue_len: quarantine replays AND
        # router failover/drain requeues sit outside the accounting — an
        # exempt requeue must neither be shed nor displace an accepted
        # arrival over the bound
        arrived = [r for r in self._queue
                   if r.arrival_time <= now
                   and self._requeues.get(r.uid, 0) == 0
                   and r.uid not in self._exempt_uids]
        excess = len(arrived) - self.max_queue_len
        if excess <= 0:
            return
        arrived.sort(key=lambda r: r.arrival_time)
        for r in arrived[-excess:]:
            self._queue.remove(r)
            self._synth_result(r, "shed_queue_full")
            self.telemetry.counter("resilience/load_shed").inc()

    def _quarantine(self, slot: int, req: Request, phase: str):
        """Non-finite logits for ``req`` in ``slot``: contain (free the slot,
        never keep its KV), then requeue the request once for a clean replay
        — a second fault fails it. Repeated faults on one slot pull the slot
        out of rotation (suspect lane), never the last healthy one."""
        tm = self.telemetry
        tm.counter("resilience/quarantines").inc()
        if self.tracer is not None:
            self.tracer.record(req.uid, "quarantine", phase=phase, slot=slot)
        if self._incidents is not None:
            self._incidents.trigger(
                "nan_quarantine", time.perf_counter() - self._epoch,
                uid=req.uid, slot=slot, phase=phase)
        # scrub before the slot can be reused: NaN KV anywhere in the row
        # poisons later occupants through masked attention (see SlotWorker.fill_slot)
        self.worker.fill_slot(slot, 0.0)
        self._slot_faults[slot] += 1
        healthy = self.n_slots - len(self._quarantined_slots)
        if (self._slot_faults[slot] >= self.slot_quarantine_after
                and healthy > 1 and slot not in self._quarantined_slots):
            self._quarantined_slots.add(slot)
            tm.counter("resilience/slots_quarantined").inc()
            log_dist(
                f"serving: slot {slot} quarantined after "
                f"{int(self._slot_faults[slot])} consecutive NaN faults",
                ranks=[0])
        n = self._requeues.get(req.uid, 0)
        if n < self.quarantine_max_requeues:
            self._requeues[req.uid] = n + 1
            tm.counter("resilience/requeues").inc()
            log_dist(
                f"serving: request {req.uid} hit non-finite logits in slot "
                f"{slot} ({phase}); requeued for clean replay "
                f"({n + 1}/{self.quarantine_max_requeues})", ranks=[0])
            self._queue.append(req)
        else:
            tm.counter("resilience/failed_requests").inc()
            self._synth_result(req, "failed_nan", slot=slot)

    def _advance_flight(self, ahead: bool) -> None:
        """One step handed to the device and the one in flight (if any) fetched and
        emitted; ``ahead``: in that order, else the step in flight is only collected and
        nothing is enqueued (the caller does that once the host holds every token). A
        decode step, or for a model that generates by diffusion over blocks a block step
        (``_plan_decode_ahead`` / ``_plan_block_ahead`` say what each knows without the
        fetch; a block step runs ahead under the static schedule alone).

        Not known ahead: an EOS, a ``bad`` sentinel. A row enqueued for a request that one
        of those (or a cancel, an eviction) has ended since is dropped when its step is
        emitted; its K/V write is harmless, since whatever enters the slot later is
        enqueued behind it."""
        blocks = self.block_len > 1
        fl = self._flight
        kept = fl.active & (fl.epoch == self._slot_epoch)
        dropped = int(np.count_nonzero(fl.active & ~kept))
        fetched = None
        if ahead and (self._block_static or not blocks):
            fetched = (self._plan_block_ahead if blocks else self._plan_decode_ahead)(
                fl, kept, dropped)
        if fetched is None:
            fetched = self.worker.collect(rows_discarded=dropped)
            self._flight = None
        if dropped:
            self.telemetry.counter("serving/block_rows_discarded" if blocks
                                   else "serving/decode_rows_discarded").inc(
                dropped * self.block_len)
            for slot in map(int, np.flatnonzero(fl.active & ~kept & fetched[-1])):
                # a dropped row's sentinel: nobody is there to quarantine, but the
                # position it wrote may hold NaN K/V; scrub while the slot is empty
                # (an occupant's own sentinel catches it otherwise)
                if slot in self._free:
                    self.worker.fill_slot(slot, 0.0)
        if blocks:
            self._emit(self._emit_blocks, fl, kept, *fetched)
        else:
            self._emit(self._emit_decoded, kept, *fetched)

    def _plan_decode_ahead(self, fl: _Flight, kept, dropped: int):
        """The next decode step, enqueued behind the one in flight (None: no slot
        continues). What it needs is known WITHOUT the fetch: the rows of the step in
        flight whose request is still there (``kept``) move on by one position, those
        among them that this exhausts (``remaining``) end by length and are left out, and
        a slot activated since joins with the token the host has (a prefill's first, an
        import's). Only the tokens of the kept rows are the device's alone: they stay
        there (``SlotWorker._toks``) and ``from_host`` marks every other row whose token
        changes, an ended row's to 0, so the program's operands are to the bit what they
        would be had the host fetched first."""
        last = np.fromiter((st.remaining <= 1 for st in self._slots), np.bool_, self.n_slots)
        active = self._active & ~(kept & last)
        if not active.any():
            return None
        return self._enqueue_decode(
            active, self._pos + kept, ~(kept & active) & (fl.active | active), dropped)

    def _plan_block_ahead(self, fl: _Flight, kept, dropped: int):
        """The next block step, enqueued behind the one in flight (None: no slot
        continues). Under the static schedule a pass reveals ``count`` of a slot's masked
        positions, so the host knows how many are left behind every step it has enqueued
        (``_bleft``), which pass comes next (denoise while any is left, then the commit,
        then the next block opens B positions on), which pass finishes a block, and which
        finished block uses up the request's ``max_new_tokens`` (``_bdone``: that slot's
        last step; it takes no commit, nothing will read its K/V). The blocks themselves
        stay on the device (``SlotWorker._btoks`` / ``_bmask``)."""
        active = self._active & ~self._bdone
        return self._enqueue_block_step(active, dropped) if active.any() else None

    def _enqueue_decode(self, active, pos, from_host, rows_discarded: int = 0):
        """``worker.decode`` over the rows ``active`` at ``pos``: enqueues the step,
        makes it the one in flight, and returns what the call fetched of the step
        before (None: nothing was unfetched; ``rows_discarded``: how many of that
        step's rows the scheduler will drop, for the call's span). Every operand is
        an array of this call's own: the slots' state arrays change under the
        device's feet otherwise, the call being asynchronous. Rows outside ``active`` read as a
        freed slot's do (token 0, greedy), whatever their slot still holds."""
        self._note_device_step(int(np.count_nonzero(active)))
        # inactive slots WRITE at position Smax — the cache scatter's
        # mode="drop" discards their garbage KV entirely. Writing at 0 (the
        # pre-chunked-prefill scheme) corrupted PREFILLING slots — a slot
        # mid-admission already holds its prefix KV at position 0, and
        # decode steps run interleaved with its remaining chunks. Their
        # ATTENTION position is 0, so the length-aware decode kernel never
        # streams the full cache for them.
        def rows(state, idle):
            return np.where(active, state, idle).astype(state.dtype, copy=False)

        pos = rows(np.asarray(pos, np.int32), 0)
        fetched = self.worker.decode(
            rows(self._last_tok, 0), from_host, pos, rows(pos, self.Smax), active,
            rows(self._temp, 0), rows(self._top_k, 0), rows(self._top_p, 1),
            rows_discarded=rows_discarded)
        self._flight = _Flight(active=active, epoch=self._slot_epoch.copy())
        return fetched

    def _enqueue_block_step(self, active, rows_discarded: int = 0):
        """``worker.block_step`` over the slots ``active``, each at the pass its plan says:
        enqueues the step, makes it the one in flight, moves the plan on, and returns what
        the call fetched of the step before (None: nothing was unfetched). A slot with
        masked positions left takes a DENOISING pass that reveals ``B // T`` of them (all
        that are left on its T-th; under the dynamic strategy those over the threshold
        besides, so what is left is read from the fetch, ``_emit_blocks``); a slot with none
        takes the COMMIT, behind which its next block opens B positions on, all masked.
        Every operand is an array of this call's own; rows outside ``active`` read as a
        freed slot's do."""
        B, T = self.block_len, self.block_passes
        self._note_device_step(int(np.count_nonzero(active)))
        left = np.where(active, self._bleft, 0)
        denoise, commit = active & (left > 0), active & (left == 0)
        count = np.where(self._bpass >= T - 1, left, np.minimum(B // T, left)).astype(np.int32)
        count = np.where(denoise, count, 0)
        threshold = np.full((self.n_slots,), np.inf, np.float32)
        if not self._block_static:
            threshold[denoise] = self.block_cfg.threshold
        pos = np.where(active, self._pos, 0).astype(np.int32)
        state = lambda rows, idle: np.where(active, rows, idle).astype(rows.dtype)  # noqa: E731
        fetched = self.worker.block_step(
            self._bopen & active, self._bnew_toks.copy(), self._bnew_mask.copy(), pos,
            np.where(active, pos, self.Smax), active, count, threshold,
            state(self._temp, 0), state(self._top_k, 0), state(self._top_p, 1),
            masked_rows=int(left.sum()), commits=int(np.count_nonzero(commit)),
            rows_discarded=rows_discarded)
        self._flight = _Flight(active=active, epoch=self._slot_epoch.copy(), denoise=denoise,
                               masked=left, gen_from=self._bgen_from.copy())
        # the plan behind this step
        self._bopen[active] = False
        self._bpass[denoise] += 1
        if self._block_static:
            self._bleft[denoise] -= count[denoise]
            finishes = denoise & (self._bleft == 0)
            taken = np.minimum(B - self._bgen_from, self._bbudget)
            self._bbudget[finishes] -= taken[finishes]
            self._bdone |= finishes & (self._bbudget == 0)
        self._pos[commit] += B
        self._bopen[commit] = True
        self._bnew_toks[commit], self._bnew_mask[commit] = 0, True
        self._bleft[commit], self._bpass[commit], self._bgen_from[commit] = B, 0, 0
        return fetched

    def _emit_blocks(self, fl: _Flight, rows, toks, mask, bad) -> int:
        """A fetched block step onto the requests of ``rows`` [n_slots] bool (the rows it
        ran whose request is still in its slot): a denoising pass that left no masked
        position FINISHED its block, and the request receives the block's positions from
        ``gen_from`` on, up to its ``max_new_tokens`` (the rest of its last block is
        thrown away) or an EOS."""
        tm = self.telemetry
        left = mask.sum(axis=1)
        tm.counter("serving/tokens_revealed").inc(int(np.sum((fl.masked - left)[rows & fl.denoise])))
        emitted = 0
        for slot in map(int, np.flatnonzero(rows)):
            st = self._slots[slot]
            if bad[slot]:
                tm.counter("resilience/nan_logit_faults").inc()
                self._quarantine(slot, st.request, "block_step")
                self._release_slot(slot)
                continue
            if not fl.denoise[slot]:
                continue
            if not self._block_static:  # the plan reads what the pass left
                self._bleft[slot] = left[slot]
            if left[slot]:
                continue
            if not st.tokens:
                st.result.first_token_time = time.perf_counter() - self._epoch
                if self.tracer is not None:
                    self.tracer.record(st.uid, "first_token", t=st.result.first_token_time,
                                       slot=slot)
            finished = False
            for tok in map(int, toks[slot, fl.gen_from[slot]:]):
                st.tokens.append(tok)
                st.remaining -= 1
                emitted += 1
                if tok == st.eos or st.remaining <= 0:
                    finished = True
                    break
            if finished:
                self._finish(slot)
        return emitted

    def _note_device_step(self, n_active: int) -> None:
        """The gauges of a device step (decode or verify) over ``n_active`` rows."""
        tm = self.telemetry
        tm.gauge("serving/active_slots").set(n_active)
        tm.histogram("serving/queue_depth_hist").observe(len(self._queue))
        tm.histogram("serving/slot_occupancy").observe(n_active / self.n_slots)

    def _emit(self, bookkeeping, *fetched) -> None:
        """The host's work after a device step's fetch, under one span:
        token append, EOS/limit, ``_finish``, quarantine."""
        with self.telemetry.span("emit") as sp:
            done0 = len(self._terminal_uids)
            sp.annotate(tokens=bookkeeping(*fetched),
                        finished=len(self._terminal_uids) - done0)

    def _emit_decoded(self, rows, nxt, bad) -> int:
        """The tokens of a fetched decode step onto the requests of ``rows``
        [n_slots] bool: the rows it ran whose request is still in its slot."""
        tm = self.telemetry
        emitted = 0
        for slot in map(int, np.flatnonzero(rows)):
            st = self._slots[slot]
            if bad[slot]:
                # non-finite logits: the slot's KV/state is poisoned. The
                # sampled token is garbage — discard the request's partial
                # output, free the slot (host-side transition only) and
                # requeue for a clean replay. The batch keeps decoding.
                tm.counter("resilience/nan_logit_faults").inc()
                req = st.request
                self._quarantine(slot, req, "decode")
                self._release_slot(slot)
                continue
            tok = int(nxt[slot])
            st.tokens.append(tok)
            st.remaining -= 1
            self._pos[slot] += 1
            self._last_tok[slot] = tok
            emitted += 1
            if tok == st.eos or st.remaining <= 0:
                self._finish(slot)  # records the uid in _terminal_uids
        return emitted

    def _step_verify(self, drafts: dict[int, np.ndarray], wpos):
        """Advance every active slot up to ``bucket + 1`` tokens through ONE
        verify dispatch. The bucket is the pow2 ceiling of the longest real
        draft this step; shorter-drafted (or draft-less) slots ride along
        padded and are clamped on the host, so mixed spec/non-spec slots
        share the step. Rejection "rollback" is positional: ``pos`` simply
        never advances past the accepted prefix + bonus token, and the
        rejected tail's stale KV is masked (causally) until overwritten."""
        bucket = _next_pow2(max(len(d) for d in drafts.values()))
        toks = np.zeros((self.n_slots, bucket + 1), np.int32)
        toks[:, 0] = self._last_tok
        for slot, d in drafts.items():
            toks[slot, 1:1 + len(d)] = d
        # every ACTIVE slot greedy (ride-along samplers included) -> the
        # argmax-only program family; one sampled slot anywhere in the
        # batch needs the full acceptance-rule machinery for its rows
        greedy_only = bool(np.all(self._temp[self._active] <= 0.0))
        accept, resample, clean, bad = self.worker.verify(
            bucket, toks, self._pos, wpos, self._active,
            self._temp, self._top_k, self._top_p, greedy_only=greedy_only)
        self._spec_steps += 1
        self._emit(self._emit_verified, drafts, accept, resample, clean, bad)

    def _emit_verified(self, drafts, accept, resample, clean, bad) -> int:
        tm = self.telemetry
        total = 0
        for slot in range(self.n_slots):
            if not self._active[slot]:
                continue
            st = self._slots[slot]
            if bad[slot]:
                # same containment as the decode sentinel: a NaN anywhere
                # in the block means nothing from this dispatch is usable
                tm.counter("resilience/nan_logit_faults").inc()
                req = st.request
                self._quarantine(slot, req, "verify")
                self._release_slot(slot)
                continue
            d = drafts.get(slot)
            rl = 0 if d is None else len(d)
            a = 0
            while a < rl and accept[slot, a]:
                a += 1
            # the burst: accepted prefix + ONE token from the first free
            # position — the residual sample at a true rejection, the clean
            # sample when the draft was exhausted (a == rl). A draft-less
            # slot emits clean[0]: exactly the decode-step sample.
            bonus = int(resample[slot, a]) if a < rl else int(clean[slot, a])
            burst = [int(x) for x in d[:a]] + [bonus] if rl else [bonus]
            if rl:
                if a == 0:
                    # acceptance-aware scheduling: consecutive ZERO-
                    # acceptance verifies first floor the AIMD cap at 1
                    # (cheapest verify bucket), then suppress drafting
                    # entirely (cap 0 — plain decode steps) with a
                    # DECAYING re-probe: each failed probe doubles the
                    # wait before the next one, so a never-accepting
                    # request converges to decode-step dispatch rates
                    self._spec_zero_streak[slot] += 1
                    streak = int(self._spec_zero_streak[slot])
                    if streak >= _SPEC_SUPPRESS_AFTER:
                        self._spec_len[slot] = 0
                        self._spec_probe_wait[slot] = 1 << min(
                            streak - _SPEC_SUPPRESS_AFTER,
                            _SPEC_PROBE_WAIT_MAX_LOG2)
                        tm.counter("serving/spec_suppressions").inc()
                    else:
                        self._spec_len[slot] = 1
                else:
                    # any acceptance clears the streak and resumes AIMD:
                    # a fully-accepted draft doubles the slot's cap
                    # (ramping repetitive output to full depth in
                    # log2(depth) steps); a partial rejection halves it,
                    # parking mispredicting slots in cheap small buckets
                    self._spec_zero_streak[slot] = 0
                    self._spec_probe_wait[slot] = 0
                    self._spec_len[slot] = (
                        min(self.spec_cfg.depth, 4 * rl) if a == rl
                        else max(2, rl // 2))
            self._spec_drafted += rl
            self._spec_accepted += a
            tm.counter("serving/spec_drafted").inc(rl)
            tm.counter("serving/spec_accepted").inc(a)
            if rl:
                tm.histogram("serving/spec_acceptance").observe(a / rl)
            emitted = 0
            finished = False
            for tok in burst:
                # token-by-token so EOS / max_new_tokens truncate the burst
                # exactly where one-at-a-time decode would have stopped
                st.tokens.append(tok)
                st.remaining -= 1
                self._pos[slot] += 1
                self._last_tok[slot] = tok
                emitted += 1
                if tok == st.eos or st.remaining <= 0:
                    finished = True
                    break
            tm.histogram("serving/spec_burst_tokens").observe(emitted)
            total += emitted
            if finished:
                self._finish(slot)
        return total

    def step(self, now: float | None = None, *,
             enforce_deadlines: bool = True) -> list[int]:
        """One scheduler iteration: enqueue the next decode step behind the one
        in flight and fetch and emit that one (``_step``: the scheduler runs ONE
        step ahead), sweep deadlines and shed queue overflow,
        admit arrived requests, advance at most ``chunks_per_step`` admission
        chunks (round-robin over prefilling slots — active slots never stall
        behind a long prompt), and with nothing in flight enqueue a decode step
        (one device call, not waited for: its tokens are appended, and show in
        ``live_progress()``, in the NEXT iteration). Returns the uids that reached
        a TERMINAL state
        since the last step() returned — finished ok, expired, shed,
        deadline-evicted, cancelled, or failed — so a caller driving the
        scheduler directly never waits forever on a degraded request.
        ``enforce_deadlines=False`` (drain mode) skips the deadline sweep —
        drain's ``now=inf`` would otherwise expire everything."""
        if now is None:
            now = time.perf_counter() - self._epoch
        self._steps += 1
        with self.telemetry.span(
                "serve/step", replica_id=self.replica_id, step=self._steps,
                n_active=int(self._active.sum()), queue_len=len(self._queue)):
            return self._step(now, enforce_deadlines)

    def _step(self, now: float, enforce_deadlines: bool) -> list[int]:
        """The scheduler runs ONE decode step ahead of the host's copy of the
        tokens. The order of an iteration:

          1. a step is in flight (k) and nothing needs its tokens first: enqueue
             step k + 1 for the slots that continue (``_advance_flight``);
          2. fetch step k and emit it against the rows that produced it;
          3. sweep, admit (a one-shot prefill stays a synchronous call, queued
             behind step k + 1), advance admission chunks;
          4. nothing is in flight now (the first step, or 1 did not run): enqueue a
             step, and return without waiting for it.

        So the host's side of a step, and whatever its caller does between two
        iterations, runs while the device works on a step queued before it began; a
        token reaches the host (``live_progress()``, the returned uids) one
        iteration after its step was enqueued. Depth is 0 or 1 by what the step
        holds, no switch: 1 does not run, and the step in flight is collected
        first, where the next step cannot be planned without its tokens on the
        host: with a drafter (it proposes from them, and ``verify`` takes the host's
        tokens) and with a fault injector armed (it picks its victim by them)."""
        tm = self.telemetry
        self.worker.step_compiled = False  # fresh heartbeat window
        if self._flight is not None:
            self._advance_flight(ahead=self._drafter is None and self._inj is None)
        with tm.span("sweep"):
            self._maybe_sample_rings(now)
            if self._incidents is not None and self._incidents.pending \
                    and math.isfinite(now):
                self._incidents.tick(now, self._incident_context)
            if enforce_deadlines:
                if self._deadlines_armed:
                    self._sweep_deadlines(now)
                # drain-mode (now=inf) exemption applies here too: it would
                # treat every future-dated request as simultaneously arrived
                # and shed a backlog that real-time stepping would have
                # admitted one slot at a time
                self._shed_overflow(now)
        self._admit(now)
        tm.gauge("serving/queue_depth").set(len(self._queue))
        tm.gauge("serving/prefilling_slots").set(len(self._prefilling))
        if self._prefilling:
            with tm.span("chunks"):
                for _ in range(self.chunk_cfg.chunks_per_step):
                    if not self._prefilling:
                        break
                    slots = sorted(self._prefilling)
                    self._advance_prefill(slots[self._rr % len(slots)])
                    self._rr += 1
        if self._flight is None and self._active.any():
            self._step_from_host()
        if not self._active.any():
            # the occupancy gauge must read 0 once the engine idles — the
            # bench's slot-leak check watches exactly this
            tm.gauge("serving/active_slots").set(0)
        finished = self._terminal_uids
        self._terminal_uids = []
        return finished

    def _step_from_host(self) -> None:
        """A device step with nothing in flight: the host holds every active slot's
        token. A ``verify`` where a drafter proposed (synchronous: it is fetched and
        emitted here), else a decode step, enqueued and left in flight."""
        tm = self.telemetry
        if self._inj is not None:
            # decode-phase fault injection: NaN-poison the chosen request's
            # slot KV BEFORE the decode dispatch, so THIS decode genuinely
            # computes non-finite logits and the device sentinel must fire
            for slot in range(self.n_slots):
                st = self._slots[slot]
                if self._active[slot] and self._inj.garbage_logits(
                        st.uid, "decode", len(st.tokens) - 1):
                    self.worker.fill_slot(slot, float("nan"))
                    tm.counter("resilience/injected_faults").inc()
        if self.block_len > 1:
            active = self._active & ~self._bdone
            if active.any():
                self._enqueue_block_step(active)
            return
        drafts: dict[int, np.ndarray] = {}
        if self._drafter is not None:
            with tm.span("draft") as sp:
                drafts = self._draft()
                sp.annotate(slots=len(drafts))
        if drafts:
            self._note_device_step(int(self._active.sum()))
            # idle rows write at Smax (dropped) and attend at 0: ``_enqueue_decode``
            self._step_verify(drafts, np.where(self._active, self._pos, np.int32(self.Smax)))
        else:
            # no slot drafted this step (speculation off, or the histories
            # have no n-gram match yet): the plain ONE-token decode program
            # — the non-speculative path stays exercised, and a spec-enabled
            # engine pays ZERO verify overhead on draft-less steps
            self._enqueue_decode(self._active.copy(), self._pos,
                                 np.ones((self.n_slots,), np.bool_))

    def _draft(self) -> dict[int, np.ndarray]:
        """Each active slot's proposal for this step, where it has one."""
        drafts: dict[int, np.ndarray] = {}
        for slot in range(self.n_slots):
            if not self._active[slot]:
                continue
            st = self._slots[slot]
            # a draft longer than ``remaining`` could never be fully
            # emitted AND would write KV past the admission budget —
            # the cap keeps every verify write inside the slot window.
            # The adaptive per-slot cap (AIMD, see _spec_len) further
            # clamps it so mispredicting slots draft shallow/cheap
            cap = min(self.spec_cfg.depth, st.remaining,
                      int(self._spec_len[slot]))
            if cap < 1:
                if self._spec_len[slot] == 0 and st.remaining > 0:
                    # suppressed slot: this decode step pays ZERO
                    # drafting/verify overhead. Tick down the decaying
                    # probe timer; when it expires, re-arm a depth-1
                    # probe so a workload that BECOMES predictable can
                    # climb back onto the AIMD ramp
                    self._spec_suppressed_steps += 1
                    self.telemetry.counter(
                        "serving/spec_suppressed_steps").inc()
                    self._spec_probe_wait[slot] -= 1
                    if self._spec_probe_wait[slot] <= 0:
                        self._spec_len[slot] = 1
                        self._spec_probes += 1
                        self.telemetry.counter(
                            "serving/spec_probes").inc()
                continue
            d = self._drafter.propose(
                np.concatenate([
                    np.asarray(st.request.prompt, np.int32).reshape(-1),
                    np.asarray(st.tokens, np.int32)]), cap)
            if d.size:
                drafts[slot] = d
        return drafts

    def drain(self) -> dict[int, RequestResult]:
        """Run steps until queue and slots are empty and no decode step is
        unfetched (ignoring arrival
        times, deadlines AND the queue bound — drain's ``now=inf`` clock
        would otherwise expire every deadline-bearing request and shed
        every future-dated one as a simultaneous arrival); return all
        results so far."""
        while (self._queue or self._prefilling or self._active.any()
               or self._flight is not None):  # nothing stays unfetched
            self.step(now=float("inf"), enforce_deadlines=False)
        if self._incidents is not None and self._incidents.pending:
            # drain's now=inf never ticks the recorder (non-finite clock);
            # a staged incident must not be lost because the engine idled
            self._incidents.flush(self._incident_context)
        return dict(self._results)

    def serve(self, requests: list[Request]) -> dict[int, RequestResult]:
        """Wall-clock driver: admit each request when its arrival_time has
        passed, run continuous decode until every SUBMITTED request completes
        (work already queued/in-flight keeps decoding alongside and stays in
        flight if it outlives this call). Returns {uid: RequestResult} for
        this call's requests, timed against the engine epoch — which is
        reset only when the engine is idle, so in-flight requests' timings
        stay coherent. A request load-shed at submit time still gets a
        result (status ``shed_queue_full``) rather than an exception — the
        typed ``RequestRejected`` is for direct ``submit()`` callers."""
        if not self._queue and not self._prefilling and not self._active.any():
            self._epoch = time.perf_counter()
        target = set()
        for r in sorted(requests, key=lambda r: r.arrival_time):
            try:
                target.add(self.submit(r))
            except RequestRejected as e:
                self._synth_result(r, "shed_" + e.reason)
                target.add(r.uid)
        while not target <= set(self._results):
            now = time.perf_counter() - self._epoch
            if (not self._active.any() and not self._prefilling
                    and self._queue):
                wait = min(r.arrival_time for r in self._queue) - now
                if wait > 0:
                    time.sleep(min(wait, 0.05))
            self.step()
        # nothing stays unfetched: a step still in flight is other requests' (they
        # keep their tokens, and stay in flight if they outlive this call)
        if self._flight is not None:
            self._advance_flight(ahead=False)
        return {u: self._results[u] for u in target}

    # -- observability --------------------------------------------------

    def compile_counts(self) -> dict:
        """How many XLA programs this engine's worker traced — the
        continuous-batching invariant is decode == 1 regardless of workload
        mix, and every chunk width / prefix copy is likewise ONE program."""
        return self.worker.compile_counts()

    def prefix_cache_stats(self) -> Optional[dict]:
        """Host-side prefix-cache view: hit/miss/reuse totals, pool
        occupancy, and the resident entries (length/hits/refs) — None when
        the feature is off."""
        return self._pfx.stats() if self._pfx is not None else None

    def warm_verify(self, *, sampled: bool = False) -> list[int]:
        """Compile the speculative verify program family ahead of traffic:
        one no-op dispatch per pow2 bucket up to ``speculation.depth``
        (every slot inactive, so each KV write lands past ``Smax`` and the
        scatter drops it — nothing observable changes). Serving then never
        pays a verify compile mid-request, the same reason deployments warm
        prefill buckets. Warms the all-greedy family; ``sampled=True`` adds
        the mixed-sampler family. Returns the warmed buckets; no-op when
        speculation is off."""
        if self._drafter is None:
            return []
        buckets, d = [], 1
        while True:
            buckets.append(d)
            if d >= self.spec_cfg.depth:
                break
            d *= 2
        pos = np.zeros(self.n_slots, np.int32)
        wpos = np.full(self.n_slots, self.worker.Smax, np.int32)
        off = np.zeros(self.n_slots, bool)
        for b in buckets:
            toks = np.zeros((self.n_slots, b + 1), np.int32)
            for greedy_only in ((True, False) if sampled else (True,)):
                self.worker.verify(b, toks, pos, wpos, off, self._temp,
                                   self._top_k, self._top_p,
                                   greedy_only=greedy_only, warm=True)
        return buckets

    def spec_stats(self) -> Optional[dict]:
        """Host-side speculative-decoding view: drafted/accepted token
        totals, the derived acceptance rate, and verify dispatch count —
        None when the feature is off. Pure host ints (no registry read);
        this is the block a worker process piggybacks on its step reply so
        a Router aggregates fleet acceptance with zero extra RPCs."""
        if self._drafter is None:
            return None
        drafted, accepted = self._spec_drafted, self._spec_accepted
        return {
            "enabled": True,
            "depth": int(self.spec_cfg.depth),
            "draft_source": self.spec_cfg.draft_source,
            "verify_steps": int(self._spec_steps),
            "drafted": int(drafted),
            "accepted": int(accepted),
            "acceptance_rate": (accepted / drafted) if drafted else 0.0,
            "suppressed_steps": int(self._spec_suppressed_steps),
            "probes": int(self._spec_probes),
        }

    def telemetry_snapshot(self) -> dict:
        """ONE call that reports everything: the metrics registry (TTFT/TPOT/
        queue/occupancy histograms, admission/eviction/token counters), the
        recompile table, the XLA program counts, the program ledger (per-
        program flops/bytes/HBM + derived MFU and roofline verdict), the
        HBM memory ledger (params / slot KV / prefix pool), the per-request
        timeline buffer, the trace-time collective summary, and the
        prefix-cache table when the feature is on. Carries ``replica_id``
        (engine identity) so a Router's merged fleet view stays
        attributable. Also appended to the JSONL log (type ``snapshot``)
        when a sink is configured."""
        from ..comm.logger import comms_logger

        # lazy per-tenant occupancy gauges, refreshed only at snapshot time
        # (docs/serving.md "Multi-tenant isolation"): arrival-queue depth
        # and HBM-slot occupancy per live tenant — pure host counting
        if self._uid_tenant:
            qd: dict[str, int] = {}
            occ: dict[str, int] = {}
            for r in self._queue:
                if r.tenant:
                    qd[r.tenant] = qd.get(r.tenant, 0) + 1
            for s in self._slots:
                t = self._uid_tenant.get(s.uid) if s.uid >= 0 else None
                if t:
                    occ[t] = occ.get(t, 0) + 1
            for p in self._prefilling.values():
                t = self._uid_tenant.get(p.req.uid)
                if t:
                    occ[t] = occ.get(t, 0) + 1
            for t in set(qd) | set(occ):
                self.telemetry.gauge(f"tenant/{t}/queued").set(qd.get(t, 0))
                self.telemetry.gauge(f"tenant/{t}/slots").set(occ.get(t, 0))

        extra = {}
        if self._pfx is not None:
            extra["prefix_cache"] = self._pfx.stats()
        if self._drafter is not None:
            extra["speculation"] = self.spec_stats()
        if self._inj is not None:
            extra["fault_injection"] = self._inj.stats()
        if self.tracer is not None:
            extra["request_trace"] = self.tracer.events()
        # the newest of this replica's ended spans, on perf_counter's clock;
        # request_trace's times are seconds since ``epoch``, so ``epoch + t``
        # puts an event beside them. (The whole ring: telemetry.tracing.spans)
        mine = [sp for sp in ended_spans(self._t_built)
                if sp.replica_id == self.replica_id]
        extra["spans"] = [sp.as_dict() for sp in mine[-_SNAPSHOT_SPANS:]]
        if self._rings is not None:
            extra["rings"] = self._rings.snapshot()
        if self._incidents is not None:
            extra["incidents"] = self._incidents.index()
        snap = self.telemetry.snapshot(
            replica_id=self.replica_id,
            epoch=self._epoch,
            compiles=self.compile_counts(),
            comm=comms_logger.summary(),
            hbm=hbm_snapshot(self.worker.hbm_pools(),
                             self.ledger_cfg.hbm_warn_fraction),
            **extra,
        )
        self.telemetry.emit({"type": "snapshot", **snap})
        return snap
