"""Deterministic, config-selected fault injection.

The recovery paths in this codebase (NaN skip/rewind, torn-checkpoint
fallback, serving quarantine/load-shed) are only trustworthy if each has a
test that *fails when recovery is broken*. This module is the failure
source: a seeded injector whose every decision is a pure function of
``(seed, site, key)`` — two runs with the same config inject the same
faults at the same sites, so recovery tests are reproducible and a
greedy-parity comparison against an unfaulted run is meaningful.

Fault sites (see docs/resilience.md for where each is wired):

  ``nan_grads``       non-finite loss/gradients at a chosen training step
                      (runtime/engine.py poisons the loss scale transiently,
                      producing genuinely non-finite values *inside* the
                      compiled step — the program itself is unchanged).
  ``io_error``        ``OSError`` on the Nth guarded checkpoint/swap write
                      (checkpoint/saver.py consults the installed injector
                      before each file write).
  ``io_flaky``        *transient* ``TransientIOError`` (an OSError) on the
                      Nth guarded write — the write clock keeps advancing,
                      so a retried save lands on fresh write numbers and
                      succeeds; this is the site the retry wrapper
                      (resilience/retry.py) exists to survive, while
                      ``io_error`` models the permanent fault retries must
                      NOT mask.
  ``garbage_logits``  NaN logits for a chosen request: the serving engine
                      poisons the request's slot KV so the next compiled
                      decode/prefill genuinely computes non-finite logits
                      (the device-side sentinel must catch it).
  ``preempt``         simulated preemption before a chosen training step
                      (``PreemptionSignal`` raised pre-dispatch).
  ``replica_dead``    a serving Router replica dies before a chosen router
                      step: the replica's scheduler is never stepped again
                      and its in-flight requests must fail over
                      (inference/router.py).
  ``replica_hang``    a replica's step is observed past ``health.timeout``
                      at a chosen router step (the verdict path — the step
                      itself completes in-process; the Router treats the
                      synthetic latency as a hung heartbeat).
  ``rpc_timeout``     the Nth RPC call of a given method never sees its
                      reply inside the per-call deadline (the call HAS
                      executed remotely — the client raises ``RpcTimeout``
                      after receiving and discarding the reply, modelling
                      a reply that arrived too late; inference/rpc.py).
  ``rpc_conn_reset``  the connection drops after the Nth call of a method
                      executes (reply discarded, socket closed —
                      ``RpcConnectionLost``; the next call pays the
                      bounded-backoff reconnect). Over the TCP family the
                      client closes with SO_LINGER(0), so the peer sees a
                      genuine RST — the abortive reset a yanked cable or a
                      kill -9'd host produces, not a graceful FIN
                      (inference/rpc.py ``RpcClient._drop``).
  ``rpc_garbled_frame``  the Nth reply frame of a method fails the
                      magic/CRC check (``RpcGarbledFrame``; the stream is
                      desynchronized, so the socket is closed too).
  ``gateway_disconnect``  the HTTP gateway's SSE stream for a request sees
                      its client vanish after the Nth streamed token (the
                      write raises as if the peer reset) — the gateway
                      must ``Router.cancel`` the request and free its slot
                      (launcher/http_gateway.py consumes this).
  ``gateway_stall``   the stream's client stops READING after the Nth
                      token: the send blocks past the gateway's write
                      deadline (simulated as a send timeout). Same
                      containment contract as a disconnect — a slow reader
                      must not hold a slot or a handler thread hostage.
  ``router_crash``    the CONTROL PLANE dies at a chosen router step:
                      ``Router.step`` raises a typed ``ControlPlaneCrash``
                      so recovery tests can abandon the Router mid-traffic
                      and rebuild one over the same replicas + request
                      journal — the deterministic in-process spelling of
                      the ``drills.py --router-chaos`` gateway+router
                      SIGKILL (inference/router.py consumes this).

Two selection modes compose:

  * **deterministic lists** (``nan_grad_steps``, ``io_error_writes``,
    ``garbage_logits_uids`` + phase/step, ``preempt_steps``) fire exactly
    once per listed key — a rewound/replayed step or a requeued request is
    NOT re-faulted, modelling a transient fault rather than a permanent one;
  * **rate mode** (``rate`` in (0, 1], optionally restricted by ``sites``)
    draws per opportunity from a crc32 hash of ``(seed, site, #opportunity)``
    — deterministic across runs, independent across opportunities.

Stdlib-only (no jax/numpy): importable from ``checkpoint/saver.py`` and the
report CLI without pulling in a device runtime.
"""

from __future__ import annotations

import threading
import zlib
from collections import Counter
from typing import Any, Optional


def _get(cfg: Any, name: str, default):
    if isinstance(cfg, dict):
        return cfg.get(name, default)
    return getattr(cfg, name, default)


class FaultInjector:
    """Seeded deterministic fault source. ``cfg`` is a
    ``runtime.config.FaultInjectionConfig``, a plain dict with the same
    keys, or None (disabled)."""

    SITES = ("nan_grads", "io_error", "io_flaky", "garbage_logits", "preempt",
             "replica_dead", "replica_hang",
             "rpc_timeout", "rpc_conn_reset", "rpc_garbled_frame",
             "gateway_disconnect", "gateway_stall", "router_crash")

    def __init__(self, cfg: Any = None):
        self.enabled = bool(_get(cfg, "enabled", False)) if cfg is not None else False
        self.seed = int(_get(cfg, "seed", 0))
        self.rate = float(_get(cfg, "rate", 0.0))
        self.sites = set(_get(cfg, "sites", []) or [])
        self.nan_grad_steps = set(_get(cfg, "nan_grad_steps", []) or [])
        self.io_error_writes = set(_get(cfg, "io_error_writes", []) or [])
        self.io_flaky_writes = set(_get(cfg, "io_flaky_writes", []) or [])
        # journal-append clock (io_error family): 1-based indices of
        # RequestJournal appends that fail permanently — the ENOSPC model
        self.io_error_journal_appends = set(
            _get(cfg, "io_error_journal_appends", []) or [])
        self.garbage_logits_uids = set(_get(cfg, "garbage_logits_uids", []) or [])
        self.garbage_logits_phase = str(_get(cfg, "garbage_logits_phase", "decode"))
        self.garbage_logits_decode_step = int(_get(cfg, "garbage_logits_decode_step", 0))
        self.preempt_steps = set(_get(cfg, "preempt_steps", []) or [])
        # router replica faults: [replica_id, router_step] pairs (1-based
        # steps, like every other step-keyed list)
        self.replica_dead_at = {tuple(int(x) for x in p)
                                for p in _get(cfg, "replica_dead_at", []) or []}
        self.replica_hang_at = {tuple(int(x) for x in p)
                                for p in _get(cfg, "replica_hang_at", []) or []}
        # rpc transport faults: [method, nth-call-of-that-method] pairs
        # (1-based, per-client per-method call clocks — inference/rpc.py)
        self.rpc_timeout_at = {(str(p[0]), int(p[1]))
                               for p in _get(cfg, "rpc_timeout_at", []) or []}
        self.rpc_conn_reset_at = {(str(p[0]), int(p[1]))
                                  for p in _get(cfg, "rpc_conn_reset_at", []) or []}
        self.rpc_garbled_at = {(str(p[0]), int(p[1]))
                               for p in _get(cfg, "rpc_garbled_at", []) or []}
        # gateway stream faults: [uid, nth-streamed-token] pairs (1-based)
        self.gateway_disconnect_at = {
            tuple(int(x) for x in p)
            for p in _get(cfg, "gateway_disconnect_at", []) or []}
        self.gateway_stall_at = {
            tuple(int(x) for x in p)
            for p in _get(cfg, "gateway_stall_at", []) or []}
        # control-plane crash: 1-based router steps (router_crash site)
        self.router_crash_at = set(
            _get(cfg, "router_crash_at", []) or [])
        self._writes = 0  # guarded-write clock (io_error site)
        self._journal_appends = 0  # journal-append clock (io_error family)
        self._fired: set = set()  # list-mode keys fire exactly once
        self._lock = threading.Lock()
        self.injected: Counter = Counter()
        self.opportunities: Counter = Counter()

    # -- core decisions -------------------------------------------------

    def _rate_fire(self, site: str) -> bool:
        if self.rate <= 0.0 or (self.sites and site not in self.sites):
            return False
        # one independent deterministic draw per opportunity: the hash is
        # keyed by the per-site opportunity counter, so a replayed request /
        # rewound step gets a FRESH draw (its counter has advanced)
        n = self.opportunities[site]
        h = zlib.crc32(f"{self.seed}:{site}:{n}".encode()) & 0xFFFFFFFF
        return h / float(0x100000000) < self.rate

    def _fire(self, site: str, listed: bool, key) -> bool:
        """One fault decision. List-mode keys fire once, ever."""
        with self._lock:
            self.opportunities[site] += 1
            hit = False
            if listed:
                k = (site, key)
                if k not in self._fired:
                    self._fired.add(k)
                    hit = True
            if not hit:
                hit = self._rate_fire(site)
            if hit:
                self.injected[site] += 1
            return hit

    # -- typed sites ----------------------------------------------------

    def nan_grads(self, step: int) -> bool:
        """True if the training step about to run (1-based global step)
        should see non-finite gradients."""
        if not self.enabled:
            return False
        return self._fire("nan_grads", step in self.nan_grad_steps, step)

    def io_error(self, path: str) -> None:
        """Guarded-write hook: advances the (shared) write clock and raises
        ``OSError`` when this write is armed for the permanent ``io_error``
        site, or ``TransientIOError`` for the ``io_flaky`` site (listed
        indices are 1-based; a RETRY of a failed save advances the clock
        past the armed index, which is what makes the flaky site
        transient)."""
        if not self.enabled:
            return
        with self._lock:
            self._writes += 1
            n = self._writes
        if self._fire("io_error", n in self.io_error_writes, n):
            from .errors import PermanentIOError

            raise PermanentIOError(
                f"fault injection: io_error on guarded write #{n} ({path})")
        if self._fire("io_flaky", n in self.io_flaky_writes, n):
            from .errors import TransientIOError

            raise TransientIOError(
                f"fault injection: io_flaky (transient) on guarded write "
                f"#{n} ({path})")

    def journal_append(self, path: str) -> None:
        """Journal-append hook (``io_error`` family): advances a dedicated
        per-injector append clock and raises ``PermanentIOError`` when this
        append index is armed via ``io_error_journal_appends`` (1-based).
        A separate clock from the checkpoint write clock on purpose — a
        schedule arming "the 3rd journal append" must not depend on how
        many checkpoint writes happened first. The fired-set key is the
        tuple ``("journal", n)`` so it can never collide with the plain
        integer keys the guarded-write sites use."""
        if not self.enabled:
            return
        with self._lock:
            self._journal_appends += 1
            n = self._journal_appends
        if self._fire("io_error", n in self.io_error_journal_appends,
                      ("journal", n)):
            from .errors import PermanentIOError

            raise PermanentIOError(
                f"fault injection: io_error on journal append #{n} ({path})")

    def garbage_logits(self, uid: int, phase: str, decode_step: int = 0) -> bool:
        """True if request ``uid`` should produce NaN logits now. ``phase``
        is ``prefill`` (at admission completion) or ``decode`` with the
        request's 0-based decode-step index."""
        if not self.enabled:
            return False
        listed = (
            uid in self.garbage_logits_uids
            and phase == self.garbage_logits_phase
            and (phase == "prefill" or decode_step == self.garbage_logits_decode_step)
        )
        return self._fire("garbage_logits", listed, (uid, phase, decode_step))

    def preempt(self, step: int) -> bool:
        """True if a preemption signal should fire before running ``step``
        (1-based global step)."""
        if not self.enabled:
            return False
        return self._fire("preempt", step in self.preempt_steps, step)

    def replica_dead(self, replica: int, step: int) -> bool:
        """True if Router replica ``replica`` should be found dead before
        router step ``step`` (1-based)."""
        if not self.enabled:
            return False
        return self._fire("replica_dead",
                          (replica, step) in self.replica_dead_at,
                          (replica, step))

    def replica_hang(self, replica: int, step: int) -> bool:
        """True if replica ``replica``'s router step ``step`` should be
        observed as hung (step latency past ``health.timeout``)."""
        if not self.enabled:
            return False
        return self._fire("replica_hang",
                          (replica, step) in self.replica_hang_at,
                          (replica, step))

    def rpc_timeout(self, method: str, call_n: int) -> bool:
        """True if the ``call_n``-th RPC call of ``method`` (1-based, per
        client) should lose its reply to a deadline."""
        if not self.enabled:
            return False
        return self._fire("rpc_timeout",
                          (method, call_n) in self.rpc_timeout_at,
                          (method, call_n))

    def rpc_conn_reset(self, method: str, call_n: int) -> bool:
        """True if the connection should reset after the ``call_n``-th call
        of ``method`` executes."""
        if not self.enabled:
            return False
        return self._fire("rpc_conn_reset",
                          (method, call_n) in self.rpc_conn_reset_at,
                          (method, call_n))

    def rpc_garbled_frame(self, method: str, call_n: int) -> bool:
        """True if the ``call_n``-th reply frame of ``method`` should fail
        its integrity check."""
        if not self.enabled:
            return False
        return self._fire("rpc_garbled_frame",
                          (method, call_n) in self.rpc_garbled_at,
                          (method, call_n))

    def gateway_disconnect(self, uid: int, token_n: int) -> bool:
        """True if the SSE stream for request ``uid`` should observe its
        client gone after streaming token ``token_n`` (1-based)."""
        if not self.enabled:
            return False
        return self._fire("gateway_disconnect",
                          (uid, token_n) in self.gateway_disconnect_at,
                          (uid, token_n))

    def gateway_stall(self, uid: int, token_n: int) -> bool:
        """True if the stream's reader should stall (send deadline
        overrun) after token ``token_n`` (1-based)."""
        if not self.enabled:
            return False
        return self._fire("gateway_stall",
                          (uid, token_n) in self.gateway_stall_at,
                          (uid, token_n))

    def router_crash(self, step: int) -> bool:
        """True if the control plane should crash (typed
        ``ControlPlaneCrash`` out of ``Router.step``) at router step
        ``step`` (1-based)."""
        if not self.enabled:
            return False
        return self._fire("router_crash", step in self.router_crash_at, step)

    def stats(self) -> dict:
        return {
            "injected": dict(self.injected),
            "opportunities": dict(self.opportunities),
            "guarded_writes": self._writes,
            "journal_appends": self._journal_appends,
        }


# -- process-global injector -------------------------------------------
# checkpoint/saver.py's free functions have no engine handle to thread an
# injector through; they consult this slot instead. The engine installs its
# injector at init; tests install/clear around save/load calls.

_installed: Optional[FaultInjector] = None


def install_injector(inj: Optional[FaultInjector]) -> None:
    global _installed
    _installed = inj


def clear_injector() -> None:
    install_injector(None)


def get_injector() -> Optional[FaultInjector]:
    return _installed


def maybe_io_error(path: str) -> None:
    """Guarded-write hook for code without an injector reference (no-op
    unless an enabled injector is installed)."""
    if _installed is not None:
        _installed.io_error(path)
