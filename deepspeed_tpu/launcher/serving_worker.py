"""Serving worker process: one ``ServingEngine`` behind the serving RPC.

``python -m deepspeed_tpu.launcher.serving_worker --socket S --spec F``
boots one scheduler+worker pair (model/params rebuilt deterministically
from the spec — params come from ``PRNGKey(0)``, so every worker of a
fleet, and the router's reference engine, hold bit-identical weights) and
serves the scheduler surface over ``inference/rpc.RpcServer``. The Router
drives it through ``rpc.ReplicaClient`` exactly as it drives an in-process
replica.

``--socket`` takes a unix socket path (same-host fleets) or
``tcp://host:port`` (replicas on separate hosts; port 0 binds an
ephemeral port and the resolved address rides the ``ready`` line, which
is how the supervisor discovers it). Per-worker device/platform
assignment: ``--platform`` pins the jax platform for THIS process before
its backend initializes, and the supervisor's ``worker_env`` injects arbitrary
per-slot environment (e.g. ``TPU_VISIBLE_CHIPS`` / mesh selection), so
each replica of a fleet can own a different device set or mesh.

Process lifecycle:

  * heartbeat — when ``--heartbeat FILE`` is given the worker touches it on
    every serve-loop tick (throttled to ~5 Hz). The supervisor judges
    staleness on a MONOTONIC clock against its own observations of the
    file changing, so an NTP step can neither mint a false hung verdict
    nor hide a real one.
  * SIGTERM — drain-then-exit, reusing ``resilience/preemption.py``: the
    handler only sets a flag; the serve loop notices it at a frame
    boundary, stops serving, runs ``engine.drain()`` so every accepted
    request still reaches a terminal state in-process, prints a final
    ``{"event": "drained", ...}`` JSON line, and exits 0. (The Router-side
    rolling-restart path drains the replica FIRST — migrating queued work
    — so by the time SIGTERM lands the worker is typically idle.)
  * SIGKILL — nothing runs; the Router sees ``RpcConnectionLost`` on its
    next call (DEAD verdict, exactly-once failover from router-side
    request state) and the ``WorkerSupervisor`` respawns a fresh process
    after its bounded backoff. This is the ``drills.py --chaos-serving``
    drill's fault.

Replay-safe step contract: terminal uids (and their encoded results)
accumulate UNACKED across step replies until the client acknowledges them
on its next step — a reply lost to a connection reset is re-delivered, and
the Router's ``_collect`` dedups. ``withdraw`` results are cached per uid
for the same reason. Each step reply also piggybacks the engine's bounded
request-trace flush, so a later SIGKILL cannot take the timeline with it.

``WorkerSupervisor`` owns spawn/respawn: one process per replica slot,
socket + heartbeat under a (short-pathed) work directory, heartbeat-
timeout/SIGKILL discipline borrowed from ``elasticity/elastic_agent.py``,
and bounded-backoff respawn pacing from ``resilience/retry.py``.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()  # the ``ready`` line's ``startup_s`` counts from here

import json
import os
import signal
import subprocess
import sys
import tempfile
from collections import Counter
from typing import Optional

import threading

from ..inference.rpc import (ReplicaClient, RpcConnectionLost, RpcServer,
                             _dec_value, decode_kv_window, decode_request,
                             encode_kv_window, encode_request, encode_result)
from ..resilience.heartbeat import HeartbeatJudge
from ..resilience.preemption import PreemptionGuard
from ..resilience.retry import RetryPolicy, backoff_delay
from ..runtime.config import RouterTransportConfig
from ..telemetry import tracing
from ..utils.durability import write_durable_bytes
from ..utils.jax_env import require_chip_free, use_compile_cache
from ..utils.logging import logger


def build_serving_engine(spec: dict, replica_id: int | str = 0,
                         role: str | None = None):
    """Deterministic engine construction from a plain-JSON spec:
    ``{"model": {TransformerConfig kwargs, "dtype": "float32"},
    "engine_dtype": "fp32", "serving": {ServingEngine config}}``.
    Params are initialized from ``PRNGKey(0)`` inside ``InferenceEngine``,
    so every process building the same spec holds identical weights.
    ``role`` (the ``--role`` flag) overrides any ``serving.role`` in the
    spec — disaggregated pools share ONE spec and differ only by flag.

    The whole build is the kept span ``startup/build``: the engine's phases
    (``mesh``, ``shapes``, ``draw``, ``cache``) and every program traced,
    compiled or loaded on the way end under it (``tracing.startup_table``)."""
    import jax.numpy as jnp

    from ..inference import InferenceEngine
    from ..inference.serving import ServingEngine
    from ..models.transformer import Model, TransformerConfig, rotary_kinds_fact

    with tracing.span(tracing.STARTUP, keep=True, replica_id=replica_id,
                      role=role or "both") as built:
        model_spec = dict(spec.get("model", {}))
        dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[
            str(model_spec.pop("dtype", "float32"))]
        cfg = TransformerConfig(dtype=dtype, **model_spec)
        if cfg.pos_emb == "rotary":  # how each layer kind turns its q and k
            built.annotate(rotary_kinds=rotary_kinds_fact(cfg))
        engine = InferenceEngine(
            model=Model(cfg), config={"dtype": spec.get("engine_dtype", "fp32")})
        return ServingEngine(engine, config=dict(spec.get("serving", {})),
                             replica_id=replica_id, role=role)


class WorkerHost:
    """RPC handler table around one ``ServingEngine`` (see module
    docstring for the replay-safety rules)."""

    def __init__(self, engine, heartbeat: Optional[str] = None):
        self.engine = engine
        self.heartbeat = heartbeat
        self._hb_last = 0.0
        self._unacked: list[int] = []  # terminal uids awaiting client ack
        self._withdrawn: dict[int, dict] = {}  # uid -> encoded request
        if heartbeat:
            # beat from a daemon thread, not only between frames: a long
            # handler (a cold XLA compile inside the first step, a big
            # drain) blocks the serve loop for longer than any sane
            # heartbeat timeout, and the supervisor must not SIGKILL a
            # healthy worker for it. Device compiles/executes release the
            # GIL, so the thread keeps beating through them; a genuinely
            # wedged interpreter stops it — which is the hang signal.
            threading.Thread(target=self._beat_forever, daemon=True).start()

    # -- liveness --------------------------------------------------------

    def tick(self) -> None:
        if self.heartbeat and time.monotonic() - self._hb_last > 0.2:
            # dstpu: allow[thread-race] -- advisory throttle shared by the serve loop's on_tick and the daemon beat thread: the worst interleaving is two near-simultaneous beats double-touching the heartbeat file (one extra utime); no liveness verdict reads _hb_last — the supervisor judges the FILE's mtime on its own monotonic clock
            self._hb_last = time.monotonic()
            try:
                os.utime(self.heartbeat, None)
            except OSError:
                try:
                    with open(self.heartbeat, "w"):
                        pass
                except OSError:
                    pass  # heartbeat is advisory; serving goes on

    def _beat_forever(self) -> None:
        while True:
            self.tick()
            time.sleep(0.5)

    def ping(self) -> dict:
        return {"pid": os.getpid(), "mono": time.monotonic(),
                "replica_id": self.engine.replica_id,
                "role": getattr(self.engine, "role", "both")}

    # -- scheduler surface ----------------------------------------------

    def _state(self, now=None) -> dict:
        e = self.engine
        return {
            "load": e.load, "idle": e.idle, "queue_len": e.queue_len,
            "arrived": e.arrived_queue_len(now),
            "pending": e.pending_arrival_times(),
            "occupancy": getattr(e, "occupancy", 0.0),
        }

    def submit(self, request: dict) -> dict:
        uid = self.engine.submit(decode_request(request))
        return {"uid": uid, **self._state()}

    def requeue(self, request: dict) -> dict:
        req = decode_request(request)
        self._withdrawn.pop(req.uid, None)  # a re-queued uid may be re-drained
        try:
            uid = self.engine.requeue(req)
        except ValueError as e:
            if ("already in flight" in str(e)
                    and self.engine.result(req.uid) is None):
                uid = req.uid  # replay-safe: a retried requeue re-delivered
            else:
                raise
        return {"uid": uid, **self._state()}

    def withdraw(self, uid: int) -> dict:
        uid = int(uid)
        if uid in self._withdrawn:  # replay-safe: reply lost, not the request
            return {"request": self._withdrawn[uid], **self._state()}
        req = self.engine.withdraw(uid)
        enc = None if req is None else encode_request(req)
        if enc is not None:
            self._withdrawn[uid] = enc
        return {"request": enc, **self._state()}

    def cancel(self, uid: int) -> dict:
        ok = self.engine.cancel(int(uid))
        res = self.engine.result(int(uid))
        return {"cancelled": ok,
                "result": None if res is None else encode_result(res),
                **self._state()}

    def result(self, uid: int):
        res = self.engine.result(int(uid))
        return None if res is None else encode_result(res)

    def step(self, now=None, enforce_deadlines: bool = True,
             ack=None, progress: bool = False) -> dict:
        for uid in ack or []:
            try:
                self._unacked.remove(int(uid))
            except ValueError:
                pass
        uids = self.engine.step(
            now=None if now is None else float(now),
            enforce_deadlines=bool(enforce_deadlines))
        known = set(self._unacked)
        self._unacked.extend(u for u in uids if u not in known)
        results = {}
        for u in self._unacked:
            res = self.engine.result(u)
            if res is not None:
                results[str(u)] = encode_result(res)
        reply = {
            "uids": list(self._unacked),
            "results": results,
            "trace": self.engine.take_trace_flush(256),
            "compiled": self.engine.last_step_compiled,
            **self._state(now),
        }
        rings = self.engine.take_ring_flush(256)
        if rings:
            # closed flight-recorder cells ride the reply like trace —
            # the Router's mirror ingest costs zero extra RPCs; omitted
            # when empty (the common off/idle case adds no wire bytes)
            reply["rings"] = rings
        if getattr(self.engine, "role", "both") == "prefill":
            # parked prefill-complete requests ride the reply so the
            # Router's handoff pump never polls — the disaggregated twin
            # of the trace/spec piggybacks
            reply["handoff"] = self.engine.handoff_ready()
        spec = self.engine.spec_stats()
        if spec is not None:
            # speculative acceptance counts ride the step reply exactly
            # like progress/trace — the Router's fleet aggregation costs
            # zero extra RPCs (a handful of ints; always-on when enabled)
            reply["spec"] = spec
        if progress:
            # tokens-so-far per decoding slot: the gateway's SSE streams
            # advance from this piggyback — zero extra round trips.
            # OPT-IN (the gateway flips it via Router.
            # enable_stream_progress): re-sending each stream's full
            # token list per step is O(tokens^2) wire over a generation,
            # and a fleet with no streaming front door must not pay it
            reply["progress"] = {
                str(u): list(t) for u, t in self.engine.live_progress().items()}
        return reply

    def live_requests(self) -> list:
        return [encode_request(r) for r in self.engine.live_requests()]

    def reconcile(self, uids) -> dict:
        """One recovery round trip (``Router._recover``): for the
        journaled non-terminal ``uids`` a restarted control plane asks
        about, report which this worker still holds LIVE and every
        terminal result it has for them — the unacked-result buffer and
        the engine's result map both survive a ROUTER crash, since only
        the router process died. Read-only and replay-safe."""
        results = {}
        for u in uids or []:
            res = self.engine.result(int(u))
            if res is not None:
                results[str(int(u))] = encode_result(res)
        live = [int(r.uid) for r in self.engine.live_requests()]
        return {"live": live, "results": results, **self._state()}

    def arrived_queue_len(self, now=None) -> int:
        return self.engine.arrived_queue_len(
            None if now is None else float(now))

    def prefix_match_len(self, prompt) -> int:
        return self.engine.prefix_match_len(_dec_value(prompt))

    def set_epoch(self, elapsed: float) -> dict:
        # cross-process epoch alignment: perf_counter references are
        # per-process, so the wire carries the caller's elapsed-since-epoch
        # and we re-anchor the local clock to match (skew = rpc latency)
        self.engine.set_epoch(time.perf_counter() - float(elapsed))
        return self._state()

    def drain(self) -> dict:
        return {str(u): encode_result(r)
                for u, r in self.engine.drain().items()}

    # -- disaggregated handoff surface (docs/serving.md) -----------------

    def kv_export_window(self, uid, start, width,
                         compression: str = "none") -> dict:
        k, v = self.engine.kv_export_window(int(uid), int(start), int(width))
        return encode_kv_window(k, v, str(compression))

    def kv_import_window(self, uid, start, width, window: dict) -> dict:
        k, v = decode_kv_window(window)
        self.engine.kv_import_window(int(uid), int(start), int(width), k, v)
        return self._state()

    def kv_import_begin(self, request: dict, pos, first,
                        prefix_hit_tokens=0, t_admit=0.0,
                        t_first=0.0) -> dict:
        slot = self.engine.kv_import_begin(
            decode_request(request), pos=int(pos), first=int(first),
            prefix_hit_tokens=int(prefix_hit_tokens),
            t_admit=float(t_admit), t_first=float(t_first))
        return {"slot": int(slot), **self._state()}

    def kv_import_commit(self, uid) -> dict:
        return {"committed": self.engine.kv_import_commit(int(uid)),
                **self._state()}

    def kv_import_abort(self, uid) -> dict:
        return {"aborted": self.engine.kv_import_abort(int(uid)),
                **self._state()}

    def handoff_release(self, uid) -> dict:
        return {"released": self.engine.handoff_release(int(uid)),
                **self._state()}

    # -- observability ---------------------------------------------------

    def telemetry_snapshot(self) -> dict:
        return self.engine.telemetry_snapshot()

    def compile_counts(self) -> dict:
        return self.engine.compile_counts()

    def prefix_cache_stats(self):
        return self.engine.prefix_cache_stats()

    def handlers(self) -> dict:
        return {name: getattr(self, name) for name in (
            "ping", "submit", "requeue", "withdraw", "cancel", "result",
            "step", "live_requests", "reconcile", "arrived_queue_len",
            "prefix_match_len", "set_epoch", "drain", "telemetry_snapshot",
            "compile_counts", "prefix_cache_stats",
            "kv_export_window", "kv_import_window", "kv_import_begin",
            "kv_import_commit", "kv_import_abort", "handoff_release")}


def _pid_alive(pid: int) -> bool:
    """Liveness probe (signal 0). EPERM means alive-but-not-ours — still
    alive for the purposes of never SIGKILLing a recycled pid."""
    try:
        os.kill(int(pid), 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        return True
    return True


class _AdoptedProc:
    """Popen-shaped handle for a worker ADOPTED from a dead predecessor
    supervisor's pidfile: the process is not our child, so there is no
    real returncode — ``poll`` degrades to the pid-liveness probe and a
    vanished process reports the conventional ``-SIGKILL``. ``wait`` is a
    bounded poll loop (retire/shutdown paths); ``kill`` delivers the
    signal directly."""

    def __init__(self, pid: int):
        self.pid = int(pid)
        self.returncode: Optional[int] = None

    def poll(self) -> Optional[int]:
        if self.returncode is None and not _pid_alive(self.pid):
            self.returncode = -signal.SIGKILL  # true rc unknowable: not our child
        return self.returncode

    def wait(self, timeout: Optional[float] = None) -> int:
        deadline = None if timeout is None else time.monotonic() + timeout
        while self.poll() is None:
            if deadline is not None and time.monotonic() > deadline:
                raise subprocess.TimeoutExpired(
                    f"adopted worker pid {self.pid}", timeout)
            time.sleep(0.05)
        return self.returncode

    def kill(self) -> None:
        try:
            os.kill(self.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m deepspeed_tpu.launcher.serving_worker",
        description="Host one ServingEngine replica behind the serving RPC.")
    ap.add_argument("--socket", required=True,
                    help="address to bind: a unix socket path, or "
                         "tcp://host:port (port 0 = OS-assigned; the "
                         "resolved address is printed in the ready line)")
    ap.add_argument("--spec", required=True,
                    help="JSON spec file: {model, engine_dtype, serving}")
    ap.add_argument("--replica-id", default="0",
                    help="identity stamped into telemetry snapshots")
    ap.add_argument("--heartbeat", default="",
                    help="heartbeat file touched each serve-loop tick")
    ap.add_argument("--platform", default="",
                    help="pin the jax platform for this worker (per-worker "
                         "device/platform assignment)")
    ap.add_argument("--role", default="", choices=["", "both", "prefill",
                                                   "decode"],
                    help="disaggregated serving role: prefill workers park "
                         "finished prefills for KV handoff, decode workers "
                         "import KV and own decode/speculation "
                         "(default: both)")
    args = ap.parse_args(argv)

    if args.platform:
        import jax

        # jax is already imported (the package imports it), so the
        # environment variable alone is too late for this process
        os.environ["JAX_PLATFORMS"] = args.platform
        jax.config.update("jax_platforms", args.platform)
    use_compile_cache()

    with open(args.spec) as f:
        spec = json.load(f)
    rid = int(args.replica_id) if str(args.replica_id).isdigit() else args.replica_id

    # SIGTERM/SIGINT -> flag only (resilience/preemption.py); consumed at a
    # frame boundary below for the drain-then-exit path
    guard = PreemptionGuard(["SIGTERM", "SIGINT"])
    guard.install()

    # engine BEFORE socket: a connectable socket means a servable worker
    engine = build_serving_engine(spec, replica_id=rid,
                                  role=args.role or None)
    host = WorkerHost(engine, heartbeat=args.heartbeat or None)
    server = RpcServer(args.socket, host.handlers())
    # the RESOLVED address (a tcp://host:0 bind reports its real port):
    # the supervisor reads this line to learn where to connect
    # how long ready took, and the build's phases (seconds by path): what an
    # autoscaler's scale-up lag was made of
    phases = tracing.startup_table()["phases"]
    print(json.dumps({"event": "ready", "pid": os.getpid(),
                      "replica_id": rid, "socket": server.address,
                      "startup_s": round(time.perf_counter() - T_PROCESS_START, 3),
                      "phases": {k: round(v, 3) for k, v in phases.items()}}),
          flush=True)
    try:
        server.serve_forever(should_stop=guard.pending, on_tick=host.tick)
    finally:
        server.close()
    if guard.pending():
        # graceful retirement: finish every accepted request in-process so
        # nothing is stranded mid-decode, then report and exit 0
        in_flight = engine.load
        results = engine.drain()
        print(json.dumps({"event": "drained", "signal": guard.last_signal,
                          "in_flight_at_signal": in_flight,
                          "results": len(results)}), flush=True)
    return 0


# -- supervision -------------------------------------------------------------

class WorkerSupervisor:
    """Spawn/respawn serving worker processes — the elastic agent's
    heartbeat-timeout/SIGKILL discipline applied to the serving fleet.

    One replica SLOT per worker; each (re)spawn is a new generation with a
    fresh address (unix socket path, or ``transport.host:port_base+slot``
    / an OS-assigned ephemeral port under the TCP family). ``poll()``
    detects exited workers and SIGKILLs hung ones (heartbeat stale on a
    monotonic clock); ``respawn()`` pays the bounded-backoff delay and
    boots a replacement. The caller (usually ``inference/autoscaler.
    Autoscaler``) wires respawned clients back into a Router via
    ``Router.attach_replica`` — a replacement process is a NEW replica,
    never a resurrection of the dead rid.

    Respawn-budget healing: ``_respawn_count[slot]`` decays by one for
    every ``respawn_heal_s`` of heartbeat-healthy uptime the slot's
    current generation accrues, so a long-lived fleet with occasional
    preemptions is never one respawn from permanent ``max_respawns``
    exhaustion. Crash-loop detection is unchanged — rapid deaths never
    live long enough to heal and still exhaust the budget.

    ``worker_env`` maps slot -> extra environment for THAT worker only
    (on top of the fleet-wide ``env``) — per-worker device/platform
    assignment: e.g. ``{0: {"JAX_PLATFORMS": "tpu",
    "TPU_VISIBLE_CHIPS": "0"}, 1: {"TPU_VISIBLE_CHIPS": "1"}}`` puts each
    replica on its own chip set / mesh."""

    def __init__(self, spec: dict, n_workers: int, *,
                 workdir: Optional[str] = None,
                 transport: RouterTransportConfig | dict | None = None,
                 respawn_backoff: RetryPolicy | dict | None = None,
                 max_respawns: int = 3,
                 respawn_heal_s: float = 300.0,
                 seed: int = 0,
                 env: Optional[dict] = None,
                 worker_env: Optional[dict] = None,
                 roles: Optional[dict] = None,
                 clock=None):
        if isinstance(transport, dict):
            transport = RouterTransportConfig(**transport)
        self.transport = transport or RouterTransportConfig()
        if isinstance(respawn_backoff, dict):
            respawn_backoff = RetryPolicy(**respawn_backoff)
        self.respawn_backoff = respawn_backoff or RetryPolicy(
            max_attempts=1 << 30, base_delay_s=0.5, max_delay_s=8.0,
            jitter=0.25)
        self.max_respawns = int(max_respawns)
        self.respawn_heal_s = float(respawn_heal_s)
        self.seed = int(seed)
        self.n_workers = int(n_workers)
        # verdict/heal clock: monotonic (injectable for fake-clock tests;
        # never wall time — the PR 8 NTP lesson)
        self._now = clock if clock is not None else time.monotonic
        # sockets live here: a caller-supplied deep path can overflow the
        # AF_UNIX sun_path limit (~108 chars), so default to a short tmpdir
        self.workdir = workdir or tempfile.mkdtemp(prefix="dstpu_srv_")
        os.makedirs(self.workdir, exist_ok=True)
        self.spec_path = os.path.join(self.workdir, "spec.json")
        with open(self.spec_path, "w") as f:
            json.dump(spec, f)
        self.extra_env = dict(env or {})
        self.worker_env = {int(k): dict(v)
                           for k, v in (worker_env or {}).items()}
        # slot -> serving role ("prefill"/"decode"/"both"): disaggregated
        # pools differ only by this flag — same spec, same weights. A slot
        # keeps its role across respawns (a replacement prefill worker is
        # still a prefill worker); missing slots default to "both".
        self.roles = {int(k): str(v) for k, v in (roles or {}).items()}
        self._procs: dict[int, subprocess.Popen] = {}
        self._clients: dict[int, ReplicaClient] = {}
        self._logs: dict[int, str] = {}
        self._gen: Counter = Counter()
        self._respawn_count: Counter = Counter()
        self._heal_anchor: dict[int, float] = {}
        # heartbeat staleness is judged by the shared monotonic judge
        # (resilience/heartbeat.HeartbeatJudge, same as the elastic
        # agent): mtime-change observations on a monotonic clock — an NTP
        # step can't mint a false hung verdict — with a 10x startup grace
        # until the worker's first touch
        self._hb_path: dict[int, str] = {}
        self._hb_judge: dict[int, HeartbeatJudge] = {}
        self.respawns = 0

    # -- spawn -----------------------------------------------------------

    def set_spec(self, spec: dict) -> None:
        """Install a NEW engine spec for future (re)spawns — the rolling
        upgrade's generation replacement (``Router.rolling_upgrade``):
        running workers keep serving their old generation's spec; each
        retire→spawn wave boots the new one. Durable write (tmp + fsync +
        rename) so a crash mid-upgrade never leaves a torn spec for the
        next respawn to boot from."""
        write_durable_bytes(self.spec_path,
                            json.dumps(spec).encode("utf-8"))

    def _pidfile(self, slot: int) -> str:
        return os.path.join(self.workdir, f"w{slot}.pid")

    def _write_pidfile(self, slot: int, info: dict) -> None:
        """Per-slot pidfile, written tmp + fsync + rename (+ dir fsync):
        the adoption record a RESTARTED supervisor reads to find workers
        that survived the control plane's death. A torn pidfile would be
        adopted as garbage or reaped as stale — durability is the hygiene
        here, same discipline as ``set_spec``."""
        write_durable_bytes(self._pidfile(slot),
                            json.dumps(info).encode("utf-8"))

    def _remove_pidfile(self, slot: int) -> None:
        try:
            os.unlink(self._pidfile(slot))
        except OSError:
            pass

    def _listen_address(self, slot: int) -> str:
        """The address the slot's NEXT generation binds: a per-generation
        unix socket path, or ``tcp://host:{port_base+slot}`` (port 0 under
        an unset ``port_base`` — the worker binds an ephemeral port and
        the supervisor learns it from the ready line)."""
        t = self.transport
        if t.family == "tcp":
            port = t.port_base + slot if t.port_base else 0
            return f"tcp://{t.host}:{port}"
        return os.path.join(self.workdir, f"w{slot}g{self._gen[slot]}.sock")

    def _ready_address(self, slot: int) -> Optional[str]:
        """The resolved address from the worker's ``ready`` log line (how
        an ephemeral TCP port is discovered); None until printed."""
        try:
            with open(self._logs[slot]) as f:
                for line in f:
                    line = line.strip()
                    if not line.startswith("{"):
                        continue
                    try:
                        ev = json.loads(line)
                    except ValueError:
                        continue
                    if ev.get("event") == "ready":
                        return ev.get("socket")
        except OSError:
            pass
        return None

    def spawn(self, slot: int) -> ReplicaClient:
        """Boot the worker for ``slot`` and block until its socket serves a
        ping (bounded by ``transport.boot_timeout_s``)."""
        addr = self._listen_address(slot)
        hb = os.path.join(self.workdir, f"hb{slot}")
        with open(hb, "w"):
            pass
        self._hb_path[slot] = hb
        judge = HeartbeatJudge(hb, float(self.transport.heartbeat_timeout_s))
        judge.reset()
        self._hb_judge[slot] = judge
        self._heal_anchor[slot] = self._now()
        log_path = os.path.join(self.workdir,
                                f"w{slot}g{self._gen[slot]}.log")
        self._logs[slot] = log_path
        env = dict(os.environ)
        env.update(self.extra_env)
        env.update(self.worker_env.get(slot, {}))
        require_chip_free("WorkerSupervisor", env)
        cmd = [sys.executable, "-m", "deepspeed_tpu.launcher.serving_worker",
               "--socket", addr, "--spec", self.spec_path,
               "--replica-id", str(slot), "--heartbeat", hb]
        role = self.roles.get(slot)
        if role:
            cmd += ["--role", role]
        with open(log_path, "w") as log_f:
            proc = subprocess.Popen(cmd, env=env, stdout=log_f,
                                    stderr=subprocess.STDOUT,
                                    start_new_session=True)
        self._procs[slot] = proc
        # adoption record FIRST (pid + declared address): a control-plane
        # crash during boot must not leave an untracked orphan; the
        # resolved address is rewritten below once the worker is up
        self._write_pidfile(slot, {
            "pid": proc.pid, "slot": slot, "gen": self._gen[slot],
            "addr": addr, "heartbeat": hb, "log": log_path})
        # an ephemeral-port worker resolves its address at bind time; poll
        # the ready line for it before the first connect
        ephemeral = addr.startswith("tcp://") and addr.endswith(":0")
        client: Optional[ReplicaClient] = None
        deadline = time.monotonic() + float(self.transport.boot_timeout_s)
        while True:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"serving worker slot {slot} exited rc={proc.returncode} "
                    f"during boot (log: {log_path}): {self.log_tail(slot)}")
            if time.monotonic() > deadline:
                proc.kill()
                raise RuntimeError(
                    f"serving worker slot {slot} did not serve within "
                    f"boot_timeout_s={self.transport.boot_timeout_s} "
                    f"(log: {log_path})")
            if client is None:
                target = self._ready_address(slot) if ephemeral else addr
                if target is None:
                    time.sleep(0.1)
                    continue
                client = ReplicaClient(target, replica_id=slot,
                                       transport=self.transport,
                                       seed=self.seed * 1009 + slot)
            try:
                client.connect()
                client.ping()
                break
            except RpcConnectionLost:
                time.sleep(0.1)
        self._clients[slot] = client
        if client.rpc.path != addr:
            # ephemeral TCP port resolved at bind time: the adoption
            # record must carry the address a successor can connect to
            self._write_pidfile(slot, {
                "pid": proc.pid, "slot": slot, "gen": self._gen[slot],
                "addr": client.rpc.path, "heartbeat": hb, "log": log_path})
        logger.info("serving supervisor: slot %d generation %d up (pid %d, "
                    "%s)", slot, self._gen[slot], proc.pid, client.rpc.path)
        return client

    # -- orphan adoption (docs/serving.md "Crash-safe control plane") ----

    def adopt(self) -> dict[int, ReplicaClient]:
        """Adopt still-running workers a DEAD predecessor supervisor left
        behind, from the fsync'd per-slot pidfiles in ``workdir`` — a
        restarted control plane re-attaches surviving workers instead of
        double-spawning onto their ports/sockets.

        Hygiene rules (the recycled-pid hazard): a pidfile whose pid is
        dead is STALE and reaped (unlinked); a pid that is alive must ALSO
        prove identity — the recorded RPC address answers ``ping`` with
        the recorded pid — before adoption. A recycled pid that merely
        exists (or an unrelated process squatting the address) fails the
        identity check and only the FILE is reaped: this supervisor never
        signals a pid it cannot prove is its worker. Returns
        ``{slot: ReplicaClient}`` for every adopted worker; missing slots
        are the caller's to ``spawn()``."""
        adopted: dict[int, ReplicaClient] = {}
        try:
            names = sorted(os.listdir(self.workdir))
        except OSError:
            return adopted
        for name in names:
            if not (name.startswith("w") and name.endswith(".pid")):
                continue
            path = os.path.join(self.workdir, name)
            try:
                with open(path) as f:
                    info = json.load(f)
                slot = int(info["slot"])
                pid = int(info["pid"])
                addr = str(info["addr"])
            except (OSError, ValueError, KeyError, TypeError):
                logger.warning("serving supervisor: unreadable pidfile %s "
                               "— reaping", path)
                try:
                    os.unlink(path)
                except OSError:
                    pass
                continue
            if slot in self._procs:
                continue  # this supervisor already owns the slot
            if not _pid_alive(pid):
                logger.info("serving supervisor: stale pidfile %s (pid %d "
                            "dead) — reaped", path, pid)
                try:
                    os.unlink(path)
                except OSError:
                    pass
                continue
            # liveness is not identity: prove over the RPC socket that
            # the live pid IS our worker before supervising (or ever
            # signalling) it
            client = ReplicaClient(addr, replica_id=slot,
                                   transport=self.transport,
                                   seed=self.seed * 1009 + slot)
            try:
                reply = client.ping()
                verified = int(reply.get("pid", -1)) == pid
            except (RpcError, OSError):
                verified = False
            if not verified:
                client.close()
                logger.warning(
                    "serving supervisor: pidfile %s names live pid %d but "
                    "%s does not answer as it — recycled pid or squatted "
                    "address; reaping the FILE, never the pid", path, pid,
                    addr)
                try:
                    os.unlink(path)
                except OSError:
                    pass
                continue
            self._procs[slot] = _AdoptedProc(pid)
            self._clients[slot] = client
            self._gen[slot] = int(info.get("gen", 0))
            self._logs[slot] = str(info.get("log", "")) or os.path.join(
                self.workdir, f"w{slot}g{self._gen[slot]}.log")
            hb = str(info.get("heartbeat", "")) or os.path.join(
                self.workdir, f"hb{slot}")
            self._hb_path[slot] = hb
            judge = HeartbeatJudge(
                hb, float(self.transport.heartbeat_timeout_s))
            judge.reset()
            self._hb_judge[slot] = judge
            self._heal_anchor[slot] = self._now()
            adopted[slot] = client
            logger.info("serving supervisor: ADOPTED slot %d (pid %d, %s, "
                        "generation %d) from a previous supervisor",
                        slot, pid, addr, self._gen[slot])
        return adopted

    def start(self) -> list[ReplicaClient]:
        return [self.spawn(slot) for slot in range(self.n_workers)]

    def client(self, slot: int) -> ReplicaClient:
        return self._clients[slot]

    def proc(self, slot: int) -> subprocess.Popen:
        return self._procs[slot]

    def log_tail(self, slot: int, lines: int = 5) -> str:
        try:
            with open(self._logs[slot]) as f:
                return " | ".join(f.read().strip().splitlines()[-lines:])
        except (KeyError, OSError):  # never-spawned slot / unreadable log
            return "<no log>"

    # -- liveness --------------------------------------------------------

    def _heartbeat_stale(self, slot: int) -> bool:
        judge = self._hb_judge.get(slot)
        return judge is not None and judge.stale()

    def poll(self) -> list[int]:
        """One supervision pass: slots whose worker exited, plus slots
        whose heartbeat went stale (those are SIGKILL'd first — a wedged
        worker already ignored its chance to exit). Returns the slots that
        now need ``respawn()``.

        Healthy uptime also HEALS the respawn budget here: every
        ``respawn_heal_s`` of alive-and-heartbeating time decays the
        slot's ``_respawn_count`` by one, so occasional preemptions over a
        long fleet lifetime never accumulate into ``max_respawns``
        exhaustion. A crash-looping worker never lives that long — its
        budget still runs out."""
        bad = []
        for slot, proc in list(self._procs.items()):
            if proc.poll() is not None:
                bad.append(slot)
            elif self._heartbeat_stale(slot):
                logger.warning(
                    "serving supervisor: slot %d heartbeat stale >%.1fs — "
                    "SIGKILL", slot, self.transport.heartbeat_timeout_s)
                proc.kill()
                proc.wait()
                bad.append(slot)
            elif self.respawn_heal_s > 0 and self._respawn_count[slot] > 0:
                anchor = self._heal_anchor.get(slot, self._now())
                while (self._respawn_count[slot] > 0
                       and self._now() - anchor >= self.respawn_heal_s):
                    self._respawn_count[slot] -= 1
                    anchor += self.respawn_heal_s
                    logger.info(
                        "serving supervisor: slot %d respawn budget healed "
                        "to %d after sustained health", slot,
                        self._respawn_count[slot])
                self._heal_anchor[slot] = anchor
        return bad

    def respawn(self, slot: int) -> ReplicaClient:
        """Replace a dead/hung worker: pay the bounded-backoff delay for
        this slot's respawn count, then spawn a fresh generation. Raises
        once ``max_respawns`` for the slot is exhausted (a crash-looping
        spec must surface, not spin)."""
        self._respawn_count[slot] += 1
        if self._respawn_count[slot] > self.max_respawns:
            raise RuntimeError(
                f"serving worker slot {slot} exhausted its respawn budget "
                f"({self.max_respawns}); last log: {self.log_tail(slot)}")
        proc = self._procs.get(slot)
        if proc is not None and proc.poll() is None:
            proc.kill()
            proc.wait()
        old = self._clients.pop(slot, None)
        if old is not None:
            old.close()
        delay = backoff_delay(self._respawn_count[slot], self.respawn_backoff,
                              seed=self.seed * 7919 + slot)
        if delay > 0:
            time.sleep(delay)
        self._gen[slot] += 1
        self.respawns += 1
        return self.spawn(slot)

    def kill(self, slot: int, sig: int = signal.SIGKILL) -> None:
        """Deliver ``sig`` to the slot's worker (the chaos drill's kill -9)."""
        os.kill(self._procs[slot].pid, sig)

    def retire(self, slot: int, timeout: float = 30.0) -> None:
        """Permanently remove ``slot`` from supervision — the autoscaler's
        scale-down path (its replica has drained; nothing is in flight).
        SIGTERM gives a live worker its drain-then-exit-0 path; a corpse
        is simply reaped. The slot never appears in later ``poll()``s and
        is never respawned (``spawn(slot)`` would start a fresh
        generation if the fleet grows again)."""
        proc = self._procs.pop(slot, None)
        client = self._clients.pop(slot, None)
        self._hb_judge.pop(slot, None)
        self._hb_path.pop(slot, None)
        self._heal_anchor.pop(slot, None)
        self._remove_pidfile(slot)
        if client is not None:
            client.close()
        if proc is None:
            return
        if proc.poll() is None:
            try:
                os.kill(proc.pid, signal.SIGTERM)
            except OSError:
                pass
            try:
                proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
        logger.info("serving supervisor: slot %d retired (rc=%s)",
                    slot, proc.returncode)

    def shutdown(self, sig: int = signal.SIGTERM, timeout: float = 10.0) -> None:
        # snapshot: a background retire (rolling upgrade) may pop slots
        # concurrently, and dict iteration must not race it
        for slot, proc in list(self._procs.items()):
            if proc.poll() is None:
                try:
                    os.kill(proc.pid, sig)
                except OSError:
                    pass
        deadline = time.monotonic() + timeout
        for proc in list(self._procs.values()):
            remaining = max(0.1, deadline - time.monotonic())
            try:
                proc.wait(timeout=remaining)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
        for client in self._clients.values():
            client.close()
        self._clients.clear()
        for slot in list(self._procs):
            # the workers are down: their adoption records are stale now
            self._remove_pidfile(slot)


if __name__ == "__main__":
    sys.exit(main())
