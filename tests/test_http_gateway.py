"""HTTP/SSE gateway (launcher/http_gateway.py) + rolling upgrades
(Router.rolling_upgrade).

The contract under test: the fleet's degradation machinery is reachable
from a socket with correct HTTP semantics (typed rejections → distinct
status codes + Retry-After), a vanished or stalled reader frees its slot
(disconnect → ``Router.cancel``), SIGTERM stops accepting but finishes
in-flight streams, and a rolling upgrade replaces every replica
generation with zero accepted-request loss — aborting (old generation
keeps serving) when the newcomer cannot prove a healthy non-compiling
step.

Speed discipline: the gateway's HTTP/SSE/status/drain behavior is pure
host code, so most tests drive it over a ``_FakeRouter`` (milliseconds
each, no device work). The upgrade state machine runs over host-only
``_FakeEngine`` scheduler surfaces behind a REAL Router. Exactly ONE test
builds real engines — on the session ``tiny_serving_engine`` shapes
(n_slots 2, the [5, 11, 23]/max_new-8 parity set test_serving cached), so
it adds no new XLA programs. The multi-process TCP gateway drill is
``drills.py --gateway-chaos``; its in-tree sibling here is the slow-tier
``test_gateway_over_worker_process`` (warm sibling: the real-engine
integration below).
"""

import json
import socket
import struct
import time

import numpy as np
import pytest

from deepspeed_tpu.inference import Router
from deepspeed_tpu.inference.serving import RequestResult
from deepspeed_tpu.launcher.http_gateway import HttpGateway
from deepspeed_tpu.resilience import RequestRejected
from deepspeed_tpu.telemetry import Telemetry, request_timeline
from deepspeed_tpu.telemetry.request_trace import sort_timeline


# ---------------------------------------------------------------- fakes


class _FakeRouter:
    """Host-only Router surface: everything the gateway reads. ``plan``
    maps uid -> token list; ``step()`` reveals one more planned token per
    call (paced by ``pace_s`` of wall time when set, so a stream can be
    caught mid-flight)."""

    def __init__(self, pace_s=0.0):
        self.telemetry = Telemetry()
        self._epoch = time.perf_counter()
        self._owner = {}
        self._results = {}
        self._revealed = {}
        self.plan = {}
        self.pace_s = pace_s
        self._last_emit = 0.0
        self.submitted = []
        self.cancelled = []
        self.reject_with = None
        self.brownout = False
        self._autoscaler = None
        self._idem = {}

    # -- surface ---------------------------------------------------------

    def now(self):
        return time.perf_counter() - self._epoch

    def submit(self, request, idempotency_key=None):
        if self.reject_with is not None:
            raise self.reject_with
        self.submitted.append(request)
        self._owner[request.uid] = 0
        self._revealed[request.uid] = 0
        self.plan.setdefault(request.uid, [7, 8, 9])
        if idempotency_key:
            self._idem[idempotency_key] = request.uid
        return request.uid

    def idempotency_lookup(self, key):
        return self._idem.get(key)

    def idempotency_map(self):
        return dict(self._idem)

    def cancel(self, uid):
        if uid not in self._owner:
            return False
        del self._owner[uid]
        self._finish(uid, "cancelled", self._revealed.get(uid, 0))
        self.cancelled.append(uid)
        return True

    def _finish(self, uid, status, n):
        self._results[uid] = RequestResult(
            uid=uid, tokens=np.asarray(self.plan.get(uid, [])[:n], np.int32),
            prompt_len=3, arrival_time=0.0, status=status,
            finish_time=self.now())

    def step(self, now=None, enforce_deadlines=True):
        if self.pace_s and time.perf_counter() - self._last_emit < self.pace_s:
            return []
        self._last_emit = time.perf_counter()
        terminal = []
        for uid in list(self._owner):
            n = self._revealed[uid] = self._revealed[uid] + 1
            if n >= len(self.plan[uid]):
                del self._owner[uid]
                self._finish(uid, "ok", len(self.plan[uid]))
                terminal.append(uid)
        return terminal

    def partial_result(self, uid):
        res = self._results.get(uid)
        if res is not None:
            return np.asarray(res.tokens, np.int32), res
        if uid not in self._owner:
            return None
        toks = self.plan[uid][:self._revealed[uid]]
        return np.asarray(toks, np.int32), None

    def result(self, uid):
        return self._results.get(uid)

    def replica_states(self):
        return {0: "healthy"}

    def telemetry_snapshot(self):
        return {"router": {"metrics": self.telemetry.registry.snapshot(),
                           "request_trace": []},
                "replicas": {}}


class _FakeAutoscaler:
    def __init__(self, cooldown_s):
        from deepspeed_tpu.runtime.config import AutoscaleConfig

        self.cfg = AutoscaleConfig(cooldown_s=cooldown_s)


# ---------------------------------------------------------- http helpers


def _gw(request, router, cfg=None, **kw):
    gw = HttpGateway(router, {"stream_poll_s": 0.005,
                              "shutdown_grace_s": 5.0, **(cfg or {})}, **kw)
    gw.start()
    request.addfinalizer(lambda: (gw.trigger_shutdown(), gw.close()))
    deadline = time.monotonic() + 5.0
    while gw.port == 0 and time.monotonic() < deadline:
        time.sleep(0.01)
    return gw


def _post(gw, body, headers=None, raw_body=None):
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", gw.port, timeout=30)
    payload = raw_body if raw_body is not None else json.dumps(body)
    conn.request("POST", "/v1/generate", body=payload,
                 headers=headers or {})
    resp = conn.getresponse()
    out = {"status": resp.status,
           "retry_after": resp.getheader("Retry-After"),
           "uid": resp.getheader("X-DSTPU-Uid")}
    if resp.getheader("Content-Type", "").startswith("application/json"):
        out["json"] = json.loads(resp.read())
        conn.close()
    else:
        out["resp"], out["conn"] = resp, conn
    return out


def _read_sse(resp, conn, until_done=True):
    """Parse SSE blocks off an open http.client response."""
    events, buf = [], b""
    while True:
        chunk = resp.read1(65536) if hasattr(resp, "read1") else resp.read(1)
        if not chunk:
            break
        buf += chunk
        while b"\n\n" in buf:
            block, buf = buf.split(b"\n\n", 1)
            ev = {}
            for line in block.splitlines():
                if line.startswith(b"event: "):
                    ev["event"] = line[7:].decode()
                elif line.startswith(b"data: "):
                    ev["data"] = json.loads(line[6:])
                elif line.startswith(b"id: "):
                    ev["id"] = int(line[4:])
            if ev:
                events.append(ev)
        if until_done and any(e.get("event") == "done" for e in events):
            break
    conn.close()
    return events


def _sse_socket(gw, body_dict, timeout=30.0):
    """Raw-socket POST: returns (sock, header_bytes) with the socket still
    open on the SSE stream — the disconnect tests need to RST it."""
    body = json.dumps(body_dict).encode()
    req = (b"POST /v1/generate HTTP/1.1\r\nHost: t\r\n"
           b"Content-Length: %d\r\n\r\n" % len(body)) + body
    s = socket.create_connection(("127.0.0.1", gw.port), timeout=timeout)
    s.sendall(req)
    data = b""
    while b"\r\n\r\n" not in data:
        data += s.recv(4096)
    return s, data


def _rst_close(s):
    s.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                 struct.pack("ii", 1, 0))
    s.close()


# ------------------------------------------------------- status mapping


@pytest.mark.parametrize("reason,status", [
    ("queue_full", 429),
    ("overloaded", 429),
    ("no_healthy_replicas", 503),
])
def test_typed_rejections_map_to_status_codes(request, reason, status):
    router = _FakeRouter()
    router.reject_with = RequestRejected(1, reason, "synthetic overload")
    gw = _gw(request, router)
    out = _post(gw, {"prompt": [1, 2, 3]})
    assert out["status"] == status
    assert out["json"]["reason"] == reason
    # 429/503 always hint when to come back; no autoscaler -> 1s floor
    assert out["retry_after"] == "1"
    counters = router.telemetry.registry.snapshot()["counters"]
    assert counters["gateway/rejected"] == 1


def test_retry_after_derives_from_autoscaler_cooldown(request):
    router = _FakeRouter()
    router._autoscaler = _FakeAutoscaler(cooldown_s=7.0)
    router.reject_with = RequestRejected(1, "queue_full", "full")
    gw = _gw(request, router)
    assert _post(gw, {"prompt": [1]})["retry_after"] == "7"
    # an explicit config wins over the derivation
    gw2 = _gw(request, router, cfg={"retry_after_s": 3.0})
    assert _post(gw2, {"prompt": [1]})["retry_after"] == "3"


def test_bad_requests_are_400_not_429(request):
    router = _FakeRouter()
    gw = _gw(request, router)
    # malformed JSON
    assert _post(gw, None, raw_body="{nope")["status"] == 400
    # missing/empty/typed-wrong prompt
    assert _post(gw, {})["status"] == 400
    assert _post(gw, {"prompt": []})["status"] == 400
    assert _post(gw, {"prompt": "abc"})["status"] == 400
    # malformed priority header
    out = _post(gw, {"prompt": [1]}, headers={"X-DSTPU-Priority": "high"})
    assert out["status"] == 400
    # an unservable request (engine budget ValueError) is the client's
    # fault: 400, never a back-off hint
    router.reject_with = ValueError("prompt + max_new_tokens exceeds budget")
    assert _post(gw, {"prompt": [1, 2]})["status"] == 400
    # unknown path / oversized body
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", gw.port, timeout=10)
    conn.request("POST", "/v1/elsewhere", body="{}")
    assert conn.getresponse().status == 404
    router.reject_with = None
    gw3 = _gw(request, _FakeRouter(), cfg={"max_body_bytes": 64})
    big = {"prompt": list(range(200))}
    assert _post(gw3, big)["status"] == 413
    assert router.submitted == []  # nothing malformed ever reached submit


def test_priority_and_deadline_headers_map_onto_request(request):
    router = _FakeRouter()
    gw = _gw(request, router)
    out = _post(gw, {"prompt": [1, 2], "max_new_tokens": 2,
                     "temperature": 0.5, "top_k": 3, "eos_token": 9},
                headers={"X-DSTPU-Priority": "2",
                         "X-DSTPU-Deadline-S": "1.5"})
    _read_sse(out["resp"], out["conn"])
    req = router.submitted[0]
    assert req.priority == 2 and req.deadline_s == 1.5
    assert req.max_new_tokens == 2 and req.temperature == 0.5
    assert req.top_k == 3 and req.eos_token == 9
    assert int(out["uid"]) == req.uid


# ------------------------------------------------------------- streaming


def test_sse_stream_framing_and_done_event(request):
    router = _FakeRouter()
    gw = _gw(request, router)
    out = _post(gw, {"prompt": [1, 2, 3]})
    assert out["status"] == 200
    events = _read_sse(out["resp"], out["conn"])
    toks = [e["data"]["token"] for e in events if e["event"] == "token"]
    assert toks == [7, 8, 9]
    assert [e["data"]["i"] for e in events
            if e["event"] == "token"] == [0, 1, 2]
    done = [e for e in events if e["event"] == "done"]
    assert len(done) == 1
    assert done[0]["data"]["status"] == "ok"
    assert done[0]["data"]["tokens"] == [7, 8, 9]
    # the handler thread increments streams_done AFTER writing the done
    # frame, so the client can observe the frame first — poll briefly
    deadline = time.time() + 5.0
    while time.time() < deadline:
        counters = router.telemetry.registry.snapshot()["counters"]
        if "gateway/streams_done" in counters:
            break
        time.sleep(0.01)
    assert counters["gateway/streams_done"] == 1


def test_blocking_mode_returns_one_json_document(request):
    router = _FakeRouter()
    gw = _gw(request, router)
    out = _post(gw, {"prompt": [1, 2, 3], "stream": False})
    assert out["status"] == 200
    assert out["json"]["status"] == "ok" and out["json"]["tokens"] == [7, 8, 9]


def test_client_disconnect_mid_stream_cancels(request):
    router = _FakeRouter(pace_s=0.05)  # slow stream: catch it mid-flight
    router.plan[1] = list(range(40))
    gw = _gw(request, router)
    s, _ = _sse_socket(gw, {"prompt": [1, 2, 3]})
    buf = b""
    while buf.count(b"event: token") < 2:
        buf += s.recv(4096)
    _rst_close(s)  # the reader vanishes with an RST mid-stream
    deadline = time.monotonic() + 10
    while not router.cancelled and time.monotonic() < deadline:
        time.sleep(0.01)
    assert router.cancelled == [1]
    assert router.result(1).status == "cancelled"
    counters = router.telemetry.registry.snapshot()["counters"]
    assert counters["gateway/disconnects"] == 1
    assert counters["gateway/cancelled_on_disconnect"] == 1
    # the gateway-side stream record is gone (no leaked feeds)
    deadline = time.monotonic() + 5
    while gw._streams and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not gw._streams


def test_injected_disconnect_and_stall_sites(request):
    """The seeded fault sites land in the SAME containment path a real
    transport error takes: cancel fleet-side, slot freed, counters."""
    router = _FakeRouter(pace_s=0.05)  # keep requests live past injection
    router.plan[1] = list(range(12))
    router.plan[2] = list(range(12))
    gw = _gw(request, router, fault_injection={
        "enabled": True, "seed": 0,
        "gateway_disconnect_at": [[1, 3]],  # uid 1 after token 3
        "gateway_stall_at": [[2, 2]],       # uid 2 after token 2
    })
    out1 = _post(gw, {"prompt": [1]})
    events = _read_sse(out1["resp"], out1["conn"], until_done=False)
    out2 = _post(gw, {"prompt": [2]})
    events2 = _read_sse(out2["resp"], out2["conn"], until_done=False)
    deadline = time.monotonic() + 10
    while len(router.cancelled) < 2 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert sorted(router.cancelled) == [1, 2]
    # the injected disconnect cut the stream after its Nth token
    assert len([e for e in events if e.get("event") == "token"]) == 3
    assert len([e for e in events2 if e.get("event") == "token"]) == 2
    counters = router.telemetry.registry.snapshot()["counters"]
    assert counters["gateway/disconnects"] == 2
    assert counters["gateway/stalls"] == 1
    assert counters["gateway/injected_faults"] == 2


# ------------------------------------------------------ SIGTERM drain


def test_sigterm_drain_finishes_streams_rejects_new(request):
    router = _FakeRouter(pace_s=0.03)
    router.plan[1] = list(range(20))
    gw = _gw(request, router)
    out = _post(gw, {"prompt": [1, 2, 3]})
    # catch the stream mid-flight, then deliver the "SIGTERM"
    time.sleep(0.15)
    gw.trigger_shutdown()
    # new work is refused with the typed shutting_down 503 + Retry-After
    rej = _post(gw, {"prompt": [9, 9]})
    assert rej["status"] == 503 and rej["json"]["reason"] == "shutting_down"
    assert rej["retry_after"] == "1"
    # the in-flight stream still finishes (drain, not abort)
    events = _read_sse(out["resp"], out["conn"])
    done = [e for e in events if e["event"] == "done"]
    assert done and done[0]["data"]["status"] == "ok"
    assert done[0]["data"]["tokens"] == list(range(20))
    # the loop exits once drained
    gw._loop_thread.join(timeout=10)
    assert not gw._loop_thread.is_alive()
    status, body = gw.healthz()
    assert status == 503 and body["status"] == "draining"


def test_concurrent_close_is_race_free(request):
    """Regression (dstpu-audit ``thread-race`` on ``_http_thread``): the
    serve loop's exit path and an external ``close()`` may both tear the
    gateway down; the old check-then-join could read a handle the other
    caller just nulled (``None.join`` AttributeError). ``close()`` now
    CLAIMS the handle atomically under the gateway lock, so any number of
    concurrent closers is safe and idempotent."""
    import threading

    router = _FakeRouter()
    gw = _gw(request, router)
    gw.trigger_shutdown()  # the loop's own finally will also call close()
    errors = []

    def closer():
        try:
            gw.close()
        except Exception as e:  # noqa: BLE001 — the regression IS the raise
            errors.append(e)

    threads = [threading.Thread(target=closer) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=15)
    assert not errors, errors
    assert gw._http_thread is None


def test_open_streams_gauge_snapshot_taken_under_lock(request):
    """Regression (dstpu-audit ``thread-race`` on ``_streams``): the
    open-streams gauge used to be set from ``len(self._streams)`` AFTER
    releasing the lock — a concurrent insert could publish a stale count.
    The count is now snapshotted inside the critical section that popped
    the stream."""
    from deepspeed_tpu.launcher.http_gateway import _Stream

    router = _FakeRouter()
    gw = _gw(request, router)
    with gw._lock:
        gw._streams[101] = _Stream(101)
        gw._streams[102] = _Stream(102)
    gw._close_stream(101)
    assert gw.telemetry.gauge("gateway/open_streams").value == 1
    gw._close_stream(102)
    assert gw.telemetry.gauge("gateway/open_streams").value == 0


def test_healthz_and_metrics_endpoints(request):
    import http.client

    router = _FakeRouter()
    gw = _gw(request, router)
    conn = http.client.HTTPConnection("127.0.0.1", gw.port, timeout=10)
    conn.request("GET", "/healthz")
    resp = conn.getresponse()
    body = json.loads(resp.read())
    assert resp.status == 200 and body["status"] == "ok"
    assert body["healthy_replicas"] == 1 and body["brownout"] is False
    conn.request("GET", "/metrics")
    resp = conn.getresponse()
    text = resp.read().decode()
    assert resp.status == 200 and "gateway" in text
    conn.request("GET", "/nope")
    assert conn.getresponse().status == 404


# ------------------------------------------------- gateway trace events


def test_gateway_stage_events_merge_in_timeline_order(request):
    router = _FakeRouter(pace_s=0.02)
    router.plan[1] = list(range(10))
    gw = _gw(request, router)
    s, _ = _sse_socket(gw, {"prompt": [4, 5, 6]})
    buf = b""
    while buf.count(b"event: token") < 2:
        buf += s.recv(4096)
    _rst_close(s)
    deadline = time.monotonic() + 10
    while not router.cancelled and time.monotonic() < deadline:
        time.sleep(0.01)
    snap = gw.telemetry_snapshot()
    gw_events = snap["gateway"]["request_trace"]
    kinds = [e["event"] for e in gw_events]
    assert kinds == ["http_accepted", "stream_started",
                     "client_disconnected"]
    assert all(e["replica_id"] == "gateway0" for e in gw_events)
    # merged with engine-side events, the gateway stages interleave at
    # their documented ranks: accept before arrival, stream_started after
    # first_token, client_disconnected before the cancel's terminal
    t_acc = gw_events[0]["t"]
    engine_events = [
        {"uid": 1, "event": "arrived", "t": t_acc},
        {"uid": 1, "event": "admitted", "t": t_acc + 1e-4},
        {"uid": 1, "event": "first_token",
         "t": gw_events[1]["t"] - 1e-6},
        {"uid": 1, "event": "terminal", "t": gw_events[2]["t"],
         "status": "cancelled"},
    ]
    tl = request_timeline({"request_trace": engine_events, "gateway":
                           {"request_trace": gw_events}}, 1)
    order = [e["event"] for e in tl]
    assert order == ["http_accepted", "arrived", "admitted", "first_token",
                     "stream_started", "client_disconnected", "terminal"]
    # stream_done outranks terminal at an equal clock
    done_tl = sort_timeline([
        {"uid": 2, "event": "stream_done", "t": 5.0},
        {"uid": 2, "event": "terminal", "t": 5.0},
    ])
    assert [e["event"] for e in done_tl] == ["terminal", "stream_done"]


# ----------------------------------------------- rolling upgrade (fakes)


class _FakeResult:
    """Just enough RequestResult surface for the canary gate (ok/status/
    tokens) without pulling the serving dataclass into a host-only fake."""

    def __init__(self, uid, status="ok"):
        self.uid = uid
        self.status = status
        self.tokens = [1, 2]

    @property
    def ok(self):
        return self.status == "ok"


class _FakeEngine:
    """Host-only scheduler surface behind a REAL Router (the
    test_autoscaler idiom, plus ``partial_tokens``). ``serves=True``
    (default) makes ``step`` finish each queued request after one step —
    enough to pass the rolling upgrade's per-wave canary generate;
    ``serves=False`` models a newcomer that boots and steps clean but can
    never actually serve (the idle-step-gate hole the canary closes)."""

    def __init__(self, rid=0, compiled=False, serves=True):
        self.replica_id = rid
        self.queued = []
        self.last_step_compiled = compiled
        self.fail_next_step = False
        self.serves = serves
        self.results = {}
        self._aged = []

    def submit(self, req):
        self.queued.append(req)
        return req.uid

    def requeue(self, req):
        return self.submit(req)

    def withdraw(self, uid):
        for i, r in enumerate(self.queued):
            if r.uid == uid:
                return self.queued.pop(i)
        return None

    def cancel(self, uid):
        # faithful to the real engine: a cancel frees the queued request
        n = len(self.queued) + len(self._aged)
        self.queued = [r for r in self.queued if r.uid != uid]
        self._aged = [r for r in self._aged if r.uid != uid]
        if len(self.queued) + len(self._aged) == n:
            return False
        self.results[uid] = _FakeResult(uid, status="cancelled")
        return True

    def result(self, uid):
        return self.results.get(uid)

    def partial_tokens(self, uid):
        return np.zeros((0,), np.int32)

    def step(self, now=None, enforce_deadlines=True):
        if self.fail_next_step:
            self.fail_next_step = False
            raise OSError("fake worker gone")
        if not self.serves:
            return []
        done = [r.uid for r in self._aged]
        for r in self._aged:
            self.results[r.uid] = _FakeResult(r.uid)
        self._aged = list(self.queued)  # served on the NEXT step
        self.queued = []
        return done

    def live_requests(self):
        return list(self.queued)

    def arrived_queue_len(self, now=None):
        return len(self.queued)

    def prefix_match_len(self, prompt):
        return 0

    def pending_arrival_times(self):
        return []

    def set_epoch(self, epoch):
        pass

    def telemetry_snapshot(self):
        return {"replica_id": self.replica_id}

    @property
    def load(self):
        return len(self.queued)

    @property
    def idle(self):
        return not self.queued

    @property
    def queue_len(self):
        return len(self.queued)


class _FakeSupervisor:
    def __init__(self, fail_slots=(), compiled_slots=()):
        self.fail_slots = set(fail_slots)
        self.compiled_slots = set(compiled_slots)
        self.spawned = []
        self.retired = []
        self.spec = None

    def set_spec(self, spec):
        self.spec = spec

    def poll(self):
        return []

    def spawn(self, slot):
        if slot in self.fail_slots:
            raise RuntimeError(f"boot of slot {slot} failed")
        e = _FakeEngine(200 + slot, compiled=slot in self.compiled_slots)
        self.spawned.append((slot, e))
        return e

    def retire(self, slot):
        self.retired.append(slot)


def _await(cond, timeout=5.0):
    """Poll a condition (background retire threads need real time)."""
    deadline = time.monotonic() + timeout
    while not cond() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert cond()


def _drive(router, n=50, dt=0.25, t0=10.0):
    for k in range(n):
        router.step(now=t0 + k * dt)
        st = router.upgrade_status()
        if st is not None and st["state"] != "running":
            # keep stepping a little so drains settle
            for j in range(4):
                router.step(now=t0 + (n + j) * dt)
            return st
        time.sleep(0.005)  # background boot threads need real time
    return router.upgrade_status()


def test_rolling_upgrade_replaces_every_generation():
    engines = [_FakeEngine(0), _FakeEngine(1)]
    router = Router(replica_engines=engines,
                    config={"router": {"health": {"timeout": 0}}})
    sup = _FakeSupervisor()
    router.rolling_upgrade(supervisor=sup, slots={0: 0, 1: 1},
                           spec={"generation": 2})
    assert sup.spec == {"generation": 2}  # installed BEFORE the first boot
    st = _drive(router)
    assert st["state"] == "done"
    assert [w["outcome"] for w in st["waves"]] == ["upgraded", "upgraded"]
    # old generations drained + their worker slots retired; newcomers live
    states = router.replica_states()
    assert states[0] == "drained" and states[1] == "drained"
    assert states[2] == "healthy" and states[3] == "healthy"
    _await(lambda: sorted(sup.retired) == [0, 1])
    assert [s for s, _ in sup.spawned] == [2, 3]  # fresh slots per wave
    assert st["slots"] == {2: 2, 3: 3}
    counters = router.telemetry.registry.snapshot()["counters"]
    assert counters["router/upgrade_waves"] == 2
    assert counters.get("router/upgrade_aborts", 0) == 0


def test_upgrade_syncs_the_autoscaler_slot_ledger():
    """A bound Autoscaler owns the same slot namespace: after an upgrade
    its rid->slot ledger must hold the NEW generation (a stale ledger
    would make a later scale-up spawn onto a live worker's slot and a
    scale-down retirement silently no-op)."""
    from deepspeed_tpu.inference import Autoscaler

    engines = [_FakeEngine(0), _FakeEngine(1)]
    router = Router(replica_engines=engines,
                    config={"router": {"health": {"timeout": 0}}})
    sup = _FakeSupervisor()
    asc = Autoscaler(router, {"enabled": True, "min_replicas": 1,
                              "max_replicas": 4},
                     supervisor=sup, slots={0: 0, 1: 1})
    router.rolling_upgrade(supervisor=sup, slots=dict(asc._slots))
    st = _drive(router)
    assert st["state"] == "done"
    # the autoscaler's ledger followed every wave: old rids gone, new
    # rids mapped to their fresh slots, and the slot sequence advanced
    # past them (no future spawn can collide)
    assert asc._slots == {2: 2, 3: 3}
    assert asc._slot_seq >= 4


def test_upgrade_aborts_on_boot_failure_old_keeps_serving():
    engines = [_FakeEngine(0), _FakeEngine(1)]
    router = Router(replica_engines=engines,
                    config={"router": {"health": {"timeout": 0}}})
    sup = _FakeSupervisor(fail_slots={2})
    router.rolling_upgrade(supervisor=sup, slots={0: 0, 1: 1})
    st = _drive(router)
    assert st["state"] == "aborted" and "boot failed" in st["reason"]
    # the OLD generation is untouched and still accepting
    assert router.replica_states() == {0: "healthy", 1: "healthy"}
    counters = router.telemetry.registry.snapshot()["counters"]
    assert counters["router/upgrade_aborts"] == 1
    assert counters.get("router/upgrade_waves", 0) == 0


def test_upgrade_aborts_when_newcomer_dies_before_proving():
    engines = [_FakeEngine(0), _FakeEngine(1)]
    router = Router(replica_engines=engines,
                    config={"router": {"health": {"timeout": 0}}})

    class _DyingSupervisor(_FakeSupervisor):
        def spawn(self, slot):
            e = _FakeEngine(200 + slot)
            e.fail_next_step = True  # dies on its FIRST step
            self.spawned.append((slot, e))
            return e

    sup = _DyingSupervisor()
    router.rolling_upgrade(supervisor=sup, slots={0: 0, 1: 1})
    st = _drive(router)
    assert st["state"] == "aborted" and "died" in st["reason"]
    assert router.replica_states()[0] == "healthy"
    assert router.replica_states()[1] == "healthy"
    # the dead newcomer's slot was reaped
    _await(lambda: sup.retired == [2])


def test_upgrade_gate_times_out_on_compiling_forever_newcomer():
    """A newcomer whose every step pays a compile never proves itself:
    the gate must time out and abort (old generation keeps serving) —
    and the attached-but-unproven newcomer is DRAINED, not stranded."""
    engines = [_FakeEngine(0)]
    router = Router(replica_engines=engines,
                    config={"router": {"health": {"timeout": 0}}})
    sup = _FakeSupervisor(compiled_slots={1})
    router.rolling_upgrade(supervisor=sup, slots={0: 0}, gate_timeout_s=2.0)
    st = _drive(router, n=60, dt=0.25)
    assert st["state"] == "aborted" and "non-compiling" in st["reason"]
    states = router.replica_states()
    assert states[0] == "healthy"          # old generation serving
    assert states[1] in ("drained", "dead")  # newcomer cleanly out
    _await(lambda: sup.retired == [1])


def test_upgrade_canary_closes_the_idle_step_gate():
    """The hole the per-wave canary closes (PR 13's documented limit): a
    newcomer that boots and steps clean but can never SERVE passed the
    idle-step gate. With the canary (default on) it aborts — the old
    generation keeps serving; with ``canary=False`` the same newcomer
    sails through, which is exactly why the canary is the default."""

    class _NoServeSupervisor(_FakeSupervisor):
        def spawn(self, slot):
            e = _FakeEngine(200 + slot, serves=False)
            self.spawned.append((slot, e))
            return e

    router = Router(replica_engines=[_FakeEngine(0)],
                    config={"router": {"health": {"timeout": 0}}})
    sup = _NoServeSupervisor()
    router.rolling_upgrade(supervisor=sup, slots={0: 0}, gate_timeout_s=2.0)
    st = _drive(router, n=60)
    assert st["state"] == "aborted" and "canary" in st["reason"]
    assert router.replica_states()[0] == "healthy"  # old keeps serving
    # the SAME cannot-serve newcomer passes the legacy idle-step-only gate
    router2 = Router(replica_engines=[_FakeEngine(0)],
                     config={"router": {"health": {"timeout": 0}}})
    sup2 = _NoServeSupervisor()
    router2.rolling_upgrade(supervisor=sup2, slots={0: 0},
                            gate_timeout_s=2.0, canary=False)
    assert _drive(router2)["state"] == "done"


def test_upgrade_canary_uid_band_is_reserved_and_untraced():
    """Canary generates live in the RESERVED uid band: never in the
    Router's user results, never recorded by any RequestTracer — they are
    infrastructure, not traffic."""
    from deepspeed_tpu.telemetry.request_trace import (RESERVED_UID_BASE,
                                                       RequestTracer)

    router = Router(replica_engines=[_FakeEngine(0)],
                    config={"router": {"health": {"timeout": 0}}})
    sup = _FakeSupervisor()
    router.rolling_upgrade(supervisor=sup, slots={0: 0})
    st = _drive(router)
    assert st["state"] == "done"
    (_, newcomer), = sup.spawned
    canary_uids = [u for u in newcomer.results if u >= RESERVED_UID_BASE]
    assert canary_uids, "the wave never served a canary"
    assert all(u < RESERVED_UID_BASE for u in router.results)
    assert st["waves"][0].get("canary_status") == "ok"
    # tracer band filter: a reserved uid is dropped at record time
    tr = RequestTracer(16)
    tr.record(RESERVED_UID_BASE + 1, "arrived")
    tr.record(5, "arrived")
    assert [e["uid"] for e in tr.events()] == [5]


def test_upgrade_canary_survives_a_long_lived_fleet_clock():
    """Deadlines are ABSOLUTE (arrival_time + deadline_s on the fleet
    clock), so a canary submitted with arrival_time=0.0 would already be
    expired on any fleet older than gate_timeout_s and every upgrade
    would spuriously abort. The canary must arrive at NOW on the fleet
    clock — this drives an upgrade on a fleet that has been up for ~10k
    seconds and asserts the canary rode the live clock."""

    class _RecordingSupervisor(_FakeSupervisor):
        def spawn(self, slot):
            e = _FakeEngine(300 + slot)
            submitted = []
            orig = e.submit

            def submit(req):
                submitted.append(req)
                return orig(req)

            e.submit = submit
            e.submitted = submitted
            self.spawned.append((slot, e))
            return e

    router = Router(replica_engines=[_FakeEngine(0)],
                    config={"router": {"health": {"timeout": 0}}})
    sup = _RecordingSupervisor()
    router.rolling_upgrade(supervisor=sup, slots={0: 0}, gate_timeout_s=5.0)
    st = _drive(router, t0=10_000.0)  # fleet clock ~10k s at upgrade time
    assert st["state"] == "done"
    (_, newcomer), = sup.spawned
    (canary,) = newcomer.submitted
    # arrived on the live fleet clock — deadline is gate_timeout_s from
    # SUBMISSION, not an absolute instant 10k seconds in the past
    assert canary.arrival_time >= 10_000.0
    assert canary.arrival_time + canary.deadline_s > 10_000.0


# ------------------------------------------- idempotency & stream resume


def test_idempotency_key_retry_never_forks_a_uid(request):
    router = _FakeRouter()
    gw = _gw(request, router)
    hdr = {"X-DSTPU-Idempotency-Key": "job-42"}
    first = _post(gw, {"prompt": [1, 2, 3], "stream": False}, headers=hdr)
    assert first["status"] == 200 and first["json"]["status"] == "ok"
    retry = _post(gw, {"prompt": [1, 2, 3], "stream": False}, headers=hdr)
    assert retry["json"]["uid"] == first["json"]["uid"]
    assert retry["json"]["tokens"] == first["json"]["tokens"] == [7, 8, 9]
    assert len(router.submitted) == 1, "a retried key forked a submit"
    counters = router.telemetry.registry.snapshot()["counters"]
    assert counters["gateway/idempotent_replays"] == 1


def test_idempotency_retry_race_single_submit(request):
    """Two concurrent POSTs with ONE key: the serve loop processes submits
    serially, so exactly one reaches the Router — both clients stream the
    same uid to the same terminal result."""
    import threading as _threading

    router = _FakeRouter(pace_s=0.02)
    router.plan[1] = list(range(12))
    gw = _gw(request, router)
    hdr = {"X-DSTPU-Idempotency-Key": "raced"}
    outs = {}

    def post(tag):
        out = _post(gw, {"prompt": [1, 2, 3]}, headers=hdr)
        outs[tag] = {"uid": out["uid"],
                     "events": _read_sse(out["resp"], out["conn"])}

    ts = [_threading.Thread(target=post, args=(k,)) for k in ("a", "b")]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30.0)
    assert len(router.submitted) == 1, "the race forked a submit"
    uids = {outs[k]["uid"] for k in outs}
    assert len(uids) == 1
    for k in outs:
        done = [e for e in outs[k]["events"] if e["event"] == "done"]
        assert done and done[0]["data"]["tokens"] == list(range(12))


def test_last_event_id_resumes_across_a_gateway_restart(request):
    """The session-resume contract without a journal: gateway 1 serves a
    keyed stream to completion and STOPS; gateway 2 over the same Router
    seeds its idempotency map from the fleet and a reconnect with
    ``Last-Event-ID`` replays exactly the suffix — one bitwise stream
    across two gateway processes' worth of state."""
    router = _FakeRouter()
    gw1 = _gw(request, router)
    out = _post(gw1, {"prompt": [1, 2, 3]},
                headers={"X-DSTPU-Idempotency-Key": "ride-out"})
    events = _read_sse(out["resp"], out["conn"])
    toks = [e for e in events if e["event"] == "token"]
    assert [e["id"] for e in toks] == [0, 1, 2]  # id: lines = resume cursor
    gw1.trigger_shutdown()
    gw1.stop()

    gw2 = _gw(request, router)
    out2 = _post(gw2, {"prompt": [1, 2, 3]},
                 headers={"X-DSTPU-Idempotency-Key": "ride-out",
                          "Last-Event-ID": "0"})
    events2 = _read_sse(out2["resp"], out2["conn"])
    toks2 = [e for e in events2 if e["event"] == "token"]
    assert [e["id"] for e in toks2] == [1, 2]  # resumed PAST the cursor
    assert [e["data"]["token"] for e in toks2] == [8, 9]
    done2 = [e for e in events2 if e["event"] == "done"][0]["data"]
    assert done2["tokens"] == [7, 8, 9]
    assert len(router.submitted) == 1
    counters = router.telemetry.registry.snapshot()["counters"]
    assert counters["gateway/resumed_streams"] == 1


def test_last_event_id_resume_parity_real_engine(request, tiny_serving_engine):
    """Satellite proof on REAL decode programs (session shapes, watchdog
    RAISE): a keyed stream completed through gateway 1 resumes through
    gateway 2 at ``Last-Event-ID`` with the exact greedy suffix — the
    concatenated client view is bit-identical to ``generate``."""
    engine = tiny_serving_engine
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 97, size=5).astype(np.int32)
    ref = [int(t) for t in engine.generate(prompt[None], max_new_tokens=8)[0]]
    router = Router(engine, config={
        "n_slots": 2, "max_seq_len": 128, "watchdog_mode": "raise",
        "router": {"replicas": 1, "health": {"timeout": 60.0}}})
    gw1 = _gw(request, router, cfg={"stream_poll_s": 0.01})
    hdr = {"X-DSTPU-Idempotency-Key": "parity"}
    out = _post(gw1, {"prompt": [int(t) for t in prompt],
                      "max_new_tokens": 8}, headers=hdr)
    events = _read_sse(out["resp"], out["conn"])
    got = [e["data"]["token"] for e in events if e["event"] == "token"]
    assert got == ref
    gw1.trigger_shutdown()
    gw1.stop()

    gw2 = _gw(request, router, cfg={"stream_poll_s": 0.01})
    out2 = _post(gw2, {"prompt": [int(t) for t in prompt],
                       "max_new_tokens": 8},
                 headers={**hdr, "Last-Event-ID": "2"})
    events2 = _read_sse(out2["resp"], out2["conn"])
    toks2 = [e for e in events2 if e["event"] == "token"]
    assert [e["id"] for e in toks2] == list(range(3, 8))
    assert got[:3] + [e["data"]["token"] for e in toks2] == ref
    done2 = [e for e in events2 if e["event"] == "done"][0]["data"]
    assert done2["status"] == "ok" and done2["tokens"] == ref
    # one submit ever, one decode program ever (raise-mode held)
    assert router._replicas[0].engine.compile_counts()["decode"] == 1
    counters = router.telemetry.registry.snapshot()["counters"]
    assert counters["gateway/resumed_streams"] == 1
    assert counters["gateway/idempotent_replays"] == 1


class _BurstRouter(_FakeRouter):
    """A ``_FakeRouter`` whose ``step()`` reveals WHOLE BURSTS — the
    gateway-side shape of speculative decoding, where one verify step
    accepts k>1 tokens at once. The schedule is a list of burst sizes
    applied in order to every stream."""

    def __init__(self, bursts, plan_tokens=None, **kw):
        super().__init__(**kw)
        self._bursts = list(bursts)
        self._burst_i = {}
        self._plan_tokens = plan_tokens

    def submit(self, request, idempotency_key=None):
        uid = super().submit(request, idempotency_key)
        if self._plan_tokens is not None:
            self.plan[uid] = list(self._plan_tokens)
        return uid

    def step(self, now=None, enforce_deadlines=True):
        terminal = []
        for uid in list(self._owner):
            i = self._burst_i.get(uid, 0)
            k = self._bursts[i] if i < len(self._bursts) else 1
            self._burst_i[uid] = i + 1
            n = self._revealed[uid] = min(
                self._revealed[uid] + k, len(self.plan[uid]))
            if n >= len(self.plan[uid]):
                del self._owner[uid]
                self._finish(uid, "ok", n)
                terminal.append(uid)
        return terminal


def test_speculative_burst_streams_one_event_per_token(request):
    """Satellite: a k-token accepted burst must still come out of the
    gateway as ONE SSE ``token`` event per token with monotone
    token-index ids — bursts change pacing, never framing."""
    router = _BurstRouter(bursts=[3, 1, 4], plan_tokens=range(40, 48))
    gw = _gw(request, router)
    out = _post(gw, {"prompt": [1, 2, 3]})
    events = _read_sse(out["resp"], out["conn"])
    toks = [e for e in events if e["event"] == "token"]
    assert [e["id"] for e in toks] == list(range(8))
    assert [e["data"]["token"] for e in toks] == list(range(40, 48))
    done = [e for e in events if e["event"] == "done"][0]["data"]
    assert done["tokens"] == list(range(40, 48))


def test_last_event_id_resumes_mid_burst(request):
    """Satellite: ``Last-Event-ID`` falling INSIDE an accepted burst
    still resumes bitwise-identically across a gateway restart — resume
    ids are token indices, not step indices, so burst boundaries are
    invisible to the client."""
    router = _BurstRouter(bursts=[3, 1, 4], plan_tokens=range(40, 48))
    gw1 = _gw(request, router)
    out = _post(gw1, {"prompt": [1, 2, 3]},
                headers={"X-DSTPU-Idempotency-Key": "burst"})
    events = _read_sse(out["resp"], out["conn"])
    got = [e["data"]["token"] for e in events if e["event"] == "token"]
    assert got == list(range(40, 48))
    gw1.trigger_shutdown()
    gw1.stop()

    # id 5 lands inside the third burst (boundaries after ids 2, 3, 7)
    gw2 = _gw(request, router)
    out2 = _post(gw2, {"prompt": [1, 2, 3]},
                 headers={"X-DSTPU-Idempotency-Key": "burst",
                          "Last-Event-ID": "5"})
    events2 = _read_sse(out2["resp"], out2["conn"])
    toks2 = [e for e in events2 if e["event"] == "token"]
    assert [e["id"] for e in toks2] == [6, 7]
    assert got[:6] + [e["data"]["token"] for e in toks2] == got
    done2 = [e for e in events2 if e["event"] == "done"][0]["data"]
    assert done2["tokens"] == got
    assert len(router.submitted) == 1  # replay, not re-submit


def test_supervisor_set_spec_is_durable(tmp_path):
    """``WorkerSupervisor.set_spec`` swaps the spec future spawns boot —
    written tmp+fsync+rename so a crash mid-upgrade can't tear it."""
    from deepspeed_tpu.launcher.serving_worker import WorkerSupervisor

    sup = WorkerSupervisor({"model": {"a": 1}}, 0,
                           workdir=str(tmp_path / "wd"))
    with open(sup.spec_path) as f:
        assert json.load(f) == {"model": {"a": 1}}
    sup.set_spec({"model": {"a": 2}, "generation": 2})
    with open(sup.spec_path) as f:
        assert json.load(f) == {"model": {"a": 2}, "generation": 2}


# ----------------------------------------- real-engine integration (ONE)


def test_gateway_real_engine_stream_parity_disconnect_and_upgrade(
        request, tiny_serving_engine):
    """THE real-engine integration, on session shapes only (test_serving's
    [5, 11, 23]/max_new-8 parity set, n_slots 2): HTTP-streamed greedy
    tokens are bit-identical to ``InferenceEngine.generate``, a reader
    that vanishes mid-stream frees its slot (occupancy back to 0), and an
    in-process rolling upgrade under live traffic loses nothing — all
    under watchdog RAISE (no new XLA programs)."""
    engine = tiny_serving_engine
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 97, size=s).astype(np.int32)
               for s in (5, 11, 23)]
    refs = [engine.generate(p[None], max_new_tokens=8)[0] for p in prompts]
    router = Router(engine, config={
        "n_slots": 2, "max_seq_len": 128, "watchdog_mode": "raise",
        "router": {"replicas": 2, "health": {"timeout": 60.0}}})
    gw = _gw(request, router, cfg={"stream_poll_s": 0.01})

    # two parity streams through real decode programs
    outs = [_post(gw, {"prompt": [int(t) for t in p], "max_new_tokens": 8})
            for p in prompts[:2]]
    for out, ref in zip(outs, refs[:2]):
        events = _read_sse(out["resp"], out["conn"])
        toks = [e["data"]["token"] for e in events if e["event"] == "token"]
        done = [e for e in events if e["event"] == "done"][0]["data"]
        assert done["status"] == "ok"
        assert toks == done["tokens"] == [int(t) for t in ref]

    # a rolling upgrade begins while the third request streams
    s, _head = _sse_socket(gw, {"prompt": [int(t) for t in prompts[2]],
                                "max_new_tokens": 8})
    router_states_before = dict(router.replica_states())
    router.rolling_upgrade()  # in-process: fresh replicas, same programs
    buf = b""
    deadline = time.monotonic() + 60
    while b"event: done" not in buf or not buf.endswith(b"\n\n"):
        assert time.monotonic() < deadline
        chunk = s.recv(4096)
        if not chunk:
            break
        buf += chunk
    s.close()
    done = [json.loads(line[6:]) for block in buf.split(b"\n\n")
            for line in block.splitlines()
            if b"event: done" in block and line.startswith(b"data: ")]
    assert done and done[0]["status"] == "ok"
    assert done[0]["tokens"] == [int(t) for t in refs[2]]

    # wait the upgrade out, then: new generation serving, zero loss
    deadline = time.monotonic() + 60
    while True:
        st = router.upgrade_status()
        if st["state"] != "running" and not any(
                v == "draining" for v in router.replica_states().values()):
            break
        assert time.monotonic() < deadline, st
        time.sleep(0.02)
    assert st["state"] == "done", st
    assert len(router_states_before) == 2
    states = router.replica_states()
    assert states[0] == "drained" and states[1] == "drained"
    assert sum(1 for v in states.values() if v == "healthy") == 2

    # disconnect mid-stream on the UPGRADED fleet: slot frees, cancel lands
    s2, _ = _sse_socket(gw, {"prompt": [int(t) for t in prompts[1]],
                             "max_new_tokens": 32})
    buf = b""
    while buf.count(b"event: token") < 2:
        buf += s2.recv(4096)
    _rst_close(s2)
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        live = [r for r in router._replicas if r.state == "healthy"]
        if (not router._owner
                and all(r.engine.n_active == 0 and r.engine.n_prefilling == 0
                        for r in live)):
            break
        time.sleep(0.02)
    live = [r for r in router._replicas if r.state == "healthy"]
    assert not router._owner
    for r in live:
        assert r.engine.n_active == 0 and r.engine.n_prefilling == 0
        assert r.engine.n_free == r.engine.n_slots
        # raise-mode held: ONE decode program, ever (a rookie that saw no
        # traffic yet has 0 — never 2)
        assert r.engine.compile_counts()["decode"] <= 1
    counters = router.telemetry.registry.snapshot()["counters"]
    assert counters["gateway/cancelled_on_disconnect"] >= 1


# ------------------------------------------------- slow-tier process drill


@pytest.mark.slow  # warm sibling: the real-engine integration above; the
#                    full TCP drill is drills.py --gateway-chaos
def test_gateway_over_worker_process(tmp_path):
    """ONE worker process behind the gateway over the real RPC transport:
    the step-piggybacked progress cache streams tokens with parity, and a
    mid-stream disconnect cancels across the process boundary."""
    from deepspeed_tpu.launcher.serving_worker import WorkerSupervisor

    spec = {"model": {"vocab_size": 97, "max_seq_len": 128, "num_layers": 2,
                      "num_heads": 4, "hidden_size": 32, "dtype": "float32",
                      "loss_chunk_size": 0, "decode_attn": "xla",
                      "pos_emb": "rotary"},
            "engine_dtype": "fp32",
            "serving": {"n_slots": 2, "max_seq_len": 128,
                        "watchdog_mode": "raise"}}
    import os

    sup = WorkerSupervisor(
        spec, 1, workdir=str(tmp_path / "wd"),
        transport={"call_timeout_s": 120.0, "boot_timeout_s": 300.0},
        # the session cache settings live in jax.config (invisible to a
        # subprocess) — exported or the worker cold-compiles every program
        env={"JAX_PLATFORMS": "cpu", "JAX_THREEFRY_PARTITIONABLE": "1",
             "JAX_COMPILATION_CACHE_DIR": os.path.join(
                 os.path.dirname(__file__), ".xla_cache")})
    try:
        clients = sup.start()
        router = Router(config={"router": {"replicas": 1,
                                           "health": {"timeout": 60.0}}},
                        replica_engines=clients)
        gw = HttpGateway(router, {"stream_poll_s": 0.01})
        gw.start()
        try:
            rng = np.random.default_rng(0)
            prompt = rng.integers(0, 97, size=11).astype(np.int32)
            out = _post(gw, {"prompt": [int(t) for t in prompt],
                             "max_new_tokens": 8})
            events = _read_sse(out["resp"], out["conn"])
            done = [e for e in events if e["event"] == "done"][0]["data"]
            assert done["status"] == "ok" and len(done["tokens"]) == 8
            toks = [e["data"]["token"] for e in events
                    if e["event"] == "token"]
            assert toks == done["tokens"]  # piggybacked progress = result
            s, _ = _sse_socket(gw, {"prompt": [int(t) for t in prompt],
                                    "max_new_tokens": 32})
            buf = b""
            while buf.count(b"event: token") < 2:
                buf += s.recv(4096)
            _rst_close(s)
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                if not router._owner:
                    break
                time.sleep(0.05)
            assert not router._owner
            # stop the loop BEFORE snapshotting: the RPC socket is owned
            # by the serve-loop thread (a concurrent call would desync it)
            gw.stop()
            snap = router.telemetry_snapshot()
            eng_counters = snap["replicas"][0]["metrics"]["counters"]
            assert eng_counters.get("resilience/cancelled", 0) >= 1
        finally:
            gw.trigger_shutdown()
            gw.close()
    finally:
        sup.shutdown()
