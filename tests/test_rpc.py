"""Serving RPC transport (inference/rpc.py) + the Router over remote
replicas.

The contract under test: the fleet guarantees PR 6 proved in-process
(exactly-once failover, terminal-uid completeness, greedy parity, drain)
hold when a replica sits behind the RPC transport — and the transport's
OWN failure modes (lost replies, resets, corrupt frames, deadlines) map
onto the Router's health machine instead of corrupting it.

Speed discipline: everything here is host-only or reuses the session
``tiny_serving_engine`` shapes (prompts [5, 11, 23], max_new 8, n_slots 2
— the test_serving parity set); remote replicas are REAL ServingEngines
hosted by an ``RpcServer`` in a background thread, so no new XLA programs
and no process boots. Real worker processes are covered by
tests/test_serving_worker.py and the ``drills.py --chaos-serving`` drill.
"""

import os
import socket
import struct
import threading
import time
import zlib

import numpy as np
import pytest

from deepspeed_tpu.inference.rpc import (ReplicaClient, RpcServer,
                                         decode_request, decode_result,
                                         encode_request, encode_result,
                                         recv_frame, send_frame)
from deepspeed_tpu.resilience import (FaultInjector, RpcConnectionLost,
                                      RpcGarbledFrame, RpcTimeout)
from deepspeed_tpu.runtime.config import RouterTransportConfig

# short per-call deadlines keep a real transport wedge from eating the
# suite budget; generous enough for a loaded CI box stepping a tiny model
TRANSPORT = dict(call_timeout_s=60.0, connect_attempts=2,
                 base_delay_s=0.05, max_delay_s=0.1, jitter=0.0)

# the replay-safety / garble-detection / kill-failover proofs run over
# BOTH address families: the TCP transport must honor the exact same
# frame + verdict contract as the PR 8 unix sockets
FAMILIES = ["unix", "tcp"]


def _sock_pair(family):
    """A connected stream pair of the given family (socketpair is always
    AF_UNIX; TCP builds a real loopback connection)."""
    if family == "unix":
        return socket.socketpair()
    lst = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    lst.settimeout(5.0)
    lst.bind(("127.0.0.1", 0))
    lst.listen(1)
    a = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    a.settimeout(5.0)
    a.connect(lst.getsockname())
    b, _ = lst.accept()
    b.settimeout(5.0)
    lst.close()
    return a, b


def _worker_addr(tmp_path, name, family):
    """The bind address a thread-hosted worker uses for ``family``."""
    if family == "tcp":
        return "tcp://127.0.0.1:0"  # ephemeral; RpcServer reports it
    return os.path.join(str(tmp_path), f"{name}.sock")


# ---------------------------------------------------------------- frames

def test_frame_roundtrip_numpy_and_nesting():
    a, b = socket.socketpair()
    try:
        obj = {"method": "step", "arr": np.arange(7, dtype=np.int32),
               "nested": {"f": 1.5, "l": [1, "two", None]}}
        send_frame(a, obj)
        out = recv_frame(b, timeout=5.0)
        np.testing.assert_array_equal(out["arr"], np.arange(7))
        assert out["arr"].dtype == np.int32
        assert out["nested"] == {"f": 1.5, "l": [1, "two", None]}
    finally:
        a.close()
        b.close()


@pytest.mark.parametrize("family", FAMILIES)
def test_frame_garble_truncation_and_deadline(family):
    # bad magic
    a, b = _sock_pair(family)
    try:
        a.sendall(b"XXXX" + struct.pack("!II", 2, 0) + b"{}")
        with pytest.raises(RpcGarbledFrame, match="bad frame header"):
            recv_frame(b, timeout=5.0)
    finally:
        a.close()
        b.close()
    # crc mismatch (one payload byte flipped after the header was built)
    a, b = _sock_pair(family)
    try:
        payload = b'{"x":1}'
        a.sendall(b"DSRP" + struct.pack(
            "!II", len(payload), zlib.crc32(payload)) + b'{"x":2}')
        with pytest.raises(RpcGarbledFrame, match="crc mismatch"):
            recv_frame(b, timeout=5.0)
    finally:
        a.close()
        b.close()
    # peer closes mid-frame
    a, b = _sock_pair(family)
    try:
        payload = b'{"x":1}'
        a.sendall(b"DSRP" + struct.pack(
            "!II", len(payload), zlib.crc32(payload)) + payload[:3])
        a.close()
        with pytest.raises(RpcConnectionLost):
            recv_frame(b, timeout=5.0)
    finally:
        b.close()
    # nothing arrives inside the deadline
    a, b = _sock_pair(family)
    try:
        with pytest.raises(RpcTimeout):
            recv_frame(b, timeout=0.05)
    finally:
        a.close()
        b.close()


def test_request_result_codec_roundtrip():
    from deepspeed_tpu.inference.serving import Request, RequestResult

    req = Request(uid=3, prompt=np.arange(9, dtype=np.int32),
                  max_new_tokens=4, temperature=0.5, top_k=7, top_p=0.9,
                  eos_token=2, arrival_time=1.25, deadline_s=3.0)
    back = decode_request(encode_request(req))
    np.testing.assert_array_equal(back.prompt, req.prompt)
    assert (back.uid, back.max_new_tokens, back.temperature, back.top_k,
            back.top_p, back.eos_token, back.arrival_time,
            back.deadline_s) == (3, 4, 0.5, 7, 0.9, 2, 1.25, 3.0)
    res = RequestResult(uid=3, tokens=np.asarray([4, 5], np.int32),
                        prompt_len=9, arrival_time=1.25, finish_time=2.0,
                        slot=1, status="ok", requeues=1)
    back = decode_result(encode_result(res))
    np.testing.assert_array_equal(back.tokens, res.tokens)
    assert (back.uid, back.prompt_len, back.slot, back.status,
            back.requeues) == (3, 9, 1, "ok", 1)
    assert back.ok


def test_rpc_fault_sites_deterministic_and_once():
    cfg = {"enabled": True, "seed": 0,
           "rpc_timeout_at": [["step", 2]],
           "rpc_conn_reset_at": [["submit", 1]],
           "rpc_garbled_at": [["step", 3]]}
    a, b = FaultInjector(cfg), FaultInjector(cfg)
    for inj in (a, b):
        assert not inj.rpc_timeout("step", 1)
        assert inj.rpc_timeout("step", 2)
        assert not inj.rpc_timeout("step", 2)  # list keys fire exactly once
        assert inj.rpc_conn_reset("submit", 1)
        assert not inj.rpc_conn_reset("step", 1)  # keyed per method
        assert inj.rpc_garbled_frame("step", 3)
    assert a.stats()["injected"] == b.stats()["injected"] == {
        "rpc_timeout": 1, "rpc_conn_reset": 1, "rpc_garbled_frame": 1}


def test_transport_and_fault_config_schema():
    from deepspeed_tpu.runtime.config import (DeepSpeedConfig,
                                              DeepSpeedConfigError)

    cfg = DeepSpeedConfig.from_dict({
        "train_batch_size": 1,
        "serving": {"router": {"transport": {
            "call_timeout_s": 5.0, "connect_attempts": 2,
            "heartbeat_timeout_s": 3.0}}},
    })
    tr = cfg.serving.router.transport
    assert (tr.call_timeout_s, tr.connect_attempts,
            tr.heartbeat_timeout_s) == (5.0, 2, 3.0)
    with pytest.raises(DeepSpeedConfigError, match="call_timeout_s"):
        DeepSpeedConfig.from_dict({
            "train_batch_size": 1,
            "serving": {"router": {"transport": {"call_timeout_s": 0}}}})
    with pytest.raises(DeepSpeedConfigError, match="str, int"):
        DeepSpeedConfig.from_dict({
            "train_batch_size": 1,
            "serving": {"fault_injection": {"rpc_timeout_at": [[1, "step"]]}}})


def test_real_timeout_drops_desynced_stream(tmp_path):
    """Review regression: a REAL deadline miss (not injected) leaves the
    late reply in the stream. The client must drop the connection on
    RpcTimeout and validate reply ids — the next call gets ITS OWN reply
    over a fresh connection, never the previous call's stale one."""
    from deepspeed_tpu.inference.rpc import RpcClient

    path = os.path.join(str(tmp_path), "late.sock")
    lst = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    lst.bind(path)
    lst.listen(2)
    calls = []

    def serve():
        while len(calls) < 2:
            conn, _ = lst.accept()
            try:
                while True:
                    req = recv_frame(conn, timeout=10.0)
                    calls.append(req["method"])
                    if len(calls) == 1:
                        time.sleep(0.6)  # blow the client's 0.2s deadline
                    send_frame(conn, {"id": req["id"], "ok": True,
                                      "result": {"served": req["method"]}})
            except Exception:  # noqa: BLE001 — client dropped the conn
                conn.close()

    t = threading.Thread(target=serve, daemon=True)
    t.start()
    try:
        client = RpcClient(path, transport=RouterTransportConfig(
            call_timeout_s=0.2, connect_attempts=2,
            base_delay_s=0.01, max_delay_s=0.02, jitter=0.0))
        with pytest.raises(RpcTimeout):
            client.call("first")
        assert not client.connected  # desynced stream was dropped
        out = client.call("second", timeout=10.0)
        assert out == {"served": "second"}  # never the stale 'first' reply
        assert client.stats["reconnects"] >= 1
    finally:
        lst.close()
        t.join(timeout=5)


# ------------------------------------------------- thread-hosted replicas

class _ThreadWorker:
    """A REAL ServingEngine behind a REAL RpcServer, in a thread — the
    transport and scheduler surface of a worker process without paying a
    process boot. ``stop()`` is the SIGKILL stand-in: the listener and
    streams close, and the next client call sees RpcConnectionLost."""

    def __init__(self, engine, tmp_path, name, config=None, replica_id=0,
                 family="unix"):
        from deepspeed_tpu.inference.serving import ServingEngine
        from deepspeed_tpu.launcher.serving_worker import WorkerHost

        cfg = {"n_slots": 2, "max_seq_len": 128, "watchdog_mode": "raise",
               **(config or {})}
        self.engine = ServingEngine(engine, config=cfg, replica_id=replica_id)
        self.host = WorkerHost(self.engine)
        self.server = RpcServer(_worker_addr(tmp_path, name, family),
                                self.host.handlers())
        # the RESOLVED address (a tcp://...:0 bind reports its real port)
        self.path = self.server.address
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self.server.serve_forever,
            kwargs={"should_stop": self._stop.is_set}, daemon=True)
        self._thread.start()

    def client(self, **kw) -> ReplicaClient:
        kw.setdefault("transport", RouterTransportConfig(**TRANSPORT))
        return ReplicaClient(self.path, **kw)

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=5)
        self.server.close()


def _prompts(sizes, seed=0, vocab=97):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=s).astype(np.int32) for s in sizes]


def test_replica_client_matches_inprocess_engine(tiny_serving_engine, tmp_path):
    """The full scheduler surface over the wire: greedy parity with the
    solo generate, terminal-uid contract, cached load/idle state, remote
    snapshot attribution, compile counts — under watchdog raise (the
    transport added no XLA programs)."""
    from deepspeed_tpu.inference.serving import Request

    prompts = _prompts([5, 11, 23])
    refs = [tiny_serving_engine.generate(p[None], max_new_tokens=8)[0]
            for p in prompts]
    w = _ThreadWorker(tiny_serving_engine, tmp_path, "solo", replica_id=9)
    try:
        client = w.client(replica_id=9)
        assert client.ping()["replica_id"] == 9
        for i, p in enumerate(prompts):
            client.submit(Request(uid=i, prompt=p, max_new_tokens=8))
        assert client.load == 3 and not client.idle
        done = set()
        for _ in range(40):
            done |= set(client.step(now=0.0))
            if len(done) == 3:
                break
        assert done == {0, 1, 2}
        for i in range(3):
            res = client.result(i)
            assert res.ok
            np.testing.assert_array_equal(res.tokens, refs[i])
        assert client.idle and client.load == 0
        assert client.compile_counts()["decode"] == 1
        snap = client.telemetry_snapshot()
        assert snap["replica_id"] == 9
        assert snap["transport"]["calls"] > 0
        # match-length probe works over the wire (0: no prefix cache here)
        assert client.prefix_match_len(prompts[0]) == 0
    finally:
        w.stop()


@pytest.mark.parametrize("family", FAMILIES)
def test_step_reply_loss_recovered_by_replay_safe_retry(tiny_serving_engine,
                                                        tmp_path, family):
    """A step reply lost to a conn reset or a garbled frame is re-delivered
    after the transparent reconnect+retry: terminal uids accumulate unacked
    on the worker, so nothing is dropped and nothing is double-recorded.
    Proven over BOTH address families — the TCP variant's injected reset is
    a genuine linger-0 RST."""
    from deepspeed_tpu.inference.serving import Request

    prompts = _prompts([5, 11], seed=5)
    refs = [tiny_serving_engine.generate(p[None], max_new_tokens=8)[0]
            for p in prompts]
    w = _ThreadWorker(tiny_serving_engine, tmp_path, "retry", family=family)
    try:
        client = w.client(fault_injection={
            "enabled": True, "seed": 0,
            "rpc_conn_reset_at": [["step", 2]],
            "rpc_garbled_at": [["step", 5]]})
        for i, p in enumerate(prompts):
            client.submit(Request(uid=i, prompt=p, max_new_tokens=8))
        done = []
        for _ in range(40):
            done += client.step(now=0.0)
            if len(done) >= 2:
                break
        assert sorted(done) == [0, 1]  # no uid lost, none duplicated
        for i in range(2):
            np.testing.assert_array_equal(client.result(i).tokens, refs[i])
        st = client.rpc_stats()
        assert st["conn_resets"] >= 1 and st["garbled_frames"] >= 1
        assert st["reconnects"] >= 2 and st["retries"] >= 2
    finally:
        w.stop()


@pytest.mark.parametrize("family", FAMILIES)
def test_router_remote_kill_dead_failover_parity(tiny_serving_engine,
                                                 tmp_path, family):
    """A mixed fleet (one remote replica, one in-process) — the Router
    cannot tell them apart. Killing the remote's transport mid-decode draws
    the DEAD verdict; its requests fail over from ROUTER-side state (the
    worker can't be asked), complete with solo-generate parity, and the
    merged snapshot still shows the dead replica's timeline from the
    piggybacked trace mirror. Both address families: a vanished TCP
    listener must earn the same verdict as a vanished unix socket."""
    from deepspeed_tpu.inference.serving import Request, ServingEngine
    from deepspeed_tpu.inference import Router
    from deepspeed_tpu.telemetry import request_timeline

    prompts = _prompts([5, 11, 23])
    refs = [tiny_serving_engine.generate(p[None], max_new_tokens=8)[0]
            for p in prompts]
    w = _ThreadWorker(tiny_serving_engine, tmp_path, "kill", replica_id=0,
                      family=family)
    try:
        client = w.client(replica_id=0)
        local = ServingEngine(tiny_serving_engine, n_slots=2, max_seq_len=128,
                              replica_id=1)
        router = Router(
            config={"router": {"replicas": 2, "health": {"timeout": 30.0}}},
            replica_engines=[client, local])
        for i, p in enumerate(prompts):
            router.submit(Request(uid=i, prompt=p, max_new_tokens=8))
        on_remote = [u for u in range(3) if router.owner_of(u) == 0]
        assert on_remote  # least-loaded spread put work on the remote
        router.step(now=0.0)
        router.step(now=0.0)  # both replicas decoding
        w.stop()  # SIGKILL stand-in: the transport is simply gone
        res = router.drain()
        for i in range(3):
            assert res[i].ok, (i, res[i].status)
            np.testing.assert_array_equal(res[i].tokens, refs[i])
        assert router.replica_states() == {0: "dead", 1: "healthy"}
        counters = router.telemetry.registry.snapshot()["counters"]
        assert counters["router/failovers"] == len(on_remote)
        assert counters.get("router/failed_requests", 0) == 0
        assert counters["rpc/calls"] > 0  # transport metrics in the registry
        # killed-worker timeline: the snapshot substitutes the trace mirror
        snap = router.telemetry_snapshot()
        dead = snap["replicas"][0]
        assert "unreachable" in dead and dead["replica_id"] == 0
        mirror = dead["request_trace"]
        assert mirror and all(e["replica_id"] == 0 for e in mirror)
        tl = request_timeline(snap, on_remote[0])
        names = [e["event"] for e in tl]
        assert "admitted" in names  # recorded by the KILLED replica
        assert "failover" in names  # recorded by the router
        fo = next(e for e in tl if e["event"] == "failover")
        assert fo["from_replica"] == 0 and fo["to_replica"] == 1
        # the survivor stayed one-program under the fault
        assert local.compile_counts()["decode"] == 1
    finally:
        w.stop()


def test_router_rpc_timeout_is_hung_verdict(tiny_serving_engine, tmp_path):
    """An injected step-reply timeout (call executed, reply late) draws the
    HUNG verdict — probation + failover, NOT dead: the process may recover,
    and after the backoff the re-admitted replica serves traffic again."""
    from deepspeed_tpu.inference.serving import Request, ServingEngine
    from deepspeed_tpu.inference import Router

    prompts = _prompts([5, 11], seed=7)
    refs = [tiny_serving_engine.generate(p[None], max_new_tokens=8)[0]
            for p in prompts]
    w = _ThreadWorker(tiny_serving_engine, tmp_path, "hang", replica_id=0)
    try:
        client = w.client(replica_id=0, fault_injection={
            "enabled": True, "seed": 0, "rpc_timeout_at": [["step", 2]]})
        local = ServingEngine(tiny_serving_engine, n_slots=2, max_seq_len=128,
                              replica_id=1)
        router = Router(
            config={"router": {"replicas": 2,
                               "health": {"timeout": 30.0, "max_attempts": 3,
                                          "base_delay_s": 1.0, "jitter": 0.0}}},
            replica_engines=[client, local])
        for i, p in enumerate(prompts):
            router.submit(Request(uid=i, prompt=p, max_new_tokens=8))
        router.step(now=0.0)  # both admitted+decoding
        router.step(now=0.0)  # injected timeout on the remote step
        assert router.replica_states()[0] == "probation"
        assert client.rpc_stats()["timeouts"] == 1
        router.step(now=0.5)
        assert router.replica_states()[0] == "probation"  # backoff = 1.0s
        router.step(now=1.5)
        assert router.replica_states()[0] == "healthy"  # process recovered
        res = router.drain()
        for i in range(2):
            assert res[i].ok, (i, res[i].status)
            np.testing.assert_array_equal(res[i].tokens, refs[i])
        counters = router.telemetry.registry.snapshot()["counters"]
        assert counters["router/hung_verdicts"] == 1
        assert counters["router/readmissions"] == 1
        # the hung-path cancel reached the (healthy) worker: nothing is
        # still decoding an abandoned copy there
        assert client.idle
        # re-admitted replica accepts dispatch again
        router.submit(Request(uid=50, prompt=prompts[0], max_new_tokens=2))
        assert router.owner_of(50) == 0
        router.drain()
    finally:
        w.stop()


def test_attach_replica_grows_fleet(tiny_serving_engine, tmp_path):
    """The supervisor's respawn path: a replacement replica joins as a NEW
    rid, accepts dispatch, and reports under its own id in the merged
    snapshot (the dead rid stays detached)."""
    from deepspeed_tpu.inference.serving import Request, ServingEngine
    from deepspeed_tpu.inference import Router

    (p,) = _prompts([5], seed=9)
    ref = tiny_serving_engine.generate(p[None], max_new_tokens=4)[0]
    local = ServingEngine(tiny_serving_engine, n_slots=2, max_seq_len=128,
                          replica_id=0)
    router = Router(config={"router": {"replicas": 1,
                                       "health": {"timeout": 30.0}}},
                    replica_engines=[local])
    w = _ThreadWorker(tiny_serving_engine, tmp_path, "grow", replica_id=1)
    try:
        rid = router.attach_replica(w.client(replica_id=1))
        assert rid == 1
        assert router.replica_states() == {0: "healthy", 1: "healthy"}
        # drain rid 0 so dispatch MUST land on the attached replica
        router.drain_replica(0, block=True)
        router.submit(Request(uid=0, prompt=p, max_new_tokens=4))
        assert router.owner_of(0) == 1
        res = router.drain()
        np.testing.assert_array_equal(res[0].tokens, ref)
        assert router.telemetry_snapshot()["replicas"][1]["replica_id"] == 1
    finally:
        w.stop()
