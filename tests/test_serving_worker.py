"""Serving worker processes (launcher/serving_worker.py).

Real OS processes: one worker = one ServingEngine behind the RPC in its
own interpreter. These tests are HOST-ONLY in the XLA sense — the worker
builds the session-standard tiny model (the exact ``tiny_serving_engine``
config) and inherits ``tests/.xla_cache`` + the pytest RNG flags through
the environment, so its programs are cache loads, never new shapes — but
they do pay interpreter+jax boot per process, so the warm tier keeps
exactly ONE spawn; the respawn/failover drill with a second process is
slow-tier (the real kill-9 parity drill is ``drills.py --chaos-serving``).
"""

import json
import os
import signal
import time

import numpy as np
import pytest

from deepspeed_tpu.launcher.serving_worker import WorkerSupervisor
from deepspeed_tpu.runtime.config import RouterTransportConfig

# EXACTLY the tiny_serving_engine config (tests/conftest.py) — the worker's
# programs must hash into the same tests/.xla_cache entries
SPEC = {
    "model": {"vocab_size": 97, "max_seq_len": 128, "num_layers": 2,
              "num_heads": 4, "hidden_size": 32, "dtype": "float32",
              "loss_chunk_size": 0, "decode_attn": "xla",
              "pos_emb": "rotary"},
    "engine_dtype": "fp32",
    "serving": {"n_slots": 2, "max_seq_len": 128, "watchdog_mode": "raise"},
}


def _worker_env():
    # children must match the pytest jax config (conftest sets it via
    # jax.config, which subprocesses cannot see) or their RNG-bearing
    # programs hash differently and cold-compile instead of cache-loading
    return {
        "JAX_PLATFORMS": "cpu",
        "JAX_THREEFRY_PARTITIONABLE": "1",
        "JAX_COMPILATION_CACHE_DIR": os.path.join(
            os.path.dirname(__file__), ".xla_cache"),
    }


def _transport(**kw):
    kw.setdefault("call_timeout_s", 120.0)
    kw.setdefault("boot_timeout_s", 180.0)
    kw.setdefault("heartbeat_timeout_s", 30.0)
    kw.setdefault("base_delay_s", 0.05)
    kw.setdefault("max_delay_s", 0.2)
    return RouterTransportConfig(**kw)


def _events(log_path):
    out = []
    with open(log_path) as f:
        for line in f:
            line = line.strip()
            if line.startswith("{"):
                try:
                    out.append(json.loads(line))
                except ValueError:
                    pass
    return out


def test_worker_process_roundtrip_and_sigterm_drain(tiny_serving_engine):
    """One real worker process: boots from the spec with bit-identical
    params (PRNGKey(0) + matched RNG flags — greedy outputs equal the
    parent fixture's generate), serves the scheduler surface over RPC
    under watchdog raise, heartbeats, and on SIGTERM drains in-flight work
    to a terminal state before exiting 0 with a ``drained`` event line."""
    from deepspeed_tpu.inference.serving import Request

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 97, size=s).astype(np.int32) for s in (5, 11)]
    refs = [tiny_serving_engine.generate(p[None], max_new_tokens=6)[0]
            for p in prompts]
    sup = WorkerSupervisor(
        SPEC, 1, transport=_transport(),
        respawn_backoff={"max_attempts": 10, "base_delay_s": 0.05,
                         "max_delay_s": 0.1, "jitter": 0.0},
        env=_worker_env())
    try:
        (client,) = sup.start()
        assert client.ping()["pid"] == sup.proc(0).pid
        for i, p in enumerate(prompts):
            client.submit(Request(uid=i, prompt=p, max_new_tokens=6))
        done = set()
        for _ in range(40):
            done |= set(client.step(now=0.0))
            if len(done) == 2:
                break
        assert done == {0, 1}
        for i in range(2):
            res = client.result(i)
            assert res.ok
            # cross-process greedy parity: the worker rebuilt the SAME
            # params from the spec (deterministic PRNGKey(0) init)
            np.testing.assert_array_equal(res.tokens, refs[i])
        assert client.compile_counts()["decode"] == 1  # raise mode held
        snap = client.telemetry_snapshot()
        assert snap["replica_id"] == 0 and snap["transport"]["calls"] > 0
        # heartbeat: the worker touches its file while serving
        hb = sup._hb_path[0]
        m0 = os.path.getmtime(hb)
        time.sleep(0.5)
        assert os.path.getmtime(hb) > m0
        assert sup.poll() == []  # alive and fresh

        # SIGTERM drain-then-exit with work in flight
        client.submit(Request(uid=7, prompt=prompts[0], max_new_tokens=6))
        client.step(now=0.0)  # admitted, decoding
        os.kill(sup.proc(0).pid, signal.SIGTERM)
        assert sup.proc(0).wait(timeout=60) == 0
        events = {e.get("event") for e in _events(sup._logs[0])}
        assert {"ready", "drained"} <= events
        # how long ready took and what the build was made of (kept spans, seconds by path)
        ready = next(e for e in _events(sup._logs[0]) if e.get("event") == "ready")
        assert ready["startup_s"] >= ready["phases"]["startup/build"] > 0
        assert {"startup/build/draw", "startup/build/cache"} <= set(ready["phases"])
        drained = next(e for e in _events(sup._logs[0])
                       if e.get("event") == "drained")
        # the in-flight request reached a terminal state before exit
        assert drained["in_flight_at_signal"] >= 1
        assert drained["results"] >= 3
        assert sup.poll() == [0]  # clean exit still reported for respawn
    finally:
        sup.shutdown()


def test_worker_process_tcp_roundtrip_with_parity(tiny_serving_engine):
    """ONE additional warm worker-process boot, over the TCP family with
    an OS-assigned ephemeral port: the supervisor discovers the resolved
    ``tcp://host:port`` from the worker's ready line, the full scheduler
    surface rides the same DSRP frames, greedy outputs stay bit-identical
    to the parent fixture's generate, and watchdog raise holds (the
    transport family changes nothing about the program inventory). The
    respawn drill over TCP is slow-tier below."""
    from deepspeed_tpu.inference.serving import Request

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 97, size=s).astype(np.int32) for s in (5, 11)]
    refs = [tiny_serving_engine.generate(p[None], max_new_tokens=6)[0]
            for p in prompts]
    sup = WorkerSupervisor(
        SPEC, 1, transport=_transport(family="tcp", host="127.0.0.1",
                                      port_base=0),
        respawn_backoff={"max_attempts": 10, "base_delay_s": 0.05,
                         "max_delay_s": 0.1, "jitter": 0.0},
        env=_worker_env())
    try:
        (client,) = sup.start()
        assert client.rpc.path.startswith("tcp://127.0.0.1:")
        assert client.ping()["pid"] == sup.proc(0).pid
        for i, p in enumerate(prompts):
            client.submit(Request(uid=i, prompt=p, max_new_tokens=6))
        done = set()
        for _ in range(40):
            done |= set(client.step(now=0.0))
            if len(done) == 2:
                break
        assert done == {0, 1}
        for i in range(2):
            res = client.result(i)
            assert res.ok
            np.testing.assert_array_equal(res.tokens, refs[i])
        assert client.compile_counts()["decode"] == 1  # raise mode held
        assert sup.poll() == []  # alive and heartbeating over tcp too
    finally:
        sup.shutdown()


class _FakeProc:
    def __init__(self, rc=None):
        self.returncode = rc

    def poll(self):
        return self.returncode


class _FakeJudge:
    def __init__(self, stale=False):
        self._stale = stale

    def stale(self):
        return self._stale


def test_respawn_budget_heals_after_sustained_health(tmp_path):
    """Regression (fake clock, no processes): ``_respawn_count`` decays by
    one per ``respawn_heal_s`` of alive-and-heartbeating uptime, so a
    long-lived fleet with occasional preemptions is never one respawn from
    permanent ``max_respawns`` exhaustion — while a crash-looping slot
    (which never lives that long) still exhausts its budget."""
    clk = {"t": 1000.0}
    sup = WorkerSupervisor(
        {}, 0, workdir=str(tmp_path), max_respawns=3, respawn_heal_s=60.0,
        clock=lambda: clk["t"])
    # a slot that has been respawned twice and is now healthy
    sup._procs[0] = _FakeProc()
    sup._hb_judge[0] = _FakeJudge(stale=False)
    sup._respawn_count[0] = 2
    sup._heal_anchor[0] = clk["t"]
    assert sup.poll() == []
    assert sup._respawn_count[0] == 2  # no decay yet
    clk["t"] += 59.0
    sup.poll()
    assert sup._respawn_count[0] == 2  # under the heal window
    clk["t"] += 2.0  # 61s of healthy uptime total
    sup.poll()
    assert sup._respawn_count[0] == 1
    clk["t"] += 130.0  # two more windows accrue in one gap
    sup.poll()
    assert sup._respawn_count[0] == 0
    # crash-loop detection unchanged: rapid deaths exhaust the budget
    # before any heal window elapses (the budget check precedes the spawn)
    sup._respawn_count[1] = 3
    with pytest.raises(RuntimeError, match="exhausted its respawn budget"):
        sup.respawn(1)
    # a stale heartbeat never heals: the slot is SIGKILL-bad, not healthy
    sup._procs[2] = _FakeProc()
    sup._hb_judge[2] = _FakeJudge(stale=False)
    sup._respawn_count[2] = 1
    sup._heal_anchor[2] = clk["t"]
    sup._hb_judge[2]._stale = True
    clk["t"] += 120.0
    # poll SIGKILLs the fake (no real pid: _FakeProc has no .kill — use a
    # dead proc instead to model "reported bad", which skips the heal arm)
    sup._procs[2] = _FakeProc(rc=-9)
    assert sup.poll() == [2]
    assert sup._respawn_count[2] == 1  # bad slots never decay


@pytest.mark.slow  # second+third process boots (~15s/family); the warm
# siblings above keep spawn/drain/heartbeat coverage on BOTH families
# (unix roundtrip + tcp roundtrip), and drills.py --chaos-serving /
# --surge are the full kill-9 parity drills
@pytest.mark.parametrize("family", ["unix", "tcp"])
def test_supervisor_kill9_respawn_and_router_reattach(tiny_serving_engine,
                                                      family):
    """SIGKILL a worker mid-decode: the Router draws the DEAD verdict from
    the vanished transport and replays with parity; the supervisor detects
    the corpse, respawns within its backoff budget, and the replacement
    joins the fleet as a NEW replica that serves traffic. Parameterized
    over both address families — kill-9 failover parity must hold over
    TCP exactly as over unix sockets."""
    from deepspeed_tpu.inference import Router
    from deepspeed_tpu.inference.serving import Request

    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 97, size=s).astype(np.int32) for s in (5, 11)]
    refs = [tiny_serving_engine.generate(p[None], max_new_tokens=8)[0]
            for p in prompts]
    transport = (_transport(family="tcp", host="127.0.0.1", port_base=0)
                 if family == "tcp" else _transport())
    sup = WorkerSupervisor(
        SPEC, 2, transport=transport,
        respawn_backoff={"max_attempts": 10, "base_delay_s": 0.05,
                         "max_delay_s": 0.1, "jitter": 0.0},
        env=_worker_env())
    try:
        clients = sup.start()
        router = Router(
            config={"router": {"replicas": 2, "health": {"timeout": 60.0}}},
            replica_engines=clients)
        for i, p in enumerate(prompts):
            router.submit(Request(uid=i, prompt=p, max_new_tokens=8))
        router.step(now=0.0)
        on0 = [u for u in (0, 1) if router.owner_of(u) == 0]
        assert on0
        sup.kill(0, signal.SIGKILL)  # mid-decode, for real
        res = router.drain()
        for i in range(2):
            assert res[i].ok, (i, res[i].status)
            np.testing.assert_array_equal(res[i].tokens, refs[i])
        assert router.replica_states()[0] == "dead"
        t0 = time.monotonic()
        bad = sup.poll()
        assert bad == [0]
        new_client = sup.respawn(0)
        respawn_s = time.monotonic() - t0
        assert sup.respawns == 1 and respawn_s < 120  # backoff + boot budget
        rid = router.attach_replica(new_client)
        # force dispatch onto the respawned replica to prove it serves
        router.drain_replica(1, block=True)
        router.submit(Request(uid=9, prompt=prompts[0], max_new_tokens=8))
        assert router.owner_of(9) == rid
        out = router.drain()
        np.testing.assert_array_equal(out[9].tokens, refs[0])
        assert new_client.compile_counts()["decode"] == 1
    finally:
        sup.shutdown()
