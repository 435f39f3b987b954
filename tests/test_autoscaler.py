"""Ledger-driven autoscaler + overload brownout (inference/autoscaler.py,
the Router's brownout ladder).

The contract under test: the telemetry→membership loop closes WITHOUT an
operator — backlog grows the fleet, idleness shrinks it (through PR 6's
zero-loss drain), a dead replica is replaced by a NEW rid, and at max
capacity the Router degrades gracefully (deadline tightening, priority
shedding newest-first, typed ``overloaded``) instead of shedding blindly.
Hysteresis and cooldown make every decision flap-proof.

Speed discipline: the decision machine is pure host code, so most tests
drive the Router over ``_FakeEngine`` scheduler surfaces (zero device
work, milliseconds each). Exactly ONE test builds real engines — on the
session ``tiny_serving_engine`` shapes (n_slots 2, prompts [5, 11, 23],
max_new 8: the test_serving parity set), so it adds no new XLA programs.
The process-mode end of the loop (WorkerSupervisor spawn/respawn/retire)
is proven by ``drills.py --surge``.
"""

import numpy as np
import pytest

from deepspeed_tpu.inference import Autoscaler, Request, Router
from deepspeed_tpu.resilience import (RequestRejected, RpcConnectionLost,
                                      RpcTimeout)


class _FakeEngine:
    """Host-only scheduler surface (everything the Router + autoscaler
    read), with a controllable queue and an optional step fault."""

    def __init__(self, rid=0):
        self.replica_id = rid
        self.queued = []
        self.last_step_compiled = False
        self.fail_next_step = False

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
        return False

    def result(self, uid):
        return None

    def step(self, now=None, enforce_deadlines=True):
        if self.fail_next_step:
            self.fail_next_step = False
            raise RpcConnectionLost("fake worker gone")
        return []

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
        return {"replica_id": self.replica_id,
                "metrics": {"gauges": {"serving/mfu": 0.6}}}

    @property
    def load(self):
        return len(self.queued)

    @property
    def idle(self):
        return not self.queued

    @property
    def queue_len(self):
        return len(self.queued)


def _req(uid, priority=0, deadline_s=0.0):
    return Request(uid=uid, prompt=np.arange(4, dtype=np.int32),
                   max_new_tokens=4, priority=priority, deadline_s=deadline_s)


def _fleet(asc_cfg=None, router_cfg=None, n=1, spawn=None, retire=None):
    engines = [_FakeEngine(i) for i in range(n)]
    router = Router(replica_engines=engines,
                    config={"router": {"health": {"timeout": 0},
                                       **(router_cfg or {})}})
    spawned = []

    def default_spawn():
        e = _FakeEngine(100 + len(spawned))
        spawned.append(e)
        return e

    asc = Autoscaler(router, {
        "enabled": True, "min_replicas": 1, "max_replicas": 2,
        "scale_up_queue": 3, "scale_up_load": 3.0, "scale_down_load": 0.5,
        "up_consecutive": 2, "down_consecutive": 3, "cooldown_s": 0.0,
        **(asc_cfg or {})}, spawn=spawn or default_spawn, retire=retire)
    return router, asc, engines, spawned


# ------------------------------------------------------------ decisions


def test_backlog_scales_up_after_hysteresis_window():
    router, asc, (eng,), spawned = _fleet()
    for i in range(4):
        router.submit(_req(i))
    router.step(now=1.0)  # tick 1: up-signal observed, no action yet
    assert asc.target == 1 and not spawned
    router.step(now=2.0)  # tick 2: hysteresis satisfied -> scale up
    assert asc.target == 2 and len(spawned) == 1
    counters = router.telemetry.registry.snapshot()["counters"]
    assert counters["router/autoscale/scale_ups"] == 1
    assert router.telemetry.registry.snapshot()["gauges"][
        "router/autoscale/target_replicas"] == 2
    kinds = [e["kind"] for e in asc.events]
    assert "scale_up" in kinds


def test_flapping_signal_never_scales():
    """A metric that alternates above/below the threshold every tick can
    never satisfy ``up_consecutive`` — the fleet holds steady."""
    router, asc, (eng,), spawned = _fleet()
    for t in range(10):
        if t % 2 == 0:
            for i in range(4):
                router.submit(_req(1000 + t * 10 + i))
        else:
            # drain: requests vanish (the flap's other half)
            for r in list(eng.queued):
                router.cancel(r.uid)
                eng.queued.clear()
            router._owner.clear()
            router._requests.clear()
        router.step(now=float(t))
    assert asc.target == 1 and not spawned


def test_cooldown_paces_consecutive_scale_ups():
    router, asc, engines, spawned = _fleet(
        asc_cfg={"max_replicas": 4, "cooldown_s": 100.0})
    for i in range(12):
        router.submit(_req(i))
    for t in range(6):  # persistent up-signal, cooldown 100s
        router.step(now=float(t))
    assert asc.target == 2 and len(spawned) == 1  # one action, then cooldown
    router.step(now=105.0)  # cooldown elapsed on the router clock
    assert asc.target == 3 and len(spawned) == 2


def test_idle_scales_down_drains_and_retires():
    retired = []
    router, asc, engines, spawned = _fleet(
        n=2, retire=lambda rid, e: retired.append(rid))
    assert asc.target == 2
    for t in range(10):
        router.step(now=float(t))
        if retired:
            break
    assert asc.target == 1
    assert retired == [1]  # least-loaded rookie drained, then retired
    states = router.replica_states()
    assert states[1] == "drained" and states[0] == "healthy"
    counters = router.telemetry.registry.snapshot()["counters"]
    assert counters["router/autoscale/scale_downs"] == 1
    assert counters["router/replicas_drained"] == 1  # PR 6 drain, zero loss


def test_min_replicas_floor_holds():
    router, asc, engines, spawned = _fleet(n=1)
    for t in range(20):
        router.step(now=float(t))
    assert asc.target == 1
    assert router.replica_states() == {0: "healthy"}


def test_dead_replica_respawned_as_new_rid():
    """The healing half: a replica whose step raises (SIGKILL'd worker,
    vanished transport) is replaced by a NEW rid the same tick the fleet
    notices it is under target — never a resurrection of the dead rid."""
    router, asc, (eng,), spawned = _fleet()
    eng.fail_next_step = True
    router.step(now=1.0)  # dead verdict, then the tick recovers
    assert router.replica_states()[0] == "dead"
    assert len(spawned) == 1
    assert router.replica_states()[1] == "healthy"
    counters = router.telemetry.registry.snapshot()["counters"]
    assert counters["router/autoscale/respawns"] == 1
    assert any(e["kind"] == "respawn" for e in asc.events)
    # the replacement serves: dispatch lands on it
    uid = router.submit(_req(7))
    assert router.owner_of(uid) == 1


def test_spawn_failure_is_paced_not_fatal():
    def bad_spawn():
        raise RuntimeError("boot failed")

    router, asc, (eng,), _ = _fleet(spawn=bad_spawn)
    for i in range(4):
        router.submit(_req(i))
    router.step(now=1.0)
    router.step(now=2.0)  # scale-up attempt -> spawn fails, loop survives
    assert asc.target == 1
    counters = router.telemetry.registry.snapshot()["counters"]
    assert counters["router/autoscale/spawn_failures"] >= 1
    assert any(e["kind"] == "spawn_failed" for e in asc.events)


class _FakeSupervisor:
    """Host-only WorkerSupervisor surface: a controllable boot delay and
    a corpse set that poll() RE-REPORTS until the slot is respawned or
    retired — exactly like the real supervisor's dead-proc table."""

    def __init__(self, boot_s=0.0):
        self.boot_s = boot_s
        self.spawned = []
        self.respawned = []
        self.retired = []
        self.corpses = set()

    def spawn(self, slot):
        import time

        if self.boot_s:
            time.sleep(self.boot_s)
        self.spawned.append(slot)
        self.corpses.discard(slot)
        return _FakeEngine(200 + slot)

    def respawn(self, slot):
        self.respawned.append(slot)
        return self.spawn(slot)

    def poll(self):
        return sorted(self.corpses)

    def retire(self, slot):
        self.retired.append(slot)
        self.corpses.discard(slot)


def test_supervisor_boot_is_async_never_stalls_the_step_loop():
    """Review regression: a worker-process boot takes seconds — it must
    run on a background thread, with the new replica attached by a LATER
    tick, so the serving loop keeps stepping replicas throughout."""
    import time

    sup = _FakeSupervisor(boot_s=0.3)
    router = Router(replica_engines=[_FakeEngine(0)],
                    config={"router": {"health": {"timeout": 0}}})
    asc = Autoscaler(router, {
        "enabled": True, "min_replicas": 1, "max_replicas": 2,
        "scale_up_queue": 2, "scale_up_load": 2.0, "scale_down_load": 0.0,
        "up_consecutive": 1, "down_consecutive": 1000, "cooldown_s": 0.0},
        supervisor=sup, slots={0: 0})
    for i in range(4):
        router.submit(_req(i))
    t0 = time.monotonic()
    router.step(now=1.0)  # decision: boot starts in the background
    assert time.monotonic() - t0 < 0.25  # the step did NOT pay the boot
    assert asc.target == 2 and len(router._replicas) == 1
    deadline = time.monotonic() + 5.0
    while len(router._replicas) < 2:
        assert time.monotonic() < deadline
        router.step(now=router.now())  # loop keeps stepping; boot lands
        time.sleep(0.02)
    assert sup.spawned == [1]
    counters = router.telemetry.registry.snapshot()["counters"]
    assert counters["router/autoscale/scale_ups"] == 1
    kinds = [e["kind"] for e in asc.events]
    assert "scale_up_started" in kinds and "scale_up" in kinds


def test_probation_corpse_is_respawned_not_retired():
    """Review regression: a worker that wedged (HUNG verdict → probation)
    and was then SIGKILL'd by the supervisor's heartbeat judge must be
    RESPAWNED — the supervisor's corpse observation converts the
    probation to an immediate dead verdict (a dead process can never
    re-admit), instead of the slot being silently retired while the
    router waits out a probation that can only end in another failure."""
    import time

    sup = _FakeSupervisor()
    router = Router(replica_engines=[_FakeEngine(0)],
                    config={"router": {"health": {"timeout": 0}}})
    asc = Autoscaler(router, {
        "enabled": True, "min_replicas": 1, "max_replicas": 2,
        "scale_up_queue": 0, "scale_up_load": 0.0, "scale_down_load": 0.0,
        "up_consecutive": 1, "down_consecutive": 1000, "cooldown_s": 0.0},
        supervisor=sup, slots={0: 0})
    router._replicas[0].state = "probation"  # the hung verdict landed
    sup.corpses = {0}  # ...and then the supervisor SIGKILL'd the worker
    asc.tick(now=1.0)
    assert router.replica_states()[0] == "dead"  # mark_dead, not backoff
    assert sup.retired == []  # the slot was NOT reaped away
    deadline = time.monotonic() + 5.0
    while len(router._replicas) < 2:
        assert time.monotonic() < deadline
        asc.tick(now=router.now())
        time.sleep(0.02)
    assert sup.respawned == [0]  # same slot, fresh generation
    counters = router.telemetry.registry.snapshot()["counters"]
    assert counters["router/autoscale/respawns"] == 1
    assert router.replica_states()[1] == "healthy"


# ------------------------------------------------------------- brownout


def _saturate_to_brownout(router, asc, n=4):
    for i in range(n):
        router.submit(_req(i))
    router.step(now=1.0)
    router.step(now=2.0)  # scale to max
    router.step(now=3.0)
    router.step(now=4.0)  # still saturated at max -> brownout
    assert router.brownout


def test_brownout_ladder_deadline_priority_shed_and_overloaded():
    """At max and saturated the Router degrades on the documented ladder:
    (1) deadline-free submits get the brownout deadline; (2) a full queue
    sheds the lowest-priority NEWEST queued request for a higher-priority
    arrival; (3) only an arrival no queued request undercuts bounces, with
    the typed ``overloaded`` reason."""
    router, asc, engines, spawned = _fleet(
        asc_cfg={"brownout_deadline_s": 5.0},
        router_cfg={"max_queue_len": 4})
    _saturate_to_brownout(router, asc)
    counters = router.telemetry.registry.snapshot()["counters"]
    assert counters["router/autoscale/brownouts"] == 1
    assert router.telemetry.registry.snapshot()["gauges"][
        "router/autoscale/brownout"] == 1

    # rung 2: priority 1 arrival sheds the newest priority-0 queued request
    router.submit(_req(50, priority=1))
    shed = [u for u, r in router.results.items()
            if r.status == "shed_brownout"]
    assert shed == [3]  # newest of the lowest class, never the oldest
    assert 3 not in router._owner  # owner map moved on
    # rung 1 rode along: the accepted arrival carries the brownout deadline
    all_queued = [r for e in engines + spawned for r in e.queued]
    req50 = next(r for r in all_queued if r.uid == 50)
    assert req50.deadline_s == 5.0
    # a request with its OWN deadline is never tightened
    router.cancel(50)
    # rung 3: an equal-priority arrival has nothing to shed -> overloaded
    with pytest.raises(RequestRejected) as ei:
        router.submit(_req(60, priority=0))
    assert ei.value.reason == "overloaded"
    counters = router.telemetry.registry.snapshot()["counters"]
    assert counters["router/autoscale/brownout_shed"] == 1
    assert counters["router/autoscale/overloaded_rejects"] == 1
    assert counters["router/autoscale/brownout_deadlines"] >= 1


def test_brownout_lifts_when_pressure_clears():
    router, asc, engines, spawned = _fleet(
        asc_cfg={"brownout_deadline_s": 5.0})
    _saturate_to_brownout(router, asc)
    for e in engines + spawned:
        e.queued.clear()
    router._owner.clear()
    router._requests.clear()
    router.step(now=5.0)
    router.step(now=6.0)  # calm for up_consecutive ticks
    assert not router.brownout
    assert router.telemetry.registry.snapshot()["gauges"][
        "router/autoscale/brownout"] == 0
    kinds = [e["kind"] for e in asc.events]
    assert "brownout_on" in kinds and "brownout_off" in kinds
    # post-brownout submits are NOT deadline-tightened
    router.submit(_req(70))
    req70 = next(r for e in engines + spawned for r in e.queued
                 if r.uid == 70)
    assert req70.deadline_s == 0.0


def test_brownout_lift_requires_wall_time_not_just_ticks():
    """Review regression: an unpaced driver ticks hundreds of times
    through a 100ms trough — the brownout must not lift until the calm
    has ALSO spanned cooldown_s of router-clock time."""
    router, asc, engines, spawned = _fleet(asc_cfg={"cooldown_s": 5.0})
    for i in range(4):
        router.submit(_req(i))
    # reach max + brownout despite the 5s action cooldown: scale-up at
    # t=10 (cooldown from -inf elapsed), then saturation at max
    router.step(now=9.0)
    router.step(now=10.0)
    router.step(now=11.0)
    router.step(now=12.0)
    assert router.brownout
    for e in engines + spawned:
        e.queued.clear()
    router._owner.clear()
    router._requests.clear()
    # many calm TICKS inside a sliver of wall time: must stay browned out
    for k in range(10):
        router.step(now=13.0 + k * 0.01)
    assert router.brownout
    router.step(now=19.0)  # calm has now spanned >= cooldown_s
    assert not router.brownout


def test_brownout_shed_survives_withdraw_timeout():
    """Review regression: a withdraw whose reply is lost to the per-call
    deadline MAY have executed remotely — the victim must still reach a
    terminal shed state (either side's leftover copy is an ignored
    orphan), never strand owned-but-held-by-nobody."""

    class _TimeoutOnceEngine(_FakeEngine):
        def __init__(self, rid=0):
            super().__init__(rid)
            self.timeouts = 1

        def withdraw(self, uid):
            if self.timeouts:
                self.timeouts -= 1
                raise RpcTimeout("reply lost to the per-call deadline")
            return super().withdraw(uid)

    eng = _TimeoutOnceEngine(0)
    router = Router(replica_engines=[eng],
                    config={"router": {"max_queue_len": 2,
                                       "health": {"timeout": 0}}})
    router.set_brownout(True)
    router.submit(_req(0, priority=0))
    router.submit(_req(1, priority=0))
    uid = router.submit(_req(2, priority=1))  # shed probe times out
    assert uid == 2
    shed = [u for u, r in router.results.items()
            if r.status == "shed_brownout"]
    assert shed == [1]  # terminal despite the lost reply
    assert 1 not in router._owner  # nothing strands: drain() can finish
    # the next step() returns the shed uid (terminal-uid contract)
    assert 1 in router.step(now=0.0)


def test_exhausted_corpse_is_dropped_so_other_corpses_recover():
    """Review regression: a corpse whose respawn fails (budget exhausted)
    must leave supervision — not camp at the head of poll()'s corpse
    queue starving every OTHER dead worker's recovery."""
    import time

    class _ExhaustedSlot0(_FakeSupervisor):
        def respawn(self, slot):
            self.respawned.append(slot)
            if slot == 0:
                raise RuntimeError(
                    "serving worker slot 0 exhausted its respawn budget")
            return self.spawn(slot)

    sup = _ExhaustedSlot0()
    router = Router(replica_engines=[_FakeEngine(0), _FakeEngine(1)],
                    config={"router": {"health": {"timeout": 0}}})
    asc = Autoscaler(router, {
        "enabled": True, "min_replicas": 2, "max_replicas": 3,
        "scale_up_queue": 0, "scale_up_load": 0.0, "scale_down_load": 0.0,
        "up_consecutive": 1, "down_consecutive": 1000, "cooldown_s": 0.0},
        supervisor=sup, slots={0: 0, 1: 1})
    # both workers die; slot 0's respawn budget is spent
    router._replicas[0].state = "dead"
    router._replicas[1].state = "dead"
    sup.corpses = {0, 1}
    deadline = time.monotonic() + 5.0
    while sum(1 for s in router.replica_states().values()
              if s == "healthy") < 2:
        assert time.monotonic() < deadline, (router.replica_states(),
                                             sup.respawned, sup.retired)
        asc.tick(now=router.now())
        time.sleep(0.02)
    assert 0 in sup.retired          # the exhausted corpse left supervision
    assert sup.respawned.count(0) == 1  # never retried head-of-line
    assert 1 in sup.respawned        # the healable corpse DID recover
    counters = router.telemetry.registry.snapshot()["counters"]
    assert counters["router/autoscale/spawn_failures"] == 1


def test_own_deadline_survives_brownout_tightening():
    router, asc, engines, spawned = _fleet(
        asc_cfg={"brownout_deadline_s": 5.0})
    _saturate_to_brownout(router, asc)
    router.submit(_req(80, priority=3, deadline_s=99.0))
    req80 = next(r for e in engines + spawned for r in e.queued
                 if r.uid == 80)
    assert req80.deadline_s == 99.0  # the caller's budget, not ours


# ----------------------------------------------------------- mfu signal


def test_mfu_signal_flows_from_fleet_snapshot():
    """PR 7's ledger gauges reach the decision loop through
    ``Router.telemetry_snapshot()``: ``observe()`` folds the replicas'
    ``serving/mfu`` gauges into the up-signal when ``scale_up_mfu`` is
    armed — a compute-saturated fleet scales before queues grow."""
    router, asc, (eng,), spawned = _fleet(
        asc_cfg={"scale_up_queue": 0, "scale_up_load": 0.0,
                 "scale_up_mfu": 0.5})
    assert asc.observe(router.telemetry_snapshot()) == pytest.approx(0.6)
    assert asc.signals(0.0)["mfu"] == pytest.approx(0.6)
    router.step(now=1.0)
    router.step(now=2.0)  # mfu 0.6 >= 0.5 for two ticks
    assert asc.target == 2 and len(spawned) == 1


# ------------------------------------------------- config + observability


def test_autoscale_config_schema():
    from deepspeed_tpu.runtime.config import (DeepSpeedConfig,
                                              DeepSpeedConfigError)

    cfg = DeepSpeedConfig.from_dict({
        "train_batch_size": 1,
        "serving": {"router": {"autoscale": {
            "enabled": True, "min_replicas": 2, "max_replicas": 5,
            "cooldown_s": 1.5, "brownout_deadline_s": 10.0}}},
    })
    a = cfg.serving.router.autoscale
    assert (a.enabled, a.min_replicas, a.max_replicas,
            a.cooldown_s, a.brownout_deadline_s) == (True, 2, 5, 1.5, 10.0)
    with pytest.raises(DeepSpeedConfigError, match="max_replicas"):
        DeepSpeedConfig.from_dict({
            "train_batch_size": 1,
            "serving": {"router": {"autoscale": {
                "min_replicas": 4, "max_replicas": 2}}}})
    with pytest.raises(DeepSpeedConfigError, match="scale_down_load"):
        DeepSpeedConfig.from_dict({
            "train_batch_size": 1,
            "serving": {"router": {"autoscale": {
                "scale_up_load": 1.0, "scale_down_load": 2.0}}}})


def test_snapshot_carries_autoscale_and_report_renders():
    from deepspeed_tpu.telemetry.report import summarize

    router, asc, (eng,), spawned = _fleet()
    for i in range(4):
        router.submit(_req(i))
    router.step(now=1.0)
    router.step(now=2.0)
    snap = router.telemetry_snapshot()
    block = snap["router"]["autoscale"]
    assert block["target"] == 2 and block["enabled"]
    assert any(e["kind"] == "scale_up" for e in block["events"])
    out = summarize([{"type": "snapshot", **snap}])
    assert "autoscaler (target 2" in out
    assert "scale_up" in out


# ------------------------------------------------- real-engine integration


def test_inprocess_autoscaled_fleet_serves_with_parity(tiny_serving_engine):
    """ONE real-engine pass: ``Router(engine, config)`` with
    ``autoscale.enabled`` builds its own autoscaler, grows under a backlog
    of 6 requests, serves every one with solo-generate greedy parity under
    watchdog RAISE (in-process scale-up reuses the session XLA shapes —
    zero new programs), and drains back to min once idle."""
    prompts = [np.random.default_rng(0).integers(0, 97, size=s).astype(np.int32)
               for s in (5, 11, 23)]
    refs = [tiny_serving_engine.generate(p[None], max_new_tokens=8)[0]
            for p in prompts]
    router = Router(tiny_serving_engine, config={
        "n_slots": 2, "max_seq_len": 128, "watchdog_mode": "raise",
        "router": {"replicas": 1, "health": {"timeout": 30.0},
                   "autoscale": {"enabled": True, "min_replicas": 1,
                                 "max_replicas": 2, "scale_up_queue": 2,
                                 "scale_up_load": 2.0,
                                 "scale_down_load": 0.5,
                                 "up_consecutive": 1, "down_consecutive": 2,
                                 "cooldown_s": 0.0}}})
    asc = router._autoscaler
    assert asc is not None and asc.cfg.enabled
    reqs = [Request(uid=i, prompt=prompts[i % 3], max_new_tokens=8)
            for i in range(6)]
    res = router.serve(reqs)
    for i in range(6):
        assert res[i].ok, (i, res[i].status)
        np.testing.assert_array_equal(res[i].tokens, refs[i % 3])
    counters = router.telemetry.registry.snapshot()["counters"]
    assert counters["router/autoscale/scale_ups"] >= 1
    assert len(router._replicas) >= 2
    # idle ticks shrink the fleet back to min (PR 6 drain, zero loss)
    for t in range(40):
        router.step(now=router.now())
        states = router.replica_states()
        if (asc.target == 1
                and all(s in ("healthy", "drained")
                        for s in states.values())
                and sum(1 for s in states.values() if s == "healthy") == 1):
            break
    assert asc.target == 1
    assert sum(1 for s in router.replica_states().values()
               if s == "healthy") == 1
    # watchdog raise held fleet-wide: no replica ever traced a SECOND
    # decode program (0 = a short-lived rookie that never decoded)
    for r in router._replicas:
        assert r.engine.compile_counts()["decode"] <= 1
