"""Correctness drills: ten CPU-pinned soaks of the resilience and serving
contracts. Each prints ONE JSON line and exits 0 when its contract held;
none of them measures speed (the benchmark is ``BENCHMARK.json`` +
``chipbench/``). ``python drills.py`` alone lists them.

Fault-injection smoke (``python drills.py --fault-rate 0.05``, CI tier):
runs a CPU serving workload with seeded rate-mode NaN-logit injection and
ASSERTS the resilience contract — every request reaches a terminal status,
``resilience/recovered`` is non-zero (at least one quarantined request's
clean replay finished), and no slot leaks (occupancy gauge back to 0, every
non-quarantined slot back in the free pool). Prints one JSON line.

Surge drill (``python drills.py --surge [n_requests] [--surge-seed N]``, CI
tier): the self-healing elastic fleet end-to-end — real worker processes
behind the Router + the ledger-driven Autoscaler, an open-loop bursty
trace with heavy-tail prompt lengths and mixed priorities, and a
mid-trace worker SIGKILL. ASSERTS the elasticity contract: the fleet
grows to max under the burst, the killed worker is recovered (supervisor
respawn + attach as a NEW replica), the fleet shrinks back to min after
the burst, every accepted request reaches a terminal state with greedy
parity on the completed set, brownout engaged while saturated at max, and
no worker compiled a second decode program. Prints one JSON line with
scale/respawn/brownout/shed counts and p99 TTFT.

Gateway chaos drill (``python drills.py --gateway-chaos [--gateway-seed N]``,
CI tier): the HTTP/SSE front door end-to-end — real worker processes over
the TCP transport behind a real ``launcher/http_gateway`` server, open-loop
HTTP clients with heavy-tail prompts, mid-stream client disconnects
(RST'd sockets), one worker SIGKILL, and a rolling fleet upgrade under
live traffic. ASSERTS the front-door contract: zero accepted-request
loss, disconnect→cancel frees slots (occupancy and prefix refs back to
0), bitwise greedy parity on completed requests vs an unfaulted
single-engine run, all upgrade waves complete, watchdog raise everywhere.
Prints one JSON line.

Chaos soak drill (``python drills.py --chaos [steps] [--chaos-seed N]``, CI
tier): a supervisor loop trains a tiny model to a target step count under
seeded random preemptions (each takes a just-in-time ``preempt``-tag
checkpoint and kills the generation), one NaN step, and a transient
``io_flaky`` checkpoint-write fault, relaunching a fresh engine from
'latest' after every preemption. ASSERTS the elastic contract: >= 2
preemptions and >= 1 retried write survived, the survivor reaches the
target step count, and its final-step loss is BITWISE the clean
uninterrupted run's (batches are keyed on the device step, so skip/resume
replay exactly the data the clean run saw). Prints one JSON line with
preemption/resume/retry counts.
"""

import json
import os
import subprocess
import sys
import time


def _cpu_drill_env():
    """Every CPU-pinned correctness drill that spawns workers: pin the
    platform and apply the compile-cache rule (utils/jax_env.py) — parent
    and workers share one cache, so repeat drills are warm."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from deepspeed_tpu.utils.jax_env import use_compile_cache

    use_compile_cache()


def _fault_smoke(rate: float) -> int:
    """Serving fault-injection smoke: inject NaN-logit faults at ``rate``
    during a CPU serving run and assert the engine degrades instead of
    corrupting or leaking (see module docstring). In-process and
    CPU-pinned — this is a correctness smoke, not a throughput number."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference import InferenceEngine
    from deepspeed_tpu.inference.serving import Request, ServingEngine
    from deepspeed_tpu.models.transformer import Model, TransformerConfig

    t0 = time.perf_counter()
    cfg = TransformerConfig(
        vocab_size=97, max_seq_len=128, num_layers=2, num_heads=4,
        hidden_size=32, dtype=jnp.float32, loss_chunk_size=0,
        decode_attn="xla", pos_emb="rotary",
    )
    engine = InferenceEngine(model=Model(cfg), config={"dtype": "fp32"})
    srv = ServingEngine(engine, config={
        "n_slots": 4,
        "max_seq_len": 128,
        "max_queue_len": 32,
        "fault_injection": {
            "enabled": True, "seed": 0, "rate": rate,
            "sites": ["garbage_logits"],
        },
    })
    rng = np.random.default_rng(0)
    reqs = [
        Request(uid=i, prompt=rng.integers(1, 97, size=(int(rng.integers(4, 24)),)).astype(np.int32),
                max_new_tokens=8)
        for i in range(24)
    ]
    results = srv.serve(reqs)
    snap = srv.telemetry_snapshot()
    counters = snap["metrics"]["counters"]
    gauges = snap["metrics"]["gauges"]

    # -- the resilience contract, via the shared oracle library ------------
    from deepspeed_tpu.resilience.invariants import (
        check, occupancy_drained, occupancy_view, single_decode_program,
        zero_accepted_loss)

    check(zero_accepted_loss([r.uid for r in reqs], results))
    recovered = counters.get("resilience/recovered", 0)
    injected = counters.get("resilience/injected_faults", 0)
    assert injected > 0, (
        f"fault rate {rate} injected nothing over ~{len(reqs) * 9} "
        "opportunities — raise --fault-rate")
    assert recovered > 0, (
        "faults were injected but no quarantined request recovered "
        f"(counters: { {k: v for k, v in counters.items() if 'resil' in k} })")
    # no slot leak: engine drained, occupancy gauge back to 0, decode
    # never retraced — the occupancy oracle covers active/prefilling/queue
    # and the free+quarantined==slots accounting
    check(occupancy_drained([occupancy_view(srv, name="srv")]))
    assert gauges.get("serving/active_slots", -1) == 0, gauges
    check(single_decode_program({"srv": srv.compile_counts()["decode"]}))

    from collections import Counter as _Counter

    statuses = _Counter(r.status for r in results.values())
    print(json.dumps({
        "metric": "serving fault-injection smoke (recovered requests)",
        "value": int(recovered),
        "unit": "requests",
        # CPU-pinned correctness smoke: never a perf datapoint
        "platform": "cpu",
        "fault_rate": rate,
        "n_requests": len(reqs),
        "statuses": dict(statuses),
        "injected_faults": int(injected),
        "resilience": {k.split("/", 1)[1]: v for k, v in counters.items()
                       if k.startswith("resilience/")},
        "quarantined_slots": sorted(srv.quarantined_slots),
        "elapsed_s": round(time.perf_counter() - t0, 2),
    }), flush=True)
    return 0


def _chaos(steps: int, seed: int) -> int:
    """Chaos soak drill (see module docstring): preempt/NaN/io_flaky faults
    with relaunches must reach the same step count and final-step loss as a
    clean run. In-process and CPU-pinned — a correctness soak, not a
    throughput number."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import random
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models.transformer import Model, TransformerConfig
    from deepspeed_tpu.resilience import PreemptionSignal

    t0 = time.perf_counter()
    B, V, S = 8, 128, 32

    def build_engine(fault_cfg=None, save_dir=""):
        cfg = TransformerConfig(
            vocab_size=V, max_seq_len=S, num_layers=2, num_heads=4,
            hidden_size=32, dtype=jnp.float32, loss_chunk_size=0,
        )
        ds = {
            "train_batch_size": B,
            "train_micro_batch_size_per_gpu": B,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 1},
            "steps_per_print": 10**9,
            "mesh": {"data": -1},
        }
        if fault_cfg is not None:
            ds["resilience"] = {
                "enabled": True,
                "max_consecutive_bad_steps": 3,
                "preemption": {"enabled": False, "save_dir": save_dir,
                               "tag": "preempt"},
                "retry": {"max_attempts": 3, "base_delay_s": 0.01,
                          "max_delay_s": 0.05},
                "fault_injection": {"enabled": True, "seed": seed,
                                    **fault_cfg},
            }
        engine, _, _, _ = deepspeed_tpu.initialize(model=Model(cfg), config=ds)
        return engine

    def batch_for(step):
        # DEVICE-step-keyed deterministic data: a skipped/preempted step is
        # re-drawn on replay, so the applied-update sequence — and therefore
        # the final loss — is bitwise the clean run's
        rng = np.random.default_rng(seed * 100003 + step)
        return {"tokens": rng.integers(0, V, size=(B, S + 1)).astype(np.int32)}

    # -- clean reference run -----------------------------------------------
    clean = build_engine()
    m = None
    while clean.get_global_step() < steps:
        m = clean.train_batch(batch_for(clean.get_global_step()))
    clean_loss = float(np.asarray(jax.device_get(m["loss"])))
    assert clean.get_global_step() == steps

    # -- chaos plan (seeded): 2 preemptions, 1 NaN step, 1 transient write --
    plan_rng = random.Random(seed)
    candidates = list(range(2, steps))
    preempt_steps = sorted(plan_rng.sample(candidates, k=2))
    nan_step = plan_rng.choice([s for s in candidates if s not in preempt_steps])

    tallies = {"preemptions": 0, "resumes": 0, "ckpt_retries": 0,
               "nan_skipped_steps": 0, "jit_checkpoints": 0}

    def absorb(engine):
        counters = engine.telemetry.registry.snapshot()["counters"]
        for k in tallies:
            tallies[k] += int(counters.get(f"resilience/{k}", 0))

    with tempfile.TemporaryDirectory() as ckpt_dir:
        remaining = list(preempt_steps)
        generations = 0
        final_loss = None
        while True:
            generations += 1
            # a correct run is bounded at 1 + planned preemptions; a
            # recovery regression must FAIL the drill, not hang CI
            assert generations <= len(preempt_steps) + 1, (
                "relaunch loop exceeded the planned-preemption bound",
                generations, tallies)
            engine = build_engine(
                {"preempt_steps": remaining, "nan_grad_steps": [nan_step],
                 # only the first generation's JIT save hits the flaky write
                 "io_flaky_writes": [1] if generations == 1 else []},
                save_dir=ckpt_dir)
            if generations > 1:
                engine.load_checkpoint(ckpt_dir)  # 'latest' -> preempt tag
            try:
                m = None
                while engine.get_global_step() < steps:
                    m = engine.train_batch(batch_for(engine.get_global_step()))
                final_loss = float(np.asarray(jax.device_get(m["loss"])))
                absorb(engine)
                break
            except PreemptionSignal as e:
                # transient-preemption model: the relaunched reservation is
                # not re-evicted at the same instant — drop the fired step
                remaining = [s for s in remaining if s != e.step + 1]
                absorb(engine)
                del engine
        survivor_steps = steps

    # -- the elastic contract, asserted ------------------------------------
    from deepspeed_tpu.resilience.invariants import Violation, check

    assert tallies["preemptions"] >= 2, tallies
    assert tallies["resumes"] >= 2, tallies
    assert tallies["ckpt_retries"] >= 1, (
        "the io_flaky transient write was never retried", tallies)
    assert tallies["nan_skipped_steps"] >= 1, tallies
    # training-side spelling of the parity oracle: one scalar, same name
    check([] if final_loss == clean_loss else [Violation(
        "bitwise_parity_vs_reference",
        f"survivor final-step loss {final_loss!r} != clean run "
        f"{clean_loss!r} — resume is not bitwise")])

    print(json.dumps({
        "metric": "chaos soak drill (injected faults survived)",
        "value": int(tallies["preemptions"] + tallies["ckpt_retries"]
                     + tallies["nan_skipped_steps"]),
        "unit": "faults",
        # CPU-pinned correctness soak: never a perf datapoint
        "platform": "cpu",
        "target_steps": steps,
        "survivor_steps": survivor_steps,
        "generations": generations,
        "preempt_steps": preempt_steps,
        "nan_step": nan_step,
        "final_loss": final_loss,
        "clean_loss": clean_loss,
        "loss_bitwise_match": final_loss == clean_loss,
        "resilience": tallies,
        "seed": seed,
        "elapsed_s": round(time.perf_counter() - t0, 2),
    }), flush=True)
    return 0


def _chaos_serving(seed: int) -> int:
    """Cross-process serving chaos drill (``drills.py --chaos-serving``):
    3 REAL worker processes behind the Router's RPC transport; one is
    SIGKILL'd mid-prefill and one mid-decode. Asserts the fleet contract
    across genuine OS process boundaries: every accepted request reaches a
    terminal state, every completed greedy stream is BIT-IDENTICAL to an
    unfaulted single-engine run in this process (workers rebuild identical
    params from the spec), the supervisor respawns both corpses within its
    backoff budget and the replacements serve traffic, and the merged
    telemetry snapshot attributes the dead workers' piggybacked timelines
    to the right replica ids. Workers run with the RecompileWatchdog in
    RAISE mode throughout — a new XLA program shape on any worker fails
    the drill. In-process transport-fault variants live in tests/test_rpc.py;
    this drill is the real-process proof. CPU-pinned correctness soak."""
    _cpu_drill_env()
    import signal

    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference import InferenceEngine, Router
    from deepspeed_tpu.inference.serving import Request, ServingEngine
    from deepspeed_tpu.launcher.serving_worker import WorkerSupervisor
    from deepspeed_tpu.models.transformer import Model, TransformerConfig
    from deepspeed_tpu.telemetry import request_timeline

    t0 = time.perf_counter()
    serving_cfg = {
        "n_slots": 2, "max_seq_len": 128, "watchdog_mode": "raise",
        # chunked prefill makes admission span several router steps, so
        # the mid-PREFILL kill window is real, not a race
        "chunked_prefill": {"enabled": True, "chunk_size": 16},
    }
    model_spec = {"vocab_size": 97, "max_seq_len": 128, "num_layers": 2,
                  "num_heads": 4, "hidden_size": 32, "dtype": "float32",
                  "loss_chunk_size": 0, "decode_attn": "xla",
                  "pos_emb": "rotary"}
    spec = {"model": model_spec, "engine_dtype": "fp32",
            "serving": serving_cfg}

    # -- unfaulted single-engine reference (identical PRNGKey(0) params) --
    cfg = TransformerConfig(**{**model_spec, "dtype": jnp.float32})
    ref_srv = ServingEngine(
        InferenceEngine(model=Model(cfg), config={"dtype": "fp32"}),
        config=serving_cfg)
    rng = np.random.default_rng(seed)
    prompts = {i: rng.integers(0, 97, size=int(rng.integers(5, 24))).astype(np.int32)
               for i in range(6)}
    prompts[6] = rng.integers(0, 97, size=90).astype(np.int32)  # mid-prefill bait
    for j in range(7, 12):  # spares: kill-2 bait + respawn traffic
        prompts[j] = rng.integers(0, 97, size=int(rng.integers(5, 24))).astype(np.int32)

    def mk(uid):
        return Request(uid=uid, prompt=prompts[uid], max_new_tokens=24)

    for uid in sorted(prompts):
        ref_srv.submit(mk(uid))
    ref = {u: r.tokens for u, r in ref_srv.drain().items()}
    assert all(r.status == "ok" for r in ref_srv.drain().values())

    sup = WorkerSupervisor(
        spec, 3,
        transport={"call_timeout_s": 120.0, "boot_timeout_s": 300.0,
                   "heartbeat_timeout_s": 30.0, "base_delay_s": 0.05,
                   "max_delay_s": 0.2, "jitter": 0.0},
        respawn_backoff={"max_attempts": 10, "base_delay_s": 0.2,
                         "max_delay_s": 1.0, "jitter": 0.25},
        seed=seed)
    submitted: set = set()
    try:
        clients = sup.start()
        router = Router(config={"router": {"replicas": 3,
                                           "health": {"timeout": 60.0}}},
                        replica_engines=clients)
        rid_to_slot = {0: 0, 1: 1, 2: 2}

        def drive_until_terminal(uids):
            for _ in range(400):
                router.step(now=0.0)
                if all(u in router.results for u in uids):
                    return
            raise AssertionError(
                f"uids {sorted(set(uids) - set(router.results))} never "
                "reached a terminal state")

        # -- phase 1: kill a worker MID-PREFILL ---------------------------
        for uid in range(6):
            router.submit(mk(uid))
            submitted.add(uid)
        router.step(now=0.0)
        router.step(now=0.0)  # shorts admitted, decoding
        router.submit(mk(6))
        submitted.add(6)
        victim_prefill = router.owner_of(6)
        router.step(now=0.0)  # long prompt enters chunked prefill
        sup.kill(rid_to_slot[victim_prefill], signal.SIGKILL)
        drive_until_terminal(list(submitted))
        assert router.replica_states()[victim_prefill] == "dead"

        # -- phase 2: kill another worker MID-DECODE ----------------------
        for uid in (7, 8):
            router.submit(mk(uid))
            submitted.add(uid)
        router.step(now=0.0)
        router.step(now=0.0)  # decoding
        victim_decode = router.owner_of(7)
        if victim_decode is None or victim_decode == victim_prefill:
            victim_decode = router.owner_of(8)
        assert victim_decode is not None and victim_decode != victim_prefill
        sup.kill(rid_to_slot[victim_decode], signal.SIGKILL)
        drive_until_terminal(list(submitted))

        # -- the fleet contract, via the shared oracle library ------------
        from deepspeed_tpu.resilience.invariants import (
            bitwise_parity_vs_reference, check, exactly_once_failover,
            single_decode_program, zero_accepted_loss)

        check(zero_accepted_loss(submitted, router.results))
        bad_status = {u: router.results[u].status for u in submitted
                      if not router.results[u].ok}
        assert not bad_status, f"non-ok terminals: {bad_status}"
        check(bitwise_parity_vs_reference(
            {u: router.results[u] for u in submitted}, ref,
            uids=sorted(submitted), statuses=None,
            min_compared=len(submitted)))
        stats = router.router_stats()
        check(exactly_once_failover(stats, min_recovered=2))

        # -- supervisor respawn within the backoff budget -----------------
        t_respawn = time.monotonic()
        dead_slots = sup.poll()
        assert sorted(dead_slots) == sorted(
            rid_to_slot[r] for r in (victim_prefill, victim_decode))
        for slot in dead_slots:
            new_client = sup.respawn(slot)
            rid = router.attach_replica(new_client)
            rid_to_slot[rid] = slot
        respawn_s = time.monotonic() - t_respawn
        assert sup.respawns == 2
        # budget: 2 x (backoff <= 1.25s + boot); boots measured ~3-5s cold
        assert respawn_s < 2 * (1.25 + 300.0), respawn_s

        # respawned replicas serve fresh traffic (3 idle healthy replicas,
        # 3 requests -> least-loaded puts one on each, incl. both rookies)
        for uid in (9, 10, 11):
            router.submit(mk(uid))
            submitted.add(uid)
        rookie_rids = [r for r in router.replica_states()
                       if r > 2]  # attached after the kills
        assert any(router.owner_of(u) in rookie_rids for u in (9, 10, 11))
        drive_until_terminal([9, 10, 11])
        # min_compared forces all three to be ok-status AND bit-equal
        check(bitwise_parity_vs_reference(
            {u: router.results[u] for u in (9, 10, 11)}, ref,
            uids=(9, 10, 11), min_compared=3))

        # -- merged snapshot attribution + watchdog-raise inventory -------
        snap = router.telemetry_snapshot()
        for victim in (victim_prefill, victim_decode):
            dead_snap = snap["replicas"][victim]
            assert "unreachable" in dead_snap
            mirror = dead_snap["request_trace"]
            assert mirror and all(e["replica_id"] == victim for e in mirror)
        tl = request_timeline(snap, 6)
        fo = [e for e in tl if e["event"] == "failover"]
        assert fo and fo[0]["from_replica"] == victim_prefill
        # the dead worker never stored its mid-prefill KV anywhere a
        # replay could see — its pool died with the process; bit-equality
        # above is the proof. Reachable replicas: ONE decode program each.
        decode_compiles = {}
        for r, state in router.replica_states().items():
            if state == "dead":
                continue
            decode_compiles[r] = router._replicas[r].engine.compile_counts()["decode"]
        check(single_decode_program(decode_compiles))

        rpc_totals = {}
        for r in router._replicas:
            stats_fn = getattr(r.engine, "rpc_stats", None)
            if stats_fn is None:
                continue
            for k, v in stats_fn().items():
                if isinstance(v, (int, float)) and not k.startswith("call_sec"):
                    rpc_totals[k] = rpc_totals.get(k, 0) + v

        from collections import Counter as _Counter

        statuses = _Counter(router.results[u].status for u in submitted)
        print(json.dumps({
            "metric": "serving kill-9 chaos drill (failed-over requests recovered)",
            "value": int(stats["failovers_recovered"]),
            "unit": "requests",
            # CPU-pinned correctness soak: never a perf datapoint
            "platform": "cpu",
            "workers": 3,
            "kills": {"mid_prefill_rid": victim_prefill,
                      "mid_decode_rid": victim_decode},
            "n_requests": len(submitted),
            "statuses": dict(statuses),
            "greedy_bitwise_match": True,
            "respawns": sup.respawns,
            "respawn_wait_s": round(respawn_s, 2),
            "rpc": rpc_totals,
            "seed": seed,
            "elapsed_s": round(time.perf_counter() - t0, 2),
        }), flush=True)
        return 0
    finally:
        sup.shutdown()


def _disagg_drill(seed: int) -> int:
    """Disaggregated prefill/decode drill (``drills.py --disagg``): the
    role-split fleet's headline proof, three phases —

      1. IN-PROCESS parity matrix: a 2-prefill + 2-decode fleet vs the
         co-located single-replica fleet, across the chunked-prefill +
         prefix-cache matrix with and without speculation. Every greedy
         stream must be BITWISE identical; the tokens/sec ratio vs the
         co-located run is measured and reported (never gated — CPU).
      2. PER-POOL autoscaling: an arrival burst must draw at least one
         scale decision in EACH pool (prefill on queue/backlog, decode on
         occupancy/parked handoffs), and both pools must return to their
         floors after the burst.
      3. MID-HANDOFF SIGKILL over REAL worker processes: two prefill-role
         + one decode-role workers; the prefill worker streaming the
         second KV handoff is SIGKILL'd between export windows. Zero
         accepted-request loss, bitwise parity with the co-located
         reference, exactly-once failover, and the dead verdict on the
         corpse are all asserted.

    Emits one JSON row with handoff p50/p99, per-pool replica counts and
    scale decisions, and the tokens/sec ratio as flat ``disagg_*`` keys.
    CPU-pinned correctness soak, never a perf datapoint."""
    _cpu_drill_env()
    import signal

    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference import InferenceEngine, Router
    from deepspeed_tpu.inference.serving import Request, ServingEngine
    from deepspeed_tpu.launcher.serving_worker import WorkerSupervisor
    from deepspeed_tpu.models.transformer import Model, TransformerConfig
    from deepspeed_tpu.resilience.invariants import (
        bitwise_parity_vs_reference, check, exactly_once_failover,
        zero_accepted_loss)

    t0 = time.perf_counter()
    serving_cfg = {
        "n_slots": 2, "max_seq_len": 128, "watchdog_mode": "raise",
        "chunked_prefill": {"enabled": True, "chunk_size": 16},
        "prefix_cache": {"enabled": True, "n_slots": 4, "block": 8,
                         "max_prefix_len": 64, "insert_policy": "always"},
    }
    model_spec = {"vocab_size": 97, "max_seq_len": 128, "num_layers": 2,
                  "num_heads": 4, "hidden_size": 32, "dtype": "float32",
                  "loss_chunk_size": 0, "decode_attn": "xla",
                  "pos_emb": "rotary"}
    cfg = TransformerConfig(**{**model_spec, "dtype": jnp.float32})
    eng = InferenceEngine(model=Model(cfg), config={"dtype": "fp32"})
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, 97, size=int(n)).astype(np.int32)
               for n in rng.integers(8, 42, size=6)]

    def mk(uid, i, max_new=12):
        return Request(uid=uid, prompt=prompts[i], max_new_tokens=max_new)

    # -- phase 1: in-process parity matrix + tokens/sec ratio -------------
    legs = {"base": {}, "speculation": {
        "speculation": {"enabled": True, "depth": 4, "ngram_min_match": 2}}}
    ratio = None
    for leg, extra in legs.items():
        base = Router(eng, config={**serving_cfg, **extra}, replicas=1)
        for i in range(6):
            base.submit(mk(i, i))
        t_base = time.perf_counter()
        ref = base.drain()
        t_base = time.perf_counter() - t_base
        dis = Router(eng, config={
            **serving_cfg, **extra,
            "router": {"disagg": {"enabled": True, "prefill_replicas": 2,
                                  "decode_replicas": 2}}})
        for i in range(6):
            dis.submit(mk(i, i))
        t_dis = time.perf_counter()
        out = dis.drain()
        t_dis = time.perf_counter() - t_dis
        assert all(ref[i].ok and out[i].ok for i in range(6)), (
            leg, {i: out[i].status for i in range(6)})
        # shared parity oracle: role-split output must be bit-identical
        # to the co-located fleet's (min_compared pins all six)
        check(bitwise_parity_vs_reference(
            out, ref, uids=range(6), min_compared=6))
        st = dis.router_stats()
        assert st["disagg"]["handoffs"] == 6, (leg, st["disagg"])
        if leg == "base":
            # same tokens both runs, so the ratio is pure wall-clock
            ratio = round(t_base / t_dis, 3)

    # -- phase 2: per-pool autoscaling over an arrival burst --------------
    asc_router = Router(eng, config={
        **serving_cfg,
        "router": {
            "disagg": {"enabled": True, "prefill_replicas": 1,
                       "decode_replicas": 1, "prefill_max_replicas": 2,
                       "decode_max_replicas": 2, "prefill_scale_up_queue": 3,
                       "prefill_scale_up_backlog": 3,
                       "decode_scale_up_occupancy": 0.75},
            "autoscale": {"enabled": True, "min_replicas": 1,
                          "max_replicas": 4, "up_consecutive": 2,
                          "down_consecutive": 2, "cooldown_s": 0.0}}})
    for i in range(8):
        asc_router.submit(Request(
            uid=i, prompt=rng.integers(1, 97, size=20 + i).astype(np.int32),
            max_new_tokens=16))
    t = 0.0
    while asc_router._owner:
        t += 1.0
        asc_router.step(now=t, enforce_deadlines=False)
    for _ in range(30):
        t += 1.0
        asc_router.step(now=t)
    assert all(r.ok for r in asc_router.results.values())
    asc = asc_router._autoscaler.describe()
    decisions = {"prefill": 0, "decode": 0}
    for e in asc["events"]:
        if (e["kind"] in ("scale_up", "scale_up_started", "scale_down")
                and e.get("pool") in decisions):
            decisions[e["pool"]] += 1
    assert decisions["prefill"] >= 1, asc["events"]
    assert decisions["decode"] >= 1, asc["events"]
    assert all(p["target"] == 1 for p in asc["pools"].values()), asc["pools"]

    # -- phase 3: mid-handoff SIGKILL over real worker processes ----------
    spec = {"model": model_spec, "engine_dtype": "fp32",
            "serving": serving_cfg}
    ref_srv = ServingEngine(eng, config=serving_cfg)
    for i in range(6):
        ref_srv.submit(mk(100 + i, i))
    ref = {u: r.tokens for u, r in ref_srv.drain().items()}

    sup = WorkerSupervisor(
        spec, 3,
        transport={"call_timeout_s": 120.0, "boot_timeout_s": 300.0,
                   "heartbeat_timeout_s": 30.0, "base_delay_s": 0.05,
                   "max_delay_s": 0.2, "jitter": 0.0},
        roles={0: "prefill", 1: "prefill", 2: "decode"},
        seed=seed)
    try:
        clients = sup.start()
        router = Router(
            config={"router": {"replicas": 3, "health": {"timeout": 60.0},
                               "disagg": {"enabled": True}}},
            replica_engines=clients)

        # arm the mid-handoff kill: the SECOND KV window export anywhere in
        # the fleet SIGKILLs its own worker first, so the stream dies with
        # the process BETWEEN import_begin and the window landing — the
        # exact failure site the handoff state machine must replay across
        kill_state = {"exports": 0, "victim": None}

        def _arm(slot, client):
            orig = client.kv_export_window

            def _export(uid, start, width, compression="none"):
                kill_state["exports"] += 1
                if kill_state["exports"] == 2 and kill_state["victim"] is None:
                    kill_state["victim"] = slot
                    os.kill(sup.proc(slot).pid, signal.SIGKILL)
                    sup.proc(slot).wait(timeout=30)
                return orig(uid, start, width, compression=compression)

            client.kv_export_window = _export

        for slot in (0, 1):
            _arm(slot, clients[slot])

        for i in range(6):
            router.submit(mk(100 + i, i))
        for _ in range(600):
            router.step(now=0.0)
            if all(100 + i in router.results for i in range(6)):
                break
        check(zero_accepted_loss([100 + i for i in range(6)],
                                 router.results))
        bad = {u: router.results[u].status for u in ref
               if not router.results[u].ok}
        assert not bad, f"non-ok terminals: {bad}"
        check(bitwise_parity_vs_reference(
            router.results, ref, uids=sorted(ref), statuses=None,
            min_compared=len(ref)))
        assert kill_state["victim"] is not None, "kill never fired"
        victim_rid = kill_state["victim"]  # slot == rid at boot
        stats = router.router_stats()
        assert router.replica_states()[victim_rid] == "dead"
        check(exactly_once_failover(stats, min_recovered=1))
        assert stats["disagg"]["handoffs"] == 6, stats["disagg"]
        hist = router.telemetry.registry.snapshot()["histograms"]
        handoff_sec = hist.get("router/disagg/handoff_sec", {})

        from collections import Counter as _Counter

        statuses = _Counter(r.status for r in router.results.values())
        print(json.dumps({
            "metric": "disaggregated prefill/decode drill "
                      "(handoffs under mid-transfer kill)",
            "value": int(stats["disagg"]["handoffs"]),
            "unit": "handoffs",
            "platform": "cpu",
            "workers": {"prefill": 2, "decode": 1},
            "kill": {"victim_rid": victim_rid, "site": "kv_export_window#2"},
            "n_requests": len(ref),
            "statuses": dict(statuses),
            "greedy_bitwise_match": True,
            "failovers_recovered": int(stats["failovers_recovered"]),
            "disagg_handoff_p50_sec": round(handoff_sec.get("p50", 0.0), 6),
            "disagg_handoff_p99_sec": round(handoff_sec.get("p99", 0.0), 6),
            "disagg_prefill_replicas": stats["disagg"]["prefill_replicas"],
            "disagg_decode_replicas": stats["disagg"]["decode_replicas"],
            "disagg_tokens_per_sec_vs_colocated_ratio": ratio,
            "scale_decisions": decisions,
            "seed": seed,
            "elapsed_s": round(time.perf_counter() - t0, 2),
        }), flush=True)
        return 0
    finally:
        sup.shutdown()


def _surge(n_requests: int, seed: int) -> int:
    """Trace-driven surge/failure drill (``drills.py --surge [n]``): the
    self-healing elastic fleet end-to-end. One REAL worker process behind
    the Router + a ledger-driven Autoscaler over the WorkerSupervisor; an
    open-loop trace (two bursts, heavy-tail prompt lengths, mixed
    priorities) drives arrivals while one worker is SIGKILL'd mid-trace.
    ASSERTS: the autoscaler grows the fleet to max under the burst,
    recovers the killed worker (supervisor respawn + attach as a NEW rid),
    shrinks back to min after the burst, every ACCEPTED request reaches a
    terminal state, completed (ok) greedy streams are BITWISE the
    unfaulted single-engine run's, brownout engaged while saturated at
    max, and no worker compiled a second decode program (watchdog RAISE
    everywhere). Emits one JSON row with scale/respawn/brownout/shed
    counts and p99 TTFT. CPU-pinned correctness soak, never a perf
    datapoint."""
    _cpu_drill_env()
    import signal

    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference import Autoscaler, InferenceEngine, Router
    from deepspeed_tpu.inference.serving import Request, ServingEngine
    from deepspeed_tpu.launcher.serving_worker import WorkerSupervisor
    from deepspeed_tpu.resilience import RequestRejected

    t0 = time.perf_counter()
    serving_cfg = {
        "n_slots": 2, "max_seq_len": 128, "watchdog_mode": "raise",
        "chunked_prefill": {"enabled": True, "chunk_size": 16},
    }
    model_spec = {"vocab_size": 97, "max_seq_len": 128, "num_layers": 2,
                  "num_heads": 4, "hidden_size": 32, "dtype": "float32",
                  "loss_chunk_size": 0, "decode_attn": "xla",
                  "pos_emb": "rotary"}
    spec = {"model": model_spec, "engine_dtype": "fp32",
            "serving": serving_cfg}

    # -- the trace: bursty arrivals, heavy-tail prompts, mixed priorities.
    # Worker boots are ASYNC (the fleet keeps serving while one boots, ~3s
    # each), so the pressure must be sustained — burst A trips the first
    # scale-up, burst B holds the up-signal through the serial boots (and
    # the post-kill respawn), burst C's high-priority stragglers land on
    # the saturated, browned-out fleet: the priority-shed path's bait.
    rng = np.random.default_rng(seed)
    n_a = max(4, int(n_requests * 0.3))           # burst A at t ~ 0
    n_c = max(2, int(n_requests * 0.2))           # high-priority burst C
    n_b = max(4, n_requests - n_a - n_c)          # burst B mid-trace
    prompts, priorities, offsets = {}, {}, {}
    for uid in range(n_a + n_b + n_c):
        heavy = rng.random() < 0.2                # heavy-tail prompt length
        prompts[uid] = rng.integers(
            0, 97, size=int(rng.integers(48, 90) if heavy
                            else rng.integers(5, 24))).astype(np.int32)
        if uid < n_a:
            offsets[uid] = float(rng.uniform(0.0, 0.3))
            priorities[uid] = int(rng.integers(0, 2))
        elif uid < n_a + n_b:
            offsets[uid] = float(rng.uniform(2.5, 3.2))
            priorities[uid] = int(rng.integers(0, 2))
        else:
            offsets[uid] = float(rng.uniform(4.5, 5.5))
            priorities[uid] = 2

    def mk(uid, arrival=0.0):
        return Request(uid=uid, prompt=prompts[uid], max_new_tokens=32,
                       arrival_time=arrival, priority=priorities[uid])

    # -- unfaulted single-engine reference (identical PRNGKey(0) params) --
    from deepspeed_tpu.models.transformer import Model, TransformerConfig

    cfg = TransformerConfig(**{**model_spec, "dtype": jnp.float32})
    ref_srv = ServingEngine(
        InferenceEngine(model=Model(cfg), config={"dtype": "fp32"}),
        config=serving_cfg)
    for uid in sorted(prompts):
        ref_srv.submit(mk(uid))
    ref = {u: r.tokens for u, r in ref_srv.drain().items()}

    sup = WorkerSupervisor(
        spec, 1,
        transport={"call_timeout_s": 120.0, "boot_timeout_s": 300.0,
                   "heartbeat_timeout_s": 30.0, "base_delay_s": 0.05,
                   "max_delay_s": 0.2, "jitter": 0.0},
        respawn_backoff={"max_attempts": 10, "base_delay_s": 0.2,
                         "max_delay_s": 1.0, "jitter": 0.25},
        seed=seed)
    try:
        clients = sup.start()
        router = Router(
            config={"router": {
                "replicas": 1, "max_queue_len": 12,
                "health": {"timeout": 60.0},
                "autoscale": {
                    "enabled": True, "min_replicas": 1, "max_replicas": 3,
                    "scale_up_queue": 3, "scale_up_load": 3.0,
                    "scale_down_load": 0.5, "up_consecutive": 2,
                    "down_consecutive": 8, "cooldown_s": 0.75,
                    "brownout_deadline_s": 60.0},
            }},
            replica_engines=clients)
        asc = Autoscaler(router, supervisor=sup, slots={0: 0})

        def healthy_n():
            return sum(1 for s in router.replica_states().values()
                       if s == "healthy")

        now0 = router.now()
        arrivals = sorted(
            (mk(uid, arrival=now0 + offsets[uid]) for uid in prompts),
            key=lambda r: r.arrival_time)
        kill_at = now0 + 2.0
        submitted, rejected = set(), {}
        killed_slot = None
        max_healthy = 1
        deadline = time.monotonic() + 420.0
        while arrivals or not submitted <= set(router.results):
            assert time.monotonic() < deadline, (
                "surge drill wall-clock cap exceeded",
                sorted(submitted - set(router.results)))
            now = router.now()
            while arrivals and arrivals[0].arrival_time <= now:
                req = arrivals.pop(0)
                try:
                    router.submit(req)
                    submitted.add(req.uid)
                except RequestRejected as e:
                    rejected[req.uid] = e.reason
            if (killed_slot is None and now >= kill_at and healthy_n() >= 2
                    and router._owner):
                victim_rid = router.owner_of(next(iter(router._owner)))
                if victim_rid is not None and asc.slot_of(victim_rid) is not None:
                    killed_slot = asc.slot_of(victim_rid)
                    sup.kill(killed_slot, signal.SIGKILL)
            router.step()
            max_healthy = max(max_healthy, healthy_n())
            if all(r.engine.idle for r in router._replicas if r.stepped):
                # idle trough between bursts: pace the loop like a real
                # serving driver instead of hot-spinning state polls
                time.sleep(0.01)

        # feed the MFU signal path once through a real fleet snapshot
        # (unrated on CPU: the signal stays null, the plumbing is exercised)
        asc.observe(router.telemetry_snapshot())

        # -- post-burst: the fleet must shrink back to min ----------------
        # (a boot that landed just as the last request finished still
        # counts toward the peak — the fleet DID grow to it)
        shrink_deadline = time.monotonic() + 120.0
        while (healthy_n() > 1 or asc._boots
               or any(s == "draining"
                      for s in router.replica_states().values())):
            assert time.monotonic() < shrink_deadline, (
                "fleet never scaled back down", router.replica_states())
            router.step()
            max_healthy = max(max_healthy, healthy_n())
            time.sleep(0.02)

        counters = router.telemetry.registry.snapshot()["counters"]
        asc_c = {k.rsplit("/", 1)[1]: int(v) for k, v in counters.items()
                 if k.startswith("router/autoscale/")}

        # -- the elastic contract, asserted -------------------------------
        assert max_healthy >= 3, (
            f"fleet never grew to max under the burst (peak {max_healthy})")
        assert killed_slot is not None, "the mid-trace SIGKILL never fired"
        assert sup.respawns >= 1 and asc_c.get("respawns", 0) >= 1, (
            "the killed worker was never recovered", asc_c)
        assert asc_c.get("scale_ups", 0) >= 2, asc_c
        assert asc_c.get("scale_downs", 0) >= 1, asc_c
        assert asc_c.get("brownouts", 0) >= 1, (
            "the saturated-at-max window never browned out", asc_c)
        assert healthy_n() == 1 and asc.target == 1
        from deepspeed_tpu.resilience.invariants import (
            bitwise_parity_vs_reference, check, single_decode_program,
            zero_accepted_loss)

        check(zero_accepted_loss(submitted, router.results))
        ok_uids = [u for u in submitted if router.results[u].ok]
        check(bitwise_parity_vs_reference(
            router.results, ref, uids=ok_uids, statuses=None,
            min_compared=len(ok_uids)))
        # watchdog RAISE held on every reachable worker: ONE decode program
        check(single_decode_program(
            {rid: router._replicas[rid].engine.compile_counts()["decode"]
             for rid, state in router.replica_states().items()
             if state == "healthy"}))

        from collections import Counter as _Counter

        statuses = _Counter(router.results[u].status for u in submitted)
        ttfts = sorted(router.results[u].ttft for u in ok_uids)
        p99 = ttfts[min(len(ttfts) - 1,
                        int(0.99 * (len(ttfts) - 1) + 0.5))] if ttfts else None
        print(json.dumps({
            "metric": "serving surge drill (autoscale events)",
            "value": int(asc_c.get("scale_ups", 0)
                         + asc_c.get("scale_downs", 0)
                         + asc_c.get("respawns", 0)),
            "unit": "events",
            # CPU-pinned correctness soak: never a perf datapoint
            "platform": "cpu",
            "n_requests": len(prompts),
            "accepted": len(submitted),
            "rejected_at_submit": dict(
                _Counter(rejected.values())) if rejected else {},
            "statuses": dict(statuses),
            "max_healthy": max_healthy,
            "autoscale": asc_c,
            "respawns": sup.respawns,
            "greedy_bitwise_match_ok_set": True,
            "ttft_p99_s": None if p99 is None else round(p99, 3),
            "seed": seed,
            "elapsed_s": round(time.perf_counter() - t0, 2),
        }), flush=True)
        return 0
    finally:
        sup.shutdown()


def _gateway_chaos(seed: int) -> int:
    """Front-door chaos drill (``drills.py --gateway-chaos``): REAL worker
    processes (TCP transport) behind a REAL HTTP/SSE gateway, driven by
    open-loop HTTP clients with heavy-tail prompts. Mid-trace: several
    clients DISCONNECT mid-stream, one worker is SIGKILL'd (recovered via
    supervisor respawn + attach), and a rolling upgrade replaces every
    worker generation under live traffic. ASSERTS: zero accepted-request
    loss (every uid the gateway accepted reaches a terminal state —
    disconnected streams terminate ``cancelled``, their slots freed),
    bitwise greedy parity on COMPLETED requests vs an unfaulted
    single-engine run, slot AND prefix-pool-ref occupancy back to 0 on
    every live replica, the rolling upgrade completing with all waves
    ``upgraded``, and the RecompileWatchdog in RAISE mode everywhere (ONE
    decode program per worker). The FLIGHT RECORDER rides the whole drill:
    rings + SLO classification on every worker, rings + incidents on the
    router — the SIGKILL must leave >=1 autopsy bundle whose timeline
    shows the dead verdict and the failover storm, ``bin/dstpu_autopsy``
    must exit 0 on it, and the measured ring-sampling overhead must stay
    under 1% of decode step wall (the docs/observability.md claim).
    CPU-pinned correctness soak, never a perf datapoint."""
    _cpu_drill_env()
    import glob
    import shutil
    import signal
    import socket as socket_mod
    import struct
    import subprocess
    import tempfile
    import threading

    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference import InferenceEngine, Router
    from deepspeed_tpu.inference.serving import Request, ServingEngine
    from deepspeed_tpu.launcher.http_gateway import HttpGateway
    from deepspeed_tpu.launcher.serving_worker import WorkerSupervisor
    from deepspeed_tpu.models.transformer import Model, TransformerConfig

    t0 = time.perf_counter()
    incidents_dir = tempfile.mkdtemp(prefix="dstpu-gw-chaos-incidents-")
    serving_cfg = {
        "n_slots": 2, "max_seq_len": 128, "watchdog_mode": "raise",
        # chunked prefill + prefix cache: the full program inventory under
        # kill/upgrade churn, and prefix-ref accounting to prove clean
        "chunked_prefill": {"enabled": True, "chunk_size": 16},
        "prefix_cache": {"enabled": True, "n_slots": 4, "block": 4,
                         "insert_policy": "always", "min_hits": 1},
        # flight recorder, worker side: rings sampled from the step loop
        # (flushed to the router over step-reply piggyback) + SLO terminal
        # classification. Thresholds are generous — this is a CPU soak;
        # the drill proves the recorder rides along, not that CPUs are
        # fast. Engine-side incidents stay off: the router-side recorder
        # owns the drill's bundle story.
        "timeseries": {"enabled": True, "interval_s": 0.25},
        "slo": {"enabled": True, "ttft_s": 120.0, "tpot_s": 60.0},
    }
    model_spec = {"vocab_size": 97, "max_seq_len": 128, "num_layers": 2,
                  "num_heads": 4, "hidden_size": 32, "dtype": "float32",
                  "loss_chunk_size": 0, "decode_attn": "xla",
                  "pos_emb": "rotary"}
    spec = {"model": model_spec, "engine_dtype": "fp32",
            "serving": serving_cfg}

    # -- the trace: open-loop bursts, heavy-tail prompts, a shared prefix
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, 97, size=12).astype(np.int32)  # prefix bait
    n_req = 15
    prompts, offsets, disconnect_after = {}, {}, {}
    for i in range(n_req):
        heavy = rng.random() < 0.25
        tail = rng.integers(0, 97, size=int(
            rng.integers(40, 80) if heavy else rng.integers(4, 16)))
        if rng.random() < 0.4:  # shared-prefix traffic warms the pool
            prompts[i] = np.concatenate([shared, tail]).astype(np.int32)
        else:
            prompts[i] = tail.astype(np.int32)
        # burst A lands immediately; burst B spans the kill-recovery and
        # rolling-upgrade window so both happen under live streams
        offsets[i] = (float(rng.uniform(0.0, 0.5)) if i < 6
                      else float(rng.uniform(2.0, 9.0)))
    for i in (1, 7, 10):  # mid-stream disconnectors (2-4 tokens in)
        disconnect_after[i] = int(rng.integers(2, 5))

    def mk(i):
        return Request(uid=1000 + i, prompt=prompts[i], max_new_tokens=24)

    # -- unfaulted single-engine reference (identical PRNGKey(0) params) --
    cfg = TransformerConfig(**{**model_spec, "dtype": jnp.float32})
    ref_srv = ServingEngine(
        InferenceEngine(model=Model(cfg), config={"dtype": "fp32"}),
        config=serving_cfg)
    # serve() (not submit+drain): greedy tokens are identical either way,
    # but serve()'s finite clock drives the ring sampler — this run doubles
    # as the sampling-overhead probe asserted below
    ref = {u - 1000: r.tokens
           for u, r in ref_srv.serve([mk(i) for i in sorted(prompts)]).items()}

    # -- the fleet: 3 TCP workers + supervisor + router + gateway ---------
    sup = WorkerSupervisor(
        spec, 3,
        transport={"family": "tcp", "host": "127.0.0.1", "port_base": 0,
                   "call_timeout_s": 120.0, "boot_timeout_s": 300.0,
                   "heartbeat_timeout_s": 30.0, "base_delay_s": 0.05,
                   "max_delay_s": 0.2, "jitter": 0.0},
        respawn_backoff={"max_attempts": 10, "base_delay_s": 0.2,
                         "max_delay_s": 1.0, "jitter": 0.25},
        seed=seed)
    state = {"slots": {}, "respawns": 0, "upgrade_started": False,
             "killed_slot": None}
    try:
        clients = sup.start()
        router = Router(config={"router": {"replicas": 3, "max_queue_len": 16,
                                           "health": {"timeout": 60.0}},
                                # flight recorder, router side: fleet rings
                                # + replica mirrors, SLO burn tracking, and
                                # the incident recorder the SIGKILL must
                                # leave a bundle in
                                "timeseries": {"enabled": True,
                                               "interval_s": 0.25},
                                "slo": {"enabled": True, "ttft_s": 120.0,
                                        "tpot_s": 60.0},
                                # window_after_s spans the whole drill: the
                                # kill, the failover storm, the respawn AND
                                # the rolling-upgrade waves coalesce into
                                # ONE bundle, finalized by the force-flush
                                # below once the upgrade is done — the
                                # autopsy timeline then shows the full arc
                                "incidents": {"enabled": True,
                                              "dir": incidents_dir,
                                              "window_before_s": 60.0,
                                              "window_after_s": 600.0}},
                        replica_engines=clients)
        state["slots"] = {0: 0, 1: 1, 2: 2}
        kill_at = [None]  # router-clock kill time, armed once serving

        def on_tick():
            # runs on the gateway's serve loop thread — the only thread
            # allowed to mutate fleet membership. Respawn BOOTS run on a
            # background thread (the autoscaler's discipline): a boot
            # inline here would freeze every client's token stream for
            # its duration — exactly the stall PR 11 removed
            now = router.now()
            if (state["killed_slot"] is None and kill_at[0] is not None
                    and now >= kill_at[0] and router._owner):
                victim = router.owner_of(next(iter(router._owner)))
                if victim is not None and victim in state["slots"]:
                    state["killed_slot"] = state["slots"][victim]
                    sup.kill(state["killed_slot"], signal.SIGKILL)
            boot = state.get("boot")
            if boot is not None and not boot["thread"].is_alive():
                state["boot"] = None
                if boot.get("client") is not None:
                    new_rid = router.attach_replica(boot["client"])
                    state["slots"][new_rid] = boot["slot"]
                    state["respawns"] += 1
            for slot in sup.poll():
                if state.get("boot") is not None:
                    break  # one replacement boot at a time (1 kill planned)
                rid = next((r for r, s in state["slots"].items()
                            if s == slot), None)
                if rid is not None:
                    router.mark_dead(rid)  # corpse: immediate dead verdict
                    state["slots"].pop(rid)
                holder = {"slot": slot, "client": None}

                def boot_run(holder=holder):
                    holder["client"] = sup.respawn(holder["slot"])

                holder["thread"] = threading.Thread(target=boot_run,
                                                    daemon=True)
                state["boot"] = holder
                holder["thread"].start()
            if (not state["upgrade_started"] and state["respawns"] >= 1
                    and sum(1 for s in router.replica_states().values()
                            if s == "healthy") >= 3):
                # the corpse is recovered: roll the whole fleet to the new
                # generation spec while burst B streams through it
                state["upgrade_started"] = True
                new_spec = dict(spec)
                new_spec["serving"] = {**serving_cfg, "seed": seed + 1}
                router.rolling_upgrade(supervisor=sup,
                                       slots=dict(state["slots"]),
                                       spec=new_spec)

        gw = HttpGateway(router, {"stream_poll_s": 0.01,
                                  "write_timeout_s": 30.0},
                         on_tick=on_tick)
        gw.start()
        kill_at[0] = router.now() + 1.5

        # -- open-loop HTTP clients --------------------------------------
        outcomes: dict[int, dict] = {}

        def client(i):
            time.sleep(offsets[i])
            out = {"i": i}
            outcomes[i] = out
            body = json.dumps({"prompt": [int(t) for t in prompts[i]],
                               "max_new_tokens": 24}).encode()
            req = (b"POST /v1/generate HTTP/1.1\r\nHost: gw\r\n"
                   b"Content-Length: %d\r\n\r\n" % len(body)) + body
            s = socket_mod.create_connection(("127.0.0.1", gw.port),
                                             timeout=240.0)
            try:
                s.sendall(req)
                data, headers_done = b"", False
                while True:
                    chunk = s.recv(65536)
                    if not chunk:
                        break
                    data += chunk
                    if not headers_done and b"\r\n\r\n" in data:
                        headers_done = True
                        head, data = data.split(b"\r\n\r\n", 1)
                        out["status_code"] = int(
                            head.split(b" ", 2)[1].decode())
                        for line in head.split(b"\r\n"):
                            if line.lower().startswith(b"x-dstpu-uid:"):
                                out["uid"] = int(line.split(b":")[1])
                    n_tok = data.count(b"event: token")
                    if (i in disconnect_after and out.get("uid") is not None
                            and n_tok >= disconnect_after[i]):
                        # vanish abruptly: linger-0 close sends a genuine
                        # RST mid-stream (the fault the gateway must turn
                        # into Router.cancel)
                        s.setsockopt(socket_mod.SOL_SOCKET,
                                     socket_mod.SO_LINGER,
                                     struct.pack("ii", 1, 0))
                        out["disconnected_at"] = n_tok
                        return
                    if b"event: done" in data and data.endswith(b"\n\n"):
                        break
                for block in data.split(b"\n\n"):
                    if b"event: done" in block:
                        for line in block.splitlines():
                            if line.startswith(b"data: "):
                                out["done"] = json.loads(line[6:])
                if out.get("status_code") not in (None, 200):
                    # rejected (429/503): body is one JSON document
                    try:
                        out["rejected"] = json.loads(data.decode())
                    except ValueError:
                        pass
            finally:
                try:
                    s.close()
                except OSError:
                    pass

        threads = [threading.Thread(target=client, args=(i,), daemon=True)
                   for i in sorted(prompts)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=420.0)
        assert not any(t.is_alive() for t in threads), "client threads hung"

        # -- wait out the upgrade + all terminals -------------------------
        deadline = time.monotonic() + 300.0
        accepted = {out["uid"]: i for i, out in outcomes.items()
                    if out.get("uid") is not None}
        while True:
            st = router.upgrade_status()
            done = (st is not None and st["state"] != "running"
                    and all(router.result(u) is not None for u in accepted)
                    and not any(s == "draining"
                                for s in router.replica_states().values()))
            if done:
                break
            assert time.monotonic() < deadline, (
                "drill wall-clock cap exceeded",
                st, router.replica_states())
            time.sleep(0.1)

        # stop the serve loop BEFORE asserting: the RPC sockets are owned
        # by the loop thread, and the direct compile_counts/prefix-stats
        # calls below would otherwise interleave frames with its steps
        gw.stop()

        # -- the front-door contract, asserted ----------------------------
        assert state["killed_slot"] is not None, "the SIGKILL never fired"
        assert state["respawns"] >= 1, "the corpse was never recovered"
        # zero accepted-request loss: every uid the gateway accepted is
        # terminal; disconnected streams terminate cancelled
        from deepspeed_tpu.resilience.invariants import (
            bitwise_parity_vs_reference, check, occupancy_drained,
            occupancy_view, single_decode_program, zero_accepted_loss)

        terminals = {u: router.result(u) for u in accepted
                     if router.result(u) is not None}
        check(zero_accepted_loss(accepted, terminals))
        statuses = {u: terminals[u].status for u in accepted}
        disconnected_uids = [outcomes[i]["uid"] for i in disconnect_after
                             if outcomes[i].get("uid") is not None
                             and "disconnected_at" in outcomes[i]]
        assert disconnected_uids, "no mid-stream disconnect happened"
        cancelled = [u for u in disconnected_uids
                     if statuses[u] == "cancelled"]
        assert cancelled, (
            "no vanished reader was cancelled fleet-side", statuses)
        # bitwise greedy parity on completed requests vs the unfaulted run
        # (reference re-keyed uid -> clean tokens via the client index);
        # min_compared guards the vacuous-green case the old hand-rolled
        # parity_checked >= 6 assert covered
        ok_uids = [u for u, st_u in statuses.items() if st_u == "ok"]
        check(bitwise_parity_vs_reference(
            terminals, {u: ref[i] for u, i in accepted.items()},
            uids=ok_uids, statuses=None, min_compared=6))
        for u in ok_uids:
            i = accepted[u]
            done_ev = outcomes[i].get("done")
            if done_ev is not None:
                assert done_ev["tokens"] == [int(t) for t in ref[i]], (
                    "SSE-streamed tokens diverged", i)
        parity_checked = len(ok_uids)
        assert parity_checked >= 6, (
            f"only {parity_checked} completed requests to compare",
            statuses)
        # the rolling upgrade replaced every generation under traffic
        st = router.upgrade_status()
        assert st["state"] == "done", st
        upgraded = [w for w in st["waves"] if w.get("outcome") == "upgraded"]
        assert len(upgraded) >= 3, st
        # slot + prefix-ref occupancy back to 0 on every live replica;
        # watchdog RAISE held (ONE decode program per reachable worker)
        live = [r for r in router._replicas if r.state == "healthy"]
        assert live, router.replica_states()
        check(occupancy_drained(
            occupancy_view(r.engine, name=r.rid) for r in live))
        # raise-mode held: ONE decode program ever (a post-upgrade rookie
        # that saw no traffic has 0 — never 2)
        check(single_decode_program(
            {r.rid: r.engine.compile_counts()["decode"] for r in live}))

        # -- flight recorder: the SIGKILL left an autopsy bundle ----------
        # the dead verdict staged replica_dead, the failover storm
        # coalesced onto it, and step() finalized it window_after_s later;
        # drain() would force-flush a straggler
        if router.incidents is not None and router.incidents.pending:
            router.incidents.flush(router._incident_context)
        bundles = sorted(glob.glob(os.path.join(incidents_dir,
                                                "incident-*.json")))
        assert bundles, "SIGKILL produced no incident bundle"
        dead_bundles = [p for p in bundles if "replica_dead" in p]
        assert dead_bundles, ("no replica_dead bundle among", bundles)
        with open(dead_bundles[0]) as f:
            bundle = json.load(f)
        trig_kinds = [t["kind"] for t in bundle["triggers"]]
        assert trig_kinds[0] == "replica_dead", trig_kinds
        assert "failover" in trig_kinds, (
            "the failover storm did not coalesce onto the dead verdict",
            trig_kinds)
        assert bundle["rings"]["router"]["series"], (
            "bundle carries no ring window")
        assert any(ev.get("event") == "failover"
                   for ev in bundle.get("trace_events", ())), (
            "no failover edge in the bundle timeline")
        # the same bundle correlates the rolling-upgrade waves against the
        # ring window (context captured post-upgrade by the flush above)
        assert bundle.get("upgrade", {}).get("state") == "done", (
            "bundle missing the completed upgrade", bundle.get("upgrade"))
        assert len(bundle["upgrade"].get("waves", [])) >= 3
        # the CLI contract the bundle feeds: autopsy renders it, exit 0
        autopsy = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "bin", "dstpu_autopsy")
        proc = subprocess.run([sys.executable, autopsy, dead_bundles[0]],
                              capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, (proc.returncode, proc.stdout,
                                      proc.stderr)
        assert "failover" in proc.stdout, "autopsy timeline lost the story"
        assert "wave" in proc.stdout, "autopsy timeline lost the upgrade"

        snap = gw.telemetry_snapshot()
        counters = snap["router"]["metrics"]["counters"]
        gw_c = {k.split("/", 1)[1]: int(v) for k, v in counters.items()
                if k.startswith("gateway/")}

        # the ring window spans the upgrade: the snapshot rings + the
        # upgrade wave log come from the same fleet clock, so the report
        # CLI / autopsy can correlate the waves against queue-depth cells
        assert "rings" in snap["router"] and "slo" in snap["router"]
        assert snap["router"]["incidents"], "snapshot lost the bundle index"

        # measured sampling overhead: ring walk wall vs decode step wall
        # (the docs/observability.md "<1% of step time" claim is MEASURED
        # here, not asserted from faith). The LOADED reference engine is
        # the probe — same sampler, full trace, cannot be retired
        # mid-drill. Fleet replicas are reported but not asserted: a
        # near-idle replica keeps sampling on health steps while its
        # decode denominator stays tiny, so its ratio measures idleness,
        # not per-step cost
        ref_reg = ref_srv.telemetry.registry
        ref_ring = ref_reg.get("serving/ring_sample_sec")
        ref_step = ref_reg.get("serving/decode_step_sec")
        assert ref_ring is not None and ref_step is not None
        overhead_pct = 100.0 * ref_ring.value / ref_step.summary()["sum"]
        assert overhead_pct < 1.0, (
            "ring sampling cost >=1% of decode step wall under load",
            overhead_pct)
        fleet_overhead_pct = []
        for rep in snap["replicas"].values():
            m = rep.get("metrics") or {}
            ring = (m.get("counters") or {}).get("serving/ring_sample_sec")
            step = ((m.get("histograms") or {})
                    .get("serving/decode_step_sec") or {}).get("sum")
            if ring is not None and step:
                fleet_overhead_pct.append(round(100.0 * ring / step, 4))

        from collections import Counter as _Counter

        print(json.dumps({
            "metric": "gateway chaos drill (disconnects+kill+upgrade survived)",
            "value": int(len(cancelled) + state["respawns"]
                         + len(upgraded)),
            "unit": "events",
            # CPU-pinned correctness soak: never a perf datapoint
            "platform": "cpu",
            "workers": 3,
            "transport": "tcp",
            "n_requests": n_req,
            "accepted": len(accepted),
            "rejected_at_submit": len([o for o in outcomes.values()
                                       if o.get("status_code", 200) != 200]),
            "statuses": dict(_Counter(statuses.values())),
            "disconnects": len(disconnected_uids),
            "cancelled_on_disconnect": len(cancelled),
            "respawns": state["respawns"],
            "upgrade_waves": len(upgraded),
            "greedy_bitwise_match_ok_set": True,
            "parity_checked": parity_checked,
            "gateway": gw_c,
            "incident_bundles": len(bundles),
            "bundle_triggers": dict(_Counter(trig_kinds)),
            "ring_sample_overhead_pct": round(overhead_pct, 4),
            "fleet_ring_sample_pct_incl_idle": fleet_overhead_pct,
            "seed": seed,
            "elapsed_s": round(time.perf_counter() - t0, 2),
        }), flush=True)
        return 0
    finally:
        sup.shutdown()
        shutil.rmtree(incidents_dir, ignore_errors=True)


def _router_chaos_child(cfg_path: str) -> int:
    """The CONTROL-PLANE process of the ``--router-chaos`` drill: worker
    supervisor (ADOPTING any still-running workers a dead predecessor left
    behind via their fsync'd pidfiles), a journaled Router (cold-start
    recovery happens in its constructor when the journal holds state), and
    the HTTP/SSE gateway. Prints a ``gw_ready`` JSON line (port + recovery
    counters), serves until SIGTERM, then drains and prints a ``final``
    stats line. The parent SIGKILLs the FIRST incarnation mid-traffic and
    starts a second one against the same workdir + journal."""
    _cpu_drill_env()
    with open(cfg_path) as f:
        cfg = json.load(f)

    from deepspeed_tpu.inference import Router
    from deepspeed_tpu.launcher.http_gateway import HttpGateway
    from deepspeed_tpu.launcher.serving_worker import WorkerSupervisor
    from deepspeed_tpu.resilience.preemption import PreemptionGuard

    guard = PreemptionGuard(["SIGTERM"])
    guard.install()
    sup = WorkerSupervisor(
        cfg["spec"], cfg["workers"], workdir=cfg["workdir"],
        transport={"family": "tcp", "host": "127.0.0.1", "port_base": 0,
                   "call_timeout_s": 120.0, "boot_timeout_s": 300.0,
                   "heartbeat_timeout_s": 30.0, "base_delay_s": 0.05,
                   "max_delay_s": 0.2, "jitter": 0.0},
        seed=int(cfg["seed"]))
    adopted = sup.adopt()
    for slot in range(int(cfg["workers"])):
        if slot not in adopted:
            sup.spawn(slot)
    clients = [sup.client(s) for s in range(int(cfg["workers"]))]
    router = Router(
        config={"router": {
            "replicas": int(cfg["workers"]), "max_queue_len": 32,
            "health": {"timeout": 60.0},
            "journal": {"enabled": True, "path": cfg["journal"]}}},
        replica_engines=clients)

    def counters():
        snap = router.telemetry.registry.snapshot()["counters"]
        return {k: int(v) for k, v in snap.items()
                if k.startswith(("router/recovery/", "router/journal/",
                                 "gateway/", "tenant/"))}

    # --tenant-chaos rides the same child with a gateway auth block (the
    # one config that drives bearer auth + DWRR weights + quotas)
    gw_conf = {"stream_poll_s": 0.01, "write_timeout_s": 30.0}
    gw_conf.update(cfg.get("gateway") or {})
    gw = HttpGateway(router, gw_conf, gateway_id=1)
    gw.start()
    print(json.dumps({"event": "gw_ready", "port": gw.port,
                      "pid": os.getpid(), "adopted": sorted(adopted),
                      "recovery": counters()}), flush=True)
    while not guard.pending():
        time.sleep(0.05)
    gw.stop()
    # the serve loop is stopped: direct per-replica queries are safe now
    final = {"event": "final", "replica_states": router.replica_states(),
             "loads": {}, "decode_compiles": {}, "prefix_leaks": {},
             "tenant_counters": {}, "counters": counters()}
    for rid, state in router.replica_states().items():
        if state != "healthy":
            continue
        eng = router._replicas[rid].engine
        final["loads"][str(rid)] = int(eng.load)
        final["decode_compiles"][str(rid)] = int(
            eng.compile_counts().get("decode", 0))
        pstats = eng.prefix_cache_stats()
        final["prefix_leaks"][str(rid)] = [
            e for e in (pstats or {}).get("entries", []) if e.get("refs")]
        # engine-side per-tenant accounting (sheds/quota rejects/latency
        # live in each replica's private registry), summed fleet-wide
        esnap = eng.telemetry_snapshot()
        for k, v in (esnap.get("metrics", {}).get("counters", {})).items():
            if k.startswith("tenant/"):
                final["tenant_counters"][k] = (
                    final["tenant_counters"].get(k, 0) + int(v))
    for k, v in counters().items():  # router-side tenant counters too
        if k.startswith("tenant/"):
            final["tenant_counters"][k] = (
                final["tenant_counters"].get(k, 0) + int(v))
    print(json.dumps(final), flush=True)
    if cfg.get("shutdown_workers"):
        sup.shutdown()
    return 0


def _router_chaos(seed: int) -> int:
    """Control-plane chaos drill (``drills.py --router-chaos``): 3 REAL TCP
    worker processes under live HTTP/SSE traffic; the gateway+router
    process is SIGKILL'd mid-prefill and mid-stream, then RESTARTED
    against the same request journal and worker workdir. The restarted
    brain adopts the surviving workers from their pidfiles, replays the
    journal, reconciles the owner map over the new reconcile RPC round,
    and clients ride the restart on idempotency keys + ``Last-Event-ID``
    SSE resume. ASSERTS the crash-safe control-plane contract: zero
    accepted-request loss, a retried idempotency key never forks a uid,
    >= 1 SSE stream resumed across the restart with one bitwise-identical
    token stream, bitwise greedy parity vs an unfaulted single-engine run
    on EVERY completion, journal replay idempotence, slot/prefix-ref
    occupancy back to 0, and watchdog RAISE held on every worker.
    CPU-pinned correctness soak, never a perf datapoint."""
    _cpu_drill_env()
    import signal
    import socket as socket_mod
    import tempfile
    import threading

    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference import InferenceEngine
    from deepspeed_tpu.inference.serving import Request, ServingEngine
    from deepspeed_tpu.models.transformer import Model, TransformerConfig

    t0 = time.perf_counter()
    serving_cfg = {
        "n_slots": 2, "max_seq_len": 128, "watchdog_mode": "raise",
        "chunked_prefill": {"enabled": True, "chunk_size": 16},
        "prefix_cache": {"enabled": True, "n_slots": 4, "block": 4,
                         "insert_policy": "always", "min_hits": 1},
    }
    model_spec = {"vocab_size": 97, "max_seq_len": 128, "num_layers": 2,
                  "num_heads": 4, "hidden_size": 32, "dtype": "float32",
                  "loss_chunk_size": 0, "decode_attn": "xla",
                  "pos_emb": "rotary"}
    spec = {"model": model_spec, "engine_dtype": "fp32",
            "serving": serving_cfg}

    # -- the trace: burst A rides the kill, burst B rides the restart.
    # Client 0 is the mid-PREFILL bait (90-token prompt through 16-token
    # chunks); several burst-A streams are mid-DECODE at the kill.
    rng = np.random.default_rng(seed)
    n_req = 12
    prompts, offsets, blocking = {}, {}, set()
    prompts[0] = rng.integers(0, 97, size=90).astype(np.int32)
    offsets[0] = 0.0
    for i in range(1, n_req):
        prompts[i] = rng.integers(
            0, 97, size=int(rng.integers(5, 24))).astype(np.int32)
        offsets[i] = (float(rng.uniform(0.0, 0.4)) if i < 6
                      else float(rng.uniform(2.0, 6.0)))
        if i % 4 == 3:
            blocking.add(i)  # non-streaming clients ride the key alone

    # -- unfaulted single-engine reference (identical PRNGKey(0) params) --
    cfg = TransformerConfig(**{**model_spec, "dtype": jnp.float32})
    ref_srv = ServingEngine(
        InferenceEngine(model=Model(cfg), config={"dtype": "fp32"}),
        config=serving_cfg)
    for i in sorted(prompts):
        ref_srv.submit(Request(uid=i, prompt=prompts[i], max_new_tokens=24))
    ref = {i: [int(t) for t in r.tokens]
           for i, r in ref_srv.drain().items()}

    workdir = tempfile.mkdtemp(prefix="dstpu_rc_")
    journal = os.path.join(workdir, "router.journal")
    cfg_path = os.path.join(workdir, "drill.json")
    child_cfg = {"spec": spec, "workers": 3, "workdir": workdir,
                 "journal": journal, "seed": seed}

    def launch(shutdown_workers=False, tag="c1"):
        cc = dict(child_cfg, shutdown_workers=shutdown_workers)
        path = os.path.join(workdir, f"drill_{tag}.json")
        with open(path, "w") as f:
            json.dump(cc, f)
        log = open(os.path.join(workdir, f"{tag}.log"), "w")
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--router-chaos-child", path],
            stdout=log, stderr=subprocess.STDOUT)
        return proc, log.name

    def wait_ready(log_path, proc, timeout=600.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                with open(log_path) as f:
                    raise AssertionError(
                        f"control-plane child exited rc={proc.returncode} "
                        f"during boot: {f.read()[-2000:]}")
            try:
                with open(log_path) as f:
                    for line in f:
                        line = line.strip()
                        if line.startswith("{"):
                            try:
                                ev = json.loads(line)
                            except ValueError:
                                continue
                            if ev.get("event") == "gw_ready":
                                return ev
            except OSError:
                pass
            time.sleep(0.1)
        raise AssertionError("control-plane child never printed gw_ready")

    def read_final(log_path):
        with open(log_path) as f:
            for line in f:
                line = line.strip()
                if line.startswith("{"):
                    try:
                        ev = json.loads(line)
                    except ValueError:
                        continue
                    if ev.get("event") == "final":
                        return ev
        return None

    state = {"port": None, "restart": threading.Event()}
    outcomes = {i: {"attempts": 0, "uids": set(), "tokens": {},
                    "resume_ids": [], "resumed": False, "done": None}
                for i in prompts}

    def http_attempt(i, out, resume_after):
        """One POST; returns ('done', result) | ('dead', last_id) |
        ('refused', None) when the gateway is not up."""
        body = {"prompt": [int(t) for t in prompts[i]],
                "max_new_tokens": 24}
        if i in blocking:
            body["stream"] = False
        payload = json.dumps(body).encode()
        headers = (f"POST /v1/generate HTTP/1.1\r\nHost: d\r\n"
                   f"Content-Length: {len(payload)}\r\n"
                   f"X-DSTPU-Idempotency-Key: rc{seed}-{i}\r\n")
        if resume_after is not None:
            headers += f"Last-Event-ID: {resume_after}\r\n"
        try:
            s = socket_mod.create_connection(("127.0.0.1", state["port"]),
                                             timeout=240.0)
        except OSError:
            return "refused", None
        try:
            s.sendall(headers.encode() + b"\r\n" + payload)
            data, headers_done, first_id = b"", False, None
            while True:
                try:
                    chunk = s.recv(65536)
                except OSError:
                    chunk = b""
                if not chunk:
                    # connection died (the kill): report how far we got
                    last = max(out["tokens"], default=None)
                    return "dead", last
                data += chunk
                if not headers_done and b"\r\n\r\n" in data:
                    headers_done = True
                    head, data = data.split(b"\r\n\r\n", 1)
                    status = int(head.split(b" ", 2)[1].decode())
                    for line in head.split(b"\r\n"):
                        if line.lower().startswith(b"x-dstpu-uid:"):
                            out["uids"].add(int(line.split(b":")[1]))
                    if i in blocking:
                        # JSON document follows; read to socket close or
                        # content-length — simplest: read until close
                        cl = next((int(line.split(b":")[1])
                                   for line in head.split(b"\r\n")
                                   if line.lower().startswith(
                                       b"content-length:")), None)
                        while cl is not None and len(data) < cl:
                            chunk = s.recv(65536)
                            if not chunk:
                                break
                            data += chunk
                        if status != 200:
                            return "dead", None
                        doc = json.loads(data.decode())
                        out["uids"].add(int(doc["uid"]))
                        return "done", doc
                # parse complete SSE events as they arrive
                while b"\n\n" in data:
                    block, data = data.split(b"\n\n", 1)
                    ev_id, ev_name, ev_data = None, None, None
                    for line in block.splitlines():
                        if line.startswith(b"id: "):
                            ev_id = int(line[4:])
                        elif line.startswith(b"event: "):
                            ev_name = line[7:].decode()
                        elif line.startswith(b"data: "):
                            ev_data = json.loads(line[6:])
                    if ev_name == "token":
                        if first_id is None:
                            first_id = ev_id
                            out["resume_ids"].append(first_id)
                        tok = int(ev_data["token"])
                        prev = out["tokens"].get(ev_id)
                        assert prev is None or prev == tok, (
                            "re-delivered token diverged", i, ev_id)
                        out["tokens"][ev_id] = tok
                    elif ev_name == "done":
                        return "done", ev_data
        finally:
            try:
                s.close()
            except OSError:
                pass

    def client(i):
        time.sleep(offsets[i])
        out = outcomes[i]
        resume_after = None
        deadline = time.monotonic() + 600.0
        while time.monotonic() < deadline:
            out["attempts"] += 1
            kind, got = http_attempt(i, out, resume_after)
            if kind == "done":
                out["done"] = got
                return
            if kind == "refused":
                out["attempts"] -= 1  # never reached the gateway
                time.sleep(0.25)
                continue
            # the connection died mid-flight: wait out the restart, then
            # retry the SAME idempotency key — resuming the stream past
            # the last received token id when we got any
            state["restart"].wait(timeout=300.0)
            if got is not None:
                resume_after = got
                out["resumed"] = True
                out["resumed_from"] = got
        raise AssertionError(f"client {i} never finished")

    child = None
    try:
        child, log1 = launch(tag="c1")
        ready = wait_ready(log1, child)
        state["port"] = ready["port"]
        threads = [threading.Thread(target=client, args=(i,), daemon=True)
                   for i in sorted(prompts)]
        for t in threads:
            t.start()

        # -- the kill: long prompt accepted (mid-prefill bait) AND some
        # stream mid-decode (>= 2 tokens on the wire)
        kill_deadline = time.monotonic() + 300.0
        while True:
            assert time.monotonic() < kill_deadline, (
                "kill precondition never met",
                {i: dict(o, tokens=len(o["tokens"]))
                 for i, o in outcomes.items()})
            streaming = any(len(o["tokens"]) >= 2 for i, o in
                            outcomes.items() if i not in blocking and i != 0)
            if outcomes[0]["uids"] and streaming:
                break
            time.sleep(0.01)
        os.kill(child.pid, signal.SIGKILL)
        child.wait()
        kill_t = time.perf_counter()

        # -- restart the brain against the same journal + workdir --------
        child, log2 = launch(shutdown_workers=True, tag="c2")
        ready2 = wait_ready(log2, child)
        state["port"] = ready2["port"]
        state["restart"].set()
        for t in threads:
            t.join(timeout=600.0)
        assert not any(t.is_alive() for t in threads), "client threads hung"

        # -- drain the second brain and collect its final stats ----------
        os.kill(child.pid, signal.SIGTERM)
        child.wait(timeout=300.0)
        final = read_final(log2)
        assert final is not None, "restarted child printed no final stats"

        # -- the crash-safe control-plane contract, asserted -------------
        rec = ready2["recovery"]
        assert rec.get("router/recovery/recoveries") == 1, rec
        assert rec.get("router/recovery/adopted_requests", 0) >= 1, rec
        # zero accepted-request loss + bitwise parity on EVERY completion
        from deepspeed_tpu.resilience.invariants import (
            bitwise_parity_vs_reference, check)

        for i, out in outcomes.items():
            assert out["done"] is not None, (i, out)
            assert out["done"]["status"] == "ok", (i, out["done"])
            assert len(out["uids"]) == 1, (
                "a retried idempotency key forked a uid", i, out["uids"])
        # every client's terminal token list vs the unfaulted reference
        # (keys are client indices; the oracle reads bare lists)
        check(bitwise_parity_vs_reference(
            {i: out["done"]["tokens"] for i, out in outcomes.items()},
            ref, uids=sorted(outcomes), statuses=None,
            min_compared=len(outcomes)))
        for i, out in outcomes.items():
            if i not in blocking:
                # streamed-event continuity: every id present, in order
                n = len(ref[i])
                toks = [out["tokens"].get(k) for k in range(n)]
                assert toks == ref[i], (
                    "streamed tokens diverged/gapped", i, toks, ref[i])
        resumed = [i for i, o in outcomes.items() if o["resumed"]]
        assert resumed, "no SSE stream resumed across the restart"
        for i in resumed:
            # continuity: the resumed attempt's FIRST token id is exactly
            # one past the last id the dead gateway delivered — nothing
            # re-sent, nothing skipped (Last-Event-ID honored)
            ids = outcomes[i]["resume_ids"]
            if len(ids) >= 2:
                assert ids[1] == outcomes[i]["resumed_from"] + 1, (
                    "resume did not continue at Last-Event-ID + 1",
                    i, ids, outcomes[i]["resumed_from"])
        # occupancy back to 0, watchdog RAISE held, prefix refs clean
        assert final["loads"] and all(
            v == 0 for v in final["loads"].values()), final["loads"]
        from deepspeed_tpu.resilience.invariants import single_decode_program
        check(single_decode_program(final["decode_compiles"]))
        assert all(not v for v in final["prefix_leaks"].values()), final
        assert final["counters"].get("gateway/resumed_streams", 0) >= 1, (
            final["counters"])
        # journal replay is idempotent: two replays, equal states
        from deepspeed_tpu.inference.journal import replay as _replay
        assert _replay(journal) == _replay(journal)

        from collections import Counter as _Counter

        statuses = _Counter(o["done"]["status"] for o in outcomes.values())
        print(json.dumps({
            "metric": "router chaos drill (control-plane restart survived)",
            "value": int(rec.get("router/recovery/adopted_requests", 0)
                         + rec.get("router/recovery/recovered_results", 0)
                         + rec.get("router/recovery/redispatched", 0)
                         + len(resumed)),
            "unit": "requests",
            # CPU-pinned correctness soak: never a perf datapoint
            "platform": "cpu",
            "workers": 3,
            "transport": "tcp",
            "n_requests": n_req,
            "statuses": dict(statuses),
            "adopted_workers": ready2["adopted"],
            "recovery": {k.split("/", 2)[2]: v for k, v in rec.items()
                         if k.startswith("router/recovery/")},
            "resumed_streams": len(resumed),
            "greedy_bitwise_match": True,
            "restart_to_ready_s": round(time.perf_counter() - kill_t, 2),
            "seed": seed,
            "elapsed_s": round(time.perf_counter() - t0, 2),
        }), flush=True)
        return 0
    finally:
        if child is not None and child.poll() is None:
            try:
                os.kill(child.pid, signal.SIGKILL)
            except OSError:
                pass
        # reap any workers the drill leaked (pidfiles are the roster)
        try:
            for name in os.listdir(workdir):
                if name.startswith("w") and name.endswith(".pid"):
                    with open(os.path.join(workdir, name)) as f:
                        info = json.load(f)
                    try:
                        os.kill(int(info["pid"]), signal.SIGKILL)
                    except (OSError, ValueError):
                        pass
        except OSError:
            pass


def _tenant_chaos(seed: int) -> int:
    """Multi-tenant isolation drill (``drills.py --tenant-chaos``): a REAL
    2-worker TCP fleet behind the authenticated HTTP gateway, serving a
    conformant VICTIM tenant (weight 4), a 10x-concurrency AGGRESSOR
    tenant (weight 1, per-tenant quota), and an invalid-token ATTACKER.
    Phase A measures the victim's solo TTFT baseline on the same fleet;
    phase B unleashes the aggressor + attacker against fresh victim
    prompts, SIGKILLs the gateway+router process mid-stream, and restarts
    it against the same journal. ASSERTS the isolation contract: victim
    p99 TTFT within 2x of the solo baseline (250 ms timer-noise floor),
    ZERO victim sheds/rejects, the aggressor contained by its OWN quota
    (typed 429s, never victim degradation), every completed stream
    bitwise-identical to an unfaulted single-engine reference (zero
    cross-tenant contamination), tenant-scoped idempotency intact across
    the restart (the aggressor replaying the victim's key gets its OWN
    uid), per-tenant accounting rebuilt after the SIGKILL, no raw bearer
    token in the journal or child logs, and the decode program count flat
    (the tenant axis never becomes a traced operand). CPU-pinned
    correctness soak, never a perf datapoint."""
    _cpu_drill_env()
    import hashlib
    import signal
    import socket as socket_mod
    import tempfile
    import threading

    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference import InferenceEngine
    from deepspeed_tpu.inference.serving import Request, ServingEngine
    from deepspeed_tpu.models.transformer import Model, TransformerConfig

    t0 = time.perf_counter()
    vic_tok = f"tc-victim-{seed}-0123456789abcdef"
    agg_tok = f"tc-aggressor-{seed}-fedcba9876543210"
    sha = lambda s: hashlib.sha256(s.encode()).hexdigest()  # noqa: E731
    tenants_policy = {"victim": {"weight": 4.0},
                      "aggressor": {"weight": 1.0, "max_queued": 2}}
    serving_cfg = {
        "n_slots": 2, "max_seq_len": 128, "watchdog_mode": "raise",
        "chunked_prefill": {"enabled": True, "chunk_size": 16},
        "prefix_cache": {"enabled": True, "n_slots": 4, "block": 4,
                         "insert_policy": "always", "min_hits": 1},
        "tenants": tenants_policy,  # engine-side DWRR + quota
    }
    model_spec = {"vocab_size": 97, "max_seq_len": 128, "num_layers": 2,
                  "num_heads": 4, "hidden_size": 32, "dtype": "float32",
                  "loss_chunk_size": 0, "decode_attn": "xla",
                  "pos_emb": "rotary"}
    spec = {"model": model_spec, "engine_dtype": "fp32",
            "serving": serving_cfg}
    auth = {"enabled": True, "tenants": {
        "victim": dict(tenants_policy["victim"],
                       token_sha256=sha(vic_tok)),
        "aggressor": dict(tenants_policy["aggressor"],
                          token_sha256=sha(agg_tok)),
    }}

    # -- traces: phase A (solo) and phase B (contended) use DISJOINT
    # victim prompts so the prefix cache can't flatter the contended
    # numbers; each aggressor thread re-posts one fixed prompt
    rng = np.random.default_rng(seed)
    n_vic, n_agg = 8, 10
    vic_solo = {i: rng.integers(0, 97, size=int(rng.integers(5, 24)))
                .astype(np.int32) for i in range(n_vic)}
    vic_cont = {i: rng.integers(0, 97, size=int(rng.integers(5, 24)))
                .astype(np.int32) for i in range(n_vic)}
    agg_prompts = {j: rng.integers(0, 97, size=int(rng.integers(5, 16)))
                   .astype(np.int32) for j in range(n_agg)}
    VIC_NEW, AGG_NEW = 24, 8

    # -- unfaulted single-engine reference (identical PRNGKey(0) params):
    # the bitwise yardstick for BOTH tenants — any cross-tenant
    # contamination shows up as a token-stream mismatch
    tcfg = TransformerConfig(**{**model_spec, "dtype": jnp.float32})
    ref_srv = ServingEngine(
        InferenceEngine(model=Model(tcfg), config={"dtype": "fp32"}),
        config={k: v for k, v in serving_cfg.items() if k != "tenants"})
    uid = 0
    ref_map = {}
    for tag, prompts, mx in (("solo", vic_solo, VIC_NEW),
                             ("cont", vic_cont, VIC_NEW),
                             ("agg", agg_prompts, AGG_NEW)):
        for i in sorted(prompts):
            ref_srv.submit(Request(uid=uid, prompt=prompts[i],
                                   max_new_tokens=mx))
            ref_map[uid] = (tag, i)
            uid += 1
    ref = {ref_map[u]: [int(t) for t in r.tokens]
           for u, r in ref_srv.drain().items()}

    workdir = tempfile.mkdtemp(prefix="dstpu_tc_")
    journal = os.path.join(workdir, "router.journal")
    child_cfg = {"spec": spec, "workers": 2, "workdir": workdir,
                 "journal": journal, "seed": seed,
                 "gateway": {"auth": auth}}

    def launch(shutdown_workers=False, tag="c1"):
        cc = dict(child_cfg, shutdown_workers=shutdown_workers)
        path = os.path.join(workdir, f"drill_{tag}.json")
        with open(path, "w") as f:
            json.dump(cc, f)
        log = open(os.path.join(workdir, f"{tag}.log"), "w")
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--router-chaos-child", path],
            stdout=log, stderr=subprocess.STDOUT)
        return proc, log.name

    def wait_ready(log_path, proc, timeout=600.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                with open(log_path) as f:
                    raise AssertionError(
                        f"control-plane child exited rc={proc.returncode} "
                        f"during boot: {f.read()[-2000:]}")
            try:
                with open(log_path) as f:
                    for line in f:
                        line = line.strip()
                        if line.startswith("{"):
                            try:
                                ev = json.loads(line)
                            except ValueError:
                                continue
                            if ev.get("event") == "gw_ready":
                                return ev
            except OSError:
                pass
            time.sleep(0.1)
        raise AssertionError("control-plane child never printed gw_ready")

    def read_final(log_path):
        with open(log_path) as f:
            for line in f:
                line = line.strip()
                if line.startswith("{"):
                    try:
                        ev = json.loads(line)
                    except ValueError:
                        continue
                    if ev.get("event") == "final":
                        return ev
        return None

    state = {"port": None, "restart": threading.Event()}

    def post(body, *, token=None, idem=None, resume_after=None, out=None):
        """One POST. Returns ('done', doc) | ('status', (code, headers)) |
        ('dead', last_token_id) | ('refused', None). Streaming when
        ``out`` is given (records tokens + client-side TTFT there)."""
        payload = json.dumps(body).encode()
        head = (f"POST /v1/generate HTTP/1.1\r\nHost: d\r\n"
                f"Content-Length: {len(payload)}\r\n")
        if token is not None:
            head += f"Authorization: Bearer {token}\r\n"
        if idem is not None:
            head += f"X-DSTPU-Idempotency-Key: {idem}\r\n"
        if resume_after is not None:
            head += f"Last-Event-ID: {resume_after}\r\n"
        try:
            s = socket_mod.create_connection(("127.0.0.1", state["port"]),
                                             timeout=240.0)
        except OSError:
            return "refused", None
        try:
            s.sendall(head.encode() + b"\r\n" + payload)
            t_send = time.perf_counter()
            data, headers_done, status, hdrs = b"", False, None, {}
            while True:
                try:
                    chunk = s.recv(65536)
                except OSError:
                    chunk = b""
                if not chunk:
                    last = max(out["tokens"], default=None) if out else None
                    return "dead", last
                data += chunk
                if not headers_done and b"\r\n\r\n" in data:
                    headers_done = True
                    hblk, data = data.split(b"\r\n\r\n", 1)
                    status = int(hblk.split(b" ", 2)[1].decode())
                    for line in hblk.split(b"\r\n")[1:]:
                        k, _, v = line.decode().partition(":")
                        hdrs[k.strip().lower()] = v.strip()
                    if status != 200:
                        return "status", (status, hdrs)
                    if out is None:  # blocking mode: read the JSON doc
                        cl = int(hdrs.get("content-length", 0))
                        while len(data) < cl:
                            chunk = s.recv(65536)
                            if not chunk:
                                return "dead", None
                            data += chunk
                        return "done", json.loads(data.decode())
                    if "x-dstpu-uid" in hdrs:
                        out["uids"].add(int(hdrs["x-dstpu-uid"]))
                while out is not None and b"\n\n" in data:
                    block, data = data.split(b"\n\n", 1)
                    ev_id, ev_name, ev_data = None, None, None
                    for line in block.splitlines():
                        if line.startswith(b"id: "):
                            ev_id = int(line[4:])
                        elif line.startswith(b"event: "):
                            ev_name = line[7:].decode()
                        elif line.startswith(b"data: "):
                            ev_data = json.loads(line[6:])
                    if ev_name == "token":
                        if out.get("ttft") is None:
                            out["ttft"] = time.perf_counter() - t_send
                        tok = int(ev_data["token"])
                        prev = out["tokens"].get(ev_id)
                        assert prev is None or prev == tok, (
                            "re-delivered token diverged", ev_id)
                        out["tokens"][ev_id] = tok
                    elif ev_name == "done":
                        return "done", ev_data
        finally:
            try:
                s.close()
            except OSError:
                pass

    def run_victim_request(i, prompt, idem, outcomes, ttfts):
        """One victim request to completion, riding idempotency key +
        Last-Event-ID resume across gateway deaths."""
        out = outcomes[i] = {"tokens": {}, "uids": set(), "ttft": None,
                             "done": None, "resumed": False}
        resume_after = None
        deadline = time.monotonic() + 600.0
        while time.monotonic() < deadline:
            kind, got = post(
                {"prompt": [int(t) for t in prompt],
                 "max_new_tokens": VIC_NEW},
                token=vic_tok, idem=idem, resume_after=resume_after,
                out=out)
            if kind == "done":
                out["done"] = got
                if out["ttft"] is not None:
                    ttfts.append(out["ttft"])
                return
            assert kind != "status", (
                "victim got a non-200", i, got)  # zero rejects, typed
            if kind == "refused":
                time.sleep(0.25)
                continue
            state["restart"].wait(timeout=300.0)
            if got is not None:
                resume_after = got
                out["resumed"] = True
            out["ttft"] = None  # re-attempt measures its own TTFT
        raise AssertionError(f"victim request {i} never finished")

    def p99(xs):
        xs = sorted(xs)
        return xs[max(0, -(-99 * len(xs) // 100) - 1)]

    child = None
    try:
        child, log1 = launch(tag="c1")
        ready = wait_ready(log1, child)
        state["port"] = ready["port"]

        # -- phase A: solo victim baseline (one discarded warmup pays the
        # cold prefill buckets, then 8 measured requests)
        warm = {}
        run_victim_request("warm", vic_solo[0], f"tcw{seed}", warm, [])
        solo_out, solo_ttfts = {}, []
        for i in sorted(vic_solo):
            run_victim_request(i, vic_solo[i], f"tcs{seed}-{i}",
                               solo_out, solo_ttfts)
        for i in sorted(vic_solo):
            toks = solo_out[i]["done"]["tokens"]
            assert toks == ref[("solo", i)], ("solo parity", i)
        p99_solo = p99(solo_ttfts)

        # -- phase B: aggressor burst + attacker + mid-drill SIGKILL ------
        vic_state = {"done": 0, "cur_tokens": 0}
        cont_out, cont_ttfts = {}, []
        agg_stats = {"s429": 0, "s200": 0, "other": [], "parity": 0,
                     "retry_after": 0}
        attacker = {"codes": []}
        stop = threading.Event()

        def victim_loop():
            for i in sorted(vic_cont):
                run_victim_request(i, vic_cont[i], f"tcc{seed}-{i}",
                                   cont_out, cont_ttfts)
                vic_state["done"] += 1
            stop.set()

        def aggressor_loop(j):
            rounds = 0
            while not stop.is_set() and rounds < 40:
                rounds += 1
                kind, got = post(
                    {"prompt": [int(t) for t in agg_prompts[j]],
                     "max_new_tokens": AGG_NEW, "stream": False},
                    token=agg_tok)
                if kind == "done":
                    agg_stats["s200"] += 1
                    if got["tokens"] == ref[("agg", j)]:
                        agg_stats["parity"] += 1
                    else:
                        agg_stats["other"].append(("parity", j))
                elif kind == "status":
                    code, hdrs = got
                    if code == 429:
                        agg_stats["s429"] += 1
                        if "retry-after" in hdrs:
                            agg_stats["retry_after"] += 1
                        time.sleep(0.05)
                    else:
                        agg_stats["other"].append((code, j))
                elif kind == "refused":
                    state["restart"].wait(timeout=300.0)
                else:  # dead mid-read (the kill): just retry
                    state["restart"].wait(timeout=300.0)

        def attacker_loop():
            while not stop.is_set():
                for tok in (f"forged-{seed}", None):
                    kind, got = post(
                        {"prompt": [1, 2, 3], "max_new_tokens": 4,
                         "stream": False}, token=tok)
                    if kind == "status":
                        attacker["codes"].append(got[0])
                    elif kind == "done":
                        attacker["codes"].append(200)
                    else:
                        state["restart"].wait(timeout=300.0)
                time.sleep(0.1)

        # track the victim's in-flight token count for the kill trigger
        def watch_victim():
            while not stop.is_set():
                live = [o for o in cont_out.values() if o["done"] is None]
                vic_state["cur_tokens"] = (
                    max((len(o["tokens"]) for o in live), default=0))
                time.sleep(0.01)

        threads = ([threading.Thread(target=victim_loop, daemon=True),
                    threading.Thread(target=attacker_loop, daemon=True),
                    threading.Thread(target=watch_victim, daemon=True)]
                   + [threading.Thread(target=aggressor_loop, args=(j,),
                                       daemon=True)
                      for j in range(n_agg)])
        for t in threads:
            t.start()

        # -- the kill: victim mid-stream, aggressor already contained ----
        kill_deadline = time.monotonic() + 300.0
        while True:
            assert time.monotonic() < kill_deadline, (
                "kill precondition never met",
                dict(vic_state, s429=agg_stats["s429"]))
            if (vic_state["done"] >= 2 and agg_stats["s429"] >= 1
                    and vic_state["cur_tokens"] >= 1):
                break
            time.sleep(0.01)
        os.kill(child.pid, signal.SIGKILL)
        child.wait()

        # -- restart the brain against the same journal + workdir --------
        child, log2 = launch(shutdown_workers=True, tag="c2")
        ready2 = wait_ready(log2, child)
        state["port"] = ready2["port"]
        state["restart"].set()
        stop_deadline = time.monotonic() + 600.0
        while not stop.is_set() and time.monotonic() < stop_deadline:
            time.sleep(0.1)
        assert stop.is_set(), "victim never finished after the restart"
        for t in threads:
            t.join(timeout=120.0)

        # -- tenant-scoped idempotency across the restart: the aggressor
        # replaying the VICTIM's key must get its OWN uid, never the
        # victim's journaled stream
        kind, got = post({"prompt": [int(t) for t in agg_prompts[0]],
                          "max_new_tokens": AGG_NEW, "stream": False},
                         token=agg_tok, idem=f"tcc{seed}-0")
        vic0_uids = cont_out[0]["uids"]
        if kind == "done":
            assert int(got["uid"]) not in vic0_uids, (
                "cross-tenant idempotency replay", got["uid"], vic0_uids)
            assert got["tokens"] == ref[("agg", 0)], (
                "cross-tenant replay returned foreign tokens")
        else:
            assert kind == "status" and got[0] == 429, (
                "aggressor idem probe", kind, got)

        os.kill(child.pid, signal.SIGTERM)
        child.wait(timeout=300.0)
        final = read_final(log2)
        assert final is not None, "restarted child printed no final stats"

        # -- the isolation contract, asserted ----------------------------
        from deepspeed_tpu.resilience.invariants import (
            bitwise_parity_vs_reference, check, no_raw_secret_in_artifacts,
            single_decode_program)

        # victim: every request ok, bitwise-identical to the reference
        for i in sorted(vic_cont):
            out = cont_out[i]
            assert out["done"] is not None and \
                out["done"]["status"] == "ok", (i, out["done"])
            assert len(out["uids"]) == 1, (
                "a retried victim key forked a uid", i, out["uids"])
            n = len(ref[("cont", i)])
            toks = [out["tokens"].get(k) for k in range(n)]
            assert toks == ref[("cont", i)], (
                "victim tokens diverged (cross-tenant contamination?)", i)
        check(bitwise_parity_vs_reference(
            {i: cont_out[i]["done"]["tokens"] for i in vic_cont},
            {i: ref[("cont", i)] for i in vic_cont},
            uids=sorted(vic_cont), statuses=None,
            min_compared=len(vic_cont)))
        # victim p99 TTFT bounded vs solo. The factor + floor budget the
        # CPU smoke's worst case — router + 2 workers + 13 client threads
        # timesharing as little as ONE core, where even a perfectly
        # contained victim pays scheduler quanta behind aggressor decodes
        # already in flight. Containment is still what it proves: with no
        # isolation the victim would sit behind the aggressor's ~80-deep
        # unthrottled backlog (tens of seconds), not inside 5x solo.
        p99_cont = p99(cont_ttfts)
        bound = 5.0 * max(p99_solo, 0.5)
        assert p99_cont <= bound, (
            "victim p99 TTFT degraded past the isolation bound",
            {"solo": p99_solo, "contended": p99_cont, "bound": bound})
        # zero victim sheds/rejects, fleet-wide (engines + router)
        tc_cnt = final["tenant_counters"]
        assert tc_cnt.get("tenant/victim/sheds", 0) == 0, tc_cnt
        assert tc_cnt.get("tenant/victim/rejected", 0) == 0, tc_cnt
        # aggressor contained by its OWN quota: typed 429s observed, every
        # completion bitwise-clean, nothing but 429 among its rejections
        assert agg_stats["s429"] >= 1, agg_stats
        assert agg_stats["s200"] == agg_stats["parity"], agg_stats
        assert not agg_stats["other"], agg_stats
        assert agg_stats["retry_after"] == agg_stats["s429"], agg_stats
        # attacker: only 401/403, never a stream, counted at the gate.
        # The counter restarts from zero with the SIGKILL'd router, so the
        # fleet-visible count only covers post-restart attempts — assert
        # the gate is counting, bounded by the attacker's true total.
        assert attacker["codes"], "attacker never got an answer"
        assert set(attacker["codes"]) <= {401, 403}, attacker["codes"]
        auth_fails = final["counters"].get("gateway/auth_failures", 0)
        assert 1 <= auth_fails <= len(attacker["codes"]), (
            auth_fails, len(attacker["codes"]))
        # accounting rebuilt across the SIGKILL (recovery ran, victim
        # requests adopted) + program count flat under the tenant mix
        rec = ready2["recovery"]
        assert rec.get("router/recovery/recoveries") == 1, rec
        check(single_decode_program(final["decode_compiles"]))
        assert final["loads"] and all(
            v == 0 for v in final["loads"].values()), final["loads"]
        # secret hygiene end to end: no raw bearer token in the journal
        # or either child log (digests only) — the oracle reports secrets
        # by index, never by content
        artifacts = {}
        for name, lp in (("journal", journal), ("log1", log1),
                         ("log2", log2)):
            with open(lp, "rb") as f:
                artifacts[name] = f.read()
        check(no_raw_secret_in_artifacts(artifacts, (vic_tok, agg_tok)))

        resumed = [i for i, o in cont_out.items() if o["resumed"]]
        print(json.dumps({
            "metric": "tenant isolation drill (victim SLO held under attack)",
            "value": int(agg_stats["s429"] + len(attacker["codes"])),
            "unit": "contained_requests",
            # CPU-pinned correctness soak: never a perf datapoint
            "platform": "cpu",
            "workers": 2,
            "transport": "tcp",
            "tenants": 2,
            "victim_requests": n_vic,
            "victim_ttft_p99_solo_s": round(p99_solo, 4),
            "victim_ttft_p99_contended_s": round(p99_cont, 4),
            "tenant_victim_ttft_p99_ratio": round(
                p99_cont / max(p99_solo, 1e-9), 3),
            "tenant_victim_sheds": 0,
            "tenant_aggressor_429s": int(agg_stats["s429"]),
            "aggressor_completions": int(agg_stats["s200"]),
            "attacker_rejections": len(attacker["codes"]),
            "resumed_streams": len(resumed),
            "greedy_bitwise_match": True,
            "seed": seed,
            "elapsed_s": round(time.perf_counter() - t0, 2),
        }), flush=True)
        return 0
    finally:
        stop_evt = locals().get("stop")
        if stop_evt is not None:
            stop_evt.set()
        if child is not None and child.poll() is None:
            try:
                os.kill(child.pid, signal.SIGKILL)
            except OSError:
                pass
        # reap any workers the drill leaked (pidfiles are the roster)
        try:
            for name in os.listdir(workdir):
                if name.startswith("w") and name.endswith(".pid"):
                    with open(os.path.join(workdir, name)) as f:
                        info = json.load(f)
                    try:
                        os.kill(int(info["pid"]), signal.SIGKILL)
                    except (OSError, ValueError):
                        pass
        except OSError:
            pass


def _chaos_search(n_schedules: int, seed: int) -> int:
    """Seeded fault-space search (``drills.py --chaos-search``): run
    ``n_schedules`` generated ``FaultSchedule``s against the shared
    invariant suite over the host-only fake fleet
    (``resilience/chaos.py``). Every violation is delta-debugged to a
    minimal reproducer written rename-durably to
    ``chaos-repros/chaos-repro-NNN.json`` — re-execute one bit-identically
    with ``--chaos-replay FILE``. Exit 0 only when every schedule is
    green. CPU-pinned, in-process, zero XLA programs — a correctness
    search, never a perf number."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from deepspeed_tpu.resilience.chaos import ChaosRunner, search

    t0 = time.perf_counter()
    runner = ChaosRunner()
    row = search(
        runner, n_schedules, seed,
        artifact_dir=os.path.join(os.getcwd(), "chaos-repros"),
        log=lambda m: print(f"chaos-search: {m}", file=sys.stderr,
                            flush=True))
    counters = runner.telemetry.registry.snapshot()["counters"]
    site_fired = {s: int(counters.get(f"chaos/site/{s}/fired", 0))
                  for s in row["sites_covered"]}
    print(json.dumps({
        "metric": "chaos fault-space search (green schedules)",
        "value": int(row["schedules_run"]) - len(row["violations"]),
        "unit": "schedules",
        # CPU-pinned correctness search: never a perf datapoint
        "platform": "cpu",
        "schedules_run": row["schedules_run"],
        "sites_covered": row["sites_covered"],
        "site_fired": site_fired,
        "violations": row["violations"],
        "seed": seed,
        "elapsed_s": round(time.perf_counter() - t0, 2),
    }), flush=True)
    return 1 if row["violations"] else 0


def _chaos_replay(path: str) -> int:
    """Replay one ``chaos-repro-NNN.json`` (``drills.py --chaos-replay``)
    and verify bit-identical reproduction: the re-run must produce the
    SAME outcome digest and trip the SAME invariant set the artifact
    recorded. Also accepts a bare schedule JSON (replays without the
    digest comparison)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from deepspeed_tpu.resilience.chaos import ChaosRunner, replay_repro

    t0 = time.perf_counter()
    with open(path) as f:
        repro = json.load(f)
    rep = replay_repro(ChaosRunner(), repro)
    ok = bool(rep["digest_match"] and rep["violations_match"])
    print(json.dumps({
        "metric": "chaos repro replay (bit-identical)",
        "value": int(ok),
        "unit": "bool",
        # CPU-pinned correctness replay: never a perf datapoint
        "platform": "cpu",
        "repro": os.path.basename(path),
        "digest": rep["digest"],
        "digest_match": rep["digest_match"],
        "tripped": rep["tripped"],
        "violations_match": rep["violations_match"],
        "elapsed_s": round(time.perf_counter() - t0, 2),
    }), flush=True)
    return 0 if ok else 1


# One entry a drill: flag, function, operand, seed flag. An operand is
# (usage text, type, default, least value, why that is the least); a default
# of None makes it required, and a drill with no operand refuses one. Where
# two flags are given the earlier entry runs (``--chaos-replay --chaos-search``
# is a replay that lacks its FILE).
_DRILLS = (
    ("--router-chaos", _router_chaos, None, "--router-seed"),
    ("--tenant-chaos", _tenant_chaos, None, "--tenant-seed"),
    ("--fault-rate", _fault_smoke,
     ("<float in (0, 1]>", float, None, None, None), None),
    ("--surge", _surge,
     ("[n_requests >= 12]", int, 30, 12,
      "n_requests must be >= 12 (room for two bursts + the high-priority "
      "stragglers)"), "--surge-seed"),
    ("--gateway-chaos", _gateway_chaos, None, "--gateway-seed"),
    ("--disagg", _disagg_drill, None, "--disagg-seed"),
    ("--chaos-replay", _chaos_replay,
     ("<chaos-repro.json>", str, None, None, None), None),
    ("--chaos-search", _chaos_search,
     ("[n_schedules >= 1]", int, 64, 1, "n_schedules must be >= 1"),
     "--chaos-search-seed"),
    ("--chaos-serving", _chaos_serving, None, "--chaos-seed"),
    ("--chaos", _chaos,
     ("[steps >= 6]", int, 12, 6,
      "steps must be >= 6 (room for 2 preempts + 1 NaN)"), "--chaos-seed"),
)


def _usage(flag, operand, seed_flag):
    words = (flag, operand and operand[0], seed_flag and f"[{seed_flag} <int>]")
    return " ".join(w for w in words if w)


def _drill_args(flag, operand, seed_flag):
    """One entry's arguments off ``sys.argv``. Anything malformed prints the
    drill's usage line and exits 2 before jax is imported: never a
    traceback, never a started drill."""
    argv, args = sys.argv, []
    try:
        word = argv[argv.index(flag) + 1:][:1]
        # "--"-prefixed means the next FLAG; a bare "-3" is a (bad) operand
        # and must hit the usage check, not be ignored
        given = bool(word) and not word[0].startswith("--")
        if operand is None:
            if given:
                raise ValueError(f"unexpected operand {word[0]!r} (the drill "
                                 f"takes only {seed_flag})")
        else:
            _, kind, default, least, why = operand
            if not given and default is None:
                raise ValueError("missing operand")
            args.append(kind(word[0]) if given else default)
            if least is not None and args[0] < least:
                raise ValueError(why)
        if seed_flag is not None:
            args.append(int(argv[argv.index(seed_flag) + 1])
                        if seed_flag in argv else 0)
    except (IndexError, ValueError) as e:
        print(f"usage: drills.py {_usage(flag, operand, seed_flag)} ({e})",
              file=sys.stderr)
        sys.exit(2)
    return args


if __name__ == "__main__":
    if "--router-chaos-child" in sys.argv:
        # internal: the control-plane process the --router-chaos parent
        # launches (and SIGKILLs); not a user-facing drill entry
        sys.exit(_router_chaos_child(
            sys.argv[sys.argv.index("--router-chaos-child") + 1]))
    for flag, drill, operand, seed_flag in _DRILLS:
        if flag in sys.argv:
            sys.exit(drill(*_drill_args(flag, operand, seed_flag)))
    print("usage: drills.py <drill>, one of", file=sys.stderr)
    for flag, _, operand, seed_flag in _DRILLS:
        print(f"  drills.py {_usage(flag, operand, seed_flag)}", file=sys.stderr)
    sys.exit(2)
