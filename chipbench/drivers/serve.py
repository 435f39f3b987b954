"""Driver for a serving cell: ``build_serving_engine(spec)`` driven through
``submit()`` + ``step()``, open or closed loop as the traffic says.

The harness runs ``ServingEngine.serve()``'s own loop itself (submit the whole
schedule with its arrival times, then ``step()`` with the same sleep rule), so
that it can stamp every step, see every output token when the streaming
surface (``live_progress()``, what the worker's step reply carries to an SSE
gateway) first shows it, and know how late each arrival was first looked at.

The cell file gives ``deployment`` (``n_slots``, ``max_seq_len``), ``serving``
(the engine's ``serving`` block: the defaults, stated), ``traffic`` (generator
and parameters) and ``tuning`` (nothing yet).

Correctness, in set-up, at the published widths: two seeded prompts go
through the engine itself (greedy, 9 tokens). Then (a) a probe program — the
engine's own ``apply_with_cache`` on the engine's own weights, prefilling each
prompt into a slot cache as ``SlotWorker`` does and taking 8 decode steps
through that cache, fed the tokens the engine produced — returns the logits
the serving programs sample from, and they agree with the full forward pass
of the plain float32 reference the configuration names (``references/``,
handed the engine's whole parameter tree) within ``LOGIT_TOL``; (b) every
token the engine produced lies within ``LOGIT_TOL`` of the reference's top
logit at its step (bf16 streams may part from the reference only at a near
tie). ``LOGIT_TOL``, the prompts and the probe are the same for every
configuration.
"""

from __future__ import annotations

import time

import numpy as np

from ..references import load_reference

# max |logit| difference between the bf16 serving path and the float32
# reference on the same weights, logits of standard deviation about 1.
# Measured on the chip at the real size (PR 23, every run made): 0.053 to 0.060
# for bloom-1b7, 0.046 to 0.051 for pythia-1.4b. The tolerance is 1.5 x the
# largest; 8-bit floats (2^-3 to 2^-4 relative against bf16's 2^-8) would land
# an order of magnitude above it. The measured value is printed on the
# "setup" line of every run.
LOGIT_TOL = 0.09
DECODE_STEPS = 8
CHECK_PROMPT_LENS = (200, 97)  # <= 256 tokens, two different buckets
WARM_UID = 10 ** 9  # warm-up and check requests sit far above the traffic's uids

SPANS = ("serve.step", "serve.decode", "serve.prefill", "stamp", "generator", "idle_wait")
# the least a traced run's host window may be, as a share of the window: the
# host-clock per-layer metrics and the run's attempted / failed are read there
HOST_WINDOW_MIN_SHARE = 0.2
# a cell's traced length leaves that share at this many times the stop rate its file states:
# operations a second rise with steps a second, so a program twice as fast stops twice as long
# (the farthest a decode program stands from its floor is a factor of 2.3; PERF.md section 6, PR 57)
STOP_RATE_HEADROOM = 2.0


class HostWindowTooShort(RuntimeError):
    """stop_trace ate the window a traced run reads its host-clock metrics from."""


def trace_block(trace: dict, profiler: dict) -> dict:
    """A cell's ``trace`` block as a run takes it, with what the cell's ``profiler``
    block states: ``stop_rate`` (seconds of ``stop_trace`` a profiled second, as read
    on the chip) and, where it has one, ``trace_seconds`` in place of ``trace.seconds``
    (a block that a test outside the benchmark holds to its letter stays as it is)."""
    out = {**trace, "stop_rate": profiler.get("stop_rate")}
    if "trace_seconds" in profiler:
        out["seconds"] = profiler["trace_seconds"]
    return out


def host_window_left(trace: dict, lead_in_s: float, window_s: float, before_s: float,
                     stop_rate: float) -> float:
    """Seconds of host window a traced run keeps where ``stop_trace`` takes
    ``stop_rate`` seconds for each second profiled: the profiler starts ``before_s``
    ahead of the window (or with the loop, in a shorter lead-in)."""
    profiled = min(lead_in_s, before_s) + trace["seconds"]
    return window_s - trace["seconds"] - stop_rate * profiled - trace["settle_s"]


def _bucket(srv, n: int) -> int:
    """The prefill bucket the engine pads a prompt of n tokens to (its own
    rule: a private method, so a rename fails here, loudly)."""
    return int(srv._bucket_len(n))


def _build(run):
    from deepspeed_tpu.launcher.serving_worker import build_serving_engine

    dep = run.sized("deployment")
    spec = {"model": {**run.program, "dtype": "bfloat16"}, "engine_dtype": "bf16",
            "serving": {**run.cell["serving"], "n_slots": dep["n_slots"],
                        "max_seq_len": dep["max_seq_len"], "seed": run.seed}}
    return build_serving_engine(spec), dep


def _request(Request, r: dict, arrival=None):
    return Request(uid=r["uid"], prompt=r["prompt"],
                   max_new_tokens=r["max_new_tokens"], temperature=r["temperature"],
                   top_p=r["top_p"],
                   arrival_time=r["arrival_time"] if arrival is None else arrival)


def probe_logits(cfg, params, prompts, buckets, forced):
    """Logits [2, 1 + DECODE_STEPS, V] of the serving path's own computation:
    prefill of each of the two prompts, padded to its bucket, into a slot
    cache (what ``SlotWorker._build_prefill`` does), then decode steps through
    that cache at per-row positions (``_build_decode``), fed ``forced``
    [2, DECODE_STEPS]."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from deepspeed_tpu.models import transformer as tfm

    lens = np.asarray([len(p) for p in prompts], np.int32)
    padded = []
    for p, b in zip(prompts, buckets):
        row = np.zeros((1, b), np.int32)
        row[0, :len(p)] = p
        padded.append(row)
    smax = -(-(int(max(r.shape[1] for r in padded)) + DECODE_STEPS) // 128) * 128

    def probe(params, p0, p1, lens, forced):
        cache = tfm.init_cache(cfg, 2, smax, dtype=cfg.dtype)
        first = []
        for j, p in enumerate((p0, p1)):
            local = tfm.init_cache(cfg, 1, p.shape[1], dtype=cfg.dtype)
            logits, local = tfm.apply_with_cache(cfg, params, p, local, 0,
                                                 last_index=lens[j] - 1)
            first.append(logits[0, 0])
            cache = {kv: lax.dynamic_update_slice(cache[kv], local[kv], (0, j, 0, 0, 0))
                     for kv in ("k", "v")}

        def decode(carry, toks):
            cache, pos = carry
            logits, cache = tfm.apply_with_cache(cfg, params, toks[:, None], cache, pos,
                                                 write_pos=pos)
            return (cache, pos + 1), logits[:, 0]

        _, steps = lax.scan(decode, (cache, lens), forced.T)
        return jnp.concatenate([jnp.stack(first)[:, None], steps.transpose(1, 0, 2)], axis=1)

    out = jax.jit(probe)(params, padded[0], padded[1], lens, np.asarray(forced, np.int32))
    return np.asarray(out, np.float32)


def _check(run, srv, Request) -> dict:
    """Warm the engine's own path on the two check prompts and compare."""
    reference = load_reference(run.program)
    rng = np.random.default_rng([run.seed, 0xC4EC])
    vocab = run.program["vocab_size"]
    budget = run.sized("deployment")["max_seq_len"]
    lens = [min(n, budget - DECODE_STEPS - 2) for n in CHECK_PROMPT_LENS]
    prompts = [rng.integers(0, vocab, size=n).astype(np.int32) for n in lens]
    reqs = [Request(uid=WARM_UID + i, prompt=p, max_new_tokens=DECODE_STEPS + 1)
            for i, p in enumerate(prompts)]
    results = srv.serve(reqs)
    got = [np.asarray(results[r.uid].tokens, np.int32) for r in reqs]
    if any(results[r.uid].status != "ok" or len(g) != DECODE_STEPS + 1
           for r, g in zip(reqs, got)):
        return {"ok": False, "why": "a check request did not complete"}
    params = srv.engine.params
    probe = probe_logits(srv.engine.cfg, params, prompts, [_bucket(srv, len(p)) for p in prompts],
                         np.stack([g[:DECODE_STEPS] for g in got]))
    err, tie_gap, spread = 0.0, 0.0, 0.0
    for j, (p, g) in enumerate(zip(prompts, got)):
        rows = np.arange(len(p) - 1, len(p) + DECODE_STEPS)
        ref = reference.logits_at(run.program, params, np.concatenate([p, g[:DECODE_STEPS]]),
                                  rows, fetch=lambda leaves: leaves)  # all on the one chip
        err = max(err, float(np.max(np.abs(probe[j] - ref))))
        tie_gap = max(tie_gap, float(np.max(ref.max(axis=-1) - ref[np.arange(len(g)), g])))
        spread = float(np.std(ref))
    finite = bool(np.isfinite(probe).all())
    return {"ok": finite and err <= LOGIT_TOL and tie_gap <= LOGIT_TOL,
            "logit_max_abs_err": err, "token_gap_to_reference_top": tie_gap,
            "reference_logit_std": spread, "logit_tol": LOGIT_TOL}


def _warm(srv, Request, requests, vocab: int, seed: int) -> list:
    """One short request per prefill bucket this run's traffic reaches (the
    decode program is warm from the check); returns the buckets."""
    rng = np.random.default_rng([seed, 0x3A])
    longest = {}
    for r in requests:
        b = _bucket(srv, len(r["prompt"]))
        longest[b] = max(longest.get(b, 0), len(r["prompt"]))
    reqs = [Request(uid=WARM_UID + 100 + i,
                    prompt=rng.integers(0, vocab, size=n).astype(np.int32), max_new_tokens=2,
                    temperature=0.7 if i % 2 else 0.0, top_p=0.9 if i % 2 else 1.0)
            for i, (b, n) in enumerate(sorted(longest.items()))]
    results = srv.serve(reqs)
    if any(res.status != "ok" for res in results.values()):
        raise RuntimeError("a warm-up request did not complete")
    return sorted(longest)


def _instrument(run, worker, method: str, samples: list) -> None:
    """Time the calls into the device-program layer from outside it, and open
    a span round each so idle gaps can be put down to them."""
    fn = getattr(worker, method)

    def timed(*args, **kwargs):
        t0 = time.perf_counter()
        with run.span(f"serve.{method}"):
            out = fn(*args, **kwargs)
        samples.append((t0, time.perf_counter()))
        return out

    setattr(worker, method, timed)


def _memory_analysis(run, srv):
    """The compiler's account of the decode program, lowered again at its own
    operand shapes (a cache hit). Reads the worker's private handles: a rename
    fails here, loudly."""
    import jax
    import jax.numpy as jnp

    w = srv.worker
    n = w.n_slots
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
    vec = lambda d: jax.ShapeDtypeStruct((n,), d)
    return run.memory_dict(w._decode.lower(
        jax.tree.map(sds, w.params), jax.tree.map(sds, w._cache), vec(jnp.int32),
        vec(jnp.int32), vec(jnp.int32), vec(jnp.bool_), jax.random.PRNGKey(0),
        vec(jnp.float32), vec(jnp.int32), vec(jnp.float32)).compile())


def _loop(run, srv, Request, traffic, samples) -> dict:
    """The measured loop. Returns per-request and per-step records on the
    engine's clock (seconds since the loop began)."""
    reqs = traffic["requests"]
    lo, hi = traffic["window"]
    closed = traffic["loop"] == "closed"
    # after the window: no new work, and grace_s for what arrived in it to finish
    grace = float(run.sized("traffic")["grace_s"])
    # With --trace 1 the profiler starts run.TRACE_START_BEFORE_S before the
    # window, inside the lead-in, which no metric reads (in the loop's first
    # iteration where the lead-in is no longer than that): stop_trace stalls the
    # loop in proportion to what was profiled (0.7-3.6 s a profiled second, by
    # the cell: its file states the rate, ``profiler.stop_rate``), so it pays
    # for that second and the traced ones, not for the lead-in. The traced
    # window is the first trace.seconds of the measured window, and the
    # host-clock per-layer metrics are read from what arrives once stop_trace
    # and the backlog it caused are over.
    trace = trace_block(run.sized("trace"), run.sized("profiler"))
    tracing = "waiting" if run.trace else None
    resume_at = lo
    # loop clock, seconds; stop_rate: stop_s a profiled second, beside the cell file's
    profiler = {"start_at": None, "start_s": None, "stop_s": None, "stop_rate": None,
                "stop_rate_stated": trace["stop_rate"]}

    stamps: dict[int, list] = {}
    steps, first_seen, submitted = [], {}, {}
    prompt_len = {r["uid"]: len(r["prompt"]) for r in reqs}
    traced = [None, None]  # the traced sub-window on the loop's clock
    nxt = 0  # next request to hand out (closed) / next arrival not yet seen (open)
    if not closed:
        for r in reqs:
            srv.submit(_request(Request, r))
            submitted[r["uid"]] = r["arrival_time"]
    epoch = time.perf_counter()
    srv.set_epoch(epoch)
    clock = lambda: time.perf_counter() - epoch
    if closed:
        for r in reqs[:traffic["clients"]]:
            now = clock()
            srv.submit(_request(Request, r, arrival=now))
            submitted[r["uid"]] = now
        nxt = traffic["clients"]
    done, total = 0, len(reqs)
    while done < (len(submitted) if closed else total):
        now = clock()
        if now >= hi + grace:
            break
        if tracing == "waiting" and now >= lo - run.TRACE_START_BEFORE_S:
            run.trace_start(window=False)
            tracing, profiler["start_at"] = "armed", now
            now = clock()  # a start-up that runs past lo opens the window late
            profiler["start_s"] = now - profiler["start_at"]
        if tracing == "armed" and now >= lo:
            run.trace_window_open()
            tracing, traced[0] = "open", now
        if tracing == "open" and now >= lo + trace["seconds"]:
            run.trace_stop()
            tracing, traced[1] = None, now
            stopped = clock()
            profiler["stop_s"] = stopped - now
            profiler["stop_rate"] = profiler["stop_s"] / (now - profiler["start_at"])
            resume_at = stopped + trace["settle_s"]
        if not closed and srv.n_active == 0 and srv.n_prefilling == 0 and nxt < total:
            wait = reqs[nxt]["arrival_time"] - now  # serve()'s own sleep rule
            if wait > 0:
                with run.span("idle_wait"):
                    time.sleep(min(wait, 0.05))
        ts = clock()
        if not closed:
            while nxt < total and reqs[nxt]["arrival_time"] <= ts:
                first_seen[reqs[nxt]["uid"]] = ts
                nxt += 1
        with run.span("serve.step"):
            finished = srv.step()
        te = clock()
        with run.span("stamp"):
            progress = srv.live_progress()
            live_tokens = 0  # cached tokens the decode of this step attended to
            for uid, toks in progress.items():
                live_tokens += prompt_len.get(uid, 0) + len(toks) - 1
                seen = stamps.setdefault(uid, [])
                if len(toks) > len(seen):
                    seen.extend([te] * (len(toks) - len(seen)))
            for uid in finished:
                seen = stamps.setdefault(uid, [])
                n = len(srv.result(uid).tokens)
                if n > len(seen):
                    seen.extend([te] * (n - len(seen)))
            done += len(finished)
            steps.append((ts, te, min(len(progress) + len(finished), srv.n_slots),
                          live_tokens))
        if closed and finished and te < hi:  # each client sends its next request
            with run.span("generator"):
                for _ in finished:
                    if nxt < total:
                        now = clock()
                        srv.submit(_request(Request, reqs[nxt], arrival=now))
                        submitted[reqs[nxt]["uid"]] = now
                        nxt += 1
            if nxt >= total:
                raise RuntimeError("the closed loop ran out of pre-generated requests: "
                                   "raise the traffic's max_rps")
    t_end = clock()
    if run.trace:
        arrivals = sum(resume_at <= t < hi for t in submitted.values())
        if hi - resume_at < HOST_WINDOW_MIN_SHARE * (hi - lo) or not arrivals:
            stop_s = profiler["stop_s"]  # None: the loop ended before the traced window did
            raise HostWindowTooShort(
                f"stop_trace took trace_stop_s={stop_s and round(stop_s, 1)} s (rate "
                f"{profiler['stop_rate'] and round(profiler['stop_rate'], 2)} a profiled second, "
                f"the cell file states {trace['stop_rate']}) and left the "
                f"host window [resume_at={resume_at:.1f}, hi={hi:.1f}) with {arrivals} "
                f"arrival(s): under {HOST_WINDOW_MIN_SHARE:.0%} of the window's "
                f"{hi - lo:.1f} s, or empty. Shorten the cell's traced seconds")

    records = []
    for r in reqs:
        uid = r["uid"]
        if uid not in submitted:
            continue
        res = srv.result(uid)  # None: not finished grace_s after the window
        times = list(stamps.get(uid, []))
        first = res.first_token_time if res is not None else (times[0] if times else None)
        if times and first is not None:
            times[0] = min(first, times[0])  # the engine's own, exact
        records.append({
            "uid": uid, "arrival": submitted[uid], "seen": first_seen.get(uid),
            "status": res.status if res is not None else "unfinished",
            "admitted": res.admitted_time if res is not None else None,
            "first_token": first,
            "finish": res.finish_time if res is not None else None,
            "prompt_len": len(r["prompt"]),
            "n_out": len(res.tokens) if res is not None else len(times),
            "token_times": times,
        })
    calls = {m: [(a - epoch, b - epoch) for a, b in s] for m, s in samples.items()}
    return {"records": records, "steps": steps, "calls": calls, "window": (resume_at, hi),
            "t_end": t_end, "loop": traffic["loop"], "epoch": epoch, "traced": tuple(traced),
            "profiler": profiler}


def run(run) -> dict:
    from deepspeed_tpu.inference.serving import Request

    srv, dep = _build(run)
    t_built = time.perf_counter()
    traffic = run.traffic(n_slots=dep["n_slots"])
    check = _check(run, srv, Request)
    t_checked = time.perf_counter()
    buckets = _warm(srv, Request, traffic["requests"], run.program["vocab_size"], run.seed)
    memory_analysis = _memory_analysis(run, srv) if run.trace else None
    samples = {"decode": [], "prefill": []}
    for method, sink in samples.items():
        _instrument(run, srv.worker, method, sink)
    t_window = time.perf_counter()
    lo, hi = traffic["window"]
    setup_s = t_window - run.t_start + lo  # the lead-in fills the slots: set-up too
    run.note(event="setup", build_s=t_built - run.t_start, check_s=t_checked - t_built,
             warm_s=t_window - t_checked, lead_in_s=lo, check=check, prefill_buckets=buckets,
             n_slots=dep["n_slots"], requests=len(traffic["requests"]),
             memory_analysis=memory_analysis, compile_counts=str(srv.compile_counts()))

    measured = _loop(run, srv, Request, traffic, samples)
    if run.trace:
        run.trace_reduce(SPANS)

    recs = measured["records"]
    lo = measured["window"][0]  # later than the traffic's with --trace 1
    # attempted: what arrived in the window; failed: whatever of it is not "ok"
    # grace_s after the window (shed, error, unfinished)
    counted = [r for r in recs if lo <= r["arrival"] < hi]
    failed = [r for r in counted if r["status"] != "ok"]
    completed = [r for r in recs if r["status"] == "ok" and lo <= r["finish"] < hi]
    measured.update(counted=counted, completed=completed, n_slots=dep["n_slots"])
    epoch = measured["epoch"]
    # more statistics of the same samples than the metrics report, for a reader
    # of the log
    gaps = [np.diff(r["token_times"]) for r in counted if len(r["token_times"]) > 1]
    gaps = np.concatenate(gaps) if gaps else np.zeros((1,))
    firsts = [r["first_token"] - r["arrival"] for r in counted if r["first_token"] is not None]
    pct = lambda xs, qs: {f"p{q}": 1e3 * float(np.percentile(xs, q)) for q in qs}
    stats = {"ttft_ms": {"mean": 1e3 * float(np.mean(firsts)), **pct(firsts, (50, 75, 90, 95))}
             if firsts else None,
             "itl_ms": {"n": int(gaps.size), "mean": 1e3 * float(np.mean(gaps)),
                        **pct(gaps, (50, 90, 95, 99))},
             "completed_in_window": len(completed),
             "tokens_out": int(sum(r["n_out"] for r in completed)),
             "prompt_tokens": int(sum(r["prompt_len"] for r in completed))}
    return {
        "correct": bool(check["ok"]), "attempted": len(counted), "failed": len(failed),
        "t_setup": setup_s, "window_s": hi - lo,
        "n_compiles": run.compiles_between(epoch + lo, epoch + measured["t_end"]),
        "train": None, "serve": measured, "memory_analysis": memory_analysis,
        "notes": {"check": check, "steps": len(measured["steps"]), "stats": stats,
                  "statuses": sorted({r["status"] for r in counted}),
                  "traced": measured["traced"], "host_window": measured["window"],
                  **{f"trace_{k}": v for k, v in measured["profiler"].items()},
                  "compile_counts_after": str(srv.compile_counts())},
    }
