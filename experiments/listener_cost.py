"""What the process-wide xla/* listener (telemetry/tracing.py) costs a cell's check (PR 54).

    python3 experiments/listener_cost.py <mode> <tag> [--profile] -- <chipbench.run arguments>

Runs one benchmark cell as ``chipbench.run`` does, up to the end of the training driver's
check (the float32 reference's loss), and stops there: build and check, no warm-up, no
window. ``mode`` says what listens on ``jax.monitoring`` beside the harness's own pair:

  on     the listeners as ``deepspeed_tpu.telemetry.tracing`` installs them
  off    none of them (``tracing.listen(False)`` after the import)
  noop   three functions that do nothing in their place: what jax's dispatch to a listener costs
  timed  the three, each call timed (perf_counter_ns) and counted by event name and thread
  noretain  the three, but an ended ``xla/*`` span is built and dropped: neither ring nor kept list
  nospan    the three, but no ``Span`` is built at all: the thread's depth, state and totals only

Prints one JSON line: ``build_s``, ``check_s``, where the check's seconds went outside Python
(``outside``: the sharded leaves' copies to the host, ``ArrayImpl._value``; the backend's compiles
and loads; ``batched_device_put``), the collector's runs and seconds by generation (``gc``), and
with ``timed`` the seconds in each handler and the counts. ``--profile`` runs the check under
cProfile and writes the 60 entries with most own and most cumulative time to
``chiprun_out/listener_cost/<tag>.tottime.txt`` / ``.cumulative.txt`` (the same overhead in every
mode, so two modes' tables are compared line by line).
"""

import collections
import cProfile
import gc
import io
import json
import os
import pstats
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

mode, tag = sys.argv[1], sys.argv[2]
profile = "--profile" in sys.argv
run_args = sys.argv[sys.argv.index("--") + 1:]
assert mode in ("on", "off", "noop", "timed", "noretain", "nospan"), mode

import chipbench.run as bench_run  # noqa: E402  (the clock of set-up starts at its import)
import jax._src.monitoring as monitoring  # noqa: E402
from chipbench.drivers import train  # noqa: E402
from deepspeed_tpu.telemetry import tracing  # noqa: E402

LISTS = {"_on_begin": monitoring._scalar_listeners,
         "_on_duration": monitoring._event_duration_secs_listeners,
         "_on_event": monitoring._event_listeners}
seconds = collections.Counter()
counts = collections.Counter()


def timed(name, fn):
    def call(event, *args, **kwargs):
        t0 = time.perf_counter_ns()
        try:
            return fn(event, *args, **kwargs)
        finally:
            seconds[name] += (time.perf_counter_ns() - t0) * 1e-9
            counts[f"{name} {event.rsplit('/', 1)[-1]} {threading.current_thread().name}"] += 1
    return call


if mode == "off":
    tracing.listen(False)
for name, listeners in () if mode == "off" else LISTS.items():
    fn = getattr(tracing, name)
    at = listeners.index(fn)
    if mode == "noop":
        listeners[at] = lambda *a, **k: None
    elif mode == "timed":
        listeners[at] = timed(name, fn)

if mode == "noretain":
    tracing._end = lambda span: None
elif mode == "nospan":
    def without_span(event, duration, **_):
        if event in tracing._XLA_KINDS:
            st = tracing._xla_state()
            st.depth -= st.depth > 0
    at = LISTS["_on_duration"].index(tracing._on_duration)
    LISTS["_on_duration"][at] = without_span

# where the check's seconds go outside Python, and what the collector does meanwhile
outside = collections.Counter()


def clocked(name, fn):
    def call(*args, **kwargs):
        t0 = time.perf_counter_ns()
        try:
            return fn(*args, **kwargs)
        finally:
            outside[name + "_s"] += (time.perf_counter_ns() - t0) * 1e-9
            outside[name + "_n"] += 1
    return call


import jax._src.array as jax_array  # noqa: E402
import jax._src.compiler as jax_compiler  # noqa: E402
from jax._src.interpreters import pxla  # noqa: E402

jax_array.ArrayImpl._value = property(clocked("to_host", jax_array.ArrayImpl._value.fget))
jax_compiler.backend_compile_and_load = clocked("backend_compile",
                                                jax_compiler.backend_compile_and_load)
pxla.batched_device_put = clocked("device_put", pxla.batched_device_put)
gc_runs = collections.Counter()
gc_t0 = [0]


def on_gc(phase, info):
    if phase == "start":
        gc_t0[0] = time.perf_counter_ns()
    else:
        gc_runs[f"gen{info['generation']}_n"] += 1
        gc_runs[f"gen{info['generation']}_s"] += (time.perf_counter_ns() - gc_t0[0]) * 1e-9


gc.callbacks.append(on_gc)
reference_loss = train._reference_loss


def check_and_stop(run, engine, sequences):
    t_built = time.perf_counter()
    before = dict(seconds), dict(counts), dict(outside), dict(gc_runs)
    prof = cProfile.Profile() if profile else None
    if prof:
        prof.enable()
    loss = reference_loss(run, engine, sequences)
    if prof:
        prof.disable()
    out = {"mode": mode, "tag": tag, "profile": profile, "build_s": t_built - run.t_start,
           "check_s": time.perf_counter() - t_built, "reference_loss": loss,
           "kept": tracing.kept_stats(),
           "outside": {k: v - before[2].get(k, 0) for k, v in sorted(outside.items())},
           "gc": {k: v - before[3].get(k, 0) for k, v in sorted(gc_runs.items())},
           "gc_objects": len(gc.get_objects())}
    if mode == "timed":
        out["handler_s_in_check"] = {k: v - before[0].get(k, 0.0) for k, v in seconds.items()}
        out["handler_s_in_build"] = before[0]
        out["events_in_check"] = {k: v - before[1].get(k, 0) for k, v in sorted(counts.items())
                                  if v - before[1].get(k, 0)}
    print(json.dumps(out), flush=True)
    if prof:
        os.makedirs("chiprun_out/listener_cost", exist_ok=True)
        for order in ("tottime", "cumulative"):
            s = io.StringIO()
            pstats.Stats(prof, stream=s).sort_stats(order).print_stats(60)
            with open(f"chiprun_out/listener_cost/{tag}.{order}.txt", "w") as f:
                f.write(s.getvalue())
    raise SystemExit(0)


train._reference_loss = check_and_stop
sys.exit(bench_run.main(run_args))
