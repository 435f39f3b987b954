#!/usr/bin/env python3
"""chipbench.run — one run of one cell of the benchmark.

    python3 -m chipbench.run --workload W --seed N --seconds S --trace 0|1

Everything a cell is made of is a file found by name: the cell itself
(``workloads/W.json``), its configuration (``configs/``) and the plain
reference that configuration names (``references/``), the driver that knows
how to build and drive the system under test (``drivers/``), the traffic
generator (``traffic/``), and one reader per metric (``end_to_end/`` and
``layer_metrics/``). Which metrics a cell reports, with their units, is read
from ``BENCHMARK.json``. This file holds no cell, configuration or metric
name.

The last line of standard output is the result: one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and, with
``--trace 1``, ``breakdown``. Without a TPU of a kind listed in
``peaks.json``, or with fewer chips than the cell asks for, the run exits
non-zero and prints no result.

``--rehearse`` drives the same control flow at a tiny size on the CPU (Pallas
interpreted, virtual devices for a multi-chip cell). It never prints a
result: a number from a CPU run is not a device metric.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()  # set-up is counted from here

import argparse
import glob
import importlib
import json
import os
import shutil
import sys
import tempfile

from .references import load_reference, named_reference, program_of  # imports no jax

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
                "/jax/compilation_cache/cache_misses": "writes"}


def note(**fields) -> None:
    """An earlier line of standard output: anything worth reading that is
    not the result."""
    print(json.dumps(fields, default=str), flush=True)


def _named(directory: str, name: str) -> dict:
    path = os.path.join(HERE, directory, f"{name}.json")
    if not os.path.isfile(path):
        have = sorted(os.path.splitext(f)[0] for f in os.listdir(os.path.join(HERE, directory)))
        raise SystemExit(f"no {directory}/{name}.json; there are: {', '.join(have)}")
    with open(path) as f:
        return json.load(f)


class Run:
    """What a driver needs from the harness: the cell, the clock, the
    compile counters, spans, and the profiler for the traced sub-window."""

    def __init__(self, args, cell, entry, config, peak):
        self.args, self.cell, self.entry, self.config, self.peak = args, cell, entry, config, peak
        self.rehearse = bool(args.rehearse)
        self.t_start, self.note = T_PROCESS_START, note
        self.seed, self.trace = int(args.seed), bool(args.trace)
        self.seconds = float(cell["rehearse"]["seconds"] if self.rehearse else args.seconds)
        self.chips = int(entry["chips"])
        # the model's keywords as run; remembers the reference the configuration names
        self.program = program_of(
            config, "rehearse_program" if self.rehearse else "program")
        self.cache = {"hits": 0, "writes": 0}
        self.compile_times: list[float] = []  # perf_counter at the end of each compile
        self.trace_dir = None
        self.reduced = None
        self._window_span = None

    def sized(self, block: str) -> dict:
        """A block of the cell file, with the rehearsal's tiny overrides."""
        out = dict(self.cell[block])
        if self.rehearse:
            out.update(self.cell["rehearse"].get(block, {}))
        return out

    def traffic(self, **deployment) -> dict:
        params = self.sized("traffic")
        gen = importlib.import_module(f"chipbench.traffic.{params['kind']}")
        return gen.generate(params, seed=self.seed, seconds=self.seconds,
                            vocab_size=self.program["vocab_size"], **deployment)

    @staticmethod
    def memory_dict(compiled) -> dict:
        """The compiler's own account of a compiled program, per device."""
        ma = compiled.memory_analysis()
        return {"argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes, "alias_bytes": ma.alias_size_in_bytes}

    # -- compile accounting ----------------------------------------------
    def listen(self) -> None:
        import jax.monitoring

        def on_event(event, **_):
            if event in CACHE_EVENTS:
                self.cache[CACHE_EVENTS[event]] += 1

        def on_duration(event, duration, **_):
            if event == COMPILE_EVENT:
                self.compile_times.append(time.perf_counter())

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def compiles_between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t in self.compile_times)

    # -- spans and the traced sub-window --------------------------------
    @staticmethod
    def span(name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)

    # A serving driver starts the profiler this long before its window, not
    # before its loop: stop_trace stalls the loop for 0.7-3.6 s a PROFILED second
    # (PERF.md section 6, PR 44), so a profile of the whole lead-in left a traced
    # run no window to read its host-clock metrics from. The start-up itself
    # takes 0.04-0.05 s on the chip: a second leaves some 30 to 100 steady steps
    # before the window in every cell.
    TRACE_START_BEFORE_S = 1.0

    def trace_start(self, window: bool = True) -> None:
        """Start the profiler (0.04 s on the chip, PR 44) and, unless the driver
        opens it later itself, the traced window."""
        import jax

        self.trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # the benchmark's own spans are enough
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        if window:
            self.trace_window_open()

    def trace_window_open(self) -> None:
        from . import reduce

        self._window_span = self.span(reduce.WINDOW_SPAN)
        self._window_span.__enter__()

    def trace_stop(self) -> None:
        import jax

        self._window_span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def trace_reduce(self, span_names) -> None:
        """Reduce the trace in-process (after the window) and drop the files."""
        from . import reduce

        files = glob.glob(os.path.join(self.trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
        if not files:
            raise RuntimeError(f"the profiler wrote no .xplane.pb under {self.trace_dir}")
        keep = os.environ.get("CHIPBENCH_KEEP_TRACE")
        if keep:  # for looking at a trace by hand; not used by a benchmark run
            os.makedirs(keep, exist_ok=True)
            shutil.copy(files[0], os.path.join(keep, f"{self.args.workload}.xplane.pb"))
        self.reduced = reduce.reduce(reduce.load(files[0]), span_names)
        shutil.rmtree(self.trace_dir, ignore_errors=True)


def _device_info(run: Run, devices) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices[:run.chips]]
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": run.chips, "memory_peak_bytes": int(max(peaks))}
    if run.reduced is not None:
        info["busy_s"] = run.reduced["busy_s_mean"]
        info["window_s"] = run.reduced["window_s"]
    return info


def _read_metrics(bench: dict, run: Run, ctx: dict) -> dict:
    group, package = (("per_layer", "layer_metrics") if run.trace
                      else ("end_to_end", "end_to_end"))
    out = {}
    for m in bench[group]:
        if "workloads" in m and run.args.workload not in m["workloads"]:
            continue
        # "<reader>" or "<reader>.<which end-to-end metric it moves here>"
        reader = importlib.import_module(f"chipbench.{package}.{m['name'].split('.')[0]}")
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU; never prints a result")
    args = ap.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    if args.seconds is None:
        args.seconds = float(bench["run_seconds"])
    cell = _named("workloads", args.workload)
    entries = [w for w in bench["workloads"] if w["name"] == args.workload]
    if not entries:
        raise SystemExit(f"BENCHMARK.json has no workload {args.workload!r}")
    config = _named("configs", cell["config"])
    named_reference(config)  # none, or one with no file: ends here, with the list of those there
    chips = int(entries[0]["chips"])

    if args.rehearse:  # before jax is imported
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + f" --xla_force_host_platform_device_count={max(chips, 1)}")
    import jax

    from deepspeed_tpu.utils.jax_env import use_compile_cache

    devices = jax.devices()
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    # a rehearsal's numbers are never printed: any row stands in for the CPU
    peak = peaks.get(devices[0].device_kind, next(iter(peaks.values())) if args.rehearse else None)
    if not args.rehearse:
        problem = (
            devices[0].platform != "tpu" and f"need platform 'tpu', found {devices[0].platform!r}"
            or len(devices) < chips and f"need {chips} chip(s), found {len(devices)}"
            or peak is None and f"device kind {devices[0].device_kind!r} is not in peaks.json")
        if problem:
            print(f"chipbench: {problem}", file=sys.stderr)
            return 3
    cache_dir = use_compile_cache()
    run = Run(args, cell, entries[0], config, peak)
    load_reference(run.program)  # a key the reference does not cover: refused here, by its name
    run.listen()
    note(event="start", workload=args.workload, seed=run.seed, seconds=run.seconds,
         trace=run.trace, rehearse=run.rehearse, cache_dir=cache_dir,
         device=devices[0].device_kind, devices=len(devices))

    driver = importlib.import_module(f"chipbench.drivers.{cell['driver']}")
    ctx = driver.run(run)  # builds, warms up, checks, measures; see drivers/
    ctx.update(run=run, peak=peak, chips=chips, program=run.program, trace=run.reduced,
               devices=devices[:chips])
    note(event="measured", t_setup=ctx["t_setup"], window_s=ctx["window_s"],
         compile_cache=run.cache, compiles_total=len(run.compile_times),
         n_compiles=ctx["n_compiles"], **ctx.get("notes", {}))

    metrics = _read_metrics(bench, run, ctx)
    if args.rehearse:
        print(json.dumps({"rehearsal": "passed", "correct": bool(ctx["correct"]),
                          "attempted": ctx["attempted"], "failed": ctx["failed"],
                          "would_report": sorted(metrics)}), flush=True)
        return 0 if ctx["correct"] else 1
    result = {"correct": bool(ctx["correct"]), "attempted": int(ctx["attempted"]),
              "failed": int(ctx["failed"]), "metrics": metrics,
              "device": _device_info(run, devices)}
    if run.reduced is not None:
        from . import reduce

        result["breakdown"] = reduce.breakdown(run.reduced)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
