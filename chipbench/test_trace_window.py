"""Where a traced serving run starts and stops the profiler, and what it leaves
to read: ``drivers/serve.py::_loop`` on a virtual clock, no engine, no jax.

    python3 -m pytest chipbench/test_trace_window.py -q

``selftest.py`` runs the same cases (``CASES``), so the repository's tests hold
them through ``python3 -m chipbench.selftest``. The stub server takes 20 ms of
the virtual clock a step; the stub profiler takes ``start_s`` to start and
``stop_rate`` seconds to stop for every second it profiled, which is how
``jax.profiler.stop_trace`` costs on the chip (README.md, "The timeline of a
traced serving run").
"""

from __future__ import annotations

import contextlib
import glob
import json
import os
from types import SimpleNamespace
from unittest import mock

from .drivers import serve
from .run import Run

HERE = os.path.dirname(os.path.abspath(__file__))
BEFORE = Run.TRACE_START_BEFORE_S
STEP_S = 0.02
# over the slowest stop_trace read on the chip, seconds a profiled second: bloom-1b7.serve-doc's
# 9.05 s for 4.05 is 2.23 (PERF.md section 6, PR 44; the six cells read 0.7-2.2, by their operations a second)
WORST_STOP_RATE = 2.5


class Clock:
    """``time`` for ``_loop``: nothing waits, ``sleep`` moves the clock."""

    def __init__(self):
        self.t = 1000.0

    def perf_counter(self) -> float:
        return self.t

    def sleep(self, seconds: float) -> None:
        self.t += seconds


class StubRun:
    TRACE_START_BEFORE_S = BEFORE

    def __init__(self, clock, *, trace, seconds, settle_s=5.0, start_s=0.05, stop_rate=1.2):
        self.clock, self.trace = clock, trace
        self.blocks = {"trace": {"seconds": seconds, "settle_s": settle_s},
                       "traffic": {"grace_s": 5.0}}
        self.start_s, self.stop_rate = start_s, stop_rate
        self.events = []  # (name, clock at entry, clock at exit)

    def sized(self, block):
        return self.blocks[block]

    @staticmethod
    def span(name):
        return contextlib.nullcontext()

    def _event(self, name, takes):
        t0 = self.clock.t
        self.clock.sleep(takes)
        self.events.append((name, t0, self.clock.t))

    def trace_start(self, window=True):
        assert window is False
        self._event("trace_start", self.start_s)

    def trace_window_open(self):
        self._event("trace_window_open", 0.0)

    def trace_stop(self):
        profiled = self.clock.t - self.events[0][1]  # the start-up counts as profiled
        self._event("trace_stop", self.stop_rate * profiled)


class StubServer:
    """Four slots; a request is done ``steps_per_request`` steps after admission."""

    n_slots = 4

    def __init__(self, clock, steps_per_request=25):
        self.clock, self.steps_per_request = clock, steps_per_request
        self.queue, self.live, self.results = [], {}, {}
        self.epoch = None

    n_active = property(lambda self: len(self.live))
    n_prefilling = property(lambda self: len(self.queue))

    def set_epoch(self, epoch):
        self.epoch = epoch

    def submit(self, request):
        self.queue.append(request.uid)

    def step(self):
        self.clock.sleep(STEP_S)
        now = self.clock.t - self.epoch
        while self.queue and len(self.live) < self.n_slots:
            self.live[self.queue.pop(0)] = [now, 0]
        finished = []
        for uid, state in list(self.live.items()):
            state[1] += 1
            if state[1] == self.steps_per_request:
                self.results[uid] = SimpleNamespace(
                    status="ok", tokens=[0] * state[1], admitted_time=state[0],
                    first_token_time=state[0] + STEP_S, finish_time=now)
                del self.live[uid]
                finished.append(uid)
        return finished

    def live_progress(self):
        return {uid: [0] * n for uid, (_, n) in self.live.items()}

    def result(self, uid):
        return self.results.get(uid)


def drive(*, lo, seconds, trace=True, steps_per_request=25, loop="closed", **profiler):
    """One closed loop of eight clients over ``[lo, lo + 51)`` (``loop="open"``:
    every request arrives at 0); returns what ``_loop`` returned and the stub
    profiler's calls as (entry, exit) on the loop's clock."""
    clock = Clock()
    hi = lo + 51.0
    run = StubRun(clock, trace=trace, seconds=seconds, **profiler)
    requests = [{"uid": i, "prompt": [0], "max_new_tokens": steps_per_request,
                 "temperature": 0.0, "top_p": 1.0, "arrival_time": 0.0} for i in range(4000)]
    traffic = {"requests": requests[:4000 if loop == "closed" else 8], "window": (lo, hi),
               "loop": loop, "clients": 8}
    with mock.patch.object(serve, "time", clock):
        out = serve._loop(run, StubServer(clock, steps_per_request), SimpleNamespace, traffic, {})
    on_loop_clock = {name: (t0 - out["epoch"], t1 - out["epoch"]) for name, t0, t1 in run.events}
    return out, on_loop_clock


def _raises(**kwargs) -> str:
    try:
        drive(**kwargs)
    except serve.HostWindowTooShort as e:
        return str(e)
    raise AssertionError("a traced run with no host window to read printed a result")


def case_profiler_starts_inside_a_long_lead_in():
    """lfm2's sizes: lead-in 30, 6 s traced."""
    out, at = drive(lo=30.0, seconds=6.0)
    start = at["trace_start"]
    assert 30.0 - BEFORE <= start[0] < 30.0 - BEFORE + 2 * STEP_S, start  # not before the clock
    assert start[1] < 30.0
    assert out["profiler"]["start_at"] == start[0]
    assert abs(out["profiler"]["start_s"] - 0.05) < 1e-9


def case_short_lead_in_starts_in_the_first_iteration():
    """A lead-in no longer than the constant (a rehearsal's 1 s): the same lines,
    the start falls at 0 on the loop's clock and the window opens on time."""
    for lo in (BEFORE, BEFORE / 2):
        out, at = drive(lo=lo, seconds=1.0, settle_s=0.5)
        assert at["trace_start"][0] == 0.0 == out["profiler"]["start_at"], at
        assert lo <= out["traced"][0] < lo + 2 * STEP_S


def case_traced_pair_is_the_first_seconds_of_the_window():
    out, at = drive(lo=30.0, seconds=6.0)
    t0, t1 = out["traced"]
    assert 30.0 <= t0 < 30.0 + 2 * STEP_S and 36.0 <= t1 < 36.0 + 2 * STEP_S, (t0, t1)
    assert at["trace_window_open"][0] == t0 and at["trace_stop"][0] == t1


def case_host_window_runs_from_the_stop_and_the_settle_to_hi():
    out, at = drive(lo=30.0, seconds=6.0, stop_rate=1.5)
    resume_at, hi = out["window"]
    assert hi == 81.0 and abs(resume_at - (at["trace_stop"][1] + 5.0)) < 1e-9
    profiled = at["trace_stop"][0] - at["trace_start"][0]
    assert profiled <= BEFORE + 6.0 + 3 * STEP_S  # not the 36 s a start before the clock profiles
    assert abs(out["profiler"]["stop_s"] - 1.5 * profiled) < 1e-9
    # ISSUE 44's arithmetic: 81 - (36 + 1.5 x (BEFORE + 6) + 5), 23 s at its BEFORE of 5
    assert hi - resume_at >= 40.0 - 1.5 * (BEFORE + 6.0) - 0.2 >= 23.0, resume_at
    assert sum(resume_at <= r["arrival"] < hi for r in out["records"]) > 100


def case_start_up_past_lo_opens_the_window_late():
    out, at = drive(lo=30.0, seconds=6.0, start_s=BEFORE + 1.5)
    assert out["traced"][0] == at["trace_start"][1] and out["traced"][0] >= 31.5
    assert 36.0 <= out["traced"][1] < 36.0 + 2 * STEP_S  # the window still closes at lo + seconds


def case_stop_past_hi_raises():
    message = _raises(lo=30.0, seconds=6.0, stop_rate=46.0 / (BEFORE + 6.0))  # resumes at 87
    assert "trace_stop_s=" in message and "resume_at=" in message and "hi=81.0" in message


def case_host_window_under_a_fifth_raises():
    # 36 + 30.8 + 5 = 71.8: a host window of 9.2 s, under a fifth of 51
    message = _raises(lo=30.0, seconds=6.0, stop_rate=30.8 / (BEFORE + 6.0))
    assert "trace_stop_s=" in message and "under 20%" in message


def case_host_window_with_no_arrival_raises():
    message = _raises(lo=30.0, seconds=6.0, steps_per_request=10 ** 6)
    assert "with 0 arrival(s)" in message


def case_loop_that_ends_before_the_window_raises():
    """Eight open-loop requests are done in a second: the profiler never started,
    nothing arrived in the window, and the message says so (``trace_stop_s=None``)."""
    message = _raises(lo=30.0, seconds=6.0, loop="open")
    assert "trace_stop_s=None" in message and "with 0 arrival(s)" in message


def case_untraced_run_touches_no_profiler():
    out, at = drive(lo=30.0, seconds=6.0, trace=False)
    assert at == {} and out["window"] == (30.0, 81.0) and out["traced"] == (None, None)
    assert out["profiler"] == {"start_at": None, "start_s": None, "stop_s": None}


def case_every_cell_file_leaves_a_host_window():
    """``trace.seconds`` is bounded by the host window it leaves: at the slowest
    stop_trace read in any cell, every serving cell keeps the least share."""
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        window = float(json.load(f)["run_seconds"])
    seen = 0
    for path in sorted(glob.glob(os.path.join(HERE, "workloads", "*.json"))):
        with open(path) as f:
            cell = json.load(f)
        if "lead_in_s" not in cell.get("traffic", {}):
            continue  # the train driver profiles after its window
        seen += 1
        lead, trace = float(cell["traffic"]["lead_in_s"]), cell["trace"]
        profiled = min(lead, BEFORE) + trace["seconds"]
        left = window - trace["seconds"] - WORST_STOP_RATE * profiled - trace["settle_s"]
        assert left >= serve.HOST_WINDOW_MIN_SHARE * window, (cell["name"], left)
    assert seen >= 6


CASES = {name[len("case_"):]: fn for name, fn in sorted(globals().items())
         if name.startswith("case_")}


def pytest_generate_tests(metafunc):
    if "case" in metafunc.fixturenames:
        metafunc.parametrize("case", list(CASES))


def test_trace_window(case):
    CASES[case]()
