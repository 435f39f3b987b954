"""The readers that are shares of the DEVICE's time, on a hand-made ring and trace:
the decode floors' helper (``layer_metrics/decode_floor.py``) and
``decode_attn_roofline_pct``. No engine, no jax.

    python3 -m pytest chipbench/test_device_shares.py -q

``test_trace_window.py`` takes these cases into its ``CASES``, through which the
repository's tests and ``selftest.py`` run them (``tests/`` holds one file that reads
that table, and a benchmark PR adds none there).
"""

from __future__ import annotations

import json
import os
from types import SimpleNamespace
from unittest import mock

from . import flops, loop_cost
from .layer_metrics import decode_attn_roofline_pct, decode_hbm_floor_pct, loop_decode_hbm_floor_pct
from .layer_metrics import span_ring as R
from .references import program_of

HERE = os.path.dirname(os.path.abspath(__file__))
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
E = 1000.0  # the loop's epoch: traced window [10, 13), host window [40, 61) on its clock
LOOPED = {"layer_passes": 4, "cache_layers": 48}


def _program(name: str):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return program_of(json.load(f))


def _call(i, t0, t1, **attrs):
    sp = lambda j, parent, name, a, b, **kw: SimpleNamespace(  # noqa: E731
        id=j, parent=parent, name=name, path="serve/step/" + name, t0=a, t1=b, attrs=kw)
    return [sp(i, None, "decode", t0, t1, compiled=False, **attrs),
            sp(i + 1, i, "dispatch", t0, t0 + 1e-4), sp(i + 2, i, "fetch", t0 + 1e-4, t1)]


def _ctx(program, ring, ops, notes, traced=(10.0, 13.0)):
    """Ten traced steps of 9,800 live tokens and ten of the host window at 5,000; ``ops``
    are the trace's seconds by operation (None: the run has no trace)."""
    steps = [(10.0 + 0.3 * i, 10.0 + 0.3 * i + 0.02, 24, 9_800) for i in range(10)]
    steps += [(40.0 + i, 40.0 + i + 0.02, 24, 5_000) for i in range(10)]
    trace = None if ops is None else {"op_seconds": dict(ops), "op_text": {
        k: f'%{k} = bf16[24,16,128] custom-call(), custom_call_target="tpu_custom_call"' for k in ops}}
    ctx = {"serve": {"epoch": E, "window": (40.0, 61.0), "traced": traced, "steps": steps},
           "program": program, "peak": PEAK, "trace": trace,
           "run": SimpleNamespace(note=lambda **kw: notes.append(kw))}
    return mock.patch.object(R, "ring", lambda since: [sp for sp in ring if sp.t0 >= since]), ctx


def _ring(span_s: float, **attrs) -> list:
    """Ten decode calls of ``span_s`` in each window, whole inside it."""
    ring = []
    for w, t in enumerate((10.0, 40.0)):
        for i in range(10):
            ring += _call(100 * w + 3 * i, E + t + 0.3 * i, E + t + 0.3 * i + span_s, **attrs)
    return ring


def case_floor_is_over_the_device_time_though_the_span_is_shorter():
    """A step enqueued ahead: every decode span is 5 ms where the device takes 21 ms a
    run. Over the span the share reads 219%; over the device's time, the 52% it is."""
    program, notes = _program("ouro-2.6b-L12"), []
    patch, ctx = _ctx(program, _ring(0.005, **LOOPED), {"jit_decode/fusion.1": 0.150,
                                                        "jit_decode/decode_attention.6": 0.060,
                                                        "jit_prefill/fusion.2": 0.5}, notes)
    need = loop_cost.decode_min_bytes(program, 9_800.0)
    with patch:
        got = loop_decode_hbm_floor_pct.read(ctx)
    floor_ms = 1e3 * need / 819e9
    assert abs(got - 100 * floor_ms / 21.0) < 1e-9 and 50 < got < 55, got
    assert 100 * floor_ms / 5.0 > 105  # what the span gave: refused as impossible
    (note,) = notes
    assert (note["device_ms"], note["live_tokens"], note["window"]) == (21.0, 9_800.0, "traced")
    assert abs(note["span_ms"] - 5.0) < 1e-6 and note["bytes"] == need and note["floor_ms"] == floor_ms
    assert note["layer_passes"] == 4 and note["cache_layers"] == 48


def case_floor_counts_the_traced_steps_not_the_host_windows():
    """Numerator and denominator are of the same steps: the live tokens and the spans'
    attributes are the traced window's (9,800 and 56 experts), not the host window's."""
    program, notes = _program("olmoe-1b-7b-L4"), []
    ring = _ring(0.030, experts_touched=56.0)
    for sp in ring:
        if sp.t0 > E + 30 and "experts_touched" in sp.attrs:
            sp.attrs["experts_touched"] = 64.0
    patch, ctx = _ctx(program, ring, {"jit_decode/fusion.1": 0.070}, notes)
    with patch:
        got = decode_hbm_floor_pct.read(ctx)
    assert (notes[0]["live_tokens"], notes[0]["experts_touched"]) == (9_800.0, 56.0)
    assert abs(notes[0]["device_ms"] - 7.0) < 1e-9 and 0 < got < 100, (got, notes)


def case_floor_without_the_decode_program_in_a_chip_trace_is_none():
    program, notes = _program("ouro-2.6b-L12"), []
    ring = _ring(0.020, **LOOPED)
    # a chip's trace (it names its programs) in which no decode program ran
    patch, ctx = _ctx(program, ring, {"jit_prefill/fusion.2": 0.5}, notes)
    with patch:
        assert loop_decode_hbm_floor_pct.read(ctx) is None and not notes
    # no traced call: the ring's calls all lie in the host window
    patch, ctx = _ctx(program, [sp for sp in ring if sp.t0 > E + 30], {"jit_decode/f": 0.2}, notes)
    with patch:
        assert loop_decode_hbm_floor_pct.read(ctx) is None and not notes
    # a cell that does not serve
    patch, ctx = _ctx(program, ring, {"jit_decode/f": 0.2}, notes)
    with patch:
        assert loop_decode_hbm_floor_pct.read({**ctx, "serve": None}) is None and not notes


def case_floor_of_a_trace_that_names_no_program_stands_on_the_span():
    """The CPU's trace (a rehearsal, which prints no number) names no program, and a run
    that traced nothing has no traced window: the median span stands in, so the
    rehearsal still drives the cost function and lists the metric."""
    program, notes = _program("ouro-2.6b-L12"), []
    floor_ms = 1e3 * loop_cost.decode_min_bytes(program, 9_800.0) / 819e9
    patch, ctx = _ctx(program, _ring(0.020, **LOOPED), {"fusion.1": 0.2, "dot.3": 0.1}, notes)
    with patch:
        assert abs(loop_decode_hbm_floor_pct.read(ctx) - 100 * floor_ms / 20.0) < 1e-6
    patch, ctx = _ctx(program, _ring(0.020, **LOOPED), None, notes, traced=(None, None))
    with patch:
        got = loop_decode_hbm_floor_pct.read(ctx)
    host_floor_ms = 1e3 * loop_cost.decode_min_bytes(program, 5_000.0) / 819e9
    assert abs(got - 100 * host_floor_ms / 20.0) < 1e-6 and notes[-1]["window"] == "window"


def case_decode_roofline_counts_the_kernel_by_its_name_alone():
    """A trace with the decode kernel, the grouped GEMM and the flash forward (three
    Pallas calls: the old pattern, ``tpu_custom_call`` in the text, took all three)."""
    program, notes = _program("olmoe-1b-7b-L4"), []
    ops = {"jit_decode/decode_attention.6": 0.185, "jit_prefill/ragged-dot-gmm.19": 0.4,
           "jit_prefill/flash_fwd.9": 0.3, "jit_decode/fusion.190": 0.7}
    patch, ctx = _ctx(program, [], ops, notes)
    with patch:
        got = decode_attn_roofline_pct.read(ctx)
    cost = flops.decode_attention_cost(10 * 9_800, 16, 128, 4)
    assert abs(got - 100 * cost["bytes"] / 819e9 / 0.185) < 1e-9, got
    assert notes[0]["seconds"] == 0.185 and notes[0]["cache_layers"] == 4
    assert notes[0]["bound"] == "memory" and notes[0]["live_tokens"] == 98_000
    # the kernel did not run (alibi, grouped heads, "decode_attn": "xla"): nothing
    del ops["jit_decode/decode_attention.6"]
    patch, ctx = _ctx(program, [], ops, notes)
    with patch:
        assert decode_attn_roofline_pct.read(ctx) is None and len(notes) == 1
    for bare in ({**ctx, "trace": None}, {**ctx, "serve": None}):
        assert decode_attn_roofline_pct.read(bare) is None


def case_decode_roofline_counts_a_cache_layer_a_pass_and_layer():
    """12 layers run four times keep 48 caches: four times the bytes of ``num_layers``."""
    program, notes = _program("ouro-2.6b-L12"), []
    assert (loop_cost.passes(program), program["num_layers"]) == (4, 12)
    ops = {"jit_decode/decode_attention.6": 3.153 / 2, "jit_decode/fusion.190": 0.36}
    patch, ctx = _ctx(program, [], ops, notes)
    with patch:
        got = decode_attn_roofline_pct.read(ctx)
    by_hand = 100 * (10 * 9_800 * 393_216 / 819e9) / (3.153 / 2)  # 393,216 B a live token
    assert abs(got - by_hand) < 1e-9 and notes[0]["cache_layers"] == 48, (got, by_hand)


CASES = {name[len("case_"):]: fn for name, fn in sorted(globals().items())
         if name.startswith("case_")}


def pytest_generate_tests(metafunc):
    if "case" in metafunc.fixturenames:
        metafunc.parametrize("case", list(CASES))


def test_device_shares(case):
    CASES[case]()
