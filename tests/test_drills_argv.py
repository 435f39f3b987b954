"""drills.py's command line: one argument table, one reader.

The contract: a malformed value for any drill prints that drill's usage
line on stderr and exits 2 — never a traceback, never a started drill — and
the check runs before jax is imported, so each subprocess is cheap. With no
drill flag the script lists the drills and starts nothing."""

import importlib.util
import os
import re
import subprocess
import sys

import pytest

_DRILLS_PY = os.path.join(os.path.dirname(__file__), "..", "drills.py")


@pytest.fixture(scope="module")
def drills():
    spec = importlib.util.spec_from_file_location("drills", _DRILLS_PY)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run_drills_argv(*argv):
    return subprocess.run([sys.executable, _DRILLS_PY, *argv],
                          capture_output=True, text=True, timeout=60)


@pytest.mark.parametrize("argv", [
    ("--surge", "-3"),            # negative operand, not the next flag
    ("--surge", "abc"),           # non-numeric operand
    ("--surge", "4"),             # below the structural minimum
    ("--surge", "30", "--surge-seed", "xyz"),  # non-numeric seed
    ("--surge", "30", "--surge-seed"),         # dangling seed flag
    ("--gateway-chaos", "7"),                       # unexpected operand
    ("--gateway-chaos", "--gateway-seed", "xyz"),
    ("--gateway-chaos", "--gateway-seed"),
    ("--router-chaos", "7"),
    ("--router-chaos", "--router-seed", "xyz"),
    ("--router-chaos", "--router-seed"),
    ("--tenant-chaos", "7"),
    ("--tenant-chaos", "--tenant-seed", "xyz"),
    ("--tenant-chaos", "--tenant-seed"),
    ("--disagg", "7"),
    ("--disagg", "--disagg-seed", "xyz"),
    ("--disagg", "--disagg-seed"),
    ("--chaos-search", "0"),                          # n below floor
    ("--chaos-search", "xyz"),
    ("--chaos-search", "8", "--chaos-search-seed"),
    ("--chaos-search", "--chaos-search-seed", "xyz"),
    ("--chaos-replay",),                  # missing FILE operand
    ("--chaos-replay", "--chaos-search"),  # flag where FILE belongs
    ("--chaos", "-3"),
    ("--chaos", "abc"),
    ("--chaos", "4"),             # no room for 2 preempts + 1 NaN
    ("--chaos", "12", "--chaos-seed", "xyz"),
    ("--chaos", "12", "--chaos-seed"),
    ("--chaos-serving", "--chaos-seed", "xyz"),
    ("--chaos-serving", "--chaos-seed"),
    ("--fault-rate",),            # missing operand
    ("--fault-rate", "lots"),
], ids=" ".join)
def test_argv_contract_exits_2_with_usage(argv):
    proc = _run_drills_argv(*argv)
    assert proc.returncode == 2, (argv, proc.stderr)
    assert f"usage: drills.py {argv[0]} " in proc.stderr
    assert "Traceback" not in proc.stderr
    assert proc.stdout == ""


def test_no_flag_lists_every_drill_and_starts_nothing(drills):
    proc = _run_drills_argv()
    assert proc.returncode == 2 and proc.stdout == ""
    assert "Traceback" not in proc.stderr
    for flag, _, operand, seed_flag in drills._DRILLS:
        assert f"drills.py {drills._usage(flag, operand, seed_flag)}" in proc.stderr


def test_table_names_ten_drills_and_their_functions(drills):
    flags = [entry[0] for entry in drills._DRILLS]
    assert len(flags) == len(set(flags)) == 10
    for flag, drill, operand, seed_flag in drills._DRILLS:
        assert getattr(drills, drill.__name__) is drill
        assert drills._usage(flag, operand, seed_flag).split()[0] == flag


@pytest.mark.parametrize("argv, args", [
    (("--surge",), [30, 0]),
    (("--surge", "--surge-seed", "5"), [30, 5]),      # "--" is the next flag
    (("--chaos", "9", "--chaos-seed", "-2"), [9, -2]),
    (("--chaos-serving", "--chaos-seed", "3"), [3]),
    (("--chaos-search",), [64, 0]),
    (("--fault-rate", "0.05"), [0.05]),
    (("--chaos-replay", "repro.json"), ["repro.json"]),
    (("--disagg",), [0]),
], ids=lambda v: " ".join(v) if isinstance(v, tuple) else None)
def test_well_formed_argv_reads_the_parents_defaults(drills, monkeypatch,
                                                     argv, args):
    flag, _, operand, seed_flag = next(
        e for e in drills._DRILLS if e[0] == argv[0])
    monkeypatch.setattr(sys, "argv", ["drills.py", *argv])
    assert drills._drill_args(flag, operand, seed_flag) == args


def test_drill_rows_say_cpu_and_nothing_of_speed():
    """A drill's row names its platform as a literal; the labelled nulls of
    the old measurement's stamp are gone (``--tenant-chaos`` keeps the three
    ``tenant_*`` values it measures, as its own keys)."""
    with open(_DRILLS_PY) as f:
        text = f.read()
    assert len(re.findall(r'^\s+"platform": "cpu",$', text, re.M)) == 10
    for key in ("comparable", "mfu", "roofline", "step_anatomy",
                "spec_acceptance_rate",
                "spec_tokens_per_sec_per_request_ratio"):
        assert f'"{key}"' not in text
    for key in ("tenant_victim_ttft_p99_ratio", "tenant_victim_sheds",
                "tenant_aggressor_429s"):
        assert text.count(f'"{key}"') == 1
