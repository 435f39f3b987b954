"""The benchmark's traced-window cases (``chipbench/test_trace_window.py``: where a
traced serving run starts and stops the profiler and the host window that leaves,
on a virtual clock), each a tier-1 test of its own; ``chipbench.selftest`` runs
them too, as part of one."""

import pytest

from chipbench.test_trace_window import CASES


@pytest.mark.parametrize("case", list(CASES))
def test_trace_window(case):
    CASES[case]()
