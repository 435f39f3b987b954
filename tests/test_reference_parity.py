"""Every ``chipbench/configs/*.json`` at its rehearsal size: the system's float32
model against the plain reference it names (``chipbench/parity.py``: ``apply``,
the serving probe through a cache, the loss), and the control that the tolerance
means something: bfloat16 compute fails it (``chipbench/selftest.py`` runs both
halves by hand; PR 47 moved the tier-1 run of them here). A configuration added is
three more cases of each with no edit here."""
import pytest

from chipbench import parity


@pytest.mark.parametrize("config,check", parity.cases())
def test_model_matches_its_reference(config, check):
    parity.check(config, check)


@pytest.mark.parametrize("config,check", parity.cases())
def test_bfloat16_compute_fails_the_tolerance(config, check):
    err = parity.error(config, check, bf16=True)
    assert err > parity.TOL[check], f"bfloat16 compute passes the tolerance ({err:.3g})"
