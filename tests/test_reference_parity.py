"""Every ``chipbench/configs/*.json`` at its rehearsal size: the system's float32
model against the plain reference it names (``chipbench/parity.py``: ``apply``,
the serving probe through a cache, the loss). A configuration added is three
more cases with no edit here."""
import pytest

from chipbench import parity


@pytest.mark.parametrize("config,check", parity.cases())
def test_model_matches_its_reference(config, check):
    parity.check(config, check)
