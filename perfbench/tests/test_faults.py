"""The control and the planted faults (``faults.py``): a run with the
served path broken underneath ends with a failure of the check itself,
not of the platform alone."""

import pytest

from rehearsal import rehearse


@pytest.mark.parametrize("fault, says, loses", [
    ("share_twice", "delivered to a wrong set", False),
    ("stranger", "delivered to a wrong set", False),
    ("drop", "QoS 1 deliveries never arrived", True),
])
def test_a_broken_run_is_not_correct(fault, says, loses):
    line, failures = rehearse("fleet-1m.steady", 0, fault=fault)
    assert line["correct"] is False
    assert says in failures
    assert (line["failed"] > 0) == loses
