"""Several Shoal kernels on one device (the paper's several kernels on
one node): the Jacobi app against the plain reference, the AM ops
between co-resident kernels against one kernel per device, patterns
with LOCAL and ICI pairs at once, the reliable put under ICI faults,
and the collective budgets.  The
checks run once, in a subprocess with 8 host devices
(tests/colocated_checks.py); each is a case here."""

import json

import pytest

from colocated_checks import CHECKS
from conftest import run_subprocess_checks


@pytest.fixture(scope="module")
def results():
    out = run_subprocess_checks("colocated_checks.py", n_devices=8,
                                timeout=900)
    lines = [json.loads(l) for l in out.splitlines() if l.startswith("{")]
    return {r["check"]: r for r in lines}


@pytest.mark.parametrize("name", sorted(CHECKS))
def test_colocated(results, name):
    assert name in results, f"{name} did not report"
    assert results[name]["ok"], results[name]["error"]
