"""Jacobi's four-kernel cell at a small size on 4 virtual CPU devices
(run by test_chipbench_correct.py in a child process): a sound run is
correct and agrees with the reference; the control and a run with the
halo exchange left out are not correct."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import chipbench_helpers as h  # noqa: E402
import harness  # noqa: E402

NAME = "jacobi-4096.4chip"


def main():
    r = h.run_small(NAME)
    assert r["correct"], r["checks"]
    assert r["checks"]["grid_max_abs_err"]["value"] == 0.0, r["checks"]
    assert r["checks"]["halo_max_abs_err"]["value"] == 0.0, r["checks"]

    prog, ctrl, _ = h.readings_small(NAME)
    assert harness.correct(prog) and not harness.correct(ctrl), (prog, ctrl)

    from repro.apps.jacobi import JacobiApp
    JacobiApp._halo_exchange = lambda self, st, block, it=None: st
    r = h.run_small(NAME)
    assert not r["correct"], r["checks"]
    print("four-kernel checks passed")


if __name__ == "__main__":
    main()
