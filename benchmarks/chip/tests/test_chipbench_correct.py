"""The correctness check on the CPU, at small sizes: sound runs come out
correct, and the control and every fault the cells can have come out
not correct."""

import dataclasses

import numpy as np
import pytest

import chipbench_helpers as h
import harness

ONE_KERNEL = "jacobi-4096.1chip"


def test_sound_run_is_correct():
    r = h.run_small(ONE_KERNEL)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0


def test_control_is_not_correct():
    prog, ctrl, calls = h.readings_small(ONE_KERNEL)
    assert calls >= 1
    assert harness.correct(prog), prog
    assert not harness.correct(ctrl), ctrl


# -- faults planted in the program under test ----------------------------------

def _stencil(monkeypatch, change):
    from repro.apps.jacobi import JacobiApp

    orig = JacobiApp._stencil

    def faulty(self, block, top, bot, kid):
        return change(block, orig(self, block, top, bot, kid))
    monkeypatch.setattr(JacobiApp, "_stencil", faulty)


JACOBI_FAULTS = {
    "state_unchanged": lambda old, new: old,
    "half_left_out": lambda old, new: new.at[new.shape[0] // 2:].set(
        old[new.shape[0] // 2:]),
    "answer_altered": lambda old, new: new.at[3, 5].add(1e-2),
}


@pytest.mark.parametrize("fault", sorted(JACOBI_FAULTS))
def test_jacobi_fault_is_not_correct(monkeypatch, fault):
    _stencil(monkeypatch, JACOBI_FAULTS[fault])
    r = h.run_small(ONE_KERNEL)
    assert not r["correct"], r["checks"]


def test_four_kernel_checks():
    """Jacobi on 4 virtual devices: a sound run, the control, and the
    exchange between chips left out."""
    out = h.run_script(h.BENCH_DIR / "tests" / "chipbench_four_kernels.py",
                       n_devices=4)
    assert "four-kernel checks passed" in out, out


def test_window_failure_is_reported():
    cell = h.small_cell(ONE_KERNEL)

    class Broken:
        def setup(self, *a, **k):
            s = cell.app.setup(*a, **k)
            s.call = lambda: (_ for _ in ()).throw(RuntimeError("lost"))
            return s
    r = h.run_small(ONE_KERNEL, 0.2,
                    cell=dataclasses.replace(cell, app=Broken()))
    assert not r["correct"] and r["failed"] == 1 and r["metrics"] == {}
    assert np.isfinite(r["attempted"])
