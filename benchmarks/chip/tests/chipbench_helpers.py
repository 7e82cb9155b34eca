"""Shared helpers of the benchmark's CPU tests: small copies of the cells.

The cells keep their configurations' shapes of work but at sizes a CPU
test can hold: a 64x64 grid for 8 iterations.  Pallas runs in interpret
mode (the apps pick it on a CPU).
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import subprocess
import sys
import tempfile
from pathlib import Path
from unittest import mock

BENCH_DIR = Path(__file__).resolve().parent.parent
ROOT = BENCH_DIR.parent.parent
for p in (str(BENCH_DIR), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402

SEED = 2**31 + 12345   # seeds may need more than 32 signed bits

SMALL = {
    "jacobi-4096.1chip": {"n": 64, "iters_per_solve": 8},
    "jacobi-4096.4chip": {"n": 64, "iters_per_solve": 8},
}


def small_cell(name: str) -> harness.Cell:
    """The cell ``name`` of BENCHMARK.json cut to a CPU test's size."""
    cell = harness.find_cell(name)
    return dataclasses.replace(cell, config={**cell.config, **SMALL[name]})


@contextlib.contextmanager
def on_cpu():
    """Skip the harness's look for a chip: it takes JAX's CPU devices, no
    peaks, and writes each run's file to a temporary directory."""
    import jax

    with tempfile.TemporaryDirectory() as runs, \
            mock.patch.object(harness, "find_devices",
                              lambda chips: jax.devices()[:chips]), \
            mock.patch.object(harness.pk, "peaks_for", lambda kind: {}), \
            mock.patch.object(harness, "RUNS_DIR", Path(runs)):
        yield


def run_small(name: str, seconds: float = 0.3, seed: int = SEED,
              trace: bool = False, cell: harness.Cell | None = None) -> dict:
    """One run of the small cell on the CPU: everything but the look for
    a chip."""
    with on_cpu():
        return harness.run_cell(cell or small_cell(name), seed, seconds,
                                trace)


def readings_small(name: str, seconds: float = 0.3, seed: int = SEED):
    """The program's and the control's readings of the small cell."""
    import control

    with on_cpu():
        return control.readings(small_cell(name), seed, seconds)


def run_script(script: Path, n_devices: int, timeout: int = 600) -> str:
    """Run ``script`` in a child pinned to the CPU with ``n_devices``
    virtual devices."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          capture_output=True, text=True, timeout=timeout,
                          cwd=str(ROOT))
    if proc.returncode != 0:
        raise AssertionError(f"{script.name} failed (rc={proc.returncode})\n"
                             f"{proc.stdout[-3000:]}\n{proc.stderr[-3000:]}")
    return proc.stdout
