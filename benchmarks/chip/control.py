"""Readings of the correctness check for the program and its control.

    python3 benchmarks/chip/control.py --workload <name> --seconds <s> \\
        --seeds <n> [<n> ...]

For each seed: set up the cell as a run does, run a closed-loop window
of ``seconds``, collect what it produced, and compare it twice: as the
program produced it (the lower reading of each number) and with the
plain reference, computed in bfloat16 in the program's place (the
control, which has to come out as not correct).  One process holds the
chips for every seed.  Prints one JSON line per seed; the benchmark's
own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))

import harness  # noqa: E402


def readings(cell, seed: int, seconds: float):
    """``(program checks, control checks, calls)`` for one seed."""
    _, session, run = harness.set_up(cell, seed, time.perf_counter())
    harness.run_window(session, run, seconds)
    data = session.collect()
    return (session.judge(data), session.judge(data, control=True),
            run.n_calls)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(harness.SRC))
    cell = harness.find_cell(args.workload)
    harness.enable_compile_cache()
    try:
        for seed in args.seeds:
            prog, ctrl, calls = readings(cell, seed, args.seconds)
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "calls": calls, "program": prog,
                              "program_correct": harness.correct(prog),
                              "control": ctrl,
                              "control_correct": harness.correct(ctrl)}),
                  flush=True)
    except (harness.NoChip, harness.pk.UnknownDevice) as e:
        print(f"[chipbench] not running: {e}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
