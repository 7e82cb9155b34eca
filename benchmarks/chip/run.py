"""Run one cell of the on-chip benchmark.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

Run from the checkout root on a machine that holds the chips the cell
asks for.  The last line of standard output is the JSON result; the
numbers the correctness check compared, each with its limit, are the
last lines of standard error.  Exits non-zero, printing no result, when
JAX finds no TPU or fewer chips than the cell needs, when the device
kind has no published peaks, or when the program under test is absent.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

T_PROCESS = time.perf_counter()
BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))

import harness  # noqa: E402
import peaks  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (harness.SRC / "repro").is_dir():
        print(f"[chipbench] the program under test is not at {harness.SRC}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(harness.SRC))
    cell = harness.find_cell(args.workload)
    harness.enable_compile_cache()
    try:
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), t_process=T_PROCESS)
    except (harness.NoChip, peaks.UnknownDevice) as e:
        print(f"[chipbench] not running: {e}", file=sys.stderr)
        return 1
    for line in harness.format_checks(result["checks"]):
        print(line, file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
