"""The whole Jacobi step's share of the chip's HBM roofline, in %.

The least bytes an iteration must move on a chip are the algorithm's
own: read its band of ``n / kernels`` rows of ``n`` float32 once and
write it once, ``2 * rows * n * 4`` bytes.  At the peak HBM bandwidth
of the device kind (``peaks.py``) that takes the least time; the share
is that time over the device's busy time per iteration (the union of
its op intervals in the traced window over the iterations in it), mean
over the cell's chips.  It is bound by bytes, not operations: 4 flops
per 8 bytes is far below the v5e's ridge.

HBM bounds the step only where the band lives in HBM.  On one kernel the
compiled solve carries its 64-MiB band in HBM; on four kernels it
carries the 16-MiB band in on-chip memory (memory space ``S(1)`` in the
compiled HLO), where HBM's bandwidth is no ceiling.  So
``BENCHMARK.json`` reads this share in the one-kernel cell only."""

import trace_reduce as tr


def read(run):
    if not run.trace or not run.peaks:
        return None
    d = run.details
    rows = d["n"] // d["kernels"]
    least_s = 2 * rows * d["n"] * 4 / run.peaks["hbm_Bps"]
    busy = tr.busy_ns(run.trace, *run.trace_window)
    iters = run.work("iters")
    shares = {k: 100.0 * least_s / (b * 1e-9 / iters)
              for k, b in busy.items() if b}
    return tr.mean(shares) if shares else None
