"""The rate at which each chip moves its band through an iteration, in
GB/s (1e9 bytes/s).

The least bytes an iteration must move on a chip are the algorithm's
own: read the chip's rows of the grid once and write them once,
``2 * (n / chips) * n * itemsize`` bytes, however many Shoal kernels
share the chip.  The rate is those bytes over the chip's busy time per
iteration (the union of its op intervals in the traced window over the
iterations in it), mean over the cell's chips.

It is a rate, not a share of a peak.  The band (at most 64 MiB a chip in
these cells) fits in the v5e's 128 MiB of on-chip memory, and the
compiled solve carries it there (memory space ``S(1)``), so HBM's
bandwidth is no ceiling; and no published figure gives the bandwidth
of the on-chip memory.  A cell whose band outgrows it can be read
against HBM's peak again."""

import numpy as np

import trace_reduce as tr


def bytes_per_chip(n: int, chips: int, dtype: str) -> float:
    """The bytes an iteration reads and writes of one chip's rows."""
    return 2 * (n / chips) * n * np.dtype(dtype).itemsize


def read(run):
    if not run.trace:
        return None
    moved = bytes_per_chip(run.details["n"], run.cell.chips,
                           run.cell.config["dtype"])
    busy = tr.busy_ns(run.trace, *run.trace_window)
    iters = run.work("iters")
    rates = {k: moved * iters / b for k, b in busy.items() if b}
    return tr.mean(rates) if rates else None
