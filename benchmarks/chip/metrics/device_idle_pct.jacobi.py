"""Share of the traced window in which no op ran on the device (mean
over the cell's chips), in % -- on the Jacobi cells."""

import trace_reduce as tr


def read(run):
    return tr.idle_pct(run.trace, *run.trace_window) if run.trace else None
