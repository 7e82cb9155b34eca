"""Device time per iteration in the Pallas stencil's kernel (its own
self time, mean over the cell's chips), in ms.  The kernel's custom call
carries the name of its wrapper, ``jacobi_step_pallas``."""

import trace_reduce as tr

KERNEL = "jacobi_step_pallas"


def read(run):
    if not run.trace:
        return None
    per_dev = tr.self_ns_where(run.trace, *run.trace_window,
                               lambda name: name.startswith(KERNEL))
    if not any(per_dev.values()):
        return None
    return tr.mean(per_dev) * 1e-6 / run.work("iters")
