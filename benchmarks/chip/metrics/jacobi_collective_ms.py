"""Device time per iteration in collective ops (collective-permute and
all-reduce, their start and done halves included), mean over the cell's
chips, in ms.  Also writes to the run's file the per-chip values and the
collective-permutes started per iteration."""

import trace_reduce as tr


def is_collective(name: str) -> bool:
    return name.startswith(("collective-permute", "all-reduce"))


def is_permute_start(name: str) -> bool:
    return name.startswith("collective-permute") and "done" not in name


def read(run):
    if not run.trace:
        return None
    lo, hi = run.trace_window
    per_dev = tr.self_ns_where(run.trace, lo, hi, is_collective)
    if not any(per_dev.values()):
        return None
    iters = run.work("iters")
    starts = tr.count_where(run.trace, lo, hi, is_permute_start)
    run.details["collective_ms_per_iter"] = {
        str(k): v * 1e-6 / iters for k, v in per_dev.items()}
    run.details["collective_permutes_per_iter"] = {
        str(k): v / iters for k, v in starts.items()}
    return tr.mean(per_dev) * 1e-6 / iters
