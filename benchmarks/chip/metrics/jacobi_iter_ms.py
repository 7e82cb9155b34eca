"""Window time over all iterations of the solves completed in it, in
milliseconds per iteration.  Host clock."""


def read(run):
    iters = run.work("iters")
    return run.window_s * 1e3 / iters if iters else None
