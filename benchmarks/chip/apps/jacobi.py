"""The paper's Jacobi app as Shoal runs it: ``JacobiApp.build()``.

One call is one whole solve: the configuration's iterations over a
seeded ``(n, n)`` float32 grid, row-partitioned over one Shoal kernel per
chip, with halo rows put through the PGAS segment each iteration, a
barrier each iteration and the compiled Pallas stencil.  Solves run back
to back and cycle through the traffic's seeded grids; the PGAS state
threads from solve to solve.

The check takes a sample of solves drawn from the seed, and the last
one, and compares each final grid with the plain reference run from the
same grid.  On several kernels it also compares the halo rows left in
each kernel's segment with the rows of the reference's grid before the
last iteration, which shows that the halos arrived through the PGAS
segment.  The credit
file, the deferred-ack ledger, the error bits and each solve's barrier
count (a difference, so the int32 epoch may wrap) are compared exactly.
"""

from __future__ import annotations

import numpy as np

import traffic as tg
from reference import jacobi as ref
from sample import Reservoir

# limit on the largest |program - reference| over a compared grid: set
# from the program's readings (0 on every seed) and the bfloat16
# control's (PERF.md, "Correctness")
GRID_ERR_LIMIT = 1e-4


def setup(config, traffic, seed, devices, *, sample, rng):
    return Session(config, traffic, seed, devices, sample, rng)


def _u32(x) -> np.ndarray:
    return np.asarray(x).astype(np.int64) & 0xFFFFFFFF


class Session:
    def __init__(self, config, traffic, seed, devices, sample, rng):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.apps.jacobi import JacobiApp
        from repro.core.address_space import GlobalAddressSpace
        from repro.runtime.transport import Transport

        if traffic["kind"] != "solves":
            raise ValueError(f"jacobi runs solves, not {traffic['kind']!r}")
        k = int(traffic["kernels"])
        if k != len(devices):
            raise ValueError(f"{k} kernels need {k} chips, one each")
        self.n, self.iters, self.kernels = (int(config["n"]),
                                            int(config["iters_per_solve"]),
                                            k)
        transport = Transport(name="tcp", acked=config["acked"],
                              max_packet_bytes=config["mtu_bytes"])
        app = JacobiApp(n=self.n, kernels=k, iters=self.iters,
                        transport=transport,
                        use_pallas=config["stencil"] == "pallas",
                        interpret=devices[0].platform != "tpu",
                        piggyback=config["piggyback_acks"])
        self.rows = app.rows
        shard = NamedSharding(app.mesh, P(("kernel",)))
        self.blocks = tg.solve_grids(self.n, int(traffic["grids"]), seed,
                                     (k, app.rows, self.n), shard)
        self.st0 = GlobalAddressSpace(app.ctx).make_global_state()
        # warm up as the window calls: the jitted function (its fast
        # dispatch path), fed its own output state
        self.fn = fn = app.build()
        jax.block_until_ready(fn(fn(self.st0, self.blocks[0])[0],
                                 self.blocks[-1]))
        self.st = self.st0
        self.calls = 0
        self.kept = Reservoir(sample, rng)
        self.device = devices[0]
        self.work_per_call = {"iters": self.iters}

    def call(self):
        self.prev = self.st
        self.st, out = self.fn(self.st,
                               self.blocks[self.calls % len(self.blocks)])
        self.calls += 1
        return self.st, out

    def keep(self, result) -> None:
        st, out = result
        self.kept.offer(self.calls - 1, (self.prev.barrier_epoch, st, out))

    # -- the check -------------------------------------------------------------

    def outputs(self) -> dict:
        """What the window produced at the sampled solves, on the host."""
        import jax

        out = {}
        for c, (epoch0, st, grid) in self.kept.chosen().items():
            seg, cred, owed, err, epoch, g = jax.device_get(
                (st.segment, st.credits, st.deferred_acks, st.error,
                 st.barrier_epoch, grid))
            out[c] = {"grid": np.asarray(g).reshape(self.n, self.n),
                      "segment": np.asarray(seg),
                      "credits": np.abs(np.asarray(cred)).sum()
                      + np.abs(np.asarray(owed)).sum(),
                      "error": int(np.bitwise_or.reduce(np.asarray(err))),
                      "epochs": (_u32(epoch) - _u32(epoch0)) % (1 << 32)}
        return out

    def details(self) -> dict:
        return {"calls": self.calls, "iters_per_solve": self.iters,
                "kernels": self.kernels, "n": self.n}

    def collect(self) -> dict:
        """After the window: host copies of what is compared; the
        program's state is freed before any reference runs."""
        outputs = self.outputs()
        grids = {c: c % len(self.blocks) for c in outputs}
        del self.st, self.prev, self.kept, self.fn
        inputs = {g: np.asarray(self.blocks[g]).reshape(self.n, self.n)
                  for g in set(grids.values())}
        del self.blocks
        return {"outputs": outputs, "grids": grids, "inputs": inputs}

    def judge(self, data: dict, control: bool = False) -> dict:
        """Compare the sampled solves with the reference.  ``control``
        puts the reference, computed in bfloat16, in the program's
        place."""
        outputs, grids = data["outputs"], data["grids"]
        if "refs" not in data:
            data["refs"] = reference(data["inputs"], self.iters, self.device)
        refs = data["refs"]
        if control:
            outputs = self.control_outputs(outputs, grids, data["inputs"])
        return compare(self, outputs, grids, refs)

    def check(self) -> dict:
        return self.judge(self.collect())

    def control_outputs(self, outputs, grids, inputs) -> dict:
        """The reference in bfloat16, with its halo rows in the segment
        and every credit and counter as a correct run leaves them."""
        import jax.numpy as jnp

        low = reference(inputs, self.iters, self.device, jnp.bfloat16)
        n, rows, out = self.n, self.rows, {}
        for c, o in outputs.items():
            final, before = low[grids[c]]
            seg = np.zeros_like(o["segment"])
            for kid in range(self.kernels):
                if kid > 0:
                    seg[kid, :n] = before[kid * rows - 1]
                if kid < self.kernels - 1:
                    seg[kid, n:2 * n] = before[(kid + 1) * rows]
            out[c] = {"grid": final, "segment": seg, "credits": 0,
                      "error": 0, "epochs": np.full(self.kernels, self.iters)}
        return out


def reference(inputs: dict, iters: int, device, dtype=None) -> dict:
    """The plain reference from each grid: ``{grid: (final, before)}``."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.float32 if dtype is None else dtype
    out = {}
    for g, grid in inputs.items():
        final, before = ref.solve(jax.device_put(grid, device), iters,
                                  dtype=dtype)
        out[g] = (np.asarray(final), np.asarray(before))
    return out


def compare(session, outputs, grids, refs) -> dict:
    """Each number compared, with its limit."""
    n, rows, k = session.n, session.rows, session.kernels
    err, halo_err, credits, error, epochs_off = 0.0, 0.0, 0, 0, 0
    for c, o in outputs.items():
        final, before = refs[grids[c]]
        err = max(err, float(np.max(np.abs(o["grid"] - final))))
        for kid in range(k):
            seg = o["segment"][kid]
            if kid > 0:      # top halo: the row above this band
                halo_err = max(halo_err, float(np.max(np.abs(
                    seg[:n] - before[kid * rows - 1]))))
            if kid < k - 1:  # bottom halo: the row below it
                halo_err = max(halo_err, float(np.max(np.abs(
                    seg[n:2 * n] - before[(kid + 1) * rows]))))
        credits += int(o["credits"])
        error |= o["error"]
        epochs_off += int(np.sum(np.abs(o["epochs"] - session.iters)))
    checks = {"grid_max_abs_err": {"value": err, "limit": GRID_ERR_LIMIT}}
    if k > 1:
        checks["halo_max_abs_err"] = {"value": halo_err,
                                      "limit": GRID_ERR_LIMIT}
    checks.update({
        "credits_left": {"value": credits, "limit": 0},
        "error_bits": {"value": error, "limit": 0},
        "barrier_epochs_off": {"value": epochs_off, "limit": 0},
    })
    return checks
