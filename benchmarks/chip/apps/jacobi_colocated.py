"""The paper's Jacobi with several Shoal kernels on one chip:
``JacobiApp(chips=...)``.

As ``apps/jacobi.py``: one call is one whole solve of the
configuration's iterations over a seeded ``(n, n)`` float32 grid, halo
rows put through the PGAS segment and a barrier each iteration, the
compiled Pallas stencil, solves back to back over the traffic's seeded
grids.  Here the traffic's ``kernels`` share the chips,
``kernels_per_chip`` to a chip, so the halo puts between kernels on one
chip take the LOCAL path and issue no collective.

The check is ``apps/jacobi.py``'s, over every kernel boundary.  The
run's file also gets the packets and bytes one iteration carries on each
link class (``links_per_iter``), counted from the program's trace.
"""

from __future__ import annotations

import dataclasses

import traffic as tg
from apps import jacobi as base
from sample import Reservoir

GRID_ERR_LIMIT = base.GRID_ERR_LIMIT


def setup(config, traffic, seed, devices, *, sample, rng):
    return Session(config, traffic, seed, devices, sample, rng)


def make_app(config, traffic, interpret: bool):
    """The program under test, as the configuration and traffic place it."""
    from repro.apps.jacobi import JacobiApp
    from repro.runtime.transport import Transport

    if "chips" not in {f.name for f in dataclasses.fields(JacobiApp)}:
        raise NotImplementedError(
            "the program under test places one Shoal kernel per chip: "
            "JacobiApp takes no `chips`")
    k, per_chip = int(traffic["kernels"]), int(traffic["kernels_per_chip"])
    if (k, per_chip) != (int(config["kernels"]),
                         int(config["kernels_per_chip"])):
        raise ValueError("the traffic's kernels and kernels_per_chip "
                         "differ from the configuration's")
    return JacobiApp(n=int(config["n"]), kernels=k,
                     iters=int(config["iters_per_solve"]),
                     transport=Transport(name="tcp", acked=config["acked"],
                                         max_packet_bytes=config["mtu_bytes"]),
                     use_pallas=config["stencil"] == "pallas",
                     interpret=interpret,
                     piggyback=config["piggyback_acks"],
                     chips=k // per_chip)


class Session(base.Session):
    def __init__(self, config, traffic, seed, devices, sample, rng):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.core.address_space import GlobalAddressSpace

        if traffic["kind"] != "solves":
            raise ValueError(f"jacobi runs solves, not {traffic['kind']!r}")
        app = make_app(config, traffic, devices[0].platform != "tpu")
        if app.chips != len(devices):
            raise ValueError(f"{app.kernels} kernels, {app.kernels // app.chips}"
                             f" a chip, need {app.chips} chips")
        self.n, self.iters, self.kernels = app.n, app.iters, app.kernels
        self.per_chip = app.kernels // app.chips
        self.rows = app.rows
        shard = NamedSharding(app.mesh, P(("kernel",)))
        self.blocks = tg.solve_grids(
            self.n, int(traffic["grids"]), seed,
            (self.kernels, app.rows, self.n), shard)
        self.st0 = GlobalAddressSpace(app.ctx).make_global_state()
        # warm up as the window calls: the jitted function (its fast
        # dispatch path), fed its own output state
        self.fn = fn = app.build()
        jax.block_until_ready(fn(fn(self.st0, self.blocks[0])[0],
                                 self.blocks[-1]))
        self.links = app.links_per_iteration()
        self.st = self.st0
        self.calls = 0
        self.kept = Reservoir(sample, rng)
        self.device = devices[0]
        self.work_per_call = {"iters": self.iters}

    def details(self) -> dict:
        return {**super().details(), "kernels_per_chip": self.per_chip,
                "links_per_iter": self.links}
