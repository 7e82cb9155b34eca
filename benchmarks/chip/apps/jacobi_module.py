"""The compiled text of the module a Jacobi cell's window runs, for the
layer readers (``layers.py``): the program is built as
``apps/jacobi.py``'s session builds it, for the devices JAX gives, and
compiled for the window's shapes (a hit in the compile cache)."""

from __future__ import annotations


def module_text(config: dict, traffic: dict) -> str:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.apps.jacobi import JacobiApp
    from repro.core.address_space import GlobalAddressSpace
    from repro.runtime.transport import Transport

    n, k = int(config["n"]), int(traffic["kernels"])
    app = JacobiApp(n=n, kernels=k, iters=int(config["iters_per_solve"]),
                    transport=Transport(name="tcp", acked=config["acked"],
                                        max_packet_bytes=config["mtu_bytes"]),
                    use_pallas=config["stencil"] == "pallas",
                    interpret=jax.devices()[0].platform != "tpu",
                    piggyback=config["piggyback_acks"])
    grids = jax.ShapeDtypeStruct(
        (k, app.rows, n), jnp.float32,
        sharding=NamedSharding(app.mesh, P(("kernel",))))
    state = GlobalAddressSpace(app.ctx).make_global_state()
    return app.build().lower(state, grids).compile().as_text()
