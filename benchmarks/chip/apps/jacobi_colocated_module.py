"""The compiled text of the module a cell of ``apps/jacobi_colocated.py``
runs, for the layer readers (``layers.py``): the program is built as
that app's session builds it, for the devices JAX gives, and compiled
for the window's shapes (a hit in the compile cache)."""

from __future__ import annotations


def module_text(config: dict, traffic: dict) -> str:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from apps.jacobi_colocated import make_app
    from repro.core.address_space import GlobalAddressSpace

    app = make_app(config, traffic,
                   interpret=jax.devices()[0].platform != "tpu")
    grids = jax.ShapeDtypeStruct(
        (app.kernels, app.rows, app.n), jnp.float32,
        sharding=NamedSharding(app.mesh, P(("kernel",))))
    state = GlobalAddressSpace(app.ctx).make_global_state()
    return app.build().lower(state, grids).compile().as_text()
