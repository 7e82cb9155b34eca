import os

if __name__ == "__main__":
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")

"""Figs. 7 & 8: the Jacobi application.

Fig. 7 analogue: run time vs kernel count for grids 256..4096 on one
"software node" (the CPU host; iterations scaled 1024 -> 32 for CPU
time, noted in the derived column as iterations).  Small grids are
communication-dominated (more kernels hurt); large grids gain.

Fig. 8 analogue: grid 4096 with 8 kernels co-located on one device
(the paper's one node: every halo put takes the LOCAL path, no
collective) vs the same 8 kernels spread over two devices, 4 each (the
one boundary between them crosses the mesh) — the paper's
multi-node-spread experiment, with the kernels placed for real.

The grid-4096 rows exercise halo rows of 16 KiB > the 9000-byte jumbo
frame: the configuration footnote 2 of the paper could NOT run.  Our
transparent AM segmentation handles it (the correctness check at the
bottom asserts it).
"""

import numpy as np

from repro.apps.jacobi import JacobiApp, jacobi_reference

from benchmarks._timing import time_fn

ITERS = 32


def main():
    rng = np.random.default_rng(0)
    for n in [256, 1024, 4096]:
        grid = rng.standard_normal((n, n)).astype(np.float32)
        for k in [1, 2, 4, 8]:
            app = JacobiApp(n=n, kernels=k, iters=ITERS)
            fn = app.build()
            from repro.core.address_space import GlobalAddressSpace
            import jax.numpy as jnp
            gas = GlobalAddressSpace(app.ctx)
            st = gas.make_global_state()
            blocks = jnp.asarray(grid.reshape(k, n // k, n))
            us = time_fn(fn, st, blocks, iters=3, warmup=1)
            print(f"jacobi/sw/{n}x{n}/k{k},{us:.0f},{ITERS}")

    # Fig. 8: 8 kernels on 1 device (one node) vs spread over 2 devices
    # (two nodes); the paper finds the spread halves per-node memory
    # contention (Sec. IV-C2)
    n = 4096
    grid = rng.standard_normal((n, n)).astype(np.float32)
    ref = jacobi_reference(grid.copy(), ITERS)
    for chips in (1, 2):
        app = JacobiApp(n=n, kernels=8, iters=ITERS, chips=chips)
        out = app.run(grid.copy())
        err = float(np.abs(out - ref).max())
        # >MTU segmentation correctness (paper's footnote-2 failing config)
        assert err < 1e-4, f"4096 halo segmentation broke: {err}"
        from repro.core.address_space import GlobalAddressSpace
        import jax.numpy as jnp
        st = GlobalAddressSpace(app.ctx).make_global_state()
        us = time_fn(app.build(), st,
                     jnp.asarray(grid.reshape(8, n // 8, n)), iters=3,
                     warmup=1)
        print(f"jacobi/fig8/{n}x{n}/k8-on-{chips}dev,{us:.0f},{ITERS}")
    print(f"jacobi/mtu-segmentation-4096/correct,0.0,{err:.2e}")


if __name__ == "__main__":
    main()
