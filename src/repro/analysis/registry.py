"""Registry of lintable entry points — the programs CI guards.

Each entry builds (lazily; the imports are heavy) one representative
compiled program of a subsystem and exposes it to both analyzer passes:
the traceable ``fn(*args)`` for the jaxpr lint and an ``hlo()`` thunk
yielding optimized HLO text for the budget diff.  ``run_entry`` is the
single path the CLI, CI, tests, and benchmarks all share, so "zero
findings on shipped entry points" means the same thing everywhere.

The host platform must be forced to enough devices *before* jax import
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``); the CLI does
this itself, subprocess tests inherit it from conftest.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable

from repro.analysis import hlo_budget, jaxpr_lint
from repro.analysis.report import Report


@dataclasses.dataclass(frozen=True)
class Entry:
    name: str
    description: str
    devices: int                  # host devices the program needs
    build: Callable[[], tuple]    # -> (fn, args, hlo_thunk)


def _tiny_tcp():
    import dataclasses as dc

    from repro.runtime import TCP
    return dc.replace(TCP, max_packet_bytes=64)


def _build_jacobi():
    """Jacobi halo exchange, 64x64 on 8 kernels, segmenting halos."""
    import jax.numpy as jnp

    from repro.apps.jacobi import JacobiApp
    from repro.core.address_space import GlobalAddressSpace

    app = JacobiApp(n=64, kernels=8, iters=1, transport=_tiny_tcp())
    gas = GlobalAddressSpace(app.ctx)
    st = gas.make_global_state()
    blocks = jnp.zeros((8, 64 // 8, 64), jnp.float32)
    fn = app.build()
    return fn, (st, blocks), lambda: fn.lower(st, blocks).compile().as_text()


def _build_jacobi_steady():
    """Jacobi steady state: 4 piggybacked iterations in one scan.

    The budget divides out the trip count: 2 collective-permutes per
    iteration (one per halo direction, no ack collectives — acks ride
    the next iteration's reverse-link packet) plus the 2 loop-exit
    ledger drains.
    """
    import jax.numpy as jnp

    from repro.apps.jacobi import JacobiApp
    from repro.core.address_space import GlobalAddressSpace

    app = JacobiApp(n=64, kernels=8, iters=4, transport=_tiny_tcp(),
                    piggyback=True)
    gas = GlobalAddressSpace(app.ctx)
    st = gas.make_global_state()
    blocks = jnp.zeros((8, 64 // 8, 64), jnp.float32)
    fn = app.build()
    return fn, (st, blocks), lambda: fn.lower(st, blocks).compile().as_text()


def _build_jacobi_colocated():
    """The Jacobi steady state with its 8 kernels on ONE device: 4
    piggybacked iterations whose halo puts, ack lanes, drains and
    barriers all stay inside the device (the LOCAL path)."""
    import jax.numpy as jnp

    from repro.apps.jacobi import JacobiApp
    from repro.core.address_space import GlobalAddressSpace

    app = JacobiApp(n=64, kernels=8, iters=4, transport=_tiny_tcp(),
                    piggyback=True, chips=1)
    gas = GlobalAddressSpace(app.ctx)
    st = gas.make_global_state()
    blocks = jnp.zeros((8, 64 // 8, 64), jnp.float32)
    fn = app.build()
    return fn, (st, blocks), lambda: fn.lower(st, blocks).compile().as_text()


def _build_actors_mailbox():
    """The actor-layer headline: 1024 4-word sends -> one flush."""
    import jax
    import numpy as np

    from repro.core import ops
    from repro.core.address_space import GlobalAddressSpace
    from repro.core.state import ShoalContext
    from repro.runtime import TCP
    from repro.runtime.topology import make_cpu_mesh

    n_msgs, w, n = 1024, 4, 8
    ring = [(i, (i + 1) % n) for i in range(n)]
    ctx = ShoalContext(mesh=make_cpu_mesh(n, ("kernel",)), axes=("kernel",),
                       transport=TCP, segment_words=n_msgs * w + 64)
    gas = GlobalAddressSpace(ctx)

    def prog(st):
        mb = ctx.mailbox(ring, msg_words=w, watermark=1 << 20, token=1)
        base = np.arange(w, dtype=np.float32)
        for i in range(n_msgs):
            st = mb.send(st, base + i, dst_addr=w * i)
        st = mb.flush(st)
        return ops.wait_replies(ctx, st, token=1, n=1)

    fn = gas.spmd(prog)
    st0 = gas.make_global_state()
    jfn = jax.jit(fn)
    return fn, (st0,), lambda: jfn.lower(st0).compile().as_text()


def _build_moe_dispatch():
    """MoE all-to-all expert dispatch (a2a islands, mesh (2, 4))."""
    import dataclasses as dc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.model import ModelConfig, build_model
    from repro.models.moe import MoEDims
    from repro.runtime.topology import make_mesh

    mesh = make_mesh((2, 4), ("data", "model"))
    dims = MoEDims(n_experts=8, top_k=2, d_ff_expert=64, n_shared=1,
                   capacity_factor=16.0, dispatch="a2a")
    cfg = ModelConfig(name="lint-moe", family="moe", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=4, d_ff=128, vocab=128,
                      fsdp=True, seq_shard=True, aux_loss_weight=0.0,
                      moe=dims, dtype=jnp.float32)
    model = build_model(cfg, mesh=mesh, dp_axes=("data",))
    params = build_model(dc.replace(cfg, fsdp=False, seq_shard=False)).init(
        jax.random.PRNGKey(1))
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 128, (4, 32)),
                       jnp.int32)
    batch = {"tokens": toks, "labels": toks}
    jfn = jax.jit(model.loss)
    return (model.loss, (params, batch),
            lambda: jfn.lower(params, batch).compile().as_text())


def _build_lossy_put():
    """Reliable put over a 1%-drop DCN link: 4-seg acked put + wait.

    The compiled program unrolls 1 + max_retries attempt rounds, each a
    data exchange plus an ack exchange — 2*(1+max_retries) CPs — but
    rounds after delivery ship all-NOP packets, so the *dynamic* cost is
    tracked by the ``retransmits`` state counter, not the CP count.
    """
    import jax

    from repro.core import ops
    from repro.core.address_space import GlobalAddressSpace
    from repro.core.faults import FaultModel
    from repro.core.state import ShoalContext
    from repro.runtime import LossyTransport
    from repro.runtime.topology import make_cpu_mesh

    import jax.numpy as jnp

    n = 8
    ring = [(i, (i + 1) % n) for i in range(n)]
    transport = LossyTransport(faults=FaultModel(drop=0.01, seed=7),
                               max_packet_bytes=16, max_retries=4)
    ctx = ShoalContext(mesh=make_cpu_mesh(n, ("kernel",)), axes=("kernel",),
                       transport=transport, segment_words=64)
    gas = GlobalAddressSpace(ctx)

    def prog(st):
        me = ctx.my_id()
        pay = (jnp.arange(16, dtype=jnp.float32) + 1) * (me + 1)
        st = ops.put_long(ctx, st, pay, ring, dst_addr=10, token=1)
        return ops.wait_replies(ctx, st, token=1, n=1)

    fn = gas.spmd(prog)
    st0 = gas.make_global_state()
    jfn = jax.jit(fn)
    return fn, (st0,), lambda: jfn.lower(st0).compile().as_text()


def _build_kv_migrate():
    """Disaggregated-serving KV migration (one vectored put + reply)."""
    import jax
    import jax.numpy as jnp

    from repro.launch.mesh import ServingSlices
    from repro.models.model import ModelConfig, build_model
    from repro.serving.disagg import DisaggServeTier
    from repro.serving.engine import lane_slice

    cfg = ModelConfig(name="lint-kv", family="dense", n_layers=2, d_model=32,
                      n_heads=2, n_kv_heads=2, d_ff=64, vocab=64,
                      dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tier = DisaggServeTier(model, params, ServingSlices(n_prefill=2,
                                                       n_decode=2),
                           lanes_per_decode=2, slots=16)
    blocks = tuple(tier.kv.pack_lane(
        lane_slice(tier.workers[0]._cache0, 0)))
    fn = tier._migration(0, 2, 0)
    st = tier.state
    return fn, (st, blocks), lambda: fn.lower(st, blocks).compile().as_text()


ENTRIES: tuple[Entry, ...] = (
    Entry("jacobi", "Jacobi halo exchange (64x64, 8 kernels, 16-word MTU)",
          8, _build_jacobi),
    Entry("jacobi-steady",
          "Jacobi steady state: 4 piggybacked iterations, <=2 CPs/iter",
          8, _build_jacobi_steady),
    Entry("jacobi-colocated",
          "Jacobi steady state, 8 kernels on 1 device: no collective",
          1, _build_jacobi_colocated),
    Entry("actors-mailbox", "1024 4-word mailbox sends, one flush + wait",
          8, _build_actors_mailbox),
    Entry("moe-dispatch", "MoE a2a expert dispatch, mesh (2,4), 2 layers",
          8, _build_moe_dispatch),
    Entry("kv-migrate", "serving KV migration, prefill 0 -> decode 2",
          4, _build_kv_migrate),
    Entry("lossy-put",
          "reliable 4-seg put over 1%-drop DCN, retransmit + dedup",
          8, _build_lossy_put),
)


def names() -> list[str]:
    return [e.name for e in ENTRIES]


def get(name: str) -> Entry:
    for e in ENTRIES:
        if e.name == name:
            return e
    raise KeyError(f"unknown lint entry {name!r}; known: {names()}")


def run_entry(name: str, budgets: dict | None = None, *,
              include_hlo: bool = True) -> Report:
    """Run both analyzer passes over one registered entry point."""
    import jax

    e = get(name)
    if len(jax.devices()) < e.devices:
        raise RuntimeError(
            f"lint entry {name!r} needs {e.devices} host devices; run "
            "with XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{e.devices} (set before jax import)")
    t0 = time.perf_counter()
    fn, args, hlo_thunk = e.build()
    rep = jaxpr_lint.lint(fn, *args, name=name)
    if include_hlo:
        spec = (hlo_budget.load_budgets() if budgets is None
                else budgets).get(name)
        stats = hlo_budget.measure(hlo_thunk())
        rep.extend(hlo_budget.check_budget(name, stats, spec))
        rep.budget = hlo_budget.budget_row(stats, spec)
    rep.wall_time_s = time.perf_counter() - t0
    return rep
