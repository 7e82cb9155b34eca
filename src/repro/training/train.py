"""The trainer: train_step factories with selectable comm backend.

Two backends, both producing the same math (tested against each other):

* ``xla`` — the whole step is one jit-GSPMD program: batch sharded over
  the DP axes, weights per the model's PartitionSpecs, collectives
  inserted and fused/overlapped by the compiler.  The *beyond-paper*
  path and the hillclimb vehicle.
* ``shoal`` — the paper-faithful path: loss+grad run *manually* sharded
  over the DP axes (partial-manual shard_map, model axis left to
  GSPMD), and the DP gradient sync is an explicit Shoal ring
  all-reduce (:func:`repro.core.collectives.ring_all_reduce`) — i.e. the
  one-sided Long-put-with-ADD datapath.  Optional int8 error-feedback
  compression on the sync.  Requires replicated-over-DP params (no
  FSDP): every rank applies the synced gradient to a full copy.

Also here: gradient accumulation (microbatching), straggler-quorum DP
(see :mod:`repro.training.elastic`), and metrics.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map

from repro.core import collectives as coll
from repro.models.model import Model
from repro.optim import adamw as aw
from repro.optim import dist as od


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jnp.ndarray
    ef_residual: Any = None       # int8 error-feedback buffers (or None)


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    comm_backend: str = "xla"       # xla | shoal
    microbatches: int = 1
    grad_compression: bool = False  # int8 EF on the DP sync (shoal backend)
    donate: bool = True


class Trainer:
    def __init__(self, model: Model, opt_cfg: aw.AdamWConfig,
                 tcfg: TrainerConfig = TrainerConfig(),
                 dp_axes: tuple[str, ...] | None = None):
        """``dp_axes`` defaults to the model's.  For the shoal backend the
        model should be built with ``dp_axes=()`` (its activation
        constraints must not mention the manual DP axes) and the real DP
        axes passed here."""
        self.model = model
        self.opt_cfg = opt_cfg
        self.tcfg = tcfg
        self.mesh = model.mesh
        self.dp_axes = dp_axes if dp_axes is not None else model.dp_axes

    # -- state ----------------------------------------------------------------

    def init_state(self, key) -> TrainState:
        params = self.model.init(key)
        opt = aw.adamw_init(params)
        ef = (od.make_error_feedback(params)
              if self.tcfg.grad_compression else None)
        return TrainState(params=params, opt_state=opt,
                          step=jnp.zeros((), jnp.int32), ef_residual=ef)

    def state_pspecs(self, state: TrainState):
        pp = self.model.param_pspecs(state.params)
        dp = self.dp_axes[-1]
        dp_size = self.mesh.shape[dp] if self.mesh is not None else 1
        opt_p = {
            "m": od.zero1_pspecs(pp, dp, state.params, dp_size),
            "v": od.zero1_pspecs(pp, dp, state.params, dp_size),
            "count": P(),
        }
        ef = None if state.ef_residual is None else jax.tree.map(
            lambda *_: P(), state.ef_residual)
        return TrainState(params=pp, opt_state=opt_p, step=P(),
                          ef_residual=ef)

    def state_shardings(self, state: TrainState):
        if self.mesh is None:
            return None
        specs = self.state_pspecs(state)
        return jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))

    def batch_pspec(self) -> P:
        return P(self.dp_axes)

    def batch_shardings(self, batch):
        if self.mesh is None:
            return {k: None for k in batch}
        return {k: NamedSharding(self.mesh, P(self.dp_axes))
                for k in batch}

    # -- losses ----------------------------------------------------------------

    def _loss_microbatched(self, params, batch):
        n = self.tcfg.microbatches
        if n == 1:
            return self.model.loss(params, batch)

        def slice_mb(x, i):
            mb = x.shape[0] // n
            return jax.lax.dynamic_slice_in_dim(x, i * mb, mb, 0)

        def body(acc, i):
            mb = {k: slice_mb(v, i) for k, v in batch.items()}
            return acc + self.model.loss(params, mb), None

        # checkpoint the microbatch body: otherwise the scan stacks every
        # microbatch's residuals and grad accumulation saves no memory
        body = jax.checkpoint(body)
        total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                                jnp.arange(n))
        return total / n

    # -- xla backend -------------------------------------------------------------

    def make_train_step(self):
        if self.tcfg.comm_backend == "shoal":
            return self._make_train_step_shoal()
        return self._make_train_step_xla()

    def _apply_update(self, state: TrainState, grads, loss):
        new_params, new_opt, metrics = aw.adamw_update(
            self.opt_cfg, grads, state.opt_state, state.params)
        metrics["loss"] = loss
        new_state = TrainState(params=new_params, opt_state=new_opt,
                               step=state.step + 1,
                               ef_residual=state.ef_residual)
        return new_state, metrics

    def _make_train_step_xla(self):
        def step(state: TrainState, batch):
            loss, grads = jax.value_and_grad(self._loss_microbatched)(
                state.params, batch)
            return self._apply_update(state, grads, loss)

        donate = (0,) if self.tcfg.donate else ()
        return jax.jit(step, donate_argnums=donate)

    # -- shoal backend --------------------------------------------------------------

    def _make_train_step_shoal(self):
        """Manual-DP: per-device grads on the local batch shard, then an
        explicit Shoal ring all-reduce (optionally int8-EF-compressed)."""
        mesh = self.mesh
        assert mesh is not None, "shoal backend needs a mesh"
        assert not self.model.cfg.fsdp, (
            "shoal DP backend needs replicated-over-DP params (no FSDP): "
            "its ring all-reduces whole gradient leaves")
        dp = self.dp_axes
        n_dp = 1
        for a in dp:
            n_dp *= mesh.shape[a]

        def grads_fn(params, batch):
            loss, grads = jax.value_and_grad(self._loss_microbatched)(
                params, batch)
            return loss, grads

        def sync(avg_or_tree):
            """ring all-reduce each grad leaf over the flattened DP axes."""
            def one(g):
                red = coll.ring_all_reduce(g.astype(jnp.float32), dp, n_dp)
                return (red / n_dp).astype(g.dtype)
            return jax.tree.map(one, avg_or_tree)

        def sync_compressed(grads, residual):
            qtree, new_res = od.ef_compress_tree(grads, residual)

            def one(qs):
                q, s = qs
                # int8 payloads are summed in int32, so the ring ships as
                # many bytes as f32; scales are reduced alongside
                red = coll.ring_all_reduce(q.astype(jnp.int32), dp, n_dp)
                smax = coll.ring_all_reduce(s[None], dp, n_dp)[0] / n_dp
                return (red.astype(jnp.float32) * smax / n_dp)

            synced = jax.tree.map(one, qtree,
                                  is_leaf=lambda x: isinstance(x, tuple))
            return synced, new_res

        def local_step(state: TrainState, batch):
            loss, grads = grads_fn(state.params, batch)
            loss = jax.lax.pmean(loss, dp)
            if self.tcfg.grad_compression:
                synced, new_res = sync_compressed(grads, state.ef_residual)
                state = TrainState(params=state.params,
                                   opt_state=state.opt_state,
                                   step=state.step, ef_residual=new_res)
            else:
                synced = sync(grads)
            return self._apply_update(state, synced, loss)

        def spmd_step(state, batch):
            # partial-manual: DP axes manual (explicit shoal ring); the
            # model axis stays GSPMD-auto.  P() / P(dp) are prefix specs
            # broadcast over the pytrees.
            batch_specs = {k: P(dp) for k in batch}
            fn = shard_map(
                local_step, mesh=mesh,
                in_specs=(P(), batch_specs),
                out_specs=(P(), P()),
                axis_names=set(dp), check_vma=False)
            return fn(state, batch)

        donate = (0,) if self.tcfg.donate else ()
        return jax.jit(spmd_step, donate_argnums=donate)
