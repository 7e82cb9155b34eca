"""Disaggregated serving tier: prefill and decode on disjoint mesh slices.

The single-host :class:`~repro.serving.engine.ServeEngine` interleaves
prefill and decode on one program; at production scale they fight — a
long prompt stalls every decoding lane.  The disaggregated tier splits
the kernel axis into a prefill slice and a decode slice
(:class:`repro.launch.mesh.ServingSlices`) and moves a finished
prefill's KV to a free decode lane as ONE one-sided
``put_long_vectored`` into the decode kernel's PGAS segment
(:class:`~repro.serving.kv_space.KvSegmentSpace` fixes the per-lane /
per-layer layout at trace time), instead of a gather/scatter collective.

Emulation note: kernels here are devices of one host mesh (the same
emulation the comm benchmarks use), so "a prefill worker" is a
host-driven jitted program and the migration is the compiled SPMD
program over the kernel mesh.  The wire cost is still the *measured*
HLO of that program — ≤ 2 collective-permutes per migration (1 fused
vectored packet + 1 coalesced reply), asserted by
``tests/serving_checks.py`` and the ``--serving`` benchmark mode.

Bit-identity contract: a migrated request decodes to exactly the tokens
the single-host engine produces, because (a) prefill workers run the
same ``reset_lane`` + per-lane prefill path as the engine and (b) the
segment round trip is value-exact (see :mod:`repro.serving.kv_space`).
"""

from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.address_space import GlobalAddressSpace
from repro.core.state import ShoalContext
from repro.launch.mesh import ServingSlices, make_serving_mesh
from jax import shard_map
from repro.runtime.transport import TCP
from repro.serving.engine import Request, ServeEngine, lane_slice, reset_lane
from repro.serving.kv_space import MIGRATE_TOKEN, KvSegmentSpace


class PrefillWorker:
    """One prefill kernel: ragged-prompt prefill into a single-lane cache.

    Deliberately reuses the engine's lane helpers (``reset_lane`` +
    ``lane_slice`` + ``model.prefill``) so its compiled program computes
    the same values the single-host engine's ``_prefill_lane`` does —
    the precondition for bit-identical migrated decode.
    """

    def __init__(self, model, params, slots: int, kernel_id: int):
        self.model = model
        self.params = params
        self.kernel_id = kernel_id
        self._cache0 = model.make_cache(1, slots)
        self.prefills = 0

        def _pf(params, cache, toks):
            lc = lane_slice(cache, 0)
            logits, lc = model.prefill(params, {"tokens": toks}, lc)
            return logits, lc

        self._prefill = jax.jit(_pf)

    def prefill(self, prompt: np.ndarray):
        """Returns ``(last_logits (vocab,), lane_cache)`` for one prompt."""
        cache = reset_lane(self._cache0, 0)
        toks = jnp.asarray(prompt, jnp.int32)[None]
        logits, lane_cache = self._prefill(self.params, cache, toks)
        self.prefills += 1
        return logits[0], lane_cache


class DisaggServeTier:
    """Prefill slice + PGAS KV migration + decode slice.

    Duck-types the :class:`ServeEngine` scheduler surface (``submit`` /
    ``step`` / ``drain`` / ``idle`` / ``run``) so the admission
    front-end (:mod:`repro.serving.frontend`) drives either tier.
    """

    def __init__(self, model, params, slices: ServingSlices, *,
                 lanes_per_decode: int, slots: int, transport=TCP,
                 segment_words: int | None = None, mesh=None,
                 greedy: bool = True, seed: int = 0, event_sink=None):
        self.model = model
        self.params = params
        self.slices = slices
        self.mesh = mesh if mesh is not None else make_serving_mesh(slices)
        probe_words = _lane_words(model, slots)
        if segment_words is None:
            segment_words = lanes_per_decode * probe_words
        self.ctx = ShoalContext(mesh=self.mesh, axes=(slices.axis,),
                                transport=transport,
                                segment_words=segment_words)
        self.gas = GlobalAddressSpace(self.ctx)
        self.kv = KvSegmentSpace(self.gas, model, lanes=lanes_per_decode,
                                 slots=slots)
        self.state = self.gas.make_global_state()
        self.workers = {pid: PrefillWorker(model, params, slots, pid)
                        for pid in slices.prefill_ids}
        self._next_prefill = itertools.cycle(slices.prefill_ids)
        self.engines = {
            did: ServeEngine(model, params, lanes=lanes_per_decode,
                             slots=slots, greedy=greedy, seed=seed + did,
                             event_sink=event_sink)
            for did in slices.decode_ids}
        self._migrations: dict[tuple[int, int, int], object] = {}
        self.migrations = 0

    # -- migration program cache ------------------------------------------------

    def _migration(self, src: int, dst: int, lane: int):
        """Compiled SPMD migration program for one (src, dst, lane)."""
        key = (src, dst, lane)
        fn = self._migrations.get(key)
        if fn is None:
            pattern = self.slices.migration_pattern(src, dst)
            ctx, kv = self.ctx, self.kv
            spec = P(ctx.axes)

            def inner(state, blocks):
                state = jax.tree.map(lambda x: x[0], state)
                state = kv.migrate(state, blocks, pattern, lane,
                                   token=MIGRATE_TOKEN)
                return jax.tree.map(lambda x: x[None], state)

            fn = jax.jit(shard_map(inner, mesh=ctx.mesh,
                                   in_specs=(spec, P()), out_specs=spec))
            self._migrations[key] = fn
        return fn

    def migration_hlo(self, src: int, dst: int, lane: int = 0) -> str:
        """Optimized HLO of one migration (for collective-budget gates)."""
        blocks = tuple(self.kv.pack_lane(
            lane_slice(self.workers[src]._cache0, 0)))
        fn = self._migration(src, dst, lane)
        return fn.lower(self.state, blocks).compile().as_text()

    # -- scheduling (ServeEngine duck type) --------------------------------------

    @property
    def active(self):
        return [r for eng in self.engines.values() for r in eng.active]

    @property
    def idle(self) -> bool:
        return all(eng.idle for eng in self.engines.values())

    def find_free_lane(self):
        for did, eng in self.engines.items():
            lane = eng.find_free_lane()
            if lane is not None:
                return did, lane
        return None

    def submit(self, req: Request) -> bool:
        """Prefill on the prefill slice, migrate KV, adopt on decode.

        False when every decode lane is busy (the front-end's
        backpressure signal)."""
        slot = self.find_free_lane()
        if slot is None:
            return False
        did, lane = slot
        src = next(self._next_prefill)
        logits, lane_cache = self.workers[src].prefill(req.prompt)
        eng = self.engines[did]
        tok = eng._sample(np.asarray(logits))
        # ONE one-sided vectored put: lane KV -> decode kernel's segment
        blocks = tuple(self.kv.pack_lane(lane_cache))
        self.state = self._migration(src, did, lane)(self.state, blocks)
        self.migrations += 1
        # decode-side view refresh: the lane cache now lives in the PGAS
        # segment; the engine adopts it from there
        seg_row = np.asarray(jax.device_get(self.state.segment))[did]
        req.out.append(int(tok))
        eng.adopt_lane(lane, self.kv.unpack_lane(seg_row, lane), req,
                       pos=len(req.prompt), last_tok=int(tok))
        return True

    def step(self):
        for eng in self.engines.values():
            eng.step()

    def drain(self):
        out = []
        for eng in self.engines.values():
            out.extend(eng.drain())
        return out

    def run(self, requests: list[Request]) -> list[Request]:
        """FCFS to completion — same scheduler loop as the single-host
        engine, so token outputs are comparable request-for-request."""
        pending = list(requests)
        done: list[Request] = []
        while pending or not self.idle:
            while pending and self.submit(pending[0]):
                pending.pop(0)
            self.step()
            for r in requests:
                if r.done and r not in done:
                    done.append(r)
        self.drain()
        return done


def _lane_words(model, slots: int) -> int:
    """Words one lane's cache occupies in a segment (layout probe)."""
    proto = model.make_cache(1, slots)
    total = 0
    for leaf in jax.tree_util.tree_leaves(proto):
        if leaf.ndim < 2:
            raise ValueError("cache leaf with no lane dim")
        per_layer = 1
        for d in leaf.shape[2:]:
            per_layer *= d
        total += leaf.shape[0] * per_layer
    return total
