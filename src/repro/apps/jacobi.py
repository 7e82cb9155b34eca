"""The Jacobi stencil application on Shoal (paper Sec. IV-C).

The grid (N x N) is row-partitioned over kernels.  Each iteration:

  1. every kernel one-sided-puts its first/last owned row into its
     neighbors' halo slots (Shoal Long puts — *not* send/recv pairs;
     boundary kernels simply aren't in the pattern),
  2. waits for its own halos' replies (wait_replies = GASNet quiet),
  3. runs the von Neumann stencil over its band: jnp, or with
     ``use_pallas`` the Pallas kernel from :mod:`repro.kernels.jacobi`
     (compiled; ``interpret=True`` runs its body on the CPU).

Segment layout per kernel: [0, N) = top halo row, [N, 2N) = bottom halo.

Placement: one kernel per chip by default; ``chips`` puts
``kernels / chips`` consecutive kernels on each chip, as the paper's
Figs. 7-8 put several kernels on one node.  Halo puts between kernels
on one chip take the LOCAL path (no collective), the others cross ICI,
and the compiled stencil runs once per chip over the stack of its
kernels' bands.

The paper's footnote-2 limitation — at grid 4096 a halo row exceeds the
9000-byte jumbo frame and their runs *fail* — is handled here by the
transparent >MTU segmentation in :func:`repro.core.ops.put_long_multi`;
the benchmark runs exactly that configuration.

Steady-state wire plan (``piggyback=True``, the default on an acked
transport): both halo puts go through one ``put_long_multi`` call with
``defer_ack`` — the up/down patterns share every interior kernel as a
source, so they cannot merge into one permutation, but neither put
ships a reply collective.  Instead each direction's data packet carries
the *opposite* direction's acks home in its piggyback lane (token 1 =
up puts, token 2 = down puts; the up packet travels the reverse of the
down link, so it piggybacks token 2's acks and vice versa).  That makes
the loop body exactly 2 collective-permutes per iteration — down from 4
— with iteration *k*'s acks arriving on iteration *k+1*'s packets, so
the waits are gated past the first iteration and a pair of
``drain_deferred_acks`` after the loop balances the books.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.trace import layer
from repro.core import handlers as hd
from repro.core import ops
from repro.core.gascore import dataclasses_replace
from repro.core.state import PgasState, ShoalContext
from repro.kernels.jacobi import jacobi_band_step
from repro.runtime import TCP
from repro.runtime.topology import make_cpu_mesh


@dataclasses.dataclass
class JacobiApp:
    n: int                    # grid is n x n
    kernels: int
    iters: int
    transport: object = TCP
    use_pallas: bool = False
    interpret: bool = False   # Pallas interpret mode (CPU runs)
    piggyback: bool = True    # defer halo acks onto the next iteration's
                              # reverse-link data packet (acked transports)
    chips: int | None = None  # devices the kernels share; None: one each

    def __post_init__(self):
        assert self.n % self.kernels == 0
        if self.chips is None:
            self.chips = self.kernels
        if self.kernels % self.chips:
            raise ValueError(f"{self.kernels} kernels do not split evenly "
                             f"over {self.chips} chips")
        self.rows = self.n // self.kernels
        self.mesh = make_cpu_mesh(self.chips, ("kernel",))
        self.ctx = ShoalContext(mesh=self.mesh, axes=("kernel",),
                                transport=self.transport,
                                segment_words=2 * self.n,
                                kernels_per_device=self.kernels // self.chips)
        k = self.kernels
        self.up = [(i, i - 1) for i in range(1, k)]      # send top row up
        self.down = [(i, i + 1) for i in range(k - 1)]   # send bottom row down

    # -- one iteration (runs inside shard_map) --------------------------------

    @property
    def _use_piggyback(self) -> bool:
        return self.piggyback and self.transport.acked and self.kernels > 1

    def _halo_exchange(self, st: PgasState, block: jnp.ndarray,
                       it=None) -> PgasState:
        n = self.n
        if self.kernels == 1:
            return st
        me = self.ctx.my_id()
        has_down = (me < self.kernels - 1).astype(jnp.int32)
        has_up = (me > 0).astype(jnp.int32)
        # my top row -> upper neighbor's *bottom* halo [n, 2n);
        # my bottom row -> lower neighbor's *top* halo [0, n)
        items = [(block[0], self.up, n), (block[-1], self.down, 0)]
        if self._use_piggyback:
            # Steady state: no reply collectives at all.  Receivers
            # ledger the acks and each direction's data packet carries
            # the OPPOSITE direction's ledgered acks home (the up packet
            # travels the reverse of the down link, so pb_token=2).
            st = ops.put_long_multi(self.ctx, st, items,
                                    handler=hd.H_WRITE, tokens=[1, 2],
                                    defer_ack=True, piggyback_tokens=[2, 1])
            # iteration k's ack rides iteration k+1's packet: wait only
            # from the second iteration on (drain_deferred_acks after
            # the loop balances the final iteration)
            ready = (jnp.asarray(it) > 0).astype(jnp.int32) \
                if it is not None else jnp.zeros((), jnp.int32)
            st = ops.wait_replies(self.ctx, st, 1, has_up * ready)
            st = ops.wait_replies(self.ctx, st, 2, has_down * ready)
            return st
        st = ops.put_long_multi(self.ctx, st, items, handler=hd.H_WRITE,
                                tokens=[1, 2],
                                asynchronous=not self.transport.acked)
        if self.transport.acked:
            # Replies coalesce across >MTU segmentation (only the final
            # packet of a halo row is acked), so each halo *message*
            # earns exactly one credit regardless of how many packets
            # the transport split it into.
            # replies for token 1 come from puts I sent up, etc.
            st = ops.wait_replies(self.ctx, st, 1, has_up)
            st = ops.wait_replies(self.ctx, st, 2, has_down)
        return st

    def _drain_acks(self, st: PgasState) -> PgasState:
        """Loop exit for the piggyback plan: the last iteration's acks
        are still ledgered at the halo receivers; ship them home (the
        token-1 ledger lives at up-put receivers = the down link's
        senders, and vice versa) and consume the final credit."""
        if not self._use_piggyback:
            return st
        me = self.ctx.my_id()
        st = ops.drain_deferred_acks(self.ctx, st, self.down, token=1)
        st = ops.drain_deferred_acks(self.ctx, st, self.up, token=2)
        st = ops.wait_replies(self.ctx, st, 1,
                              (me > 0).astype(jnp.int32))
        st = ops.wait_replies(self.ctx, st, 2,
                              (me < self.kernels - 1).astype(jnp.int32))
        return st

    def _stencil(self, block: jnp.ndarray, top: jnp.ndarray,
                 bot: jnp.ndarray, kid) -> jnp.ndarray:
        """One stencil pass over this kernel's (rows, n) band, given the
        halo rows above and below it."""
        if self.use_pallas:
            return jacobi_band_step(block, top, bot, kid * self.rows,
                                    m_total=self.n, interpret=self.interpret)
        block_pad = jnp.concatenate([top[None], block, bot[None]], axis=0)
        up = block_pad[:-2]
        down = block_pad[2:]
        mid = block_pad[1:-1]
        left = jnp.pad(mid[:, :-1], ((0, 0), (1, 0)))
        right = jnp.pad(mid[:, 1:], ((0, 0), (0, 1)))
        stencil = 0.25 * (up + down + left + right)
        rows, n = mid.shape
        grow = kid * rows + jax.lax.broadcasted_iota(jnp.int32, (rows, n), 0)
        gcol = jax.lax.broadcasted_iota(jnp.int32, (rows, n), 1)
        interior = ((grow > 0) & (grow < self.n - 1)
                    & (gcol > 0) & (gcol < n - 1))
        return jnp.where(interior, stencil.astype(mid.dtype), mid)

    def _iteration(self, st: PgasState, block: jnp.ndarray, it=None):
        n = self.n
        kid = self.ctx.my_id()
        st = self._halo_exchange(st, block, it)
        with layer("compute"):
            top_halo = st.segment[:n]
            bot_halo = st.segment[n:2 * n]
            # boundary kernels have no halo: use zero rows (masked anyway)
            top = jnp.where(kid > 0, top_halo, 0.0)
            bot = jnp.where(kid < self.kernels - 1, bot_halo, 0.0)
            block = self._stencil(block, top, bot, kid)
        st = ops.barrier(self.ctx, st)
        return st, block

    # -- host-level driver ------------------------------------------------------

    def build(self):
        """Returns a jitted function (grid_blocks) -> grid_blocks running
        all iterations; grid_blocks: (kernels, rows, n) sharded."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        ctx = self.ctx

        def per_kernel(st, block):
            st = jax.tree.map(lambda x: x[0], st)
            block = block[0]

            def body(carry, it):
                st, blk = carry
                st, blk = self._iteration(st, blk, it)
                return (st, blk), ()

            # two iterations a trip: with one stencil call a trip the TPU
            # compiler copies the band between on-chip memory and HBM
            # every iteration; with two, the calls alternate the band
            # between the memories and the loop holds no copy of it
            (st, block), _ = jax.lax.scan(body, (st, block),
                                          jnp.arange(self.iters), unroll=2)
            st = self._drain_acks(st)
            return (jax.tree.map(lambda x: x[None], st), block[None])

        spec = P(("kernel",))
        fn = ctx.kernel_map(per_kernel, in_specs=(spec, spec),
                            out_specs=(spec, spec))
        return jax.jit(fn)

    def links_per_iteration(self) -> dict:
        """Packets and bytes one iteration ships, by link class (``LOCAL``
        between kernels on one chip, ``ICI`` between chips), counted
        from its trace: ``{class: {"packets": p, "bytes": b}}``."""
        from jax.sharding import PartitionSpec as P

        from repro.analysis import trace

        def one(st, block):
            st = jax.tree.map(lambda x: x[0], st)
            st, block = self._iteration(st, block[0], jnp.ones((), jnp.int32))
            return jax.tree.map(lambda x: x[None], st), block[None]

        spec = P(("kernel",))
        fn = self.ctx.kernel_map(one, in_specs=(spec, spec),
                                 out_specs=(spec, spec))
        st = jax.eval_shape(lambda: jax.tree.map(
            lambda x: jnp.zeros((self.kernels,) + x.shape, x.dtype),
            self.ctx.make_state()))
        blocks = jax.ShapeDtypeStruct((self.kernels, self.rows, self.n),
                                      jnp.float32)
        with trace.record() as rec:
            jax.eval_shape(fn, st, blocks)
        return rec.links

    def run(self, grid: np.ndarray):
        """Run on a host grid (n, n); returns the final grid."""
        from repro.core.address_space import GlobalAddressSpace

        gas = GlobalAddressSpace(self.ctx)
        st = gas.make_global_state()
        blocks = jnp.asarray(grid.reshape(self.kernels, self.rows, self.n))
        fn = self.build()
        st, out = fn(st, blocks)
        return np.asarray(out).reshape(self.n, self.n)


def jacobi_reference(grid: np.ndarray, iters: int) -> np.ndarray:
    """Single-kernel oracle."""
    from repro.kernels.jacobi.ref import jacobi_step_ref
    x = jnp.asarray(grid)
    step = jax.jit(jacobi_step_ref)
    for _ in range(iters):
        x = step(x)
    return np.asarray(x)
