"""The partitioned global address space (paper Sec. II-A3).

A ``GlobalAddressSpace`` names a global word array of
``num_kernels * segment_words`` words; kernel *k* owns words
``[k*segment_words, (k+1)*segment_words)``.  Locality is explicit: a
global address resolves to (owner kernel, local offset), and only
accesses to non-owned partitions become AMs — "this locality information
is known to the programmer" (Sec. II-A3).

Host-side helpers move data between a NumPy/global view and the
per-device segments (sharded ``jax.Array``), which is how applications
(e.g. Jacobi) load initial conditions and read results back.  The
global view is laid out by kernel, ``(num_kernels, ...)``, and split
over the devices: a device holds the ``kernels_per_device`` consecutive
kernels it hosts, so kernel ``k`` owns the same segment wherever it
lands (:meth:`GlobalAddressSpace.placement`).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.state import PgasState, ShoalContext


@dataclasses.dataclass(frozen=True)
class GlobalAddressSpace:
    ctx: ShoalContext
    dtype: jnp.dtype = jnp.float32

    @property
    def segment_words(self) -> int:
        return self.ctx.segment_words

    @property
    def total_words(self) -> int:
        return self.ctx.num_kernels * self.ctx.segment_words

    # -- addressing -------------------------------------------------------

    def owner_of(self, gaddr: int) -> int:
        return gaddr // self.segment_words

    def placement(self, gaddr: int) -> tuple[int, int]:
        """``(device, slot)`` of the kernel that owns ``gaddr``."""
        return divmod(self.owner_of(gaddr), self.ctx.kernels_per_device)

    def local_offset(self, gaddr: int) -> int:
        return gaddr % self.segment_words

    def global_addr(self, kernel: int, offset: int) -> int:
        if not 0 <= kernel < self.ctx.num_kernels:
            raise ValueError(
                f"global_addr: kernel {kernel} out of range "
                f"(num_kernels={self.ctx.num_kernels})")
        if not 0 <= offset < self.segment_words:
            # an out-of-range offset would silently alias into another
            # kernel's partition of the flat global word array
            would_own = (kernel * self.segment_words + offset) // self.segment_words
            raise ValueError(
                f"global_addr: offset {offset} outside the "
                f"{self.segment_words}-word segment owned by kernel "
                f"{kernel}; the aliased address would land in kernel "
                f"{would_own}'s partition at local offset "
                f"{offset % self.segment_words}")
        return kernel * self.segment_words + offset

    def check_local_range(self, kernel: int, offset: int, nwords: int) -> int:
        """Validate that ``[offset, offset + nwords)`` stays inside
        ``kernel``'s segment; returns ``offset``.  Used by callers that
        hand *local* destination addresses to the AM ops (where aliasing
        past the segment end is clipped by the GAScore, not wrapped)."""
        self.global_addr(kernel, offset)
        if nwords < 0 or offset + nwords > self.segment_words:
            raise ValueError(
                f"range [{offset}, {offset + nwords}) overruns kernel "
                f"{kernel}'s {self.segment_words}-word segment")
        return offset

    def vectored_addrs(self, kernel: int, base: int, block_words,
                       *, stride: int | None = None) -> list[int]:
        """Per-block local addresses for a vectored put into ``kernel``.

        ``block_words`` is the static per-block word count list; blocks
        land back-to-back from ``base`` unless ``stride`` pins a fixed
        distance between block starts (the per-layer stride of a KV
        segment layout).  Every block is validated against the segment
        bounds, so a bad layout fails at trace time with the owner in
        the message instead of silently clipping at ingress.
        """
        addrs, off = [], base
        for i, w in enumerate(block_words):
            a = base + i * stride if stride is not None else off
            self.check_local_range(kernel, a, int(w))
            addrs.append(a)
            off = a + int(w)
        return addrs

    # -- host <-> device views ---------------------------------------------

    def _sharding(self):
        return NamedSharding(self.ctx.mesh, P(self.ctx.axes))

    def make_global_state(self, init: np.ndarray | None = None):
        """Build the sharded PgasState for all kernels.

        Returns a PgasState whose leaves are global arrays with leading
        dim = num_kernels, split over the devices (``kernels_per_device``
        kernels each); inside ``ctx.spmd`` each kernel sees its own
        (segment_words,) slice.
        """
        n = self.ctx.num_kernels
        proto = PgasState.make(self.segment_words, self.dtype)

        def globalize(leaf):
            arr = np.broadcast_to(np.asarray(leaf)[None], (n,) + leaf.shape).copy()
            return arr

        leaves = jax.tree.map(globalize, proto)
        if init is not None:
            if init.size != self.total_words:
                raise ValueError(
                    f"init has {init.size} words, address space has {self.total_words}")
            import dataclasses as _dc
            leaves = _dc.replace(
                leaves,
                segment=init.reshape(n, self.segment_words).astype(self.dtype))
        shd = self._sharding()

        def put(leaf):
            spec = P(self.ctx.axes) if leaf.ndim >= 1 else P(self.ctx.axes)
            # every leaf gained a leading kernel dim
            return jax.device_put(leaf, NamedSharding(self.ctx.mesh, P(self.ctx.axes)))

        return jax.tree.map(put, leaves)

    def read_global(self, state: PgasState) -> np.ndarray:
        """Gather the whole address space back to the host (all segments,
        kernel order)."""
        return np.asarray(jax.device_get(state.segment)).reshape(-1)

    def spmd(self, fn, **kw):
        """Per-kernel wrapper: ``fn(state) -> state`` written per-kernel;
        the global view gives every PgasState leaf a leading kernel dim
        split over the devices, removed inside."""
        spec = P(self.ctx.axes)

        def inner(state):
            state = jax.tree.map(lambda x: x[0], state)  # drop kernel dim
            out = fn(state)
            return jax.tree.map(lambda x: x[None], out)

        return self.ctx.kernel_map(inner, spec, spec, **kw)
