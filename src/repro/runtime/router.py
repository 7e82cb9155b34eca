"""Routing: kernel IDs <-> mesh coordinates and link classification.

libGalapagos routes packets between local kernels in software and hands
off-node traffic to the network driver.  The XLA analogue: traffic whose
source and destination kernels live on the same chip never becomes a
collective (LOCAL: a move inside the chip); intra-pod traffic lowers to
collective-permute on ICI; inter-pod traffic crosses the DCN ("pod")
axis.
"""

from __future__ import annotations

import dataclasses

from repro.runtime.topology import ClusterSpec, device_of, kernel_coords, pod_of
from repro.runtime.transport import LinkClass


@dataclasses.dataclass(frozen=True)
class Router:
    spec: ClusterSpec

    def classify(self, src: int, dst: int) -> LinkClass:
        """Which link class a src->dst AM traverses."""
        kpd = self.spec.kernels_per_device
        if device_of(src, kpd) == device_of(dst, kpd):
            return LinkClass.LOCAL
        if pod_of(self.spec, src) != pod_of(self.spec, dst):
            return LinkClass.DCN
        return LinkClass.ICI

    def classify_pattern(self, pattern: list[tuple[int, int]]) -> LinkClass:
        """Worst link class over a pattern (the paper reports per-topology
        numbers; a mixed pattern is bounded by its slowest hop)."""
        worst = LinkClass.LOCAL
        for s, d in pattern:
            c = self.classify(s, d)
            if c.value > worst.value:
                worst = c
        return worst

    def coords(self, kernel_id: int) -> dict[str, int]:
        return kernel_coords(self.spec, kernel_id)

    def is_pure_local(self, pattern: list[tuple[int, int]]) -> bool:
        return all(self.classify(s, d) == LinkClass.LOCAL
                   for s, d in pattern)
