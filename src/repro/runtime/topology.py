"""Cluster topology: the Galapagos cluster-description analogue.

Galapagos turns user configuration files into a deployed cluster of
CPU/FPGA nodes, each holding one or more kernels.  Here a "cluster" is a
JAX device mesh: pods (DCN-connected) x chips (ICI-connected), and a
"kernel" is one program instance under ``shard_map``.  A device holds
``kernels_per_device`` kernels (one by default), in slots; the kernel ID
of the paper is ``device * kernels_per_device + slot``, with ``device``
the flattened mesh index.  Two kernels on one device share no
collective: packets between them take the LOCAL path, a move inside the
device (libGalapagos' software routing between kernels on one node).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
from jax.sharding import AxisType


@dataclasses.dataclass(frozen=True)
class ClusterSpec:
    """Static description of a cluster (the Galapagos config-file analogue).

    Attributes:
      mesh_shape: devices per named axis, e.g. ``(2, 16, 16)``.
      axis_names: names per axis, e.g. ``("pod", "data", "model")``.
      kernel_axes: the axes over which Shoal kernels are enumerated.  By
        default all axes: every device in the mesh is one kernel.
      pod_axis: name of the inter-pod (DCN) axis, or None for single-pod.
      kernels_per_device: Shoal kernels on each device of the kernel
        axes (the paper's kernels on one node).
    """

    mesh_shape: tuple[int, ...]
    axis_names: tuple[str, ...]
    kernel_axes: tuple[str, ...] | None = None
    pod_axis: str | None = None
    kernels_per_device: int = 1

    def __post_init__(self):
        if len(self.mesh_shape) != len(self.axis_names):
            raise ValueError("mesh_shape and axis_names must have equal length")
        if self.kernel_axes is None:
            object.__setattr__(self, "kernel_axes", tuple(self.axis_names))
        for ax in self.kernel_axes:
            if ax not in self.axis_names:
                raise ValueError(f"kernel axis {ax!r} not in {self.axis_names}")
        if self.pod_axis is not None and self.pod_axis not in self.axis_names:
            raise ValueError(f"pod axis {self.pod_axis!r} not in {self.axis_names}")
        if self.kernels_per_device < 1:
            raise ValueError("kernels_per_device must be at least 1")

    @property
    def num_devices(self) -> int:
        return math.prod(self.mesh_shape)

    @property
    def num_kernels(self) -> int:
        n = self.kernels_per_device
        for ax, size in zip(self.axis_names, self.mesh_shape):
            if ax in self.kernel_axes:
                n *= size
        return n

    def axis_size(self, name: str) -> int:
        return self.mesh_shape[self.axis_names.index(name)]

    def make(self) -> jax.sharding.Mesh:
        return make_mesh(self.mesh_shape, self.axis_names)


def make_mesh(shape: Sequence[int], names: Sequence[str]) -> jax.sharding.Mesh:
    """Build a mesh with explicit Auto axis types (silences 0.9 deprecation)."""
    return jax.make_mesh(tuple(shape), tuple(names),
                         axis_types=(AxisType.Auto,) * len(shape))


def make_cpu_mesh(n: int | None = None, names: tuple[str, ...] = ("kernel",)):
    """1-D mesh over however many (host) devices exist; used by the
    microbenchmarks and semantic tests that emulate a multi-node cluster
    with ``--xla_force_host_platform_device_count``."""
    avail = len(jax.devices())
    n = avail if n is None else n
    if n > avail:
        raise ValueError(f"requested {n} devices, only {avail} available")
    return make_mesh((n,), names)


def device_of(kernel_id: int, kernels_per_device: int = 1) -> int:
    """The flattened index of the device that holds kernel ``kernel_id``."""
    return kernel_id // kernels_per_device


def split_local(pattern: Sequence[tuple[int, int]],
                kernels_per_device: int = 1):
    """``(local, remote)``: the pairs of ``pattern`` whose two kernels
    share a device (the LOCAL path, no collective), and the others."""
    local, remote = [], []
    for s, d in pattern:
        same = (device_of(s, kernels_per_device)
                == device_of(d, kernels_per_device))
        (local if same else remote).append((s, d))
    return local, remote


def kernel_coords(spec: ClusterSpec, kernel_id: int) -> dict[str, int]:
    """kernel ID -> per-axis coordinates of its device (row-major over
    kernel_axes)."""
    sizes = [spec.axis_size(a) for a in spec.kernel_axes]
    coords: dict[str, int] = {}
    rem = device_of(kernel_id, spec.kernels_per_device)
    for ax, size in zip(reversed(spec.kernel_axes), reversed(sizes)):
        coords[ax] = rem % size
        rem //= size
    if rem:
        raise ValueError(f"kernel id {kernel_id} out of range")
    return coords


def pod_of(spec: ClusterSpec, kernel_id: int) -> int:
    """Which pod a kernel lives on (0 if single-pod)."""
    if spec.pod_axis is None or spec.pod_axis not in spec.kernel_axes:
        return 0
    return kernel_coords(spec, kernel_id)[spec.pod_axis]


def neighbors_ring(n: int, shift: int = 1) -> list[tuple[int, int]]:
    """Ring permutation pattern (the workhorse of ring collectives)."""
    return [(i, (i + shift) % n) for i in range(n)]


def pairwise(pairs: Sequence[tuple[int, int]]) -> list[tuple[int, int]]:
    """Validate an explicit src->dst pattern (each src/dst at most once,
    mirroring one outstanding AM per kernel per call)."""
    srcs = [s for s, _ in pairs]
    dsts = [d for _, d in pairs]
    if len(set(srcs)) != len(srcs) or len(set(dsts)) != len(dsts):
        raise ValueError("pattern must have unique sources and destinations")
    return list(pairs)
