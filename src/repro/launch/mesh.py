"""Production mesh definitions (TPU v5e pods).

A function, not a module-level constant: importing this module must
never touch JAX device state (the dry-run sets the host-device-count
flag before first JAX init).
"""

from __future__ import annotations

import dataclasses

from repro.runtime.topology import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods over DCN when ``multi_pod``.

    Axes: ``data`` = batch parallelism (+FSDP weight sharding for the
    large configs), ``model`` = tensor/expert parallelism, ``pod`` = the
    DCN axis (stacked onto data parallelism by the trainer).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def dp_axes_of(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


# --------------------------------------------------------------------------
# disaggregated-serving slices
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ServingSlices:
    """Prefill/decode split of one kernel axis for disaggregated serving.

    The first ``n_prefill`` kernel IDs form the prefill slice, the next
    ``n_decode`` the decode slice; both live on ONE mesh so a finished
    prefill's KV migrates decode-ward as a single one-sided vectored put
    along ``migration_pattern`` (no gather/scatter collective, no
    cross-mesh transfer).
    """

    n_prefill: int
    n_decode: int
    axis: str = "kernel"

    def __post_init__(self):
        if self.n_prefill < 1 or self.n_decode < 1:
            raise ValueError(
                f"serving slices need >= 1 kernel each, got "
                f"prefill={self.n_prefill} decode={self.n_decode}")

    @property
    def num_kernels(self) -> int:
        return self.n_prefill + self.n_decode

    @property
    def prefill_ids(self) -> tuple[int, ...]:
        return tuple(range(self.n_prefill))

    @property
    def decode_ids(self) -> tuple[int, ...]:
        return tuple(range(self.n_prefill, self.n_prefill + self.n_decode))

    def role_of(self, kernel: int) -> str:
        if kernel in self.prefill_ids:
            return "prefill"
        if kernel in self.decode_ids:
            return "decode"
        raise ValueError(f"kernel {kernel} outside the serving mesh "
                         f"({self.num_kernels} kernels)")

    def migration_pattern(self, prefill: int, decode: int):
        """The static ``[(src, dst)]`` a finished prefill's KV rides."""
        if prefill not in self.prefill_ids:
            raise ValueError(f"kernel {prefill} is not in the prefill "
                             f"slice {self.prefill_ids}")
        if decode not in self.decode_ids:
            raise ValueError(f"kernel {decode} is not in the decode "
                             f"slice {self.decode_ids}")
        return [(prefill, decode)]


def make_serving_mesh(slices: ServingSlices):
    """One 1-D kernel mesh spanning both slices (prefill IDs first)."""
    return make_mesh((slices.num_kernels,), (slices.axis,))
