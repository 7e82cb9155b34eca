from repro.kernels.jacobi.ops import jacobi_band_step, jacobi_step, jacobi_run
from repro.kernels.jacobi.ref import jacobi_step_ref

__all__ = ["jacobi_band_step", "jacobi_step", "jacobi_run", "jacobi_step_ref"]
