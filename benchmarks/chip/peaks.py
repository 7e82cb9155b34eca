"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` that JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s
of inter-chip interconnect per chip.

A device that is not in the table is an error, never a default: a
roofline share against the wrong peak is a wrong number.
"""

from __future__ import annotations

SOURCE = "Google Cloud documentation, TPU v5e system architecture"

PEAKS = {
    # a TPU v5e reports itself as "TPU v5 lite"
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes": 16e9,
        "hbm_Bps": 819e9,
        "ici_bps": 1600e9,
    },
}


class UnknownDevice(LookupError):
    """The device kind has no entry in :data:`PEAKS`."""


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None
