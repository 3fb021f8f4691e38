"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s per chip.

A device that is not in the table is an error, never a default: a roofline
share computed against another chip's peaks would be a wrong number.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


class UnknownDevice(KeyError):
    pass


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device_kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None


def roofline_s(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """Least time the chip could take: the larger of the compute and the
    memory bound, and which of the two it is."""
    compute = flops / peaks["bf16_flops_per_s"]
    memory = nbytes / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
