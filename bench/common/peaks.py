"""Published peaks of each chip the benchmark may run on, keyed by the
``device_kind`` JAX reports.  A kind that is not here is an error."""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture page)
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud, TPU v5e: 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s",
    },
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def least_time_s(flops: float, nbytes: float, row: dict) -> tuple[float, str]:
    """The least time the chip could take for ``flops`` operations moving
    ``nbytes`` bytes, and which of the two bounds it (``compute`` or
    ``memory``)."""
    tc = flops / row["bf16_flops_per_s"]
    tm = nbytes / row["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
