"""Published peaks of the devices this benchmark may run on, keyed by
`device_kind` as JAX reports it. A device that is not here is an error,
never a default."""

from __future__ import annotations

#: Google Cloud documentation, "TPU v5e" (system architecture page):
#: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2**30,
        "source": "cloud.google.com/tpu/docs/v5e: 197 TFLOP/s bf16, "
                  "819 GB/s HBM, 16 GB per chip",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add it to "
            "benchmarks/lib/peaks.py with its source") from None


def roofline_seconds(flops: float, nbytes: float, device_kind: str
                     ) -> tuple[float, str]:
    """The least time the chip could take for `flops` operations over
    `nbytes` bytes, and which of the two bounds it."""
    p = peaks_for(device_kind)
    t_flops = flops / p["flops_per_s"]
    t_bytes = nbytes / p["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
