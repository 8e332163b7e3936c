"""Published per-chip peaks, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16,
393 TOP/s in int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
interconnect.  A device kind that is not in the table is an error, never
a default: a roofline against a guessed peak is no measurement.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "int8_ops": 393e12, "hbm_bw": 819e9,
                    "hbm_bytes": 16e9, "ici_bw": 1600e9 / 8},
}


def device_peaks(device_kind: str) -> dict[str, float]:
    """Peaks of one chip of ``device_kind``; raises for an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; have {sorted(PEAKS)}") from None


def least_time(flops: float, bytes_moved: float, device_kind: str
               ) -> tuple[float, str]:
    """The least time the chip could take for this work, and which of the
    two bounds (compute or memory) sets it."""
    peak = device_peaks(device_kind)
    t_c, t_m = flops / peak["flops"], bytes_moved / peak["hbm_bw"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
