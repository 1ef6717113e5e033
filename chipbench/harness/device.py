"""The accelerator the run measures, and its published peaks.

Each peak names its source. A device kind that is not in the table is an
error: a roofline or utilization over an unknown peak means nothing.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture page):
    # 197 TFLOP/s bf16 and 394 TOP/s int8 per chip, 16 GB HBM2 at
    # 819 GB/s, 1,600 Gbit/s inter-chip interconnect.
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def peaks_for(kind: str) -> dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise NoAccelerator(f"no published peaks for device kind {kind!r}; "
                            f"known: {sorted(PEAKS)}") from None


def require(chips: int):
    """The first ``chips`` TPU devices, or NoAccelerator. Never falls back
    to the CPU."""
    import jax
    try:
        devs = jax.devices("tpu")
    except RuntimeError as e:
        raise NoAccelerator(f"JAX finds no TPU: {e}") from None
    if len(devs) < chips:
        raise NoAccelerator(f"cell needs {chips} chips, JAX finds "
                            f"{len(devs)}")
    devs = devs[:chips]
    peaks_for(devs[0].device_kind)
    return devs


def describe(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest chip since the process started."""
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)
