"""The device a run is on, its published peaks, and its memory peak."""
from __future__ import annotations

import jax

# Published per-chip peaks, keyed by ``device_kind``. Source: Google Cloud
# documentation, "TPU v5e" (197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at
# 819 GB/s). A kind that is not here is an error, never a default.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def peaks(kind: str) -> dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r}") from None


def require_chips(chips: int, allow_cpu: bool = False) -> list:
    """The first ``chips`` devices; raises ``NoChip`` unless they are TPUs.

    ``allow_cpu`` is for the CPU rehearsal in the tests only."""
    devs = jax.devices()
    if devs[0].platform != "tpu" and not allow_cpu:
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def describe(devs) -> dict:
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def memory_peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest of ``devs`` (0 where unreported)."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
