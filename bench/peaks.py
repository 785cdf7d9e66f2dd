"""Peak rates of the chips the benchmark runs on, keyed by JAX's
``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI).  A kind
that is not in the table is an error, never a default.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes": 16e9, "hbm_bw": 819e9, "ici_bw": 200e9},
}


def peak(kind: str, key: str) -> float:
    """One peak of ``kind``; KeyError names the known kinds."""
    if kind not in PEAKS:
        raise KeyError(f"no peaks recorded for device kind {kind!r}; "
                       f"known kinds: {sorted(PEAKS)}")
    return PEAKS[kind][key]
