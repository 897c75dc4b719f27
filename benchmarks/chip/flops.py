"""Operations and bytes the algorithms need, counted from their shapes.

Useful work only: the K(K-1)/2 unique client pairs of a proximity matrix,
whatever the kernel computes to produce them (both triangles, padding,
several MXU passes per float32 product).
"""
from __future__ import annotations


def proximity_flops(K: int, n: int, p: int, measure: str) -> float:
    """Multiply-adds (2 flops each) of the Gram entries the measure needs:
    the p diagonal entries per pair for eq3, all p^2 for eq2."""
    pairs = K * (K - 1) / 2
    entries = p if measure == "eq3" else p * p
    return 2.0 * n * entries * pairs


def proximity_bytes(K: int, n: int, p: int) -> float:
    """float32 signatures read once, float32 upper triangle written once."""
    return 4.0 * K * n * p + 4.0 * K * (K - 1) / 2


def roofline_s(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    t_compute = flops / peak["bf16_flops_per_s"]
    t_memory = nbytes / peak["hbm_bytes_per_s"]
    if t_compute >= t_memory:
        return t_compute, "compute"
    return t_memory, "memory"

