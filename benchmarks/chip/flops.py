"""Operations and bytes the algorithms need, counted from their shapes.

Useful work only: the K(K-1)/2 unique client pairs of a proximity matrix,
whatever the kernel computes to produce them (both triangles, padding,
several MXU passes per float32 product); LeNet-5's multiply-adds per sample,
whatever the compiled convolutions compute.
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



def lenet5_forward_macs(hw: int, channels: int, n_classes: int) -> int:
    """Multiply-adds of one LeNet-5 forward pass on an (hw, hw, channels)
    image: two 5x5 VALID convolutions (6 and 16 channels, each followed by
    2x2 pooling) and fully connected layers of 120, 84 and n_classes."""
    h1 = hw - 4                       # conv1 output edge
    h2 = h1 // 2 - 4                  # conv2 output edge, after pool1
    flat = (h2 // 2) ** 2 * 16        # after pool2
    conv = h1 * h1 * 6 * 25 * channels + h2 * h2 * 16 * 25 * 6
    return conv + flat * 120 + 120 * 84 + 84 * n_classes


def lenet5_train_flops(hw: int, channels: int, n_classes: int) -> float:
    """Flops of one sample's forward and backward pass: 3 x the forward's
    2 flops per multiply-add."""
    return 3.0 * 2.0 * lenet5_forward_macs(hw, channels, n_classes)
