"""Operations and bytes from shapes, for the utilization and roofline
metrics. Counted from what the algorithm needs, not from what the program
happens to run: padding rows and recomputation are not counted. A
payload's FLOPs per example come from its configuration's reference
(``flops_per_example``); the CNN's constants are here for its reference.
"""

from __future__ import annotations

from typing import Iterable, Sequence

# MNIST CNN, one 28x28x1 example, forward pass: 2 FLOPs per multiply-add.
CONV1_FLOPS = 2 * 28 * 28 * 16 * (3 * 3 * 1)  # 225,792
CONV2_FLOPS = 2 * 14 * 14 * 32 * (3 * 3 * 16)  # 1,806,336
FC1_FLOPS = 2 * (32 * 7 * 7) * 128  # 401,408
FC2_FLOPS = 2 * 128 * 10  # 2,560
CNN_FORWARD_FLOPS = CONV1_FLOPS + CONV2_FLOPS + FC1_FLOPS + FC2_FLOPS  # 2,436,096
TRAIN_FACTOR = 3  # forward + backward (input and weight gradients)


def _rows_cols(shape: Sequence[int]):
    r = int(shape[0])
    n = 1
    for s in shape[1:]:
        n *= int(s)
    return r, n


def fedavg_reduce_bytes(leaf_shapes: Iterable[Sequence[int]]) -> int:
    """Weighted mean over R rows of each [R, ...] leaf: read R*N f32 and
    the R weights, write N f32."""
    total = 0
    for shape in leaf_shapes:
        r, n = _rows_cols(shape)
        total += 4 * r * n + 4 * r + 4 * n
    return total


def roofline_pct(bytes_moved: float, flops: float, seconds: float, peak: dict):
    """Share (%) of the least time the chip could take -- the larger of
    bytes over peak bandwidth and FLOPs over peak FLOP/s -- in ``seconds``
    of kernel time. None where there is no kernel time to divide by."""
    if seconds <= 0 or (bytes_moved <= 0 and flops <= 0):
        return None
    least = max(bytes_moved / peak["hbm_bytes_per_s"], flops / peak["bf16_flops_per_s"])
    return 100.0 * least / seconds
