"""Operations and bytes of the posit radix-2 FFT butterfly kernel, from
its shapes: one stage over ``batch`` complex rows of ``n`` points.

A stage runs n/2 butterflies per row; each is one complex multiply
(4 multiplies, 2 adds) and two complex adds (4 adds): 10 real operations,
each followed by a posit rounding, counted as one more operation.  It
reads the row's real and imaginary parts and the n/2 twiddles, and writes
the row back, all float32.
"""
from __future__ import annotations

from typing import Tuple

NAMES = ("butterfly",)        # how the kernel shows in a device trace


def cost(batch: int, n: int) -> Tuple[float, float]:
    """(operations, bytes) of one butterfly stage."""
    fly = batch * (n // 2)
    ops = 20.0 * fly
    nbytes = 4.0 * (2 * 2 * batch * n + 2 * (n // 2))
    return ops, nbytes
