"""Operations and bytes of the posit rounding kernel (``posit_round_2d``),
from the shape of one call as the device trace names it: the op's text
carries its output, ``f32[rows,128]``.  It reads and writes each float32
element once and runs one rounding per element, counted as one
operation."""
from __future__ import annotations

import re
from typing import Tuple

NAMES = ("posit_round_2d",)   # how the kernel shows in a device trace
_SHAPE = re.compile(r"= f32\[([0-9,]+)\]")


def cost(elements: int) -> Tuple[float, float]:
    """(operations, bytes) of one call over ``elements`` floats."""
    return float(elements), 8.0 * elements


def elements_of(op_text: str) -> int:
    """Elements of the call named by ``op_text`` (0 if it names none)."""
    m = _SHAPE.search(op_text)
    if not m:
        return 0
    n = 1
    for d in m.group(1).split(","):
        n *= int(d)
    return n
