"""Operations and bytes of the posit-KV decode attention kernel, from its
shapes: what one layer's call must do for the rows that are decoding.

Per active row and layer, with ``n`` valid cache positions: read the K and
V posit bits of the ``kv_heads`` heads (n · head_dim bytes each, at
``kv_bits`` per element), the query and write the output in float32;
2·2·heads·head_dim·n operations (scores and the weighted sum), plus the
posit decode of every K and V element, counted as one operation each.
Positions past a row's length, and rows not decoding, are not work.
"""
from __future__ import annotations

from typing import Iterable, Tuple

NAMES = ("posit_kv_attention",)   # how the kernel shows in a device trace


def cost(contexts: Iterable[int], heads: int, kv_heads: int,
         head_dim: int, kv_bits: int) -> Tuple[float, float]:
    """(operations, bytes) of one layer's call over rows at ``contexts``."""
    ops = nbytes = 0.0
    for n in contexts:
        ops += 4.0 * heads * head_dim * n + 2.0 * kv_heads * head_dim * n
        nbytes += 2.0 * kv_heads * n * head_dim * kv_bits / 8.0
        nbytes += 2.0 * heads * head_dim * 4.0
    return ops, nbytes
