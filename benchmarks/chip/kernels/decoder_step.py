"""Operations of a dense decoder's tokens, from the configuration's shapes:
what ``mfu`` counts as the model's work.

Per token: 2 operations per weight of every matrix product held here (the
attention and MLP projections of each layer, and the head), plus attention
at the token's context: 2·2·heads·head_dim operations per attended
position and layer (scores and the weighted sum of values).  Recomputed
or padded work does not count.
"""
from __future__ import annotations

from typing import Dict, Iterable


def matmul_weights(cfg: Dict) -> int:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    layer = d * H * hd * 2 + d * KV * hd * 2 + 3 * d * f
    return cfg["num_hidden_layers"] * layer + cfg["vocab_size"] * d


def attention_ops(cfg: Dict, context: int) -> int:
    return (4 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * cfg["head_dim"] * context)


def decode_ops(cfg: Dict, contexts: Iterable[int]) -> int:
    """Decode tokens, one per entry of ``contexts`` (positions attended)."""
    w = 2 * matmul_weights(cfg)
    return sum(w + attention_ops(cfg, c) for c in contexts)


def prefill_ops(cfg: Dict, length: int) -> int:
    """A prompt of ``length`` tokens, causal: token t attends t+1."""
    return (2 * matmul_weights(cfg) * length
            + attention_ops(cfg, length * (length + 1) // 2))
