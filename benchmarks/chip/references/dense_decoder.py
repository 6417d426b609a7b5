"""Plain float32 reference of a Qwen3-style dense decoder, and the check
that holds served tokens to it.

Written from the architecture, not from the model code under test: token
embedding; per layer RMSNorm (gain 1 + g), GQA attention with per-head
RMSNorm on queries and keys (qk-norm), rotary embedding (half-split, base
``rope_theta``), causal softmax, output projection, residual; RMSNorm,
SwiGLU MLP, residual; a final RMSNorm and the head tied to the embedding.
Every matrix product runs at ``Precision.HIGHEST``: on a TPU a float32
product otherwise runs in bfloat16 passes.

The check: run the reference once over each sampled request's prompt and
served tokens, and at each position that produced a served token take the
gap by which that token's logit lies below the reference's best.  Greedy
decoding at the configuration's precision picks the reference's argmax
up to rounding, so the gaps stay small; a lower precision moves them.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + g.astype(jnp.float32))


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = jnp.exp(-jnp.log(theta) * jnp.arange(half, dtype=jnp.float32)
                  / half)
    ang = pos[:, None, None] * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _mm(a, w):
    return jnp.matmul(a, w.astype(jnp.float32), precision=HI)


def logits(cfg: Dict, params, tokens):
    """float32 logits (S, vocab) at every position of ``tokens`` (S,)."""
    S = tokens.shape[0]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    theta = cfg["rope_theta"]
    pos = jnp.arange(S, dtype=jnp.float32)
    causal = jnp.tril(jnp.ones((S, S), bool))
    table = params["embed"]["table"]
    x = table[tokens].astype(jnp.float32)

    def layer(x, lp):
        a = lp["attn"]
        h = _rms(x, lp["ln1"], eps)
        q = _rms(_mm(h, a["wq"]["w"]).reshape(S, H, hd), a["q_gamma"], eps)
        k = _rms(_mm(h, a["wk"]["w"]).reshape(S, KV, hd), a["k_gamma"], eps)
        v = _mm(h, a["wv"]["w"]).reshape(S, KV, hd)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        q = q.reshape(S, KV, H // KV, hd)
        att = jnp.einsum("skgd,tkd->kgst", q, k, precision=HI) * hd ** -0.5
        att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), -1)
        o = jnp.einsum("kgst,tkd->skgd", att, v,
                       precision=HI).reshape(S, H * hd)
        x = x + _mm(o, a["wo"]["w"])
        f = lp["ffn"]
        h = _rms(x, lp["ln2"], eps)
        x = x + _mm(jax.nn.silu(_mm(h, f["w_gate"]["w"]))
                    * _mm(h, f["w_up"]["w"]), f["w_down"]["w"])
        return x, None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _rms(x, params["final_ln"], eps)
    return jnp.matmul(x, table[:cfg["vocab_size"]].astype(jnp.float32).T,
                      precision=HI)


def make_gap_fn(cfg: Dict):
    """jitted (params, tokens (S,)) → gap (S,): at position t, the
    reference's best logit minus its logit for tokens[t + 1]."""
    def fn(params, tokens):
        lg = logits(cfg, params, tokens)
        nxt = jnp.concatenate([tokens[1:], tokens[:1]])
        return lg.max(-1) - jnp.take_along_axis(lg, nxt[:, None], 1)[:, 0]
    return jax.jit(fn)
