"""Attention: GQA/MQA with qk-norm, bias, softcap, local windows; chunked
online-softmax for long sequences; posit-quantized KV cache for decode.

The KV cache is where the paper's low-precision storage pays off at LM scale:
decode steps are memory-bound on cache reads, so posit8 storage (validated by
the paper's §IV-B finding that 8-bit posits keep working where FP8 fails)
halves-to-quarters the dominant roofline term.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.formats import PositFormat
from repro.core.posit import decode as posit_decode, encode as posit_encode
from repro.core.quant import PositTensor

from .common import Builder, dense, make_dense, rms_norm, rope, softcap, wval

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class KVCache:
    """Fixed-capacity KV cache; storage either bf16 arrays or posit bits.

    ``length`` is a scalar int32 (every row advances together — the classic
    static-batch decode) or a (B,) vector of per-row valid lengths (the
    serving engine's continuous-batching slots, where each slot holds a
    different request at a different context depth).

    Storage is head-major, (B, KV, S, D): the decode kernel
    (``kernels.posit_kv_attention``) reads each (row, kv head) as one
    contiguous (S, D) slab, straight from the cache.
    """

    k: object  # jax.Array (B,KV,S,D) bf16  |  PositTensor bits
    v: object
    length: jax.Array  # int32 scalar | (B,): number of valid positions

    def tree_flatten(self):
        return (self.k, self.v, self.length), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def capacity(self) -> int:
        k = self.k.bits if isinstance(self.k, PositTensor) else self.k
        return k.shape[2]

    @property
    def per_row(self) -> bool:
        return self.length.ndim == 1

    # -- storage ---------------------------------------------------------
    @staticmethod
    def create(batch: int, capacity: int, kv_heads: int, head_dim: int,
               fmt: Optional[PositFormat] = None, per_row: bool = False):
        shape = (batch, kv_heads, capacity, head_dim)
        length = jnp.zeros((batch,) if per_row else (), jnp.int32)
        if fmt is None:
            z = jnp.zeros(shape, jnp.bfloat16)
            return KVCache(z, z, length)
        bits = jnp.zeros(shape, fmt.storage_dtype)
        return KVCache(
            PositTensor(bits, fmt, None), PositTensor(bits, fmt, None),
            length,
        )

    def read(self, dtype=jnp.bfloat16):
        """Dequantized (k, v) in the storage layout, (B, KV, S, D)."""
        def rd(store):
            if isinstance(store, PositTensor):
                return store.dequant(jnp.float32).astype(dtype)
            return store.astype(dtype)

        return rd(self.k), rd(self.v)

    def _encode(self, store, new):
        """(B, S_new, KV, D) values → head-major storage entries."""
        new = jnp.swapaxes(new, 1, 2)
        if isinstance(store, PositTensor):
            scaled = new.astype(jnp.float32)
            if store.scale is not None:
                scaled = scaled / store.scale
            return posit_encode(scaled, store.fmt)
        return new.astype(store.dtype)

    @staticmethod
    def _raw(store):
        return store.bits if isinstance(store, PositTensor) else store

    def _wrap(self, store, raw):
        if isinstance(store, PositTensor):
            return PositTensor(raw, store.fmt, store.scale)
        return raw

    def append(self, k_new: jax.Array, v_new: jax.Array,
               new_length: Optional[jax.Array] = None) -> "KVCache":
        """Write S_new positions into the cache.

        Scalar-length caches write at ``length`` (every row in lockstep).
        Per-row caches write one position per row at each row's own
        ``length`` when S_new == 1 (continuous-batching decode), or a fresh
        block at position 0 when S_new > 1 (right-padded prefill:
        ``new_length`` then carries the true per-row prompt lengths; the
        pad tail beyond them is dead weight that the length mask hides and
        later decode steps overwrite).
        """
        S_new = k_new.shape[1]

        def wr(store, new):
            enc = self._encode(store, new)
            raw = self._raw(store)
            if self.per_row and S_new == 1:
                # a select, not a scatter: a scatter at per-row positions
                # makes XLA:TPU hold the cache sequence-major and copy it
                # to and from the kernel's head-major layout every step
                at = (jnp.arange(raw.shape[2])[None, None, :, None]
                      == self.length[:, None, None, None])
                out = jnp.where(at, enc, raw)
            else:
                idx = jnp.zeros((), jnp.int32) if self.per_row \
                    else self.length
                out = jax.lax.dynamic_update_slice(raw, enc, (0, 0, idx, 0))
            return self._wrap(store, out)

        if new_length is None:
            new_length = self.length + S_new
        else:
            new_length = jnp.asarray(new_length, jnp.int32)
        return KVCache(wr(self.k, k_new), wr(self.v, v_new), new_length)


# ---------------------------------------------------------------------------
# Core attention math
# ---------------------------------------------------------------------------

def plain_attention(q, k, v, *, causal, window, cap, q_offset=0, kv_len=None,
                    heads_major=False):
    """Reference/materialized path (short sequences, decode).

    ``q_offset`` and ``kv_len`` accept scalars (shared by every row) or
    (B,) vectors — per-row offsets/lengths are how ragged right-padded
    prompts and continuous-batching decode slots mask their own context.
    k/v are (B, S, KV, D), or (B, KV, S, D) — the ``KVCache`` layout —
    with ``heads_major``.
    """
    B, Sq, H, D = q.shape
    if heads_major:
        KV, S, kv = k.shape[1], k.shape[2], "bksd"
    else:
        S, KV, kv = k.shape[1], k.shape[2], "bskd"
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, D)
    logits = jnp.einsum(f"bqkgd,{kv}->bkgqs", qg, k,
                        preferred_element_type=jnp.float32)
    logits = logits * (D ** -0.5)
    logits = softcap(logits, cap)
    # (1|B, Sq) query positions vs (S,) key positions
    qpos = jnp.reshape(jnp.asarray(q_offset), (-1, 1)) + jnp.arange(Sq)
    kpos = jnp.arange(S)
    m = (qpos[:, :, None] - kpos[None, None, :]) < window
    if causal:
        m &= kpos[None, None, :] <= qpos[:, :, None]
    if kv_len is not None:
        m &= kpos[None, None, :] < jnp.reshape(jnp.asarray(kv_len),
                                               (-1, 1, 1))
    logits = jnp.where(m[:, None, None], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(f"bkgqs,{kv}->bqkgd", w.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Sq, H, D).astype(q.dtype)


def chunked_attention(q, k, v, *, causal, window, cap,
                      q_block=512, k_block=512, q_offset=0):
    """Online-softmax blocked attention — never materializes (Sq, Skv).

    Scans query blocks (outer) and key blocks (inner) with running
    (max, denom, out) carries; f32 accumulation throughout (quire-style).
    """
    B, Sq, H, D = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV
    q_block = min(q_block, Sq)
    k_block = min(k_block, Skv)
    assert Sq % q_block == 0 and Skv % k_block == 0
    nq, nk = Sq // q_block, Skv // k_block

    qb = q.reshape(B, nq, q_block, KV, G, D)
    kb = k.reshape(B, nk, k_block, KV, D)
    vb = v.reshape(B, nk, k_block, KV, D)
    scale = D ** -0.5

    def q_step(_, qi_blk):
        qi, q_blk = qi_blk
        qpos = q_offset + qi * q_block + jnp.arange(q_block)

        def k_step(carry, kj_blk):
            m_run, l_run, o_run = carry
            kj, k_blk, v_blk = kj_blk
            kpos = kj * k_block + jnp.arange(k_block)
            logits = jnp.einsum("bqkgd,bskd->bkgqs", q_blk, k_blk,
                                preferred_element_type=jnp.float32) * scale
            logits = softcap(logits, cap)
            msk = (qpos[:, None] - kpos[None, :]) < window
            if causal:
                msk &= kpos[None, :] <= qpos[:, None]
            logits = jnp.where(msk[None, None, None], logits, NEG_INF)
            m_new = jnp.maximum(m_run, logits.max(axis=-1))
            p = jnp.exp(logits - m_new[..., None])
            p = jnp.where(msk[None, None, None], p, 0.0)
            alpha = jnp.exp(m_run - m_new)
            l_new = l_run * alpha + p.sum(axis=-1)
            pv = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(v_blk.dtype), v_blk,
                            preferred_element_type=jnp.float32)
            o_new = o_run * alpha[..., None] + pv
            return (m_new, l_new, o_new), None

        m0 = jnp.full((B, KV, G, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, q_block), jnp.float32)
        o0 = jnp.zeros((B, KV, G, q_block, D), jnp.float32)
        (m_f, l_f, o_f), _ = jax.lax.scan(
            k_step, (m0, l0, o0),
            (jnp.arange(nk), jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0)))
        out = o_f / jnp.maximum(l_f, 1e-30)[..., None]
        # (B, KV, G, q_block, D) → (B, q_block, H, D)
        out = jnp.moveaxis(out, 3, 1).reshape(B, q_block, KV * G, D)
        return None, out

    _, outs = jax.lax.scan(q_step, None,
                           (jnp.arange(nq), jnp.moveaxis(qb, 1, 0)))
    # outs: (nq, B, q_block, H, D)
    out = jnp.moveaxis(outs, 0, 1).reshape(B, Sq, H, D)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Attention layer (params + apply)
# ---------------------------------------------------------------------------

def init_attention(b: Builder, cfg) -> dict:
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    p = {
        "wq": make_dense(b, "wq", d, H * hd, "model", bias=cfg.qkv_bias),
        "wk": make_dense(b, "wk", d, KV * hd, "model", bias=cfg.qkv_bias),
        "wv": make_dense(b, "wv", d, KV * hd, "model", bias=cfg.qkv_bias),
        "wo": make_dense(b, "wo", H * hd, d, None, logical_in="model"),
    }
    if cfg.qk_norm:
        p["q_gamma"] = b.param("q_gamma", (hd,), (None,), init="zeros")
        p["k_gamma"] = b.param("k_gamma", (hd,), (None,), init="zeros")
    return p


def _project_qkv(p, x, cfg, positions):
    B, S, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = dense(p["wq"], x).reshape(B, S, H, hd)
    k = dense(p["wk"], x).reshape(B, S, KV, hd)
    v = dense(p["wv"], x).reshape(B, S, KV, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_gamma"])
        k = rms_norm(k, p["k_gamma"])
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    return q, k, v


BIG_WINDOW = 1 << 30


def attention_train(p, x, cfg, *, window=BIG_WINDOW, causal=True):
    """Full-sequence attention (training)."""
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    q, k, v = _project_qkv(p, x, cfg, positions)
    if S > 1024:
        out = chunked_attention(q, k, v, causal=causal, window=window,
                                cap=cfg.attn_softcap)
    else:
        out = plain_attention(q, k, v, causal=causal, window=window,
                              cap=cfg.attn_softcap)
    return dense(p["wo"], out.reshape(B, S, -1))


def attention_prefill(p, x, cfg, cache: KVCache, *, window=BIG_WINDOW,
                      causal=True, lengths=None):
    """Full-sequence attention + cache fill. Attention uses the fresh bf16
    k/v (standard practice); the cache stores the quantized copy that decode
    will read.

    ``lengths`` (B,) marks right-padded prompts: key positions at or past a
    row's length are masked out of the prefill attention, and the cache
    records the true per-row lengths instead of the padded S.
    """
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    q, k, v = _project_qkv(p, x, cfg, positions)
    cache = cache.append(k, v, new_length=lengths)
    if S > 1024 and lengths is None:
        out = chunked_attention(q, k, v, causal=causal, window=window,
                                cap=cfg.attn_softcap)
    else:
        out = plain_attention(q, k, v, causal=causal, window=window,
                              cap=cfg.attn_softcap, kv_len=lengths)
    return dense(p["wo"], out.reshape(B, S, -1)), cache


def _fused_kv_eligible(cfg, cache: KVCache, S_new: int) -> bool:
    """Route decode attention through the Pallas posit-KV kernel?

    Static conditions only (they bake into the trace): posit bit storage
    without an RMS scale, one query position, no logit softcap, and no
    local-window layers (``cfg.local_window`` is the static source — the
    per-layer window value itself is a scanned tracer).  The backend
    selection mirrors ``Arith.matmul``'s routing: the fused kernel runs
    when ``REPRO_ROUND_BACKEND`` resolves to pallas AND fused kernels are
    on; every other combination keeps the jnp decode-then-attend oracle.
    """
    from repro.core.arith import get_fused_kernels, get_round_backend

    return (isinstance(cache.k, PositTensor)
            and isinstance(cache.v, PositTensor)
            and cache.k.scale is None and cache.v.scale is None
            and S_new == 1
            and cfg.attn_softcap == 0.0
            and cfg.local_window == 0
            and get_round_backend() == "pallas"
            and get_fused_kernels())


def attention_decode(p, x, cfg, cache: KVCache, *, window=BIG_WINDOW):
    """Single-token decode against a (possibly posit-quantized) cache.

    Per-row caches mask and position each row by its own length.  Posit
    caches additionally route through ``kernels.posit_kv_attention`` (the
    fused online-softmax kernel that decodes K/V bits in VMEM) when the
    PR-5 backend machinery selects the pallas realization — the jnp
    decode-then-attend path below is its oracle, property-tested bitwise
    against the kernel in tests/test_kernels.py / tests/test_serve.py.
    """
    B, S_new, _ = x.shape
    positions = jnp.reshape(cache.length, (-1, 1)) + jnp.arange(S_new)
    positions = jnp.broadcast_to(positions, (B, S_new))
    q, k_new, v_new = _project_qkv(p, x, cfg, positions)
    cache = cache.append(k_new, v_new)
    if _fused_kv_eligible(cfg, cache, S_new):
        from repro.kernels import ops as kernel_ops
        KV, hd = k_new.shape[2], k_new.shape[3]
        G = q.shape[2] // KV
        out = kernel_ops.kv_attention(
            q[:, 0].reshape(B, KV, G, hd).astype(jnp.float32),
            cache.k.bits, cache.v.bits, cache.length, cache.k.fmt)
        out = out.reshape(B, 1, KV * G, hd).astype(x.dtype)
    else:
        # f32 operands, as in the kernel: exact for bf16 and posit
        # entries, and a batched dot that contracts the head-major
        # cache's sequence dim has no bf16 kernel on the CPU backend
        k, v = cache.read(dtype=jnp.float32)
        out = plain_attention(
            q, k, v, causal=True, window=window, cap=cfg.attn_softcap,
            q_offset=cache.length - S_new, kv_len=cache.length,
            heads_major=True)
    return dense(p["wo"], out.reshape(B, S_new, -1)), cache


def cross_attention(p, x, cfg, enc_k, enc_v, enc_len=None):
    """Decoder→encoder attention (seamless); encoder KV precomputed."""
    B, S_new, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = dense(p["wq"], x).reshape(B, S_new, H, hd)
    out = plain_attention(q, enc_k, enc_v, causal=False, window=BIG_WINDOW,
                          cap=0.0, kv_len=enc_len)
    return dense(p["wo"], out.reshape(B, S_new, -1))
