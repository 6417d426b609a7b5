"""Public jit'd wrappers around the Pallas kernels.

On the CPU backend the kernels execute in ``interpret=True`` mode — same
kernel body, Python-evaluated — so the whole framework remains runnable and
testable off-TPU (``common.interpret_mode``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.formats import PositFormat

from .common import interpret_mode
from .posit_decode import posit_decode_2d
from .posit_encode import posit_encode_2d
from .posit_matmul import posit_matmul, rounded_matmul
from .posit_round import posit_butterfly
from .posit_kv_attention import posit_kv_attention_batched


def decode(bits: jax.Array, fmt: PositFormat, out_dtype=jnp.float32):
    """Arbitrary-shape decode: reshaped onto (rows, 128·k) tiles."""
    shape = bits.shape
    flat = bits.reshape(-1)
    n = flat.shape[0]
    pad = (-n) % (8 * 128)
    if pad:
        flat = jnp.pad(flat, (0, pad))
    mat = flat.reshape(-1, 128)
    out = posit_decode_2d(mat, fmt, out_dtype,
                          block_rows=min(512, mat.shape[0]),
                          interpret=interpret_mode())
    return out.reshape(-1)[:n].reshape(shape)


def encode(x: jax.Array, fmt: PositFormat):
    shape = x.shape
    flat = x.reshape(-1)
    n = flat.shape[0]
    pad = (-n) % (8 * 128)
    if pad:
        flat = jnp.pad(flat, (0, pad))
    mat = flat.reshape(-1, 128)
    out = posit_encode_2d(mat, fmt, block_rows=min(512, mat.shape[0]),
                          interpret=interpret_mode())
    return out.reshape(-1)[:n].reshape(shape)


def matmul(a_bits: jax.Array, b_bits: jax.Array, fmt: PositFormat, **kw):
    return posit_matmul(a_bits, b_bits, fmt, interpret=interpret_mode(), **kw)


def matmul_rounded(a: jax.Array, b: jax.Array, fmt: PositFormat, **kw):
    """Fused round_fmt(a·b) on float values (the Arith.matmul quire path)."""
    return rounded_matmul(a, b, fmt, interpret=interpret_mode(), **kw)


def butterfly(e_re, e_im, o_re, o_im, w_re, w_im, fmt: PositFormat):
    """One fused rounded radix-2 butterfly over whole broadcastable planes."""
    return posit_butterfly(e_re, e_im, o_re, o_im, w_re, w_im, fmt,
                           interpret=interpret_mode())


def kv_attention(q: jax.Array, k_bits: jax.Array, v_bits: jax.Array,
                 length, fmt: PositFormat, bs: int = 512):
    """Batched wrapper: q (B, KV, G, D); k/v bits head-major (B, KV, S, D),
    the ``KVCache`` storage layout, so each kernel block ends in (S, D).

    ``length`` is a scalar shared by every row or a (B,) vector of per-row
    valid lengths — the serving engine's continuous-batching slots each
    carry their own context length.
    """
    B = q.shape[0]
    length = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (B,))
    return posit_kv_attention_batched(q, k_bits, v_bits, length, fmt, bs=bs,
                                      interpret=interpret_mode())
