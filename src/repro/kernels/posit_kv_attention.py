"""Pallas TPU kernel: decode-step attention against a posit-quantized KV
cache — the memory-bound hot spot of the decode_32k / long_500k cells.

For each (row, kv-head group): q (G, D) attends over K/V stored as posit
bits (S, D). The kernel streams S in blocks, decodes K/V tiles in VMEM, and
keeps an online-softmax carry — HBM traffic is 2·S·D narrow integers
instead of bf16/f32, cutting the dominant roofline term by the storage
ratio.

Grid: (B, KV, S // bs); carries live in VMEM scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import PositFormat

from .common import decode_tile

NEG_INF = -1e30


def _block_plan(S: int, bs: int):
    """(bs, S_pad): block size clamped to the padded sequence and rounded to
    the f32 sublane multiple (8), and S padded up to a whole number of
    blocks.  ``ref.kv_attention_oracle`` mirrors this plan exactly — the
    bitwise fused≡oracle contract depends on both sides seeing the same
    blocks in the same order."""
    rounded = -(-max(S, 1) // 8) * 8
    bs = max(8, min(bs, rounded))
    return bs, -(-S // bs) * bs


def _kv_attn_kernel(len_ref, q_ref, kbits_ref, vbits_ref, out_ref,
                    m_ref, l_ref, acc_ref, *, fmt: PositFormat, bs: int):
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...]                                  # (G, D) f32
    k = decode_tile(kbits_ref[...], fmt, jnp.float32)   # (bs, D)
    v = decode_tile(vbits_ref[...], fmt, jnp.float32)
    D = q.shape[-1]

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * (D ** -0.5)  # (G, bs)
    pos = i * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    valid = pos < len_ref[pl.program_id(0)]
    logits = jnp.where(valid, logits, NEG_INF)

    m_prev = m_ref[...]                             # (G, 1)
    m_new = jnp.maximum(m_prev, logits.max(axis=-1, keepdims=True))
    p = jnp.exp(logits - m_new)
    p = jnp.where(valid, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_new = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    m_ref[...] = m_new
    l_ref[...] = l_new
    acc_ref[...] = acc_new

    @pl.when(i == pl.num_programs(2) - 1)
    def _finish():
        out_ref[...] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


@functools.partial(jax.jit,
                   static_argnames=("fmt", "bs", "interpret"))
def posit_kv_attention_batched(q: jax.Array, k_bits: jax.Array,
                               v_bits: jax.Array, lengths: jax.Array,
                               fmt: PositFormat, bs: int = 512,
                               interpret: bool = False) -> jax.Array:
    """q: (B, KV, G, D); k_bits/v_bits: (B, KV, S, D) head-major posit
    patterns; lengths: (B,) valid positions per row.  Returns (B, KV, G, D)
    f32.

    One grid step per (row, kv head, sequence block), the block axis last
    so the online-softmax carry in VMEM scratch runs in order.  Every
    block keeps (rows, D) as its last two dims — the tiling Mosaic
    accepts for f32, int16 and int8 alike.  S needs no relation to ``bs``:
    the sequence is padded internally to a whole number of blocks (zero
    bit-patterns, masked out by the ``pos < length`` guard).  S == 0 —
    and, via that same mask, length == 0 — return all-zeros.
    """
    B, KV, G, D = q.shape
    S = k_bits.shape[2]
    assert k_bits.shape == v_bits.shape == (B, KV, S, D), k_bits.shape
    q = q.astype(jnp.float32)
    if S == 0:
        return jnp.zeros((B, KV, G, D), jnp.float32)
    bs, S_pad = _block_plan(S, bs)
    if S_pad != S:
        pad = ((0, 0), (0, 0), (0, S_pad - S), (0, 0))
        k_bits = jnp.pad(k_bits, pad)
        v_bits = jnp.pad(v_bits, pad)
    head = pl.BlockSpec((None, None, G, D), lambda b, h, i: (b, h, 0, 0))
    kv = pl.BlockSpec((None, None, bs, D), lambda b, h, i: (b, h, i, 0))
    return pl.pallas_call(
        functools.partial(_kv_attn_kernel, fmt=fmt, bs=bs),
        grid=(B, KV, S_pad // bs),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), head, kv, kv],
        out_specs=head,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
        interpret=interpret,
    )(jnp.minimum(lengths.astype(jnp.int32), S), q, k_bits, v_bits)


def posit_kv_attention(q: jax.Array, k_bits: jax.Array, v_bits: jax.Array,
                       length: jax.Array, fmt: PositFormat, bs: int = 512,
                       interpret: bool = False) -> jax.Array:
    """One kv head: q (G, D); k_bits/v_bits (S, D); length: valid S.
    Returns (G, D) f32 — the batched kernel on a (1, 1) grid prefix."""
    out = posit_kv_attention_batched(
        q[None, None], k_bits[None, None], v_bits[None, None],
        jnp.reshape(length, (1,)), fmt, bs=bs, interpret=interpret)
    return out[0, 0]
