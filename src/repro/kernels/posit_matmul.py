"""Pallas TPU kernels: fused posit matmuls with f32 accumulation.

* ``posit_matmul`` — C[M,N] = decode(A_bits[M,K]) · decode(B_bits[K,N])

  This is the Coprosit datapath mapped onto the TPU memory hierarchy:
  HBM holds n-bit posit patterns; tiles are decoded **in VMEM** right before
  entering the MXU; accumulation is f32 (the quire analogue — no
  intermediate rounding to storage precision). The HBM side therefore moves
  2 bytes (or 1 for posit8) per element instead of 4 — the paper's
  bandwidth/energy saving, without materializing a decoded copy in HBM like
  the naive decode→matmul.

  Tiling: (bm×bk) + (bk×bn) int16 tiles + (bm×bn) f32 accumulator in VMEM.
  Default 256×512×256: 256·512·2·2 + 256·256·4 = 768 KiB ≪ 16 MiB VMEM, and
  every MXU dim is a multiple of 128.

* ``posit_matmul_round`` — C = round_fmt(A[M,K] · B[K,N]) on float values:
  the ``Arith.matmul`` quire path (one wide product, ONE rounding per
  output) in a single launch instead of a matmul dispatch plus a rounding
  dispatch.  K is kept whole per tile (grid over M×N only) so every output
  element is one uninterrupted MXU accumulation; ``do_round=False`` exposes
  the raw wide product — the oracle hook ``tests/test_fused_backend.py``
  uses to verify the fused rounding bit-exactly (the wide product itself is
  the device matmul, whose accumulation order is the same implementation
  detail the jnp path's ``a @ b`` already relies on).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.formats import PositFormat
from repro.core.posit import round_posit_math

from .common import decode_tile, interpret_mode


def _matmul_kernel(a_ref, b_ref, out_ref, *, fmt: PositFormat,
                   compute_dtype):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    a = decode_tile(a_ref[...], fmt, compute_dtype)
    b = decode_tile(b_ref[...], fmt, compute_dtype)
    out_ref[...] += jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


@functools.partial(
    jax.jit, static_argnames=("fmt", "bm", "bn", "bk", "compute_dtype",
                              "interpret"))
def posit_matmul(a_bits: jax.Array, b_bits: jax.Array, fmt: PositFormat,
                 bm: int = 256, bn: int = 256, bk: int = 512,
                 compute_dtype=jnp.bfloat16,
                 interpret: bool = False) -> jax.Array:
    """(M,K)·(K,N) posit bits → f32. Dims must divide the block sizes."""
    M, K = a_bits.shape
    K2, N = b_bits.shape
    assert K == K2
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    grid = (M // bm, N // bn, K // bk)
    return pl.pallas_call(
        functools.partial(_matmul_kernel, fmt=fmt,
                          compute_dtype=compute_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=interpret,
    )(a_bits, b_bits)


def _round_matmul_kernel(a_ref, b_ref, out_ref, *, fmt: PositFormat,
                         do_round: bool):
    wide = jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    out_ref[...] = round_posit_math(wide, fmt) if do_round else wide


@functools.partial(
    jax.jit, static_argnames=("fmt", "bm", "bn", "do_round", "interpret"))
def posit_matmul_round_2d(a: jax.Array, b: jax.Array, fmt: PositFormat,
                          bm: int = 256, bn: int = 256,
                          do_round: bool = True,
                          interpret: bool = False) -> jax.Array:
    """round_fmt(A[M,K] · B[K,N]) → f32, one rounding per output element.

    K stays whole per tile (the hot-path matmuls are tall-skinny: mel
    filterbank 2049→20, DCT-II 20→13, forest votes T→1, so (bm, K) +
    (K, bn) f32 tiles fit VMEM comfortably); dims must divide the blocks.
    """
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    bm, bn = min(bm, M), min(bn, N)
    assert M % bm == 0 and N % bn == 0, (M, N, bm, bn)
    return pl.pallas_call(
        functools.partial(_round_matmul_kernel, fmt=fmt, do_round=do_round),
        grid=(M // bm, N // bn),
        in_specs=[
            pl.BlockSpec((bm, K), lambda i, j: (i, 0)),
            pl.BlockSpec((K, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=interpret,
    )(a, b)


def rounded_matmul(a: jax.Array, b: jax.Array, fmt: PositFormat,
                   do_round: bool = True,
                   interpret: bool | None = None) -> jax.Array:
    """(M,K)·(K,N) float values → round_fmt(wide product), any dims.

    Pads K to the 128-lane multiple (zero K-columns add exact zero terms
    to the accumulation), and M/N up to shapes the kernel's grid divides:
    below one block they become the block themselves (M at the f32
    sublane multiple 8, N at the 128-lane multiple), above it they round
    up to whole 256-blocks.  Padded rows/columns are sliced away.
    """
    if interpret is None:
        interpret = interpret_mode()
    M, K = a.shape
    _, N = b.shape
    if M == 0 or N == 0:
        return jnp.zeros((M, N), jnp.float32)

    def _pad_dim(d: int, unit: int, block: int = 256) -> int:
        d = -(-d // unit) * unit
        return d if d <= block else -(-d // block) * block

    Mp, Np = _pad_dim(M, 8), _pad_dim(N, 128)
    Kp = max(-(-K // 128) * 128, 128)
    if (Mp, Kp) != (M, K):
        a = jnp.pad(a, ((0, Mp - M), (0, Kp - K)))
    if (Kp, Np) != (K, N):
        b = jnp.pad(b, ((0, Kp - K), (0, Np - N)))
    out = posit_matmul_round_2d(a, b, fmt, do_round=do_round,
                                interpret=interpret)
    return out[:M, :N]
