"""Pallas TPU kernels: fused posit rounding on the float datapath.

The PRAU-style in-register rounding: instead of materializing an encode →
decode codec round trip per elementary op, the kernel rounds a float tile
onto the posit lattice in place with the direct float-bit manipulation of
``repro.core.posit.round_posit_math`` (elementwise, no clz — Pallas-safe),
optionally fused with the producing op so each streaming butterfly / MAC is
one kernel launch instead of a dispatch chain:

* ``posit_round_2d``    — elementwise x → nearest-posit(x)
* ``posit_fma_round_2d``— round(a·b + c), one rounding (PRAU MAC)
* ``posit_butterfly_2d``— the radix-2 DIT FFT butterfly with every
  elementary op rounded, the §VI-B hot loop of the cough pipeline:
  t = w ⊗ o (4 mul + 2 add, each rounded), u = e + t, v = e − t.

On the CPU backend the kernels run in ``interpret=True`` mode — same
kernel body — so CPU hosts stay testable; ``repro.core.arith`` routes
through these kernels only when the backend profits from them (TPU), via
the ``REPRO_ROUND_BACKEND`` switch.

Tiling: (block_rows, 128) float32 tiles, lane dim a multiple of 128,
sublane a multiple of 8 — the f32 minimum tile of the VPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.formats import PositFormat
from repro.core.posit import round_posit_math

from .common import interpret_mode, pad_to_tiles as _pad_2d


def _round_kernel(x_ref, out_ref, *, fmt: PositFormat):
    out_ref[...] = round_posit_math(x_ref[...], fmt)


def _fma_round_kernel(a_ref, b_ref, c_ref, out_ref, *, fmt: PositFormat):
    out_ref[...] = round_posit_math(
        a_ref[...] * b_ref[...] + c_ref[...], fmt)


def _butterfly_kernel(er_ref, ei_ref, or_ref, oi_ref, wr_ref, wi_ref,
                      ur_ref, ui_ref, vr_ref, vi_ref, *, fmt: PositFormat):
    rnd = functools.partial(round_posit_math, fmt=fmt)
    er, ei = er_ref[...], ei_ref[...]
    o_r, o_i = or_ref[...], oi_ref[...]
    wr, wi = wr_ref[...], wi_ref[...]
    t_r = rnd(rnd(wr * o_r) - rnd(wi * o_i))
    t_i = rnd(rnd(wr * o_i) + rnd(wi * o_r))
    ur_ref[...] = rnd(er + t_r)
    ui_ref[...] = rnd(ei + t_i)
    vr_ref[...] = rnd(er - t_r)
    vi_ref[...] = rnd(ei - t_i)


@functools.partial(jax.jit,
                   static_argnames=("fmt", "block_rows", "interpret"))
def posit_round_2d(x: jax.Array, fmt: PositFormat, block_rows: int = 512,
                   interpret: bool = False) -> jax.Array:
    """(M, 128·k) floats → nearest posit values, same shape/dtype."""
    M, N = x.shape
    bm, bn = min(block_rows, M), min(128, N)
    assert M % bm == 0 and N % bn == 0, (M, N, bm, bn)
    return pl.pallas_call(
        functools.partial(_round_kernel, fmt=fmt),
        grid=(M // bm, N // bn),
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        interpret=interpret,
    )(x)


@functools.partial(jax.jit,
                   static_argnames=("fmt", "block_rows", "interpret"))
def posit_fma_round_2d(a: jax.Array, b: jax.Array, c: jax.Array,
                       fmt: PositFormat, block_rows: int = 512,
                       interpret: bool = False) -> jax.Array:
    """round(a·b + c) with a single rounding — the quire-style MAC."""
    M, N = a.shape
    bm, bn = min(block_rows, M), min(128, N)
    assert M % bm == 0 and N % bn == 0, (M, N, bm, bn)
    spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    return pl.pallas_call(
        functools.partial(_fma_round_kernel, fmt=fmt),
        grid=(M // bm, N // bn),
        in_specs=[spec] * 3,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((M, N), a.dtype),
        interpret=interpret,
    )(a, b, c)


@functools.partial(jax.jit,
                   static_argnames=("fmt", "block_rows", "interpret"))
def posit_butterfly_2d(e_re, e_im, o_re, o_im, w_re, w_im,
                       fmt: PositFormat, block_rows: int = 512,
                       interpret: bool = False):
    """One rounded radix-2 butterfly over (M, 128·k) planes.

    Returns (u_re, u_im, v_re, v_im) with the exact per-op rounding
    sequence of ``apps.dsp.fft_format`` — 10 rounded ops fused into one
    kernel launch instead of ten.
    """
    M, N = e_re.shape
    bm, bn = min(block_rows, M), min(128, N)
    assert M % bm == 0 and N % bn == 0, (M, N, bm, bn)
    spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    out = jax.ShapeDtypeStruct((M, N), e_re.dtype)
    return pl.pallas_call(
        functools.partial(_butterfly_kernel, fmt=fmt),
        grid=(M // bm, N // bn),
        in_specs=[spec] * 6,
        out_specs=[spec] * 4,
        out_shape=[out] * 4,
        interpret=interpret,
    )(e_re, e_im, o_re, o_im, w_re, w_im)


def posit_round(x: jax.Array, fmt: PositFormat,
                interpret: bool | None = None) -> jax.Array:
    """Arbitrary-shape fused round (reshaped onto (rows, 128) tiles)."""
    if interpret is None:
        interpret = interpret_mode()
    mat, n, bm = _pad_2d(x)
    out = posit_round_2d(mat, fmt, block_rows=bm, interpret=interpret)
    return out.reshape(-1)[:n].reshape(x.shape)


def posit_fma_round(a: jax.Array, b: jax.Array, c: jax.Array,
                    fmt: PositFormat,
                    interpret: bool | None = None) -> jax.Array:
    """Arbitrary-shape fused round(a·b + c) (broadcasts like jnp)."""
    if interpret is None:
        interpret = interpret_mode()
    a, b, c = jnp.broadcast_arrays(jnp.asarray(a), jnp.asarray(b),
                                   jnp.asarray(c))
    am, n, bm = _pad_2d(a)
    bmat, _, _ = _pad_2d(b)
    cmat, _, _ = _pad_2d(c)
    out = posit_fma_round_2d(am, bmat, cmat, fmt, block_rows=bm,
                             interpret=interpret)
    return out.reshape(-1)[:n].reshape(a.shape)


def posit_butterfly(e_re, e_im, o_re, o_im, w_re, w_im, fmt: PositFormat,
                    interpret: bool | None = None):
    """Arbitrary-shape batched rounded butterfly: one launch per FFT stage.

    Broadcasts the six operands together (the stage loop passes whole
    (batch, …, L, R/2) planes with the plan's twiddle constants broadcast
    along the run axis), flattens them onto the (rows, 128) f32 tiles of
    ``posit_butterfly_2d``, and unpads the four outputs.  Padding lanes
    compute garbage butterflies that are sliced away — the kernel body is
    elementwise, so real lanes are unaffected.
    """
    if interpret is None:
        interpret = interpret_mode()
    arrs = jnp.broadcast_arrays(e_re, e_im, o_re, o_im, w_re, w_im)
    shape = arrs[0].shape
    mats, n, bm = [], None, None
    for a in arrs:
        m, n, bm = _pad_2d(a)
        mats.append(m)
    outs = posit_butterfly_2d(*mats, fmt, block_rows=bm, interpret=interpret)
    return tuple(o.reshape(-1)[:n].reshape(shape) for o in outs)
