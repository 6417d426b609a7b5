"""Quire semantics: fused accumulation without intermediate storage rounding.

The paper's quire is a 16n-bit fixed-point register that accumulates up to
2^31-1 MACs exactly before a single rounding to posit (the Xposit
QMADD...QROUND sequence, PAPER.md §V).  No float accumulator IS a quire, but
an exact accumulation can be *simulated* in floats with error-free
transformations: ``two_sum``/``two_prod`` split every partial result into a
rounded value plus its exact rounding error, and the compensated ``comp_*``
reducers below carry the running sum as an unevaluated ``(s, c)`` pair whose
sum equals the exact result far beyond working precision.  ``Arith`` routes
its posit reductions through these under ``REPRO_QUIRE=on``; the pure-Python
``quire_dot_exact`` Fractions oracle pins them bit-exact
(tests/test_quire_mode.py).

Exactness envelope (per posit⟨n,es⟩; significand = n−2−es bits):

* **Products.** A product of two posit values carries ≤ 2·(n−2−es)
  significand bits: exact in f32 for n ≤ 16 (posit16: exactly 24 bits), in
  f64 for n ≤ 24.  posit32 products (56 bits) are inexact even in f64, so
  the compensated reducers take ``product_eft=True`` there and split each
  product through ``two_prod`` (Dekker; no FMA required).
* **Range.** A product's scale reaches 2·max_scale: ±112 for posit16 (fits
  f32), ±176/±240 for posit24/32 — the wide posits REQUIRE the f64
  accumulator, i.e. x64 mode (``jax.enable_x64``).
  ``quire_acc_dtype`` resolves this per format.
* **Accumulation.** The pairwise ``(s, c)`` tree is not literally exact for
  adversarial chains (the compensation term itself rounds), but its error
  is O(u²·K·cond) with u = 2^-24/2^-53 — below half an ulp of every posit
  lattice point for any K and conditioning reachable from posit inputs at
  the vector lengths used here; the property suite pins bit-identity
  against the Fractions oracle, including crafted catastrophic
  cancellation.
* **Final rounding.** ``rnd(s + c)`` rounds the float image of the exact
  sum once; the f64→posit double rounding is exact except on measure-zero
  ties of the compensated tail (never observed on the pinned vectors).
* **Specials.** NaR in any operand decodes to NaN, survives every EFT, and
  encodes back to ``nar_pattern`` — the standard's poisoning.  Zero-length
  accumulations return exact 0, matching ``encode_scalar(0)``.

Public pieces:

* ``quire_dot_exact``   — pure-Python exact oracle (Fractions) for tests.
* ``two_sum``/``two_prod``/``comp_sum``/``comp_dot``/``comp_cumsum`` — the
  EFT building blocks ``Arith`` uses for its quire paths.
* ``qdot``              — bits-in/bits-out fused dot: decode → exact
                          compensated accumulation → single final rounding.
* ``quire_matmul_ref``  — the jnp oracle used by the Pallas posit matmul.
"""
from __future__ import annotations

from fractions import Fraction
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .formats import PositFormat
from .posit import decode, encode
from .posit_scalar import decode_scalar, encode_scalar


# ---------------------------------------------------------------------------
# Exact oracle
# ---------------------------------------------------------------------------

def quire_dot_exact(a_bits: np.ndarray, b_bits: np.ndarray, fmt: PositFormat) -> int:
    """Exact fused dot product of two posit vectors → posit pattern.

    Mirrors the PRAU quire path: products and the running sum are exact; one
    rounding at the end (QMADD...QROUND sequence in the Xposit ISA). NaR in
    any operand poisons the result, as in the standard.
    """
    total = Fraction(0)
    for pa, pb in zip(np.asarray(a_bits).ravel(), np.asarray(b_bits).ravel()):
        va = decode_scalar(int(pa), fmt)
        vb = decode_scalar(int(pb), fmt)
        if va is None or vb is None:
            return fmt.nar_pattern
        total += va * vb
    return encode_scalar(total, fmt)


# ---------------------------------------------------------------------------
# Error-free transformations (the float realization of the quire)
# ---------------------------------------------------------------------------

def two_sum(a: jax.Array, b: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Knuth's branch-free EFT: ``s = fl(a+b)`` and ``s + e == a + b``
    exactly, for any finite IEEE inputs (NaN/Inf propagate)."""
    s = a + b
    bp = s - a
    e = (a - (s - bp)) + (b - bp)
    return s, e


# Dekker split constants 2^ceil(p/2) + 1: p = 24 (f32) → 2^12+1,
# p = 53 (f64) → 2^27+1.
_SPLIT = {np.dtype(np.float32): np.float32(4097.0),
          np.dtype(np.float64): np.float64(134217729.0)}


def two_prod(a: jax.Array, b: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Dekker's EFT product: ``p = fl(a·b)`` and ``p + e == a·b`` exactly
    (no FMA — XLA CPU has none for separate mul/add graphs), provided the
    split ``(2^⌈p/2⌉+1)·a`` does not overflow (|scale| ≲ 1000 in f64 —
    every posit32 product qualifies)."""
    split = _SPLIT[np.dtype(jnp.result_type(a, b))]
    p = a * b
    ca = split * a
    ah = ca - (ca - a)
    al = a - ah
    cb = split * b
    bh = cb - (cb - b)
    bl = b - bh
    e = ((ah * bh - p) + ah * bl + al * bh) + al * bl
    return p, e


def _comp_reduce_last(s: jax.Array, c: jax.Array
                      ) -> Tuple[jax.Array, jax.Array]:
    """Pairwise compensated reduction over the last axis of an ``(s, c)``
    pair field → scalar-last ``(s, c)``.  Zero-padding to a power of two is
    exact; each merge is one ``two_sum`` plus exact-error carries, so depth
    is log2 K and the compensation never sees a long sequential chain."""
    K = s.shape[-1]
    if K == 0:
        z = jnp.zeros(s.shape[:-1], s.dtype)
        return z, z
    P = 1 << (K - 1).bit_length()
    if P != K:
        pad = [(0, 0)] * (s.ndim - 1) + [(0, P - K)]
        s = jnp.pad(s, pad)
        c = jnp.pad(c, pad)
    while s.shape[-1] > 1:
        h = s.shape[-1] // 2
        s, e = two_sum(s[..., :h], s[..., h:])
        c = (c[..., :h] + c[..., h:]) + e
    return s[..., 0], c[..., 0]


def comp_sum(x: jax.Array, axis=-1) -> Tuple[jax.Array, jax.Array]:
    """Compensated sum along ``axis`` (None = ravel): returns ``(s, c)``
    with ``s + c`` the near-exact total (envelope in module docstring)."""
    x = jnp.asarray(x)
    if axis is None:
        x = x.reshape(-1)
        axis = -1
    moved = jnp.moveaxis(x, axis, -1)
    return _comp_reduce_last(moved, jnp.zeros_like(moved))


def comp_dot(a: jax.Array, b: jax.Array, axis=-1, product_eft: bool = False
             ) -> Tuple[jax.Array, jax.Array]:
    """Compensated dot along ``axis`` (Ogita–Rump–Oishi Dot2 shape):
    products (split through ``two_prod`` when ``product_eft`` — needed only
    where a single product overflows the accumulator significand, i.e.
    posit32 in f64) feed the pairwise compensated reduction."""
    a, b = jnp.asarray(a), jnp.asarray(b)
    if axis is None:
        a, b = jnp.broadcast_arrays(a, b)
        a, b = a.reshape(-1), b.reshape(-1)
        axis = -1
    if product_eft:
        a, b = jnp.broadcast_arrays(a, b)
        p, e = two_prod(a, b)
    else:
        p = a * b
        e = jnp.zeros_like(p)
    return _comp_reduce_last(jnp.moveaxis(p, axis, -1),
                             jnp.moveaxis(e, axis, -1))


def comp_cumsum(x: jax.Array, axis=-1) -> Tuple[jax.Array, jax.Array]:
    """Compensated prefix sums along ``axis`` (None = ravel): every prefix
    is its own quire accumulation, returned as an ``(s, c)`` pair field."""
    x = jnp.asarray(x)
    if axis is None:
        x = x.reshape(-1)
        axis = -1
    moved = jnp.moveaxis(x, axis, 0)
    z = jnp.zeros(moved.shape[1:], moved.dtype)

    def step(carry, xk):
        s, c = carry
        s2, e = two_sum(s, xk)
        out = (s2, c + e)
        return out, out

    _, (ss, cc) = jax.lax.scan(step, (z, z), moved)
    return jnp.moveaxis(ss, 0, axis), jnp.moveaxis(cc, 0, axis)


# ---------------------------------------------------------------------------
# Per-format accumulator resolution
# ---------------------------------------------------------------------------

def product_eft_needed(fmt: PositFormat, acc_dtype) -> bool:
    """True iff a single product of ``fmt`` values can be inexact in
    ``acc_dtype`` (2·significand bits exceed the accumulator's): only
    posit32 in f64 among the registered formats."""
    mant = 53 if np.dtype(acc_dtype) == np.dtype(np.float64) else 24
    return 2 * (fmt.max_fraction_bits + 1) > mant


def quire_acc_dtype(fmt: PositFormat):
    """Narrowest float dtype whose significand AND exponent range carry
    ``fmt``'s products exactly: f32 for n ≤ 16, f64 for the wide posits
    (24/32 — product scales ±176/±240 overflow f32).  f64 needs x64 mode;
    without it the f32 fallback keeps the seed behavior and the bit-exact
    envelope excludes the wide formats (documented above)."""
    needs_wide = (2 * (fmt.max_fraction_bits + 1) > 24
                  or 2 * fmt.max_scale > 126)
    if needs_wide and jax.config.jax_enable_x64:
        return jnp.float64
    return jnp.float32


# ---------------------------------------------------------------------------
# Fused bits-in/bits-out ops
# ---------------------------------------------------------------------------

def qdot(
    a_bits: jax.Array,
    b_bits: jax.Array,
    fmt: PositFormat,
    acc_dtype=None,
    out_format: Optional[PositFormat] = None,
) -> jax.Array:
    """Fused posit dot product: decode → exact accumulate → single rounding.

    ``acc_dtype=None`` resolves per format through ``quire_acc_dtype`` —
    the seed's fixed-f32 default was provably inexact for the wide posits
    (a posit24 product already needs 40 significand bits and scale ±176).
    Accumulation is compensated (``comp_dot``), with ``two_prod`` product
    splitting where the format requires it, so the result is bit-exact
    against ``quire_dot_exact`` over the envelope in the module docstring.

    Returns posit bit patterns when ``out_format`` is given, else the wide
    accumulator value (the common case inside a network, where the next op
    consumes the wide output directly).
    """
    if acc_dtype is None:
        acc_dtype = quire_acc_dtype(fmt)
    va = decode(a_bits, fmt, dtype=acc_dtype).reshape(-1)
    vb = decode(b_bits, fmt, dtype=acc_dtype).reshape(-1)
    s, c = comp_dot(va, vb, axis=-1,
                    product_eft=product_eft_needed(fmt, acc_dtype))
    acc = s + c
    if out_format is None:
        return acc
    return encode(acc, out_format)


def quire_matmul_ref(
    a_bits: jax.Array,
    b_bits: jax.Array,
    fmt: PositFormat,
    acc_dtype=jnp.float32,
    compute_dtype=jnp.bfloat16,
) -> jax.Array:
    """Oracle for the Pallas posit matmul: (M,K)·(K,N) posit bits → f32.

    Decode to ``compute_dtype`` (the MXU input format), multiply-accumulate
    in ``acc_dtype`` (the MXU accumulator = quire analogue).
    """
    va = decode(a_bits, fmt, dtype=jnp.float32).astype(compute_dtype)
    vb = decode(b_bits, fmt, dtype=jnp.float32).astype(compute_dtype)
    return jax.lax.dot_general(
        va, vb,
        dimension_numbers=(((va.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=acc_dtype,
    )
