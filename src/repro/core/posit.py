"""Vectorized posit⟨n,es⟩ codec in pure JAX bitwise arithmetic.

This is the software realization of the paper's PRAU datapath: posit bit
patterns live in narrow integer tensors (the "memory side", where the energy /
bandwidth savings come from) and are decoded to IEEE floats only at compute
time (the "MXU side").

Conventions
-----------
* Bit patterns are n-bit, stored in the low bits of an unsigned container.
  Negative posits are the two's complement of their absolute value over n bits
  (Posit Standard 2022).
* ``decode``: exact for every posit with ≤ 24 significand bits when the output
  dtype is float32; exact for all n ≤ 32 when the output dtype is float64
  (requires x64 mode — used by tests and the app-level simulations).
* ``encode``: round-to-nearest-even on the posit lattice, saturating to
  maxpos/minpos (posits never overflow to NaR nor underflow to zero);
  NaN/±Inf map to NaR.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .formats import PositFormat

_U32 = jnp.uint32


def _as_u32(bits: jax.Array, fmt: PositFormat) -> jax.Array:
    """View stored (possibly signed, narrow) patterns as masked uint32."""
    # Signed storage (int8/int16/int32) sign-extends on astype; mask restores
    # the raw n-bit pattern.
    if bits.dtype in (jnp.int8, jnp.int16, jnp.int32):
        u = bits.astype(jnp.uint32)
    elif bits.dtype in (jnp.uint8, jnp.uint16, jnp.uint32):
        u = bits.astype(jnp.uint32)
    else:
        raise TypeError(f"posit bit patterns must be integer, got {bits.dtype}")
    return u & _U32(fmt.mask)


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(1, 2))
def decode(bits: jax.Array, fmt: PositFormat, dtype=jnp.float32) -> jax.Array:
    """Decode n-bit posit patterns to floating point values.

    NaR decodes to NaN (the standard float mapping used by the Universal
    library and by PERCIVAL's float-posit conversion instructions).
    """
    n, es = fmt.n, fmt.es
    x = _as_u32(bits, fmt)

    sign = (x >> _U32(n - 1)) & _U32(1)
    is_zero = x == _U32(0)
    is_nar = x == _U32(fmt.nar_pattern)

    # Two's-complement magnitude (positive posit with identical |value|).
    mag = jnp.where(sign == 1, (~x + _U32(1)) & _U32(fmt.mask), x)

    # Align the n-1 bits below the sign to the top of a 32-bit word.
    y = (mag << _U32(33 - n)).astype(_U32)

    r0 = y >> _U32(31)
    inv = jnp.where(r0 == 1, ~y, y)
    k = lax.clz(inv).astype(jnp.int32)          # regime run length
    k = jnp.minimum(k, n - 1)
    r = jnp.where(r0 == 0, -k, k - 1)           # regime value

    # Drop regime bits + terminator; exponent lands at the top.
    sh = jnp.minimum(k + 1, 31).astype(_U32)
    z = jnp.where(k + 1 >= 32, _U32(0), y << sh)
    if es > 0:
        e = (z >> _U32(32 - es)).astype(jnp.int32)
        frac_top = (z << _U32(es)).astype(_U32)
    else:
        e = jnp.zeros_like(k)
        frac_top = z

    scale = r * (1 << es) + e
    f = frac_top.astype(dtype) * jnp.asarray(2.0 ** -32, dtype)
    # Exact 2**scale via exponent-field construction (exp2 is inexact on some
    # backends). |scale| <= 120 for n <= 32, so both f32/f64 stay normal.
    if dtype == jnp.float64:
        pw = lax.bitcast_convert_type(
            (jnp.clip(scale, -1022, 1023) + 1023).astype(jnp.uint64) << 52,
            jnp.float64,
        )
    else:
        pw = lax.bitcast_convert_type(
            (jnp.clip(scale, -126, 127) + 127).astype(jnp.uint32) << 23,
            jnp.float32,
        ).astype(dtype)
    val = (jnp.asarray(1.0, dtype) + f) * pw
    val = jnp.where(sign == 1, -val, val)
    val = jnp.where(is_zero, jnp.asarray(0.0, dtype), val)
    val = jnp.where(is_nar, jnp.asarray(jnp.nan, dtype), val)
    return val.astype(dtype)


# ---------------------------------------------------------------------------
# Encode
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(1,))
def encode(values: jax.Array, fmt: PositFormat) -> jax.Array:
    """Encode floats to n-bit posit patterns (RNE on the posit lattice).

    Works from float32 inputs always; from float64 inputs when x64 is enabled
    (needed for exact posit24/32 round-trips in the app-level simulations).
    Returns patterns in ``fmt.storage_dtype``.
    """
    n, es = fmt.n, fmt.es
    v = values
    if v.dtype == jnp.float64:
        mbits, ubits_dtype, ebits, ebias = 52, jnp.uint64, 11, 1023
    else:
        v = v.astype(jnp.float32)
        mbits, ubits_dtype, ebits, ebias = 23, jnp.uint32, 8, 127
    U = ubits_dtype
    TBITS = es + mbits

    sign = jnp.signbit(v) & (v != 0)
    is_zero = v == 0
    is_nar = ~jnp.isfinite(v)

    a = jnp.abs(v)
    # Saturation: posits never round to zero or NaR (Posit Standard 2022 §6).
    a = jnp.clip(a, fmt.minpos, fmt.maxpos)

    abits = lax.bitcast_convert_type(a, U)
    biased = (abits >> U(mbits)) & U((1 << ebits) - 1)
    man = abits & U((1 << mbits) - 1)
    q = biased.astype(jnp.int32) - ebias               # power-of-two scale

    r = q >> es                                        # floor division
    e = (q - (r << es)).astype(U)                      # 0 .. 2^es - 1

    # Regime field: r>=0 → (r+1) ones then 0; r<0 → (-r) zeros then 1.
    r_pos = jnp.maximum(r, 0).astype(U)
    R = jnp.where(r >= 0,
                  ((U(1) << (r_pos + U(1))) - U(1)) << U(1),
                  U(1))
    nR = jnp.where(r >= 0, r + 2, 1 - r)               # regime bit count

    T = (e << U(mbits)) | man                          # exp ++ fraction
    shift = nR + TBITS - (n - 1)                       # bits dropped from S

    # Case shift in [1, TBITS]: body = R<<(TBITS-shift) | T>>shift.
    sh_p = jnp.clip(shift, 1, TBITS).astype(U)
    body_p = (R << (U(TBITS) - sh_p)) | (T >> sh_p)
    g_p = (T >> (sh_p - U(1))) & U(1)
    st_p = (T & ((U(1) << (sh_p - U(1))) - U(1))) != 0

    # Case shift <= 0 (wide posit, narrow mantissa): no rounding needed.
    sh_n = jnp.clip(-shift, 0, 31).astype(U)
    body_n = (R << jnp.clip(TBITS - shift, 0, 63).astype(U)) | (T << sh_n)

    # Case shift > TBITS: regime truncation — only exact maxpos reaches here
    # after clamping (T == 0), body = top n-1 bits of R.
    sh_t = jnp.clip(shift - TBITS, 0, 31).astype(U)
    body_t = R >> sh_t

    body = jnp.where(shift <= 0, body_n,
                     jnp.where(shift <= TBITS, body_p, body_t))
    g = jnp.where((shift >= 1) & (shift <= TBITS), g_p, U(0))
    st = jnp.where((shift >= 1) & (shift <= TBITS), st_p, False)

    # Round to nearest, ties to even.
    body = body + (g & (st.astype(U) | (body & U(1))))
    body = jnp.minimum(body, U(fmt.maxpos_pattern))
    body = jnp.maximum(body, U(fmt.minpos_pattern))

    pattern = jnp.where(sign, (~body + U(1)) & U(fmt.mask), body)
    pattern = jnp.where(is_zero, U(0), pattern)
    pattern = jnp.where(is_nar, U(fmt.nar_pattern), pattern)

    # Narrow to storage container (pattern fits by construction).
    return pattern.astype(jnp.uint32).astype(fmt.storage_dtype)


# ---------------------------------------------------------------------------
# Round-through (quantize a float tensor onto the posit lattice)
# ---------------------------------------------------------------------------

def round_posit_math(x: jax.Array, fmt: PositFormat) -> jax.Array:
    """Direct rounding onto the posit lattice by float-bit manipulation.

    Instead of the encode→decode codec round trip (regime construction,
    clz, exponent reassembly — ~60 elementwise ops), derive the regime run
    length from the float exponent and RNE the float's own significand in
    place at the posit's last kept bit.  With ``shift`` as in ``encode``
    (the number of (exponent ++ mantissa) bits the posit cannot keep at
    this scale), the float-bit integer and the posit pattern agree on
    guard/sticky/LSB at that position, so integer RNE on the float bits
    lands on exactly the value ``decode(encode(x))`` produces — including
    carries across binade and regime boundaries, which step to the next
    posit (always a power of two, always representable in range).

    Two non-obvious cases:
    * e-field truncation (``shift > mbits``, at most ``es`` bits): the
      kept bits extend into the float's exponent field.  Adding 1 to the
      biased exponent makes ``bias + 1 ≡ 0 (mod 8) ⊇ (mod 2^es)``, so
      truncating the adjusted bits truncates the power-of-two scale
      itself, matching the decoded zero-fill of missing exponent bits.
    * pure-regime patterns (``shift == es + mbits``): the pattern's last
      kept bit is the regime's low bit — 0 for r ≥ 0, 1 for r < 0 — not a
      bit of the float, so the RNE tie-break LSB is overridden there.

    Elementwise only (no clz/popcount), hence Pallas-safe; shared by the
    jnp fast path and the fused kernels in ``repro.kernels.posit_round``.
    Bit-identity vs the codec oracle is tested exhaustively (tests/).
    """
    n, es = fmt.n, fmt.es
    # Signed containers throughout: the sign bit is split off first, so
    # every magnitude below fits a non-negative int32 (int64 for f64), and
    # Mosaic lowers no unsigned compare/clamp on the TPU.
    if x.dtype == jnp.float64:
        I, mbits, ebits, bias = jnp.int64, 52, 11, 1023
        nan_bits, dtype = 0x7FF8000000000000, jnp.float64
    else:
        x = x.astype(jnp.float32)
        I, mbits, ebits, bias = jnp.int32, 23, 8, 127
        nan_bits, dtype = 0x7FC00000, jnp.float32
    tbits = es + mbits
    mag_mask = (1 << (mbits + ebits)) - 1                 # all but the sign
    full_exp = ((1 << ebits) - 1) << mbits                # |Inf| bit pattern
    # saturation bounds as bit patterns (positive-float ordering is the
    # integer ordering, so the clamp runs in the integer domain)
    minpos_bits = (bias - fmt.max_scale) << mbits
    maxpos_bits = (bias + fmt.max_scale) << mbits

    bits = lax.bitcast_convert_type(x, I)
    sbit = bits & I(~mag_mask)
    mag = bits & I(mag_mask)
    # zero via the same FLOAT compare the codec runs: on FTZ backends
    # (XLA CPU/TPU) subnormals flush to zero in both paths, on non-FTZ
    # backends both clamp them up to minpos — bit-identical either way
    is_zero = x == 0
    is_nar = mag >= I(full_exp)                           # ±Inf or NaN
    m = jnp.clip(mag, I(minpos_bits), I(maxpos_bits))

    q = (m >> mbits).astype(jnp.int32) - bias             # power-of-two scale
    r = q >> es                                           # regime value
    # regime bit count, branchless: r>=0 → r+2; r<0 → 1-r == (~r)+2
    nr = (r ^ (r >> 31)) + 2
    drop = nr + (tbits - (n - 1))                         # == encode's shift
    if 2 + tbits - (n - 1) >= 1:          # narrow formats: drop >= 1 always
        dropc = jnp.minimum(drop, tbits).astype(I)
    else:
        dropc = jnp.clip(drop, 1, tbits).astype(I)

    adj = m + I(1 << mbits)                               # bias+1 alignment
    half_ulp = I(1) << (dropc - I(1))
    # pure-regime patterns (drop >= tbits): the last kept bit is the
    # regime's low bit — 0 for r >= 0, 1 for r < 0 (r's sign bit)
    lsb = jnp.where(drop < tbits,
                    (adj >> dropc) & I(1),
                    ((r >> 31).astype(I)) & I(1))
    rounded = (adj + (half_ulp - I(1)) + lsb) & ~((half_ulp << I(1)) - I(1))
    out = rounded - I(1 << mbits)
    if 2 + tbits - (n - 1) < 1:                           # only wide posits
        out = jnp.where(drop >= 1, out, m)                # can be exact
    out = out | sbit
    out = jnp.where(is_zero, I(0), out)
    out = jnp.where(is_nar, I(nan_bits), out)
    return lax.bitcast_convert_type(out, dtype)


@functools.partial(jax.jit, static_argnums=(1, 2))
def round_to_posit(x: jax.Array, fmt: PositFormat, dtype=None) -> jax.Array:
    """Nearest posit value, in float — the direct float-bit fast path.

    Bit-identical to :func:`round_to_posit_codec` (the oracle) on every
    input; roughly 4x fewer elementwise ops and no clz.
    """
    out_dtype = dtype or x.dtype
    return round_posit_math(x, fmt).astype(out_dtype)


@functools.partial(jax.jit, static_argnums=(1, 2))
def round_to_posit_codec(x: jax.Array, fmt: PositFormat, dtype=None
                         ) -> jax.Array:
    """encode∘decode: nearest posit value, in float (codec oracle path)."""
    out_dtype = dtype or x.dtype
    return decode(encode(x, fmt), fmt, dtype=out_dtype)
