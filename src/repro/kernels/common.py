"""Shared in-kernel posit bit math (Pallas-safe: no lax.clz — uses the
smear+popcount idiom, which lowers to TPU vector ops), the common
tile-padding helper the arbitrary-shape kernel wrappers use, and the one
rule for when a kernel runs in the Pallas interpreter."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.formats import PositFormat


def interpret_mode() -> bool:
    """Kernels run in the Pallas interpreter on the CPU backend only.

    Every other backend compiles them: a host whose accelerator did not
    come up fails loudly at the first kernel instead of carrying on in the
    interpreter.
    """
    return jax.default_backend() == "cpu"


def pad_to_tiles(x, block_rows: int = 512):
    """Flatten to (rows, 128) tiles whose row count the block size divides.

    Row counts below ``block_rows`` round up to the f32 sublane multiple
    (8) and become the block themselves; larger ones round up to a whole
    number of ``block_rows`` blocks, so the kernels' grid assertions
    always hold.  Returns ``(tiles, n, bm)`` — the padded (rows, 128)
    plane, the original element count, and the block size to launch with.
    """
    flat = x.reshape(-1)
    n = flat.shape[0]
    rows = -(-n // 128)
    if rows >= block_rows:
        rows_p, bm = -(-rows // block_rows) * block_rows, block_rows
    else:
        rows_p = bm = -(-rows // 8) * 8
    pad = rows_p * 128 - n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(rows_p, 128), n, bm


def _i32(v: int):
    """A 32-bit pattern as an int32 constant (two's-complement wrap)."""
    v &= 0xFFFFFFFF
    return jnp.int32(v - (1 << 32) if v >= 1 << 31 else v)


def _srl(x, s):
    """Logical right shift of int32 (the unsigned ``>>``)."""
    return lax.shift_right_logical(x, jnp.asarray(s, jnp.int32))


def clz31(x):
    """Leading zeros of a non-negative int32 below bit 31 (clz31(0) = 31),
    via bit-smear + population count."""
    x = x | (x >> 1)
    x = x | (x >> 2)
    x = x | (x >> 4)
    x = x | (x >> 8)
    x = x | (x >> 16)
    return 31 - lax.population_count(x)


def decode_tile(bits, fmt: PositFormat, dtype=jnp.float32):
    """Decode a tile of posit patterns (same values as core.posit.decode,
    written without lax.clz so it lowers inside pallas_call).

    All integer work is signed int32: the n-1 bits below the sign are
    aligned under bit 30 of a 31-bit word, so every intermediate is a
    non-negative int32 and the fraction converts to float as a signed
    integer (Mosaic has no unsigned-to-float cast on the TPU).
    """
    n, es = fmt.n, fmt.es
    word = 0x7FFFFFFF
    x = bits.astype(jnp.int32)
    if n < 32:
        x = x & jnp.int32(fmt.mask)

    sign = _srl(x, n - 1) & 1
    is_zero = x == 0
    is_nar = x == _i32(fmt.nar_pattern)

    mag = jnp.where(sign == 1, (-x) & _i32(fmt.mask), x)
    y = (mag << (32 - n)) & word                 # regime starts at bit 30

    r0 = y >> 30
    inv = jnp.where(r0 == 1, y ^ word, y)
    k = jnp.minimum(clz31(inv), n - 1)
    r = jnp.where(r0 == 0, -k, k - 1)

    z = jnp.where(k + 1 >= 31, 0, (y << jnp.minimum(k + 1, 30)) & word)
    if es > 0:
        e = z >> (31 - es)
        frac_top = (z << es) & word
    else:
        e = jnp.zeros_like(k)
        frac_top = z

    scale = r * (1 << es) + e
    f = frac_top.astype(jnp.float32) * jnp.float32(2.0 ** -31)
    pw = lax.bitcast_convert_type(
        (jnp.clip(scale, -126, 127) + 127) << 23, jnp.float32)
    val = (jnp.float32(1.0) + f) * pw
    val = jnp.where(sign == 1, -val, val)
    val = jnp.where(is_zero, jnp.float32(0.0), val)
    val = jnp.where(is_nar, jnp.float32(jnp.nan), val)
    return val.astype(dtype)


def encode_tile(v, fmt: PositFormat):
    """Encode a float32 tile to posit patterns (RNE, saturating).

    The arithmetic is core.posit.encode's, on int32 bit patterns: add,
    shift left, and, or are the same bits signed or unsigned, and the
    only right shift of a value that can reach bit 31 (the regime of a
    32-bit posit) is logical.
    """
    n, es = fmt.n, fmt.es
    I = jnp.int32
    mbits = 23
    TBITS = es + mbits

    v = v.astype(jnp.float32)
    vbits = lax.bitcast_convert_type(v, I)
    sign = (vbits < 0) & (v != 0)
    is_zero = v == 0
    is_nar = ~jnp.isfinite(v)

    a = jnp.clip(jnp.abs(v), fmt.minpos, fmt.maxpos)
    abits = lax.bitcast_convert_type(a, I)
    biased = (abits >> mbits) & 0xFF
    man = abits & ((1 << mbits) - 1)
    q = biased - 127

    r = q >> es
    e = q - (r << es)
    r_pos = jnp.maximum(r, 0)
    R = jnp.where(r >= 0, ((I(1) << (r_pos + 1)) - 1) << 1, I(1))
    nR = jnp.where(r >= 0, r + 2, 1 - r)

    T = (e << mbits) | man
    shift = nR + TBITS - (n - 1)

    sh_p = jnp.clip(shift, 1, TBITS)
    body_p = (R << (TBITS - sh_p)) | (T >> sh_p)
    g_p = (T >> (sh_p - 1)) & 1
    st_p = ((T & ((I(1) << (sh_p - 1)) - 1)) != 0).astype(I)

    sh_n = jnp.clip(-shift, 0, 31)
    body_n = (R << jnp.clip(TBITS - shift, 0, 63)) | (T << sh_n)

    sh_t = jnp.clip(shift - TBITS, 0, 31)
    body_t = _srl(R, sh_t)

    body = jnp.where(shift <= 0, body_n,
                     jnp.where(shift <= TBITS, body_p, body_t))
    in_p = (shift >= 1) & (shift <= TBITS)
    g = jnp.where(in_p, g_p, 0)
    st = jnp.where(in_p, st_p, 0)

    # RNE; the selected body is below 2^(n-1), so the signed clamp is the
    # unsigned one
    body = body + (g & (st | (body & 1)))
    body = jnp.clip(body, fmt.minpos_pattern, fmt.maxpos_pattern)

    pattern = jnp.where(sign, (-body) & _i32(fmt.mask), body)
    pattern = jnp.where(is_zero, 0, pattern)
    pattern = jnp.where(is_nar, _i32(fmt.nar_pattern), pattern)
    return pattern.astype(fmt.storage_dtype)
