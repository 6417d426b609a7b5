"""Property and example tests for the vectorized posit codec vs exact oracle."""
import math
from fractions import Fraction

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    POSIT8,
    POSIT10,
    POSIT12,
    POSIT16,
    POSIT16E3,
    POSIT24,
    POSIT32,
    PositFormat,
    get_format,
)
from repro.core.posit import decode, encode, round_to_posit
from repro.core.posit_scalar import decode_scalar, encode_scalar

SMALL_FMTS = [POSIT8, POSIT10, POSIT12, POSIT16, POSIT16E3, PositFormat(6, 1)]
WIDE_FMTS = [POSIT24, POSIT32]


# ---------------------------------------------------------------------------
# Worked example from the paper (Fig. 2): 1001101000111000 ≡ -46.25 (posit16)
# ---------------------------------------------------------------------------
def test_paper_worked_example_decode():
    pat = 0b1001101000111000
    assert decode_scalar(pat, POSIT16) == Fraction(-185, 4)  # -46.25
    got = decode(jnp.array([pat], dtype=jnp.int32), POSIT16)
    np.testing.assert_allclose(np.asarray(got), [-46.25], rtol=0)


def test_paper_worked_example_encode():
    got = encode(jnp.array([-46.25], dtype=jnp.float32), POSIT16)
    assert (int(np.asarray(got)[0]) & POSIT16.mask) == 0b1001101000111000


def test_specials():
    for fmt in SMALL_FMTS:
        assert decode_scalar(0, fmt) == 0
        assert decode_scalar(fmt.nar_pattern, fmt) is None
        pats = jnp.array([0, fmt.nar_pattern], dtype=jnp.int32)
        vals = np.asarray(decode(pats, fmt))
        assert vals[0] == 0.0 and math.isnan(vals[1])
        enc = np.asarray(
            encode(jnp.array([0.0, np.nan, np.inf, -np.inf], jnp.float32), fmt)
        ).astype(np.int64) & fmt.mask
        assert enc[0] == 0
        assert all(p == fmt.nar_pattern for p in enc[1:])


def test_maxpos_minpos_saturation():
    for fmt in SMALL_FMTS:
        hi, lo = fmt.maxpos * 4.0, fmt.minpos / 4.0
        big = jnp.array([hi, -hi, lo, -lo], dtype=jnp.float32)
        pats = np.asarray(encode(big, fmt)).astype(np.int64) & fmt.mask
        assert pats[0] == fmt.maxpos_pattern
        assert pats[1] == ((~fmt.maxpos_pattern + 1) & fmt.mask)
        assert pats[2] == fmt.minpos_pattern
        assert pats[3] == ((~fmt.minpos_pattern + 1) & fmt.mask)


# ---------------------------------------------------------------------------
# Exhaustive decode agreement for every pattern of the small formats
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fmt", SMALL_FMTS, ids=lambda f: f.name)
def test_decode_exhaustive_vs_oracle(fmt):
    pats = np.arange(1 << fmt.n, dtype=np.int64)
    got = np.asarray(decode(jnp.asarray(pats, dtype=jnp.int32), fmt))
    for p in pats:
        ref = decode_scalar(int(p), fmt)
        if ref is None:
            assert math.isnan(got[p]), p
        else:
            assert got[p] == float(ref), (p, got[p], float(ref))


# ---------------------------------------------------------------------------
# Round-trip: encode(decode(p)) == p for every non-NaR pattern
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fmt", SMALL_FMTS, ids=lambda f: f.name)
def test_roundtrip_exhaustive(fmt):
    pats = np.arange(1 << fmt.n, dtype=np.int64)
    pats = pats[pats != fmt.nar_pattern]
    vals = decode(jnp.asarray(pats, dtype=jnp.int32), fmt)
    back = np.asarray(encode(vals, fmt)).astype(np.int64) & fmt.mask
    np.testing.assert_array_equal(back, pats)


def test_roundtrip_wide_formats_f64():
    with jax.enable_x64():
        for fmt in WIDE_FMTS:
            rng = np.random.default_rng(0)
            pats = rng.integers(0, 1 << fmt.n, size=20000, dtype=np.int64)
            pats = pats[pats != fmt.nar_pattern]
            vals = decode(jnp.asarray(pats, dtype=jnp.int32), fmt, dtype=jnp.float64)
            back = np.asarray(encode(vals, fmt)).astype(np.int64) & fmt.mask
            np.testing.assert_array_equal(back, pats)


# ---------------------------------------------------------------------------
# Property: encode matches the oracle's nearest-even choice for random floats
# ---------------------------------------------------------------------------
@settings(max_examples=300, deadline=None)
@given(
    st.floats(
        allow_nan=False,
        allow_infinity=False,
        allow_subnormal=False,
        width=32,
    ),
    st.sampled_from(range(len(SMALL_FMTS))),
)
def test_encode_matches_oracle(v, fmt_i):
    fmt = SMALL_FMTS[fmt_i]
    ref = encode_scalar(v, fmt)
    got = int(np.asarray(encode(jnp.array([v], jnp.float32), fmt))[0]) & fmt.mask
    assert got == ref, (v, fmt.name, bin(got), bin(ref))


@settings(max_examples=200, deadline=None)
@given(
    st.floats(
        min_value=-1e6,
        max_value=1e6,
        allow_nan=False,
        allow_subnormal=False,  # XLA CPU FTZ flushes subnormal inputs to 0
        width=32,
    ),
    st.sampled_from(range(len(SMALL_FMTS))),
)
def test_round_is_nearest(v, fmt_i):
    """round_to_posit must agree with the exact scalar oracle's rounding."""
    fmt = SMALL_FMTS[fmt_i]
    r = float(np.asarray(round_to_posit(jnp.array([v], jnp.float32), fmt))[0])
    ref = decode_scalar(encode_scalar(v, fmt), fmt)
    assert r == float(ref)


# ---------------------------------------------------------------------------
# Ordering property: posit patterns compare like 2's-complement ints
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fmt", [POSIT8, POSIT10], ids=lambda f: f.name)
def test_monotone_ordering(fmt):
    pats = np.arange(1 << fmt.n, dtype=np.int64)
    pats = pats[pats != fmt.nar_pattern]
    # reinterpret as signed n-bit ints and sort
    signed = np.where(pats >= (1 << (fmt.n - 1)), pats - (1 << fmt.n), pats)
    order = np.argsort(signed, kind="stable")
    vals = np.asarray(decode(jnp.asarray(pats[order], dtype=jnp.int32), fmt))
    assert np.all(np.diff(vals) > 0)


def test_decode_storage_dtypes():
    """int8/int16 storage sign-extension must not corrupt patterns."""
    fmt = POSIT8
    pats = np.arange(256, dtype=np.int64)
    as_i8 = jnp.asarray(pats.astype(np.int8))
    as_i32 = jnp.asarray(pats, dtype=jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(decode(as_i8, fmt)), np.asarray(decode(as_i32, fmt))
    )
    fmt16 = POSIT16
    pats16 = np.arange(0, 1 << 16, 7, dtype=np.int64)
    np.testing.assert_array_equal(
        np.asarray(decode(jnp.asarray(pats16.astype(np.int16)), fmt16)),
        np.asarray(decode(jnp.asarray(pats16, dtype=jnp.int32), fmt16)),
    )


def test_get_format_parsing():
    assert get_format("posit16").n == 16 and get_format("posit16").es == 2
    assert get_format("posit16e3").es == 3
    assert get_format("bfloat16").name == "bfloat16"
    with pytest.raises(KeyError):
        get_format("fp7")
