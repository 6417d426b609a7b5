"""Bit-identity of the direct posit rounding fast path vs the codec oracle,
the fused Pallas round kernels, the FFT-plan/rfft restructure, and the O(1)
engine bucket math."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import POSIT8, POSIT10, POSIT16, POSIT24, POSIT32, PositFormat
from repro.core.arith import Arith, get_round_backend, set_round_backend
from repro.core.posit import decode, round_to_posit, round_to_posit_codec

SMALL_FMTS = [POSIT8, POSIT10, POSIT16, PositFormat(16, 3), PositFormat(6, 1),
              PositFormat(10, 0)]


def _bits32(a):
    return np.asarray(a, np.float32).view(np.uint32)


def _assert_bit_identical(fmt, x):
    d = round_to_posit(x, fmt)
    c = round_to_posit_codec(x, fmt)
    np.testing.assert_array_equal(_bits32(d), _bits32(c))


# ---------------------------------------------------------------------------
# Exhaustive: every posit16 pattern, every adjacent-lattice midpoint
# ---------------------------------------------------------------------------
def test_direct_round_exhaustive_posit16_lattice():
    pats = np.arange(1 << 16, dtype=np.int64)
    vals = decode(jnp.asarray(pats, jnp.int32), POSIT16)
    _assert_bit_identical(POSIT16, vals)
    # lattice points round to themselves (idempotency)
    keep = ~np.isnan(np.asarray(vals))
    np.testing.assert_array_equal(
        np.asarray(round_to_posit(vals, POSIT16))[keep],
        np.asarray(vals)[keep])


def test_direct_round_exhaustive_posit16_midpoints():
    """Ties between every pair of adjacent posit16 values (exact in f32:
    adjacent posits share a scale or straddle a power of two, so the
    midpoint needs ≤ 15 significand bits)."""
    pats = np.arange(1 << 16, dtype=np.int64)
    v = np.sort(np.asarray(decode(jnp.asarray(pats, jnp.int32), POSIT16),
                           np.float64))
    v = v[~np.isnan(v)]
    mids = ((v[:-1] + v[1:]) / 2).astype(np.float32)
    _assert_bit_identical(POSIT16, jnp.asarray(mids))


@pytest.mark.parametrize("fmt", SMALL_FMTS, ids=lambda f: f.name)
def test_direct_round_exhaustive_small_lattice(fmt):
    pats = np.arange(1 << fmt.n, dtype=np.int64)
    vals = decode(jnp.asarray(pats, jnp.int32), fmt)
    _assert_bit_identical(fmt, vals)


# ---------------------------------------------------------------------------
# Sampled float grids: small + wide formats, f32 and f64 datapaths
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fmt", SMALL_FMTS + [POSIT24, POSIT32],
                         ids=lambda f: f.name)
def test_direct_round_sampled_grid_f32(fmt):
    rng = np.random.default_rng(0)
    x = np.concatenate([
        np.exp(rng.uniform(-88, 88, 100000)).astype(np.float32)
        * rng.choice([-1.0, 1.0], 100000).astype(np.float32),
        rng.normal(0, 1e3, 50000).astype(np.float32),
        # subnormal band: FTZ backends flush these to zero in both paths,
        # non-FTZ backends saturate both to ±minpos
        (rng.uniform(-1, 1, 20000) * 1e-38).astype(np.float32),
        np.array([0.0, -0.0, np.nan, np.inf, -np.inf, 1e-45, -1e-45,
                  np.finfo(np.float32).max, np.finfo(np.float32).tiny],
                 np.float32)])
    _assert_bit_identical(fmt, jnp.asarray(x))


@pytest.mark.parametrize("fmt", [POSIT16, POSIT24, POSIT32],
                         ids=lambda f: f.name)
def test_direct_round_sampled_grid_f64(fmt):
    with jax.enable_x64():
        rng = np.random.default_rng(1)
        pats = rng.integers(0, 1 << fmt.n, size=50000, dtype=np.int64)
        lattice = np.asarray(decode(jnp.asarray(pats, jnp.int32), fmt,
                                    dtype=jnp.float64), np.float64)
        x = np.concatenate([
            lattice,
            np.exp(rng.uniform(-200, 200, 100000))
            * rng.choice([-1.0, 1.0], 100000),
            np.array([0.0, -0.0, np.nan, np.inf, -np.inf, 1e308, 5e-324])])
        xj = jnp.asarray(x, jnp.float64)
        d = np.asarray(round_to_posit(xj, fmt), np.float64).view(np.uint64)
        c = np.asarray(round_to_posit_codec(xj, fmt),
                       np.float64).view(np.uint64)
        np.testing.assert_array_equal(d, c)


# ---------------------------------------------------------------------------
# Property tests: NaR / saturation edges
# ---------------------------------------------------------------------------
@settings(max_examples=300, deadline=None)
@given(st.floats(allow_nan=False, allow_infinity=False,
                 allow_subnormal=False, width=32),
       st.sampled_from(range(len(SMALL_FMTS))))
def test_direct_round_matches_codec_property(v, fmt_i):
    fmt = SMALL_FMTS[fmt_i]
    _assert_bit_identical(fmt, jnp.array([v], jnp.float32))


@settings(max_examples=100, deadline=None)
@given(st.sampled_from(range(len(SMALL_FMTS))))
def test_direct_round_nar_and_saturation(fmt_i):
    fmt = SMALL_FMTS[fmt_i]
    x = jnp.array([np.nan, np.inf, -np.inf,
                   fmt.maxpos * 8, -fmt.maxpos * 8,
                   fmt.minpos / 8, -fmt.minpos / 8, 0.0, -0.0], jnp.float32)
    got = np.asarray(round_to_posit(x, fmt))
    assert np.isnan(got[:3]).all()            # NaR → NaN, never saturates
    assert got[3] == fmt.maxpos and got[4] == -fmt.maxpos
    assert got[5] == fmt.minpos and got[6] == -fmt.minpos  # never → 0
    assert got[7] == 0.0 and got[8] == 0.0 and not np.signbit(got[7:]).any()


# ---------------------------------------------------------------------------
# Arith dispatch backends agree
# ---------------------------------------------------------------------------
def test_arith_round_backend_switch():
    ar = Arith.make("posit16")
    x = jnp.asarray(np.random.default_rng(3).normal(0, 50, 4096)
                    .astype(np.float32))
    outs = {}
    assert get_round_backend() in ("jnp", "pallas")
    for backend in ("jnp", "codec", "pallas"):
        set_round_backend(backend)
        try:
            outs[backend] = np.asarray(ar.rnd(x))
        finally:
            set_round_backend("auto")
    np.testing.assert_array_equal(_bits32(outs["jnp"]), _bits32(outs["codec"]))
    np.testing.assert_array_equal(_bits32(outs["jnp"]),
                                  _bits32(outs["pallas"]))


# ---------------------------------------------------------------------------
# Pallas fused kernels (interpret mode on CPU) vs the jnp fast path
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fmt", [POSIT8, POSIT16], ids=lambda f: f.name)
def test_pallas_round_kernel_matches(fmt):
    from repro.kernels.posit_round import posit_round
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(0, 1e4, (5, 7, 99)).astype(np.float32))
    np.testing.assert_array_equal(
        _bits32(posit_round(x, fmt)),
        _bits32(round_to_posit(x, fmt)))


def test_pallas_round_kernel_large_nondivisible_shape():
    """PSD-sized tensors pad to >512 tile rows that 512 does not divide —
    the block size must adapt so the grid assertions hold."""
    from repro.kernels.posit_round import posit_round
    rng = np.random.default_rng(10)
    x = jnp.asarray(rng.normal(0, 50, (32, 2, 2049)).astype(np.float32))
    np.testing.assert_array_equal(
        _bits32(posit_round(x, POSIT16)),
        _bits32(round_to_posit(x, POSIT16)))


def test_pallas_fma_round_kernel_matches():
    from repro.kernels.posit_round import posit_fma_round
    rng = np.random.default_rng(5)
    a = jnp.asarray(rng.normal(0, 30, (33, 130)).astype(np.float32))
    b = jnp.asarray(rng.normal(0, 30, (33, 130)).astype(np.float32))
    c = jnp.asarray(rng.normal(0, 30, (33, 130)).astype(np.float32))
    np.testing.assert_array_equal(
        _bits32(posit_fma_round(a, b, c, POSIT16)),
        _bits32(round_to_posit(a * b + c, POSIT16)))


def test_pallas_butterfly_kernel_matches_arith_ops():
    from repro.kernels.posit_round import posit_butterfly_2d
    ar = Arith.make("posit16")
    rng = np.random.default_rng(6)
    mk = lambda: jnp.asarray(rng.normal(0, 100, (8, 128)).astype(np.float32))
    e_re, e_im, o_re, o_im, w_re, w_im = (mk() for _ in range(6))
    u_re, u_im, v_re, v_im = posit_butterfly_2d(
        e_re, e_im, o_re, o_im, w_re, w_im, POSIT16, interpret=True)
    t_re = ar.sub(ar.mul(w_re, o_re), ar.mul(w_im, o_im))
    t_im = ar.add(ar.mul(w_re, o_im), ar.mul(w_im, o_re))
    np.testing.assert_array_equal(_bits32(u_re), _bits32(ar.add(e_re, t_re)))
    np.testing.assert_array_equal(_bits32(u_im), _bits32(ar.add(e_im, t_im)))
    np.testing.assert_array_equal(_bits32(v_re), _bits32(ar.sub(e_re, t_re)))
    np.testing.assert_array_equal(_bits32(v_im), _bits32(ar.sub(e_im, t_im)))


# ---------------------------------------------------------------------------
# FFT plan / rfft split: bit-identical to the naive all-ops reference
# ---------------------------------------------------------------------------
def _fft_reference(ar, re, im):
    """The pre-plan implementation, verbatim: per-call tables, full
    butterflies at every stage, concatenate joins."""
    n = re.shape[-1]
    levels = int(np.log2(n))
    rev = np.zeros(n, dtype=np.int64)
    for i in range(n):
        b, x = 0, i
        for _ in range(levels):
            b = (b << 1) | (x & 1)
            x >>= 1
        rev[i] = b
    re = ar.rnd(re[..., rev])
    im = ar.rnd(im[..., rev])
    for s in range(1, levels + 1):
        m = 1 << s
        half = m // 2
        ang = -2.0 * np.pi * np.arange(half) / m
        wr = ar.rnd(jnp.asarray(np.cos(ang), re.dtype))
        wi = ar.rnd(jnp.asarray(np.sin(ang), re.dtype))
        x_re = re.reshape(*re.shape[:-1], n // m, m)
        x_im = im.reshape(*im.shape[:-1], n // m, m)
        e_re, o_re = x_re[..., :half], x_re[..., half:]
        e_im, o_im = x_im[..., :half], x_im[..., half:]
        t_re = ar.sub(ar.mul(wr, o_re), ar.mul(wi, o_im))
        t_im = ar.add(ar.mul(wr, o_im), ar.mul(wi, o_re))
        u_re = ar.add(e_re, t_re)
        u_im = ar.add(e_im, t_im)
        v_re = ar.sub(e_re, t_re)
        v_im = ar.sub(e_im, t_im)
        re = jnp.concatenate([u_re, v_re], axis=-1).reshape(*re.shape[:-1], n)
        im = jnp.concatenate([u_im, v_im], axis=-1).reshape(*im.shape[:-1], n)
    return re, im


def _assert_equal_nan(a, b):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("fmt", ["posit16", "posit8", "fp16", "fp32",
                                 "bfloat16", "posit32"])
@pytest.mark.parametrize("n", [8, 256])
def test_fft_plan_bit_identical_to_reference(fmt, n):
    from repro.apps.dsp import fft_format, rfft_format
    ar = Arith.make(fmt)
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(0, 3e3, (3, n)).astype(np.float32))
    y = jnp.asarray(rng.normal(0, 1, (3, n)).astype(np.float32))
    r0, i0 = _fft_reference(ar, x, y)
    r1, i1 = fft_format(ar, x, y)
    _assert_equal_nan(r0, r1)
    _assert_equal_nan(i0, i1)
    rr0, ii0 = _fft_reference(ar, x, jnp.zeros_like(x))
    rr1, ii1 = rfft_format(ar, x)
    _assert_equal_nan(np.asarray(rr0)[..., : n // 2 + 1], rr1)
    _assert_equal_nan(np.asarray(ii0)[..., : n // 2 + 1], ii1)


@pytest.mark.slow
def test_fft_plan_bit_identical_4096_posit16():
    from repro.apps.dsp import fft_format, rfft_format
    ar = Arith.make("posit16")
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.normal(0, 3e3, (2, 4096)).astype(np.float32))
    r0, i0 = _fft_reference(ar, x, jnp.zeros_like(x))
    r1, i1 = rfft_format(ar, x)
    _assert_equal_nan(np.asarray(r0)[..., :2049], r1)
    _assert_equal_nan(np.asarray(i0)[..., :2049], i1)
    r2, i2 = fft_format(ar, x, jnp.zeros_like(x))
    _assert_equal_nan(r0, r2)
    _assert_equal_nan(i0, i2)


def test_spectral_rolloff_format_parity():
    """Rolloff threshold math must run in the target arithmetic: for a
    coarse format the rounded prefix-sum/threshold pair can pick a
    different (correct-in-format) bin than unrounded fp32 math."""
    from repro.apps.dsp import spectral_features
    rng = np.random.default_rng(9)
    psd = jnp.asarray(rng.uniform(0.1, 1.0, (4, 129)).astype(np.float32))
    ar8 = Arith.make("posit8")
    feats = np.asarray(spectral_features(ar8, ar8.rnd(psd), 16000.0))
    # the rolloff feature is one of the tabulated frequencies and the
    # rounded cumulative energy at that bin crosses the rounded threshold
    freqs = np.linspace(0, 8000.0, 129).astype(np.float32)
    cum = np.asarray(ar8.cumsum(ar8.rnd(psd), axis=-1))
    thr = np.asarray(ar8.mul(ar8.rnd(jnp.asarray(0.85, jnp.float32)),
                             jnp.asarray(cum[..., -1:])))
    expect = freqs[np.argmax(cum >= thr, axis=-1)]
    np.testing.assert_array_equal(feats[:, 1], expect)
    # fp32 path is unchanged by the parity fix
    ar32 = Arith.make("fp32")
    f32 = np.asarray(spectral_features(ar32, psd, 16000.0))
    cum32 = np.cumsum(np.asarray(psd), axis=-1)
    expect32 = freqs[np.argmax(cum32 >= 0.85 * cum32[..., -1:], axis=-1)]
    np.testing.assert_array_equal(f32[:, 1], expect32)


# ---------------------------------------------------------------------------
# Engine bucket math
# ---------------------------------------------------------------------------
def test_bucket_size_exhaustive_vs_loop_reference():
    from repro.stream import bucket_size

    def ref(n, max_batch):
        b = 1
        while b < n and b < max_batch:
            b *= 2
        return min(b, max_batch)

    for max_batch in (1, 2, 3, 7, 8, 32, 48, 64, 100, 128):
        for n in range(0, 300):
            assert bucket_size(n, max_batch) == ref(n, max_batch), \
                (n, max_batch)
