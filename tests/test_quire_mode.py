"""REPRO_QUIRE property suite: the live arithmetic's quire mode against the
scalar Fractions oracle (``quire_dot_exact``), bit for bit, for every
registered posit format — plus the mode plumbing (cache key, overrides),
the axis=None reduction regression, fused-path bit identity, and the
ledger's billing invariance under the orthogonal backend switches.

Bit-pattern comparisons mask with ``(1 << n) - 1``: storage dtypes are
signed, the oracle returns unsigned ints, and e.g. posit8's NaR prints as
-128 on one side and 128 on the other.
"""
import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.arith import Arith, backend_overrides, fusion_cache_key
from repro.core.formats import POSIT_FORMATS
from repro.core.posit import decode, encode
from repro.core.posit_scalar import encode_scalar
from repro.core.quire import qdot, quire_dot_exact
from repro.energy.model import OpCounts

# posit24/32 products need more than f32's 24 significand bits; their
# exactness contract is scoped to x64 mode (see core/quire.py docstring)
_WIDE = ("posit24", "posit32")


def _ctx(name):
    return jax.enable_x64() if name in _WIDE else contextlib.nullcontext()


def _dtype(name):
    return jnp.float64 if name in _WIDE else jnp.float32


def _rand_bits(rng, fmt, k):
    """Random posit bit patterns (NaR filtered out) in the storage dtype."""
    mask = (1 << fmt.n) - 1
    bits = rng.integers(0, 1 << fmt.n, size=k)
    bits[bits == fmt.nar_pattern] = 0
    return bits.astype(np.int64).astype(fmt.storage_dtype)


def _bits(x, fmt):
    return int(np.asarray(x)) & ((1 << fmt.n) - 1)


# ---------------------------------------------------------------------------
# Tentpole contract: quire-on Arith ≡ the scalar exact oracle, bit for bit
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", sorted(POSIT_FORMATS))
def test_quire_dot_bit_identity_vs_oracle(name):
    fmt = POSIT_FORMATS[name]
    mask = (1 << fmt.n) - 1
    rng = np.random.default_rng(hash(name) % (2 ** 31))
    with _ctx(name):
        dt = _dtype(name)
        ar = Arith.make(name)
        for k in (1, 2, 3, 17, 64, 201):
            a = _rand_bits(rng, fmt, k)
            b = _rand_bits(rng, fmt, k)
            want = quire_dot_exact(a, b, fmt) & mask
            got_qdot = _bits(qdot(a, b, fmt, out_format=fmt), fmt)
            assert got_qdot == want, (name, k)
            with backend_overrides(quire="on"):
                va = decode(jnp.asarray(a), fmt, dtype=dt)
                vb = decode(jnp.asarray(b), fmt, dtype=dt)
                got_ar = _bits(encode(ar.dot(va, vb), fmt), fmt)
            assert got_ar == want, (name, k)


@pytest.mark.parametrize("name", ["posit8", "posit16", "posit16e3"])
def test_quire_matmul_bit_identity_vs_oracle(name):
    fmt = POSIT_FORMATS[name]
    mask = (1 << fmt.n) - 1
    rng = np.random.default_rng(11)
    M, K, N = 5, 37, 4
    A = _rand_bits(rng, fmt, M * K).reshape(M, K)
    B = _rand_bits(rng, fmt, K * N).reshape(K, N)
    ar = Arith.make(name)
    with backend_overrides(quire="on"):
        va = decode(jnp.asarray(A), fmt)
        vb = decode(jnp.asarray(B), fmt)
        got = np.asarray(encode(ar.matmul(va, vb), fmt)).astype(np.int64)
    for i in range(M):
        for j in range(N):
            want = quire_dot_exact(A[i], B[:, j], fmt) & mask
            assert got[i, j] & mask == want, (name, i, j)


# ---------------------------------------------------------------------------
# Oracle pins: specials and cancellation, every format (satellite 3)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", sorted(POSIT_FORMATS))
def test_qdot_specials_match_oracle(name):
    fmt = POSIT_FORMATS[name]
    mask = (1 << fmt.n) - 1
    with _ctx(name):
        # NaR poisoning: one NaR operand → NaR out, oracle and qdot agree
        a = np.asarray([fmt.nar_pattern, 3], np.int64).astype(fmt.storage_dtype)
        b = np.asarray([1, 2], np.int64).astype(fmt.storage_dtype)
        assert quire_dot_exact(a, b, fmt) & mask == fmt.nar_pattern
        assert _bits(qdot(a, b, fmt, out_format=fmt), fmt) == fmt.nar_pattern
        # zero-length: exact 0
        e = np.zeros(0, fmt.storage_dtype)
        zero = encode_scalar(0, fmt) & mask
        assert quire_dot_exact(e, e, fmt) & mask == zero
        assert _bits(qdot(e, e, fmt, out_format=fmt), fmt) == zero
        # catastrophic cancellation: [x, eps, -x]·[1,1,1] must survive as
        # eps exactly (per-op rounding loses it — see divergence test)
        eps_bits = encode_scalar(2.0 ** -(fmt.max_fraction_bits + 2), fmt)
        one_bits = encode_scalar(1, fmt)
        x = np.asarray([one_bits, eps_bits, one_bits | (1 << fmt.n)],
                       np.int64).astype(fmt.storage_dtype)
        # negate the third entry: posit negation is two's complement
        x[2] = np.int64(-int(x[0])).astype(fmt.storage_dtype)
        ones = np.asarray([one_bits] * 3, np.int64).astype(fmt.storage_dtype)
        want = quire_dot_exact(x, ones, fmt) & mask
        assert want == eps_bits & mask
        assert _bits(qdot(x, ones, fmt, out_format=fmt), fmt) == want


# ---------------------------------------------------------------------------
# First-divergence sweep: where quire-on and quire-off part ways (satellite 5)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", sorted(POSIT_FORMATS))
def test_quire_on_off_first_divergence(name):
    """Prefix sweep of a drift vector: the on arm must equal the oracle at
    EVERY prefix length, and the off arm must diverge somewhere.  The
    quire-off posit sum is already a wide float accumulation rounded once,
    so the drift vector must overflow the ACCUMULATOR's significand (24
    bits in f32, 53 in f64), not merely the posit lattice: big + small is
    inexact in the accumulator, so the cancel against -big loses small on
    the off arm while the compensated on arm keeps it exactly."""
    fmt = POSIT_FORMATS[name]
    mask = (1 << fmt.n) - 1
    e = 30 if name in _WIDE else 13          # 2e > accumulator significand
    big, small = 2.0 ** e, 2.0 ** -e
    drift = [big, small, -big, small, big, -big]
    with _ctx(name):
        dt = _dtype(name)
        ar = Arith.make(name)
        vals = np.asarray([encode_scalar(v, fmt) for v in drift],
                          np.int64).astype(fmt.storage_dtype)
        first_div = None
        for k in range(len(drift) + 1):
            prefix = vals[:k]
            ones = np.asarray([encode_scalar(1, fmt)] * k,
                              np.int64).astype(fmt.storage_dtype)
            want = quire_dot_exact(prefix, ones, fmt) & mask
            va = decode(jnp.asarray(prefix), fmt, dtype=dt)
            with backend_overrides(quire="on"):
                on = _bits(encode(ar.sum(va), fmt), fmt)
            with backend_overrides(quire="off"):
                off = _bits(encode(ar.sum(va), fmt), fmt)
            assert on == want, (name, k)  # on arm never drifts
            if first_div is None and off != on:
                first_div = k
        # the whole point of the mode: per-op rounding diverges somewhere
        assert first_div is not None, name


# ---------------------------------------------------------------------------
# axis=None regression (satellite 2): used to crash the IEEE paths
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", ["posit16", "fp16", "bfloat16", "fp32"])
@pytest.mark.parametrize("quire", ["off", "on"])
def test_reductions_accept_axis_none(name, quire):
    rng = np.random.default_rng(3)
    ar = Arith.make(name)
    x = ar.rnd(jnp.asarray(rng.normal(size=(4, 6)).astype(np.float32)))
    with backend_overrides(quire=quire):
        flat = x.reshape(-1)
        s = ar.sum(x, axis=None)
        assert s.shape == ()
        np.testing.assert_array_equal(np.asarray(s),
                                      np.asarray(ar.sum(flat)))
        m = ar.mean(x, axis=None)
        np.testing.assert_array_equal(np.asarray(m),
                                      np.asarray(ar.mean(flat)))
        c = ar.cumsum(x, axis=None)
        assert c.shape == (x.size,)
        np.testing.assert_array_equal(np.asarray(c),
                                      np.asarray(ar.cumsum(flat)))


# ---------------------------------------------------------------------------
# Fused realization ≡ unfused under quire: same elementary ops, same bits
# ---------------------------------------------------------------------------
def test_fused_unfused_bit_identity_under_quire():
    from repro.apps.dsp import power_spectrum

    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=512).astype(np.float32))
    ar = Arith.make("posit16")
    outs = {}
    for fused in ("on", "off"):
        with backend_overrides(fused=fused, quire="on"):
            outs[fused] = np.asarray(power_spectrum(ar, ar.rnd(x)))
    np.testing.assert_array_equal(outs["on"], outs["off"])


# ---------------------------------------------------------------------------
# Mode plumbing: cache key, override restore (tentpole wiring)
# ---------------------------------------------------------------------------
def test_fusion_cache_key_carries_quire():
    base = fusion_cache_key()
    with backend_overrides(quire="on"):
        on = fusion_cache_key()
        assert on != base and on[2] is True
    assert fusion_cache_key() == base  # override restored


def test_quire_is_posit_only():
    with backend_overrides(quire="on"):
        assert Arith.make("posit16").quire
        assert not Arith.make("fp16").quire
        assert not Arith.make("fp32").quire


# ---------------------------------------------------------------------------
# Billing (satellites 1/5): quire pricing orthogonal to the other switches
# ---------------------------------------------------------------------------
def test_roundings_quire_arithmetic():
    ops = OpCounts(add=10, mul=6, div=1, conv=3, quire_mac=8, quire_round=2)
    assert ops.roundings() == ops.total() == 20
    assert ops.roundings(quire=True) == 20 - 8 + 2


def test_window_nj_invariant_under_fused_and_round_backend():
    """With quire ON, nJ/window must not move when the realization switches
    (fused kernels, rounding backend) — only the quire switch itself may
    change the bill."""
    from repro.stream.accounting import window_energy_nj
    from repro.stream.pipelines import rpeak_pipeline

    ops = rpeak_pipeline().ops_per_window
    bills = []
    for fused in ("on", "off"):
        for rb in ("jnp", "codec"):
            with backend_overrides(fused=fused, round_backend=rb,
                                   quire="on"):
                bills.append(window_energy_nj(ops, "posit8"))
    assert len(set(bills)) == 1
    with backend_overrides(quire="off"):
        off_bill = window_energy_nj(ops, "posit8")
    assert off_bill != bills[0]


def test_ledger_bills_live_quire_switch():
    """window_energy_nj(quire=None) reads the live REPRO_QUIRE switch, and
    IEEE windows price identically in both modes (no quire on the FPU)."""
    from repro.stream.accounting import cough_window_op_counts, window_energy_nj

    ops = cough_window_op_counts()
    with backend_overrides(quire="on"):
        assert window_energy_nj(ops, "posit16") == \
            window_energy_nj(ops, "posit16", quire=True)
        assert window_energy_nj(ops, "fp16") == \
            window_energy_nj(ops, "fp16", quire=False)
    with backend_overrides(quire="off"):
        assert window_energy_nj(ops, "posit16") == \
            window_energy_nj(ops, "posit16", quire=False)
