"""Pallas kernels vs pure-jnp oracles: shape/dtype/format sweeps in
interpret mode (kernel bodies execute in Python on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.formats import POSIT8, POSIT16, POSIT_FORMATS, PositFormat
from repro.kernels import ops, ref

FMTS = [POSIT8, POSIT16, PositFormat(12, 2)]


@pytest.mark.parametrize("fmt", FMTS, ids=lambda f: f.name)
@pytest.mark.parametrize("shape", [(8, 128), (16, 256), (512,), (3, 5, 7)])
def test_decode_kernel_matches_ref(fmt, shape):
    rng = np.random.default_rng(0)
    bits = rng.integers(0, 1 << fmt.n, size=shape)
    bits = jnp.asarray(bits.astype(np.int32)).astype(fmt.storage_dtype)
    got = ops.decode(bits, fmt)
    want = ref.decode_ref(bits, fmt)
    np.testing.assert_array_equal(np.nan_to_num(np.asarray(got), nan=7.0),
                                  np.nan_to_num(np.asarray(want), nan=7.0))


@pytest.mark.parametrize("fmt", FMTS, ids=lambda f: f.name)
@pytest.mark.parametrize("shape", [(8, 128), (64, 128), (1000,)])
def test_encode_kernel_matches_ref(fmt, shape):
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=shape) * 10.0, jnp.float32)
    got = ops.encode(x, fmt)
    want = ref.encode_ref(x, fmt)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("fmt", [POSIT16, POSIT8], ids=lambda f: f.name)
@pytest.mark.parametrize("mnk", [(128, 128, 128), (256, 128, 256)])
def test_matmul_kernel_matches_ref(fmt, mnk):
    M, N, K = mnk
    rng = np.random.default_rng(2)
    # realistic magnitudes (weights/activations), not raw extreme patterns —
    # the ±2^56 corner values make any accumulation-order difference blow
    # past float tolerance (decode/encode kernels cover raw patterns).
    a_bits = ref.encode_ref(jnp.asarray(rng.normal(size=(M, K)), jnp.float32),
                            fmt)
    b_bits = ref.encode_ref(
        jnp.asarray(rng.normal(size=(K, N)) / np.sqrt(K), jnp.float32), fmt)
    got = ops.matmul(a_bits, b_bits, fmt, bm=128, bn=128, bk=128)
    want = ref.matmul_ref(a_bits, b_bits, fmt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("fmt", [POSIT16, POSIT8], ids=lambda f: f.name)
def test_kv_attention_kernel_matches_ref(fmt):
    G, D, S = 4, 128, 1024
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(G, D)), jnp.float32)
    kv = rng.normal(size=(2, S, D)).astype(np.float32)
    k_bits = ref.encode_ref(jnp.asarray(kv[0]), fmt)
    v_bits = ref.encode_ref(jnp.asarray(kv[1]), fmt)
    length = jnp.asarray(S - 100, jnp.int32)
    from repro.kernels.posit_kv_attention import posit_kv_attention
    got = posit_kv_attention(q, k_bits, v_bits, length, fmt, bs=256,
                             interpret=True)
    want = ref.kv_attention_ref(q, k_bits, v_bits, length, fmt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_batched_kv_attention_wrapper():
    fmt = POSIT16
    B, KV, G, D, S = 2, 2, 3, 128, 512
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.normal(size=(B, KV, G, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, KV, S, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, KV, S, D)), jnp.float32)
    k_bits, v_bits = ref.encode_ref(k, fmt), ref.encode_ref(v, fmt)
    out = ops.kv_attention(q, k_bits, v_bits, S, fmt, bs=256)
    assert out.shape == (B, KV, G, D)
    for b in range(B):
        for h in range(KV):
            want = ref.kv_attention_ref(q[b, h], k_bits[b, h],
                                        v_bits[b, h],
                                        jnp.asarray(S), fmt)
            np.testing.assert_allclose(np.asarray(out[b, h]),
                                       np.asarray(want), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Fused KV attention ≡ its oracle, BITWISE.  The oracle mirrors the kernel's
# block schedule and is itself jitted (so both realizations get the same XLA
# fusion freedom — eager evaluation drifts by 1 ulp across the block-carry
# FMA); with that, fused and oracle agree to the last mantissa bit on CPU
# interpret mode for every registered posit format.
# ---------------------------------------------------------------------------
def _kv_case(fmt, S, seed):
    rng = np.random.default_rng(seed)
    G, D = 4, 64
    q = jnp.asarray(rng.normal(size=(G, D)), jnp.float32)
    k_bits = ref.encode_ref(
        jnp.asarray(rng.normal(size=(S, D)), jnp.float32), fmt)
    v_bits = ref.encode_ref(
        jnp.asarray(rng.normal(size=(S, D)), jnp.float32), fmt)
    return q, k_bits, v_bits


@pytest.mark.parametrize("fmt_name", sorted(POSIT_FORMATS))
@pytest.mark.parametrize("S,bs", [(700, 256), (512, 512), (96, 256)],
                         ids=["ragged-blocks", "exact", "sub-block"])
def test_kv_attention_bitwise_matches_oracle(fmt_name, S, bs):
    from repro.core.formats import get_format
    from repro.kernels.posit_kv_attention import posit_kv_attention

    fmt = get_format(fmt_name)
    q, k_bits, v_bits = _kv_case(fmt, S, seed=5)
    length = jnp.asarray(S - S // 7, jnp.int32)
    got = posit_kv_attention(q, k_bits, v_bits, length, fmt, bs=bs,
                             interpret=True)
    want = ref.kv_attention_oracle(q, k_bits, v_bits, length, fmt, bs=bs)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_kv_attention_zero_length_and_zero_seq():
    """length==0 → zero weights (not a uniform average over garbage);
    S==0 → zero output without launching a kernel."""
    from repro.kernels.posit_kv_attention import posit_kv_attention

    fmt = POSIT16
    q, k_bits, v_bits = _kv_case(fmt, 64, seed=6)
    got = posit_kv_attention(q, k_bits, v_bits, jnp.asarray(0, jnp.int32),
                             fmt, bs=64, interpret=True)
    want = ref.kv_attention_oracle(q, k_bits, v_bits,
                                   jnp.asarray(0, jnp.int32), fmt, bs=64)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not np.isnan(np.asarray(got)).any()

    empty_k = k_bits[:0]
    out = posit_kv_attention(q, empty_k, empty_k, jnp.asarray(0, jnp.int32),
                             fmt, bs=64, interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.zeros(q.shape, np.float32))
    np.testing.assert_array_equal(
        np.asarray(ref.kv_attention_ref(q, empty_k, empty_k,
                                        jnp.asarray(0, jnp.int32), fmt)),
        np.zeros(q.shape, np.float32))


def test_batched_kv_attention_per_row_lengths():
    """The serving wrapper takes (B,) per-row lengths: each row must match
    the single-head reference at ITS OWN length."""
    fmt = POSIT8
    B, KV, G, D, S = 3, 2, 2, 64, 256
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(B, KV, G, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, KV, S, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, KV, S, D)), jnp.float32)
    k_bits, v_bits = ref.encode_ref(k, fmt), ref.encode_ref(v, fmt)
    lengths = jnp.asarray([256, 97, 5], jnp.int32)
    out = ops.kv_attention(q, k_bits, v_bits, lengths, fmt, bs=128)
    for b in range(B):
        for h in range(KV):
            want = ref.kv_attention_ref(q[b, h], k_bits[b, h],
                                        v_bits[b, h], lengths[b], fmt)
            np.testing.assert_allclose(np.asarray(out[b, h]),
                                       np.asarray(want),
                                       rtol=2e-5, atol=2e-5)
