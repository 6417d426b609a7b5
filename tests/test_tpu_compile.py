"""Compile the main path's Pallas kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler that ships with libtpu compiles for a chip
that is described, not attached, and refuses what Mosaic cannot lower —
unsigned compares and casts, unaligned block shapes, too much VMEM —
which interpret mode on the CPU never sees.  Each test asserts that the
compiled program holds the kernel (``tpu_custom_call``).

The topology is described inside a module fixture (never at import:
only one process at a time may load the TPU library), and the tests skip
where it cannot be described.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.formats import get_format
from repro.kernels.posit_decode import posit_decode_2d
from repro.kernels.posit_encode import posit_encode_2d
from repro.kernels.posit_kv_attention import posit_kv_attention_batched
from repro.kernels.posit_matmul import rounded_matmul
from repro.kernels.posit_round import posit_butterfly_2d, posit_round_2d

BATCH = 64          # windows per stream-engine dispatch (the smoke's)


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("fmt", ["posit8", "posit10", "posit16"])
def test_round_kernel_compiles(one_chip, fmt):
    x = _spec(one_chip, (512, 128), jnp.float32)
    fn = functools.partial(posit_round_2d, fmt=get_format(fmt))
    assert "tpu_custom_call" in _compiled_text(fn, x)


def test_butterfly_kernel_compiles(one_chip):
    plane = _spec(one_chip, (512, 128), jnp.float32)
    fmt = get_format("posit16")
    text = _compiled_text(lambda *a: posit_butterfly_2d(*a, fmt), *[plane] * 6)
    assert "tpu_custom_call" in text


def test_rounded_matmul_compiles_at_mel_width(one_chip):
    """The mel filterbank product: a 2049-bin PSD row block times the
    (2049, 20) filterbank, K whole per tile."""
    a = _spec(one_chip, (2 * BATCH, 2049), jnp.float32)
    b = _spec(one_chip, (2049, 20), jnp.float32)
    fn = functools.partial(rounded_matmul, fmt=get_format("posit16"),
                           interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, a, b)


@pytest.mark.parametrize("fmt", ["posit8", "posit16"])
def test_decode_encode_kernels_compile(one_chip, fmt):
    f = get_format(fmt)
    bits = _spec(one_chip, (512, 128), f.storage_dtype)
    vals = _spec(one_chip, (512, 128), jnp.float32)
    assert "tpu_custom_call" in _compiled_text(
        functools.partial(posit_decode_2d, fmt=f), bits)
    assert "tpu_custom_call" in _compiled_text(
        functools.partial(posit_encode_2d, fmt=f), vals)


@pytest.mark.parametrize("fmt", ["posit8", "posit16"])
@pytest.mark.parametrize("S", [80, 1024], ids=["one-block", "two-blocks"])
def test_kv_attention_kernel_compiles(one_chip, fmt, S):
    """qwen3-8b's decode attention: 8 kv heads of 128, 4 query heads per
    group, a batch of 4 serving slots."""
    f = get_format(fmt)
    B, KV, G, D = 4, 8, 4, 128
    q = _spec(one_chip, (B, KV, G, D), jnp.float32)
    kv = _spec(one_chip, (B, KV, S, D), f.storage_dtype)
    lengths = _spec(one_chip, (B,), jnp.int32)
    fn = functools.partial(posit_kv_attention_batched, fmt=f)
    assert "tpu_custom_call" in _compiled_text(fn, q, kv, kv, lengths)


@pytest.fixture
def as_on_tpu(monkeypatch):
    """Steer the code that asks for the backend onto its TPU branch: round
    backend ``auto`` → pallas, and kernels compiled, not interpreted."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_cough_window_compiles(one_chip, as_on_tpu):
    from repro.apps.cough import train_reference_forest
    from repro.stream.pipelines import cough_pipeline

    forest = train_reference_forest(32, 123, n_trees=10, depth=5)
    fn = cough_pipeline(forest).make_fn("posit16")
    text = fn.lower({"audio": _spec(one_chip, (BATCH, 2, 4800), jnp.float32),
                     "imu": _spec(one_chip, (BATCH, 9, 30), jnp.float32)}
                    ).compile().as_text()
    assert "tpu_custom_call" in text


def test_rpeak_window_compiles(one_chip, as_on_tpu):
    from repro.stream.pipelines import rpeak_pipeline

    fn = rpeak_pipeline().make_fn("posit10")
    text = fn.lower({"ecg": _spec(one_chip, (BATCH, 1, 500), jnp.float32)}
                    ).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("fmt", ["posit8", "posit10"])
def test_rpeak_threshold_compiles(one_chip, as_on_tpu, fmt):
    """The trackers' batched 2-means: one fixed-shape program per format,
    its chain loop on the device, its posit rounds fused by XLA (no
    kernel call inside the loop)."""
    from repro.apps.bayeslope import (RESERVOIR_SIZE, THRESHOLD_ROWS,
                                      _threshold_program)

    R = THRESHOLD_ROWS
    text = _threshold_program(fmt, (
        _spec(one_chip, (R, RESERVOIR_SIZE), jnp.float32),
        _spec(one_chip, (R,), jnp.int32),
        _spec(one_chip, (R, 2), jnp.float32),
        _spec(one_chip, (R,), jnp.bool_),
        _spec(one_chip, (R,), jnp.int32),
        _spec(one_chip, (), jnp.int32))).as_text()
    assert " while(" in text and "tpu_custom_call" not in text
