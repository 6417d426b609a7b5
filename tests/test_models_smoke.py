"""Per-arch reduced-config smoke tests: one forward/train step on CPU,
asserting output shapes and no NaNs. Full configs are exercised only via the
dry-run (ShapeDtypeStruct, no allocation)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import CONFIGS, reduced
from repro.core.policy import QuantPolicy
from repro.distributed.sharding import MeshInfo
from repro.models import build_model

ARCHS = sorted(CONFIGS)


def tiny_minfo():
    from jax.sharding import AxisType
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    return MeshInfo(mesh, dp_axes=("data",))


def make_batch(cfg, B=2, S=32):
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab, size=(B, S)), jnp.int32)}
    if cfg.family == "vlm":
        batch["frontend"] = jnp.asarray(
            rng.normal(size=(B, cfg.frontend_len, cfg.d_model)), jnp.float32)
    if cfg.family == "encdec":
        batch["frames"] = jnp.asarray(
            rng.normal(size=(B, S, cfg.d_model)), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_smoke(arch):
    cfg = reduced(CONFIGS[arch])
    minfo = tiny_minfo()
    with minfo.mesh:
        model = build_model(cfg, minfo)
        params = model.init(jax.random.key(0))
        batch = make_batch(cfg)

        def loss_fn(p):
            loss, metrics = model.loss(p, batch)
            return loss

        loss, grads = jax.value_and_grad(loss_fn)(params)
        assert np.isfinite(float(loss)), (arch, float(loss))
        leaves = jax.tree_util.tree_leaves(grads)
        assert leaves
        for g in leaves:
            assert np.all(np.isfinite(np.asarray(g, dtype=np.float32)))


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_smoke(arch):
    cfg = reduced(CONFIGS[arch])
    minfo = tiny_minfo()
    B, S = 2, 16
    with minfo.mesh:
        model = build_model(cfg, minfo)
        params = model.init(jax.random.key(1))
        batch = make_batch(cfg, B=B, S=S)
        logits, cache = model.prefill(params, batch, capacity=S + 4)
        assert logits.shape == (B, 1, cfg.padded_vocab)
        assert np.all(np.isfinite(np.asarray(logits, np.float32)))
        tok = jnp.argmax(logits[:, -1, :cfg.vocab], axis=-1)[:, None]
        tok = tok.astype(jnp.int32)
        logits2, cache = model.decode_step(params, tok, cache)
        assert logits2.shape == (B, 1, cfg.padded_vocab)
        assert np.all(np.isfinite(np.asarray(logits2, np.float32)))


def test_posit_kv_cache_decode_matches_bf16():
    """posit16 KV cache should track the bf16 cache closely (paper's claim)."""
    cfg = reduced(CONFIGS["qwen3-8b"])
    minfo = tiny_minfo()
    B, S = 2, 16
    with minfo.mesh:
        m_plain = build_model(cfg, minfo, QuantPolicy())
        m_quant = build_model(cfg, minfo, QuantPolicy(kv_cache="posit16"))
        params = m_plain.init(jax.random.key(2))
        batch = make_batch(cfg, B=B, S=S)
        lp, cp = m_plain.prefill(params, batch, capacity=S + 2)
        lq, cq = m_quant.prefill(params, batch, capacity=S + 2)
        tok = jnp.argmax(lp[:, -1, :cfg.vocab], axis=-1)[:, None].astype(jnp.int32)
        lp2, _ = m_plain.decode_step(params, tok, cp)
        lq2, _ = m_quant.decode_step(params, tok, cq)
        np.testing.assert_allclose(
            np.asarray(lp2, np.float32), np.asarray(lq2, np.float32),
            atol=0.15, rtol=0.1)
