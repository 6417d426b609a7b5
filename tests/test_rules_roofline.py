"""Sharding rules, roofline parsing, and arith-vs-oracle property coverage."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import POSIT16
from repro.core.arith import Arith
from repro.core.posit_scalar import decode_scalar, encode_scalar
from repro.distributed.rules import (_first_fit_cache_spec, _leaf_spec,
                                     params_shardings, zero1_shardings)
from repro.distributed.sharding import MeshInfo
from repro.roofline.analysis import collective_bytes, roofline_terms


def minfo_2x4():
    # AbstractMesh: spec-level tests need axis sizes, not 8 real devices
    from jax.sharding import AbstractMesh
    mesh = AbstractMesh((2, 4), ("data", "model"))
    return MeshInfo(mesh, dp_axes=("data",))


# -- sharding rules ----------------------------------------------------------
def test_leaf_spec_conventions():
    mi = minfo_2x4()
    # column-parallel weight shards last dim
    assert _leaf_spec(["layers", "attn", "wq", "w"], (8, 16), mi) == \
        jax.sharding.PartitionSpec(None, "model")
    # row-parallel shards dim -2
    assert _leaf_spec(["layers", "ffn", "w_down", "w"], (16, 8), mi) == \
        jax.sharding.PartitionSpec("model", None)
    # embed table shards vocab
    assert _leaf_spec(["embed", "table"], (128, 8), mi) == \
        jax.sharding.PartitionSpec("model", None)
    # MoE expert dim
    assert _leaf_spec(["layers", "moe", "w_gate"], (4, 8, 8, 16), mi) == \
        jax.sharding.PartitionSpec(None, "model", None, None)
    # non-divisible → replicate, loudly not wrongly
    assert _leaf_spec(["layers", "attn", "wq", "w"], (8, 10), mi) == \
        jax.sharding.PartitionSpec()
    # norms replicate
    assert _leaf_spec(["layers", "ln1"], (8,), mi) == \
        jax.sharding.PartitionSpec()


def test_cache_spec_never_tp_on_sequence():
    """§Perf iteration 1 regression guard."""
    mi = minfo_2x4()
    # (B, S, KV, D): tp must land on D (last divisible), dp on B
    spec = _first_fit_cache_spec((8, 64, 2, 16), mi)
    assert spec == jax.sharding.PartitionSpec("data", None, None, "model")
    # batch=1 long-context: dp falls to the sequence dim
    spec = _first_fit_cache_spec((1, 64, 2, 16), mi)
    assert spec[1] == "data" and spec[3] == "model"


def test_zero1_adds_data_axis():
    mi = minfo_2x4()
    params = {"layers": {"ffn": {"w_up": {"w": jnp.zeros((8, 16))}}}}
    base = params_shardings(mi, params)["layers"]["ffn"]["w_up"]["w"]
    z1 = zero1_shardings(mi, params)["layers"]["ffn"]["w_up"]["w"]
    assert base.spec == jax.sharding.PartitionSpec(None, "model")
    assert z1.spec == jax.sharding.PartitionSpec("data", "model")


# -- roofline parsing ---------------------------------------------------------
def test_collective_bytes_parser():
    hlo = """
      %ar = f32[1024,16]{1,0} all-reduce(%x), replica_groups={}
      %ag.1 = bf16[64]{0} all-gather(%y), dimensions={0}
      %a2a = (s16[8,4]{1,0}, s16[8,4]{1,0}) all-to-all(%a, %b)
      %cp = u8[100]{0} collective-permute(%z)
      %not_a_collective = f32[4]{0} add(%p, %q)
    """
    out = collective_bytes(hlo)
    assert out["all-reduce"] == 1024 * 16 * 4 * 2.0
    assert out["all-gather"] == 64 * 2
    assert out["all-to-all"] == 2 * 8 * 4 * 2
    assert out["collective-permute"] == 100
    assert out["total"] == sum(v for k, v in out.items() if k != "total")


def test_roofline_terms_dominance():
    t = roofline_terms(flops=197e12, bytes_=0.0, coll=0.0)
    assert t["dominant"] == "compute" and abs(t["compute_s"] - 1.0) < 1e-9
    t = roofline_terms(flops=0.0, bytes_=819e9, coll=1e9)
    assert t["dominant"] == "memory"
    t = roofline_terms(flops=1e12, bytes_=1e9, coll=50e9)
    assert t["dominant"] == "collective"
    assert 0 < t["roofline_fraction"] <= 1


# -- arith double-rounding vs exact oracle ------------------------------------
@settings(max_examples=100, deadline=None)
@given(st.floats(-100, 100, allow_nan=False, allow_subnormal=False, width=32),
       st.floats(-100, 100, allow_nan=False, allow_subnormal=False, width=32))
def test_arith_add_matches_exact_oracle_posit16(a, b):
    """f32-intermediate + round == correctly-rounded posit16 add (f32 has
    enough slack below n=16 except measure-zero double-rounding ties)."""
    ar = Arith.make("posit16")
    ra = float(decode_scalar(encode_scalar(a, POSIT16), POSIT16))
    rb = float(decode_scalar(encode_scalar(b, POSIT16), POSIT16))
    got = float(ar.add(jnp.float32(ra), jnp.float32(rb)))
    want = float(decode_scalar(encode_scalar(ra + rb, POSIT16), POSIT16))
    assert got == want, (a, b, got, want)


# -- posit algebraic properties ------------------------------------------------
@settings(max_examples=100, deadline=None)
@given(st.integers(1, (1 << 16) - 1))
def test_posit_negation_is_twos_complement(pat):
    if pat == POSIT16.nar_pattern:
        return
    v = decode_scalar(pat, POSIT16)
    neg_pat = (-pat) & POSIT16.mask
    assert decode_scalar(neg_pat, POSIT16) == -v
