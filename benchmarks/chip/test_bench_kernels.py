"""The kernels' operation and byte counts against hand counts at small
shapes."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402


def _kernel(name):
    return harness.load_module(harness.kernel_path(name), "chipbench_kernel")


def test_posit_kv_attention_counts_valid_positions_of_decoding_rows():
    kv = _kernel("posit_kv_attention")
    # one row, 3 positions, 2 query heads over 1 kv head of 4 dims, posit8:
    # scores 2·2·3·4 = 48 and values 48 → 96, decode 2·1·4·3 = 24 → 120;
    # K and V bits 2·1·3·4·1 B = 24 B, q and out 2·2·4·4 B = 64 B
    ops, nbytes = kv.cost([3], heads=2, kv_heads=1, head_dim=4, kv_bits=8)
    assert (ops, nbytes) == (120.0, 88.0)
    # rows add; a posit16 cache doubles the K/V bytes only
    ops2, b2 = kv.cost([3, 3], heads=2, kv_heads=1, head_dim=4, kv_bits=16)
    assert ops2 == 240.0 and b2 == 2 * (48.0 + 64.0)
    assert kv.cost([], 2, 1, 4, 8) == (0.0, 0.0)


def test_posit_butterfly_counts_one_stage():
    bf = _kernel("posit_butterfly")
    # 2 rows of 8 points: 8 butterflies × 20 ops; bytes 4·(2·2·2·8 + 2·4)
    assert bf.cost(2, 8) == (160.0, 4.0 * (64 + 8))


def test_decoder_step_counts_weights_and_attention():
    dec = _kernel("decoder_step")
    cfg = {"hidden_size": 4, "intermediate_size": 6,
           "num_attention_heads": 2, "num_key_value_heads": 1,
           "head_dim": 2, "vocab_size": 10, "num_hidden_layers": 3}
    # per layer: q,o 4·2·2 each = 32, k,v 4·1·2 each = 16, mlp 3·4·6 = 72
    # → 120 per layer, 360 + head 40 = 400 weights
    assert dec.matmul_weights(cfg) == 400
    assert dec.attention_ops(cfg, 5) == 4 * 3 * 2 * 2 * 5
    assert dec.decode_ops(cfg, [5, 1]) == 2 * (800) + 240 + 48
    # 3 prompt tokens attend 1 + 2 + 3 = 6 positions
    assert dec.prefill_ops(cfg, 3) == 800 * 3 + dec.attention_ops(cfg, 6)


@pytest.mark.parametrize("name", ["posit_kv_attention", "posit_butterfly"])
def test_each_kernel_names_how_it_shows_in_a_trace(name):
    assert _kernel(name).NAMES
