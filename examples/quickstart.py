"""Quickstart: posit arithmetic as a drop-in storage format.

Run: PYTHONPATH=src python examples/quickstart.py
"""
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.launch.process import enable_compile_cache

enable_compile_cache()

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import POSIT8, POSIT16, decode, encode, round_to_posit
from repro.core.arith import Arith
from repro.core.quant import quantize
from repro.kernels import ops

# 1. the paper's worked example (Fig. 2)
pat = jnp.array([0b1001101000111000], jnp.int32)
print("posit16 0b1001101000111000 =", float(decode(pat, POSIT16)[0]))  # -46.25

# 2. round a tensor onto the posit16 lattice
x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 4)), jnp.float32)
print("max |x - posit16(x)| =",
      float(jnp.abs(x - round_to_posit(x, POSIT16)).max()))

# 3. dynamic range: posit16 survives where fp16 overflows
big = jnp.asarray([3e7, 6e4, 1e-6], jnp.float32)
ar16 = Arith.make("posit16")
fp16 = Arith.make("fp16")
print("posit16:", np.asarray(ar16.rnd(big)))
print("fp16:   ", np.asarray(fp16.rnd(big)))

# 4. posit-quantized weights + the fused Pallas matmul (interpret on CPU)
w = jnp.asarray(np.random.default_rng(1).normal(size=(256, 256)) / 16,
                jnp.float32)
a = jnp.asarray(np.random.default_rng(2).normal(size=(128, 256)), jnp.float32)
qa, qw = encode(a, POSIT16), encode(w, POSIT16)
out = ops.matmul(qa, qw, POSIT16, bm=128, bn=128, bk=128)
ref = a @ w
print("fused posit16 matmul rel err:",
      float(jnp.linalg.norm(out - ref) / jnp.linalg.norm(ref)))

# 5. posit8 KV-cache memory ratio
kv_bf16 = 2 * 32768 * 8 * 128 * 2
kv_posit8 = 2 * 32768 * 8 * 128 * 1
print(f"decode-step KV bytes: bf16={kv_bf16/1e6:.0f}MB "
      f"posit8={kv_posit8/1e6:.0f}MB (x{kv_bf16/kv_posit8:.0f} less HBM traffic)")
