"""Serve a small model with continuous batching: posit16 weights + posit8
KV cache (the paper's deployment corner), one extra posit16-KV lane, and
the nJ/token ledger.

Run: PYTHONPATH=src python examples/serve_lm.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.configs import CONFIGS, reduced
from repro.launch.mesh import make_debug_mesh_info
from repro.launch.process import enable_compile_cache
from repro.models import build_model
from repro.serve import (AGGRESSIVE_SERVE, PAPER_SERVE, ServeConfig,
                         ServingEngine)


def main():
    enable_compile_cache()
    cfg = reduced(CONFIGS["gemma2-2b"])
    minfo = make_debug_mesh_info()
    with minfo.mesh:
        model = build_model(cfg, minfo)
        params = model.init(jax.random.key(0))
        engine = ServingEngine(
            model, params,
            ServeConfig(batch_size=2, max_prompt=16, max_new_tokens=16,
                        seed=0),
            AGGRESSIVE_SERVE)  # w=posit16 / kv=posit8
        rng = np.random.default_rng(0)
        # six requests through two slots per lane: the scheduler reuses a
        # slot the moment its request finishes (continuous batching)
        for n in (5, 9, 12, 7):
            engine.submit(rng.integers(0, cfg.vocab, size=n)
                          .astype(np.int32))
        # one request on a wider KV lane + one sampled request
        engine.submit(rng.integers(0, cfg.vocab, size=8).astype(np.int32),
                      policy=PAPER_SERVE)  # w=posit16 / kv=posit16
        engine.submit(rng.integers(0, cfg.vocab, size=6).astype(np.int32),
                      temperature=0.8, max_new_tokens=8)
        for c in sorted(engine.run(), key=lambda c: c.rid):
            print(f"[serve] rid={c.rid} lane={c.lane} "
                  f"prompt={c.prompt_len} finish={c.finish_reason} "
                  f"tokens={c.tokens.tolist()}")
        for lane, row in engine.ledger.summary().items():
            print(f"[ledger] {lane}: {row['decode_tokens']:.0f} tokens, "
                  f"{row['us_per_token']:.0f} µs/token, "
                  f"{row['nj_per_token']:.1f} nJ/token")
        print("[serve] posit bits on HBM, f32 accumulation on the MXU "
              "(quire analogue); the posit8 lane's KV traffic is half "
              "the posit16 lane's")


if __name__ == "__main__":
    main()
