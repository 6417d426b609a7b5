"""End-to-end driver: train a ~35M-param qwen3-family model for a few hundred
steps with posit16 QAT weights + posit16-quantized checkpoints, surviving a
simulated mid-run restart.

Run: PYTHONPATH=src python examples/train_lm.py  [--steps 300]
"""
import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import dataclasses

from repro.configs import CONFIGS
from repro.core.policy import QuantPolicy
from repro.launch.process import enable_compile_cache
from repro.launch.train import train


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    args = ap.parse_args()

    ckpt = tempfile.mkdtemp(prefix="repro_ckpt_")
    policy = QuantPolicy(weights="posit16")

    # phase 1: train to ~60% then "crash"
    crash_at = max(args.steps * 6 // 10, 60)
    print(f"[example] phase 1: steps 0..{crash_at} (then simulated failure)")
    _, losses1 = train("qwen3-8b", steps=crash_at, batch=8, seq=128,
                       policy=policy, ckpt_dir=ckpt, microbatches=2)

    # phase 2: restart — resumes from the latest checkpoint automatically
    print("[example] phase 2: restart from checkpoint")
    _, losses2 = train("qwen3-8b", steps=args.steps, batch=8, seq=128,
                       policy=policy, ckpt_dir=ckpt, microbatches=2)

    print(f"[example] loss {losses1[0]:.3f} → {losses2[-1]:.3f} "
          f"over {args.steps} steps (posit16 QAT, resumable)")
    assert losses2[-1] < losses1[0], "training should reduce loss"


if __name__ == "__main__":
    main()
