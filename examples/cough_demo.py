"""Cough-detection format study (paper Fig. 4): FFT/MFCC features + random
forest, per-op rounded arithmetic.

Run: PYTHONPATH=src python examples/cough_demo.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.launch.process import enable_compile_cache

enable_compile_cache()

from repro.apps.cough import run_cough_detection

FMTS = ["fp32", "posit24", "posit16", "posit16e3", "bfloat16", "fp16"]

res = run_cough_detection(FMTS, n_windows=120, n_train=280)
print(f"{'format':10s}  AUC    FPR@TPR0.95")
for k, v in res.items():
    print(f"{k:10s}  {v['auc']:.3f}  {v['fpr_at_tpr95']:.3f}")
print("\npaper's claim: 16-bit posits replace FP32 with minimal loss; "
      "FP16 collapses on the 24-bit-PCM FFT pipeline.")
