"""BayeSlope R-peak detection across arithmetic formats (paper Fig. 5).

Run: PYTHONPATH=src python examples/rpeak_demo.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.launch.process import enable_compile_cache

enable_compile_cache()

from repro.apps.bayeslope import run_rpeak_detection

FMTS = ["fp32", "posit16", "posit12", "posit10", "posit8",
        "bfloat16", "fp16", "fp8e5m2", "fp8e4m3"]

res = run_rpeak_detection(FMTS, n_subjects=3, segments_per_subject=5,
                          segment_s=12.0)
print(f"{'format':10s}  F1")
for k, v in res.items():
    bar = "#" * int(v * 40)
    print(f"{k:10s}  {v:.3f} {bar}")
print("\npaper's claim: posits stay >0.9 down to 8-10 bits; "
      "FP16 needs its full 16 and FP8E4M3 fails outright.")
