"""Benchmark harness — one entry per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (derived = the headline metric
the paper reports for that table/figure).

  fig3_formats        — Fig. 3/6: precision bits + dynamic range per format
  fig4_cough_roc      — Fig. 4: cough-detection AUC / FPR@TPR0.95 sweep
  fig5_rpeak_f1       — Fig. 5: BayeSlope F1 sweep
  tab1_3_area         — Tables I–III: area model + 38% saving
  tab4_5_power_energy — Tables IV/V + §VI-B: power, FFT cycles/energy
  fft_accuracy        — FFT numerical error per format (supports Fig. 4)
  quant_matmul        — framework tie-in: posit-quantized matmul err/bytes
  roofline_summary    — reads results/dryrun cells → §Roofline table
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.launch.process import enable_compile_cache


def _timed(fn, *args, repeat=1, **kw):
    t0 = time.perf_counter()
    for _ in range(repeat):
        out = fn(*args, **kw)
    dt = (time.perf_counter() - t0) / repeat
    return out, dt * 1e6


def fig3_formats():
    from repro.core.formats import ALL_FORMATS, PositFormat
    for name, f in ALL_FORMATS.items():
        if isinstance(f, PositFormat):
            print(f"fig3_formats/{name},0,bits={f.n};"
                  f"max_significand={f.max_fraction_bits + 1};"
                  f"maxval={f.maxpos:.3e}")
        else:
            print(f"fig3_formats/{name},0,bits={f.n};"
                  f"max_significand={f.man_bits + 1};"
                  f"maxval={f.max_value:.3e}")


def fig4_cough_roc():
    from repro.apps.cough import run_cough_detection
    fmts = ["fp32", "posit32", "posit24", "posit16", "posit16e3",
            "bfloat16", "fp16"]
    res, us = _timed(run_cough_detection, fmts, n_windows=120, n_train=280)
    for k, v in res.items():
        print(f"fig4_cough_roc/{k},{us/len(fmts):.0f},"
              f"auc={v['auc']:.3f};fpr_at_tpr95={v['fpr_at_tpr95']:.3f}")


def fig5_rpeak_f1():
    from repro.apps.bayeslope import run_rpeak_detection
    fmts = ["fp32", "posit32", "posit16", "bfloat16", "fp16", "posit12",
            "posit10", "posit8", "fp8e5m2", "fp8e4m3"]
    res, us = _timed(run_rpeak_detection, fmts, n_subjects=3,
                     segments_per_subject=5, segment_s=12.0)
    for k, v in res.items():
        print(f"fig5_rpeak_f1/{k},{us/len(fmts):.0f},f1={v:.3f}")


def tab1_3_area():
    from repro.energy import model as em
    a_c = em.area_total(em.AREA_COPROSIT)
    a_f = em.area_total(em.AREA_FPU_SS)
    print(f"tab1_area/coprosit,0,total_um2={a_c:.2f}")
    print(f"tab1_area/fpu_ss,0,total_um2={a_f:.2f}")
    print(f"tab1_area/saving,0,fraction={em.area_saving_fraction():.3f}"
          f";paper=0.38")
    prau = em.AREA_PRAU_UNITS
    fpu = em.AREA_FPU_UNITS
    print(f"tab2_units/prau_addmul,0,um2={prau['Add'] + prau['Mul']}"
          f";fpu_fma={fpu['FMA']};ratio={(prau['Add']+prau['Mul'])/fpu['FMA']:.2f}")


def tab4_5_power_energy():
    from repro.energy import model as em
    print(f"tab4_power/coprosit,0,total_uW={em.POWER_TOTAL['coprosit']}")
    print(f"tab4_power/fpu_ss,0,total_uW={em.POWER_TOTAL['fpu_ss']}")
    print(f"tab5_unit_power/saving,0,"
          f"fraction={em.unit_power_saving_fraction():.3f};paper=0.423")
    for cfg in ("coprosit", "fpu_ss", "fpu_ss_nonasm"):
        print(f"sec6b_fft_energy/{cfg},0,cycles={em.FFT_CYCLES[cfg]}"
              f";energy_nJ={em.fft_energy_nj(cfg):.1f}")
    print(f"sec6b_fft_energy/saving_asm,0,"
          f"fraction={em.fft_energy_saving_fraction():.3f};paper=0.271")
    print(f"sec6b_fft_energy/saving_nonasm,0,"
          f"fraction={em.fft_energy_saving_fraction(nonasm=True):.3f}"
          f";paper=0.194")
    ops = em.fft_op_counts(4096)
    est = em.estimate_app_energy_nj(ops, "coprosit")
    print(f"sec6b_fft_energy/opcount_model,0,est_nJ={est:.1f};measured=404.2")


def fft_accuracy():
    import jax.numpy as jnp
    from repro.core.arith import Arith
    from repro.apps.dsp import fft_format
    rng = np.random.default_rng(0)
    x = rng.normal(size=(4, 1024)) * 1000.0
    ref = np.fft.fft(x)
    xj = jnp.asarray(x, jnp.float32)
    for name in ["fp32", "posit32", "posit24", "posit16", "bfloat16", "fp16",
                 "posit12"]:
        ar = Arith.make(name)
        (re, im), us = _timed(
            lambda ar=ar: [np.asarray(v) for v in
                           fft_format(ar, xj, jnp.zeros_like(xj))])
        err = np.sqrt(np.nanmean((re - ref.real) ** 2 + (im - ref.imag) ** 2))
        scale = np.sqrt(np.mean(np.abs(ref) ** 2))
        print(f"fft_accuracy/{name},{us:.0f},rel_rmse={err/scale:.3e}")


def quant_matmul():
    import jax.numpy as jnp
    from repro.core.formats import POSIT8, POSIT16
    from repro.core.quant import quantize
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.normal(size=(256, 512)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(512, 512)) / np.sqrt(512), jnp.float32)
    ref = np.asarray(a @ w)
    for fmt in (POSIT16, POSIT8):
        qw = quantize(w, fmt, scaled=True)
        out, us = _timed(lambda qw=qw: np.asarray(a @ qw.dequant()), repeat=3)
        err = np.sqrt(np.mean((out - ref) ** 2)) / np.sqrt(np.mean(ref ** 2))
        print(f"quant_matmul/{fmt.name},{us:.0f},rel_rmse={err:.3e}"
              f";bytes_ratio={fmt.storage_bytes / 4:.2f}")


def roofline_summary():
    d = os.path.join(os.path.dirname(__file__), "..", "results", "dryrun")
    if not os.path.isdir(d):
        print("roofline_summary/missing,0,run=launch.dryrun first")
        return
    for f in sorted(os.listdir(d)):
        if not f.endswith(".json"):
            continue
        r = json.load(open(os.path.join(d, f)))
        if r.get("skipped") or "error" in r or r.get("mesh") != "16x16":
            continue
        t = r["terms"]
        print(f"roofline/{r['arch']}/{r['shape']},0,"
              f"dom={t['dominant']};bound_s={t['bound_s']:.3f};"
              f"frac={t['roofline_fraction']:.3f}")


BENCHES = [fig3_formats, tab1_3_area, tab4_5_power_energy, quant_matmul,
           fft_accuracy, fig5_rpeak_f1, fig4_cough_roc, roofline_summary]


def main() -> None:
    enable_compile_cache()
    only = sys.argv[1] if len(sys.argv) > 1 else None
    for b in BENCHES:
        if only and only not in b.__name__:
            continue
        t0 = time.perf_counter()
        try:
            b()
        except Exception as e:  # keep the harness running
            print(f"{b.__name__}/ERROR,0,{type(e).__name__}:{e}")
        print(f"# {b.__name__} done in {time.perf_counter()-t0:.1f}s",
              file=sys.stderr)


if __name__ == '__main__':
    main()
