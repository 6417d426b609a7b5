"""Streaming runtime demo: a small mixed fleet of wearable patients.

Three cough-monitoring patients (2-mic audio @ 16 kHz + 9-axis IMU @ 100 Hz)
and four exercise-ECG patients (250 Hz) stream ragged radio packets into one
StreamEngine.  Each patient stream is routed to its paper-table posit format
(one high-risk patient pinned to fp32, one frail-battery patient pinned to
posit8), windows are batched across patients per format, and per-patient
``RPeakTracker``s carry BayeSlope's adaptive threshold + Bayesian gap
recovery across window boundaries — so the stream emits confirmed R-peak
positions, not just scores.

The posit8 patient also demonstrates the XBioSiP-style quality-feedback
escalation: when candidate scores crowd the decision threshold, the router
climbs posit8 → posit10 → posit16 for the next windows, recovers beats the
static posit8 stream misses, and the ledger bills the extra nJ to the
escalation column.

Results drain through the ``repro.ingest.Supervisor`` bounded queue — the
pattern long-running callers should copy: the engine's backlog stays flat
however long the stream runs, and the supervisor carries the per-patient
windows/sec + latency telemetry.

  PYTHONPATH=src python examples/stream_demo.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.apps.cough import train_reference_forest
from repro.apps.metrics import rpeak_f1
from repro.data.biosignals import (ECG_FS, cough_stream_signals,
                                   ecg_stream_signal, ragged_chunks)
from repro.ingest import Supervisor
from repro.launch.process import enable_compile_cache
from repro.stream import (EscalationPolicy, PrecisionRouter, StreamEngine,
                          cough_pipeline, rpeak_pipeline)

N_WINDOWS = 4
FRAIL_WINDOWS = 10          # 20 s of ECG for the escalation storyline
FRAIL_SEED = 13


def build_engine(forest, escalate):
    return StreamEngine(
        {"cough": cough_pipeline(forest), "rpeak": rpeak_pipeline()},
        router=PrecisionRouter(
            escalation=EscalationPolicy() if escalate else None),
        max_batch=8)


def stream_frail_only(forest, sig, escalate):
    """The posit8 patient alone, window-at-a-time (feedback reacts)."""
    eng = build_engine(forest, escalate)
    sup = Supervisor(eng)
    eng.register_patient("ecg-frail", "rpeak", fmt="posit8")
    W = 500
    for k in range(0, (len(sig) // W) * W, W):
        eng.ingest("ecg-frail", "rpeak", "ecg", sig[None, k: k + W])
        eng.pump()
        sup.poll()
    eng.drain()
    eng.finalize_all()
    sup.poll()
    return eng, sup


def main():
    enable_compile_cache()
    print("training the offline forest (float32 reference features)...")
    forest = train_reference_forest(64, 7, n_trees=8, depth=5)

    engine = build_engine(forest, escalate=True)
    sup = Supervisor(engine, capacity=256)
    engine.register_patient("cough-hi-risk", "cough", fmt="fp32")
    engine.register_patient("ecg-frail", "rpeak", fmt="posit8")

    rng = np.random.default_rng(0)
    labels, truths = {}, {}
    queues = []
    for k, pid in enumerate(["cough-a", "cough-b", "cough-hi-risk"]):
        audio, imu, y = cough_stream_signals(N_WINDOWS, seed=k)
        labels[pid] = y
        queues.append((pid, "cough", "audio",
                       list(ragged_chunks(audio, rng, 500, 8000))))
        queues.append((pid, "cough", "imu",
                       list(ragged_chunks(imu, rng, 5, 40))))
    for k, pid in enumerate(["ecg-rest", "ecg-jog", "ecg-sprint"]):
        sig, r = ecg_stream_signal(N_WINDOWS * 2.0, seed=50 + k,
                                   n_phases=k + 1)
        truths[pid] = r
        queues.append((pid, "rpeak", "ecg",
                       list(ragged_chunks(sig[None, :], rng, 60, 800))))
    frail_sig, frail_r = ecg_stream_signal(FRAIL_WINDOWS * 2.0,
                                           seed=FRAIL_SEED, n_phases=4)
    truths["ecg-frail"] = frail_r
    queues.append(("ecg-frail", "rpeak", "ecg",
                   list(ragged_chunks(frail_sig[None, :], rng, 60, 800))))

    print("streaming ragged packets from 7 patients...")
    live = [q for q in queues if q[3]]
    while live:
        j = int(rng.integers(len(live)))
        pid, task, mod, chunks = live[j]
        engine.ingest(pid, task, mod, chunks.pop(0))
        if not chunks:
            live.pop(j)
        engine.pump()     # dispatch eagerly so escalation feedback reacts
        sup.poll()        # bounded drain: engine backlog stays flat
    engine.drain()
    engine.finalize_all()
    sup.poll()

    print("\nper-patient timelines:")
    for pid in ("cough-a", "cough-b", "cough-hi-risk"):
        rs = sup.results_for(pid, "cough")
        probs = " ".join(f"{float(r.outputs['p_cough']):.2f}" for r in rs)
        truth = " ".join(str(int(v)) for v in labels[pid])
        print(f"  {pid:14s} [{rs[0].fmt:7s}] P(cough) per window: {probs}"
              f"   (truth: {truth})")
    for pid in ("ecg-rest", "ecg-jog", "ecg-sprint", "ecg-frail"):
        rs = sup.results_for(pid, "rpeak")
        fmts = "→".join(dict.fromkeys(r.fmt for r in rs))  # format journey
        peaks = engine.tracker_for(pid, "rpeak").peaks
        dur_s = len(rs) * 2.0
        _, _, rec = rpeak_f1(peaks, truths[pid], ECG_FS)
        print(f"  {pid:14s} [{fmts:23s}] beats={len(peaks):3d} "
              f"(truth {len(truths[pid]):3d})  ≈HR {60 * len(peaks) / dur_s:3.0f} bpm"
              f"  sensitivity {rec:.2f}")

    print("\nescalation storyline (ecg-frail @ posit8, same record twice):")
    static, _ = stream_frail_only(forest, frail_sig, escalate=False)
    esc, esc_sup = stream_frail_only(forest, frail_sig, escalate=True)
    p_static = static.tracker_for("ecg-frail", "rpeak").peaks
    p_esc = esc.tracker_for("ecg-frail", "rpeak").peaks
    _, _, rec_s = rpeak_f1(p_static, frail_r, ECG_FS)
    _, _, rec_e = rpeak_f1(p_esc, frail_r, ECG_FS)
    tp_s, tp_e = round(rec_s * len(frail_r)), round(rec_e * len(frail_r))
    journey = "→".join(dict.fromkeys(
        r.fmt for r in esc_sup.results_for("ecg-frail", "rpeak")))
    att = esc.ledger.escalation_summary().get("ecg-frail",
                                              {"windows": 0, "extra_nj": 0.0})
    base_nj = static.fleet_summary()["fleet"]["total_nj"]
    print(f"  static posit8        : {tp_s}/{len(frail_r)} beats found")
    print(f"  with escalation      : {tp_e}/{len(frail_r)} beats found "
          f"({journey})")
    print(f"  recovered beats      : {tp_e - tp_s}")
    print(f"  escalation cost      : {att['extra_nj']:.1f} nJ over "
          f"{att['windows']:.0f} windows "
          f"(+{100 * att['extra_nj'] / base_nj:.0f}% vs static posit8)")

    print("\nfleet summary (throughput + ASIC-model energy):")
    for key, row in engine.fleet_summary().items():
        print(f"  {key:16s} windows={row['windows']:3.0f}"
              f"  windows/s={row['windows_per_s']:8.2f}"
              f"  nJ/window={row['nj_per_window']:8.1f}"
              f"  escalation_nJ={row['escalation_nj']:6.1f}")
    esc_fleet = engine.ledger.escalation_summary()
    if esc_fleet:
        print("\nper-patient escalation ledger:")
        for pid, d in esc_fleet.items():
            print(f"  {pid:14s} windows={d['windows']:3.0f} "
                  f"extra_nJ={d['extra_nj']:.1f}")

    tele = sup.telemetry()
    q, lat = tele["queue"], tele["latency_ms"]
    print(f"\nsupervisor drain: {q['total_windows']} windows through a "
          f"bounded queue (capacity {q['capacity']}, dropped {q['dropped']})"
          f"; ready→result latency p50 {lat['p50']:.1f} ms / "
          f"p99 {lat['p99']:.1f} ms")


if __name__ == "__main__":
    main()
