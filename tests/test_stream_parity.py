"""Offline ↔ streaming R-peak parity.

The contract under test: for ANY chunking/interleaving of an ECG record, the
streaming ``RPeakTracker`` (fed window scores by the engine as packets
arrive) confirms exactly the peaks the offline ``detect_rpeaks`` fold
produces on the full recording — same absolute samples, same order — because
both drive the identical ``RPeakFold`` call sequence over the identical
jit-compiled window scores.  Plus: the explicit k-means reservoir bound that
replaced the stride-derived subsample, and the per-window ``peaks``
provenance surfaced through ``pop_results``.
"""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.apps.bayeslope import (RESERVOIR_SIZE, RESERVOIR_STRIDE,
                                  RPeakFold, detect_rpeaks, reservoir_update)
from repro.apps.metrics import rpeak_f1
from repro.core.arith import Arith
from repro.data.biosignals import (ECG_FS, ecg_dataset, ecg_stream_signal,
                                   ragged_chunks)
from repro.stream import StreamEngine, rpeak_pipeline

W = 500  # samples per 2 s streaming window
PARITY_FMTS = ("posit16", "posit10", "fp32")

# module-level caches: the offline reference is computed once per format,
# the property test then re-streams the same record many ways
_RECORD = {}
_OFFLINE = {}


def _record():
    if not _RECORD:
        sig, true_r = ecg_stream_signal(12.0, seed=42, n_phases=3)
        _RECORD["sig"], _RECORD["true_r"] = sig, true_r
    return _RECORD["sig"], _RECORD["true_r"]


def _offline(fmt):
    if fmt not in _OFFLINE:
        sig, _ = _record()
        _OFFLINE[fmt] = detect_rpeaks(Arith.make(fmt), sig)
    return _OFFLINE[fmt]


def _stream(sig, fmt, rng, max_batch, patient="p"):
    """Stream one record through the engine under a random chunking and
    pump cadence; returns (tracker, per-window results)."""
    eng = StreamEngine({"rpeak": rpeak_pipeline()}, max_batch=max_batch)
    eng.register_patient(patient, "rpeak", fmt=fmt)
    for chunk in ragged_chunks(sig[None, :], rng, 3, 900):
        eng.ingest(patient, "rpeak", "ecg", chunk)
        if rng.uniform() < 0.3:
            eng.pump()
    eng.drain()
    eng.finalize_patient(patient, "rpeak")
    return eng.tracker_for(patient, "rpeak"), eng.results_for(patient, "rpeak")


@settings(max_examples=21, deadline=None)
@given(st.integers(0, 10**6))
def test_streaming_peaks_equal_offline_for_any_chunking(seed):
    """≥ 20 random chunkings × {posit16, posit10, fp32}: identical peaks."""
    sig, _ = _record()
    for fmt in PARITY_FMTS:
        rng = np.random.default_rng(seed)
        max_batch = int(rng.integers(1, 9))
        tracker, results = _stream(sig, fmt, rng, max_batch)
        assert tracker.peaks == _offline(fmt), (fmt, seed)
        # provenance: every window carries the peaks IT confirmed; their
        # concatenation in widx order is the same ascending peak stream
        assert [r.widx for r in results] == list(range(len(sig) // W))
        emitted = [int(p) for r in results for p in r.outputs["peaks"]]
        assert emitted == tracker.peaks[: len(emitted)]
        # the finalize tail is exactly what per-window emission deferred
        assert emitted + [int(p) for p in
                          tracker.peaks[len(emitted):]] == tracker.peaks


def test_multipatient_fleet_sensitivity_matches_offline():
    """A seeded mixed-format fleet, raggedly interleaved: every patient's
    streamed peaks — and hence per-patient sensitivity — equal the offline
    ``run_rpeak_detection``-style evaluation of the same recordings."""
    fleet = {
        "p16": ("posit16", 200),
        "p10a": ("posit10", 201),
        "p10b": ("posit10", 202),
        "p32": ("fp32", 203),
    }
    rng = np.random.default_rng(99)
    eng = StreamEngine({"rpeak": rpeak_pipeline()}, max_batch=4)
    sources, queues = {}, []
    for pid, (fmt, seed) in fleet.items():
        sig, true_r = ecg_stream_signal(16.0, seed=seed, n_phases=4)
        sources[pid] = (sig, true_r)
        eng.register_patient(pid, "rpeak", fmt=fmt)
        queues.append((pid, list(ragged_chunks(sig[None, :], rng, 30, 700))))
    while any(q for _, q in queues):
        k = int(rng.integers(len(queues)))
        pid, chunks = queues[k]
        if chunks:
            eng.ingest(pid, "rpeak", "ecg", chunks.pop(0))
    eng.drain()
    eng.finalize_all()
    for pid, (fmt, _) in fleet.items():
        sig, true_r = sources[pid]
        offline_peaks = detect_rpeaks(Arith.make(fmt), sig)
        streamed = eng.tracker_for(pid, "rpeak").peaks
        assert streamed == offline_peaks, pid
        # the offline evaluation's per-record sensitivity, reproduced live
        _, _, rec_off = rpeak_f1(offline_peaks, true_r, ECG_FS)
        _, _, rec_stream = rpeak_f1(streamed, true_r, ECG_FS)
        assert rec_stream == rec_off
        assert rec_stream > 0.9, (pid, rec_stream)


@pytest.mark.slow
def test_parity_full_segment_set():
    """Slow lane: the paper-protocol segment set (MIT-BIH-style intensity
    sweep) streamed segment-per-patient — parity must hold on every one."""
    data = ecg_dataset(n_subjects=3, segments_per_subject=3,
                       segment_s=20.0, seed=5)
    for fmt in ("posit16", "posit10"):
        rng = np.random.default_rng(11)
        for i, (sig, _) in enumerate(data):
            offline_peaks = detect_rpeaks(Arith.make(fmt), sig)
            tracker, _ = _stream(np.asarray(sig), fmt, rng,
                                 max_batch=int(rng.integers(1, 9)),
                                 patient=f"s{i}")
            assert tracker.peaks == offline_peaks, (fmt, i)


# ---------------------------------------------------------------------------
# Explicit k-means reservoir bound (replaces the stride-derived subsample
# that kept EVERY sample for 501..999-sample segments)
# ---------------------------------------------------------------------------
def test_reservoir_update_is_bounded():
    r = np.zeros(0, np.float32)
    for n in (10, 499, 500, 501, 999, 4096):
        r = reservoir_update(r, np.ones(n, np.float32))
        assert len(r) <= RESERVOIR_SIZE
    # saturated: FIFO keeps exactly the cap
    assert len(r) == RESERVOIR_SIZE


@pytest.mark.parametrize("n", [300, 501, 750, 999, 2000, 7000])
def test_fold_reservoir_never_exceeds_cap(n):
    """The 501..999-sample regime of the old stride bug, plus short and
    long segments: the fold's reservoir stays within its explicit size."""
    rng = np.random.default_rng(n)
    ar = Arith.make("posit16")
    fold = RPeakFold()
    expected = 0
    for s0 in range(0, n, W):
        s = rng.uniform(0, 1, min(W, n - s0)).astype(np.float32)
        fold.push(ar, s)
        expected = min(expected + len(s[::RESERVOIR_STRIDE]), RESERVOIR_SIZE)
        assert len(fold.reservoir) == expected
        assert len(fold.reservoir) <= RESERVOIR_SIZE
    fold.finalize(ar)
    assert len(fold.reservoir) <= RESERVOIR_SIZE


def test_detect_rpeaks_tiny_trailing_windows_do_not_crash():
    """Recording lengths ≡ 1 or 2 (mod 500) leave a trailing window too
    short for a slope product — it must be skipped, not crash enhance()."""
    rng = np.random.default_rng(8)
    ar = Arith.make("posit16")
    for n in (501, 502, 1002, 2, 3):
        sig = rng.normal(size=n) * 200.0
        peaks = detect_rpeaks(ar, sig)      # must not raise
        assert all(0 <= p < n for p in peaks)


def test_nan_window_does_not_poison_threshold_reservoir():
    """One collapsed (NaN-score) window must cost only itself: the
    reservoir takes sanitized scores, so the 2-means threshold recovers as
    soon as the arithmetic does."""
    ar = Arith.make("fp32")
    sig, true_r = _record()
    clean = detect_rpeaks(ar, sig)
    fold = RPeakFold()
    got = []
    n_windows = len(sig) // W
    for k in range(n_windows):
        if k == 1:
            scores = np.full(W, np.nan, np.float32)   # artifact window
        else:
            from repro.apps.bayeslope import _score_fn
            scores = np.asarray(_score_fn(ar.name, W)(sig[k * W:(k + 1) * W]
                                                      .astype(np.float32)))
        got.extend(int(p) for p in fold.push(ar, scores))
        assert np.isfinite(fold.thr) or k == 0
    got.extend(int(p) for p in fold.finalize(ar))
    # every clean-region beat outside the artifact window is still found
    missed = [p for p in clean if not (W <= p < 2 * W) and p not in got]
    assert not missed


def test_detect_rpeaks_short_segments_stay_reasonable():
    """501..999-sample segments (the mis-sized regime) still detect beats."""
    rng = np.random.default_rng(3)
    from repro.data.biosignals import ecg_segment
    ar = Arith.make("posit16")
    for dur in (2.6, 3.2, 3.9):        # 650..975 samples
        sig, true_r = ecg_segment(dur, 0.2, rng)
        peaks = detect_rpeaks(ar, sig)
        f1, _, _ = rpeak_f1(peaks, true_r, ECG_FS)
        assert f1 > 0.8, (dur, f1, peaks, true_r)
