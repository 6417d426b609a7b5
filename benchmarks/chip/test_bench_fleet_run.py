"""Whole runs of the fleet cell on the CPU at a small size, past the
harness's look for a chip: a sound run is correct, and each fault the
cell can have, planted in the timed path, makes ``correct`` false."""
import dataclasses
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402
import run  # noqa: E402

sys.path.insert(0, str(harness.ROOT / "src"))


def _small_cell():
    cell = harness.resolve_cell(harness.load_spec(), "fleet.ecg_steady")
    # a long warm burst gives each patient a record long enough to hold
    # the tracker to the true peaks away from the record's ends
    return dataclasses.replace(cell, traffic={**cell.traffic,
                                              "patients": 8,
                                              "warm_windows": 14})


def _run(seconds=3.0, trace=False, control=False):
    return run.run_cell("fleet.ecg_steady", 2**31 + 101, seconds, trace,
                        require_tpu=False, control=control,
                        cell=_small_cell())


def _answer_altered(monkeypatch):
    """Every window's scores come back shifted where they are produced."""
    import repro.stream.pipelines as pipelines
    orig = pipelines._rpeak_batch_fn

    def altered(*a, **k):
        fn = orig(*a, **k)
        return lambda arrays: {**fn(arrays),
                               "scores": fn(arrays)["scores"] * 0.5}
    monkeypatch.setattr(pipelines, "_rpeak_batch_fn", altered)


def _half_batch(monkeypatch):
    """Each dispatch scores only the first half of its windows."""
    from repro.stream.engine import StreamEngine
    orig = StreamEngine._dispatch

    def half(self, task, fmt, windows):
        orig(self, task, fmt, windows[:max(len(windows) // 2, 1)])
    monkeypatch.setattr(StreamEngine, "_dispatch", half)


def _state_unchanged(monkeypatch):
    """The tracker's step returns its state unchanged: no peak is ever
    confirmed."""
    from repro.stream.tracker import RPeakTracker, TrackerUpdate

    def stuck(self, widx, row, fmt):
        return TrackerUpdate(self.patient, widx, fmt,
                             np.zeros(0, np.int64), 0.5, np.inf, False)
    monkeypatch.setattr(RPeakTracker, "update", stuck)


def test_a_sound_run_is_correct_and_reports_the_cell():
    line = _run()
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == {"windows_per_s", "setup_s"}
    # a full batch of 64 is what dispatches: at 8 patients none may fill
    # inside a 3 s window, so the rate may read 0 here
    assert line["metrics"]["windows_per_s"]["value"] >= 0
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["checks"]["missing_windows"]["value"] == 0
    assert set(line["checks"]) >= {"score_mae.posit10", "score_mae.posit8",
                                   "peak_miss"}


@pytest.mark.parametrize("fault", [_answer_altered, _half_batch,
                                   _state_unchanged])
def test_a_fault_in_the_timed_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    line = _run()
    assert line["correct"] is False, (fault.__name__, line["checks"])


def test_the_lower_precision_control_is_not_correct():
    line = _run(control=True)
    assert line["correct"] is False
    c = line["checks"]
    assert c["score_mae.posit10"]["value"] > c["score_mae.posit10"]["limit"]
