"""The reader of the trackers' threshold launches, on contexts built by
hand: the ledger's windows over the ``dispatch/tracker.threshold`` spans
that start in the measured window, and None where there is nothing to
read.  CPU only."""
import math
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402

SPEC = harness.load_spec()
SPAN = "dispatch/tracker.threshold"
NAMES = ("threshold_windows_per_launch.fleet",
         "threshold_windows_per_launch.backlog")


def _ctx(spans, windows=128, trace=None):
    return {"t_open": 10.0, "t_close": 20.0, "trace": trace,
            "host_spans": spans,
            "ledger": {"windows": windows, "padded": 0}}


def _read(name, ctx):
    return harness.load_module(harness.metric_path(name),
                               "chipbench_metric").read(ctx)


@pytest.mark.parametrize("name", NAMES)
def test_threshold_launches_are_counted_where_they_start(name):
    spans = [(SPAN, 9.99, 10.01),            # starts before the open
             (SPAN, 12.0, 12.002),
             (SPAN, 19.999, 20.5),           # starts inside
             (SPAN, 20.0, 20.1),             # at the close: after it
             ("dispatch/tracker", 11.0, 13.0)]
    assert _read(name, _ctx(spans)) == pytest.approx(64.0)
    # one launch per window, as a program with a round trip per window
    per_window = [(SPAN, 10.0 + 0.01 * i, 10.005 + 0.01 * i)
                  for i in range(128)]
    assert _read(name, _ctx(per_window)) == pytest.approx(1.0)


@pytest.mark.parametrize("name", NAMES)
def test_threshold_reader_has_nothing_to_read(name):
    assert _read(name, _ctx([])) is None                  # untraced
    assert _read(name, _ctx([(SPAN, 11.0, 12.0)], windows=0)) is None
    # dispatch spans without a tracker's launches
    assert _read(name, _ctx([("dispatch/cough/posit16", 11.0, 12.0)])) \
        is None
    # traced, windows dispatched, and no span recorded at all
    assert _read(name, _ctx([], trace=object())) == math.inf


def test_threshold_metric_is_read_in_the_cells_it_lists():
    entries = {m["name"]: m for m in SPEC["per_layer"] if m["name"] in NAMES}
    assert set(entries) == set(NAMES)
    for name, m in entries.items():
        assert m["layer"] == "stream engine"
        assert m["moves"] == "windows_per_s" and m["better"] == "higher"
        assert harness.metric_path(name).is_file(), name
        for cell in m["workloads"]:
            assert m in harness.resolve_cell(SPEC, cell).per_layer
