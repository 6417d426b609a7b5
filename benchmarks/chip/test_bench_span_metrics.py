"""The readers of the program's spans inside the fleet's dispatch, the
server's ACK walk and the serving engine's step, on contexts built by
hand: clipped to the measured window, divided by the windows the ledger
counts, and None where there is nothing to read.  CPU only."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402

SPEC = harness.load_spec()


def _reader(name):
    return harness.load_module(harness.metric_path(name),
                               "chipbench_metric").read


FLEET = [("tracker_us_per_window", "dispatch/tracker"),
         ("device_call_us_per_window", "dispatch/device"),
         ("flush_acks_us_per_window", "frame/flush_acks")]


def _fleet_ctx(spans, windows=4):
    return {"t_open": 10.0, "t_close": 20.0, "trace": None,
            "host_spans": spans,
            "ledger": {"windows": windows, "padded": 0}}


@pytest.mark.parametrize("quantity,span", FLEET)
def test_a_fleet_span_reader_sums_clipped_spans_over_the_ledgers_windows(
        quantity, span):
    spans = [(span, 9.999, 10.001),        # 1 ms of it inside the window
             (span, 12.0, 12.002),         # 2 ms
             (span, 19.999, 20.5),         # 1 ms inside
             (span, 25.0, 26.0),           # after the close
             ("dispatch/rpeak/posit10", 11.0, 13.0),
             ("frame/decode", 11.0, 11.5)]
    read = _reader(f"{quantity}.fleet")
    assert read(_fleet_ctx(spans)) == pytest.approx(1e6 * 4e-3 / 4)
    assert read(_fleet_ctx(spans, windows=8)) == pytest.approx(500.0)


@pytest.mark.parametrize("quantity,span", FLEET)
def test_a_fleet_span_reader_has_nothing_to_read(quantity, span):
    read = _reader(f"{quantity}.backlog")
    # an untraced run: no spans
    assert read(_fleet_ctx([])) is None
    # nothing dispatched in the window
    assert read(_fleet_ctx([(span, 11.0, 12.0)], windows=0)) is None
    # a program whose spans of the category do not include this one
    older = [("dispatch/rpeak/posit10", 11.0, 13.0),
             ("frame/decode", 11.0, 11.5)]
    assert read(_fleet_ctx(older)) is None


def test_each_new_metric_is_read_in_the_cells_it_lists():
    names = {q + s for q, _ in FLEET for s in (".fleet", ".backlog")}
    names |= {"step_p95_ms.serve", "account_ms_p50.serve"}
    entries = {m["name"]: m for m in SPEC["per_layer"] if m["name"] in names}
    assert set(entries) == names
    for name, m in entries.items():
        assert m["source"] == "program_span"
        assert harness.metric_path(name).is_file(), name
        for cell in m["workloads"]:
            assert m in harness.resolve_cell(SPEC, cell).per_layer


def _ev(name, s, e, cat="serve"):
    """A tracer event: (ph, cat, name, start, end, track, args, id,
    parent, key)."""
    return ("X", cat, name, s, e, "lane:x", None, 0, None, None)


def test_step_p95_reads_the_steps_that_end_in_the_window():
    read = _reader("step_p95_ms.serve")
    steps = [_ev("step", 10.0 + i, 10.0 + i + 0.001 * (i + 1))
             for i in range(20)]                       # 1..20 ms
    late = [_ev("step", 9.0, 10.5)]            # begins before, ends inside
    out = [_ev("step", 9.0, 9.9), _ev("step", 30.0, 30.4)]  # end outside
    ctx = {"t_open": 10.0, "t_close": 30.0,
           "spans": steps + out + [_ev("decode", 11.0, 11.5)]}
    assert read(ctx) == pytest.approx(19.0)
    ctx["spans"] = steps + late
    assert read(ctx) == pytest.approx(20.0)   # 20 of 21 are at most 20 ms
    assert read({**ctx, "spans": out}) is None
    assert read({**ctx, "spans": []}) is None


def test_account_p50_reads_the_accounting_spans_clipped_to_the_window():
    read = _reader("account_ms_p50.serve")
    spans = [_ev("account", 11.0, 11.002), _ev("account", 12.0, 12.004),
             _ev("account", 29.999, 30.003),     # 1 ms of it inside
             _ev("account", 31.0, 31.5),         # after the close
             _ev("decode", 11.0, 11.5)]
    ctx = {"t_open": 10.0, "t_close": 30.0, "spans": spans}
    assert read(ctx) == pytest.approx(2.0)
    assert read({**ctx, "spans": spans[3:]}) is None
    assert read({**ctx, "spans": []}) is None
