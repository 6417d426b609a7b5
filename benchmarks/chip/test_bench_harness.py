"""The harness: cells found by name from files alone, the generators'
schedules, infinite latency for answers that never came, and the result
line's keys.  CPU only; nothing here describes a TPU."""
import asyncio
import dataclasses
import hashlib
import json
import math
import os
import shutil
import sys
import time
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402

fleet_gen = harness.load_module(harness.generator_path("fleet_stream"),
                                "chipbench_gen")
chat_gen = harness.load_module(harness.generator_path("poisson_chat"),
                               "chipbench_gen")
SPEC = harness.load_spec()
FLEET_CFG = harness.load_json(
    harness.ROOT / "benchmarks/chip/configs/wearable_fleet.json")
STEADY = harness.load_json(harness.traffic_path("ecg_steady"))


def _digest(root):
    out = {}
    for dirpath, dirs, files in os.walk(root):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            p = os.path.join(dirpath, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_a_new_cell_needs_new_files_and_entries_only(tmp_path):
    """A later PR adds a configuration, a mix and a per-layer metric as new
    files plus new BENCHMARK.json entries; no file there is edited."""
    bench = tmp_path / "benchmarks" / "chip"
    shutil.copytree(HERE, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digest(bench)
    (bench / "configs" / "toy_fleet.json").write_text(json.dumps(
        {**FLEET_CFG, "max_batch": 8}))
    (bench / "traffic" / "toy_mix.json").write_text(json.dumps(
        {**STEADY, "patients": 3}))
    (bench / "metrics" / "toy_lag.fleet.py").write_text(
        "def read(ctx):\n    return 2.0 * ctx['x']\n")
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({"name": "toy_fleet", "source": "x",
                            "file": "benchmarks/chip/configs/toy_fleet.json",
                            "reduced": [], "why": "toy"})
    spec["workloads"].append({"name": "toy.cell", "config": "toy_fleet",
                              "traffic": "toy_mix", "chips": 1,
                              "why": "toy"})
    next(m for m in spec["end_to_end"]
         if m["name"] == "windows_per_s")["workloads"].append("toy.cell")
    spec["per_layer"].append({"name": "toy_lag.fleet", "unit": "ms",
                              "better": "lower", "source": "program_span",
                              "layer": "ingest",
                              "moves": "windows_per_s",
                              "workloads": ["toy.cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.resolve_cell(harness.load_spec(tmp_path), "toy.cell",
                                root=tmp_path, base=bench)
    assert cell.config["max_batch"] == 8 and cell.traffic["patients"] == 3
    assert cell.config["system"] == "fleet"
    assert {m["name"] for m in cell.end_to_end} == {
        "windows_per_s", "setup_s"}
    assert [m["name"] for m in cell.per_layer] == ["toy_lag.fleet"]
    assert harness.read_per_layer(cell, {"x": 1.5}, base=bench) == {
        "toy_lag.fleet": 3.0}
    after = _digest(bench)
    assert {k: after[k] for k in before} == before


def test_a_metric_of_a_cell_suffix_is_read_by_its_quantitys_reader(
        tmp_path):
    """``<quantity>.<suffix>`` is read by ``metrics/<quantity>.py`` unless
    a reader of its own exists."""
    m = tmp_path / "metrics"
    m.mkdir()
    (m / "lag.py").write_text("")
    (m / "lag.serve.py").write_text("")
    assert harness.metric_path("lag.fleet", tmp_path) == m / "lag.py"
    assert harness.metric_path("lag.serve", tmp_path) == m / "lag.serve.py"
    assert harness.metric_path("lag", tmp_path) == m / "lag.py"
    assert not harness.metric_path("other.fleet", tmp_path).is_file()


def test_every_cell_of_the_benchmark_resolves():
    for w in SPEC["workloads"]:
        cell = harness.resolve_cell(SPEC, w["name"])
        assert harness.system_path(cell.config["system"]).is_file()
        assert harness.reference_path(cell.config["reference"]).is_file()
        assert harness.generator_path(cell.traffic["generator"]).is_file()
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert harness.metric_path(m["name"]).is_file(), m["name"]


def test_fleet_schedule_is_fixed_by_the_seed():
    a = fleet_gen.Fleet.make(FLEET_CFG, STEADY, 2**31 + 11)
    b = fleet_gen.Fleet.make(FLEET_CFG, STEADY, 2**31 + 11)
    c = fleet_gen.Fleet.make(FLEET_CFG, STEADY, 2**31 + 12)
    for p in (0, 3):
        for k in (0, 1):
            assert a.chunks(p, k) == b.chunks(p, k)
            assert a.send_order(p, k) == b.send_order(p, k)
            np.testing.assert_array_equal(a.segment(p, k)[0],
                                          b.segment(p, k)[0])
            assert sorted(set(a.send_order(p, k))) == list(range(
                len(a.chunks(p, k))))        # every frame sent
        assert a.phase_s(p) == b.phase_s(p)
    assert not np.array_equal(a.segment(0, 1)[0], c.segment(0, 1)[0])
    # the work does not depend on the seed: same windows per segment, and
    # in each format group the same phases, frames and faults, dealt out
    # to the patients in another order
    assert a.segment_bounds(2) == c.segment_bounds(2)
    for pinned in (False, True):
        group = [p for p in range(a.patients) if a.pinned(p) == pinned]
        timing = lambda f: sorted((f.phase_s(p), tuple(f.chunks(p, 1)),
                                   tuple(f.send_order(p, 1)))
                                  for p in group)
        assert timing(a) == timing(c)
        assert sorted(a.slot(p) for p in group) == group
    assert [a.slot(p) for p in range(a.patients)] != [
        c.slot(p) for p in range(c.patients)]
    sig, r = a.segment(1, 1)
    assert sig.shape == (fleet_gen.SEGMENT_WINDOWS * a.window,)
    assert len(r) > 10 and r.min() >= a.segment_bounds(1)[0]


def test_chat_schedule_offers_the_same_work_for_every_seed():
    mix = harness.load_json(harness.traffic_path("chat"))
    a = chat_gen.schedule(mix, 2**31 + 3, 20.0, 1000)
    b = chat_gen.schedule(mix, 2**31 + 3, 20.0, 1000)
    c = chat_gen.schedule(mix, 5, 20.0, 1000)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    # the same sizes at the same moments; other token ids
    shape = lambda s: [(r.arrival_s, len(r.prompt), r.max_new_tokens)
                       for r in s]
    assert shape(a) == shape(b) == shape(c)
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))
    assert len(a) == round(mix["rate_per_s"] * 20.0)
    assert a[-1].arrival_s < 20.0
    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    assert all(lo <= len(r.prompt) <= hi for r in a)
    assert len({len(r.prompt) for r in a}) > 10


class _StalledClient:
    """Stands in for the wire client: every send blocks for STALL seconds,
    as against a receiver that has stopped reading."""

    STALL = 0.5
    sent = []

    def __init__(self, patient, task, lookup, **kw):
        self.stats = types.SimpleNamespace(unconfirmed_closes=0)

    async def send(self, frame):
        if frame.payload is not None:
            _StalledClient.sent.append((time.perf_counter(), frame.seq))
        await asyncio.sleep(self.STALL)

    async def close(self):
        pass


class _Ctrl:
    def __init__(self, window_s):
        self.window_s = window_s
        self.msgs = []

    def send(self, msg):
        self.msgs.append(msg)

    def poll(self):
        return True

    def recv(self):
        self.t_open = time.perf_counter() + 0.05
        return ("open", self.t_open, self.t_open + self.window_s)


def test_generator_stays_open_loop_when_the_receiver_stalls(monkeypatch):
    sys.path.insert(0, str(harness.ROOT / "src"))
    import repro.ingest.client as client
    monkeypatch.setattr(client, "ReplayingClient", _StalledClient)
    _StalledClient.sent = []
    fleet = fleet_gen.Fleet.make(FLEET_CFG, {**STEADY, "patients": 1,
                                             "warm_windows": 1}, 7)
    ctrl = _Ctrl(window_s=2.0)
    rep = asyncio.run(fleet_gen._stream(fleet, "steady", "x", 0, ctrl,
                                        str(harness.ROOT / "src")))
    assert ctrl.msgs[0][0] == "warm_sent" and ctrl.msgs[0][1] >= 4
    late = rep.lateness_s
    # frames are due every 0.1-0.5 s; each send blocks 0.5 s, so the
    # generator falls behind, and keeps the schedule: it neither waits
    # for the receiver nor moves later frames' due times
    assert len(late) >= 4
    assert late[-1] > late[0] + 0.1
    assert all(x >= -1e-3 for x in late)
    # each frame's due time is a function of the schedule alone
    t_open = ctrl.t_open
    ch = fleet.chunks(0, 1)
    due = [fleet_gen.due_time(fleet, 0, b, t_open) for _, b in ch]
    assert due == sorted(due) and due[0] > t_open
    assert due[1] - due[0] == pytest.approx((ch[1][1] - ch[0][1]) / fleet.fs)


def test_an_answer_that_never_came_is_infinitely_late():
    assert harness.percentile([1.0, 2.0, math.inf], 99) == math.inf
    assert harness.percentile([], 50) == math.inf
    assert harness.percentile([3.0, 1.0, 2.0], 50) == 2.0
    assert harness.percentile(list(range(1, 101)), 99) == 99


def _outcome(values, checks, trace=None):
    return harness.Outcome(attempted=10, failed=1, checks=checks,
                           metrics=values, ctx={"host_spans": []},
                           devices=[types.SimpleNamespace(
                               platform="tpu", device_kind="TPU v5 lite")],
                           memory_peak_bytes=123, trace=trace)


def test_the_last_line_has_the_driver_keys_and_checks_last(capsys):
    cell = harness.resolve_cell(SPEC, "serve.chat")
    checks = [harness.Check("missing_windows", 1.0, 0),
              harness.Check("score_mae.posit10", 0.01, 0.03)]
    line = harness.result_line(cell, _outcome(
        {"itl_p95_ms": math.inf, "setup_s": 12.5}, checks),
        trace=False)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is False
    assert line["metrics"]["itl_p95_ms"] == {"value": None, "unit": "ms"}
    assert line["device"] == {"platform": "tpu", "kind": "TPU v5 lite",
                              "count": 1, "memory_peak_bytes": 123}
    harness.emit(line)
    out, err = capsys.readouterr()
    assert json.loads(out.strip().splitlines()[-1]) == line
    assert err.strip().splitlines()[-2:] == [
        "check missing_windows = 1.0 (limit 0)",
        "check score_mae.posit10 = 0.01 (limit 0.03)"]


def test_a_traced_line_carries_busy_window_and_breakdown():
    import trace_reduce as tr
    cell = harness.resolve_cell(SPEC, "fleet.ecg_steady")
    rnd = "%posit_round_2d.7 = f32[8,128]{1,0} custom-call(f32[8,128] %x)"
    # an op that reads the kernel's output is not the kernel
    use = "%reshape.26 = f32[512,2]{1,0} reshape(f32[8,128] %posit_round_2d.7)"
    # 8·128 floats read and written: 8192 B, 10 ns at 819 GB/s, in 1 us
    trace = tr.DeviceTrace({"/device:TPU:0": [("fusion", 0.0, 0.5),
                                              (use, 0.1, 0.2),
                                              (rnd, 0.6, 0.600001)]},
                           0.0, 2.0)
    ctx = {"due": [(0.0, 0.01, 0.02, 0.03)], "ledger": {"windows": 3,
                                                        "padded": 1},
           "compiles": 0, "trace": trace, "host_spans": [],
           "kind": "TPU v5 lite"}
    out = _outcome({}, [harness.Check("path_errors", 0.0, 0)], trace)
    out = dataclasses.replace(out, ctx=ctx)
    line = harness.result_line(cell, out, trace=True)
    assert list(line)[-2:] == ["breakdown", "checks"]
    assert line["device"]["busy_s"] == pytest.approx(0.500001)
    assert line["device"]["window_s"] == 2.0
    m = line["metrics"]
    assert m["device_idle_share.fleet"]["value"] == pytest.approx(75.0,
                                                                 abs=1e-3)
    assert m["posit_round_roofline.fleet"]["value"] == pytest.approx(
        100 * 8192 / 819e9 / 1e-6, rel=1e-3)
    assert m["batch_fill.fleet"]["value"] == pytest.approx(75.0)
    assert m["ingest_lag_p99_ms.fleet"]["value"] == pytest.approx(10.0)
    assert m["window_latency_p99_ms.fleet"]["value"] == pytest.approx(30.0)
    assert set(m) == {x["name"] for x in cell.per_layer}


def test_the_fleet_tail_counts_a_window_never_answered_as_infinite():
    read = harness.load_module(
        harness.metric_path("window_latency_p99_ms.fleet"),
        "chipbench_metric").read
    due = [(float(i), 0.0, 0.0, i + 0.5) for i in range(99)]
    assert read({"due": due}) == pytest.approx(500.0)
    lost = [(0.0, math.inf, math.inf, math.inf)] * 2    # 2 of 101: the p99
    assert read({"due": due + lost}) == math.inf
    assert read({"due": []}) is None
