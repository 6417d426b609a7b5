"""Unified fleet observability: trace spans, the metrics registry, the
scrapeable telemetry plane, and — the load-bearing contract — that NONE of
it changes the numbers.

Three families of guarantee:

* **Exactness** — scraped ``/metrics`` gauges reconcile bit-for-bit with
  ``EnergyLedger.summary()`` / ``Supervisor.telemetry()`` (the bridges
  copy the ledger floats at collect time; there is no second accounting
  path), and multi-worker aggregation concatenates raw histogram samples
  instead of averaging per-worker percentiles.
* **Bit-identity** — the 64-patient TCP fleet with the registry AND the
  span tracer armed produces exactly the outputs, R-peak streams, energy
  totals, and transport counters of the untraced run; instrumentation
  observes the pipeline, never participates in it.
* **Bounded cost** — the tracer ring drops (and counts) instead of
  growing, the null registry is inert, and the jit compile probes show
  two identical dispatch passes share one compiled program.
"""
import asyncio
import json
import warnings

import numpy as np
import pytest

from repro.apps.cough import train_reference_forest
from repro.ingest import (ACK, EVICTED, FleetSimulator, FrameDecoder,
                          IngestServer, ProtocolError, SessionManager,
                          Supervisor, data, evicted, hello)
from repro.obs import (NULL_METRICS, Counter, Gauge, MetricsRegistry,
                       Tracer, http_get, merge_snapshots, parse_prometheus,
                       percentiles, render_snapshot_prometheus,
                       validate_chrome_trace)
from repro.stream import StreamEngine, cough_pipeline, rpeak_pipeline


@pytest.fixture(scope="module")
def forest():
    return train_reference_forest(48, 123, n_trees=5, depth=4)


@pytest.fixture(scope="module")
def pipelines(forest):
    """ONE pipeline dict shared by every engine in this module: the
    memoized make_fn means parity pairs share compiled functions."""
    return {"cough": cough_pipeline(forest), "rpeak": rpeak_pipeline()}


# ---------------------------------------------------------------------------
# Tracer: bounded ring, valid Chrome export
# ---------------------------------------------------------------------------
def test_tracer_ring_drops_oldest_and_counts():
    tr = Tracer(capacity=4)
    for i in range(7):
        t = tr.now()
        tr.complete("stage", f"s{i}", t, t + 1e-6)
    assert len(tr) == 4 and tr.dropped == 3
    # the SURVIVORS are the newest four
    names = [ev[2] for ev in tr.events()]
    assert names == ["s3", "s4", "s5", "s6"]
    doc = tr.chrome_trace()
    assert doc["otherData"]["dropped_events"] == 3
    tr.reset()
    assert len(tr) == 0 and tr.dropped == 0


def test_tracer_chrome_export_is_valid_and_tracked(tmp_path):
    tr = Tracer()
    t0 = tr.now()
    sid = tr.new_id()
    tr.complete("dispatch", "tracker.threshold", t0, t0 + 1e-3,
                track="p-0", parent=sid, key="p-0/3")
    tr.instant("serve", "retire", track="p-0", args={"rid": 3}, key=3)
    tr.complete("dispatch", "cough/posit16", t0, t0 + 2e-3,
                track="dispatch", args={"B": 4}, sid=sid)
    path = tmp_path / "trace.json"
    tr.export(str(path))
    doc = json.loads(path.read_text())
    events = validate_chrome_trace(doc)
    assert len(events) == 3
    assert {e["cat"] for e in events} == {"dispatch", "serve"}
    # spans on the same track share a tid; the metadata names it
    by_name = {e["name"]: e for e in events}
    assert by_name["tracker.threshold"]["tid"] == by_name["retire"]["tid"]
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert {m["args"]["name"] for m in meta} == {"dispatch", "p-0"}
    # the complete span's duration is the recorded wall, in µs
    assert by_name["cough/posit16"]["dur"] == pytest.approx(2e3, rel=1e-6)
    # id, parent and key ride in args, beside the caller's own
    assert by_name["cough/posit16"]["args"] == {"B": 4, "id": sid}
    assert by_name["tracker.threshold"]["args"]["parent"] == sid
    assert by_name["tracker.threshold"]["args"]["key"] == "p-0/3"
    assert by_name["retire"]["args"]["rid"] == 3
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [{"ph": "X", "pid": 0}]})
    with pytest.raises(ValueError):
        validate_chrome_trace({})


def test_tracer_ids_parents_and_self_time():
    tr = Tracer()
    t0 = tr.now()
    parent = tr.new_id()          # taken before its children close
    dev = tr.complete("dispatch", "device", t0, t0 + 1e-3, parent=parent)
    trk = tr.complete("dispatch", "tracker", t0 + 1e-3, t0 + 3e-3,
                      parent=parent, key="p-0/4")
    assert tr.complete("dispatch", "rpeak/posit10", t0, t0 + 4e-3,
                       args={"B": 2}, sid=parent) == parent
    inst = tr.instant("serve", "retire", parent=parent, key=7)
    assert len({parent, dev, trk, inst}) == 4
    evs = tr.events()
    # fields 0-6 keep their places; id, parent and key follow
    assert evs[1] == ("X", "dispatch", "tracker", t0 + 1e-3, t0 + 3e-3,
                      "main", None, trk, parent, "p-0/4")
    ph, cat, name, start, end = evs[2][:5]
    assert (ph, cat, name, start, end) == ("X", "dispatch", "rpeak/posit10",
                                           t0, t0 + 4e-3)
    assert evs[2][5:] == ("main", {"B": 2}, parent, None, None)
    assert evs[3][0] == "i" and evs[3][7:] == (inst, parent, 7)
    # self time: the duration less what the children cover
    st = tr.self_times()
    assert st[parent] == pytest.approx(1e-3)
    assert st[dev] == pytest.approx(1e-3) and st[trk] == pytest.approx(2e-3)
    assert inst not in st


# ---------------------------------------------------------------------------
# Metrics registry: render/parse exactness, kinds, null fast path
# ---------------------------------------------------------------------------
def test_prometheus_render_parse_roundtrips_exact_floats():
    reg = MetricsRegistry()
    c = reg.counter("frames_total", "frames seen")
    c.inc(3, patient="p-0")
    c.inc(0.1 + 0.2, patient="p-1")          # a float that repr must carry
    reg.gauge("nj_per_window", "energy").set(1144.0961538461538, group="fleet")
    h = reg.histogram("latency_seconds", "e2e")
    for v in (0.001, 0.002, 0.004, 0.008):
        h.observe(v, patient="p-0")
    text = reg.render_prometheus()
    assert "# TYPE frames_total counter" in text
    assert "# TYPE nj_per_window gauge" in text
    assert "# TYPE latency_seconds summary" in text
    got = parse_prometheus(text)
    # bit-exact round-trip: repr(float) formatting carries full precision
    assert got[("frames_total", (("patient", "p-0"),))] == 3.0
    assert got[("frames_total", (("patient", "p-1"),))] == 0.1 + 0.2
    assert got[("nj_per_window", (("group", "fleet"),))] == 1144.0961538461538
    assert got[("latency_seconds_count", (("patient", "p-0"),))] == 4.0
    assert got[("latency_seconds_sum", (("patient", "p-0"),))] == 0.015
    q50 = got[("latency_seconds", (("patient", "p-0"), ("quantile", "0.5")))]
    assert q50 == percentiles([0.001, 0.002, 0.004, 0.008])["p50"]


def test_registry_kind_collisions_and_idempotent_handles():
    reg = MetricsRegistry()
    a = reg.counter("x_total")
    assert reg.counter("x_total") is a        # same name → same instrument
    with pytest.raises(TypeError):
        reg.gauge("x_total")
    reg.gauge("g")
    with pytest.raises(TypeError):
        reg.counter("g")
    with pytest.raises(TypeError):
        reg.histogram("g")


def test_registry_reset_clears_values_keeps_registrations():
    reg = MetricsRegistry()
    c = reg.counter("n_total")
    c.inc(5)
    seen = []
    reg.register_collector(lambda: seen.append(1))
    reg.reset()
    assert c.total() == 0.0
    assert reg.counter("n_total") is c
    reg.snapshot()
    assert seen == [1]                        # collector survived the reset


def test_null_registry_is_inert():
    null = NULL_METRICS
    assert not null.enabled
    c = null.counter("anything", "ignored")
    c.inc(5, patient="p")
    null.histogram("h").observe(1.0)
    null.register_collector(lambda: 1 / 0)    # must never run
    assert null.render_prometheus() == ""
    assert null.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}
    assert c.value(patient="p") == 0.0 and c.samples() == []


def test_histogram_reservoir_is_bounded():
    reg = MetricsRegistry()
    h = reg.histogram("lat", reservoir=8)
    for i in range(100):
        h.observe(float(i), patient="p")
    assert h.count(patient="p") == 100        # count survives the ring
    assert h.samples(patient="p") == [float(i) for i in range(92, 100)]


# ---------------------------------------------------------------------------
# Worker aggregation: concat raw samples, never average percentiles
# ---------------------------------------------------------------------------
def test_merged_fleet_p50_is_not_the_mean_of_worker_p50s():
    """The statistical contract behind ``merge_snapshots``: on a skewed
    split, TRUE fleet percentiles (over the concatenated raw samples)
    differ from the mean of per-worker percentiles — so the latter must
    never be what the rollup publishes."""
    a, b = MetricsRegistry(), MetricsRegistry()
    for v in (1.0, 2.0, 3.0, 4.0, 5.0):       # worker A: fast patients
        a.histogram("lat").observe(v)
    for v in (100.0, 200.0, 300.0):           # worker B: three stragglers
        b.histogram("lat").observe(v)
    merged = merge_snapshots([a.snapshot(), b.snapshot()])
    samples = merged["histograms"]["lat"]["series"][0][1]["samples"]
    assert sorted(samples) == [1, 2, 3, 4, 5, 100, 200, 300]
    fleet_p50 = percentiles(samples)["p50"]
    mean_of_p50s = (percentiles([1, 2, 3, 4, 5])["p50"]
                    + percentiles([100, 200, 300])["p50"]) / 2
    assert fleet_p50 != mean_of_p50s
    # and the merged reservoir is exactly what a single-process registry
    # holding all 8 samples would report
    ref = MetricsRegistry()
    for v in (1.0, 2.0, 3.0, 4.0, 5.0, 100.0, 200.0, 300.0):
        ref.histogram("lat").observe(v)
    assert percentiles(ref.histogram("lat").samples()) == \
        percentiles(samples)


def test_merged_counters_sum_exactly_to_in_process_reference():
    """Two 'workers' each metering half the traffic must merge to the
    same counters as one registry metering all of it — per label set,
    exact floats, and the Prometheus rendering of the merge parses back
    to the same values."""
    traffic = [("p-0", 3), ("p-1", 5), ("p-0", 2), ("p-2", 7), ("p-1", 1)]
    workers = [MetricsRegistry(), MetricsRegistry()]
    ref = MetricsRegistry()
    for i, (patient, n) in enumerate(traffic):
        workers[i % 2].counter("windows_total").inc(n, patient=patient)
        ref.counter("windows_total").inc(n, patient=patient)
    merged = merge_snapshots([w.snapshot() for w in workers])
    assert merged["counters"]["windows_total"]["series"] == \
        ref.snapshot()["counters"]["windows_total"]["series"]
    got = parse_prometheus(render_snapshot_prometheus(merged))
    for patient, want in (("p-0", 5.0), ("p-1", 6.0), ("p-2", 7.0)):
        assert got[("windows_total", (("patient", patient),))] == want


# ---------------------------------------------------------------------------
# EVICTED protocol frame
# ---------------------------------------------------------------------------
def test_evicted_frame_roundtrip_and_direction():
    from repro.ingest import encode_frame
    f = evicted("ecg-031", "rpeak", "stall")
    got = FrameDecoder().feed(encode_frame(f))
    assert len(got) == 1
    g = got[0]
    assert g.ftype == EVICTED and g.patient == "ecg-031"
    assert g.task == "rpeak" and g.modality == "stall"   # reason rides here
    assert g.payload is None
    # server-originated only: a client sending it is a protocol error
    eng = StreamEngine({"rpeak": rpeak_pipeline()})
    sm = SessionManager(eng)
    sm.on_frame(hello("p", "rpeak"), now=0.0)
    with pytest.raises(ProtocolError):
        sm.on_frame(evicted("p", "rpeak", "stall"), now=0.0)


def test_evicted_notice_delivery_counted_by_reason():
    """BYE-close and stall-evict both emit an EVICTED notice through the
    registered sender; delivery (or the lack of a sender) is counted."""
    eng = StreamEngine({"rpeak": rpeak_pipeline()})
    sm = SessionManager(eng, stall_timeout_s=1.0)
    sent = []
    sm.register_sender("p-0", sent.append)
    sm.on_frame(hello("p-0", "rpeak"), now=0.0)
    sm.on_frame(data("p-0", "rpeak", "ecg", 0, np.zeros((1, 500))), now=0.0)
    from repro.ingest import bye
    sm.on_frame(bye("p-0", "rpeak"), now=0.5)
    assert len(sent) == 1
    f = FrameDecoder().feed(sent[0])[0]
    assert f.ftype == EVICTED and f.modality == "bye"
    # stall path, no sender registered: counted as undelivered
    sm.on_frame(hello("p-1", "rpeak"), now=1.0)
    sm.on_frame(data("p-1", "rpeak", "ecg", 0, np.zeros((1, 500))), now=1.0)
    assert sm.reap(now=3.0) == ["p-1"]
    c = eng.metrics.counter("ingest_evicted_notices_total")
    assert c.value(reason="bye", delivered="true") == 1
    assert c.value(reason="stall", delivered="false") == 1


def test_evicted_notice_reaches_tcp_client():
    """End-to-end over a real socket: a client that stalls mid-stream
    reads the EVICTED frame off its own connection when the reaper fires."""
    eng = StreamEngine({"rpeak": rpeak_pipeline()})

    async def main():
        sm = SessionManager(eng, stall_timeout_s=0.3)
        async with IngestServer(sm, port=0, reap_interval_s=0.05) as srv:
            from repro.ingest import encode_frame
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", srv.port)
            writer.write(encode_frame(hello("p-0", "rpeak")))
            writer.write(encode_frame(
                data("p-0", "rpeak", "ecg", 0, np.zeros((1, 500)))))
            await writer.drain()
            # go silent; the flow-control ACKs stream first, then the
            # reaper must evict and notify on THIS socket
            dec = FrameDecoder()
            frames = []
            deadline = asyncio.get_event_loop().time() + 5.0
            while not any(f.ftype == EVICTED for f in frames):
                budget = deadline - asyncio.get_event_loop().time()
                raw = await asyncio.wait_for(reader.read(1 << 16),
                                             timeout=max(budget, 0.01))
                frames.extend(dec.feed(raw))
            writer.close()
            return frames

    frames = asyncio.run(main())
    assert frames[-1].ftype == EVICTED
    assert all(f.ftype == ACK for f in frames[:-1])   # the flow-control plane
    assert frames[-1].patient == "p-0" and frames[-1].modality == "stall"
    assert eng.ledger.transport_summary()["p-0"]["evictions"] == 1
    c = eng.metrics.counter("ingest_evicted_notices_total")
    assert c.value(reason="stall", delivered="true") == 1


# ---------------------------------------------------------------------------
# Supervisor: overflow attribution + rate-limited warning
# ---------------------------------------------------------------------------
def test_supervisor_attributes_queue_drops_per_patient(pipelines, forest):
    from repro.data.biosignals import cough_stream_signals
    eng = StreamEngine({"cough": pipelines["cough"]}, max_batch=4,
                       result_capacity=None)
    sup = Supervisor(eng, capacity=3)
    a, i, _ = cough_stream_signals(6, seed=3)
    for k in range(2):
        pid = f"c-{k}"
        eng.ingest(pid, "cough", "audio", a)
        eng.ingest(pid, "cough", "imu", i)
    eng.drain()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        sup.poll()
    # 12 results into a 3-slot queue: 9 drops, oldest-first, attributed
    assert sup.dropped == 9
    by_patient = sup.dropped_by_patient()
    assert sum(by_patient.values()) == sup.dropped
    assert set(by_patient) <= {"c-0", "c-1"}
    # the registry counter IS the attribution (same storage)
    c = eng.metrics.counter("result_queue_dropped_total")
    assert {d["patient"]: int(v) for d, v in c.items()} == by_patient
    # rate-limited: warnings at the 1st, 2nd, 4th, 8th drop — not all 9
    msgs = [str(x.message) for x in w
            if issubclass(x.category, RuntimeWarning)]
    assert len(msgs) == 4
    # the warning names the worst offenders with their counts
    assert "most-dropped" in msgs[-1]
    worst = max(by_patient, key=by_patient.get)
    assert f"{worst}={by_patient[worst]}" in msgs[-1]
    # telemetry carries the same attribution
    tele = sup.telemetry()
    assert tele["queue"]["dropped_by_patient"] == by_patient
    assert tele["queue"]["dropped"] == 9


# ---------------------------------------------------------------------------
# jit compile probes: identical dispatches share a program
# ---------------------------------------------------------------------------
def test_retrace_guard_stable_compile_count_across_identical_passes():
    from repro.core.arith import backend_overrides
    eng = StreamEngine({"rpeak": rpeak_pipeline()}, max_batch=2,
                       result_capacity=None)
    sig = np.random.default_rng(0).normal(size=(1, 1000))
    programs = eng.metrics.counter("jit_programs_total")
    hits = eng.metrics.counter("jit_cache_hits_total")
    eng.ingest("p-0", "rpeak", "ecg", sig)
    eng.drain()
    n0 = programs.total()
    assert n0 >= 1
    # an identical second dispatch must be a pure cache hit
    eng.ingest("p-1", "rpeak", "ecg", sig)
    eng.drain()
    assert programs.total() == n0
    assert hits.total() >= 1
    # flipping the fusion backend is a DIFFERENT program (the cache is
    # keyed on fusion_cache_key, so a stale-backend fn can never serve)
    changes = eng.metrics.counter("jit_fusion_key_changes_total")
    with backend_overrides(fused="off"):
        eng.ingest("p-2", "rpeak", "ecg", sig)
        eng.drain()
    assert programs.total() == n0 + 1
    assert changes.value(site="stream") == 1


# ---------------------------------------------------------------------------
# Reconciliation: /metrics ≡ the ledgers, exactly
# ---------------------------------------------------------------------------
def test_scraped_metrics_reconcile_exactly_with_ledger_and_telemetry(
        pipelines):
    sim = FleetSimulator(n_patients=8, windows=2, seed=5, mixed=True)
    eng = StreamEngine(pipelines, max_batch=8, pad_policy="max",
                       result_capacity=None)
    sup = Supervisor(eng, capacity=512)
    sim.run_inproc(eng)
    sup.poll()
    got = parse_prometheus(eng.metrics.render_prometheus())
    summary = eng.ledger.summary()
    for group, row in summary.items():
        for k, v in row.items():
            assert got[(f"stream_{k}", (("group", group),))] == float(v), \
                (group, k)
    for patient, counters in eng.ledger.transport_summary().items():
        for field, v in counters.items():
            key = ("ingest_transport", (("counter", field),
                                        ("patient", patient)))
            assert got[key] == float(v)
    tele = sup.telemetry()
    assert got[("result_queue_depth", ())] == tele["queue"]["depth"]
    windows = {d["patient"]: int(v) for d, v in
               eng.metrics.counter("stream_windows_total").items()}
    assert sum(windows.values()) == tele["queue"]["total_windows"] == 16
    for pid, row in tele["patients"].items():
        assert row["windows"] == windows[pid]


def test_serving_metrics_reconcile_with_token_ledger():
    import jax

    from repro.configs import CONFIGS, reduced
    from repro.launch.mesh import make_debug_mesh_info
    from repro.models import build_model
    from repro.serve import ServeConfig, ServingEngine
    cfg = reduced(CONFIGS["qwen3-8b"])
    minfo = make_debug_mesh_info()
    with minfo.mesh:
        model = build_model(cfg, minfo)
        params = model.init(jax.random.key(0))
        eng = ServingEngine(model, params,
                            ServeConfig(batch_size=2, max_prompt=8,
                                        max_new_tokens=3, seed=0))
        rng = np.random.default_rng(0)
        for _ in range(2):
            eng.submit(rng.integers(1, cfg.vocab, size=5).astype(np.int32))
        comps = eng.run()
    assert len(comps) == 2
    got = parse_prometheus(eng.metrics.render_prometheus())
    for lane, row in eng.ledger.summary().items():
        for k, v in row.items():
            assert got[(f"serve_{k}", (("lane", lane),))] == float(v), \
                (lane, k)
    comp = eng.metrics.counter("serve_completions_total")
    assert comp.total() == 2
    # one decode program + one prefill-bucket program for the lane
    programs = eng.metrics.counter("jit_programs_total")
    assert programs.total() >= 2


# ---------------------------------------------------------------------------
# Scrape plane over HTTP + the acceptance run: traced ≡ untraced
# ---------------------------------------------------------------------------
def _run_tcp_fleet(engine, sim, stall_timeout_s=1.0, reap_interval_s=0.2,
                   scrape=False):
    """Serve one simulated fleet over localhost TCP until every session
    closes; optionally scrape /metrics + /telemetry mid-flight and return
    (supervisor, scraped_metrics_text, telemetry_json)."""
    sup = Supervisor(engine, capacity=8192)
    scraped = {}

    async def main():
        sm = SessionManager(engine, stall_timeout_s=stall_timeout_s)
        sim.pin_all(engine)
        async with IngestServer(sm, port=0, reap_interval_s=reap_interval_s,
                                supervisor=sup,
                                scrape_port=0 if scrape else None) as srv:
            done = [False]
            pump = asyncio.ensure_future(
                sup.run_async(0.005, stop=lambda: done[0]))
            await sim.run_tcp("127.0.0.1", srv.port)
            deadline = asyncio.get_event_loop().time() + 60.0
            while not sm.all_closed():
                if asyncio.get_event_loop().time() > deadline:
                    raise TimeoutError(
                        f"sessions never closed: {sm.open_sessions()}")
                await asyncio.sleep(0.02)
            done[0] = True
            await pump
            if scrape:
                scraped["metrics"] = await http_get(
                    "127.0.0.1", srv.scrape_port, "/metrics")
                scraped["telemetry"] = json.loads(await http_get(
                    "127.0.0.1", srv.scrape_port, "/telemetry"))
                with pytest.raises(RuntimeError):
                    await http_get("127.0.0.1", srv.scrape_port, "/nope")

    asyncio.run(main())
    engine.drain()
    sup.poll()
    return sup, scraped.get("metrics"), scraped.get("telemetry")


def test_scrape_endpoint_over_live_tcp_fleet(pipelines):
    """The CI fast-lane smoke: a TCP fleet with the scrape plane armed —
    /metrics parses as Prometheus text that reconciles with the ledger,
    /telemetry carries the supervisor view + server counters."""
    sim = FleetSimulator(n_patients=4, windows=2, seed=9, mixed=True)
    eng = StreamEngine(pipelines, max_batch=4, pad_policy="max",
                       result_capacity=None)
    sup, metrics_text, tele = _run_tcp_fleet(eng, sim, scrape=True)
    got = parse_prometheus(metrics_text)
    assert got, "scrape produced no parseable series"
    # scraped-at-runtime counters agree with the final ledger on totals
    # that were already final at scrape time (all sessions closed first)
    ts = eng.ledger.transport_summary()
    assert got[("ingest_transport",
                (("counter", "frames"), ("patient", "fleet")))] == \
        ts["fleet"]["frames"]
    total = sum(v for (name, _), v in got.items()
                if name == "stream_windows_total")
    assert total == sup.total_windows == 8
    assert tele["queue"]["total_windows"] == 8
    assert tele["server"]["connections_total"] >= 4
    assert set(tele["latency_ms"]) == {"p50", "p90", "p99"}


def test_fleet_64_patient_tcp_traced_bit_identical_to_untraced(pipelines):
    """The acceptance run: the full 64-patient TCP fleet (duplicates,
    deferred frames, one mid-stream stall) with the metrics registry AND
    the span tracer armed is bit-identical — window outputs, R-peak
    streams, energy totals, transport counters — to the untraced run,
    and the trace itself is a valid Chrome document spanning the whole
    ingest → dispatch → drain path."""
    def build_sim():
        return FleetSimulator(n_patients=64, windows=2, seed=0, mixed=True,
                              dup_rate=0.05, defer_rate=0.05,
                              stall_after={"ecg-031": 1})

    tracer = Tracer()
    runs = {}
    for arm, kw in (("traced", dict(metrics=MetricsRegistry(),
                                    tracer=tracer)),
                    ("untraced", dict(metrics=NULL_METRICS, tracer=None))):
        eng = StreamEngine(pipelines, max_batch=16, pad_policy="max",
                           result_capacity=None, **kw)
        sup, _, _ = _run_tcp_fleet(eng, build_sim())
        rows = {(r.patient, r.task, r.widx): r for r in sup.pop()}
        runs[arm] = (eng, rows)

    eng_t, rows_t = runs["traced"]
    eng_u, rows_u = runs["untraced"]
    # 1. window outputs: identical key sets, bit-identical arrays
    assert rows_t.keys() == rows_u.keys() and rows_t
    for key, r in rows_t.items():
        ref = rows_u[key]
        assert r.fmt == ref.fmt, key
        for k, v in r.outputs.items():
            np.testing.assert_array_equal(v, ref.outputs[k],
                                          err_msg=f"{key} {k}")
    # 2. R-peak trackers for every delivered stream
    for (patient, task, _w) in rows_t:
        if task != "rpeak":
            continue
        tr_t = eng_t.tracker_for(patient, "rpeak")
        tr_u = eng_u.tracker_for(patient, "rpeak")
        assert (tr_t.peaks if tr_t else []) == \
            (tr_u.peaks if tr_u else []), patient
    # 3. energy ledger: batching-invariant columns identical per group
    st, su = eng_t.ledger.summary(), eng_u.ledger.summary()
    assert st.keys() == su.keys()
    for group in st:
        for col in ("windows", "nj_per_window", "total_nj",
                    "escalated_windows", "escalation_nj"):
            assert st[group][col] == su[group][col], (group, col)
    # 4. transport counters: deterministic per-patient columns identical
    tt, tu = eng_t.ledger.transport_summary(), eng_u.ledger.transport_summary()
    assert tt.keys() == tu.keys()
    for patient in tt:
        for col in ("frames", "bytes", "dup_frames", "reordered_frames",
                    "gap_events", "connects", "evictions"):
            assert tt[patient][col] == tu[patient][col], (patient, col)
    assert tt["ecg-031"]["evictions"] == 1
    assert tt["fleet"]["dup_frames"] > 0      # faults actually injected
    assert tt["fleet"]["reordered_frames"] > 0
    # 5. the trace: valid Chrome JSON covering ≥4 span categories
    events = validate_chrome_trace(tracer.chrome_trace())
    cats = {e["cat"] for e in events}
    assert len(cats) >= 4, cats
    assert {"frame", "dispatch", "drain"} <= cats
    assert "reorder" in cats                  # deferred frames were held


def test_traced_fleet_spans_nest_inside_each_dispatch(pipelines):
    """Each dispatch's children (stage, device, tracker, account) lie
    inside it and cover it; each dispatch's tracker span holds one batched
    threshold launch, whose rows are the dispatch's windows, so the rows
    sum to the windows dispatched; with ACKs on, every read that delivered
    frames is followed by one ``flush_acks``."""
    tracer = Tracer()
    eng = StreamEngine({"rpeak": pipelines["rpeak"]}, max_batch=16,
                       pad_policy="max", result_capacity=None,
                       tracer=tracer)
    sim = FleetSimulator(n_patients=12, windows=2, seed=3, n_cough=0)
    sup, _, _ = _run_tcp_fleet(eng, sim)
    results = sup.pop()
    assert len(results) == 24
    spans = [ev for ev in tracer.events() if ev[0] == "X"]
    by_id = {ev[7]: ev for ev in spans}
    dispatches = [ev for ev in spans if ev[1] == "dispatch" and ev[8] is None]
    assert dispatches
    self_time = tracer.self_times()
    for par in dispatches:
        assert par[2].startswith("rpeak/")
        kids = [ev for ev in spans if ev[8] == par[7]]
        assert [k[2] for k in kids] == ["stage", "device", "tracker",
                                        "account"]
        assert all(par[3] <= k[3] <= k[4] <= par[4] for k in kids)
        assert self_time[par[7]] <= 0.05 * (par[4] - par[3])
    thr = [ev for ev in spans if ev[2] == "tracker.threshold"]
    assert len(thr) == len(dispatches)
    for ev in thr:
        trk = by_id[ev[8]]
        assert trk[2] == "tracker" and trk[3] <= ev[3] <= ev[4] <= trk[4]
        assert ev[6]["rows"] == by_id[trk[8]][6]["B"]
        assert 1 <= ev[6]["depth"] <= ev[6]["rows"]
    assert sum(ev[6]["rows"] for ev in thr) == len(results)
    reads = [ev for ev in spans if ev[1:3] == ("frame", "decode")]
    acks = [ev for ev in spans if ev[1:3] == ("frame", "flush_acks")]
    assert reads and len(acks) == len(reads)
    assert sum(ev[6]["acks"] for ev in acks) > 0
