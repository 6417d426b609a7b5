"""Fault-tolerance layer: persistent result spill, HELLO auth, chaos
fault injection, and worker-pool crash failover.

The pinned contract: faults change *delivery timing*, never results.
Patients untouched by a fault are bit-identical to the fault-free run;
patients on a killed worker are re-delivered and land exactly-once (the
per-patient sha256 digests catch both a missing and a duplicated window).
Recovery is observable — restart/replay/spill counters, not just logs.
"""
import asyncio
import os

import numpy as np
import pytest

from repro.apps.bayeslope import detect_rpeaks
from repro.core.arith import Arith
from repro.distributed.fault_tolerance import RestartPolicy
from repro.ingest import (ChaosPlan, FleetSimulator, IngestServer,
                          ResultSpill, SessionManager, Supervisor,
                          auth_token, data, encode_frame, hello)
from repro.stream import StreamEngine, rpeak_pipeline
from repro.stream.engine import WindowResult

W = 500  # samples per 2 s R-peak window


def _rpeak_engine(**kw):
    return StreamEngine({"rpeak": rpeak_pipeline()}, **kw)


def _offline_prefix(sig_1d, fmt="posit10"):
    n = (len(sig_1d) // W) * W
    return detect_rpeaks(Arith.make(fmt), sig_1d[:n])


# ---------------------------------------------------------------------------
# Result spill: lossless round-trip, torn tail, disk budget
# ---------------------------------------------------------------------------
def _result(patient, widx, **outputs):
    return WindowResult(patient=patient, task="rpeak", widx=widx,
                        fmt="posit10", t0_s=2.0 * widx, outputs=outputs,
                        ready_wall=100.0 + widx, done_wall=101.0 + widx)


def _assert_results_equal(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert (g.patient, g.task, g.widx, g.fmt) == \
            (w.patient, w.task, w.widx, w.fmt)
        assert g.t0_s == w.t0_s
        assert g.ready_wall == w.ready_wall and g.done_wall == w.done_wall
        assert set(g.outputs) == set(w.outputs)
        for k in w.outputs:
            a, b = np.asarray(g.outputs[k]), np.asarray(w.outputs[k])
            assert a.dtype == b.dtype and a.shape == b.shape, k
            np.testing.assert_array_equal(a, b, err_msg=k)


def test_spill_round_trip_is_lossless(tmp_path):
    rng = np.random.default_rng(0)
    rows = [
        _result("p0", 0,
                f32=rng.normal(size=(3, 4)).astype(np.float32),
                f64=rng.normal(size=(7,)),
                # the f64 carrier is exact for integers below 2^53
                big=np.asarray([2**52 + 3, -17], dtype=np.int64)),
        _result("p0", 1, scalar=np.float32(0.5),
                empty=np.zeros((0,), dtype=np.int32)),
        _result("p1", 0, mask=np.asarray([1, 0, 1], dtype=np.uint8)),
    ]
    path = str(tmp_path / "spill.seg")
    with ResultSpill(path) as sp:
        for r in rows:
            assert sp.append(r)
    assert sp.counters()["spilled"] == 3
    assert sp.counters()["spilled_by_patient"] == {"p0": 2, "p1": 1}
    _assert_results_equal(ResultSpill.recover(path), rows)


def test_spill_torn_tail_loses_only_the_last_record(tmp_path):
    rows = [_result("p0", i, x=np.arange(4, dtype=np.float32) + i)
            for i in range(3)]
    path = str(tmp_path / "spill.seg")
    with ResultSpill(path) as sp:
        for r in rows:
            sp.append(r)
    # crash mid-append: tear bytes off the tail — the CRC framing drops
    # the incomplete final record, everything before it survives intact
    with open(path, "r+b") as fh:
        fh.truncate(os.path.getsize(path) - 7)
    _assert_results_equal(ResultSpill.recover(path), rows[:2])


def test_spill_refuses_past_disk_budget(tmp_path):
    path = str(tmp_path / "spill.seg")
    r = _result("p0", 0, x=np.ones((64,), dtype=np.float64))
    with ResultSpill(path, budget_bytes=1 << 20) as sp:
        assert sp.append(r)
        first = sp.bytes_written
    with ResultSpill(str(tmp_path / "tiny.seg"),
                     budget_bytes=first - 1) as sp:
        assert not sp.append(r)          # would break the budget: refused
        assert sp.rejected == 1 and sp.bytes_written == 0
    assert not os.path.exists(str(tmp_path / "tiny.seg"))
    # a missing segment recovers to nothing, not an error
    assert ResultSpill.recover(str(tmp_path / "nope.seg")) == []


# ---------------------------------------------------------------------------
# Supervisor integration: overflow spills instead of dropping; restart
# recovery re-admits the segment
# ---------------------------------------------------------------------------
def test_supervisor_overflow_spills_then_recovers(tmp_path):
    path = str(tmp_path / "worker.seg")
    eng = _rpeak_engine(max_batch=2)
    sup = Supervisor(eng, capacity=2, spill=ResultSpill(path))
    sim = FleetSimulator(n_patients=2, windows=3, seed=2, mixed=False,
                         n_cough=0)
    sim.run_inproc(eng)
    sup.poll()
    # 6 windows through a 2-slot queue: 4 evicted — all PERSISTED, none lost
    assert sup.total_windows == 6 and len(sup.queue) == 2
    assert sup.spilled == 4
    tele = sup.telemetry()
    assert tele["queue"]["dropped"] == 0          # spilled ≠ dropped
    assert tele["queue"]["spilled"] == 4
    assert tele["queue"]["spill_bytes"] > 0
    assert sum(tele["queue"]["spilled_by_patient"].values()) == 4
    assert sup.metrics.counter("spilled_results_total", "").value(
        patient="ecg-000") > 0
    spilled = ResultSpill.recover(path)
    retained = list(sup.queue)
    sup.spill.close()

    # restart recovery: a fresh incarnation re-admits the segment
    eng2 = _rpeak_engine(max_batch=2)
    sup2 = Supervisor(eng2, capacity=64, spill=ResultSpill(path))
    assert sup2.recover_spill() == 4
    _assert_results_equal(list(sup2.queue), spilled)
    # spilled ∪ retained is exactly the 6 windows, no dup, no loss
    keys = {(r.patient, r.task, r.widx) for r in spilled + retained}
    assert len(keys) == 6


# ---------------------------------------------------------------------------
# HELLO auth: unauthenticated connections dropped and counted
# ---------------------------------------------------------------------------
def test_hello_auth_rejects_and_counts():
    async def main():
        eng = _rpeak_engine(max_batch=4)
        sm = SessionManager(eng, stall_timeout_s=60.0)
        async with IngestServer(sm, port=0, auth_secret="s3cret") as srv:
            async def attempt(*frames):
                r, w = await asyncio.open_connection("127.0.0.1", srv.port)
                for f in frames:
                    w.write(encode_frame(f))
                await w.drain()
                got = await r.read()       # server drops the connection
                w.close()
                await w.wait_closed()
                return got

            # no token / wrong token / replayed token bound to another
            # patient: all rejected before any session state exists
            await attempt(hello("p0", "rpeak"))
            await attempt(hello("p0", "rpeak", auth="deadbeef"))
            await attempt(hello("p0", "rpeak",
                                auth=auth_token("s3cret", "p1", "rpeak")))
            # DATA without a verified HELLO on THIS connection: rejected
            await attempt(data("p0", "rpeak", "ecg", 0, np.zeros((1, 8))))
            assert srv.auth_failures == 4
            assert "p0" not in sm.sessions
            assert eng.metrics.counter(
                "ingest_auth_failures_total", "").value() == 4
        return eng
    eng = asyncio.run(main())

    # the real token works end-to-end (full simulated drive, reconnects
    # re-authenticate) and the failure counter stays untouched
    async def authed():
        eng = _rpeak_engine(max_batch=4)
        sm = SessionManager(eng, stall_timeout_s=60.0)
        sim = FleetSimulator(n_patients=2, windows=1, seed=4, mixed=False,
                             n_cough=0, disconnect_every=2,
                             ecg_chunk=(40, 200))
        sim.pin_all(eng)
        async with IngestServer(sm, port=0, auth_secret="s3cret") as srv:
            await sim.run_tcp("127.0.0.1", srv.port, auth_secret="s3cret")
            deadline = asyncio.get_event_loop().time() + 30.0
            while not sm.all_closed():
                assert asyncio.get_event_loop().time() < deadline
                await asyncio.sleep(0.02)
            assert srv.auth_failures == 0
        eng.drain()
        return eng, sim
    eng, sim = asyncio.run(authed())
    assert eng.ledger.transport_summary()["fleet"]["connects"] >= 4


# ---------------------------------------------------------------------------
# Connection-level chaos against a single server: partitions + corruption
# recover to bit-identical streams (replay + CRC + dedup)
# ---------------------------------------------------------------------------
def test_partition_and_corruption_recover_bit_identical():
    sim = FleetSimulator(n_patients=3, windows=2, seed=9, mixed=False,
                         n_cough=0, ecg_chunk=(40, 200))
    plan_ids = [p.patient for p in sim.plans]
    chaos = ChaosPlan(partition_patients=(plan_ids[1],),
                      partition_after_frames=2,
                      corrupt_patients=(plan_ids[2],), corrupt_at_frame=1)
    stats = {}

    async def main():
        eng = _rpeak_engine(max_batch=4)
        sm = SessionManager(eng, stall_timeout_s=30.0)
        sim.pin_all(eng)
        async with IngestServer(sm, port=0, ack=True) as srv:
            # paced drive: at socket speed the whole stream sits in kernel
            # buffers before the server's CRC-close propagates back, and
            # the client would finish without ever noticing the fault
            await sim.run_tcp("127.0.0.1", srv.port, chaos=chaos,
                              realtime_factor=40.0,
                              stats_out=stats, ledger=eng.ledger)
            deadline = asyncio.get_event_loop().time() + 60.0
            while not sm.all_closed():
                assert asyncio.get_event_loop().time() < deadline, \
                    f"sessions never closed: {sm.open_sessions()}"
                await asyncio.sleep(0.02)
        eng.drain()
        return eng
    eng = asyncio.run(main())

    # the faults actually fired…
    assert stats[plan_ids[1]].partitions == 1
    assert stats[plan_ids[2]].corrupted_frames == 1
    assert stats[plan_ids[1]].reconnects >= 1   # partition → reconnect
    assert stats[plan_ids[2]].reconnects >= 1   # CRC drop → reconnect
    # …and every patient (faulted or not) still matches the offline
    # detector bit for bit: replay + server-side dedup = exactly-once
    for p in sim.plans:
        assert eng.tracker_for(p.patient, "rpeak").peaks == \
            _offline_prefix(p.signals["ecg"][0]), p.patient
    ts = eng.ledger.transport_summary()
    assert ts["fleet"]["replayed_frames"] > 0
    assert ts[plan_ids[1]].get("replayed_frames", 0) > 0   # partition
    assert ts[plan_ids[2]].get("replayed_frames", 0) > 0   # corruption
    assert ts[plan_ids[0]].get("replayed_frames", 0) == 0  # untouched


# ---------------------------------------------------------------------------
# Clean close against a server whose event loop is blocked (a jit compile in
# a read handler, as in a pool worker's first dispatch)
# ---------------------------------------------------------------------------
def _drive_blocked_server(block_s, n_patients=4, wait_closed=True,
                          **tcp_kw):
    """Serve from a second thread and event loop (a pool worker's view:
    the clients' loop keeps running while the server's is blocked); the
    first dispatch sleeps ``block_s`` on the server's loop.  Returns
    (sim, engine, sessions, client stats)."""
    import dataclasses
    import threading
    import time

    sim = FleetSimulator(n_patients=n_patients, windows=2, seed=3,
                         mixed=False, n_cough=0, ecg_chunk=(20, 40))
    pipe = rpeak_pipeline()
    blocked = []

    def make_fn(fmt):
        fn = pipe.make_fn(fmt)

        def first_call_blocks(arrays):
            if not blocked:
                blocked.append(fmt)
                time.sleep(block_s)
            return fn(arrays)
        return first_call_blocks

    eng = StreamEngine({"rpeak": dataclasses.replace(pipe, make_fn=make_fn)},
                       max_batch=1)
    sm = SessionManager(eng, stall_timeout_s=120.0)
    sim.pin_all(eng)
    ready, stop, box = threading.Event(), threading.Event(), {}

    def serve():
        async def main():
            # small reads: a stream spans many reads, each answered by ACKs
            async with IngestServer(sm, port=0, ack=True,
                                    read_bytes=512) as srv:
                box["port"] = srv.port
                ready.set()
                while not stop.is_set():
                    await asyncio.sleep(0.01)
        asyncio.run(main())

    th = threading.Thread(target=serve)
    th.start()
    stats = {}
    try:
        assert ready.wait(60.0)
        asyncio.run(sim.run_tcp("127.0.0.1", box["port"], stats_out=stats,
                                flow_control=False, **tcp_kw))
        deadline = time.monotonic() + 60.0
        while (wait_closed and not sm.all_closed()
               and time.monotonic() < deadline):
            time.sleep(0.02)
    finally:
        stop.set()
        th.join()
    eng.drain()
    return sim, eng, sm, stats


def test_close_waits_for_a_server_blocked_in_a_compile():
    """The clients finish sending while the server is blocked for longer
    than a close used to wait (5 s).  Closing then let the server's next
    ACK draw a reset that discarded the frames it had not read: patients
    lost windows and their sessions never closed.  A close now waits for
    the server to read everything, so every stream arrives whole."""
    sim, eng, sm, stats = _drive_blocked_server(6.0)
    assert sm.all_closed(), sm.open_sessions()
    assert sum(s.unconfirmed_closes for s in stats.values()) == 0
    frames = sum(len(c) for p in sim.plans for c in p.chunks.values())
    assert eng.ledger.transport_summary()["fleet"]["frames"] >= frames
    for p in sim.plans:
        assert eng.tracker_for(p.patient, "rpeak").peaks == \
            _offline_prefix(p.signals["ecg"][0]), p.patient


def test_close_timeout_is_bounded_and_counted():
    """A close gives up after ``close_timeout_s`` (liveness against a hung
    server) and counts each close it could not confirm."""
    _, _, _, stats = _drive_blocked_server(1.0, wait_closed=False,
                                           close_timeout_s=0.2)
    assert sum(s.unconfirmed_closes for s in stats.values()) >= 1


# ---------------------------------------------------------------------------
# Worker-pool failover: drain-barrier timeout surfaces, kills recover
# ---------------------------------------------------------------------------
def test_supervise_drain_barrier_timeout_fails_worker():
    from repro.ingest.workers import WorkerConfig, _supervise, _Worker

    class _StubProc:
        exitcode = None

        def __init__(self):
            self.alive = True

        def is_alive(self):
            return self.alive

        def terminate(self):
            self.alive = False

        def kill(self):
            self.alive = False

        def join(self, timeout=None):
            pass

    class _StubConn:
        closed = False

        def poll(self):
            return False

        def close(self):
            self.closed = True

    w = _Worker(wid=0, cfg=WorkerConfig(worker_id=0, tasks=(), pins=()),
                plans=[], proc=_StubProc(), conn=_StubConn(),
                port=5555, phase="draining", drain_deadline=-1.0)

    async def main():
        await asyncio.wait_for(
            _supervise(w, None, RestartPolicy(max_restarts=0), None,
                       start_timeout_s=60.0, hb_timeout_s=None), 10.0)
    proc, conn = w.proc, w.conn
    asyncio.run(main())
    # never waited on forever: the hung worker is killed and surfaced
    assert w.failed == "drain barrier timed out"
    assert not proc.is_alive() and conn.closed
    assert w.port is None      # unpublished: the lookup stops routing here


def _digest_reference(sim, max_batch=8):
    """Fault-free per-patient digests from the in-process driver — what a
    chaos pool run must reproduce bit for bit."""
    from repro.ingest.workers import _result_digests
    ref = _rpeak_engine(max_batch=max_batch, result_capacity=None)
    sim.run_inproc(ref)
    sup = Supervisor(ref, capacity=1 << 16)
    sup.poll()
    return _result_digests(sup)


def test_pool_failover_kill_worker_exactly_once(tmp_path):
    """The fast chaos smoke (CI fast lane): 2 workers, one SIGKILLed
    mid-stream, auth + spill armed.  The pool respawns it, the clients
    replay, and every patient's digest matches the fault-free reference —
    exactly-once, bit-identical, with the recovery counted."""
    from repro.ingest import run_worker_fleet

    sim = FleetSimulator(n_patients=8, windows=2, seed=6, mixed=False,
                         n_cough=0)
    want = _digest_reference(sim)
    doc = run_worker_fleet(
        sim, 2, max_batch=8, realtime_factor=40.0,
        auth_secret="s3cret", spill_dir=str(tmp_path),
        chaos=ChaosPlan(kill_worker=0, kill_after_s=0.4),
        restart_policy=RestartPolicy(max_restarts=3, backoff_s=0.05))

    assert doc["failed_workers"] == []
    assert doc["windows"] == sim.expected_windows() == 16
    assert doc["recovery"]["worker_restarts"] >= 1
    assert doc["recovery"]["recovery_s"]          # measured, not inferred
    assert doc["transport"]["fleet"]["replayed_frames"] > 0
    assert doc["servers"]["auth_failures"] == 0
    assert set(doc["digests"]) == set(want)
    for pid, d in want.items():
        assert doc["digests"][pid] == d, pid


@pytest.mark.slow
def test_chaos_acceptance_64_patients(tmp_path):
    """The acceptance run: a 64-patient fleet across 2 worker processes;
    one worker killed mid-stream, one patient partitioned, one corrupted.
    Unaffected patients bit-identical, failed-over patients exactly-once,
    recovery visible in the rollup.  (ECG-only keeps the reference driver
    cheap; the mixed-fleet chaos soak lives in ``stream_bench --chaos``.)"""
    from repro.ingest import run_worker_fleet

    sim = FleetSimulator(n_patients=64, windows=2, seed=0, mixed=False,
                         n_cough=0)
    want = _digest_reference(sim, max_batch=16)
    ecg = [p.patient for p in sim.plans]
    doc = run_worker_fleet(
        sim, 2, max_batch=16, realtime_factor=40.0,
        auth_secret="s3cret", spill_dir=str(tmp_path),
        chaos=ChaosPlan(kill_worker=0, kill_after_s=0.4,
                        partition_patients=(ecg[-1],),
                        partition_after_frames=2,
                        corrupt_patients=(ecg[-2],), corrupt_at_frame=1),
        restart_policy=RestartPolicy(max_restarts=3, backoff_s=0.05))

    assert doc["failed_workers"] == []
    assert doc["windows"] == sim.expected_windows() == 128
    assert doc["recovery"]["worker_restarts"] >= 1
    assert doc["recovery"]["client"]["partitions"] >= 1
    assert doc["recovery"]["client"]["corrupted_frames"] >= 1
    assert doc["transport"]["fleet"]["replayed_frames"] > 0
    assert set(doc["digests"]) == set(want)
    mismatches = [p for p, d in want.items() if doc["digests"][p] != d]
    assert mismatches == []
