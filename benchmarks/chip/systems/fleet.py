"""Driver of the wearable fleet: the served path as a deployment runs it.

Set-up builds ``StreamEngine`` → ``SessionManager`` → ``IngestServer``
(TCP, ACK/credit flow control) with a ``Supervisor`` draining results,
starts the generator in a process of its own, and lets every patient send
its warm burst, which fills the tracker's reservoir and compiles every
shape.  The window then opens: the generator sends on its schedule
(``steady``) or as fast as the credit allows (``backlog``), and every
result is stamped when the supervisor drains it.  After the close each
patient says BYE; results still owed are waited for, a minute at most.

Then, with the program's work done and the memory peak read, every window
the program scored is held to the float64 reference, and every patient's
confirmed R peaks to the true peaks of its record.
"""
from __future__ import annotations

import asyncio
import math
import multiprocessing
import os
import sys
import types
from typing import Dict, List, Tuple

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import harness  # noqa: E402

WARM_TIMEOUT_S = 900.0       # the first run of a checkout compiles here
DRAIN_S = 60.0               # results owed after the close: a minute
POLL_S = 0.005               # the supervisor's drain interval
OPEN_LEAD_S = 0.25           # from "open" to the window's first instant
WORK_SPANS = ("frame", "dispatch", "drain")   # the program's host work

# limits of the checks; PERF.md gives the readings each was set from
LIMITS = {
    "missing_windows": 0,
    "path_errors": 0,
    "score_mae.posit10": 0.035,
    "score_mae.posit8": 0.13,
    "peak_miss": 0.035,
}


def _repro_src() -> str:
    return str(harness.ROOT / "src")


def _formats(config: dict, fleet, control: bool) -> Tuple[Dict[str, str],
                                                          Dict[int, str]]:
    """(pins the router gets, the stated format of each patient).  The
    control routes every patient one step below what is stated."""
    stated = {p: (config["pinned_format"] if fleet.pinned(p)
                  else config["table_format"])
              for p in range(fleet.patients)}
    if control:
        low = config["control_formats"]
        pins = {fleet.patient_id(p): low[f] for p, f in stated.items()}
    else:
        pins = {fleet.patient_id(p): f for p, f in stated.items()
                if f != config["table_format"]}
    return pins, stated


def run(cell: "harness.Cell", seed: int, seconds: float, trace: bool,
        devs: list, meter: "harness.CompileMeter", t_start: float,
        control: bool = False) -> "harness.Outcome":
    sys.path.insert(0, _repro_src())
    from repro.ingest import IngestServer, SessionManager, Supervisor
    from repro.obs import Tracer
    from repro.stream import PrecisionRouter, StreamEngine, rpeak_pipeline

    gen = harness.load_module(harness.generator_path(
        cell.traffic["generator"]), "chipbench_gen")
    cfg, mix = cell.config, cell.traffic
    if cfg["pad_policy"] != "max":
        raise ValueError("the batch fill counts the rows of batches padded "
                         "to max_batch only")
    fleet = gen.Fleet.make(cfg, mix, seed)
    pins, stated = _formats(cfg, fleet, control)
    tracer = Tracer(capacity=1 << 20) if trace else None
    engine = StreamEngine({cfg["task"]: rpeak_pipeline()},
                          router=PrecisionRouter(patient_formats=pins),
                          max_batch=int(cfg["max_batch"]),
                          pad_policy=cfg["pad_policy"],
                          result_capacity=None, tracer=tracer)
    sessions = SessionManager(engine,
                              stall_timeout_s=float(cfg["stall_timeout_s"]))
    supervisor = Supervisor(engine, capacity=1 << 30)
    drained: List[Tuple[object, float]] = []      # (result, drain time)

    gcm = gen.GcMeter()          # the server's collections, for the log
    ctx_mp = multiprocessing.get_context("spawn")
    n_send = int(mix.get("senders", 1))
    pipes = [ctx_mp.Pipe() for _ in range(n_send)]
    box: Dict[str, object] = {"procs": []}

    async def main() -> None:
        async with IngestServer(sessions, port=0) as srv:
            box["srv"] = srv
            stop = [False]

            async def pump() -> None:
                while not stop[0]:
                    supervisor.poll()
                    t = harness.now()
                    drained.extend((r, t) for r in supervisor.pop())
                    await asyncio.sleep(POLL_S)

            pumping = asyncio.ensure_future(pump())
            for i, (_, child) in enumerate(pipes):
                proc = ctx_mp.Process(
                    target=harness.call_in_file,
                    args=(gen.__file__, "sender_main", child, cfg, mix, seed,
                          "127.0.0.1", srv.port, _repro_src(), i, n_send,
                          seconds),
                    daemon=True)
                proc.start()
                box["procs"].append(proc)
            try:
                await _serve(fleet, engine, sessions,
                             [parent for parent, _ in pipes], box["procs"],
                             drained, box, seconds, trace)
            except BaseException:
                # the server waits for its connections to close: stop the
                # senders, or it waits for ever
                for proc in box["procs"]:
                    proc.kill()
                raise
            finally:
                stop[0] = True
                await pumping
                supervisor.poll()
                t = harness.now()
                drained.extend((r, t) for r in supervisor.pop())

    try:
        asyncio.run(main())
    finally:
        for proc in box["procs"]:
            proc.join(timeout=30)
            if proc.is_alive():
                proc.kill()
                proc.join()
    if box.get("recorder") is not None:
        box["trace"] = box["recorder"].stop(box["t_open"], box["t_close"])
    peak = harness.memory_peak(devs)
    srv = box["srv"]
    rep = box["report"]
    t_open, t_close = box["t_open"], box["t_close"]
    setup_s = t_open - t_start

    # -- what the window measured -------------------------------------------
    by_key = {}
    dups = 0
    for r, t in drained:
        k = (r.patient, r.widx)
        if k in by_key:
            dups += 1
        by_key[k] = (r, t)
    due = []          # (due, ready, done, drained) of windows due in window
    for p in range(fleet.patients if mix["mode"] == "steady" else 0):
        pid = fleet.patient_id(p)
        n_win = rep.samples.get(p, 0) // fleet.window
        for w in range(fleet.warm_windows, n_win):
            d = gen.window_due(fleet, p, w, t_open)
            if not (t_open <= d < t_close):
                continue
            got = by_key.get((pid, w))
            if got is None:
                due.append((d, math.inf, math.inf, math.inf))
            else:
                r, t = got
                due.append((d, r.ready_wall, r.done_wall, t))
    in_window = [r for r, t in drained if t_open <= t < t_close]
    scored = len(in_window)
    lat_ms = [1e3 * (t - d) for d, _, _, t in due]
    metrics = {"setup_s": setup_s,
               "windows_per_s": scored / (t_close - t_open)}
    if mix["mode"] == "steady":
        attempted = len(due)
        failed = sum(1 for v in lat_ms if math.isinf(v))
    else:
        attempted = scored
        failed = 0

    # -- the program's state goes before the reference runs ------------------
    tr = engine.ledger.transport_summary()["fleet"]
    errors = (srv.session_errors + srv.protocol_errors
              + tr["windows_dropped"] + supervisor.dropped
              + engine.dropped_results + dups + rep.unconfirmed_closes
              + box.get("engine_errors", 0))
    ledger = _batch_fill(drained, t_open, t_close, int(cfg["max_batch"]))
    spans = tracer.events() if tracer is not None else []
    del engine, sessions, supervisor

    checks = _reference_checks(cell, fleet, stated, rep, by_key)
    checks.insert(1, harness.Check("path_errors", float(errors),
                                   LIMITS["path_errors"]))
    # the spans of host work name the device's idle gaps; a reorder hold
    # or a window staged for dispatch is waiting, not work
    host_spans = [(f"{ev[1]}/{ev[2]}", ev[3], ev[4]) for ev in spans
                  if ev[0] == "X" and ev[1] in WORK_SPANS]
    ctx = {"mode": mix["mode"], "t_open": t_open, "t_close": t_close,
           "due": due, "ledger": ledger,
           "compiles": meter.between(t_open, t_close),
           "trace": box.get("trace"), "host_spans": host_spans,
           "generator_lateness_s": rep.lateness_s,
           "kind": devs[0].device_kind}
    gcm.close()
    ctx["server_gc"] = gcm.between(t_open, t_close)
    _report_side(rep, ctx, scored, due, t_open, t_close)
    return harness.Outcome(attempted=attempted, failed=failed,
                           checks=checks, metrics=metrics, ctx=ctx,
                           devices=devs, memory_peak_bytes=peak,
                           trace=box.get("trace"))


async def _serve(fleet, engine, sessions, ctrls, procs, drained, box,
                 seconds, trace) -> None:
    """Warm burst, window, close and drain, on the server's event loop."""
    async def message(deadline: float) -> list:
        """One message from every sender."""
        out = []
        for ctrl, proc in zip(ctrls, procs):
            while not ctrl.poll():
                if harness.now() > deadline or not proc.is_alive():
                    if ctrl.poll():
                        break
                    raise RuntimeError("a generator stopped without a word")
                await asyncio.sleep(0.01)
            msg = ctrl.recv()
            if msg[0] == "error":
                raise RuntimeError(f"a generator failed: {msg[1]}")
            out.append(msg[1])
        return out

    deadline = harness.now() + WARM_TIMEOUT_S
    n_frames = sum(await message(deadline))
    # every warm frame read by the session layer, the partial batches
    # dispatched, every result drained
    srv = box["srv"]
    while engine.ledger.transport_summary()["fleet"]["frames"] < n_frames:
        if srv.session_errors or srv.protocol_errors:
            break            # a dropped connection loses frames: the run
                             # is not correct, and its checks say so
        if harness.now() > deadline:
            raise RuntimeError("the warm burst did not arrive")
        await asyncio.sleep(0.01)
    try:
        engine.drain()
    except Exception:        # noqa: BLE001 — the engine failed a dispatch:
        box["engine_errors"] = 1     # the run is not correct, and says so
    await _settle(engine, drained, deadline)

    t_open = harness.now() + OPEN_LEAD_S
    t_close = t_open + seconds
    for ctrl in ctrls:
        ctrl.send(("open", t_open, t_close))
    recorder = None
    if trace:
        from trace_reduce import Recorder
        recorder = Recorder()
        recorder.start()
    await asyncio.sleep(max(t_close - harness.now(), 0.0))
    box["recorder"] = recorder       # stopped once the server is down: its
                                     # files take seconds to write and read
    box["t_open"], box["t_close"] = t_open, t_close

    reps = await message(t_close + 600.0)
    box["report"] = rep = types.SimpleNamespace(
        samples={p: n for r in reps for p, n in r["samples"].items()},
        lateness_s=[x for r in reps for x in r["lateness_s"]],
        frames=sum(r["frames"] for r in reps),
        unconfirmed_closes=sum(r["unconfirmed_closes"] for r in reps),
        gc_in_window=[tuple(x) for r in reps for x in r["gc_in_window"]])
    owed = sum(n // fleet.window for n in rep.samples.values())
    end = harness.now() + DRAIN_S
    while len({(r.patient, r.widx) for r, _ in drained}) < owed:
        if sessions.all_closed():    # every BYE in: what is left is owed
            await _settle(engine, drained, end)
            break
        if harness.now() > end:
            break
        await asyncio.sleep(0.05)


async def _settle(engine, drained, deadline: float) -> None:
    """Wait until the engine's queues and the drained results have not
    changed for a quarter of a second (empty, unless a dispatch failed)."""
    state, quiet_since = None, harness.now()
    while harness.now() < deadline:
        now = (len(drained), engine.pending_windows(), len(engine.results))
        if now != state:
            state, quiet_since = now, harness.now()
        elif harness.now() - quiet_since > 0.25:
            return
        await asyncio.sleep(0.02)


def _batch_fill(drained, t_open: float, t_close: float, max_batch: int
                ) -> Dict[str, int]:
    """Real and padded rows of the dispatches that finished in the window:
    the results of one dispatch share its ``done_wall``, and a dispatch of
    n windows runs ``max_batch`` rows (the configuration pads to max)."""
    per = {}
    for r, _ in drained:
        if t_open <= r.done_wall < t_close:
            per[r.done_wall] = per.get(r.done_wall, 0) + 1
    return {"windows": sum(per.values()),
            "padded": sum(max_batch - n for n in per.values())}


def _reference_checks(cell, fleet, stated, rep, by_key
                      ) -> List["harness.Check"]:
    """Every scored window against the float64 reference, every patient's
    confirmed peaks against its record's true peaks, by stated format."""
    ref = harness.load_module(harness.reference_path(
        cell.config["reference"]), "chipbench_ref")
    tol = int(round(cell.config["peak_tolerance_s"] * fleet.fs))
    margin = 4 * fleet.window            # the tracker's retained tail
    err_sum: Dict[str, float] = {}
    err_n: Dict[str, int] = {}
    match = [0, 0, 0]              # pooled over the fleet: tp, fp, fn
    missing = 0
    for p in range(fleet.patients):
        pid = fleet.patient_id(p)
        fmt = stated[p]
        n_win = rep.samples.get(p, 0) // fleet.window
        if n_win == 0:
            continue
        sig = fleet.signal(p, n_win * fleet.window).reshape(n_win,
                                                            fleet.window)
        want = ref.window_scores(sig)
        peaks = []
        for w in range(n_win):
            got = by_key.get((pid, w))
            if got is None:
                missing += 1
                continue
            r = got[0]
            s = np.asarray(r.outputs["scores"], np.float64)
            err_sum[fmt] = err_sum.get(fmt, 0.0) + float(
                np.abs(s - want[w]).sum())
            err_n[fmt] = err_n.get(fmt, 0) + s.size
            peaks.extend(int(v) for v in np.asarray(r.outputs["peaks"]))
        lo, hi = fleet.fs, n_win * fleet.window - margin
        if hi <= lo:
            continue
        truth = fleet.truth(p, n_win * fleet.window)
        truth = truth[(truth >= lo) & (truth < hi)]
        pk = [v for v in peaks if lo - tol <= v < hi + tol]
        tp, fp, fn = ref.match_peaks(pk, truth, tol)
        for i, v in enumerate((tp, fp, fn)):
            match[i] += v
    checks = [harness.Check("missing_windows", float(missing),
                            LIMITS["missing_windows"])]
    for fmt in sorted(err_n):
        checks.append(harness.Check(f"score_mae.{fmt}",
                                    err_sum[fmt] / err_n[fmt],
                                    LIMITS[f"score_mae.{fmt}"]))
    checks.append(harness.Check("peak_miss", ref.miss_share(*match),
                                LIMITS["peak_miss"]))
    return checks


def _report_side(rep, ctx, scored: int, due, t_open: float,
                 t_close: float) -> None:
    """What a reader of the run's log wants beside the result line: how
    late the generator ran, how much was sent and scored, whether the
    latency grew across the window (a growing backlog), and its tail."""
    late = rep.lateness_s
    mid = 0.5 * (t_open + t_close)
    halves = [[1e3 * (t - d) for d, _, _, t in due if (d < mid) == first]
              for first in (True, False)]
    p50 = [harness.percentile(h, 50) if h else math.nan for h in halves]
    p99 = harness.percentile(halves[0] + halves[1], 99) if due else math.nan
    print(f"fleet: frames_sent={rep.frames} windows_due={len(due)} "
          f"scored_in_window={scored} "
          f"generator_late_p99_ms="
          f"{1e3 * harness.percentile(late, 99) if late else math.nan:.3f} "
          f"latency_p50_ms_first_half={p50[0]:.3f} "
          f"latency_p50_ms_second_half={p50[1]:.3f} "
          f"window_latency_p99_ms={p99:.3f} "
          f"compiles_in_window={ctx['compiles']} "
          f"{_gc_side('server', ctx['server_gc'])} "
          f"{_gc_side('generator', rep.gc_in_window)}", file=sys.stderr)


def _gc_side(who: str, pauses) -> str:
    """Garbage collections begun in the window: how many of generation 2,
    and the longest pause, ms."""
    gen2 = sum(1 for _, g in pauses if g == 2)
    worst = 1e3 * max((d for d, _ in pauses), default=0.0)
    return f"{who}_gc_gen2={gen2} {who}_gc_max_ms={worst:.3f}"
