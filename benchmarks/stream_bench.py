"""Fleet streaming benchmark: ≥64 simulated wearable patients through the
cough and R-peak pipelines concurrently, ragged radio-packet arrival,
per-format throughput (windows/sec) and model energy (nJ/window).

  python benchmarks/stream_bench.py              # 64 patients, warmed run
  python benchmarks/stream_bench.py --smoke      # CI-sized single pass
  python benchmarks/stream_bench.py --patients 128 --windows 10
  python benchmarks/stream_bench.py --json       # + BENCH_stream.json
  python benchmarks/stream_bench.py --escalate   # quality-feedback routing
  python benchmarks/stream_bench.py --transport tcp --smoke --stall 1
                                                 # fleet soak over localhost
                                                 # TCP + a stalled patient
  python benchmarks/stream_bench.py --ab fused,unfused --repeat 3 --json
                                                 # paired fused-vs-oracle
                                                 # medians of alternating runs
  python benchmarks/stream_bench.py --json --ab fused,unfused,codec \
                                    --smoke-baseline   # regenerate the
                                                 # committed record + CI gate
  python benchmarks/stream_bench.py --devices 4  # shard_map dispatch over 4
                                                 # forced host devices
  python benchmarks/stream_bench.py --workers 2 --transport tcp
                                                 # fleet split across worker
                                                 # processes (one engine and
                                                 # GIL per worker)
  python benchmarks/stream_bench.py --json --scaling 1,2,4 \
                                    --scaling-patients 32,64
                                                 # commit the device-count ×
                                                 # fleet-size scaling curve
  python benchmarks/stream_bench.py --smoke --json --quire-ab --repeat 3
                                                 # paired REPRO_QUIRE on/off
                                                 # A/B (µs + nJ + accuracy)
  python benchmarks/stream_bench.py --smoke --trace trace.json
                                                 # export the measured pass
                                                 # as Chrome trace-event
                                                 # JSON (open in Perfetto)
  python benchmarks/stream_bench.py --smoke --json --obs-ab --repeat 3
                                                 # telemetry-plane on/off
                                                 # overhead A/B (CI-gated
                                                 # at a few percent)
  python benchmarks/stream_bench.py --smoke --json --chaos --repeat 1
                                                 # fault harness: worker
                                                 # kill + partition +
                                                 # corrupt, recovery
                                                 # asserted bit-identical,
                                                 # plus the ACK-plane
                                                 # overhead A/B (CI-gated
                                                 # by --chaos-max)

Output follows benchmarks/run.py conventions: ``name,us_per_call,derived``
CSV rows, one per (task, format) group plus a fleet rollup.  ``--json``
additionally writes a machine-readable ``BENCH_stream.json`` (windows/sec,
µs/window, nJ/window per task×format, escalation-rate stats, and the
``transport`` block: frame/gap/dup/eviction counters, end-to-end latency
percentiles, result-queue drops) so the perf trajectory is tracked across
PRs; ``tests/test_stream.py`` pins its schema against the committed copy.

``--transport`` selects the ingest path: ``inproc`` (chunks straight into
the engine — the pre-PR-4 driver and the perf baseline), ``loopback``
(every chunk through the framed wire protocol byte codec + SessionManager,
no sockets), or ``tcp`` (a real asyncio ``IngestServer`` on localhost with
one client connection per patient — the fleet soak configuration).
``--stall N`` silences the last N ECG patients mid-stream so the
stall-timeout eviction policy runs and its counters land in the JSON.
Results drain through the ``repro.ingest.Supervisor`` bounded queue in all
modes — the engine backlog stays flat no matter how long the soak runs.
"""
import argparse
import asyncio
import json
import os
import sys
import time
from statistics import median as _median

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.launch.process import enable_compile_cache, force_host_devices


def build_forest(seed: int = 123):
    from repro.apps.cough import train_reference_forest
    return train_reference_forest(96, seed, n_trees=10, depth=5)


def build_fleet(n_patients: int, n_windows: int, mixed: bool, rng):
    """Per-patient chunk queues: half cough, half ECG; a quarter of each arm
    pinned to an IEEE / narrower-posit comparison format when ``mixed``."""
    from repro.data.biosignals import (cough_stream_signals,
                                      ecg_stream_signal, ragged_chunks)
    from repro.stream.pipelines import RPEAK_WINDOW_S

    queues, pins = [], {}
    n_cough = n_patients // 2
    for p in range(n_patients):
        if p < n_cough:
            pid = f"cough-{p:03d}"
            a, i, _ = cough_stream_signals(n_windows, seed=p)
            queues.append((pid, "cough", "audio",
                           list(ragged_chunks(a, rng, 400, 9600))))
            queues.append((pid, "cough", "imu",
                           list(ragged_chunks(i, rng, 4, 60))))
            if mixed and p % 4 == 3:
                pins[pid] = "fp16"
        else:
            pid = f"ecg-{p - n_cough:03d}"
            s, _ = ecg_stream_signal(n_windows * RPEAK_WINDOW_S, seed=1000 + p)
            queues.append((pid, "rpeak", "ecg",
                           list(ragged_chunks(s[None, :], rng, 50, 1000))))
            if mixed and p % 4 == 3:
                pins[pid] = "posit8"
    return queues, pins


def stream_fleet(engine, queues, rng, supervisor=None):
    """Ragged round-robin arrival across every (patient, modality) stream,
    draining dispatched results through the supervisor as traffic flows."""
    # deep-copy the chunk lists: a warmup pass must not drain the real ones
    queues = [(pid, task, mod, list(chunks))
              for pid, task, mod, chunks in queues]
    live = [q for q in queues if q[3]]
    while live:
        k = int(rng.integers(len(live)))
        pid, task, mod, chunks = live[k]
        engine.ingest(pid, task, mod, chunks.pop(0))
        if not chunks:
            live.pop(k)
        if supervisor is not None:
            supervisor.poll()
    engine.drain()
    engine.finalize_all()
    if supervisor is not None:
        supervisor.poll()


def _build_simulator(patients, windows, mixed, stall, seed):
    from repro.ingest import FleetSimulator
    n_cough = patients // 2
    n_ecg = patients - n_cough
    if stall > n_ecg:
        raise ValueError(f"--stall {stall} exceeds the {n_ecg} ECG patients")
    # silence the LAST `stall` ECG patients after 2 DATA frames: enough for
    # a delivered prefix, early enough that eviction frees real state
    stall_after = {f"ecg-{n_ecg - 1 - k:03d}": 2 for k in range(stall)}
    return FleetSimulator(patients, windows, seed=seed, mixed=mixed,
                          dup_rate=0.02, defer_rate=0.02,
                          stall_after=stall_after)


def _stream_transport(engine, supervisor, sim, transport, stall_timeout_s,
                      arrival_seed):
    """Drive one measured pass over the loopback or TCP transport; returns
    after every session is closed (BYE or evicted)."""
    from repro.ingest import IngestServer, SessionManager

    if transport == "loopback":
        sm = SessionManager(engine, stall_timeout_s=stall_timeout_s)
        sim.run_loopback(sm, arrival_seed=arrival_seed)
        supervisor.poll()
        # loopback has no wall clock to wait on: force the reap horizon
        sm.reap(now=sm.clock() + stall_timeout_s + 1.0)
        supervisor.poll()
        return

    async def tcp_main():
        sm = SessionManager(engine, stall_timeout_s=stall_timeout_s)
        sim.pin_all(engine)
        async with IngestServer(sm, port=0,
                                reap_interval_s=stall_timeout_s / 4) as srv:
            done = [False]
            pump = asyncio.ensure_future(
                supervisor.run_async(0.005, stop=lambda: done[0]))
            await sim.run_tcp("127.0.0.1", srv.port,
                              arrival_seed=arrival_seed,
                              ledger=engine.ledger)
            # stalled patients close only via the reaper: wait for it
            deadline = time.perf_counter() + 4 * stall_timeout_s + 10.0
            while not sm.all_closed():
                if time.perf_counter() > deadline:
                    raise TimeoutError(
                        f"sessions still open past the reap deadline: "
                        f"{sm.open_sessions()}")
                await asyncio.sleep(0.02)
            done[0] = True
            await pump
        supervisor.poll()

    asyncio.run(tcp_main())


# A/B arms: each sets the (fused, round-backend) selection for one full
# alternating pass — "fused" is the default PR-5 backend, "unfused" the
# retained element-per-step/per-op oracles, "codec" additionally swaps the
# posit rounding for the encode∘decode oracle (the deep before).
AB_ARMS = {
    "fused": ("on", None),
    "unfused": ("off", None),
    "codec": ("off", "codec"),
}


def run(patients: int, windows: int, max_batch: int, smoke: bool = False,
        homogeneous: bool = False, escalate: bool = False, seed: int = 0,
        json_path=None, forest=None, transport: str = "inproc",
        stall: int = 0, stall_timeout_s: float = 1.5,
        pad_policy=None, fused=None, round_backend=None, quire=None,
        devices: int = 0, workers: int = 0, obs=None, trace_path=None):
    """Build and stream the fleet; returns the machine-readable result doc
    (and writes it to ``json_path`` when given).

    ``fused``/``round_backend``/``quire`` override the backend selection
    for this run only (the A/B harnesses alternate them); ``None`` keeps
    the process-wide setting.  ``devices > 1`` shards every dispatch over a
    forced host device mesh (the caller must have set XLA_FLAGS before jax
    imported — ``main()`` does); ``workers > 1`` partitions the fleet
    across spawned worker processes instead (TCP transport only).

    ``obs`` selects the telemetry plane for this run: ``None`` keeps the
    engine default (a live metrics registry, no tracer), ``"on"`` arms the
    registry AND a span tracer, ``"off"`` installs the null registry so
    every instrument call is a no-op — the ``--obs-ab`` overhead gate
    alternates "on"/"off".  ``trace_path`` exports the measured pass's
    spans as Chrome trace-event JSON (implies a tracer).
    """
    from repro.core.arith import backend_overrides

    if obs not in (None, "on", "off"):
        raise ValueError(f"unknown obs mode {obs!r} (None, 'on' or 'off')")
    if transport not in ("inproc", "loopback", "tcp"):
        raise ValueError(f"unknown transport {transport!r}")
    if stall and transport == "inproc":
        raise ValueError("--stall needs a transport (loopback or tcp): "
                         "the in-process driver has no stall clock")
    if workers and workers > 1:
        if transport != "tcp":
            raise ValueError("--workers needs --transport tcp: the pool IS "
                             "a set of TCP ingest servers")
        if escalate:
            raise ValueError("--escalate is per-engine state; not supported "
                             "across --workers yet")
        if fused is not None or round_backend is not None or quire is not None:
            raise ValueError("A/B backend overrides do not cross the "
                             "worker-pool spawn boundary")
        if obs is not None or trace_path:
            raise ValueError("--trace/--obs-ab run in-process; worker-pool "
                             "telemetry is the per-worker metrics snapshot "
                             "rollup (and --scrape on the workers)")
        return _run_workers(patients, windows, max_batch, smoke,
                            homogeneous, seed, json_path, stall,
                            stall_timeout_s, pad_policy, devices, workers)
    if forest is None:
        t0 = time.perf_counter()
        forest = build_forest()
        print(f"# forest trained in {time.perf_counter() - t0:.1f}s",
              file=sys.stderr)

    with backend_overrides(
            fused=None if fused is None else ("on" if fused else "off"),
            round_backend=round_backend, quire=quire):
        return _run_measured(patients, windows, max_batch, smoke,
                             homogeneous, escalate, seed, json_path, forest,
                             transport, stall, stall_timeout_s, pad_policy,
                             devices, obs, trace_path)


def _run_measured(patients, windows, max_batch, smoke, homogeneous,
                  escalate, seed, json_path, forest, transport, stall,
                  stall_timeout_s, pad_policy, devices=0, obs=None,
                  trace_path=None):
    import jax

    from repro.core.arith import (get_fused_kernels, get_quire,
                                  get_round_backend)
    from repro.ingest import Supervisor
    from repro.obs import NULL_METRICS, Tracer
    from repro.stream import (EscalationPolicy, PrecisionRouter,
                              StreamEngine, cough_pipeline, rpeak_pipeline)

    metrics = NULL_METRICS if obs == "off" else None   # None = live default
    tracer = Tracer() if (obs == "on" or trace_path) else None
    rng = np.random.default_rng(seed)
    mixed = not homogeneous
    sim = None
    if transport == "inproc":
        queues, pins = build_fleet(patients, windows, mixed=mixed, rng=rng)
    else:
        sim = _build_simulator(patients, windows, mixed, stall, seed)
        queues, pins = None, sim.pins
    mesh_info = None
    if devices > 1:
        from repro.launch.mesh import make_fleet_mesh_info
        mesh_info = make_fleet_mesh_info(devices)
    engine = StreamEngine({"cough": cough_pipeline(forest),
                           "rpeak": rpeak_pipeline()},
                          router=PrecisionRouter(
                              patient_formats=pins,
                              escalation=EscalationPolicy() if escalate
                              else None),
                          max_batch=max_batch,
                          # one compiled shape per arm unless overridden
                          pad_policy=pad_policy or "max",
                          mesh_info=mesh_info,
                          metrics=metrics, tracer=tracer)
    supervisor = Supervisor(engine, capacity=4096)

    if not smoke:  # warm the compile caches, then measure steady state
        t0 = time.perf_counter()
        if transport == "inproc":
            stream_fleet(engine, queues, np.random.default_rng(seed + 1),
                         supervisor)
        else:
            sim.run_inproc(engine, arrival_seed=seed + 1)
            supervisor.poll()
        print(f"# warmup pass in {time.perf_counter() - t0:.1f}s "
              f"(pad strategy: {engine.pad_strategy()})", file=sys.stderr)
        engine.reset()
        if tracer is not None:
            tracer.reset()   # the exported trace covers the measured pass
        supervisor = Supervisor(engine, capacity=4096)

    t0 = time.perf_counter()
    if transport == "inproc":
        stream_fleet(engine, queues, np.random.default_rng(seed + 2),
                     supervisor)
    else:
        _stream_transport(engine, supervisor, sim, transport,
                          stall_timeout_s, arrival_seed=seed + 2)
    wall = time.perf_counter() - t0

    n = supervisor.total_windows
    expect = patients * windows  # every patient emits each window
    if stall == 0:
        assert n == expect, f"windows processed {n} != expected {expect}"
    else:  # stalled patients deliver only a prefix
        assert (patients - stall) * windows <= n <= expect, (n, expect)
    groups = {}
    for key, row in engine.fleet_summary().items():
        us = 1e6 / row["windows_per_s"] if row["windows_per_s"] else 0.0
        groups[key] = {"us_per_window": us, **row}
    esc = engine.ledger.escalation_summary()
    esc_windows = sum(int(d["windows"]) for d in esc.values())
    tele = supervisor.telemetry()
    doc = {
        "benchmark": "stream_bench",
        "config": {"patients": patients, "windows": windows,
                   "max_batch": max_batch, "smoke": smoke,
                   "homogeneous": homogeneous, "escalate": escalate,
                   "seed": seed, "backend": jax.default_backend(),
                   "round_backend": get_round_backend(),
                   "fused_kernels": "on" if get_fused_kernels() else "off",
                   "quire": "on" if get_quire() else "off",
                   "transport": transport, "stall": stall,
                   "pad_strategy": engine.pad_strategy(),
                   "devices": max(1, devices), "workers": 1,
                   "obs": obs or "default",
                   # wall-clock provenance of the groups' timing columns:
                   # a single measured pass, unless the --ab harness
                   # overrides them with its fused-arm medians
                   "measured": "single_pass"},
        "groups": groups,
        "ab": None,             # filled by the --ab paired harness
        "obs_ab": None,         # filled by the --obs-ab overhead harness
        "quire_ab": None,       # filled by the --quire-ab paired harness
        "chaos": None,          # filled by the --chaos fault harness
        "smoke_baseline": None,  # filled by --smoke-baseline (CI perf gate)
        "scaling": None,        # filled by the --scaling curve harness
        "microbench": None,     # filled by --microbench
        "escalation": {
            "patients": esc,
            "windows_escalated": esc_windows,
            "extra_nj": sum(d["extra_nj"] for d in esc.values()),
            "rate": esc_windows / n if n else 0.0,
        },
        "transport": {
            "mode": transport,
            "counters": engine.ledger.transport_summary()["fleet"],
            "latency_ms": tele["latency_ms"],
            "result_queue": tele["queue"],
            "workers": None,    # per-worker rows (worker-pool runs only)
            "servers": None,    # summed server counters (worker-pool runs)
        },
        "wall": {"elapsed_s": wall, "windows": n,
                 "end_to_end_windows_per_s": n / wall},
    }
    if trace_path:
        tracer.export(trace_path)
        print(f"# wrote {trace_path} ({len(tracer)} spans, "
              f"{len(tracer.categories())} categories, "
              f"{tracer.dropped} dropped)", file=sys.stderr)
    if json_path:
        write_json(doc, json_path)
    return doc


def _run_workers(patients, windows, max_batch, smoke, homogeneous, seed,
                 json_path, stall, stall_timeout_s, pad_policy, devices,
                 workers):
    """Worker-pool measured pass: the fleet partitioned across spawned
    processes (each a full TCP ingest server + device-local engine), the
    per-worker telemetry merged into the standard doc shape."""
    import jax

    from repro.core.arith import (get_fused_kernels, get_quire,
                                  get_round_backend)
    from repro.ingest.workers import run_worker_fleet

    sim = _build_simulator(patients, windows, not homogeneous, stall, seed)
    roll = run_worker_fleet(sim, workers, devices=devices,
                            max_batch=max_batch,
                            pad_policy=pad_policy or "max",
                            stall_timeout_s=stall_timeout_s,
                            arrival_seed=seed + 2)
    n = roll["windows"]
    expect = patients * windows
    if stall == 0:
        assert n == expect, f"windows processed {n} != expected {expect}"
    else:
        assert (patients - stall) * windows <= n <= expect, (n, expect)
    groups = {}
    for key, row in roll["groups"].items():
        us = 1e6 / row["windows_per_s"] if row["windows_per_s"] else 0.0
        groups[key] = {"us_per_window": us, **row}
    esc = roll["escalation"]
    esc_windows = sum(int(d["windows"]) for d in esc.values())
    doc = {
        "benchmark": "stream_bench",
        "config": {"patients": patients, "windows": windows,
                   "max_batch": max_batch, "smoke": smoke,
                   "homogeneous": homogeneous, "escalate": False,
                   "seed": seed, "backend": jax.default_backend(),
                   "round_backend": get_round_backend(),
                   "fused_kernels": "on" if get_fused_kernels() else "off",
                   "quire": "on" if get_quire() else "off",
                   "transport": "tcp", "stall": stall,
                   "pad_strategy": pad_policy or "max",
                   "devices": max(1, devices), "workers": workers,
                   "obs": "default",
                   "measured": "worker_pool"},
        "groups": groups,
        "ab": None,
        "obs_ab": None,
        "quire_ab": None,
        "chaos": None,
        "smoke_baseline": None,
        "scaling": None,
        "microbench": None,
        "escalation": {
            "patients": esc,
            "windows_escalated": esc_windows,
            "extra_nj": sum(d["extra_nj"] for d in esc.values()),
            "rate": esc_windows / n if n else 0.0,
        },
        "transport": {
            "mode": "tcp",
            "counters": roll["transport"]["fleet"],
            "latency_ms": roll["latency_ms"],
            "result_queue": roll["result_queue"],
            "workers": roll["workers"],
            "servers": roll["servers"],
        },
        "wall": {"elapsed_s": roll["wall_s"], "windows": n,
                 "end_to_end_windows_per_s": n / roll["wall_s"]},
    }
    if json_path:
        write_json(doc, json_path)
    return doc


def run_microbench(devices: int = 0, batch: int = 32, reps: int = 30,
                   fmt: str = "posit16"):
    """Per-device dispatch microbenchmark: one fixed-shape R-peak batch
    through the warmed compiled window fn — sharded over the device mesh
    when ``devices > 1`` — isolating the dispatch floor (device transfer +
    kernel + materialization) from ingest/session overhead."""
    import jax

    from repro.stream import rpeak_pipeline

    pipe = rpeak_pipeline()
    fn = pipe.make_fn(fmt)
    rng = np.random.default_rng(0)
    arrays = {m.name: rng.normal(size=(
        batch, m.channels, pipe.spec.window_samples(m))).astype(np.float32)
        for m in pipe.spec.modalities}
    if devices > 1:
        from repro.distributed.sharding import fleet_pad, make_fleet_batch_fn
        from repro.launch.mesh import make_fleet_mesh_info
        B = fleet_pad(batch, devices)
        arrays = {k: np.concatenate(
            [v, np.zeros((B - batch,) + v.shape[1:], np.float32)])
            for k, v in arrays.items()}
        mask = np.zeros((B,), np.int32)
        mask[:batch] = 1
        sfn = make_fleet_batch_fn(fn, make_fleet_mesh_info(devices))

        def call():
            return sfn(arrays, mask)[0]
    else:
        def call():
            return fn(arrays)
    jax.block_until_ready(call())          # compile + warm
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(call())
        times.append(time.perf_counter() - t0)
    us = _median(times) * 1e6
    return {"task": "rpeak", "fmt": fmt, "batch": batch, "reps": reps,
            "devices": max(1, devices),
            "us_per_dispatch": us, "us_per_window": us / batch}


def run_scaling(device_counts, patient_counts, windows, max_batch, seed):
    """The committed scaling curve: one COLD subprocess per (device count,
    fleet size) grid point — the forced XLA host-device split must be set
    before jax imports, so every point needs its own process — each a full
    warmed run plus the per-device dispatch microbenchmark, so the curve
    measures steady-state throughput, not compile time."""
    import subprocess
    import tempfile
    grid = []
    for d in device_counts:
        for p in patient_counts:
            print(f"# scaling point devices={d} patients={p}",
                  file=sys.stderr)
            with tempfile.TemporaryDirectory() as tmp:
                path = os.path.join(tmp, "scaling.json")
                subprocess.run([sys.executable, os.path.abspath(__file__),
                                "--patients", str(p),
                                "--windows", str(windows),
                                "--max-batch", str(max_batch),
                                "--seed", str(seed),
                                "--devices", str(d),
                                "--microbench", "--json", path],
                               check=True)
                with open(path) as f:
                    sdoc = json.load(f)
            grid.append({"devices": max(1, d), "patients": p,
                         "fleet": sdoc["groups"]["fleet"],
                         "wall": sdoc["wall"],
                         "microbench": sdoc["microbench"]})
    return {"windows": windows, "max_batch": max_batch, "workers": 1,
            "grid": grid}


def write_json(doc, json_path):
    with open(json_path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# wrote {json_path}", file=sys.stderr)


def run_ab(arms, repeat, forest, **kwargs):
    """Paired A/B: ``repeat`` ALTERNATING full runs per arm (arm order
    cycles within each round, so machine drift hits every arm equally),
    per-group medians and the unfused/fused ratio."""
    if repeat < 1:
        raise ValueError(f"--repeat must be >= 1, got {repeat}")
    for arm in arms:
        if arm not in AB_ARMS:
            raise ValueError(f"unknown A/B arm {arm!r} "
                             f"(choose from {sorted(AB_ARMS)})")
    passes = {arm: [] for arm in arms}
    for r in range(repeat):
        # rotate the start arm each round so monotonic machine drift
        # (thermal ramp, cache warmup) doesn't systematically favour it
        order = list(arms[r % len(arms):]) + list(arms[:r % len(arms)])
        for arm in order:
            fused_mode, rb = AB_ARMS[arm]
            print(f"# ab pass {r + 1}/{repeat} arm={arm}", file=sys.stderr)
            doc = run(forest=forest, fused=(fused_mode == "on"),
                      round_backend=rb, **kwargs)
            passes[arm].append(doc)
    out = {"repeat": repeat, "arms": {}}
    for arm, docs in passes.items():
        groups = {}
        for key in docs[0]["groups"]:
            groups[key] = {
                "us_per_window": _median(
                    [d["groups"][key]["us_per_window"] for d in docs]),
                "windows_per_s": _median(
                    [d["groups"][key]["windows_per_s"] for d in docs]),
            }
        out["arms"][arm] = {
            "groups": groups,
            "wall_s": _median([d["wall"]["elapsed_s"] for d in docs]),
        }
    if "fused" in passes and "unfused" in passes:
        out["ratio"] = {
            key: (out["arms"]["unfused"]["groups"][key]["us_per_window"]
                  / out["arms"]["fused"]["groups"][key]["us_per_window"])
            for key in out["arms"]["fused"]["groups"]
            if out["arms"]["fused"]["groups"][key]["us_per_window"]
        }
    return out


def run_obs_ab(repeat, forest, **kwargs):
    """Paired observability-overhead A/B: ``repeat`` alternating full runs
    with the telemetry plane armed ("on": live registry + span tracer)
    versus disabled ("off": null registry, no tracer), fleet-row medians
    and the on/off µs/window ratio — the number the check_perf overhead
    gate reads (instrumentation must stay within a few percent of free)."""
    if repeat < 1:
        raise ValueError(f"--repeat must be >= 1, got {repeat}")
    passes = {"on": [], "off": []}
    for r in range(repeat):
        # alternate the start arm so monotonic machine drift (thermal
        # ramp, page-cache warmup) doesn't systematically favour one
        order = ("on", "off") if r % 2 == 0 else ("off", "on")
        for arm in order:
            print(f"# obs_ab pass {r + 1}/{repeat} arm={arm}",
                  file=sys.stderr)
            doc = run(forest=forest, obs=arm, **kwargs)
            passes[arm].append(doc)
    out = {"repeat": repeat, "arms": {}}
    for arm, docs in passes.items():
        out["arms"][arm] = {
            "fleet_us_per_window": _median(
                [d["groups"]["fleet"]["us_per_window"] for d in docs]),
            "fleet_windows_per_s": _median(
                [d["groups"]["fleet"]["windows_per_s"] for d in docs]),
            "wall_s": _median([d["wall"]["elapsed_s"] for d in docs]),
        }
    off_us = out["arms"]["off"]["fleet_us_per_window"]
    out["ratio"] = (out["arms"]["on"]["fleet_us_per_window"] / off_us
                    if off_us else 0.0)
    return out


def run_chaos(patients, windows, max_batch, stall_timeout_s, pad_policy,
              seed, repeat=1, workers=2, realtime_factor=40.0):
    """The fault harness: a worker-pool fleet under injected faults, plus
    the flow-control overhead A/B.

    **Soak** — one fault-free reference pass, then one pass with a fault
    schedule (worker 0 SIGKILLed mid-stream, one patient's connection
    partitioned, one patient's frame corrupted in flight) over the SAME
    replay.  The recovery contract is asserted, not just reported: every
    delivered window recovered (respawn + HELLO reconnect-replay), every
    patient's result digest bit-identical to the fault-free run —
    unaffected patients untouched, failed-over patients exactly-once.

    **Overhead** — ``repeat`` alternating fault-free pool passes with the
    ACK/credit plane armed vs disabled (the PR-4 wire behaviour); medians
    and the on/off µs/window ratio, which ``check_perf --chaos-max`` gates
    (resilience must ride along nearly free when nothing fails).
    """
    from repro.ingest import ChaosPlan
    from repro.ingest.workers import run_worker_fleet

    def fleet(ack, chaos=None, rt=0.0):
        sim = _build_simulator(patients, windows, True, 0, seed)
        return run_worker_fleet(
            sim, workers, max_batch=max_batch, pad_policy=pad_policy
            or "max", stall_timeout_s=stall_timeout_s,
            arrival_seed=seed + 2, ack=ack, chaos=chaos,
            realtime_factor=rt)

    print("# chaos soak: fault-free reference pass", file=sys.stderr)
    ref = fleet(ack=True)
    # fault schedule: kill the first worker mid-stream (realtime pacing
    # stretches the drive so the kill lands while frames are in flight),
    # partition one surviving patient, corrupt one frame in flight
    victims = sorted(ref["digests"])
    # early triggers: even a smoke-sized stream has ≥2 DATA frames, so the
    # partition and corruption demonstrably fire (asserted below)
    plan = ChaosPlan(kill_worker=0, kill_after_s=0.4,
                     partition_patients=(victims[-1],),
                     partition_after_frames=2,
                     corrupt_patients=(victims[-2],), corrupt_at_frame=1)
    print("# chaos soak: faulted pass (kill worker 0 + partition + "
          "corrupt)", file=sys.stderr)
    doc = fleet(ack=True, chaos=plan, rt=realtime_factor)
    matches = sum(1 for p, d in ref["digests"].items()
                  if doc["digests"].get(p) == d)
    expect = patients * windows
    assert not doc["failed_workers"], doc["failed_workers"]
    assert doc["windows"] == expect, (doc["windows"], expect)
    assert matches == len(ref["digests"]) == patients, (
        f"digest mismatch: {matches}/{len(ref['digests'])} patients "
        f"bit-identical to the fault-free run")
    cl = doc["recovery"]["client"]
    assert doc["recovery"]["worker_restarts"] >= 1
    assert cl["partitions"] >= 1 and cl["corrupted_frames"] >= 1, cl
    soak = {
        "patients": patients, "windows": doc["windows"],
        "worker_killed": plan.kill_worker,
        "worker_restarts": doc["recovery"]["worker_restarts"],
        "recovery_s": doc["recovery"]["recovery_s"],
        "client": doc["recovery"]["client"],
        "digest_matches": matches, "digest_total": len(ref["digests"]),
        "failed_workers": doc["failed_workers"],
        "result_queue": doc["result_queue"],
    }

    passes = {"ack_on": [], "ack_off": []}
    for r in range(repeat):
        order = (("ack_on", "ack_off") if r % 2 == 0
                 else ("ack_off", "ack_on"))
        for arm in order:
            print(f"# chaos overhead pass {r + 1}/{repeat} arm={arm}",
                  file=sys.stderr)
            passes[arm].append(fleet(ack=(arm == "ack_on")))
    arms = {}
    for arm, docs in passes.items():
        # end-to-end µs/window (wall / windows): the ACK/credit/heartbeat
        # work lives on the server's event loop and the client's pacing,
        # not in engine dispatch — only the end-to-end clock sees it
        arms[arm] = {
            "fleet_us_per_window": _median(
                [1e6 * d["wall_s"] / d["windows"] if d["windows"] else 0.0
                 for d in docs]),
            "wall_s": _median([d["wall_s"] for d in docs]),
        }
    off_us = arms["ack_off"]["fleet_us_per_window"]
    return {"repeat": repeat, "workers": workers, "soak": soak,
            "overhead": {
                "arms": arms,
                "ratio": (arms["ack_on"]["fleet_us_per_window"] / off_us
                          if off_us else 0.0)}}


def _quire_ab_inputs(forest, batch):
    """The two acceptance sweeps: one real cough batch (posit16) and one
    real ECG batch (posit8), each with its pipeline and the output key the
    accuracy comparison reads."""
    from repro.data.biosignals import cough_stream_signals, ecg_stream_signal
    from repro.stream import cough_pipeline, rpeak_pipeline
    from repro.stream.pipelines import RPEAK_WINDOW_S

    cough = cough_pipeline(forest)
    a, i, _ = cough_stream_signals(batch, seed=7)
    ca = {"audio": a.reshape(a.shape[0], batch, -1).swapaxes(0, 1).copy(),
          "imu": i.reshape(i.shape[0], batch, -1).swapaxes(0, 1).copy()}
    rpeak = rpeak_pipeline()
    s, _ = ecg_stream_signal(batch * RPEAK_WINDOW_S, seed=11)
    ra = {"ecg": s.reshape(batch, 1, -1).copy()}
    return [("cough", "posit16", cough, ca, "p_cough"),
            ("rpeak", "posit8", rpeak, ra, "scores")]


def run_quire_ab(forest, repeat=3, batch=16):
    """Paired quire-on/off A/B on the acceptance sweeps (cough/posit16,
    rpeak/posit8): µs/window of the warmed jitted window core, nJ/window
    from the ledger pricing (QMADD…QROUND vs per-op rounding), and
    accuracy as mean |output − fp32 reference| per arm — the
    accuracy-per-nJ trade the quire exists to buy."""
    import jax

    from repro.core.arith import backend_overrides
    from repro.stream.accounting import window_energy_nj

    out = {"repeat": repeat, "batch": batch, "tasks": {}}
    for task, fmt, pipe, arrays, key in _quire_ab_inputs(forest, batch):
        with backend_overrides(quire="off"):
            fn32 = pipe.make_fn("fp32")
            ref = np.asarray(jax.block_until_ready(fn32(arrays))[key],
                             dtype=np.float64)
        row = {}
        for arm in ("off", "on"):
            print(f"# quire_ab {task}/{fmt} arm={arm}", file=sys.stderr)
            with backend_overrides(quire=arm):
                fn = pipe.make_fn(fmt)
                got = jax.block_until_ready(fn(arrays))   # compile + warm
                times = []
                for _ in range(repeat):
                    t0 = time.perf_counter()
                    jax.block_until_ready(fn(arrays))
                    times.append(time.perf_counter() - t0)
                err = float(np.mean(np.abs(
                    np.asarray(got[key], dtype=np.float64) - ref)))
                row[arm] = {
                    "us_per_window": _median(times) * 1e6 / batch,
                    "nj_per_window": window_energy_nj(
                        pipe.ops_per_window, fmt, quire=(arm == "on")),
                    "err_vs_fp32": err,
                }
        off, on = row["off"], row["on"]
        row["us_ratio"] = (on["us_per_window"] / off["us_per_window"]
                           if off["us_per_window"] else 0.0)
        row["nj_ratio"] = (on["nj_per_window"] / off["nj_per_window"]
                           if off["nj_per_window"] else 0.0)
        row["err_delta"] = off["err_vs_fp32"] - on["err_vs_fp32"]
        out["tasks"][f"{task}/{fmt}"] = row
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--patients", type=int, default=None,
                    help="fleet size (default 64; 8 with --smoke)")
    ap.add_argument("--windows", type=int, default=None,
                    help="windows per patient (default 4; 2 with --smoke)")
    ap.add_argument("--max-batch", type=int, default=None,
                    help="dispatch batch cap (default 32; 8 with --smoke)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized defaults + no warmup pass")
    ap.add_argument("--homogeneous", action="store_true",
                    help="paper-table formats only (no fp16/posit8 arms)")
    ap.add_argument("--escalate", action="store_true",
                    help="arm the quality-feedback precision escalation "
                         "policy (posit8→posit10→posit16)")
    ap.add_argument("--transport", choices=("inproc", "loopback", "tcp"),
                    default="inproc",
                    help="ingest path: in-process chunks (default), framed "
                         "wire protocol without sockets, or a live asyncio "
                         "TCP server on localhost")
    ap.add_argument("--stall", type=int, default=0,
                    help="silence this many ECG patients mid-stream so the "
                         "stall-timeout eviction policy fires (transport "
                         "modes only)")
    ap.add_argument("--stall-timeout", type=float, default=1.5,
                    metavar="S", help="session stall timeout in seconds "
                    "(transport modes; default 1.5)")
    ap.add_argument("--devices", type=int, default=0, metavar="N",
                    help="shard every dispatch over N forced host devices "
                         "(XLA_FLAGS is set before jax imports; outputs "
                         "stay bit-identical to single-device)")
    ap.add_argument("--workers", type=int, default=0, metavar="M",
                    help="partition the fleet across M spawned worker "
                         "processes, one TCP ingest server + engine each "
                         "(forces --transport tcp; combine with --devices "
                         "for the processes × devices topology)")
    ap.add_argument("--microbench", action="store_true",
                    help="additionally time the per-device dispatch floor "
                         "(one fixed R-peak batch, warmed) into the JSON "
                         "'microbench' block")
    ap.add_argument("--scaling", default=None, metavar="DEVICES",
                    help="comma list of device counts: run one cold warmed "
                         "subprocess per (devices, fleet size) grid point "
                         "and embed the scaling curve (needs --json)")
    ap.add_argument("--scaling-patients", default=None, metavar="SIZES",
                    help="comma list of fleet sizes for the --scaling grid "
                         "(default: the run's --patients)")
    ap.add_argument("--pad-policy", choices=("max", "pow2", "auto"),
                    default=None,
                    help="dispatch padding strategy (default max; auto "
                         "consults the ledger's padding ratio after warmup)")
    ap.add_argument("--json", nargs="?", const="BENCH_stream.json",
                    default=None, metavar="PATH",
                    help="also write machine-readable results (default "
                         "PATH: BENCH_stream.json)")
    ap.add_argument("--repeat", type=int, default=3, metavar="N",
                    help="measured passes per A/B arm (with --ab; "
                         "default 3)")
    ap.add_argument("--ab", default=None, metavar="ARMS",
                    help="paired A/B mode: comma list of backend arms to "
                         "alternate (e.g. fused,unfused or "
                         "fused,unfused,codec); medians of the alternating "
                         "runs land in the JSON 'ab' block")
    ap.add_argument("--smoke-baseline", action="store_true",
                    help="additionally run a smoke-sized pass and embed "
                         "its fleet row as the CI perf-gate baseline "
                         "(benchmarks/check_perf.py)")
    ap.add_argument("--quire-ab", action="store_true",
                    help="paired REPRO_QUIRE on/off A/B on the acceptance "
                         "sweeps (cough/posit16, rpeak/posit8): µs/window, "
                         "nJ/window and accuracy vs fp32 per arm; lands in "
                         "the JSON 'quire_ab' block")
    ap.add_argument("--chaos", action="store_true",
                    help="fault harness: a worker-pool fleet with worker 0 "
                         "SIGKILLed mid-stream (+ a partitioned and a "
                         "corrupted patient), asserted bit-identical to "
                         "the fault-free pass, plus the paired ACK-plane "
                         "on/off overhead A/B; lands in the JSON 'chaos' "
                         "block (check_perf --chaos-max gates the ratio)")
    ap.add_argument("--chaos-workers", type=int, default=2, metavar="M",
                    help="worker processes for the --chaos fleet "
                         "(default 2)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="export the measured pass's spans as Chrome "
                         "trace-event JSON (opens in Perfetto / "
                         "chrome://tracing); in-process runs only")
    ap.add_argument("--obs-ab", action="store_true",
                    help="paired telemetry-plane on/off A/B (live registry "
                         "+ tracer vs null registry): fleet medians and "
                         "the overhead ratio land in the JSON 'obs_ab' "
                         "block (benchmarks/check_perf.py gates it)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    smoke_d, full_d = (8, 2, 8), (64, 4, 32)
    defaults = smoke_d if args.smoke else full_d
    patients = args.patients if args.patients is not None else defaults[0]
    windows = args.windows if args.windows is not None else defaults[1]
    max_batch = (args.max_batch if args.max_batch is not None
                 else defaults[2])
    if patients < 2:
        ap.error("--patients must be ≥ 2 (one cough + one ECG arm)")
    if args.ab and args.repeat < 1:
        ap.error("--repeat must be ≥ 1")
    if ((args.ab or args.smoke_baseline or args.scaling or args.quire_ab
            or args.obs_ab or args.chaos) and not args.json):
        ap.error("--ab/--smoke-baseline/--scaling/--quire-ab/--obs-ab/"
                 "--chaos results only land in the JSON record: pass "
                 "--json [PATH]")
    if args.chaos and args.chaos_workers < 2:
        ap.error("--chaos needs ≥ 2 workers (one dies, one survives)")
    if args.workers > 1:
        if args.transport == "inproc":
            print("# --workers forces --transport tcp", file=sys.stderr)
            args.transport = "tcp"
        if args.ab:
            ap.error("--ab backend overrides cannot cross the worker-pool "
                     "spawn boundary")
        if args.trace or args.obs_ab:
            ap.error("--trace/--obs-ab run in-process; worker-pool "
                     "telemetry is the per-worker metrics snapshot rollup")
    # the forced host device split must land in the environment before
    # the first JAX backend starts in this process (forest training below
    # starts one)
    force_host_devices(args.devices)
    enable_compile_cache()

    forest = None
    if args.ab or args.smoke_baseline or args.quire_ab or args.obs_ab:
        t0 = time.perf_counter()
        forest = build_forest()
        print(f"# forest trained in {time.perf_counter() - t0:.1f}s",
              file=sys.stderr)
    kwargs = dict(patients=patients, windows=windows, max_batch=max_batch,
                  smoke=args.smoke, homogeneous=args.homogeneous,
                  escalate=args.escalate, seed=args.seed,
                  transport=args.transport, stall=args.stall,
                  stall_timeout_s=args.stall_timeout,
                  pad_policy=args.pad_policy,
                  devices=args.devices, workers=args.workers)
    doc = run(forest=forest, trace_path=args.trace, **kwargs)
    if args.ab:
        doc["ab"] = run_ab(args.ab.split(","), args.repeat, forest,
                           **kwargs)
        # the tracked baseline should be the most defensible number we
        # have: when the paired harness measured the default (fused) arm,
        # its alternating-run medians replace the single-pass timings
        fused_arm = doc["ab"]["arms"].get("fused")
        if fused_arm:
            for key, med in fused_arm["groups"].items():
                if key in doc["groups"]:
                    doc["groups"][key].update(med)
            doc["config"]["measured"] = "ab_fused_median"
    if args.smoke_baseline:
        # the CI gate runs `--smoke --json` in a COLD process (compile time
        # included), so the baseline must be recorded the same way — a warm
        # in-process pass would under-read by the whole jit-cache warmup
        # and the gate would flake on every cold CI run.  One entry per
        # gated topology: single-device, and the multi-device fast lane's
        # sharded smoke (check_perf selects by matching config keys)
        import subprocess
        import tempfile
        entries = []
        for dev in (1, 4):
            with tempfile.TemporaryDirectory() as tmp:
                path = os.path.join(tmp, "smoke_baseline.json")
                subprocess.run([sys.executable, os.path.abspath(__file__),
                                "--smoke", "--devices", str(dev),
                                "--json", path,
                                "--seed", str(args.seed)], check=True)
                with open(path) as f:
                    sdoc = json.load(f)
            entries.append({"config": sdoc["config"],
                            "fleet": sdoc["groups"]["fleet"]})
        doc["smoke_baseline"] = entries
    if args.obs_ab:
        doc["obs_ab"] = run_obs_ab(args.repeat, forest, **kwargs)
    if args.quire_ab:
        doc["quire_ab"] = run_quire_ab(forest, repeat=args.repeat)
    if args.chaos:
        doc["chaos"] = run_chaos(patients, windows, max_batch,
                                 args.stall_timeout, args.pad_policy,
                                 args.seed, repeat=args.repeat,
                                 workers=args.chaos_workers)
    if args.microbench:
        doc["microbench"] = run_microbench(devices=args.devices)
    if args.scaling:
        device_counts = [int(d) for d in args.scaling.split(",")]
        patient_counts = ([int(p) for p in args.scaling_patients.split(",")]
                          if args.scaling_patients else [patients])
        doc["scaling"] = run_scaling(device_counts, patient_counts,
                                     windows, max_batch, args.seed)
    if args.json:
        write_json(doc, args.json)
    for key, row in doc["groups"].items():
        print(f"stream_bench/{key},{row['us_per_window']:.0f},"
              f"windows={row['windows']};"
              f"windows_per_s={row['windows_per_s']:.1f};"
              f"nj_per_window={row['nj_per_window']:.1f};"
              f"escalated={row['escalated_windows']}")
    wall = doc["wall"]
    print(f"stream_bench/wall,0,patients={patients};"
          f"windows={wall['windows']};elapsed_s={wall['elapsed_s']:.2f};"
          f"end_to_end_windows_per_s="
          f"{wall['end_to_end_windows_per_s']:.1f}")
    esc = doc["escalation"]
    print(f"stream_bench/escalation,0,"
          f"windows_escalated={esc['windows_escalated']};"
          f"rate={esc['rate']:.3f};extra_nj={esc['extra_nj']:.1f}")
    tr = doc["transport"]
    print(f"stream_bench/transport,0,mode={tr['mode']};"
          f"frames={tr['counters']['frames']};"
          f"dups={tr['counters']['dup_frames']};"
          f"gaps={tr['counters']['gap_events']};"
          f"evictions={tr['counters']['evictions']};"
          f"latency_p50_ms={tr['latency_ms']['p50']:.2f};"
          f"latency_p99_ms={tr['latency_ms']['p99']:.2f};"
          f"queue_dropped={tr['result_queue']['dropped']}")
    if doc["transport"]["workers"]:
        for w in doc["transport"]["workers"]:
            print(f"stream_bench/worker/{w['worker_id']},0,"
                  f"windows={w['windows']};devices={w['devices']}")
    if doc["microbench"]:
        mb = doc["microbench"]
        print(f"stream_bench/microbench,{mb['us_per_dispatch']:.0f},"
              f"task={mb['task']};fmt={mb['fmt']};batch={mb['batch']};"
              f"devices={mb['devices']};"
              f"us_per_window={mb['us_per_window']:.1f}")
    if doc["scaling"]:
        for e in doc["scaling"]["grid"]:
            f = e["fleet"]
            print(f"stream_bench/scaling/d{e['devices']}p{e['patients']},"
                  f"{f['us_per_window']:.0f},"
                  f"windows_per_s={f['windows_per_s']:.1f};"
                  f"nj_per_window={f['nj_per_window']:.1f};"
                  f"end_to_end_windows_per_s="
                  f"{e['wall']['end_to_end_windows_per_s']:.1f}")
    if doc["ab"]:
        arms = doc["ab"]["arms"]
        for key in sorted(next(iter(arms.values()))["groups"]):
            row = ";".join(
                f"{arm}={arms[arm]['groups'][key]['us_per_window']:.0f}"
                for arm in arms)
            ratio = doc["ab"].get("ratio", {}).get(key)
            if ratio is not None:
                row += f";ratio={ratio:.2f}"
            print(f"stream_bench/ab/{key},0,{row}")
    if doc["obs_ab"]:
        oab = doc["obs_ab"]
        print(f"stream_bench/obs_ab,0,"
              f"on={oab['arms']['on']['fleet_us_per_window']:.0f};"
              f"off={oab['arms']['off']['fleet_us_per_window']:.0f};"
              f"ratio={oab['ratio']:.3f}")
    if doc["chaos"]:
        ch = doc["chaos"]
        sk = ch["soak"]
        print(f"stream_bench/chaos,0,"
              f"restarts={sk['worker_restarts']};"
              f"recovered_windows={sk['windows']};"
              f"replayed_frames={sk['client']['replayed_frames']};"
              f"digests={sk['digest_matches']}/{sk['digest_total']};"
              f"ack_overhead_ratio={ch['overhead']['ratio']:.3f}")
    if doc["quire_ab"]:
        for key, t in doc["quire_ab"]["tasks"].items():
            print(f"stream_bench/quire_ab/{key},0,"
                  f"us_off={t['off']['us_per_window']:.0f};"
                  f"us_on={t['on']['us_per_window']:.0f};"
                  f"nj_off={t['off']['nj_per_window']:.1f};"
                  f"nj_on={t['on']['nj_per_window']:.1f};"
                  f"err_off={t['off']['err_vs_fp32']:.3e};"
                  f"err_on={t['on']['err_vs_fp32']:.3e};"
                  f"us_ratio={t['us_ratio']:.2f}")


if __name__ == "__main__":
    main()
