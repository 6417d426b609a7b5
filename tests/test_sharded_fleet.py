"""Sharded fleet dispatch: shard_map multi-device batches and the
multi-process worker pool.

The load-bearing contract is **bit-identity**: a dispatch sharded over a
forced host device mesh produces byte-for-byte the same window outputs,
tracker peaks, and ledger energy as the single-device engine on the same
fleet — the mesh buys throughput, never arithmetic.  The padding remainder
path is exercised on every dispatch (a batch cap that is NOT a multiple of
the device count), and the psum-reduced device-local ledger row must agree
exactly with the host's staged count.

Multi-device cases run in a subprocess (XLA_FLAGS must force the host
device split before jax's first import; the test process itself sees one
device).  The worker pool spawns real processes and is compared against the
in-process reference driver on the same simulator plans.
"""
import subprocess
import sys
import textwrap

import numpy as np
import pytest

# ---------------------------------------------------------------------------
# pure helpers (no devices needed)
# ---------------------------------------------------------------------------


def test_fleet_pad_rounds_to_shard_multiple():
    from repro.distributed.sharding import fleet_pad
    assert fleet_pad(5, 4) == 8
    assert fleet_pad(8, 4) == 8
    assert fleet_pad(1, 1) == 1
    assert fleet_pad(3, 2) == 4
    assert fleet_pad(6, 4) == 8


def test_make_fleet_mesh_info_host_fallback_and_errors():
    import jax

    from repro.launch.mesh import make_fleet_mesh_info

    # no argument: a mesh over every visible device — the host-CPU
    # fallback is a working 1-device mesh, not an error
    minfo = make_fleet_mesh_info()
    assert minfo.dp_size == jax.device_count()
    with pytest.raises(ValueError):
        make_fleet_mesh_info(0)
    with pytest.raises(RuntimeError, match="XLA_FLAGS"):
        make_fleet_mesh_info(jax.device_count() + 1)


def test_one_device_mesh_degenerates_to_plain_dispatch():
    # a 1-device mesh takes the plain (unsharded) dispatch path and is
    # bit-identical to a meshless engine — the degenerate contract
    from repro.data.biosignals import ecg_stream_signal
    from repro.launch.mesh import make_fleet_mesh_info
    from repro.stream import StreamEngine, rpeak_pipeline

    pipes = {"rpeak": rpeak_pipeline()}
    sig, _ = ecg_stream_signal(4, seed=2)
    engines = [StreamEngine(pipes, max_batch=4),
               StreamEngine(pipes, max_batch=4,
                            mesh_info=make_fleet_mesh_info(1))]
    assert engines[1].dp_size == 1
    for eng in engines:
        eng.ingest("p0", "rpeak", "ecg", sig[None, :])
        eng.drain()
    a, b = (e.results_for("p0", "rpeak") for e in engines)
    assert len(a) == len(b) == 2
    for ra, rb in zip(a, b):
        for k in ra.outputs:
            np.testing.assert_array_equal(ra.outputs[k], rb.outputs[k])
    sa, sb = (e.ledger.summary() for e in engines)
    assert set(sa) == set(sb)
    for key in sa:       # timing columns differ run to run; energy may not
        for col in ("windows", "total_nj", "nj_per_window",
                    "escalated_windows"):
            assert sa[key][col] == sb[key][col], (key, col)


# ---------------------------------------------------------------------------
# aggregation (pure merge logic, synthetic payloads)
# ---------------------------------------------------------------------------

def _payload(groups, patients, lat, windows, connects):
    transport = {p: {"frames": 2, "bytes": 100, "dup_frames": 0,
                     "reordered_frames": 0, "gap_events": 0,
                     "connects": 1, "late_frames": 0, "abandoned_frames": 0,
                     "evictions": 0, "modality_stalls": 0,
                     "windows_flushed": 0, "windows_dropped": 0,
                     "staged_freed": 0} for p in patients}
    transport["fleet"] = {k: sum(r[k] for r in transport.values())
                          for k in next(iter(transport.values()))}
    return {
        "groups": groups,
        "transport": transport,
        "escalation": {},
        "patients": {p: {"windows": 1, "windows_per_s": 0.0,
                         "latency_ms": {}} for p in patients},
        "latency_s": lat,
        "queue": {"capacity": 8, "depth": 0, "dropped": 0,
                  "total_windows": windows},
        "server": {"connections_total": connects, "protocol_errors": 0,
                   "session_errors": 0},
        "windows": windows,
        "devices": 1,
    }


def test_aggregate_rollup_sums_rows_and_concatenates_latency():
    from repro.ingest import aggregate_rollup

    row = dict(windows=4, batches=2, padded_windows=1, latency_s=2.0,
               energy_nj=100.0, escalated_windows=0, escalation_nj=0.0)
    a = _payload({"rpeak/posit10": dict(row)}, ["e0", "e1"],
                 [0.001] * 3, 4, 2)
    b = _payload({"rpeak/posit10": dict(row)}, ["e2"], [0.1], 4, 1)
    out = aggregate_rollup([a, b])
    g = out["groups"]["rpeak/posit10"]
    assert g["windows"] == 8 and g["batches"] == 4
    assert g["total_nj"] == 200.0 and g["nj_per_window"] == 25.0
    assert g["windows_per_s"] == 8 / 4.0
    fleet = out["groups"]["fleet"]
    assert fleet["windows"] == 8 and fleet["total_nj"] == 200.0
    # rollup fleet row carries the SAME keys as every per-group row
    # (batches/padded_windows included) — parity with EnergyLedger.summary
    assert set(fleet) == set(g)
    assert fleet["batches"] == 4 and fleet["padded_windows"] == 2
    # percentiles come from the CONCATENATED samples, never averaged
    # per-worker percentiles: the p50 of [1,1,1,100] ms is 1 ms
    assert out["latency_ms"]["p50"] == pytest.approx(1.0)
    assert out["latency_ms"]["p99"] > 50.0
    assert out["transport"]["fleet"]["connects"] == 3
    assert set(out["transport"]) == {"e0", "e1", "e2", "fleet"}
    assert out["servers"]["connections_total"] == 3
    assert out["windows"] == 8
    assert [w["windows"] for w in out["workers"]] == [4, 4]


def test_partition_plans_round_robin():
    from repro.ingest import FleetSimulator, partition_plans

    sim = FleetSimulator(n_patients=5, windows=1, mixed=False, n_cough=2)
    parts = partition_plans(sim.plans, 2)
    assert [p.patient for p in parts[0]] == \
        [sim.plans[i].patient for i in (0, 2, 4)]
    assert [p.patient for p in parts[1]] == \
        [sim.plans[i].patient for i in (1, 3)]
    # every worker sees a slice of the fleet's task mix when possible
    assert {p.task for p in parts[0]} == {"cough", "rpeak"}


# ---------------------------------------------------------------------------
# multi-device bit-identity (subprocess: forced 4-device host split)
# ---------------------------------------------------------------------------

SHARDED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.apps.cough import train_reference_forest
    from jax import shard_map
    from repro.distributed.collectives import ledger_psum
    from repro.ingest import FleetSimulator
    from repro.launch.mesh import make_fleet_mesh_info
    from repro.stream import StreamEngine, cough_pipeline, rpeak_pipeline

    assert jax.device_count() == 4
    minfo = make_fleet_mesh_info(4)

    # ledger_psum is exact on integer counters: the sharded ledger row is
    # the SUM of the device-local rows, bit for bit
    rows = jnp.arange(8, dtype=jnp.int32).reshape(4, 2)
    fn = shard_map(lambda r: ledger_psum(r, "data"), mesh=minfo.mesh,
                   in_specs=P("data"), out_specs=P(), check_vma=False)
    np.testing.assert_array_equal(np.asarray(fn(rows)), [[12, 16]])

    # 64-patient mixed fleet (cough + ECG, a quarter of each arm pinned),
    # batch cap 6: every dispatch pads 6 -> 8 rows across 4 devices, so the
    # remainder path runs on every single batch
    forest = train_reference_forest(48, 123, n_trees=5, depth=4)
    pipes = {"cough": cough_pipeline(forest), "rpeak": rpeak_pipeline()}
    sim = FleetSimulator(n_patients=64, windows=1, seed=3, mixed=True)
    plain = StreamEngine(pipes, max_batch=6, pad_policy="max")
    shard = StreamEngine(pipes, max_batch=6, pad_policy="max",
                         mesh_info=minfo)
    assert shard.dp_size == 4
    sim.run_inproc(plain, arrival_seed=11)
    sim.run_inproc(shard, arrival_seed=11)

    key = lambda r: (r.patient, r.task, r.widx)
    rp = sorted(plain.results, key=key)
    rs = sorted(shard.results, key=key)
    assert len(rp) == len(rs) == 64
    for a, b in zip(rp, rs):
        assert (a.patient, a.task, a.widx, a.fmt) == \\
            (b.patient, b.task, b.widx, b.fmt)
        assert set(a.outputs) == set(b.outputs)
        for k in a.outputs:
            np.testing.assert_array_equal(np.asarray(a.outputs[k]),
                                          np.asarray(b.outputs[k]))

    sp, ss = plain.ledger.summary(), shard.ledger.summary()
    assert set(sp) == set(ss)
    for k in sp:
        assert sp[k]["windows"] == ss[k]["windows"], k
        assert sp[k]["total_nj"] == ss[k]["total_nj"], k      # exact
    # the device slab rounding may pad MORE, never fewer, never billed
    for (task, fmt), g in plain.ledger.stats.items():
        assert shard.ledger.stats[(task, fmt)].padded_windows \\
            >= g.padded_windows
    print("SHARDED_FLEET_OK")
""")


def test_sharded_dispatch_bit_identical_subprocess():
    r = subprocess.run([sys.executable, "-c", SHARDED_SCRIPT],
                       capture_output=True, text=True, timeout=570,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            **__import__("os").environ})
    assert "SHARDED_FLEET_OK" in r.stdout, r.stdout + r.stderr


# ---------------------------------------------------------------------------
# worker pool (real spawned processes, ECG-only fleet for speed)
# ---------------------------------------------------------------------------

def test_worker_pool_matches_inproc_reference():
    from repro.ingest import FleetSimulator, run_worker_fleet
    from repro.stream import StreamEngine, rpeak_pipeline

    sim = FleetSimulator(n_patients=4, windows=2, seed=5, mixed=True,
                         n_cough=0)
    ref = StreamEngine({"rpeak": rpeak_pipeline()}, max_batch=4)
    sim.run_inproc(ref)
    want = ref.ledger.summary()

    doc = run_worker_fleet(sim, 2, max_batch=4)
    assert doc["n_workers"] == 2
    assert doc["windows"] == sim.expected_windows() == 8
    got = doc["groups"]
    assert set(got) == set(want)
    for k in want:
        assert got[k]["windows"] == want[k]["windows"], k
        # the energy model is deterministic per window: partitioning the
        # fleet across processes must not change a single nanojoule
        assert got[k]["total_nj"] == pytest.approx(want[k]["total_nj"]), k
    tr = doc["transport"]["fleet"]
    assert tr["connects"] == 4 and tr["evictions"] == 0
    assert doc["servers"]["connections_total"] == 4
    assert doc["servers"]["protocol_errors"] == 0
    assert doc["servers"]["session_errors"] == 0
    assert sum(w["windows"] for w in doc["workers"]) == 8
    assert doc["wall_s"] > 0
