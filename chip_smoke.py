"""Chip smoke: the patient fleet and the posit-KV serving lane, end to end
on a TPU, each checked against its reference.

  python chip_smoke.py               # one chip: fleet phase + serving phase
  python chip_smoke.py --chips 4     # four chips: the one-chip worker pool
                                     # and the sharded engine, each against
                                     # a single-device reference
  python chip_smoke.py --seed 7      # another fleet, prompt set and weights

* **Fleet phase** — 512 ECG + 64 cough patients, 4 windows each, a
  quarter of each arm pinned to posit8 / fp16, streamed by a
  ``FleetSimulator`` over TCP into an ``IngestServer`` → ``StreamEngine`` →
  ``Supervisor``.  Every window's outputs must equal, bit for bit, the
  offline window functions (``make_cough_scorer``, ``rpeak_window_scores``)
  run on the same device at the engine's batch shape; every patient's
  confirmed R peaks must equal offline ``detect_rpeaks`` on its record;
  every window must be scored, with no session or protocol error and no
  dropped window.
* **Serving phase** — ``ServingEngine`` answers greedy requests with
  qwen3-8b at its published widths, cut to ``SERVE_LAYERS`` whole layers,
  posit16 weights, on two lanes: a posit8 and a posit16 KV cache.  The
  logits the engine sampled every served token from (its prefill, then its
  batched decode: ``ServeConfig(keep_logits=True)``) must have that token
  as their argmax and agree with a float32 forward of the same weights
  under ``default_matmul_precision("highest")`` within the lane's bound in
  ``LANE_RTOL`` (relative L2 error per position).
* **Four chips** (``--chips 4``) — a four-worker pool (worker *i* owns chip
  *i*; this process stays off JAX until the pool is done), then in this
  process the engine sharded over a 4-chip mesh and a single-device
  reference engine on one chip of the same host.  Per-patient result
  digests, outputs and ledger windows must be equal; only
  ``padded_windows`` may grow.

Earlier lines report, per phase, wall time, compiles and compile seconds
(with persistent-cache hits), the Pallas kernels found in the compiled
programs (``tpu_custom_call``) and the comparisons.  The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU, or when any check fails, the script exits non-zero and
prints no result line.  Weights, data and the forest are made from
``--seed`` (and the forest's fixed recipe); nothing is read from disk.
"""
import argparse
import asyncio
import dataclasses
import json
import os
import resource
import sys
import time
import types

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np

# fleet: 512 ECG + 64 cough patients, 4 windows each
N_ECG, N_COUGH, WINDOWS = 512, 64, 4
MAX_BATCH = 64
FOREST_RECIPE = (96, 123, 10, 5)   # n_windows, seed, trees, depth
# serving: qwen3-8b widths, depth cut to whole layers
SERVE_LAYERS = 2
SERVE_REQUESTS, MAX_PROMPT, NEW_TOKENS, SLOTS = 4, 64, 16, 4
# KV lanes (all with posit16 weights) and the largest relative L2 error of
# a position's logits against the float32 reference that each may show.
# The weights are the same posit16 values on both sides; bf16 activations
# and the KV format are the error sources: about 0.016 with a posit16 and
# 0.08 with a posit8 cache on a 2-layer, d_model 512 CPU run.
LANE_RTOL = (("posit8", 0.25), ("posit16", 0.05))


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def report(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


class CompileMeter:
    """Counts backend compiles (persistent-cache fetches included) and
    their seconds, plus cache hits, from JAX's monitoring events."""

    def __init__(self):
        import jax.monitoring as mon
        self.n = 0
        self.secs = 0.0
        self.hits = 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.n += 1
                self.secs += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)

    def mark(self):
        return (self.n, self.secs, self.hits)

    def since(self, mark):
        n, s, h = mark
        return {"compiles": self.n - n,
                "compile_s": round(self.secs - s, 3),
                "cache_hits": self.hits - h}


def kernels_in(jitted, *args) -> int:
    """Pallas TPU kernels in the program ``jitted`` runs for ``args``."""
    return jitted.lower(*args).as_text().count("tpu_custom_call")


def build_forest():
    from repro.apps.cough import train_reference_forest
    n, seed, trees, depth = FOREST_RECIPE
    return train_reference_forest(n, seed, n_trees=trees, depth=depth)


def make_fleet(seed: int, n_ecg: int, n_cough: int, windows: int):
    from repro.ingest import FleetSimulator
    return FleetSimulator(n_ecg + n_cough, windows, seed=seed, mixed=True,
                          n_cough=n_cough, dup_rate=0.02, defer_rate=0.02)


def make_engine(forest, sim, mesh_info=None):
    from repro.stream import (PrecisionRouter, StreamEngine, cough_pipeline,
                              rpeak_pipeline)
    return StreamEngine({"cough": cough_pipeline(forest),
                         "rpeak": rpeak_pipeline()},
                        router=PrecisionRouter(patient_formats=sim.pins),
                        max_batch=MAX_BATCH, pad_policy="max",
                        mesh_info=mesh_info)


def stream_tcp(engine, sim, seed: int, timeout_s: float = 600.0):
    """The served path: clients → IngestServer → SessionManager → engine,
    results drained by a Supervisor.  Returns (supervisor, server)."""
    from repro.ingest import IngestServer, SessionManager, Supervisor

    supervisor = Supervisor(engine, capacity=2 * sim.expected_windows())
    # compiles block the event loop for seconds: no live patient may look
    # stalled meanwhile (every stream here ends with BYE)
    sessions = SessionManager(engine, stall_timeout_s=timeout_s)
    box = {}

    async def main():
        sim.pin_all(engine)
        async with IngestServer(sessions, port=0) as srv:
            done = [False]
            pump = asyncio.ensure_future(
                supervisor.run_async(0.005, stop=lambda: done[0]))
            await sim.run_tcp("127.0.0.1", srv.port, arrival_seed=seed + 2,
                              ledger=engine.ledger, close_timeout_s=timeout_s)
            deadline = time.perf_counter() + timeout_s
            while not sessions.all_closed():
                if time.perf_counter() > deadline:
                    fail(f"sessions still open: {sessions.open_sessions()}")
                await asyncio.sleep(0.02)
            done[0] = True
            await pump
            box["srv"] = srv
        supervisor.poll()

    asyncio.run(main())
    return supervisor, box["srv"]


def window_arrays(plan, widx: int):
    """One window's modality arrays, cut from the patient's record on the
    pipeline's window grid."""
    from repro.stream.pipelines import COUGH_SPEC, RPEAK_SPEC
    spec = COUGH_SPEC if plan.task == "cough" else RPEAK_SPEC
    out = {}
    for m in spec.modalities:
        s0 = int(round(widx * spec.hop_s * m.rate))
        out[m.name] = plan.signals[m.name][
            :, s0:s0 + spec.window_samples(m)]
    return out


def offline_outputs(forest, plans_by_pid, results):
    """The offline window functions on the same windows, same device, at
    the engine's padded batch shape: {(patient, widx): outputs}."""
    import jax
    import jax.numpy as jnp

    from repro.apps.bayeslope import rpeak_window_scores
    from repro.apps.cough import make_cough_scorer
    from repro.core.arith import Arith

    groups = {}
    for r in results:
        groups.setdefault((r.task, r.fmt), []).append(r)
    out = {}
    for (task, fmt), rs in sorted(groups.items()):
        if task == "cough":
            scorer = make_cough_scorer(fmt, forest)
            fn = lambda a: {"p_cough": scorer(a["audio"], a["imu"])}
        else:
            ar = Arith.make(fmt)
            score = jax.jit(lambda x, ar=ar: rpeak_window_scores(ar, x))
            fn = lambda a, score=score: {"scores": score(a["ecg"][:, 0])}
        for i in range(0, len(rs), MAX_BATCH):
            chunk = rs[i:i + MAX_BATCH]
            wins = [window_arrays(plans_by_pid[r.patient], r.widx)
                    for r in chunk]
            batch = {m: np.zeros((MAX_BATCH,) + wins[0][m].shape,
                                 np.float32) for m in wins[0]}
            for j, w in enumerate(wins):
                for m, a in w.items():
                    batch[m][j] = a
            got = fn({m: jnp.asarray(a) for m, a in batch.items()})
            got = {k: np.asarray(v) for k, v in got.items()}
            for j, r in enumerate(chunk):
                out[(r.patient, r.widx)] = {k: v[j] for k, v in got.items()}
    return out


def fleet_phase(seed: int, meter, n_ecg=N_ECG, n_cough=N_COUGH,
                windows=WINDOWS):
    """Stream the fleet over TCP and hold every output to its offline
    reference.  Returns the phase's report fields."""
    from repro.apps.bayeslope import detect_rpeaks
    from repro.core.arith import Arith

    t0 = time.perf_counter()
    m0 = meter.mark()
    forest = build_forest()
    sim = make_fleet(seed, n_ecg, n_cough, windows)
    engine = make_engine(forest, sim)
    supervisor, srv = stream_tcp(engine, sim, seed)
    t_stream = time.perf_counter() - t0
    stream_compiles = meter.since(m0)

    results = list(supervisor.queue)
    expected = {(p.patient, w) for p in sim.plans for w in range(windows)}
    got = [(r.patient, r.widx) for r in results]
    if len(got) != len(set(got)) or set(got) != expected:
        fail(f"scored {len(set(got))} distinct windows ({len(got)} "
             f"results), expected {len(expected)}")
    tr = engine.ledger.transport_summary()["fleet"]
    errors = {"session_errors": srv.session_errors,
              "protocol_errors": srv.protocol_errors,
              "windows_dropped": tr["windows_dropped"],
              "results_dropped": supervisor.dropped + engine.dropped_results}
    if any(errors.values()):
        fail(f"errors on the served path: {errors}")

    # the kernels in the programs the engine ran, by (task, format)
    kernels = {}
    for (task, fmt) in sorted({(r.task, r.fmt) for r in results}):
        pipe = engine.pipelines[task]
        spec = {m.name: np.zeros((MAX_BATCH, m.channels,
                                  pipe.spec.window_samples(m)), np.float32)
                for m in pipe.spec.modalities}
        kernels[f"{task}/{fmt}"] = kernels_in(pipe.make_fn(fmt), spec)
    if not all(kernels[k] for k in kernels if "posit" in k):
        fail(f"a posit window program holds no Pallas kernel: {kernels}")

    plans = {p.patient: p for p in sim.plans}
    ref = offline_outputs(forest, plans, results)
    mismatched = [(r.patient, r.widx, k) for r in results
                  for k, want in ref[(r.patient, r.widx)].items()
                  if not np.array_equal(np.asarray(r.outputs[k]), want)]
    if mismatched:
        fail(f"{len(mismatched)} window outputs differ from the offline "
             f"functions, first {mismatched[:5]}")

    peak_diff, n_peaks = [], 0
    for p in sim.plans:
        if p.task != "rpeak":
            continue
        fmt = engine.router.route(p.patient, "rpeak").fmt
        want = detect_rpeaks(Arith.make(fmt), p.signals["ecg"][0])
        have = engine.tracker_for(p.patient, "rpeak").peaks
        n_peaks += len(want)
        if have != want:
            peak_diff.append(p.patient)
    if peak_diff:
        fail(f"{len(peak_diff)} patients' streamed peaks differ from "
             f"offline detect_rpeaks, first {peak_diff[:5]}")
    return dict(patients=len(sim.plans), ecg=n_ecg, cough=n_cough,
                windows=len(results), stream_wall_s=round(t_stream, 3),
                wall_s=round(time.perf_counter() - t0, 3),
                **{f"stream_{k}": v for k, v in stream_compiles.items()},
                **meter.since(m0), kernels=json.dumps(kernels,
                                                      separators=(",", ":")),
                outputs_equal=len(results), peaks_equal=n_peaks,
                **errors)


def reference_logits(cfg, params, tokens):
    """Plain float32 forward of the dense decoder (no cache): logits at
    every position of ``tokens`` (S,).  Written from the architecture, not
    from the model code under test."""
    import jax
    import jax.numpy as jnp

    f32 = lambda a: a.astype(jnp.float32)

    def rms(x, g, eps=1e-6):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * (1.0 + f32(g))

    def rope(x, pos):
        half = x.shape[-1] // 2
        inv = jnp.exp(-jnp.log(cfg.rope_theta)
                      * jnp.arange(half, dtype=jnp.float32) / half)
        ang = pos[:, None, None] * inv
        c, s = jnp.cos(ang), jnp.sin(ang)
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)

    S = tokens.shape[0]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    pos = jnp.arange(S, dtype=jnp.float32)
    causal = jnp.tril(jnp.ones((S, S), bool))
    table = params["embed"]["table"]
    x = table[tokens]
    for i in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        a = lp["attn"]
        h = rms(x, lp["ln1"])
        q = rms((h @ a["wq"]["w"]).reshape(S, H, hd), a["q_gamma"])
        k = rms((h @ a["wk"]["w"]).reshape(S, KV, hd), a["k_gamma"])
        v = (h @ a["wv"]["w"]).reshape(S, KV, hd)
        q, k = rope(q, pos), rope(k, pos)
        q = q.reshape(S, KV, H // KV, hd)
        att = jnp.einsum("skgd,tkd->kgst", q, k) * hd ** -0.5
        att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), -1)
        o = jnp.einsum("kgst,tkd->skgd", att, v).reshape(S, H * hd)
        x = x + o @ a["wo"]["w"]
        f = lp["ffn"]
        h = rms(x, lp["ln2"])
        x = x + (jax.nn.silu(h @ f["w_gate"]["w"]) * (h @ f["w_up"]["w"])) \
            @ f["w_down"]["w"]
    return rms(x, params["final_ln"]) @ table.T


def serving_phase(seed: int, meter, layers=SERVE_LAYERS, cfg=None):
    """Greedy requests through ServingEngine on two KV lanes; prefill +
    decode logits held to the float32 reference.  Returns the phase's
    report fields."""
    import jax
    import jax.numpy as jnp

    from repro.configs import CONFIGS
    from repro.core.quant import PositTensor
    from repro.launch.mesh import make_debug_mesh_info
    from repro.models import build_model
    from repro.serve import ServeConfig, ServePolicy, ServingEngine

    t0 = time.perf_counter()
    m0 = meter.mark()
    cfg = cfg or dataclasses.replace(CONFIGS["qwen3-8b"], n_layers=layers)
    minfo = make_debug_mesh_info()
    rng = np.random.default_rng(seed)
    # prompt lengths in (MAX_PROMPT/2, MAX_PROMPT]: one prefill bucket
    prompts = [rng.integers(1, cfg.vocab, size=int(n)).astype(np.int32)
               for n in rng.integers(MAX_PROMPT // 2 + 1, MAX_PROMPT + 1,
                                     size=SERVE_REQUESTS)]
    lanes = [(ServePolicy(weights="posit16", kv=kv), rtol)
             for kv, rtol in LANE_RTOL]
    with minfo.mesh:
        model = build_model(cfg, minfo)
        params = model.init(jax.random.key(seed))
        engine = ServingEngine(
            model, params, ServeConfig(batch_size=SLOTS,
                                       max_prompt=MAX_PROMPT,
                                       max_new_tokens=NEW_TOKENS, seed=seed,
                                       keep_logits=True),
            lanes[0][0])
        del params
        rids = [engine.submit(p, policy=lanes[i % len(lanes)][0])
                for i, p in enumerate(prompts)]
        comps = {c.rid: c for c in engine.run()}
        t_serve = time.perf_counter() - t0
        serve_compiles = meter.since(m0)
        if sorted(comps) != sorted(rids) or any(
                len(comps[r].tokens) != NEW_TOKENS for r in rids):
            fail(f"served {[len(c.tokens) for c in comps.values()]} tokens "
                 f"for {len(rids)} requests of {NEW_TOKENS}")

        spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt)
        dequant = jax.jit(lambda p: jax.tree.map(
            lambda l: (l.dequant(jnp.float32) if isinstance(l, PositTensor)
                       else l.astype(jnp.float32)),
            p, is_leaf=lambda l: isinstance(l, PositTensor)))
        ref_fn = jax.jit(lambda p, t: reference_logits(cfg, dequant(p), t))
        ref_len = MAX_PROMPT + NEW_TOKENS - 1
        fields = {}
        for li, (policy, rtol) in enumerate(lanes):
            lane = engine._lane(policy)
            tag = f"kv_{policy.kv}"
            kernels = kernels_in(
                lane._decode, lane.params, spec((SLOTS,), jnp.int32),
                lane.caches, engine._base_key, spec((SLOTS,), jnp.int32),
                spec((SLOTS,), jnp.int32), spec((SLOTS,), jnp.float32),
                spec((SLOTS,), jnp.bool_))
            if not kernels:
                fail(f"the {tag} decode step holds no Pallas kernel")
            # the logits the engine sampled each served token from: the
            # B=1 prefill, then the lane's batched decode at SLOTS rows
            worst, agree_ref, n = 0.0, 0, 0
            for i in range(li, len(rids), len(lanes)):
                prompt, toks = prompts[i], np.asarray(comps[rids[i]].tokens)
                P = len(prompt)
                got = np.stack(engine.served_logits[rids[i]])
                if got.shape != (len(toks), cfg.vocab) \
                        or not np.isfinite(got).all():
                    fail(f"{tag} request {rids[i]}: served logits of shape "
                         f"{got.shape}, finite={np.isfinite(got).all()}")
                if not np.array_equal(got.argmax(-1), toks):
                    fail(f"{tag} request {rids[i]}: a greedy token is not "
                         f"the argmax of the logits it was sampled from")
                seq = np.zeros(ref_len, np.int32)   # causal: the zero tail
                seq[:P + len(toks) - 1] = np.concatenate([prompt, toks[:-1]])
                with jax.default_matmul_precision("highest"):
                    ref = ref_fn(lane.params, jnp.asarray(seq))
                ref = np.asarray(ref[P - 1:P - 1 + len(toks), :cfg.vocab],
                                 np.float32)
                err = (np.linalg.norm(got - ref, axis=-1)
                       / np.linalg.norm(ref, axis=-1))
                worst = max(worst, float(err.max()))
                agree_ref += int((ref.argmax(-1) == toks).sum())
                n += len(toks)
            if worst > rtol:
                fail(f"{tag} logits differ from the float32 reference by "
                     f"relative L2 {worst:.4g} > {rtol}")
            fields.update({f"{tag}_decode_kernels": kernels,
                           f"{tag}_tokens": n,
                           f"{tag}_logit_rel_l2_max": round(worst, 6),
                           f"{tag}_logit_rtol": rtol,
                           f"{tag}_greedy_agree_f32": f"{agree_ref}/{n}"})
    return dict(model=f"{cfg.name}/{cfg.n_layers}-layers",
                d_model=cfg.d_model, vocab=cfg.vocab, weights="posit16",
                requests=len(rids), serve_wall_s=round(t_serve, 3),
                wall_s=round(time.perf_counter() - t0, 3),
                **{f"serve_{k}": v for k, v in serve_compiles.items()},
                **meter.since(m0), **fields)


def _digests(results):
    """Per-patient digests, as each pool worker computes them over its
    supervisor's queue."""
    from repro.ingest.workers import _result_digests
    return _result_digests(types.SimpleNamespace(queue=list(results)))


def four_chip_phase(seed: int, meter, chips: int = 4, n_ecg=N_ECG,
                    n_cough=N_COUGH, windows=WINDOWS):
    """Worker pool (parent off JAX), then sharded engine vs a one-chip
    reference in this process."""
    from repro.ingest import run_worker_fleet
    from repro.launch.process import backend_started

    t0 = time.perf_counter()
    sim = make_fleet(seed, n_ecg, n_cough, windows)
    pool = run_worker_fleet(sim, chips, max_batch=MAX_BATCH,
                            pad_policy="max", stall_timeout_s=600.0,
                            arrival_seed=seed + 2,
                            drain_timeout_s=600.0, start_timeout_s=600.0)
    t_pool = time.perf_counter() - t0
    if backend_started():
        fail("the pool's parent started a JAX backend")
    if pool["windows"] != sim.expected_windows():
        fail(f"the pool scored {pool['windows']} windows, expected "
             f"{sim.expected_windows()}")
    srv = pool["servers"]
    unconfirmed = pool["recovery"]["client"].get("unconfirmed_closes", 0)
    if pool["failed_workers"] or srv["session_errors"] \
            or srv["protocol_errors"] or unconfirmed \
            or pool["transport"]["fleet"]["windows_dropped"]:
        fail(f"worker pool errors: failed={pool['failed_workers']} "
             f"servers={srv} unconfirmed_closes={unconfirmed}")

    import jax
    if jax.device_count() < chips:
        fail(f"after the pool this process sees {jax.devices()}")
    from repro.launch.mesh import make_fleet_mesh_info

    m0 = meter.mark()
    forest = build_forest()
    ref = make_engine(forest, sim)
    sim.run_inproc(ref, arrival_seed=seed + 2)
    shard = make_engine(forest, sim, mesh_info=make_fleet_mesh_info(chips))
    sim.run_inproc(shard, arrival_seed=seed + 2)

    want = _digests(ref.results)
    if len(ref.results) != sim.expected_windows():
        fail(f"reference scored {len(ref.results)} windows")
    task_of = {p.patient: p.task for p in sim.plans}
    for name, got in (("pool", pool["digests"]),
                      ("sharded", _digests(shard.results))):
        bad = sorted(p for p in want if got.get(p) != want[p])
        if bad or set(got) != set(want):
            by_group = {}
            for p in bad:
                g = f"{task_of[p]}/{ref.router.route(p, task_of[p]).fmt}"
                by_group[g] = by_group.get(g, 0) + 1
            fail(f"{name} digests differ for {len(bad)} patients "
                 f"{by_group}, first {bad[:5]}")
    key = lambda r: (r.patient, r.task, r.widx)
    for a, b in zip(sorted(ref.results, key=key),
                    sorted(shard.results, key=key)):
        if key(a) != key(b) or a.fmt != b.fmt or any(
                not np.array_equal(a.outputs[k], b.outputs[k])
                for k in a.outputs):
            fail(f"sharded output differs at {key(a)}")
    padded = {}
    for (task, fmt), g in ref.ledger.stats.items():
        s = shard.ledger.stats[(task, fmt)]
        p = pool["groups"][f"{task}/{fmt}"]
        if not (g.windows == s.windows == p["windows"]):
            fail(f"{task}/{fmt} windows ref={g.windows} sharded={s.windows}"
                 f" pool={p['windows']}")
        if s.padded_windows < g.padded_windows:
            fail(f"{task}/{fmt} sharded padded fewer windows")
        padded[f"{task}/{fmt}"] = f"{g.padded_windows}->{s.padded_windows}"
    return dict(patients=len(sim.plans), windows=len(ref.results),
                pool_workers=pool["n_workers"],
                pool_wall_s=round(t_pool, 3),
                pool_windows=pool["windows"],
                pool_digests_equal=len(pool["digests"]),
                sharded_digests_equal=len(want),
                sharded_devices=shard.dp_size,
                padded_windows=json.dumps(padded, separators=(",", ":")),
                wall_s=round(time.perf_counter() - t0, 3),
                **meter.since(m0))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    # one process holds the whole fleet's client and server sockets
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    resource.setrlimit(resource.RLIMIT_NOFILE, (hard, hard))

    from repro.launch.process import enable_compile_cache, tpu_chips_on_host
    cache_dir = enable_compile_cache()
    report("setup", fleet=f"{N_ECG}-ecg+{N_COUGH}-cough-x{WINDOWS}-windows",
           cut="none", compile_cache=cache_dir)

    if args.chips == 4:
        # the pool's workers take the chips: no JAX backend here until
        # they are done
        found = tpu_chips_on_host()
        if found < 4:
            fail(f"--chips 4 needs a host with 4 TPU chips, found {found}")
        import jax
        meter = CompileMeter()
        report("four-chip", **four_chip_phase(args.seed, meter))
    else:
        import jax
        devs = jax.devices()
        if devs[0].platform != "tpu":
            fail(f"no TPU: JAX found {devs[0].platform} devices")
        from repro.kernels.common import interpret_mode
        if interpret_mode():
            fail("Pallas kernels would run in the interpreter")
        meter = CompileMeter()
        report("fleet", **fleet_phase(args.seed, meter))
        report("serving", **serving_phase(args.seed, meter))
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"the phases ran on {devs[0].platform}, not a TPU")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
