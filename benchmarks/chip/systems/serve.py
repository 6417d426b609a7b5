"""Driver of the posit-KV serving lane: ``ServingEngine`` under open-loop
chat traffic.

Set-up makes the weights on the device from the seed (one jitted call, in
bfloat16, the engine's source copy), builds the engine at the
configuration's precision, and serves one warm request per prompt bucket
the mix can use, which compiles every prefill and the decode step.  In the
window each request is submitted when it is due and the engine is stepped;
after every step each request's new tokens are stamped on the host clock.
After the close no request is submitted; the ones owed are finished, a
minute at most.

Then the memory peak is read, the engine is freed, the weights are made
again from the seed, and a sample of the finished requests drawn from the
seed — the longest among them, and one from every batch slot that served
one, so that a fault confined to a slot cannot miss it — is held to the
float32 reference.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import os
import sys
import time
from typing import Dict, List

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import harness  # noqa: E402

DRAIN_S = 60.0
OPEN_LEAD_S = 0.25

# limits of the checks; PERF.md gives the readings each was set from
LIMITS = {"unfinished": 0, "logit_gap_mean": 0.12}

# the program's ModelConfig field for each key of the configuration file
_FIELDS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
           "num_attention_heads": "n_heads",
           "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
           "vocab_size": "vocab", "num_hidden_layers": "n_layers",
           "rope_theta": "rope_theta", "qk_norm": "qk_norm"}


def model_config(cfg: dict):
    """The program's ModelConfig for the configuration file; a field the
    file states and the program cannot take is an error."""
    from repro.configs import CONFIGS
    base = CONFIGS[cfg["model"]]
    out = dataclasses.replace(base, **{f: cfg[k] for k, f in _FIELDS.items()})
    if out.resolved_head_dim != cfg["head_dim"]:
        raise ValueError("head_dim does not reach the model")
    return out


def make_weights(shapes, seed: int):
    """Weights from the seed, on the device, in one jitted call: matrices
    normal with std 1/sqrt(fan-in) (the embedding 0.02), norm gains zero
    (the models scale by 1 + g).  bfloat16, the engine's source copy."""
    import jax
    import jax.numpy as jnp

    leaves, tree = jax.tree_util.tree_flatten_with_path(shapes)

    def make(key):
        out = []
        for i, (path, s) in enumerate(leaves):
            name = str(getattr(path[-1], "key", path[-1]))
            if len(s.shape) < 2:
                out.append(jnp.zeros(s.shape, jnp.bfloat16))
                continue
            std = 0.02 if name == "table" else 1.0 / math.sqrt(s.shape[-2])
            k = jax.random.fold_in(key, i)
            out.append((jax.random.normal(k, s.shape, jnp.float32) * std)
                       .astype(jnp.bfloat16))
        return jax.tree_util.tree_unflatten(tree, out)

    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
    return jax.jit(make)(key)


def run(cell: "harness.Cell", seed: int, seconds: float, trace: bool,
        devs: list, meter: "harness.CompileMeter", t_start: float,
        control: bool = False) -> "harness.Outcome":
    sys.path.insert(0, str(harness.ROOT / "src"))
    import jax

    from repro.launch.mesh import make_debug_mesh_info
    from repro.models import build_model
    from repro.obs import Tracer
    from repro.serve import ServeConfig, ServePolicy, ServingEngine

    cfg, mix = cell.config, cell.traffic
    gen = harness.load_module(harness.generator_path(mix["generator"]),
                              "chipbench_gen")
    mcfg = model_config(cfg)
    minfo = make_debug_mesh_info()
    policy = ServePolicy(weights=(cfg["control_weights"] if control
                                  else cfg["weights"]), kv=cfg["kv_cache"])
    reqs = gen.schedule(mix, seed, seconds, cfg["vocab_size"])
    tracer = Tracer(capacity=1 << 20) if trace else None
    with minfo.mesh:
        model = build_model(mcfg, minfo)
        shapes = jax.eval_shape(model.init, jax.random.key(0))
        engine = ServingEngine(
            model, make_weights(shapes, seed),
            ServeConfig(batch_size=int(cfg["batch_size"]),
                        max_prompt=int(cfg["max_prompt"]),
                        max_new_tokens=int(cfg["max_new_tokens"]),
                        seed=seed & 0x7FFFFFFF, max_completions=None),
            policy, tracer=tracer)
        _warm(engine, gen.buckets(mix), cfg["vocab_size"])
        if tracer is not None:
            tracer.reset()
        box = _window(engine, reqs, seconds, trace, meter)
    peak = harness.memory_peak(devs)
    t_open, t_close = box["t_open"], box["t_close"]
    spans = tracer.events() if tracer is not None else []
    del engine, model
    gc.collect()

    # -- the end-to-end metrics --------------------------------------------
    stamps: Dict[int, List[float]] = box["stamps"]
    ttft, gaps, failed = [], [], 0
    for i, r in enumerate(reqs):
        ts = stamps.get(i, [])
        due = t_open + r.arrival_s
        ttft.append(1e3 * (ts[0] - due) if ts else math.inf)
        gaps.extend(1e3 * (b - a) for a, b in zip(ts, ts[1:]))
        if len(ts) < r.max_new_tokens:
            failed += 1
    metrics = {"setup_s": t_open - t_start,
               "itl_p95_ms": harness.percentile(gaps, 95)}

    checks = [harness.Check("unfinished", float(failed),
                            LIMITS["unfinished"])]
    checks.extend(_reference_checks(cell, shapes, seed, reqs, box))
    ctx = {"t_open": t_open, "t_close": t_close, "trace": box.get("trace"),
           "spans": spans, "steps": box["steps"], "config": cfg,
           "kind": devs[0].device_kind,
           "host_spans": [(f"{ev[1]}/{ev[2]}", ev[3], ev[4]) for ev in spans
                          if ev[0] == "X"]}
    half = [[t for t, r in zip(ttft, reqs) if (r.arrival_s < seconds / 2)
             == first] for first in (True, False)]
    p50 = [harness.percentile(h, 50) if h else math.nan for h in half]
    # the TTFT tail is printed, not reported: PERF.md says why it holds no
    # bound yet
    print(f"serve: requests={len(reqs)} tokens={sum(map(len, stamps.values()))}"
          f" ttft_p95_ms={harness.percentile(ttft, 95):.3f}"
          f" ttft_p50_ms_first_half={p50[0]:.3f}"
          f" ttft_p50_ms_second_half={p50[1]:.3f}"
          f" compiles_in_window={box['compiles']}", file=sys.stderr)
    return harness.Outcome(attempted=len(reqs), failed=failed, checks=checks,
                           metrics=metrics, ctx=ctx, devices=devs,
                           memory_peak_bytes=peak, trace=box.get("trace"))


def _warm(engine, lengths: List[int], vocab: int) -> None:
    """One short greedy request per prompt bucket: compiles each prefill
    and the decode step before the window."""
    rng = np.random.default_rng(0)
    for n in lengths:
        engine.submit(rng.integers(1, vocab, n).astype(np.int32),
                      max_new_tokens=2)
    engine.run()


def _window(engine, reqs, seconds: float, trace: bool, meter) -> dict:
    """Submit each request when due, step the engine, stamp new tokens.
    Returns the stamps, the per-step record and the window's bounds."""
    sched = engine.scheduler
    rid_of: Dict[int, int] = {}          # engine rid → request index
    count: Dict[int, int] = {}           # request index → tokens so far
    slot_of: Dict[int, int] = {}         # request index → its batch slot
    stamps: Dict[int, List[float]] = {}
    tokens: Dict[int, np.ndarray] = {}   # request index → served tokens
    steps = []      # (t_end, decode contexts, prefill lengths) per step
    recorder = None
    if trace:
        from trace_reduce import Recorder
        recorder = Recorder()
        recorder.start()
    t_open = harness.now() + OPEN_LEAD_S
    t_close = t_open + seconds
    box = {"t_open": t_open, "t_close": t_close}
    while harness.now() < t_open:
        time.sleep(0.001)
    nxt = 0
    deadline = t_close + DRAIN_S
    while True:
        t = harness.now()
        while nxt < len(reqs) and t_open + reqs[nxt].arrival_s <= t:
            r = reqs[nxt]
            rid_of[engine.submit(r.prompt, max_new_tokens=r.max_new_tokens)] \
                = nxt
            nxt += 1
        if sched.idle:
            if nxt == len(reqs) or t > deadline:
                break
            time.sleep(max(min(t_open + reqs[nxt].arrival_s - t, 0.002), 0))
            continue
        if t > deadline:
            break
        engine.step()
        t = harness.now()
        seen = {}
        for table in sched.slots.values():
            for j, slot in enumerate(table):
                if slot is not None:
                    i = rid_of[slot.request.rid]
                    seen[i] = len(slot.tokens)
                    slot_of[i] = j
        for c in sched.pop_completions():
            seen[rid_of[c.rid]] = len(c.tokens)
            tokens[rid_of[c.rid]] = np.asarray(c.tokens)
        ctxs, prefills = [], []
        for i, n in seen.items():
            before = count.get(i, 0)
            if n <= before:
                continue
            P = len(reqs[i].prompt)
            if before == 0:
                prefills.append(P)
                before = 1          # the first token comes from prefill
            ctxs.extend(P + k for k in range(before, n))
            stamps.setdefault(i, []).extend([t] * (n - count.get(i, 0)))
            count[i] = n
        steps.append((t, ctxs, prefills))
    if recorder is not None:     # after the drain: its files take seconds
        box["trace"] = recorder.stop(t_open, t_close)
    box["compiles"] = meter.between(t_open, t_close)
    box.update(stamps=stamps, steps=steps, tokens=tokens, slot_of=slot_of)
    return box


def sample(done: List[int], reqs, slot_of: Dict[int, int], seed: int
           ) -> List[int]:
    """The finished requests the reference checks, drawn from the seed:
    the longest, and one from each batch slot that served a finished
    request."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFFFFFF, 11])
    longest = max(done, key=lambda i: reqs[i].max_new_tokens)
    pick, slots = [longest], {slot_of.get(longest)}
    for i in rng.permutation(done):
        j = slot_of.get(int(i))
        if j not in slots:
            pick.append(int(i))
            slots.add(j)
    return pick


def _reference_checks(cell, shapes, seed: int, reqs, box
                      ) -> List["harness.Check"]:
    """Over the sampled requests' served tokens, the mean gap by which a
    served token's reference logit lies below the reference's best.  (The
    widest gap is printed beside it; PERF.md says why it is not the number
    compared.)"""
    import jax.numpy as jnp

    cfg = cell.config
    stamps = box["stamps"]
    done = [i for i, r in enumerate(reqs)
            if len(stamps.get(i, [])) == r.max_new_tokens]
    if not done:
        return [harness.Check("logit_gap_mean", math.nan,
                              LIMITS["logit_gap_mean"])]
    pick = sample(done, reqs, box["slot_of"], seed)
    ref = harness.load_module(harness.reference_path(cfg["reference"]),
                              "chipbench_ref")
    params = make_weights(shapes, seed)
    gap_fn = ref.make_gap_fn(cfg)
    S = int(cfg["max_prompt"]) + int(cfg["max_new_tokens"])
    gaps = []
    for i in pick:
        toks = box["tokens"][i]
        P = len(reqs[i].prompt)
        seq = np.zeros(S, np.int32)
        seq[:P] = reqs[i].prompt
        seq[P:P + len(toks)] = toks
        g = np.asarray(gap_fn(params, jnp.asarray(seq)))
        gaps.extend(g[P - 1:P - 1 + len(toks)].tolist())
    del params
    print(f"serve: reference tokens={len(gaps)} widest_gap={max(gaps)!r}",
          file=sys.stderr)
    return [harness.Check("logit_gap_mean", float(np.mean(gaps)),
                          LIMITS["logit_gap_mean"])]
