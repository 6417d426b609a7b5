"""Token-serving benchmark: continuous-batching decode on the reduced LM,
µs/token + model nJ/token per precision lane, paired across KV-cache
storage formats.

  python benchmarks/serve_bench.py               # warmed full-size run
  python benchmarks/serve_bench.py --smoke       # CI-sized cold pass
  python benchmarks/serve_bench.py --json        # + BENCH_serve.json
  python benchmarks/serve_bench.py --ab bf16,posit16,posit10,posit8 \
      --repeat 2 --json                          # paired KV-format arms,
                                                 # medians of alternating
                                                 # warm passes
  python benchmarks/serve_bench.py --width-sweep --json
                                                 # greedy first-divergence
                                                 # of posit weights vs fp32
  python benchmarks/serve_bench.py --json --ab bf16,posit16,posit10,posit8 \
      --width-sweep --smoke-baseline             # regenerate the committed
                                                 # record + CI gate baseline

Output follows benchmarks/run.py conventions (``name,us_per_call,derived``
CSV rows, one per lane plus the fleet rollup).  ``--json`` writes
``BENCH_serve.json``: per-lane µs/token and nJ/token (KV HBM traffic
priced at the STORAGE width — the serving side of the paper's narrow-
storage argument), the ``ab`` block pairing KV formats over alternating
runs, the ``width_sweep`` block (first greedy-decode token index at which
each posit weights width diverges from the fp32 reference), and the
cold-subprocess ``smoke_baseline`` consumed by ``benchmarks/check_perf.py
--benchmark serve``.  ``tests/test_serve.py`` pins the schema against the
committed copy.
"""
import argparse
import json
import os
import sys
import time
from statistics import median as _median

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.launch.process import enable_compile_cache

# A/B arms: KV-cache storage format of the lane (weights fixed at the
# paper's posit16 deployment corner so the pairing isolates the cache).
KV_ARMS = {"bf16": None, "posit16": "posit16", "posit12": "posit12",
           "posit10": "posit10", "posit8": "posit8"}
WIDTH_SWEEP_FMTS = ("posit6", "posit8", "posit10", "posit12", "posit16")


def build_model(seed: int = 0):
    """Reduced qwen3-8b (the fused-eligible family: no softcap, no local
    window) + raw fp32 params; shared across every arm and the sweep."""
    import jax
    from repro.configs import CONFIGS, reduced
    from repro.launch.mesh import make_debug_mesh_info
    from repro.models import build_model as _build

    cfg = reduced(CONFIGS["qwen3-8b"])
    minfo = make_debug_mesh_info()
    with minfo.mesh:
        model = _build(cfg, minfo)
        params = model.init(jax.random.key(seed))
    return cfg, minfo, model, params


def build_prompts(n: int, max_prompt: int, vocab: int, seed: int):
    rng = np.random.default_rng(seed)
    lens = rng.integers(3, max_prompt + 1, size=n)
    return [rng.integers(1, vocab, size=int(L)).astype(np.int32)
            for L in lens]


def make_engine(model, params, batch_size, max_prompt, max_new_tokens,
                seed, kv_fmt, weights_fmt="posit16"):
    from repro.serve import ServeConfig, ServePolicy, ServingEngine
    return ServingEngine(
        model, params,
        ServeConfig(batch_size=batch_size, max_prompt=max_prompt,
                    max_new_tokens=max_new_tokens, seed=seed),
        ServePolicy(weights=weights_fmt, kv=kv_fmt))


def measured_pass(engine, prompts, minfo):
    """Submit every prompt, drive to completion on a FRESH ledger; returns
    (ledger summary, completions, elapsed seconds)."""
    from repro.serve import TokenLedger
    engine.ledger = TokenLedger()
    with minfo.mesh:
        t0 = time.perf_counter()
        for p in prompts:
            engine.submit(p)
        comps = engine.run()
        wall = time.perf_counter() - t0
    return engine.ledger.summary(), comps, wall


def run(requests: int, max_new_tokens: int, batch_size: int,
        max_prompt: int, smoke: bool = False, seed: int = 0,
        json_path=None, built=None, kv_fmt: str = "posit8",
        engine=None):
    """One measured serving pass; returns the machine-readable doc.

    ``engine`` (pre-warmed, from the A/B harness) skips engine
    construction so repeated arms share compiled lanes; otherwise a fresh
    engine runs one warmup pass first unless ``smoke`` (the CI gate
    measures cold, compile included, like stream_bench).
    """
    import jax
    from repro.core.arith import get_fused_kernels, get_round_backend

    if built is None:
        t0 = time.perf_counter()
        built = build_model(seed)
        print(f"# model built in {time.perf_counter() - t0:.1f}s",
              file=sys.stderr)
    cfg, minfo, model, params = built
    prompts = build_prompts(requests, max_prompt, cfg.vocab, seed + 1)
    if engine is None:
        engine = make_engine(model, params, batch_size, max_prompt,
                             max_new_tokens, seed, KV_ARMS[kv_fmt])
        if not smoke:
            t0 = time.perf_counter()
            measured_pass(engine, prompts, minfo)  # warm the jit caches
            print(f"# warmup pass in {time.perf_counter() - t0:.1f}s",
                  file=sys.stderr)
    groups, comps, wall = measured_pass(engine, prompts, minfo)
    n_tokens = sum(len(c.tokens) for c in comps)
    assert len(comps) == requests, (len(comps), requests)
    doc = {
        "benchmark": "serve_bench",
        "config": {"requests": requests, "max_new_tokens": max_new_tokens,
                   "batch_size": batch_size, "max_prompt": max_prompt,
                   "smoke": smoke, "seed": seed, "kv": kv_fmt,
                   "weights": "posit16", "model": "qwen3-8b/reduced",
                   "backend": jax.default_backend(),
                   "round_backend": get_round_backend(),
                   "fused_kernels": "on" if get_fused_kernels() else "off",
                   "measured": "single_pass"},
        "groups": groups,
        "ab": None,             # filled by the --ab paired harness
        "width_sweep": None,    # filled by --width-sweep
        "smoke_baseline": None,  # filled by --smoke-baseline (CI gate)
        "wall": {"elapsed_s": wall, "tokens": n_tokens,
                 "tokens_per_s": n_tokens / wall if wall else 0.0},
    }
    if json_path:
        write_json(doc, json_path)
    return doc


def write_json(doc, json_path):
    with open(json_path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# wrote {json_path}", file=sys.stderr)


def run_ab(arms, repeat, built, **kwargs):
    """Paired KV-format A/B: one warm engine per arm, ``repeat``
    ALTERNATING measured passes (arm order rotates each round so machine
    drift hits every arm equally), fleet medians + nJ/µs ratios vs the
    first arm (the wide-storage baseline)."""
    if repeat < 1:
        raise ValueError(f"--repeat must be >= 1, got {repeat}")
    for arm in arms:
        if arm not in KV_ARMS:
            raise ValueError(f"unknown A/B arm {arm!r} "
                             f"(choose from {sorted(KV_ARMS)})")
    cfg, minfo, model, params = built
    prompts = build_prompts(kwargs["requests"], kwargs["max_prompt"],
                            cfg.vocab, kwargs["seed"] + 1)
    engines = {}
    for arm in arms:
        engines[arm] = make_engine(model, params, kwargs["batch_size"],
                                   kwargs["max_prompt"],
                                   kwargs["max_new_tokens"],
                                   kwargs["seed"], KV_ARMS[arm])
        print(f"# ab warmup arm={arm}", file=sys.stderr)
        measured_pass(engines[arm], prompts, minfo)
    passes = {arm: [] for arm in arms}
    for r in range(repeat):
        order = list(arms[r % len(arms):]) + list(arms[:r % len(arms)])
        for arm in order:
            print(f"# ab pass {r + 1}/{repeat} arm={arm}", file=sys.stderr)
            groups, _, _ = measured_pass(engines[arm], prompts, minfo)
            passes[arm].append(groups)
    out = {"repeat": repeat, "arms": {}}
    for arm, rounds in passes.items():
        out["arms"][arm] = {
            "us_per_token": _median(
                [g["fleet"]["us_per_token"] for g in rounds]),
            "nj_per_token": _median(
                [g["fleet"]["nj_per_token"] for g in rounds]),
            "kv_read_bytes": rounds[0]["fleet"]["kv_read_bytes"],
        }
    base = out["arms"][arms[0]]
    out["ratio_vs_" + arms[0]] = {
        arm: {"us": (row["us_per_token"] / base["us_per_token"]
                     if base["us_per_token"] else 0.0),
              "nj": (row["nj_per_token"] / base["nj_per_token"]
                     if base["nj_per_token"] else 0.0)}
        for arm, row in out["arms"].items()}
    return out


def run_width_sweep(built, requests, max_new_tokens, max_prompt, seed):
    """Greedy-decode the same prompts with posit-quantized weights at each
    width and report the first token index where the output diverges from
    the fp32-weight reference (-1 = identical for the whole horizon).
    Storage-width fidelity on real token streams — the serving analogue of
    the paper's accuracy-vs-width tables."""
    cfg, minfo, model, params = built
    prompts = build_prompts(requests, max_prompt, cfg.vocab, seed + 1)

    def greedy(weights_fmt, kv_fmt):
        eng = make_engine(model, params, min(requests, 4), max_prompt,
                          max_new_tokens, seed, kv_fmt,
                          weights_fmt=weights_fmt)
        _, comps, _ = measured_pass(eng, prompts, minfo)
        return [c.tokens for c in sorted(comps, key=lambda c: c.rid)]

    ref = greedy(None, None)  # raw fp32 weights, bf16 cache
    sweep = {}
    for fmt in WIDTH_SWEEP_FMTS:
        outs = greedy(fmt, None)
        first = -1
        matches = total = 0
        for a, b in zip(ref, outs):
            n = min(len(a), len(b))
            total += n
            diff = np.nonzero(a[:n] != b[:n])[0]
            matches += n - len(diff)
            if len(diff) and (first < 0 or int(diff[0]) < first):
                first = int(diff[0])
        sweep[fmt] = {"first_divergence": first,
                      "match_fraction": matches / total if total else 1.0}
        print(f"# width_sweep {fmt}: first_divergence={first} "
              f"match={sweep[fmt]['match_fraction']:.3f}", file=sys.stderr)
    return sweep


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=None,
                    help="prompts to serve (default 8; 4 with --smoke)")
    ap.add_argument("--max-new", type=int, default=None,
                    help="decode budget per request (default 12; 6 smoke)")
    ap.add_argument("--batch-size", type=int, default=None,
                    help="slots per lane (default 4; 2 with --smoke)")
    ap.add_argument("--max-prompt", type=int, default=None,
                    help="prompt cap (default 32; 12 with --smoke)")
    ap.add_argument("--kv", choices=sorted(KV_ARMS), default="posit8",
                    help="KV-cache storage format of the main run "
                         "(default posit8)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized defaults + no warmup pass (cold, "
                         "compile included — what the perf gate measures)")
    ap.add_argument("--json", nargs="?", const="BENCH_serve.json",
                    default=None, metavar="PATH",
                    help="also write machine-readable results (default "
                         "PATH: BENCH_serve.json)")
    ap.add_argument("--ab", default=None, metavar="ARMS",
                    help="paired KV-format arms, comma list (e.g. "
                         "bf16,posit16,posit10,posit8); fleet medians of "
                         "alternating warm runs land in the JSON 'ab'")
    ap.add_argument("--repeat", type=int, default=2, metavar="N",
                    help="measured passes per A/B arm (default 2)")
    ap.add_argument("--width-sweep", action="store_true",
                    help="greedy first-divergence of posit weight widths "
                         "vs the fp32 reference")
    ap.add_argument("--smoke-baseline", action="store_true",
                    help="embed a COLD-subprocess smoke pass as the CI "
                         "perf-gate baseline (check_perf --benchmark serve)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    smoke_d, full_d = (4, 6, 2, 12), (8, 12, 4, 32)
    d = smoke_d if args.smoke else full_d
    requests = args.requests if args.requests is not None else d[0]
    max_new = args.max_new if args.max_new is not None else d[1]
    batch = args.batch_size if args.batch_size is not None else d[2]
    max_prompt = args.max_prompt if args.max_prompt is not None else d[3]
    if (args.ab or args.smoke_baseline or args.width_sweep) \
            and not args.json:
        ap.error("--ab/--width-sweep/--smoke-baseline results only land "
                 "in the JSON record: pass --json [PATH]")

    t0 = time.perf_counter()
    built = build_model(args.seed)
    print(f"# model built in {time.perf_counter() - t0:.1f}s",
          file=sys.stderr)
    kwargs = dict(requests=requests, max_new_tokens=max_new,
                  batch_size=batch, max_prompt=max_prompt,
                  smoke=args.smoke, seed=args.seed)
    doc = run(built=built, kv_fmt=args.kv, **kwargs)
    if args.ab:
        doc["ab"] = run_ab(args.ab.split(","), args.repeat, built,
                           **kwargs)
        # the tracked fleet row should be the most defensible number: the
        # main arm's alternating-run medians replace the single-pass one
        med = doc["ab"]["arms"].get(args.kv)
        if med and "fleet" in doc["groups"]:
            doc["groups"]["fleet"]["us_per_token"] = med["us_per_token"]
            doc["groups"]["fleet"]["nj_per_token"] = med["nj_per_token"]
            doc["config"]["measured"] = "ab_median"
    if args.width_sweep:
        doc["width_sweep"] = run_width_sweep(built, min(requests, 4),
                                             max_new, max_prompt,
                                             args.seed)
    if args.smoke_baseline:
        # the CI gate runs `--smoke --json` in a COLD process (compile
        # time included), so the baseline must be recorded the same way
        import subprocess
        import tempfile
        with tempfile.TemporaryDirectory() as tmp:
            path = os.path.join(tmp, "smoke_baseline.json")
            subprocess.run([sys.executable, os.path.abspath(__file__),
                            "--smoke", "--json", path,
                            "--seed", str(args.seed)], check=True)
            with open(path) as f:
                sdoc = json.load(f)
        doc["smoke_baseline"] = {"config": sdoc["config"],
                                 "fleet": sdoc["groups"]["fleet"]}
    if args.json:
        write_json(doc, args.json)
    for key, row in doc["groups"].items():
        print(f"serve_bench/{key},{row['us_per_token']:.0f},"
              f"decode_tokens={row['decode_tokens']};"
              f"nj_per_token={row['nj_per_token']:.1f};"
              f"prefill_us_per_token={row['prefill_us_per_token']:.0f};"
              f"padded_rows={row['padded_rows']}")
    wall = doc["wall"]
    print(f"serve_bench/wall,0,requests={requests};"
          f"tokens={wall['tokens']};elapsed_s={wall['elapsed_s']:.2f};"
          f"tokens_per_s={wall['tokens_per_s']:.1f}")
    if doc["ab"]:
        for arm, row in doc["ab"]["arms"].items():
            print(f"serve_bench/ab/{arm},{row['us_per_token']:.0f},"
                  f"nj_per_token={row['nj_per_token']:.1f};"
                  f"kv_read_bytes={row['kv_read_bytes']:.0f}")
    if doc["width_sweep"]:
        for fmt, row in doc["width_sweep"].items():
            print(f"serve_bench/width/{fmt},0,"
                  f"first_divergence={row['first_divergence']};"
                  f"match_fraction={row['match_fraction']:.3f}")


if __name__ == "__main__":
    main()
