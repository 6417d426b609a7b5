"""The benchmark's shared machinery: cells, files found by name, the
compile cache, the device check, percentiles and the result line.

Everything that belongs to one configuration, traffic mix, per-layer
metric or kernel lives in a file of its own under this directory and is
found from its name in ``BENCHMARK.json``:

* ``configs/<config>.json``     — the deployment as it is run; its
  ``system`` names ``systems/<system>.py`` (the driver of the system under
  test) and its ``reference`` names ``references/<reference>.py``;
* ``traffic/<mix>.json``        — parameters; its ``generator`` names
  ``generators/<generator>.py``;
* ``metrics/<metric>.py``       — ``read(ctx)`` for one per-layer metric
  (``metrics/<quantity>.py`` serves ``<quantity>.<cell suffix>``);
* ``kernels/<kernel>.py``       — ``cost(**shapes)`` → operations, bytes;
* ``peaks.json``                — peaks by ``device_kind``.

Nothing here touches JAX at import time.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import sys
import time
from pathlib import Path
from typing import Any, Dict, List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]          # the checkout: benchmarks/chip → root


# -- files found by name ------------------------------------------------------

def load_spec(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, prefix: str = "chipbench"):
    """Import one file by path, under a name no other file shares."""
    path = Path(path)
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    name = f"{prefix}_{path.parent.name}_{path.stem}".replace(".", "_") \
        .replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def call_in_file(path: str, func: str, *args) -> None:
    """Target of a spawned process: load ``path`` and call ``func`` — a
    file loaded by path cannot be named by the child, this module can."""
    getattr(load_module(Path(path), "chipbench_child"), func)(*args)


def traffic_path(mix: str, base: Path = HERE) -> Path:
    return base / "traffic" / f"{mix}.json"


def metric_path(name: str, base: Path = HERE) -> Path:
    """``metrics/<name>.py``; where there is none, the reader of the
    quantity before the cell suffix: ``device_idle_share.serve`` is read
    by ``metrics/device_idle_share.py``."""
    own = base / "metrics" / f"{name}.py"
    if own.is_file() or "." not in name:
        return own
    return base / "metrics" / f"{name.rsplit('.', 1)[0]}.py"


def kernel_path(name: str, base: Path = HERE) -> Path:
    return base / "kernels" / f"{name}.py"


def system_path(name: str, base: Path = HERE) -> Path:
    return base / "systems" / f"{name}.py"


def generator_path(name: str, base: Path = HERE) -> Path:
    return base / "generators" / f"{name}.py"


def reference_path(name: str, base: Path = HERE) -> Path:
    return base / "references" / f"{name}.py"


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files resolved."""

    name: str
    chips: int
    config_name: str
    config: dict            # the configuration file's contents
    traffic_name: str
    traffic: dict           # the traffic mix's parameters
    end_to_end: List[dict]  # the end-to-end metrics this cell reports
    per_layer: List[dict]   # the per-layer metrics this cell reports


def _reports(metric: dict, cell: str) -> bool:
    wl = metric.get("workloads")
    return wl is None or cell in wl


def resolve_cell(spec: dict, name: str, root: Path = ROOT,
                 base: Path = HERE) -> Cell:
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    centry = configs[w["config"]]
    e2e = [m for m in spec["end_to_end"] if _reports(m, name)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return Cell(name=name, chips=int(w["chips"]), config_name=centry["name"],
                config=load_json(root / centry["file"]),
                traffic_name=w["traffic"],
                traffic=load_json(traffic_path(w["traffic"], base)),
                end_to_end=e2e, per_layer=per_layer)


# -- JAX set-up ---------------------------------------------------------------

def enable_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every program kept however fast it compiled.  Must run before the
    first compile; exported so that the program agrees."""
    cache = str(root / ".jax_cache")
    os.makedirs(cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    import jax
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: its bookkeeping reads a timestamp file per entry, and a
    # missing one fails the write of every later entry
    jax.config.update("jax_compilation_cache_max_size", -1)
    return cache


class NoAccelerator(SystemExit):
    pass


def require_chips(n: int) -> list:
    """The devices of this run: ``n`` TPU chips, or exit without a result."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"chip benchmark: no TPU (JAX found "
                            f"{devs[0].platform} devices); no result")
    if len(devs) < n:
        raise NoAccelerator(f"chip benchmark: the cell needs {n} chips, "
                            f"JAX found {len(devs)}; no result")
    return devs[:n]


class CompileMeter:
    """When each backend compile (a persistent-cache load included)
    finished, from JAX's monitoring events."""

    def __init__(self):
        import jax.monitoring as mon
        self.ends: List[float] = []

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.ends.append(now())

        mon.register_event_duration_secs_listener(on_duration)

    def between(self, t0: float, t1: float) -> int:
        """Compiles that finished in [t0, t1) of the benchmark's clock."""
        return sum(1 for t in self.ends if t0 <= t < t1)


def device_record(devs, peak_bytes: int) -> Dict[str, Any]:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak_bytes)}


def memory_peak(devs) -> int:
    """Peak bytes in use on the fullest of ``devs`` (0 where the backend
    keeps no count)."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


# -- statistics ---------------------------------------------------------------

def percentile(values, q: float) -> float:
    """Nearest-rank percentile; ``inf`` entries (answers that never came)
    sort last and count like any other sample."""
    vals = sorted(values)
    if not vals:
        return math.inf
    k = max(int(math.ceil(q / 100.0 * len(vals))) - 1, 0)
    return float(vals[k])


# -- the result line ----------------------------------------------------------

@dataclasses.dataclass
class Check:
    """One number compared with its limit: a reading above it, or none at
    all, fails."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return not math.isnan(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a system driver hands back to the harness for one run."""

    attempted: int
    failed: int
    checks: List[Check]
    metrics: Dict[str, float]           # end-to-end metric values
    ctx: Dict[str, Any]                 # what per-layer readers read
    devices: list
    memory_peak_bytes: int
    trace: Any = None                   # trace_reduce.DeviceTrace or None


def read_per_layer(cell: Cell, ctx: Dict[str, Any],
                   base: Path = HERE) -> Dict[str, float]:
    """Run each per-layer metric's reader; a reader with nothing to read
    returns None and the metric is left out."""
    out = {}
    for m in cell.per_layer:
        mod = load_module(metric_path(m["name"], base), "chipbench_metric")
        v = mod.read(ctx)
        if v is not None:
            out[m["name"]] = float(v)
    return out


def result_line(cell: Cell, outcome: Outcome, trace: bool,
                base: Path = HERE) -> Dict[str, Any]:
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if trace:
        values = read_per_layer(cell, outcome.ctx, base)
    else:
        values = {m["name"]: outcome.metrics[m["name"]]
                  for m in cell.end_to_end}
    device = device_record(outcome.devices, outcome.memory_peak_bytes)
    line: Dict[str, Any] = {
        "correct": all(c.ok for c in outcome.checks),
        "attempted": int(outcome.attempted),
        "failed": int(outcome.failed),
        # an answer that never came reads as infinite: JSON has no such
        # number, so it is written as null (and the run is not correct)
        "metrics": {k: {"value": v if math.isfinite(v) else None,
                        "unit": units[k]} for k, v in values.items()},
        "device": device,
    }
    if trace and outcome.trace is not None:
        device["busy_s"] = outcome.trace.busy_s()
        device["window_s"] = outcome.trace.window_s
        line["breakdown"] = outcome.trace.breakdown(
            outcome.ctx.get("host_spans", []))
    line["checks"] = {c.name: {"value": c.value if math.isfinite(c.value)
                               else None, "limit": c.limit}
                      for c in outcome.checks}
    return line


def emit(line: Dict[str, Any]) -> None:
    """The checks on standard error as its last lines, then the result as
    the last line of standard output."""
    sys.stdout.flush()
    for name, c in line["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line, allow_nan=False), flush=True)


def now() -> float:
    """The benchmark's one clock: ``perf_counter`` is the system-wide
    monotonic clock on Linux, so the generator process and the server
    read the same time."""
    return time.perf_counter()
