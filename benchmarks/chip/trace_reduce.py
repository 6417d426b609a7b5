"""From a profiler trace to the numbers the per-layer metrics report.

The JAX profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``.
Each device is a plane named ``/device:TPU:<i>``; its ``XLA Ops`` line
holds one event per operation that ran on the device, Pallas kernels
under their kernel's name.  Host spans are on ``/host:CPU``.  Event times
are nanoseconds from the trace's own origin; a ``TraceAnnotation`` the
benchmark opens at a known ``perf_counter`` reading (``MARKER``) ties that
origin to the host clock that the program's spans use.

* busy time is the union of a device's op intervals inside the window,
  averaged over the devices used; the idle share is 1 − busy / window;
* kernel time is the sum of the durations of the ops whose instruction
  (the HLO text before `` = ``) is named after the kernel: an op that
  only consumes the kernel's output names it among its operands, and is
  not the kernel's time;
* a roofline share is the least time the chip could take for the
  kernel's operations and bytes (the larger of flops / peak FLOP/s and
  bytes / peak bytes/s) over the kernel's measured time.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

HERE = Path(__file__).resolve().parent
MARKER = "chipbench.window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]
# ops whose trace events span the ops of their bodies
CONTAINERS = ("while", "conditional", "call")


def instruction(op: str) -> str:
    """The HLO instruction an op event is named by: ``%posit_round_2d.7 =
    f32[8,128] custom-call(...)`` → ``posit_round_2d.7``."""
    return op.split(" = ", 1)[0].strip().lstrip("%")


def is_kernel(op: str, kernel: str) -> bool:
    """Whether the op is a call of ``kernel`` itself, not an op that reads
    its output."""
    return instruction(op).startswith(kernel)


def short_name(op: str, width: int = 160) -> str:
    """An XLA op event is named by its HLO text: keep the instruction and
    what it computes, within ``width`` characters."""
    return op if len(op) <= width else op[:width - 3] + "..."


class UnknownDevice(KeyError):
    pass


def load_peaks(path: Path = HERE / "peaks.json") -> dict:
    with open(path) as f:
        return json.load(f)


def peaks_for(device_kind: str, table: Optional[dict] = None) -> dict:
    """The peaks of one chip; a device the table does not hold is an
    error, never a default."""
    table = load_peaks() if table is None else table
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r} in "
                            f"peaks.json") from None


def union_length(intervals: Iterable[Interval]) -> float:
    """Total length covered by possibly overlapping intervals."""
    total, end = 0.0, None
    start = None
    for a, b in sorted(intervals):
        if end is None or a > end:
            if end is not None:
                total += end - start
            start, end = a, b
        else:
            end = max(end, b)
    if end is not None:
        total += end - start
    return total


@dataclasses.dataclass
class DeviceTrace:
    """Device operations of one traced window, on the host's clock
    (``perf_counter`` seconds)."""

    ops: Dict[str, List[Tuple[str, float, float]]]   # device → (name, t0, t1)
    t0: float                                         # window start
    t1: float                                         # window end

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def _clipped(self, dev: str) -> List[Tuple[str, float, float]]:
        return [(n, max(a, self.t0), min(b, self.t1))
                for n, a, b in self.ops[dev] if b > self.t0 and a < self.t1]

    def busy_s(self) -> float:
        """Union of op intervals in the window, averaged over devices."""
        if not self.ops:
            return 0.0
        return sum(union_length((a, b) for _, a, b in self._clipped(d))
                   for d in self.ops) / len(self.ops)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def kernel_time(self, kernel: str) -> Tuple[float, int]:
        """(seconds, calls) of the calls of ``kernel``, summed over
        devices."""
        secs, calls = 0.0, 0
        for d in self.ops:
            for n, a, b in self._clipped(d):
                if is_kernel(n, kernel):
                    secs += b - a
                    calls += 1
        return secs, calls

    def op_totals(self) -> Dict[str, float]:
        """Seconds per op, by its short name (the HLO text up to the first
        space after the result type); loops and calls, whose events hold
        their body's ops, are left out."""
        tot: Dict[str, float] = {}
        for d in self.ops:
            for n, a, b in self._clipped(d):
                short = short_name(n)
                if instruction(n).split(".")[0] in CONTAINERS:
                    continue
                tot[short] = tot.get(short, 0.0) + (b - a)
        return tot

    def gaps(self, dev: Optional[str] = None) -> List[Interval]:
        """Idle intervals of one device (the first, by default)."""
        dev = dev or sorted(self.ops)[0]
        ivs = sorted((a, b) for _, a, b in self._clipped(dev))
        out, cur = [], self.t0
        for a, b in ivs:
            if a > cur:
                out.append((cur, a))
            cur = max(cur, b)
        if cur < self.t1:
            out.append((cur, self.t1))
        return out

    def breakdown(self, host_spans: Sequence[Tuple[str, float, float]] = (),
                  n: int = 10) -> Dict[str, list]:
        """The ``n`` device ops that took most time, and the ``n`` longest
        idle gaps named by the host span that covers most of each."""
        ops = sorted(self.op_totals().items(), key=lambda kv: -kv[1])[:n]
        gaps = sorted(self.gaps() if self.ops else [(self.t0, self.t1)],
                      key=lambda g: g[0] - g[1])[:n]
        named = []
        for a, b in gaps:
            best, cover = "no host span", 0.0
            for name, s, e in host_spans:
                c = min(b, e) - max(a, s)
                if c > cover:
                    best, cover = name, c
            named.append([best, b - a])
        return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": named}


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peaks: dict, dtype: str = "bf16") -> Tuple[float, str]:
    """(percent of the roofline, which bound applies)."""
    t_flops = flops / peaks[f"{dtype}_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound


# -- reading the profiler's file ---------------------------------------------

def find_xplane(logdir: str) -> str:
    files = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return files[-1]


def planes_from_profile(pd, scan_ns: float = 10e9
                        ) -> List[Tuple[str, Dict[str, list]]]:
    """(plane name, {line name: [(event name, start_ns, dur_ns)]}) with what
    the reduction reads: every op of each device's ``XLA Ops`` line, and
    the ``MARKER`` annotation of the host.  The marker opens right after
    the trace starts, so a host line is read only as far as ``scan_ns``
    into the trace (all of it, if the marker is not found there); the host
    lines of a busy window hold millions of events."""
    out = []
    for pl in pd.planes:
        lines = {}
        if pl.name.startswith(DEVICE_PREFIX):
            for ln in pl.lines:
                if ln.name == OPS_LINE:
                    lines[ln.name] = [(e.name, float(e.start_ns),
                                       float(e.duration_ns))
                                      for e in ln.events]
        elif pl.name.startswith("/host:"):
            for bounded in (True, False):
                for ln in pl.lines:
                    for e in ln.events:
                        if bounded and e.start_ns > scan_ns:
                            break
                        if e.name == MARKER:
                            lines.setdefault(ln.name, []).append(
                                (e.name, float(e.start_ns),
                                 float(e.duration_ns)))
                            break
                if lines:
                    break
        out.append((pl.name, lines))
    return out


def reduce_planes(planes: List[Tuple[str, Dict[str, list]]],
                  marker_host_s: float, t0: float, t1: float
                  ) -> DeviceTrace:
    """Device ops on the host clock.  ``marker_host_s`` is the
    ``perf_counter`` reading at which the ``MARKER`` annotation opened."""
    marker_ns = None
    for name, lines in planes:
        if not name.startswith("/host:"):
            continue
        for evs in lines.values():
            for ev, s, _ in evs:
                if ev == MARKER and (marker_ns is None or s < marker_ns):
                    marker_ns = s
    if marker_ns is None:
        raise ValueError(f"the trace holds no {MARKER!r} annotation")
    off = marker_host_s - marker_ns * 1e-9
    ops = {}
    for name, lines in planes:
        if name.startswith(DEVICE_PREFIX) and OPS_LINE in lines:
            ops[name] = [(ev, off + s * 1e-9, off + (s + d) * 1e-9)
                         for ev, s, d in lines[OPS_LINE]]
    return DeviceTrace(ops=ops, t0=t0, t1=t1)


def read_trace(logdir: str, marker_host_s: float, t0: float, t1: float
               ) -> DeviceTrace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(find_xplane(logdir))
    return reduce_planes(planes_from_profile(pd), marker_host_s, t0, t1)


def describe(planes: List[Tuple[str, Dict[str, list]]], top: int = 15
             ) -> dict:
    """What a trace holds, for looking at one by hand: planes, lines,
    event counts and the most frequent event names."""
    out = {}
    for name, lines in planes:
        pl = {}
        for ln, evs in lines.items():
            counts: Dict[str, int] = {}
            for ev, _, _ in evs:
                counts[ev] = counts.get(ev, 0) + 1
            pl[ln] = {"events": len(evs), "names": sorted(
                counts.items(), key=lambda kv: -kv[1])[:top]}
        out[name] = pl
    return out


class Recorder:
    """The profiler over one window: started before the window opens,
    stopped at its close, read, and its files removed."""

    def __init__(self):
        import tempfile
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        self.marker = 0.0

    def start(self) -> None:
        import time

        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # no per-call Python events
        opts.host_tracer_level = 1          # user annotations (the marker)
                                            # only: the runtime's own host
                                            # events fill a busy window
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.marker = time.perf_counter()
        with jax.profiler.TraceAnnotation(MARKER):
            pass

    def stop(self, t0: float, t1: float) -> DeviceTrace:
        import shutil

        import jax
        jax.profiler.stop_trace()
        try:
            return read_trace(self.dir, self.marker, t0, t1)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
