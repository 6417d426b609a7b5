"""The fleet's load generator: wearables that stream biosignals over the
system's wire protocol, open loop, in a process of their own.

One generator serves every fleet mix; a mix is a JSON file of parameters:

* ``mode``: ``"steady"`` streams at sensor rate — each frame is due when
  its last sample exists, and is sent then (or as soon after as the
  process can), whatever the server does; ``"backlog"`` uploads buffered
  records as fast as the credit window allows, endlessly, the way a
  gateway syncs after a radio outage;
* ``patients``: how many wearables; ``warm_windows``: the burst each sends
  before the window opens (it fills the tracker's reservoir and compiles
  every shape); ``chunk_samples``: [min, max] samples per radio frame;
* the configuration adds the deployment's sensor rate, window length,
  formats and radio faults (``dup_rate``, ``defer_rate``,
  ``defer_depth``).

Everything is drawn from ``--seed``: the records' content, and which
patient takes which timing.  The timing — each patient's phase, its frame
sizes and the radio's faults — comes from a set that is the same for
every seed: a seed deals it out over the patients of each format group in
another order.  So every seed offers the same arrivals in each group, and
only the content and the order differ.  The records are built here (a synthetic exercise ECG with known
R peaks, after the BayeSlope protocol), so the reference knows the truth
without asking the program.

Nothing here imports JAX: the sender process never touches the chip.
"""
from __future__ import annotations

import asyncio
import dataclasses
import functools
import gc
import math
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

SEGMENT_WINDOWS = 10          # one record segment: 10 windows (20 s of ECG)


# -- the records ----------------------------------------------------------------

def _rng(seed: int, *key: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFFFFFF, *key])


TIMING = 0          # the key of the timing set: the same for every seed


@functools.lru_cache(maxsize=1 << 16)
def _phase(slot: int, span: float) -> float:
    return float(_rng(TIMING, slot, 7).uniform(0.0, span))


@functools.lru_cache(maxsize=16)
def _slots(seed: int, patients: int, pin_every: int) -> Tuple[int, ...]:
    """Patient → the timing slot it takes: a permutation, drawn from the
    seed, of the patients within each format group (pinned or not)."""
    out = list(range(patients))
    rng = _rng(seed, 11)
    for pinned in (False, True):
        group = [p for p in range(patients)
                 if (p % pin_every == pin_every - 1) == pinned]
        for p, q in zip(group, rng.permutation(group)):
            out[p] = int(q)
    return tuple(out)


class GcMeter:
    """The process's garbage collections: when each began, how long it
    held the interpreter, and its generation."""

    def __init__(self):
        self.pauses: List[Tuple[float, float, int]] = []
        self._t0 = None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append((self._t0, time.perf_counter() - self._t0,
                                info["generation"]))
            self._t0 = None

    def between(self, t0: float, t1: float) -> List[Tuple[float, int]]:
        """(seconds, generation) of the collections begun in [t0, t1)."""
        return [(d, g) for t, d, g in self.pauses if t0 <= t < t1]

    def close(self) -> None:
        gc.callbacks.remove(self._on_gc)


_WAVES = ((1.0, 0.0, 0.008), (-0.25, -0.025, 0.01), (-0.30, 0.03, 0.012),
          (0.3, 0.18, 0.04))          # R, Q, S, T: (amplitude, offset, width)


def beats(rng: np.random.Generator, first: int, last: int,
          rr: float) -> np.ndarray:
    """Times (s) of beats ``first`` .. ``last - 1``: beat j sits at j·rr,
    jittered by 5% of rr."""
    j = np.arange(first, last)
    return rr * (j + 0.05 * np.clip(rng.normal(size=len(j)), -3, 3))


def ecg(times: np.ndarray, t0: float, n: int, fs: int, intensity: float,
        phase: float, noise: np.random.Generator) -> np.ndarray:
    """Exercise ECG over samples [t0·fs, t0·fs + n) in ADC units: a QRS
    complex and T wave at every beat time, baseline wander and EMG noise
    that grow with ``intensity`` (after the BayeSlope protocol)."""
    ts = t0 + np.arange(n) / fs
    sig = np.zeros(n)
    amp = 1.2 * (1.0 + 0.6 * intensity)
    half = int(0.4 * fs)
    for p in times:
        c = int(round((p - t0) * fs))
        lo, hi = max(c - half, 0), min(c + half, n)
        if lo >= hi:
            continue
        tt = ts[lo:hi]
        for a, dt, w in _WAVES:
            a = amp if dt == 0.0 else a
            sig[lo:hi] += a * np.exp(-((tt - p - dt) ** 2) / (2 * w * w))
    sig += (0.1 + 0.4 * intensity) * np.sin(2 * np.pi * 0.33 * ts + phase)
    sig += noise.normal(0, 0.02 + 0.15 * intensity, n)
    return sig * 200.0


@dataclasses.dataclass(frozen=True)
class Fleet:
    """The deployment as the generator needs it."""

    seed: int
    patients: int
    fs: int                    # sensor rate, samples/s
    window: int                # samples per window
    warm_windows: int
    chunk_min: int
    chunk_max: int
    dup_rate: float
    defer_rate: float
    defer_depth: int
    pin_every: int             # every pin_every-th patient is pinned
    task: str = "rpeak"
    modality: str = "ecg"

    @classmethod
    def make(cls, config: dict, traffic: dict, seed: int) -> "Fleet":
        return cls(seed=int(seed), patients=int(traffic["patients"]),
                   fs=int(config["ecg_fs"]),
                   window=int(round(config["window_s"] * config["ecg_fs"])),
                   warm_windows=int(traffic["warm_windows"]),
                   chunk_min=int(traffic["chunk_samples"][0]),
                   chunk_max=int(traffic["chunk_samples"][1]),
                   dup_rate=float(config["dup_rate"]),
                   defer_rate=float(config["defer_rate"]),
                   defer_depth=int(config["defer_depth"]),
                   pin_every=int(config["pin_every"]))

    def patient_id(self, p: int) -> str:
        return f"ecg-{p:05d}"

    def pinned(self, p: int) -> bool:
        return p % self.pin_every == self.pin_every - 1

    def slot(self, p: int) -> int:
        """The timing slot patient ``p`` takes under this seed."""
        return _slots(self.seed, self.patients, self.pin_every)[p]

    def phase_s(self, p: int) -> float:
        """When in its window grid patient ``p`` joins the measured window."""
        return _phase(self.slot(p), self.window / self.fs)

    # segment 0 is the warm burst; segment k ≥ 1 covers SEGMENT_WINDOWS
    # windows from sample warm + (k-1)·L on
    def segment_bounds(self, k: int) -> Tuple[int, int]:
        warm = self.warm_windows * self.window
        if k == 0:
            return 0, warm
        L = SEGMENT_WINDOWS * self.window
        return warm + (k - 1) * L, warm + k * L

    def intensity(self, p: int) -> float:
        """Exercise level of patient ``p``: 60, 100, 140 or 180 bpm."""
        return (p % 4) / 3.0

    def rr_s(self, p: int) -> float:
        return 60.0 / (60.0 + 120.0 * self.intensity(p))

    def _beats(self, p: int, k: int) -> np.ndarray:
        """Beat times (s) whose nominal time falls in segment ``k``."""
        a, b = self.segment_bounds(k)
        rr = self.rr_s(p)
        first = int(np.ceil(a / self.fs / rr))
        last = int(np.ceil(b / self.fs / rr))
        return beats(_rng(self.seed, p, k, 1), first, last, rr)

    def segment(self, p: int, k: int) -> Tuple[np.ndarray, np.ndarray]:
        """Patient ``p``'s record over segment ``k``: (signal float32, R peak
        sample indices, absolute).  A segment depends on nothing but the
        seed, the patient and ``k``, yet the record runs on across
        segments: beats near an edge are drawn in both."""
        a, b = self.segment_bounds(k)
        near = [self._beats(p, j) for j in (k - 1, k, k + 1)
                if j >= 0 and self.segment_bounds(j)[0] >= 0]
        times = np.concatenate(near)
        sig = ecg(times, a / self.fs, b - a, self.fs, self.intensity(p),
                  float(_rng(self.seed, p, 9).uniform(0, 6)),
                  _rng(self.seed, p, k, 4))
        r = np.round(self._beats(p, k) * self.fs).astype(np.int64)
        return sig.astype(np.float32), r

    def segments_for(self, n_samples: int) -> int:
        """How many segments hold the first ``n_samples`` samples."""
        k = 0
        while self.segment_bounds(k)[1] < n_samples:
            k += 1
        return k + 1

    def chunks(self, p: int, k: int) -> List[Tuple[int, int]]:
        """Radio frames of segment ``k`` as absolute [start, end) samples."""
        a, b = self.segment_bounds(k)
        rng = _rng(TIMING, self.slot(p), k, 2)
        out, pos = [], a
        while pos < b:
            n = int(rng.integers(self.chunk_min, self.chunk_max + 1))
            out.append((pos, min(pos + n, b)))
            pos += n
        return out

    def send_order(self, p: int, k: int) -> List[int]:
        """Indices into ``chunks(p, k)`` in the order the radio delivers
        them: ``dup_rate`` re-sends an earlier frame of the segment,
        ``defer_rate`` holds one back ``defer_depth`` sends (a loss and a
        late retransmission).  Every frame is sent at least once."""
        n = len(self.chunks(p, k))
        rng = _rng(TIMING, self.slot(p), k, 3)
        out: List[int] = []
        deferred: List[Tuple[int, int]] = []
        for i in range(n):
            if self.defer_rate and rng.uniform() < self.defer_rate:
                deferred.append((len(out) + self.defer_depth, i))
            else:
                out.append(i)
            if self.dup_rate and out and rng.uniform() < self.dup_rate:
                out.append(out[int(rng.integers(len(out)))])
            for d in [d for d in deferred if d[0] <= len(out)]:
                deferred.remove(d)
                out.append(d[1])
        out.extend(i for _, i in deferred)
        return out

    def truth(self, p: int, n_samples: int) -> np.ndarray:
        """The R peaks of patient ``p``'s first ``n_samples`` samples."""
        rs = [self.segment(p, k)[1] for k in range(self.segments_for(
            n_samples))]
        r = np.concatenate(rs) if rs else np.zeros(0, np.int64)
        return r[r < n_samples]

    def signal(self, p: int, n_samples: int) -> np.ndarray:
        sig = np.concatenate([self.segment(p, k)[0] for k in range(
            self.segments_for(n_samples))])
        return sig[:n_samples]


# -- the schedule ---------------------------------------------------------------

def due_time(fleet: Fleet, p: int, end_sample: int, t_open: float) -> float:
    """When a frame whose last sample is ``end_sample - 1`` is due, for the
    samples after the warm burst: the moment that sample exists."""
    warm = fleet.warm_windows * fleet.window
    return t_open + fleet.phase_s(p) + (end_sample - warm) / fleet.fs


def window_due(fleet: Fleet, p: int, widx: int, t_open: float) -> float:
    """Due time of the frame that carries window ``widx``'s last sample
    (the window cannot be complete before it is sent); -inf for the warm
    burst's windows, which are never due inside the window."""
    last = (widx + 1) * fleet.window - 1
    warm = fleet.warm_windows * fleet.window
    if last < warm:
        return -math.inf
    k = 1 + (last - warm) // (SEGMENT_WINDOWS * fleet.window)
    for a, b in fleet.chunks(p, k):
        if a <= last < b:
            return due_time(fleet, p, b, t_open)
    raise AssertionError("a sample outside every frame")


# -- the sender process ---------------------------------------------------------

@dataclasses.dataclass
class SenderReport:
    """What the sender hands back: samples each patient sent (its windows
    are due), and how late it ran against its schedule."""

    samples: Dict[int, int]
    lateness_s: List[float]
    frames: int
    unconfirmed_closes: int
    gc_in_window: List[Tuple[float, int]]    # (seconds, generation)


async def _stream(fleet: Fleet, mode: str, host: str, port: int, ctrl,
                  repro_src: str, patients: Sequence[int] = None,
                  horizon_s: float = 0.0) -> SenderReport:
    """Drive ``patients`` (all, by default): the warm burst, then — once
    ``ctrl`` says the window is open — the schedule, then BYE.  In steady
    mode every frame due within ``horizon_s`` of the opening is built
    before the window opens, so that building records never delays a
    send."""
    import sys
    if repro_src not in sys.path:
        sys.path.insert(0, repro_src)
    from repro.ingest.client import ReplayingClient
    from repro.ingest.protocol import bye, data

    patients = range(fleet.patients) if patients is None else patients
    gcm = GcMeter()
    clients = {}
    samples: Dict[int, int] = {}
    lateness: List[float] = []
    frames = [0]
    seq_base: Dict[int, int] = {}     # patient → seq of its segment's
                                      # first frame (stream order)
    built: Dict[Tuple[int, int], tuple] = {}

    def build(p: int, k: int) -> tuple:
        """Segment ``k`` of patient ``p``: signal, frames, send order and
        each frame's due time from the opening (steady mode)."""
        got = built.pop((p, k), None)
        if got is not None:
            return got
        sig, _ = fleet.segment(p, k)
        ch = fleet.chunks(p, k)
        due = ({b: due_time(fleet, p, b, 0.0) for _, b in ch}
               if mode == "steady" else None)
        return sig, ch, fleet.send_order(p, k), due

    async def send_segment(p: int, k: int, t_open: Optional[float],
                           stop_at: float) -> bool:
        """Send segment ``k``; from ``t_open`` on, each frame waits for its
        slot in the schedule.  False once past ``stop_at``."""
        cli = clients[p]
        sig, ch, order, due = build(p, k)
        base = seq_base.get(p, 0)
        seq_base[p] = base + len(ch)
        a0 = fleet.segment_bounds(k)[0]
        sent = set()
        slot = -math.inf     # a resent or late frame takes the next slot
        for i in order:
            if t_open is not None and due is not None:
                slot = max(slot, t_open + due[ch[i][1]])
                if slot > stop_at:
                    break
                wait = slot - time.perf_counter()
                if wait > 0:
                    await asyncio.sleep(wait)
                lateness.append(time.perf_counter() - slot)
            elif time.perf_counter() > stop_at:
                break
            a, b = ch[i]
            await cli.send(data(fleet.patient_id(p), fleet.task,
                                fleet.modality, base + i,
                                sig[None, a - a0:b - a0]))
            frames[0] += 1
            sent.add(i)
        # the gap-free prefix of the record that reached the wire: a frame
        # deferred past the stop was never sent, so what follows it is not
        # a window that is due
        for i, (a, b) in enumerate(ch):
            if i not in sent:
                return False
            samples[p] = b
        return True

    async def warm(p: int) -> None:
        clients[p] = ReplayingClient(
            fleet.patient_id(p), fleet.task, lambda: (host, port),
            close_timeout_s=120.0)
        samples[p] = 0
        await send_segment(p, 0, None, math.inf)

    async def run(p: int, t_open: float, t_close: float) -> None:
        k = 1
        while await send_segment(p, k, t_open if mode == "steady" else None,
                                 t_close):
            k += 1

    await asyncio.gather(*(warm(p) for p in patients))
    if mode == "steady":
        seg_s = SEGMENT_WINDOWS * fleet.window / fleet.fs
        n_seg = int(math.ceil((horizon_s + fleet.window / fleet.fs)
                              / seg_s)) + 1
        for p in patients:
            for k in range(1, n_seg + 1):
                built[(p, k)] = build(p, k)
    ctrl.send(("warm_sent", frames[0]))
    while not ctrl.poll():
        await asyncio.sleep(0.01)
    _, t_open, t_close = ctrl.recv()
    await asyncio.gather(*(run(p, t_open, t_close) for p in patients))
    for p, cli in clients.items():
        await cli.send(bye(fleet.patient_id(p), fleet.task))
    await asyncio.gather(*(cli.close() for cli in clients.values()))
    unconfirmed = sum(c.stats.unconfirmed_closes for c in clients.values())
    gcm.close()
    return SenderReport(samples=samples, lateness_s=lateness,
                        frames=frames[0], unconfirmed_closes=unconfirmed,
                        gc_in_window=gcm.between(t_open, t_close))


def sender_main(conn, config: dict, traffic: dict, seed: int, host: str,
                port: int, repro_src: str, part: int = 0, parts: int = 1,
                horizon_s: float = 0.0) -> None:
    """Entry point of a sender process (started with ``spawn``): it drives
    patients ``part``, ``part + parts``, ... of the fleet."""
    try:
        fleet = Fleet.make(config, traffic, seed)
        rep = asyncio.run(_stream(fleet, traffic["mode"], host, port, conn,
                                  repro_src,
                                  range(part, fleet.patients, parts),
                                  horizon_s))
        conn.send(("done", dataclasses.asdict(rep)))   # plain data
    except BaseException as e:  # noqa: BLE001 — must reach the parent
        conn.send(("error", repr(e)))
        raise
    finally:
        conn.close()
