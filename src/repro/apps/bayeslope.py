"""BayeSlope R-peak detection (paper §IV-B), format-parametrized.

Pipeline per the paper's description of [8]:
  1. slope-product peak enhancement (this is where amplitudes blow past
     FP16/FP8 ranges — the ECG is in ADC-scale units),
  2. generalized-logistic normalization,
  3. k-means (2 clusters) → adaptive R-vs-baseline threshold,
  4. Bayesian filter: Gaussian prior on the next R position from the running
     RR estimate, used to re-weight candidates under intense exercise.

Stages 1-2 run vectorized in the target format over fixed windows
(``rpeak_window_scores``) — the same jit-compiled core the streaming runtime
dispatches. Stages 3-4 are *window-incremental*: ``fold_thresholds`` (an
incremental 2-means over a bounded score reservoir, arithmetic in the
window's format, many windows in one launch), ``stitch_peaks``
(greedy-refractory candidate selection stitched across window boundaries
via a deferred commit frontier) and ``recover_gaps`` (the Bayesian
RR-prior gap walk over the retained score tail). ``RPeakFold`` threads the cross-window state through those functions;
``detect_rpeaks`` is a thin fold over the windows of a full recording, and
the streaming ``repro.stream.tracker.RPeakTracker`` drives the *same* fold
as windows arrive — so streaming peak output is identical to the offline
path by construction, and ``tests/test_stream_parity.py`` locks it down.

The stage 3-4 control flow runs in float64 on the format-rounded scores (on
PHEE it would run on the host core; its values are O(1) and
format-insensitive — noted in DESIGN.md simplifications); the k-means
threshold itself runs in the window's routed arithmetic.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Callable, Deque, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.arith import (Arith, backend_overrides, fusion_cache_key,
                              get_round_backend)
from repro.data.biosignals import ECG_FS, ecg_dataset

from .kmeans import kmeans_1d_rows
from .metrics import rpeak_f1

# Canonical fold/stream window (the streaming runtime's R-peak hop grid).
RPEAK_WINDOW_S = 2.0
# Greedy-refractory spacing between accepted peaks (~270 bpm ceiling).
REFRACTORY_S = 0.22
# Explicit k-means reservoir: at most this many subsampled scores feed the
# 2-means threshold, regardless of how much signal has streamed past.  (The
# old offline path derived a stride from the segment length — `len // 500` —
# which kept EVERY sample for 501..999-sample segments; the bounded reservoir
# replaces it.)
RESERVOIR_SIZE = 500
# Every RESERVOIR_STRIDE-th score of each window enters the reservoir, so at
# the 2 s / 500-sample window the reservoir spans the last ~5 windows (10 s)
# of scores — the threshold adapts on that horizon.
RESERVOIR_STRIDE = 5
# Rows of one launch of the batched 2-means threshold, one window each: a
# fixed shape, so each format compiles one threshold program whatever the
# dispatch's size or the reservoirs' lengths.
THRESHOLD_ROWS = 64
# Candidates collected before the RR estimate bootstraps (median of diffs).
RR_BOOT = 8
# Retained score-tail cap: the Bayesian gap walk can re-search at most this
# far back, which bounds per-patient tracker memory.
TAIL_MAX_S = 8.0


def enhance(ar: Arith, sig: jnp.ndarray) -> jnp.ndarray:
    """|slope_t| * |slope_{t+1}|, 3-tap smoothed — steep on both sides ⇒ R.

    The smoothing (computed in-format) suppresses single-sample EMG spikes,
    whose slope products otherwise share the R-peak amplitude range.

    Operates over the LAST axis: a full 1-D segment (offline detection) or a
    (..., B, n) batch of windows (streaming runtime) go through the same ops.
    """
    x = ar.rnd(sig)
    n = x.shape[-1]
    d = ar.sub(x[..., 1:], x[..., :-1])
    ad = jnp.abs(d)
    enh = ar.mul(ad[..., :-1], ad[..., 1:])
    enh = jnp.concatenate([enh[..., :1], enh, enh[..., -1:]], axis=-1)
    # moving-window integration (~0.1 s), every add/div in-format.
    # Pre-scaled accumulation again: divide first so IEEE sums stay in range.
    K = 25
    contrib = ar.div(enh, float(K))
    zeros = jnp.zeros((*enh.shape[:-1], K - 1), enh.dtype)
    pad = jnp.concatenate([zeros, contrib], axis=-1)
    acc = pad[..., :n] * 0.0
    for i in range(K):
        acc = ar.add(acc, pad[..., i: i + n])
    return acc


def glf_normalize(ar: Arith, enh: jnp.ndarray) -> jnp.ndarray:
    """Generalized logistic squashing around the running scale (last axis)."""
    mu = ar.mean(enh, axis=-1)
    scale = jnp.maximum(mu, 1e-12)[..., None]
    z = ar.div(enh, scale)
    # y = 1 / (1 + exp(-(z - 1)))  computed with rounded ops
    e = ar.exp(jnp.clip(ar.sub(1.0, z), -30.0, 30.0))
    return ar.div(1.0, ar.add(1.0, e))


def rpeak_window_scores(ar: Arith, windows: jnp.ndarray) -> jnp.ndarray:
    """Window-level core of BayeSlope stages 1–2, shared by the offline
    ``detect_rpeaks`` path and the streaming runtime: slope-product
    enhancement + GLF normalization over the last axis."""
    return glf_normalize(ar, enhance(ar, windows))


@functools.lru_cache(maxsize=None)
def _score_fn_cached(fmt_name: str, n: int, backend_key: tuple):
    ar = Arith.make(fmt_name)
    return jax.jit(lambda x: rpeak_window_scores(ar, x))


def _score_fn(fmt_name: str, n: int):
    """jit-compiled stage 1-2 scores for one (format, window length); keyed
    on the backend selection so an A/B toggle retraces."""
    return _score_fn_cached(fmt_name, n, fusion_cache_key())


def _threshold_program(fmt_name: str, args: Sequence[jax.ShapeDtypeStruct]):
    """Compile the batched 2-means of one format for ``args``, the shapes
    of its inputs: reservoirs (rows, entries), valid lengths, warm starts
    (rows, 2) and whether each row has one, predecessor rows (-1: none),
    and the chain depth to loop over.

    Its posit rounds are plain jnp ops, bit-identical to the Pallas round
    kernel: XLA fuses each k-means iteration of the chain loop into a few
    kernels, where a Pallas round is a custom call it cannot fuse."""
    ar = Arith.make(fmt_name)

    def run(x, n, init, has_init, pred, depth):
        valid = jnp.arange(x.shape[1])[None, :] < n[:, None]
        chained = pred >= 0
        src = jnp.maximum(pred, 0)

        def level(_, cents):
            # a row at chain depth d is final after level d: its
            # predecessor was final after level d - 1.  Non-finite
            # centroids never warm-start; that row starts cold.
            prev = cents[src]
            start = jnp.where(chained[:, None], prev, init)
            warm = jnp.where(chained, jnp.all(jnp.isfinite(prev), axis=1),
                             has_init)
            return kmeans_1d_rows(ar, x, valid, start, warm)

        return jax.lax.fori_loop(0, depth, level, jnp.zeros_like(init))

    rb = "jnp" if get_round_backend() == "pallas" else None
    with backend_overrides(round_backend=rb):
        return jax.jit(run).lower(*args).compile()


@functools.lru_cache(maxsize=None)
def _threshold_fn_cached(fmt_name: str, backend_key: tuple):
    R, N = THRESHOLD_ROWS, RESERVOIR_SIZE
    S = jax.ShapeDtypeStruct
    return _threshold_program(fmt_name, (
        S((R, N), jnp.float32), S((R,), jnp.int32), S((R, 2), jnp.float32),
        S((R,), jnp.bool_), S((R,), jnp.int32), S((), jnp.int32)))


def _threshold_fn(fmt_name: str):
    """The batched 2-means of one format: (THRESHOLD_ROWS, RESERVOIR_SIZE)
    reservoirs, whatever their lengths, in one compiled program."""
    return _threshold_fn_cached(fmt_name, fusion_cache_key())


# ---------------------------------------------------------------------------
# Stages 3-4 as pure window-incremental functions
# ---------------------------------------------------------------------------

def reservoir_update(reservoir: np.ndarray, scores: np.ndarray,
                     size: int = RESERVOIR_SIZE,
                     stride: int = RESERVOIR_STRIDE) -> np.ndarray:
    """FIFO reservoir of subsampled window scores feeding the threshold.

    Keeps the LAST ``size`` entries, so the threshold always reflects recent
    signal — never more than ``size`` values regardless of stream length.
    """
    sub = np.asarray(scores, np.float32).reshape(-1)[::stride]
    return np.concatenate([reservoir, sub])[-size:]


@dataclasses.dataclass
class StagedWindow:
    """One window of a fold between its reservoir step (``RPeakFold.stage``)
    and the rest of its step (``RPeakFold.advance``)."""

    scores: np.ndarray                # sanitized, float64
    # the reservoir its threshold clusters; None: an empty window, which
    # keeps the fold's threshold
    reservoir: Optional[np.ndarray]
    # set by ``fold_thresholds``: the 2-means centroids and threshold
    cents: Optional[np.ndarray] = None
    thr: Optional[float] = None


def _chain_depth(pred: np.ndarray) -> int:
    """Windows in the deepest chain of a launch: the levels it loops over."""
    depth = np.zeros(len(pred), np.int64)
    for i, p in enumerate(pred):
        if p >= 0:
            depth[i] = depth[p] + 1
    return int(depth.max(initial=-1)) + 1


def fold_thresholds(ar: Arith,
                    rows: Sequence[Tuple["RPeakFold", StagedWindow]],
                    on_launch: Optional[Callable[[float, float, int, int],
                                                 None]] = None) -> None:
    """Incremental 2-means thresholds of staged windows, in ``ar``'s format.

    ``rows`` holds each fold's staged windows in its window order.  One
    launch of the batched 2-means takes ``THRESHOLD_ROWS`` of them, with
    one copy of the centroids to the host.  A window warm-starts from the
    previous window of its fold: from a row before it in the same launch
    (the launch loops over the chains' depth), else from the centroids the
    fold holds.  Sets each window's ``thr`` = 0.3·low + 0.7·high (weighted
    toward the R cluster), NaN when the arithmetic collapsed (e.g. FP8E4M3
    → NaN), and each fold's warm start: its last centroids where finite,
    else None, so its next window starts cold.

    ``on_launch(start, end, rows, depth)`` hears of each launch, timed on
    ``time.perf_counter``.
    """
    fn = _threshold_fn(ar.name)
    for c0 in range(0, len(rows), THRESHOLD_ROWS):
        chunk = rows[c0: c0 + THRESHOLD_ROWS]
        x = np.zeros((THRESHOLD_ROWS, RESERVOIR_SIZE), np.float32)
        n = np.zeros(THRESHOLD_ROWS, np.int32)
        init = np.zeros((THRESHOLD_ROWS, 2), np.float32)
        has_init = np.zeros(THRESHOLD_ROWS, bool)
        pred = np.full(THRESHOLD_ROWS, -1, np.int32)
        last: Dict[int, int] = {}
        for i, (fold, st) in enumerate(chunk):
            res = st.reservoir
            x[i, :len(res)] = res
            n[i] = len(res)
            j = last.get(id(fold))
            if j is not None:
                pred[i] = j
            elif fold.cents is not None:
                init[i], has_init[i] = fold.cents, True
            last[id(fold)] = i
        depth = _chain_depth(pred[:len(chunk)])
        t0 = time.perf_counter()
        cents = np.asarray(fn(x, n, init, has_init, pred, np.int32(depth)))
        if on_launch is not None:
            on_launch(t0, time.perf_counter(), len(chunk), depth)
        for (fold, st), c in zip(chunk, cents):
            lo, hi = np.sort(c.astype(np.float64))
            thr = 0.3 * lo + 0.7 * hi
            st.cents = c
            st.thr = float(thr) if np.isfinite(thr) else float("nan")
            fold.cents = c if np.all(np.isfinite(c)) else None


def stitch_peaks(e: np.ndarray, start: int, committed: int, commit_to: int,
                 end: int, thr: float, refractory: int,
                 taken: List[int]) -> List[int]:
    """Greedy-refractory candidate peaks on the newly committable region.

    ``e`` is the retained score tail (float64, NaN→0) with ``e[0]`` at
    absolute sample ``start``; candidates are finalized for absolute
    positions [``committed``, ``commit_to``) — the caller leaves a
    refractory+1 lookahead margin uncommitted until the next window (or the
    final flush), so a peak straddling a window boundary is judged with both
    neighbours present.  ``taken`` holds recently accepted peaks (absolute);
    accepted candidates are appended to it.  Returns the newly accepted
    candidates in ascending order.
    """
    lo = max(committed, 1)              # first sample has no left neighbour
    hi = min(commit_to, end - 1)        # last sample has no right neighbour
    if hi <= lo or not np.isfinite(thr):
        return []
    idx = np.arange(lo, hi)
    v = e[idx - start]
    is_max = (v > thr) & (v >= e[idx - start - 1]) & (v >= e[idx - start + 1])
    cand = idx[is_max]
    if not len(cand):
        return []
    order = cand[np.argsort(-e[cand - start], kind="stable")]
    accepted: List[int] = []
    for p in order:
        p = int(p)
        if any(p - refractory <= q < p + refractory for q in taken):
            continue
        taken.append(p)
        accepted.append(p)
    accepted.sort()
    return accepted


def recover_gaps(e: np.ndarray, start: int, out: List[int], nxt: int,
                 rr: float, thr: float, refractory: int) -> float:
    """Bayesian RR-prior gap walk between ``out[-1]`` and candidate ``nxt``.

    For inter-peak gaps much longer than the running RR estimate, re-search
    the retained score tail with a Gaussian prior on the expected position
    and a relaxed threshold.  Appends recovered peaks plus ``nxt`` to ``out``
    and returns the updated RR estimate.
    """
    gap = nxt - out[-1]
    while gap > 1.55 * rr:
        expect = out[-1] + rr
        lo = int(max(out[-1] + refractory, expect - 0.4 * rr))
        hi = int(min(nxt - refractory, expect + 0.4 * rr))
        lo = max(lo, start)                   # tail-trim clamp
        hi = min(hi, start + len(e))
        if hi <= lo:
            break
        t = np.arange(lo, hi)
        prior = np.exp(-((t - expect) ** 2) / (2 * (0.3 * rr) ** 2))
        j = int(np.argmax(e[lo - start: hi - start] * prior))
        p = lo + j
        if np.isfinite(thr) and e[p - start] > 0.25 * thr:
            out.append(p)
            rr = 0.8 * rr + 0.2 * (out[-1] - out[-2])
            gap = nxt - out[-1]
        else:
            break
    out.append(nxt)
    if len(out) >= 2:
        rr = 0.8 * rr + 0.2 * min(nxt - out[-2], 1.5 * rr)
    return rr


class RPeakFold:
    """Cross-window BayeSlope stages 3-4 state machine.

    One instance per ECG stream; it consumes consecutive windows' stage 1-2
    scores and returns newly *confirmed* peaks (absolute sample indices,
    ascending across calls).  The offline ``detect_rpeaks`` and the
    streaming ``RPeakTracker`` both drive this class with the identical
    sequence of window steps, thresholds from the same compiled program —
    then one empty ``finalize`` flush — which is what makes streaming
    output equal offline output for any chunking of the input.

    State carried across windows:
      * score ``reservoir`` + warm-started centroids → adaptive threshold,
      * a retained score ``tail`` (bounded by ``tail_max_s``) for boundary
        stitching and gap re-search,
      * the deferred commit frontier (refractory+1 lookahead) so candidates
        at a window edge are judged with both neighbours present,
      * recently accepted candidates (``taken``) enforcing the refractory
        across boundaries,
      * the RR estimate (bootstrapped from the first ``rr_boot`` candidates,
        then EMA-updated exactly as the paper's stage 4).

    Each window takes two steps: ``stage`` feeds its scores to the
    reservoir, and ``advance`` — once ``fold_thresholds`` has set the
    window's threshold — does the rest of it.  Reservoirs depend on scores
    alone, so a batch stages all its windows and clusters them in one
    launch before any advances; ``push`` takes both steps for one window.
    """

    def __init__(self, fs: int = ECG_FS,
                 reservoir_stride: int = RESERVOIR_STRIDE,
                 rr_boot: int = RR_BOOT, tail_max_s: float = TAIL_MAX_S):
        self.fs = fs
        self.refractory = int(REFRACTORY_S * fs)
        self.reservoir_stride = reservoir_stride
        self.rr_boot = rr_boot
        self.tail_max = int(tail_max_s * fs)
        self.reservoir = np.zeros(0, np.float32)
        self.cents: Optional[np.ndarray] = None   # warm-start centroids
        self.staged: Deque[StagedWindow] = collections.deque()
        self.thr = float("nan")
        self.tail = np.zeros(0, np.float64)
        self.tail_start = 0
        self.end = 0                    # absolute samples consumed
        self.committed = 0              # candidates finalized for [0, here)
        self.taken: List[int] = []      # recent accepted candidates
        self.pending: List[int] = []    # candidates before the RR bootstrap
        self.out: List[int] = []        # confirmed peak stream
        self.rr: Optional[float] = None
        self.emitted = 0
        self.finalized = False

    def stage(self, scores: np.ndarray) -> StagedWindow:
        """First step of the next window: its scores into the reservoir.

        The SANITIZED scores enter the reservoir: one NaN/Inf artifact
        window must not poison the threshold for the reservoir's whole FIFO
        lifetime after the arithmetic recovers."""
        if self.finalized:
            raise RuntimeError("RPeakFold already finalized")
        s32 = np.asarray(scores, np.float32).reshape(-1)
        s = np.nan_to_num(np.asarray(s32, np.float64),
                          nan=0.0, posinf=0.0, neginf=0.0)
        reservoir = None
        if len(s):
            self.reservoir = reservoir = reservoir_update(
                self.reservoir, s, RESERVOIR_SIZE, self.reservoir_stride)
        st = StagedWindow(s, reservoir)
        self.staged.append(st)
        return st

    def push(self, ar: Arith, scores: np.ndarray,
             final: bool = False) -> np.ndarray:
        """Consume the next window's scores; return newly confirmed peaks.
        The window's threshold is a launch of one row."""
        st = self.stage(scores)
        if st.reservoir is not None:
            fold_thresholds(ar, [(self, st)])
        return self.advance(final)

    def advance(self, final: bool = False) -> np.ndarray:
        """Second step of the oldest staged window, its threshold set:
        stitching, the RR prior and the gap walk; returns newly confirmed
        peaks."""
        st = self.staged.popleft()
        if st.reservoir is not None:
            if st.thr is None:
                raise RuntimeError("window advanced before its threshold")
            self.thr = st.thr
        s = st.scores
        self.tail = np.concatenate([self.tail, s])
        self.end += len(s)
        commit_to = self.end if final else max(
            self.end - (self.refractory + 1), self.committed)
        new_cands = stitch_peaks(self.tail, self.tail_start, self.committed,
                                 commit_to, self.end, self.thr,
                                 self.refractory, self.taken)
        self.committed = max(self.committed, commit_to)
        self.taken = [q for q in self.taken
                      if q >= self.committed - self.refractory]
        for c in new_cands:
            if self.rr is None:
                self.pending.append(c)
                if len(self.pending) >= self.rr_boot:
                    self._bootstrap()
            else:
                self.rr = recover_gaps(self.tail, self.tail_start, self.out,
                                       c, self.rr, self.thr, self.refractory)
        if final:
            self.finalized = True
            if self.rr is None:
                if len(self.pending) >= 3:
                    self._bootstrap()
                else:           # too few beats for an RR prior: emit as-is
                    self.out.extend(self.pending)
                    self.pending = []
        self._trim()
        new = np.asarray(self.out[self.emitted:], np.int64)
        self.emitted = len(self.out)
        return new

    def finalize(self, ar: Arith) -> np.ndarray:
        """End-of-stream flush: commit the deferred lookahead margin."""
        if self.finalized:
            return np.zeros(0, np.int64)
        return self.push(ar, np.zeros(0, np.float32), final=True)

    @property
    def peaks(self) -> List[int]:
        """All confirmed peaks so far (complete after ``finalize``)."""
        return list(self.out)

    def _bootstrap(self) -> None:
        # RR prior from the first candidates' median spacing, then walk the
        # rest of them through the gap recovery retroactively.
        self.rr = float(np.median(np.diff(self.pending)))
        self.out.append(self.pending[0])
        for c in self.pending[1:]:
            self.rr = recover_gaps(self.tail, self.tail_start, self.out, c,
                                   self.rr, self.thr, self.refractory)
        self.pending = []

    def _trim(self) -> None:
        # retain: stitch context behind the frontier, the gap-walk span back
        # to the last confirmed (or first pending) peak — all capped by
        # tail_max so a flatlined stream cannot grow the tail unboundedly.
        anchors = [self.committed - (self.refractory + 1)]
        if self.out:
            anchors.append(self.out[-1])
        if self.pending:
            anchors.append(self.pending[0])
        keep_from = max(min(anchors), self.end - self.tail_max,
                        self.tail_start, 0)
        if keep_from > self.tail_start:
            self.tail = self.tail[keep_from - self.tail_start:]
            self.tail_start = keep_from


def detect_rpeaks(ar: Arith, sig_np: np.ndarray, fs: int = ECG_FS,
                  window_s: float = RPEAK_WINDOW_S) -> List[int]:
    """Offline BayeSlope detection: a thin fold over fixed windows.

    Splits the recording on the streaming hop grid, scores each window with
    the shared jit-compiled stages 1-2, and folds stages 3-4 through
    ``RPeakFold`` — byte-for-byte the computation the streaming tracker
    performs as windows arrive, so offline and streaming peaks agree for any
    chunking of the same record (``tests/test_stream_parity.py``).
    """
    sig = np.asarray(sig_np, np.float32)
    n = len(sig)
    if n < 4:
        return []
    W = int(round(window_s * fs))
    fold = RPeakFold(fs=fs)
    peaks: List[int] = []
    for s0 in range(0, n, W):
        w = sig[s0: s0 + W]
        if len(w) >= 3:     # enhance() needs ≥ 1 slope product
            scores = np.asarray(_score_fn(ar.name, len(w))(jnp.asarray(w)))
        else:
            scores = np.zeros(0, np.float32)
        peaks.extend(int(p) for p in fold.push(ar, scores))
    peaks.extend(int(p) for p in fold.finalize(ar))
    return peaks


def run_rpeak_detection(fmt_names, n_subjects: int = 8,
                        segments_per_subject: int = 3,
                        segment_s: float = 20.0, seed: int = 1
                        ) -> Dict[str, float]:
    """Sweep formats; returns {fmt: mean F1} (paper Fig. 5)."""
    data = ecg_dataset(n_subjects, segments_per_subject, segment_s, seed)
    out = {}
    for name in fmt_names:
        ar = Arith.make(name)
        f1s = []
        for sig, true_r in data:
            pred = detect_rpeaks(ar, sig)
            f1, _, _ = rpeak_f1(pred, true_r, ECG_FS)
            f1s.append(f1)
        out[name] = float(np.mean(f1s))
    return out
