"""Format-parametrized window pipelines: one compiled function per
(task, format), shared bit-for-bit with the offline evaluation paths.

* cough  — ``apps.cough.make_cough_scorer`` (FFT→PSD→MFCC→spectral + IMU
  features → random forest), batch over windows from many patients.
* rpeak  — BayeSlope stages 1–2 (``apps.bayeslope.rpeak_window_scores``)
  jit+vmap over windows, plus an in-format candidate-peak count per window
  (the per-window heart-rate proxy the fleet monitor consumes).

Each pipeline also states its per-window arithmetic op counts so the engine
can put nJ/window numbers next to throughput (see ``stream.accounting``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.apps.bayeslope import RPEAK_WINDOW_S, rpeak_window_scores
from repro.apps.cough import make_cough_scorer
from repro.apps.forest import Forest
from repro.core.arith import Arith, fusion_cache_key
from repro.data.biosignals import AUDIO_SR, ECG_FS, IMU_SR, WINDOW_S
from repro.energy.model import OpCounts

from .accounting import cough_window_op_counts, rpeak_window_op_counts
from .ring import ModalitySpec, WindowSpec
from .tracker import RPeakTracker


def _jit_batch_fn(fn):
    """jit the batched window fn, donating the input buffers: the engine
    builds fresh arrays per dispatch, so XLA may reuse their pages for the
    outputs. CPU ignores donation (and warns) — skip it there."""
    if jax.default_backend() == "cpu":
        return jax.jit(fn)
    return jax.jit(fn, donate_argnums=0)

COUGH_SPEC = WindowSpec(
    task="cough",
    modalities=(ModalitySpec("audio", 2, AUDIO_SR),
                ModalitySpec("imu", 9, IMU_SR)),
    window_s=WINDOW_S, hop_s=WINDOW_S)

RPEAK_SPEC = WindowSpec(
    task="rpeak",
    modalities=(ModalitySpec("ecg", 1, ECG_FS),),
    window_s=RPEAK_WINDOW_S, hop_s=RPEAK_WINDOW_S)


@dataclasses.dataclass(frozen=True)
class Pipeline:
    """One streaming task: its window grid, compiled-fn factory, op counts.

    ``make_fn(fmt)`` returns a jit-compiled function mapping a dict of
    batched modality arrays (each ``(B, channels, n)`` float32) to a dict of
    batched outputs; rows are independent, so any batch size reuses the same
    compiled code per bucket and padding rows never affect real rows.

    ``make_tracker`` (optional) builds a per-patient ``RPeakTracker`` from a
    patient id; the engine stages each batch's thresholds in one launch
    (``tracker.stage_thresholds``), then feeds each tracker its windows'
    outputs in ``widx`` order (``tracker.update(widx, outputs, fmt)``), and
    the updates land on the ``WindowResult`` plus the router's escalation
    feedback.
    """

    name: str
    spec: WindowSpec
    make_fn: Callable[[str], Callable[[Dict[str, jax.Array]],
                                      Dict[str, jax.Array]]]
    ops_per_window: OpCounts
    make_tracker: Optional[Callable[[str], object]] = None


def cough_pipeline(forest: Forest) -> Pipeline:
    @functools.lru_cache(maxsize=None)
    def make_fn_cached(fmt: str, backend_key: tuple):
        # memoized per pipeline instance: engines sharing one Pipeline
        # (e.g. a transport engine and its in-process parity reference)
        # share the compiled function instead of re-tracing per engine
        scorer = make_cough_scorer(fmt, forest)

        def fn(arrays: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
            # audio arrives at the full 300 ms window (4800 samples); the
            # scorer itself crops/pads to the 4096-point FFT like the
            # offline path.
            return {"p_cough": scorer(arrays["audio"], arrays["imu"])}

        return _jit_batch_fn(fn)

    def make_fn(fmt: str):
        return make_fn_cached(fmt, fusion_cache_key())

    # bill energy for the forest actually deployed, not the default size
    ops = cough_window_op_counts(n_trees=forest.feat.shape[0],
                                 depth=forest.depth)
    return Pipeline("cough", COUGH_SPEC, make_fn, ops)


@functools.lru_cache(maxsize=None)
def _rpeak_batch_fn_cached(fmt: str, peak_threshold: float, refr: int,
                           backend_key: tuple):
    ar = Arith.make(fmt)

    def one_window(sig: jax.Array) -> Dict[str, jax.Array]:
        norm = rpeak_window_scores(ar, sig)
        # candidate count: above threshold AND the maximum within the
        # ±refractory neighbourhood (≥ towards the past, > towards the
        # future — the same tie-break as the offline detector's greedy
        # pass). A cheap per-window HR proxy, not the Bayesian stage.
        is_peak = norm > peak_threshold
        ones = jnp.ones((), jnp.bool_)
        for d in range(1, refr + 1):
            ge_past = jnp.concatenate(
                [jnp.broadcast_to(ones, (d,)), norm[d:] >= norm[:-d]])
            gt_future = jnp.concatenate(
                [norm[:-d] > norm[d:], jnp.broadcast_to(ones, (d,))])
            is_peak &= ge_past & gt_future
        return {"scores": norm,
                "peak_count": jnp.sum(is_peak).astype(jnp.int32)}

    def fn(arrays: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
        sig = arrays["ecg"][:, 0, :]            # (B, n) single lead
        return jax.vmap(one_window)(sig)

    return _jit_batch_fn(fn)


def _rpeak_batch_fn(fmt: str, peak_threshold: float, refr: int):
    """Compiled-batch-fn cache shared across Pipeline/engine instances —
    re-creating an engine (benchmark warmups, property tests streaming one
    record many ways) reuses the jit cache instead of re-tracing.  Keyed on
    the round-backend/fused selection so an A/B toggle retraces instead of
    serving a function traced under the other arm."""
    return _rpeak_batch_fn_cached(fmt, peak_threshold, refr,
                                  fusion_cache_key())


def rpeak_pipeline(window_s: float = RPEAK_WINDOW_S,
                   peak_threshold: float = 0.5,
                   refractory_s: float = 0.1,
                   track_peaks: bool = True) -> Pipeline:
    """``track_peaks`` attaches a per-patient ``RPeakTracker`` carrying
    BayeSlope stages 3-4 across windows — each ``WindowResult`` then gains a
    ``peaks`` output (absolute samples confirmed by that window), identical
    to the offline ``detect_rpeaks`` stream."""
    n = int(round(window_s * ECG_FS))
    refr = max(int(round(refractory_s * ECG_FS)), 1)
    spec = RPEAK_SPEC if window_s == RPEAK_WINDOW_S else WindowSpec(
        task="rpeak", modalities=(ModalitySpec("ecg", 1, ECG_FS),),
        window_s=window_s, hop_s=window_s)

    def make_fn(fmt: str):
        return _rpeak_batch_fn(fmt, peak_threshold, refr)

    make_tracker = (
        (lambda patient: RPeakTracker(
            patient, fs=ECG_FS, window_samples=n))
        if track_peaks else None)
    return Pipeline("rpeak", spec, make_fn, rpeak_window_op_counts(n),
                    make_tracker)
