"""StreamEngine: continuous multi-patient windowed inference.

Chunks from a fleet of simulated wearables flow in (any interleaving across
patients; in-order within one stream).  Each patient's dispatcher emits
fixed-size windows exactly once; ready windows are kept grouped per
(patient, task) with per-(task, format) counts maintained incrementally, so
ingest and pump bookkeeping stay O(1) per window instead of re-routing and
re-counting the whole pending backlog on every pump.  The engine pads each
dispatch group to a small set of batch buckets and runs the shared
jit-compiled window function, so steady-state traffic hits a handful of
compiled programs regardless of fleet size or arrival pattern.
Per-dispatch wall-clock and per-window model energy land in the ledger.

Pipelines that declare ``make_tracker`` (the R-peak pipeline does) get a
per-patient stateful tracker: each dispatched window's outputs stream
through it in order, confirmed R-peak positions come back on the
``WindowResult`` (``outputs["peaks"]``, absolute samples), and the tracker's
quality signal drives the router's precision-escalation policy, with the
extra energy of escalated windows attributed in the ledger.
"""
from __future__ import annotations

import collections
import dataclasses
import time
import warnings
from typing import Deque, Dict, List, Optional, Set, Tuple

import jax
import numpy as np

from repro.core.arith import fusion_cache_key
from repro.obs import MetricsRegistry, bind_stream_engine

from .accounting import EnergyLedger, window_energy_nj
from .pipelines import Pipeline
from .ring import Window, WindowDispatcher
from .router import PrecisionRouter
from .tracker import stage_thresholds


def bucket_size(n: int, max_batch: int) -> int:
    """Smallest power of two ≥ n (capped): bounds jit recompilation to
    log2(max_batch)+1 batch shapes per (task, format).  O(1) bit math."""
    if n <= 1:
        return 1
    return min(1 << (n - 1).bit_length(), max_batch)


def bounded_admit(queue: Deque, item, capacity: Optional[int],
                  dropped: int, warn_at: int, label,
                  on_drop=None) -> Tuple[int, int]:
    """Append ``item`` to a bounded deque, dropping the OLDEST entry past
    ``capacity`` with a rate-limited (doubling) warning.  Returns the
    updated ``(dropped, warn_at)`` counters.  Shared by the engine's result
    backlog and the supervisor's queue so the overflow policy has exactly
    one implementation.

    ``on_drop(victim)`` runs for every evicted entry BEFORE the warning
    fires, so callers can attribute drops (per patient, into a metrics
    counter) rather than only summing them; ``label`` may be a callable
    producing the message lazily — attribution detail is only formatted
    on the rate-limited path, never per admit."""
    if capacity is not None and len(queue) >= capacity:
        victim = queue.popleft()
        dropped += 1
        if on_drop is not None:
            on_drop(victim)
        if dropped >= warn_at:
            msg = label() if callable(label) else label
            warnings.warn(f"{msg}: dropped oldest — {dropped} drops so "
                          f"far", RuntimeWarning, stacklevel=3)
            warn_at = max(warn_at * 2, 1)
    queue.append(item)
    return dropped, warn_at


@dataclasses.dataclass
class WindowResult:
    """One window's inference output with full provenance.

    ``outputs`` holds zero-copy row views into the batch output arrays —
    the batch is materialized from device to numpy once per dispatch, not
    once per window.
    """

    patient: str
    task: str
    widx: int
    fmt: str
    t0_s: float
    outputs: Dict[str, np.ndarray]  # per-window slices of the batch outputs
    ready_wall: float = 0.0         # wall clock when the window became ready
    # wall clock once its batch's trackers and ledger row are done (later
    # than the outputs' arrival on the host by the tracker's time); one
    # stamp per dispatch
    done_wall: float = 0.0


class StreamEngine:
    def __init__(self, pipelines: Dict[str, Pipeline],
                 router: Optional[PrecisionRouter] = None,
                 max_batch: int = 64, pad_to_max: bool = False,
                 pad_policy: Optional[str] = None,
                 autotune_horizon: int = 256,
                 pad_auto_threshold: float = 0.25,
                 result_capacity: Optional[int] = 4096,
                 mesh_info=None, metrics=None, tracer=None):
        """``pad_to_max``: always pad dispatches to ``max_batch`` — exactly
        one compiled batch shape per (task, format), the steady-state service
        configuration. Default pow2 bucketing compiles more shapes but wastes
        less compute on ragged tails.

        ``pad_policy`` supersedes the boolean: ``"pow2"`` / ``"max"`` force a
        strategy; ``"auto"`` warms up on pad-to-max (so the ledger's
        ``padded_windows`` measures the TRUE single-shape padding waste —
        pow2 bucketing would hide it, every ragged dispatch landing in a
        snug bucket) and, once ``autotune_horizon`` windows are on the
        ledger, stays there iff the observed padding ratio
        padded/(windows+padded) is ≤ ``pad_auto_threshold``; ragged traffic
        falls back to pow2 bucketing.  The decision survives ``reset()`` so
        a benchmark can learn during warmup and measure the tuned steady
        state.

        ``result_capacity`` bounds the memory-resident ``results`` backlog:
        an undrained engine drops its OLDEST results past the cap (counted
        in ``dropped_results``, with a rate-limited warning) instead of
        growing forever.  ``None`` restores the unbounded legacy behavior.

        ``metrics`` is the engine's observability registry (a
        ``repro.obs.MetricsRegistry``; ``None`` creates a private one, and
        ``repro.obs.NULL_METRICS`` disables the plane at ~zero cost).  The
        session/supervisor/server layers share it.  ``tracer`` (a
        ``repro.obs.Tracer``, default off) records each dispatch split
        into stage, device, tracker (with each batched threshold launch)
        and account — both are host-side only and never enter jit.

        ``mesh_info`` (a ``repro.distributed.MeshInfo``, e.g. from
        ``launch.mesh.make_fleet_mesh_info``) shards every dispatch over the
        mesh's data axis via shard_map: the batch is padded to a multiple of
        the data-parallel size, each device runs the identical per-row graph
        on its slab, and the per-device ledger row is reduced through
        ``distributed.collectives.ledger_psum``.  Outputs are bit-identical
        to the single-device path (``tests/test_sharded_fleet.py`` pins
        this).  A 1-device mesh (or ``None``) takes the plain path.
        """
        self.pipelines = dict(pipelines)
        self.router = router or PrecisionRouter()
        self.max_batch = int(max_batch)
        self.pad_to_max = bool(pad_to_max)
        if pad_policy is None:
            pad_policy = "max" if pad_to_max else "pow2"
        if pad_policy not in ("pow2", "max", "auto"):
            raise ValueError(f"pad_policy {pad_policy!r} not in "
                             f"('pow2', 'max', 'auto')")
        self.pad_policy = pad_policy
        self.autotune_horizon = int(autotune_horizon)
        self.pad_auto_threshold = float(pad_auto_threshold)
        self._pad_decision: Optional[bool] = None  # auto: None until decided
        self.mesh_info = mesh_info
        self.dp_size = int(mesh_info.dp_size) if mesh_info is not None else 1
        self.result_capacity = (None if result_capacity is None
                                else int(result_capacity))
        self.dropped_results = 0
        self._drop_warn_at = 1
        self.ledger = EnergyLedger()
        self.metrics = MetricsRegistry() if metrics is None else metrics
        self.tracer = tracer
        bind_stream_engine(self.metrics, self)
        self._jit_programs = self.metrics.counter(
            "jit_programs_total", "compiled programs by site")
        self._jit_hits = self.metrics.counter(
            "jit_cache_hits_total", "compiled-program cache hits by site")
        self._fusion_changes = self.metrics.counter(
            "jit_fusion_key_changes_total",
            "fusion_cache_key() flips observed between dispatches — "
            "each flip retraces every live (task, fmt, shape) program")
        self._last_fusion_key = None
        self._thr_launches = self.metrics.counter(
            "tracker_threshold_launches_total",
            "batched 2-means threshold launches of the trackers")
        self._thr_rows = self.metrics.counter(
            "tracker_threshold_rows_total",
            "windows whose 2-means threshold those launches computed")
        self.results: Deque[WindowResult] = collections.deque()
        self._evicted: Set[Tuple[str, str]] = set()
        self._dispatchers: Dict[Tuple[str, str], WindowDispatcher] = {}
        # pending windows grouped per (patient, task) in arrival order;
        # routed per GROUP at pump time (not per window), so a re-pinned
        # patient picks up the new format on the next pump
        self._pending: Dict[Tuple[str, str], List[Window]] = {}
        self._pending_counts: Dict[Tuple[str, str], int] = {}
        self._fns: Dict[Tuple, object] = {}
        # per-(patient, task) stateful trackers (pipelines with make_tracker)
        self._trackers: Dict[Tuple[str, str], object] = {}

    # -- ingest ---------------------------------------------------------------
    def register_patient(self, patient: str, task: str,
                         fmt: Optional[str] = None) -> None:
        key = (patient, task)
        if key in self._evicted:
            raise KeyError(f"{patient!r}'s {task!r} stream was closed "
                           f"(BYE or stall eviction); reset() starts fresh")
        if key in self._dispatchers:
            raise KeyError(f"{patient!r} already registered for {task!r}")
        self._dispatchers[key] = WindowDispatcher(
            patient, self.pipelines[task].spec)
        if fmt is not None:
            self.router.pin(patient, fmt)

    def _group_key(self, patient: str, task: str) -> Tuple[str, str]:
        try:
            return (task, self.router.route(patient, task).fmt)
        except Exception:
            return (task, "?")  # unroutable: error surfaces at pump()

    def ingest(self, patient: str, task: str, modality: str,
               chunk: np.ndarray) -> None:
        """Feed one in-order chunk; dispatches automatically once a full
        batch of windows is ready somewhere in the fleet."""
        key = (patient, task)
        if key not in self._dispatchers:
            self.register_patient(patient, task)
        for w in self._dispatchers[key].push(modality, chunk):
            self._pending.setdefault(key, []).append(w)
            # auto-pump only when ONE (task, fmt) group can fill a batch —
            # O(1) count maintenance per emitted window
            gkey = self._group_key(patient, task)
            cnt = self._pending_counts.get(gkey, 0) + 1
            self._pending_counts[gkey] = cnt
            if cnt >= self.max_batch:
                self.pump(include_partial=False)

    # -- dispatch -------------------------------------------------------------
    def pump(self, include_partial: bool = True) -> int:
        """Dispatch pending windows now; returns the number processed.

        ``include_partial=False`` (the auto-pump mode) only dispatches groups
        that fill a whole ``max_batch`` — ragged remainders stay pending for
        a later pump/drain instead of burning a padded batch per trickle.
        A failing dispatch leaves every unprocessed window pending before
        the exception propagates: one bad route never drops healthy streams.
        """
        # route once per (patient, task) group — not once per window
        groups: Dict[Tuple[str, str], List[Tuple[str, str]]] = {}
        first_err: Optional[BaseException] = None
        for (patient, task), ws in self._pending.items():
            if not ws:
                continue
            try:
                fmt = self.router.route(patient, task).fmt
            except Exception as e:          # stays pending, surfaces below
                first_err = first_err or e
                continue
            groups.setdefault((task, fmt), []).append((patient, task))
        n = 0
        for (task, fmt), members in groups.items():
            total = sum(len(self._pending[k]) for k in members)
            try:
                while total >= self.max_batch or (include_partial
                                                  and total > 0):
                    batch: List[Window] = []
                    take: List[Tuple[Tuple[str, str], int]] = []
                    for k in members:
                        if len(batch) == self.max_batch:
                            break
                        ws = self._pending[k]
                        t = min(len(ws), self.max_batch - len(batch))
                        if t:
                            batch.extend(ws[:t])
                            take.append((k, t))
                    self._dispatch(task, fmt, batch)
                    for k, t in take:       # consume only after success
                        del self._pending[k][:t]
                    total -= len(batch)
                    n += len(batch)
            except Exception as e:
                first_err = first_err or e
        self._recount_pending()
        if first_err is not None:
            raise first_err
        return n

    def _recount_pending(self) -> None:
        """Rebuild the auto-pump trigger counts: one route per non-empty
        (patient, task) group, independent of backlog depth."""
        self._pending = {k: ws for k, ws in self._pending.items() if ws}
        self._pending_counts = {}
        for (patient, task), ws in self._pending.items():
            gkey = self._group_key(patient, task)
            self._pending_counts[gkey] = \
                self._pending_counts.get(gkey, 0) + len(ws)

    def drain(self) -> int:
        """End-of-stream flush: dispatch everything still pending."""
        return self.pump(include_partial=True)

    def pending_windows(self) -> int:
        """Ready-but-undispatched window count across the fleet — the
        transport layer's backpressure signal."""
        return sum(len(ws) for ws in self._pending.values())

    def _effective_pad_to_max(self) -> bool:
        if self.pad_policy == "max":
            return True
        if self.pad_policy == "pow2":
            return False
        # auto: warm up on pad-to-max so padded_windows measures the true
        # single-shape waste, then consult the ledger once
        if self._pad_decision is None:
            tot_w = sum(g.windows for g in self.ledger.stats.values())
            if tot_w < self.autotune_horizon:
                return True
            tot_p = sum(g.padded_windows
                        for g in self.ledger.stats.values())
            self._pad_decision = (
                tot_p / (tot_w + tot_p) <= self.pad_auto_threshold)
        return self._pad_decision

    def pad_strategy(self) -> str:
        """The strategy dispatches use right now: "pow2" or "max" (an
        undecided "auto" engine reports its warmup strategy, "max")."""
        return "max" if self._effective_pad_to_max() else "pow2"

    def _fn(self, task: str, fmt: str):
        # keyed on the live fusion_cache_key so a backend/quire toggle
        # mid-flight builds a fresh program instead of serving the stale
        # one — and so the jit probes see every retrace storm it causes
        fkey = fusion_cache_key()
        if self._last_fusion_key is None:
            self._last_fusion_key = fkey
        elif fkey != self._last_fusion_key:
            self._fusion_changes.inc(site="stream")
            self._last_fusion_key = fkey
        key = (task, fmt, fkey)
        fn = self._fns.get(key)
        if fn is None:
            fn = self._fns[key] = self.pipelines[task].make_fn(fmt)
            self._jit_programs.inc(site="stream", task=task, fmt=fmt)
        else:
            self._jit_hits.inc(site="stream", task=task, fmt=fmt)
        return fn

    def _sharded_fn(self, task: str, fmt: str):
        """shard_map wrapper over the mesh's data axis (cached per
        (pipeline fn, mesh) — engines sharing both share the program)."""
        from repro.distributed.sharding import make_fleet_batch_fn
        return make_fleet_batch_fn(self._fn(task, fmt), self.mesh_info)

    def _dispatch(self, task: str, fmt: str, windows: List[Window]) -> None:
        tr = self.tracer
        trk_id = None
        if tr is not None:
            t_in, sid, trk_id = tr.now(), tr.new_id(), tr.new_id()
        pipe = self.pipelines[task]
        B = len(windows)
        Bpad = self.max_batch if self._effective_pad_to_max() \
            else bucket_size(B, self.max_batch)
        if self.dp_size > 1:
            # every device gets an equal slab; the extra rows are ordinary
            # padding (zeros), indistinguishable from bucket padding
            from repro.distributed.sharding import fleet_pad
            Bpad = fleet_pad(Bpad, self.dp_size)
        # fresh per-dispatch buffers: safe to donate to the jit call, so
        # XLA may reuse their pages for outputs instead of allocating
        arrays: Dict[str, np.ndarray] = {}
        for m in pipe.spec.modalities:
            stack = np.zeros((Bpad, m.channels, pipe.spec.window_samples(m)),
                             np.float32)
            for i, w in enumerate(windows):
                stack[i] = w.arrays[m.name]
            arrays[m.name] = stack
        t0 = time.perf_counter()
        if self.dp_size > 1:
            mask = np.zeros((Bpad,), np.int32)
            mask[:B] = 1
            outs, ledger_row = self._sharded_fn(task, fmt)(arrays, mask)
        else:
            outs = self._fn(task, fmt)(arrays)
            ledger_row = None
        # one device→host materialization per batch; WindowResult rows are
        # zero-copy views into these arrays
        outs = {k: np.asarray(jax.block_until_ready(v))
                for k, v in outs.items()}
        t_dev = time.perf_counter()
        dt = t_dev - t0
        if ledger_row is None:
            n_real, n_padded = B, Bpad - B
        else:
            # the psum-reduced device-local counts ARE the ledger's row; a
            # mismatch with the host view means the sharding dropped rows
            n_real, n_padded = (int(v) for v in np.asarray(ledger_row))
            if n_real != B:
                raise RuntimeError(
                    f"sharded dispatch accounted {n_real} real windows, "
                    f"host staged {B} (task={task!r}, fmt={fmt!r})")
        rows = [{k: v[i] for k, v in outs.items()}
                for i in range(len(windows))]
        n_esc, esc_nj = self._track(pipe, task, fmt, windows, rows,
                                    span=trk_id)
        t_trk = tr.now() if tr is not None else 0.0
        self.ledger.record(task, fmt, n_real, n_padded, dt,
                           pipe.ops_per_window,
                           n_escalated=n_esc, escalation_extra_nj=esc_nj)
        # after the trackers and the ledger: the results of one dispatch
        # share this stamp
        done = time.perf_counter()
        for w, row in zip(windows, rows):
            self._append_result(WindowResult(
                w.patient, task, w.widx, fmt, w.t0_s, row,
                ready_wall=w.ready_wall, done_wall=done))
        if tr is not None:
            # the children tile the parent: stage, device, tracker, account
            t_end = tr.now()
            tr.complete("dispatch", "stage", t_in, t0, track="dispatch",
                        parent=sid)
            tr.complete("dispatch", "device", t0, t_dev, track="dispatch",
                        parent=sid)
            tr.complete("dispatch", "tracker", t_dev, t_trk,
                        track="dispatch", sid=trk_id, parent=sid)
            tr.complete("dispatch", "account", t_trk, t_end,
                        track="dispatch", parent=sid)
            tr.complete("dispatch", f"{task}/{fmt}", t_in, t_end,
                        track="dispatch", sid=sid,
                        args={"task": task, "fmt": fmt, "B": B,
                              "Bpad": Bpad})

    def _append_result(self, r: WindowResult) -> None:
        """Retain one result, dropping the oldest past ``result_capacity``
        (counted + rate-limited warning): an undrained engine stays bounded."""
        self.dropped_results, self._drop_warn_at = bounded_admit(
            self.results, r, self.result_capacity, self.dropped_results,
            self._drop_warn_at,
            f"engine results backlog full (result_capacity="
            f"{self.result_capacity}); drain with pop_results() or run a "
            f"repro.ingest.Supervisor",
            on_drop=lambda v: self.metrics.counter(
                "engine_results_dropped_total",
                "WindowResults evicted from the engine backlog"
            ).inc(patient=v.patient))

    def _track(self, pipe: Pipeline, task: str, fmt: str,
               windows: List[Window], rows: List[Dict[str, np.ndarray]],
               span: Optional[int] = None) -> Tuple[int, float]:
        """Run the per-patient stateful trackers over a dispatched batch.

        First every window's 2-means threshold, the whole batch in one
        launch (``tracker.stage_thresholds``); then windows hit each
        tracker in ``widx`` order (the pending groups are FIFO per
        patient), the tracker's confirmed peaks land on the window's
        outputs, and its quality signal feeds the router's escalation policy
        — affecting how the patient's NEXT windows are routed.  Windows that
        ran above the patient's static format are billed to the escalation
        column, per patient and per group.  With a tracer, each threshold
        launch is a ``dispatch/tracker.threshold`` span, a child of
        ``span``, with its rows and deepest chain in ``args``.
        """
        if pipe.make_tracker is None:
            return 0, 0.0
        n_esc, esc_nj = 0, 0.0
        # fmt and ops are batch constants; base formats and the escalation
        # energy delta are memoized so the per-window loop stays cheap
        base_fmts: Dict[str, str] = {}
        extra_by_base: Dict[str, float] = {}
        tracer = self.tracer
        trackers = []
        for w in windows:
            key = (w.patient, task)
            tr = self._trackers.get(key)
            if tr is None:
                tr = self._trackers[key] = pipe.make_tracker(w.patient)
            trackers.append(tr)

        def launched(t0: float, t1: float, n_rows: int, depth: int) -> None:
            self._thr_launches.inc()
            self._thr_rows.inc(n_rows)
            if tracer is not None:
                tracer.complete("dispatch", "tracker.threshold", t0, t1,
                                track="dispatch", parent=span,
                                args={"rows": n_rows, "depth": depth})

        stage_thresholds([(tr, w.widx, row) for tr, w, row
                          in zip(trackers, windows, rows)], fmt, launched)
        for tr, w, row in zip(trackers, windows, rows):
            upd = tr.update(w.widx, row, fmt)
            row["peaks"] = upd.new_peaks
            base_fmt = base_fmts.get(w.patient)
            if base_fmt is None:
                base_fmt = base_fmts[w.patient] = \
                    self.router.base_route(w.patient, task).fmt
            if fmt != base_fmt:
                extra = extra_by_base.get(base_fmt)
                if extra is None:
                    extra = extra_by_base[base_fmt] = (
                        window_energy_nj(pipe.ops_per_window, fmt)
                        - window_energy_nj(pipe.ops_per_window, base_fmt))
                n_esc += 1
                esc_nj += extra
                self.ledger.record_escalation(w.patient, extra)
            self.router.observe(w.patient, task, upd.boundary_gap,
                                upd.mid_refractory)
        return n_esc, esc_nj

    # -- stateful trackers ----------------------------------------------------
    def tracker_for(self, patient: str, task: str):
        """The per-patient tracker (None until its first window dispatches)."""
        return self._trackers.get((patient, task))

    def finalize_patient(self, patient: str, task: str) -> np.ndarray:
        """End-of-stream flush for one tracked stream: commits the tracker's
        deferred stitching margin.  Returns the tail peaks; the tracker's
        ``peaks`` then holds the complete stream."""
        tr = self._trackers.get((patient, task))
        if tr is None:
            return np.zeros(0, np.int64)
        return tr.finalize(self.router.route(patient, task).fmt)

    def finalize_all(self) -> Dict[Tuple[str, str], np.ndarray]:
        """Flush every tracked stream; {(patient, task): tail peaks}."""
        return {key: self.finalize_patient(*key)
                for key in sorted(self._trackers)}

    # -- stream close / stall eviction ----------------------------------------
    def release_patient(self, patient: str, task: str) -> Tuple[int, int]:
        """Free a closed stream's dispatcher — ring buffers, partially
        staged slices, window-grid state — and refuse further ingest for
        it.  The tracker (the stream's peak history) and any undrained
        results are kept.  Returns the (slices, bytes) freed.  The session
        layer calls this after a clean BYE so a churning fleet doesn't
        accumulate one dispatcher per patient ever seen."""
        key = (patient, task)
        self._evicted.add(key)
        disp = self._dispatchers.pop(key, None)
        return disp.staged_cost() if disp is not None else (0, 0)

    def evict_patient(self, patient: str, task: str) -> Dict[str, int]:
        """Close one stream — clean BYE or stall eviction: dispatch its
        complete pending windows (so the delivered prefix is fully scored),
        finalize its tracker, and free its dispatcher — rings, partially
        staged slices, sequencing state.  Further ingest for the stream
        raises.  Returns what was flushed/dropped/freed, for the ledger's
        transport column.

        This path must never raise (a close that wedges the session layer
        is worse than a lossy close): a failing dispatch drops the stream's
        remaining windows and counts them, batches dispatched before the
        failure still count as flushed, and a finalize failure is swallowed
        after the state is freed.

        The delivered-prefix guarantee: after eviction the tracker's
        ``peaks`` equal the offline detector's output on exactly the window
        prefix that fully arrived (``tests/test_ingest.py`` pins this).
        """
        key = (patient, task)
        flushed = dropped = 0
        ws = self._pending.pop(key, [])
        if ws:
            try:
                fmt = self.router.route(patient, task).fmt
                while ws:
                    batch = ws[: self.max_batch]
                    self._dispatch(task, fmt, batch)
                    del ws[: len(batch)]
                    flushed += len(batch)
            except Exception:
                dropped = len(ws)   # the un-dispatched remainder is lost
            self._recount_pending()
        staged_slices, staged_bytes = self.release_patient(patient, task)
        if key in self._trackers:
            try:
                self.finalize_patient(patient, task)
            except Exception:
                pass    # unroutable tracker flush: state is already freed
        return {"windows_flushed": flushed, "windows_dropped": dropped,
                "staged_slices": staged_slices,
                "staged_bytes": staged_bytes}

    def reset(self) -> None:
        """Fresh streams and metrics; compiled (task, format) functions are
        kept so a benchmark can warm up, reset, then measure steady state —
        and so is an ``"auto"`` pad-policy decision learned during warmup."""
        self._dispatchers.clear()
        self._pending.clear()
        self._pending_counts.clear()
        self._trackers.clear()
        self._evicted.clear()
        self.results = collections.deque()
        self.dropped_results = 0
        self._drop_warn_at = 1
        self.ledger = EnergyLedger()
        # metric VALUES reset with the ledger (registrations + collectors
        # survive, like the compiled fns); warmup counts never leak into a
        # measured pass
        self.metrics.reset()
        self._last_fusion_key = None

    # -- reporting ------------------------------------------------------------
    def fleet_summary(self) -> Dict[str, Dict[str, float]]:
        return self.ledger.summary()

    def results_for(self, patient: str, task: str) -> List[WindowResult]:
        out = [r for r in self.results
               if r.patient == patient and r.task == task]
        return sorted(out, key=lambda r: r.widx)

    def pop_results(self, max_n: Optional[int] = None) -> List[WindowResult]:
        """Consume up to ``max_n`` results (all, when None) in FIFO order —
        the supervisor's non-blocking drain.  The backlog itself is bounded
        by ``result_capacity`` (drop-oldest), so even an undrained engine's
        memory stays flat; drops are counted in ``dropped_results``."""
        if max_n is None:
            out = list(self.results)
            self.results.clear()
            return out
        n = min(int(max_n), len(self.results))
        return [self.results.popleft() for _ in range(n)]
