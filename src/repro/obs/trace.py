"""Ring-buffered trace spans with a Chrome trace-event exporter.

The tracer is a host-side, monotonic-clock (``time.perf_counter``, the
same clock that stamps ``Window.ready_wall``/``done_wall``) event log.
It never runs inside jit: callers stamp timestamps around dispatches and
record completed spans after the fact, so a disabled tracer is simply
``None`` and the hot path pays one attribute load + ``is None`` test —
every site reads the clock, takes an id or builds ``args`` only behind
that test.

Memory is bounded: events land in a ring of ``capacity`` entries and the
oldest are dropped (and counted in ``dropped``) when full — a soak can
run forever with a live tracer without growing.

Every event carries an integer id.  A span may name its parent's id —
taken with ``new_id()`` before the parent's work starts, so that its
children, which close first, can point at it — and a ``key`` shared by
the spans of one window (``"patient/widx"``) or one request (the rid).
``self_times`` subtracts from each span the part of it its children
cover.

Span taxonomy (``category/name``; children indented under their parent):

=========================== ===============================================
span                        covers
=========================== ===============================================
frame/decode                wire bytes → decoded frames (per socket read)
frame/flush_acks            the ACK/credit walk after a read that delivered
                            frames (``args["acks"]``: ACK frames sent)
reorder/held                out-of-order DATA held → released
dispatch/<task>/<fmt>       one engine dispatch, staging to appended results
  dispatch/stage            the padded numpy batch is built
  dispatch/device           jit call → outputs are numpy arrays on the host
  dispatch/tracker          the per-patient trackers over the batch
    dispatch/tracker.threshold  one batched 2-means launch, one host copy
                            (``args``: rows, depth of its deepest chain)
  dispatch/account          ledger record, results appended
drain/supervisor.poll       results popped by the supervisor
serve/step                  one ``ServingEngine.step`` (admitted, rows)
  serve/admit               one admission (key: rid)
    serve/prefill           B=1 prefill, cache install, first token
  serve/decode              one lane's batched decode, tokens on the host
  serve/account             that lane's per-row energy, KV bytes, tokens
serve/retire (instant)      a request finished
=========================== ===============================================

Export is Chrome trace-event JSON (the ``{"traceEvents": [...]}`` shape,
id, parent and key in each event's ``args``) so ``stream_bench --trace
out.json`` produces a file that opens directly in Perfetto /
``chrome://tracing``.
"""
from __future__ import annotations

import itertools
import json
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

__all__ = ["Tracer"]

# Chrome trace-event phases used here: "X" complete span, "i" instant.
_COMPLETE = "X"
_INSTANT = "i"


class Tracer:
    """Bounded in-memory span log. All times are perf_counter seconds.

    ``events()`` gives ``(ph, cat, name, start, end, track, args, id,
    parent, key)`` tuples; fields 0-6 keep their places for readers that
    unpack them by position."""

    def __init__(self, capacity: int = 1 << 16):
        if capacity <= 0:
            raise ValueError("capacity must be positive")
        self.capacity = int(capacity)
        self._events: deque = deque()
        self.dropped = 0
        self._t0 = time.perf_counter()
        self._ids = itertools.count(1)

    # -- recording ---------------------------------------------------------

    def now(self) -> float:
        return time.perf_counter()

    def new_id(self) -> int:
        """A fresh span id: a parent takes its id before its work starts
        and hands it to ``complete`` (``sid=``) once it ends."""
        return next(self._ids)

    def _push(self, ev: Tuple) -> None:
        if len(self._events) >= self.capacity:
            self._events.popleft()
            self.dropped += 1
        self._events.append(ev)

    def complete(
        self,
        cat: str,
        name: str,
        start_s: float,
        end_s: float,
        track: str = "main",
        args: Optional[Dict[str, Any]] = None,
        *,
        sid: Optional[int] = None,
        parent: Optional[int] = None,
        key: Any = None,
    ) -> int:
        """Record a completed span [start_s, end_s] (perf_counter seconds);
        returns its id (``sid``, or a fresh one)."""
        if sid is None:
            sid = next(self._ids)
        self._push((_COMPLETE, cat, name, start_s, max(end_s, start_s),
                    track, args, sid, parent, key))
        return sid

    def instant(
        self,
        cat: str,
        name: str,
        ts_s: Optional[float] = None,
        track: str = "main",
        args: Optional[Dict[str, Any]] = None,
        *,
        parent: Optional[int] = None,
        key: Any = None,
    ) -> int:
        if ts_s is None:
            ts_s = time.perf_counter()
        sid = next(self._ids)
        self._push((_INSTANT, cat, name, ts_s, ts_s, track, args, sid,
                    parent, key))
        return sid

    def reset(self) -> None:
        """Clear recorded events and re-zero the export epoch (a bench
        warmup pass must not leak spans into the measured trace)."""
        self._events.clear()
        self.dropped = 0
        self._t0 = time.perf_counter()

    # -- inspection --------------------------------------------------------

    def __len__(self) -> int:
        return len(self._events)

    def categories(self) -> set:
        return {ev[1] for ev in self._events}

    def events(self) -> List[Tuple]:
        return list(self._events)

    def self_times(self) -> Dict[int, float]:
        """Each complete span's self time, by id: its duration less the
        part of it that its children cover (children of one parent do not
        overlap: they run one after another on the host)."""
        spans = {ev[7]: ev for ev in self._events if ev[0] == _COMPLETE}
        out = {sid: ev[4] - ev[3] for sid, ev in spans.items()}
        for ev in spans.values():
            par = spans.get(ev[8])
            if par is not None:
                out[ev[8]] -= max(0.0, min(ev[4], par[4])
                                  - max(ev[3], par[3]))
        return out

    # -- export ------------------------------------------------------------

    def _ts_us(self, t: float) -> float:
        return max(0.0, (t - self._t0) * 1e6)

    def chrome_trace(self) -> Dict[str, Any]:
        """Render the ring as a Chrome trace-event document."""
        tracks: Dict[str, int] = {}
        events: List[Dict[str, Any]] = []
        for (ph, cat, name, start, end, track, args, sid, parent,
             key) in self._events:
            tid = tracks.setdefault(track, len(tracks))
            ev: Dict[str, Any] = {
                "name": name,
                "cat": cat,
                "ph": ph,
                "ts": self._ts_us(start),
                "pid": 0,
                "tid": tid,
            }
            if ph == _COMPLETE:
                ev["dur"] = max(0.0, (end - start) * 1e6)
            else:
                ev["s"] = "t"
            ev["args"] = dict(args or {}, id=sid)
            if parent is not None:
                ev["args"]["parent"] = parent
            if key is not None:
                ev["args"]["key"] = key
            events.append(ev)
        meta = [
            {
                "name": "thread_name",
                "ph": "M",
                "pid": 0,
                "tid": tid,
                "args": {"name": track},
            }
            for track, tid in sorted(tracks.items(), key=lambda kv: kv[1])
        ]
        return {
            "traceEvents": meta + events,
            "displayTimeUnit": "ms",
            "otherData": {"dropped_events": self.dropped},
        }

    def export(self, path: str) -> None:
        with open(path, "w") as fh:
            json.dump(self.chrome_trace(), fh)


def validate_chrome_trace(doc: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Check ``doc`` is a well-formed Chrome trace-event document.

    Returns the non-metadata events. Raises ``ValueError`` on malformed
    input — used by tests and by the CI trace-artifact smoke.
    """
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        raise ValueError("not a trace-event document: missing traceEvents")
    out = []
    for ev in doc["traceEvents"]:
        if not isinstance(ev, dict):
            raise ValueError("event is not an object")
        for key in ("ph", "pid", "tid"):
            if key not in ev:
                raise ValueError(f"event missing {key!r}")
        if ev["ph"] == "M":
            continue
        for key in ("name", "cat", "ts"):
            if key not in ev:
                raise ValueError(f"event missing {key!r}")
        if ev["ph"] == _COMPLETE and "dur" not in ev:
            raise ValueError("complete event missing dur")
        out.append(ev)
    return out
