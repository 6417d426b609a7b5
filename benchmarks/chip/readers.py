"""Reductions that several per-layer metrics share.  Each metric keeps a
file of its own under ``metrics/``; the arithmetic they have in common is
here.  A reader with nothing to read returns None."""
from __future__ import annotations

import math
from typing import Optional

import harness


def p99_ms(pairs) -> Optional[float]:
    """99th percentile, in ms, of ``b - a`` over the pairs whose ends are
    both known."""
    vals = [1e3 * (b - a) for a, b in pairs
            if math.isfinite(a) and math.isfinite(b)]
    return harness.percentile(vals, 99) if vals else None


def batch_fill_pct(ctx) -> Optional[float]:
    led = ctx.get("ledger") or {}
    tot = led.get("windows", 0) + led.get("padded", 0)
    return 100.0 * led["windows"] / tot if tot else None


def idle_pct(ctx) -> Optional[float]:
    tr = ctx.get("trace")
    if tr is None or not tr.ops:
        return None
    return 100.0 * tr.idle_share()


def span_durations(ctx, cat: str, name: str):
    """Durations of the program's ``cat/name`` spans that ended inside the
    measured window, with the window's part of each."""
    t0, t1 = ctx["t_open"], ctx["t_close"]
    return [min(e, t1) - max(s, t0) for ph, c, n, s, e, *_ in
            ctx.get("spans", []) if ph == "X" and c == cat and n == name
            and e > t0 and s < t1]


def steps_in_window(ctx):
    t0, t1 = ctx["t_open"], ctx["t_close"]
    return [s for s in ctx.get("steps", []) if t0 < s[0] <= t1]


def peaks(ctx):
    from trace_reduce import peaks_for
    return peaks_for(ctx["kind"])


def kernel_roofline_pct(ctx, names, ops_bytes) -> Optional[float]:
    """Roofline share of the kernel whose trace ops carry one of ``names``,
    with ``ops_bytes`` the (operations, bytes) it had to do in the window;
    None where the trace shows no such kernel."""
    from trace_reduce import roofline_share
    tr = ctx.get("trace")
    if tr is None:
        return None
    secs = sum(tr.kernel_time(n)[0] for n in names)
    if secs <= 0:
        return None
    ops, nbytes = ops_bytes
    return roofline_share(ops, nbytes, secs, peaks(ctx))[0]
