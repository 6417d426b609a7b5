"""Host time of the per-patient trackers per window: the program's
``dispatch/tracker`` spans (each window's 2-means round trip, peak
stitching, the router's feedback) inside the measured window, over the
real windows of the dispatches that finished in it, in us.

``us_per_window`` is shared with the other fleet readers of a host span
per window."""


def us_per_window(ctx, name):
    """Sum of the ``name`` host spans (``cat/name``), clipped to the window,
    over the window's dispatched real windows, in us.  None where nothing
    was dispatched, where the run was not traced, or where the spans of
    the span's category are there but this one is not (a program that
    does not record it)."""
    spans = ctx.get("host_spans") or []
    windows = (ctx.get("ledger") or {}).get("windows", 0)
    if not windows or (not spans and ctx.get("trace") is None):
        return None
    mine = [(s, e) for n, s, e in spans if n == name]
    if not mine:
        cat = name.split("/", 1)[0] + "/"
        return None if any(n.startswith(cat) for n, _, _ in spans) else 0.0
    t0, t1 = ctx["t_open"], ctx["t_close"]
    busy = sum(max(0.0, min(e, t1) - max(s, t0)) for s, e in mine)
    return 1e6 * busy / windows


def read(ctx):
    return us_per_window(ctx, "dispatch/tracker")
