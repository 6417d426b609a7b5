"""Windows per 2-means threshold launch of the R-peak trackers: the real
windows of the dispatches that finished in the measured window, over the
``dispatch/tracker.threshold`` spans that start in it.  A program that
makes one threshold round trip per window reads about 1; one launch per
full dispatch of 64 windows reads about 64."""
import math

SPAN = "dispatch/tracker.threshold"


def read(ctx):
    """None where nothing was dispatched, where the run was not traced, or
    where the program's dispatch spans are there without threshold spans
    (a pipeline with no tracker); infinite where windows were dispatched
    and the program recorded no span at all."""
    spans = ctx.get("host_spans") or []
    windows = (ctx.get("ledger") or {}).get("windows", 0)
    if not windows or (not spans and ctx.get("trace") is None):
        return None
    if not spans:
        return math.inf
    t0, t1 = ctx["t_open"], ctx["t_close"]
    launches = sum(1 for n, s, _ in spans if n == SPAN and t0 <= s < t1)
    if launches:
        return windows / launches
    if any(n.startswith("dispatch/") for n, _, _ in spans):
        return None
    return math.inf
