"""A window's latency as its patient sees it: from the moment the frame
carrying the window's last sample was due to the moment the supervisor
drained the window's result; 99th percentile over every window due in the
measured window, a window never answered counting as infinite."""
import math

import harness


def read(ctx):
    due = ctx.get("due")
    if not due:
        return None
    lat = [1e3 * (drained - d) if math.isfinite(drained) else math.inf
           for d, _, _, drained in due]
    return harness.percentile(lat, 99)
