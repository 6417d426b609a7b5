"""Stream-engine wait: from the window's emission (``ready_wall``) to the
moment its batch's outputs reached the host (``done_wall``), 99th
percentile over the windows due in the measured window."""
from readers import p99_ms


def read(ctx):
    return p99_ms((ready, done) for _, ready, done, _ in ctx.get("due", []))
