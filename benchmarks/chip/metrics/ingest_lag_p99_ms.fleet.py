"""Ingest lag: from the moment a window's last frame was due to the moment
the server's session layer emitted the window (``Window.ready_wall``),
99th percentile over the windows due in the measured window."""
from readers import p99_ms


def read(ctx):
    return p99_ms((due, ready) for due, ready, _, _ in ctx.get("due", []))
