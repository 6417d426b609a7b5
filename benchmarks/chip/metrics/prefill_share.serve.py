"""Share of the measured window the serving engine spent in prefill: the
program's ``serve/prefill`` spans (B=1 prefill, cache install, first
token) over the window."""
from readers import span_durations


def read(ctx):
    d = span_durations(ctx, "serve", "prefill")
    return 100.0 * sum(d) / (ctx["t_close"] - ctx["t_open"]) if d else None
