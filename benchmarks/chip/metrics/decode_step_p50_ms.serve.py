"""Median batched decode step: the program's ``serve/decode`` spans in the
measured window (device step, sampling and the copy of the tokens)."""
import statistics

from readers import span_durations


def read(ctx):
    d = span_durations(ctx, "serve", "decode")
    return 1e3 * statistics.median(d) if d else None
