"""Median host time of one lane's per-row accounting after its decode:
the program's ``serve/account`` spans in the measured window (energy and
KV bytes per row, the scheduler's token and retire, the logits kept), in
ms."""
import statistics

from readers import span_durations


def read(ctx):
    d = span_durations(ctx, "serve", "account")
    return 1e3 * statistics.median(d) if d else None
