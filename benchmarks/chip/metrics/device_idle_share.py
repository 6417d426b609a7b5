"""Share of the measured window in which no operation ran on the device:
1 - (union of the device's op intervals) / window, from the profiler."""
from readers import idle_pct as read  # noqa: F401
