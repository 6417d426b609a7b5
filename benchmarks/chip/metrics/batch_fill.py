"""Share of the dispatched batch rows that held a real window: real rows
over real and padded rows of the dispatches that finished in the measured
window."""
from readers import batch_fill_pct as read  # noqa: F401
