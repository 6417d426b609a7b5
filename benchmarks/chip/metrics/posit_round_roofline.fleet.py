"""Roofline share of the posit rounding kernel in the fleet: every call in
the trace, its bytes and operations from the shape the trace gives it
(``kernels/posit_round.py``), over the calls' device time."""
import harness
from readers import peaks

pr = harness.load_module(harness.kernel_path("posit_round"),
                         "chipbench_kernel")


def read(ctx):
    from trace_reduce import is_kernel, roofline_share
    tr = ctx.get("trace")
    if tr is None:
        return None
    ops = nbytes = secs = 0.0
    for dev in tr.ops:
        for name, a, b in tr._clipped(dev):
            if any(is_kernel(name, k) for k in pr.NAMES):
                o, by = pr.cost(pr.elements_of(name))
                ops, nbytes, secs = ops + o, nbytes + by, secs + (b - a)
    if secs <= 0 or nbytes <= 0:
        return None
    return roofline_share(ops, nbytes, secs, peaks(ctx))[0]
