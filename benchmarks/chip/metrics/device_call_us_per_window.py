"""Host time of the device call per window: the program's
``dispatch/device`` spans (the jit call, the wait for the device and the
copy of the outputs to the host) inside the measured window, over the real
windows of the dispatches that finished in it, in us."""
import harness

_span = harness.load_module(harness.metric_path("tracker_us_per_window"),
                            "chipbench_metric")


def read(ctx):
    return _span.us_per_window(ctx, "dispatch/device")
