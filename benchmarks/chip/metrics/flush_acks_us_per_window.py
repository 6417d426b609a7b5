"""Host time of the ingest server's ACK/credit walk per window: the
program's ``frame/flush_acks`` spans (one after every socket read that
delivered frames; it visits every session) inside the measured window,
over the real windows of the dispatches that finished in it, in us."""
import harness

_span = harness.load_module(harness.metric_path("tracker_us_per_window"),
                            "chipbench_metric")


def read(ctx):
    return _span.us_per_window(ctx, "frame/flush_acks")
