"""The whole serving step's share of the chip's bf16 peak: the model
operations of every token the window produced (prefill and decode, with
attention at each token's context, ``kernels/decoder_step.py``) over the
window's length times the peak in ``peaks.json``."""
import harness
from readers import peaks, steps_in_window

dec = harness.load_module(harness.kernel_path("decoder_step"),
                          "chipbench_kernel")


def read(ctx):
    steps = steps_in_window(ctx)
    if not steps:
        return None
    cfg = ctx["config"]
    ops = sum(dec.decode_ops(cfg, ctxs)
              + sum(dec.prefill_ops(cfg, p) for p in pre)
              for _, ctxs, pre in steps)
    window = ctx["t_close"] - ctx["t_open"]
    return 100.0 * ops / (window * peaks(ctx)["bf16_flops_per_s"])
