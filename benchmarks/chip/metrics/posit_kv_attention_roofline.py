"""Roofline share of the posit-KV decode attention kernel: its device time
in the trace against the operations and bytes its calls had to do
(``kernels/posit_kv_attention.py``): each decode step calls it once per
layer over the rows that were decoding."""
import harness
from readers import kernel_roofline_pct, steps_in_window

kv = harness.load_module(harness.kernel_path("posit_kv_attention"),
                         "chipbench_kernel")


def read(ctx):
    cfg = ctx["config"]
    bits = int(cfg["kv_cache"].removeprefix("posit"))
    ops = nbytes = 0.0
    for _, ctxs, _ in steps_in_window(ctx):
        o, b = kv.cost(ctxs, cfg["num_attention_heads"],
                       cfg["num_key_value_heads"], cfg["head_dim"], bits)
        ops += o * cfg["num_hidden_layers"]
        nbytes += b * cfg["num_hidden_layers"]
    if not nbytes:
        return None
    return kernel_roofline_pct(ctx, kv.NAMES, (ops, nbytes))
