"""The packed logits kernels' (forward and dW backward) share of their
roofline: the least time for the operations and bytes their algorithm
needs (bench/counts.py, real rows only), at the chip's bf16 peak and
HBM bandwidth, over their device time in the trace.  Per chip: rows and
steps split over the chips, kernel time averaged over them."""
from bench import counts
from bench.peaks import peaks

KERNELS = r"bbit_linear_packed_(fwd|bwd_dw)_pallas"


def read(ctx):
    t, c = ctx["trace"], ctx["counters"]
    kernel_s = t.op_seconds(KERNELS)
    if kernel_s <= 0:
        return None
    p = peaks(ctx["device_kind"])
    ops, nbytes = counts.logits_train(c["rows"] // ctx["chips"], c["steps"],
                                      c["k"], c["b"], c["classes_out"])
    floor = counts.roofline_seconds(ops, nbytes, p["bf16_flops"],
                                    p["hbm_bytes_per_s"])
    return 100.0 * floor / kernel_s
