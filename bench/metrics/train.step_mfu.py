"""Model FLOP/s utilisation of the whole training step: the model
operations of every row trained in the window (2·k·C forward plus
2·k·C for dW, over the one-hot expansion), over window × chips × the
chip's bf16 peak."""
from bench import counts
from bench.peaks import peaks


def read(ctx):
    c = ctx["counters"]
    if c["rows"] <= 0:
        return None
    flops = c["rows"] * counts.model_flops_per_row(c["k"], c["classes_out"])
    peak = peaks(ctx["device_kind"])["bf16_flops"]
    return 100.0 * flops / (c["window_s"] * ctx["chips"] * peak)
