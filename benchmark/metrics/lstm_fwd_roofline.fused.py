"""lstm_fwd_roofline.fused: LSTM forward (residual-producing) kernel events over the benchmark's least time."""

from benchmark.lib import readers


def read(ctx):
    return readers.roofline_share(ctx, "lstm_fwd")
