"""lstm_bwd_roofline.fused: LSTM backward kernel events over the benchmark's least time."""

from benchmark.lib import readers


def read(ctx):
    return readers.roofline_share(ctx, "lstm_bwd")
