"""ssd_device_share.fused: device self time of ops under scope ssd (a Mamba-2 layer's projection, convolution, scan, gate and norm, and output projection; rollout and update, forward, rematerialized forward and backward) over the device time of the step runs that lie whole inside the trace (benchmark/lib/whole_runs.py), mean over chips. None on a program with no such scope."""

from benchmark.lib import whole_runs


def read(ctx):
    return whole_runs.share_where(ctx, r"\bssd\b")
