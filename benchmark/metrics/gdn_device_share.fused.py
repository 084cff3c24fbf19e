"""gdn_device_share.fused: device self time of ops under scope gdn (a Gated-DeltaNet layer's projection, convolution, delta-rule scan, norm and gate, and output projection; rollout and update, forward, rematerialized forward and backward) over the device time of the step runs that lie whole inside the trace (benchmark/lib/whole_runs.py), mean over chips. None on a program with no such scope."""

from benchmark.lib import whole_runs


def read(ctx):
    return whole_runs.share_where(ctx, r"\bgdn\b")
