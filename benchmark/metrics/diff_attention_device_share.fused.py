"""diff_attention_device_share.fused: device self time of ops under scope attention of a differential-attention policy (projections, the two score streams through the cache under window, full and cross, the lambda combination, the pair's norm, output projection; rollout and update, forward, rematerialized forward and backward) over the device time of the step runs that lie whole inside the trace (benchmark/lib/whole_runs.py), mean over chips. None on a program with no such scope."""

from benchmark.lib import whole_runs


def read(ctx):
    return whole_runs.share_where(ctx, r"\battention\b")
