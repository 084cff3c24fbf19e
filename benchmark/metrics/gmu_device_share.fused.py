"""gmu_device_share.fused: device self time of ops under scope gmu (a memory unit's two projections and its gate by the state-space layer's memory; rollout and update, forward, rematerialized forward and backward) over the device time of the step runs that lie whole inside the trace (benchmark/lib/whole_runs.py), mean over chips. None on a program with no such scope."""

from benchmark.lib import whole_runs


def read(ctx):
    return whole_runs.share_where(ctx, r"\bgmu\b")
