"""moe_device_share.fused: device self time of ops under scope moe (router, dispatch, the held experts' grouped products, the shared expert, combine; rollout and update, forward, rematerialized forward and backward) over the device time of the step runs that lie whole inside the trace (benchmark/lib/whole_runs.py), mean over chips."""

from benchmark.lib import whole_runs


def read(ctx):
    return whole_runs.share_where(ctx, r"\bmoe\b")
