"""whole_step_rollout_share.fused: device self time of ops under scope rollout (the acting scan: one token through every layer and the cache, the world's step) over the device time of the step runs that lie whole inside the trace (benchmark/lib/whole_runs.py), mean over chips."""

from benchmark.lib import whole_runs


def read(ctx):
    return whole_runs.share(ctx, "rollout")
