"""rollout_device_share.fused: device self time of ops under scope rollout over the device time of whole step runs, mean over chips."""

from benchmark.lib import scopes


def read(ctx):
    return scopes.share(ctx, "rollout")
