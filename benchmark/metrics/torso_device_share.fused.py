"""torso_device_share.fused: the same for ops under learner_update whose scope path holds convnet (forward, remat, backward, and the pad/copy ops that inherit its name)."""

from benchmark.lib import scopes


def read(ctx):
    return scopes.share(ctx, "update.torso")
