"""telemetry_device_share.fused: the same for ops under scope telemetry: what the step computes that only the obs plane reads."""

from benchmark.lib import scopes


def read(ctx):
    return scopes.share(ctx, "telemetry")
