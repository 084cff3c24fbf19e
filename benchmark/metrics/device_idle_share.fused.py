"""device_idle_share.fused: 1 - union of device-op intervals over the traced window, mean over chips."""

from benchmark.lib import readers


def read(ctx):
    return readers.idle_share(ctx)
