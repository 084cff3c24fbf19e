"""fused_step_device_ms: device time of whole runs of the fused step program in the traced window, per run."""

from benchmark.lib import readers


def read(ctx):
    return readers.step_device_ms(ctx)
