"""update_device_ms.host: device time of whole runs of the update program in the traced window, per run."""

from benchmark.lib import readers


def read(ctx):
    return readers.step_device_ms(ctx)
