"""device_mfu.fused: model FLOPs of one fused step from shapes (acting forward, learning forward and backward; a rematerialized forward is not model work) over the device time of whole runs of the step in the trace, over chips x peak."""

from benchmark.lib import readers


def read(ctx):
    return readers.mfu(ctx)
