"""whole_step_mfu.fused: model FLOPs of one fused step from shapes (acting forward, learning forward and backward; a rematerialized forward is not model work) over the device time of a step run that lies whole inside the trace (benchmark/lib/whole_runs.py), over chips x peak."""

from benchmark.lib import whole_runs


def read(ctx):
    return whole_runs.mfu(ctx)
