"""whole_step_device_ms.fused: device time of the runs of the fused step program that lie whole inside the traced window, per run (benchmark/lib/whole_runs.py: fused_step_device_ms counts the runs the trace cut, two of the three a 1.76 s step leaves in the window)."""

from benchmark.lib import whole_runs


def read(ctx):
    return whole_runs.step_device_ms(ctx)
