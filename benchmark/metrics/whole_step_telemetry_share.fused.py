"""whole_step_telemetry_share.fused: device self time of ops under scope telemetry (what only the obs plane reads: devtel, learning dynamics, the expert layers' load, the acting / learning mismatch) over the device time of the step runs that lie whole inside the trace (benchmark/lib/whole_runs.py), mean over chips."""

from benchmark.lib import whole_runs


def read(ctx):
    return whole_runs.share(ctx, "telemetry")
