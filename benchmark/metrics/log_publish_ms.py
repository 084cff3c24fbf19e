"""log_publish_ms: mean duration of the program's driver/log_publish spans in the window, before the profiler starts."""

import statistics

from benchmark.lib import readers


def read(ctx):
    durations = readers.span_durations(ctx, "driver/log_publish")
    return statistics.mean(durations) * 1e3 if durations else None
