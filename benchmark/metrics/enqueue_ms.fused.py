"""enqueue_ms.fused: median fused/enqueue span of the window, what handing a step to the runtime costs the host."""

import statistics

from benchmark.lib import dispatch_spans


def read(ctx):
    durations = [e["dur"] for e in dispatch_spans.enqueues(ctx)]
    return statistics.median(durations) * 1e-3 if durations else None
