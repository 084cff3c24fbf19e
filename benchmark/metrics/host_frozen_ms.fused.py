"""host_frozen_ms.fused: summed host/late_wakeup spans of the window, time in which no thread of the process got the GIL or the process did not run."""

from benchmark.lib import dispatch_spans


def read(ctx):
    return dispatch_spans.summed_ms(ctx, "host/late_wakeup")
