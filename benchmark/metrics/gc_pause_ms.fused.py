"""gc_pause_ms.fused: summed gc/collect spans (full collections) of the window."""

from benchmark.lib import dispatch_spans


def read(ctx):
    return dispatch_spans.summed_ms(ctx, "gc/collect")
