"""setup_trace_lower_s: union of compile/trace and compile/lower spans ending before the window opens, less what backend compiles cover."""

from benchmark.lib import timeline


def read(ctx):
    return timeline.setup_part(ctx, "trace_lower")
