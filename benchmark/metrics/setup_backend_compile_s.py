"""setup_backend_compile_s: union of compile/backend spans (a compile or a cache load) ending before the window opens."""

from benchmark.lib import timeline


def read(ctx):
    return timeline.setup_part(ctx, "backend")
