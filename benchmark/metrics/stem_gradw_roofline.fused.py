"""stem_gradw_roofline.fused: stem grad-W kernel events over the benchmark's least time."""

from benchmark.lib import readers


def read(ctx):
    return readers.roofline_share(ctx, "stem_gradw")
