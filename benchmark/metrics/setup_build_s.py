"""setup_build_s: the setup/* stages (main entry to the first dispatch returning) less the compile spans inside them."""

from benchmark.lib import timeline


def read(ctx):
    return timeline.setup_part(ctx, "build")
