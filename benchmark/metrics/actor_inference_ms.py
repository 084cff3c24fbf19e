"""actor_inference_ms: actor/inference span, median."""

from benchmark.lib import readers


def read(ctx):
    return readers.span_median_ms(ctx, "actor/inference")
