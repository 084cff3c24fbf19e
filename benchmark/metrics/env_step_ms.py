"""env_step_ms: actor/env_step span, median per group-step."""

from benchmark.lib import readers


def read(ctx):
    return readers.span_median_ms(ctx, "actor/env_step")
