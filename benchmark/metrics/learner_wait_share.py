"""learner_wait_share: learner/wait_batch span time over the window the spans cover."""

from benchmark.lib import readers


def read(ctx):
    waits = readers.span_durations(ctx, "learner/wait_batch")
    span = readers.span_window_s(ctx)
    if not waits or not span:
        return None
    return 100.0 * sum(waits) / span
