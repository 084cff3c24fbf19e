"""dispatch_longest_over_median.fused: the longest learner/train_step span that starts in the window over their median (1.0-1.3 steady; 2-3 when one dispatch was held)."""

import statistics

from benchmark.lib import dispatch_spans


def read(ctx):
    durations = [e["dur"] for e in dispatch_spans.dispatches(ctx)]
    if not durations or statistics.median(durations) <= 0:
        return None
    return max(durations) / statistics.median(durations)
