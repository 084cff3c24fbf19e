"""A roofline share of work the program marks by SCOPE, not by a
kernel's name: the least time the chip could take for it (from shapes,
``rooflines/<kernel>.py least``) over the device self time a step spends
in the ops its ``in_update(op_name)`` finds, over the step runs that lie
whole inside the trace (``whole_runs``), mean over chips.  The same work
whatever implements it: the scopes are the program's, the count is from
shapes.  What ``metrics/attention_update_roofline.fused.py`` does for
its own scopes, for the rooflines a later configuration brings.
"""

import statistics
from typing import Optional

from benchmark.lib import readers, scopes, whole_runs


def share(ctx, kernel: str, what: str) -> Optional[float]:
    """None where the program has no such scope (the parent of the PR
    that brought it), or the trace no whole run."""
    module = readers.roofline_module(kernel)
    table = scopes.table(ctx)
    if table is None or ctx.peak is None:
        return None
    per_plane = []
    for plane in readers.planes(ctx):
        ops, _ = whole_runs._ops(ctx, plane)
        runs = len(whole_runs.runs(ctx, plane))
        if runs:
            per_plane.append(sum(
                self_s for name, self_s in ops
                if module.in_update(table.get(name))) / runs)
    if not per_plane or statistics.mean(per_plane) <= 0:
        return None
    measured = statistics.mean(per_plane)
    counts = module.least(ctx)
    if counts is None:
        return None
    least_s, bound = readers.least_seconds(counts["flops"], counts["bytes"],
                                           ctx.peak)
    ctx.notes.append(
        f"{what}: {measured * 1e3:.2f} ms a step measured, least "
        f"{least_s * 1e3:.2f} ms ({bound}-bound: {counts['flops']:.4g} flop, "
        f"{counts['bytes']:.4g} B)")
    return 100.0 * least_s / measured
