"""Step runs that lie WHOLE inside a traced window, for a cell whose
step is long beside the window.

``trace_reduce.module_runs`` keeps every run of the step program that
begins and ends between the trace's first and last op.  A run the trace
began or ended in the middle of passes that test: its event is cut to
the ops that were seen, so it starts with the first op and ends with
the last.  Where a step takes 36 ms and the 4 s window holds 110 of
them, two cut runs move a mean by a percent.  ``trinity.ingraph``'s
step takes 1.76 s: the window runs from one retire to the retire two
intervals on (3.5 s), the device being a fetch's latency into the next
step by then, and holds a run cut at its start (1.70 of 1.76 s), ONE
whole run and a sliver of a third; ``readers.step_device_ms`` divides
the window by three and reads 1,153 ms (my chip runs, PR 32).  The
readers here take the runs that touch neither edge.  Where a stall left
no whole run in the window they fall back on the longest cut run (a
lower bound) and say so in the run's notes.
"""

import re
import statistics
from typing import Dict, List, Optional, Tuple

from benchmark.lib import readers, scopes, trace_reduce

_EDGE_S = 1e-6


def runs(ctx, plane: str) -> List:
    """The whole runs of the cell's step program on one chip; the
    longest cut run alone where the window holds none."""
    found = sorted(readers.step_runs(ctx, plane), key=lambda r: r.start)
    if not found:
        return []
    ops = trace_reduce.line_events(ctx.events, plane,
                                   trace_reduce.OPS_LINE) or found
    lo = min(ops[0].start, found[0].start)
    hi = max(max(o.start + o.dur for o in ops),
             found[-1].start + found[-1].dur)
    whole = [r for r in found if r.start > lo + _EDGE_S
             and r.start + r.dur < hi - _EDGE_S]
    if whole:
        return whole
    note = ("no whole step run inside the traced window: the whole-run "
            "metrics read the longest cut run, a lower bound")
    if note not in ctx.notes:
        ctx.notes.append(note)
    return [max(found, key=lambda r: r.dur)]


def step_device_ms(ctx) -> Optional[float]:
    per_plane = [statistics.mean(r.dur for r in found) * 1e3
                 for found in (runs(ctx, p) for p in readers.planes(ctx))
                 if found]
    return statistics.mean(per_plane) if per_plane else None


def mfu(ctx) -> Optional[float]:
    """``readers.mfu`` over the whole runs."""
    step_ms = step_device_ms(ctx)
    if step_ms is None or ctx.peak is None:
        return None
    count = getattr(getattr(ctx, "reference", None),
                    "train_flops_per_env_frame",
                    readers.train_flops_per_env_frame)
    flops = count(ctx.config) * ctx.frames_per_update
    return (100.0 * flops
            / (ctx.chips * step_ms * 1e-3 * ctx.peak["flops_bf16"]))


def _ops(ctx, plane: str) -> Tuple[List[Tuple[str, float]], float]:
    """([(instruction name, self seconds)] of the op events inside the
    whole runs, seconds of those runs): ``scopes._step_ops`` over
    ``runs``."""
    found = runs(ctx, plane)
    out = []
    events = trace_reduce.line_events(ctx.events, plane,
                                      trace_reduce.OPS_LINE)
    cursor = 0
    for event, self_s in trace_reduce.self_times(events):
        while cursor < len(found) and event.start >= (
                found[cursor].start + found[cursor].dur):
            cursor += 1
        if cursor == len(found):
            break
        run = found[cursor]
        if event.start < run.start - 1e-9 or (
                event.start + event.dur > run.start + run.dur + 1e-9):
            continue
        out.append((trace_reduce.short_name(event.name), self_s))
    return out, sum(r.dur for r in found)


def _share(ctx, wanted) -> Optional[float]:
    """% of the whole runs' device time in ops whose ``op_name``
    ``wanted`` accepts; mean over chips; None with no scope table or no
    step run at all."""
    table = scopes.table(ctx)
    if table is None:
        return None
    per_plane = []
    for plane in readers.planes(ctx):
        ops, total = _ops(ctx, plane)
        if total <= 0:
            continue
        per_plane.append(100.0 * sum(
            self_s for name, self_s in ops if wanted(table.get(name)))
            / total)
    return statistics.mean(per_plane) if per_plane else None


def share(ctx, kind: str) -> Optional[float]:
    """``scopes.share`` (one of its seven classes) over the whole runs."""
    return _share(ctx, lambda op_name: scopes.classify(op_name) == kind)


def share_where(ctx, pattern) -> Optional[float]:
    """``scopes.share_where`` over the whole runs."""
    wanted = re.compile(pattern)
    return _share(ctx, lambda op_name: bool(wanted.search(op_name or "")))
