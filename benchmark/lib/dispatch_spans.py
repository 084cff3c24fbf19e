"""The fused loop's host side over the WHOLE window, from the program's
own spans (``timeline.spans``, which keeps ``args``): the spans that
START in ``[ctx.t_open, ctx.t_close]``, the profiled end too.

``fused/enqueue`` is the program's hand-over of one step to the runtime;
its ``in_flight`` arg is how many of the trainer's earlier dispatches the
device still had queued when it began (0: the chip ran dry).  Its parent,
``learner/train_step``, also holds the harness's wait for the retire two
steps back.  ``gc/collect`` and ``host/late_wakeup`` are what can freeze
the host.  A program that records no ``fused/enqueue`` (the parent of
the PR that added it) has none of these instruments: every reader here
then finds nothing, whatever other spans the run has.
"""

from typing import List, Optional, Tuple

from benchmark.lib import timeline

ENQUEUE = "fused/enqueue"


def _window_us(ctx) -> Tuple[float, float]:
    return ((ctx.t_open or 0.0) * 1e6,
            (ctx.t_close or float("inf")) * 1e6)


def in_window(ctx, name: str) -> List[dict]:
    """The run's spans called ``name`` that start inside the window."""
    lo, hi = _window_us(ctx)
    return [e for e in timeline.spans(ctx)
            if e["name"] == name and lo <= e["ts"] <= hi]


def enqueues(ctx) -> List[dict]:
    return in_window(ctx, ENQUEUE)


def dispatches(ctx) -> List[dict]:
    """The window's ``learner/train_step`` spans that hold an enqueue
    and end by the window's close: the dispatch whose wait closes the
    window also holds the harness's stopping of the profiler (12-24 s,
    my chip runs, PR 36), which is no dispatch's time."""
    parents = {e.get("parent") for e in enqueues(ctx)}
    _, hi = _window_us(ctx)
    return [e for e in in_window(ctx, "learner/train_step")
            if e["sid"] in parents and e["ts"] + e["dur"] <= hi]


def summed_ms(ctx, name: str) -> Optional[float]:
    """Milliseconds of the window's spans called ``name``: 0.0 where
    the instruments were on and recorded none, None where they were
    not there."""
    if not enqueues(ctx):
        return None
    return sum(e["dur"] for e in in_window(ctx, name)) * 1e-3
