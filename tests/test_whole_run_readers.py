"""The benchmark's whole-run readers (benchmark/lib/whole_runs.py): over a
traced window that begins inside one run of the step and ends inside
another, they read the runs the trace holds whole and leave out the two
it cut; with no whole run in the window they read the longest cut one
and say so.
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (ROOT, os.path.join(ROOT, "tests")):
    if path not in sys.path:
        sys.path.insert(0, path)

from test_token_policy import TINY, ref  # noqa: E402


def _traced_window(step=1.756, cut=0.05, sliver=0.02):
    """A trace as ``trinity.ingraph``'s: it begins ``cut`` into one run
    of the step, holds the next whole, and ends ``sliver`` into a third;
    every run is a rollout op (half) and an update op (half)."""
    from benchmark.lib import trace_reduce

    plane = "/device:TPU:0"
    events = trace_reduce.EventList()
    spans = [(0.0, step - cut), (step - cut, step),
             (2 * step - cut, sliver)]
    for i, (start, dur) in enumerate(spans):
        events.append(trace_reduce.Event(
            plane, trace_reduce.MODULES_LINE, f"jit__fused({i})", start,
            dur))
        if i == 0:       # cut at its start: the rollout's head is missing
            halves = [("fusion.1", step / 2 - cut), ("fusion.2", step / 2)]
        elif i == 1:
            halves = [("fusion.1", step / 2), ("fusion.2", step / 2)]
        else:
            halves = [("fusion.1", sliver)]
        at = start
        for name, length in halves:
            events.append(trace_reduce.Event(
                plane, trace_reduce.OPS_LINE, f"%{name} = f32[] fusion()",
                at, length))
            at += length
    return events


class _Ctx:
    def __init__(self, events):
        self.events = events
        self.traffic = {"step_module": "_fused"}
        self.notes = []
        self.op_scopes = {
            "fusion.1": "jit(_fused)/rollout/while/body/attention/dot",
            "fusion.2": "jit(_fused)/learner_update/layer_1/moe/experts"}
        self.peak = {"flops_bf16": 197e12}
        self.chips = 1
        self.config = dict(TINY, mean_context=8)
        self.frames_per_update = 8192.0
        self.reference = ref


@pytest.mark.parametrize("reader, want", [
    ("step_device_ms", 1756.0), ("rollout", 50.0), ("moe", 50.0)])
def test_the_whole_run_readers_leave_out_the_runs_the_trace_cut(
        reader, want):
    from benchmark.lib import readers, whole_runs

    ctx = _Ctx(_traced_window())
    # what the accepted reader gives there: the window over three
    assert abs(readers.step_device_ms(ctx) - (2 * 1756 - 50 + 20) / 3) < 1
    got = {"step_device_ms": lambda: whole_runs.step_device_ms(ctx),
           "rollout": lambda: whole_runs.share(ctx, "rollout"),
           "moe": lambda: whole_runs.share_where(ctx, r"\bmoe\b")}[reader]()
    assert abs(got - want) < 1e-6
    assert not ctx.notes
    assert 0 < whole_runs.mfu(ctx) < 100


def test_a_window_with_no_whole_run_reads_the_longest_cut_run():
    from benchmark.lib import trace_reduce, whole_runs

    events = trace_reduce.EventList(
        e for e in _traced_window() if e.start < 1.7)
    ctx = _Ctx(events)
    assert abs(whole_runs.step_device_ms(ctx) - 1706.0) < 1e-6
    assert len(ctx.notes) == 1 and "no whole step run" in ctx.notes[0]
