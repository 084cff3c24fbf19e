"""The trace reduction on hand-made events and on the small recorded
slice of a real v5e trace kept beside this file."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib import trace_reduce as tr  # noqa: E402
from benchmark.lib.trace_reduce import Event  # noqa: E402

DEV = "/device:TPU:0"
HERE = os.path.dirname(os.path.abspath(__file__))


def op(name, start, dur, plane=DEV):
    return Event(plane, tr.OPS_LINE, name, start, dur)


def run(name, start, dur, plane=DEV):
    return Event(plane, tr.MODULES_LINE, name, start, dur)


def host(name, start, dur):
    return Event(tr.HOST_PLANE, "learner", name, start, dur)


def synthetic():
    """Two runs of jit_step: a while loop [0,4] holding two fusions,
    then a kernel in two events; an idle gap; then the same again."""
    events = []
    for base in (0.0, 10.0):
        events += [
            run("jit_step(7)", base, 7.0),
            op("%while.1 = (s32[]) while(...)", base, 4.0),
            op("%fusion.2 = bf16[8] fusion(...)", base + 0.5, 1.0),
            op("%fusion.3 = bf16[8] fusion(...)", base + 2.0, 1.5),
            op("%_bwd_kernel.1 = f32[4] custom-call(...)", base + 4.0, 2.0),
            op("%_bwd_kernel.1 = f32[4] custom-call(...)", base + 6.0, 1.0),
        ]
    events += [
        host("learner/train_step", 0.0, 7.5),
        host("actor/inference", 7.2, 2.7),
        host("tpu::System::Execute", 7.3, 0.1),
    ]
    return events


def test_busy_is_the_union_and_the_window_spans_all_ops():
    busy, window_s = tr.busy_and_window(synthetic())
    assert busy == pytest.approx(14.0)        # two runs of 7 s, no overlap
    assert window_s == pytest.approx(17.0)


def test_self_time_takes_children_out_of_the_while():
    totals = tr.op_totals(synthetic(), DEV)
    by_short = {tr.short_name(k): v for k, v in totals.items()}
    assert by_short["while.1"][0] == pytest.approx(2 * (4.0 - 1.0 - 1.5))
    assert by_short["fusion.3"] == (pytest.approx(3.0), 2)
    assert by_short["_bwd_kernel.1"] == (pytest.approx(6.0), 4)
    top = tr.top_ops(synthetic(), DEV, n=1)
    assert top[0][0].startswith("_bwd_kernel.1") and top[0][1] == \
        pytest.approx(6.0)


def test_kernel_seconds_sum_every_event_inside_whole_runs():
    events = synthetic()
    runs = tr.module_runs(events, DEV, "jit_step")
    assert len(runs) == 2
    seconds, count = tr.kernel_seconds(
        events, DEV, lambda n: "_bwd_kernel" in n, inside=runs)
    # a two-event kernel: BOTH events of each call are summed
    assert (seconds, count) == (pytest.approx(6.0), 4)
    assert seconds / len(runs) == pytest.approx(3.0)


def test_a_run_cut_by_the_trace_edge_is_left_out():
    events = synthetic() + [run("jit_step(7)", 16.5, 7.0)]
    assert len(tr.module_runs(events, DEV, "jit_step")) == 2


def test_idle_gaps_go_to_the_innermost_program_span():
    gaps = dict(tr.gaps_by_host_span(synthetic(), DEV))
    assert gaps == {"actor_inference": pytest.approx(3.0)}
    only_device = [e for e in synthetic() if e.plane == DEV]
    assert dict(tr.gaps_by_host_span(only_device, DEV)) == {
        tr.NO_SPAN: pytest.approx(3.0)}


def test_exposed_collective_time_is_what_no_other_op_covers():
    events = [
        op("%all-reduce.1 = f32[8] all-reduce(...)", 0.0, 2.0),
        op("%fusion.9 = f32[8] fusion(...)", 1.5, 2.0),
        op("%all-reduce.2 = f32[8] all-reduce(...)", 5.0, 1.0),
    ]
    exposed = tr.exposed_seconds(events, DEV, lambda n: "all-reduce" in n)
    assert exposed == pytest.approx(1.5 + 1.0)


def test_breakdown_has_at_most_ten_of_each_and_ledger_safe_names():
    out = tr.breakdown(synthetic())
    assert 0 < len(out["device_ops"]) <= 10
    assert 0 < len(out["idle_gaps"]) <= 10
    for name, seconds in out["device_ops"] + out["idle_gaps"]:
        assert seconds > 0 and len(name) <= 64
        assert all(c.isalnum() or c in "_.-" for c in name)


RECORDED = os.path.join(HERE, "recorded_v5e_slice.json")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded slice beside the test")
def test_recorded_slice_of_a_real_trace():
    with open(RECORDED) as f:
        recorded = json.load(f)
    events = [Event(*row) for row in recorded["events"]]
    want = recorded["expect"]
    planes = tr.device_ids(events)
    assert planes == want["planes"]
    busy = tr.busy_seconds(events, planes[0])
    assert busy == pytest.approx(want["busy_s"], rel=1e-9)
    totals = tr.op_totals(events, planes[0])
    assert sum(v[0] for v in totals.values()) == pytest.approx(
        busy, rel=1e-6)                       # self times tile the union
    assert len(tr.line_events(events, planes[0], tr.MODULES_LINE)) \
        == want["module_events"]
    # the rollout's while loop: its self time is its duration less the
    # ops nested in it (the expectation was worked out pair by pair)
    loop = [name for name in totals if tr.short_name(name) == "while.71"]
    assert totals[loop[0]] == (pytest.approx(want["while_self_s"]), 1)
    # the slice holds 1.6 ms of ops: no program run lies whole inside it
    assert tr.module_runs(events, planes[0], "_fused") == []
