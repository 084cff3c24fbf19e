"""The set-up and publish readers on a hand-built span list and on the
span list a real run recorded (kept beside this file)."""

import json
import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib import manifest, timeline  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "recorded_v5e_timeline.json")
SETUP = ("setup_before_main_s", "setup_build_s", "setup_trace_lower_s",
         "setup_backend_compile_s")


def reader(name):
    return manifest.load_module(os.path.join(
        manifest.BENCH_DIR, manifest.METRICS_DIR, name + ".py"), name)


def span(name, cat, start_s, dur_s, sid, parent=None, self_s=None):
    event = {"name": name, "cat": cat, "ts": int(start_s * 1e6),
             "dur": int(dur_s * 1e6), "tid": 1, "sid": sid,
             "self": int((dur_s if self_s is None else self_s) * 1e6)}
    if parent is not None:
        event["parent"] = parent
    return event


def hand_built():
    """Launch at 100 s; main entered at 130.  Stages: config [130, 131],
    trainer_init [131, 141] (holding a 2 s trace, a 1 s lower and a 3 s
    backend compile whose first second overlaps the lower), first
    dispatch [141, 146] (a 4 s backend compile).  After it: a 0.5 s
    trace and a 0.5 s backend compile; the window opens at 150."""
    spans = [
        span("setup/config", "setup", 130, 1, 1),
        span("setup/trainer_init", "setup", 131, 10, 2, self_s=5),
        span("compile/trace", "compile", 132, 2, 3, parent=2),
        span("compile/lower", "compile", 134, 1, 4, parent=2),
        span("compile/backend", "compile", 134.5, 3, 5, parent=2),
        span("setup/first_dispatch", "setup", 141, 5, 6, self_s=0),
        span("learner/train_step", "learner", 141, 5, 7, parent=6,
             self_s=1),
        span("compile/backend", "compile", 141.5, 4, 8, parent=7),
        span("compile/trace", "compile", 147, 0.5, 9),
        span("compile/backend", "compile", 147.5, 0.5, 10),
        span("compile/backend", "compile", 151, 9, 11),   # in the window
    ]
    return types.SimpleNamespace(
        program_spans=spans, t_launch=100.0, t_open=150.0, notes=[],
        spans=[("driver/log_publish", 155.0, 0.120),
               ("learner/train_step", 155.2, 0.001),
               ("driver/log_publish", 165.0, 0.140)])


def test_setup_parts_on_a_hand_built_timeline():
    ctx = hand_built()
    values = {name: reader(name).read(ctx) for name in SETUP}
    assert values["setup_before_main_s"] == pytest.approx(30.0)
    # 16 s of stages less the compile union inside them:
    # [132, 137.5] and [141.5, 145.5]
    assert values["setup_build_s"] == pytest.approx(16.0 - 5.5 - 4.0)
    # trace and lower cover [132, 135] and [147, 147.5]; the backend
    # compile covers [134.5, 135] of that, and counts as backend
    assert values["setup_trace_lower_s"] == pytest.approx(2.5 + 0.5)
    # ... and [134.5, 137.5], [141.5, 145.5], [147.5, 148]; the compile
    # inside the window is not set-up
    assert values["setup_backend_compile_s"] == pytest.approx(
        3.0 + 4.0 + 0.5)
    # the four and the warm-up after the first dispatch (less its own
    # compiles, already counted) are setup_s
    parts = timeline.setup_parts(ctx)
    assert parts["after_first_dispatch"] == pytest.approx(4.0)
    assert parts["compiles_after_first_dispatch"] == pytest.approx(1.0)
    assert sum(values.values()) + 4.0 - 1.0 == pytest.approx(50.0)
    assert any(note.startswith("set-up by part") and "= 50.00 of setup_s "
               "50.00" in note for note in ctx.notes)
    assert sum(note.startswith("set-up") for note in ctx.notes) == 2


def test_log_publish_is_the_mean_of_the_windows_publishes():
    assert reader("log_publish_ms").read(hand_built()) == \
        pytest.approx(130.0)


@pytest.mark.parametrize("name", SETUP + ("log_publish_ms",))
def test_a_program_without_a_timeline_gives_nothing_and_does_not_raise(
        name):
    ctx = types.SimpleNamespace(program_spans=[], spans=[], notes=[],
                                t_launch=0.0, t_open=40.0)
    assert reader(name).read(ctx) is None
    ctx = hand_built()
    ctx.t_open = None                      # the window never opened
    if name != "log_publish_ms":
        assert reader(name).read(ctx) is None


def test_no_traced_run_in_this_process_means_no_spans(monkeypatch):
    from scalable_agent_tpu.obs import trace

    monkeypatch.setattr(trace, "last_trace_path", lambda: None)
    ctx = types.SimpleNamespace(notes=[], t_launch=0.0, t_open=1.0)
    assert timeline.spans(ctx) == []
    # the parent of the PR that added the function has none at all
    monkeypatch.delattr(trace, "last_trace_path")
    assert timeline.trace_path() is None


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded timeline beside the test")
def test_recorded_timeline_of_a_real_run_adds_up():
    with open(RECORDED) as f:
        recorded = json.load(f)
    ctx = types.SimpleNamespace(
        program_spans=recorded["spans"], notes=[],
        spans=[tuple(row) for row in recorded["window_spans"]],
        t_launch=recorded["t_launch"], t_open=recorded["t_open"])
    want = recorded["expect"]
    for name in SETUP + ("log_publish_ms",):
        assert reader(name).read(ctx) == pytest.approx(
            want[name], rel=1e-6), name
    parts = timeline.setup_parts(ctx)
    total = (sum(want[name] for name in SETUP)
             + parts["after_first_dispatch"]
             - parts["compiles_after_first_dispatch"])
    # the stages are contiguous, so the parts are the whole of setup_s
    assert total == pytest.approx(ctx.t_open - ctx.t_launch, abs=0.05)
    names = [e["name"] for e in timeline.stages(ctx)]
    assert names[0] == "setup/config"
    assert names[-1] == "setup/first_dispatch"
