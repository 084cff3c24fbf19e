"""The scope reader: the classes, the arithmetic on a synthetic trace,
and one whole step of a real v5e trace with the program's scope table
(kept beside this file)."""

import json
import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib import manifest, scopes  # noqa: E402
from benchmark.lib.trace_reduce import (  # noqa: E402
    MODULES_LINE,
    OPS_LINE,
    Event,
)

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "recorded_v5e_scopes.json")
DEV = "/device:TPU:0"
UPDATE = "jit(_fused)/jit(main)/while/body/learner_update/"


def reader(name):
    return manifest.load_module(os.path.join(
        manifest.BENCH_DIR, manifest.METRICS_DIR, name + ".py"), name)


@pytest.mark.parametrize("op_name, kind", [
    ("jit(_fused)/jit(main)/while/body/rollout/while/body/closed_call/"
     "actor_inference/ImpalaAgent/convnet/conv_0/conv_general_dilated",
     "rollout"),                       # acting's torso is the rollout's
    ("jit(_fused)/jit(main)/while/body/rollout/while", "rollout"),
    ("jit(_fused)/jit(main)/while/body/telemetry/reduce_sum", "telemetry"),
    (UPDATE + "jvp(vtrace_loss)/telemetry/reduce_sum", "telemetry"),
    (UPDATE + "telemetry/sqrt", "telemetry"),
    (UPDATE + "transpose(jvp(ImpalaAgent))/convnet/conv_0/"
     "pallas_conv0_gradw/pallas_call", "update.torso"),
    (UPDATE + "jvp(ImpalaAgent)/checkpoint/convnet/conv_1/add",
     "update.torso"),
    (UPDATE + "jvp(ImpalaAgent)/core/pallas_lstm_fwd/pallas_call",
     "update.core"),
    (UPDATE + "transpose(jvp(ImpalaAgent))/core/pallas_lstm_bwd/"
     "pallas_call", "update.core"),
    (UPDATE + "jvp(vtrace_loss)/mul", "update.loss_heads"),
    (UPDATE + "jvp(ImpalaAgent)/policy_logits/dot_general",
     "update.loss_heads"),
    (UPDATE + "transpose(jvp(ImpalaAgent))/baseline/dot_general",
     "update.loss_heads"),
    (UPDATE + "optimizer/mul", "update.optimizer"),
    (UPDATE + "jvp(ImpalaAgent)/div", "unscoped"),
    ("jit(_fused)/jit(main)/while/body/jit(_where)/select_n", "unscoped"),
    # a word inside another word is not the word
    (UPDATE + "score/add", "unscoped"),
    ("jit(_fused)/jit(main)/while/body/baseline_offset/add", "unscoped"),
    (None, "unscoped"), ("", "unscoped"),
])
def test_each_op_goes_to_exactly_one_class(op_name, kind):
    assert scopes.classify(op_name) == kind
    assert kind in scopes.CLASSES


def synthetic(chips=1):
    """Per chip: a run of jit__fused [0, 10] holding a rollout while
    [0, 4] with two fusions in it (1 + 1.5 s), a torso fusion 3 s, a
    Pallas LSTM call 1 s, an optimizer fusion 0.5 s, a telemetry
    reduce 0.25 s, an op the table does not know 0.25 s, and 1 s of
    gaps; then a run cut by the trace's end."""
    events = []
    for chip in range(chips):
        plane = f"/device:TPU:{chip}"

        def op(name, start, dur):
            events.append(Event(plane, OPS_LINE, name, start, dur))

        events.append(Event(plane, MODULES_LINE, "jit__fused(1)", 0.0, 10.0))
        op("%while.71 = (s32[]) while(...)", 0.0, 4.0)
        op("%fusion.1 = bf16[8] fusion(...)", 0.5, 1.0)
        op("%fusion.2 = bf16[8] fusion(...)", 2.0, 1.5)
        op("%fusion.238 = bf16[8] fusion(...)", 4.0, 3.0)
        op("%pallas_lstm_fwd.3 = f32[4] custom-call(...)", 7.0, 1.0)
        op("%fusion.9 = f32[8] fusion(...)", 8.0, 0.5)
        op("%reduce.4 = f32[] reduce(...)", 8.5, 0.25)
        op("%copy.77 = f32[8] copy(...)", 8.75, 0.25)
        events.append(Event(plane, MODULES_LINE, "jit__fused(1)", 10.0, 10.0))
        op("%fusion.238 = bf16[8] fusion(...)", 10.0, 3.0)   # cut run
    ops = {
        "while.71": "jit(_fused)/jit(main)/while/body/rollout/while",
        "fusion.1": "jit(_fused)/jit(main)/while/body/rollout/while/body/"
                    "closed_call/env_step/add",
        "fusion.2": "jit(_fused)/jit(main)/while/body/rollout/while/body/"
                    "closed_call/actor_inference/ImpalaAgent/convnet/dot",
        "fusion.238": UPDATE + "jvp(ImpalaAgent)/convnet/conv_0/add",
        "pallas_lstm_fwd.3": UPDATE + "jvp(ImpalaAgent)/core/"
                             "pallas_lstm_fwd/pallas_call",
        "fusion.9": UPDATE + "optimizer/mul",
        "reduce.4": UPDATE + "telemetry/reduce_sum",
    }
    return types.SimpleNamespace(
        events=events, op_scopes=ops, notes=[],
        traffic={"step_module": "_fused"})


@pytest.mark.parametrize("chips", [1, 4])
def test_shares_are_self_time_over_whole_step_runs(chips):
    ctx = synthetic(chips)
    # the cut run holds an op: only ops inside WHOLE runs count, so the
    # second fusion.238 must not enter (the trace's last op ends at 13)
    found = scopes.shares(ctx)
    assert found["rollout"] == pytest.approx(40.0)    # the while ONCE
    assert found["update.torso"] == pytest.approx(30.0)
    assert found["update.core"] == pytest.approx(10.0)
    assert found["update.optimizer"] == pytest.approx(5.0)
    assert found["telemetry"] == pytest.approx(2.5)
    assert found["unscoped"] == pytest.approx(2.5)
    assert found["update.loss_heads"] == 0.0
    assert sum(found.values()) == pytest.approx(90.0)  # 1 s of gaps
    assert reader("rollout_device_share.fused").read(ctx) == \
        pytest.approx(40.0)
    assert reader("torso_device_share.fused").read(ctx) == \
        pytest.approx(30.0)
    assert reader("telemetry_device_share.fused").read(ctx) == \
        pytest.approx(2.5)
    # the whole table is kept, once, with what is in `unscoped`
    table = [n for n in ctx.notes if n.startswith("step device time")]
    assert len(table) == 1 and "gaps between ops 10.00" in table[0]
    assert f"mean over {chips} chip(s)" in table[0]
    assert any("copy.77 2.50 (not in the table)" in n for n in ctx.notes)


@pytest.mark.parametrize("name", [
    "rollout_device_share.fused", "torso_device_share.fused",
    "telemetry_device_share.fused"])
def test_no_table_or_no_device_plane_gives_nothing(name, monkeypatch):
    from benchmark.lib import timeline

    monkeypatch.setattr(timeline, "trace_path", lambda: None)
    ctx = synthetic()
    ctx.op_scopes = None          # the program left no table
    assert reader(name).read(ctx) is None
    ctx = synthetic()
    ctx.events = [e for e in ctx.events if e.line != MODULES_LINE]
    assert reader(name).read(ctx) is None      # no whole step run


def test_the_table_is_found_beside_the_programs_trace(tmp_path,
                                                       monkeypatch):
    from benchmark.lib import timeline

    trace = tmp_path / "trace.p0.4242.json"
    trace.write_text("[\n")
    (tmp_path / "op_scopes.p0.4242.json").write_text(json.dumps(
        {"module": "jit__fused", "ops": {"fusion.1": "a/rollout/b"}}))
    monkeypatch.setattr(timeline, "trace_path", lambda: str(trace))
    ctx = types.SimpleNamespace(notes=[])
    assert scopes.table(ctx) == {"fusion.1": "a/rollout/b"}


def recorded_step():
    """(the recorded file, a ctx holding its one whole step run)."""
    with open(RECORDED) as f:
        recorded = json.load(f)
    plane = recorded["plane"]
    name, start, dur = recorded["run"]
    events = [Event(plane, MODULES_LINE, name, start * 1e-9, dur * 1e-9)]
    events += [Event(plane, OPS_LINE, recorded["names"][i], s * 1e-9,
                     d * 1e-9) for i, s, d in recorded["ops"]]
    # the file holds ONE run; in the trace it came from, ops of the runs
    # before and after stand on both sides, which is how a run is known
    # to lie whole inside the traced window
    events += [Event(plane, OPS_LINE, "%neighbour = ...", at, 1e-9)
               for at in (-1e-6, (start + dur) * 1e-9 + 1e-6)]
    return recorded, types.SimpleNamespace(
        events=events, op_scopes=recorded["op_scopes"], notes=[],
        traffic={"step_module": "_fused"})


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded step beside the test")
def test_one_recorded_step_of_a_real_v5e_trace():
    recorded, ctx = recorded_step()
    found = scopes.shares(ctx)
    want = recorded["expect"]
    for kind in scopes.CLASSES:
        assert found[kind] == pytest.approx(want[kind], abs=1e-6), kind
    # the classes and the gaps between ops are the whole step
    assert 99.0 < sum(found.values()) <= 100.0 + 1e-9
    assert found["unscoped"] < 10.0
    # the LSTM's three Mosaic calls carry their own names now
    names = " ".join(recorded["names"])
    for kernel in ("pallas_lstm_fwd.", "pallas_lstm_step.",
                   "pallas_lstm_bwd.", "pallas_conv0_gradw."):
        assert "%" + kernel in names, kernel
