"""attention_update_roofline.fused on synthetic traces: one whose update
attention is the blockwise kernel's two Mosaic calls a layer, one whose
update attention is the parent's shape of ops (score fusions, a
rematerialized copy of them, their backward, under a ``lax.map``'s
while), and a program with no such scope."""

import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib import manifest, peaks  # noqa: E402
from benchmark.lib.trace_reduce import (  # noqa: E402
    MODULES_LINE,
    OPS_LINE,
    Event,
)

PLANE = "/device:TPU:0"
UPDATE = "jit(_fused)/jit(main)/while/body/learner_update/"
LAYER = "TokenPolicy/layer_4/attention/"
READER = manifest.load_module(os.path.join(
    manifest.BENCH_DIR, manifest.METRICS_DIR,
    "attention_update_roofline.fused.py"), "attention_update_roofline")


def trinity():
    cell = manifest.load_cell("trinity.ingraph", with_readers=False)
    return cell.config, manifest.driver_flags(cell), cell.traffic


def ctx_of(ops, seconds):
    """A whole run of the step [1, 3] s between two cut ones, holding
    ``ops`` ({instruction: op_name}) one after the other, ``seconds``
    each."""
    config, flags, traffic = trinity()
    events = [Event(PLANE, MODULES_LINE, "jit__fused(1)", 0.0, 1.0),
              Event(PLANE, OPS_LINE, "%before = f32[] add(...)", 0.0, 1.0),
              Event(PLANE, MODULES_LINE, "jit__fused(1)", 1.0, 2.0),
              Event(PLANE, MODULES_LINE, "jit__fused(1)", 3.0, 1.0),
              Event(PLANE, OPS_LINE, "%after = f32[] add(...)", 3.0, 1.0)]
    at = 1.0
    for name in ops:
        events.append(Event(PLANE, OPS_LINE,
                            f"%{name} = f32[8] fusion(...)", at, seconds))
        at += seconds
    return types.SimpleNamespace(
        events=events, op_scopes=dict(ops), notes=[], config=config,
        flags=flags, traffic=traffic, chips=1,
        peak=peaks.for_kind("TPU v5 lite"))


ROLLOUT = {
    "fusion.3449": "jit(_fused)/jit(main)/while/body/rollout/while/body/"
                   "closed_call/actor_inference/" + LAYER
                   + "full/dot_general",
}
WITH_KERNEL = {
    "pallas_call.8": UPDATE + "jvp(TokenPolicy)/" + LAYER
                     + "full/pallas_call",
    "pallas_call.9": UPDATE + "transpose(jvp(TokenPolicy))/checkpoint/"
                     "rematted_computation/" + LAYER + "full/pallas_call",
    "pallas_call.10": UPDATE + "transpose(jvp(TokenPolicy))/" + LAYER
                      + "window/pallas_call",
    "copy.6300": UPDATE + "jvp(TokenPolicy)/" + LAYER + "full/transpose",
}
WITHOUT_KERNEL = {
    "while.40": UPDATE + "jvp(TokenPolicy)/" + LAYER + "full/while",
    "fusion.801": UPDATE + "jvp(TokenPolicy)/" + LAYER
                  + "full/while/body/checkpoint/dot_general",
    "fusion.802": UPDATE + "transpose(jvp(TokenPolicy))/checkpoint/"
                  "rematted_computation/" + LAYER
                  + "window/while/body/reduce_max",
    "fusion.803": UPDATE + "transpose(jvp(TokenPolicy))/" + LAYER
                  + "window/while/body/transpose(jvp(checkpoint))/exp",
}
ELSEWHERE = {
    "fusion.11": UPDATE + "jvp(TokenPolicy)/" + LAYER + "q_proj/dot_general",
    "fusion.12": UPDATE + "jvp(TokenPolicy)/TokenPolicy/layer_4/moe/"
                 "experts/ragged_dot",
    "fusion.13": UPDATE + "jvp(TokenPolicy)/full_attention_bias/add",
}


@pytest.mark.parametrize("ops", [WITH_KERNEL, WITHOUT_KERNEL],
                         ids=["the kernel's ops", "the parent's ops"])
def test_it_reads_the_update_attention_whatever_implements_it(ops):
    seconds = 0.1
    ctx = ctx_of({**ROLLOUT, **ops, **ELSEWHERE}, seconds)
    value = READER.read(ctx)
    counts = READER.least(ctx)
    least_s = max(counts["flops"] / ctx.peak["flops_bf16"],
                  counts["bytes"] / ctx.peak["hbm_bytes_per_s"])
    # the rollout's attention, the projections and the experts are not
    # in it; the whole run is counted once
    assert value == pytest.approx(100.0 * least_s / (seconds * len(ops)))
    assert 0.0 < value <= 100.0
    assert any(n.startswith("update attention:") for n in ctx.notes)


def test_the_least_work_is_the_configurations_own_count():
    counts = READER.least(ctx_of({}, 0.1))
    # 32 envs x 257 queries x 32 heads x 128 x 2,048 keys x 2 products
    # x 2 FLOPs, five layers, forward and twice that backward
    assert counts["flops"] == pytest.approx(
        3 * 5 * 4.0 * 32 * 257 * 32 * 128 * 2048)
    # the rings once a pass: 4 x 2,304 + 4,352 slots of 2 KiB an env
    ring = 2.0 * 32 * (4 * 2304 + 4352) * 2048
    assert ring < counts["bytes"] < ring + 2.0 * 5 * 32 * 257 * 128 * (
        2 * 40 + 4 * 32) + 1


def test_the_steps_least_time_cannot_pass_what_a_chip_could_do():
    # were the update's attention all a step held, at the chip's peak,
    # the share would read 100 and no more
    ctx = ctx_of(WITH_KERNEL, 1.0)
    counts = READER.least(ctx)
    at_peak = counts["flops"] / ctx.peak["flops_bf16"]
    ctx = ctx_of(WITH_KERNEL, at_peak / len(WITH_KERNEL))
    assert READER.read(ctx) == pytest.approx(100.0)


@pytest.mark.parametrize("ops", [{}, {**ROLLOUT, **ELSEWHERE}],
                         ids=["no op", "no op of the update's attention"])
def test_a_program_with_no_such_scope_reads_nothing(ops):
    assert READER.read(ctx_of(ops, 0.1)) is None


def test_no_table_reads_nothing(monkeypatch):
    from benchmark.lib import timeline

    monkeypatch.setattr(timeline, "trace_path", lambda: None)
    ctx = ctx_of(WITH_KERNEL, 0.1)
    ctx.op_scopes = None
    assert READER.read(ctx) is None
