"""Each roofline count against a hand-worked one, the share arithmetic
on a synthetic trace, and the refusal of a share over 100%."""

import os
import subprocess
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib import manifest, peaks, readers  # noqa: E402
from benchmark.lib.trace_reduce import (  # noqa: E402
    MODULES_LINE,
    OPS_LINE,
    Event,
)

DEV = "/device:TPU:0"


def ctx_for(batch=32, unroll=100, events=()):
    config = manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "configs", "impala_shallow.json"))
    return types.SimpleNamespace(
        config=config, flags={"batch_size": batch, "unroll_length": unroll},
        traffic={"step_module": "jit_step"}, events=list(events),
        peak=peaks.for_kind("TPU v5 lite"), notes=[])


def test_lstm_fwd_counts_weights_once_per_call():
    counts = readers.roofline_module("lstm_fwd").least(ctx_for())
    t, b, d, h = 101, 32, 266, 256
    assert counts["flops"] == 2 * t * b * (d + h) * 4 * h      # 3.455e9
    weights = (d + h) * 4 * h
    per_step = b * d + b + b * h                                # x, done, ys
    assert counts["bytes"] == 4 * (t * per_step + weights + 4 * h
                                   + 4 * b * h)
    # once per STEP would add 100 more copies of the weights: the
    # count must stay well under that
    assert counts["bytes"] < 4 * (t * per_step + 2 * weights + 4 * b * h
                                  + 4 * h)


def test_lstm_bwd_is_twice_the_forward_flops():
    fwd = readers.roofline_module("lstm_fwd").least(ctx_for())
    bwd = readers.roofline_module("lstm_bwd").least(ctx_for())
    assert bwd["flops"] == 2 * fwd["flops"]
    assert bwd["bytes"] > fwd["bytes"]


def test_stem_gradw_hand_count():
    counts = readers.roofline_module("stem_gradw").least(ctx_for())
    n = 101 * 32
    assert counts["flops"] == 2 * n * 18 * 24 * 32 * (8 * 8 * 3)
    assert counts["bytes"] == 2 * n * (72 * 96 * 3 + 18 * 24 * 32) \
        + 4 * 8 * 8 * 3 * 32


# The three LSTM Mosaic calls as the v5e trace names them (my chip run,
# PR 23, at batch 32 here): all under the flax scope's name.
FWD = ("%core.19 = (f32[101,32,256]{2,1,0:T(8,128)S(1)}, "
       "f32[101,32,1024]{2,1,0:T(8,128)}, f32[101,32,256]{2,1,0}, "
       "f32[32,256]{1,0}) custom-call(f32[101,32,266]{2,1,0} %x, "
       "f32[266,1024]{1,0} %wi), custom_call_target=\"tpu_custom_call\"")
BWD = ("%core.20 = (f32[101,32,266]{2,1,0:T(8,128)S(1)}, "
       "f32[266,1024]{1,0:T(8,128)S(1)}, f32[256,1024]{1,0}, "
       "f32[1,1024]{1,0}) custom-call(f32[101,32,256]{2,1,0} %dys, "
       "f32[101,32,1024]{2,1,0} %core.19), "
       "custom_call_target=\"tpu_custom_call\"")
LEAN = ("%core.21 = (f32[1,32,256]{2,1,0:T(8,128)S(1)}, f32[32,256]{1,0}, "
        "f32[32,256]{1,0}) custom-call(f32[1,32,266]{2,1,0} %x, "
        "f32[266,1024]{1,0} %wi), custom_call_target=\"tpu_custom_call\"")
GRADW = ("%pallas_conv0_gradw.2 = f32[192,32]{1,0} custom-call("
         "bf16[3237,19,25,48]{3,2,1,0} %pad.44)")
USES_GRADW = ("%fusion.7 = f32[8,8,3,32]{3,2,1,0} fusion(f32[192,32]{1,0} "
              "%pallas_conv0_gradw.2)")


def test_the_three_core_calls_are_told_apart_by_what_they_return():
    ctx = ctx_for()
    fwd = readers.roofline_module("lstm_fwd").matcher(ctx)
    bwd = readers.roofline_module("lstm_bwd").matcher(ctx)
    assert fwd(FWD) and not fwd(BWD) and not fwd(LEAN)
    assert bwd(BWD) and not bwd(FWD) and not bwd(LEAN)
    # the backward TAKES the gates; only the forward RETURNS them
    assert "f32[101,32,1024]" in BWD
    gradw = readers.roofline_module("stem_gradw").matcher(ctx)
    assert gradw(GRADW) and not gradw(USES_GRADW) and not gradw(FWD)


def test_which_bound_applies():
    peak = peaks.for_kind("TPU v5 lite")
    assert readers.least_seconds(197e12, 1.0, peak) == (1.0, "compute")
    assert readers.least_seconds(1.0, 819e9, peak) == (1.0, "memory")
    with pytest.raises(KeyError):
        peaks.for_kind("TPU v9 imaginary")


def trace_with_kernel(seconds_per_call, extra_lean_calls=0):
    events = []
    for i in range(3):
        base = 10.0 * i
        events.append(Event(DEV, MODULES_LINE, "jit_step(1)", base, 5.0))
        # the kernel shows as two events per call: both are summed
        half = seconds_per_call / 2
        events.append(Event(DEV, OPS_LINE, FWD, base + 1.0, half))
        events.append(Event(DEV, OPS_LINE, FWD, base + 2.0, half))
        for j in range(extra_lean_calls):
            events.append(Event(DEV, OPS_LINE, LEAN,
                                base + 3.0 + 0.01 * j, 1e-5))
    return events


def test_share_is_least_over_measured_per_call():
    ctx = ctx_for()
    counts = readers.roofline_module("lstm_fwd").least(ctx)
    least, bound = readers.least_seconds(
        counts["flops"], counts["bytes"], ctx.peak)
    ctx.events = trace_with_kernel(4 * least, extra_lean_calls=50)
    assert readers.roofline_share(ctx, "lstm_fwd") == pytest.approx(25.0)
    assert bound in ctx.notes[-1]


def test_counting_the_inference_calls_passes_100_percent():
    """The fault a by-name match makes, kept as a test: all three LSTM
    calls are named ``core.<n>``, so a match on the name counts the T=1
    inference calls as calls of the update's forward, the per-call time
    shrinks and the share passes 100%."""
    ctx = ctx_for()
    counts = readers.roofline_module("lstm_fwd").least(ctx)
    least, _ = readers.least_seconds(counts["flops"], counts["bytes"],
                                     ctx.peak)
    events = trace_with_kernel(1.25 * least, extra_lean_calls=50)
    naive = [e for e in events if e.name.startswith("%core.")]
    per_call_naive = sum(e.dur for e in naive) / (3 * (1 + 50))
    assert 100.0 * least / per_call_naive > 100.0
    ctx.events = events
    assert readers.roofline_share(ctx, "lstm_fwd") == pytest.approx(80.0)


def test_nothing_to_read_returns_nothing():
    ctx = ctx_for(events=[])
    assert readers.roofline_share(ctx, "lstm_fwd") is None
    ctx.events = [Event(DEV, MODULES_LINE, "jit_step(1)", 0.0, 5.0),
                  Event(DEV, OPS_LINE, "%fusion.1 = f32[1] fusion()",
                        1.0, 1.0)]
    assert readers.roofline_share(ctx, "lstm_fwd") is None


def test_model_flops_from_shapes():
    config = ctx_for().config
    parts = readers.forward_flops_per_step(config)
    assert parts["conv_0"] == 2 * 18 * 24 * 32 * 8 * 8 * 3
    assert parts["lstm"] == 2 * (266 + 256) * 1024
    forward = sum(v for k, v in parts.items() if k != "_stem")
    assert readers.train_flops_per_env_frame(config) == pytest.approx(
        (4 * forward - parts["conv_0"]) / 4)


def test_the_run_command_refuses_a_share_over_100(tmp_path):
    """The guard lives in run.py: a '%' reading over 100 exits non-zero
    and names the metric instead of printing it."""
    source = open(os.path.join(manifest.BENCH_DIR, "run.py")).read()
    assert 'metric.entry["unit"] == "%" and value > 100.0' in source
    assert "return 3" in source
    code = (
        "import sys, types; sys.path.insert(0, %r)\n"
        "import benchmark.run as run\n"
        "metric = types.SimpleNamespace(name='lstm_fwd_roofline.fused',"
        " entry={'unit': '%%'}, module=types.SimpleNamespace("
        "read=lambda ctx: 107.6))\n"
        "sys.exit(run.report_per_layer([metric], None, {}))\n"
        % manifest.ROOT)
    done = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True)
    assert done.returncode == 3
    assert "lstm_fwd_roofline.fused" in done.stderr
    assert done.stdout.strip() == ""


def test_mfu_is_model_flops_of_a_step_over_its_device_time():
    """From the trace, not from a host-clock rate: two chips each run
    the step in 0.5 s; the model's FLOPs for the step's frames over
    chips x step time x peak."""
    ctx = ctx_for()
    ctx.chips, ctx.frames_per_update = 2, 256 * 100 * 4.0
    other = "/device:TPU:1"
    ctx.events = [Event(dev, MODULES_LINE, "jit_step(1)", start, 0.5)
                  for dev in (DEV, other) for start in (1.0, 2.0, 3.0)]
    flops = readers.train_flops_per_env_frame(ctx.config) * 256 * 100 * 4.0
    assert readers.mfu(ctx) == pytest.approx(
        100.0 * flops / (2 * 0.5 * ctx.peak["flops_bf16"]))
    # a step twice as long on the device halves it; nothing else moves it
    ctx.events = [e._replace(dur=1.0) for e in ctx.events]
    assert readers.mfu(ctx) == pytest.approx(
        100.0 * flops / (2 * 1.0 * ctx.peak["flops_bf16"]))
    ctx.events = []
    assert readers.mfu(ctx) is None
