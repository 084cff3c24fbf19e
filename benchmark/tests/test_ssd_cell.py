"""``nemotron3.ingraph``'s own benchmark files: the two scan rooflines
against counts worked by hand at the cell's shapes, the limits file's
rows under its limits, and the trace readers on a slice recorded on the
chip.  (The accepted files' tests are theirs; a cell's files are added
beside them.)

CPU only, run by hand: ``python -m pytest benchmark/tests``.
"""

import json
import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib import correct, manifest, peaks, readers  # noqa: E402
from benchmark.lib.trace_reduce import (  # noqa: E402
    MODULES_LINE,
    OPS_LINE,
    Event,
)

CELL = "nemotron3.ingraph"
RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "recorded_v5e_ssd_slice.json")
# the cell's shapes: 32 envs, 64 heads of 64 channels, 8 groups of 128
# states, chunks of 128 tokens, 4 scan layers
STATE = 32 * 64 * 64 * 128              # numbers of one layer's states


def ctx_of(config=None, events=()):
    cell = manifest.load_cell(CELL)
    return types.SimpleNamespace(
        config=config or cell.config, flags=manifest.driver_flags(cell),
        chips=1, traffic=cell.traffic, events=list(events),
        peak=peaks.for_kind("TPU v5 lite"), notes=[])


def test_ssd_decode_by_hand():
    """256 tokens a step, 4 layers: each env's 2 MiB state read and
    written once a token a layer is 0.537 GB a token, 137 GB a step."""
    counts = readers.roofline_module("ssd_decode").least(ctx_of())
    assert 4 * STATE * 4 * 2 == 536_870_912
    small = 32 * (2 * 4096 + 64 + 2 * 1024)
    assert counts["bytes"] == 4 * 256 * 4.0 * (2 * STATE + small)
    assert counts["flops"] == 4 * 256 * 4.0 * STATE
    least_s, bound = readers.least_seconds(
        counts["flops"], counts["bytes"], ctx_of().peak)
    assert bound == "memory"


def test_ssd_scan_by_hand():
    """257 tokens in three chunks of 128: x, y, d y and d x are 33.7M
    numbers each, the chunk-start states 3 x 16.8M a pass, and a token a
    head costs 2 x 128 x 64 + 4 x 128 x 64 + 2 x 128 x 128 / 8
    multiply-adds' flops a pass, three passes."""
    counts = readers.roofline_module("ssd_scan").least(ctx_of())
    per_token, per_head = 32 * 257 * 4096, 32 * 257 * 64
    per_group = 32 * 257 * 1024
    forward = 4.0 * (2 * per_token + per_head + 2 * per_group + 5 * STATE)
    backward = 4.0 * (3 * per_token + 2 * per_head + 4 * per_group
                      + 5 * STATE)
    assert counts["bytes"] == 4 * (forward + backward)
    a_pass = 32 * 257 * 64 * (2.0 * 128 * 64 + 4.0 * 128 * 64
                              + 2.0 * 128 * 128 / 8)
    assert counts["flops"] == 4 * 3.0 * a_pass
    _, bound = readers.least_seconds(counts["flops"], counts["bytes"],
                                     ctx_of().peak)
    assert bound == "memory"


def test_a_configuration_without_the_scan_reads_nothing():
    """On another cell's configuration (no ``mamba_num_heads``) both
    rooflines have nothing to count, and on a ctx with no trace the
    readers return None and do not raise."""
    other = manifest.load_cell("kanana2.ingraph")
    ctx = ctx_of(config=other.config)
    for name in ("ssd_scan", "ssd_decode"):
        assert readers.roofline_module(name).least(ctx) is None
    by_name = {m.name: m.module for m in manifest.load_cell(CELL).per_layer}
    for name in ("ssd_scan_roofline.fused", "ssd_decode_roofline.fused",
                 "ssd_device_share.fused"):
        assert by_name[name].read(ctx_of()) is None


@pytest.mark.parametrize("op_name,update,decode", [
    ("jit(_fused)/while/body/learner/TokenPolicy/layer_0/ssd/scan/"
     "pallas_ssd_fwd/pallas_call", True, False),
    ("jit(_fused)/while/body/rollout/while/body/actor_inference/"
     "TokenPolicy/layer_2/ssd/scan/mul", False, True),
    ("jit(_fused)/while/body/learner/TokenPolicy/layer_0/ssd/conv/mul",
     False, False),
    ("jit(_fused)/while/body/learner/TokenPolicy/layer_0/ssm/scan/x",
     False, False),
    (None, False, False),
])
def test_the_scopes_the_two_rooflines_read(op_name, update, decode):
    assert readers.roofline_module("ssd_scan").in_update(op_name) is update
    assert readers.roofline_module("ssd_decode").in_update(op_name) is decode


def test_the_chips_own_rows_under_the_cells_limits():
    """As ``test_correct.py`` holds the conv cells' files: the control
    and half the batch come out not correct on every seed read, the
    sound rows that hold a number's largest correct, and every number
    is failed by some fault.  The cell's own fault (the update's scan
    starting every chunk from a zero state) is read beside them and
    fails too."""
    data = manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "limits", CELL + ".json"))
    limits, readings = data["limits"], data["set_from"]["readings"]
    faults = ["control_fp8", "half_batch", "zero_chunk_state"]
    for kind in faults:
        assert len(readings[kind]) >= 3, kind
        for row in readings[kind]:
            assert not all(ok for *_, ok in correct.judge(row, limits)), (
                kind, row)
    assert len(readings["sound_largest_rows"]) >= 12
    for row in readings["sound_largest_rows"]:
        assert all(ok for *_, ok in correct.judge(row, limits)), row
    for number in limits:
        assert any(row[number] > limits[number]
                   for kind in faults for row in readings[kind]), number


def recorded_step():
    """(the recorded file, a ctx's events holding its one whole step
    run)."""
    with open(RECORDED) as f:
        recorded = json.load(f)
    plane = recorded["plane"]
    name, start, dur = recorded["run"]
    events = [Event(plane, MODULES_LINE, name, start * 1e-9, dur * 1e-9)]
    events += [Event(plane, OPS_LINE, recorded["names"][i], s * 1e-9,
                     d * 1e-9) for i, s, d in recorded["ops"]]
    events += [Event(plane, OPS_LINE, "%neighbour = ...", at, 1e-9)
               for at in (-1e-6, (start + dur) * 1e-9 + 1e-6)]
    return recorded, events


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded slice beside the test")
def test_the_readers_on_a_recorded_slice_of_the_cells_trace():
    """One whole step run of a traced chip run of the cell, every op
    under ``ssd`` kept with its scope and the rest of the step as one
    op: the share and the two rooflines read what the recording says
    they read, each roofline under 100, and the kernels are there by
    name under ``ssd/scan``."""
    recorded, events = recorded_step()
    ctx = ctx_of(events=events)
    ctx.op_scopes = recorded["op_scopes"]
    by_name = {m.name: m.module for m in manifest.load_cell(CELL).per_layer}
    for name, want in recorded["expect"].items():
        got = by_name[name].read(ctx)
        assert got == pytest.approx(want, rel=1e-6), name
        assert 0.0 < got < 100.0, name
    kernels = [scope for scope in recorded["op_scopes"].values()
               if "pallas_ssd" in scope]
    assert any("pallas_ssd_fwd" in scope for scope in kernels)
    assert any("pallas_ssd_bwd" in scope for scope in kernels)
    assert all("/ssd/scan/" in scope for scope in kernels)
