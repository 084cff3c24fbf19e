"""``olmohybrid.ingraph``'s own benchmark files: the two scan rooflines
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

CELL = "olmohybrid.ingraph"
RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "recorded_v5e_gdn_slice.json")
# the cell's shapes: 8 envs, 30 heads with keys of 96 and values of 192,
# chunks of 128 tokens, 3 delta-rule layers
ENVS = 8
STATE = ENVS * 30 * 192 * 96            # numbers of one layer's states


def ctx_of(config=None, events=()):
    cell = manifest.load_cell(CELL)
    return types.SimpleNamespace(
        config=config or cell.config, flags=manifest.driver_flags(cell),
        chips=1, traffic=cell.traffic, events=list(events),
        peak=peaks.for_kind("TPU v5 lite"), notes=[])


def test_the_cell_runs_eight_envs():
    assert ctx_of().flags["batch_size"] == ENVS
    assert ctx_of().flags["preemption_grace_s"] == 120


def test_gdn_decode_by_hand():
    """256 tokens a step, 3 layers: each env's 2.1 MiB state read and
    written once a token a layer is 106 MB a token, 27 GB a step."""
    counts = readers.roofline_module("gdn_decode").least(ctx_of())
    assert 3 * STATE * 4 * 2 == 106_168_320
    small = ENVS * 30 * (2 * 96 + 2 * 192 + 2)
    assert counts["bytes"] == 3 * 256 * 4.0 * (2 * STATE + small)
    assert counts["flops"] == 3 * 256 * 6.0 * STATE
    _, bound = readers.least_seconds(
        counts["flops"], counts["bytes"], ctx_of().peak)
    assert bound == "memory"


def test_gdn_scan_by_hand():
    """257 tokens, two WHOLE chunks of 128: q and k are 5.9M numbers
    each, v and o 11.8M, the chunk-start states 2 x 4.4M a pass, and a
    token a head costs the recurrence's own three products of 2 x 192 x
    96 flops a pass, three passes: no chunk size is in the count."""
    counts = readers.roofline_module("gdn_scan").least(ctx_of())
    per_key, per_value = ENVS * 257 * 30 * 96, ENVS * 257 * 30 * 192
    per_head = ENVS * 257 * 30
    forward = 4.0 * (2 * per_key + 2 * per_value + 2 * per_head + 4 * STATE)
    backward = 4.0 * (4 * per_key + 3 * per_value + 4 * per_head
                      + 4 * STATE)
    assert counts["bytes"] == 3 * (forward + backward)
    a_pass = ENVS * 257 * 30 * 3 * 2.0 * 192 * 96
    assert counts["flops"] == 3 * 3.0 * a_pass
    _, bound = readers.least_seconds(counts["flops"], counts["bytes"],
                                     ctx_of().peak)
    assert bound == "memory"
    smaller = ctx_of(config=dict(ctx_of().config, chunk_size=64))
    assert readers.roofline_module("gdn_scan").least(smaller)[
        "flops"] == counts["flops"]


def test_a_configuration_without_the_scan_reads_nothing():
    """On another cell's configuration (no delta-rule layer) both
    rooflines have nothing to count, and on a ctx with no trace the
    readers return None and do not raise."""
    other = manifest.load_cell("nemotron3.ingraph")
    ctx = ctx_of(config=other.config)
    for name in ("gdn_scan", "gdn_decode"):
        assert readers.roofline_module(name).least(ctx) is None
    by_name = {m.name: m.module for m in manifest.load_cell(CELL).per_layer}
    for name in ("gdn_scan_roofline.fused", "gdn_decode_roofline.fused",
                 "gdn_device_share.fused"):
        assert by_name[name].read(ctx_of()) is None


@pytest.mark.parametrize("op_name,update,decode", [
    ("jit(_fused)/while/body/learner/TokenPolicy/layer_0/gdn/scan/"
     "pallas_gdn_fwd/pallas_call", True, False),
    ("jit(_fused)/while/body/rollout/while/body/actor_inference/"
     "TokenPolicy/layer_2/gdn/scan/mul", False, True),
    ("jit(_fused)/while/body/learner/TokenPolicy/layer_0/gdn/conv/mul",
     False, False),
    ("jit(_fused)/while/body/learner/TokenPolicy/layer_0/ssd/scan/x",
     False, False),
    (None, False, False),
])
def test_the_scopes_the_two_rooflines_read(op_name, update, decode):
    assert readers.roofline_module("gdn_scan").in_update(op_name) is update
    assert readers.roofline_module("gdn_decode").in_update(op_name) is decode


def test_the_chips_own_rows_under_the_cells_limits():
    """As ``test_correct.py`` holds the conv cells' files: the control
    and half the batch come out not correct on every seed read, the
    sound rows that hold a number's largest correct, and every number
    is failed by some fault.  The cell's own fault (the update's scans
    never reading the state against the key) is read beside them and
    fails too."""
    data = manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "limits", CELL + ".json"))
    limits, readings = data["limits"], data["set_from"]["readings"]
    faults = ["control_fp8", "half_batch", "no_delta"]
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
    under ``gdn`` kept with its scope and the rest of the step as one
    op: the share and the two rooflines read what the recording says
    they read, each roofline under 100, and the kernels are there by
    name under ``gdn/scan``."""
    recorded, events = recorded_step()
    ctx = ctx_of(events=events)
    ctx.op_scopes = recorded["op_scopes"]
    by_name = {m.name: m.module for m in manifest.load_cell(CELL).per_layer}
    for name, want in recorded["expect"].items():
        got = by_name[name].read(ctx)
        assert got == pytest.approx(want, rel=1e-6), name
        assert 0.0 < got < 100.0, name
    kernels = [scope for scope in recorded["op_scopes"].values()
               if "pallas_gdn" in scope]
    assert any("pallas_gdn_fwd" in scope for scope in kernels)
    assert any("pallas_gdn_bwd" in scope for scope in kernels)
    assert all("/gdn/scan/" in scope for scope in kernels)
