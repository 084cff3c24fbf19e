"""``kanana2.ingraph``'s own benchmark files: the two latent rooflines
against counts worked by hand at the cell's shapes, the limits file's
rows under its limits, and the three trace readers on a slice recorded
on the chip.  (The accepted files' tests are theirs; a cell's files are
added beside them.)

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

CELL = "kanana2.ingraph"
RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "recorded_v5e_latent_slice.json")
# the cell's shapes: 32 heads, rank 512, 128 + 64 a key, 128 a value
ABSORBED = 32 * (2 * 512 + 64)          # MACs a (query, live row)
SCORED = 32 * (128 + 64 + 128)          # the same, whole keys and values
UP = 512 * 32 * (128 + 128)             # MACs to up-project one row
ROW = 2 * (512 + 64)                    # bytes a row: 1,152


def ctx_at(update, monkeypatch, events=()):
    cell = manifest.load_cell(CELL)
    decode = readers.roofline_module("latent_decode")
    monkeypatch.setattr(decode, "traced_updates", lambda ctx: [update])
    return types.SimpleNamespace(
        config=cell.config, flags=manifest.driver_flags(cell), chips=1,
        traffic=cell.traffic, events=list(events),
        peak=peaks.for_kind("TPU v5 lite"), notes=[])


def test_the_live_rows_are_the_worlds_own_stagger(monkeypatch):
    """32 envs begin 320 tokens apart in episodes of 10,240 and every
    ring is empty at launch: before an env's first episode end a query
    finds the tokens since launch, after it the tokens of its episode."""
    decode = readers.roofline_module("latent_decode")
    ctx = ctx_at(0, monkeypatch)
    first = decode.live_rows(ctx, 0, [0, 255])
    assert first.shape == (32, 2)
    assert (first == [0, 255]).all()
    # update 40 begins at token 10,240: env i is 320 i tokens into the
    # episode it began at launch + 10,240 - 320 i
    steady = decode.live_rows(ctx, 40, [0, 256])
    assert steady[:, 0].tolist() == [320 * i for i in range(32)]
    assert steady[31, 1] == 9920 + 256
    # env 31's first episode ends 320 tokens after launch
    assert decode.live_rows(ctx, 1, [63, 64])[31].tolist() == [319, 0]


def test_latent_decode_by_hand(monkeypatch):
    """At update 0 query t of every env finds t rows: 32 x (0 + ... +
    255) = 1,044,480 (query, row) pairs a layer.  One query an env
    cannot share an up-projection, so the absorbed form is the lesser."""
    counts = readers.roofline_module("latent_decode").least(
        ctx_at(0, monkeypatch))
    rows = 32 * (255 * 256 // 2)
    assert rows == 1_044_480
    assert ABSORBED == 34_816 and ABSORBED < UP + SCORED
    assert counts["flops"] == 2 * 5 * rows * ABSORBED       # 3.64e11
    per_query = 32 * 256 * (2 * 32 * 576 + ROW + 4 * 32 * 128)
    assert counts["bytes"] == 5 * (ROW * rows + per_query)  # 1.01e10


def test_latent_update_by_hand_takes_the_lesser_form(monkeypatch):
    """Update 0: 257 queries an env, query t finds t rows; the 257 own
    rows up-projected cost more than they save, so absorbed.  Update 40
    (env i 320 i tokens into its episode): 41.8M pairs a layer against
    166,913 rows to up-project, and the up-projected form is the
    lesser, by 1.13e12 to 1.46e12 MACs a layer a pass."""
    update = readers.roofline_module("latent_update")
    first = update.least(ctx_at(0, monkeypatch))
    pairs = 32 * (256 * 257 // 2)
    assert pairs * ABSORBED < 32 * 257 * UP + pairs * SCORED
    assert first["flops"] == 2 * 5 * 2 * pairs * ABSORBED
    per_pass = ROW * 32 * 257 + 32 * 257 * (2 * 32 * 576 + 4 * 32 * 128)
    assert first["bytes"] == 5 * 2 * per_pass

    steady = update.least(ctx_at(40, monkeypatch))
    pairs = 257 * 320 * sum(range(32)) + 32 * (256 * 257 // 2)
    seen = 257 + sum(320 * i + 256 for i in range(1, 32))
    assert (pairs, seen) == (41_843_712, 166_913)
    up_projected = seen * UP + pairs * SCORED
    assert up_projected < pairs * ABSORBED
    assert steady["flops"] == 2 * 5 * 2 * up_projected      # 2.26e13
    assert steady["bytes"] == 5 * 2 * (
        ROW * seen + 32 * 257 * (2 * 32 * 576 + 4 * 32 * 128))


def test_the_traced_updates_come_from_the_counter_and_the_trace(monkeypatch):
    """38 updates dispatched and the harness keeps 2 in flight: the 38th
    was in flight when the closing retire stopped the trace, the 37th
    (index 36) is the newest whole run, and a trace that holds two whole
    runs holds the 36th too.  The work is the mean over them."""
    from scalable_agent_tpu.obs import get_registry

    decode = readers.roofline_module("latent_decode")
    monkeypatch.setattr(
        type(get_registry()), "snapshot",
        lambda self: {"devtel/learner/updates_total": 38.0})
    cell = manifest.load_cell(CELL)
    plane = "/device:TPU:0"

    def ctx_with(whole):
        # a run cut at its start, ``whole`` whole runs, a sliver
        spans = [(0.0, 1.0)] + [(1.0 + 1.7 * i, 1.7) for i in range(whole)]
        spans.append((1.0 + 1.7 * whole, 0.01))
        events = [Event(plane, MODULES_LINE, "jit__fused(1)", at, dur)
                  for at, dur in spans]
        events += [Event(plane, OPS_LINE, "%op = ...", at, dur)
                   for at, dur in spans]
        return types.SimpleNamespace(
            config=cell.config, flags=manifest.driver_flags(cell), chips=1,
            traffic=cell.traffic, events=events, notes=[],
            peak=peaks.for_kind("TPU v5 lite"))

    assert decode.traced_updates(ctx_with(1)) == [36]
    assert decode.traced_updates(ctx_with(2)) == [35, 36]
    two = decode.least(ctx_with(2))
    each = [decode.least_at(ctx_with(2), update)[0] for update in (35, 36)]
    assert two["bytes"] == pytest.approx(
        (each[0]["bytes"] + each[1]["bytes"]) / 2)
    assert each[0]["bytes"] < two["bytes"] < each[1]["bytes"]


def test_a_configuration_with_no_latent_cache_has_nothing_to_count(
        monkeypatch):
    ctx = ctx_at(3, monkeypatch)
    ctx.config = manifest.load_cell("trinity.ingraph").config
    assert readers.roofline_module("latent_decode").least(ctx) is None
    assert readers.roofline_module("latent_update").least(ctx) is None


def test_a_program_without_the_counter_has_nothing_to_count():
    """The parent: no ``devtel/learner/updates_total`` in this process's
    registry, so no update to work the live rows out for."""
    cell = manifest.load_cell(CELL)
    ctx = types.SimpleNamespace(
        config=cell.config, flags=manifest.driver_flags(cell), chips=1,
        traffic=cell.traffic, events=[], notes=[],
        peak=peaks.for_kind("TPU v5 lite"))
    for name in ("latent_decode", "latent_update"):
        assert readers.roofline_module(name).least(ctx) is None
    for name in ("latent_decode_roofline.fused",
                 "latent_update_roofline.fused",
                 "latent_attention_device_share.fused"):
        cell_metric = next(m for m in manifest.load_cell(CELL).per_layer
                           if m.name == name)
        assert cell_metric.module.read(ctx) is None


def test_the_chips_own_rows_under_the_cells_limits():
    """As ``test_correct.py`` holds the conv cells' files: the control
    and half the batch come out not correct on every seed read, the
    sound rows that hold a number's largest correct, and every number
    is failed by some fault.  The cell's own fault (the shared key left
    unrotated) is read beside them; PERF.md section 6 says what it
    failed."""
    data = manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "limits", CELL + ".json"))
    limits, readings = data["limits"], data["set_from"]["readings"]
    faults = ["control_fp8", "half_batch"]
    for kind in faults + ["no_rope_on_shared_key"]:
        assert len(readings[kind]) >= 3, kind
    for kind in faults:
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
    """(the recorded file, a ctx holding its one whole step run)."""
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
def test_the_readers_on_a_recorded_slice_of_the_cells_trace(monkeypatch):
    """One whole step run of a traced chip run of the cell, every op
    under ``attention/latent`` kept with its scope and the rest of the
    step as one op: the share and the two rooflines read what the
    recording says they read on the chip, each roofline under 100."""
    recorded, events = recorded_step()
    ctx = ctx_at(recorded["traced_update"], monkeypatch, events)
    ctx.op_scopes = recorded["op_scopes"]
    by_name = {m.name: m.module for m in manifest.load_cell(CELL).per_layer}
    for name, want in recorded["expect"].items():
        got = by_name[name].read(ctx)
        assert got == pytest.approx(want, rel=1e-6), name
        assert 0.0 < got < 100.0, name
