"""The comparison that decides ``correct``, shown to fail.

- The control: the reference at the nearest precision below the
  configuration's bfloat16 (fp8 operands), put in the program's place,
  comes out NOT correct under the cell's own limits
  (``benchmark/limits/<cell>.json``): computed here at a size a
  test can hold, and by every row the chip read at the
  cell's own size (the limits file keeps them).
- The timed path broken underneath (an optimizer step that hands the
  parameters back unchanged): the harness, driven past its look for a
  chip, reports ``correct`` false.

CPU only, tiny sizes, run by hand: ``python -m pytest benchmark/tests``.
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib import correct, manifest  # noqa: E402

TINY = {"world": {"episode_length": 1000}, "batch": 8,
        "unroll_length": 12, "program_seed": 5}
FPU = 8 * 12 * 4.0
# fp8's gap grows with the rows a sum runs over (the cells': 256 x 100
# and more).  (batch, unroll, reference_block) from which on it fails
# the cell's limits, in seconds of a CPU.
CONTROL_SIZE = {"impala_shallow": (32, 100, 16),
                "impala_deep": (16, 50, 8)}
CELLS = ("shallow.ingraph", "shallow.ingraph.x4", "deep.ingraph")


def config(name):
    return manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "configs", name + ".json"))


def limits_file(cell):
    return manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "limits", cell + ".json"))


def limits_of(cell):
    return limits_file(cell)["limits"]


@pytest.mark.parametrize("name, cell", [
    ("impala_shallow", "shallow.ingraph"),
    ("impala_shallow", "shallow.ingraph.x4"),
    ("impala_deep", "deep.ingraph")])
def test_reference_is_deterministic_and_the_control_fails(name, cell):
    cfg = dict(config(name), reference_block=4)
    limits = limits_of(cell)
    ref = correct.follow(cfg, 11, FPU, fused=TINY)
    again = correct.follow(cfg, 11, FPU, fused=TINY)
    same = correct.compare(again, ref)
    assert all(row[3] for row in correct.judge(same, limits))
    assert same["loss_gap"] == 0.0

    batch, unroll, block = CONTROL_SIZE[name]
    cfg = dict(cfg, reference_block=block)
    sized = dict(TINY, batch=batch, unroll_length=unroll)
    frames = batch * unroll * 4.0
    ref = correct.follow(cfg, 11, frames, fused=sized)
    control = correct.follow(cfg, 11, frames, fused=sized, quant="fp8")
    rows = correct.judge(correct.compare(control, ref), limits)
    assert not all(row[3] for row in rows), rows


@pytest.mark.parametrize("cell", CELLS)
def test_the_chips_own_rows_under_the_cells_limits(cell):
    """At the cell's own size, on the chip: the control, the step that
    hands its state back and part of the batch left out each come out
    not correct on every seed read, and the sound rows that hold a
    number's largest come out correct."""
    limits = limits_of(cell)
    readings = limits_file(cell)["set_from"]["readings"]
    faults = ["control_fp8", "frozen_step", "half_batch"]
    if cell.endswith(".x4"):
        faults.append("one_chip_share")    # the exchange left out
    for kind in faults:
        assert len(readings[kind]) >= 3, kind
        for row in readings[kind]:
            judged = correct.judge(row, limits)
            assert not all(ok for *_, ok in judged), (kind, row)
    assert len(readings["sound_largest_rows"]) >= 3
    for row in readings["sound_largest_rows"]:
        judged = correct.judge(row, limits)
        assert all(ok for *_, ok in judged), row
    # every number the cell compares is failed by some fault
    for number in limits:
        assert any(row[number] > limits[number]
                   for kind in faults for row in readings[kind]), number


def test_a_step_that_changes_nothing_reads_a_gap_of_one():
    cfg = dict(config("impala_shallow"), reference_block=4)
    ref = correct.follow(cfg, 3, FPU, fused=TINY)
    frozen = dict(ref, delta_norms={k: 0.0 for k in ref["delta_norms"]})
    numbers = correct.compare(frozen, ref)
    assert numbers["delta_norm_gap"] == pytest.approx(1.0)
    for cell in CELLS:
        assert numbers["delta_norm_gap"] > 2 * limits_of(cell)[
            "delta_norm_gap"]


def test_part_of_the_batch_left_out_moves_the_loss():
    cfg = dict(config("impala_shallow"), reference_block=4)
    ref = correct.follow(cfg, 3, FPU, fused=TINY)
    half = correct.follow(cfg, 3, FPU / 2, fused=dict(TINY, batch=4))
    numbers = correct.compare(half, ref)
    assert numbers["loss1_gap"] > 10 * limits_of("shallow.ingraph")[
        "loss1_gap"]
    # and every leaf's gradient with it: the median leaf's too
    for cell in CELLS:
        assert numbers["grad_median_gap"] > 2 * limits_of(cell)[
            "grad_median_gap"]


def _run_cell(capsys, extra=()):
    import benchmark.run as run

    rc = run.main(["--workload", "shallow.ingraph", "--seed", "21",
                   "--seconds", "2", "--trace", "0", "--rehearse", "1",
                   *extra])
    lines = [line for line in capsys.readouterr().out.splitlines() if line]
    return rc, json.loads(lines[-1]), lines


def test_harness_sees_a_broken_step(capsys, monkeypatch):
    """Past the look for a chip (``--rehearse 1``) the rest of a run is
    the real one.  Sound first, then with the optimizer step handing
    the parameters back unchanged."""
    rc, line, lines = _run_cell(capsys)
    assert rc == 0
    assert line["device"]["platform"] == "cpu" and line["metrics"] == {}
    # (the limits are set at the cells' own sizes; at this tiny size the
    # gradient worked out of the optimizer state is noisy, so the sound
    # run is held to the rows the broken run must fail)
    failed = [text.split(":")[0] for text in lines
              if text.startswith("check ") and text.endswith("FAILED")]
    assert "check delta_norm_gap" not in failed
    assert set(failed) <= {"check grad_median_gap", "check loss1_gap"}

    from scalable_agent_tpu.runtime import learner

    monkeypatch.setattr(learner.optax, "apply_updates",
                        lambda params, updates: params)
    rc, line, lines = _run_cell(capsys)
    assert rc == 0 and line["correct"] is False
    assert any(text.startswith("check delta_norm_gap")
               and text.endswith("FAILED") for text in lines)
