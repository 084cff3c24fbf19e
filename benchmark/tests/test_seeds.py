"""``benchmark/seeds.py`` reads what a run reads.

The tool re-seeds the cell's compiled step before every third dispatch
instead of paying a process per seed.  Its SECOND seed (the first that
starts from a copy of the step's first arguments) has to give the
compared numbers of ``benchmark/run.py --seed`` with that seed, and the
planted faults have to read as faults.  CPU, rehearsal sizes, two
subprocesses of ~30 s each: ``python -m pytest benchmark/tests``.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "shallow.ingraph.x4"      # several chips: every fault is read


def _run(*argv):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)   # both ask for the cell's device count
    done = subprocess.run([sys.executable, *argv], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    return done.stdout.splitlines()


def test_the_tools_second_seed_reads_what_a_run_of_that_seed_reads():
    lines = _run("benchmark/seeds.py", "--workload", CELL, "--rehearse",
                 "1", "--seeds", "8,7", "--faults", "1")
    rows = [json.loads(text[5:]) for text in lines
            if text.startswith("seed ")]
    assert [row["seed"] for row in rows] == [8, 7]

    run = json.loads(_run(
        "benchmark/run.py", "--workload", CELL, "--rehearse", "1",
        "--seed", "7", "--seconds", "2")[-1])
    for name, judged in run["compared"].items():
        assert rows[1]["compared"][name] == pytest.approx(
            judged["value"], rel=1e-6), name

    first = rows[0]
    assert set(first) >= {"control_fp8", "frozen", "half_batch",
                          "one_chip_share"}
    assert "frozen" not in rows[1]             # --faults 1
    assert first["frozen"]["delta_norm_gap"] == pytest.approx(1.0)
    assert first["half_batch"]["loss1_gap"] == pytest.approx(0.5, abs=0.05)
    assert first["one_chip_share"]["loss1_gap"] == pytest.approx(
        0.75, abs=0.05)
    assert (first["control_fp8"]["loss1_gap"]
            > 5 * first["compared"]["loss1_gap"])
