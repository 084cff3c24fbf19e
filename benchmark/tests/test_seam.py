"""A cell carries its reference module, and the harness asks the cell.

- ``benchmark/lib/reference.py`` is still the reference of the two
  IMPALA configurations, whose files name none, and what follows from
  it is the parent's to the bit (``golden_follow.json``: recorded from
  commit 601746c, before the first edit of PR 31).
- A configuration of another architecture lands as files: its
  configuration file (``"reference": "<name>"``), its reference module,
  its world, its FLOP count, a cell, a mix, limits, and a reader that
  asks for a scope's share, with no edit to anything that exists.
- The last line names the checks that failed, the comparisons with the
  reference first.

CPU only, tiny sizes, run by hand: ``python -m pytest benchmark/tests``.
"""

import json
import os
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, HERE)

from benchmark.lib import correct, manifest, readers, scopes  # noqa: E402
from benchmark.lib import reference as impala  # noqa: E402
from benchmark.lib.trace_reduce import MODULES_LINE  # noqa: E402
from test_correct import FPU, TINY, config  # noqa: E402
from test_manifest import copy_benchmark, digest  # noqa: E402
from test_scope_readers import (  # noqa: E402
    RECORDED,
    recorded_step,
    synthetic,
)

# what ``classify`` calls update.torso, as one pattern
TORSO = (r"^(?!.*\b(?:rollout|telemetry)\b)(?=.*\blearner_update\b)"
         r".*\bconvnet\b")

TOY_REFERENCE = '''
"""The default reference with the loss doubled, and its own FLOP count."""
from benchmark.lib import reference as _impala
from benchmark.lib.reference import *  # noqa: F401,F403


def loss_and_grads(cfg, params, batch, block, quant=None):
    value, grads = _impala.loss_and_grads(cfg, params, batch, block, quant)
    return 2.0 * value, grads


def train_flops_per_env_frame(cfg):
    return 1.0e6 * cfg["toy_layers"]
'''


# -- (a) the accepted configurations read as the parent read them -------------

@pytest.mark.parametrize("name", ["impala_shallow", "impala_deep"])
def test_the_default_reference_follows_as_the_parent_did(name):
    with open(os.path.join(HERE, "golden_follow.json")) as f:
        golden = json.load(f)[name]
    cfg = dict(config(name), reference_block=4)
    assert "reference" not in cfg           # the file names none
    ref = correct.follow(cfg, 11, FPU, fused=TINY)
    assert ref["losses"] == golden["losses"]
    for kind in ("grad_norms", "delta_norms"):
        got = {"/".join(path): value for path, value in ref[kind].items()}
        assert got == golden[kind], kind
    # and the module handed over by the cell is that same module
    named = correct.follow(cfg, 11, FPU, fused=TINY, reference=impala)
    assert named == ref
    same = correct.compare(named, ref)
    assert tuple(same) == correct.COMPARED and set(same.values()) == {0.0}


@pytest.mark.parametrize("cell", ["shallow.ingraph", "deep.ingraph",
                                  "shallow.ingraph.x4"])
def test_an_accepted_cell_gets_the_default_module(cell):
    loaded = manifest.load_cell(cell, with_readers=False)
    assert loaded.reference is None
    assert manifest.reference_module(loaded) is impala


# -- (b) a second architecture as files only ----------------------------------

def add_a_toy_architecture(root):
    bench = manifest.load_json(root / "BENCHMARK.json")
    cfg = manifest.load_json(root / "benchmark/configs/impala_shallow.json")
    cfg.update(name="toy", reference="toy", toy_layers=3)
    (root / "benchmark/configs/toy.json").write_text(json.dumps(cfg))
    os.makedirs(root / "benchmark/references", exist_ok=True)
    (root / "benchmark/references/toy.py").write_text(TOY_REFERENCE)
    mix = manifest.load_json(root / "benchmark/traffic/fused_fake72x96.json")
    mix["world"]["episode_length"] = 500       # the module's own world
    (root / "benchmark/traffic/fused_toy.json").write_text(json.dumps(mix))
    (root / "benchmark/limits/toy.ingraph.json").write_text(json.dumps(
        {"limits": {"loss1_gap": 0.01}, "set_from": {}}))
    (root / "benchmark/metrics/experts_device_share.fused.py").write_text(
        "from benchmark.lib import scopes\n"
        "def read(ctx):\n"
        "    return scopes.share_where(ctx, r'\\bconv_0\\b')\n")
    bench["configs"].append({
        "name": "toy", "source": "https://example.org/toy",
        "file": "benchmark/configs/toy.json", "reduced": [], "why": "toy"})
    bench["workloads"].append({
        "name": "toy.ingraph", "config": "toy", "traffic": "fused_toy",
        "chips": 1, "why": "another architecture, as files"})
    bench["per_layer"].append({
        "name": "experts_device_share.fused", "unit": "%",
        "better": "lower", "source": "device_trace", "layer": "fused step",
        "moves": "fused_env_frames_per_s", "workloads": ["toy.ingraph"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def test_a_second_architecture_is_added_as_files_only(tmp_path):
    root = copy_benchmark(tmp_path)
    before = digest(root)
    add_a_toy_architecture(root)

    cell = manifest.load_cell("toy.ingraph", root=str(root))
    assert cell.reference == str(root / "benchmark/references/toy.py")
    toy = manifest.reference_module(cell)
    assert toy is not impala and toy.make_weights is impala.make_weights

    # the comparison follows through the named module ...
    cfg = dict(cell.config, reference_block=4)
    fused = dict(TINY, world=cell.traffic["world"])
    mine = correct.follow(cfg, 11, FPU, fused=fused, reference=toy)
    default = correct.follow(cfg, 11, FPU, fused=fused)
    numbers = correct.compare(default, mine)
    assert numbers["loss1_gap"] == pytest.approx(0.5)
    rows = correct.judge(numbers, cell.limits)
    assert [row[0] for row in rows] == ["loss1_gap"] and not rows[0][3]
    # ... the FLOPs come from it, and its reader asks for its scope
    ctx = synthetic()
    ctx.config, ctx.reference, ctx.chips = cell.config, toy, 1
    ctx.frames_per_update, ctx.peak = 1000.0, {"flops_bf16": 1e12}
    step_s = 10.0                                   # synthetic()'s one run
    assert readers.mfu(ctx) == pytest.approx(
        100.0 * 3.0e6 * 1000.0 / (step_s * 1e12))
    (reader,) = [m.module for m in cell.per_layer
                 if m.name == "experts_device_share.fused"]
    assert reader.read(ctx) == pytest.approx(30.0)  # fusion.238, conv_0
    assert "device_mfu.fused" in [m.name for m in cell.per_layer]

    # the cells that were there load as they did, with the default module
    old = manifest.load_cell("shallow.ingraph", root=str(root))
    assert old.reference is None and old.config == config("impala_shallow")
    # and no file and no entry that existed was touched
    after = digest(root)
    assert all(after[key] == data for key, data in before.items())


def test_a_named_reference_that_is_not_there_is_an_error(tmp_path):
    root = copy_benchmark(tmp_path)
    add_a_toy_architecture(root)
    os.remove(root / "benchmark/references/toy.py")
    with pytest.raises(FileNotFoundError, match="toy"):
        manifest.load_cell("toy.ingraph", root=str(root))


# -- (c) what a module leaves out, the harness has ----------------------------

def test_the_first_gradient_is_the_modules_to_invert_or_rmsprops():
    cfg = config("impala_shallow")
    decay = cfg["optimizer"]["rmsprop_decay"]
    assert not hasattr(impala, "first_gradient_norms")
    start = impala.make_weights(cfg, 3)
    paths = sorted(start)
    nu1 = [decay + (1.0 - decay) * np.full(start[p].shape, 4.0, np.float32)
           for p in paths]
    params = impala.to_tree(start)
    default = correct.program_numbers(cfg, 3, paths, [1.0], nu1, params)
    for path in paths:
        assert default["grad_norms"][path] == pytest.approx(
            2.0 * np.sqrt(start[path].size), rel=1e-4)

    def first_gradient_norms(cfg, paths, opt_leaves):
        return {path: 7.0 for path in paths}

    adam = types.SimpleNamespace(
        make_weights=impala.make_weights, from_tree=impala.from_tree,
        first_gradient_norms=first_gradient_norms)
    mine = correct.program_numbers(cfg, 3, paths, [1.0], nu1, params,
                                   reference=adam)
    assert set(mine["grad_norms"].values()) == {7.0}
    assert mine["delta_norms"] == default["delta_norms"]


@pytest.mark.parametrize("module", [
    None, types.SimpleNamespace(), impala])
def test_a_module_without_a_flop_count_gets_the_harnesss(module):
    ctx = synthetic()
    ctx.config, ctx.chips = config("impala_shallow"), 1
    ctx.frames_per_update, ctx.peak = 1000.0, {"flops_bf16": 1e12}
    if module is not None:
        ctx.reference = module
    want = readers.train_flops_per_env_frame(ctx.config)
    assert want == 18523136.0          # the accepted count, as it was
    assert readers.mfu(ctx) == pytest.approx(
        100.0 * want * 1000.0 / (10.0 * 1e12))


def test_a_policy_without_a_conv_stem_is_noted_with_none():
    from benchmark.lib import probe as probe_lib

    probe = probe_lib.Probe(
        config={}, backend="ingraph", seed=1, seconds=1.0, trace=False,
        trace_seconds=0.0, trace_dir="", t_launch=0.0)
    assert probe.reference is impala
    learner = types.SimpleNamespace(
        _agent=types.SimpleNamespace(core_impl="scan", torso_type="mlp"),
        mesh=types.SimpleNamespace(devices=np.zeros((2, 2))))
    probe._note_learner(learner)
    assert probe.policy == {
        "core_impl": "scan", "conv_backend": None,
        "core_matmul_dtype": None, "remat_torso": None,
        "torso_type": "mlp", "mesh_devices": 4}


# -- (d) a scope's share, for a reader the seven classes do not serve ---------

@pytest.mark.parametrize("chips", [1, 4])
def test_share_where_reads_what_share_reads(chips):
    ctx = synthetic(chips)
    assert scopes.share_where(ctx, TORSO) == pytest.approx(
        scopes.share(ctx, "update.torso"))
    assert scopes.share_where(ctx, TORSO) == pytest.approx(30.0)
    assert scopes.share_where(ctx, r"\brollout\b") == pytest.approx(
        scopes.share(ctx, "rollout"))
    # a word inside another word is not the word, and nothing is 0.0
    assert scopes.share_where(ctx, r"\bconv\b") == 0.0
    assert scopes.share_where(ctx, r"\bexperts\b") == 0.0
    # the seven classes are what they were: with the gaps, the whole step
    found = scopes.shares(ctx)
    assert set(found) == set(scopes.CLASSES) and len(scopes.CLASSES) == 7
    assert sum(found.values()) == pytest.approx(90.0)   # 1 s of 10 is gaps


def test_share_where_has_nothing_to_read_without_a_table_or_a_run(
        monkeypatch):
    from benchmark.lib import timeline

    monkeypatch.setattr(timeline, "trace_path", lambda: None)
    ctx = synthetic()
    ctx.op_scopes = None
    assert scopes.share_where(ctx, TORSO) is None
    ctx = synthetic()
    ctx.events = [e for e in ctx.events if e.line != MODULES_LINE]
    assert scopes.share_where(ctx, TORSO) is None


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded step beside the test")
def test_share_where_on_one_recorded_step_of_a_real_v5e_trace():
    recorded, ctx = recorded_step()
    assert scopes.share_where(ctx, TORSO) == pytest.approx(
        recorded["expect"]["update.torso"], abs=1e-6)
    found = scopes.shares(ctx)
    assert 99.0 < sum(found.values()) <= 100.0 + 1e-9


# -- (e) the last line says what failed ---------------------------------------

PASSED = [("window_updates_min", 40, 2, True), ("loss_gap", 0.1, 0.24, True),
          ("loss1_gap", 0.002, 0.025, True)]
NUMBERS = dict.fromkeys(correct.COMPARED, 0.0)


@pytest.mark.parametrize("checks, want", [
    (PASSED, {}),
    ([("window_updates_min", 1, 2, False),
      ("delta_norm_gap", 1.0, 0.27, False), ("loss_gap", 0.1, 0.24, True),
      ("loss1_gap", 0.5, 0.025, False),
      ("leftover_processes", 1, 0, False)],
     ["loss1_gap", "delta_norm_gap", "window_updates_min",
      "leftover_processes"]),
    ([("loss_gap", float("nan"), 0.24, False)], ["loss_gap"]),
])
def test_checks_failed_names_the_reference_comparisons_first(checks, want):
    import benchmark.run as run

    failed = run.checks_failed(checks, NUMBERS)
    assert list(failed) == list(want)
    for name, value, limit, _ in checks:
        if name in failed:
            assert failed[name]["limit"] == limit
            assert failed[name]["value"] == (
                "nan" if value != value else value)
    json.loads(json.dumps(failed, allow_nan=False))


def _rehearse(capfd, seed):
    import benchmark.run as run

    rc = run.main(["--workload", "shallow.ingraph", "--seed", str(seed),
                   "--seconds", "2", "--trace", "0", "--rehearse", "1"])
    out, err = capfd.readouterr()
    lines = [line for line in out.splitlines() if line]
    failed = [text.split(":")[0][len("check "):] for text in lines
              if text.startswith("check ") and text.endswith("FAILED")]
    return rc, json.loads(lines[-1]), failed, err.strip().splitlines()


def test_the_last_line_names_what_a_broken_step_fails(capfd, monkeypatch):
    """``test_correct.test_harness_sees_a_broken_step``'s two runs, read
    for what the last line now says: every key it had, then
    ``checks_failed`` and, last, each number compared beside its limit
    (the last lines of stderr hold them too)."""
    limits = manifest.load_cell("shallow.ingraph", with_readers=False).limits
    compared = sorted(limits)
    rc, line, failed, err = _rehearse(capfd, 21)
    assert rc == 0 and line["device"]["platform"] == "cpu"
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-2:] == ["checks_failed", "compared"]
    assert sorted(line["checks_failed"]) == sorted(failed)
    assert line["correct"] is (line["checks_failed"] == {})
    assert sorted(line["compared"]) == compared
    assert [text.split(":")[0] for text in err[-4:]] == [
        "compared " + name for name in line["compared"]]

    from scalable_agent_tpu.runtime import learner

    monkeypatch.setattr(learner.optax, "apply_updates",
                        lambda params, updates: params)
    rc, line, failed, err = _rehearse(capfd, 21)
    assert rc == 0 and line["correct"] is False
    assert sorted(line["checks_failed"]) == sorted(failed)
    assert list(line["checks_failed"])[0] in compared   # the reference's first
    assert line["checks_failed"]["delta_norm_gap"]["value"] == \
        pytest.approx(1.0)
    assert {name: row["limit"] for name, row in line["compared"].items()} \
        == limits
