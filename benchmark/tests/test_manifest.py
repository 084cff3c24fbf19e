"""A cell, a traffic mix and a per-layer metric added as NEW files plus
NEW entries, with no edit to a file or an entry that exists."""

import json
import os
import shutil
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib import correct, manifest  # noqa: E402

LISTS = ("configs", "workloads", "end_to_end", "per_layer")


def copy_benchmark(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(manifest.BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), root)
    return root


def digest(root):
    """Every file under benchmark/, and every entry BENCHMARK.json has."""
    out = {}
    for folder, _, files in os.walk(root / "benchmark"):
        for name in files:
            path = os.path.join(folder, name)
            out[path] = open(path, "rb").read()
    bench = manifest.load_json(root / "BENCHMARK.json")
    for key in LISTS:
        for entry in bench[key]:
            out[(key, entry["name"])] = json.dumps(entry, sort_keys=True)
    for key in set(bench) - set(LISTS):
        out[key] = json.dumps(bench[key])
    return out


def test_every_cell_loads_with_its_files():
    bench = manifest.load_benchmark()
    for entry in bench["workloads"]:
        cell = manifest.load_cell(entry["name"])
        names = [m.name for m in cell.end_to_end]
        assert sorted(names) == sorted(
            manifest.EVERY_CELL + (cell.traffic["rate_metric"],))
        assert cell.per_layer, entry["name"]
        # held to the first step's loss, the median leaf's first
        # gradient, and the later steps' loss and worst leaf's change
        assert set(cell.limits) == {"loss1_gap", "loss_gap",
                                    "grad_median_gap", "delta_norm_gap"}
        assert set(cell.limits) <= set(correct.COMPARED)
        flags = manifest.driver_flags(cell)
        assert flags["batch_size"] and flags["unroll_length"] == 100
        for metric in cell.per_layer:
            assert callable(metric.module.read)
            assert not hasattr(metric.module, "META")   # one copy: the entry


def test_limits_follow_their_own_cells_readings():
    """Each limit stands between its two readings (PERF.md, PR 31): over
    the largest that sound runs gave, by at least 1.5 times, and under
    the upper reading the file names — the control's smallest, or the
    smallest of a planted fault — which is itself three times the
    lower or more.  The control fails at least one number of the cell.
    The pending host-loop cell's file still has PR 23's rule."""
    limits_dir = os.path.join(manifest.BENCH_DIR, manifest.LIMITS_DIR)
    for name in sorted(os.listdir(limits_dir)):
        data = manifest.load_json(os.path.join(limits_dir, name))
        readings = data["set_from"]
        if "rule" not in readings:                 # PR 23's
            for number, limit in data["limits"].items():
                sound = readings[number]["sound_largest"]
                control = readings[number]["control_fp8_smallest"]
                if number in ("loss1_gap", "grad_norm_gap"):
                    assert 1.5 * sound <= limit <= control / 1.1, (
                        name, number)
                else:
                    assert 2.5 * sound <= limit <= 3.1 * sound, (
                        name, number)
            continue
        assert readings["sound_seeds"] >= 12, name
        for number, limit in data["limits"].items():
            read = readings[number]
            lower = read["sound_largest"]
            upper = read[read["upper_reading"] + "_smallest"]
            assert upper >= 3 * lower, (name, number)
            assert 1.5 * lower <= limit <= upper / 1.2, (name, number)
            # and the more of the room above the lower
            assert limit / lower >= upper / limit, (name, number)
        assert any(readings[number]["control_fp8_smallest"] > limit
                   for number, limit in data["limits"].items()), name
        # the readings named are those of the chip's rows the file keeps
        # (PR 23's, which it does not keep, may lie beyond them)
        for kind, rows in readings["readings"].items():
            if kind == "sound_largest_rows":
                for number in data["limits"]:
                    assert readings[number]["sound_largest"] >= max(
                        row[number] for row in rows), (name, number)
                continue
            assert len(rows) >= 3, (name, kind)
            for number in data["limits"]:
                assert readings[number].get(
                    kind + "_smallest", 0.0) <= min(
                        row[number] for row in rows), (name, kind, number)


def add_a_fused_cell(root):
    """What a model_config or perf_opt PR may do: new files, new entries."""
    mix = manifest.load_json(root / "benchmark/traffic/fused_fake72x96.json")
    mix["flags"]["updates_per_dispatch"] = 8
    (root / "benchmark/traffic/fused_fake72x96_k8.json").write_text(
        json.dumps(mix))
    shutil.copy(root / "benchmark/limits/shallow.ingraph.json",
                root / "benchmark/limits/shallow.ingraph.k8.json")
    (root / "benchmark/metrics/dispatch_gap_ms.py").write_text(
        "from benchmark.lib import readers\n"
        "def read(ctx):\n"
        "    return readers.span_median_ms(ctx, 'learner/train_step')\n")
    bench = manifest.load_json(root / "BENCHMARK.json")
    bench["workloads"].append({
        "name": "shallow.ingraph.k8", "config": "impala_shallow",
        "traffic": "fused_fake72x96_k8", "chips": 1,
        "why": "eight updates to a dispatch"})
    bench["per_layer"].append({
        "name": "dispatch_gap_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "fused step",
        "moves": "fused_env_frames_per_s",
        "workloads": ["shallow.ingraph.k8"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def add_the_pending_host_cell(root):
    """What only a benchmark PR may do (a new end-to-end metric), and
    still with no edit: the entries kept in benchmark/pending/."""
    pending = manifest.load_json(
        root / "benchmark/pending/shallow.hostloop.json")
    bench = manifest.load_json(root / "BENCHMARK.json")
    bench["workloads"].append(pending["workload"])
    bench["end_to_end"] += pending["end_to_end"]
    bench["per_layer"] += pending["per_layer"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


@pytest.mark.parametrize("add, new, has, lacks", [
    (add_a_fused_cell, "shallow.ingraph.k8",
     ["dispatch_gap_ms", "fused_step_device_ms", "device_mfu.fused"],
     ["env_step_ms", "lstm_fwd_roofline.fused"]),
    (add_the_pending_host_cell, "shallow.hostloop",
     ["env_step_ms", "device_idle_share.host", "host_block_rate_median"],
     ["fused_step_device_ms", "device_mfu.fused"]),
])
def test_a_cell_a_mix_and_a_metric_are_added_as_files_only(
        tmp_path, add, new, has, lacks):
    root = copy_benchmark(tmp_path)
    before = digest(root)
    add(root)

    cell = manifest.load_cell(new, root=str(root))
    rate = cell.traffic["rate_metric"]
    # the cell's rate comes with its traffic file: no end-to-end entry
    # was edited to list it
    assert sorted(m.name for m in cell.end_to_end) == sorted(
        manifest.EVERY_CELL + (rate,))
    names = [m.name for m in cell.per_layer]
    # metrics without a workloads key follow the end-to-end metric they
    # move: the new cell reports them with no edit anywhere
    assert set(has) <= set(names) and not set(lacks) & set(names)
    assert all(callable(m.module.read) for m in cell.per_layer)
    # the cells that were there report what they did
    old = manifest.load_cell("shallow.ingraph", root=str(root))
    assert [m.name for m in old.per_layer] == [
        m.name for m in manifest.load_cell("shallow.ingraph").per_layer]
    assert [m.name for m in old.end_to_end] == [
        "setup_s", "fused_env_frames_per_s", "peak_hbm_gib"]
    # and no file and no entry that existed was touched
    after = digest(root)
    assert all(after[key] == data for key, data in before.items())


def test_contract_shape_of_benchmark_json():
    bench = manifest.load_benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(bench["workloads"]) // 4)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    for metric in bench["end_to_end"]:
        assert 0.01 <= metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
    for metric in bench["per_layer"]:
        assert metric["moves"] in e2e
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        assert os.path.exists(os.path.join(
            manifest.BENCH_DIR, manifest.METRICS_DIR,
            metric["name"] + ".py"))
    for cell in bench["workloads"]:
        assert len(cell["why"]) <= 200
    assert len(json.dumps(bench)) < 64 * 1024
