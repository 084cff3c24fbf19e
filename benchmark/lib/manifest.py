"""BENCHMARK.json and the data files it names: one cell -> what to run.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own under ``benchmark/``, found by
the name ``BENCHMARK.json`` uses.  A later PR adds a cell by adding
files and entries; nothing here knows a cell, a configuration or a
metric by name.  A configuration file may name its plain reference
(``"reference": "<name>"`` -> ``benchmark/references/<name>.py``); one
that names none gets ``benchmark/lib/reference.py``.
"""

import importlib
import importlib.util
import json
import os
from typing import Any, Dict, List, NamedTuple, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

TRAFFIC_DIR = "traffic"
METRICS_DIR = "metrics"
ROOFLINES_DIR = "rooflines"
LIMITS_DIR = "limits"
REFERENCES_DIR = "references"
DEFAULT_REFERENCE = "benchmark.lib.reference"
_REFERENCES: Dict[str, Any] = {}     # path -> module, loaded once a process

# End-to-end metrics the harness takes in every cell.  The one other is
# the cell's rate, which its traffic file names (``rate_metric``).
EVERY_CELL = ("setup_s", "peak_hbm_gib")


class Metric(NamedTuple):
    name: str
    entry: Dict[str, Any]     # the BENCHMARK.json entry
    module: Any               # the reader module (per-layer) or None


class Cell(NamedTuple):
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]    # the configuration file, whole
    reference: Optional[str]  # the file's ``reference``: the module's path
    traffic_name: str
    traffic: Dict[str, Any]   # the traffic file, whole
    limits: Dict[str, float]  # benchmark/limits/<cell>.json: ``correct``'s
    end_to_end: List[Metric]  # those this cell reports
    per_layer: List[Metric]   # those this cell may report
    run_seconds: int


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import one file by path (metric names may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "benchmark_file_" + name.replace(".", "_").replace("-", "_"),
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reference_module(cell: "Cell"):
    """The plain reference of the cell's configuration.  It imports
    jax, so it is loaded where it is first needed and not with the
    cell: ``run.py`` settles the platform before anything imports jax."""
    if cell.reference is None:
        return importlib.import_module(DEFAULT_REFERENCE)
    if cell.reference not in _REFERENCES:      # its jitted functions: once
        name = os.path.splitext(os.path.basename(cell.reference))[0]
        _REFERENCES[cell.reference] = load_module(
            cell.reference, "reference_" + name)
    return _REFERENCES[cell.reference]


def _reports(entry: Dict[str, Any], cell: str,
             cell_e2e: List[str]) -> bool:
    """Does this per-layer metric belong to this cell?  With a
    ``workloads`` key: if the cell is listed.  Without: every cell that
    reports the end-to-end metric it moves."""
    if "workloads" in entry:
        return cell in entry["workloads"]
    return entry["moves"] in cell_e2e


def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def find_file(bench_dir: str, kind: str, name: str) -> str:
    path = os.path.join(bench_dir, kind, name + ".json")
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"{kind} {name!r}: no file {name}.json under "
            f"{os.path.join(bench_dir, kind)}")
    return path


def load_cell(workload: str, root: str = ROOT,
              with_readers: bool = True) -> Cell:
    bench = load_benchmark(root)
    bench_dir = os.path.join(root, bench["paths"][0])
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(
            f"no workload {workload!r} in BENCHMARK.json "
            f"(have: {sorted(cells)})")
    entry = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config_entry = configs[entry["config"]]
    config = load_json(os.path.join(root, config_entry["file"]))
    traffic = load_json(find_file(bench_dir, TRAFFIC_DIR, entry["traffic"]))
    limits = load_json(find_file(bench_dir, LIMITS_DIR, workload))["limits"]
    reference = config.get("reference")
    if reference is not None:
        reference = os.path.join(bench_dir, REFERENCES_DIR,
                                 reference + ".py")
        if not os.path.exists(reference):
            raise FileNotFoundError(
                f"configuration {entry['config']!r} names a reference "
                f"that is not there: {reference}")

    # The cell's rate is the one its traffic file names: a later cell
    # brings its rate with its files, and no end-to-end entry is edited.
    mine = EVERY_CELL + (traffic["rate_metric"],)
    end_to_end = [Metric(m["name"], m, None) for m in bench["end_to_end"]
                  if m["name"] in mine
                  and workload in m.get("workloads", [workload])]
    e2e_names = [m.name for m in end_to_end]
    per_layer = []
    for m in bench["per_layer"]:
        if not _reports(m, workload, e2e_names):
            continue
        module = None
        if with_readers:
            module = load_module(
                os.path.join(bench_dir, METRICS_DIR, m["name"] + ".py"),
                m["name"])
        per_layer.append(Metric(m["name"], m, module))
    return Cell(
        name=workload, chips=int(entry["chips"]),
        config_name=entry["config"], config=config, reference=reference,
        traffic_name=entry["traffic"], traffic=traffic, limits=limits,
        end_to_end=end_to_end, per_layer=per_layer,
        run_seconds=int(bench["run_seconds"]))


def driver_flags(cell: Cell, rehearse: bool = False) -> Dict[str, Any]:
    """The flags ``scalable_agent_tpu.driver.main`` gets for this cell:
    the configuration's, then the traffic's, then (``--rehearse``) the
    tiny-size overrides of both.  The env batch comes from the
    configuration's ``sizing`` table under the key the traffic names —
    the largest batch that fits is a property of the model, not of the
    traffic."""
    flags: Dict[str, Any] = {}
    flags.update(cell.config.get("flags", {}))
    flags.update(cell.traffic.get("flags", {}))
    for flag, key in cell.traffic.get("flags_from_sizing", {}).items():
        flags[flag] = cell.config["sizing"][key]
    if rehearse:
        flags.update(cell.config.get("rehearsal_flags", {}))
        flags.update(cell.traffic.get("rehearsal_flags", {}))
    return flags


def flags_to_argv(flags: Dict[str, Any]) -> List[str]:
    argv = []
    for key in sorted(flags):
        value = flags[key]
        if isinstance(value, bool):
            value = "true" if value else "false"
        argv.append(f"--{key}={value}")
    return argv
