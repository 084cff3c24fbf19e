"""The numbers behind ``correct``, read on many seeds in one process:
``python3 benchmark/seeds.py --workload <name> --seeds 11,12,13
[--faults 3]``.

A limit of ``benchmark/limits/<cell>.json`` stands between two readings:
the largest that sound runs give over a dozen seeds or more, and the
smallest that a control or a planted fault gives.  A run of
``benchmark/run.py`` reads one seed and pays the whole set-up for it;
this tool pays it once.  It drives the same entry (``driver.main`` with
the cell's flags), the same compiled step at the cell's own sizes and
the same probe (``probe.Probe``, through its ``before_step`` hook), and
before every third dispatch hands the step a copy of its first
arguments with the next seed's weights in them: the start every run of
the cell has.  Each seed's three steps are then held against the cell's
reference exactly as a run holds them (``correct.program_numbers`` /
``follow`` / ``compare``).  Nothing is timed and no metric is printed:
its last line is no result line, and the driver never calls it.  Fused
cells only.

``--faults n`` also follows the first ``n`` seeds with the reference put
in the program's place four ways (``FAULTS``): at fp8 (the control);
with an optimizer step that hands its state back; over the first half
of the batch; and, in a cell of several chips, over one chip's share of
it, which is what a gradient exchange left out trains on (the loss is a
sum, so nothing is rescaled).  ``--flag name=value`` hands the program
another flag than the cell's (a second witness when a seed reads far
off: another kernel path, float32); such a run is not the cell's and
sets no reading.  ``--rehearse 1``: on the CPU at the rehearsal's sizes
(``benchmark/tests/test_seeds.py``).
"""

import argparse
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

WORST_LEAVES = 3


def parse_args(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True,
                        help="comma-separated whole numbers")
    parser.add_argument("--faults", type=int, default=0,
                        help="read FAULTS on the first n seeds")
    parser.add_argument("--flag", action="append", default=[],
                        metavar="NAME=VALUE")
    parser.add_argument("--rehearse", type=int, default=0, choices=(0, 1))
    return parser.parse_args(argv)


FAULTS = ("control_fp8", "frozen", "half_batch", "one_chip_share")


class Frozen:
    """A reference module whose optimizer step changes nothing."""

    def __init__(self, reference):
        self._reference = reference

    def __getattr__(self, name):
        return getattr(self._reference, name)

    def rmsprop_step(self, cfg, params, nu, grads, env_frames):
        return params, nu


def faults(cell, reference, follow):
    """{kind: ``correct.follow``'s arguments with the fault planted}."""

    def part(share):
        return dict(follow,
                    frames_per_update=follow["frames_per_update"] / share,
                    fused=dict(follow["fused"],
                               batch=follow["fused"]["batch"] // share))

    planted = {"control_fp8": dict(follow, quant="fp8"),
               "frozen": dict(follow, reference=Frozen(reference)),
               "half_batch": part(2)}
    if cell.chips > 1:
        planted["one_chip_share"] = part(cell.chips)
    return planted


def worst_leaves(correct, program, ref):
    """[(gap, leaf, program's norm, reference's norm)], widest first."""
    gaps = [(gap, "/".join(path), program[path], ref[path])
            for path, gap in correct.leaf_gaps(program, ref).items()]
    return sorted(gaps, reverse=True)[:WORST_LEAVES]


def make_probe(probe_lib, seeds, **kwargs):
    import jax
    import jax.numpy as jnp
    import numpy as np

    steps = probe_lib.CHECK_STEPS

    def copy(tree):
        return jax.tree_util.tree_map(
            lambda x: jnp.copy(x) if isinstance(x, jax.Array) else x, tree)

    class SeedsProbe(probe_lib.Probe):
        """One first-steps record per seed, through the probe's own
        install: only what a dispatch is given and what is kept of its
        result differ from a run's."""

        def __init__(self):
            super().__init__(seed=seeds[0], **kwargs)
            self.records = []      # {seed, losses, nu1, params}
            self.first = None      # a copy of the step's first arguments

        def before_step(self, k, state, carry, counter):
            index, j = divmod(k - 1, steps)
            if index >= len(seeds):               # the drain's last steps
                return state, carry, counter
            if self.first is None:
                self.first = (copy(state), copy(carry))
            if j == 0:
                self.seed = seeds[index]
                state = self._replace_weights(copy(self.first[0]))
                carry = copy(self.first[1])
                self.records.append({"seed": seeds[index], "losses": []})
            return state, carry, np.int32(j)

        def _capture_post(self, k, new_state, metrics):
            index, j = divmod(k - 1, steps)
            if index >= len(seeds):
                return
            record = self.records[-1]
            record["losses"].append(metrics["total_loss"])
            if j == 0:
                record["nu1"] = jax.device_get(
                    jax.tree_util.tree_leaves(new_state.opt_state))
            if j == steps - 1:
                record["params"] = jax.device_get(new_state.params)
                record["losses"] = [
                    float(x) for x in jax.device_get(record["losses"])]
                if index == len(seeds) - 1:
                    from scalable_agent_tpu.runtime.fleet import get_fleet

                    get_fleet().request_preemption("benchmark seeds read")

        def on_retire(self, t, metrics):
            """Nothing is timed: no window opens."""

    return SeedsProbe()


def main(argv=None) -> int:
    args = parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    from benchmark.lib import manifest

    cell = manifest.load_cell(args.workload, with_readers=False)
    flags = manifest.driver_flags(cell, rehearse=bool(args.rehearse))
    flags.update(item.split("=", 1) for item in args.flag)
    if cell.traffic["backend"] == "host":
        print("benchmark: seeds.py reads fused cells only", file=sys.stderr)
        return 2
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell.chips}")

    import jax

    if not args.rehearse and (jax.default_backend() != "tpu"
                              or len(jax.devices()) != cell.chips):
        print(f"benchmark: {args.workload} needs {cell.chips} TPU chip(s)",
              file=sys.stderr)
        return 2

    from benchmark.lib import correct, probe as probe_lib
    from scalable_agent_tpu import driver

    reference = manifest.reference_module(cell)
    program_seed = 1          # as benchmark/run.py fixes it
    logdir = tempfile.mkdtemp(prefix="benchmark_seeds_")
    probe = make_probe(
        probe_lib, seeds, config=cell.config, reference=reference,
        backend=cell.traffic["backend"], seconds=1.0, trace=False,
        trace_seconds=0.0, trace_dir=os.path.join(logdir, "profile"),
        t_launch=0.0)
    probe.install()
    try:
        driver.main(manifest.flags_to_argv(flags) + [
            "--mode=train", f"--logdir={logdir}", f"--seed={program_seed}",
            "--trace=false"])
    finally:
        probe.uninstall()
        probe_lib.stop_children()
        shutil.rmtree(logdir, ignore_errors=True)
    del probe.first

    frames_per_update = float(flags["batch_size"] * flags["unroll_length"]
                              * flags["num_action_repeats"])
    follow = dict(
        reference=reference, frames_per_update=frames_per_update,
        fused={"world": cell.traffic["world"],
               "batch": int(flags["batch_size"]),
               "unroll_length": int(flags["unroll_length"]),
               "program_seed": program_seed})
    rows = []
    for i, record in enumerate(probe.records):
        if "params" not in record:
            continue
        seed = record["seed"]
        program = correct.program_numbers(
            cell.config, seed, probe.param_paths, record["losses"],
            record["nu1"], record["params"], reference=reference)
        ref = correct.follow(cell.config, seed, **follow)
        row = {"seed": seed, "compared": correct.compare(program, ref),
               "losses": [program["losses"], ref["losses"]],
               "worst_leaves": {
                   kind: worst_leaves(correct, program[kind], ref[kind])
                   for kind in ("grad_norms", "delta_norms")}}
        if i < args.faults:
            for kind, changed in faults(cell, reference, follow).items():
                row[kind] = correct.compare(
                    correct.follow(cell.config, seed, **changed), ref)
        rows.append(row)
        print("seed", json.dumps(row), flush=True)

    for name in sorted(rows[0]["compared"]) if rows else ():
        sound = [row["compared"][name] for row in rows]
        read = {kind: [row[kind][name] for row in rows if kind in row]
                for kind in FAULTS}
        print(f"reading {name}: sound largest {max(sound)} of {len(sound)} "
              f"seeds, sorted {sorted(sound)}; smallest of "
              + "; ".join(f"{kind} {min(gaps) if gaps else 'not read'}"
                          for kind, gaps in read.items())
              + f"; limit {cell.limits.get(name, 'none')}", flush=True)
    out_dir = os.path.join(ROOT, "chiprun_out", "benchmark")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"seeds.{args.workload}.json"),
              "w") as f:
        json.dump({"workload": args.workload,
                   "platform": jax.default_backend(), "rows": rows}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
