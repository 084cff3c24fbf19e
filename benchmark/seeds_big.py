"""``benchmark/seeds.py`` for a cell whose state does not fit the chip
three times over: ``python3 benchmark/seeds_big.py --workload <name>
--seeds 11,12,13 [--faults 3]``.

``seeds.py`` keeps a copy of the step's first arguments, hands every
third dispatch a copy of that copy, and keeps every seed's parameters
and mean square on the host until the program has ended.  At 6.5 GB of
state (``trinity.ingraph``) the first does not fit the chip and the
second does not fit the host.  This tool reads the same numbers through
the same entry, the same compiled step and the same probe, and holds
one state:

- before every third dispatch the state the loop hands over is re-made
  in place: the next seed's weights (the reference's own, made on the
  device a leaf at a time: ``make_weight_on_device``), the
  optimizer's leaves back at the constants they started from, the
  counters back at their first values; the carry (the worlds, the
  cache) is a copy of the first carry, the one copy kept;
- of each seed's three steps it keeps what ``correct.compare`` reads
  and nothing else: the losses, the first gradient's leaf norms (out of
  RMSProp's mean square after step one, as ``first_gradient_norms``
  takes them, in float32) and the leaf norms of the parameters' change,
  all reduced on the device;
- the reference follows each seed after the program has ended (it
  needs the chip to itself), and every row is written as it is read:
  ``chiprun_out/benchmark/seeds_big.<cell>.jsonl``.

``--faults n``: the first ``n`` seeds also with the reference in the
program's place at fp8 (the control) and over the first half of the
batch (``seeds.faults``; a step that hands its state back reads
``delta_norm_gap`` 1 by construction and is not run).  Nothing is
timed; the driver never calls this.  Fused cells on one chip only.
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

FAULTS = ("control_fp8", "half_batch")


def parse_args(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True,
                        help="comma-separated whole numbers")
    parser.add_argument("--faults", type=int, default=0,
                        help="read FAULTS on the first n seeds")
    parser.add_argument("--rehearse", type=int, default=0, choices=(0, 1))
    return parser.parse_args(argv)


def make_probe(probe_lib, correct, seeds, **kwargs):
    import jax
    import jax.numpy as jnp
    import numpy as np

    steps = probe_lib.CHECK_STEPS
    reference = kwargs["reference"]
    config = kwargs["config"]

    def copy(tree):
        return jax.tree_util.tree_map(
            lambda x: jnp.copy(x) if isinstance(x, jax.Array) else x, tree)

    @jax.jit
    def gradient_norms(nu1, decay):
        # ``first_gradient_norms`` of the cell's reference, in float32 on
        # the device (2.8 GB of mean square is ~15 s through the host):
        # nu1 = decay + (1 - decay) * g**2, the decay as float32 holds it
        rest = jnp.float32(1.0) - decay
        return [jnp.sqrt(jnp.sum(jnp.maximum((nu - decay) / rest, 0.0)))
                for nu in nu1]

    @jax.jit
    def leaf_norm(after, start):
        return jnp.sqrt(jnp.sum(jnp.square(
            after.astype(jnp.float32) - start.astype(jnp.float32))))

    class BigProbe(probe_lib.Probe):
        def __init__(self):
            super().__init__(seed=seeds[0], **kwargs)
            self.records = []          # {seed, losses, grad_norms, ...}
            self.first_carry = None
            self.first_small = None    # the state's leaves but params
            self.first_opt = None      # [(shape, dtype, sharding, value)]

        def _weight(self, seed, path):
            """One leaf of the reference's weights for ``seed``, made on
            the device (the cell's reference brings
            ``make_weight_on_device``: all of them at once do not fit
            beside the program)."""
            return reference.make_weight_on_device(config, seed, path)

        def _remember(self, state, carry):
            self.first_carry = copy(carry)
            self.first_opt = []
            leaves = jax.tree_util.tree_leaves(state.opt_state)
            for leaf, (low, high) in zip(leaves, jax.device_get(
                    jax.jit(lambda xs: [(jnp.min(x), jnp.max(x))
                                        for x in xs])(leaves))):
                low, high = float(low), float(high)
                if low != high:
                    raise RuntimeError(
                        "seeds_big: an optimizer leaf does not start "
                        "constant; this tool cannot re-make it")
                self.first_opt.append(
                    (leaf.shape, leaf.dtype, leaf.sharding, low))
            self.first_small = copy(state._replace(
                params=None, opt_state=None))

        def _remake(self, state, carry):
            """The state and carry the loop handed over, as the next
            seed's run would start: nothing of the last seed's is left
            in them.  What was handed over goes FIRST, as a donation
            would have taken it (the loop never reads it again): the
            loaded step keeps its scratch reserved, and beside that two
            states do not fit."""
            jax.block_until_ready((state, carry))
            leaves, tree = jax.tree_util.tree_flatten_with_path(
                state.params)
            like = [(probe_lib._key_names(path), leaf.dtype, leaf.sharding)
                    for path, leaf in leaves]
            opt_tree = jax.tree_util.tree_structure(state.opt_state)
            for leaf in jax.tree_util.tree_leaves((state, carry)):
                if isinstance(leaf, jax.Array):
                    leaf.delete()
            params = jax.tree_util.tree_unflatten(tree, [
                jax.device_put(jnp.asarray(self._weight(
                    self.seed, names[1:] if names[0] == "params"
                    else names)).astype(dtype), sharding)
                for names, dtype, sharding in like])
            opt_state = jax.tree_util.tree_unflatten(opt_tree, [
                jax.device_put(jnp.full(shape, value, dtype), sharding)
                for shape, dtype, sharding, value in self.first_opt])
            return (copy(self.first_small)._replace(
                params=params, opt_state=opt_state),
                copy(self.first_carry))

        def before_step(self, k, state, carry, counter):
            index, j = divmod(k - 1, steps)
            if index >= len(seeds):               # the drain's last steps
                return state, carry, counter
            if self.first_carry is None:
                self._remember(state, carry)
            if j == 0:
                self.seed = seeds[index]
                if index:
                    state, carry = self._remake(state, carry)
                self.records.append({"seed": seeds[index], "losses": []})
            return state, carry, np.int32(j)

        def _capture_post(self, k, new_state, metrics):
            index, j = divmod(k - 1, steps)
            if index >= len(seeds):
                return
            record = self.records[-1]
            record["losses"].append(metrics["total_loss"])
            if j == 0:
                norms = gradient_norms(
                    jax.tree_util.tree_leaves(new_state.opt_state),
                    np.float32(config["optimizer"]["rmsprop_decay"]))
                record["grad_norms"] = dict(zip(self.param_paths, norms))
            if j == steps - 1:
                after = reference.from_tree(
                    new_state.params["params"]
                    if "params" in new_state.params else new_state.params)
                paths = sorted(after)
                # a leaf at a time: the start is 2.8 GB more
                norms = [leaf_norm(after[p], jnp.asarray(
                    self._weight(record["seed"], p))) for p in paths]
                record["delta_norms"] = {
                    p: float(n) for p, n in zip(paths,
                                                jax.device_get(norms))}
                record["grad_norms"] = {
                    p: float(n) for p, n in zip(
                        self.param_paths,
                        jax.device_get(list(
                            record["grad_norms"].values())))}
                record["losses"] = [
                    float(x) for x in jax.device_get(record["losses"])]
                print("program", record["seed"], record["losses"],
                      flush=True)
                if index == len(seeds) - 1:
                    from scalable_agent_tpu.runtime.fleet import get_fleet

                    get_fleet().request_preemption("benchmark seeds read")

        def on_retire(self, t, metrics):
            """Nothing is timed: no window opens."""

    return BigProbe()


def main(argv=None) -> int:
    args = parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    from benchmark.lib import manifest

    cell = manifest.load_cell(args.workload, with_readers=False)
    flags = manifest.driver_flags(cell, rehearse=bool(args.rehearse))
    if cell.traffic["backend"] == "host" or cell.chips != 1:
        print("benchmark: seeds_big.py reads fused cells on one chip",
              file=sys.stderr)
        return 2
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    if not args.rehearse and (jax.default_backend() != "tpu"
                              or len(jax.devices()) != cell.chips):
        print(f"benchmark: {args.workload} needs {cell.chips} TPU chip(s)",
              file=sys.stderr)
        return 2

    from benchmark import seeds as seeds_tool
    from benchmark.lib import correct, probe as probe_lib
    from scalable_agent_tpu import driver

    reference = manifest.reference_module(cell)
    program_seed = 1          # as benchmark/run.py fixes it
    logdir = tempfile.mkdtemp(prefix="benchmark_seeds_")
    probe = make_probe(
        probe_lib, correct, seeds, config=cell.config, reference=reference,
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
    probe.first_carry = probe.first_small = None

    frames_per_update = float(flags["batch_size"] * flags["unroll_length"]
                              * flags["num_action_repeats"])
    follow = dict(
        reference=reference, frames_per_update=frames_per_update,
        fused={"world": cell.traffic["world"],
               "batch": int(flags["batch_size"]),
               "unroll_length": int(flags["unroll_length"]),
               "program_seed": program_seed})
    out_dir = os.path.join(ROOT, "chiprun_out", "benchmark")
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, f"seeds_big.{args.workload}.jsonl")
    records = [r for r in probe.records if "delta_norms" in r]
    with open(out_path, "w") as out:
        def keep(row):
            print("seed", json.dumps(row), flush=True)
            out.write(json.dumps(row) + "\n")
            out.flush()

        refs = {}
        for record in records:
            seed = record["seed"]
            refs[seed] = correct.follow(cell.config, seed, **follow)
            keep({"seed": seed, "kind": "sound",
                  "compared": correct.compare(record, refs[seed]),
                  "losses": [record["losses"], refs[seed]["losses"]],
                  "worst_leaves": {
                      kind: seeds_tool.worst_leaves(
                          correct, record[kind], refs[seed][kind])
                      for kind in ("grad_norms", "delta_norms")}})
        planted = seeds_tool.faults(cell, reference, follow)
        for record in records[:args.faults]:
            seed = record["seed"]
            for kind in FAULTS:
                keep({"seed": seed, "kind": kind,
                      "compared": correct.compare(
                          correct.follow(cell.config, seed,
                                         **planted[kind]), refs[seed])})
    return 0


if __name__ == "__main__":
    sys.exit(main())
