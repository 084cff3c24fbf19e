"""Compile a cell's step at its real sizes for a described v5e, with no
chip attached: ``python3 benchmark/aot.py --workload <name> [--batch N]``.

The TPU compiler is installed in the sandbox and compiles for a
topology that is described, not attached (``v5e:2x2``).  This is how
the env batch of a fused cell is sized — the largest power of two whose
step's ``memory_analysis()`` fits the chip — and how a four-chip
program is proven to partition before a four-chip call is paid for.
Nothing runs: it prints bytes, collective and Mosaic-call counts, never
a time.

The program builds its mesh from ``jax.devices()``, so for the length
of the build those calls answer with the described devices.
"""

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

HBM_BYTES = 16e9 * 0.985      # what the allocator leaves of 16 GB


def compile_cell(workload: str, batch=None):
    import jax
    import numpy as np
    from jax.experimental import topologies

    from benchmark.lib import manifest

    cell = manifest.load_cell(workload, with_readers=False)
    flags = manifest.driver_flags(cell)
    if batch:
        flags["batch_size"] = int(batch)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    devices = list(topo.devices[:cell.chips])

    from scalable_agent_tpu import driver
    from scalable_agent_tpu.config import Config
    from scalable_agent_tpu.parallel import (
        batch_sharding,
        replicated_sharding,
    )

    real = (jax.devices, jax.local_devices, jax.default_backend,
            jax.device_count, jax.local_device_count, jax.device_put)
    # No array can live on a described device: placements become
    # no-ops, and only shapes with shardings go to the compiler.
    jax.device_put = lambda x, *a, **k: x
    jax.devices = lambda *a, **k: devices
    jax.local_devices = lambda *a, **k: devices
    jax.default_backend = lambda: "tpu"
    jax.device_count = lambda *a, **k: len(devices)
    jax.local_device_count = lambda *a, **k: len(devices)
    try:
        config = Config.from_argv(
            manifest.flags_to_argv(flags) + ["--logdir=/tmp/unused"])
        observation_spec, action_space, _ = driver.probe_env(config)
        agent = driver.build_agent(config, action_space,
                                   observation_spec.frame.shape)
        learner = driver.build_training_learner(config, agent)
        mesh = learner.mesh
        replicated = replicated_sharding(mesh)

        def abstract(tree, sharding_of):
            return jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=sharding_of(x)), tree)

        if cell.traffic["backend"] == "ingraph":
            from scalable_agent_tpu.envs.device import make_device_env
            from scalable_agent_tpu.runtime import InGraphTrainer

            env = make_device_env(
                config.level_name, height=config.height,
                width=config.width, num_actions=action_space.n,
                num_action_repeats=config.num_action_repeats,
                with_instruction=False)
            trainer = InGraphTrainer(
                agent, learner, env, config.unroll_length,
                config.batch_size, seed=1)
            state, carry = jax.eval_shape(trainer.init,
                                          jax.random.key(0))
            rows = batch_sharding(mesh, batch_axis_index=0)
            carry = carry._replace(
                rollout=abstract(carry.rollout,
                                 lambda x: rows if x.ndim else replicated),
                telemetry=abstract(carry.telemetry,
                                   lambda x: replicated),
                streak_peak=abstract(carry.streak_peak,
                                     lambda x: replicated))
            lowered = trainer.train_step.lower(
                abstract(state, lambda x: replicated), carry,
                jax.ShapeDtypeStruct((), np.int32, sharding=replicated))
        else:
            from scalable_agent_tpu.runtime.learner import Trajectory

            example = driver.zero_trajectory(
                config, observation_spec, agent, batch=config.batch_size,
                t_plus_1=config.unroll_length + 1)
            state = jax.eval_shape(learner.init, jax.random.key(0),
                                   example)
            time_major = batch_sharding(mesh, 1)
            trajectory = Trajectory(
                agent_state=abstract(example.agent_state,
                                     lambda x: batch_sharding(mesh, 0)),
                env_outputs=abstract(example.env_outputs,
                                     lambda x: time_major),
                agent_outputs=abstract(example.agent_outputs,
                                       lambda x: time_major))
            lowered = learner.lower_update(
                abstract(state, lambda x: replicated), trajectory,
                abstract(learner.device_telemetry,
                         lambda x: replicated))
        compiled = lowered.compile()
    finally:
        (jax.devices, jax.local_devices, jax.default_backend,
         jax.device_count, jax.local_device_count, jax.device_put) = real
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    return {
        "workload": workload, "chips": cell.chips,
        "batch_size": int(flags["batch_size"]),
        "kernel_policy": {"core_impl": agent.core_impl,
                          "conv_backend": agent.conv_backend},
        "argument_bytes": mem.argument_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "alias_bytes": mem.alias_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "live_bytes_per_device": live,
        "live_gib_per_device": live / 2.0 ** 30,
        "fits_16GB": bool(live < HBM_BYTES),
        "mosaic_calls": text.count("tpu_custom_call"),
        "all_reduces": text.count(" all-reduce("),
        "compiled_for": str(devices[0].device_kind),
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--batch", type=int, default=0)
    args = parser.parse_args(argv)
    print(json.dumps(compile_cell(args.workload, args.batch or None)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
