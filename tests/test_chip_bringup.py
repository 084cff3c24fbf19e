"""Chip bring-up contracts that the CPU rig can hold (ISSUE 21).

Nothing here touches a TPU.  What it pins instead:

- the compile-cache helper: the environment variable wins and nothing
  else is set; unset means the fixed in-checkout path; never a
  temporary directory;
- the ONE kernel policy: ``core_impl`` and ``conv_backend`` agree for 1
  and 4 devices, the interpret decision has one home, a stem the
  grad-W kernel does not take is routed (auto) or refused (explicit);
- no CPU fallback: ``chip_smoke.py`` and ``bench.py`` exit non-zero
  without a TPU, before doing any work, printing no result;
- the roofline peak and the one-machine launcher fail loudly;
- and the class of fault interpret mode can never see — a VMEM limit,
  the SPMD partitioner — via an AOT compile of the default learner
  update for a real v5e topology (``libtpu`` compiles without a chip),
  at production shapes, on 1 and on 4 devices, plus the float32 and
  ResNet configurations.
"""

import dataclasses
import math
import os
import re
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import pytest

from scalable_agent_tpu import driver
from scalable_agent_tpu.config import Config
from scalable_agent_tpu.parallel import mesh as mesh_lib
from scalable_agent_tpu.utils import compile_cache

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- the compile cache ------------------------------------------------------


@pytest.fixture
def cache_config():
    """Restore jax's cache config after a helper call."""
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {name: getattr(jax.config, name) for name in names}
    yield
    for name, value in before.items():
        jax.config.update(name, value)


class TestCompileCache:
    def test_env_var_wins_and_nothing_else_is_set(
            self, monkeypatch, tmp_path, cache_config):
        placed = str(tmp_path / "placed_from_outside")
        monkeypatch.setenv(compile_cache.ENV_VAR, placed)
        before = jax.config.jax_compilation_cache_dir
        assert compile_cache.setup_compile_cache() == placed
        # JAX reads the variable itself; the helper names no directory
        # in code (and creates none).
        assert jax.config.jax_compilation_cache_dir == before
        assert not os.path.exists(
            os.path.join(REPO_ROOT, ".jax_cache", "placed_from_outside"))

    def test_unset_means_the_fixed_in_checkout_path(
            self, monkeypatch, cache_config):
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        path = compile_cache.setup_compile_cache()
        assert path == os.path.join(REPO_ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert os.path.isdir(path)
        assert not path.startswith(tempfile.gettempdir())
        # Same answer every time: no pid, no timestamp.
        assert compile_cache.setup_compile_cache() == path

    def test_the_path_is_ignored_by_git(self):
        ignored = open(os.path.join(REPO_ROOT, ".gitignore")).read()
        assert ".jax_cache/" in ignored.split()

    def test_config_has_no_cache_flag(self):
        fields = {f.name for f in dataclasses.fields(Config)}
        assert not [name for name in fields if "cache" in name]


# -- the kernel policy ------------------------------------------------------


def _as_tpu(monkeypatch):
    """Trace-time stand-in for a TPU backend: every policy decision
    reads ``jax.default_backend()`` (user-level attribute; jax's own
    internals do not go through it)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


class TestKernelPolicy:
    @pytest.mark.parametrize("devices,want", [(1, "pallas"), (4, "xla")])
    def test_core_and_conv_agree_on_tpu(self, monkeypatch, devices, want):
        _as_tpu(monkeypatch)
        config = Config(mesh_data=devices)
        assert driver.resolve_core_impl(config) == want
        assert driver.resolve_conv_backend(config) == want

    def test_off_tpu_both_are_xla(self):
        config = Config(mesh_data=1)
        assert driver.resolve_core_impl(config) == "xla"
        assert driver.resolve_conv_backend(config) == "xla"
        assert mesh_lib.pallas_interpret() is True

    def test_a_tpu_run_never_interprets(self, monkeypatch):
        _as_tpu(monkeypatch)
        assert mesh_lib.pallas_interpret() is False

    def test_the_interpret_decision_has_one_home(self):
        """No module but parallel/mesh.py derives ``interpret=`` (or
        anything else) from a ``default_backend() != "tpu"`` test, and
        every kernel call site asks ``pallas_interpret``."""
        pattern = re.compile(r"default_backend\(\)\s*!=")
        offenders = []
        package = os.path.join(REPO_ROOT, "scalable_agent_tpu")
        for root, _, files in os.walk(package):
            for name in files:
                path = os.path.join(root, name)
                if not name.endswith(".py"):
                    continue
                if pattern.search(open(path).read()):
                    offenders.append(os.path.relpath(path, REPO_ROOT))
        assert offenders == ["scalable_agent_tpu/parallel/mesh.py"]
        for relative in ("models/agent.py", "models/networks.py",
                         "ops/vtrace.py"):
            source = open(os.path.join(package, relative)).read()
            assert "pallas_interpret()" in source, relative

    def test_float32_stem_gets_a_smaller_tile_not_a_mosaic_error(self):
        from scalable_agent_tpu.ops.conv_pallas import gradw_batch_tile

        shape = (101 * 32, 72, 96, 3)
        bf16 = gradw_batch_tile(shape, 32, 8, 4, "bfloat16")
        f32 = gradw_batch_tile(shape, 32, 8, 4, "float32")
        # The images are the operands' lane dim since PR 25, so a tile
        # is whole lane tiles of them.  AOT compiles for v5e take 256
        # in bf16 and 128 in f32 (TestAotCompileForV5e below compiles
        # both, ragged last step included: 3,232 = 12.6 x 256).
        assert (bf16, f32) == (256, 128)

    @pytest.mark.parametrize("overrides,tile,masked", [
        (dict(batch_size=256), 256, 0),       # the fused cell: 25,856
        (dict(batch_size=64), 128, 64),       # the host loop: 6,464
        (dict(batch_size=256, conv_backend="xla"), 0, 0),
    ])
    def test_the_stem_kernels_tile_is_a_gauge_and_in_the_policy_line(
            self, monkeypatch, overrides, tile, masked):
        """The tile and what the last grid step masks are decided at
        trace time: ``build_agent`` sets them as gauges, once, and says
        them in the kernel-policy line.  Nothing is padded in HBM in
        any case; the fused cell's batch masks nothing either."""
        from scalable_agent_tpu.obs import get_registry

        _as_tpu(monkeypatch)
        said = []
        monkeypatch.setattr(
            driver.log, "info",
            lambda message, *args: said.append(message % args))
        config = Config(mesh_data=1, compute_dtype="bfloat16",
                        logdir="/tmp/unused", **overrides)
        observation_spec, action_space, _ = driver.probe_env(config)
        driver.build_agent(config, action_space,
                           observation_spec.frame.shape)
        gauges = get_registry().snapshot()
        assert gauges["conv0_gradw/batch_tile"] == tile
        assert gauges["conv0_gradw/padded_images"] == masked
        (line,) = [m for m in said if m.startswith("kernel policy")]
        assert (f"conv0_gradw batch_tile={tile} "
                f"padded_images={masked}") in line

    def test_resnet_stem_is_routed_to_xla_loudly(self, monkeypatch):
        from scalable_agent_tpu.ops.conv_pallas import gradw_batch_tile

        _as_tpu(monkeypatch)
        assert gradw_batch_tile((101 * 32, 72, 96, 3), 16, 3, 1,
                                "bfloat16") == 0
        warned = []
        monkeypatch.setattr(
            driver.log, "warning",
            lambda message, *args: warned.append(message % args))
        config = Config(mesh_data=1, torso_type="resnet")
        assert driver.resolve_conv_backend(config) == "xla"
        assert warned and "resnet stem" in warned[0]
        # The core is not dragged along: one rule, two kernels, each
        # where it fits.
        assert driver.resolve_core_impl(config) == "pallas"
        with pytest.raises(ValueError, match="does not take"):
            driver.resolve_conv_backend(
                dataclasses.replace(config, conv_backend="pallas"))


# -- no CPU fallback --------------------------------------------------------


def _run(argv, cwd, timeout=120):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, *argv], cwd=cwd, env=env,
                          capture_output=True, text=True,
                          timeout=timeout)


class TestNoCpuFallback:
    def test_chip_smoke_refuses_without_a_tpu(self, tmp_path):
        proc = _run(["chip_smoke.py"], REPO_ROOT)
        assert proc.returncode != 0
        assert proc.stdout.strip() == ""       # no result, no training
        assert "no TPU" in proc.stderr
        assert not list(tmp_path.iterdir())

    def test_chip_smoke_alone_in_a_directory_fails(self, tmp_path):
        """The script without the program (the driver's negative
        control) must fail too — not print an ok line."""
        import shutil

        shutil.copy(os.path.join(REPO_ROOT, "chip_smoke.py"), tmp_path)
        proc = _run(["chip_smoke.py"], str(tmp_path))
        assert proc.returncode != 0
        assert proc.stdout.strip() == ""

    def test_bench_refuses_without_a_tpu(self):
        proc = _run(["bench.py", "--suites=bench_obs"], REPO_ROOT)
        assert proc.returncode != 0
        assert proc.stdout.strip() == ""
        assert "no TPU" in proc.stderr

    def test_bench_has_no_backend_probe_or_cpu_switch(self):
        source = open(os.path.join(REPO_ROOT, "bench.py")).read()
        assert "_probe_backend" not in source
        assert 'os.environ["JAX_PLATFORMS"]' not in source
        assert '"jax_platforms"' not in source


# -- loud failures on the chip path -----------------------------------------


class _FakeTpu:
    platform = "tpu"
    device_kind = "TPU v9 imaginary"


class TestLoudFailures:
    def test_unknown_tpu_kind_is_an_error(self, monkeypatch):
        monkeypatch.delenv("SCALABLE_AGENT_LEDGER_MFU_PEAK",
                           raising=False)
        monkeypatch.setattr(jax, "local_devices", lambda: [_FakeTpu()])
        with pytest.raises(ValueError, match="TPU v9 imaginary"):
            driver._resolve_roofline_peak()

    def test_unknown_cpu_kind_is_not(self, monkeypatch):
        monkeypatch.delenv("SCALABLE_AGENT_LEDGER_MFU_PEAK",
                           raising=False)
        assert driver._resolve_roofline_peak() is None

    def test_unparsable_peak_override_is_an_error(self, monkeypatch):
        monkeypatch.setenv("SCALABLE_AGENT_LEDGER_MFU_PEAK", "fast")
        with pytest.raises(ValueError, match="not a number"):
            driver._resolve_roofline_peak()

    def test_disarmed_mfu_gauge_is_a_warning(self, monkeypatch):
        monkeypatch.setenv("SCALABLE_AGENT_LEDGER_MFU_PEAK", "1e12")
        warned = []
        monkeypatch.setattr(
            driver.log, "warning",
            lambda message, *args: warned.append(message % args))

        def lower_fn():
            raise RuntimeError("no cost model for this custom call")

        driver._configure_live_mfu(object(), lower_fn, 1)
        assert warned and "DISARMED" in warned[0]

    @pytest.mark.parametrize("platforms", ["", "tpu", "tpu,cpu"])
    def test_one_machine_launcher_refuses_off_the_cpu_rig(
            self, monkeypatch, platforms):
        from scalable_agent_tpu.runtime import elastic

        monkeypatch.setattr(
            elastic.subprocess, "Popen",
            lambda *a, **k: pytest.fail("a worker was started"))
        launcher = elastic.DriverLauncher(
            Config(), env={"JAX_PLATFORMS": platforms})
        with pytest.raises(RuntimeError, match="CPU rig"):
            launcher.launch(epoch=0, num_processes=2, port=1)


# -- AOT compile for a real v5e topology ------------------------------------


@pytest.fixture(scope="module")
def v5e_topology():
    try:
        from jax.experimental import topologies

        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # no libtpu in this install: skip, not pass
        pytest.skip(f"cannot build a v5e topology here: {exc}")


def _compile_default_update(monkeypatch, topology, devices,
                            compiled=False, **overrides):
    """Lower + compile the learner update the driver would build for
    ``devices`` v5e chips at the production shapes (T=100, B=32, 72x96
    uint8), against abstract arguments placed on the topology's
    devices.  Returns ``(agent, lowered_text)`` — the compiled
    (partitioned, per-device) text instead with ``compiled=True``;
    compile errors (VMEM, partitioning) propagate."""
    from scalable_agent_tpu.parallel import (
        MeshSpec,
        batch_sharding,
        make_mesh,
        replicated_sharding,
    )
    from scalable_agent_tpu.runtime.learner import Trajectory

    _as_tpu(monkeypatch)
    # per_leaf: the transport is not part of the update program, and
    # the packed one would build its staging layout for nothing.
    config = Config(mesh_data=devices, logdir="/tmp/unused",
                    transport="per_leaf", **overrides)
    observation_spec, action_space, _ = driver.probe_env(config)
    agent = driver.build_agent(config, action_space,
                               observation_spec.frame.shape)
    learner = driver.build_training_learner(config, agent)
    example = driver.zero_trajectory(
        config, observation_spec, agent, batch=config.batch_size,
        t_plus_1=config.unroll_length + 1)
    state = jax.eval_shape(learner.init, jax.random.key(0), example)

    mesh = make_mesh(MeshSpec(data=devices),
                     devices=topology.devices[:devices])
    replicated = replicated_sharding(mesh)

    def abstract(tree, sharding):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=sharding), tree)

    time_major = batch_sharding(mesh, 1)
    trajectory = Trajectory(
        agent_state=abstract(example.agent_state,
                             batch_sharding(mesh, 0)),
        env_outputs=abstract(example.env_outputs, time_major),
        agent_outputs=abstract(example.agent_outputs, time_major))
    lowered = learner.lower_update(
        abstract(state, replicated), trajectory,
        abstract(learner.device_telemetry, replicated))
    executable = lowered.compile()
    return agent, (executable if compiled else lowered).as_text()


def _compile_default_fused_step(monkeypatch, topology, devices,
                                **overrides):
    """Compile the fused (rollout + update) step the driver would build
    for ``devices`` v5e chips at a benchmark cell's sizes (T=100, 72x96
    uint8, bf16, 256 envs a chip unless overridden), carry and state as
    shapes placed on the topology's devices.  The trainer builds its
    mesh from ``jax.devices()``, so for the length of the test those
    calls answer with the described devices (``benchmark/aot.py``'s
    way).  Returns the compiled (partitioned, per-device) text."""
    from scalable_agent_tpu.envs.device import make_device_env
    from scalable_agent_tpu.parallel import (
        batch_sharding,
        replicated_sharding,
    )
    from scalable_agent_tpu.runtime import InGraphTrainer

    chips = list(topology.devices[:devices])
    _as_tpu(monkeypatch)
    for name in ("devices", "local_devices"):
        monkeypatch.setattr(jax, name, lambda *a, **k: chips)
    for name in ("device_count", "local_device_count"):
        monkeypatch.setattr(jax, name, lambda *a, **k: len(chips))
    # No array can live on a described device: placements are no-ops.
    monkeypatch.setattr(jax, "device_put", lambda x, *a, **k: x)
    flags = dict(mesh_data=devices, batch_size=256 * devices,
                 train_backend="ingraph", level_name="fake_benchmark",
                 compute_dtype="bfloat16", num_action_repeats=4,
                 logdir="/tmp/unused")
    flags.update(overrides)
    config = Config(**flags)
    observation_spec, action_space, _ = driver.probe_env(config)
    agent = driver.build_agent(config, action_space,
                               observation_spec.frame.shape)
    learner = driver.build_training_learner(config, agent)
    env = make_device_env(
        config.level_name, height=config.height, width=config.width,
        num_actions=action_space.n,
        num_action_repeats=config.num_action_repeats)
    trainer = InGraphTrainer(agent, learner, env, config.unroll_length,
                             config.batch_size, seed=1)
    state, carry = jax.eval_shape(trainer.init, jax.random.key(0))
    replicated = replicated_sharding(learner.mesh)

    def abstract(tree, sharding_of):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=sharding_of(x)), tree)

    rows = batch_sharding(learner.mesh, 0)
    carry = carry._replace(
        rollout=abstract(carry.rollout,
                         lambda x: rows if x.ndim else replicated),
        telemetry=abstract(carry.telemetry, lambda x: replicated),
        streak_peak=abstract(carry.streak_peak, lambda x: replicated),
        frames=abstract(carry.frames, lambda x: trainer._frame_slots(
            carry.rollout.env_output).sharding))
    return trainer.train_step.lower(
        abstract(state, lambda x: replicated), carry,
        jax.ShapeDtypeStruct((), jnp.int32, sharding=replicated)
    ).compile().as_text()


class TestAotCompileForV5e:
    def test_one_chip_default_keeps_all_three_kernels(
            self, monkeypatch, v5e_topology):
        agent, text = _compile_default_update(monkeypatch, v5e_topology, 1)
        assert (agent.core_impl, agent.conv_backend) == ("pallas",
                                                         "pallas")
        # LSTM forward, LSTM backward, stem grad-W.
        assert text.count("tpu_custom_call") == 3

    def test_four_chip_default_lowers_with_no_mosaic_call(
            self, monkeypatch, v5e_topology):
        """The plain default command on a four-chip host: before the
        one-policy fix the stem stayed Pallas on the data=4 mesh and
        lowering died with "Mosaic kernels cannot be automatically
        partitioned"."""
        agent, text = _compile_default_update(monkeypatch, v5e_topology, 4)
        assert (agent.core_impl, agent.conv_backend) == ("xla", "xla")
        assert text.count("tpu_custom_call") == 0

    def test_four_chip_update_moves_gradients_and_nothing_else(
            self, monkeypatch, v5e_topology):
        """ISSUE 26, on the v5e's own partitioner: the update compiled
        for data=4 holds each device to its own 8 of the 32 envs.  No
        instruction's per-device shape has the global merge (101 x 32 =
        3,232 rows) as a dim, the torso runs on 808, the only gather
        left is a [T, B] of scalars (V-trace's exact p95 sorts all of
        it), and the all-reduce is the whole parameter set — before
        PR 26 it was the LSTM and the heads alone, because every chip
        had computed every torso gradient itself from an all-gathered
        ``u8[101,32,72,96,3]``."""
        from scalable_agent_tpu.obs import kernels as kernels_lib

        _, text = _compile_default_update(
            monkeypatch, v5e_topology, 4, compiled=True)
        rows = kernels_lib.collectives(text)
        gathered = [dims for row in rows if row["kind"] == "all_gather"
                    for dims in row["dims"]]
        assert all(math.prod(dims) <= 101 * 32 for dims in gathered), (
            gathered)
        totals = kernels_lib.collective_bytes(rows)
        assert totals["other"] == 0
        assert totals["all_reduce"] > 4e6, totals     # ~1.6M parameters
        has_dim = lambda n: re.search(  # noqa: E731
            r"\[(?:\d+,)*%d(?:,\d+)*\]" % n, text)
        assert not has_dim(101 * 32)
        assert has_dim(101 * 8)

    def test_one_chip_update_is_merged_as_ever(
            self, monkeypatch, v5e_topology):
        """One shard: the merge is the plain time-major reshape of
        every PR before 26 (a bitcast on the chip), so the frames are
        never transposed and the one-chip cells' step is the parent's."""
        _, text = _compile_default_update(monkeypatch, v5e_topology, 1)
        assert not re.search(
            r"stablehlo\.transpose.*x72x96x3xui8>", text)

    def test_float32_compiles_with_the_kernels(
            self, monkeypatch, v5e_topology):
        """Was: pallas_conv0_gradw scoped VMEM 17.36M > 16.00M."""
        agent, text = _compile_default_update(
            monkeypatch, v5e_topology, 1, compute_dtype="float32")
        assert agent.conv_backend == "pallas"
        assert text.count("tpu_custom_call") == 3

    def test_resnet_compiles_with_its_stem_on_xla(
            self, monkeypatch, v5e_topology):
        """Was: pallas_conv0_gradw scoped VMEM 42.81M > 16.00M."""
        agent, text = _compile_default_update(
            monkeypatch, v5e_topology, 1, torso_type="resnet")
        assert (agent.core_impl, agent.conv_backend) == ("pallas", "xla")
        assert text.count("tpu_custom_call") == 2

    def test_stem_gradw_operands_arrive_without_a_relayout(
            self, v5e_topology):
        """The grad-W kernel at the fused cell's size (25,856 images,
        bf16), compiled alone for a v5e: its operands keep the batch in
        the lanes as XLA itself does, so the cotangent reaches the call
        through a bitcast, the input through ONE fused pad, and nothing
        is copied, reshaped or padded along the batch (the parent of
        PR 25 paid 37 ms a step for pad.44, pad.45, copy.141 and
        reshape.184 in front of a 9.5 ms call).  A compiler verdict on
        layouts, not a run."""
        from jax.sharding import SingleDeviceSharding

        from scalable_agent_tpu.ops import conv_pallas

        n = 256 * 101
        one_chip = SingleDeviceSharding(v5e_topology.devices[0])
        x = jax.ShapeDtypeStruct((n, 72, 96, 3), jnp.bfloat16,
                                 sharding=one_chip)
        g = jax.ShapeDtypeStruct((n, 18, 24, 32), jnp.bfloat16,
                                 sharding=one_chip)
        text = jax.jit(lambda x, g: conv_pallas.conv_gradw(
            x, g, 8, 4, interpret=False, matmul_dtype="bfloat16")
        ).lower(x, g).compile().as_text()
        entry = text[text.index("ENTRY "):]
        # Opcode of every entry instruction whose result has the batch
        # as a dim: the two parameters, g's bitcast, x's pad fusion.
        batch_ops = sorted(
            match.group(2) for match in re.finditer(
                r"= \w+\[([\d,]+)\]\S* ([\w-]+)\(", entry)
            if str(n) in match.group(1).split(","))
        assert batch_ops == ["bitcast", "fusion", "parameter",
                             "parameter"], entry
        assert "pad(" in text and str(n + 1) not in text

    @pytest.mark.parametrize("slots,window", [(2304, 2048), (4352, None)],
                             ids=["window", "full"])
    def test_update_attention_compiles_with_no_score_in_hbm(
            self, monkeypatch, v5e_topology, slots, window):
        """ISSUE 33: the blockwise attention kernels (ops/attention.py,
        T > 1) at ``trinity.ingraph``'s widths — 257 queries of 32
        heads over 4 key/value heads of 128, a ring of 2,304 / 4,352
        slots — compiled alone for a v5e, forward and backward (the
        interpreter never sees a VMEM limit or a tiling rule): two
        Mosaic calls, and no float32 result as large as one env's
        scores (257 x 32 heads x the ring) anywhere in the program."""
        from jax.sharding import SingleDeviceSharding

        from scalable_agent_tpu.ops import attention

        _as_tpu(monkeypatch)
        envs, queries, heads, kv, dim = 2, 257, 32, 4, 128
        one_chip = SingleDeviceSharding(v5e_topology.devices[0])

        def operand(shape, dtype=jnp.bfloat16):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        def loss(query, key, value, *cache):
            out, _ = attention.cached_attention(query, key, value, *cache,
                                                window=window)
            return jnp.sum(out)

        text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
            operand((envs, queries, heads, dim)),
            operand((envs, queries, kv, dim)),
            operand((envs, queries, kv, dim)),
            operand((envs, slots, kv, dim)), operand((envs, slots, kv, dim)),
            operand((slots,), jnp.int32), operand((queries,), jnp.int32),
            operand((envs, queries), jnp.int32)).compile().as_text()
        assert text.count("tpu_custom_call") == 2
        scores = queries * heads * slots
        largest = max(
            math.prod(int(n) for n in dims.split(","))
            for dims in re.findall(r"= f32\[([\d,]+)\]", text))
        assert largest < scores, (largest, scores)

    @pytest.mark.parametrize("slots,window", [(768, 512), (6400, None)],
                             ids=["window", "full_or_cross"])
    def test_differential_update_attention_compiles(
            self, monkeypatch, v5e_topology, slots, window):
        """ISSUE 34: the same kernels with two score streams a pair of
        heads (``streams=2``) at ``phi4flash.ingraph``'s widths — 257
        queries of 20 query pairs over 10 key pairs of 128 (two heads of
        64 side by side), a ring of 768 / 6,400 slots — compiled alone
        for a v5e, forward and backward: two Mosaic calls, and no
        float32 result as large as one env's scores."""
        from jax.sharding import SingleDeviceSharding

        from scalable_agent_tpu.ops import attention

        _as_tpu(monkeypatch)
        envs, queries, pairs, kv, dim = 2, 257, 20, 10, 128
        one_chip = SingleDeviceSharding(v5e_topology.devices[0])

        def operand(shape, dtype=jnp.bfloat16):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        def loss(query, key, value, *cache):
            out, _ = attention.cached_attention(
                query, key, value, *cache, window=window, streams=2)
            return jnp.sum(out)

        text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
            operand((envs, queries, pairs, dim)),
            operand((envs, queries, kv, dim)),
            operand((envs, queries, kv, dim)),
            operand((envs, slots, kv, dim)), operand((envs, slots, kv, dim)),
            operand((slots,), jnp.int32), operand((queries,), jnp.int32),
            operand((envs, queries), jnp.int32)).compile().as_text()
        assert text.count("tpu_custom_call") == 2
        scores = queries * 2 * pairs * slots
        largest = max(
            math.prod(int(n) for n in dims.split(","))
            for dims in re.findall(r"= f32\[([\d,]+)\]", text))
        assert largest < scores, (largest, scores)

    @pytest.mark.parametrize("heads,kv,streams,slots,window", [
        (32, 4, 1, 2304, 2048), (32, 4, 1, 4352, None),
        (20, 10, 2, 768, 512), (20, 10, 2, 6400, None)],
        ids=["trinity_window", "trinity_full", "phi4flash_window",
             "phi4flash_full_or_cross"])
    def test_decode_attention_compiles_with_no_score_in_hbm(
            self, monkeypatch, v5e_topology, heads, kv, streams, slots,
            window):
        """ISSUE 35: the decode kernel (ops/attention.py, one query an
        env) at both token cells' widths — 32 envs, a ring of 2,304 /
        4,352 slots of 4 key/value heads read by groups of 8, a ring of
        768 / 6,400 slots of 10 key pairs read by groups of 2 in two
        streams — compiled alone for a v5e: one Mosaic call, and no
        float32 result as large as the envs' scores or as a ring."""
        from jax.sharding import SingleDeviceSharding

        from scalable_agent_tpu.ops import attention

        _as_tpu(monkeypatch)
        envs, dim = 32, 128
        one_chip = SingleDeviceSharding(v5e_topology.devices[0])

        def operand(shape, dtype=jnp.bfloat16):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        def act(query, key, value, *cache):
            return attention.cached_attention(
                query, key, value, *cache, window=window,
                streams=streams)[0]

        compiled = jax.jit(act).lower(
            operand((envs, 1, heads, dim)), operand((envs, 1, kv, dim)),
            operand((envs, 1, kv, dim)),
            operand((envs, slots, kv, dim)), operand((envs, slots, kv, dim)),
            operand((slots,), jnp.int32), operand((1,), jnp.int32),
            operand((envs, 1), jnp.int32)).compile()
        text = compiled.as_text()
        assert text.count("tpu_custom_call") == 1
        largest = max(
            math.prod(int(n) for n in dims.split(","))
            for dims in re.findall(r"= f32\[([\d,]+)\]", text))
        assert largest < envs * slots * min(heads * streams, kv * dim), (
            largest, slots)

    @pytest.mark.parametrize("queries,calls", [(257, 2), (1, 1)],
                             ids=["update", "decode"])
    def test_latent_attention_compiles_with_no_whole_key_in_hbm(
            self, monkeypatch, v5e_topology, queries, calls):
        """ISSUE 38: the latent kernels (ops/attention.py
        ``latent_attention``) at ``kanana2.ingraph``'s widths — 32 query
        heads over a ring of 10,752 rows of 512 + 64 numbers, 257
        queries an env forward and backward (two Mosaic calls) and one
        query of 32 envs (one) — compiled alone for a v5e (a row is no
        whole number of lanes: the interpreter never sees the tiling
        rule): no float32 result as large as one env's scores (the envs' at one
        query), and nothing as large as the envs' up-projected keys (slots x 32
        heads x 128 an env) in any dtype."""
        from jax.sharding import SingleDeviceSharding

        from scalable_agent_tpu.ops import attention

        _as_tpu(monkeypatch)
        envs = 2 if queries > 1 else 32
        heads, dim, value_dim = 32, 576, 512
        slots = attention.latent_ring_slots(10240 + 256, dim * 2)
        one_chip = SingleDeviceSharding(v5e_topology.devices[0])

        def operand(shape, dtype=jnp.bfloat16):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        def attend(query, latent, *cache):
            out, _ = attention.latent_attention(
                query, latent, *cache, value_dim, 192 ** -0.5)
            return jnp.sum(out) if queries > 1 else out

        fn = jax.grad(attend, (0, 1)) if queries > 1 else attend
        text = jax.jit(fn).lower(
            operand((envs, queries, heads, dim)),
            operand((envs, queries, dim)), operand((envs, dim, slots)),
            operand((slots,), jnp.int32), operand((queries,), jnp.int32),
            operand((envs, queries), jnp.int32)).compile().as_text()
        assert text.count("tpu_custom_call") == calls
        largest = max(
            math.prod(int(n) for n in dims.split(","))
            for dims in re.findall(r"= f32\[([\d,]+)\]", text))
        scores = (envs if queries == 1 else 1) * queries * heads * slots
        assert largest < scores, (largest, scores)
        anything = max(
            math.prod(int(n) for n in dims.split(","))
            for dims in re.findall(r"= \w+\[([\d,]+)\]", text))
        assert anything < envs * slots * heads * 128, anything

    def test_latent_slot_write_compiles_in_place_in_a_scanned_decode(
            self, monkeypatch, v5e_topology):
        """ISSUE 43: a scanned decode step at ``kanana2.ingraph``'s
        widths (32 envs, rows of 576, ``latent_ring_slots(10240 + 256,
        1152)`` slots, the ring in the carry): the decode kernel reads
        the ring and ``latent_ring_write`` then moves the slot's lane
        tile in a Mosaic call of its own with the ring aliased to its
        result.  The compiled text holds no ``dynamic-update-slice`` of
        the ring and no ring-sized ``copy`` (an aliased call XLA cannot
        order after the reader gets one: 396 MB a ring a decode step),
        and the program's temporaries are far under one ring."""
        from jax.sharding import SingleDeviceSharding

        from scalable_agent_tpu.ops import attention

        _as_tpu(monkeypatch)
        envs, heads, dim, value_dim, steps = 32, 32, 576, 512, 4
        slots = attention.latent_ring_slots(10240 + 256, dim * 2)
        one_chip = SingleDeviceSharding(v5e_topology.devices[0])

        def operand(shape, dtype=jnp.bfloat16):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        def act(ring, ring_index, written, queries, latents, episode_start):
            def step(carry, new):
                ring, ring_index, written = carry
                query, latent = new
                out, _ = attention.latent_attention(
                    query, latent, ring, ring_index, written[None],
                    episode_start, value_dim, 192 ** -0.5)
                ring = attention.latent_ring_write(ring, latent, written)
                ring_index = attention.index_write(ring_index, written, 1)
                return (ring, ring_index, written + 1), out

            return jax.lax.scan(step, (ring, ring_index, written),
                                (queries, latents))

        compiled = jax.jit(act, donate_argnums=(0, 1)).lower(
            operand((envs, dim, slots)), operand((slots,), jnp.int32),
            operand((), jnp.int32), operand((steps, envs, 1, heads, dim)),
            operand((steps, envs, 1, dim)),
            operand((envs, 1), jnp.int32)).compile()
        text = compiled.as_text()
        ring = re.escape(f"bf16[{envs},{dim},{slots}]")
        made = re.findall(
            rf"^\s*(?:ROOT )?%\S+ = {ring}\S* ([\w\-]+)\(", text, re.M)
        assert set(made) <= {"parameter", "get-tuple-element",
                             "custom-call", "bitcast"}, made
        assert made.count("custom-call") == 1       # the write, aliased
        assert text.count("tpu_custom_call") == 2   # and the decode
        assert "output_to_operand_aliasing" in text
        assert (compiled.memory_analysis().temp_size_in_bytes
                < envs * dim * slots * 2 // 100)

    def test_selective_scan_compiles_with_no_state_a_token_in_hbm(
            self, monkeypatch, v5e_topology):
        """ISSUE 34: the selective-scan kernels (ops/ssm.py, T > 1) at
        ``phi4flash.ingraph``'s widths — 257 tokens, 5,120 channels, 16
        states — compiled alone for a v5e, forward and backward: two
        Mosaic calls, and no float32 result as large as a state a token
        (the forward keeps a state a chunk of 64 tokens)."""
        from jax.sharding import SingleDeviceSharding

        from scalable_agent_tpu.ops import ssm

        _as_tpu(monkeypatch)
        envs, tokens, width, states = 2, 257, 5120, 16
        one_chip = SingleDeviceSharding(v5e_topology.devices[0])

        def operand(shape, dtype=jnp.float32):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        def loss(x, delta, a, dp, b, c, state, reset):
            y, last = ssm.selective_scan(x, delta, a, dp, b, c, reset, state)
            return jnp.sum(y) + jnp.sum(last)

        text = jax.jit(jax.grad(loss, tuple(range(7)))).lower(
            operand((envs, tokens, width)), operand((envs, tokens, width)),
            operand((states, width)), operand((width,)),
            operand((envs, tokens, states)), operand((envs, tokens, states)),
            operand((envs, states, width)),
            operand((envs, tokens), jnp.bool_)).compile().as_text()
        assert text.count("tpu_custom_call") == 2
        a_state_a_token = envs * tokens * states * width
        largest = max(
            math.prod(int(n) for n in dims.split(","))
            for dims in re.findall(r"= f32\[([\d,]+)\]", text))
        assert largest * 8 < a_state_a_token, (largest, a_state_a_token)

    def test_ssd_scan_compiles_with_no_state_a_token_in_hbm(
            self, monkeypatch, v5e_topology):
        """ISSUE 42: the chunked Mamba-2 scan's kernels (ops/ssd.py, T >
        1) at ``nemotron3.ingraph``'s widths — 257 tokens, 64 heads of 64
        channels, 8 groups of 128 states, chunks of 128, bfloat16
        operands — compiled alone for a v5e, forward and backward: two
        Mosaic calls, and no float32 result as large as a state a token
        (the forward keeps a state a chunk: three of them)."""
        from jax.sharding import SingleDeviceSharding

        from scalable_agent_tpu.ops import ssd

        _as_tpu(monkeypatch)
        envs, tokens, heads, dim, groups, states = 2, 257, 64, 64, 8, 128
        one_chip = SingleDeviceSharding(v5e_topology.devices[0])

        def operand(shape, dtype=jnp.float32):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        def loss(x, delta, a, d, b, c, state, reset):
            y, last = ssd.ssd_scan(x, delta, a, d, b, c, reset, state,
                                   chunk=128, dtype=jnp.bfloat16)
            return jnp.sum(y) + jnp.sum(last)

        text = jax.jit(jax.grad(loss, tuple(range(7)))).lower(
            operand((envs, tokens, heads, dim)),
            operand((envs, tokens, heads)), operand((heads,)),
            operand((heads,)), operand((envs, tokens, groups, states)),
            operand((envs, tokens, groups, states)),
            operand((envs, heads, dim, states)),
            operand((envs, tokens), jnp.bool_)).compile().as_text()
        assert text.count("tpu_custom_call") == 2
        a_state_a_token = envs * tokens * heads * dim * states
        largest = max(
            math.prod(int(n) for n in dims.split(","))
            for dims in re.findall(r"= f32\[([\d,]+)\]", text))
        assert largest * 8 < a_state_a_token, (largest, a_state_a_token)

    def test_gated_delta_scan_compiles_with_no_state_a_token_in_hbm(
            self, monkeypatch, v5e_topology):
        """ISSUE 46: the chunked delta-rule scan's kernels
        (ops/gated_delta.py, T > 1) at ``olmohybrid.ingraph``'s widths —
        257 tokens (two chunks of 128 and one token as a step), 30 heads
        with keys of 96 and values of 192, bfloat16 operands — compiled
        alone for a v5e, forward and backward: two Mosaic calls (a chunk
        of 128 tokens along the lanes of V^T, keys padded to a lane tile,
        the solve's float32 products), and no float32 result as large as
        a state a token (the forward keeps a state a chunk: two of
        them)."""
        from jax.sharding import SingleDeviceSharding

        from scalable_agent_tpu.ops import gated_delta

        _as_tpu(monkeypatch)
        envs, tokens, heads, keys, values = 2, 257, 30, 96, 192
        one_chip = SingleDeviceSharding(v5e_topology.devices[0])

        def operand(shape, dtype=jnp.float32):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        def loss(q, k, v, beta, log_decay, state, reset):
            o, last = gated_delta.gated_delta_scan(
                q, k, v, beta, log_decay, reset, state, chunk=128,
                dtype=jnp.bfloat16)
            return jnp.sum(o) + jnp.sum(last)

        text = jax.jit(jax.grad(loss, tuple(range(6)))).lower(
            operand((envs, tokens, heads, keys)),
            operand((envs, tokens, heads, keys)),
            operand((envs, tokens, heads, values)),
            operand((envs, tokens, heads)), operand((envs, tokens, heads)),
            operand((envs, heads, values, keys)),
            operand((envs, tokens), jnp.bool_)).compile().as_text()
        assert text.count("tpu_custom_call") == 2
        a_state_a_token = envs * tokens * heads * values * keys
        largest = max(
            math.prod(int(n) for n in dims.split(","))
            for dims in re.findall(r"= f32\[([\d,]+)\]", text))
        assert largest * 8 < a_state_a_token, (largest, a_state_a_token)

    @pytest.mark.parametrize("devices,overrides,merged,handed", [
        (1, {}, 101 * 256, True),
        (1, {"torso_type": "resnet", "batch_size": 128}, 101 * 128, False),
        (4, {}, 101 * 256, False),
    ], ids=["shallow", "resnet", "shallow-data4"])
    def test_fused_step_writes_the_frames_once(
            self, monkeypatch, v5e_topology, devices, overrides, merged,
            handed):
        """ISSUE 29, the compiler's verdict on the three fused cells'
        steps: no instruction results in the whole uint8 frame tensor
        but the loop that fills the carry's buffer and its in-place
        slot writes, bitcasts, and (inside its fusion) the stem weight
        gradient's pad — no concatenate for the overlap entry, no
        transposing copy for the ``[T+1, B] -> [(T+1)*B]`` merge, on a
        mesh no per-device reshape (the parent: 1.07 GB of such results
        a step on one chip, 1.6 GB a chip on four).  And the update's
        stem forward conv still reads the uint8 frames and converts as
        it goes (``frames_batch_minor``): no float copy of all the
        frames is written first.

        ISSUE 37, on the same compiled text: behind the Pallas stem
        (one chip, the shallow torso) the update runs no stem conv —
        conv_1 reads a bitcast of the buffer the acting steps filled,
        and nothing copies or transposes a tensor of that size; the
        other two steps, handed nothing, keep the stem conv they had."""
        from scalable_agent_tpu.obs import kernels as kernels_lib

        text = _compile_default_fused_step(
            monkeypatch, v5e_topology, devices, **overrides)
        rows = kernels_lib.frame_relayouts(text)
        assert not rows, (
            f"{sum(row['bytes'] for row in rows):,} bytes a device a "
            f"step still go on holding the frame tensor again: {rows}")
        # The merge of the buffer is there, as a bitcast...
        assert re.search(
            r"= u8\[%d,72,96,3\]\S* bitcast\(" % merged, text), merged
        # ...and no instruction a trace names (one outside a fusion's
        # body) results in all the frames as floats.
        bodies = set(re.findall(r"\sfusion\(.*\scalls=%?([\w.\-]+)", text))
        floats, computation = [], None
        for line in text.splitlines():
            header = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{$",
                              line)
            if header:
                computation = header.group(1)
            elif computation not in bodies and re.match(
                    r"\s+(?:ROOT )?%%?[\w.\-]+ = (?:bf16|f32)"
                    r"\[%d,72,96,3\]" % merged, line):
                floats.append(line.split(" = ")[0].strip())
        assert not floats, floats
        # (the forward's own: a recomputed one's op_name starts with
        # the transpose's path)
        stem_forwards = len(re.findall(
            r" convolution\(.*closed_call/learner_update/"
            r"jvp\(ImpalaAgent\)/convnet/(?:convnet\._\w+/)?"
            r"(?:conv_0|downscale_0)/conv_general_dilated", text))
        assert stem_forwards == (0 if handed else 1)
        if handed:
            assert re.search(
                r"= bf16\[%d,18,24,32\]\S* bitcast\(" % merged, text)
            moved = re.findall(
                r"= bf16\[(?:%d,18,24,32|18,24,4,101,8,256)\]\S* "
                r"(?:copy|transpose)\(" % merged, text)
            assert not moved, moved

