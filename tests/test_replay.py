"""ISSUE 13: device-resident trajectory replay + the IMPACT
clipped-target learner (``runtime/replay.py`` + ``ops/impact.py``).

Four contracts are pinned here:

1. **The slab is correct**: insert/sample round-trips bit-exactly, the
   ring overwrites oldest-first, and the device's uniform slot draw is
   EXACTLY reproducible by the host-side CPU mirror (threefry is
   backend-independent) — the property the no-sync staleness
   attribution stands on.
2. **The slab is silent**: insert + sample dispatch zero host↔device
   transfers beyond the operands already on device — proven the PR 12
   way (``jax.transfer_guard("disallow")`` + materialization spies).
3. **IMPACT behaves**: ratio ≡ 1 against a fresh target (the surrogate
   reduces to the advantage sum), the clip activates on a drifted
   online net, the target network hard-copies on its schedule, and
   replayed updates hold both env_frames and that schedule.
4. **The dial's zero position is free**: ``--replay_ratio=0
   --loss=vtrace`` (the defaults) is bit-exact with the pre-replay
   code — the golden 30-update loss sequence below was generated from
   the pre-PR commit under this exact harness (CPU backend,
   ``--xla_force_host_platform_device_count=8``) and must keep
   reproducing, and the default TrainState/replay path allocates
   nothing new.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from scalable_agent_tpu.models import ImpalaAgent
from scalable_agent_tpu.obs import get_registry
from scalable_agent_tpu.ops import impact as impact_lib
from scalable_agent_tpu.parallel import MeshSpec, make_mesh
from scalable_agent_tpu.runtime import (
    DeviceReplayBuffer,
    Learner,
    LearnerHyperparams,
    Trajectory,
)
from scalable_agent_tpu.runtime.replay import _slot_index
from scalable_agent_tpu.types import (
    AgentOutput,
    AgentState,
    Observation,
    StepOutput,
    StepOutputInfo,
)

T, B, H, W, A = 4, 2, 16, 16, 4


def make_traj(step: int) -> Trajectory:
    """Deterministic per-step trajectory — seeded numpy only, so the
    sequence is identical in the pre-PR golden generator and here."""
    rng = np.random.default_rng(1000 + step)
    t1 = T + 1
    return Trajectory(
        agent_state=AgentState(
            c=np.zeros((B, 256), np.float32),
            h=np.zeros((B, 256), np.float32)),
        env_outputs=StepOutput(
            reward=rng.standard_normal((t1, B)).astype(np.float32),
            info=StepOutputInfo(
                episode_return=np.zeros((t1, B), np.float32),
                episode_step=np.zeros((t1, B), np.int32)),
            done=rng.random((t1, B)) < 0.05,
            observation=Observation(
                frame=rng.integers(0, 256, (t1, B, H, W, 3),
                                   dtype=np.uint8),
                instruction=None)),
        agent_outputs=AgentOutput(
            action=rng.integers(0, A, (t1, B)).astype(np.int32),
            policy_logits=rng.standard_normal((t1, B, A)).astype(
                np.float32),
            baseline=rng.standard_normal((t1, B)).astype(np.float32)),
    )


def one_device_learner(**kwargs) -> Learner:
    agent = ImpalaAgent(num_actions=A)
    mesh = make_mesh(MeshSpec(data=1, model=1), devices=jax.devices()[:1])
    return Learner(agent, LearnerHyperparams(total_environment_frames=1e6),
                   mesh, frames_per_update=T * B, device_telemetry=False,
                   **kwargs)


def device_tree(value: float):
    """A small pytree (with a None leaf, the transport convention) whose
    float leaf encodes ``value`` — slot identity for ring tests."""
    return {
        "x": jnp.full((3, 4), np.float32(value)),
        "n": jnp.arange(6, dtype=jnp.int32).reshape(2, 3),
        "absent": None,
    }


def tree_value(tree) -> float:
    return float(np.asarray(tree["x"])[0, 0])


# ---------------------------------------------------------------------------
# The slab
# ---------------------------------------------------------------------------


class TestDeviceReplayBuffer:
    def test_insert_sample_round_trip_bit_exact(self):
        buf = DeviceReplayBuffer(4, seed=0)
        tree = device_tree(7.5)
        buf.insert(tree)
        out = buf.sample()
        assert out["absent"] is None
        np.testing.assert_array_equal(np.asarray(out["x"]),
                                      np.full((3, 4), 7.5, np.float32))
        np.testing.assert_array_equal(np.asarray(out["n"]),
                                      np.arange(6).reshape(2, 3))

    def test_ring_overwrites_oldest(self):
        buf = DeviceReplayBuffer(2, seed=1)
        for value in (1.0, 2.0, 3.0):
            buf.insert(device_tree(value))
        assert buf.size == 2
        seen = {tree_value(buf.sample()) for _ in range(32)}
        # Slot 0 was overwritten by the third insert: only the two
        # newest batches can ever come back.
        assert seen <= {2.0, 3.0}
        assert len(seen) == 2

    def test_sampling_is_uniform_over_valid_slots_only(self):
        buf = DeviceReplayBuffer(8, seed=2)
        for value in (1.0, 2.0, 3.0):
            buf.insert(device_tree(value))
        seen = {tree_value(buf.sample()) for _ in range(64)}
        # Never a zero-initialized (invalid) slot; all three filled
        # slots reachable.
        assert seen == {1.0, 2.0, 3.0}

    def test_empty_sample_raises(self):
        buf = DeviceReplayBuffer(4, seed=0)
        with pytest.raises(RuntimeError, match="empty"):
            buf.sample()

    def test_structure_mismatch_raises(self):
        buf = DeviceReplayBuffer(4, seed=0)
        buf.insert(device_tree(1.0))
        with pytest.raises(ValueError, match="structure"):
            buf.insert({"different": jnp.zeros((2,))})

    def test_capacity_validation(self):
        with pytest.raises(ValueError, match="capacity"):
            DeviceReplayBuffer(0)

    def test_counters_and_occupancy_gauge(self):
        buf = DeviceReplayBuffer(4, seed=0)
        before_ins = get_registry().snapshot().get(
            "replay/insert_total", 0.0)
        before_smp = get_registry().snapshot().get(
            "replay/sampled_total", 0.0)
        buf.insert(device_tree(1.0))
        buf.insert(device_tree(2.0))
        buf.sample()
        snap = get_registry().snapshot()
        assert snap["replay/insert_total"] == before_ins + 2
        assert snap["replay/sampled_total"] == before_smp + 1
        assert snap["replay/occupancy"] == 0.5
        assert snap["replay/insert_s/count"] >= 2

    def test_device_slot_draw_matches_host_mirror(self):
        """THE staleness-attribution property: the jitted on-device
        gather and the host's CPU-backend replay of the same
        (seed, counter, filled) PRNG pick the SAME slot, every draw —
        so frame age lands on the right batch without a device fetch."""
        seed, capacity = 11, 4
        buf = DeviceReplayBuffer(capacity, seed=seed)
        for value in range(capacity):
            buf.insert(device_tree(float(value)))
        cpu = jax.local_devices(backend="cpu")[0]
        for counter in range(16):
            sampled = tree_value(buf.sample())
            with jax.default_device(cpu):
                expect = int(_slot_index(seed, counter, capacity))
            assert sampled == float(expect), (
                f"draw {counter}: device gathered slot {sampled}, "
                f"host mirror computed {expect}")

    def test_insert_and_sample_issue_no_host_syncs(self, monkeypatch):
        """ISSUE 13 acceptance: insert + sample add ZERO host syncs
        beyond the operands already on device — under
        ``jax.transfer_guard("disallow")`` (hard-errors any transfer)
        with every Python-level D2H materialization idiom spied (the
        PR 12 instrumentation).  The staleness mirror is silenced for
        the window: it is host-local CPU-backend work by construction
        (its own int() materializes a CPU scalar, not a device fetch),
        and ``test_device_slot_draw_matches_host_mirror`` covers it."""
        from scalable_agent_tpu.envs.device.conformance import (
            materialization_spy)

        buf = DeviceReplayBuffer(4, seed=3)
        warm = device_tree(1.0)
        buf.insert(warm)       # compiles the insert program
        buf.sample()           # compiles the sample program
        fresh = device_tree(2.0)
        jax.block_until_ready(fresh["x"])

        monkeypatch.setattr(DeviceReplayBuffer, "_mirror_slot",
                            lambda self, counter, filled: None)
        with materialization_spy() as calls:
            with jax.transfer_guard("disallow"):
                buf.insert(fresh)
                out = buf.sample()
        assert calls == [], (
            f"replay insert/sample materialized device values on the "
            f"host: {calls}")
        # The sampled tree is real — materializing it (outside the
        # guard) is the caller's explicit choice, exactly like the
        # devtel fetch.
        assert float(np.asarray(out["x"])[0, 0]) in (1.0, 2.0)

    def test_postprocess_is_applied(self):
        buf = DeviceReplayBuffer(
            2, seed=0, postprocess=lambda tree: tree["x"] * 2.0)
        buf.insert(device_tree(3.0))
        out = buf.sample()
        np.testing.assert_array_equal(
            np.asarray(out), np.full((3, 4), 6.0, np.float32))


# ---------------------------------------------------------------------------
# The IMPACT surrogate (ops/impact.py) and its learner integration
# ---------------------------------------------------------------------------


class TestImpactSurrogate:
    def test_unit_ratio_reduces_to_advantage_sum(self):
        rng = np.random.default_rng(0)
        logits = rng.standard_normal((3, 2, A)).astype(np.float32)
        actions = rng.integers(0, A, (3, 2)).astype(np.int32)
        adv = rng.standard_normal((3, 2)).astype(np.float32)
        out = impact_lib.surrogate_from_logits(logits, logits, actions,
                                               adv)
        # online == target -> r == 1 everywhere -> L = -sum(adv).
        assert float(out.ratio_mean) == pytest.approx(1.0, abs=1e-6)
        assert float(out.clip_fraction) == 0.0
        assert float(out.loss) == pytest.approx(-float(adv.sum()),
                                                rel=1e-5)

    def test_clip_activates_on_drifted_online_net(self):
        rng = np.random.default_rng(1)
        target = rng.standard_normal((3, 2, A)).astype(np.float32)
        online = target + 5.0 * rng.standard_normal(
            (3, 2, A)).astype(np.float32)
        actions = rng.integers(0, A, (3, 2)).astype(np.int32)
        adv = np.ones((3, 2), np.float32)
        out = impact_lib.surrogate_from_logits(
            online, target, actions, adv, clip_epsilon=0.1)
        assert float(out.clip_fraction) > 0.0
        # With adv == 1 the clipped objective is bounded above by 1+eps
        # per cell -> the loss is bounded below.
        assert float(out.loss) >= -(3 * 2) * 1.1 - 1e-4

    def test_clip_epsilon_validated(self):
        with pytest.raises(ValueError, match="clip_epsilon"):
            impact_lib.surrogate_from_logits(
                np.zeros((1, 1, A), np.float32),
                np.zeros((1, 1, A), np.float32),
                np.zeros((1, 1), np.int32),
                np.zeros((1, 1), np.float32),
                clip_epsilon=0.0)


class TestImpactLearner:
    def test_impact_update_trains_and_reports_diagnostics(self):
        learner = one_device_learner(loss="impact")
        assert learner.loss_name == "impact"
        state = learner.init(jax.random.key(0), make_traj(0))
        assert state.target_params is not None
        state, m = learner.update(
            state, learner.put_trajectory(make_traj(0)))
        assert np.isfinite(float(np.asarray(m["total_loss"])))
        # First update: target == the init-time online params, so the
        # ratio is exactly 1 and nothing clips.
        assert float(np.asarray(m["impact_ratio_mean"])) == \
            pytest.approx(1.0, abs=1e-5)
        assert float(np.asarray(m["impact_clip_fraction"])) == 0.0

    def test_target_network_hard_copies_on_schedule(self):
        learner = one_device_learner(loss="impact",
                                     target_update_interval=2)
        state = learner.init(jax.random.key(0), make_traj(0))
        init_target = jax.tree_util.tree_map(
            lambda x: np.asarray(x).copy(), state.target_params)
        state, _ = learner.update(
            state, learner.put_trajectory(make_traj(0)))
        # Update 1 of 2: target still the init copy, params moved away.
        for before, after in zip(
                jax.tree_util.tree_leaves(init_target),
                jax.tree_util.tree_leaves(state.target_params)):
            np.testing.assert_array_equal(before, np.asarray(after))
        state, _ = learner.update(
            state, learner.put_trajectory(make_traj(1)))
        # Update 2: the schedule fires — target == the JUST-updated
        # online params, bit-exact.
        for p, t in zip(jax.tree_util.tree_leaves(state.params),
                        jax.tree_util.tree_leaves(state.target_params)):
            np.testing.assert_array_equal(np.asarray(p), np.asarray(t))

    def test_replayed_update_holds_frames_and_schedule(self):
        learner = one_device_learner(loss="impact",
                                     target_update_interval=2)
        state = learner.init(jax.random.key(0), make_traj(0))
        state, _ = learner.update(
            state, learner.put_trajectory(make_traj(0)))
        frames = float(np.asarray(state.env_frames))
        target = jax.tree_util.tree_map(
            lambda x: np.asarray(x).copy(), state.target_params)
        # A replayed update: frames held, the (due-next-update) target
        # sync NOT taken, but the params still move.
        params = jax.tree_util.tree_map(
            lambda x: np.asarray(x).copy(), state.params)
        state, m = learner.update(
            state, learner.put_trajectory(make_traj(1)), fresh=False)
        assert float(np.asarray(state.env_frames)) == frames
        assert float(np.asarray(m["env_frames"])) == frames
        for before, after in zip(
                jax.tree_util.tree_leaves(target),
                jax.tree_util.tree_leaves(state.target_params)):
            np.testing.assert_array_equal(before, np.asarray(after))
        moved = any(
            not np.array_equal(before, np.asarray(after))
            for before, after in zip(
                jax.tree_util.tree_leaves(params),
                jax.tree_util.tree_leaves(state.params)))
        assert moved, "replayed update did not train"

    def test_invalid_loss_and_interval_raise(self):
        with pytest.raises(ValueError, match="loss"):
            one_device_learner(loss="ppo")
        with pytest.raises(ValueError, match="target_update_interval"):
            one_device_learner(loss="impact", target_update_interval=0)


# ---------------------------------------------------------------------------
# The dial's zero position: bit-exact with the pre-replay code
# ---------------------------------------------------------------------------


# 30 total_loss values of this file's exact setup (one_device_learner()
# defaults + make_traj(step) per update) under the test harness
# environment (JAX_PLATFORMS=cpu,
# --xla_force_host_platform_device_count=8).  The default path
# (--replay_ratio=0 --loss=vtrace) must keep reproducing them
# bit-for-bit: target_params=None adds zero leaves and the fresh vtrace
# update's program is the pre-replay program.
#
# Re-recorded in PR 28.  The first recording (commit d66b257, generated
# at 8a01cc7) began -0.2577, -1.4789, 2.9639 and had been red since
# this round's seed — because of the installed JAX, not of a default of
# this program: that very commit gives today's values here, and
# today's tree gives the old ones to six digits once
# ``jax_threefry_partitionable`` is set back to False (JAX 0.5 made
# True the default: the initializers draw other bits from the same
# key).  The values repeat across runs and under the suite's six
# workers.
PRE_REPLAY_GOLDEN_LOSSES = [
    0.05555073171854019,
    -1.5114123821258545,
    2.8023760318756104,
    12.591068267822266,
    2.7376716136932373,
    -5.295569896697998,
    6.158614635467529,
    -2.6037518978118896,
    0.3982926905155182,
    10.871377944946289,
    -0.6831086874008179,
    10.990255355834961,
    -3.7800045013427734,
    11.013604164123535,
    -2.1275439262390137,
    -7.069136619567871,
    -0.8839548826217651,
    4.54905366897583,
    -0.9141564965248108,
    0.3464739918708801,
    0.7336164116859436,
    -11.381795883178711,
    -0.11170890927314758,
    -7.600537300109863,
    5.261862754821777,
    -4.479100227355957,
    -1.5059324502944946,
    3.636831521987915,
    8.575611114501953,
    0.14488585293293,
]


class TestDefaultPathBitExact:
    def test_vtrace_defaults_reproduce_pre_replay_golden_losses(self):
        learner = one_device_learner()   # loss="vtrace", the default
        state = learner.init(jax.random.key(0), make_traj(0))
        # No target network, no extra leaves: the default TrainState is
        # structurally the pre-replay 5-field state (None carries zero
        # pytree leaves), so its checkpoint bytes are unchanged too.
        assert state.target_params is None
        assert len(jax.tree_util.tree_leaves(state)) == (
            len(jax.tree_util.tree_leaves(state.params))
            + len(jax.tree_util.tree_leaves(state.opt_state)) + 3)
        losses = []
        for step in range(30):
            state, m = learner.update(
                state, learner.put_trajectory(make_traj(step)))
            losses.append(float(np.asarray(m["total_loss"])))
        assert losses == PRE_REPLAY_GOLDEN_LOSSES

    def test_replay_off_allocates_nothing(self):
        from scalable_agent_tpu.config import Config
        from scalable_agent_tpu.driver import build_replay

        learner = one_device_learner()
        # The dial's zero position: no slab, no sink, no buffer object.
        assert build_replay(Config(), learner) is None
