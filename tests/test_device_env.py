"""On-device env (envs/device/fake.py) + fused in-graph trainer.

The device mirror must be transition-exact against the host stack
``ImpalaStream(StreamAdapter(FakeEnv))`` — frames, rewards, dones,
episode accounting — across episode boundaries, action repeats, and
length jitter.  The fused trainer must train (finite losses, exact frame
accounting) with zero per-step host involvement.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scalable_agent_tpu.envs.core import ImpalaStream, StreamAdapter
from scalable_agent_tpu.envs.device import DeviceEnvState, DeviceFakeEnv
from scalable_agent_tpu.envs.fake import FakeEnv
from scalable_agent_tpu.models import ImpalaAgent
from scalable_agent_tpu.parallel import MeshSpec, make_mesh
from scalable_agent_tpu.runtime import Learner, LearnerHyperparams
from scalable_agent_tpu.runtime.ingraph import InGraphTrainer
from scalable_agent_tpu.runtime.learner import Trajectory

H = W = 12
NUM_ACTIONS = 4


def host_streams(seeds, episode_length, jitter, repeats,
                 reward_mode="schedule"):
    streams = []
    for s in seeds:
        env = FakeEnv(height=H, width=W, num_actions=NUM_ACTIONS,
                      episode_length=episode_length, length_jitter=jitter,
                      seed=s, num_action_repeats=repeats,
                      reward_mode=reward_mode)
        streams.append(ImpalaStream(StreamAdapter(env)))
    return streams


@pytest.mark.parametrize("repeats,jitter,reward_mode", [
    (1, 0, "schedule"), (4, 0, "schedule"), (4, 3, "schedule"),
    # Learnable modes (tests/test_learning.py) must mirror exactly too:
    # the ingraph learning proof is only as real as this equivalence.
    (1, 0, "bandit"), (3, 0, "bandit"),
    (1, 0, "memory"), (3, 0, "memory"),
])
def test_device_env_mirrors_host_stack(repeats, jitter, reward_mode):
    seeds = [0, 3, 11]
    episode_length = 5
    dev = DeviceFakeEnv(height=H, width=W, num_actions=NUM_ACTIONS,
                        episode_length=episode_length,
                        length_jitter=jitter,
                        num_action_repeats=repeats,
                        reward_mode=reward_mode)
    streams = host_streams(seeds, episode_length, jitter, repeats,
                           reward_mode)
    state, out = dev.initial(np.asarray(seeds, np.int32))
    host_outs = [s.initial() for s in streams]
    step = jax.jit(dev.step)

    rng = np.random.default_rng(0)
    for t in range(40):
        for i, h in enumerate(host_outs):
            np.testing.assert_array_equal(
                np.asarray(out.observation.frame[i]),
                np.asarray(h.observation.frame),
                err_msg=f"frame mismatch env {i} step {t}")
            assert bool(out.done[i]) == bool(h.done), (i, t)
            np.testing.assert_allclose(
                float(out.reward[i]), float(h.reward), rtol=1e-6)
            np.testing.assert_allclose(
                float(out.info.episode_return[i]),
                float(h.info.episode_return), rtol=1e-6)
            assert int(out.info.episode_step[i]) == int(
                h.info.episode_step), (i, t)
        actions = rng.integers(0, NUM_ACTIONS, size=len(seeds))
        state, out = step(state, jnp.asarray(actions, jnp.int32))
        host_outs = [s.step(int(a)) for s, a in zip(streams, actions)]
    for s in streams:
        s.close()


def test_device_env_rejects_overflow_seeds():
    # Length jitter still multiplies the raw seed (host bigints vs
    # device int32), so jittered envs keep the tight seed bound.
    dev = DeviceFakeEnv(height=H, width=W, length_jitter=2)
    with pytest.raises(ValueError, match="seeds must stay below"):
        dev.initial(np.asarray([10**7], np.int32))


@pytest.mark.parametrize("reward_mode", ["schedule", "bandit", "memory"])
def test_device_env_mirrors_host_at_large_seed(reward_mode):
    """ADVICE r5: ``(seed * 131) % a`` overflowed int32 above seed
    ~16.4M, so device and host cues (and schedule-mode frames) silently
    disagreed.  The mod-before-multiply fix must be exact at seeds far
    beyond that bound."""
    seeds = [100_000_000, 2**31 - 1]
    episode_length = 4
    dev = DeviceFakeEnv(height=H, width=W, num_actions=NUM_ACTIONS,
                        episode_length=episode_length,
                        reward_mode=reward_mode)
    streams = host_streams(seeds, episode_length, jitter=0, repeats=1,
                           reward_mode=reward_mode)
    state, out = dev.initial(np.asarray(seeds, np.int32))
    host_outs = [s.initial() for s in streams]
    step = jax.jit(dev.step)

    rng = np.random.default_rng(1)
    for t in range(10):
        for i, h in enumerate(host_outs):
            np.testing.assert_array_equal(
                np.asarray(out.observation.frame[i]),
                np.asarray(h.observation.frame),
                err_msg=f"frame mismatch seed {seeds[i]} step {t}")
            np.testing.assert_allclose(
                float(out.reward[i]), float(h.reward), rtol=1e-6,
                err_msg=f"reward mismatch seed {seeds[i]} step {t}")
            assert bool(out.done[i]) == bool(h.done), (i, t)
        actions = rng.integers(0, NUM_ACTIONS, size=len(seeds))
        state, out = step(state, jnp.asarray(actions, jnp.int32))
        host_outs = [s.step(int(a)) for s, a in zip(streams, actions)]
    for s in streams:
        s.close()


class TestInGraphTrainer:
    T = 5
    B = 4

    def make(self):
        agent = ImpalaAgent(num_actions=NUM_ACTIONS)
        mesh = make_mesh(MeshSpec(data=1, model=1),
                         devices=jax.devices()[:1])
        learner = Learner(agent, LearnerHyperparams(
            total_environment_frames=1e6), mesh,
            frames_per_update=self.T * self.B)
        env = DeviceFakeEnv(height=H, width=W, num_actions=NUM_ACTIONS,
                            episode_length=7)
        return InGraphTrainer(agent, learner, env, self.T, self.B, seed=5)

    def test_fused_training_runs_and_counts_frames(self):
        trainer = self.make()
        state, carry = trainer.init(jax.random.key(0))
        state, carry, metrics = trainer.run(state, carry, 4)
        assert np.isfinite(float(np.asarray(metrics["total_loss"])))
        assert float(np.asarray(metrics["env_frames"])) == (
            4 * self.T * self.B)

    def test_deterministic(self):
        t1 = self.make()
        s1, c1 = t1.init(jax.random.key(0))
        s1, c1, m1 = t1.run(s1, c1, 3)
        t2 = self.make()
        s2, c2 = t2.init(jax.random.key(0))
        s2, c2, m2 = t2.run(s2, c2, 3)
        np.testing.assert_allclose(
            float(np.asarray(m1["total_loss"])),
            float(np.asarray(m2["total_loss"])), rtol=1e-6)

    def test_unroll_overlap_layout(self):
        """Entry 0 of the rollout == the carried previous last entry."""
        trainer = self.make()
        state, carry = trainer.init(jax.random.key(0))
        rng = jax.random.key(1)
        # _rollout takes the bare RolloutCarry and the frame buffer it
        # fills; the telemetry half of the TrainCarry rides only the
        # fused step.
        traj1, carry2, frames, _ = jax.jit(trainer._rollout)(
            state.params, carry.rollout, rng, carry.frames)
        traj2, _, _, _ = jax.jit(trainer._rollout)(
            state.params, carry2, jax.random.key(2), frames)
        np.testing.assert_array_equal(
            np.asarray(traj1.env_outputs.observation.frame[self.T]),
            np.asarray(traj2.env_outputs.observation.frame[0]))
        np.testing.assert_array_equal(
            np.asarray(traj1.agent_outputs.action[self.T]),
            np.asarray(traj2.agent_outputs.action[0]))


class TestFrameSlots:
    """ISSUE 29: the rollout writes the trajectory's frames once, into
    the carry's buffer — slot 0 the overlap entry, slot t+1 in scan
    step t, T inside [H][C][W/8] where W is whole sublanes — and the
    trajectory's frame leaf is a view of it.  Where the bytes lie is
    all that changed: every value is what the scan's stacked ``ys``
    under ``_stack_first``'s concatenate gave."""

    T, B = 5, 4
    # fake_benchmark at 16x24: W is three sublanes, the tiled order;
    # the real worlds' 15x15 and 10x10 frames take the time-major one.
    LEVELS = {"fake_benchmark": dict(height=16, width=24),
              "device_grid_small": {}, "device_minatar_breakout": {}}

    def make(self, level, k=1, emit_trajectory=False):
        from scalable_agent_tpu.envs.device import make_device_env

        env = make_device_env(level, **self.LEVELS[level])
        agent = ImpalaAgent(num_actions=env.num_actions)
        mesh = make_mesh(MeshSpec(data=1, model=1),
                         devices=jax.devices()[:1])
        learner = Learner(agent, LearnerHyperparams(
            total_environment_frames=1e6), mesh,
            frames_per_update=self.T * self.B)
        return InGraphTrainer(agent, learner, env, self.T, self.B,
                              seed=5, updates_per_dispatch=k,
                              emit_trajectory=emit_trajectory)

    @staticmethod
    def stacked_frames(trainer, params, carry, update_index):
        """The frame leaf as every PR before 29 assembled it: the
        scan's stacked ys behind the carry's entry."""
        from scalable_agent_tpu.models.agent import actor_step
        from scalable_agent_tpu.runtime.ingraph import _stack_first

        rng = jax.random.fold_in(jax.random.key(trainer._seed),
                                 update_index)

        def scan_fn(c, t):
            out, core = actor_step(
                trainer._agent, params, jax.random.fold_in(rng, t),
                c.agent_output.action, c.env_output, c.core_state)
            env_state, env_output = trainer._env.step(
                c.env_state, out.action)
            return type(c)(env_state, env_output, out, core), (
                env_output.observation.frame)

        _, frames = jax.lax.scan(scan_fn, carry,
                                 jnp.arange(trainer._unroll_length))
        return _stack_first(carry.env_output.observation.frame, frames)

    @pytest.mark.parametrize("level", sorted(LEVELS))
    def test_frame_leaf_is_the_stacked_assembly(self, level):
        trainer = self.make(level, emit_trajectory=True)
        state, carry = trainer.init(jax.random.key(0))
        tiled = level == "fake_benchmark"
        assert carry.frames.ndim == (6 if tiled else 5)
        last = None
        for update in range(2):
            want = np.asarray(jax.jit(
                self.stacked_frames, static_argnums=(0, 3))(
                    trainer, state.params, carry.rollout, update))
            state, carry, _, trajectory = trainer.train_step(
                state, carry, np.int32(update))
            got = np.asarray(trajectory.env_outputs.observation.frame)
            assert got.shape == (self.T + 1, self.B) + tuple(
                trainer._env.observation_spec.frame.shape)
            assert got.dtype == np.uint8
            np.testing.assert_array_equal(got, want)
            if last is not None:        # the T+1 overlap entry
                np.testing.assert_array_equal(got[0], last)
            last = got[self.T]
            assert got[1:].any(), "the world drew nothing"

    @pytest.mark.parametrize("level", sorted(LEVELS))
    def test_k2_in_one_dispatch_is_two_dispatches(self, level):
        """The buffer is scratch — every slot is written before it is
        read — so riding the megaloop's scan carry changes nothing."""
        one = self.make(level, k=1)
        state1, carry1 = one.init(jax.random.key(0))
        state1, carry1, _ = one.run(state1, carry1, 2)
        two = self.make(level, k=2)
        state2, carry2 = two.init(jax.random.key(0))
        state2, carry2, _ = two.run(state2, carry2, 2)
        for a, b in zip(jax.tree_util.tree_leaves((state1, carry1)),
                        jax.tree_util.tree_leaves((state2, carry2))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestMegaloop:
    """updates_per_dispatch=K (ISSUE 15): K fused updates per device
    launch as one lax.scan, bit-exact with K single-update dispatches."""

    T, B = 5, 4

    def make(self, k, emit_trajectory=False):
        agent = ImpalaAgent(num_actions=NUM_ACTIONS)
        mesh = make_mesh(MeshSpec(data=1, model=1),
                         devices=jax.devices()[:1])
        learner = Learner(agent, LearnerHyperparams(
            total_environment_frames=1e6), mesh,
            frames_per_update=self.T * self.B)
        env = DeviceFakeEnv(height=H, width=W, num_actions=NUM_ACTIONS,
                            episode_length=7)
        return InGraphTrainer(agent, learner, env, self.T, self.B,
                              seed=5, updates_per_dispatch=k,
                              emit_trajectory=emit_trajectory)

    def test_k8_bit_exact_with_k1_and_episode_stats_aggregate(self):
        """THE golden property: 1 dispatch of K=8 == 8 dispatches of
        K=1, bitwise, in final params AND optimizer state — and the
        megaloop's episode stats aggregate over all K unrolls
        (episode_length 7 < the window's agent steps, so episodes
        finish inside it) with the return mean weighted across them."""
        t1 = self.make(1)
        s1, c1 = t1.init(jax.random.key(0))
        counts, ret_sums = 0.0, 0.0
        for i in range(8):
            s1, c1, m1 = t1.run(s1, c1, 1, counter_start=i)
            n = float(np.asarray(m1["episodes_completed"]))
            if n:
                counts += n
                ret_sums += n * float(np.asarray(m1["episode_return"]))
        t8 = self.make(8)
        s8, c8 = t8.init(jax.random.key(0))
        s8, c8, m8 = t8.run(s8, c8, 8)
        for leaf1, leaf8 in zip(
                jax.tree_util.tree_leaves((s1.params, s1.opt_state)),
                jax.tree_util.tree_leaves((s8.params, s8.opt_state))):
            np.testing.assert_array_equal(np.asarray(leaf1),
                                          np.asarray(leaf8))
        assert float(np.asarray(m1["env_frames"])) == float(
            np.asarray(m8["env_frames"])) == 8 * self.T * self.B
        # Gauges read the LAST scanned update — identical streams, so
        # identical losses too.
        np.testing.assert_array_equal(
            np.asarray(m1["total_loss"]), np.asarray(m8["total_loss"]))
        # Episode aggregation: the K=8 dispatch's stats equal the sum /
        # weighted mean over the 8 single-update dispatches.
        assert counts > 0
        assert float(np.asarray(m8["episodes_completed"])) == counts
        np.testing.assert_allclose(
            float(np.asarray(m8["episode_return"])), ret_sums / counts,
            rtol=1e-6)

    def test_run_rejects_misaligned_update_count(self):
        trainer = self.make(4)
        state, carry = trainer.init(jax.random.key(0))
        with pytest.raises(ValueError, match="not divisible"):
            trainer.run(state, carry, 6)

    def test_constructor_rejects_bad_k_and_emit_with_k(self):
        with pytest.raises(ValueError, match="updates_per_dispatch"):
            self.make(0)
        with pytest.raises(ValueError, match="emit_trajectory"):
            self.make(2, emit_trajectory=True)

    def test_run_refuses_to_drop_emitted_trajectories(self):
        """Satellite fix: an emit_trajectory trainer's run() used to
        silently discard every emitted trajectory; now it demands a
        sink — and feeds it."""
        trainer = self.make(1, emit_trajectory=True)
        state, carry = trainer.init(jax.random.key(0))
        with pytest.raises(ValueError, match="on_trajectory"):
            trainer.run(state, carry, 2)
        collected = []
        state, carry, metrics = trainer.run(
            state, carry, 3, on_trajectory=collected.append)
        assert len(collected) == 3
        frame = collected[0].env_outputs.observation.frame
        assert frame.shape[:2] == (self.T + 1, self.B)
        assert np.isfinite(float(np.asarray(metrics["total_loss"])))


class TestInGraphDataParallel:
    """The fused rollout+update shards over the data axis: the carry
    constraint propagates through the scan, so env transitions and
    inference compute per-shard on a multi-device mesh."""

    T, B = 5, 8

    def make(self, data):
        agent = ImpalaAgent(num_actions=NUM_ACTIONS)
        mesh = make_mesh(MeshSpec(data=data, model=1),
                         devices=jax.devices()[:data])
        learner = Learner(agent, LearnerHyperparams(
            total_environment_frames=1e6), mesh,
            frames_per_update=self.T * self.B)
        env = DeviceFakeEnv(height=H, width=W, num_actions=NUM_ACTIONS,
                            episode_length=7)
        return InGraphTrainer(agent, learner, env, self.T, self.B, seed=5)

    def test_multi_device_runs_and_matches_single(self):
        t1 = self.make(data=1)
        s1, c1 = t1.init(jax.random.key(0))
        s1, c1, m1 = t1.run(s1, c1, 3)
        t4 = self.make(data=4)
        s4, c4 = t4.init(jax.random.key(0))
        # the carry really is sharded over the mesh once constrained
        s4, c4, m4 = t4.run(s4, c4, 3)
        loss1 = float(np.asarray(m1["total_loss"]))
        loss4 = float(np.asarray(m4["total_loss"]))
        np.testing.assert_allclose(loss4, loss1, rtol=1e-4)
        assert float(np.asarray(m4["env_frames"])) == 3 * self.T * self.B


class TestSlotOrders:
    """The slot buffer of any ``[B, ...]`` leaf (ISSUE 37 generalised
    ISSUE 29's frame buffer): which axis shares the tiles with the
    batch comes from the leaf's shape alone, and no value depends on
    where the bytes lie."""

    SLOTS = 6

    @pytest.mark.parametrize("shape,buffer_shape,dtype", [
        # frames: three channels fill no tile, W does
        ((4, 16, 24, 3), (16, 3, 3, 6, 8, 4), np.uint8),
        # a stem's activation: the channels do
        ((4, 4, 6, 32), (4, 6, 4, 6, 8, 4), np.float32),
        # the 10x10 worlds' frames, and a leaf that is no image:
        # stacked time-major
        ((4, 10, 10, 3), (6, 4, 10, 10, 3), np.uint8),
        ((4, 7), (6, 4, 7), np.float32),
    ], ids=("width-tiled", "channel-tiled", "untiled-frame", "untiled"))
    # the carry's buffer, born blank, and one a step makes for itself
    @pytest.mark.parametrize("born", ("empty", "unwritten"))
    def test_values_never_depend_on_the_order(self, shape, buffer_shape,
                                              dtype, born):
        from scalable_agent_tpu.runtime.ingraph import _Slots

        mesh = make_mesh(MeshSpec(data=1, model=1),
                         devices=jax.devices()[:1])
        slots = _Slots(shape, self.SLOTS, mesh)
        leaves = np.random.default_rng(0).integers(
            0, 255, (self.SLOTS,) + shape).astype(dtype)
        buffer = getattr(slots, born)(dtype)
        assert buffer.shape == buffer_shape and buffer.dtype == dtype
        write = jax.jit(slots.write)
        for index in (3, 0, 5, 1, 4, 2):    # any order, each slot once
            buffer = write(buffer, leaves[index], index)
        np.testing.assert_array_equal(slots.stacked(buffer), leaves)


class TestStemHandOver:
    """ISSUE 37: behind the Pallas stem the acting steps hand the
    update their stem activations — slot t from scan step t, slot T
    from one more acting step after the scan — beside the trajectory,
    to the one update whose parameters are the ones that acted."""

    T, B = 5, 4

    def make(self, loss="vtrace", emit_trajectory=False, **agent_kwargs):
        from scalable_agent_tpu.envs.device import make_device_env

        env = make_device_env("fake_benchmark", height=16, width=24)
        agent = ImpalaAgent(num_actions=env.num_actions, **agent_kwargs)
        mesh = make_mesh(MeshSpec(data=1, model=1),
                         devices=jax.devices()[:1])
        learner = Learner(agent, LearnerHyperparams(
            total_environment_frames=1e6), mesh,
            frames_per_update=self.T * self.B, loss=loss)
        return InGraphTrainer(agent, learner, env, self.T, self.B,
                              seed=5, emit_trajectory=emit_trajectory)

    def test_handed_slots_are_the_stem_of_the_trajectorys_frames(self):
        from scalable_agent_tpu.models.networks import HANDOVER

        trainer = self.make(conv_backend="pallas")
        state, carry = trainer.init(jax.random.key(0))
        trajectory, _, _, handed = jax.jit(trainer._rollout)(
            state.params, carry.rollout, jax.random.key(1), carry.frames)
        stem = handed["convnet"]["stem"]
        assert stem.shape == (self.T + 1, self.B, 4, 6, 32)
        _, sown = trainer._agent.apply(
            state.params, trajectory.agent_outputs.action,
            trajectory.env_outputs, trajectory.agent_state,
            mutable=[HANDOVER])
        want = np.asarray(sown[HANDOVER]["convnet"]["stem"]).reshape(
            stem.shape)
        assert np.abs(np.diff(want, axis=0)).max() > 0, "frames all alike"
        np.testing.assert_allclose(stem, want, rtol=1e-5, atol=1e-6)

    def test_an_agent_that_declares_nothing_hands_nothing(self):
        trainer = self.make()       # XLA's stem: the default
        state, carry = trainer.init(jax.random.key(0))
        assert jax.eval_shape(
            trainer._rollout, state.params, carry.rollout,
            jax.random.key(1), carry.frames)[3] is None

    @pytest.mark.parametrize("loss", ("vtrace", "impact"))
    def test_only_the_fused_updates_own_forward_is_handed_anything(
            self, monkeypatch, loss):
        """Not ``_replay_step`` (other parameters acted), not the
        IMPACT target's forward (other parameters), not the emitted
        trajectory (it goes to both)."""
        trainer = self.make(loss=loss, emit_trajectory=True,
                            conv_backend="pallas")
        forwards, updates = [], []
        forward, update = Learner._forward, Learner._update_impl

        def spy_forward(self, params, trajectory, capture=False,
                        handed=None):
            forwards.append(handed is not None)
            return forward(self, params, trajectory, capture, handed)

        def spy_update(self, state, trajectory, devtel, fresh=True,
                       handed=None):
            updates.append((fresh, handed is not None))
            return update(self, state, trajectory, devtel, fresh, handed)

        monkeypatch.setattr(Learner, "_forward", spy_forward)
        monkeypatch.setattr(Learner, "_update_impl", spy_update)
        state, carry = trainer.init(jax.random.key(0))
        forwards.clear()
        state, carry, _, trajectory = trainer.train_step(
            state, carry, np.int32(0))
        # the online forward, then (IMPACT) the target network's
        assert forwards == [True, False][:1 + (loss == "impact")]
        assert updates == [(True, True)]
        assert isinstance(trajectory, Trajectory)
        assert all(leaf.shape[-1] != 32 for leaf in
                   jax.tree_util.tree_leaves(trajectory))
        trainer.replay_step(state, carry.telemetry, trajectory)
        assert updates[1:] == [(False, False)]
        assert not any(forwards[2:])
