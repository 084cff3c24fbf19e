"""The real device worlds (ISSUE 15): dynamics, host twins, driver e2e.

Three layers of proof for ``device_grid_*`` / ``device_minatar_*``:

1. Game-rule unit tests against hand-crafted states — key pickup, door
   locking, goal termination, paddle save/lose, brick scoring, gold vs
   enemy collisions, sticky actions.  (The conformance matrix in
   tests/test_device_conformance.py covers the protocol layer.)
2. Host-twin equivalence: the ``device_`` registry family serves the
   SAME transition function through the gym-like adapter, so the host
   ImpalaStream and the device rollout agree bit-for-bit.
3. Acceptance smokes: both worlds train end-to-end through
   ``--train_backend=ingraph`` (complete conservation-checked ledger
   artifact, ``devtel/env/*`` episodes > 0), and a short real training
   run IMPROVES return on ``device_grid_small``.

And the token world (``token_recall*``, what the token policy acts in):
its stream ignores the action and repeats past its period, and the
reference's copy of it emits the program's tokens under the program's
keys.
"""

import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scalable_agent_tpu.config import Config
from scalable_agent_tpu.envs.device import make_device_env
from scalable_agent_tpu.envs.device.gridworld import (
    DeviceGridState,
    DeviceGridWorld,
)
from scalable_agent_tpu.envs.device.minatar import (
    DeviceAsterix,
    DeviceBreakout,
)


def _batched(value, dtype=jnp.int32):
    return jnp.asarray([value], dtype)


# -- gridworld dynamics ------------------------------------------------------


class TestGridWorld:
    SEED = 4

    def make(self):
        return make_device_env("device_grid_small")

    def layout(self, env, seed, episode=0):
        return [int(v) for v in env._layout(jnp.int32(seed),
                                            jnp.int32(episode))]

    def state_at(self, env, seed, row, col, has_key=0, door_open=0,
                 step=0):
        return DeviceGridState(
            seed=_batched(seed), episode=_batched(0),
            step=_batched(step),
            episode_return=_batched(0.0, jnp.float32),
            episode_step=_batched(step), row=_batched(row),
            col=_batched(col), has_key=_batched(has_key),
            door_open=_batched(door_open))

    def step(self, env, state, action):
        state, out = jax.jit(env.step)(state, _batched(action))
        return state, out

    def toward(self, fr, fc, tr, tc):
        """The action moving one cell from (fr, fc) to (tr, tc)."""
        if tr == fr - 1:
            return 0  # up
        if tr == fr + 1:
            return 1  # down
        if tc == fc - 1:
            return 2  # left
        assert tc == fc + 1
        return 3  # right

    def key_neighbor(self, env, seed):
        """A near-side cell adjacent to the key (not the wall)."""
        wall, door, ar, ac, kr, kc, gr, gc = self.layout(env, seed)
        g = env.grid_size
        for dr, dc in ((-1, 0), (1, 0), (0, -1), (0, 1)):
            r, c = kr + dr, kc + dc
            if 0 <= r < g and 0 <= c < wall:
                return (r, c), (kr, kc)
        raise AssertionError("key has no free near-side neighbor")

    def test_key_pickup_rewards_and_disappears(self):
        env = self.make()
        (r, c), (kr, kc) = self.key_neighbor(env, self.SEED)
        state = self.state_at(env, self.SEED, r, c)
        # The key is visible (pure green cell) before pickup.
        frame_before = np.asarray(env.step(
            state, _batched(0))[1].observation.frame[0])
        state, out = self.step(env, self.state_at(env, self.SEED, r, c),
                               self.toward(r, c, kr, kc))
        assert float(out.reward[0]) == pytest.approx(0.5)
        assert int(state.has_key[0]) == 1
        assert int(state.row[0]) == kr and int(state.col[0]) == kc
        # Post-pickup frame: no free-key cell remains; the agent marker
        # at the window center brightens to the carrying value (192).
        frame_after = np.asarray(out.observation.frame[0])
        assert (frame_before[..., 1] == 255).any()
        assert not (frame_after[..., 1] == 255).any()
        assert (frame_after[..., 1] == 192).any()
        # Picking it up again is impossible: step off and back.
        state, out = self.step(env, state, self.toward(kr, kc, r, c))
        assert float(out.reward[0]) == 0.0
        state, out = self.step(env, state, self.toward(r, c, kr, kc))
        assert float(out.reward[0]) == 0.0

    def test_wall_blocks_and_door_needs_key(self):
        env = self.make()
        wall, door, *_ = self.layout(env, self.SEED)
        g = env.grid_size
        # A wall row that is not the door row.
        row = (door + 1) % g
        state = self.state_at(env, self.SEED, row, wall - 1)
        state, out = self.step(env, state, 3)  # right, into the wall
        assert int(state.col[0]) == wall - 1, "wall must block"
        assert float(out.reward[0]) == 0.0
        # The door cell without the key: also blocked.
        state = self.state_at(env, self.SEED, door, wall - 1)
        state, out = self.step(env, state, 3)
        assert int(state.col[0]) == wall - 1, "locked door must block"
        # With the key: passes, +0.5 exactly once.
        state = self.state_at(env, self.SEED, door, wall - 1, has_key=1)
        state, out = self.step(env, state, 3)
        assert int(state.col[0]) == wall
        assert float(out.reward[0]) == pytest.approx(0.5)
        assert int(state.door_open[0]) == 1
        # Back and through again: no second door bonus.
        state, out = self.step(env, state, 2)
        state, out = self.step(env, state, 3)
        assert int(state.col[0]) == wall
        assert float(out.reward[0]) == 0.0

    def test_goal_terminates_with_bonus_and_autoresets(self):
        env = self.make()
        wall, door, ar, ac, kr, kc, gr, gc = self.layout(env, self.SEED)
        g = env.grid_size
        for dr, dc in ((-1, 0), (1, 0), (0, -1), (0, 1)):
            r, c = gr + dr, gc + dc
            if 0 <= r < g and wall < c < g:
                break
        else:
            raise AssertionError("goal has no far-side neighbor")
        state = self.state_at(env, self.SEED, r, c, has_key=1,
                              door_open=1)
        state, out = self.step(env, state, self.toward(r, c, gr, gc))
        assert float(out.reward[0]) == pytest.approx(1.0)
        assert bool(out.done[0])
        # Emitted info includes the final step; the carried state is the
        # NEXT episode's start (episode 1, zeroed accounting).
        assert float(out.info.episode_return[0]) == pytest.approx(1.0)
        assert int(out.info.episode_step[0]) == 1
        assert int(state.episode[0]) == 1
        assert int(state.step[0]) == 0
        assert int(state.has_key[0]) == 0

    def test_horizon_truncates_without_bonus(self):
        env = self.make()
        wall, door, ar, ac, *_ = self.layout(env, self.SEED)
        state = self.state_at(env, self.SEED, ar, ac,
                              step=env.episode_length - 1)
        state, out = self.step(env, state, 0)
        assert bool(out.done[0])
        assert float(out.reward[0]) < 1.0
        assert int(state.episode[0]) == 1

    def test_layouts_vary_by_episode_and_stay_solvable(self):
        env = DeviceGridWorld(grid_size=11, view=5, episode_length=96)
        layouts = {tuple(self.layout(env, 9, ep)) for ep in range(16)}
        assert len(layouts) > 8, "layout hash is not varying by episode"
        g = env.grid_size
        for wall, door, ar, ac, kr, kc, gr, gc in layouts:
            assert 2 <= wall <= g - 3
            assert 0 <= door < g
            assert ac < wall and kc < wall, "agent+key on the near side"
            assert gc > wall, "goal behind the wall"
            assert (ar, ac) != (kr, kc)


# -- minatar breakout dynamics -----------------------------------------------


class TestBreakout:
    def make(self, **kwargs):
        return make_device_env("device_minatar_breakout", **kwargs)

    def base_state(self, env, **overrides):
        state, _ = env.initial(np.asarray([2], np.int32))
        fields = {}
        for name, value in overrides.items():
            if name == "bricks":
                fields[name] = jnp.asarray([value], jnp.int32)
            else:
                fields[name] = _batched(value)
        return state._replace(**fields)

    def step(self, env, state, action):
        return jax.jit(env.step)(state, _batched(action))

    def test_paddle_moves_and_clamps(self):
        env = self.make()
        state = self.base_state(env, paddle_c=0, ball_r=3, dir_r=1)
        state, _ = self.step(env, state, 1)  # left at the edge
        assert int(state.paddle_c[0]) == 0
        state, _ = self.step(env, state, 2)  # right
        assert int(state.paddle_c[0]) == 1

    def test_paddle_saves_the_ball(self):
        env = self.make()
        # Ball one row above the bottom, falling right into the paddle.
        state = self.base_state(env, ball_r=8, ball_c=4, dir_r=1,
                                dir_c=1, paddle_c=5)
        state, out = self.step(env, state, 0)
        assert not bool(out.done[0])
        assert int(state.dir_r[0]) == -1, "save must bounce upward"
        assert int(state.ball_r[0]) == 8

    def test_missed_ball_ends_the_episode(self):
        env = self.make()
        state = self.base_state(env, ball_r=8, ball_c=4, dir_r=1,
                                dir_c=1, paddle_c=0)
        state, out = self.step(env, state, 0)
        assert bool(out.done[0])
        assert int(state.episode[0]) == 1  # auto-reset into episode 1

    def test_brick_hit_scores_and_bounces(self):
        env = self.make()
        # Ball at row 4 center, moving up into the brick wall's row 3.
        state = self.base_state(env, ball_r=4, ball_c=4, dir_r=-1,
                                dir_c=1)
        before = np.asarray(state.bricks[0]).sum()
        state, out = self.step(env, state, 0)
        assert float(out.reward[0]) == pytest.approx(1.0)
        assert np.asarray(state.bricks[0]).sum() == before - 1
        assert int(state.dir_r[0]) == 1, "brick hit bounces downward"

    def test_cleared_wall_respawns(self):
        env = self.make()
        bricks = np.zeros((3, 10), np.int32)
        bricks[2, 5] = 1  # one brick left, straight above the ball
        state = self.base_state(env, ball_r=4, ball_c=4, dir_r=-1,
                                dir_c=1, bricks=bricks)
        state, out = self.step(env, state, 0)
        assert float(out.reward[0]) == pytest.approx(1.0)
        assert np.asarray(state.bricks[0]).sum() == 30, "next wave"

    def test_sticky_actions_change_the_trajectory(self):
        plain = self.make()
        sticky = self.make(sticky_prob=0.7)
        seeds = np.asarray([3, 5, 9, 12], np.int32)
        actions = jnp.asarray(np.random.default_rng(0).integers(
            0, 3, size=(40, 4)).astype(np.int32))

        def rollout(env):
            state, _ = env.initial(seeds)
            return jax.jit(lambda s, a: jax.lax.scan(env.step, s, a))(
                state, actions)[1]

        frames_plain = np.asarray(rollout(plain).observation.frame)
        frames_sticky = np.asarray(rollout(sticky).observation.frame)
        assert (frames_plain != frames_sticky).any(), (
            "sticky_prob=0.7 never repeated an action over 160 steps")


# -- minatar asterix dynamics ------------------------------------------------


class TestAsterix:
    def make(self):
        return make_device_env("device_minatar_asterix")

    def with_entity(self, env, gold, player=(5, 5), ent=(5, 4),
                    direction=1):
        state, _ = env.initial(np.asarray([2], np.int32))
        slots = np.zeros((1, 8), np.int32)
        slots[0, 0] = 1
        ent_r = np.zeros((1, 8), np.int32)
        ent_r[0, 0] = ent[0]
        ent_c = np.zeros((1, 8), np.int32)
        ent_c[0, 0] = ent[1]
        ent_dir = np.ones((1, 8), np.int32)
        ent_dir[0, 0] = direction
        ent_gold = np.zeros((1, 8), np.int32)
        ent_gold[0, 0] = gold
        return state._replace(
            player_r=_batched(player[0]), player_c=_batched(player[1]),
            ent_active=jnp.asarray(slots), ent_r=jnp.asarray(ent_r),
            ent_c=jnp.asarray(ent_c), ent_dir=jnp.asarray(ent_dir),
            ent_gold=jnp.asarray(ent_gold))

    def test_gold_scores_and_frees_the_slot(self):
        env = self.make()
        state = self.with_entity(env, gold=1)  # moves 4 -> 5 onto player
        state, out = jax.jit(env.step)(state, _batched(0))
        assert float(out.reward[0]) == pytest.approx(1.0)
        assert not bool(out.done[0])
        assert int(state.ent_active[0, 0]) == 0

    def test_enemy_ends_the_episode(self):
        env = self.make()
        state = self.with_entity(env, gold=0)
        state, out = jax.jit(env.step)(state, _batched(0))
        assert bool(out.done[0])
        assert float(out.reward[0]) == 0.0
        assert int(state.episode[0]) == 1

    def test_swap_collision_does_not_phase_through(self):
        """Player and enemy exchanging cells in one sub-step collide
        (the MinAtar pre-move + post-move check) — no phasing."""
        env = self.make()
        # Player at (5, 6) moves left onto (5, 5); the enemy at (5, 5)
        # moves right onto (5, 6): a swap.
        state = self.with_entity(env, gold=0, player=(5, 6), ent=(5, 5),
                                 direction=1)
        state, out = jax.jit(env.step)(state, _batched(3))  # left
        assert bool(out.done[0]), "swap with an enemy must terminate"
        # Same swap against gold: collected, not streamed through.
        state = self.with_entity(env, gold=1, player=(5, 6), ent=(5, 5),
                                 direction=1)
        state, out = jax.jit(env.step)(state, _batched(3))
        assert float(out.reward[0]) == pytest.approx(1.0)
        assert int(state.ent_active[0, 0]) == 0

    def test_converging_golds_pay_per_entity(self):
        env = self.make()
        state = self.with_entity(env, gold=1)  # slot 0: (5,4) dir +1
        # Slot 1: a second gold converging from the right, (5,6) dir -1.
        fields = {}
        for name, value in (("ent_active", 1), ("ent_r", 5),
                            ("ent_c", 6), ("ent_dir", -1),
                            ("ent_gold", 1)):
            arr = np.array(getattr(state, name))
            arr[0, 1] = value
            fields[name] = jnp.asarray(arr)
        state = state._replace(**fields)
        state, out = jax.jit(env.step)(state, _batched(0))
        assert float(out.reward[0]) == pytest.approx(2.0)
        assert int(np.asarray(state.ent_active)[0, :2].sum()) == 0

    def test_entities_stream_and_despawn_at_the_edge(self):
        env = self.make()
        state = self.with_entity(env, gold=0, player=(1, 0),
                                 ent=(5, 9), direction=1)
        state, out = jax.jit(env.step)(state, _batched(0))
        assert int(state.ent_active[0, 0]) == 0, (
            "entity leaving the grid must free its slot")


# -- host twins (the device_ registry family) --------------------------------


class TestHostTwin:
    @pytest.mark.parametrize("level", ["device_grid_small",
                                       "device_minatar_breakout"])
    def test_impala_stream_matches_device_rollout(self, level):
        """ImpalaStream(StreamAdapter(HostDeviceEnv)) == the device
        env's own [B=1] stream, bit for bit — by construction, and now
        by test."""
        from scalable_agent_tpu.envs import make_impala_stream

        seed = 6
        stream = make_impala_stream(level, seed=seed)
        env = make_device_env(level)
        state, out = env.initial(np.asarray([seed], np.int32))
        step = jax.jit(env.step)
        try:
            host = stream.initial()
            rng = np.random.default_rng(1)
            for t in range(60):
                np.testing.assert_array_equal(
                    np.asarray(out.observation.frame[0]),
                    np.asarray(host.observation.frame),
                    err_msg=f"frame mismatch at t={t}")
                assert bool(out.done[0]) == bool(host.done), t
                np.testing.assert_allclose(
                    float(out.reward[0]), float(host.reward), rtol=1e-6)
                np.testing.assert_allclose(
                    float(out.info.episode_return[0]),
                    float(host.info.episode_return), rtol=1e-6)
                assert (int(out.info.episode_step[0])
                        == int(host.info.episode_step)), t
                action = int(rng.integers(0, env.num_actions))
                state, out = step(state, np.asarray([action], np.int32))
                host = stream.step(action)
        finally:
            stream.close()

    def test_probe_env_serves_device_levels(self):
        """The driver's probe path works for device-native levels via
        the registry's device_ family."""
        from scalable_agent_tpu.driver import probe_env

        config = Config(level_name="device_minatar_asterix")
        observation_spec, action_space, num_agents = probe_env(config)
        env = make_device_env("device_minatar_asterix")
        assert tuple(observation_spec.frame.shape) == tuple(
            env.observation_spec.frame.shape)
        assert action_space.n == env.num_actions
        assert num_agents == 1

    def test_registry_defaults_come_from_device_levels(self):
        """Satellite: the fake family's host defaults READ the
        DEVICE_LEVELS entries — mutate the registry entry, observe the
        host factory follow."""
        from scalable_agent_tpu.envs.device.protocol import DEVICE_LEVELS
        from scalable_agent_tpu.envs.registry import create_env

        entry = DEVICE_LEVELS["fake_small"]
        original = dict(entry.defaults)
        try:
            entry.defaults["height"] = 24
            env = create_env("fake_small")
            assert env.observation_spec.frame.shape[0] == 24
        finally:
            entry.defaults.clear()
            entry.defaults.update(original)


# -- driver end-to-end (the ISSUE 15 acceptance smokes) ----------------------


def _ingraph_config(tmp_path, level, **overrides):
    base = dict(
        mode="train",
        logdir=str(tmp_path / "run"),
        level_name=level,
        train_backend="ingraph",
        num_actors=4,
        batch_size=4,
        unroll_length=5,
        num_action_repeats=1,
        total_environment_frames=160,  # 8 updates of 20 frames
        compute_dtype="float32",
        checkpoint_interval_s=1e9,
        log_interval_s=0.0,
        seed=7,
    )
    base.update(overrides)
    return Config(**base)


def _prom_values(path):
    out = {}
    for line in open(path):
        if line.startswith("#") or " " not in line:
            continue
        key, _, value = line.rpartition(" ")
        try:
            out[key] = float(value)
        except ValueError:
            pass
    return out


@pytest.mark.parametrize("level,updates_per_dispatch", [
    ("device_grid_small", 2),
    ("device_minatar_breakout", 4),
])
def test_ingraph_driver_trains_device_world(tmp_path, level,
                                            updates_per_dispatch):
    """The acceptance smoke: a REAL device world trains end-to-end via
    --train_backend=ingraph under the megaloop — complete
    conservation-checked ledger artifact, devtel/env/* episodes > 0,
    coherent training metrics."""
    from scalable_agent_tpu import driver
    from scalable_agent_tpu.obs import get_registry

    config = _ingraph_config(tmp_path, level,
                             updates_per_dispatch=updates_per_dispatch)

    def _counters():
        snap = get_registry().snapshot()
        return {key: snap.get(f"ledger/trajectories_{key}_total", 0.0)
                for key in ("opened", "retired", "discarded",
                            "abandoned")}

    before = _counters()
    metrics = driver.train(config)
    assert metrics["env_frames"] == 160
    assert np.isfinite(metrics["total_loss"])

    # Ledger: one record per DISPATCH, all retired, conservation holds
    # on this run's deltas (the registry is process-global).
    delta = {key: value - before[key]
             for key, value in _counters().items()}
    dispatches = 8 // updates_per_dispatch
    assert delta["opened"] == dispatches
    assert delta["opened"] == (delta["retired"] + delta["discarded"]
                               + delta["abandoned"])
    paths = glob.glob(os.path.join(config.logdir, "ledger.p0.json"))
    assert len(paths) == 1, paths
    artifact = json.load(open(paths[0]))
    assert artifact["open_records"] == []

    # Device telemetry: the env's episode stream surfaced through the
    # prom plane with real episodes (both worlds finish episodes well
    # inside 40 agent steps/env).
    values = _prom_values(os.path.join(config.logdir, "metrics.prom"))
    assert values["impala_devtel_env_episodes"] > 0
    assert values["impala_devtel_env_steps"] == 160.0
    assert values["impala_devtel_learner_updates"] == 8.0

    # Training rows made it to disk.
    rows = [json.loads(line) for line in
            open(os.path.join(config.logdir, "metrics.jsonl"))]
    assert any("total_loss" in r for r in rows)


@pytest.mark.slow
def test_ingraph_driver_megaloop_resume_is_deterministic(tmp_path):
    """Checkpoint/resume under K > 1 continues the exact rng stream:
    the same interrupted 4+4-update schedule (K=2) run twice ends
    bit-identical.  (Resumed != uninterrupted by design — the device
    env rollout restarts from fresh episodes on restore, like the host
    pipeline's env processes.)"""
    from scalable_agent_tpu import driver

    def interrupted(logdir):
        for total_frames in (80.0, 160.0):
            config = _ingraph_config(
                tmp_path, "device_grid_small", logdir=str(logdir),
                updates_per_dispatch=2,
                total_environment_frames=total_frames,
                checkpoint_interval_s=0.0)  # checkpoint every dispatch
            metrics = driver.train(config)
        assert metrics["env_frames"] == 160
        return metrics

    m_a = interrupted(tmp_path / "a")
    m_b = interrupted(tmp_path / "b")
    assert m_a["total_loss"] == m_b["total_loss"]
    assert m_a["grad_norm"] == m_b["grad_norm"]


def test_driver_rejects_megaloop_on_host_backend():
    from scalable_agent_tpu.driver import build_training_learner
    from scalable_agent_tpu.models import ImpalaAgent

    config = Config(train_backend="host", updates_per_dispatch=2)
    with pytest.raises(ValueError, match="updates_per_dispatch"):
        build_training_learner(config, ImpalaAgent(num_actions=4))


def test_driver_rejects_megaloop_with_replay(tmp_path):
    from scalable_agent_tpu import driver

    config = _ingraph_config(tmp_path, "device_grid_small",
                             updates_per_dispatch=2, replay_ratio=1)
    with pytest.raises(ValueError, match="updates_per_dispatch"):
        driver.train(config)


# -- learning: return must RISE on the real world ----------------------------


def test_device_grid_learning_improves():
    """The ISSUE 15 learning smoke: a short real training run on
    device_grid_small (CNN+LSTM from pixels, sparse key/door/goal
    rewards) lifts mean episode return well clear of the random
    policy's.  Hyperparameters are tuned for short-horizon credit
    assignment (discounting 0.95 against 24-step episodes); the run is
    CPU-deterministic at this fixed seed, measured at early 0.45 →
    late 0.66 — thresholds sit at ~half the measured margin to absorb
    software-stack drift."""
    from scalable_agent_tpu.models import ImpalaAgent
    from scalable_agent_tpu.parallel import MeshSpec, make_mesh
    from scalable_agent_tpu.runtime import (
        InGraphTrainer, Learner, LearnerHyperparams)

    unroll, batch, updates, k = 16, 32, 160, 8
    env = make_device_env("device_grid_small")
    agent = ImpalaAgent(num_actions=env.num_actions)
    mesh = make_mesh(MeshSpec(data=1, model=1),
                     devices=jax.devices()[:1])
    hp = LearnerHyperparams(
        # 4x headroom: the linear LR decay must not hit zero mid-run.
        total_environment_frames=float(4 * updates * unroll * batch),
        learning_rate=0.003, entropy_cost=0.006, discounting=0.95)
    learner = Learner(agent, hp, mesh,
                      frames_per_update=unroll * batch)
    trainer = InGraphTrainer(agent, learner, env, unroll, batch,
                             seed=3, updates_per_dispatch=k)
    state, carry = trainer.init(jax.random.key(3))
    returns = []
    for u in range(0, updates, k):
        state, carry, m = trainer.run(state, carry, k, counter_start=u)
        if float(np.asarray(m["episodes_completed"])) > 0:
            returns.append(float(np.asarray(m["episode_return"])))
    third = len(returns) // 3
    early = float(np.mean(returns[:third]))
    late = float(np.mean(returns[-third:]))
    assert late >= early + 0.10, (
        f"return did not improve on device_grid_small: early "
        f"{early:.3f} late {late:.3f}")
    assert late >= 0.55, (
        f"final return {late:.3f} stayed near the random policy's")


# -- the token world (``token_recall*``) ---------------------------------------

TOKEN_ENVS = 4


def roll(env, actions, seeds):
    state, first = env.initial(seeds)
    _, outs = jax.lax.scan(env.step, state, actions)
    return first, outs


def test_the_stream_ignores_the_action():
    env = make_device_env("token_recall_small")
    seeds = np.arange(TOKEN_ENVS, dtype=np.int32) + 1
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.integers(0, 64, (40, TOKEN_ENVS)), jnp.int32)
    b = jnp.asarray(rng.integers(0, 64, (40, TOKEN_ENVS)), jnp.int32)
    _, outs_a = roll(env, a, seeds)
    _, outs_b = roll(env, b, seeds)
    np.testing.assert_array_equal(outs_a.observation.frame,
                                  outs_b.observation.frame)
    np.testing.assert_array_equal(outs_a.done, outs_b.done)
    assert not np.array_equal(outs_a.reward, outs_b.reward)


def test_a_position_past_the_period_repeats():
    env = make_device_env("token_recall_small")
    seeds = np.zeros((1,), np.int32) + 7
    first, outs = roll(env, jnp.zeros((15, 1), jnp.int32), seeds)
    tokens = np.concatenate([np.asarray(first.observation.frame)[None],
                             np.asarray(outs.observation.frame)])[:, 0]
    np.testing.assert_array_equal(tokens[10:16], tokens[0:6])


def test_the_references_world_emits_the_programs_tokens():
    """The tiny world every family's preset acts in, against the first
    family's reference's copy (the cells' own worlds, a case a family:
    tests/family_suite.py)."""
    from benchmark.lib import manifest

    ref = manifest.load_module(
        os.path.join(manifest.BENCH_DIR, "references", "afmoe_token.py"),
        "reference_afmoe_token_worlds")
    world = {"name": "token_recall_small", "vocab_size": 64,
             "episode_length": 16, "period": 10}
    env = make_device_env(world["name"])
    seeds = np.arange(TOKEN_ENVS, dtype=np.int32) + 1
    rng = np.random.default_rng(4)
    actions = jnp.asarray(
        rng.integers(0, world["vocab_size"], (40, TOKEN_ENVS)), jnp.int32)
    first, outs = roll(env, actions, seeds)
    state, (reward, done, token) = ref.world_initial(world, seeds)
    np.testing.assert_array_equal(first.observation.frame, token)
    np.testing.assert_array_equal(first.done, done)
    for t in range(actions.shape[0]):
        state, (reward, done, token) = ref.world_step(
            world, state, actions[t])
        np.testing.assert_array_equal(outs.observation.frame[t], token)
        np.testing.assert_array_equal(outs.reward[t], reward)
        np.testing.assert_array_equal(outs.done[t], done)
