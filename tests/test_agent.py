"""Agent model tests.

Mirrors what the reference relies on but never unit-tests (its Agent has no
test file): unroll shapes, step/unroll equivalence, done-triggered state
reset, and the instruction encoder's length masking.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scalable_agent_tpu.models import ImpalaAgent, actor_step, initial_state
from scalable_agent_tpu.models.instruction import (
    InstructionEncoder,
    hash_instruction,
)
from scalable_agent_tpu.types import Observation, StepOutput, StepOutputInfo

NUM_ACTIONS = 5
FRAME = (16, 16, 3)


def make_env_outputs(rng, unroll_len, batch, done=None, instruction=False):
    frame = rng.integers(0, 256, (unroll_len, batch) + FRAME, dtype=np.uint8)
    if done is None:
        done = np.zeros((unroll_len, batch), bool)
    instr = (
        rng.integers(0, 10, (unroll_len, batch, 4), dtype=np.int32)
        if instruction else None)
    return StepOutput(
        reward=rng.standard_normal((unroll_len, batch)).astype(np.float32),
        info=StepOutputInfo(
            episode_return=np.zeros((unroll_len, batch), np.float32),
            episode_step=np.zeros((unroll_len, batch), np.int32)),
        done=done,
        observation=Observation(frame=frame, instruction=instr),
    )


def init_agent(**kwargs):
    agent = ImpalaAgent(num_actions=NUM_ACTIONS, **kwargs)
    rng = np.random.default_rng(0)
    env_outputs = make_env_outputs(
        rng, 1, 1, instruction=kwargs.get("use_instruction", False))
    actions = np.zeros((1, 1), np.int32)
    params = agent.init(
        jax.random.key(0), actions, env_outputs, initial_state(1))
    return agent, params


class TestUnroll:
    def test_shapes(self):
        agent, params = init_agent()
        rng = np.random.default_rng(1)
        unroll_len, batch = 7, 3
        env_outputs = make_env_outputs(rng, unroll_len, batch)
        actions = rng.integers(0, NUM_ACTIONS, (unroll_len, batch)).astype(
            np.int32)
        (logits, baseline), state = agent.apply(
            params, actions, env_outputs, initial_state(batch))
        assert logits.shape == (unroll_len, batch, NUM_ACTIONS)
        assert baseline.shape == (unroll_len, batch)
        assert state.c.shape == (batch, 256)
        assert state.h.shape == (batch, 256)

    def test_unroll_equals_stepwise(self):
        """T-step unroll == T sequential 1-step unrolls (shared weights),

        the property the reference gets from sharing Agent.unroll between
        actor and learner (reference: experiment.py:212-237)."""
        agent, params = init_agent()
        rng = np.random.default_rng(2)
        unroll_len, batch = 5, 2
        done = rng.random((unroll_len, batch)) < 0.3
        env_outputs = make_env_outputs(rng, unroll_len, batch, done=done)
        actions = rng.integers(0, NUM_ACTIONS, (unroll_len, batch)).astype(
            np.int32)

        (full_logits, full_baseline), full_state = agent.apply(
            params, actions, env_outputs, initial_state(batch))

        state = initial_state(batch)
        for t in range(unroll_len):
            step_outputs = jax.tree_util.tree_map(
                lambda x: x[t:t + 1] if x is not None else None,
                env_outputs, is_leaf=lambda x: x is None)
            (logits, baseline), state = agent.apply(
                params, actions[t:t + 1], step_outputs, state)
            np.testing.assert_allclose(
                logits[0], full_logits[t], rtol=2e-5, atol=2e-5)
            np.testing.assert_allclose(
                baseline[0], full_baseline[t], rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(state.c, full_state.c, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(state.h, full_state.h, rtol=2e-5, atol=2e-5)

    def test_done_resets_state(self):
        """A done at step t erases all dependence on pre-t history

        (reference: experiment.py:230-234)."""
        agent, params = init_agent()
        rng = np.random.default_rng(3)
        unroll_len, batch = 4, 1
        done = np.zeros((unroll_len, batch), bool)
        done[2] = True  # episode boundary before step 2's core update
        env_outputs = make_env_outputs(rng, unroll_len, batch, done=done)
        actions = rng.integers(0, NUM_ACTIONS, (unroll_len, batch)).astype(
            np.int32)

        # Same trajectory but with a *different random* pre-boundary history.
        alt = make_env_outputs(rng, unroll_len, batch, done=done)
        alt_frames = np.array(alt.observation.frame)
        alt_frames[2:] = np.asarray(env_outputs.observation.frame)[2:]
        alt_rewards = np.array(alt.reward)
        alt_rewards[2:] = np.asarray(env_outputs.reward)[2:]
        alt = alt._replace(
            reward=alt_rewards,
            observation=alt.observation._replace(frame=alt_frames))
        alt_actions = rng.integers(
            0, NUM_ACTIONS, (unroll_len, batch)).astype(np.int32)
        alt_actions[2:] = actions[2:]

        (_, _), state_a = agent.apply(
            params, actions, env_outputs, initial_state(batch))
        (_, _), state_b = agent.apply(
            params, alt_actions, alt, initial_state(batch))
        # Post-boundary inputs agree ⇒ final states agree despite different
        # pre-boundary history... but ONLY if done resets the core.
        np.testing.assert_allclose(state_a.h, state_b.h, rtol=1e-5, atol=1e-5)

        # Sanity: without the boundary the histories would diverge.
        no_done = np.zeros((unroll_len, batch), bool)
        (_, _), state_c = agent.apply(
            params, actions, env_outputs._replace(done=no_done),
            initial_state(batch))
        (_, _), state_d = agent.apply(
            params, alt_actions, alt._replace(done=no_done),
            initial_state(batch))
        assert not np.allclose(state_c.h, state_d.h, rtol=1e-5, atol=1e-5)

    def test_resnet_torso(self):
        agent, params = init_agent(torso_type="resnet")
        rng = np.random.default_rng(4)
        env_outputs = make_env_outputs(rng, 2, 2)
        actions = np.zeros((2, 2), np.int32)
        (logits, baseline), _ = agent.apply(
            params, actions, env_outputs, initial_state(2))
        assert logits.shape == (2, 2, NUM_ACTIONS)
        assert baseline.shape == (2, 2)

    def test_instruction_conditioning(self):
        agent, params = init_agent(use_instruction=True)
        rng = np.random.default_rng(5)
        env_outputs = make_env_outputs(rng, 2, 2, instruction=True)
        actions = np.zeros((2, 2), np.int32)
        (logits, _), _ = agent.apply(
            params, actions, env_outputs, initial_state(2))
        # Different instructions must change the policy.
        obs = env_outputs.observation
        other = env_outputs._replace(observation=obs._replace(
            instruction=np.asarray(obs.instruction) + 1))
        (logits2, _), _ = agent.apply(
            params, actions, other, initial_state(2))
        assert not np.allclose(logits, logits2)


class TestActorStep:
    def test_shapes_and_determinism(self):
        agent, params = init_agent()
        rng = np.random.default_rng(6)
        batch = 4
        env_outputs = make_env_outputs(rng, 1, batch)
        env_output = jax.tree_util.tree_map(
            lambda x: x[0] if x is not None else None,
            env_outputs, is_leaf=lambda x: x is None)
        out, state = actor_step(
            agent, params, jax.random.key(0),
            np.zeros((batch,), np.int32), env_output, initial_state(batch))
        assert out.action.shape == (batch,)
        assert out.action.dtype == jnp.int32
        assert out.policy_logits.shape == (batch, NUM_ACTIONS)
        assert out.baseline.shape == (batch,)
        assert state.c.shape == (batch, 256)
        # Same key ⇒ same sample; different key ⇒ (almost surely) may differ.
        out2, _ = actor_step(
            agent, params, jax.random.key(0),
            np.zeros((batch,), np.int32), env_output, initial_state(batch))
        np.testing.assert_array_equal(out.action, out2.action)

    def test_actions_within_range(self):
        agent, params = init_agent()
        rng = np.random.default_rng(7)
        batch = 8
        env_output = jax.tree_util.tree_map(
            lambda x: x[0] if x is not None else None,
            make_env_outputs(rng, 1, batch),
            is_leaf=lambda x: x is None)
        for seed in range(3):
            out, _ = actor_step(
                agent, params, jax.random.key(seed),
                np.zeros((batch,), np.int32), env_output,
                initial_state(batch))
            assert np.all((np.asarray(out.action) >= 0)
                          & (np.asarray(out.action) < NUM_ACTIONS))


class TestInstructionEncoder:
    def test_padding_is_ignored(self):
        enc = InstructionEncoder()
        ids = np.array([[3, 7, 0, 0]], np.int32)
        params = enc.init(jax.random.key(0), ids)
        out = enc.apply(params, ids)
        assert out.shape == (1, 64)
        # Changing only the padded tail must not change the encoding...
        ids_b = np.array([[3, 7, 0, 0]], np.int32)
        np.testing.assert_allclose(
            out, enc.apply(params, ids_b), rtol=1e-6)
        # ...while changing a real token must.
        ids_c = np.array([[3, 9, 0, 0]], np.int32)
        assert not np.allclose(out, enc.apply(params, ids_c))

    def test_hash_instruction(self):
        ids = hash_instruction("go to the red door")
        assert ids.shape == (16,)
        assert ids.dtype == np.int32
        assert np.all(ids[:5] > 0) and np.all(ids[5:] == 0)
        # Deterministic and word-order-sensitive.
        np.testing.assert_array_equal(ids, hash_instruction(
            "go to the red door"))
        assert not np.array_equal(ids, hash_instruction(
            "go to the blue door"))
        # Empty instruction (Doom/Atari path) is all padding.
        assert np.all(hash_instruction("") == 0)


class TestPallasCore:
    """The fused Pallas LSTM core (ops/lstm_pallas.py) must be a drop-in
    for the nn.scan path: identical param tree, identical init values,
    matching outputs and gradients on the same params."""

    def test_param_trees_identical(self):
        _, params_xla = init_agent(core_impl="xla")
        _, params_pal = init_agent(core_impl="pallas")
        flat_x = jax.tree_util.tree_flatten_with_path(params_xla)[0]
        flat_p = jax.tree_util.tree_flatten_with_path(params_pal)[0]
        assert [p for p, _ in flat_x] == [p for p, _ in flat_p]
        for (path, a), (_, b) in zip(flat_x, flat_p):
            assert a.shape == b.shape, path
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), err_msg=str(path),
                rtol=1e-6, atol=1e-7)

    def test_forward_parity_with_done_resets(self):
        agent_x, params = init_agent(core_impl="xla")
        agent_p = ImpalaAgent(num_actions=NUM_ACTIONS, core_impl="pallas")
        rng = np.random.default_rng(2)
        unroll_len, batch = 9, 4
        done = rng.random((unroll_len, batch)) < 0.3
        env_outputs = make_env_outputs(rng, unroll_len, batch, done=done)
        actions = rng.integers(0, NUM_ACTIONS, (unroll_len, batch)).astype(
            np.int32)
        state0 = initial_state(batch)
        (lx, bx), sx = agent_x.apply(params, actions, env_outputs, state0)
        (lp, bp), sp = agent_p.apply(params, actions, env_outputs, state0)
        np.testing.assert_allclose(np.asarray(lp), np.asarray(lx),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(bp), np.asarray(bx),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(sp.c), np.asarray(sx.c),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(sp.h), np.asarray(sx.h),
                                   rtol=1e-4, atol=1e-5)

    def test_grad_parity(self):
        agent_x, params = init_agent(core_impl="xla")
        agent_p = ImpalaAgent(num_actions=NUM_ACTIONS, core_impl="pallas")
        rng = np.random.default_rng(3)
        unroll_len, batch = 6, 3
        done = rng.random((unroll_len, batch)) < 0.2
        env_outputs = make_env_outputs(rng, unroll_len, batch, done=done)
        actions = rng.integers(0, NUM_ACTIONS, (unroll_len, batch)).astype(
            np.int32)
        state0 = initial_state(batch)

        def loss(agent):
            def fn(p):
                (logits, baseline), state = agent.apply(
                    p, actions, env_outputs, state0)
                return (jnp.sum(logits * logits) + jnp.sum(baseline)
                        + jnp.sum(state.c) + jnp.sum(state.h))
            return fn

        gx = jax.grad(loss(agent_x))(params)
        gp = jax.grad(loss(agent_p))(params)
        flat_x = jax.tree_util.tree_flatten_with_path(gx)[0]
        flat_p = jax.tree_util.tree_flatten_with_path(gp)[0]
        for (path, a), (_, b) in zip(flat_x, flat_p):
            np.testing.assert_allclose(
                np.asarray(b), np.asarray(a), err_msg=str(path),
                rtol=2e-3, atol=1e-4)

    def test_unknown_core_impl_raises(self):
        with pytest.raises(ValueError, match="core_impl"):
            init_agent(core_impl="bogus")

    def test_bf16_matmul_core_close_and_grads_finite(self):
        """core_matmul_dtype="bfloat16" (MXU mixed precision,
        ops/lstm_pallas.py) tracks the f32 core within bf16 rounding and
        keeps gradients finite — the opt-in knob behind the r3 MFU push
        (VERDICT item 7)."""
        agent_x, params = init_agent(core_impl="xla")
        agent_b = ImpalaAgent(num_actions=NUM_ACTIONS, core_impl="pallas",
                              core_matmul_dtype="bfloat16")
        rng = np.random.default_rng(4)
        unroll_len, batch = 7, 4
        done = rng.random((unroll_len, batch)) < 0.25
        env_outputs = make_env_outputs(rng, unroll_len, batch, done=done)
        actions = rng.integers(0, NUM_ACTIONS, (unroll_len, batch)).astype(
            np.int32)
        state0 = initial_state(batch)
        (lx, bx), sx = agent_x.apply(params, actions, env_outputs, state0)
        (lb, bb), sb = agent_b.apply(params, actions, env_outputs, state0)
        # bf16 operands: ~1e-2 relative tolerance (8-bit mantissa),
        # carries stay f32 so drift does not compound catastrophically.
        np.testing.assert_allclose(np.asarray(lb), np.asarray(lx),
                                   rtol=0.1, atol=0.05)
        np.testing.assert_allclose(np.asarray(bb), np.asarray(bx),
                                   rtol=0.1, atol=0.05)
        np.testing.assert_allclose(np.asarray(sb.c), np.asarray(sx.c),
                                   rtol=0.1, atol=0.05)
        np.testing.assert_allclose(np.asarray(sb.h), np.asarray(sx.h),
                                   rtol=0.1, atol=0.05)

        def loss(p):
            (logits, baseline), state = agent_b.apply(
                p, actions, env_outputs, state0)
            return jnp.sum(logits * logits) + jnp.sum(baseline)

        grads = jax.grad(loss)(params)
        for leaf in jax.tree_util.tree_leaves(grads):
            assert np.isfinite(np.asarray(leaf)).all()

    def test_bad_matmul_dtype_raises(self):
        from scalable_agent_tpu.ops import lstm_pallas

        with pytest.raises(ValueError, match="matmul_dtype"):
            lstm_pallas.lstm_unroll(
                jnp.zeros((2, 2, 8), jnp.float32),
                jnp.zeros((2, 2), jnp.float32),
                jnp.zeros((2, 4), jnp.float32),
                jnp.zeros((2, 4), jnp.float32),
                jnp.zeros((8, 16), jnp.float32),
                jnp.zeros((4, 16), jnp.float32),
                jnp.zeros((16,), jnp.float32),
                True, "int8")


# (torso_type, conv_backend) -> where remat_torso puts the checkpoint
REMAT_PLACEMENTS = {
    ("resnet", "xla"): "stem",
    ("shallow", "xla"): "torso",
    ("shallow", "pallas"): "none",
}


@pytest.mark.parametrize("case", sorted(REMAT_PLACEMENTS), ids="-".join)
class TestRematTorso:
    """``remat_torso`` is handed to the torso, which places the
    checkpoint (ISSUE 27): same outputs, same gradients, same
    parameter tree — a checkpoint written with either value restores
    into the other."""

    def _agents(self, case):
        torso_type, conv_backend = case
        return [ImpalaAgent(num_actions=NUM_ACTIONS, torso_type=torso_type,
                            conv_backend=conv_backend, remat_torso=remat)
                for remat in (False, True)]

    def _inputs(self):
        rng = np.random.default_rng(3)
        done = rng.random((4, 2)) < 0.3
        return (rng.integers(0, NUM_ACTIONS, (4, 2)).astype(np.int32),
                make_env_outputs(rng, 4, 2, done=done), initial_state(2))

    def test_placement_and_parameter_tree(self, case):
        plain, remat = self._agents(case)
        assert plain.remat_placement == "none"
        assert remat.remat_placement == REMAT_PLACEMENTS[case]
        assert remat.remat_torso is True     # the probes read a bool
        trees = [
            {jax.tree_util.keystr(path): (leaf.shape, leaf.dtype)
             for path, leaf in jax.tree_util.tree_leaves_with_path(
                 agent.init(jax.random.key(0), *self._inputs()))}
            for agent in (plain, remat)]
        assert trees[0] == trees[1]
        stem = "downscale_0" if case[0] == "resnet" else "conv_0"
        assert f"['params']['convnet']['{stem}']['kernel']" in trees[1]

    def test_outputs_and_gradients_equal_to_the_bit(self, case):
        plain, remat = self._agents(case)
        inputs = self._inputs()
        params = plain.init(jax.random.key(0), *inputs)

        def loss(agent):
            def fn(p):
                (logits, baseline), state = agent.apply(p, *inputs)
                return (jnp.sum(logits ** 2) + jnp.sum(baseline ** 2)
                        + jnp.sum(state.h))
            return fn

        jax.tree_util.tree_map(
            np.testing.assert_array_equal,
            plain.apply(params, *inputs), remat.apply(params, *inputs))
        jax.tree_util.tree_map(
            np.testing.assert_array_equal,
            jax.grad(loss(plain))(params), jax.grad(loss(remat))(params))
