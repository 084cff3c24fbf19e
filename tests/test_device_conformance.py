"""The DeviceEnv conformance matrix: every registered level × every check.

The harness itself lives in envs/device/conformance.py (reusable outside
pytest); this file is its pytest surface plus the red-tests that prove
the checks have discriminating power — a harness that cannot fail a
broken env pins nothing.

``CONFORMANCE_LEVELS`` is EXPLICIT, not computed from the registry: the
registry-closure lint in tests/test_hotpath_lint.py cross-checks it
against DEVICE_LEVELS in both directions, so registering a new level
without adding its conformance parametrization fails the suite (and a
stale entry for a deleted level fails too).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from scalable_agent_tpu.envs.device import (
    DEVICE_LEVELS,
    DeviceFakeEnv,
    make_device_env,
)
from scalable_agent_tpu.envs.device import conformance

CONFORMANCE_LEVELS = (
    "device_grid_large",
    "device_grid_small",
    "device_minatar_asterix",
    "device_minatar_breakout",
    "fake_bandit",
    "fake_benchmark",
    "fake_memory",
    "fake_small",
    "token_recall",
    "token_recall_10k",
    "token_recall_14k",
    "token_recall_8k",
    "token_recall_long",
    "token_recall_small",
)


def test_conformance_levels_cover_the_registry():
    """Self-check mirroring the hotpath lint: the explicit tuple and
    the registry agree exactly."""
    assert set(CONFORMANCE_LEVELS) == set(DEVICE_LEVELS), (
        "CONFORMANCE_LEVELS and DEVICE_LEVELS diverged — every "
        "registered device level must carry the full conformance "
        "matrix (and only registered levels may appear here)")


@pytest.mark.parametrize("check", sorted(conformance.CHECKS))
@pytest.mark.parametrize("level", CONFORMANCE_LEVELS)
def test_level_conformance(level, check):
    conformance.CHECKS[check](lambda: make_device_env(level))


# -- edge cases over the harness itself --------------------------------------


def test_jittered_fake_runs_the_full_harness_at_the_seed_bound():
    """The length_jitter DeviceFakeEnv tightens its valid-seed bound to
    (2**31-1)//1000003 (the host-bigint mirror limit); the harness must
    pick its seeds INSIDE that bound — and still pin the bound's edge
    seed exactly."""
    def factory():
        return make_device_env("fake_small", length_jitter=3)

    env = factory()
    assert env.max_seed == (2**31 - 1) // 1000003
    seeds = conformance.conformance_seeds(env, 4)
    assert seeds.max() == env.max_seed  # the edge is IN the matrix
    assert (seeds >= 0).all() and (seeds <= env.max_seed).all()
    conformance.run_conformance(factory)


def test_sticky_action_breakout_passes_conformance():
    """The sticky-action option draws from the hashed counter stream,
    so stochasticity costs none of the protocol guarantees (notably
    bit-determinism)."""
    conformance.run_conformance(
        lambda: make_device_env("device_minatar_breakout",
                                sticky_prob=0.25))


def test_action_repeats_pass_conformance_on_a_real_world():
    conformance.run_conformance(
        lambda: make_device_env("device_grid_small",
                                num_action_repeats=3))


# -- red-tests: the harness can actually fail --------------------------------


class _BrokenAccountingEnv(DeviceFakeEnv):
    """Emits episode_step 0 on done rows — the classic accounting bug
    (`done & episode_step > 0` then undercounts every episode)."""

    def step(self, state, action):
        state, out = super().step(state, action)
        info = out.info._replace(
            episode_step=jnp.where(out.done, 0, out.info.episode_step))
        return state, out._replace(info=info)


def test_harness_catches_broken_episode_accounting():
    with pytest.raises(AssertionError, match="episode_step"):
        conformance.check_autoreset(
            lambda: _BrokenAccountingEnv(height=8, width=8,
                                         episode_length=5))


class _AliasedBufferEnv(DeviceFakeEnv):
    """initial() shares ONE buffer between two state leaves — the
    donation hazard the protocol's distinct-buffer rule exists for."""

    def initial(self, seeds):
        state, out = super().initial(seeds)
        return state._replace(episode=state.step), out


def test_harness_catches_aliased_initial_buffers():
    with pytest.raises(Exception, match="[Dd]onat"):
        conformance.check_donation(
            lambda: _AliasedBufferEnv(height=8, width=8,
                                      episode_length=5))


class _TraceLeakEnv(DeviceFakeEnv):
    """Bakes trace-time Python state into the program: each trace sees
    a different offset, so a re-traced (fresh-instance) rollout
    diverges — exactly the nondeterminism the check exists to catch."""

    _traces = [0]

    def step(self, state, action):
        self._traces[0] += 1
        state, out = super().step(state, action)
        frame = out.observation.frame + np.uint8(self._traces[0] % 7)
        return state, out._replace(
            observation=out.observation._replace(frame=frame))


def test_harness_catches_trace_dependent_state():
    _TraceLeakEnv._traces[0] = 0
    with pytest.raises(AssertionError, match="diverges"):
        conformance.check_determinism(
            lambda: _TraceLeakEnv(height=8, width=8, episode_length=5))
