"""Conformance harness: the DeviceEnv protocol, enforced mechanically.

Every level registered in DEVICE_LEVELS must pass every check here
(tests/test_device_conformance.py parametrizes the full matrix, and the
registry-closure lint in tests/test_hotpath_lint.py fails the suite if
a level is registered without a conformance parametrization).  The
checks are plain functions over an ``env_factory`` (a zero-arg callable
returning a FRESH env instance) so the bench and ad-hoc world authors
can run them outside pytest:

    from scalable_agent_tpu.envs.device import conformance
    conformance.run_conformance(lambda: MyWorld())

What is pinned (the protocol contract, envs/device/protocol.py):

- ``spec``: initial/step output shapes and dtypes match the declared
  spec for ANY seeds (seeds select content, never structure).
- ``determinism``: the trajectory is a bit-exact function of
  (seeds, actions) — identical across a per-step ``jit`` loop, a
  ``lax.scan``, and a fresh env instance.
- ``autoreset``: emitted-vs-carried episode accounting — emitted info
  includes the final step (``episode_step >= 1`` after initial, return
  sums the whole episode), the carried accounting restarts after done,
  and ``done & episode_step > 0`` is a valid finished-episode detector
  (initial's done=True rows carry step 0).
- ``zero_host_sync``: a compiled rollout issues no device→host
  materialization and no host→device transfer (the PR 12 spies +
  ``jax.transfer_guard("disallow")``).
- ``donation``: the full ``(state, output)`` carry donates cleanly,
  twice — no aliased buffers anywhere in the pytree (initial's
  distinct-buffer rule AND the step program's output buffers).
"""

import contextlib
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

__all__ = [
    "CHECKS",
    "check_autoreset",
    "check_determinism",
    "check_donation",
    "check_spec",
    "check_zero_host_sync",
    "conformance_seeds",
    "materialization_spy",
    "run_conformance",
]


def conformance_seeds(env, batch: int, salt: int = 0) -> np.ndarray:
    """A spread of valid seeds INCLUDING the env's documented
    ``max_seed`` bound (the length-jitter-bounded DeviceFakeEnv is the
    reason this is part of the harness: the bound edge must stay
    exact, not just small seeds).  ``salt`` selects a DIFFERENT
    multiset (not a permutation), so the spec check's two legs probe
    genuinely distinct seed values."""
    max_seed = int(getattr(env, "max_seed", 2**31 - 1))
    seeds = (np.arange(batch, dtype=np.int64) * (91757 + 2 * salt)
             + 7 + 104729 * salt) % (max_seed + 1)
    seeds[-1] = max_seed
    return seeds.astype(np.int32)


def _actions(env, batch: int, steps: int, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.integers(0, env.num_actions,
                        size=(steps, batch)).astype(np.int32)


def _scan_rollout(env):
    """jitted ``(state, actions [T, B]) -> (final_state, outputs)``."""
    import jax

    def run(state, actions):
        return jax.lax.scan(env.step, state, actions)

    return jax.jit(run)


def _leaves(tree):
    import jax

    return jax.tree_util.tree_leaves(
        tree, is_leaf=lambda x: x is None)


# -- checks ------------------------------------------------------------------


def check_spec(env_factory: Callable[[], object], batch: int = 5,
               steps: int = 3) -> None:
    """Shapes/dtypes match ``spec`` and are seed-independent."""
    import jax

    env = env_factory()
    spec = env.spec
    assert spec.action_space.n == spec.num_actions, (
        "spec.action_space must agree with spec.num_actions")
    frame_spec = spec.observation_spec.frame

    def assert_output(out, where):
        frame = out.observation.frame
        assert tuple(frame.shape) == (batch,) + tuple(frame_spec.shape), (
            f"{where}: frame {tuple(frame.shape)} != spec "
            f"[B]+{tuple(frame_spec.shape)}")
        assert frame.dtype == frame_spec.dtype, (
            f"{where}: frame dtype {frame.dtype} != {frame_spec.dtype}")
        assert out.reward.shape == (batch,), where
        assert out.reward.dtype == np.float32, where
        assert out.done.shape == (batch,), where
        assert out.done.dtype == np.bool_, where
        assert out.info.episode_return.dtype == np.float32, where
        assert out.info.episode_step.dtype == np.int32, where

    step = jax.jit(env.step)
    for tag, salt in (("seeds_a", 0), ("seeds_b", 1)):
        seeds = conformance_seeds(env, batch, salt=salt)
        state, out = env.initial(seeds)
        assert_output(out, f"{tag} initial")
        assert bool(np.asarray(out.done).all()), (
            f"{tag}: initial must emit done=True (start-of-episode)")
        assert not np.asarray(out.info.episode_step).any(), (
            f"{tag}: initial must emit episode_step 0")
        assert not np.asarray(out.reward).any(), (
            f"{tag}: initial must emit reward 0")
        actions = _actions(env, batch, steps)
        for t in range(steps):
            state, out = step(state, actions[t])
            assert_output(out, f"{tag} step {t}")


def check_determinism(env_factory: Callable[[], object], batch: int = 4,
                      steps: int = 33) -> None:
    """Bit-exact across jit/scan boundaries and env re-instantiation."""
    import jax

    env = env_factory()
    seeds = conformance_seeds(env, batch)
    actions = _actions(env, batch, steps)

    # Path A: per-step jit loop.
    step = jax.jit(env.step)
    state, _ = env.initial(seeds)
    loop_outs = []
    for t in range(steps):
        state, out = step(state, actions[t])
        loop_outs.append(jax.tree_util.tree_map(
            lambda x: None if x is None else np.asarray(x), out,
            is_leaf=lambda x: x is None))
    # Path B: one lax.scan.
    state_b, _ = env.initial(seeds)
    _, scan_outs = _scan_rollout(env)(state_b, actions)
    # Path C: a FRESH env instance, scanned.
    env_c = env_factory()
    state_c, _ = env_c.initial(seeds)
    _, scan_outs_c = _scan_rollout(env_c)(state_c, actions)

    for t in range(steps):
        for name, a, b, c in (
                ("frame", loop_outs[t].observation.frame,
                 scan_outs.observation.frame[t],
                 scan_outs_c.observation.frame[t]),
                ("reward", loop_outs[t].reward, scan_outs.reward[t],
                 scan_outs_c.reward[t]),
                ("done", loop_outs[t].done, scan_outs.done[t],
                 scan_outs_c.done[t]),
                ("episode_return", loop_outs[t].info.episode_return,
                 scan_outs.info.episode_return[t],
                 scan_outs_c.info.episode_return[t]),
                ("episode_step", loop_outs[t].info.episode_step,
                 scan_outs.info.episode_step[t],
                 scan_outs_c.info.episode_step[t])):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"jit-loop vs scan: {name} diverges at t={t}")
            np.testing.assert_array_equal(
                np.asarray(b), np.asarray(c),
                err_msg=f"scan vs fresh-instance scan: {name} diverges "
                        f"at t={t}")


def check_autoreset(env_factory: Callable[[], object], batch: int = 4,
                    steps: Optional[int] = None) -> None:
    """Emitted-vs-carried accounting + auto-reset invariants.  The
    window sizes itself to the level's horizon so every level crosses
    at least one episode boundary."""
    env = env_factory()
    if steps is None:
        # One horizon + slack guarantees every env crosses at least one
        # episode boundary (no episode outlives episode_length).
        horizon = (int(getattr(env, "episode_length", 32))
                   + int(getattr(env, "length_jitter", 0)))
        repeats = int(getattr(env, "num_action_repeats", 1))
        steps = max(16, -(-horizon // repeats) + 4)
    seeds = conformance_seeds(env, batch)
    actions = _actions(env, batch, steps, seed=1)
    state, out0 = env.initial(seeds)
    _, outs = _scan_rollout(env)(state, actions)
    reward = np.asarray(outs.reward)
    done = np.asarray(outs.done)
    ep_return = np.asarray(outs.info.episode_return)
    ep_step = np.asarray(outs.info.episode_step)

    assert (ep_step >= 1).all(), (
        "emitted episode_step must include the step just taken (>= 1 "
        "after initial) — `done & episode_step > 0` is the finished-"
        "episode detector and a 0 here breaks episode accounting")
    finished = 0
    for b in range(batch):
        expect_return, expect_step = 0.0, 0
        for t in range(steps):
            expect_return = np.float32(expect_return + reward[t, b])
            expect_step += 1
            np.testing.assert_allclose(
                ep_return[t, b], expect_return, rtol=1e-6,
                err_msg=f"emitted episode_return env {b} t={t} (must "
                        f"include the final step's reward)")
            assert ep_step[t, b] == expect_step, (
                f"emitted episode_step env {b} t={t}: {ep_step[t, b]} "
                f"!= {expect_step}")
            if done[t, b]:
                # Carried accounting resets: the NEXT emission starts a
                # fresh episode.
                expect_return, expect_step = 0.0, 0
                finished += 1
    assert finished > 0, (
        f"no episode finished in {steps} steps — the autoreset check "
        f"has no power; lower the level's episode_length or raise "
        f"`steps`")


@contextlib.contextmanager
def materialization_spy():
    """Spy every Python-level D2H materialization path on jax arrays —
    ``_value``, ``__array__`` — yielding the list of calls observed.
    THE one shared copy of the PR 12 instrumentation (the zero-sync
    tests in tests/test_device_telemetry.py and tests/test_replay.py
    delegate here), so a jaxlib upgrade that moves the materialization
    surface is fixed in one place."""
    from jaxlib import _jax

    cls = _jax.ArrayImpl
    calls: List[str] = []
    orig_value = cls.__dict__["_value"]
    orig_array = cls.__array__

    def spy_value(self):
        calls.append("_value")
        return orig_value.fget(self)

    def spy_array(self, *args, **kwargs):
        calls.append("__array__")
        return orig_array(self, *args, **kwargs)

    cls._value = property(spy_value)
    cls.__array__ = spy_array
    try:
        yield calls
    finally:
        cls._value = orig_value
        cls.__array__ = orig_array


def check_zero_host_sync(env_factory: Callable[[], object],
                         batch: int = 4, steps: int = 16) -> None:
    """A compiled rollout runs with zero host syncs: no device→host
    materialization (spied) and no host→device transfer
    (``jax.transfer_guard("disallow")`` hard-errors them)."""
    import jax
    import jax.numpy as jnp

    env = env_factory()
    seeds = conformance_seeds(env, batch)
    state, _ = env.initial(seeds)
    actions = jnp.asarray(_actions(env, batch, steps))
    rollout = _scan_rollout(env)
    state, _ = rollout(state, actions)  # pays the compile
    with materialization_spy() as calls:
        with jax.transfer_guard("disallow"):
            state, outs = rollout(state, actions)
    assert calls == [], (
        f"env rollout materialized device values on the host: {calls} "
        f"— a host callback or eager read is hiding in the step path")
    # The harness itself still reads results — outside the guard.
    assert np.isfinite(np.asarray(outs.reward)).all()


def check_donation(env_factory: Callable[[], object], batch: int = 4,
                   steps: int = 8) -> None:
    """The FULL (state, output) carry donates cleanly, twice: once for
    ``initial()``'s buffers (the distinct-buffer rule) and once for the
    step program's own outputs."""
    import jax
    import jax.numpy as jnp

    env = env_factory()
    seeds = conformance_seeds(env, batch)

    def run(carry, actions):
        def body(c, a):
            state, _ = c
            state, out = env.step(state, a)
            return (state, out), None

        carry, _ = jax.lax.scan(body, carry, actions)
        return carry

    run_jit = jax.jit(run, donate_argnums=(0,))
    actions = jnp.asarray(_actions(env, batch, steps))
    carry = env.initial(seeds)
    # Call 1 donates initial()'s buffers; call 2 donates the step
    # program's outputs.  Aliased leaves fail either call with
    # "attempt to donate the same buffer twice".
    carry = run_jit(carry, actions)
    carry = run_jit(carry, actions)
    assert np.asarray(carry[1].info.episode_step).min() >= 1


CHECKS: Dict[str, Callable[..., None]] = {
    "spec": check_spec,
    "determinism": check_determinism,
    "autoreset": check_autoreset,
    "zero_host_sync": check_zero_host_sync,
    "donation": check_donation,
}


def run_conformance(env_factory: Callable[[], object],
                    checks: Optional[Sequence[str]] = None) -> List[str]:
    """Run ``checks`` (default: all) against a fresh-env factory;
    raises AssertionError on the first violation, returns the names of
    the checks that ran."""
    names = list(checks) if checks is not None else sorted(CHECKS)
    for name in names:
        CHECKS[name](env_factory)
    return names
