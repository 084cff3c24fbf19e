"""``DeviceTokenRecall``: a world that emits tokens.

Each env shows a seeded token stream over a slice of a vocabulary and
the action at step t is a prediction of the token shown at t + 1
(``reward = 1`` where ``action % 16 == next_token % 16``, so a random
policy earns 1/16 and returns are not constant on seeded weights).

The stream is stateless in the position::

    token(p) = zipf_draw(fold_in(episode_key, p mod period))

so the first ``period`` positions of an episode are fresh draws and
position p >= period repeats position p - period.  With a period
longer than a policy's attention window and shorter than its episode
(2,560 between a 2,048 window and 4,096), the token to predict then lies
``period - 1`` positions back: past the window, inside a full-attention
layer's cache — recall through the full layer is what earns reward.
Draws are log-uniform (Zipf, exponent 1: ``P(k) = log((k + 2) / (k + 1))
/ log(V + 1)``), so a few tokens carry most of the stream, as topics
make text do, and an expert router's load is uneven.

**The stream does not depend on the action**, as ``fake_benchmark``'s
frames do not: only the reward does.  A checker can roll the world out
on its own under the same keys, and a sample flipped by rounding does
not send an episode elsewhere.

Episodes end every ``episode_length`` tokens; at ``initial`` the envs
are staggered through their first episode by ``episode_length // B``
positions, by row, so that a batch's episodes do not all end on one
step.  ``Observation.frame`` is the int32 token id (shape ``[B]``).
"""

import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from scalable_agent_tpu.envs.device.protocol import DeviceEnvSpec
from scalable_agent_tpu.envs.spaces import Discrete
from scalable_agent_tpu.envs.spec import TensorSpec
from scalable_agent_tpu.types import (
    Observation,
    StepOutput,
    StepOutputInfo,
)

__all__ = ["DeviceTokenRecall", "TokenRecallState"]

WORLD_KEY = 20483           # the base of every env's keys
REWARD_CLASSES = 16


class TokenRecallState(NamedTuple):
    """Per-env state, all [B]."""

    seed: jax.Array  # i32, fixed per env
    episode: jax.Array  # i32
    step: jax.Array  # i32, position in the episode of the token shown
    episode_return: jax.Array  # f32, carried accumulator
    episode_step: jax.Array  # i32, agent steps within the episode


class DeviceTokenRecall:
    """See the module docstring; ``initial``/``step`` follow the
    DeviceEnv protocol (envs/device/protocol.py).  ``num_actions`` is the
    vocabulary slice: tokens shown and tokens predicted share it."""

    def __init__(self, num_actions: int = 25024,
                 episode_length: int = 4096, period: int = 2560,
                 num_action_repeats: int = 1):
        if num_action_repeats != 1:
            raise ValueError(
                "token_recall: one action is one token; "
                f"num_action_repeats={num_action_repeats} has no meaning")
        if not 0 < period <= episode_length:
            raise ValueError(
                f"token_recall: period {period} must lie in "
                f"(0, episode_length {episode_length}]")
        self.num_actions = int(num_actions)
        self.episode_length = int(episode_length)
        self.period = int(period)
        self.num_action_repeats = 1
        self.max_seed = 2**31 - 1
        self.action_space = Discrete(self.num_actions)
        self.observation_spec = Observation(
            frame=TensorSpec((), np.int32, "frame"), instruction=None)

    @property
    def spec(self) -> DeviceEnvSpec:
        return DeviceEnvSpec(
            observation_spec=self.observation_spec,
            action_space=self.action_space,
            num_actions=self.num_actions)

    def _token(self, seed, episode, step):
        """The token each env shows at position ``step`` of ``episode``."""
        vocab = self.num_actions

        def one(seed, episode, step):
            key = jax.random.fold_in(jax.random.fold_in(
                jax.random.fold_in(jax.random.key(WORLD_KEY), seed),
                episode), step % self.period)
            u = jax.random.uniform(key, (), jnp.float32)
            rank = jnp.floor(jnp.exp(u * math.log(vocab + 1.0))) - 1.0
            return jnp.clip(rank.astype(jnp.int32), 0, vocab - 1)

        return jax.vmap(one)(seed, episode, step)

    def initial(self, seeds) -> Tuple[TokenRecallState, StepOutput]:
        seeds = jnp.asarray(seeds, jnp.int32)
        b = seeds.shape[0]
        step = (jnp.arange(b, dtype=jnp.int32)
                * (self.episode_length // b)) % self.episode_length

        # One DISTINCT buffer per leaf (the donation rule).
        def zero_i():
            return jnp.zeros((b,), jnp.int32)

        def zero_f():
            return jnp.zeros((b,), jnp.float32)

        state = TokenRecallState(
            seed=seeds, episode=zero_i(), step=step,
            episode_return=zero_f(), episode_step=zero_i())
        output = StepOutput(
            reward=zero_f(),
            info=StepOutputInfo(
                episode_return=zero_f(), episode_step=zero_i()),
            done=jnp.ones((b,), bool),
            observation=Observation(
                frame=self._token(seeds, state.episode, step),
                instruction=None))
        return state, output

    def step(self, state: TokenRecallState, action
             ) -> Tuple[TokenRecallState, StepOutput]:
        action = jnp.asarray(action, jnp.int32)
        if action.ndim > 1:  # composite: component 0 is the prediction
            action = action[:, 0]
        step = state.step + 1
        done = step >= self.episode_length
        episode = state.episode + done.astype(jnp.int32)
        step = jnp.where(done, 0, step)
        # After done the token shown is already the next episode's first.
        token = self._token(state.seed, episode, step)
        reward = (action % REWARD_CLASSES
                  == token % REWARD_CLASSES).astype(jnp.float32)
        emitted_return = state.episode_return + reward
        emitted_step = state.episode_step + 1
        new_state = TokenRecallState(
            seed=state.seed, episode=episode, step=step,
            episode_return=jnp.where(done, 0.0, emitted_return),
            episode_step=jnp.where(done, 0, emitted_step))
        output = StepOutput(
            reward=reward,
            info=StepOutputInfo(
                episode_return=emitted_return, episode_step=emitted_step),
            done=done,
            observation=Observation(frame=token, instruction=None))
        return new_state, output
