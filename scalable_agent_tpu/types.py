"""Core pytree data structures shared by actor, learner, and envs.

These mirror the reference's namedtuples so trajectories have an identical
nesting structure (reference: experiment.py:98-102 ``ActorOutput`` /
``AgentOutput``; environments.py:143-146 ``StepOutput`` /
``StepOutputInfo``), but are JAX pytrees flowing through jitted functions
instead of graph-mode tensors.
"""

from typing import Any, NamedTuple, Optional


class StepOutputInfo(NamedTuple):
    """Episode bookkeeping carried alongside every env step.

    (reference: environments.py:143-144)
    """

    episode_return: Any  # f32 []
    episode_step: Any  # i32 []


class Observation(NamedTuple):
    """What the env shows the agent each step.

    ``frame`` is HWC uint8.  ``instruction`` is either hashed int32 token ids
    (language-conditioned DMLab levels) or None — the reference carries a raw
    string and hashes it in-graph (reference: experiment.py:123-146); strings
    cannot live on a TPU, so hashing happens host-side in
    ``models/instruction.py`` and the device only ever sees int32 ids.
    ``measurements`` is an optional f32 vector of game-state scalars
    (health/ammo/weapons — the Doom additional-input wrapper, reference:
    envs/doom/wrappers/additional_input.py:7-96); None everywhere else.
    """

    frame: Any
    instruction: Optional[Any] = None
    measurements: Optional[Any] = None


class StepOutput(NamedTuple):
    """One env transition.  (reference: environments.py:145-146)"""

    reward: Any  # f32 []
    info: Any  # StepOutputInfo
    done: Any  # bool []
    observation: Any  # Observation


class AgentState(NamedTuple):
    """LSTM core carry.  (reference: experiment.py:118-121)

    The IMPALA agents' state.  An agent's state is whatever pytree it
    declares (``agent.initial_state(batch)``, reset at ``done`` inside
    its own step): the token policy's is its attention cache
    (models/token_policy.py ``TokenCache``)."""

    c: Any
    h: Any


class AgentOutput(NamedTuple):
    """Per-step model output.  (reference: experiment.py:101-102)"""

    action: Any  # i32 []
    # f32 [num_logits]; [1], the taken action's log-probability, where
    # the policy is one large categorical (ops/distributions.py
    # ``behaviour_size``): a vocabulary of logits a step is not kept
    policy_logits: Any
    baseline: Any  # f32 []


class ActorOutput(NamedTuple):
    """One length-T+1 trajectory sent from an actor to the learner.

    (reference: experiment.py:98-100)
    """

    level_name: Any
    agent_state: Any  # AgentState at trajectory start
    env_outputs: Any  # StepOutput, [T+1, ...]
    agent_outputs: Any  # AgentOutput, [T+1, ...]


def map_structure(fn, *trees):
    """``tree.map_structure`` equivalent over pytrees (None treated as leaf).

    jax is imported lazily: env worker subprocesses import this module for
    the pytree structs but must never pull in jax (spawn-start cost, and the
    TPU runtime must not initialize in children).
    """
    import jax

    return jax.tree_util.tree_map(fn, *trees, is_leaf=lambda x: x is None)
