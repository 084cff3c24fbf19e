"""Categorical and tuple-categorical action distributions (pure JAX).

The reference implements these as torch classes
(reference: algorithms/utils/action_distributions.py —
``CategoricalActionDistribution`` :49-108, ``TupleActionDistribution``
:111-201, ``calc_num_logits`` :10-17).  TPU-native re-design:

- A distribution is not an object but a static ``DistributionSpec``
  (the per-component logit widths) plus pure functions over a single
  concatenated logits tensor [..., sum(sizes)].  Static widths mean XLA
  sees fixed slices — no ragged structures, no host control flow.
- Component independence makes every quantity a sum over components:
  log_prob, entropy, and KL all reduce with one vectorized pass per
  component (K is tiny — Doom's largest composite has 6 components).
- Actions are int32 with a trailing component axis [..., K]; the K == 1
  case also accepts component-less actions so the plain-Discrete fast
  path keeps its existing [T, B] layout.
"""

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from scalable_agent_tpu.envs.spaces import (
    Discrete,
    Space,
    TupleSpace,
    calc_num_logits,
)


class DistributionSpec(NamedTuple):
    """Static shape of a (tuple-)categorical policy: logit width per
    independent component."""

    sizes: Tuple[int, ...]
    # One categorical over a vocabulary of tokens (the token policy
    # declares it): see ``stores_log_prob``.
    vocabulary: bool = False

    @property
    def num_logits(self) -> int:
        return sum(self.sizes)

    @property
    def num_components(self) -> int:
        return len(self.sizes)


def stores_log_prob(spec: DistributionSpec) -> bool:
    """Does a trajectory of this policy hold the taken action's
    log-probability in place of the logits?  A vocabulary's does:
    ``[T+1, B, 25024]`` float32 logits are 823 MB at T = 256, B = 32,
    and the behaviour policy's log-probability of the action taken is
    all V-trace reads of them."""
    return spec.vocabulary and spec.num_components == 1


def behaviour_size(spec: DistributionSpec) -> int:
    """Width of ``AgentOutput.policy_logits`` in a trajectory."""
    return 1 if stores_log_prob(spec) else spec.num_logits


def spec_for_space(space: Space) -> DistributionSpec:
    """Space -> DistributionSpec (reference: calc_num_logits, :10-17)."""
    if isinstance(space, Discrete):  # includes Discretized
        return DistributionSpec(sizes=(space.n,))
    if isinstance(space, TupleSpace):
        sizes = []
        for sub in space.spaces:
            sub_spec = spec_for_space(sub)
            sizes.extend(sub_spec.sizes)
        return DistributionSpec(sizes=tuple(sizes))
    raise NotImplementedError(f"no categorical policy over {space!r}")


def _offsets(spec: DistributionSpec):
    offsets = []
    start = 0
    for size in spec.sizes:
        offsets.append((start, size))
        start += size
    return offsets


def _component_logits(logits, spec: DistributionSpec):
    """Split [..., num_logits] into per-component views (static slices)."""
    if logits.shape[-1] != spec.num_logits:
        raise ValueError(
            f"logits last dim {logits.shape[-1]} != spec {spec.num_logits}")
    return [logits[..., start:start + size]
            for start, size in _offsets(spec)]


def _component_actions(actions, spec: DistributionSpec):
    """Actions [..., K] (or [...] when K == 1) -> list of [...] int32."""
    k = spec.num_components
    actions = jnp.asarray(actions)
    if k == 1:
        # Single-component policies always use the component-less layout
        # ([T, B] etc.) — never a trailing K axis, avoiding ambiguity
        # with batch dims of size 1.
        return [actions]
    if actions.shape[-1] != k:
        raise ValueError(
            f"actions last dim {actions.shape[-1]} != {k} components")
    return [actions[..., i] for i in range(k)]


def sample(rng: jax.Array, logits, spec: DistributionSpec):
    """Sample all components; returns int32 [..., K], squeezed to [...]
    for K == 1 (preserving the plain-Discrete layout)."""
    parts = []
    for i, chunk in enumerate(_component_logits(logits, spec)):
        parts.append(jax.random.categorical(
            jax.random.fold_in(rng, i), chunk, axis=-1))
    stacked = jnp.stack(parts, axis=-1).astype(jnp.int32)
    if spec.num_components == 1:
        return stacked[..., 0]
    return stacked


def log_prob(logits, actions, spec: DistributionSpec):
    """Joint log pi(a|s): sum of component log-probs (independence).

    (reference: TupleActionDistribution.log_prob, :160-165)
    """
    total = None
    for chunk, action in zip(_component_logits(logits, spec),
                             _component_actions(actions, spec)):
        lp = jnp.take_along_axis(
            jax.nn.log_softmax(chunk, axis=-1),
            action[..., None].astype(jnp.int32), axis=-1)[..., 0]
        total = lp if total is None else total + lp
    return total


def entropy(logits, spec: DistributionSpec):
    """Joint entropy: sum of component entropies.

    (reference: TupleActionDistribution.entropy, :180-184)
    """
    total = None
    for chunk in _component_logits(logits, spec):
        log_p = jax.nn.log_softmax(chunk, axis=-1)
        ent = -jnp.sum(jnp.exp(log_p) * log_p, axis=-1)
        total = ent if total is None else total + ent
    return total


def kl_divergence(p_logits, q_logits, spec: DistributionSpec):
    """KL(p || q), summed over components.

    (reference: CategoricalActionDistribution.kl_divergence :96-100,
    TupleActionDistribution sums over the tuple :186-192)
    """
    total = None
    for p_chunk, q_chunk in zip(_component_logits(p_logits, spec),
                                _component_logits(q_logits, spec)):
        log_p = jax.nn.log_softmax(p_chunk, axis=-1)
        log_q = jax.nn.log_softmax(q_chunk, axis=-1)
        kl = jnp.sum(jnp.exp(log_p) * (log_p - log_q), axis=-1)
        total = kl if total is None else total + kl
    return total


def symmetric_kl(p_logits, q_logits, spec: DistributionSpec):
    """0.5 * (KL(p || q) + KL(q || p)), summed over components.

    The reference's ``kl_divergence`` is in fact this symmetric form
    (reference: CategoricalActionDistribution._kl_symmetric/_kl_inverse
    :84-93 and kl_divergence :100-101; TupleActionDistribution sums over
    the tuple :193-201).
    """
    return 0.5 * (kl_divergence(p_logits, q_logits, spec)
                  + kl_divergence(q_logits, p_logits, spec))


def kl_to_prior(logits, spec: DistributionSpec):
    """Symmetric KL against the uniform prior, summed over components.

    (reference: CategoricalActionDistribution.kl_prior :95-98 — the
    prior is uniform over each component's actions, log_prior_probs
    :60-63; TupleActionDistribution.kl_prior :187-191.)
    """
    total = None
    for chunk in _component_logits(logits, spec):
        prior = jnp.zeros_like(chunk)  # uniform after log_softmax
        component_spec = DistributionSpec(sizes=(chunk.shape[-1],))
        kl = symmetric_kl(chunk, prior, component_spec)
        total = kl if total is None else total + kl
    return total


def one_hot_actions(actions, spec: DistributionSpec):
    """Concatenated per-component one-hots [..., num_logits] — the
    "last action" conditioning input for composite spaces (generalizes
    the reference's single one_hot, experiment.py:196-198)."""
    parts = [
        jax.nn.one_hot(action, size, dtype=jnp.float32)
        for (_, size), action in zip(
            _offsets(spec), _component_actions(actions, spec))
    ]
    return jnp.concatenate(parts, axis=-1) if len(parts) > 1 else parts[0]
