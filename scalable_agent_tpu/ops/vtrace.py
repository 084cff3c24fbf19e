"""V-trace off-policy actor-critic targets, TPU-native.

Functional parity with the reference's ``vtrace.py`` (reference:
vtrace.py:71-161 ``from_logits``, vtrace.py:164-280
``from_importance_weights``), re-designed for TPU:

- The reference computes the v_s recurrence with a strictly sequential
  reverse ``tf.scan`` (``parallel_iterations=1``) deliberately placed on CPU
  because it was slow on GPU (reference: experiment.py:387-389,
  vtrace.py:250-262).  The recurrence

      acc_s = delta_s + (discount_s * c_s) * acc_{s+1}

  is a first-order *linear* recurrence, so here it is reformulated as a
  parallel ``jax.lax.associative_scan`` over composed affine maps — O(log T)
  depth on-device, fully fusable by XLA, and shardable over a mesh axis for
  sequence parallelism.  A sequential ``lax.scan`` path is kept for
  cross-checking (``scan_impl='sequential'``), and ``scan_impl='pallas'``
  runs the whole computation as ONE fused VMEM-resident Pallas kernel
  (ops/vtrace_pallas.py) — possible precisely because the outputs are
  stop-gradient'ed, so no VJP is ever needed through it.

- Like the reference, extra trailing dimensions are supported: ``rewards``
  may be [T, B, C...], ``bootstrap_value`` [B, C...] (reference:
  vtrace.py:176-180).

All math is float32; outputs are wrapped in ``stop_gradient`` exactly as the
reference does (reference: vtrace.py:279-280).
"""

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax


class VTraceDiagnostics(NamedTuple):
    """Scalar off-policyness diagnostics of one V-trace batch (ISSUE 17:
    the learning-dynamics plane).  All f32 scalars, stop-gradient'ed —
    pure telemetry, never part of the loss tape:

    - ``rho_clip_fraction`` / ``cs_clip_fraction`` /
      ``pg_rho_clip_fraction``: fraction of cells whose rho exceeded
      the rho-bar / 1.0 (the c-bar) / pg-rho-bar threshold — how much
      of the correction V-trace actually truncated.
    - ``log_rho_mean`` / ``log_rho_p95``: location and tail of the log
      importance ratio (0 when on-policy).
    - ``ess_frac``: effective sample size of the UNclipped importance
      weights, (Σρ)²/(N·Σρ²), as a fraction of N — 1.0 on-policy,
      → 1/N when one cell dominates.
    """

    rho_clip_fraction: jax.Array
    cs_clip_fraction: jax.Array
    pg_rho_clip_fraction: jax.Array
    log_rho_mean: jax.Array
    log_rho_p95: jax.Array
    ess_frac: jax.Array


class VTraceReturns(NamedTuple):
    vs: jax.Array
    pg_advantages: jax.Array
    # Trailing default keeps positional unpacking (vs, pg) working for
    # every pre-ISSUE-17 caller.
    diagnostics: Optional[VTraceDiagnostics] = None


class VTraceFromLogitsReturns(NamedTuple):
    vs: jax.Array
    pg_advantages: jax.Array
    log_rhos: jax.Array
    behaviour_action_log_probs: jax.Array
    target_action_log_probs: jax.Array
    diagnostics: Optional[VTraceDiagnostics] = None


def importance_diagnostics(log_rhos,
                           clip_rho_threshold: Optional[float] = 1.0,
                           clip_pg_rho_threshold: Optional[float] = 1.0
                           ) -> VTraceDiagnostics:
    """Off-policyness diagnostics from log importance ratios.

    Strict ``>`` comparisons: a rho exactly AT a threshold is returned
    unchanged by ``minimum``, so only values the clip actually altered
    count (an exactly-on-policy batch reports 0 clipped everywhere).
    A ``None`` threshold disables that clip, so its fraction is 0.
    """
    log_rhos = lax.stop_gradient(jnp.asarray(log_rhos, jnp.float32))
    rhos = jnp.exp(log_rhos)
    zero = jnp.zeros((), jnp.float32)
    rho_clip_fraction = (
        jnp.mean((rhos > jnp.float32(clip_rho_threshold))
                 .astype(jnp.float32))
        if clip_rho_threshold is not None else zero)
    pg_rho_clip_fraction = (
        jnp.mean((rhos > jnp.float32(clip_pg_rho_threshold))
                 .astype(jnp.float32))
        if clip_pg_rho_threshold is not None else zero)
    cs_clip_fraction = jnp.mean(
        (rhos > jnp.float32(1.0)).astype(jnp.float32))
    # ESS is scale-invariant in the weights, so shift by the max log
    # ratio before exponentiating — exp(2*log_rho) overflows f32 from
    # log_rho ~ 44, and one rogue trajectory would NaN the gauge.
    shifted = jnp.exp(log_rhos - jnp.max(log_rhos))
    sum_rho = jnp.sum(shifted)
    sum_rho_sq = jnp.sum(jnp.square(shifted))
    n = jnp.float32(log_rhos.size)
    ess_frac = jnp.square(sum_rho) / jnp.maximum(
        n * sum_rho_sq, jnp.float32(1e-30))
    return VTraceDiagnostics(
        rho_clip_fraction=rho_clip_fraction,
        cs_clip_fraction=cs_clip_fraction,
        pg_rho_clip_fraction=pg_rho_clip_fraction,
        log_rho_mean=jnp.mean(log_rhos),
        log_rho_p95=jnp.quantile(log_rhos, 0.95),
        ess_frac=ess_frac)


def log_probs_from_logits_and_actions(policy_logits, actions):
    """Sampling log-probability of ``actions`` under softmax ``policy_logits``.

    policy_logits: [T, B, NUM_ACTIONS] float; actions: [T, B] int.
    Returns [T, B] float32.  (reference: vtrace.py:45-68)
    """
    policy_logits = jnp.asarray(policy_logits, jnp.float32)
    actions = jnp.asarray(actions, jnp.int32)
    log_pi = jax.nn.log_softmax(policy_logits, axis=-1)
    return jnp.take_along_axis(log_pi, actions[..., None], axis=-1).squeeze(-1)


def compose_affine(later, earlier):
    """Affine-map composition for the reverse recurrence, shared by the
    single-device associative scan and the time-sharded path
    (parallel/sequence.py).  With reverse=True, associative_scan folds
    later timesteps into the left operand; composing
    f_earlier ∘ f_later gives (a_e * a_l, b_e + a_e * b_l)."""
    a_l, b_l = later
    a_e, b_e = earlier
    return a_e * a_l, b_e + a_e * b_l


def elementwise_prologue(log_rhos, discounts, rewards, values,
                         bootstrap_value, clip_rho_threshold):
    """The V-trace elementwise pre-computation shared by every
    recurrence implementation (single-device scans here, the Pallas
    kernel's host-side wrapper, and the time-sharded path in
    parallel/sequence.py): returns (a, deltas, rhos, values_t_plus_1)
    where acc solves acc_s = deltas_s + a_s * acc_{s+1}."""
    rhos = jnp.exp(log_rhos)
    if clip_rho_threshold is not None:
        clipped_rhos = jnp.minimum(jnp.float32(clip_rho_threshold), rhos)
    else:
        clipped_rhos = rhos
    cs = jnp.minimum(jnp.float32(1.0), rhos)
    values_t_plus_1 = jnp.concatenate(
        [values[1:], bootstrap_value[None]], axis=0)
    deltas = clipped_rhos * (rewards + discounts * values_t_plus_1 - values)
    return discounts * cs, deltas, rhos, values_t_plus_1


def elementwise_epilogue(rhos, discounts, rewards, values, vs_t_plus_1,
                         clip_pg_rho_threshold):
    """The shared pg-advantage computation given vs_{t+1}."""
    if clip_pg_rho_threshold is not None:
        clipped_pg_rhos = jnp.minimum(
            jnp.float32(clip_pg_rho_threshold), rhos)
    else:
        clipped_pg_rhos = rhos
    return clipped_pg_rhos * (rewards + discounts * vs_t_plus_1 - values)


def _linear_recurrence_reverse(a, b, scan_impl: str):
    """Solve acc_s = b_s + a_s * acc_{s+1} with acc_T = 0, over axis 0.

    Each timestep is the affine map f_s(x) = b_s + a_s * x; the answer at s is
    (f_s ∘ f_{s+1} ∘ ... ∘ f_{T-1})(0).  Affine-map composition is
    associative, so the whole solve is one ``associative_scan``.
    """
    if scan_impl == "sequential":
        def step(acc, ab):
            a_t, b_t = ab
            acc = b_t + a_t * acc
            return acc, acc

        _, out = lax.scan(step, jnp.zeros_like(b[0]), (a, b), reverse=True)
        return out

    if scan_impl != "associative":
        raise ValueError(f"unknown scan_impl: {scan_impl!r}")

    _, acc = lax.associative_scan(compose_affine, (a, b), reverse=True)
    return acc


def from_importance_weights(
    log_rhos,
    discounts,
    rewards,
    values,
    bootstrap_value,
    clip_rho_threshold: Optional[float] = 1.0,
    clip_pg_rho_threshold: Optional[float] = 1.0,
    scan_impl: str = "associative",
    mesh=None,
    seq_axis: str = "seq",
) -> VTraceReturns:
    """V-trace targets from log importance weights.

    Shapes: log_rhos/discounts/rewards/values [T, B, C...],
    bootstrap_value [B, C...].  (reference: vtrace.py:164-280)

    ``scan_impl="time_sharded"``: the recurrence's time dimension shards
    over ``mesh[seq_axis]`` (sequence/context parallelism,
    parallel/sequence.py) — the distributed replacement for the
    reference's CPU-pinned sequential scan (vtrace.py:250-262).
    """
    if scan_impl == "time_sharded":
        if mesh is None:
            raise ValueError(
                "scan_impl='time_sharded' needs the mesh argument")
        from scalable_agent_tpu.parallel import sequence

        sharded = sequence.from_importance_weights_sharded(
            mesh, log_rhos, discounts, rewards, values, bootstrap_value,
            clip_rho_threshold=clip_rho_threshold,
            clip_pg_rho_threshold=clip_pg_rho_threshold,
            seq_axis=seq_axis)
        # The diagnostics are elementwise reductions with no time
        # recurrence, so they need none of the sequence sharding —
        # compute them here and attach them to the delegated result.
        with jax.named_scope("telemetry"):  # as below
            return sharded._replace(diagnostics=importance_diagnostics(
                log_rhos, clip_rho_threshold, clip_pg_rho_threshold))
    log_rhos = jnp.asarray(log_rhos, jnp.float32)
    discounts = jnp.asarray(discounts, jnp.float32)
    rewards = jnp.asarray(rewards, jnp.float32)
    values = jnp.asarray(values, jnp.float32)
    bootstrap_value = jnp.asarray(bootstrap_value, jnp.float32)

    if values.ndim != log_rhos.ndim:
        raise ValueError(
            f"values rank {values.ndim} != log_rhos rank {log_rhos.ndim}")
    if bootstrap_value.ndim != log_rhos.ndim - 1:
        raise ValueError(
            f"bootstrap_value rank {bootstrap_value.ndim} != "
            f"log_rhos rank {log_rhos.ndim} - 1")
    if discounts.ndim != log_rhos.ndim or rewards.ndim != log_rhos.ndim:
        raise ValueError("discounts/rewards rank must match log_rhos rank")

    # Only the obs plane reads these: their ops go under the scope the
    # benchmark's scope reader files as telemetry (runtime/learner.py).
    with jax.named_scope("telemetry"):
        diagnostics = importance_diagnostics(
            log_rhos, clip_rho_threshold, clip_pg_rho_threshold)

    if scan_impl == "pallas":
        # Fused single-kernel path (ops/vtrace_pallas.py).  The kernel is
        # rank-2 [T, B]; extra trailing value dims are flattened into the
        # batch (lane) axis — the recurrence is independent per column.
        from scalable_agent_tpu.ops import vtrace_pallas
        from scalable_agent_tpu.parallel.mesh import pallas_interpret

        shape = log_rhos.shape
        # Stop gradients at the kernel INPUTS: the outputs are
        # stop-gradient'ed anyway, and pallas_call has no JVP rule, so the
        # tape must be severed before the call, not after.
        flat = lambda x: lax.stop_gradient(x).reshape(shape[0], -1)
        bootstrap_value = lax.stop_gradient(bootstrap_value)
        vs, pg = vtrace_pallas.vtrace_fused(
            flat(log_rhos), flat(discounts), flat(rewards), flat(values),
            bootstrap_value.reshape(-1),
            clip_rho_threshold=clip_rho_threshold,
            clip_pg_rho_threshold=clip_pg_rho_threshold,
            interpret=pallas_interpret())
        return VTraceReturns(
            vs=lax.stop_gradient(vs.reshape(shape)),
            pg_advantages=lax.stop_gradient(pg.reshape(shape)),
            diagnostics=diagnostics)

    a, deltas, rhos, _ = elementwise_prologue(
        log_rhos, discounts, rewards, values, bootstrap_value,
        clip_rho_threshold)
    vs_minus_v_xs = _linear_recurrence_reverse(a, deltas, scan_impl)
    vs = vs_minus_v_xs + values

    vs_t_plus_1 = jnp.concatenate([vs[1:], bootstrap_value[None]], axis=0)
    pg_advantages = elementwise_epilogue(
        rhos, discounts, rewards, values, vs_t_plus_1,
        clip_pg_rho_threshold)

    return VTraceReturns(
        vs=lax.stop_gradient(vs),
        pg_advantages=lax.stop_gradient(pg_advantages),
        diagnostics=diagnostics)


def from_logits(
    behaviour_policy_logits,
    target_policy_logits,
    actions,
    discounts,
    rewards,
    values,
    bootstrap_value,
    clip_rho_threshold: Optional[float] = 1.0,
    clip_pg_rho_threshold: Optional[float] = 1.0,
    scan_impl: str = "associative",
    dist_spec=None,
    mesh=None,
    seq_axis: str = "seq",
) -> VTraceFromLogitsReturns:
    """V-trace for softmax policies.  (reference: vtrace.py:71-161)

    behaviour/target logits: [T, B, NUM_LOGITS]; actions: [T, B] int
    ([T, B, K] for composite policies with ``dist_spec``);
    discounts/rewards/values: [T, B]; bootstrap_value: [B].

    ``dist_spec`` (ops/distributions.DistributionSpec): composite
    tuple-categorical policies — log-rhos become joint (summed) component
    log-prob ratios, the natural generalization the reference never built
    (its V-trace is single-categorical only, vtrace.py:45-68).
    """
    behaviour_policy_logits = jnp.asarray(behaviour_policy_logits, jnp.float32)
    target_policy_logits = jnp.asarray(target_policy_logits, jnp.float32)
    actions = jnp.asarray(actions, jnp.int32)

    if behaviour_policy_logits.ndim != 3 or target_policy_logits.ndim != 3:
        raise ValueError("policy logits must be rank 3 [T, B, NUM_LOGITS]")
    if dist_spec is None or dist_spec.num_components == 1:
        if actions.ndim != 2:
            raise ValueError("actions must be rank 2 [T, B]")
        behaviour_action_log_probs = log_probs_from_logits_and_actions(
            behaviour_policy_logits, actions)
        target_action_log_probs = log_probs_from_logits_and_actions(
            target_policy_logits, actions)
    else:
        from scalable_agent_tpu.ops import distributions

        if actions.ndim != 3:
            raise ValueError(
                "composite actions must be rank 3 [T, B, K]")
        behaviour_action_log_probs = distributions.log_prob(
            behaviour_policy_logits, actions, dist_spec)
        target_action_log_probs = distributions.log_prob(
            target_policy_logits, actions, dist_spec)
    log_rhos = target_action_log_probs - behaviour_action_log_probs

    vtrace_returns = from_importance_weights(
        log_rhos=log_rhos,
        discounts=discounts,
        rewards=rewards,
        values=values,
        bootstrap_value=bootstrap_value,
        clip_rho_threshold=clip_rho_threshold,
        clip_pg_rho_threshold=clip_pg_rho_threshold,
        scan_impl=scan_impl,
        mesh=mesh,
        seq_axis=seq_axis)

    return VTraceFromLogitsReturns(
        vs=vtrace_returns.vs,
        pg_advantages=vtrace_returns.pg_advantages,
        log_rhos=log_rhos,
        behaviour_action_log_probs=behaviour_action_log_probs,
        target_action_log_probs=target_action_log_probs,
        diagnostics=vtrace_returns.diagnostics)


def from_behaviour_log_probs(
    behaviour_action_log_probs,
    target_policy_logits,
    actions,
    discounts,
    rewards,
    values,
    bootstrap_value,
    clip_rho_threshold: Optional[float] = 1.0,
    clip_pg_rho_threshold: Optional[float] = 1.0,
    scan_impl: str = "associative",
    on_policy: bool = False,
) -> VTraceFromLogitsReturns:
    """``from_logits`` for a trajectory that kept the behaviour
    policy's log-probability of each action taken and not its logits
    (one large categorical, ops/distributions.py ``stores_log_prob``):
    the importance ratios read nothing else of the behaviour policy.

    ``on_policy``: the caller knows the actions were sampled under the
    very parameters it evaluates (the fused step's update follows its
    own rollout).  The ratio is then 1 by construction, and what is
    measured — the same policy evaluated by two compiled programs, one
    token at a time and over the unroll, whose bfloat16 roundings fall
    independently after a few layers — is rounding, not a second
    policy.  Fed to the recursion it is worse than noise: ``min(1,
    rho)`` keeps its negative half, and the product of the ``c``'s over
    the ~100 steps a discount of 0.99 looks ahead reads a log-ratio
    scatter of 4.5e-3 as traces an eighth short (PERF.md section 6, PR
    32).  So the targets are computed at ratios of exactly 1, which is
    what a learner that evaluates the behaviour log-probabilities with
    its own program would store, and the measured ratios go to the
    diagnostics alone (``log_rho_p95`` is then the acting / learning
    mismatch).

    behaviour_action_log_probs: [T, B]; target logits: [T, B,
    NUM_LOGITS]; actions: [T, B] int.
    """
    target_action_log_probs = log_probs_from_logits_and_actions(
        target_policy_logits, actions)
    behaviour_action_log_probs = jnp.asarray(
        behaviour_action_log_probs, jnp.float32)
    log_rhos = target_action_log_probs - behaviour_action_log_probs
    returns = from_importance_weights(
        # times zero, not ``zeros_like``: the targets then still wait
        # for the update's logits, and the compiler keeps the schedule
        # it has without ``on_policy`` (with ``zeros_like`` the recursion
        # runs early and one more [T+1, B, vocabulary] float32 tensor is
        # live at the step's peak: 12.95 against 12.17 GiB, AOT, PR 32)
        log_rhos=log_rhos * 0.0 if on_policy else log_rhos,
        discounts=discounts, rewards=rewards,
        values=values, bootstrap_value=bootstrap_value,
        clip_rho_threshold=clip_rho_threshold,
        clip_pg_rho_threshold=clip_pg_rho_threshold, scan_impl=scan_impl)
    diagnostics = returns.diagnostics
    if on_policy:
        with jax.named_scope("telemetry"):
            diagnostics = importance_diagnostics(
                log_rhos, clip_rho_threshold, clip_pg_rho_threshold)
    return VTraceFromLogitsReturns(
        vs=returns.vs, pg_advantages=returns.pg_advantages,
        log_rhos=log_rhos,
        behaviour_action_log_probs=behaviour_action_log_probs,
        target_action_log_probs=target_action_log_probs,
        diagnostics=diagnostics)
