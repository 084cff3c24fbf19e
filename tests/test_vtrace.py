"""V-trace numerical tests against an O(T^2) numpy ground truth.

Mirrors the reference's test strategy (reference: vtrace_test.py:44-83):
the ground truth literally expands the V-trace definition from the paper,
independent of any scan formulation.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from scalable_agent_tpu.ops import vtrace


def _shaped_arange(*shape):
    return np.arange(int(np.prod(shape)), dtype=np.float32).reshape(*shape)


def _softmax(x, axis=-1):
    x = x - x.max(axis=axis, keepdims=True)
    e = np.exp(x)
    return e / e.sum(axis=axis, keepdims=True)


def ground_truth_vtrace(log_rhos, discounts, rewards, values, bootstrap_value,
                        clip_rho_threshold, clip_pg_rho_threshold):
    """Literal-notation O(T^2) V-trace computation in numpy."""
    vs = []
    seq_len = len(discounts)
    rhos = np.exp(log_rhos)
    cs = np.minimum(rhos, 1.0)
    clipped_rhos = rhos
    if clip_rho_threshold:
        clipped_rhos = np.minimum(rhos, clip_rho_threshold)
    clipped_pg_rhos = rhos
    if clip_pg_rho_threshold:
        clipped_pg_rhos = np.minimum(rhos, clip_pg_rho_threshold)

    # v_s = V(x_s) + sum_{t=s}^{T-1} gamma^{t-s} * (prod_{i=s}^{t-1} c_i)
    #               * clipped_rho_t * (r_t + gamma V(x_{t+1}) - V(x_t))
    values_t_plus_1 = np.concatenate(
        [values, bootstrap_value[None, :]], axis=0)
    for s in range(seq_len):
        v_s = np.copy(values[s])
        for t in range(s, seq_len):
            v_s += (
                np.prod(discounts[s:t], axis=0)
                * np.prod(cs[s:t], axis=0)
                * clipped_rhos[t]
                * (rewards[t] + discounts[t] * values_t_plus_1[t + 1]
                   - values[t]))
        vs.append(v_s)
    vs = np.stack(vs, axis=0)

    vs_t_plus_1 = np.concatenate([vs[1:], bootstrap_value[None, :]], axis=0)
    pg_advantages = clipped_pg_rhos * (
        rewards + discounts * vs_t_plus_1 - values)
    return vs, pg_advantages


def _make_inputs(seq_len, batch_size, rho_scale=None):
    rng = np.random.RandomState(seq_len * 100 + batch_size)
    if rho_scale is None:
        rho_scale = [10.0, 2.0, 1.0, 0.5, 0.1]
    log_rhos = rng.uniform(-2.5, 2.5, (seq_len, batch_size)).astype(np.float32)
    values = {
        "log_rhos": log_rhos,
        "discounts": (rng.uniform(0.0, 1.0, (seq_len, batch_size))
                      .astype(np.float32) * 0.9),
        "rewards": _shaped_arange(seq_len, batch_size) / 10.0,
        "values": _shaped_arange(seq_len, batch_size) / 100.0,
        "bootstrap_value": _shaped_arange(batch_size) + 1.0,
    }
    return values


@pytest.mark.parametrize("batch_size", [1, 5])
@pytest.mark.parametrize("scan_impl", ["associative", "sequential", "pallas"])
def test_vtrace_matches_ground_truth(batch_size, scan_impl):
    seq_len = 5
    inputs = _make_inputs(seq_len, batch_size)
    clip_rho, clip_pg_rho = 3.7, 2.2

    out = vtrace.from_importance_weights(
        clip_rho_threshold=clip_rho, clip_pg_rho_threshold=clip_pg_rho,
        scan_impl=scan_impl, **inputs)
    gt_vs, gt_pg = ground_truth_vtrace(
        inputs["log_rhos"], inputs["discounts"], inputs["rewards"],
        inputs["values"], inputs["bootstrap_value"], clip_rho, clip_pg_rho)

    np.testing.assert_allclose(gt_vs, np.asarray(out.vs), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        gt_pg, np.asarray(out.pg_advantages), rtol=1e-4, atol=1e-5)


def test_vtrace_no_clipping():
    inputs = _make_inputs(7, 3)
    out = vtrace.from_importance_weights(
        clip_rho_threshold=None, clip_pg_rho_threshold=None, **inputs)
    gt_vs, gt_pg = ground_truth_vtrace(
        inputs["log_rhos"], inputs["discounts"], inputs["rewards"],
        inputs["values"], inputs["bootstrap_value"], None, None)
    np.testing.assert_allclose(gt_vs, np.asarray(out.vs), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        gt_pg, np.asarray(out.pg_advantages), rtol=1e-4, atol=1e-5)


def test_associative_matches_sequential_long_sequence():
    """The parallel scan must agree with the sequential one at T=100."""
    inputs = _make_inputs(100, 4)
    a = vtrace.from_importance_weights(scan_impl="associative", **inputs)
    s = vtrace.from_importance_weights(scan_impl="sequential", **inputs)
    np.testing.assert_allclose(
        np.asarray(a.vs), np.asarray(s.vs), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(a.pg_advantages), np.asarray(s.pg_advantages),
        rtol=1e-4, atol=1e-5)


def test_pallas_t1_edge():
    """T=1 must not emit a zero-size values[1:] slice (Mosaic rejects
    zero-size vectors)."""
    inputs = _make_inputs(1, 8)
    p = vtrace.from_importance_weights(scan_impl="pallas", **inputs)
    s = vtrace.from_importance_weights(scan_impl="sequential", **inputs)
    np.testing.assert_allclose(
        np.asarray(p.vs), np.asarray(s.vs), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(p.pg_advantages), np.asarray(s.pg_advantages),
        rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("batch_size", [4, 128, 200])
def test_pallas_matches_sequential_long_sequence(batch_size):
    """The fused Pallas kernel must agree at T=100 across batch sizes that
    exercise lane padding (4, 200) and the exact-tile case (128)."""
    inputs = _make_inputs(100, batch_size)
    p = vtrace.from_importance_weights(scan_impl="pallas", **inputs)
    s = vtrace.from_importance_weights(scan_impl="sequential", **inputs)
    np.testing.assert_allclose(
        np.asarray(p.vs), np.asarray(s.vs), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(p.pg_advantages), np.asarray(s.pg_advantages),
        rtol=1e-4, atol=1e-5)


def test_pallas_higher_rank_and_no_clipping():
    """Trailing dims flatten into the lane axis; None thresholds disable
    clipping inside the kernel."""
    seq_len, batch_size, c = 4, 2, 3
    rng = np.random.RandomState(3)
    inputs = {
        "log_rhos": rng.uniform(-1, 1, (seq_len, batch_size, c))
                        .astype(np.float32),
        "discounts": np.full((seq_len, batch_size, c), 0.9, np.float32),
        "rewards": _shaped_arange(seq_len, batch_size, c),
        "values": _shaped_arange(seq_len, batch_size, c) / 10.0,
        "bootstrap_value": _shaped_arange(batch_size, c),
    }
    p = vtrace.from_importance_weights(
        scan_impl="pallas", clip_rho_threshold=None,
        clip_pg_rho_threshold=None, **inputs)
    s = vtrace.from_importance_weights(
        scan_impl="sequential", clip_rho_threshold=None,
        clip_pg_rho_threshold=None, **inputs)
    assert p.vs.shape == (seq_len, batch_size, c)
    np.testing.assert_allclose(
        np.asarray(p.vs), np.asarray(s.vs), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(p.pg_advantages), np.asarray(s.pg_advantages),
        rtol=1e-4, atol=1e-5)


def test_higher_rank_inputs():
    """Extra trailing dims, as the reference supports (vtrace.py:176-180)."""
    seq_len, batch_size, c = 4, 2, 3
    rng = np.random.RandomState(0)
    inputs = {
        "log_rhos": rng.uniform(-1, 1, (seq_len, batch_size, c))
                        .astype(np.float32),
        "discounts": np.full((seq_len, batch_size, c), 0.9, np.float32),
        "rewards": _shaped_arange(seq_len, batch_size, c),
        "values": _shaped_arange(seq_len, batch_size, c) / 10.0,
        "bootstrap_value": _shaped_arange(batch_size, c),
    }
    out = vtrace.from_importance_weights(**inputs)
    assert out.vs.shape == (seq_len, batch_size, c)

    # Ground truth computed per trailing index.
    for i in range(c):
        gt_vs, gt_pg = ground_truth_vtrace(
            inputs["log_rhos"][..., i], inputs["discounts"][..., i],
            inputs["rewards"][..., i], inputs["values"][..., i],
            inputs["bootstrap_value"][..., i], 1.0, 1.0)
        np.testing.assert_allclose(
            gt_vs, np.asarray(out.vs[..., i]), rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(
            gt_pg, np.asarray(out.pg_advantages[..., i]),
            rtol=1e-4, atol=1e-5)


def test_rank_mismatch_raises():
    inputs = _make_inputs(5, 2)
    inputs["bootstrap_value"] = np.zeros((2, 3), np.float32)
    with pytest.raises(ValueError):
        vtrace.from_importance_weights(**inputs)


def test_log_probs_from_logits_and_actions():
    seq_len, batch_size, num_actions = 7, 3, 5
    rng = np.random.RandomState(1)
    logits = rng.normal(size=(seq_len, batch_size, num_actions)) \
                .astype(np.float32)
    actions = rng.randint(0, num_actions, (seq_len, batch_size)) \
                 .astype(np.int32)
    out = vtrace.log_probs_from_logits_and_actions(logits, actions)

    probs = _softmax(logits)
    expected = np.log(
        np.take_along_axis(probs, actions[..., None], axis=-1)[..., 0])
    np.testing.assert_allclose(expected, np.asarray(out), rtol=1e-4, atol=1e-5)


def test_from_logits_equals_importance_weights_path():
    seq_len, batch_size, num_actions = 6, 2, 4
    rng = np.random.RandomState(2)
    behaviour = rng.normal(size=(seq_len, batch_size, num_actions)) \
                   .astype(np.float32)
    target = rng.normal(size=(seq_len, batch_size, num_actions)) \
                .astype(np.float32)
    actions = rng.randint(0, num_actions, (seq_len, batch_size)) \
                 .astype(np.int32)
    base = _make_inputs(seq_len, batch_size)

    out = vtrace.from_logits(
        behaviour_policy_logits=behaviour,
        target_policy_logits=target,
        actions=actions,
        discounts=base["discounts"],
        rewards=base["rewards"],
        values=base["values"],
        bootstrap_value=base["bootstrap_value"])

    log_rhos = (
        np.asarray(vtrace.log_probs_from_logits_and_actions(target, actions))
        - np.asarray(
            vtrace.log_probs_from_logits_and_actions(behaviour, actions)))
    ref = vtrace.from_importance_weights(
        log_rhos=log_rhos,
        discounts=base["discounts"],
        rewards=base["rewards"],
        values=base["values"],
        bootstrap_value=base["bootstrap_value"])

    np.testing.assert_allclose(np.asarray(log_rhos),
                               np.asarray(out.log_rhos), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ref.vs), np.asarray(out.vs),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(ref.pg_advantages), np.asarray(out.pg_advantages),
        rtol=1e-5, atol=1e-6)


def test_vtrace_inside_jit_and_grad_stopped():
    """vs/pg_advantages are stop_gradient'ed (reference: vtrace.py:279-280)."""
    inputs = _make_inputs(5, 2)

    def loss_fn(values):
        out = vtrace.from_importance_weights(
            log_rhos=inputs["log_rhos"], discounts=inputs["discounts"],
            rewards=inputs["rewards"], values=values,
            bootstrap_value=inputs["bootstrap_value"])
        return jnp.sum(out.vs) + jnp.sum(out.pg_advantages)

    g = jax.jit(jax.grad(loss_fn))(jnp.asarray(inputs["values"]))
    np.testing.assert_allclose(np.zeros_like(inputs["values"]), np.asarray(g))


# -- V-trace from the stored log-probability ----------------------------------

@pytest.mark.parametrize("field", ["vs", "pg_advantages", "log_rhos"])
def test_vtrace_from_log_probs_is_vtrace_from_logits(field):
    rng = np.random.default_rng(0)
    shape = (7, 3)
    behaviour = jnp.asarray(rng.normal(size=shape + (9,)), jnp.float32)
    target = jnp.asarray(rng.normal(size=shape + (9,)), jnp.float32)
    actions = jnp.asarray(rng.integers(0, 9, shape), jnp.int32)
    rest = dict(
        discounts=jnp.full(shape, 0.99), rewards=jnp.asarray(
            rng.normal(size=shape), jnp.float32),
        values=jnp.asarray(rng.normal(size=shape), jnp.float32),
        bootstrap_value=jnp.asarray(rng.normal(size=shape[1:]),
                                    jnp.float32))
    want = vtrace.from_logits(behaviour, target, actions, **rest)
    got = vtrace.from_behaviour_log_probs(
        vtrace.log_probs_from_logits_and_actions(behaviour, actions),
        target, actions, **rest)
    np.testing.assert_array_equal(np.asarray(getattr(got, field)),
                                  np.asarray(getattr(want, field)))


def _vtrace_inputs(shape=(40, 3), actions=64, seed=0):
    rng = np.random.default_rng(seed)
    target = jnp.asarray(rng.normal(size=shape + (actions,)), jnp.float32)
    taken = jnp.asarray(rng.integers(0, actions, shape), jnp.int32)
    rest = dict(
        discounts=jnp.full(shape, 0.99), rewards=jnp.asarray(
            rng.integers(0, 2, shape), jnp.float32),
        values=jnp.asarray(rng.normal(size=shape), jnp.float32),
        bootstrap_value=jnp.asarray(rng.normal(size=shape[1:]),
                                    jnp.float32))
    return rng, target, taken, rest


@pytest.mark.parametrize("field", ["vs", "pg_advantages"])
def test_on_policy_vtrace_is_vtrace_at_ratios_of_one(field):
    """What acting and learning round differently is not a second
    policy: told that the data is on policy, the targets are those of
    ratios of exactly 1, whatever the stored log-probabilities say, and
    the diagnostics still carry what was measured."""
    rng, target, taken, rest = _vtrace_inputs()
    exact = vtrace.log_probs_from_logits_and_actions(target, taken)
    noisy = exact + jnp.asarray(rng.normal(0, 5e-3, exact.shape),
                                jnp.float32)
    want = vtrace.from_behaviour_log_probs(exact, target, taken, **rest)
    got = vtrace.from_behaviour_log_probs(noisy, target, taken,
                                          on_policy=True, **rest)
    np.testing.assert_array_equal(np.asarray(getattr(got, field)),
                                  np.asarray(getattr(want, field)))
    np.testing.assert_array_equal(np.asarray(got.log_rhos),
                                  np.asarray(exact - noisy))
    assert float(got.diagnostics.log_rho_p95) > 1e-3
    assert float(want.diagnostics.log_rho_p95) == 0.0


def test_the_clip_at_one_turns_rounding_into_a_trace_cut_short():
    """Why the fused loop tells the learner it is on policy: a scatter
    of 4.5e-3 round a log-ratio of 0 (what bfloat16 leaves between
    T = 1 and T = unroll on the chip) shortens every trace through
    ``min(1, rho)``, and at a discount of 0.99 the targets of a world
    that pays 1 a step read percents low."""
    rng, target, taken, rest = _vtrace_inputs(shape=(256, 8), seed=1)
    rest["rewards"] = jnp.ones_like(rest["rewards"])
    rest["values"] = jnp.zeros_like(rest["values"])
    rest["bootstrap_value"] = jnp.zeros_like(rest["bootstrap_value"])
    exact = vtrace.log_probs_from_logits_and_actions(target, taken)
    noisy = exact + jnp.asarray(rng.normal(0, 4.5e-3, exact.shape),
                                jnp.float32)
    clean = vtrace.from_behaviour_log_probs(exact, target, taken, **rest)
    stored = vtrace.from_behaviour_log_probs(noisy, target, taken, **rest)
    told = vtrace.from_behaviour_log_probs(noisy, target, taken,
                                           on_policy=True, **rest)
    low = 1.0 - float(jnp.mean(stored.vs[0]) / jnp.mean(clean.vs[0]))
    assert 0.05 < low < 0.25, low
    np.testing.assert_array_equal(np.asarray(told.vs),
                                  np.asarray(clean.vs))
