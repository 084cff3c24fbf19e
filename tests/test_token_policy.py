"""The token policy's first family (``afmoe``, models/token_policy.py)
against its plain reference (benchmark/references/afmoe_token.py), at
the tiny preset: hidden 64, 4 heads / 2 kv heads of 16, 8 experts top-2
of which 2 are held, window 8, vocabulary 64, unroll 6, episodes of 16,
seeded weights.

(a, b) ``TestPolicy``: the suite every family inherits
    (tests/family_suite.py ``PolicyConformance``) at this preset, and
    the decode gauge against the kernel's block lists over a rollout;
(c) the reference inside a run's time limit: a seed's start made once,
    blocks of batch columns that add up;
(d) the on-policy learner does not read the stored ratio;
(e) the fused step's carry, the cache in it, survives a save and a
    restore bit for bit, and acting and learning agree in bfloat16;
(f) the first family builds and steps as it did before the others came.

The driver and the benchmark's harness at this preset are in
tests/test_token_harness.py; the expert layer (ops/moe.py) is in
tests/test_moe.py, the token world in tests/test_device_worlds.py,
V-trace from a stored log-probability in tests/test_vtrace.py, the
benchmark's whole-run readers in tests/test_whole_run_readers.py, what
the IMPALA agents' learner reports in tests/test_learning_dynamics.py.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (ROOT, os.path.join(ROOT, "tests")):
    if path not in sys.path:
        sys.path.insert(0, path)

from family_suite import (  # noqa: E402
    LOSS,
    OPTIMIZER,
    SMALL_WORLD,
    PolicyConformance,
    Preset,
    env_outputs,
    learner_of,
    rel,
)
from scalable_agent_tpu.envs.device import make_device_env  # noqa: E402
from scalable_agent_tpu.models.token_policy import (  # noqa: E402
    TokenModelConfig,
    TokenPolicy,
)
from scalable_agent_tpu.runtime import InGraphTrainer  # noqa: E402

UNROLL, EPISODE, BATCH, VOCAB = 6, 16, 4, 64
TINY = {
    "model_type": "afmoe", "hidden_act": "silu", "score_func": "sigmoid",
    "rope_scaling": None, "vocab_size": VOCAB, "hidden_size": 64,
    "head_dim": 16, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_experts": 8, "num_experts_per_tok": 2, "num_shared_experts": 1,
    "num_hidden_layers": 3, "num_dense_layers": 1,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "full_attention"],
    "sliding_window": 8, "route_scale": 2.826, "route_norm": True,
    "rope_theta": 10000, "rms_norm_eps": 1e-05, "mup_enabled": True,
    "experts_held": 2, "first_expert": 0, "reference_block": 2,
    "loss": LOSS, "optimizer": OPTIMIZER,
}
WORLD = SMALL_WORLD


def stream(steps, batch=BATCH, seed=0, p_done=0.15):
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(0, 64, (steps, batch)), jnp.int32)
    done = np.asarray(rng.random((steps, batch)) < p_done)
    done[0] = True
    return tokens, jnp.asarray(done)


class _Preset(Preset):
    def unroll_stream(self, seed):
        """This family's hand-made unroll ends episodes at random (15% a
        step an env), and draws the rest from a generator of its own."""
        tokens, done = stream(self.unroll + 1, seed=seed)
        return tokens, done, np.random.default_rng(seed + 100)


PRESET = _Preset(
    tiny=TINY, reference="afmoe_token",
    cell="trinity.ingraph", config_file="trinity_mini_ep8",
    traffic_file="fused_token_recall_u256",
    level="token_recall", world=(25024, 4096, 2560),
    why_says=("512 tokens", "8x"),
    own_metrics=(
        "attention_device_share.fused", "attention_update_roofline.fused",
        "expert_load_max_over_mean", "loss_heads_device_share.fused",
        "moe_device_share.fused", "whole_step_device_ms.fused",
        "whole_step_mfu.fused", "whole_step_rollout_share.fused",
        "whole_step_telemetry_share.fused"),
    groups=("embedding", "attention", "experts", "mlp", "norms", "heads"),
    kernel_policy_says=("2 sliding_attention, 1 full_attention",
                        "experts_held=2/8"),
    lacking=("head_dim", "layer_types", "num_experts", "route_scale",
             "mup_enabled", "experts_held"),
    # https://huggingface.co/arcee-ai/Trinity-Mini config.json; its
    # ``layer_types`` are this period eight times over
    published={
        "global_attn_every_n_layers": 4, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
        "model_type": "afmoe", "moe_intermediate_size": 1024,
        "mup_enabled": True, "n_group": 1, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_expert_groups": 1, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 32,
        "num_key_value_heads": 4, "num_limited_groups": 1,
        "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
        "rope_theta": 10000, "route_norm": True, "route_scale": 2.826,
        "score_func": "sigmoid", "sliding_window": 2048,
        "tie_word_embeddings": False, "topk_group": 1,
        "use_grouped_mm": True, "vocab_size": 200192,
        "layer_types": ["sliding_attention", "sliding_attention",
                        "sliding_attention", "full_attention"] * 8},
    reduced_numbers=("num_hidden_layers", "num_dense_layers", "layer_types",
                     "vocab_size"),
    prints=("expert_load_max_over_mean",), does_not_print=(),
    # this family's copy of the suite never asked that a leaf's gradient
    # be more than 0
    every_leaf_has_a_gradient=False,
    bfloat16_band=0.02,      # the loss, against the float32 reference's
    unrolls_from=("forward",),
    seeds=(3000000007, 3000000008, 11), grad_norm_bound=1e-4,
    half_batch_moves=0.3, fp8_moves=0.1)
MODEL = PRESET.model
ref = PRESET.ref
policy, weights, trajectory = PRESET.policy, PRESET.weights, PRESET.trajectory


class TestPolicy(PolicyConformance):
    """(a, b): the suite at this preset.  Forty steps: the window ring
    (8 + 6 slots) wraps twice and the full ring (16 + 6) once."""

    preset = PRESET
    # this family's reference plants no fault of its own: the cell's
    # faults are the harness's (an fp8 cast, half the batch)
    test_the_references_planted_fault_moves_its_loss = None

    def test_the_decode_gauge_is_the_kernels_block_lists_over_a_rollout(
            self, forty_steps, monkeypatch):
        """``attention/decode_key_blocks_visited_share`` as the update's pass
        says it against the block lists the decode kernel walked, summed
        over the rollout that made the unroll: both from ``decode_visits``,
        the first at once from the unroll's indices, the second a step at a
        time from the state acting found.  Blocks of two slots, so that the
        tiny rings (14 and 22 slots) are several."""
        from scalable_agent_tpu.ops import attention as attention_lib

        block = 2
        monkeypatch.setattr(attention_lib, "_decode_block",
                            lambda slots, slot_bytes: block)
        attention_lib._decode.clear_cache()
        agent, params, tokens, done, stepwise, *_ = forty_steps
        stats = agent.stats_collection
        unroll = jax.jit(lambda p, e, s: agent.apply(
            p, jnp.zeros(e.done.shape, jnp.int32), e, s, mutable=[stats]))
        state = agent.initial_state(BATCH)
        first = 24          # both rings have wrapped, every env is mid-episode
        for t in range(0, first + UNROLL, UNROLL):
            start = state
            ((_, state), said) = unroll(
                params, env_outputs(tokens[t:t + UNROLL], done[t:t + UNROLL]),
                state)
        step = jax.jit(lambda p, e, s: agent.apply(
            p, jnp.zeros(e.done.shape, jnp.int32), e, s))
        state, rows = start, []
        shares = {kind: 0.0 for kind in MODEL.layer_types}
        for t in range(first, first + UNROLL):
            began = jnp.where(done[t], state.written, state.episode_start)
            for kind, ring_index, window in (
                    ("sliding_attention", state.window_index,
                     MODEL.sliding_window),
                    ("full_attention", state.full_index, None)):
                visit = attention_lib.decode_visits(
                    ring_index, state.written[None], began[:, None], window,
                    block)[:, 0]
                shares[kind] += (float(jnp.sum(visit)) + BATCH) / (
                    BATCH * UNROLL * (visit.shape[1] + 1))
            (logits, _), state = step(
                params, env_outputs(tokens[t:t + 1], done[t:t + 1]), state)
            rows.append(logits[0])
        # the decode at these blocks is still the decode
        assert rel(jnp.stack(rows), stepwise[first:first + UNROLL]) < 1e-5
        want = np.mean([shares[kind] for kind in MODEL.layer_types])
        got = said[stats]["attention/decode_key_blocks_visited_share"]
        assert 0.0 < want < 1.0
        assert float(got) == pytest.approx(want, rel=1e-6)
        attention_lib._decode.clear_cache()


# -- (c) the reference inside a run's time limit ------------------------------

def _follow_a_step(cfg, seed=5, nu=None):
    """One step of the harness's ``follow`` at the tiny preset:
    (loss, gradients, parameters after, mean square after)."""
    start = ref.make_weights(cfg, seed)
    params = ref.to_tree(start)
    nu = ref.rmsprop_init(params) if nu is None else nu(params)
    carry = ref.rollout_initial(cfg, WORLD, BATCH, 1)
    batch, _ = ref.rollout(cfg, WORLD, params, carry, 1, 0, UNROLL)
    value, grads = ref.loss_and_grads(cfg, params, batch,
                                      cfg["reference_block"])
    grads = jax.tree_util.tree_map(jnp.copy, grads)    # the step's to keep
    after, nu = ref.rmsprop_step(cfg, params, nu, grads, 0.0)
    return float(value), batch, jax.device_get((after, nu))


@pytest.mark.parametrize("what", ["same_leaves", "made_again", "let_go"])
def test_the_references_start_is_made_once_a_seed(what):
    """The harness asks for a seed's weights three times a run: they
    cross to the host once, and the copy on the chip is made again by
    the programs that made it (the same bits) and let go by the
    optimizer's step."""
    first = ref.make_weights(TINY, 21)
    if what == "same_leaves":
        again = ref.make_weights(TINY, 21)
        assert again is not first
        assert all(again[path] is leaf for path, leaf in first.items())
        other = ref.make_weights(TINY, 22)
        assert not np.array_equal(other[("embed", "embedding")],
                                  first[("embed", "embedding")])
        return
    params = ref.to_tree(first)
    chip = ref._on_chip(params)
    if what == "made_again":
        flat = ref.from_tree(chip)
        assert all(isinstance(leaf, jax.Array) for leaf in flat.values())
        for path, leaf in first.items():
            np.testing.assert_array_equal(flat[path], leaf)
        assert ref._on_chip(params) is chip and ref._on_chip(chip) is chip
        # a tree that is not the last start's goes over as it is
        moved = jax.tree_util.tree_map(lambda x: x + 1.0, params)
        np.testing.assert_array_equal(
            ref._on_chip(moved)["final_norm"]["scale"],
            moved["final_norm"]["scale"])
        ref._ON_CHIP.clear()
        return
    grads = jax.tree_util.tree_map(jnp.zeros_like, chip)
    ref.rmsprop_step(TINY, params, ref.rmsprop_init(params), grads, 0.0)
    assert ref._ON_CHIP == []


def test_the_mean_square_starts_as_ones():
    """``rmsprop_init`` gives a scalar 1 a leaf; the step reads it as
    the leaf of ones TF's RMSProp starts from."""
    ones = lambda params: jax.tree_util.tree_map(      # noqa: E731
        lambda p: np.ones(p.shape, np.float32), params)
    _, _, (after, nu) = _follow_a_step(TINY)
    _, _, (after_ones, nu_ones) = _follow_a_step(TINY, nu=ones)
    for a, b in zip(jax.tree_util.tree_leaves((after, nu)),
                    jax.tree_util.tree_leaves((after_ones, nu_ones))):
        assert a.shape == b.shape
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("block", [1, 2])
def test_the_references_blocks_add_up_to_the_batch(block):
    """The rollout's and the loss's blocks of batch columns are one
    compiled forward each, whatever their number: every block size
    gives the whole batch's actions, log-probabilities, loss and
    parameters."""
    whole = _follow_a_step(dict(TINY, reference_block=BATCH))
    parts = _follow_a_step(dict(TINY, reference_block=block))
    np.testing.assert_array_equal(parts[1].action, whole[1].action)
    np.testing.assert_allclose(parts[1].log_prob, whole[1].log_prob,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(parts[0], whole[0], rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(parts[2][0]),
                    jax.tree_util.tree_leaves(whole[2][0])):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
    with pytest.raises(ValueError, match="does not divide"):
        _follow_a_step(dict(TINY, reference_block=3))


def test_the_first_gradients_norms_are_the_float64_readings():
    """``first_gradient_norms`` subtracts in float32 and sums in
    float64; an element no token reached still reads exactly 0."""
    rng = np.random.default_rng(0)
    decay = np.float32(TINY["optimizer"]["rmsprop_decay"])
    grads = [rng.normal(size=shape).astype(np.float32) * scale
             for shape, scale in (((64, 64), 3.0), ((64,), 1e-3),
                                  ((7, 5), 0.0))]
    nu1 = [decay + (np.float32(1.0) - decay) * g * g for g in grads]
    paths = [("a",), ("b",), ("c",)]
    got = ref.first_gradient_norms(TINY, paths, nu1)
    assert got[("c",)] == 0.0
    rest = np.float64(np.float32(1.0) - decay)
    for path, nu in zip(paths[:2], nu1):
        want = float(np.sqrt(np.sum(np.maximum(
            (np.asarray(nu, np.float64) - np.float64(decay)) / rest,
            0.0))))
        assert got[path] == pytest.approx(want, rel=1e-12)


# -- (d) the on-policy learner ------------------------------------------------

@pytest.mark.parametrize("what", ["loss", "gradient"])
def test_the_on_policy_learner_does_not_read_the_stored_ratio(what):
    """The learner the fused loop builds (``on_policy=True``): its loss
    and gradient are the reference's at the behaviour log-probabilities
    the update's own forward pass gives, though the trajectory's are
    0.2 off."""
    agent = policy()
    params = weights()
    traj, batch = trajectory(agent, params)
    learner = learner_of(agent, BATCH * UNROLL, on_policy=True)
    (value, _), grads = jax.value_and_grad(
        learner._loss, has_aux=True)(params, traj, None)
    (logits, _), _ = agent.apply(
        params, traj.agent_outputs.action, traj.env_outputs,
        traj.agent_state)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    own = jnp.take_along_axis(
        logp[:-1], traj.agent_outputs.action[1:, :, None], -1)[..., 0]
    batch = batch._replace(log_prob=jnp.concatenate(
        [jnp.zeros((1, BATCH)), own]))
    want, want_grads = jax.value_and_grad(
        lambda p: ref.loss(TINY, p, batch))(params["params"])
    if what == "loss":
        assert rel(value, want) < 1e-5
    else:
        got = ref.from_tree(grads["params"])
        want_grads = ref.from_tree(want_grads)
        scale = max(float(np.max(np.abs(v))) for v in want_grads.values())
        worst = max(float(np.max(np.abs(
            np.asarray(got[path], np.float64)
            - np.asarray(want_grads[path], np.float64))))
            for path in want_grads)
        assert worst <= 1e-5 * scale, (worst, scale)


# -- (e) the carry, the cache in it -------------------------------------------

def test_the_carry_survives_a_save_and_a_restore_bit_for_bit():
    agent = policy()
    learner = learner_of(agent, BATCH * UNROLL)
    env = make_device_env("token_recall_small")

    def trainer():
        return InGraphTrainer(agent, learner, env, UNROLL, BATCH, seed=1)

    first = trainer()
    state, carry = first.init(jax.random.key(0))
    state, carry, _ = first.run(state, carry, 2)
    saved = jax.device_get((state, carry))
    state, carry, want = first.run(state, carry, 2, counter_start=2)

    second = trainer()
    state2, carry2 = jax.tree_util.tree_map(jnp.asarray, saved)
    _, carry2, got = second.run(state2, carry2, 2, counter_start=2)
    assert float(got["total_loss"]) == float(want["total_loss"])
    for a, b in zip(jax.tree_util.tree_leaves(carry2.rollout),
                    jax.tree_util.tree_leaves(carry.rollout)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_acting_and_learning_agree_on_policy_in_bfloat16():
    """The fused loop is on-policy, so the importance ratios are 1 up to
    what acting (T = 1 through the cache) and learning (T = unroll) round
    differently, which the learner's diagnostics still measure (its
    targets no longer read it: ``on_policy``).  Every rounding to
    bfloat16 is an explicit one of a matmul operand (``ops/attention.py
    round_to``), so on the CPU, where both programs sum alike, the two
    agree almost to the bit."""
    agent = policy(jnp.bfloat16)
    learner = learner_of(agent, BATCH * UNROLL)
    env = make_device_env("token_recall_small")
    trainer = InGraphTrainer(agent, learner, env, UNROLL, BATCH, seed=1)
    state, carry = trainer.init(jax.random.key(0))
    for update in range(3):
        state, carry, metrics = trainer.train_step(
            state, carry, np.int32(update))
        assert abs(float(metrics["log_rho_mean"])) < 2e-3, update
        assert abs(float(metrics["log_rho_p95"])) < 2e-2, update
        assert float(metrics["ess_frac"]) > 0.999, update


# -- (f) the first family, as before the others came --------------------------

def test_the_first_family_builds_and_steps_as_before():
    """Its state holds no scan's, its parameter groups and statistics
    are the ones the class declares, and a step of it runs."""
    agent = policy()
    state = agent.initial_state(BATCH)
    assert state.ssm_state == () and state.conv_tail == ()
    assert len(state.keys) == 3
    assert agent.layer_groups == TokenPolicy.layer_groups
    assert agent.STATS == TokenPolicy.STATS
    assert TokenModelConfig.model_type == "afmoe"
    tokens = jnp.zeros((1, BATCH), jnp.int32)
    outputs = env_outputs(tokens, jnp.ones((1, BATCH), bool))
    params = agent.init(jax.random.key(0), tokens, outputs, state)
    (logits, _), new = agent.apply(params, tokens, outputs, state)
    assert logits.shape == (1, BATCH, VOCAB)
    assert int(new.written) == 1
