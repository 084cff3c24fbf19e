"""The token policy (models/token_policy.py) against its plain reference
(benchmark/references/afmoe_token.py), at the tiny preset: hidden 64,
4 heads / 2 kv heads of 16, 8 experts top-2 of which 2 are held, window
8, vocabulary 64, unroll 6, episodes of 16, seeded weights.

(a) one T = unroll forward, the loss and its gradients against the
    reference in float32, and in bfloat16 inside a band an fp8 cast
    falls out of;
(b) acting step by step through the cache gives the logits of a whole
    forward, across episode boundaries and the rings' wrap;
(c) the share adds up: the expert layer run as each share of the
    experts sums to the uncut reference layer;
(d) no token is dropped when every pair lands on one held expert;
(e) the world: its stream ignores the action, and the reference's copy
    emits the program's tokens under the program's keys;
(f) V-trace from a stored log-probability is V-trace from stored logits;
(g) the fused step trains through ``driver.main``, and each combination
    the policy is not built for is refused by name;
(h) the fused step's carry, the cache in it, survives a save and a
    restore bit for bit.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import manifest  # noqa: E402
from scalable_agent_tpu import driver  # noqa: E402
from scalable_agent_tpu.envs.device import make_device_env  # noqa: E402
from scalable_agent_tpu.models.token_policy import (  # noqa: E402
    TokenModelConfig,
    TokenPolicy,
)
from scalable_agent_tpu.ops import moe, vtrace  # noqa: E402
from scalable_agent_tpu.parallel import MeshSpec, make_mesh  # noqa: E402
from scalable_agent_tpu.runtime import InGraphTrainer  # noqa: E402
from scalable_agent_tpu.runtime.learner import (  # noqa: E402
    Learner,
    LearnerHyperparams,
    Trajectory,
)
from scalable_agent_tpu.types import (  # noqa: E402
    AgentOutput,
    Observation,
    StepOutput,
    StepOutputInfo,
)

ref = manifest.load_module(
    os.path.join(ROOT, "benchmark", "references", "afmoe_token.py"),
    "reference_afmoe_token_tests")

UNROLL, EPISODE, BATCH = 6, 16, 4
TINY = {
    "model_type": "afmoe", "hidden_act": "silu", "score_func": "sigmoid",
    "rope_scaling": None, "vocab_size": 64, "hidden_size": 64,
    "head_dim": 16, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_experts": 8, "num_experts_per_tok": 2, "num_shared_experts": 1,
    "num_hidden_layers": 3, "num_dense_layers": 1,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "full_attention"],
    "sliding_window": 8, "route_scale": 2.826, "route_norm": True,
    "rope_theta": 10000, "rms_norm_eps": 1e-05, "mup_enabled": True,
    "experts_held": 2, "first_expert": 0, "reference_block": 2,
    "loss": {"name": "vtrace", "entropy_cost": 0.00025,
             "baseline_cost": 0.5, "discounting": 0.99,
             "reward_clipping": "abs_one", "clip_rho_threshold": 1.0,
             "clip_pg_rho_threshold": 1.0},
    "optimizer": {"name": "rmsprop", "learning_rate": 0.00048,
                  "rmsprop_decay": 0.99, "rmsprop_momentum": 0.0,
                  "rmsprop_epsilon": 0.1, "initial_mean_square": 1.0,
                  "total_environment_frames": 1e9},
}
WORLD = {"name": "token_recall_small", "vocab_size": 64,
         "episode_length": EPISODE, "period": 10}
MODEL = TokenModelConfig.from_dict(TINY)


def policy(dtype=jnp.float32):
    return TokenPolicy(model=MODEL, unroll_length=UNROLL,
                       episode_length=EPISODE, compute_dtype=dtype)


def weights(seed=5):
    return {"params": ref.to_tree(ref.make_weights(TINY, seed))}


def env_outputs(tokens, done, reward=None):
    zeros = jnp.zeros(tokens.shape, jnp.float32)
    return StepOutput(
        reward=zeros if reward is None else reward,
        info=StepOutputInfo(zeros, jnp.zeros(tokens.shape, jnp.int32)),
        done=done, observation=Observation(frame=tokens))


def stream(steps, batch=BATCH, seed=0, p_done=0.15):
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(0, 64, (steps, batch)), jnp.int32)
    done = np.asarray(rng.random((steps, batch)) < p_done)
    done[0] = True
    return tokens, jnp.asarray(done)


def learner_of(agent):
    mesh = make_mesh(MeshSpec(data=1), devices=jax.devices()[:1])
    return Learner(agent, LearnerHyperparams(), mesh,
                   frames_per_update=BATCH * UNROLL)


def trajectory(agent, params, seed=3):
    """One unroll as the fused rollout lays it out, made by hand: T+1
    entries, behaviour log-probabilities from the policy's own logits
    moved a little off, so that the importance ratios are not 1."""
    tokens, done = stream(UNROLL + 1, seed=seed)
    rng = np.random.default_rng(seed + 100)
    actions = jnp.asarray(rng.integers(0, 64, (UNROLL + 1, BATCH)),
                          jnp.int32)
    reward = jnp.asarray(rng.integers(0, 2, (UNROLL + 1, BATCH)),
                         jnp.float32)
    state = agent.initial_state(BATCH)
    (logits, _), _ = agent.apply(
        params, actions, env_outputs(tokens, done, reward), state)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    taken = jnp.take_along_axis(logp[:-1], actions[1:, :, None],
                                -1)[..., 0]
    noise = jnp.asarray(rng.normal(0, 0.2, taken.shape), jnp.float32)
    behaviour = jnp.concatenate(
        [jnp.zeros((1, BATCH)), taken + noise])
    traj = Trajectory(
        agent_state=state,
        env_outputs=env_outputs(tokens, done, reward),
        agent_outputs=AgentOutput(
            action=actions, policy_logits=behaviour[..., None],
            baseline=jnp.zeros((UNROLL + 1, BATCH))))
    batch = ref.Batch(actions, behaviour, reward, done, tokens,
                      ref.empty_history(TINY, BATCH))
    return traj, batch


def flat_grads(tree):
    return ref.from_tree(tree["params"] if "params" in tree else tree)


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


# -- (a) forward, loss and gradients against the reference --------------------

@pytest.fixture(scope="module")
def float32_pair():
    agent, params = policy(), weights()
    traj, batch = trajectory(agent, params)
    learner = learner_of(agent)
    (loss, _), grads = jax.value_and_grad(
        learner._loss, has_aux=True)(params, traj, None)
    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: ref.loss(TINY, p, batch))(params["params"])
    (logits, baseline), _ = agent.apply(
        params, traj.agent_outputs.action, traj.env_outputs,
        traj.agent_state)
    ref_logits, ref_baseline, _ = ref.forward(
        TINY, params["params"], batch.token, batch.done, batch.history)
    return dict(loss=(loss, ref_loss), logits=(logits, ref_logits),
                baseline=(baseline, ref_baseline),
                grads=(flat_grads(grads), ref.from_tree(ref_grads)))


@pytest.mark.parametrize("what", ["logits", "baseline", "loss"])
def test_float32_forward_and_loss_are_the_references(float32_pair, what):
    got, want = float32_pair[what]
    assert rel(got, want) < 1e-5


@pytest.mark.parametrize("leaf", sorted(
    "/".join(path) for path in ref.weight_shapes(TINY)))
def test_float32_gradient_is_the_references(float32_pair, leaf):
    got, want = float32_pair["grads"]
    path = tuple(leaf.split("/"))
    scale = max(float(np.max(np.abs(v))) for v in want.values())
    gap = float(np.max(np.abs(np.asarray(got[path], np.float64)
                              - np.asarray(want[path], np.float64))))
    assert gap <= 1e-5 * scale, (leaf, gap, scale)


BFLOAT16_BAND = 0.02       # the loss, against the float32 reference's


def test_bfloat16_loss_is_inside_a_band_fp8_falls_out_of():
    params = weights()
    agent = policy(jnp.bfloat16)
    traj, batch = trajectory(policy(), params)
    traj = traj._replace(agent_state=agent.initial_state(BATCH))
    loss, _ = learner_of(agent)._loss(params, traj, None)
    want = float(ref.loss(TINY, params["params"], batch))
    fp8 = float(ref.loss(TINY, params["params"], batch, quant="fp8"))
    assert abs(float(loss) - want) / abs(want) < BFLOAT16_BAND
    assert abs(fp8 - want) / abs(want) > BFLOAT16_BAND


# -- (b) acting through the cache is the whole forward ------------------------

@pytest.fixture(scope="module")
def forty_steps():
    """40 steps of 4 envs in episodes of 16, staggered: every env
    crosses two episode boundaries, the window ring (8 + 6 slots) wraps
    twice and the full ring (16 + 6) once."""
    steps = 40
    rng = np.random.default_rng(11)
    tokens = jnp.asarray(rng.integers(0, 64, (steps, BATCH)), jnp.int32)
    offset = np.arange(BATCH) * (EPISODE // BATCH)
    done = (np.arange(steps)[:, None] + offset[None, :]) % EPISODE == 0
    done[0] = True
    done = jnp.asarray(done)
    agent, params = policy(), weights(9)
    step = jax.jit(lambda p, e, s: agent.apply(
        p, jnp.zeros(e.done.shape, jnp.int32), e, s))
    state, rows = agent.initial_state(BATCH), []
    for t in range(steps):
        (logits, _), state = step(
            params, env_outputs(tokens[t:t + 1], done[t:t + 1]), state)
        rows.append(logits[0])
    return agent, params, tokens, done, jnp.stack(rows), state


def test_stepwise_logits_are_the_references_whole_forward(forty_steps):
    _, params, tokens, done, stepwise, _ = forty_steps
    whole, _, _ = ref.forward(TINY, params["params"], tokens, done,
                              ref.empty_history(TINY, BATCH))
    assert rel(stepwise, whole) < 1e-5


@pytest.mark.parametrize("chunk", [2, 5, 7])
def test_stepwise_logits_are_the_chunked_forwards(forty_steps, chunk):
    agent, params, tokens, done, stepwise, last = forty_steps
    state, rows = agent.initial_state(BATCH), []
    for t in range(0, tokens.shape[0], chunk):
        (logits, _), state = agent.apply(
            params, jnp.zeros((chunk, BATCH), jnp.int32),
            env_outputs(tokens[t:t + chunk], done[t:t + chunk]), state)
        rows.append(logits)
    got = jnp.concatenate(rows)
    assert rel(got, stepwise[:got.shape[0]]) < 1e-5
    if got.shape[0] == stepwise.shape[0]:
        for a, b in zip(jax.tree_util.tree_leaves(state),
                        jax.tree_util.tree_leaves(last)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=1e-5)


def test_the_update_unrolls_from_the_rollouts_own_rings(forty_steps):
    """``unroll_state``: the rings as a rollout LEFT them, under the
    counters of its start, give the forward that the start's own rings
    give."""
    agent, params, tokens, done, _, _ = forty_steps
    state = agent.initial_state(BATCH)
    zeros = jnp.zeros((UNROLL, BATCH), jnp.int32)
    for t in range(0, 30, UNROLL):
        start = state
        (_, _), state = agent.apply(
            params, zeros, env_outputs(tokens[t:t + UNROLL],
                                       done[t:t + UNROLL]), state)
    t = 30 - UNROLL
    again = env_outputs(tokens[t:t + UNROLL + 1], done[t:t + UNROLL + 1])
    actions = jnp.zeros((UNROLL + 1, BATCH), jnp.int32)
    (want, _), _ = agent.apply(params, actions, again, start)
    (got, _), _ = agent.apply(params, actions, again,
                              agent.unroll_state(start, state))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_the_decode_gauge_is_the_kernels_block_lists_over_a_rollout(
        forty_steps, monkeypatch):
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
    agent, params, tokens, done, stepwise, _ = forty_steps
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


# -- (c) the share adds up ----------------------------------------------------

@pytest.fixture(scope="module")
def expert_layer_inputs():
    rng = jax.random.key(2)
    hidden, width, experts = 64, 32, 8
    keys = jax.random.split(rng, 6)
    # enough pairs (640) for a chunk of the grouped path (512 rows, two
    # of the product's row tiles) to be less than all of them
    x = jax.random.normal(keys[0], (320, hidden), jnp.float32)
    p = {"router": {"kernel": jax.random.normal(
            keys[1], (hidden, experts)) / 8.0},
         "experts": {
             "gate_proj": jax.random.normal(
                 keys[2], (experts, hidden, width)) / 8.0,
             "up_proj": jax.random.normal(
                 keys[3], (experts, hidden, width)) / 8.0,
             "down_proj": jax.random.normal(
                 keys[4], (experts, width, hidden)) / 6.0},
         "shared": ref.to_tree({
             ("gate_proj", "kernel"): jnp.zeros((hidden, width)),
             ("up_proj", "kernel"): jnp.zeros((hidden, width)),
             ("down_proj", "kernel"): jnp.zeros((width, hidden))})}
    whole = ref.expert_layer(TINY, p, x, lambda v: v, experts=(0, experts))
    return x, p, whole


# The grouped path's chunk (ops/moe.py), by how many of the eight experts
# a chip holds: two, and the sorted pairs are walked 512 rows at a time
# (of 640 pairs); four, and there is nothing to compact: one chunk holds
# every pair.
HELD = pytest.mark.parametrize("held", [2, 4], ids=["compact", "every_pair"])


def held_share(x, p, first, held=2, every_expert=False):
    routing = moe.route(x, p["router"]["kernel"], jnp.zeros((8,)), 2,
                        TINY["route_scale"], True)
    stack = {k: v[first:first + held] for k, v in p["experts"].items()}
    return moe.held_experts(x, routing, stack["gate_proj"],
                            stack["up_proj"], stack["down_proj"], first, 8,
                            jnp.float32, every_expert=every_expert)


@pytest.mark.parametrize("first", [0, 2, 4, 6])
def test_a_decode_steps_share_is_the_grouped_products(
        expert_layer_inputs, first):
    """A decode step runs every held expert over every row and weights
    by the routing: the grouped product's sum and the same load (it
    sorts into no buffer, and says nothing of one)."""
    x, p, _ = expert_layer_inputs
    want, want_stats = held_share(x, p, first)
    got, stats = held_share(x, p, first, every_expert=True)
    assert rel(got, want) < 1e-5
    assert sorted(stats) == sorted(set(want_stats) - {"compact_share"})
    for name, value in stats.items():
        assert float(want_stats[name]) == pytest.approx(float(value)), name


def test_a_decode_step_of_the_policy_runs_every_expert():
    """The policy takes the decode step's form at one token an env, and
    the grouped product over an unroll: ``ragged_dot`` is in the
    unroll's program alone."""
    agent, params = policy(), weights()
    for steps, grouped in ((1, False), (UNROLL, True)):
        tokens, done = stream(steps)
        text = str(jax.make_jaxpr(
            lambda p: agent.apply(
                p, jnp.zeros((steps, BATCH), jnp.int32),
                env_outputs(tokens, done), agent.initial_state(BATCH)))(
                    params))
        assert ("ragged_dot" in text) == grouped, steps


@pytest.mark.parametrize("first,held", [(0, 2), (2, 2), (4, 2), (6, 2),
                                        (0, 4), (4, 4)])
def test_a_share_is_the_references_share(expert_layer_inputs, first, held):
    x, p, _ = expert_layer_inputs
    got, stats = held_share(x, p, first, held)
    stack = {k: v[first:first + held] for k, v in p["experts"].items()}
    want = ref.expert_layer(TINY, dict(p, experts=stack), x,
                            lambda v: v, experts=(first, held))
    assert rel(got, want) < 1e-5
    assert float(stats["compact_share"]) == (held == 2)


@HELD
@pytest.mark.parametrize("family", ["afmoe", "deepseek_v3"])
def test_the_shares_sum_to_the_uncut_layer(expert_layer_inputs, family,
                                           held):
    if family == "afmoe":
        x, p, whole = expert_layer_inputs
        parts = [held_share(x, p, first, held)
                 for first in range(0, 8, held)]
        total = sum(part for part, _ in parts)   # the shared expert is 0
    else:       # two shared experts, counted once; its own router's rule
        from test_kanana_policy import shares_of_the_layer

        total, whole, parts = shares_of_the_layer(held)
    assert rel(total, whole) < 1e-5
    # every pair lands on exactly one share
    assert sum(float(stats["pairs_here_share"])
               for _, stats in parts) == pytest.approx(1.0)


# -- (d) no token is dropped --------------------------------------------------

@HELD
@pytest.mark.parametrize("every_expert", [False, True])
@pytest.mark.parametrize("held_expert", [0, 1])
def test_no_pair_is_dropped_when_all_land_on_one_expert(
        expert_layer_inputs, held_expert, every_expert, held):
    """All 640 pairs land here: more than a chunk's 512 rows, so where
    there is something to compact the walk goes on to a second chunk."""
    x, p, _ = expert_layer_inputs
    tokens = x.shape[0]
    routing = moe.Routing(
        jnp.full((tokens, 2), held_expert, jnp.int32),
        jnp.tile(jnp.asarray([[0.7, 0.4]], jnp.float32), (tokens, 1)))
    stack = {k: v[:held] for k, v in p["experts"].items()}
    got, stats = moe.held_experts(
        x, routing, stack["gate_proj"], stack["up_proj"],
        stack["down_proj"], 0, 8, jnp.float32, every_expert=every_expert)
    one = (jax.nn.silu(x @ stack["gate_proj"][held_expert])
           * (x @ stack["up_proj"][held_expert])
           ) @ stack["down_proj"][held_expert]
    assert rel(got, 1.1 * one) < 1e-5
    assert float(stats["pairs_here_share"]) == 1.0
    assert every_expert or float(stats["compact_share"]) == 0.0
    assert float(stats["tokens_per_expert_mean"]) == 2 * tokens / held
    assert float(stats["expert_load_max_over_mean"]) == held


@HELD
def test_the_expert_layers_gradient_ignores_rows_no_pair_holds(
        expert_layer_inputs, held):
    """Rows of the sorted buffer past the pairs that landed here are in
    no group; neither pass may read them."""
    x, p, _ = expert_layer_inputs

    def total(x, experts):
        routing = moe.route(x, p["router"]["kernel"], jnp.zeros((8,)), 2,
                            1.0, True)
        y, _ = moe.held_experts(x, routing, experts["gate_proj"][:held],
                                experts["up_proj"][:held],
                                experts["down_proj"][:held], 0, 8,
                                jnp.float32)
        return jnp.sum(jnp.square(y))

    def want(x, experts):
        stack = {k: v[:held] for k, v in experts.items()}
        y = ref.expert_layer(
            dict(TINY, route_scale=1.0), dict(p, experts=stack), x,
            lambda v: v, experts=(0, held))
        return jnp.sum(jnp.square(y))

    got = jax.grad(total, argnums=(0, 1))(x, p["experts"])
    ref_grads = jax.grad(want, argnums=(0, 1))(x, p["experts"])
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref_grads)):
        assert np.isfinite(np.asarray(a)).all()
        assert rel(a, b) < 1e-4


def shapes_in(jaxpr):
    """The shape of every array a jaxpr makes, its sub-jaxprs' too."""
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            yield tuple(var.aval.shape)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from shapes_in(sub)


def test_the_walk_agrees_with_one_chunk_on_either_side_of_a_chunks_rows(
        expert_layer_inputs):
    """512 pairs land on the two held experts and fill the first chunk
    to its last row; 513 need a second.  Either way the layer's value and
    every gradient are those of one chunk with room for every pair (the
    same two experts told they are two of four: nothing to compact),
    ``compact_share`` says whether the first chunk held the pass, and
    no activation of the walk, forward or backward, has a row a pair
    (the sort's own index arrays do, a few numbers wide)."""
    x, p, _ = expert_layer_inputs
    tokens, pairs, rows = x.shape[0], 2 * x.shape[0], 512
    assert moe.compact_rows(pairs, 2, 8) == rows
    assert moe.compact_rows(pairs, 2, 4) == pairs
    stacks = [p["experts"][name][:2]
              for name in ("gate_proj", "up_proj", "down_proj")]
    weights = jax.random.uniform(jax.random.key(3), (tokens, 2),
                                 jnp.float32, 0.1, 1.0)

    def layer(num_experts, chosen):
        def value(x, weights, *stacks):
            y, stats = moe.held_experts(
                x, moe.Routing(chosen, weights), *stacks, 0, num_experts,
                jnp.float32)
            return jnp.sum(jnp.square(y)), (y, stats)
        return jax.value_and_grad(value, argnums=range(5), has_aux=True)

    for landed in (rows, rows + 1):
        # pair i lands on held expert i % 2 if i is among the first
        # ``landed`` of a shuffle, else on one of the six held elsewhere
        at = jax.random.permutation(jax.random.key(landed), pairs)
        chosen = jnp.where(at < landed, at % 2, 2 + at % 6).astype(
            jnp.int32).reshape(tokens, 2)
        (_, (got, stats)), grads = layer(8, chosen)(x, weights, *stacks)
        (_, (want, want_stats)), want_grads = layer(4, chosen)(
            x, weights, *stacks)
        assert float(stats["compact_share"]) == (landed == rows)
        assert float(want_stats["compact_share"]) == 0.0
        assert float(stats["pairs_here_share"]) == pytest.approx(
            landed / pairs)
        assert float(jnp.max(jnp.abs(want))) > 0.0
        assert rel(got, want) < 1e-6
        for a, b in zip(grads, want_grads):
            assert float(jnp.max(jnp.abs(b))) > 0.0
            assert rel(a, b) < 1e-6

    def wide(num_experts):
        return {shape for shape in shapes_in(jax.make_jaxpr(
            layer(num_experts, chosen))(x, weights, *stacks).jaxpr)
                if len(shape) == 2 and shape[0] >= pairs
                and shape[1] >= stacks[0].shape[-1]}

    assert not wide(8)
    assert (pairs, x.shape[1]) in wide(4)


# -- (e) the world ------------------------------------------------------------

def roll(env, actions, seeds):
    state, first = env.initial(seeds)
    _, outs = jax.lax.scan(env.step, state, actions)
    return first, outs


def test_the_stream_ignores_the_action():
    env = make_device_env("token_recall_small")
    seeds = np.arange(BATCH, dtype=np.int32) + 1
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.integers(0, 64, (40, BATCH)), jnp.int32)
    b = jnp.asarray(rng.integers(0, 64, (40, BATCH)), jnp.int32)
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


@pytest.mark.parametrize("level, world", [
    ("token_recall_small", WORLD),
    ("token_recall", {"vocab_size": 25024, "episode_length": 4096,
                      "period": 2560})])
def test_the_references_world_emits_the_programs_tokens(level, world):
    env = make_device_env(level)
    seeds = np.arange(BATCH, dtype=np.int32) + 1
    rng = np.random.default_rng(4)
    actions = jnp.asarray(
        rng.integers(0, world["vocab_size"], (40, BATCH)), jnp.int32)
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


# -- the reference inside a run's time limit ----------------------------------

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


# -- (f) V-trace from the stored log-probability ------------------------------

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


@pytest.mark.parametrize("what", ["loss", "gradient"])
def test_the_on_policy_learner_does_not_read_the_stored_ratio(what):
    """The learner the fused loop builds (``on_policy=True``): its loss
    and gradient are the reference's at the behaviour log-probabilities
    the update's own forward pass gives, though the trajectory's are
    0.2 off."""
    agent = policy()
    params = weights()
    traj, batch = trajectory(agent, params)
    mesh = make_mesh(MeshSpec(data=1), devices=jax.devices()[:1])
    learner = Learner(agent, LearnerHyperparams(), mesh,
                      frames_per_update=BATCH * UNROLL, on_policy=True)
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
        got, want_grads = flat_grads(grads), ref.from_tree(want_grads)
        scale = max(float(np.max(np.abs(v))) for v in want_grads.values())
        worst = max(float(np.max(np.abs(
            np.asarray(got[path], np.float64)
            - np.asarray(want_grads[path], np.float64))))
            for path in want_grads)
        assert worst <= 1e-5 * scale, (worst, scale)


# -- the benchmark's whole-run readers -----------------------------------------

def _traced_window(step=1.756, cut=0.05, sliver=0.02):
    """A trace as ``trinity.ingraph``'s: it begins ``cut`` into one run
    of the step, holds the next whole, and ends ``sliver`` into a third;
    every run is a rollout op (half) and an update op (half)."""
    from benchmark.lib import trace_reduce

    plane = "/device:TPU:0"
    events = trace_reduce.EventList()
    spans = [(0.0, step - cut), (step - cut, step),
             (2 * step - cut, sliver)]
    for i, (start, dur) in enumerate(spans):
        events.append(trace_reduce.Event(
            plane, trace_reduce.MODULES_LINE, f"jit__fused({i})", start,
            dur))
        if i == 0:       # cut at its start: the rollout's head is missing
            halves = [("fusion.1", step / 2 - cut), ("fusion.2", step / 2)]
        elif i == 1:
            halves = [("fusion.1", step / 2), ("fusion.2", step / 2)]
        else:
            halves = [("fusion.1", sliver)]
        at = start
        for name, length in halves:
            events.append(trace_reduce.Event(
                plane, trace_reduce.OPS_LINE, f"%{name} = f32[] fusion()",
                at, length))
            at += length
    return events


class _Ctx:
    def __init__(self, events):
        self.events = events
        self.traffic = {"step_module": "_fused"}
        self.notes = []
        self.op_scopes = {
            "fusion.1": "jit(_fused)/rollout/while/body/attention/dot",
            "fusion.2": "jit(_fused)/learner_update/layer_1/moe/experts"}
        self.peak = {"flops_bf16": 197e12}
        self.chips = 1
        self.config = dict(TINY, mean_context=8)
        self.frames_per_update = 8192.0
        self.reference = ref


@pytest.mark.parametrize("reader, want", [
    ("step_device_ms", 1756.0), ("rollout", 50.0), ("moe", 50.0)])
def test_the_whole_run_readers_leave_out_the_runs_the_trace_cut(
        reader, want):
    from benchmark.lib import readers, whole_runs

    ctx = _Ctx(_traced_window())
    # what the accepted reader gives there: the window over three
    assert abs(readers.step_device_ms(ctx) - (2 * 1756 - 50 + 20) / 3) < 1
    got = {"step_device_ms": lambda: whole_runs.step_device_ms(ctx),
           "rollout": lambda: whole_runs.share(ctx, "rollout"),
           "moe": lambda: whole_runs.share_where(ctx, r"\bmoe\b")}[reader]()
    assert abs(got - want) < 1e-6
    assert not ctx.notes
    assert 0 < whole_runs.mfu(ctx) < 100


def test_a_window_with_no_whole_run_reads_the_longest_cut_run():
    from benchmark.lib import trace_reduce, whole_runs

    events = trace_reduce.EventList(
        e for e in _traced_window() if e.start < 1.7)
    ctx = _Ctx(events)
    assert abs(whole_runs.step_device_ms(ctx) - 1706.0) < 1e-6
    assert len(ctx.notes) == 1 and "no whole step run" in ctx.notes[0]


# -- (g) through the driver ---------------------------------------------------

def driver_argv(tmp_path, *more):
    path = tmp_path / "tiny.json"
    path.write_text(json.dumps(TINY))
    return [
        "--mode=train", f"--logdir={tmp_path / 'run'}",
        f"--model_config={path}", "--level_name=token_recall_small",
        "--train_backend=ingraph", f"--batch_size={BATCH}",
        f"--unroll_length={UNROLL}", "--num_action_repeats=1",
        "--compute_dtype=float32", "--mesh_data=1",
        f"--total_environment_frames={3 * BATCH * UNROLL}",
        "--log_interval_s=0.2", *more]


def test_three_updates_through_the_driver(tmp_path):
    final = driver.main(driver_argv(tmp_path))
    assert final["env_frames"] == 3 * BATCH * UNROLL
    assert np.isfinite(final["total_loss"])
    assert final["nonfinite_skips"] == 0
    for name in TokenPolicy.STATS:
        assert np.isfinite(final[name]), name
    assert 0.0 < final["moe/pairs_here_share"] < 1.0
    # the update's attention says how many key blocks it visited, and
    # the number is a gauge like the expert layers' (ISSUE 33)
    assert 0.0 < final["attention/key_blocks_visited_share"] <= 1.0
    assert driver.get_registry().gauge(
        "attention/key_blocks_visited_share").value == pytest.approx(
            final["attention/key_blocks_visited_share"])
    for group in TokenPolicy.layer_groups:
        assert f"devtel/learn/grad_norm_{group}" in (
            driver.get_registry().snapshot())


@pytest.mark.parametrize("flags, names", [
    (["--train_backend=host"], "host loop"),
    (["--loss=impact"], "--loss=impact"),
    (["--replay_ratio=1"], "--replay_ratio=1"),
    (["--mesh_data=4"], "a mesh of 4 devices"),
    (["--sentinel_interval=5"], "--sentinel_interval=5"),
    (["--level_name=fake_small"], "token world"),
])
def test_what_the_token_policy_is_not_built_for_is_refused_by_name(
        tmp_path, flags, names):
    argv = [a for a in driver_argv(tmp_path)
            if a.split("=")[0] not in {f.split("=")[0] for f in flags}]
    with pytest.raises(ValueError, match=names):
        driver.main(argv + flags)


# -- (h) the carry, the cache in it -------------------------------------------

def test_the_carry_survives_a_save_and_a_restore_bit_for_bit():
    agent = policy()
    learner = learner_of(agent)
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
    learner = learner_of(agent)
    env = make_device_env("token_recall_small")
    trainer = InGraphTrainer(agent, learner, env, UNROLL, BATCH, seed=1)
    state, carry = trainer.init(jax.random.key(0))
    for update in range(3):
        state, carry, metrics = trainer.train_step(
            state, carry, np.int32(update))
        assert abs(float(metrics["log_rho_mean"])) < 2e-3, update
        assert abs(float(metrics["log_rho_p95"])) < 2e-2, update
        assert float(metrics["ess_frac"]) > 0.999, update


# -- the learner's telemetry follows the agent --------------------------------

def test_the_impala_agents_report_what_they_reported():
    from scalable_agent_tpu.runtime.learner import learning_telemetry_spec

    gauges = learning_telemetry_spec().gauges()
    for group in ("torso", "core", "heads"):
        assert f"grad_norm_{group}" in gauges
    assert not [g for g in gauges if "attention" in g or "experts" in g]
    assert not learning_telemetry_spec().histograms()


# -- the benchmark's harness drives the cell, at the tiny preset --------------

def _tiny_checkout(tmp_path, compute_dtype="float32"):
    """A copy of the benchmark whose ``trinity.ingraph`` files hold the
    tiny preset (the harness hands a cell's reference the configuration
    file whole, so the preset has to BE the file)."""
    import shutil

    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    os.symlink(os.path.join(ROOT, "scalable_agent_tpu"),
               root / "scalable_agent_tpu")
    config_path = root / "benchmark/configs/trinity_mini_ep8.json"
    config = json.loads(config_path.read_text())
    config.update(TINY)
    config["flags"].update(
        unroll_length=UNROLL, compute_dtype=compute_dtype, mesh_data=1,
        learning_rate=TINY["optimizer"]["learning_rate"])
    config["sizing"]["fused_env_batch_1chip"] = BATCH
    config["mean_context"] = 8
    config_path.write_text(json.dumps(config))
    traffic_path = root / "benchmark/traffic/fused_token_recall_u256.json"
    traffic = json.loads(traffic_path.read_text())
    traffic["flags"]["level_name"] = "token_recall_small"
    traffic["world"].update(WORLD)
    traffic_path.write_text(json.dumps(traffic))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    return root, env


def test_the_cell_rehearses_through_the_harness_at_the_tiny_preset(tmp_path):
    """``benchmark/run.py --rehearse 1`` on a copy of the benchmark whose
    ``trinity.ingraph`` files hold the tiny preset: the probe's patches,
    the seeded weights into the policy's own tree, the three checked
    steps against the reference's own rollout of the world, the readers.
    In float32 the program IS the reference: every compared number under
    1e-4."""
    import subprocess

    root, env = _tiny_checkout(tmp_path)
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "trinity.ingraph", "--rehearse", "1", "--seed", "3000000007",
         "--seconds", "2", "--trace", "1"],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] and line["checks_failed"] == {}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["compared"]) == {"loss1_gap", "loss_gap",
                                     "grad_median_gap", "delta_norm_gap"}
    for name, row in line["compared"].items():
        assert row["value"] < 1e-4, (name, row)
    assert "expert_load_max_over_mean" in (
        line["rehearsal"]["metrics_that_would_print"])
    (device,) = [l for l in lines if l.startswith("device:")]
    for attribute in ("core_impl", "conv_backend", "torso_type"):
        assert f"'{attribute}': None" in device


def test_seeds_big_reads_three_seeds_with_one_state(tmp_path):
    """``benchmark/seeds_big.py`` at the tiny preset: every third
    dispatch starts from the next seed's weights, the optimizer's leaves
    and the carry re-made in place; in float32 each seed's three steps
    are the reference's (a seed that inherited anything of the last
    one's would not be), and both planted faults read far off."""
    import subprocess

    root, env = _tiny_checkout(tmp_path)
    done = subprocess.run(
        [sys.executable, "benchmark/seeds_big.py", "--workload",
         "trinity.ingraph", "--rehearse", "1", "--seeds",
         "3000000007,3000000008,11", "--faults", "1"],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    rows = [json.loads(line.split(" ", 1)[1])
            for line in done.stdout.splitlines()
            if line.startswith("seed ")]
    sound = [row for row in rows if row["kind"] == "sound"]
    assert [row["seed"] for row in sound] == [3000000007, 3000000008, 11]
    for row in sound:
        for name, value in row["compared"].items():
            assert value < 1e-4, (row["seed"], name, value)
    planted = {row["kind"]: row["compared"] for row in rows
               if row["kind"] != "sound"}
    assert set(planted) == {"control_fp8", "half_batch"}
    assert planted["half_batch"]["loss1_gap"] > 0.3
    assert planted["control_fp8"]["loss_gap"] > 0.1
