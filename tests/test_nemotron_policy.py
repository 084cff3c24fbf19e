"""The token policy of the ``nemotron_h`` family (models/token_policy.py:
every layer one mixer alone, a Mamba-2 scan with a matrix state a head
(ops/ssd.py), experts without a gate (ops/moe.py) or plain grouped-query
attention) against its plain reference
(benchmark/references/nemotron_h_token.py: a ``lax.scan`` over tokens,
every held expert over every token), at a tiny preset: hidden 64, 4 scan
heads of 8 channels in 2 groups of 16 states, chunks of 4 tokens, 4
query heads on 2 key heads of 8, 128 experts of which 8 are held, 6 a
token, one shared, vocabulary 64, unroll 6, episodes of 16, seeded
weights, the pattern ``MEM*E``.

(a) one T = unroll forward, the loss and every leaf's gradient against
    the reference in float32 (1e-5); the reference's planted fault moves
    its loss;
(b) acting a token at a time through the cache gives the logits and
    baseline of the reference's whole forward, across episode ends, and
    of forwards a few tokens at a time (resets inside a chunk and at a
    chunk's edge);
(c) ``ssd_scan``'s kernels under the interpreter against the
    token-by-token recurrence, forward and backward, at chunk sizes that
    do and do not divide the unroll;
(d) the 16 shares of one expert layer, the shared expert counted once,
    are the uncut layer; the ungated stages of ``held_experts`` against
    a dense loop over experts, walking past the first chunk;
(e) ``TokenModelConfig.from_dict`` refuses a pattern letter and an
    activation it does not build; the state's shapes and bytes;
The driver, the world, the configuration file and the benchmark's
harness at this preset are in tests/test_nemotron_harness.py.
"""

import functools
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

from benchmark.lib import manifest  # noqa: E402
from scalable_agent_tpu.models import token_policy  # noqa: E402
from scalable_agent_tpu.models.token_policy import (  # noqa: E402
    TokenModelConfig,
    TokenPolicy,
)
from scalable_agent_tpu.ops import moe, ssd  # noqa: E402
from scalable_agent_tpu.runtime.learner import Trajectory  # noqa: E402
from scalable_agent_tpu.types import AgentOutput  # noqa: E402
from test_sambay_policy import env_outputs, learner_of, rel  # noqa: E402

ref = manifest.load_module(
    os.path.join(ROOT, "benchmark", "references", "nemotron_h_token.py"),
    "reference_nemotron_h_token_tests")

UNROLL, EPISODE, BATCH, VOCAB = 6, 16, 4, 64
TINY = {
    "model_type": "nemotron_h", "mlp_hidden_act": "relu2",
    "mamba_hidden_act": "silu", "tie_word_embeddings": False,
    "attention_bias": False, "mamba_proj_bias": False, "mlp_bias": False,
    "use_bias": False, "use_conv_bias": True, "sliding_window": None,
    "vocab_size": VOCAB, "hidden_size": 64, "intermediate_size": 16,
    "num_hidden_layers": 5, "hybrid_override_pattern": "MEM*E",
    "mamba_num_heads": 4, "mamba_head_dim": 8, "ssm_state_size": 16,
    "n_groups": 2, "conv_kernel": 4, "chunk_size": 4, "expand": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "moe_intermediate_size": 16, "moe_shared_expert_intermediate_size": 32,
    "n_routed_experts": 128, "num_experts_per_tok": 6,
    "n_shared_experts": 1, "n_group": 1, "topk_group": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "layer_norm_epsilon": 1e-05, "rope_theta": 10000,
    "experts_held": 8, "first_expert": 0,
    "reference": "nemotron_h_token", "reference_block": 2,
    "mean_context": 8,
    "loss": {"name": "vtrace", "entropy_cost": 0.00025,
             "baseline_cost": 0.5, "discounting": 0.99,
             "reward_clipping": "abs_one", "clip_rho_threshold": 1.0,
             "clip_pg_rho_threshold": 1.0},
    "optimizer": {"name": "rmsprop", "learning_rate": 0.00048,
                  "rmsprop_decay": 0.99, "rmsprop_momentum": 0.0,
                  "rmsprop_epsilon": 0.1, "initial_mean_square": 1.0,
                  "total_environment_frames": 1e9},
}
MODEL = TokenModelConfig.from_dict(TINY)


def policy(dtype=jnp.float32, model=MODEL):
    return TokenPolicy(model=model, unroll_length=UNROLL,
                       episode_length=EPISODE, compute_dtype=dtype)


def weights(seed=5, cfg=TINY):
    return {"params": ref.to_tree(ref.make_weights(cfg, seed))}


def trajectory(agent, params, seed=3):
    """One unroll as the fused rollout lays it out, made by hand, with
    an episode's end inside it for two of the four envs (at token 3,
    inside the first chunk of 4, and at token 4, the second's first);
    behaviour log-probabilities from the policy's own logits moved a
    little off."""
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(0, VOCAB, (UNROLL + 1, BATCH)),
                         jnp.int32)
    done = np.zeros((UNROLL + 1, BATCH), bool)
    done[0] = True
    done[3, 1] = done[4, 2] = True
    done = jnp.asarray(done)
    actions = jnp.asarray(rng.integers(0, VOCAB, (UNROLL + 1, BATCH)),
                          jnp.int32)
    reward = jnp.asarray(rng.integers(0, 2, (UNROLL + 1, BATCH)),
                         jnp.float32)
    state = agent.initial_state(BATCH)
    (logits, _), _ = jax.jit(agent.apply)(
        params, actions, env_outputs(tokens, done, reward), state)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    taken = jnp.take_along_axis(logp[:-1], actions[1:, :, None],
                                -1)[..., 0]
    noise = jnp.asarray(rng.normal(0, 0.2, taken.shape), jnp.float32)
    behaviour = jnp.concatenate([jnp.zeros((1, BATCH)), taken + noise])
    traj = Trajectory(
        agent_state=state,
        env_outputs=env_outputs(tokens, done, reward),
        agent_outputs=AgentOutput(
            action=actions, policy_logits=behaviour[..., None],
            baseline=jnp.zeros((UNROLL + 1, BATCH))))
    batch = ref.Batch(actions, behaviour, reward, done, tokens,
                      ref.empty_history(TINY, BATCH))
    return traj, batch


# -- (a) forward, loss and gradients against the reference --------------------

@pytest.fixture(scope="module")
def float32_pair():
    agent, params = policy(), weights()
    traj, batch = trajectory(agent, params)
    learner = learner_of(agent)
    (loss, _), grads = jax.jit(jax.value_and_grad(
        lambda p, t: learner._loss(p, t, None), has_aux=True))(params, traj)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p, b: ref.loss(TINY, p, b)))(params["params"], batch)
    (logits, baseline), _ = jax.jit(agent.apply)(
        params, traj.agent_outputs.action, traj.env_outputs,
        traj.agent_state)
    ref_logits, ref_baseline, _ = jax.jit(
        lambda p, b: ref.forward(TINY, p, b.token, b.done, b.history))(
            params["params"], batch)
    return dict(loss=(loss, ref_loss), logits=(logits, ref_logits),
                baseline=(baseline, ref_baseline), batch=batch,
                params=params,
                grads=(ref.from_tree(grads["params"]),
                       ref.from_tree(ref_grads)))


@pytest.mark.parametrize("what", ["logits", "baseline", "loss"])
def test_float32_forward_and_loss_are_the_references(float32_pair, what):
    """1e-5: both are float32 sums of the same terms in another order
    (the program's scan goes a chunk of 4 tokens at a time through
    matrix products, the reference's a token at a time)."""
    got, want = float32_pair[what]
    assert rel(got, want) < 1e-5


@pytest.mark.parametrize("leaf", sorted(
    "/".join(path) for path in ref.weight_shapes(TINY)))
def test_float32_gradient_is_the_references(float32_pair, leaf):
    got, want = float32_pair["grads"]
    path = tuple(leaf.split("/"))
    scale = max(float(np.max(np.abs(v))) for v in want.values())
    # a leaf whose gradient is tiny beside the largest is held to the
    # float32 sum's own noise, not to its own size
    assert float(np.max(np.abs(np.asarray(got[path], np.float64)
                               - np.asarray(want[path], np.float64)))) < (
        1e-5 * max(float(np.max(np.abs(want[path]))), 1e-3 * scale))


def test_the_program_has_the_references_leaves_and_no_other():
    agent = policy()
    shapes = jax.eval_shape(
        lambda: agent.init(
            jax.random.key(0), jnp.zeros((1, BATCH), jnp.int32),
            env_outputs(jnp.zeros((1, BATCH), jnp.int32),
                        jnp.ones((1, BATCH), bool)),
            agent.initial_state(BATCH)))["params"]
    assert ({path: leaf.shape for path, leaf
             in ref.from_tree(shapes).items()}
            == {path: tuple(shape) for path, shape
                in ref.weight_shapes(TINY).items()})


def test_the_references_planted_fault_moves_its_loss(float32_pair):
    """``zero_chunk_state``: the update's scan starts every chunk of 4
    from a zero state.  It is the loss's alone: the reference's rollout
    under it is the sound one."""
    batch, params = float32_pair["batch"], float32_pair["params"]["params"]
    want = float(ref.loss(TINY, params, batch))
    planted = float(ref.loss(TINY, params, batch, quant="zero_chunk_state"))
    assert abs(planted - want) > 1e-3 * abs(want)
    sound = ref.forward(TINY, params, batch.token, batch.done, batch.history)
    same = ref.forward(TINY, params, batch.token, batch.done, batch.history,
                       "zero_chunk_state")
    assert rel(same[0], sound[0]) == 0.0


# -- (b) acting through the cache is the whole forward ------------------------

@pytest.fixture(scope="module")
def forty_steps():
    """40 steps of 4 envs in episodes of 16, staggered by 4 + env (so
    that ends fall on a chunk's first token, inside a chunk and on its
    last): every env crosses two episode ends and the attention ring
    (16 + 6 slots) wraps once."""
    steps = 40
    rng = np.random.default_rng(11)
    tokens = jnp.asarray(rng.integers(0, VOCAB, (steps, BATCH)), jnp.int32)
    offset = np.arange(BATCH) * (EPISODE // BATCH + 1)
    done = (np.arange(steps)[:, None] + offset[None, :]) % EPISODE == 0
    done[0] = True
    done = jnp.asarray(done)
    agent, params = policy(), weights(9)
    step = jax.jit(lambda p, e, s: agent.apply(
        p, jnp.zeros(e.done.shape, jnp.int32), e, s))
    state, logits, values = agent.initial_state(BATCH), [], []
    for t in range(steps):
        (row, value), state = step(
            params, env_outputs(tokens[t:t + 1], done[t:t + 1]), state)
        logits.append(row[0])
        values.append(value[0])
    return (agent, params, tokens, done, jnp.stack(logits),
            jnp.stack(values), state, step)


@pytest.mark.parametrize("what", ["logits", "baseline"])
def test_stepwise_outputs_are_the_references_whole_forward(
        forty_steps, what):
    _, params, tokens, done, logits, values, _, _ = forty_steps
    whole, baseline, _ = jax.jit(lambda p: ref.forward(
        TINY, p, tokens, done, ref.empty_history(TINY, BATCH)))(
            params["params"])
    got, want = ((logits, whole) if what == "logits"
                 else (values, baseline))
    assert rel(got, want) < 1e-5


@pytest.mark.parametrize("count", [4, 5, 7])
def test_stepwise_logits_are_the_kernels_forwards(forty_steps, count):
    """``count`` tokens a call go through the kernels, whose chunks of 4
    then start at other tokens than the episodes do: resets fall inside
    a chunk, on its first token and on its last; the state a call hands
    the next is the recurrence's."""
    agent, params, tokens, done, stepwise, _, last, step = forty_steps
    state, rows = agent.initial_state(BATCH), []
    for t in range(0, tokens.shape[0] - count + 1, count):
        (logits, _), state = step(
            params, env_outputs(tokens[t:t + count], done[t:t + count]),
            state)
        rows.append(logits)
    got = jnp.concatenate(rows)
    assert rel(got, stepwise[:got.shape[0]]) < 1e-5
    if got.shape[0] == stepwise.shape[0]:
        for mine, theirs in zip(state.ssm_state + state.conv_tail,
                                last.ssm_state + last.conv_tail):
            assert rel(mine, theirs) < 1e-5


@pytest.mark.parametrize("what", ["logits", "state"])
def test_the_update_unrolls_from_the_starts_states(forty_steps, what):
    """``unroll_state``: the rings as the rollout left them, the scans'
    states and tails as the unroll's start had them."""
    agent, params, tokens, done, stepwise, _, _, step = forty_steps
    state = agent.initial_state(BATCH)
    held = {}
    for t in range(2 * UNROLL):
        if t == UNROLL:
            held["start"] = state
        (_, _), state = step(
            params, env_outputs(tokens[t:t + 1], done[t:t + 1]), state)
    begin = agent.unroll_state(held["start"], state)
    if what == "state":
        assert begin.ssm_state is held["start"].ssm_state
        assert begin.conv_tail is held["start"].conv_tail
        assert begin.keys is state.keys
        return
    (logits, _), _ = step(
        params,
        env_outputs(tokens[UNROLL:2 * UNROLL], done[UNROLL:2 * UNROLL]),
        begin)
    assert rel(logits, stepwise[UNROLL:2 * UNROLL]) < 1e-5


# -- (c) the scan's kernels against the recurrence ----------------------------

def recurrence(x, delta, a, d, b, c, reset, state):
    """``ssd_scan`` a token at a time: the step in a ``lax.scan``."""
    def step(s, inputs):
        xt, dt, bt, ct, rt = inputs
        y, s = ssd.ssd_step(xt, dt, a, d, bt, ct, rt, s)
        return s, y

    def time_major(v):
        return jnp.swapaxes(v, 0, 1)

    state, y = jax.lax.scan(
        step, state, tuple(map(time_major, (x, delta, b, c, reset))))
    return time_major(y), state


def scan_operands(steps, seed=0):
    batch, heads, dim, groups, states = 2, 4, 8, 2, 16
    keys = jax.random.split(jax.random.key(seed), 8)
    return dict(
        x=jax.random.normal(keys[0], (batch, steps, heads, dim)),
        delta=jax.nn.softplus(
            jax.random.normal(keys[1], (batch, steps, heads)) - 1.0),
        a=-jnp.exp(2.0 * jax.random.uniform(keys[2], (heads,))),
        d=jax.random.normal(keys[3], (heads,)),
        b=jax.random.normal(keys[4], (batch, steps, groups, states)),
        c=jax.random.normal(keys[5], (batch, steps, groups, states)),
        state=jax.random.normal(keys[6], (batch, heads, dim, states)),
        reset=jax.random.uniform(keys[7], (batch, steps)) < 0.15)


_DIFFERENTIABLE = ("x", "delta", "a", "d", "b", "c", "state")


@functools.lru_cache(maxsize=None)
def scanned(steps, chunk):
    """(outputs, gradients) of the kernels and of the recurrence."""
    ops = scan_operands(steps)

    def run(fn):
        def loss(*values):
            y, last = fn(*values[:6], ops["reset"], values[6])
            return jnp.sum(y * jnp.cos(y)) + jnp.sum(last * last)

        values = [ops[name] for name in _DIFFERENTIABLE]
        return jax.jit(lambda *values: (
            fn(*values[:6], ops["reset"], values[6]),
            jax.grad(loss, argnums=tuple(range(7)))(*values)))(*values)

    return run(lambda *v: ssd.ssd_scan(*v, chunk=chunk)), run(recurrence)


# 17 tokens: chunks of 8 leave one over; 16: whole chunks of 8; 9 in one
# chunk of 16
_SHAPES = [(17, 8), (16, 8), (9, 16)]


@pytest.mark.parametrize("steps,chunk", _SHAPES)
@pytest.mark.parametrize("what", ["y", "state"])
def test_the_scans_kernels_are_the_recurrence(steps, chunk, what):
    (got, _), (want, _) = scanned(steps, chunk)
    at = ("y", "state").index(what)
    assert rel(got[at], want[at]) < 1e-5


@pytest.mark.parametrize("steps,chunk", _SHAPES)
@pytest.mark.parametrize("operand", _DIFFERENTIABLE)
def test_the_scans_backward_kernel_is_the_recurrences(steps, chunk, operand):
    (_, got), (_, want) = scanned(steps, chunk)
    at = _DIFFERENTIABLE.index(operand)
    assert rel(got[at], want[at]) < 2e-5


def test_one_token_is_a_step_and_no_kernel():
    ops = scan_operands(1)
    text = jax.jit(lambda **o: ssd.ssd_scan(
        o["x"], o["delta"], o["a"], o["d"], o["b"], o["c"], o["reset"],
        o["state"])).lower(**ops).as_text()
    assert "pallas" not in text and "custom_call" not in text


# -- (d) the share tied to the model, and the experts without a gate ----------

def shares_of_the_layer(held=8):
    """(the shares' sum, the uncut layer, each share's (routed part,
    load), what they were made from): 16 chips hold 8 of the 128 experts each (``first_expert``
    0, 8, ..., 120).  A chip's policy layer gives the shared expert's
    result plus its own experts' part of the routed sum (the router over
    all 128, six a token, the chosen scores normalised and scaled by
    2.5); the routed parts and the shared expert counted once are the
    reference's layer over all 128."""
    shares = 128 // held
    cfg = dict(TINY, experts_held=128, first_expert=0)
    whole = ref.to_tree(ref.make_weights(cfg, 17))["layer_1"]["moe"]
    m = jnp.asarray(np.random.default_rng(5).normal(size=(96, 64)),
                    jnp.float32)
    want = ref.expert_layer(cfg, whole, m, lambda x: x)
    shared = ref.relu2_mlp(whole["shared"], m, lambda x: x)
    assert float(jnp.max(jnp.abs(shared))) > 0.0
    parts = []
    for share in range(shares):
        model = TokenModelConfig.from_dict(dict(
            TINY, experts_held=held, first_expert=share * held))
        mine = dict(whole, experts={
            name: stack[share * held:(share + 1) * held]
            for name, stack in whole["experts"].items()})
        got, stats = token_policy._MoE(model, jnp.float32).apply(
            {"params": mine}, m)
        parts.append((got - shared, stats))
    return (shared + sum(part for part, _ in parts), want, parts,
            (cfg, whole, m, shared))


@pytest.fixture(scope="module")
def sixteen_shares():
    return shares_of_the_layer()


def test_the_sixteen_shares_sum_to_the_uncut_layer(sixteen_shares):
    total, whole, parts, _ = sixteen_shares
    assert len(parts) == 16
    assert rel(total, whole) < 1e-5
    # every pair lands on exactly one share
    assert sum(float(stats["pairs_here_share"])
               for _, stats in parts) == pytest.approx(1.0, abs=1e-6)


@pytest.mark.parametrize("first", range(0, 128, 8))
def test_a_share_is_the_references_share(sixteen_shares, first):
    _, _, parts, (cfg, whole, m, shared) = sixteen_shares
    mine = dict(whole, experts={
        name: stack[first:first + 8]
        for name, stack in whole["experts"].items()})
    want = ref.expert_layer(cfg, mine, m, lambda x: x,
                            experts=(first, 8)) - shared
    assert rel(parts[first // 8][0], want) < 1e-5


def walk_against_the_dense_loop(hidden, width):
    """640 tokens x 6 of 128 experts, 8 held, with the router pushed
    towards the held ones: more pairs land than the first chunk of the
    walk (512 rows) holds, so the loop walks on."""
    rng = np.random.default_rng(7)
    tokens, held = 640, 8
    x = jnp.asarray(rng.normal(size=(tokens, hidden)), jnp.float32)
    up = jnp.asarray(rng.normal(size=(held, hidden, width)) / 6, jnp.float32)
    down = jnp.asarray(rng.normal(size=(held, width, hidden)) / 4,
                       jnp.float32)
    scores = rng.normal(size=(tokens, 128))
    scores[:, :held] += 1.2
    chosen = jnp.asarray(np.argsort(-scores, axis=1)[:, :6], jnp.int32)
    routing = moe.Routing(chosen, jnp.asarray(
        rng.uniform(0.1, 1.0, size=(tokens, 6)), jnp.float32))

    def dense(x, up, down, weights):
        total = jnp.zeros_like(x)
        for expert in range(held):
            w = jnp.sum(jnp.where(chosen == expert, weights, 0.0), axis=1)
            hidden_rows = jnp.square(jax.nn.relu(x @ up[expert]))
            total = total + w[:, None] * (hidden_rows @ down[expert])
        return total

    def walked(x, up, down, weights):
        return moe.held_experts(
            x, moe.Routing(chosen, weights), None, up, down, 0, 128,
            jnp.float32, act="relu2")

    def loss(fn):
        return lambda *v: jnp.sum(jnp.sin(fn(*v)))

    values = (x, up, down, routing.weights)
    y, stats = walked(*values)
    return dict(
        y=(y, dense(*values)), stats=stats,
        grads=(jax.grad(loss(lambda *v: walked(*v)[0]), (0, 1, 2, 3))(
            *values), jax.grad(loss(dense), (0, 1, 2, 3))(*values)))


@pytest.fixture(scope="module")
def ungated_walk():
    return walk_against_the_dense_loop(32, 16)


@pytest.fixture(scope="module")
def padded_walk():
    """The same walk at widths that are more than one tile of the
    grouped product and not whole tiles (40 and 56 of tiles of 16, as
    2,688 and 1,856 are of 256): the stacks are padded to 64."""
    patch = pytest.MonkeyPatch()
    patch.setattr(moe, "_LANE_TILE", 16)
    patch.setattr(moe, "_LANE_PAD", 32)
    try:
        assert (moe.lane_padded(40), moe.lane_padded(56)) == (64, 64)
        return walk_against_the_dense_loop(40, 56)
    finally:
        patch.undo()


@pytest.mark.parametrize("size,padded", [
    (2688, 3072), (1856, 2048),            # nemotron_h: padded
    (2048, 2048), (1024, 1024), (768, 768),   # the first families': whole tiles
    (32, 32), (300, 300),                  # under one tile: left
])
def test_widths_are_padded_to_whole_tiles_of_the_grouped_product(
        size, padded):
    assert moe.lane_padded(size) == padded


@pytest.mark.parametrize("operand", ["y", "x", "up_proj", "down_proj",
                                     "weights"])
def test_the_padded_walk_is_the_dense_loop(padded_walk, operand):
    if operand == "y":
        got, want = padded_walk["y"]
        assert got.shape == want.shape == (640, 40)
    else:
        at = ["x", "up_proj", "down_proj", "weights"].index(operand)
        got, want = (side[at] for side in padded_walk["grads"])
        assert got.shape == want.shape
    assert rel(got, want) < 1e-5


def test_the_ungated_walk_goes_past_the_first_chunk(ungated_walk):
    stats = ungated_walk["stats"]
    rows = moe.compact_rows(640 * 6, 8, 128)
    assert rows == 512
    assert float(stats["pairs_here_share"]) * 640 * 6 > rows
    assert float(stats["compact_share"]) == 0.0
    got, want = ungated_walk["y"]
    assert rel(got, want) < 1e-5


@pytest.mark.parametrize("operand", ["x", "up_proj", "down_proj", "weights"])
def test_the_ungated_walks_gradient_is_the_dense_loops(ungated_walk,
                                                       operand):
    at = ["x", "up_proj", "down_proj", "weights"].index(operand)
    got, want = ungated_walk["grads"]
    assert rel(got[at], want[at]) < 1e-5


def test_a_decode_step_runs_every_expert_without_a_gate():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(4, 32)), jnp.float32)
    up = jnp.asarray(rng.normal(size=(8, 32, 16)) / 6, jnp.float32)
    down = jnp.asarray(rng.normal(size=(8, 16, 32)) / 4, jnp.float32)
    chosen = jnp.asarray(
        [rng.permutation(16)[:6] for _ in range(4)], jnp.int32)
    routing = moe.Routing(chosen, jnp.asarray(
        rng.uniform(0.1, 1.0, size=(4, 6)), jnp.float32))
    every, _ = moe.held_experts(x, routing, None, up, down, 0, 128,
                                jnp.float32, every_expert=True, act="relu2")
    grouped, _ = moe.held_experts(x, routing, None, up, down, 0, 128,
                                  jnp.float32, act="relu2")
    assert rel(every, grouped) < 1e-5


# -- (e) what the configuration refuses; the state ----------------------------

@pytest.mark.parametrize("change,said", [
    ({"hybrid_override_pattern": "ME-M*"}, "'-'"),
    ({"mlp_hidden_act": "silu"}, "mlp_hidden_act"),
    ({"mlp_hidden_act": "gelu"}, "mlp_hidden_act"),
    ({"hybrid_override_pattern": "MEM*"}, "num_hidden_layers"),
    ({"n_group": 2}, "n_group"),
    ({"model_type": "nemotron_g"}, "nemotron_h"),
])
def test_the_configuration_refuses_what_is_not_built(change, said):
    with pytest.raises(ValueError, match=said):
        TokenModelConfig.from_dict(dict(TINY, **change))


def test_the_layers_come_from_the_files_pattern():
    assert MODEL.layer_types == (
        token_policy.MAMBA2, token_policy.EXPERTS, token_policy.MAMBA2,
        token_policy.FULL, token_policy.EXPERTS)
    assert [MODEL.is_expert_layer(layer) for layer in range(5)] == [
        False, True, False, False, True]
    assert MODEL.mixer_alone and MODEL.rms_norm_eps == 1e-05
    assert MODEL.route_scale == 2.5 and MODEL.num_experts == 128
    assert MODEL.shared_expert_width == 32


def test_the_state_is_a_matrix_a_head_and_a_tail_over_x_b_c():
    agent = policy()
    state = jax.eval_shape(lambda: agent.initial_state(BATCH))
    assert [s.shape for s in state.ssm_state] == [(BATCH, 4, 8, 16)] * 2
    assert [t.shape for t in state.conv_tail] == [(BATCH, 3, 32 + 64)] * 2
    assert all(s.dtype == jnp.float32
               for s in state.ssm_state + state.conv_tail)
    assert [k.shape for k in state.keys] == [(BATCH, EPISODE + UNROLL, 2, 8)]
    assert agent.ssm_state_bytes(1) == 2 * 4 * (4 * 8 * 16 + 3 * 96)
    assert agent.ssm_state_bytes(BATCH) == BATCH * agent.ssm_state_bytes(1)
    # at the published widths: 2 MiB a layer of state, 72 KiB of tail
    cell = manifest.load_json(os.path.join(
        ROOT, "benchmark", "configs", "nemotron3_nano_ep16.json"))
    wide = TokenPolicy(model=TokenModelConfig.from_dict(cell),
                       unroll_length=256, episode_length=14336,
                       compute_dtype=jnp.bfloat16)
    assert wide.ssm_state_bytes(1) == 4 * (2097152 + 73728) == 8683520
    assert wide.cache_bytes(32) == 32 * 14592 * 1024

