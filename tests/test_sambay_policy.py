"""The token policy of the ``phi4flash`` family (models/token_policy.py:
state-space layers, differential attention over its own ring and over
another layer's, memory units, a tied head) against its plain reference
(benchmark/references/sambay_token.py), at a tiny preset: hidden 64, 4
heads / 2 kv heads of 16 (2 query pairs over 1 key pair), d_inner 128,
state 8, conv 4, window 8, vocabulary 64, unroll 6, episodes of 16,
seeded weights, one layer of each kind in the model's order.

(a) one T = unroll forward, the loss and its gradients against the
    reference in float32 (1e-5), and in bfloat16 inside a band an fp8
    cast falls out of;
(b) acting step by step through the state gives the logits of a whole
    forward, across episode ends and the rings' wrap;
(c) ``unroll_state``: the rings of the unroll's end, the recurrent state
    and the convolution's tail of its start;
(d) differential attention, one query an env and many, against the
    reference's, through an own ring and through a ring two layers read
    (the owner's keys get each reader's cotangent);
(e) the share tied to the model: the logits of the eight vocabulary
    slices, side by side, are the uncut reference's head.

The driver, the world, the configuration file and the benchmark's
harness at this preset are in tests/test_sambay_harness.py.
"""

import math
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
from scalable_agent_tpu.models import token_policy  # noqa: E402
from scalable_agent_tpu.models.token_policy import (  # noqa: E402
    TokenModelConfig,
    TokenPolicy,
)
from scalable_agent_tpu.ops import attention as attention_lib  # noqa: E402
from scalable_agent_tpu.parallel import MeshSpec, make_mesh  # noqa: E402
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
    os.path.join(ROOT, "benchmark", "references", "sambay_token.py"),
    "reference_sambay_token_tests")

UNROLL, EPISODE, BATCH, VOCAB = 6, 16, 4, 64
TINY = {
    "model_type": "phi4flash", "hidden_act": "silu", "vocab_size": VOCAB,
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 96, "num_hidden_layers": 6, "sliding_window": 8,
    "layer_norm_eps": 1e-05, "mb_per_layer": 2,
    "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False,
    "mamba_d_state": 8, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_dt_rank": 4,
    "layer_kinds": [
        {"kind": "state_space", "published_index": 0},
        {"kind": "sliding_attention", "published_index": 1},
        {"kind": "state_space", "published_index": 16},
        {"kind": "full_attention", "published_index": 17},
        {"kind": "memory_unit", "published_index": 18},
        {"kind": "cross_attention", "published_index": 19}],
    "reference": "sambay_token", "reference_block": 2,
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


def env_outputs(tokens, done, reward=None):
    zeros = jnp.zeros(tokens.shape, jnp.float32)
    return StepOutput(
        reward=zeros if reward is None else reward,
        info=StepOutputInfo(zeros, jnp.zeros(tokens.shape, jnp.int32)),
        done=done, observation=Observation(frame=tokens))


def learner_of(agent):
    mesh = make_mesh(MeshSpec(data=1), devices=jax.devices()[:1])
    return Learner(agent, LearnerHyperparams(), mesh,
                   frames_per_update=BATCH * UNROLL)


def trajectory(agent, params, seed=3):
    """One unroll as the fused rollout lays it out, made by hand, with
    an episode's end inside it for two of the four envs; behaviour
    log-probabilities from the policy's own logits moved a little off."""
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(0, VOCAB, (UNROLL + 1, BATCH)),
                         jnp.int32)
    done = np.zeros((UNROLL + 1, BATCH), bool)
    done[0] = True
    done[3, 1] = done[5, 2] = True
    done = jnp.asarray(done)
    actions = jnp.asarray(rng.integers(0, VOCAB, (UNROLL + 1, BATCH)),
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
                grads=(ref.from_tree(grads["params"]),
                       ref.from_tree(ref_grads)))


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
    assert float(np.max(np.abs(want[path]))) > 0.0, leaf


def test_the_program_has_the_references_leaves_and_no_other():
    agent = policy()
    traj, _ = trajectory(agent, weights())
    made = jax.eval_shape(
        agent.init, jax.random.key(0), traj.agent_outputs.action,
        traj.env_outputs, traj.agent_state)["params"]
    shapes = {path: leaf.shape for path, leaf in ref.from_tree(made).items()}
    assert shapes == {path: tuple(shape) for path, shape
                      in ref.weight_shapes(TINY).items()}


# The loss against the float32 reference's.  bfloat16 reads 2e-3 here and
# fp8 0.2: the band lies a decade from each.
BFLOAT16_BAND = 0.02


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


def test_the_references_planted_fault_moves_its_loss():
    """``quant="no_reset"`` (the limits file's own fault): the scan's
    state is carried over an episode's end, and the loss moves by far
    more than float32's rounding."""
    agent, params = policy(), weights()
    _, batch = trajectory(agent, params)
    want = float(ref.loss(TINY, params["params"], batch))
    planted = float(ref.loss(TINY, params["params"], batch,
                             quant=ref.NO_RESET))
    assert abs(planted - want) / abs(want) > 1e-4


# -- (b) acting through the state is the whole forward ------------------------

@pytest.fixture(scope="module")
def forty_steps():
    """40 steps of 4 envs in episodes of 16, staggered: every env
    crosses two episode ends, the window ring (8 + 6 slots) wraps twice
    and the full ring (16 + 6) once."""
    steps = 40
    rng = np.random.default_rng(11)
    tokens = jnp.asarray(rng.integers(0, VOCAB, (steps, BATCH)), jnp.int32)
    offset = np.arange(BATCH) * (EPISODE // BATCH)
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
            jnp.stack(values), state)


@pytest.mark.parametrize("what", ["logits", "baseline"])
def test_stepwise_outputs_are_the_references_whole_forward(
        forty_steps, what):
    _, params, tokens, done, logits, values, _ = forty_steps
    whole, baseline, _ = ref.forward(TINY, params["params"], tokens, done,
                                     ref.empty_history(TINY, BATCH))
    got, want = ((logits, whole) if what == "logits"
                 else (values, baseline))
    assert rel(got, want) < 1e-5


@pytest.mark.parametrize("chunk", [2, 5, 7])
def test_stepwise_logits_are_the_chunked_forwards(forty_steps, chunk):
    agent, params, tokens, done, stepwise, _, last = forty_steps
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


# -- (c) what the update unrolls from -----------------------------------------

def test_the_state_holds_rings_for_the_layers_that_make_keys():
    state = policy().initial_state(BATCH)
    # a window layer and the full layer; the cross layer owns none
    assert [k.shape for k in state.keys] == [
        (BATCH, 8 + UNROLL, 1, 32), (BATCH, EPISODE + UNROLL, 1, 32)]
    assert [s.shape for s in state.ssm_state] == [(BATCH, 8, 128)] * 2
    assert [s.shape for s in state.conv_tail] == [(BATCH, 3, 128)] * 2
    assert all(s.dtype == jnp.float32 for s in state.ssm_state)
    assert policy().ring_readers == 2


@pytest.mark.parametrize("what", ["forward", "rings", "recurrent"])
def test_the_update_unrolls_from_the_ends_rings_and_the_starts_state(
        forty_steps, what):
    agent, params, tokens, done, *_ = forty_steps
    state = agent.initial_state(BATCH)
    zeros = jnp.zeros((UNROLL, BATCH), jnp.int32)
    for t in range(0, 30, UNROLL):
        start = state
        (_, _), state = agent.apply(
            params, zeros, env_outputs(tokens[t:t + UNROLL],
                                       done[t:t + UNROLL]), state)
    handed = agent.unroll_state(start, state)
    if what == "rings":
        for got, want in zip(handed.keys + handed.values,
                             state.keys + state.values):
            assert got is want
        assert handed.written is start.written
    elif what == "recurrent":
        for got, want in zip(handed.ssm_state + handed.conv_tail,
                             start.ssm_state + start.conv_tail):
            assert got is want
        assert float(jnp.max(jnp.abs(
            state.ssm_state[0] - start.ssm_state[0]))) > 0.0
    else:
        t = 30 - UNROLL
        again = env_outputs(tokens[t:t + UNROLL + 1],
                            done[t:t + UNROLL + 1])
        actions = jnp.zeros((UNROLL + 1, BATCH), jnp.int32)
        (want, _), _ = agent.apply(params, actions, again, start)
        (got, _), _ = agent.apply(params, actions, again, handed)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -- (d) differential attention -----------------------------------------------

SLOTS, PAIRS, KV, DIM = 16, 4, 2, 8       # a pair's width is 2 * DIM


def attention_case(queries, seed=0):
    """A ring of 16 slots holding stream indices 3..14 (two slots empty,
    in ring order), then ``queries`` own tokens from index 15; env 1's
    episode began at index 9, env 0's at 0 unless a reset falls inside."""
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    ring_index = np.full((SLOTS,), attention_lib.NO_KEY, np.int32)
    for index in range(3, 15):
        ring_index[index % SLOTS] = index
    index = 15 + np.arange(queries, dtype=np.int32)
    start = np.zeros((2, queries), np.int32)
    start[1] = 9
    if queries > 3:
        start[0, 3:] = 18                  # an episode begins mid-call
    return dict(
        query=normal(2, queries, PAIRS, 2 * DIM),
        key=normal(2, queries, KV, 2 * DIM),
        value=normal(2, queries, KV, 2 * DIM),
        ring_keys=normal(2, SLOTS, KV, 2 * DIM),
        ring_values=normal(2, SLOTS, KV, 2 * DIM),
        ring_index=jnp.asarray(ring_index), index=jnp.asarray(index),
        episode_start=jnp.asarray(start))


def two_softmaxes(case, window, lam):
    """(A1 - lambda A2) v, by the reference's equations in numpy-plain
    jnp: history then own keys, one masked softmax a stream."""
    query, keys = case["query"], jnp.concatenate(
        [case["ring_keys"], case["key"]], axis=1)
    values = jnp.concatenate([case["ring_values"], case["value"]], axis=1)
    key_index = jnp.concatenate([case["ring_index"], case["index"]])
    index, start = case["index"], case["episode_start"]
    seen = ((key_index[None, None, :] <= index[None, :, None])
            & (key_index[None, None, :] >= start[:, :, None]))
    if window is not None:
        seen &= index[None, :, None] - key_index[None, None, :] < window
    b, t = query.shape[:2]
    q = query.reshape(b, t, KV, PAIRS // KV, 2, DIM)
    k = keys.reshape(b, -1, KV, 2, DIM)
    scores = jnp.einsum("btkgzd,bskzd->bkgzts", q, k,
                        precision="highest") / math.sqrt(DIM)
    weights = jax.nn.softmax(
        jnp.where(seen[:, None, None, None], scores, -jnp.inf), -1)
    out = jnp.einsum("bkgts,bskd->btkgd",
                     weights[:, :, :, 0] - lam * weights[:, :, :, 1],
                     values, precision="highest")
    return out.reshape(b, t, PAIRS, 2 * DIM)


def through_the_cache(case, window, lam, **replaced):
    case = dict(case, **replaced)
    out, _ = attention_lib.cached_attention(
        case["query"], case["key"], case["value"], case["ring_keys"],
        case["ring_values"], case["ring_index"], case["index"],
        case["episode_start"], window=window, streams=2)
    out = out.reshape(out.shape[:2] + (PAIRS, 2, 2 * DIM))
    return out[..., 0, :] - lam * out[..., 1, :]


@pytest.mark.parametrize("window", [None, 5])
@pytest.mark.parametrize("queries", [1, 7])
def test_differential_attention_is_the_two_softmaxes(queries, window):
    """One query an env goes through the decode kernel, seven through
    ``_blockwise``."""
    case = attention_case(queries)
    got = through_the_cache(case, window, 0.37)
    assert rel(got, two_softmaxes(case, window, 0.37)) < 1e-5


@pytest.mark.parametrize("operand", ["query", "key", "value"])
@pytest.mark.parametrize("window", [None, 5])
def test_differential_attentions_gradient_is_the_two_softmaxes(
        operand, window):
    case = attention_case(7, seed=1)
    weigh = jnp.cos(jnp.arange(2 * DIM, dtype=jnp.float32))

    def total(fn):
        return lambda x: jnp.sum(fn(dict(case, **{operand: x})) * weigh)

    got = jax.grad(total(lambda c: through_the_cache(c, window, 0.37)))(
        case[operand])
    want = jax.grad(total(lambda c: two_softmaxes(c, window, 0.37)))(
        case[operand])
    assert rel(got, want) < 1e-5


@pytest.mark.parametrize("queries", [1, 7])
def test_a_ring_two_layers_read_gives_each_its_own_result(queries):
    """A cross layer brings queries of its own to the owner's keys,
    values and ring: what it reads back is what an owner with those
    queries would."""
    case = attention_case(queries, seed=2)
    other = attention_case(queries, seed=3)["query"]
    for query in (case["query"], other):
        got = through_the_cache(case, None, 0.2, query=query)
        want = two_softmaxes(dict(case, query=query), None, 0.2)
        assert rel(got, want) < 1e-5


@pytest.mark.parametrize("operand", ["key", "value"])
def test_the_owners_keys_get_the_sum_of_the_readers_cotangents(operand):
    case = attention_case(7, seed=4)
    other = attention_case(7, seed=5)["query"]
    weigh = jnp.sin(jnp.arange(2 * DIM, dtype=jnp.float32))

    def reader(query, lam):
        return lambda x: jnp.sum(through_the_cache(
            dict(case, **{operand: x}), None, lam, query=query) * weigh)

    own, cross = reader(case["query"], 0.2), reader(other, 0.55)
    both = jax.grad(lambda x: own(x) + cross(x))(case[operand])
    apart = jax.grad(own)(case[operand]) + jax.grad(cross)(case[operand])
    assert float(jnp.max(jnp.abs(jax.grad(cross)(case[operand])))) > 0.0
    assert rel(both, apart) < 1e-6
    want = jax.grad(lambda x: sum(
        jnp.sum(two_softmaxes(dict(case, query=q, **{operand: x}), None, lam)
                * weigh)
        for q, lam in ((case["query"], 0.2), (other, 0.55))))(case[operand])
    assert rel(both, want) < 1e-5


def test_one_stream_is_attention_as_it_was():
    """``streams=1`` is the call every other policy makes."""
    case = attention_case(7, seed=6)
    args = [case[name] for name in (
        "query", "key", "value", "ring_keys", "ring_values", "ring_index",
        "index", "episode_start")]
    got, _ = attention_lib.cached_attention(*args, window=5)
    want, _ = attention_lib.cached_attention(*args, window=5, streams=1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -- (e) the share tied to the model ------------------------------------------

def test_the_eight_vocabulary_slices_are_the_uncut_head():
    """The deployment shares the tied matrix by its rows, eight chips.
    A chip's policy is a model of an eighth of the vocabulary whose
    table is its rows; the world draws its tokens from the slice, so
    chip 0's whole policy runs here, and gives the uncut reference's
    final hidden state and its slice of the logits.  What every chip
    computes alike (the layers) is counted once: the other chips' slices
    are the policy's own head (``tied_logits``) over their rows, and the
    eight side by side are the uncut reference's head."""
    shares, rows = 8, VOCAB // 8
    whole = weights(13)["params"]
    table = whole["embed"]["embedding"]
    tokens = jnp.asarray(np.random.default_rng(2).integers(
        0, rows, (UNROLL, BATCH)), jnp.int32)
    done = jnp.zeros((UNROLL, BATCH), bool).at[0].set(True)
    want, _, _ = ref.forward(TINY, whole, tokens, done,
                             ref.empty_history(TINY, BATCH))
    assert want.shape[-1] == VOCAB
    agent = policy(model=TokenModelConfig.from_dict(
        dict(TINY, vocab_size=rows)))
    ((logits, _), _), seen = agent.apply(
        {"params": dict(whole, embed={"embedding": table[:rows]})},
        jnp.zeros(tokens.shape, jnp.int32), env_outputs(tokens, done),
        agent.initial_state(BATCH), mutable=["intermediates"],
        capture_intermediates=lambda module, _: module.name == "final_norm")
    z = jnp.swapaxes(
        seen["intermediates"]["final_norm"]["__call__"][0], 0, 1)
    slices = [token_policy.tied_logits(
        z, table[share * rows:(share + 1) * rows], jnp.float32)
        for share in range(shares)]
    np.testing.assert_array_equal(np.asarray(slices[0]), np.asarray(logits))
    assert rel(jnp.concatenate(slices, axis=-1), want) < 1e-5
