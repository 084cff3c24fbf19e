"""The token policy of the ``phi4flash`` family (models/token_policy.py:
state-space layers, differential attention over its own ring and over
another layer's, memory units, a tied head) against its plain reference
(benchmark/references/sambay_token.py), at a tiny preset: hidden 64, 4
heads / 2 kv heads of 16 (2 query pairs over 1 key pair), d_inner 128,
state 8, conv 4, window 8, vocabulary 64, unroll 6, episodes of 16,
seeded weights, one layer of each kind in the model's order.

(a-c) ``TestPolicy``: the suite every family inherits
    (tests/family_suite.py ``PolicyConformance``) at this preset, the
    planted fault the scan's state carried over an episode's end;
    ``unroll_state`` hands on the recurrent state and the convolution's
    tail of the unroll's start; the state's shapes;
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
for path in (ROOT, os.path.join(ROOT, "tests")):
    if path not in sys.path:
        sys.path.insert(0, path)

from family_suite import (  # noqa: E402
    LOSS,
    OPTIMIZER,
    PolicyConformance,
    Preset,
    env_outputs,
    rel,
)
from scalable_agent_tpu.models import token_policy  # noqa: E402
from scalable_agent_tpu.models.token_policy import (  # noqa: E402
    TokenModelConfig,
)
from scalable_agent_tpu.ops import attention as attention_lib  # noqa: E402

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
    "loss": LOSS, "optimizer": OPTIMIZER,
}
PRESET = Preset(
    tiny=TINY, reference="sambay_token",
    cell="phi4flash.ingraph", config_file="phi4_mini_flash_vp8",
    traffic_file="fused_token_recall_u256_e6144",
    level="token_recall_long", world=(25008, 6144, 3584),
    why_says=("6,144", "2 of 6 layers"),
    own_metrics=(
        "diff_attention_device_share.fused",
        "diff_attention_update_roofline.fused", "gmu_device_share.fused",
        "ssm_device_share.fused", "ssm_scan_roofline.fused"),
    groups=("embedding", "attention", "ssm", "gmu", "mlp", "norms", "heads"),
    kernel_policy_says=("2 state_space", "1 cross_attention",
                        "ring_readers=2"),
    lacking=("layer_kinds", "mamba_d_state", "layer_norm_eps",
             "sliding_window"),
    published={
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40,
        "num_hidden_layers": 32, "num_key_value_heads": 20,
        "resid_pdrop": 0, "sliding_window": 512,
        "tie_word_embeddings": True, "mlp_bias": False,
        "lm_head_bias": False, "vocab_size": 200064},
    reduced_numbers=("num_hidden_layers", "vocab_size"),
    prints=(),
    # the expert layer's counter is another cell's
    does_not_print=("expert_load_max_over_mean",),
    # The loss against the float32 reference's.  bfloat16 reads 2e-3 here
    # and fp8 0.2: the band lies a decade from each.
    bfloat16_band=0.02,
    # the scan's state is carried over an episode's end
    fault="no_reset",
    unrolls_from=("forward", "rings", "recurrent"),
    # the cell's file states a head's width; this family's tiny one
    # leaves it to the hidden size's share
    not_in_tiny=("head_dim",))
MODEL = PRESET.model
ref = PRESET.ref
policy, weights = PRESET.policy, PRESET.weights


class TestPolicy(PolicyConformance):
    """(a-c): the suite at this preset.  Forty steps: the window ring
    (8 + 6 slots) wraps twice and the full ring (16 + 6) once."""

    preset = PRESET


# -- (c) the state ------------------------------------------------------------

def test_the_state_holds_rings_for_the_layers_that_make_keys():
    state = policy().initial_state(BATCH)
    # a window layer and the full layer; the cross layer owns none
    assert [k.shape for k in state.keys] == [
        (BATCH, 8 + UNROLL, 1, 32), (BATCH, EPISODE + UNROLL, 1, 32)]
    assert [s.shape for s in state.ssm_state] == [(BATCH, 8, 128)] * 2
    assert [s.shape for s in state.conv_tail] == [(BATCH, 3, 128)] * 2
    assert all(s.dtype == jnp.float32 for s in state.ssm_state)
    assert policy().ring_readers == 2


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
