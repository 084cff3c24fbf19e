"""The token policy of the ``olmo_hybrid`` family (models/token_policy.py:
three Gated-DeltaNet layers, a delta-rule matrix state a head
(ops/gated_delta.py), to one full-attention layer with as many key heads
as query heads and its query and key normed over the whole projection;
a gated MLP behind every mixer; each branch's result normed, its input
not) against its plain reference
(benchmark/references/olmo_hybrid_token.py: the delta rule a ``lax.scan``
over tokens), at a tiny preset: hidden 64, 2 delta-rule heads with keys
of 8 and values of 16, chunks of 4 tokens, 4 query heads on 4 key heads
of 16, an MLP of 48, vocabulary 64, unroll 6, episodes of 16, seeded
weights, one period of the model's four layers.

(a, b) ``TestPolicy``: the suite every family inherits
    (tests/family_suite.py ``PolicyConformance``) at this preset, the
    planted fault the correction dropped; acting a token at a time
    against forwards a few tokens at a time through the kernels (resets
    inside a chunk and at a chunk's edge), and ``unroll_state``;
(d) the mechanisms only this family has: the whole-projection norm of
    the query and the key, a branch's input left as it is, a write
    strength in (0, 1) where the file says so;
(e) ``TokenModelConfig.from_dict`` refuses a layer kind and a rotation
    it does not build; the state's shapes and bytes.
The scan's kernels alone are in tests/test_gated_delta.py, with the ring
under one query head a key head.  The driver, the world, the
configuration file and the benchmark's harness at this preset are in
tests/test_olmo_hybrid_harness.py.
"""

import dataclasses
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
    TokenPolicy,
)

UNROLL, EPISODE, BATCH, VOCAB = 6, 16, 4, 64
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
TINY = {
    "model_type": "olmo_hybrid", "hidden_act": "silu",
    "attention_bias": False, "tie_word_embeddings": False,
    "rope_parameters": {"rope_theta": None},
    "vocab_size": VOCAB, "hidden_size": 64, "intermediate_size": 48,
    "num_hidden_layers": 4, "layer_types": PERIOD,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "linear_num_key_heads": 2, "linear_num_value_heads": 2,
    "linear_key_head_dim": 8, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rms_norm_eps": 1e-06, "max_position_embeddings": 65536,
    "chunk_size": 4,
    "reference": "olmo_hybrid_token", "reference_block": 2,
    "mean_context": 8, "loss": LOSS, "optimizer": OPTIMIZER,
}
PRESET = Preset(
    tiny=TINY, reference="olmo_hybrid_token",
    cell="olmohybrid.ingraph", config_file="olmo_hybrid_7b_vp8",
    traffic_file="fused_token_recall_u256_e7936",
    level="token_recall_8k", world=(12544, 7936, 4096),
    why_says=("7,936", "delta-rule", "2.1 MiB"),
    own_metrics=("gdn_decode_roofline.fused", "gdn_device_share.fused",
                 "gdn_scan_roofline.fused", "gdn_state_bytes_per_env"),
    groups=("embedding", "attention", "gdn", "mlp", "norms", "heads"),
    kernel_policy_says=("3 linear_attention, 1 full_attention",
                        "experts_held=0/0"),
    lacking=("layer_types", "linear_num_key_heads", "linear_key_head_dim",
             "linear_value_head_dim", "linear_conv_kernel_dim",
             "linear_allow_neg_eigval", "rms_norm_eps", "chunk_size"),
    published={
        "model_type": "olmo_hybrid", "vocab_size": 100352,
        "hidden_size": 3840, "intermediate_size": 11008,
        "num_hidden_layers": 32, "num_attention_heads": 30,
        "num_key_value_heads": 30, "hidden_act": "silu",
        "max_position_embeddings": 65536, "attention_bias": False,
        "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
        "layer_types": PERIOD * 8,
        "linear_num_key_heads": 30, "linear_num_value_heads": 30,
        "linear_key_head_dim": 96, "linear_value_head_dim": 192,
        "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
        "rope_parameters": {"rope_theta": None}},
    reduced_numbers=("num_hidden_layers", "layer_types", "vocab_size"),
    prints=("gdn_state_bytes_per_env",),
    does_not_print=("latent_cache_bytes_per_token",
                    "ssd_state_bytes_per_env"),
    # an episode's end at token 3, inside the first chunk of 4, and at
    # token 4, the second's first
    ends_inside=((3, 1), (4, 2)),
    jitted=True, leaf_floor=1e-3,
    # Three delta-rule layers one on another amplify a float32 rounding
    # (queries and keys brought to unit length, every branch's result
    # normed): the REFERENCE's own float32 forward lies 1.0e-5 (this
    # preset) to 2.0e-5 (four delta-rule layers) from the same forward in
    # float64, 1.5e-6 with one such layer and 1.2e-6 with four attention
    # layers (my CPU run, PR 46), and the program's from the reference's
    # 1.2e-5 to 4e-5.
    float32_gap=6e-5,
    # and three steps on the harness's numbers read up to 7.7e-4 (the
    # parameters' change, worst leaf) where the reference against ITSELF
    # at another block size reads 1.0e-4 on the later losses (my CPU
    # runs, PR 46); a fault reads 0.05 or more
    rehearsal_gap=3e-3,
    # The loss against the float32 reference's.  bfloat16 reads 3e-3 here
    # and fp8 0.1: the band lies between.
    bfloat16_band=0.02,
    # the update's scans never read the state against the key
    fault="no_delta", fault_moves=1e-3,
    # staggered by 4 + env, so that ends fall on a chunk's first token,
    # inside a chunk and on its last
    stagger=EPISODE // BATCH + 1)
MODEL = PRESET.model
ref = PRESET.ref
policy = PRESET.policy


class TestPolicy(PolicyConformance):
    """(a, b): the suite at this preset.  6e-5 in float32 (the preset
    has why): the program's
    scan goes a chunk of 4 tokens at a time through a triangular solve
    and matrix products, the reference's a token at a time.  Forty
    steps: the attention ring (16 + 6 slots) wraps once.  The scans'
    kernels see whole calls of a few tokens, not the suite's ragged
    chunks, and the update's start is held a token at a time: two tests
    of this family's own take the suite's places."""

    preset = PRESET
    test_stepwise_logits_are_the_chunked_forwards = None
    test_the_update_unrolls_from_the_rollouts_own_rings = None

    def test_the_references_planted_fault_moves_its_loss(self, float32_pair):
        """``no_delta`` is the loss's alone: the reference's rollout
        under it is the sound one."""
        super().test_the_references_planted_fault_moves_its_loss(
            float32_pair)
        batch, params = float32_pair["batch"], float32_pair["params"]["params"]
        sound = ref.forward(TINY, params, batch.token, batch.done,
                            batch.history)
        same = ref.forward(TINY, params, batch.token, batch.done,
                           batch.history, "no_delta")
        assert rel(same[0], sound[0]) == 0.0

    @pytest.mark.parametrize("count", [4, 5, 7])
    def test_stepwise_logits_are_the_kernels_forwards(self, forty_steps,
                                                      count):
        """``count`` tokens a call go through the kernels (5: a chunk of
        4 and one token as a step; 7: two chunks, one of padding), whose
        chunks then start at other tokens than the episodes do: resets
        fall inside a chunk, on its first token and on its last; the
        state a call hands the next is the recurrence's."""
        agent, params, tokens, done, stepwise, _, last, step = forty_steps
        state, rows = agent.initial_state(BATCH), []
        for t in range(0, tokens.shape[0] - count + 1, count):
            (logits, _), state = step(
                params, env_outputs(tokens[t:t + count], done[t:t + count]),
                state)
            rows.append(logits)
        got = jnp.concatenate(rows)
        assert rel(got, stepwise[:got.shape[0]]) < PRESET.float32_gap
        if got.shape[0] == stepwise.shape[0]:
            for mine, theirs in zip(state.ssm_state + state.conv_tail,
                                    last.ssm_state + last.conv_tail):
                assert rel(mine, theirs) < PRESET.float32_gap

    @pytest.mark.parametrize("what", ["logits", "state"])
    def test_the_update_unrolls_from_the_starts_states(self, forty_steps,
                                                       what):
        """``unroll_state``: the ring as the rollout left it, the scans'
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
        assert rel(logits, stepwise[UNROLL:2 * UNROLL]) < PRESET.float32_gap


# -- (d) what only this family has --------------------------------------------

def one_forward(cfg, seed=7):
    """(the program's logits, the reference's) of one unroll under
    ``cfg``, float32."""
    preset = dataclasses.replace(PRESET, tiny=cfg)
    params = preset.weights(seed)
    tokens, done, _ = preset.unroll_stream(seed)
    agent = preset.policy()
    (logits, _), _ = jax.jit(agent.apply)(
        params, jnp.zeros(tokens.shape, jnp.int32),
        env_outputs(tokens, done), agent.initial_state(BATCH))
    want, _, _ = jax.jit(lambda prm: preset.ref.forward(
        cfg, prm, tokens, done, preset.ref.empty_history(cfg, BATCH)))(
            params["params"])
    return logits, want


@pytest.mark.parametrize("change", [
    {"linear_allow_neg_eigval": False},
    {"num_key_value_heads": 2},
    {"layer_types": ["full_attention", "linear_attention"],
     "num_hidden_layers": 2},
], ids=["write-strength-under-one", "two-query-heads-a-key-head",
        "attention-first"])
def test_a_file_that_says_otherwise_is_the_references_too(change):
    """What the record reads as a field and does not assume: a write
    strength in (0, 1), grouped queries, another order of the layers."""
    got, want = one_forward(dict(TINY, **change))
    assert rel(got, want) < PRESET.float32_gap


def test_the_write_strength_is_read_from_the_file():
    under_two, _ = one_forward(TINY)
    under_one, _ = one_forward(dict(TINY, linear_allow_neg_eigval=False))
    assert rel(under_one, under_two) > 1e-3


def test_the_query_and_the_key_are_normed_over_the_whole_projection():
    """One mean square over every head's numbers, a weight a number: the
    tree has ``q_norm`` and ``k_norm`` of the projections' whole widths,
    and scaling one HEAD's share of the query projection changes the
    other heads' queries (a norm a head would not)."""
    shapes = ref.weight_shapes(TINY)
    at = ("layer_3", "attention")
    assert shapes[at + ("q_norm", "scale")] == (4 * 16,)
    assert shapes[at + ("k_norm", "scale")] == (4 * 16,)
    module = token_policy._PlainAttention(MODEL, jnp.float32,
                                          whole_norms=True)
    a = jnp.asarray(np.random.default_rng(0).normal(size=(1, 1, 64)),
                    jnp.float32)
    ring = jnp.zeros((1, 8, 4, 16), jnp.float32)
    args = (a, jnp.zeros((1,), jnp.int32), jnp.zeros((1, 1), jnp.int32),
            ring, ring, jnp.full((8,), -(2 ** 30), jnp.int32),
            jnp.zeros((), jnp.int32))
    params = module.init(jax.random.key(0), *args)

    def keys_written(params):
        return module.apply(params, *args)[1][0, 0]          # [kv, D]

    scaled = jax.tree_util.tree_map(lambda x: x, params)
    kernel = params["params"]["k_proj"]["kernel"]
    scaled["params"]["k_proj"]["kernel"] = kernel.at[:, :16].multiply(8.0)
    before, after = keys_written(params), keys_written(scaled)
    assert rel(after[1:], before[1:]) > 0.1
    # and the whole row has mean square 1 either way
    for row in (before, after):
        assert float(jnp.mean(jnp.square(row))) == pytest.approx(1.0,
                                                                 rel=1e-3)


def test_a_branchs_input_is_not_normed_and_its_result_is():
    names = {path[1] for path in ref.weight_shapes(TINY)
             if path[0] == "layer_0" and path[-1] == "scale"}
    assert names == {"post_attn_norm", "post_mlp_norm"}
    family = token_policy._FAMILY["olmo_hybrid"]
    assert family.result_norms and not family.input_norms
    # every earlier family norms a branch's input
    assert all(record.input_norms
               for name, record in token_policy._FAMILY.items()
               if name != "olmo_hybrid")


# -- (e) what the configuration refuses; the state ----------------------------

@pytest.mark.parametrize("change,said", [
    ({"layer_types": PERIOD[:3] + ["sliding_attention"]}, "layer_types"),
    ({"layer_types": PERIOD[:3]}, "num_hidden_layers"),
    ({"rope_parameters": {"rope_theta": 500000.0}}, "rope_parameters"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"attention_bias": True}, "attention_bias"),
    ({"linear_num_value_heads": 4}, "a key head a value head"),
    ({"num_key_value_heads": 3}, "num_key_value_heads"),
    ({"model_type": "olmo_hybrid2"}, "olmo_hybrid"),
])
def test_the_configuration_refuses_what_is_not_built(change, said):
    with pytest.raises(ValueError, match=said):
        TokenModelConfig.from_dict(dict(TINY, **change))


def test_the_layers_come_from_the_files_layer_types():
    assert MODEL.layer_types == (token_policy.LINEAR,) * 3 + (
        token_policy.FULL,)
    assert not MODEL.mixer_alone and MODEL.rms_norm_eps == 1e-06
    assert (MODEL.head_dim, MODEL.conv_kernel, MODEL.chunk_size) == (
        16, 4, 4)
    assert MODEL.linear_widths == (16, 32)
    assert not any(MODEL.is_expert_layer(layer) for layer in range(4))


def test_the_state_is_a_matrix_a_head_and_a_tail_over_q_k_v():
    agent = policy()
    state = jax.eval_shape(lambda: agent.initial_state(BATCH))
    assert [s.shape for s in state.ssm_state] == [(BATCH, 2, 16, 8)] * 3
    assert [t.shape for t in state.conv_tail] == [(BATCH, 3, 16 + 16 + 32)] * 3
    assert all(s.dtype == jnp.float32
               for s in state.ssm_state + state.conv_tail)
    assert [k.shape for k in state.keys] == [(BATCH, EPISODE + UNROLL, 4, 16)]
    assert agent.ssm_state_bytes(1) == 3 * 4 * (2 * 16 * 8 + 3 * 64)
    assert agent.ssm_state_bytes(BATCH) == BATCH * agent.ssm_state_bytes(1)
    # at the published widths: 2.1 MiB a layer of state, 135 KiB of tail,
    # and a ring slot of 15,360 bytes
    cell = manifest.load_json(os.path.join(
        ROOT, "benchmark", "configs", "olmo_hybrid_7b_vp8.json"))
    wide = TokenPolicy(model=TokenModelConfig.from_dict(cell),
                       unroll_length=256, episode_length=7936,
                       compute_dtype=jnp.bfloat16)
    assert wide.ssm_state_bytes(1) == 3 * (2211840 + 138240) == 7050240
    assert wide.cache_bytes(8) == 8 * 8192 * 15360
    gauges = {name: value for name, value, _ in wide.gauges(8)}
    assert gauges["gdn/state_bytes_per_env"] == 7050240
    assert gauges["ssd/state_bytes_per_env"] == 0
    assert gauges["ssm/state_bytes"] == 8 * 7050240


@pytest.mark.parametrize("family", ["afmoe", "phi4flash", "deepseek_v3",
                                    "nemotron_h"])
def test_an_earlier_familys_state_is_its_records_own_statement(family):
    """``initial_state`` asks the record for a scan state's shape and no
    field of the model: the two families with scans state theirs, the
    two without state none, and no family's gauge of a delta-rule state
    reads anything."""
    record = token_policy._FAMILY[family]
    has_scans = any(kind in token_policy._SCANS for kind in record.mixers)
    assert (record.scan_state is not None) == has_scans
