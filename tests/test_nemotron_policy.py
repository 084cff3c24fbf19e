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

(a, b) ``TestPolicy``: the suite every family inherits
    (tests/family_suite.py ``PolicyConformance``) at this preset, the
    planted fault a zero state at every chunk's start; acting a token at
    a time against forwards a few tokens at a time through the kernels
    (resets inside a chunk and at a chunk's edge), and ``unroll_state``;
(d) the 16 shares of one expert layer, the shared expert counted once,
    are the uncut layer;
(e) ``TokenModelConfig.from_dict`` refuses a pattern letter and an
    activation it does not build; the state's shapes and bytes.
The scan's kernels alone are in tests/test_ssd.py, the experts without a
gate in tests/test_moe.py.
The driver, the world, the configuration file and the benchmark's
harness at this preset are in tests/test_nemotron_harness.py.
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
    "mean_context": 8, "loss": LOSS, "optimizer": OPTIMIZER,
}
PRESET = Preset(
    tiny=TINY, reference="nemotron_h_token",
    cell="nemotron3.ingraph", config_file="nemotron3_nano_ep16",
    traffic_file="fused_token_recall_u256_e14336",
    level="token_recall_14k", world=(16384, 14336, 8192),
    why_says=("384", "16x"),
    own_metrics=("ssd_decode_roofline.fused", "ssd_device_share.fused",
                 "ssd_scan_roofline.fused", "ssd_state_bytes_per_env"),
    groups=("embedding", "attention", "ssd", "experts", "mlp", "norms",
            "heads"),
    kernel_policy_says=("2 mamba2, 2 experts, 1 full_attention",
                        "experts_held=8/128"),
    lacking=("hybrid_override_pattern", "mamba_num_heads", "n_groups",
             "chunk_size", "layer_norm_epsilon", "experts_held"),
    published={
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
        "expand": 2, "head_dim": 128, "hidden_size": 2688,
        "hybrid_override_pattern":
            "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
        "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
        "mamba_head_dim": 64, "mamba_hidden_act": "silu",
        "mamba_num_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "mlp_bias": False,
        "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
        "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
        "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
        "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 6,
        "num_hidden_layers": 52, "num_key_value_heads": 2,
        "num_logits_to_keep": 1, "partial_rotary_factor": 1,
        "rescale_prenorm_residual": True, "residual_in_fp32": False,
        "rope_theta": 10000, "routed_scaling_factor": 2.5,
        "sliding_window": None, "ssm_state_size": 128,
        "tie_word_embeddings": False, "time_step_floor": 0.0001,
        "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
        "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True,
        "vocab_size": 131072},
    reduced_numbers=("num_hidden_layers", "hybrid_override_pattern",
                     "vocab_size"),
    prints=("ssd_state_bytes_per_env",),
    does_not_print=("latent_cache_bytes_per_token",),
    # an episode's end at token 3, inside the first chunk of 4, and at
    # token 4, the second's first
    ends_inside=((3, 1), (4, 2)),
    jitted=True, leaf_floor=1e-3, every_leaf_has_a_gradient=False,
    # The loss against the float32 reference's.  bfloat16 reads 8e-3 here
    # and fp8 0.17: the band lies between.
    bfloat16_band=0.03,
    # the update's scan starts every chunk of 4 from a zero state
    fault="zero_chunk_state", fault_moves=1e-3,
    # staggered by 4 + env, so that ends fall on a chunk's first token,
    # inside a chunk and on its last
    stagger=EPISODE // BATCH + 1)
MODEL = PRESET.model
ref = PRESET.ref
policy = PRESET.policy


class TestPolicy(PolicyConformance):
    """(a, b): the suite at this preset.  1e-5 in float32: the program's
    scan goes a chunk of 4 tokens at a time through matrix products, the
    reference's a token at a time.  Forty steps: the attention ring
    (16 + 6 slots) wraps once.  The scans' kernels see whole calls of a
    few tokens, not the suite's ragged chunks, and the update's start is
    held a token at a time: two tests of this family's own take the
    suite's places."""

    preset = PRESET
    test_stepwise_logits_are_the_chunked_forwards = None
    test_the_update_unrolls_from_the_rollouts_own_rings = None

    def test_the_references_planted_fault_moves_its_loss(self, float32_pair):
        """``zero_chunk_state`` is the loss's alone: the reference's
        rollout under it is the sound one."""
        super().test_the_references_planted_fault_moves_its_loss(
            float32_pair)
        batch, params = float32_pair["batch"], float32_pair["params"]["params"]
        sound = ref.forward(TINY, params, batch.token, batch.done,
                            batch.history)
        same = ref.forward(TINY, params, batch.token, batch.done,
                           batch.history, "zero_chunk_state")
        assert rel(same[0], sound[0]) == 0.0

    @pytest.mark.parametrize("count", [4, 5, 7])
    def test_stepwise_logits_are_the_kernels_forwards(self, forty_steps,
                                                      count):
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
    def test_the_update_unrolls_from_the_starts_states(self, forty_steps,
                                                       what):
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


# -- (d) the share tied to the model ------------------------------------------

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

