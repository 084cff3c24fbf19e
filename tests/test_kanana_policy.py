"""The token policy of the ``deepseek_v3`` family (models/token_policy.py:
latent attention through a ring of one compressed row a token, the
up-projection absorbed; shared experts and routed ones behind a leading
dense layer) against its plain reference
(benchmark/references/deepseek_v3_token.py: whole keys and values
up-projected for every token), at a tiny preset: hidden 64, 4 heads of
8 + 4 (a row of 16 + 4), values of 8, 8 experts of which 2 are held, 2
a token, 2 shared, vocabulary 64, unroll 6, episodes of 16, seeded
weights, one dense layer and two expert layers.

(a, b) ``TestPolicy``: the suite every family inherits
    (tests/family_suite.py ``PolicyConformance``) at this preset, the
    planted fault the shared key left unrotated; acting through the slot
    kernel is the chunked forward;
(c) the state is a ring of rows a layer, and nothing as large as a
    past token's whole keys is in the lowered update or decode step;
(d) interleaved rotary pairs are the half-split ones on permuted
    weights;
(e) the share tied to the model: the four shares' routed parts and the
    shared experts once are the uncut layer (the test is
    tests/test_moe.py's, a case a family).

The driver, the world, the configuration file and the benchmark's
harness at this preset are in tests/test_kanana_harness.py.
"""

import os
import re
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
    TokenPolicy,
)

UNROLL, EPISODE, BATCH, VOCAB = 6, 16, 4, 64
TINY = {
    "model_type": "deepseek_v3", "hidden_act": "silu",
    "scoring_func": "sigmoid", "rope_scaling": None, "q_lora_rank": None,
    "tie_word_embeddings": False, "attention_bias": False,
    "vocab_size": VOCAB, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 4, "kv_lora_rank": 16,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "qk_head_dim": 12,
    "v_head_dim": 8, "intermediate_size": 96, "moe_intermediate_size": 32,
    "n_routed_experts": 8, "num_experts_per_tok": 2, "n_shared_experts": 2,
    "first_k_dense_replace": 1, "moe_layer_freq": 1, "n_group": 1,
    "topk_group": 1, "topk_method": "noaux_tc", "norm_topk_prob": True,
    "routed_scaling_factor": 2.448, "num_hidden_layers": 3,
    "rope_theta": 1000000, "rope_interleave": True, "rms_norm_eps": 1e-06,
    "experts_held": 2, "first_expert": 0,
    "reference": "deepseek_v3_token", "reference_block": 2,
    "mean_context": 8, "loss": LOSS, "optimizer": OPTIMIZER,
}
ROW = TINY["kv_lora_rank"] + TINY["qk_rope_head_dim"]
PRESET = Preset(
    tiny=TINY, reference="deepseek_v3_token",
    cell="kanana2.ingraph", config_file="kanana2_30b_ep8",
    traffic_file="fused_token_recall_u256_e10240",
    level="token_recall_10k", world=(16032, 10240, 6144),
    why_says=("384", "8x"),
    own_metrics=(
        "latent_attention_device_share.fused",
        "latent_cache_bytes_per_token", "latent_decode_roofline.fused",
        "latent_slot_kernel_share", "latent_update_roofline.fused"),
    groups=("embedding", "attention", "experts", "mlp", "norms", "heads"),
    kernel_policy_says=("3 latent_attention",
                        f"latent_bytes_per_token={4 * ROW}",
                        "experts_held=2/8"),
    lacking=("kv_lora_rank", "qk_rope_head_dim", "v_head_dim",
             "n_routed_experts", "first_k_dense_replace",
             "routed_scaling_factor", "rope_interleave", "experts_held"),
    published={
        "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "kv_lora_rank": 512,
        "max_position_embeddings": 32768, "model_type": "deepseek_v3",
        "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 128, "n_shared_experts": 2,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 6, "num_hidden_layers": 48,
        "num_key_value_heads": 32, "q_lora_rank": None, "qk_head_dim": 192,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_interleave": True,
        "rope_scaling": None, "rope_theta": 1000000,
        "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 128256},
    reduced_numbers=("num_hidden_layers", "vocab_size"),
    prints=("latent_cache_bytes_per_token",),
    does_not_print=("expert_load_max_over_mean",),
    # The loss against the float32 reference's.  bfloat16 reads 1e-3 here
    # and fp8 0.05 or more: the band lies between.
    bfloat16_band=0.01,
    # the shared key is never rotated
    fault="no_rope_on_shared_key",
    rehearse_flags=("--control", "1"),
    fp8_moves=0.02)
MODEL = PRESET.model
ref = PRESET.ref
policy, weights = PRESET.policy, PRESET.weights


class TestPolicy(PolicyConformance):
    """(a, b): the suite at this preset.  1e-5 in float32: the program
    scores ``q Wkvb_k^T`` against the row, the reference ``q`` against
    the row's up-projection.  Forty steps: the ring (16 + 6 rows) wraps
    once."""

    preset = PRESET


def test_acting_through_the_slot_kernel_is_the_chunked_forward(monkeypatch):
    """Rows of 20 + 4 numbers in float32 and rings of 122 + 6 slots: whole
    sublane tiles and one whole lane tile, so every acting step's write
    moves the slot's lane tile in the kernel (``ops/attention.py
    _latent_slot_write``; the preset's rings of 22 slots keep the slice
    update).  Fourteen steps across two episode ends a step at a time
    give the logits and the rings of two chunks of seven, whose writes
    are the scatter (float32's rounding apart: the rows come from
    products of other shapes)."""
    from scalable_agent_tpu.obs import registry
    from scalable_agent_tpu.ops import attention

    monkeypatch.setattr(registry, "_registry", registry.MetricsRegistry())
    monkeypatch.setattr(attention, "_slot_writes", [0, 0])
    cfg = dict(TINY, kv_lora_rank=20)
    agent = TokenPolicy(model=TokenModelConfig.from_dict(cfg),
                        unroll_length=UNROLL, episode_length=122,
                        compute_dtype=jnp.float32)
    params, steps, chunk = weights(4, cfg), 14, 7
    rng = np.random.default_rng(13)
    tokens = jnp.asarray(rng.integers(0, VOCAB, (steps, BATCH)), jnp.int32)
    done = np.zeros((steps, BATCH), bool)
    done[0] = True
    done[5, 1] = done[9, 3] = True
    done = jnp.asarray(done)
    step = jax.jit(lambda p, e, s: agent.apply(
        p, jnp.zeros(e.done.shape, jnp.int32), e, s))
    state, stepwise = agent.initial_state(BATCH), []
    assert [r.shape for r in state.keys] == [(BATCH, 24, 128)] * 3
    for t in range(steps):
        (row, _), state = step(
            params, env_outputs(tokens[t:t + 1], done[t:t + 1]), state)
        stepwise.append(row[0])
    assert registry.get_registry().snapshot()[
        "attention/latent_slot_kernel_share"] == 1.0
    chunked, rows = agent.initial_state(BATCH), []
    for t in range(0, steps, chunk):
        (logits, _), chunked = agent.apply(
            params, jnp.zeros((chunk, BATCH), jnp.int32),
            env_outputs(tokens[t:t + chunk], done[t:t + chunk]), chunked)
        rows.append(logits)
    assert rel(jnp.stack(stepwise), jnp.concatenate(rows)) < 1e-5
    for a, b in zip(state.keys, chunked.keys):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
        assert float(jnp.max(jnp.abs(a))) > 0.0


# -- (c) the state, and what the update unrolls from --------------------------

def test_the_state_is_one_ring_of_rows_a_layer():
    agent = policy(jnp.bfloat16)
    state = agent.initial_state(BATCH)
    assert state.values == ()       # nothing beside the compressed rows
    assert [r.shape for r in state.keys] == [
        (BATCH, ROW, EPISODE + UNROLL)] * 3                 # a token a column
    assert all(r.dtype == jnp.bfloat16 for r in state.keys)
    assert state.full_index.shape == (EPISODE + UNROLL,)
    # the gauge reads the state's own arrays: whole values kept beside
    # the rows would count
    assert agent.latent_bytes_per_token == 2 * ROW == sum(
        r.nbytes for r in state.keys) // (3 * BATCH * (EPISODE + UNROLL))
    assert agent.ring_bytes(BATCH) == BATCH * (EPISODE + UNROLL) * ROW * 2
    assert agent.cache_bytes(BATCH) == 3 * agent.ring_bytes(BATCH)
    assert agent.ring_readers == 1
    # the published sizes: a row of 512 + 64 in bfloat16, rings of whole
    # decode blocks
    big = TokenPolicy(
        model=TokenModelConfig.from_dict(dict(
            TINY, kv_lora_rank=512, qk_rope_head_dim=64)),
        unroll_length=256, episode_length=10240,
        compute_dtype=jnp.bfloat16)
    assert big.latent_bytes_per_token == 1152
    assert big.full_slots == 10752


@pytest.mark.parametrize("steps", [UNROLL + 1, 1], ids=["update", "decode"])
def test_no_past_tokens_whole_keys_are_in_the_lowered_step(steps):
    """The ring holds rows and both passes score rows: no array that
    has a ring's slots beside the heads' keys or values (heads x 8, or
    both: 32 or 64 numbers a slot), in any dtype, anywhere in the
    update's gradient or the decode step — the interpreter's kernel
    bodies included, which are part of the text here."""
    agent, params = policy(), weights()
    state = agent.initial_state(BATCH)
    tokens = jnp.zeros((steps, BATCH), jnp.int32)
    outputs = env_outputs(tokens, jnp.zeros((steps, BATCH), bool))

    def run(p):
        (logits, baseline), new = agent.apply(p, tokens, outputs, state)
        return jnp.sum(logits) + jnp.sum(baseline), new

    fn = jax.grad(run, has_aux=True) if steps > 1 else run
    text = jax.jit(fn).lower(params).as_text()
    slots, heads = EPISODE + UNROLL, TINY["num_attention_heads"]
    wide = TINY["qk_nope_head_dim"]             # = v_head_dim
    shapes = {tuple(int(n) for n in dims.rstrip("x").split("x"))
              for dims in re.findall(r"tensor<((?:\d+x)+)[a-z]", text)}
    with_slots = [shape for shape in shapes
                  if slots in shape or slots + steps in shape]
    assert (BATCH, ROW, slots) in with_slots                # the rings
    for shape in with_slots:
        rest = list(shape)
        rest.remove(slots if slots in shape else slots + steps)
        assert not ({heads * wide, 2 * heads * wide} & set(rest)
                    or (rest.count(heads) > (BATCH in shape)
                        and wide in rest)), shape


# -- (d) the rotation ---------------------------------------------------------

def test_interleaved_pairs_are_the_half_split_ones_on_permuted_weights():
    """``rope_interleave`` turns numbers (2i, 2i + 1) where the other
    rotation turns (i, i + D / 2): a model whose rotated columns of Wq
    and Wkva are permuted accordingly, run with the half-split rotation,
    is the same function."""
    turned, nope = TINY["qk_rope_head_dim"], TINY["qk_nope_head_dim"]
    rank, heads = TINY["kv_lora_rank"], TINY["num_attention_heads"]
    # half-split position j holds interleaved position order[j]
    order = np.concatenate([np.arange(0, turned, 2),
                            np.arange(1, turned, 2)])
    params = weights(21)
    permuted = jax.tree_util.tree_map(lambda x: x, params)
    for layer in range(TINY["num_hidden_layers"]):
        attn = permuted["params"][f"layer_{layer}"]["attention"]
        wq = np.asarray(attn["q_proj"]["kernel"]).reshape(
            -1, heads, nope + turned)
        wq = np.concatenate([wq[..., :nope], wq[..., nope:][..., order]], -1)
        attn["q_proj"] = {"kernel": jnp.asarray(wq.reshape(-1, heads * (
            nope + turned)))}
        wa = np.asarray(attn["kv_a_proj"]["kernel"])
        attn["kv_a_proj"] = {"kernel": jnp.asarray(np.concatenate(
            [wa[:, :rank], wa[:, rank:][:, order]], -1))}
    tokens = jnp.asarray(np.random.default_rng(8).integers(
        0, VOCAB, (UNROLL, BATCH)), jnp.int32)
    done = jnp.zeros((UNROLL, BATCH), bool).at[0].set(True)
    outputs = env_outputs(tokens, done)
    pairs, split = policy(), policy(model=TokenModelConfig.from_dict(
        dict(TINY, rope_interleave=False)))
    (want, _), _ = pairs.apply(params, tokens, outputs,
                               pairs.initial_state(BATCH))
    (got, _), _ = split.apply(permuted, tokens, outputs,
                              split.initial_state(BATCH))
    assert rel(got, want) < 1e-5
    (other, _), _ = split.apply(params, tokens, outputs,
                                split.initial_state(BATCH))
    assert rel(other, want) > 1e-3
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 3, 1, turned)),
                    jnp.float32)
    position = jnp.asarray([[0, 5, 9], [2, 0, 1]], jnp.int32)
    np.testing.assert_allclose(
        np.asarray(token_policy.rope_interleaved(x, position, 1e6))[
            ..., order],
        np.asarray(token_policy.rope(x[..., order], position, 1e6)),
        atol=1e-6)


# -- (e) the share tied to the model ------------------------------------------

def shares_of_the_layer(held=2):
    """(the shares' sum, the uncut layer, each share's (routed
    part, load)) — the body of ``test_the_shares_sum_to_the_uncut_layer``
    for this family (tests/test_token_policy.py has the test, a case a
    family).  ``8 / held`` chips hold ``held`` of the eight experts each
    (at four there is nothing for ops/moe.py to compact).  A chip's
    policy layer gives the shared experts' result plus its own experts'
    part of the routed sum (the router over all eight, two a token, the
    chosen scores normalised and scaled); the routed parts and the
    two shared experts counted once are the reference's layer over all
    eight."""
    shares = 8 // held
    cfg = dict(TINY, experts_held=8, first_expert=0)
    whole = ref.to_tree(ref.make_weights(cfg, 17))["layer_1"]["moe"]
    # 320 tokens: enough pairs for a chunk of ops/moe.py's walk (512
    # rows) to be less than all 640
    m = jnp.asarray(np.random.default_rng(5).normal(size=(320, 64)),
                    jnp.float32)
    want = ref.expert_layer(cfg, whole, m, lambda x: x)
    shared = ref.gated_mlp(whole["shared"], m, lambda x: x)
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
    return shared + sum(part for part, _ in parts), want, parts
