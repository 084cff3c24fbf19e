"""The expert layer (ops/moe.py), float32, against the references' plain
statements of it (benchmark/references/afmoe_token.py, and the other
expert families' through their presets):

(a) a share: what one chip's held experts give, by the grouped path (the
    sorted pairs walked a chunk of ``compact_rows`` rows at a time) and
    by a decode step's every-expert form, is the reference's share, and
    the shares sum to the uncut layer (a case a family that shares
    experts so);
(b) no pair is dropped when all land on one held expert, and the
    gradient ignores the rows no pair holds;
(c) the walk agrees with one chunk on either side of a chunk's rows, and
    no activation of it has a row a pair;
(d) experts without a gate (``act="relu2"``: two matrices) against a
    dense loop over experts, walking past the first chunk, at widths
    that are whole tiles of the grouped product and at widths padded to
    them.

A family's own 16 shares are in tests/test_nemotron_policy.py; the
benchmark's reader of the layer's load is benchmark/tests'.
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

from family_suite import env_outputs, rel  # noqa: E402
from scalable_agent_tpu.ops import moe  # noqa: E402
from test_token_policy import (  # noqa: E402
    BATCH,
    TINY,
    UNROLL,
    policy,
    ref,
    stream,
    weights,
)


# -- (a) the share adds up ----------------------------------------------------

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


# -- (b) no token is dropped --------------------------------------------------

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


# -- (c) the walk, a chunk at a time ------------------------------------------

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


# -- (d) experts without a gate -----------------------------------------------

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
