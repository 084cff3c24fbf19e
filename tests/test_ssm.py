"""The selective-scan kernel (ops/ssm.py) under the Pallas interpreter
against a ``lax.scan`` over time, float32: the forward, the gradient of
every operand, ``done`` at step 0, mid-unroll, on a chunk's edges and
twice in one unroll, at every row of one 8-token block and on a ragged
tail's first and last token, unrolls of one chunk, of whole chunks, with
a ragged last one and of whole chunks and one token (the cell's 257 in
small), widths of one lane tile and of two, and the state carried from
one call into the next, across a ragged tail too.
The interpreter proves the arithmetic and the custom VJP's plumbing;
tests/test_chip_bringup.py compiles both kernels for a v5e at the
cell's widths.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scalable_agent_tpu.ops import ssm

BATCH, WIDTH, STATES = 2, 256, 16
OPERANDS = ("x", "delta", "a", "dp", "b", "c", "state")
CHUNK = ssm._CHUNK

TWO_TILES = 768             # channels the kernels take in two lane tiles

# name -> (steps, [(env, step) that begins an episode])
CASES = {
    "one_chunk_done_at_0": (24, [(0, 0), (1, 0)]),
    "mid_unroll": (CHUNK + 37, [(0, 0), (1, 50)]),
    "twice_in_one_unroll": (CHUNK + 37, [(0, 5), (0, 81), (1, 0)]),
    "chunk_edges": (2 * CHUNK + 3, [(0, CHUNK - 1), (0, CHUNK),
                                    (1, 2 * CHUNK), (1, 2 * CHUNK + 2)]),
    "whole_chunks": (2 * CHUNK, [(1, 77)]),
    "ragged_last_chunk_of_one": (CHUNK + 1, [(0, CHUNK)]),
    "no_done": (40, []),
    # the bulk passes take the tokens' rows eight at a time
    "every_row_of_a_block": (CHUNK + 37, [(0, step) for step in range(16, 24)]
                             + [(1, 19)]),
    "tail_first_and_last": (CHUNK + 37, [(0, CHUNK), (0, CHUNK + 36),
                                         (1, CHUNK + 36)]),
    # phi4flash.ingraph's 257 = 4 x 64 + 1 in small
    "whole_chunks_and_one": (2 * CHUNK + 1, [(0, 2 * CHUNK), (1, 70)]),
    "two_lane_tiles": (CHUNK + 5, [(0, 0), (1, CHUNK + 2)]),
}
WIDTHS = {"two_lane_tiles": TWO_TILES}      # the other cases': WIDTH, one tile


def scan_over_time(x, delta, a, dp, b, c, reset, state):
    """``selective_scan`` as a ``lax.scan`` of its own one-token step:
    what the kernels are held to."""
    def step(state, inputs):
        y, state = ssm.scan_step(*inputs[:2], a, dp, *inputs[2:], state)
        return state, y

    time_major = [jnp.swapaxes(v, 0, 1) for v in (x, delta, b, c, reset)]
    state, y = jax.lax.scan(step, state, tuple(time_major))
    return jnp.swapaxes(y, 0, 1), state


def operands(steps, seed=0, width=WIDTH):
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    return dict(
        x=normal(BATCH, steps, width),
        delta=jax.nn.softplus(normal(BATCH, steps, width) - 1.0),
        a=-jnp.exp(0.3 * normal(STATES, width)), dp=normal(width),
        b=normal(BATCH, steps, STATES), c=normal(BATCH, steps, STATES),
        state=normal(BATCH, STATES, width))


def resets(steps, at):
    reset = np.zeros((BATCH, steps), bool)
    for env, step in at:
        reset[env, step] = True
    return jnp.asarray(reset)


def scalar(fn, reset, width=WIDTH):
    """A number that weighs every output, the last state among them."""
    weights = jnp.cos(jnp.arange(width, dtype=jnp.float32))

    def total(*values):
        y, last = fn(*values[:6], reset, values[6])
        return jnp.sum(y * weights) + 0.3 * jnp.sum(last * weights[::-1])

    return total


@pytest.fixture(scope="module", params=sorted(CASES))
def case(request):
    steps, at = CASES[request.param]
    width = WIDTHS.get(request.param, WIDTH)
    values, reset = operands(steps, width=width), resets(steps, at)
    ordered = [values[name] for name in OPERANDS]
    run = {}
    for name, fn in (("kernel", ssm.selective_scan),
                     ("scan", scan_over_time)):
        y, last = fn(*ordered[:6], reset, ordered[6])
        grads = jax.grad(scalar(fn, reset, width),
                         argnums=range(7))(*ordered)
        run[name] = dict(y=y, last=last, **dict(zip(OPERANDS, grads)))
    return run


def gap(got, want):
    return float(jnp.max(jnp.abs(got - want))
                 / (jnp.max(jnp.abs(want)) + 1e-30))


def test_the_cases_widths_are_one_lane_tile_and_two():
    assert WIDTH // ssm._lanes(WIDTH, ssm._LANES) == 1
    assert TWO_TILES // ssm._lanes(TWO_TILES, ssm._LANES) == 2


@pytest.mark.parametrize("what", ["y", "last"])
def test_the_forward_is_the_scans(case, what):
    assert gap(case["kernel"][what], case["scan"][what]) < 1e-6


@pytest.mark.parametrize("operand", OPERANDS)
def test_the_gradient_of_every_operand_is_the_scans(case, operand):
    assert gap(case["kernel"][operand], case["scan"][operand]) < 1e-5


@pytest.mark.parametrize("steps,cut", [
    (CHUNK + 20, 29),               # the second call begins before a reset
    (3 * CHUNK + 2, 2 * CHUNK + 1),   # the first ends on a ragged tail of one
], ids=["before_a_reset", "across_a_ragged_tail"])
def test_the_state_carries_from_one_call_into_the_next(steps, cut):
    values = operands(steps, seed=3)
    reset = resets(steps, [(0, 0), (1, 30)])
    ordered = [values[name] for name in OPERANDS]
    whole, last = ssm.selective_scan(*ordered[:6], reset, ordered[6])
    state, parts = ordered[6], []
    for part in (slice(0, cut), slice(cut, steps)):
        y, state = ssm.selective_scan(
            ordered[0][:, part], ordered[1][:, part], ordered[2],
            ordered[3], ordered[4][:, part], ordered[5][:, part],
            reset[:, part], state)
        parts.append(y)
    assert gap(jnp.concatenate(parts, axis=1), whole) < 1e-6
    assert gap(state, last) < 1e-6


def test_steps_of_one_token_are_the_unroll():
    steps = 12
    values = operands(steps, seed=5)
    reset = resets(steps, [(0, 0), (1, 4), (1, 5)])
    ordered = [values[name] for name in OPERANDS]
    whole, last = ssm.selective_scan(*ordered[:6], reset, ordered[6])
    state, rows = ordered[6], []
    for t in range(steps):
        at = slice(t, t + 1)
        y, state = ssm.selective_scan(
            ordered[0][:, at], ordered[1][:, at], ordered[2], ordered[3],
            ordered[4][:, at], ordered[5][:, at], reset[:, at], state)
        rows.append(y)
    assert gap(jnp.concatenate(rows, axis=1), whole) < 1e-6
    assert gap(state, last) < 1e-6


def test_a_reset_left_out_is_seen():
    """What the comparisons above would miss if they could not see a
    reset: the same inputs without one differ by far more than their
    tolerance."""
    steps = 40
    values = operands(steps, seed=7)
    ordered = [values[name] for name in OPERANDS]
    with_reset, _ = ssm.selective_scan(
        *ordered[:6], resets(steps, [(0, 20)]), ordered[6])
    without, _ = ssm.selective_scan(
        *ordered[:6], resets(steps, []), ordered[6])
    assert gap(with_reset[0, 20:], without[0, 20:]) > 1e-2
    np.testing.assert_array_equal(np.asarray(with_reset[0, :20]),
                                  np.asarray(without[0, :20]))
