"""The delta-rule scan (ops/gated_delta.py: a matrix state a head, a
decay and a write strength a head a token, the state read against the
key before it is written) under the Pallas interpreter against the
token-by-token recurrence (``gated_delta_step`` in a ``lax.scan``),
float32: the forward and the gradient of every operand, at chunk sizes
that do and do not divide the unroll, an episode's end inside a chunk,
at a chunk's edge and at token 0, write strengths near 0, 1 and 2, the
chunk's triangular solve against a plain inverse, and one token as a
step with no kernel; and ``ops/attention.py``'s ring under ONE query head
a key head, the corner only the ``olmo_hybrid`` family has, through
``_decode`` and ``_blockwise``.  The interpreter proves the arithmetic
and the custom VJP's plumbing; tests/test_chip_bringup.py compiles both
kernels for a v5e at the cell's widths; tests/test_olmo_hybrid_policy.py
holds the family's whole policy, these kernels in it, to its reference.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scalable_agent_tpu.ops import attention, gated_delta


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def recurrence(q, k, v, beta, log_decay, reset, state):
    """``gated_delta_scan`` a token at a time: the step in a
    ``lax.scan``."""
    def step(s, inputs):
        o, s = gated_delta.gated_delta_step(*inputs, s)
        return s, o

    def time_major(x):
        return jnp.swapaxes(x, 0, 1)

    state, o = jax.lax.scan(
        step, state, tuple(map(time_major, (q, k, v, beta, log_decay,
                                            reset))))
    return time_major(o), state


# where episodes end, by name: scattered; inside a chunk (token 3 of
# chunks of 8) and at a chunk's edge (token 8); at token 0 alone; none
_RESETS = {
    "scattered": None,
    "mid-chunk-and-edge": (3, 8),
    "token-0": (0,),
    "none": (),
}
# the write strength b: as the model has it, in (0, 2); near 0 (the
# state hardly written), at 1 (a key's old value replaced outright), and
# near 2 (the transition's eigenvalue along the key near -1)
_WRITES = {"model": None, "near-0": 1e-3, "one": 1.0, "near-2": 2.0 - 1e-3}


def scan_operands(steps, resets="scattered", write="model", seed=0):
    batch, heads, keys, values = 2, 3, 8, 16
    ks = jax.random.split(jax.random.key(seed), 8)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    beta = 2.0 * jax.nn.sigmoid(
        2.0 * jax.random.normal(ks[3], (batch, steps, heads)))
    if _WRITES[write] is not None:
        beta = jnp.full_like(beta, _WRITES[write])
    if _RESETS[resets] is None:
        reset = jax.random.uniform(ks[6], (batch, steps)) < 0.15
    else:
        reset = jnp.zeros((batch, steps), bool)
        for env, at in enumerate(_RESETS[resets]):
            if at < steps:
                reset = reset.at[env % batch, at].set(True)
    return dict(
        q=unit(jax.random.normal(ks[0], (batch, steps, heads, keys)))
        / np.sqrt(keys),
        # neighbouring tokens' keys alike, so that the correction is large
        k=unit(jax.random.normal(ks[1], (batch, steps, heads, keys))
               + 2.0 * jax.random.normal(ks[7], (batch, 1, heads, keys))),
        v=jax.random.normal(ks[2], (batch, steps, heads, values)),
        beta=beta,
        log_decay=-jax.nn.softplus(
            jax.random.normal(ks[4], (batch, steps, heads)) - 1.0),
        state=jax.random.normal(ks[5], (batch, heads, values, keys)),
        reset=reset)


_DIFFERENTIABLE = ("q", "k", "v", "beta", "log_decay", "state")


@functools.lru_cache(maxsize=None)
def scanned(steps, chunk, resets="scattered", write="model"):
    """(outputs, gradients) of the kernels and of the recurrence."""
    ops = scan_operands(steps, resets, write)

    def run(fn):
        def loss(*values):
            o, last = fn(*values[:5], ops["reset"], values[5])
            return jnp.sum(o * jnp.cos(o)) + jnp.sum(last * last)

        values = [ops[name] for name in _DIFFERENTIABLE]
        return jax.jit(lambda *values: (
            fn(*values[:5], ops["reset"], values[5]),
            jax.grad(loss, argnums=tuple(range(6)))(*values)))(*values)

    return (run(lambda *v: gated_delta.gated_delta_scan(*v, chunk=chunk)),
            run(recurrence))


# 17 tokens: two chunks of 8 and one token as a step; 16: whole chunks
# of 8; 9 in one chunk of 16, seven of them padding; 13 in chunks of 4:
# three of padding; 21 in chunks of 8: three of padding
_SHAPES = [(17, 8), (16, 8), (9, 16), (13, 4), (21, 8)]


_OUTPUTS = ("o", "state")


def held_to_the_recurrence(what, gradient_within, *case):
    """``what`` (an output, or the gradient of an operand) of the
    kernels against the recurrence's, on ``scanned(*case)``."""
    (got, got_grads), (want, want_grads) = scanned(*case)
    if what in _OUTPUTS:
        at = _OUTPUTS.index(what)
        assert rel(got[at], want[at]) < 1e-5
    else:
        at = _DIFFERENTIABLE.index(what)
        assert rel(got_grads[at], want_grads[at]) < gradient_within


@pytest.mark.parametrize("steps,chunk", _SHAPES)
@pytest.mark.parametrize("what", _OUTPUTS)
def test_the_scans_kernels_are_the_recurrence(steps, chunk, what):
    held_to_the_recurrence(what, None, steps, chunk)


@pytest.mark.parametrize("steps,chunk", _SHAPES)
@pytest.mark.parametrize("operand", _DIFFERENTIABLE)
def test_the_scans_backward_kernel_is_the_recurrences(steps, chunk, operand):
    held_to_the_recurrence(operand, 2e-5, steps, chunk)


@pytest.mark.parametrize("resets", ["mid-chunk-and-edge", "token-0", "none"])
@pytest.mark.parametrize("what", _OUTPUTS + _DIFFERENTIABLE)
def test_an_episodes_end_wherever_it_falls(resets, what):
    """An end inside a chunk (token 3 of 8) and at a chunk's edge (token
    8, the second chunk's first), at token 0 alone, and none at all: the
    forward and every gradient."""
    held_to_the_recurrence(what, 2e-5, 17, 8, resets)


def test_a_reset_token_meets_a_zero_state():
    """What the state held before an episode's first token reaches
    nothing after it: with an end at token 0 the start state's gradient
    is zero in that env, and not in the other."""
    (_, grads), _ = scanned(17, 8, "token-0")
    d_state = np.asarray(grads[_DIFFERENTIABLE.index("state")])
    assert np.all(d_state[0] == 0.0) and np.any(d_state[1] != 0.0)


@pytest.mark.parametrize("write", ["near-0", "one", "near-2"])
@pytest.mark.parametrize("what", _OUTPUTS + _DIFFERENTIABLE)
def test_a_write_strength_near_0_1_and_2(write, what):
    """b near 2 with keys alike is where the triangular system's
    entries are largest (``A[t, j]`` near 2): the solve is block
    elimination, not a series, and stays the recurrence's."""
    held_to_the_recurrence(what, 5e-5, 16, 8, "scattered", write)


@pytest.mark.parametrize("size", [2, 4, 16, 64])
def test_the_solve_is_the_inverse_of_a_unit_lower_triangle(size):
    """``_inverse``: block elimination against a plain inverse in
    float64, entries as large as the model's write strength allows
    (every below-diagonal entry in (-2, 2))."""
    rng = np.random.default_rng(size)
    a = np.tril(rng.uniform(-2.0, 2.0, (size, size)), -1)
    down = jnp.arange(size, dtype=jnp.int32)[:, None] + jnp.zeros(
        (1, size), jnp.int32)
    got = gated_delta._inverse(jnp.asarray(a, jnp.float32), down, down.T)
    want = np.linalg.inv(np.eye(size) + a)
    assert rel(got, want) < 1e-5
    assert float(jnp.max(jnp.abs(jnp.triu(got, 1)))) == 0.0


def test_one_token_is_a_step_and_no_kernel():
    ops = scan_operands(1)
    text = jax.jit(lambda **o: gated_delta.gated_delta_scan(
        o["q"], o["k"], o["v"], o["beta"], o["log_decay"], o["reset"],
        o["state"])).lower(**ops).as_text()
    assert "pallas" not in text and "custom_call" not in text


def test_a_chunk_is_a_power_of_two():
    ops = scan_operands(12)
    with pytest.raises(ValueError, match="power of two"):
        gated_delta.gated_delta_scan(
            ops["q"], ops["k"], ops["v"], ops["beta"], ops["log_decay"],
            ops["reset"], ops["state"], chunk=6)


def test_no_state_a_token_is_made():
    """The kernels' results, as lowered: the largest float32 array is a
    state a CHUNK (the forward's kept starts), never one a token."""
    import math
    import re

    ops = scan_operands(16)
    text = jax.jit(lambda **o: gated_delta.gated_delta_scan(
        o["q"], o["k"], o["v"], o["beta"], o["log_decay"], o["reset"],
        o["state"], chunk=8)).lower(**ops).as_text()
    a_state_a_token = 2 * 16 * 3 * 16 * 8
    shapes = re.findall(r"tensor<([\dx]+)xf32>", text)
    largest = max(math.prod(int(n) for n in dims.split("x"))
                  for dims in shapes)
    # keys are padded to a lane tile (8 -> 128) in the kernels' operands
    assert largest <= 2 * 3 * 2 * 16 * 128 < a_state_a_token * 16


# -- the ring under one query head a key head ---------------------------------

def ring_case(queries, seed=0):
    """30 -> 3 heads: as many key/value heads as query heads, a ring of
    24 slots holding 20 tokens of two envs, one of which began its
    episode at token 6."""
    batch, heads, dim, slots, written = 2, 3, 8, 24, 20
    ks = jax.random.split(jax.random.key(seed), 5)
    ring_index = jnp.where(jnp.arange(slots) < written, jnp.arange(slots),
                           attention.NO_KEY).astype(jnp.int32)
    index = written + jnp.arange(queries, dtype=jnp.int32)
    start = jnp.broadcast_to(jnp.asarray([[0], [6]], jnp.int32),
                             (batch, queries))
    shape = (batch, queries, heads, dim)
    return dict(
        query=jax.random.normal(ks[0], shape),
        key=jax.random.normal(ks[1], shape),
        value=jax.random.normal(ks[2], shape),
        ring_keys=jax.random.normal(ks[3], (batch, slots, heads, dim)),
        ring_values=jax.random.normal(ks[4], (batch, slots, heads, dim)),
        ring_index=ring_index, index=index, episode_start=start)


@pytest.mark.parametrize("queries,kernel", [(1, "_decode"), (5, "_blockwise")])
def test_one_query_head_a_key_head_through_the_ring(queries, kernel):
    """A group of ONE query head a key head (the cells before have 2, 8
    or 16): ``cached_attention`` through ``_decode`` (one query an env)
    and ``_blockwise`` (more) is the plain ``_attend``."""
    case = ring_case(queries)
    got, _ = jax.jit(attention.cached_attention)(**case)
    batch, _, heads, dim = case["query"].shape
    want = attention._attend(
        case["query"].reshape(batch, queries, heads, 1, dim), case["key"],
        case["value"], case["ring_keys"], case["ring_values"],
        case["ring_index"], case["index"], case["episode_start"], None)
    assert rel(got, want.reshape(got.shape)) < 1e-5
    assert hasattr(attention, kernel)


def test_one_query_head_a_key_head_differentiates_through_blockwise():
    case = ring_case(5)
    batch, queries, heads, dim = case["query"].shape
    rest = {k: v for k, v in case.items()
            if k not in ("query", "key", "value")}

    def through(fn):
        return jax.grad(lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v))),
                        argnums=(0, 1, 2))(
                            case["query"], case["key"], case["value"])

    got = through(lambda q, k, v: attention.cached_attention(
        q, k, v, **rest)[0])
    want = through(lambda q, k, v: attention._attend(
        q.reshape(batch, queries, heads, 1, dim), k, v, rest["ring_keys"],
        rest["ring_values"], rest["ring_index"], rest["index"],
        rest["episode_start"], None))
    for mine, theirs in zip(got, want):
        assert rel(mine, theirs) < 2e-5
