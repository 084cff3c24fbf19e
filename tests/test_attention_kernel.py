"""The attention kernels (ops/attention.py: the blockwise one of T > 1
and the decode's, one query an env) against the score-everything form
they replaced (``_attend``, which the program no longer calls): tiny
shapes, the Pallas interpreter, float32 operands, so the two agree to
float32's rounding."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scalable_agent_tpu.ops import attention as A

KV, GROUP, DIM = 2, 2, 8


def case(queries=5, slots=12, window=4, written=20, done_at=None,
         empty=False, batch=3, seed=0):
    """Arguments of ``cached_attention``: a ring that holds the tokens
    ``written - slots .. written - 1`` of every env's stream (wrapped
    when ``written > slots``; nothing when ``empty``), env 1's episode
    three tokens old, env 2's beginning at query ``done_at``."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)

    def normal(key, *shape):
        return jax.random.normal(key, shape, jnp.float32)

    ring_index = np.full((slots,), A.NO_KEY, np.int32)
    if not empty:
        for token in range(max(0, written - slots), written):
            ring_index[token % slots] = token
    start = np.zeros((batch, queries), np.int32)
    start[1, :] = max(written - 3, 0)
    if done_at is not None:
        start[2, done_at:] = written + done_at
    return (normal(keys[0], batch, queries, KV * GROUP, DIM),
            normal(keys[1], batch, queries, KV, DIM),
            normal(keys[2], batch, queries, KV, DIM),
            normal(keys[3], batch, slots, KV, DIM),
            normal(keys[4], batch, slots, KV, DIM),
            jnp.asarray(ring_index),
            written + jnp.arange(queries, dtype=jnp.int32),
            jnp.asarray(start)), window


def reference(query, key, value, *rest, window, streams=1):
    batch, queries, heads, dim = query.shape
    grouped = query.reshape(batch, queries, KV, heads // KV, dim)
    return A._attend(grouped, key, value, *rest, window, streams).reshape(
        batch, queries, heads * streams * dim)


def mask(ring_index, index, episode_start, window):
    """bool [B, T, S]: the rule as ``_attend`` writes it."""
    seen = ((ring_index[None, None, :] <= index[None, :, None])
            & (ring_index[None, None, :] >= episode_start[:, :, None]))
    if window is not None:
        seen &= index[None, :, None] - ring_index[None, None, :] < window
    return np.asarray(seen)


# Few shapes and windows, many rings: a case is data under a compiled
# program another case already paid for.
WINDOW = dict(queries=5, slots=12, window=4)
FULL = dict(queries=5, slots=24, window=None)
BLOCKS = dict(queries=9, slots=384, window=200)
CASES = {
    "window": dict(WINDOW),
    "a ring that has wrapped": dict(WINDOW, written=31),
    "an episode that began inside the unroll": dict(WINDOW, done_at=2),
    "an empty ring": dict(WINDOW, empty=True, written=0),
    "full": dict(FULL),
    "a ring not yet full": dict(FULL, written=7),
    "an episode that began inside it, full": dict(FULL, done_at=3),
    "queries past one tile": dict(FULL, queries=17),
    "three key blocks, the middle one skipped": dict(BLOCKS, written=500),
}


@functools.partial(jax.jit, static_argnames="window")
def _both(args, window):
    """(out, gradients) of the kernel and of ``_attend`` on one case."""
    weight = jax.random.normal(jax.random.PRNGKey(9),
                               (KV * GROUP * DIM,), jnp.float32)

    def through(forward):
        def loss(query, key, value):
            out = forward(query, key, value)
            return jnp.sum(jnp.sin(out) * weight), out
        grads, out = jax.grad(loss, (0, 1, 2), has_aux=True)(*args[:3])
        return out, grads

    return (through(lambda q, k, v: A.cached_attention(
                q, k, v, *args[3:], window=window)[0]),
            through(lambda q, k, v: reference(q, k, v, *args[3:],
                                              window=window)))


@functools.lru_cache(maxsize=None)
def both(name):
    args, window = case(**CASES[name])
    return _both(args, window)


@pytest.mark.parametrize("name", CASES)
def test_the_kernels_forward_is_attend(name):
    (out, _), (want, _) = both(name)
    np.testing.assert_allclose(out, want, rtol=0, atol=2e-6)


@pytest.mark.parametrize("name", CASES)
def test_the_kernels_gradient_is_grad_through_attend(name):
    (_, got), (_, want) = both(name)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=0, atol=5e-6)


def test_the_ring_gets_no_cotangent():
    args, window = case()

    def loss(ring_keys, ring_values):
        return jnp.sum(A.cached_attention(
            *args[:3], ring_keys, ring_values, *args[5:],
            window=window)[0])

    for grad in jax.jit(jax.grad(loss, (0, 1)))(args[3], args[4]):
        assert not np.any(np.asarray(grad))


@functools.partial(jax.jit, static_argnames="window")
def _forward(args, window):
    return A.cached_attention(*args, window=window)


@pytest.mark.parametrize("written,want", [
    (500, [True, False, True]),      # tokens 116..499 held, 301.. seen
    (384, [False, True, True]),
    (100, [True, False, False]),     # the ring a quarter full
    (0, [False, False, False]),      # and empty
])
def test_the_skipped_blocks_are_those_no_query_sees(written, want):
    args, window = case(**dict(BLOCKS, written=written,
                               empty=written == 0))
    ring_index, index, start = args[5:]
    batch = start.shape[0]
    visit = np.asarray(A.visited_blocks(ring_index, index, start, window,
                                        128))
    seen = mask(ring_index, index, start, window).reshape(batch, 9, 3, 128)
    np.testing.assert_array_equal(visit, seen.any(axis=(1, 3)))
    # env 0's episode is as old as the stream: its row is ``want``
    assert visit[0].tolist() == want
    # what is skipped is not read: poison there changes nothing
    poison = jnp.where(jnp.asarray(np.repeat(visit, 128, axis=1))
                       [:, :, None, None], 0.0, jnp.nan)
    out, stats = _forward(
        args[:3] + (args[3] + poison, args[4] + poison) + args[5:], window)
    np.testing.assert_allclose(out, reference(*args, window=window),
                               rtol=0, atol=2e-6)
    # the own keys are one more block, always visited
    assert float(stats["key_blocks_visited_share"]) == pytest.approx(
        (visit.sum() + batch) / (batch * 4))


def test_one_query_an_env_is_the_decode_kernel_and_equals_attend():
    args, window = case(queries=1)
    out, stats = _forward(args, window)
    assert stats == {}
    np.testing.assert_allclose(
        out, jax.jit(functools.partial(reference, window=window))(*args),
        rtol=0, atol=2e-6)

    def kernels(queries):
        args, window = case(queries=queries)
        text = str(jax.make_jaxpr(
            lambda *a: A.cached_attention(*a, window=window)[0])(*args))
        assert "pallas_call" in text
        return [name for name in ("_decode", "_blockwise")
                if f"name={name}" in text]

    assert kernels(1) == ["_decode"]
    assert kernels(2) == ["_blockwise"]


# -- the decode kernel: one query an env --------------------------------------

DECODE_CASES = dict(
    CASES, **{
        "an env that sees the whole ring": dict(FULL, written=40),
        "a window that wraps the ring's end": dict(WINDOW, written=26),
        "a window that wraps the ring's end, by blocks": dict(
            BLOCKS, written=400),
    })
DECODE_BLOCK = 128              # slots a grid step, on the BLOCKS ring


@pytest.fixture(scope="module")
def small_blocks():
    """Decode blocks of 128 slots, three to the BLOCKS ring: a MiB of
    keys would hold any of the tests' rings whole.  For the module's
    length: the jitted kernel reads the size when it is traced."""
    real = A._DECODE_BLOCK_BYTES
    A._DECODE_BLOCK_BYTES = DECODE_BLOCK * KV * DIM * 4
    A._decode.clear_cache()
    yield
    A._DECODE_BLOCK_BYTES = real
    A._decode.clear_cache()


def decode_case(name):
    """The case with one query an env; env 2's episode, where the case
    has one begin inside the unroll, begins at this very token."""
    kwargs = dict(DECODE_CASES[name], queries=1)
    if "done_at" in kwargs:
        kwargs["done_at"] = 0
    return case(**kwargs)


# every ring with one stream; two streams on a ring of each shape
TWO_STREAMS = ("a ring that has wrapped",
               "an episode that began inside it, full",
               "a window that wraps the ring's end, by blocks")


@pytest.mark.parametrize(
    "name,streams", [(name, 1) for name in DECODE_CASES]
    + [(name, 2) for name in TWO_STREAMS])
def test_the_decode_kernel_is_attend(small_blocks, name, streams):
    args, window = decode_case(name)
    out, stats = A.cached_attention(*args, window=window, streams=streams)
    assert stats == {} and out.dtype == jnp.float32
    np.testing.assert_allclose(
        out, reference(*args, window=window, streams=streams), rtol=0,
        atol=1e-5)


@pytest.mark.parametrize("written,want", [
    (500, [True, False, True]),      # tokens 116..499 held, 301.. seen
    (400, [True, True, True]),       # the window wraps the ring's end
    (384, [False, True, True]),
    (100, [True, False, False]),     # the ring a quarter full
    (0, [False, False, False]),      # and empty
])
def test_the_decode_skips_the_blocks_its_query_does_not_see(
        small_blocks, written, want):
    args, window = case(**dict(BLOCKS, queries=1, written=written,
                               empty=written == 0))
    ring_index, index, start = args[5:]
    batch = start.shape[0]
    visit = np.asarray(A.decode_visits(ring_index, index, start, window,
                                       DECODE_BLOCK))[:, 0]
    seen = mask(ring_index, index, start, window).reshape(
        batch, 3, DECODE_BLOCK)
    np.testing.assert_array_equal(visit, seen.any(axis=2))
    assert visit[0].tolist() == want
    # the grid walks the visited pairs in their order, and one step (to
    # skip) of an env that sees no block
    order, steps = A._decode_order(jnp.asarray(visit))
    walked = np.asarray(order)[:int(steps[0])]
    lone = ~visit.any(axis=1, keepdims=True) & (np.arange(3) == 0)
    np.testing.assert_array_equal(
        walked, np.flatnonzero((visit | lone).reshape(-1)))
    # what is skipped is not read: poison there changes nothing
    poison = jnp.where(jnp.asarray(np.repeat(visit, DECODE_BLOCK, axis=1))
                       [:, :, None, None], 0.0, jnp.nan)
    out, _ = A.cached_attention(
        *args[:3], args[3] + poison, args[4] + poison, *args[5:],
        window=window)
    np.testing.assert_allclose(out, reference(*args, window=window),
                               rtol=0, atol=1e-5)


def test_the_updates_pass_counts_what_its_decode_steps_visited(
        small_blocks):
    """``decode_key_blocks_visited_share``: query ``t`` of an unroll
    against the ring with the unroll's tokens before ``t`` in their
    slots is the decode step that made token ``t``."""
    queries = 9
    args, window = case(**dict(BLOCKS, written=500, done_at=4))
    ring_index, index, start = args[5:]
    batch = start.shape[0]
    visited = 0
    for t in range(queries):
        visited += int(jnp.sum(A.decode_visits(
            ring_index, index[t:t + 1], start[:, t:t + 1], window,
            DECODE_BLOCK)))
        ring_index = A.index_write(ring_index, index[t], 1)
    _, stats = A.cached_attention(*args, window=window)
    assert float(stats["decode_key_blocks_visited_share"]) == pytest.approx(
        (visited + batch * queries) / (batch * queries * 4))


def test_the_decode_block_is_the_most_lane_tiles_within_its_bytes(
        monkeypatch):
    monkeypatch.setattr(A, "_DECODE_BLOCK_BYTES", 2 ** 20)
    # the cells' rings at their widths (slots, bytes a slot of keys)
    assert A._decode_block(2304, 4 * 128 * 2) == 768
    assert A._decode_block(4352, 4 * 128 * 2) == 256
    assert A._decode_block(768, 10 * 128 * 2) == 384
    assert A._decode_block(6400, 10 * 128 * 2) == 256
    # a ring no lane tile divides is one block
    assert A._decode_block(12, 64) == 12


def test_bfloat16_operands_stay_within_their_rounding():
    """The kernel rounds a block's unnormalised weights where ``_attend``
    rounds the normalised ones: both are one bfloat16 rounding of each
    weight, so the two stand 2^-8 of the output's scale apart, not
    more."""
    args, window = case(**FULL)
    low = tuple(A.round_to(x, jnp.bfloat16) for x in args[:5]) + args[5:]
    out, _ = _forward(low, window)
    want = reference(*low, window=window)
    assert out.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(out - want))) < 2 ** -7 * float(
        jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("streams", [1, 2])
def test_the_decode_kernels_bfloat16_stays_within_its_rounding(
        small_blocks, streams):
    """As above for one query an env over three blocks: a block's
    unnormalised weights are rounded once, as ``_attend`` rounds the
    normalised ones."""
    args, window = case(**dict(BLOCKS, queries=1, written=500))
    low = tuple(A.round_to(x, jnp.bfloat16) for x in args[:5]) + args[5:]
    out, _ = A.cached_attention(*low, window=window, streams=streams)
    want = reference(*low, window=window, streams=streams)
    assert out.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(out - want))) < 2 ** -7 * float(
        jnp.max(jnp.abs(want)))
