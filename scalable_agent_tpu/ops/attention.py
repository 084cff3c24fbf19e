"""Causal attention that continues from a cache: one function for
acting (T = 1, through the cache) and learning (T = unroll, over the
unroll and the cached prefix).

The keys a query may see are decided by three numbers, never by where
a key lies in memory: the key's index in its env's token stream, the
query's index, and the index at which the query's episode began.  A
query sees a key when the key is not later than it, belongs to its
episode, and (window layers) is fewer than ``window`` tokens back.  The
cache is a ring, so its slots are in no order; each slot's stream index
rides beside it (``ring_index``, ``NO_KEY`` where a slot holds nothing
a query may see).  Every array's shape is fixed: how full the cache is
changes a mask and, in the decode, how many blocks a kernel walks.

Two kernels, chosen by the one shape that tells them apart; no score of
either leaves VMEM.  One query an env (acting) goes through ``_decode``:
a step needs the keys and values of the ring's LIVE slots once and
nothing else, so the kernel walks, for each env, the blocks that hold a
key its query may see (``decode_visits``: an episode half over sees
half of a full ring) and brings every key/value head of a block in one
fetch; the scores lie queries down the sublanes (``group * streams``
rows a key/value head), keys along the lanes.  More than one (learning)
would write ``[B, heads, T, S + T]`` scores in float32 (2.7 GB a window
layer at 32 envs x 257 queries x 2,561 keys, 4.9 GB on the full layer)
and read them back several times, forward, rematerialized forward and
backward; there ``_blockwise`` walks the ring and then the call's own
keys a block at a time in one Pallas kernel with a running maximum and
sum.  Its backward kernel recomputes a block's scores from the saved
log-sum-exp and gives the query's gradient and the call's OWN keys' and
values'; the ring is the agent's state and gets no cotangent.  A key
block none of an env's queries can see is neither fetched nor scored
(``attention/key_blocks_visited_share`` counts the rest, and
``attention/decode_key_blocks_visited_share`` what the unroll's decode
steps visited).  Grouped queries: the ``heads // kv_heads`` query heads
that share a key/value head are one matmul's columns (16, 8 or 2 in the
first four families; ONE in ``olmo_hybrid``, whose 30 key/value heads
make a ring slot of 15,360 bytes a token: the decode pads the one query
row a head to a sublane tile, and a block of the ring is then every
head's keys of that many slots).  Inside the
update's kernels a block's scores lie keys down, queries across
(``[K, R]``): the maximum and the sum over keys are then elementwise
over vregs, and what a query carries (its bounds, maximum, sum) is a
lane vector.  ``_attend`` scores every slot in XLA: the plain form both
kernels are held to (tests/test_attention_kernel.py), which nothing
else calls.
"""

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def round_to(x, dtype):
    """``x`` rounded to ``dtype`` by an op the compiler may not drop.
    A bare ``astype`` to bfloat16 that is widened again is fair game for
    XLA's excess-precision rule, and it is dropped in one compiled
    program (acting's matrix-vector products run on the vector unit in
    float32) and kept in another (learning's run on the MXU), so the two
    disagreed by bfloat16's rounding on every score."""
    dtype = jnp.dtype(dtype)
    if dtype == jnp.dtype(jnp.float32) or x.dtype == dtype:
        return x.astype(dtype)
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(
        x, exponent_bits=info.nexp, mantissa_bits=info.nmant).astype(dtype)


# A slot no query may see: below every episode's start (those are >= 0).
NO_KEY = -(2 ** 30)
_MASKED = -1e30


def _attend(query, key, value, ring_keys, ring_values, ring_index, index,
            episode_start, window, streams=1):
    """One block of envs.  query [b, T, kv, g, D]; key/value [b, T, kv,
    D]; ring_* [b, S, kv, D]; ring_index [S]; index [T];
    episode_start [b, T].  ``streams`` > 1 (differential attention): a
    head's D holds that many queries (keys) side by side, each scored
    and normalized alone, ``z`` below; all weigh the one value, and the
    result's D holds their sums side by side."""
    dtype = query.dtype
    scale = 1.0 / math.sqrt(query.shape[-1] // streams)
    z = "z" if streams > 1 else ""
    if z:
        query = query.reshape(query.shape[:-1] + (streams, -1))

    def scores(keys):
        if z:
            keys = keys.reshape(keys.shape[:-1] + (streams, -1))
        return jnp.einsum(f"btkg{z}d,bsk{z}d->bkg{z}ts", query, keys,
                          preferred_element_type=jnp.float32) * scale

    def seen(key_index):                    # [S'] -> bool [b, T, S']
        mask = ((key_index[None, None, :] <= index[None, :, None])
                & (key_index[None, None, :] >= episode_start[:, :, None]))
        if window is not None:
            mask &= (index[None, :, None] - key_index[None, None, :]
                     < window)
        return mask

    slots = ring_keys.shape[1]
    logits = jnp.concatenate([scores(ring_keys), scores(key)], axis=-1)
    mask = jnp.concatenate([seen(ring_index), seen(index)], axis=-1)
    logits = jnp.where(mask[(slice(None),) + (None,) * (logits.ndim - 3)],
                       logits, _MASKED)
    # every query sees itself, so no row is all masked
    weights = round_to(jax.nn.softmax(logits, axis=-1), dtype)
    weighted = f"bkg{z}ts,bskd->btkg{z}d"
    out = (jnp.einsum(weighted, weights[..., :slots], ring_values,
                      preferred_element_type=jnp.float32)
           + jnp.einsum(weighted, weights[..., slots:], value,
                        preferred_element_type=jnp.float32))
    return out.reshape(out.shape[:4] + (-1,))


# -- learning: blockwise, scores in VMEM only ---------------------------------

_KEY_BLOCKS = (512, 256, 128)   # ring slots a grid step; the first that
                                # divides the ring (a ring none divides,
                                # a test's, is one block)
_LANES = 128                    # queries lie along lanes, padded to these,
                                # and so are the call's own keys
_FAR = 2 ** 30                  # an index no query reaches: a padded key


def _round_up(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


def _key_block(slots: int) -> int:
    for block in _KEY_BLOCKS:
        if slots % block == 0:
            return block
    return slots


def _bounds(index, episode_start, window):
    """A query sees the keys of stream index ``low <= k <= high``:
    ([B, T], [T])."""
    low = episode_start
    if window is not None:
        low = jnp.maximum(low, index[None, :] - (window - 1))
    return low, index


def _seen_blocks(ring_index, low, high, block: int):
    """bool [..., S // block]: does the query of bounds ``low`` / ``high``
    [...] see any key of the ring's block, the slots holding the stream
    indices ``ring_index`` [..., S]?  The mask's own rule, reduced."""
    seen = ((ring_index >= low[..., None]) & (ring_index <= high[..., None]))
    return seen.reshape(seen.shape[:-1] + (-1, block)).any(axis=-1)


def visited_blocks(ring_index, index, episode_start, window, block: int):
    """bool [B, S // block]: does any query of the env see any key of
    the ring's block?"""
    low, high = _bounds(index, episode_start, window)
    return _seen_blocks(ring_index[None, None, :], low, high[None, :],
                        block).any(axis=1)


def decode_visits(ring_index, index, episode_start, window, block: int):
    """bool [B, T, S // block]: the ring blocks the decode kernel visits
    for the query of stream index ``index[t]``, had the ring stood as
    acting finds it at that token: ``ring_index`` and this call's own
    tokens before ``t`` in their slots.  One query (acting) has none
    before it and this is the kernel's block list; an unroll's (learning)
    is what its decode steps visited, by the same rule."""
    slots = ring_index.shape[0]
    slot = jnp.arange(slots, dtype=jnp.int32)
    # the one token from index[0] on that lands in each slot
    own = index[0] + (slot - index[0]) % slots
    at = jnp.where(own[None, :] < index[:, None], own[None, :],
                   ring_index[None, :])                     # [T, S]
    low, high = _bounds(index, episode_start, window)
    return _seen_blocks(at[None], low, high[None, :], block)


_NN = (((1,), (0,)), ((), ()))          # [K, D] x [D, R]
_NT = (((1,), (1,)), ((), ()))          # [K, R] x [D, R] -> [K, D]
_TN = (((0,), (0,)), ((), ()))          # [K, D] x [K, R] -> [D, R]


def _scores(q, k, key_index, low, high, scale):
    """Masked float32 scores of one key block, keys down the sublanes
    and queries along the lanes (a reduction over keys is then a
    vreg-wise one, and a per-query number is a lane vector): q [D, R],
    k [K, D], key_index [K, 1], low / high [1, R] -> [K, R]."""
    s = jax.lax.dot_general(k, q, _NN,
                            preferred_element_type=jnp.float32) * scale
    return jnp.where((key_index >= low) & (key_index <= high), s, _MASKED)


def _flat(env, step, blocks):
    """Where (env, ring step) lies in the flat ``visit`` / ``fetch``; the
    own keys' step reads the last ring block's entry and ignores it."""
    return env * blocks + jnp.minimum(step, blocks - 1)


def _stream(x, z, streams):
    """[streams * d, R] with every stream's rows but ``z``'s zeroed: a
    product over all the rows is then stream ``z``'s alone (and as wide
    as the MXU either way)."""
    if streams == 1:
        return x
    rows = x.shape[0] // streams
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.where((row >= z * rows) & (row < (z + 1) * rows), x,
                     jnp.zeros_like(x))


def _rows(ref, z, streams):
    """Stream ``z``'s rows of a ref that holds the streams' one below the
    other (all of it where there is one)."""
    if streams == 1:
        return (Ellipsis,)
    rows = ref.shape[0] // streams
    return (slice(z * rows, (z + 1) * rows),)


def _forward_kernel(visit_ref, fetch_ref, q_ref, low_ref, high_ref, rk_ref,
                    rv_ref, ri_ref, ok_ref, ov_ref, oi_ref, out_ref, lse_ref,
                    m_ref, l_ref, acc_ref, *, scale, blocks, streams):
    del fetch_ref                       # the index maps read it
    env, step = pl.program_id(0), pl.program_id(2)

    @pl.when(step == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def block(k_ref, v_ref, index_ref):
        # A query that has seen no key yet keeps m = _MASKED and gathers
        # weights of exp(0); the first real score's alpha = exp(-1e30)
        # wipes them, and every query sees itself among the own keys,
        # which come last.  Each stream scores the block that one fetch
        # brought and keeps a maximum, a sum and a weighted value of its
        # own.
        for z in range(streams):
            one, wide = _rows(m_ref, z, streams), _rows(acc_ref, z, streams)
            s = _scores(_stream(q_ref[...], z, streams), k_ref[...],
                        index_ref[...], low_ref[...], high_ref[...], scale)
            m_old = m_ref[one]
            m_new = jnp.maximum(m_old, jnp.max(s, axis=0, keepdims=True))
            alpha = jnp.exp(m_old - m_new)
            p = jnp.exp(s - m_new)
            l_ref[one] = alpha * l_ref[one] + jnp.sum(p, axis=0,
                                                      keepdims=True)
            v = v_ref[...]
            acc_ref[wide] = alpha * acc_ref[wide] + jax.lax.dot_general(
                v, p.astype(v.dtype), _TN,
                preferred_element_type=jnp.float32)
            m_ref[one] = m_new

    @pl.when((step < blocks) & (visit_ref[_flat(env, step, blocks)] == 1))
    def _():
        block(rk_ref, rv_ref, ri_ref)

    @pl.when(step == blocks)
    def _():
        block(ok_ref, ov_ref, oi_ref)
        for z in range(streams):
            one, wide = _rows(m_ref, z, streams), _rows(acc_ref, z, streams)
            out_ref[wide] = acc_ref[wide] / l_ref[one]
            lse_ref[one] = m_ref[one] + jnp.log(l_ref[one])


def _backward_kernel(visit_ref, fetch_ref, q_ref, low_ref, high_ref, rk_ref,
                     rv_ref, ri_ref, ok_ref, ov_ref, oi_ref, out_ref,
                     lse_ref, do_ref, dq_ref, dk_ref, dv_ref, delta_ref,
                     dob_ref, acc_ref, *, scale, blocks, streams):
    del fetch_ref
    env, step = pl.program_id(0), pl.program_id(2)

    @pl.when(step == 0)
    def _():
        do = do_ref[...]
        weighed = out_ref[...] * do
        for z in range(streams):
            delta_ref[_rows(delta_ref, z, streams)] = jnp.sum(
                weighed[_rows(do_ref, z, streams)], axis=0, keepdims=True)
        dob_ref[...] = do.astype(dob_ref.dtype)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def block(k_ref, v_ref, index_ref):
        """A stream apiece (the weights, d scores) of one key block,
        both in the compute dtype, after adding its part of d query."""
        k = k_ref[...]
        streamed = []
        for z in range(streams):
            one, wide = _rows(lse_ref, z, streams), _rows(dob_ref, z, streams)
            s = _scores(_stream(q_ref[...], z, streams), k, index_ref[...],
                        low_ref[...], high_ref[...], scale)
            p = jnp.exp(s - lse_ref[one])
            dp = jax.lax.dot_general(v_ref[...], dob_ref[wide], _NN,
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - delta_ref[one]) * scale).astype(k.dtype)
            acc_ref[...] += _stream(jax.lax.dot_general(
                k, ds, _TN, preferred_element_type=jnp.float32), z, streams)
            streamed.append((p.astype(k.dtype), ds))
        return streamed

    @pl.when((step < blocks) & (visit_ref[_flat(env, step, blocks)] == 1))
    def _():
        block(rk_ref, rv_ref, ri_ref)

    @pl.when(step == blocks)
    def _():
        streamed = block(ok_ref, ov_ref, oi_ref)

        def over_streams(term):
            total = term(0, *streamed[0])
            for z in range(1, streams):
                total = total + term(z, *streamed[z])
            return total

        dk_ref[...] = over_streams(lambda z, p, ds: jax.lax.dot_general(
            ds, _stream(q_ref[...], z, streams), _NT,
            preferred_element_type=jnp.float32))
        dv_ref[...] = over_streams(lambda z, p, ds: jax.lax.dot_general(
            p, dob_ref[_rows(dob_ref, z, streams)], _NT,
            preferred_element_type=jnp.float32))
        dq_ref[...] = acc_ref[...]


_VMEM_LIMIT = 96 * 2 ** 20


@functools.partial(jax.jit,
                   static_argnames=("backward", "interpret", "streams"))
def _kernel(operands, residuals=(), *, backward=False, interpret, streams=1):
    """One of the two kernels over ``_operands`` (the backward one also
    over ``residuals``: out, log-sum-exp, d out, queries along lanes),
    grid (env, kv head, key step): the ring's blocks, then the own keys.
    Jitted for the eager caller's sake, who would otherwise compile the
    interpreter's program at every call.  ``streams``: the queries
    (keys) side by side in a head's ``dim``; out, log-sum-exp and d out
    then hold a stream's rows below the other's."""
    q, ring, own_keys = operands[2], operands[5], operands[8]
    batch, kv, dim, rows = q.shape
    own = own_keys.shape[2]
    blocks = operands[0].shape[0] // batch
    block = ring.shape[2] // blocks

    def ring_block(env, step, fetch):
        # a step that skips names the block the last visited step
        # fetched, so nothing moves
        return fetch[_flat(env, step, blocks)]

    def fixed(*shape):                   # one block an (env, kv head)
        return pl.BlockSpec((None, None) + shape,
                            lambda e, h, s, *_: (e, h, 0, 0))

    per_query = pl.BlockSpec((None, 1, rows), lambda e, h, s, *_: (e, 0, 0))
    ring_kv = pl.BlockSpec(
        (None, None, block, dim),
        lambda e, h, s, visit, fetch: (e, h, ring_block(e, s, fetch), 0))
    ring_index = pl.BlockSpec(
        (block, 1), lambda e, h, s, visit, fetch: (ring_block(e, s, fetch), 0))
    own_index = pl.BlockSpec((own, 1), lambda e, h, s, *_: (0, 0))
    in_specs = [fixed(dim, rows), per_query, per_query, ring_kv, ring_kv,
                ring_index, fixed(own, dim), fixed(own, dim), own_index]

    def result(*shape):
        return jax.ShapeDtypeStruct((batch, kv) + shape, jnp.float32)

    if backward:
        kernel = _backward_kernel
        in_specs += [fixed(streams * dim, rows), fixed(streams, rows),
                     fixed(streams * dim, rows)]
        out_specs = [fixed(dim, rows), fixed(own, dim), fixed(own, dim)]
        out_shape = [result(dim, rows), result(own, dim), result(own, dim)]
        scratch = [pltpu.VMEM((streams, rows), jnp.float32),
                   pltpu.VMEM((streams * dim, rows), q.dtype),
                   pltpu.VMEM((dim, rows), jnp.float32)]
    else:
        kernel = _forward_kernel
        out_specs = [fixed(streams * dim, rows), fixed(streams, rows)]
        out_shape = [result(streams * dim, rows), result(streams, rows)]
        scratch = [pltpu.VMEM((streams, rows), jnp.float32),
                   pltpu.VMEM((streams, rows), jnp.float32),
                   pltpu.VMEM((streams * dim, rows), jnp.float32)]
    return pl.pallas_call(
        functools.partial(kernel, scale=1.0 / math.sqrt(dim // streams),
                          blocks=blocks, streams=streams),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(batch, kv, blocks + 1),
            in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret)(*operands, *residuals)


def _query_operands(query, index, episode_start, visit, window):
    """What a blockwise kernel of any cache reads of the block list and
    the queries: (visit, fetch, q [B, kv, D, R], low / high [B, 1, R]),
    and the own keys' index [K, 1] with K, the own keys padded to whole
    lanes.  A query is a lane (lane = g * T + t); padded queries repeat
    the last one's bounds."""
    batch, queries, kv, group, dim = query.shape
    rows = _round_up(group * queries, _LANES)
    own = _round_up(queries, _LANES)
    # a skipped step names the block of the last visited step before it
    # (the first visited one, before any)
    blocks = visit.shape[1]
    at = jnp.where(visit, jnp.arange(blocks, dtype=jnp.int32), -1)
    last = jax.lax.cummax(at, axis=1)
    first = jnp.argmax(visit, axis=1).astype(jnp.int32)
    fetch = jnp.where(last >= 0, last, first[:, None])

    low, high = _bounds(index, episode_start, window)
    high = jnp.broadcast_to(high[None, :], low.shape)

    def per_query(x):                    # [B, T] -> [B, 1, R]
        x = jnp.tile(x, (1, group))
        return jnp.pad(x, ((0, 0), (0, rows - group * queries)),
                       mode="edge")[:, None, :]

    own_index = jnp.pad(index, (0, own - queries),
                        constant_values=_FAR)[:, None]
    return ((visit.astype(jnp.int32).reshape(-1), fetch.reshape(-1),
             _to_lanes(query), per_query(low), per_query(high)),
            own_index, own)


def _operands(query, key, value, ring_keys, ring_values, ring_index, index,
              episode_start, visit, window):
    """What both kernels read, in the order their blocks want:
    ``_query_operands``, then the ring and the own keys and values
    head-major, [B, kv, S, D] and [B, kv, K, D], each with its index
    [S, 1] / [K, 1].  Head-major is the order the compiled step keeps
    the rings in (its decode's products want it), so their transpose is
    a bitcast there, not a copy."""
    queries = query.shape[1]
    shared, own_index, own = _query_operands(query, index, episode_start,
                                             visit, window)

    def keys(x):                         # [B, T, kv, D] -> [B, kv, K, D]
        return jnp.pad(_head_major(x),
                       ((0, 0), (0, 0), (0, own - queries), (0, 0)))

    return shared + (_head_major(ring_keys), _head_major(ring_values),
                     ring_index[:, None], keys(key), keys(value), own_index)


def _head_major(x):
    """[B, n, kv, D] -> [B, kv, n, D]."""
    return jnp.transpose(x, (0, 2, 1, 3))


def _to_lanes(x):
    """[B, T, kv, g, D] -> [B, kv, D, R], zeros in the padding."""
    batch, queries, kv, group, dim = x.shape
    x = jnp.transpose(x, (0, 2, 4, 3, 1)).reshape(
        batch, kv, dim, group * queries)
    return jnp.pad(x, ((0, 0),) * 3 + (
        (0, _round_up(group * queries, _LANES) - group * queries),))


def _from_lanes(x, queries, group):
    """[B, kv, D, R] -> [B, T, kv, g, D]."""
    batch, kv, dim, _ = x.shape
    x = x[..., :group * queries].reshape(batch, kv, dim, group, queries)
    return jnp.transpose(x, (0, 4, 1, 3, 2))


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10, 11))
def _blockwise(query, key, value, ring_keys, ring_values, ring_index, index,
               episode_start, visit, window, interpret, streams):
    """``_attend``'s result for any number of queries, with no score
    outside VMEM.  Its arguments (query [B, T, kv, g, D]) and ``visit``,
    ``visited_blocks`` of them."""
    return _blockwise_fwd(query, key, value, ring_keys, ring_values,
                          ring_index, index, episode_start, visit, window,
                          interpret, streams)[0]


def _blockwise_fwd(query, key, value, ring_keys, ring_values, ring_index,
                   index, episode_start, visit, window, interpret, streams):
    operands = _operands(query, key, value, ring_keys, ring_values,
                         ring_index, index, episode_start, visit, window)
    out, lse = _kernel(operands, interpret=interpret, streams=streams)
    return (_from_lanes(out, query.shape[1], query.shape[3]),
            (operands, out, lse))


def _blockwise_bwd(window, interpret, streams, saved, d_out):
    del window
    operands, out, lse = saved
    queries, group = d_out.shape[1], d_out.shape[3]
    dtype = operands[2].dtype
    dq, dk, dv = _kernel(
        operands, (out, lse, _to_lanes(d_out.astype(jnp.float32))),
        backward=True, interpret=interpret, streams=streams)

    def own_keys_back(x):                # [B, kv, K, D] -> [B, T, kv, D]
        return jnp.transpose(x[:, :, :queries], (0, 2, 1, 3)).astype(dtype)

    # the ring is the agent's state: nothing differentiates it
    return (_from_lanes(dq, queries, group).astype(dtype),
            own_keys_back(dk), own_keys_back(dv)) + (None,) * 6


_blockwise.defvjp(_blockwise_fwd, _blockwise_bwd)


# -- acting: one query an env, the ring's live blocks only --------------------

_DECODE_BLOCK_BYTES = 2 ** 20   # the keys a decode grid step brings, every
                                # key/value head of a block of slots: a step
                                # costs ~0.35 us whatever it moves


def _decode_block(slots: int, slot_bytes: int) -> int:
    """Ring slots a decode grid step: the most whole lane tiles that
    divide the ring and hold ``_DECODE_BLOCK_BYTES`` of keys or fewer (a
    ring none divides, a test's, is one block)."""
    fit = [block for block in range(_LANES, slots + 1, _LANES)
           if slots % block == 0
           and block * slot_bytes <= _DECODE_BLOCK_BYTES]
    return max(fit, default=slots)


# a key/value head at a time, h: [h, R, D] x [h, K, D] -> [h, R, K], and
# [h, R, K] x [h, K, D] -> [h, R, D]
_ROWS_KEYS = (((2,), (2,)), ((0,), (0,)))
_ROWS_VALUES = (((2,), (1,)), ((0,), (0,)))


def _decode_kernel(order_ref, visit_ref, low_ref, high_ref, steps_ref, q_ref,
                   ok_ref, ov_ref, rk_ref, rv_ref, ri_ref, out_ref, m_ref,
                   l_ref, acc_ref, *, scale, blocks):
    """Grid step ``i`` is the (env, ring block) pair ``order[i]``, an
    env's pairs side by side.  Queries down the sublanes, keys along the
    lanes: q [kv, R, D], a block's keys and values [kv, K, D], its scores
    [kv, R, K].  Every query sees itself, so the own key is where an
    env's running maximum, sum and weighted value start."""
    i = pl.program_id(0)
    pair = order_ref[i]
    env = pair // blocks
    first = (i == 0) | (order_ref[jnp.maximum(i - 1, 0)] // blocks != env)
    last = ((i == steps_ref[0] - 1)
            | (order_ref[jnp.minimum(i + 1, steps_ref[0] - 1)] // blocks
               != env))

    @pl.when(first)
    def _():
        q = q_ref[...].astype(jnp.float32)
        m_ref[...] = jnp.sum(q * ok_ref[...][:, None, :], axis=-1,
                             keepdims=True) * scale
        l_ref[...] = jnp.ones_like(l_ref)
        acc_ref[...] = jnp.broadcast_to(ov_ref[...][:, None, :],
                                        acc_ref.shape)

    @pl.when(visit_ref[pair] == 1)
    def _():
        v = rv_ref[...]
        s = jax.lax.dot_general(q_ref[...], rk_ref[...], _ROWS_KEYS,
                                preferred_element_type=jnp.float32) * scale
        key_index = ri_ref[...]                              # [1, K]
        seen = (key_index >= low_ref[env]) & (key_index <= high_ref[0])
        s = jnp.where(seen[None], s, _MASKED)
        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        p = jnp.exp(s - m_new)          # 0 where masked: m is a real score
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p.astype(v.dtype), v, _ROWS_VALUES,
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(last)
    def _():
        out_ref[...] = acc_ref[...] / l_ref[...]


def _decode_order(visit):
    """(order, steps) of ``visit`` [B, blocks]: the flat (env, block)
    pairs the kernel's grid walks, the visited ones in their order and
    block 0 (to skip) of an env that sees none, so that every env has a
    step to start and end in; ``steps`` [1] of them, the rest of
    ``order`` [B * blocks] unused.  A step costs its ~0.35 us whether it
    fetches or skips, so the pairs to skip are not in the grid at all."""
    blocks = visit.shape[1]
    none = ~jnp.any(visit, axis=1, keepdims=True)
    step = visit | (none & (jnp.arange(blocks) == 0)[None, :])
    ends = jnp.cumsum(step.reshape(-1).astype(jnp.int32))
    # step i is the first pair with i + 1 steps up to and with it
    at = jnp.arange(ends.shape[0], dtype=jnp.int32)
    order = jnp.sum((ends[None, :] <= at[:, None]).astype(jnp.int32), axis=1)
    # the unused entries name a pair that exists: an index map may run
    # one step ahead of the grid
    return jnp.minimum(order, ends.shape[0] - 1), ends[-1:]


@functools.partial(jax.jit,
                   static_argnames=("window", "interpret", "streams"))
def _decode(query, key, value, ring_keys, ring_values, ring_index, index,
            episode_start, *, window, interpret, streams=1):
    """``_attend``'s result for one query an env (query [B, 1, kv, g, D]),
    reading only the ring blocks ``decode_visits`` names.  A head's rows
    are its ``g * streams`` queries: stream ``z``'s row keeps its own part
    of ``D`` and zeros elsewhere, so one product over a block gives every
    stream's scores and each row its own softmax.  The rings go to the
    kernel head-major, the order the compiled step keeps them in (the
    update's kernels read them so too): a bitcast there, and the token's
    slot is written after, in place."""
    batch, _, kv, group, dim = query.shape
    slots = ring_keys.shape[1]
    dtype = ring_keys.dtype
    block = _decode_block(slots, kv * dim * dtype.itemsize)
    blocks = slots // block
    visit = decode_visits(ring_index, index, episode_start, window,
                          block)[:, 0]
    order, steps = _decode_order(visit)
    low, high = _bounds(index, episode_start, window)

    real = group * streams
    rows = _round_up(real, 32 // dtype.itemsize)    # whole sublane tiles
    lane = jnp.arange(dim, dtype=jnp.int32) // (dim // streams)
    q = jnp.where(lane[None, :] == jnp.arange(streams)[:, None],
                  query[:, 0, :, :, None, :], jnp.zeros((), query.dtype))
    q = jnp.pad(q.reshape(batch, kv, real, dim),
                ((0, 0), (0, 0), (0, rows - real), (0, 0)))

    def per_env(*shape):
        return pl.BlockSpec(
            (None,) + shape,
            lambda i, order, *_: (order[i] // blocks,) + (0,) * len(shape))

    ring_kv = pl.BlockSpec(
        (None, kv, block, dim),
        lambda i, order, *_: (order[i] // blocks, 0, order[i] % blocks, 0))
    block_index = pl.BlockSpec(
        (1, block), lambda i, order, *_: (0, order[i] % blocks))
    out = pl.pallas_call(
        functools.partial(_decode_kernel, blocks=blocks,
                          scale=1.0 / math.sqrt(dim // streams)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(steps[0],),
            in_specs=[per_env(kv, rows, dim), per_env(kv, dim),
                      per_env(kv, dim), ring_kv, ring_kv, block_index],
            out_specs=per_env(kv, rows, dim),
            scratch_shapes=[pltpu.VMEM((kv, rows, 1), jnp.float32),
                            pltpu.VMEM((kv, rows, 1), jnp.float32),
                            pltpu.VMEM((kv, rows, dim), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((batch, kv, rows, dim), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret)(
            order, visit.astype(jnp.int32).reshape(-1), low[:, 0], high,
            steps, q, key[:, 0].astype(jnp.float32),
            value[:, 0].astype(jnp.float32), _head_major(ring_keys),
            _head_major(ring_values), ring_index[None, :])
    return out[:, :, :real].reshape(batch, 1, kv, group, streams * dim)


def cached_attention(query, key, value, ring_keys, ring_values, ring_index,
                     index, episode_start, window: Optional[int] = None,
                     streams: int = 1):
    """``query`` [B, T, heads, D] and this call's own ``key`` / ``value``
    [B, T, kv, D] against themselves and the cache ``ring_keys`` /
    ``ring_values`` [B, S, kv, D] -> ([B, T, heads * D] in float32,
    what the pass says of itself) (operands in their own dtype, scores,
    softmax and the weighted sum in float32).

    ``ring_index`` [S]: the stream index of the token in each slot
    (``NO_KEY`` where the slot is empty or is one of this call's own
    tokens); ``index`` [T]: this call's tokens' stream indices;
    ``episode_start`` [B, T]: where each query's episode began;
    ``window``: None on a full layer.

    ``key`` / ``value`` and the ring may be another layer's (a layer
    that projects queries only and reads a cache it does not write): a
    ring has as many readers as calls name it, and its owner's own keys
    get a cotangent from each.  ``streams`` > 1 is differential
    attention's two softmaxes over one value: a head's ``D`` holds the
    streams' queries (keys) side by side, and the result
    ``heads * streams * D`` wide each stream's weighted value, for the
    caller to combine.

    One query an env goes through the decode kernel, more go blockwise
    through the update's, and say which share of (env, key block) pairs
    it visited (the ring's blocks some query of the env sees, and the
    own keys) and which share of (env, query, key block) triples the
    decode kernel visits, a decode step a query (the own key a block
    more, and the blocks the decode's own)."""
    from scalable_agent_tpu.parallel.mesh import pallas_interpret

    batch, queries, heads, dim = query.shape
    kv = key.shape[2]
    slots = ring_keys.shape[1]
    query = query.reshape(batch, queries, kv, heads // kv, dim)
    if queries == 1:
        out = _decode(query, key, value, ring_keys, ring_values, ring_index,
                      index, episode_start, window=window,
                      interpret=pallas_interpret(), streams=streams)
        return out.reshape(batch, queries, heads * streams * dim), {}
    visit = visited_blocks(ring_index, index, episode_start, window,
                           _key_block(slots))
    out = _blockwise(query, key, value, jax.lax.stop_gradient(ring_keys),
                     jax.lax.stop_gradient(ring_values), ring_index, index,
                     episode_start, visit, window, pallas_interpret(),
                     streams)
    decode = decode_visits(
        ring_index, index, episode_start, window,
        _decode_block(slots, kv * dim * ring_keys.dtype.itemsize))

    return (out.reshape(batch, queries, heads * streams * dim),
            {"key_blocks_visited_share": _visited_share(visit),
             "decode_key_blocks_visited_share": _visited_share(decode)})


def _visited_share(visit):
    """Of the ring's blocks and one more (the own keys), always seen."""
    seen = jnp.mean(visit.astype(jnp.float32))
    return (seen * visit.shape[-1] + 1.0) / (visit.shape[-1] + 1)


def ring_write(ring, new, written):
    """``ring`` [B, S, ...] with ``new`` [B, T, ...] in the slots of
    stream indices ``written .. written + T - 1`` (slot = index mod S).
    One token (acting) is a slice update in place."""
    slots = ring.shape[1]
    count = new.shape[1]
    if count == 1:
        return jax.lax.dynamic_update_slice_in_dim(
            ring, round_to(new, ring.dtype), written % slots, axis=1)
    at = (written + jnp.arange(count, dtype=jnp.int32)) % slots
    return ring.at[:, at].set(round_to(new, ring.dtype))


def index_write(ring_index, written, count: int):
    """The slots' stream indices after ``count`` tokens from
    ``written``."""
    new = written + jnp.arange(count, dtype=jnp.int32)
    return ring_index.at[new % ring_index.shape[0]].set(new)


# -- a latent cache: one row a token is every head's key and value ------------
#
# Latent attention (MLA) keeps one row of ``D`` numbers a token a layer:
# ``value_dim`` normalised ones, which every head's no-position key and
# value are up-projections of, and the one rotated key all heads share.
# With the up-projection absorbed into the query and taken out of the
# weighted sum (the caller's two products), a head's key IS the row and
# its value the row's first ``value_dim`` numbers: one key/value "head"
# under a group of every query head, a key wider than the value, and a
# softmax scale that is neither width's.  Both passes below take that
# form, so no key or value of a past token is ever made, in HBM or in
# VMEM; a block of the ring is fetched once and serves as both.  The
# block lists and the masks are the ones above.
#
# The ring lies ``[B, D, slots]``, a token a column: a row of 576
# numbers is no whole number of lanes, and the chip keeps a ``[B, slots,
# 576]`` buffer slots-minor whatever the program says, so a kernel that
# wants it rows-major pays a copy of every ring into the step and one
# out of it (2 GB more at the peak; AOT for a v5e, PR 38).  Keys along
# the lanes is also how the decode's scores lie.
#
# The layout's price is the acting step's write: a token's 576 numbers
# lie one in each of 576 rows, so as a slice update they are 18,432
# elements an env batch of 32, each in a tile of its own, and XLA's took
# 0.081 ms a layer a decode step for 36 KB of new data (PR 38's trace).
# The chip moves whole ``(sublanes, 128)`` tiles at its memory's rate,
# so ``latent_ring_write`` moves the ONE lane tile that holds the slot:
# ``_latent_slot_write`` brings ``[envs, D, 128]`` (36 bfloat16 tiles an
# env, 147 KB), replaces lane ``slot % 128`` by a select against a lane
# iota and writes the block back into the same buffer (the ring aliased
# to the kernel's result): 9.4 MB a call in place of 18,432 elements one
# at a time, the same bytes in the same slots.  More tokens than one
# (the update) keep the scatter.

_LATENT_LANES = 2304            # query lanes (heads x queries) a grid step of
                                # the update: its scores are [block, lanes]


def _latent_scores(q, rows, key_index, low, high, scale):
    """``_scores`` of a block of latent rows that lie a token a column:
    q [D, R], rows [D, K], key_index [K, 1], low / high [1, R] -> [K,
    R]."""
    s = jax.lax.dot_general(rows, q, _TN,
                            preferred_element_type=jnp.float32) * scale
    return jnp.where((key_index >= low) & (key_index <= high), s, _MASKED)


def _latent_forward_kernel(visit_ref, fetch_ref, q_ref, low_ref, high_ref,
                           ring_ref, ri_ref, own_ref, oi_ref, out_ref,
                           lse_ref, m_ref, l_ref, acc_ref, *, scale, blocks,
                           value_dim):
    del fetch_ref
    env, step = pl.program_id(0), pl.program_id(2)

    @pl.when(step == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def block(rows_ref, index_ref):
        rows = rows_ref[...]                                 # [D, K]
        s = _latent_scores(q_ref[...], rows, index_ref[...], low_ref[...],
                           high_ref[...], scale)
        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=0, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=0, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            rows[:value_dim], p.astype(rows.dtype), _NN,
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when((step < blocks) & (visit_ref[_flat(env, step, blocks)] == 1))
    def _():
        block(ring_ref, ri_ref)

    @pl.when(step == blocks)
    def _():
        block(own_ref, oi_ref)
        out_ref[...] = (acc_ref[...] / l_ref[...]).astype(out_ref.dtype)
        lse_ref[...] = m_ref[...] + jnp.log(l_ref[...])


def _latent_backward_kernel(visit_ref, fetch_ref, q_ref, low_ref, high_ref,
                            ring_ref, ri_ref, own_ref, oi_ref, out_ref,
                            lse_ref, do_ref, dq_ref, dl_ref, delta_ref,
                            acc_ref, *, scale, blocks, value_dim):
    del fetch_ref
    env, step = pl.program_id(0), pl.program_id(2)

    @pl.when(step == 0)
    def _():
        delta_ref[...] = jnp.sum(
            out_ref[...].astype(jnp.float32)
            * do_ref[...].astype(jnp.float32), axis=0, keepdims=True)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def block(rows_ref, index_ref):
        """(the weights, d scores) of one block of rows, both in the
        compute dtype, after adding its part of d query."""
        rows = rows_ref[...]                                 # [D, K]
        s = _latent_scores(q_ref[...], rows, index_ref[...], low_ref[...],
                           high_ref[...], scale)
        p = jnp.exp(s - lse_ref[...])
        dp = jax.lax.dot_general(rows[:value_dim], do_ref[...], _TN,
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[...]) * scale).astype(rows.dtype)
        acc_ref[...] += jax.lax.dot_general(
            rows, ds, _NN, preferred_element_type=jnp.float32)
        return p.astype(rows.dtype), ds

    @pl.when((step < blocks) & (visit_ref[_flat(env, step, blocks)] == 1))
    def _():
        block(ring_ref, ri_ref)

    @pl.when(step == blocks)
    def _():
        p, ds = block(own_ref, oi_ref)
        # an own row is a key and, in its first numbers, a value: one
        # array collects both cotangents
        dl_ref[...] = jax.lax.dot_general(
            q_ref[...], ds, _NT, preferred_element_type=jnp.float32)
        dl_ref[:value_dim] += jax.lax.dot_general(
            do_ref[...], p, _NT, preferred_element_type=jnp.float32)
        dq_ref[...] = acc_ref[...].astype(dq_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("backward", "interpret", "value_dim", "scale"))
def _latent_kernel(operands, residuals=(), *, backward=False, interpret,
                   value_dim, scale):
    """One of the two latent kernels over ``_latent_operands`` (the
    backward one also over out, log-sum-exp and d out, queries along
    lanes), grid (env, tile of query heads, key step): the ring's
    blocks, then the own rows.  Every tile of heads reads the same
    rows.  What the caller rounds to the compute dtype at once (out, d
    query) leaves the kernel in it: at 32 heads of 576 a float32 copy is
    0.6 GB a layer."""
    q, ring, own_rows = operands[2], operands[5], operands[7]
    batch, tiles, dim, rows = q.shape
    own = own_rows.shape[2]
    blocks = operands[0].shape[0] // batch
    block = ring.shape[2] // blocks

    def ring_block(env, step, fetch):
        return fetch[_flat(env, step, blocks)]

    def fixed(*shape):                   # one block an (env, tile)
        return pl.BlockSpec((None, None) + shape,
                            lambda e, h, s, *_: (e, h, 0, 0))

    per_query = pl.BlockSpec((None, 1, rows), lambda e, h, s, *_: (e, 0, 0))
    in_specs = [
        fixed(dim, rows), per_query, per_query,
        pl.BlockSpec((None, dim, block), lambda e, h, s, visit, fetch:
                     (e, 0, ring_block(e, s, fetch))),
        pl.BlockSpec((block, 1), lambda e, h, s, visit, fetch:
                     (ring_block(e, s, fetch), 0)),
        pl.BlockSpec((None, dim, own), lambda e, h, s, *_: (e, 0, 0)),
        pl.BlockSpec((own, 1), lambda e, h, s, *_: (0, 0))]

    def result(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct((batch, tiles) + shape, dtype)

    if backward:
        kernel, name = _latent_backward_kernel, "latent_update_bwd"
        in_specs += [fixed(value_dim, rows), fixed(1, rows),
                     fixed(value_dim, rows)]
        out_specs = [fixed(dim, rows), fixed(dim, own)]
        out_shape = [result(dim, rows, dtype=q.dtype), result(dim, own)]
        scratch = [pltpu.VMEM((1, rows), jnp.float32),
                   pltpu.VMEM((dim, rows), jnp.float32)]
    else:
        kernel, name = _latent_forward_kernel, "latent_update_fwd"
        out_specs = [fixed(value_dim, rows), fixed(1, rows)]
        out_shape = [result(value_dim, rows, dtype=q.dtype), result(1, rows)]
        scratch = [pltpu.VMEM((1, rows), jnp.float32),
                   pltpu.VMEM((1, rows), jnp.float32),
                   pltpu.VMEM((value_dim, rows), jnp.float32)]
    return pl.pallas_call(
        functools.partial(kernel, scale=scale, blocks=blocks,
                          value_dim=value_dim),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(batch, tiles, blocks + 1),
            in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=name)(*operands, *residuals)


def _head_tile(heads: int, queries: int) -> int:
    """Query heads a grid step of the update: the most that divide
    ``heads`` and keep the step's lanes within ``_LATENT_LANES``."""
    fit = [tile for tile in range(1, heads + 1)
           if heads % tile == 0
           and _round_up(tile * queries, _LANES) <= _LATENT_LANES]
    return max(fit, default=1)


def _latent_operands(query, latent, ring, ring_index, index, episode_start,
                     visit):
    """``_operands`` for the latent kernels: ``_query_operands`` (q [B,
    tiles, D, R]), then the ring [B, D, S] as it lies and the own rows
    [B, D, K], each with its index."""
    queries = query.shape[1]
    shared, own_index, own = _query_operands(query, index, episode_start,
                                             visit, None)
    return shared + (ring, ring_index[:, None],
                     jnp.pad(jnp.swapaxes(latent, 1, 2),
                             ((0, 0), (0, 0), (0, own - queries))),
                     own_index)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _latent_blockwise(query, latent, ring, ring_index, index, episode_start,
                      visit, interpret, value_dim, scale):
    """Latent attention for any number of queries, with no score outside
    VMEM: query [B, T, tiles, g, D], the call's own rows ``latent`` [B,
    T, D], ``ring`` [B, D, S] -> [B, T, tiles, g, value_dim]."""
    return _latent_blockwise_fwd(query, latent, ring, ring_index, index,
                                 episode_start, visit, interpret, value_dim,
                                 scale)[0]


def _latent_blockwise_fwd(query, latent, ring, ring_index, index,
                          episode_start, visit, interpret, value_dim, scale):
    operands = _latent_operands(query, latent, ring, ring_index, index,
                                episode_start, visit)
    out, lse = _latent_kernel(operands, interpret=interpret,
                              value_dim=value_dim, scale=scale)
    return (_from_lanes(out, query.shape[1], query.shape[3]),
            (operands, out, lse))


def _latent_blockwise_bwd(interpret, value_dim, scale, saved, d_out):
    operands, out, lse = saved
    queries, group = d_out.shape[1], d_out.shape[3]
    dtype = operands[2].dtype
    dq, dl = _latent_kernel(
        operands, (out, lse, _to_lanes(d_out.astype(dtype))),
        backward=True, interpret=interpret, value_dim=value_dim, scale=scale)
    # every tile of heads read the own rows; the ring is the agent's
    # state: nothing differentiates it
    own = jnp.swapaxes(jnp.sum(dl, axis=1)[..., :queries], 1, 2)
    return (_from_lanes(dq, queries, group),
            own.astype(dtype)) + (None,) * 5


_latent_blockwise.defvjp(_latent_blockwise_fwd, _latent_blockwise_bwd)


def _latent_decode_kernel(order_ref, visit_ref, low_ref, high_ref, steps_ref,
                          q_ref, own_ref, ring_ref, ri_ref, out_ref, m_ref,
                          l_ref, acc_ref, *, scale, blocks, value_dim):
    """``_decode_kernel`` over latent rows: every query head of an env
    is a row of q [R, D], a block of the ring [D, K] is the keys and, in
    its first numbers, the values."""
    i = pl.program_id(0)
    pair = order_ref[i]
    env = pair // blocks
    first = (i == 0) | (order_ref[jnp.maximum(i - 1, 0)] // blocks != env)
    last = ((i == steps_ref[0] - 1)
            | (order_ref[jnp.minimum(i + 1, steps_ref[0] - 1)] // blocks
               != env))

    @pl.when(first)
    def _():
        q = q_ref[...].astype(jnp.float32)
        own = own_ref[...]                                   # [1, D]
        m_ref[...] = jnp.sum(q * own, axis=-1, keepdims=True) * scale
        l_ref[...] = jnp.ones_like(l_ref)
        acc_ref[...] = jnp.broadcast_to(own[:, :value_dim], acc_ref.shape)

    @pl.when(visit_ref[pair] == 1)
    def _():
        rows = ring_ref[...]                                 # [D, K]
        s = jax.lax.dot_general(q_ref[...], rows, _NN,
                                preferred_element_type=jnp.float32) * scale
        key_index = ri_ref[...]                              # [1, K]
        seen = (key_index >= low_ref[env]) & (key_index <= high_ref[0])
        s = jnp.where(seen, s, _MASKED)
        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        p = jnp.exp(s - m_new)          # 0 where masked: m is a real score
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p.astype(rows.dtype), rows[:value_dim], _NT,
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(last)
    def _():
        out_ref[...] = acc_ref[...] / l_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("interpret", "value_dim", "scale"))
def _latent_decode(query, latent, ring, ring_index, index, episode_start, *,
                   interpret, value_dim, scale):
    """Latent attention for one query an env (query [B, 1, H, D]),
    reading only the ring blocks ``decode_visits`` names, each once."""
    batch, _, heads, dim = query.shape
    slots = ring.shape[2]
    dtype = ring.dtype
    block = _decode_block(slots, dim * dtype.itemsize)
    blocks = slots // block
    visit = decode_visits(ring_index, index, episode_start, None,
                          block)[:, 0]
    order, steps = _decode_order(visit)
    low, high = _bounds(index, episode_start, None)
    rows = _round_up(heads, 32 // dtype.itemsize)    # whole sublane tiles
    q = jnp.pad(query[:, 0], ((0, 0), (0, rows - heads), (0, 0)))

    def per_env(*shape):
        return pl.BlockSpec(
            (None,) + shape,
            lambda i, order, *_: (order[i] // blocks,) + (0,) * len(shape))

    out = pl.pallas_call(
        functools.partial(_latent_decode_kernel, blocks=blocks, scale=scale,
                          value_dim=value_dim),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(steps[0],),
            in_specs=[per_env(rows, dim), per_env(1, dim),
                      pl.BlockSpec((None, dim, block), lambda i, order, *_:
                                   (order[i] // blocks, 0,
                                    order[i] % blocks)),
                      pl.BlockSpec((1, block), lambda i, order, *_:
                                   (0, order[i] % blocks))],
            out_specs=per_env(rows, value_dim),
            scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, value_dim), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((batch, rows, value_dim),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="latent_decode")(
            order, visit.astype(jnp.int32).reshape(-1), low[:, 0], high,
            steps, q, latent.astype(jnp.float32), ring, ring_index[None, :])
    return out[:, None, :heads]


def latent_ring_slots(needed: int, slot_bytes: int) -> int:
    """The slots of a latent ring that has to hold ``needed``: the next
    multiple of the most lane tiles a decode grid step may bring
    (``_DECODE_BLOCK_BYTES``), so that a step brings that many.  A row
    of 1,152 bytes has no power of two of them in a MiB, and a ring of
    just ``needed`` slots may share few lane tiles with any block (82
    tiles: blocks of 2), each grid step at its fixed cost.  A ring one
    step brings whole stays as it is."""
    most = _DECODE_BLOCK_BYTES // slot_bytes // _LANES * _LANES
    return needed if needed <= most else _round_up(needed, most)


def latent_attention(query, latent, ring, ring_index, index, episode_start,
                     value_dim: int, scale: float):
    """``cached_attention`` through a cache of latent rows: ``query`` [B,
    T, heads, D] (a head's up-projection absorbed: its part over the
    row's first ``value_dim`` numbers, its rotated part over the rest)
    and this call's own rows ``latent`` [B, T, D] against themselves and
    the ring [B, D, S] (a token a column) -> ([B, T, heads, value_dim]:
    each head's weighted sum of rows' first ``value_dim`` numbers, for
    the caller to up-project — float32 from the decode, the compute
    dtype from the update, whose caller rounds it at once; the pass's
    visited shares, as ``cached_attention`` gives them).  Every layer of
    such a cache is a full one (no window).  ``scale`` multiplies the
    scores (the model's own key width's, which no width here is).  The
    own rows get the cotangent of both their uses; the ring's are
    constants."""
    from scalable_agent_tpu.parallel.mesh import pallas_interpret

    batch, queries, heads, dim = query.shape
    slots = ring.shape[2]
    if queries == 1:
        return _latent_decode(
            query, latent, ring, ring_index, index, episode_start,
            interpret=pallas_interpret(), value_dim=value_dim,
            scale=scale), {}
    visit = visited_blocks(ring_index, index, episode_start, None,
                           _key_block(slots))
    tile = _head_tile(heads, queries)
    out = _latent_blockwise(
        query.reshape(batch, queries, heads // tile, tile, dim), latent,
        jax.lax.stop_gradient(ring), ring_index, index, episode_start,
        visit, pallas_interpret(), value_dim, scale)
    decode = decode_visits(
        ring_index, index, episode_start, None,
        _decode_block(slots, dim * ring.dtype.itemsize))
    return (out.reshape(batch, queries, heads, value_dim),
            {"key_blocks_visited_share": _visited_share(visit),
             "decode_key_blocks_visited_share": _visited_share(decode)})


_SLOT_BLOCK_BYTES = 5 * 2 ** 18    # of a ring a grid step of the slot write
# brings and writes back: 8 envs' tiles of 147 KB at the cell's widths,
# four steps over 32 envs.  On a v5e the write alone reads 18.5 / 17.5 /
# 16.4 / 20.0 us at 4 / 8 / 16 / 32 envs a step (XLA's slice update
# of this layout took 80, in the step; PR 43): 8 and 16 are within 1.4 ms
# of a 1,543 ms step, and 8 is the form the cell was measured with.


def _latent_slot_write_kernel(slot_ref, columns_ref, ring_ref, out_ref):
    """The lane tile of a block of envs with one lane replaced:
    ``ring_ref`` / ``out_ref`` [b, D, 128] (the same memory),
    ``columns_ref`` [D, b] the envs' new rows, each a column."""
    here = jax.lax.broadcasted_iota(
        jnp.int32, ring_ref.shape[1:], 1) == slot_ref[0] % _LANES
    for env in range(ring_ref.shape[0]):
        out_ref[env] = jnp.where(here, columns_ref[:, env:env + 1],
                                 ring_ref[env])


@functools.partial(jax.jit, static_argnames=("interpret",))
def _latent_slot_write(ring, rows, slot, *, interpret):
    """``ring`` [B, D, S] with ``rows`` [B, D] (the ring's dtype) in
    column ``slot``, in place: the kernel's result is the ring's own
    buffer, of which it touches lane tile ``slot // 128`` alone."""
    batch, dim, _ = ring.shape
    tile = dim * _LANES * ring.dtype.itemsize
    envs = max((envs for envs in range(1, batch + 1) if batch % envs == 0
                and envs * tile <= _SLOT_BLOCK_BYTES), default=1)
    steps = batch // envs

    def lane_tile(step, slot):
        return step, 0, slot[0] // _LANES

    return pl.pallas_call(
        _latent_slot_write_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(steps,),
            in_specs=[pl.BlockSpec((None, dim, envs),
                                   lambda step, slot: (step, 0, 0)),
                      pl.BlockSpec((envs, dim, _LANES), lane_tile)],
            out_specs=pl.BlockSpec((envs, dim, _LANES), lane_tile)),
        out_shape=jax.ShapeDtypeStruct(ring.shape, ring.dtype),
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="latent_slot_write")(
            slot.astype(jnp.int32).reshape(1),
            jnp.swapaxes(rows.reshape(steps, envs, dim), 1, 2), ring)


def _moves_whole_tiles(ring) -> bool:
    """Whether a column of ``ring`` [B, D, S] lies in whole tiles of one
    lane tile: the slots whole lane tiles, the rows whole sublane tiles
    of the dtype (8 rows of 32 bits, 16 of 16)."""
    return (ring.shape[2] % _LANES == 0
            and ring.shape[1] % (32 // ring.dtype.itemsize) == 0)


_slot_writes = [0, 0]    # one-token latent writes traced; those in the kernel


def _count_slot_write(kernel: bool):
    """Trace time: one more T = 1 latent write, through the kernel or
    not (``attention/latent_slot_kernel_share``)."""
    from scalable_agent_tpu.obs.registry import get_registry

    _slot_writes[0] += 1
    _slot_writes[1] += kernel
    get_registry().gauge(
        "attention/latent_slot_kernel_share",
        "share of the one-token latent ring writes traced that move the "
        "slot's one lane tile in place (ops/attention.py "
        "_latent_slot_write) and not XLA's element-wise slice update: 1.0 "
        "where every ring's shape fits the kernel").set(
            _slot_writes[1] / _slot_writes[0])


def latent_ring_write(ring, rows, written):
    """``ring_write`` into a latent ring [B, D, S]: ``rows`` [B, T, D] in
    the columns of stream indices ``written .. written + T - 1``.  One
    token into a ring of whole tiles moves the slot's lane tile
    (``_latent_slot_write``); one into any other ring is a slice update,
    more are a scatter.  The same bytes in the same slots each way."""
    from scalable_agent_tpu.parallel.mesh import pallas_interpret

    slots = ring.shape[2]
    count = rows.shape[1]
    rows = round_to(rows, ring.dtype)
    if count == 1:
        kernel = _moves_whole_tiles(ring)
        _count_slot_write(kernel)
        if kernel:
            return _latent_slot_write(ring, rows[:, 0], written % slots,
                                      interpret=pallas_interpret())
        return jax.lax.dynamic_update_slice_in_dim(
            ring, jnp.swapaxes(rows, 1, 2), written % slots, axis=2)
    at = (written + jnp.arange(count, dtype=jnp.int32)) % slots
    return ring.at[:, :, at].set(jnp.swapaxes(rows, 1, 2))
