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
a query may see).  Every shape is fixed: how full the cache is changes
a mask and no trip count.

The score tensor ``[B, heads, T, S + T]`` in float32 is 2.4 GB a layer
at 32 envs x 257 queries x 2,561 keys, so the batch is taken ``block``
envs at a time under ``jax.checkpoint``: the backward pass recomputes a
block's scores instead of keeping every block's.  Grouped queries: the
``heads // kv_heads`` query heads that share a key/value head are one
matmul's rows.
"""

import math
from typing import Optional

import jax
import jax.numpy as jnp


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


def score_block(batch: int, heads: int, queries: int, keys: int,
                budget_bytes: int = 256 * 2 ** 20) -> int:
    """Envs per block: the largest divisor of ``batch`` whose float32
    scores fit ``budget_bytes`` (one env where none does)."""
    per_env = heads * queries * keys * 4
    for block in range(batch, 0, -1):
        if batch % block == 0 and block * per_env <= budget_bytes:
            return block
    return 1


def _attend(query, key, value, ring_keys, ring_values, ring_index, index,
            episode_start, window):
    """One block of envs.  query [b, T, kv, g, D]; key/value [b, T, kv,
    D]; ring_* [b, S, kv, D]; ring_index [S]; index [T];
    episode_start [b, T]."""
    dtype = query.dtype
    scale = 1.0 / math.sqrt(query.shape[-1])

    def scores(keys):
        return jnp.einsum("btkgd,bskd->bkgts", query, keys,
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
    logits = jnp.where(mask[:, None, None], logits, _MASKED)
    # every query sees itself, so no row is all masked
    weights = round_to(jax.nn.softmax(logits, axis=-1), dtype)
    return (jnp.einsum("bkgts,bskd->btkgd", weights[..., :slots],
                       ring_values, preferred_element_type=jnp.float32)
            + jnp.einsum("bkgts,bskd->btkgd", weights[..., slots:], value,
                         preferred_element_type=jnp.float32))


def cached_attention(query, key, value, ring_keys, ring_values, ring_index,
                     index, episode_start, window: Optional[int] = None,
                     block: Optional[int] = None):
    """``query`` [B, T, heads, D] and this call's own ``key`` / ``value``
    [B, T, kv, D] against themselves and the cache ``ring_keys`` /
    ``ring_values`` [B, S, kv, D] -> [B, T, heads * D] in float32
    (operands in their own dtype, scores, softmax and the weighted sum
    in float32).

    ``ring_index`` [S]: the stream index of the token in each slot
    (``NO_KEY`` where the slot is empty or is one of this call's own
    tokens); ``index`` [T]: this call's tokens' stream indices;
    ``episode_start`` [B, T]: where each query's episode began;
    ``window``: None on a full layer."""
    batch, queries, heads, dim = query.shape
    kv = key.shape[2]
    query = query.reshape(batch, queries, kv, heads // kv, dim)
    if block is None:
        block = score_block(batch, heads, queries,
                            ring_keys.shape[1] + queries)
    if block >= batch:
        out = _attend(query, key, value, ring_keys, ring_values, ring_index,
                      index, episode_start, window)
        return out.reshape(batch, queries, heads * dim)

    def blocks(x):
        return x.reshape((batch // block, block) + x.shape[1:])

    @jax.checkpoint
    def one(xs):
        q, k, v, rk, rv, start = xs
        return _attend(q, k, v, rk, rv, ring_index, index, start, window)

    out = jax.lax.map(one, tuple(blocks(x) for x in (
        query, key, value, ring_keys, ring_values, episode_start)))
    return out.reshape(batch, queries, heads * dim)


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
