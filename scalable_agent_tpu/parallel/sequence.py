"""Sequence-parallel V-trace: the linear recurrence sharded over time.

Long-context handling (SURVEY §5.7): the reference's only treatment of
the time dimension is a sequential in-graph LSTM unroll and a
CPU-pinned sequential V-trace scan (reference: experiment.py:228-237,
387-397; vtrace.py:250-262).  Here the V-trace recurrence

    acc_s = delta_s + a_s * acc_{s+1},   acc_T = 0

is distributed over a mesh axis carrying the TIME dimension, the same
decomposition ring-attention-style context parallelism uses for
attention: each shard owns a contiguous time chunk, computes its local
affine composition, exchanges ONE composed (A, B) pair per shard over
the axis (all_gather — S pairs of [B]-vectors, a few KB), derives its
boundary accumulator from the suffix composition, and finishes locally.
Cross-shard traffic is O(S * B) floats regardless of T — the recurrence
itself never leaves the chip.

The heavy elementwise work (rhos, clipping, deltas) happens OUTSIDE the
shard_map in plain jnp, so XLA shards it over the same time axis with
zero communication; only the recurrence needs the hand-written
decomposition.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from scalable_agent_tpu.ops.vtrace import (
    VTraceReturns,
    compose_affine,
    elementwise_epilogue,
    elementwise_prologue,
)


def _chunk_recurrence(a, b, axis_name):
    """shard_map body: solve the reverse recurrence over time chunks.

    a, b: the LOCAL [T/S, B...] chunk.  Returns (acc, acc_next) where
    acc_next[s] = acc[s+1] globally (the next chunk's first accumulator
    at the chunk boundary).
    """
    # Composed suffix maps within the chunk: (A_s, B_s) such that
    # acc_s = B_s + A_s * x where x is the accumulator just past the
    # chunk end.
    comp_a, comp_b = lax.associative_scan(compose_affine, (a, b), reverse=True)

    # One composed pair per shard (its first element composes the whole
    # chunk); gather S of them and fold the suffix on every shard.
    all_a = lax.all_gather(comp_a[0], axis_name)    # [S, B...]
    all_b = lax.all_gather(comp_b[0], axis_name)

    # suffix[j] = (f_j o f_{j+1} o ... o f_{S-1})(0): reverse scan over
    # the shard axis (S is tiny — this is S log S work on [B] vectors).
    _, suffix = lax.associative_scan(
        compose_affine, (all_a, all_b), reverse=True, axis=0)
    # boundary for shard j = acc at the first element of shard j+1
    # = suffix[j+1], with suffix[S] = 0.
    suffix_padded = jnp.concatenate(
        [suffix[1:], jnp.zeros_like(suffix[:1])], axis=0)
    my = lax.axis_index(axis_name)
    boundary = jnp.take(suffix_padded, my, axis=0)  # [B...]

    acc = comp_b + comp_a * boundary[None]
    # acc_next: shift within the chunk; the last position's successor is
    # exactly the boundary accumulator.
    acc_next = jnp.concatenate([acc[1:], boundary[None]], axis=0)
    return acc, acc_next


def from_importance_weights_sharded(
    mesh: Mesh,
    log_rhos,
    discounts,
    rewards,
    values,
    bootstrap_value,
    clip_rho_threshold: Optional[float] = 1.0,
    clip_pg_rho_threshold: Optional[float] = 1.0,
    seq_axis: str = "data",
) -> VTraceReturns:
    """V-trace with the time dimension sharded over ``mesh[seq_axis]``.

    Inputs as ops/vtrace.from_importance_weights ([T, B...] etc.); T
    must divide evenly by the axis size.  Numerics match the
    single-device associative path (same composition order).
    """
    log_rhos = jnp.asarray(log_rhos, jnp.float32)
    discounts = jnp.asarray(discounts, jnp.float32)
    rewards = jnp.asarray(rewards, jnp.float32)
    values = jnp.asarray(values, jnp.float32)
    bootstrap_value = jnp.asarray(bootstrap_value, jnp.float32)

    seq_size = mesh.shape[seq_axis]
    if log_rhos.shape[0] % seq_size:
        raise ValueError(
            f"unroll length {log_rhos.shape[0]} must divide evenly over "
            f"sequence axis {seq_axis!r} of size {seq_size}")

    a, deltas, rhos, values_t_plus_1 = elementwise_prologue(
        log_rhos, discounts, rewards, values, bootstrap_value,
        clip_rho_threshold)

    ndim = log_rhos.ndim
    # Keep the batch dimension sharded over 'data' while time shards
    # over the seq axis: on a dp x sp mesh the inputs then move WITHOUT
    # any batch all-gather (each device holds its [T/S, B/D] tile and
    # computes only its shard's recurrence).  When the caller uses the
    # data axis itself as the time axis (standalone/demo usage), the
    # batch stays unsharded — an axis can appear only once in a spec.
    batch_axis = ("data" if ndim >= 2 and seq_axis != "data"
                  and "data" in mesh.axis_names else None)
    trailing = [None] * max(0, ndim - 2)
    if ndim >= 2:
        time_sharded = PartitionSpec(seq_axis, batch_axis, *trailing)
    else:
        time_sharded = PartitionSpec(seq_axis)
    fn = jax.shard_map(
        functools.partial(_chunk_recurrence, axis_name=seq_axis),
        mesh=mesh,
        in_specs=(time_sharded, time_sharded),
        out_specs=(time_sharded, time_sharded),
    )
    constrain = lambda x: lax.with_sharding_constraint(
        x, NamedSharding(mesh, time_sharded))
    acc, acc_next = fn(constrain(a), constrain(deltas))

    vs = acc + values
    vs_t_plus_1 = acc_next + values_t_plus_1
    pg_advantages = elementwise_epilogue(
        rhos, discounts, rewards, values, vs_t_plus_1,
        clip_pg_rho_threshold)
    return VTraceReturns(
        vs=lax.stop_gradient(vs),
        pg_advantages=lax.stop_gradient(pg_advantages))
