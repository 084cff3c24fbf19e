"""GPipe-style pipeline parallelism over a mesh axis (prototype).

Design rationale and scoping: docs/pipeline_parallelism.md (SURVEY
§2.5 scopes PP to a design note — the reference has none, and the
IMPALA-size net never needs it; this module makes the design concrete
and testable rather than prose).

The scheme is the classic synchronous GPipe schedule expressed as pure
SPMD — no runtime, no scheduler threads, no new concepts beyond what
the rest of `parallel/` already uses:

- every device holds ONE stage's params (leading-axis sharding over the
  pipeline axis);
- a `lax.scan` over S + M - 1 ticks drives all stages every tick;
  stage-boundary activations hop to the next device with ONE
  `lax.ppermute` (a neighbor transfer — the cheapest ICI collective);
- stage s computes microbatch m at tick t = s + m; ticks outside that
  window are pipeline bubble (the compute runs on stale data and is
  masked out at collection), giving the textbook M/(M+S-1) utilization;
- the backward pass is `jax.grad` through the program: XLA
  differentiates `ppermute` into the inverse permutation, yielding the
  reverse pipeline schedule automatically.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec


def gpipe_spmd(mesh, stage_fn, stage_params, microbatches,
               axis: str = "stage"):
    """Run ``x -> stage_fn(p_{S-1}, ... stage_fn(p_0, x))`` as a
    microbatched pipeline over ``mesh[axis]``.

    stage_fn: (params_one_stage, x [mb, ...]) -> y [mb, ...] — stages
      must be shape-preserving (equal boundary widths), the usual GPipe
      contract.
    stage_params: pytree whose leaves carry a leading [S] stage axis.
    microbatches: [M, mb, ...] array, replicated.

    Returns [M, mb, ...]: the last stage's outputs per microbatch,
    replicated over the mesh.  Differentiable in ``stage_params`` and
    ``microbatches``.
    """
    num_stages = mesh.shape[axis]
    num_micro = microbatches.shape[0]
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            stage_params)[0]:
        if leaf.ndim == 0 or leaf.shape[0] != num_stages:
            raise ValueError(
                f"stage_params leaf {jax.tree_util.keystr(path)} has "
                f"shape {getattr(leaf, 'shape', ())} but every leaf "
                f"needs a leading (stage) dim of {num_stages} (one "
                f"stage per device on mesh axis {axis!r}, exactly)")

    def spmd(params_local, xs):
        # params_local leaves arrive as [1, ...] (their stage's slice).
        params_one = jax.tree_util.tree_map(lambda p: p[0], params_local)
        stage = lax.axis_index(axis)
        perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]

        def tick(carry, t):
            # ``carry`` is the activation handed over by the previous
            # stage at the previous tick; stage 0 instead injects
            # microbatch t (clipped — out-of-window ticks are bubble).
            inbound = carry
            m = jnp.clip(t, 0, num_micro - 1)
            x = jnp.where(stage == 0, xs[m], inbound)
            y = stage_fn(params_one, x)
            handoff = lax.ppermute(y, axis, perm)
            return handoff, y

        # The carry must be typed as device-varying over the pipeline
        # axis (ppermute's output is), or the scan carry types mismatch.
        zero = lax.pcast(jnp.zeros_like(xs[0]), axis, to="varying")
        _, ys = lax.scan(tick, zero, jnp.arange(num_stages + num_micro - 1))

        # The last stage emits microbatch m at tick t = (S-1) + m; mask
        # everything else and psum-broadcast so the result is replicated
        # (every other stage contributes zeros).
        ticks = num_stages - 1 + jnp.arange(num_micro)
        outs = ys[ticks]  # [M, mb, ...] (only valid on the last stage)
        # SELECT rather than multiply-by-mask: bubble-tick activations
        # may be non-finite for some stage_fns, and 0 * inf would
        # poison the psum with NaN.
        contribution = jnp.where(stage == num_stages - 1, outs,
                                 jnp.zeros_like(outs))
        return lax.psum(contribution, axis)

    stage_sharded = jax.tree_util.tree_map(
        lambda p: PartitionSpec(axis, *([None] * (p.ndim - 1))),
        stage_params)
    fn = jax.shard_map(
        spmd, mesh=mesh,
        in_specs=(stage_sharded, PartitionSpec()),
        out_specs=PartitionSpec(),
    )
    constrained = jax.tree_util.tree_map(
        lambda p, s: lax.with_sharding_constraint(
            p, NamedSharding(mesh, s)),
        stage_params, stage_sharded)
    return fn(constrained, microbatches)


def sequential_reference(stage_fn, stage_params, microbatches):
    """The pipeline's ground truth: compose all S stages sequentially
    per microbatch (what gpipe_spmd must reproduce exactly)."""
    num_stages = jax.tree_util.tree_leaves(stage_params)[0].shape[0]

    def apply_all(x):
        for s in range(num_stages):
            params_s = jax.tree_util.tree_map(
                lambda p, s=s: p[s], stage_params)
            x = stage_fn(params_s, x)
        return x

    return jax.vmap(apply_all)(microbatches)


def pipeline_utilization(num_stages: int, num_micro: int) -> float:
    """The GPipe bubble bound: fraction of device-ticks doing real
    work, M / (M + S - 1)."""
    return num_micro / (num_micro + num_stages - 1)
