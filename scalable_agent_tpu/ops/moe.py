"""An expert layer that is told which experts it holds.

The router scores every token against ALL ``num_experts`` experts and
picks ``top_k`` of them, as on every chip of an expert-parallel
deployment; this chip then computes the part of the routed sum that
falls on the experts it holds (``first_expert .. first_expert +
experts_held - 1``).  A (token, expert) pair routed elsewhere contributes
nothing here: the chips that hold those experts compute it, and nothing
stands in for them or for the exchange that would bring their part back.

No capacity and no dropped token.  The pairs that land here are sorted
by expert and run as one grouped matrix product
(``jax.lax.ragged_dot``: on a TPU a Mosaic kernel of XLA's own that
visits only the row tiles its groups cover; operands in the compute
dtype, products in float32), gathered back and weighted.
The sorted buffer has room for every pair, since a routing may send
them all here; rows past the pairs that did land here belong to no group,
are not computed, and are masked out of both passes.  Shapes are fixed:
how the load falls changes group sizes, never a shape.

A decode step (``every_expert``: a few rows, one token an env) runs
EVERY held expert over every row instead and weights by the routing (0
where a row did not choose the expert).  At a few rows an expert the
chip multiplies by an expert's matrices in less time than it reads them,
so this costs what reading all of them costs, whatever the routing;
the grouped product reads only the experts a step's rows chose, and
which those are changes with the weights: the fused step's time then
followed the router by 1.4% from seed to seed (my chip runs, PR 32;
``PERF.md`` section 6), and 256 decode steps an update are half of it.
"""

from functools import partial
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from scalable_agent_tpu.ops.attention import round_to


class Routing(NamedTuple):
    chosen: jax.Array      # i32 [N, k] expert ids, over all experts
    weights: jax.Array     # f32 [N, k]


def route(x, router_kernel, expert_bias, top_k: int, route_scale: float,
          route_norm: bool = True) -> Routing:
    """Sigmoid scores in float32 over all experts; the bias takes part
    in the choice and not in the weights."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router_kernel.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, chosen = jax.lax.top_k(scores + expert_bias, top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if route_norm:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return Routing(chosen.astype(jnp.int32), picked * route_scale)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gather_pairs(x, order, inverse, top_k: int):
    """Row i of the result is the token of sorted pair i: ``x[order[i]
    // top_k]``.  Its cotangent comes back by the inverse permutation
    (a gather and a sum over a token's pairs), not by a scatter-add."""
    del inverse
    return x[order // top_k]


def _gather_pairs_fwd(x, order, inverse, top_k):
    return x[order // top_k], (inverse, x.shape[0])


def _gather_pairs_bwd(top_k, residuals, g):
    inverse, tokens = residuals
    return g[inverse].reshape(tokens, top_k, -1).sum(axis=1), None, None


_gather_pairs.defvjp(_gather_pairs_fwd, _gather_pairs_bwd)


@jax.custom_vjp
def _permute(x, perm, inverse):
    """``x[perm]`` for a permutation; the cotangent is ``g[inverse]``."""
    del inverse
    return x[perm]


def _permute_fwd(x, perm, inverse):
    return x[perm], (perm, inverse)


def _permute_bwd(residuals, g):
    _, inverse = residuals
    return g[inverse], None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


# Rows up to which a decode step may run every held expert: under ~240
# rows an expert a v5e reads an expert's matrices (12.6 MB at the cell's
# widths) no faster than it multiplies by them.
EVERY_EXPERT_MAX_ROWS = 128


def _every_expert(x, routing: Routing, gate_proj, up_proj, down_proj,
                  first_expert: int, dtype):
    """Every held expert over every row, weighted by the routing: the
    roundings and the float32 sums of the grouped path, in another
    order."""
    held = gate_proj.shape[0]
    with jax.named_scope("dispatch"):
        local = routing.chosen - first_expert
        chose = local[..., None] == jnp.arange(held, dtype=jnp.int32)
        # [N, held]: what row n's routing gives expert e, 0 if not chosen
        weights = jnp.sum(
            jnp.where(chose, routing.weights[..., None], 0.0), axis=1)
        sizes = jnp.sum(chose, axis=(0, 1), dtype=jnp.int32)
    with jax.named_scope("experts"):
        def product(lhs, rhs, spec):
            return jnp.einsum(spec, round_to(lhs, dtype), rhs.astype(dtype),
                              preferred_element_type=jnp.float32)

        hidden = (jax.nn.silu(product(x, gate_proj, "nh,ehw->enw"))
                  * product(x, up_proj, "nh,ehw->enw"))
        out = product(hidden, down_proj, "enw,ewh->enh")
    with jax.named_scope("combine"):
        # a float32 sum, not a dot: a dot would round both to bfloat16
        y = jnp.sum(out * weights.T[..., None], axis=0)
    return y, sizes


def held_experts(x, routing: Routing, gate_proj, up_proj, down_proj,
                 first_expert: int, dtype, every_expert: bool = False
                 ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """``sum_{e chosen and held here} w_e * expert_e(x)`` for x [N,
    hidden] and the held experts' stacked silu-gated MLPs
    (``gate_proj`` / ``up_proj`` [held, hidden, width], ``down_proj``
    [held, width, hidden]), with the load's numbers.  ``every_expert``:
    the decode step's form (the module's docstring)."""
    tokens, top_k = routing.chosen.shape
    held = gate_proj.shape[0]
    pairs = tokens * top_k
    if every_expert:
        y, sizes = _every_expert(x, routing, gate_proj, up_proj, down_proj,
                                 first_expert, dtype)
        return y, _load(sizes, pairs)
    with jax.named_scope("dispatch"):
        local = routing.chosen - first_expert
        here = (local >= 0) & (local < held)
        # elsewhere sorts behind every held expert
        group = jnp.where(here, local, held).reshape(pairs)
        order = jnp.argsort(group, stable=True).astype(jnp.int32)
        inverse = jnp.zeros((pairs,), jnp.int32).at[order].set(
            jnp.arange(pairs, dtype=jnp.int32))
        sizes = jnp.sum(
            group[:, None] == jnp.arange(held, dtype=jnp.int32)[None, :],
            axis=0, dtype=jnp.int32)
        landed = jnp.sum(sizes)
        live = (jnp.arange(pairs, dtype=jnp.int32) < landed)[:, None]
        rows = jnp.where(
            live, _gather_pairs(round_to(x, dtype), order, inverse, top_k),
            0)
    with jax.named_scope("experts"):
        def grouped(lhs, rhs):
            # Rows past ``landed`` are in no group: the product leaves
            # them as it found the buffer, in this pass and in the
            # cotangent it hands back.  Every operand and every result
            # goes through ``live``, so neither pass reads one.
            return jnp.where(live, jax.lax.ragged_dot(
                round_to(lhs, dtype), rhs.astype(dtype), sizes,
                preferred_element_type=jnp.float32), 0)

        hidden = jnp.where(
            live, jax.nn.silu(grouped(rows, gate_proj))
            * grouped(rows, up_proj), 0)
        out = grouped(hidden, down_proj)
    with jax.named_scope("combine"):
        back = _permute(out, inverse, order).reshape(tokens, top_k, -1)
        weights = jnp.where(here, routing.weights, 0.0)
        # a float32 sum, not a dot: a dot would round both to bfloat16
        y = jnp.sum(back * weights[..., None], axis=1)
    return y, _load(sizes, pairs)


def _load(sizes, pairs: int) -> Dict[str, jax.Array]:
    """The load's numbers from the held experts' pair counts."""
    with jax.named_scope("telemetry"):
        sizes_f = sizes.astype(jnp.float32)
        mean = jnp.mean(sizes_f)
        return {
            "pairs_here_share": jnp.sum(sizes_f) / pairs,
            "tokens_per_expert_mean": mean,
            "expert_load_max_over_mean": jnp.max(sizes_f)
            / jnp.maximum(mean, 1e-9),
        }
