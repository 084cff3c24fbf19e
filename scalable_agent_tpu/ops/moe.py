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
dtype, products in float32), and each token sums its pairs' rows,
weighted, straight out of the sorted buffer.

The sorted pairs are walked a chunk of ``C`` rows at a time, and every
gather, mask, product and sum of the walk has ``C`` rows: ``C`` is twice
the share of the pairs that an even routing sends to the held experts,
``2 * pairs * experts_held / num_experts`` rounded up to the grouped
product's row tile
(``compact_rows``: 16,640 of 65,792 pairs where a chip holds 16 of 128
experts).  The ops round the product do not skip a row as the product
does, so they cost what their buffer holds, not what landed; a buffer
with room for every pair, which a routing may need, cost eight times
the common load in every pass.  The first chunk is straight-line code
and is all there is when what landed fits it (``compact_share`` in the
layer's numbers: 1.0 for such a pass); a routing that sends more here
is walked on, chunk by chunk, by a loop that stops at the last pair
that landed, so any routing is exact, the work follows ``landed`` in
steps of ``C``, and no tensor of the program has a row a pair.  Where
the chip holds half the experts or more there is nothing to compact and
the one chunk holds every pair.  Rows of a chunk past the pairs that
landed belong to no group, are not computed, and are masked out of
both passes.  Shapes are fixed: how the load falls changes group sizes
and the loop's length, never a shape.  The walk's stacks and rows are as
wide as the grouped product's tiles want them (``lane_padded``:
``nemotron_h``'s 2,688 x 1,856 walk as 3,072 x 2,048, zeros beyond; the
decode's form takes the stacks as they are).

A loop that stops where the input says is not differentiable by
tracing, and a layer under ``nn.remat`` may keep only what its
backward reads: the grouped path is a ``custom_vjp`` (``_grouped``)
whose forward keeps the first chunk's stage inputs (``_stages``) and
whose backward pulls the cotangent back through them stage by stage,
then walks the later chunks again, each one's forward inside the loop's
body.

An expert is one of two forms, which the caller's configuration
names (``act``): gated, ``(act(x Wg) * (x Wu)) Wd`` (three matrices,
``silu``: the first families'), or not, ``act(x Wu) Wd`` (two matrices,
``relu2``, ``relu(.)^2``: ``nemotron_h``'s; ``gate_proj`` is None).  The
walk, its stages and the decode's form are the same for both: the stage
that projects takes one product or two.

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


ACTIVATIONS = {
    "silu": jax.nn.silu,
    "relu2": lambda x: jnp.square(jax.nn.relu(x)),
}


def activate(projected, act: str):
    """An expert's hidden row from its projections, (gate, up) or (up,):
    ``act(gate) * up`` or ``act(up)``.  The projections are taken one at
    a time, so a caller that makes them as they are asked for has the
    gate's activation traced before the second product."""
    projected = iter(projected)
    hidden = ACTIVATIONS[act](next(projected))
    for up in projected:
        hidden = hidden * up
    return hidden


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


# The grouped product's row tile.  XLA's kernel takes the largest power of
# two that divides its buffer's rows, up to 256, at a time
# (``ragged_dot_tiling`` in the compiled text): a buffer of 16,448 rows
# (64 x 257) is multiplied 64 rows at a time, and every product took 1.5
# times what it took over the every-pair buffer's 256 (my chip runs, PR
# 41).  A chunk is a whole number of full tiles.
_ROW_TILE = 256


def compact_rows(pairs: int, held: int, num_experts: int) -> int:
    """Rows of a chunk of the sorted pairs (the module's docstring);
    ``pairs`` where there is nothing to compact."""
    if 2 * held >= num_experts:
        return pairs
    rows = -(-2 * pairs * held // num_experts)
    return min(pairs, -(-rows // _ROW_TILE) * _ROW_TILE)


# The grouped product's other two tiles.  XLA's kernel likewise takes, of
# the contracted and of the result's width, the largest power of two up
# to 512 that divides it (``ragged_dot_tiling="256,128,128"`` for
# ``nemotron_h``'s 2,688 x 1,856 where the first families' 2,048 x 1,024
# and 2,048 x 768 read ``"256,512,512"`` and ``"256,512,256"``; compiled
# for a v5e, PR 42).  A width of more than one tile that is not whole
# tiles of 256 is padded with zeros up to whole tiles of 512 for the walk:
# zero columns of a projection give ``act(0) = 0`` (both forms'
# activations), zero rows of the down projection add nothing, so the sums
# are the unpadded ones.
_LANE_TILE = 256
_LANE_PAD = 512


def lane_padded(size: int) -> int:
    """``size`` as the walk's products see it (the comment above)."""
    if size % _LANE_TILE == 0 or size < _LANE_PAD:
        return size
    return -(-size // _LANE_PAD) * _LANE_PAD


def _pad_last(stack, *sizes: int):
    """``stack`` with its last ``len(sizes)`` axes zero-padded to
    ``sizes``; the stack itself where they are its own."""
    pads = [(0, 0)] * (stack.ndim - len(sizes)) + [
        (0, size - have)
        for size, have in zip(sizes, stack.shape[stack.ndim - len(sizes):])]
    return jnp.pad(stack, pads) if any(hi for _, hi in pads) else stack


class _Dispatch(NamedTuple):
    """Where the sort put each pair.  Pairs count ``token * top_k +
    choice``; the pairs that landed here come first, by expert."""
    order: jax.Array       # i32 [pairs, padded to whole chunks] the pair
                           # in each sorted row
    slot: jax.Array        # i32 [pairs] the sorted row of each pair
    here: jax.Array        # bool [N, k] the pair landed on a held expert
    sizes: jax.Array       # i32 [held] pairs on each held expert


def _slot_sum(rows, weights, slot):
    """``sum_k weights[:, k] * rows[slot[:, k]]``: each token's pairs'
    rows out of the sorted buffer, summed in float32 in the order of its
    choices: ``k`` gathers of [N, width], never one of [pairs, width].
    ``slot`` [N, k] lies inside ``rows``; a pair with no row of its own
    has weight 0."""
    total = jnp.zeros((slot.shape[0], rows.shape[1]), jnp.float32)
    for choice in range(slot.shape[1]):
        total = total + (weights[:, choice, None]
                         * rows[slot[:, choice]].astype(jnp.float32))
    return total


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def _take_tokens(x, order, slot, here, top_k: int):
    """Row i of the result is the token of sorted pair i: ``x[order[i]
    // top_k]``.  Its cotangent comes back as a token's sum over the
    rows of its pairs that landed (``_slot_sum``), not by a
    scatter-add."""
    del slot, here
    return x[order // top_k]


def _take_tokens_fwd(x, order, slot, here, top_k):
    return x[order // top_k], (slot, here)


def _take_tokens_bwd(top_k, residuals, g):
    slot, here = residuals
    return (_slot_sum(g, here.astype(jnp.float32), slot).astype(g.dtype),
            None, None, None)


_take_tokens.defvjp(_take_tokens_fwd, _take_tokens_bwd)


@jax.custom_vjp
def _combine(out, weights, order, slot):
    """``_slot_sum(out, weights, slot)``; the cotangents are taken on
    the sorted side, a row a pair of the buffer: ``out``'s is the
    token's times the pair's weight, a weight's the dot of the two."""
    del order
    return _slot_sum(out, weights, slot)


def _combine_fwd(out, weights, order, slot):
    return _slot_sum(out, weights, slot), (out, weights, order, slot)


def _combine_bwd(residuals, g):
    out, weights, order, slot = residuals
    of_row = g[order // weights.shape[1]]
    d_out = of_row * weights.reshape(-1)[order][:, None]
    d_weight = jnp.sum(of_row * out, axis=-1)
    return d_out, d_weight[slot], None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


# Rows up to which a decode step may run every held expert: under ~240
# rows an expert a v5e reads an expert's matrices (12.6 MB at the cell's
# widths) no faster than it multiplies by them.
EVERY_EXPERT_MAX_ROWS = 128


def _every_expert(x, routing: Routing, projections, down_proj,
                  first_expert: int, dtype, act: str):
    """Every held expert over every row, weighted by the routing: the
    roundings and the float32 sums of the grouped path, in another
    order."""
    held = down_proj.shape[0]
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

        hidden = activate((product(x, stack, "nh,ehw->enw")
                           for stack in projections), act)
        out = product(hidden, down_proj, "enw,ewh->enh")
    with jax.named_scope("combine"):
        # a float32 sum, not a dot: a dot would round both to bfloat16
        y = jnp.sum(out * weights.T[..., None], axis=0)
    return y, sizes


def _stages(dispatch: _Dispatch, dtype, rows: int, chunk, act: str):
    """The grouped path over rows ``[chunk * rows, (chunk + 1) * rows)``
    of the sorted pairs as a chain of stages: each takes the stage
    before's result, then the layer's inputs it reads
    (``_stage_inputs``), so a backward pass can start from any stage's
    kept input.  The last stage's result is the chunk's part of every
    token's sum."""
    tokens, top_k = dispatch.here.shape
    first = chunk * rows
    order = jax.lax.dynamic_slice_in_dim(dispatch.order, first, rows)
    live = (first + jnp.arange(rows, dtype=jnp.int32)
            < jnp.sum(dispatch.sizes))[:, None]
    # the chunk's rows of each held expert's run of the sorted pairs
    ends = jnp.cumsum(dispatch.sizes)
    sizes = (jnp.clip(ends, first, first + rows)
             - jnp.clip(ends - dispatch.sizes, first, first + rows))
    local = dispatch.slot.reshape(tokens, top_k) - first
    mine = dispatch.here & (local >= 0) & (local < rows)
    # a pair with no row in the chunk: any row does, at weight 0
    slot = jnp.clip(local, 0, rows - 1)

    def grouped(lhs, rhs):
        # Rows past the pairs that landed are in no group: the product
        # leaves them as it found the buffer, in this pass and in the
        # cotangent it hands back.  Every operand and every result goes
        # through ``live``, so neither pass reads one.
        return jnp.where(live, jax.lax.ragged_dot(
            round_to(lhs, dtype), rhs, sizes,
            preferred_element_type=jnp.float32), 0)

    def take(x):
        with jax.named_scope("dispatch"):
            return jnp.where(
                live, _take_tokens(round_to(x, dtype), order, slot, mine,
                                   top_k), 0)

    def project(taken, *projections):
        with jax.named_scope("experts"):
            return tuple(grouped(taken, stack) for stack in projections)

    def gate(projected):
        with jax.named_scope("experts"):
            return round_to(jnp.where(live, activate(projected, act), 0),
                            dtype)

    def down(hidden, down_proj):
        with jax.named_scope("experts"):
            return grouped(hidden, down_proj)

    def combine(out, weights):
        with jax.named_scope("combine"):
            # a float32 sum, not a dot: a dot would round both to
            # bfloat16
            return _combine(out, jnp.where(mine, weights, 0.0), order, slot)

    return take, project, gate, down, combine


def _stage_inputs(weights, projections, down_proj):
    """What each of ``_stages`` reads beside the stage before's
    result."""
    return (), tuple(projections), (), (down_proj,), (weights,)


# One chunk's two passes are jitted so that they are traced and lowered
# once for a whole model: every expert layer, its rematerialized twin and
# both loops' bodies call the same two functions at the same shapes.

@partial(jax.jit, static_argnames=("dtype", "rows", "act"))
def _forward(x, inputs, dispatch, chunk, *, dtype, rows, act="silu"):
    """The chain's result for one chunk, and the input of each stage
    after the first."""
    kept = []
    for stage, more in zip(_stages(dispatch, dtype, rows, chunk, act),
                           inputs):
        x = stage(x, *more)
        kept.append(x)
    return kept.pop(), kept


@partial(jax.jit, static_argnames=("dtype", "rows", "act"))
def _backward(x, kept, inputs, dispatch, chunk, g, *, dtype, rows,
              act="silu"):
    """``g`` pulled back through one chunk's chain from each stage's
    kept input: the cotangent of ``x`` and, a stage a tuple, of
    ``inputs``.  A stage's own result is not computed again unless its
    cotangent needs it (a product's needs its operands alone)."""
    d_inputs = []
    for stage, at, more in reversed(list(zip(
            _stages(dispatch, dtype, rows, chunk, act), [x, *kept],
            inputs))):
        g, *d_more = jax.vjp(stage, at, *more)[1](g)
        d_inputs.insert(0, tuple(d_more))
    return g, tuple(d_inputs)


def _chunks(dispatch: _Dispatch, rows: int):
    """How many chunks of ``rows`` rows hold a pair that landed: a
    traced number, or 1 where the one chunk holds every pair."""
    if rows == dispatch.slot.shape[0]:
        return 1
    return -(-jnp.sum(dispatch.sizes) // rows)


def _walk_on(chunks, one_chunk, total):
    """``total`` plus ``one_chunk(chunk)`` for every chunk after the
    first: no pass of the loop when what landed fits the first."""
    if isinstance(chunks, int):
        return total
    return jax.lax.fori_loop(
        1, chunks, lambda chunk, total: jax.tree_util.tree_map(
            jnp.add, total, one_chunk(chunk)), total)


@partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _grouped(x, weights, dispatch: _Dispatch, projections, down_proj,
             dtype, rows: int, act: str):
    """The weighted sum over the held experts, the sorted pairs walked
    ``rows`` rows at a time (the module's docstring).  The stacks
    (``projections``: (gate, up) or (up,)) come in the compute dtype:
    cast outside, the update shares the step's one cast of them with
    the decode."""
    return _grouped_fwd(x, weights, dispatch, projections, down_proj, dtype,
                        rows, act)[0]


def _grouped_fwd(x, weights, dispatch, projections, down_proj, dtype, rows,
                 act):
    inputs = _stage_inputs(weights, projections, down_proj)
    forward = partial(_forward, x, inputs, dispatch, dtype=dtype, rows=rows,
                      act=act)
    y, kept = forward(jnp.int32(0))
    y = _walk_on(_chunks(dispatch, rows), lambda chunk: forward(chunk)[0], y)
    return y, (x, kept, inputs, dispatch)


def _grouped_bwd(dtype, rows, act, residuals, g):
    x, kept, inputs, dispatch = residuals

    def backward(chunk, kept):
        return _backward(x, kept, inputs, dispatch, chunk, g, dtype=dtype,
                         rows=rows, act=act)

    def later(chunk):
        # a later chunk kept nothing: its forward again, in the loop
        return backward(chunk, _forward(x, inputs, dispatch, chunk,
                                        dtype=dtype, rows=rows, act=act)[1])

    d_x, ((), d_projections, (), (d_down_proj,), (d_weights,)) = (
        _walk_on(_chunks(dispatch, rows), later,
                 backward(jnp.int32(0), kept)))
    return d_x, d_weights, None, d_projections, d_down_proj


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def held_experts(x, routing: Routing, gate_proj, up_proj, down_proj,
                 first_expert: int, num_experts: int, dtype,
                 every_expert: bool = False, act: str = "silu"
                 ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """``sum_{e chosen and held here} w_e * expert_e(x)`` for x [N,
    hidden] and the held experts' stacked MLPs (``gate_proj`` /
    ``up_proj`` [held, hidden, width], ``down_proj`` [held, width,
    hidden]; ``gate_proj`` None: experts without a gate, ``act(x Wu)
    Wd``) of ``num_experts`` in all, with the load's numbers.
    ``every_expert``: the decode step's form (the module's
    docstring)."""
    tokens, top_k = routing.chosen.shape
    held = down_proj.shape[0]
    pairs = tokens * top_k
    projections = (up_proj,) if gate_proj is None else (gate_proj, up_proj)
    if every_expert:
        y, sizes = _every_expert(x, routing, projections, down_proj,
                                 first_expert, dtype, act)
        return y, _load(sizes, pairs)
    with jax.named_scope("dispatch"):
        local = routing.chosen - first_expert
        here = (local >= 0) & (local < held)
        # elsewhere sorts behind every held expert
        group = jnp.where(here, local, held).reshape(pairs)
        order = jnp.argsort(group, stable=True).astype(jnp.int32)
        slot = jnp.zeros((pairs,), jnp.int32).at[order].set(
            jnp.arange(pairs, dtype=jnp.int32))
        sizes = jnp.sum(
            group[:, None] == jnp.arange(held, dtype=jnp.int32)[None, :],
            axis=0, dtype=jnp.int32)
    rows = compact_rows(pairs, held, num_experts)
    hidden, width = x.shape[1], down_proj.shape[1]
    wide, deep = lane_padded(hidden), lane_padded(width)
    with jax.named_scope("experts"):
        projections = [_pad_last(stack.astype(dtype), wide, deep)
                       for stack in projections]
        down_proj = _pad_last(down_proj.astype(dtype), deep, wide)
    y = _grouped(
        _pad_last(x, wide), routing.weights, _Dispatch(
            jnp.pad(order, (0, -pairs % rows)), slot, here, sizes),
        tuple(projections), down_proj, dtype, rows, act)
    if wide != hidden:
        y = y[:, :hidden]
    return y, dict(_load(sizes, pairs), compact_share=jnp.float32(
        (jnp.sum(sizes) <= rows) if rows < pairs else 0.0))


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
