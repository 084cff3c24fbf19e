"""The scan of a Mamba-2 (SSD) layer, with episode resets: one function
for acting (T = 1, a step in XLA) and learning (T = unroll, the chunked
form as two Pallas kernels under a ``custom_vjp``).

For one env and one head (``P`` channels, ``N`` states; the ``H`` heads
of a group share ``B_t`` and ``C_t``), with one decay a head a token::

    S_t = exp(delta_t A) keep_t S_(t-1) + (delta_t x_t) (x) B_t       [P, N]
    y_t = S_t C_t + D x_t                                             [P]

``keep_t`` is 0 where ``reset`` says token ``t`` begins an episode (the
state it meets is zero, mid-unroll and mid-chunk too) and 1 elsewhere.
The state is a MATRIX a head (``ops/ssm.py``'s is a vector a channel):
``[64 heads, 64, 128]`` float32 is 2 MiB an env a layer at the published
widths, so a time loop that carries it in vregs is not a form it can
take, and ``[T, B, H, P, N]`` is 17 GB a layer: no state a token is ever
written to HBM.

The chunked form.  Over a chunk of ``Q`` tokens, with ``l_t`` the
running sum of ``delta A`` inside the chunk (a cumulative sum in XLA,
float32) and ``g_t`` the number of resets at or before ``t`` inside it::

    M[t, s] = exp(l_t - l_s)       s <= t and g_s == g_t, else 0
    Y       = ((C B^T) o M) U  +  [g_t == 0] exp(l_t) (C S_start^T)
              with U = delta o X
    S_end   = [g_Q == 0] exp(l_Q) S_start
              + sum_s [g_s == g_Q] exp(l_Q - l_s) U_s (x) B_s

so a token sees only what its own episode wrote, and every term is a
matrix product on the MXU.  ``l`` only falls (``A < 0 <= delta``), so no
exponent here is positive where it is used.  The decays, the state and
every sum are float32; the products' operands (``C``, ``B``, ``(C B^T)
o M``, ``U``, ``U`` scaled by its decay, and the state where a product
reads it) are rounded to ``dtype``, the model's compute dtype, as every
matrix product's operands in the model are.

The kernels: grid (env, group, chunk), the chunks in order (the
backward's last to first) with the group's state in VMEM between them.
The forward keeps the state each chunk STARTS from (``[B, chunks, H, P,
N]``: 6 MiB an env at three chunks) and the backward recomputes a
chunk's decays from ``l`` and pulls the cotangent back through the
products.  The heads of a group go a lane tile at a time (two heads of
64 channels fill 128 lanes), each head's rows of the tile picked by a
select: no slice narrower than a tile is loaded or stored.  ``D x`` and
``U = delta o X`` are XLA's, outside, with their gradients.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

FWD_KERNEL_NAME = "pallas_ssd_fwd"
BWD_KERNEL_NAME = "pallas_ssd_bwd"

_LANES = 128
_VMEM_LIMIT = 64 * 2 ** 20
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def ssd_step(x, delta, a, d, b, c, reset, state):
    """One token an env, in XLA: x [B, H, P]; delta [B, H]; a, d [H]; b,
    c [B, G, N]; reset bool [B]; state [B, H, P, N] -> (y [B, H, P],
    state).  Float32 throughout."""
    per_group = x.shape[1] // b.shape[1]
    b, c = (jnp.repeat(v, per_group, axis=1)[:, :, None, :] for v in (b, c))
    state = jnp.where(reset[:, None, None, None], 0.0, state)
    state = (jnp.exp(delta * a)[..., None, None] * state
             + (delta[..., None] * x)[..., None] * b)
    return jnp.sum(state * c, axis=-1) + d[:, None] * x, state


def _tile_heads(per_group: int, dim: int) -> int:
    """Heads of a group that go together: the most whose channels fit
    one lane tile, and that divide the group."""
    heads = max(1, min(per_group, _LANES // dim))
    while per_group % heads:
        heads -= 1
    return heads


def _column(block, at):
    """Column ``at`` (traced) of ``block`` [Q, W] as [Q, 1], by a select
    and a sum along the lanes: exact."""
    lane = lax.broadcasted_iota(jnp.int32, block.shape, 1)
    return jnp.sum(jnp.where(lane == at, block, 0.0), axis=1, keepdims=True)


def _last(row):
    """The last number of ``row`` [1, Q] as [1, 1]."""
    return _column(row, row.shape[1] - 1)


def _as_row(column):
    """[Q, 1] -> [1, Q], through the diagonal: exact."""
    size = column.shape[0]
    down = lax.broadcasted_iota(jnp.int32, (size, size), 0)
    along = lax.broadcasted_iota(jnp.int32, (size, size), 1)
    return jnp.sum(jnp.where(down == along, column, 0.0), axis=0,
                   keepdims=True)


def _dot(lhs, rhs, dims=(((1,), (0,)), ((), ()))):
    return lax.dot_general(lhs, rhs, dims,
                           preferred_element_type=jnp.float32)


def _chunk(b_ref, c_ref, segc_ref, segr_ref, dtype):
    """What every head of the group shares in a chunk: B and C rounded,
    ``C B^T``, which (t, s) a token may see, the resets before each token
    down a column, and those before the chunk's last token."""
    b, c = b_ref[...].astype(dtype), c_ref[...].astype(dtype)
    seg_down, seg_along = segc_ref[...], segr_ref[...]
    size = seg_down.shape[0]
    down = lax.broadcasted_iota(jnp.int32, (size, size), 0)
    along = lax.broadcasted_iota(jnp.int32, (size, size), 1)
    sees = (along <= down) & (seg_down == seg_along)
    return b, c, _dot(c, b, _NT), sees, seg_down, _last(seg_along)


def _decays(cum_all, cumr_ref, local, head, sees, seg_down, seg_last):
    """One head's decays in a chunk: ``M`` [Q, Q], from the chunk's start
    to each token [Q, 1], from each token to the chunk's end [Q, 1], and
    through the whole chunk [1, 1]."""
    down = _column(cum_all, head)                        # l_t, [Q, 1]
    along = cumr_ref[pl.ds(local, 1), :]                 # l_s, [1, Q]
    last = _last(along)
    among = jnp.where(sees, jnp.exp(down - along), 0.0)
    from_start = jnp.where(seg_down == 0.0, jnp.exp(down), 0.0)
    to_end = jnp.where(seg_down == seg_last, jnp.exp(last - down), 0.0)
    whole = jnp.where(seg_last == 0.0, jnp.exp(last), 0.0)
    return among, from_start, to_end, whole


def _tiles(per_group: int, dim: int):
    """(first head of the tile within the group, its lanes) a tile."""
    heads = _tile_heads(per_group, dim)
    return [(first, slice(first * dim, (first + heads) * dim))
            for first in range(0, per_group, heads)]


def _of_head(k: int, dim: int, width: int):
    """Which lanes [1, W] and which rows [W, 1] of a tile are head k's."""
    lane = lax.broadcasted_iota(jnp.int32, (1, width), 1)
    row = lax.broadcasted_iota(jnp.int32, (width, 1), 0)
    return ((lane >= k * dim) & (lane < (k + 1) * dim),
            (row >= k * dim) & (row < (k + 1) * dim))


def _tile_decays(cum_all, cumr_ref, first, head, dim, width, shared):
    """The decays of a tile's heads, ``first`` the tile's first head in
    the group and ``head`` in the model: [(``M``, head k's lanes [1, W],
    its rows [W, 1], its decay through the chunk [1, 1])] and, each head
    in its own lanes (rows), the decays from the chunk's start and to
    its end [Q, W] and through the chunk [W, 1]."""
    size = cum_all.shape[0]
    from_start = jnp.zeros((size, width), jnp.float32)
    to_end = jnp.zeros((size, width), jnp.float32)
    whole = jnp.zeros((width, 1), jnp.float32)
    heads = []
    for k in range(width // dim):
        among, start_k, end_k, whole_k = _decays(
            cum_all, cumr_ref, first + k, head + k, *shared)
        lanes, rows = _of_head(k, dim, width)
        from_start = jnp.where(lanes, start_k, from_start)
        to_end = jnp.where(lanes, end_k, to_end)
        whole = jnp.where(rows, whole_k, whole)
        heads.append((among, lanes, rows, start_k, end_k, whole_k))
    return heads, from_start, to_end, whole


def _forward_kernel(u_ref, b_ref, c_ref, cumc_ref, cumr_ref, segc_ref,
                    segr_ref, s0_ref, y_ref, kept_ref, last_ref, s_ref, *,
                    dim, per_group, dtype):
    group, part = pl.program_id(1), pl.program_id(2)

    @pl.when(part == 0)
    def _():
        s_ref[...] = s0_ref[...]

    kept_ref[...] = s_ref[...]
    b, c, gram, *shared = _chunk(b_ref, c_ref, segc_ref, segr_ref, dtype)
    cum_all = cumc_ref[...]
    for first, at in _tiles(per_group, dim):
        u, state = u_ref[:, at], s_ref[at, :]
        rounded = u.astype(dtype)
        heads, from_start, to_end, whole = _tile_decays(
            cum_all, cumr_ref, first, group * per_group + first, dim,
            u.shape[1], shared)
        within = jnp.zeros_like(u)
        for among, lanes, *_ in heads:
            within = jnp.where(
                lanes, _dot((gram * among).astype(dtype), rounded), within)
        y_ref[:, at] = within + from_start * _dot(
            c, state.astype(dtype), _NT)
        s_ref[at, :] = whole * state + _dot(
            (u * to_end).astype(dtype), b, _TN)
    last_ref[...] = s_ref[...]


def _backward_kernel(u_ref, b_ref, c_ref, cumc_ref, cumr_ref, segc_ref,
                     segr_ref, kept_ref, dy_ref, dlast_ref, du_ref, db_ref,
                     dc_ref, dcum_ref, ds0_ref, g_ref, *, dim, per_group,
                     dtype):
    group, turn = pl.program_id(1), pl.program_id(2)

    @pl.when(turn == 0)
    def _():
        g_ref[...] = dlast_ref[...]

    b, c, gram, *shared = _chunk(b_ref, c_ref, segc_ref, segr_ref, dtype)
    cum_all = cumc_ref[...]
    size = cum_all.shape[0]
    at_end = lax.broadcasted_iota(jnp.int32, (1, size), 1) == size - 1
    d_gram = jnp.zeros_like(gram)
    d_b = jnp.zeros(b.shape, jnp.float32)
    d_c = jnp.zeros(c.shape, jnp.float32)
    for first, at in _tiles(per_group, dim):
        u, dy = u_ref[:, at], dy_ref[:, at]
        state, g = kept_ref[at, :], g_ref[at, :]
        u_r, dy_r = u.astype(dtype), dy.astype(dtype)
        state_r, g_r = state.astype(dtype), g.astype(dtype)
        carried = _dot(c, state_r, _NT)          # C S_start^T     [Q, W]
        ahead = _dot(b, g_r, _NT)                # B dS_end^T      [Q, W]
        heads, from_start, to_end, whole = _tile_decays(
            cum_all, cumr_ref, first, group * per_group + first, dim,
            u.shape[1], shared)
        d_u = jnp.zeros_like(u)
        for k, (among, lanes, rows, start_k, end_k, whole_k) in enumerate(
                heads):
            weights = gram * among
            d_weights = _dot(jnp.where(lanes, dy, 0.0).astype(dtype), u_r,
                             _NT)
            d_u = jnp.where(
                lanes, _dot(weights.astype(dtype), dy_r, _TN), d_u)
            d_gram = d_gram + d_weights * among
            through = d_weights * weights         # d (l_t - l_s)
            d_start = jnp.sum(jnp.where(lanes, dy * carried, 0.0), axis=1,
                              keepdims=True) * start_k
            d_end = jnp.sum(jnp.where(lanes, u * ahead, 0.0), axis=1,
                            keepdims=True) * end_k
            d_whole = jnp.sum(jnp.where(rows, g * state, 0.0),
                              keepdims=True) * whole_k
            down = jnp.sum(through, axis=1, keepdims=True) + d_start - d_end
            dcum_ref[pl.ds(first + k, 1), :] = (
                _as_row(down) - jnp.sum(through, axis=0, keepdims=True)
                + jnp.where(at_end, jnp.sum(d_end, axis=0, keepdims=True)
                            + d_whole, 0.0))
        started = (dy * from_start).astype(dtype)
        du_ref[:, at] = d_u + to_end * ahead
        g_ref[at, :] = whole * g + _dot(started, c, _TN)
        d_c = d_c + _dot(started, state_r)
        d_b = d_b + _dot((u * to_end).astype(dtype), g_r)
    d_gram = d_gram.astype(dtype)
    dc_ref[...] = d_c + _dot(d_gram, b)
    db_ref[...] = d_b + _dot(d_gram, c, _TN)
    ds0_ref[...] = g_ref[...]


@functools.partial(jax.jit, static_argnames=(
    "chunk", "dtype", "backward", "interpret"))
def _kernel(operands, extra=(), *, chunk, dtype, backward=False, interpret):
    """One of the two kernels, grid (env, group, chunk of time):
    ``operands`` are (u [B, T, H P], b, c [B, T, G N], l [B, T, H], the
    resets before each token [B, T], state [B, H P, N]), T a whole number
    of chunks; the backward one takes the kept states in the state's
    place and ``extra`` = (d y, d last state)."""
    u, b, _, cum, seg, state = operands
    batch, steps, _ = u.shape
    heads = cum.shape[2]
    states = state.shape[-1]      # the backward's are the kept states
    groups = b.shape[2] // states
    per_group = heads // groups
    dim = u.shape[2] // heads
    parts = steps // chunk
    width = per_group * dim

    def part_of(turn):
        return parts - 1 - turn if backward else turn

    per_token = pl.BlockSpec((None, chunk, width),
                             lambda e, g, p: (e, part_of(p), g))
    shared = pl.BlockSpec((None, chunk, states),
                          lambda e, g, p: (e, part_of(p), g))
    down = pl.BlockSpec((None, chunk, heads),
                        lambda e, g, p: (e, part_of(p), 0))
    along = pl.BlockSpec((None, per_group, chunk),
                         lambda e, g, p: (e, g, part_of(p)))
    seg_down = pl.BlockSpec((None, chunk, 1),
                            lambda e, g, p: (e, part_of(p), 0))
    seg_along = pl.BlockSpec((None, 1, chunk),
                             lambda e, g, p: (e, 0, part_of(p)))
    per_env = pl.BlockSpec((None, width, states), lambda e, g, p: (e, g, 0))
    kept = pl.BlockSpec((None, None, width, states),
                        lambda e, g, p: (e, part_of(p), g, 0))
    f32 = jnp.float32
    tokens = jax.ShapeDtypeStruct(u.shape, f32)
    columns = jax.ShapeDtypeStruct(b.shape, f32)
    carried = jax.ShapeDtypeStruct((batch, u.shape[2], states), f32)
    operands = (u, b, operands[2], cum, jnp.swapaxes(cum, 1, 2),
                seg[:, :, None], seg[:, None, :], state)
    in_specs = [per_token, shared, shared, down, along, seg_down, seg_along]
    if backward:
        kernel, name = _backward_kernel, BWD_KERNEL_NAME
        in_specs += [kept, per_token, per_env]
        out_specs = [per_token, shared, shared, along, per_env]
        out_shape = [tokens, columns, columns,
                     jax.ShapeDtypeStruct((batch, heads, steps), f32),
                     carried]
    else:
        kernel, name = _forward_kernel, FWD_KERNEL_NAME
        in_specs += [per_env]
        out_specs = [per_token, kept, per_env]
        out_shape = [tokens, jax.ShapeDtypeStruct(
            (batch, parts) + carried.shape[1:], f32), carried]
    with jax.named_scope(name):
        return pl.pallas_call(
            functools.partial(kernel, dim=dim, per_group=per_group,
                              dtype=dtype),
            grid=(batch, groups, parts), in_specs=in_specs,
            out_specs=out_specs, out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((width, states), f32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret, name=name)(*operands, *extra)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _chunked(u, b, c, cum, seg, state, chunk, dtype, interpret):
    return _chunked_fwd(u, b, c, cum, seg, state, chunk, dtype,
                        interpret)[0]


def _chunked_fwd(u, b, c, cum, seg, state, chunk, dtype, interpret):
    y, kept, last = _kernel((u, b, c, cum, seg, state), chunk=chunk,
                            dtype=dtype, interpret=interpret)
    return (y, last), (u, b, c, cum, seg, kept)


def _chunked_bwd(chunk, dtype, interpret, saved, cotangents):
    du, db, dc, dcum, ds0 = _kernel(saved, cotangents, chunk=chunk,
                                    dtype=dtype, backward=True,
                                    interpret=interpret)
    return du, db, dc, jnp.swapaxes(dcum, 1, 2), None, ds0


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


def ssd_scan(x, delta, a, d, b, c, reset, state, *, chunk: int = 128,
             dtype=jnp.float32):
    """``x`` [B, T, H, P]; ``delta`` [B, T, H] (after its softplus);
    ``a`` [H] (negative), ``d`` [H]; ``b``, ``c`` [B, T, G, N] (head i
    reads group ``i // (H / G)``); ``reset`` bool [B, T] (token t meets a
    zero state); ``state`` [B, H, P, N] -> (y [B, T, H, P], the state
    after the last token); all float32.  One token an env is a step in
    XLA; more go through the kernels, ``chunk`` tokens at a time, which
    differentiate in everything but ``reset``.  ``dtype``: what the
    products' operands are rounded to (the module's docstring)."""
    batch, steps, heads, dim = x.shape
    if steps == 1:
        y, state = ssd_step(x[:, 0], delta[:, 0], a, d, b[:, 0], c[:, 0],
                            reset[:, 0], state)
        return y[:, None], state
    from scalable_agent_tpu.parallel.mesh import pallas_interpret

    parts = -(-steps // chunk)

    def whole_chunks(v):
        # a token past the last has delta 0: it leaves the state as it
        # finds it and adds nothing to it
        v = v.reshape(v.shape[:2] + (-1,))
        return jnp.pad(v, ((0, 0), (0, parts * chunk - steps), (0, 0)))

    def within_chunks(v):
        """The running sum inside each chunk, float32."""
        v = whole_chunks(v)
        return jnp.cumsum(v.reshape(batch, parts, chunk, -1),
                          axis=2).reshape(v.shape)

    y, last = _chunked(
        whole_chunks(delta[..., None] * x), whole_chunks(b), whole_chunks(c),
        within_chunks(delta * a),
        within_chunks(reset.astype(jnp.float32))[..., 0],
        state.reshape(batch, heads * dim, -1), chunk, jnp.dtype(dtype),
        pallas_interpret())
    y = y[:, :steps].reshape(x.shape) + d[:, None] * x
    return y, last.reshape(state.shape)
