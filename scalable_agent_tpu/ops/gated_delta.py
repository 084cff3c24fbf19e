"""The scan of a Gated-DeltaNet layer (Yang, Kautz and Hatamizadeh,
arXiv:2412.06464), with episode resets: one function for acting (T = 1,
a rank-one step in XLA) and learning (T = unroll, the chunked form as
two Pallas kernels under a ``custom_vjp``).

For one env and one head (keys of ``K`` numbers, values of ``V``), with
one decay ``a_t`` in (0, 1) and one write strength ``b_t`` in (0, 2) a
head a token::

    S_t = a_t keep_t S_(t-1) + b_t (v_t - a_t keep_t S_(t-1) k_t) k_t^T  [V, K]
    o_t = S_t q_t                                                       [V]

``keep_t`` is 0 where ``reset`` says token ``t`` begins an episode (the
state it meets is zero, mid-unroll and mid-chunk too) and 1 elsewhere.
A token does not only decay the state and add an outer product
(``ops/ssd.py``'s transition is a scalar): it first reads the state
against its key and writes the DIFFERENCE, so the transition ``a_t (I -
b_t k_t k_t^T)`` is not diagonal and every token of a chunk depends on
the corrected values of the tokens before it.  The state is a matrix a
head (``[30 heads, 192, 96]`` float32 is 2.1 MiB an env a layer at the
published widths); ``[T, B, H, V, K]`` is 4.5 GB a layer and no state a
token is ever written to HBM.

The chunked form.  Over a chunk of ``C`` tokens, with ``g_t`` the running
sum of ``log a`` inside the chunk (a cumulative sum in XLA, float32),
``s_t`` the number of resets at or before ``t`` inside it, ``E[t, j] =
exp(g_t - g_j)`` where ``j <= t`` and ``s_j == s_t`` and 0 elsewhere, and
``d_t = [s_t == 0] exp(g_t)`` (what of the chunk's start state ``S_0``
reaches token ``t``)::

    A  = b o E_(j<t) o (K K^T)          strictly lower, row t scaled by b_t
    T  = (I + A)^-1                     unit lower triangular (the solve)
    U~ = T (b o V) - T (b o d o K) S_0^T        the corrected values
    O  = d o (Q S_0^T) + (E o (Q K^T)) U~
    S_C = [s_C == 0] exp(g_C) S_0 + (U~ o e)^T K
         with e_j = [s_j == s_C] exp(g_C - g_j)

which follows from the recurrence by writing ``u~_t = b_t (v_t - a_t
keep_t S_(t-1) k_t)``.  ``g`` only falls, so no exponent is positive
where it is used.  The solve is block elimination, not a series: the
inverse of the 2 x 2 diagonal blocks is read off, and each doubling
``T <- T - T (A o off) T`` (``off`` the lower-left blocks of the next
size) is exact for the blocks it joins, ``log2 C - 1`` times: what
forward substitution computes, as matrix products.  The decays, the
state, every sum and the whole solve are float32 (the solve's products
at ``Precision.HIGHEST``); the other products' operands (``Q``, ``K``,
``b o V``, ``b o d o K``, ``T`` where it is applied, ``W = T (b o d o
K)``, ``U~``, ``E o (Q K^T)`` and the state where a product reads it)
are rounded to ``dtype``, the model's compute dtype, as every matrix
product's operands in the model are.

The kernels: grid (env, head, chunk), the chunks in order (the
backward's last to first) with the head's state in VMEM between them.
Values lie TRANSPOSED in the kernels, ``V^T`` ``[V, C]`` with the
chunk's tokens along the lanes, so that with ``C`` = 128 every operand
is whole (8, 128) tiles (192 values are 24 sublanes' worth, not a lane
tile and a half) and every product is a plain or a transposed-right one;
keys are zero-padded to whole lane tiles (96 -> 128: a zero key column
reads and writes nothing).  On the chip a chunk is therefore a multiple
of 128 tokens, or the whole call.  The forward keeps the state each
chunk STARTS from (``[B, H, chunks, V, K]``) and the backward recomputes
the chunk's terms, the solve among them, and pulls the cotangent back
through the products (``dA = -T^T dT T^T``).  The transposes, the
padding and the running sums are XLA's, outside, with their gradients.
Where the call is a whole number of chunks and ONE token (an unroll and
the token that bootstraps it), that token goes through the step and not
through a chunk of padding.
"""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

FWD_KERNEL_NAME = "pallas_gdn_fwd"
BWD_KERNEL_NAME = "pallas_gdn_bwd"

_LANES = 128
_VMEM_LIMIT = 64 * 2 ** 20
_NN = (((1,), (0,)), ((), ()))      # a @ b
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def gated_delta_step(q, k, v, beta, log_decay, reset, state):
    """One token an env, in XLA: q, k [B, H, K]; v [B, H, V]; beta,
    log_decay [B, H]; reset bool [B]; state [B, H, V, K] -> (o [B, H, V],
    state).  Float32 throughout, no matrix product."""
    state = jnp.where(reset[:, None, None, None], 0.0, state)
    state = jnp.exp(log_decay)[..., None, None] * state
    held = jnp.sum(state * k[:, :, None, :], axis=-1)
    new = beta[..., None] * (v - held)
    state = state + new[..., None] * k[:, :, None, :]
    return jnp.sum(state * q[:, :, None, :], axis=-1), state


def _dot(lhs, rhs, dims=_NN, precision=None):
    return lax.dot_general(lhs, rhs, dims, precision=precision,
                           preferred_element_type=jnp.float32)


def _exact(lhs, rhs, dims=_NN):
    return _dot(lhs, rhs, dims, lax.Precision.HIGHEST)


def _last(row):
    """The last number of ``row`` [1, C] as [1, 1]."""
    lane = lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return jnp.sum(jnp.where(lane == row.shape[1] - 1, row, 0.0), axis=1,
                   keepdims=True)


def _inverse(a, down, along):
    """``(I + a)^-1`` of a strictly lower triangular ``a`` [C, C], C a
    power of two, by block elimination (the module's docstring)."""
    size = a.shape[0]
    eye = (down == along).astype(jnp.float32)
    solve = eye - jnp.where((down >> 1) == (along >> 1), a, 0.0)
    bits = 1
    while (1 << bits) < size:
        off = (((down >> (bits + 1)) == (along >> (bits + 1)))
               & ((down >> bits) != (along >> bits)))
        solve = solve - _exact(solve, _exact(jnp.where(off, a, 0.0), solve))
        bits += 1
    return solve


class _Chunk(NamedTuple):
    """A chunk's terms, as the forward makes them and the backward makes
    them again ([C, .] a row a token, [., C] a column a token)."""

    down: jax.Array         # i32 [C, C]: a row's, a column's token
    along: jax.Array
    beta_c: jax.Array       # b, [C, 1] and [1, C]
    beta_r: jax.Array
    decay: jax.Array        # E [C, C], the diagonal too
    strict: jax.Array       # E below the diagonal
    start_c: jax.Array      # d, [C, 1] and [1, C]
    start_r: jax.Array
    end_r: jax.Array        # e [1, C]
    whole: jax.Array        # [s_C == 0] exp(g_C), [1, 1]
    gram: jax.Array         # K K^T
    a: jax.Array            # A
    solve: jax.Array        # T
    fed_t: jax.Array        # (b o V)^T [V, C]
    fed_k: jax.Array        # b o d o K [C, K]
    w: jax.Array            # T (b o d o K) [C, K]
    new_t: jax.Array        # U~^T [V, C]
    scores: jax.Array       # E o (Q K^T)
    carried: jax.Array      # S_0 Q^T [V, C]


def _chunk(q, k, v_t, rows_ref, state, dtype) -> _Chunk:
    size = q.shape[0]
    down = lax.broadcasted_iota(jnp.int32, (size, size), 0)
    along = lax.broadcasted_iota(jnp.int32, (size, size), 1)

    def column(row):
        """[1, C] -> [C, 1], through the diagonal: exact."""
        return jnp.sum(jnp.where(down == along, row, 0.0), axis=1,
                       keepdims=True)

    beta_r, cum_r, seg_r = (rows_ref[pl.ds(at, 1), :] for at in range(3))
    beta_c, cum_c, seg_c = column(beta_r), column(cum_r), column(seg_r)
    sees = (seg_c == seg_r) & (along <= down)
    decay = jnp.where(sees, jnp.exp(jnp.where(sees, cum_c - cum_r, 0.0)),
                      0.0)
    strict = jnp.where(along < down, decay, 0.0)
    seg_last, cum_last = _last(seg_r), _last(cum_r)
    start_r = jnp.where(seg_r == 0.0, jnp.exp(cum_r), 0.0)
    start_c = column(start_r)
    end_r = jnp.where(seg_r == seg_last, jnp.exp(cum_last - cum_r), 0.0)
    whole = jnp.where(seg_last == 0.0, jnp.exp(cum_last), 0.0)
    q_r, k_r, state_r = q.astype(dtype), k.astype(dtype), state.astype(dtype)
    gram = _dot(k_r, k_r, _NT)
    a = beta_c * strict * gram
    solve = _inverse(a, down, along)
    solve_r = solve.astype(dtype)
    fed_t = v_t * beta_r
    fed_k = (beta_c * start_c) * k
    w = _dot(solve_r, fed_k.astype(dtype))
    new_t = (_dot(fed_t.astype(dtype), solve_r, _NT)
             - _dot(state_r, w.astype(dtype), _NT))
    scores = decay * _dot(q_r, k_r, _NT)
    return _Chunk(down, along, beta_c, beta_r, decay, strict, start_c,
                  start_r, end_r, whole, gram, a, solve, fed_t, fed_k, w,
                  new_t, scores, _dot(state_r, q_r, _NT))


def _forward_kernel(q_ref, k_ref, vt_ref, rows_ref, s0_ref, ot_ref, kept_ref,
                    last_ref, s_ref, *, dtype):
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = s0_ref[...]

    state, k = s_ref[...], k_ref[...]
    kept_ref[...] = state
    c = _chunk(q_ref[...], k, vt_ref[...], rows_ref, state, dtype)
    ot_ref[...] = c.start_r * c.carried + _dot(
        c.new_t.astype(dtype), c.scores.astype(dtype), _NT)
    last = c.whole * state + _dot((c.new_t * c.end_r).astype(dtype),
                                  k.astype(dtype))
    s_ref[...] = last
    last_ref[...] = last


def _backward_kernel(q_ref, k_ref, vt_ref, rows_ref, kept_ref, dot_ref,
                     dlast_ref, dq_ref, dk_ref, dvt_ref, drows_ref, ds0_ref,
                     g_ref, *, dtype):
    @pl.when(pl.program_id(2) == 0)
    def _():
        g_ref[...] = dlast_ref[...]

    q, k, v_t, state = q_ref[...], k_ref[...], vt_ref[...], kept_ref[...]
    c = _chunk(q, k, v_t, rows_ref, state, dtype)
    size = q.shape[0]

    def rounded(x):
        return x.astype(dtype)

    def as_row(column):
        return jnp.sum(jnp.where(c.down == c.along, column, 0.0), axis=0,
                       keepdims=True)

    q_r, k_r, state_r = rounded(q), rounded(k), rounded(state)
    solve_r, new_r = rounded(c.solve), rounded(c.new_t)
    d_out, d_last = dot_ref[...], g_ref[...]
    d_out_r, d_last_r = rounded(d_out), rounded(d_last)
    # S_C = whole S_0 + (U~ o e)^T K
    ahead = _dot(d_last_r, k_r, _NT)                     # dS_C K^T   [V, C]
    d_new = c.end_r * ahead
    d_end = jnp.sum(c.new_t * ahead, axis=0, keepdims=True)
    d_k = _dot(rounded(c.new_t * c.end_r), d_last_r, _TN)
    d_whole = jnp.sum(d_last * state, keepdims=True)
    d_state = c.whole * d_last
    # O = d o (Q S_0^T) + P U~
    d_start_r = jnp.sum(d_out * c.carried, axis=0, keepdims=True)
    started = rounded(d_out * c.start_r)
    d_state = d_state + _dot(started, q_r)
    d_q = _dot(started, state_r, _TN)
    d_new = d_new + _dot(d_out_r, rounded(c.scores))
    d_scores = _dot(d_out_r, new_r, _TN)                 # dO U~^T    [C, C]
    through = d_scores * c.scores                        # d (g_t - g_j)
    d_pairs = rounded(d_scores * c.decay)
    d_q = d_q + _dot(d_pairs, k_r)
    d_k = d_k + _dot(d_pairs, q_r, _TN)
    # U~ = T (b o V) - W S_0^T,  W = T (b o d o K)
    d_new_r = rounded(d_new)
    d_state = d_state - _dot(d_new_r, rounded(c.w))
    d_w = rounded(-_dot(d_new_r, state_r, _TN))          # [C, K]
    d_solve = (_dot(d_w, rounded(c.fed_k), _NT)
               + _dot(d_new_r, rounded(c.fed_t), _TN))
    d_fed_k = _dot(solve_r, d_w, _TN)
    d_fed_t = _dot(d_new_r, solve_r)
    dvt_ref[...] = d_fed_t * c.beta_r
    d_beta_r = jnp.sum(d_fed_t * v_t, axis=0, keepdims=True)
    d_k = d_k + (c.beta_c * c.start_c) * d_fed_k
    fed = jnp.sum(d_fed_k * k, axis=1, keepdims=True)    # [C, 1]
    d_beta_c = c.start_c * fed
    d_start_c = c.beta_c * fed
    # T = (I + A)^-1,  A = b o E_(j<t) o (K K^T)
    d_a = -_exact(c.solve, _exact(d_solve, c.solve, _NT), _TN)
    d_beta_c = d_beta_c + jnp.sum(d_a * c.strict * c.gram, axis=1,
                                  keepdims=True)
    through = through + d_a * c.a
    d_gram = rounded(d_a * c.beta_c * c.strict)
    d_k = d_k + _dot(d_gram, k_r) + _dot(d_gram, k_r, _TN)
    dq_ref[...] = d_q
    dk_ref[...] = d_k
    # the decays: g_t through every exp it is in
    at_end = lax.broadcasted_iota(jnp.int32, (1, size), 1) == size - 1
    d_start = (d_start_r + as_row(d_start_c)) * c.start_r
    d_end = d_end * c.end_r
    d_cum = (as_row(jnp.sum(through, axis=1, keepdims=True))
             - jnp.sum(through, axis=0, keepdims=True) + d_start - d_end
             + jnp.where(at_end, jnp.sum(d_end, axis=1, keepdims=True)
                         + d_whole * c.whole, 0.0))
    drows_ref[pl.ds(0, 1), :] = d_beta_r + as_row(d_beta_c)
    drows_ref[pl.ds(1, 1), :] = d_cum
    drows_ref[pl.ds(2, 1), :] = jnp.zeros_like(d_cum)
    g_ref[...] = d_state
    ds0_ref[...] = d_state


@functools.partial(jax.jit, static_argnames=(
    "chunk", "dtype", "backward", "interpret"))
def _kernel(operands, extra=(), *, chunk, dtype, backward=False, interpret):
    """One of the two kernels, grid (env, head, chunk of time):
    ``operands`` are (q, k [B, H, T, K], v^T [B, H, V, T], the rows (b, g,
    the resets before each token) [B, H, 3, T], state [B, H, V, K]), T a
    whole number of chunks; the backward one takes the kept states in the
    state's place and ``extra`` = (d o^T, d last state)."""
    q, _, v_t, rows, state = operands
    batch, heads, steps, keys = q.shape
    values = v_t.shape[2]
    parts = steps // chunk

    def part_of(turn):
        return parts - 1 - turn if backward else turn

    per_key = pl.BlockSpec((None, None, chunk, keys),
                           lambda e, h, p: (e, h, part_of(p), 0))
    per_value = pl.BlockSpec((None, None, values, chunk),
                             lambda e, h, p: (e, h, 0, part_of(p)))
    per_row = pl.BlockSpec((None, None, 3, chunk),
                           lambda e, h, p: (e, h, 0, part_of(p)))
    per_head = pl.BlockSpec((None, None, values, keys),
                            lambda e, h, p: (e, h, 0, 0))
    kept = pl.BlockSpec((None, None, None, values, keys),
                        lambda e, h, p: (e, h, part_of(p), 0, 0))
    f32 = jnp.float32
    carried = jax.ShapeDtypeStruct((batch, heads, values, keys), f32)
    in_specs = [per_key, per_key, per_value, per_row]
    if backward:
        kernel, name = _backward_kernel, BWD_KERNEL_NAME
        in_specs += [kept, per_value, per_head]
        out_specs = [per_key, per_key, per_value, per_row, per_head]
        out_shape = [jax.ShapeDtypeStruct(q.shape, f32)] * 2 + [
            jax.ShapeDtypeStruct(v_t.shape, f32),
            jax.ShapeDtypeStruct(rows.shape, f32), carried]
    else:
        kernel, name = _forward_kernel, FWD_KERNEL_NAME
        in_specs += [per_head]
        out_specs = [per_value, kept, per_head]
        out_shape = [jax.ShapeDtypeStruct(v_t.shape, f32),
                     jax.ShapeDtypeStruct(
                         (batch, heads, parts, values, keys), f32), carried]
    with jax.named_scope(name):
        return pl.pallas_call(
            functools.partial(kernel, dtype=dtype),
            grid=(batch, heads, parts), in_specs=in_specs,
            out_specs=out_specs, out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((values, keys), f32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret, name=name)(*operands, *extra)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _chunked(q, k, v_t, rows, state, chunk, dtype, interpret):
    return _chunked_fwd(q, k, v_t, rows, state, chunk, dtype, interpret)[0]


def _chunked_fwd(q, k, v_t, rows, state, chunk, dtype, interpret):
    out_t, kept, last = _kernel((q, k, v_t, rows, state), chunk=chunk,
                                dtype=dtype, interpret=interpret)
    return (out_t, last), (q, k, v_t, rows, kept)


def _chunked_bwd(chunk, dtype, interpret, saved, cotangents):
    return tuple(_kernel(saved, cotangents, chunk=chunk, dtype=dtype,
                         backward=True, interpret=interpret))


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


def gated_delta_scan(q, k, v, beta, log_decay, reset, state, *,
                     chunk: int = 128, dtype=jnp.float32):
    """``q``, ``k`` [B, T, H, K] (as the recurrence reads them: normed,
    the query scaled); ``v`` [B, T, H, V]; ``beta`` [B, T, H] (the write
    strength); ``log_decay`` [B, T, H] (``log a``, not positive);
    ``reset`` bool [B, T] (token t meets a zero state); ``state`` [B, H,
    V, K] -> (o [B, T, H, V], the state after the last token); all
    float32.  One token an env is a step in XLA; more go through the
    kernels, ``chunk`` tokens (a power of two) at a time, which
    differentiate in everything but ``reset``.  ``dtype``: what the
    products' operands are rounded to (the module's docstring)."""
    batch, steps, heads, keys = q.shape
    if steps == 1:
        o, state = gated_delta_step(q[:, 0], k[:, 0], v[:, 0], beta[:, 0],
                                    log_decay[:, 0], reset[:, 0], state)
        return o[:, None], state
    if chunk < 2 or chunk & (chunk - 1):
        raise ValueError(f"gated delta scan: a chunk of {chunk} tokens is "
                         f"not a power of two")
    from scalable_agent_tpu.parallel.mesh import pallas_interpret

    # whole chunks and one token: that token is a step (the docstring)
    through = steps - 1 if steps % chunk == 1 else steps
    parts = -(-through // chunk)
    more = parts * chunk - through
    wide = -(-keys // _LANES) * _LANES

    def heads_first(x):
        """[B, T, H, ...] of the kernels' tokens -> [B, H, T, ...]."""
        return jnp.swapaxes(x[:, :through], 1, 2)

    def whole_chunks(x, axis=2):
        # a token past the last has b = 0 and log a = 0: it leaves the
        # state as it finds it
        pad = [(0, 0)] * x.ndim
        pad[axis] = (0, more)
        return jnp.pad(x, pad)

    def within_chunks(x):
        """[B, H, T] -> the running sum inside each chunk, float32."""
        x = whole_chunks(x)
        return jnp.cumsum(x.reshape(batch, heads, parts, chunk),
                          axis=3).reshape(x.shape)

    def keys_wide(x):
        return whole_chunks(jnp.pad(
            heads_first(x), ((0, 0),) * 3 + ((0, wide - keys),)))

    rows = jnp.stack([
        whole_chunks(heads_first(beta)),
        within_chunks(heads_first(log_decay)),
        within_chunks(jnp.broadcast_to(
            reset[:, None, :through].astype(jnp.float32),
            (batch, heads, through)))], axis=2)
    out_t, last = _chunked(
        keys_wide(q), keys_wide(k),
        whole_chunks(jnp.moveaxis(v[:, :through], 1, 3), axis=3), rows,
        jnp.pad(state, ((0, 0),) * 3 + ((0, wide - keys),)), chunk,
        jnp.dtype(dtype), pallas_interpret())
    out = jnp.moveaxis(out_t[..., :through], 3, 1)
    state = last[..., :keys]
    if through < steps:
        o, state = gated_delta_step(
            q[:, -1], k[:, -1], v[:, -1], beta[:, -1], log_decay[:, -1],
            reset[:, -1], state)
        out = jnp.concatenate([out, o[:, None]], axis=1)
    return out, state
