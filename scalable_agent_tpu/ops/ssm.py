"""The selective scan of a state-space layer, with episode resets: one
function for acting (T = 1, a step) and learning (T = unroll, a Pallas
kernel with the time loop inside and a backward pass).

For one env, channels ``d`` of ``D`` (``d_inner``) and states ``n`` of
``N`` (``d_state``)::

    s_t = exp(delta_t A) * (keep_t s_(t-1)) + (delta_t x_t) B_t^T     [N, D]
    y_t = sum_n s_t[n] C_t[n] + Dp * x_t                              [D]

``keep_t`` is 0 where ``reset`` says token ``t`` begins an episode (the
state it meets is zero, mid-unroll too) and 1 elsewhere.  Everything is
float32: the scan has no matrix product, and a recurrence kept in a
shorter float drifts with its length.

The state lies ``[B, N, D]``, states down the sublanes and channels
along the lanes (``[B, D, N]`` would pad 16 states to 128 lanes, eight
times the bytes): a step is then elementwise over vregs, ``delta_t`` and
``x_t`` are rows, ``B_t`` and ``C_t`` columns, ``y_t`` a sum over
sublanes.

Learning never writes a state a token to HBM (``[T, B, N, D]`` in
float32 is 2.7 GB a layer at 32 envs x 257 tokens x 16 x 5,120): the
forward kernel walks time in passes of ``_CHUNK`` tokens, emits ``y``
and keeps the state each pass STARTS from (``[B, chunks, N, D]``, 52 MB
there); the backward kernel takes the passes last to first, runs a
pass's states again into VMEM from the one it started from, and walks
them backwards.

Within a pass the channels go ``_LANES`` at a time, and only the
recurrence is a loop that carries anything.  For one lane tile:

- in bulk, a token on its own (``_UNROLL`` tokens a turn of a loop whose
  count is static, so their loads, exps and stores overlap): the decay
  ``exp(delta_t A) keep_t`` (``keep`` is 0 or 1 and the decay finite, so
  folding it in is exact: the reset's scalar leaves the chain) and the
  input's term ``(delta_t x_t) B_t^T``, both into VMEM ``[chunk, N,
  lanes]``;
- the recurrence: ``s = decay_t * s + u_t``, a product and a sum a
  token on the state's vregs, ``s_t`` stored where ``u_t`` lay;
- in bulk again, ``_ROWS`` = 8 tokens a block: ``y_t`` from the stored
  states, the eight tokens' sums over the states taken together
  (``_sums_as_rows``: rotates, sums and selects that leave token j's sum
  in row j) and written as whole vregs.

The backward runs the states again the same way, then one short loop
backwards that carries only ``g_t = d s_t`` (``g_t = g_(t+1) decay_(t+1)
+ C_t dy_t``, stored), then everything else in bulk from the states, the
``g_t`` and the decays, eight tokens a block as ``y_t`` is: ``d delta``,
``d x``, ``d A``, ``d Dp``, and ``d B_t`` / ``d C_t``, which are sums
over ALL channels: they add up
lane tile on lane tile in a ``[chunk, N, 128]`` partial and cross the
lanes once a pass, not once a token a tile.  ``B_t`` and ``C_t`` arrive
as columns (one lane); a pass spreads them along 128 lanes once, for
every tile's use.

What is re-associated against ``scan_step``'s sums (float32, within
``tests/test_ssm.py``'s tolerances): the sums over the states for
``y_t``, ``d delta_t`` and ``d x_t`` (halves, then the rounds of
``_sums_as_rows``), ``d B_t`` and ``d C_t`` (the tiles' sum before the
lanes'), and ``d Dp`` (a block's eight tokens, then the blocks last to
first).  ``d A``'s sum over a pass's tokens, last to first, and the envs'
sums are as they were.

A ragged last pass (257 = 4 x 64 + 1) is not masked: its count is as
static as a whole pass's, so it gets a body of its own (``_by_pass``),
one token long there, where a mask would run 63 dead tokens and a
dynamic count would stop the turns from overlapping.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

FWD_KERNEL_NAME = "pallas_ssm_scan_fwd"
BWD_KERNEL_NAME = "pallas_ssm_scan_bwd"

_CHUNK = 64                 # tokens between two kept states
_LANES = 512                # channels a pass takes at a time: 8 vregs a state
_UNROLL = 8                 # tokens a turn of a loop that goes token by token
_ROWS = 8                   # tokens a block of the others: a vreg's sublanes
_VMEM_LIMIT = 64 * 2 ** 20


def scan_step(x, delta, a, dp, b, c, reset, state):
    """One token an env, in XLA: x, delta [B, D]; a [N, D]; dp [D]; b, c
    [B, N]; reset bool [B]; state [B, N, D] -> (y [B, D], state)."""
    state = jnp.where(reset[:, None, None], 0.0, state)
    state = (jnp.exp(delta[:, None, :] * a[None]) * state
             + (delta * x)[:, None, :] * b[:, :, None])
    return jnp.sum(state * c[:, :, None], axis=1) + dp * x, state


def _lanes(width: int, most: int) -> int:
    """The widest multiple of 128 up to ``most`` that divides ``width``
    (a test's width, under 128, is one tile)."""
    for lanes in range(most, 0, -128):
        if width % lanes == 0:
            return lanes
    return width


def _over(count, body, carry=None):
    """``body(t, carry)`` for every token of a pass, ``_UNROLL`` a turn
    of the loop: the count is static, so one turn's loads, exps and
    stores overlap.  (Mosaic's own ``unroll`` is all or nothing.)"""
    turns, left = divmod(count, _UNROLL)

    def turn(i, carry):
        for j in range(_UNROLL):
            carry = body(i * _UNROLL + j, carry)
        return carry

    if turns:
        carry = lax.fori_loop(0, turns, turn, carry)
    for t in range(count - left, count):
        carry = body(t, carry)
    return carry


def _blocks(count, body, carry=None, last_first=False):
    """``body(t0, rows, carry)`` over a pass's tokens a block of
    ``_ROWS`` at a time, ``t0`` a multiple of it; a ragged last block
    has fewer rows and a static ``t0``."""
    whole, left = divmod(count, _ROWS)

    def ragged(carry):
        return body(whole * _ROWS, left, carry) if left else carry

    def turn(i, carry):
        block = whole - 1 - i if last_first else i
        return body(pl.multiple_of(block * _ROWS, _ROWS), _ROWS, carry)

    if last_first:
        carry = ragged(carry)
    if whole:
        carry = lax.fori_loop(0, whole, turn, carry)
    return carry if last_first else ragged(carry)


def _tiles(width, lanes, body):
    """``body(at)`` for every lane tile of the width, as a loop (a
    Python loop's ten copies of a pass cost the compiler ten times)."""
    def tile(j, _):
        body(pl.ds(pl.multiple_of(j * lanes, lanes), lanes))

    lax.fori_loop(0, width // lanes, tile, None)


def _by_pass(part, parts, steps, chunk, run):
    """``run(count)`` with the count static: ``chunk`` for every pass but
    a ragged last one, which gets a body of its own."""
    tail = steps % chunk
    if not tail:
        run(chunk)
        return
    pl.when(part < parts - 1)(lambda: run(chunk))
    pl.when(part == parts - 1)(lambda: run(tail))


def _wide(column, lanes):
    """A column already along 128 lanes, along ``lanes``: the same vregs
    again, no lane moves."""
    return jnp.tile(column, (1, lanes // column.shape[1]))


def _down(value):
    """[N, L] -> [8, L]: the sublane tiles' sum, vreg on vreg."""
    return sum(value[low:low + _ROWS]
               for low in range(0, value.shape[0], _ROWS))


def _sums_as_rows(tiles):
    """Up to eight [8, L] tiles -> [rows, L] whose row j is tile j's sum
    down its sublanes, whole vregs to store: three rounds of a rotate, a
    sum and a select, each halving the tiles (a token at a time it is
    three rotates and sums a tile and a one-row store)."""
    rows = len(tiles)
    tiles = tiles + [jnp.zeros_like(tiles[0])] * (_ROWS - rows)
    sub = lax.broadcasted_iota(jnp.int32, tiles[0].shape, 0)

    def merge(low, high, shift, low_rows):
        return jnp.where(low_rows, low + pltpu.roll(low, _ROWS - shift, 0),
                         high + pltpu.roll(high, shift, 0))

    fours = [merge(tiles[i], tiles[i + 4], 4, sub < 4) for i in range(4)]
    twos = [merge(fours[i], fours[i + 2], 2, sub % 4 < 2) for i in range(2)]
    return merge(twos[0], twos[1], 1, sub % 2 == 0)[:rows]


def _states(count, base, at, reset_ref, x_ref, dt_ref, a, b_wide, first,
            decay_ref, states_ref):
    """One lane tile's states over a pass of ``count`` tokens, into VMEM:
    ``decay_ref[t]`` = exp(delta_t A) keep_t, ``states_ref[0]`` the state
    the pass starts from and ``states_ref[t + 1]`` = s_t; returns the
    last.  In bulk first, every token on its own: the decay and the
    input's term ``u_t`` (which lies where s_t will).  Then the
    recurrence alone, a product and a sum a token."""
    lanes = a.shape[1]

    def ahead(t, _):
        row = pl.ds(t, 1)
        dt = dt_ref[row, at]
        keep = jnp.where(reset_ref[base + t] == 1, 0.0, 1.0)
        decay_ref[t] = jnp.exp(dt * a) * keep.astype(jnp.float32)
        states_ref[t + 1] = (dt * x_ref[row, at]) * _wide(b_wide[t], lanes)

    def step(t, s):
        s = decay_ref[t] * s + states_ref[t + 1]
        states_ref[t + 1] = s
        return s

    _over(count, ahead)
    states_ref[0] = first
    return _over(count, step, first)


def _forward_kernel(reset_ref, x_ref, dt_ref, a_ref, dp_ref, b_ref, c_ref,
                    s0_ref, y_ref, kept_ref, last_ref, s_ref, decay_ref,
                    states_ref, b_wide, c_wide, *, steps, parts, lanes):
    env, part = pl.program_id(0), pl.program_id(1)
    chunk, width = x_ref.shape
    b_wide[...] = jnp.broadcast_to(b_ref[...], b_wide.shape)
    c_wide[...] = jnp.broadcast_to(c_ref[...], c_wide.shape)

    @pl.when(part == 0)
    def _():
        s_ref[...] = s0_ref[...]

    kept_ref[...] = s_ref[...]
    base = env * steps + part * chunk

    def run(count):
        def tile(at):
            dp = dp_ref[:, at]
            s_ref[:, at] = _states(
                count, base, at, reset_ref, x_ref, dt_ref, a_ref[:, at],
                b_wide, s_ref[:, at], decay_ref, states_ref)

            def emit(t0, rows, _):
                at_rows = pl.ds(t0, rows)
                sums = [_down(states_ref[t0 + j + 1]
                              * _wide(c_wide[t0 + j], lanes))
                        for j in range(rows)]
                y_ref[at_rows, at] = (_sums_as_rows(sums)
                                      + dp * x_ref[at_rows, at])

            _blocks(count, emit)

        _tiles(width, lanes, tile)

    _by_pass(part, parts, steps, chunk, run)
    last_ref[...] = s_ref[...]


def _backward_kernel(reset_ref, x_ref, dt_ref, a_ref, dp_ref, b_ref, c_ref,
                     kept_ref, dy_ref, dlast_ref, dx_ref, ddt_ref, da_ref,
                     ddp_ref, db_ref, dc_ref, ds0_ref, g_ref, decay_ref,
                     states_ref, b_wide, c_wide, into_ref, db_part, dc_part,
                     *, steps, parts, lanes):
    env, turn = pl.program_id(0), pl.program_id(1)
    part = parts - 1 - turn
    chunk, width = x_ref.shape
    across = db_part.shape[-1]
    b_wide[...] = jnp.broadcast_to(b_ref[...], b_wide.shape)
    c_wide[...] = jnp.broadcast_to(c_ref[...], c_wide.shape)

    @pl.when(turn == 0)
    def _():
        g_ref[...] = dlast_ref[...]
        da_ref[...] = jnp.zeros_like(da_ref)
        ddp_ref[...] = jnp.zeros_like(ddp_ref)

    db_part[...] = jnp.zeros_like(db_part)
    dc_part[...] = jnp.zeros_like(dc_part)
    base = env * steps + part * chunk

    def folded(value):
        """[N, lanes] -> [N, across]: the lane tiles' sum, vreg on vreg."""
        return sum(value[:, low:low + across]
                   for low in range(0, lanes, across))

    def run(count):
        def tile(at):
            a, dp = a_ref[:, at], dp_ref[:, at]
            _states(count, base, at, reset_ref, x_ref, dt_ref, a, b_wide,
                    kept_ref[:, at], decay_ref, states_ref)

            def back(i, g):
                t = count - 1 - i
                g = g + _wide(c_wide[t], lanes) * dy_ref[pl.ds(t, 1), at]
                into_ref[t] = g             # d s_t, whole
                return g * decay_ref[t]

            def rest(t0, rows, carry):
                da, ddp = carry
                at_rows = pl.ds(t0, rows)
                x, dt, dy = (ref[at_rows, at]
                             for ref in (x_ref, dt_ref, dy_ref))
                dtx = dt * x
                du_sums, ddt_sums = [], []
                for j in reversed(range(rows)):
                    t = t0 + j
                    g = into_ref[t]
                    through = g * states_ref[t] * decay_ref[t]
                    du_sums.insert(0, _down(g * _wide(b_wide[t], lanes)))
                    ddt_sums.insert(0, _down(through * a))  # d (delta_t A)
                    dc_part[t] += folded(states_ref[t + 1] * dy[j:j + 1])
                    db_part[t] += folded(g * dtx[j:j + 1])
                    da = da + through * dt[j:j + 1]
                du = _sums_as_rows(du_sums)
                ddt_ref[at_rows, at] = _sums_as_rows(ddt_sums) + du * x
                dx_ref[at_rows, at] = du * dt + dp * dy
                return da, ddp + jnp.sum(dy * x, axis=0, keepdims=True)

            g_ref[:, at] = _over(count, back, g_ref[:, at])
            da, ddp = _blocks(
                count, rest, (jnp.zeros_like(a), jnp.zeros_like(dp)),
                last_first=True)
            da_ref[:, at] += da
            ddp_ref[:, at] += ddp

        _tiles(width, lanes, tile)

    _by_pass(part, parts, steps, chunk, run)
    db_ref[...] = jnp.sum(db_part[...], axis=2, keepdims=True)
    dc_ref[...] = jnp.sum(dc_part[...], axis=2, keepdims=True)
    ds0_ref[...] = g_ref[...]


@functools.partial(jax.jit, static_argnames=("backward", "interpret"))
def _kernel(reset, operands, extra=(), *, backward=False, interpret):
    """One of the two kernels, grid (env, chunk of time): ``operands``
    are (x, delta [B, T, D], a [N, D], dp [1, D], b, c [B, T, N, 1],
    state [B, N, D]); the backward one takes the kept states in the
    state's place and ``extra`` = (d y, d last state)."""
    x, _, a = operands[:3]
    batch, steps, width = x.shape
    states = a.shape[0]
    if states % _ROWS:
        raise ValueError(f"the scan's kernels take the states in sublane "
                         f"tiles of {_ROWS}: {states} states")
    parts = pl.cdiv(steps, _CHUNK)

    def part_of(turn):
        return parts - 1 - turn if backward else turn

    per_token = pl.BlockSpec((None, _CHUNK, width),
                             lambda e, p, *_: (e, part_of(p), 0))
    column = pl.BlockSpec((None, _CHUNK, states, 1),
                          lambda e, p, *_: (e, part_of(p), 0, 0))
    whole = pl.BlockSpec((states, width), lambda e, p, *_: (0, 0))
    row = pl.BlockSpec((1, width), lambda e, p, *_: (0, 0))
    per_env = pl.BlockSpec((None, states, width), lambda e, p, *_: (e, 0, 0))
    kept = pl.BlockSpec((None, None, states, width),
                        lambda e, p, *_: (e, part_of(p), 0, 0))
    f32 = jnp.float32
    tokens = jax.ShapeDtypeStruct((batch, steps, width), f32)
    columns = jax.ShapeDtypeStruct((batch, steps, states, 1), f32)
    state = jax.ShapeDtypeStruct((batch, states, width), f32)
    lanes = _lanes(width, _LANES)
    a_pass = pltpu.VMEM((_CHUNK, states, lanes), f32)
    columns_wide = pltpu.VMEM(
        (_CHUNK, states, 128 if lanes % 128 == 0 else lanes), f32)
    both = [pltpu.VMEM((states, width), f32), a_pass,
            pltpu.VMEM((_CHUNK + 1, states, lanes), f32),
            columns_wide, columns_wide]
    if backward:
        kernel = functools.partial(_backward_kernel, steps=steps,
                                   parts=parts, lanes=lanes)
        name = BWD_KERNEL_NAME
        in_specs = [per_token, per_token, whole, row, column, column, kept,
                    per_token, per_env]
        out_specs = [per_token, per_token, per_env,
                     pl.BlockSpec((None, 1, width),
                                  lambda e, p, *_: (e, 0, 0)),
                     column, column, per_env]
        out_shape = [tokens, tokens, state,
                     jax.ShapeDtypeStruct((batch, 1, width), f32),
                     columns, columns, state]
        scratch = both + [a_pass, columns_wide, columns_wide]
    else:
        kernel = functools.partial(_forward_kernel, steps=steps,
                                   parts=parts, lanes=lanes)
        name = FWD_KERNEL_NAME
        in_specs = [per_token, per_token, whole, row, column, column,
                    per_env]
        out_specs = [per_token, kept, per_env]
        out_shape = [tokens, jax.ShapeDtypeStruct(
            (batch, parts, states, width), f32), state]
        scratch = both
    with jax.named_scope(name):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(batch, parts),
                in_specs=in_specs, out_specs=out_specs,
                scratch_shapes=scratch),
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret, name=name)(reset, *operands, *extra)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def _scan(x, delta, a, dp, b, c, reset, state, interpret):
    return _scan_fwd(x, delta, a, dp, b, c, reset, state, interpret)[0]


def _scan_fwd(x, delta, a, dp, b, c, reset, state, interpret):
    operands = (x, delta, a, dp[None, :], b[..., None], c[..., None])
    reset = reset.astype(jnp.int32).reshape(-1)
    y, kept, last = _kernel(reset, operands + (state,), interpret=interpret)
    return (y, last), (reset, operands, kept)


def _scan_bwd(interpret, saved, cotangents):
    reset, operands, kept = saved
    dy, dlast = cotangents
    dx, ddt, da, ddp, db, dc, ds0 = _kernel(
        reset, operands + (kept,), (dy, dlast), backward=True,
        interpret=interpret)
    # the weights' gradients come an env apiece: the envs' sum is XLA's
    return (dx, ddt, jnp.sum(da, axis=0), jnp.sum(ddp, axis=(0, 1)),
            db[..., 0], dc[..., 0], None, ds0)


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(x, delta, a, dp, b, c, reset, state):
    """``x``, ``delta`` [B, T, D] (delta after its softplus), ``a`` [N,
    D] (negative), ``dp`` [D], ``b``, ``c`` [B, T, N], ``reset`` bool
    [B, T] (token t meets a zero state), ``state`` [B, N, D] -> (y [B,
    T, D], the state after the last token); all float32.  One token an
    env is a step in XLA; more go through the kernel, which
    differentiates in everything but ``reset``."""
    if x.shape[1] == 1:
        y, state = scan_step(x[:, 0], delta[:, 0], a, dp, b[:, 0], c[:, 0],
                             reset[:, 0], state)
        return y[:, None], state
    from scalable_agent_tpu.parallel.mesh import pallas_interpret

    return _scan(x, delta, a, dp, b, c, reset, state, pallas_interpret())
