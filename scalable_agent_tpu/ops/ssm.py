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
forward kernel walks time in chunks of ``_CHUNK`` tokens, emits ``y``
and keeps the state each chunk STARTS from (``[B, chunks, N, D]``, 52 MB
there); the backward kernel takes the chunks last to first, runs a
chunk's states again into VMEM from the one it started from, and walks
them backwards.  Within a chunk the channels go ``lanes`` at a time with
the state a loop's carry, so it lives in vregs, not VMEM.
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
_FWD_LANES = 512            # channels a loop carries at a time: 8 vregs
_BWD_LANES = 256            # the backward loop carries three such tiles
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


def _keep(reset_ref, at):
    return jnp.where(reset_ref[at] == 1, 0.0, 1.0).astype(jnp.float32)


def _forward_kernel(reset_ref, x_ref, dt_ref, a_ref, dp_ref, b_ref, c_ref,
                    s0_ref, y_ref, kept_ref, last_ref, s_ref, *, steps,
                    lanes):
    env, part = pl.program_id(0), pl.program_id(1)
    chunk, width = x_ref.shape

    @pl.when(part == 0)
    def _():
        s_ref[...] = s0_ref[...]

    kept_ref[...] = s_ref[...]
    count = jnp.minimum(chunk, steps - part * chunk)
    base = env * steps + part * chunk
    for low in range(0, width, lanes):
        at = slice(low, low + lanes)
        a, dp = a_ref[:, at], dp_ref[:, at]

        def step(t, s, at=at, a=a, dp=dp):
            row = pl.ds(t, 1)
            x, dt = x_ref[row, at], dt_ref[row, at]
            s = (jnp.exp(dt * a) * (s * _keep(reset_ref, base + t))
                 + (dt * x) * b_ref[t])
            y_ref[row, at] = (jnp.sum(s * c_ref[t], axis=0, keepdims=True)
                              + dp * x)
            return s

        s_ref[:, at] = lax.fori_loop(0, count, step, s_ref[:, at])
    last_ref[...] = s_ref[...]


def _backward_kernel(reset_ref, x_ref, dt_ref, a_ref, dp_ref, b_ref, c_ref,
                     kept_ref, dy_ref, dlast_ref, dx_ref, ddt_ref, da_ref,
                     ddp_ref, db_ref, dc_ref, ds0_ref, g_ref, states_ref, *,
                     steps, parts, lanes):
    env, turn = pl.program_id(0), pl.program_id(1)
    part = parts - 1 - turn
    chunk, width = x_ref.shape

    @pl.when(turn == 0)
    def _():
        g_ref[...] = dlast_ref[...]
        da_ref[...] = jnp.zeros_like(da_ref)
        ddp_ref[...] = jnp.zeros_like(ddp_ref)

    db_ref[...] = jnp.zeros_like(db_ref)
    dc_ref[...] = jnp.zeros_like(dc_ref)
    count = jnp.minimum(chunk, steps - part * chunk)
    base = env * steps + part * chunk
    for low in range(0, width, lanes):
        at = slice(low, low + lanes)
        a, dp = a_ref[:, at], dp_ref[:, at]
        first = kept_ref[:, at]

        def again(t, s, at=at, a=a):
            row = pl.ds(t, 1)
            dt = dt_ref[row, at]
            s = (jnp.exp(dt * a) * (s * _keep(reset_ref, base + t))
                 + (dt * x_ref[row, at]) * b_ref[t])
            states_ref[t] = s
            return s

        lax.fori_loop(0, count, again, first)

        def back(i, carry, at=at, a=a, dp=dp, first=first):
            g, da, ddp = carry
            t = count - 1 - i
            row = pl.ds(t, 1)
            x, dt, dy = x_ref[row, at], dt_ref[row, at], dy_ref[row, at]
            keep = _keep(reset_ref, base + t)
            before = jnp.where(
                t == 0, first, states_ref[jnp.maximum(t - 1, 0)]) * keep
            s = states_ref[t]
            g = g + c_ref[t] * dy
            dc_ref[t] += jnp.sum(s * dy, axis=1, keepdims=True)
            decay = jnp.exp(dt * a)
            through = g * before * decay          # d (delta_t A)
            du = jnp.sum(g * b_ref[t], axis=0, keepdims=True)
            db_ref[t] += jnp.sum(g * (dt * x), axis=1, keepdims=True)
            ddt_ref[row, at] = (jnp.sum(through * a, axis=0, keepdims=True)
                                + du * x)
            dx_ref[row, at] = du * dt + dp * dy
            return (g * decay * keep, da + through * dt, ddp + dy * x)

        g, da, ddp = lax.fori_loop(
            0, count, back,
            (g_ref[:, at], jnp.zeros_like(a), jnp.zeros_like(dp)))
        g_ref[:, at] = g
        da_ref[:, at] += da
        ddp_ref[:, at] += ddp
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
    if backward:
        lanes = _lanes(width, _BWD_LANES)
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
        scratch = [pltpu.VMEM((states, width), f32),
                   pltpu.VMEM((_CHUNK, states, lanes), f32)]
    else:
        kernel = functools.partial(_forward_kernel, steps=steps,
                                   lanes=_lanes(width, _FWD_LANES))
        name = FWD_KERNEL_NAME
        in_specs = [per_token, per_token, whole, row, column, column,
                    per_env]
        out_specs = [per_token, kept, per_env]
        out_shape = [tokens, jax.ShapeDtypeStruct(
            (batch, parts, states, width), f32), state]
        scratch = [pltpu.VMEM((states, width), f32)]
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
