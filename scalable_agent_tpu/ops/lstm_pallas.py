"""Fused done-reset LSTM unroll for TPU, written in Pallas.

The agent core is a single-layer LSTM(256) scanned over T timesteps
with a per-step done-triggered state reset (reference:
experiment.py:225-237 — the reference's own comment notes the reset
rules out CuDNN, forcing a Python unroll; the XLA path here uses
``nn.scan``).  This module goes one step further than ``nn.scan``: the
whole unroll is ONE Pallas program with

- the gate weights (Wi [D,4H], Wh [H,4H], bias [4H]) resident in VMEM
  across all T steps (constant-index blocks — fetched once, not
  re-streamed from HBM per step),
- the (c, h) carry living in VMEM scratch between grid steps (the TPU
  grid executes sequentially, which is exactly what a recurrence needs),
- per-timestep inputs/outputs streamed HBM<->VMEM by the Pallas
  pipeline with double buffering.

Unlike V-trace, gradients DO flow through the core, so the op carries a
custom VJP: the forward kernel stashes the gate activations and
post-reset carries as residuals, and a second Pallas kernel runs the
standard BPTT recurrence in reverse (grid index map ``t -> T-1-t``),
accumulating the weight gradients in VMEM scratch and writing them out
on the final grid step.

Math and parameter layout exactly match
``flax.linen.OptimizedLSTMCell`` (gate order i, f, g, o; i/f/o
sigmoid, g tanh; c' = f*c + i*g; h' = o*tanh(c'); no forget-gate bias
offset), so the flax cell and this kernel are interchangeable on the
same parameter pytree — see models/agent.py, which concatenates the
cell's ii/if/ig/io and hi/hf/hg/ho kernels into Wi/Wh.

Carry/gate math is float32.  The four matmuls (the kernel's only MXU
work) run at a configurable precision: ``matmul_dtype="float32"``
(default — bit-exact parity with the flax cell, which promotes to the
f32 params' dtype regardless of a bfloat16 torso) or ``"bfloat16"``
(operands cast to bf16, accumulation still f32 via
``preferred_element_type`` — 2x the MXU rate at ~1e-2 relative gate
error, the standard mixed-precision recipe).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# The three Mosaic calls, by the name each carries twice: as its
# ``pallas_call`` name and as the ``jax.named_scope`` it is bound
# under.  The scope is what survives into the compiled instruction's
# name, so a device trace shows ``pallas_lstm_fwd.<n>`` where all three
# used to show the flax scope's ``core.<n>`` (the stem kernel does the
# same, ops/conv_pallas.py GRADW_KERNEL_NAME).  Read by the profiler's
# viewers and the benchmark's trace readers; obs/kernels.py matches
# Pallas calls by their custom-call target, not by these names.
FWD_KERNEL_NAME = "pallas_lstm_fwd"    # the update's residual-producing unroll
STEP_KERNEL_NAME = "pallas_lstm_step"  # T=1 inference, the lean forward
BWD_KERNEL_NAME = "pallas_lstm_bwd"


def _mm(a, b, matmul_dtype):
    """MXU matmul at the configured operand precision, f32 accumulate."""
    return jnp.dot(a.astype(matmul_dtype), b.astype(matmul_dtype),
                   preferred_element_type=jnp.float32)


def _cell_step(x_ref, done_ref, c0_ref, h0_ref, wi_ref, wh_ref, b_ref,
               c_s, h_s, matmul_dtype):
    """Shared cell math for one grid step: reset the carry where done,
    run the gates, update the VMEM carry.  Returns the intermediates
    the residual-producing kernel stashes for BPTT."""
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        c_s[:] = c0_ref[:]
        h_s[:] = h0_ref[:]

    keep = 1.0 - done_ref[0]                       # [B, 1]
    c = keep * c_s[:]
    h = keep * h_s[:]

    gates = (
        _mm(x_ref[0], wi_ref[:], matmul_dtype)
        + _mm(h, wh_ref[:], matmul_dtype)
        + b_ref[0][None, :])
    hidden = c.shape[-1]
    i = jax.nn.sigmoid(gates[:, :hidden])
    f = jax.nn.sigmoid(gates[:, hidden:2 * hidden])
    g = jnp.tanh(gates[:, 2 * hidden:3 * hidden])
    o = jax.nn.sigmoid(gates[:, 3 * hidden:])

    c_new = f * c + i * g
    h_new = o * jnp.tanh(c_new)
    c_s[:] = c_new
    h_s[:] = h_new
    return c, h, i, f, g, o, c_new, h_new


def _fwd_kernel_lean(x_ref, done_ref, c0_ref, h0_ref, wi_ref, wh_ref,
                     b_ref, ys_ref, ct_ref, ht_ref, c_s, h_s,
                     matmul_dtype=jnp.float32):
    """Inference-only forward: writes just ys and the final carry — no
    residual traffic (the primal path of lstm_unroll; XLA cannot DCE
    individual outputs of one kernel, so the residual variant would pay
    ~7x the HBM writes for nothing outside a grad context)."""
    _, _, _, _, _, _, c_new, h_new = _cell_step(
        x_ref, done_ref, c0_ref, h0_ref, wi_ref, wh_ref, b_ref, c_s, h_s,
        matmul_dtype)
    ys_ref[0] = h_new
    # Constant-index output block: the last grid step's write survives.
    ct_ref[:] = c_new
    ht_ref[:] = h_new


def _fwd_kernel(x_ref, done_ref, c0_ref, h0_ref, wi_ref, wh_ref, b_ref,
                ys_ref, ifgo_ref, cpost_ref, hpost_ref, cnew_ref,
                ct_ref, ht_ref, c_s, h_s, matmul_dtype=jnp.float32):
    """Residual-producing forward (the VJP primal): additionally stashes
    the gate activations ifgo [1,B,4H], post-reset carries cpost/hpost
    [1,B,H], and cnew [1,B,H] per timestep for the backward kernel."""
    c, h, i, f, g, o, c_new, h_new = _cell_step(
        x_ref, done_ref, c0_ref, h0_ref, wi_ref, wh_ref, b_ref, c_s, h_s,
        matmul_dtype)
    cpost_ref[0] = c
    hpost_ref[0] = h
    ifgo_ref[0] = jnp.concatenate([i, f, g, o], axis=-1)
    cnew_ref[0] = c_new
    ys_ref[0] = h_new
    ct_ref[:] = c_new
    ht_ref[:] = h_new


def _bwd_kernel(dys_ref, x_ref, done_ref, ifgo_ref, cpost_ref, hpost_ref,
                cnew_ref, wi_ref, wh_ref, dct_ref, dht_ref,
                dx_ref, dwi_ref, dwh_ref, db_ref, dc0_ref, dh0_ref,
                dc_s, dh_s, dwi_s, dwh_s, db_s,
                matmul_dtype=jnp.float32):
    """One reverse timestep of BPTT (grid step k visits t = T-1-k via the
    index maps; inside the kernel every per-t ref is already the t-th
    block)."""
    k = pl.program_id(0)

    @pl.when(k == 0)
    def _():
        dc_s[:] = dct_ref[:]
        dh_s[:] = dht_ref[:]
        dwi_s[:] = jnp.zeros_like(dwi_s)
        dwh_s[:] = jnp.zeros_like(dwh_s)
        db_s[:] = jnp.zeros_like(db_s)

    hidden = dc_s.shape[-1]
    ifgo = ifgo_ref[0]
    i = ifgo[:, :hidden]
    f = ifgo[:, hidden:2 * hidden]
    g = ifgo[:, 2 * hidden:3 * hidden]
    o = ifgo[:, 3 * hidden:]
    c_new = cnew_ref[0]
    tanh_c = jnp.tanh(c_new)

    dh = dys_ref[0] + dh_s[:]
    do = dh * tanh_c * o * (1.0 - o)
    dc = dc_s[:] + dh * o * (1.0 - tanh_c * tanh_c)
    df = dc * cpost_ref[0] * f * (1.0 - f)
    di = dc * g * i * (1.0 - i)
    dg = dc * i * (1.0 - g * g)
    dgates = jnp.concatenate([di, df, dg, do], axis=-1)   # [B, 4H]

    # dx = dgates @ Wi^T ; dh_prev = dgates @ Wh^T  (contract gate dim).
    mm = lambda a, b, dims: lax.dot_general(
        a.astype(matmul_dtype), b.astype(matmul_dtype), dims,
        preferred_element_type=jnp.float32)
    contract_last = (((1,), (1,)), ((), ()))
    dx_ref[0] = mm(dgates, wi_ref[:], contract_last)
    dh_prev = mm(dgates, wh_ref[:], contract_last)
    dc_prev = dc * f

    # Weight grads: x^T @ dgates and h_post^T @ dgates (contract batch).
    contract_batch = (((0,), (0,)), ((), ()))
    dwi_s[:] += mm(x_ref[0], dgates, contract_batch)
    dwh_s[:] += mm(hpost_ref[0], dgates, contract_batch)
    db_s[:] += jnp.sum(dgates, axis=0, keepdims=True)

    # Chain through the pre-step reset: grads vanish where done was 1.
    keep = 1.0 - done_ref[0]                       # [B, 1]
    dc_s[:] = dc_prev * keep
    dh_s[:] = dh_prev * keep

    # Constant-index output blocks: written every grid step, the final
    # (t=0) step's values survive.
    dwi_ref[:] = dwi_s[:]
    dwh_ref[:] = dwh_s[:]
    db_ref[0] = db_s[0]
    dc0_ref[:] = dc_s[:]
    dh0_ref[:] = dh_s[:]


def _fwd_call(x, done, c0, h0, wi, wh, b, *, interpret, with_residuals,
              matmul_dtype=jnp.float32):
    unroll_len, batch, in_dim = x.shape
    hidden = c0.shape[-1]
    f32 = jnp.float32
    t_spec = lambda *shape: pl.BlockSpec((1,) + shape, lambda t: (t,) + (0,) * len(shape))
    const = lambda *shape: pl.BlockSpec(shape, lambda t: (0,) * len(shape))
    tb = lambda *shape: jax.ShapeDtypeStruct((unroll_len,) + shape, f32)
    carry_spec, carry_shape = const(batch, hidden), jax.ShapeDtypeStruct(
        (batch, hidden), f32)
    if with_residuals:
        kernel, name = _fwd_kernel, FWD_KERNEL_NAME
        out_specs = (
            t_spec(batch, hidden),           # ys
            t_spec(batch, 4 * hidden),       # ifgo
            t_spec(batch, hidden),           # cpost
            t_spec(batch, hidden),           # hpost
            t_spec(batch, hidden),           # cnew
            carry_spec,                      # cT
            carry_spec,                      # hT
        )
        out_shape = (
            tb(batch, hidden), tb(batch, 4 * hidden), tb(batch, hidden),
            tb(batch, hidden), tb(batch, hidden), carry_shape, carry_shape)
    else:
        kernel, name = _fwd_kernel_lean, STEP_KERNEL_NAME
        out_specs = (t_spec(batch, hidden), carry_spec, carry_spec)
        out_shape = (tb(batch, hidden), carry_shape, carry_shape)
    call = pl.pallas_call(
        functools.partial(kernel, matmul_dtype=matmul_dtype),
        grid=(unroll_len,),
        in_specs=[
            t_spec(batch, in_dim),           # x
            t_spec(batch, 1),                # done [T,B,1]
            carry_spec,                      # c0
            carry_spec,                      # h0
            const(in_dim, 4 * hidden),       # wi
            const(hidden, 4 * hidden),       # wh
            const(1, 4 * hidden),            # b
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((batch, hidden), f32),
            pltpu.VMEM((batch, hidden), f32),
        ],
        interpret=interpret,
        name=name,
    )
    with jax.named_scope(name):
        return call(x, done[..., None], c0, h0, wi, wh, b.reshape(1, -1))


def _bwd_call(residuals, cotangents, *, interpret,
              matmul_dtype=jnp.float32):
    x, done, wi, wh, ifgo, cpost, hpost, cnew = residuals
    dys, dct, dht = cotangents
    unroll_len, batch, in_dim = x.shape
    hidden = cpost.shape[-1]
    f32 = jnp.float32
    rev = lambda *shape: pl.BlockSpec(
        (1,) + shape, lambda k: (unroll_len - 1 - k,) + (0,) * len(shape))
    const = lambda *shape: pl.BlockSpec(shape, lambda k: (0,) * len(shape))
    call = pl.pallas_call(
        functools.partial(_bwd_kernel, matmul_dtype=matmul_dtype),
        grid=(unroll_len,),
        in_specs=[
            rev(batch, hidden),              # dys
            rev(batch, in_dim),              # x
            rev(batch, 1),                   # done [T,B,1]
            rev(batch, 4 * hidden),          # ifgo
            rev(batch, hidden),              # cpost
            rev(batch, hidden),              # hpost
            rev(batch, hidden),              # cnew
            const(in_dim, 4 * hidden),       # wi
            const(hidden, 4 * hidden),       # wh
            const(batch, hidden),            # dcT
            const(batch, hidden),            # dhT
        ],
        out_specs=(
            rev(batch, in_dim),              # dx
            const(in_dim, 4 * hidden),       # dwi
            const(hidden, 4 * hidden),       # dwh
            const(1, 4 * hidden),            # db
            const(batch, hidden),            # dc0
            const(batch, hidden),            # dh0
        ),
        out_shape=(
            jax.ShapeDtypeStruct((unroll_len, batch, in_dim), f32),
            jax.ShapeDtypeStruct((in_dim, 4 * hidden), f32),
            jax.ShapeDtypeStruct((hidden, 4 * hidden), f32),
            jax.ShapeDtypeStruct((1, 4 * hidden), f32),
            jax.ShapeDtypeStruct((batch, hidden), f32),
            jax.ShapeDtypeStruct((batch, hidden), f32),
        ),
        scratch_shapes=[
            pltpu.VMEM((batch, hidden), f32),       # dc carry
            pltpu.VMEM((batch, hidden), f32),       # dh carry
            pltpu.VMEM((in_dim, 4 * hidden), f32),  # dwi accum
            pltpu.VMEM((hidden, 4 * hidden), f32),  # dwh accum
            pltpu.VMEM((1, 4 * hidden), f32),       # db accum
        ],
        interpret=interpret,
        name=BWD_KERNEL_NAME,
    )
    with jax.named_scope(BWD_KERNEL_NAME):
        return call(dys, x, done[..., None], ifgo, cpost, hpost, cnew,
                    wi, wh, dct, dht)


def _resolve_matmul_dtype(matmul_dtype):
    dtype = jnp.dtype(matmul_dtype)
    if dtype not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        raise ValueError(
            f"matmul_dtype must be float32 or bfloat16, got {dtype}")
    return dtype


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def lstm_unroll(x, done, c0, h0, wi, wh, b, interpret=False,
                matmul_dtype="float32"):
    """Fused done-reset LSTM unroll.

    x [T,B,D] float32, done [T,B] float32 (1.0 resets the carry BEFORE
    the step), c0/h0 [B,H], wi [D,4H], wh [H,4H], b [4H] in flax
    OptimizedLSTMCell's (i,f,g,o) gate order.  Returns
    (ys [T,B,H], (cT, hT)).  Differentiable in everything but ``done``.

    ``matmul_dtype``: operand precision for the gate/BPTT matmuls —
    "float32" (bit-exact vs the flax cell) or "bfloat16" (2x MXU rate,
    f32 accumulation).
    """
    ys, ct, ht = _fwd_call(
        x, done, c0, h0, wi, wh, b, interpret=interpret,
        with_residuals=False,
        matmul_dtype=_resolve_matmul_dtype(matmul_dtype))
    return ys, (ct, ht)


def _vjp_fwd(x, done, c0, h0, wi, wh, b, interpret, matmul_dtype):
    ys, ifgo, cpost, hpost, cnew, ct, ht = _fwd_call(
        x, done, c0, h0, wi, wh, b, interpret=interpret,
        with_residuals=True,
        matmul_dtype=_resolve_matmul_dtype(matmul_dtype))
    residuals = (x, done, wi, wh, ifgo, cpost, hpost, cnew)
    return (ys, (ct, ht)), residuals


def _vjp_bwd(interpret, matmul_dtype, residuals, cotangents):
    dys, (dct, dht) = cotangents
    dx, dwi, dwh, db, dc0, dh0 = _bwd_call(
        residuals, (dys, dct, dht), interpret=interpret,
        matmul_dtype=_resolve_matmul_dtype(matmul_dtype))
    ddone = jnp.zeros_like(residuals[1])  # non-differentiable data input
    return dx, ddone, dc0, dh0, dwi, dwh, db.reshape(-1)


lstm_unroll.defvjp(_vjp_fwd, _vjp_bwd)
