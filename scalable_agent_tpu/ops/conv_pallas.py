"""Pallas weight-gradient kernel for the torso's strided stem conv.

``stem_conv`` is the 8x8/stride-4 convolution wrapped in a
``jax.custom_vjp``:

- **forward** and **grad-input** stay XLA's (grad-input is DCE'd
  entirely in the torso, whose stem input needs no gradient),
- **grad-W** is a Pallas MXU kernel that takes both operands THE WAY
  XLA KEEPS THEM.  For a conv over few channels the TPU compiler lays
  activations out batch-minor (the images in the 128 lanes, W or the
  features in the sublanes).  The kernel's operands are declared in
  that order — x as [HP, C, WP, N], the cotangent as [OH, OW, F, N] —
  so the transposes that get them there compile to bitcasts, and the
  only XLA op in front of the call is one fused pad of x (SAME's zero
  borders, W filled out to whole vregs).  A sequential grid walks the
  batch a lane tile at a time; per output row one ``q @ k^T`` matmul
  contracts the images (and the row's column groups, side by side in
  the lanes) of the stacked tap windows [K*C*WIN, ...] against the
  stacked cotangents [JG*F, ...], accumulating a [K*C*WIN, JG*F] band
  in float32 VMEM scratch across grid steps — one revisited
  constant-index output block, the lstm_pallas.py accumulation idiom.
  dW is the band's diagonal, picked out by four slices afterwards.

What it replaced, and why (TPU v5e, the fused cell's 25,856 images in
bf16; PERF.md section 5 has the tables).  Until PR 25 the kernel took
row-major operands: a space-to-depth'd x [N,19,25,48] and g
[N,18,24,32], 48 and 32 channels in 128 lanes.  The call read 7.8 GB
for 1.9 GB of data (9.1 ms), XLA spent 37 ms a step re-laying-out and
batch-padding its operands (``pad.44``, ``pad.45``, ``copy.141``,
``reshape.184``; ledger, PR 24), and the row-major constraint on g
reached back through the ReLU into the forward conv's output and
conv_1's input gradient, each copied into a 3.8 GB lane-padded array:
a 158 ms step where XLA's own lowering gives 52.  XLA's grad-W conv
is one fusion of 8.38 ms in that update; this kernel is a 3.09 ms call
behind a 2.82 ms pad, and the step 48.5 ms (my chip runs, PR 25).  No
operand is padded along the batch: a tile is chosen that divides N
where one does, and a ragged last grid step masks its lanes past N in
the kernel.

Which geometries the kernel takes is decided in ONE place,
``gradw_batch_tile``: it takes ``K % S == 0`` (true for the 8/4 stem)
and a lane tile of images inside the VMEM budget (true for the shallow
stem in either dtype; false for the ResNet 3x3/stride-1 stem at 72x96,
whose cotangent alone is 27 MB per 128 images).  The driver's
``conv_backend=auto`` policy asks it before routing a stem here;
``conv_gradw`` itself REFUSES an unsupported geometry rather than
quietly handing XLA the derivative — a run that says Pallas runs
Pallas.

``stem_conv_handed`` is the same layer for a caller that already holds
its output: the forward returns the handed activation
``relu(conv + b)`` and runs no conv, the backward is the one above
(ISSUE 37: the fused loop's update, whose acting steps computed that
activation 256 images at a time under the parameters being
differentiated).

``interpret`` comes from parallel/mesh.py ``pallas_interpret`` (the one
home of that decision); ``matmul_dtype`` picks the MXU operand
precision ("float32" bit-parity / "bfloat16" 2x rate, f32 accumulation
either way via ``preferred_element_type``).
"""

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Trace/HLO name of the grad-W kernel.  obs/kernels.py keys its
# custom-call FLOPs model on this exact string appearing in the
# instruction's op_name metadata — change them together.
GRADW_KERNEL_NAME = "pallas_conv0_gradw"

# VMEM one grid step may hold, and the scoped limit asked of Mosaic for
# it (the 16 MiB default is a fraction of a v5e core's 128 MiB).  What
# a step holds is modelled by _image_vmem_bytes: AOT compiles for v5e
# take the shallow stem at 256 images a step in bf16 and 128 in f32;
# the ResNet stem does not fit at 128.
_VMEM_BUDGET_BYTES = 48 << 20
_VMEM_LIMIT_BYTES = 64 << 20
_MAX_BATCH_TILE = 512
_LANES = 128


def _resolve_matmul_dtype(matmul_dtype):
    dtype = jnp.dtype(matmul_dtype)
    if dtype not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        raise ValueError(
            f"matmul_dtype must be float32 or bfloat16, got {dtype}")
    return dtype


def _forward(x, w, stride, normalize=None):
    if normalize:
        # The raw-frame entry: see parallel/mesh.py frames_batch_minor.
        from scalable_agent_tpu.parallel.mesh import frames_batch_minor

        x = frames_batch_minor(x)
    return lax.conv_general_dilated(
        normalize(x) if normalize else x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _same_pads(size, k, s):
    """XLA SAME padding: out = ceil(size/s); lo gets the smaller half."""
    out = -(-size // s)
    total = max((out - 1) * s + k - size, 0)
    return out, (total // 2, total - total // 2)


def _round_up(x, m):
    return -(-x // m) * m


def _sublanes(dtype):
    """Rows of one vreg at this width: 8 x 32 bits, narrower types
    packed along the sublanes."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


class _Geometry(NamedTuple):
    """How one conv's grad-W is laid on the MXU (see _gradw_kernel)."""
    out_h: int
    out_w: int
    pads_h: Tuple[int, int]
    pad_w_lo: int
    group: int      # JG: output columns contracted by one matmul
    groups: int     # OW / JG
    window: int     # WIN: input columns one group's taps span, in vregs
    width: int      # W as handed to the kernel: every window in range


def _geometry(h, w_in, f, k, s, dtype) -> _Geometry:
    out_h, pads_h = _same_pads(h, k, s)
    out_w, (pad_w_lo, _) = _same_pads(w_in, k, s)
    # As many output columns as fill the MXU's 128 result lanes with
    # (column, feature) pairs — and divide OW, so no group is ragged.
    # For the 8/4 stem: 4 columns, windows 16 input columns apart and
    # 32 wide — every one starts and ends on a vreg boundary.
    group = max(j for j in range(1, out_w + 1)
                if out_w % j == 0 and (j * f <= _LANES or j == 1))
    groups = out_w // group
    window = _round_up(s * (group - 1) + k, _sublanes(dtype))
    return _Geometry(out_h, out_w, pads_h, pad_w_lo, group, groups,
                     window, s * group * (groups - 1) + window)


def _gradw_kernel(x_ref, g_ref, acc_ref, acc_s, *, kernel_size, stride,
                  geometry, images, matmul_dtype):
    """One batch tile of the grad-W contraction, the batch in the lanes.

    x_ref [HP, C, WP, BN] is the zero-padded input and g_ref
    [OH, OW, F, BN] the output cotangent, both as XLA itself keeps a
    few-channel conv's activations (batch minor, W or F in the
    sublanes): no operand is re-laid-out to get here.  Output columns
    are taken JG at a time.  Group ``q`` of output row ``i`` needs, for
    each tap row kh and channel c, input columns ``S*JG*q .. +WIN`` of
    padded row ``S*i + kh`` — a [WIN, BN] slab of whole vregs.  The
    K*C slabs stack into [K*C*WIN, BN]; the group's cotangents stack
    into [JG*F, BN]; the groups of one output row sit side by side in
    the lanes, and one q @ k^T matmul contracts images and groups at
    once into [K*C*WIN, JG*F], accumulated in f32 scratch.  Entry
    ``[(kh, c, S*jj + kw), (jj, f)]`` of it is the weight gradient's
    ``[kh, kw, c, f]`` share from every JG-th output column, the
    jj-th on; the rest of the band is the price of feeding the MXU whole vregs (K/WIN
    of its work is kept).  The constant-index acc_ref block is written
    every step (last survives).  ``images`` is N: a ragged last step
    masks the lanes past it in BOTH operands (what a block reads out of
    bounds is not zeros, and 0 * NaN is NaN)."""
    step = pl.program_id(0)
    k, s, geo = kernel_size, stride, geometry
    channels, bn = x_ref.shape[1], x_ref.shape[3]

    @pl.when(step == 0)
    def _():
        acc_s[...] = jnp.zeros_like(acc_s)

    def contract(valid):
        def load(ref, *index):
            tile = ref[index]
            if valid is not None:
                lane = lax.broadcasted_iota(jnp.int32, tile.shape, 1)
                tile = jnp.where(lane < valid, tile, jnp.zeros_like(tile))
            return tile.astype(matmul_dtype)

        def row(i, carry):
            taps, cots = [], []
            for q in range(geo.groups):
                columns = pl.ds(s * geo.group * q, geo.window)
                taps.append(jnp.concatenate(
                    [load(x_ref, s * i + kh, c, columns)
                     for kh in range(k) for c in range(channels)],
                    axis=0))
                cots.append(jnp.concatenate(
                    [load(g_ref, i, geo.group * q + jj)
                     for jj in range(geo.group)], axis=0))
            acc_s[...] += lax.dot_general(
                jnp.concatenate(taps, axis=1),
                jnp.concatenate(cots, axis=1),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            return carry

        lax.fori_loop(0, geo.out_h, row, 0)

    ragged = images % bn
    if ragged:
        last = pl.num_programs(0) - 1
        pl.when(step == last)(lambda: contract(ragged))
        pl.when(step != last)(lambda: contract(None))
    else:
        contract(None)
    acc_ref[...] = acc_s[...]


def _image_vmem_bytes(geo, hp, c, f, dtype):
    """VMEM one image (one lane) costs a grid step: its column of both
    operand blocks, double-buffered by the pipeline.  WP is whole vregs
    by construction; F is rounded up to them."""
    return 2 * jnp.dtype(dtype).itemsize * (
        hp * c * geo.width
        + geo.out_h * geo.out_w * _round_up(f, _sublanes(dtype)))


def gradw_batch_tile(x_shape, features, kernel_size, stride,
                     dtype) -> int:
    """Images per grid step for the grad-W kernel at this geometry, or
    0 when the kernel does not take it: ``kernel_size % stride != 0``
    (no limit of this formulation — the cut the kernel has had since
    its space-to-depth days, kept because no stem needs it lifted and
    no chip has run one), or not even one lane tile of images fits the
    VMEM budget (the ResNet 3x3/1 stem: its
    72x96x16 cotangent alone is 27 MB per 128 images).  The images are
    the lane dim of both operands, so a tile short of N is a multiple
    of 128 — the one whose last grid step runs past N by the fewest
    images, the largest of equals: one that divides N when one does
    (25,856 = 101 x 256: no step is ragged), and for the rest (6,464 =
    50.5 x 128) the kernel masks the lanes past N in its last step.
    Nothing is ever padded in HBM to round the batch.  The ONE support predicate — the
    driver's ``conv_backend`` policy and ``conv_gradw`` both ask here.
    ``dtype`` is the activations' (x and its cotangent); the MXU
    operands' sizes nothing (they are cast a vreg at a time)."""
    n, h, w_in, c = x_shape
    k, s = int(kernel_size), int(stride)
    if k % s != 0:
        return 0
    geo = _geometry(h, w_in, features, k, s, dtype)
    per_image = _image_vmem_bytes(
        geo, h + sum(geo.pads_h), c, features, dtype)
    fit = _VMEM_BUDGET_BYTES // per_image
    if fit < min(n, _LANES):
        return 0
    cap = min(n, _MAX_BATCH_TILE, fit)
    if cap == n or cap < _LANES:    # under a lane tile: the tests' cap
        return cap
    return min(range(cap // _LANES * _LANES, 0, -_LANES),
               key=lambda tile: gradw_padded_images(n, tile))


def gradw_padded_images(n, tile) -> int:
    """Lanes of the last grid step past image N — masked in the kernel,
    never copied or padded in HBM.  0 when the tile divides N."""
    return -n % tile if tile else 0


def conv_gradw(x, g, kernel_size, stride, interpret=False,
               matmul_dtype="float32", normalize=None):
    """Weight gradient of the SAME-padded ``kernel_size``/``stride``
    conv: x [N,H,W,C], g [N,OH,OW,F] -> dW [K,K,C,F] float32.  With
    ``normalize`` the conv's input is ``normalize(x)`` (``stem_conv``'s
    raw-frame entry).  Raises ValueError for a geometry
    ``gradw_batch_tile`` does not take."""
    matmul_dtype = _resolve_matmul_dtype(matmul_dtype)
    n, h, w_in, c = x.shape
    f = g.shape[-1]
    k, s = int(kernel_size), int(stride)
    dtype = (jax.eval_shape(normalize, x).dtype if normalize
             else x.dtype)
    bn = gradw_batch_tile(x.shape, f, k, s, dtype)
    if bn == 0:
        raise ValueError(
            f"the Pallas grad-W kernel does not take a {k}x{k}/stride-"
            f"{s} conv over {h}x{w_in}x{c} {dtype} frames "
            f"(kernel_size % stride must be 0 and a lane tile of "
            f"images must fit VMEM); use conv_backend=xla or auto")

    geo = _geometry(h, w_in, f, k, s, dtype)
    # The one pass over x: SAME's zero borders, W filled out so that
    # every group's window is in range.  A raw frame is normalised
    # AFTER the pad (the borders stay zero: normalize(0) is 0), so this
    # normalisation is an expression of its own that XLA fuses with
    # the pad, and the forward conv keeps its own fused: sharing one
    # normalised copy between them costs two extra passes over the
    # frames (5.0 ms a step; my chip run, PR 25).  The transposes put
    # the batch minor — where XLA already keeps it — and move nothing.
    xt = jnp.pad(x, ((0, 0), geo.pads_h,
                     (geo.pad_w_lo, geo.width - w_in - geo.pad_w_lo),
                     (0, 0)))
    if normalize:
        xt = normalize(xt)
    xt = xt.transpose(1, 3, 2, 0)
    gt = g.transpose(1, 2, 3, 0)
    band = (k * c * geo.window, geo.group * f)
    with jax.named_scope(GRADW_KERNEL_NAME):
        acc = pl.pallas_call(
            functools.partial(
                _gradw_kernel, kernel_size=k, stride=s, geometry=geo,
                images=n, matmul_dtype=matmul_dtype),
            grid=(-(-n // bn),),
            in_specs=[
                pl.BlockSpec(xt.shape[:3] + (bn,), lambda i: (0, 0, 0, i)),
                pl.BlockSpec(gt.shape[:3] + (bn,), lambda i: (0, 0, 0, i)),
            ],
            out_specs=pl.BlockSpec(band, lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct(band, jnp.float32),
            scratch_shapes=[pltpu.VMEM(band, jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_VMEM_LIMIT_BYTES),
            interpret=interpret,
            name=GRADW_KERNEL_NAME,
        )(xt, gt)
    # The band's diagonal: output column jj of a group saw tap kw at
    # window column S*jj + kw.
    acc = acc.reshape(k, c, geo.window, geo.group, f)
    dw = sum(acc[:, :, s * jj:s * jj + k, jj, :]
             for jj in range(geo.group))
    return dw.transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def stem_conv(x, w, stride=4, interpret=False, matmul_dtype="float32",
              normalize=None):
    """SAME-padded NHWC conv (x [N,H,W,C], w [K,K,C,F], square stride)
    whose weight gradient is the Pallas kernel above.  Forward and
    input gradient are XLA's — numerically this op IS
    ``lax.conv_general_dilated(..., "SAME")``; only d/dW's lowering
    differs.  ``interpret`` and ``matmul_dtype`` follow
    ops/lstm_pallas.py's contract.  Only for geometries
    ``gradw_batch_tile`` takes: the backward pass raises otherwise.

    ``normalize`` is the raw-frame entry: x is the frame as the torso
    was given it (uint8, no gradient), the conv's input is
    ``normalize(x)`` — an elementwise map with ``normalize(0) == 0``,
    the torso's own, so the forward and the kernel see the same value
    of every pixel — and the residual is the frame itself."""
    return _forward(x, w, stride, normalize)


def _vjp_fwd(x, w, stride, interpret, matmul_dtype, normalize):
    return _forward(x, w, stride, normalize), (x, w)


def _vjp_bwd(stride, interpret, matmul_dtype, normalize, residuals, g):
    x, w = residuals
    if jnp.issubdtype(x.dtype, jnp.floating):
        # Input gradient: XLA's transposed conv.  In the torso the
        # stem's input is the gradient-free frame, so this whole
        # branch is dead code XLA eliminates; it exists for standalone
        # parity.
        _, vjp_x = jax.vjp(
            lambda xx: _forward(xx, w, stride, normalize), x)
        dx = vjp_x(g)[0]
    else:
        dx = np.zeros(x.shape, jax.dtypes.float0)
    dw = conv_gradw(x, g, w.shape[0], stride, interpret=interpret,
                    matmul_dtype=matmul_dtype, normalize=normalize)
    return dx, dw.astype(w.dtype)


stem_conv.defvjp(_vjp_fwd, _vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def stem_conv_handed(x, w, b, activation, stride=4, interpret=False,
                     matmul_dtype="float32", normalize=None):
    """``relu(stem_conv(x, w, ...) + b)`` for a caller that already
    holds it: ``activation`` [N,OH,OW,F] IS that value, computed from
    this ``x`` under this ``w`` and ``b`` by an earlier call (the fused
    loop's acting steps run the stem over every frame the update then
    reads, under the parameters the update differentiates —
    runtime/ingraph.py).  The forward returns it and runs no conv.  The
    backward is the one ``relu(stem_conv(...) + b)`` has: the ReLU's
    mask read off the activation itself (``relu(z) > 0`` where
    ``z > 0``), the bias gradient the masked cotangent's sum, the
    weight gradient ``conv_gradw`` of the frame and the masked
    cotangent.  ``activation`` gets no cotangent: it is the same
    function of ``w`` and ``b`` that is being differentiated here, and
    whoever made it differentiates nothing through it.  Handing in
    anything else is silently another function."""
    del x, w, b
    return activation


def _handed_fwd(x, w, b, activation, stride, interpret, matmul_dtype,
                normalize):
    return activation, (x, w, b, activation)


def _handed_bwd(stride, interpret, matmul_dtype, normalize, residuals, g):
    x, w, b, activation = residuals
    g = jnp.where(activation > 0, g, jnp.zeros_like(g))
    dx, dw = _vjp_bwd(stride, interpret, matmul_dtype, normalize, (x, w), g)
    # the bias's broadcast transposed, as ``conv + b`` transposes it
    # (``jnp.sum`` would accumulate a bfloat16 sum in float32)
    db, = jax.vjp(lambda bias: jnp.broadcast_to(bias, g.shape), b)[1](g)
    return dx, dw, db, jnp.zeros_like(activation)


stem_conv_handed.defvjp(_handed_fwd, _handed_bwd)
