"""Pallas weight-gradient kernel for the torso's strided stem conv.

The per-kernel roofline ledger names ``conv0_gradw`` as the learner's
worst kernel: XLA lowers the 8x8/stride-4 stem's weight gradient to a
kernel last measured at 0.107 MFU for ~13 ms at the B=256 merged batch
(ROADMAP, "What the record says"), and the space-to-depth reformulation
made it WORSE (0.047) because it only helps the input gradient — which
the stem, fed by the gradient-free uint8 frame, never computes.  This
module attacks the weight gradient directly.

``stem_conv`` is the SAME 8x8/stride-4 convolution wrapped in a
``jax.custom_vjp``:

- **forward** and **grad-input** stay XLA's (both already run near the
  layer's output-lane ceiling; grad-input is DCE'd entirely in the
  torso, whose stem input needs no gradient),
- **grad-W** is a Pallas im2col-tiled MXU matmul.  The padded input is
  re-laid-out once (space-to-depth by the stride S, so every kernel tap
  becomes a CONTIGUOUS slice), then a sequential grid over the batch
  gathers per-tile patch matrices ``P [BN*OH*OW, K*K*Cin]`` from D*D
  static slices (D = K/S), contracts them against the output cotangent
  ``G [BN*OH*OW, Cout]`` on the MXU, and accumulates ``[K*K*Cin, Cout]``
  in float32 VMEM scratch across grid steps — one revisited
  constant-index output block, exactly the lstm_pallas.py accumulation
  idiom.

Why this beats XLA's lowering: XLA derives grad-W as a conv with the
8x8 kernel dims mapped to the *spatial output* of a big dilated
convolution — a shape (8x8 "image", 32 lanes) that strands most of the
MXU.  Here the contraction is a single [K*K*Cin, N*OH*OW] x
[N*OH*OW, Cout] matmul with the huge merged batch as the contracting
dimension, which is the shape the MXU was built for.

Which geometries the kernel takes is decided in ONE place,
``gradw_batch_tile``: it needs ``K % S == 0`` (D = K/S; true for the
8/4 stem) and one image's working set inside the VMEM budget (true for
the shallow stem in either dtype; false for the ResNet 3x3/stride-1
stem at 72x96, whose 3-channel taps pad 3 -> 128 lanes).  The driver's
``conv_backend=auto`` policy asks it before routing a stem here;
``conv_gradw`` itself REFUSES an unsupported geometry rather than
quietly handing XLA the derivative — a run that says Pallas runs
Pallas.

``interpret`` comes from parallel/mesh.py ``pallas_interpret`` (the one
home of that decision); ``matmul_dtype`` picks the MXU operand
precision ("float32" bit-parity / "bfloat16" 2x rate, f32 accumulation
either way via ``preferred_element_type``).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Trace/HLO name of the grad-W kernel.  obs/kernels.py keys its
# custom-call FLOPs model on this exact string appearing in the
# instruction's op_name metadata — change them together.
GRADW_KERNEL_NAME = "pallas_conv0_gradw"

# Scoped-VMEM budget for one grid step, out of the 16 MiB Mosaic grants
# a kernel on a v5e by default.  What the step holds is modelled by
# _image_vmem_bytes below — deliberately the worst case (every tap
# gather live at once): AOT compiles for v5e showed the shallow stem
# compiling up to BN=17 (bf16) / BN=8 (f32) where this model stops at
# 11 / 5, and the ResNet stem failing even at BN=1 where it says so.
_VMEM_BUDGET_BYTES = 14 << 20
_MAX_BATCH_TILE = 32
_LANES, _SUBLANES = 128, 8


def _resolve_matmul_dtype(matmul_dtype):
    dtype = jnp.dtype(matmul_dtype)
    if dtype not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        raise ValueError(
            f"matmul_dtype must be float32 or bfloat16, got {dtype}")
    return dtype


def _forward(x, w, stride):
    return lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _same_pads(size, k, s):
    """XLA SAME padding: out = ceil(size/s); lo gets the smaller half."""
    out = -(-size // s)
    total = max((out - 1) * s + k - size, 0)
    return out, (total // 2, total - total // 2)


def _gradw_kernel(xs_ref, g_ref, dw_ref, acc_s, *, depth, out_h, out_w,
                  matmul_dtype):
    """One batch tile of the grad-W contraction.

    xs_ref [BN, OH+D-1, OW+D-1, S*S*C] — space-to-depth input; each
    kernel tap (dh, dw) of the ORIGINAL conv is the contiguous slice
    ``xs[:, dh:dh+OH, dw:dw+OW, :]``.  g_ref [BN, OH, OW, F] is the
    output cotangent.  Accumulates [D*D*S*S*C, F] in f32 scratch; the
    constant-index dw_ref block is written every step (last survives).
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        acc_s[...] = jnp.zeros_like(acc_s)

    bn = xs_ref.shape[0]
    s2c = xs_ref.shape[-1]
    f = g_ref.shape[-1]
    rows = bn * out_h * out_w
    patches = [
        xs_ref[:, dh:dh + out_h, dw:dw + out_w, :].reshape(rows, s2c)
        for dh in range(depth) for dw in range(depth)
    ]
    p = jnp.concatenate(patches, axis=-1).astype(matmul_dtype)
    g = g_ref[...].reshape(rows, f).astype(matmul_dtype)
    # [D*D*S*S*C, BN*OH*OW] x [BN*OH*OW, F]: the merged batch is the
    # contracting dim — the MXU-shaped form of grad-W.
    acc_s[...] += lax.dot_general(
        p, g, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dw_ref[...] = acc_s[...]


def _round_up(x, m):
    return -(-x // m) * m


def _image_vmem_bytes(tile_h, tile_w, s2c, out_h, out_w, f, depth,
                      itemsize, mm_itemsize):
    """Scoped VMEM one image costs a grid step, in the (8, 128)-tiled
    layout Mosaic allocates (minor dim padded to 128 lanes, second-minor
    to 8 sublanes — what a float count misses by 2.7x on a 48-lane
    block and 42x on a 3-lane one): both input blocks double-buffered
    by the pipeline, the D*D tap gathers, the concatenated patch matrix
    and the flattened cotangent."""
    inputs = 2 * itemsize * (
        tile_h * _round_up(tile_w, _SUBLANES) * _round_up(s2c, _LANES)
        + out_h * _round_up(out_w, _SUBLANES) * _round_up(f, _LANES))
    rows = out_h * out_w
    taps = depth * depth
    gathers = taps * rows * _round_up(s2c, _LANES) * mm_itemsize
    patches = rows * _round_up(taps * s2c, _LANES) * mm_itemsize
    cotangent = rows * _round_up(f, _LANES) * mm_itemsize
    return inputs + gathers + patches + cotangent


def gradw_batch_tile(x_shape, features, kernel_size, stride, dtype,
                     matmul_dtype=None) -> int:
    """Images per grid step for the grad-W kernel at this geometry, or
    0 when the kernel does not take it: ``kernel_size % stride != 0``
    (the D-slice gather needs every tap on the s2d lattice), or a
    single image's working set already exceeds the VMEM budget.  The
    ONE support predicate — the driver's ``conv_backend`` policy and
    ``conv_gradw`` both ask here.  ``dtype`` is the activations' (x and
    its cotangent); ``matmul_dtype`` the MXU operands', ``dtype``'s
    own when omitted (PallasStemConv's default)."""
    n, h, w_in, c = x_shape
    k, s = int(kernel_size), int(stride)
    if k % s != 0:
        return 0
    depth = k // s
    out_h, _ = _same_pads(h, k, s)
    out_w, _ = _same_pads(w_in, k, s)
    per_image = _image_vmem_bytes(
        out_h + depth - 1, out_w + depth - 1, s * s * c, out_h, out_w,
        features, depth, jnp.dtype(dtype).itemsize,
        jnp.dtype(matmul_dtype or dtype).itemsize)
    # The [K*K*C, F] f32 accumulator: output block (double-buffered)
    # plus the scratch copy.
    fixed = 3 * _round_up(k * k * c, _SUBLANES) * _round_up(
        features, _LANES) * 4
    return max(0, min(n, _MAX_BATCH_TILE,
                      (_VMEM_BUDGET_BYTES - fixed) // per_image))


def conv_gradw(x, g, kernel_size, stride, interpret=False,
               matmul_dtype="float32"):
    """Weight gradient of the SAME-padded ``kernel_size``/``stride``
    conv: x [N,H,W,C], g [N,OH,OW,F] -> dW [K,K,C,F] float32.  Raises
    ValueError for a geometry ``gradw_batch_tile`` does not take."""
    matmul_dtype = _resolve_matmul_dtype(matmul_dtype)
    n, h, w_in, c = x.shape
    _, out_h, out_w, f = g.shape
    k, s = int(kernel_size), int(stride)
    bn = gradw_batch_tile(x.shape, f, k, s, x.dtype, matmul_dtype)
    if bn == 0:
        raise ValueError(
            f"the Pallas grad-W kernel does not take a {k}x{k}/stride-"
            f"{s} conv over {h}x{w_in}x{c} {x.dtype} frames "
            f"(kernel_size % stride must be 0 and one image's tiles "
            f"must fit VMEM); use conv_backend=xla or auto")

    depth = k // s
    _, (ph_lo, ph_hi) = _same_pads(h, k, s)
    _, (pw_lo, pw_hi) = _same_pads(w_in, k, s)
    xp = jnp.pad(x, ((0, 0), (ph_lo, ph_hi), (pw_lo, pw_hi), (0, 0)))
    hp, wp = xp.shape[1], xp.shape[2]
    # Space-to-depth by the stride: [N, HP/S, WP/S, S*S*C], depth rows
    # ordered (sh, sw, c).  HP = (OH-1)*S + K = (OH+D-1)*S exactly, so
    # the lattice always divides.
    xs = xp.reshape(n, hp // s, s, wp // s, s, c)
    xs = xs.transpose(0, 1, 3, 2, 4, 5).reshape(
        n, hp // s, wp // s, s * s * c)
    tile_h, tile_w = out_h + depth - 1, out_w + depth - 1
    s2c = s * s * c
    n_pad = -(-n // bn) * bn
    if n_pad != n:
        # Zero-padded images contribute zero cotangent rows — exact.
        xs = jnp.pad(xs, ((0, n_pad - n), (0, 0), (0, 0), (0, 0)))
        g = jnp.pad(g, ((0, n_pad - n), (0, 0), (0, 0), (0, 0)))
    rows_out = depth * depth * s2c
    with jax.named_scope(GRADW_KERNEL_NAME):
        dw = pl.pallas_call(
            functools.partial(
                _gradw_kernel, depth=depth, out_h=out_h, out_w=out_w,
                matmul_dtype=matmul_dtype),
            grid=(n_pad // bn,),
            in_specs=[
                pl.BlockSpec((bn, tile_h, tile_w, s2c),
                             lambda i: (i, 0, 0, 0)),
                pl.BlockSpec((bn, out_h, out_w, f),
                             lambda i: (i, 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((rows_out, f), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((rows_out, f), jnp.float32),
            scratch_shapes=[pltpu.VMEM((rows_out, f), jnp.float32)],
            interpret=interpret,
            name=GRADW_KERNEL_NAME,
        )(xs, g)
    # Rows are ordered (dh, dw, sh, sw, c); kh = dh*S + sh.
    dw = dw.reshape(depth, depth, s, s, c, f).transpose(0, 2, 1, 3, 4, 5)
    return dw.reshape(k, k, c, f)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def stem_conv(x, w, stride=4, interpret=False, matmul_dtype="float32"):
    """SAME-padded NHWC conv (x [N,H,W,C], w [K,K,C,F], square stride)
    whose weight gradient is the Pallas im2col kernel above.  Forward
    and input gradient are XLA's — numerically this op IS
    ``lax.conv_general_dilated(..., "SAME")``; only d/dW's lowering
    differs.  ``interpret`` and ``matmul_dtype`` follow
    ops/lstm_pallas.py's contract.  Only for geometries
    ``gradw_batch_tile`` takes: the backward pass raises otherwise."""
    return _forward(x, w, stride)


def _vjp_fwd(x, w, stride, interpret, matmul_dtype):
    return _forward(x, w, stride), (x, w)


def _vjp_bwd(stride, interpret, matmul_dtype, residuals, g):
    x, w = residuals
    # Input gradient: XLA's transposed conv.  In the torso the stem's
    # input is the gradient-free normalized frame, so this whole branch
    # is dead code XLA eliminates; it exists for standalone parity.
    _, vjp_x = jax.vjp(lambda xx: _forward(xx, w, stride), x)
    dx = vjp_x(g)[0]
    dw = conv_gradw(x, g, w.shape[0], stride, interpret=interpret,
                    matmul_dtype=matmul_dtype)
    return dx, dw.astype(w.dtype)


stem_conv.defvjp(_vjp_fwd, _vjp_bwd)
