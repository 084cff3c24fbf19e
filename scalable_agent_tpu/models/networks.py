"""Visual torsos for the IMPALA agent.

Two variants, matching the reference's two (one active, one commented-out):

- ``ShallowConvTorso``: the 3-layer conv stack the fork actually runs —
  (32, 8x8, /4), (64, 4x4, /2), (128, 3x3, /2), each ReLU, then
  flatten → Dense(256) → ReLU (reference: experiment.py:178-189).
- ``ResNetTorso``: the deep IMPALA ResNet the fork keeps commented out —
  3 sections of [conv3x3 → maxpool/2 → 2 residual blocks] with channels
  (16, 32, 32) (reference: experiment.py:156-176).

TPU notes: callers flatten [T, B] into one [T*B] batch before the torso so
every conv/matmul hits the MXU with the largest possible batch; compute can
run in bfloat16 (``dtype``) with float32 params.
"""

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

# Stem-conv backends every torso accepts (``--conv_backend``).  "xla"
# is the plain nn.Conv lowering; "pallas" swaps ONLY the weight
# gradient for the Pallas MXU kernel (ops/conv_pallas.py) — forward
# math is identical, parameter trees are identical, checkpoints are
# interchangeable.  The (negative-result) space-to-depth formulation
# is deliberately NOT in this registry: it stays reachable via
# ``ShallowConvTorso(space_to_depth=True)`` as documentation of the
# measurement (see _SpaceToDepthFirstConv), but it is retired from
# the flag surface.
CONV_BACKENDS = ("xla", "pallas")

# Each torso's stem conv as (features, kernel_size, stride) — read by
# the torsos below and by the driver's conv_backend policy, which asks
# ops/conv_pallas.gradw_batch_tile whether the Pallas grad-W kernel
# takes that geometry at the run's frame size and dtype.
STEM_GEOMETRY = {
    "shallow": (32, 8, 4),
    "resnet": (16, 3, 1),
}


# Where a torso built with ``remat=True`` puts its ``jax.checkpoint``
# boundary (each torso's ``remat_placement``; the driver's kernel-policy
# line names it).  One checkpoint around a WHOLE torso recomputes the
# entire forward before the first backward op can run, and at that
# moment every residual of the torso is live at once, as if nothing had
# been rematerialised: it frees only what would otherwise sit across
# the core, the heads and the loss, for the price of a whole forward
# (ISSUE 27: 0.50 of 10.2 GiB on the ResNet at the fused cell's batch,
# nothing behind the Pallas stem).  So the boundary goes around the
# segment whose residuals are large and cheap to rebuild, and each
# torso knows its own.
REMAT_PLACEMENTS = ("none", "stem", "torso")

# The variable collection a torso sows into what an update over the same
# frames under the same parameters can take in place of computing it
# again (``ShallowConvTorso.hands_stem``).  Sown only where a caller
# makes the collection mutable; nothing of it is a parameter.
HANDOVER = "handover"


def _normalize_frame(frame, dtype):
    """uint8 HWC frame -> [0, 1] float.  (reference: experiment.py:153-155)"""
    return jnp.asarray(frame, dtype) / 255.0


def _stem_input(frame, dtype):
    """The normalised frames as an XLA stem conv's input: the layout
    hint first, on the bytes (the Pallas stem's forward gives the same
    one inside ``stem_conv``)."""
    from scalable_agent_tpu.parallel.mesh import frames_batch_minor

    return _normalize_frame(frames_batch_minor(frame), dtype)


def space_to_depth_rearrange(x, kernel):
    """The stem's space-to-depth re-indexing, as one pure function:
    ``(x [N,H,W,C], kernel [8,8,C,F]) -> (x' [N,bh,bw,16C],
    k' [2,2,16C,F])`` such that a VALID 2x2/stride-1 conv of the primed
    pair equals the SAME 8x8/stride-4 conv of the originals.  Shared by
    ``_SpaceToDepthFirstConv`` and bench.py's cross-round conv
    diagnostic so the published timing always measures the shipped
    formulation."""
    n, height, width, c = x.shape
    f = kernel.shape[-1]

    # SAME padding for kernel 8 / stride 4; the padded extent
    # (ceil(d/4) + 1) * 4 is always a multiple of the block size.
    def pads(size):
        total = max(0, (-(-size // 4) - 1) * 4 + 8 - size)
        return total // 2, total - total // 2

    x = jnp.pad(x, ((0, 0), pads(height), pads(width), (0, 0)))
    bh, bw = x.shape[1] // 4, x.shape[2] // 4
    x = x.reshape(n, bh, 4, bw, 4, c).transpose(0, 1, 3, 2, 4, 5)
    x = x.reshape(n, bh, bw, 16 * c)
    # kernel (kh, kw) -> (block, in-block) pairs, matching the
    # (ph, pw, c) channel order the input rearrangement produced.
    k = kernel.reshape(2, 4, 2, 4, c, f)
    k = k.transpose(0, 2, 1, 3, 4, 5).reshape(2, 2, 16 * c, f)
    return x, k


class _SpaceToDepthFirstConv(nn.Module):
    """The torso's 8x8/stride-4 stem conv, computed as space-to-depth(4)
    + a 2x2/stride-1 conv — the classic TPU reformulation for
    small-channel strided stems.  Measured on v5e at the bench shapes
    (0.047 vs 0.107 MFU — ROADMAP D5), it is a NEGATIVE result for THIS
    architecture and stays off by default: the win only exists when the
    conv's input gradient is computed (3.4x there), but the stem's
    input is the uint8 frame — a gradient-free leaf — and with
    weights-only backward the direct form is 2.3x FASTER than s2d
    (XLA's native lowering already runs at the layer's output-lane
    ceiling, and the explicit 1 GB block transpose is pure added HBM
    traffic).  Kept because the measurement matters and because other
    torso stacks (an image-gradient consumer) may want it.

    Parameter tree, shapes, and initializers are IDENTICAL to the
    ``nn.Conv(32, (8, 8), strides=4, padding="SAME")`` it replaces —
    kernel [8, 8, C, F] + bias under the same module name — so
    checkpoints are interchangeable both ways, and the rearrangement is
    a pure re-indexing (numerically equal output up to contraction
    order; tests/test_networks.py)."""

    features: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        c = x.shape[-1]
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (8, 8, c, self.features))
        bias = self.param("bias", nn.initializers.zeros_init(),
                          (self.features,))
        x, k = space_to_depth_rearrange(x, kernel)
        x, k, b = (jnp.asarray(t, self.dtype) for t in (x, k, bias))
        out = jax.lax.conv_general_dilated(
            x, k, (1, 1), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return out + b


class PallasStemConv(nn.Module):
    """A SAME-padded strided conv whose weight gradient is the Pallas
    kernel of ops/conv_pallas.py (``stem_conv``).  Forward and input
    gradient are XLA's own — numerically this IS the ``nn.Conv`` it
    replaces; only d/dW's lowering changes: the kernel reads the
    normalised frame and the cotangent batch-minor, as XLA keeps them,
    so nothing is re-laid-out or batch-padded on the way in (on a v5e
    in the fused cell's update, 25,856 bf16 images: a 3.09 ms call
    behind one 2.82 ms fused pad, against 8.38 ms for XLA's own grad-W
    fusion; my chip runs, PR 25 — PERF.md section 5).  Parameter tree,
    shapes, and initializers are IDENTICAL to
    ``nn.Conv(features, (k, k), strides=s, padding="SAME")`` — kernel
    [k, k, C, F] + bias under the same module name — so checkpoints
    are interchangeable both ways (the _SpaceToDepthFirstConv
    contract, tests/test_conv_pallas.py pins it).

    Interpreted or compiled is parallel/mesh.py ``pallas_interpret``'s
    call (the one home of that decision), so CPU tier-1 exercises the
    same kernel body.  MXU operand precision follows ``dtype``: a bfloat16
    module runs bf16 operands with f32 accumulation; override with
    ``matmul_dtype`` to decouple them.  ``normalize`` is the torso's
    raw-frame entry: the module is then called on the frame as the
    torso got it (uint8) and the op applies ``normalize`` itself, where
    the forward conv and the kernel's one pad can each fuse it in;
    without it the input is the conv's input as it stands.

    ``handed`` is ``relu`` of this layer's output for this ``x`` as an
    earlier call under THESE parameters computed it: the call then
    returns it — past the ReLU, no conv run — with the gradients
    ``relu(self(x))`` gives the kernel and the bias
    (ops/conv_pallas.py ``stem_conv_handed``)."""

    features: int
    kernel_size: int = 8
    stride: int = 4
    dtype: Any = jnp.float32
    matmul_dtype: Optional[str] = None
    normalize: Optional[Callable] = None

    @nn.compact
    def __call__(self, x, handed=None):
        # Lazy like _PallasCore: XLA-only consumers never pay (or
        # depend on) the Pallas TPU imports.
        from scalable_agent_tpu.ops import conv_pallas
        from scalable_agent_tpu.parallel.mesh import pallas_interpret

        c = x.shape[-1]
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (self.kernel_size, self.kernel_size, c, self.features))
        bias = self.param("bias", nn.initializers.zeros_init(),
                          (self.features,))
        k, b = (jnp.asarray(t, self.dtype) for t in (kernel, bias))
        if self.normalize is None:
            x = jnp.asarray(x, self.dtype)
        matmul_dtype = self.matmul_dtype or (
            "bfloat16" if jnp.dtype(self.dtype) == jnp.dtype(jnp.bfloat16)
            else "float32")
        if handed is not None:
            return conv_pallas.stem_conv_handed(
                x, k, b, jnp.asarray(handed, self.dtype), self.stride,
                pallas_interpret(), matmul_dtype, self.normalize)
        out = conv_pallas.stem_conv(
            x, k, self.stride, pallas_interpret(), matmul_dtype,
            self.normalize)
        return out + b


def _stem_backend(conv_backend):
    if conv_backend not in CONV_BACKENDS:
        raise ValueError(
            f"unknown conv_backend: {conv_backend!r} "
            f"(choices: {CONV_BACKENDS})")
    return conv_backend == "pallas"


class ShallowConvTorso(nn.Module):
    """(32,8,4), (64,4,2), (128,3,2) conv stack + Dense(256).

    Input [N, H, W, C] uint8; output [N, 256] float32.
    (reference: experiment.py:178-189)

    ``conv_backend`` ("xla" | "pallas") picks the stem conv's grad-W
    lowering (see CONV_BACKENDS); ``space_to_depth`` computes the stem
    conv in its space-to-depth form — same parameters, same linear
    map.  Default OFF: measured SLOWER for this torso, whose stem
    input needs no gradient (see _SpaceToDepthFirstConv for the
    measurement story).  Output dtype is ``dtype`` — the caller owns
    any upcast (the agent's heads return f32 logits/baseline).

    ``remat`` (the agent's ``remat_torso``): behind the Pallas stem
    there is nothing worth rebuilding — its VJP keeps the uint8 frame,
    not a float copy, and the step's peak is the grad-W kernel's
    operand late in the backward, when the saved activations are
    already gone — so no checkpoint is placed.  XLA's stem VJP keeps
    the normalised float frames (the largest residual by far), and the
    only boundary found that frees them within the peak-memory bound
    is the one around the whole torso (ISSUE 27: +16% live bytes
    around ``conv_0`` alone, +67% with none), so that path keeps it.

    What is handed over (``hands_stem``, the Pallas stem only): a call
    sows the stem's activation — ``relu(conv_0(frame))``
    ``[N, H/4, W/4, 32]`` in ``dtype``, what ``conv_1`` reads — into
    the ``HANDOVER`` collection, which costs nothing unless the caller
    made that collection mutable; and a call given ``stem``, that
    activation for these frames under these parameters, starts at
    ``conv_1``.  The fused loop acts on every frame before it learns
    from it, on one set of parameters, so its update's forward never
    runs the stem conv (runtime/ingraph.py; 5.4 of a 35.6 ms step,
    ISSUE 37).  Handed exactly where this torso keeps that tensor
    whole across the backward anyway, so no step's peak moves: behind
    XLA's stem the handed tensor would be a saved input of the
    whole-torso checkpoint on top of what the checkpoint rebuilds.
    """

    dtype: Any = jnp.float32
    space_to_depth: bool = False
    conv_backend: str = "xla"
    remat: bool = False

    @property
    def remat_placement(self) -> str:
        """One of REMAT_PLACEMENTS: where ``remat`` puts the boundary."""
        if not self.remat or _stem_backend(self.conv_backend):
            return "none"
        return "torso"

    @property
    def hands_stem(self) -> bool:
        """Whether a call sows the stem's activation and takes one
        back: behind the Pallas stem, whose torso holds no checkpoint."""
        return (_stem_backend(self.conv_backend)
                and self.remat_placement == "none")

    @nn.compact
    def __call__(self, frame, stem=None):
        if stem is not None and not self.hands_stem:
            raise ValueError(
                f"a {self.conv_backend!r} stem (remat="
                f"{self.remat_placement}) takes no handed activation")
        forward = ShallowConvTorso._forward
        if self.remat_placement == "torso":
            # Lifted over a function of THIS module, not a child: the
            # parameter paths (convnet/conv_0/...) do not move.
            forward = nn.remat(forward)
        return forward(self, frame, stem)

    @nn.nowrap  # no scope or capture of its own when called directly
    def _forward(self, frame, stem=None):
        pallas_stem = _stem_backend(self.conv_backend)
        x = _stem_input(frame, self.dtype)
        for i, (num_ch, filter_size, stride) in enumerate(
                [STEM_GEOMETRY["shallow"], (64, 4, 2), (128, 3, 2)]):
            if i == 0 and pallas_stem:
                conv_0 = PallasStemConv(
                    num_ch, filter_size, stride, dtype=self.dtype,
                    normalize=functools.partial(
                        _normalize_frame, dtype=self.dtype),
                    name="conv_0")
                if stem is not None:
                    x = conv_0(frame, handed=stem)  # past its ReLU
                    continue
                x = conv_0(frame)
            elif i == 0 and self.space_to_depth:
                x = _SpaceToDepthFirstConv(
                    num_ch, dtype=self.dtype, name="conv_0")(x)
            else:
                x = nn.Conv(
                    num_ch, (filter_size, filter_size),
                    strides=(stride, stride),
                    padding="SAME", dtype=self.dtype, name=f"conv_{i}")(x)
            x = nn.relu(x)
            if i == 0 and self.hands_stem and not self.is_initializing():
                # (``init`` makes every collection mutable, and this is
                # no variable of the model.)  One value, not sow's
                # default tuple of every call's.
                self.sow(HANDOVER, "stem", x, init_fn=lambda: None,
                         reduce_fn=lambda _, new: new)
        x = x.reshape((x.shape[0], -1))
        x = nn.Dense(256, dtype=self.dtype, name="fc")(x)
        x = nn.relu(x)
        # The torso stays in its compute dtype end-to-end: under a
        # bfloat16 policy the downstream concat/core/head matmuls are
        # the point of the policy, and the agent upcasts its OUTPUTS
        # (logits/baseline) to f32 for the loss.  asarray is an
        # identity under the f32 default, so the golden-loss anchor
        # (tests/test_replay.py) is untouched.
        return jnp.asarray(x, self.dtype)


class _ResidualBlock(nn.Module):
    num_ch: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        block_input = x
        x = nn.relu(x)
        x = nn.Conv(self.num_ch, (3, 3), padding="SAME", dtype=self.dtype,
                    name="conv_0")(x)
        x = nn.relu(x)
        x = nn.Conv(self.num_ch, (3, 3), padding="SAME", dtype=self.dtype,
                    name="conv_1")(x)
        return x + block_input


class ResNetTorso(nn.Module):
    """Deep IMPALA ResNet: sections (16, 32, 32) x 2 residual blocks.

    Input [N, H, W, C] uint8; output [N, 256] in ``dtype`` (the agent
    owns the f32 upcast of its outputs — see ShallowConvTorso).
    (reference: experiment.py:156-176, commented-out variant)

    ``conv_backend="pallas"`` routes the stem (``downscale_0`` — like
    the shallow torso's conv_0, its input is the gradient-free frame)
    through the Pallas grad-W kernel, so both torsos honor the one
    flag — where the kernel takes the geometry: 3x3/stride-1 over
    3-channel 72x96 frames pads 3 lanes to 128 and does not fit VMEM
    (ops/conv_pallas.gradw_batch_tile), so the driver's ``auto`` keeps
    this stem on XLA and an explicit ``pallas`` is refused there.

    ``remat`` (the agent's ``remat_torso``) checkpoints the stem
    segment alone: frame -> ``_normalize_frame`` -> ``downscale_0`` ->
    ``max_pool``.  The stem conv's full-resolution output is the one
    residual worth rebuilding (a quarter of the torso's saved bytes
    for one 3-channel conv; the pool's backward reads it, the pool's
    own output is not needed again and is dropped), so the backward
    recomputes one convolution of fifteen and ``residual_*``,
    ``downscale_1/2`` and ``fc`` keep their activations.
    """

    dtype: Any = jnp.float32
    conv_backend: str = "xla"
    remat: bool = False

    @property
    def remat_placement(self) -> str:
        """One of REMAT_PLACEMENTS: where ``remat`` puts the boundary."""
        return "stem" if self.remat else "none"

    @property
    def hands_stem(self) -> bool:
        """Never (see ShallowConvTorso): the stem's full-resolution
        output is the residual this torso exists NOT to keep."""
        return False

    @nn.nowrap  # no scope or capture of its own when called directly
    def _stem(self, frame):
        if _stem_backend(self.conv_backend):
            x = PallasStemConv(*STEM_GEOMETRY["resnet"], dtype=self.dtype,
                               normalize=functools.partial(
                                   _normalize_frame, dtype=self.dtype),
                               name="downscale_0")(frame)
        else:
            x = nn.Conv(STEM_GEOMETRY["resnet"][0], (3, 3), padding="SAME",
                        dtype=self.dtype, name="downscale_0")(
                            _stem_input(frame, self.dtype))
        return nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")

    @nn.compact
    def __call__(self, frame):
        stem = ResNetTorso._stem
        if self.remat_placement == "stem":
            # Lifted over a function of THIS module, not a child: the
            # parameter paths (convnet/downscale_0/...) do not move.
            stem = nn.remat(stem)
        for i, (num_ch, num_blocks) in enumerate([(16, 2), (32, 2), (32, 2)]):
            if i == 0:
                x = stem(self, frame)
            else:
                x = nn.Conv(num_ch, (3, 3), padding="SAME",
                            dtype=self.dtype, name=f"downscale_{i}")(x)
                x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
            for j in range(num_blocks):
                x = _ResidualBlock(num_ch, dtype=self.dtype,
                                   name=f"residual_{i}_{j}")(x)
        x = nn.relu(x)
        x = x.reshape((x.shape[0], -1))
        x = nn.Dense(256, dtype=self.dtype, name="fc")(x)
        x = nn.relu(x)
        return jnp.asarray(x, self.dtype)


TORSOS = {
    "shallow": ShallowConvTorso,
    "resnet": ResNetTorso,
}
