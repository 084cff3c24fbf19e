"""Device mesh construction and sharding rules.

The reference has NO collectives — its "distribution" is a TF1 gRPC
parameter-server pattern with a single learner (reference:
experiment.py:506-512; SURVEY §2.5).  The TPU-native framework replaces
that with an SPMD mesh:

- axis ``data``: learner data parallelism.  Trajectory batches are sharded
  over it; gradients are all-reduced over ICI by XLA (the jit partitioner
  inserts the psum — we only annotate shardings).
- axis ``model``: tensor parallelism for the network.  Degenerate (=1) for
  the IMPALA-size net but wired through from day one so larger torsos can
  shard without interface changes.

Multi-host: the same mesh spans hosts via ``jax.distributed.initialize``;
data-parallel gradient traffic then rides ICI within a slice and DCN
across slices, chosen by XLA from the device topology.
"""

import functools
import math
from typing import NamedTuple, Optional, Sequence

import jax
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint
from jax.sharding import Mesh, NamedSharding, PartitionSpec


class MeshSpec(NamedTuple):
    """Logical mesh shape: data x seq x model.

    ``seq`` is the sequence/context-parallel axis (SURVEY §5.7): batches
    shard over (data x seq) for the model compute, and the V-trace
    recurrence's TIME dimension shards over ``seq``
    (parallel/sequence.py) when the Learner runs
    ``scan_impl="time_sharded"``.  Degenerate (=1) everywhere else."""

    data: int
    seq: int = 1
    model: int = 1


def auto_data_axis(batch_size: int, num_devices: int,
                   seq: int = 1, model: int = 1) -> int:
    """The largest data-axis size a single-process mesh can take: the
    batch shards over (data x seq), so ``data * seq`` must divide the
    batch, out of the devices left after seq/model take theirs (a
    4-batch debug run on an 8-device host uses 4 devices rather than
    failing).  Pure math, shared by the driver's mesh sizing and every
    "auto" kernel-choice estimate — and the reason an ELASTIC restart
    at a different device count resizes its mesh without operator
    input: the same batch re-shards over whatever devices the new
    membership epoch has (tests/test_elastic.py pins the adaptation
    table)."""
    non_data = seq * model
    return math.gcd(
        max(1, batch_size // seq),
        max(1, num_devices // non_data))


def make_mesh(spec: Optional[MeshSpec] = None,
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build a 3-axis ('data', 'seq', 'model') mesh over ``devices``.

    Defaults: all devices on the data axis, seq=model=1.
    """
    devices = list(devices if devices is not None else jax.devices())
    if spec is None:
        spec = MeshSpec(data=len(devices))
    if spec.data * spec.seq * spec.model != len(devices):
        raise ValueError(
            f"mesh {spec} needs {spec.data * spec.seq * spec.model} "
            f"devices, got {len(devices)}")
    array = np.asarray(devices).reshape(spec.data, spec.seq, spec.model)
    return Mesh(array, axis_names=("data", "seq", "model"))


# The mesh axes the batch dimension is cut over, outermost first.
BATCH_AXES = ("data", "seq")


def batch_sharding(mesh: Mesh, batch_axis_index: int = 1) -> NamedSharding:
    """Shard the batch dimension over the (data, seq) axes.

    Trajectories are time-major [T, B, ...]; B is ``batch_axis_index`` 1.
    The seq axis joins the batch sharding so its devices carry real
    model compute too — time-resharding happens only around the V-trace
    recurrence (parallel/sequence.py).

    The rule that comes with a sharded B (ISSUE 26): **a merge of the
    batch axis with another keeps the shard index outermost.**
    ``[T, B] -> [T*B]`` time-major is not a tiling of the merged axis,
    and the SPMD partitioner answers by gathering the operand and
    computing everything downstream on every device, silently.  Merge
    batch-major (``[B, T] -> [B*T]``) or, where a device's own rows
    should keep their time-major layout, shard-major (``[S, T, B/S]``
    with S = ``batch_shards`` — models/agent.py); and reduce a
    ``[T, B]`` array over its axes as they are, never through a
    ``ravel``.
    """
    pspec = [None] * (batch_axis_index + 1)
    pspec[batch_axis_index] = (BATCH_AXES
                               if "seq" in mesh.shape else "data")
    return NamedSharding(mesh, PartitionSpec(*pspec))


def batch_shards(mesh_shape) -> int:
    """How many pieces ``batch_sharding`` cuts the batch axis in on a
    mesh of this ``Mesh.shape`` (any mapping of axis sizes): the
    product over ``BATCH_AXES``.  What the Learner sets
    ``ImpalaAgent.batch_shards`` to, so that the unroll's
    ``[T, B] -> [T*B]`` merge stays sharded."""
    return math.prod(int(mesh_shape.get(axis, 1)) for axis in BATCH_AXES)


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Fully replicated (params, optimizer state, scalars)."""
    return NamedSharding(mesh, PartitionSpec())


def model_parallel_shardings(mesh: Mesh, tree):
    """Tensor-parallel shardings for a params-shaped pytree.

    Output-channel partitioning: every rank>=2 leaf whose LAST axis
    divides the ``model`` axis size shards that axis over ``model``
    (conv kernels [kh, kw, cin, cout] and dense/LSTM kernels [in, out]
    split their output features; XLA inserts the all-gathers/psums the
    dataflow needs).  Biases, scalars, and indivisible leaves (e.g. a
    9-logit head on model=2) replicate.  With model=1 every leaf
    replicates, so this is always safe to use.

    Works for optimizer state too: rmsprop/momentum accumulators are
    params-shaped, so the same rule aligns them with their params.
    """
    model_size = mesh.shape["model"]

    def shard(leaf):
        shape = getattr(leaf, "shape", ())
        if (model_size > 1 and len(shape) >= 2
                and shape[-1] % model_size == 0):
            spec = [None] * (len(shape) - 1) + ["model"]
            return NamedSharding(mesh, PartitionSpec(*spec))
        return NamedSharding(mesh, PartitionSpec())

    return jax.tree_util.tree_map(shard, tree)


def pallas_interpret() -> bool:
    """The ONE home of every kernel's ``interpret=`` decision: Pallas
    kernels run under the interpreter exactly when the backend is not a
    TPU (the CPU test rig), and are compiled by Mosaic otherwise — a
    TPU run never interprets.  Interpret mode proves the kernel body's
    arithmetic; it never sees a VMEM limit, a tiling rule, or the SPMD
    partitioner (tests/test_chip_bringup.py AOT-compiles for that)."""
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnums=1)
def _with_layout(x, major_to_minor):
    return with_layout_constraint(x, Layout(major_to_minor=major_to_minor))


def layout_hint(x, major_to_minor):
    """``x`` with a hint to the TPU compiler: keep it in HBM with its
    axes in this order, outermost first.  No value changes.  Off a TPU
    the hint is not given — the same one decision as
    ``pallas_interpret`` — because the CPU's SPMD partitioner cannot
    shard through the hint's custom call and gathers its operand.  The
    hint sits in a ``jit`` of its own, which a compiled program
    inlines: evaluated op by op (a model's ``init``, an eager
    ``jax.checkpoint``) it then ends at that small program's result,
    where a bare ``with_layout_constraint`` would hand the next op an
    array in an order its executable was not compiled for."""
    if jax.default_backend() != "tpu":
        return x
    return _with_layout(x, tuple(major_to_minor))


def frames_batch_minor(frames):
    """Raw uint8 frames ``[N, H, W, C]`` on their way into a stem's
    FORWARD conv, asked for (``layout_hint``) in the order the TPU
    compiler gives that conv's input — batch in the lanes, channels in
    the sublanes — WHILE THEY ARE STILL ONE BYTE A PIXEL.  Without the
    hint the compiler is free to turn the frames to the conv's order
    AFTER ``frame / 255`` instead, on the float copy, and then the
    normalisation is a pass of its own that writes every frame out in
    bf16 for the conv to read back (1 GiB a step at the fused cell's
    size).  It did exactly that as soon as the frames reached the
    update in a buffer whose order is fixed (ISSUE 29,
    runtime/ingraph.py ``_Slots``), and had always done it to the
    T=1 acting step, whose stem conv is 1.1 ms a step faster for the
    hint (my chip run, PR 29).  With it the conv's fusion reads the
    uint8 frames and converts as it goes.

    Given per use (models/networks.py's XLA stems, ops/conv_pallas.py's
    forward), not once for all: the hinted frames are a value of their
    own, and a second reader (a rematerialised forward, the weight
    gradient's own pad) would make the compiler write them out."""
    return layout_hint(frames, (1, 2, 3, 0))


def fused_kernels_profitable(mesh: Optional[Mesh] = None,
                             num_devices: Optional[int] = None) -> bool:
    """THE policy behind every ``"auto"`` Pallas-kernel choice — the
    fused LSTM core (``core_impl``, ops/lstm_pallas.py) and the stem
    grad-W kernel (``conv_backend``, ops/conv_pallas.py) alike: Pallas
    only on a single-device TPU mesh.  ``pallas_call`` has no SPMD
    partitioning rule, so on a multi-device mesh the update does not
    lower at all ("Mosaic kernels cannot be automatically partitioned.
    Please wrap the call in a shard_map."), and non-TPU backends only
    have the interpreter.  (V-trace's scan_impl="auto" does not consult
    this: the associative scan is the shardable form and auto always
    picks it.)

    Pass the actual ``mesh`` when one exists; ``num_devices`` when only
    the intended mesh size is known (e.g. from Config before the mesh is
    built); neither to ask about the whole process.
    """
    if pallas_interpret():
        return False
    if mesh is not None and getattr(mesh, "devices", None) is not None:
        return mesh.devices.size == 1
    if num_devices is None:
        num_devices = len(jax.devices())
    return num_devices == 1
