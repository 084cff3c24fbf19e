"""Per-kernel roofline ledger: profiler trace × cost analysis → kernels.json.

BENCH_r04/r05 located the learner's worst kernel (``conv0_gradw`` at
0.107 MFU for ~13 ms) by a human reading rooflines off a bench stage.
The MFU 16%→40% push (ROADMAP item 3) needs that reading automated and
attached to every profiled run: this module joins the two artifacts a
run already produces —

- a ``jax.profiler`` trace window (``--profile_dir``), whose device
  events carry per-kernel names and durations (the event names are the
  optimized HLO module's instruction names, identical on the CPU rig
  and on TPU), and
- the lowered update's compiled HLO text + ``cost_analysis()`` FLOPs
  (the same numerator the live ``ledger/mfu`` gauge uses),

into a per-kernel table: time, calls, FLOPs, bytes, arithmetic
intensity, and roofline MFU against the shared ``PEAK_FLOPS`` table
(obs/ledger.py — one denominator for the bench headline, the live
gauge, and this ledger).

Per-kernel FLOPs come from a mini HLO cost model (``parse_hlo_kernel_
costs``): dots count ``2·prod(result)·K`` from the contracting dims,
convolutions ``2·out_elems·kernel_elems/out_features`` from
``dim_labels``, fusions sum their called computation, named Pallas
custom-calls get explicit per-kernel cost entries (XLA cannot see
inside a ``pallas_call``, and the elementwise floor would misprice an
MXU matmul kernel by ~3 orders of magnitude), elementwise ops count
one flop per result element.  The raw estimates are then
NORMALIZED so the matched kernels' per-update FLOPs sum exactly to the
XLA cost-analysis total — XLA's aggregate is authoritative (it is the
MFU numerator), the HLO parse distributes it across kernels.  Both the
raw estimate and the normalized attribution land in ``kernels.json``.

Intentionally jax-free, like report/aggregate: everything here parses
text the caller hands over (trace json, HLO text), so the report CLI
can re-read ``kernels.json`` on a laptop and tests can feed synthetic
modules.  The driver's entry point is ``harvest()`` (both backends
call it right after ``jax.profiler.stop_trace()``).
"""

import glob
import gzip
import json
import math
import os
import re
import threading
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = [
    "BENCH_KERNEL_KEY_RE",
    "BENCH_KERNEL_SERIES_RE",
    "KERNELS_JSON_NAME",
    "build_kernel_table",
    "collective_bytes",
    "collectives",
    "find_profiler_traces",
    "harvest",
    "holds_scope",
    "hlo_module_name",
    "last_dominant",
    "last_worst",
    "load_trace_kernel_events",
    "op_scopes_path",
    "parse_hlo_kernel_costs",
    "primary_kernel_names",
    "publish_kernel_metrics",
    "rematerialized",
    "scan_kernel_series",
    "write_kernels_json",
    "write_op_scopes",
]

_SCHEMA_VERSION = 2  # 2: + per-row "scope" and table "scope_time_shares"
KERNELS_JSON_NAME = "kernels.json"

# The bench's per-kernel diag keys (``kernel_<name>_us`` /
# ``kernel_<name>_mfu``) — matched against parsed dict keys by
# bench.py's kernel_regression_guard and the rounds trajectory.
BENCH_KERNEL_KEY_RE = re.compile(
    r"^kernel_(?P<name>.+)_(?P<kind>us|mfu)$")

# The same series in RAW artifact text: tolerates both plain JSON
# (``"kernel_x_us": 1.2``) and the escaped form inside a tail-embedded
# fragment (``\"kernel_x_us\": 1.2``) — committed artifacts come in
# both, and BENCH_r05's fragment is truncated mid-line, so consumers
# scan text instead of requiring a full parse.
BENCH_KERNEL_SERIES_RE = re.compile(
    r'\\?"kernel_(?P<name>[A-Za-z0-9_]+?)_(?P<kind>us|mfu)\\?"\s*:\s*'
    r'(?P<value>-?[0-9][0-9.eE+\-]*)')


def scan_kernel_series(text: str) -> Dict[str, Dict[str, float]]:
    """``{kernel_name: {"us": ..., "mfu": ...}}`` scanned from raw
    artifact text (the shared salvage used by obs/report.py's
    bench-kernel section and the rounds trajectory)."""
    kernels: Dict[str, Dict[str, float]] = {}
    for match in BENCH_KERNEL_SERIES_RE.finditer(text):
        try:
            value = float(match.group("value"))
        except ValueError:
            continue
        entry = kernels.setdefault(match.group("name"), {})
        entry[match.group("kind")] = value
    return kernels


def primary_kernel_names(names) -> set:
    """The PRIMARY kernels among ``names``: a reading whose name
    extends another's with a suffix (``conv0_gradw_s2d``,
    ``lstm_grad_pallas_bf16``, ``..._b256``) is an experiment variant
    of that measurement — it stays in tables but must not claim the
    worst-kernel verdict over the production path."""
    names = set(names)
    return {
        name for name in names
        if not any(name != other and name.startswith(other + "_")
                   for other in names)}

# Kernels below this share of matched device time are excluded from the
# "worst kernel" verdict: a 0.1%-of-time kernel at 0.01 MFU is noise,
# not the roofline target.
WORST_MIN_TIME_SHARE = 0.02

# How many kernels get per-kernel registry gauges (the full table lives
# in kernels.json; the registry carries the actionable head).
PUBLISH_TOP_N = 8


# -- HLO parsing -------------------------------------------------------------

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
    "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1,
    "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"(?P<dtype>[a-z]+[0-9a-z]*)\[(?P<dims>[0-9,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*(?P<shape>\([^)]*\)|\S+)"
    r"\s+(?P<op>[\w\-]+)\((?P<args>[^()]*)\)(?P<attrs>.*)$")
_COMPUTATION_RE = re.compile(
    r"^\s*(?P<entry>ENTRY\s+)?%?(?P<name>[\w.\-]+)\s*(?:\([^)]*\))?\s*->"
    r".*\{\s*$")
# The called-computation attr differs per op: fusion/call use
# ``calls=``, while uses ``body=`` (one trip's worth — the static
# estimate; trip counts aren't in the HLO text), map uses
# ``to_apply=``.  Conditional's ``branch_computations={...}`` is a
# list and is left to the elementwise fallback.
_CALLS_RE = re.compile(r"(?:calls|body|to_apply)=%?([\w.\-]+)")
_OPERAND_NAME_RE = re.compile(r"%([\w.\-]+)")
# The jax.named_scope breadcrumbs inside the instruction metadata's
# op_name — how device time attributes to pipeline stages inside one
# fused program (runtime/ingraph.py wraps its three phases in these
# scopes).
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_SCOPE_MARKERS = (
    ("env_step", "env"),
    ("actor_inference", "inference"),
    ("learner_update", "learner"),
)
_LHS_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_DIM_LABELS_RE = re.compile(r"dim_labels=([\w?]+)_([\w?]+)->([\w?]+)")

# Opcodes that move/reshape data without arithmetic.
_ZERO_FLOP_OPS = frozenset((
    "parameter", "constant", "bitcast", "bitcast-convert", "copy",
    "copy-start", "copy-done", "reshape", "broadcast", "transpose",
    "get-tuple-element", "tuple", "iota", "slice", "dynamic-slice",
    "dynamic-update-slice", "concatenate", "pad", "reverse", "gather",
    "scatter", "after-all", "partition-id", "replica-id", "rng-state",
    "opt-barrier", "domain", "send", "send-done", "recv", "recv-done",
))


def _parse_shapes(text: str) -> List[Tuple[int, List[int]]]:
    """Every ``dtype[d0,d1,...]`` in ``text`` -> (bytes_per_elem, dims).
    Handles tuple results by simply yielding each component."""
    out = []
    for m in _SHAPE_RE.finditer(text):
        dtype = m.group("dtype")
        if dtype not in _DTYPE_BYTES:
            continue
        dims_text = m.group("dims")
        dims = [int(d) for d in dims_text.split(",") if d] or [1]
        out.append((_DTYPE_BYTES[dtype], dims))
    return out


def _elems(shapes: List[Tuple[int, List[int]]]) -> int:
    return sum(math.prod(dims) for _, dims in shapes)


def _bytes(shapes: List[Tuple[int, List[int]]]) -> int:
    return sum(b * math.prod(dims) for b, dims in shapes)


# -- Pallas custom-call costs ------------------------------------------------
# A ``pallas_call`` lowers to a ``custom-call`` whose body XLA cannot
# see, so the generic model would fall through to the one-flop-per-
# element floor — mispricing an MXU matmul kernel by orders of
# magnitude and hiding it from the worst-kernel verdict.  Named Pallas
# kernels therefore get explicit cost entries, keyed on the kernel name
# the op stamps into its instruction metadata (both the named_scope
# breadcrumb in ``op_name`` and the pallas_call ``name=`` carry it).
# The name strings are a CONTRACT with ops/* (this module stays
# jax-free, so it cannot import them); tests/test_kernel_ledger.py pins
# that the two sides agree.

# ops/conv_pallas.py GRADW_KERNEL_NAME.
_PALLAS_GRADW_MARKER = "pallas_conv0_gradw"


def _pallas_gradw_flops(result: List, operands: List) -> Optional[float]:
    """ops/conv_pallas.py grad-W: per output row and group of JG output
    columns, one matmul contracting the images of ``g=[OH,OW,F,N]``
    (batch in the lanes) against the stacked tap windows into the
    band ``[K*Cin*WIN, JG*F]``: ``2 * rows * JG*F * N*OH*OW/JG`` =
    ``2 * rows * prod(g)`` — what the MXU executes, of which K/WIN
    lands in dW (the rest is the band's off-diagonal).  The g operand
    is recognized among the custom-call's inputs as the (last) 4-d
    tensor whose third dim, F, divides the result's columns (the padded
    input ``[HP,Cin,WP,N]`` has WP there)."""
    if not result or not operands:
        return None
    out_dims = result[0][1]
    if len(out_dims) != 2:
        return None
    rows, columns = out_dims
    g_dims = next((dims for _, dims in reversed(operands)
                   if len(dims) == 4 and columns % dims[2] == 0), None)
    if g_dims is None:
        return None
    return 2.0 * rows * math.prod(g_dims)


_PALLAS_KERNEL_COSTS = (
    (_PALLAS_GRADW_MARKER, _pallas_gradw_flops),
)


def _custom_call_flops(result: List, operands: List,
                       attrs: str) -> Optional[float]:
    """Explicit cost for a recognized named Pallas custom-call, or None
    to fall through to the elementwise floor.  The marker is searched in
    the whole attr text: TPU lowers pallas_call to ``custom-call
    ... custom_call_target="tpu_custom_call"`` with the kernel name in
    the metadata ``op_name`` scope path and/or backend config."""
    for marker, cost_fn in _PALLAS_KERNEL_COSTS:
        if marker in attrs:
            flops = cost_fn(result, operands)
            if flops is not None:
                return flops
    return None


def _instruction_flops(op: str, result: List, operands: List,
                       attrs: str, called_flops: Optional[float]) -> float:
    """The mini cost model, per execution of one instruction."""
    if op in _ZERO_FLOP_OPS:
        return 0.0
    out_elems = _elems(result)
    if op == "custom-call":
        flops = _custom_call_flops(result, operands, attrs)
        if flops is not None:
            return flops
    if op == "dot":
        m = _LHS_CONTRACT_RE.search(attrs)
        if m and operands:
            lhs_dims = operands[0][1]
            k = math.prod(
                lhs_dims[int(i)] for i in m.group(1).split(",")
                if i and int(i) < len(lhs_dims)) or 1
            return 2.0 * out_elems * k
        return 2.0 * out_elems
    if op == "convolution":
        m = _DIM_LABELS_RE.search(attrs)
        if m and len(operands) >= 2:
            out_labels = m.group(3)
            kernel_elems = math.prod(operands[1][1])
            feature_axis = out_labels.find("f")
            out_features = (result[0][1][feature_axis]
                            if result and 0 <= feature_axis
                            < len(result[0][1]) else 1)
            return 2.0 * out_elems * kernel_elems / max(1, out_features)
        return 2.0 * out_elems
    if op in ("fusion", "call", "while", "map"):
        # The kernel's arithmetic is its called computation's (for
        # while: one trip of the body — the static estimate).
        return called_flops if called_flops is not None else 0.0
    if op in ("reduce", "reduce-window", "reduce-scatter", "all-reduce",
              "select-and-scatter", "sort", "cumsum"):
        return float(_elems(operands) or out_elems)
    # Elementwise / transcendental / comparison / rng / unrecognized-
    # custom-call fallback: one flop per result element — a floor, not
    # a claim.
    return float(out_elems)


def parse_hlo_kernel_costs(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Optimized-HLO text -> per-instruction cost estimates.

    Returns ``{instruction_name: {"flops_est", "bytes", "op"}}`` for
    every instruction in every computation (while-loop bodies included
    — their instructions are the kernels a scan's trace events name),
    with fusion/call instructions summing their called computation's
    flops and charging bytes at the fusion boundary (operands + result
    — the memory the fused kernel actually touches)."""
    # Pass 1: collect raw instructions per computation.
    computations: Dict[str, List[dict]] = {}
    current = None
    for line in hlo_text.splitlines():
        comp = _COMPUTATION_RE.match(line)
        if comp:
            current = comp.group("name")
            computations[current] = []
            continue
        if line.strip() == "}":
            current = None
            continue
        if current is None:
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        computations[current].append({
            "name": m.group("name"),
            "op": m.group("op"),
            "result": _parse_shapes(m.group("shape")),
            "operands": _parse_shapes(m.group("args")),
            "args": m.group("args"),
            "attrs": m.group("attrs"),
        })
    # Newer XLA prints an operand by name alone (``dot(%a, %b)``): its
    # shape is the result shape of the instruction (or parameter) of
    # that name.
    results = {instr["name"]: instr["result"]
               for instrs in computations.values() for instr in instrs}
    for instrs in computations.values():
        for instr in instrs:
            if not instr["operands"]:
                instr["operands"] = [
                    shape for name in _OPERAND_NAME_RE.findall(
                        instr["args"])
                    for shape in results.get(name, ())]

    # Pass 2: per-computation flops sums (for fusion/call resolution),
    # resolved iteratively so nesting order in the text doesn't matter.
    comp_flops: Dict[str, float] = {}

    def _computation_flops(name: str, stack: Tuple[str, ...]) -> float:
        if name in comp_flops:
            return comp_flops[name]
        if name in stack:  # recursive call structure: refuse the cycle
            return 0.0
        total = 0.0
        for instr in computations.get(name, ()):
            total += _resolve_flops(instr, stack + (name,))
        comp_flops[name] = total
        return total

    def _resolve_flops(instr: dict, stack: Tuple[str, ...]) -> float:
        called = None
        if instr["op"] in ("fusion", "call", "while", "map"):
            m = _CALLS_RE.search(instr["attrs"])
            if m:
                called = _computation_flops(m.group(1), stack)
        return _instruction_flops(instr["op"], instr["result"],
                                  instr["operands"], instr["attrs"],
                                  called)

    costs: Dict[str, Dict[str, float]] = {}
    for comp_name, instrs in computations.items():
        for instr in instrs:
            costs[instr["name"]] = {
                "flops_est": _resolve_flops(instr, (comp_name,)),
                "bytes": float(_bytes(instr["operands"])
                               + _bytes(instr["result"])),
                "op": instr["op"],
                "scope": _scope_of(instr["attrs"]),
            }
    return costs


def _scope_of(attrs: str) -> Optional[str]:
    """Pipeline-stage attribution off the instruction metadata's
    ``op_name`` (the jax.named_scope path): "env" / "inference" /
    "learner", or None when the instruction carries no scope marker
    (fused kernels mixing stages keep their ROOT instruction's
    scope)."""
    m = _OP_NAME_RE.search(attrs)
    if not m:
        return None
    op_name = m.group(1)
    for marker, scope in _SCOPE_MARKERS:
        if marker in op_name:
            return scope
    return None


_INSTR_NAME_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
# an op_name with no path and no ``jit(...)``: a compiler pass's own
_PASS_NAMED_RE = re.compile(r"^[\w.\-]+$")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")


def op_scopes_path(trace_path: str) -> str:
    """``<logdir>/op_scopes.p<proc>.<pid>.json`` for a run's
    ``<logdir>/trace.p<proc>.<pid>.json``: same directory, same
    suffix."""
    folder, name = os.path.split(trace_path)
    return os.path.join(folder, "op_scopes." + name.split(".", 1)[-1])


def holds_scope(hlo_text: str, scope: str) -> bool:
    """Does some instruction's ``op_name`` hold ``scope`` as a whole
    component of its path (wrapped by autodiff or not)?"""
    return re.search(r'op_name="[^"]*[/(]%s[/)"]' % re.escape(scope),
                     hlo_text) is not None


# Cross-device ops of a partitioned program, by the kind the gauges
# are published under.  The ``-start`` half of an async pair carries
# the shapes; the ``-done`` half is the same transfer and is skipped.
_COLLECTIVE_KINDS = {
    "all-reduce": "all_reduce",
    "all-gather": "all_gather",
    "all-to-all": "other",
    "collective-permute": "other",
    "reduce-scatter": "other",
    "collective-broadcast": "other",
    "ragged-all-to-all": "other",
}
_COLLECTIVE_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*"
    r"(?P<shape>\(.*?\)|\S+)\s+"
    r"(?P<op>" + "|".join(map(re.escape, _COLLECTIVE_KINDS))
    + r")(?P<start>-start)?\(")
_CHANNEL_RE = re.compile(r"channel_id=(\d+)")


def collectives(hlo_text: str) -> List[dict]:
    """Every collective instruction of a compiled (partitioned)
    module: ``{"name", "op", "kind", "dims", "bytes", "op_name"}`` in
    text order.  ``dims`` are the dims of each array of the result —
    PER DEVICE, as every shape of a partitioned module is — and
    ``bytes`` their size: what one device holds of the collective's
    output (an all-gather's gathered array, an all-reduce's sum).  An
    async ``-start`` result also lists its operands first; those are
    dropped.  One transfer the TPU compiler chains over several fused
    computations prints once per link under one ``channel_id``: the
    first stands for it."""
    out, channels = [], set()
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_RE.match(line)
        if not m:
            continue
        channel = _CHANNEL_RE.search(line)
        if channel:
            if channel.group(1) in channels:
                continue
            channels.add(channel.group(1))
        shapes = _parse_shapes(m.group("shape"))
        if m.group("start") and m.group("op") != "all-reduce":
            # (operands..., results..., [context scalars]): keep the
            # results — the larger half for a gather, either for a
            # permute.
            arrays = [s for s in shapes if s[1] != [1]] or shapes
            shapes = arrays[len(arrays) // 2:]
        scope = _OP_NAME_RE.search(line)
        out.append({
            "name": m.group("name"),
            "op": m.group("op"),
            "kind": _COLLECTIVE_KINDS[m.group("op")],
            "dims": [dims for _, dims in shapes],
            "bytes": _bytes(shapes),
            "op_name": scope.group(1) if scope else None,
        })
    return out


def collective_bytes(rows: Sequence[dict]) -> Dict[str, int]:
    """``collectives()`` rows -> ``{"all_reduce", "all_gather",
    "other"}``: bytes one device holds of that kind's outputs in one
    run of the module (``other``: all-to-all, collective-permute,
    reduce-scatter, broadcast).  A data-parallel step's all-reduce is
    its parameters' size and its all-gather a few scalars' worth; an
    all-gather the size of the batch says some op was not partitioned
    and every device computes it whole (ISSUE 26: the ``[T, B] ->
    [T*B]`` merge)."""
    totals = {"all_reduce": 0, "all_gather": 0, "other": 0}
    for row in rows:
        totals[row["kind"]] += row["bytes"]
    return totals


_OPCODE_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*(?:\(.*?\)|\S+)\s+"
    r"(?P<op>[\w\-]+)\(")
_FUSED_BODY_RE = re.compile(r"\sfusion\(.*\scalls=%?([\w.\-]+)")
# How many recomputed instructions the notes name (the count is whole).
REMAT_NAMES_MAX = 24


def rematerialized(hlo_text: str) -> dict:
    """What a compiled step computes a second time in its backward
    pass, read off its text: ``jax.checkpoint`` marks the ops inside
    its boundary with a ``checkpoint`` component in their ``op_name``
    path, and the forward ops it runs AGAIN for the backward with
    ``checkpoint/rematted_computation``.  Returns ``{"instructions":
    recomputed instructions a device trace names (those not inside a
    fusion's body), "convolutions": recomputed ``convolution`` ops
    wherever they sit, "names": {instruction: op_name} of the first
    REMAT_NAMES_MAX of the former, those that hold a convolution
    first, "checkpointed_instructions": every instruction inside a
    boundary, recomputed or not}`` — so a reader of a traced run sees
    what is recomputed without matching twin ops by hand (ISSUE 27:
    one boundary around a whole torso recomputed 16 convolutions a
    step of the ResNet's update; around its stem segment, one)."""
    fused_bodies = set(_FUSED_BODY_RE.findall(hlo_text))
    named, convolutions, checkpointed, in_fusion = [], 0, 0, False
    for line in hlo_text.splitlines():
        computation = _COMPUTATION_RE.match(line)
        if computation:
            in_fusion = computation.group("name") in fused_bodies
            continue
        scope = _OP_NAME_RE.search(line)
        instr = _OPCODE_RE.match(line) if scope else None
        if not instr:
            continue
        path = scope.group(1).split("/")
        if "checkpoint" not in path:
            continue
        checkpointed += 1
        if "rematted_computation" not in path:
            continue
        convolutions += instr.group("op") == "convolution"
        if not in_fusion:
            named.append((instr.group("name"), scope.group(1)))
    named.sort(key=lambda row: "conv_general_dilated" not in row[1])
    return {"instructions": len(named), "convolutions": convolutions,
            "names": dict(named[:REMAT_NAMES_MAX]),
            "checkpointed_instructions": checkpointed}


# A computation's header whatever its parameters (_COMPUTATION_RE stops
# at a tuple parameter's first ``)``, so it misses every loop body).
_HEADER_RE = re.compile(
    r"^(?:ENTRY\s+)?%?(?P<name>[\w.\-]+)\s+\(.*\)\s*->.*\{\s*$")
_U8_RESULT_RE = re.compile(
    r"^\s+(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*u8\[(?P<dims>[\d,]+)\]"
    r"\S*\s+(?P<op>[\w\-]+)\(")
# Opcodes whose result is their operand's bytes again, in another order
# or another place; a fusion counts unless it writes in place.
_RELAYOUT_OPS = frozenset((
    "copy", "concatenate", "pad", "reshape", "transpose", "fusion"))


def frame_relayouts(hlo_text: str) -> List[dict]:
    """The instructions of a compiled step that write the trajectory's
    WHOLE uint8 frame tensor out again without computing anything:
    ``{"name", "op", "dims", "bytes", "op_name"}`` in text order, PER
    DEVICE as every shape of a partitioned module is.  The frame tensor
    is the largest ``u8`` array any instruction a device trace names
    (one outside a fusion's body) results in; a row is a ``copy``,
    ``concatenate``, ``pad``, ``reshape``, ``transpose`` or a fusion
    with that many elements as its result.  Not rows: the loop that
    fills the tensor and a fusion around a ``dynamic-update-slice``
    (they write a slot in place), bitcasts, tuple plumbing, and what
    sits inside a fusion's body — the stem weight gradient's pad, which
    reads the frames once and results in their padded float copy.
    ISSUE 29: stacked by the rollout's scan, the frames paid a
    concatenate (XLA lowers it as pad + add) for the overlap entry and
    a transposing copy for the ``[T+1, B] -> [(T+1)*B]`` merge, 2 x
    536 MB of results a step on one chip and 3 x on four; written once
    into the buffer the update reads (runtime/ingraph.py
    ``_Slots``) there is no row."""
    fused_bodies = set(_FUSED_BODY_RE.findall(hlo_text))
    in_place, named, current = set(), [], None
    for line in hlo_text.splitlines():
        header = _HEADER_RE.match(line)
        if header:
            current = header.group("name")
        elif current in fused_bodies:
            if " dynamic-update-slice(" in line:
                in_place.add(current)
        else:
            result = _U8_RESULT_RE.match(line)
            if result:
                dims = [int(d) for d in result.group("dims").split(",")]
                named.append((result, dims, line))
    whole = max((math.prod(dims) for _, dims, _ in named), default=0)
    rows = []
    for result, dims, line in named:
        if (math.prod(dims) != whole
                or result.group("op") not in _RELAYOUT_OPS):
            continue
        body = _FUSED_BODY_RE.search(line)
        if body and body.group(1) in in_place:
            continue
        scope = _OP_NAME_RE.search(line)
        rows.append({
            "name": result.group("name"),
            "op": result.group("op"),
            "dims": dims,
            "bytes": whole,
            "op_name": scope.group(1) if scope else None,
        })
    return rows


def write_op_scopes(trace_path: str, hlo_text: str,
                    registry=None) -> str:
    """Leave, beside a run's span trace, the table a device trace needs
    to tell which layer an op belongs to: instruction name ->
    ``op_name`` (the ``jax.named_scope`` / flax-module path) for every
    instruction of the compiled step that carries one.  A v5e profiler
    trace names each event by its instruction (``%fusion.238 = ...``)
    and carries no ``op_name``; the join is on the instruction name.
    The same parse says what the partitioner made the step move between
    devices: ``collective_bytes`` by kind goes to the table's ``notes``
    (beside the largest collectives and their per-device shapes) and to
    the ``spmd/collective_bytes/<kind>`` gauges; and what the step
    recomputes under ``jax.checkpoint`` (``rematerialized``), to the
    notes too; and what it spends writing the trajectory's frames out
    again (``frame_relayouts``): ``frame_relayout_bytes`` in the notes,
    with the rows, and the gauge ``fused/frame_relayout_bytes``.  And,
    beside it, ``stem_handed_share``: the reading of the gauge
    ``fused/stem_handed_share``, which tracing the step set
    (runtime/ingraph.py) — the share of an update's frames whose stem
    activation the acting steps handed over, so that a reader of the
    table's ops knows whether to look for the update's stem conv.
    Returns the path written."""
    from scalable_agent_tpu.obs.registry import get_registry

    ops = {}
    renamed = {}     # instruction -> its operands, where a pass named it
    for line in hlo_text.splitlines():
        scope = _OP_NAME_RE.search(line)
        name = _INSTR_NAME_RE.match(line) if scope else None
        if name:
            ops[name.group(1)] = scope.group(1)
            if _PASS_NAMED_RE.match(scope.group(1)):
                renamed[name.group(1)] = _OPERAND_RE.findall(
                    line[name.end():].split("metadata=", 1)[0])
    # An op an XLA pass made (the grouped matrix product's Mosaic calls,
    # ``op_name="ragged-dot-none"``) lost the scope path of the op it
    # stands for: it takes that of its last operand that has one (the
    # weights' cast, the sorted rows' gather), and so its layer.
    for instruction, operands in renamed.items():
        for operand in reversed(operands):
            path = ops.get(operand, "")
            if "/" in path:
                ops[instruction] = f"{path}/{ops[instruction]}"
                break
    rows = sorted(collectives(hlo_text), key=lambda row: -row["bytes"])
    totals = collective_bytes(rows)
    registry = registry or get_registry()
    for kind, value in totals.items():
        registry.gauge(
            f"spmd/collective_bytes/{kind}",
            "bytes one device holds of this kind of collective's "
            "outputs in one run of the compiled fused step").set(value)
    relayouts = frame_relayouts(hlo_text)
    relayout_bytes = sum(row["bytes"] for row in relayouts)
    registry.gauge(
        "fused/frame_relayout_bytes",
        "bytes one device writes in one run of the compiled fused step "
        "to hold the trajectory's whole uint8 frame tensor again "
        "(copies, concatenates, pads, reshapes): 0 when the rollout "
        "writes the frames where the update reads them").set(
            relayout_bytes)
    folder, name = os.path.split(op_scopes_path(trace_path))
    return write_kernels_json(
        folder, {"module": hlo_module_name(hlo_text), "ops": ops,
                 "notes": {"collective_bytes": totals,
                           "largest_collectives": rows[:8],
                           "rematerialized": rematerialized(hlo_text),
                           "frame_relayout_bytes": relayout_bytes,
                           "frame_relayouts": relayouts[:8],
                           "stem_handed_share": registry.gauge(
                               "fused/stem_handed_share").value}},
        name=name)


# -- trace ingestion ---------------------------------------------------------


def find_profiler_traces(profile_dir: str) -> List[str]:
    """The newest profiler session's ``*.trace.json(.gz)`` files under
    ``<profile_dir>/plugins/profile/<timestamp>/`` (the layout
    ``jax.profiler.start_trace`` writes)."""
    sessions = sorted(glob.glob(
        os.path.join(profile_dir, "plugins", "profile", "*")))
    if not sessions:
        return []
    newest = sessions[-1]
    return sorted(glob.glob(os.path.join(newest, "*.trace.json.gz"))
                  + glob.glob(os.path.join(newest, "*.trace.json")))


_HLO_MODULE_RE = re.compile(r"^HloModule\s+([^\s,]+)")


def hlo_module_name(hlo_text: str) -> Optional[str]:
    """The module name off the compiled HLO's ``HloModule ...`` header
    (what the profiler stamps as ``args.hlo_module`` on its kernel
    events)."""
    m = _HLO_MODULE_RE.match(hlo_text)
    return m.group(1) if m else None


def load_trace_kernel_events(path: str, module: Optional[str] = None
                             ) -> Dict[str, Dict[str, float]]:
    """One Chrome-trace file -> ``{event_name: {"time_us", "calls"}}``
    aggregated over every complete ('X') event.

    ``module`` scopes the read to one HLO module: XLA instruction
    names are unique only PER MODULE, and other jitted programs run
    concurrently during the window (the host backend's actor_step,
    inference services), so an annotated event whose
    ``args.hlo_module`` differs from ``module`` is dropped — its
    ``fusion.1`` is not the update's ``fusion.1``.  Events without the
    annotation pass through (the cost-table join downstream still
    decides what is a kernel), so an exotic backend that doesn't stamp
    modules degrades to the by-name join instead of an empty table."""
    if path.endswith(".gz"):
        raw = gzip.open(path, "rt").read()
    else:
        raw = open(path).read()
    data = json.loads(raw)
    events = data.get("traceEvents", data if isinstance(data, list) else [])
    out: Dict[str, Dict[str, float]] = {}
    for event in events:
        if event.get("ph") != "X":
            continue
        name = event.get("name")
        if not name:
            continue
        if module is not None:
            event_module = (event.get("args") or {}).get("hlo_module")
            if event_module is not None and event_module != module:
                continue
        entry = out.setdefault(name, {"time_us": 0.0, "calls": 0.0})
        entry["time_us"] += float(event.get("dur", 0.0))
        entry["calls"] += 1.0
    return out


# -- the join ----------------------------------------------------------------


def build_kernel_table(events: Dict[str, Dict[str, float]],
                       costs: Dict[str, Dict[str, float]],
                       flops_total: float = 0.0,
                       peak_flops: Optional[float] = None,
                       executions: int = 1) -> dict:
    """Join trace events with HLO costs by kernel name.

    ``flops_total`` is the XLA cost-analysis FLOPs for ONE execution of
    the profiled program (the ledger-MFU numerator); ``executions`` is
    how many times it ran inside the trace window.  Per-kernel
    ``flops`` (per execution) are the HLO estimates normalized so they
    sum exactly to ``flops_total`` — XLA's aggregate stays
    authoritative, the parse distributes it.  Rows sort by total time
    descending."""
    rows = []
    matched_time = 0.0
    est_total = 0.0
    for name, event in events.items():
        cost = costs.get(name)
        if cost is None:
            continue
        matched_time += event["time_us"]
        per_exec = event["calls"] / max(1, executions)
        est_total += cost["flops_est"] * per_exec
        rows.append({
            "name": name,
            "time_us": round(event["time_us"], 3),
            "calls": int(event["calls"]),
            "flops_est": cost["flops_est"] * per_exec,
            "flops_est_per_call": cost["flops_est"],
            "bytes": cost["bytes"],
            "op": cost["op"],
            "scope": cost.get("scope"),
        })
    scale = (flops_total / est_total
             if flops_total > 0 and est_total > 0 else 1.0)
    window_time_us = sum(e["time_us"] for e in events.values())
    for row in rows:
        row["flops"] = row["flops_est"] * scale
        row["time_share"] = (row["time_us"] / matched_time
                             if matched_time else 0.0)
        # Intensity is a PER-CALL property (flops/byte of one kernel
        # launch): a scan-body kernel called T times per execution has
        # T-times the aggregate flops but the same per-call bytes, so
        # using the aggregate would inflate it T-fold and misread
        # memory-bound kernels as compute-bound.
        row["intensity"] = (row["flops_est_per_call"] / row["bytes"]
                            if row["bytes"] else 0.0)
        seconds = row["time_us"] / 1e6
        achieved = (row["flops"] * executions / seconds
                    if seconds > 0 else 0.0)
        row["mfu"] = (achieved / peak_flops if peak_flops else 0.0)
    rows.sort(key=lambda r: -r["time_us"])

    unmatched = sorted(
        ({"name": name, "time_us": round(e["time_us"], 3),
          "calls": int(e["calls"])}
         for name, e in events.items() if name not in costs),
        key=lambda r: -r["time_us"])

    worst = None
    for row in rows:
        if row["mfu"] <= 0 or row["time_share"] < WORST_MIN_TIME_SHARE:
            continue
        if worst is None or row["mfu"] < worst["mfu"]:
            worst = row
    dominant = rows[0] if rows else None
    # Stage attribution (the device_bound split obs/report.py names):
    # matched device time by named-scope origin — env vs inference vs
    # learner — with scope-less kernels surfaced honestly as
    # "unattributed" rather than folded into a stage.
    scope_time: Dict[str, float] = {}
    for row in rows:
        key = row["scope"] or "unattributed"
        scope_time[key] = scope_time.get(key, 0.0) + row["time_us"]
    scope_time_shares = {
        key: value / matched_time
        for key, value in sorted(scope_time.items())
    } if matched_time else {}
    return {
        "schema_version": _SCHEMA_VERSION,
        "executions": executions,
        "flops_total": flops_total,
        "flops_est_total": est_total,
        "flops_scale": scale,
        "peak_flops": peak_flops,
        "matched_time_us": round(matched_time, 3),
        "matched_time_frac": (matched_time / window_time_us
                              if window_time_us else 0.0),
        "kernels": rows,
        "unmatched_events": unmatched[:16],
        "worst_kernel": worst["name"] if worst else None,
        "worst_kernel_mfu": worst["mfu"] if worst else None,
        "dominant_kernel": dominant["name"] if dominant else None,
        "dominant_time_share": (dominant["time_share"] if dominant
                                else None),
        "scope_time_shares": scope_time_shares,
    }


def write_kernels_json(logdir: str, table: dict,
                       extra: Optional[dict] = None,
                       name: str = KERNELS_JSON_NAME) -> str:
    """Atomically persist the kernel table as ``<logdir>/<name>``
    (default ``kernels.json``, the artifact obs/report.py reads; the
    health plane writes anomaly windows as
    ``kernels.<anomaly_id>.json``)."""
    payload = dict(table)
    if extra:
        payload.update(extra)
    path = os.path.join(logdir, name)
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=1)
    os.replace(tmp, path)
    return path


# -- registry export + verdict hand-off --------------------------------------

# Last published verdict, gated on registry identity like the ledger's
# stall hand-off: the stall attributor (obs/stall.py) reads it to name
# the worst kernel inside a device_bound verdict, and a table published
# against a private registry must not leak into another run's verdict.
_last_lock = threading.Lock()
_last: Dict[str, object] = {}


def publish_kernel_metrics(table: dict, registry=None) -> None:
    """Fold the table head into the metrics registry: per-kernel
    ``kernel/<name>/mfu`` + ``kernel/<name>/time_share`` gauges for the
    top ``PUBLISH_TOP_N`` kernels by time, plus the verdict gauges
    ``kernel/worst_mfu`` / ``kernel/dominant_time_share`` and the
    match-coverage gauge.  Fleet folds (obs/aggregate.py): every
    ``kernel/*`` series takes the MAX — the busiest/most-telling
    process wins, and the worst-kernel label rides the per-kernel
    series names."""
    from scalable_agent_tpu.obs.registry import get_registry

    registry = registry or get_registry()
    for row in table["kernels"][:PUBLISH_TOP_N]:
        registry.gauge(
            f"kernel/{row['name']}/mfu",
            "roofline MFU of this kernel in the last profile window"
        ).set(row["mfu"])
        registry.gauge(
            f"kernel/{row['name']}/time_share",
            "share of matched device time in the last profile window"
        ).set(row["time_share"])
    if table.get("worst_kernel") is not None:
        registry.gauge(
            "kernel/worst_mfu",
            "lowest roofline MFU among kernels above the time-share "
            "floor (the roofline target)").set(
                table["worst_kernel_mfu"] or 0.0)
    if table.get("dominant_kernel") is not None:
        registry.gauge(
            "kernel/dominant_time_share",
            "time share of the single largest kernel").set(
                table["dominant_time_share"] or 0.0)
    registry.gauge(
        "kernel/matched_time_frac",
        "fraction of trace event time joined to an HLO kernel").set(
            table.get("matched_time_frac", 0.0))
    with _last_lock:
        _last["registry"] = registry
        _last["worst"] = ((table["worst_kernel"],
                           table["worst_kernel_mfu"])
                          if table.get("worst_kernel") else None)
        _last["dominant"] = ((table["dominant_kernel"],
                              table["dominant_time_share"])
                             if table.get("dominant_kernel") else None)


def last_worst(registry) -> Optional[Tuple[str, float]]:
    """(name, mfu) of the worst kernel from the last table published
    against ``registry``; None when none was, or it was another
    registry's."""
    with _last_lock:
        if _last.get("registry") is not registry:
            return None
        return _last.get("worst")


def last_dominant(registry) -> Optional[Tuple[str, float]]:
    with _last_lock:
        if _last.get("registry") is not registry:
            return None
        return _last.get("dominant")


# -- the driver entry point --------------------------------------------------


def harvest(profile_dir: str, hlo_text: str, flops_total: float,
            peak_flops: Optional[float], logdir: Optional[str],
            registry=None, executions: int = 1,
            extra: Optional[dict] = None,
            out_name: str = KERNELS_JSON_NAME) -> Optional[dict]:
    """Build + persist + publish the kernel ledger for one profile
    window.  Returns the table, or None when the window left no trace
    files (the profiler can fail silently on exotic backends) — never
    raises on missing artifacts, this runs on the driver's teardown-
    adjacent path."""
    traces = find_profiler_traces(profile_dir)
    if not traces:
        return None
    module = hlo_module_name(hlo_text)
    events: Dict[str, Dict[str, float]] = {}
    for path in traces:
        try:
            for name, entry in load_trace_kernel_events(
                    path, module=module).items():
                agg = events.setdefault(name,
                                        {"time_us": 0.0, "calls": 0.0})
                agg["time_us"] += entry["time_us"]
                agg["calls"] += entry["calls"]
        except (OSError, json.JSONDecodeError):
            continue
    if not events:
        return None
    costs = parse_hlo_kernel_costs(hlo_text)
    table = build_kernel_table(events, costs, flops_total=flops_total,
                               peak_flops=peak_flops,
                               executions=executions)
    if logdir:
        write_kernels_json(logdir, table, extra=extra, name=out_name)
    publish_kernel_metrics(table, registry=registry)
    return table
