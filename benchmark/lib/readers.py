"""What the per-layer readers share: span statistics, program runs in
the trace, kernel roofline shares, model FLOPs from shapes.

A reader (``benchmark/metrics/<name>.py``) is ``read(ctx) -> number or
None``: None when there is nothing to read (no such span, no device
plane, a kernel that did not run), and the harness then leaves the
metric out of the line.
"""

import os
import statistics
from typing import Dict, List, Optional, Tuple

from benchmark.lib import manifest, trace_reduce, window

_ROOFLINES: Dict[str, object] = {}


# -- spans --------------------------------------------------------------------

def span_durations(ctx, name: str) -> List[float]:
    return [dur for span, _, dur in ctx.spans if span == name]


def span_median_ms(ctx, name: str) -> Optional[float]:
    durations = span_durations(ctx, name)
    return statistics.median(durations) * 1e3 if durations else None


def span_window_s(ctx) -> Optional[float]:
    if not ctx.spans:
        return None
    start = min(s for _, s, _ in ctx.spans)
    end = max(s + d for _, s, d in ctx.spans)
    return end - start


# -- the device trace ---------------------------------------------------------

def planes(ctx) -> List[str]:
    return trace_reduce.device_ids(ctx.events)


def step_runs(ctx, plane: str):
    """Whole runs, inside the traced window, of the program the cell's
    traffic names as its step (``step_module``)."""
    return trace_reduce.module_runs(
        ctx.events, plane, ctx.traffic.get("step_module"))


def step_device_ms(ctx) -> Optional[float]:
    per_plane = []
    for plane in planes(ctx):
        runs = step_runs(ctx, plane)
        if runs:
            per_plane.append(sum(r.dur for r in runs) / len(runs))
    return statistics.mean(per_plane) * 1e3 if per_plane else None


def idle_share(ctx) -> Optional[float]:
    if not planes(ctx):
        return None
    busy, span = trace_reduce.busy_and_window(ctx.events)
    return 100.0 * (1.0 - busy / span) if span > 0 else None


def top_op_share(ctx) -> Optional[float]:
    if not planes(ctx):
        return None
    plane = planes(ctx)[0]
    totals = trace_reduce.op_totals(ctx.events, plane)
    busy = trace_reduce.busy_seconds(ctx.events, plane)
    if not totals or busy <= 0:
        return None
    name, (seconds, _) = max(totals.items(), key=lambda kv: kv[1][0])
    ctx.notes.append(
        f"top device op: {trace_reduce.label(name, 120)} "
        f"{seconds:.6f}s of {busy:.6f}s busy")
    return 100.0 * seconds / busy


def custom_call_table(ctx) -> List[List]:
    """Every custom-call name on the first device with its event count
    and summed seconds: what a roofline's name match has to tell apart."""
    if not planes(ctx):
        return []
    rows = []
    totals = trace_reduce.op_totals(ctx.events, planes(ctx)[0])
    for name, (seconds, count) in totals.items():
        if "custom-call" in name or "custom_call" in name \
                or "kernel" in name:
            rows.append([name[:300], count, seconds])
    return sorted(rows, key=lambda r: -r[2])[:40]


# -- rooflines ----------------------------------------------------------------

def roofline_module(kernel: str):
    if kernel not in _ROOFLINES:
        path = os.path.join(manifest.BENCH_DIR, manifest.ROOFLINES_DIR,
                            kernel + ".py")
        _ROOFLINES[kernel] = manifest.load_module(path, kernel)
    return _ROOFLINES[kernel]


def least_seconds(flops: float, bytes_moved: float,
                  peak: dict) -> Tuple[float, str]:
    by_flops = flops / peak["flops_bf16"]
    by_bytes = bytes_moved / peak["hbm_bytes_per_s"]
    return ((by_flops, "compute") if by_flops >= by_bytes
            else (by_bytes, "memory"))


def roofline_share(ctx, kernel: str) -> Optional[float]:
    """Least time of one call (from shapes) over measured time of one
    call: ALL device events of the kernel inside whole step runs, over
    the kernel's calls counted the same way (``calls_per_step`` x step
    runs)."""
    module = roofline_module(kernel)
    plane_list = planes(ctx)
    if not plane_list or ctx.peak is None:
        return None
    plane = plane_list[0]
    match = module.matcher(ctx)
    # whole step runs in which the kernel ran: its calls are counted
    # where its events are summed, nowhere else
    runs = [run for run in step_runs(ctx, plane)
            if trace_reduce.kernel_seconds(
                ctx.events, plane, match, inside=[run])[1]]
    if not runs:
        return None
    seconds, events = trace_reduce.kernel_seconds(
        ctx.events, plane, match, inside=runs)
    calls = module.CALLS_PER_STEP * len(runs)
    counts = module.least(ctx)
    least, bound = least_seconds(counts["flops"], counts["bytes"], ctx.peak)
    per_call = seconds / calls
    ctx.notes.append(
        f"{kernel}: {events} events in {len(runs)} step runs, "
        f"{per_call * 1e3:.4f} ms/call measured, least "
        f"{least * 1e3:.4f} ms ({bound}-bound: {counts['flops']:.4g} flop, "
        f"{counts['bytes']:.4g} B)")
    return 100.0 * least / per_call


# -- model FLOPs from shapes --------------------------------------------------

def _same(size: int, stride: int) -> int:
    return -(-size // stride)


def forward_flops_per_step(cfg) -> Dict[str, float]:
    """Multiply-add FLOPs (2 per MAC) of one agent step's forward pass,
    by part, from the configuration's sizes."""
    h, w, c = cfg["frame_height"], cfg["frame_width"], cfg["frame_channels"]
    parts: Dict[str, float] = {}
    if cfg["torso"] == "shallow":
        cin = c
        for i, (cout, k, s) in enumerate(cfg["conv_layers"]):
            h, w = _same(h, s), _same(w, s)
            parts[f"conv_{i}"] = 2.0 * h * w * cout * k * k * cin
            cin = cout
        stem = "conv_0"
    else:
        cin = c
        for i, (cout, blocks) in enumerate(cfg["resnet_sections"]):
            parts[f"downscale_{i}"] = 2.0 * h * w * cout * 9 * cin
            h, w = _same(h, 2), _same(w, 2)
            parts[f"residual_{i}"] = (
                blocks * 2 * 2.0 * h * w * cout * 9 * cout)
            cin = cout
        stem = "downscale_0"
    fc, hid, acts = cfg["fc_size"], cfg["lstm_size"], cfg["num_actions"]
    parts["fc"] = 2.0 * h * w * cin * fc
    parts["lstm"] = 2.0 * (fc + 1 + acts + hid) * 4 * hid
    parts["heads"] = 2.0 * hid * (acts + 1)
    parts["_stem"] = parts[stem]
    return parts


def train_flops_per_env_frame(cfg) -> float:
    """Acting forward + learning forward + backward (2x forward, less
    the stem's input gradient, which nothing needs) per agent step,
    over the action repeats.  Rematerialized forwards are not counted."""
    parts = forward_flops_per_step(cfg)
    stem = parts.pop("_stem")
    forward = sum(parts.values())
    per_step = forward + forward + (2.0 * forward - stem)
    return per_step / cfg["num_action_repeats"]


def mfu(ctx) -> Optional[float]:
    """Model FLOPs of one step (from shapes; a rematerialized forward is
    not model work and is not counted) over the device time of one whole
    run of the step program in the trace, over the chip's peak.  The
    count is the cell's reference module's ``train_flops_per_env_frame``
    where it brings one, and the one above where it does not."""
    step_ms = step_device_ms(ctx)
    if step_ms is None or ctx.peak is None:
        return None
    count = getattr(getattr(ctx, "reference", None),
                    "train_flops_per_env_frame", train_flops_per_env_frame)
    flops = count(ctx.config) * ctx.frames_per_update
    return (100.0 * flops
            / (ctx.chips * step_ms * 1e-3 * ctx.peak["flops_bf16"]))


def block_median_rate(ctx) -> Optional[float]:
    return window.block_median_rate(ctx.retires, ctx.frames_per_update)


def interval_p95_ms(ctx) -> Optional[float]:
    return window.percentile(window.intervals_ms(ctx.retires), 95)
