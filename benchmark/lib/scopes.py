"""Device time of a step by the layer its ops belong to.

A v5e profiler trace names each device event by its HLO instruction
(``%fusion.238 = ...``) and carries no ``op_name`` (my chip run, PR 24:
an ``XLA Ops`` event holds ``device_offset_ps``, ``device_duration_ps``
and nothing else), so an op's scope comes from the table the program
leaves beside its span trace after a ``--trace`` run,
``op_scopes.p<proc>.<pid>.json``: instruction name -> ``op_name``, the
``jax.named_scope`` and flax-module path (``obs/kernels.py
write_op_scopes``).  The reader joins on the instruction name.  Found
beside ``obs.trace.last_trace_path()``; with no table (the parent of
the PR that added it) there is nothing to read.  A test hands a table
over as ``ctx.op_scopes``.

Every op event inside a whole run of the step program goes, by its
SELF time (a ``while`` does not count its body twice), to exactly one
class, first match in this order:

    rollout             under scope ``rollout`` (the acting scan)
    telemetry           under scope ``telemetry`` (only the obs plane
                        reads what these compute)
    update.torso        under ``learner_update``, path holds ``convnet``
    update.core         ... holds ``core`` (the LSTM)
    update.loss_heads   ... holds ``vtrace_loss``, ``policy_logits`` or
                        ``baseline``
    update.optimizer    ... holds ``optimizer``
    unscoped            anything else, and ops the table does not name

A share is of the device time of those whole step runs, so the classes
and the gaps between ops add up to 100; the mean over the cell's chips.
A reader of a scope these classes do not hold apart asks
``share_where(ctx, pattern)``.
"""

import json
import re
import statistics
from typing import Dict, Optional

from benchmark.lib import readers, timeline, trace_reduce

CLASSES = ("rollout", "telemetry", "update.torso", "update.core",
           "update.loss_heads", "update.optimizer", "unscoped")


def _word(*names: str):
    return re.compile(r"(?<![A-Za-z0-9_])(?:%s)(?![A-Za-z0-9_])"
                      % "|".join(names))


_FIRST = (("rollout", _word("rollout")), ("telemetry", _word("telemetry")))
_UPDATE = _word("learner_update")
_IN_UPDATE = (
    ("update.torso", _word("convnet")),
    ("update.core", _word("core")),
    ("update.loss_heads", _word("vtrace_loss", "policy_logits",
                                "baseline")),
    ("update.optimizer", _word("optimizer")),
)


def classify(op_name: Optional[str]) -> str:
    if not op_name:
        return "unscoped"
    for name, pattern in _FIRST:
        if pattern.search(op_name):
            return name
    if _UPDATE.search(op_name):
        for name, pattern in _IN_UPDATE:
            if pattern.search(op_name):
                return name
    return "unscoped"


def table(ctx) -> Optional[Dict[str, str]]:
    """Instruction name -> op_name, or None where the program left no
    table."""
    if getattr(ctx, "op_scopes", None) is None:
        trace_path = timeline.trace_path()
        if trace_path is None:
            return None
        # a program that offers its trace has the table's naming rule
        from scalable_agent_tpu.obs.kernels import op_scopes_path

        try:
            with open(op_scopes_path(trace_path)) as f:
                ctx.op_scopes = json.load(f)["ops"]
        except (OSError, ValueError, KeyError):
            return None
    return ctx.op_scopes


def _step_ops(ctx, plane: str):
    """([(instruction name, self seconds)] of every op event inside a
    whole run of the step program, seconds of those runs) on one chip."""
    runs = sorted(readers.step_runs(ctx, plane), key=lambda r: r.start)
    found = []
    events = trace_reduce.line_events(ctx.events, plane,
                                      trace_reduce.OPS_LINE)
    cursor = 0
    for event, self_s in trace_reduce.self_times(events):
        while cursor < len(runs) and event.start >= (
                runs[cursor].start + runs[cursor].dur):
            cursor += 1
        if cursor == len(runs):
            break
        run = runs[cursor]
        if event.start < run.start - 1e-9 or (
                event.start + event.dur > run.start + run.dur + 1e-9):
            continue
        found.append((trace_reduce.short_name(event.name), self_s))
    return found, sum(r.dur for r in runs)


def _plane_shares(ctx, plane: str, ops: Dict[str, str]):
    """({class: seconds}, {unscoped op: seconds}, seconds of whole step
    runs) on one chip."""
    seconds = dict.fromkeys(CLASSES, 0.0)
    unscoped: Dict[str, float] = {}
    found, total = _step_ops(ctx, plane)
    for name, self_s in found:
        kind = classify(ops.get(name))
        seconds[kind] += self_s
        if kind == "unscoped":
            unscoped[name] = unscoped.get(name, 0.0) + self_s
    return seconds, unscoped, total


def shares(ctx) -> Optional[Dict[str, float]]:
    """{class: % of the step's device time}, mean over chips; the whole
    table goes to the run's notes once."""
    if getattr(ctx, "scope_shares", None) is not None:
        return ctx.scope_shares
    ops = table(ctx)
    if ops is None:
        return None
    per_plane, first_unscoped = [], None
    for plane in readers.planes(ctx):
        seconds, unscoped, total = _plane_shares(ctx, plane, ops)
        if total <= 0:
            continue
        per_plane.append({k: 100.0 * v / total for k, v in seconds.items()})
        if first_unscoped is None:
            first_unscoped = (unscoped, total)
    if not per_plane:
        return None
    out = {k: statistics.mean(p[k] for p in per_plane) for k in CLASSES}
    ctx.notes.append(
        "step device time by scope (% of whole step runs, mean over "
        f"{len(per_plane)} chip(s)): "
        + ", ".join(f"{k} {out[k]:.2f}" for k in CLASSES)
        + f"; gaps between ops {100.0 - sum(out.values()):.2f}")
    unscoped, total = first_unscoped
    top = sorted(unscoped.items(), key=lambda kv: -kv[1])[:6]
    if top:
        ctx.notes.append(
            "largest unscoped ops (% of step, op_name): " + "; ".join(
                f"{trace_reduce.label(name)} {100.0 * s / total:.2f} "
                f"({(ops.get(name) or 'not in the table')[-60:]})"
                for name, s in top))
    ctx.scope_shares = out
    return out


def share(ctx, kind: str) -> Optional[float]:
    found = shares(ctx)
    return None if found is None else found[kind]


def share_where(ctx, pattern) -> Optional[float]:
    """% of the step's device time in ops whose ``op_name`` the pattern
    finds (``re.search``; a string or a compiled pattern; a scope is a
    whole word of the path: ``r"\\bexperts\\b"``): the same self times
    over the same whole step runs as ``share``, mean over chips, for a
    scope the seven classes do not hold apart.  0.0 where nothing
    matches; None with no table or no whole step run."""
    ops = table(ctx)
    if ops is None:
        return None
    wanted = re.compile(pattern)
    per_plane = []
    for plane in readers.planes(ctx):
        found, total = _step_ops(ctx, plane)
        if total <= 0:
            continue
        seconds = 0.0
        for name, self_s in found:
            if wanted.search(ops.get(name) or ""):
                seconds += self_s
        per_plane.append(100.0 * seconds / total)
    return statistics.mean(per_plane) if per_plane else None
