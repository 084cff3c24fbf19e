"""From a profiler trace to numbers: busy/idle, per-op self time, kernel
sums, idle gaps by what the host was doing, exposed collectives.

Two stages.  ``load_xplane`` turns ``*.xplane.pb`` (read with
``jax.profiler.ProfileData``, nothing but JAX) into plain ``Event``
tuples; everything after that is pure Python on those tuples, so the
tests run it on a small recorded list kept beside them.

Device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line
holds one event per executed HLO instruction (a ``while`` spans its
body's events, so time per op is SELF time: an event's duration minus
the events nested inside it), and ``XLA Modules`` one event per program
run.  The host plane's lines are threads; the program's spans land
there through ``jax.profiler.TraceAnnotation``.
"""

import glob
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start: float      # seconds on the trace's clock
    dur: float        # seconds


DEVICE_PLANE_RE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
NO_SPAN = "no_host_span_open"
BETWEEN_OPS = "between_device_ops"
TINY_GAP_S = 20e-6


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


class EventList(list):
    """A trace's events with their (plane, line) groups built once: a
    host-loop trace holds millions, and every reduction asks for one
    line of one plane."""

    groups: Optional[Dict[Tuple[str, str], List[Event]]] = None


def load_xplane(path: str) -> List[Event]:
    from jax.profiler import ProfileData

    events = EventList()
    data = ProfileData.from_file(path)
    for plane in data.planes:
        device = DEVICE_PLANE_RE.match(plane.name)
        if not device and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for event in line.events:
                if event.duration_ns <= 0:
                    continue
                events.append(Event(plane.name, line.name, event.name,
                                    event.start_ns * 1e-9,
                                    event.duration_ns * 1e-9))
    return events


def short_name(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    head = name.strip().split(" ", 1)[0]
    return head.lstrip("%")


def label(name: str, width: int = 64) -> str:
    """A name the ledger can hold: letters, digits, ``_.-`` only."""
    return re.sub(r"[^A-Za-z0-9_.\-]", "_", name.lstrip("%"))[:width]


def device_ids(events: Iterable[Event]) -> List[str]:
    return sorted({e.plane for e in events
                   if DEVICE_PLANE_RE.match(e.plane)})


def line_events(events: Iterable[Event], plane: str,
                line: str) -> List[Event]:
    """One line of one plane, by start (longest first on a tie)."""
    order = lambda e: (e.start, -e.dur)  # noqa: E731
    if not isinstance(events, EventList):
        return sorted((e for e in events
                       if e.plane == plane and e.line == line), key=order)
    if events.groups is None:
        events.groups = {}
        for event in events:
            events.groups.setdefault(
                (event.plane, event.line), []).append(event)
        for group in events.groups.values():
            group.sort(key=order)
    return events.groups.get((plane, line), [])


def union_length(intervals: Iterable[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in merge(intervals))


def merge(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def window_of(events: Sequence[Event]) -> Tuple[float, float]:
    """The traced window: first start to last end over all planes."""
    return (min(e.start for e in events),
            max(e.start + e.dur for e in events))


def self_times(ops: Sequence[Event]) -> List[Tuple[Event, float]]:
    """(event, self seconds) for events of ONE line, nested by time:
    a parent's self time is its duration less its direct children's."""
    out: List[List] = []
    stack: List[int] = []
    for event in sorted(ops, key=lambda e: (e.start, -e.dur)):
        while stack:
            top = out[stack[-1]][0]
            if event.start >= top.start + top.dur - 1e-12:
                stack.pop()
            else:
                break
        if stack:
            out[stack[-1]][1] -= min(event.dur,
                                     out[stack[-1]][0].start
                                     + out[stack[-1]][0].dur - event.start)
        out.append([event, event.dur])
        stack.append(len(out) - 1)
    return [(e, max(0.0, s)) for e, s in out]


def busy_seconds(events: Sequence[Event], plane: str) -> float:
    ops = line_events(events, plane, OPS_LINE) or line_events(
        events, plane, MODULES_LINE)
    return union_length((e.start, e.start + e.dur) for e in ops)


def busy_and_window(events: Sequence[Event]) -> Tuple[float, float]:
    """(device busy seconds averaged over the chips used, seconds of
    the traced window)."""
    planes = device_ids(events)
    if not planes:
        return 0.0, 0.0
    start, end = window_of([e for e in events if e.plane in planes])
    busy = [busy_seconds(events, plane) for plane in planes]
    return sum(busy) / len(busy), end - start


def op_totals(events: Sequence[Event], plane: str
              ) -> Dict[str, Tuple[float, int]]:
    """short op name -> (self seconds, events) on one device."""
    totals: Dict[str, List[float]] = {}
    for event, self_s in self_times(line_events(events, plane, OPS_LINE)):
        entry = totals.setdefault(event.name, [0.0, 0])
        entry[0] += self_s
        entry[1] += 1
    return {name: (v[0], int(v[1])) for name, v in totals.items()}


def top_ops(events: Sequence[Event], plane: str,
            n: int = 10) -> List[Tuple[str, float]]:
    totals = op_totals(events, plane)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1][0])[:n]
    return [(label(name), seconds) for name, (seconds, _) in ranked]


def module_runs(events: Sequence[Event], plane: str,
                contains: Optional[str] = None) -> List[Event]:
    """Program runs on one device, whole inside the traced window.  With
    no ``contains``: the runs of the module that took most time."""
    runs = line_events(events, plane, MODULES_LINE)
    if not runs:
        return []
    if contains is None:
        by_name: Dict[str, float] = {}
        for run in runs:
            key = re.sub(r"\(\d+\)$", "", run.name)
            by_name[key] = by_name.get(key, 0.0) + run.dur
        contains = max(by_name, key=by_name.get)
    ops = line_events(events, plane, OPS_LINE)
    lo = ops[0].start if ops else runs[0].start
    hi = max((o.start + o.dur for o in ops), default=runs[-1].start)
    return [r for r in runs if contains in r.name
            and r.start >= lo - 1e-9 and r.start + r.dur <= hi + 1e-6]


def kernel_seconds(events: Sequence[Event], plane: str, match,
                   inside: Optional[Sequence[Event]] = None
                   ) -> Tuple[float, int]:
    """(summed seconds of ALL events whose name ``match`` accepts,
    number of such events), optionally only those inside the given
    program runs."""
    spans = merge((r.start, r.start + r.dur) for r in inside) \
        if inside is not None else None
    total, count = 0.0, 0
    for event in line_events(events, plane, OPS_LINE):
        if not match(event.name):
            continue
        if spans is not None and not any(
                a - 1e-9 <= event.start and event.start + event.dur
                <= b + 1e-9 for a, b in spans):
            continue
        total += event.dur
        count += 1
    return total, count


def idle_gaps(events: Sequence[Event], plane: str
              ) -> List[Tuple[float, float]]:
    ops = line_events(events, plane, OPS_LINE) or line_events(
        events, plane, MODULES_LINE)
    if not ops:
        return []
    start, end = window_of([e for e in events
                            if DEVICE_PLANE_RE.match(e.plane)])
    busy = merge((e.start, e.start + e.dur) for e in ops)
    gaps, cursor = [], start
    for a, b in busy:
        if a > cursor:
            gaps.append((cursor, a))
        cursor = max(cursor, b)
    if end > cursor:
        gaps.append((cursor, end))
    return gaps


def gaps_by_host_span(events: Sequence[Event], plane: str,
                      n: int = 10) -> List[Tuple[str, float]]:
    """Idle seconds of one device by what the host was doing: each gap
    goes to the host span open at its middle — the program's own spans
    (``layer/name``) first, innermost first; else any host event; else
    ``no_host_span_open``.  Gaps under ``TINY_GAP_S`` (the pause
    between two ops of one program) are not looked up: they go to
    ``between_device_ops``.  One sweep over gaps and host events."""
    host = sorted((e for e in events if e.plane == HOST_PLANE
                   and not e.name.startswith("$")),
                  key=lambda e: e.start)
    totals: Dict[str, float] = {}
    active: List[Event] = []
    cursor = 0
    for a, b in idle_gaps(events, plane):
        if b - a < TINY_GAP_S:
            totals[BETWEEN_OPS] = totals.get(BETWEEN_OPS, 0.0) + (b - a)
            continue
        mid = 0.5 * (a + b)
        while cursor < len(host) and host[cursor].start <= mid:
            active.append(host[cursor])
            cursor += 1
        active = [e for e in active if e.start + e.dur > mid]
        own = [e for e in active if "/" in e.name]
        pick = own or active
        name = (max(pick, key=lambda e: e.start).name if pick
                else NO_SPAN)
        totals[name] = totals.get(name, 0.0) + (b - a)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [(label(name), seconds) for name, seconds in ranked]


def exposed_seconds(events: Sequence[Event], plane: str,
                    is_collective) -> float:
    """Seconds in which a collective runs on the device and no other
    operation does (self times, so a fused region counts once)."""
    leaf = [(e, s) for e, s in self_times(
        line_events(events, plane, OPS_LINE)) if s > 0]
    coll = merge((e.start, e.start + e.dur) for e, _ in leaf
                 if is_collective(e.name))
    other = merge((e.start, e.start + e.dur) for e, _ in leaf
                  if not is_collective(e.name)
                  and not short_name(e.name).startswith("while"))
    exposed = 0.0
    for a, b in coll:
        covered = union_length(
            (max(a, c), min(b, d)) for c, d in other
            if c < b and d > a)
        exposed += (b - a) - covered
    return exposed


def breakdown(events: Sequence[Event]) -> Dict[str, List]:
    planes = device_ids(events)
    if not planes:
        return {"device_ops": [], "idle_gaps": []}
    plane = planes[0]
    return {"device_ops": [[n, s] for n, s in top_ops(events, plane)],
            "idle_gaps": [[n, s] for n, s in
                          gaps_by_host_span(events, plane)]}
