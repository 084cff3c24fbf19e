"""The program's own timeline of a finished run: its spans from the
first line of ``driver.main`` to teardown, with each span's parent and
self time (``scalable_agent_tpu/obs/trace.py``).

Found through the one function the program documents for it,
``obs.trace.last_trace_path()``; a program that has no such function
(the parent of the PR that added it) offers no timeline, every reader
here then finds nothing and its metric is left out of the line.  A test
hands a recorded span list over as ``ctx.program_spans``.

Set-up, launch to window open, in four parts that do not overlap:
before ``main`` was entered (imports and the backend's start-up); the
``setup/*`` stages less the compile spans inside them; tracing and
lowering; backend compiles (or cache loads).  What is left of
``setup_s`` after them is the warm-up after the first dispatch, and a
note says how the parts add up.
"""

import os
from typing import Dict, List, Optional, Tuple

from benchmark.lib.trace_reduce import union_length

BACKEND = "compile/backend"


def trace_path() -> Optional[str]:
    from scalable_agent_tpu.obs import trace

    finder = getattr(trace, "last_trace_path", None)
    path = finder() if finder else None
    return path if path and os.path.exists(path) else None


def spans(ctx) -> List[dict]:
    """Every span of the run (``name, cat, ts, dur`` in microseconds on
    the harness's ``perf_counter`` clock, ``tid, sid, self`` and,
    below the top, ``parent``), read once."""
    if getattr(ctx, "program_spans", None) is None:
        from scalable_agent_tpu.obs.trace import load_trace_events

        path = trace_path()
        ctx.program_spans = [] if path is None else [
            e for e in load_trace_events(path)
            if e.get("ph") == "X" and "sid" in e]
    return ctx.program_spans


def stages(ctx) -> List[dict]:
    """The ``setup/*`` stages, main entry to the first dispatch
    returning: top-level spans of the set-up category, in order."""
    return sorted((e for e in spans(ctx) if e.get("cat") == "setup"
                   and "parent" not in e), key=lambda e: e["ts"])


def _seconds(intervals) -> float:
    return union_length(intervals) * 1e-6


def _compiles(ctx, lo_us: float, hi_us: float,
              only: Optional[str] = None) -> List[Tuple[float, float]]:
    """Intervals of compile spans (all, or those named ``only``) that
    start at or after ``lo_us`` and end by ``hi_us``."""
    return [(e["ts"], e["ts"] + e["dur"]) for e in spans(ctx)
            if e.get("cat") == "compile"
            and (only is None or e["name"] == only)
            and e["ts"] >= lo_us and e["ts"] + e["dur"] <= hi_us]


def setup_parts(ctx) -> Optional[Dict[str, float]]:
    """Seconds of each part of set-up (see the module's text), or None
    where the program recorded no stages or the window never opened."""
    if getattr(ctx, "setup_parts", None) is not None:
        return ctx.setup_parts
    staged = stages(ctx)
    if not staged or ctx.t_open is None:
        return None
    entry = staged[0]["ts"]
    done = max(e["ts"] + e["dur"] for e in staged)
    t_open = ctx.t_open * 1e6
    in_stages = _seconds(_compiles(ctx, entry, done))
    every = _seconds(_compiles(ctx, entry, t_open))
    backend = _seconds(_compiles(ctx, entry, t_open, only=BACKEND))
    parts = {
        "before_main": entry * 1e-6 - ctx.t_launch,
        "build": sum(e["dur"] for e in staged) * 1e-6 - in_stages,
        "trace_lower": every - backend,
        "backend": backend,
        "after_first_dispatch": (t_open - done) * 1e-6,
        "compiles_after_first_dispatch": every - in_stages,
    }
    total = (parts["before_main"] + parts["build"] + parts["trace_lower"]
             + parts["backend"] + parts["after_first_dispatch"]
             - parts["compiles_after_first_dispatch"])
    by_stage = ", ".join(
        f"{e['name'][len('setup/'):]} {e['dur'] * 1e-6:.2f}"
        f" (self {e['self'] * 1e-6:.2f})" for e in staged)
    ctx.notes.append(
        "set-up by part (s): before main {before_main:.2f} + build "
        "{build:.2f} + trace/lower {trace_lower:.2f} + backend "
        "{backend:.2f} + after first dispatch {after_first_dispatch:.2f} "
        "(of which compiles, counted above, "
        "{compiles_after_first_dispatch:.2f})".format(**parts)
        + f" = {total:.2f} of setup_s {ctx.t_open - ctx.t_launch:.2f}")
    ctx.notes.append(f"set-up stages (s): {by_stage}")
    ctx.setup_parts = parts
    return parts


def setup_part(ctx, name: str) -> Optional[float]:
    parts = setup_parts(ctx)
    return None if parts is None else parts[name]
