"""Chaos soak engine: seeded fault schedules graded against SLOs.

Every recovery mechanism in the robustness layer exists in isolation —
non-finite rollback, fleet fault domains, elastic reshard, the
numerics sentinel — and each chaos point (runtime/faults.py) is proven
one-at-a-time in tests.  This module is the layer that turns them into
ONE graded, repeatable claim (ROADMAP item 3; the availability story
of Espeholt et al. 1802.01561): a **seeded randomized fault schedule**
sampled from the chaos registry with per-point weights, injected into
an **already-running** fleet through the runtime channel
(``<logdir>/chaos_inject.jsonl`` under ``--chaos_channel``), graded by
a continuous **invariant checker** and written atomically as a
schema'd ``soak_report.json``.

The invariants (each graded independently; the soak passes only when
every one holds):

- ``throughput_floor`` — every healthy-window throughput reading stays
  >= ``floor`` (default 0.8) of the run's OWN healthy-window baseline
  (median fps over rows whose measurement interval touches no injected
  fault's declared recovery window; the first row — startup compile —
  is always excluded).
- ``mttr_ceiling`` — every reshard's epochs-log ``mttr`` event
  (runtime/elastic.py) stays under the ceiling.
- ``frame_exactness`` — the final verified checkpoint's
  ``env_frames == updates * frames_per_update`` exactly: no fault may
  double-count or drop a frame.
- ``final_checkpoint`` — the walk-back restore
  (runtime/checkpoint.py) finds a checkpoint that verifies against its
  per-leaf CRC manifest.
- ``quiet_outside_windows`` — zero health-plane anomaly records
  (obs/health.py) outside the injected windows, and no more sentinel
  trips than injected sentinel-class faults: recovery noise must be
  attributable to the schedule, never spontaneous.

CLI::

    python -m scalable_agent_tpu.runtime.soak run \
        --soak_seed=1 --soak_faults=6 --soak_budget_s=120 \
        --logdir=/tmp/soak --mode=train --level_name=fake_small ...
    python -m scalable_agent_tpu.runtime.soak report --logdir=/tmp/soak

``run`` takes the driver's full flag surface after its own ``--soak_*``
flags, forces ``--chaos_channel``, launches the elastic supervisor
(``--distributed_num_processes`` > 1 or ``--elastic``) or the
single-process driver, appends the schedule's channel lines at their
sampled times, SIGTERMs the run at the wall budget (the preemption
grace protocol drains to one final verified checkpoint), then grades.
Mid-soak relaunches compile from disk: the persistent compile cache
(utils/compile_cache.py) is always armed and every child inherits
``JAX_COMPILATION_CACHE_DIR`` — the MTTR engineering half of the story
(docs/robustness.md, "Running a chaos soak").

The schedule is deterministic in (seed, faults, budget, points):
``sample_schedule`` drives one ``random.Random(seed)``, so a soak
failure replays with the same flags.  Faults are sampled only inside
the middle of the budget (after ``SCHEDULE_WARMUP_FRAC``, before
``SCHEDULE_COOLDOWN_FRAC`` from the end) so startup compile and the
final drain checkpoint stay clean.

``bench.py bench_soak`` runs a short seeded single-process soak and
publishes ``soak_pass`` / ``soak_throughput_floor_frac`` /
``soak_mttr_worst_s`` into the round artifact, where
``soak_regression_guard`` and the ``rounds report`` scoreboard's
``chaos_soak`` target (item 3) grade it per round.
"""

import argparse
import dataclasses
import json
import os
import random
import re
import signal
import subprocess
import sys
import time
from typing import Dict, List, Optional, Sequence

from scalable_agent_tpu.runtime.faults import CHANNEL_NAME, CHAOS_POINTS
from scalable_agent_tpu.utils import log

__all__ = [
    "DEFAULT_WEIGHTS",
    "FLEET_ONLY_POINTS",
    "SOAK_REPORT_NAME",
    "check_invariants",
    "grade_soak",
    "main",
    "read_soak_report",
    "run_soak",
    "sample_schedule",
]

SOAK_REPORT_NAME = "soak_report.json"
SOAK_SCHEMA_VERSION = 1

# Schedule sampling weights over the chaos registry.  Weight 0 points
# exist in the registry but are excluded from random schedules:
# service_stall needs --actor=service, replay_corrupt needs
# --replay_ratio>0, the sentinel-class points need --sentinel_interval
# — a schedule is sampled against the CONFIG the soak runs, and
# run_soak enables exactly the points the config can consume (callers
# can pass their own points/weights).
DEFAULT_WEIGHTS: Dict[str, float] = {
    "nan_grad": 3.0,
    "throughput_sag": 3.0,
    "actor_raise": 2.0,
    "worker_kill": 2.0,
    "ckpt_torn": 1.0,
    "ckpt_save_fail": 1.0,
    "peer_exit": 2.0,
    "preempt_sigterm": 0.0,   # ends the run — opt-in only
    "peer_hang": 0.0,         # wedges a peer until peer_timeout_s
    "service_stall": 0.0,
    "replay_corrupt": 0.0,
    "param_bitflip": 0.0,
    "kernel_miscompute": 0.0,
    "replica_diverge": 0.0,
}

# Points that only make sense with a multi-process fleet under the
# elastic supervisor (they kill/wedge a peer and expect a reshard).
FLEET_ONLY_POINTS = ("peer_exit", "peer_hang", "preempt_sigterm",
                     "replica_diverge")

# Declared recovery window per point (seconds after injection during
# which throughput readings and anomaly records are expected and
# excluded from the healthy-window grading).  Fleet deaths cover a
# full relaunch; everything else is absorbed in-process.
DEFAULT_RECOVERY_S: Dict[str, float] = {
    "peer_exit": 120.0,
    "peer_hang": 150.0,
    "preempt_sigterm": 120.0,
    "worker_kill": 30.0,
    "actor_raise": 20.0,
    "ckpt_torn": 10.0,
    "ckpt_save_fail": 10.0,
    "service_stall": 30.0,
    "throughput_sag": 15.0,
    "nan_grad": 15.0,
    "replay_corrupt": 15.0,
    "param_bitflip": 30.0,
    "kernel_miscompute": 30.0,
    "replica_diverge": 60.0,
}
_FALLBACK_RECOVERY_S = 30.0

# The fraction of the budget kept clean at each end: startup compile
# (and its fps row) at the front, the drain's final verified
# checkpoint at the back.
SCHEDULE_WARMUP_FRAC = 0.25
SCHEDULE_COOLDOWN_FRAC = 0.25

# Sentinel-class points: a sentinel trip during the soak is only
# "quiet" if the schedule injected at least that many of these.
SENTINEL_POINTS = ("param_bitflip", "kernel_miscompute",
                   "replica_diverge")


def sample_schedule(seed: int, num_faults: int, budget_s: float,
                    points: Optional[Sequence[str]] = None,
                    weights: Optional[Dict[str, float]] = None,
                    num_processes: int = 1,
                    recovery_s: Optional[Dict[str, float]] = None,
                    ) -> List[dict]:
    """A deterministic fault schedule: ``num_faults`` events sampled
    from ``points`` by weight, at times uniform over the middle of the
    budget, sorted.  Each event is
    ``{"t_s", "point", "proc", "recovery_s"}`` (``proc`` is None
    single-process, else a sampled target process id)."""
    weights = dict(DEFAULT_WEIGHTS if weights is None else weights)
    if points is None:
        points = [p for p, w in weights.items() if w > 0]
        if num_processes <= 1:
            points = [p for p in points if p not in FLEET_ONLY_POINTS]
    unknown = sorted(set(points) - set(CHAOS_POINTS))
    if unknown:
        raise ValueError(
            f"unknown chaos point(s) {unknown} — the registry is "
            f"runtime/faults.py CHAOS_POINTS")
    if not points:
        raise ValueError("no chaos points to sample from")
    recovery_s = dict(DEFAULT_RECOVERY_S if recovery_s is None
                      else recovery_s)
    rng = random.Random(seed)
    lo = budget_s * SCHEDULE_WARMUP_FRAC
    hi = budget_s * (1.0 - SCHEDULE_COOLDOWN_FRAC)
    point_weights = [max(weights.get(p, 1.0), 1e-9) for p in points]
    events = []
    for _ in range(max(0, int(num_faults))):
        point = rng.choices(list(points), weights=point_weights)[0]
        events.append({
            "t_s": round(rng.uniform(lo, hi), 3),
            "point": point,
            "proc": (rng.randrange(num_processes)
                     if num_processes > 1 else None),
            "recovery_s": float(recovery_s.get(point,
                                               _FALLBACK_RECOVERY_S)),
        })
    events.sort(key=lambda e: (e["t_s"], e["point"]))
    return events


# ---------------------------------------------------------------------------
# The invariant checker (pure — unit-tested against synthetic streams)
# ---------------------------------------------------------------------------


def _windows(injected: Sequence[dict]) -> List[tuple]:
    """[(start_unix, end_unix)] recovery windows of the injected
    events (events that never landed carry no ``t_unix`` and declare
    no window)."""
    out = []
    for event in injected:
        t = event.get("t_unix")
        if t is None:
            continue
        out.append((float(t),
                    float(t) + float(event.get("recovery_s",
                                               _FALLBACK_RECOVERY_S))))
    return out


def _in_windows(t: float, windows: Sequence[tuple]) -> bool:
    return any(lo <= t <= hi for lo, hi in windows)


def _overlaps(lo: float, hi: float, windows: Sequence[tuple]) -> bool:
    return any(lo <= whi and wlo <= hi for wlo, whi in windows)


def check_invariants(*, metrics_rows: Sequence[dict],
                     mttr_events: Sequence[dict],
                     anomalies: Sequence[dict],
                     injected: Sequence[dict],
                     ckpt: dict,
                     frames_per_update: int,
                     throughput_floor: float = 0.8,
                     mttr_ceiling_s: float = 180.0,
                     sentinel_trips: int = 0,
                     warmup_until_unix: Optional[float] = None,
                     ) -> Dict[str, dict]:
    """Grade every soak invariant against the run's streams.  Pure:
    callers (and tests/test_soak.py) hand in parsed rows.  Returns
    ``{invariant: {"ok": bool, ...evidence...}}`` — every invariant is
    always present and always graded.

    ``warmup_until_unix``: throughput rows whose measurement interval
    starts before this are excluded — the schedule keeps its warmup
    fraction fault-free precisely because startup compile and actor
    ramp-up are not steady state."""
    windows = _windows(injected)

    # -- throughput_floor --------------------------------------------------
    fps_rows = [r for r in metrics_rows
                if isinstance(r.get("fps"), (int, float))
                and isinstance(r.get("time"), (int, float))]
    graded, excluded = [], 0
    for i, row in enumerate(fps_rows):
        if i == 0:
            excluded += 1  # startup: the first interval is compile
            continue
        interval = (float(fps_rows[i - 1]["time"]), float(row["time"]))
        if warmup_until_unix is not None \
                and interval[0] < warmup_until_unix:
            excluded += 1
            continue
        if _overlaps(interval[0], interval[1], windows):
            excluded += 1
            continue
        graded.append(float(row["fps"]))
    if graded:
        ordered = sorted(graded)
        baseline = ordered[len(ordered) // 2]
        worst = min(graded)
        frac = (worst / baseline) if baseline > 0 else 0.0
        throughput = {
            "ok": bool(baseline > 0 and frac >= throughput_floor),
            "floor": throughput_floor,
            "baseline_fps": round(baseline, 3),
            "worst_fps": round(worst, 3),
            "worst_frac": round(frac, 4),
            "rows_graded": len(graded),
            "rows_excluded": excluded,
        }
    else:
        throughput = {
            "ok": False,
            "floor": throughput_floor,
            "rows_graded": 0,
            "rows_excluded": excluded,
            "detail": "no healthy-window throughput rows to grade",
        }

    # -- mttr_ceiling ------------------------------------------------------
    mttrs = [float(e["mttr_s"]) for e in mttr_events
             if isinstance(e.get("mttr_s"), (int, float))]
    mttr = {
        "ok": bool(all(m <= mttr_ceiling_s for m in mttrs)),
        "ceiling_s": mttr_ceiling_s,
        "events": len(mttrs),
        "worst_s": round(max(mttrs), 3) if mttrs else None,
    }

    # -- frame_exactness ---------------------------------------------------
    step = ckpt.get("step")
    env_frames = ckpt.get("env_frames")
    if step is None or env_frames is None:
        exactness = {"ok": False,
                     "detail": "no verified checkpoint to account "
                               "against"}
    else:
        expected = float(step) * float(frames_per_update)
        exactness = {
            "ok": bool(abs(float(env_frames) - expected) < 0.5),
            "updates": int(step),
            "frames_per_update": int(frames_per_update),
            "env_frames": float(env_frames),
            "expected": expected,
        }

    # -- final_checkpoint --------------------------------------------------
    final = {"ok": bool(ckpt.get("verified")), "step": step}
    if ckpt.get("error"):
        final["error"] = ckpt["error"]

    # -- quiet_outside_windows ---------------------------------------------
    stray = [a for a in anomalies
             if isinstance(a.get("ts_unix"), (int, float))
             and not _in_windows(float(a["ts_unix"]), windows)]
    sentinel_budget = sum(1 for e in injected
                          if e.get("t_unix") is not None
                          and e.get("point") in SENTINEL_POINTS)
    quiet = {
        "ok": bool(not stray and sentinel_trips <= sentinel_budget),
        "stray_anomalies": [
            {"id": a.get("id"), "detector": a.get("detector"),
             "ts_unix": a.get("ts_unix")} for a in stray],
        "anomalies_total": len(anomalies),
        "sentinel_trips": sentinel_trips,
        "sentinel_trip_budget": sentinel_budget,
    }

    return {
        "throughput_floor": throughput,
        "mttr_ceiling": mttr,
        "frame_exactness": exactness,
        "final_checkpoint": final,
        "quiet_outside_windows": quiet,
    }


# ---------------------------------------------------------------------------
# Artifact readers (torn-line tolerant, jax-free)
# ---------------------------------------------------------------------------


def _read_jsonl(path: str) -> List[dict]:
    try:
        lines = open(path).read().splitlines()
    except OSError:
        return []
    rows = []
    for line in lines:
        line = line.strip()
        if not line:
            continue
        try:
            row = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(row, dict):
            rows.append(row)
    return rows


def _read_anomalies(logdir: str) -> List[dict]:
    """Last record per anomaly id (the obs/health.py event-sourced
    read, reimplemented jax-free)."""
    by_id: Dict[str, dict] = {}
    for row in _read_jsonl(os.path.join(logdir, "anomalies.jsonl")):
        anomaly_id = row.get("id")
        if isinstance(anomaly_id, str):
            by_id[anomaly_id] = row
    return list(by_id.values())


_PROM_LINE = re.compile(
    r"^impala_([A-Za-z0-9_]+?)(?:\{[^}]*\})?\s+([0-9eE+.\-]+)\s*$")


def _read_prom_counters(logdir: str) -> Dict[str, float]:
    """{bare_metric_name: max value across label variants} from the
    run's final metrics.prom snapshot."""
    out: Dict[str, float] = {}
    try:
        lines = open(os.path.join(logdir, "metrics.prom")).read(
        ).splitlines()
    except OSError:
        return out
    for line in lines:
        match = _PROM_LINE.match(line.strip())
        if not match:
            continue
        try:
            value = float(match.group(2))
        except ValueError:
            continue
        name = match.group(1)
        out[name] = max(out.get(name, value), value)
    return out


def _inspect_final_checkpoint(logdir: str) -> dict:
    """Walk-back restore + CRC verify of the run's newest checkpoint
    (imports jax — grading runs in the engine process, not the hot
    path).  Returns {"verified", "step", "env_frames", "error"}."""
    from scalable_agent_tpu.runtime.checkpoint import (
        CheckpointIntegrityError,
        CheckpointManager,
    )

    info = {"verified": False, "step": None, "env_frames": None,
            "error": None}
    try:
        restored = CheckpointManager(logdir).restore(target=None)
    except CheckpointIntegrityError as exc:
        info["error"] = str(exc)
        return info
    except Exception as exc:  # unexpected — grade, don't crash
        info["error"] = f"{type(exc).__name__}: {exc}"
        return info
    if restored is None:
        info["error"] = "no checkpoint on disk"
        return info
    step, state = restored
    info["verified"] = True
    info["step"] = int(step)
    env_frames = (state or {}).get("env_frames")
    if env_frames is not None:
        try:
            import numpy as np

            info["env_frames"] = float(np.asarray(env_frames))
        except Exception:
            info["env_frames"] = None
    return info


# ---------------------------------------------------------------------------
# Grading + report
# ---------------------------------------------------------------------------


def grade_soak(logdir: str, *, injected: Sequence[dict],
               planned: Sequence[dict], frames_per_update: int,
               throughput_floor: float = 0.8,
               mttr_ceiling_s: float = 180.0,
               warmup_until_unix: Optional[float] = None,
               meta: Optional[dict] = None) -> dict:
    """Read the run's artifacts (metrics.jsonl, fleet_epochs.jsonl,
    anomalies.jsonl, metrics.prom, the checkpoint directory), grade
    every invariant, and return the schema'd report dict."""
    metrics_rows = _read_jsonl(os.path.join(logdir, "metrics.jsonl"))
    epoch_events = _read_jsonl(os.path.join(logdir,
                                            "fleet_epochs.jsonl"))
    mttr_events = [e for e in epoch_events if e.get("event") == "mttr"]
    anomalies = _read_anomalies(logdir)
    counters = _read_prom_counters(logdir)
    ckpt = _inspect_final_checkpoint(logdir)
    invariants = check_invariants(
        metrics_rows=metrics_rows,
        mttr_events=mttr_events,
        anomalies=anomalies,
        injected=injected,
        ckpt=ckpt,
        frames_per_update=frames_per_update,
        throughput_floor=throughput_floor,
        mttr_ceiling_s=mttr_ceiling_s,
        sentinel_trips=int(counters.get("sentinel_trips_total", 0)),
        warmup_until_unix=warmup_until_unix)
    report = {
        "schema_version": SOAK_SCHEMA_VERSION,
        "logdir": os.path.abspath(logdir),
        "pass": bool(all(v["ok"] for v in invariants.values())),
        "invariants": invariants,
        "injected": list(injected),
        "planned_not_injected": [e for e in planned
                                 if e.get("t_unix") is None],
        "points": sorted({e["point"] for e in injected
                          if e.get("t_unix") is not None}),
        "counters": {
            "faults_injected_total": counters.get(
                "faults_injected_total", 0.0),
            "sentinel_trips_total": counters.get(
                "sentinel_trips_total", 0.0),
            "watchdog_stalls_total": counters.get(
                "watchdog_stalls_total", 0.0),
        },
        "mttr_events": mttr_events,
        "checkpoint": ckpt,
    }
    report.update(meta or {})
    return report


def write_report(logdir: str, report: dict,
                 path: Optional[str] = None) -> str:
    """Atomic (tmp + rename) ``soak_report.json`` write — a killed
    grader must never leave a torn report for `rounds` to parse."""
    path = path or os.path.join(logdir, SOAK_REPORT_NAME)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    os.replace(tmp, path)
    return path


def read_soak_report(logdir: str) -> Optional[dict]:
    try:
        report = json.load(open(os.path.join(logdir,
                                             SOAK_REPORT_NAME)))
    except (OSError, json.JSONDecodeError, ValueError):
        return None
    return report if isinstance(report, dict) else None


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------


def _worker_command(config) -> List[str]:
    """The subprocess the soak drives: the elastic supervisor for a
    fleet (or when --elastic is set), the plain driver otherwise."""
    fleet = (config.distributed_num_processes or 0) > 1 \
        or getattr(config, "elastic", False)
    module = ("scalable_agent_tpu.runtime.elastic" if fleet
              else "scalable_agent_tpu.driver")
    return [sys.executable, "-m", module] + config.to_argv()


def _append_channel_line(logdir: str, event: dict) -> float:
    """Arm one injection in the running fleet.  Returns the stamped
    ``t_unix`` (the injector skips lines predating its own arm time,
    so relaunched epochs never replay consumed lines)."""
    t_unix = time.time()
    line = {"point": event["point"], "t_unix": t_unix}
    if event.get("proc") is not None:
        line["proc"] = int(event["proc"])
    with open(os.path.join(logdir, CHANNEL_NAME), "a") as f:
        f.write(json.dumps(line) + "\n")
        f.flush()
    return t_unix


def run_soak(config, *, seed: int = 0, num_faults: int = 6,
             budget_s: float = 120.0,
             points: Optional[Sequence[str]] = None,
             weights: Optional[Dict[str, float]] = None,
             throughput_floor: float = 0.8,
             mttr_ceiling_s: float = 180.0,
             recovery_s: Optional[Dict[str, float]] = None,
             drain_grace_s: float = 60.0,
             poll_s: float = 0.2,
             env: Optional[Dict[str, str]] = None,
             report_path: Optional[str] = None) -> dict:
    """Run one seeded soak against ``config`` and return the graded
    report (also written to ``<logdir>/soak_report.json``).

    The run ends at whichever comes first: the config's
    ``total_environment_frames``, or ``budget_s`` of wall clock — at
    the budget the engine SIGTERMs the fleet and the preemption grace
    protocol drains it to one final verified checkpoint.  Events still
    pending at exit are reported under ``planned_not_injected``."""
    config = dataclasses.replace(config, chaos_channel=True)
    num_processes = config.distributed_num_processes or 1
    schedule = sample_schedule(
        seed, num_faults, budget_s, points=points, weights=weights,
        num_processes=num_processes, recovery_s=recovery_s)
    os.makedirs(config.logdir, exist_ok=True)
    cmd = _worker_command(config)
    run_env = dict(os.environ)
    run_env.update(env or {})
    log.info("soak: launching %s (seed=%d, %d scheduled fault(s), "
             "budget %.0fs)", " ".join(cmd[:3]), seed, len(schedule),
             budget_s)
    started_unix = time.time()
    start = time.monotonic()
    proc = subprocess.Popen(cmd, env=run_env)
    pending = list(schedule)
    injected: List[dict] = []
    drain_sent = False
    try:
        while proc.poll() is None:
            elapsed = time.monotonic() - start
            while pending and pending[0]["t_s"] <= elapsed:
                # Stamp the SCHEDULE entry itself (not a copy):
                # grade_soak tells planned-but-never-injected events
                # apart by the missing t_unix.
                event = pending.pop(0)
                event["t_unix"] = _append_channel_line(config.logdir,
                                                       event)
                injected.append(event)
                log.info("soak: t=%.1fs injected %r%s", elapsed,
                         event["point"],
                         "" if event.get("proc") is None
                         else f" (proc {event['proc']})")
            if not drain_sent and elapsed >= budget_s:
                drain_sent = True
                log.info("soak: budget reached — draining the run to "
                         "its final checkpoint")
                try:
                    proc.send_signal(signal.SIGTERM)
                except OSError:
                    pass
            if drain_sent and elapsed >= budget_s + drain_grace_s:
                log.error("soak: drain grace exhausted — killing")
                proc.kill()
                break
            time.sleep(poll_s)
        rc = proc.wait()
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    finished_unix = time.time()
    report = grade_soak(
        config.logdir, injected=injected,
        planned=schedule, frames_per_update=config.frames_per_update(),
        throughput_floor=throughput_floor,
        mttr_ceiling_s=mttr_ceiling_s,
        warmup_until_unix=started_unix
        + budget_s * SCHEDULE_WARMUP_FRAC,
        meta={
            "seed": seed,
            "num_faults": num_faults,
            "budget_s": budget_s,
            "num_processes": num_processes,
            "mode": "fleet" if num_processes > 1 else "single",
            "worker_rc": rc,
            "drained": drain_sent,
            "started_unix": round(started_unix, 3),
            "wall_s": round(finished_unix - started_unix, 3),
        })
    path = write_report(config.logdir, report, path=report_path)
    log.info("soak: %s — report at %s",
             "PASS" if report["pass"] else "FAIL", path)
    return report


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def _format_report(report: dict) -> str:
    lines = [
        f"chaos soak: {'PASS' if report.get('pass') else 'FAIL'} "
        f"(seed={report.get('seed')}, mode={report.get('mode')}, "
        f"wall {report.get('wall_s')}s, worker rc "
        f"{report.get('worker_rc')})",
        f"  injected: {len(report.get('injected', []))} event(s) "
        f"across points {report.get('points')}",
    ]
    for name, verdict in sorted(report.get("invariants", {}).items()):
        evidence = {k: v for k, v in verdict.items() if k != "ok"}
        lines.append(
            f"  [{'ok' if verdict.get('ok') else 'FAIL'}] {name}: "
            f"{json.dumps(evidence, sort_keys=True)}")
    skipped = report.get("planned_not_injected") or []
    if skipped:
        lines.append(f"  note: {len(skipped)} scheduled event(s) "
                     f"never injected (run ended first)")
    return "\n".join(lines)


def main(argv: Optional[Sequence[str]] = None) -> int:
    """``python -m scalable_agent_tpu.runtime.soak run|report``."""
    from scalable_agent_tpu.config import Config

    argv = list(sys.argv[1:] if argv is None else argv)
    parser = argparse.ArgumentParser(
        prog="python -m scalable_agent_tpu.runtime.soak",
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("command", choices=("run", "report"))
    parser.add_argument("--soak_seed", type=int, default=0)
    parser.add_argument("--soak_faults", type=int, default=6)
    parser.add_argument("--soak_budget_s", type=float, default=120.0)
    parser.add_argument(
        "--soak_points", type=str, default="",
        help="comma-separated chaos points to sample (default: every "
             "positive-weight point valid for the fleet size)")
    parser.add_argument("--soak_floor", type=float, default=0.8)
    parser.add_argument("--soak_mttr_ceiling_s", type=float,
                        default=180.0)
    parser.add_argument("--soak_report", type=str, default="",
                        help="report path (default "
                             "<logdir>/soak_report.json)")
    parser.add_argument("--logdir", type=str, default="",
                        help="(report) the soaked run's logdir")
    args, rest = parser.parse_known_args(argv)

    if args.command == "report":
        logdir = args.logdir or (rest[0] if rest else "")
        if not logdir:
            parser.error("report needs --logdir")
        report = read_soak_report(logdir)
        if report is None:
            print(f"no {SOAK_REPORT_NAME} under {logdir}")
            return 1
        print(_format_report(report))
        return 0 if report.get("pass") else 1

    if args.logdir:
        rest = [f"--logdir={args.logdir}"] + rest
    config = Config.from_argv(
        rest,
        description="chaos soak worker config (the driver's flag "
                    "surface)")
    if config.mode != "train":
        raise ValueError("the soak engine drives --mode=train runs")
    points = ([p.strip() for p in args.soak_points.split(",")
               if p.strip()] or None)
    report = run_soak(
        config, seed=args.soak_seed, num_faults=args.soak_faults,
        budget_s=args.soak_budget_s, points=points,
        throughput_floor=args.soak_floor,
        mttr_ceiling_s=args.soak_mttr_ceiling_s,
        report_path=args.soak_report or None)
    print(_format_report(report))
    return 0 if report["pass"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
