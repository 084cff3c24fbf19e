"""Elastic fleet membership: reshard-and-continue on peer loss.

PR 5 (runtime/fleet.py) turned "one peer dies -> every survivor hangs
forever" into a bounded, attributed exit 72 — but the whole fleet still
died with the peer: the survivors' only recovery was a full external
restart at the SAME size, impossible while the lost host is gone.  This
module is that external restart, made a first-class, membership-aware
part of the system (ROADMAP item 3; the availability story behind
IMPALA's decoupled design and the preemption-tolerant fleet schedulers
in PAPERS.md):

- **The supervisor** (``--elastic`` on the driver, or
  ``python -m scalable_agent_tpu.runtime.elastic`` with the same
  flags) owns the N worker processes.  It never trains — and never
  initializes a jax backend (on TPU that would lock the chips its
  workers need).  It watches worker EXIT CODES through the registry in
  runtime/exit_codes.py and the machine-readable membership verdict
  the fleet monitor writes (``<logdir>/fleet_epoch.json``: epoch,
  kind, lost peers, last verified checkpoint step).

- **Membership epochs.**  Every (re)launch is one epoch.  A
  fleet-fatal (exit 72 on the survivors, the lost worker SIGKILLed)
  becomes a RESHARD event: the lost slot is marked out, and the
  survivors relaunch as an (N-1)-process fleet — within a restart
  budget with capped exponential backoff — resuming frame-exact from
  the newest verified checkpoint (the walk-back restore owns which
  step that is; ``verify_after_reshard`` in runtime/checkpoint.py
  re-proves the per-leaf CRCs after the state reshards over the new,
  smaller mesh).  ``fleet/resize_total`` counts membership-size
  changes; MTTR (first observed worker death -> first post-reshard
  metrics row) lands in ``fleet/mttr_s`` and ``fleet_epochs.jsonl``,
  decomposed into detect/relaunch/compile/restore segments via the
  driver's ``mttr_breakdown.json`` startup beacon (the compile
  segment also lands in ``fleet/mttr_compile_s``; the persistent
  compile cache — utils/compile_cache.py, inherited by every relaunch
  through ``JAX_COMPILATION_CACHE_DIR`` — is what flattens it).

- **Rejoin.**  When the lost host comes back (locally:
  ``--elastic_rejoin_delay_s`` elapsed, or an operator touched
  ``<logdir>/rejoin.<slot>``), the supervisor schedules a scale-up at
  the next checkpoint boundary: it SIGTERMs the running fleet, whose
  preemption-grace protocol (PR 5) drains to ONE coordinated verified
  checkpoint and exits 0, then relaunches at the full size — so the
  fleet returns to N without losing a single verified frame.

- **Exit-code policy** (docs/robustness.md renders this): 72 and
  SIGABRT (134 / signal 6 — jax's own client fatal when the
  coordinator dies, see runtime/fleet.py) are *reshardable*; SIGKILL
  marks the slot *lost*; 70 (watchdog wedge) and 73 (the numerics
  sentinel's silent-corruption verdict, runtime/sentinel.py) restart
  at the same shape — a wedge clears on relaunch, and a sentinel trip
  that survived the ladder + rollback points at transient hardware
  state a fresh process may not share (the resumed run re-audits from
  its first interval); 71 (non-finite) is *fatal* — something
  poisoned the regime
  and a supervisor restarting blindly would just replay it; 0 is done
  — unless the epoch's verdict file says "preempt", in which case the
  drain was a checkpoint, not a finish line, and the fleet relaunches.

The membership history is one timeline: ``fleet_epochs.jsonl`` (one
JSON line per launch/exit/mttr event), the workers' ``fleet/epoch``
gauge (obs/aggregate.py folds it max), and the supervisor's own
``metrics.supervisor.prom`` snapshot (``fleet/resize_total``,
``fleet/mttr_s``) that the aggregator merges under the ``supervisor``
process label.

Everything is testable without real fleets: the launcher is
injectable (tests/test_elastic.py drives the whole state machine with
scripted fake workers and a virtual clock), and the real soak
(tests/test_elastic_multiproc.py, markers ``multiproc slow``) proves a
3-process fleet losing a peer via SIGKILL continues as 2 within the
MTTR budget and scales back to 3, frame-exact throughout.
"""

import json
import os
import signal
import subprocess
import sys
import time
from typing import Callable, Dict, List, Optional, Sequence

from scalable_agent_tpu.runtime.exit_codes import (
    FLEET_EXIT_CODE,
    NONFINITE_EXIT_CODE,
    SENTINEL_EXIT_CODE,
    WATCHDOG_EXIT_CODE,
)
from scalable_agent_tpu.runtime.fleet import EPOCH_VERDICT_NAME
from scalable_agent_tpu.utils import log

__all__ = [
    "DriverLauncher",
    "ElasticSupervisor",
    "classify_exit",
    "compatible_fleet_size",
    "main",
    "run_supervised",
]

EPOCHS_LOG_NAME = "fleet_epochs.jsonl"
SUPERVISOR_PROM_NAME = "metrics.supervisor.prom"
# The driver's startup-cost beacon (driver._write_mttr_breakdown):
# {"epoch": E, "restore_s": ..., "compile_s": ...} written atomically
# by the relaunched coordinator after its first dispatch.  The
# supervisor joins it (epoch-matched) into the epochs-log ``mttr``
# record so the recovery time decomposes into detect / relaunch /
# compile / restore segments — the evidence behind the compile-cache
# MTTR engineering (docs/robustness.md).
MTTR_BREAKDOWN_NAME = "mttr_breakdown.json"

# Exit-code policy (the supervisor side of runtime/exit_codes.py).
RESHARDABLE = "reshardable"   # relaunch; the slot survives
LOST = "lost"                 # the slot's host is gone: reshard N-1
RESTART_SAME = "restart"      # wedge (watchdog 70): relaunch as-is
FATAL = "fatal"               # inspect before restarting (non-finite)
OK = "ok"

# jax's C++ coordination client aborts (signal 6) when the coordinator
# dies under it — a SURVIVOR of someone else's death, not a lost host
# (runtime/fleet.py module docstring; subprocess reports it as -6,
# a shell as 134).
_SIGABRT_CODES = (-signal.SIGABRT, 128 + signal.SIGABRT)
_SIGKILL_CODES = (-signal.SIGKILL, 128 + signal.SIGKILL)


def compatible_fleet_size(batch_size: Optional[int], max_n: int) -> int:
    """The largest fleet size <= ``max_n`` that divides the global
    batch (the driver shards the batch evenly over processes).  An
    elastic reshard cannot pick its survivor count — hosts die where
    they die — so incompatible intermediate sizes are SKIPPED: a
    batch-256 fleet that drops from 4 hosts to 3 runs as 2 (the third
    stays idle until the lost host rejoins) rather than failing at
    relaunch.  ``batch_size=None`` disables the constraint."""
    if batch_size is None:
        return max_n
    for n in range(max_n, 1, -1):
        if batch_size % n == 0:
            return n
    return 1


def _exit_status(code: int) -> int:
    """``Popen``'s killed-by-signal ``-N`` -> the POSIX ``128+N``
    status an outer scheduler actually sees; non-negative codes pass
    through.  Without this, propagating ``max(codes)`` of a
    segfaulting fleet would exit the supervisor with a raw negative
    (rendered as a meaningless 2xx status) instead of 139."""
    return 128 - code if code < 0 else code


def classify_exit(code: int) -> str:
    """One worker exit code -> supervisor policy bucket."""
    if code == 0:
        return OK
    if code == NONFINITE_EXIT_CODE:
        return FATAL
    if code in (WATCHDOG_EXIT_CODE, SENTINEL_EXIT_CODE):
        # 73: the sentinel exhausted its ladder + rollback — the shape
        # is fine, the arithmetic wasn't; relaunch as-is and let the
        # fresh process's audits re-judge the hardware.
        return RESTART_SAME
    if code in _SIGKILL_CODES:
        return LOST
    if code == FLEET_EXIT_CODE or code in _SIGABRT_CODES:
        return RESHARDABLE
    # Any other death (tracebacked exception, segfault, OOM-kill shows
    # as SIGKILL above): the host is fine, the process crashed —
    # relaunch against the restart budget.
    return RESHARDABLE


class DriverLauncher:
    """Spawn one epoch's worker fleet: N copies of the driver CLI on
    this machine, sharing a fresh coordinator port.  Workers inherit
    the supervisor's stdout/stderr (nothing buffers, nothing
    deadlocks) and environment.

    CPU rig only, and it says so: N drivers on ONE machine each claim
    every accelerator they can see, and a TPU chip belongs to one
    process — the second worker would fail or hang at backend init.
    ``launch`` therefore refuses unless the workers' environment pins
    ``JAX_PLATFORMS=cpu``.  Real multi-host deployments replace this
    class (one worker per host via the cluster scheduler — ROADMAP
    R7); the supervisor's state machine doesn't change."""

    # Supervisor-owned fields the workers must not inherit verbatim.
    EXCLUDE = ("elastic", "fleet_epoch", "distributed_coordinator",
               "distributed_num_processes", "distributed_process_id")

    def __init__(self, config, env: Optional[Dict[str, str]] = None):
        self._config = config
        self._env = env

    def launch(self, epoch: int, num_processes: int,
               port: int) -> List[subprocess.Popen]:
        env = os.environ if self._env is None else self._env
        platforms = env.get("JAX_PLATFORMS", "")
        if platforms.split(",")[0].strip() != "cpu":
            raise RuntimeError(
                f"DriverLauncher starts {num_processes} driver "
                f"process(es) on this one machine and has only ever run "
                f"on the CPU rig: with JAX_PLATFORMS={platforms!r} every "
                f"worker would claim every TPU chip, and a chip belongs "
                f"to one process.  Set JAX_PLATFORMS=cpu, or launch one "
                f"worker per host with your scheduler (ROADMAP R7)")
        base = self._config.to_argv(exclude=self.EXCLUDE)
        workers = []
        for proc_id in range(num_processes):
            args = [
                sys.executable, "-m", "scalable_agent_tpu.driver",
                *base,
                f"--fleet_epoch={epoch}",
                f"--distributed_coordinator=localhost:{port}",
                f"--distributed_num_processes={num_processes}",
                f"--distributed_process_id={proc_id}",
            ]
            workers.append(subprocess.Popen(args, env=self._env))
        return workers


class ElasticSupervisor:
    """The membership state machine.  Injectable launcher/clock/sleep/
    port factory so every transition is unit-testable; the defaults
    run real fleets."""

    def __init__(self, n_target: int, logdir: str,
                 launcher,
                 restart_budget: int = 8,
                 stable_s: float = 300.0,
                 rejoin_delay_s: float = 60.0,
                 backoff_initial_s: float = 1.0,
                 backoff_cap_s: float = 30.0,
                 poll_s: float = 0.5,
                 clock: Callable[[], float] = time.monotonic,
                 sleep: Callable[[float], None] = time.sleep,
                 port_factory: Optional[Callable[[], int]] = None,
                 registry=None,
                 batch_size: Optional[int] = None):
        if n_target < 1:
            raise ValueError(f"n_target must be >= 1, got {n_target}")
        self.n_target = int(n_target)
        self._batch_size = batch_size
        self.logdir = os.path.abspath(logdir)
        self._launcher = launcher
        self._restart_budget = int(restart_budget)
        self._stable_s = float(stable_s)
        self._rejoin_delay_s = float(rejoin_delay_s)
        self._backoff_initial_s = float(backoff_initial_s)
        self._backoff_cap_s = float(backoff_cap_s)
        self._poll_s = float(poll_s)
        self._clock = clock
        self._sleep = sleep
        if port_factory is None:
            from scalable_agent_tpu.parallel.distributed import (
                pick_unused_port,
            )

            port_factory = pick_unused_port
        self._port_factory = port_factory

        # Slot model: slot i is a host seat.  available -> may run a
        # worker; lost_at timestamps when its worker was killed out
        # from under us (SIGKILL = the host is gone).
        self._available = [True] * self.n_target
        self._lost_at: Dict[int, float] = {}
        self.epoch = -1
        self._prev_n: Optional[int] = None
        self._consecutive_failures = 0
        self._shutdown_requested = False
        self._last_mttr_s: Optional[float] = None

        if registry is None:
            from scalable_agent_tpu.obs import get_registry

            registry = get_registry()
        self._epoch_gauge = registry.gauge(
            "fleet/epoch",
            "membership epoch of the currently-running fleet")
        self._size_gauge = registry.gauge(
            "fleet/size", "worker processes in the current epoch")
        self._resizes = registry.counter(
            "fleet/resize_total",
            "membership-size changes (reshard down + rejoin up)")
        self._mttr_gauge = registry.gauge(
            "fleet/mttr_s",
            "last reshard's mean-time-to-recover: first observed "
            "worker death to the first post-reshard metrics row")
        self._mttr_compile_gauge = registry.gauge(
            "fleet/mttr_compile_s",
            "compile segment of the last reshard's MTTR (the relaunched "
            "coordinator's first dispatch) — near-zero when the "
            "persistent compile cache turns it into a disk read")
        self._restarts = registry.counter(
            "fleet/supervisor_restarts_total",
            "fleet relaunches after a non-clean epoch exit")
        from scalable_agent_tpu.obs import PrometheusExporter

        os.makedirs(self.logdir, exist_ok=True)
        self._prom = PrometheusExporter(
            registry, os.path.join(self.logdir, SUPERVISOR_PROM_NAME))

    # -- small pure helpers (unit-tested) ----------------------------------

    def available_slots(self) -> List[int]:
        return [i for i, up in enumerate(self._available) if up]

    def mark_lost(self, slot: int, now: Optional[float] = None):
        if self._available[slot]:
            self._available[slot] = False
            self._lost_at[slot] = (self._clock() if now is None
                                   else now)

    def rejoinable_slots(self, now: Optional[float] = None) -> List[int]:
        """Lost slots whose hosts count as back: the rejoin delay
        elapsed, or an operator touched ``<logdir>/rejoin.<slot>``."""
        now = self._clock() if now is None else now
        back = []
        for slot, lost_at in self._lost_at.items():
            marker = os.path.join(self.logdir, f"rejoin.{slot}")
            if (now - lost_at >= self._rejoin_delay_s
                    or os.path.exists(marker)):
                back.append(slot)
        return sorted(back)

    def _rejoin(self, slots: Sequence[int]):
        for slot in slots:
            self._available[slot] = True
            self._lost_at.pop(slot, None)
            marker = os.path.join(self.logdir, f"rejoin.{slot}")
            try:
                os.remove(marker)
            except OSError:
                pass

    def backoff_s(self) -> float:
        """Capped exponential backoff keyed on consecutive failures."""
        if self._consecutive_failures <= 0:
            return 0.0
        return min(self._backoff_cap_s,
                   self._backoff_initial_s
                   * 2 ** (self._consecutive_failures - 1))

    def read_verdict(self) -> Optional[dict]:
        """The fleet's membership verdict file (fleet_epoch.json), or
        None when absent/unparseable.  ``_run`` deletes the file
        before every launch, so what's here was written by a CURRENT
        incarnation's epoch — callers still compare
        ``verdict["epoch"]`` against the epoch that just exited (an
        older epoch of THIS incarnation could have raced its exit)."""
        try:
            return json.load(open(
                os.path.join(self.logdir, EPOCH_VERDICT_NAME)))
        except (OSError, json.JSONDecodeError, ValueError):
            return None

    def _record(self, event: str, **fields):
        """One line of membership history (fleet_epochs.jsonl) + a
        fresh supervisor prom snapshot."""
        payload = dict(event=event, epoch=self.epoch,
                       t_unix=time.time(), **fields)
        path = os.path.join(self.logdir, EPOCHS_LOG_NAME)
        try:
            with open(path, "a") as f:
                f.write(json.dumps(payload) + "\n")
        except OSError:
            log.exception("elastic: could not append %s", path)
        try:
            self._prom.dump()
        except Exception:
            log.exception("elastic: supervisor prom dump failed")

    # -- steady-state cycle (bench-timed) ----------------------------------

    def watch_cycle(self, workers, jsonl_baseline: Optional[int],
                    mttr_anchor: Optional[float]):
        """One supervisor poll: worker exit codes, the post-reshard
        MTTR beacon, and the rejoin probe.  This is the WHOLE
        steady-state cost of being supervised (bench.py bench_elastic
        amortizes it at the poll cadence against the update stage);
        everything heavier happens only on membership transitions.

        Returns ``(codes, mttr_s)`` — per-worker exit codes (None =
        running) and the measured MTTR if the beacon fired this
        cycle."""
        codes = [w.poll() for w in workers]
        mttr_s = None
        if mttr_anchor is not None:
            path = os.path.join(self.logdir, "metrics.jsonl")
            try:
                size = os.path.getsize(path)
            except OSError:
                size = 0
            if size > (jsonl_baseline or 0):
                mttr_s = self._clock() - mttr_anchor
        return codes, mttr_s

    # -- the run -----------------------------------------------------------

    def _install_signals(self):
        """Supervisor SIGTERM/SIGINT = drain the fleet gracefully and
        exit — the workers' own grace protocol turns that into one
        coordinated verified checkpoint.  Returns an uninstall
        callable (run() restores the handlers on the way out so an
        in-process caller — a test — keeps its own)."""

        def _on_signal(signum, frame):
            self._shutdown_requested = True
            log.warning("elastic: %s — draining the fleet to a final "
                        "checkpoint and exiting",
                        signal.Signals(signum).name)

        prev = {}
        try:
            for sig in (signal.SIGTERM, signal.SIGINT):
                prev[sig] = signal.signal(sig, _on_signal)
        except ValueError:  # not the main thread (tests)
            prev.clear()

        def uninstall():
            for sig, handler in prev.items():
                try:
                    signal.signal(sig, handler)
                except ValueError:
                    pass

        return uninstall

    def _read_mttr_breakdown(self) -> dict:
        """The current epoch's startup-cost beacon
        (``MTTR_BREAKDOWN_NAME``), or {} when absent, unparseable, or
        written by a different epoch (an old driver, or a beacon the
        relaunch hasn't reached yet)."""
        try:
            payload = json.load(open(
                os.path.join(self.logdir, MTTR_BREAKDOWN_NAME)))
        except (OSError, json.JSONDecodeError, ValueError):
            return {}
        if not isinstance(payload, dict) \
                or payload.get("epoch") != self.epoch:
            return {}
        return payload

    def _mttr_segments(self, mttr_s: float,
                       mttr_anchor: Optional[float],
                       launched_at: Optional[float]) -> dict:
        """Decompose a measured MTTR into detect / relaunch / compile /
        restore segments: detect = death -> relaunch (supervisor
        detection, epoch drain, backoff), restore/compile from the
        driver's startup beacon, relaunch = the remainder (spawn, jax
        and env construction, first-row wait).  Segments that cannot
        be attributed are omitted — the record stays honest when the
        relaunched driver predates the beacon."""
        segments = {}
        if mttr_anchor is not None and launched_at is not None:
            segments["detect_s"] = round(
                max(0.0, launched_at - mttr_anchor), 3)
        breakdown = self._read_mttr_breakdown()
        for key in ("restore_s", "compile_s"):
            value = breakdown.get(key)
            if isinstance(value, (int, float)):
                segments[key] = round(float(value), 3)
        if "compile_s" in segments:
            self._mttr_compile_gauge.set(segments["compile_s"])
        if {"detect_s", "restore_s", "compile_s"} <= set(segments):
            segments["relaunch_s"] = round(
                max(0.0, mttr_s - segments["detect_s"]
                    - segments["restore_s"] - segments["compile_s"]), 3)
        return segments

    def _watch(self, workers, mttr_anchor: Optional[float],
               launched_at: Optional[float] = None):
        """Poll one epoch's fleet to completion.  Returns
        ``(codes, drained_for_scale_up, first_death_at)``."""
        jsonl_path = os.path.join(self.logdir, "metrics.jsonl")
        try:
            jsonl_baseline = os.path.getsize(jsonl_path)
        except OSError:
            jsonl_baseline = 0
        drain_sent = False
        scale_up = False
        first_death_at: Optional[float] = None
        n = len(workers)
        while True:
            codes, mttr_s = self.watch_cycle(
                workers, jsonl_baseline, mttr_anchor)
            now = self._clock()
            if mttr_s is not None:
                self._last_mttr_s = mttr_s
                self._mttr_gauge.set(mttr_s)
                segments = self._mttr_segments(mttr_s, mttr_anchor,
                                               launched_at)
                self._record("mttr", mttr_s=round(mttr_s, 3),
                             **segments)
                log.info("elastic: reshard MTTR %.1fs (kill -> first "
                         "post-reshard metrics row) %s", mttr_s,
                         {k: v for k, v in segments.items()})
                mttr_anchor = None
            if first_death_at is None and any(
                    c is not None for c in codes):
                first_death_at = now
            if all(c is not None for c in codes):
                return codes, scale_up, first_death_at
            if not drain_sent and self._shutdown_requested:
                drain_sent = True
                self._terminate_all(workers)
            if (not drain_sent and first_death_at is None
                    and n < self.n_target and self.rejoinable_slots(now)):
                # Scale-up at the next checkpoint boundary: the grace
                # drain IS that boundary — one coordinated verified
                # checkpoint, every worker exits 0, and the relaunch
                # below resumes the larger fleet from it.
                drain_sent = True
                scale_up = True
                log.info(
                    "elastic: slot(s) %s rejoinable — draining the "
                    "%d-process fleet at the next checkpoint boundary "
                    "to scale back up", self.rejoinable_slots(now), n)
                self._record("scale_up_drain",
                             slots=self.rejoinable_slots(now))
                self._terminate_all(workers)
            self._sleep(self._poll_s)

    @staticmethod
    def _terminate_all(workers):
        for worker in workers:
            if worker.poll() is None:
                try:
                    worker.terminate()
                except OSError:
                    pass

    def run(self) -> int:
        """Supervise until the training run completes (0), the restart
        budget is exhausted (the dominant worker code), or a fatal
        verdict lands (71)."""
        uninstall = self._install_signals()
        try:
            return self._run()
        finally:
            uninstall()

    def _run(self) -> int:
        mttr_anchor: Optional[float] = None
        while True:
            if self._shutdown_requested:
                # A SIGTERM that lands between epochs (e.g. during the
                # backoff sleep) must not launch one more fleet.
                return 0
            slots = self.available_slots()
            if not slots:
                log.error("elastic: no available slots left")
                return FLEET_EXIT_CODE
            # The batch must shard evenly over the fleet: skip
            # incompatible intermediate sizes (the extra healthy slots
            # idle until the lost host rejoins).
            n = compatible_fleet_size(self._batch_size, len(slots))
            if n < len(slots):
                log.warning(
                    "elastic: batch %s does not divide over %d "
                    "processes — launching %d, slot(s) %s idle this "
                    "epoch", self._batch_size, len(slots), n,
                    slots[n:])
            slots = slots[:n]
            self.epoch += 1
            self._epoch_gauge.set(float(self.epoch))
            self._size_gauge.set(float(n))
            if self._prev_n is not None and n != self._prev_n:
                self._resizes.inc()
            self._prev_n = n
            port = self._port_factory()
            epoch_started = self._clock()
            # A membership verdict can only belong to the epoch that
            # writes it: clear any stale file (a previous epoch's, or a
            # previous supervisor INCARNATION's whose epoch numbering
            # restarted at 0 and would pass the epoch-match check).
            try:
                os.remove(os.path.join(self.logdir, EPOCH_VERDICT_NAME))
            except OSError:
                pass
            workers = self._launcher.launch(self.epoch, n, port)
            self._record(
                "launch", num_processes=n, slots=slots, port=port,
                pids=[getattr(w, "pid", None) for w in workers])
            log.info("elastic: epoch %d up — %d worker(s) on slots %s",
                     self.epoch, n, slots)

            codes, scale_up, first_death_at = self._watch(
                workers, mttr_anchor, launched_at=epoch_started)
            mttr_anchor = None
            ran_s = self._clock() - epoch_started
            if ran_s >= self._stable_s:
                self._consecutive_failures = 0
            kinds = [classify_exit(c) for c in codes]
            verdict = self.read_verdict()
            stale = not verdict or verdict.get("epoch") != self.epoch
            outcome, ret = self._classify_epoch(
                codes, kinds, scale_up,
                None if stale else verdict)
            self._record(
                "exit", codes=codes, outcome=outcome,
                lost_slots=[slots[i] for i, k in enumerate(kinds)
                            if k == LOST],
                verdict_kind=(None if stale else verdict.get("kind")),
                ran_s=round(ran_s, 3))
            log.info("elastic: epoch %d down (%s) — codes %s",
                     self.epoch, outcome, codes)

            if outcome == "done":
                return 0
            if outcome == "fatal":
                return NONFINITE_EXIT_CODE
            if outcome == "shutdown":
                return ret
            if outcome == "scale_up":
                self._rejoin(self.rejoinable_slots())
                continue
            if outcome == "preempt":
                # External preemption drained cleanly: not a failure.
                continue
            # reshard / restart: mark SIGKILLed slots lost, charge the
            # budget, back off, relaunch the survivors.
            now = self._clock()
            for i, kind in enumerate(kinds):
                if kind == LOST:
                    self.mark_lost(slots[i], now)
            self._consecutive_failures += 1
            self._restarts.inc()
            if self._consecutive_failures > self._restart_budget:
                log.error(
                    "elastic: restart budget exhausted (%d consecutive "
                    "failed epochs) — giving up with code %d",
                    self._consecutive_failures - 1, ret)
                self._record("budget_exhausted",
                             failures=self._consecutive_failures - 1)
                return ret
            mttr_anchor = first_death_at if first_death_at is not None \
                else now
            delay = self.backoff_s()
            if delay:
                log.warning(
                    "elastic: relaunching in %.1fs (failure %d/%d)",
                    delay, self._consecutive_failures,
                    self._restart_budget)
                self._sleep(delay)

    def _classify_epoch(self, codes, kinds, scale_up, verdict):
        """(outcome, exit_code) for one finished epoch.  ``verdict`` is
        the epoch-matched fleet_epoch.json payload or None."""
        if self._shutdown_requested:
            return "shutdown", max(
                (_exit_status(c) for c in codes if c), default=0)
        if FATAL in kinds:
            return "fatal", NONFINITE_EXIT_CODE
        if all(k == OK for k in kinds):
            if scale_up:
                return "scale_up", 0
            if verdict and verdict.get("kind") == "preempt":
                return "preempt", 0
            return "done", 0
        if LOST in kinds:
            return "reshard", FLEET_EXIT_CODE
        return "restart", max(
            (_exit_status(c) for c in codes if c),
            default=FLEET_EXIT_CODE)


def run_supervised(config) -> int:
    """Driver ``--elastic`` entry: supervise
    ``--distributed_num_processes`` (or 1) workers running this exact
    config."""
    n_target = config.distributed_num_processes or 1
    # The FULL fleet must be able to shard the batch (fail at launch,
    # not at first dispatch); intermediate reshard sizes need not —
    # compatible_fleet_size skips them, idling the extra slots.
    if config.batch_size % n_target:
        raise ValueError(
            f"batch_size {config.batch_size} is not divisible by the "
            f"fleet size {n_target} (--distributed_num_processes)")
    supervisor = ElasticSupervisor(
        n_target, config.logdir, DriverLauncher(config),
        restart_budget=config.elastic_restart_budget,
        stable_s=config.elastic_stable_s,
        rejoin_delay_s=config.elastic_rejoin_delay_s,
        batch_size=config.batch_size)
    return supervisor.run()


def main(argv: Optional[Sequence[str]] = None) -> int:
    """``python -m scalable_agent_tpu.runtime.elastic`` — the same
    flag surface as the driver (config.py), supervisor mode forced."""
    from scalable_agent_tpu.config import Config

    config = Config.from_argv(
        argv,
        description=(
            "Elastic fleet supervisor: owns "
            "--distributed_num_processes worker processes, reshards "
            "the survivors on peer loss, and scales back up on "
            "rejoin.  Takes the driver's full flag surface — see "
            "python -m scalable_agent_tpu.driver --help for the "
            "curated flag reference."))
    if config.mode != "train":
        raise ValueError("the elastic supervisor only supervises "
                         "--mode=train runs")
    return run_supervised(config)


if __name__ == "__main__":
    raise SystemExit(main())
