"""Pipeline ledger: per-trajectory provenance and queueing-model gap
attribution.

BENCH_r04's verdict is a 200x gap between what the learner can eat
(~2.55M env_frames/s) and what the pipeline delivers (12.6k), and the
stall attributor (obs/stall.py) can only name the coarse side of that
gap (device_bound / env_bound / learner_starved).  The ledger answers
the next question: *where along the actor→queue→transport→learner path
does each frame lose its time* — the stage-by-stage pipeline accounting
the async-whole-machine analysis in "Accelerated Methods for Deep RL"
(PAPERS.md) runs on paper, run live against every trajectory.

Every trajectory gets a compact provenance record — birth (the wall /
monotonic moment its unroll started, plus actor thread and env group),
then a stamp at each stage boundary it crosses:

    birth → unroll_done → queue_put → queue_get →
    [transport_pack → transport_upload → transport_unpack] →
    put_done → dispatch → retire

The consecutive stamp pairs partition the trajectory's life into
``SEGMENTS`` (unroll, backpressure, queue_wait, transport, staged_wait,
device), and from the records closed each interval the ledger derives
and publishes through the metrics registry:

- per-segment **arrival rate** ``ledger/rate/<seg>_per_s`` and
  **occupancy** ``ledger/rho/<seg>`` = busy_seconds / interval.  For a
  single-server stage (the prefetch thread's transport, the device)
  that is the classic utilization ρ = λ·S; for a wait stage it is
  Little's-law **L = λ·W** — the mean number of trajectories parked in
  that stage, i.e. *which stage holds the frames*.
- per-segment latency histograms ``ledger/stage/<seg>_s``.
- a **frame-age-at-consumption staleness histogram**
  ``ledger/staleness_s`` (birth → retire; p50/p95/p99 via the registry
  histogram) — the principled staleness metric ROADMAP item 2 needs
  before IMPACT-style replay can be tuned.
- a **live MFU gauge** ``ledger/mfu`` = flops_per_update × retire rate
  / (peak_flops × devices), with flops from the lowered update's cost
  analysis and the peak from the same per-chip roofline table bench.py
  uses (``PEAK_FLOPS`` lives here so the two can never disagree).
- latency shares ``ledger/latency_share/<seg>`` feeding the stall
  verdict's dominant-stage attribution ("learner_starved: 78% of frame
  latency in batcher wait", obs/stall.py) and the gap report
  (``python -m scalable_agent_tpu.obs.report <logdir>``).

Cost discipline (the <2% obs budget, bench.py ``bench_ledger``):
``stamp()`` is lock-free — one dict store on the record plus one atomic
``deque(maxlen)`` append into the flightrec-style stage ring — and runs
per *trajectory stage crossing* (a handful per unroll of thousands of
env frames), never per env step.  ``open``/``close``/``publish`` take
one small lock at trajectory cadence.  Derivation runs only at the
driver's log interval, on the logging thread.

Lifecycle contract (tests/test_ledger.py): every opened record is
eventually closed — ``retire`` (the update materialized), ``discard``
(InflightWindow.discard on the non-finite-rollback path: recorded with
``retired=False`` and counted into ``ledger/frames_discarded_total``
instead of vanishing), or ``abandoned`` (shutdown caught it
in-pipeline; ``finalize()`` sweeps these) — so a clean run exits with
zero open records.

Intentionally jax-free: the report CLI (obs/report.py) imports this
module on a laptop against rsync'd artifacts.
"""

import json
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

__all__ = [
    "PEAK_FLOPS",
    "SEGMENT_LABELS",
    "SEGMENTS",
    "SERVICE_STAGES",
    "SERVICE_UTILIZATION_STAGES",
    "STAGES",
    "TIMING_STAGE_MAP",
    "PipelineLedger",
    "configure_ledger",
    "get_ledger",
    "now_us",
    "peak_flops_per_chip",
]

_SCHEMA_VERSION = 1

# The stage boundaries a trajectory crosses, in pipeline order.  The
# transport_* stamps appear only on the packed transport path (per-leaf
# and device-resident trajectories skip them); every other stamp is laid
# down by the host pipeline for every trajectory.
STAGES = (
    "birth",             # unroll start (first env step of the unroll)
    "unroll_done",       # actor finished the T-step unroll
    "queue_put",         # entered the ActorPool trajectory queue
    "queue_get",         # left the pool queue (prefetch thread)
    "transport_pack",    # staging-buffer pack done (packed transport)
    "transport_upload",  # H2D upload dispatched
    "transport_unpack",  # on-device unpack dispatched
    "put_done",          # device placement complete (any transport)
    "dispatch",          # learner update dispatched
    "retire",            # update materialized (InflightWindow retire)
)

# Consecutive stamp pairs partitioning birth → retire.  Durations clamp
# at zero: queue_put/queue_get race across threads by design (the
# producer stamps after a successful put the consumer may already have
# served), and a few microseconds of skew must not read as negative
# latency.
SEGMENTS = (
    ("unroll", "birth", "unroll_done"),
    ("backpressure", "unroll_done", "queue_put"),
    ("queue_wait", "queue_put", "queue_get"),
    ("transport", "queue_get", "put_done"),
    ("staged_wait", "put_done", "dispatch"),
    ("device", "dispatch", "retire"),
)

# Service stages fed by note_service (arrival count + busy seconds per
# executed batch) rather than by per-record stamps: the dynamic-batching
# inference service runs *beside* the trajectory path, and its ρ answers
# "is actor inference dispatch the constraint".  The continuous-batching
# actor service (runtime/service.py) splits its side into the two
# halves a queueing model needs: ``service_wait`` (request submission →
# batch formation; busy seconds are summed request waits, so ρ is
# Little's-law L — how many requests sit parked) and ``service_batch``
# (the one inference thread's batched execution; ρ is its true
# utilization).  ``replay_insert``/``replay_sample`` are the device
# replay slab's two host-dispatch points (runtime/replay.py) — also
# beside the per-trajectory path: a replayed batch re-enters the
# learner without a new provenance record (its frames were accounted at
# fresh consumption), so its cost shows up here as rate + busy share,
# and its AGE in ``ledger/staleness_replayed_s``.
SERVICE_STAGES = ("inference_service", "service_wait", "service_batch",
                  "replay_insert", "replay_sample")

# The subset of SERVICE_STAGES whose ρ is a genuine utilization in
# [0, 1] (one server's busy seconds per wall second) — the stages
# ``service_pressure()`` and the report's service-dominated verdict
# judge saturation against.  Wait stages (ρ = L, unbounded) stay out.
SERVICE_UTILIZATION_STAGES = ("inference_service", "service_batch")

# Human labels for verdict lines and the report's stage table.
SEGMENT_LABELS = {
    "unroll": "actor unroll (env stepping + inference)",
    "backpressure": "actor backpressure (trajectory queue full)",
    "queue_wait": "batcher wait (trajectory queue)",
    "transport": "host->device transport",
    "staged_wait": "staging wait (learner busy)",
    "device": "device execution (in-flight window)",
    "inference_service": "dynamic-batching inference service",
    "service_wait": "actor-service request wait (batch formation)",
    "service_batch": "actor-service batched inference execution",
    "replay_insert": "replay slab insert dispatch (device-side write)",
    "replay_sample": "replay slab sample dispatch (gather + unpack)",
}

# Every *timing* histogram the runtime registers (names ending `_s`,
# runtime/ + driver.py) must map to the ledger stage whose span it
# measures — tests/test_ledger_lint.py walks the ASTs and fails when a
# new timing stage appears without a mapping (or an explicit allowlist
# entry), so the ledger's stage graph can't silently fall behind the
# instrumentation it is meant to decompose.
TIMING_STAGE_MAP = {
    "actor/env_step_s": "unroll",
    "actor/inference_s": "unroll",
    "batcher/request_latency_s": "inference_service",
    "native_batcher/request_latency_s": "inference_service",
    "learner/put_trajectory_s": "transport",
    "transport/pack_s": "transport",
    "transport/upload_s": "transport",
    "transport/unpack_s": "transport",
    "learner/retire_s": "device",
    "service/wait_s": "service_wait",
    "service/batch_s": "service_batch",
    # enqueue → action spans wait + execution; under load the wait half
    # dominates, so the latency histogram reads with the wait stage.
    "service/request_latency_s": "service_wait",
    "replay/insert_s": "replay_insert",
    "replay/sample_s": "replay_sample",
}

# Peak bf16 matmul FLOP/s per chip by jax device_kind prefix — the ONE
# roofline table: bench.py's MFU numbers and the ledger's live
# ``ledger/mfu`` gauge both read it, so a bench MFU and a run's gauge
# can never disagree about the denominator.
PEAK_FLOPS = [
    ("TPU v6", 918e12),
    ("TPU v5p", 459e12),
    ("TPU v5", 197e12),  # v5e / "TPU v5 lite"
    ("TPU v4", 275e12),
    ("TPU v3", 123e12),
    ("TPU v2", 46e12),
]


def peak_flops_per_chip(device_kind: str) -> Optional[float]:
    """Roofline peak for a jax ``device_kind`` string; None when the
    kind is not in the table — fine for a CPU device, an error for a
    TPU (driver._resolve_roofline_peak raises there)."""
    for prefix, peak in PEAK_FLOPS:
        if device_kind.startswith(prefix):
            return peak
    return None


def now_us() -> int:
    """Monotonic microseconds on the same clock the tracer and flight
    recorder use, so ledger stamps align with trace spans directly."""
    return time.perf_counter_ns() // 1000


class _Record:
    """One trajectory's provenance: identity + stage stamps."""

    __slots__ = ("tid", "actor", "group", "frames", "stamps", "fate")

    def __init__(self, tid: int, actor: str, group: str, frames: float,
                 birth_us: int):
        self.tid = tid
        self.actor = actor
        self.group = group
        self.frames = frames
        self.stamps: Dict[str, int] = {"birth": birth_us}
        self.fate: Optional[str] = None  # retired | discarded | abandoned

    def as_dict(self) -> dict:
        return {"tid": self.tid, "actor": self.actor, "group": self.group,
                "frames": self.frames, "fate": self.fate,
                "stamps": dict(self.stamps)}


class PipelineLedger:
    """Provenance records + queueing-model derivation + export.

    Thread model: ``stamp`` is lock-free (hot path); ``open``/``close``/
    ``bind``/``lookup``/``publish`` share one lock and run at trajectory
    (not env-step) cadence; ``set_current`` is thread-local.
    """

    def __init__(self, registry=None, frames_per_trajectory: float = 0.0,
                 logdir: Optional[str] = None, process_index: int = 0,
                 open_capacity: int = 8192, closed_capacity: int = 8192,
                 ring_capacity: int = 65536, bind_capacity: int = 8192):
        from scalable_agent_tpu.obs.registry import get_registry

        self.registry = registry or get_registry()
        self._registry = self.registry
        self.frames_per_trajectory = float(frames_per_trajectory)
        self.logdir = logdir
        self.process_index = process_index
        self._open_capacity = int(open_capacity)
        self._closed_capacity = int(closed_capacity)
        self._bind_capacity = int(bind_capacity)
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._next_tid = 0
        self._open: Dict[int, _Record] = {}
        self._closed: deque = deque()
        # Flightrec-style per-stage event ring: one atomic append per
        # stamp, dumped with the ledger artifact so a post-mortem can
        # replay the last ~64k stage crossings in order.
        self._ring: deque = deque(maxlen=ring_capacity)
        self._stamps_total = 0  # monotonic; vs ring maxlen = truncation
        self._bindings: Dict[int, int] = {}
        # Service-stage accumulators (note_service): name -> [n, busy_s].
        self._service: Dict[str, List[float]] = {}
        # MFU model (configure_mfu): flops per update / peak / devices.
        self._mfu_flops = 0.0
        self._mfu_peak = 0.0
        self._mfu_devices = 1
        # Derivation state.
        self._epoch_unix_us = int(time.time() * 1e6)
        self._epoch_perf_us = now_us()
        self._last_publish_us = now_us()
        self._last_stats: Dict[str, object] = {}
        self._last_shares: Dict[str, float] = {}
        # Last interval's per-service-stage ρ (persists across empty
        # intervals, like the shares): feeds service_pressure() and the
        # stall verdict's service attribution.
        self._last_service_rho: Dict[str, float] = {}

        reg = self._registry
        self._c_opened = reg.counter(
            "ledger/trajectories_opened_total",
            "trajectory provenance records opened")
        self._c_retired = reg.counter(
            "ledger/trajectories_retired_total",
            "records closed by a materialized update (clean retire)")
        self._c_discarded = reg.counter(
            "ledger/trajectories_discarded_total",
            "records closed retired=False by InflightWindow.discard "
            "(rollback) — their frames never advanced training")
        self._c_abandoned = reg.counter(
            "ledger/trajectories_abandoned_total",
            "records still in-pipeline at shutdown, swept by finalize()")
        self._c_frames_discarded = reg.counter(
            "ledger/frames_discarded_total",
            "env frames in discarded/abandoned trajectories")
        self._c_dropped = reg.counter(
            "ledger/records_dropped_total",
            "records evicted by capacity bounds before derivation "
            "(open-table or closed-window overflow)")
        self._c_late = reg.counter(
            "ledger/late_stamps_total",
            "stamps arriving for an already-closed/evicted record")
        self._g_truncated = reg.gauge(
            "ledger/truncated",
            "1 when any ledger ring/table hit its capacity bound "
            "(derived stats then cover a truncated window)")
        import weakref

        self_ref = weakref.ref(self)
        reg.gauge(
            "ledger/open_records",
            "trajectories currently in flight between birth and close",
            fn=lambda: (len(led._open)
                        if (led := self_ref()) is not None else 0.0))
        self._h_staleness = reg.histogram(
            "ledger/staleness_s",
            "FRESH frame age at consumption: unroll birth -> update "
            "retire (the staleness metric IMPACT-style replay tunes "
            "against; replayed consumptions read the _replayed series "
            "so this histogram stays honest when replay_ratio > 0)")
        self._h_staleness_replayed = reg.histogram(
            "ledger/staleness_replayed_s",
            "REPLAYED frame age at consumption: unroll birth -> replay "
            "sample (runtime/replay.py's deterministic slot mirror — "
            "the dial obs.report judges the IMPACT clip's useful range "
            "against)")
        self._g_mfu = reg.gauge(
            "ledger/mfu",
            "live model FLOPs utilization: flops_per_update x retire "
            "rate / (peak x devices); 0 until configure_mfu ran")
        self._seg_hists = {
            name: reg.histogram(
                f"ledger/stage/{name}_s",
                f"per-trajectory seconds in {SEGMENT_LABELS[name]}")
            for name, _, _ in SEGMENTS
        }
        self._seg_rate = {
            name: reg.gauge(
                f"ledger/rate/{name}_per_s",
                f"trajectories/s completing {name} (last interval)")
            for name, _, _ in SEGMENTS
        }
        self._seg_rho = {
            name: reg.gauge(
                f"ledger/rho/{name}",
                "busy seconds per wall second in this stage over the "
                "last interval (utilization for a service stage; "
                "Little's-law L for a wait stage)")
            for name, _, _ in SEGMENTS
        }
        self._seg_share = {
            name: reg.gauge(
                f"ledger/latency_share/{name}",
                "this stage's share of mean birth->retire latency "
                "(last interval with closed records)")
            for name, _, _ in SEGMENTS
        }
        for name in SERVICE_STAGES:
            self._seg_rate[name] = reg.gauge(
                f"ledger/rate/{name}_per_s",
                f"requests/s served by {SEGMENT_LABELS[name]}")
            self._seg_rho[name] = reg.gauge(
                f"ledger/rho/{name}",
                f"utilization of {SEGMENT_LABELS[name]} (busy s / s)")

    # -- record lifecycle (trajectory cadence) -----------------------------

    def open(self, actor: str, group: str,
             birth_us: Optional[int] = None,
             frames: Optional[float] = None) -> int:
        """Create a provenance record; returns its trajectory id."""
        birth = int(birth_us) if birth_us is not None else now_us()
        with self._lock:
            tid = self._next_tid
            self._next_tid += 1
            record = _Record(
                tid, actor, group,
                float(frames) if frames is not None
                else self.frames_per_trajectory, birth)
            self._open[tid] = record
            if len(self._open) > self._open_capacity:
                # Evict the oldest open record: a stamp source died
                # without closing it, and an unbounded table would turn
                # a leak into unbounded memory.  Counted + flagged so
                # the truncation is visible, never silent.
                oldest = next(iter(self._open))
                self._open.pop(oldest)
                self._c_dropped.inc()
                self._g_truncated.set(1.0)
        self._c_opened.inc()
        self._ring.append((birth, tid, "birth"))
        self._stamps_total += 1
        return tid

    def stamp(self, tid: int, stage: str,
              ts_us: Optional[int] = None) -> None:
        """Lock-free stage-boundary stamp: one record-dict store + one
        atomic ring append (bench.py bench_ledger times this)."""
        ts = int(ts_us) if ts_us is not None else now_us()
        record = self._open.get(tid)
        if record is None:
            self._c_late.inc()
            return
        record.stamps[stage] = ts
        self._ring.append((ts, tid, stage))
        self._stamps_total += 1

    def close(self, tid: int, retired: bool,
              fate: Optional[str] = None) -> None:
        """Finish a record.  ``retired=True`` stamps ``retire`` (if the
        caller didn't) and feeds the staleness histogram; False records
        the trajectory as discarded/abandoned — stamps survive, frames
        land in ``ledger/frames_discarded_total``, nothing leaks open."""
        ts = now_us()
        with self._lock:
            record = self._open.pop(tid, None)
            if record is None:
                self._c_late.inc()
                return
            record.fate = fate or ("retired" if retired else "discarded")
            if retired and "retire" not in record.stamps:
                record.stamps["retire"] = ts
            self._closed.append(record)
            if len(self._closed) > self._closed_capacity:
                self._closed.popleft()
                self._c_dropped.inc()
                self._g_truncated.set(1.0)
        if retired:
            self._c_retired.inc()
            self._h_staleness.observe(
                max(0.0, (record.stamps["retire"]
                          - record.stamps["birth"]) / 1e6))
        else:
            (self._c_abandoned if record.fate == "abandoned"
             else self._c_discarded).inc()
            self._c_frames_discarded.inc(record.frames)
        self._ring.append((ts, tid, f"close:{record.fate}"))
        self._stamps_total += 1

    # -- hand-off plumbing -------------------------------------------------

    def bind(self, key: int, tid: int) -> None:
        """Attach a record to an object crossing a queue (key =
        ``id(obj)``), so the consumer can recover the tid without any
        ordering assumption between producer threads."""
        with self._lock:
            self._bindings[key] = tid
            if len(self._bindings) > self._bind_capacity:
                self._bindings.pop(next(iter(self._bindings)))

    def lookup(self, key: int) -> Optional[int]:
        """POP the tid bound to ``key`` — one-shot by design: the
        binding is consumed so object-id reuse can never mis-attribute
        (a second lookup returns None)."""
        with self._lock:
            return self._bindings.pop(key, None)

    # Removing a binding IS the one-shot pop; the alias exists so
    # abandon paths read as intent ("drop this binding") rather than
    # as a discarded lookup.
    unbind = lookup

    def birth_us(self, tid: int) -> Optional[int]:
        """An OPEN record's birth stamp (ledger clock) — the replay
        insert path reads it to tag the slot's age source; None once
        the record closed (the caller then falls back to now)."""
        record = self._open.get(tid)
        return None if record is None else record.stamps.get("birth")

    def observe_replay_staleness(self, age_s: float) -> None:
        """One replayed consumption's frame age (runtime/replay.py's
        host-side slot mirror) — the replayed half of the staleness
        split."""
        self._h_staleness_replayed.observe(max(0.0, float(age_s)))

    def set_current(self, tid: Optional[int]) -> None:
        """Thread-local cursor: the prefetch thread sets it at queue_get
        so the transport/learner layers can stamp without plumbing tids
        through their signatures."""
        self._tls.tid = tid

    def current(self) -> Optional[int]:
        return getattr(self._tls, "tid", None)

    def stamp_current(self, stage: str) -> None:
        tid = self.current()
        if tid is not None:
            self.stamp(tid, stage)

    # -- service stages ----------------------------------------------------

    def note_service(self, name: str, n: int, busy_s: float) -> None:
        """One executed service batch: ``n`` requests served in
        ``busy_s`` seconds (the dynamic batchers feed this per batch)."""
        with self._lock:
            acc = self._service.setdefault(name, [0.0, 0.0])
            acc[0] += n
            acc[1] += busy_s

    # -- MFU ---------------------------------------------------------------

    def configure_mfu(self, flops_per_update: float,
                      peak_flops: float, num_devices: int = 1) -> None:
        """Arm the live MFU gauge.  ``flops_per_update`` comes from the
        lowered update's cost analysis (driver._configure_live_mfu);
        ``peak_flops`` from ``peak_flops_per_chip`` — bench.py's table."""
        self._mfu_flops = float(flops_per_update)
        self._mfu_peak = float(peak_flops)
        self._mfu_devices = max(1, int(num_devices))

    # -- derivation --------------------------------------------------------

    def publish(self, interval_s: Optional[float] = None
                ) -> Dict[str, object]:
        """Derive and export stage stats from the records closed since
        the last publish.  Runs on the logging thread at log-interval
        cadence.  ``interval_s`` overrides the measured wall interval
        (tests feed synthetic timelines)."""
        with self._lock:
            records = list(self._closed)
            self._closed.clear()
            service = {k: tuple(v) for k, v in self._service.items()}
            self._service.clear()
        ts = now_us()
        if interval_s is None:
            interval_s = max(1e-9, (ts - self._last_publish_us) / 1e6)
        self._last_publish_us = ts

        busy = {name: 0.0 for name, _, _ in SEGMENTS}
        counts = {name: 0 for name, _, _ in SEGMENTS}
        retired = 0
        # Hoisted segment table: publish is the ledger's only O(records)
        # pass on the logging thread, and bench_ledger amortizes its
        # per-record cost onto the update stage — keep the inner loop
        # to dict probes and one histogram observe per covered segment.
        seg_table = [(name, start, end, self._seg_hists[name].observe)
                     for name, start, end in SEGMENTS]
        for record in records:
            if record.fate == "retired":
                retired += 1
            stamps = record.stamps
            get = stamps.get
            for name, start, end, observe in seg_table:
                t0, t1 = get(start), get(end)
                if t0 is not None and t1 is not None:
                    dur = (t1 - t0) / 1e6 if t1 > t0 else 0.0
                    busy[name] += dur
                    counts[name] += 1
                    observe(dur)

        stats: Dict[str, object] = {
            "interval_s": interval_s,
            "records": len(records),
            "retired": retired,
            "segments": {},
        }
        total_busy = 0.0
        for name, _, _ in SEGMENTS:
            rate = counts[name] / interval_s
            rho = busy[name] / interval_s
            mean = busy[name] / counts[name] if counts[name] else 0.0
            self._seg_rate[name].set(rate)
            self._seg_rho[name].set(rho)
            stats["segments"][name] = {
                "rate_per_s": rate, "rho": rho, "mean_s": mean,
                "count": counts[name]}
            total_busy += busy[name]
        if records and total_busy > 0.0:
            shares = {name: busy[name] / total_busy
                      for name, _, _ in SEGMENTS}
            self._last_shares = shares
            for name, share in shares.items():
                self._seg_share[name].set(share)
        stats["latency_shares"] = dict(self._last_shares)

        for name, (n, busy_s) in service.items():
            rate_gauge = self._seg_rate.get(name)
            rho_gauge = self._seg_rho.get(name)
            if rate_gauge is not None:
                rate_gauge.set(n / interval_s)
            if rho_gauge is not None:
                rho_gauge.set(busy_s / interval_s)
            self._last_service_rho[name] = busy_s / interval_s
            stats["segments"][name] = {
                "rate_per_s": n / interval_s,
                "rho": busy_s / interval_s}

        if self._mfu_flops and self._mfu_peak:
            mfu = (self._mfu_flops * retired / interval_s
                   / (self._mfu_peak * self._mfu_devices))
            stats["mfu"] = mfu
            # The gauge keeps the last interval that RETIRED updates
            # (like the latency shares): the shutdown drain's empty
            # window must not zero the number the final snapshot and
            # the report read.
            if retired:
                self._g_mfu.set(mfu)
        self._last_stats = stats
        return stats

    def latency_shares(self) -> Dict[str, float]:
        """Last published per-segment share of mean birth→retire
        latency; empty until records have closed.  Feeds the stall
        verdict's dominant-stage attribution."""
        return dict(self._last_shares)

    def dominant_segment(self) -> Optional[Tuple[str, float]]:
        shares = self._last_shares
        if not shares:
            return None
        name = max(shares, key=shares.get)
        return name, shares[name]

    def service_pressure(self, threshold: float = 0.5
                         ) -> Optional[Tuple[str, float]]:
        """The busiest *utilization-type* service stage's ``(name, ρ)``
        when it crossed ``threshold`` in the last interval that fed it
        — the signal that an unroll-dominated verdict is really
        inference-service-dominated (the service runs INSIDE the unroll
        segment, so latency shares alone can't name it)."""
        candidates = {name: rho
                      for name, rho in self._last_service_rho.items()
                      if name in SERVICE_UTILIZATION_STAGES}
        if not candidates:
            return None
        name = max(candidates, key=candidates.get)
        rho = candidates[name]
        return (name, rho) if rho >= threshold else None

    # -- shutdown ----------------------------------------------------------

    def finalize(self) -> Optional[str]:
        """Sweep records still open (in-pipeline at shutdown) as
        ``abandoned``, run one last derivation pass, and dump the
        ledger artifact.  Idempotent; never raises on the dump path."""
        with self._lock:
            leftover = list(self._open)
        for tid in leftover:
            self.close(tid, retired=False, fate="abandoned")
        self.publish()
        try:
            return self.dump()
        except Exception:
            return None

    def snapshot(self) -> dict:
        """The ledger's current state as one JSON-able dict (also the
        dump payload).

        Tolerates live stampers: ``stamp()`` appends to the ring (and
        to records' stamp dicts) WITHOUT the lock, so a thread that
        outlived its join timeout — exactly the wedged-thread case the
        post-mortem artifact exists for — can mutate them mid-copy.
        Copies retry on the resulting RuntimeError rather than letting
        ``finalize()`` swallow it and silently skip the dump."""

        def _copy(make, fallback):
            for _ in range(5):
                try:
                    return make()
                except RuntimeError:  # mutated during iteration
                    continue
            return fallback

        with self._lock:
            open_records = _copy(
                lambda: [r.as_dict() for r in self._open.values()], [])
            ring = _copy(lambda: list(self._ring), [])
        return {
            "schema_version": _SCHEMA_VERSION,
            "process_index": self.process_index,
            "pid": os.getpid(),
            "epoch_unix_us": self._epoch_unix_us,
            "epoch_perf_us": self._epoch_perf_us,
            "frames_per_trajectory": self.frames_per_trajectory,
            # Approximate under concurrency: stamp() increments it
            # lock-free (a lost increment costs a count, never a ring
            # entry), so the truncation verdict ALSO checks ring
            # fullness — a wrapped ring is full by construction.
            "stamps_total": self._stamps_total,
            "ring_truncated": bool(
                (maxlen := self._ring.maxlen or 0)
                and (self._stamps_total > maxlen
                     or len(ring) >= maxlen)),
            "open_records": open_records,
            "last_stats": self._last_stats,
            "counters": {
                "opened": self._c_opened.value,
                "retired": self._c_retired.value,
                "discarded": self._c_discarded.value,
                "abandoned": self._c_abandoned.value,
                "frames_discarded": self._c_frames_discarded.value,
                "dropped": self._c_dropped.value,
                "late_stamps": self._c_late.value,
            },
            "ring_tail": [
                {"ts_us": ts, "tid": tid, "stage": stage}
                for ts, tid, stage in ring[-2048:]
            ],
        }

    def dump(self, path: Optional[str] = None) -> Optional[str]:
        """Atomically write the ledger artifact
        (``<logdir>/ledger.p<proc>.json``) the report CLI reads."""
        if path is None:
            if self.logdir is None:
                return None
            path = os.path.join(
                self.logdir, f"ledger.p{self.process_index}.json")
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.snapshot(), f)
        os.replace(tmp, path)
        return path


# -- module-global ledger ----------------------------------------------------
# Always live, like the flight recorder: instrumented runtime code never
# branches on "is there a ledger"; an unconfigured ledger records (and
# derives) into the global registry and simply has nowhere to dump.

_ledger = PipelineLedger()
_ledger_lock = threading.Lock()


def get_ledger() -> PipelineLedger:
    return _ledger


def configure_ledger(registry=None, frames_per_trajectory: float = 0.0,
                     logdir: Optional[str] = None,
                     process_index: int = 0, **kwargs) -> PipelineLedger:
    """Install (and return) a fresh process-global ledger for one run —
    the driver calls this at setup so one run's open records and
    derivation state can never leak into the next in-process run."""
    global _ledger
    with _ledger_lock:
        _ledger = PipelineLedger(
            registry=registry,
            frames_per_trajectory=frames_per_trajectory,
            logdir=logdir, process_index=process_index, **kwargs)
        return _ledger
