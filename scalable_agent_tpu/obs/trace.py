"""Host-side span tracing with Chrome-trace-event export.

The pipeline's stages live on host threads (actor unrolls, batcher
consumers, the prefetch stage, the learner loop) where
``jax.profiler``'s device trace can't see the hand-offs.  A ``Tracer``
records nested spans per (process, thread) and writes them in the
Chrome trace-event format — one JSON event per line — which Perfetto
(https://ui.perfetto.dev) and chrome://tracing load directly.

While a ``--profile_dir`` device capture is recording, the driver flips
``set_annotate(True)`` so every span also enters a
``jax.profiler.TraceAnnotation`` of the same name and the profiler
timeline shows the host spans aligned with the XLA ops they dispatched.
(Annotations are invisible outside a capture and cost ~100x the span
itself, so they stay off otherwise.)

Cost discipline: a disabled tracer's ``span()`` returns a shared no-op
context manager — one call + two no-op dunders, no allocation — so
instrumented hot loops (per-step actor code) stay well under the <2%
overhead budget whether or not a trace is being captured
(bench.py bench_obs measures this every round).

File format: the first line is ``[`` and every event line ends with a
comma — the Trace Event spec explicitly allows the unclosed array, which
is what makes the file appendable/crash-safe AND loadable by Perfetto.
``load_trace_events`` parses it back for tests/tools.

One timeline per run: the driver starts the tracer at ``driver.main``'s
first line, before the process index is known (``deferred=True``: events
are held in memory), and ``attach`` opens the file once it is.  Every
span ("ph": "X") carries three fields beside the Chrome ones: ``sid``
(its id), ``parent`` (the ``sid`` of the span open on the same thread
when it started; absent at the top) and ``self`` (microseconds: its
duration less the part its children cover).  ``add_wall_span`` places a
span that something else timed on the wall clock (JAX's compile events)
on the span clock through the tracer's ``trace_epoch`` pair.
``last_trace_path()`` is how the same process finds a finished run's
spans afterwards.

What can freeze the host is on the same timeline (``HostWatch``, started
and stopped with a run's tracer): every full garbage collection is a
span ``gc/collect``, and a thread that sleeps 10 ms at a time records
``host/late_wakeup`` whenever it wakes 50 ms or more late, which is when
nothing in this process got the GIL, or the process did not run.
"""

import gc
import itertools
import json
import os
import resource
import threading
import time
from typing import Dict, Iterator, List, Optional, Tuple

__all__ = [
    "HostWatch",
    "Tracer",
    "configure_tracer",
    "get_tracer",
    "last_trace_path",
    "load_trace_events",
    "span",
    "start_host_watch",
    "stop_host_watch",
    "thread_usage",
    "usage_since",
]


class _NullSpan:
    """Shared no-op context manager for disabled tracers."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc_info):
        return False

    def close(self, end_ns=None):
        pass


_NULL_SPAN = _NullSpan()

# flightrec imports only stdlib, so this direct submodule import is
# cycle-free even though both live under the obs package.
from scalable_agent_tpu.obs.flightrec import (  # noqa: E402
    get_flight_recorder as _flight_recorder,
)


def _cover(covered: List[Tuple[int, int]], start: int, end: int) -> int:
    """Add the child interval [start, end] to ``covered``, a span's
    disjoint child intervals, and return how much of it earlier children
    already covered.  Children are reported when they END, so ends only
    grow and only the list's tail can touch the new interval: a span
    reported after the spans nested in it (a compile event arrives after
    the inner jits' events) swallows them here, and the return value is
    what its own self time leaves out."""
    inside, lo = 0, start
    while covered and covered[-1][1] > start:
        s, e = covered.pop()
        inside += max(0, min(e, end) - max(s, start))
        lo, end = min(lo, s), max(end, e)
    covered.append((lo, end))
    return inside


def _cover_child(parent, start: int, end: int) -> int:
    inside = _cover(parent._covered, start, end)
    # A thread's root never closes: keep only the tail a late span can
    # still touch.
    if parent._sid is None and len(parent._covered) > 256:
        del parent._covered[:128]
    return inside


class _Root:
    """The bottom of a thread's span stack: no span is open."""

    __slots__ = ("_sid", "_covered")

    def __init__(self):
        self._sid = None
        self._covered: List[Tuple[int, int]] = []


class _Span:
    __slots__ = ("_tracer", "_name", "_cat", "_args", "_start_us",
                 "_annotation", "_sid", "_parent", "_covered", "_stack")

    def __init__(self, tracer: "Tracer", name: str, cat: str, args,
                 start_ns: Optional[int] = None):
        self._tracer = tracer
        self._name = name
        self._cat = cat
        self._args = args
        self._annotation = None
        self._start_us = None if start_ns is None else start_ns // 1000

    def __enter__(self):
        tracer = self._tracer
        if tracer._annotate:
            try:
                import jax

                self._annotation = jax.profiler.TraceAnnotation(self._name)
                self._annotation.__enter__()
            except Exception:  # profiler unavailable: spans still record
                tracer._annotate = False
        self._stack = stack = tracer._stack()
        self._sid = next(tracer._ids)
        self._parent = stack[-1]
        self._covered = []
        stack.append(self)
        if self._start_us is None:
            self._start_us = time.perf_counter_ns() // 1000
        return self

    def __exit__(self, *exc_info):
        self.close()
        return False

    def close(self, end_ns: Optional[int] = None):
        """End the span, at ``end_ns`` (a ``perf_counter_ns`` reading
        the caller took) where one is given: the span that starts on
        the same reading then begins exactly where this one ends."""
        end_us = (time.perf_counter_ns() if end_ns is None
                  else end_ns) // 1000
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
        stack = self._stack
        if stack[-1] is self:
            stack.pop()
        elif self in stack:  # closed out of order: keep the rest intact
            stack.remove(self)
        dur = end_us - self._start_us
        _cover_child(self._parent, self._start_us, end_us)
        self._tracer._complete(
            self._name, self._cat, self._start_us, dur, self._args,
            self._sid, self._parent._sid,
            dur - sum(e - s for s, e in self._covered))


class Tracer:
    """Collects spans and writes Chrome trace events to ``path``.

    ``span(name)`` spans nest naturally: events on the same (pid, tid)
    track whose [ts, ts+dur] intervals contain each other render as a
    stack in Perfetto; readers that want the tree without sorting use
    the ``sid``/``parent`` fields, kept from a per-thread stack.

    ``deferred=True`` with no ``path`` records into memory until
    ``attach(path, process_index)`` opens the file (a run's first spans
    exist before ``jax.process_index()`` may be called).
    """

    def __init__(self, path: Optional[str] = None,
                 process_name: str = "scalable_agent_tpu",
                 annotate: bool = False,
                 flush_every_events: int = 8192,
                 max_events: int = 2_000_000,
                 process_index: int = 0,
                 deferred: bool = False):
        self.path = None
        self.enabled = path is not None or deferred
        self.process_index = process_index
        self._process_name = process_name
        self._deferred = deferred and path is None
        self._annotate = annotate and self.enabled
        self._flush_every = flush_every_events
        # Hard event budget (~100 bytes/event -> ~200 MB at the
        # default): per-env-step spans on a multi-hour run would
        # otherwise grow the file past what Perfetto loads (and fill the
        # logdir disk).  At exhaustion the tracer writes one truncation
        # marker and disables itself — the head of the run stays
        # loadable.
        self._remaining_events = max_events
        # Re-entrant: a full collection that starts while this thread
        # holds the lock ends in ``gc/collect``'s own ``_push``.
        self._lock = threading.RLock()
        self._events: List[str] = []  # preformatted JSON event lines
        self._file = None
        self._named_tids: Dict[int, str] = {}
        self._pid = os.getpid()
        self._ids = itertools.count(1)
        self._local = threading.local()
        # Per-process clock epoch: a back-to-back (unix wall time,
        # monotonic span clock) pair.  Event timestamps are
        # process-local perf_counter microseconds; the aggregator
        # (obs/aggregate.py) uses this record to shift every process's
        # events onto one shared wall-clock timeline, and add_wall_span
        # to place wall-clock-stamped spans on the span clock.
        self._epoch_perf_us = time.perf_counter_ns() // 1000
        self._epoch_unix_us = int(time.time() * 1e6)
        if path is not None:
            self.attach(path, process_index)

    def attach(self, path: str, process_index: int = 0):
        """Open the trace file and write what was held in memory."""
        global _last_trace_path
        parent = os.path.dirname(os.path.abspath(path))
        os.makedirs(parent, exist_ok=True)
        with self._lock:
            self.path = path
            self.process_index = process_index
            self._file = open(path, "w")
            self._file.write("[\n")
            self._deferred = False
        _last_trace_path = path
        self._meta("process_name", {"name": self._process_name})
        self._meta("process_sort_index", {"sort_index": process_index})
        self._push(json.dumps({
            "name": "trace_epoch", "ph": "i", "s": "g", "cat": "meta",
            "ts": self._epoch_perf_us, "pid": self._pid, "tid": 0,
            "args": {"unix_time_us": self._epoch_unix_us,
                     "perf_time_us": self._epoch_perf_us,
                     "process_index": process_index}}))
        self.flush()

    def set_annotate(self, flag: bool):
        """Toggle ``jax.profiler.TraceAnnotation`` wrapping.  An
        annotation is only visible while a jax profiler capture is
        recording, and costs ~1-2 orders of magnitude more than the span
        itself — so the driver flips this on exactly for the
        ``--profile_dir`` capture window and off again after."""
        self._annotate = bool(flag) and self.enabled

    # -- recording ---------------------------------------------------------

    def span(self, name: str, cat: str = "pipeline",
             args: Optional[dict] = None, start_ns: Optional[int] = None):
        """Context manager timing one nested span.  ``args`` is written
        when the span ends, so what is put into the dict while it is
        open is kept; ``start_ns`` is a ``perf_counter_ns`` reading to
        start it on in place of its own (``_Span.close`` takes the
        other end)."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, cat, args, start_ns)

    def add_span(self, name: str, cat: str, start_us: int, end_us: int,
                 args: Optional[dict] = None):
        """Record a span timed elsewhere, [start_us, end_us] on the span
        clock, as a child of the span open on the calling thread.  Call
        it when the span ends (``_cover`` relies on that order)."""
        if not self.enabled:
            return
        parent = self._stack()[-1]
        inside = _cover_child(parent, start_us, end_us)
        dur = end_us - start_us
        self._complete(name, cat, start_us, dur, args,
                       next(self._ids), parent._sid, dur - inside)

    def add_wall_span(self, name: str, cat: str, start_unix_s: float,
                      end_unix_s: float, args: Optional[dict] = None):
        """``add_span`` for a span stamped with ``time.time()`` (JAX's
        compile events): moved onto the span clock through this
        tracer's ``trace_epoch`` pair, and never past now."""
        if not self.enabled:
            return
        shift = self._epoch_perf_us - self._epoch_unix_us
        end_us = min(int(end_unix_s * 1e6) + shift,
                     time.perf_counter_ns() // 1000)
        dur = max(0, int((end_unix_s - start_unix_s) * 1e6))
        self.add_span(name, cat, end_us - dur, end_us, args)

    def _stack(self) -> list:
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = stack = [_Root()]
            return stack

    def instant(self, name: str, cat: str = "pipeline",
                args: Optional[dict] = None):
        """A zero-duration marker (stall reports, weight publications)."""
        if not self.enabled:
            return
        self._push(json.dumps({
            "name": name, "ph": "i", "cat": cat, "s": "t",
            "ts": time.perf_counter_ns() // 1000,
            "pid": self._pid, "tid": self._tid(), "args": args or {}}))

    def counter(self, name: str, values: Dict[str, float]):
        """A Chrome counter-track sample (queue depths over time)."""
        if not self.enabled:
            return
        self._push(json.dumps({
            "name": name, "ph": "C",
            "ts": time.perf_counter_ns() // 1000,
            "pid": self._pid, "tid": 0,
            "args": {k: float(v) for k, v in values.items()}}))

    def _complete(self, name, cat, ts, dur, args, sid, parent, self_us):
        # Completed spans also enter the flight recorder's ring
        # (obs/flightrec.py) — on a crash the unflushed trace tail is
        # lost, but the ring's copy survives into flightrec.<pid>.json.
        _flight_recorder().record_span(name, cat, ts, dur)
        # Hot path: format the event line directly — ~5x cheaper than
        # dict + json.dumps, and span names/cats are code literals (the
        # rare quote/backslash falls back to the robust path).
        if '"' in name or "\\" in name or '"' in cat or "\\" in cat:
            event = {"name": name, "ph": "X", "cat": cat, "ts": ts,
                     "dur": dur, "pid": self._pid, "tid": self._tid(),
                     "sid": sid, "self": self_us}
            if parent is not None:
                event["parent"] = parent
            if args:
                event["args"] = args
            self._push(json.dumps(event))
            return
        suffix = (", \"args\": %s}" % json.dumps(args)) if args else "}"
        if parent is not None:
            suffix = ', "parent": %d%s' % (parent, suffix)
        self._push(
            '{"name": "%s", "ph": "X", "cat": "%s", "ts": %d, '
            '"dur": %d, "pid": %d, "tid": %d, "sid": %d, "self": %d%s'
            % (name, cat, ts, dur, self._pid, self._tid(), sid, self_us,
               suffix))

    def _tid(self) -> int:
        tid = threading.get_ident()
        if tid not in self._named_tids:
            name = threading.current_thread().name
            self._named_tids[tid] = name
            self._meta("thread_name", {"name": name}, tid=tid)
        return tid

    def _meta(self, name: str, args: dict, tid: int = 0):
        self._push(json.dumps({"name": name, "ph": "M", "pid": self._pid,
                               "tid": tid, "args": args}))

    def _push(self, line: str):
        with self._lock:
            if self._remaining_events <= 0:
                return
            self._remaining_events -= 1
            self._events.append(line)
            if self._remaining_events == 0:
                self._events.append(json.dumps({
                    "name": "trace_truncated", "ph": "i", "s": "g",
                    "cat": "pipeline",
                    "ts": time.perf_counter_ns() // 1000,
                    "pid": self._pid, "tid": 0,
                    "args": {"reason": "max_events budget exhausted"}}))
                # Spans become no-ops from here on; close() still
                # flushes this tail.
                self.enabled = False
                self._annotate = False
            if len(self._events) >= self._flush_every:
                self._flush_locked()

    # -- lifecycle ---------------------------------------------------------

    def _flush_locked(self):
        if self._deferred:
            return  # held until attach() opens the file
        if self._file is None or not self._events:
            self._events.clear()
            return
        self._file.write(",\n".join(self._events) + ",\n")
        self._events.clear()
        self._file.flush()

    def flush(self):
        with self._lock:
            self._flush_locked()

    def close(self):
        with self._lock:
            self._deferred = False  # never attached: nothing to keep
            self._flush_locked()
            if self._file is not None:
                self._file.close()
                self._file = None
            self.enabled = False

    def __enter__(self):
        return self

    def __exit__(self, *exc_info):
        self.close()


# -- module-global tracer ---------------------------------------------------
# Instrumented runtime modules (actor, batcher, learner, driver) call
# ``obs.span(...)`` against this singleton; the driver swaps in a real
# file-backed tracer when --trace is set and restores the null one after.

_tracer = Tracer(path=None)
_tracer_lock = threading.Lock()
_last_trace_path: Optional[str] = None


def last_trace_path() -> Optional[str]:
    """The file of the newest file-backed trace this process opened —
    after ``driver.main`` returns, the finished run's timeline
    (``load_trace_events`` reads it).  THE way for code in the same
    process (the benchmark's readers) to find a run's spans; the
    run's ``op_scopes.p<proc>.<pid>.json`` (obs/kernels.py) lies beside
    it under the same suffix.  None before any traced run."""
    return _last_trace_path


def get_tracer() -> Tracer:
    return _tracer


def configure_tracer(path: Optional[str], **kwargs) -> Tracer:
    """Install (and return) the process-global tracer.  ``path=None``
    restores the disabled tracer (or, with ``deferred=True``, starts
    one that records into memory until ``attach``); a previous
    file-backed tracer is closed first so its tail is flushed."""
    global _tracer
    with _tracer_lock:
        old, _tracer = _tracer, Tracer(path=path, **kwargs)
        # Close on the FILE, not on `enabled`: a tracer that exhausted
        # its event budget has enabled=False but still holds buffered
        # events (incl. the truncation marker) and the open handle.
        if old._file is not None:
            old.close()
        return _tracer


def span(name: str, cat: str = "pipeline", args: Optional[dict] = None):
    """``with obs.span('learner/update'):`` against the global tracer."""
    return _tracer.span(name, cat=cat, args=args)


# -- what a span cost its thread ----------------------------------------------
# The args a span takes when the question is what its thread WAITED for:
# major page faults (paging), involuntary context switches
# (descheduled), voluntary ones (blocked), blocks written (throttled on
# dirty pages).

USAGE_ARGS = ("majflt", "nivcsw", "nvcsw", "oublock")


def thread_usage() -> Tuple[int, int, int, int]:
    """The calling thread's ``USAGE_ARGS`` counts so far."""
    usage = resource.getrusage(resource.RUSAGE_THREAD)
    return (usage.ru_majflt, usage.ru_nivcsw, usage.ru_nvcsw,
            usage.ru_oublock)


def usage_since(before: Tuple[int, int, int, int]) -> Dict[str, int]:
    """``USAGE_ARGS`` -> what the calling thread has added to each
    since ``before`` (a ``thread_usage()`` reading of its own)."""
    return dict(zip(USAGE_ARGS, (
        now - then for then, now in zip(before, thread_usage()))))


# -- what can freeze the host ------------------------------------------------

class HostWatch:
    """A garbage collection and a frozen host, as spans on ``tracer``.

    ``gc/collect`` (cat ``host``): a ``gc.callbacks`` hook opens a real
    span when a generation-2 collection starts and closes it when the
    collection stops (args ``collected``, ``uncollectable``), on the
    thread that collects, under whatever span that thread has open; the
    younger generations only count (``gc/collections_total``,
    ``gc/pause_s_total``: every generation's).

    ``host/late_wakeup`` (cat ``host``): the daemon thread
    ``host-pulse`` sleeps ``PULSE_NS`` at a time and records the
    interval it overslept when that is ``LATE_NS`` or more (args
    ``late_ms``): no thread of this process got the GIL for that long,
    or the process did not run.  The span is PLACED when the thread
    wakes (``add_span``), never held open: it belongs to no thread that
    works, and a reader that asks which span was open in an idle gap
    must not find it there.

    A stall reads three ways: ``gc/collect`` covers it (the collector);
    ``host/late_wakeup`` without ``gc/collect`` (another thread or
    native code kept the GIL, or the process was stopped: the thread
    with a span open across it is the suspect); neither, and the span
    that is long ran with the GIL free (its own args say what it waited
    for).  ``start`` installs both, ``stop`` takes both out again."""

    PULSE_NS = 10_000_000
    LATE_NS = 50_000_000

    def __init__(self, tracer: Tracer, registry):
        self._tracer = tracer
        # Looked up here and not in the hook: making an instrument
        # takes the registry's lock and allocates, and a collection
        # may begin under that lock.
        self._collections = registry.counter(
            "gc/collections_total",
            "garbage collections while the tracer was on")
        self._pause_s = registry.counter(
            "gc/pause_s_total",
            "seconds inside garbage collections while the tracer was on")
        self._gc_t0_ns = 0
        self._gc_span = None
        self._gc_args: Optional[dict] = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> "HostWatch":
        gc.callbacks.append(self._on_gc)
        self._thread = threading.Thread(
            target=self._pulse, name="host-pulse", daemon=True)
        self._thread.start()
        return self

    def stop(self):
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        self._stop.set()
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def _on_gc(self, phase: str, info: dict):
        if phase == "start":
            self._gc_t0_ns = time.perf_counter_ns()
            if info.get("generation") == 2:
                self._gc_args = {}
                self._gc_span = self._tracer.span(
                    "gc/collect", cat="host", args=self._gc_args,
                    start_ns=self._gc_t0_ns)
                self._gc_span.__enter__()
            return
        now = time.perf_counter_ns()
        self._collections.inc()
        self._pause_s.inc(max(0, now - self._gc_t0_ns) * 1e-9)
        if self._gc_span is not None:
            self._gc_args["collected"] = info.get("collected", 0)
            self._gc_args["uncollectable"] = info.get("uncollectable", 0)
            span, self._gc_span = self._gc_span, None
            span.close(now)

    @classmethod
    def late_interval(cls, due_ns: int, woke_ns: int
                      ) -> Optional[Tuple[int, int, dict]]:
        """The pulse's rule: due at ``due_ns``, awake at ``woke_ns`` —
        ``(start_us, end_us, args)`` of the span to record, or None
        when the wake-up was on time."""
        late_ns = woke_ns - due_ns
        if late_ns < cls.LATE_NS:
            return None
        return (due_ns // 1000, woke_ns // 1000,
                {"late_ms": round(late_ns * 1e-6, 3)})

    def _pulse(self):
        period_s = self.PULSE_NS * 1e-9
        due_ns = time.perf_counter_ns() + self.PULSE_NS
        while not self._stop.wait(period_s):
            woke_ns = time.perf_counter_ns()
            late = self.late_interval(due_ns, woke_ns)
            if late is not None:
                self._tracer.add_span("host/late_wakeup", "host", *late)
            due_ns = woke_ns + self.PULSE_NS


_host_watch: Optional[HostWatch] = None


def start_host_watch(registry) -> HostWatch:
    """Watch the host on the process tracer, which the caller has just
    made (``driver._open_timeline``); a watch left from an earlier run
    is stopped first."""
    global _host_watch
    stop_host_watch()
    _host_watch = HostWatch(_tracer, registry).start()
    return _host_watch


def stop_host_watch():
    global _host_watch
    watch, _host_watch = _host_watch, None
    if watch is not None:
        watch.stop()


def load_trace_events(path: str) -> Iterator[dict]:
    """Parse a trace file written by ``Tracer`` (tests and tooling).
    Tolerates the unclosed-array format and a truncated last line."""
    with open(path) as f:
        for line in f:
            line = line.strip().rstrip(",")
            if not line or line in ("[", "]"):
                continue
            try:
                yield json.loads(line)
            except json.JSONDecodeError:
                continue  # torn tail of a crashed run
