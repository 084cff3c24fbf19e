"""The host side of one fused dispatch: handing a step to the runtime.

``runtime/ingraph.py`` is the program the device runs; this is what the
host does to start it.  ``StepHandover`` wraps the jitted step and says,
of every call, what the hand-over cost the host and what the device had
left to run meanwhile:

- ``in_flight``: how many of this trainer's earlier dispatches are not
  finished when the call begins — their ``total_loss`` asked
  ``is_ready()``, no sync.  0 means the chip has nothing queued: the
  host starved it, whatever the host was doing.  Counted, the first
  dispatch apart, in ``fused/dispatch_starved_total``; the longest call
  of a log interval goes out as the gauge ``fused/enqueue_ms_max`` with
  that interval's publish.  Both whether or not the run is traced.
- with the tracer on, the call is a span, ``fused/enqueue`` (cat
  ``learner``, a child of the driver's ``learner/train_step``, whose
  self time is then whatever a harness waits for in it), with args
  ``update``, ``in_flight``, ``in_flight_after`` (the same count when
  the call returns: 0 after a long call means the device drained while
  the host sat in it) and what the call cost the calling thread
  (``obs/trace.py USAGE_ARGS``): ``majflt`` (major page faults),
  ``nivcsw`` / ``nvcsw`` (involuntary and voluntary context switches),
  ``oublock`` (blocks written).
"""

import collections
import time

from scalable_agent_tpu.obs import trace
from scalable_agent_tpu.obs.registry import get_registry

# The deepest device queue a hand-over can report.
MAX_IN_FLIGHT_SEEN = 64


class StepHandover:
    """``step(state, carry, counter)``, instrumented as the module
    says.  ``lower`` is the jitted step's own: callers lower it for
    the MFU gauge and the scope table, and a harness that wraps this
    object copies the attribute."""

    def __init__(self, step):
        self.step = step
        self.lower = step.lower
        # ``total_loss`` of the newest dispatches, oldest first, until
        # each is seen ready.  Bounded: a deeper queue reads as this.
        self._unready = collections.deque(maxlen=MAX_IN_FLIGHT_SEEN)
        self._dispatches = 0
        self._enqueue_ms_max = 0.0
        registry = get_registry()
        self._starved = registry.counter(
            "fused/dispatch_starved_total",
            "fused dispatches that found nothing of this trainer's "
            "still queued on the device (the first apart)")
        self._enqueue_gauge = registry.gauge(
            "fused/enqueue_ms_max",
            "longest hand-over of a fused step to the runtime in the "
            "log interval that ended with the last publish, ms")

    def in_flight(self) -> int:
        """Dispatches not yet finished.  The device runs them in order,
        so the ready ones are at the old end."""
        unready = self._unready
        while unready and unready[0].is_ready():
            unready.popleft()
        return len(unready)

    def __call__(self, state, carry, counter):
        in_flight = self.in_flight()
        if in_flight == 0 and self._dispatches:
            self._starved.inc()
        self._dispatches += 1
        tracer = trace.get_tracer()
        t0 = time.perf_counter()
        if tracer.enabled:
            args = {"update": int(counter), "in_flight": in_flight}
            before = trace.thread_usage()
            with tracer.span("fused/enqueue", cat="learner", args=args):
                out = self.step(state, carry, counter)
                args["in_flight_after"] = self.in_flight()
                args.update(trace.usage_since(before))
        else:
            out = self.step(state, carry, counter)
        self._enqueue_ms_max = max(
            self._enqueue_ms_max, (time.perf_counter() - t0) * 1e3)
        self._unready.append(out[2]["total_loss"])
        return out

    def publish(self):
        """The interval's longest hand-over into the gauge, and a
        fresh start for the next interval's."""
        self._enqueue_gauge.set(self._enqueue_ms_max)
        self._enqueue_ms_max = 0.0
