"""Typed metrics registry: counters, gauges, streaming histograms.

One registry instance holds every instrument the runtime exposes —
queue-depth/occupancy gauges on the batchers, actor-vs-learner frame
counters, stage-latency histograms, JAX recompilation hooks, device
memory — and renders to any exporter (obs/exporters.py: Prometheus text
exposition, the JSONL/TensorBoard writer) from one ``snapshot()``.

Instruments are cheap and thread-safe:

- ``Counter.inc`` / ``Gauge.set`` take one small lock (instrumented code
  calls them per-unroll/per-update, not per env step).
- ``Gauge`` can instead be backed by a callback (``registry.gauge(name,
  fn=...)``) — queue depths are then sampled at snapshot time and cost
  the hot path NOTHING.
- ``Histogram`` keeps exact ``count``/``sum`` plus a bounded ring of
  recent observations; p50/p95/p99 are computed over that window with
  numpy at snapshot time (tests assert agreement with ``np.percentile``).
"""

import threading
import time
import weakref
from collections import deque
from typing import Callable, Dict, List, Optional, Union

import numpy as np

from scalable_agent_tpu.obs.trace import get_tracer

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "get_registry",
]


class Counter:
    """Monotonically increasing float counter."""

    kind = "counter"

    def __init__(self, name: str, help: str = ""):
        self.name = name
        self.help = help
        self._lock = threading.Lock()
        self._value = 0.0

    def inc(self, amount: float = 1.0):
        if amount < 0:
            raise ValueError(f"counter {self.name} cannot decrease")
        with self._lock:
            self._value += amount

    @property
    def value(self) -> float:
        with self._lock:
            return self._value


class Gauge:
    """Point-in-time value: ``set()`` it, or back it with a callback so
    it is sampled only when a snapshot is taken."""

    kind = "gauge"

    def __init__(self, name: str, help: str = "",
                 fn: Optional[Callable[[], float]] = None):
        self.name = name
        self.help = help
        self._lock = threading.Lock()
        self._value = 0.0
        self._fn = fn

    def set(self, value: float):
        with self._lock:
            self._value = float(value)
            self._fn = None

    def set_fn(self, fn: Callable[[], float]):
        """Rebind the sampling callback (a new pool/batcher instance
        re-registering the same gauge name takes ownership)."""
        with self._lock:
            self._fn = fn

    @property
    def value(self) -> float:
        with self._lock:
            fn = self._fn
            if fn is None:
                return self._value
        try:
            return float(fn())
        except Exception:
            return float("nan")  # a dying queue must not kill a snapshot


class Histogram:
    """Streaming latency histogram: exact count/sum, windowed quantiles.

    The quantile window holds the most recent ``window`` observations;
    for pipeline stage latencies this tracks current behaviour (what the
    stall attributor needs) rather than run-lifetime history.
    """

    kind = "histogram"
    QUANTILES = (0.5, 0.95, 0.99)

    def __init__(self, name: str, help: str = "", window: int = 2048):
        self.name = name
        self.help = help
        self._lock = threading.Lock()
        self._samples = deque(maxlen=window)
        self._count = 0
        self._sum = 0.0

    def observe(self, value: float):
        value = float(value)
        with self._lock:
            self._samples.append(value)
            self._count += 1
            self._sum += value

    def time(self):
        """``with hist.time():`` observes the elapsed seconds."""
        return _HistTimer(self)

    @property
    def count(self) -> int:
        with self._lock:
            return self._count

    @property
    def sum(self) -> float:
        with self._lock:
            return self._sum

    def quantiles(self) -> Dict[float, float]:
        with self._lock:
            samples = list(self._samples)
        if not samples:
            return {q: 0.0 for q in self.QUANTILES}
        values = np.percentile(
            np.asarray(samples, np.float64),
            [q * 100.0 for q in self.QUANTILES])
        return dict(zip(self.QUANTILES, (float(v) for v in values)))


class _HistTimer:
    __slots__ = ("_hist", "_t0")

    def __init__(self, hist: Histogram):
        self._hist = hist

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc_info):
        self._hist.observe(time.perf_counter() - self._t0)
        return False


Instrument = Union[Counter, Gauge, Histogram]


class MetricsRegistry:
    """Name -> instrument, idempotent registration.

    ``counter``/``gauge``/``histogram`` return the existing instrument
    when the name is already registered (so modules can look up shared
    instruments without import-order coupling); asking for a different
    KIND under a taken name is a bug and raises.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._instruments: Dict[str, Instrument] = {}

    def _get_or_create(self, cls, name: str, help: str, **kwargs):
        with self._lock:
            existing = self._instruments.get(name)
            if existing is not None:
                if not isinstance(existing, cls):
                    raise ValueError(
                        f"metric {name!r} already registered as "
                        f"{existing.kind}, requested {cls.kind}")
                return existing
            instrument = cls(name, help, **kwargs)
            self._instruments[name] = instrument
            return instrument

    def counter(self, name: str, help: str = "") -> Counter:
        return self._get_or_create(Counter, name, help)

    def gauge(self, name: str, help: str = "",
              fn: Optional[Callable[[], float]] = None) -> Gauge:
        gauge = self._get_or_create(Gauge, name, help)
        if fn is not None:
            gauge.set_fn(fn)
        return gauge

    def histogram(self, name: str, help: str = "",
                  window: int = 2048) -> Histogram:
        return self._get_or_create(Histogram, name, help, window=window)

    def instruments(self) -> List[Instrument]:
        with self._lock:
            return [self._instruments[k]
                    for k in sorted(self._instruments)]

    def snapshot(self) -> Dict[str, float]:
        """Flat name -> value dict: counters and gauges verbatim;
        histograms expand to ``<name>/p50|p95|p99|count|sum|mean``."""
        out: Dict[str, float] = {}
        for instrument in self.instruments():
            if isinstance(instrument, Histogram):
                count, total = instrument.count, instrument.sum
                for q, v in instrument.quantiles().items():
                    out[f"{instrument.name}/p{int(q * 100)}"] = v
                out[f"{instrument.name}/count"] = float(count)
                out[f"{instrument.name}/sum"] = total
                out[f"{instrument.name}/mean"] = (
                    total / count if count else 0.0)
            else:
                out[instrument.name] = instrument.value
        return out

    # -- runtime hooks -----------------------------------------------------

    def install_jax_hooks(self) -> "MetricsRegistry":
        """Register JAX compile counters and device-memory gauges on
        this registry.  Idempotent per registry; safe when the
        monitoring API or memory_stats are unavailable (CPU backends
        return None there — the gauges then read 0).

        ``jax/compile_count`` / ``jax/compile_time_s`` count BACKEND
        compiles (one per program XLA compiled or loaded from the
        persistent cache; tracing and lowering are spans, not
        compiles), ``jax/compile_cache_hits_total`` /
        ``jax/compile_cache_misses_total`` tell the two apart."""
        if getattr(self, "_jax_hooks_installed", False):
            return self
        self._jax_hooks_installed = True
        self.counter("jax/compile_count",
                     "XLA backend compiles (or cache loads) observed")
        self.counter("jax/compile_time_s",
                     "cumulative XLA backend compile seconds")
        self.counter("jax/compile_cache_hits_total",
                     "programs loaded from the persistent compile cache")
        self.counter("jax/compile_cache_misses_total",
                     "programs the persistent compile cache did not hold")
        _hook_jax_monitoring(self)

        def _memory_bytes() -> float:
            try:
                import jax

                stats = jax.local_devices()[0].memory_stats()
                return float((stats or {}).get("bytes_in_use", 0.0))
            except Exception:
                return 0.0

        self.gauge("device/memory_bytes_in_use",
                   "live HBM bytes on local device 0", fn=_memory_bytes)
        return self


# -- JAX's monitoring events --------------------------------------------------
# ONE pair of listeners per process, however many registries hook in:
# JAX offers no way to take a listener back, and each of the three
# compile events must become one span, not one per registry.

_COMPILE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile/trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile/lower",
    "/jax/core/compile/backend_compile_duration": "compile/backend",
}
_CACHE_COUNTERS = {
    "/jax/compilation_cache/cache_hits": "jax/compile_cache_hits_total",
    "/jax/compilation_cache/cache_misses": "jax/compile_cache_misses_total",
}
_jax_hooked: "weakref.WeakSet[MetricsRegistry]" = weakref.WeakSet()
_jax_hook_lock = threading.Lock()
_jax_listening = False


def _on_compile_span(event: str, start_time: float, end_time: float,
                     **kwargs):
    """JAX's time-span listener: each compile event is a span on the
    process tracer's clock (``args.fun_name`` names the program), under
    whatever span the compiling thread has open; backend compiles also
    count."""
    name = _COMPILE_SPANS.get(event)
    if name is None:
        return
    if name == "compile/backend":
        for registry in list(_jax_hooked):
            registry.counter("jax/compile_count").inc()
            registry.counter("jax/compile_time_s").inc(
                max(0.0, end_time - start_time))
    tracer = get_tracer()
    if tracer.enabled:
        tracer.add_wall_span(
            name, "compile", start_time, end_time,
            {"fun_name": str(kwargs.get("fun_name", ""))})


def _on_cache_event(event: str, **kwargs):
    name = _CACHE_COUNTERS.get(event)
    if name is not None:
        for registry in list(_jax_hooked):
            registry.counter(name).inc()


def _hook_jax_monitoring(registry: MetricsRegistry):
    global _jax_listening
    with _jax_hook_lock:
        _jax_hooked.add(registry)
        if _jax_listening:
            return
        try:
            import jax.monitoring

            jax.monitoring.register_event_time_span_listener(
                _on_compile_span)
            jax.monitoring.register_event_listener(_on_cache_event)
        except Exception:  # no monitoring API: the counters read 0
            return
        _jax_listening = True


# -- module-global registry --------------------------------------------------
# The runtime instruments itself against this singleton so the driver,
# batchers, and actor pool agree on one namespace without plumbing a
# registry through every constructor (constructors still accept an
# explicit registry for tests).

_registry = MetricsRegistry()


def get_registry() -> MetricsRegistry:
    return _registry
