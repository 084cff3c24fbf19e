"""On-device telemetry: obs instruments that live INSIDE jitted programs.

The host obs plane (obs/registry.py) instruments thread boundaries —
queue hand-offs, span enters, histogram observes — but the fused
on-device flywheel (runtime/ingraph.py, ROADMAP item 1) has no thread
boundaries left to stamp: an entire env-step → inference → pack →
update megastep is one device program, and anything the host wants to
know must either ride a per-update fetch (a host sync the architecture
exists to avoid) or go dark.  The non-finite skip counters
(runtime/learner.py TrainState.nonfinite_skips) already proved the
third way: carry the instrument ON the device, accumulate it inside
the jitted program, and fetch it only when the driver was going to
sync anyway (log-interval metrics).  This module generalizes that
pattern into a declarative instrument set:

- ``DeviceTelemetry`` is a SPEC: declare counters, gauges, and
  bucketed histograms once; ``init()`` materializes them as a flat
  pytree of f32 buffers (one distinct buffer per leaf, so the pytree
  is donation-safe).
- The in-graph ops — ``inc``/``set``/``observe`` — are pure functions
  ``(tel, name, value) -> tel`` usable under ``jit``/``scan``/``vmap``.
  A histogram observe is a searchsorted + one-hot matmul over the
  declared bucket edges: O(N·K) elementwise work fused into the
  surrounding program, no host interaction of any kind.
- The telemetry pytree rides the jitted step as a DONATED argument
  (the caller rebinds the returned buffers), so accumulation is
  in-place on device and costs no extra live HBM copies.
- ``fetch()`` is the ONE host sync: a single ``device_get`` of a few
  hundred bytes at log-interval cadence.  ``TelemetryPublisher`` folds
  the fetched snapshot into the ordinary metrics registry under
  ``devtel/...`` names, so device-resident instruments publish through
  the same prom/report/aggregate path as every host instrument
  (fleet folds: obs/aggregate.py — devtel counters SUM, devtel gauges
  MAX).

Precision: leaves are f32 scalars/vectors like the non-finite
counters — exact for counts to 2^24, which at one update per count is
weeks of wall clock; histogram bucket counts share the bound.

Cost discipline (bench.py ``bench_devtel``, <1% of the update stage):
the in-graph ops add a handful of scalar adds + one [N, K] one-hot
reduction per update — measured as sub-microsecond against the
multi-millisecond update — and the fetch/publish pair runs at log
cadence, never per update.  tests/test_device_telemetry.py proves the
stronger claim directly: a telemetry-bearing update issues ZERO
device→host materializations and ZERO host→device transfers outside
the log-interval fetch.
"""

from typing import Dict, Iterable, List, Sequence, Tuple, Union

import numpy as np

__all__ = [
    "DeviceTelemetry",
    "TelemetryPublisher",
    "fetch_merged",
    "merge_init",
]

# jax is imported lazily inside the device-side methods: this module
# rides the jax-free ``obs`` package init (the report/aggregate CLIs
# must keep running on a laptop against rsync'd artifacts), and only
# the in-graph ops and buffer lifecycle ever touch a device.

# Pytree key prefixes per instrument kind.  Keys are globally unique
# (namespace included), so telemetry dicts from several specs merge by
# plain dict union (merge_init) and each spec's ops touch only its own
# leaves while passing every other key through untouched.
_COUNTER = "c:"
_GAUGE = "g:"
_HIST = "h:"


def _edge_label(edge: float) -> str:
    """Bucket edge -> metric-name fragment (prom-safe after the
    exporter's sanitizer): 10.0 -> "10", 2.5 -> "2_5", -10.0 -> "m10"
    (one "m" convention for every negative edge — a raw "-" would
    sanitize to "_" and read ambiguously against the positive edge)."""
    if edge == int(edge):
        text = str(int(edge))
    else:
        text = repr(float(edge)).replace(".", "_")
    return text.replace("-", "m")


class DeviceTelemetry:
    """Declarative spec for a set of device-resident instruments.

    ``namespace`` scopes the published metric names:
    ``devtel/<namespace>/<name>``.  Declaration happens at construction
    time on the host; all ``inc``/``set``/``observe`` calls are pure
    jnp and safe under tracing.
    """

    def __init__(self, namespace: str):
        self.namespace = namespace
        self._counters: Dict[str, str] = {}
        self._gauges: Dict[str, str] = {}
        self._hists: Dict[str, Tuple[Tuple[float, ...], str]] = {}

    # -- declaration (host, construction time) -----------------------------

    def _check_new(self, name: str):
        if (name in self._counters or name in self._gauges
                or name in self._hists):
            raise ValueError(
                f"telemetry instrument {name!r} already declared in "
                f"namespace {self.namespace!r}")

    def counter(self, name: str, help: str = "") -> "DeviceTelemetry":
        """A monotonically accumulated f32 scalar (``inc``)."""
        self._check_new(name)
        self._counters[name] = help
        return self

    def gauge(self, name: str, help: str = "") -> "DeviceTelemetry":
        """A last-value f32 scalar (``set``)."""
        self._check_new(name)
        self._gauges[name] = help
        return self

    def histogram(self, name: str, edges: Sequence[float],
                  help: str = "") -> "DeviceTelemetry":
        """A bucketed histogram: ``len(edges) + 1`` counts (the last
        bucket is ``> edges[-1]``), plus exact running sum and count —
        so means are exact regardless of bucket resolution."""
        self._check_new(name)
        edges = tuple(float(e) for e in edges)
        if list(edges) != sorted(edges) or len(set(edges)) != len(edges):
            raise ValueError(
                f"histogram {name!r} edges must be strictly increasing")
        if not edges:
            raise ValueError(f"histogram {name!r} needs >= 1 edge")
        self._hists[name] = (edges, help)
        return self

    @property
    def empty(self) -> bool:
        return not (self._counters or self._gauges or self._hists)

    def full_name(self, name: str) -> str:
        """Registry/metric name for an instrument of this spec."""
        return f"devtel/{self.namespace}/{name}"

    def _key(self, prefix: str, name: str) -> str:
        return f"{prefix}{self.namespace}/{name}"

    # -- buffer lifecycle --------------------------------------------------

    def init(self) -> Dict:
        """A fresh zeroed telemetry pytree.  One DISTINCT buffer per
        leaf: sharing one zeros array across leaves would make donation
        of the containing pytree fail with "attempt to donate the same
        buffer twice" (the envs/device.py lesson)."""
        import jax.numpy as jnp

        tel: Dict = {}
        for name in self._counters:
            tel[self._key(_COUNTER, name)] = jnp.zeros((), jnp.float32)
        for name in self._gauges:
            tel[self._key(_GAUGE, name)] = jnp.zeros((), jnp.float32)
        for name, (edges, _) in self._hists.items():
            base = self._key(_HIST, name)
            tel[base + ":buckets"] = jnp.zeros(
                (len(edges) + 1,), jnp.float32)
            tel[base + ":sum"] = jnp.zeros((), jnp.float32)
            tel[base + ":count"] = jnp.zeros((), jnp.float32)
        return tel

    # -- in-graph ops (pure, trace-safe) -----------------------------------

    def inc(self, tel: Dict, name: str, amount=1.0) -> Dict:
        """``tel`` with counter ``name`` increased by ``amount`` (a
        python scalar or a traced f32 scalar)."""
        import jax.numpy as jnp

        if name not in self._counters:
            raise KeyError(f"unknown telemetry counter {name!r}")
        key = self._key(_COUNTER, name)
        tel = dict(tel)
        tel[key] = tel[key] + jnp.asarray(amount, jnp.float32)
        return tel

    def set(self, tel: Dict, name: str, value) -> Dict:
        """``tel`` with gauge ``name`` set to ``value``."""
        import jax.numpy as jnp

        if name not in self._gauges:
            raise KeyError(f"unknown telemetry gauge {name!r}")
        key = self._key(_GAUGE, name)
        tel = dict(tel)
        tel[key] = jnp.asarray(value, jnp.float32).reshape(())
        return tel

    def observe(self, tel: Dict, name: str, values,
                where=None) -> Dict:
        """``tel`` with histogram ``name`` fed every element of
        ``values`` (any shape) for which ``where`` is True (``where``
        broadcasts against ``values``; None = all).  Bucketing is a
        ``searchsorted`` over the declared edges plus a one-hot
        reduction — pure elementwise/matmul work that fuses into the
        surrounding program."""
        import jax
        import jax.numpy as jnp

        if name not in self._hists:
            raise KeyError(f"unknown telemetry histogram {name!r}")
        edges, _ = self._hists[name]
        raw = jnp.asarray(values, jnp.float32)
        # Nothing here flattens ``values``: a [T, B] input whose B is
        # sharded over a mesh stays sharded through the bucketing, and
        # the reductions below become partial sums plus an all-reduce
        # of the buckets (a ravel was a time-major merge of the
        # sharded axis: an all-gather of the input on every device).
        if where is None:
            weights = jnp.ones(raw.shape, jnp.float32)
        else:
            weights = jnp.broadcast_to(
                jnp.asarray(where), raw.shape).astype(jnp.float32)
        # Masked-out entries must be SELECTED out, not multiplied by
        # zero: NaN * 0 = NaN, so a masked non-finite value would
        # still poison the cumulative ":sum" buffer (and relying on
        # XLA to rewrite the multiply into a select is an optimizer
        # behavior, not a contract).
        values = jnp.where(weights > 0, raw, 0.0)
        edges_arr = jnp.asarray(edges, jnp.float32)
        # side="left": a value exactly equal to an edge lands in that
        # edge's bucket, matching the published ``le_<edge>`` (<=)
        # label — prometheus ``le`` semantics.
        idx = jnp.searchsorted(edges_arr, values, side="left")
        onehot = jax.nn.one_hot(idx, len(edges) + 1, dtype=jnp.float32)
        base = self._key(_HIST, name)
        tel = dict(tel)
        tel[base + ":buckets"] = (tel[base + ":buckets"]
                                  + (onehot * weights[..., None]).sum(
                                      tuple(range(raw.ndim))))
        tel[base + ":sum"] = tel[base + ":sum"] + (values * weights).sum()
        tel[base + ":count"] = tel[base + ":count"] + weights.sum()
        return tel

    # -- host side ---------------------------------------------------------

    def fetch(self, tel: Dict) -> Dict[str, np.ndarray]:
        """Materialize THIS spec's leaves of ``tel`` on the host — the
        one device→host sync, sized a few hundred bytes.  Leaves of
        other specs in a merged pytree are left untouched (not
        fetched).  Multi-process replicated leaves read their local
        shard (every process holds the full value)."""
        return _materialize_leaves(
            {key: value for key, value in tel.items()
             if self.owns_key(key)})

    def owns_key(self, key: str) -> bool:
        prefix = self.namespace + "/"
        return (key.startswith((_COUNTER + prefix, _GAUGE + prefix,
                                _HIST + prefix)))

    # -- introspection (publisher + tests) ---------------------------------

    def counters(self) -> List[str]:
        return sorted(self._counters)

    def gauges(self) -> List[str]:
        return sorted(self._gauges)

    def histograms(self) -> Dict[str, Tuple[float, ...]]:
        return {name: edges
                for name, (edges, _) in sorted(self._hists.items())}

    def value(self, fetched: Dict[str, np.ndarray], name: str):
        """Read one instrument out of a ``fetch()`` result: counters
        and gauges return a float; histograms a dict with ``buckets``
        (np array), ``sum``, ``count``, and exact ``mean``."""
        if name in self._counters:
            return float(fetched[self._key(_COUNTER, name)])
        if name in self._gauges:
            return float(fetched[self._key(_GAUGE, name)])
        if name in self._hists:
            base = self._key(_HIST, name)
            count = float(fetched[base + ":count"])
            total = float(fetched[base + ":sum"])
            return {
                "buckets": np.asarray(fetched[base + ":buckets"]),
                "sum": total,
                "count": count,
                "mean": total / count if count else 0.0,
            }
        raise KeyError(f"unknown telemetry instrument {name!r}")


def _materialize_leaves(mine: Dict) -> Dict[str, np.ndarray]:
    """Host copies of every leaf in ``mine``, as ONE device→host
    transfer when possible: the f32 leaves are device-concatenated
    into a single vector, copied once, and split back on the host.
    Per-leaf ``np.asarray`` would pay one round trip per leaf — on a
    remote device that is a full link RTT each, turning the
    "few hundred bytes" fetch into ~a second of serial latency.  The
    per-leaf path remains as the fallback for host arrays and
    non-fully-addressable (multi-process) leaves, which read their
    local shard."""
    try:
        import jax
        import jax.numpy as jnp
    except ImportError:  # jax-free consumers hand numpy leaves
        jax = None
    if (jax is not None and mine
            and all(isinstance(v, jax.Array)
                    and getattr(v, "is_fully_addressable", True)
                    for v in mine.values())):
        flat = np.asarray(jnp.concatenate(
            [jnp.atleast_1d(v).ravel() for v in mine.values()]))
        out = {}
        offset = 0
        for key, value in mine.items():
            n = int(np.prod(value.shape)) if value.shape else 1
            out[key] = flat[offset:offset + n].reshape(value.shape)
            offset += n
        return out

    def _host(x):
        if (hasattr(x, "is_fully_addressable")
                and not x.is_fully_addressable):
            return np.asarray(x.addressable_shards[0].data)
        return np.asarray(x)

    return {key: _host(value) for key, value in mine.items()}


def fetch_merged(specs: Iterable[DeviceTelemetry],
                 tel: Dict) -> Dict[str, np.ndarray]:
    """Materialize EVERY spec's leaves of a merged pytree as ONE
    device→host transfer.  ``spec.fetch`` per spec would pay one link
    round trip each — the fused in-graph program carries env + learner
    telemetry in one donated dict precisely so the log-interval fetch
    stays a single sync."""
    specs = list(specs)
    return _materialize_leaves(
        {key: value for key, value in tel.items()
         if any(spec.owns_key(key) for spec in specs)})


def merge_init(specs: Iterable[DeviceTelemetry]) -> Dict:
    """One telemetry pytree holding every spec's instruments (the fused
    in-graph program carries env + learner telemetry in ONE donated
    dict).  Namespaces keep keys disjoint; a collision raises."""
    tel: Dict = {}
    for spec in specs:
        part = spec.init()
        overlap = set(part) & set(tel)
        if overlap:
            raise ValueError(
                f"telemetry namespace collision on {sorted(overlap)}")
        tel.update(part)
    return tel


class TelemetryPublisher:
    """Host side: fold fetched telemetry snapshots into a
    MetricsRegistry so device instruments ride the existing
    prom/report/aggregate path.

    Published names (after the exporter's ``impala_`` prefix +
    sanitizer):

    - counter ``name`` ->
        ``devtel/<ns>/<name>_total``  registry Counter (delta-inc'd, so
        the process counter stays monotonic across runs and fleet folds
        SUM it), plus
        ``devtel/<ns>/<name>``        registry Gauge = this run's
        device-cumulative value (exact per-run reading).
    - gauge ``name`` -> ``devtel/<ns>/<name>`` registry Gauge.
    - histogram ``name`` ->
        ``devtel/<ns>/<name>/count`` / ``/sum`` / ``/mean`` Gauges
        (device-cumulative; mean is exact), plus one Counter per bucket
        ``devtel/<ns>/<name>/bucket/le_<edge>_total`` (last bucket
        ``gt_<edge>_total``), delta-inc'd.

    Delta tracking is per publisher instance — one publisher per run —
    so a fresh run's device buffers (restarting at zero) never make a
    process-global counter appear to go backwards.
    """

    def __init__(self, specs: Union[DeviceTelemetry,
                                    Sequence[DeviceTelemetry]],
                 registry=None):
        from scalable_agent_tpu.obs.registry import get_registry

        if isinstance(specs, DeviceTelemetry):
            specs = [specs]
        self._specs = list(specs)
        self._registry = registry or get_registry()
        self._instruments: Dict[str, object] = {}
        reg = self._registry
        for spec in self._specs:
            for name in spec.counters():
                full = spec.full_name(name)
                self._instruments[full + "_total"] = reg.counter(
                    full + "_total",
                    f"device-accumulated {full} (fetched at log "
                    f"cadence)")
                self._instruments[full] = reg.gauge(
                    full, f"this run's device-cumulative {full}")
            for name in spec.gauges():
                full = spec.full_name(name)
                self._instruments[full] = reg.gauge(
                    full, f"device-resident gauge {full}")
            for name, edges in spec.histograms().items():
                full = spec.full_name(name)
                for label in self._bucket_labels(edges):
                    key = f"{full}/bucket/{label}_total"
                    self._instruments[key] = reg.counter(
                        key, f"device-bucketed {full} observations")
                for suffix in ("count", "sum", "mean"):
                    key = f"{full}/{suffix}"
                    self._instruments[key] = reg.gauge(
                        key, f"device histogram {full} {suffix} "
                             f"(exact, cumulative this run)")
        self._last: Dict[str, float] = {}

    @staticmethod
    def _bucket_labels(edges: Tuple[float, ...]) -> List[str]:
        labels = [f"le_{_edge_label(e)}" for e in edges]
        labels.append(f"gt_{_edge_label(edges[-1])}")
        return labels

    def _delta_inc(self, key: str, cumulative: float):
        last = self._last.get(key, 0.0)
        if cumulative > last:
            self._instruments[key].inc(cumulative - last)
            self._last[key] = cumulative

    def publish(self, fetched: Dict[str, np.ndarray]):
        """Fold one (or several merged) ``spec.fetch()`` results into
        the registry.  Missing keys are skipped, so a partial fetch
        (one spec of a merged pytree) publishes what it has."""
        for spec in self._specs:
            for name in spec.counters():
                key = spec._key(_COUNTER, name)
                if key not in fetched:
                    continue
                value = float(fetched[key])
                full = spec.full_name(name)
                self._delta_inc(full + "_total", value)
                self._instruments[full].set(value)
            for name in spec.gauges():
                key = spec._key(_GAUGE, name)
                if key not in fetched:
                    continue
                self._instruments[spec.full_name(name)].set(
                    float(fetched[key]))
            for name, edges in spec.histograms().items():
                base = spec._key(_HIST, name)
                if base + ":count" not in fetched:
                    continue
                full = spec.full_name(name)
                buckets = np.asarray(fetched[base + ":buckets"])
                for label, value in zip(self._bucket_labels(edges),
                                        buckets):
                    self._delta_inc(f"{full}/bucket/{label}_total",
                                    float(value))
                count = float(fetched[base + ":count"])
                total = float(fetched[base + ":sum"])
                self._instruments[full + "/count"].set(count)
                self._instruments[full + "/sum"].set(total)
                self._instruments[full + "/mean"].set(
                    total / count if count else 0.0)
