"""What one run showed from the inside: thin wrappers round the program's
public calls that record and then call straight through (the technique
of ``chip_smoke.py``'s ``_Probe``, kept in the benchmark's own files).

Wrapped: ``Learner.init`` (the weights become the benchmark's, made on
the device from ``--seed``), ``Learner.update`` and
``InflightWindow.retire`` (host loop), ``driver.start_prefetch`` (to
time the learner's wait for a staged batch), and each
``InGraphTrainer``'s jitted ``train_step`` (fused loop).  The window is
closed through the program's own preemption drain
(``get_fleet().request_preemption``), the path a SIGTERM takes.
"""

import collections
import time
from typing import Any, Dict, List, Optional

from benchmark.lib import reference as default_reference, window

CHECK_STEPS = 3        # the reference follows this many first steps
FUSED_WARMUP = 6       # fused updates discarded before the window
HOST_MIN_WARMUP = CHECK_STEPS
FUSED_INFLIGHT = 2     # dispatched-but-unretired fused steps
LOG_WAIT_S = 30.0      # no log publish by then: open the window anyway


def _key_names(path) -> tuple:
    names = []
    for key in path:
        for attr in ("key", "name", "idx"):
            if hasattr(key, attr):
                names.append(str(getattr(key, attr)))
                break
        else:
            names.append(str(key))
    return tuple(names)


class CompileCounter:
    """JAX's own compile events, counted by the benchmark."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    def install(self):
        import jax.monitoring

        def on_duration(event: str, duration: float, **kwargs):
            if "compile" in event:
                self.count += 1
                self.seconds += max(0.0, float(duration))

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        return self


class Probe:
    def __init__(self, *, config: Dict[str, Any], backend: str, seed: int,
                 seconds: float, trace: bool, trace_seconds: float,
                 trace_dir: str, t_launch: float, reference=None):
        self.config = config
        # the cell's plain reference: the seeded weights are its
        self.reference = reference or default_reference
        self.backend = backend
        self.seed = seed
        self.trace = trace
        self.trace_seconds = min(trace_seconds, seconds)
        self.trace_dir = trace_dir
        self.t_launch = t_launch
        host = backend == "host"
        self.clock = window.WindowClock(
            seconds,
            min_warmup=HOST_MIN_WARMUP if host else FUSED_WARMUP,
            needs_drain=host)
        self.log_writes = 0
        self.clock.gate = lambda: self.log_writes >= 1 or (
            self.t_first_update is not None
            and time.perf_counter() - self.t_first_update > LOG_WAIT_S)
        self.compiles = CompileCounter().install()
        # -- recorded ------------------------------------------------------
        self.dispatched = 0
        self.last_wait: Optional[float] = None
        self.t_first_update: Optional[float] = None
        self.t_open: Optional[float] = None
        self.t_close: Optional[float] = None
        self.window_losses: List[Any] = []
        self.window_skips: List[Any] = []
        self.counters_open: Dict[str, float] = {}
        self.counters_close: Dict[str, float] = {}
        self.trace_started_at: Optional[float] = None
        self.trace_stopped_at: Optional[float] = None
        self.policy: Dict[str, Any] = {}
        self.param_devices: Optional[int] = None
        self.weights_replaced = False
        self.marks: Dict[str, float] = {}      # seconds since launch
        # -- the first steps, for the reference ----------------------------
        self.check_batches: List[Any] = []     # host loop: Batch each
        self.check_losses: List[Any] = []
        self.check_nu1: Optional[List[Any]] = None
        self.check_params: Optional[Dict] = None
        self.param_paths: List[tuple] = []
        self._unpatch: List = []

    def mark(self, name: str) -> None:
        self.marks.setdefault(name, time.perf_counter() - self.t_launch)

    # -- counters the window is held to -------------------------------------

    def _counters(self) -> Dict[str, float]:
        from scalable_agent_tpu.obs import get_registry

        snap = get_registry().snapshot()
        return {
            "compiles": self.compiles.count,
            "compile_s": self.compiles.seconds,
            "worker_respawns": snap.get("env/worker_respawns_total", 0.0),
            "actor_restarts": snap.get("actor/restarts_total", 0.0),
            "health_windows": snap.get("health/profile_windows_total", 0.0),
            "nonfinite_skips": snap.get(
                "learner/nonfinite_skips_total", 0.0),
        }

    # -- the window ----------------------------------------------------------

    def on_retire(self, t: float, metrics) -> None:
        status = self.clock.on_retire(t, self.last_wait)
        if status == "opened":
            self.t_open = t
            self.counters_open = self._counters()
        elif status == "inside":
            self.window_losses.append(metrics["total_loss"])
            if "update_skipped" in metrics:
                self.window_skips.append(metrics["update_skipped"])
        if status in ("opened", "inside") and self.trace:
            self._maybe_start_trace(t)
        if status == "closed" and self.t_close is None:
            self.t_close = t
            self._stop_trace()
            self.counters_close = self._counters()
            from scalable_agent_tpu.runtime.fleet import get_fleet

            get_fleet().request_preemption("benchmark window closed")

    def _maybe_start_trace(self, t: float) -> None:
        if self.trace_started_at is not None:
            return
        if t - self.t_open < self.clock.seconds - self.trace_seconds:
            return
        import jax

        from scalable_agent_tpu.obs import get_tracer

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0    # spans yes, Python frames no
        options.host_tracer_level = 1      # ...and not the runtime's own
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        get_tracer().set_annotate(True)
        self.trace_started_at = time.perf_counter()

    def _stop_trace(self) -> None:
        if self.trace_started_at is None or self.trace_stopped_at:
            return
        import jax

        from scalable_agent_tpu.obs import get_tracer

        self.trace_stopped_at = time.perf_counter()
        get_tracer().set_annotate(False)
        jax.profiler.stop_trace()

    # -- weights -------------------------------------------------------------

    def _replace_weights(self, state):
        """The program's seeded init gives way to the benchmark's
        weights (same tree, same shapes, same placement)."""
        import jax

        flat = self.reference.make_weights(self.config, self.seed)
        seen = []

        def swap(path, leaf):
            names = _key_names(path)
            names = names[1:] if names and names[0] == "params" else names
            new = flat.get(names)
            if new is None or tuple(new.shape) != tuple(leaf.shape):
                raise RuntimeError(
                    f"parameter {'/'.join(names)} {tuple(leaf.shape)}: "
                    f"the configuration file gives "
                    f"{None if new is None else tuple(new.shape)}")
            seen.append(names)
            return jax.device_put(new.astype(leaf.dtype), leaf.sharding)

        params = jax.tree_util.tree_map_with_path(swap, state.params)
        if len(seen) != len(flat):
            raise RuntimeError(
                f"the program has {len(seen)} parameter leaves, the "
                f"configuration file gives {len(flat)}")
        self.param_paths = seen
        self.weights_replaced = True
        return state._replace(params=params)

    def _note_learner(self, learner) -> None:
        # a policy without a conv stem has no ``conv_backend``: None
        agent = learner._agent
        self.policy = {name: getattr(agent, name, None) for name in (
            "core_impl", "conv_backend", "core_matmul_dtype",
            "remat_torso", "torso_type")}
        self.policy["mesh_devices"] = int(learner.mesh.devices.size)

    # -- the first steps -----------------------------------------------------

    def before_step(self, k: int, state, carry, counter):
        """What fused dispatch ``k`` (from 1) is given.  A run gives it
        what the program hands over; ``benchmark/seeds.py`` starts
        every third from the next seed's weights."""
        return state, carry, counter

    def _capture_post(self, k: int, new_state, metrics) -> None:
        import jax

        if k > CHECK_STEPS:
            return
        self.check_losses.append(metrics["total_loss"])
        if k == 1:
            self.check_nu1 = jax.device_get(
                jax.tree_util.tree_leaves(new_state.opt_state))
            self.t_first_update = time.perf_counter()
            self.mark("first_update_done")
            leaf = jax.tree_util.tree_leaves(new_state.params)[0]
            self.param_devices = len(leaf.sharding.device_set)
        if k == CHECK_STEPS:
            self.check_params = jax.device_get(new_state.params)
            self.mark("check_steps_done")

    # -- patches -------------------------------------------------------------

    def install(self) -> None:
        from scalable_agent_tpu.runtime.learner import Learner

        probe = self
        original_init = Learner.init

        def init(self, rng, example_trajectory, env_frames=0.0):
            probe.mark("learner_init_enter")
            state = original_init(self, rng, example_trajectory,
                                  env_frames)
            probe.mark("program_init_done")
            probe._note_learner(self)
            state = probe._replace_weights(state)
            probe.mark("weights_replaced")
            return state

        Learner.init = init
        self._unpatch.append(lambda: setattr(Learner, "init",
                                             original_init))
        # The first log-interval publish compiles a few tiny programs:
        # it has to be behind us before the window opens.
        from scalable_agent_tpu.obs import MetricsWriter

        original_write = MetricsWriter.write

        def write(self, *args, **kwargs):
            probe.log_writes += 1
            return original_write(self, *args, **kwargs)

        MetricsWriter.write = write
        self._unpatch.append(lambda: setattr(MetricsWriter, "write",
                                             original_write))
        if self.backend == "host":
            self._install_host()
        else:
            self._install_fused()

    def uninstall(self) -> None:
        while self._unpatch:
            self._unpatch.pop()()

    def _install_host(self) -> None:
        import jax

        from scalable_agent_tpu import driver
        from scalable_agent_tpu.runtime.learner import Learner
        from scalable_agent_tpu.runtime.transport import InflightWindow

        probe = self
        original_update = Learner.update
        original_retire = InflightWindow.retire
        original_prefetch = driver.start_prefetch

        def update(self, state, trajectory, fresh=True):
            probe.dispatched += 1
            k = probe.dispatched
            probe.mark("first_dispatch")
            if k <= CHECK_STEPS:
                host = jax.device_get(trajectory)
                probe.check_batches.append(probe.reference.Batch(
                    action=host.agent_outputs.action,
                    logits=host.agent_outputs.policy_logits,
                    reward=host.env_outputs.reward,
                    done=host.env_outputs.done,
                    frame=host.env_outputs.observation.frame,
                    c0=host.agent_state.c, h0=host.agent_state.h))
            new_state, metrics = original_update(
                self, state, trajectory, fresh)
            probe._capture_post(k, new_state, metrics)
            return new_state, metrics

        def retire(self):
            metrics = original_retire(self)
            probe.on_retire(time.perf_counter(), metrics)
            return metrics

        def start_prefetch(pool, learner, staged, stop):
            original_get = staged.get

            def timed_get(*args, **kwargs):
                t0 = time.perf_counter()
                item = original_get(*args, **kwargs)
                probe.last_wait = time.perf_counter() - t0
                return item

            staged.get = timed_get
            return original_prefetch(pool, learner, staged, stop)

        Learner.update = update
        InflightWindow.retire = retire
        driver.start_prefetch = start_prefetch
        self._unpatch += [
            lambda: setattr(Learner, "update", original_update),
            lambda: setattr(InflightWindow, "retire", original_retire),
            lambda: setattr(driver, "start_prefetch", original_prefetch)]

    def _install_fused(self) -> None:
        import jax

        from scalable_agent_tpu.runtime.ingraph import InGraphTrainer

        probe = self
        original = InGraphTrainer.__init__

        def init(self, agent, learner, *args, **kwargs):
            original(self, agent, learner, *args, **kwargs)
            step = self.train_step
            pending = collections.deque()

            def train_step(state, carry, counter):
                probe.dispatched += 1
                k = probe.dispatched
                probe.mark("first_dispatch")
                out = step(*probe.before_step(k, state, carry, counter))
                probe._capture_post(k, out[0], out[2])
                pending.append(out[2])
                if len(pending) >= FUSED_INFLIGHT:
                    metrics = pending.popleft()
                    jax.block_until_ready(metrics["total_loss"])
                    probe.on_retire(time.perf_counter(), metrics)
                return out

            train_step.lower = step.lower   # the driver lowers it
            self.train_step = train_step

        InGraphTrainer.__init__ = init
        self._unpatch.append(
            lambda: setattr(InGraphTrainer, "__init__", original))


def stop_children(timeout_s: float = 10.0) -> List[str]:
    """Every env worker must be gone; end (and name) what is left."""
    import multiprocessing

    leftover = multiprocessing.active_children()
    for child in leftover:
        child.terminate()
    for child in leftover:
        child.join(timeout=timeout_s)
    return [child.name for child in leftover]


def device_facts() -> Dict[str, Any]:
    """The device as JAX reports it.  ``memory_peak_bytes`` is the peak
    on the fullest chip: the allocator's ``peak_bytes_in_use`` (arrays)
    plus ``peak_bytes_reserved`` — the scratch memory of loaded
    programs, which on a TPU is reserved apart from the arrays and is
    most of a fused step's footprint (my chip run, PR 23: a program
    with 1 GiB of temporaries moved ``bytes_reserved`` by 1 GiB and
    ``peak_bytes_in_use`` not at all)."""
    import jax

    devices = jax.devices()
    peaks, stats_all = [], []
    for device in jax.local_devices():
        stats = device.memory_stats() or {}
        stats_all.append(stats)
        peaks.append(int(stats.get("peak_bytes_in_use", 0))
                     + int(stats.get("peak_bytes_reserved", 0)))
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(peaks) if peaks else 0,
            "memory_stats": stats_all[:1]}
