"""Host-side actor runtime: experience generation feeding the learner.

Re-design of the reference's in-graph actor machinery (reference:
experiment.py:240-321 ``build_actor`` + QueueRunner threads :559-562) for
a host-runtime world:

- A ``VectorActor`` drives one vectorized env group: ONE jitted
  ``actor_step`` evaluates the whole group's policies as a single [B]
  batch on the TPU (the role of the reference's dynamic batcher — but
  batching is structural here, not opportunistic; the ``DynamicBatcher``
  service remains for irregular callers).
- Trajectory layout matches the reference exactly: each unroll emits T+1
  entries whose first entry is the last entry of the previous unroll, plus
  the LSTM state at the unroll boundary (reference: experiment.py:311-321).
  The learner drops the first behaviour entry and bootstraps from the last
  (runtime/learner.py).
- An ``ActorPool`` runs several groups in Python threads; while one group
  waits on env subprocess pipes, another's inference runs on device (the
  overlap the reference gets from async TF ops).  Trajectories flow
  through a bounded queue (capacity 1 per group — the policy-lag semantics
  of the reference's FIFOQueue(1), experiment.py:531).
- Weights: actors read a versioned host-side snapshot published by the
  learner loop (replacing implicit parameter-server variable reads,
  reference: experiment.py:503-505).
"""

import functools
import queue as queue_lib
import threading
import time
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from scalable_agent_tpu.models.agent import ImpalaAgent, actor_step, initial_state
from scalable_agent_tpu.envs.vector import MultiEnv
from scalable_agent_tpu.obs import (
    get_flight_recorder,
    get_ledger,
    get_registry,
    get_tracer,
    get_watchdog,
)
from scalable_agent_tpu.obs.ledger import now_us as ledger_now_us
from scalable_agent_tpu.types import (
    ActorOutput,
    AgentOutput,
    AgentState,
    map_structure,
)


def actor_stage_histograms(registry=None):
    """The shared per-step stage histograms every actor flavour feeds
    (and the stall attributor reads): (env_step_s, inference_s).  One
    registration point so the metric names can't drift apart across
    VectorActor / AccumVectorActor / GroupedAccumActor."""
    registry = registry or get_registry()
    return (
        registry.histogram(
            "actor/env_step_s",
            "seconds per vectorized env step (send+recv)"),
        registry.histogram(
            "actor/inference_s",
            "seconds per batched inference step (dispatch+fetch)"),
    )


def _to_numpy(tree):
    return map_structure(
        lambda x: None if x is None else np.asarray(x), tree)


def _stack_time(entries):
    """List of [B, ...] pytrees -> one [T, B, ...] pytree."""
    return map_structure(
        lambda *xs: None if xs[0] is None else np.stack(xs), *entries)


def snapshot_params_for_inference(params, device):
    """Re-place learner params as a private single-device snapshot.

    Shared by ActorPool.set_params and ActorService.set_params: the
    snapshot must be a real COPY — ``device_put`` aliases any existing
    copy the target device already holds (single-device meshes
    trivially; multi-device replicated params via their local shard),
    and the learner's donated update would free the aliased buffer out
    from under the actors ("Array has been deleted").  Params are
    small; the on-device copy is cheap."""

    def local_view(leaf):
        # Multi-host: a global array isn't fully addressable here.
        # Replicated leaves carry the full value in every local shard —
        # take this process's copy.  (Cross-host tensor-sharded params
        # would need a DCN gather; actors don't support that layout.)
        if (hasattr(leaf, "is_fully_addressable")
                and not leaf.is_fully_addressable):
            shard = leaf.addressable_shards[0].data
            if shard.shape != leaf.shape:
                raise NotImplementedError(
                    "actor inference needs replicated (or host-local) "
                    "params; got a cross-host-sharded leaf of shape "
                    f"{leaf.shape} with local shard {shard.shape}")
            return shard
        return leaf

    params = jax.tree_util.tree_map(local_view, params)
    params = jax.device_put(params, device)
    return jax.tree_util.tree_map(jnp.copy, params)


def publish_trajectory(queue, trajectory, stop, *, actor_name: str,
                       level_name: str, birth_us=None, frames: float = 0.0,
                       frames_counter=None, trajectories_counter=None
                       ) -> bool:
    """Hand one trajectory to the learner queue with full provenance.

    Opens the ledger record at the unroll's birth, binds it to the
    trajectory OBJECT (so the consumer recovers the id regardless of
    producer interleaving), blocks on the bounded queue re-touching the
    watchdog (a full queue is backpressure, not a wedge), and — when
    shutdown catches the hand-off — closes the record as ``abandoned``
    instead of leaking it open.  Returns True when delivered.  Shared
    by ActorPool's unroll loop and the ActorService trajectory packer
    (runtime/service.py)."""
    ledger = get_ledger()
    watchdog = get_watchdog()
    tid = ledger.open(actor_name, level_name or "actor",
                      birth_us=birth_us)
    ledger.stamp(tid, "unroll_done")
    ledger.bind(id(trajectory), tid)
    delivered = False
    with get_tracer().span("batcher/queue_put", cat="queue"):
        while not stop.is_set():
            watchdog.touch()
            try:
                queue.put(trajectory, timeout=0.1)
                delivered = True
                break
            except queue_lib.Full:
                continue
    if delivered:
        ledger.stamp(tid, "queue_put")
        get_flight_recorder().record("queue", "put")
        if trajectories_counter is not None:
            trajectories_counter.inc()
        if frames_counter is not None and frames:
            frames_counter.inc(frames)
    else:
        # Shutdown caught the hand-off: the record must not leak open
        # (and its binding must not alias a later object at the same
        # address).
        ledger.unbind(id(trajectory))
        ledger.close(tid, retired=False, fate="abandoned")
    return delivered


def consume_trajectory(queue, timeout: Optional[float] = None):
    """The learner-side half of the queue hand-off (ActorPool and
    ActorService ``get_trajectory``): pop one item, re-raise marshalled
    producer exceptions, recover the provenance record bound to the
    object and make it the consuming thread's CURRENT record so the
    transport/learner layers downstream stamp the right one."""
    with get_tracer().span("batcher/queue_get", cat="queue"):
        item = queue.get(timeout=timeout)
    get_flight_recorder().record("queue", "get")
    if isinstance(item, Exception):
        raise item
    ledger = get_ledger()
    tid = ledger.lookup(id(item))
    if tid is not None:
        ledger.stamp(tid, "queue_get")
    ledger.set_current(tid)
    return item


def merged_episode_stats(envs_iter):
    """Merged completed-episode (return, length) ring buffers across a
    fleet of MultiEnvs (ActorPool and ActorService share this)."""
    stats = []
    for envs in envs_iter:
        stats.extend(envs.episode_stats)
    return stats


def drain_level_stats(envs_iter):
    """Pop all level-attributed episodes completed since the last
    drain: {level_name: [(episode_return, episode_length), ...]}.

    Feeds multi-task per-level metrics and the DMLab-30 training suite
    score (reference: experiment.py:634-667, which clears the per-level
    lists after each score — draining gives the same
    each-episode-counted-once semantics).  popleft is atomic, so env
    threads can keep appending during the drain.  Shared by ActorPool
    and ActorService."""
    by_level = {}
    for envs in envs_iter:
        queue = getattr(envs, "level_episode_stats", None)
        if not queue:
            continue
        while True:
            try:
                level, ret, length = queue.popleft()
            except IndexError:
                break
            by_level.setdefault(level, []).append((ret, length))
    return by_level


def run_with_retry(loop_fn, *, stop: threading.Event, deliver,
                   reset=None, max_restarts: int = 3,
                   backoff_s: float = 0.5, backoff_cap_s: float = 30.0,
                   window_s: float = 600.0, restarts_counter=None):
    """Bounded-respawn shell around a producer thread's steady-state
    loop: a transient simulator/link fault must not end a multi-day run
    (docs/robustness.md).

    ``loop_fn`` runs until clean stop or an exception; a failure gets
    ``max_restarts`` respawns within a sliding ``window_s`` (crash-loop
    detection — isolated faults days apart age out) with capped
    exponential backoff, ``reset()`` called before each retry; the
    terminal exception goes to ``deliver(exc)`` (the queue hand-off
    that marshals it to the driver).  Shared by ActorPool's actor
    threads and the ActorService env-group threads."""
    from collections import deque as _deque

    from scalable_agent_tpu.utils import log

    recorder = get_flight_recorder()
    thread_name = threading.current_thread().name
    restart_times = _deque()
    try:
        while not stop.is_set():
            try:
                loop_fn()
                return  # clean stop
            except Exception as exc:
                if stop.is_set():
                    return  # shutdown cascade (e.g. batcher closed)
                recorder.record("exception", type(exc).__name__,
                                {"where": thread_name})
                now = time.monotonic()
                while (restart_times
                       and now - restart_times[0] > window_s):
                    restart_times.popleft()
                if len(restart_times) >= max_restarts:
                    # Budget spent: surface the terminal failure.  The
                    # deliver hand-off carries the exception to the
                    # driver; the flight-recorder dump preserves THIS
                    # thread's last moments (ring tail + every thread's
                    # stack) even if the driver never drains it.
                    recorder.dump_all(
                        f"exception:{type(exc).__name__}:{thread_name}")
                    deliver(exc)
                    return
                restart_times.append(now)
                in_window = len(restart_times)
                backoff = min(backoff_cap_s,
                              backoff_s * 2 ** (in_window - 1))
                if restarts_counter is not None:
                    restarts_counter.inc()
                recorder.record(
                    "actor_restart", thread_name,
                    {"restart": in_window, "max": max_restarts,
                     "backoff_s": round(backoff, 3),
                     "error": type(exc).__name__})
                log.error(
                    "actor %s failed (%s: %s) — restart %d/%d in the "
                    "%.0fs window, retrying in %.2fs",
                    thread_name, type(exc).__name__, exc, in_window,
                    max_restarts, window_s, backoff)
                # Idle backoff is not a wedge; the next loop's touch
                # re-arms the heartbeat.
                get_watchdog().suspend()
                if reset is not None:
                    try:
                        reset()
                    except Exception:
                        log.exception("actor %s reset failed before "
                                      "retry", thread_name)
                stop.wait(backoff)
    finally:
        get_watchdog().suspend()


def _service_step(agent, params, key_data, actions, env_outputs, states):
    """k co-batched group requests ([k, B, ...]) -> [k, B, ...] outputs.

    vmapped so each group keeps its own rng stream; params are shared
    across the vmap (one weight broadcast, k-fold batched compute)."""

    rngs = jax.random.wrap_key_data(key_data)  # [k] typed keys

    def one_group(rng, action, env_output, state):
        return actor_step(agent, params, rng, action, env_output, state)

    return jax.vmap(one_group)(rngs, actions, env_outputs, states)


class VectorActor:
    """One env group: batched inference + trajectory accumulation."""

    def __init__(
        self,
        agent: ImpalaAgent,
        envs: MultiEnv,
        unroll_length: int,
        level_name: str = "",
        seed: int = 0,
        step_fn: Optional[Callable] = None,
    ):
        self._agent = agent
        self._envs = envs
        self._unroll_length = unroll_length
        self.level_name = level_name
        self._rng = jax.random.key(seed)
        self._step_count = 0
        # One jitted inference step shared by everything that hands us the
        # same agent (jit caches on shapes).
        self._actor_step = step_fn or jax.jit(
            lambda params, rng, action, env_output, state: actor_step(
                agent, params, rng, action, env_output, state))
        self._last_env_output = None
        self._last_agent_output = None
        self._core_state = None
        self._h_env, self._h_infer = actor_stage_histograms()

    def _bootstrap(self, params):
        """First-ever unroll: create the initial carried entries.

        The reference initializes persistent state from a zero action and
        a zero agent output (experiment.py:243-251).
        """
        batch = self._envs.num_envs
        self._last_env_output = self._envs.initial()
        self._core_state = initial_state(batch, self._agent.core_size)
        self._last_agent_output = AgentOutput(
            action=np.asarray(self._agent.zero_actions(batch)),
            policy_logits=np.zeros(
                (batch, self._agent.num_logits), np.float32),
            baseline=np.zeros((batch,), np.float32),
        )

    def run_unroll(self, params) -> ActorOutput:
        """Generate one [T+1, B] trajectory batch under ``params``."""
        # Ledger birth stamp (obs/ledger.py): the moment this unroll's
        # first env step happens — the age every downstream staleness/
        # latency number is measured from.  The pool reads it when it
        # opens the trajectory's provenance record.
        self.unroll_birth_us = ledger_now_us()
        if self._last_env_output is None:
            self._bootstrap(params)

        env_entries = [self._last_env_output]
        agent_entries = [self._last_agent_output]
        first_state = _to_numpy(
            AgentState(c=self._core_state.c, h=self._core_state.h))

        env_output = self._last_env_output
        agent_output = self._last_agent_output
        core_state = self._core_state
        tracer = get_tracer()
        watchdog = get_watchdog()
        for _ in range(self._unroll_length):
            watchdog.touch()  # per-step heartbeat: one dict store
            self._step_count += 1
            rng = jax.random.fold_in(self._rng, self._step_count)
            t0 = time.perf_counter()
            with tracer.span("actor/inference", cat="actor"):
                out, core_state = self._actor_step(
                    params, rng, agent_output.action, env_output,
                    core_state)
                agent_output = _to_numpy(out)
            t1 = time.perf_counter()
            # Dispatch env steps, then wait — device work for other groups
            # can run while this thread blocks on the pipes.
            with tracer.span("actor/env_step", cat="actor"):
                self._envs.step_send(agent_output.action)
                env_output = self._envs.step_recv()
            self._h_infer.observe(t1 - t0)
            self._h_env.observe(time.perf_counter() - t1)
            env_entries.append(env_output)
            agent_entries.append(agent_output)

        self._last_env_output = env_output
        self._last_agent_output = agent_output
        self._core_state = core_state

        return ActorOutput(
            level_name=self.level_name,
            agent_state=first_state,
            env_outputs=_stack_time(env_entries),
            agent_outputs=_stack_time(agent_entries),
        )

    def reset(self):
        """Drop the carried unroll state after a mid-unroll failure
        (ActorPool's retry path): re-align the env pipes and force a
        fresh bootstrap — the next unroll starts from clean initial
        outputs instead of a half-stepped carry."""
        resync = getattr(self._envs, "resync", None)
        if resync is not None:
            resync()
        self._last_env_output = None
        self._last_agent_output = None
        self._core_state = None

    def close(self):
        self._envs.close()


class ActorPool:
    """N groups of vectorized actors on threads, feeding a bounded queue.

    Two inference modes:

    - ``structural`` (default): each group evaluates its own jitted
      ``actor_step`` on its full [B] batch — regular, shape-stable device
      calls.
    - ``service``: groups submit their inference requests to a
      ``NativeBatcher`` (the C++ dynamic-batching core) whose consumer
      thread co-batches however many groups arrive within ``timeout_ms``
      into ONE device call (vmapped over groups).  This is the reference's
      dynamic-batching architecture — many irregular callers amortized
      onto one accelerator (reference: dynamic_batching.py:65-102 +
      batcher.cc) — and pays off when there are many small groups.
    """

    def __init__(
        self,
        agent: ImpalaAgent,
        env_groups: Sequence[MultiEnv],
        unroll_length: int,
        level_name: str = "",
        seed: int = 0,
        queue_capacity: Optional[int] = None,
        inference_device: Optional[jax.Device] = None,
        inference_mode: str = "structural",
        service_timeout_ms: float = 5.0,
        observation_spec=None,
        fused_shards: int = 0,
        max_restarts: int = 3,
        restart_backoff_s: float = 0.5,
        restart_backoff_cap_s: float = 30.0,
        restart_window_s: float = 600.0,
    ):
        # Inference runs on ONE device (by default the first): actor
        # threads must never launch multi-device SPMD programs — concurrent
        # SPMD launches from several threads can interleave differently
        # across devices and deadlock.  set_params therefore re-places the
        # learner's (mesh-sharded) params as a single-device snapshot — the
        # explicit versioned weight publication replacing the reference's
        # parameter-server variable reads (reference: experiment.py:503-505).
        # local_devices: in a multi-host job each process's actors infer
        # on that process's own first device.
        self._inference_device = inference_device or jax.local_devices()[0]
        self._agent = agent
        if inference_mode == "structural":
            step_fn = jax.jit(
                lambda params, rng, action, env_output, state: actor_step(
                    agent, params, rng, action, env_output, state))
        elif inference_mode == "service":
            sizes = {envs.num_envs for envs in env_groups}
            if len(sizes) > 1:
                raise ValueError(
                    f"service inference needs uniform group sizes, got "
                    f"{sorted(sizes)}")
            self._service_max = len(env_groups)
            self._service_timeout_ms = service_timeout_ms
            self._batcher = None  # built lazily from the first request
            self._batcher_lock = threading.Lock()
            # One device call for k co-batched groups: vmap over the group
            # axis with per-group rng.
            self._service_jit = jax.jit(functools.partial(
                _service_step, agent))
            step_fn = self._service_request
        elif inference_mode not in ("accum", "accum_fused"):
            raise ValueError(f"unknown inference_mode {inference_mode!r}")
        self._inference_mode = inference_mode
        if inference_mode in ("accum", "accum_fused"):
            # On-device trajectory accumulation: per step only flat frame
            # bytes go up and sampled actions come down; the trajectory
            # never re-crosses the link (runtime/accum_actor.py).
            from scalable_agent_tpu.runtime.accum_actor import (
                AccumPrograms,
                AccumVectorActor,
                GroupedAccumActor,
            )

            sizes = {envs.num_envs for envs in env_groups}
            if len(sizes) > 1:
                raise ValueError(
                    f"accum inference needs uniform group sizes, got "
                    f"{sorted(sizes)}")
            # Optional observation streams (instruction token ids,
            # Doom measurement vectors) need device buffers sized from
            # the spec — the driver passes its probed observation_spec
            # so language/measurement levels work in accum mode.
            instr_spec = getattr(observation_spec, "instruction", None)
            meas_spec = getattr(observation_spec, "measurements", None)
            programs = AccumPrograms(
                agent, unroll_length, env_groups[0].num_envs,
                env_groups[0].frame_slab().shape[1:],
                instruction_shape=(tuple(instr_spec.shape)
                                   if instr_spec is not None else None),
                measurements_shape=(tuple(meas_spec.shape)
                                    if meas_spec is not None else None))
            if inference_mode == "accum_fused":
                # Cross-group co-dispatch: a lockstep driver serves its
                # groups with one vmapped device call + one fused
                # action fetch per step (~1 link RTT for its k groups;
                # see GroupedAccumActor).  ``fused_shards`` > 1 splits
                # the fleet into that many lockstep drivers on separate
                # threads, so one shard's env stepping/upload overlaps
                # another's link round trip — the middle ground between
                # fully-threaded accum (k RTTs) and one lockstep batch
                # (no overlap).  Same per-group seeds as the threaded
                # path either way, so trajectories are identical.
                # 0 = auto: probe the link at startup and pick the
                # predicted-best count (1 co-located, 2 on a
                # bandwidth-bound link — runtime/linktune.py).
                from scalable_agent_tpu.runtime.linktune import (
                    resolve_fused_shards,
                )
                from scalable_agent_tpu.utils import log

                frame_shape = env_groups[0].frame_slab().shape[1:]
                shards, link = resolve_fused_shards(
                    fused_shards, len(env_groups),
                    env_groups[0].num_envs,
                    int(np.prod(frame_shape)),
                    device=self._inference_device)
                if link is not None:
                    log.info(
                        "auto accum_fused_shards=%d (probed rtt "
                        "%.1f ms, h2d %.0f MB/s, %d groups x %d envs)",
                        shards, link.rtt_s * 1e3,
                        link.h2d_bytes_per_s / 1e6, len(env_groups),
                        env_groups[0].num_envs)
                self.fused_shards = shards
                # Balanced split: exactly ``shards`` drivers (e.g. 4
                # groups over 3 shards -> [2, 1, 1]), so the config
                # value means what it says.
                base, extra = divmod(len(env_groups), shards)
                sizes = [base + (1 if s < extra else 0)
                         for s in range(shards)]
                bounds = [0]
                for size in sizes:
                    bounds.append(bounds[-1] + size)
                self._actors = [
                    GroupedAccumActor(
                        programs, env_groups[lo:hi],
                        level_name=level_name,
                        seeds=[seed + 1000 * i for i in range(lo, hi)])
                    for lo, hi in zip(bounds, bounds[1:])
                ]
            else:
                self._actors = [
                    AccumVectorActor(programs, envs,
                                     level_name=level_name,
                                     seed=seed + 1000 * i)
                    for i, envs in enumerate(env_groups)
                ]
        else:
            self._actors = [
                VectorActor(agent, envs, unroll_length,
                            level_name=level_name, seed=seed + 1000 * i,
                            step_fn=step_fn)
                for i, envs in enumerate(env_groups)
            ]
        self.queue = queue_lib.Queue(
            maxsize=queue_capacity or len(env_groups))
        self._params = None
        self._params_version = 0
        self._params_lock = threading.Lock()
        self._stop = threading.Event()
        self._threads = []
        self._errors = []
        # Bounded respawn budget per actor thread (--actor_max_restarts):
        # a transient fault retries with capped exponential backoff; the
        # terminal exception surfaces only once the budget is spent.
        # The budget is WINDOWED (restarts within restart_window_s, the
        # same crash-loop-not-lifetime-fault semantics as MultiEnv's
        # worker respawn budget): isolated faults days apart must never
        # accumulate into a kill.  0 restores the old fail-fast
        # marshalling.
        self._max_restarts = max(0, int(max_restarts))
        self._restart_backoff_s = float(restart_backoff_s)
        self._restart_backoff_cap_s = float(restart_backoff_cap_s)
        self._restart_window_s = float(restart_window_s)

        # Observability: trajectory-queue gauges sample by callback
        # (nothing on the hot path); the frames counter gives actor-side
        # FPS independently of the learner's consumption rate.  The
        # callbacks hold only WEAK references — the process-global
        # registry must never keep a finished pool (and the trajectories
        # buffered in its queue) alive.
        import weakref

        registry = get_registry()
        queue_ref = weakref.ref(self.queue)
        registry.gauge(
            "actor_pool/queue_depth",
            "trajectories staged for the learner",
            fn=lambda: (q.qsize() if (q := queue_ref()) is not None
                        else 0.0))
        registry.gauge(
            "actor_pool/queue_capacity",
            "trajectory queue bound").set(self.queue.maxsize)
        pool_ref = weakref.ref(self)
        registry.gauge(
            "actor_pool/params_version",
            "newest published weight snapshot",
            fn=lambda: (p._params_version if (p := pool_ref()) is not None
                        else 0.0))
        self._frames_counter = registry.counter(
            "actor/agent_steps_total",
            "agent steps generated across all groups (x action repeats "
            "= env frames)")
        self._trajectories_counter = registry.counter(
            "actor/trajectories_total", "unrolls handed to the queue")
        self._restarts_counter = registry.counter(
            "actor/restarts_total",
            "actor-thread respawns after a transient failure (the "
            "per-actor detail rides the flight recorder's "
            "actor_restart events)")
        self._frames_per_trajectory = unroll_length * (
            env_groups[0].num_envs if env_groups else 0)

    # -- service-mode plumbing ---------------------------------------------

    def _service_request(self, params, rng, action, env_output, state):
        """VectorActor-facing step_fn: one group's request -> the shared
        batcher (params arg ignored; the consumer reads the newest
        snapshot at batch time, like the reference's variable reads)."""
        del params
        sample = (
            np.asarray(jax.random.key_data(rng), np.uint32),
            np.asarray(action),
            _to_numpy(env_output),
            np.asarray(state.c),
            np.asarray(state.h),
        )
        batcher = self._ensure_batcher(sample)
        out, c, h = batcher.compute(sample)
        return out, AgentState(c=c, h=h)

    def _ensure_batcher(self, example_sample):
        with self._batcher_lock:
            if self._batcher is None:
                from scalable_agent_tpu.runtime.batcher import (
                    bucket_ladder)
                from scalable_agent_tpu.runtime.native_batcher import (
                    NativeBatcher)

                example_result = self._service_compute(
                    map_structure(
                        lambda x: None if x is None else x[None],
                        example_sample), 1)
                example_result = map_structure(
                    lambda x: None if x is None else x[0], example_result)
                pad = bucket_ladder(self._service_max)
                self._batcher = NativeBatcher(
                    self._service_compute,
                    example_sample=example_sample,
                    example_result=example_result,
                    minimum_batch_size=1,
                    maximum_batch_size=self._service_max,
                    timeout_ms=self._service_timeout_ms,
                    pad_to_sizes=pad,
                )
            return self._batcher

    def _service_compute(self, batched, k):
        """Batcher consumer: k co-batched group requests -> one vmapped
        jitted device call under the newest params."""
        key_data, action, env_output, c, h = batched
        out, new_state = self._service_jit(
            self._get_params(), key_data, action, env_output,
            AgentState(c=c, h=h))
        out = _to_numpy(out)
        new_state = _to_numpy(new_state)
        return (out, new_state.c, new_state.h)

    # -- weight publication ------------------------------------------------

    def set_params(self, params, version: Optional[int] = None):
        """Publish a new weight snapshot for subsequent unrolls.

        The snapshot must be a real COPY when the learner's params already
        live solely on the inference device (a 1-device mesh): there
        ``device_put`` aliases the learner's buffers, and the learner's
        donated update (donate_argnums) would invalidate the actors'
        snapshot on the very next step ("Array has been deleted").
        ``snapshot_params_for_inference`` owns that re-placement.
        """
        params = snapshot_params_for_inference(params,
                                               self._inference_device)
        with self._params_lock:
            self._params = params
            self._params_version = (
                version if version is not None else self._params_version + 1)

    def _get_params(self):
        with self._params_lock:
            return self._params

    # -- run ---------------------------------------------------------------

    def _chaos_kill_worker(self, actor) -> None:
        """``worker_kill`` injection: SIGKILL one env worker process of
        this actor — MultiEnv's respawn machinery must absorb it."""
        envs_list = (getattr(actor, "envs_list", None)
                     or [getattr(actor, "_envs", None)])
        for envs in envs_list:
            procs = getattr(envs, "_procs", None)
            if not procs:
                continue
            proc = procs[0]
            if proc is not None and proc.is_alive():
                from scalable_agent_tpu.utils import log

                log.warning("chaos: killing env worker pid %d", proc.pid)
                proc.kill()
                return

    def _unroll_loop(self, actor: VectorActor):
        """The steady-state produce loop for one actor (runs until stop
        or an exception; the retry layer in _actor_loop owns both)."""
        from scalable_agent_tpu.runtime.faults import get_fault_injector

        recorder = get_flight_recorder()
        while not self._stop.is_set():
            # Re-read the global tracer each unroll: the driver may
            # enable tracing after this thread was born.
            tracer = get_tracer()
            watchdog = get_watchdog()
            watchdog.touch()
            injector = get_fault_injector()
            if injector.active:
                injector.maybe_raise("actor_raise")
                if injector.should_fire("worker_kill"):
                    self._chaos_kill_worker(actor)
            params = self._get_params()
            with tracer.span("actor/unroll", cat="actor"):
                result = actor.run_unroll(params)
            # Grouped (co-dispatch) actors emit one trajectory per
            # group per lockstep unroll.
            items = result if isinstance(result, list) else [result]
            recorder.record("unroll", actor.level_name or "actor",
                            {"trajectories": len(items)})
            thread_name = threading.current_thread().name
            birth_us = getattr(actor, "unroll_birth_us", None)
            for trajectory in items:
                # Provenance record born at the unroll's first env step,
                # bound to the trajectory object; shutdown can abandon
                # the put (publish_trajectory closes the record then).
                publish_trajectory(
                    self.queue, trajectory, self._stop,
                    actor_name=thread_name,
                    level_name=actor.level_name,
                    birth_us=birth_us,
                    frames=self._frames_per_trajectory,
                    frames_counter=self._frames_counter,
                    trajectories_counter=self._trajectories_counter)

    def _actor_loop(self, actor: VectorActor):
        """Retry shell around ``_unroll_loop``: the shared
        ``run_with_retry`` gives a failing actor thread
        ``max_restarts`` respawns within a sliding ``restart_window_s``
        (crash-loop detection — isolated faults days apart age out)
        with capped exponential backoff before its terminal exception
        is marshalled to the driver through the queue
        (docs/robustness.md)."""

        def deliver(exc):
            self._errors.append(exc)
            self.queue.put(exc)

        run_with_retry(
            lambda: self._unroll_loop(actor),
            stop=self._stop, deliver=deliver,
            reset=getattr(actor, "reset", None),
            max_restarts=self._max_restarts,
            backoff_s=self._restart_backoff_s,
            backoff_cap_s=self._restart_backoff_cap_s,
            window_s=self._restart_window_s,
            restarts_counter=self._restarts_counter)

    def start(self):
        if self._params is None:
            raise RuntimeError("set_params before start")
        for i, actor in enumerate(self._actors):
            # Stable names: watchdog heartbeats, flight-recorder events,
            # and trace thread tracks all key on the thread name.
            t = threading.Thread(
                target=self._actor_loop, args=(actor,), daemon=True,
                name=f"actor-{i}")
            t.start()
            self._threads.append(t)
        return self

    def get_trajectory(self, timeout: Optional[float] = None) -> ActorOutput:
        # Ledger hand-off inside: recovers the provenance record bound
        # to the object and makes it the consuming thread's CURRENT
        # record, so the transport/learner layers stamp the right one.
        return consume_trajectory(self.queue, timeout=timeout)

    def stop(self):
        self._stop.set()
        if self._inference_mode == "service":
            with self._batcher_lock:
                if self._batcher is not None:
                    # Cascades BatcherClosedError to any actor thread
                    # blocked awaiting a batch (reference: batcher.cc
                    # close semantics, :393-431).
                    self._batcher.close()
        for t in self._threads:
            t.join(timeout=10)
        for actor in self._actors:
            actor.close()

    def _all_envs(self):
        """Every MultiEnv behind every actor (grouped actors own
        several)."""
        out = []
        for actor in self._actors:
            out.extend(getattr(actor, "envs_list", None)
                       or [actor._envs])
        return out

    @property
    def num_envs(self) -> int:
        return sum(envs.num_envs for envs in self._all_envs())

    def episode_stats(self):
        """Merged completed-episode (return, length) ring buffers."""
        return merged_episode_stats(self._all_envs())

    def drain_level_stats(self):
        """Pop all level-attributed episodes completed since the last
        drain (shared implementation: ``drain_level_stats``)."""
        return drain_level_stats(self._all_envs())
