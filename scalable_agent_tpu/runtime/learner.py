"""The learner: one jitted, mesh-sharded IMPALA update step.

Functional parity with the reference's ``build_learner`` (reference:
experiment.py:346-427), re-designed for TPU:

- The whole update — target-policy unroll, V-trace, losses, RMSProp — is
  ONE jitted function over a ``('data', 'seq', 'model')`` mesh.
  Trajectory batches are sharded over ``data``; parameters are
  replicated; XLA's partitioner inserts the gradient all-reduce (psum
  over ICI) — and that all-reduce, with a few scalar metrics, is ALL
  that crosses devices, as long as every merge of the sharded batch
  axis keeps the shard index outermost (batch-major or shard-major,
  never ``[T, B] -> [T*B]`` time-major: parallel/mesh.py
  batch_sharding; the partitioner answers a time-major merge by
  gathering the batch and computing the torso on every device, and
  says nothing).  tests/test_data_parallel_unroll.py holds it.  The
  reference instead runs a single-GPU learner fed by a gRPC queue and
  places V-trace on the *CPU* because its sequential scan was slow on
  device (experiment.py:387-397) — here V-trace is an associative scan and
  stays on the TPU (ops/vtrace.py).

- The learning rate decays linearly to zero as a function of the
  environment-frame count (reference: experiment.py:409-420, where the
  global step literally counts env frames).  ``env_frames`` is carried as
  a float32 scalar in TrainState: float32 integer precision (~2^24) is
  exhausted at 16M, so frames are accumulated in units of
  ``frames_per_update`` at update granularity — exact for billions of
  frames — and the authoritative count also lives host-side.

- The time dimension (unroll T=100) is handled inside the model's
  ``lax.scan`` and V-trace's ``associative_scan``; an optional sequence-
  parallel mesh axis for very long unrolls hooks in at ops/vtrace.py.
"""

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from scalable_agent_tpu.models.agent import ImpalaAgent
from scalable_agent_tpu.obs import (
    get_flight_recorder,
    get_ledger,
    get_registry,
    get_tracer,
)
from scalable_agent_tpu.obs.device_telemetry import (
    DeviceTelemetry,
    TelemetryPublisher,
    fetch_merged,
    merge_init,
)
from scalable_agent_tpu.ops import distributions
from scalable_agent_tpu.ops import impact as impact_lib
from scalable_agent_tpu.ops import losses as losses_lib
from scalable_agent_tpu.ops import vtrace
from scalable_agent_tpu.parallel.mesh import (
    batch_shards,
    batch_sharding,
    model_parallel_shardings,
    replicated_sharding,
)
from scalable_agent_tpu.runtime.faults import get_fault_injector
from scalable_agent_tpu.runtime.transport import (
    broadcast_prefix,
    make_transport,
)
from scalable_agent_tpu.types import AgentOutput, AgentState, StepOutput


class Trajectory(NamedTuple):
    """Device-side trajectory batch (ActorOutput minus the level name —
    strings stay on the host).  (reference: experiment.py:98-100)

    agent_state: AgentState [B, H]; env_outputs: StepOutput [T+1, B, ...];
    agent_outputs: AgentOutput [T+1, B, ...].
    """

    agent_state: AgentState
    env_outputs: StepOutput
    agent_outputs: AgentOutput


class LearnerHyperparams(NamedTuple):
    """Loss/optimizer knobs, reference defaults.

    (reference: experiment.py:61-95)
    """

    entropy_cost: float = 0.00025
    baseline_cost: float = 0.5
    discounting: float = 0.99
    reward_clipping: str = "abs_one"  # abs_one | soft_asymmetric | none
    learning_rate: float = 0.00048
    total_environment_frames: float = 1e9
    rmsprop_decay: float = 0.99
    rmsprop_momentum: float = 0.0
    rmsprop_epsilon: float = 0.1
    clip_rho_threshold: float = 1.0
    clip_pg_rho_threshold: float = 1.0


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    env_frames: jax.Array  # f32 scalar, counts frames in exact multiples
    # Non-finite-guard state (docs/robustness.md): cumulative skipped
    # updates and the current consecutive-skip streak, carried ON DEVICE
    # so the verdict rides whatever metrics fetch the driver already
    # pays — no extra host sync per update.  f32 scalars (exact to 2^24
    # counts); they ride the checkpoint like env_frames so a resumed
    # run keeps its skip accounting.
    nonfinite_skips: jax.Array
    nonfinite_streak: jax.Array
    # IMPACT target network (ops/impact.py): a periodic hard copy of
    # ``params`` anchoring the clipped-target surrogate, refreshed
    # in-graph every ``target_update_interval`` fresh updates.  None
    # under ``--loss=vtrace`` (a None pytree node carries zero leaves,
    # so the default path's TrainState allocates nothing new and its
    # checkpoint bytes are unchanged); populated under
    # ``--loss=impact`` and carried through the checkpoint so a resumed
    # run keeps its anchor (runtime/checkpoint.py migrates checkpoints
    # from either generation across the loss modes).
    target_params: Any = None


# Per-field batch-axis positions: agent_state leaves are [B, ...], the
# [T+1, B, ...] subtrees carry the batch at axis 1.  The transport layer
# splits/joins the data-sharding axis here.
_TRAJ_BATCH_AXES = Trajectory(agent_state=0, env_outputs=1,
                              agent_outputs=1)

# Re-exported for callers that used the private helper here.
_broadcast_prefix = broadcast_prefix


def learner_telemetry_spec() -> DeviceTelemetry:
    """The learner's device-resident instrument set (obs/
    device_telemetry.py): update/skip counters, the last loss, and a
    log-bucketed grad-norm histogram — all accumulated INSIDE the
    jitted update in donated buffers (the non-finite-counter pattern
    generalized), fetched once per log interval."""
    return (
        DeviceTelemetry("learner")
        .counter("updates", "update steps executed on device")
        .counter("skipped", "updates the fused non-finite guard no-op'd")
        .gauge("loss", "total_loss of the newest accumulated update")
        .histogram(
            "grad_norm",
            (0.01, 0.1, 1.0, 10.0, 100.0, 1000.0, 10000.0),
            "global grad norm per update, log-ish buckets")
    )


# Per-layer-group telemetry buckets: the agent's param tree divides
# into the groups the agent declares (``agent.layer_groups`` /
# ``agent.layer_group(path)``).  The IMPALA agents': the conv torso
# ("convnet" + the optional instruction encoder), the recurrent core
# ("core"/lstm), and the linear heads ("policy_logits"/"baseline"),
# keyed on flax module names so a new head lands in "heads" and anything
# else defaults to the torso.
LAYER_GROUPS = ImpalaAgent.layer_groups

# Bucket edges for what a forward pass reports of itself
# (``agent.STATS``: shares, loads, ratios); the exact sum and count give
# the mean over every update between fetches at any resolution.
_STAT_EDGES = (0.01, 0.05, 0.125, 0.25, 0.5, 1.0, 2.0, 4.0, 16.0, 64.0,
               256.0, 1024.0)


def stat_histogram_name(stat: str) -> str:
    """``moe/expert_load_max_over_mean`` -> its ``devtel/learn``
    histogram's name."""
    return stat.replace("/", "_")

# Shared bucket edges for fraction-valued histograms ([0, 1] series:
# clip fractions, ESS, normalized entropy).
_FRACTION_EDGES = (0.01, 0.05, 0.1, 0.25, 0.5, 0.75, 0.9, 0.99)


def learning_telemetry_spec(loss: str = "vtrace",
                            groups=LAYER_GROUPS,
                            stats=(),
                            dead_units: bool = True) -> DeviceTelemetry:
    """The learning-dynamics instrument set (ISSUE 17): off-policy clip
    diagnostics, policy entropy/KL, value explained-variance, and
    per-layer-group optimizer health — all accumulated INSIDE the
    jitted update in the same donated devtel buffers as
    ``learner_telemetry_spec`` (merged via ``merge_init``), fetched in
    the one existing log-interval transfer.

    Gauges carry the newest update's value (what the health detectors
    and ``obs.watch`` read); histograms additionally aggregate across
    every update between fetches — in particular all K updates of an
    ``--updates_per_dispatch=K`` megaloop dispatch, where the metrics
    dict only surfaces the last update's scalars.
    """
    spec = DeviceTelemetry("learn")
    for name, help_text in (
        ("entropy_frac",
         "policy entropy / max entropy (1.0 = uniform; ~0 = collapsed)"),
        ("kl",
         "KL(behaviour || learner) — how far the learner has moved off "
         "the data-generating policy"),
        ("ess_frac",
         "effective sample size of the V-trace importance weights as a "
         "fraction of the batch (1.0 = on-policy)"),
        ("explained_variance",
         "1 - Var(vs - baseline)/Var(vs): how much of the value target "
         "the baseline explains (<=0 = diverging critic)"),
        ("rho_clip_fraction",
         "fraction of V-trace rhos cut by clip_rho_threshold"),
        ("cs_clip_fraction",
         "fraction of V-trace cs cut by the c-bar clip"),
        ("pg_rho_clip_fraction",
         "fraction of pg-rhos cut by clip_pg_rho_threshold"),
        ("log_rho_mean",
         "mean log importance ratio log(pi/mu) (0 = on-policy)"),
        ("log_rho_p95",
         "p95 log importance ratio — the off-policy tail"),
    ) + ((
        # only for an agent that names the module to read them from
        ("dead_torso_frac",
         "fraction of conv-torso output units at <=0 across the whole "
         "batch (dead ReLUs)"),
    ) if dead_units else ()):
        spec.gauge(name, help_text)
    for stat in stats:
        spec.histogram(stat_histogram_name(stat), _STAT_EDGES,
                       f"{stat} of each update's forward pass")
    for group in groups:
        spec.gauge(f"grad_norm_{group}",
                   f"gradient norm over the {group} param group")
        spec.gauge(f"param_norm_{group}",
                   f"param norm of the {group} param group")
        spec.gauge(f"update_ratio_{group}",
                   f"|lr-scaled update| / |param| for the {group} group "
                   "(healthy ~1e-4..1e-2)")
    if loss == "impact":
        # ISSUE 17 satellite: the IMPACT ratio series ride HISTOGRAMS
        # (not just the per-update metrics dict) so a megaloop dispatch
        # aggregates all K updates instead of surfacing only the last.
        spec.histogram(
            "impact_ratio",
            (0.5, 0.8, 0.9, 0.95, 1.0, 1.05, 1.1, 1.25, 2.0),
            "per-update mean IMPACT ratio pi_theta/pi_tgt (~1 = online "
            "net hugging its target anchor)")
        spec.histogram(
            "impact_clip_fraction", _FRACTION_EDGES,
            "per-update fraction of cells where the IMPACT clip bound "
            "was active")
        spec.gauge("impact_log_ratio_p95",
                   "p95 of log(pi_theta/pi_tgt) — online-to-target "
                   "drift tail")
        spec.gauge("impact_ess_frac",
                   "ESS fraction of the online-to-target importance "
                   "weights")
    return spec


def _module_filter(module: str):
    """flax capture_intermediates filter: only the output of the module
    the agent names for the dead-unit reading (the conv torso; not the
    segment a torso rematerializes, which flax calls as a method of the
    same module)."""
    def keep(mdl, method_name) -> bool:
        return mdl.name == module and method_name == "__call__"

    return keep


def _dead_unit_fraction(captured, module: str) -> jax.Array:
    """Fraction of the module's output units that are <= 0 for EVERY
    element of the [T*B] batch — dead ReLUs the optimizer can no longer
    reach."""
    out = captured["intermediates"][module]["__call__"][0]
    out = jax.lax.stop_gradient(jnp.asarray(out, jnp.float32))
    return jnp.mean(jnp.all(out <= 0.0, axis=0).astype(jnp.float32))


def _make_optimizer(hp: LearnerHyperparams) -> optax.GradientTransformation:
    # lr=1.0 here; the decayed lr is applied inside the update so it can be
    # keyed on env frames rather than update count (resume-exact, reference
    # experiment.py:409-415).
    #
    # initial_scale=1.0: tf.train.RMSPropOptimizer initializes the
    # mean-square accumulator to ONE (optax defaults to zero), and with
    # eps=0.1 that difference makes the first updates far larger than the
    # reference's — early training dynamics would diverge.
    #
    # Momentum-ordering note: with rmsprop_momentum != 0, the momentum
    # trace here accumulates un-lr-scaled steps (the decayed lr multiplies
    # the final update), whereas TF accumulates lr-scaled steps.  The two
    # differ only while the lr changes between steps; the reference default
    # is momentum=0, where both reduce to the same update.
    return optax.rmsprop(
        learning_rate=1.0,
        decay=hp.rmsprop_decay,
        eps=hp.rmsprop_epsilon,
        initial_scale=1.0,
        momentum=(hp.rmsprop_momentum
                  if hp.rmsprop_momentum else None),
    )


class Learner:
    """Owns the jitted sharded update.  Construct once per training run.

    ``frames_per_update`` = batch_size * unroll_length *
    num_action_repeats (reference: experiment.py:417-420).
    """

    def __init__(
        self,
        agent: ImpalaAgent,
        hp: LearnerHyperparams,
        mesh,
        frames_per_update: int,
        scan_impl: str = "auto",
        transport: str = "per_leaf",
        finite_guard: bool = True,
        device_telemetry: bool = True,
        learn_telemetry: bool = True,
        loss: str = "vtrace",
        target_update_interval: int = 100,
        impact_clip_epsilon: float = 0.3,
        fused_forward: bool = True,
        on_policy: bool = False,
    ):
        # The unroll's [T, B] -> [T*B] merge must keep the mesh's shard
        # index outermost, or the partitioner replicates the torso on
        # every device (parallel/mesh.py batch_sharding has the rule).
        # The agent is built before any mesh exists; the mesh in hand
        # says how the batch is cut, so the learner's copy of the agent
        # follows it.  Parameters do not depend on it.
        shards = batch_shards(mesh.shape)
        if agent.batch_shards != shards:
            agent = agent.clone(batch_shards=shards)
        self._agent = agent
        # Are fresh trajectories sampled under the very parameters the
        # update evaluates (the fused step, nothing replayed)?  Read
        # where the trajectory keeps the taken action's log-probability
        # (ops/vtrace.py from_behaviour_log_probs has why).
        self._on_policy = bool(on_policy)
        # What the agent's forward pass reports of itself (the expert
        # layers' load): sown into ``agent.stats_collection``, read out
        # by ``_forward`` and carried in the update's metrics.
        self._forward_stats = (tuple(agent.STATS)
                               if agent.stats_collection else ())
        # Fused single-forward loss (default): ONE whole-trajectory
        # unroll (Learner._forward) produces both the
        # behaviour-comparison quantities V-trace consumes (target
        # logits, values, bootstrap) and the differentiated loss
        # outputs.  ``False`` compiles the two-pass REFERENCE shape —
        # a separate stop-gradiented comparison unroll behind an
        # optimization barrier (so XLA cannot CSE it back into one) —
        # kept as bench_kernel_war's measurable baseline, not for
        # production.  Both compile to the same loss value and
        # gradient: V-trace stop-gradients every input (ops/vtrace.py).
        self._fused_forward = bool(fused_forward)
        self._hp = hp
        self._mesh = mesh
        self._frames_per_update = float(frames_per_update)
        # Loss surrogate: "vtrace" (the seed path, bit-for-bit) or
        # "impact" (clipped-target surrogate, ops/impact.py — the
        # replay-tolerant objective ROADMAP item 2 calls for).
        if loss not in ("vtrace", "impact"):
            raise ValueError(
                f"unknown loss {loss!r} (vtrace | impact)")
        if target_update_interval < 1:
            raise ValueError(
                f"target_update_interval must be >= 1, got "
                f"{target_update_interval}")
        self._loss_name = loss
        self._target_update_interval = float(target_update_interval)
        self._impact_clip_epsilon = float(impact_clip_epsilon)
        # The non-finite guard is fused into the jitted update (a
        # tree-wide isfinite reduction + per-leaf selects); ``False``
        # exists for bench_resilience's baseline measurement, not for
        # production runs.
        self._finite_guard = bool(finite_guard)
        if scan_impl == "auto":
            # The associative scan is the auto choice everywhere: at
            # production shapes V-trace is ~2-5 us on-chip either way
            # (r04/r05 — earlier "1.23x pallas win" numbers were
            # artifacts of independent-dispatch timing), and only the
            # associative form shards over data/seq axes.  Explicit
            # "pallas" still forces the fused kernel (ops/
            # vtrace_pallas.py).  A seq axis > 1 auto-selects the
            # time-sharded recurrence (parallel/sequence.py — SURVEY
            # §5.7 sequence parallelism).
            if mesh.shape.get("seq", 1) > 1:
                scan_impl = "time_sharded"
            else:
                scan_impl = "associative"
        if scan_impl == "time_sharded" and mesh.shape.get("seq", 1) == 1:
            # Degenerate seq axis: the shard_map would be pure overhead.
            scan_impl = "associative"
        self._scan_impl = scan_impl
        if hp.rmsprop_momentum:
            import warnings

            warnings.warn(
                "rmsprop_momentum != 0: the momentum trace accumulates "
                "un-lr-scaled steps (TF accumulates lr-scaled steps), so "
                "updates diverge from the reference while the decayed lr "
                "changes between steps (see _make_optimizer note)",
                stacklevel=2)
        self._tx = _make_optimizer(hp)

        replicated = replicated_sharding(mesh)
        batch_b = batch_sharding(mesh, batch_axis_index=0)  # [B, ...]
        batch_tb = batch_sharding(mesh, batch_axis_index=1)  # [T+1, B, ...]
        # Prefix pytree: one sharding per Trajectory field covers the whole
        # subtree beneath it.
        traj_shardings = Trajectory(
            agent_state=batch_b,
            env_outputs=batch_tb,
            agent_outputs=batch_tb,
        )
        # Computation follows data: ``init``/``place_state`` and
        # ``put_trajectory`` commit arguments to their mesh shardings
        # (params/optimizer tensor-parallel over 'model', batch over
        # 'data'), and jit compiles the SPMD program from the argument
        # placements — no in_shardings pinning, so the same Learner
        # serves any (data, model) mesh shape.  The device-telemetry
        # pytree (obs/device_telemetry.py) rides as a third DONATED
        # argument: accumulation is in-place on device, and the host
        # only touches it at the log-interval fetch.
        self._update = jax.jit(self._update_impl, donate_argnums=(0, 2))
        # Replayed-batch variant: ``fresh=False`` is a PYTHON branch in
        # _update_impl (env_frames held, no target-net sync), so the
        # two jits are two specializations; the fresh one's jaxpr is
        # byte-identical to the pre-replay program.
        import functools

        self._update_replayed = jax.jit(
            functools.partial(self._update_impl, fresh=False),
            donate_argnums=(0, 2))
        self._replicated = replicated
        self._devtel_enabled = bool(device_telemetry)
        self._devtel_spec = (learner_telemetry_spec()
                             if self._devtel_enabled
                             else DeviceTelemetry("learner"))
        # Learning-dynamics plane (ISSUE 17): a second spec in its own
        # "learn" namespace, merged into the SAME donated pytree —
        # same buffers, same single log-interval fetch, zero new syncs.
        self._learn_enabled = bool(learn_telemetry) and self._devtel_enabled
        self._learn_spec = (learning_telemetry_spec(
                                loss, agent.layer_groups,
                                self._forward_stats,
                                agent.dead_unit_module is not None)
                            if self._learn_enabled
                            else DeviceTelemetry("learn"))
        # Normalizer for entropy_frac: the distribution's max entropy
        # (sum of log cell sizes — the joint entropy of the uniform
        # policy).
        self._max_entropy = max(
            float(sum(np.log(s) for s in agent.dist_spec.sizes)), 1e-6)
        self._devtel = self._place_replicated(
            merge_init(self.devtel_specs))
        self._devtel_publisher = (
            TelemetryPublisher(self.devtel_specs)
            if self._devtel_enabled else None)
        self._traj_shardings = traj_shardings
        # Host->device trajectory placement strategy: "per_leaf" (one
        # device_put per leaf — the seed path, bit-for-bit preserved) or
        # "packed" (single-copy H2D + jitted on-device unpack,
        # runtime/transport.py).
        self._transport = make_transport(
            transport, mesh, traj_shardings, _TRAJ_BATCH_AXES)
        registry = get_registry()
        self._h_put = registry.histogram(
            "learner/put_trajectory_s",
            "host->device trajectory placement seconds")
        self._updates_counter = registry.counter(
            "learner/updates_total", "update steps dispatched")
        self._frames_counter = registry.counter(
            "learner/env_frames_total",
            "env frames consumed by dispatched updates")
        self._replayed_counter = registry.counter(
            "learner/replayed_updates_total",
            "update steps dispatched on REPLAYED batches (their frames "
            "were already counted at fresh consumption)")
        if self._loss_name == "impact":
            # The anchor cadence, published so obs.report can convert
            # it into a staleness budget (interval / update rate) and
            # judge the replayed-staleness p95 against the clip's
            # useful range.
            registry.gauge(
                "replay/target_update_interval",
                "fresh updates between IMPACT target-network hard "
                "copies (the clipped-target surrogate's anchor "
                "cadence)").set(self._target_update_interval)

    @property
    def loss_name(self) -> str:
        """"vtrace" or "impact" — which surrogate the update compiles."""
        return self._loss_name

    @property
    def mesh(self):
        """The device mesh this learner's update is sharded over."""
        return self._mesh

    # -- device telemetry --------------------------------------------------

    def _place_replicated(self, tree):
        """Commit a small host pytree replicated onto the mesh — the
        multi-process path builds from local data (the place_state
        discipline: device_put onto a non-addressable sharding runs a
        hidden value-dependent collective)."""
        if jax.process_count() <= 1:
            return jax.device_put(tree, self._replicated)

        def _place(x):
            host = np.asarray(x)
            return jax.make_array_from_callback(
                host.shape, self._replicated,
                lambda idx, _h=host: _h[idx])

        return jax.tree_util.tree_map(_place, tree)

    @property
    def devtel_spec(self) -> DeviceTelemetry:
        """The learner's device-telemetry spec (empty when disabled)."""
        return self._devtel_spec

    @property
    def learn_spec(self) -> DeviceTelemetry:
        """The learning-dynamics spec (``devtel/learn/*``; empty when
        disabled)."""
        return self._learn_spec

    @property
    def devtel_specs(self):
        """Every non-empty spec riding this learner's donated telemetry
        pytree (learner counters + the learning-dynamics plane)."""
        return [spec for spec in (self._devtel_spec, self._learn_spec)
                if not spec.empty]

    @property
    def device_telemetry(self):
        """The CURRENT device-resident telemetry buffers.  Callers
        driving ``_update`` directly (bench AOT path, in-graph trainer)
        thread this pytree themselves; everyone else just calls
        ``update()``/``publish_device_telemetry()``."""
        return self._devtel

    def adopt_device_telemetry(self, devtel) -> None:
        """Rebind the telemetry buffers.  Callers driving the RAW
        jitted/AOT update themselves (bench's compiled wrapper, the
        in-graph trainer) receive the donated-and-returned pytree from
        each call; handing it back here keeps ``fetch_device_
        telemetry`` reading live buffers instead of donated husks."""
        self._devtel = devtel

    def lower_update(self, state: "TrainState", trajectory: "Trajectory",
                     devtel=None):
        """``jax.jit(...).lower`` of the update at these shapes — the
        one sanctioned way to lower it (cost analysis for the MFU
        gauge, HLO text for the kernel ledger) now that the jitted
        signature carries the telemetry buffers.  ``devtel`` stands in
        for the live buffers when lowering against ABSTRACT arguments
        (``jax.ShapeDtypeStruct`` s with shardings on devices this
        process does not hold — the compile-only TPU topology probe,
        tests/test_chip_bringup.py)."""
        return self._update.lower(
            state, trajectory, self._devtel if devtel is None else devtel)

    def fetch_device_telemetry(self) -> Optional[Dict[str, np.ndarray]]:
        """Materialize the telemetry on the host — the ONE device→host
        sync the telemetry ever causes, sized a few hundred bytes; the
        driver calls it at log-interval cadence.  None when disabled."""
        if not self._devtel_enabled:
            return None
        return fetch_merged(self.devtel_specs, self._devtel)

    def publish_device_telemetry(self) -> Optional[Dict[str, np.ndarray]]:
        """Fetch + fold into the metrics registry (``devtel/learner/*``
        names ride the normal prom/report/aggregate path)."""
        fetched = self.fetch_device_telemetry()
        if fetched is not None:
            self._devtel_publisher.publish(fetched)
        return fetched

    # -- state ------------------------------------------------------------

    def init(self, rng: jax.Array, example_trajectory: Trajectory,
             env_frames: float = 0.0) -> TrainState:
        """Initialize params/optimizer, replicated over the mesh."""
        example = jax.tree_util.tree_map(
            lambda x: x if x is None else jnp.asarray(x),
            example_trajectory, is_leaf=lambda x: x is None)
        agent = self._agent
        # The IMPALA agents initialize op by op (a hundred tiny
        # programs, ~6 s on a TPU); an agent of hundreds of ops and
        # parameters asks for ONE program, of which the compiler keeps
        # the initializers and drops the forward pass.
        init = (jax.jit(agent.init) if agent.init_in_one_program
                else agent.init)
        actions, env_outputs = (example.agent_outputs.action,
                                example.env_outputs)
        if agent.init_steps:
            # an agent whose parameters do not depend on the unroll's
            # length is initialized through its first steps alone
            actions, env_outputs = jax.tree_util.tree_map(
                lambda x: x[:agent.init_steps], (actions, env_outputs))
        params = init(rng, actions, env_outputs, example.agent_state)
        if agent.stats_collection:
            # what the forward pass sows of itself is no parameter
            params = {name: tree for name, tree in params.items()
                      if name != agent.stats_collection}
        opt_state = self._tx.init(params)
        state = TrainState(
            params=params,
            opt_state=opt_state,
            env_frames=jnp.float32(env_frames),
            nonfinite_skips=jnp.float32(0.0),
            nonfinite_streak=jnp.float32(0.0),
            # IMPACT: the target net starts as a DISTINCT copy of the
            # online params (jnp.array copies) — aliased buffers would
            # make the update's pytree donation try to donate the same
            # buffer twice.
            target_params=(jax.tree_util.tree_map(jnp.array, params)
                           if self._loss_name == "impact" else None),
        )
        return self.place_state(state)

    def state_shardings(self, state: TrainState) -> TrainState:
        """Sharding pytree for a TrainState: params + optimizer state
        tensor-parallel over 'model' (replicated when model=1), frame
        counter replicated."""
        return TrainState(
            params=model_parallel_shardings(self._mesh, state.params),
            opt_state=model_parallel_shardings(
                self._mesh, state.opt_state),
            env_frames=self._replicated,
            nonfinite_skips=self._replicated,
            nonfinite_streak=self._replicated,
            target_params=(
                None if state.target_params is None
                else model_parallel_shardings(
                    self._mesh, state.target_params)),
        )

    def place_state(self, state: TrainState) -> TrainState:
        """Commit a (host or device) TrainState onto the mesh — also the
        restore path after checkpoint load.

        Multi-process placement builds each global array from
        process-local data (``make_array_from_callback``) instead of
        ``jax.device_put``: device_put onto a non-addressable sharding
        runs a hidden per-leaf ``multihost_utils.assert_equal``
        collective inside jax whose fire-or-skip decision depends on
        each leaf's commitment state — the one value-dependent
        collective sequence in the whole setup path, and gloo (the CPU
        rig's transport) aborts the entire fleet on any cross-process
        divergence (pair.cc "op.preamble.length <= op.nbytes").  The
        callers already guarantee process-identical values (init: same
        seed; restore/rollback: the primary's state arrives by explicit
        broadcast), so the local build is also strictly cheaper: no
        params-sized network broadcast per init/restore."""
        if self._loss_name == "impact" and state.target_params is None:
            # Checkpoint migration (docs/robustness.md): a pre-IMPACT
            # (or --loss=vtrace) checkpoint restored into an impact run
            # initializes the target net FROM the online params — the
            # host-level copy below lands as distinct device buffers,
            # keeping the update's donation aliasing-free.  Runs AFTER
            # restore()'s manifest verification, which checked the
            # un-widened tree.
            host_params = jax.tree_util.tree_map(
                np.asarray, state.params)
            state = state._replace(
                target_params=jax.tree_util.tree_map(
                    np.array, host_params))
        shardings = self.state_shardings(state)
        if jax.process_count() <= 1:
            return jax.device_put(state, shardings)

        def _place(x, s):
            host = np.asarray(x)
            return jax.make_array_from_callback(
                host.shape, s, lambda idx, _h=host: _h[idx])

        return jax.tree_util.tree_map(_place, state, shardings)

    def put_trajectory(self, trajectory: Trajectory) -> Trajectory:
        """Host batch -> device, sharded over the data axis.

        Multi-process (multi-host): each process holds its LOCAL batch
        shard; the global array is assembled from per-process data so
        the data axis spans hosts (DCN) exactly like the reference's
        actors feeding one learner queue over gRPC
        (reference: experiment.py:531,556-562).  The fleet guard
        (runtime/fleet.py) bounds + attributes the assembly when a peer
        is lost under it — disabled/single-process it is one no-op
        call."""
        from scalable_agent_tpu.runtime.fleet import get_fleet

        with get_tracer().span("learner/put_trajectory", cat="h2d"), \
                self._h_put.time(), \
                get_fleet().collective("put_trajectory"):
            result = self._transport.put(trajectory)
        # Ledger stage boundary: device placement complete for the
        # calling thread's current trajectory record (the packed path
        # additionally stamped pack/upload/unpack inside put()).
        get_ledger().stamp_current("put_done")
        get_flight_recorder().record("queue", "put_trajectory")
        return result

    # -- update -----------------------------------------------------------

    def _forward(self, params, trajectory: Trajectory, capture=False,
                 handed=None):
        """The ONE whole-trajectory unroll of the update (reference:
        experiment.py:358-365).  Every loss quantity — the
        behaviour-comparison logits V-trace consumes AND the
        differentiated policy/value outputs — derives from this single
        apply; tests/test_learner_fused.py counts the lowered convs to
        pin it.  ``capture=True`` additionally captures the torso
        output (flax capture_intermediates) for the dead-unit gauge —
        still no second forward.  Returns ``((logits [T+1,B,L] f32,
        baselines [T+1,B] f32), observed)``: what the pass showed
        besides its outputs, as metrics — ``dead_torso_frac``, or, for an
        agent that names no such module, its forward pass's own numbers
        (``agent.STATS``); empty without ``capture``.  ``handed`` is
        ``_update_impl``'s, for the agent (its ``handover_collection``)."""
        agent = self._agent
        module = agent.dead_unit_module
        args = (params, trajectory.agent_outputs.action,
                trajectory.env_outputs, trajectory.agent_state)
        kwargs = {} if handed is None else {"handed": handed}
        if capture and module is not None:
            (out, _), captured = agent.apply(
                *args, capture_intermediates=_module_filter(module),
                mutable=["intermediates"], **kwargs)
            with jax.named_scope("telemetry"):
                return out, {"dead_torso_frac": _dead_unit_fraction(
                    captured, module)}
        if capture and self._forward_stats:
            # no module's dead units to read, but the pass's own numbers
            (out, _), sown = agent.apply(
                *args, mutable=[agent.stats_collection], **kwargs)
            stats = sown.get(agent.stats_collection, {})
            return out, {name: jax.lax.stop_gradient(stats[name])
                         for name in self._forward_stats}
        out, _ = agent.apply(*args, **kwargs)
        return out, {}

    def _comparison_forward(self, params, trajectory: Trajectory):
        """The UNFUSED (``fused_forward=False``) reference: a separate
        stop-gradiented unroll for the comparison quantities V-trace
        reads.  The optimization barrier keeps XLA from CSE-ing this
        pass back into the differentiated one (the two forwards are
        value-identical by construction, so without the barrier the
        'double forward' baseline would silently measure the fused
        program).  Exists to keep the single-vs-double-forward delta
        measurable (bench_kernel_war); production always fuses.
        ``stop_gradient`` BEFORE the barrier: optimization_barrier has
        no differentiation rule, and the comparison pass never needs
        one (its outputs are stop-gradiented anyway); stop_gradient is
        identity in lowered HLO, so the anti-CSE barrier survives."""
        barrier_params = jax.lax.optimization_barrier(
            jax.lax.stop_gradient(params))
        (logits, baselines), _ = self._forward(barrier_params, trajectory)
        return (jax.lax.stop_gradient(logits),
                jax.lax.stop_gradient(baselines))

    def _loss(self, params, trajectory: Trajectory, target_params=None,
              handed=None):
        """Dispatch on the construction-time surrogate choice (a Python
        branch: each jit specialization compiles exactly one).
        ``handed`` (``_update_impl``'s) goes to the ONE forward whose
        parameters are the ones that acted: the differentiated one."""
        if self._loss_name == "impact":
            return self._loss_impact(params, trajectory, target_params,
                                     handed)
        return self._loss_vtrace(params, trajectory, handed)

    def _loss_vtrace(self, params, trajectory: Trajectory, handed=None):
        hp = self._hp
        (target_logits, baselines), observed = self._forward(
            params, trajectory, capture=self._learn_enabled, handed=handed)
        if self._fused_forward:
            comparison_logits, comparison_baselines = (
                target_logits, baselines)
        else:
            comparison_logits, comparison_baselines = (
                self._comparison_forward(params, trajectory))
        # ``vtrace_loss``: what the loss computes outside the flax
        # modules (a scope name the benchmark's scope reader files
        # under update.loss_heads; metadata only).
        with jax.named_scope("vtrace_loss"):
            # The last baseline is the bootstrap; then drop the last
            # target output and the first behaviour/env entry
            # (reference: experiment.py:368-375 — "use last baseline
            # value for bootstrapping").
            bootstrap_value = comparison_baselines[-1]
            behaviour = jax.tree_util.tree_map(
                lambda t: t[1:], trajectory.agent_outputs)
            env_outputs = jax.tree_util.tree_map(
                lambda t: t[1:], trajectory.env_outputs)
            target_logits = target_logits[:-1]
            baselines = baselines[:-1]
            comparison_logits = comparison_logits[:-1]
            comparison_baselines = comparison_baselines[:-1]

            rewards = losses_lib.clip_rewards(
                env_outputs.reward, hp.reward_clipping)
            discounts = jnp.where(
                env_outputs.done, 0.0, hp.discounting).astype(jnp.float32)

            dist_spec = self._agent.dist_spec
            # V-trace reads the COMPARISON quantities (identical
            # tensors in the fused path; V-trace stop-gradients
            # internally, so the unfused reference matches it
            # bit-for-bit)...
            if distributions.stores_log_prob(dist_spec):
                # One large categorical: the trajectory kept the taken
                # action's behaviour log-probability, not the logits.
                vt = vtrace.from_behaviour_log_probs(
                    behaviour.policy_logits[..., 0], comparison_logits,
                    behaviour.action, discounts, rewards,
                    comparison_baselines, bootstrap_value,
                    clip_rho_threshold=hp.clip_rho_threshold,
                    clip_pg_rho_threshold=hp.clip_pg_rho_threshold,
                    scan_impl=self._scan_impl,
                    on_policy=self._on_policy)
            else:
                vt = vtrace.from_logits(
                    behaviour_policy_logits=behaviour.policy_logits,
                    target_policy_logits=comparison_logits,
                    actions=behaviour.action,
                    discounts=discounts,
                    rewards=rewards,
                    values=comparison_baselines,
                    bootstrap_value=bootstrap_value,
                    clip_rho_threshold=hp.clip_rho_threshold,
                    clip_pg_rho_threshold=hp.clip_pg_rho_threshold,
                    scan_impl=self._scan_impl,
                    dist_spec=dist_spec,
                    mesh=(self._mesh if self._scan_impl == "time_sharded"
                          else None),
                )

            # ...while the DIFFERENTIATED outputs feed the loss terms.
            pg_loss = losses_lib.compute_policy_gradient_loss(
                target_logits, behaviour.action, vt.pg_advantages,
                dist_spec=dist_spec)
            baseline_loss = losses_lib.compute_baseline_loss(
                vt.vs - baselines)
            entropy_loss = losses_lib.compute_entropy_loss(
                target_logits, dist_spec=dist_spec)
            total = (pg_loss + hp.baseline_cost * baseline_loss
                     + hp.entropy_cost * entropy_loss)
        metrics = {
            "total_loss": total,
            "policy_gradient_loss": pg_loss,
            "baseline_loss": baseline_loss,
            "entropy_loss": entropy_loss,
        }
        if self._learn_enabled:
            metrics.update(self._learning_metrics(
                vt, behaviour.policy_logits, target_logits, baselines,
                dist_spec, observed))
        return total, metrics

    def _loss_impact(self, params, trajectory: Trajectory, target_params,
                     handed=None):
        """IMPACT clipped-target surrogate (ops/impact.py): V-trace
        advantages computed with the TARGET network as the target
        policy (so the β = min(c̄, π_tgt/μ) behaviour→target correction
        is V-trace's clipped pg-rho), then the PPO-shaped ratio clip of
        π_θ against π_tgt.  Baseline/entropy terms keep the vtrace
        branch's shape so the cost hyperparameters transfer."""
        hp = self._hp
        # ONE online unroll (capture feeds the dead-unit gauge — the
        # params being optimized).
        (online_logits, baselines), observed = self._forward(
            params, trajectory, capture=self._learn_enabled, handed=handed)
        if self._fused_forward:
            comparison_baselines = baselines
        else:
            _, comparison_baselines = self._comparison_forward(
                params, trajectory)
        # Second (TARGET-net) unroll: the staleness anchor.  This one
        # is irreducible — different params — and is the price of
        # tolerating arbitrarily stale behaviour data; the fused-
        # forward contract is about the ONLINE net only (and so is
        # ``handed``: nothing acted under the target's parameters).
        (anchor_logits, _), _ = self._forward(target_params, trajectory)
        with jax.named_scope("vtrace_loss"):  # as in _loss_vtrace
            bootstrap_value = comparison_baselines[-1]
            behaviour = jax.tree_util.tree_map(
                lambda t: t[1:], trajectory.agent_outputs)
            env_outputs = jax.tree_util.tree_map(
                lambda t: t[1:], trajectory.env_outputs)
            online_logits = online_logits[:-1]
            anchor_logits = anchor_logits[:-1]
            baselines = baselines[:-1]
            comparison_baselines = comparison_baselines[:-1]

            rewards = losses_lib.clip_rewards(
                env_outputs.reward, hp.reward_clipping)
            discounts = jnp.where(
                env_outputs.done, 0.0, hp.discounting).astype(jnp.float32)

            dist_spec = self._agent.dist_spec
            vt = vtrace.from_logits(
                behaviour_policy_logits=behaviour.policy_logits,
                target_policy_logits=anchor_logits,
                actions=behaviour.action,
                discounts=discounts,
                rewards=rewards,
                values=comparison_baselines,
                bootstrap_value=bootstrap_value,
                clip_rho_threshold=hp.clip_rho_threshold,
                clip_pg_rho_threshold=hp.clip_pg_rho_threshold,
                scan_impl=self._scan_impl,
                dist_spec=dist_spec,
                mesh=(self._mesh if self._scan_impl == "time_sharded"
                      else None),
            )

            surrogate = impact_lib.surrogate_from_logits(
                online_logits, anchor_logits, behaviour.action,
                vt.pg_advantages,
                clip_epsilon=self._impact_clip_epsilon,
                dist_spec=dist_spec)
            baseline_loss = losses_lib.compute_baseline_loss(
                vt.vs - baselines)
            entropy_loss = losses_lib.compute_entropy_loss(
                online_logits, dist_spec=dist_spec)
            total = (surrogate.loss + hp.baseline_cost * baseline_loss
                     + hp.entropy_cost * entropy_loss)
        metrics = {
            "total_loss": total,
            "policy_gradient_loss": surrogate.loss,
            "baseline_loss": baseline_loss,
            "entropy_loss": entropy_loss,
            "impact_ratio_mean": surrogate.ratio_mean,
            "impact_clip_fraction": surrogate.clip_fraction,
        }
        if self._learn_enabled:
            metrics.update(self._learning_metrics(
                vt, behaviour.policy_logits, online_logits, baselines,
                dist_spec, observed))
            metrics["impact_log_ratio_mean"] = surrogate.log_ratio_mean
            metrics["impact_log_ratio_p95"] = surrogate.log_ratio_p95
            metrics["impact_ess_frac"] = surrogate.ess_frac
        return total, metrics

    def _learning_metrics(self, vt, behaviour_logits, online_logits,
                          baselines, dist_spec, observed
                          ) -> Dict[str, jax.Array]:
        """The learning-dynamics scalars (ISSUE 17): V-trace clip/ESS
        diagnostics, policy entropy (absolute + normalized),
        behaviour→learner KL, value explained-variance, dead torso
        units.  All stop-gradiented — pure observation, the loss value
        and its gradient are bit-identical with the plane on or off."""
        sg = jax.lax.stop_gradient
        diag = vt.diagnostics
        with jax.named_scope("telemetry"):
            online = sg(online_logits)
            entropy = jnp.mean(distributions.entropy(online, dist_spec))
            if distributions.stores_log_prob(dist_spec):
                # No behaviour logits were kept: the estimate of
                # KL(behaviour || learner) over the actions taken.
                kl = -jnp.mean(sg(vt.log_rhos))
            else:
                kl = jnp.mean(distributions.kl_divergence(
                    sg(behaviour_logits), online, dist_spec))
            vs = sg(vt.vs)
            explained_variance = 1.0 - (
                jnp.var(vs - sg(baselines))
                / jnp.maximum(jnp.var(vs), jnp.float32(1e-8)))
            entropy_frac = entropy / jnp.float32(self._max_entropy)
        return {
            "policy_entropy": entropy,
            "entropy_frac": entropy_frac,
            "behaviour_kl": kl,
            "explained_variance": explained_variance,
            "rho_clip_fraction": diag.rho_clip_fraction,
            "cs_clip_fraction": diag.cs_clip_fraction,
            "pg_rho_clip_fraction": diag.pg_rho_clip_fraction,
            "log_rho_mean": diag.log_rho_mean,
            "log_rho_p95": diag.log_rho_p95,
            "ess_frac": diag.ess_frac,
            **observed,
        }

    def _update_impl(self, state: TrainState, trajectory: Trajectory,
                     devtel: Dict, fresh: bool = True, handed=None
                     ) -> Tuple[TrainState, Dict, Dict[str, jax.Array]]:
        """One update.  ``devtel`` is the device-telemetry pytree
        (donated; may carry other specs' leaves — e.g. the in-graph
        trainer's env instruments — which pass through untouched).
        ``fresh`` is a PYTHON (specialization-time) flag: a replayed
        batch's update holds env_frames (the frames were counted at
        fresh consumption) and skips the target-net sync schedule.
        ``handed`` is what the acting steps that made ``trajectory``
        sowed into the agent's ``handover_collection``, leaves
        ``[T+1, B, ...]`` — only from a caller whose every acting step
        ran under ``state.params`` themselves, which the fused loop's
        did (runtime/ingraph.py) and a replayed batch's, a host actor's
        or a restored run's did not.
        Returns ``(new_state, new_devtel, metrics)``."""
        (_, metrics), grads = jax.value_and_grad(
            self._loss, has_aux=True)(
                state.params, trajectory, state.target_params, handed)

        # Linear decay to 0 over total frames (reference:
        # experiment.py:409-412 polynomial_decay power=1).
        frames = state.env_frames
        lr = self._hp.learning_rate * jnp.maximum(
            0.0, 1.0 - frames / self._hp.total_environment_frames)

        # Scope names for the benchmark's scope reader (metadata only):
        # ``optimizer`` is the step the update takes, the finite guard's
        # select included; ``telemetry`` is whatever the step computes
        # that only the obs plane reads.
        with jax.named_scope("optimizer"):
            updates, opt_state = self._tx.update(
                grads, state.opt_state, state.params)
            updates = jax.tree_util.tree_map(lambda u: u * lr, updates)
            params = optax.apply_updates(state.params, updates)

        metrics = dict(metrics)
        metrics["learning_rate"] = lr
        with jax.named_scope("telemetry"):
            metrics["grad_norm"] = optax.global_norm(grads)

        skips, streak = state.nonfinite_skips, state.nonfinite_streak
        if self._finite_guard:
            # All-finite verdict over loss + every gradient leaf, fused
            # into the update program (no host sync; the select below
            # makes a non-finite step a no-op on params/opt_state while
            # env_frames still advances — the batch WAS consumed, and
            # the driver's host-side frame accounting increments
            # unconditionally, so the two counts stay exact).
            with jax.named_scope("optimizer"):
                finite = jnp.isfinite(metrics["total_loss"])
                for leaf in jax.tree_util.tree_leaves(grads):
                    finite = jnp.logical_and(
                        finite, jnp.all(jnp.isfinite(leaf)))

                def keep(new, old):
                    return jnp.where(finite, new, old)

                params = jax.tree_util.tree_map(keep, params, state.params)
                opt_state = jax.tree_util.tree_map(
                    keep, opt_state, state.opt_state)
            skipped = 1.0 - finite.astype(jnp.float32)
            skips = skips + skipped
            streak = jnp.where(finite, 0.0, streak + 1.0)
            # The verdict rides the existing metrics dict: cumulative +
            # streak counters mean NO skip is lost even when the driver
            # only materializes metrics every few updates (in-flight
            # window) and only fetches them at log time.
            metrics["update_skipped"] = skipped
            metrics["nonfinite_skips"] = skips
            metrics["nonfinite_streak"] = streak

        target_params = state.target_params
        if self._loss_name == "impact" and fresh:
            # Periodic hard copy, fused into the update program (no
            # host sync): the UPDATED params overwrite the target every
            # ``target_update_interval`` fresh updates.  The schedule
            # keys on the frame counter (exact multiples of
            # frames_per_update, resume-exact like the LR schedule);
            # replayed updates hold the counter, so they never advance
            # the schedule.  The guard's `keep` select above already
            # chose params vs state.params, so a skipped (non-finite)
            # update syncs the HELD params — the target can never
            # absorb a poisoned step.
            k_next = (frames + self._frames_per_update) \
                / self._frames_per_update
            sync = jnp.mod(jnp.round(k_next),
                           self._target_update_interval) == 0.0
            target_params = jax.tree_util.tree_map(
                lambda t, p: jnp.where(sync, p, t),
                state.target_params, params)
        new_state = TrainState(
            params=params,
            opt_state=opt_state,
            env_frames=(frames + self._frames_per_update
                        if fresh else frames),
            nonfinite_skips=skips,
            nonfinite_streak=streak,
            target_params=target_params,
        )
        metrics["env_frames"] = new_state.env_frames
        with jax.named_scope("telemetry"):
            devtel = self._record_update_telemetry(
                devtel, metrics, grads, updates, params)
        return new_state, devtel, metrics

    def _record_update_telemetry(self, devtel, metrics, grads, updates,
                                 params):
        """What the update leaves in the donated devtel pytree: the
        update counters and the learning-dynamics plane."""
        if self._devtel_enabled:
            # Device telemetry: the same zero-host-sync contract as the
            # non-finite counters — a few scalar adds and one bucketed
            # observe fused into the update program.
            spec = self._devtel_spec
            devtel = spec.inc(devtel, "updates")
            devtel = spec.set(devtel, "loss", metrics["total_loss"])
            # A non-finite grad norm (the event the finite guard
            # absorbs) must not reach the histogram: its ":sum" buffer
            # is CUMULATIVE, so one NaN would poison every subsequent
            # fetch of the run.
            devtel = spec.observe(
                devtel, "grad_norm", metrics["grad_norm"],
                where=jnp.isfinite(metrics["grad_norm"]))
            if self._finite_guard:
                devtel = spec.inc(devtel, "skipped",
                                  metrics["update_skipped"])
        if self._learn_enabled:
            devtel = self._accumulate_learning_telemetry(
                devtel, metrics, grads, updates, params)
        return devtel

    def _accumulate_learning_telemetry(self, devtel, metrics, grads,
                                       updates, params):
        """Fold the learning-dynamics scalars into the donated devtel
        pytree inside the update program — gauge sets, histogram
        observes, and three tree reductions per layer group; no host
        sync (the same contract as the non-finite counters, proven by
        the transfer-guard tests)."""
        lspec = self._learn_spec
        for name in ("entropy_frac", "ess_frac", "explained_variance",
                     "rho_clip_fraction", "cs_clip_fraction",
                     "pg_rho_clip_fraction", "log_rho_mean",
                     "log_rho_p95") + (
                         ("dead_torso_frac",)
                         if self._agent.dead_unit_module is not None
                         else ()):
            devtel = lspec.set(devtel, name, metrics[name])
        devtel = lspec.set(devtel, "kl", metrics["behaviour_kl"])
        if self._loss_name == "impact":
            # Satellite fix: histograms aggregate EVERY update between
            # fetches — under --updates_per_dispatch=K the metrics dict
            # only surfaces the last of the K scan iterations, but
            # these observes run inside each iteration on the carried
            # devtel dict, so count/sum/mean cover all K.
            for hist, key in (("impact_ratio", "impact_ratio_mean"),
                              ("impact_clip_fraction",
                               "impact_clip_fraction")):
                value = metrics[key]
                devtel = lspec.observe(devtel, hist, value,
                                       where=jnp.isfinite(value))
            devtel = lspec.set(devtel, "impact_log_ratio_p95",
                               metrics["impact_log_ratio_p95"])
            devtel = lspec.set(devtel, "impact_ess_frac",
                               metrics["impact_ess_frac"])
        for stat in self._forward_stats:
            devtel = lspec.observe(devtel, stat_histogram_name(stat),
                                   metrics[stat])
        # Per-layer-group optimizer health: grads/updates/params share
        # one treedef, so a single flatten-with-path keys all three.
        zero = jnp.zeros((), jnp.float32)
        acc = {group: [zero, zero, zero]
               for group in self._agent.layer_groups}
        flat_grads, _ = jax.tree_util.tree_flatten_with_path(grads)
        flat_updates = jax.tree_util.tree_leaves(updates)
        flat_params = jax.tree_util.tree_leaves(params)
        for (path, g), u, p in zip(flat_grads, flat_updates, flat_params):
            group = acc[self._agent.layer_group(path)]
            group[0] = group[0] + jnp.sum(
                jnp.square(jnp.asarray(g, jnp.float32)))
            group[1] = group[1] + jnp.sum(
                jnp.square(jnp.asarray(u, jnp.float32)))
            group[2] = group[2] + jnp.sum(
                jnp.square(jnp.asarray(p, jnp.float32)))
        for name, (g_sq, u_sq, p_sq) in acc.items():
            param_norm = jnp.sqrt(p_sq)
            devtel = lspec.set(devtel, f"grad_norm_{name}",
                               jnp.sqrt(g_sq))
            devtel = lspec.set(devtel, f"param_norm_{name}", param_norm)
            # ``updates`` is already lr-scaled, so this is the actual
            # step taken relative to the weights it moved.
            devtel = lspec.set(
                devtel, f"update_ratio_{name}",
                jnp.sqrt(u_sq) / (param_norm + jnp.float32(1e-8)))
        return devtel

    def update(self, state: TrainState, trajectory: Trajectory,
               fresh: bool = True
               ) -> Tuple[TrainState, Dict[str, jax.Array]]:
        """One training step.  ``trajectory`` should already be on device
        (``put_trajectory``) for best overlap; host batches also work.
        ``fresh=False`` marks a REPLAYED batch (runtime/replay.py): the
        update holds env_frames and the target-net schedule — the
        frames were counted when the batch was consumed fresh."""
        injector = get_fault_injector()
        if injector.active and injector.should_fire("nan_grad"):
            # Chaos: poison this batch's rewards so the loss (and every
            # gradient) goes NaN — the guard must absorb it as a skip.
            trajectory = trajectory._replace(
                env_outputs=trajectory.env_outputs._replace(
                    reward=trajectory.env_outputs.reward
                    * jnp.float32(float("nan"))))
        with get_tracer().span("learner/update", cat="learner"):
            update = self._update if fresh else self._update_replayed
            new_state, self._devtel, metrics = update(
                state, trajectory, self._devtel)
            out = (new_state, metrics)
        self._updates_counter.inc()
        if fresh:
            self._frames_counter.inc(self._frames_per_update)
        else:
            self._replayed_counter.inc()
        # Step-number breadcrumb: a crash dump's ring then pins exactly
        # how far training got, independent of any metrics flush.
        get_flight_recorder().record(
            "update", "learner", {"update": int(self._updates_counter.value)})
        return out


class NonFiniteTracker:
    """Host-side observer for the fused non-finite guard.

    The jitted update carries cumulative/consecutive skip counters in
    TrainState and mirrors them into its metrics dict; this tracker
    reads them whenever the driver fetches metrics anyway (log time),
    keeps the process-wide ``learner/nonfinite_skips_total`` counter and
    flight-recorder breadcrumbs in step, and answers the one policy
    question: has the consecutive-skip streak exhausted
    ``--nonfinite_tolerance``?  (``tolerance=0`` disables the policy;
    skips are still counted.)
    """

    def __init__(self, tolerance: int, registry=None):
        from scalable_agent_tpu.obs import get_registry as _get_registry

        self.tolerance = int(tolerance)
        registry = registry or _get_registry()
        self._counter = registry.counter(
            "learner/nonfinite_skips_total",
            "updates skipped by the non-finite guard (params/opt_state "
            "held, env frames still retired)")
        self._last_total = 0.0

    def observe(self, host_metrics: Dict[str, float]) -> bool:
        """Fold one fetched metrics dict in; True when the consecutive
        streak has reached the tolerance (caller rolls back / exits)."""
        total = float(host_metrics.get("nonfinite_skips", 0.0))
        streak = float(host_metrics.get("nonfinite_streak", 0.0))
        # Megaloop contract (runtime/ingraph.py TrainCarry.streak_peak):
        # the end-of-dispatch streak can have RESET mid-dispatch after
        # breaching the tolerance; the carried peak is the worst streak
        # since the last rollback, so the boundary check honors the
        # documented trigger at any updates_per_dispatch.
        streak = max(streak, float(
            host_metrics.get("nonfinite_streak_peak", 0.0)))
        delta = total - self._last_total
        if delta > 0:
            self._counter.inc(delta)
            get_flight_recorder().record(
                "nonfinite_skip", "learner",
                {"skips_total": total, "streak": streak})
        self._last_total = max(self._last_total, total)
        return bool(self.tolerance > 0 and streak >= self.tolerance)

    def rebase(self, total: float):
        """Re-anchor after a rollback: the restored state's cumulative
        counter is older than what we already counted — without this,
        the next observe() would double-count the gap."""
        self._last_total = float(total)
