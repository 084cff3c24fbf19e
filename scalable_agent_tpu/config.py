"""One dataclass-based config for the whole framework.

Replaces the reference's two coexisting systems — tf.app.flags
(reference: experiment.py:49-95) and SF argparse with per-env overrides +
cfg.json persistence (reference: algorithms/utils/arguments.py:27-99) —
with a single dataclass: reference hyperparameter names/defaults are kept
verbatim so parity runs transfer unchanged, JSON round-trips to
``<logdir>/config.json``, and env families can override defaults through
``apply_env_overrides``.
"""

import dataclasses
import json
import os
from typing import Optional, Tuple


@dataclasses.dataclass
class Config:
    # -- run control (reference: experiment.py:49-60)
    mode: str = "train"  # train | test
    logdir: str = "/tmp/agent"
    level_name: str = "fake_benchmark"
    seed: int = 1

    # -- training sizes (reference: experiment.py:61-72)
    num_actors: int = 64  # total env count across groups
    batch_size: int = 32
    unroll_length: int = 100
    num_action_repeats: int = 4
    total_environment_frames: float = 1e9

    # -- loss (reference: experiment.py:73-81)
    entropy_cost: float = 0.00025
    baseline_cost: float = 0.5
    discounting: float = 0.99
    reward_clipping: str = "abs_one"  # abs_one | soft_asymmetric | none

    # -- optimizer (reference: experiment.py:89-95)
    learning_rate: float = 0.00048
    rmsprop_decay: float = 0.99
    rmsprop_momentum: float = 0.0
    rmsprop_epsilon: float = 0.1

    # -- env (reference: experiment.py:82-88)
    width: int = 96
    height: int = 72
    benchmark_mode: bool = False
    num_env_workers_per_group: int = 8
    # DMLab-only: psychlab dataset location and renderer backend
    # (reference: experiment.py:77-87 dataset_path/renderer flags;
    # software is the run-anywhere default, hardware needs EGL).
    dataset_path: str = ""
    renderer: str = "software"

    # -- eval (reference: experiment.py:57-58)
    test_num_episodes: int = 10
    test_batch_size: int = 8  # parallel eval envs per level
    test_num_workers: int = 2  # env worker processes per eval fleet
    # Record eval episodes (frames.npy + actions/rewards JSON per
    # episode, one subdir per level/env slot) — the Sample-Factory
    # record_to flag's role (reference: env_wrappers.py:433-497).
    record_to: str = ""  # empty = no recording; test mode only

    # -- TPU-native knobs (no reference equivalent)
    torso_type: str = "shallow"  # shallow | resnet
    # Which policy acts: the conv torso + LSTM agent (models/agent.py)
    # when this is empty; else the token policy (models/token_policy.py)
    # that the named JSON file describes under the source's own key
    # names (model_type, hidden_size, num_experts, layer_types, ...; the
    # benchmark's configuration files are such files; ``model_type``
    # says which decoder family, one of ``token_policy.FAMILIES``).  The
    # token policy runs on the fused loop (``--train_backend=ingraph``)
    # in a token world (``--level_name=token_recall``), one chip, V-trace.
    model_config: str = ""
    # Activation/matmul dtype END-TO-END (torso, LSTM core, heads):
    # params, loss, V-trace, and optimizer reductions stay f32
    # regardless (models/agent.py documents the full policy).
    compute_dtype: str = "bfloat16"
    # LSTM core: auto | xla | pallas — auto picks the fused Pallas
    # unroll (ops/lstm_pallas.py) on a single-device TPU mesh, the
    # nn.scan path elsewhere (parallel/mesh.py
    # fused_kernels_profitable — the one rule both kernel "auto"s
    # follow).  Param trees are identical either way.
    core_impl: str = "auto"
    # Pallas-core matmul operand precision: auto | float32 | bfloat16.
    # "auto" follows the ONE dtype policy: the pallas core's matmuls
    # run at compute_dtype (bf16 operands, f32 accumulation — the
    # parity-proven recipe), while the xla core always trains at the
    # f32 params' precision.  Explicit values decouple the two.
    core_matmul_dtype: str = "auto"
    # Stem-conv grad-W lowering: auto | xla | pallas.  "pallas" swaps
    # ONLY the stem's weight gradient for the Pallas MXU kernel
    # (ops/conv_pallas.py) — the named worst kernel in the roofline
    # ledger (conv0_gradw, 0.107 MFU).  "auto" follows core_impl's
    # rule — pallas on a single-device TPU mesh, xla elsewhere — and
    # only for a stem the kernel takes (the shallow 8x8/stride-4 stem;
    # the ResNet stem stays on XLA: driver.resolve_conv_backend).
    # Param trees are identical either way.
    conv_backend: str = "auto"
    # Fused single-forward loss (runtime/learner.py): one unroll feeds
    # both the behaviour-comparison quantities and the differentiated
    # loss outputs.  False compiles the two-pass reference shape —
    # bench_kernel_war's baseline, not a production setting.
    fused_forward: bool = True
    # Rematerialize the torso in the backward pass: auto | on | off.
    # "auto" = on for TPU runs (keeps the fused single-forward update
    # flat on peak activation memory at B=256), off elsewhere.
    # Numerically identity; trades a torso recompute for memory.
    remat_torso: str = "auto"
    use_instruction: bool = False
    # (the actor-group count is derived: num_actors // batch_size — each
    # group is one learner batch; >= 2 groups overlap env-sim with TPU
    # inference.  See driver.make_env_groups.)
    mesh_data: int = 0  # 0 = all devices
    # Sequence/context parallelism (SURVEY §5.7): batches shard over
    # (data x seq); the V-trace recurrence's time dimension shards over
    # seq (parallel/sequence.py, scan_impl="time_sharded").
    mesh_seq: int = 1
    mesh_model: int = 1
    # Multi-host (DCN) distribution — empty/0/-1 = single process.
    # (role of the reference's ClusterSpec + --job_name/--task flags,
    # experiment.py:497-512)
    distributed_coordinator: str = ""  # e.g. "10.0.0.1:8476"
    distributed_num_processes: int = 0
    distributed_process_id: int = -1
    # Actor inference: "structural" (one jitted step per group),
    # "service" (C++ dynamic batcher co-batches groups into one call —
    # the reference's architecture, dynamic_batching.py + batcher.cc),
    # "accum" (on-device trajectory accumulation: per step only frame
    # bytes go up and actions come down, runtime/accum_actor.py), or
    # "accum_fused" (accum + cross-group lockstep co-dispatch: ONE
    # device call and ONE action fetch serve all groups per step —
    # ~1 link RTT regardless of group count).
    inference_mode: str = "structural"
    # accum_fused only: number of lockstep shards the group fleet
    # splits into (separate threads).  1 = one device call serves ALL
    # groups (minimum RTTs, right co-located); 2 lets one shard's
    # upload + env stepping overlap the other's link round trip —
    # measured 1.6-1.8x e2e on bandwidth-constrained links
    # (the r4 shard sweep; 3 shards regressed).  Default 0 = AUTO:
    # the pool probes the link at startup (RTT + H2D bandwidth) and
    # picks the predicted-best count from the RTT-floor model
    # (runtime/linktune.py) — so co-located chips get 1 and degraded
    # links get 2 without per-deployment tuning.  The pool clamps
    # explicit values to the group count.
    accum_fused_shards: int = 0
    # Host actor runtime: "grouped" (the ActorPool — one thread per env
    # group, lockstep step_send/step_recv, the slowest env gates its
    # group) or "service" (runtime/service.py — continuous-batching:
    # env workers stream observations out individually, one inference
    # thread batches whatever arrived against a device-resident LSTM
    # state slab, per-env trajectory packing; no per-step group
    # barrier).  docs/performance.md, "Continuous-batching actor
    # service".
    actor: str = "grouped"
    # service only: the largest device batch the inference thread forms
    # (rows = envs).  Formed batches pad up a power-of-two bucket
    # ladder so XLA sees ~log2(max) shapes.  0 = auto (all of this
    # process's envs — one full sweep fits one batch).
    service_max_batch: int = 0
    # Training backend: "host" (actor pool + prefetch + learner — the
    # reference's architecture, experiment.py:479-672) or "ingraph"
    # (rollout + update fused into ONE jitted device program for
    # device-expressible levels, runtime/ingraph.py — zero per-step
    # host↔device traffic).
    train_backend: str = "host"
    # ingraph only: fused updates per device dispatch (the multi-update
    # megaloop, runtime/ingraph.py).  K > 1 runs K rollout+update
    # iterations as ONE lax.scan per launch, so a cheap-env run is no
    # longer dispatch-bound — bit-exact with K dispatches of 1 over the
    # same total update count.  Checkpoint/log/preemption decisions
    # land on dispatch boundaries (granularity K updates).
    # Incompatible with replay_ratio > 0 (replayed updates interleave
    # between dispatches).
    updates_per_dispatch: int = 1
    # Trajectory transport (runtime/transport.py): "packed" flattens
    # every trajectory leaf into ONE contiguous staging buffer per batch
    # (dtype-segmented, 128-byte-aligned offsets) so a batch costs a
    # single H2D copy + a jitted on-device unpack; "per_leaf" is the
    # seed path — one device_put per leaf — preserved bit-for-bit.
    # Device-resident trajectories (inference_mode=accum*) bypass the
    # pack either way: they re-shard on device instead of uploading.
    transport: str = "packed"
    # Bounded in-flight dispatch: keep up to this many updates dispatched
    # but unmaterialized; the driver blocks only when the window is full
    # (metrics surface when their update falls out of the window).  The
    # default of 2 overlaps batch k+1's pack/upload with update k while
    # blocking at most one update behind — the seed loop's effective
    # pipelining, now with an explicit bound; 1 forces strict lock-step
    # (a per-update completion wait the seed loop never paid — use it
    # for debugging, not throughput).
    inflight_updates: int = 2
    # vtrace: auto | associative | sequential | pallas | time_sharded —
    # auto picks time_sharded when mesh_seq > 1, the fused Pallas kernel
    # on a single-device TPU mesh, associative else.
    scan_impl: str = "auto"
    # -- off-policy replay (runtime/replay.py, ops/impact.py;
    # docs/performance.md "Replay & the off-policy dial") ----------------
    # Loss surrogate: "vtrace" (the seed objective, bit-for-bit) or
    # "impact" (clipped-target surrogate with a target network riding
    # in TrainState — tolerates far staler data, the objective replay
    # needs).
    loss: str = "vtrace"
    # Replayed updates per fresh batch: every fresh batch's packed
    # upload also lands in the device-resident replay slab, and R
    # uniformly sampled batches ride behind each fresh update — the
    # learner-throughput dial that decouples learner fps from actor
    # fps.  0 disables replay entirely (no slab is ever allocated).
    # Replayed updates do NOT advance env_frames (fresh frames count
    # exactly once) and are tuned against the
    # ledger/staleness_replayed_s split.
    replay_ratio: int = 0
    # Replay slab capacity in whole batches.  Device HBM cost is
    # capacity x packed-batch bytes; contents are intentionally not
    # checkpointed (docs/robustness.md, replay warm-up after restore).
    replay_capacity: int = 64
    # IMPACT target network: hard-copy the online params into the
    # target every this many FRESH updates (in-graph, no extra sync).
    target_update_interval: int = 100
    # IMPACT surrogate ratio clip epsilon (pi_theta/pi_target outside
    # [1-eps, 1+eps] stops contributing gradient).
    impact_clip_epsilon: float = 0.3
    checkpoint_interval_s: float = 600.0  # reference: experiment.py:611-612
    checkpoint_keep: int = 5
    log_interval_s: float = 10.0
    # jax.profiler tracing (SURVEY §5.1): capture device+host traces for
    # profile_num_updates updates starting at profile_start_update.
    profile_dir: str = ""  # empty = disabled
    profile_start_update: int = 10
    profile_num_updates: int = 5
    # Observability (obs/): --trace captures host pipeline spans (actor
    # env-step/inference, batcher queues, learner update, checkpoint,
    # h2d transfers) to <logdir>/trace.json — Chrome trace-event format,
    # loadable in Perfetto.  Unlike --profile_dir's device trace this
    # shows the host-side hand-offs, costs a few us per span, and is
    # bounded: capture stops (with a truncation marker) at the tracer's
    # 2M-event budget (~200 MB) so long runs can't fill the disk.  The
    # metrics registry + Prometheus snapshot (<logdir>/metrics.prom) and
    # the stall attributor are always on; see docs/observability.md.
    # Trace files carry a .p<proc>.<pid> suffix so two runs sharing a
    # logdir (or N processes of one run) can never clobber each other;
    # `python -m scalable_agent_tpu.obs.aggregate <logdir>` merges them.
    trace: bool = False
    # Watchdog (obs/watchdog.py): a pipeline thread (actor, batcher
    # consumer, prefetch, learner) that makes no progress for this many
    # seconds trips the stalled_thread verdict and dumps the flight
    # recorder + all-thread stacks (<logdir>/flightrec.<pid>.json,
    # stacks.<pid>.txt).  0 disables (unit tests construct their own).
    # The default is generous: it must sit above a worst-case production
    # compile or checkpoint, not above a step.
    watchdog_timeout_s: float = 300.0
    # Abort the process (exit 70) after the watchdog dump instead of
    # hanging forever — the right setting under a supervisor that
    # restarts failed workers.
    watchdog_abort: bool = False
    # Serve live Prometheus text over HTTP at this port (0 = disabled):
    # scrapers hit http://host:<port>/metrics instead of polling
    # <logdir>/metrics.prom off disk.  Multi-process runs offset the
    # port by the process index.
    metrics_http_port: int = 0
    # Learning-dynamics plane (docs/observability.md): V-trace/IMPACT
    # clip + ESS diagnostics, policy entropy/KL, value explained-
    # variance, and per-layer-group optimizer telemetry accumulated
    # in-graph (devtel/learn/*, zero added host syncs), read by the
    # health detectors, obs.watch, obs.report, and `python -m
    # scalable_agent_tpu.obs.diagnose <logdir>`.
    learn_telemetry: bool = True
    # -- run-health plane (obs/health.py, docs/observability.md) ---------
    # Online anomaly detection at log-interval cadence: EWMA z-score
    # (level shifts), CUSUM (slow drifts), hard thresholds (invariants)
    # over throughput/loss/grad-norm/staleness/segment-rho/nonfinite/
    # peers.  A trip appends <logdir>/anomalies.jsonl, pins + dumps the
    # flight recorder, and may open a bounded auto-profile window.
    health: bool = True
    # Log intervals before a detector arms (the compile-dominated first
    # intervals must not poison the baseline or trip an alarm).
    health_warmup_intervals: int = 8
    # EWMA smoothing for the detector baselines (mean and variance).
    health_ewma_alpha: float = 0.35
    # z-score a deviation needs to trip (with a material relative
    # deviation); a relative drop/rise past health_rel_threshold trips
    # on its own regardless of the variance estimate.
    health_z_threshold: float = 4.0
    health_rel_threshold: float = 0.6
    # Per-detector re-trip cooldown AND the minimum gap between auto-
    # profile windows: a flapping detector logs one suppressed count
    # per swallowed trip instead of a record per interval.
    health_cooldown_s: float = 120.0
    # Auto-profile window budget for the whole run (0 disables windows;
    # detection, records, and flightrec dumps stay on).
    health_max_windows: int = 2
    # Updates one anomaly-triggered profiling window spans.
    health_window_updates: int = 5
    # -- self-healing (docs/robustness.md) --------------------------------
    # Non-finite guard: a NaN/Inf loss or gradient makes the update a
    # no-op (params/opt_state held, frames still retired) and counts in
    # learner/nonfinite_skips_total.  This many CONSECUTIVE skips
    # triggers a rollback to the last verified checkpoint (or exit 71
    # with --no_rollback).  0 disables the rollback policy; the guard
    # itself is always on.
    nonfinite_tolerance: int = 10
    # Numerics sentinel (runtime/sentinel.py): every K updates, shadow-
    # audit the hot path's gradients and param deltas against the
    # reference path (XLA stem, f32 compute, two-pass loss) and demote
    # down the degradation ladder on breach; also publish a param
    # fingerprint per log interval and compare it across processes at
    # the decision-broadcast cadence.  0 disables the sentinel entirely
    # (the default path stays bit-exact).  In-graph runs require
    # --updates_per_dispatch=1 while the sentinel is armed.
    sentinel_interval: int = 0
    # Max per-leaf L2-relative deviation ||hot - ref|| / (||ref|| + eps)
    # any grad or param-delta leaf may show before an audit breaches.
    # Calibrated against bench_sentinel's clean hot-vs-reference run at
    # production shapes: legitimate bf16-vs-f32 drift measures ~0.38 on
    # the worst (near-cancelled conv-bias) leaf, a 2x-miscomputing
    # kernel reads exactly 1.0, and a param bit-flip dwarfs the
    # reference delta's norm — 0.6 splits the bands with margin both
    # ways.  Watch devtel/sentinel/max_deviation to re-calibrate.
    sentinel_rtol: float = 0.6
    # Exit with code 71 instead of rolling back when the non-finite
    # tolerance is exhausted — the right setting under a supervisor
    # that reschedules the run (rollback-on-restart then happens via
    # the normal resume path).
    no_rollback: bool = False
    # Bounded actor-thread respawn: a failing actor retries with capped
    # exponential backoff this many times before its exception ends the
    # run (actor/restarts_total; per-actor detail in the flight
    # recorder).  0 restores fail-fast.
    actor_max_restarts: int = 3
    # Deterministic fault injection (runtime/faults.py), chaos testing
    # only: 'point@i[:j...]' / 'point@t=30s' / 'point@p=0.01' entries
    # joined by ';', e.g.
    # 'nan_grad@7;actor_raise@3:12;ckpt_torn@t=5s;worker_kill@p=0.01'.
    # Empty = no faults.
    chaos_spec: str = ""
    # Arm the runtime injection channel: the injector tails
    # <logdir>/chaos_inject.jsonl and fires each appended
    # {"point": ..., "t_unix": ...} line once at that point's next
    # evaluation — faults land in an ALREADY-RUNNING fleet (the chaos
    # soak engine, runtime/soak.py, writes the lines).  Propagates to
    # relaunched elastic workers like any other flag.
    chaos_channel: bool = False
    # -- fleet fault domains (runtime/fleet.py, docs/robustness.md) ------
    # Peer heartbeat deadline: in a multi-process run, a peer whose
    # KV-store heartbeat stops advancing for this long (local monotonic
    # clock) is declared lost — forensic dump + exit 72 instead of
    # hanging forever in the next collective.  0 disables detection
    # (single-process runs never arm it).
    peer_timeout_s: float = 60.0
    # Preemption grace: SIGTERM raises a fleet-wide preemption flag
    # instead of dumping and dying; every process drains its in-flight
    # window and takes ONE coordinated final verified checkpoint within
    # this many seconds, then exits 0 for frame-exact resume.  Blowing
    # the window means forensics + exit 72; a second SIGTERM escalates
    # to the legacy immediate dump.  0 restores dump-and-exit(143).
    preemption_grace_s: float = 30.0
    # Deadline on each blocking cross-process point (decision
    # broadcasts, trajectory assembly, checkpoint save/restore
    # collectives): a collective older than this is attributed in the
    # flight recorder and the process exits 72.  0 = auto
    # (max(600, 4x peer_timeout_s)) — it must sit above a worst-case
    # first-update compile or Orbax read, not above a step; the
    # heartbeat deadline above is the FAST detector.
    collective_timeout_s: float = 0.0
    # Bounded retry (capped exponential backoff) around
    # jax.distributed.initialize: process N racing the coordinator's
    # startup retries for this long before failing the run
    # (fleet/init_retries_total counts the attempts).
    coordinator_init_timeout_s: float = 60.0
    # -- elastic fleet membership (runtime/elastic.py) -------------------
    # Supervisor mode: instead of training directly, own
    # distributed_num_processes (or 1) worker processes, watch their
    # exit codes, and convert a fleet-fatal (exit 72) or preemption
    # into a RESHARD event — relaunch the survivors as an (N-1)-process
    # fleet resuming from the newest verified checkpoint — then scale
    # back to N when the lost slot rejoins.  Equivalent CLI:
    # python -m scalable_agent_tpu.runtime.elastic <same flags>.
    elastic: bool = False
    # Membership epoch this worker belongs to (set by the supervisor on
    # every (re)launch; surfaces as the fleet/epoch gauge and in the
    # fleet_epoch.json membership verdict).  Operators never set it.
    fleet_epoch: int = 0
    # Reshard-restart budget: consecutive fleet relaunches (capped
    # exponential backoff between them) before the supervisor gives up
    # and exits with the workers' code.  The counter resets once an
    # epoch survives elastic_stable_s.
    elastic_restart_budget: int = 8
    # Seconds a fleet must run before its epoch counts as stable
    # (resets the restart budget and the backoff).
    elastic_stable_s: float = 300.0
    # Seconds after a slot is LOST (worker SIGKILLed / host gone)
    # before the supervisor may schedule its rejoin; an operator can
    # force an earlier rejoin by touching <logdir>/rejoin.<slot>.
    # The scale-up itself happens at the next checkpoint boundary: the
    # running fleet is drained through the preemption-grace protocol
    # (one coordinated verified checkpoint, exit 0) and relaunched at
    # the larger size.
    elastic_rejoin_delay_s: float = 60.0

    # -------------------------------------------------------------------

    def group_size(self) -> int:
        """Envs per actor group == this host's share of the learner
        batch (minimum slice layout; ``batch_size`` is GLOBAL in
        multi-host runs, matching the reference's one learner batch fed
        by all actors, experiment.py:576)."""
        import jax

        processes = jax.process_count()
        if self.batch_size % processes:
            raise ValueError(
                f"batch_size {self.batch_size} not divisible by "
                f"{processes} processes")
        return self.batch_size // processes

    def frames_per_update(self) -> int:
        """(reference: experiment.py:417-420)"""
        return (self.batch_size * self.unroll_length
                * self.num_action_repeats)

    def save(self, path: Optional[str] = None) -> str:
        """Persist to JSON (the reference's cfg.json,
        algorithms/utils/agent.py:190-193)."""
        path = path or os.path.join(self.logdir, "config.json")
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(dataclasses.asdict(self), f, indent=2, sort_keys=True)
        return path

    @classmethod
    def load(cls, path: str) -> "Config":
        with open(path) as f:
            raw = json.load(f)
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in raw.items() if k in known})

    @classmethod
    def from_argv(cls, argv=None, description=None) -> "Config":
        """Parse a full CLI flag set (one ``--<field>`` per dataclass
        field) into a Config — the ONE parser shared by the driver and
        the elastic supervisor entry points, so their flag surfaces can
        never drift.  ``description`` is what ``--help`` prints above
        the option list (the driver passes its module docstring); what
        each flag is for stands in the comment above its field."""
        import argparse

        parser = argparse.ArgumentParser(
            description=description,
            formatter_class=argparse.RawDescriptionHelpFormatter)
        for field in dataclasses.fields(cls):
            arg_type = type(field.default)
            if arg_type is bool:
                parser.add_argument(
                    f"--{field.name}", type=lambda v: v.lower() in
                    ("1", "true", "yes"), default=field.default)
            else:
                parser.add_argument(
                    f"--{field.name}", type=arg_type,
                    default=field.default)
        return cls(**vars(parser.parse_args(argv)))

    def to_argv(self, exclude: Tuple[str, ...] = ()) -> list:
        """The inverse of ``from_argv``: the minimal ``--field=value``
        list reproducing this config (non-default fields only, minus
        ``exclude``) — how the elastic supervisor hands its own config
        to the worker processes it spawns."""
        args = []
        for field in dataclasses.fields(self):
            if field.name in exclude:
                continue
            value = getattr(self, field.name)
            if value == field.default:
                continue
            if isinstance(value, bool):
                value = "true" if value else "false"
            args.append(f"--{field.name}={value}")
        return args

    @classmethod
    def from_checkpoint_dir(cls, logdir: str, **overrides) -> "Config":
        """Load a run's persisted config, applying CLI overrides on top
        (the reference's checkpoint-config precedence,
        arguments.py:69-89)."""
        path = os.path.join(logdir, "config.json")
        config = cls.load(path) if os.path.exists(path) else cls()
        return dataclasses.replace(config, logdir=logdir, **overrides)


# Per-env-family default overrides (the reference's
# env_override_defaults / *_params.py pattern, envs/env_config.py:1-24).
_ENV_OVERRIDES = {
    "doom_": {"width": 128, "height": 72, "num_action_repeats": 4},
    "atari_": {"width": 84, "height": 84, "num_action_repeats": 4},
    "dmlab_": {"width": 96, "height": 72, "num_action_repeats": 4},
    # The full suite: DMLab defaults + instruction observations (the
    # language levels need them; the reference's dmlab30 agent always
    # consumes INSTR, experiment.py:179-189).
    "dmlab30": {"width": 96, "height": 72, "num_action_repeats": 4,
                "use_instruction": True},
}


def apply_env_overrides(config: Config) -> Config:
    for prefix, overrides in _ENV_OVERRIDES.items():
        if config.level_name.startswith(prefix):
            defaults = Config()
            fields = {
                k: v for k, v in overrides.items()
                # CLI-set values win over family defaults.
                if getattr(config, k) == getattr(defaults, k)
            }
            return dataclasses.replace(config, **fields)
    return config
