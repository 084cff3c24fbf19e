"""Experiment driver: train/test entry points and CLI.

The role of the reference's ``experiment.py`` driver (reference:
experiment.py:479-733) without its TF1 machinery: no sessions, no in-graph
queues.  ``train`` is one loop (``_run``) over a backend — host-stepped
simulators behind ActorPool -> device prefetch -> Learner, or a device
world fused with the update into one program — with checkpointing,
metrics, and DMLab-30 scoring; ``test`` evaluates a checkpoint.

Run:
    python -m scalable_agent_tpu.driver --mode=train \
        --level_name=fake_benchmark --total_environment_frames=100000
    python -m scalable_agent_tpu.driver --mode=test --logdir=...

Every flag is a field of ``Config`` (config.py), documented where it is
declared; docs/performance.md, docs/robustness.md and
docs/observability.md say what each group is for.
"""

import dataclasses
import functools
import json
import os
import queue as queue_lib
import threading
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from scalable_agent_tpu.config import Config, apply_env_overrides
from scalable_agent_tpu.envs import (
    MultiEnv,
    create_env,
    make_impala_stream,
)
from scalable_agent_tpu.envs import dmlab30
from scalable_agent_tpu.envs.spec import TensorSpec
from scalable_agent_tpu.models import (
    CONV_BACKENDS,
    ImpalaAgent,
    actor_step,
    initial_state,
)
from scalable_agent_tpu.obs import (
    MetricsHTTPServer,
    MetricsWriter,
    PrometheusExporter,
    StallAttributor,
    configure_flight_recorder,
    configure_ledger,
    configure_tracer,
    configure_watchdog,
    get_flight_recorder,
    get_ledger,
    get_registry,
    get_tracer,
    get_watchdog,
    install_crash_handlers,
    start_host_watch,
    stop_host_watch,
)
from scalable_agent_tpu.parallel import MeshSpec, make_mesh
from scalable_agent_tpu.parallel.distributed import (
    initialize_distributed,
    is_coordinator,
)
from scalable_agent_tpu.runtime import (
    ActorPool,
    InGraphTrainer,
    InflightWindow,
    Learner,
    LearnerHyperparams,
    NonFiniteTracker,
    TrainState,
    Trajectory,
    configure_faults,
    configure_fleet,
    get_fleet,
)
from scalable_agent_tpu.runtime.checkpoint import CheckpointManager
from scalable_agent_tpu.runtime.exit_codes import (
    NONFINITE_EXIT_CODE,
    SENTINEL_EXIT_CODE,
)
from scalable_agent_tpu.runtime.faults import (
    CHANNEL_NAME,
    get_fault_injector,
    throughput_sag_s,
)
from scalable_agent_tpu.types import (
    AgentOutput,
    AgentState,
    Observation,
    StepOutput,
    StepOutputInfo,
)
from scalable_agent_tpu.utils import Timing, log
from scalable_agent_tpu.utils.compile_cache import setup_compile_cache


def env_kwargs(config: Config, name: Optional[str] = None) -> dict:
    """Per-family constructor kwargs (the reference threads width/height/
    etc. through create_environment, experiment.py:430-459)."""
    name = name or config.level_name
    if name.startswith(("fake_", "dmlab_")):
        kwargs = {"height": config.height, "width": config.width,
                  "with_instruction": config.use_instruction}
        if name.startswith("dmlab_"):
            kwargs.update(dataset_path=config.dataset_path,
                          renderer=config.renderer)
        return kwargs
    if name.startswith(("atari_", "gym_", "doom_")):
        return {"height": config.height, "width": config.width}
    return {}


def resolve_mesh_data(config: Config) -> int:
    """The data-axis size train() will actually use — shared by the
    mesh construction and every "auto" kernel-choice estimate so they
    can never disagree."""
    n_devices = len(jax.devices())
    non_data = config.mesh_seq * config.mesh_model
    if jax.process_count() > 1:
        # Multi-host meshes must span EVERY process's devices: a
        # truncated device list would exclude whole processes, whose
        # local batch shards then have no addressable home in
        # make_array_from_process_local_data.
        mesh_data = config.mesh_data or n_devices // non_data
        if mesh_data * non_data != n_devices:
            raise ValueError(
                f"multi-host mesh (data={mesh_data}, "
                f"seq={config.mesh_seq}, model={config.mesh_model}) "
                f"must cover all {n_devices} global devices")
        return mesh_data
    # Single process: the shared auto-sizing rule (parallel/mesh.py) —
    # the largest data axis such that data*seq divides the batch, out
    # of the devices left after seq/model take theirs.  Elastic
    # restarts lean on this: a fleet relaunched with a different
    # process/device count resizes its mesh here with no operator
    # input.
    from scalable_agent_tpu.parallel.mesh import auto_data_axis

    return config.mesh_data or auto_data_axis(
        config.batch_size, n_devices, seq=config.mesh_seq,
        model=config.mesh_model)


def _intended_mesh_size(config: Config) -> int:
    """Device count of the mesh train() will build (the agent is built
    before the mesh exists) — what both kernel "auto"s are sized from."""
    return resolve_mesh_data(config) * config.mesh_seq * config.mesh_model


def resolve_core_impl(config: Config) -> str:
    """"auto" defers to the shared fused-kernel policy
    (parallel/mesh.py fused_kernels_profitable)."""
    if config.core_impl != "auto":
        return config.core_impl
    from scalable_agent_tpu.parallel.mesh import fused_kernels_profitable
    return ("pallas" if fused_kernels_profitable(
        num_devices=_intended_mesh_size(config)) else "xla")


def stem_gradw_tile(config: Config, frame_shape=None):
    """What the Pallas grad-W kernel would do with this run's stem:
    ``(images per grid step or 0, images its last step masks, the
    geometry in words)`` from ops/conv_pallas.gradw_batch_tile at the
    merged batch, frame size and dtype.  ``frame_shape`` is the probed
    (H, W, C); the config's height/width x 3 when omitted."""
    from scalable_agent_tpu.models.networks import STEM_GEOMETRY
    from scalable_agent_tpu.ops.conv_pallas import (
        gradw_batch_tile,
        gradw_padded_images,
    )

    if config.torso_type not in STEM_GEOMETRY:
        raise ValueError(
            f"torso_type must be one of {sorted(STEM_GEOMETRY)}, got "
            f"{config.torso_type!r}")
    features, kernel_size, stride = STEM_GEOMETRY[config.torso_type]
    height, width, channels = (
        frame_shape or (config.height, config.width, 3))
    dtype = jnp.dtype(config.compute_dtype)
    images = (config.unroll_length + 1) * config.batch_size
    tile = gradw_batch_tile((images, height, width, channels), features,
                            kernel_size, stride, dtype)
    geometry = (f"the {config.torso_type} stem ({kernel_size}x"
                f"{kernel_size}/stride-{stride}) over {height}x{width}x"
                f"{channels} {dtype.name} frames")
    return tile, gradw_padded_images(images, tile), geometry


def resolve_conv_backend(config: Config, frame_shape=None) -> str:
    """"auto" follows the SAME mesh-size rule as ``core_impl``
    (fused_kernels_profitable: Pallas only on a single-device TPU mesh
    — a multi-device mesh cannot lower a ``pallas_call`` at all), and
    then only for a stem the grad-W kernel takes at this run's frame
    size and dtype (``stem_gradw_tile`` — the ResNet 3x3/stride-1 stem
    at 72x96 does not fit VMEM).  An explicit ``pallas`` the kernel
    cannot take is refused here, at config time, instead of dying
    inside Mosaic."""
    if config.conv_backend not in ("auto",) + CONV_BACKENDS:
        raise ValueError(
            f"conv_backend must be auto or one of {CONV_BACKENDS}, "
            f"got {config.conv_backend!r}")
    if config.conv_backend == "xla":
        return "xla"
    from scalable_agent_tpu.parallel.mesh import fused_kernels_profitable

    if (config.conv_backend == "auto" and not fused_kernels_profitable(
            num_devices=_intended_mesh_size(config))):
        return "xla"
    tile, _, geometry = stem_gradw_tile(config, frame_shape)
    if tile:
        return "pallas"
    if config.conv_backend == "pallas":
        raise ValueError(
            f"conv_backend=pallas: the grad-W kernel does not take "
            f"{geometry} (ops/conv_pallas.gradw_batch_tile); use auto "
            f"or xla")
    log.warning("conv_backend=auto: the Pallas grad-W kernel does not "
                "take %s — the stem stays on XLA", geometry)
    return "xla"


def resolve_core_matmul_dtype(config: Config, core_impl: str) -> str:
    """"auto" follows the dtype policy: the pallas core's MXU matmuls
    run at compute_dtype (f32 accumulation either way); the xla core
    always trains at the f32 params' precision, so auto resolves to
    float32 there and the flag stays inert."""
    if config.core_matmul_dtype != "auto":
        return config.core_matmul_dtype
    if core_impl != "pallas":
        return "float32"
    return ("bfloat16"
            if jnp.dtype(config.compute_dtype) == jnp.dtype(jnp.bfloat16)
            else "float32")


def resolve_remat_torso(config: Config) -> bool:
    """Whether the torso rematerializes in its backward pass: "auto"
    = on a TPU (where HBM caps the batch), off elsewhere.  "On" is
    each torso's OWN placement of the ``jax.checkpoint`` boundary
    (models/networks.py REMAT_PLACEMENTS; the kernel-policy line names
    it): the ResNet's stem segment, nothing behind the shallow torso's
    Pallas stem, the whole shallow torso behind XLA's stem.  Never one
    boundary around whichever torso: that recomputes the whole forward
    and frees nothing at the peak, where every residual is live again
    (ISSUE 27)."""
    if config.remat_torso not in ("auto", "on", "off"):
        raise ValueError(
            f"remat_torso must be auto, on, or off, got "
            f"{config.remat_torso!r}")
    if config.remat_torso != "auto":
        return config.remat_torso == "on"
    return jax.default_backend() == "tpu"


# What the token policy does not run on yet, each with the flag that
# asks for it and why (ROADMAP R6-R10 have what is left of each).
def _token_policy_refusals(config: Config):
    mesh = _intended_mesh_size(config)
    return (
        (config.train_backend != "ingraph",
         f"--train_backend={config.train_backend}: the token policy runs "
         f"on the fused loop only (--train_backend=ingraph); the host "
         f"loop's actors, batcher and transport carry an LSTM state and a "
         f"vector of logits a step, not an attention cache"),
        (config.actor == "service",
         "--actor=service: the inference service batches requests that "
         "carry an LSTM state, not an attention cache"),
        (config.inference_mode.startswith("accum"),
         f"--inference_mode={config.inference_mode}: the accumulating "
         f"actors keep an LSTM state on the device, not an attention "
         f"cache"),
        (config.loss == "impact",
         "--loss=impact: IMPACT unrolls a target network through the "
         "same trajectory, and the token policy's update reads the "
         "rollout's one cache; only --loss=vtrace is built"),
        (config.replay_ratio > 0,
         f"--replay_ratio={config.replay_ratio}: a replayed trajectory "
         f"would need its own copy of the attention cache (the slab "
         f"holds none); only --replay_ratio=0 is built"),
        (config.sentinel_interval > 0,
         f"--sentinel_interval={config.sentinel_interval}: the shadow "
         f"audit takes the dispatch's trajectory, which would carry the "
         f"attention cache out of the step"),
        (mesh > 1,
         f"a mesh of {mesh} devices: the token policy runs on one chip "
         f"(one expert-parallel chip's share, without the experts' "
         f"exchange); pass --mesh_data=1 on a host with more"),
    )


def build_token_policy(config: Config, action_space, frame_shape=None):
    """The token policy (models/token_policy.py) from the file
    ``--model_config`` names (its ``model_type`` says which of the
    policy's families: ``token_policy.FAMILIES``), its rings sized for this run's unroll and its
    world's episodes.  Every combination it is not built for is refused
    here, at configuration time, by name."""
    from scalable_agent_tpu.envs.device import DEVICE_LEVELS
    from scalable_agent_tpu.models.token_policy import (
        TokenModelConfig,
        TokenPolicy,
    )

    # a family the policy does not build is refused there, with the
    # families it does
    model = TokenModelConfig.from_file(config.model_config)
    family = model.model_type
    for refused, why in _token_policy_refusals(config):
        if refused:
            raise ValueError(
                f"the token policy (--model_config, family {family}) does "
                f"not run with {why}")
    level = DEVICE_LEVELS.get(config.level_name)
    tokens = getattr(action_space, "n", None)
    if (level is None or "episode_length" not in level.defaults
            or tuple(frame_shape or ()) != ()):
        raise ValueError(
            f"the token policy (--model_config, family {family}) acts in "
            f"a token world (a device level whose "
            f"observation is a token id, e.g. token_recall); "
            f"--level_name={config.level_name} is none")
    if tokens != model.vocab_size:
        raise ValueError(
            f"--level_name={config.level_name} shows {tokens} tokens, "
            f"{config.model_config} has vocab_size {model.vocab_size}: "
            f"the world's tokens are the policy's actions")
    agent = TokenPolicy(
        model=model, unroll_length=config.unroll_length,
        episode_length=int(level.defaults["episode_length"]),
        compute_dtype=jnp.dtype(config.compute_dtype))
    registry = get_registry()
    for name, value, text in agent.gauges(config.batch_size):
        registry.gauge(name, text).set(value)
    log.info(
        "kernel policy: backend=%s mesh_devices=%d policy=token family=%s "
        "model_config=%s layers=%d (%s) experts_held=%d/%d (first %d) "
        "window_slots=%d full_slots=%d ring_readers=%d "
        "latent_bytes_per_token=%d core_impl=%s conv_backend=%s remat=%s "
        "compute_dtype=%s",
        jax.default_backend(), _intended_mesh_size(config), family,
        config.model_config, model.num_hidden_layers,
        ", ".join(f"{model.layer_types.count(kind)} {kind}"
                  for kind in dict.fromkeys(model.layer_types)),
        model.experts_held, model.num_experts, model.first_expert,
        agent.window_slots, agent.full_slots, agent.ring_readers,
        agent.latent_bytes_per_token, agent.core_impl, agent.conv_backend,
        agent.remat_placement, config.compute_dtype)
    return agent


def build_agent(config: Config, action_space,
                frame_shape=None) -> ImpalaAgent:
    """Policy heads derive from the probed action space — one Discrete
    head or a composite tuple-categorical (ops/distributions.py).  The
    kernel policy (every "auto" resolved, interpreted or compiled) is
    logged here, once per agent built.  ``--model_config=<file>``
    builds the token policy that file describes instead
    (``build_token_policy``)."""
    if config.model_config:
        return build_token_policy(config, action_space, frame_shape)
    core_impl = resolve_core_impl(config)
    core_matmul_dtype = resolve_core_matmul_dtype(config, core_impl)
    if core_matmul_dtype not in ("float32", "bfloat16"):
        raise ValueError(
            f"core_matmul_dtype must be auto, float32, or bfloat16, "
            f"got {core_matmul_dtype!r}")
    if core_matmul_dtype != "float32" and core_impl != "pallas":
        import warnings

        warnings.warn(
            f"core_matmul_dtype={core_matmul_dtype!r} only "
            f"affects the pallas core; this run resolves to "
            f"core_impl={core_impl!r} and trains at float32",
            stacklevel=2)
    conv_backend = resolve_conv_backend(config, frame_shape)
    remat_torso = resolve_remat_torso(config)
    from scalable_agent_tpu.parallel.mesh import pallas_interpret

    # The grad-W kernel's tile and what its last step masks are
    # decided at trace time: gauges, set once, beside the policy.
    tile, padded = (stem_gradw_tile(config, frame_shape)[:2]
                    if conv_backend == "pallas" else (0, 0))
    registry = get_registry()
    registry.gauge(
        "conv0_gradw/batch_tile",
        "images per grid step of the Pallas stem grad-W kernel "
        "(0: the stem is XLA's)").set(tile)
    registry.gauge(
        "conv0_gradw/padded_images",
        "images past N its last grid step masks (none is ever padded "
        "in HBM)").set(padded)
    agent = ImpalaAgent(
        action_space=action_space,
        torso_type=config.torso_type,
        use_instruction=config.use_instruction,
        compute_dtype=jnp.dtype(config.compute_dtype),
        core_impl=core_impl,
        core_matmul_dtype=core_matmul_dtype,
        conv_backend=conv_backend,
        remat_torso=remat_torso,
    )
    log.info(
        "kernel policy: backend=%s mesh_devices=%d core_impl=%s "
        "(matmul %s) conv_backend=%s (conv0_gradw batch_tile=%d "
        "padded_images=%d) remat_torso=%s (remat=%s) compute_dtype=%s "
        "pallas_interpret=%s",
        jax.default_backend(), _intended_mesh_size(config), core_impl,
        core_matmul_dtype, conv_backend, tile, padded, remat_torso,
        agent.remat_placement, config.compute_dtype, pallas_interpret())
    return agent


def training_level_names(config: Config) -> List[str]:
    """The level list training spreads env slots over.

    ``--level_name=dmlab30 --mode=train`` is multi-task: env slot e runs
    train level ``e % 30`` (the reference assigns actor i level
    ``level_names[i % len]``, experiment.py:552-555, with the train-
    variant list for train mode, :711-717).  Anything else trains one
    level."""
    if config.level_name == "dmlab30":
        return [f"dmlab_{name}" for name in dmlab30.TRAIN_LEVELS]
    return [config.level_name]


def probe_env(config: Config):
    """Open one env to read (observation_spec, action_space,
    num_agents), then tear it down.  num_agents > 1 marks a lockstep
    multi-agent level (create_env returns a MultiAgentEnv there)."""
    env = create_env(config.level_name, **env_kwargs(config))
    try:
        return (env.observation_spec, env.action_space,
                getattr(env, "num_agents", 1))
    finally:
        env.close()


def zero_trajectory(config: Config, observation_spec, agent: ImpalaAgent,
                    batch: int = 1, t_plus_1: int = 2) -> Trajectory:
    """All-zeros [t_plus_1, batch] trajectory for shape-only use: the
    [2, 1] default initializes params; the live-MFU cost analysis lowers
    the update at the run's REAL [T+1, B] shape."""
    frame_spec = observation_spec.frame

    def zeros(shape, dtype):
        return np.zeros((t_plus_1, batch) + tuple(shape), dtype)

    instruction = None
    if observation_spec.instruction is not None:
        instr_spec = observation_spec.instruction
        instruction = zeros(instr_spec.shape, instr_spec.dtype)
    num_components = agent.num_action_components
    action_shape = () if num_components == 1 else (num_components,)
    return Trajectory(
        agent_state=AgentState(
            c=np.zeros((batch, 256), np.float32),
            h=np.zeros((batch, 256), np.float32)),
        env_outputs=StepOutput(
            reward=zeros((), np.float32),
            info=StepOutputInfo(
                episode_return=zeros((), np.float32),
                episode_step=zeros((), np.int32)),
            done=zeros((), bool),
            observation=Observation(
                frame=zeros(frame_spec.shape, frame_spec.dtype),
                instruction=instruction),
        ),
        agent_outputs=AgentOutput(
            action=zeros(action_shape, np.int32),
            policy_logits=zeros((agent.num_logits,), np.float32),
            baseline=zeros((), np.float32)),
    )


def match_port_scheme(total_matches: int):
    """UDP port scheme shared by every concurrent-match constructor
    (training groups AND eval fleets): each match probes its own
    residue class — base ``DEFAULT_UDP_PORT + stride*index``, increment
    ``stride*total`` — so concurrent inits can't race each other, with
    >= ~4 retry probes per match kept under the 65536 ceiling.

    Returns ``stride``; raises when ``total_matches`` exhausts the port
    space above DEFAULT_UDP_PORT."""
    from scalable_agent_tpu.envs.doom.multiplayer import DEFAULT_UDP_PORT

    stride = max(1, min(1000, 25000 // max(1, 8 * total_matches)))
    retries = (65536 - DEFAULT_UDP_PORT - stride * total_matches) // (
        stride * total_matches)
    if retries < 2:
        raise ValueError(
            f"{total_matches} concurrent matches do not fit the UDP "
            f"port space above {DEFAULT_UDP_PORT} with retry headroom; "
            f"reduce the fleet or lower DOOM_DEFAULT_UDP_PORT")
    return stride


def make_env_groups(config: Config, frame_spec: TensorSpec,
                    num_agents: int = 1,
                    level_names: Optional[List[str]] = None
                    ) -> List[MultiEnv]:
    """num_actors envs as groups of batch_size (each group = one learner
    batch; >= 2 groups so env simulation and TPU inference overlap).

    ``frame_spec`` is the PROBED post-wrapper spec — pipelines change the
    channel count (e.g. Atari's grayscale stack-4 emits [84, 84, 4]), so
    the shared-memory slab layout cannot be assumed 3-channel.

    Multi-agent levels (``num_agents > 1``, from probe_env — e.g.
    ``doom_dm``, where ``create_env`` returns a lockstep
    ``MultiAgentEnv``, not an Environment) route to
    ``MultiAgentVectorEnv`` groups — K matches x A agents per group,
    each agent one batch slot (the role of the reference's
    ``create_multi_env`` dispatch, envs/env_utils.py:6-20)."""
    group_size = config.group_size()
    num_groups = max(1, config.num_actors // group_size)
    level_names = level_names or [config.level_name]

    if num_agents > 1:
        if len(level_names) > 1:
            raise ValueError(
                "multi-task training is not supported for multi-agent "
                "levels")
        if config.benchmark_mode:
            raise ValueError(
                "benchmark_mode is not supported for multi-agent levels")
        if group_size % num_agents:
            raise ValueError(
                f"batch_size {group_size} must be a multiple of the "
                f"level's num_agents ({num_agents})")
        from scalable_agent_tpu.envs.doom.multiplayer import (
            DEFAULT_UDP_PORT,
            MultiAgentVectorEnv,
        )

        matches = group_size // num_agents
        # Per-match seed (player seeds derive from it) and DISJOINT
        # port-search sequences, both GLOBALLY unique across multi-host
        # processes: the base stride shrinks as the global match count
        # grows so every base stays under the 65535 UDP limit, and each
        # match's fallback increment is stride * total, keeping every
        # match's probes in its own residue class — concurrent group
        # init (any host) can't race another match's host.
        proc = jax.process_index()
        total_global = num_groups * matches * jax.process_count()
        stride = match_port_scheme(total_global)

        def match_index(g: int, m: int) -> int:
            return proc * num_groups * matches + g * matches + m

        return [
            MultiAgentVectorEnv([
                functools.partial(
                    create_env, config.level_name,
                    num_action_repeats=config.num_action_repeats,
                    # Non-overlapping seed fields: one globally-unique
                    # match index scales the run seed, so no two matches
                    # (any host) can derive the same per-player seeds.
                    seed=config.seed * total_global + match_index(g, m),
                    port_base=(DEFAULT_UDP_PORT
                               + stride * match_index(g, m)),
                    port_increment=stride * total_global,
                    **env_kwargs(config))
                for m in range(matches)
            ])
            for g in range(num_groups)
        ]

    groups = []
    # GLOBAL env slot (multi-host: each process owns a disjoint slot
    # range) round-robins the level list so every level gets an equal
    # share of actors across the whole job — per-host indexing would
    # make every host train the same level prefix (reference assigns by
    # global actor id, experiment.py:552-555).
    slot_base = jax.process_index() * num_groups * group_size
    for g in range(num_groups):
        labels = [
            level_names[(slot_base + g * group_size + i)
                        % len(level_names)]
            for i in range(group_size)
        ]
        fns = [
            functools.partial(
                make_impala_stream, labels[i],
                seed=config.seed * 100000 + g * 1000 + i,
                benchmark_mode=config.benchmark_mode,
                num_action_repeats=config.num_action_repeats,
                **env_kwargs(config, labels[i]))
            for i in range(group_size)
        ]
        groups.append(MultiEnv(
            fns, frame_spec,
            num_workers=config.num_env_workers_per_group,
            env_labels=labels))
    return groups


def to_trajectory(actor_output) -> Trajectory:
    return Trajectory(
        agent_state=actor_output.agent_state,
        env_outputs=actor_output.env_outputs,
        agent_outputs=actor_output.agent_outputs,
    )


def start_prefetch(pool, learner, staged: queue_lib.Queue,
                   stop: threading.Event) -> threading.Thread:
    """Start the device-prefetch stage: pulls ActorPool trajectories,
    places them sharded on device, and stages them one deep — the
    reference's StagingArea +1-step policy lag (experiment.py:587-597).
    Exceptions surface through the staged queue."""

    def prefetch_loop():
        watchdog = get_watchdog()
        try:
            while not stop.is_set():
                # Every bounded wait below re-touches, so the prefetch
                # heartbeat only goes stale when the thread truly wedges
                # (e.g. inside a hung device placement).
                watchdog.touch()
                try:
                    out = pool.get_trajectory(timeout=0.5)
                except queue_lib.Empty:
                    continue
                traj = learner.put_trajectory(to_trajectory(out))
                # Re-bind the provenance record (this thread's current,
                # set by get_trajectory) to the PLACED object the main
                # loop will pull off the staged queue.
                ledger = get_ledger()
                tid = ledger.current()
                if tid is not None:
                    ledger.bind(id(traj), tid)
                while not stop.is_set():
                    watchdog.touch()
                    try:
                        staged.put(traj, timeout=0.5)
                        break
                    except queue_lib.Full:
                        continue
        except Exception as exc:  # surface in the consumer loop
            recorder = get_flight_recorder()
            recorder.record("exception", type(exc).__name__,
                            {"where": "prefetch"})
            recorder.dump_all(f"exception:{type(exc).__name__}:prefetch")
            staged.put(exc)
        finally:
            watchdog.suspend()

    thread = threading.Thread(target=prefetch_loop, daemon=True,
                              name="prefetch")
    thread.start()
    return thread


def _host_scalar(x) -> float:
    """Scalar metric -> host float, multi-host safe (replicated global
    arrays are not fully addressable; the local copy is)."""
    if hasattr(x, "is_fully_addressable") and not x.is_fully_addressable:
        return float(np.asarray(x.addressable_shards[0].data))
    return float(np.asarray(x))


def _resolve_roofline_peak() -> Optional[float]:
    """Per-chip roofline peak (obs/ledger.py PEAK_FLOPS), overridable
    via SCALABLE_AGENT_LEDGER_MFU_PEAK so the full MFU/kernel path is
    exercisable on the CPU rig.  None only off-TPU with no override: on
    a TPU an unknown ``device_kind`` is an error (the gauge would read
    0 forever and look like an idle chip), and so is an override that
    is not a number."""
    from scalable_agent_tpu.obs.ledger import peak_flops_per_chip

    override = os.environ.get("SCALABLE_AGENT_LEDGER_MFU_PEAK")
    if override:
        try:
            return float(override)
        except ValueError:
            raise ValueError(
                f"SCALABLE_AGENT_LEDGER_MFU_PEAK={override!r} is not a "
                f"number (peak FLOP/s per chip)") from None
    device = jax.local_devices()[0]
    peak = peak_flops_per_chip(device.device_kind)
    if peak is None and device.platform == "tpu":
        raise ValueError(
            f"no roofline peak for TPU device_kind "
            f"{device.device_kind!r}: add it to obs/ledger.py PEAK_FLOPS "
            f"or set SCALABLE_AGENT_LEDGER_MFU_PEAK")
    return peak


def _harvest_kernel_ledger(config: Config, lower_fn,
                           executions: int,
                           profile_dir: Optional[str] = None,
                           out_name: Optional[str] = None
                           ) -> Optional[dict]:
    """Join a finished trace window with the compiled update's HLO +
    cost analysis into the per-kernel roofline ledger:
    ``<logdir>/<out_name>`` plus ``kernel/*`` registry gauges
    (obs/kernels.py; the worst-kernel verdict also feeds the stall
    line).  Defaults serve the scheduled ``--profile_dir`` window
    (``kernels.json``); the run-health plane passes its own window's
    trace dir and ``kernels.<anomaly_id>.json`` so both backends can
    harvest a programmatic mid-run window through the same path.
    Pays one AOT compile of the update — acceptable inside an explicit
    profiling window, and the only sanctioned way to read the
    optimized HLO whose instruction names the trace events carry.
    Never raises: the ledger is forensics, not the training path.
    Returns the harvested table (None on any failure)."""
    from scalable_agent_tpu.obs import kernels as kernels_lib

    profile_dir = profile_dir or config.profile_dir
    out_name = out_name or kernels_lib.KERNELS_JSON_NAME
    try:
        compiled = lower_fn().compile()
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        flops = float((cost or {}).get("flops", 0.0))
        hlo_text = compiled.as_text()
    except Exception:
        log.exception("kernel ledger: update compile/cost read failed")
        return None
    try:
        table = kernels_lib.harvest(
            profile_dir, hlo_text, flops,
            _resolve_roofline_peak(), config.logdir,
            registry=get_registry(), executions=executions,
            extra={"device_kind": jax.local_devices()[0].device_kind,
                   "logdir": config.logdir},
            out_name=out_name)
    except Exception:
        log.exception("kernel ledger harvest failed")
        return None
    if table is None:
        log.warning("kernel ledger: no trace files under %s",
                    profile_dir)
        return None
    log.info(
        "kernel ledger: %d kernels joined (%.0f%% of event time), "
        "dominant %s (%.0f%% of kernel time), worst %s (mfu %s) — "
        "%s/%s",
        len(table["kernels"]), 100 * table["matched_time_frac"],
        table.get("dominant_kernel"),
        100 * (table.get("dominant_time_share") or 0.0),
        table.get("worst_kernel"),
        (f"{table['worst_kernel_mfu']:.3f}"
         if table.get("worst_kernel_mfu") is not None else "n/a"),
        config.logdir, out_name)
    return table


# Scopes every fused step's text holds (runtime/ingraph.py): where a
# compiled step's text lacks one, it was compiled from an older version
# of the program.
_STEP_SCOPES = ("rollout", "learner_update")


def _write_op_scopes(trace_path: Optional[str], trainer, state, carry):
    """After a ``--trace`` run of the fused loop: the table that gives
    each instruction of the compiled step its scope path, beside the
    run's trace (obs/kernels.write_op_scopes; the benchmark's scope
    reader joins a device trace to it), and with it what the
    partitioner made the step move between devices (the table's notes
    and the ``spmd/collective_bytes/<kind>`` gauges).  The running
    step's own executable is at hand (nothing is traced or compiled
    again) — unless the persistent cache handed back one compiled from
    an older version of the program, whose text names that version's
    scopes:
    then the step is compiled afresh for its names
    (``InGraphTrainer.compile_step_afresh``), which is why this runs
    last, after every deadline of the run is disarmed.  Never raises: it
    is forensics, not the training path."""
    from scalable_agent_tpu.obs import kernels as kernels_lib

    if trace_path is None:
        return
    t0 = time.monotonic()
    try:
        text = trainer.train_step.lower(
            state, carry, np.int32(0)).compile().as_text()
        if not all(kernels_lib.holds_scope(text, scope)
                   for scope in _STEP_SCOPES):
            log.warning(
                "op scopes: the compile cache's executable of the step "
                "was compiled from an older version of the program "
                "(same ops, other scope names); compiling the step "
                "afresh for its names")
            text = trainer.compile_step_afresh(state, carry).as_text()
        path = kernels_lib.write_op_scopes(trace_path, text)
    except Exception:
        log.exception("op scopes: reading the compiled step failed")
        return
    log.info("op scopes written to %s in %.1fs", path,
             time.monotonic() - t0)


def _configure_live_mfu(ledger, lower_fn, num_devices: int,
                        updates_per_execution: int = 1):
    """Arm the ledger's live ``ledger/mfu`` gauge (obs/ledger.py).

    FLOPs per update come from the LOWERED (uncompiled) update
    program's cost analysis — tracing cost only, a few seconds at
    startup, no second XLA compile — and the per-chip peak from the
    shared roofline table in obs/ledger.py (the same one bench.py's MFU
    uses, so a run's gauge and the bench headline share a denominator).
    Skipped off-TPU with no peak override (the CPU rig — the gauge
    stays at 0, and no test pays the lowering); anywhere else a gauge
    that cannot be armed is a WARNING, never silence: a 0 reading must
    not be mistaken for an idle chip.

    ``updates_per_execution``: the in-graph megaloop runs K updates
    per dispatched program, but XLA's cost analysis counts a lax.scan
    body ONCE regardless of trip count — so the lowered flops cover
    one update while a retired ledger record covers K; the gauge
    scales the numerator by K to stay honest."""
    peak = _resolve_roofline_peak()
    if not peak:
        return
    try:
        cost = lower_fn().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        flops = float((cost or {}).get("flops", 0.0))
    except Exception as exc:  # an obs gauge must never kill training
        log.warning("live MFU gauge DISARMED (cost analysis failed; "
                    "ledger/mfu will read 0): %s", exc)
        return
    flops *= max(1, int(updates_per_execution))
    if flops <= 0:
        log.warning("live MFU gauge DISARMED (cost analysis reported no "
                    "flops; ledger/mfu will read 0)")
        return
    ledger.configure_mfu(flops, peak, num_devices)
    log.info("live MFU gauge armed: %.3g flops/record against "
             "%.3g peak flops/s x %d device(s)",
             flops, peak, num_devices)


class _SetupStages:
    """The run's set-up as contiguous ``setup/*`` stages, from
    ``driver.main``'s first line to the first dispatch returning.

    ``enter(name)`` ends the open stage and starts the next, so the
    stages leave no gap: whatever runs before the next ``enter`` is the
    open stage's.  Each stage's seconds are kept whether or not the run
    is traced (``_write_mttr_breakdown`` reads ``setup/restore`` and
    ``setup/first_dispatch`` from here); with ``--trace`` each is also
    a span, ``cat="setup"``, and the compile spans of the programs it
    traced, lowered and compiled nest inside it (obs/registry.py)."""

    def __init__(self, t_entry_ns: int):
        # setup/config (Config.from_argv) ran before --trace was known:
        # it is recorded when it ends, which is now.
        now = time.perf_counter_ns()
        self.seconds: Dict[str, float] = {
            "setup/config": (now - t_entry_ns) * 1e-9}
        get_tracer().add_span("setup/config", "setup",
                              t_entry_ns // 1000, now // 1000)
        self._name: Optional[str] = None
        self._t0_ns = now
        self._span = None

    @property
    def open(self) -> Optional[str]:
        return self._name

    def enter(self, name: Optional[str]):
        """End the open stage; start ``name`` (None: start nothing).
        Both take ONE clock reading, so a stage begins exactly where
        the one before it ends, however long the hand-over takes."""
        now = time.perf_counter_ns()
        if self._span is not None:
            self._span.close(now)
            self._span = None
        if self._name is not None:
            self.seconds[self._name] = (
                self.seconds.get(self._name, 0.0)
                + (now - self._t0_ns) * 1e-9)
        self._name, self._t0_ns = name, now
        if name is not None:
            self._span = get_tracer().span(name, cat="setup",
                                           start_ns=now)
            self._span.__enter__()

    def done(self):
        self.enter(None)


def _open_timeline(config: Config, t_entry_ns: Optional[int]
                   ) -> _SetupStages:
    """Start the run's one timeline.  With ``--trace`` the span tracer
    records from here (in memory: ``_attach_trace_file`` opens
    ``trace.p<proc>.<pid>.json`` once the process index may be asked
    for) and ``_teardown_observability`` closes it; the compile
    listener is hooked before the first program compiles."""
    if t_entry_ns is None:
        t_entry_ns = time.perf_counter_ns()
    if config.trace:
        configure_tracer(None, deferred=True)
        # Full collections and a frozen host are spans of the same
        # timeline for as long as it records (obs/trace.py HostWatch).
        start_host_watch(get_registry())
    get_registry().install_jax_hooks()
    return _SetupStages(t_entry_ns)


def _close_timeline(config: Config):
    """End what ``_open_timeline`` started: the host watch's hook and
    thread go, the file tracer is closed (and flushed)."""
    if config.trace:
        stop_host_watch()
        configure_tracer(None)


def _attach_trace_file(config: Config):
    if config.trace:
        # Per-(process, pid) file names: N processes of one run share
        # the logdir, and two runs pointed at the same logdir must not
        # clobber each other's trace.  obs/aggregate.py merges them.
        proc = jax.process_index()
        get_tracer().attach(
            os.path.join(config.logdir,
                         f"trace.p{proc}.{os.getpid()}.json"),
            process_index=proc)


@dataclasses.dataclass
class _ObsHandles:
    """Everything _setup_observability wires and _teardown unwinds."""

    registry: object
    prom: Optional[PrometheusExporter]
    http: Optional[MetricsHTTPServer] = None
    uninstall_handlers: Optional[callable] = None


def _setup_observability(config: Config, coordinator: bool) -> _ObsHandles:
    """Wire the obs subsystem for one training run: JAX compile/memory
    hooks on the global registry, a per-process Prometheus snapshot file
    (the coordinator keeps the plain metrics.prom name), the flight
    recorder + crash handlers (SIGTERM/SIGINT, unhandled exceptions),
    the watchdog (--watchdog_timeout_s), and the optional live scrape
    endpoint (--metrics_http_port).  The span tracer is NOT made here:
    it has been recording since ``_open_timeline``."""
    proc = jax.process_index()
    registry = get_registry().install_jax_hooks()
    prom_name = "metrics.prom" if coordinator else f"metrics.p{proc}.prom"
    prom = PrometheusExporter(
        registry, os.path.join(config.logdir, prom_name))
    # Failure forensics: the ring buffer dumps (with all-thread stacks
    # and a final prom snapshot) on SIGTERM/SIGINT, unhandled
    # exceptions, and watchdog stalls.
    recorder = configure_flight_recorder(config.logdir,
                                         process_index=proc,
                                         registry=registry)
    recorder.exporter = prom
    uninstall = install_crash_handlers(recorder)
    configure_watchdog(config.watchdog_timeout_s, registry=registry,
                       abort=config.watchdog_abort,
                       flight_recorder=recorder)
    http = None
    if config.metrics_http_port:
        try:
            http = MetricsHTTPServer(registry,
                                     config.metrics_http_port + proc,
                                     logdir=config.logdir)
            log.info("serving Prometheus metrics on :%d/metrics "
                     "(+ /anomalies, /health)", http.port)
        except OSError as exc:  # a taken port must not kill training
            log.error("metrics HTTP endpoint unavailable on port %d: %s",
                      config.metrics_http_port + proc, exc)
    return _ObsHandles(registry=registry, prom=prom, http=http,
                       uninstall_handlers=uninstall)


def _teardown_observability(config: Config, handles: _ObsHandles):
    """Dump forensics if we are unwinding an exception, then flush the
    trace tail and the final metrics snapshot and unwind the hooks."""
    import sys

    recorder = get_flight_recorder()
    exc = sys.exc_info()[1]
    if exc is not None and not isinstance(exc, (SystemExit,
                                                KeyboardInterrupt)):
        # Exceptions unwinding through train() dump here, while every
        # thread whose stack explains the failure is still alive.
        recorder.dump_all(f"exception:{type(exc).__name__}")
    elif recorder.pending_dump_reason:
        # A signal handler requested the dump: its in-handler attempt
        # may have been abandoned (bounded join) if the interrupted
        # frame held a tracer/instrument lock — this stack is clean,
        # so complete/refresh it now.
        recorder.dump_all(recorder.pending_dump_reason)
    configure_watchdog(None)
    if handles.http is not None:
        handles.http.close()
    _close_timeline(config)
    if handles.prom is not None:
        handles.prom.dump()
    if handles.uninstall_handlers is not None:
        handles.uninstall_handlers()


class _HealthPlane:
    """Driver-side state of the run-health plane (obs/health.py): the
    ``HealthMonitor`` plus the single in-flight anomaly-triggered
    profiling window.  The monitor arbitrates (budget, cooldown, one window at a
    time); this class owns the jax.profiler start/stop and the
    ``_harvest_kernel_ledger`` call against the window's own trace dir
    and ``kernels.<anomaly_id>.json`` name.  Inert (every method a
    no-op) when ``--health`` is off."""

    def __init__(self, config: Config, backend: str):
        self.monitor = None
        self.window_id: Optional[str] = None
        self.window_dir: Optional[str] = None
        self.window_stop_at: Optional[int] = None
        self._config = config
        if not config.health:
            return
        from scalable_agent_tpu.obs.health import (
            HealthMonitor,
            default_detectors,
        )

        self.monitor = HealthMonitor(
            default_detectors(
                backend=backend,
                warmup=config.health_warmup_intervals,
                alpha=config.health_ewma_alpha,
                z_threshold=config.health_z_threshold,
                rel_threshold=config.health_rel_threshold),
            logdir=config.logdir,
            registry=get_registry(),
            cooldown_s=config.health_cooldown_s,
            max_windows=config.health_max_windows)

    @property
    def active(self) -> bool:
        return self.monitor is not None

    @property
    def window_open(self) -> bool:
        return self.window_stop_at is not None

    def step(self, metrics, update: int, verdict=None, evidence=None):
        """One detector pass at log cadence.  Never raises — health is
        forensics, not the training path."""
        if self.monitor is None:
            return
        try:
            self.monitor.step(metrics=metrics, update=update,
                              verdict=verdict, evidence=evidence)
        except Exception:
            log.exception("health detector step failed")

    def maybe_open_window(self, updates: int) -> bool:
        """Open the pending anomaly's profiling window (if any): its
        own trace dir under the logdir, stop scheduled
        ``health_window_updates`` updates from now."""
        if self.monitor is None or self.window_open:
            return False
        anomaly_id = self.monitor.poll_window()
        if anomaly_id is None:
            return False
        trace_dir = os.path.join(self._config.logdir,
                                 f"health_profile.{anomaly_id}")
        try:
            jax.profiler.start_trace(trace_dir)
        except Exception:
            log.exception("health profile window failed to start")
            return False
        get_tracer().set_annotate(True)
        self.window_id = anomaly_id
        self.window_dir = trace_dir
        self.window_stop_at = (updates
                               + self._config.health_window_updates)
        self.monitor.note_window_open(anomaly_id, trace_dir)
        get_tracer().instant("health/window", cat="health",
                             args={"id": anomaly_id, "state": "open"})
        log.info("health: auto-profile window %s open through update "
                 "%d (%s)", anomaly_id, self.window_stop_at, trace_dir)
        return True

    def close_window(self, lower_fn, executions: int):
        """Stop the window's trace and harvest its kernel ledger
        (``executions`` updates ran in it) into
        ``kernels.<anomaly_id>.json``, finalizing the anomaly record
        with the worst-kernel delta vs the run's baseline window."""
        if self.monitor is None or not self.window_open:
            return
        anomaly_id, trace_dir = self.window_id, self.window_dir
        self.window_id = self.window_dir = self.window_stop_at = None
        try:
            jax.profiler.stop_trace()
        except Exception:
            log.exception("health profile window failed to stop")
        get_tracer().set_annotate(False)
        get_tracer().instant("health/window", cat="health",
                             args={"id": anomaly_id, "state": "closed"})
        out_name = f"kernels.{anomaly_id}.json"
        table = _harvest_kernel_ledger(
            self._config, lower_fn, executions=executions,
            profile_dir=trace_dir, out_name=out_name)
        self.monitor.note_window_result(
            anomaly_id, table,
            kernels_json=(os.path.join(self._config.logdir, out_name)
                          if table else None))

    def note_baseline(self, table: Optional[dict]):
        """The scheduled ``--profile_dir`` window's kernel table — the
        reference the anomaly windows' deltas are computed against."""
        if self.monitor is not None and table:
            self.monitor.note_baseline_kernels(table)

    def finalize(self):
        """Teardown: stop a still-open window's trace (no harvest —
        the run is ending) and flush open anomaly records."""
        if self.monitor is None:
            return
        if self.window_open:
            get_tracer().instant(
                "health/window", cat="health",
                args={"id": self.window_id, "state": "closed"})
            self.window_id = self.window_dir = None
            self.window_stop_at = None
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            get_tracer().set_annotate(False)
        try:
            self.monitor.flush()
        except Exception:
            log.exception("health flush failed")


# NONFINITE_EXIT_CODE (71, re-exported above from runtime/exit_codes.py
# — the one registry for watchdog 70 / non-finite 71 / fleet 72): a run
# ended by the non-finite guard with --no_rollback, or with no
# checkpoint left to roll back to.  Distinct codes let a supervisor
# tell a numeric divergence from a hang from a lost peer.


def _rollback_or_exit(config: Config, ckpt: CheckpointManager,
                      learner: Learner, state: TrainState,
                      tracker: NonFiniteTracker,
                      reason: str = "nonfinite",
                      exit_code: int = NONFINITE_EXIT_CODE):
    """A guard's tolerance is exhausted (``reason``: the non-finite
    streak, or the numerics sentinel's surviving breach): restore the
    newest VERIFIED checkpoint (watchdog suspended across the read) and
    return ``(state, updates, frames)`` on the rolled-back timeline —
    or raise ``SystemExit(exit_code)`` (71 non-finite / 73 sentinel)
    when rollback is disabled or impossible."""
    recorder = get_flight_recorder()
    registry = get_registry()
    guard = ("sentinel" if reason == "sentinel"
             else "non-finite guard")
    if config.no_rollback:
        log.error(
            "%s: rollback wanted and --no_rollback is set — exiting %d",
            guard, exit_code)
        recorder.record("rollback", "disabled",
                        {"streak": tracker.tolerance, "reason": reason})
        recorder.dump_all(f"{reason}:no_rollback")
        raise SystemExit(exit_code)
    watchdog = get_watchdog()
    # A long Orbax read is recovery, not a wedge: the learner heartbeat
    # must not trip stalled_thread (or --watchdog_abort) mid-restore.
    watchdog.suspend("learner")
    from scalable_agent_tpu.runtime.checkpoint import (
        CheckpointIntegrityError,
    )

    try:
        restored = ckpt.restore(target=state)
    except CheckpointIntegrityError as exc:
        # Checkpoints exist but none verified: with the tolerance
        # already exhausted there is nothing to roll back to — same
        # terminal outcome as having no checkpoint at all.
        log.error("%s: %s", guard, exc)
        restored = None
    if restored is None:
        log.error(
            "%s: rollback wanted and no restorable checkpoint under "
            "%s — exiting %d", guard, config.logdir, exit_code)
        recorder.record("rollback", "no_checkpoint", {"reason": reason})
        recorder.dump_all(f"{reason}:no_checkpoint")
        raise SystemExit(exit_code)
    step, host_state = restored
    # Zero the streak so the restored timeline gets the full tolerance
    # again (the checkpoint may have been saved mid-streak).
    host_state = host_state._replace(
        nonfinite_streak=np.zeros_like(
            np.asarray(host_state.nonfinite_streak)))
    state = learner.place_state(host_state)
    registry.counter(
        "learner/rollbacks_total",
        "rollbacks to the last good checkpoint after a guard's "
        "tolerance was exhausted (non-finite streak or sentinel "
        "breach)").inc()
    frames = _host_scalar(state.env_frames)
    recorder.record("rollback", "restored",
                    {"step": step, "env_frames": frames,
                     "reason": reason})
    tracker.rebase(_host_scalar(state.nonfinite_skips))
    watchdog.touch("learner")
    log.warning(
        "%s: rolled back to checkpoint step %d (%.0f frames)",
        guard, step, frames)
    return state, step, frames


def _write_mttr_breakdown(config: Config, stages: _SetupStages):
    """Publish this process's startup-cost segments for the elastic
    supervisor's MTTR decomposition (runtime/elastic.py reads the file
    at the recovery beacon and folds the segments into the epochs-log
    ``mttr`` record): the ``setup/restore`` stage, and the
    ``setup/first_dispatch`` stage — the first dispatch blocks through
    the step's compile, so its wall time is the compile segment.
    Coordinator only; atomic replace."""
    if jax.process_index() != 0:
        return
    from scalable_agent_tpu.runtime.elastic import MTTR_BREAKDOWN_NAME

    payload = {"epoch": int(config.fleet_epoch),
               "restore_s": round(
                   stages.seconds.get("setup/restore", 0.0), 3),
               "compile_s": round(
                   stages.seconds.get("setup/first_dispatch", 0.0), 3),
               "t_unix": time.time()}
    path = os.path.join(config.logdir, MTTR_BREAKDOWN_NAME)
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, path)
    except OSError:
        log.exception("mttr breakdown write failed (non-fatal)")


def build_training_learner(config: Config, agent: ImpalaAgent):
    """Validation + mesh + Learner construction shared by BOTH train
    backends (host and ingraph), so their hyperparameters and checks can
    never drift."""
    mesh_data = resolve_mesh_data(config)
    if config.batch_size % (mesh_data * config.mesh_seq):
        raise ValueError(
            f"batch_size {config.batch_size} not divisible by the "
            f"batch-sharding axes data*seq = "
            f"{mesh_data * config.mesh_seq}")
    if config.transport not in ("packed", "per_leaf"):
        raise ValueError(
            f"unknown transport {config.transport!r} (packed | per_leaf)")
    if config.actor not in ("grouped", "service"):
        raise ValueError(
            f"unknown actor {config.actor!r} (grouped | service)")
    transport = config.transport
    if (transport == "packed" and jax.process_count() > 1
            and jax.devices()[0].platform == "cpu"):
        # Multi-process CPU collectives ride gloo, which pairs ops by
        # arrival order: the packed transport's jitted unpack (prefetch
        # thread) running concurrently with the update's all-reduce
        # (main thread) mispairs them and aborts the whole fleet with a
        # gloo size-mismatch.  TPU/GPU streams serialize collectives in
        # issue order, so only the CPU test rig needs the downgrade.
        log.warning(
            "transport=packed downgraded to per_leaf: multi-process "
            "CPU (gloo) runs mispair the concurrent unpack program's "
            "ops with the update's collectives")
        transport = "per_leaf"
    if config.inflight_updates < 1:
        raise ValueError(
            f"inflight_updates must be >= 1, got "
            f"{config.inflight_updates}")
    if config.updates_per_dispatch < 1:
        raise ValueError(
            f"updates_per_dispatch must be >= 1, got "
            f"{config.updates_per_dispatch}")
    if (config.updates_per_dispatch > 1
            and config.train_backend != "ingraph"):
        raise ValueError(
            "--updates_per_dispatch is the in-graph megaloop knob "
            "(train_backend=ingraph); the host backend pipelines via "
            "--inflight_updates instead")
    if config.loss not in ("vtrace", "impact"):
        raise ValueError(
            f"unknown loss {config.loss!r} (vtrace | impact)")
    if config.replay_ratio < 0:
        raise ValueError(
            f"replay_ratio must be >= 0, got {config.replay_ratio}")
    if config.replay_ratio > 0 and config.replay_capacity < 1:
        raise ValueError(
            f"replay_capacity must be >= 1 with replay enabled, got "
            f"{config.replay_capacity}")
    if (config.replay_ratio > 0 and config.train_backend == "host"
            and transport != "packed"):
        # The host backend's replay insert IS the packed upload landing
        # in the slab (runtime/replay.py); the per-leaf path has no
        # single device buffer to tap.  This also covers the
        # multi-process-CPU gloo downgrade above.
        raise ValueError(
            "replay_ratio > 0 requires --transport=packed on the host "
            "backend (the replay slab is fed by the packed upload)")
    if config.mesh_seq > 1 and config.unroll_length % config.mesh_seq:
        raise ValueError(
            f"unroll_length {config.unroll_length} not divisible by "
            f"seq-axis size {config.mesh_seq} (time-sharded V-trace "
            f"chunks the unroll evenly)")
    devices = jax.devices()[:mesh_data * config.mesh_seq
                            * config.mesh_model]
    mesh = make_mesh(MeshSpec(data=mesh_data, seq=config.mesh_seq,
                              model=config.mesh_model),
                     devices=devices)
    hp = LearnerHyperparams(
        entropy_cost=config.entropy_cost,
        baseline_cost=config.baseline_cost,
        discounting=config.discounting,
        reward_clipping=config.reward_clipping,
        learning_rate=config.learning_rate,
        total_environment_frames=config.total_environment_frames,
        rmsprop_decay=config.rmsprop_decay,
        rmsprop_momentum=config.rmsprop_momentum,
        rmsprop_epsilon=config.rmsprop_epsilon,
    )
    # The mesh is reachable as learner.mesh; returning just the Learner
    # keeps one source of truth.
    return Learner(agent, hp, mesh, config.frames_per_update(),
                   scan_impl=config.scan_impl,
                   transport=transport,
                   learn_telemetry=config.learn_telemetry,
                   loss=config.loss,
                   target_update_interval=config.target_update_interval,
                   impact_clip_epsilon=config.impact_clip_epsilon,
                   fused_forward=config.fused_forward,
                   # the fused step's update follows its own rollout
                   on_policy=(config.train_backend == "ingraph"
                              and config.replay_ratio == 0))


def build_replay(config: Config, learner: Learner):
    """The device replay slab for one training run (None when replay is
    off — the dial's zero position allocates nothing).  Host backend:
    the slab stores the packed transport's uploaded buffers and samples
    unpack through the transport's existing jitted unpack; the insert
    tap carries the current ledger record's birth stamp so
    ``ledger/staleness_replayed_s`` measures true frame age.  Fused
    backend: no transport, so the slab holds the unroll's Trajectory
    pytree as the step emits it, and the loop inserts it."""
    if config.replay_ratio <= 0:
        return None
    from scalable_agent_tpu.runtime.replay import DeviceReplayBuffer

    if config.train_backend == "ingraph":
        return DeviceReplayBuffer(config.replay_capacity,
                                  seed=config.seed)
    transport = learner._transport
    from scalable_agent_tpu.runtime.transport import PackedTransport

    if not isinstance(transport, PackedTransport):
        raise ValueError(
            "replay requires the packed transport on the host backend")
    replay = DeviceReplayBuffer(
        config.replay_capacity, seed=config.seed,
        postprocess=transport.unpack)

    def sink(device_buf):
        ledger = get_ledger()
        tid = ledger.current()
        birth = ledger.birth_us(tid) if tid is not None else None
        replay.insert(device_buf, birth_us=birth)

    transport.set_upload_sink(sink)
    return replay


def build_sentinel(config: Config, agent, learner, action_space,
                   frame_shape=None):
    """The numerics sentinel for one training run (None when
    ``--sentinel_interval=0``, the default — nothing constructed,
    nothing jitted, no hot-path change).  Shared by both train
    backends; the rebuild closure routes every ladder rung and the
    reference arm through the SAME agent/learner factories as the
    original construction, so a demoted path is exactly the path the
    corresponding flags would have built."""
    if config.sentinel_interval <= 0:
        return None
    from scalable_agent_tpu.runtime.sentinel import NumericsSentinel

    def rebuild(cfg):
        rebuilt_agent = build_agent(cfg, action_space, frame_shape)
        return rebuilt_agent, build_training_learner(cfg, rebuilt_agent)

    return NumericsSentinel(config, agent, learner, rebuild)


def train(config: Config,
          t_entry_ns: Optional[int] = None) -> Dict[str, float]:
    """Train until total_environment_frames.  Returns final metrics.

    ``t_entry_ns``: when the caller's work for this run began on the
    ``time.perf_counter_ns`` clock (``main``'s first line) — where the
    run's timeline starts; now when not given.

    ``--train_backend`` picks where trajectories come from (``host``:
    env workers -> actors -> learner; ``ingraph``: rollout and update
    fused into one device program); the loop is ``_run``, written once.

    Multi-host (host backend): run the SAME command on every host with
    --distributed_coordinator/--distributed_num_processes/
    --distributed_process_id set (or JAX_* env vars).  Every process
    runs its own actor pool contributing 1/P of each global batch; the
    learner update is one SPMD program over the global device mesh
    (parallel/distributed.py; role of the reference's learner+actor
    jobs, experiment.py:497-512)."""
    stages = _open_timeline(config, t_entry_ns)
    try:
        backends = {"host": _HostBackend, "ingraph": _FusedBackend}
        if config.train_backend not in backends:
            raise ValueError(
                f"unknown train_backend {config.train_backend!r} "
                f"(host | ingraph)")
        return _run(backends[config.train_backend](config, stages))
    finally:
        # However it ended: close the stage a failed set-up left open,
        # and the trace where a raise before ``_teardown_observability``
        # left it open.
        stages.done()
        _close_timeline(config)


class _Backend:
    """One training run's services, and the operations in which the two
    backends differ.  ``_run`` is the loop over them.

    ``setup() -> state`` is each backend's own — the order of the
    set-up stages differs (the host backend brings observability up
    before its actor threads are born; the fused one has nothing to
    observe until its trainer exists) — built from the shared pieces
    below.  Each backend also defines:

    * ``next_batch() -> (trajectory or None, ledger record id)`` of the
      next fresh step, waiting for it where there is something to wait
      for;
    * ``dispatch(state, trajectory, ledger_id, updates) -> (state,
      dispatched metrics, the trajectory trained on — None where the
      step keeps it on the device and nothing asked for it)``: one
      fresh step, its provenance record stamped and kept until it
      retires;
    * ``replay_update(state, trajectory, dispatched) -> (state, newest
      dispatched metrics)``: one replayed update (env_frames held, no
      provenance record: the batch's frames were accounted at fresh
      consumption, its age lands in ``ledger/staleness_replayed_s`` at
      sample time);
    * ``retire(dispatched)``: the metrics the next publish should
      read, when newer ones are known than it has, else None;
    * ``publish_telemetry()``: the one fetch the on-device instruments
      cost (a few hundred bytes), folded into the registry as
      ``devtel/*``;
    * ``adopt(learner, agent)``: the sentinel demoted the hot path —
      train on these from the next dispatch (which re-jits);
    * ``lower_step(state)``: the step lowered at the run's real shapes
      (the live MFU gauge's cost analysis, the kernel ledger's HLO);
    * ``drain(dispatched)``: the loop ended — retire what is in
      flight; the newest update's metrics where they are not the
      caller's already.

    The rest have defaults that do nothing: the loop calls them for
    both."""

    name = ""
    updates_per_step = 1    # updates one fresh dispatch advances
    # gloo (the multi-process CPU collectives transport) pairs ops by
    # ARRIVAL order per process-pair: no two programs with collectives
    # may ever be in flight at once, or their ops mispair across
    # processes and abort the whole fleet with a size mismatch.  TPU/GPU
    # streams serialize collectives in issue order, so only the CPU rig
    # (a multi-process host backend there sets this) pays explicit
    # materialization barriers between such programs.
    cpu_lockstep = False

    def __init__(self, config: Config, stages: _SetupStages):
        self.config = config
        self.stages = stages
        self.timing = Timing()
        # Per-interval stage sums (``timing`` keeps moving averages;
        # stall attribution needs THIS interval).
        self.interval = Timing()
        self.obs = self.fleet = self.ledger = self.health = None
        self.agent = self.learner = self.sentinel = self.replay = None
        self.ckpt = self.writer = self.nonfinite = None
        self.start_updates = 0

    # -- shared set-up pieces ----------------------------------------------

    def _arm(self):
        """Env overrides, the persisted config, the compile cache and
        the chaos harness: the --chaos_spec triggers plus, under
        --chaos_channel, the <logdir>/chaos_inject.jsonl runtime
        channel (the soak engine's injection path).  ``_run``'s finally
        disarms it, so one run's spec can't leak into the next
        in-process run."""
        self.config = config = apply_env_overrides(self.config)
        if is_coordinator():
            config.save()
        setup_compile_cache()
        configure_faults(
            config.chaos_spec,
            channel_path=(os.path.join(config.logdir, CHANNEL_NAME)
                          if config.chaos_channel else None),
            seed=config.seed,
            process_id=max(0, config.distributed_process_id))

    def _bring_up_services(self):
        """Observability, then the fleet's fault domains (peer
        heartbeats over the jax.distributed KV store, collective
        deadlines, the SIGTERM preemption-grace protocol — its handler
        layers over the crash handlers observability just installed;
        single-process only the grace protocol arms), then the pipeline
        ledger (obs/ledger.py: one provenance record per dispatch,
        derived into per-stage rates, staleness and the live MFU gauge
        at each publish; fresh per run, so one run's open records can
        never leak into the next)."""
        config = self.config
        # The log interval's clock starts here, not at the first
        # dispatch: a run whose set-up outlasts the interval publishes
        # right after its first update, so progress shows at once and
        # the first publish's own small compiles are behind the run
        # before its steady state.
        self.log_clock_start = time.monotonic()
        self.obs = _setup_observability(config, is_coordinator())
        self.fleet = configure_fleet(
            config.peer_timeout_s,
            preemption_grace_s=config.preemption_grace_s,
            collective_timeout_s=config.collective_timeout_s,
            registry=self.obs.registry,
            recorder=get_flight_recorder(),
            epoch=config.fleet_epoch,
            logdir=config.logdir)
        # A restore that ran before the fleet was up is noted now.
        self.fleet.note_checkpoint(self.start_updates)
        self.ledger = configure_ledger(
            registry=self.obs.registry,
            frames_per_trajectory=(config.frames_per_update()
                                   * self.updates_per_step),
            logdir=config.logdir,
            process_index=jax.process_index())

    def _build_learner(self, action_space, frame_shape):
        """The learner, the device replay slab behind it (None at
        ``--replay_ratio=0``) and the numerics sentinel (None at
        ``--sentinel_interval=0``: nothing jitted, the default path
        stays bit-exact)."""
        self.learner = build_training_learner(self.config, self.agent)
        self.replay = build_replay(self.config, self.learner)
        self.sentinel = build_sentinel(
            self.config, self.agent, self.learner, action_space,
            frame_shape)

    def _restore(self, state):
        """``state``, or the newest verified checkpoint placed on this
        run's mesh.  Topology-agnostic resume (runtime/elastic.py):
        when this run's process/device layout differs from the one
        that wrote the checkpoint, the placed state is gathered back
        and re-verified against the per-leaf CRC manifest — collective,
        so every process reaches it together (``restore`` returns
        non-None on all of them together)."""
        config = self.config
        self.ckpt = CheckpointManager(config.logdir,
                                      config.checkpoint_interval_s,
                                      config.checkpoint_keep)
        restored = self.ckpt.restore(target=state)
        if restored is None:
            return state
        self.start_updates, host_state = restored
        state = self.learner.place_state(host_state)
        if self.cpu_lockstep:
            jax.block_until_ready(state)
        self.ckpt.verify_after_reshard(self.start_updates, state)
        get_fleet().note_checkpoint(self.start_updates)
        log.info("restored checkpoint at update %d (%.0f frames)",
                 self.start_updates, _host_scalar(state.env_frames))
        return state

    def _enter_loop(self, state):
        """What the loop reads besides the services: the run-health
        plane, the non-finite guard's policy and the metrics writer."""
        config, registry = self.config, self.obs.registry
        self.health = _HealthPlane(config, backend=self.name)
        # The jitted update carries the skip counters in its metrics
        # (runtime/learner.py); the tracker reads them at the publish
        # and arbitrates rollback vs exit 71.  Baseline at the restored
        # state's cumulative count: a resumed run must not re-count the
        # previous run's lifetime skips.
        self.nonfinite = NonFiniteTracker(config.nonfinite_tolerance,
                                          registry=registry)
        self.nonfinite.rebase(_host_scalar(state.nonfinite_skips))
        if is_coordinator():
            self.writer = MetricsWriter(config.logdir, registry=registry)

    # -- what differs (defaults: nothing to do) ------------------------------

    def replay_insert(self, trajectory):
        """Put the fresh step's trajectory into the replay slab, where
        its upload has not already."""

    def after_update(self, state, updates: int):
        """The fresh update and its replayed ones are dispatched."""

    def synced(self):
        """The caller has waited for the newest dispatch: the device
        stream is in order, so everything dispatched has run."""

    def fetch(self, metrics) -> Dict[str, float]:
        """Device metrics -> the host dict that is logged and
        returned."""
        return {k: _host_scalar(v) for k, v in metrics.items()}

    def publish_extras(self, host_metrics, elapsed_s: float):
        """What this backend adds to a publish, after the ledger's:
        rows into ``host_metrics``; returns ``(stall verdict, its
        evidence, what the log line says of both)``."""
        return None, None, ""

    def after_rollback(self, state, updates: int):
        """``state`` is the restored one: nothing of the abandoned
        timeline may leak forward."""

    def stop(self):
        """Teardown of the backend's own threads and processes; must
        stand a set-up that failed partway."""

    def after_teardown(self, trace_path: Optional[str], state):
        """A clean run's last act, after every deadline is disarmed."""


class _HostBackend(_Backend):
    """Host-stepped simulators: env workers -> actor pool or service ->
    device prefetch -> ``Learner.update``, up to ``--inflight_updates``
    updates dispatched but not materialized."""

    name = "host"

    def __init__(self, config, stages):
        super().__init__(config, stages)
        self.pool = self.prefetch_thread = self.inflight = None
        self.prefetch_stop = threading.Event()

    def setup(self):
        stages = self.stages
        stages.enter("setup/distributed_init")
        config = self.config
        initialize_distributed(
            config.distributed_coordinator or None,
            config.distributed_num_processes or None,
            config.distributed_process_id
            if config.distributed_process_id >= 0 else None,
            init_timeout_s=config.coordinator_init_timeout_s)
        self.cpu_lockstep = (jax.process_count() > 1
                             and jax.devices()[0].platform == "cpu")
        _attach_trace_file(config)

        stages.enter("setup/compile_cache")
        self._arm()
        config = self.config
        # Observability comes up BEFORE the actor pool so its threads
        # are born with the live tracer and watchdog (spans/heartbeats
        # from the very first unroll), and the fleet before the
        # learner/restore so a peer lost during the (collective)
        # restore or first compile is already bounded.
        stages.enter("setup/observability")
        self._bring_up_services()
        registry = self.obs.registry

        stages.enter("setup/probe_env")
        level_names = training_level_names(config)
        multi_task = len(level_names) > 1
        probe_config = (
            dataclasses.replace(config, level_name=level_names[0])
            if multi_task else config)
        observation_spec, action_space, num_agents = probe_env(
            probe_config)
        self.observation_spec = observation_spec
        stages.enter("setup/build_agent")
        self.agent = build_agent(config, action_space,
                                 observation_spec.frame.shape)
        stages.enter("setup/build_learner")
        self._build_learner(action_space, observation_spec.frame.shape)

        stages.enter("setup/trainer_init")
        state = self.learner.init(
            jax.random.key(config.seed),
            zero_trajectory(config, observation_spec, self.agent))
        if self.cpu_lockstep:
            # init is a global-mesh program whose collectives would
            # otherwise still be draining when restore()'s has_any
            # broadcast posts its own ops.
            jax.block_until_ready(state)
        stages.enter("setup/restore")
        state = self._restore(state)

        stages.enter("setup/live_mfu")
        # The denominator is this PROCESS'S share of the mesh (local
        # devices), matching the local-batch numerator — each process
        # gauges its own chips' utilization, and the aggregator's MAX
        # fold shows the busiest process.
        _configure_live_mfu(
            self.ledger, lambda: self.lower_step(state),
            max(1, self.learner.mesh.devices.size // jax.process_count()))

        stages.enter("setup/env_groups")
        env_groups = make_env_groups(config, observation_spec.frame,
                                     num_agents=num_agents,
                                     level_names=level_names)
        if config.actor == "service":
            # Continuous-batching actor service (runtime/service.py):
            # same queue/get_trajectory surface as the pool, so the
            # prefetch stage and everything downstream are unchanged.
            from scalable_agent_tpu.runtime.service import ActorService

            if config.inference_mode != "structural":
                raise ValueError(
                    f"--actor=service owns its inference (one "
                    f"continuous-batching thread); inference_mode="
                    f"{config.inference_mode!r} applies to "
                    f"--actor=grouped only")
            self.pool = ActorService(
                self.agent, env_groups, config.unroll_length,
                level_name=config.level_name, seed=config.seed,
                max_batch=config.service_max_batch,
                max_restarts=config.actor_max_restarts)
        else:
            self.pool = ActorPool(
                self.agent, env_groups, config.unroll_length,
                level_name=config.level_name, seed=config.seed,
                inference_mode=config.inference_mode,
                observation_spec=observation_spec,
                fused_shards=config.accum_fused_shards,
                max_restarts=config.actor_max_restarts)
        self.pool.set_params(state.params)
        self.pool.start()

        # Device prefetch stage: stages the next batch while the current
        # update runs (the reference's StagingArea +1-step policy lag,
        # experiment.py:587-597).
        stages.enter("setup/prefetch_start")
        self.staged: queue_lib.Queue = queue_lib.Queue(maxsize=1)
        self.prefetch_thread = start_prefetch(
            self.pool, self.learner, self.staged, self.prefetch_stop)

        self.stall = StallAttributor(registry)
        self.actor_steps = registry.counter("actor/agent_steps_total")
        self.actor_steps_at_last_log = self.actor_steps.value
        self.actor_fps_gauge = registry.gauge(
            "actor/fps", "env frames/s generated by this host's actors")
        # Multi-task: per-level returns accumulated toward the TRAINING
        # suite score, cleared after each score like the reference
        # (experiment.py:652-667).
        self.suite_returns: Dict[str, List[float]] = (
            {name: [] for name in dmlab30.TRAIN_LEVELS}
            if multi_task else {})
        # Bounded in-flight dispatch (runtime/transport.py): the loop
        # blocks ("retire") only when the window fills, so the next
        # batch's staging overlaps the running update while
        # backpressure and per-update metrics ordering stay exact.
        inflight_updates = config.inflight_updates
        if inflight_updates > 1 and self.cpu_lockstep:
            log.warning(
                "inflight_updates=%d downgraded to 1: multi-process "
                "CPU (gloo) runs mispair collectives from overlapping "
                "update executions", inflight_updates)
            inflight_updates = 1
        self.inflight = InflightWindow(inflight_updates,
                                       registry=registry)
        self._enter_loop(state)
        return state

    def next_batch(self):
        watchdog = get_watchdog()
        # Disarm the learner heartbeat while blocked on the staged
        # queue: starvation is the stall attributor's domain, and a
        # wedged UPSTREAM thread's own stale heartbeat names the
        # culprit — the learner waiting on it is a symptom.
        watchdog.suspend("learner")
        with self.timing.time_avg("wait_batch"), \
                self.interval.add_time("wait_batch"), \
                get_tracer().span("learner/wait_batch", cat="learner"):
            traj = self.staged.get()
        watchdog.touch("learner")
        if isinstance(traj, Exception):
            raise traj
        # The batch's provenance record; the in-flight window owns its
        # end (retire stamps + close, or the rollback discard's
        # retired=False close).
        return traj, self.ledger.lookup(id(traj))

    def dispatch(self, state, trajectory, ledger_id, updates):
        state, dispatched = self.learner.update(state, trajectory)
        if ledger_id is not None:
            self.ledger.stamp(ledger_id, "dispatch")
        self.inflight.push(dispatched, ledger_id=ledger_id)
        if self.cpu_lockstep:
            # Materialize the WHOLE update before the loop can reach
            # another cross-process point (decision broadcast, save
            # collective): metrics resolving does not mean the
            # program's last all-reduce has drained.
            jax.block_until_ready(state)
        return state, dispatched, trajectory

    def replay_update(self, state, trajectory, dispatched):
        state, dispatched = self.learner.update(state, trajectory,
                                                fresh=False)
        self.inflight.push(dispatched, ledger_id=None)
        return state, dispatched

    def retire(self, dispatched):
        if not self.inflight.full:
            return None
        # Materialize the OLDEST in-flight update's metrics (FIFO, so
        # the logged metrics always belong to a known update and
        # env_frames accounting is exact); this is the loop's only
        # device wait — in a multi-process run it materializes the
        # cross-host all-reduce, so a peer lost mid-update surfaces
        # (and is attributed) here.
        with self.timing.time_avg("retire"), \
                self.interval.add_time("retire"), \
                self.fleet.collective("retire_update"):
            return self.inflight.retire()

    def after_update(self, state, updates):
        self.pool.set_params(state.params, version=updates)

    def publish_extras(self, host_metrics, elapsed_s):
        config = self.config
        stats = self.pool.episode_stats()
        if stats:
            host_metrics["episode_return"] = float(
                np.mean([r for r, _ in stats]))
            host_metrics["episode_frames"] = float(
                np.mean([l for _, l in stats])
                * config.num_action_repeats)
        # Per-level attribution (reference logs <level>/episode_return
        # and /episode_frames per episode, experiment.py:634-650;
        # interval means here).
        suite_returns = self.suite_returns
        for level, entries in self.pool.drain_level_stats().items():
            host_metrics[f"{level}/episode_return"] = float(
                np.mean([r for r, _ in entries]))
            host_metrics[f"{level}/episode_frames"] = float(
                np.mean([l for _, l in entries])
                * config.num_action_repeats)
            bare = (level[len("dmlab_"):]
                    if level.startswith("dmlab_") else level)
            if bare in suite_returns:
                suite_returns[bare].extend(r for r, _ in entries)
        if suite_returns and min(
                len(v) for v in suite_returns.values()) >= 1:
            # Every level reported since the last score: emit the
            # capped/uncapped human-normalized TRAINING score and clear
            # (reference: experiment.py:652-667).
            host_metrics["dmlab30/training_no_cap"] = (
                dmlab30.compute_human_normalized_score(
                    suite_returns, per_level_cap=None))
            host_metrics["dmlab30/training_cap_100"] = (
                dmlab30.compute_human_normalized_score(
                    suite_returns, per_level_cap=100.0))
            log.info(
                "dmlab30 training score — no cap: %.2f cap 100: %.2f",
                host_metrics["dmlab30/training_no_cap"],
                host_metrics["dmlab30/training_cap_100"])
            self.suite_returns = {
                name: [] for name in dmlab30.TRAIN_LEVELS}
        # Actor fps beside the learner's: the learner's consumption
        # rate can hide an actor surplus or deficit that the queue
        # currently masks.
        actor_steps = self.actor_steps.value
        actor_fps = ((actor_steps - self.actor_steps_at_last_log)
                     * config.num_action_repeats / elapsed_s)
        self.actor_steps_at_last_log = actor_steps
        self.actor_fps_gauge.set(actor_fps)
        host_metrics["actor_fps"] = actor_fps
        # Stall attribution over THIS interval's stage sums — after
        # the ledger's derivation, so the verdict line carries this
        # interval's dominant-stage share.
        interval = self.interval.summary()
        category, evidence = self.stall.attribute(
            interval.get("wait_batch", 0.0),
            interval.get("update", 0.0),
            retire_s=interval.get("retire", 0.0))
        return category, evidence, (
            f" | actors {actor_fps:.0f} fps | "
            f"{StallAttributor.describe(category, evidence)}")

    def publish_telemetry(self):
        if self.learner is not None:
            self.learner.publish_device_telemetry()

    def adopt(self, learner, agent):
        # Flush the old hot path's devtel before dropping it.  The
        # prefetch thread keeps the old learner's transport; its placed
        # trajectories feed the new learner unchanged (computation
        # follows data).
        self.learner.publish_device_telemetry()
        self.learner, self.agent = learner, agent

    def after_rollback(self, state, updates):
        # Drop in-flight metrics (without blocking on them) and
        # republish the restored weights.
        self.inflight.discard()
        self.pool.set_params(state.params, version=updates)

    def lower_step(self, state):
        config = self.config
        example = zero_trajectory(
            config, self.observation_spec, self.agent,
            batch=max(1, config.batch_size // jax.process_count()),
            t_plus_1=config.unroll_length + 1)
        return self.learner.lower_update(state, example)

    def drain(self, dispatched):
        return self.inflight.drain()

    def stop(self):
        self.prefetch_stop.set()
        if self.pool is not None:
            self.pool.stop()
        if self.prefetch_thread is not None:
            self.prefetch_thread.join(timeout=5)


# How many fused dispatches may be unretired before the fused backend
# forces one materialization to retire them: safely under the ledger's
# 8192 open-record capacity, and high enough that the log-interval
# fetch almost always fires first.
_INGRAPH_PENDING_CAP = 2048


class _FusedBackend(_Backend):
    """Device worlds: rollout + update as ONE jitted program per
    dispatch (runtime/ingraph.py; K = ``--updates_per_dispatch`` fused
    updates per launch), for levels whose simulator is expressible in
    XLA (envs/device/, the DEVICE_LEVELS registry).  Replaces the whole
    host actor pipeline the reference is built around
    (experiment.py:479-672) with zero per-step host<->device traffic;
    the Learner, the checkpoints, the metric names, the LR schedule and
    resume are the host backend's.  Single-process.  There is no
    in-flight window: a dispatch's ledger record stays open until the
    loop next waits for the device (publish, profiling-window close,
    the cap above, the end), so birth->retire is the true
    dispatch-to-materialization latency of the fused stream."""

    name = "ingraph"

    def __init__(self, config, stages):
        super().__init__(config, stages)
        self.updates_per_step = config.updates_per_dispatch
        self.trainer = self.carry = None
        # Dispatched, not yet known to have run.
        self.pending_tids: List[int] = []

    def _make_trainer(self):
        config = self.config
        return InGraphTrainer(
            self.agent, self.learner, self.env, config.unroll_length,
            config.batch_size, seed=config.seed,
            # Replay and the sentinel's shadow audit both consume the
            # dispatch's trajectory: either turns emission on.
            emit_trajectory=self.emitting,
            updates_per_dispatch=config.updates_per_dispatch)

    def setup(self):
        from scalable_agent_tpu.envs.device import make_device_env

        stages = self.stages
        # The first stage after setup/config also pays the backend's
        # start-up where this process has not touched jax yet.
        stages.enter("setup/compile_cache")
        config = self.config
        # jax.distributed is never initialized here, so check the
        # config flags too — process_count() alone is still 1 even
        # when the user asked for a distributed run, and silently
        # training P independent duplicate runs into one logdir would
        # be far worse than this error.
        if (jax.process_count() > 1 or config.distributed_coordinator
                or config.distributed_num_processes > 0):
            raise ValueError(
                "train_backend=ingraph is single-process (the host "
                "backend covers multi-host training)")
        if config.actor == "service":
            raise ValueError(
                "train_backend=ingraph has no host actor pipeline; "
                "--actor=service applies to the host backend")
        if config.replay_ratio > 0 and config.updates_per_dispatch > 1:
            raise ValueError(
                "replay_ratio > 0 requires --updates_per_dispatch=1: "
                "replayed updates interleave with fresh ones between "
                "dispatches (runtime/ingraph.py)")
        if config.sentinel_interval > 0 and config.updates_per_dispatch > 1:
            raise ValueError(
                "sentinel_interval > 0 requires --updates_per_dispatch=1: "
                "the shadow audit snapshots state at update granularity "
                "(runtime/sentinel.py)")
        _attach_trace_file(config)
        self._arm()
        config = self.config

        # Probe the HOST twin of the level so action/observation specs
        # stay in lock-step with the device env.  For the fake family
        # the twin is the mirrored envs/fake.py implementation; for
        # device-native levels (device_*) it is the HostDeviceEnv
        # adapter driving the same transition function.
        stages.enter("setup/probe_env")
        observation_spec, action_space, _ = probe_env(config)
        stages.enter("setup/build_agent")
        self.agent = build_agent(config, action_space,
                                 observation_spec.frame.shape)
        stages.enter("setup/device_env")
        self.env = make_device_env(
            config.level_name, height=config.height, width=config.width,
            # Composite spaces have no .n; make_device_env rejects their
            # levels with a clear error before num_actions matters.
            num_actions=getattr(action_space, "n", 0),
            num_action_repeats=config.num_action_repeats,
            with_instruction=config.use_instruction)
        host_frame = tuple(observation_spec.frame.shape)
        device_frame = tuple(self.env.observation_spec.frame.shape)
        if host_frame != device_frame:
            raise ValueError(
                f"host/device observation drift: host frame {host_frame} "
                f"!= device mirror {device_frame} (envs/fake.py and "
                f"envs/device/ must stay in lock-step)")

        stages.enter("setup/build_learner")
        self._build_learner(action_space, observation_spec.frame.shape)
        self.emitting = (config.replay_ratio > 0
                         or config.sentinel_interval > 0)
        self.trainer = self._make_trainer()
        stages.enter("setup/trainer_init")
        state, self.carry = self.trainer.init(jax.random.key(config.seed))
        # The device env's rollout restarts from fresh episodes on a
        # restore, like the host pipeline's env processes.
        stages.enter("setup/restore")
        state = self._restore(state)

        stages.enter("setup/observability")
        self._bring_up_services()
        stages.enter("setup/live_mfu")
        # XLA's cost analysis counts a lax.scan body ONCE whatever its
        # trip count, so the lowered flops cover one update while a
        # retired ledger record covers K.
        _configure_live_mfu(
            self.ledger, lambda: self.lower_step(state),
            self.learner.mesh.devices.size,
            updates_per_execution=self.updates_per_step)
        # What is left before the loop (the health plane, the metrics
        # writer and its TensorBoard import) is a stage of its own, not
        # the gauge's.
        stages.enter("setup/loop_entry")
        self._enter_loop(state)
        return state

    def next_batch(self):
        return None, self.ledger.open("ingraph", self.config.level_name)

    def dispatch(self, state, trajectory, ledger_id, updates):
        tracer = get_tracer()
        with tracer.span("learner/train_step", cat="learner",
                         args=({"update": updates}
                               if tracer.enabled else None)):
            # The update counter keys the rollout rng
            # (jax.random.fold_in), so resume continues the exact
            # action-sampling stream the interrupted run would have
            # used.  Called through the instance attribute each time.
            out = self.trainer.train_step(state, self.carry,
                                          np.int32(updates))
        self.ledger.stamp(ledger_id, "dispatch")
        self.pending_tids.append(ledger_id)
        state, self.carry, dispatched = out[:3]
        return state, dispatched, (out[3] if self.emitting else None)

    def replay_insert(self, trajectory):
        # No transport in this backend, so no packed buffer to store:
        # the unroll's device-born Trajectory goes straight into the
        # slab (the per-leaf slabs carry the batch sharding the rollout
        # constrains).
        self.replay.insert(trajectory)

    def replay_update(self, state, trajectory, dispatched):
        state, telemetry, replayed = self.trainer.replay_step(
            state, self.carry.telemetry, trajectory)
        self.carry = self.carry._replace(telemetry=telemetry)
        # The replayed dict carries loss keys only: the FRESH step's
        # metrics keep the log line's episode stats, with the loss
        # readings of the last replayed update (the freshest params).
        return state, dict(dispatched, **replayed)

    def retire(self, dispatched):
        # Bound the open-record stream: a run fast enough to dispatch
        # thousands of steps inside one log interval would overflow the
        # ledger's open-record table and trip its eviction path.
        if len(self.pending_tids) >= _INGRAPH_PENDING_CAP:
            jax.block_until_ready(dispatched["total_loss"])
            self.synced()
        return dispatched

    def synced(self):
        for tid in self.pending_tids:
            self.ledger.close(tid, retired=True)
        self.pending_tids.clear()

    def fetch(self, metrics):
        """Per-unroll episode means appear only when episodes actually
        finished, and frames are simulator frames (agent steps x
        num_action_repeats): the host backend's contract, for the
        logged rows and the returned dict alike."""
        host_metrics = super().fetch(metrics)
        # What the agent's forward pass reports of itself (the expert
        # layers' load) is a gauge under its own name as well.
        for name in self.agent.STATS:
            if name in host_metrics:
                get_registry().gauge(name).set(host_metrics[name])
        if host_metrics.pop("episodes_completed", 0) < 1:
            host_metrics.pop("episode_return", None)
            host_metrics.pop("episode_frames", None)
        elif "episode_frames" in host_metrics:
            host_metrics["episode_frames"] *= (
                self.config.num_action_repeats)
        return host_metrics

    def publish_telemetry(self):
        # Env episodes + learner update instruments ride the donated
        # carry.
        if self.trainer is not None:
            self.trainer.publish_telemetry(self.carry)

    def adopt(self, learner, agent):
        # Rebuild the fused trainer around the demoted learner.  The
        # rollout carry is env-side state and rides through unchanged —
        # the rollout rng is keyed by the update counter, so the action
        # stream stays continuous — and device telemetry rides the
        # carry, so it survives the swap as it is.
        self.learner, self.agent = learner, agent
        self.trainer = self._make_trainer()

    def after_rollback(self, state, updates):
        # The rollout carry is env-side state, not params: it rides
        # through the rollback like the host backend's env processes
        # do.  The in-graph streak peak is the abandoned timeline's.
        if self.carry.streak_peak is not None:
            self.carry = self.carry._replace(
                streak_peak=jnp.zeros((), jnp.float32))

    def lower_step(self, state):
        return self.trainer.train_step.lower(state, self.carry,
                                             np.int32(0))

    def drain(self, dispatched):
        if self.pending_tids and dispatched:
            # One final materialization retires every still-pending
            # record (otherwise finalize() would sweep real retires as
            # "abandoned").
            jax.block_until_ready(dispatched["total_loss"])
            self.synced()
        return None

    def after_teardown(self, trace_path, state):
        _write_op_scopes(trace_path, self.trainer, state, self.carry)


def _run(backend: _Backend) -> Dict[str, float]:
    """The training loop: set the backend up, step it until
    ``total_environment_frames``, tear everything down.  One iteration
    is one fresh dispatch, the replayed updates that chase it, the
    profiling windows' ends, the publish when the log interval has
    elapsed, and the decision point (rollback, preemption, checkpoint)
    every process reaches on the same iteration."""
    b = backend
    stages = b.stages
    profiling = False
    completed = False
    trace_path = None
    state = None
    metrics = {}
    try:
        state = b.setup()
        config, fleet, ledger, health = b.config, b.fleet, b.ledger, b.health
        sentinel, replay, ckpt = b.sentinel, b.replay, b.ckpt
        registry, prom = b.obs.registry, b.obs.prom
        nonfinite, writer = b.nonfinite, b.writer
        timing, interval = b.timing, b.interval
        watchdog = get_watchdog()
        injector = get_fault_injector()  # the one setup armed
        learner_fps_gauge = registry.gauge(
            "learner/fps", "env frames/s consumed by the learner")
        start_updates = updates = b.start_updates
        frames_per_step = config.frames_per_update() * b.updates_per_step
        # The restored TrainState's env_frames (which drives the LR
        # schedule) is authoritative — recomputing from the CURRENT
        # config would silently disagree if batch_size/unroll_length/
        # num_action_repeats changed between runs.
        frames = _host_scalar(state.env_frames)
        last_log = b.log_clock_start
        frames_at_last_log = frames
        profile_stop_at = None
        rollback_wanted = False
        dispatched = {}
        # Compile windows are recovery/startup cost, not wedges: the
        # first dispatch (cold or relaunch compile) and the re-jit
        # after a sentinel ladder demotion (~13s measured) run with the
        # learner heartbeat suspended — the same treatment rollback
        # restore gets — so a tight --watchdog_timeout_s doesn't read
        # them as hangs.  The post-dispatch touch re-arms.
        rejit_pending = True
        while frames < config.total_environment_frames:
            if (config.profile_dir and profile_stop_at is None
                    and not health.window_open
                    and updates - start_updates
                    >= config.profile_start_update):
                # Device-level tracing (SURVEY §5.1): --profile_dir
                # captures a jax.profiler trace of updates
                # [profile_start_update, +profile_num_updates) — the
                # capture the kernel ledger joins below.  >=, not ==:
                # ``updates`` advances in strides (K per dispatch, the
                # replayed updates) that need not land on the start;
                # the one-shot gate is the still-None profile_stop_at.
                jax.profiler.start_trace(config.profile_dir)
                # Host spans annotate into the device capture only
                # while it records (TraceAnnotation is ~100x a span;
                # see Tracer.set_annotate).
                get_tracer().set_annotate(True)
                profiling = True
                profile_stop_at = updates + config.profile_num_updates
            traj, ledger_tid = b.next_batch()
            audit_snap = None
            if sentinel is not None and sentinel.audit_due(updates):
                # Pre-update snapshot for the shadow audit below: the
                # step donates its input state, so the audit needs its
                # own buffers.
                audit_snap = sentinel.snapshot(state)
            if rejit_pending:
                watchdog.suspend("learner")
                rejit_pending = False
                if updates == start_updates:
                    stages.enter("setup/first_dispatch")
            with timing.time_avg("update"), interval.add_time("update"):
                state, dispatched, traj = b.dispatch(
                    state, traj, ledger_tid, updates)
                # Chaos: a deterministic mid-run slowdown (thermal
                # throttle / noisy neighbor stand-in) the health plane
                # must catch — occurrences count fresh dispatches.
                # Inside the update timing block so the stall
                # attributor reads it as a slow device.
                if injector.active and injector.should_fire(
                        "throughput_sag"):
                    time.sleep(throughput_sag_s())
            watchdog.touch("learner")
            if stages.open == "setup/first_dispatch":
                # Set-up ends here (the first dispatch blocks through
                # the step's compile); the startup-cost beacon for the
                # supervisor's MTTR decomposition goes out with it.
                stages.done()
                _write_mttr_breakdown(config, stages)
            if audit_snap is not None:
                # Shadow audit: recompute this batch's grads + param
                # delta through the reference arm on device and compare
                # (one D2H bool at audit cadence).  Runs BEFORE the
                # replayed updates below so the delta compare sees the
                # fresh update's params, and may demote the ladder.
                # The reference arm's own compile (first audit) and the
                # compare are recovery machinery, not progress the
                # heartbeat should time — suspend like rollback
                # restore; the touch below re-arms.
                watchdog.suspend("learner")
                with timing.time_avg("audit"), interval.add_time("audit"):
                    state = sentinel.audit(audit_snap, traj, state,
                                           updates)
                if sentinel.consume_swap():
                    # Adopt the demoted learner.  The replay slab's
                    # lineage is suspect (filled by the breached path)
                    # — drop it and re-warm.  The demoted rung re-jits
                    # inside the next dispatch: suspend across it too.
                    b.adopt(sentinel.learner, sentinel.agent)
                    if replay is not None:
                        replay.flush()
                    rejit_pending = True
                watchdog.touch("learner")
            if replay is not None:
                b.replay_insert(traj)
            # The size gate covers the re-warm-up window after a
            # rollback/demotion flush: until the slab holds a batch
            # again the replayed updates are skipped (fresh training
            # continues at ratio 0) rather than sampling an empty ring.
            if replay is not None and replay.size >= 1:
                # The off-policy dial: R replayed updates behind every
                # fresh batch — on-device sample + update.
                for _ in range(config.replay_ratio):
                    with timing.time_avg("update"), \
                            interval.add_time("update"), \
                            get_tracer().span("learner/replay_update",
                                              cat="learner"):
                        state, dispatched = b.replay_update(
                            state, replay.sample(), dispatched)
                    updates += 1
                    metrics = b.retire(dispatched) or metrics
                    watchdog.touch("learner")
            b.after_update(state, updates)
            updates += b.updates_per_step
            frames += frames_per_step
            metrics = b.retire(dispatched) or metrics
            watchdog.touch("learner")
            scheduled_ends = profiling and updates >= profile_stop_at
            if scheduled_ends or (health.window_open
                                  and updates >= health.window_stop_at):
                # A profiling window (the scheduled one, or an
                # anomaly's) ends.  Retire what the wait materializes
                # NOW, before the harvest's AOT compile of the step at
                # its production shape (multi-minute on TPU) would
                # inflate its birth->retire stamps by compile time the
                # updates never saw; and disarm the learner heartbeat
                # across that compile like every other healthy long
                # pause — the next loop touch re-arms.
                jax.block_until_ready(dispatched["total_loss"])
                b.synced()
                watchdog.suspend("learner")
                lower_fn = functools.partial(b.lower_step, state)
                if scheduled_ends:
                    jax.profiler.stop_trace()
                    get_tracer().set_annotate(False)
                    profiling = False
                    log.info("profiler trace written to %s",
                             config.profile_dir)
                    # The scheduled window doubles as the health
                    # plane's baseline: anomaly windows report their
                    # worst-kernel delta against it.
                    health.note_baseline(_harvest_kernel_ledger(
                        config, lower_fn, executions=_whole_dispatches(
                            config.profile_num_updates,
                            b.updates_per_step)))
                else:
                    # Into kernels.<anomaly_id>.json and back into the
                    # anomaly record.
                    health.close_window(
                        lower_fn, executions=_whole_dispatches(
                            config.health_window_updates,
                            b.updates_per_step))

            now = time.monotonic()
            if now - last_log >= config.log_interval_s:
                # The log-time fetches drain the device queue and the
                # publishes run with nothing dispatched — until the
                # next dispatch the chip idles, which is why the whole
                # block and each part of it is a span.
                tracer = get_tracer()
                with tracer.span("driver/log_publish", cat="log",
                                 args=({"update": updates}
                                       if tracer.enabled else None)):
                    if not metrics:
                        # Nothing has been retired yet (the first W-1
                        # updates of an in-flight window): log the
                        # newest dispatched update rather than an empty
                        # dict.
                        metrics = dispatched
                    # Right after an audit or ladder demotion the
                    # device queue carries the recovery path's
                    # compiles.  That wait is device backlog, not a
                    # wedged learner: disarm across the fetch section;
                    # the touch after ledger.publish re-arms.
                    watchdog.suspend("learner")
                    with tracer.span("log/fetch_metrics", cat="log"):
                        host_metrics = b.fetch(metrics)
                        if metrics is dispatched:
                            b.synced()
                    # Only RECORD the verdict here: the log gate runs
                    # on local wall clocks, and acting inside it would
                    # let multi-host processes enter the collective
                    # restore on different iterations.  The rollback
                    # itself happens at the fixed per-iteration point
                    # below.
                    if nonfinite.observe(host_metrics):
                        rollback_wanted = True
                    elapsed = now - last_log
                    fps = (frames - frames_at_last_log) / elapsed
                    host_metrics["fps"] = fps
                    learner_fps_gauge.set(fps)
                    # Machine-readable timing snapshot (Timing.summary):
                    # the same numbers as the log line.
                    timing_summary = timing.summary()
                    host_metrics.update(
                        {f"timing/{k}": v
                         for k, v in timing_summary.items()})
                    with tracer.span("log/telemetry", cat="log"):
                        b.publish_telemetry()
                        if sentinel is not None:
                            sentinel.publish()
                    # Rates/rho/staleness/MFU land in the registry and
                    # ride the writer/prom dumps below.
                    with tracer.span("log/ledger", cat="log"):
                        ledger.publish()
                    watchdog.touch("learner")
                    category, evidence, note = b.publish_extras(
                        host_metrics, elapsed)
                    interval.clear()
                    # Health detectors over the registry stream plus
                    # this interval's host metrics, with the verdict
                    # and ledger attribution captured at trip time; a
                    # fresh trip may arm a profiling window, opened
                    # here (next update onward profiles) unless the
                    # scheduled window is live.
                    with tracer.span("log/health", cat="log"):
                        if health.active:
                            health.step(
                                {**registry.snapshot(), **host_metrics},
                                update=updates, verdict=category,
                                evidence=evidence)
                            if not profiling:
                                health.maybe_open_window(updates)
                    with tracer.span("log/write", cat="log"):
                        if writer is not None:
                            writer.write(updates, host_metrics)
                            # Registry snapshot rows (obs/ prefix): the
                            # per-interval devtel/learn/* series
                            # obs.report and obs.diagnose read.
                            writer.write_registry(updates)
                    with tracer.span("log/prom", cat="log"):
                        if prom is not None:
                            prom.dump()
                    log.info(
                        "update %d frames %.3g fps %.0f loss %.3f "
                        "return %s | %s%s",
                        updates, frames, fps,
                        host_metrics.get("total_loss", float("nan")),
                        f"{host_metrics.get('episode_return', float('nan')):.2f}",
                        " ".join(f"{k} {v:.4f}s"
                                 for k, v in timing_summary.items()),
                        note)
                    last_log, frames_at_last_log = now, frames
            # Rollback AND preemption decisions at a point EVERY
            # process reaches on the SAME iteration, with the
            # coordinator's verdict broadcast — the divergent-local-
            # clocks discipline maybe_save applies to its save decision
            # — so the collective restore inside _rollback_or_exit (or
            # the coordinated preemption drain) is entered by all
            # processes together.  The multi-host broadcast is gated on
            # the update counter (identical on every process, unlike
            # wall clocks) every 8 updates, so the hot loop doesn't pay
            # a second per-update collective; the added detection
            # latency is dwarfed by the log-interval gate above for
            # rollback and by the grace window for preemption.  A
            # SIGTERM'd process must NOT act on its local flag alone:
            # entering the final-save collective while peers keep
            # training is exactly the unpaired-collective hang this
            # layer exists to prevent — the KV flag carries the signal
            # to the coordinator, whose broadcast verdict commits
            # everyone at once.
            do_rollback = rollback_wanted
            rollback_reason = "nonfinite"
            do_preempt = fleet.preemption_requested()
            # Param fingerprint at the decision-broadcast cadence: an
            # update-counter gate, so the multi-process allgather below
            # is issued on the same iteration everywhere.  A single
            # process has no peer to compare against — the gauge (and
            # the replica_diverge chaos point's occurrence counting)
            # still ride it.
            fingerprint = None
            if sentinel is not None and updates % 8 == 0:
                fingerprint = sentinel.local_fingerprint(state.params)
            if jax.process_count() > 1:
                do_rollback = do_preempt = False
                if updates % 8 == 0:
                    from jax.experimental import multihost_utils

                    with fleet.collective("decision_broadcast"):
                        verdict = multihost_utils.broadcast_one_to_all(
                            np.asarray([rollback_wanted,
                                        fleet.preemption_requested()]))
                        if fingerprint is not None:
                            gathered = multihost_utils.process_allgather(
                                np.asarray([fingerprint], np.float64))
                    do_rollback = bool(verdict[0])
                    do_preempt = bool(verdict[1])
                    if (fingerprint is not None
                            and sentinel.check_fingerprints(gathered)):
                        # Replicas disagree bit-exact: SDC or a
                        # divergent replica.  Every process sees the
                        # same gathered set, so every process reaches
                        # this verdict together — no extra broadcast.
                        do_rollback = True
                        rollback_reason = "sentinel"
            if sentinel is not None and sentinel.rollback_pending:
                # An audit breach survived the full degradation ladder:
                # the sentinel wants the newest verified checkpoint.
                # The audit cadence is update-counter gated, so every
                # process set this flag on the same iteration —
                # SPMD-consistent without a broadcast.
                do_rollback = True
                rollback_reason = "sentinel"
            if do_preempt:
                # Coordinated preemption drain: fall through to the
                # normal shutdown tail below — in-flight updates
                # drained, ONE forced verified checkpoint (whose
                # internal broadcast/allgather every process now
                # reaches together), clean exit 0.  The fleet monitor's
                # grace deadline bounds this whole tail with exit 72.
                fleet.note_preempt_decision(updates)
                log.warning(
                    "preemption drain: stopping at update %d "
                    "(%.3g frames) for the coordinated final "
                    "checkpoint", updates, frames)
                break
            if do_rollback:
                rollback_wanted = False
                state, updates, frames = _rollback_or_exit(
                    config, ckpt, b.learner, state, nonfinite,
                    reason=rollback_reason,
                    exit_code=(SENTINEL_EXIT_CODE
                               if rollback_reason == "sentinel"
                               else NONFINITE_EXIT_CODE))
                # Nothing from the abandoned timeline may leak forward:
                # not its metrics, and not the replay slab (stale-
                # lineage samples must not feed post-restore updates;
                # the off-policy dial re-warms from fresh batches).
                metrics = {}
                if replay is not None:
                    replay.flush()
                if sentinel is not None and rollback_reason == "sentinel":
                    sentinel.note_rollback()
                b.after_rollback(state, updates)
                last_log = time.monotonic()
                frames_at_last_log = frames
                interval.clear()
                continue
            if ckpt.maybe_save(updates, state):
                # The membership verdict (fleet_epoch.json) names the
                # newest resumable step — the elastic supervisor's
                # answer to "where will the resharded fleet resume".
                fleet.note_checkpoint(updates)
        # Disarm before the shutdown tail (final forced checkpoint,
        # pool joins, writer close): a slow-but-healthy shutdown must
        # not read as a stalled_thread wedge — and must never be
        # os._exit'ed mid-checkpoint under --watchdog_abort.
        watchdog.suspend("learner")
        # The returned metrics are the NEWEST update's.
        metrics = b.drain(dispatched) or metrics
        if ckpt.maybe_save(updates, state, force=True):
            fleet.note_checkpoint(updates)
        completed = True
        trace_path = get_tracer().path  # the teardown closes the tracer
    finally:
        # Membership verdict FIRST: an exception unwinding a
        # multi-process run is usually a peer's death arriving as an
        # aborted collective, and jax's own client fatal (SIGABRT) can
        # end this process anywhere in the teardown below — the
        # elastic supervisor's epoch-stamped verdict must already be
        # on disk by then (fleet.note_fatal_error no-ops on clean
        # exits, single-process runs, and when the monitor's richer
        # verdict already landed).
        import sys as _sys

        _exc = _sys.exc_info()[1]
        if _exc is not None and not isinstance(
                _exc, (SystemExit, KeyboardInterrupt)):
            get_fleet().note_fatal_error(_exc)
        # Disarm the watchdog for the WHOLE teardown tail — the
        # exception path skips the loop-exit suspend above, and pool
        # joins/writer/ckpt closes must never be os._exit(70)'d by a
        # heartbeat that simply stopped because the run is ending.
        # (The exception dump in _teardown_observability still runs.)
        configure_watchdog(None)
        configure_faults("")  # chaos spec must not outlive its run
        if profiling:
            jax.profiler.stop_trace()
        # Construction may have failed partway: clean up whatever
        # exists (None-guards), and always flush/close the obs state.
        # Health teardown BEFORE the obs teardown's final prom dump so
        # health/* counters land in the last snapshot.
        if b.health is not None:
            b.health.finalize()
        b.stop()
        # Ledger finalize AFTER the pipeline threads stopped (no new
        # stamps) and BEFORE the final prom dump, so the snapshot shows
        # the swept state: in-pipeline records closed as abandoned,
        # zero open records on a clean exit, ledger.p<proc>.json on
        # disk.
        if b.ledger is not None:
            try:
                b.ledger.finalize()
            except Exception:
                log.exception("ledger finalize failed")
        # Final telemetry publish BEFORE the final prom dump, on both
        # exit paths: a run (or run tail) shorter than log_interval_s
        # never hit the interval gate, and the final metrics.prom would
        # show devtel/* absent or frozen at the last fetch.  Guarded —
        # on the exception path the device buffers may be donated
        # husks, or the set-up never got as far as having any.
        try:
            b.publish_telemetry()
        except Exception:
            log.exception("final device-telemetry publish failed")
        if b.sentinel is not None:
            try:
                b.sentinel.publish()
            except Exception:
                log.exception("final sentinel-telemetry publish failed")
        if b.writer is not None:
            b.writer.close()
        if b.ckpt is not None:
            b.ckpt.close()
        if b.obs is not None:
            _teardown_observability(b.config, b.obs)
        if completed and jax.process_count() > 1:
            # No process may exit (tearing down the coordination
            # service) until every process finished its checkpoint IO.
            # Skipped on the EXCEPTION path: a failed process must not
            # block in a barrier its healthy peers (stuck inside their
            # own collectives) can never reach — dying fast surfaces
            # the error and unblocks everyone.
            from jax.experimental import multihost_utils

            with get_fleet().collective("train_exit_barrier"):
                multihost_utils.sync_global_devices("train_exit")
        # Fleet teardown LAST: peer-loss detection and the preemption
        # grace deadline must cover the whole teardown tail — a peer
        # dying during the final save or exit barrier is still a
        # bounded exit 72, not a hang.
        configure_fleet(None)
    b.after_teardown(trace_path, state)
    return b.fetch(metrics)


def _whole_dispatches(updates: int, updates_per_step: int) -> int:
    """The updates a profiling window of ``updates`` really holds: it
    runs whole dispatches, ceil(updates / K) of them.  (The lowered
    step's flops are ONE update's whatever K — see
    ``_configure_live_mfu`` — so the harvest wants the update count.)"""
    return -(-updates // updates_per_step) * updates_per_step


def _eval_loop(envs, config: Config, agent: ImpalaAgent, params, step_fn,
               num_episodes: int) -> List[float]:
    """Drive any MultiEnv-protocol fleet (initial/step_send/step_recv)
    under one jitted [B] inference call until ``num_episodes`` episodes
    complete.

    Fixed per-slot episode quota: taking the global first-N completions
    would overrepresent short episodes (fast finishers complete more
    often), biasing mean returns vs the reference's one-env sequential
    protocol.  Each slot contributes at most ceil(N / B) episodes."""
    batch = envs.num_envs
    quota = -(-num_episodes // batch)
    counts = np.zeros((batch,), np.int64)
    returns: List[float] = []
    try:
        output = envs.initial()
        core_state = agent.initial_state(batch)
        action = np.asarray(agent.zero_actions(batch))
        rng = jax.random.key(config.seed)
        step_index = 0
        while len(returns) < num_episodes:
            step_index += 1
            agent_out, core_state = step_fn(
                params, jax.random.fold_in(rng, step_index), action,
                output, core_state)
            action = np.asarray(agent_out.action)
            envs.step_send(action)
            output = envs.step_recv()
            for i in np.nonzero(np.asarray(output.done))[0]:
                if (int(output.info.episode_step[i]) > 0
                        and counts[i] < quota):
                    counts[i] += 1
                    returns.append(float(output.info.episode_return[i]))
    finally:
        envs.close()
    return returns[:num_episodes]


def _eval_level(config: Config, agent: ImpalaAgent, params, step_fn,
                level_name: str, frame_spec: TensorSpec,
                num_episodes: int) -> List[float]:
    """Collect ``num_episodes`` returns with a BATCHED eval fleet: a
    MultiEnv of ``test_batch_size`` envs stepped under one jitted [B]
    inference call (the reference evaluates batch-1 synchronously,
    experiment.py:691-701 — this is the same protocol at fleet width)."""
    batch = max(1, min(num_episodes, config.test_batch_size))
    fns = [
        functools.partial(
            make_impala_stream, level_name,
            seed=config.seed * 977 + 131 * i,
            num_action_repeats=config.num_action_repeats,
            # One directory per (level, env slot): parallel recorders
            # must never interleave episode indices in one dir.
            record_to=(os.path.join(config.record_to, level_name,
                                    f"env_{i:02d}")
                       if config.record_to else ""),
            **env_kwargs(config, level_name))
        for i in range(batch)
    ]
    envs = MultiEnv(fns, frame_spec,
                    num_workers=min(batch, config.test_num_workers))
    return _eval_loop(envs, config, agent, params, step_fn, num_episodes)


def _eval_multi_agent(config: Config, agent: ImpalaAgent, params, step_fn,
                      num_agents: int, num_episodes: int) -> List[float]:
    """Self-play eval for lockstep multi-agent levels: K matches of A
    agents, every slot driven by the SAME policy under one jitted [K*A]
    call; per-slot episode returns pool into the result (the reference
    has no multi-agent eval at all — this goes beyond parity).
    """
    from scalable_agent_tpu.envs.doom.multiplayer import (
        DEFAULT_UDP_PORT,
        MultiAgentVectorEnv,
    )

    matches = max(1, config.test_batch_size // num_agents)
    if matches * num_agents != config.test_batch_size:
        # Eval batch is throughput sizing, not a correctness property
        # (unlike the training batch, where make_env_groups raises) —
        # round down to whole matches, loudly.
        log.info(
            "test_batch_size %d is not a multiple of num_agents %d; "
            "evaluating %d matches (%d agent slots)",
            config.test_batch_size, num_agents, matches,
            matches * num_agents)
    # Globally-unique port residue classes across a multi-process job
    # (same invariant make_env_groups enforces for training), and eval
    # seeds DECORRELATED from training's seed formula (977/131 mixing,
    # like _eval_level) so eval matches never replay trained env seeds.
    proc = jax.process_index()
    total = matches * jax.process_count()
    stride = match_port_scheme(total)
    envs = MultiAgentVectorEnv([
        functools.partial(
            create_env, config.level_name,
            num_action_repeats=config.num_action_repeats,
            seed=config.seed * 977 + 131 * (proc * matches + m),
            port_base=DEFAULT_UDP_PORT + stride * (proc * matches + m),
            port_increment=stride * total,
            # One directory per (level, match); the multiplayer factory
            # adds per-player subdirs beneath it, so parallel matches
            # and players never interleave episode streams (role of
            # the reference's record path, env_wrappers.py:433-497).
            record_to=(os.path.join(
                config.record_to, config.level_name,
                f"match_{proc * matches + m:02d}")
                if config.record_to else None),
            **env_kwargs(config))
        for m in range(matches)
    ])
    return _eval_loop(envs, config, agent, params, step_fn, num_episodes)


def test(config: Config) -> Dict[str, List[float]]:
    """Evaluate a checkpoint: test_num_episodes per level, batched.

    ``--level_name=dmlab30`` evaluates the FULL suite (every DMLab-30
    test variant) and emits capped/uncapped human-normalized suite
    scores to the log and ``<logdir>/eval_scores.json``
    (reference: experiment.py:675-708 + :716-717).
    """
    config = apply_env_overrides(config)
    setup_compile_cache()
    # The network architecture is a property of the CHECKPOINT, not of
    # the eval-time level: adopt the trained run's architecture fields
    # from its persisted config so e.g. a no-instruction checkpoint
    # evaluates under --level_name=dmlab30 (whose env override would
    # otherwise grow an instruction tower the restore can't match).
    # ONLY param-tree-shaping fields are adopted — execution knobs
    # (core_impl/dtypes) restore fine either way and must stay CLI-
    # controllable, e.g. evaluating a pallas-trained checkpoint with
    # --core_impl=xla on a CPU-only host.
    saved_path = os.path.join(config.logdir, "config.json")
    if os.path.exists(saved_path):
        saved = Config.load(saved_path)
        config = dataclasses.replace(
            config, torso_type=saved.torso_type,
            use_instruction=saved.use_instruction,
            # The loss shapes the TrainState (--loss=impact carries a
            # target network): the restore TEMPLATE must match the
            # checkpoint's generation so the structure retry in
            # runtime/checkpoint.py stays the exception, not the rule.
            loss=saved.loss)
    suite = config.level_name == "dmlab30"
    level_names = ([f"dmlab_{name}" for name in dmlab30.TEST_LEVELS]
                   if suite else [config.level_name])

    probe_config = (dataclasses.replace(config, level_name=level_names[0])
                    if suite else config)
    observation_spec, action_space, num_agents = probe_env(probe_config)
    agent = build_agent(config, action_space,
                        observation_spec.frame.shape)

    # Restore against a structure template so optimizer-state NamedTuples
    # come back typed (only params are used here, but the checkpoint holds
    # the full TrainState).
    mesh = make_mesh(MeshSpec(data=len(jax.devices()), model=1))
    hp = LearnerHyperparams()
    learner = Learner(agent, hp, mesh, config.frames_per_update())
    template = learner.init(
        jax.random.key(0),
        zero_trajectory(probe_config, observation_spec, agent))
    ckpt = CheckpointManager(config.logdir)
    restored = ckpt.restore(target=template)
    if restored is None:
        raise FileNotFoundError(
            f"no checkpoint under {config.logdir}/checkpoints")
    _, host_state = restored
    params = jax.device_put(host_state.params)

    step_fn = jax.jit(
        lambda params, rng, action, env_output, state: actor_step(
            agent, params, rng, action, env_output, state))

    level_returns: Dict[str, List[float]] = {}
    if num_agents > 1:
        # Self-play multi-agent eval (suite levels are never
        # multi-agent, so this is always the single-level path).
        returns = _eval_multi_agent(
            config, agent, params, step_fn, num_agents,
            config.test_num_episodes)
        level_returns[config.level_name] = returns
        log.info("multi-agent level %s: mean self-play return %.2f "
                 "over %d agent-episodes",
                 config.level_name, float(np.mean(returns)),
                 len(returns))
        return level_returns
    for level_name in level_names:
        returns = _eval_level(
            config, agent, params, step_fn, level_name,
            observation_spec.frame, config.test_num_episodes)
        level_returns[level_name] = returns
        log.info("level %s: mean return %.2f over %d episodes",
                 level_name, float(np.mean(returns)), len(returns))

    if suite:
        # Scoring keys are bare test-level names (reference:
        # dmlab30.py:186-218).
        by_level = {name[len("dmlab_"):]: r
                    for name, r in level_returns.items()}
        no_cap = dmlab30.compute_human_normalized_score(
            by_level, per_level_cap=None)
        cap_100 = dmlab30.compute_human_normalized_score(
            by_level, per_level_cap=100.0)
        log.info("suite score — no cap: %.2f  cap 100: %.2f",
                 no_cap, cap_100)
        scores_path = os.path.join(config.logdir, "eval_scores.json")
        os.makedirs(config.logdir, exist_ok=True)
        with open(scores_path, "w") as f:
            json.dump({
                "human_normalized_no_cap": no_cap,
                "human_normalized_cap_100": cap_100,
                "episodes_per_level": config.test_num_episodes,
                "mean_returns": {k: float(np.mean(v))
                                 for k, v in by_level.items()},
            }, f, indent=2)
        log.info("suite scores written to %s", scores_path)
    else:
        # Single-level runs can't produce the full-suite score; log the
        # per-level normalized value (reference computes the suite mean,
        # experiment.py:703-708).  Registry names carry the dmlab_
        # prefix; the score tables hold bare level names.
        bare = (config.level_name[len("dmlab_"):]
                if config.level_name.startswith("dmlab_")
                else config.level_name)
        if bare in dmlab30.ALL_LEVELS:
            returns = level_returns[config.level_name]
            record = dmlab30.LEVELS.get(
                bare, dmlab30._BY_TEST_NAME.get(bare))
            if record:
                normalized = (np.mean(returns) - record.random) / (
                    record.human - record.random) * 100.0
                log.info("human-normalized: %.2f%%", normalized)
    return level_returns


def main(argv: Optional[Sequence[str]] = None):
    """The CLI entry point.  Returns what the mode produced — train's
    final metrics, test's per-level returns — so a caller driving the
    CLI in-process (chip_smoke.py) reads the run's result, not only
    its exit."""
    t_entry_ns = time.perf_counter_ns()  # where a run's timeline starts
    config = Config.from_argv(argv, description=__doc__)
    if config.mode == "train":
        if config.elastic:
            # Elastic supervisor mode (runtime/elastic.py): this
            # process owns N worker fleets across membership epochs
            # instead of training itself — it must never initialize a
            # jax backend (on TPU that would lock the chips its
            # workers need).
            from scalable_agent_tpu.runtime.elastic import (
                run_supervised,
            )

            code = run_supervised(config)
            if code:
                raise SystemExit(code)
            return None
        return train(config, t_entry_ns)
    if config.mode == "test":
        return test(config)
    raise ValueError(f"unknown mode {config.mode!r}")


if __name__ == "__main__":
    main()
