"""On-device trajectory accumulation for the actor runtime.

The structural ``VectorActor`` (runtime/actor.py) round-trips the full
agent output to the host every step and re-uploads the assembled
trajectory to the device for the learner — the host↔device link carries
every observation TWICE plus per-step logits/baselines, and the host pays
a blocking fetch latency for each of them.  On hardware where that link
is expensive (any TPU, and catastrophically so over a remote
attachment), the actor loop becomes link-bound, not compute-bound.

This module inverts the data flow, which is the idiomatic JAX answer:

- Per step the host uploads exactly TWO arrays — the frame batch as FLAT
  bytes (multi-dim uint8 ``device_put`` pays an order-of-magnitude layout
  penalty over some transports; reshape is free inside XLA) and one
  packed ``[4, B]`` f32 array of (reward, done, episode_return,
  episode_step) — and fetches exactly ONE: the sampled actions the
  simulators need.  Nothing else crosses.
- The jitted step writes the incoming env fields and the computed agent
  outputs into a device-resident ``[T+1, B, ...]`` trajectory buffer via
  donated in-place ``dynamic_update_slice``.
- At unroll end the buffer IS the learner's ``Trajectory`` — zero
  re-upload, zero host-side stacking — and a fresh buffer for the next
  unroll is seeded with the T+1 overlap entry (the reference's
  first-entry-is-last-entry layout, reference: experiment.py:311-321).

The trajectory layout, rng stream, and math are identical to the
structural path (tests/test_accum_actor.py asserts trajectory
equivalence), so the learner and V-trace see the same data either way.
"""

import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from scalable_agent_tpu.envs.vector import MultiEnv
from scalable_agent_tpu.obs import get_tracer, get_watchdog
from scalable_agent_tpu.obs.ledger import now_us as ledger_now_us
from scalable_agent_tpu.models.agent import (
    ImpalaAgent,
    actor_step,
    initial_state,
)
from scalable_agent_tpu.types import (
    ActorOutput,
    AgentOutput,
    AgentState,
    Observation,
    StepOutput,
    StepOutputInfo,
)


def _pack_env_fields(env_output: StepOutput) -> np.ndarray:
    """Small per-step env fields -> ONE [4, B] f32 host array (one upload
    instead of four; episode_step fits f32 exactly below 2^24)."""
    return np.stack([
        np.asarray(env_output.reward, np.float32),
        np.asarray(env_output.done, np.float32),
        np.asarray(env_output.info.episode_return, np.float32),
        np.asarray(env_output.info.episode_step, np.float32),
    ])


class AccumPrograms:
    """The jitted step/finish/bootstrap programs for one (agent, T, B,
    frame-shape) signature.  Build ONCE per ActorPool and share across
    groups so every group hits the same executable cache."""

    def __init__(self, agent: ImpalaAgent, unroll_length: int,
                 batch: int, frame_shape: Tuple[int, ...],
                 instruction_shape: Optional[Tuple[int, ...]] = None,
                 measurements_shape: Optional[Tuple[int, ...]] = None):
        self.agent = agent
        self.unroll_length = unroll_length
        self.batch = batch
        self.frame_shape = tuple(frame_shape)
        # Optional per-env trailing shapes for instruction token ids
        # (int32, language DMLab levels) and measurement vectors (f32,
        # Doom's additional-input wrapper) — when set, both ride the
        # per-step upload and get their own [T+1, B, ...] device
        # buffers, so language/measurement levels keep the accum path's
        # two-uploads-one-fetch link discipline.
        self.instruction_shape = (tuple(instruction_shape)
                                  if instruction_shape is not None else None)
        self.measurements_shape = (
            tuple(measurements_shape)
            if measurements_shape is not None else None)
        t1 = unroll_length + 1
        k = agent.num_action_components
        self._action_shape = (batch,) if k == 1 else (batch, k)
        self._bufs_shape = dict(
            frame=(t1, batch) + self.frame_shape,
            action=(t1,) + self._action_shape,
            logits=(t1, batch, agent.num_logits),
        )

        self.step = jax.jit(self._step_impl, donate_argnums=(5,))
        self.finish = jax.jit(self._finish_impl, donate_argnums=(3,))
        self.bootstrap = jax.jit(self._bootstrap_impl)

    # -- buffer pytree -----------------------------------------------------

    def _unpack(self, frame_flat, packed, extras):
        """(flat frame bytes, [4,B] f32, (instr?, meas?)) -> StepOutput
        batch.  ``extras`` members are None exactly when the matching
        shape is unconfigured (a static property of the programs)."""
        frame = frame_flat.reshape((self.batch,) + self.frame_shape)
        instruction, measurements = extras
        return StepOutput(
            reward=packed[0],
            info=StepOutputInfo(
                episode_return=packed[2],
                episode_step=packed[3].astype(jnp.int32)),
            done=packed[1] > 0.5,
            observation=Observation(frame=frame, instruction=instruction,
                                    measurements=measurements),
        )

    def _zero_bufs(self):
        t1 = self.unroll_length + 1
        b = self.batch
        return (
            StepOutput(
                reward=jnp.zeros((t1, b), jnp.float32),
                info=StepOutputInfo(
                    episode_return=jnp.zeros((t1, b), jnp.float32),
                    episode_step=jnp.zeros((t1, b), jnp.int32)),
                done=jnp.zeros((t1, b), bool),
                observation=Observation(
                    frame=jnp.zeros(self._bufs_shape["frame"], jnp.uint8),
                    instruction=(
                        jnp.zeros((t1, b) + self.instruction_shape,
                                  jnp.int32)
                        if self.instruction_shape is not None else None),
                    measurements=(
                        jnp.zeros((t1, b) + self.measurements_shape,
                                  jnp.float32)
                        if self.measurements_shape is not None else None)),
            ),
            AgentOutput(
                action=jnp.zeros(self._bufs_shape["action"], jnp.int32),
                policy_logits=jnp.zeros(
                    self._bufs_shape["logits"], jnp.float32),
                baseline=jnp.zeros((t1, b), jnp.float32),
            ),
        )

    @staticmethod
    def _write(bufs, slot, env_entry=None, agent_entry=None):
        """Write one [B, ...] entry at time index ``slot`` (traced)."""
        env_bufs, agent_bufs = bufs

        def put(buf, val):
            if buf is None:
                return None
            return jax.lax.dynamic_update_index_in_dim(
                buf, val.astype(buf.dtype), slot, axis=0)

        if env_entry is not None:
            env_bufs = jax.tree_util.tree_map(
                put, env_bufs, env_entry,
                is_leaf=lambda x: x is None)
        if agent_entry is not None:
            agent_bufs = jax.tree_util.tree_map(
                put, agent_bufs, agent_entry,
                is_leaf=lambda x: x is None)
        return (env_bufs, agent_bufs)

    # -- programs ----------------------------------------------------------

    def _bootstrap_impl(self, frame_flat, packed, extras):
        """First-ever entry: env slot 0 = initial output, agent slot 0 =
        zeros (reference: experiment.py:243-251)."""
        env_entry = self._unpack(frame_flat, packed, extras)
        agent_entry = AgentOutput(
            action=jnp.zeros(self._action_shape, jnp.int32),
            policy_logits=jnp.zeros(
                (self.batch, self.agent.num_logits), jnp.float32),
            baseline=jnp.zeros((self.batch,), jnp.float32),
        )
        return self._write(self._zero_bufs(), 0, env_entry, agent_entry)

    def _step_impl(self, params, seed, counter, slot, frame_flat, bufs,
                   packed, extras, core_state):
        """Iteration ``slot`` (1-based): the incoming env fields are
        entry ``slot-1``; the computed agent output is entry ``slot``.

        The last action feeding the model is read back from agent slot
        ``slot-1`` on device — it never crosses to the host."""
        env_entry = self._unpack(frame_flat, packed, extras)
        bufs = self._write(bufs, slot - 1, env_entry=env_entry)
        last_action = jax.lax.dynamic_index_in_dim(
            bufs[1].action, slot - 1, axis=0, keepdims=False)
        rng = jax.random.fold_in(jax.random.key(seed), counter)
        out, new_core = actor_step(
            self.agent, params, rng, last_action, env_entry, core_state)
        bufs = self._write(bufs, slot, agent_entry=out)
        return out.action, new_core, bufs

    def _finish_impl(self, frame_flat, packed, extras, bufs):
        """Seal the unroll: write env slot T (the output of the host env
        step taken AFTER the last inference), emit the trajectory, and
        seed the next unroll's buffers with the overlap entry."""
        t = self.unroll_length
        env_entry = self._unpack(frame_flat, packed, extras)
        traj = self._write(bufs, t, env_entry=env_entry)
        last_agent = jax.tree_util.tree_map(
            lambda x: None if x is None else x[t], traj[1],
            is_leaf=lambda x: x is None)
        next_bufs = self._write(
            self._zero_bufs(), 0, env_entry=env_entry,
            agent_entry=last_agent)
        return traj, next_bufs


def _h2d_bytes_counter():
    """The transport layer's shared upload-byte counter (one
    registration site, runtime/transport.py): the accum actors'
    per-step uploads and the learner-side packed trajectory staging
    both feed it."""
    from scalable_agent_tpu.runtime.transport import h2d_bytes_counter

    return h2d_bytes_counter()


def _fields_nbytes(fields) -> int:
    """Total bytes of one upload's (frame, packed, extras) payload."""
    import jax

    return sum(np.asarray(leaf).nbytes
               for leaf in jax.tree_util.tree_leaves(fields))


def _upload_fields(programs: AccumPrograms, env_output: StepOutput):
    """One env group's per-step host->device payload: (flat frame bytes,
    packed [4, B] f32, (instruction?, measurements?)).  Validates that
    the env's optional observation streams match the programs' static
    buffer configuration with a pointed error."""
    obs = env_output.observation
    if (obs.instruction is not None) != (
            programs.instruction_shape is not None):
        raise ValueError(
            "instruction observation/programs mismatch: the env "
            f"{'emits' if obs.instruction is not None else 'lacks'} "
            "instructions but AccumPrograms was built "
            f"{'without' if programs.instruction_shape is None else 'with'} "
            "instruction_shape (pass the observation_spec through "
            "ActorPool)")
    if (obs.measurements is not None) != (
            programs.measurements_shape is not None):
        raise ValueError(
            "measurements observation/programs mismatch: the env "
            f"{'emits' if obs.measurements is not None else 'lacks'} "
            "measurements but AccumPrograms was built "
            f"{'without' if programs.measurements_shape is None else 'with'} "
            "measurements_shape (pass the observation_spec through "
            "ActorPool)")
    extras = (
        None if obs.instruction is None
        else np.asarray(obs.instruction, np.int32),
        None if obs.measurements is None
        else np.asarray(obs.measurements, np.float32),
    )
    frame = np.asarray(obs.frame)
    return frame.reshape(-1), _pack_env_fields(env_output), extras


class AccumVectorActor:
    """One env group driven through the accumulation programs.

    Drop-in for ``VectorActor``: ``run_unroll(params) -> ActorOutput``
    whose array leaves live on device."""

    def __init__(
        self,
        programs: AccumPrograms,
        envs: MultiEnv,
        level_name: str = "",
        seed: int = 0,
    ):
        if envs.num_envs != programs.batch:
            raise ValueError(
                f"group size {envs.num_envs} != programs batch "
                f"{programs.batch}")
        self._p = programs
        self._envs = envs
        self.level_name = level_name
        self._seed = np.int32(seed)
        self._counter = 0
        self._bufs = None
        self._core_state = None
        self._last_env_host: Optional[StepOutput] = None
        from scalable_agent_tpu.runtime.actor import actor_stage_histograms

        self._h_env, self._h_infer = actor_stage_histograms()
        self._h2d_bytes = _h2d_bytes_counter()

    @staticmethod
    def _flat_frame(env_output: StepOutput) -> np.ndarray:
        frame = np.asarray(env_output.observation.frame)
        return frame.reshape(-1)  # free view; MultiEnv hands a fresh copy

    def _upload(self, env_output: StepOutput):
        fields = _upload_fields(self._p, env_output)
        self._h2d_bytes.inc(_fields_nbytes(fields))
        return fields

    def run_unroll(self, params) -> ActorOutput:
        # Ledger birth (obs/ledger.py): same contract as VectorActor —
        # the pool opens this unroll's provenance record at this stamp.
        self.unroll_birth_us = ledger_now_us()
        p = self._p
        if self._bufs is None:
            self._last_env_host = self._envs.initial()
            self._bufs = p.bootstrap(*self._upload(self._last_env_host))
            self._core_state = initial_state(
                p.batch, p.agent.core_size)

        first_state = AgentState(
            c=self._core_state.c, h=self._core_state.h)
        core_state = self._core_state
        bufs = self._bufs
        tracer = get_tracer()
        watchdog = get_watchdog()
        for slot in range(1, p.unroll_length + 1):
            watchdog.touch()  # per-step heartbeat: one dict store
            self._counter += 1
            t0 = time.perf_counter()
            # Inference = upload + dispatch + the blocking action fetch
            # (the single per-step host<->device round trip).
            with tracer.span("actor/inference", cat="actor"):
                frame_flat, packed, extras = self._upload(
                    self._last_env_host)
                action_dev, core_state, bufs = p.step(
                    params, self._seed, np.int32(self._counter),
                    np.int32(slot), frame_flat, bufs, packed, extras,
                    core_state)
                actions = np.asarray(action_dev)  # the ONLY per-step fetch
            t1 = time.perf_counter()
            with tracer.span("actor/env_step", cat="actor"):
                self._envs.step_send(actions)
                self._last_env_host = self._envs.step_recv()
            self._h_infer.observe(t1 - t0)
            self._h_env.observe(time.perf_counter() - t1)

        traj, self._bufs = p.finish(*self._upload(self._last_env_host),
                                    bufs)
        self._core_state = core_state
        env_bufs, agent_bufs = traj
        return ActorOutput(
            level_name=self.level_name,
            agent_state=first_state,
            env_outputs=env_bufs,
            agent_outputs=agent_bufs,
        )

    def reset(self):
        """Drop device buffers + host carry after a mid-unroll failure
        (the ActorPool retry path, mirroring VectorActor.reset): the
        donated step program may have consumed ``_bufs`` before the
        exception, so the next unroll must re-bootstrap rather than
        touch possibly-invalidated device memory."""
        resync = getattr(self._envs, "resync", None)
        if resync is not None:
            resync()
        self._bufs = None
        self._core_state = None
        self._last_env_host = None

    def close(self):
        self._envs.close()


def _stack_group_axis(trees):
    """List of k pytrees -> one pytree with a leading [k] axis."""
    return jax.tree_util.tree_map(
        lambda *xs: None if xs[0] is None else np.stack(xs),
        *trees, is_leaf=lambda x: x is None)


class GroupedAccumActor:
    """Cross-group co-dispatch: ALL k accum groups advance in lockstep
    through ONE vmapped device call per step, and all k groups' actions
    come back in ONE fused fetch.

    The plain accum path pays one dispatch + one blocking action fetch
    per group per step (runtime/accum_actor.py AccumVectorActor), so k
    groups cost ~k link round-trips per step even with thread overlap;
    the service path co-batches but round-trips full agent outputs
    (runtime/actor.py).  This merges the two designs — accum's
    upload-only link discipline with service's co-batching — so the
    per-step link cost is ~1 RTT regardless of k.  The trade: groups
    step in lockstep (the slowest group's env gates the batch), which
    is the right trade exactly when the link RTT, not env variance,
    dominates (any remote TPU attachment; the r3 rig measured
    70-120 ms blocking fetches).

    Trajectory layout, rng streams, and math are identical to
    ``AccumVectorActor`` with the same per-group seeds
    (tests/test_accum_actor.py asserts equivalence).
    """

    def __init__(self, programs: AccumPrograms, env_groups,
                 level_name: str = "", seeds=None):
        sizes = {envs.num_envs for envs in env_groups}
        if sizes != {programs.batch}:
            raise ValueError(
                f"group sizes {sorted(sizes)} != programs batch "
                f"{programs.batch}")
        self._p = programs
        self.envs_list = list(env_groups)
        self.level_name = level_name
        k = len(self.envs_list)
        if seeds is None:
            seeds = [1000 * i for i in range(k)]
        if len(seeds) != k:
            raise ValueError(f"{len(seeds)} seeds for {k} groups")
        self._seeds = np.asarray(seeds, np.int32)  # [k]
        self._counter = 0
        self._bufs = None
        self._core = None  # AgentState with [k, B, H] leaves
        self._last_outs = None  # k host StepOutputs
        from scalable_agent_tpu.runtime.actor import actor_stage_histograms

        self._h_env, self._h_infer = actor_stage_histograms()
        self._h2d_bytes = _h2d_bytes_counter()

        # One fused program per phase, vmapped over the group axis.
        # params/counter/slot are shared (in_axes None): lockstep means
        # every group is always at the same slot with the same weights.
        self.step = jax.jit(
            jax.vmap(programs._step_impl,
                     in_axes=(None, 0, None, None, 0, 0, 0, 0, 0)),
            donate_argnums=(5,))
        self.finish = jax.jit(
            jax.vmap(programs._finish_impl), donate_argnums=(3,))
        self.bootstrap = jax.jit(jax.vmap(programs._bootstrap_impl))

    def _stacked_upload(self):
        frames, packeds, extras = zip(*(
            _upload_fields(self._p, out) for out in self._last_outs))
        stacked = (np.stack(frames), np.stack(packeds),
                   _stack_group_axis(list(extras)))
        self._h2d_bytes.inc(_fields_nbytes(stacked))
        return stacked

    def run_unroll(self, params):
        """One lockstep unroll -> list of k ActorOutputs (one per
        group, each [T+1, B] on device)."""
        # One birth stamp for the whole lockstep unroll: all k groups'
        # trajectories share it (the pool opens k records from it).
        self.unroll_birth_us = ledger_now_us()
        p = self._p
        k = len(self.envs_list)
        if self._bufs is None:
            self._last_outs = [envs.initial() for envs in self.envs_list]
            self._bufs = self.bootstrap(*self._stacked_upload())
            single = initial_state(p.batch, p.agent.core_size)
            self._core = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (k,) + x.shape).copy(),
                single)

        first_core = self._core
        core, bufs = self._core, self._bufs
        tracer = get_tracer()
        watchdog = get_watchdog()
        for slot in range(1, p.unroll_length + 1):
            watchdog.touch()  # per-step heartbeat: one dict store
            self._counter += 1
            t0 = time.perf_counter()
            with tracer.span("actor/inference", cat="actor",
                             args={"groups": k}):
                frames, packeds, extras = self._stacked_upload()
                actions_dev, core, bufs = self.step(
                    params, self._seeds, np.int32(self._counter),
                    np.int32(slot), frames, bufs, packeds, extras, core)
                # ONE fetch for ALL groups
                actions = np.asarray(actions_dev)
            t1 = time.perf_counter()
            with tracer.span("actor/env_step", cat="actor"):
                for envs, group_actions in zip(self.envs_list, actions):
                    envs.step_send(group_actions)
                self._last_outs = [envs.step_recv()
                                   for envs in self.envs_list]
            self._h_infer.observe(t1 - t0)
            self._h_env.observe(time.perf_counter() - t1)

        traj, self._bufs = self.finish(*self._stacked_upload(), bufs)
        self._core = core
        env_bufs, agent_bufs = traj
        outputs = []
        for i in range(k):
            take = lambda x: None if x is None else x[i]
            outputs.append(ActorOutput(
                level_name=self.level_name,
                agent_state=AgentState(c=first_core.c[i],
                                       h=first_core.h[i]),
                env_outputs=jax.tree_util.tree_map(
                    take, env_bufs, is_leaf=lambda x: x is None),
                agent_outputs=jax.tree_util.tree_map(
                    take, agent_bufs, is_leaf=lambda x: x is None),
            ))
        return outputs

    def reset(self):
        """Mirror of AccumVectorActor.reset for the lockstep driver:
        re-align every group's env pipes and force a re-bootstrap (the
        vmapped step donates ``_bufs`` too)."""
        for envs in self.envs_list:
            resync = getattr(envs, "resync", None)
            if resync is not None:
                resync()
        self._bufs = None
        self._core = None
        self._last_outs = None

    def close(self):
        for envs in self.envs_list:
            envs.close()
