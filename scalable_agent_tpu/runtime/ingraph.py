"""Fused in-graph training: rollout + IMPALA update as ONE device program.

With an on-device environment (envs/device/) the whole actor side —
T agent-inference steps, T env transitions, trajectory assembly — plus
the learner update compiles into a single jitted function.  A train step
involves NO host↔device data movement at all (the host only dispatches),
so chained dispatches stream to the device back-to-back; metrics are
fetched on whatever cadence the caller wants.

Per-update semantics match the host pipeline:

- Trajectory layout is the reference's T+1 overlap layout (first entry of
  unroll k+1 == last entry of unroll k, reference: experiment.py:311-321)
  via the rollout carry.
- The trajectory's frames — all but a thousandth of its bytes — are
  written ONCE, by the rollout, where the update reads them
  (``_Slots``): one buffer of T+1 slots rides the donated carry,
  slot 0 takes the carry's frame (the overlap entry), scan step t
  writes slot t+1 in place, and the buffer's order is the order of the
  update's merged ``[(T+1)*B]`` frames, so the merge is a bitcast.
  Stacked as the scan's ``ys`` they were written three times — the
  stack, a concatenate for the overlap entry, a transposing copy for
  the merge — 8% of a step on one chip and 10% on four that computed
  nothing (ISSUE 29).  Every other leaf (under 1 MB together) is still
  stacked and concatenated.
- The rollout runs under the params of the CURRENT state, i.e. zero
  policy lag.  The host pipeline has >= 1 update of lag (the reference's
  queue + staging design, experiment.py:531,587-597); V-trace corrects
  for the behaviour/target gap in both cases, so this only shifts where
  on the on/off-policy spectrum the data sits.
- So the update's forward starts with work the rollout has just done,
  on the same frames under the same parameters, and an agent may say
  what of it the acting step hands over (``agent.handover_collection``;
  the shallow conv agent behind the Pallas stem hands its stem
  activation, a sixth of that cell's step recomputed otherwise —
  ISSUE 37; every other agent hands nothing and compiles to the step
  it had).  Scan step t acts on slot t's frame and writes what it
  sowed to slot t of one more ``_Slots`` buffer per leaf; slot T, the
  last env step's frame, which no acting step of THIS unroll sees,
  takes one more acting step's after the scan, of which only the sown
  part is live code.  The buffers are made inside the step and handed
  to ``Learner._update_impl`` beside the trajectory, never in it and
  never in ``TrainCarry``: a carried buffer is alive at the step's
  peak (the stem weight gradient's operands, late in the backward)
  where the update's own copy of that tensor is already dead, and a
  trajectory leaf would go out through ``emit_trajectory`` to updates
  that run under other parameters (``_replay_step``).
"""

from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from scalable_agent_tpu.envs.device import (
    env_telemetry_spec,
    record_episode_telemetry,
)
from scalable_agent_tpu.models.agent import ImpalaAgent, actor_step
from scalable_agent_tpu.obs.device_telemetry import (
    TelemetryPublisher,
    fetch_merged,
    merge_init,
)
from scalable_agent_tpu.obs.registry import get_registry
from scalable_agent_tpu.obs.trace import get_tracer
from scalable_agent_tpu.ops import distributions
from scalable_agent_tpu.parallel.mesh import (
    batch_sharding,
    layout_hint,
    replicated_sharding,
)
from scalable_agent_tpu.runtime.faults import get_fault_injector
from scalable_agent_tpu.runtime.learner import Learner, Trajectory
from scalable_agent_tpu.types import AgentOutput


class RolloutCarry(NamedTuple):
    """Everything that flows from one unroll into the next, all [B]."""

    env_state: object
    env_output: object  # StepOutput
    agent_output: AgentOutput
    # the agent's state, whatever pytree it declares
    # (``agent.initial_state``): the IMPALA agents' LSTM carry, the
    # token policy's attention cache
    core_state: object


class TrainCarry(NamedTuple):
    """The fused step's full donated carry: the rollout state plus the
    device-telemetry pytree (obs/device_telemetry.py) — env episode
    instruments and the learner's update instruments accumulate inside
    the same jitted program, in the same donated buffers, and the host
    fetches them only at log-interval cadence.  This is how the fused
    megastep keeps a live obs plane with zero per-update host sync."""

    rollout: RolloutCarry
    telemetry: Dict
    # The WORST consecutive non-finite-skip streak seen inside the
    # megaloop since the host last acted on it (f32 scalar; None — an
    # empty pytree node — when the finite guard is off).  TrainState
    # carries the streak at the LAST update of a dispatch, so with
    # K = updates_per_dispatch > 1 a streak that reaches the rollback
    # tolerance mid-dispatch and then resets (one finite update) would
    # be invisible at the dispatch boundary — up to K-1 skips past the
    # documented trigger.  The peak is monotone across scan iterations
    # AND across dispatches, surfaced as
    # ``metrics['nonfinite_streak_peak']``; the host's NonFiniteTracker
    # takes max(streak, peak), and the driver resets the peak to 0 on
    # rollback (the only action that forgives a tolerance breach).
    streak_peak: Any = None
    # The trajectory's frame buffer (``_Slots``): T+1 slots the
    # rollout fills in place and the update reads where they lie.  It
    # is SCRATCH — every slot is overwritten before anything reads it,
    # so no step depends on what it held on entry — and rides the
    # donated carry only so that it is born once: made inside the step
    # it cost a 536 MB fill every step and, living from the rollout's
    # first write to the stem's weight gradient, 0.55 GiB of the
    # compiler's heap (AOT, ISSUE 29).  Nothing saves the carry.
    frames: Any = None


def _stack_first(first, seq):
    """[B] entry + [T, B] sequence -> [T+1, B]."""
    return jax.tree_util.tree_map(
        lambda f, r: None if f is None else jnp.concatenate(
            [f[None], r], axis=0),
        first, seq, is_leaf=lambda x: x is None)


# Rows of a TPU tile.  The compiled update keeps a few-channel conv's
# merged ``[(T+1)*B, H, W, C]`` input and output with the batch in the
# lanes and, sharing the tiles of 8 x 128 with it, the channels where
# they fill whole tiles, else W: the frames ``u8[N,72,96,3]`` minor to
# major N, W, C, H; the stem's activation ``bf16[N,18,24,32]`` N, C, W,
# H (read off the compiled step, benchmark/aot.py's lowering).
_SUBLANES = 8
# By the axis of ``[B, H, W, C]`` that shares the tiles: how a leaf, that
# axis split in (tiles, rows), goes into a slot, and how the buffer
# ``[H, <the other axis>, tiles, T+1, 8, B]`` comes back out as
# ``[T+1, B, H, W, C]`` with the axis still split.
_SLOT_ORDERS = {2: ((1, 4, 2, 3, 0), (3, 5, 0, 2, 4, 1)),
                3: ((1, 2, 3, 4, 0), (3, 5, 0, 1, 2, 4))}


class _Slots:
    """One ``[B, ...]`` leaf of the rollout as ONE buffer of T+1 slots
    that the rollout fills in place, in the physical order the update
    reads: the trajectory's frames, and what an agent's acting step
    hands the update.

    The frames are nearly all of a trajectory's bytes (536 MB of 537 at
    256 envs of 72x96x3), and stacked as the scan's ``ys`` they were
    written three times: by the scan, time-major; by ``_stack_first``'s
    concatenate, to make room for the overlap entry; and by a
    transposing copy, because the update's ``[T+1, B] -> [(T+1)*B]``
    merge wants T INSIDE ``[H][C][W/8]``, directly above the (8 x B)
    tiles, and a time-major stack has it outermost (ISSUE 29: 3.26 ms
    of a 40.75 ms step that compute nothing).  Here a slot is written
    with a ``dynamic_update_slice`` (XLA updates a while-carried buffer
    in place, which is how ``ys`` are stacked anyway), and the buffer's
    logical shape is ``[H, C, W/8, T+1, 8, B]`` for the frames and
    ``[H, W, C/8, T+1, 8, B]`` for a leaf whose channels fill the
    sublanes: its row-major order IS the order of the merged leaf, so
    ``stacked()``'s transpose back to ``[T+1, B, H, W, C]`` and the
    agent's merge compile to bitcasts.  ``write`` asks for the buffer
    to stay row-major, or the compiler may turn it to the order of the
    scan's own value for the length of the loop and pay two whole
    copies for that (the ResNet's step did, AOT).

    The order comes from the leaf's shape alone.  A leaf that is not
    ``[B, H, W, C]`` with C or W a whole number of sublanes (the 10x10
    and 15x15 worlds' frames) has no such split: its slots are stacked
    time-major, and the compiler inserts whatever copy it inserted
    before.  Values never depend on the order."""

    def __init__(self, leaf_shape, slots: int, mesh):
        self._shape = tuple(leaf_shape)
        self._slots = slots
        self._split = next(
            (axis for axis in (3, 2) if len(self._shape) == 4
             and self._shape[axis] % _SUBLANES == 0), None)
        if self._split is not None:
            height, other = self._shape[1], self._shape[5 - self._split]
            self._slot_axis, batch_axis = 3, 5
            self._buffer_shape = (
                height, other, self._shape[self._split] // _SUBLANES,
                slots, _SUBLANES, self._shape[0])
        else:
            self._slot_axis, batch_axis = 0, 1
            self._buffer_shape = (slots,) + self._shape
        # The buffer is sharded as the rollout is: over its batch axis.
        self.sharding = batch_sharding(mesh, batch_axis)

    def _tiles(self):
        """``[B, H, W, C]`` with the split axis in (tiles, rows)."""
        split = self._split
        return (self._shape[:split]
                + (self._shape[split] // _SUBLANES, _SUBLANES)
                + self._shape[split + 1:])

    def empty(self, dtype):
        """The buffer, all slots blank, born on its own devices: made
        whole on one chip first, a four-chip buffer is that chip's
        high-water mark for the life of the process (5.43 GiB where
        the step holds 2.18; my chip run, PR 29)."""
        return jnp.zeros(self._buffer_shape, dtype, device=self.sharding)

    def unwritten(self, dtype):
        """The buffer for a step that makes it, writes every slot and
        reads it, all inside one program: allocated, not filled (on a
        TPU; zeros elsewhere).  Filled, the stem activations' 715 MB
        were 0.99 ms of a 31.7 ms step (my chip run, PR 37)."""
        return jax.lax.empty(self._buffer_shape, dtype)

    def write(self, buffer, leaf, index):
        """``buffer`` with ``leaf`` ``[B, ...]`` in slot ``index``."""
        if self._split is not None:
            leaf = leaf.reshape(self._tiles()).transpose(
                _SLOT_ORDERS[self._split][0])
        buffer = jax.lax.dynamic_update_slice_in_dim(
            buffer, jnp.expand_dims(leaf, self._slot_axis), index,
            self._slot_axis)
        if self._split is not None:
            buffer = layout_hint(buffer, range(buffer.ndim))
        return jax.lax.with_sharding_constraint(buffer, self.sharding)

    def stacked(self, buffer):
        """The buffer as the ``[T+1, B, ...]`` stack of its slots."""
        if self._split is None:
            return buffer
        return buffer.transpose(_SLOT_ORDERS[self._split][1]).reshape(
            (self._slots,) + self._shape)


class InGraphTrainer:
    """Owns the fused (rollout + update) jitted step for a device env.

    ``env`` must expose ``initial(seeds) -> (env_state, StepOutput[B])``
    and ``step(env_state, action) -> (env_state, StepOutput[B])`` as pure
    jnp functions (see envs/device.DeviceFakeEnv).
    """

    def __init__(
        self,
        agent: ImpalaAgent,
        learner: Learner,
        env,
        unroll_length: int,
        batch: int,
        seed: int = 0,
        emit_trajectory: bool = False,
        updates_per_dispatch: int = 1,
    ):
        self._agent = agent
        self._learner = learner
        self._env = env
        self._unroll_length = unroll_length
        self._batch = batch
        self._seed = int(seed)
        # The multi-update megaloop: one device dispatch runs K =
        # updates_per_dispatch fused (rollout + update) iterations as a
        # lax.scan, so a cheap-env run is no longer bound by the
        # per-dispatch host overhead (the Python loop + runtime launch
        # path) — the measured fps measures the chip.  K == 1 keeps one
        # update per dispatch THROUGH THE SAME scan body, so K is a
        # pure batching knob: K updates are bit-exact with K dispatches
        # of 1 over the same total update count (tests/test_device_env
        # pins this golden property).
        self._updates_per_dispatch = int(updates_per_dispatch)
        if self._updates_per_dispatch < 1:
            raise ValueError(
                f"updates_per_dispatch must be >= 1, got "
                f"{updates_per_dispatch}")
        # Replay tap (runtime/replay.py): when set, train_step ALSO
        # returns the unroll's device-resident Trajectory so the driver
        # can insert it into the replay slab — extra HBM output, zero
        # host traffic.  Off (the default) the fused program is
        # unchanged.  Incompatible with K > 1: the replay dial samples
        # the slab BETWEEN fresh updates, which only exists between
        # dispatches.
        self._emit_trajectory = bool(emit_trajectory)
        if self._emit_trajectory and self._updates_per_dispatch > 1:
            raise ValueError(
                "emit_trajectory requires updates_per_dispatch == 1: "
                "replayed updates interleave with fresh ones on the "
                "host side, between dispatches")
        # Shard the rollout over the learner's data axis: one constraint
        # on the carry propagates through the scan, so env transitions
        # and agent inference compute on their batch shard's device
        # (PartitionSpec("data") shards axis 0 at any rank).
        self._batch_sharding = batch_sharding(
            learner.mesh, batch_axis_index=0)
        self._env_tel_spec = env_telemetry_spec()
        self._tel_specs = [self._env_tel_spec]
        # Every learner-owned spec rides the same merged carry dict:
        # the update counters AND the learning-dynamics plane
        # (devtel/learn/*), whose in-update observes accumulate across
        # all K megaloop iterations of a dispatch.
        self._tel_specs.extend(learner.devtel_specs)
        self._tel_publisher = TelemetryPublisher(self._tel_specs)
        self.train_step = self._handover = self._instrumented(
            jax.jit(self._fused, donate_argnums=(0, 1)))
        # Replayed-batch update: the learner's fresh=False specialization
        # with THIS trainer's merged telemetry pytree (donated likewise).
        self.replay_step = jax.jit(self._replay_step,
                                   donate_argnums=(0, 1))

    def compile_step_afresh(self, state, carry):
        """The fused step compiled from THIS program's text, metadata
        and all.  The persistent compile cache's key leaves op metadata
        out, so ``train_step`` may be running an executable that an
        older version of the program compiled — same ops, other scope
        names — and that executable's text then names the old scopes.
        Here the key takes the metadata in and the function is traced
        anew (a fresh wrapper: the jit's own caches would hand the old
        executable back).  Costs a trace, a lowering and, the first time
        for a version of the program, a compile; the result is not used
        to run anything."""
        def _fused(state, carry, counter):
            return self._fused(state, carry, counter)

        option = "jax_compilation_cache_include_metadata_in_key"
        before = getattr(jax.config, option)
        jax.config.update(option, True)
        try:
            return jax.jit(_fused, donate_argnums=(0, 1)).lower(
                state, carry, np.int32(0)).compile()
        finally:
            jax.config.update(option, before)

    # -- initialization ----------------------------------------------------

    def init(self, rng: jax.Array) -> Tuple[object, TrainCarry]:
        """(TrainState, TrainCarry) ready for ``train_step``."""
        seeds = np.arange(self._batch, dtype=np.int32) + self._seed
        env_state, env_output = self._env.initial(seeds)
        agent_output = AgentOutput(
            action=jnp.asarray(self._agent.zero_actions(self._batch)),
            policy_logits=jnp.zeros(
                (self._batch,
                 distributions.behaviour_size(self._agent.dist_spec)),
                jnp.float32),
            baseline=jnp.zeros((self._batch,), jnp.float32),
        )
        core_state = self._agent.initial_state(self._batch)
        carry = TrainCarry(
            rollout=RolloutCarry(env_state, env_output, agent_output,
                                 core_state),
            telemetry=merge_init(self._tel_specs),
            # None (an empty pytree node, nothing allocated) when the
            # finite guard is off — the carry structure then matches
            # pre-peak checkpointed runs byte-for-byte.
            streak_peak=(jnp.float32(0.0)
                         if self._learner._finite_guard else None),
            frames=self._frame_slots(env_output).empty(
                env_output.observation.frame.dtype))
        # Commit the carry to the placement the fused step hands back
        # (batch-sharded rollout state, replicated scalars): an
        # uncommitted first carry has different input types from every
        # later one, and the whole fused program compiled TWICE.
        replicated = replicated_sharding(self._learner.mesh)
        carry = jax.device_put(carry, TrainCarry(
            rollout=jax.tree_util.tree_map(
                lambda x: (self._batch_sharding if np.ndim(x)
                           else replicated), carry.rollout),
            telemetry=replicated,
            streak_peak=(None if carry.streak_peak is None
                         else replicated),
            frames=self._frame_slots(env_output).sharding))
        example = Trajectory(
            agent_state=core_state,
            env_outputs=_stack_first(
                env_output,
                jax.tree_util.tree_map(
                    lambda x: None if x is None else x[None],
                    env_output, is_leaf=lambda x: x is None)),
            agent_outputs=_stack_first(
                agent_output,
                jax.tree_util.tree_map(
                    lambda x: None if x is None else x[None],
                    agent_output, is_leaf=lambda x: x is None)),
        )
        with get_tracer().span("setup/learner_init", cat="setup"):
            state = self._learner.init(rng, example)
        return state, carry

    # -- the fused program -------------------------------------------------

    def _slots(self, leaf) -> _Slots:
        return _Slots(leaf.shape, self._unroll_length + 1,
                      self._learner.mesh)

    def _frame_slots(self, env_output) -> _Slots:
        return self._slots(env_output.observation.frame)

    def _rollout(self, params, carry: RolloutCarry, rng, frames):
        """One unroll: ``(trajectory, new carry, frames, handed)``.
        ``frames`` is the frame buffer (``TrainCarry.frames``), handed
        back filled with this unroll's T+1 frames; the trajectory's
        frame leaf is a view of it.  ``handed`` is what the acting
        steps sowed for an update UNDER ``params`` (the agent's
        ``handover_collection``), leaves ``[T+1, B, ...]``; None from
        an agent that hands nothing."""
        agent, env = self._agent, self._env
        handover = agent.handover_collection

        # The named scopes (here, ``telemetry`` and ``learner_update``
        # below, and the learner's own) land in the compiled HLO's
        # op_name metadata.  Two readers: the kernel ledger
        # (obs/kernels.py) attributes device time env-vs-inference-vs-
        # learner inside a device_bound verdict, and the benchmark's
        # scope reader (benchmark/lib/scopes.py, through the table
        # obs/kernels.write_op_scopes leaves beside a --trace run's
        # trace) splits a step's device time by layer.  They are
        # metadata: no op, fusion or number depends on them.
        slots = self._frame_slots(carry.env_output)
        with jax.named_scope("rollout"):
            # what the steps read, made once before the scan (a token
            # policy's matrices in its compute dtype; the IMPALA
            # agents' parameters as they are)
            params = agent.acting_params(params)

        def without_frame(env_output, frame=None):
            return env_output._replace(
                observation=env_output.observation._replace(frame=frame))

        def act(c, key):
            """The acting step on carry ``c``: ``(agent output, core
            state, what it hands the update)``."""
            with jax.named_scope("actor_inference"):
                result = actor_step(
                    agent, params, key, c.agent_output.action,
                    c.env_output, c.core_state, handover=handover)
            return result if handover else result + ({},)

        # A buffer per handed leaf, in the update's order as the frames
        # are, made here: alive from its first write to the update's
        # last read of it and no longer (see the module docstring).
        sown_shapes = (jax.eval_shape(act, carry, rng)[2] if handover
                       else {})
        handed_slots = jax.tree_util.tree_map(self._slots, sown_shapes)
        handed = jax.tree_util.tree_map(
            lambda leaf_slots, leaf: leaf_slots.unwritten(leaf.dtype),
            handed_slots, sown_shapes)

        def hand_over(handed, sown, index):
            return jax.tree_util.tree_map(
                lambda leaf_slots, buffer, leaf: leaf_slots.write(
                    buffer, leaf, index), handed_slots, handed, sown)

        def scan_fn(c, t):
            c, frames, handed = c
            out, core, sown = act(c, jax.random.fold_in(rng, t))
            with jax.named_scope("env_step"):
                env_state, env_output = env.step(c.env_state, out.action)
            # The frame goes to its slot of the one buffer; every other
            # leaf (under 1 MB together) is stacked as the scan's ys.
            frames = slots.write(
                frames, env_output.observation.frame, t + 1)
            # What the step sowed is of the frame it ACTED on: slot t.
            handed = hand_over(handed, sown, t)
            return (RolloutCarry(env_state, env_output, out, core),
                    frames, handed), (without_frame(env_output), out)

        with jax.named_scope("rollout"):
            # Slot 0: the overlap entry, the previous unroll's last.
            frames = slots.write(
                frames, carry.env_output.observation.frame, 0)
            (new_carry, frames, handed), (env_seq, agent_seq) = (
                jax.lax.scan(scan_fn, (carry, frames, handed),
                             jnp.arange(self._unroll_length)))
            if handover:
                # Slot T: the last env step's frame, which the NEXT
                # unroll's first step acts on, under the next
                # parameters.  One more acting step under these; only
                # what it sows is read, the rest is dead code.
                handed = hand_over(handed, act(new_carry, rng)[2],
                                   self._unroll_length)
        env_outputs = _stack_first(
            without_frame(carry.env_output), env_seq)
        trajectory = Trajectory(
            # the state at the unroll's START is what the update
            # unrolls from; an agent whose state is a cache hands back
            # the rollout's own buffers under the start's counters
            # instead of a copy (models/token_policy.py unroll_state)
            agent_state=agent.unroll_state(carry.core_state,
                                           new_carry.core_state),
            env_outputs=without_frame(env_outputs, slots.stacked(frames)),
            agent_outputs=_stack_first(carry.agent_output, agent_seq),
        )
        handed = jax.tree_util.tree_map(
            lambda leaf_slots, buffer: leaf_slots.stacked(buffer),
            handed_slots, handed) if handover else None
        return trajectory, new_carry, frames, handed

    def _constrain_batch(self, tree):
        return jax.tree_util.tree_map(
            lambda x: x if x is None or getattr(x, "ndim", 0) == 0
            else jax.lax.with_sharding_constraint(x, self._batch_sharding),
            tree, is_leaf=lambda x: x is None)

    def _one_update(self, state, rollout_carry, telemetry, update_index,
                    frames):
        """One fused (rollout + update) iteration — the megaloop's scan
        body.  ``update_index`` is the GLOBAL update counter (it keys
        the rollout rng), so K scanned iterations are the same stream
        as K separate dispatches."""
        rng = jax.random.fold_in(
            jax.random.key(self._seed), update_index)
        trajectory, new_rollout, frames, handed = self._rollout(
            state.params, rollout_carry, rng, frames)
        get_registry().gauge(
            "fused/stem_handed_share",
            "share of the frames an update reads whose stem activation "
            "an acting step had computed under the same parameters and "
            "handed over (T of the T+1 slots; the last one's is "
            "computed after the scan, for the update alone): 0 where "
            "the agent hands nothing").set(
                self._unroll_length / (self._unroll_length + 1.0)
                if handed is not None else 0.0)
        # Chaos (trace-time): the host backend's ``nan_grad`` hook
        # lives in Learner.update, which this fused path never calls —
        # bake the armed occurrence set into the compiled program and
        # match it against the GLOBAL update index on device instead
        # (faults.occurrences: 1-based, so occurrence n poisons update
        # index n-1's batch; not counted in faults/injected_total).
        injector = get_fault_injector()
        if injector.active:
            armed = sorted(injector.occurrences("nan_grad"))
            if armed:
                fire = jnp.any(jnp.asarray(armed, jnp.int32)
                               == update_index + 1)
                poison = jnp.where(fire, jnp.float32(float("nan")),
                                   jnp.float32(1.0))
                trajectory = trajectory._replace(
                    env_outputs=trajectory.env_outputs._replace(
                        reward=trajectory.env_outputs.reward * poison))
        # The [1:] slice drops the T+1 overlap entry (it was the
        # PREVIOUS unroll's last step — counting it again would
        # double-book every episode boundary), for both the metrics
        # accounting below and the device telemetry.
        emitted = jax.tree_util.tree_map(
            lambda t: None if t is None else t[1:],
            trajectory.env_outputs, is_leaf=lambda x: x is None)
        with jax.named_scope("telemetry"):
            telemetry = record_episode_telemetry(
                self._env_tel_spec, telemetry, emitted)
        with jax.named_scope("learner_update"):
            # ``handed`` holds only here: the rollout above and this
            # update read the one ``state.params``.
            new_state, telemetry, metrics = self._learner._update_impl(
                state, trajectory, telemetry, handed=handed)
        # Episode accounting from the on-device env stream (the host
        # backend reads MultiEnv ring buffers; here the trajectory
        # itself carries the emitted per-done episode stats), as SUMS so
        # the megaloop can fold them across the scan.
        done = emitted.done
        steps = emitted.info.episode_step
        finished = jnp.logical_and(done, steps > 0)
        episode_sums = {
            "count": jnp.sum(finished),
            "return_sum": jnp.sum(jnp.where(
                finished, emitted.info.episode_return, 0.0)),
            "frames_sum": jnp.sum(jnp.where(
                finished, steps, 0)).astype(jnp.float32),
        }
        return new_state, new_rollout, telemetry, metrics, \
            episode_sums, trajectory, frames

    def _fused(self, state, carry: TrainCarry, counter):
        # Only the rollout state takes the batch-sharding constraint:
        # the telemetry leaves are replicated scalars/bucket vectors
        # with no batch axis.
        rollout_carry = self._constrain_batch(carry.rollout)
        k = self._updates_per_dispatch

        def body(loop_carry, update_index):
            state, rollout_carry, telemetry, peak, frames = loop_carry
            (state, rollout_carry, telemetry, metrics, episode_sums,
             trajectory, frames) = self._one_update(
                state, rollout_carry, telemetry, update_index, frames)
            if peak is not None and "nonfinite_streak" in metrics:
                # The megaloop's tolerance contract: fold the
                # post-update streak into the monotone peak each
                # iteration, so a streak that breaches mid-dispatch
                # and then resets is still visible at the boundary.
                peak = jnp.maximum(peak, metrics["nonfinite_streak"])
            ys = (metrics, episode_sums)
            if self._emit_trajectory:
                ys = ys + (trajectory,)
            return (state, rollout_carry, telemetry, peak, frames), ys

        # K == 1 runs through the SAME scan body: lax.scan compiles the
        # body as its own while-loop computation at any length, so a
        # K-update dispatch is bit-exact with K single-update dispatches
        # (the golden property driver resume / the K knob rely on).
        (new_state, new_rollout, telemetry, peak, frames), ys = (
            jax.lax.scan(
                body,
                (state, rollout_carry, carry.telemetry, carry.streak_peak,
                 carry.frames),
                counter + jnp.arange(k, dtype=jnp.int32)))
        metrics_seq, episode_seq = ys[0], ys[1]
        # Scalar gauges (loss, lr, grad_norm, env_frames, ...) read the
        # LAST update's value — the state the dispatch hands back;
        # episode stats aggregate across all K unrolls.
        metrics = jax.tree_util.tree_map(lambda x: x[-1], metrics_seq)
        count = episode_seq["count"].sum()
        denom = jnp.maximum(count, 1).astype(jnp.float32)
        metrics["episodes_completed"] = count
        metrics["episode_return"] = episode_seq["return_sum"].sum() / denom
        metrics["episode_frames"] = episode_seq["frames_sum"].sum() / denom
        if peak is not None:
            metrics["nonfinite_streak_peak"] = peak
        out_carry = TrainCarry(new_rollout, telemetry, peak, frames)
        if self._emit_trajectory:
            # K == 1 (enforced in __init__): drop the length-1 scan
            # axis so the replay tap sees the plain [T+1, B] pytree.
            trajectory = jax.tree_util.tree_map(
                lambda x: x[0], ys[2])
            return new_state, out_carry, metrics, trajectory
        return new_state, out_carry, metrics

    def _replay_step(self, state, telemetry, trajectory):
        """One REPLAYED update (env_frames held, target-net schedule
        held — runtime/learner.py fresh=False).  Returns
        ``(new_state, new_telemetry, metrics)``; the caller rebinds the
        carry's telemetry."""
        return self._learner._update_impl(
            state, trajectory, telemetry, fresh=False)

    # -- host loop ---------------------------------------------------------

    def run(self, state, carry, num_updates: int, counter_start: int = 0,
            on_trajectory=None):
        """Dispatch ``num_updates`` chained fused steps WITHOUT any host
        synchronization; the caller decides when to fetch metrics (e.g.
        ``float(np.asarray(metrics['total_loss']))``).

        ``on_trajectory`` is the emitted-trajectory sink for an
        ``emit_trajectory=True`` trainer (e.g. ``replay.insert``): it
        receives the device-resident Trajectory of every dispatch.  An
        emitting trainer REFUSES to run without a sink — silently
        dropping emitted trajectories here once cost replay its data
        (the insert path and run() couldn't compose)."""
        if self._emit_trajectory and on_trajectory is None:
            raise ValueError(
                "this trainer emits trajectories (emit_trajectory="
                "True) but run() was given no on_trajectory sink; "
                "pass one (e.g. replay.insert) or drive train_step "
                "directly")
        k = self._updates_per_dispatch
        if num_updates % k:
            raise ValueError(
                f"num_updates {num_updates} not divisible by "
                f"updates_per_dispatch {k}")
        metrics = None
        for i in range(0, num_updates, k):
            result = self.train_step(
                state, carry, np.int32(counter_start + i))
            state, carry, metrics = result[:3]
            if self._emit_trajectory:
                on_trajectory(result[3])
        return state, carry, metrics

    # -- telemetry (host side, log-interval cadence) -----------------------

    def fetch_telemetry(self, carry: TrainCarry) -> dict:
        """Materialize every telemetry instrument riding ``carry`` —
        the obs plane's ONE device→host sync, a few hundred bytes."""
        return fetch_merged(self._tel_specs, carry.telemetry)

    def publish_telemetry(self, carry: TrainCarry) -> dict:
        """Fetch + fold into the metrics registry (``devtel/env/*`` and
        ``devtel/learner/*`` ride the normal prom/report path)."""
        fetched = self.fetch_telemetry(carry)
        self._tel_publisher.publish(fetched)
        self._handover.publish()
        return fetched

    # -- the hand-over -----------------------------------------------------

    @staticmethod
    def _instrumented(step):
        """``train_step``: the jitted step inside ``StepHandover``,
        which says what each call cost the host and what the device
        had left to run.  Callers (the driver, ``run``, a harness that
        wraps the attribute) dispatch through it; ``.lower`` is the
        jitted step's.  Down here, import and all, because everything
        above is traced into the step, and the compiled kernels' text
        holds the line numbers of what traced them: lines added above
        make every Mosaic kernel's serialized body, and with it the
        step's lowered text and its compile-cache key, another's."""
        from scalable_agent_tpu.runtime.handover import StepHandover

        return StepHandover(step)
