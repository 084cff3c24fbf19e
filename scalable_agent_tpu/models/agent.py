"""The IMPALA agent: conv torso + optional language LSTM + LSTM core + heads.

Functional parity with the reference ``Agent`` (reference:
experiment.py:109-237), re-designed for TPU/XLA:

- The reference unrolls the LSTM with a *Python loop over tf.unstack'd
  timesteps* because the per-step ``tf.where(done)`` state reset rules out
  CuDNN (reference: experiment.py:225-237 and its own comment).  Here the
  unroll is a single ``nn.scan``/``lax.scan`` — XLA compiles it to one fused
  on-device loop, and the done-reset is a multiply by ``(1 - done)`` (the
  initial state is zeros, so "reset to initial" == "zero the carry").

- The torso runs on the whole [T*B] flattened batch at once (one big conv
  batch for the MXU) instead of the reference's per-timestep BatchApply.
  Where a mesh shards B, the merge puts the shard index outermost
  (``batch_shards``), so the merged axis is sharded too and each device
  runs the torso over its own envs only.

- Sampling is separated from the forward pass: the model returns logits and
  baseline; ``actor_step`` samples with an explicit PRNG key (the reference
  samples with ``tf.multinomial`` inside ``_head``, experiment.py:205-208 —
  implicit-RNG ops don't exist in JAX).
"""

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from scalable_agent_tpu.models.instruction import InstructionEncoder
from scalable_agent_tpu.models.networks import HANDOVER, TORSOS
from scalable_agent_tpu.ops import distributions
from scalable_agent_tpu.types import (
    AgentOutput,
    AgentState,
    StepOutput,
    map_structure,
)

CORE_SIZE = 256  # reference: experiment.py:118


def initial_state(batch_size: int, core_size: int = CORE_SIZE) -> AgentState:
    """Zero LSTM carry.  (reference: experiment.py:120-121)"""
    return AgentState(
        c=jnp.zeros((batch_size, core_size), jnp.float32),
        h=jnp.zeros((batch_size, core_size), jnp.float32),
    )


class _CoreStep(nn.Module):
    """One LSTM-core step with done-triggered state reset.

    The reset happens *before* the cell step, using the done flag of the
    incoming env output — matching the reference exactly
    (reference: experiment.py:230-234).
    """

    features: int

    @nn.compact
    def __call__(self, carry, xs):
        torso_out, done = xs
        keep = (1.0 - done)[:, None]  # initial state is zeros ⇒ reset = zero
        carry = jax.tree_util.tree_map(lambda c: keep * c, carry)
        new_carry, y = nn.OptimizedLSTMCell(self.features, name="lstm")(
            carry, torso_out)
        return new_carry, y


class _GateParams(nn.Module):
    """One gate's kernel (+bias), mirroring the param tree that
    ``flax.linen.OptimizedLSTMCell`` builds via its DenseParams
    children — same names, shapes, and initializers, so both core
    implementations share one checkpoint format."""

    features: int
    in_features: int
    use_bias: bool
    kernel_init: Any

    @nn.compact
    def __call__(self):
        kernel = self.param("kernel", self.kernel_init,
                            (self.in_features, self.features))
        bias = (self.param("bias", nn.initializers.zeros_init(),
                           (self.features,))
                if self.use_bias else None)
        return kernel, bias


class _PallasCoreParams(nn.Module):
    """Declares the 8 OptimizedLSTMCell gate params (ii/if/ig/io input
    kernels, hi/hf/hg/ho recurrent kernels + biases) and returns them
    concatenated as (Wi [D,4H], Wh [H,4H], b [4H]) in (i,f,g,o) order —
    the layout ops/lstm_pallas.lstm_unroll consumes."""

    features: int
    in_features: int

    @nn.compact
    def __call__(self):
        ks_i, ks_h, bs = [], [], []
        for comp in "ifgo":
            k, _ = _GateParams(
                self.features, self.in_features, False,
                nn.initializers.lecun_normal(), name=f"i{comp}")()
            ks_i.append(k)
            k, b = _GateParams(
                self.features, self.features, True,
                nn.initializers.orthogonal(), name=f"h{comp}")()
            ks_h.append(k)
            bs.append(b)
        return (jnp.concatenate(ks_i, axis=-1),
                jnp.concatenate(ks_h, axis=-1),
                jnp.concatenate(bs, axis=-1))


class _PallasCore(nn.Module):
    """The fused Pallas done-reset LSTM unroll (ops/lstm_pallas.py),
    parameter-compatible with the ``nn.scan(_CoreStep)`` path: both
    produce params under core/lstm/{ii..ho}."""

    features: int
    matmul_dtype: str = "float32"

    @nn.compact
    def __call__(self, carry, x, done):
        # Lazy like vtrace.py's pallas path: XLA-only consumers never
        # pay (or depend on) the Pallas TPU imports.
        from scalable_agent_tpu.ops import lstm_pallas
        from scalable_agent_tpu.parallel.mesh import pallas_interpret

        wi, wh, b = _PallasCoreParams(
            self.features, x.shape[-1], name="lstm")()
        ys, (ct, ht) = lstm_pallas.lstm_unroll(
            jnp.asarray(x, jnp.float32), done, carry[0], carry[1],
            wi, wh, b, pallas_interpret(), self.matmul_dtype)
        return (ct, ht), ys


class ImpalaAgent(nn.Module):
    """ConvNet/ResNet torso + LSTM(256) core + policy/baseline heads.

    ``__call__`` is the whole-trajectory unroll (the reference's
    ``Agent.unroll``, experiment.py:219-237), shared verbatim between actor
    inference (T=1) and learner training (T=unroll_length) — exactly as the
    reference shares one ``_build``/``unroll``.

    Inputs are time-major: actions [T, B] int32, env_outputs with
    reward [T, B], done [T, B], observation.frame [T, B, H, W, C] uint8,
    observation.instruction [T, B, L] int32 or None.
    """

    num_actions: int = 0
    torso_type: str = "shallow"
    use_instruction: bool = False
    core_size: int = CORE_SIZE
    # The ONE compute-dtype policy (f32 default; bfloat16 on TPU via
    # --compute_dtype): params stay float32, the torso/concat/head
    # matmuls run in compute_dtype, and the agent's OUTPUTS
    # (policy_logits, baseline) are upcast to f32 so every loss /
    # V-trace / optimizer reduction downstream stays f32.  The XLA
    # LSTM core is the one documented exception: flax's cell promotes
    # to the f32 params' dtype (the Pallas core's matmul precision is
    # core_matmul_dtype's job instead).
    compute_dtype: Any = jnp.float32
    # LSTM core implementation: "xla" = nn.scan over OptimizedLSTMCell;
    # "pallas" = the fused single-program unroll (ops/lstm_pallas.py).
    # Parameter trees are identical, so checkpoints are interchangeable.
    core_impl: str = "xla"
    # Operand precision for the Pallas core's gate/BPTT matmuls:
    # "float32" (bit-exact vs the flax cell) or "bfloat16" (2x MXU
    # rate, f32 accumulation).  Ignored by the xla core.
    core_matmul_dtype: str = "float32"
    # Stem-conv grad-W lowering: "xla" (plain nn.Conv) or "pallas"
    # (ops/conv_pallas.py MXU kernel).
    # Identical parameter trees — checkpoints are interchangeable.
    conv_backend: str = "xla"
    # Rematerialize in the torso's backward pass (jax.checkpoint),
    # where the torso itself says it pays (models/networks.py
    # REMAT_PLACEMENTS): the ResNet's stem segment, nothing behind
    # the shallow torso's Pallas stem, the whole shallow torso behind
    # XLA's stem.  Until ISSUE 27 this wrapped whichever torso whole,
    # which recomputes the entire forward to free nothing at the peak
    # (every residual is live again before the first backward op).
    # Default OFF so the default-path jaxpr (and the golden-loss
    # anchor) is untouched; the driver turns it on on a TPU.
    remat_torso: bool = False
    # How many pieces the mesh cuts the batch axis in (parallel/mesh.py
    # batch_shards: data x seq; the Learner sets it on its copy of the
    # agent from the mesh it is given).  It orders the [T, B] -> [T*B]
    # merge: B is the sharded axis, and a time-major merge of it is not
    # a tiling of the merged axis, so the SPMD partitioner gathered the
    # frames and every device computed the whole global batch's torso,
    # heads and backward (ISSUE 26: four chips at 1.09x one).  Merged
    # as [S, T, B/S] — shard index outermost — the merged axis is
    # tiled S ways, nothing is gathered, and a device's own rows stay
    # time-major exactly as on one chip, where S = 1 and the merge is
    # the plain reshape.  Only the order of rows inside the merge
    # changes, never a value: any S that divides B gives the same
    # outputs on any mesh.
    batch_shards: int = 1
    # Composite policies: a TupleSpace mixing Discrete/Discretized
    # components (reference: TupleActionDistribution,
    # algorithms/utils/action_distributions.py:111-201).  When unset, the
    # policy is one Discrete(num_actions) head, the original layout.
    action_space: Optional[Any] = None

    @property
    def dist_spec(self) -> distributions.DistributionSpec:
        if self.action_space is not None:
            return distributions.spec_for_space(self.action_space)
        return distributions.DistributionSpec(sizes=(self.num_actions,))

    @property
    def num_logits(self) -> int:
        return self.dist_spec.num_logits

    @property
    def num_action_components(self) -> int:
        return self.dist_spec.num_components

    @property
    def remat_placement(self) -> str:
        """Where ``remat_torso`` puts the checkpoint in THIS agent's
        torso: one of models/networks.py REMAT_PLACEMENTS."""
        return self._unbound_torso().remat_placement

    def _unbound_torso(self):
        """This agent's torso outside any ``apply``, for what it says
        of its own structure."""
        return TORSOS[self.torso_type](
            conv_backend=self.conv_backend, remat=self.remat_torso,
            parent=None)

    def zero_actions(self, batch: int) -> jnp.ndarray:
        """All-zeros last-action input at the agent's action layout
        ([B] for plain Discrete, [B, K] for composites)."""
        k = self.num_action_components
        shape = (batch,) if k == 1 else (batch, k)
        return jnp.zeros(shape, jnp.int32)

    # -- what every agent declares (models/token_policy.py declares its
    # own): its state, and what the learner's telemetry reads of it ------

    def initial_state(self, batch_size: int) -> AgentState:
        return initial_state(batch_size, self.core_size)

    def unroll_state(self, start: AgentState, end: AgentState) -> AgentState:
        """The state the update unrolls from, given the rollout's first
        and last: the first."""
        del end
        return start

    def acting_params(self, params):
        """The parameters as the rollout's steps read them."""
        return params

    @property
    def handover_collection(self) -> Optional[str]:
        """The variable collection into which an acting step
        (``actor_step(..., handover=...)``) sows what an update over
        the same frames UNDER THE SAME PARAMETERS takes back as
        ``__call__``'s ``handed`` in place of computing it again; None
        where there is nothing to hand.  Here the torso's to say: the
        shallow torso's stem activation behind the Pallas stem
        (models/networks.py ``hands_stem``)."""
        return HANDOVER if self._unbound_torso().hands_stem else None

    # Parameter groups of the learning-dynamics gauges
    # (runtime/learner.py), the module whose output the dead-unit
    # reading takes, and no collection of the forward pass's own numbers
    # (``STATS`` names them).  ``Learner.init`` initializes op by op.
    layer_groups = ("torso", "core", "heads")
    dead_unit_module = "convnet"
    stats_collection = None
    STATS = ()
    init_in_one_program = False
    init_steps = None           # the whole example trajectory

    @staticmethod
    def layer_group(path) -> str:
        """A param-tree path's group: the conv torso ("convnet" and the
        optional instruction encoder), the recurrent core, the heads."""
        keys = {str(getattr(entry, "key", entry)) for entry in path}
        if "core" in keys:
            return "core"
        if "policy_logits" in keys or "baseline" in keys:
            return "heads"
        return "torso"

    @nn.compact
    def __call__(
        self,
        actions,
        env_outputs: StepOutput,
        core_state: AgentState,
        handed=None,
    ) -> Tuple[Tuple[jax.Array, jax.Array], AgentState]:
        """``handed``: what acting steps sowed into
        ``handover_collection`` on exactly these frames under exactly
        these parameters, each leaf stacked ``[T, B, ...]``."""
        unroll_len, batch = actions.shape[:2]
        reward, _, done, observation = env_outputs
        frame = observation.frame
        spec = self.dist_spec

        # ---- Torso over the merged [T*B] batch (reference: _torso,
        # experiment.py:148-198, but batched over all timesteps at once).
        # T = 1 (acting) has nothing to order, and a batch the shard
        # count does not divide (a shape-only init) is not sharded.
        shards = (self.batch_shards
                  if unroll_len > 1 and batch % self.batch_shards == 0
                  else 1)
        per_shard = batch // shards

        def flat(x):  # [T, B, ...] -> [T*B, ...], shard index outermost
            trailing = x.shape[2:]
            if shards > 1:
                x = jnp.swapaxes(x.reshape(
                    (unroll_len, shards, per_shard) + trailing), 0, 1)
            return x.reshape((unroll_len * batch,) + trailing)

        def unflat(x, *trailing):  # [T*B, ...] -> [T, B, *trailing]
            if shards > 1:
                x = jnp.swapaxes(x.reshape(
                    (shards, unroll_len, per_shard) + trailing), 0, 1)
            return x.reshape((unroll_len, batch) + trailing)

        # Where (and whether) the backward recomputes is the torso's
        # own placement, from its structure and its stem path.
        torso = TORSOS[self.torso_type](
            dtype=self.compute_dtype, conv_backend=self.conv_backend,
            remat=self.remat_torso, name="convnet")
        conv_out = (  # [T*B, 256] compute_dtype
            torso(flat(frame)) if handed is None else
            torso(flat(frame), stem=flat(handed["convnet"]["stem"])))

        clipped_reward = jnp.clip(
            jnp.asarray(flat(reward), jnp.float32), -1.0, 1.0)[:, None]
        one_hot_last_action = distributions.one_hot_actions(
            flat(actions), spec)
        parts = [conv_out, clipped_reward, one_hot_last_action]
        if self.use_instruction:
            instruction = observation.instruction
            parts.append(
                InstructionEncoder(name="instruction")(flat(instruction)))
        # Mixed-dtype concat promotes to f32; the policy casts back so
        # the core consumes compute_dtype activations (identity under
        # the f32 default — the golden anchor sees the same jaxpr
        # values).
        torso_out = jnp.asarray(
            jnp.concatenate(parts, axis=-1), self.compute_dtype)
        torso_out = unflat(torso_out, torso_out.shape[-1])

        # ---- LSTM core: one fused scan over time with done-reset
        # (reference: experiment.py:228-237).
        carry = (core_state.c, core_state.h)
        done_f32 = jnp.asarray(done, jnp.float32)
        if self.core_impl == "pallas":
            carry, core_outputs = _PallasCore(
                self.core_size, matmul_dtype=self.core_matmul_dtype,
                name="core")(carry, torso_out, done_f32)
        elif self.core_impl == "xla":
            scan = nn.scan(
                _CoreStep,
                variable_broadcast="params",
                split_rngs={"params": False},
                in_axes=0,
                out_axes=0,
            )
            carry, core_outputs = scan(self.core_size, name="core")(
                carry, (torso_out, done_f32))
        else:
            raise ValueError(f"unknown core_impl: {self.core_impl!r}")
        new_state = AgentState(c=carry[0], h=carry[1])

        # ---- Heads (reference: _head, experiment.py:200-210), again on the
        # merged batch.
        core_flat = flat(core_outputs)
        num_logits = self.num_logits
        # Heads run at compute_dtype; the OUTPUTS are upcast to f32 —
        # the loss/V-trace/optimizer side of the dtype policy never
        # sees bf16 (under the f32 default both casts are identities).
        policy_logits = unflat(jnp.asarray(
            nn.Dense(num_logits, dtype=self.compute_dtype,
                     name="policy_logits")(core_flat),
            jnp.float32), num_logits)
        baseline = unflat(jnp.asarray(
            nn.Dense(1, dtype=self.compute_dtype, name="baseline")(
                core_flat),
            jnp.float32))
        return (policy_logits, baseline), new_state


def actor_step(
    agent,
    params,
    rng: jax.Array,
    last_action,
    env_output: StepOutput,
    core_state: AgentState,
    handover: Optional[str] = None,
):
    """One batched inference step: unroll T=1, sample an action.

    last_action [B] int32, env_output batched [B, ...].  Returns
    (AgentOutput with action [B], new core state).  Jit this (it is pure);
    the batching service calls it on gathered actor requests.
    (reference: Agent._build, experiment.py:212-217 + _head sampling
    :205-208)

    With ``handover`` (the agent's ``handover_collection``) a third
    result: what the step sowed there, leaves ``[B, ...]``.
    """
    expand = lambda x: x[None] if x is not None else None
    actions = expand(last_action)
    env_outputs = map_structure(expand, env_output)
    if handover:
        ((policy_logits, baseline), new_state), sown = agent.apply(
            params, actions, env_outputs, core_state, mutable=[handover])
    else:
        (policy_logits, baseline), new_state = agent.apply(
            params, actions, env_outputs, core_state)
    policy_logits = policy_logits[0]  # [B, num_logits]
    baseline = baseline[0]  # [B]
    # Composite spaces sample every component ([B, K]); plain Discrete
    # keeps the [B] layout.
    action = distributions.sample(rng, policy_logits, agent.dist_spec)
    if distributions.stores_log_prob(agent.dist_spec):
        # a vocabulary: the trajectory keeps what V-trace reads of the
        # behaviour policy, the taken action's log-probability
        policy_logits = distributions.log_prob(
            policy_logits, action, agent.dist_spec)[..., None]
    output = AgentOutput(
        action=jnp.asarray(action, jnp.int32),
        policy_logits=policy_logits,
        baseline=baseline,
    )
    if handover:
        return output, new_state, sown[handover]
    return output, new_state
