"""Fused single-forward loss (ISSUE 18): structure and identity.

The learner's ``_forward`` docstring promises ONE unroll produces both
the behaviour-comparison quantities and the loss's differentiated
outputs; this file pins that structurally (the lowered gradient program
contains exactly one unfused-unroll's-worth fewer convolutions than the
``fused_forward=False`` reference) and numerically (the two programs
are value-identical, because vtrace stop-gradients every comparison
input internally — the fusion is a pure program transformation, not an
algorithm change).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from __graft_entry__ import _example_trajectory
from scalable_agent_tpu.models import ImpalaAgent
from scalable_agent_tpu.parallel import MeshSpec, make_mesh
from scalable_agent_tpu.runtime import Learner, LearnerHyperparams

T, B, HW, NUM_ACTIONS = 6, 4, 16, 5


def _make(fused, loss="vtrace", **agent_kwargs):
    agent = ImpalaAgent(num_actions=NUM_ACTIONS, **agent_kwargs)
    mesh = make_mesh(MeshSpec(data=1, model=1),
                     devices=jax.devices()[:1])
    learner = Learner(agent, LearnerHyperparams(), mesh,
                      frames_per_update=T * B, loss=loss,
                      fused_forward=fused)
    traj = _example_trajectory(T, B, HW, HW, NUM_ACTIONS)
    state = learner.init(jax.random.key(0), traj)
    return learner, state, learner.put_trajectory(traj)


def _conv_count(learner, state, traj):
    """Convolution-primitive count in the traced gradient program —
    each forward unroll contributes the torso's conv stack, so an extra
    comparison unroll is directly visible here."""
    jaxpr = jax.make_jaxpr(
        lambda p: jax.grad(lambda q: learner._loss(q, traj)[0])(p)
    )(state.params)
    return str(jaxpr).count("conv_general_dilated")


class TestRematPlacement:
    """Where the update's gradient recomputes (ISSUE 27): each torso
    places its own checkpoint.  One boundary around the whole ResNet
    recomputed all 15 forward convolutions to free nothing at the
    peak; around the stem segment it is one."""

    @pytest.mark.parametrize("agent_kwargs,convs,recomputed", [
        # 15 forward + 14 input-gradient + 15 weight-gradient
        (dict(torso_type="resnet"), 44, 1),
        # 3 + 2 + 3; XLA's stem keeps the old whole-torso boundary
        (dict(torso_type="shallow", conv_backend="xla"), 8, 3),
        # the stem's weight gradient is the Pallas kernel; nothing
        # behind it is worth rebuilding
        (dict(torso_type="shallow", conv_backend="pallas"), 7, 0),
    ], ids=("resnet", "shallow-xla", "shallow-pallas"))
    def test_the_gradient_recomputes_what_the_torso_placed(
            self, agent_kwargs, convs, recomputed):
        counts = {
            remat: _conv_count(*_make(True, remat_torso=remat,
                                      **agent_kwargs))
            for remat in (False, True)}
        assert counts == {False: convs, True: convs + recomputed}

    @pytest.mark.parametrize("remat", (False, True))
    @pytest.mark.parametrize("loss,convs", [
        # 7 less the stem's forward: 2 + 2 + 2
        ("vtrace", 6),
        # and the target network's own forward, stem and all: + 3
        ("impact", 9)])
    def test_the_handed_update_runs_no_stem_conv(self, loss, convs, remat):
        """ISSUE 37: given the acting steps' stem activations, the
        update's gradient holds exactly one convolution fewer — the
        stem's forward — and the IMPACT target's forward, under other
        parameters, keeps the stem conv it has."""
        learner, state, traj = _make(True, loss=loss, remat_torso=remat,
                                     conv_backend="pallas")
        agent = learner._agent
        assert agent.handover_collection == "handover"
        frames = traj.env_outputs.observation.frame
        handed = {"convnet": {"stem": jnp.zeros(
            frames.shape[:2] + (HW // 4, HW // 4, 32), jnp.float32)}}

        def count(**handed):
            return str(jax.make_jaxpr(jax.grad(
                lambda p: learner._loss(p, traj, state.params,
                                        **handed)[0]))(state.params)
                       ).count("conv_general_dilated")

        assert (count(), count(handed=handed)) == (convs + 1, convs)

    @pytest.mark.parametrize("agent_kwargs", [
        dict(torso_type="resnet"), dict(torso_type="resnet",
                                        conv_backend="pallas"),
        dict(torso_type="shallow", conv_backend="xla"),
        dict(torso_type="shallow", conv_backend="xla", remat_torso=True)],
        ids=("resnet", "resnet-pallas", "shallow-xla", "shallow-xla-remat"))
    def test_every_other_agent_hands_nothing(self, agent_kwargs):
        assert ImpalaAgent(num_actions=NUM_ACTIONS,
                           **agent_kwargs).handover_collection is None

    @pytest.mark.parametrize("torso_type", ("shallow", "resnet"))
    def test_the_default_program_holds_no_checkpoint(self, torso_type):
        """``remat_torso`` is off by default: the golden-loss anchor's
        program is untouched."""
        learner, state, traj = _make(True, torso_type=torso_type)
        assert learner._agent.remat_torso is False
        text = str(jax.make_jaxpr(
            lambda p: jax.grad(lambda q: learner._loss(q, traj)[0])(p)
        )(state.params))
        assert "remat2[" not in text  # jax.checkpoint's primitive


class TestSingleForward:
    def test_fused_lowers_fewer_convs(self):
        """The unfused program runs one extra stop-gradiented unroll
        (3 torso convs); fused must shed EXACTLY those — fewer would
        mean the loss lost a real forward, more would mean the
        comparison pass snuck back in."""
        fused, f_state, f_traj = _make(True)
        unfused, u_state, u_traj = _make(False)
        n_fused = _conv_count(fused, f_state, f_traj)
        n_unfused = _conv_count(unfused, u_state, u_traj)
        assert n_unfused - n_fused == 3, (
            f"fused {n_fused} vs unfused {n_unfused} convolutions")

    @pytest.mark.parametrize("loss", ("vtrace", "impact"))
    def test_fused_and_unfused_value_identical(self, loss):
        """vtrace stop-gradients all its outputs internally, so the
        fused program and the double-forward reference are the SAME
        mathematical function — loss and gradients must agree to float
        round-off, for both loss families."""
        fused, f_state, f_traj = _make(True, loss=loss)
        unfused, u_state, u_traj = _make(False, loss=loss)

        def loss_and_grads(learner, state, traj):
            # impact reads the target network; anchoring it at the
            # online params keeps the comparison self-contained.
            val, grads = jax.value_and_grad(
                lambda p: learner._loss(
                    p, traj, target_params=state.params)[0])(state.params)
            return val, grads

        f_val, f_grads = loss_and_grads(fused, f_state, f_traj)
        u_val, u_grads = loss_and_grads(unfused, u_state, u_traj)
        np.testing.assert_allclose(f_val, u_val, rtol=1e-6)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                a, b, rtol=1e-5, atol=1e-6), f_grads, u_grads)

    @pytest.mark.parametrize("conv_backend", ("xla", "pallas"))
    def test_update_adds_no_host_sync(self, conv_backend):
        """Acceptance (ISSUE 18): the kernel-war configuration — bf16
        compute, fused forward, either conv backend — keeps the update
        free of device↔host round-trips, pinned the same way ISSUE 12
        pinned telemetry: spied materializations + a hard transfer
        guard around steady-state updates."""
        from scalable_agent_tpu.envs.device.conformance import (
            materialization_spy)

        learner, state, traj = _make(True, compute_dtype=jnp.bfloat16,
                                     conv_backend=conv_backend)
        state, _ = learner.update(state, traj)  # warm the compile
        with materialization_spy() as calls:
            with jax.transfer_guard("disallow"):
                for _ in range(3):
                    state, _ = learner.update(state, traj)
            assert calls == [], (
                f"{conv_backend} update materialized device values on "
                f"the host: {calls}")

    def test_bf16_update_keeps_f32_params_and_finite_loss(self):
        """One real update under bf16 compute: optimizer state and
        params stay f32 (the master-weights contract) and the loss is
        finite — the e2e learning proof lives in test_learning.py's
        bf16 bandit run."""
        learner, state, traj = _make(True, compute_dtype=jnp.bfloat16)
        new_state, metrics = learner.update(state, traj)
        assert np.isfinite(float(metrics["total_loss"]))
        for leaf in jax.tree_util.tree_leaves(new_state.params):
            assert leaf.dtype == jnp.float32
