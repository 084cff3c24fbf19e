"""ISSUE 19: the numerics sentinel — silent-corruption defense with a
graceful-degradation ladder for the optimized hot path.

Coverage map (tests/test_chaos_lint.py holds the chaos points here):

- **e2e, both backends**: a chaos run injecting ``param_bitflip`` (host)
  / ``kernel_miscompute`` (in-graph) must detect the corruption at the
  next shadow audit, demote one ladder rung, and FINISH TRAINING — with
  the trip visible as counters + a pinned flight-recorder reason.
- **ladder exhaustion**: breaches surviving every rung roll back once,
  then exit ``SENTINEL_EXIT_CODE`` (73); elastic restarts at the same
  shape.
- **fingerprints**: deterministic uint32 checksums, the
  ``replica_diverge`` corruption, and the cross-process compare.
- **megaloop tolerance**: at ``--updates_per_dispatch=8`` a non-finite
  streak that breaches ``--nonfinite_tolerance=3`` MID-dispatch (and
  resets before the boundary) still honors the policy, via the streak
  peak carried in ``TrainCarry``.
- **rollback lineage**: a non-finite rollback with ``--replay_ratio>0``
  flushes the replay slab (the abandoned timeline's trajectories) and
  the run re-warms and completes.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scalable_agent_tpu.config import Config
from scalable_agent_tpu.driver import build_sentinel, zero_trajectory
from scalable_agent_tpu.driver import train as run_train
from scalable_agent_tpu.envs.spec import TensorSpec
from scalable_agent_tpu.models import ImpalaAgent
from scalable_agent_tpu.obs import (
    configure_flight_recorder,
    get_flight_recorder,
    get_registry,
)
from scalable_agent_tpu.parallel import MeshSpec, make_mesh
from scalable_agent_tpu.runtime import (
    Learner,
    LearnerHyperparams,
    configure_faults,
)
from scalable_agent_tpu.runtime.elastic import RESTART_SAME, classify_exit
from scalable_agent_tpu.runtime.exit_codes import SENTINEL_EXIT_CODE
from scalable_agent_tpu.runtime.replay import DeviceReplayBuffer
from scalable_agent_tpu.runtime.sentinel import (
    _DIVERGE_MASK,
    LADDER,
    NumericsSentinel,
    _reference_config,
)

pytestmark = pytest.mark.chaos

NUM_ACTIONS = 4
FRAME = TensorSpec((8, 8, 3), np.uint8, "frame")


class _ObsSpec:
    frame = FRAME
    instruction = None
    measurements = None


def _counter_value(name: str) -> float:
    return float(get_registry().snapshot().get(name, 0.0))


@pytest.fixture(autouse=True)
def _clean_faults():
    configure_faults("")
    yield
    configure_faults("")


@pytest.fixture(scope="module")
def learner_setup():
    agent = ImpalaAgent(num_actions=NUM_ACTIONS)
    traj = zero_trajectory(Config(), _ObsSpec, agent, batch=4)
    mesh = make_mesh(MeshSpec(data=4, model=1), devices=jax.devices()[:4])
    learner = Learner(
        agent, LearnerHyperparams(total_environment_frames=1e6), mesh,
        frames_per_update=16)
    state = learner.init(jax.random.key(0), traj)
    return agent, learner, state


def _make_sentinel(agent, learner, rebuild=None, **config_overrides):
    overrides = dict(sentinel_interval=8)
    overrides.update(config_overrides)
    config = Config(**overrides)
    return NumericsSentinel(
        config, agent, learner,
        rebuild=rebuild or (lambda cfg: (agent, learner)))


def _sentinel_config(tmp_path, **overrides) -> Config:
    defaults = dict(
        mode="train",
        logdir=str(tmp_path / "run"),
        level_name="fake_small",
        num_actors=4,
        batch_size=2,
        unroll_length=4,
        num_action_repeats=1,
        total_environment_frames=48,  # 6 updates of 8 frames
        height=16,
        width=16,
        num_env_workers_per_group=2,
        compute_dtype="float32",
        checkpoint_interval_s=0.0,
        log_interval_s=0.0,
        seed=5,
        sentinel_interval=2,  # audits after the 2nd, 4th, 6th updates
    )
    defaults.update(overrides)
    return Config(**defaults)


# ---------------------------------------------------------------------------
# Wiring / cadence units
# ---------------------------------------------------------------------------


class TestSentinelWiring:
    def test_constructor_rejects_sentinel_off(self, learner_setup):
        agent, learner, _ = learner_setup
        with pytest.raises(ValueError, match="sentinel_interval"):
            NumericsSentinel(Config(), agent, learner,
                             rebuild=lambda cfg: (agent, learner))

    def test_build_sentinel_returns_none_when_off(self):
        # The driver's default path never constructs the class — the
        # sentinel-off invariant the PR 13 goldens pin bit-exactly.
        assert Config().sentinel_interval == 0
        assert build_sentinel(Config(), None, None, None) is None

    def test_audit_due_cadence(self, learner_setup):
        agent, learner, _ = learner_setup
        sentinel = _make_sentinel(agent, learner, sentinel_interval=2)
        # 0-based pre-update counter: audits wrap the 2nd, 4th, ...
        assert [sentinel.audit_due(u) for u in range(6)] == [
            False, True, False, True, False, True]

    def test_consume_swap_is_one_shot(self, learner_setup):
        agent, learner, _ = learner_setup
        sentinel = _make_sentinel(agent, learner)
        assert not sentinel.consume_swap()
        sentinel._on_breach(1.0, updates=0)
        assert sentinel.consume_swap()
        assert not sentinel.consume_swap()

    def test_reference_config_is_full_ladder(self):
        ref = _reference_config(Config())
        assert ref.conv_backend == "xla"
        assert ref.compute_dtype == "float32"
        assert ref.fused_forward is False

    def test_ingraph_megaloop_with_sentinel_rejected(self, tmp_path):
        config = _sentinel_config(
            tmp_path, train_backend="ingraph", updates_per_dispatch=8)
        with pytest.raises(ValueError, match="sentinel"):
            run_train(config)

    def test_classify_exit_73_restarts_same_shape(self):
        assert SENTINEL_EXIT_CODE == 73
        assert classify_exit(SENTINEL_EXIT_CODE) == RESTART_SAME


# ---------------------------------------------------------------------------
# Degradation ladder
# ---------------------------------------------------------------------------


class TestDegradationLadder:
    def test_rungs_apply_cumulative_overrides(self, learner_setup):
        agent, learner, _ = learner_setup
        seen = []

        def rebuild(cfg):
            seen.append(cfg)
            return agent, learner

        sentinel = _make_sentinel(agent, learner, rebuild=rebuild)
        sentinel._on_breach(1.0, updates=0)
        assert sentinel.rung == 1
        assert seen[-1].conv_backend == "xla"
        assert seen[-1].compute_dtype == Config().compute_dtype
        sentinel._on_breach(1.0, updates=1)
        assert sentinel.rung == 2
        assert seen[-1].compute_dtype == "float32"
        sentinel._on_breach(1.0, updates=2)
        assert sentinel.rung == 3
        assert seen[-1].fused_forward is False
        assert len(LADDER) == 3

    def test_exhaustion_rolls_back_once_then_exits_73(
            self, learner_setup):
        agent, learner, _ = learner_setup
        # A fresh recorder: the pin is sticky, and an earlier driver
        # run in this worker may have left its own (a health trip's).
        configure_flight_recorder(None)
        sentinel = _make_sentinel(agent, learner)
        trips_before = _counter_value("sentinel/trips_total")
        for updates in range(len(LADDER)):
            sentinel._on_breach(1.0, updates=updates)
        assert not sentinel.rollback_pending
        # Breach 4: the ladder is spent — request ONE rollback.
        sentinel._on_breach(1.0, updates=3)
        assert sentinel.rollback_pending
        sentinel.note_rollback()
        assert not sentinel.rollback_pending
        # Breach 5: the reference path itself can't be reproduced.
        with pytest.raises(SystemExit) as excinfo:
            sentinel._on_breach(1.0, updates=4)
        assert excinfo.value.code == SENTINEL_EXIT_CODE
        recorder = get_flight_recorder()
        # The dump itself needs a configured logdir (driver runs have
        # one); the breadcrumbs and the sticky pin are always there.
        names = {(e["kind"], e["name"]) for e in recorder.snapshot()}
        assert ("sentinel_trip", "exhausted") in names
        assert recorder.reason_pin.startswith("sentinel_trip")
        assert _counter_value("sentinel/trips_total") == trips_before + 5


# ---------------------------------------------------------------------------
# Param fingerprints
# ---------------------------------------------------------------------------


class TestFingerprints:
    def test_deterministic_and_published(self, learner_setup):
        agent, learner, state = learner_setup
        sentinel = _make_sentinel(agent, learner)
        fp = sentinel.local_fingerprint(state.params)
        assert sentinel.local_fingerprint(state.params) == fp
        assert 0 <= fp < 2 ** 32
        assert _counter_value("sentinel/param_fingerprint") == float(fp)

    def test_fingerprint_tracks_param_bits(self, learner_setup):
        agent, learner, state = learner_setup
        sentinel = _make_sentinel(agent, learner)
        fp = sentinel.local_fingerprint(state.params)
        perturbed = jax.tree_util.tree_map(
            lambda p: p + jnp.ones_like(p) * 1e-3, state.params)
        assert sentinel.local_fingerprint(perturbed) != fp

    def test_replica_diverge_chaos_corrupts_fingerprint(
            self, learner_setup):
        agent, learner, state = learner_setup
        sentinel = _make_sentinel(agent, learner)
        fp = sentinel.local_fingerprint(state.params)
        configure_faults("replica_diverge@1")
        assert sentinel.local_fingerprint(state.params) == (
            fp ^ _DIVERGE_MASK)
        # Occurrence 2 is unarmed: back to the honest checksum.
        assert sentinel.local_fingerprint(state.params) == fp

    def test_check_fingerprints_agreement_and_mismatch(
            self, learner_setup):
        agent, learner, _ = learner_setup
        sentinel = _make_sentinel(agent, learner)
        mismatches_before = _counter_value(
            "sentinel/fingerprint_mismatch_total")
        assert not sentinel.check_fingerprints(
            np.asarray([[1234.0], [1234.0]]))
        assert sentinel.check_fingerprints(
            np.asarray([[1234.0], [1235.0]]))
        assert _counter_value("sentinel/fingerprint_mismatch_total") == (
            mismatches_before + 1)
        kinds = {(e["kind"], e["name"])
                 for e in get_flight_recorder().snapshot()}
        assert ("sentinel_trip", "fingerprint") in kinds


# ---------------------------------------------------------------------------
# Replay slab lineage
# ---------------------------------------------------------------------------


class TestReplayFlush:
    def test_flush_empties_slab_counts_and_rearms(self):
        buf = DeviceReplayBuffer(capacity=4, seed=0)
        tree = {"reward": jnp.ones((4, 2), jnp.float32)}
        buf.insert(tree)
        buf.insert(tree)
        assert buf.size == 2
        flushes_before = _counter_value("replay/rollback_flushes_total")
        buf.flush()
        assert buf.size == 0
        assert _counter_value("replay/rollback_flushes_total") == (
            flushes_before + 1)
        # The slab re-warms: inserts after a flush are sampleable.
        buf.insert(tree)
        assert buf.size == 1
        sampled = buf.sample()
        np.testing.assert_array_equal(
            np.asarray(sampled["reward"]), np.ones((4, 2), np.float32))

    def test_flush_before_first_insert_is_safe(self):
        buf = DeviceReplayBuffer(capacity=4, seed=0)
        buf.flush()
        assert buf.size == 0


# ---------------------------------------------------------------------------
# E2E chaos: detect -> demote -> finish, both backends
# ---------------------------------------------------------------------------


def _sentinel_counters():
    return {name: _counter_value(name) for name in (
        "sentinel/trips_total",
        "sentinel/demotions_total",
        "devtel/sentinel/audits_total",
        "devtel/sentinel/breaches_total",
        "faults/injected_total",
    )}


@pytest.mark.slow
class TestSentinelE2E:
    """Driver e2e runs (compile-heavy): slow-marked like TestChaosSoak;
    the fast deterministic sentinel subset above stays tier-1."""

    def test_host_param_bitflip_detect_demote_finish(self, tmp_path):
        config = _sentinel_config(
            tmp_path, chaos_spec="param_bitflip@1")
        before = _sentinel_counters()
        metrics = run_train(config)
        assert metrics["env_frames"] == 48
        assert np.isfinite(metrics["total_loss"])
        after = _sentinel_counters()
        # 6 updates at interval 2 -> 3 audits; the first is poisoned
        # and breaches (the delta arm sees the flipped bit), demoting
        # one rung; the post-demotion audits run clean so the run
        # FINISHES — detect -> demote -> finish.
        assert after["devtel/sentinel/audits_total"] == (
            before["devtel/sentinel/audits_total"] + 3)
        assert after["devtel/sentinel/breaches_total"] == (
            before["devtel/sentinel/breaches_total"] + 1)
        assert after["sentinel/trips_total"] == (
            before["sentinel/trips_total"] + 1)
        assert after["sentinel/demotions_total"] == (
            before["sentinel/demotions_total"] + 1)
        assert after["faults/injected_total"] == (
            before["faults/injected_total"] + 1)
        assert _counter_value("sentinel/rung") == 1.0
        entries = get_flight_recorder().snapshot()
        names = {(e["kind"], e["name"]) for e in entries}
        assert ("sentinel_trip", "audit") in names
        assert ("sentinel_trip", "demote") in names

    def test_ingraph_kernel_miscompute_detect_demote_finish(
            self, tmp_path):
        config = _sentinel_config(
            tmp_path, train_backend="ingraph",
            chaos_spec="kernel_miscompute@1")
        before = _sentinel_counters()
        metrics = run_train(config)
        assert metrics["env_frames"] == 48
        assert np.isfinite(metrics["total_loss"])
        after = _sentinel_counters()
        assert after["devtel/sentinel/audits_total"] == (
            before["devtel/sentinel/audits_total"] + 3)
        assert after["devtel/sentinel/breaches_total"] == (
            before["devtel/sentinel/breaches_total"] + 1)
        assert after["sentinel/trips_total"] == (
            before["sentinel/trips_total"] + 1)
        assert after["sentinel/demotions_total"] == (
            before["sentinel/demotions_total"] + 1)
        assert _counter_value("sentinel/rung") == 1.0
        names = {(e["kind"], e["name"])
                 for e in get_flight_recorder().snapshot()}
        assert ("sentinel_trip", "demote") in names

    def test_sentinel_quiet_on_clean_run(self, tmp_path):
        # No chaos: the audits run and STAY QUIET — the false-positive
        # guard for the rtol calibration (on CPU every ladder arm
        # compiles to near-identical programs, so the deviation floor
        # here is XLA scheduling noise only).
        config = _sentinel_config(tmp_path, total_environment_frames=32)
        before = _sentinel_counters()
        metrics = run_train(config)
        assert metrics["env_frames"] == 32
        after = _sentinel_counters()
        assert after["devtel/sentinel/audits_total"] == (
            before["devtel/sentinel/audits_total"] + 2)
        assert after["devtel/sentinel/breaches_total"] == (
            before["devtel/sentinel/breaches_total"])
        assert after["sentinel/trips_total"] == (
            before["sentinel/trips_total"])


# ---------------------------------------------------------------------------
# Megaloop tolerance contract (K=8, tolerance=3)
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestMegaloopStreakPeak:
    def test_midloop_blowthrough_honors_policy_at_boundary(
            self, tmp_path):
        """4 consecutive poisoned updates INSIDE one K=8 dispatch, with
        finite updates after them, breach tolerance=3 only via the
        streak PEAK carried in TrainCarry — the boundary streak has
        already reset.  With --no_rollback the policy is exit 71, which
        proves the dispatch-boundary check honors the contract."""
        config = _sentinel_config(
            tmp_path, train_backend="ingraph", sentinel_interval=0,
            updates_per_dispatch=8, nonfinite_tolerance=3,
            no_rollback=True, total_environment_frames=128,
            chaos_spec="nan_grad@2:3:4:5")
        with pytest.raises(SystemExit) as excinfo:
            run_train(config)
        assert excinfo.value.code == 71
        assert get_flight_recorder().last_dump_reason == (
            "nonfinite:no_rollback")

    def test_streak_inside_tolerance_completes(self, tmp_path):
        skips_before = _counter_value("learner/nonfinite_skips_total")
        config = _sentinel_config(
            tmp_path, train_backend="ingraph", sentinel_interval=0,
            updates_per_dispatch=8, nonfinite_tolerance=3,
            no_rollback=True, total_environment_frames=128,
            chaos_spec="nan_grad@2:3")
        metrics = run_train(config)
        assert metrics["env_frames"] == 128
        assert np.isfinite(metrics["total_loss"])
        assert _counter_value("learner/nonfinite_skips_total") == (
            skips_before + 2)


# ---------------------------------------------------------------------------
# Rollback lineage: the replay slab flush
# ---------------------------------------------------------------------------


class TestRollbackFlushesReplay:
    def test_nonfinite_rollback_flushes_slab_and_run_rewarns(
            self, tmp_path):
        """A non-finite rollback with --replay_ratio>0 abandons the
        post-checkpoint timeline; its trajectories in the slab would
        poison post-rollback sampling (off-policy corrections assume a
        behaviour policy the restored learner never produced).  The
        driver flushes the slab, the host loop's size gate skips replay
        until fresh inserts re-warm it, and the run completes."""
        # nan_grad occurrences count EVERY Learner.update call, and
        # with replay_ratio=1 clean replayed updates interleave with
        # fresh ones (resetting the consecutive-skip streak); four
        # consecutive poisoned calls guarantee a streak >= 2 whatever
        # the fresh/replay mix.
        config = _sentinel_config(
            tmp_path, total_environment_frames=64, sentinel_interval=0,
            chaos_spec="nan_grad@3:4:5:6", nonfinite_tolerance=2,
            replay_ratio=1, replay_capacity=8, loss="impact")
        before = {
            "flushes": _counter_value("replay/rollback_flushes_total"),
            "rollbacks": _counter_value("learner/rollbacks_total"),
        }
        metrics = run_train(config)
        assert metrics["env_frames"] == 64
        assert np.isfinite(metrics["total_loss"])
        assert _counter_value("learner/rollbacks_total") == (
            before["rollbacks"] + 1)
        assert _counter_value("replay/rollback_flushes_total") >= (
            before["flushes"] + 1)


# ---------------------------------------------------------------------------
# Watchdog vs recovery windows (ISSUE 20 satellite)
# ---------------------------------------------------------------------------


class TestWatchdogSuspendedAcrossRecovery:
    """The ~13s degradation-ladder re-jit (and the audit itself) must
    not read as a learner wedge: the driver suspends the learner
    heartbeat across the audit window and every compile window (first
    dispatch, post-demotion re-jit) — the same suspend treatment the
    rollback restore already gets.  Run with a watchdog deadline far
    below the compile time: without the suspends this trips
    ``watchdog/stalls_total`` three times over."""

    def test_no_stalls_across_audit_and_rejit(self, tmp_path,
                                              monkeypatch):
        real_audit = NumericsSentinel.audit
        slept = []

        def slow_audit(self, snap, trajectory, state, updates):
            if not slept:  # one long audit is enough to cross the
                slept.append(updates)  # deadline; keep the test short
                import time as _time

                _time.sleep(6.0)
            return real_audit(self, snap, trajectory, state, updates)

        monkeypatch.setattr(NumericsSentinel, "audit", slow_audit)
        config = _sentinel_config(
            tmp_path, chaos_spec="param_bitflip@1",
            watchdog_timeout_s=4.0)
        stalls_before = _counter_value("watchdog/stalls_total")
        demotions_before = _counter_value("sentinel/demotions_total")
        metrics = run_train(config)
        assert metrics["env_frames"] == 48
        assert slept, "the slow audit never ran"
        # The recovery actually happened (trip -> demote -> re-jit on
        # the next dispatch)...
        assert _counter_value("sentinel/demotions_total") == (
            demotions_before + 1)
        # ...and neither the 6s audit, the first-dispatch compile, nor
        # the post-demotion re-jit (all >> the 4s deadline) tripped
        # the watchdog.
        assert _counter_value("watchdog/stalls_total") == stalls_before
