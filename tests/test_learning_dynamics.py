"""ISSUE 17: the learning-dynamics plane.

Device side: V-trace/IMPACT clip diagnostics (golden fractions on a
hand-built off-policy batch), the loss path's entropy/KL/explained-
variance, per-layer-group optimizer telemetry — and THE acceptance
property: the instrumented update issues zero host syncs (transfer
guard + materialization spies), including all K updates of a
``--updates_per_dispatch=K`` megaloop dispatch.

Host side: the jax-free obs/learning.py rules, the ``obs.diagnose``
CLI over synthetic and real driver artifacts, the report/watch
learning sections, the fleet fold rules for devtel/learn series, and
the chaos e2e — an oversized-lr driver run must trip the
``entropy_collapse`` anomaly (with a pinned flightrec dump) and the
matching diagnose verdict while the sane twin stays verdict-clean.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scalable_agent_tpu.obs import (
    MetricsRegistry,
    get_registry,
    render_prometheus,
)
from scalable_agent_tpu.obs import learning
from scalable_agent_tpu.obs.aggregate import (
    aggregate_prometheus,
    parse_prometheus,
)
from scalable_agent_tpu.obs.diagnose import (
    build_diagnosis,
    render_diagnosis,
)
from scalable_agent_tpu.obs.diagnose import main as diagnose_main
from scalable_agent_tpu.ops.impact import surrogate_from_logits
from scalable_agent_tpu.ops.vtrace import (
    from_importance_weights,
    importance_diagnostics,
)

NUM_ACTIONS = 4


# ---------------------------------------------------------------------------
# Golden clip-fraction diagnostics (ops layer).
# ---------------------------------------------------------------------------


class TestImportanceDiagnostics:
    def test_on_policy_batch_reports_zero_everywhere(self):
        d = importance_diagnostics(np.zeros((5, 4), np.float32))
        assert float(d.rho_clip_fraction) == 0.0
        assert float(d.cs_clip_fraction) == 0.0
        assert float(d.pg_rho_clip_fraction) == 0.0
        assert float(d.log_rho_mean) == 0.0
        assert float(d.log_rho_p95) == 0.0
        assert float(d.ess_frac) == pytest.approx(1.0)

    def test_golden_fractions_on_hand_built_batch(self):
        """rhos [0.5, 1.0, 2.0, 4.0] against rho-bar=1: exactly the two
        rhos ABOVE the threshold count (strict >, the value exactly at
        the bar is returned unchanged by the clip)."""
        rhos = np.asarray([0.5, 1.0, 2.0, 4.0], np.float64)
        d = importance_diagnostics(np.log(rhos).astype(np.float32))
        assert float(d.rho_clip_fraction) == pytest.approx(0.5)
        assert float(d.cs_clip_fraction) == pytest.approx(0.5)
        assert float(d.pg_rho_clip_fraction) == pytest.approx(0.5)
        assert float(d.log_rho_mean) == pytest.approx(
            np.log(rhos).mean(), rel=1e-5)
        assert float(d.log_rho_p95) == pytest.approx(
            np.quantile(np.log(rhos), 0.95), rel=1e-5)
        want_ess = rhos.sum() ** 2 / (len(rhos) * (rhos ** 2).sum())
        assert float(d.ess_frac) == pytest.approx(want_ess, rel=1e-5)

    def test_ess_survives_extreme_log_rhos(self):
        """exp(2*log_rho) overflows f32 from log_rho ~ 44; the ESS is
        scale-invariant so the max-shifted form must stay finite (a
        single rogue trajectory must not NaN the gauge)."""
        d = importance_diagnostics(np.full((4, 2), 50.0, np.float32))
        # All weights equal => ESS is exactly 1 regardless of scale.
        assert float(d.ess_frac) == pytest.approx(1.0)
        mixed = np.zeros((4, 2), np.float32)
        mixed[0, 0] = 80.0  # one weight utterly dominates: ESS -> 1/N
        d2 = importance_diagnostics(mixed)
        assert float(d2.ess_frac) == pytest.approx(1.0 / mixed.size)

    def test_custom_and_none_thresholds(self):
        rhos = np.asarray([0.5, 1.5, 2.5, 4.0], np.float64)
        log_rhos = np.log(rhos).astype(np.float32)
        d = importance_diagnostics(log_rhos, clip_rho_threshold=2.5,
                                   clip_pg_rho_threshold=None)
        # Only 4.0 exceeds 2.5 (2.5 itself is AT the bar, not over it).
        assert float(d.rho_clip_fraction) == pytest.approx(0.25)
        assert float(d.pg_rho_clip_fraction) == 0.0  # clip disabled
        # The c-bar is always 1.0: three rhos exceed it.
        assert float(d.cs_clip_fraction) == pytest.approx(0.75)

    def test_vtrace_returns_carry_the_diagnostics(self):
        T, B = 6, 3
        rng = np.random.default_rng(0)
        log_rhos = rng.normal(scale=0.5, size=(T, B)).astype(np.float32)
        out = from_importance_weights(
            log_rhos=log_rhos,
            discounts=np.full((T, B), 0.9, np.float32),
            rewards=rng.normal(size=(T, B)).astype(np.float32),
            values=rng.normal(size=(T, B)).astype(np.float32),
            bootstrap_value=rng.normal(size=(B,)).astype(np.float32))
        assert out.diagnostics is not None
        want = importance_diagnostics(log_rhos)
        for field in want._fields:
            assert float(getattr(out.diagnostics, field)) == (
                pytest.approx(float(getattr(want, field)), abs=1e-6)), field


class TestImpactDiagnostics:
    def _logits(self, scale=0.0, seed=1):
        rng = np.random.default_rng(seed)
        online = rng.normal(size=(5, 4, NUM_ACTIONS)).astype(np.float32)
        target = online + rng.normal(
            scale=scale, size=online.shape).astype(np.float32)
        actions = rng.integers(0, NUM_ACTIONS, size=(5, 4))
        adv = rng.normal(size=(5, 4)).astype(np.float32)
        return online, target, actions.astype(np.int32), adv

    def test_anchored_online_net_is_exactly_on_target(self):
        online, _, actions, adv = self._logits()
        out = surrogate_from_logits(online, online, actions, adv)
        assert float(out.ratio_mean) == pytest.approx(1.0)
        assert float(out.clip_fraction) == 0.0
        assert float(out.log_ratio_mean) == pytest.approx(0.0, abs=1e-6)
        assert float(out.log_ratio_p95) == pytest.approx(0.0, abs=1e-6)
        assert float(out.ess_frac) == pytest.approx(1.0)

    def test_drifted_online_net_reports_the_tail(self):
        online, target, actions, adv = self._logits(scale=1.0)
        out = surrogate_from_logits(online, target, actions, adv)
        from scalable_agent_tpu.ops import distributions

        spec = distributions.DistributionSpec(sizes=(NUM_ACTIONS,))
        log_ratio = np.asarray(
            distributions.log_prob(online, actions, spec)
            - distributions.log_prob(target, actions, spec))
        assert float(out.log_ratio_mean) == pytest.approx(
            log_ratio.mean(), abs=1e-5)
        assert float(out.log_ratio_p95) == pytest.approx(
            np.quantile(log_ratio, 0.95), abs=1e-4)
        r = np.exp(log_ratio.astype(np.float64))
        want_ess = r.sum() ** 2 / (r.size * (r ** 2).sum())
        assert float(out.ess_frac) == pytest.approx(want_ess, rel=1e-4)
        assert 0.0 < float(out.ess_frac) < 1.0


# ---------------------------------------------------------------------------
# The jax-free rule pass (obs/learning.py).
# ---------------------------------------------------------------------------


HEALTHY = {
    "entropy_frac": 0.7, "kl": 0.01, "ess_frac": 0.9,
    "explained_variance": 0.5, "rho_clip_fraction": 0.1,
    "dead_torso_frac": 0.05, "update_ratio_torso": 1e-3,
    "update_ratio_core": 1e-3, "update_ratio_heads": 1e-3,
}


class TestLearningRules:
    def test_healthy_snapshot_is_clean(self):
        assert learning.derive_verdicts(HEALTHY) == []

    def test_empty_snapshot_is_clean_not_broken(self):
        assert learning.derive_verdicts({}) == []

    def _fired(self, overrides):
        snapshot = {**HEALTHY, **overrides}
        return [v["name"] for v in learning.derive_verdicts(snapshot)]

    def test_entropy_collapse(self):
        assert self._fired({"entropy_frac": 0.01}) == ["entropy_collapse"]
        assert self._fired({"entropy_frac": 0.06}) == []

    def test_value_divergence_allows_warmup_negative_ev(self):
        assert self._fired({"explained_variance": -0.8}) == [
            "value_divergence"]
        # Mildly negative EV is a warming-up critic, not divergence.
        assert self._fired({"explained_variance": -0.1}) == []

    def test_off_policy_saturated_via_clip_or_ess(self):
        verdicts = learning.derive_verdicts(
            {**HEALTHY, "rho_clip_fraction": 0.95})
        assert [v["name"] for v in verdicts] == ["off_policy_saturated"]
        assert "replay_ratio" in verdicts[0]["remedy"]
        assert "target_update_interval" in verdicts[0]["remedy"]
        assert self._fired({"ess_frac": 0.05}) == ["off_policy_saturated"]

    def test_update_ratio_fires_high_only(self):
        fired = learning.derive_verdicts(
            {**HEALTHY, "update_ratio_core": 0.5})
        assert [v["name"] for v in fired] == ["update_ratio_out_of_band"]
        assert fired[0]["evidence"]["group"] == "core"
        # The lr schedule anneals the ratio to zero at end of run: a
        # tiny ratio must NOT be a verdict.
        assert self._fired({"update_ratio_heads": 0.0}) == []

    def test_dead_torso(self):
        assert self._fired({"dead_torso_frac": 0.95}) == ["dead_torso"]
        # Tiny fake-env batches legitimately idle half the torso.
        assert self._fired({"dead_torso_frac": 0.6}) == []

    def test_extract_snapshot_filters_nonfinite(self):
        snap = learning.extract_snapshot({
            "devtel/learn/entropy_frac": 0.5,
            "devtel/learn/kl": float("nan"),
            "devtel/learn/ess_frac": None,
            "unrelated/metric": 1.0})
        assert snap == {"entropy_frac": 0.5}


class TestStalenessClipRelationship:
    S_KEY = "ledger/staleness_replayed_s/p95"
    C_KEY = "devtel/learn/rho_clip_fraction"

    def _rows(self, pairs):
        return [{self.S_KEY: s, self.C_KEY: c} for s, c in pairs]

    def test_positive_correlation_measured(self):
        rows = self._rows([(0.1, 0.05), (0.5, 0.2), (1.0, 0.4),
                           (2.0, 0.75)])
        out = learning.staleness_clip_relationship(rows)
        assert out["intervals"] == 4
        assert out["pearson_r"] > 0.95
        assert out["clip_per_staleness_s"] > 0.0
        assert "correlate" in out["statement"]

    def test_too_few_points_or_constant_series_is_none(self):
        assert learning.staleness_clip_relationship(
            self._rows([(0.1, 0.1), (0.2, 0.2)])) is None
        assert learning.staleness_clip_relationship(
            self._rows([(0.5, 0.1), (0.5, 0.2), (0.5, 0.3)])) is None

    def test_rows_missing_either_series_are_skipped(self):
        rows = self._rows([(0.1, 0.05), (0.5, 0.2), (1.0, 0.4)])
        rows.insert(1, {self.S_KEY: 0.3})  # no clip reading
        out = learning.staleness_clip_relationship(rows)
        assert out["intervals"] == 3

    def test_read_interval_rows_strips_prefix_and_skips_torn(
            self, tmp_path):
        path = tmp_path / "metrics.jsonl"
        rows = [
            {"step": 1, "obs/devtel/learn/rho_clip_fraction": 0.1,
             "obs/ledger/staleness_replayed_s/p95": 0.2,
             "total_loss": 3.0},
            {"step": 2, "obs/devtel/learn/rho_clip_fraction": 0.3},
        ]
        text = "\n".join(json.dumps(r) for r in rows)
        path.write_text(text + '\n{"step": 3, "obs/trunc')  # torn tail
        parsed = learning.read_interval_rows(str(tmp_path))
        assert len(parsed) == 2
        assert parsed[0]["devtel/learn/rho_clip_fraction"] == 0.1
        assert parsed[0]["ledger/staleness_replayed_s/p95"] == 0.2
        assert parsed[0]["step"] == 1
        assert "total_loss" not in parsed[0]  # only obs/ rows


# ---------------------------------------------------------------------------
# Learner integration: in-graph stats + zero-host-sync acceptance.
# ---------------------------------------------------------------------------


def _small_learner(loss="vtrace"):
    from __graft_entry__ import _example_trajectory
    from scalable_agent_tpu.models import ImpalaAgent
    from scalable_agent_tpu.parallel import MeshSpec, make_mesh
    from scalable_agent_tpu.runtime import Learner, LearnerHyperparams

    T, B = 4, 2
    agent = ImpalaAgent(num_actions=NUM_ACTIONS)
    mesh = make_mesh(MeshSpec(data=1, model=1),
                     devices=jax.devices()[:1])
    learner = Learner(agent, LearnerHyperparams(
        total_environment_frames=1e6), mesh, frames_per_update=T * B,
        loss=loss)
    traj_host = _example_trajectory(T, B, 16, 16, NUM_ACTIONS)
    state = learner.init(jax.random.key(0), traj_host)
    traj = learner.put_trajectory(traj_host)
    return learner, state, traj


@pytest.fixture(scope="module")
def vtrace_setup():
    learner, state, traj = _small_learner("vtrace")
    return {"learner": learner, "state": state, "traj": traj}


@pytest.fixture(scope="module")
def impact_setup():
    learner, state, traj = _small_learner("impact")
    return {"learner": learner, "state": state, "traj": traj}


class TestLearnerPlane:
    def test_update_metrics_carry_learning_stats(self, vtrace_setup):
        learner, traj = vtrace_setup["learner"], vtrace_setup["traj"]
        state, metrics = learner.update(vtrace_setup["state"], traj)
        vtrace_setup["state"] = state
        for key in ("policy_entropy", "entropy_frac", "behaviour_kl",
                    "explained_variance", "rho_clip_fraction",
                    "cs_clip_fraction", "pg_rho_clip_fraction",
                    "log_rho_mean", "log_rho_p95", "ess_frac",
                    "dead_torso_frac"):
            assert key in metrics, key
        assert 0.0 < float(np.asarray(metrics["entropy_frac"])) <= 1.0
        assert 0.0 < float(np.asarray(metrics["ess_frac"])) <= 1.0
        assert 0.0 <= float(np.asarray(metrics["dead_torso_frac"])) < 1.0
        assert float(np.asarray(metrics["behaviour_kl"])) >= 0.0

    def test_gauges_published_under_devtel_learn(self, vtrace_setup):
        learner, traj = vtrace_setup["learner"], vtrace_setup["traj"]
        state, metrics = learner.update(vtrace_setup["state"], traj)
        vtrace_setup["state"] = state
        fetched = learner.publish_device_telemetry()
        lspec = learner.learn_spec
        # Every instrument of the plane must come back in the one
        # merged fetch (red side: a key the spec declares but the
        # update never writes would still appear — value defaults — so
        # ALSO pin the gauge mirrors the last update's metric exactly).
        for name in lspec.gauges():
            assert lspec.value(fetched, name) is not None, name
        assert lspec.value(fetched, "entropy_frac") == pytest.approx(
            float(np.asarray(metrics["entropy_frac"])), rel=1e-6)
        assert lspec.value(fetched, "ess_frac") == pytest.approx(
            float(np.asarray(metrics["ess_frac"])), rel=1e-6)
        for group in ("torso", "core", "heads"):
            assert lspec.value(fetched, f"param_norm_{group}") > 0.0
            assert lspec.value(fetched, f"update_ratio_{group}") >= 0.0
        snap = get_registry().snapshot()
        assert "devtel/learn/entropy_frac" in snap
        assert "devtel/learn/update_ratio_core" in snap

    def test_vtrace_updates_issue_no_host_syncs(self, vtrace_setup):
        """THE zero-added-sync acceptance (ISSUE 17): the fully
        instrumented update — clip diagnostics, entropy/KL/EV, dead
        units, per-group norms — materializes nothing on the host; the
        log-interval fetch stays the only sync."""
        from scalable_agent_tpu.envs.device.conformance import (
            materialization_spy)

        learner, traj = vtrace_setup["learner"], vtrace_setup["traj"]
        state, _ = learner.update(vtrace_setup["state"], traj)  # warm
        with materialization_spy() as calls:
            with jax.transfer_guard("disallow"):
                for _ in range(3):
                    state, _ = learner.update(state, traj)
            assert calls == [], (
                f"learning-telemetry updates materialized device "
                f"values on the host: {calls}")
            vtrace_setup["state"] = state
            learner.fetch_device_telemetry()
            assert calls, "the explicit fetch IS the sync"

    def test_impact_updates_issue_no_host_syncs(self, impact_setup):
        from scalable_agent_tpu.envs.device.conformance import (
            materialization_spy)

        learner, traj = impact_setup["learner"], impact_setup["traj"]
        state, _ = learner.update(impact_setup["state"], traj)  # warm
        with materialization_spy() as calls:
            with jax.transfer_guard("disallow"):
                for _ in range(3):
                    state, _ = learner.update(state, traj)
            assert calls == []
        impact_setup["state"] = state

    def test_impact_histograms_aggregate_across_updates(
            self, impact_setup):
        learner, traj = impact_setup["learner"], impact_setup["traj"]
        state = impact_setup["state"]
        before = learner.fetch_device_telemetry()
        lspec = learner.learn_spec
        count0 = lspec.value(before, "impact_ratio")["count"]
        for _ in range(3):
            state, metrics = learner.update(state, traj)
        impact_setup["state"] = state
        fetched = learner.fetch_device_telemetry()
        hist = lspec.value(fetched, "impact_ratio")
        assert hist["count"] == count0 + 3
        clip_hist = lspec.value(fetched, "impact_clip_fraction")
        assert clip_hist["count"] >= 3
        assert lspec.value(fetched, "impact_ess_frac") == pytest.approx(
            float(np.asarray(metrics["impact_ess_frac"])), rel=1e-6)
        # The per-update ratio is ~1 (the online net hugs its anchor).
        assert hist["mean"] == pytest.approx(1.0, abs=0.2)

    def test_disabled_plane_is_inert(self):
        from __graft_entry__ import _example_trajectory
        from scalable_agent_tpu.models import ImpalaAgent
        from scalable_agent_tpu.parallel import MeshSpec, make_mesh
        from scalable_agent_tpu.runtime import (
            Learner, LearnerHyperparams)

        agent = ImpalaAgent(num_actions=NUM_ACTIONS)
        mesh = make_mesh(MeshSpec(data=1, model=1),
                         devices=jax.devices()[:1])
        learner = Learner(agent, LearnerHyperparams(), mesh,
                          frames_per_update=8, learn_telemetry=False)
        traj = _example_trajectory(4, 2, 16, 16, NUM_ACTIONS)
        state = learner.init(jax.random.key(0), traj)
        state, metrics = learner.update(state, traj)
        assert "entropy_frac" not in metrics
        assert learner.learn_spec.empty
        fetched = learner.fetch_device_telemetry()
        assert not any(k.startswith("g:learn/") for k in fetched)


class TestMegaloopAggregation:
    """``--updates_per_dispatch=K``: one device dispatch runs K fused
    updates; the learn histograms must cover ALL K (the metrics dict
    only surfaces the last scan iteration's scalars)."""

    T, B = 5, 4
    K = 4

    def make(self):
        from scalable_agent_tpu.envs.device import DeviceFakeEnv
        from scalable_agent_tpu.models import ImpalaAgent
        from scalable_agent_tpu.parallel import MeshSpec, make_mesh
        from scalable_agent_tpu.runtime import (
            Learner, LearnerHyperparams)
        from scalable_agent_tpu.runtime.ingraph import InGraphTrainer

        agent = ImpalaAgent(num_actions=NUM_ACTIONS)
        mesh = make_mesh(MeshSpec(data=1, model=1),
                         devices=jax.devices()[:1])
        learner = Learner(agent, LearnerHyperparams(
            total_environment_frames=1e6), mesh,
            frames_per_update=self.T * self.B, loss="impact")
        env = DeviceFakeEnv(height=12, width=12,
                            num_actions=NUM_ACTIONS, episode_length=7)
        return InGraphTrainer(agent, learner, env, self.T, self.B,
                              seed=5, updates_per_dispatch=self.K,
                              ), learner

    def test_one_dispatch_observes_all_k_updates(self):
        trainer, learner = self.make()
        state, carry = trainer.init(jax.random.key(0))
        state, carry, _ = trainer.run(state, carry, self.K)
        fetched = trainer.fetch_telemetry(carry)
        lspec = learner.learn_spec
        for hist in ("impact_ratio", "impact_clip_fraction"):
            assert lspec.value(fetched, hist)["count"] == self.K, hist
        # Gauges carry the last update of the fused scan.
        assert 0.0 < lspec.value(fetched, "entropy_frac") <= 1.0

    def test_fused_dispatch_issues_no_host_syncs(self):
        """The K-update dispatch adds no host sync beyond the update
        counter (a pre-existing per-dispatch input, placed on device
        here so the guard sees only what the learning plane added)."""
        from scalable_agent_tpu.envs.device.conformance import (
            materialization_spy)

        trainer, _ = self.make()
        state, carry = trainer.init(jax.random.key(0))
        counters = [jax.device_put(np.int32(i * self.K))
                    for i in range(3)]
        # Warm the device-counter call signature outside the guard.
        state, carry, _ = trainer.train_step(
            state, carry, counters[0])[:3]
        with materialization_spy() as calls:
            with jax.transfer_guard("disallow"):
                for counter in counters[1:]:
                    state, carry, _ = trainer.train_step(
                        state, carry, counter)[:3]
            assert calls == [], (
                f"the megaloop dispatch materialized device values on "
                f"the host: {calls}")


# ---------------------------------------------------------------------------
# Fleet folds for the new series.
# ---------------------------------------------------------------------------


class TestLearnFleetFolds:
    def _fold(self, metric, values, mtype="gauge"):
        texts = {
            str(i): (f"# TYPE {metric} {mtype}\n{metric} {v}\n")
            for i, v in enumerate(values)}
        families = parse_prometheus(aggregate_prometheus(texts))
        for fam, data in families.items():
            for (name, labels), value in data["series"].items():
                if name == metric and dict(labels).get("fold"):
                    return value, dict(labels)["fold"]
        raise AssertionError(f"no fleet series for {metric}")

    def test_low_is_bad_gauges_fold_min(self):
        """The fleet reading of entropy/ESS/EV keeps the SICKEST
        process — a healthy peer must not mask a collapsing one."""
        for metric in ("impala_devtel_learn_entropy_frac",
                       "impala_devtel_learn_ess_frac",
                       "impala_devtel_learn_explained_variance"):
            value, fold = self._fold(metric, [0.9, 0.2])
            assert (value, fold) == (0.2, "min"), metric

    def test_high_is_bad_gauges_fold_max(self):
        for metric in ("impala_devtel_learn_rho_clip_fraction",
                       "impala_devtel_learn_kl",
                       "impala_devtel_learn_dead_torso_frac",
                       "impala_devtel_learn_update_ratio_core"):
            value, fold = self._fold(metric, [0.1, 0.7])
            assert (value, fold) == (0.7, "max"), metric

    def test_impact_bucket_counters_sum(self):
        metric = ("impala_devtel_learn_impact_ratio_bucket_le_1_total")
        value, fold = self._fold(metric, [3.0, 5.0], mtype="counter")
        assert (value, fold) == (8.0, "sum")


# ---------------------------------------------------------------------------
# obs.diagnose / obs.report / obs.watch over on-disk artifacts.
# ---------------------------------------------------------------------------


def _write_snapshot(logdir, overrides=(), extra=None):
    os.makedirs(logdir, exist_ok=True)
    readings = {**HEALTHY,
                "cs_clip_fraction": 0.1, "pg_rho_clip_fraction": 0.1,
                "log_rho_mean": 0.02, "log_rho_p95": 0.3,
                "grad_norm_torso": 1.0, "grad_norm_core": 1.0,
                "grad_norm_heads": 1.0, "param_norm_torso": 20.0,
                "param_norm_core": 40.0, "param_norm_heads": 3.0,
                **dict(overrides)}
    registry = MetricsRegistry()
    for short, value in readings.items():
        registry.gauge(learning.LEARNING_GAUGES[short], "test").set(value)
    for name, value in (extra or {}).items():
        registry.gauge(name, "test").set(value)
    with open(os.path.join(logdir, "metrics.prom"), "w") as f:
        f.write(render_prometheus(registry))
    return readings


class TestDiagnoseCLI:
    def test_clean_run_exits_zero(self, tmp_path, capsys):
        _write_snapshot(tmp_path)
        assert diagnose_main([str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "verdict: clean" in out
        assert "entropy (normalized)" in out
        assert "layer group" in out and "update/param" in out

    def test_collapsed_run_exits_one_and_names_the_anomaly(
            self, tmp_path, capsys):
        _write_snapshot(tmp_path, overrides={"entropy_frac": 0.004})
        record = {"id": "a001-entropy_collapse",
                  "detector": "entropy_collapse", "update": 12,
                  "observed": 0.004,
                  "flightrec": {"dump": "health:a001-entropy_collapse"},
                  "window": {"status": "closed"}}
        (tmp_path / "anomalies.jsonl").write_text(
            json.dumps(record) + "\n")
        assert diagnose_main([str(tmp_path)]) == 1
        out = capsys.readouterr().out
        assert "entropy_collapse" in out
        assert "a001-entropy_collapse" in out
        assert "flightrec dump: health:a001-entropy_collapse" in out
        assert "raise --entropy_cost" in out

    def test_missing_logdir_exits_two(self, tmp_path, capsys):
        assert diagnose_main([str(tmp_path / "nope")]) == 2
        assert "obs.diagnose" in capsys.readouterr().err

    def test_json_payload_round_trips(self, tmp_path, capsys):
        _write_snapshot(tmp_path, overrides={"ess_frac": 0.02})
        assert diagnose_main([str(tmp_path), "--json"]) == 1
        payload = json.loads(capsys.readouterr().out)
        assert payload["clean"] is False
        assert [v["name"] for v in payload["verdicts"]] == [
            "off_policy_saturated"]

    def test_impact_anchor_line_renders(self, tmp_path):
        _write_snapshot(tmp_path, extra={
            "devtel/learn/impact_ratio/mean": 1.01,
            "devtel/learn/impact_ratio/count": 64.0,
            "devtel/learn/impact_clip_fraction/mean": 0.12,
            "devtel/learn/impact_log_ratio_p95": 0.2,
            "devtel/learn/impact_ess_frac": 0.95})
        diagnosis = build_diagnosis(str(tmp_path))
        assert diagnosis["impact"]["updates_observed"] == 64.0
        text = render_diagnosis(diagnosis)
        assert "IMPACT anchor: ratio mean 1.0100" in text
        assert "over 64 updates" in text

    def test_staleness_clip_statement_from_interval_rows(
            self, tmp_path):
        """Satellite 2: the report/diagnose correlate the ledger's
        replayed-staleness series with the clip-fraction series across
        intervals and state the measured relationship."""
        _write_snapshot(tmp_path)
        rows = [
            {"step": i,
             "obs/ledger/staleness_replayed_s/p95": 0.1 * i,
             "obs/devtel/learn/rho_clip_fraction": 0.05 + 0.08 * i}
            for i in range(1, 6)]
        (tmp_path / "metrics.jsonl").write_text(
            "\n".join(json.dumps(r) for r in rows) + "\n")
        diagnosis = build_diagnosis(str(tmp_path))
        relation = diagnosis["staleness_clip"]
        assert relation["intervals"] == 5
        assert relation["pearson_r"] == pytest.approx(1.0, abs=1e-6)
        assert "staleness→clipping:" in render_diagnosis(diagnosis)


class TestReportAndWatchSections:
    def test_report_carries_learning_section(self, tmp_path):
        from scalable_agent_tpu.obs.report import (
            build_report, render_report)

        _write_snapshot(tmp_path, overrides={"entropy_frac": 0.004})
        report = build_report(str(tmp_path))
        section = report["learning"]
        assert section["snapshot"]["entropy_frac"] == pytest.approx(
            0.004)
        assert [v["name"] for v in section["verdicts"]] == [
            "entropy_collapse"]
        text = render_report(str(tmp_path))
        assert "learning dynamics" in text
        assert "entropy_collapse" in text

    def test_watch_payload_carries_learning_panel(self, tmp_path):
        from scalable_agent_tpu.obs.watch import build_payload, render

        _write_snapshot(tmp_path, overrides={"rho_clip_fraction": 0.97})
        payload = build_payload(str(tmp_path))
        panel = payload["learning"]
        assert panel["snapshot"]["rho_clip_fraction"] == pytest.approx(
            0.97)
        assert [v["name"] for v in panel["verdicts"]] == [
            "off_policy_saturated"]
        text = render(payload)
        assert "learning" in text
        assert "!! off_policy_saturated" in text

    def test_runs_without_the_plane_render_none(self, tmp_path):
        from scalable_agent_tpu.obs.report import build_report
        from scalable_agent_tpu.obs.watch import build_payload

        os.makedirs(tmp_path, exist_ok=True)
        registry = MetricsRegistry()
        registry.gauge("learner/fps", "t").set(100.0)
        (tmp_path / "metrics.prom").write_text(
            render_prometheus(registry))
        assert build_report(str(tmp_path))["learning"] is None
        assert build_payload(str(tmp_path))["learning"] is None


# ---------------------------------------------------------------------------
# Chaos e2e: the oversized-lr run trips entropy_collapse; the sane twin
# stays clean.
# ---------------------------------------------------------------------------


def _driver_config(tmp_path, name, **overrides):
    from scalable_agent_tpu.config import Config

    defaults = dict(
        mode="train",
        logdir=str(tmp_path / name),
        level_name="fake_small",
        num_actors=4,
        batch_size=2,
        unroll_length=4,
        num_action_repeats=1,
        total_environment_frames=80,
        height=16,
        width=16,
        num_env_workers_per_group=2,
        compute_dtype="float32",
        checkpoint_interval_s=0.0,
        log_interval_s=0.0,
        seed=5,
    )
    defaults.update(overrides)
    return Config(**defaults)


class TestChaosEntropyCollapse:
    def test_oversized_lr_trips_the_verdict_sane_twin_clean(
            self, tmp_path):
        """ISSUE 17 chaos e2e: a driver run with a divergence-scale lr
        and an inverted entropy bonus collapses the policy; the health
        plane must write an ``entropy_collapse`` anomaly record with a
        pinned flightrec dump, and ``obs.diagnose`` must name it.  The
        identical sane config stays verdict-clean — same shapes, so
        the second run rides the first one's jit cache."""
        from scalable_agent_tpu.driver import train as run_train
        from scalable_agent_tpu.obs.health import read_anomalies

        bad = _driver_config(tmp_path, "bad", learning_rate=0.5,
                             entropy_cost=-5.0)
        run_train(bad)
        records = read_anomalies(bad.logdir)
        collapse = [r for r in records
                    if r.get("detector") == "entropy_collapse"]
        assert collapse, (
            f"no entropy_collapse anomaly; detectors seen: "
            f"{[r.get('detector') for r in records]}")
        assert (collapse[-1].get("flightrec") or {}).get("dump"), (
            "the collapse anomaly must pin a flight-recorder dump")
        diagnosis = build_diagnosis(bad.logdir)
        names = [v["name"] for v in diagnosis["verdicts"]]
        assert "entropy_collapse" in names
        verdict = diagnosis["verdicts"][names.index("entropy_collapse")]
        # The verdict links the anomaly record the plane wrote live.
        assert any(a.get("flightrec", {}).get("dump")
                   for a in verdict["anomalies"])
        assert diagnose_main([bad.logdir]) == 1

        sane = _driver_config(tmp_path, "sane")
        run_train(sane)
        sane_diag = build_diagnosis(sane.logdir)
        assert sane_diag["clean"], (
            f"sane run fired: {sane_diag['verdicts']}")
        assert not [r for r in read_anomalies(sane.logdir)
                    if r.get("detector") in ("entropy_collapse",
                                             "clip_saturation")]
        assert diagnose_main([sane.logdir]) == 0


# -- the learner's telemetry follows the agent (the token policy declares its
# own groups: models/token_policy.py ``_Family.groups``) ------------------------

def test_the_impala_agents_report_what_they_reported():
    from scalable_agent_tpu.runtime.learner import learning_telemetry_spec

    gauges = learning_telemetry_spec().gauges()
    for group in ("torso", "core", "heads"):
        assert f"grad_norm_{group}" in gauges
    assert not [g for g in gauges if "attention" in g or "experts" in g]
    assert not learning_telemetry_spec().histograms()
