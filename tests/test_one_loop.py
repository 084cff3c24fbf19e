"""The one training loop (ISSUE 28): what a run of either backend shows
from outside, recorded at the commit that still had two loops and held
by the commit that has one.

* the fused backend's logged ``total_loss`` rows and final frame count,
  to the bit (K = 1 and K = 2);
* the host backend's update count, frame count, checkpoint steps and
  ``metrics.jsonl`` key set (its losses depend on thread timing: which
  params an actor thread sees is not a function of the seed);
* one case per backend for each behaviour on which the two loops
  disagreed and the one loop took a side: the ``--profile_dir`` window
  opens once when ``updates`` strides past ``profile_start_update``,
  and a non-finite streak seen at a publish rolls back at the decision
  point, after the publish wrote its row.
"""

import json
import os

import jax
import pytest

from scalable_agent_tpu import driver
from scalable_agent_tpu.config import Config
from scalable_agent_tpu.obs import get_registry
from scalable_agent_tpu.runtime import configure_faults

FRAMES_PER_UPDATE = 8      # batch 2 x unroll 4 x 1 repeat


@pytest.fixture(autouse=True)
def _clean_faults():
    configure_faults("")
    yield
    configure_faults("")


def _config(tmp_path, backend, updates=6, **overrides) -> Config:
    fields = dict(
        mode="train", logdir=str(tmp_path / "run"),
        level_name="fake_small", train_backend=backend,
        num_actors=4, batch_size=2, unroll_length=4,
        num_action_repeats=1, height=16, width=16,
        num_env_workers_per_group=2, compute_dtype="float32",
        total_environment_frames=updates * FRAMES_PER_UPDATE,
        checkpoint_interval_s=1e9, log_interval_s=0.0, seed=7)
    fields.update(overrides)
    return Config(**fields)


def _rows(logdir):
    """The training rows of ``metrics.jsonl`` (not the ``obs/``
    registry snapshots that ride beside them)."""
    with open(os.path.join(logdir, "metrics.jsonl")) as f:
        rows = [json.loads(line) for line in f]
    return [row for row in rows if "total_loss" in row]


def _checkpoint_steps(logdir):
    return sorted(int(name) for name in os.listdir(
        os.path.join(logdir, "checkpoints")) if name.isdigit())


def _counter(name):
    return float(get_registry().snapshot().get(name, 0.0))


# (step, total_loss.hex()) of every training row of the fused run below,
# recorded at the parent commit (289cec2) under the test harness
# (JAX_PLATFORMS=cpu, 8 virtual devices, float32).
FUSED_GOLDEN = {
    1: [(1, "0x1.00476a0000000p+2"), (2, "0x1.6fdf7a0000000p+2"),
        (3, "0x1.838a3a0000000p+3"), (4, "0x1.8e1dce0000000p+1"),
        (5, "0x1.5758ac0000000p+4"), (6, "0x1.042eea0000000p+2")],
    # The megaloop logs each dispatch's last update: the same stream.
    2: [(2, "0x1.6fdf7a0000000p+2"), (4, "0x1.8e1dce0000000p+1"),
        (6, "0x1.042eea0000000p+2")],
}


@pytest.mark.parametrize("k", [1, 2])
def test_fused_run_logs_the_recorded_losses(tmp_path, k):
    config = _config(tmp_path, "ingraph", updates_per_dispatch=k)
    metrics = driver.train(config)
    assert metrics["env_frames"] == 6 * FRAMES_PER_UPDATE
    logged = [(row["step"], float(row["total_loss"]).hex())
              for row in _rows(config.logdir)]
    assert logged == FUSED_GOLDEN[k]
    assert float(metrics["total_loss"]).hex() == logged[-1][1]
    assert _checkpoint_steps(config.logdir) == [6]


@pytest.mark.parametrize("k", [1, 2])
def test_fused_run_behind_the_pallas_stem_is_the_run_that_hands_nothing(
        tmp_path, monkeypatch, k):
    """ISSUE 37: behind the Pallas stem (the interpreter here) the
    rollout hands the update its stem activations and the update's
    forward starts at conv_1 — the same function of the same
    parameters, 256 images at a time where the update's own conv took
    them all at once, so update by update the losses are those of the
    run whose agent declares nothing, to float32 round-off.  (The
    recorded run above resolves to XLA's stem off a TPU: nothing is
    handed there, and its bits stand.)"""
    from scalable_agent_tpu.models import ImpalaAgent

    def losses(name):
        config = _config(tmp_path / name, "ingraph", conv_backend="pallas",
                         updates_per_dispatch=k)
        metrics = driver.train(config)
        assert metrics["env_frames"] == 6 * FRAMES_PER_UPDATE
        return [(row["step"], row["total_loss"])
                for row in _rows(config.logdir)]

    assert ImpalaAgent(num_actions=3, conv_backend="pallas"
                       ).handover_collection == "handover"
    handed = losses("handed")
    assert get_registry().gauge("fused/stem_handed_share").value == 4 / 5
    monkeypatch.setattr(ImpalaAgent, "handover_collection", None)
    plain = losses("plain")
    assert get_registry().gauge("fused/stem_handed_share").value == 0.0
    assert [step for step, _ in handed] == [step for step, _ in plain] == (
        list(range(k, 7, k)))
    assert [loss for _, loss in handed] == pytest.approx(
        [loss for _, loss in plain], rel=1e-5)


# The union of the host run's training-row keys, recorded at the parent
# commit, less the ``episode_*`` ones (in a row only when an episode
# ended inside its interval).
HOST_ROW_KEYS = [
    "actor_fps", "baseline_loss", "behaviour_kl", "cs_clip_fraction",
    "dead_torso_frac", "entropy_frac", "entropy_loss", "env_frames",
    "ess_frac", "explained_variance", "fps", "grad_norm",
    "learning_rate", "log_rho_mean", "log_rho_p95", "nonfinite_skips",
    "nonfinite_streak", "pg_rho_clip_fraction", "policy_entropy",
    "policy_gradient_loss", "rho_clip_fraction", "step", "time",
    "timing/retire", "timing/update", "timing/wait_batch", "total_loss",
    "update_skipped"]


def test_host_run_counts_and_keys(tmp_path):
    config = _config(tmp_path, "host", inflight_updates=1,
                     checkpoint_interval_s=0.0, checkpoint_keep=10)
    saves_before = _counter("checkpoint/saves_total")
    metrics = driver.train(config)
    assert metrics["env_frames"] == 6 * FRAMES_PER_UPDATE
    rows = _rows(config.logdir)
    assert [row["step"] for row in rows] == [1, 2, 3, 4, 5, 6]
    # One save per update and the forced one at the end, which finds
    # step 6 already on disk.
    assert _checkpoint_steps(config.logdir) == [1, 2, 3, 4, 5, 6]
    assert _counter("checkpoint/saves_total") - saves_before >= 6
    keys = sorted(key for key in set().union(*rows)
                  if "episode_" not in key)
    assert keys == HOST_ROW_KEYS


BACKEND_STRIDES = [
    # ``updates`` advances by 2 per iteration in both: K = 2 fused
    # updates per dispatch; one replayed update behind each fresh one.
    pytest.param("ingraph", dict(updates_per_dispatch=2), id="ingraph-k2"),
    pytest.param("host", dict(replay_ratio=1, replay_capacity=4),
                 id="host-replay1"),
]


@pytest.mark.parametrize("backend,overrides", BACKEND_STRIDES)
def test_profile_window_opens_once_when_updates_stride_past_its_start(
        tmp_path, monkeypatch, backend, overrides):
    started, stopped, harvested = [], [], []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda path, *a, **k: started.append(path))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: stopped.append(True))
    # The harvest pays an AOT compile of the update: not this test's.
    monkeypatch.setattr(
        driver, "_harvest_kernel_ledger",
        lambda config, lower_fn, executions, **kw:
        harvested.append(executions))
    profile_dir = str(tmp_path / "profile")
    config = _config(
        tmp_path, backend, updates=6, profile_dir=profile_dir,
        profile_start_update=1, profile_num_updates=2, **overrides)
    driver.train(config)
    # 0 -> 2 -> 4: ``updates`` never equals 1.
    assert started == [profile_dir]
    assert stopped == [True]
    assert harvested == [2]


@pytest.mark.parametrize("backend", ["ingraph", "host"])
def test_nonfinite_streak_seen_at_a_publish_rolls_back_at_the_decision(
        tmp_path, backend):
    config = _config(
        tmp_path, backend, updates=6, inflight_updates=1,
        checkpoint_interval_s=0.0, checkpoint_keep=10,
        chaos_spec="nan_grad@3:4", nonfinite_tolerance=2)
    rollbacks_before = _counter("learner/rollbacks_total")
    metrics = driver.train(config)
    assert metrics["env_frames"] == 6 * FRAMES_PER_UPDATE
    assert _counter("learner/rollbacks_total") == rollbacks_before + 1
    rows = _rows(config.logdir)
    # The publish that saw the streak (after update 4) wrote its row;
    # the rollback came after it, at the decision point, to the newest
    # checkpoint (step 3), and updates 4-6 ran again with every
    # update's checkpoint taken.
    assert [row["step"] for row in rows] == [1, 2, 3, 4, 4, 5, 6]
    assert [row["update_skipped"] for row in rows[:4]] == [0, 0, 1, 1]
    assert _checkpoint_steps(config.logdir) == [1, 2, 3, 4, 5, 6]
