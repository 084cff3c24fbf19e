"""ISSUE 16: the run-health plane (obs/health.py) + its consoles.

Golden synthetic streams per detector kind (step change, slow drift,
flapping under cooldown, warm-up gating, baseline-primed immediate
fire), the anomaly-record schema, the profiling-window budget/cooldown
arbitration, the fleet fold rules for ``health/*`` series, the
``obs.watch`` console on a synthetic logdir, the exit-2 contract of
both jax-free CLIs, the ``/anomalies`` + ``/health`` HTTP routes — and
the tier-1 acceptance run: a CPU driver run under
``--chaos_spec='throughput_sag@...'`` must detect the sag, pin + dump
the flight recorder, and auto-profile exactly one window whose
harvested ``kernels.<anomaly_id>.json`` lands back in the record,
while the identical run without chaos stays anomaly-free.
"""

import glob
import json
import os
import subprocess
import sys
import time
import urllib.error
import urllib.request

import pytest

from scalable_agent_tpu.obs import aggregate
from scalable_agent_tpu.obs.exporters import MetricsHTTPServer
from scalable_agent_tpu.obs.health import (
    ANOMALIES_JSONL,
    DetectorSpec,
    HealthMonitor,
    default_detectors,
    read_anomalies,
)
from scalable_agent_tpu.obs.registry import MetricsRegistry

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def advance(self, dt):
        self.t += dt

    def __call__(self):
        return self.t


class _StubRecorder:
    """Flight-recorder stand-in: records the pin/dump protocol without
    touching the process-global ring."""

    def __init__(self):
        self.reason_pin = None
        self.last_dump_reason = None
        self.events = []

    def record(self, kind, name, payload=None):
        self.events.append((kind, name, payload))

    def dump_all(self, reason):
        if self.reason_pin is not None:
            reason = self.reason_pin
        self.last_dump_reason = reason


def _monitor(detectors, clock=None, logdir=None, **kwargs):
    kwargs.setdefault("registry", MetricsRegistry())
    kwargs.setdefault("recorder", _StubRecorder())
    return HealthMonitor(detectors, logdir=logdir,
                         clock=clock or _FakeClock(), **kwargs)


class TestDetectorGoldens:
    def test_step_change_trips_ewma_after_warmup(self):
        clock = _FakeClock()
        mon = _monitor([DetectorSpec(name="fps", metric="m", warmup=3)],
                       clock=clock)
        for _ in range(4):
            clock.advance(10.0)
            assert mon.step({"m": 1000.0}) == []
        clock.advance(10.0)
        fired = mon.step({"m": 250.0}, update=5)
        assert len(fired) == 1
        record = fired[0]
        assert record["detector"] == "fps"
        assert record["observed"] == 250.0
        assert record["baseline"] == pytest.approx(1000.0)
        assert record["rel"] >= 0.6
        assert record["primed"] is False

    def test_warmup_gates_an_early_drop(self):
        clock = _FakeClock()
        mon = _monitor([DetectorSpec(name="fps", metric="m", warmup=3)],
                       clock=clock)
        clock.advance(10.0)
        assert mon.step({"m": 1000.0}) == []
        clock.advance(10.0)
        # A 10x drop on sample 2 (compile-dominated interval in a real
        # run) must NOT fire — the detector is still warming up.
        assert mon.step({"m": 100.0}) == []

    def test_slow_drift_trips_cusum_but_not_ewma(self):
        """A +4%-per-interval loss creep: each interval's z stays far
        under the spike threshold (no single-step anomaly exists), but
        the one-sided CUSUM accumulates it into a drift verdict."""
        clock = _FakeClock()
        specs = [
            DetectorSpec(name="spike", metric="loss", kind="ewma",
                         direction="high", warmup=4, z_threshold=5.0,
                         rel_threshold=None, min_rel=0.0,
                         sigma_floor_rel=0.05),
            DetectorSpec(name="drift", metric="loss", kind="cusum",
                         direction="high", warmup=4,
                         sigma_floor_rel=0.05),
        ]
        mon = _monitor(specs, clock=clock, cooldown_s=0.0)
        fired_names = []
        value = 1.0
        for i in range(30):
            clock.advance(10.0)
            if i >= 5:
                value += 0.04
            fired_names += [r["detector"]
                            for r in mon.step({"loss": value})]
        assert "drift" in fired_names
        assert "spike" not in fired_names

    def test_flapping_is_suppressed_by_cooldown(self):
        clock = _FakeClock()
        reg = MetricsRegistry()
        mon = _monitor([DetectorSpec(name="fps", metric="m", warmup=3)],
                       clock=clock, registry=reg, cooldown_s=120.0)
        for _ in range(4):
            clock.advance(10.0)
            mon.step({"m": 1000.0})
        fired = []
        # Flap: bad/good alternating at 10 s — only the FIRST bad
        # interval may open a record inside the 120 s cooldown.
        for i in range(6):
            clock.advance(10.0)
            value = 100.0 if i % 2 == 0 else 1000.0
            fired += mon.step({"m": value})
        assert len(fired) == 1
        snap = reg.snapshot()
        assert snap["health/anomalies_total"] == 1.0
        assert snap["health/suppressed_total"] >= 2.0
        # After the cooldown expires the detector may alarm again.
        clock.advance(200.0)
        assert len(mon.step({"m": 100.0})) == 1

    def test_primed_baseline_fires_inside_warmup(self, tmp_path):
        artifact = {"metric": "x", "value": 1, "unit": "fps",
                    "vs_baseline": 1.0,
                    "e2e_env_frames_per_sec": 50_000.0}
        (tmp_path / "BENCH_r07.json").write_text(json.dumps(artifact))
        clock = _FakeClock()
        mon = _monitor(default_detectors(warmup=8), clock=clock)
        assert mon.prime_from_bench(str(tmp_path)) == "BENCH_r07.json"
        clock.advance(10.0)
        # First-ever sample, deep inside warm-up: 20k is under half the
        # committed 50k baseline -> immediate primed trip.
        fired = mon.step({"learner/fps": 20_000.0}, update=1)
        assert [r["detector"] for r in fired] == ["throughput"]
        record = fired[0]
        assert record["primed"] is True
        assert record["baseline"] == 50_000.0
        assert record["baseline_source"] == "BENCH_r07.json"
        assert record["z"] is None

    def test_prime_from_committed_rounds(self, bench_history):
        """A bench dir of parseable BENCH rounds with the throughput
        keys — priming must find them, across every wrapper format."""
        mon = _monitor(default_detectors())
        assert mon.prime_from_bench(bench_history) is not None

    def test_nonfinite_rate_detector(self):
        clock = _FakeClock()
        mon = _monitor([spec for spec in default_detectors()
                        if spec.name == "nonfinite"], clock=clock)
        clock.advance(10.0)
        assert mon.step(
            {"learner/nonfinite_skips_total": 0.0}) == []  # reference
        clock.advance(10.0)
        assert mon.step({"learner/nonfinite_skips_total": 0.0}) == []
        clock.advance(10.0)
        fired = mon.step({"learner/nonfinite_skips_total": 2.0})
        assert [r["detector"] for r in fired] == ["nonfinite"]
        assert fired[0]["observed"] == pytest.approx(0.2)  # 2 per 10 s
        # The nonfinite guard owns its own forensics: never pin.
        assert fired[0]["flightrec"]["pinned"] is False

    def test_peers_alive_learns_fleet_size_from_first_sample(self):
        clock = _FakeClock()
        mon = _monitor([spec for spec in default_detectors()
                        if spec.name == "peers_alive"], clock=clock)
        for _ in range(2):
            clock.advance(10.0)
            assert mon.step({"fleet/peers_alive": 2.0}) == []
        clock.advance(10.0)
        fired = mon.step({"fleet/peers_alive": 1.0})
        assert [r["detector"] for r in fired] == ["peers_alive"]
        assert fired[0]["baseline"] == 2.0


class TestRecordSchemaAndArtifact:
    def _trip(self, tmp_path, **monitor_kwargs):
        clock = _FakeClock()
        recorder = _StubRecorder()
        mon = _monitor([DetectorSpec(name="fps", metric="m", warmup=2)],
                       clock=clock, logdir=str(tmp_path),
                       recorder=recorder, **monitor_kwargs)
        for _ in range(3):
            clock.advance(10.0)
            mon.step({"m": 1000.0})
        clock.advance(10.0)
        (record,) = mon.step({"m": 100.0}, update=7,
                             verdict="env_bound",
                             evidence={"ledger_dominant": "unroll",
                                       "ledger_dominant_share": 0.8})
        return mon, record, recorder

    def test_record_schema_and_pin_protocol(self, tmp_path):
        mon, record, recorder = self._trip(tmp_path)
        assert record["schema_version"] == 1
        assert record["id"] == "a001-fps"
        assert record["kind"] == "ewma"
        assert record["metric"] == "m"
        assert record["update"] == 7
        assert record["verdict"] == "env_bound"
        assert record["dominant_segment"] == "unroll"
        assert record["dominant_share"] == 0.8
        assert record["flightrec"] == {"pinned": True,
                                       "dump": "health:a001-fps"}
        assert recorder.reason_pin == "health:a001-fps"
        assert ("anomaly", "fps", {"id": "a001-fps", "metric": "m"}) \
            in recorder.events
        # The event-sourced artifact round-trips.
        (reread,) = read_anomalies(str(tmp_path))
        assert reread["id"] == record["id"]
        assert reread["window"]["status"] == "armed"

    def test_existing_pin_is_never_demoted(self, tmp_path):
        clock = _FakeClock()
        recorder = _StubRecorder()
        recorder.reason_pin = "nonfinite:no_rollback"
        mon = _monitor([DetectorSpec(name="fps", metric="m", warmup=2)],
                       clock=clock, logdir=str(tmp_path),
                       recorder=recorder)
        for _ in range(3):
            clock.advance(10.0)
            mon.step({"m": 1000.0})
        clock.advance(10.0)
        (record,) = mon.step({"m": 100.0})
        assert recorder.reason_pin == "nonfinite:no_rollback"
        assert record["flightrec"]["pinned"] is False
        assert record["flightrec"]["dump"] == "nonfinite:no_rollback"

    def test_read_anomalies_skips_torn_tail(self, tmp_path):
        path = tmp_path / ANOMALIES_JSONL
        path.write_text(json.dumps({"id": "a001-x", "detector": "x"})
                        + "\n" + '{"id": "a002-y", "detec')
        records = read_anomalies(str(tmp_path))
        assert [r["id"] for r in records] == ["a001-x"]

    def test_last_record_per_id_wins(self, tmp_path):
        path = tmp_path / ANOMALIES_JSONL
        path.write_text(
            json.dumps({"id": "a001-x", "window": {"status": "armed"}})
            + "\n"
            + json.dumps({"id": "a001-x", "window": {"status": "done"}})
            + "\n")
        (record,) = read_anomalies(str(tmp_path))
        assert record["window"]["status"] == "done"


class TestWindowArbitration:
    def _specs(self):
        return [DetectorSpec(name="a", metric="ma", warmup=2),
                DetectorSpec(name="b", metric="mb", warmup=2)]

    def _warm(self, mon, clock, steps=3):
        for _ in range(steps):
            clock.advance(10.0)
            mon.step({"ma": 1000.0, "mb": 1000.0})

    def test_busy_budget_and_cooldown(self, tmp_path):
        clock = _FakeClock()
        mon = _monitor(self._specs(), clock=clock,
                       logdir=str(tmp_path), cooldown_s=120.0,
                       max_windows=2)
        self._warm(mon, clock)
        clock.advance(10.0)
        (rec_a,) = mon.step({"ma": 100.0, "mb": 1000.0})
        assert rec_a["window"]["status"] == "armed"
        assert mon.poll_window() == rec_a["id"]
        assert mon.poll_window() == rec_a["id"]  # poll does not consume
        mon.note_window_open(rec_a["id"], trace_dir="/t")
        # While a window is open, a second trip cannot arm another.
        clock.advance(10.0)
        (rec_b,) = mon.step({"ma": 100.0, "mb": 100.0})
        assert rec_b["window"]["status"] == "skipped:busy"
        mon.note_window_result(
            rec_a["id"],
            {"worst_kernel": "f.1", "worst_kernel_mfu": 0.3,
             "dominant_kernel": "f.1", "kernels": [
                 {"name": "f.1", "mfu": 0.3, "time_us": 180.0}]},
            kernels_json="k.json")
        # Window cooldown: 60 s after the open is inside the 120 s
        # window cooldown even though detector b's own cooldown has
        # NOT expired — advance past the detector cooldown but keep
        # the window one active via a fresh detector.
        clock.advance(170.0)  # t = open + 180 > 120: cooldown clear
        (rec_b2,) = mon.step({"ma": 1000.0, "mb": 100.0})
        assert rec_b2["window"]["status"] == "armed"
        mon.note_window_open(rec_b2["id"])
        mon.note_window_result(rec_b2["id"], None)
        # Budget exhausted (max_windows=2): further trips skip.
        clock.advance(170.0)
        (rec_a2,) = mon.step({"ma": 100.0, "mb": 1000.0})
        assert rec_a2["window"]["status"] == "skipped:budget"

    def test_window_cooldown_skips(self, tmp_path):
        clock = _FakeClock()
        mon = _monitor(self._specs(), clock=clock,
                       logdir=str(tmp_path), cooldown_s=120.0,
                       max_windows=5)
        self._warm(mon, clock)
        clock.advance(10.0)
        (rec_a,) = mon.step({"ma": 100.0, "mb": 1000.0})
        mon.note_window_open(rec_a["id"])
        mon.note_window_result(rec_a["id"], None)
        # Detector b trips for the FIRST time (no detector cooldown)
        # 60 s after the window opened: the window cooldown gates it.
        clock.advance(60.0)
        (rec_b,) = mon.step({"ma": 1000.0, "mb": 100.0})
        assert rec_b["window"]["status"] == "skipped:cooldown"

    def test_result_carries_worst_kernel_delta(self, tmp_path):
        clock = _FakeClock()
        mon = _monitor(self._specs(), clock=clock,
                       logdir=str(tmp_path), cooldown_s=0.0,
                       max_windows=1)
        mon.note_baseline_kernels(
            {"worst_kernel": "f.1", "worst_kernel_mfu": 0.5,
             "kernels": [{"name": "f.1", "mfu": 0.5,
                          "time_us": 100.0}]})
        self._warm(mon, clock)
        clock.advance(10.0)
        (record,) = mon.step({"ma": 100.0, "mb": 1000.0})
        mon.note_window_open(record["id"], trace_dir="/t")
        mon.note_window_result(
            record["id"],
            {"worst_kernel": "f.1", "worst_kernel_mfu": 0.3,
             "dominant_kernel": "f.1",
             "kernels": [{"name": "f.1", "mfu": 0.3,
                          "time_us": 180.0}]},
            kernels_json="kernels.a001-a.json")
        (final,) = read_anomalies(str(tmp_path))
        window = final["window"]
        assert window["status"] == "done"
        assert window["kernels_json"] == "kernels.a001-a.json"
        assert window["worst_kernel"] == "f.1"
        assert window["baseline_worst_kernel"] == "f.1"
        assert window["worst_kernel_mfu_delta"] == pytest.approx(-0.2)
        assert window["worst_kernel_time_delta_us"] == pytest.approx(80.0)

    def test_flush_finalizes_open_records(self, tmp_path):
        clock = _FakeClock()
        mon = _monitor(self._specs(), clock=clock,
                       logdir=str(tmp_path), cooldown_s=0.0,
                       max_windows=2)
        self._warm(mon, clock)
        clock.advance(10.0)
        (rec_a,) = mon.step({"ma": 100.0, "mb": 1000.0})
        mon.note_window_open(rec_a["id"])
        clock.advance(130.0)
        (rec_b,) = mon.step({"ma": 1000.0, "mb": 100.0})
        # b armed while a is... a is open, so b was skipped:busy —
        # release a's slot first so b can arm.
        assert rec_b["window"]["status"] == "skipped:busy"
        mon.flush()
        by_id = {r["id"]: r for r in read_anomalies(str(tmp_path))}
        assert by_id[rec_a["id"]]["window"]["status"] \
            == "aborted:run_ended"

    def test_flush_skips_never_opened_armed_window(self, tmp_path):
        clock = _FakeClock()
        mon = _monitor(self._specs(), clock=clock,
                       logdir=str(tmp_path), cooldown_s=0.0)
        self._warm(mon, clock)
        clock.advance(10.0)
        (record,) = mon.step({"ma": 100.0, "mb": 1000.0})
        assert record["window"]["status"] == "armed"
        mon.flush()
        (final,) = read_anomalies(str(tmp_path))
        assert final["window"]["status"] == "skipped:run_ended"
        assert mon.poll_window() is None


class TestFleetFold:
    def test_health_series_fold_rules(self):
        # One-hot fired gauges and the open-anomaly level: "did ANY
        # process see it" — max.
        assert aggregate._fleet_fold(
            "impala_health_fired_throughput",
            "impala_health_fired_throughput", "gauge", ()) == "max"
        assert aggregate._fleet_fold(
            "impala_health_open_anomalies",
            "impala_health_open_anomalies", "gauge", ()) == "max"
        # The totals are real counters: the kind rule sums them.
        assert aggregate._fleet_fold(
            "impala_health_anomalies_total",
            "impala_health_anomalies_total", "counter", ()) == "sum"


def _write_synthetic_logdir(logdir):
    os.makedirs(logdir, exist_ok=True)
    with open(os.path.join(logdir, "metrics.prom"), "w") as f:
        f.write(
            "# TYPE impala_learner_fps gauge\n"
            "impala_learner_fps 1000.0\n"
            "# TYPE impala_actor_fps gauge\n"
            "impala_actor_fps 1200.0\n"
            "# TYPE impala_ledger_mfu gauge\n"
            "impala_ledger_mfu 0.12\n"
            "# TYPE impala_fleet_peers_alive gauge\n"
            "impala_fleet_peers_alive 2.0\n"
            "# TYPE impala_health_suppressed_total counter\n"
            "impala_health_suppressed_total 1.0\n"
            "# TYPE impala_health_profile_windows_total counter\n"
            "impala_health_profile_windows_total 1.0\n"
            "# TYPE impala_ledger_latency_share_device gauge\n"
            "impala_ledger_latency_share_device 0.6\n"
            "# TYPE impala_ledger_latency_share_unroll gauge\n"
            "impala_ledger_latency_share_unroll 0.2\n"
            "# TYPE impala_ledger_staleness_s summary\n"
            'impala_ledger_staleness_s{quantile="0.95"} 0.5\n')
    with open(os.path.join(logdir, ANOMALIES_JSONL), "w") as f:
        f.write(json.dumps({
            "id": "a001-throughput", "detector": "throughput",
            "metric": "learner/fps", "observed": 250.0,
            "baseline": 1000.0, "z": 6.1,
            "window": {"status": "done",
                       "worst_kernel": "loss_grad_fusion",
                       "worst_kernel_mfu": 0.11,
                       "worst_kernel_mfu_delta": -0.2}}) + "\n")
        f.write(json.dumps({
            "id": "a002-staleness", "detector": "staleness",
            "metric": "ledger/staleness_s/p95", "observed": 4.0,
            "baseline": 0.5, "z": 5.0,
            "window": {"status": "armed"}}) + "\n")


class TestWatchConsole:
    def test_build_payload_on_synthetic_logdir(self, tmp_path):
        from scalable_agent_tpu.obs import watch

        logdir = str(tmp_path / "run")
        _write_synthetic_logdir(logdir)
        payload = watch.build_payload(logdir,
                                      bench_dir=str(tmp_path / "none"))
        assert payload["fps"]["learner"] == 1000.0
        assert payload["verdict"]["dominant_segment"] == "device"
        assert payload["staleness_p95_s"] == 0.5
        assert payload["health"]["anomalies"] == 2
        assert payload["health"]["open"] == 1
        assert payload["health"]["profile_windows"] == 1.0
        text = watch.render(payload)
        assert "a001-throughput" in text
        assert "loss_grad_fusion" in text
        assert "anomalies  2 total (1 open" in text

    def test_vs_baseline_uses_committed_rounds(self, tmp_path,
                                               bench_history):
        from scalable_agent_tpu.obs import watch

        logdir = str(tmp_path / "run")
        _write_synthetic_logdir(logdir)
        payload = watch.build_payload(logdir, bench_dir=bench_history)
        assert payload["baseline"] is not None
        assert payload["fps"]["vs_baseline"] is not None

    def test_missing_logdir_exits_2_in_process(self, tmp_path, capsys):
        from scalable_agent_tpu.obs import watch

        assert watch.main([str(tmp_path / "nope"), "--once"]) == 2
        assert "obs.watch:" in capsys.readouterr().err

    def test_metrics_free_logdir_exits_2_in_process(self, tmp_path,
                                                    capsys):
        from scalable_agent_tpu.obs import watch

        empty = tmp_path / "empty"
        empty.mkdir()
        assert watch.main([str(empty), "--once"]) == 2
        err = capsys.readouterr().err
        assert "obs.watch:" in err and "metrics" in err

    def test_once_json_emits_payload(self, tmp_path, capsys):
        from scalable_agent_tpu.obs import watch

        logdir = str(tmp_path / "run")
        _write_synthetic_logdir(logdir)
        assert watch.main([logdir, "--once", "--json",
                           "--bench_dir", str(tmp_path / "none")]) == 0
        payload = json.loads(capsys.readouterr().out)
        assert payload["health"]["anomalies"] == 2


class TestCLIExitCodes:
    """Satellite 2: both jax-free CLIs exit 2 with a one-line
    diagnosis on a missing/metrics-free logdir — as subprocesses, the
    way an operator hits them."""

    def test_watch_subprocess_exit_2(self, tmp_path):
        proc = subprocess.run(
            [sys.executable, "-m", "scalable_agent_tpu.obs.watch",
             str(tmp_path / "missing"), "--once"],
            capture_output=True, text=True, cwd=REPO_ROOT, timeout=120)
        assert proc.returncode == 2
        assert proc.stderr.strip().startswith("obs.watch:")
        assert len(proc.stderr.strip().splitlines()) == 1

    def test_report_subprocess_exit_2(self, tmp_path):
        empty = tmp_path / "empty"
        empty.mkdir()
        proc = subprocess.run(
            [sys.executable, "-m", "scalable_agent_tpu.obs.report",
             str(empty)],
            capture_output=True, text=True, cwd=REPO_ROOT, timeout=120)
        assert proc.returncode == 2
        assert proc.stderr.strip().startswith("obs.report:")
        assert len(proc.stderr.strip().splitlines()) == 1

    def test_watch_subprocess_json_payload(self, tmp_path):
        logdir = str(tmp_path / "run")
        _write_synthetic_logdir(logdir)
        proc = subprocess.run(
            [sys.executable, "-m", "scalable_agent_tpu.obs.watch",
             logdir, "--once", "--json",
             "--bench_dir", str(tmp_path / "none")],
            capture_output=True, text=True, cwd=REPO_ROOT, timeout=120)
        assert proc.returncode == 0, proc.stderr
        payload = json.loads(proc.stdout)
        assert payload["logdir"] == logdir
        assert payload["health"]["anomalies"] == 2


class TestReportAndRoundsSections:
    def test_report_carries_anomalies_section(self, tmp_path, capsys):
        from scalable_agent_tpu.obs import report

        logdir = str(tmp_path / "run")
        _write_synthetic_logdir(logdir)
        payload = report.build_report(logdir)
        assert payload["anomalies"] is not None
        ids = [a["id"] for a in payload["anomalies"]]
        assert ids == ["a001-throughput", "a002-staleness"]
        assert report.main([logdir]) == 0
        out = capsys.readouterr().out
        assert "anomalies (2 recorded" in out
        assert "a001-throughput" in out

    def test_report_without_anomalies_is_none(self, tmp_path):
        from scalable_agent_tpu.obs import report

        logdir = str(tmp_path / "run")
        _write_synthetic_logdir(logdir)
        os.remove(os.path.join(logdir, ANOMALIES_JSONL))
        assert report.build_report(logdir)["anomalies"] is None

    def test_rounds_trajectory_carries_anomalies(self, tmp_path,
                                                 capsys):
        from scalable_agent_tpu.obs import rounds

        artifact = {"metric": "x", "value": 1, "unit": "fps",
                    "vs_baseline": 1.0,
                    "e2e_env_frames_per_sec": 9000.0,
                    "anomalies": [{
                        "id": "a001-throughput",
                        "detector": "throughput",
                        "metric": "learner/fps", "observed": 250.0,
                        "baseline": 1000.0, "z": 6.1,
                        "window": {"status": "done"}}]}
        (tmp_path / "BENCH_r01.json").write_text(json.dumps(artifact))
        trajectory = rounds.build_trajectory(str(tmp_path))
        assert 1 in trajectory["anomalies"]
        assert trajectory["anomalies"][1][0]["id"] == "a001-throughput"
        text = rounds.render_trajectory(trajectory)
        assert "run-health anomalies (obs/health.py):" in text
        assert "a001-throughput" in text
        assert rounds.main(["report", "--json",
                            "--bench_dir", str(tmp_path)]) == 0
        machine = json.loads(capsys.readouterr().out)
        assert machine["anomalies"]["1"][0]["id"] == "a001-throughput"


class TestHTTPRoutes:
    def test_anomalies_and_health_routes(self, tmp_path):
        logdir = str(tmp_path / "run")
        _write_synthetic_logdir(logdir)
        registry = MetricsRegistry()
        registry.counter("scrapes").inc()
        with MetricsHTTPServer(registry, port=0,
                               logdir=logdir) as server:
            base = f"http://127.0.0.1:{server.port}"
            body = urllib.request.urlopen(
                f"{base}/anomalies", timeout=5).read().decode()
            lines = [json.loads(line)
                     for line in body.splitlines() if line.strip()]
            assert [r["id"] for r in lines] \
                == ["a001-throughput", "a002-staleness"]
            health = json.loads(urllib.request.urlopen(
                f"{base}/health", timeout=5).read().decode())
            assert health["health"]["anomalies"] == 2
            # The plain scrape still works next to the new routes.
            metrics = urllib.request.urlopen(
                f"{base}/metrics", timeout=5).read().decode()
            assert "impala_scrapes" in metrics

    def test_health_route_503_before_first_snapshot(self, tmp_path):
        logdir = str(tmp_path / "empty")
        os.makedirs(logdir)
        with MetricsHTTPServer(MetricsRegistry(), port=0,
                               logdir=logdir) as server:
            base = f"http://127.0.0.1:{server.port}"
            # No anomalies yet: an empty, valid NDJSON stream.
            assert urllib.request.urlopen(
                f"{base}/anomalies", timeout=5).read() == b""
            with pytest.raises(urllib.error.HTTPError) as err:
                urllib.request.urlopen(f"{base}/health", timeout=5)
            assert err.value.code == 503

    def test_routes_absent_without_logdir(self):
        with MetricsHTTPServer(MetricsRegistry(), port=0) as server:
            with pytest.raises(urllib.error.HTTPError) as err:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{server.port}/anomalies",
                    timeout=5)
            assert err.value.code == 404


# -- the tier-1 acceptance run ----------------------------------------------


def _health_config(tmp_path, **overrides):
    from scalable_agent_tpu.config import Config

    base = dict(
        mode="train",
        logdir=str(tmp_path / "run"),
        level_name="fake_small",
        num_actors=4,
        batch_size=2,
        unroll_length=4,
        num_action_repeats=1,
        total_environment_frames=96,  # 12 updates of 8 frames
        height=16,
        width=16,
        num_env_workers_per_group=2,
        compute_dtype="float32",
        checkpoint_interval_s=1e9,
        log_interval_s=0.0,
        seed=5,
        # 12 update-cadence intervals: the first 6 (compile-dominated,
        # noisy-loss warm-in) only build baselines.  The z paths are
        # off: a batch-2 run's loss and grad-norm swing by multiples
        # (4 <-> 21; 1.3 <-> 9.5), differently on every host-backend
        # run (which params an actor thread sees is thread timing), and
        # a z trip on one of them takes the cooldown the sag's record
        # needs.  The sag's ~97% relative fps drop trips the rel path
        # on its own; the z arithmetic has its unit tests above.
        health_warmup_intervals=6,
        health_z_threshold=1e9,
        health_max_windows=1,
        health_window_updates=2,
    )
    base.update(overrides)
    return Config(**base)


@pytest.fixture(autouse=True)
def _clean_faults():
    from scalable_agent_tpu.runtime import configure_faults

    configure_faults("")
    yield
    configure_faults("")


@pytest.mark.chaos
def test_throughput_sag_drives_the_full_anomaly_protocol(
        tmp_path, monkeypatch, capsys):
    """The acceptance loop: a chaos-injected mid-run slowdown must (1)
    land a throughput anomaly record with attribution, (2) pin + dump
    the flight recorder, and (3) auto-profile exactly one window whose
    harvested kernel ledger is referenced from the final record."""
    from scalable_agent_tpu.driver import train as run_train
    from scalable_agent_tpu.obs import get_registry, report

    monkeypatch.setenv("SCALABLE_AGENT_LEDGER_MFU_PEAK", "1e12")
    config = _health_config(tmp_path,
                            chaos_spec="throughput_sag@8:11")
    # The registry is a process singleton: health counters accumulate
    # across every driver test in the session, so assert deltas.
    before = get_registry().snapshot()
    windows_before = before.get("health/profile_windows_total", 0.0)
    metrics = run_train(config)
    assert metrics["env_frames"] == 96

    records = read_anomalies(config.logdir)
    throughput = [r for r in records if r["detector"] == "throughput"]
    assert throughput, records
    record = throughput[0]
    assert record["observed"] < record["baseline"]
    assert record["rel"] >= 0.6
    # Attribution at trip time: the host backend runs the stall
    # attributor and the ledger, so the record names at least one.
    assert (record["verdict"] is not None
            or record["dominant_segment"] is not None), record

    # (2) pinned + dumped flight recorder.
    assert record["flightrec"]["pinned"] is True
    assert record["flightrec"]["dump"] == f"health:{record['id']}"
    assert glob.glob(os.path.join(config.logdir, "flightrec.*.json"))

    # (3) exactly one auto-profile window, done, with the harvested
    # per-anomaly kernel ledger written back into the record.
    assert record["window"]["status"] == "done", record
    kernels_json = record["window"]["kernels_json"]
    assert os.path.basename(kernels_json) \
        == f"kernels.{record['id']}.json"
    assert os.path.exists(kernels_json)
    table = json.load(open(kernels_json))
    assert table["kernels"] and table["dominant_kernel"]
    # The table's verdicts are written back.  Its worst kernel is the
    # lowest-MFU one above a share of the window's time that no kernel
    # with flops need reach in a CPU trace of this tiny update: it may
    # be None, which the kernel ledger's own tests cover.
    assert record["window"]["dominant_kernel"] == table["dominant_kernel"]
    assert record["window"]["worst_kernel"] == table["worst_kernel"]

    prom = open(os.path.join(config.logdir, "metrics.prom")).read()
    assert "impala_health_profile_windows_total" in prom
    assert "impala_health_anomalies_total" in prom
    after = get_registry().snapshot()
    assert after.get("health/profile_windows_total", 0.0) \
        - windows_before == 1.0
    assert len(glob.glob(os.path.join(
        config.logdir, "health_profile.*"))) == 1

    # The second sag (occurrence 8) fell inside the cooldown: one
    # throughput record total, suppressions counted.
    assert len(throughput) == 1

    # The consoles surface it: watch --once --json and the report.
    from scalable_agent_tpu.obs import watch

    assert watch.main([config.logdir, "--once", "--json",
                       "--bench_dir",
                       str(tmp_path / "nobench")]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["health"]["anomalies"] >= 1
    assert any(r["detector"] == "throughput"
               for r in payload["health"]["recent"])

    assert report.main(["--json", config.logdir]) == 0
    machine = json.loads(capsys.readouterr().out)
    assert machine["anomalies"] is not None
    assert any(a["id"] == record["id"] for a in machine["anomalies"])


class _SteadyClock:
    """The ``time`` module with a ``monotonic`` that advances one
    second a call: the loop's fps then depends on no load."""

    def __init__(self):
        self._now = 0.0

    def monotonic(self):
        self._now += 1.0
        return self._now

    def __getattr__(self, name):
        return getattr(time, name)


@pytest.mark.chaos
def test_clean_run_stays_anomaly_free(tmp_path, monkeypatch):
    """A run without chaos: zero anomalies — the detectors' warm-up +
    thresholds must absorb a small run's learning noise.  Every
    detector input is made a function of the seed: the fused backend's
    losses are (tests/test_one_loop.py holds them to the bit; the host
    backend's depend on thread timing), and the loop's clock is pinned,
    so the suite's load (six workers) cannot trip the fps detector."""
    from scalable_agent_tpu import driver
    from scalable_agent_tpu.driver import train as run_train
    from scalable_agent_tpu.obs import get_registry

    monkeypatch.setattr(driver, "time", _SteadyClock())
    config = _health_config(tmp_path, train_backend="ingraph",
                            health_z_threshold=6.0)
    before = get_registry().snapshot().get("health/anomalies_total", 0.0)
    metrics = run_train(config)
    assert metrics["env_frames"] == 96
    assert read_anomalies(config.logdir) == []
    prom = open(os.path.join(config.logdir, "metrics.prom")).read()
    assert "impala_health_anomalies_total" in prom
    after = get_registry().snapshot().get("health/anomalies_total", 0.0)
    assert after - before == 0.0
