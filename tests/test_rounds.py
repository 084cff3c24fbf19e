"""obs/rounds.py: the bench-round orchestrator, artifact validator,
and longitudinal trajectory/scoreboard (ISSUE 14).

Four suites, all tier-1 and jax-free on the module under test:

- **golden parse/trajectory** over the synthetic five-round history
  (tests/bench_history.py, the ``bench_history`` fixture) — r01's
  failed round, r02-r04's wrapper formats, r05's TRUNCATED tail
  (regex-salvaged with zero hand-editing of the artifact JSON), the
  e2e 12.6k fps / 0.42x headline, the conv0_gradw worst-kernel series,
  and the r05 learning curve;
- **scoreboard** met/unmet/unmeasured unit tests against the encoded
  ROADMAP r06 targets;
- **validate** over that history (the tripwire: a truncated-tail
  artifact without its sidecar fails fast) plus hermetic truncation/
  sidecar/schema-violation cases in tmp dirs;
- **round-runner stage isolation** against a stub bench: a hard-crashed
  suite and a hung suite both land as failed/timeout stage records
  while every other suite's numbers survive in a schema-valid artifact,
  subset re-runs merge onto the newest artifact, and the cross-suite
  context hand-off delivers earlier suites' keys to later ones.
"""

import json
import os
import subprocess
import sys

import pytest

import bench
from scalable_agent_tpu.obs import rounds

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- salvage ----------------------------------------------------------------


class TestSalvage:
    def test_scalars_bools_strings(self):
        text = ('_auto": true}, "fps": 12.5, "count": 3, '
                '"name": "TPU v5 lite", "flag": false, "gone": null')
        metrics = rounds.salvage_metrics(text)
        assert metrics["fps"] == 12.5
        assert metrics["count"] == 3
        assert metrics["name"] == "TPU v5 lite"
        assert metrics["flag"] is False
        assert metrics["gone"] is None
        # The pair truncation cut mid-key lost its opening quote — it
        # is unrecoverable, and salvage must not hallucinate it.
        assert "_auto" not in metrics

    def test_curve_arrays_recovered_whole(self):
        text = ('"learning_curve": [[25, 7.41], [50, 8.38]], '
                '"replay_ratio_curve": [[0, 12.0, -1.5], [2, 11.0, -1.2]]')
        metrics = rounds.salvage_metrics(text)
        assert metrics["learning_curve"] == [[25, 7.41], [50, 8.38]]
        assert metrics["replay_ratio_curve"] == [
            [0, 12.0, -1.5], [2, 11.0, -1.2]]

    def test_wrapper_bookkeeping_keys_skipped(self):
        metrics = rounds.salvage_metrics('"rc": 0, "n": 5, "x": 1.0')
        assert "rc" not in metrics and "n" not in metrics
        assert metrics["x"] == 1.0

    def test_traceback_noise_yields_nothing(self):
        text = ('File "/opt/venv/lib/python3.12/site-packages/jax/'
                '_src/xla_bridge.py", line 908, in _init_backend\n'
                'RuntimeError: Unable to initialize backend')
        assert rounds.salvage_metrics(text) == {}


# -- parse kinds over the five-round history --------------------------------


class TestParseCommitted:
    def test_every_round_discovered_in_numeric_order(self, bench_history):
        found = rounds.discover_artifacts(bench_history)
        assert [number for number, _ in found] == [1, 2, 3, 4, 5]
        assert all(not path.endswith(rounds.SALVAGE_SUFFIX)
                   for _, path in found)

    def test_kinds_across_schema_drift(self, bench_history):
        kinds = {}
        for number, path in rounds.discover_artifacts(bench_history):
            kinds[number] = rounds.parse_bench_artifact(path).kind
        assert kinds[1] == "wrapper_failed"
        assert kinds[2] == "wrapper_parsed"
        assert kinds[4] == "wrapper_parsed"
        assert kinds[5] == "wrapper_salvaged"

    def test_r05_salvage_recovers_the_surviving_tail(self, bench_history):
        art = rounds.parse_bench_artifact(
            os.path.join(bench_history, "BENCH_r05.json"))
        assert art.salvaged
        assert art.sidecar is not None
        assert art.metrics["e2e_env_frames_per_sec"] == 8613.0
        assert art.metrics["kernel_conv0_gradw_us"] == 12964.61
        assert art.metrics["kernel_conv0_gradw_mfu"] == 0.107
        assert art.metrics["learning_final_return"] == 10.93
        assert art.metrics["learning_curve"][-1] == [150, 10.94]
        # The head of the line is LOST (truncation) — salvage must not
        # hallucinate it.
        assert "value" not in art.metrics
        assert "platform" not in art.metrics

    def test_newest_artifact_is_r05(self, bench_history):
        art = rounds.newest_artifact(bench_history)
        assert art.name == "BENCH_r05.json"
        assert art.metrics  # salvaged, not empty


# -- the trajectory ---------------------------------------------------------


class TestTrajectoryGolden:
    @pytest.fixture(scope="class")
    def trajectory(self, bench_history):
        return rounds.build_trajectory(bench_history)

    def test_all_rounds_present(self, trajectory):
        assert [r["round"] for r in trajectory["rounds"]] == [1, 2, 3, 4, 5]
        by_round = {r["round"]: r for r in trajectory["rounds"]}
        assert by_round[5]["salvaged"] and by_round[5]["has_sidecar"]
        assert not by_round[1]["has_metrics"]

    def test_e2e_headline_series(self, trajectory):
        series = trajectory["series"]
        assert series["e2e_env_frames_per_sec"][4] == 12648.4
        assert series["e2e_vs_baseline"][4] == 0.422
        assert series["e2e_env_frames_per_sec"][5] == 8613.0
        assert series["value"][4] == 2552779.7
        assert series["mfu"][4] == 0.1522
        assert series["ingraph_vs_baseline"][5] == 5.539

    def test_round_over_round_deltas(self, trajectory):
        deltas = trajectory["deltas"]["e2e_env_frames_per_sec"]
        # r03 -> r04 was the 6.4x host-pipeline jump; r05 regressed on
        # the degraded link.
        assert deltas[4] > 5.0
        assert deltas[5] < 0.0

    def test_conv0_gradw_worst_kernel_series(self, trajectory):
        assert trajectory["kernels"]["conv0_gradw"][5] == {
            "us": 12964.61, "mfu": 0.107}
        worst = trajectory["worst_kernel"][5]
        assert worst["name"] == "conv0_gradw"
        assert worst["mfu"] == 0.107
        # Variant readings (_s2d at 0.047) exist but must not claim
        # the verdict over the production path.
        assert "conv0_gradw_s2d" in trajectory["kernels"]

    def test_learning_curve_series(self, trajectory):
        curve = trajectory["learning_curves"][5]
        assert curve[0] == [25, 7.41]
        assert curve[-1] == [150, 10.94]

    def test_multichip_series(self, trajectory):
        latest = trajectory["multichip"][-1]
        assert latest["round"] == 5
        assert latest["n_devices"] == 8 and latest["ok"]
        assert latest["mesh"] == "data=2, seq=2, model=2"
        assert latest["total_loss"] == 6.3302

    def test_latest_scoreboard_every_target_unmet_or_unmeasured(
            self, trajectory):
        assert trajectory["latest_round"] == 5
        cells = trajectory["latest_scoreboard"]
        assert set(cells) == {t.name for t in rounds.R06_TARGETS}
        assert all(cell["status"] in ("unmet", "unmeasured")
                   for cell in cells.values())
        # r04 measured the MFU target; r05's headline was truncated
        # away so it reads unmeasured there.
        r04 = trajectory["scoreboard"][4]
        assert r04["learner_mfu"] == {
            "status": "unmet", "value": 0.1522, "threshold": 0.4}
        assert cells["learner_mfu"]["status"] == "unmeasured"

    def test_text_render_carries_the_headlines(self, trajectory):
        text = rounds.render_trajectory(trajectory)
        assert "12.6k" in text            # r04 e2e headline
        assert "conv0_gradw" in text
        assert "150:10.94" in text        # the learning curve tail
        assert "acceptance scoreboard" in text

    def test_report_cli_json_is_machine_readable(self, bench_history):
        proc = subprocess.run(
            [sys.executable, "-m", "scalable_agent_tpu.obs.rounds",
             "report", "--json", f"--bench_dir={bench_history}"],
            capture_output=True, text=True, timeout=60, cwd=REPO_ROOT)
        assert proc.returncode == 0, proc.stderr
        payload = json.loads(proc.stdout)
        assert payload["series"]["e2e_env_frames_per_sec"]["4"] == 12648.4
        assert payload["series"]["e2e_vs_baseline"]["4"] == 0.422
        assert payload["kernels"]["conv0_gradw"]["5"]["mfu"] == 0.107
        statuses = {name: cell["status"]
                    for name, cell in payload["latest_scoreboard"].items()}
        assert all(status in ("unmet", "unmeasured")
                   for status in statuses.values())


# -- the scoreboard ---------------------------------------------------------


class TestScoreboard:
    def test_met_unmet_unmeasured(self):
        scores = rounds.score_round({
            "service_vs_grouped": 2.5,      # met
            "ingraph_vs_baseline": 3.0,     # unmet (needs 10x)
            "replay_sampled_vs_fresh_fps": 0.97,  # met
        })
        assert scores["service_vs_grouped"]["status"] == "met"
        assert scores["device_resident_e2e"]["status"] == "unmet"
        assert scores["device_resident_e2e"]["value"] == 3.0
        assert scores["replay_sampled_fps"]["status"] == "met"
        assert scores["learner_mfu"]["status"] == "unmeasured"
        assert scores["dominant_stage_device_bound"]["status"] == (
            "unmeasured")

    def test_threshold_is_inclusive(self):
        scores = rounds.score_round({"mfu": 0.40})
        assert scores["learner_mfu"]["status"] == "met"

    def test_verdict_equality_target(self):
        met = rounds.score_round(
            {"dominant_stage_verdict": "device_bound"})
        assert met["dominant_stage_device_bound"]["status"] == "met"
        unmet = rounds.score_round(
            {"dominant_stage_verdict": "learner_starved"})
        assert unmet["dominant_stage_device_bound"]["status"] == "unmet"

    def test_non_numeric_values_read_unmeasured(self):
        scores = rounds.score_round({"mfu": True,
                                     "service_vs_grouped": "fast"})
        assert scores["learner_mfu"]["status"] == "unmeasured"
        assert scores["service_vs_grouped"]["status"] == "unmeasured"

    def test_empty_round_all_unmeasured(self):
        scores = rounds.score_round(None)
        assert all(cell["status"] == "unmeasured"
                   for cell in scores.values())


# -- validate ---------------------------------------------------------------


def _truncated_wrapper(**overrides):
    wrapper = {
        "n": 9,
        "cmd": "python bench.py",
        "rc": 0,
        "tail": ('_head_lost": 1.2}, "a_key": 1.0, "b_key": 2.5, '
                 '"c_key": 3.0, "verdict": "degraded"'),
        "parsed": None,
    }
    wrapper.update(overrides)
    return wrapper


class TestValidate:
    def test_committed_artifacts_pass(self, bench_history):
        """The tripwire (ISSUE 14 satellite): every artifact of the
        five-round history validates — r05 only because its salvage
        sidecar is present and still matches a fresh salvage."""
        result = rounds.validate_artifacts(bench_history)
        assert result["ok"], result["errors"]
        statuses = {entry["name"]: entry["status"]
                    for entry in result["artifacts"]}
        assert statuses["BENCH_r01.json"] == "failed_round"
        assert statuses["BENCH_r04.json"] == "ok"
        assert statuses["BENCH_r05.json"] == "salvaged"
        assert statuses["MULTICHIP_r05.json"] == "ok"

    def test_truncated_without_sidecar_fails(self, tmp_path):
        (tmp_path / "BENCH_r07.json").write_text(
            json.dumps(_truncated_wrapper()))
        result = rounds.validate_artifacts(str(tmp_path))
        assert not result["ok"]
        assert any("TRUNCATED" in error for error in result["errors"])
        assert result["artifacts"][0]["status"] == "truncated"

    def test_write_salvage_then_passes(self, tmp_path):
        (tmp_path / "BENCH_r07.json").write_text(
            json.dumps(_truncated_wrapper()))
        first = rounds.validate_artifacts(str(tmp_path),
                                          write_salvage=True)
        assert first["ok"]
        sidecar = json.loads(
            (tmp_path / "BENCH_r07.salvage.json").read_text())
        assert sidecar["salvaged_from"] == "BENCH_r07.json"
        assert sidecar["metrics"]["a_key"] == 1.0
        assert "note" in sidecar
        second = rounds.validate_artifacts(str(tmp_path))
        assert second["ok"], second["errors"]
        assert second["artifacts"][0]["status"] == "salvaged"

    def test_stale_sidecar_fails(self, tmp_path):
        (tmp_path / "BENCH_r07.json").write_text(
            json.dumps(_truncated_wrapper()))
        rounds.write_salvage_sidecar(
            str(tmp_path / "BENCH_r07.json"), {"a_key": 999.0})
        result = rounds.validate_artifacts(str(tmp_path))
        assert not result["ok"]
        assert any("STALE" in error for error in result["errors"])

    def test_bench_line_missing_required_keys_is_violation(
            self, tmp_path):
        (tmp_path / "BENCH_r07.json").write_text(
            json.dumps({"metric": "m", "value": 1.0}))
        result = rounds.validate_artifacts(str(tmp_path))
        assert not result["ok"]
        assert any("required keys" in error
                   for error in result["errors"])

    def test_unreadable_json_is_invalid(self, tmp_path):
        (tmp_path / "BENCH_r07.json").write_text('{"n": 5, "tail": "tr')
        result = rounds.validate_artifacts(str(tmp_path))
        assert not result["ok"]
        assert result["artifacts"][0]["status"] == "invalid"

    def test_multichip_missing_keys_flagged(self, tmp_path):
        (tmp_path / "MULTICHIP_r01.json").write_text(
            json.dumps({"tail": "dryrun"}))
        result = rounds.validate_artifacts(str(tmp_path))
        assert not result["ok"]
        assert any("MULTICHIP_r01" in error
                   for error in result["errors"])

    def test_cli_exit_codes(self, tmp_path, bench_history):
        (tmp_path / "BENCH_r07.json").write_text(
            json.dumps(_truncated_wrapper()))
        proc = subprocess.run(
            [sys.executable, "-m", "scalable_agent_tpu.obs.rounds",
             "validate", f"--bench_dir={tmp_path}"],
            capture_output=True, text=True, timeout=60, cwd=REPO_ROOT)
        assert proc.returncode == 1
        proc = subprocess.run(
            [sys.executable, "-m", "scalable_agent_tpu.obs.rounds",
             "validate", f"--bench_dir={bench_history}"],
            capture_output=True, text=True, timeout=60, cwd=REPO_ROOT)
        assert proc.returncode == 0, proc.stdout + proc.stderr


# -- the round runner -------------------------------------------------------

# A stub bench implementing the orchestrator's contract (--list
# --json, --suites/--context/--json_out): alpha emits a metric, beta
# HARD-crashes before emitting anything, gamma hangs past its timeout,
# delta proves the cross-suite context hand-off, guards emits a
# summary.
STUB_BENCH = r'''
import argparse, json, os, sys, time

SUITES = [
    {"name": "alpha", "timeout_s": 30, "description": "emits alpha_key"},
    {"name": "beta", "timeout_s": 30, "description": "crashes hard"},
    {"name": "gamma", "timeout_s": 2, "description": "hangs"},
    {"name": "delta", "timeout_s": 30, "description": "reads context"},
]

parser = argparse.ArgumentParser()
parser.add_argument("--list", action="store_true")
parser.add_argument("--json", action="store_true")
parser.add_argument("--suites", default=None)
parser.add_argument("--context", default=None)
parser.add_argument("--json_out", default=None)
parser.add_argument("--crash", default=None)
parser.add_argument("--crash_hard", default=None)
parser.add_argument("--bench_dir", default=None)
parser.add_argument("--guard_exclude", default=None)
args = parser.parse_args()
if args.list:
    print(json.dumps({"suites": SUITES, "guards": [
        {"name": "stub_guard", "policy": "binding",
         "description": "stub"}], "policies": {}}))
    sys.exit(0)
name = args.suites
ctx = json.load(open(args.context)) if args.context else {}
out = {"metric": "m", "value": 1.0, "unit": "u", "vs_baseline": 0.1,
       "errors": [], "stage": "done", "platform": "cpu",
       "device_kind": "cpu", "n_devices": 1, "jax_version": "0"}
if name == "alpha":
    out["alpha_key"] = float(os.environ.get("STUB_ALPHA", "1.5"))
if name == "beta":
    sys.exit(3)
if name == "gamma":
    time.sleep(30)
if name == "delta":
    out["delta_saw_alpha"] = ctx.get("alpha_key")
if name == "guards":
    breached = bool(os.environ.get("STUB_GUARD_ERRORS"))
    if breached:
        out["errors"] = ["REGRESSION: synthetic guard breach"]
    out["guard_summary"] = {"stub_guard": {
        "status": "failed" if breached else "ok", "policy": "binding",
        "errors": int(breached), "warnings": 0}}
    out["guards_saw_bench_dir"] = args.bench_dir
    out["guards_saw_exclude"] = args.guard_exclude
line = json.dumps(out)
if args.json_out:
    open(args.json_out, "w").write(line)
print(line)
'''


def _stub_cmd(tmp_path):
    path = tmp_path / "stub_bench.py"
    path.write_text(STUB_BENCH)
    return [sys.executable, str(path)]


def _quiet(message):
    pass


class TestRunRound:
    def test_stage_isolation(self, tmp_path):
        """The acceptance shape: one hard-crashed suite and one hung
        suite still leave a schema-valid artifact with every other
        suite's numbers present and the failures named."""
        outcome = rounds.run_round(
            bench_dir=str(tmp_path), bench_cmd=_stub_cmd(tmp_path),
            log=_quiet)
        assert not outcome["ok"]
        assert outcome["path"].endswith("BENCH_r01.json")
        artifact = outcome["artifact"]
        stages = artifact["stages"]
        assert stages["alpha"]["status"] == "ok"
        assert stages["alpha"]["data"]["alpha_key"] == 1.5
        assert stages["beta"]["status"] == "failed"
        assert stages["beta"]["rc"] == 3
        assert stages["gamma"]["status"] == "timeout"
        # Cross-suite context hand-off: delta ran AFTER alpha in its
        # own process and still saw alpha's metric.
        assert stages["delta"]["data"]["delta_saw_alpha"] == 1.5
        assert stages["guards"]["status"] == "ok"
        assert artifact["guard_summary"]["stub_guard"]["status"] == "ok"
        merged = artifact["merged"]
        assert merged["alpha_key"] == 1.5
        assert any("beta" in error for error in merged["errors"])
        assert any("gamma" in error for error in merged["errors"])
        # The artifact on disk is schema-valid despite the crash+hang.
        result = rounds.validate_artifacts(str(tmp_path))
        assert result["ok"], result["errors"]
        assert artifact["fingerprint"]["platform"] == "cpu"

    def test_subset_rerun_merges_onto_newest_artifact(self, tmp_path,
                                                      monkeypatch):
        cmd = _stub_cmd(tmp_path)
        first = rounds.run_round(
            bench_dir=str(tmp_path), bench_cmd=cmd,
            suites=["alpha", "delta", "guards"], log=_quiet)
        assert first["ok"]
        monkeypatch.setenv("STUB_ALPHA", "7.5")
        second = rounds.run_round(
            bench_dir=str(tmp_path), bench_cmd=cmd, suites=["alpha"],
            log=_quiet)
        assert second["path"] == first["path"]
        artifact = second["artifact"]
        assert artifact["round"] == first["artifact"]["round"]
        assert artifact["stages"]["alpha"]["data"]["alpha_key"] == 7.5
        assert artifact["merged"]["alpha_key"] == 7.5
        # delta's stage record (and its metric) survive the re-run.
        assert artifact["stages"]["delta"]["status"] == "ok"
        assert artifact["merged"]["delta_saw_alpha"] == 1.5
        assert artifact["guard_summary"] is not None

    def test_guard_breach_fails_the_round(self, tmp_path,
                                          monkeypatch):
        """A binding guard error must fail the guards stage (and the
        round), even though the guards subprocess exits rc=0."""
        monkeypatch.setenv("STUB_GUARD_ERRORS", "1")
        outcome = rounds.run_round(
            bench_dir=str(tmp_path), bench_cmd=_stub_cmd(tmp_path),
            suites=["alpha", "guards"], log=_quiet)
        assert not outcome["ok"]
        record = outcome["artifact"]["stages"]["guards"]
        assert record["status"] == "failed"
        assert "guard error" in record["error"]
        assert outcome["artifact"]["guard_summary"]["stub_guard"][
            "status"] == "failed"

    def test_guards_compare_against_round_dir_minus_self(
            self, tmp_path):
        """The orchestrator points the guards at --bench_dir and
        excludes the artifact being written, so a subset re-run grades
        against the PREVIOUS round instead of itself."""
        outcome = rounds.run_round(
            bench_dir=str(tmp_path), bench_cmd=_stub_cmd(tmp_path),
            suites=["alpha", "guards"], log=_quiet)
        merged = outcome["artifact"]["merged"]
        assert merged["guards_saw_bench_dir"] == str(tmp_path)
        assert merged["guards_saw_exclude"] == "BENCH_r01.json"
        # And on the merge re-run, the exclusion still names the
        # artifact on disk being merged onto.
        second = rounds.run_round(
            bench_dir=str(tmp_path), bench_cmd=_stub_cmd(tmp_path),
            suites=["guards"], log=_quiet)
        assert second["artifact"]["merged"]["guards_saw_exclude"] == (
            "BENCH_r01.json")

    def test_unknown_suite_rejected(self, tmp_path):
        with pytest.raises(ValueError, match="unknown suites"):
            rounds.run_round(bench_dir=str(tmp_path),
                             bench_cmd=_stub_cmd(tmp_path),
                             suites=["nope"], log=_quiet)

    def test_round_numbering_continues_the_committed_series(
            self, tmp_path):
        (tmp_path / "BENCH_r04.json").write_text(
            json.dumps({"metric": "m", "value": 1.0, "unit": "u",
                        "vs_baseline": 0.1}))
        outcome = rounds.run_round(
            bench_dir=str(tmp_path), bench_cmd=_stub_cmd(tmp_path),
            suites=["alpha"], log=_quiet)
        # Newest artifact is not schema-v1, so a fresh round starts at
        # the next number instead of merging into an alien format.
        assert outcome["path"].endswith("BENCH_r05.json")
        assert outcome["artifact"]["round"] == 5

    def test_latest_bench_artifact_reads_round_v1(self, tmp_path):
        rounds.run_round(bench_dir=str(tmp_path),
                         bench_cmd=_stub_cmd(tmp_path),
                         suites=["alpha", "guards"], log=_quiet)
        diag = {"errors": []}
        prev, name = bench._latest_bench_artifact(
            diag, bench_dir=str(tmp_path))
        assert name == "BENCH_r01.json"
        assert prev["alpha_key"] == 1.5
        assert prev["platform"] == "cpu"
        assert diag["errors"] == []


# -- bench.py CLI surface ---------------------------------------------------


class TestBenchCLI:
    def test_list_json_registry(self, capsys):
        assert bench.main(["--list", "--json"]) == 0
        payload = json.loads(
            capsys.readouterr().out.strip().splitlines()[-1])
        names = [suite["name"] for suite in payload["suites"]]
        assert names == [spec.name for spec in bench.SUITE_REGISTRY]
        assert len(payload["guards"]) == len(bench.GUARD_REGISTRY)
        assert set(payload["policies"]) == set(bench.GUARD_POLICIES)

    def test_list_text_names_every_suite_and_guard(self, capsys):
        assert bench.main(["--list"]) == 0
        text = capsys.readouterr().out
        for spec in bench.SUITE_REGISTRY:
            assert spec.name in text
        for spec in bench.GUARD_REGISTRY:
            assert spec.name in text

    def test_unknown_suite_exits_2(self, capsys):
        assert bench.main(["--suites=definitely_not_a_suite"]) == 2

    def test_crash_injection_is_stage_isolated(self, tmp_path,
                                               monkeypatch, capsys):
        """--crash=<suite> poisons exactly that suite: its failure is
        recorded, the sibling suite's numbers land, the JSON-line
        contract (stdout + --json_out) holds — and the exit code is
        non-zero, because ``errors`` is not empty."""
        # The chip requirement is the one thing stubbed: these two
        # suites are jax-free, and bench.py refuses to start without a
        # TPU (tests/test_chip_bringup.py pins that).
        monkeypatch.setattr(bench, "_require_chip",
                            lambda: ("cpu", "cpu", 1))
        context = tmp_path / "ctx.json"
        context.write_text('{"sec_per_update": 0.005}')
        json_out = tmp_path / "out.json"
        rc = bench.main([
            "--suites=bench_obs,bench_ledger", "--crash=bench_obs",
            f"--context={context}", f"--json_out={json_out}"])
        assert rc == 1
        emitted = json.loads(json_out.read_text())
        assert any("bench_obs failed" in error
                   and "injected crash" in error
                   for error in emitted["errors"])
        # The crashed suite's keys are absent; the sibling's landed.
        assert "obs_span_enabled_us" not in emitted
        assert emitted["ledger_stamp_us"] is not None
        assert emitted["ledger_overhead_frac_on_update"] > 0.0
        # stdout carried the same line (the historical contract).
        stdout_line = [line for line in
                       capsys.readouterr().out.splitlines()
                       if line.startswith("{")][-1]
        assert json.loads(stdout_line) == emitted
