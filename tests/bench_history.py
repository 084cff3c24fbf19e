"""A synthetic five-round bench history for the artifact-reading tests.

The repo once committed ``BENCH_r01``-``r05.json`` /
``MULTICHIP_r01``-``r05.json`` and the tests read them from the repo
root.  Those records are gone (ROADMAP keeps the numbers worth
keeping); ``write_history`` rebuilds the same SHAPES into a directory a
test owns — the four schema kinds the parsers must keep reading:

- r01: a driver wrapper around a FAILED run (rc=1, traceback tail,
  ``parsed: null``);
- r02-r04: wrappers whose tail's JSON line parsed (``parsed`` filled) —
  r02 with the bogus 298%-MFU headline, r03 a CPU-platform round, r04
  the one complete TPU round;
- r05: a wrapper whose JSON line lost its HEAD to truncation
  (``parsed: null``, regex-salvageable tail) plus its salvage sidecar,
  regenerated here by the production salvage path;
- MULTICHIP_r01-r05: the virtual-device dry-run records.
"""

import json
import os

from scalable_agent_tpu.obs import rounds

_CMD = "if [ -f bench.py ]; then python bench.py; else exit 0; fi"
_HEAD = {"metric": "learner_env_frames_per_sec_per_chip",
         "unit": "env_frames/s", "stage": "done", "n_devices": 1,
         "jax_version": "0.9.0"}
_TPU = {"platform": "tpu", "device_kind": "TPU v5 lite"}

R01_TAIL = (
    '  File "/opt/venv/lib/python3.12/site-packages/jax/_src/'
    'xla_bridge.py", line 840, in backends\n'
    "    raise RuntimeError(err_msg)\n"
    "RuntimeError: Unable to initialize backend 'tpu': UNAVAILABLE: "
    "TPU backend setup/compile error (Unavailable). (set "
    "JAX_PLATFORMS='' to automatically choose an available backend)\n")

R02 = dict(
    _HEAD, **_TPU, value=49961975.6, vs_baseline=1665.399, errors=[],
    compile_s=4.56, flops_per_update=150395863040.0,
    sec_per_update=0.0003, bench_iters=30, mfu=2.9799,
    model_tflops_per_s=587.04, e2e_env_frames_per_sec=804.2,
    e2e_updates_measured=5)

R03 = dict(
    _HEAD, platform="cpu", device_kind="cpu", value=2143.5,
    vs_baseline=0.071,
    errors=["tpu backend unavailable: backend init hung >120s "
            "(attempt 2/2)",
            "learner bench ran only 2 iters (backend too slow for the "
            "30-iter statistical floor inside the watchdog budget)"],
    link_rtt_ms=0.17, link_h2d_flat_mb_s=1767.0, compile_s=5.41,
    flops_per_update=152971444224.0, sec_per_update=5.971619,
    bench_iters=2,
    e2e_config={"groups": 2, "group_size": 16, "unroll_length": 100,
                "action_repeats": 4, "inference_mode": "accum"},
    e2e_env_frames_per_sec=1987.4, e2e_updates_measured=6,
    e2e_vs_baseline=0.066, ingraph_env_frames_per_sec=1910.4,
    ingraph_updates_measured=3, ingraph_vs_baseline=0.064,
    ingraph_final_loss=87846.828)

R04 = dict(
    _HEAD, **_TPU, value=2552779.7, vs_baseline=85.093, errors=[],
    link_rtt_ms=66.68, link_h2d_flat_mb_s=89.0, compile_s=5.05,
    flops_per_update=150292791296.0, sec_per_update=0.005014,
    bench_iters=300, mfu=0.1522, model_tflops_per_s=29.97,
    e2e_config={"groups": 5, "group_size": 256, "unroll_length": 100,
                "action_repeats": 4, "inference_mode": "accum_fused",
                "fused_shards": 2},
    e2e_env_frames_per_sec=12648.4, e2e_updates_measured=30,
    e2e_vs_baseline=0.422, ingraph_core_matmul_dtype="float32",
    ingraph_env_frames_per_sec=166605.8, ingraph_updates_measured=131,
    ingraph_vs_baseline=5.554, ingraph_final_loss=96087.43,
    kernel_vtrace_associative_us=2.8, kernel_vtrace_pallas_us=6.8,
    kernel_lstm_grad_xla_us=307.5, kernel_lstm_grad_pallas_us=183.6,
    kernel_lstm_grad_pallas_bf16_us=185.8,
    kernel_lstm_grad_xla_b256_us=1822.7,
    kernel_lstm_grad_pallas_b256_us=808.1,
    kernel_lstm_grad_pallas_bf16_b256_us=803.9,
    roofline_forward_unroll_us=1825.5, roofline_loss_forward_us=1921.4,
    roofline_loss_grad_us=4247.2, roofline_optimizer_us=74.4,
    roofline_lstm_flops=10262937600.0, roofline_lstm_flops_frac=0.0683,
    learner_b256_compile_s=6.98, learner_b256_sec_per_update=0.037193,
    learner_b256_iters=100,
    learner_b256_flops_per_update=1202216501248.0,
    learner_b256_mfu=0.1641, learner_b256_env_frames_per_sec=2753172.4)

# r05's line as bench.py printed it; the driver kept only its tail, so
# everything up to and including the ``fused_shards_auto`` key's name is
# cut off below.
R05 = dict(
    _HEAD, **_TPU, value=2694531.2, vs_baseline=89.818, errors=[],
    link_rtt_ms=70.2, link_h2d_flat_mb_s=143.0,
    e2e_config={"groups": 5, "group_size": 256, "fused_shards": 2,
                "fused_shards_auto": True},
    e2e_env_frames_per_sec=8613.0, e2e_updates_measured=30,
    e2e_vs_baseline=0.287, ingraph_core_matmul_dtype="float32",
    ingraph_env_frames_per_sec=166168.3, ingraph_updates_measured=130,
    ingraph_vs_baseline=5.539, ingraph_final_loss=42161.23,
    ingraph_final_loss_per_step=13.175,
    learning_curve=[[25, 7.41], [50, 8.38], [75, 10.47], [100, 10.19],
                    [125, 10.91], [150, 10.94]],
    learning_random_return=4.0, learning_optimal_return=16.0,
    learning_final_return=10.93, learning_improved=True,
    kernel_vtrace_associative_us=5.07, kernel_vtrace_pallas_us=14.78,
    kernel_lstm_grad_xla_us=303.55, kernel_lstm_grad_pallas_us=186.11,
    kernel_lstm_grad_pallas_bf16_us=181.5,
    kernel_lstm_grad_xla_b256_us=1830.6,
    kernel_lstm_grad_pallas_b256_us=811.76,
    kernel_lstm_grad_pallas_bf16_b256_us=816.35,
    kernel_conv0_gradw_us=12964.61, kernel_conv0_gradw_mfu=0.107,
    kernel_conv1_gradxw_us=5554.05, kernel_conv1_gradxw_mfu=0.502,
    kernel_conv2_gradxw_us=2266.27, kernel_conv2_gradxw_mfu=0.769,
    kernel_conv0_gradw_s2d_us=29807.69, kernel_conv0_gradw_s2d_mfu=0.047,
    roofline_forward_unroll_us=1840.4, roofline_loss_forward_us=1861.91,
    roofline_loss_grad_us=4202.72, roofline_optimizer_us=8.3,
    roofline_optimizer_us_note=(
        "below timer resolution (~8.30 us window spread); reported as "
        "the floor, not a measurement"),
    roofline_lstm_flops=10262937600.0, roofline_lstm_flops_frac=0.0683,
    learner_b256_mfu=0.1641, learner_b256_env_frames_per_sec=2754378.6,
    e2e_link_probes=[{"at_s": 1060.0, "h2d_mb_s": 143.0},
                     {"at_s": 1121.0, "h2d_mb_s": 150.0}],
    e2e_retry_verdict=(
        "no probe reached 300 MB/s before the watchdog budget; e2e "
        "number stands as a degraded-link measurement"),
    regression_reference="BENCH_r04.json")
_R05_CUT = '_auto": true}'

MULTICHIP_TAILS = (
    "dryrun_multichip(8): OK, total_loss=46.1869\n",
    "dryrun_multichip(8): OK, total_loss=46.1869\n",
    "dryrun_multichip(8): OK over mesh (data=4, model=2), "
    "total_loss=6.3302\n",
    "dryrun_multichip(8): OK over mesh (data=2, seq=2, model=2), "
    "total_loss=6.3302\n",
    "dryrun_multichip(8): OK over mesh (data=2, seq=2, model=2), "
    "total_loss=6.3302\n",
)


def _wrapper(n, rc, tail, parsed):
    return {"n": n, "cmd": _CMD, "rc": rc, "tail": tail,
            "parsed": parsed}


def write_history(bench_dir) -> str:
    """Write the five-round history into ``bench_dir``; returns it."""
    bench_dir = str(bench_dir)
    os.makedirs(bench_dir, exist_ok=True)

    def dump(name, payload):
        with open(os.path.join(bench_dir, name), "w") as f:
            json.dump(payload, f, indent=1)

    dump("BENCH_r01.json", _wrapper(1, 1, R01_TAIL, None))
    for n, parsed in ((2, R02), (3, R03), (4, R04)):
        line = json.dumps(parsed)
        dump(f"BENCH_r{n:02d}.json",
             _wrapper(n, 0, "some log line before the result\n" + line
                      + "\n", parsed))
    line = json.dumps(R05)
    tail = line[line.index(_R05_CUT):] + "\n"
    dump("BENCH_r05.json", _wrapper(5, 0, tail, None))
    r05_path = os.path.join(bench_dir, "BENCH_r05.json")
    rounds.write_salvage_sidecar(
        r05_path, rounds.parse_bench_artifact(r05_path).metrics)
    for n, tail in enumerate(MULTICHIP_TAILS, start=1):
        dump(f"MULTICHIP_r{n:02d}.json",
             {"n_devices": 8, "rc": 0, "ok": True, "skipped": False,
              "tail": tail})
    return bench_dir
