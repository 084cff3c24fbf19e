"""One cell, once: ``python3 benchmark/run.py --workload <name> --seed <n>
--seconds <s> --trace <0|1>``.

A fresh process that holds the cell's chips: it fails without a TPU,
drives ``scalable_agent_tpu.driver.main`` (the users' entry point) with
the flags the cell's configuration and traffic files hold, warms up,
measures for ``--seconds``, stops every env worker, checks the timed
object's first steps against the plain reference, and prints ONE JSON
object as the last line of stdout.  ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics (from the
program's spans, its counters and a profiler trace of the window's
last seconds).

``--rehearse 1`` is the sandbox's dry run: tiny sizes on the CPU (4
virtual devices for a four-chip cell); it prints NO metric and its last
line says ``"platform": "cpu"``.  ``--control 1`` also computes the
control (the reference at fp8) beside the program's numbers.
"""

import time

T_LAUNCH = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TRACE_SECONDS = 4.0    # the profiler records the window's last seconds

# The TPU runtime pins a host buffer for transfers when it starts.  On a
# machine without transparent hugepages (the check's: JAX warns of it in
# every run) the default buffer took 9.4-12.3 s of ``jax.devices()`` in
# one call's runs and 21.4 s in its first, 1.6-2.4 s at this size (my
# chip runs, PR 31): a quarter of set-up's seconds and most of its spread,
# none of it the program's.  The cells move a few MB between host and
# device (metrics, the three checked steps' parameters).  A value the
# environment brings is kept.
TPU_PREMAPPED_BUFFER_BYTES = 256 * 2 ** 20


def say(*parts):
    print(*parts, flush=True)


def parse_args(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, default=0, choices=(0, 1))
    parser.add_argument("--rehearse", type=int, default=0, choices=(0, 1))
    parser.add_argument("--control", type=int, default=0, choices=(0, 1))
    parser.add_argument("--dump_trace", type=int, default=0,
                        choices=(0, 1))
    return parser.parse_args(argv)


def load_spans(logdir, t0, t1):
    """The program's own spans (its ``--trace`` file) inside [t0, t1],
    on the ``perf_counter`` clock the tracer stamps them with."""
    import glob

    from scalable_agent_tpu.obs.trace import load_trace_events

    spans = []
    for path in glob.glob(os.path.join(logdir, "trace.p*.json")):
        for event in load_trace_events(path):
            if event.get("ph") != "X":
                continue
            start = event["ts"] * 1e-6
            if t0 <= start and start + event["dur"] * 1e-6 <= t1:
                spans.append((event["name"], start, event["dur"] * 1e-6))
    return spans


def main(argv=None) -> int:
    args = parse_args(argv)
    from benchmark.lib import manifest

    cell = manifest.load_cell(args.workload)
    flags = manifest.driver_flags(cell, rehearse=bool(args.rehearse))
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell.chips}")
    os.environ.setdefault("TPU_PREMAPPED_BUFFER_SIZE",
                          str(TPU_PREMAPPED_BUFFER_BYTES))

    import jax

    from benchmark.lib import peaks

    platform = jax.default_backend()
    if not args.rehearse and (platform != "tpu"
                              or len(jax.devices()) != cell.chips):
        print(f"benchmark: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX gives {len(jax.devices())} x {platform!r} "
              f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '')!r}). "
              f"There is no CPU path; --rehearse 1 is the dry run.",
              file=sys.stderr)
        return 2
    peak = None if args.rehearse else peaks.for_kind(
        jax.devices()[0].device_kind)

    from scalable_agent_tpu import driver  # noqa: F401  (set-up pays it)

    t_imported = time.perf_counter() - T_LAUNCH
    logdir = tempfile.mkdtemp(prefix="benchmark_run_")
    try:
        return run_cell(args, cell, flags, logdir, peak, t_imported)
    finally:
        shutil.rmtree(logdir, ignore_errors=True)


def run_cell(args, cell, flags, logdir, peak, t_imported) -> int:
    """Everything after the look for a chip: drive the program, measure,
    check, report.  ``logdir`` is the caller's to remove."""
    import jax

    from benchmark.lib import correct, manifest, probe as probe_lib, window
    from scalable_agent_tpu import driver

    backend = cell.traffic["backend"]
    reference = manifest.reference_module(cell)
    trace_dir = os.path.join(logdir, "profile")
    # The program's own seed (its worlds' seeds and its sampling keys) is
    # the same in every run: the fused step bakes it into the compiled
    # program as a constant, so a new seed is a new program and a 33 s
    # compile in every run of every check (my chip runs, PR 23: set-up
    # 73 s on a seed the cache had not seen, 40 s on one it had).
    # ``--seed`` makes the weights, and through them the actions and
    # the trajectories; the work is the same for every seed.
    program_seed = 1
    frames_per_update = float(flags["batch_size"] * flags["unroll_length"]
                              * flags["num_action_repeats"])
    probe = probe_lib.Probe(
        config=cell.config, backend=backend, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace),
        trace_seconds=TRACE_SECONDS, trace_dir=trace_dir,
        t_launch=T_LAUNCH, reference=reference)
    argv_driver = manifest.flags_to_argv(flags) + [
        "--mode=train", f"--logdir={logdir}", f"--seed={program_seed}",
        f"--trace={'true' if args.trace else 'false'}"]
    say("benchmark:", args.workload, "driver flags:", " ".join(argv_driver))
    probe.install()
    final = {}
    try:
        final = driver.main(argv_driver) or {}
    finally:
        probe.uninstall()
        leftover = probe_lib.stop_children()
    device = probe_lib.device_facts()   # before the reference runs

    retires = probe.clock.retires
    host = backend == "host"
    checks = []    # (name, value, limit, ok)

    def check(name, value, limit, ok):
        checks.append((name, value, limit, bool(ok)))

    updates = max(0, len(retires) - 1)
    losses = [float(x) for x in jax.device_get(probe.window_losses)]
    skips = [float(x) for x in jax.device_get(probe.window_skips)]
    failed = sum(1 for x in losses if not (x == x and abs(x) < 1e30)) \
        + int(sum(skips))
    check("window_updates_min", updates, 2, updates >= 2)
    check("nonfinite_or_skipped_updates", failed, 0, failed == 0)
    frames_ran = probe.dispatched * frames_per_update
    check("env_frames_minus_updates_x_frames_per_update",
          final.get("env_frames", -1.0) - frames_ran, 0,
          final.get("env_frames") == frames_ran)
    opened, closed = probe.counters_open, probe.counters_close
    inside = {key: closed.get(key, 0) - opened.get(key, 0)
              for key in ("compiles", "worker_respawns", "actor_restarts",
                          "health_windows", "nonfinite_skips")}
    for key, value in inside.items():
        check(f"{key}_inside_window", value, 0,
              value == 0 and bool(closed))
    check("leftover_processes", len(leftover), 0, not leftover)
    check("weights_from_seed", int(probe.weights_replaced), 1,
          probe.weights_replaced)
    if not args.rehearse:
        check("param_devices", probe.param_devices, cell.chips,
              probe.param_devices == cell.chips)

    # -- end-to-end numbers (host clock) -------------------------------------
    # The rate is taken over all the work and all the time of the
    # window, first retire to last: a stall inside it shows.  The block
    # rates are printed beside it and kept in the run's file.
    rate = window.window_rate(retires, frames_per_update)
    blocks = window.block_rates(retires, frames_per_update)
    setup_s = (probe.t_open - T_LAUNCH) if probe.t_open else None
    values = {
        "setup_s": setup_s,
        cell.traffic["rate_metric"]: rate,
        "peak_hbm_gib": device["memory_peak_bytes"] / 2.0 ** 30,
    }

    # -- the first steps against the reference (outside the window) ---------
    numbers, control_numbers, ref_seconds = {}, {}, None
    complete = (len(probe.check_losses) == correct.STEPS
                and probe.check_nu1 is not None
                and probe.check_params is not None)
    check("first_steps_recorded", int(complete), 1, complete)
    if complete:
        t0 = time.perf_counter()
        program = correct.program_numbers(
            cell.config, args.seed, probe.param_paths,
            jax.device_get(probe.check_losses), probe.check_nu1,
            probe.check_params, reference=reference)
        fused = None if host else {
            "world": cell.traffic["world"],
            "batch": int(flags["batch_size"]),
            "unroll_length": int(flags["unroll_length"]),
            "program_seed": program_seed}
        follow = dict(frames_per_update=frames_per_update,
                      batches=probe.check_batches if host else None,
                      fused=fused, reference=reference)
        ref = correct.follow(cell.config, args.seed, **follow)
        numbers = correct.compare(program, ref)
        ref_seconds = time.perf_counter() - t0
        for row in correct.judge(numbers, cell.limits):
            checks.append(row)
        say("losses program", program["losses"], "reference",
            ref["losses"])
        if args.control:
            control = correct.follow(cell.config, args.seed, quant="fp8",
                                     **follow)
            control_numbers = correct.compare(control, ref)
            say("control fp8:", json.dumps(control_numbers))

    # -- per-layer numbers (traced run) ---------------------------------------
    metrics = {}
    trace_summary = {}
    if args.trace:
        from benchmark.lib import readers, trace_reduce

        events = []
        xplane = trace_reduce.find_xplane(trace_dir)
        if xplane:
            t0 = time.perf_counter()
            events = trace_reduce.load_xplane(xplane)
            say(f"trace: {len(events)} events read in "
                f"{time.perf_counter() - t0:.1f}s")
        ctx = types.SimpleNamespace(
            config=cell.config, reference=reference, flags=flags,
            chips=cell.chips, traffic=cell.traffic, retires=retires,
            rate=rate,
            frames_per_update=frames_per_update,
            t_launch=T_LAUNCH, t_open=probe.t_open, t_close=probe.t_close,
            t_first_update=probe.t_first_update,
            compile_s_before_window=opened.get("compile_s"),
            spans=load_spans(logdir, *_span_window(probe)),
            events=events, peak=peak,
            ledger_ring=_ledger_ring(logdir), notes=[])
        busy_s, window_s = trace_reduce.busy_and_window(events)
        device["busy_s"], device["window_s"] = busy_s, window_s
        refused = report_per_layer(cell.per_layer, ctx, metrics)
        if refused:
            return refused
        trace_summary = {
            "breakdown": trace_reduce.breakdown(events),
            "notes": ctx.notes,
            "custom_calls": readers.custom_call_table(ctx),
        }
        for note in ctx.notes:
            say("note:", note)
        if args.dump_trace:
            _dump_events(args.workload, events)
        if not args.rehearse:
            check("device_busy_s_min", busy_s, 0.0, busy_s > 0)
    else:
        for metric in cell.end_to_end:
            value = values.get(metric.name)
            if value is not None:
                metrics[metric.name] = {"value": value,
                                        "unit": metric.entry["unit"]}
        check("end_to_end_reported", len(metrics), len(cell.end_to_end),
              len(metrics) == len(cell.end_to_end))

    ok = all(row[3] for row in checks)
    for name, value, limit, passed in checks:
        say(f"check {name}: value={value} limit={limit} "
            f"{'ok' if passed else 'FAILED'}")
    say(f"window: updates={updates} discarded_before={probe.clock.discarded} "
        f"backlog_drained={probe.clock.drained} whole_window_rate="
        f"{rate} block_rates={blocks}")
    marks = dict(probe.marks, imported=t_imported, window_open=setup_s)
    say("setup marks (s since launch):", json.dumps(marks))
    say(f"device: platform={device['platform']} device_kind={device['kind']} "
        f"count={device['count']} policy={probe.policy}")

    record = {
        "workload": args.workload, "seed": args.seed,
        "program_seed": program_seed, "seconds": args.seconds,
        "trace": args.trace, "rehearse": args.rehearse,
        "device": device, "kernel_policy": probe.policy,
        "values": values, "whole_window_rate": rate,
        "block_rates": blocks, "updates_in_window": updates,
        "updates_discarded": probe.clock.discarded,
        "backlog_drained": probe.clock.drained,
        "inside_window": inside,
        "compile_events_before_window": opened,
        "setup_marks_s": marks,
        "first_update_s": (probe.t_first_update - T_LAUNCH
                           if probe.t_first_update else None),
        "checks": checks, "compared": numbers,
        "control_fp8": control_numbers,
        "reference_seconds": ref_seconds,
        "intervals_ms": window.intervals_ms(retires),
        "final_metrics": {k: v for k, v in final.items()
                          if isinstance(v, (int, float))},
        "trace_summary": trace_summary,
    }
    _write_record(record)

    device = {key: value for key, value in device.items()
              if key != "memory_stats"}          # the record keeps them
    line = {"correct": ok, "attempted": updates, "failed": failed,
            "metrics": {} if args.rehearse else metrics, "device": device}
    if args.rehearse:
        line["rehearsal"] = {
            "metrics_that_would_print": sorted(metrics), "updates": updates,
            "note": "the limits behind `correct` are set at the cell's own "
                    "sizes; at rehearsal sizes a gap may pass them"}
    if args.trace and trace_summary.get("breakdown") and not args.rehearse:
        line["breakdown"] = trace_summary["breakdown"]
    # What failed, the comparisons with the reference first (a refusal's
    # record keeps the first numbers of this line), then every number
    # compared beside its limit: last in the line and last on stderr.
    line["checks_failed"] = checks_failed(checks, numbers)
    line["compared"] = {
        name: {"value": _plain(value), "limit": limit}
        for name, value, limit, _ in checks if name in numbers}
    say(json.dumps(line))
    for name, row in line["compared"].items():
        print(f"compared {name}: value={row['value']} "
              f"limit={row['limit']}", file=sys.stderr, flush=True)
    return 0


def checks_failed(checks, compared=()):
    """{name: {value, limit}} of the rows of ``checks`` that did not
    pass; those named in ``compared`` (the numbers held against the
    reference) first, in ``compared``'s order."""
    failed = {name: {"value": _plain(value), "limit": limit}
              for name, value, limit, passed in checks if not passed}
    first = [name for name in compared if name in failed]
    return {name: failed[name]
            for name in first + [n for n in failed if n not in first]}


def _plain(value):
    """A number as it is; one that JSON has no word for (nan, inf) as
    its name, so that the line stays one any reader can parse."""
    if isinstance(value, float) and not math.isfinite(value):
        return repr(value)
    return value


def report_per_layer(per_layer, ctx, metrics) -> int:
    """Read every per-layer metric of the cell into ``metrics``.  A
    reader with nothing to read returns None and is left out.  A share
    over 100% is never printed: no chip gives one, so its operations or
    bytes are counted too high or its time leaves out part of the work —
    the run exits non-zero naming the metric."""
    for metric in per_layer:
        value = metric.module.read(ctx)
        if value is None:
            continue
        if metric.entry["unit"] == "%" and value > 100.0:
            print(f"benchmark: {metric.name} reads {value}% — over 100%: "
                  f"its operations or bytes are counted too high, or its "
                  f"time leaves out part of the work. Refusing to print "
                  f"it.", file=sys.stderr)
            return 3
        metrics[metric.name] = {"value": value,
                                "unit": metric.entry["unit"]}
    return 0


def _span_window(probe):
    """Spans are read before the profiler starts (an annotated span
    costs ~100x a plain one), unless that leaves under a quarter of the
    window: then over the whole window."""
    t_open = probe.t_open or 0.0
    t_close = probe.t_close or float("inf")
    started = probe.trace_started_at
    if started and started - t_open >= 0.25 * (t_close - t_open):
        return t_open, started
    return t_open, t_close


def _dump_events(workload, events, slice_s=0.05) -> None:
    """A small slice of the real trace for the tests' recorded file:
    every program run, and the ops and host events of the first
    ``slice_s`` seconds after the first whole run starts."""
    from benchmark.lib import trace_reduce

    runs = [e for e in events if e.line == trace_reduce.MODULES_LINE]
    if not runs:
        return
    start = sorted(r.start for r in runs)[min(1, len(runs) - 1)]
    kept = [e for e in events if e.line == trace_reduce.MODULES_LINE
            or start <= e.start <= start + slice_s]
    out_dir = os.path.join(ROOT, "chiprun_out", "benchmark")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"{workload}.events.json"), "w") as f:
        json.dump([list(e) for e in kept], f)


def _ledger_ring(logdir):
    """The tail of the program's pipeline-ledger stamps (its
    ``ledger.p0.json``): ``[{ts_us, tid, stage}]`` on the
    ``perf_counter`` clock."""
    try:
        with open(os.path.join(logdir, "ledger.p0.json")) as f:
            return json.load(f).get("ring_tail", [])
    except (OSError, ValueError):
        return []


def _write_record(record) -> None:
    out_dir = os.path.join(ROOT, "chiprun_out", "benchmark")
    try:
        os.makedirs(out_dir, exist_ok=True)
        name = (f"{record['workload']}.seed{record['seed']}"
                f".trace{record['trace']}.json")
        with open(os.path.join(out_dir, name), "w") as f:
            json.dump(record, f, default=str)
    except OSError as exc:
        say(f"benchmark: run file not written ({exc})")


if __name__ == "__main__":
    # The guard is load-bearing: env workers use the spawn context and
    # re-import __main__; nothing above touches jax at import time.
    sys.exit(main())
