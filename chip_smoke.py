"""chip_smoke.py — the quickest proof that the trainer still starts on the chip.

Runs the default trainer's main path once, on the TPU this process is
given, through the entry point a user calls
(``scalable_agent_tpu.driver.main``, i.e. ``python -m
scalable_agent_tpu.driver --mode=train``) at the full width of the
default agent — shallow torso + LSTM(256), 72x96 frames, T=100, B=32,
64 actors, ``compute_dtype=bfloat16``, every ``auto`` left on ``auto``:

  (a) host backend: spawned env workers -> actor inference on the chip
      -> packed transport -> jitted update;
  (b) ``--train_backend=ingraph``: rollout + update fused on the chip;

each for a few updates from random weights (seeded), each in a fresh
logdir.  Then it checks what came out, from the runs' own artifacts:
the update count and ``env_frames`` are exactly what was asked for,
the loss is finite, the parameters moved, parameters and trajectories
live on TPU devices (all of them, when there are several), and on one
chip the update that ran holds the Mosaic kernels the kernel policy
promised — nothing interpreted, nothing given way to a reference.

There is no CPU path: without a TPU the script exits non-zero before
training and prints no result.  One process holds the chip; the only
children are the jax-free env workers, and all of them are stopped.
On success the LAST stdout line is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
the line before it is the run's summary (information, not a claim),
also written to ``chiprun_out/chip_smoke_summary.json``.
"""

import json
import math
import os
import shutil
import sys
import tempfile
import time

UPDATES = 16           # per backend; the first one pays the compile
# The driver's defaults, spelled out so the run cannot drift from them.
ACTORS, BATCH, UNROLL, REPEATS, HEIGHT, WIDTH = 64, 32, 100, 4, 72, 96
# Mosaic calls the single-chip policy promises inside the update:
# LSTM forward, LSTM backward, stem grad-W.
POLICY_MOSAIC_CALLS = 3


class _Probe:
    """What one training run showed from the inside: filled by thin
    wrappers around the learner's public update / the fused trainer's
    step, which record and then call straight through."""

    def __init__(self):
        self.dispatches = 0
        self.done_at = {}            # update number -> time it finished
        self.param_before = None     # host copy of one param leaf
        self.param_devices = None    # device set of a param leaf
        self.batch_devices = None    # device set of a trajectory leaf
        self.mosaic_calls = None     # tpu_custom_call count, lowered
        self.agent = None


def _mark_done(probe, result):
    """Count one dispatch; wait for the result of the first, the middle
    and the last one (dispatch is asynchronous — the calls alone would
    time the enqueue).  The first half runs on whatever the actors
    queued while update 1 compiled; the second half is the better
    reading of the loop's own pace."""
    import jax

    probe.dispatches += 1
    if probe.dispatches in (1, UPDATES // 2, UPDATES):
        jax.block_until_ready(result)
        probe.done_at[probe.dispatches] = time.monotonic()
    return result


def _device_facts(leaf):
    devices = sorted(leaf.sharding.device_set, key=lambda d: d.id)
    return {"count": len(devices),
            "platforms": sorted({d.platform for d in devices})}


def _param_leaf(params):
    return params["params"]["policy_logits"]["kernel"]


def _spy_host(probe):
    """Wrap ``Learner.update`` (the host backend's one update call)."""
    import numpy as np

    from scalable_agent_tpu.runtime.learner import Learner

    original = Learner.update

    def update(self, state, trajectory, fresh=True):
        if not probe.dispatches:
            # Before the first dispatch donates the state.
            probe.agent = self._agent
            probe.param_before = np.asarray(_param_leaf(state.params))
            probe.mosaic_calls = self.lower_update(
                state, trajectory).as_text().count("tpu_custom_call")
        probe.param_devices = _device_facts(_param_leaf(state.params))
        probe.batch_devices = _device_facts(
            trajectory.env_outputs.observation.frame)
        return _mark_done(
            probe, original(self, state, trajectory, fresh))

    Learner.update = update
    return lambda: setattr(Learner, "update", original)


def _spy_ingraph(probe):
    """Wrap each ``InGraphTrainer``'s jitted ``train_step``."""
    import numpy as np

    from scalable_agent_tpu.runtime.ingraph import InGraphTrainer

    original = InGraphTrainer.__init__

    def init(self, agent, *args, **kwargs):
        original(self, agent, *args, **kwargs)
        step = self.train_step

        def train_step(state, carry, counter):
            if not probe.dispatches:
                probe.agent = agent
                probe.param_before = np.asarray(
                    _param_leaf(state.params))
                probe.mosaic_calls = step.lower(
                    state, carry, counter).as_text().count(
                        "tpu_custom_call")
            # The rollout carry takes its batch sharding inside the
            # first step, so the LAST dispatch's inputs are the ones
            # that show where the batch lives.
            probe.param_devices = _device_facts(
                _param_leaf(state.params))
            probe.batch_devices = _device_facts(
                carry.rollout.env_output.observation.frame)
            return _mark_done(probe, step(state, carry, counter))

        train_step.lower = step.lower  # the driver lowers it for MFU
        self.train_step = train_step

    InGraphTrainer.__init__ = init
    return lambda: setattr(InGraphTrainer, "__init__", original)


def _run_phase(name, extra_flags, spy, root, n_devices, failures):
    """One training run through driver.main + its checks.  Returns the
    phase's summary dict; appends to ``failures``."""
    import jax
    import numpy as np

    from scalable_agent_tpu import driver
    from scalable_agent_tpu.obs import get_registry
    from scalable_agent_tpu.runtime.checkpoint import CheckpointManager
    from scalable_agent_tpu.runtime.elastic import MTTR_BREAKDOWN_NAME

    def check(ok, message):
        if not ok:
            failures.append(f"[{name}] {message}")

    logdir = os.path.join(root, name)
    frames = float(UPDATES * BATCH * UNROLL * REPEATS)
    probe = _Probe()
    unspy = spy(probe)
    compiles_before = get_registry().snapshot()
    t0 = time.monotonic()
    try:
        metrics = driver.main([
            "--mode=train", f"--logdir={logdir}",
            "--level_name=fake_benchmark", f"--num_actors={ACTORS}",
            f"--batch_size={BATCH}", f"--unroll_length={UNROLL}",
            f"--height={HEIGHT}", f"--width={WIDTH}",
            f"--total_environment_frames={frames:.0f}",
            *extra_flags])
    finally:
        unspy()
    wall_s = time.monotonic() - t0

    # -- what was asked for is what ran ---------------------------------
    check(probe.dispatches == UPDATES,
          f"{probe.dispatches} updates dispatched, asked for {UPDATES}")
    restored = CheckpointManager(logdir).restore(target=None)
    check(restored is not None, "no verified checkpoint on disk")
    step, saved = restored if restored is not None else (None, {})
    check(step == UPDATES, f"checkpoint step {step}, want {UPDATES}")
    saved_frames = saved.get("env_frames")
    check(saved_frames is not None
          and float(np.asarray(saved_frames)) == frames,
          f"checkpoint env_frames {saved_frames}, want {frames}")
    check(metrics.get("env_frames") == frames,
          f"final metrics env_frames {metrics.get('env_frames')}, "
          f"want {frames}")

    # -- it learned something finite ------------------------------------
    loss = metrics.get("total_loss", float("nan"))
    check(math.isfinite(loss), f"final total_loss {loss} not finite")
    check(metrics.get("nonfinite_skips", 0.0) == 0.0,
          f"{metrics.get('nonfinite_skips')} updates skipped as "
          f"non-finite")
    moved = None
    if restored is not None and probe.param_before is not None:
        after = np.asarray(_param_leaf(saved["params"]))
        check(bool(np.all(np.isfinite(after))),
              "saved params are not finite")
        moved = float(np.max(np.abs(after - probe.param_before)))
        check(moved > 0.0, "params did not move")

    # -- it ran on the chip(s), all of them -----------------------------
    for what, facts in (("params", probe.param_devices),
                        ("trajectory batch", probe.batch_devices)):
        check(facts is not None, f"{what}: never observed")
        if facts is None:
            continue
        check(facts["platforms"] == ["tpu"],
              f"{what} live on {facts['platforms']}, want ['tpu']")
        check(facts["count"] == n_devices,
              f"{what} span {facts['count']} device(s) of {n_devices}")

    # -- the kernels the policy promised are in the program -------------
    agent = probe.agent
    policy = None
    if agent is not None:
        policy = {"core_impl": agent.core_impl,
                  "conv_backend": agent.conv_backend,
                  "core_matmul_dtype": agent.core_matmul_dtype,
                  "remat_torso": agent.remat_torso}
        want = "pallas" if n_devices == 1 else "xla"
        check(agent.core_impl == want and agent.conv_backend == want,
              f"kernel policy resolved to {policy} on {n_devices} "
              f"device(s), want {want}/{want}")
        if n_devices == 1:
            check(probe.mosaic_calls >= POLICY_MOSAIC_CALLS,
                  f"{probe.mosaic_calls} Mosaic calls in the lowered "
                  f"program, the policy promises >= "
                  f"{POLICY_MOSAIC_CALLS} (LSTM fwd, LSTM bwd, stem "
                  f"grad-W)")
        else:
            check(probe.mosaic_calls == 0,
                  f"{probe.mosaic_calls} Mosaic calls on a "
                  f"{n_devices}-device mesh (no partitioning rule)")

    try:
        with open(os.path.join(logdir, MTTR_BREAKDOWN_NAME)) as f:
            compile_s = json.load(f).get("compile_s")
    except (OSError, ValueError):
        compile_s = None
    def pace(first, last):
        if first not in probe.done_at or last not in probe.done_at:
            return None
        return round((probe.done_at[last] - probe.done_at[first])
                     / (last - first), 4)

    stats = jax.local_devices()[0].memory_stats() or {}
    compiles = get_registry().snapshot()
    return {
        "updates": probe.dispatches,
        "env_frames": metrics.get("env_frames"),
        "total_loss": loss,
        "param_max_abs_delta": moved,
        "kernel_policy": policy,
        "mosaic_calls_lowered": probe.mosaic_calls,
        "param_devices": probe.param_devices,
        "batch_devices": probe.batch_devices,
        "first_dispatch_compile_s": compile_s,
        "jax_compile_events": int(compiles.get("jax/compile_count", 0)
                            - compiles_before.get("jax/compile_count", 0)),
        "jax_compile_events_s": round(
            compiles.get("jax/compile_time_s", 0.0)
            - compiles_before.get("jax/compile_time_s", 0.0), 1),
        "sec_per_update_first_half": pace(1, UPDATES // 2),
        "sec_per_update_second_half": pace(UPDATES // 2, UPDATES),
        "wall_s": round(wall_s, 1),
        "peak_bytes_in_use_device0": stats.get("peak_bytes_in_use"),
        "bytes_limit_device0": stats.get("bytes_limit"),
    }


def main() -> int:
    import logging
    import multiprocessing

    import jax

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU — jax.default_backend() is "
              f"{jax.default_backend()!r} (JAX_PLATFORMS="
              f"{os.environ.get('JAX_PLATFORMS', '')!r}); this script "
              f"has no CPU path", file=sys.stderr)
        return 2

    from scalable_agent_tpu.utils import log
    from scalable_agent_tpu.utils.compile_cache import (
        setup_compile_cache,
    )

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    cache_dir = setup_compile_cache()

    # Warnings the run logs (a disarmed MFU gauge, a routed-away
    # kernel) are part of the summary, not lost in the scroll.
    warnings = []

    class _Collect(logging.Handler):
        def emit(self, record):
            warnings.append(record.getMessage()[:300])

    collector = _Collect(level=logging.WARNING)
    log.addHandler(collector)

    failures = []
    phases = {}
    root = tempfile.mkdtemp(prefix="chip_smoke_")
    t0 = time.monotonic()
    try:
        phases["host"] = _run_phase(
            "host", [], _spy_host, root, len(devices), failures)
        phases["ingraph"] = _run_phase(
            "ingraph", ["--train_backend=ingraph"], _spy_ingraph, root,
            len(devices), failures)
    finally:
        log.removeHandler(collector)
        leftover = multiprocessing.active_children()
        for child in leftover:
            child.terminate()
        for child in leftover:
            child.join(timeout=10)
        shutil.rmtree(root, ignore_errors=True)
    if leftover:
        failures.append(
            f"{len(leftover)} child process(es) outlived the runs: "
            f"{[child.name for child in leftover]}")

    summary = {
        "device": device,
        "jax_version": jax.__version__,
        "jax_cache_dir": cache_dir,
        "phases": phases,
        "warnings": warnings,
        "failures": failures,
        "wall_s": round(time.monotonic() - t0, 1),
    }
    line = json.dumps(summary)
    out_dir = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "chiprun_out")
    try:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, "chip_smoke_summary.json"),
                  "a") as f:
            f.write(line + "\n")
    except OSError:
        pass  # stdout still carries it
    print(line, flush=True)
    if failures:
        for failure in failures:
            print(f"chip_smoke: FAILED {failure}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    # The guard is load-bearing: env workers use the spawn context and
    # re-import __main__ (envs/worker.py); nothing above touches jax at
    # import time.
    sys.exit(main())
