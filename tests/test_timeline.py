"""One timeline from launch to retire (ISSUE 24): the tracer starts at
``driver.main``'s first line, every set-up stage and every compile is a
span with a parent and a self time, the log publish is a span, and the
fused step's ops carry their layer's scope.

Two tiny traced runs (fused and host), each made once per module, and
unit checks of the tracer and the compile listener beside them.
"""

import gc
import glob
import json
import os
import re
import threading
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scalable_agent_tpu import driver
from scalable_agent_tpu.obs import MetricsRegistry, get_registry
from scalable_agent_tpu.obs import kernels as kernels_lib
from scalable_agent_tpu.obs import trace as trace_lib

FUSED_STAGES = [
    "setup/config", "setup/compile_cache", "setup/probe_env",
    "setup/build_agent", "setup/device_env", "setup/build_learner",
    "setup/trainer_init", "setup/restore", "setup/observability",
    "setup/live_mfu", "setup/loop_entry", "setup/first_dispatch"]
HOST_STAGES = [
    "setup/config", "setup/distributed_init", "setup/compile_cache",
    "setup/observability", "setup/probe_env", "setup/build_agent",
    "setup/build_learner", "setup/trainer_init", "setup/restore",
    "setup/live_mfu", "setup/env_groups", "setup/prefetch_start",
    "setup/first_dispatch"]
PUBLISH_CHILDREN = ["log/fetch_metrics", "log/telemetry", "log/ledger",
                    "log/health", "log/write", "log/prom"]
UPDATES = 3
SLACK_US = 5000     # clock conversion and stage hand-over: within 5 ms
ENQUEUE_ARGS = {"update", "in_flight", "in_flight_after",
                *trace_lib.USAGE_ARGS}


def _run(tmp_path_factory, backend):
    logdir = str(tmp_path_factory.mktemp(f"timeline_{backend}") / "run")
    argv = [
        "--mode=train", f"--logdir={logdir}", "--trace=true",
        "--level_name=fake_small", "--num_actors=4", "--batch_size=2",
        "--unroll_length=4", "--num_action_repeats=1", "--height=16",
        "--width=16", "--num_env_workers_per_group=2",
        "--compute_dtype=float32", "--checkpoint_interval_s=1e9",
        "--log_interval_s=0", "--seed=5",
        f"--total_environment_frames={UPDATES * 8}"]
    if backend == "ingraph":
        argv.append("--train_backend=ingraph")
    t_before = time.perf_counter_ns() // 1000
    metrics = driver.main(argv)
    assert metrics["env_frames"] == UPDATES * 8
    path = trace_lib.last_trace_path()
    assert os.path.dirname(path) == logdir
    spans = [e for e in trace_lib.load_trace_events(path)
             if e.get("ph") == "X"]
    return {"logdir": logdir, "path": path, "spans": spans,
            "t_before": t_before,
            "by_sid": {e["sid"]: e for e in spans}}


@pytest.fixture(scope="module")
def fused(tmp_path_factory):
    return _run(tmp_path_factory, "ingraph")


@pytest.fixture(scope="module")
def host(tmp_path_factory):
    return _run(tmp_path_factory, "host")


def _stages(run):
    return sorted((e for e in run["spans"] if e["cat"] == "setup"
                   and "parent" not in e), key=lambda e: e["ts"])


def _end(e):
    return e["ts"] + e["dur"]


def _inside(run, child, name):
    """Is ``child`` (transitively) under a span called ``name``?"""
    while "parent" in child:
        child = run["by_sid"][child["parent"]]
        if child["name"] == name:
            return True
    return False


# -- the tracer alone --------------------------------------------------------

def test_self_time_is_duration_less_children_on_a_hand_built_nest(
        tmp_path):
    tracer = trace_lib.Tracer(str(tmp_path / "t.json"))
    # a [0, 100] holds b [10, 40] (which holds c [20, 30]) and d [50, 70],
    # reported as they end: c, b, d, a.
    with tracer.span("a") as a:
        a_sid = a._sid
        with tracer.span("b") as b:
            b_sid = b._sid
            tracer.add_span("c", "x", 20, 30)
    tracer.close()
    events = {e["name"]: e for e in trace_lib.load_trace_events(
        str(tmp_path / "t.json")) if e.get("ph") == "X"}
    assert events["c"]["parent"] == b_sid and events["c"]["self"] == 10
    assert events["b"]["parent"] == a_sid and "parent" not in events["a"]

    # the arithmetic, on intervals a clock cannot blur
    covered = []
    assert trace_lib._cover(covered, 20, 30) == 0        # c
    assert trace_lib._cover(covered, 50, 70) == 0        # d
    assert covered == [(20, 30), (50, 70)]
    # a span reported AFTER the spans nested in it swallows them: its
    # self time leaves out what they covered
    assert trace_lib._cover(covered, 10, 80) == 30
    assert covered == [(10, 80)]
    assert 100 - sum(e - s for s, e in covered) == 30    # a's self time


def test_spans_nest_per_thread_and_self_times_add_up(tmp_path):
    tracer = trace_lib.Tracer(str(tmp_path / "t.json"))
    with tracer.span("outer"):
        with tracer.span("first"):
            time.sleep(0.01)
        with tracer.span("second"):
            time.sleep(0.01)
    tracer.close()
    events = {e["name"]: e for e in trace_lib.load_trace_events(
        str(tmp_path / "t.json")) if e.get("ph") == "X"}
    outer = events["outer"]
    assert events["first"]["parent"] == outer["sid"]
    assert events["second"]["parent"] == outer["sid"]
    assert outer["self"] == outer["dur"] - (
        events["first"]["dur"] + events["second"]["dur"])
    assert events["first"]["self"] == events["first"]["dur"]


def test_a_wall_clock_span_lands_on_the_span_clock_within_5ms(tmp_path):
    tracer = trace_lib.Tracer(str(tmp_path / "t.json"))
    with tracer.span("stage"):
        perf0, wall0 = time.perf_counter_ns() // 1000, time.time()
        time.sleep(0.02)
        wall1, perf1 = time.time(), time.perf_counter_ns() // 1000
        tracer.add_wall_span("compile/backend", "compile", wall0, wall1,
                             {"fun_name": "f"})
    tracer.close()
    events = {e["name"]: e for e in trace_lib.load_trace_events(
        str(tmp_path / "t.json")) if e.get("ph") == "X"}
    placed = events["compile/backend"]
    assert abs(placed["ts"] - perf0) < SLACK_US
    assert abs(_end(placed) - perf1) < SLACK_US
    assert placed["parent"] == events["stage"]["sid"]
    assert placed["args"] == {"fun_name": "f"}


def test_a_deferred_tracer_holds_its_first_spans_until_attach(tmp_path):
    tracer = trace_lib.Tracer(deferred=True)
    with tracer.span("early"):
        pass
    tracer.flush()                       # nowhere to write yet: kept
    path = str(tmp_path / "trace.p3.1.json")
    tracer.attach(path, process_index=3)
    with tracer.span("late"):
        pass
    tracer.close()
    events = list(trace_lib.load_trace_events(path))
    names = [e["name"] for e in events if e.get("ph") == "X"]
    assert names == ["early", "late"]
    epoch = [e for e in events if e["name"] == "trace_epoch"][0]
    assert epoch["args"]["process_index"] == 3
    assert trace_lib.last_trace_path() == path
    assert kernels_lib.op_scopes_path(path) == str(
        tmp_path / "op_scopes.p3.1.json")


def test_tracing_off_is_the_shared_noop(tmp_path):
    assert not trace_lib.get_tracer().enabled
    assert trace_lib.span("learner/train_step") is trace_lib._NULL_SPAN
    tracer = trace_lib.get_tracer()
    tracer.add_span("x", "y", 0, 1)      # all no-ops, nothing recorded
    tracer.add_wall_span("x", "y", 0.0, 1.0)
    # a run without --trace leaves the process tracer as it was, and
    # its stage records are still kept (the MTTR beacon reads them)
    stages = driver._open_timeline(
        driver.Config(logdir=str(tmp_path), trace=False), None)
    stages.enter("setup/restore")
    stages.done()
    assert trace_lib.get_tracer() is tracer
    assert set(stages.seconds) == {"setup/config", "setup/restore"}
    assert not glob.glob(str(tmp_path / "trace.*"))


# -- the compile listener ----------------------------------------------------

def test_compile_count_counts_backend_compiles_one_per_program():
    registry = MetricsRegistry().install_jax_hooks()
    count = registry.counter("jax/compile_count")
    step = jax.jit(lambda x: jnp.tanh(x) * 3.0 + 1.0)
    x = jnp.ones((7, 3))     # made before the count: asarray compiles too
    before = count.value
    jax.block_until_ready(step(x))       # trace + lower + ONE backend compile
    assert count.value == before + 1
    assert registry.counter("jax/compile_time_s").value > 0.0
    jax.block_until_ready(step(x))       # cached in memory: nothing
    assert count.value == before + 1


def test_cache_hit_and_miss_counters_move_over_one_cache_directory(
        tmp_path):
    from jax.experimental.compilation_cache import compilation_cache

    registry = MetricsRegistry().install_jax_hooks()
    hits = registry.counter("jax/compile_cache_hits_total")
    misses = registry.counter("jax/compile_cache_misses_total")
    saved = {name: getattr(jax.config, name) for name in (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    try:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        compilation_cache.reset_cache()
        x = jnp.ones((5, 11))

        def first_run_then_second():
            step = jax.jit(lambda v: jnp.sin(v) * 5.0 - 2.0)
            jax.block_until_ready(step(x))

        first_run_then_second()
        assert misses.value >= 1 and hits.value == 0
        missed = misses.value
        jax.clear_caches()               # a second process, in effect
        first_run_then_second()
        assert hits.value >= 1 and misses.value == missed
    finally:
        for name, value in saved.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()


# -- the fused run's timeline ------------------------------------------------

@pytest.mark.parametrize("which, expected", [
    ("fused", FUSED_STAGES), ("host", HOST_STAGES)])
def test_setup_stages_are_contiguous_from_main_entry_to_first_dispatch(
        which, expected, request):
    run = request.getfixturevalue(which)
    stages = _stages(run)
    assert [e["name"] for e in stages] == expected
    # the timeline starts where driver.main was entered, not where the
    # tracer was made
    assert 0 <= stages[0]["ts"] - run["t_before"] < SLACK_US
    for before, after in zip(stages, stages[1:]):
        gap = after["ts"] - _end(before)
        assert 0 <= gap < SLACK_US, (before["name"], after["name"], gap)
    covered = sum(e["dur"] for e in stages)
    assert covered >= 0.95 * (_end(stages[-1]) - stages[0]["ts"])
    # set-up ends where the first dispatch returns: the step ran inside
    # the last stage and the second dispatch outside it
    name = "learner/train_step" if which == "fused" else "learner/update"
    steps = sorted((e for e in run["spans"] if e["name"] == name),
                   key=lambda e: e["ts"])
    assert _inside(run, steps[0], "setup/first_dispatch")
    assert steps[1]["ts"] >= _end(stages[-1])


def test_learner_init_is_a_stage_inside_trainer_init(fused):
    inner = [e for e in fused["spans"]
             if e["name"] == "setup/learner_init"]
    assert len(inner) == 1
    assert fused["by_sid"][inner[0]["parent"]]["name"] == \
        "setup/trainer_init"


def test_compile_spans_name_their_program_and_nest_in_their_stage(fused):
    compiles = [e for e in fused["spans"] if e["cat"] == "compile"]
    assert {e["name"] for e in compiles} == {
        "compile/trace", "compile/lower", "compile/backend"}
    stages = _stages(fused)
    setup_end = _end(stages[-1])
    for e in compiles:
        assert e["args"]["fun_name"]
        if _end(e) > setup_end:
            continue
        parent = fused["by_sid"][e["parent"]]    # every one has a parent
        assert parent["ts"] - SLACK_US <= e["ts"]
        assert _end(e) <= _end(parent) + SLACK_US
    # the fused step itself: traced, lowered and compiled once, by the
    # first dispatch
    step = [e for e in compiles if e["args"]["fun_name"].endswith(
        ("_fused", "_fused)"))]
    inside = [e for e in step
              if _inside(fused, e, "setup/first_dispatch")]
    assert {e["name"] for e in inside} == {
        "compile/trace", "compile/lower", "compile/backend"}
    assert all(_inside(fused, e, "learner/train_step") for e in inside)
    assert sum(e["name"] == "compile/backend" for e in step) == 1
    # a stage's self time leaves its compiles out
    first = stages[-1]
    assert first["self"] <= first["dur"] - sum(
        e["dur"] for e in inside if e["name"] == "compile/backend")


@pytest.mark.parametrize("which", ["fused", "host"])
def test_log_publish_and_its_six_children_once_per_log_interval(
        which, request):
    run = request.getfixturevalue(which)
    publishes = [e for e in run["spans"]
                 if e["name"] == "driver/log_publish"]
    # --log_interval_s=0: one publish per update
    assert len(publishes) == UPDATES
    for publish in publishes:
        children = sorted(
            (e for e in run["spans"]
             if e.get("parent") == publish["sid"] and e["cat"] == "log"),
            key=lambda e: e["ts"])
        assert [e["name"] for e in children] == PUBLISH_CHILDREN
        assert publish["self"] == publish["dur"] - sum(
            e["dur"] for e in children)
    if which == "fused":
        # fused-loop spans share the update counter
        assert [p["args"]["update"] for p in publishes] == [1, 2, 3]
        steps = [e for e in run["spans"]
                 if e["name"] == "learner/train_step"]
        assert [s["args"]["update"] for s in steps] == [0, 1, 2]


def test_mttr_beacon_reads_the_stage_records(fused):
    beacon = json.load(open(os.path.join(
        fused["logdir"], "mttr_breakdown.json")))
    stages = {e["name"]: e for e in _stages(fused)}
    assert beacon["compile_s"] == pytest.approx(
        stages["setup/first_dispatch"]["dur"] * 1e-6, abs=0.01)
    assert beacon["restore_s"] == pytest.approx(
        stages["setup/restore"]["dur"] * 1e-6, abs=0.01)


def test_op_scopes_table_lies_beside_the_trace(fused):
    table = json.load(open(kernels_lib.op_scopes_path(fused["path"])))
    assert table["module"].endswith("_fused")
    scopes = " ".join(table["ops"].values())
    for name in ("rollout", "learner_update", "vtrace_loss", "optimizer",
                 "telemetry", "convnet", "core"):
        assert name in scopes, name


# -- the scopes in the step's text -------------------------------------------

@pytest.fixture(scope="module")
def tiny_trainer():
    from scalable_agent_tpu.envs.device import DeviceFakeEnv
    from scalable_agent_tpu.models import ImpalaAgent
    from scalable_agent_tpu.parallel import MeshSpec, make_mesh
    from scalable_agent_tpu.runtime import (
        InGraphTrainer,
        Learner,
        LearnerHyperparams,
    )

    agent = ImpalaAgent(num_actions=4, core_impl="pallas")
    mesh = make_mesh(MeshSpec(data=1, model=1), devices=jax.devices()[:1])
    learner = Learner(agent, LearnerHyperparams(
        total_environment_frames=1e6), mesh, frames_per_update=8)
    env = DeviceFakeEnv(height=16, width=16, num_actions=4,
                        episode_length=7)
    trainer = InGraphTrainer(agent, learner, env, 4, 2, seed=5)
    state, carry = trainer.init(jax.random.key(0))
    return trainer, state, carry


def test_the_lowered_fused_step_carries_its_layers_scopes(tiny_trainer):
    from scalable_agent_tpu.ops import conv_pallas, lstm_pallas

    trainer, state, carry = tiny_trainer
    text = trainer.train_step.lower(
        state, carry, np.int32(0)).as_text(debug_info=True)
    for scope in ("rollout", "vtrace_loss", "optimizer", "telemetry",
                  "learner_update", "actor_inference", "env_step",
                  lstm_pallas.FWD_KERNEL_NAME, lstm_pallas.STEP_KERNEL_NAME,
                  lstm_pallas.BWD_KERNEL_NAME):
        # a whole component of a name stack ("jit(_fused)/rollout/while",
        # "env_step/tanh", "jvp(vtrace_loss)/mul"): the debug text splits
        # stacks across locs, and autodiff wraps the component
        assert re.search(r'["/(]%s["/)]' % scope, text), scope
    # no new scope may be mistaken for the stem kernel's
    names = (lstm_pallas.FWD_KERNEL_NAME, lstm_pallas.STEP_KERNEL_NAME,
             lstm_pallas.BWD_KERNEL_NAME, "rollout", "vtrace_loss",
             "optimizer", "telemetry")
    assert not any(conv_pallas.GRADW_KERNEL_NAME in n for n in names)


def test_holds_scope_wants_a_whole_component():
    line = ('%fusion.1 = f32[8] fusion(...), metadata={op_name="jit(_fused)/'
            'while/body/learner_update/jvp(vtrace_loss)/mul"}\n')
    assert kernels_lib.holds_scope(line, "vtrace_loss")
    assert kernels_lib.holds_scope(line, "learner_update")
    assert not kernels_lib.holds_scope(line, "rollout")
    assert not kernels_lib.holds_scope(line, "loss")
    assert not kernels_lib.holds_scope(line, "learner")


def test_a_stale_executable_from_the_cache_is_compiled_afresh_for_its_names(
        tiny_trainer, tmp_path):
    """The persistent cache's key leaves op metadata out: a hit may hand
    back an executable that an older version of the program compiled,
    whose text names the OLD scopes.  The table must hold this
    program's."""
    trainer, state, carry = tiny_trainer
    fresh = trainer.compile_step_afresh(state, carry).as_text()
    assert all(kernels_lib.holds_scope(fresh, scope)
               for scope in driver._STEP_SCOPES)
    assert kernels_lib.hlo_module_name(fresh).endswith("_fused")
    # the same instructions as the step the loop runs, so the join on
    # instruction names still holds
    own = trainer.train_step.lower(
        state, carry, np.int32(0)).compile().as_text()
    table = lambda text: set(json.load(open(kernels_lib.write_op_scopes(  # noqa: E731
        str(tmp_path / "trace.p0.1.json"), text)))["ops"])
    assert table(fresh) == table(own)

    class Stale:
        """A step whose cached executable predates the scopes."""

        def __init__(self):
            self.afresh = 0

        def lower(self, *args):
            return self

        def compile(self):
            return self

        def as_text(self):
            return own.replace("rollout/", "").replace("rollout\"", "\"")

        train_step = property(lambda self: self)

        def compile_step_afresh(self, state, carry):
            self.afresh += 1
            return trainer.compile_step_afresh(state, carry)

    stale = Stale()
    assert not kernels_lib.holds_scope(stale.as_text(), "rollout")
    trace_path = str(tmp_path / "trace.p0.2.json")
    driver._write_op_scopes(trace_path, stale, state, carry)
    assert stale.afresh == 1
    ops = json.load(open(kernels_lib.op_scopes_path(trace_path)))["ops"]
    assert any("/rollout/" in name for name in ops.values())
    # an executable that is this program's own is read as it is
    driver._write_op_scopes(trace_path, trainer, state, carry)
    driver._write_op_scopes(None, None, None, None)      # tracing off


# -- inside the fused dispatch (ISSUE 36) ------------------------------------

def test_fused_enqueue_is_the_train_steps_child_and_says_what_it_cost(
        fused):
    steps = {e["sid"]: e for e in fused["spans"]
             if e["name"] == "learner/train_step"}
    enqueues = sorted((e for e in fused["spans"]
                       if e["name"] == "fused/enqueue"),
                      key=lambda e: e["ts"])
    assert len(enqueues) == len(steps) == UPDATES
    for e in enqueues:
        step = steps[e["parent"]]
        assert e["cat"] == "learner"
        assert set(e["args"]) == ENQUEUE_ARGS
        assert all(isinstance(v, int) for v in e["args"].values())
        assert e["args"]["update"] == step["args"]["update"]
        # what the step's span holds beside the hand-over is its self
        # time: under a harness its wait, here next to nothing
        assert step["self"] == step["dur"] - e["dur"]
    # --log_interval_s=0: every dispatch follows a publish, whose fetch
    # emptied the queue
    assert [e["args"]["in_flight"] for e in enqueues] == [0] * UPDATES
    # the step is still traced, lowered and compiled by the first
    # dispatch, now inside its hand-over
    step_compiles = [
        e for e in fused["spans"] if e["cat"] == "compile"
        and e["args"]["fun_name"].endswith(("_fused", "_fused)"))
        and _inside(fused, e, "setup/first_dispatch")]
    assert {e["name"] for e in step_compiles} == {
        "compile/trace", "compile/lower", "compile/backend"}
    for e in step_compiles:
        assert _inside(fused, e, "fused/enqueue")
        assert _inside(fused, e, "learner/train_step")


def test_writer_rows_are_spans_of_the_writers_thread(fused):
    threads = {e["tid"]: e["args"]["name"]
               for e in trace_lib.load_trace_events(fused["path"])
               if e.get("name") == "thread_name"}
    rows = [e for e in fused["spans"] if e["name"] == "writer/rows"]
    assert rows and all(e["cat"] == "log" for e in rows)
    # what each batch cost the writer's thread, as fused/enqueue says it
    # of the loop's
    assert all(set(e["args"]) == {"rows"} | set(trace_lib.USAGE_ARGS)
               for e in rows)
    # two rows a publish (the metrics, the registry's snapshot)
    assert sum(e["args"]["rows"] for e in rows) == 2 * UPDATES
    loop = {e["tid"] for e in fused["spans"]
            if e["name"] == "learner/train_step"}
    assert {threads[e["tid"]] for e in rows} == {"metrics-writer"}
    assert not loop & {e["tid"] for e in rows}
    # log/write hands the rows over and returns: none is its child
    assert all("parent" not in e for e in rows)
    flushes = [e for e in fused["spans"] if e["name"] == "writer/flush"]
    assert len(flushes) == 1      # the timed one, inside the first batch
    assert fused["by_sid"][flushes[0]["parent"]]["name"] == "writer/rows"


class _Loss:
    """Stands where a dispatch's ``total_loss`` does in the queue."""

    def __init__(self, ready):
        self.ready = ready

    def is_ready(self):
        return self.ready


@pytest.fixture()
def traced(tmp_path):
    """The process tracer on, into a file; its spans once it is off."""
    path = str(tmp_path / "trace.p0.1.json")
    trace_lib.configure_tracer(path)

    def spans():
        trace_lib.configure_tracer(None)
        return [e for e in trace_lib.load_trace_events(path)
                if e.get("ph") == "X"]

    yield spans
    trace_lib.configure_tracer(None)


def test_in_flight_is_the_dispatches_not_yet_ready_and_zero_is_starved(
        tiny_trainer, traced, monkeypatch):
    trainer, _, _ = tiny_trainer
    handover = trainer._handover
    assert trainer.train_step is handover
    assert handover.lower == handover.step.lower     # still lowers
    starved = get_registry().counter("fused/dispatch_starved_total")
    state, carry = trainer.init(jax.random.key(1))   # the step donates
    handover._unready.clear()
    handover._dispatches = 0
    before = starved.value
    out = trainer.train_step(state, carry, np.int32(0))
    assert starved.value == before           # the first dispatch apart
    jax.block_until_ready(out)               # waited for: the queue is empty
    out = trainer.train_step(out[0], out[1], np.int32(1))
    assert starved.value == before + 1
    # dispatched straight after another: that one is still running (what
    # a loop reads while the device keeps up, and stops reading when a
    # throughput_sag holds the host)
    jax.block_until_ready(out)
    handover._unready.append(_Loss(ready=False))
    out = trainer.train_step(out[0], out[1], np.int32(2))
    assert starved.value == before + 1
    # the ready ones leave at the old end, in the device's order
    handover._unready.clear()
    handover._unready.extend([_Loss(True), _Loss(False), _Loss(True)])
    assert handover.in_flight() == 2
    jax.block_until_ready(out)
    handover._unready.clear()
    enqueues = [e for e in traced() if e["name"] == "fused/enqueue"]
    assert [e["args"]["update"] for e in enqueues] == [0, 1, 2]
    assert [e["args"]["in_flight"] for e in enqueues] == [0, 0, 1]
    assert enqueues[2]["args"]["in_flight_after"] == 1
    assert all(set(e["args"]) == ENQUEUE_ARGS for e in enqueues)
    # the interval's longest hand-over goes out with the publish, and
    # the next interval starts afresh
    assert handover._enqueue_ms_max >= max(
        e["dur"] for e in enqueues[1:]) * 1e-3
    handover._enqueue_gauge.set(0.0)
    trainer.publish_telemetry(out[1])
    assert get_registry().gauge("fused/enqueue_ms_max").value > 0.0
    assert handover._enqueue_ms_max == 0.0

    # tracing off: the count and the gauge go on, and nothing asks the
    # kernel for the thread's usage
    trace_lib.configure_tracer(None)

    def no_usage(*args):
        raise AssertionError("getrusage on the dispatch path")

    monkeypatch.setattr(trace_lib.resource, "getrusage", no_usage)
    jax.block_until_ready(out)
    out = trainer.train_step(out[0], out[1], np.int32(3))
    assert starved.value == before + 2
    assert handover._enqueue_ms_max > 0.0
    jax.block_until_ready(out)
    handover._unready.clear()


def test_a_full_collection_is_one_gc_collect_span_with_its_args(tmp_path):
    callbacks = list(gc.callbacks)
    threads = {t.name for t in threading.enumerate()}
    registry = MetricsRegistry()
    tracer = trace_lib.Tracer(str(tmp_path / "t.json"))
    watch = trace_lib.HostWatch(tracer, registry).start()
    try:
        assert len(gc.callbacks) == len(callbacks) + 1
        assert "host-pulse" in {t.name for t in threading.enumerate()}
        enabled = gc.isenabled()
        gc.disable()                 # none but the collections asked for
        try:
            with tracer.span("region") as region:
                gc.collect(0)                # a young one: counted only
                junk = [[] for _ in range(1000)]
                for item in junk:
                    item.append(item)        # cycles: the collector's
                del junk, item
                gc.collect()
        finally:
            if enabled:
                gc.enable()
    finally:
        watch.stop()
    tracer.close()
    assert list(gc.callbacks) == callbacks
    assert {t.name for t in threading.enumerate()} == threads
    spans = [e for e in trace_lib.load_trace_events(str(tmp_path / "t.json"))
             if e.get("ph") == "X" and e["name"] != "host/late_wakeup"]
    (collect,) = [e for e in spans if e["name"] == "gc/collect"]
    assert collect["cat"] == "host"
    assert collect["parent"] == region._sid
    assert set(collect["args"]) == {"collected", "uncollectable"}
    assert collect["args"]["collected"] >= 1000
    assert registry.counter("gc/collections_total").value == 2
    assert registry.counter("gc/pause_s_total").value \
        >= collect["dur"] * 1e-6


def test_the_host_watch_lives_only_while_a_traced_run_does(tmp_path):
    callbacks = list(gc.callbacks)
    threads = {t.name for t in threading.enumerate()}

    def watched():
        return (len(gc.callbacks) - len(callbacks),
                "host-pulse" in {t.name for t in threading.enumerate()})

    off = driver.Config(logdir=str(tmp_path), trace=False)
    driver._open_timeline(off, None)
    assert watched() == (0, False)
    driver._close_timeline(off)
    on = driver.Config(logdir=str(tmp_path), trace=True)
    driver._open_timeline(on, None)
    try:
        assert watched() == (1, True)
        assert trace_lib.get_tracer().enabled
    finally:
        driver._close_timeline(on)
    assert not trace_lib.get_tracer().enabled
    assert list(gc.callbacks) == callbacks
    assert {t.name for t in threading.enumerate()} == threads


def test_the_pulse_records_a_wake_up_50_ms_late_and_no_earlier(tmp_path):
    late = trace_lib.HostWatch.late_interval
    due = 7_000_000_000
    assert late(due, due) is None
    assert late(due, due + 49_999_999) is None      # scheduling jitter
    assert late(due, due + 50_000_000) == (
        7_000_000, 7_050_000, {"late_ms": 50.0})
    assert late(due, due + 2_400_000_000) == (
        7_000_000, 9_400_000, {"late_ms": 2400.0})
    # the span is PLACED when the thread wakes, over the time it
    # overslept: at no moment is it open on any thread
    tracer = trace_lib.Tracer(str(tmp_path / "t.json"))
    with tracer.span("loop"):
        pulse = threading.Thread(
            target=lambda: tracer.add_span(
                "host/late_wakeup", "host", *late(due, due + 60_000_000)),
            name="host-pulse")
        pulse.start()
        pulse.join()
    tracer.close()
    events = {e["name"]: e for e in trace_lib.load_trace_events(
        str(tmp_path / "t.json")) if e.get("ph") == "X"}
    wake = events["host/late_wakeup"]
    assert (wake["ts"], wake["dur"]) == (7_000_000, 60_000)
    assert wake["args"] == {"late_ms": 60.0} and "parent" not in wake
    assert wake["tid"] != events["loop"]["tid"]


def test_a_span_takes_the_stamps_it_is_handed(tmp_path):
    tracer = trace_lib.Tracer(str(tmp_path / "t.json"))
    first = tracer.span("setup/a", cat="setup", start_ns=1_000_000)
    first.__enter__()
    time.sleep(0.002)                 # however long the hand-over takes
    first.close(5_000_000)
    second = tracer.span("setup/b", cat="setup", start_ns=5_000_000)
    second.__enter__()
    second.close(9_000_999)
    tracer.close()
    a, b = [e for e in trace_lib.load_trace_events(str(tmp_path / "t.json"))
            if e.get("ph") == "X"]
    assert (a["ts"], a["dur"]) == (1000, 4000)
    assert (b["ts"], b["dur"]) == (5000, 4000)
    assert b["ts"] == _end(a)
    trace_lib._NULL_SPAN.close(1)      # tracing off: the same calls


# -- the readers of those spans (benchmark/metrics/*.fused.py) ---------------

DISPATCH_READERS = (
    "dispatch_longest_over_median.fused", "enqueue_ms.fused",
    "starved_dispatch_share.fused", "host_frozen_ms.fused",
    "gc_pause_ms.fused")


def _dispatch_reader(name):
    from benchmark.lib import manifest

    return manifest.load_module(os.path.join(
        manifest.BENCH_DIR, manifest.METRICS_DIR, name + ".py"), name)


def _recorded(steps, extra=(), enqueue=True):
    """A window [100 s, 145 s] of a run's spans: before it the first
    dispatch (30 s, the compile) and a full collection, then ``steps``
    as ``(start_s, train_step_ms, enqueue_ms, in_flight)``, and
    ``extra`` as ``(name, start_s, ms)``."""
    spans, sid = [], iter(range(1, 10_000))

    def span(name, cat, start_s, ms, parent=None, args=None, tid=1):
        event = {"name": name, "cat": cat, "ts": int(start_s * 1e6),
                 "dur": int(ms * 1e3), "tid": tid, "sid": next(sid),
                 "self": int(ms * 1e3)}
        if parent is not None:
            event["parent"] = parent
        if args is not None:
            event["args"] = args
        spans.append(event)
        return event["sid"]

    span("gc/collect", "host", 50.0, 900.0,
         args={"collected": 5, "uncollectable": 0})
    for update, (start_s, step_ms, enqueue_ms, in_flight) in enumerate(
            [(60.0, 30_000.0, 29_990.0, 0)] + list(steps)):
        parent = span("learner/train_step", "learner", start_s, step_ms,
                      args={"update": update})
        if enqueue:
            span("fused/enqueue", "learner", start_s, enqueue_ms,
                 parent=parent, args={
                     "update": update, "in_flight": in_flight,
                     "in_flight_after": in_flight, "majflt": 0,
                     "nivcsw": 0, "nvcsw": 1, "oublock": 0})
    for name, start_s, ms in extra:
        span(name, "host", start_s, ms,
             tid=2 if name == "host/late_wakeup" else 1)
    return types.SimpleNamespace(
        program_spans=spans, t_open=100.0, t_close=145.0, notes=[])


_STEADY = [(100.0 + 1.2 * k, 1160.0 + k, 2.0 + 0.1 * (k % 3), 1)
           for k in range(37)]
_AFTER_A_PUBLISH = [(s, d, e, 0 if k in (8, 17, 26, 35) else f)
                    for k, (s, d, e, f) in enumerate(_STEADY)]
_HELD = list(_AFTER_A_PUBLISH)
_HELD[30] = (136.0, 3580.0, 2420.0, 1)       # the cold run's stall
_HELD[31] = (139.6, 1160.0, 2.0, 0)          # ...and the queue it drained
# The dispatch whose wait closes the window holds the harness's stopping
# of the profiler too: an enqueue of the window, no dispatch time of it.
_CLOSING = (144.5, 12_000.0, 2.1, 1)
_AFTER_A_PUBLISH.append(_CLOSING)
_HELD.append(_CLOSING)


@pytest.mark.parametrize("name, ctx, expected", [
    ("steady", _recorded(_AFTER_A_PUBLISH), {
        "dispatch_longest_over_median.fused": 1196.0 / 1178.0,
        "enqueue_ms.fused": 2.1,
        "starved_dispatch_share.fused": 100.0 * 4 / 38,
        "host_frozen_ms.fused": 0.0, "gc_pause_ms.fused": 0.0}),
    ("held_by_the_collector", _recorded(_HELD, extra=[
        ("gc/collect", 136.1, 2400.0),
        ("host/late_wakeup", 136.11, 2385.0),
        ("host/late_wakeup", 146.0, 70.0)]), {     # after the window
        "dispatch_longest_over_median.fused": 3580.0 / 1177.0,
        "enqueue_ms.fused": 2.1,
        "starved_dispatch_share.fused": 100.0 * 5 / 38,
        "host_frozen_ms.fused": 2385.0, "gc_pause_ms.fused": 2400.0}),
    ("held_by_another_thread", _recorded(_HELD, extra=[
        ("host/late_wakeup", 136.11, 1200.0),
        ("host/late_wakeup", 137.4, 1100.0)]), {
        "dispatch_longest_over_median.fused": 3580.0 / 1177.0,
        "enqueue_ms.fused": 2.1,
        "starved_dispatch_share.fused": 100.0 * 5 / 38,
        "host_frozen_ms.fused": 2300.0, "gc_pause_ms.fused": 0.0}),
    ("the_parents_spans", _recorded(_HELD, enqueue=False),
     dict.fromkeys(DISPATCH_READERS)),
], ids=lambda v: v if isinstance(v, str) else "spans")
def test_the_dispatch_readers_on_a_recorded_span_list(name, ctx, expected):
    assert set(expected) == set(DISPATCH_READERS)
    for reader in DISPATCH_READERS:
        value = _dispatch_reader(reader).read(ctx)
        if expected[reader] is None:
            assert value is None, reader
        else:
            assert value == pytest.approx(expected[reader]), reader
    if name != "the_parents_spans":
        # the window's spans only: the compile's dispatch and the
        # collection before the window are in none of them
        assert any(n.startswith("starved dispatches: ") and
                   n.endswith("of 38 in the window, updates "
                              + str([9, 18, 27, 36] if name == "steady"
                                    else [9, 18, 27, 32, 36]))
                   for n in ctx.notes), ctx.notes
