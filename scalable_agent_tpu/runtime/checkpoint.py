"""Checkpoint/resume via Orbax, with integrity verification + fallback.

Replaces the reference's MonitoredTrainingSession auto-checkpointing
(reference: experiment.py:608-616 — all global variables incl. the
env-frame global step, every 600s) and the SF explicit rotation
(reference: algorithms/utils/agent.py:129-193):

- Saves (params, opt_state, env_frames, guard counters) on a wall-clock
  cadence with keep-last-N rotation.
- env_frames rides in the checkpoint so the frame-keyed LR schedule
  resumes exactly (SURVEY §5.4).
- The config JSON snapshot is written separately by Config.save.

Robustness layer (docs/robustness.md):

- Every save also writes a per-leaf crc32 **integrity manifest**
  (``checkpoints/manifests/<step>.json``), and ``restore()`` verifies
  the restored leaves against it.  A torn or corrupt step — a crash
  mid-save, a bad disk — no longer bricks resume: restore **walks back**
  through the retained steps, newest first, until one verifies
  (``checkpoint/restore_fallbacks_total`` counts each rejected step).
- Non-forced ``maybe_save`` failures (disk full, transient Orbax
  errors) degrade to a logged ``checkpoint/save_failures_total``
  instead of killing a training run that is otherwise healthy; only the
  forced final save re-raises.  The multi-process decision broadcast
  and the state allgather happen BEFORE any fallible IO, so a failing
  primary can never strand its peers inside a collective.
- The learner watchdog heartbeat must be suspended by the caller across
  ``restore()``/rollback (the driver does) — a long Orbax read is not a
  wedge; ``restore()`` additionally suspends the calling thread's own
  heartbeat.
"""

import json
import os
import time
import zlib
from typing import Any, List, Optional, Tuple

import jax
import numpy as np
import orbax.checkpoint as ocp

from scalable_agent_tpu.obs import (
    get_flight_recorder,
    get_registry,
    get_tracer,
    get_watchdog,
)
from scalable_agent_tpu.runtime.faults import get_fault_injector
from scalable_agent_tpu.runtime.fleet import get_fleet
from scalable_agent_tpu.runtime.learner import TrainState
from scalable_agent_tpu.utils import log

_MANIFEST_SCHEMA = 1

# TrainState fields a pre-guard checkpoint (before nonfinite_skips/
# nonfinite_streak) was saved with — the legacy-migration restore target.
_LEGACY_FIELDS = ("params", "opt_state", "env_frames")

# TrainState fields a pre-IMPACT checkpoint (before target_params) was
# saved with.  Orbax records even a None field in the tree structure,
# so restores that cross the IMPACT generation boundary IN EITHER
# DIRECTION need a structure retry (see _restore_step):
# - an --loss=impact run resuming a pre-IMPACT (or vtrace) checkpoint
#   retries with target_params=None, and Learner.place_state then
#   initializes the target net from the restored online params;
# - a --loss=vtrace run resuming an --loss=impact checkpoint retries
#   with the online params as the target's shape donor and carries the
#   restored target through untouched (the vtrace update ignores it),
#   so the checkpoint's integrity manifest still verifies leaf-exact.
_PRE_IMPACT_FIELDS = ("params", "opt_state", "env_frames",
                      "nonfinite_skips", "nonfinite_streak")


class CheckpointIntegrityError(RuntimeError):
    """Retained checkpoint steps exist but NONE restored and verified.

    Deliberately loud: silently returning "no checkpoint" here would
    make the driver retrain from step 0 into the same logdir — and
    rotation would then delete the very steps an operator might still
    recover by hand."""


def _to_host(x):
    """Fetch an array to host memory, multi-host safe: non-addressable
    global arrays are allgathered (a collective — every process must
    reach this together)."""
    if hasattr(x, "is_fully_addressable") and not x.is_fully_addressable:
        from jax.experimental import multihost_utils

        return np.asarray(
            multihost_utils.process_allgather(x, tiled=True))
    return np.asarray(x)


def _current_topology() -> dict:
    """The process/device layout of THIS run — stamped into manifests
    so a resumed run can tell it resharded."""
    return {"num_processes": int(jax.process_count()),
            "num_devices": int(jax.device_count())}


def _leaf_checksums(host_state) -> List[dict]:
    """Per-leaf (shape, dtype, crc32) in flatten order — the integrity
    manifest's body.  Flatten order is deterministic for a fixed
    TrainState structure, so index-keyed entries suffice."""
    entries = []
    for leaf in jax.tree_util.tree_leaves(host_state):
        arr = np.ascontiguousarray(np.asarray(leaf))
        entries.append({
            "shape": list(arr.shape),
            "dtype": str(arr.dtype),
            "crc32": zlib.crc32(arr.tobytes()),
        })
    return entries


class CheckpointManager:
    """Cadenced save/restore.  Multi-process discipline: ONLY process 0
    owns an Orbax manager and touches the checkpoint directory; the
    state is allgathered to host collectively before a save, and a
    restore is read by process 0 and broadcast to everyone — so the
    on-disk format is identical to single-host runs and no two
    processes ever race on the same paths."""

    def __init__(self, logdir: str, interval_s: float = 600.0,
                 keep: int = 5):
        self._dir = os.path.join(os.path.abspath(logdir), "checkpoints")
        self._manifest_dir = os.path.join(self._dir, "manifests")
        self._is_primary = jax.process_index() == 0
        self._manager = None
        if self._is_primary:
            os.makedirs(self._dir, exist_ok=True)
            options = ocp.CheckpointManagerOptions(
                max_to_keep=keep, create=True)
            if jax.process_count() > 1:
                # The manager lives ONLY on process 0; restrict orbax's
                # internal barriers to it, or its construction/save
                # collectives would pair up with unrelated collectives
                # on the other processes.
                from orbax.checkpoint import options as ocp_options

                # create=False: with active_processes set, orbax insists
                # the caller makes the root dir (done above).
                options = ocp.CheckpointManagerOptions(
                    max_to_keep=keep, create=False,
                    multiprocessing_options=(
                        ocp_options.MultiprocessingOptions(
                            primary_host=0, active_processes={0})),
                )
            self._manager = ocp.CheckpointManager(self._dir,
                                                  options=options)
        self._interval_s = interval_s
        # The cadence counts from here: the monotonic clock's zero is
        # the host's boot, and a cadence counted from it saves when the
        # HOST turns an interval old, wherever in the run that falls.
        self._last_save = time.monotonic()
        registry = get_registry()
        self._save_failures = registry.counter(
            "checkpoint/save_failures_total",
            "non-forced checkpoint saves that failed and were degraded "
            "to a logged retry-next-cadence")
        self._restore_fallbacks = registry.counter(
            "checkpoint/restore_fallbacks_total",
            "retained checkpoint steps rejected during restore (torn/"
            "corrupt/unreadable) before an older step verified")
        self._restored_step_gauge = registry.gauge(
            "checkpoint/restored_step",
            "step of the last successfully verified restore (-1 = none)")
        self._restored_step_gauge.set(-1.0)

    # -- integrity manifest ------------------------------------------------

    def _manifest_path(self, step: int) -> str:
        return os.path.join(self._manifest_dir, f"{step}.json")

    def _write_manifest(self, step: int, host_state) -> None:
        """Atomic (tmp + rename) per-leaf checksum manifest for one
        saved step, and prune manifests of rotated-out steps.

        The manifest is computed over the HOST-GATHERED (fully
        replicated/global) state, so it is topology-agnostic by
        construction: the same bytes describe the checkpoint whether it
        is later restored onto 1 process or N — ``topology`` records
        the writing layout purely so a resumed run can DETECT a
        reshard and re-verify after placement
        (``verify_after_reshard``)."""
        os.makedirs(self._manifest_dir, exist_ok=True)
        payload = {
            "schema_version": _MANIFEST_SCHEMA,
            "step": step,
            "topology": _current_topology(),
            "leaves": _leaf_checksums(host_state),
        }
        path = self._manifest_path(step)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, path)
        retained = {str(s) for s in self._manager.all_steps()}
        for name in os.listdir(self._manifest_dir):
            stem, ext = os.path.splitext(name)
            if ext == ".json" and stem not in retained and stem != str(step):
                try:
                    os.remove(os.path.join(self._manifest_dir, name))
                except OSError:
                    pass

    def _verify(self, step: int, restored) -> Tuple[bool, str]:
        """Check restored leaves against the step's manifest.  A missing
        manifest (pre-manifest checkpoint) is accepted — integrity
        verification must not reject every checkpoint written before it
        existed."""
        path = self._manifest_path(step)
        if not os.path.exists(path):
            return True, "no manifest (legacy checkpoint, accepted)"
        try:
            manifest = json.load(open(path))
        except (OSError, json.JSONDecodeError) as exc:
            return False, f"unreadable manifest: {exc}"
        expected = manifest.get("leaves", [])
        got = _leaf_checksums(restored)
        if len(expected) != len(got):
            return False, (f"leaf count {len(got)} != manifest "
                           f"{len(expected)}")
        # Multiset comparison: a typed (NamedTuple) restore and a raw
        # target=None restore flatten the same data in different leaf
        # orders (dict keys sort; NamedTuples keep field order) — bit
        # corruption changes a crc, it cannot reorder leaves.
        def key(entry):
            return (tuple(entry["shape"]), entry["dtype"], entry["crc32"])

        missing = sorted(map(key, expected))
        found = sorted(map(key, got))
        if missing != found:
            bad = next((a, b) for a, b in zip(missing, found) if a != b)
            return False, (f"leaf checksum mismatch: manifest {bad[0]!r}"
                           f" vs restored {bad[1]!r}")
        return True, ""

    def _tear_step(self, step: int) -> None:
        """Chaos (``ckpt_torn``): corrupt the just-written step on disk
        — a deterministic stand-in for a crash mid-save.  Inverts a span
        of bytes in the step's largest file, so either Orbax's restore
        raises or the manifest crc catches the change."""
        step_dir = os.path.join(self._dir, str(step))
        largest, size = None, -1
        for root, _, files in os.walk(step_dir):
            for name in files:
                path = os.path.join(root, name)
                nbytes = os.path.getsize(path)
                if nbytes > size:
                    largest, size = path, nbytes
        if largest is None or size <= 0:
            return
        offset = size // 2
        span = min(256, size - offset)
        with open(largest, "r+b") as f:
            f.seek(offset)
            chunk = f.read(span)
            f.seek(offset)
            f.write(bytes(b ^ 0xFF for b in chunk))
        log.warning("chaos: tore checkpoint step %d (%s, %d bytes "
                    "inverted)", step, os.path.basename(largest), span)

    # -- save --------------------------------------------------------------

    def maybe_save(self, step: int, state: TrainState,
                   force: bool = False) -> bool:
        """Save if the cadence interval elapsed.  ``step`` = update index.

        Multi-process: the wall-clock decision is process 0's, broadcast
        so every process enters the collective allgather (or none does)
        — divergent local clocks must never deadlock it.  The allgather
        runs BEFORE the fallible Orbax IO, so a primary-side save
        failure is local to process 0 and degrades (non-forced) to
        ``checkpoint/save_failures_total`` + a retry next cadence; only
        the forced final save re-raises."""
        now = time.monotonic()
        decision = force or now - self._last_save >= self._interval_s
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            # Fleet-guarded (runtime/fleet.py): a peer lost inside the
            # decision broadcast or the allgather below is attributed
            # and bounded (exit 72) instead of hanging every survivor.
            with get_fleet().collective("ckpt_save_decision"):
                decision = bool(multihost_utils.broadcast_one_to_all(
                    np.asarray(decision)))
        if not decision:
            return False
        registry = get_registry()
        injector = get_fault_injector()
        with get_tracer().span("checkpoint/save", cat="checkpoint"), \
                registry.histogram(
                    "checkpoint/save_s",
                    "state fetch + orbax write seconds").time():
            # Collective state fetch FIRST (every process participates,
            # nothing here may fail on only one of them)...
            with get_fleet().collective("ckpt_save_allgather"):
                host_state = jax.tree_util.tree_map(_to_host, state)
            # ...then the primary-only, fallible IO.
            try:
                if injector.active:
                    injector.maybe_raise("ckpt_save_fail")
                if self._manager is not None:
                    self._manager.save(
                        step, args=ocp.args.StandardSave(host_state))
                    if jax.process_count() > 1:
                        # Complete the write before any peer can race
                        # ahead to process exit — a departing peer tears
                        # down the coordination service and cancels
                        # in-flight async writes on the primary.
                        self._manager.wait_until_finished()
                    self._write_manifest(step, host_state)
            except Exception as exc:
                if force:
                    # The final save is the run's durable result — a
                    # silent degrade here would lose it.
                    raise
                self._save_failures.inc()
                get_flight_recorder().record(
                    "ckpt_save_failure", type(exc).__name__,
                    {"step": step})
                log.error(
                    "checkpoint save at step %d failed (%s: %s) — "
                    "training continues, retry next cadence",
                    step, type(exc).__name__, exc)
                # Back off a full interval: a disk-full loop must not
                # turn every update into a failed save attempt.
                self._last_save = now
                return False
            if (self._manager is not None and injector.active
                    and injector.should_fire("ckpt_torn")):
                self._manager.wait_until_finished()
                self._tear_step(step)
        registry.counter("checkpoint/saves_total",
                         "checkpoints written").inc()
        self._last_save = now
        return True

    # -- restore -----------------------------------------------------------

    def _restore_step(self, step: int, host_target):
        # Always pass explicit StandardRestore args: a FRESH manager
        # over an existing directory has no handler registered for the
        # 'default' item until a save runs, so a bare restore(step)
        # raises — exactly the resume-after-crash situation.
        try:
            return self._manager.restore(
                step, args=ocp.args.StandardRestore(host_target))
        except Exception:
            if host_target is None or not isinstance(host_target,
                                                     TrainState):
                raise
            # IMPACT-generation migration (loss-mode crossing, either
            # direction).  A structure mismatch here fails fast in
            # orbax's key validation, before the array reads — so a
            # genuinely torn step pays at most one wasted retry and
            # the walk-back still proceeds.
            if host_target.target_params is not None:
                # impact run <- pre-IMPACT/vtrace checkpoint: restore
                # the narrower structure; the target net is
                # initialized from the online params AFTER manifest
                # verification (Learner.place_state).
                try:
                    restored = self._manager.restore(
                        step, args=ocp.args.StandardRestore(
                            host_target._replace(target_params=None)))
                    log.warning(
                        "checkpoint step %d predates the IMPACT "
                        "target network; target params will be "
                        "initialized from the restored online params",
                        step)
                    return restored
                except Exception:
                    pass
            else:
                # vtrace run <- impact checkpoint: the online params
                # donate the target subtree's structure; the restored
                # target rides along untouched so the per-leaf CRC
                # manifest still verifies the full checkpoint.
                try:
                    restored = self._manager.restore(
                        step, args=ocp.args.StandardRestore(
                            host_target._replace(
                                target_params=host_target.params)))
                    log.warning(
                        "checkpoint step %d carries an IMPACT target "
                        "network; restored under --loss=vtrace it is "
                        "carried through unused", step)
                    return restored
                except Exception:
                    pass
            # Pre-PR trees: a checkpoint written before target_params
            # existed AT ALL has no entry for it (not even a None
            # placeholder), so both 6-field retries above mismatch —
            # restore the plain 5-field structure and let the default
            # None widen it.
            try:
                restored = self._manager.restore(
                    step, args=ocp.args.StandardRestore(
                        {name: getattr(host_target, name)
                         for name in _PRE_IMPACT_FIELDS}))
                log.warning(
                    "checkpoint step %d restored via the pre-IMPACT "
                    "5-field structure", step)
                return TrainState(**restored)
            except Exception:
                pass
            # Legacy migration: checkpoints written before the guard
            # counters existed carry a 3-field TrainState; a structure
            # mismatch against the widened target must not read as
            # "torn" (that would walk past EVERY old step and silently
            # retrain from scratch).  Retry with the legacy structure
            # and zero-fill the new counters; a genuinely torn step
            # makes this retry raise too, and the walk-back proceeds.
            # Gated on manifest ABSENCE: pre-guard checkpoints predate
            # the manifests, while a torn post-guard step has one — so
            # the walk-back never pays a doubled full read per rejected
            # modern step.
            if os.path.exists(self._manifest_path(step)):
                raise
            legacy_target = {name: getattr(host_target, name)
                             for name in _LEGACY_FIELDS}
            restored = self._manager.restore(
                step, args=ocp.args.StandardRestore(legacy_target))
            log.warning(
                "checkpoint step %d restored via the legacy pre-guard "
                "structure; nonfinite counters start at zero", step)
            return TrainState(
                params=restored["params"],
                opt_state=restored["opt_state"],
                env_frames=restored["env_frames"],
                nonfinite_skips=np.float32(0.0),
                nonfinite_streak=np.float32(0.0),
            )

    def _note_bad_step(self, step: int, why: str) -> None:
        self._restore_fallbacks.inc()
        get_flight_recorder().record(
            "ckpt_fallback", str(step), {"why": why[:200]})
        log.error(
            "checkpoint step %d failed integrity/restore (%s) — "
            "falling back to the next older retained step", step, why)

    def _walk_back(self, host_target) -> Optional[Tuple[int, Any]]:
        """Try retained steps newest-first until one restores AND
        verifies; None when every retained step is bad."""
        rejected: List[int] = []
        for step in sorted(self._manager.all_steps(), reverse=True):
            try:
                restored = self._restore_step(step, host_target)
            except Exception as exc:  # torn files make orbax raise
                self._note_bad_step(
                    step, f"{type(exc).__name__}: {exc}")
                rejected.append(step)
                continue
            ok, why = self._verify(step, restored)
            if not ok:
                self._note_bad_step(step, why)
                rejected.append(step)
                continue
            # Delete the NEWER, proven-bad steps now that a good older
            # one exists: a torn step left as latest_step would make
            # Orbax silently skip (save() returns False) every coming
            # save at a step <= it — including the resumed run's final
            # forced save — while the manifests got rewritten for data
            # never on disk.  Only deleted on a successful walk-back;
            # the nothing-verified path keeps everything for the
            # operator.
            for bad in rejected:
                try:
                    self._manager.delete(bad)
                    log.warning(
                        "deleted corrupt checkpoint step %d (newer "
                        "than the verified step %d it would shadow)",
                        bad, step)
                except Exception:
                    log.exception(
                        "could not delete corrupt checkpoint step %d",
                        bad)
            self._restored_step_gauge.set(float(step))
            return step, restored
        return None

    def restore(self, target: Optional[Any] = None
                ) -> Optional[Tuple[int, Any]]:
        """Newest VERIFIED (step, host-side TrainState pytree), or None.

        ``target``: a structure-matching pytree (e.g. a freshly
        initialized TrainState) — required to restore custom NamedTuple
        nodes like optax optimizer states with their original types.

        Walks back through retained steps when the latest is torn or
        corrupt (crash mid-save), so a bad newest step degrades resume
        by one cadence interval instead of bricking it.  Callers that
        own a named watchdog heartbeat (the driver's ``learner``) must
        suspend it around this call — a long Orbax read is not a wedge;
        the calling thread's own heartbeat is suspended here."""
        get_watchdog().suspend()
        multiprocess = jax.process_count() > 1
        if not multiprocess:
            if not self._manager.all_steps():
                return None
            host_target = (None if target is None else
                           jax.tree_util.tree_map(_to_host, target))
            found = self._walk_back(host_target)
            if found is None:
                raise CheckpointIntegrityError(
                    f"checkpoints exist under {self._dir} but none "
                    f"restored and verified — refusing to silently "
                    f"retrain from scratch (move or delete the "
                    f"directory to start fresh)")
            return found

        from jax.experimental import multihost_utils

        # Every collective below rides the fleet guard: a peer that
        # died between init and restore would otherwise hang the whole
        # fleet at its very first cross-process point.
        fleet = get_fleet()
        has_any = (bool(self._manager.all_steps())
                   if self._is_primary else False)
        with fleet.collective("ckpt_restore_has_any"):
            has_any = bool(multihost_utils.broadcast_one_to_all(
                np.asarray(has_any)))
        if not has_any:
            return None
        if target is None:
            raise ValueError(
                "multi-process restore requires a structure target "
                "(the broadcast needs a pytree shape donor)")
        # Collective (_to_host allgathers) — only pay it once a
        # checkpoint actually exists; every process reaches it together,
        # BEFORE the primary's fallible walk-back.
        with fleet.collective("ckpt_restore_allgather"):
            host_target = jax.tree_util.tree_map(_to_host, target)
        found = self._walk_back(host_target) if self._is_primary else None
        with fleet.collective("ckpt_restore_step_broadcast"):
            step = int(multihost_utils.broadcast_one_to_all(
                np.asarray(-1 if found is None else found[0])))
        if step < 0:
            # has_any was True, so a negative step can only mean the
            # primary's walk-back rejected every retained step — raise
            # on EVERY process (the broadcast keeps them in lock-step).
            raise CheckpointIntegrityError(
                f"checkpoints exist under {self._dir} but none "
                f"restored and verified — refusing to silently retrain "
                f"from scratch (move or delete the directory to start "
                f"fresh)")
        restored = found[1] if self._is_primary else host_target
        with fleet.collective("ckpt_restore_state_broadcast"):
            restored = multihost_utils.broadcast_one_to_all(restored)
        return step, restored

    def saved_topology(self, step: int) -> Optional[dict]:
        """The ``{"num_processes", "num_devices"}`` layout that wrote
        ``step``'s manifest; None for legacy/absent manifests.  A disk
        read — in multi-process runs only the primary's answer is
        authoritative (``verify_after_reshard`` broadcasts the
        decision)."""
        try:
            manifest = json.load(open(self._manifest_path(step)))
        except (OSError, json.JSONDecodeError):
            return None
        return manifest.get("topology")

    def verify_after_reshard(self, step: int, placed_state,
                             force: bool = False) -> bool:
        """Re-verify per-leaf CRCs AFTER a restored state was committed
        onto THIS run's mesh, iff the checkpoint was written by a
        DIFFERENT process/device layout (elastic reshard, ISSUE 6).

        The on-disk format is host-gathered and fully replicated, so a
        reshard is value-preserving by construction — this check proves
        it held end-to-end (restore broadcast + ``place_state``
        resharding included) by gathering the PLACED state back to host
        and comparing it against the step's manifest.  Topology
        unchanged (or unknown/legacy manifest) is a no-op returning
        False; a verified reshard returns True; a mismatch raises
        ``CheckpointIntegrityError`` on every process.

        Collective in multi-process runs (the gather allgathers and the
        decision/verdict are broadcast) — every process must call it
        together, which the driver's restore path guarantees.
        ``force=True`` verifies regardless of the recorded topology
        (same value on every process) — the audit knob, and how the
        single-process reshard tests exercise the machinery on a rig
        whose global device count never changes."""
        current = _current_topology()
        fleet = get_fleet()
        saved = None
        why = ""
        if jax.process_count() <= 1:
            saved = self.saved_topology(step)
            if not (force or (saved and saved != current)):
                return False
            ok, why = self._verify(
                step, jax.tree_util.tree_map(_to_host, placed_state))
        else:
            from jax.experimental import multihost_utils

            if self._is_primary:
                saved = self.saved_topology(step)
            resharded = force or (bool(saved) and saved != current)
            with fleet.collective("ckpt_reshard_decision"):
                resharded = bool(multihost_utils.broadcast_one_to_all(
                    np.asarray(resharded)))
            if not resharded:
                return False
            with fleet.collective("ckpt_reshard_allgather"):
                host_state = jax.tree_util.tree_map(
                    _to_host, placed_state)
            ok = True
            if self._is_primary:
                ok, why = self._verify(step, host_state)
            with fleet.collective("ckpt_reshard_verdict"):
                ok = bool(multihost_utils.broadcast_one_to_all(
                    np.asarray(ok)))
        if not ok:
            raise CheckpointIntegrityError(
                f"checkpoint step {step} failed per-leaf CRC "
                f"verification after resharding onto {current} "
                f"(saved at {saved}): {why or 'see the primary log'}")
        get_registry().counter(
            "checkpoint/reshard_verifications_total",
            "restores that crossed a process/device-layout change and "
            "re-verified their manifest after resharding").inc()
        get_flight_recorder().record(
            "ckpt_reshard", str(step),
            {"saved": saved, "current": current})
        log.info(
            "checkpoint step %d restored across a topology change "
            "(%s -> %s); per-leaf CRCs re-verified after reshard",
            step, saved, current)
        return True

    def latest_verified_step(self) -> Optional[int]:
        """The newest retained step (no verification — cheap metadata
        peek for tests/tools); None when the directory is empty."""
        if self._manager is None:
            return None
        steps = self._manager.all_steps()
        return max(steps) if steps else None

    def wait(self):
        if self._manager is not None:
            self._manager.wait_until_finished()

    def close(self):
        if self._manager is not None:
            self._manager.wait_until_finished()
            self._manager.close()
