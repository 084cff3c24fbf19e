"""Multi-host distribution over DCN.

The reference distributes with the TF1 gRPC runtime: one learner process
hosting a FIFOQueue, N actor processes enqueueing trajectories and
reading parameters over gRPC (reference: experiment.py:497-512,531,
556-562).  The TPU-native replacement is SPMD: every process calls
``jax.distributed.initialize``; the mesh spans all processes' devices;
the learner update is ONE jitted program whose data-axis collectives
ride ICI within a host and DCN across hosts (XLA picks the transport
from the topology); each host's actor pool contributes its local shard
of every global batch via ``jax.make_array_from_process_local_data``
(runtime/learner.py put_trajectory).

Process roles collapse: there is no separate "learner job" — every
process runs actors AND its slice of the learner, the standard JAX
multi-host pattern.  Host-side artifacts (metrics, logs) are written by
process 0 only; checkpoints are written collectively (Orbax handles
multi-host save/restore of global arrays).
"""

import os
import socket
import time
from typing import Optional

import jax

from scalable_agent_tpu.utils import log


def pick_unused_port(host: str = "localhost") -> int:
    """An OS-assigned free TCP port — the coordinator-port allocator
    for launchers that stand fleets up on one machine (the elastic
    supervisor, the multi-process test harness).  The usual bind(0)
    race applies: the port is only *probably* free by the time the
    coordinator binds it, which is why ``initialize_distributed``'s
    retry loop — not this helper — owns robustness."""
    with socket.socket() as sock:
        sock.bind((host, 0))
        return sock.getsockname()[1]

# Backoff shape for the coordinator-connect retry: first retry after
# 0.5s, doubling to a 10s cap — a fleet scheduler routinely starts
# process N seconds before the coordinator's container is reachable.
_INIT_BACKOFF_INITIAL_S = 0.5
_INIT_BACKOFF_CAP_S = 10.0


def _reset_distributed_state():
    """Undo a half-done ``jax.distributed.initialize`` so the retry
    loop can call it again.  jax assigns ``global_state.client`` (and
    process 0's service) BEFORE the blocking ``connect()``, so a failed
    connect leaves state behind and every later initialize raises
    'should only be called once' — without this reset the backoff loop
    could never actually retry."""
    try:
        jax.distributed.shutdown()
        return
    except Exception:
        pass
    try:  # client.shutdown() on a never-connected client may itself
        from jax._src import distributed  # raise: force-clear the state

        distributed.global_state.client = None
        distributed.global_state.service = None
        distributed.global_state.preemption_sync_manager = None
    except Exception:  # pragma: no cover - jax internals moved
        log.warning("could not reset jax.distributed state; the next "
                    "initialize attempt may refuse to run")


def _enable_cpu_gloo_collectives():
    """Point the (not-yet-initialized) CPU backend's cross-process
    collectives at gloo, returning a restore callable.  Restoring
    matters on the init-failed path: gloo demands the distributed
    client that never came up, so a leaked flag would poison every
    later backend init in this process with an unrelated-looking
    ``make_gloo_tcp_collectives`` error."""
    flag, value = "jax_cpu_collectives_implementation", "gloo"
    prev = getattr(jax.config, flag)
    try:
        jax.config.update(flag, value)
    except Exception:
        log.warning("could not enable gloo CPU collectives; "
                    "multi-process CPU collectives may fail")
        return lambda: None

    def restore():
        try:
            jax.config.update(flag, prev)
        except Exception:  # pragma: no cover
            pass

    return restore


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    init_timeout_s: float = 60.0,
) -> bool:
    """Initialize jax.distributed when configured; returns True if the
    job is multi-process.

    Explicit args win; otherwise standard env vars
    (JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / JAX_PROCESS_ID) or a
    TPU-pod auto-detecting environment apply.  A no-config single
    process is left untouched.

    The coordinator is routinely NOT up yet when a scheduler launches
    the fleet: ``jax.distributed.initialize`` is retried with capped
    exponential backoff for up to ``init_timeout_s``
    (``--coordinator_init_timeout_s``), each retry counted in
    ``fleet/init_retries_total``, before the failure is re-raised with
    the attempt history attached.
    """
    coordinator = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS")
    if num_processes is None:
        env = os.environ.get("JAX_NUM_PROCESSES")
        num_processes = int(env) if env else None
    if process_id is None:
        env = os.environ.get("JAX_PROCESS_ID")
        process_id = int(env) if env else None
    if coordinator is None and num_processes is None:
        return jax.process_count() > 1
    platform = (os.environ.get("JAX_PLATFORMS", "")
                or str(getattr(jax.config, "jax_platforms", None) or ""))
    restore_collectives = lambda: None
    if platform.startswith("cpu"):
        # Cross-process collectives on the CPU backend need the gloo
        # transport; without it every multi-process CPU run (the
        # localhost test rig, a CPU smoke of a TPU job) dies at its
        # first psum with "Multiprocess computations aren't
        # implemented".  Checked via config/env, never jax.devices():
        # backend init must stay AFTER jax.distributed.initialize.
        restore_collectives = _enable_cpu_gloo_collectives()
    from scalable_agent_tpu.obs import get_registry

    retries = get_registry().counter(
        "fleet/init_retries_total",
        "jax.distributed.initialize attempts retried while waiting "
        "for the coordinator to come up")
    deadline = time.monotonic() + max(0.0, init_timeout_s)
    delay = _INIT_BACKOFF_INITIAL_S
    attempt = 0
    while True:
        attempt += 1
        try:
            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=num_processes,
                process_id=process_id,
                # Bound jax's own blocking connect so OUR deadline (not
                # its multi-minute default) paces the retry loop.
                initialization_timeout=max(
                    5, int(deadline - time.monotonic()) or 5),
            )
            break
        except Exception as exc:
            _reset_distributed_state()
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                restore_collectives()
                raise RuntimeError(
                    f"coordinator {coordinator} unreachable after "
                    f"{attempt} attempt(s) over "
                    f"{init_timeout_s:.0f}s "
                    f"(--coordinator_init_timeout_s)") from exc
            retries.inc()
            sleep_s = min(delay, remaining)
            log.warning(
                "jax.distributed.initialize attempt %d failed (%s: "
                "%s) — coordinator %s not up yet? retrying in %.1fs "
                "(%.0fs left)", attempt, type(exc).__name__, exc,
                coordinator, sleep_s, remaining)
            time.sleep(sleep_s)
            delay = min(delay * 2, _INIT_BACKOFF_CAP_S)
    log.info("jax.distributed up: process %d/%d, %d local / %d global "
             "devices", jax.process_index(), jax.process_count(),
             jax.local_device_count(), jax.device_count())
    return jax.process_count() > 1


def is_coordinator() -> bool:
    return jax.process_index() == 0


def local_batch_size(global_batch: int) -> int:
    """Per-process share of a batch sharded over all processes."""
    processes = jax.process_count()
    if global_batch % processes:
        raise ValueError(
            f"global batch {global_batch} not divisible by "
            f"{processes} processes")
    return global_batch // processes
