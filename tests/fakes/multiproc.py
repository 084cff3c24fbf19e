"""Reusable N-real-subprocess ``jax.distributed`` harness (ISSUE 5).

The fleet fault-domain layer can only be proven against REAL processes
— a thread can't be SIGKILL'd, and a mocked KV store can't lose its
coordinator — so the heartbeat tests and the multi-process soaks
(tests/test_fleet_multiproc.py, marker ``multiproc``) all spawn actual
interpreters running ``jax.distributed`` over localhost CPU.  This
module is the one copy of that machinery:

- ``FleetHarness(n)``: allocates a coordinator port and spawns ``n``
  processes — either ``spawn_script`` (a ``python -c`` body templated
  with ``{port}``/``{proc}``/``{n}``) or ``spawn_driver`` (the real
  ``scalable_agent_tpu.driver`` CLI with the distributed flags added).
  Per-process env/args overrides let a chaos spec arm a fault on
  exactly one peer.
- ``kill(i)`` / ``terminate(i)``: SIGKILL / SIGTERM one peer.
- ``wait_all(timeout)``: collect ``(returncode, output)`` per process,
  killing stragglers at the deadline so a hung assertion can't hang
  the suite.

Import pattern (tests/fakes has no package ``__init__``; the insert
must be SCOPED — this directory also holds fake simulator modules that
would shadow the real ones for any later ``find_spec``)::

    sys.path.insert(0, FAKES_DIR)
    try:
        import multiproc
    finally:
        sys.path.remove(FAKES_DIR)
"""

import os
import signal
import socket
import subprocess
import sys
import time
from typing import Dict, List, Optional, Sequence, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def free_port() -> int:
    with socket.socket() as sock:
        sock.bind(("localhost", 0))
        return sock.getsockname()[1]


def base_env(devices_per_process: int = 1,
             extra: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """CPU-pinned subprocess environment (same forcing as conftest.py:
    the device-count flag must be set before backend init)."""
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS=(f"--xla_force_host_platform_device_count="
                   f"{devices_per_process}"),
        PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
    )
    env.update(extra or {})
    return env


class FleetHarness:
    """N real ``jax.distributed`` subprocesses sharing one coordinator.

    Context-manager: exit kills every still-running process, so a
    failing assertion can never leak interpreters into the test
    session."""

    def __init__(self, n: int, devices_per_process: int = 1):
        self.n = n
        self.port = free_port()
        self.devices_per_process = devices_per_process
        self.procs: List[subprocess.Popen] = []

    # -- spawning ----------------------------------------------------------

    def spawn_script(self, body: str,
                     extra_env: Optional[Dict[str, str]] = None,
                     per_proc_env: Optional[Sequence[Optional[dict]]]
                     = None) -> "FleetHarness":
        """Launch ``python -c body`` once per process; ``body`` is
        ``str.format``-ed with ``port``/``proc``/``n``."""
        for proc_id in range(self.n):
            env = base_env(self.devices_per_process, extra_env)
            if per_proc_env and per_proc_env[proc_id]:
                env.update(per_proc_env[proc_id])
            self.procs.append(subprocess.Popen(
                [sys.executable, "-c",
                 body.format(port=self.port, proc=proc_id, n=self.n)],
                cwd=REPO, env=env, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True))
        return self

    def spawn_driver(self, logdir: str, common_args: Sequence[str],
                     per_proc_args: Optional[Sequence[Sequence[str]]]
                     = None) -> "FleetHarness":
        """Launch the real driver CLI once per process with the
        distributed flags appended; ``per_proc_args[i]`` (e.g. a chaos
        spec) rides on exactly process i."""
        for proc_id in range(self.n):
            args = [
                sys.executable, "-m", "scalable_agent_tpu.driver",
                "--logdir", logdir,
                f"--distributed_coordinator=localhost:{self.port}",
                f"--distributed_num_processes={self.n}",
                f"--distributed_process_id={proc_id}",
            ] + list(common_args)
            if per_proc_args and per_proc_args[proc_id]:
                args += list(per_proc_args[proc_id])
            self.procs.append(subprocess.Popen(
                args, cwd=REPO, env=base_env(self.devices_per_process),
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True))
        return self

    # -- faults ------------------------------------------------------------

    def kill(self, index: int):
        """SIGKILL peer ``index`` — no handler, no flush, no goodbye."""
        self.procs[index].kill()

    def terminate(self, index: int):
        """SIGTERM peer ``index`` — the preemption-grace entry point."""
        self.procs[index].send_signal(signal.SIGTERM)

    # -- collection --------------------------------------------------------

    def wait_all(self, timeout_s: float) -> List[Tuple[int, str]]:
        """(returncode, combined output) per process, in spawn order.
        Stragglers past the shared deadline are SIGKILLed and reported
        with returncode -9 — the caller's assertion then names them."""
        deadline = time.monotonic() + timeout_s
        results: List[Optional[Tuple[int, str]]] = [None] * self.n
        for index, proc in enumerate(self.procs):
            remaining = max(0.1, deadline - time.monotonic())
            try:
                out = proc.communicate(timeout=remaining)[0]
            except subprocess.TimeoutExpired:
                proc.kill()
                out = proc.communicate(timeout=30)[0]
            results[index] = (proc.returncode, out or "")
        return results  # type: ignore[return-value]

    def wait_one(self, index: int, timeout_s: float) -> Tuple[int, str]:
        proc = self.procs[index]
        out = proc.communicate(timeout=timeout_s)[0]
        return proc.returncode, out or ""

    def poll(self, index: int) -> Optional[int]:
        return self.procs[index].poll()

    def __enter__(self) -> "FleetHarness":
        return self

    def __exit__(self, *exc):
        for proc in self.procs:
            if proc.poll() is None:
                proc.kill()
        for proc in self.procs:
            if proc.poll() is None:
                try:
                    proc.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    pass
        return False
