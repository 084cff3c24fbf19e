"""Multi-host distribution: 2 CPU processes, one SPMD learner/driver.

The reference's distributed mode is localhost multi-process TF jobs
(reference: experiment.py:497-512, README.md:63-69); the equivalent here
is N identical processes with jax.distributed over a shared mesh.  These
tests spawn REAL separate processes (not simulated) on the virtual CPU
backend.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def free_port():
    with socket.socket() as sock:
        sock.bind(("localhost", 0))
        return sock.getsockname()[1]


def spawn(args, devices_per_process=2, extra_env=None):
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS=(f"--xla_force_host_platform_device_count="
                   f"{devices_per_process}"),
        PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
    )
    env.update(extra_env or {})
    return subprocess.Popen(
        [sys.executable] + args, cwd=REPO, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)


@pytest.mark.slow
def test_two_process_learner_dryrun():
    port = free_port()
    procs = [
        spawn(["-m", "scalable_agent_tpu.parallel.dryrun_process",
               f"--coordinator=localhost:{port}",
               "--num_processes=2", f"--process_id={i}",
               "--updates=2"])
        for i in range(2)
    ]
    outs = [proc.communicate(timeout=300)[0] for proc in procs]
    for i, (proc, out) in enumerate(zip(procs, outs)):
        assert proc.returncode == 0, f"proc {i}:\n{out[-3000:]}"
        assert "DRYRUN-MP-OK" in out, out[-3000:]
    # both processes computed the SAME replicated loss
    losses = [out.split("loss=")[1].split(" ")[0] for out in outs]
    assert losses[0] == losses[1], losses


@pytest.mark.slow
def test_two_process_driver_train(tmp_path):
    """Full driver.train across 2 processes: each contributes half of
    every global batch from its own env workers; training reaches the
    frame target and process 0 writes the checkpoint."""
    logdir = tmp_path / "run"
    port = free_port()
    total_frames = 3 * 4 * 3 * 2  # 3 updates x batch 4 x T=3 x repeats 2
    script = (
        "import json, sys\n"
        "import jax\n"
        # Force the virtual-CPU backend the same way conftest does.
        "jax.config.update('jax_platforms', 'cpu')\n"
        "from scalable_agent_tpu.config import Config\n"
        "from scalable_agent_tpu.driver import train\n"
        "metrics = train(Config(\n"
        f"    logdir={str(logdir)!r},\n"
        "    level_name='fake_small',\n"
        "    num_actors=4, batch_size=4, unroll_length=3,\n"
        "    num_action_repeats=2, num_env_workers_per_group=1,\n"
        f"    total_environment_frames={total_frames},\n"
        "    compute_dtype='float32', checkpoint_interval_s=1e9,\n"
        f"    distributed_coordinator='localhost:{port}',\n"
        "    distributed_num_processes=2,\n"
        "    distributed_process_id=int(sys.argv[1])))\n"
        "print('METRICS', json.dumps(metrics))\n"
    )
    procs = [spawn(["-c", script, str(i)]) for i in range(2)]
    outs = [proc.communicate(timeout=600)[0] for proc in procs]
    for i, (proc, out) in enumerate(zip(procs, outs)):
        assert proc.returncode == 0, f"proc {i}:\n{out[-4000:]}"
        assert "METRICS" in out, out[-4000:]
    metrics = json.loads(outs[0].split("METRICS ", 1)[1].splitlines()[0])
    assert metrics["env_frames"] == total_frames
    assert np.isfinite(metrics["total_loss"])
    # the collective checkpoint landed (written by process 0)
    ckpts = os.listdir(logdir / "checkpoints")
    assert any(name.isdigit() for name in ckpts), ckpts
