"""Multi-process correctness harness: spawn N real controller processes on
localhost over jax.distributed (CPU backend, one device each) + the native
TCPStore, and assert eager collective parity and DP train-step parity.

Reference analog: the spawn-on-localhost harness
test/legacy_test/test_parallel_dygraph_dataparallel.py:161
(start_local_trainers) driving per-rank bodies with NCCL over TCP rendezvous.
"""

import json
import os
import socket
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _spawn_world(world, timeout=300):
    coord, store = _free_port(), _free_port()
    procs = []
    for rank in range(world):
        env = {
            "PYTHONPATH": REPO,
            "PATH": os.environ.get("PATH", ""),
            "HOME": os.environ.get("HOME", "/root"),
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
            "JAX_COORDINATOR_ADDRESS": f"127.0.0.1:{coord}",
            "PADDLE_MASTER": f"127.0.0.1:{store}",
            "WORLD_SIZE": str(world),
            "RANK": str(rank),
        }
        procs.append(subprocess.Popen(
            [sys.executable, "-u", os.path.join(REPO, "tests", "multiproc_worker.py")],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True))
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return procs, outs


@pytest.mark.parametrize("world", [2, 4])
def test_multiprocess_collectives_and_dp_parity(world):
    procs, outs = _spawn_world(world)
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"rank failed:\n{out[-4000:]}"
    # every rank converged on the same loss trajectory
    losses = {}
    for out in outs:
        line = [l for l in out.splitlines() if l.startswith("{")][-1]
        rec = json.loads(line)
        losses[rec["rank"]] = rec["losses"]
    assert set(losses) == set(range(world))
    ref = losses[0]
    for r in range(1, world):
        assert losses[r] == pytest.approx(ref, rel=1e-5)
