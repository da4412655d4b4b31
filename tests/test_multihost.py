"""REAL multi-process pod test: two OS processes, each owning 4 virtual CPU
devices, bootstrap one 8-device pod via ``jax.distributed`` and run a
sharded training step on a globally-assembled batch. This exercises the
actual DCN-path code (process init, cross-process mesh,
``make_array_from_process_local_data``, collective gradient psum) that the
single-process suite can only emulate — and that the reference has no
analogue of at all (SURVEY.md S2.3)."""

import os
import socket
import subprocess
import sys
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.slow
def test_two_process_pod_step():
    port = _free_port()
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS")
    }
    procs = [
        subprocess.Popen(
            [sys.executable, str(REPO / "tests" / "_multihost_child.py"),
             str(pid), str(port)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env, cwd=str(REPO),
        )
        for pid in (0, 1)
    ]
    try:
        # drain both children concurrently: sequential communicate() could
        # deadlock if the not-yet-read child fills its pipe buffer while
        # the other blocks on a collective
        with ThreadPoolExecutor(2) as pool:
            results = list(
                pool.map(lambda p: p.communicate(timeout=540), procs)
            )
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for p, (out, err) in zip(procs, results):
        if "Multiprocess computations aren't implemented on the CPU backend" in err:
            pytest.skip(
                "environment gate: this jax build's CPU backend has no "
                "cross-process collectives (XlaRuntimeError: Multiprocess "
                "computations aren't implemented on the CPU backend)"
            )
        assert p.returncode == 0, (out[-500:], err[-2000:])

    losses = {}
    for out, _err in results:
        for line in out.splitlines():
            if line.startswith("RANK"):
                _, rank, _, loss, _, gnorm = line.split()
                losses[int(rank)] = (float(loss), float(gnorm))
    assert set(losses) == {0, 1}, results
    # both ranks computed the SAME global step: loss and grad norm agree
    assert losses[0] == losses[1], losses
