"""Every examples/ script must actually run — they are the front door for
users switching from the reference package, so they rot loudly here
(EX_TINY=1 shrinks dims; each runs in its own process like a user would)."""

import glob
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = sorted(glob.glob(os.path.join(REPO, "examples", "*.py")))


def test_examples_exist():
    assert len(EXAMPLES) >= 5


@pytest.mark.parametrize("path", EXAMPLES, ids=[os.path.basename(p) for p in EXAMPLES])
def test_example_runs(path):
    env = dict(
        os.environ,
        EX_TINY="1",
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
    )
    proc = subprocess.run(
        [sys.executable, path],
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
        cwd=REPO,
    )
    assert proc.returncode == 0, f"{path} failed:\n{proc.stdout}\n{proc.stderr}"
    assert proc.stdout.strip().endswith("ok"), proc.stdout
