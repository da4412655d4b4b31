"""Flight recorder tests (observe/flightrec.py): environment scrubbing,
bounded rings, once-per-reason dump semantics, SIGTERM dump."""

import glob
import json
import os
import signal
import subprocess
import sys

import pytest

from alphafold2_tpu.observe.flightrec import (
    REDACTED,
    FlightRecorder,
    install,
    install_signal_handler,
    maybe_install_from_env,
    scrub_env,
)
from alphafold2_tpu.observe.tracing import Tracer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _isolate_singleton():
    from alphafold2_tpu.observe import flightrec

    saved = flightrec._ACTIVE["recorder"]
    flightrec._ACTIVE["recorder"] = None
    yield
    flightrec._ACTIVE["recorder"] = saved


# ------------------------------------------------------------------ scrub


def test_scrub_env_redacts_secrets():
    env = {
        "MY_API_KEY": "hunter2",
        "SOME_TOKEN": "abc",
        "DB_PASSWORD": "pw",
        "AUTH_HEADER": "Bearer x",
        "JAX_PLATFORMS": "cpu",
        "PATH": "/usr/bin",
    }
    out = scrub_env(env)
    assert out["MY_API_KEY"] == REDACTED
    assert out["SOME_TOKEN"] == REDACTED
    assert out["DB_PASSWORD"] == REDACTED
    assert out["AUTH_HEADER"] == REDACTED
    assert out["JAX_PLATFORMS"] == "cpu"  # non-secrets pass through
    assert out["PATH"] == "/usr/bin"
    assert list(out) == sorted(out)  # deterministic ordering


# ------------------------------------------------------------------- rings


def test_dump_contains_rings_and_is_once_per_reason(tmp_path):
    rec = FlightRecorder(directory=str(tmp_path), capacity=32)
    tracer = Tracer(enabled=True)
    rec.attach(tracer)
    for i in range(50):  # more than capacity: ring keeps the newest
        tracer.instant(f"ev{i}")
    rec.note("dispatch_error", bucket=16, error="boom")
    rec.snapshot("registry", {"sched.admitted": 3})

    path = rec.dump("test_reason", extra={"detail": 7})
    assert path and os.path.exists(path)
    doc = json.load(open(path))
    assert doc["reason"] == "test_reason" and doc["extra"]["detail"] == 7
    assert doc["pid"] == os.getpid()
    names = [e["name"] for e in doc["events"]]
    assert len(names) == 32 and names[-1] == "ev49"  # bounded, newest kept
    assert doc["notes"][0]["kind"] == "dispatch_error"
    assert doc["metric_snapshots"][0]["data"] == {"sched.admitted": 3}

    # second dump for the same reason is suppressed; force overrides
    assert rec.dump("test_reason") is None
    assert rec.dump("test_reason", force=True) is not None
    assert rec.dump("other_reason") is not None


def test_dump_without_directory_is_a_noop():
    rec = FlightRecorder(directory=None)
    if not os.environ.get("AF2TPU_FLIGHTREC_DIR"):
        assert rec.dump("x") is None


def test_maybe_install_from_env(tmp_path, monkeypatch):
    monkeypatch.delenv("AF2TPU_FLIGHTREC_DIR", raising=False)
    assert maybe_install_from_env() is None
    monkeypatch.setenv("AF2TPU_FLIGHTREC_DIR", str(tmp_path))
    rec = maybe_install_from_env()
    assert rec is not None and rec.directory == str(tmp_path)
    assert maybe_install_from_env() is rec  # idempotent


# ----------------------------------------------------------------- signals


def test_sigterm_dump_in_subprocess(tmp_path):
    """The installed handler dumps on SIGTERM and the process still dies
    BY the signal (default semantics restored and re-raised)."""
    code = (
        "import os, signal, time\n"
        "from alphafold2_tpu.observe.flightrec import ("
        "FlightRecorder, install_signal_handler)\n"
        f"rec = FlightRecorder(directory={str(tmp_path)!r})\n"
        "rec.note('alive')\n"
        "install_signal_handler(rec)\n"
        "os.kill(os.getpid(), signal.SIGTERM)\n"
        "time.sleep(5)\n"  # never reached: the re-raise kills us
    )
    r = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=60,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert r.returncode == -signal.SIGTERM, (r.returncode, r.stderr[-500:])
    dumps = glob.glob(str(tmp_path / "incident_sigterm_*.json"))
    assert len(dumps) == 1
    doc = json.load(open(dumps[0]))
    assert doc["notes"][0]["kind"] == "alive"
    assert doc["notes"][-1]["kind"] == "signal"


def test_install_signal_handler_off_main_thread_is_noop():
    import threading

    rec = FlightRecorder(directory=None)
    done = []
    t = threading.Thread(
        target=lambda: (install_signal_handler(rec), done.append(1))
    )
    t.start()
    t.join()
    assert done == [1]  # swallowed the ValueError, did not crash
