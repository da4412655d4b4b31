"""bench.py failure records: the train bench measures in-process, and a
failure (an exception, or the one overall deadline) leaves a value-0.0
record that says WHICH phase it died in — "backend init never returned"
and "compile too slow" demand different operator responses. The non-zero
exit code is checked end to end in tests/test_chip_smoke.py.
"""

import json

import pytest

import bench


@pytest.fixture(autouse=True)
def _reset_bench_globals():
    bench._emitted = False
    bench._PHASE["name"] = "startup"
    yield
    bench._emitted = False
    bench._PHASE["name"] = "startup"


def test_main_measures_without_emitting(monkeypatch, capsys):
    # shrink the module-default flagship so the path runs at test size
    monkeypatch.setattr(bench, "CROP", 24)
    monkeypatch.setattr(bench, "MSA_DEPTH", 2)
    monkeypatch.setattr(bench, "MSA_LEN", 24)
    monkeypatch.setattr(bench, "DIM", 16)
    monkeypatch.setattr(bench, "DEPTH", 1)
    rec = bench.main(emit=False)
    assert rec["value"] > 0
    assert "crop=24" in rec["metric"] and "dim=16" in rec["metric"]
    # no baseline is committed: nothing to be compared against
    assert rec["vs_baseline_valid"] is False
    # every record names the device it ran on; no utilization on a CPU
    assert rec["platform"] == "cpu" and rec["device_count"] >= 1
    assert "mfu" not in rec and "first_light" not in rec
    assert capsys.readouterr().out == ""  # emit=False: nothing on stdout
    assert not bench._emitted


def test_failure_record_reports_phase(capsys):
    bench._PHASE["name"] = "backend_init"
    bench._emit(bench._failure_record(bench._phase_failure_msg()))
    out = json.loads(capsys.readouterr().out)
    assert out["value"] == 0.0
    assert out["phase"] == "backend_init"
    assert "backend init never returned" in out["error"]
    assert "fallback" not in out  # no smaller config's number stands in


@pytest.mark.parametrize("phase,needle", [
    ("backend_init", "backend init never returned"),
    ("serve:backend_init", "backend init never returned"),
    ("trace_compile", "compile exceeded"),
    ("warmup_run", "too slow"),
    ("timed_run", "too slow"),
    ("startup", "before touching the backend"),
])
def test_phase_failure_messages(phase, needle):
    bench._PHASE["name"] = phase
    msg = bench._phase_failure_msg()
    assert needle in msg and phase in msg
