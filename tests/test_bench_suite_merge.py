"""bench_suite subset runs must MERGE into BENCH_SUITE.json (a partial
run re-running one config must not clobber the other rows), but only when rows are comparable (same device, same smoke flag)."""

import importlib
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts"))


def _rows(vals):
    return [{"config": f"{k}: cfg", "step_ms": v, "pairs_per_sec": 1.0}
            for k, v in vals.items()]


def _read(path):
    with open(path) as f:
        return json.load(f)


def _suite():
    return importlib.import_module("bench_suite")


def test_subset_merges_into_existing(tmp_path):
    mod = _suite()
    path = str(tmp_path / "BENCH_SUITE.json")
    mod.write_results(path, _rows({k: 1.0 for k in "12345"}),
                      "cpu", True, partial=False)
    # re-run only config 3: the other four rows must survive, 3 updates
    mod.write_results(path, _rows({"3": 99.0}), "cpu", True, partial=True)
    out = _read(path)
    assert [r["config"][0] for r in out["results"]] == list("12345")
    assert next(r for r in out["results"]
                if r["config"][0] == "3")["step_ms"] == 99.0
    assert next(r for r in out["results"]
                if r["config"][0] == "1")["step_ms"] == 1.0


def test_full_run_replaces_wholesale(tmp_path):
    mod = _suite()
    path = str(tmp_path / "BENCH_SUITE.json")
    mod.write_results(path, _rows({k: 1.0 for k in "12345"}),
                      "cpu", True, partial=False)
    mod.write_results(path, _rows({"3": 2.0}), "cpu", True, partial=False)
    out = _read(path)
    assert len(out["results"]) == 1  # full run = authoritative


def test_device_change_replaces_not_merges(tmp_path):
    # each comparability guard in isolation: a regression dropping either
    # the device check or the smoke check must fail one of these
    mod = _suite()
    path = str(tmp_path / "BENCH_SUITE.json")
    mod.write_results(path, _rows({k: 1.0 for k in "12345"}),
                      "cpu", True, partial=False)
    # same smoke, different device: no merge
    mod.write_results(path, _rows({"2": 5.0}), "TPU v5 lite", True,
                      partial=True)
    out = _read(path)
    assert out["device"] == "TPU v5 lite"
    assert len(out["results"]) == 1

    mod.write_results(path, _rows({k: 1.0 for k in "12345"}),
                      "cpu", True, partial=False)
    # same device, different smoke: no merge
    mod.write_results(path, _rows({"2": 5.0}), "cpu", False, partial=True)
    out = _read(path)
    assert out["smoke"] is False
    assert len(out["results"]) == 1


def test_unreadable_prior_file_survives(tmp_path):
    mod = _suite()
    path = str(tmp_path / "BENCH_SUITE.json")
    with open(path, "w") as f:
        f.write("{not json")
    mod.write_results(path, _rows({"2": 5.0}), "cpu", True, partial=True)
    assert _read(path)["results"][0]["step_ms"] == 5.0
