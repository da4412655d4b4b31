"""Perf regression gate tests: observe.regress verdicts (pass / regress /
invalid-record / missing-baseline), the scripts/bench_compare.py CLI,
the unified observe.flops accounting, and obs_report's train summary."""

import importlib
import json
import os
import sys

import pytest

from alphafold2_tpu.observe import regress

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BASE = {
    "metric": "serve residues/sec tiny", "device": "cpu", "mode": "serve",
    "value": 100.0, "p50_ms": 10.0, "p95_ms": 20.0, "mfu": 0.2,
}


# ------------------------------------------------------------- regress core


def test_compare_pass():
    v = regress.compare({**BASE, "value": 95.0, "p95_ms": 21.0}, BASE)
    assert v["verdict"] == "pass"
    assert {"value", "p50_ms", "p95_ms", "mfu"} <= {
        c["name"] for c in v["comparisons"]
    }
    assert v["regressions"] == []


def test_compare_regress_value_and_latency():
    v = regress.compare({**BASE, "value": 50.0}, BASE)
    assert v["verdict"] == "regress" and v["regressions"] == ["value"]
    v = regress.compare({**BASE, "p95_ms": 200.0}, BASE)
    assert v["verdict"] == "regress" and v["regressions"] == ["p95_ms"]


def test_compare_invalid_records():
    err = {"metric": BASE["metric"], "value": 0.0,
           "error": "deadline 1500s exceeded during phase 'backend_init'",
           "phase": "backend_init"}
    v = regress.compare(err, BASE)
    assert v["verdict"] == "no-data"
    assert "current record invalid" in v["reason"]
    for marker in ({"implausible": True}, {"clock_suspect": True},
                   {"liveness": "dead"}):
        assert regress.compare({**BASE, **marker}, BASE)["verdict"] == "no-data"
    # the committed withdrawn train baseline's shape (value null + invalid)
    withdrawn = {"metric": "m", "value": None, "invalid": "withdrawn: ..."}
    v = regress.compare({"metric": "m", "value": 5.0}, withdrawn)
    assert v["verdict"] == "no-data"
    assert "baseline record invalid" in v["reason"]


def test_compare_is_device_and_methodology_keyed():
    v = regress.compare({**BASE, "device": "TPU v5 lite"}, BASE)
    assert v["verdict"] == "no-data" and "device" in v["reason"]
    v = regress.compare({**BASE, "metric": "other"}, BASE)
    assert v["verdict"] == "no-data" and "metric label" in v["reason"]
    v = regress.compare({**BASE, "ingraph": 4}, {**BASE, "ingraph": 8})
    assert v["verdict"] == "no-data" and "ingraph" in v["reason"]
    assert regress.compare(BASE, None)["verdict"] == "no-data"


def test_threshold_overrides():
    th = regress.parse_threshold_overrides(["value=0.6", "p95_ms=lower:2.0"])
    assert th["value"] == ("higher", 0.6)
    assert th["p95_ms"] == ("lower", 2.0)
    assert regress.compare({**BASE, "value": 50.0}, BASE, th)["verdict"] == "pass"
    with pytest.raises(ValueError):
        regress.parse_threshold_overrides(["value"])
    with pytest.raises(ValueError):
        regress.parse_threshold_overrides(["value=sideways:0.5"])


# ------------------------------------------------------------------ the CLI


@pytest.fixture()
def bench_compare(monkeypatch):
    monkeypatch.syspath_prepend(os.path.join(REPO, "scripts"))
    sys.modules.pop("bench_compare", None)
    yield importlib.import_module("bench_compare")
    sys.modules.pop("bench_compare", None)


def _write(tmp_path, name, rec):
    p = tmp_path / name
    p.write_text(json.dumps(rec))
    return str(p)


def test_cli_pass_and_regress(bench_compare, tmp_path, capsys):
    cur = _write(tmp_path, "cur.json", {**BASE, "value": 95.0})
    base = _write(tmp_path, "base.json", BASE)
    assert bench_compare.main([cur, "--baseline", base]) == 0
    assert json.loads(capsys.readouterr().out)["verdict"] == "pass"

    cur = _write(tmp_path, "cur2.json", {**BASE, "value": 10.0})
    assert bench_compare.main([cur, "--baseline", base]) == 1
    captured = capsys.readouterr()
    assert json.loads(captured.out)["verdict"] == "regress"
    assert "REGRESSION" in captured.err


def test_cli_missing_baseline_and_bad_input(bench_compare, tmp_path, capsys):
    cur = _write(tmp_path, "cur.json", BASE)
    missing = str(tmp_path / "nope.json")
    assert bench_compare.main([cur, "--baseline", missing]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["verdict"] == "no-data" and "missing baseline" in out["reason"]

    bad = tmp_path / "bad.json"
    bad.write_text("not json at all")
    assert bench_compare.main([str(bad), "--baseline", missing]) == 2


def test_cli_invalid_bench_record_verdict(bench_compare, tmp_path, capsys):
    # the exact shape the bench watchdog emits (cf. BENCH_r05.json)
    rec = {"metric": "residue-pairs/sec/chip crop=256 ...", "value": 0.0,
           "unit": "pairs/sec", "vs_baseline": 0.0,
           "vs_baseline_valid": False,
           "error": "deadline 1500s exceeded during phase "
                    "'first_light:backend_init'",
           "phase": "first_light:backend_init"}
    cur = _write(tmp_path, "cur.json", rec)
    base = _write(tmp_path, "base.json", BASE)
    assert bench_compare.main([cur, "--baseline", base]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["verdict"] == "no-data" and "invalid" in out["reason"]


def test_cli_default_baseline_routing(bench_compare):
    assert bench_compare.default_baseline_path({"mode": "serve"}).endswith(
        "bench_serve_baseline.json"
    )
    assert bench_compare.default_baseline_path(
        {"mode": "serve-async"}
    ).endswith("bench_serve_async_baseline.json")
    assert bench_compare.default_baseline_path({}).endswith(
        "bench_baseline.json"
    )


# ------------------------------------------------- dtype / kernel keying

KERNELS_BASE = {
    "metric": "kernels fused-vs-stock speedup axial=... tied=... iters=5",
    "device": "cpu", "mode": "kernels",
    "value": 0.5, "fused_ms_total": 15.0, "stock_ms_total": 8.0,
    "interpret": True,
}


def test_kernels_threshold_selection_and_cliff():
    """--mode kernels records select KERNELS_THRESHOLDS: the geomean
    speedup is gated at 0.5x (an interpret-path blowup or a silent
    fall-back-to-dense halves it), timings at wide cross-machine
    tolerance."""
    assert regress.thresholds_for(KERNELS_BASE) is regress.KERNELS_THRESHOLDS
    ok = regress.compare({**KERNELS_BASE, "value": 0.3}, KERNELS_BASE)
    assert ok["verdict"] == "pass"  # 0.6x of baseline: inside tolerance
    cliff = regress.compare({**KERNELS_BASE, "value": 0.2}, KERNELS_BASE)
    assert cliff["verdict"] == "regress" and "value" in cliff["regressions"]


def test_dtype_and_kernel_records_never_cross_compare():
    """A bf16 record vs an f32 one — or a pipelined vs a serial dispatch
    path — is no-data, exactly like a mesh mismatch: a variant change is an
    explicit diff, never silent ratio drift."""
    bf16 = {**BASE, "dtype": "bfloat16"}
    v = regress.compare(bf16, BASE)
    assert v["verdict"] == "no-data" and "dtype mismatch" in v["reason"]
    v = regress.compare(BASE, bf16)
    assert v["verdict"] == "no-data" and "dtype mismatch" in v["reason"]
    piped = {**BASE, "pipeline": "depth2"}
    v = regress.compare(piped, BASE)
    assert v["verdict"] == "no-data" and "pipeline mismatch" in v["reason"]
    # matching variant keys compare normally
    v = regress.compare({**bf16, "value": 95.0}, bf16)
    assert v["verdict"] == "pass"


def test_cli_kernels_and_bf16_baseline_routing(bench_compare):
    assert bench_compare.default_baseline_path(
        {"mode": "kernels"}
    ).endswith("bench_kernels_baseline.json")
    assert bench_compare.default_baseline_path(
        {"mode": "serve", "dtype": "bfloat16"}
    ).endswith("bench_serve_bf16_baseline.json")
    # mesh wins over dtype (the sharded flagship owns its baseline file)
    assert bench_compare.default_baseline_path(
        {"mode": "serve", "dtype": "bfloat16", "mesh": "dp1.spr2.spc4"}
    ).endswith("bench_serve_mesh_baseline.json")


def test_committed_kernels_and_bf16_baselines_are_valid():
    """The committed kernel-microbench and bf16 serve baselines must be
    usable measurements carrying their variant keys."""
    with open(os.path.join(REPO, "bench_kernels_baseline.json")) as f:
        kb = json.load(f)
    assert regress.record_invalid_reason(kb) is None
    assert kb["mode"] == "kernels" and "kernels" not in kb
    assert len(kb["shapes"]) == 6
    with open(os.path.join(REPO, "bench_serve_bf16_baseline.json")) as f:
        sb = json.load(f)
    assert regress.record_invalid_reason(sb) is None
    assert sb["dtype"] == "bfloat16" and "dtype=bfloat16" in sb["metric"]
    assert "kernels" not in sb
    assert sb["flops_by_kernel"]["tied_row"] > 0


# ------------------------------------------------------------ mesh keying

MESH_BASE = {
    "metric": "serve residues/sec tiny mesh=1x2x4 long=512x1",
    "device": "cpu", "mode": "serve", "mesh": "dp1.spr2.spc4",
    "value": 4.0, "p50_ms": 1500.0, "p95_ms": 170000.0, "p99_ms": 170000.0,
    "per_device_program_bytes": 380_000_000,
}


def test_mesh_records_never_compare_across_meshes():
    """A sharded record vs a single-device one (or two mesh shapes) is
    no-data, whatever the device kind says."""
    v = regress.compare({**MESH_BASE, "mesh": None}, MESH_BASE)
    assert v["verdict"] == "no-data" and "mesh mismatch" in v["reason"]
    v = regress.compare({**MESH_BASE, "mesh": "dp1.spr2.spc2"}, MESH_BASE)
    assert v["verdict"] == "no-data" and "mesh mismatch" in v["reason"]


def test_mesh_threshold_selection_and_memory_cliff():
    """Mesh-serve records select SERVE_MESH_THRESHOLDS: wide cross-machine
    perf tolerances, but per-device program bytes (deterministic per
    program) gated at 2x — the forgot-the-sharding cliff."""
    assert regress.thresholds_for(MESH_BASE) is regress.SERVE_MESH_THRESHOLDS
    assert regress.thresholds_for(BASE) is regress.DEFAULT_THRESHOLDS
    ok = regress.compare({**MESH_BASE, "value": 2.0}, MESH_BASE)
    assert ok["verdict"] == "pass"  # 2x slower machine: inside tolerance
    cliff = regress.compare(
        {**MESH_BASE, "per_device_program_bytes": 8 * 380_000_000},
        MESH_BASE,
    )
    assert cliff["verdict"] == "regress"
    assert cliff["regressions"] == ["per_device_program_bytes"]


def test_cli_mesh_baseline_routing(bench_compare):
    assert bench_compare.default_baseline_path(
        {"mode": "serve", "mesh": "dp1.spr2.spc4"}
    ).endswith("bench_serve_mesh_baseline.json")
    assert bench_compare.default_baseline_path({"mode": "serve"}).endswith(
        "bench_serve_baseline.json"
    )


def test_committed_mesh_baseline_is_valid_and_self_consistent():
    """The committed mesh-keyed baseline must be a usable measurement
    (regress validity classes) carrying the acceptance fields: mesh
    shape, per-device memory, and MFU accounting."""
    with open(os.path.join(REPO, "bench_serve_mesh_baseline.json")) as f:
        base = json.load(f)
    assert regress.record_invalid_reason(base) is None
    assert base["mesh"] == "dp1.spr2.spc4" and base["mesh_devices"] == 8
    assert base["per_device_program_bytes"] > 0
    # a CPU-mesh record carries no utilization: there is no published
    # peak for a host CPU and none is estimated
    assert "mfu" not in base and "mfu_basis" not in base
    assert any(
        c["bucket"] >= 512 and c.get("mesh") for c in base["compile_records"]
    )
    v = regress.compare(base, base, regress.thresholds_for(base))
    assert v["verdict"] == "pass"


# -------------------------------------------------- serve-async thresholds

ASYNC_BASE = {
    "metric": "serve-async residues/sec tiny", "device": "cpu",
    "mode": "serve-async", "value": 100.0, "goodput_rps": 8.0,
    "p50_ms": 50.0, "p95_ms": 100.0, "p99_ms": 150.0,
    "rejection_rate": 0.05,
}


def test_serve_async_threshold_selection():
    """The gate picks the serve-async direction table by record shape, so
    open-loop records get real per-metric verdicts, not no-data."""
    assert regress.thresholds_for(ASYNC_BASE) is regress.SERVE_ASYNC_THRESHOLDS
    assert regress.thresholds_for(BASE) is regress.DEFAULT_THRESHOLDS
    assert regress.thresholds_for(None) is regress.DEFAULT_THRESHOLDS
    assert {"goodput_rps", "rejection_rate", "value", "p99_ms"} <= set(
        regress.SERVE_ASYNC_THRESHOLDS
    )


def test_compare_serve_async_directions():
    thr = regress.SERVE_ASYNC_THRESHOLDS
    v = regress.compare(ASYNC_BASE, ASYNC_BASE, thr)
    assert v["verdict"] == "pass"
    assert {"goodput_rps", "rejection_rate"} <= {
        c["name"] for c in v["comparisons"]
    }
    # goodput collapse regresses (higher-is-better)
    v = regress.compare({**ASYNC_BASE, "goodput_rps": 1.0}, ASYNC_BASE, thr)
    assert v["verdict"] == "regress" and "goodput_rps" in v["regressions"]
    # rejection storm regresses (lower-is-better)
    v = regress.compare({**ASYNC_BASE, "rejection_rate": 0.5}, ASYNC_BASE, thr)
    assert v["verdict"] == "regress" and "rejection_rate" in v["regressions"]
    # a zero-rejection baseline cannot gate the ratio (explicitly ok)
    v = regress.compare(
        {**ASYNC_BASE, "rejection_rate": 0.5},
        {**ASYNC_BASE, "rejection_rate": 0.0}, thr,
    )
    assert v["verdict"] == "pass"


def test_cli_uses_serve_async_thresholds(bench_compare, tmp_path, capsys):
    """p95 2.5x worse: within the generous default-table tolerance? No —
    and for serve-async shapes the CLI must gate goodput too."""
    cur = _write(tmp_path, "cur.json", {**ASYNC_BASE, "goodput_rps": 2.0})
    base = _write(tmp_path, "base.json", ASYNC_BASE)
    assert bench_compare.main([cur, "--baseline", base]) == 1
    out = json.loads(capsys.readouterr().out)
    assert out["verdict"] == "regress" and "goodput_rps" in out["regressions"]


def test_cli_threshold_override(bench_compare, tmp_path, capsys):
    cur = _write(tmp_path, "cur.json", {**BASE, "value": 50.0})
    base = _write(tmp_path, "base.json", BASE)
    assert bench_compare.main(
        [cur, "--baseline", base, "--threshold", "value=0.6"]
    ) == 0
    assert json.loads(capsys.readouterr().out)["verdict"] == "pass"


# --------------------------------------------------------- unified flops


def test_flops_single_parser_and_mfu():
    import jax
    import jax.numpy as jnp

    from alphafold2_tpu.observe import flops

    compiled = jax.jit(lambda x: x @ x).lower(jnp.ones((32, 32))).compile()
    costs = flops.executable_costs(compiled)
    assert flops.step_flops(compiled) == costs["flops"]
    if costs["flops"] is not None:  # CPU cost analysis exposes flops
        assert costs["flops"] > 0 and costs["bytes_accessed"] > 0
    # MFU: explicit peak works; unknown device (CPU) yields None
    assert flops.mfu(1e12, 1.0, peak=2e12) == 0.5
    assert flops.mfu(None, 1.0, peak=2e12) is None
    assert flops.mfu(1e12, 0.0, peak=2e12) is None
    assert flops.device_peak_flops() is None  # host CPU: nothing reported
    assert flops.estimate_mfu(compiled, 1.0) is None

    # bench.py sources flops/MFU from observe.flops (single parser in tree)
    import bench

    assert bench._step_flops is flops.step_flops
    assert bench._estimate_mfu is flops.estimate_mfu
    assert bench._device_peak_flops is flops.device_peak_flops


def test_cost_analysis_dict_form_and_failure():
    from alphafold2_tpu.observe import flops

    class DictCompiled:
        def cost_analysis(self):
            return {"flops": 7.0, "bytes accessed": 3.0}

    class Broken:
        def cost_analysis(self):
            raise RuntimeError("no cost analysis on this backend")

    assert flops.step_flops(DictCompiled()) == 7.0
    assert flops.executable_costs(DictCompiled())["bytes_accessed"] == 3.0
    assert flops.step_flops(Broken()) is None
    assert flops.executable_costs(Broken()) == {
        "flops": None, "bytes_accessed": None
    }


# ------------------------------------------------ obs_report train summary


@pytest.fixture()
def obs_report(monkeypatch):
    monkeypatch.syspath_prepend(os.path.join(REPO, "scripts"))
    sys.modules.pop("obs_report", None)
    yield importlib.import_module("obs_report")
    sys.modules.pop("obs_report", None)


def test_obs_report_train_summary(obs_report, tmp_path, capsys):
    nan = float("nan")
    recs = [
        {"step": 0, "time": 1.0, "compile_s": 2.5, "step_flops": 1e9},
        {"step": 0, "time": 1.0, "loss": 4.0, "grad_norm": 2.0,
         "grads_ok": 1.0, "skipped": 0.0, "grad_norm/trunk": 1.5,
         "first_step_s": 0.5},
        {"step": 1, "time": 2.0, "loss": nan, "grad_norm": nan,
         "grads_ok": 0.0, "skipped": 1.0, "grad_norm/trunk": nan,
         "steps_per_sec": 10.0},
        {"step": 1, "time": 2.0, "event": "nan_triage",
         "first_nonfinite": "trunk.layer_0.pair",
         "nonfinite": ["trunk.layer_0.pair"],
         "numerics/trunk.layer_0.pair/nan_count": 8.0,
         "numerics/trunk.layer_0.pair/l2": 0.0},
        {"step": 2, "time": 3.0, "loss": 3.5, "grad_norm": 1.8,
         "grads_ok": 1.0, "skipped": 1.0, "steps_per_sec": 12.0},
    ]
    path = tmp_path / "metrics.jsonl"
    path.write_text("\n".join(json.dumps(r) for r in recs) + "\n")
    assert obs_report.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "-- train (" in out
    assert "loss:      4 -> 3.5" in out
    assert "skipped steps: 1 total (1 of the logged steps" in out
    assert "first step: 500.00ms" in out
    assert "step compile: 2.500s" in out
    assert "per-group norms: trunk" in out
    assert "numerics anomalies" in out and "trunk.layer_0.pair" in out
    assert "nan_triage @ step 1: first non-finite = trunk.layer_0.pair" in out
    # the per-tensor numerics keys are summarized, not dumped one by one
    assert "numerics/trunk.layer_0.pair/nan_count =" not in out
