"""What every driver shares: the files a cell resolves to, the device record,
the result line."""

from __future__ import annotations

import importlib
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def enable_cache() -> None:
    """The program's persistent compile cache (``JAX_COMPILATION_CACHE_DIR``
    or ``<checkout>/.jax_cache``), taking every program however small or
    quick to compile: the reference's and the readers' too."""
    import jax

    import alphafold2_tpu

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    alphafold2_tpu.enable_compile_cache()


def resolve(workload: str, bench_dir: str = BENCH_DIR) -> dict:
    """A cell's name -> its manifest entry, configuration, traffic mix and
    per-layer metrics, each from the file its name points to."""
    manifest = load_json(
        os.path.join(os.path.dirname(bench_dir), "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(
            f"unknown workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = load_json(os.path.join(
        os.path.dirname(bench_dir), configs[cell["config"]]["file"]))
    traffic = load_json(
        os.path.join(bench_dir, "traffic", cell["traffic"] + ".json"))

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    per_layer = []
    for metric in manifest["per_layer"]:
        if mine(metric):
            spec = load_json(
                os.path.join(bench_dir, "metrics", metric["name"] + ".json"))
            per_layer.append({**metric, **spec})
    return {
        "cell": cell, "config": config, "traffic": traffic,
        "end_to_end": [m for m in manifest["end_to_end"] if mine(m)],
        "per_layer": per_layer,
        "peaks": load_json(os.path.join(bench_dir, "harness", "peaks.json")),
    }


def peaks_for(peaks: dict, device_kind: str) -> dict:
    if device_kind not in peaks["devices"]:
        raise SystemExit(
            f"no peaks for device_kind {device_kind!r} in harness/peaks.json;"
            " add the published numbers with their source")
    return peaks["devices"][device_kind]


def device_record(run: dict) -> dict:
    import jax

    d = jax.devices()
    rec = {"platform": d[0].platform, "kind": d[0].device_kind,
           "count": len(d), "memory_peak_bytes": int(run["memory_peak_bytes"])}
    trace = run.get("trace")
    if trace is not None:
        rec["busy_s"] = trace["busy_s"]
        rec["window_s"] = trace["window_s"]
    return rec


def read_metric(spec: dict, run: dict):
    """Run one per-layer metric's reader; None where it finds nothing."""
    reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    return reader.read(run, spec.get("params", {}))


def result_line(resolved: dict, run: dict, trace: bool) -> dict:
    metrics = {}
    if trace:
        for spec in resolved["per_layer"]:
            value = read_metric(spec, run)
            if value is not None:
                metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
    else:
        for m in resolved["end_to_end"]:
            metrics[m["name"]] = {"value": run["end_to_end"][m["name"]],
                                  "unit": m["unit"]}
    line = {
        "correct": bool(run["correct"]), "attempted": int(run["attempted"]),
        "failed": int(run["failed"]), "metrics": metrics,
        "device": device_record(run),
    }
    if trace and run.get("trace") is not None:
        line["breakdown"] = run["trace"]["breakdown"]
    line["reference_s"] = run.get("reference_s")
    line["setup_parts_s"] = run.get("setup_parts_s")
    line["compared"] = run["compared"]
    return line


def emit(line: dict) -> None:
    """The compared numbers as the last lines on standard error, the result
    as the last line on standard output."""
    sys.stdout.flush()
    for name, c in line["compared"].items():
        print(f"compared {name}: value {c['value']!r} limit {c['limit']!r}"
              f" {'ok' if c['ok'] else 'OUT'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
