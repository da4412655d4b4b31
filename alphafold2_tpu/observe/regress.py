"""Device-keyed perf regression gate over bench/serve records.

The early bench records were all invalid failure diagnostics, and nothing
automated ever compared a new number against the committed baselines — the
ROADMAP's "fast as the hardware allows" north star had no machinery that
notices a regression. This module is that machinery, shared by ``scripts/bench_compare.py`` (the
CI gate) and anything else that wants a verdict:

- **validity** — :func:`record_invalid_reason` distinguishes a real
  measurement from the failure shapes the bench deliberately emits
  (``error`` records, ``implausible``/``clock_suspect`` clock failures,
  value-0.0 watchdog records, withdrawn baselines).
- **comparability** — :func:`comparable_reason` requires the same metric
  label, the same device kind (a CPU-mesh number vs a TPU number is not a
  comparison), the same mesh identity (a sharded record vs a single-device
  one is not a comparison either) and, for train-bench records, the same
  in-graph step count (the timing methodology).
- **thresholds** — per-metric direction + tolerated fractional change;
  anything past tolerance in the bad direction regresses the verdict.

The output is a structured ``pass`` / ``regress`` / ``no-data`` verdict:
``no-data`` (invalid or incomparable records, missing baseline) is an
explicit third state so a broken bench can never silently read as "at
parity". Pure python, no jax — runs host-side in CI.
"""

from __future__ import annotations

from typing import Optional

# name -> (direction, tolerated fractional change vs baseline). "higher"
# means bigger is better (regress when current < (1 - tol) * baseline);
# "lower" means smaller is better (regress when current > (1 + tol) *
# baseline). Latency tolerances are generous: CI runners and the CPU mesh
# are noisy, and the gate must catch real cliffs, not scheduler jitter.
DEFAULT_THRESHOLDS = {
    "value": ("higher", 0.10),
    "mfu": ("higher", 0.15),
    "p50_ms": ("lower", 0.50),
    "p95_ms": ("lower", 0.50),
    "p99_ms": ("lower", 0.50),
    # absolute gate (baseline-independent), serve records only (train
    # records don't carry the key): fraction of the dispatch window the
    # device sat idle, computed from the trace spans
    # (observe.tracing.device_idle_fraction). The pipelined dispatch
    # exists to keep this low on the closed-loop bench — host featurize /
    # transfer / unpad overlapping compute; a pipeline wired wrong (a
    # stage serializing again, a lost overlap) shows up here before it
    # shows up in throughput noise.
    "device_idle_frac": ("absmax", 0.30),
}

# serve-async (open-loop frontend) records: the headline is goodput and
# tail latency under offered load, plus the admission-control outcome —
# each with its own direction so the gate yields real per-metric verdicts
# instead of falling back to no-data on the shape. Tolerances are wider
# still: open-loop records compare across machines (a committed CPU-mesh
# baseline vs a CI runner), where absolute speed legitimately varies —
# the gate exists for order-of-magnitude cliffs (a lost cache, a dwell
# misconfiguration, rejection storms), not machine-to-machine jitter.
SERVE_ASYNC_THRESHOLDS = {
    "value": ("higher", 0.50),  # ok-residues/sec over the open-loop window
    "goodput_rps": ("higher", 0.50),  # completed requests/sec
    "p50_ms": ("lower", 2.00),
    "p95_ms": ("lower", 2.00),
    "p99_ms": ("lower", 2.00),
    "rejection_rate": ("lower", 1.00),
    # per-priority-class tails: the class breakdown is what the SLO specs
    # promise, so a high-class-only regression must not hide in the
    # aggregate (a priority-inversion bug leaves p95_ms flat while
    # p95_ms_high triples)
    "p95_ms_high": ("lower", 2.00),
    "p95_ms_normal": ("lower", 2.00),
    "p95_ms_low": ("lower", 2.50),
    "goodput_rps_high": ("higher", 0.60),
    "goodput_rps_normal": ("higher", 0.60),
    # absolute gates (baseline-independent): the telemetry plane's own
    # contracts. Tracing/SLO/registry accounting may cost <5% goodput, and
    # ≥99% of non-rejected requests must reconstruct a complete trace.
    "telemetry_overhead_frac": ("absmax", 0.05),
    "trace_complete_fraction": ("absmin", 0.99),
    # open-loop device idleness is dominated by the offered arrival rate
    # (the device legitimately waits for Poisson gaps and dwell windows),
    # so the absolute bound is necessarily loose — it exists to catch the
    # pipeline collapsing entirely (idle ~1.0 under saturating load), not
    # to assert continuous occupancy
    "device_idle_frac": ("absmax", 0.90),
}

# mesh-sharded serve records (a "mesh" key beside mode=serve): throughput
# and latency get the wide cross-machine tolerances (the committed baseline
# is a CPU-mesh record; CI runners differ in core count), while the
# per-device program footprint gets a tight-ish one — it is DETERMINISTIC
# per (program, jax version), and a 2x jump is exactly the forgot-the-
# sharding-constraint cliff (an unsharded pair grid on a 2x4 grid mesh is
# 8x per device) this gate exists to catch.
SERVE_MESH_THRESHOLDS = {
    "value": ("higher", 0.60),
    "p50_ms": ("lower", 2.50),
    "p95_ms": ("lower", 2.50),
    "p99_ms": ("lower", 2.50),
    "per_device_program_bytes": ("lower", 1.00),
    # looser than the single-device bound: the CPU mesh's per-dispatch
    # host work (sharded device_puts per axis) is a larger fraction of
    # its window, and the gate targets lost-overlap cliffs, not jitter
    "device_idle_frac": ("absmax", 0.50),
}

# variant-scan fast-lane records (bench.py --mode serve-scan): one parent
# plus a deep-mutational-scan mutant set through the affinity-batched,
# feature-cached frontend vs the same variants dispatched cold one at a
# time. The headline (variants/sec) gets the wide cross-machine tolerance;
# the STRUCTURAL claims are absolute gates judged on the current record
# alone — the amortized speedup over the cold path is the tentpole's >=5x
# acceptance bar, and the reuse ledger must account every dispatched
# request (hits + misses + delta-reuses == featurized requests), because
# an unaccounted ledger means requests silently took the cold path.
SERVE_SCAN_THRESHOLDS = {
    "value": ("higher", 0.50),  # scan-lane variants/sec
    "p50_ms": ("lower", 2.00),
    "p95_ms": ("lower", 2.00),
    # the tentpole bar, absolute: amortized per-variant latency must stay
    # >=5x better than the measured cold path on the same machine — a
    # same-run ratio, so it holds across machine speeds
    "speedup_vs_cold": ("absmin", 5.0),
    "ledger_accounted_frac": ("absmin", 1.0),  # every request accounted
    # scan traffic is near-duplicate by construction: almost everything
    # after the parent must ride the delta/hit lanes (cold misses are the
    # parent plus at most a handful of cache-churn refills)
    "reuse_fraction": ("absmin", 0.90),
}

# kernels microbench (bench.py --mode kernels): fused-vs-stock attention
# timings at fixed shapes. The headline is the geomean speedup (on CPU the
# fused kernels run in Pallas interpret mode, so the committed CPU baseline
# sits well below 1x — the gate watches for CLIFFS in that ratio, e.g. an
# interpret-path blowup or a kernel suddenly falling back to dense, not for
# absolute speed). Wide tolerances: single-shape microbenches on shared CI
# runners are the noisiest records in the tree.
KERNELS_THRESHOLDS = {
    "value": ("higher", 0.50),
    "fused_ms_total": ("lower", 1.50),
    "stock_ms_total": ("lower", 1.50),
}

# workload record→replay records (bench.py --mode serve-replay): a
# recorded (or synthetic-diurnal) request stream replayed against a fresh
# engine in the same process. Throughput/latency ratios get the standard
# wide cross-machine tolerances; the STRUCTURAL claims — the loop this
# mode exists to close — are absolute gates judged on the current record
# alone: the replay must reproduce the recording's feature-reuse ledger
# EXACTLY (ledger_match is 1.0 or the replay is not deterministic), the
# replayed lifecycles must still reconstruct complete traces, and the
# recorder itself (submit hook + resolve hook + JSONL append) may cost
# <=5% goodput measured on/off on a warm engine, exactly like
# telemetry_overhead_frac.
REPLAY_THRESHOLDS = {
    "value": ("higher", 0.50),  # replayed ok-residues/sec
    "goodput_rps": ("higher", 0.50),
    "p50_ms": ("lower", 2.00),
    "p95_ms": ("lower", 2.00),
    "ledger_match": ("absmin", 1.0),  # exact reuse-ledger reproduction
    "replay_bytes_identical": ("absmin", 1.0),  # (seq, seed) determinism
    "trace_complete_fraction": ("absmin", 0.99),
    "recorder_overhead_frac": ("absmax", 0.05),
}


# fleet serving records (bench.py --mode serve-fleet): the same offered
# open-loop stream through N replica cells behind the health-aware
# router. Throughput/latency ratios get the standard wide cross-machine
# tolerances; the STRUCTURAL claims the fleet exists for are absolute
# gates judged on the current record alone — goodput must scale (>= 1.6x
# single-replica at 2 replicas, the tentpole bar), a mid-run replica kill
# must resolve every accepted request (zero silent drops: every handle
# reaches a terminal ServeResult), and the router hop must not break
# trace reconstruction (>= 99% complete end-to-end across the
# traceparent round-trip). Records carry ``replicas`` as a comparability
# variant key: a 2-replica number must never ratio a 4-replica baseline.
# ``thresholds_for`` waives ONLY the speedup floor on single-core hosts
# (record ``host_cpus`` < 2), where replica threads cannot run in
# parallel by construction.
FLEET_THRESHOLDS = {
    "value": ("higher", 0.50),  # fleet ok-residues/sec
    "goodput_rps": ("higher", 0.50),
    "p50_ms": ("lower", 2.00),
    "p95_ms": ("lower", 2.00),
    "fleet_speedup": ("absmin", 1.6),  # N-replica vs 1-replica goodput
    "accepted_unresolved": ("absmax", 0.0),  # drain drill: zero drops
    "dropped_requests": ("absmax", 0.0),
    "trace_complete_fraction": ("absmin", 0.99),  # across the hop
}


def thresholds_for(record) -> dict:
    """The gate's per-metric direction/tolerance table for this record's
    shape (keyed by the record's ``mode`` and mesh identity)."""
    if isinstance(record, dict) and record.get("mode") == "serve-async":
        return SERVE_ASYNC_THRESHOLDS
    if isinstance(record, dict) and record.get("mode") == "serve-fleet":
        # the speedup floor is a statement about replica PARALLELISM:
        # replica dispatchers are OS threads, so a single-core host
        # physically cannot exceed 1x and the floor would only gate the
        # machine, not the router. Zero-drop and trace-completeness stay
        # unconditional — they hold on any host.
        if record.get("host_cpus", 2) < 2:
            return {
                k: v for k, v in FLEET_THRESHOLDS.items()
                if k != "fleet_speedup"
            }
        return FLEET_THRESHOLDS
    if isinstance(record, dict) and record.get("mode") == "serve-scan":
        return SERVE_SCAN_THRESHOLDS
    if isinstance(record, dict) and record.get("mode") == "serve-replay":
        return REPLAY_THRESHOLDS
    if isinstance(record, dict) and record.get("mode") == "kernels":
        return KERNELS_THRESHOLDS
    if isinstance(record, dict) and record.get("mesh"):
        return SERVE_MESH_THRESHOLDS
    return DEFAULT_THRESHOLDS


def record_invalid_reason(rec) -> Optional[str]:
    """Why this record is NOT a usable measurement (None = it is)."""
    if not isinstance(rec, dict):
        return "not a record object"
    if rec.get("error"):
        return f"error record ({str(rec['error'])[:120]})"
    if rec.get("invalid"):
        return "withdrawn/invalid record"
    if rec.get("implausible"):
        return "implausible measurement (clock not syncing with device)"
    if rec.get("clock_suspect"):
        return "clock_suspect measurement (probe failed)"
    if rec.get("liveness") == "dead":
        return "liveness-dead failure record"
    if not rec.get("value"):
        return "no measured value"
    return None


def comparable_reason(current: dict, baseline: dict) -> Optional[str]:
    """Why these two valid records must not be compared (None = they may).

    Comparisons are keyed by metric label (which encodes the measured
    config), device kind, and — for train-bench records — the in-graph step
    count, since changing any of those changes what the number means."""
    if current.get("metric") != baseline.get("metric"):
        return (
            f"metric label mismatch: current={current.get('metric')!r} "
            f"baseline={baseline.get('metric')!r}"
        )
    cur_dev, base_dev = current.get("device"), baseline.get("device")
    if cur_dev and base_dev and cur_dev != base_dev:
        return f"device mismatch: current={cur_dev!r} baseline={base_dev!r}"
    # variant keys records carry only when non-default: mesh identity
    # (sharded serving), serving dtype (bf16 mode) and dispatch pipeline
    # ("depth2"/"off" — pipelined and serial dispatch have different
    # latency anatomy, so a pipelined record must never ratio against a
    # pre-pipeline baseline). A sharded vs single-device number or a bf16
    # vs f32 one is not a comparison — a precision change must surface as
    # an explicit no-data diff (and its own baseline), never as silent
    # ratio drift.
    # "scan" fences variant-scan fast-lane records: their value is an
    # amortized near-duplicate-traffic number that must never ratio
    # against a plain serve record (or vice versa). "replay" fences the
    # record→replay loop's knobs the same way — a time-warped or
    # load-scaled replay measures a different offered stream than the
    # flagship synthetic run the baseline committed. "replicas" fences
    # fleet records: goodput through 2 replica cells and through 4 are
    # different machines as far as a ratio is concerned.
    for key in (
        "mesh", "dtype", "pipeline", "scan", "replay", "replicas",
    ):
        if current.get(key) != baseline.get(key):
            return (
                f"{key} mismatch: current={current.get(key)!r} "
                f"baseline={baseline.get(key)!r}"
            )
    if "ingraph" in baseline and baseline.get("ingraph") != current.get(
        "ingraph"
    ):
        return (
            f"timing methodology mismatch: ingraph current="
            f"{current.get('ingraph')} baseline={baseline.get('ingraph')}"
        )
    return None


def _compare_one(name, cur, base, direction, tolerance) -> dict:
    if direction in ("absmax", "absmin"):
        # absolute bound on the CURRENT value: "tolerance" is the bound
        # itself and the baseline is informational only — for metrics that
        # are contracts (trace completeness, telemetry overhead), not
        # measurements that drift with the machine
        ok = cur <= tolerance if direction == "absmax" else cur >= tolerance
        return {
            "name": name,
            "current": cur,
            "baseline": base,
            "ratio": None,
            "direction": direction,
            "tolerance": tolerance,
            "ok": bool(ok),
        }
    ratio = cur / base if base else None
    if ratio is None:
        ok = True  # zero/absent baseline value: nothing to gate on
    elif direction == "higher":
        ok = ratio >= 1.0 - tolerance
    else:
        ok = ratio <= 1.0 + tolerance
    return {
        "name": name,
        "current": cur,
        "baseline": base,
        "ratio": round(ratio, 4) if ratio is not None else None,
        "direction": direction,
        "tolerance": tolerance,
        "ok": bool(ok),
    }


def compare(
    current: dict,
    baseline: Optional[dict],
    thresholds: Optional[dict] = None,
) -> dict:
    """Structured verdict of ``current`` against ``baseline``.

    Returns ``{"verdict": "pass"|"regress"|"no-data", ...}`` with a
    ``reason`` for no-data and per-metric ``comparisons`` otherwise. Only
    metrics present in BOTH records and named in ``thresholds`` are gated.
    ``thresholds=None`` routes by the record's shape (:func:`thresholds_for`)
    — serve-async and mesh-serve records get their own tables.
    """
    thresholds = thresholds if thresholds is not None else thresholds_for(current)
    out = {
        "metric": current.get("metric") if isinstance(current, dict) else None,
        "device": current.get("device") if isinstance(current, dict) else None,
    }
    if isinstance(current, dict) and isinstance(
        current.get("slo_alerts"), (int, float)
    ):
        # informational, never gated: a legitimately-firing SLO alert on a
        # fault-injected run must not flap CI, but the verdict should show it
        out["slo_alerts"] = current["slo_alerts"]

    reason = record_invalid_reason(current)
    if reason is not None:
        return {**out, "verdict": "no-data",
                "reason": f"current record invalid: {reason}"}
    if baseline is None:
        return {**out, "verdict": "no-data", "reason": "missing baseline"}
    reason = record_invalid_reason(baseline)
    if reason is not None:
        return {**out, "verdict": "no-data",
                "reason": f"baseline record invalid: {reason}"}
    reason = comparable_reason(current, baseline)
    if reason is not None:
        return {**out, "verdict": "no-data",
                "reason": f"not comparable: {reason}"}

    comparisons = []
    for name, (direction, tolerance) in thresholds.items():
        cur, base = current.get(name), baseline.get(name)
        if not isinstance(cur, (int, float)):
            continue
        if direction in ("absmax", "absmin"):
            # absolute gates judge the current record alone; an older
            # baseline without the metric must not disable the contract
            comparisons.append(_compare_one(
                name, float(cur),
                float(base) if isinstance(base, (int, float)) else None,
                direction, tolerance,
            ))
            continue
        if not isinstance(base, (int, float)):
            continue
        comparisons.append(
            _compare_one(name, float(cur), float(base), direction, tolerance)
        )
    if not comparisons:
        return {**out, "verdict": "no-data",
                "reason": "no shared gated metrics between the records"}
    regressions = [c["name"] for c in comparisons if not c["ok"]]
    return {
        **out,
        "verdict": "regress" if regressions else "pass",
        "comparisons": comparisons,
        "regressions": regressions,
    }


def parse_threshold_overrides(items, base: Optional[dict] = None) -> dict:
    """CLI ``metric=tolerance`` (keep the default direction) or
    ``metric=direction:tolerance`` overrides onto a copy of the defaults."""
    out = dict(base if base is not None else DEFAULT_THRESHOLDS)
    for item in items or ():
        name, _, spec = item.partition("=")
        if not spec:
            raise ValueError(
                f"bad threshold {item!r}; expected metric=tol or "
                "metric=direction:tol"
            )
        direction, _, tol = spec.rpartition(":")
        if not direction:
            direction = out.get(name, ("higher", 0.0))[0]
        if direction not in ("higher", "lower", "absmax", "absmin"):
            raise ValueError(f"bad direction {direction!r} in {item!r}")
        out[name] = (direction, float(tol))
    return out
