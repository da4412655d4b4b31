#!/usr/bin/env python
"""Summarize observability artifacts: trace-event JSONL + metrics.jsonl.

    python scripts/obs_report.py trace.json [metrics.jsonl ...]

For each Chrome-trace-event file (written by ``observe.Tracer``, or any
trace the viewer loads): per-span totals (count, total/mean/max duration)
and percentile tables over span durations. For each metrics.jsonl
(``observe.MetricsLogger``): the latest counter values with compile /
cache-hit accounting (hit rate, compile seconds by shape) and HBM peaks.

Pure host-side: imports no jax, initializes no backend — it must run on a
laptop against artifacts scp'd from a TPU host (the reason MetricsLogger
grew its ``enabled=`` override). Exits 0 on success, 1 on no input files,
2 on unreadable input OR any truncated/malformed line (every parseable
record is still reported; the malformed lines get a structured per-file
summary on stderr instead of a mid-parse traceback — a killed writer's
half-flushed tail must not hide the rest of the artifact).

``--env`` echoes the AF2TPU_/JAX_/XLA_/TPU_ environment through the
flight recorder's scrub (secret-shaped values redacted),
so a report pasted into a ticket carries the config without credentials.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from alphafold2_tpu.observe.flightrec import scrub_env
from alphafold2_tpu.observe.histogram import Histogram
from alphafold2_tpu.observe.tracectx import (
    RESOLVE_EVENT,
    SUBMIT_EVENT,
    reconstruct_traces,
    trace_incomplete_reason,
)
from alphafold2_tpu.observe.tracing import (
    DEVICE_SPAN_NAMES,
    device_idle_fraction,
    load_trace_events_lenient,
    merge_intervals,
)


def _fmt_s(seconds: float) -> str:
    if seconds >= 1.0:
        return f"{seconds:.3f}s"
    return f"{seconds * 1e3:.2f}ms"


def classify(path: str) -> str:
    """"trace" (Chrome trace events) vs "metrics" (MetricsLogger JSONL) vs
    "hlo-contracts" (analysis/hlo_audit.py snapshot) vs
    "concurrency-contracts" (analysis/concurrency.py baseline): trace files
    open with ``[`` or hold events with a ``ph`` key; metrics lines are
    flat records with a ``step`` key; an hlo_contracts.json is a single
    pretty-printed object with ``format`` + ``targets``; a
    concurrency_contracts.json has ``format`` + ``lock_graph``."""
    with open(path) as f:
        head = f.read(4096).lstrip()
    if head.startswith("["):
        return "trace"
    if head.startswith("{"):
        try:
            with open(path) as f:
                doc = json.load(f)
        except json.JSONDecodeError:
            doc = None
        if (isinstance(doc, dict) and "format" in doc
                and isinstance(doc.get("targets"), dict)):
            return "hlo-contracts"
        if (isinstance(doc, dict) and "format" in doc
                and isinstance(doc.get("lock_graph"), dict)):
            return "concurrency-contracts"
        if isinstance(doc, dict):
            # a single-record metrics file (e.g. one bench JSON line
            # longer than the sniff window) parses whole even when its
            # first 4096 bytes don't
            return "trace" if "ph" in doc else "metrics"
    first = head.splitlines()[0] if head else "{}"
    try:
        rec = json.loads(first)
    except json.JSONDecodeError:
        return "trace"
    return "trace" if "ph" in rec else "metrics"


def report_trace(path: str) -> list:
    """Span table + request-trace timelines. Returns the list of malformed-
    line descriptions (empty = clean file) for main()'s error summary."""
    events, errors = load_trace_events_lenient(path)
    spans = [e for e in events if e.get("ph") == "X"]
    print(f"== trace {path}: {len(events)} events, {len(spans)} spans ==")
    if not spans:
        report_fleet_timeline(events)
        report_request_traces(events)
        return errors
    by_name: dict = {}
    for e in spans:
        by_name.setdefault(e["name"], []).append(e.get("dur", 0.0) / 1e6)

    total_wall = sum(sum(v) for v in by_name.values())
    print(f"{'span':<28} {'count':>6} {'total':>10} {'mean':>10} "
          f"{'p50':>10} {'p95':>10} {'p99':>10} {'max':>10}")
    for name in sorted(by_name, key=lambda n: -sum(by_name[n])):
        durs = by_name[name]
        h = Histogram()
        for d in durs:
            h.observe(d)
        snap = h.snapshot()
        print(
            f"{name:<28} {len(durs):>6} {_fmt_s(sum(durs)):>10} "
            f"{_fmt_s(sum(durs) / len(durs)):>10} "
            f"{_fmt_s(snap['p50']):>10} {_fmt_s(snap['p95']):>10} "
            f"{_fmt_s(snap['p99']):>10} {_fmt_s(max(durs)):>10}"
        )
    print(f"{'(span-seconds, nested spans double-count)':<28} "
          f"{'':>6} {_fmt_s(total_wall):>10}")

    compiles = [e for e in spans if e["name"].endswith("compile")]
    if compiles:
        print("-- compiles --")
        for e in compiles:
            args = e.get("args", {})
            shape = ", ".join(f"{k}={v}" for k, v in sorted(args.items()))
            print(f"  {e['name']}({shape}): {_fmt_s(e.get('dur', 0) / 1e6)}")
    report_pipeline(events)
    report_fleet_timeline(events)
    report_request_traces(events)
    return errors


_HOST_SPAN_NAMES = ("serve.featurize", "serve.device_put")


def report_pipeline(events: list, max_shown: int = 12) -> None:
    """Pipelined-dispatch section (serve/pipeline.py): per-dispatch
    host/device timeline keyed by the ``dispatch_index`` span arg, the
    device-idle fraction over the dispatch window (the same
    ``device_idle_frac`` bench records gate), each device phase's overlap
    with OTHER dispatches' host work (the wall time double buffering
    actually reclaimed), and the in-flight admission count
    (``sched.inflight_admit`` instants from continuous batching)."""
    per: dict = {}
    for e in events:
        if e.get("ph") != "X":
            continue
        name = e.get("name")
        if name not in _HOST_SPAN_NAMES and name not in DEVICE_SPAN_NAMES:
            continue
        args = e.get("args") or {}
        if args.get("dispatch_index") is None:
            continue
        d = per.setdefault(
            args["dispatch_index"],
            {"host": [], "device": [], "bucket": args.get("bucket")},
        )
        iv = (e.get("ts", 0) / 1e6, (e.get("ts", 0) + e.get("dur", 0)) / 1e6)
        d["host" if name in _HOST_SPAN_NAMES else "device"].append(iv)
    per = {k: v for k, v in per.items() if v["device"]}
    if not per:
        return

    idle = device_idle_fraction(events)
    head = (f"device_idle_frac {idle['device_idle_frac']:.3f} over "
            f"{_fmt_s(idle['window_s'])}") if idle else "no device window"
    admits = sum(
        1 for e in events if e.get("name") == "sched.inflight_admit"
    )
    pipelined = sum(
        1 for e in events
        if e.get("name") == "serve.batch"
        and (e.get("args") or {}).get("pipelined")
    )
    print(f"-- pipelined dispatch ({len(per)} dispatches, {head}) --")
    t0 = min(iv[0] for d in per.values() for iv in d["host"] + d["device"])
    shown = 0
    for idx in sorted(per):
        if shown >= max_shown:
            print(f"  ... {len(per) - max_shown} more dispatches")
            break
        shown += 1
        d = per[idx]
        host = merge_intervals(d["host"])
        dev = merge_intervals(d["device"])
        # device time of THIS dispatch that ran while ANOTHER dispatch's
        # host stage was featurizing/transferring: the overlap the
        # pipeline reclaimed vs a serial host->device->host loop
        others = merge_intervals([
            iv for j, o in per.items() if j != idx for iv in o["host"]
        ])
        overlap = 0.0
        for ds, de in dev:
            for hs, he in others:
                overlap += max(0.0, min(de, he) - max(ds, hs))
        line = f"  #{idx:<4} bucket {str(d['bucket'] or '?'):>5} "
        if host:
            line += (f" host {_fmt_s(sum(e - s for s, e in host)):>9}"
                     f"@+{host[0][0] - t0:7.3f}s")
        else:
            line += f" host {'-':>9} {'':>9}"
        line += (f"  device {_fmt_s(sum(e - s for s, e in dev)):>9}"
                 f"@+{dev[0][0] - t0:7.3f}s")
        if overlap:
            line += f"  overlapped {_fmt_s(overlap)}"
        print(line)
    tail = f"  in-flight admissions: {admits}"
    if pipelined:
        tail += f"  (pipelined batches: {pipelined})"
    print(tail)


def report_request_traces(events: list, max_shown: int = 8) -> None:
    """Per-request lifecycle timelines reconstructed by trace_id: every
    request whose sched.submit root rode this file, its event sequence in
    ts order, its terminal status, and the completeness verdict (the same
    trace_incomplete_reason the CI gate's trace_complete_fraction uses),
    so a broken lifecycle names its missing link instead of just lowering
    a fraction."""
    traces = reconstruct_traces(events)
    # request traces only: the trace must own a sched.submit root (shared
    # batch spans list member trace_ids but belong to no single request)
    roots = {
        tid: evs for tid, evs in traces.items()
        if any(
            e.get("name") == SUBMIT_EVENT
            and (e.get("args") or {}).get("trace_id") == tid
            for e in evs
        )
    }
    if not roots:
        return
    reasons = {
        tid: trace_incomplete_reason(tid, evs) for tid, evs in roots.items()
    }
    n_ok = sum(1 for r in reasons.values() if r is None)
    print(f"-- request traces ({n_ok}/{len(roots)} complete) --")
    for i, tid in enumerate(sorted(roots)):
        if i >= max_shown:
            print(f"  ... {len(roots) - max_shown} more")
            break
        evs = sorted(roots[tid], key=lambda e: e.get("ts", 0))
        steps = []
        for e in evs:
            name = e.get("name", "?")
            if e.get("ph") == "X" and e.get("dur"):
                steps.append(f"{name}({_fmt_s(e['dur'] / 1e6)})")
            else:
                steps.append(name)
        status = next(
            ((e.get("args") or {}).get("status") for e in reversed(evs)
             if e.get("name") == RESOLVE_EVENT),
            "?",
        )
        verdict = "complete" if reasons[tid] is None else reasons[tid]
        print(f"  {tid[:12]} [{status}] {' > '.join(steps)}")
        if reasons[tid] is not None:
            print(f"    INCOMPLETE: {verdict}")


def _fin(values):
    """Finite subset (NaN-skipping min/max/mean must not be poisoned by the
    very anomalies the report exists to surface)."""
    return [v for v in values if isinstance(v, (int, float)) and v == v]


def report_train(records: list) -> None:
    """Training-run section of a metrics.jsonl: loss/grad-norm trajectory,
    skipped-step accounting, throughput, numerics anomalies and NaN-triage
    reports (train/loop.py's numerics telemetry)."""
    steps = [r for r in records if "loss" in r]
    if not steps:
        return
    print(f"-- train ({len(steps)} step records, steps "
          f"{steps[0].get('step')}..{steps[-1].get('step')}) --")
    losses = _fin([r["loss"] for r in steps])
    if losses:
        print(f"  loss:      {losses[0]:.4g} -> {losses[-1]:.4g}  "
              f"(min {min(losses):.4g})")
    gnorms = _fin([r.get("grad_norm") for r in steps])
    if gnorms:
        print(f"  grad_norm: first {gnorms[0]:.4g}  last {gnorms[-1]:.4g}  "
              f"min {min(gnorms):.4g}  max {max(gnorms):.4g}")
    groups = sorted({
        k.split("/", 1)[1] for r in steps for k in r
        if k.startswith("grad_norm/")
    })
    if groups:
        print(f"  per-group norms: {', '.join(groups)}")
    skipped = max((r.get("skipped", 0) for r in steps), default=0)
    not_ok = sum(1 for r in steps if r.get("grads_ok") in (0, 0.0, False))
    print(f"  skipped steps: {int(skipped)} total "
          f"({not_ok} of the logged steps had non-finite grads)")
    first = next((r["first_step_s"] for r in steps if "first_step_s" in r),
                 None)
    if first is not None:
        print(f"  first step: {_fmt_s(first)}")
    compile_s = next(
        (r["compile_s"] for r in records if "compile_s" in r), None
    )
    if compile_s is not None:
        print(f"  step compile: {_fmt_s(compile_s)}")
    rates = _fin([r.get("steps_per_sec") for r in steps])
    if rates:
        print(f"  steps/sec: last {rates[-1]:.4g}  max {max(rates):.4g}")

    # numerics anomalies: any logged tensor stat with NaN/Inf entries
    anomalies = sorted({
        k[len("numerics/"):k.rfind("/")]
        for r in records
        for k, v in r.items()
        if k.startswith("numerics/")
        and (k.endswith("/nan_count") or k.endswith("/inf_count"))
        and isinstance(v, (int, float)) and v > 0
    })
    if anomalies:
        print(f"  numerics anomalies (tensors with NaN/Inf): "
              f"{', '.join(anomalies)}")
    triages = [r for r in records if r.get("event") == "nan_triage"]
    for t in triages:
        print(f"  nan_triage @ step {t.get('step')}: first non-finite = "
              f"{t.get('first_nonfinite')} "
              f"({len(t.get('nonfinite', []))} tensors non-finite)")


def report_scheduler(latest: dict) -> None:
    """Async-frontend section of a metrics.jsonl: admission-control and
    queue outcomes from the ``sched.*`` counters the scheduler shares with
    the engine (serve/scheduler.py), plus the open-loop latency/queue
    summary when a serve-async bench record rode the same file. The
    queue-depth / time-to-dispatch / dwell distributions live in the
    bench record's ``histograms``; the trace file's ``sched.dispatch`` /
    ``sched.retry`` spans appear in the standard span table."""
    if not any(k.startswith("sched.") for k in latest):
        return
    submitted = latest.get("sched.submitted", 0)
    rejected = latest.get("sched.rejected", 0)
    print(f"-- async scheduler ({int(submitted)} submitted) --")
    print(f"  admitted:       {int(latest.get('sched.admitted', 0))}")
    shed = latest.get("sched.shed", 0)
    rate = rejected / submitted if submitted else 0.0
    print(f"  rejected:       {int(rejected)}  ({rate:.1%}; "
          f"{int(shed)} load-shed past the watermark)")
    print(f"  deadline miss:  {int(latest.get('sched.deadline_miss', 0))}")
    hits = latest.get("sched.cache_hits", 0)
    dedup = latest.get("sched.inflight_dedup", 0)
    saved = (hits + dedup) / submitted if submitted else 0.0
    print(f"  result cache:   {int(hits)} hits + {int(dedup)} in-flight "
          f"dedups ({saved:.1%} of submissions never dispatched)")
    retries = latest.get("sched.retries", 0)
    errors = latest.get("serve.dispatch_errors", 0)
    if retries or errors:
        print(f"  faults:         {int(errors)} dispatch errors, "
              f"{int(retries)} requests retried on another executable")
    dispatches = latest.get("sched.dispatches", 0)
    batched = latest.get("sched.batched_requests", 0)
    if dispatches:
        print(f"  dispatches:     {int(dispatches)}  "
              f"(mean batch {batched / dispatches:.2f} requests)")
    for key, label in (("p50_ms", "p50"), ("p95_ms", "p95"),
                       ("p99_ms", "p99")):
        if key not in latest:
            break
    else:
        print(f"  e2e latency:    p50 {latest['p50_ms']:.1f}ms  "
              f"p95 {latest['p95_ms']:.1f}ms  p99 {latest['p99_ms']:.1f}ms")


def report_variant_scan(latest: dict) -> None:
    """Variant-scan fast-lane section: printed when the featurization
    ledger counters (``serve.feat_*``) or a ``--mode serve-scan`` bench
    record rode the file. Shows the featurize-reuse ratio (hit/delta/miss
    accounting), mutant-family sizes from the affinity former, and the
    padding fraction of affinity-formed vs regular batch formations."""
    hits = latest.get("serve.feat_hits", 0)
    misses = latest.get("serve.feat_misses", 0)
    delta = latest.get("serve.feat_delta", 0)
    featurized = hits + misses + delta
    is_scan = latest.get("mode") == "serve-scan" or latest.get("scan")
    if not featurized and not is_scan:
        return
    print("-- variant scan --")
    if featurized:
        reuse = (hits + delta) / featurized
        print(f"  featurize reuse: {reuse:.1%} of {int(featurized)} "
              f"featurized requests "
              f"({int(hits)} cache hits + {int(delta)} delta-patched "
              f"mutants; {int(misses)} cold)")
    members = latest.get("sched.family_members", 0)
    batches = latest.get("sched.affinity_batches", 0)
    joins = latest.get("sched.family_inflight_joins", 0)
    if members:
        size = f"  (mean {members / batches:.1f} per batch)" if batches \
            else ""
        print(f"  families:        {int(members)} family members over "
              f"{int(batches)} affinity-formed batches{size}")
    if joins:
        print(f"  late siblings:   {int(joins)} joined their family's "
              f"in-flight batch")
    aff = latest.get("affinity_pad_p50")
    reg = latest.get("regular_pad_p50")
    if aff is not None or reg is not None:
        parts = []
        if aff is not None:
            parts.append(f"affinity-formed p50 {aff:.1%}")
        if reg is not None:
            parts.append(f"regular p50 {reg:.1%}")
        print(f"  padding:         {'  vs  '.join(parts)}")
    if latest.get("speedup_vs_cold") is not None:
        print(f"  amortized:       {latest['speedup_vs_cold']}x vs the "
              f"cold path "
              f"({latest.get('scan_ms_per_variant')}ms/variant scanned, "
              f"{latest.get('cold_ms_per_variant')}ms/variant cold)")
    if latest.get("ledger_accounted_frac") is not None:
        frac = latest["ledger_accounted_frac"]
        ok = "fully accounted" if frac >= 1.0 else "UNACCOUNTED"
        print(f"  ledger:          {frac:.1%} of requests accounted "
              f"({ok})")


def report_replay(latest: dict) -> None:
    """Record-vs-replay section: printed when a ``--mode serve-replay``
    bench record rode the file. Shows the recording source and replay
    knobs, the replay-vs-record goodput/latency diff, the structural
    verdicts the CI gate judges absolutely (exact reuse-ledger
    reproduction, byte-identical (seq, seed) outputs, trace completeness)
    and the measured recorder overhead."""
    if latest.get("mode") != "serve-replay":
        return
    knobs = (f"warp {latest.get('time_warp', 1)}x, "
             f"scale {latest.get('load_scale', 1)}x")
    print(f"-- record vs replay ({latest.get('source', '?')}, {knobs}) --")
    goodput = latest.get("goodput_rps")
    rec_goodput = latest.get("record_goodput_rps")
    if goodput is not None:
        line = f"  replay goodput:  {goodput} req/s"
        if rec_goodput:
            ratio = latest.get("replay_vs_record_goodput")
            line += f"  (recorded {rec_goodput} req/s"
            if ratio is not None:
                line += f", {ratio}x"
            line += ")"
        print(line)
    if latest.get("p50_ms") is not None:
        line = (f"  replay latency:  p50 {latest['p50_ms']}ms  "
                f"p95 {latest.get('p95_ms')}ms")
        if latest.get("record_p50_ms") is not None:
            line += (f"  (recorded p50 {latest['record_p50_ms']}ms  "
                     f"p95 {latest.get('record_p95_ms')}ms)")
        print(line)
    match = latest.get("ledger_match")
    if match is not None:
        verdict = "EXACT" if match >= 1.0 else "MISMATCH"
        print(f"  reuse ledger:    {verdict} reproduction of the "
              f"recording's hit/delta/miss ledger")
    bytes_id = latest.get("replay_bytes_identical")
    if bytes_id is not None:
        verdict = ("byte-identical" if bytes_id >= 1.0
                   else f"DIVERGED ({bytes_id:.1%} matched)")
        print(f"  (seq, seed):     {verdict} atom14 outputs across arms")
    frac = latest.get("trace_complete_fraction")
    if frac is not None:
        print(f"  replay traces:   {frac:.1%} complete")
    overhead = latest.get("recorder_overhead_frac")
    if overhead is not None:
        print(f"  recorder cost:   {overhead:.1%} goodput overhead "
              f"(on/off on the warm engine)")
    if latest.get("workload_log"):
        print(f"  recording:       {latest['workload_log']}")


def report_fleet(latest: dict) -> None:
    """Fleet-serving section: printed when a ``--mode serve-fleet`` bench
    record (or a metrics file carrying ``fleet.*`` counters) rode the
    file. Shows the per-replica goodput/occupancy table, the steal /
    drain / reroute accounting, the death-drill outcome (the zero-drop
    contract the CI gate judges absolutely) and the cross-replica trace
    verdict — one trace per request spanning the router hop."""
    counters = latest.get("fleet_counters") or {
        k: v for k, v in latest.items() if k.startswith("fleet.")
    }
    if latest.get("mode") != "serve-fleet" and not counters:
        return
    n = int(latest.get("replicas") or 0)
    speed = latest.get("fleet_speedup")
    head = f"{n} replica(s)" if n else "counters only"
    if speed is not None:
        head += (f", {speed}x goodput vs the 1-replica reference "
                 f"({latest.get('goodput_rps')} vs "
                 f"{latest.get('ref_goodput_rps')} req/s)")
    print(f"-- fleet serving ({head}) --")
    if n:
        print(f"  {'replica':<9} {'routed':>8} {'resolved ok':>12} "
              f"{'goodput req':>12}")
        for i in range(n):
            routed = counters.get(f"fleet.replica{i}.routed", 0)
            ok = counters.get(f"fleet.replica{i}.resolved_ok", 0)
            good = latest.get(f"goodput_requests_replica{i}", ok)
            print(f"  {i:<9} {int(routed):>8} {int(ok):>12} "
                  f"{int(good):>12}")
    moved = counters.get("fleet.steals", 0)
    rerouted = counters.get("fleet.rerouted", 0)
    drains = counters.get("fleet.drains", 0)
    print(f"  rebalancing:    {int(moved)} stolen, {int(rerouted)} "
          f"rerouted, {int(drains)} drain(s), "
          f"{int(counters.get('fleet.no_replica', 0))} with no live "
          f"replica")
    drill = latest.get("drill") or {}
    if drill:
        fault = drill.get("fault") or {}
        fired = "fired" if fault.get("fired") else "NOT FIRED"
        unresolved = drill.get("unresolved", 0)
        verdict = ("ZERO DROPPED" if not unresolved
                   else f"{int(unresolved)} UNRESOLVED")
        print(f"  death drill:    {fault.get('kind', '?')} replica "
              f"{fault.get('replica', '?')} at {fault.get('at_s', '?')}s "
              f"({fired}): {drill.get('completed', 0)}/"
              f"{drill.get('requests', 0)} completed, "
              f"{int(drill.get('rerouted', 0))} rerouted -> {verdict}")
    frac = latest.get("trace_complete_fraction")
    if frac is not None:
        print(f"  hop traces:     {frac:.1%} reconstruct end-to-end "
              f"across the router->replica hop")


_FLEET_EVENT_NAMES = ("fleet.steal", "fleet.drain", "fleet.degrade",
                      "fleet.reroute")


def report_fleet_timeline(events: list, max_shown: int = 20) -> None:
    """Steal/drain timeline from the router's instant events: what the
    health pump did and when, relative to the first fleet admission."""
    acts = [e for e in events if e.get("name") in _FLEET_EVENT_NAMES]
    if not acts:
        return
    admits = [e.get("ts", 0) for e in events
              if e.get("name") == "fleet.admit"]
    t0 = min(admits) if admits else min(e.get("ts", 0) for e in acts)
    reroutes = sum(1 for e in acts if e.get("name") == "fleet.reroute")
    print(f"-- fleet timeline ({len(acts)} router action(s), "
          f"{reroutes} reroute(s)) --")
    shown = 0
    for e in sorted(acts, key=lambda e: e.get("ts", 0)):
        if e.get("name") == "fleet.reroute":
            continue  # per-request noise; counted in the header
        if shown >= max_shown:
            print("  ...")
            break
        shown += 1
        args = e.get("args") or {}
        at = (e.get("ts", 0) - t0) / 1e6
        if e["name"] == "fleet.steal":
            detail = (f"moved {args.get('n')} request(s) replica "
                      f"{args.get('from_replica')} -> "
                      f"{args.get('to_replica')}")
        elif e["name"] == "fleet.drain":
            detail = (f"replica {args.get('replica')} drained "
                      f"({args.get('reason', '?')})")
        else:
            detail = (f"replica {args.get('replica')} degraded "
                      f"+{args.get('delay_s')}s/dispatch")
        print(f"  +{at:8.3f}s  {e['name']:<13} {detail}")


def report_kernels(latest: dict) -> None:
    """Kernels/precision section: printed when records carry the
    serving-dtype key (serve.dtype) or a --mode kernels microbench record
    rode the file. Shows the serving dtype and the per-kernel FLOPs
    attribution (observe.flops: tied-row vs axial vs rest) so MFU
    conversations can name the kernel responsible."""
    compile_records = latest.get("compile_records") or []
    by_kernel = latest.get("flops_by_kernel") or {}
    has_keys = (
        latest.get("dtype")
        or latest.get("mode") == "kernels" or by_kernel
        or any(c.get("dtype") for c in compile_records)
    )
    if not has_keys:
        return
    print("-- kernels / precision --")
    if latest.get("dtype"):
        print(f"  serve dtype:    {latest['dtype']}")
    if latest.get("mode") == "kernels":
        print(f"  fused-vs-stock: {latest.get('value')}x geomean "
              f"(fused {latest.get('fused_ms_total')}ms, stock "
              f"{latest.get('stock_ms_total')}ms"
              + (", interpret mode" if latest.get("interpret") else "")
              + ")")
        for sh in latest.get("shapes") or []:
            print(f"    {sh['name']:<22} fused {sh['fused_ms']:>8.3f}ms  "
                  f"stock {sh['stock_ms']:>8.3f}ms  {sh['speedup']}x")
    if by_kernel:
        total = sum(by_kernel.values()) or 1.0
        print("  executed FLOPs by kernel family:")
        for name, flops in sorted(by_kernel.items(), key=lambda kv: -kv[1]):
            print(f"    {name:<18} {flops / 1e9:>10.2f} GF  "
                  f"({flops / total:.1%})")


def report_mesh(latest: dict) -> None:
    """Mesh/sharding section: printed when records carry the mesh key
    (sharded serving, bench.py --mode serve with AF2TPU_SERVE_MESH).
    Shows the mesh shape, per-device memory (allocator HBM peaks when the
    backend exposes them, else the XLA memory-analysis program footprint
    from the compile records) and per-bucket compile times."""
    mesh = latest.get("mesh")
    compile_records = latest.get("compile_records") or []
    if not mesh and not any(c.get("mesh") for c in compile_records):
        return
    print(f"-- mesh sharding ({mesh or 'per-executable'}) --")
    if latest.get("mesh_devices"):
        print(f"  devices:        {int(latest['mesh_devices'])}")
    if latest.get("per_device_program_bytes"):
        print(
            "  per-device program footprint: "
            f"{latest['per_device_program_bytes'] / 2**20:.1f} MiB "
            "(XLA memory analysis: args + outputs + temps)"
        )
    hbm = sorted(
        (k, v) for k, v in latest.items()
        if k.startswith("hbm/device") and k.endswith("/peak_bytes")
    )
    for key, v in hbm:
        dev = key.split("/")[1]
        print(f"  {dev} HBM peak: {v / 2**30:.3f} GiB")
    if compile_records:
        print("  per-bucket executables:")
        for c in compile_records:
            extra = ""
            if c.get("program_bytes"):
                extra = f"  {c['program_bytes'] / 2**20:.1f} MiB/device"
            census = c.get("collectives") or {}
            if census:
                n = sum(v["count"] for v in census.values())
                moved = sum(v["bytes"] for v in census.values())
                extra += (f"  {n} collectives "
                          f"({moved / 2**10:.0f} KiB moved)")
            print(
                f"    bucket {c['bucket']:>5} batch {c['batch']} "
                f"mesh={c.get('mesh') or '-'}: compile "
                f"{_fmt_s(c['seconds'])}{extra}"
            )


def report_slo(latest: dict) -> None:
    """SLO section: the flattened ``slo/<spec>/<field>`` burn-rate keys a
    serve-async bench logs per spec (bench.py), plus the headline alert
    count — the multi-window verdicts the trace file carries as
    ``slo.alert`` instant events."""
    specs = sorted({
        k.split("/", 2)[1] for k in latest
        if k.startswith("slo/") and k.count("/") >= 2
    })
    if not specs and "slo_alerts" not in latest:
        return
    alerts = latest.get("slo_alerts")
    head = f", {int(alerts)} alert(s) fired" if alerts else ""
    print(f"-- SLO burn rates ({len(specs)} specs{head}) --")
    for spec in specs:
        def g(field, _s=spec):
            return latest.get(f"slo/{_s}/{field}")
        line = f"  {spec:<20}"
        fast, slow = g("fast_burn"), g("slow_burn")
        if fast is not None:
            line += f" fast burn {fast:>6.2f}  slow burn {slow:>6.2f}"
        bad, total = g("bad"), g("events")
        if total:
            line += f"  ({int(bad or 0)}/{int(total)} bad)"
        if g("alert"):
            line += "  ** ALERT **"
        print(line)


def report_hlo_contracts(path: str) -> list:
    """Static comm/memory contract section for a committed (or freshly
    ``--update``-written) hlo_contracts.json: per target the post-SPMD
    collective census, comm bytes beside FLOPs, the XLA program footprint
    and the HBM-budget verdict — the numbers ``analysis/hlo_audit.py
    --check`` diffs in CI, rendered for humans. Always returns [] (a
    malformed file raises into main()'s existing error path)."""
    with open(path) as f:
        doc = json.load(f)
    targets = doc.get("targets") or {}
    print(f"== hlo contracts {path}: {len(targets)} targets "
          f"(format {doc.get('format')}, jax {doc.get('jax_version')}, "
          f"{doc.get('n_devices')}x {doc.get('platform')}) ==")
    for name in sorted(targets):
        rec = targets[name]
        parts = rec.get("num_partitions", 1)
        head = f"  {name}: " + (
            f"{parts}-way partitioned" if rec.get("sharded")
            else "single-device"
        )
        if rec.get("program_bytes"):
            head += f", program {rec['program_bytes'] / 2**20:.2f} MiB/device"
        budget = rec.get("budget") or {}
        if budget.get("verdict"):
            head += f", budget {budget['verdict']}"
            if budget.get("headroom_frac") is not None:
                head += f" ({budget['headroom_frac']:+.1%} headroom)"
        print(head)
        census = rec.get("collectives") or {}
        if census:
            for kind in sorted(census):
                c = census[kind]
                print(f"    {kind:<20} x{c['count']:<4} "
                      f"{c['bytes'] / 2**10:>10.1f} KiB")
            ratio = rec.get("comm_bytes_per_flop")
            line = (f"    comm total: {rec.get('comm_bytes', 0) / 2**10:.1f} "
                    f"KiB moved")
            if ratio is not None:
                line += f"  ({ratio:.4g} bytes/FLOP)"
            print(line)
        elif rec.get("sharded"):
            print("    (no collectives — sharding constraints are inert)")
    return []


def report_concurrency_contracts(path: str) -> list:
    """Static layer-5 contract section for a committed (or freshly
    ``--update``-written) concurrency_contracts.json: the lock-order
    graph's named edges with their witness acquisition sites, and the
    per-class guard map — the shape ``analysis/concurrency.py --check``
    diffs in CI, rendered for humans. Always returns [] (a malformed
    file raises into main()'s existing error path)."""
    with open(path) as f:
        doc = json.load(f)
    edges = doc.get("lock_graph") or {}
    guards = doc.get("guards") or {}
    n_guards = sum(len(v) for v in guards.values())
    print(f"== concurrency contracts {path}: {len(edges)} lock-graph "
          f"edge(s), {n_guards} guarded attribute(s) across "
          f"{len(guards)} class(es) (format {doc.get('format')}) ==")
    if edges:
        print("  lock-order graph (acquire left before right):")
        for edge in sorted(edges):
            print(f"    {edge}    [{edges[edge]}]")
    else:
        print("  lock-order graph: no nested acquisitions (trivially "
              "acyclic)")
    for cls in sorted(guards):
        attrs = guards[cls]
        by_lock: dict = {}
        for attr, lock in attrs.items():
            by_lock.setdefault(lock, []).append(attr)
        print(f"  {cls}:")
        for lock in sorted(by_lock):
            print(f"    {lock} guards: {', '.join(sorted(by_lock[lock]))}")
    return []


def report_metrics(path: str) -> list:
    """Latest-value dump + per-domain sections. Returns the list of
    malformed-line descriptions (empty = clean) for main()'s summary —
    every parseable record is still reported."""
    records, errors = [], []
    with open(path) as f:
        for lineno, line in enumerate(f, start=1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError as e:
                errors.append(f"line {lineno}: {e.msg} ({line[:60]!r})")
                continue
            if isinstance(rec, dict):
                records.append(rec)
            else:
                errors.append(
                    f"line {lineno}: record is "
                    f"{type(rec).__name__}, not an object"
                )
    print(f"== metrics {path}: {len(records)} records ==")
    latest: dict = {}
    for rec in records:
        for k, v in rec.items():
            if k not in ("step", "time"):
                latest[k] = v
    for k in sorted(latest):
        # per-tensor numerics stats, per-device HBM peaks, SLO burn keys
        # and registry-snapshot flags are summarized by their sections
        # below, not dumped key by key
        if not k.startswith(("numerics/", "hbm/", "slo/", "slo.")) \
                and k != "registry":
            print(f"  {k} = {latest[k]}")

    report_train(records)
    report_scheduler(latest)
    report_variant_scan(latest)
    report_replay(latest)
    report_fleet(latest)
    report_slo(latest)
    report_mesh(latest)
    report_kernels(latest)

    compiles = latest.get("serve.compiles", latest.get("compiles"))
    hits = latest.get("serve.cache_hits", latest.get("cache_hits"))
    if compiles is not None and hits is not None:
        dispatches = compiles + hits
        rate = hits / dispatches if dispatches else 0.0
        print("-- compile/cache accounting --")
        print(f"  executable builds: {compiles}")
        print(f"  cache hits:        {hits}  "
              f"(hit rate {rate:.1%} of {dispatches} lookups)")
    if "hbm_peak_bytes" in latest:
        print(f"-- memory --\n  HBM peak: "
              f"{latest['hbm_peak_bytes'] / 2**30:.3f} GiB")
    return errors


def report_env() -> None:
    """The accelerator-relevant environment through the flight recorder's
    scrub: secret-named values redacted."""
    print("== environment (scrubbed) ==")
    for k, v in sorted(scrub_env().items()):
        if k.startswith(("AF2TPU_", "JAX_", "XLA_", "TPU_", "LIBTPU")):
            print(f"  {k}={v}")


def main(argv=None) -> int:
    args = list(argv if argv is not None else sys.argv[1:])
    flags = [a for a in args if a.startswith("-")]
    paths = [a for a in args if not a.startswith("-")]
    if "--env" in flags:
        report_env()
    if not paths:
        if "--env" in flags:
            return 0
        print(__doc__.strip().splitlines()[2].strip(), file=sys.stderr)
        return 1
    rc = 0
    parse_errors: dict = {}
    for path in paths:
        try:
            kind = classify(path)
            reporter = {
                "trace": report_trace,
                "hlo-contracts": report_hlo_contracts,
                "concurrency-contracts": report_concurrency_contracts,
            }.get(kind, report_metrics)
            errs = reporter(path)
            if errs:
                parse_errors[path] = errs
        except (OSError, json.JSONDecodeError) as e:
            print(f"ERROR reading {path}: {type(e).__name__}: {e}",
                  file=sys.stderr)
            rc = 2
    if parse_errors:
        # structured, machine-grepped by CI: one header, per-file counts,
        # first few offending lines — and a nonzero exit so a truncated
        # artifact fails the job instead of silently under-reporting
        print("== PARSE ERRORS ==", file=sys.stderr)
        for path, errs in parse_errors.items():
            print(f"  {path}: {len(errs)} malformed line(s)",
                  file=sys.stderr)
            for err in errs[:5]:
                print(f"    {err}", file=sys.stderr)
            if len(errs) > 5:
                print(f"    ... {len(errs) - 5} more", file=sys.stderr)
        rc = 2
    return rc


if __name__ == "__main__":
    sys.exit(main())
