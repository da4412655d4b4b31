"""Workload capture & deterministic replay plane (observe/workload.py).

The load-bearing contracts: the scrubbed default log leaks neither raw
sequences nor caller-controlled metadata (parent hints are one-way
hashed, error text never recorded) while keeping scan families visible
via edit summaries; ``build_replay`` reproduces timing/warp/scale
semantics deterministically; ``synthetic_diurnal`` is seeded; the
FlightRecorder's incident dumps carry the scrubbed workload tail; and a
combined affinity + dedup run reconstructs every lifecycle with the
recorder seeing a submit for every resolve."""

import json
import os

import numpy as np
import pytest

from alphafold2_tpu.config import (
    Config,
    DataConfig,
    ModelConfig,
    ServeConfig,
)
from alphafold2_tpu.observe import EventCounters, FlightRecorder, Tracer
from alphafold2_tpu.observe.tracectx import trace_completeness
from alphafold2_tpu.observe.workload import (
    WorkloadRecorder,
    build_replay,
    derivation_fingerprint,
    load_workload,
    replayable_reason,
    synthetic_diurnal,
)
from alphafold2_tpu.serve import (
    AsyncServeFrontend,
    ServeRequest,
    ServeResult,
)

SECRET = "PLANTED_API_TOKEN_hunter2"
SEQUENCE = "MKVLITHDSAGE"


def _cfg(buckets=(8, 16), max_batch=4, **serve_kw):
    serve_kw.setdefault("mds_iters", 10)
    return Config(
        model=ModelConfig(dim=32, depth=1, heads=2, dim_head=16,
                          max_seq_len=3 * max(buckets), bfloat16=False),
        data=DataConfig(msa_depth=2),
        serve=ServeConfig(buckets=buckets, max_batch=max_batch, **serve_kw),
    )


class FakeClock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now

    def advance(self, dt):
        self.now += dt


class TracingFakeEngine:
    def __init__(self, cfg):
        self.cfg = cfg
        self.buckets = cfg.serve.buckets
        self.max_batch = cfg.serve.max_batch
        self.mesh_desc = None
        self.counters = EventCounters()
        self.tracer = Tracer(enabled=True)
        self.dispatched = []

    def batch_for(self, bucket):
        return self.max_batch

    def dispatch_batch(self, bucket, reqs):
        self.dispatched.append((bucket, [r.seq for r in reqs]))
        return [
            ServeResult(
                seq=r.seq, bucket=bucket,
                atom14=np.zeros((len(r.seq), 14, 3), np.float32),
                latency_s=1e-3,
                trace_id=r.trace.trace_id if r.trace else None,
            )
            for r in reqs
        ]

    def retry_bucket(self, bucket):
        i = self.buckets.index(bucket)
        return self.buckets[i + 1] if i + 1 < len(self.buckets) else None


def _frontend(**serve_kw):
    serve_kw.setdefault("dwell_ms", 50.0)
    eng = TracingFakeEngine(_cfg(**serve_kw))
    clock = FakeClock()
    fe = AsyncServeFrontend(eng, clock=clock, start=False)
    return fe, eng, clock


def _recorded(path=None, record_raw=False, **serve_kw):
    fe, eng, clock = _frontend(**serve_kw)
    rec = WorkloadRecorder(
        path=path, record_raw=record_raw,
        buckets=eng.buckets, msa_depth=2, clock=clock,
    )
    fe.add_submit_observer(rec.on_submit)
    fe.add_observer(rec.observe)
    return fe, eng, clock, rec


# -------------------------------------------------------------- recording


def test_recorder_sees_submit_and_resolve_linked_by_trace():
    fe, eng, clock, rec = _recorded()
    req = ServeRequest("ACDEFG", seed=3, priority=1, deadline_s=5.0)
    h1 = fe.submit(req, priority=1)
    clock.advance(0.25)
    h2 = fe.submit("MKVLIT")
    fe.pump()
    assert h1.result(0).ok and h2.result(0).ok
    events = rec.events()
    submits = [e for e in events if e["kind"] == "submit"]
    resolves = [e for e in events if e["kind"] == "resolve"]
    assert len(submits) == 2 and len(resolves) == 2
    first = submits[0]
    assert first["trace"] == req.trace.trace_id
    assert first["t"] == 0.0  # stream t0 anchors at the first arrival
    assert submits[1]["t"] == pytest.approx(0.25)
    assert first["len"] == 6 and first["seed"] == 3
    assert first["priority"] == 1 and first["deadline_s"] == 5.0
    assert first["fp"] == derivation_fingerprint("ACDEFG", 8, 2, 3)
    resolved_traces = {e["trace"] for e in resolves}
    assert resolved_traces == {s["trace"] for s in submits}
    assert all(e["status"] == "ok" for e in resolves)


def test_scrubbed_log_leaks_no_sequence_and_no_planted_secret(tmp_path):
    # satellite 6 negative control: a secret-shaped parent hint and a
    # real sequence go in; neither literal may reach the scrubbed JSONL
    log = tmp_path / "wl.jsonl"
    fe, eng, clock, rec = _recorded(path=str(log))
    fe.submit(ServeRequest(SEQUENCE, parent_id=SECRET))
    fe.pump()
    rec.close()
    text = log.read_text()
    assert SECRET not in text
    assert "hunter2" not in text
    assert SEQUENCE not in text
    # the hint survives as a hash: same secret -> same label, so
    # affinity semantics are preserved without the content
    ev = json.loads(text.splitlines()[0])
    assert ev["kind"] == "submit" and len(ev["parent"]) == 16


def test_record_raw_opt_in_adds_sequence_but_still_hashes_parent(tmp_path):
    log = tmp_path / "wl_raw.jsonl"
    fe, eng, clock, rec = _recorded(path=str(log), record_raw=True)
    fe.submit(ServeRequest(SEQUENCE, parent_id=SECRET))
    fe.pump()
    rec.close()
    text = log.read_text()
    assert SEQUENCE in text  # the opt-in's whole point
    assert SECRET not in text  # parent hints are hashed EVEN with raw


def test_resolve_events_never_carry_error_text():
    rec = WorkloadRecorder()
    boom = ServeResult(seq="ACDEFG", bucket=8, status="error",
                       error=f"dispatch blew up on {SECRET}",
                       trace_id="t-1", latency_s=0.5)
    rec.observe(boom, priority=0)
    (ev,) = rec.events()
    assert ev["status"] == "error" and ev["trace"] == "t-1"
    assert SECRET not in json.dumps(ev)


def test_edit_summary_keeps_scan_families_visible_when_scrubbed():
    fe, eng, clock, rec = _recorded()
    parent = "ACDEFGHIKLMN"
    mutant = parent[:5] + "W" + parent[6:]
    fe.submit(ServeRequest(parent, seed=1))
    fe.submit(ServeRequest(mutant, seed=1))
    fe.pump()
    submits = [e for e in rec.events() if e["kind"] == "submit"]
    assert "edits" not in submits[0]
    assert submits[1]["edits"] == 1 and submits[1]["edit_pos"] == [5]
    assert submits[1]["parent_fp"] == submits[0]["fp"]
    assert "seq" not in submits[1]  # family visible WITHOUT content


def test_recorder_never_raises_into_the_serving_path():
    rec = WorkloadRecorder()
    rec.observe(object(), priority=0)  # wrong shape entirely
    assert rec.errors == 1 and rec.events() == []


def test_tail_and_family_by_trace():
    fe, eng, clock, rec = _recorded(affinity_batching=True)
    for i in range(12):
        fe.submit(ServeRequest("ACDEFG"[: 4 + i % 3] + "GG", seed=i,
                               parent_id="famX"))
    fe.pump()
    assert len(rec.tail(5)) == 5
    fams = rec.family_by_trace()
    assert len(fams) == 12
    hashed = {v for v in fams.values() if v}
    assert hashed and all(len(v) == 16 for v in hashed)
    assert "hint:famX" not in hashed  # family labels are hashed too


# ----------------------------------------------------------------- replay


def test_load_workload_tolerates_torn_tail(tmp_path):
    log = tmp_path / "torn.jsonl"
    evs = synthetic_diurnal(seed=1, requests=4, buckets=(12, 16))
    lines = [json.dumps(e) for e in evs]
    lines.append(json.dumps({"v": 1, "kind": "summary", "requests": 4}))
    log.write_text("\n".join(lines) + '\n{"v": 1, "kind": "sub')
    loaded = load_workload(str(log))
    assert len(loaded["submits"]) == 4
    assert loaded["summary"]["requests"] == 4
    offsets = [e["t"] for e in loaded["submits"]]
    assert offsets == sorted(offsets)


def test_build_replay_warp_and_scale_semantics():
    evs = synthetic_diurnal(seed=2, requests=6, buckets=(12, 16))
    base = build_replay(evs)
    warped = build_replay(evs, time_warp=2.0, load_scale=3)
    assert len(base) == 6 and len(warped) == 18
    assert [t for t, _ in warped] == sorted(t for t, _ in warped)
    base_off = sorted(t for t, _ in base)
    warp_off = sorted(set(t for t, _ in warped))
    assert warp_off == pytest.approx([t / 2.0 for t in base_off])
    # copies are real new work: same seq, distinct seeds
    by_seq = {}
    for _, req in warped:
        by_seq.setdefault(req.seq, set()).add(req.seed)
    for seq, seeds in by_seq.items():
        originals = {r.seed for _, r in base if r.seq == seq}
        assert len(seeds) == 3 * len(originals)


def test_build_replay_rejects_scrubbed_logs_and_bad_args():
    evs = synthetic_diurnal(seed=3, requests=3, buckets=(12, 16))
    scrubbed = [{k: v for k, v in e.items() if k != "seq"} for e in evs]
    assert replayable_reason(evs) is None
    assert "no raw sequence" in replayable_reason(scrubbed)
    assert "no submit events" in replayable_reason([])
    with pytest.raises(ValueError, match="no raw sequence"):
        build_replay(scrubbed)
    with pytest.raises(ValueError, match="time_warp"):
        build_replay(evs, time_warp=0.0)
    with pytest.raises(ValueError, match="load_scale"):
        build_replay(evs, load_scale=0)


def test_synthetic_diurnal_is_seeded_and_carries_scan_traffic():
    a = synthetic_diurnal(seed=7, requests=40)
    b = synthetic_diurnal(seed=7, requests=40)
    assert a == b  # byte-for-byte deterministic per seed
    assert a != synthetic_diurnal(seed=8, requests=40)
    keys = [(e["seq"], e["seed"]) for e in a]
    assert len(set(keys)) < len(keys)  # dup traffic present
    assert any("parent" in e for e in a)  # mutant families present
    assert all(e["bucket"] >= e["len"] for e in a)
    offsets = [e["t"] for e in a]
    assert offsets == sorted(offsets) and offsets[0] > 0


# ----------------------------------------------- flightrec workload tail


def test_flightrec_dump_includes_scrubbed_workload_tail(tmp_path):
    fe, eng, clock, rec = _recorded()
    fe.submit(ServeRequest(SEQUENCE, parent_id=SECRET))
    clock.advance(0.051)
    fe.pump()
    fr = FlightRecorder(directory=str(tmp_path)).attach_workload(rec.tail)
    path = fr.dump("test_incident")
    doc = json.loads(open(path).read())
    tail = doc["workload_tail"]
    assert [e["kind"] for e in tail] == ["submit", "resolve"]
    blob = json.dumps(tail)
    assert SECRET not in blob and SEQUENCE not in blob


def test_flightrec_dump_without_workload_has_no_tail_key(tmp_path):
    fr = FlightRecorder(directory=str(tmp_path))
    doc = json.loads(open(fr.dump("no_tail")).read())
    assert "workload_tail" not in doc


# ------------------------------------- combined lifecycles (satellite 3)


def test_affinity_dedup_and_admission_reconstruct_completely():
    """Affinity batching + duplicate dedup joins + plain admission in one
    run: every lifecycle reconstructs to a complete trace AND the workload
    recorder holds a submit event for every resolve it saw."""
    fe, eng, clock, rec = _recorded(
        affinity_batching=True, dwell_ms=50.0, max_batch=4
    )
    parent = "ACDEFGHIKLMN"
    muts = [parent[:p] + "W" + parent[p + 1:] for p in (2, 6, 9)]
    handles = [fe.submit(ServeRequest("WYTSARQQ", seed=1))]  # head, no fam
    for m in muts:
        handles.append(fe.submit(ServeRequest(m, seed=1, parent_id="famA")))
    # duplicate (seq, seed): the follower joins the leader's flight
    handles.append(fe.submit(ServeRequest("WYTSARQQ", seed=1)))
    clock.advance(0.051)
    fe.pump()
    results = [h.result(0) for h in handles]
    assert all(r.ok for r in results), [r.status for r in results]
    assert eng.counters.get("sched.affinity_batches") >= 1
    assert eng.counters.get("sched.inflight_dedup") >= 1
    assert eng.counters.get("sched.family_members") >= 3
    ids = [r.trace_id for r in results]
    summary = trace_completeness(eng.tracer.events(), ids)
    assert summary["fraction"] == 1.0, summary
    # recorder-side closure: a submit for every resolve, by trace id
    submits = {e["trace"] for e in rec.events() if e["kind"] == "submit"}
    resolves = [e["trace"] for e in rec.events() if e["kind"] == "resolve"]
    assert len(resolves) == len(handles)
    assert set(resolves) <= submits


# --------------------------------------------------- real-engine cost ledger


def test_served_results_carry_cost_ledger():
    from alphafold2_tpu.serve import ServeEngine

    eng = ServeEngine(_cfg(buckets=(16,), feature_cache_size=16))
    try:
        results = eng.predict_many(
            [ServeRequest("ACDEFGHIKLMN", seed=s) for s in range(3)]
        )
        for r in results:
            assert r.ok and r.cost is not None
            for key in ("queue_wait_s", "device_share_s",
                        "compile_share_s", "flops_share", "pad_fraction"):
                assert key in r.cost and r.cost[key] >= 0
            assert 0.0 <= r.cost["pad_fraction"] < 1.0
        # one compile amortized over the batch's real members
        assert results[0].cost["compile_share_s"] > 0
        assert results[0].cost["device_share_s"] > 0
    finally:
        eng.close()
