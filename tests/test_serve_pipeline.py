"""Pipelined dispatch tests (serve/pipeline.py + the engine's staged path).

The acceptance contract pinned here: the pipelined dispatch path must be
byte-identical to the serial path for the same (seq, seed) — including
batch-padded slots and requests admitted into an in-flight formation —
while faults in any stage surface as structured error results (the
completion worker never wedges), donation intent demonstrably reaches
XLA, and the new device_idle_frac metric / "pipeline" record key are
computed and gated the way bench.py and observe/regress.py claim.
"""

import threading

import numpy as np
import pytest

from alphafold2_tpu.config import (
    Config,
    DataConfig,
    ModelConfig,
    ServeConfig,
)
from alphafold2_tpu.observe.regress import comparable_reason
from alphafold2_tpu.observe.tracing import (
    Tracer,
    device_idle_fraction,
    merge_intervals,
)
from alphafold2_tpu.serve import (
    AsyncServeFrontend,
    DispatchHandle,
    FaultPlan,
    PipelineBatch,
    ServeEngine,
    ServeRequest,
    formation_ripe,
)


def _cfg(buckets=(8, 16), max_batch=2, **serve_kw):
    serve_kw.setdefault("mds_iters", 10)
    return Config(
        model=ModelConfig(dim=32, depth=1, heads=2, dim_head=16,
                          max_seq_len=3 * max(buckets), bfloat16=False),
        data=DataConfig(msa_depth=2),
        serve=ServeConfig(buckets=buckets, max_batch=max_batch, **serve_kw),
    )


@pytest.fixture(scope="module")
def engine():
    """Pipelined engine (the config default: depth 2)."""
    eng = ServeEngine(_cfg())
    assert eng.pipeline is not None and eng.pipeline_desc == "depth2"
    return eng


# ------------------------------------------------------- pure-host pieces


def test_formation_ripe():
    assert not formation_ripe(0, 4, 99.0, 0.05)  # empty never ripens
    assert formation_ripe(4, 4, 0.0, 0.05)  # full fires without dwell
    assert not formation_ripe(1, 4, 0.01, 0.05)  # under-full, inside dwell
    assert formation_ripe(1, 4, 0.05, 0.05)  # dwell expiry fires partial
    assert formation_ripe(1, 0, 0.0, 9.0)  # degenerate fill clamps to 1


def test_pipeline_batch_join_seal_semantics():
    b = PipelineBatch(8, [("r0",)], fill=3)
    assert b.try_join(("r1",)) and b.try_join(("r2",))
    assert not b.try_join(("r3",))  # at fill
    assert b.next_member(0) == ("r0",) and b.next_member(2) == ("r2",)
    assert b.next_member(3) is None  # drained: seals the formation
    assert b.sealed and not b.try_join(("late",))
    assert b.members == [("r0",), ("r1",), ("r2",)]


def test_dispatch_handle_resolution_and_callbacks():
    h = DispatchHandle(PipelineBatch(8, [], fill=1))
    with pytest.raises(TimeoutError):
        h.result(timeout=0.01)
    seen = []
    h.add_done_callback(seen.append)
    h.add_done_callback(lambda _r: 1 / 0)  # must not break resolution
    h._resolve(["done"])
    assert h.done() and h.result(0) == ["done"]
    assert seen == [["done"]]
    h.add_done_callback(seen.append)  # post-resolution: runs immediately
    assert seen == [["done"], ["done"]]


def test_device_idle_fraction_from_synthetic_spans():
    us = 1e6

    def span(name, start_s, dur_s):
        return {"ph": "X", "name": name, "ts": start_s * us,
                "dur": dur_s * us}

    # dispatch 0-1s, fetch 1.5-2s: window 2s, busy 1.5s -> idle 0.25
    events = [
        span("serve.dispatch", 0.0, 1.0),
        span("serve.device_get", 1.5, 0.5),
        span("serve.featurize", 0.0, 2.0),  # host span: not device time
    ]
    out = device_idle_fraction(events)
    assert out["dispatches"] == 1
    assert out["window_s"] == pytest.approx(2.0)
    assert out["busy_s"] == pytest.approx(1.5)
    assert out["device_idle_frac"] == pytest.approx(0.25)
    # no serve.dispatch spans -> no window to judge
    assert device_idle_fraction([span("serve.device_get", 0, 1)]) is None
    assert device_idle_fraction([]) is None
    # overlapping spans merge rather than double-count
    assert merge_intervals([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]


def test_regress_refuses_pipeline_variant_cross_comparison():
    base = {"metric": "serve cpu", "device": "cpu", "pipeline": "off",
            "value": 10.0}
    cur = dict(base, pipeline="depth2")
    reason = comparable_reason(cur, base)
    assert reason is not None and "pipeline" in reason
    assert comparable_reason(dict(base), base) is None


# ------------------------------------------------- real-engine contracts


def test_pipelined_byte_identical_to_serial_with_padded_slots(engine):
    """Same (seq, seed) stream through the pipelined and the serial path:
    identical bytes out, including the chunk that dispatches with a
    batch-padding slot (3 requests at max_batch=2) and a second bucket."""
    seqs = ["ACDEFG", "MKVLIT", "WY", "ACDEFGHKLMNP"]
    reqs = [ServeRequest(s, seed=i) for i, s in enumerate(seqs)]
    serial_eng = ServeEngine(
        _cfg(pipeline_depth=0), params=engine.params
    )
    assert serial_eng.pipeline is None and serial_eng.pipeline_desc == "off"

    piped = engine.predict_many(reqs)
    serial = serial_eng.predict_many(
        [ServeRequest(s, seed=i) for i, s in enumerate(seqs)]
    )
    assert [r.status for r in piped] == ["ok"] * len(seqs)
    # the padded chunk really dispatched with a dummy slot
    assert engine.counters.get("serve.padded_slots") >= 1
    for p, s in zip(piped, serial):
        assert p.seq == s.seq and p.bucket == s.bucket
        assert p.atom14.tobytes() == s.atom14.tobytes()
        assert p.backbone.tobytes() == s.backbone.tobytes()
        assert p.weights.tobytes() == s.weights.tobytes()
    # pipelined timing semantics still span arrival -> completion
    assert all(
        r.latency_s == pytest.approx(r.queue_wait_s + r.dispatch_s)
        for r in piped
    )


def test_inflight_admitted_request_byte_identical(engine, monkeypatch):
    """A request joined into an in-flight formation (continuous batching)
    comes back byte-identical to the same (seq, seed) served serially in
    the same two-request batch."""
    eng = ServeEngine(_cfg(), params=engine.params)
    gate = threading.Event()
    started = threading.Event()
    orig = ServeEngine._featurize_one

    def gated(self, bucket, req):
        started.set()
        assert gate.wait(30), "test gate never opened"
        return orig(self, bucket, req)

    monkeypatch.setattr(ServeEngine, "_featurize_one", gated)
    r1, r2 = ServeRequest("ACDEFG", seed=3), ServeRequest("MKVLIT", seed=4)
    handle = eng.dispatch_batch_async(8, [r1], joinable=True)
    assert started.wait(30)  # host stage is inside member 0's featurize
    assert handle.try_join(r2)  # formation still open: joins in flight
    gate.set()
    got = handle.result(timeout=180)
    monkeypatch.undo()
    assert [r.status for r in got] == ["ok", "ok"]
    assert not handle.try_join(ServeRequest("WY", seed=5))  # sealed

    serial_eng = ServeEngine(_cfg(pipeline_depth=0), params=engine.params)
    serial = serial_eng.dispatch_batch(8, [
        ServeRequest("ACDEFG", seed=3), ServeRequest("MKVLIT", seed=4),
    ])
    for p, s in zip(got, serial):
        assert p.seq == s.seq
        assert p.atom14.tobytes() == s.atom14.tobytes()
        assert p.weights.tobytes() == s.weights.tobytes()


def test_donation_takes_effect_for_standard_buckets(engine):
    """The donation audit (satellite): every standard-bucket executable
    asked XLA to donate the four request buffers, and XLA's unusable-
    donation report (int/bool inputs cannot alias f32 outputs) was
    captured into the compile record instead of silently suppressed."""
    # compile here rather than lean on an earlier test having used the
    # shared fixture engine: under xdist/-k this test may be its first user
    engine.predict_many([ServeRequest("ACDEFG", seed=0)])
    assert engine.compile_records
    for rec in engine.compile_records:
        assert rec["donated_args"] == 4  # seq, msa, mask, msa_mask
        # all four are int32/bool feature buffers: XLA reports every one
        # unaliasable — donation still releases them during execution
        assert rec["donation_unusable"] == 4

    off = ServeEngine(
        _cfg(donate_buffers=False), params=engine.params
    )
    off.predict_many([ServeRequest("ACDEFG", seed=0)])
    assert off.compile_records
    for rec in off.compile_records:
        assert "donated_args" not in rec
        assert "donation_unusable" not in rec


@pytest.mark.parametrize("stage", ["transfer", "compute", "fetch"])
def test_stage_fault_yields_structured_errors_not_a_wedge(engine, stage):
    """An injected fault in any pipeline stage resolves the future with
    structured per-request errors — the completion worker never wedges —
    and the very next dispatch succeeds (fault budget expired)."""
    plan = FaultPlan(fail_bucket=8, times=1, fail_stage=stage)
    eng = ServeEngine(_cfg(), params=engine.params, faults=plan)
    out = eng.predict_many([ServeRequest("ACDEFG", seed=0),
                            ServeRequest("MK", seed=1)])
    assert [r.status for r in out] == ["error", "error"]
    assert all("InjectedFault" in r.error and stage in r.error for r in out)
    assert plan.fired == [{"dispatch": 1, "bucket": 8, "stage": stage}]
    assert eng.stats()["serve.dispatch_errors"] == 1
    ok = eng.predict_many([ServeRequest("ACDEFG", seed=0)])[0]
    assert ok.ok and np.all(np.isfinite(ok.atom14))


def test_pipeline_emits_device_spans_and_batch_marker(engine):
    """The pipelined path's spans feed device_idle_fraction: dispatch and
    device_get spans carry dispatch_index, the retroactive serve.batch
    span is marked pipelined, and the idle fraction is computable."""
    tracer = Tracer(enabled=True)
    eng = ServeEngine(_cfg(), params=engine.params, tracer=tracer)
    eng.predict_many([ServeRequest("ACDEFG", seed=0),
                      ServeRequest("ACDEFGHKLMNP", seed=1)])
    events = tracer.events()
    spans = {e["name"] for e in events if e.get("ph") == "X"}
    assert {"serve.featurize", "serve.device_put", "serve.dispatch",
            "serve.device_get", "serve.unpad", "serve.batch"} <= spans
    batch_spans = [e for e in events if e.get("name") == "serve.batch"]
    assert batch_spans and all(
        (e.get("args") or {}).get("pipelined") for e in batch_spans
    )
    dispatch_args = [
        (e.get("args") or {}) for e in events
        if e.get("name") == "serve.dispatch"
    ]
    assert dispatch_args and all(
        a.get("dispatch_index") for a in dispatch_args
    )
    idle = device_idle_fraction(events)
    assert idle is not None and 0.0 <= idle["device_idle_frac"] <= 1.0
    assert idle["dispatches"] == len(dispatch_args)


def test_depth_one_pipeline_and_backpressure(engine):
    """depth=1 serializes in-flight batches (submit blocks until the
    previous batch completes) but still produces correct results."""
    eng = ServeEngine(_cfg(pipeline_depth=1), params=engine.params)
    assert eng.pipeline_desc == "depth1"
    out = eng.predict_many(
        [ServeRequest("ACDEFG", seed=i) for i in range(5)]
    )
    assert all(r.ok for r in out)
    with pytest.raises(ValueError):
        ServeEngine(_cfg(pipeline_depth=-1), params=engine.params)


def test_frontend_inflight_admission_joins_forming_batch(
    engine, monkeypatch
):
    """A request arriving while a bucket's formation sits in the host
    stage joins that in-flight batch (no queue slot, no dwell wait) and
    resolves from the same dispatch."""
    eng = ServeEngine(_cfg(dwell_ms=0.0), params=engine.params)
    gate = threading.Event()
    started = threading.Event()
    orig = ServeEngine._featurize_one

    def gated(self, bucket, req):
        started.set()
        assert gate.wait(30), "test gate never opened"
        return orig(self, bucket, req)

    monkeypatch.setattr(ServeEngine, "_featurize_one", gated)
    fe = AsyncServeFrontend(eng, start=False)
    assert fe.inflight_admission  # engine is pipelined + config default on
    h1 = fe.submit(ServeRequest("ACDEFG", seed=1))
    assert fe.pump() == 1  # zero dwell: the single request dispatches
    assert started.wait(30)
    h2 = fe.submit(ServeRequest("MKVLIT", seed=2))  # joins in flight
    assert fe.stats()["sched.inflight_admitted"] == 1
    gate.set()
    out1, out2 = h1.result(180), h2.result(180)
    monkeypatch.undo()
    assert out1.ok and out2.ok
    assert fe.stats()["sched.dispatches"] == 1  # one shared dispatch
    assert fe.stats()["sched.batched_requests"] == 2
    # the admitted request's result is byte-identical to the serial batch
    serial_eng = ServeEngine(_cfg(pipeline_depth=0), params=engine.params)
    serial = serial_eng.dispatch_batch(8, [
        ServeRequest("ACDEFG", seed=1), ServeRequest("MKVLIT", seed=2),
    ])
    assert out2.atom14.tobytes() == serial[1].atom14.tobytes()


def test_inflight_admission_disabled_by_config(engine):
    eng = ServeEngine(
        _cfg(inflight_admission=False), params=engine.params
    )
    fe = AsyncServeFrontend(eng, start=False)
    assert not fe.inflight_admission


def test_predict_many_overlaps_host_and_device(engine):
    """The tentpole's mechanism, pinned structurally: with several batches
    in flight, some batch's host stage (featurize/device_put) runs inside
    another batch's device window — the trace intervals overlap."""
    tracer = Tracer(enabled=True)
    eng = ServeEngine(_cfg(), params=engine.params, tracer=tracer)
    eng.warmup()  # keep compiles out of the overlap window
    reqs = [ServeRequest("ACDEFG", seed=i) for i in range(8)]
    eng.predict_many(reqs)
    host, dev = {}, {}
    for e in tracer.events():
        if e.get("ph") != "X":
            continue
        args = e.get("args") or {}
        idx = args.get("dispatch_index")
        if idx is None:
            continue
        iv = (e["ts"] / 1e6, (e["ts"] + e.get("dur", 0)) / 1e6)
        if e["name"] in ("serve.featurize", "serve.device_put"):
            host.setdefault(idx, []).append(iv)
        elif e["name"] in ("serve.dispatch", "serve.device_get"):
            dev.setdefault(idx, []).append(iv)
    assert len(dev) == 4  # 8 requests / max_batch 2
    overlap = 0.0
    for i, dev_ivs in dev.items():
        others = merge_intervals(
            [iv for j, ivs in host.items() if j != i for iv in ivs]
        )
        for ds, de in merge_intervals(dev_ivs):
            for hs, he in others:
                overlap += max(0.0, min(de, he) - max(ds, hs))
    assert overlap > 0.0, "no host stage ran inside another device window"


def test_close_shuts_down_stage_workers(engine):
    eng = ServeEngine(_cfg(), params=engine.params)
    assert eng.predict_many([ServeRequest("AC", seed=0)])[0].ok
    eng.close()
    with pytest.raises(RuntimeError):  # executors refuse post-shutdown work
        eng.dispatch_batch_async(8, [ServeRequest("AC", seed=1)])
