"""Unit tests for the observability subsystem (alphafold2_tpu/observe):
tracer span emission in valid Chrome trace-event format, streaming
histogram percentiles, thread-safe counters, MetricsLogger JSONL output
and jax-free construction, memory sampler no-op behavior, and Profiler
step-window logic."""

import json
import threading
import time

import numpy as np
import pytest

from alphafold2_tpu.observe import (
    EventCounters,
    Histogram,
    MemorySampler,
    MetricsLogger,
    Profiler,
    Tracer,
)
from alphafold2_tpu.observe.tracing import load_trace_events


# ------------------------------------------------------------------ tracer


def _assert_valid_chrome_events(events):
    """Every event carries the Chrome trace-event required keys with the
    right types (what Perfetto/chrome://tracing expects)."""
    assert events, "no events emitted"
    for e in events:
        assert isinstance(e["name"], str) and e["name"]
        assert e["ph"] in ("X", "i", "C")
        assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
        assert isinstance(e["pid"], int)
        if e["ph"] == "X":
            assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
            assert isinstance(e["tid"], int)


def test_tracer_emits_nested_spans_to_file(tmp_path):
    path = str(tmp_path / "trace.json")
    tracer = Tracer(path)
    with tracer.span("outer", kind="test"):
        with tracer.span("inner"):
            time.sleep(0.01)
    tracer.instant("marker", note="hi")
    tracer.counter("mem", bytes=123)
    tracer.close()

    events = load_trace_events(path)
    _assert_valid_chrome_events(events)
    by_name = {e["name"]: e for e in events}
    assert set(by_name) == {"outer", "inner", "marker", "mem"}
    outer, inner = by_name["outer"], by_name["inner"]
    # nesting: inner starts after outer and ends before it (ts+dur)
    assert inner["ts"] >= outer["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1
    assert inner["dur"] >= 10_000 * 0.5  # slept 10ms, dur is in us
    assert outer["args"] == {"kind": "test"}


def test_tracer_file_is_chrome_loadable_streaming_array(tmp_path):
    """The on-disk form: opens with '[', one JSON object per line with a
    trailing comma — the trace-event spec's streaming JSON array (closing
    ']' optional), which is also line-parseable as JSONL."""
    path = str(tmp_path / "trace.json")
    tracer = Tracer(path)
    with tracer.span("a"):
        pass
    tracer.close()
    lines = open(path).read().splitlines()
    assert lines[0] == "["
    for line in lines[1:]:
        json.loads(line.rstrip(","))  # each line parses standalone


def test_tracer_span_records_exception_and_reraises(tmp_path):
    tracer = Tracer(str(tmp_path / "t.json"))
    with pytest.raises(ValueError):
        with tracer.span("dies"):
            raise ValueError("boom")
    (event,) = tracer.events()
    assert event["args"]["error"] == "ValueError"


def test_tracer_disabled_is_noop():
    tracer = Tracer(enabled=False)
    with tracer.span("x") as sp:
        sp.set(a=1)  # null span accepts set()
    tracer.instant("y")
    assert tracer.events() == []
    assert tracer.span_totals() == {}


def test_tracer_span_totals():
    tracer = Tracer(enabled=None, path=None)
    tracer.enabled = True  # in-memory only
    for _ in range(3):
        with tracer.span("work"):
            pass
    totals = tracer.span_totals()
    assert totals["work"]["count"] == 3
    assert totals["work"]["total_s"] >= 0.0


def test_tracer_set_attaches_args():
    tracer = Tracer(enabled=True)
    with tracer.span("s") as sp:
        sp.set(verdict="hit")
    (e,) = tracer.events()
    assert e["args"]["verdict"] == "hit"


def test_tracer_threads_get_distinct_tids():
    tracer = Tracer(enabled=True)
    barrier = threading.Barrier(4)  # all threads alive at once: the OS
    # cannot recycle a finished thread's id into another span's tid

    def work():
        with tracer.span("t"):
            barrier.wait(timeout=10)

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    tids = {e["tid"] for e in tracer.events()}
    assert len(tids) == 4


# --------------------------------------------------------------- histogram


def test_histogram_percentiles_on_known_stream():
    h = Histogram()
    for v in range(1, 1001):  # 1..1000
        h.observe(float(v))
    assert h.count == 1000
    # log-bucketed estimate: within the bucket's relative error
    assert abs(h.percentile(50) - 500) / 500 < 0.08
    assert abs(h.percentile(95) - 950) / 950 < 0.08
    assert abs(h.percentile(99) - 990) / 990 < 0.08
    snap = h.snapshot()
    assert snap["count"] == 1000
    assert snap["min"] == 1.0 and snap["max"] == 1000.0
    assert abs(snap["mean"] - 500.5) < 1e-6
    assert snap["p50"] <= snap["p95"] <= snap["p99"] <= snap["max"]


def test_histogram_zeros_and_unit_scale():
    h = Histogram()
    for _ in range(10):
        h.observe(0.0)
    h.observe(0.5)
    assert h.percentile(50) == 0.0
    snap = h.snapshot(unit_scale=1e3)
    assert snap["max"] == 500.0  # 0.5 s -> ms
    assert snap["p50"] == 0.0


def test_histogram_empty_and_invalid():
    h = Histogram()
    assert h.snapshot() == {"count": 0}
    assert h.percentile(99) == 0.0
    with pytest.raises(ValueError):
        h.observe(-1.0)
    with pytest.raises(ValueError):
        h.observe(float("nan"))
    with pytest.raises(ValueError):
        Histogram(growth=1.0)


def test_histogram_thread_safety():
    h = Histogram()

    def work():
        for v in range(1, 501):
            h.observe(float(v))

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert h.count == 2000
    assert h.snapshot()["max"] == 500.0


# ---------------------------------------------------------------- counters


def test_event_counters_thread_safe_bumps():
    """Concurrent bumps from many threads must not lose updates (the
    watchdog/heartbeat threads bump beside the dispatch path)."""
    c = EventCounters()

    def work():
        for _ in range(1000):
            c.bump("n")

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.get("n") == 8000
    assert c.snapshot() == {"n": 8000}


def test_event_counters_basics():
    c = EventCounters()
    assert c.get("missing") == 0
    assert c.bump("a") == 1
    assert c.bump("a", 4) == 5
    assert c.snapshot() == {"a": 5}


# ----------------------------------------------------------- MetricsLogger


def test_metrics_logger_jsonl_output(tmp_path, capsys):
    logger = MetricsLogger(str(tmp_path), enabled=True)
    logger.log(0, {"loss": 1.5, "note": "warm"})
    logger.log(1, {"loss": 0.5})
    lines = (tmp_path / "metrics.jsonl").read_text().splitlines()
    assert len(lines) == 2
    rec0, rec1 = (json.loads(ln) for ln in lines)
    assert rec0 == {"step": 0, "time": rec0["time"], "loss": 1.5,
                    "note": "warm"}
    assert rec1["step"] == 1 and rec1["loss"] == 0.5
    assert rec1["time"] >= rec0["time"]
    out = capsys.readouterr().out
    assert "[step 0]" in out and "loss=1.5" in out


def test_metrics_logger_disabled_writes_nothing(tmp_path, capsys):
    logger = MetricsLogger(str(tmp_path / "sub"), enabled=False)
    logger.log(0, {"loss": 1.0})
    assert not (tmp_path / "sub").exists()
    assert capsys.readouterr().out == ""


def test_metrics_logger_echo_off_keeps_stdout_clean(tmp_path, capsys):
    logger = MetricsLogger(str(tmp_path), enabled=True, echo=False)
    logger.log(0, {"v": 1})
    assert capsys.readouterr().out == ""
    assert (tmp_path / "metrics.jsonl").exists()


def test_metrics_logger_constructs_without_jax(tmp_path, monkeypatch):
    """enabled=None must fall back gracefully when jax import/process_index
    fails (tools running before jax.distributed init, or without jax)."""
    import builtins

    real_import = builtins.__import__

    def no_jax(name, *a, **kw):
        if name == "jax":
            raise ImportError("no jax in this interpreter")
        return real_import(name, *a, **kw)

    monkeypatch.setattr(builtins, "__import__", no_jax)
    logger = MetricsLogger(str(tmp_path), echo=False)
    assert logger.enabled is True
    logger.log(3, {"x": 1.0})
    assert json.loads(
        (tmp_path / "metrics.jsonl").read_text()
    )["x"] == 1.0


# ----------------------------------------------------------- MemorySampler


def test_memory_sampler_graceful_without_stats():
    class Dev:
        id = 0

        def memory_stats(self):
            return None  # CPU-backend behavior

    s = MemorySampler(devices=[Dev()])
    assert s.sample() == []
    assert s.peak_bytes() is None
    s.log_to(MetricsLogger(enabled=False))  # must not raise


def test_memory_sampler_reads_stats_and_logs(tmp_path):
    class Dev:
        def __init__(self, i, peak):
            self.id = i
            self._peak = peak

        def memory_stats(self):
            return {"bytes_in_use": 10, "peak_bytes_in_use": self._peak,
                    "bytes_limit": 100}

    s = MemorySampler(devices=[Dev(0, 40), Dev(1, 70)])
    recs = s.sample()
    assert len(recs) == 2
    assert s.peak_bytes() == 70
    logger = MetricsLogger(str(tmp_path), enabled=True, echo=False)
    s.log_to(logger)
    rec = json.loads((tmp_path / "metrics.jsonl").read_text())
    assert rec["hbm_peak_bytes"] == 70 and rec["hbm_devices"] == 2

    tracer = Tracer(enabled=True)
    s.counter_to(tracer)
    counters = [e for e in tracer.events() if e["ph"] == "C"]
    assert len(counters) == 2
    assert counters[0]["args"]["peak_bytes_in_use"] == 40


def test_memory_sampler_on_real_backend():
    """Whatever this host's backend exposes, sample() must not raise and
    peak_bytes() must be a positive int or None."""
    peak = MemorySampler().peak_bytes()
    assert peak is None or peak > 0


# ---------------------------------------------------------------- Profiler


class _FakeProfiler:
    def __init__(self):
        self.calls = []

    def install(self, monkeypatch):
        import jax

        monkeypatch.setattr(
            jax.profiler, "start_trace",
            # the options (Python tracer off) are the Profiler's business
            lambda d, **options: self.calls.append(("start", d)),
        )
        monkeypatch.setattr(
            jax.profiler, "stop_trace", lambda: self.calls.append(("stop",))
        )


def test_profiler_window_boundaries(monkeypatch, tmp_path):
    fake = _FakeProfiler()
    fake.install(monkeypatch)
    p = Profiler(str(tmp_path), steps=(2, 4))
    for step in range(6):
        p.maybe_start(step)
        p.maybe_stop(step)
    # starts exactly at step 2; stop fires at the first step >= 4 — but
    # maybe_stop(2) and (3) run while active and must NOT stop early
    assert fake.calls == [("start", str(tmp_path)), ("stop",)]


def test_profiler_no_dir_never_starts(monkeypatch):
    fake = _FakeProfiler()
    fake.install(monkeypatch)
    p = Profiler(None, steps=(0, 1))
    for step in range(3):
        p.maybe_start(step)
        p.maybe_stop(step)
    assert fake.calls == []


def test_profiler_reentry_safety(monkeypatch, tmp_path):
    """Calling maybe_start repeatedly at the start step must start ONE
    trace; maybe_stop past the window with no active trace is a no-op."""
    fake = _FakeProfiler()
    fake.install(monkeypatch)
    p = Profiler(str(tmp_path), steps=(1, 2))
    p.maybe_start(1)
    p.maybe_start(1)  # re-entry: already active
    assert fake.calls.count(("start", str(tmp_path))) == 1
    p.maybe_stop(5)
    p.maybe_stop(6)  # already stopped
    assert fake.calls == [("start", str(tmp_path)), ("stop",)]
    # a fresh window instance would start again at its own start step
    p.maybe_start(1)
    assert fake.calls[-1] == ("start", str(tmp_path))


# ------------------------------------------------------- train-loop wiring


def test_train_loop_emits_step_spans(tmp_path):
    """train() with train.trace_events set writes a Chrome trace with one
    train.step span per executed step (plus batch-fetch spans)."""
    from alphafold2_tpu.config import (
        Config, DataConfig, ModelConfig, TrainConfig,
    )
    from alphafold2_tpu.train.loop import train

    path = str(tmp_path / "train_trace.json")
    cfg = Config(
        model=ModelConfig(dim=32, depth=1, heads=2, dim_head=16,
                          max_seq_len=64, bfloat16=False),
        data=DataConfig(crop_len=16, msa_depth=2, msa_len=16, batch_size=1,
                        min_len_filter=8),
        train=TrainConfig(num_steps=2, gradient_accumulate_every=1,
                          warmup_steps=1, log_every=10, trace_events=path),
    )
    train(cfg)
    events = load_trace_events(path)
    _assert_valid_chrome_events(events)
    steps = [e for e in events if e["name"] == "train.step"]
    assert len(steps) == 2
    assert [e["args"]["step"] for e in steps] == [0, 1]
    assert any(e["name"] == "train.next_batch" for e in events)


# ------------------------------------------------------------- shim imports


def test_train_observe_shim_reexports():
    from alphafold2_tpu.train import observe as shim

    assert shim.MetricsLogger is MetricsLogger
    assert shim.EventCounters is EventCounters
    assert shim.Profiler is Profiler
    assert shim.Tracer is Tracer
    assert shim.Histogram is Histogram
