"""Unified observability subsystem: spans, histograms, telemetry.

The reference's only observability is ``print`` (SURVEY.md S5.1/S5.5);
this package is the first-class answer:

- :mod:`tracing` — ``Tracer``/``Span``: nested span tracing emitted as
  Chrome-trace-event JSONL, loadable in Perfetto / ``chrome://tracing``,
  wired through the serve request lifecycle, the train step and bench.
- :mod:`histogram` — streaming log-bucketed ``Histogram`` with
  p50/p95/p99 snapshots (per-request latency, queue wait, batch occupancy,
  pad ratio).
- :mod:`metrics` — ``MetricsLogger`` (structured JSONL + stdout) and
  thread-safe ``EventCounters`` (compile counts, cache hits, totals).
- :mod:`memory` — ``MemorySampler`` over ``device.memory_stats()`` (HBM
  peaks; graceful no-op on backends that expose none).
- :mod:`profiler` — ``Profiler``: jax.profiler XLA trace over a step
  window (TensorBoard/XProf), unchanged from the original train hook.
- :mod:`numerics` — in-graph per-tensor telemetry: ``tag(name, x)``
  collects L2/max-abs/NaN/Inf stats as auxiliary jit outputs (zero ops
  when disabled), powering the train loop's NaN-triage reports.
- :mod:`flops` — the tree's single ``cost_analysis()`` parser: flops /
  bytes per compiled executable, peak-FLOPs tables and uniform MFU for
  bench, serve and the microbenchmarks.
- :mod:`regress` — device-keyed perf regression gate over bench/serve
  records (``scripts/bench_compare.py`` is the CLI/CI entry point).
- :mod:`tracectx` — request-scoped ``TraceContext`` (W3C-traceparent ids,
  thread-local with explicit handoff) plus trace reconstruction and
  completeness verification over emitted events.
- :mod:`registry` — ``MetricsRegistry``: named counters/gauges/rolling
  windows with periodic JSONL snapshots; :mod:`exposition` renders the
  same snapshot as a Prometheus text endpoint (``AF2TPU_METRICS_PORT``).
- :mod:`slo` — declarative ``SLOSpec`` objectives with multi-window
  burn-rate alerting over the resolved-request stream.
- :mod:`flightrec` — ``FlightRecorder``: bounded rings of recent
  telemetry dumped as a scrubbed incident file on dispatch error or
  SIGTERM.
- :mod:`workload` — ``WorkloadRecorder``: the request STREAM itself as
  a scrubbed, replayable JSONL artifact (fingerprints, not sequences,
  unless opted in), plus the replay builder and the seeded synthetic
  diurnal generator behind ``bench.py --mode serve-replay``.

``alphafold2_tpu.train.observe`` remains as a re-export shim for existing
imports. ``scripts/obs_report.py`` summarizes the emitted artifacts.

Everything here is importable without a jax backend (jax is imported
lazily where a device is consulted), so host-side tools stay jax-free.
"""

from alphafold2_tpu.observe import flops, numerics, regress
from alphafold2_tpu.observe.flightrec import FlightRecorder, scrub_env
from alphafold2_tpu.observe.histogram import Histogram
from alphafold2_tpu.observe.memory import MemorySampler
from alphafold2_tpu.observe.metrics import EventCounters, MetricsLogger
from alphafold2_tpu.observe.numerics import tag
from alphafold2_tpu.observe.profiler import Profiler
from alphafold2_tpu.observe.registry import MetricsRegistry
from alphafold2_tpu.observe.slo import SLOMonitor, SLOSpec, parse_slo_specs
from alphafold2_tpu.observe.tracectx import (
    TraceContext,
    current_trace,
    use_trace,
)
from alphafold2_tpu.observe.tracing import Span, Tracer
from alphafold2_tpu.observe.workload import (
    WorkloadRecorder,
    build_replay,
    load_workload,
    synthetic_diurnal,
)

__all__ = [
    "EventCounters",
    "FlightRecorder",
    "Histogram",
    "MemorySampler",
    "MetricsLogger",
    "MetricsRegistry",
    "Profiler",
    "SLOMonitor",
    "SLOSpec",
    "Span",
    "TraceContext",
    "Tracer",
    "WorkloadRecorder",
    "build_replay",
    "current_trace",
    "flops",
    "load_workload",
    "numerics",
    "parse_slo_specs",
    "regress",
    "scrub_env",
    "synthetic_diurnal",
    "tag",
    "use_trace",
]
