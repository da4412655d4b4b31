"""Structured metrics: JSONL logging and thread-safe event counters.

``MetricsLogger`` is the step-axis channel (one JSON record per step,
greppable/plottable); ``EventCounters`` is the event-axis channel (named
monotonic counters without a step: compile counts, cache hits, request
totals). Both are construction-safe without a jax backend so host-side
tools (``scripts/obs_report.py``, tests) can use them.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Optional


class MetricsLogger:
    """JSONL + stdout metrics.

    In multi-host runs only process 0 logs — otherwise every host appends
    to the same metrics.jsonl on shared storage (duplicated and potentially
    interleaved records). ``enabled`` overrides that decision explicitly:
    pass ``True``/``False`` to construct the logger without touching jax at
    all (non-JAX tools, tests, code running before jax.distributed is
    initialized — ``jax.process_index()`` on an uninitialized distributed
    runtime can itself trigger backend init or raise)."""

    def __init__(
        self,
        directory: Optional[str] = None,
        filename: str = "metrics.jsonl",
        enabled: Optional[bool] = None,
        echo: bool = True,
    ):
        # echo=False keeps stdout clean (bench.py's one-JSON-line contract:
        # the driver parses stdout, so telemetry goes to the file only)
        self._echo = echo
        if enabled is None:
            try:
                import jax

                enabled = jax.process_index() == 0
            except Exception:
                # no jax / no initialized backend: a single-process tool —
                # logging from it is always safe
                enabled = True
        self._enabled = bool(enabled)
        self._path = None
        if directory and self._enabled:
            os.makedirs(directory, exist_ok=True)
            self._path = os.path.join(directory, filename)

    @property
    def enabled(self) -> bool:
        return self._enabled

    @property
    def path(self) -> Optional[str]:
        return self._path

    def log(self, step: int, metrics: dict) -> None:
        if not self._enabled:
            return
        record = {"step": step, "time": time.time(), **metrics}
        line = json.dumps(record)
        if self._echo:
            print(f"[step {step}] " + " ".join(
                f"{k}={v:.4g}" if isinstance(v, float) else f"{k}={v}"
                for k, v in metrics.items()
            ), flush=True)
        if self._path:
            with open(self._path, "a") as f:
                f.write(line + "\n")


def flatten_metrics(metrics: dict, prefix: str = "", sep: str = "/") -> dict:
    """Flatten nested metric dicts into ``a/b/c`` float keys.

    The train loops log through this so structured step metrics (the
    numerics stats tree, per-parameter-group norms) land in metrics.jsonl
    as flat greppable keys. Leaves are coerced with ``float()`` — which
    also fetches device scalars — falling back to a list for an array with
    axes (per-layer counters) and to the raw value for non-numeric leaves
    (strings)."""
    out: dict = {}
    for k, v in metrics.items():
        key = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flatten_metrics(v, prefix=key + sep, sep=sep))
            continue
        try:
            out[key] = float(v)
        except (TypeError, ValueError):
            out[key] = v.tolist() if hasattr(v, "tolist") else v
    return out


class EventCounters:
    """Named monotonic counters for process-local accounting (compile
    counts, cache hits, request totals). Same spirit as MetricsLogger but
    for events without a step axis: ``bump`` from anywhere, ``snapshot``
    into a record, ``log_to`` to emit through a MetricsLogger. The serve
    engine's compile-count/cache-hit instrumentation is built on this so
    tests can assert exact executable-cache behavior.

    Thread-safe: the serve dispatch path and observability threads (the
    liveness watchdog's heartbeat, memory samplers) bump concurrently, and
    a lost update would corrupt the compile-count accounting the tests
    pin down."""

    def __init__(self):
        self._counts: dict = {}
        self._lock = threading.Lock()

    def bump(self, name: str, n: int = 1) -> int:
        with self._lock:
            self._counts[name] = self._counts.get(name, 0) + n
            return self._counts[name]

    def get(self, name: str) -> int:
        with self._lock:
            return self._counts.get(name, 0)

    def snapshot(self) -> dict:
        with self._lock:
            return dict(self._counts)

    def log_to(self, logger: "MetricsLogger", step: int = 0) -> None:
        logger.log(step, self.snapshot())
