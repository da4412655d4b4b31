"""Span tracing emitted as Chrome-trace-event JSONL.

``Tracer.span("serve.dispatch", bucket=32)`` times a nested region and
emits one complete ("ph": "X") trace event per span; the output file loads
directly in Perfetto / ``chrome://tracing`` (the file opens with ``[`` and
the trace-event spec makes the closing ``]`` optional, so the format is
simultaneously a streaming JSONL-per-line file and a valid JSON-array
trace). Nesting is inferred by the viewer from ts/dur overlap within a
thread — no explicit parent ids needed.

A disabled tracer (no path, ``enabled=False``) is a near-zero-cost no-op,
so instrumentation can stay permanently wired through hot paths (the serve
engine, the train step) and be switched on per run.

An enabled tracer also enters ``jax.profiler.TraceAnnotation`` for every
span and instant, so that while a ``jax.profiler`` trace is being taken the
same names land in its host plane, on the device trace's clock
(``observe.profiler`` reads them back from there). And it registers, once per
process, the ``jax.monitoring`` listeners behind :func:`compile_counts`.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from contextlib import contextmanager, nullcontext
from typing import Optional, Tuple

from alphafold2_tpu.observe.tracectx import current_trace, use_trace

# one timeline origin per process: spans from every tracer share it, so a
# serve-engine trace and a bench-stage trace interleave correctly
_PROC_T0 = time.perf_counter()


def _now_us() -> float:
    return (time.perf_counter() - _PROC_T0) * 1e6


# ---------------------------------------------------------- compile counter

_DURATIONS = {  # jax.monitoring's event -> the counter's key
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    # fires for a load from the persistent cache too
    "/jax/core/compile/backend_compile_duration": "backend_s",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"

_COMPILES = {"compiles": 0, "cache_hits": 0, "trace_s": 0.0, "lower_s": 0.0,
             "backend_s": 0.0}
_COMPILES_LOCK = threading.Lock()
_LISTENING = False


def _on_duration(event: str, duration_s: float, **_) -> None:
    key = _DURATIONS.get(event)
    if key is None:
        return
    with _COMPILES_LOCK:
        _COMPILES[key] += duration_s
        if key == "backend_s":
            _COMPILES["compiles"] += 1


def _on_event(event: str, **_) -> None:
    if event == _CACHE_HIT:
        with _COMPILES_LOCK:
            _COMPILES["cache_hits"] += 1


def _listen_for_compiles() -> None:
    """Register the listeners, once per process (jax offers no way to tell
    whether one is registered, and they are never taken off again)."""
    global _LISTENING
    with _COMPILES_LOCK:
        if _LISTENING:
            return
        _LISTENING = True
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)


def compile_counts() -> dict:
    """The process-wide compile counter since the first enabled tracer:
    ``compiles`` (programs handed to the backend's compiler, those it then
    loaded from the persistent cache included), ``cache_hits`` (those
    loads), and the summed seconds of tracing to a jaxpr (``trace_s``),
    lowering to a module (``lower_s``) and the backend's compile or load
    (``backend_s``). All zero while no tracer was ever enabled."""
    with _COMPILES_LOCK:
        return dict(_COMPILES)


def _annotation_args(args: dict) -> dict:
    """What a profiler annotation can carry: plain numbers and strings."""
    return {k: v for k, v in args.items()
            if isinstance(v, (bool, int, float, str))}


class Span:
    """Handle yielded by ``Tracer.span``: attach args mid-flight via
    ``set(key=value)`` (e.g. the compile-cache verdict known only at the
    end of the region)."""

    __slots__ = ("name", "args", "duration_s")

    def __init__(self, name: str, args: dict):
        self.name = name
        self.args = args
        self.duration_s = 0.0

    def set(self, **kw) -> "Span":
        self.args.update(kw)
        return self


class _NullSpan:
    __slots__ = ()

    def set(self, **kw):
        return self


_NULL_SPAN = _NullSpan()
_NULL_CONTEXT = nullcontext()


class Tracer:
    """Thread-safe span tracer writing Chrome trace events.

    ``path=None`` keeps events only in memory (tests, ``span_totals``), the
    newest ``max_events`` of them if that is given; ``enabled=False``
    disables everything. Events are flushed to the file
    as they complete, so a killed process still leaves a loadable trace.
    """

    def __init__(self, path: Optional[str] = None,
                 enabled: Optional[bool] = None,
                 max_events: Optional[int] = None):
        self.enabled = bool(path) if enabled is None else bool(enabled)
        self._path = path
        self._lock = threading.Lock()
        # in memory: all of them, or the newest ``max_events``
        self._events = deque(maxlen=max_events)
        self._sinks: list = []  # e.g. the flight recorder's ring buffer
        self._file = None
        self._annotated: set = set()  # names sent to the profiler's plane
        if self.enabled:
            _listen_for_compiles()
        if self.enabled and path:
            d = os.path.dirname(os.path.abspath(path))
            os.makedirs(d, exist_ok=True)
            self._file = open(path, "w")
            self._file.write("[\n")
            self._file.flush()

    @classmethod
    def from_env(cls, var: str = "AF2TPU_TRACE_EVENTS") -> "Tracer":
        """Tracer writing to $AF2TPU_TRACE_EVENTS, disabled when unset."""
        return cls(path=os.environ.get(var) or None)

    # ------------------------------------------------------------- emission

    def add_sink(self, sink) -> None:
        """Register a callback receiving every emitted event dict (the
        flight recorder's ring buffer attaches here). Sinks are invoked
        *outside* the tracer lock from a per-event snapshot, so a slow or
        re-entrant sink cannot stall or deadlock emitters."""
        with self._lock:
            if sink not in self._sinks:
                self._sinks.append(sink)

    def _emit(self, event: dict) -> None:
        # record + persist under the lock; snapshot the sink list and
        # invoke outside it (a sink that emits, or blocks, must not hold
        # every other emitting thread hostage)
        with self._lock:
            self._events.append(event)
            sinks = list(self._sinks)
            if self._file is not None:
                self._file.write(json.dumps(event) + ",\n")
                self._file.flush()
        for sink in sinks:
            try:
                sink(event)
            except Exception:
                pass  # a broken sink must never lose the trace itself

    @contextmanager
    def span(self, name: str, **args):
        """Time a region; emits one complete event on exit (exceptions
        included — a span that dies still appears, flagged ``error``).

        When a :mod:`tracectx` context is active on this thread (and the
        caller didn't attach ids explicitly), a child context is minted
        for the region — nested spans chain parent ids automatically and
        every event carries its owning ``trace_id``."""
        if not self.enabled:
            yield _NULL_SPAN
            return
        sp = Span(name, dict(args))
        ctx = None
        if "trace_id" not in sp.args:
            cur = current_trace()
            if cur is not None:
                ctx = cur.child()
                sp.args.update(ctx.event_args())
        # the same region on the profiler's clock (args as known on entry)
        annotation = self._annotation(name, args)
        annotation.__enter__()
        t0 = _now_us()
        try:
            if ctx is not None:
                with use_trace(ctx):
                    yield sp
            else:
                yield sp
        except BaseException as e:
            sp.args["error"] = type(e).__name__
            raise
        finally:
            t1 = _now_us()
            annotation.__exit__(None, None, None)
            sp.duration_s = (t1 - t0) / 1e6
            self._emit({
                "name": name, "ph": "X", "ts": round(t0, 1),
                "dur": round(t1 - t0, 1), "pid": os.getpid(),
                "tid": threading.get_ident(),
                **({"args": sp.args} if sp.args else {}),
            })

    def _annotation(self, name: str, args: dict):
        import jax.profiler

        self._annotated.add(name)
        return jax.profiler.TraceAnnotation(name, **_annotation_args(args))

    def step(self, name: str, step_num: int):
        """``jax.profiler.StepTraceAnnotation(name, step_num=...)`` around
        one iteration of a loop, so that a profiler trace groups the device's
        work by the loop's own step numbers; a null context when disabled."""
        if not self.enabled:
            return _NULL_CONTEXT
        import jax.profiler

        self._annotated.add(name)
        return jax.profiler.StepTraceAnnotation(name, step_num=step_num)

    def annotated_names(self) -> set:
        """Every name this tracer has sent to the profiler's host plane."""
        return set(self._annotated)

    def span_event(self, name: str, t0_s: float, t1_s: float, **args) -> None:
        """Emit a complete span with EXPLICIT bounds (``time.perf_counter``
        seconds) — for retroactive regions whose start predates the call,
        e.g. the scheduler's per-request queue-residency span, known only
        when the batch forms. A profiler annotation cannot be backdated, so
        this one stays on the tracer's own clock."""
        if not self.enabled:
            return
        ts = (t0_s - _PROC_T0) * 1e6
        dur = max(0.0, (t1_s - t0_s) * 1e6)
        self._emit({
            "name": name, "ph": "X", "ts": round(ts, 1),
            "dur": round(dur, 1), "pid": os.getpid(),
            "tid": threading.get_ident(),
            **({"args": dict(args)} if args else {}),
        })

    def instant(self, name: str, **args) -> None:
        """A zero-duration marker event (ph "i"). Auto-attaches the
        thread's active trace context like :meth:`span` (no child mint —
        an instant is a point, not a region)."""
        if not self.enabled:
            return
        if "trace_id" not in args:
            cur = current_trace()
            if cur is not None:
                args = {**args, **cur.event_args()}
        with self._annotation(name, args):
            pass
        self._emit({
            "name": name, "ph": "i", "ts": round(_now_us(), 1), "s": "p",
            "pid": os.getpid(), "tid": threading.get_ident(),
            **({"args": dict(args)} if args else {}),
        })

    def counter(self, name: str, **values) -> None:
        """A counter sample event (ph "C") — e.g. HBM bytes over time."""
        if not self.enabled:
            return
        self._emit({
            "name": name, "ph": "C", "ts": round(_now_us(), 1),
            "pid": os.getpid(), "args": dict(values),
        })

    # ------------------------------------------------------------ summaries

    def events(self) -> list:
        with self._lock:
            return list(self._events)

    def span_totals(self) -> dict:
        """Per-span-name aggregate: {name: {count, total_s, max_s}} over the
        complete ("X") events seen so far — the bench records embed this as
        the per-stage timing breakdown."""
        out: dict = {}
        for e in self.events():
            if e.get("ph") != "X":
                continue
            agg = out.setdefault(
                e["name"], {"count": 0, "total_s": 0.0, "max_s": 0.0}
            )
            dur_s = e.get("dur", 0.0) / 1e6
            agg["count"] += 1
            agg["total_s"] = round(agg["total_s"] + dur_s, 6)
            agg["max_s"] = round(max(agg["max_s"], dur_s), 6)
        return out

    def close(self) -> None:
        with self._lock:
            if self._file is not None:
                self._file.flush()
                self._file.close()
                self._file = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


# span names whose duration means "the device is (or is being kept) busy":
# serve.dispatch covers executable submission through (sync path) blocking
# execution; serve.device_get blocks until execution drains and results
# land on the host, so its extent covers the async execution tail too
DEVICE_SPAN_NAMES = ("serve.dispatch", "serve.device_get")


def merge_intervals(intervals) -> list:
    """Union a list of (start, end) intervals into disjoint sorted spans."""
    merged: list = []
    for start, end in sorted((s, e) for s, e in intervals if e > s):
        if merged and start <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], end))
        else:
            merged.append((start, end))
    return merged


def device_idle_fraction(events, names=DEVICE_SPAN_NAMES) -> Optional[dict]:
    """Device idle fraction over a serve trace: 1 - (union of device-busy
    span extents) / (window from first device span start to last end).

    The pipeline's whole point is to shrink this number — host featurize /
    device_put / unpad overlapping with compute shows up directly as busy
    spans tiling the window. Computed from the same trace events the
    Chrome timeline renders, so the metric and the picture can't diverge.
    Returns ``{"device_idle_frac", "busy_s", "window_s", "dispatches"}``,
    or None when the trace holds no ``serve.dispatch`` span (nothing was
    dispatched — an idle fraction would be meaningless).
    """
    intervals = []
    dispatches = 0
    for e in events:
        if e.get("ph") != "X" or e.get("name") not in names:
            continue
        ts = e.get("ts", 0.0)
        intervals.append((ts / 1e6, (ts + e.get("dur", 0.0)) / 1e6))
        if e.get("name") == "serve.dispatch":
            dispatches += 1
    if not dispatches or not intervals:
        return None
    lo = min(s for s, _ in intervals)
    hi = max(e for _, e in intervals)
    window = hi - lo
    busy = sum(e - s for s, e in merge_intervals(intervals))
    idle = max(0.0, 1.0 - busy / window) if window > 0 else 0.0
    return {
        "device_idle_frac": round(idle, 4),
        "busy_s": round(busy, 6),
        "window_s": round(window, 6),
        "dispatches": dispatches,
    }


def load_trace_events(path: str) -> list:
    """Parse a trace file written by ``Tracer`` (or any Chrome trace-event
    JSON array). Tolerates the streaming form: leading ``[``, one event per
    line with a trailing comma, no closing ``]``. Raises on malformed
    lines; use :func:`load_trace_events_lenient` to collect them instead."""
    events, errors = load_trace_events_lenient(path)
    if errors:
        raise json.JSONDecodeError(
            f"{len(errors)} malformed trace line(s) in {path} "
            f"(first: {errors[0]})",
            doc="", pos=0,
        )
    return events


def load_trace_events_lenient(path: str) -> Tuple[list, list]:
    """Like :func:`load_trace_events`, but a truncated/malformed line
    (killed writer mid-flush, disk-full tail) becomes an entry in the
    returned error list instead of an exception mid-parse — every parseable
    event is still returned. Returns ``(events, errors)`` where each error
    is a ``"line N: <detail>"`` string."""
    with open(path) as f:
        text = f.read().strip()
    if not text:
        return [], []
    try:  # a well-formed JSON array (or {"traceEvents": [...]})
        doc = json.loads(text)
        if isinstance(doc, dict):
            doc = doc.get("traceEvents", [])
        if isinstance(doc, list):
            return doc, []
        return [], [f"line 1: top-level {type(doc).__name__}, not a list"]
    except json.JSONDecodeError:
        pass
    events, errors = [], []
    for lineno, line in enumerate(text.splitlines(), start=1):
        line = line.strip().rstrip(",")
        if not line or line in ("[", "]"):
            continue
        try:
            event = json.loads(line)
        except json.JSONDecodeError as e:
            errors.append(f"line {lineno}: {e.msg} ({line[:60]!r})")
            continue
        if isinstance(event, dict):
            events.append(event)
        else:
            errors.append(
                f"line {lineno}: event is {type(event).__name__}, not dict"
            )
    return events, errors
