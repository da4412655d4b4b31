"""FlightRecorder: a bounded ring of recent telemetry, dumped on death.

A run that dies leaves at most one line of verdict — the spans, counters
and metric snapshots leading up to the death are lost with the process.
The flight recorder keeps the RECENT telemetry in bounded in-memory rings (attached
as a :class:`~alphafold2_tpu.observe.tracing.Tracer` sink, so it costs
one deque append per event while healthy) and writes one structured,
scrubbed incident file when something dies:

- **dispatch error** — the serve engine notes every converted dispatch
  exception and dumps on the first one (serve/engine.py).
- **SIGTERM** — :func:`install_signal_handler` dumps, then re-raises the
  default handler so exit semantics are unchanged.

The dump's environment echo goes through :func:`scrub_env` —
secret-shaped values redacted — because incident files get attached to
tickets and uploaded as CI artifacts. ``scripts/obs_report.py`` reuses
the same scrub for its env echo.

Module-level :func:`install` / :func:`active` hold one process-wide
recorder (bench and the engine find it without plumbing); dumps are
once-per-reason so a storm of dispatch errors yields one incident file,
not thousands. Pure stdlib, jax-free.
"""

from __future__ import annotations

import json
import os
import re
import signal
import sys
import threading
import time
from collections import deque
from typing import Optional

# env-value redaction: keys matching this carry credentials; their values
# must never reach an incident file (which CI uploads as an artifact)
_SECRET_KEY_RE = re.compile(
    r"(KEY|TOKEN|SECRET|PASSWORD|PASSWD|CREDENTIAL|AUTH|COOKIE)",
    re.IGNORECASE,
)
REDACTED = "[redacted]"


def scrub_env(env: Optional[dict] = None) -> dict:
    """A display-safe copy of ``env`` (default ``os.environ``):
    secret-shaped keys' values replaced with ``[redacted]``. Key NAMES survive redaction — "this var was set" is
    exactly what a postmortem needs; the value is what must not leak."""
    src = dict(os.environ if env is None else env)
    return {
        key: REDACTED if _SECRET_KEY_RE.search(key) else src[key]
        for key in sorted(src)
    }


class FlightRecorder:
    """Bounded rings of recent spans/events, notes, and metric snapshots.

    ``attach(tracer)`` registers the event ring as a tracer sink;
    :meth:`note` records structured annotations (dispatch errors, SLO
    alerts); :meth:`snapshot` records periodic metric snapshots (the
    registry snapshotter's ``also`` hook). :meth:`dump` writes the
    incident file — once per ``reason`` unless forced."""

    def __init__(
        self,
        directory: Optional[str] = None,
        capacity: int = 4096,
    ):
        self.directory = directory or os.environ.get("AF2TPU_FLIGHTREC_DIR")
        self._events: deque = deque(maxlen=max(16, int(capacity)))
        self._notes: deque = deque(maxlen=256)
        self._snapshots: deque = deque(maxlen=64)
        self._dumped: set = set()
        self._lock = threading.Lock()
        self._t0 = time.time()
        # optional workload-tail provider (observe/workload.py): a
        # callable returning the recorder's last-N SCRUBBED request
        # events, included in dumps so an incident file shows what
        # traffic preceded the death
        self._workload_tail = None

    # ------------------------------------------------------------ recording

    def record_event(self, event: dict) -> None:
        """Tracer-sink callback (invoked outside the tracer's lock from
        its per-event sink snapshot: a deque append only, no locks of our
        own — no deadlock surface)."""
        self._events.append(event)

    def attach(self, tracer) -> "FlightRecorder":
        tracer.add_sink(self.record_event)
        return self

    def attach_workload(self, tail_provider) -> "FlightRecorder":
        """Register ``tail_provider()`` (e.g. ``WorkloadRecorder.tail``)
        whose return — a bounded list of already-scrubbed request events —
        rides in every subsequent dump as ``workload_tail``."""
        self._workload_tail = tail_provider
        return self

    def note(self, kind: str, **info) -> None:
        self._notes.append({"kind": kind, "time": time.time(), **info})

    def snapshot(self, name: str, data: dict) -> None:
        self._snapshots.append(
            {"name": name, "time": time.time(), "data": dict(data)}
        )

    # -------------------------------------------------------------- dumping

    def dump(
        self,
        reason: str,
        extra: Optional[dict] = None,
        force: bool = False,
    ) -> Optional[str]:
        """Write the incident file; returns its path (None when no
        directory is configured or this reason already dumped)."""
        with self._lock:
            if not force and reason in self._dumped:
                return None
            self._dumped.add(reason)
        if not self.directory:
            return None
        workload_tail = None
        if self._workload_tail is not None:
            try:  # a broken provider must not mask the original failure
                workload_tail = list(self._workload_tail())[-64:]
            except Exception:
                workload_tail = None
        doc = {
            "reason": reason,
            "time_unix": round(time.time(), 3),
            "uptime_s": round(time.time() - self._t0, 3),
            "pid": os.getpid(),
            "argv": list(sys.argv),
            "python": sys.version.split()[0],
            "env": scrub_env(),
            "notes": list(self._notes),
            "metric_snapshots": list(self._snapshots),
            # newest-last; ts values are on the tracer's process timebase
            "events": list(self._events),
            # last-N request events from the workload ring (same scrub
            # contract as the recorder: hashed parents, no raw sequences
            # unless that recorder opted in)
            **({"workload_tail": workload_tail}
               if workload_tail is not None else {}),
            **({"extra": extra} if extra else {}),
        }
        try:
            os.makedirs(self.directory, exist_ok=True)
            safe = re.sub(r"[^A-Za-z0-9_.-]", "_", reason)[:64]
            path = os.path.join(
                self.directory,
                f"incident_{safe}_{os.getpid()}_{int(time.time())}.json",
            )
            with open(path, "w") as f:
                json.dump(doc, f, indent=2, default=str)
            return path
        except OSError:
            return None  # a full disk must not mask the original failure


# ------------------------------------------------------- process singleton

_ACTIVE: dict = {"recorder": None}


def install(recorder: FlightRecorder) -> FlightRecorder:
    _ACTIVE["recorder"] = recorder
    return recorder


def active() -> Optional[FlightRecorder]:
    return _ACTIVE["recorder"]


def maybe_install_from_env() -> Optional[FlightRecorder]:
    """Install a recorder when ``$AF2TPU_FLIGHTREC_DIR`` is set (the
    opt-in); returns the active recorder either way."""
    if _ACTIVE["recorder"] is None and os.environ.get("AF2TPU_FLIGHTREC_DIR"):
        install(FlightRecorder())
    return _ACTIVE["recorder"]


def install_signal_handler(recorder: FlightRecorder) -> None:
    """Dump on SIGTERM, then restore and re-raise the default handler so
    exit codes and parent-process semantics stay exactly as before. Only
    callable from the main thread (signal module rule); silently skipped
    elsewhere."""

    def _on_term(signum, frame):
        recorder.note("signal", signum=int(signum))
        recorder.dump("sigterm")
        signal.signal(signal.SIGTERM, signal.SIG_DFL)
        signal.raise_signal(signal.SIGTERM)

    try:
        signal.signal(signal.SIGTERM, _on_term)
    except ValueError:
        pass  # not the main thread
