"""Workload capture & deterministic replay plane.

The telemetry plane (tracectx/registry/slo/flightrec) can reconstruct any
single request lifecycle, but nothing records the request *stream* itself
— so scheduler/fleet changes could only ever be judged against synthetic
benches, never against the traffic that actually hit a deployment. This
module closes that gap:

- :class:`WorkloadRecorder` — logs every ``AsyncServeFrontend`` request
  as a scrubbed JSONL event via the scheduler's submit-side hook
  (``add_submit_observer``) plus the existing resolution observer
  (``add_observer``). Submit events carry the arrival offset from stream
  start, a derivation fingerprint (sha256 over
  ``serve.cache.feature_key`` — the same tuple the FeatureCache keys on),
  sequence length, a mutation-edit summary against recent traffic (so
  scan families survive scrubbing), priority, deadline, a HASHED parent
  hint and the trace id; resolve events carry status, reuse class and
  latency. **Raw sequences are recorded only with an explicit
  ``record_raw=True`` opt-in** — the scrubbed default leaks neither
  sequence content nor caller-controlled metadata (parent hints and
  family labels are one-way hashed, error text is never recorded).
- :func:`load_workload` / :func:`build_replay` — turn a recorded log
  back into a timed ``ServeRequest`` stream for ``bench.py --mode
  serve-replay``: original timing, ``time_warp`` compression and
  ``load_scale`` multiplication (extra copies get distinct seeds so they
  are real work, not dedup fodder).
- :func:`synthetic_diurnal` — a seeded inhomogeneous-Poisson generator
  (sinusoidal rate curve: the classic diurnal wave) for when no
  recording exists; its events are shaped exactly like recorded ones,
  so the replay driver treats both identically.

The recorder also keeps a bounded in-memory ring of its scrubbed events:
``FlightRecorder.attach_workload(recorder.tail)`` includes the last N
request events in incident dumps, so a watchdog/SIGTERM/dispatch-error
dump records what traffic preceded the incident.

Pure host-side python (numpy only inside the generator) — importable
without a jax backend, like the rest of ``observe``.
"""

from __future__ import annotations

import hashlib
import json
import threading
import time
from collections import deque
from typing import Callable, Iterable, Optional

# NOTE: alphafold2_tpu.serve imports are deliberately function-local.
# Importing serve.bucketing/serve.cache at module scope initializes the
# serve package (engine -> predict -> models), and models itself imports
# observe (numerics.tag) — a cycle that breaks any `import
# alphafold2_tpu.models` entry point. Deferring keeps observe leaf-free.

SCHEMA_VERSION = 1

# mutation-edit summaries stop past this many substitutions: the request
# is no longer "a mutant of" recent traffic in any scan sense (mirrors
# ServeEngine.DELTA_MAX_EDITS, kept independent so the recorded summary
# is a property of the log, not of one engine's fast-lane config)
EDIT_SUMMARY_MAX = 8


def _hash16(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def derivation_fingerprint(
    seq: str, bucket: int, msa_depth: int, seed: int
) -> str:
    """Content address of a request's derivation: sha256 over the same
    ``feature_key`` tuple the FeatureCache keys featurized trees on, so
    two log lines share a fingerprint iff the engine would featurize them
    identically. One-way: the scrubbed log never exposes the sequence."""
    from alphafold2_tpu.serve.cache import feature_key

    return _hash16(repr(feature_key(seq, bucket, msa_depth, seed)))


def _edit_summary(seq: str, recent: Iterable) -> Optional[dict]:
    """Mutation-edit summary against recent traffic: the scrubbed log's
    substitute for raw sequences — scan families stay visible (same
    ``parent_fp``, small edit counts, positions) without leaking content.
    ``recent`` iterates (seq, fingerprint) pairs, newest last."""
    best = None
    for prev, prev_fp in recent:
        if len(prev) != len(seq) or prev == seq:
            continue
        pos = [i for i, (a, b) in enumerate(zip(prev, seq)) if a != b]
        if not 0 < len(pos) <= EDIT_SUMMARY_MAX:
            continue
        if best is None or len(pos) < len(best["edit_pos"]):
            best = {"edits": len(pos), "edit_pos": pos,
                    "parent_fp": prev_fp}
    return best


class WorkloadRecorder:
    """Records one serving frontend's request stream as scrubbed events.

    Wire it to a frontend with BOTH hooks::

        rec = WorkloadRecorder(path, buckets=engine.buckets,
                               msa_depth=engine.msa_depth)
        frontend.add_submit_observer(rec.on_submit)
        frontend.add_observer(rec.observe)

    ``path=None`` keeps a ring only (the flightrec tail); with a path
    every event is also appended as one JSON line. ``record_raw=True`` is
    the explicit opt-in that adds the raw sequence to submit events —
    required for the log to be replayable, appropriate for synthetic
    bench traffic, never the default. The recorder is thread-safe and
    never raises into the serving path."""

    def __init__(
        self,
        path: Optional[str] = None,
        record_raw: bool = False,
        ring: int = 512,
        buckets: tuple = (),
        msa_depth: int = 0,
        clock: Optional[Callable[[], float]] = None,
    ):
        self.path = path
        self.record_raw = bool(record_raw)
        self.buckets = tuple(buckets)
        self.msa_depth = int(msa_depth)
        self._clock = clock if clock is not None else time.perf_counter
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=max(16, int(ring)))
        self._recent: deque = deque(maxlen=64)  # (seq, fp) edit window
        self._t0: Optional[float] = None
        self._file = open(path, "a") if path else None
        self.events_recorded = 0
        self.errors = 0

    # ---------------------------------------------------------------- hooks

    def on_submit(self, req, bucket=None, family=None) -> None:
        """Submit-side hook (``AsyncServeFrontend.add_submit_observer``):
        one scrubbed submit event per submitted request, rejects and
        unservables included."""
        try:
            from alphafold2_tpu.serve.bucketing import bucket_for

            now = req.arrival_s if req.arrival_s is not None else (
                self._clock()
            )
            if bucket is None and self.buckets:
                try:
                    bucket = bucket_for(len(req.seq), self.buckets)
                except ValueError:
                    bucket = None
            fp = derivation_fingerprint(
                req.seq, int(bucket or len(req.seq)), self.msa_depth,
                req.seed,
            )
            ev = {
                "v": SCHEMA_VERSION,
                "kind": "submit",
                "t": 0.0,  # patched under the lock once t0 is known
                "trace": req.trace.trace_id if req.trace else None,
                "fp": fp,
                "len": len(req.seq),
                "seed": int(req.seed),
                "priority": int(req.priority),
                **({"deadline_s": float(req.deadline_s)}
                   if req.deadline_s else {}),
                **({"bucket": int(bucket)} if bucket else {}),
                # caller-controlled free text is NEVER recorded verbatim:
                # parent hints and family labels are one-way hashed —
                # hint equality (all affinity batching needs) survives,
                # planted secrets do not
                **({"parent": _hash16(str(req.parent_id))}
                   if req.parent_id else {}),
                **({"family": _hash16(str(family))} if family else {}),
            }
            with self._lock:
                if self._t0 is None:
                    self._t0 = now
                ev["t"] = round(max(0.0, now - self._t0), 6)
                summary = _edit_summary(req.seq, self._recent)
                if summary is not None:
                    ev.update(summary)
                if self.record_raw:
                    ev["seq"] = req.seq
                self._recent.append((req.seq, fp))
                self._append_locked(ev)
        except Exception:
            self.errors += 1  # recording must never take serving down

    def observe(self, result, priority: int) -> None:
        """Resolution hook (``AsyncServeFrontend.add_observer``): one
        event per resolution, linked to its submit by trace id. Only the
        structured class is recorded — error text can quote request
        content, so it stays out of the log."""
        try:
            ev = {
                "v": SCHEMA_VERSION,
                "kind": "resolve",
                "t": 0.0,
                "trace": result.trace_id,
                "status": result.status,
                "priority": int(priority),
                "bucket": int(result.bucket),
                "cache_hit": bool(result.cache_hit),
                "retried": bool(result.retried),
                "latency_s": round(float(result.latency_s), 6),
                **({"reuse": result.feat_reuse}
                   if result.feat_reuse else {}),
            }
            with self._lock:
                if self._t0 is None:
                    self._t0 = self._clock()
                ev["t"] = round(max(0.0, self._clock() - self._t0), 6)
                self._append_locked(ev)
        except Exception:
            self.errors += 1

    def write_summary(self, summary: dict) -> None:
        """Append the run's closing summary (reuse ledger, goodput, tails)
        — the reference half of the replay-vs-record diff."""
        try:
            with self._lock:
                self._append_locked({
                    "v": SCHEMA_VERSION, "kind": "summary", **summary,
                })
        except Exception:
            self.errors += 1

    def _append_locked(self, ev: dict) -> None:
        self._ring.append(ev)
        self.events_recorded += 1
        if self._file is not None:
            self._file.write(json.dumps(ev) + "\n")
            self._file.flush()

    # ------------------------------------------------------------- consumers

    def events(self) -> list:
        with self._lock:
            return list(self._ring)

    def tail(self, n: int = 64) -> list:
        """The last ``n`` scrubbed events — the FlightRecorder's bounded
        workload tail (``FlightRecorder.attach_workload``)."""
        with self._lock:
            return list(self._ring)[-max(0, int(n)):]

    def family_by_trace(self) -> dict:
        """trace_id -> hashed family label, from the ring's submit events
        (the serve bench's per-family cost aggregation key)."""
        with self._lock:
            return {
                ev["trace"]: ev.get("family")
                for ev in self._ring
                if ev.get("kind") == "submit" and ev.get("trace")
            }

    def close(self) -> None:
        with self._lock:
            if self._file is not None:
                self._file.close()
                self._file = None


# ------------------------------------------------------------------ replay


def load_workload(path: str) -> dict:
    """Parse a recorded JSONL log into ``{"submits", "resolves",
    "summary"}`` (submits sorted by arrival offset; summary ``None``
    when the recording has no closing summary line). Torn trailing lines
    (a recorder killed mid-write) are tolerated."""
    submits, resolves, summary = [], [], None
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                ev = json.loads(line)
            except json.JSONDecodeError:
                continue  # torn tail line
            kind = ev.get("kind")
            if kind == "submit":
                submits.append(ev)
            elif kind == "resolve":
                resolves.append(ev)
            elif kind == "summary":
                summary = ev
    submits.sort(key=lambda e: e.get("t", 0.0))
    return {"submits": submits, "resolves": resolves, "summary": summary}


def replayable_reason(submits: list) -> Optional[str]:
    """Why this log CANNOT drive a replay (None = it can). A scrubbed
    default log carries fingerprints, not sequences — replay needs the
    ``record_raw`` opt-in at record time (bench's own synthetic
    recordings enable it; their sequences are synthetic)."""
    if not submits:
        return "no submit events in the recording"
    missing = sum(1 for ev in submits if not ev.get("seq"))
    if missing:
        return (
            f"{missing}/{len(submits)} submit events carry no raw "
            "sequence (scrubbed recording; re-record with the raw opt-in)"
        )
    return None


def build_replay(
    submits: list,
    time_warp: float = 1.0,
    load_scale: int = 1,
) -> list:
    """Turn submit events into a timed request stream: a sorted list of
    ``(offset_s, ServeRequest)``. ``time_warp`` divides every arrival
    offset (2.0 = twice as fast); ``load_scale`` issues each request that
    many times — extra copies get distinct seeds and per-copy parent
    labels so they are genuinely new work (same featurization shape,
    no result-cache dedup), multiplying offered load, not cache hits."""
    from alphafold2_tpu.serve.engine import ServeRequest

    if time_warp <= 0:
        raise ValueError(f"time_warp must be > 0, got {time_warp}")
    if load_scale < 1:
        raise ValueError(f"load_scale must be >= 1, got {load_scale}")
    out = []
    for ev in submits:
        seq = ev.get("seq")
        if not seq:
            raise ValueError(
                "un-replayable submit event (no raw sequence): "
                + (replayable_reason(submits) or "")
            )
        for copy in range(int(load_scale)):
            parent = ev.get("parent")
            if parent and copy:
                parent = f"{parent}+{copy}"
            out.append((
                float(ev.get("t", 0.0)) / float(time_warp),
                ServeRequest(
                    seq,
                    seed=int(ev.get("seed", 0)) + copy * 1000003,
                    priority=int(ev.get("priority", 0)),
                    deadline_s=ev.get("deadline_s"),
                    parent_id=parent,
                ),
            ))
    out.sort(key=lambda pair: pair[0])
    return out


# --------------------------------------------------------------- synthetic


def synthetic_diurnal(
    seed: int = 0,
    requests: int = 50,
    mean_rate: float = 8.0,
    period_s: float = 6.0,
    amplitude: float = 0.8,
    buckets: tuple = (12, 16, 24),
    msa_depth: int = 2,
    class_mix: tuple = (0.2, 0.6, 0.2),
    dup_fraction: float = 0.1,
    mutant_fraction: float = 0.3,
    deadline_s: float = 30.0,
) -> list:
    """A seeded synthetic request stream riding a diurnal load curve, for
    replay when no recording exists. Arrivals are an inhomogeneous
    Poisson process with sinusoidal rate ``mean_rate * (1 + amplitude *
    sin(2*pi*t/period_s))`` (thinning), so the scheduler sees a load wave,
    not a flat stream. ``mutant_fraction`` of requests are single-point
    mutants of earlier traffic with a parent hint (scan families);
    ``dup_fraction`` are exact (seq, seed) repeats (cache/dedup traffic).
    Returns submit events shaped exactly like a raw-opt-in recording, so
    :func:`build_replay` drives both identically. Deterministic per seed."""
    import numpy as np

    from alphafold2_tpu.serve.bucketing import bucket_for

    rng = np.random.default_rng(seed)
    alpha = "ACDEFGHIKLMNPQRSTVWY"
    lo = max(4, buckets[0] // 2)
    hi = buckets[-1]
    pri_levels = (1, 0, -1)
    lam_max = mean_rate * (1.0 + abs(amplitude))
    events: list = []
    t = 0.0
    while len(events) < requests:
        t += float(rng.exponential(1.0 / lam_max))
        lam = mean_rate * (
            1.0 + amplitude * np.sin(2.0 * np.pi * t / period_s)
        )
        if rng.uniform() * lam_max > max(0.0, lam):
            continue  # thinned: we are in the trough of the wave
        priority = pri_levels[rng.choice(len(pri_levels), p=class_mix)]
        roll = rng.uniform()
        if events and roll < dup_fraction:
            src = events[int(rng.integers(len(events)))]
            seq, seed_i, parent = src["seq"], src["seed"], None
        elif events and roll < dup_fraction + mutant_fraction:
            src = events[int(rng.integers(len(events)))]
            pos = int(rng.integers(len(src["seq"])))
            sub = alpha[int(rng.integers(len(alpha)))]
            seq = src["seq"][:pos] + sub + src["seq"][pos + 1:]
            seed_i = src["seed"]  # delta featurize requires seed equality
            parent = f"fam-{src['fp']}"
        else:
            n = int(rng.integers(lo, hi + 1))
            seq = "".join(rng.choice(list(alpha), size=n))
            seed_i = int(rng.integers(0, 4))
            parent = None
        bucket = bucket_for(len(seq), tuple(buckets))
        events.append({
            "v": SCHEMA_VERSION,
            "kind": "submit",
            "t": round(t, 6),
            "fp": derivation_fingerprint(seq, bucket, msa_depth, seed_i),
            "len": len(seq),
            "seed": seed_i,
            "priority": priority,
            **({"deadline_s": float(deadline_s)} if deadline_s else {}),
            "bucket": bucket,
            **({"parent": _hash16(parent)} if parent else {}),
            "seq": seq,  # synthetic: raw is safe by construction
        })
    return events
