"""From a profiler trace (``.xplane.pb``) to device busy time, idle gaps and
kernel time. Pure functions over ``(name, start_ns, end_ns)`` events; checked
on a hand-written trace in ``tests/data/small_trace.textproto``.

Device planes are those named ``/device:TPU:<n>``; a plane's operations are
the events of its ``XLA Ops`` line. Events overlap and nest (a ``while``
holds its body's operations), so busy time is the UNION of the intervals, and
an operation's own time is its duration less what its children cover. The
traced slice of a plane runs from its first operation's start to its last
one's end: idle time before the first and after the last operation is the
profiler's start and stop, not the program's.
"""

from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
_SHAPE = re.compile(r"\w+\[[\d,]*\]")


def short_name(event_name: str) -> str:
    """An event of the ``XLA Ops`` line is named by its whole HLO
    instruction (``%flash_attention.13 = (bf16[1,8,65536,64]{...}, ...)
    custom-call(...)``): keep the instruction's name and its first shape."""
    head, _, rest = event_name.partition(" = ")
    shape = _SHAPE.search(rest)
    name = head.lstrip("%")[:100]
    return f"{name} {shape.group(0)}" if shape else name


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def device_ops(profile) -> dict:
    """{plane name: [(name, start_ns, end_ns), ...]} sorted by start."""
    out = {}
    for plane in profile.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        suffix = plane.name[len(DEVICE_PREFIX):]
        if not suffix.isdigit():  # e.g. a per-core sub-plane
            continue
        lines = {line.name: line for line in plane.lines}
        if OPS_LINE not in lines:
            raise ValueError(
                f"plane {plane.name} has no {OPS_LINE!r} line: "
                f"{sorted(lines)}")
        out[plane.name] = sorted(
            (short_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
            for e in lines[OPS_LINE].events)
    if not out:
        raise ValueError(
            "no device plane in the trace: "
            f"{[p.name for p in profile.planes]}")
    return out


def busy_intervals(events) -> list:
    """Merged [start, end] intervals of the union, and for each the name of
    the last operation to end in it."""
    merged = []
    for name, start, end in sorted(events, key=lambda e: e[1]):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1], merged[-1][2] = end, name
        else:
            merged.append([start, end, name])
    return merged


def busy_ns(events) -> float:
    return sum(end - start for start, end, _ in busy_intervals(events))


def window_ns(events) -> float:
    return max(e[2] for e in events) - min(e[1] for e in events)


def idle_gaps(events) -> list:
    """[(name of the operation before the gap, gap_ns), ...]."""
    merged = busy_intervals(events)
    return [(a[2], b[0] - a[1]) for a, b in zip(merged, merged[1:])]


def self_times(events) -> dict:
    """{name: ns} of each operation's own time: its duration less what the
    operations nested inside it cover."""
    out: dict = {}
    stack: list = []  # [name, end, own_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            out[name] = out.get(name, 0.0) + own

    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= min(end, stack[-1][1]) - start
        stack.append([name, end, end - start])
    close(float("inf"))
    return out


def kernel_ns(events, prefixes) -> float:
    """Summed duration of the events whose name starts with any of
    ``prefixes``."""
    return sum(end - start for name, start, end in events
               if name.startswith(tuple(prefixes)))


def executions(events, prefixes) -> int:
    """How many times the program ran inside the trace: each kernel
    instruction runs once a step, so the count of the commonest kernel
    event's name."""
    counts: dict = {}
    for name, _, _ in events:
        if name.startswith(tuple(prefixes)):
            counts[name] = counts.get(name, 0) + 1
    return max(counts.values()) if counts else 0


def top(pairs: dict, n: int = 10) -> list:
    return [[k, v / 1e9] for k, v in
            sorted(pairs.items(), key=lambda kv: -kv[1])[:n]]


def summarize(profile) -> dict:
    """What the result line and the readers take from one trace: busy and
    window seconds averaged over the chips, the breakdown, and the events."""
    planes = device_ops(profile)
    busy = [busy_ns(ev) for ev in planes.values()]
    window = [window_ns(ev) for ev in planes.values()]
    first = next(iter(planes.values()))
    gaps: dict = {}
    for name, ns in idle_gaps(first):
        key = f"after {name}"
        gaps[key] = gaps.get(key, 0.0) + ns
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": sum(window) / len(window) / 1e9,
        "breakdown": {"device_ops": top(self_times(first)),
                      "idle_gaps": top(gaps)},
        "planes": planes,
    }


def summarize_dir(trace_dir: str) -> dict:
    """The one ``.xplane.pb`` that ``jax.profiler`` left under a directory."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(
            f"{len(found)} traces under {trace_dir} (expected 1): was the "
            "window shorter than the traced steps?")
    return summarize(load(found[0]))
