"""Summed seconds of the program's spans named ``params["span"]`` (the
tracer's own events: a set-up phase runs before any profiler trace). Nothing
where the program has no such span, as on a path without an AOT compile."""

from benchmark.harness import scope_reduce


def read(run: dict, params: dict):
    if not run.get("trace"):
        return None
    events = scope_reduce.span_events(
        scope_reduce.program_record(), params["span"])
    if not events:
        return None
    return sum(e["dur"] for e in events) / 1e6
