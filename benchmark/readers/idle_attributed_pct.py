"""Of the traced slice's idle time in gaps longer than ``params["min_gap_us"]``,
the share that a named host span of the program's loop thread covers, in %.
Nothing where the slice has no such gap."""

from benchmark.harness import scope_reduce


def read(run: dict, params: dict):
    record, plane = scope_reduce.traced(run)
    if plane is None:
        return None
    gaps = scope_reduce.idle_gaps(plane["ops"], 1e3 * params["min_gap_us"])
    idle = sum(b - a for a, b in gaps)
    if not idle:
        return None
    by_span = scope_reduce.idle_by_span(gaps, record.get("host") or [])
    return 100.0 * (1.0 - by_span.get(scope_reduce.OUTSIDE_ANY_SPAN, 0.0)
                    / idle)
