"""Median gap between the finish stamps of the window's steps, in ms."""

import statistics


def read(run: dict, params: dict):
    stamps = run.get("stamps") or []
    if len(stamps) < 2:
        return None
    return 1e3 * statistics.median(b - a for a, b in zip(stamps, stamps[1:]))
