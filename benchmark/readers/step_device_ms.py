"""Device time of one execution of the step's program, in ms: the mean over
the executions in the trace (``XLA Modules``). Against the host's
``step_ms_p50`` it is what the host adds."""

from benchmark.harness import scope_reduce


def read(run: dict, params: dict):
    record, plane = scope_reduce.traced(run)
    if plane is None:
        return None
    runs = scope_reduce.step_runs(plane, record["step_module"])
    if not runs:
        return None
    return sum(e - s for s, e in runs) / len(runs) / 1e6
