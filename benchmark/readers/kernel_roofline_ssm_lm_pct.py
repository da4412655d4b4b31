"""``kernel_roofline_lm_pct`` for ``kind: "train_ssm_lm"``, the routed work
only: the least time the chip could take for the held experts' products at
the rows the traced steps sent them (``harness/ops_from_shapes_ssm_lm.py``:
TWO products an expert, the program's ``moe/assignments_here`` counter of
those very steps; the larger of operations / peak FLOP/s and bytes / peak
bytes/s), times the steps in the trace, over the summed device time of the
kernels' events (``params["prefixes"]`` of their names). Nothing where the
trace holds no such event; never 0, never clamped."""

from benchmark.harness import common, ops_from_shapes_ssm_lm, trace_reduce
from benchmark.readers.kernel_roofline_lm_pct import traced_rows


def read(run: dict, params: dict):
    trace = run.get("trace")
    if not trace or run["kind"] != "train_ssm_lm":
        return None
    events = next(iter(trace["planes"].values()))
    kernel_ns = trace_reduce.kernel_ns(events, params["prefixes"])
    steps = trace_reduce.executions(events, params["prefixes"])
    if not kernel_ns or not steps:
        return None
    config = run["config"]
    rows = traced_rows(run, steps)
    flops = ops_from_shapes_ssm_lm.train_step_flops(
        config, run["traffic"]["seq_len"], rows)["routed"]
    peaks = common.peaks_for(run["peaks"], run["device_kind"])
    least_s = max(flops / peaks["bf16_flops_per_s"],
                  ops_from_shapes_ssm_lm.routed_bytes(config, rows)
                  / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s * steps / (kernel_ns / 1e9)
