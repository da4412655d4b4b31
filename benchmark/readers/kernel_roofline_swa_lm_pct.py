"""``kernel_roofline_lm_pct`` for ``kind: "train_swa_lm"``: a set of
kernels' share of their roofline in a step. The least time the chip could
take for the operations and bytes the algorithm needs in one step
(``harness/ops_from_shapes_swa_lm.py``, ``params["work"]``: ``attention`` =
scores and values of every query head at the keys its layer's mask leaves,
``routed`` = the held experts' products at the rows the traced steps sent
them, the program's ``moe/assignments_here`` counter of those very steps;
the larger of operations / peak FLOP/s and bytes / peak bytes/s), times the
steps in the trace, over the summed device time of the kernels' events
(``params["prefixes"]`` of their names). Every execution's time counts, a
recomputed forward's too, and the needed work does not grow with it; a
kernel that computes the part of a block its mask cuts loses share. Nothing
where the trace holds no such event; never 0, never clamped."""

from benchmark.harness import common, ops_from_shapes_swa_lm, trace_reduce
from benchmark.readers.kernel_roofline_lm_pct import traced_rows


def read(run: dict, params: dict):
    trace = run.get("trace")
    if not trace or run["kind"] != "train_swa_lm":
        return None
    events = next(iter(trace["planes"].values()))
    kernel_ns = trace_reduce.kernel_ns(events, params["prefixes"])
    steps = trace_reduce.executions(events, params["prefixes"])
    if not kernel_ns or not steps:
        return None
    config = run["config"]
    if params["work"] == "routed":
        rows = traced_rows(run, steps)
        least_bytes = ops_from_shapes_swa_lm.routed_bytes(config, rows)
    else:
        rows = None
        least_bytes = ops_from_shapes_swa_lm.attention_bytes(config)
    flops = ops_from_shapes_swa_lm.train_step_flops(
        config, run["traffic"]["seq_len"], rows)[params["work"]]
    peaks = common.peaks_for(run["peaks"], run["device_kind"])
    least_s = max(flops / peaks["bf16_flops_per_s"],
                  least_bytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s * steps / (kernel_ns / 1e9)
