"""``kernel_roofline_swa_lm_pct`` for ``kind: "train_dsa_lm"``: a set of
kernels' share of their roofline in a step. The least time the chip could
take for the operations and bytes the algorithm needs in one step
(``harness/ops_from_shapes_dsa_lm.py``, ``params["work"]``: ``core`` = the
sparse core's scores and values of every query head at the keys each query
selected, three passes' worth, with q, k, v, the output, the log-sum-exp
and the packed selection, forward and backward; ``routed`` = the held
experts' products at the rows the traced steps sent them, the program's
``moe/assignments_here`` counter of those very steps; the larger of
operations / peak FLOP/s and bytes / peak bytes/s), times the steps in the
trace, over the summed device time of the kernels' events
(``params["prefixes"]`` of their names). Every execution's time counts; a
core kernel that computes whole tiles for keys a query did not select loses
share. Nothing where the trace holds no such event; never 0, never
clamped."""

from benchmark.harness import common, ops_from_shapes_dsa_lm, trace_reduce
from benchmark.readers.kernel_roofline_lm_pct import traced_rows


def read(run: dict, params: dict):
    trace = run.get("trace")
    if not trace or run["kind"] != "train_dsa_lm":
        return None
    events = next(iter(trace["planes"].values()))
    kernel_ns = trace_reduce.kernel_ns(events, params["prefixes"])
    steps = trace_reduce.executions(events, params["prefixes"])
    if not kernel_ns or not steps:
        return None
    config, seq_len = run["config"], run["traffic"]["seq_len"]
    if params["work"] == "routed":
        rows = traced_rows(run, steps)
        least_bytes = ops_from_shapes_dsa_lm.routed_bytes(config, rows)
    else:
        rows = None
        least_bytes = ops_from_shapes_dsa_lm.core_bytes(config, seq_len)
    flops = ops_from_shapes_dsa_lm.train_step_flops(
        config, seq_len, rows)[params["work"]]
    peaks = common.peaks_for(run["peaks"], run["device_kind"])
    least_s = max(flops / peaks["bf16_flops_per_s"],
                  least_bytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s * steps / (kernel_ns / 1e9)
