"""A set of kernels' share of their roofline: the least time the chip could
take for the operations and bytes the algorithm needs (from shapes, the
larger of operations / peak FLOP/s and bytes / peak bytes/s, times the steps
in the trace) over the summed device time of the kernels' events. ``params``:
``prefixes`` of the kernels' event names, ``blocks`` whose attention matmuls
they compute. At the flagship the bound is compute (27.3 ms of operations
against 5.6 ms of bytes a step). Nothing where the trace holds no such
event; never 0."""

from benchmark.harness import common, ops_from_shapes, trace_reduce


def read(run: dict, params: dict):
    trace = run.get("trace")
    if not trace or run["kind"] != "train":
        return None
    events = next(iter(trace["planes"].values()))
    kernel_ns = trace_reduce.kernel_ns(events, params["prefixes"])
    steps = trace_reduce.executions(events, params["prefixes"])
    if not kernel_ns or not steps:
        return None
    config = run["config"]
    per_block = ops_from_shapes.train_step_flops(config)["attention"]
    flops = sum(per_block[b] for b in params["blocks"])
    peaks = common.peaks_for(run["peaks"], run["device_kind"])
    least_s = max(flops / peaks["bf16_flops_per_s"],
                  ops_from_shapes.attention_bytes(config, params["blocks"])
                  / peaks["hbm_bytes_per_s"]) / run["chips"]
    return 100.0 * least_s * steps / (kernel_ns / 1e9)
