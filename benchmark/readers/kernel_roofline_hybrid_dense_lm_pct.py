"""``kernel_roofline_swa_lm_pct`` for ``kind: "train_hybrid_dense_lm"``: the
attention kernels' share of their roofline in a step. The least time the
chip could take for the operations and bytes the attention layer's core
needs in one step (``harness/ops_from_shapes_hybrid_dense_lm.py``: scores
and values of every query head at the keys the causal mask leaves, at the
published head width; the larger of operations / peak FLOP/s and bytes /
peak bytes/s), times the steps in the trace, over the summed device time of
the kernels' events (``params["prefixes"]`` of their names). Every
execution's time counts; a kernel that computes the part of a block its mask
cuts, or pads a head to the matrix unit's width, loses share. Nothing where
the trace holds no such event; never 0, never clamped."""

from benchmark.harness import (
    common, ops_from_shapes_hybrid_dense_lm, trace_reduce,
)


def read(run: dict, params: dict):
    trace = run.get("trace")
    if not trace or run["kind"] != "train_hybrid_dense_lm":
        return None
    events = next(iter(trace["planes"].values()))
    kernel_ns = trace_reduce.kernel_ns(events, params["prefixes"])
    steps = trace_reduce.executions(events, params["prefixes"])
    if not kernel_ns or not steps:
        return None
    config = run["config"]
    flops = ops_from_shapes_hybrid_dense_lm.train_step_flops(
        config, run["traffic"]["seq_len"])["attention"]
    peaks = common.peaks_for(run["peaks"], run["device_kind"])
    least_s = max(
        flops / peaks["bf16_flops_per_s"],
        ops_from_shapes_hybrid_dense_lm.attention_bytes(config)
        / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s * steps / (kernel_ns / 1e9)
