"""The whole step's share of the chips' peak: matrix-multiply operations
counted from shapes (forward + backward, nothing recomputed) times steps per
second over the window, over chips x the published bf16 peak, in %."""

from benchmark.harness import common, ops_from_shapes


def read(run: dict, params: dict):
    if run["kind"] != "train" or not run["steps"]:
        return None
    flops = ops_from_shapes.train_step_flops(run["config"])["total"]
    peak = common.peaks_for(run["peaks"], run["device_kind"])[
        "bf16_flops_per_s"]
    return 100.0 * flops * run["steps"] / run["window_s"] / (
        run["chips"] * peak)
