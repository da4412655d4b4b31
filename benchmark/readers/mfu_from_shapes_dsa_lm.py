"""``mfu_from_shapes_swa_lm`` for ``kind: "train_dsa_lm"``: useful
matrix-multiply operations counted from shapes
(``harness/ops_from_shapes_dsa_lm.py``: forward + backward, nothing
recomputed; the core at the keys each query selected, min(t + 1, 2,048),
not dense; the indexer's scores forward over the causal keys and backward
over the selected; the held experts at the rows the window's steps sent
them, the mean of the program's ``moe/assignments_here`` counter over the
window) times steps per second over the window, over chips x the published
bf16 peak, in %."""

from benchmark.harness import common, ops_from_shapes_dsa_lm


def read(run: dict, params: dict):
    if run["kind"] != "train_dsa_lm" or not run["steps"]:
        return None
    rows = run["counters"]["moe/assignments_here"]
    flops = ops_from_shapes_dsa_lm.train_step_flops(
        run["config"], run["traffic"]["seq_len"],
        sum(rows) / len(rows))["total"]
    peak = common.peaks_for(run["peaks"], run["device_kind"])[
        "bf16_flops_per_s"]
    return 100.0 * flops * run["steps"] / run["window_s"] / (
        run["chips"] * peak)
