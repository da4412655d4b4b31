"""``mfu_from_shapes_ssm_lm`` for ``kind: "train_hybrid_dense_lm"``: useful
matrix-multiply operations counted from shapes
(``harness/ops_from_shapes_hybrid_dense_lm.py``: forward + backward, nothing
recomputed; the state-space layers' projections and the scan's products as
the chunked form at the published chunk size counts them; the causal half of
the attention layer's core over every query head at the published head
width; every layer's gated MLP; the tied head) times steps per second over
the window, over chips x the published bf16 peak, in %. There is no router:
the count does not depend on the run."""

from benchmark.harness import common, ops_from_shapes_hybrid_dense_lm


def read(run: dict, params: dict):
    if run["kind"] != "train_hybrid_dense_lm" or not run["steps"]:
        return None
    flops = ops_from_shapes_hybrid_dense_lm.train_step_flops(
        run["config"], run["traffic"]["seq_len"])["total"]
    peak = common.peaks_for(run["peaks"], run["device_kind"])[
        "bf16_flops_per_s"]
    return 100.0 * flops * run["steps"] / run["window_s"] / (
        run["chips"] * peak)
