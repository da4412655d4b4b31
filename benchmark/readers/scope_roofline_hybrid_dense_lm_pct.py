"""``scope_roofline_ssm_lm_pct`` for ``kind: "train_hybrid_dense_lm"``: the
state-space scan's share of its roofline in a step. The least time the chip
could take for one step's scans
(``harness/ops_from_shapes_hybrid_dense_lm.py``: three forward passes' worth
of the chunked form's products at the published chunk size, one group, over
the peak FLOP/s, or the bytes the scan must move at least once, forward and
backward, over the peak bytes/s, whichever is larger; both from the
configuration's keys alone, so whatever implements the scan is held to the
same work) over the own device time a step of the operations under
``params["scopes"]`` (``readers/scope_paths_device_ms.py``: forward,
backward and recomputation all count). Nothing where the program kept no
record or no operation carries the scope; never 0, never clamped."""

from benchmark.harness import common, ops_from_shapes_hybrid_dense_lm
from benchmark.readers import scope_paths_device_ms


def read(run: dict, params: dict):
    if run.get("kind") != "train_hybrid_dense_lm":
        return None
    ms = scope_paths_device_ms.read(run, {"scopes": params["scopes"]})
    if not ms:
        return None
    config = run["config"]
    peaks = common.peaks_for(run["peaks"], run["device_kind"])
    flops = ops_from_shapes_hybrid_dense_lm.train_step_flops(
        config, run["traffic"]["seq_len"])["scan"]
    least_s = max(flops / peaks["bf16_flops_per_s"],
                  ops_from_shapes_hybrid_dense_lm.scan_bytes(config)
                  / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
