"""The state-space scan's share of its roofline in a step of ``kind:
"train_ssm_lm"``: the least time the chip could take for one step's scans
(``harness/ops_from_shapes_ssm_lm.py``: three forward passes' worth of the
chunked form's products at the published chunk size, over the peak FLOP/s, or
the bytes the scan must move at least once, ``x``, ``B``, ``C``, ``dt`` in and
``y`` out, forward and backward, over the peak bytes/s, whichever is larger;
both from the configuration's keys alone, so whatever implements the scan is
held to the same work) over the own device time a step of the operations
under ``params["scopes"]`` (``readers/scope_paths_device_ms.py``: forward,
backward and recomputation all count, and the needed work does not grow with
them). A scope and not a kernel's name, because the scan is many XLA
operations today; a kernel called under the same scope is read the same way.
Nothing where the program kept no record or no operation carries the scope;
never 0, never clamped."""

from benchmark.harness import common, ops_from_shapes_ssm_lm
from benchmark.readers import scope_paths_device_ms


def read(run: dict, params: dict):
    if run.get("kind") != "train_ssm_lm":
        return None
    ms = scope_paths_device_ms.read(run, {"scopes": params["scopes"]})
    if not ms:
        return None
    config = run["config"]
    peaks = common.peaks_for(run["peaks"], run["device_kind"])
    flops = ops_from_shapes_ssm_lm.train_step_flops(
        config, run["traffic"]["seq_len"])["scan"]
    least_s = max(flops / peaks["bf16_flops_per_s"],
                  ops_from_shapes_ssm_lm.scan_bytes(config)
                  / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
