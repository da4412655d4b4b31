"""The indexer's share of its roofline in a step of ``kind:
"train_dsa_lm"``: the least time the chip could take for the work the
indexer's scope holds in one step (``harness/ops_from_shapes_dsa_lm.py``
``indexer``: its projections, forward and backward, and the selection's
scores over every causal key; bound by operations, the bytes it must move
being a few MB a layer) over the own device time a step of the operations
under ``params["scopes"]`` (``readers/scope_paths_device_ms.py``: forward,
backward and recomputation all count). A scope and not a kernel's name,
because the indexer is XLA operations today. Nothing where the program kept
no record or no operation carries the scope; never 0, never clamped."""

from benchmark.harness import common, ops_from_shapes_dsa_lm
from benchmark.readers import scope_paths_device_ms


def read(run: dict, params: dict):
    if run.get("kind") != "train_dsa_lm":
        return None
    ms = scope_paths_device_ms.read(run, {"scopes": params["scopes"]})
    if not ms:
        return None
    flops = ops_from_shapes_dsa_lm.train_step_flops(
        run["config"], run["traffic"]["seq_len"])["indexer"]
    peak = common.peaks_for(run["peaks"], run["device_kind"])[
        "bf16_flops_per_s"]
    return 100.0 * flops / peak / (ms / 1e3)
