"""The own device time of operations that carry no phase scope at all (not
under the model, not under ``loss``/``grads_ok``/``grad_clip``/``optimizer``/
``rng``/``metrics``, not another program's), as a share of all own device
time of the traced steps, in %: the health of the names themselves."""

from benchmark.harness import scope_reduce


def read(run: dict, params: dict):
    _, plane = scope_reduce.traced(run)
    if plane is None:
        return None
    sums = scope_reduce.by_block(plane)
    if not sums["total"]:
        return None
    return 100.0 * sums["unnamed"] / sums["total"]
