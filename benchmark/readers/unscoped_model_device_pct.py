"""``unscoped_device_pct`` for a model named by ``params["model"]`` (the
Flax module's class, as the scopes hold it: ``jit(step)/jvp(MlaMoeLM)/...``):
the own device time of operations that carry no phase scope at all (not under
that model, not under one of ``scope_reduce.PHASES``, not another program's),
as a share of all own device time of the traced steps, in %: the health of
the names themselves. The block metrics of that model take such time into
their ``all_but`` remainder, or find a kernel by its instruction's name where
XLA gives it an ``op_name`` of its own (a ragged product's ``ragged-dot-none``
counts as unscoped here): a change that loses a scope shows in this number
and nowhere else. Nothing where the program kept no record."""

from benchmark.harness import scope_reduce
from benchmark.readers.scope_paths_device_ms import names


def named(scope: str, model: str) -> bool:
    if not scope:
        return False
    plain = names(scope)
    if model in plain or any(p in scope_reduce.PHASES for p in plain):
        return True
    # another program's name; a bare jit(step)/... is not one
    return plain[0].startswith("jit(") and not plain[0].startswith("jit(step")


def read(run: dict, params: dict):
    _, plane = scope_reduce.traced(run)
    if plane is None:
        return None
    ops = plane["ops"]
    own = scope_reduce.own_times(ops)
    total = sum(own)
    if not total:
        return None
    verdict = {}
    unnamed = 0.0
    for (_, scope, _, _), ns in zip(ops, own):
        if scope not in verdict:
            verdict[scope] = named(scope, params["model"])
        if not verdict[scope]:
            unnamed += ns
    return 100.0 * unnamed / total
