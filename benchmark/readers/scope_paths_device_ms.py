"""Own device time per step, in ms, of the operations whose scope holds one
of ``params["scopes"]``: each a path such as ``mla_attn`` or ``moe/experts``
whose names occur, in that order and next to each other, among the scope's
names (``jit(step)/transpose(jvp(M))/layer_2/moe/experts/...`` holds
``moe/experts``; ``jvp(`` and ``transpose(`` wrappers are taken off first, so
forward, backward and recomputation all count), or whose instruction's name
starts with one of ``params["instructions"]``: XLA gives a ragged product's
kernel its own ``op_name`` (``ragged-dot-none``) in place of the scope it was
called under, so that one is found by name. With ``params["all_but"]``
instead: the operations that hold NONE of those paths and names, unscoped
ones included, so that a set of metrics over disjoint paths and one
``all_but`` over all of them partition the plane's own device time.

From the program's record of the traced steps (``harness/scope_reduce.py``);
raises if that record and the harness's own planes are not the same trace.
Nothing where the program kept no record."""

from benchmark.harness import scope_reduce


def names(scope: str) -> list:
    out = []
    for part in scope.split("/"):
        while scope_reduce._WRAPPED.match(part):
            part = scope_reduce._WRAPPED.match(part).group(1)
        out.append(part)
    return out


def holds(scope_names: list, path: str) -> bool:
    want = path.split("/")
    return any(scope_names[i:i + len(want)] == want
               for i in range(len(scope_names) - len(want) + 1))


def read(run: dict, params: dict):
    record, plane = scope_reduce.traced(run)
    if plane is None:
        return None
    steps = len(scope_reduce.step_runs(plane, record["step_module"]))
    if not steps:
        return None
    paths = params.get("scopes") or params["all_but"]
    inside = "scopes" in params
    by_name = tuple(params.get("instructions", ()))
    ops = plane["ops"]
    own = scope_reduce.own_times(ops)
    scope_reduce.check_against_planes(sum(own), run["trace"]["planes"])
    verdict = {}  # scope -> holds one of the paths
    total = 0.0
    for (instruction, scope, _, _), ns in zip(ops, own):
        if scope not in verdict:
            found = names(scope)
            verdict[scope] = any(holds(found, p) for p in paths)
        if (verdict[scope] or instruction.startswith(by_name)) == inside:
            total += ns
    return total / steps / 1e6
