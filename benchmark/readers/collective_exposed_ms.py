"""Per step, in ms, the time in which a collective runs on the first chip and
no other operation does: the own time of the operations whose instruction
name starts with one of ``params["prefixes"]`` (``all-reduce``,
``collective-permute``, ``all-gather``, ``reduce-scatter``, ``all-to-all``,
``async-collective``, each with its ``-start`` / ``-done``). Nothing on one
chip (a program without collectives)."""

from benchmark.harness import scope_reduce


def read(run: dict, params: dict):
    record, plane = scope_reduce.traced(run)
    if plane is None:
        return None
    steps = len(scope_reduce.step_runs(plane, record["step_module"]))
    exposed = scope_reduce.collective_by_block(plane, params["prefixes"])
    if not steps or not exposed:
        return None
    return sum(exposed.values()) / steps / 1e6
