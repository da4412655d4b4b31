"""Own device time per step, in ms, of the operations whose scope falls in
one group of blocks (``params["group"]``: ``cross_attn``, ``pair_axial``,
``msa_axial``, ``feedforward``, ``model_rest``, ``outside_model``; the six
partition the plane). From the program's record of the traced steps; raises
if that record and the harness's own planes are not the same trace. Nothing
where the program kept no record."""

from benchmark.harness import scope_reduce


def read(run: dict, params: dict):
    record, plane = scope_reduce.traced(run)
    if plane is None:
        return None
    steps = len(scope_reduce.step_runs(plane, record["step_module"]))
    if not steps:
        return None
    sums = scope_reduce.by_block(plane)
    scope_reduce.check_against_planes(sums["total"], run["trace"]["planes"])
    return sums["groups"][params["group"]] / steps / 1e6
