"""The own device time of the step's operations whose scope the compiled
step's text does not give and the program inferred (``record["inferred"]``:
``{instruction: rule}``, the rule one of ``body``, the fusion's own
instructions; ``user``, what consumes the result; ``operand``, what made the
operand; ``kin``, a kernel of XLA's own name, from those of that name around
it: ``alphafold2_tpu/observe/profiler.py`` ``infer_scopes``), as a share of
all own device time of the traced steps, in %: the health of the join between
the text and the trace. A compiler or a change that strips more names shows
here, while ``unscoped_device_pct.*`` shows only what nothing could name. An
operation of another program carries that program's name and no path
(``jit(_threefry_split)``), so an instruction of the same name there is not
counted. Nothing where the program kept no record, or one without the key (a
program from before the inference)."""

from benchmark.harness import scope_reduce


def read(run: dict, params: dict):
    record, plane = scope_reduce.traced(run)
    if plane is None or "inferred" not in record:
        return None
    inferred = record["inferred"]
    ops = plane["ops"]
    own = scope_reduce.own_times(ops)
    total = sum(own)
    if not total:
        return None
    return 100.0 * sum(
        ns for (instruction, scope, _, _), ns in zip(ops, own)
        if instruction in inferred and "/" in scope) / total
