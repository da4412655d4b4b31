"""From the program's own record of a profiler trace to per-block device
time, idle time by host span, exposed collective time and set-up phases. Pure
functions over plain lists; checked on a hand-written record in
``tests/test_scope_readers.py``.

``harness/train.py`` keeps from a trace only ``planes`` (name, start, end of
each operation) and deletes the trace before a reader runs. The program
(``alphafold2_tpu/observe/profiler.py``) reads the same trace when it stops it
and keeps a **record** in memory, which ``program_record()`` fetches:

- ``devices``: {device plane: {``ops``: [(instruction, scope, start_ns,
  end_ns)], ``modules``: [(name, start_ns, end_ns)], ``steps``: [...]}}. A
  scope is the instruction's ``op_name`` (``jit(step)/jvp(Alphafold2)/trunk/
  layer_0/pair_from_msa/to_q/dot_general``), ``jit(<program>)`` for an
  operation of another program, "" where the compiled step names none;
- ``host``: [(name, start_ns, end_ns, thread, args)], the program's spans on
  the same clock; ``step_module``: the step program's name;
- ``spans``: the tracer's own events (Chrome trace events, microseconds).

Only these raw lists are read. The arithmetic is here, so a change to the
program's log line cannot move a metric: own time is an operation's duration
less what the operations nested inside it cover (as
``trace_reduce.self_times``), and times are taken from the first device
plane (as ``trace_reduce.summarize`` does for the breakdown). A program
without such a record (the parent of the PR that added it) gives ``None``.
"""

from __future__ import annotations

import re

from benchmark.harness import trace_reduce

PHASES = ("loss", "grads_ok", "grad_clip", "optimizer", "rng", "metrics")
MODEL = "Alphafold2"
# block (the module under layer_N) -> the metric's group
GROUPS = {
    "pair_from_msa": "cross_attn", "msa_from_pair": "cross_attn",
    "pair_axial": "pair_axial", "msa_axial": "msa_axial",
    "pair_ff": "feedforward", "msa_ff": "feedforward",
}
MODEL_REST, OUTSIDE_MODEL = "model_rest", "outside_model"
ALL_GROUPS = ("cross_attn", "pair_axial", "msa_axial", "feedforward",
              MODEL_REST, OUTSIDE_MODEL)
OUTSIDE_ANY_SPAN = "outside_any_span"
STEPS_NAME = "train"  # the StepTraceAnnotation around an iteration: no span

_WRAPPED = re.compile(r"^(?:jvp|transpose)\((.*)\)$")
_LAYER = re.compile(r"^layer_\d+$")


def program_record():
    """The record the program kept of its newest trace, or None."""
    try:
        from alphafold2_tpu.observe import profiler

        return profiler.last_record()
    except (ImportError, AttributeError):
        return None


def first_plane(record):
    """The first device plane that holds operations, or None."""
    if not record:
        return None
    for plane in (record.get("devices") or {}).values():
        if plane.get("ops"):
            return plane
    return None


def traced(run: dict) -> tuple:
    """(record, its first device plane) for a traced run; (None, None) where
    the run took no trace or the program kept no record of one."""
    record = program_record() if run.get("trace") else None
    plane = first_plane(record)
    return (record, plane) if plane is not None else (None, None)


def classify(scope: str) -> tuple:
    """(group, direction, block, named) of one scope. ``direction`` is
    ``fwd`` under ``jvp(``, ``bwd`` under ``transpose(``, else "";
    ``named`` is False where the operation has no phase scope at all."""
    if not scope:
        return OUTSIDE_MODEL, "", "unscoped", False
    parts = scope.split("/")
    way = ("bwd" if any(p.startswith("transpose(") for p in parts)
           else "fwd" if any(p.startswith("jvp(") for p in parts) else "")
    plain = []
    for p in parts:
        while _WRAPPED.match(p):
            p = _WRAPPED.match(p).group(1)
        plain.append(p)
    if MODEL in plain:
        inner = plain[plain.index(MODEL) + 1:]
        for i, p in enumerate(inner):
            if _LAYER.match(p):
                inner = inner[i + 1:]
                break
        block = inner[0] if inner else MODEL
        return GROUPS.get(block, MODEL_REST), way, block, True
    phases = [p for p in plain if p in PHASES]
    if phases:  # the innermost: grad_clip sits inside optimizer
        return OUTSIDE_MODEL, way, phases[-1], True
    if plain[0].startswith("jit(") and not plain[0].startswith("jit(step"):
        return OUTSIDE_MODEL, way, plain[0], True  # another program's name
    # bare jit(step)/..., or a copy named by the argument it copies
    return OUTSIDE_MODEL, way, "unscoped", False


def own_times(ops) -> list:
    """Own nanoseconds of each of ``ops`` [(instruction, scope, start_ns,
    end_ns)], in their order."""
    own = [0.0] * len(ops)
    stack = []  # [index, end]
    for i in sorted(range(len(ops)), key=lambda i: (ops[i][2], -ops[i][3])):
        start, end = ops[i][2], ops[i][3]
        while stack and stack[-1][1] <= start:
            stack.pop()
        if stack:
            own[stack[-1][0]] -= min(end, stack[-1][1]) - start
        own[i] += end - start
        stack.append([i, end])
    return own


def step_runs(plane: dict, step_module: str) -> list:
    """[(start_ns, end_ns)] of the step program's executions."""
    return [(s, e) for name, s, e in plane.get("modules", [])
            if name.partition("(")[0] == step_module]


def by_block(plane: dict) -> dict:
    """Own nanoseconds of a plane's operations, summed three ways:
    ``groups`` {group: ns}, ``blocks`` {"fwd/pair_from_msa": ns, ...},
    ``unnamed`` (no phase scope), and their ``total``."""
    ops = plane["ops"]
    groups = dict.fromkeys(ALL_GROUPS, 0.0)
    blocks: dict = {}
    unnamed = 0.0
    for (_, scope, _, _), own in zip(ops, own_times(ops)):
        group, way, block, named = classify(scope)
        groups[group] += own
        key = f"{way}/{block}" if way else block
        blocks[key] = blocks.get(key, 0.0) + own
        if not named:
            unnamed += own
    return {"groups": groups, "blocks": blocks, "unnamed": unnamed,
            "total": sum(groups.values())}


def check_against_planes(total_ns: float, planes: dict,
                         tolerance: float = 0.01) -> None:
    """The record and the harness read one trace: the blocks' sum and the
    harness's own ``self_times`` total over its first plane may differ by
    ``tolerance`` at most."""
    events = next(iter(planes.values()))
    harness = sum(trace_reduce.self_times(events).values())
    if abs(total_ns - harness) > tolerance * harness:
        raise RuntimeError(
            f"the program's record sums to {total_ns / 1e6:.3f} ms of own "
            f"device time, the harness's planes to {harness / 1e6:.3f} ms: "
            "they are not the same trace")


def idle_gaps(ops, min_ns: float = 0.0) -> list:
    """[(start_ns, end_ns)] between the merged intervals of ``ops``, those
    longer than ``min_ns``."""
    gaps, reach = [], None
    for _, _, start, end in sorted(ops, key=lambda o: o[2]):
        if reach is not None and start - reach > min_ns:
            gaps.append((reach, start))
        reach = end if reach is None else max(reach, end)
    return gaps


def loop_thread(host) -> str:
    """The thread the step annotations are on, else the busiest."""
    counts: dict = {}
    for name, _, _, thread, _ in host:
        weight = len(host) + 1 if name == STEPS_NAME else 1
        counts[thread] = counts.get(thread, 0) + weight
    return max(counts, key=counts.get) if counts else ""


def idle_by_span(gaps, host) -> dict:
    """{span: ns}: every gap cut at the borders of the loop thread's spans,
    each piece given to the innermost span that covers it (of those that
    cover it, the one that started last); ``outside_any_span`` where none
    does."""
    thread = loop_thread(host)
    spans = [(s, e, n) for n, s, e, t, _ in host
             if t == thread and n != STEPS_NAME]
    out: dict = {}
    for a, b in gaps:
        over = [(s, e, n) for s, e, n in spans if s < b and e > a]
        cuts = sorted({a, b, *(x for s, e, _ in over for x in (s, e)
                               if a < x < b)})
        for x, y in zip(cuts, cuts[1:]):
            covering = [(s, n) for s, e, n in over if s <= x and e >= y]
            name = max(covering)[1] if covering else OUTSIDE_ANY_SPAN
            out[name] = out.get(name, 0.0) + (y - x)
    return out


def collective_by_block(plane: dict, prefixes) -> dict:
    """{block: ns} of the own time of the operations whose instruction name
    starts with one of ``prefixes`` (the collectives): the time in which a
    collective (or the wait for one, ``-done``) runs on the chip and no other
    operation does."""
    ops = plane["ops"]
    out: dict = {}
    for (instruction, scope, _, _), own in zip(ops, own_times(ops)):
        if instruction.startswith(tuple(prefixes)):
            _, way, block, _ = classify(scope)
            key = f"{way}/{block}" if way else block
            out[key] = out.get(key, 0.0) + own
    return out


def span_events(record, name: str) -> list:
    """The tracer's complete events of one name, in order of their start."""
    spans = (record or {}).get("spans") or []
    return sorted((e for e in spans
                   if e.get("name") == name and e.get("ph") == "X"),
                  key=lambda e: e["ts"])
