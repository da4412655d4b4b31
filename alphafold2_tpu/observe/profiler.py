"""XLA trace capture over a configured train-step window, reduced in place.

Complements the span tracing in :mod:`alphafold2_tpu.observe.tracing`:
``Profiler`` starts and stops a ``jax.profiler`` trace
(``train.profile_dir`` / ``train.profile_steps``) and, when it stops, reads
the ``.xplane.pb`` it has just written into a **record** kept in memory:

- per device plane the operations of the ``XLA Ops`` line as ``(instruction,
  scope, start_ns, end_ns)`` and the program executions of ``XLA Modules``
  as ``(name, start_ns, end_ns)``;
- the host plane's annotation events whose name an enabled ``Tracer`` sent
  there, as ``(name, start_ns, end_ns, thread, args)``, moved onto the
  device's clock by ``clock_offset_ns`` (``clock_offset``);
- ``inferred``: ``{instruction: rule}`` for the step's instructions whose
  scope the compiled text does not give and ``infer_scopes`` found.

An operation's scope is the ``op_name`` of its HLO instruction
(``jit(step)/jvp(Alphafold2)/trunk/layer_0/pair_from_msa/to_q/dot_general``:
Flax names the modules, ``make_train_step`` the phases around the model). The
TPU's trace does not carry it (an event is named by the instruction's text
without metadata), so it is joined by instruction name from the compiled
step's text (``name_operations``). The compiled step names a third of what
runs: the compiler's own moves, layout copies, some fusions and the kernels
it writes itself carry no ``op_name``, so the same pass reads the text as a
graph and takes such an instruction's scope from the fusion's body, from what
uses its result, or from what made its operand (``infer_scopes``). An
operation of another program (the loop's ``jax.random.split``) is given that
program's name, ``jit(<name>)``.

``summarize`` turns a record into the one line ``train()`` logs: device ms a
step by block, forward and backward apart, the step program's device ms, the
idle share, idle time by the innermost host span that covers it, the
collectives' own time by block, and under a context-parallel ring how much
of the cross-attention blocks is a ring step's kernels (``ring_block``) and
how much the merging around them (``ring_merge``). ``last_record()`` hands
the newest record to whoever asks (the benchmark's readers), also after
``train()`` was left by an exception. Nothing here runs unless a trace
directory is configured.
"""

from __future__ import annotations

import glob
import os
import re
import time
from typing import Callable, NamedTuple, Optional, Tuple

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
PHASES = ("loss", "grads_ok", "grad_clip", "optimizer", "metrics")
COLLECTIVES = ("all-reduce", "collective-permute", "all-gather",
               "reduce-scatter", "all-to-all", "async-collective")
OUTSIDE_ANY_SPAN = "outside_any_span"
# the loop thread's spans inside which the step's program and the key
# split's (jax's name for it) are sent to the device
STEP_SPAN, RNG_SPAN = "train.step", "train.rng"
RNG_PROGRAM = "jit__threefry_split"
# parallel/seq_parallel.py's two scopes inside a cross-attention block: a
# ring step's kernel calls, and the merging of the steps around them
RING_PARTS = ("ring_block", "ring_merge")
# The profiler's own Python tracer is off: it adds some 10,000 events of
# Python calls a traced step to the host plane, none of which is read.
PYTHON_TRACER_LEVEL = 0
# how far ``infer_scopes`` walks from an instruction without a scope: a move
# between memory spaces is two steps from its kernel (start, done), a tuple
# and its element two more
WALK_DEPTH = 6
# and how far where neither walk of that depth found a path: a prefetch the
# compiler slices, lays out anew and slices again is nine steps from the
# fusion that reads it (copy-start, copy-done, slice-start, slice-done, a
# ConcatBitcast, a copy, slice-start, slice-done, a ConcatBitcast)
FAR_WALK_DEPTH = 12

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# `` fusion(%a, /*index=5*/%b)``: the first lower-case word before a bracket
# (a type's ``T(8,128)`` and ``S(1)`` follow no blank), and its operands
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(([^)]*)\)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(")
_WRAPPED = re.compile(r"^(?:jvp|transpose)\((.*)\)$")
_LAYER = re.compile(r"^layer_\d+$")

_LAST: Optional[dict] = None


def last_record() -> Optional[dict]:
    """The record of the newest trace this process has stopped (with the
    tracer's own events under ``spans`` and the compile counter under
    ``compile_counts`` once ``train()`` has handed them over); None if no
    ``Profiler`` was given a directory."""
    return _LAST


class Instruction(NamedTuple):
    """One instruction of a compiled program's text."""
    op_name: str          # "" where the text gives none
    opcode: str
    operands: Tuple[str, ...]
    calls: str            # the computation a fusion calls, else ""
    computation: str      # the computation it sits in


def instruction_graph(hlo_text: str) -> Tuple[str, dict]:
    """(module name, {instruction name: Instruction}) of a compiled
    program's text (``compiled.as_text()``), in the text's order. An
    instruction's text may run over several lines (a kernel's frontend
    attributes can hold newlines: the splash kernels' do), its metadata then
    on a later one: the ``op_name`` and the ``calls=`` belong to the last
    instruction that began."""
    module = hlo_text.split(None, 2)[1].rstrip(",") if hlo_text.startswith(
        "HloModule") else ""
    graph, computation, last = {}, "", None
    for line in hlo_text.splitlines():
        named = _INSTRUCTION.match(line)
        if named:
            last = named.group(1)
            opcode = _OPCODE.search(line, named.end())
            graph[last] = Instruction(
                "", opcode.group(1) if opcode else "",
                tuple(_OPERAND.findall(opcode.group(2))) if opcode else (),
                "", computation)
        elif line.endswith("{"):
            opened = _COMPUTATION.match(line)
            if opened:
                computation, last = opened.group(1), None
        if last is None:
            continue
        at = graph[last]
        if not at.calls and at.opcode == "fusion":
            calls = _CALLS.search(line)
            if calls:
                at = graph[last] = at._replace(calls=calls.group(1))
        if not at.op_name:
            op_name = _OP_NAME.search(line)
            if op_name:
                graph[last] = at._replace(op_name=op_name.group(1))
    return module, graph


def instruction_scopes(hlo_text: str) -> Tuple[str, dict]:
    """(module name, {instruction name: op_name}) of a compiled program's
    text, for the instructions that carry one."""
    module, graph = instruction_graph(hlo_text)
    return module, {name: at.op_name for name, at in graph.items()
                    if at.op_name}


def read_scope(op_name: str) -> bool:
    """Whether an ``op_name`` is a path from a jitted program's root
    (``jit(step)/...``). XLA's own names for a kernel (``ragged-dot-none``)
    and a copy named by the argument it copies are no path."""
    return op_name.startswith("jit(")


def infer_scopes(graph: dict) -> Tuple[dict, dict]:
    """({instruction: scope}, {instruction: rule}) of one program's graph.
    An instruction whose ``op_name`` is a path keeps it. One without takes,
    stopping at the first that gives a path: ``body``, for a fusion, the
    commonest path among the instructions of the computation it calls (the
    first in the body's order where two are as common); ``user``, the path
    (for a fusion without, its body's) of the nearest instruction that
    consumes its result, breadth-first through consumers that have none, in
    its own computation and ``WALK_DEPTH`` steps at most (``copy-start`` ->
    ``copy-done`` -> the kernel); ``operand``, the same walk backwards
    through what made its operands; then ``user`` once more,
    ``FAR_WALK_DEPTH`` steps at most, so that what the short walks settled
    stays as they settled it. What none reaches keeps what the text gave it.

    ``kin`` comes before the three for the instructions that carry a name of
    XLA's own (``ragged-dot-none``: a kernel XLA wrote in place of one
    operation of the program, whose path it dropped). Such a kernel computes,
    so neither its consumer nor its producer says where it was called (the
    experts' second product feeds ``combine``, their weight gradients feed
    ``metrics``); but those of one name in one computation were called from
    one place, so together they take ``<path>/<XLA's name>``, the path being
    the one most of them find next to them, users or operands, less its
    last name."""
    bodies: dict = {}  # computation -> {path: count}, in the body's order
    users: dict = {}   # instruction -> its consumers, in the text's order
    kin: dict = {}     # (computation, XLA's own name) -> instructions
    for name, at in graph.items():
        if read_scope(at.op_name):
            counts = bodies.setdefault(at.computation, {})
            counts[at.op_name] = counts.get(at.op_name, 0) + 1
        elif at.op_name:
            kin.setdefault((at.computation, at.op_name), []).append(name)
        for operand in at.operands:
            users.setdefault(operand, []).append(name)
    scopes, inferred = {}, {}

    def known(name):
        at = graph[name]
        if read_scope(at.op_name):
            return at.op_name
        if at.op_name:  # XLA's own name: what its kin settled on, if they did
            return scopes[name] if inferred.get(name) == "kin" else ""
        counts = bodies.get(at.calls)
        return max(counts, key=counts.get) if counts else ""

    def nearest(name, neighbours, depth=None):
        """The paths of the nearest instructions that have one, along
        ``neighbours``, nearest level first and in the text's order."""
        seen, level = {name}, [name]
        for _ in range(depth or WALK_DEPTH):
            level = [n for near in level for n in neighbours(near)
                     if n in graph and n not in seen
                     and graph[n].computation == graph[name].computation
                     and not seen.add(n)]
            found = [path for path in map(known, level) if path]
            if found:
                return found
        return []

    def consumers(name):
        return users.get(name, ())

    def producers(name):
        return graph[name].operands

    # consumers' kin first: a walk that meets one then stops at its path
    for (_, own_name), members in reversed(list(kin.items())):
        votes: dict = {}
        for name in members:
            around = nearest(name, consumers) + nearest(name, producers)
            for path in dict.fromkeys(p.rpartition("/")[0] for p in around):
                votes[path] = votes.get(path, 0) + 1
        if votes:
            path = max(votes, key=votes.get)
            for name in members:
                scopes[name], inferred[name] = f"{path}/{own_name}", "kin"
    for name, at in graph.items():
        if name in inferred:  # settled with its kin
            continue
        scope, rule = known(name), "body"
        if not scope:
            scope, rule = next(iter(nearest(name, consumers)), ""), "user"
        if not scope:
            scope, rule = next(iter(nearest(name, producers)), ""), "operand"
        if not scope:
            scope, rule = next(iter(nearest(
                name, consumers, FAR_WALK_DEPTH)), ""), "user"
        if scope and not read_scope(at.op_name):
            inferred[name] = rule
        if scope or at.op_name:
            scopes[name] = scope or at.op_name
    return scopes, inferred


class Profiler:
    """Start/stop a jax profiler trace across a [start, stop) step window,
    and reduce it when it stops.

    ``span_names`` returns the names whose host events are kept (a
    ``Tracer.annotated_names``), ``log(step, metrics)`` takes the one line.
    """

    def __init__(self, trace_dir: Optional[str],
                 steps: Tuple[int, int] = (10, 13),
                 span_names: Optional[Callable[[], set]] = None,
                 log: Optional[Callable[[int, dict], None]] = None):
        global _LAST
        self._dir = trace_dir
        self._start, self._stop = steps
        self._active = False
        self._span_names = span_names or set
        self._log = log
        self._module, self._scopes, self._inferred = "", {}, {}
        self._names_s = 0.0
        if trace_dir:
            _LAST = {"devices": {}, "host": [], "step_module": ""}

    @property
    def enabled(self) -> bool:
        return bool(self._dir)

    def name_operations(self, hlo_text: str) -> None:
        """The compiled step's text: which program is the step, and the
        scope of each of its instructions, read or inferred."""
        t0 = time.perf_counter()
        self._module, graph = instruction_graph(hlo_text)
        self._scopes, self._inferred = infer_scopes(graph)
        self._names_s = time.perf_counter() - t0

    def maybe_start(self, step: int) -> None:
        if self._dir and step == self._start and not self._active:
            import jax

            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = PYTHON_TRACER_LEVEL
            jax.profiler.start_trace(self._dir, profiler_options=options)
            self._active = True

    def maybe_stop(self, step: int) -> None:
        if self._active and step >= self._stop:
            import jax

            jax.block_until_ready(jax.numpy.zeros(()))
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
            self._active = False
            t1 = time.perf_counter()
            self._reduce(step, t1 - t0)

    def close(self) -> None:
        """Stop a trace the window's end never came to (the loop was left
        early); nothing is read from it."""
        if self._active:
            import jax

            jax.profiler.stop_trace()
            self._active = False

    def _reduce(self, step: int, stop_s: float) -> None:
        global _LAST
        t0 = time.perf_counter()
        found = sorted(glob.glob(os.path.join(
            self._dir, "**", "*.xplane.pb"), recursive=True),
            key=os.path.getmtime)
        if not found:
            return
        record = read_record(found[-1], self._span_names(), self._module,
                             self._scopes, self._inferred)
        record["names_s"] = self._names_s  # set-up's share of the join
        record["stop_trace_s"] = stop_s
        record["read_s"] = time.perf_counter() - t0
        _LAST = record
        if self._log is not None:
            self._log(step, {"event": "profile", **summarize(record)})


def hand_over(spans: list, compile_counts: dict) -> None:
    """Attach the tracer's own events and the compile counter to the record
    (``train()`` calls this on its way out, whichever way that is)."""
    if _LAST is not None:
        _LAST["spans"] = spans
        _LAST["compile_counts"] = compile_counts


# ------------------------------------------------------ trace -> record ---


def _instruction_name(event_name: str) -> str:
    """``%fusion.16 = (u32[1]...) fusion(...)`` -> ``fusion.16``."""
    return event_name.partition(" = ")[0].strip().lstrip("%")


def _spans(line) -> list:
    return sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events), key=lambda span: span[1])


def _module_scope(module_event_name: str) -> str:
    """``jit__threefry_split(1193...)`` -> ``jit(_threefry_split)``."""
    name = module_event_name.partition("(")[0]
    return f"jit({name[4:]})" if name.startswith("jit_") else name


def _device_plane(plane, step_module: str, scopes: dict) -> Optional[dict]:
    lines = {line.name: line for line in plane.lines}
    if OPS_LINE not in lines:
        return None
    modules = _spans(lines[MODULES_LINE]) if MODULES_LINE in lines else []
    ops = []
    at = 0  # modules run one after another: walk them with the operations
    for e in sorted(lines[OPS_LINE].events, key=lambda e: e.start_ns):
        while at < len(modules) and modules[at][2] <= e.start_ns:
            at += 1
        inside = at < len(modules) and modules[at][1] <= e.start_ns
        instruction = _instruction_name(e.name)
        if not inside:
            scope = ""
        elif modules[at][0].partition("(")[0] == step_module:
            scope = scopes.get(instruction, "")
        else:
            scope = _module_scope(modules[at][0])
        ops.append((instruction, scope, e.start_ns,
                    e.start_ns + e.duration_ns))
    return {"ops": ops, "modules": modules}


def _cpu_ops(plane, step_module: str, scopes: dict) -> Optional[dict]:
    """The CPU backend has no device plane: its operations are host events
    that carry ``hlo_op`` and ``hlo_module`` as stats."""
    ops = []
    for line in plane.lines:
        for e in line.events:
            stats = dict(e.stats)
            if "hlo_op" not in stats:
                continue
            module = str(stats.get("hlo_module", ""))
            scope = (scopes.get(str(stats["hlo_op"]), "")
                     if module == step_module else _module_scope(module))
            ops.append((str(stats["hlo_op"]), scope, e.start_ns,
                        e.start_ns + e.duration_ns))
    if not ops:
        return None
    return {"ops": sorted(ops, key=lambda o: o[2]), "modules": []}


def clock_offset(modules, host, step_module: str) -> int:
    """The nanoseconds to add to the host's events so that no execution of
    the step's program or of the key split in ``modules`` starts before the
    loop thread's span that sent it does: the smallest such shift, so 0
    where none starts early (the two clocks of one trace differ by up to
    about a millisecond). Executions and spans are paired from the last
    backwards: the first of a trace may have been sent before it began."""
    thread = loop_thread(host)
    early = 0
    for program, span in ((step_module, STEP_SPAN), (RNG_PROGRAM, RNG_SPAN)):
        runs = [s for name, s, _ in modules
                if name.partition("(")[0] == program]
        sent = [s for name, s, _, t, _ in host if name == span and t == thread]
        for run, span_start in zip(reversed(runs), reversed(sent)):
            early = min(early, run - span_start)
    return early


def read_record(path: str, span_names, step_module: str = "",
                scopes: Optional[dict] = None,
                inferred: Optional[dict] = None) -> dict:
    """One ``.xplane.pb`` -> the record (see the module's docstring)."""
    from jax.profiler import ProfileData

    scopes = scopes or {}
    names = set(span_names)
    devices, host = {}, []
    for plane in ProfileData.from_file(path).planes:
        suffix = plane.name[len(DEVICE_PREFIX):]
        if plane.name.startswith(DEVICE_PREFIX) and suffix.isdigit():
            found = _device_plane(plane, step_module, scopes)
            if found:
                devices[plane.name] = found
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns,
                     line.name, {k: v for k, v in e.stats
                                 if not k.startswith("_")})
                    for e in line.events if e.name in names)
            if not devices:
                found = _cpu_ops(plane, step_module, scopes)
                if found:
                    devices[plane.name] = found
    plane = next(iter(devices.values()), {"modules": []})
    offset = clock_offset(plane["modules"], host, step_module)
    host = sorted(((name, start + offset, end + offset, thread, args)
                   for name, start, end, thread, args in host),
                  key=lambda h: h[1])
    return {"devices": devices, "host": host, "step_module": step_module,
            "inferred": inferred or {}, "clock_offset_ns": offset}


# ---------------------------------------------------- record -> summary ---


def block_of(scope: str) -> str:
    """The row of the table an operation's scope falls in:
    ``fwd/pair_from_msa``, ``bwd/pair_axial`` (``layer_N`` merged, forward =
    under ``jvp(``, backward = under ``transpose(``), ``fwd/loss``,
    ``optimizer``, another program's ``jit(_threefry_split)``, or
    ``unscoped``."""
    if not scope:
        return "unscoped"
    parts = scope.split("/")
    way = ("bwd/" if any(p.startswith("transpose(") for p in parts)
           else "fwd/" if any(p.startswith("jvp(") for p in parts) else "")
    plain = []
    for p in parts:
        while _WRAPPED.match(p):
            p = _WRAPPED.match(p).group(1)
        plain.append(p)
    # the model: what jvp( wraps that is no phase (Alphafold2, MlaMoeLM)
    model = next((q for p, q in zip(parts, plain)
                  if p != q and q not in PHASES), None)
    if model is not None:
        inner = plain[plain.index(model) + 1:]
        for i, p in enumerate(inner):
            if _LAYER.match(p):
                inner = inner[i + 1:]
                break
        return way + (inner[0] if inner else model)
    phases = [p for p in plain if p in PHASES]
    if phases:  # the innermost: grad_clip sits inside optimizer
        return way + phases[-1]
    if plain[0].startswith("jit(") and not plain[0].startswith("jit(step"):
        return plain[0]
    return "unscoped"  # bare jit(step)/..., or a copy named by its argument


def own_times(ops) -> list:
    """[(index, own_ns)]: each operation's duration less what the
    operations nested inside it cover (a ``while`` holds its body's)."""
    out, stack = [], []  # stack of [index, end, own]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            i, _, own = stack.pop()
            out.append((i, own))

    order = sorted(range(len(ops)), key=lambda i: (ops[i][2], -ops[i][3]))
    for i in order:
        start, end = ops[i][2], ops[i][3]
        close(start)
        if stack:
            stack[-1][2] -= min(end, stack[-1][1]) - start
        stack.append([i, end, end - start])
    close(float("inf"))
    return out


def idle_gaps(ops) -> list:
    """[(start_ns, end_ns)] between the merged intervals of ``ops``."""
    gaps, reach = [], None
    for _, _, start, end in sorted(ops, key=lambda o: o[2]):
        if reach is not None and start > reach:
            gaps.append((reach, start))
        reach = end if reach is None else max(reach, end)
    return gaps


def loop_thread(host) -> Optional[str]:
    """The thread most of the host's span events are on."""
    counts: dict = {}
    for _, _, _, thread, _ in host:
        counts[thread] = counts.get(thread, 0) + 1
    return max(counts, key=counts.get) if counts else None


def idle_by_span(gaps, host, steps_name: str = "train") -> dict:
    """{span name: ns}: every gap cut at the borders of the loop thread's
    spans, each piece given to the innermost span that covers it (the one
    that started last), ``outside_any_span`` where none does. The step
    annotation (``steps_name``) is no span."""
    thread = loop_thread(host)
    spans = [(s, e, n) for n, s, e, t, _ in host
             if t == thread and n != steps_name]
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


def summarize(record: dict) -> dict:
    """The log line of one record, from its first device plane."""
    out = {"stop_trace_s": round(record.get("stop_trace_s", 0.0), 3),
           "read_s": round(record.get("read_s", 0.0), 3),
           "names_s": round(record.get("names_s", 0.0), 3),
           "clock_offset_ns": record.get("clock_offset_ns", 0)}
    if not record["devices"]:
        return out
    plane = next(iter(record["devices"].values()))
    ops = plane["ops"]
    step_runs = [m for m in plane["modules"]
                 if m[0].partition("(")[0] == record["step_module"]]
    steps = max(len(step_runs), 1)
    blocks: dict = {}
    collectives: dict = {}
    ring: dict = {}
    inferred, total, inferred_ns = record.get("inferred", {}), 0.0, 0.0
    for i, own in own_times(ops):
        block = block_of(ops[i][1])
        blocks[block] = blocks.get(block, 0.0) + own
        total += own
        # another program's operation carries its program's name, no path
        if ops[i][0] in inferred and "/" in ops[i][1]:
            inferred_ns += own
        if ops[i][0].startswith(COLLECTIVES):
            collectives[block] = collectives.get(block, 0.0) + own
        for part in set(RING_PARTS).intersection(ops[i][1].split("/")):
            ring[part] = ring.get(part, 0.0) + own
    gaps = idle_gaps(ops)
    window = max(o[3] for o in ops) - min(o[2] for o in ops)
    idle = sum(b - a for a, b in gaps)
    out["steps"] = len(step_runs)
    if step_runs:
        out["step_device_ms"] = round(
            sum(e - s for _, s, e in step_runs) / steps / 1e6, 3)
    out["idle_pct"] = round(100.0 * idle / window, 3) if window else 0.0
    out["inferred_pct"] = round(
        100.0 * inferred_ns / total, 3) if total else 0.0
    for name, table in (("block_ms", blocks),
                        ("collective_own_ms", collectives),
                        ("ring_ms", ring),
                        ("idle_ms", idle_by_span(gaps, record["host"]))):
        for key, ns in sorted(table.items(), key=lambda kv: -kv[1]):
            if ns / steps >= 500:  # what rounds to 0.000 ms is left out
                out[f"{name}/{key}"] = round(ns / steps / 1e6, 3)
    return out
