"""Jaxpr/HLO auditor (layer 2): semantic graph-hygiene enforcement.

The AST linter (:mod:`lint`) catches what is visible in source; this module
catches what is only visible in the traced graph. It abstractly traces the
registered executables (:mod:`targets`: model forward, train step, serve
forward) on the host — no device, no compile — and statically rejects:

- ``AF2A100`` error — the target fails to trace at all (the audit cannot
  certify a graph it cannot build).
- ``AF2A101`` error — float64/complex128 anywhere in the graph (any aval or
  a ``convert_element_type`` to a wide dtype): on TPU an f64 leak is a
  silent 2x memory + emulation cliff, paid at N^2 scale in the pair stream.
- ``AF2A102`` error — host-callback primitives in the hot path
  (``pure_callback``/``io_callback``/``debug_callback``/infeed/outfeed):
  each one is a device->host round trip per step.
- ``AF2A103`` error — giant baked-in constants (> threshold bytes closed
  over into the jaxpr): they bloat every executable and recompile key
  instead of riding as arguments.
- ``AF2A104`` warning — broken donation: a ``donate_argnums`` declaration
  whose buffers can never alias any output (no shape/dtype match), i.e.
  the donation documents an intent the runtime cannot honor.
- ``AF2A105`` error — the target only traces under default dtype
  promotion: under ``jax.numpy_dtype_promotion("strict")`` the trace
  raises, meaning an implicit promotion (usually bool/int drawn into
  float math) is hiding in the graph.

Rule ``AF2A106`` (Mosaic TPU lowering failure) folds the Pallas lowering
gate (:mod:`alphafold2_tpu.analysis.lowering`, formerly the whole of
``scripts/check_tpu_lowering.py``) into the same findings stream, and the
``hlo`` rule set folds in the compiled-HLO audit
(:mod:`alphafold2_tpu.analysis.hlo_audit`) — collective census drift vs
the committed ``hlo_contracts.json`` (``AF2A107``), sharded-but-replicated
/ collective blowups (``AF2A108``), collectives in single-device targets
(``AF2A109``) and per-device HBM budget breaches (``AF2A110``) — so
``--rules jaxpr,lowering,hlo`` is the single pre-hardware gate entry point
the first TPU session runs before anything burns bench time.

Traversal note: rule scans walk :func:`iter_eqns_deep`, which additionally
recurses into ``custom_vjp``/``custom_jvp`` forward AND backward bodies
(traced on the spot from the stored thunks) — a host callback or f64
widening hiding inside a custom-VJP closure (e.g. a Pallas kernel's
backward) cannot pass silently. :func:`iter_eqns` keeps the historical
shallow-ish traversal because the graph-contract fingerprints
(:mod:`contracts`) are built on it; changing it would re-key every
committed contract.

CLI::

    JAX_PLATFORMS=cpu python -m alphafold2_tpu.analysis.jaxpr_audit \
        [--targets model_fwd,train_step] [--rules jaxpr,lowering,hlo] \
        [--const-threshold BYTES] [--json out.json]

Exit codes: 0 clean, 1 findings, 2 usage error. Targets may waive specific
rules (with a recorded reason) via ``TraceTarget.allow``.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Iterable, Optional

AUDIT_RULES = {
    "AF2A100": ("error", "target fails to trace"),
    "AF2A101": ("error", "float64/complex128 in graph"),
    "AF2A102": ("error", "host callback primitive in hot path"),
    "AF2A103": ("error", "giant baked-in constant"),
    "AF2A104": ("warning", "declared donation can never alias"),
    "AF2A105": ("error", "strict dtype promotion violation"),
    "AF2A106": ("error", "Mosaic TPU lowering failure"),
    "AF2A107": ("error", "HLO collective-census/contract drift"),
    "AF2A108": ("error", "sharded target replicated / collective blowup"),
    "AF2A109": ("error", "collectives in a single-device target"),
    "AF2A110": ("error", "per-device footprint over HBM budget"),
}

FORBIDDEN_PRIMITIVES = {
    "pure_callback",
    "io_callback",
    "debug_callback",
    "callback",
    "host_callback",
    "outside_call",
    "infeed",
    "outfeed",
}

WIDE_DTYPES = ("float64", "complex128")

DEFAULT_CONST_THRESHOLD = 1 << 20  # 1 MiB baked into a graph is a bug


@dataclasses.dataclass(frozen=True)
class AuditFinding:
    rule: str
    severity: str
    target: str
    message: str

    def format(self) -> str:
        return f"{self.target}: {self.rule} [{self.severity}] {self.message}"

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def _finding(rule: str, target: str, message: str) -> AuditFinding:
    return AuditFinding(rule, AUDIT_RULES[rule][0], target, message)


# --------------------------------------------------------------- traversal


def _sub_jaxprs(params: dict):
    from jax.extend import core as jex_core

    def walk(value):
        if isinstance(value, jex_core.ClosedJaxpr):
            yield value.jaxpr
        elif isinstance(value, jex_core.Jaxpr):
            yield value
        elif isinstance(value, (list, tuple)):
            for v in value:
                yield from walk(v)

    for value in params.values():
        yield from walk(value)


def iter_eqns(jaxpr) -> Iterable:
    """Every equation in ``jaxpr``, recursing into call/control-flow
    sub-jaxprs (scan bodies, cond branches, pjit calls, remat).

    This is the traversal the graph-contract fingerprints (:mod:`contracts`)
    are keyed on — keep it stable; rule scans use :func:`iter_eqns_deep`."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub)


def _custom_vjp_bodies(eqn, failures: Optional[list] = None):
    """The fwd and bwd bodies of a ``custom_vjp_call`` equation.

    ``_sub_jaxprs`` only sees the primal ``fun_jaxpr`` — exactly the body a
    custom VJP *replaces* under differentiation. The real fwd is stored as
    ``fwd_jaxpr_thunk`` (a ``WrappedFun`` called with one tangent-nonzero
    flag per non-const input — all True is the generic jvp) and the bwd as
    the ``bwd`` ``WrappedFun``, which we trace at the fwd's (residual,
    cotangent) avals (the fwd jaxpr returns residuals first, primal outputs
    last).
    Anything untraceable is recorded in ``failures`` instead of silently
    skipped — an unauditable closure must surface as a finding, not read
    as clean."""
    params = eqn.params
    thunk = params.get("fwd_jaxpr_thunk")
    if thunk is None:
        return
    n_primal = len(eqn.outvars)
    n_flags = len(eqn.invars) - params.get("num_consts", 0)
    try:
        fwd_jaxpr = thunk.call_wrapped(*([True] * n_flags))[0]
    except Exception as e:
        if failures is not None:
            failures.append(
                f"custom_vjp fwd body untraceable: {type(e).__name__}: "
                f"{str(e)[:200]}"
            )
        return
    yield fwd_jaxpr
    bwd = params.get("bwd")
    if bwd is None:
        return
    try:
        import jax

        # the fwd jaxpr returns only the residuals that are NOT plain
        # forwards of an input (``input_fwds[i]`` is None for those, else
        # the index of the forwarded input); bwd takes every residual
        outs = [v.aval for v in fwd_jaxpr.outvars]
        computed = iter(outs[: len(outs) - n_primal])
        ct_avals = outs[len(outs) - n_primal:]
        _, _, input_fwds = params["out_trees"]()
        res_avals = [
            next(computed) if f is None else eqn.invars[f].aval
            for f in input_fwds
        ]
        closed = jax.make_jaxpr(lambda *a: bwd.call_wrapped(*a))(*[
            jax.ShapeDtypeStruct(a.shape, a.dtype)
            for a in list(res_avals) + list(ct_avals)
        ])
        yield closed.jaxpr
    except Exception as e:
        if failures is not None:
            failures.append(
                f"custom_vjp bwd body untraceable: {type(e).__name__}: "
                f"{str(e)[:200]}"
            )


def _custom_jvp_bodies(eqn, failures: Optional[list] = None):
    """The jvp body of a ``custom_jvp_call`` equation: the memoized
    ``jvp_jaxpr_fun`` (a ``WrappedFun``) takes one *symbolic-zero* flag per
    non-const input (NOTE: inverted vs the vjp thunk's nonzero flags — all False is the
    generic every-tangent-live case) and returns ``(jaxpr, consts, ...)``.
    Failures are recorded so an unauditable closure surfaces instead of
    passing silently."""
    params = eqn.params
    thunk = params.get("jvp_jaxpr_fun")
    if thunk is None:
        return
    n_flags = len(eqn.invars) - params.get("num_consts", 0)
    try:
        jvp_jaxpr = thunk.call_wrapped(*([False] * n_flags))[0]
    except Exception as e:
        if failures is not None:
            failures.append(
                f"custom_jvp body untraceable: {type(e).__name__}: "
                f"{str(e)[:200]}"
            )
        return
    yield jvp_jaxpr


def _eqn_signature(eqn) -> tuple:
    """Structural identity of a custom_vjp/jvp call site: the standard
    pattern (``f_fwd`` calling ``f(x)``) re-embeds the SAME custom call in
    its own fwd body, so expansion must dedupe by signature or it recurses
    forever — each thunk call builds a fresh jaxpr, so object identity
    cannot terminate it."""
    return (
        eqn.primitive.name,
        tuple(str(getattr(v, "aval", v)) for v in eqn.invars),
        tuple(str(getattr(v, "aval", v)) for v in eqn.outvars),
    )


def _deep_sub_jaxprs(eqn, failures: Optional[list] = None,
                     seen: Optional[set] = None):
    """Everything :func:`_sub_jaxprs` yields, plus dict-valued params and
    the custom_vjp/custom_jvp fwd/bwd/jvp bodies (expanded once per call
    signature)."""
    from jax.extend import core as jex_core

    yield from _sub_jaxprs(eqn.params)
    for value in eqn.params.values():
        if isinstance(value, dict):
            for v in value.values():
                if isinstance(v, jex_core.ClosedJaxpr):
                    yield v.jaxpr
                elif isinstance(v, jex_core.Jaxpr):
                    yield v
    name = eqn.primitive.name
    if not (name.startswith("custom_vjp_call")
            or name.startswith("custom_jvp_call")):
        return
    sig = _eqn_signature(eqn)
    if seen is not None:
        if sig in seen:
            return
        seen.add(sig)
    if name.startswith("custom_vjp_call"):
        yield from _custom_vjp_bodies(eqn, failures)
    else:
        yield from _custom_jvp_bodies(eqn, failures)


def iter_eqns_deep(jaxpr, failures: Optional[list] = None) -> Iterable:
    """:func:`iter_eqns` plus recursion into custom_vjp/custom_jvp bodies;
    untraceable bodies append a reason to ``failures`` (when given) so the
    caller can refuse to certify what it could not walk."""
    seen: set = set()

    def rec(jx):
        for eqn in jx.eqns:
            yield eqn
            for sub in _deep_sub_jaxprs(eqn, failures, seen):
                yield from rec(sub)

    yield from rec(jaxpr)


def _aval_dtypes(eqn):
    for var in list(eqn.invars) + list(eqn.outvars):
        aval = getattr(var, "aval", None)
        dtype = getattr(aval, "dtype", None)
        if dtype is not None:
            yield str(dtype)


# ------------------------------------------------------------- jaxpr rules


def audit_closed_jaxpr(
    closed,
    target: str = "<jaxpr>",
    const_threshold: int = DEFAULT_CONST_THRESHOLD,
) -> list:
    """Pure jaxpr rules (AF2A101/102/103) over an already-traced graph.

    Walks :func:`iter_eqns_deep`, so hits inside custom_vjp/custom_jvp
    closures count (possibly twice — a primal body shared by the fwd is
    walked in both; the count is a locator, not an exact census). A body
    the walker could not trace becomes an AF2A100 finding."""
    import numpy as np

    findings: list = []
    wide_hits: dict = {}
    callback_hits: dict = {}
    trace_failures: list = []
    for eqn in iter_eqns_deep(closed.jaxpr, trace_failures):
        name = eqn.primitive.name
        if name in FORBIDDEN_PRIMITIVES:
            callback_hits[name] = callback_hits.get(name, 0) + 1
        if name == "convert_element_type":
            new = str(eqn.params.get("new_dtype", ""))
            if new in WIDE_DTYPES:
                wide_hits[f"convert_element_type->{new}"] = (
                    wide_hits.get(f"convert_element_type->{new}", 0) + 1
                )
        for dtype in _aval_dtypes(eqn):
            if dtype in WIDE_DTYPES:
                wide_hits[dtype] = wide_hits.get(dtype, 0) + 1
    for why in sorted(set(trace_failures)):
        findings.append(_finding(
            "AF2A100", target,
            f"cannot audit a closed-over body: {why}",
        ))
    for what, count in sorted(wide_hits.items()):
        findings.append(_finding(
            "AF2A101", target,
            f"{what} appears {count}x in the graph; the TPU path is "
            "f32/bf16-only — find the implicit widening",
        ))
    for prim, count in sorted(callback_hits.items()):
        findings.append(_finding(
            "AF2A102", target,
            f"host callback primitive {prim!r} appears {count}x: each is a "
            "device->host round trip per executed step",
        ))
    for i, const in enumerate(closed.consts):
        try:
            nbytes = int(const.nbytes)
        except Exception:  # extended dtypes (PRNG keys) have no nbytes
            shape = tuple(getattr(const, "shape", ()))
            itemsize = getattr(
                getattr(const, "dtype", None), "itemsize", None
            )
            nbytes = int(np.prod(shape)) * int(itemsize or 4)
        if nbytes > const_threshold:
            shape = tuple(getattr(const, "shape", ()))
            findings.append(_finding(
                "AF2A103", target,
                f"baked-in constant #{i} is {nbytes} bytes (shape {shape}) "
                f"> threshold {const_threshold}; pass it as an argument so "
                "it is not serialized into every executable",
            ))
    return findings


def audit_donation(fn, args, donate_argnums, target: str) -> list:
    """AF2A104: donated input leaves with no shape/dtype-matching output."""
    import collections

    import jax

    out_shape = jax.eval_shape(fn, *args)
    out_sig = collections.Counter(
        (tuple(leaf.shape), str(leaf.dtype))
        for leaf in jax.tree.leaves(out_shape)
        if hasattr(leaf, "shape")
    )
    findings = []
    for argnum in donate_argnums:
        donated = jax.tree.leaves(args[argnum])
        dead = []
        for leaf in donated:
            if not hasattr(leaf, "shape"):
                continue
            sig = (tuple(leaf.shape), str(leaf.dtype))
            if out_sig.get(sig, 0) > 0:
                out_sig[sig] -= 1
            else:
                dead.append(f"{leaf.dtype}{list(leaf.shape)}")
        if dead and len(dead) == len(donated):
            findings.append(_finding(
                "AF2A104", target,
                f"donated argument {argnum} ({len(dead)} buffer(s): "
                f"{', '.join(sorted(set(dead))[:4])}...) matches no output "
                "shape/dtype — XLA cannot alias any of it; drop or justify "
                "the donation",
            ))
    return findings


# ----------------------------------------------------------------- targets


def _is_promotion_error(e: BaseException) -> bool:
    text = f"{type(e).__name__}: {e}"
    return "promot" in text.lower()


def audit_target(
    target, const_threshold: int = DEFAULT_CONST_THRESHOLD
) -> list:
    """Trace one :class:`~alphafold2_tpu.analysis.targets.TraceTarget` and
    run every rule, honoring its ``allow`` waivers."""
    import jax

    name = target.name
    try:
        fn, args = target.build()
    except Exception as e:  # build failures are un-audit-able targets
        return [_finding(
            "AF2A100", name,
            f"target build failed: {type(e).__name__}: {str(e)[:300]}",
        )]

    findings: list = []
    # strict promotion first: the same trace, one config flag stricter
    with jax.numpy_dtype_promotion("strict"):
        try:
            closed = jax.make_jaxpr(fn)(*args)
            strict_ok = True
        except Exception as e:
            strict_ok = False
            if _is_promotion_error(e):
                findings.append(_finding(
                    "AF2A105", name,
                    "trace raises under strict dtype promotion: "
                    f"{str(e).splitlines()[0][:300]}",
                ))
            else:
                findings.append(_finding(
                    "AF2A100", name,
                    f"trace failed (strict promotion): {type(e).__name__}: "
                    f"{str(e)[:300]}",
                ))
    if not strict_ok:
        try:
            closed = jax.make_jaxpr(fn)(*args)
        except Exception as e:
            return [f for f in findings if f.rule != "AF2A105"] + [_finding(
                "AF2A100", name,
                f"trace failed: {type(e).__name__}: {str(e)[:300]}",
            )]

    findings.extend(audit_closed_jaxpr(closed, name, const_threshold))
    if target.donate_argnums:
        findings.extend(
            audit_donation(fn, args, target.donate_argnums, name)
        )
    return [f for f in findings if f.rule not in target.allow]


def audit(
    targets=None, const_threshold: int = DEFAULT_CONST_THRESHOLD
) -> list:
    from alphafold2_tpu.analysis.targets import default_targets

    targets = targets if targets is not None else default_targets()
    findings: list = []
    for t in targets:
        findings.extend(audit_target(t, const_threshold))
    return findings


# ------------------------------------------------------- lowering rule set


def lowering_findings(case_names=None) -> list:
    """Run the Mosaic TPU lowering gate (analysis.lowering) in a subprocess
    pinned to the CPU backend (it only lowers, so it never needs — and must
    never take — an attached chip) and convert failed cases into AF2A106
    findings.

    This is the fold-in of ``scripts/check_tpu_lowering.py``: same cases,
    same negative control, one findings stream."""
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "-m", "alphafold2_tpu.analysis.lowering"]
    cmd += list(case_names or ())
    proc = subprocess.run(
        cmd, capture_output=True, text=True, env=env, timeout=1800
    )
    findings = []
    summary = None
    for line in proc.stdout.splitlines():
        if not line.startswith("{"):
            continue
        rec = json.loads(line)
        if rec.get("gate"):
            summary = rec
        elif "case" in rec and not rec.get("ok"):
            findings.append(_finding(
                "AF2A106", rec["case"],
                f"Mosaic lowering failed: {rec.get('error', '?')[:300]}",
            ))
    if summary is None:
        findings.append(_finding(
            "AF2A106", "lowering_gate",
            "gate produced no summary record "
            f"(rc={proc.returncode}); stderr tail: {proc.stderr[-300:]}",
        ))
    elif summary.get("error"):
        # e.g. a typo'd case name: the gate refuses to certify anything —
        # that refusal must surface as a finding, not read as green
        findings.append(_finding(
            "AF2A106", "lowering_gate", f"gate error: {summary['error']}"
        ))
    return findings


# ------------------------------------------------------------ hlo rule set


def hlo_findings(target_names=None) -> list:
    """Run the compiled-HLO audit (analysis.hlo_audit --check) in a
    subprocess pinned to the CPU backend with 8 virtual devices — the same
    device count the committed ``hlo_contracts.json`` is keyed by — and
    fold its findings (AF2A107–110) into this stream.

    A subprocess because the parent may already hold a differently-sized
    backend, and device count is part of the contract key. A gate that
    produces no summary is itself an AF2A107 finding — a refusal to
    certify must never read as green."""
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    cmd = [sys.executable, "-m", "alphafold2_tpu.analysis.hlo_audit",
           "--check"]
    if target_names:
        cmd += ["--targets", ",".join(target_names)]
    proc = subprocess.run(
        cmd, capture_output=True, text=True, env=env, timeout=1800
    )
    summary = None
    for line in proc.stdout.splitlines():
        if not line.startswith("{"):
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if rec.get("gate") == "hlo":
            summary = rec
    if summary is None:
        return [_finding(
            "AF2A107", "hlo_gate",
            f"hlo gate produced no summary record (rc={proc.returncode}); "
            f"stderr tail: {proc.stderr[-300:]}",
        )]
    if summary.get("verdict") == "stale-baseline":
        print(
            "jaxpr_audit: hlo gate reports a STALE baseline "
            "(recompile key changed) — re-baseline hlo_contracts.json"
        )
    return [
        AuditFinding(
            rec["rule"], rec["severity"], rec["target"], rec["message"]
        )
        for rec in summary.get("findings", [])
    ]


# ---------------------------------------------------- concurrency rule set


def concurrency_findings() -> list:
    """Run static layer 5 in-process — the concurrency auditor (AF2C:
    lock-order graph, guard contracts, thread/queue lifecycles), its
    committed-contract check, and the knob registry (AF2K) — and fold
    the findings into this stream.

    In-process because it is pure stdlib AST: no jax, no backend, no
    subprocess. The contract check honors the same stale-baseline escape
    as the graph/hlo gates; gated-defect functions (the
    ``AF2TPU_AUDIT_INVERT_LOCKS`` negative control) surface here as
    findings when their env var is set but never enter the contracts. A
    crashed scan must never read as green — it becomes AF2C000."""
    from alphafold2_tpu.analysis import concurrency, knobs

    findings: list = []
    try:
        model = concurrency.build_model()
        for f in model.findings():
            findings.append(AuditFinding(
                f.rule, f.severity, "concurrency",
                f"{f.path}:{f.line}: {f.message}",
            ))
        verdict, lines = concurrency.check_against(
            concurrency.DEFAULT_BASELINE, concurrency.compute_contracts(model)
        )
        if verdict == "stale-baseline":
            print(
                "jaxpr_audit: concurrency gate reports a STALE baseline "
                "(format changed) — re-baseline concurrency_contracts.json"
            )
        elif verdict != "pass":
            for line in lines:
                findings.append(AuditFinding(
                    "AF2C009", "error", "concurrency_contracts", line,
                ))
    except Exception as e:  # noqa: BLE001 — a broken gate must be loud
        findings.append(AuditFinding(
            "AF2C000", "error", "concurrency",
            f"concurrency audit crashed: {type(e).__name__}: {e}",
        ))
    try:
        for f in knobs.audit():
            findings.append(AuditFinding(
                f.rule, f.severity, "knobs",
                f"{f.path}:{f.line}: {f.message}",
            ))
    except Exception as e:  # noqa: BLE001
        findings.append(AuditFinding(
            "AF2C000", "error", "knobs",
            f"knob audit crashed: {type(e).__name__}: {e}",
        ))
    return findings


# --------------------------------------------------------------------- CLI


def findings_to_json(findings: list) -> str:
    return json.dumps(
        {
            "tool": "jaxpr_audit",
            "findings": [f.to_dict() for f in findings],
            "counts": {
                "error": sum(1 for f in findings if f.severity == "error"),
                "warning": sum(
                    1 for f in findings if f.severity == "warning"
                ),
            },
        },
        indent=2,
    )


def main(argv=None) -> int:
    import argparse

    from alphafold2_tpu.analysis.targets import default_targets, target_by_name

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--targets", default=None,
        help="comma-separated target names (default: all registered)",
    )
    parser.add_argument(
        "--rules", default="jaxpr",
        help=(
            "comma-separated rule sets: jaxpr, lowering, hlo, "
            "concurrency (default: jaxpr)"
        ),
    )
    parser.add_argument(
        "--const-threshold", type=int, default=DEFAULT_CONST_THRESHOLD
    )
    parser.add_argument("--json", dest="json_path", default=None)
    args = parser.parse_args(argv)

    rule_sets = {s.strip() for s in args.rules.split(",") if s.strip()}
    unknown = rule_sets - {"jaxpr", "lowering", "hlo", "concurrency"}
    if unknown:
        print(f"unknown rule set(s): {sorted(unknown)}")
        return 2

    findings: list = []
    if "jaxpr" in rule_sets:
        if args.targets:
            try:
                targets = [
                    target_by_name(n.strip())
                    for n in args.targets.split(",") if n.strip()
                ]
            except KeyError as e:
                print(str(e))
                return 2
        else:
            targets = default_targets()
        findings.extend(audit(targets, args.const_threshold))
    if "lowering" in rule_sets:
        findings.extend(lowering_findings())
    if "hlo" in rule_sets:
        findings.extend(hlo_findings())
    if "concurrency" in rule_sets:
        findings.extend(concurrency_findings())

    for f in findings:
        print(f.format())
    if args.json_path:
        with open(args.json_path, "w", encoding="utf-8") as fh:
            fh.write(findings_to_json(findings))
    print(
        f"jaxpr_audit: {len(findings)} finding(s) over rule sets "
        f"{sorted(rule_sets)}"
    )
    return 1 if findings else 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
