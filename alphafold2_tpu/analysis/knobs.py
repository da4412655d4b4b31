"""Knob-registry auditor: every ``AF2TPU_*`` env read, cross-checked.

The repo has grown ~130 ``AF2TPU_*`` environment knobs (serve sizing,
bench drivers, session orchestration, kernel/precision switches) plus
the ``ServeConfig``/``TrainConfig``/... dataclass fields they mostly
mirror. A knob nobody documents is a knob nobody can operate, and a
documented knob nobody reads is a lie in the README — both have bitten
real deployments. This auditor enumerates, cross-checks, and gates:

- **AF2K001** (error) — a knob read in code that the README never
  mentions. Undocumented knobs can't be operated.
- **AF2K002** (error) — a knob documented in the README that no code
  (including tests) ever reads. Dead documentation misleads operators.
- **AF2K003** (warning) — a ``*Config`` dataclass field whose name is
  never referenced outside ``config.py``: a dead knob in the config
  surface.
- **AF2K004** (warning) — a ``*Config`` field with no ``#`` comment
  (trailing on its line, or a block comment directly above — the
  config.py idiom) and no README mention: undocumented config.

Enumeration is exact-match AST: any string constant fully matching
``AF2TPU_[A-Z0-9_]+`` in ``alphafold2_tpu/``, ``scripts/``, ``bench.py``
(README prose never matches because docstrings embed knob names inside
longer sentences, and comments are invisible to the AST). A literal with
a trailing underscore (``"AF2TPU_SERVE_"``) is a *prefix wildcard*: it
legitimizes every README name sharing the prefix, and any README name
matched by some code prefix is not dead. Reads in ``tests/`` count for
liveness (AF2K002) but are not themselves required to be documented.

``--markdown`` emits the README "Knob registry" tables so the committed
docs are generated, not hand-tracked. Pure stdlib; folds into
``jaxpr_audit --rules ...,concurrency`` beside the concurrency rules.
"""

from __future__ import annotations

import argparse
import ast
import json
import os
import re
import sys
from typing import Dict, Iterable, List, Optional, Tuple

from alphafold2_tpu.analysis.lint import Finding, iter_python_files

RULES = {
    "AF2K001": "env knob read in code but undocumented in README",
    "AF2K002": "env knob documented in README but never read anywhere",
    "AF2K003": "config dataclass field never referenced outside config.py",
    "AF2K004": "config field with no comment (trailing or block-above) "
               "and no README mention",
}

_SEVERITY = {
    "AF2K001": "error",
    "AF2K002": "error",
    "AF2K003": "warning",
    "AF2K004": "warning",
}

_KNOB_RE = re.compile(r"AF2TPU_[A-Z0-9_]+_?")
_REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def default_code_paths() -> list:
    return [
        os.path.join(_REPO, "alphafold2_tpu"),
        os.path.join(_REPO, "scripts"),
        os.path.join(_REPO, "bench.py"),
    ]


def default_liveness_paths() -> list:
    # tests read knobs too (AF2TPU_HEAVY gates the 768-crop grid test);
    # that keeps a README knob alive but carries no documentation duty
    return default_code_paths() + [os.path.join(_REPO, "tests")]


def collect_env_reads(paths: Iterable[str]) -> Dict[str, List[str]]:
    """knob name -> sorted read sites ("relpath:line"). Names ending in
    ``_`` are prefix wildcards used to build families dynamically."""
    out: Dict[str, List[str]] = {}
    for path in iter_python_files(paths):
        if os.path.abspath(path) == os.path.abspath(__file__):
            continue  # _GROUPS labels are classifications, not reads
        try:
            tree = ast.parse(open(path, encoding="utf-8").read())
        except (OSError, SyntaxError):
            continue
        rel = os.path.relpath(path, _REPO)
        for node in ast.walk(tree):
            if (
                isinstance(node, ast.Constant)
                and isinstance(node.value, str)
                and _KNOB_RE.fullmatch(node.value)
            ):
                out.setdefault(node.value, []).append(
                    f"{rel}:{node.lineno}"
                )
    return {k: sorted(set(v)) for k, v in out.items()}


def collect_documented(readme_path: Optional[str] = None) -> set:
    path = readme_path or os.path.join(_REPO, "README.md")
    try:
        text = open(path, encoding="utf-8").read()
    except OSError:
        return set()
    return set(re.findall(r"AF2TPU_[A-Z0-9_]+", text))


def collect_config_fields(
    config_path: Optional[str] = None,
) -> List[Tuple[str, str, int, bool]]:
    """-> [(ClassName, field, line, has_trailing_comment)] for every
    ``*Config`` dataclass field in config.py."""
    path = config_path or os.path.join(_REPO, "alphafold2_tpu", "config.py")
    source = open(path, encoding="utf-8").read()
    lines = source.splitlines()
    tree = ast.parse(source)
    out = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.ClassDef)
                and node.name.endswith("Config")):
            continue
        for item in node.body:
            if isinstance(item, ast.AnnAssign) and isinstance(
                item.target, ast.Name
            ):
                end = item.end_lineno or item.lineno
                commented = "#" in lines[end - 1] or (
                    item.lineno >= 2
                    and lines[item.lineno - 2].lstrip().startswith("#")
                )
                out.append(
                    (node.name, item.target.id, item.lineno, commented)
                )
    return out


def collect_referenced_names(
    paths: Iterable[str], exclude: str
) -> set:
    """Every attribute-access and keyword-argument name outside
    ``exclude`` — the (loose) liveness universe for config fields,
    collected in ONE pass so the per-field check is set membership."""
    names: set = set()
    for path in iter_python_files(paths):
        if os.path.abspath(path) == os.path.abspath(exclude):
            continue
        try:
            tree = ast.parse(open(path, encoding="utf-8").read())
        except (OSError, SyntaxError):
            continue
        for node in ast.walk(tree):
            if isinstance(node, ast.Attribute):
                names.add(node.attr)
            elif isinstance(node, ast.keyword) and node.arg:
                names.add(node.arg)
    return names


def audit(
    code_paths: Optional[Iterable[str]] = None,
    liveness_paths: Optional[Iterable[str]] = None,
    readme_path: Optional[str] = None,
    config_path: Optional[str] = None,
) -> List[Finding]:
    code_paths = list(code_paths or default_code_paths())
    liveness_paths = list(liveness_paths or default_liveness_paths())
    config_path = config_path or os.path.join(
        _REPO, "alphafold2_tpu", "config.py"
    )
    reads = collect_env_reads(code_paths)
    live_reads = collect_env_reads(liveness_paths)
    documented = collect_documented(readme_path)
    prefixes = {k for k in live_reads if k.endswith("_")}
    findings: List[Finding] = []

    # AF2K001 — read but undocumented (prefix literals document their
    # whole family: the README must mention the prefix itself)
    for name, sites in sorted(reads.items()):
        key = name  # prefix literals must appear verbatim in README too
        if key not in documented:
            path, _, line = sites[0].rpartition(":")
            findings.append(Finding(
                "AF2K001", _SEVERITY["AF2K001"],
                os.path.join(_REPO, path), int(line), 0,
                f"env knob {name} is read here but the README never "
                "mentions it — add it to the Knob registry "
                "(README.md, regenerate with `python -m "
                "alphafold2_tpu.analysis.knobs --markdown`)",
            ))

    # AF2K002 — documented but never read (a code prefix literal keeps
    # its README family alive)
    readme_file = readme_path or os.path.join(_REPO, "README.md")
    for name in sorted(documented):
        if name in live_reads or name + "_" in prefixes:
            continue
        if any(name.startswith(p) for p in prefixes):
            continue
        findings.append(Finding(
            "AF2K002", _SEVERITY["AF2K002"], readme_file, 0, 0,
            f"README documents env knob {name} but no code (incl. "
            "tests) ever reads it — dead documentation",
        ))

    # AF2K003/004 — config-field surface
    referenced = collect_referenced_names(liveness_paths, config_path)
    for cls, field, line, commented in collect_config_fields(config_path):
        if field not in referenced:
            findings.append(Finding(
                "AF2K003", _SEVERITY["AF2K003"], config_path, line, 0,
                f"{cls}.{field} is never referenced outside config.py — "
                "a dead knob in the config surface",
            ))
        if not commented and field not in documented:
            findings.append(Finding(
                "AF2K004", _SEVERITY["AF2K004"], config_path, line, 0,
                f"{cls}.{field} has no `#` comment (trailing or "
                "block-above) and no README mention — undocumented "
                "config",
            ))
    return findings


# ---------------------------------------------------------------- markdown


_GROUPS = [
    ("AF2TPU_SERVE_ASYNC_", "serve-async bench sizing"),
    ("AF2TPU_SERVE_FLEET_", "fleet serving driver"),
    ("AF2TPU_SERVE_REPLAY_", "workload capture/replay driver"),
    ("AF2TPU_SERVE_SCAN_", "variant-scan bench driver"),
    ("AF2TPU_SERVE_", "serve bench sizing"),
    ("AF2TPU_FLEET_", "fleet frontend"),
    ("AF2TPU_KERNEL_", "kernel microbench"),
    ("AF2TPU_BENCH_", "bench harness"),
    ("AF2TPU_", "core / misc"),
]


def markdown_registry(reads: Optional[Dict[str, List[str]]] = None) -> str:
    """The README "Knob registry" tables, grouped by family."""
    reads = reads if reads is not None else collect_env_reads(
        default_code_paths()
    )
    grouped: Dict[str, list] = {title: [] for _p, title in _GROUPS}
    for name in sorted(reads):
        for prefix, title in _GROUPS:
            if name.startswith(prefix):
                grouped[title].append(name)
                break
    lines: List[str] = []
    for _prefix, title in _GROUPS:
        names = grouped[title]
        if not names:
            continue
        lines.append(f"**{title}:**")
        lines.append("")
        lines.append("| knob | read at |")
        lines.append("|---|---|")
        for name in names:
            sites = reads[name]
            shown = ", ".join(f"`{s}`" for s in sites[:2])
            if len(sites) > 2:
                shown += f" (+{len(sites) - 2})"
            lines.append(f"| `{name}` | {shown} |")
        lines.append("")
    return "\n".join(lines)


# --------------------------------------------------------------------- CLI


def main(argv: Optional[list] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m alphafold2_tpu.analysis.knobs",
        description=__doc__.splitlines()[0],
    )
    parser.add_argument("--json", action="store_true")
    parser.add_argument("--markdown", action="store_true",
                        help="emit the README Knob registry tables")
    parser.add_argument("--select", help="comma-separated rule ids")
    parser.add_argument("--list-rules", action="store_true")
    args = parser.parse_args(argv)

    if args.list_rules:
        for rule in sorted(RULES):
            print(f"{rule} [{_SEVERITY[rule]}] {RULES[rule]}")
        return 0
    if args.markdown:
        print(markdown_registry())
        return 0

    findings = audit()
    if args.select:
        wanted = {s.strip().upper() for s in args.select.split(",")}
        findings = [f for f in findings if f.rule in wanted]
    if args.json:
        print(json.dumps(
            {
                "tool": "af2_knobs",
                "findings": [f.to_dict() for f in findings],
                "counts": {
                    sev: sum(1 for f in findings if f.severity == sev)
                    for sev in ("error", "warning")
                },
            },
            indent=2,
        ))
    else:
        for f in findings:
            print(f.format())
        if not findings:
            reads = collect_env_reads(default_code_paths())
            print(f"knob audit clean ({len(reads)} env knobs, all "
                  "documented and live)")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
