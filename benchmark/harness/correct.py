"""The comparison that decides ``correct`` for a training cell.

Both sides hand in the same readings of the first optimizer steps (see
``reference/model.py`` ``train_steps``): each step's loss, the per-leaf norm
of the first gradient as Adam gets it, and the per-leaf norm of the
parameters' change over those steps. The numbers compared:

- ``loss_step<k>``: |program - reference| / |reference|.
- ``grad_norm_worst_leaf``, ``change_norm_worst_leaf``: over the leaves, the
  largest gap between the program's norm and the reference's (not the norm of
  a difference), against the reference's norm of that leaf or of the median
  leaf, whichever is larger. A leaf that has not moved, or moved double, on
  one side reads about 1.

Leaves whose raw gradient in the reference is under a thousandth of the
median leaf's move under Adam by round-off alone (its first updates are
sign-like); they are left out of the change, by that rule and not by name.
"""

from __future__ import annotations

import math
import statistics

NEGLIGIBLE_GRADIENT = 1e-3  # of the median leaf's raw gradient norm


def worst_leaf(prog: dict, ref: dict, leaves=None) -> tuple:
    """(largest gap of norms, its leaf) over ``leaves`` (default: all)."""
    names = sorted(ref) if leaves is None else sorted(leaves)
    if sorted(prog) != sorted(ref):
        missing = sorted(set(ref) ^ set(prog))
        raise ValueError(f"the two sides' leaves differ: {missing[:6]}")
    median = statistics.median(float(ref[n]) for n in names)
    worst, where = 0.0, None
    for n in names:
        gap = abs(float(prog[n]) - float(ref[n])) / max(
            float(ref[n]), median, 1e-30)
        if gap != gap:  # a NaN gap is the worst there is
            return gap, n
        if gap > worst:
            worst, where = gap, n
    return worst, where


def training_numbers(prog: dict, ref: dict) -> dict:
    """name -> (value, detail) of every number a training cell compares."""
    if len(prog["losses"]) != len(ref["losses"]):
        raise ValueError("the two sides followed different numbers of steps")
    out = {}
    for k, (p, r) in enumerate(zip(prog["losses"], ref["losses"])):
        out[f"loss_step{k}"] = (abs(float(p) - float(r)) / abs(float(r)),
                                f"program {float(p)!r} reference {float(r)!r}")
    out["grad_norm_worst_leaf"] = worst_leaf(
        prog["grad_norms"], ref["grad_norms"])
    raw = ref["raw_grad_norms"]
    floor = NEGLIGIBLE_GRADIENT * statistics.median(
        float(v) for v in raw.values())
    moved = [n for n in raw if float(raw[n]) >= floor]
    out["change_norm_worst_leaf"] = worst_leaf(
        {n: prog["change_norms"][n] for n in moved},
        {n: ref["change_norms"][n] for n in moved}, moved)
    return out


def judge(numbers: dict, limits: dict) -> tuple:
    """({name: {"value", "limit", "ok", "at"}}, correct). Every limit must
    have its number and every number its limit."""
    if sorted(numbers) != sorted(limits):
        raise ValueError(
            f"numbers {sorted(numbers)} against limits {sorted(limits)}")
    compared = {}
    for name, (value, detail) in numbers.items():
        ok = math.isfinite(value) and value <= limits[name]
        compared[name] = {"value": value, "limit": limits[name], "ok": ok,
                          "at": detail}
    return compared, all(c["ok"] for c in compared.values())
