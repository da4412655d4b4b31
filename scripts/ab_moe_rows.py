#!/usr/bin/env python3
"""A/B of the expert layer's row movements at the three language-model
cells' shapes (PR 35): how to sum a rung's rows by token.

    chiprun -- python scripts/ab_moe_rows.py            # times, on a TPU
    JAX_PLATFORMS=cpu python scripts/ab_moe_rows.py --tiny   # agreement only

``ops/moe.py`` builds the held experts' path for a rung of R sorted rows
(``row_ladder``) where it was built for tokens x top_k. Taking a row's token
is R gathers. The other direction, (R, D) expert-sorted rows -> (tokens, D),
has three candidates, each timed alone (forward; its backward is the take)
and inside the whole path (``held_experts_sum`` forward + backward at a
balanced routing, which sits on the lowest rung), beside the path at all
tokens x top_k rows (the parent's program):

(i)   ``gather``: the parent's form, a gather by ``inverse`` over all
      tokens x top_k positions from an (R + 1)-row table whose last row is
      zero, then the sum over top_k;
(ii)  ``scatter_add``: float32 ``.at[token].add`` over the R rows, and
      ``segment_sum`` over the rows first put in token order;
(iii) ``runs``: the rows put in token order, a run's rows summed onto its
      first by top_k - 1 shifted adds, one gather a token.

One JSON line a measurement on stdout and in ``chiprun_out/ab_moe_rows.jsonl``.
Not part of the library: the winner, (i), is ``ops/moe.py``
``_sum_by_token``, written there by hand, with PERF.md's record; nothing
selects a form at run time.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from alphafold2_tpu.models.ssm_moe_lm import relu2
from alphafold2_tpu.ops import moe


# tokens, top_k, hidden, expert width, held, router's experts, gated, activation
CELLS = {
    "train_nemotron3_nano_ep16_seq8k": (
        8192, 6, 2688, 1856, 8, 128, False, relu2),
    "train_smallthinker_ep8_seq16k": (
        16384, 6, 2560, 768, 8, 64, True, jax.nn.relu),
    "train_kanana2_ep8_seq8k": (16384, 6, 2048, 768, 16, 128, True,
                                jax.nn.silu),
}
TINY = {"tiny": (512, 4, 16, 8, 2, 16, True, jax.nn.silu)}


def best_ms(fn, args, reps):
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t)
    return 1e3 * min(times)


# ------------------------------------------------------- the candidates ---


def scatter_add(rows, order, inverse, top_k):
    out = jnp.zeros((inverse.shape[0] // top_k, rows.shape[-1]), jnp.float32)
    return out.at[order // top_k].add(
        rows.astype(jnp.float32)).astype(rows.dtype)


def segment_sum_sorted(rows, order, inverse, top_k):
    by_token = jnp.argsort(order)
    return jax.ops.segment_sum(
        rows[by_token].astype(jnp.float32), order[by_token] // top_k,
        inverse.shape[0] // top_k, indices_are_sorted=True).astype(rows.dtype)


def runs(rows, order, inverse, top_k):
    n = rows.shape[0]
    by_token = jnp.argsort(order).astype(jnp.int32)
    token = order[by_token] // top_k  # ascending: a token's rows are a run
    x = rows[by_token].astype(jnp.float32)
    total = x
    for shift in range(1, min(top_k, n)):
        same = (token[shift:] == token[:-shift])[:, None]
        total = total + jnp.pad(
            jnp.where(same, x[shift:], 0), ((0, shift), (0, 0)))
    # a token's rows in the rung, and those of the tokens before it
    count = jnp.sum(inverse.reshape(-1, top_k) < n, axis=1, dtype=jnp.int32)
    first = jnp.minimum(jnp.cumsum(count) - count, n - 1)
    return jnp.where(
        (count > 0)[:, None], total.astype(rows.dtype)[first], 0)


# (i) is the library's own since the first reading
CANDIDATES = {"i_gather": moe._sum_by_token, "ii_scatter_add": scatter_add,
              "ii_segment_sum": segment_sum_sorted, "iii_runs": runs}


def as_sum_by_token(candidate):
    """``candidate`` with the library's backward pass (the take), so that
    it can stand in for ``moe._sum_by_token`` inside the whole path."""
    @functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
    def summed(rows, order, inverse, top_k):
        return candidate(rows, order, inverse, top_k)

    summed.defvjp(
        lambda rows, order, inverse, top_k: (
            summed(rows, order, inverse, top_k), (order, inverse)),
        lambda top_k, res, g: (
            moe._take_by_token(g, *res, top_k), None, None))
    return summed


# --------------------------------------------------------- measurements ---


def balanced_routing(key, tokens, top_k, n_experts):
    """top_k distinct experts a token, uniform over the router's."""
    _, experts = jax.lax.top_k(
        jax.random.uniform(key, (tokens, n_experts)), top_k)
    return experts.astype(jnp.int32)


def whole_path(static, ladder, sum_by_token):
    """value + gradient of the laddered sum (``ladder`` of one rung: that
    rung alone, no switch), ``sum_by_token`` standing in for the library's
    while it is traced."""
    top_k, dtype, activation, first, n_experts = static

    def loss(tokens, weights, mats, experts, cotangent):
        held = mats[1].shape[0]
        plan = moe.dispatch(experts, first, held, n_experts)
        cast = tuple(None if w is None else w.astype(dtype) for w in mats)
        run = (functools.partial(moe._rung_sum, (ladder[0], top_k, dtype,
                                                 activation))
               if len(ladder) == 1 else functools.partial(
                   moe._ladder_sum, ladder, (top_k, dtype, activation)))
        out = run(tokens, weights, cast, plan)
        return jnp.sum(out.astype(jnp.float32) * cotangent)

    def traced(*args):
        real, moe._sum_by_token = moe._sum_by_token, sum_by_token
        # the library keeps one set of branch functions per ladder so that a
        # switch is traced once: a stand-in needs branches of its own
        moe._branches.cache_clear()
        try:
            return jax.value_and_grad(loss, argnums=(0, 1, 2))(*args)
        finally:
            moe._sum_by_token = real
            moe._branches.cache_clear()

    return jax.jit(traced)


def measure(name, cell, reps, dtype):
    tokens, top_k, d, f, held, n_experts, gated, activation = cell
    keys = jax.random.split(jax.random.key(35), 8)
    experts = balanced_routing(keys[0], tokens, top_k, n_experts)
    x = jax.random.normal(keys[1], (tokens, d), dtype)
    weights = jax.random.uniform(keys[2], (tokens, top_k), jnp.float32)
    w_gate, w_up = (jax.random.normal(k, (held, d, f), jnp.float32)
                    * d ** -0.5 for k in keys[3:5])
    w_down = jax.random.normal(keys[5], (held, f, d), jnp.float32) * f ** -0.5
    mats = (w_gate if gated else None, w_up, w_down)
    cotangent = jax.random.normal(keys[6], (tokens, d), jnp.float32)
    ladder = moe.row_ladder(tokens * top_k, held, n_experts)
    plan = jax.jit(functools.partial(
        moe.dispatch, first=0, held=held, n_experts=n_experts))(experts)
    live, n = int(plan["group_sizes"].sum()), int(plan["rows_computed"])
    base = {"cell": name, "tokens": tokens, "top_k": top_k, "hidden": d,
            "ladder": list(ladder), "live_rows": live, "rung_rows": n,
            "dtype": jnp.dtype(dtype).name}
    order, inverse = plan["order"][:n], plan["inverse"]
    rows = jnp.where((jnp.arange(n) < live)[:, None],
                     jax.random.normal(keys[7], (n, d), dtype), 0)

    # the two movements alone, at the rung and at tokens x top_k rows
    want = None
    for impl, candidate in CANDIDATES.items():
        fn = jax.jit(functools.partial(candidate, top_k=top_k))
        rec = {**base, "what": "sum_by_token", "impl": impl}
        try:
            got = fn(rows, order, inverse).astype(jnp.float32)
            want = got if want is None else want
            rec["max_abs_gap_to_first"] = float(jnp.abs(got - want).max())
            rec["ms"] = best_ms(fn, (rows, order, inverse), reps)
        except Exception as e:
            rec["error"] = repr(e)[:300]
        yield rec
    full = tokens * top_k
    all_rows = jax.random.normal(keys[7], (full, d), dtype)
    yield {**base, "what": "sum_by_token", "impl": "parent_all_rows",
           "ms": best_ms(jax.jit(
               lambda r, i: r[i].reshape(-1, top_k, d).sum(1)),
               (all_rows, inverse), reps)}
    take = jax.jit(lambda x, o: x[o // top_k])
    yield {**base, "what": "take_by_token", "impl": "rung",
           "ms": best_ms(take, (x, order), reps)}
    yield {**base, "what": "take_by_token", "impl": "parent_all_rows",
           "ms": best_ms(take, (x, plan["order"]), reps)}
    yield {**base, "what": "sort_and_histogram", "impl": "dispatch",
           "ms": best_ms(jax.jit(functools.partial(
               moe.dispatch, first=0, held=held, n_experts=n_experts)),
               (experts,), reps)}

    # the whole path, forward + backward
    static = (top_k, dtype, activation, 0, n_experts)
    args = (x, weights, mats, experts, cotangent)
    paths = {"parent_all_rows": ((full,), moe._sum_by_token),
             "i_gather": (ladder, moe._sum_by_token)}
    for impl in ("ii_scatter_add", "ii_segment_sum", "iii_runs"):
        paths[impl] = (ladder, as_sum_by_token(CANDIDATES[impl]))
    first_grads = None
    for impl, (rungs, summed) in paths.items():
        fn = whole_path(static, rungs, summed)
        rec = {**base, "what": "held_experts_sum_fwd_bwd", "impl": impl}
        try:
            _, grads = fn(*args)
            leaves = [g.astype(jnp.float32) for g in jax.tree.leaves(grads)]
            first_grads = leaves if first_grads is None else first_grads
            rec["max_rel_gap_to_parent"] = max(
                float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-30))
                for a, b in zip(leaves, first_grads))
            rec["ms"] = best_ms(fn, args, reps)
        except Exception as e:
            rec["error"] = repr(e)[:300]
        yield rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", default="chiprun_out/ab_moe_rows.jsonl")
    ap.add_argument("--cells", nargs="*", default=list(CELLS))
    ap.add_argument("--tiny", action="store_true",
                    help="a toy shape in float32, anywhere: the candidates' "
                         "agreement, no time worth reading")
    args = ap.parse_args(argv)
    if args.tiny:
        cells, dtype = TINY, jnp.float32
    elif jax.default_backend() != "tpu":
        print("needs a TPU (or --tiny)", file=sys.stderr)
        return 1
    else:
        cells, dtype = {c: CELLS[c] for c in args.cells}, jnp.bfloat16
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "a") as out:
        for name, cell in cells.items():
            for rec in measure(name, cell, args.reps, dtype):
                rec["device"] = jax.devices()[0].device_kind
                line = json.dumps(rec)
                print(line, flush=True)
                out.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
