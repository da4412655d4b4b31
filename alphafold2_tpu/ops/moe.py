"""Routed experts for one chip's share of an expert-parallel layer.

The layer is told which experts it holds (``first`` .. ``first + held`` of
``n_experts``). It routes every token over all ``n_experts``, computes the
held experts' part of the result and nothing else: what the absent experts
would add lives on other chips and is left out here, as it is in the plain
reference given the same share. No code stands in for those chips or their
exchange.

Dropless: every assignment to a held expert is computed whatever the
imbalance. The ``tokens x top_k`` assignments are sorted by expert (those to
absent experts last) and the held experts' rows go through a grouped matrix
product whose group sizes are data.

What is static is the **rung**: the number of sorted rows the path from the
gather to the sum over a token's rows is built for. :func:`row_ladder` lists
the rungs, from twice the balanced share ``tokens x top_k x held /
n_experts`` by doubling up to ``tokens x top_k``, the most one chip can be
sent; the step picks the lowest rung that holds the live count
(``group_sizes.sum()``, which the sort has anyway) with one
``jax.lax.switch``, on the device. The sort, the histogram and the counters
stay outside the switch, on ``tokens x top_k`` integers. A layer that holds
every expert has one rung and no switch. ``rows_computed`` among the
counters is the rung a layer took in a step.

Router numerics (logits, scores, top-k, weights) are float32 whatever the
compute type: a score rounded to bfloat16 reorders near-ties.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def router_logits(x, w_router):
    """``x`` (T, D) against ``w_router`` (D, E) in float32."""
    return jnp.dot(
        x.astype(jnp.float32), w_router.astype(jnp.float32),
        precision="highest", preferred_element_type=jnp.float32,
    )


def route(x, w_router, bias, top_k: int, scaling: float):
    """Sigmoid scores over all experts, the ``top_k`` largest ``score +
    bias`` a token, weights ``scaling * s / sum(selected s)`` (the bias picks
    and does not weigh). ``x`` (T, D), ``w_router`` (D, E), ``bias`` (E,).
    Returns (experts (T, k) int32, weights (T, k) float32)."""
    scores = jax.nn.sigmoid(router_logits(x, w_router))
    _, experts = jax.lax.top_k(
        scores + jax.lax.stop_gradient(bias.astype(jnp.float32)), top_k)
    picked = jnp.take_along_axis(scores, experts, axis=-1)
    weights = scaling * picked / (picked.sum(-1, keepdims=True) + 1e-20)
    return experts.astype(jnp.int32), weights


def route_softmax(x, w_router, top_k: int):
    """The ``top_k`` largest logits a token, weights their softmax over the
    selected (a softmax over all experts renormalised over the selected is
    the same numbers). No bias, no scaling. Returns as :func:`route`."""
    picked, experts = jax.lax.top_k(router_logits(x, w_router), top_k)
    return experts.astype(jnp.int32), jax.nn.softmax(picked, axis=-1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _take_rows(x, index, inverse, fan: int):
    """``x[index]`` for an ``index`` in which every row of ``x`` occurs
    ``fan`` times (1: a permutation); ``inverse`` lists the positions of
    ``index`` sorted by value. The backward pass is then a gather too,
    ``g[inverse]`` summed over each run of ``fan``, where autodiff would
    emit a scatter-add over duplicate rows."""
    return x[index]


def _take_rows_fwd(x, index, inverse, fan):
    return x[index], inverse


def _take_rows_bwd(fan, inverse, g):
    back = g[inverse]
    if fan > 1:
        back = back.reshape(-1, fan, g.shape[-1]).sum(1)
    return back, None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


# the ragged product's row tile on the TPU: a rung is a whole number of them
ROW_TILE = 512


def row_ladder(n_rows: int, held: int, n_experts: int) -> tuple:
    """The rungs, ascending: twice the balanced share ``n_rows x held /
    n_experts`` of the ``n_rows`` assignments (a balanced router hovers at
    the share itself, and a rung's edge there would flip every few steps),
    doubled while under ``n_rows``, each rounded up to :data:`ROW_TILE`, and
    last ``n_rows`` itself, which holds whatever the router sends. One rung
    where the held experts are half of all or more, or ``n_rows`` is too few
    for two tiles."""
    ladder, rung = [], max(1, -(-2 * n_rows * held // n_experts))
    while True:
        rows = -(-rung // ROW_TILE) * ROW_TILE
        if rows >= n_rows:
            return (*ladder, n_rows)
        if rows not in ladder:  # small shares round up to one tile
            ladder.append(rows)
        rung *= 2


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _take_by_token(x, order, inverse, top_k: int):
    """``x[order // top_k]``: the token of each of a rung's sorted rows
    (``order`` is the plan's, cut to the rung; ``inverse`` the plan's,
    whole). The backward pass is :func:`_sum_by_token`."""
    return x[order // top_k]


def _take_by_token_fwd(x, order, inverse, top_k):
    return _take_by_token(x, order, inverse, top_k), (order, inverse)


def _take_by_token_bwd(top_k, res, g):
    return _sum_by_token(g, *res, top_k), None, None


_take_by_token.defvjp(_take_by_token_fwd, _take_by_token_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _sum_by_token(rows, order, inverse, top_k: int):
    """(T, D): each token's sum over those of a rung's R sorted rows that are
    its own (row ``r`` is token ``order[r] // top_k``'s; at most ``top_k`` a
    token). ``inverse``, read as (T, k), lists each token's k sorted rows:
    those inside the rung are gathered, the others read a zero row put after
    the rung's, and the k are summed as at T*k rows (in float32). Of the
    forms timed on the chip (``scripts/ab_moe_rows.py``, PERF.md section 6,
    PR 35) the fastest inside the whole path, though its gather is still
    over T x top_k positions. The backward pass is :func:`_take_by_token`."""
    n = rows.shape[0]
    table = jnp.concatenate([rows, jnp.zeros_like(rows[:1])])
    back = table[jnp.minimum(inverse, n)]
    return back.reshape(-1, top_k, rows.shape[-1]).sum(1)


def _sum_by_token_fwd(rows, order, inverse, top_k):
    return _sum_by_token(rows, order, inverse, top_k), (order, inverse)


def _sum_by_token_bwd(top_k, res, g):
    return _take_by_token(g, *res, top_k), None, None


_sum_by_token.defvjp(_sum_by_token_fwd, _sum_by_token_bwd)


def dispatch(experts, first: int, held: int, n_experts: int):
    """Sort the (T, k) assignments by held expert. Returns a dict:

    - ``order`` (T*k,): assignment (token * k + slot) at each sorted row,
      held experts' rows first and in expert order, the rest after them;
    - ``inverse`` (T*k,): the sorted row of each assignment;
    - ``group_sizes`` (held,) int32: rows of each held expert;
    - ``here`` (T*k,) bool: the assignment is to a held expert;
    - ``hist`` (n_experts,) int32: assignments of every expert, held or not;
    - ``rung`` () int32: the lowest rung of :func:`row_ladder` that holds
      the held experts' rows, and ``rows_computed`` () int32, its rows.
    """
    flat = experts.reshape(-1)
    local = flat - first
    here = (local >= 0) & (local < held)
    order = jnp.argsort(
        jnp.where(here, local, held), stable=True).astype(jnp.int32)
    hist = jnp.zeros((n_experts,), jnp.int32).at[flat].add(1)
    group_sizes = jax.lax.dynamic_slice_in_dim(hist, first, held)
    ladder = row_ladder(flat.shape[0], held, n_experts)
    live = group_sizes.sum()
    # a count of the rungs too short: the integer 0 where there is but one
    rung = sum((live > rows).astype(jnp.int32) for rows in ladder[:-1])
    return {
        "order": order, "inverse": jnp.argsort(order).astype(jnp.int32),
        "group_sizes": group_sizes, "here": here, "hist": hist,
        "rung": jnp.asarray(rung, jnp.int32),
        "rows_computed": jnp.asarray(ladder, jnp.int32)[rung],
    }


def gather_rows(x, plan, top_k: int, n_rows=None):
    """(T, D) tokens -> the first ``n_rows`` rows in sorted order (a rung;
    all T*k if None), each its token's. At T*k rows ``inverse``, read as
    (T, k), lists each token's k sorted rows and the backward pass sums
    them; under that a token's rows are found among the rung's
    (:func:`_sum_by_token`)."""
    if n_rows is None or n_rows == plan["order"].shape[0]:
        return _take_rows(x, plan["order"] // top_k, plan["inverse"], top_k)
    return _take_by_token(
        x, plan["order"][:n_rows], plan["inverse"], top_k)


def combine(rows, weights, plan, top_k: int):
    """Sum each token's rows under its weights. ``rows`` (R, D) are the
    first R in sorted order (a rung, which holds every held expert's rows,
    or all T*k), zero past the held experts' groups (as ``expert_ffn``
    leaves them). Returns (T, D)."""
    n, full = rows.shape[0], plan["order"].shape[0]
    order = plan["order"][:n]
    rows = rows * weights.reshape(-1)[order].astype(rows.dtype)[:, None]
    if n < full:
        return _sum_by_token(rows, order, plan["inverse"], top_k)
    back = _take_rows(rows, plan["inverse"], order, 1)
    return back.reshape(n // top_k, top_k, rows.shape[-1]).sum(1)


def grouped_matmul(rows, w, group_sizes):
    """``rows[group g] @ w[g]`` for the leading groups of ``rows`` (R, K),
    ``w`` (G, K, N), ``group_sizes`` (G,) int32: ``jax.lax.ragged_dot``, which
    the TPU compiler turns into a kernel of its own (tiles of 512) that
    visits the groups' rows only. Rows past the last group are not computed,
    in the product and in its gradient towards ``rows`` alike: what they
    hold there is unspecified (on the chip: whatever the buffer held)."""
    return jax.lax.ragged_dot(
        rows, w, group_sizes, preferred_element_type=rows.dtype)


def expert_ffn(rows, group_sizes, w_gate, w_up, w_down, dtype,
               activation=jax.nn.silu):
    """``W_down (activation(W_gate x) * W_up x)`` of each held expert over
    its own rows (SwiGLU as it stands, ReGLU with ``jax.nn.relu``), or, with
    ``w_gate`` None, the ungated ``W_down activation(W_up x)``: two grouped
    products of one width, not three. Weights are stacked (held, D, F) /
    (held, F, D); gate and up run as one product.
    ``rows`` are a rung's (or all T*k): every shape here follows their
    count, the products towards the weights included. Rows past the groups
    are masked on the way in and on the way out, so zeros come out of them
    and zeros go back into them: neither a token nor a token's gradient sees
    what the kernel left there."""
    live = (jnp.arange(rows.shape[0], dtype=jnp.int32)
            < group_sizes.sum())[:, None]
    w_in = w_up.astype(dtype) if w_gate is None else jnp.concatenate(
        [w_gate.astype(dtype), w_up.astype(dtype)], axis=-1)
    h = grouped_matmul(
        jnp.where(live, rows.astype(dtype), 0), w_in, group_sizes)
    if w_gate is None:
        act = activation(h.astype(jnp.float32)).astype(dtype)
    else:
        gate, up = jnp.split(h, 2, axis=-1)
        act = activation(gate.astype(jnp.float32)).astype(dtype) * up
    return jnp.where(
        live, grouped_matmul(act, w_down.astype(dtype), group_sizes), 0)


# the scope the models give this layer. An operation in a branch of the
# switch is named .../moe/cond/branch_1_fun/...: the branch opens the scope
# again, so that whoever looks for "moe" and "experts" side by side in an
# operation's name (the benchmark's readers do) finds them as before, and
# the switch itself stands under no step's scope, or a branch's operations
# would be found under two
LAYER_SCOPE = "moe"


def _rung_sum(static, tokens, weights, mats, plan):
    """The held experts' weighted sum over the first ``n_rows`` sorted rows:
    the three named scopes ``dispatch``, ``experts`` and ``combine``. Of the
    plan it reads ``order``, ``inverse`` and ``group_sizes``."""
    n_rows, top_k, dtype, activation = static
    with jax.named_scope("dispatch"):
        rows = gather_rows(tokens, plan, top_k, n_rows)
    with jax.named_scope("experts"):
        rows = expert_ffn(rows, plan["group_sizes"], *mats, dtype, activation)
    with jax.named_scope("combine"):
        return combine(rows, weights, plan, top_k)


def _rung_pull_back(static, g, tokens, weights, mats, plan):
    """The gradients of :func:`_rung_sum` towards tokens, weights and the
    experts' matrices: the rung run forward again, ``g`` pulled back."""
    _, pull = jax.vjp(
        lambda *diff: _rung_sum(static, *diff, plan), tokens, weights, mats)
    return pull(g)


@functools.lru_cache(maxsize=None)
def _branches(run, ladder, static):
    """``run((rung's rows, *static), *operands)`` for each rung, under the
    layer's scope. The same functions for the same arguments, whichever
    layer asks: ``jax.lax.switch`` traces a branch it has seen at these
    shapes once, not once a layer."""
    def branch(n_rows):
        def scoped(*operands):
            with jax.named_scope(LAYER_SCOPE):
                return run((n_rows, *static), *operands)
        return scoped
    return tuple(branch(n_rows) for n_rows in ladder)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _ladder_sum(ladder, static, tokens, weights, mats, plan):
    """:func:`_rung_sum` on the rung ``plan["rung"]`` names. Differentiated
    as a whole: the backward pass is a switch of its own whose branch runs
    the rung forward again and pulls the gradient back through it, so no
    rung's intermediate results leave a branch (autodiff of a switch would
    have every branch write zeros for all the other rungs' residuals)."""
    return jax.lax.switch(
        plan["rung"], _branches(_rung_sum, ladder, static),
        tokens, weights, mats, plan)


def _ladder_sum_fwd(ladder, static, tokens, weights, mats, plan):
    return (_ladder_sum(ladder, static, tokens, weights, mats, plan),
            (tokens, weights, mats, plan))


def _ladder_sum_bwd(ladder, static, res, g):
    grads = jax.lax.switch(
        res[-1]["rung"], _branches(_rung_pull_back, ladder, static), g, *res)
    return (*grads, None)


_ladder_sum.defvjp(_ladder_sum_fwd, _ladder_sum_bwd)


def held_experts_sum(tokens, experts, weights, w_gate, w_up, w_down,
                     first: int, n_experts: int, dtype,
                     activation=jax.nn.silu):
    """``sum over the experts held here of w_e Expert_e(token)`` for tokens
    (T, D) routed to ``experts`` (T, k) under ``weights`` (T, k); the held
    experts are ``first .. first + w_up.shape[0] - 1`` of ``n_experts``;
    ``w_gate`` None for ungated experts (:func:`expert_ffn`).
    Returns ((T, D), the dispatch plan, which :func:`load_counters` reads).
    The three steps are the named scopes ``dispatch``, ``experts`` and
    ``combine``; past the sort they run on the rung the plan names, inside
    a switch over :func:`row_ladder`'s rungs where there is more than one."""
    held, top_k = w_up.shape[0], experts.shape[-1]
    if first < 0 or first + held > n_experts:
        raise ValueError(
            f"experts {first}..{first + held - 1} are not among the "
            f"router's {n_experts}")
    with jax.named_scope("dispatch"):
        plan = dispatch(experts, first, held, n_experts)
    ladder = row_ladder(experts.size, held, n_experts)
    static = (top_k, dtype, activation)
    with jax.named_scope("experts"):
        # cast here: a branch then hands back gradients of the compute type
        mats = tuple(None if w is None else w.astype(dtype)
                     for w in (w_gate, w_up, w_down))
    sort = {k: plan[k] for k in ("order", "inverse", "group_sizes", "rung")}
    if len(ladder) == 1:
        out = _rung_sum((*ladder, *static), tokens, weights, mats, sort)
    else:
        out = _ladder_sum(ladder, static, tokens, weights, mats, sort)
    return out, plan


def load_counters(plan) -> dict:
    """One layer's routing counters of the step (PERF.md section 3).
    ``rows_computed`` is the rung: the sorted rows the step gathered, ran
    through the experts and summed in this layer, of which
    ``assignments_here`` were a held expert's."""
    sizes = plan["group_sizes"]
    live = sizes.sum()
    # an assignment to a held expert is computed when its sorted row lies
    # inside the groups and inside the rung: the sort puts every one in the
    # groups and the rung is chosen to hold them, so none is dropped
    reached = jnp.sum(plan["here"] & (
        plan["inverse"] < jnp.minimum(live, plan["rows_computed"])))
    mean = jnp.maximum(sizes.astype(jnp.float32).mean(), 1.0)
    return {
        "hist": plan["hist"],
        "assignments_here": live,
        "rows_computed": plan["rows_computed"],
        "load_max_over_mean": sizes.max().astype(jnp.float32) / mean,
        "dropped": jnp.sum(plan["here"]) - reached,
    }
