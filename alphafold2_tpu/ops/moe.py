"""Routed experts for one chip's share of an expert-parallel layer.

The layer is told which experts it holds (``first`` .. ``first + held`` of
``n_experts``). It routes every token over all ``n_experts``, computes the
held experts' part of the result and nothing else: what the absent experts
would add lives on other chips and is left out here, as it is in the plain
reference given the same share. No code stands in for those chips or their
exchange.

Dropless: every assignment to a held expert is computed whatever the
imbalance. The ``tokens x top_k`` assignments are sorted by expert (those to
absent experts last), the held experts' rows go through a grouped matrix
product whose group sizes are data, and the shapes are static at
``tokens x top_k`` rows, the most one chip can be sent.

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


def dispatch(experts, first: int, held: int, n_experts: int):
    """Sort the (T, k) assignments by held expert. Returns a dict:

    - ``order`` (T*k,): assignment (token * k + slot) at each sorted row,
      held experts' rows first and in expert order, the rest after them;
    - ``inverse`` (T*k,): the sorted row of each assignment;
    - ``group_sizes`` (held,) int32: rows of each held expert;
    - ``here`` (T*k,) bool: the assignment is to a held expert;
    - ``hist`` (n_experts,) int32: assignments of every expert, held or not.
    """
    flat = experts.reshape(-1)
    local = flat - first
    here = (local >= 0) & (local < held)
    order = jnp.argsort(
        jnp.where(here, local, held), stable=True).astype(jnp.int32)
    hist = jnp.zeros((n_experts,), jnp.int32).at[flat].add(1)
    return {
        "order": order, "inverse": jnp.argsort(order).astype(jnp.int32),
        "group_sizes": jax.lax.dynamic_slice_in_dim(hist, first, held),
        "here": here, "hist": hist,
    }


def gather_rows(x, plan, top_k: int):
    """(T, D) tokens -> (T*k, D) rows in sorted order. ``inverse``, read as
    (T, k), lists each token's k sorted rows: the backward pass sums them."""
    return _take_rows(x, plan["order"] // top_k, plan["inverse"], top_k)


def combine(rows, weights, plan, top_k: int):
    """Sum each token's ``top_k`` rows under its weights. ``rows`` (T*k, D)
    in sorted order, zero past the held experts' groups (as ``expert_ffn``
    leaves them). Returns (T, D)."""
    n = rows.shape[0]
    w_sorted = weights.reshape(-1)[plan["order"]].astype(rows.dtype)
    back = _take_rows(
        rows * w_sorted[:, None], plan["inverse"], plan["order"], 1)
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
    Rows past the groups are masked on the way in and on the way out, so
    zeros come out of them and zeros go back into them: neither a token nor
    a token's gradient sees what the kernel left there."""
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


def held_experts_sum(tokens, experts, weights, w_gate, w_up, w_down,
                     first: int, n_experts: int, dtype,
                     activation=jax.nn.silu):
    """``sum over the experts held here of w_e Expert_e(token)`` for tokens
    (T, D) routed to ``experts`` (T, k) under ``weights`` (T, k); the held
    experts are ``first .. first + w_up.shape[0] - 1`` of ``n_experts``;
    ``w_gate`` None for ungated experts (:func:`expert_ffn`).
    Returns ((T, D), the dispatch plan, which :func:`load_counters` reads).
    The three steps are the named scopes ``dispatch``, ``experts`` and
    ``combine``."""
    held, top_k = w_up.shape[0], experts.shape[-1]
    if first < 0 or first + held > n_experts:
        raise ValueError(
            f"experts {first}..{first + held - 1} are not among the "
            f"router's {n_experts}")
    with jax.named_scope("dispatch"):
        plan = dispatch(experts, first, held, n_experts)
        rows = gather_rows(tokens, plan, top_k)
    with jax.named_scope("experts"):
        rows = expert_ffn(rows, plan["group_sizes"], w_gate, w_up, w_down,
                          dtype, activation)
    with jax.named_scope("combine"):
        out = combine(rows, weights, plan, top_k)
    return out, plan


def load_counters(plan) -> dict:
    """One layer's routing counters of the step (PERF.md section 3)."""
    sizes = plan["group_sizes"]
    computed = sizes.sum()
    # an assignment to a held expert is computed when its sorted row lies
    # inside the groups: the sort puts every one there, so none is dropped
    reached = jnp.sum(plan["here"] & (plan["inverse"] < computed))
    mean = jnp.maximum(sizes.astype(jnp.float32).mean(), 1.0)
    return {
        "hist": plan["hist"],
        "assignments_here": computed,
        "load_max_over_mean": sizes.max().astype(jnp.float32) / mean,
        "dropped": jnp.sum(plan["here"]) - reached,
    }
