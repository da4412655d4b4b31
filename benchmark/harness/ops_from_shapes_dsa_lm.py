"""Matrix-multiply operations and least bytes of the ``train_dsa_lm``
configurations, counted from the configuration's sizes alone, so the count
is the same whatever implements a block (``ops_from_shapes_swa_lm.py``'s
rules, for this model's layers).

Counted, a token and a layer, forward (one multiply-add is two operations):
the attention's projections (q over all query heads, k and v over the
key/value heads, o), the indexer's (16 query heads of 64, its one key head,
its 16 weights), the core's scores and values of every QUERY head at the
keys its query selected, ``min(t + 1, topk)`` at position t (a kernel that
computes whole tiles of keys a query did not select gets no credit for
them), the router, and the routed experts HELD HERE at the rows they were
sent: ``routed_rows``, a step's assignments to held experts summed over the
layers, which the readers take from the program's ``moe/assignments_here``
counter (without it the formula ``top_k x tokens x held / router width``
stands in, for sizing a cell before its first run only). Once a token: the
output head over the vocabulary rows held. Left out: norms, rotary,
softmax, SiLU, the top-k and the packing, the embedding gather, the
dispatch's sort and gathers, the losses.

A training step is three forward passes' worth (forward, and a backward
pass that costs two) of everything with a gradient: nothing recomputed is
counted. The indexer's scores are the one exception, because their two
passes see different keys: forward over every causal key (the selection
needs all of them; the loss reuses them), backward over the selected keys
alone, where the loss's gradient is not zero. The main attention's
probabilities that the indexer's loss reads are the core's own, and are not
counted again.
"""

from __future__ import annotations


def tokens_per_step(config: dict) -> int:
    return config["pairs_per_step"]


def formula_routed_rows(config: dict) -> float:
    """Rows a step, over all layers, that a balanced router sends the held
    experts."""
    return (config["num_hidden_layers"] * config["num_experts_per_tok"]
            * tokens_per_step(config) * config["num_experts"]
            / config["router_width"])


def mean_selected(seq_len: int, topk: int) -> float:
    """Mean keys a query reads: t + 1 at position t, at most ``topk``."""
    if topk >= seq_len:
        return (seq_len + 1) / 2
    return (topk * (topk + 1) / 2 + (seq_len - topk) * topk) / seq_len


def layer_forward_flops(config: dict, seq_len: int) -> dict:
    """{part: operations a token} of one layer's forward pass, the scores
    averaged over a sequence of ``seq_len``; ``routed_row`` is one expert's
    SwiGLU on one row."""
    d, width = config["hidden_size"], config["head_dim"]
    heads, groups = (config["num_attention_heads"],
                     config["num_key_value_heads"])
    sa = config["sa_config"]
    i_heads, i_width = sa["indexer_num_heads"], sa["indexer_head_dim"]
    selected = mean_selected(seq_len, sa["topk"])
    return {
        "projections": 2 * d * width * (2 * heads + 2 * groups),
        "indexer_projections": 2 * d * (i_heads * i_width + i_width
                                        + i_heads),
        # one query's scores against one key, all the indexer's heads
        "indexer_key": 2 * i_heads * i_width,
        "causal_keys": (seq_len + 1) / 2,
        "selected_keys": selected,
        "core": 2 * heads * 2 * width * selected,
        "router": 2 * d * config["router_width"],
        "routed_row": 2 * 3 * d * config["moe_intermediate_size"],
    }


def train_step_flops(config: dict, seq_len: int, routed_rows=None) -> dict:
    """One optimizer step: {"total", "core", "indexer", "routed"}
    operations; ``indexer`` is what the indexer's scope holds (its
    projections, forward and backward, and the selection's scores over the
    causal keys), the held experts' at ``routed_rows`` (the formula's where
    None)."""
    parts = layer_forward_flops(config, seq_len)
    layers = config["num_hidden_layers"]
    tokens = tokens_per_step(config)
    if routed_rows is None:
        routed_rows = formula_routed_rows(config)
    selection = parts["indexer_key"] * parts["causal_keys"]
    a_token = layers * (
        3 * (parts["projections"] + parts["router"] + parts["core"]
             + parts["indexer_projections"])
        + selection + 2 * parts["indexer_key"] * parts["selected_keys"]) \
        + 3 * 2 * config["hidden_size"] * config["vocab_size"]
    routed = 3 * routed_rows * parts["routed_row"]
    return {
        "total": tokens * a_token + routed,
        "core": 3 * tokens * layers * parts["core"],
        "indexer": tokens * layers * (
            3 * parts["indexer_projections"] + selection),
        "routed": routed,
    }


def core_bytes(config: dict, seq_len: int) -> float:
    """Least bytes the sparse core's kernels of one step must move: q and
    the output over the query heads, k and v over the key/value heads, in
    the compute type, the log-sum-exp (float32, a query head) and the packed
    selection (a bit a key), once each for the forward pass and again with
    their gradients for the backward pass (3x)."""
    width = 2 if config["compute_dtype"] == "bfloat16" else 4
    heads = config["num_attention_heads"]
    a_token = 2 * config["head_dim"] * (
        heads + config["num_key_value_heads"]) * width \
        + 4 * heads + seq_len / 8
    return 3.0 * tokens_per_step(config) * config["num_hidden_layers"] \
        * a_token


def routed_bytes(config: dict, routed_rows=None) -> float:
    """Least bytes the grouped matrix products of one step must move: each
    held expert's three matrices once a layer, ``routed_rows`` rows (the
    formula's where None) in and out of the gate/up product and of the down
    product, for the forward pass and twice more for the backward pass
    (3x)."""
    width = 2 if config["compute_dtype"] == "bfloat16" else 4
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    if routed_rows is None:
        routed_rows = formula_routed_rows(config)
    weights = config["num_hidden_layers"] * config["num_experts"] * 3 * d * f
    return 3.0 * (weights + routed_rows * (d + 2 * f + f + d)) * width
