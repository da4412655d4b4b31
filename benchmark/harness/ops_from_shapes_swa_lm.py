"""Matrix-multiply operations and least bytes of the ``train_swa_lm``
configurations, counted from the configuration's sizes alone, so the count
is the same whatever implements a block (``ops_from_shapes_lm.py``'s rules,
for this model's layers).

Counted, a token and a layer, forward (one multiply-add is two operations):
the attention's projections (q over all query heads, k and v over the
key/value heads, o), the scores and values of every QUERY head at the keys
its layer's mask leaves it (a global layer: the causal half, position i
sees i + 1 keys; a window layer: min(i + 1, window) keys; a kernel that
computes whole blocks the mask cuts gets no credit for the rest), the
router, and the routed experts HELD HERE at the rows they were sent:
``routed_rows``, a step's assignments to held experts summed over the
layers, which the readers take from the program's ``moe/assignments_here``
counter. Without it the formula ``top_k x tokens x held / router width``
stands in, for sizing a cell before its first run only. Once a token: the
output head over the vocabulary rows held. Left out: norms, rotary, softmax,
ReLU, the embedding gather, sort/gather/scatter of the dispatch, the loss.

A training step is three forward passes' worth (forward, and a backward
pass that costs two): nothing recomputed is counted.
"""

from __future__ import annotations


def tokens_per_step(config: dict) -> int:
    return config["pairs_per_step"]


def window_layers(config: dict) -> int:
    """Of the layers that are run, those under a window."""
    return sum(config["sliding_window_layout"][:config["num_hidden_layers"]])


def formula_routed_rows(config: dict) -> float:
    """Rows a step, over all layers, that a balanced router sends the held
    experts."""
    return (config["num_hidden_layers"]
            * config["moe_num_active_primary_experts"]
            * tokens_per_step(config)
            * config["moe_num_primary_experts"] / config["router_width"])


def mean_keys(seq_len: int, window=None) -> float:
    """Mean keys a query sees: i + 1 under the causal mask, at most
    ``window`` of them under a window."""
    if window is None or window >= seq_len:
        return (seq_len + 1) / 2
    return (window * (window + 1) / 2 + (seq_len - window) * window) / seq_len


def layer_forward_flops(config: dict, seq_len: int) -> dict:
    """{part: operations a token} of one layer's forward pass, the attention
    averaged over a sequence of ``seq_len``; ``routed_row`` is one expert's
    ReGLU on one row."""
    d, width = config["hidden_size"], config["head_dim"]
    heads, groups = (config["num_attention_heads"],
                     config["num_key_value_heads"])
    a_key = 2 * heads * 2 * width  # scores and values, every query head
    return {
        "projections": 2 * d * width * (2 * heads + 2 * groups),
        "attention_global": a_key * mean_keys(seq_len),
        "attention_window": a_key * mean_keys(
            seq_len, config["sliding_window_size"]),
        "router": 2 * d * config["router_width"],
        "routed_row": 2 * 3 * d * config["moe_ffn_hidden_size"],
    }


def train_step_flops(config: dict, seq_len: int, routed_rows=None) -> dict:
    """One optimizer step: {"total", "attention", "routed"} operations, the
    held experts' at ``routed_rows`` (the formula's where None)."""
    parts = layer_forward_flops(config, seq_len)
    layers, windowed = config["num_hidden_layers"], window_layers(config)
    if routed_rows is None:
        routed_rows = formula_routed_rows(config)
    attention = (windowed * parts["attention_window"]
                 + (layers - windowed) * parts["attention_global"])
    a_token = (layers * (parts["projections"] + parts["router"]) + attention
               + 2 * config["hidden_size"] * config["vocab_size"])
    tokens = tokens_per_step(config)
    routed = 3 * routed_rows * parts["routed_row"]
    return {
        "total": 3 * tokens * a_token + routed,
        "attention": 3 * tokens * attention,
        "routed": routed,
    }


def attention_bytes(config: dict) -> float:
    """Least bytes the attention kernels of one step must move: q and the
    output over the query heads, k and v over the key/value heads, once each
    in the compute type, for the forward pass and again with their gradients
    for the backward pass (3x)."""
    width = 2 if config["compute_dtype"] == "bfloat16" else 4
    a_token = 2 * config["head_dim"] * (
        config["num_attention_heads"] + config["num_key_value_heads"])
    return 3.0 * tokens_per_step(config) * config["num_hidden_layers"] \
        * a_token * width


def routed_bytes(config: dict, routed_rows=None) -> float:
    """Least bytes the grouped matrix products of one step must move: each
    held expert's three matrices once a layer, ``routed_rows`` rows (the
    formula's where None) in and out of the gate/up product and of the down
    product, for the forward pass and twice more for the backward pass
    (3x)."""
    width = 2 if config["compute_dtype"] == "bfloat16" else 4
    d, f = config["hidden_size"], config["moe_ffn_hidden_size"]
    if routed_rows is None:
        routed_rows = formula_routed_rows(config)
    weights = config["num_hidden_layers"] \
        * config["moe_num_primary_experts"] * 3 * d * f
    return 3.0 * (weights + routed_rows * (d + 2 * f + f + d)) * width
