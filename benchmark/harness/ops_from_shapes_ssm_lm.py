"""Matrix-multiply operations and least bytes of the ``train_ssm_lm``
configurations, counted from the configuration's sizes alone, so the count
is the same whatever implements a block (``ops_from_shapes_lm.py``'s rules,
for this model's layers).

A layer is one mixer; which, the pattern's first ``num_hidden_layers``
characters say. Counted, a token, forward (one multiply-add is two
operations):

- ``M``: the two projections (hidden -> z, x, B, C, dt and inner -> hidden)
  and the scan's products **as the chunked form at the published
  ``chunk_size`` counts them, whatever implements the scan**: ``C B^T`` a
  group over a chunk (chunk x groups x state), the masked scores times ``dt
  x`` a head (chunk x heads x head width), each chunk's outgoing state and
  the entering state's contribution (state x heads x head width each). A
  chunk's whole square is counted, as the algorithm computes it, not the
  causal half;
- ``*``: q over the query heads, k and v over the key/value heads, o, and the
  scores and values of every QUERY head at the keys the causal mask leaves
  (position i sees i + 1);
- ``E``: the router, the shared expert's two products, and the routed
  experts HELD HERE at the rows they were sent, two products a row:
  ``routed_rows``, a step's assignments to held experts summed over the
  expert layers, which the readers take from the program's
  ``moe/assignments_here`` counter. Without it the formula ``top_k x tokens x
  held / router width`` stands in, for sizing a cell before its first run
  only.

Once a token: the output head over the vocabulary rows held. Left out:
norms, the convolution (4 multiply-adds a channel), softplus, the decays'
exponentials and cumulative sums, the gate, softmax, relu^2, the embedding
gather, sort/gather/scatter of the dispatch, the loss.

A training step is three forward passes' worth (forward, and a backward
pass that costs two): nothing recomputed is counted.
"""

from __future__ import annotations


def tokens_per_step(config: dict) -> int:
    return config["pairs_per_step"]


def layers_of(config: dict, kind: str) -> int:
    """Of the layers that are run, those whose mixer is ``kind``."""
    return config["hybrid_override_pattern"][
        :config["num_hidden_layers"]].count(kind)


def formula_routed_rows(config: dict) -> float:
    """Rows a step, over all expert layers, that a balanced router sends the
    held experts."""
    return (layers_of(config, "E") * config["num_experts_per_tok"]
            * tokens_per_step(config)
            * config["n_routed_experts"] / config["router_width"])


def layer_forward_flops(config: dict, seq_len: int) -> dict:
    """{part: operations a token} of the forward pass of one layer of each
    kind, the attention averaged over a sequence of ``seq_len``;
    ``routed_row`` is one expert's two products on one row."""
    d = config["hidden_size"]
    heads, width = config["mamba_num_heads"], config["mamba_head_dim"]
    groups, n = config["n_groups"], config["ssm_state_size"]
    chunk, inner = config["chunk_size"], heads * width
    q_heads, kv_heads, head = (config["num_attention_heads"],
                               config["num_key_value_heads"],
                               config["head_dim"])
    return {
        "ssm_projections": 2 * d * (2 * inner + 2 * groups * n + heads)
        + 2 * inner * d,
        "ssm_scan": 2 * (chunk * groups * n + chunk * inner + 2 * n * inner),
        "attn_projections": 2 * d * head * (2 * q_heads + 2 * kv_heads),
        "attention": 2 * q_heads * 2 * head * (seq_len + 1) / 2,
        "router": 2 * d * config["router_width"],
        "shared": 2 * 2 * d * config["moe_shared_expert_intermediate_size"]
        * config["n_shared_experts"],
        "routed_row": 2 * 2 * d * config["moe_intermediate_size"],
    }


def train_step_flops(config: dict, seq_len: int, routed_rows=None) -> dict:
    """One optimizer step: {"total", "scan", "attention", "routed"}
    operations, the held experts' at ``routed_rows`` (the formula's where
    None)."""
    parts = layer_forward_flops(config, seq_len)
    m, e, a = (layers_of(config, kind) for kind in "ME*")
    if routed_rows is None:
        routed_rows = formula_routed_rows(config)
    a_token = (m * (parts["ssm_projections"] + parts["ssm_scan"])
               + e * (parts["router"] + parts["shared"])
               + a * (parts["attn_projections"] + parts["attention"])
               + 2 * config["hidden_size"] * config["vocab_size"])
    tokens = tokens_per_step(config)
    routed = 3 * routed_rows * parts["routed_row"]
    return {
        "total": 3 * tokens * a_token + routed,
        "scan": 3 * tokens * m * parts["ssm_scan"],
        "attention": 3 * tokens * a * parts["attention"],
        "routed": routed,
    }


def scan_bytes(config: dict) -> float:
    """Least bytes the scans of one step must move: ``x``, ``B``, ``C`` in
    and ``y`` out once each in the compute type and ``dt`` in float32, a
    state-space layer, for the forward pass and again with their gradients
    for the backward pass (3x). No decay matrix, no chunk state: a kernel
    can keep those on the chip."""
    width = 2 if config["compute_dtype"] == "bfloat16" else 4
    heads = config["mamba_num_heads"]
    inner = heads * config["mamba_head_dim"]
    a_token = (2 * inner + 2 * config["n_groups"] * config["ssm_state_size"]
               ) * width + heads * 4
    return 3.0 * tokens_per_step(config) * layers_of(config, "M") * a_token


def routed_bytes(config: dict, routed_rows=None) -> float:
    """Least bytes the grouped matrix products of one step must move: each
    held expert's TWO matrices once an expert layer, ``routed_rows`` rows
    (the formula's where None) in and out of the up product and of the down
    product, for the forward pass and twice more for the backward pass
    (3x)."""
    width = 2 if config["compute_dtype"] == "bfloat16" else 4
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    if routed_rows is None:
        routed_rows = formula_routed_rows(config)
    weights = layers_of(config, "E") * config["n_routed_experts"] * 2 * d * f
    return 3.0 * (weights + routed_rows * (d + f + f + d)) * width
