"""Matrix-multiply operations and least bytes of the language-model
configurations, counted from the configuration's sizes alone, so the count
is the same whatever implements a block.

Counted, a token and a layer, forward (one multiply-add is two operations):
the attention's projections (q, kv down, kv up, o), the causal scores and
values at the PUBLISHED head sizes (q/k ``qk_nope + qk_rope``, v ``v_head``:
a kernel that pads heads gets no credit for the padding) over the causal
half (position i sees i + 1 keys), the dense or shared SwiGLU, the router,
and the routed experts HELD HERE at the rows they were sent:
``routed_rows``, a step's assignments to held experts summed over the expert
layers, which the readers take from the program's ``moe/assignments_here``
counter. Without it the formula ``top_k x tokens x held / router width``
stands in, for sizing a cell before its first run only: it is what a
balanced router sends a chip, and a run's own counts lie far from it (1.6k-17k
rows a layer for the formula's 12,288 in the first cell, and drifting over
the window: PERF.md section 6), so a share computed from the formula is not
a share of the work that ran. Once a token:
the output head over the vocabulary rows held. Left out: norms, rotary,
softmax, SiLU, the embedding gather, sort/gather/scatter of the dispatch,
the loss: work the MXU does not do.

A training step is three forward passes' worth (forward, and a backward
pass that costs two): nothing recomputed is counted, not the layer
recomputation the configuration chooses and not the logits the flash
backward recomputes.
"""

from __future__ import annotations


def tokens_per_step(config: dict) -> int:
    return config["pairs_per_step"]


def moe_layers(config: dict) -> int:
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


def formula_routed_rows(config: dict) -> float:
    """Rows a step, over all expert layers, that a balanced router sends the
    held experts."""
    return (moe_layers(config) * config["num_experts_per_tok"]
            * tokens_per_step(config)
            * config["n_routed_experts"] / config["router_width"])


def layer_forward_flops(config: dict, seq_len: int) -> dict:
    """{part: operations a token} of one layer's forward pass, the causal
    part averaged over a sequence of ``seq_len``; ``routed_row`` is one
    expert's SwiGLU on one row."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    nope, rope, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                      config["v_head_dim"])
    rank = config["kv_lora_rank"]
    keys = (seq_len + 1) / 2  # mean keys a query sees under the causal mask
    expert = 3 * d * config["moe_intermediate_size"]
    return {
        "projections": 2 * (d * heads * (nope + rope) + d * (rank + rope)
                            + rank * heads * (nope + dv) + heads * dv * d),
        "attention": 2 * heads * keys * ((nope + rope) + dv),
        "dense_ffn": 2 * 3 * d * config["intermediate_size"],
        "shared": 2 * config["n_shared_experts"] * expert,
        "router": 2 * d * config["router_width"],
        "routed_row": 2 * expert,
    }


def train_step_flops(config: dict, seq_len: int, routed_rows=None) -> dict:
    """One optimizer step: {"total", "attention", "routed"} operations, the
    held experts' at ``routed_rows`` (the formula's where None)."""
    parts = layer_forward_flops(config, seq_len)
    layers = config["num_hidden_layers"]
    dense_layers = config["first_k_dense_replace"]
    if routed_rows is None:
        routed_rows = formula_routed_rows(config)
    attn = parts["projections"] + parts["attention"]
    a_token = (
        layers * attn + dense_layers * parts["dense_ffn"]
        + moe_layers(config) * (parts["shared"] + parts["router"])
        + 2 * config["hidden_size"] * config["vocab_size"])
    tokens = tokens_per_step(config)
    routed = 3 * routed_rows * parts["routed_row"]
    return {
        "total": 3 * tokens * a_token + routed,
        "attention": 3 * tokens * layers * parts["attention"],
        "routed": routed,
    }


def attention_bytes(config: dict) -> float:
    """Least bytes the attention kernels of one step must move: q, k, v and
    the output at the published head sizes, once each in the compute type,
    for the forward pass and again with their gradients for the backward
    pass (3x)."""
    width = 2 if config["compute_dtype"] == "bfloat16" else 4
    heads = config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    a_token = heads * (2 * qk + 2 * config["v_head_dim"])
    return 3.0 * tokens_per_step(config) * config["num_hidden_layers"] \
        * a_token * width


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
    weights = moe_layers(config) * config["n_routed_experts"] * 3 * d * f
    return 3.0 * (weights + routed_rows * (d + 2 * f + f + d)) * width
