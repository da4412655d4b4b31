"""Matrix-multiply operations and least bytes of the
``train_hybrid_dense_lm`` configurations, counted from the configuration's
sizes alone, so the count is the same whatever implements a block
(``ops_from_shapes_ssm_lm.py``'s rules, for this model's layers and under
this configuration's key names).

A layer is a mixer and a gated MLP; which mixer, ``layer_types``' first
``num_hidden_layers`` entries say. Counted, a token, forward (one
multiply-add is two operations):

- ``mamba``: the two projections (hidden -> z, x, B, C, dt and inner ->
  hidden) and the scan's products **as the chunked form at the published
  ``mamba_chunk_size`` counts them, whatever implements the scan**: ``C B^T``
  a group over a chunk (chunk x groups x state), the masked scores times ``dt
  x`` a head (chunk x heads x head width), each chunk's outgoing state and the
  entering state's contribution (state x heads x head width each). A chunk's
  whole square is counted, as the algorithm computes it, not the causal half;
- ``attention``: q over the query heads, k and v over the key/value heads,
  o, and the scores and values of every QUERY head at the keys the causal
  mask leaves (position i sees i + 1), at the published head width (a
  kernel that pads a head does not earn the padding);
- every layer: the gated MLP's three products (hidden -> 2 x width, width ->
  hidden).

Once a token: the tied head over the vocabulary rows held. Left out: norms,
the convolution (4 multiply-adds a channel), softplus, the decays'
exponentials and cumulative sums, the gates, softmax, the residual sums and
the multipliers, the embedding gather, the loss.

A training step is three forward passes' worth (forward, and a backward pass
that costs two): nothing recomputed is counted.
"""

from __future__ import annotations


def tokens_per_step(config: dict) -> int:
    return config["pairs_per_step"]


def layers_of(config: dict, kind: str) -> int:
    """Of the layers that are run, those whose mixer is ``kind``."""
    return list(config["layer_types"][:config["num_hidden_layers"]]).count(
        kind)


def layer_forward_flops(config: dict, seq_len: int) -> dict:
    """{part: operations a token} of the forward pass of one layer's part,
    the attention averaged over a sequence of ``seq_len``."""
    d = config["hidden_size"]
    heads, width = config["mamba_n_heads"], config["mamba_d_head"]
    groups, n = config["mamba_n_groups"], config["mamba_d_state"]
    chunk, inner = config["mamba_chunk_size"], heads * width
    q_heads, kv_heads, head = (config["num_attention_heads"],
                               config["num_key_value_heads"],
                               config["head_dim"])
    return {
        "ssm_projections": 2 * d * (2 * inner + 2 * groups * n + heads)
        + 2 * inner * d,
        "ssm_scan": 2 * (chunk * groups * n + chunk * inner + 2 * n * inner),
        "attn_projections": 2 * d * head * (2 * q_heads + 2 * kv_heads),
        "attention": 2 * q_heads * 2 * head * (seq_len + 1) / 2,
        "mlp": 2 * 3 * d * config["intermediate_size"],
    }


def train_step_flops(config: dict, seq_len: int) -> dict:
    """One optimizer step: {"total", "scan", "attention"} operations."""
    parts = layer_forward_flops(config, seq_len)
    m, a = layers_of(config, "mamba"), layers_of(config, "attention")
    a_token = (m * (parts["ssm_projections"] + parts["ssm_scan"])
               + a * (parts["attn_projections"] + parts["attention"])
               + (m + a) * parts["mlp"]
               + 2 * config["hidden_size"] * config["vocab_size"])
    tokens = tokens_per_step(config)
    return {
        "total": 3 * tokens * a_token,
        "scan": 3 * tokens * m * parts["ssm_scan"],
        "attention": 3 * tokens * a * parts["attention"],
    }


def scan_bytes(config: dict) -> float:
    """Least bytes the scans of one step must move: ``x``, ``B``, ``C`` in
    and ``y`` out once each in the compute type and ``dt`` in float32, a
    state-space layer, for the forward pass and again with their gradients
    for the backward pass (3x). No decay matrix, no chunk state: a kernel
    can keep those on the chip."""
    width = 2 if config["compute_dtype"] == "bfloat16" else 4
    heads = config["mamba_n_heads"]
    inner = heads * config["mamba_d_head"]
    a_token = (2 * inner + 2 * config["mamba_n_groups"]
               * config["mamba_d_state"]) * width + heads * 4
    return 3.0 * tokens_per_step(config) * layers_of(config, "mamba") \
        * a_token


def attention_bytes(config: dict) -> float:
    """Least bytes the attention kernels of one step must move: q and the
    output over the query heads, k and v over the key/value heads, once each
    in the compute type at the published head width, for the forward pass
    and again with their gradients for the backward pass (3x)."""
    width = 2 if config["compute_dtype"] == "bfloat16" else 4
    a_token = 2 * config["head_dim"] * (
        config["num_attention_heads"] + config["num_key_value_heads"])
    return 3.0 * tokens_per_step(config) * layers_of(config, "attention") \
        * a_token * width
