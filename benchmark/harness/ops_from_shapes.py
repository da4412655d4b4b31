"""Matrix-multiply operations of the model, counted from the configuration's
sizes alone, so the count is the same whatever implements a block.

Counted: every projection, QK^T and PV of every attention (pair axial column
and row pass, MSA axial column pass and tied row pass, pair<-MSA and
MSA<-pair cross-attention), both GEGLU feed-forwards, the distogram head.
One multiply-add is two operations. Left out: softmax, layer norms, GELU,
the gating product, residual sums, embeddings, the loss: elementwise work
that XLA's ``cost_analysis`` counts and the MXU does not do. Their share
falls as 1 / dim: at dim 64 on the CPU XLA reads 15-19% above this count
(``tests/test_benchmark.py`` holds it between the count and 1.2x the count,
at depth 1 and 2), at the flagship's dim 256 and 4,096 keys about 1%.

The last layer's MSA update (its MSA<-pair cross-attention and its MSA
feed-forward) feeds nothing: the distogram reads the pair stream alone. No
implementation has to compute it (XLA removes it: the flagship's trace shows
three big cross-attentions a step, not four), so it is not counted.

A training step is three forward passes' worth (forward, and a backward pass
that costs two): nothing recomputed is counted.
"""

from __future__ import annotations

FF_MULT = 4
BUCKETS = 37
DEAD_IN_LAST_LAYER = ("msa_from_pair", "msa_ff")


def layers_of(block: str, depth: int) -> int:
    """In how many of the ``depth`` layers a block's result is used."""
    return depth - 1 if block in DEAD_IN_LAST_LAYER else depth


def attention_module(tokens_q: int, tokens_kv: int, keys_per_query: int,
                     dim: int, inner: int) -> dict:
    """One attention module: projections and the two attention matmuls."""
    return {
        "projections": 2 * (tokens_q * dim * inner          # to_q
                            + tokens_kv * dim * 2 * inner   # to_kv
                            + tokens_q * inner * dim),      # to_out
        "attention": 2 * 2 * tokens_q * keys_per_query * inner,
    }


def forward_blocks(sizes: dict, n: int, rows: int, nm: int) -> dict:
    """{block: {"projections", "attention"}} of ONE layer's forward pass for
    one example: pair grid n x n, MSA grid rows x nm."""
    dim, inner = sizes["dim"], sizes["heads"] * sizes["dim_head"]
    pair, msa = n * n, rows * nm
    ff = lambda tokens: {
        "projections": 2 * tokens * (dim * 2 * FF_MULT * dim
                                     + FF_MULT * dim * dim),
        "attention": 0,
    }
    return {
        "pair_axial_cols": attention_module(pair, pair, n, dim, inner),
        "pair_axial_rows": attention_module(pair, pair, n, dim, inner),
        "msa_axial_cols": attention_module(msa, msa, rows, dim, inner),
        # tied rows: one nm x nm matrix per head, contracted over rows x
        # dim_head: the same count as untied rows
        "msa_axial_rows": attention_module(msa, msa, nm, dim, inner),
        "pair_from_msa": attention_module(pair, msa, msa, dim, inner),
        "msa_from_pair": attention_module(msa, pair, pair, dim, inner),
        "pair_ff": ff(pair),
        "msa_ff": ff(msa),
    }


def forward_flops(sizes: dict, n: int, rows: int, nm: int) -> dict:
    """{"total", "attention", "by_block"} for one example's forward pass:
    ``depth`` layers and the distogram head."""
    blocks = forward_blocks(sizes, n, rows, nm)
    depth = sizes["depth"]
    by_block = {k: layers_of(k, depth) * (v["projections"] + v["attention"])
                for k, v in blocks.items()}
    by_block["distogram_head"] = 2 * n * n * sizes["dim"] * BUCKETS
    return {
        "total": sum(by_block.values()),
        "attention": {k: layers_of(k, depth) * v["attention"]
                      for k, v in blocks.items()},
        "by_block": by_block,
    }


def train_step_flops(config: dict) -> dict:
    """One optimizer step of a training configuration, all chips together."""
    fwd = forward_flops(config, config["crop"], config["msa_depth"],
                        config["msa_len"])
    batch = config["batch"] * config["mesh"]["dp"]
    return {"total": 3 * batch * fwd["total"],
            "attention": {k: 3 * batch * v
                          for k, v in fwd["attention"].items()}}


def attention_bytes(config: dict, blocks) -> int:
    """Least bytes the attention matmuls of ``blocks`` must move in one
    training step: q and the output over the queries, k and v over the keys,
    once each in the compute type, for the forward pass and again for the
    backward pass with their gradients (3x)."""
    width = 2 if config["compute_dtype"] == "bfloat16" else 4
    inner = config["heads"] * config["dim_head"]
    n, rows, nm = config["crop"], config["msa_depth"], config["msa_len"]
    pair, msa = n * n, rows * nm
    tokens = {  # queries + keys of one pass
        "pair_axial_cols": 2 * pair, "pair_axial_rows": 2 * pair,
        "msa_axial_cols": 2 * msa, "msa_axial_rows": 2 * msa,
        "pair_from_msa": pair + msa, "msa_from_pair": msa + pair,
    }
    batch = config["batch"] * config["mesh"]["dp"]
    return 3 * batch * inner * width * sum(
        layers_of(b, config["depth"]) * 2 * tokens[b] for b in blocks)
