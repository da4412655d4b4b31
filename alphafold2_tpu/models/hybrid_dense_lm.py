"""Decoder-only dense hybrid language model: nine Mamba-2 state-space layers
and one positionless grouped-query attention layer a period, every layer
followed by a gated MLP, four scalar multipliers and a tied head, as one
pipeline stage's share of the model.

Written from a Granite-4.0-H-shaped ``config.json`` (``model_type``
granitemoehybrid with ``num_local_experts`` 0, so a layer's feed-forward is
its ``shared_mlp`` alone; ``layer_types`` such as nine ``mamba`` to one
``attention``, here one character a layer). With ``r =
residual_multiplier``, no biases but the convolution's:

- the stream enters as ``embedding_multiplier`` times the embedding row;
- layer ``i``: ``h = x + r * Mixer_i(RMSNorm(x))``, then ``x' = h + r *
  W_down (silu(W_gate y) * W_up y)`` with ``y = RMSNorm(h)``: **a layer is a
  mixer and a feed-forward**, where ``models/ssm_moe_lm.py``'s is one mixer
  alone;
- ``M``, the state-space mixer: ``models/ssm_moe_lm.py``'s ``Mamba2Mixer``
  as it is, at one group of B and C read by all 64 heads and chunks of 256;
- ``*``, the attention layer: ``models/swa_moe_lm.py``'s ``GroupedAttention``
  as its global layer runs it (full causal, no positional encoding), at 32
  query heads over 8 key/value heads of 64, the softmax scale
  ``attention_multiplier`` (1 / 64, not 64 ** -0.5);
- ``logits = RMSNorm(x_L) E^T / logits_scaling`` over the rows of the
  embedding table ``E`` held here: the head has no weights of its own, and
  ``E``'s gradient arrives from both ends.

The loss and what ``train.loop`` makes a Task of are ``models/mla_moe_lm.py``'s.
Weights float32, compute ``dtype``; norm statistics, ``dt``, the decays, the
carried state, softmax statistics, each residual sum and the loss are
float32 whatever ``dtype`` is (the stream between layers is ``dtype``). The
embedding row is scaled in float32 and rounded once; the logits are float32
and divided there.

Every module is a named scope in the compiled step (``layer_N/{mixer_norm,
ffn_norm}``, ``layer_N/ssm/{in_proj,conv,scan,gate_norm,out_proj}``,
``layer_N/attn_global/{q_proj,k_proj,v_proj,core,o_proj}``,
``layer_N/dense_ffn``, ``embed``, ``final_norm``, ``head``): a profiler trace
is reduced by these names. There is no router: the step's ``moe`` counters
are ``{}``. Beside the scan's counters a state-space layer (``ssm/...``) the
step carries ``stream/rms_in``, the root mean square of the entering stream
(which ``embedding_multiplier`` sets), and ``stream/rms_out``, that of the
stream before the final norm (which ``residual_multiplier`` holds down over
two adds a layer): a wrong multiplier shows there before it shows in a loss.
"""

from __future__ import annotations

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from alphafold2_tpu.config import HybridDenseLMConfig
from alphafold2_tpu.models.mla_moe_lm import (
    RMSNorm, SwiGLU, fan_in_normal, remat_layer,
)
from alphafold2_tpu.models import ssm_moe_lm
from alphafold2_tpu.models.ssm_moe_lm import Mamba2Mixer
from alphafold2_tpu.models.swa_moe_lm import GroupedAttention

MIXERS = {"M": "ssm", "*": "attn_global"}


# the pattern's first ``num_layers`` characters, one a layer, of M and *
layer_kinds = functools.partial(ssm_moe_lm.layer_kinds, mixers=MIXERS)


def root_mean_square(x):
    return jnp.sqrt(jnp.mean(jnp.square(x.astype(jnp.float32))))


def add_scaled(x, out, multiplier: float):
    """``x + multiplier * out`` summed in float32, ``x``'s type out."""
    return (x.astype(jnp.float32)
            + multiplier * out.astype(jnp.float32)).astype(x.dtype)


class Block(nn.Module):
    """A mixer and a gated MLP, each behind its norm and added to the stream
    times the residual multiplier; ``kind`` is the pattern's character.
    Returns (the stream, the scan's counters or {})."""

    cfg: HybridDenseLMConfig
    kind: str
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        norm = functools.partial(RMSNorm, c.rms_norm_eps, self.dtype)
        y = norm(name="mixer_norm")(x)
        name = MIXERS[self.kind]
        if self.kind == "M":
            out, counters = Mamba2Mixer(c, self.dtype, name=name)(y)
        else:
            out, counters = GroupedAttention(
                c, None, self.dtype, c.attention_multiplier, name=name)(y), {}
        h = add_scaled(x, out, c.residual_multiplier)
        out = SwiGLU(c.intermediate_size, self.dtype, name="dense_ffn")(
            norm(name="ffn_norm")(h))
        return add_scaled(h, out, c.residual_multiplier), counters


class HybridDenseLM(nn.Module):
    cfg: HybridDenseLMConfig

    @nn.compact
    def __call__(self, tokens):
        """tokens (B, S) int32 -> {"logits" (B, S, vocab) float32, "moe": {}
        (no router), "ssm": the scan's counters stacked over the state-space
        layers, "stream": the root mean square of the stream entering the
        first layer and leaving the last}."""
        c = self.cfg
        dtype = jnp.bfloat16 if c.bfloat16 else jnp.float32
        kinds = layer_kinds(c)
        block = remat_layer(Block)
        # the one table, float32 (vocabulary rows held here, hidden): a row
        # times the multiplier on the way in, rounded once; x E^T over the
        # scaling on the way out
        table = nn.Embed(c.vocab_size, c.hidden_size,
                         embedding_init=fan_in_normal(1),
                         name="embed").embedding
        with jax.named_scope("embed"):
            x = (jnp.take(table, tokens, axis=0)
                 * c.embedding_multiplier).astype(dtype)
        stream = {"rms_in": root_mean_square(x)}
        scanned = []  # the state-space layers' counters
        for i, kind in enumerate(kinds):
            x, counters = block(c, kind, dtype, name=f"layer_{i}")(x)
            if counters:
                scanned.append(counters)
        stream["rms_out"] = root_mean_square(x)
        x = RMSNorm(c.rms_norm_eps, dtype, name="final_norm")(x)
        with jax.named_scope("head"):
            logits = jnp.dot(x, table.astype(dtype).T,
                             preferred_element_type=jnp.float32) \
                / c.logits_scaling
        return {
            "logits": logits, "moe": {}, "stream": stream,
            "ssm": jax.tree.map(lambda *v: jnp.stack(v), *scanned)
            if scanned else {},
        }
