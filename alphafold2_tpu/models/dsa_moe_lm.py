"""Decoder-only language model whose every layer attends, for each query,
the keys a learned indexer scores highest (DeepSeek-style sparse
attention), then softmax-routed SwiGLU experts, as one chip's share of an
expert-parallel job.

Written from a Keye-VL-2.0-shaped ``config.json`` (its language model:
Qwen3-MoE widths, ``decoder_sparse_step`` 1 and no ``mlp_only_layers``, so
every layer is an expert layer; ``sa_config`` sets the indexer). A pre-norm
block on the stream ``x``, no biases, ``u = RMSNorm(x)``:

- attention: ``q_h = rot(RMSNorm_head(W_q,h u))``, ``k_g = rot(RMSNorm_head(
  W_k,g u))``, ``v_g = W_v,g u``: 32 query heads over 4 key/value heads of
  128 (query head h reads h // 8), the per-head norm over the head's 128
  (one scale a side, shared by the heads), rotary on the two halves of the
  head at ``rope_theta``; the softmax scale 128^-0.5 rides on q from its
  norm on;
- the indexer reads ``stop_gradient(u)``: ``qI_j = W_qI,j u`` (16 heads of
  64), ``kI = LayerNorm(W_kI u)`` (one head of 64), rotary on the first half
  of each, ``w_j = W_w,j u / sqrt(16 * 64)`` in float32; scores ``I[t, s] =
  sum_j w[t, j] relu(qI[t, j] . kI[s])`` and the ``index_topk`` best keys of
  each query (``ops/dsa.py``);
- ``h = x + W_o [softmax_{s in S_t}(q_h,t . k_g,s) v_g,s]_h``;
- ``x' = h + sum_e w_e W_down,e (silu(W_gate,e y) * W_up,e y)`` with ``y =
  RMSNorm(h)``: the ``top_k`` largest of ``W_r y``, weights their softmax
  over the selected (``norm_topk_prob``), over the experts held here
  (``ops/moe.py``);
- the layer's indexer loss, the mean over queries of ``KL(p_t ||
  softmax_{s in S_t} I[t, s])``, ``p`` the main attention's probability
  summed over the heads and renormalised (no gradient through it).

The training loss is the next-token cross-entropy plus
``index_loss_weight`` times the layers' indexer losses summed: the indexer
reads a stopped stream and ``p`` is stopped, so the main model learns from
the cross-entropy alone and the indexer from its own loss alone.

Embedding, final RMSNorm, untied head over the vocabulary rows held here and
the cross-entropy are ``models/mla_moe_lm.py``'s. Weights float32, compute
``dtype``; RMSNorm and LayerNorm statistics, the router, softmax statistics,
the indexer's weights, scores and selection, and the losses are float32.

Every module is a named scope in the compiled step (``layer_N/attn/{q_proj,
k_proj,v_proj,qk_norm,rope,core,o_proj}``, ``layer_N/attn/indexer/{proj,
scores,select}``, ``layer_N/attn/indexer_loss``, ``layer_N/moe/{router,
dispatch,experts,combine}``, ``embed``, ``final_norm``, ``head``, ``loss``).
A layer's recomputation in the backward pass keeps the packed selection and
the core's output and log-sum-exp (``remat_layer``'s names and
``ops/dsa.py`` ``SELECTION``): no selection is made twice.
"""

from __future__ import annotations

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from alphafold2_tpu.config import DsaLMConfig
from alphafold2_tpu.models import mla_moe_lm as lm
from alphafold2_tpu.models.mla_moe_lm import (
    RMSNorm, _dense, decoder_stack, fan_in_normal, remat_layer,
)
from alphafold2_tpu.models.swa_moe_lm import RoutedExperts
from alphafold2_tpu.ops import dsa, mla, moe

LAYER_NORM_EPS = 1e-6  # the indexer's key norm (DeepSeek-V3.2's LayerNorm)


class HeadRMSNorm(nn.Module):
    """RMSNorm over a head's last axis, one scale shared by the heads, the
    result times ``factor`` (the softmax scale, on q) before the cast."""

    eps: float
    dtype: Any = jnp.float32
    factor: float = 1.0

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        return (x32 * jax.lax.rsqrt(var + self.eps)
                * (scale * self.factor)).astype(self.dtype)


class LayerNorm(nn.Module):
    """LayerNorm with a scale and a bias, float32 statistics."""

    eps: float = LAYER_NORM_EPS
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        width = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (width,))
        bias = self.param("bias", nn.initializers.zeros, (width,))
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
        return ((x32 - mean) * jax.lax.rsqrt(var + self.eps) * scale
                + bias).astype(self.dtype)


def rotary_first_half(x, positions, theta: float):
    """Rotary positions (half-split pairs) on the first half of the last
    axis, the rest as it is: the indexer's heads."""
    rot = x.shape[-1] // 2
    return jnp.concatenate(
        [mla.rotary_half_split(x[..., :rot], positions, theta),
         x[..., rot:]], axis=-1)


class Indexer(nn.Module):
    """The indexer's projections and the selection they make. ``u`` is the
    stopped stream. Returns (qI (B, S, J, Di), kI (B, S, Di), w (B, S, J)
    float32, the packed selection, selected keys summed over the queries,
    whether the selection's threshold came from the Pallas kernel)."""

    cfg: DsaLMConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u):
        c = self.cfg
        b, s, d = u.shape
        heads, width = c.index_num_heads, c.index_head_dim
        positions = jnp.arange(s)
        with jax.named_scope("proj"):
            q_i = _dense(heads * width, self.dtype, "q_proj")(u).reshape(
                b, s, heads, width)
            k_i = LayerNorm(dtype=self.dtype, name="k_norm")(
                _dense(width, self.dtype, "k_proj")(u))
            kernel = self.param("weights_proj", fan_in_normal(), (d, heads))
            w = moe.router_logits(u.reshape(b * s, d), kernel).reshape(
                b, s, heads) * (heads * width) ** -0.5
            q_i = rotary_first_half(q_i, positions, c.rope_theta)
            k_i = rotary_first_half(k_i[:, :, None], positions,
                                    c.rope_theta)[:, :, 0]
        packed, selected, in_kernel = dsa.selection(
            q_i, k_i, w, c.index_topk, c.index_chunk)
        return q_i, k_i, w, packed, selected, in_kernel


class SparseAttention(nn.Module):
    """Returns (the attention's output, the layer's counters of the sparse
    attention: ``keys_selected_mean``, ``indexer_kl`` (the indexer's loss,
    which carries its gradient), ``core_in_kernel``, ``select_in_kernel``,
    ``tiles_visited_pct``, ``selection_sample`` (``ops/dsa.py``
    ``selection_sample``: the words of the packed selection that the
    benchmark sets beside its reference's))."""

    cfg: DsaLMConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u):
        c = self.cfg
        b, s, _ = u.shape
        heads, groups, width = c.num_heads, c.num_kv_heads, c.head_dim
        if s % dsa.dsa_core.BLOCK_Q:
            raise ValueError(
                f"sparse attention takes lengths in whole blocks of "
                f"{dsa.dsa_core.BLOCK_Q} positions, got {s}")
        q = _dense(heads * width, self.dtype, "q_proj")(u).reshape(
            b, s, heads, width)
        k = _dense(groups * width, self.dtype, "k_proj")(u).reshape(
            b, s, groups, width)
        v = _dense(groups * width, self.dtype, "v_proj")(u).reshape(
            b, s, groups, width)
        with jax.named_scope("qk_norm"):
            q = HeadRMSNorm(c.rms_norm_eps, self.dtype, width ** -0.5,
                            name="q_norm")(q)
            k = HeadRMSNorm(c.rms_norm_eps, self.dtype, name="k_norm")(k)
        with jax.named_scope("rope"):
            positions = jnp.arange(s)
            q = mla.rotary_half_split(q, positions, c.rope_theta)
            k = mla.rotary_half_split(k, positions, c.rope_theta)
        q_i, k_i, w, packed, selected, select_in_kernel = Indexer(
            c, self.dtype, name="indexer")(jax.lax.stop_gradient(u))
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        with jax.named_scope("core"):
            out, lse, in_kernel = dsa.sparse_core(q, k, v, packed)
            out = out.transpose(0, 2, 1, 3).reshape(b, s, heads * width)
        with jax.named_scope("indexer_loss"):
            kl = dsa.indexer_loss(q_i, k_i, w, q, k, lse, packed)
        counters = {
            "keys_selected_mean": selected.astype(jnp.float32) / (b * s),
            "indexer_kl": kl,
            "core_in_kernel": jnp.float32(in_kernel),
            "select_in_kernel": jnp.float32(select_in_kernel),
            "tiles_visited_pct": dsa.tiles_visited_pct(packed),
            "selection_sample": dsa.selection_sample(packed),
        }
        return _dense(u.shape[-1], self.dtype, "o_proj")(out), counters


class Block(nn.Module):
    """Returns (the stream, (the routing counters, the sparse attention's
    counters))."""

    cfg: DsaLMConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        norm = functools.partial(RMSNorm, c.rms_norm_eps, self.dtype)
        out, attended = SparseAttention(c, self.dtype, name="attn")(
            norm(name="attn_norm")(x))
        h = x + out
        y = norm(name="ffn_norm")(h)
        out, routing = RoutedExperts(c, self.dtype, jax.nn.silu,
                                     name="moe")(y, route_from=y)
        return h + out, (routing, attended)


class DsaMoeLM(nn.Module):
    cfg: DsaLMConfig

    @nn.compact
    def __call__(self, tokens):
        """tokens (B, S) int32, S a multiple of 256 -> {"logits" (B, S,
        vocab) float32, "moe": the routing counters, "dsa": the sparse
        attention's counters, each stacked over the layers}."""
        c = self.cfg
        block = remat_layer(Block, (mla.CORE_RESIDUALS, dsa.SELECTION))
        attended = []

        def layer(i, dtype):
            run = block(c, dtype, name=f"layer_{i}")

            def both(x):
                x, (routing, sparse) = run(x)
                attended.append(sparse)
                return x, routing

            return both

        out = decoder_stack(tokens, c, layer)
        out["dsa"] = jax.tree.map(lambda *v: jnp.stack(v), *attended)
        return out


# ------------------------------------- what train.loop makes a Task of ---


def loss(outputs: dict, batch: dict, weight: float):
    """The next-token cross-entropy plus ``weight`` times the indexer
    losses summed over the layers."""
    ce = lm.loss(outputs, batch)
    with jax.named_scope("loss"):
        return ce + weight * jnp.sum(outputs["dsa"]["indexer_kl"])


def tiny_batch(sample_batch: dict) -> dict:
    """Parameter shapes do not depend on batch or length: init at 1 x 256,
    one block of the selection's packing."""
    return lm.tiny_batch(sample_batch, dsa.dsa_core.BLOCK_Q)
