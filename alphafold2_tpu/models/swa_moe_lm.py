"""Decoder-only language model: grouped-query attention, one global layer
without positions among window layers under rotary ones, and softmax-routed
ReGLU experts chosen before attention, as one chip's share of an
expert-parallel job.

Written from a SmallThinker-shaped ``config.json`` (``sliding_window_layout``
and ``rope_layout`` both ``[0, 1, 1, 1]`` repeated, ``moe_primary_router_
apply_softmax`` and ``norm_topk_prob`` true, no shared expert, no dense
layer). A pre-norm block on the stream ``x``, no biases:

- the router reads ``x`` itself, before attention and without a norm: the
  ``top_k`` largest of ``W_r x``, weights their softmax over the selected;
- ``h = x + W_o Attn(RMSNorm(x))``: 28 query heads over 4 key/value heads of
  128 (query head h reads key/value head h // 7). Layer ``i`` with ``i %
  global_every == 0`` is global: full causal, no positional encoding; every
  other is a window layer: query i sees keys i - window + 1 .. i, and q and
  k are turned by rotary positions over the whole head (pairs (j, j + 64));
- ``x' = h + sum_e w_e W_down,e (relu(W_gate,e y) * W_up,e y)`` with ``y =
  RMSNorm(h)``, over the experts held here (``ops/moe.py``): the experts and
  weights chosen from ``x``, applied to ``y``.

Embedding, final RMSNorm, untied head over the vocabulary rows held here,
the loss and what ``train.loop`` makes a Task of are ``models/mla_moe_lm.py``'s.
Weights float32, compute ``dtype``; RMSNorm statistics, the router, softmax
statistics and the loss are float32 whatever ``dtype`` is.

Every module is a named scope in the compiled step (``layer_N/attn_global/
{q_proj,k_proj,v_proj,core,o_proj}``, ``layer_N/attn_window/{q_proj,k_proj,
v_proj,rope,core,o_proj}``, ``layer_N/moe/{router,dispatch,experts,
combine}``, ``embed``, ``final_norm``, ``head``): a profiler trace is reduced
by these names. The router keeps its place under ``moe`` though it reads the
layer's input: where it runs in the step is the compiler's to schedule.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from alphafold2_tpu.config import SwaLMConfig
from alphafold2_tpu.models.mla_moe_lm import (
    RMSNorm, ScaledDense, _dense, decoder_stack, fan_in_normal, remat_layer,
)
from alphafold2_tpu.ops import mla, moe


class GroupedAttention(nn.Module):
    """``window`` None: a global layer (full causal, no positions); a
    number: a window layer (that many keys a query, rotary positions).
    ``scale`` is the softmax scale, the caller's to give: None is ``head_dim
    ** -0.5``, what this model and ``models/ssm_moe_lm.py`` publish; a model
    whose configuration states one (``models/hybrid_dense_lm.py``:
    ``attention_multiplier`` 1 / 64 at heads of 64, an eighth of the
    default) hands it in. ``cfg`` is any section with ``num_heads``,
    ``num_kv_heads``, ``head_dim`` (and ``rope_theta`` under a window)."""

    cfg: SwaLMConfig
    window: Optional[int]
    dtype: Any = jnp.float32
    scale: Optional[float] = None

    @nn.compact
    def __call__(self, x, positions=None):
        c = self.cfg
        b, s, _ = x.shape
        heads, groups, width = c.num_heads, c.num_kv_heads, c.head_dim
        # the softmax scale rides on q from its projection on, as in MLA
        scale = width ** -0.5 if self.scale is None else self.scale
        q = ScaledDense(heads * width, scale, self.dtype,
                        name="q_proj")(x).reshape(b, s, heads, width)
        k = _dense(groups * width, self.dtype, "k_proj")(x)
        v = _dense(groups * width, self.dtype, "v_proj")(x)
        k, v = (t.reshape(b, s, groups, width) for t in (k, v))
        if self.window is not None:
            with jax.named_scope("rope"):
                if positions is None:
                    positions = jnp.arange(s)
                q = mla.rotary_half_split(q, positions, c.rope_theta)
                k = mla.rotary_half_split(k, positions, c.rope_theta)
        with jax.named_scope("core"):
            out = mla.causal_core(
                q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3), window=self.window)
            out = out.transpose(0, 2, 1, 3).reshape(b, s, heads * width)
        return _dense(x.shape[-1], self.dtype, "o_proj")(out)


class RoutedExperts(nn.Module):
    """``sum_e w_e Expert_e(y)`` over the experts held here, the experts and
    weights chosen from ``route_from``; ``activation`` on the gate (ReLU:
    ReGLU experts, this model's; SiLU: SwiGLU, ``models/dsa_moe_lm.py``'s).
    Returns (output, the layer's routing counters)."""

    cfg: SwaLMConfig
    dtype: Any = jnp.float32
    activation: Any = jax.nn.relu

    @nn.compact
    def __call__(self, y, route_from):
        c = self.cfg
        b, s, d = y.shape
        held, width = c.experts_held, c.moe_intermediate_size
        with jax.named_scope("router"):
            w_router = self.param(
                "router", fan_in_normal(), (d, c.n_routed_experts))
            experts, weights = moe.route_softmax(
                route_from.reshape(b * s, d), w_router, c.num_experts_per_tok)
        w_gate = self.param("w_gate", fan_in_normal(1), (held, d, width))
        w_up = self.param("w_up", fan_in_normal(1), (held, d, width))
        w_down = self.param("w_down", fan_in_normal(1), (held, width, d))
        out, plan = moe.held_experts_sum(
            y.reshape(b * s, d), experts, weights, w_gate, w_up, w_down,
            c.first_expert, c.n_routed_experts, self.dtype, self.activation)
        return out.reshape(b, s, d), moe.load_counters(plan)


class Block(nn.Module):
    cfg: SwaLMConfig
    window: Optional[int]
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        norm = functools.partial(RMSNorm, c.rms_norm_eps, self.dtype)
        kind = "attn_global" if self.window is None else "attn_window"
        h = x + GroupedAttention(c, self.window, self.dtype, name=kind)(
            norm(name="attn_norm")(x))
        out, counters = RoutedExperts(c, self.dtype, name="moe")(
            norm(name="ffn_norm")(h), route_from=x)
        return h + out, counters


class SwaMoeLM(nn.Module):
    cfg: SwaLMConfig

    @nn.compact
    def __call__(self, tokens):
        """tokens (B, S) int32 -> {"logits" (B, S, vocab) float32, "moe":
        the routing counters, each stacked over the layers}."""
        c = self.cfg
        block = remat_layer(Block)
        return decoder_stack(
            tokens, c, lambda i, dtype: block(
                c, None if i % c.global_every == 0 else c.sliding_window,
                dtype, name=f"layer_{i}"))
