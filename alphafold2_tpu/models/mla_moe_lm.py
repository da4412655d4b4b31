"""Decoder-only language model: latent attention (MLA) and sigmoid-routed
experts beside shared ones, as one chip's share of an expert-parallel job.

Written from a DeepSeek-V3-shaped ``config.json`` (``q_lora_rank`` null,
``topk_method`` noaux_tc with one group). Pre-norm blocks ``h = x +
Attn(RMSNorm(x))``, ``x' = h + Mlp(RMSNorm(h))``, no biases; the first
``first_k_dense`` layers carry a dense SwiGLU, every later one the expert
layer ``Shared(x) + sum_e w_e Expert_e(x)`` over the experts held here
(``ops/moe.py``); a final RMSNorm and an untied head over the vocabulary
rows held here. Weights float32, compute ``dtype``; RMSNorm statistics, the
router, softmax statistics and the loss are float32 whatever ``dtype`` is.

Every module is a named scope in the compiled step (``layer_N/mla_attn/
{q_proj,kv_down,kv_up,rope,core,o_proj}``, ``layer_N/dense_ffn``,
``layer_N/moe/{router,dispatch,experts,combine,shared}``, ``embed``,
``final_norm``, ``head``): a profiler trace is reduced by these names.
"""

from __future__ import annotations

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from alphafold2_tpu.config import LMConfig
from alphafold2_tpu.ops import mla, moe


def fan_in_normal(fan_axis: int = 0):
    """Normal, variance 1 / fan-in (``shape[fan_axis]``)."""

    def init(key, shape, dtype=jnp.float32):
        return jax.random.normal(key, shape, dtype) * shape[fan_axis] ** -0.5

    return init


def _dense(features: int, dtype, name: str) -> nn.Dense:
    return nn.Dense(features, use_bias=False, dtype=dtype,
                    kernel_init=fan_in_normal(), name=name)


class ScaledDense(nn.Module):
    """``x @ (kernel * scale)``, no bias: the scale goes into the float32
    weights before their cast to ``dtype``, so the product is born scaled
    and is rounded to ``dtype`` once, as an unscaled one is."""

    features: int
    scale: float
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel", fan_in_normal(), (x.shape[-1], self.features))
        return jnp.dot(x.astype(self.dtype),
                       (kernel * self.scale).astype(self.dtype))


class RMSNorm(nn.Module):
    eps: float = 1e-6
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        return (x32 * jax.lax.rsqrt(var + self.eps) * scale).astype(self.dtype)


class SwiGLU(nn.Module):
    width: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        gate = _dense(self.width, self.dtype, "gate_proj")(x)
        up = _dense(self.width, self.dtype, "up_proj")(x)
        act = jax.nn.silu(gate.astype(jnp.float32)).astype(self.dtype) * up
        return _dense(x.shape[-1], self.dtype, "down_proj")(act)


class MLAttention(nn.Module):
    cfg: LMConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        b, s, _ = x.shape
        heads, nope, rope, dv = (c.num_heads, c.qk_nope_head_dim,
                                 c.qk_rope_head_dim, c.v_head_dim)
        # the softmax scale rides on q from its projection on: the core's
        # kernel takes none, and scaling bf16 queries would round them twice
        q = ScaledDense(heads * (nope + rope), (nope + rope) ** -0.5,
                        self.dtype, name="q_proj")(x)
        q = q.reshape(b, s, heads, nope + rope)
        latent = _dense(c.kv_lora_rank + rope, self.dtype, "kv_down")(x)
        kv = RMSNorm(c.rms_norm_eps, self.dtype, name="kv_norm")(
            latent[..., :c.kv_lora_rank])
        kv = _dense(heads * (nope + dv), self.dtype, "kv_up")(kv)
        kv = kv.reshape(b, s, heads, nope + dv)
        with jax.named_scope("rope"):
            positions = jnp.arange(s)
            q_rope = mla.rotary_interleaved(
                q[..., nope:], positions, c.rope_theta)
            # one rotary key head, shared by every query head
            k_rope = mla.rotary_interleaved(
                latent[..., None, c.kv_lora_rank:], positions, c.rope_theta)
            q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
            k = jnp.concatenate(
                [kv[..., :nope],
                 jnp.broadcast_to(k_rope, (b, s, heads, rope))], axis=-1)
        with jax.named_scope("core"):
            out = mla.causal_core(
                q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                kv[..., nope:].transpose(0, 2, 1, 3))
            out = out.transpose(0, 2, 1, 3).reshape(b, s, heads * dv)
        return _dense(x.shape[-1], self.dtype, "o_proj")(out)


class ExpertLayer(nn.Module):
    """``Shared(x) + sum_e w_e Expert_e(x)`` over the experts held here.
    Returns (output, the layer's routing counters)."""

    cfg: LMConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        b, s, d = x.shape
        held, width = c.experts_held, c.moe_intermediate_size
        tokens = x.reshape(b * s, d)
        with jax.named_scope("router"):
            w_router = self.param(
                "router", fan_in_normal(), (d, c.n_routed_experts))
            # e_score_correction_bias: a buffer, it picks experts and takes
            # no gradient (route() stops it); its update rule is not part of
            # the published configuration, so it stays at zero
            bias = self.param(
                "router_bias", nn.initializers.zeros, (c.n_routed_experts,))
            experts, weights = moe.route(
                tokens, w_router, bias, c.num_experts_per_tok,
                c.routed_scaling_factor)
        w_gate = self.param("w_gate", fan_in_normal(1), (held, d, width))
        w_up = self.param("w_up", fan_in_normal(1), (held, d, width))
        w_down = self.param("w_down", fan_in_normal(1), (held, width, d))
        routed, plan = moe.held_experts_sum(
            tokens, experts, weights, w_gate, w_up, w_down, c.first_expert,
            c.n_routed_experts, self.dtype)
        routed = routed.reshape(b, s, d)
        shared = SwiGLU(c.n_shared_experts * width, self.dtype,
                        name="shared")(x)
        return shared + routed, moe.load_counters(plan)


class Block(nn.Module):
    cfg: LMConfig
    dense: bool
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        norm = functools.partial(RMSNorm, c.rms_norm_eps, self.dtype)
        h = x + MLAttention(c, self.dtype, name="mla_attn")(
            norm(name="attn_norm")(x))
        y = norm(name="ffn_norm")(h)
        if self.dense:
            return h + SwiGLU(c.intermediate_size, self.dtype,
                              name="dense_ffn")(y), {}
        out, counters = ExpertLayer(c, self.dtype, name="moe")(y)
        return h + out, counters


class Head(nn.Module):
    """The untied output head: float32 logits over the rows held here."""

    vocab: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel", fan_in_normal(), (x.shape[-1], self.vocab))
        return jnp.dot(x.astype(self.dtype), kernel.astype(self.dtype),
                       preferred_element_type=jnp.float32)


class MlaMoeLM(nn.Module):
    cfg: LMConfig

    @nn.compact
    def __call__(self, tokens):
        """tokens (B, S) int32 -> {"logits" (B, S, vocab) float32, "moe":
        the routing counters, each stacked over the expert layers}."""
        c = self.cfg
        block = remat_layer(Block)
        return decoder_stack(
            tokens, c, lambda i, dtype: block(
                c, i < c.first_k_dense, dtype, name=f"layer_{i}"))


def remat_layer(block):
    """``block`` under ``nn.remat``: the backward pass recomputes a layer
    from its input (at 16,384 tokens one layer's activations are 2.6 GB)
    and keeps across that only what the causal core's kernel names, its
    output and log-sum-exp (0.14 GB a layer), so the recomputation runs
    every operation of the layer but the attention kernel."""
    return nn.remat(
        block, policy=jax.checkpoint_policies.save_only_these_names(
            mla.CORE_RESIDUALS))


def decoder_stack(tokens, cfg, layer):
    """Embedding, ``cfg.num_layers`` blocks, final RMSNorm and untied head,
    made inside the calling model's ``__call__`` (their parameters are the
    model's). ``layer(i, dtype)`` is block ``i``, a module that maps the
    stream to (stream, its routing counters or {}); ``cfg`` gives
    ``vocab_size``, ``hidden_size``, ``num_layers``, ``rms_norm_eps`` and
    ``bfloat16``."""
    dtype = jnp.bfloat16 if cfg.bfloat16 else jnp.float32
    x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=dtype,
                 embedding_init=fan_in_normal(1), name="embed")(tokens)
    counters = []
    for i in range(cfg.num_layers):
        x, counted = layer(i, dtype)(x)
        if counted:
            counters.append(counted)
    x = RMSNorm(cfg.rms_norm_eps, dtype, name="final_norm")(x)
    logits = Head(cfg.vocab_size, dtype, name="head")(x)
    return {
        "logits": logits,
        "moe": jax.tree.map(lambda *v: jnp.stack(v), *counters)
        if counters else {},
    }


def next_token_cross_entropy(logits, tokens):
    """Mean over positions 0..S-2 of -log softmax(logits[i])[tokens[i+1]],
    float32. The last position has no next token and is weighed 0 (a roll
    and a weight, so the (B, S, vocab) logits are not sliced into a copy)."""
    logits = logits.astype(jnp.float32)
    targets = jnp.roll(tokens, -1, axis=1)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    weight = (jnp.arange(tokens.shape[1]) < tokens.shape[1] - 1).astype(
        jnp.float32)
    return jnp.sum((lse - picked) * weight) / (
        tokens.shape[0] * (tokens.shape[1] - 1))


# ------------------------------------- what train.loop makes a Task of ---


def forward(model: nn.Module, params, batch: dict, rng=None):
    del rng  # no dropout
    return model.apply(params, batch["tokens"])


def loss(outputs: dict, batch: dict):
    with jax.named_scope("loss"):
        return next_token_cross_entropy(outputs["logits"], batch["tokens"])


def step_metrics(outputs: dict) -> dict:
    """The model's counters (every group beside the logits: ``moe``, the
    routing's, with a leading axis over the expert layers; a hybrid model's
    ``ssm``), carried beside the loss so that they cost no fetch of their
    own."""
    return {f"{group}/{k}": v for group, counters in outputs.items()
            if group != "logits" for k, v in counters.items()}


def init(model: nn.Module, rng, batch: dict):
    return model.init(rng, jnp.asarray(batch["tokens"]))


def tiny_batch(sample_batch: dict, n: int = 16) -> dict:
    """Parameter shapes do not depend on batch or length: init at 1 x n."""
    return {"tokens": np.asarray(sample_batch["tokens"])[:1, :n]}
