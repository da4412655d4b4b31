"""Decoder-only hybrid language model: Mamba-2 state-space layers,
sigmoid-routed ungated relu^2 experts beside one shared expert, and a
positionless grouped-query attention layer now and then, as one chip's share
of an expert-parallel job.

Written from a Nemotron-H-shaped ``config.json`` (``model_type`` nemotron_h,
``hybrid_override_pattern`` such as ``MEMEM*EME...``). **A layer is one norm
and one mixer**, ``x' = x + Mixer(RMSNorm(x))``, the mixer's kind read from
the pattern's character for that layer; there is no attention-then-
feed-forward pair. No biases but the convolution's.

- ``M``, the state-space mixer: ``[z, c, dt] = W_in u``; ``c`` through a
  causal depthwise convolution of ``conv_kernel`` taps and SiLU, then split
  into ``x`` (heads x head width), ``B`` and ``C`` (groups x state rows);
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)`` a head; the
  recurrence ``H_t = exp(dt_t A) H_{t-1} + dt_t B_t (x) x_t``, ``y_t = C_t^T
  H_t + D x_t`` as the chunked scan of ``ops/ssm.py``; ``g = y * silu(z)``
  (the gate before the norm), a root-mean-square norm over each group's
  channels, ``W_out``.
- ``E``, the expert layer: the sigmoid router of ``ops/moe.py`` ``route``
  over all ``n_routed_experts``, ``sum_e w_e W_down,e relu^2(W_up,e y)`` over
  the experts held here (two matrices an expert: ungated), plus one shared
  ``W_down relu^2(W_up y)`` every chip computes alike.
- ``*``, the attention layer: ``models/swa_moe_lm.py``'s ``GroupedAttention``
  as its global layer runs it (full causal, no positional encoding), at 32
  query heads over 2 key/value heads.

Embedding, final RMSNorm, untied head over the vocabulary rows held here,
the loss and what ``train.loop`` makes a Task of are
``models/mla_moe_lm.py``'s.
Weights float32, compute ``dtype``; norm statistics, the router, ``dt``, the
decays' cumulative sums and exponentials, the carried state, softmax
statistics and the loss are float32 whatever ``dtype`` is.

``ExpertLayer`` of ``mla_moe_lm.py`` is not adapted: its body is the router's
two leaves, three stacked leaves and a shared SwiGLU, and of those only the
router is this layer's. ``UngatedExperts`` composes ``moe.route`` and
``moe.held_experts_sum`` (``w_gate=None``) itself, so the other model's layer
lowers as it did.

Every module is a named scope in the compiled step (``layer_N/norm``,
``layer_N/ssm/{in_proj,conv,scan,gate_norm,out_proj}``, ``layer_N/
attn_global/{q_proj,k_proj,v_proj,core,o_proj}``, ``layer_N/moe/{router,
dispatch,experts,combine,shared}``, ``embed``, ``final_norm``, ``head``): a
profiler trace is reduced by these names. ``scan`` holds ``dt``, the decays,
the chunked products, the recurrence over the chunks and the ``D`` term.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from alphafold2_tpu.config import SsmLMConfig
from alphafold2_tpu.models.mla_moe_lm import (
    RMSNorm, _dense, decoder_stack, fan_in_normal, remat_layer,
)
from alphafold2_tpu.models.swa_moe_lm import GroupedAttention
from alphafold2_tpu.ops import moe, ssm

MIXERS = {"M": "ssm", "E": "moe", "*": "attn_global"}


def relu2(a):
    return jnp.square(jax.nn.relu(a))


def layer_kinds(cfg, mixers=MIXERS) -> str:
    """The pattern's first ``num_layers`` characters, one a layer, each a
    key of ``mixers``."""
    kinds = cfg.layer_pattern[:cfg.num_layers]
    if len(kinds) < cfg.num_layers or set(kinds) - set(mixers):
        raise ValueError(
            f"layer_pattern {cfg.layer_pattern!r} does not name "
            f"{cfg.num_layers} layers out of {sorted(mixers)}")
    return kinds


# ------------------------------------------------ published initialisers ---


def uniform_between(low: float, high: float):
    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, low, high)

    return init


def a_log_init(key, shape, dtype=jnp.float32):
    """``log a``, ``a`` uniform on [1, 16]."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def dt_bias_init(cfg: SsmLMConfig):
    """``softplus^-1(dt)``, ``dt`` log-uniform on [time_step_min,
    time_step_max] and floored at time_step_floor."""

    def init(key, shape, dtype=jnp.float32):
        dt = jnp.exp(jax.random.uniform(
            key, shape, dtype, math.log(cfg.time_step_min),
            math.log(cfg.time_step_max)))
        dt = jnp.maximum(dt, cfg.time_step_floor)
        return dt + jnp.log(-jnp.expm1(-dt))

    return init


# ------------------------------------------------------------ the mixers ---


class CausalConv(nn.Module):
    """``ops/ssm.py`` ``causal_conv`` with its leaves: ``kernel`` (channels,
    taps), uniform +-1/sqrt(taps) as a depthwise ``Conv1d``'s, and ``bias``."""

    taps: int
    channels: int
    start: int = 0

    @nn.compact
    def __call__(self, x):
        bound = self.taps ** -0.5
        kernel = self.param("kernel", uniform_between(-bound, bound),
                            (self.channels, self.taps))
        bias = self.param("bias", uniform_between(-bound, bound),
                          (self.channels,))
        return ssm.causal_conv(x, kernel, bias, self.start)


class GatedGroupRMSNorm(nn.Module):
    """``g = y * silu(z)`` (the gate before the norm), then ``scale * g /
    sqrt(mean over the group's channels of g^2 + eps)``, all in float32."""

    groups: int
    eps: float
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, y, z):
        scale = self.param("scale", nn.initializers.ones, (y.shape[-1],))
        g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        grouped = g.reshape(*g.shape[:-1], self.groups, -1)
        var = jnp.mean(jnp.square(grouped), axis=-1, keepdims=True)
        normed = (grouped * jax.lax.rsqrt(var + self.eps)).reshape(g.shape)
        return (normed * scale).astype(self.dtype)


class Mamba2Mixer(nn.Module):
    """Returns (output, the layer's counters: the smallest and the mean
    decay of a whole chunk, ``exp(sum_chunk dt A)``, over heads and chunks
    (how little of a state survives one chunk: where even the mean is 0 the
    carried term is dead and the recurrence over chunks does no work), the
    mean time step, and ``scan_in_kernel`` and ``conv_in_kernel``: 1.0
    where the scan, or the convolution, ran through its Pallas kernels, 0.0
    where it fell back to the XLA form)."""

    cfg: SsmLMConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u):
        c = self.cfg
        b, s, d = u.shape
        heads, width = c.mamba_num_heads, c.mamba_head_dim
        groups, n = c.ssm_groups, c.ssm_state_size
        inner = heads * width
        proj = _dense(2 * inner + 2 * groups * n + heads, self.dtype,
                      "in_proj")(u)
        conv_channels = inner + 2 * groups * n
        z, dt = proj[..., :inner], proj[..., inner + conv_channels:]
        # the convolution reads its channels of the projection where they
        # lie: a kernel takes no sliced copy of them
        conv_out = CausalConv(c.conv_kernel, conv_channels, inner,
                              name="conv")(proj)
        x, b_in, c_in = jnp.split(
            conv_out, [inner, inner + groups * n], axis=-1)
        a_log = self.param("A_log", a_log_init, (heads,))
        dt_bias = self.param("dt_bias", dt_bias_init(c), (heads,))
        skip = self.param("D", nn.initializers.ones, (heads,))
        with jax.named_scope("scan"):
            x = x.reshape(b, s, heads, width)
            dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
            b_in, c_in = (t.reshape(b, s, groups, n) for t in (b_in, c_in))
            y, _, chunk_decay = ssm.ssd_scan(
                x, dt, -jnp.exp(a_log), b_in, c_in, c.chunk_size, self.dtype)
            y = y + skip[:, None] * x.astype(jnp.float32)
            counters = {"chunk_decay_min": chunk_decay.min(),
                        "chunk_decay_mean": chunk_decay.mean(),
                        "dt_mean": dt.mean(),
                        "scan_in_kernel": jnp.float32(ssm.scan_kernel_takes(
                            x.shape, b_in.shape, c.chunk_size)),
                        "conv_in_kernel": jnp.float32(ssm.conv_kernel_takes(
                            conv_out.shape, c.conv_kernel, inner))}
        normed = GatedGroupRMSNorm(
            groups, c.rms_norm_eps, self.dtype, name="gate_norm")(
            y.reshape(b, s, inner), z)
        return _dense(d, self.dtype, "out_proj")(normed), counters


class Relu2Mlp(nn.Module):
    width: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        up = _dense(self.width, self.dtype, "up_proj")(x)
        act = relu2(up.astype(jnp.float32)).astype(self.dtype)
        return _dense(x.shape[-1], self.dtype, "down_proj")(act)


class UngatedExperts(nn.Module):
    """``Shared(y) + sum_e w_e W_down,e relu^2(W_up,e y)`` over the experts
    held here. Returns (output, the layer's routing counters)."""

    cfg: SsmLMConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, y):
        c = self.cfg
        b, s, d = y.shape
        held, width = c.experts_held, c.moe_intermediate_size
        tokens = y.reshape(b * s, d)
        with jax.named_scope("router"):
            w_router = self.param(
                "router", fan_in_normal(), (d, c.n_routed_experts))
            # the correction bias picks experts and takes no gradient; its
            # update rule is not published, so it stays at zero
            bias = self.param(
                "router_bias", nn.initializers.zeros, (c.n_routed_experts,))
            experts, weights = moe.route(
                tokens, w_router, bias, c.num_experts_per_tok,
                c.routed_scaling_factor)
        w_up = self.param("w_up", fan_in_normal(1), (held, d, width))
        w_down = self.param("w_down", fan_in_normal(1), (held, width, d))
        routed, plan = moe.held_experts_sum(
            tokens, experts, weights, None, w_up, w_down, c.first_expert,
            c.n_routed_experts, self.dtype, relu2)
        shared = Relu2Mlp(c.moe_shared_expert_intermediate_size, self.dtype,
                          name="shared")(y)
        return shared + routed.reshape(b, s, d), moe.load_counters(plan)


class Block(nn.Module):
    """One norm and one mixer; ``kind`` is the pattern's character."""

    cfg: SsmLMConfig
    kind: str
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        y = RMSNorm(c.rms_norm_eps, self.dtype, name="norm")(x)
        name = MIXERS[self.kind]
        if self.kind == "M":
            out, counters = Mamba2Mixer(c, self.dtype, name=name)(y)
        elif self.kind == "E":
            out, counters = UngatedExperts(c, self.dtype, name=name)(y)
        else:
            out, counters = GroupedAttention(
                c, None, self.dtype, name=name)(y), {}
        return x + out, counters


class SsmMoeLM(nn.Module):
    cfg: SsmLMConfig

    @nn.compact
    def __call__(self, tokens):
        """tokens (B, S) int32 -> {"logits" (B, S, vocab) float32, "moe": the
        routing counters stacked over the expert layers, "ssm": the scan's
        counters stacked over the state-space layers}."""
        c = self.cfg
        kinds = layer_kinds(c)
        block = remat_layer(Block)
        scanned = []  # the state-space layers' counters, beside the stack's

        def layer(i, dtype):
            run = block(c, kinds[i], dtype, name=f"layer_{i}")
            if kinds[i] != "M":
                return run

            def state_space(x):
                x, counters = run(x)
                scanned.append(counters)
                return x, {}

            return state_space

        out = decoder_stack(tokens, c, layer)
        out["ssm"] = jax.tree.map(lambda *v: jnp.stack(v), *scanned) \
            if scanned else {}
        return out

