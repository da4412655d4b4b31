"""The pieces of latent attention (MLA) that are not projections: rotary
positions on interleaved pairs, and the causal core whose query/key heads
are wider than its value heads (192 = 128 + 64 rotary against 128).

Training uses the unabsorbed form: keys and values are expanded from the
latent and attended as ordinary heads. The absorbed form (scores against
the latent itself) is for decoding through a cache, which this tree does
not have.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from alphafold2_tpu.ops import flash


def rotary_interleaved(x, positions, theta: float):
    """Turn the pairs (2i, 2i+1) of the last axis by ``positions *
    theta**(-2i / width)``. ``x`` (..., S, H, width), ``positions`` (S,).
    Float32 inside, ``x``'s type out."""
    width = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, width, 2, dtype=jnp.float32) / width)
    angle = positions.astype(jnp.float32)[:, None, None] * inv_freq  # S,1,w/2
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], width // 2, 2)
    even, odd = pairs[..., 0], pairs[..., 1]
    turned = jnp.stack(
        [even * cos - odd * sin, even * sin + odd * cos], axis=-1)
    return turned.reshape(x.shape).astype(x.dtype)


def causal_core(q, k, v, sm_scale: float):
    """softmax(q k^T * sm_scale, keys 0..i for query i) v. ``q``, ``k``
    (B, H, S, Dqk), ``v`` (B, H, S, Dv). On a TPU from 128 positions up this
    is the flash kernel through ``ops/flash.py`` (a shape it refuses is an
    error: 32 heads of 8,192^2 float32 logits are 8.6 GB a sequence, there is
    no dense run to fall back to); elsewhere and below 128 positions, dense
    ``jnp`` with float32 logits and softmax."""
    out = flash.flash_attention(q, k, v, sm_scale=sm_scale, causal=True)
    if out is not None:
        return out
    s = q.shape[2]
    logits = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32) * sm_scale
    keep = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(keep, logits, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)
