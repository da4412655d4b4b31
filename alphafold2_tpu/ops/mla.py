"""The pieces of latent attention (MLA) that are not projections: rotary
positions on interleaved pairs, and the causal core whose query/key heads
are wider than its value heads (192 = 128 + 64 rotary against 128).

Training uses the unabsorbed form: keys and values are expanded from the
latent and attended as ordinary heads. The absorbed form (scores against
the latent itself) is for decoding through a cache, which this tree does
not have.

The core runs at the published head sizes: on a TPU it is the splash kernel
shipped with JAX (``jax.experimental.pallas.ops.tpu.splash_attention``),
which takes a value head narrower than the query/key head, schedules only
the blocks a causal mask leaves and keeps its softmax statistics at (heads,
positions). Nothing is padded to a common head size (PERF.md section 6,
PR 31, has the A/B against the stock flash kernel at 256).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


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


def causal_kernel_takes(length: int) -> bool:
    """Whether the splash kernel serves a causal call of this length: on a
    TPU from one 128 block up. Below that the logits are a few KB a head and
    the dense path is the faster one; off the TPU the kernel could only be
    interpreted."""
    return jax.default_backend() == "tpu" and length >= 128


def _block(n: int, cap: int) -> int:
    """The largest multiple of 128 that divides ``n`` and is at most ``cap``
    (128 itself where nothing larger does, or the cap is under it)."""
    units = n // 128
    return 128 * max(u for u in range(1, max(cap // 128, 1) + 1)
                     if units % u == 0)


# The fused backward writes one partial dq a key block, in q's type, and
# sums them afterwards: (length / block, heads, length, d_qk), 0.8 GB a
# sequence at the language-model cell's 32 heads x 8,192 x 192 in bf16. A
# call whose partials would pass this many bytes a sequence takes the
# two-kernel backward, which holds nothing of the kind.
PARTIAL_DQ_BYTES = 2**30


def splash_block_sizes(heads: int, length: int, d_qk: int, d_v: int, dtype):
    """The splash kernel's ``BlockSizes`` for one causal call, from its shape
    alone. ``length`` is the padded length (a multiple of 128).

    Every kernel takes square blocks, the largest 128-multiple under the cap
    that divides the length (any padded length gets a valid set; one that
    only 128 divides keeps 128): a block that touches the diagonal is
    computed whole and one above it skipped, so at 8,192 positions squares
    of 1,024 compute 12.5% over the causal half. The cap is 1,024, where the
    on-chip sweep at 2 x 32 heads x 8,192 x 192/128 was fastest and past
    which no kernel compiles (2,048 on either axis runs out of scoped VMEM),
    and less where a tile of 1,024 rows would pass 512 KiB (wider heads,
    float32 operands). The logits are formed 256 keys at a time in the
    forward and 512 in the backward (PERF.md section 6, PR 31, has the sweep).

    The backward is the fused kernel, which forms the logits once for dq, dk
    and dv where the two-kernel form forms them twice (36 against 43 ms at
    the cell's shape), as long as its partial dq stays within
    ``PARTIAL_DQ_BYTES`` a sequence.
    """
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
    )

    itemsize = jnp.dtype(dtype).itemsize
    side = _block(length, min(1024, 2**19 // (max(d_qk, d_v) * itemsize)))
    backward = dict(block_q_dkv=side, block_kv_dkv=side,
                    block_kv_dkv_compute=_block(side, 512))
    if length // side * heads * length * d_qk * itemsize <= PARTIAL_DQ_BYTES:
        backward["use_fused_bwd_kernel"] = True
    else:
        backward.update(block_q_dq=side, block_kv_dq=side)
    return sk.BlockSizes(block_q=side, block_kv=side,
                         block_kv_compute=_block(side, 256), **backward)


@functools.lru_cache(maxsize=16)
def _causal_kernel(heads: int, length: int, d_qk: int, d_v: int,
                   dtype_name: str, interpret: bool):
    """The splash kernel of one (heads, length, head sizes, dtype): the
    causal mask's block schedule is host-side work at trace time, done once
    for a model's layers and their recomputation."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
        splash_attention_mask as sm,
    )

    mask = sm.MultiHeadMask([sm.CausalMask((length, length))] * heads)
    # built under a trace or not, the schedule's arrays are constants
    with jax.ensure_compile_time_eval():
        return sk.make_splash_mha(
            mask, head_shards=1, q_seq_shards=1, interpret=interpret,
            block_sizes=splash_block_sizes(
                heads, length, d_qk, d_v, jnp.dtype(dtype_name)))


def causal_core(q, k, v):
    """softmax(q k^T, keys 0..i for query i) v. ``q``, ``k`` (B, H, S, Dqk),
    ``v`` (B, H, S, Dv); ``q`` carries the softmax scale (the kernel has
    none, and scaling bf16 queries here would round them a second time: the
    model folds it into the query projection's weights before their cast).

    On a TPU from 128 positions up this is the splash kernel at the heads'
    own sizes (a shape it refuses is an error: 32 heads of 8,192^2 float32
    logits are 8.6 GB a sequence, there is no dense run to fall back to);
    elsewhere and below 128 positions, dense ``jnp`` with float32 logits and
    softmax.

    A length that is no multiple of 128 is zero-padded at the end and the
    output sliced: under a causal mask no query sees a later key, so the
    padding reaches no kept row and needs no segment ids.
    """
    _, h, s, d_qk = q.shape
    d_v = v.shape[-1]
    if not causal_kernel_takes(s):
        logits = jnp.einsum(
            "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32)
        keep = jnp.tril(jnp.ones((s, s), bool))
        p = jax.nn.softmax(jnp.where(keep, logits, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)
    pad = (-s) % 128
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
                   for x in (q, k, v))
    kernel = _causal_kernel(h, s + pad, d_qk, d_v, q.dtype.name,
                            interpret=jax.default_backend() != "tpu")
    out = jax.vmap(kernel)(q, k, v)
    return out[:, :, :s] if pad else out
