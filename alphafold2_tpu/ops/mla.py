"""The pieces of the language models' attention that are not projections:
rotary positions (on interleaved pairs, or on the two halves of a head) and
the causal core, which serves latent attention (MLA: query/key heads of 192
= 128 + 64 rotary against value heads of 128, every head with its own keys)
and grouped-query attention (28 query heads over 4 key/value heads, a
sliding window in some layers) alike.

MLA trains in the unabsorbed form: keys and values are expanded from the
latent and attended as ordinary heads. The absorbed form (scores against
the latent itself) is for decoding through a cache, which this tree does
not have.

The core runs at the published head sizes and head counts: on a TPU it is
the splash kernel shipped with JAX
(``jax.experimental.pallas.ops.tpu.splash_attention``), which takes a value
head narrower than the query/key head and fewer key/value heads than query
heads (query head h reads key/value head h // (H / G): nothing is broadcast
to H), schedules only the blocks its mask leaves (the causal half, or the
band of a window) and keeps its softmax statistics at (heads, positions).
Nothing is padded to a common head size (PERF.md section 6, PR 31, has the
A/B against the stock flash kernel at 256; PR 32 the grouped, windowed
calls).
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


def rotary_half_split(x, positions, theta: float):
    """Turn the pairs (i, i + width/2) of the last axis by ``positions *
    theta**(-2i / width)``: the convention of the decoders whose config
    carries no ``rope_interleave``. ``x`` (..., S, H, width), ``positions``
    (S,). Float32 inside, ``x``'s type out."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None, None] * inv_freq  # S,1,w/2
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x32 = x.astype(jnp.float32)
    low, high = x32[..., :half], x32[..., half:]
    return jnp.concatenate(
        [low * cos - high * sin, high * cos + low * sin], axis=-1
    ).astype(x.dtype)


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
# two-kernel backward, which holds nothing of the kind (the grouped-query
# cell's 28 heads x 16,384 x 128: 1.9 GB a layer).
PARTIAL_DQ_BYTES = 2**30

# The name (``jax.ad_checkpoint.checkpoint_name``) the kernel's forward gives
# its two results, the output (B, H, S, Dv) and the log-sum-exp (B, H, S)
# float32: all its backward needs beside q, k and v. A ``jax.checkpoint``
# whose policy saves this name keeps them, and its recomputation runs no
# forward kernel. The dense path names nothing: it has no log-sum-exp.
CORE_RESIDUALS = "causal_core"


def splash_block_sizes(heads: int, length: int, d_qk: int, d_v: int, dtype):
    """The splash kernel's ``BlockSizes`` for one causal call, from its shape
    alone. ``heads`` are the query heads, ``length`` the padded length (a
    multiple of 128). A window does not enter: its band is whole squares of
    the same side plus the two it cuts (at 16,384 positions and a window of
    4,096, 70 of the causal mask's 136 squares of 1,024).

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
                   dtype_name: str, window, interpret: bool):
    """The splash kernel of one (query heads, length, head sizes, dtype,
    window): the mask's block schedule is host-side work at trace time, done
    once for a model's layers of that kind and their recomputation. The
    key/value heads are not part of it: the kernel reads their number off
    the arrays it is called with."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
        splash_attention_mask as sm,
    )

    shape = (length, length)
    one = sm.CausalMask(shape) if window is None else sm.LocalMask(
        shape, window_size=(window - 1, 0), offset=0)
    mask = sm.MultiHeadMask([one] * heads)
    # built under a trace or not, the schedule's arrays are constants
    with jax.ensure_compile_time_eval():
        return sk.make_splash_mha(
            mask, head_shards=1, q_seq_shards=1, interpret=interpret,
            residual_checkpoint_name=CORE_RESIDUALS,
            block_sizes=splash_block_sizes(
                heads, length, d_qk, d_v, jnp.dtype(dtype_name)))


def causal_core(q, k, v, window=None):
    """softmax(q k^T, keys j <= i for query i, and i - j < ``window`` where
    one is given) v. ``q`` (B, H, S, Dqk), ``k`` (B, G, S, Dqk), ``v`` (B, G,
    S, Dv) with G dividing H: query head h reads key/value head h // (H / G),
    and nothing is broadcast to H. ``q`` carries the softmax scale (the
    kernel has none, and scaling bf16 queries here would round them a second
    time: the models fold it into the query projection's weights before
    their cast). A window that covers the sequence is no window.

    On a TPU from 128 positions up this is the splash kernel at the heads'
    own sizes and counts, under the mask the call names (a shape it refuses
    is an error: 32 heads of 8,192^2 float32 logits are 8.6 GB a sequence,
    there is no dense run to fall back to); elsewhere and below 128
    positions, dense ``jnp`` with float32 logits and softmax.

    A length that is no multiple of 128 is zero-padded at the end and the
    output sliced: under either mask no query sees a later key, so the
    padding reaches no kept row and needs no segment ids.
    """
    b, h, s, d_qk = q.shape
    g, d_v = k.shape[1], v.shape[-1]
    if window is not None and window >= s:
        window = None
    if not causal_kernel_takes(s):
        grouped = q.reshape(b, g, h // g, s, d_qk)
        logits = jnp.einsum("bgrqd,bgkd->bgrqk", grouped, k,
                            preferred_element_type=jnp.float32)
        ahead = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
        keep = ahead >= 0 if window is None else (ahead >= 0) & (ahead < window)
        p = jax.nn.softmax(jnp.where(keep, logits, -jnp.inf), axis=-1)
        out = jnp.einsum("bgrqk,bgkd->bgrqd", p.astype(v.dtype), v)
        return out.reshape(b, h, s, d_v)
    pad = (-s) % 128
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
                   for x in (q, k, v))
    kernel = _causal_kernel(h, s + pad, d_qk, d_v, q.dtype.name, window,
                            interpret=jax.default_backend() != "tpu")
    out = jax.vmap(kernel)(q, k, v)
    return out[:, :, :s] if pad else out
