"""Fused flash-attention path for the dense axial/cross attention hot loops.

The axial trunk's row/column passes materialize (B*N, H, N, N) logits in the
naive formulation — at crop 384 that dominates HBM traffic. On TPU this
module routes dense attention through the Pallas flash-attention kernels
shipped with JAX (``jax.experimental.pallas.ops.tpu.flash_attention`` —
fused QK^T/softmax/AV with full custom-VJP backward), so the N^2 attention
matrix never hits HBM. Padding masks are expressed as segment ids (valid=1,
pad=0: cross-segment pairs are masked inside the kernel).

Used automatically by :class:`ops.attention.Attention` on TPU backends for
the un-tied paths, including KV-compressed cross-attention (the kernel
sees the already-compressed k/v and the pooled mask). Off-TPU, and for
sequences shorter than one 128 block on both axes, the caller takes the jnp
dense path; a shape the kernel rejects on TPU is an error, never a silent
dense run (65,536 x 4,096 x 8 logits at the flagship cross-attention).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def flash_available() -> bool:
    return jax.default_backend() == "tpu"


def flash_attention(
    q: jnp.ndarray,  # (B, H, Nq, D)
    k: jnp.ndarray,  # (B, H, Nk, D)
    v: jnp.ndarray,
    q_mask: Optional[jnp.ndarray] = None,  # (B, Nq) bool
    kv_mask: Optional[jnp.ndarray] = None,  # (B, Nk) bool
    sm_scale: float = 1.0,
) -> Optional[jnp.ndarray]:
    """Fused attention via the stock Pallas TPU kernel.

    Returns None off-TPU and for short sequences (both axes under one 128
    block) — the caller takes the dense jnp path there by design. Whatever
    the kernel itself refuses propagates.
    """
    if not flash_available():
        return None
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        SegmentIds,
        flash_attention as _fa,
    )

    b, h, nq, d = q.shape
    nk = k.shape[2]

    # short sequences (BOTH axes < one 128 block) use the dense path by
    # design: at these sizes the dense attention matrix is trivially small
    # and the kernel's MIN_BLOCK_SIZE tiling overhead dominates. With one
    # long axis (e.g. N^2 queries against a compressed context) the fused
    # path still pays off — the short axis is padded up to a block below.
    if nq < 128 and nk < 128:
        return None

    # the kernel's block verification requires both sequence axes divisible
    # by the 128-lane block (e.g. compressed-KV cross-attention lengths
    # rarely are): pad with mask-excluded positions and slice the output
    pad_q = (-nq) % 128
    pad_k = (-nk) % 128
    need_segments = (
        q_mask is not None or kv_mask is not None or pad_q or pad_k
    )
    segment_ids = None
    if need_segments:
        qs = (
            q_mask.astype(jnp.int32)
            if q_mask is not None
            else jnp.ones((b, nq), jnp.int32)
        )
        ks = (
            kv_mask.astype(jnp.int32)
            if kv_mask is not None
            else jnp.ones((b, nk), jnp.int32)
        )
        if pad_q:
            qs = jnp.pad(qs, ((0, 0), (0, pad_q)))
        if pad_k:
            ks = jnp.pad(ks, ((0, 0), (0, pad_k)))
        segment_ids = SegmentIds(q=qs, kv=ks)
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    out = _fa(q, k, v, segment_ids=segment_ids, sm_scale=sm_scale)
    return out[:, :, :nq] if pad_q else out
