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

Both sequence axes are padded to the 128 lanes the kernel's verification
asks for, and the kernel's blocks are then chosen from the padded shape by
:func:`block_sizes_for`: the stock default runs every call at 128 x 128,
which at 65,536 x 4,096 is 131,072 grid steps a head-batch and costs ~20x
the arithmetic in them (PERF.md section 6, PR 26, holds the sweep).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def flash_available() -> bool:
    return jax.default_backend() == "tpu"


def flash_takes(nq: int, nk: int, head_dim: int) -> bool:
    """Whether the stock kernel serves a call of ``nq`` queries against
    ``nk`` keys at this head size: the one rule, asked by
    :func:`flash_attention`, by ``Attention`` before it wraps the call for a
    mesh, and by the ring for its local blocks (parallel/seq_parallel.py).

    On a TPU, and not where BOTH axes are under one 128 block: there the
    dense attention matrix is trivially small and the kernel's tiling
    overhead dominates. With one long axis (N^2 queries against a compressed
    context) the kernel still pays off; the short axis is padded up to a
    block. The kernel takes one head size for q, k and v, a multiple of 128
    once it is over 128; nothing here pads heads."""
    if not flash_available() or (nq < 128 and nk < 128):
        return False
    return head_dim <= 128 or head_dim % 128 == 0


def _block(n: int, cap: int, unit: int = 128) -> int:
    """The largest multiple of ``unit`` that divides ``n`` and is at most
    ``cap``; ``unit`` itself where nothing larger divides (1,408 = 11 x 128
    under a cap of 1,024)."""
    units = n // unit
    return unit * max(
        u for u in range(1, max(cap // unit, 1) + 1) if units % u == 0
    )


def block_sizes_for(
    batch: int, heads: int, nq: int, nk: int, head_dim: int, dtype,
):
    """The stock kernel's ``BlockSizes`` for one call, from its shape alone.

    ``nq`` and ``nk`` are the padded lengths (multiples of 128). Each inner
    block is the largest 128-multiple under its cap that divides its axis,
    and each major block the largest run of 4 (dkv keys: 2) or fewer inner
    blocks that still divides it: the kernel unrolls them. So any padded
    length gets a valid set, and an axis that only 128 divides keeps 128.
    The caps are where the on-chip sweep went flat (forward, dq, dkv at
    65,536 x 4,096, its transpose and 256 x 256: within 3% of the best of
    ~250 sets), inside what the scoped-VMEM limit compiles:

    - forward: 512 queries against 4,096 keys a grid step, the logits 1,024
      keys at a time (the running max/sum are 128 lanes wide, so a narrow
      ``block_k`` pays as much for bookkeeping as for logits). Where a
      batch entry is one block, a step takes up to 4 of them within 512 x
      1,024 logits (pair axial passes: hundreds of sequences of 256 or 384).
    - dkv: 1,024 queries (256 at a time) against 2,048 keys (1,024 at a
      time); ``block_q_major`` has to divide ``nq`` here, and does.
    - forward and dkv: a key axis of at most 2,048 is one block, which is
      the forward's single-step body and spares a length like 1,408 =
      11 x 128 (compressed keys) the 128 that is its only other divisor
      (timed at 1,408 and 2,048: within 2% of the best; at 4,096 it loses).
    - dq: 1,024 queries against 512 keys, or fewer keys where the call is
      larger: the stock wrapper materialises ``di`` in HBM at (b, h, nq,
      block_k_major_dq) float32, 1 GiB at 8 heads of 65,536 queries and
      512, which was measured to fit. Above that (crop 384, batch 2) the
      block narrows, down to the default's 128, so no call holds more
      than the larger of 1 GiB and what it held at the default.

    One K or V tile is held to 1 MiB, so wider heads or float32 operands
    shrink the key blocks instead of running out of VMEM.
    """
    from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes

    k_cap = max(128, 2**20 // (head_dim * jnp.dtype(dtype).itemsize))
    block_q = _block(nq, 512)
    if nk <= min(2048, k_cap):  # the key axis whole, whatever divides it
        block_k = block_k_major = block_k_major_dkv = nk
    else:
        block_k = _block(nk, min(1024, k_cap))
        block_k_major = _block(nk, min(4 * block_k, k_cap), block_k)
        block_k_major_dkv = _block(nk, min(2 * block_k, k_cap), block_k)
    block_b = 1
    if block_q == nq and block_k == nk:  # one block a batch entry
        block_b = max((bb for bb in (4, 2) if batch % bb == 0
                       and bb * nq * nk <= 512 * 1024), default=1)
    block_q_dkv = _block(nq, 256)
    di_cap = 2**30 // (batch * heads * nq * 4)  # keys a row of di, in 1 GiB
    block_k_dq = _block(nk, min(512, max(128, di_cap)))
    return BlockSizes(
        block_q=block_q,
        block_k_major=block_k_major,
        block_k=block_k,
        block_b=block_b,
        block_q_major_dkv=_block(nq, 4 * block_q_dkv, block_q_dkv),
        block_k_major_dkv=block_k_major_dkv,
        block_k_dkv=block_k,
        block_q_dkv=block_q_dkv,
        block_k_major_dq=block_k_dq,
        block_k_dq=block_k_dq,
        block_q_dq=_block(nq, 1024),
    )


def flash_attention(
    q: jnp.ndarray,  # (B, H, Nq, D)
    k: jnp.ndarray,  # (B, H, Nk, D)
    v: jnp.ndarray,  # (B, H, Nk, D)
    q_mask: Optional[jnp.ndarray] = None,  # (B, Nq) bool
    kv_mask: Optional[jnp.ndarray] = None,  # (B, Nk) bool
    sm_scale: float = 1.0,
) -> Optional[jnp.ndarray]:
    """Fused attention via the stock Pallas TPU kernel.

    Returns None where :func:`flash_takes` says no (off the TPU, both axes
    under one 128 block): the caller takes the dense jnp path there by
    design. Whatever the kernel itself refuses propagates.
    """
    b, h, nq, d = q.shape
    nk = k.shape[2]
    if not flash_takes(nq, nk, d):
        return None
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        SegmentIds,
        flash_attention as _fa,
    )

    # the kernel's block verification requires both sequence axes divisible
    # by its blocks, and block_sizes_for finds blocks for any multiple of
    # the 128 lanes (e.g. compressed-KV cross-attention lengths rarely are
    # one): pad with mask-excluded positions and slice the output
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
    out = _fa(
        q, k, v, segment_ids=segment_ids, sm_scale=sm_scale,
        block_sizes=block_sizes_for(b, h, nq + pad_q, nk + pad_k, d, q.dtype),
    )
    return out[:, :, :nq] if pad_q else out
