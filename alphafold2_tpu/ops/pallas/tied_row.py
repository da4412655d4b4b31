"""Fused tied-row MSA attention (MSA-Transformer style) as a Pallas kernel.

Tied-row attention shares ONE attention matrix across all R MSA rows:

    dots[b, h, i, j] = sum_r q[b, r, i, h, :] . k[b, r, j, h, :]
    out[b, r, i, h, :] = sum_j softmax(dots)[b, h, i, j] * v[b, r, j, h, :]

The dense path (ops/attention.py tied branch) materializes the full
(B, H, N, N) logits. The fused form rests on an algebraic identity: the
row sum in the logits is a single contraction over a fused (row, head_dim)
feature axis —

    dots[b, h, i, j] = <q'[b, h, i, :], k'[b, h, j, :]>,
    q'[b, h, i, (r, d)] = q[b, r, i, h, d]

— and the output is likewise one P @ V' with V' fused the same way. Tied
attention IS flash attention with head dim R*D, so this module folds the
row axis into the feature axis (two linear relayouts, nothing quadratic)
and runs the shared online-softmax kernels of :mod:`axial` with the tie
scale pre-applied to q. The N^2 logits stay in VMEM; HBM traffic is
O(R * N * D) instead of O(H * N^2).

Masking matches the dense tied path's abstention semantics: the caller
pre-zeroes padded (row, position) q/k/v entries (they abstain from the
shared logit sum exactly), passes the SHARED column mask as ``kv_mask``
(masked columns get NEG_INF bias) and the voting-row count as
``tie_scale`` — a traced per-batch array folded into q before the kernel,
so no scalar plumbing reaches the kernel. Masked queries produce zeros
(flash convention; the dense path gives them uniform attention — padded
rows are downstream-masked everywhere this runs).

VMEM bound: the fused feature axis R*D must fit a (128, R*D) f32 tile 4x
over (q/k/v/acc) — R*D <= ~4096 covers every MSA depth this model admits
(constants.MAX_NUM_MSA rows at dim_head 64 is what a caller could ask
for; the serve/train configs sit far below it).

Gradient support comes through :func:`axial.fused_attention`'s custom VJP;
the fold/unfold relayouts are plain differentiable jnp ops. Oracle-diff
(values and grads, masked + padded + ragged-row cases) in
tests/test_pallas_kernels.py; Mosaic-lowered by analysis/lowering.py.
"""

from __future__ import annotations

from typing import Optional, Union

import jax
import jax.numpy as jnp

from alphafold2_tpu.ops.pallas.axial import fused_attention


def tied_row_available() -> bool:
    """The platform half of :func:`tied_row_takes`, apart from
    ``ops/flash.py``'s: this kernel also runs interpreted off the TPU, which
    is how the CPU tests reach it (they substitute this function)."""
    return jax.default_backend() == "tpu"


def tied_row_takes(needs_probabilities: bool) -> bool:
    """Whether this kernel serves ``Attention``'s tied branch: on a TPU,
    unless the call must materialise its probabilities (active
    attention-weight dropout), which a fused kernel never does. Elsewhere
    the branch keeps its dense einsum."""
    return tied_row_available() and not needs_probabilities


def tied_row_attention(
    q: jnp.ndarray,  # (B, R, Nq, H, D) — padded entries pre-zeroed
    k: jnp.ndarray,  # (B, R, Nk, H, D)
    v: jnp.ndarray,
    q_mask: Optional[jnp.ndarray] = None,  # (B, Nq) SHARED query mask
    kv_mask: Optional[jnp.ndarray] = None,  # (B, Nk) SHARED column mask
    sm_scale: float = 1.0,
    tie_scale: Union[None, float, jnp.ndarray] = None,  # None -> R**-0.5;
    # or a per-batch voting-row scale, any shape broadcastable to (B,)
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Fused tied-row attention; returns (B, R, Nq, H, D).

    Exactly the dense tied contraction of ops/attention.py (one attention
    matrix per (batch, head), r^-0.5-style tie scaling) computed without
    materializing the (B, H, Nq, Nk) logits in HBM."""
    b, r, nq, h, d = q.shape
    if tie_scale is None:
        tie_scale = r**-0.5
    scale = jnp.asarray(tie_scale, jnp.float32).reshape(b, 1, 1, 1, 1) \
        if getattr(tie_scale, "ndim", 0) else jnp.float32(tie_scale)
    # pre-scale q: the kernel runs with sm_scale baked statically, and the
    # (possibly traced, per-batch) tie scale folds in here — mathematically
    # identical since the logits are linear in q
    q = (q.astype(jnp.float32) * scale).astype(q.dtype)

    def fold(t):  # (B, R, N, H, D) -> (B, H, N, R*D)
        n = t.shape[2]
        return jnp.transpose(t, (0, 3, 2, 1, 4)).reshape(b, h, n, r * d)

    out = fused_attention(
        fold(q), fold(k), fold(v),
        q_mask=q_mask, kv_mask=kv_mask, sm_scale=sm_scale,
        interpret=interpret,
    )  # (B, H, Nq, R*D)
    out = out.reshape(b, h, nq, r, d)
    return jnp.transpose(out, (0, 3, 2, 1, 4))  # (B, R, Nq, H, D)
