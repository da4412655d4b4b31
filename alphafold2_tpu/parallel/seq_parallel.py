"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference scales sequence length only with single-device memory tricks
(axial factorization, block sparsity, KV compression, reversibility —
SURVEY.md S5.7); it has no multi-device sequence parallelism of any kind
(S2.3). This module is the green-field capability layer: exact attention over
a sequence axis SHARDED across the ``sp`` mesh axis, in two standard flavors:

- :func:`ring_attention` — KV blocks rotate around the ring via
  ``lax.ppermute`` while each device folds them into a flash-style online
  softmax (f32 running max / sum / accumulator). Communication overlaps
  compute, memory per device is O(N/sp), and the result is exactly dense
  attention (not an approximation). ppermute rides neighbor ICI links.
- :func:`ulysses_attention` — ``lax.all_to_all`` re-shards from
  sequence-sharded to head-sharded, runs ordinary dense attention locally
  over the full sequence for H/sp heads, and all-to-alls back. Two
  collectives per call, best when heads % sp == 0 and N/sp is small.

Both are jnp-only (differentiable; XLA emits the collective gradients) and
are written to run inside ``shard_map`` with a named ``sp`` axis.
:func:`sequence_parallel_attention` is the host-level entry: it shard_maps
over an explicit (dp, sp) mesh and reduces to plain dense attention when no
mesh/axis is present, so the same call site works single-chip and on a pod.

This is the ring-attention-adjacent design SURVEY.md S7 lists as the key
novel engineering vs the reference; differential tests against the dense
oracle run on the 8-virtual-device CPU mesh (tests/test_seq_parallel.py).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from alphafold2_tpu.ops.attention import MASK_VALUE

SEQ_AXIS_NAME = "sp"
DATA_AXIS_NAME = "dp"


def _dense(q, k, v, kmask_bias):
    """Local dense attention with additive key bias. (B, H, n, d) x 3."""
    scale = q.shape[-1] ** -0.5
    dots = jnp.einsum("bhid,bhjd->bhij", q, k) * scale
    dots = dots + kmask_bias[:, None, None, :]
    attn = jax.nn.softmax(dots.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhij,bhjd->bhid", attn, v)


def ring_attention(
    q: jnp.ndarray,  # (B, H, n_local, D) — this device's query block
    k: jnp.ndarray,  # (B, H, n_local, D) — this device's KV block
    v: jnp.ndarray,
    kmask_bias: jnp.ndarray,  # (B, n_local) f32 additive bias (0 / MASK_VALUE)
    axis_name: str = SEQ_AXIS_NAME,
) -> jnp.ndarray:
    """Exact attention over the ring-sharded sequence axis.

    Flash-style accumulation: per rotation step, fold the visiting KV block
    into (running_max, running_sum, accumulator); rotate KV one hop with
    ppermute. After ``sp`` steps every query block has seen every key.
    """
    sp = lax.axis_size(axis_name)
    scale = q.shape[-1] ** -0.5
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    b, h, n, d = q.shape
    m0 = jnp.full((b, h, n, 1), MASK_VALUE, jnp.float32)
    l0 = jnp.zeros((b, h, n, 1), jnp.float32)
    acc0 = jnp.zeros((b, h, n, d), jnp.float32)

    def body(carry, _):
        m_prev, l_prev, acc, k_cur, v_cur, bias_cur = carry
        dots = (
            jnp.einsum("bhid,bhjd->bhij", q, k_cur).astype(jnp.float32) * scale
            + bias_cur[:, None, None, :]
        )
        m_new = jnp.maximum(m_prev, jnp.max(dots, axis=-1, keepdims=True))
        p = jnp.exp(dots - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum(
            "bhij,bhjd->bhid", p, v_cur.astype(jnp.float32)
        )
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        b_nxt = lax.ppermute(bias_cur, axis_name, perm)
        return (m_new, l_new, acc_new, k_nxt, v_nxt, b_nxt), None

    # checkpointed: the backward pass recomputes each visiting block's
    # (n_local x n_local) probabilities from q and the rotated k instead of
    # keeping sp of them — at the flagship cross-attention one is 4 GB
    (m, l, acc, _, _, _), _ = lax.scan(
        jax.checkpoint(body), (m0, l0, acc0, k, v, kmask_bias), None,
        length=sp,
    )
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)


def ulysses_attention(
    q: jnp.ndarray,  # (B, H, n_local, D)
    k: jnp.ndarray,
    v: jnp.ndarray,
    kmask_bias: jnp.ndarray,  # (B, n_local)
    axis_name: str = SEQ_AXIS_NAME,
) -> jnp.ndarray:
    """All-to-all sequence parallelism (Ulysses): re-shard seq -> heads,
    attend densely over the full sequence locally, re-shard back."""
    sp = lax.axis_size(axis_name)
    if q.shape[1] % sp != 0:
        raise ValueError(
            f"heads {q.shape[1]} must divide by sp={sp} for ulysses"
        )
    # (B, H, n, D) -> (B, H/sp, n*sp, D): split heads across devices,
    # gather the sequence
    def seq_to_heads(t):
        return lax.all_to_all(t, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    bias_full = lax.all_gather(kmask_bias, axis_name, axis=1, tiled=True)
    out = _dense(qh, kh, vh, bias_full)
    # back: (B, H/sp, n*sp, D) -> (B, H, n, D)
    return lax.all_to_all(out, axis_name, split_axis=2, concat_axis=1,
                          tiled=True)


def _tied_core(q, k, v, num_rows_global: int, axis_name: Optional[str]):
    """Tied-row contraction; ``axis_name`` completes row-sharded logits with
    a psum, None means the rows are all local. One source of truth for the
    scale convention and dtype-cast points."""
    d = q.shape[-1]
    scale = d**-0.5 * num_rows_global**-0.5
    logits = jnp.einsum("brhid,brhjd->bhij", q, k).astype(jnp.float32)
    if axis_name is not None:
        logits = lax.psum(logits, axis_name)
    attn = jax.nn.softmax(logits * scale, axis=-1).astype(q.dtype)
    return jnp.einsum("bhij,brhjd->brhid", attn, v)


def tied_row_attention_sharded(
    q: jnp.ndarray,  # (B, R_local, H, N, D) — this device's MSA rows
    k: jnp.ndarray,
    v: jnp.ndarray,
    num_rows_global: int,
    axis_name: str = SEQ_AXIS_NAME,
) -> jnp.ndarray:
    """Tied-row (MSA-Transformer) attention with rows SHARDED over the mesh.

    The tied attention matrix sums QK^T logits over every MSA row with an
    extra r^-0.5 scale (SURVEY.md S7: "this is where tied-rows becomes a
    collective"): each device contracts its local rows, one psum over the
    row-sharding axis completes the global logits, and the shared softmax
    is applied to the local rows' values — the MSA need not be replicated.
    Standalone primitive for row-sharded layouts; the in-model tied path
    (ops/attention.py tie_dim) currently runs on a replicated MSA.
    """
    return _tied_core(q, k, v, num_rows_global, axis_name)


def tied_row_attention(
    q: jnp.ndarray,  # (B, R, H, N, D) global arrays
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    """Host-level tied-row attention; rows sharded over sp when a mesh is
    given, dense contraction otherwise. Exact in both modes."""
    b, r = q.shape[0], q.shape[1]
    if mesh is None or SEQ_AXIS_NAME not in mesh.axis_names:
        return _tied_core(q, k, v, r, None)
    sp = mesh.shape[SEQ_AXIS_NAME]
    dp = mesh.shape.get(DATA_AXIS_NAME, 1)
    if r % sp != 0:
        raise ValueError(f"MSA rows {r} must divide by sp={sp}")
    if b % dp != 0:
        raise ValueError(f"batch {b} must divide by dp={dp}")
    spec = P(DATA_AXIS_NAME, SEQ_AXIS_NAME)
    mapped = shard_map(
        partial(tied_row_attention_sharded, num_rows_global=r),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return mapped(q, k, v)


def sequence_parallel_attention(
    q: jnp.ndarray,  # (B, H, N, D) — global arrays
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,  # (B, N) bool key padding
    mesh: Optional[Mesh] = None,
    impl: str = "ring",
) -> jnp.ndarray:
    """Host-level entry: shard the sequence axis over the mesh's sp axis and
    run ring or ulysses attention; dense fallback without a mesh."""
    if impl not in ("ring", "ulysses"):
        raise ValueError(f"unknown context-parallel impl {impl!r}")
    b = q.shape[0]
    nk = k.shape[2]  # key length — differs from q length in cross-attention
    bias = (
        jnp.where(mask, 0.0, MASK_VALUE).astype(jnp.float32)
        if mask is not None
        else jnp.zeros((b, nk), jnp.float32)
    )
    if mesh is None or SEQ_AXIS_NAME not in mesh.axis_names:
        return _dense(q, k, v, bias)

    fn = ring_attention if impl == "ring" else ulysses_attention
    qkv_spec = P(DATA_AXIS_NAME, None, SEQ_AXIS_NAME, None)
    bias_spec = P(DATA_AXIS_NAME, SEQ_AXIS_NAME)
    mapped = shard_map(
        partial(fn, axis_name=SEQ_AXIS_NAME),
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, bias_spec),
        out_specs=qkv_spec,
        check_vma=False,
    )
    return mapped(q, k, v, bias)
