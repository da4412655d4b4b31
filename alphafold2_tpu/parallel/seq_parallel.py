"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference scales sequence length only with single-device memory tricks
(axial factorization, block sparsity, KV compression, reversibility —
SURVEY.md S5.7); it has no multi-device sequence parallelism of any kind
(S2.3). This module is the green-field capability layer: exact attention over
a sequence axis SHARDED across the ``sp`` mesh axis, in two standard flavors:

- :func:`ring_attention` — KV blocks rotate around the ring via
  ``lax.ppermute`` and each device runs one flash block a step: on a TPU
  the stock Pallas flash kernel (forward with its row sums and maxima; dq
  and dk/dv in the backward), elsewhere the same three calls in jnp. The
  steps' outputs are merged by log-sum-exp in float32 and the whole ring is
  one ``jax.custom_vjp``: dk and dv travel with their block. Memory per
  device is O(N/sp), no block's probabilities are kept or written to HBM by
  the kernel, and the result is exactly dense attention (not an
  approximation). ppermute rides neighbor ICI links.
- :func:`ulysses_attention` — ``lax.all_to_all`` re-shards from
  sequence-sharded to head-sharded, runs ordinary dense attention locally
  over the full sequence for H/sp heads, and all-to-alls back. Two
  collectives per call, best when heads % sp == 0 and N/sp is small.

Ulysses is jnp-only (XLA emits the collective gradients); the ring owns its
VJP, collectives included. Both are written to run inside ``shard_map`` with
a named ``sp`` axis.
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


# ------------------------------------------------------------- the ring ---
#
# One visiting K/V block is three functions, the stock flash kernel's own
# three calls: the forward with its row sums ``l`` and row maxima ``m``, dq,
# and dk/dv, the last two from the GLOBAL ``l`` and ``m`` (so each recomputes
# the block's true probabilities and nothing of a block is kept). The ring
# below is the same for both implementations of the three.


def _flash_blocks(b, h, nq, nk, d, dtype, scale):
    """The three calls as the stock Pallas TPU kernel, at the blocks
    ``ops/flash.py`` picks for the local shape. ``seg`` (B, nk) int32 is the
    key mask as the kernel's segment ids: 1 attends, 0 is masked out."""
    from jax.experimental.pallas.ops.tpu import flash_attention as stock

    from alphafold2_tpu.ops.flash import block_sizes_for

    bs = block_sizes_for(b, h, nq, nk, d, dtype)

    def segments(seg):
        return stock.SegmentIds(q=jnp.ones((b, nq), jnp.int32), kv=seg)

    def fwd(q, k, v, seg):
        # the name the flat path's forward has in a trace (its jitted
        # wrapper's); the two backward calls name themselves
        with jax.named_scope("flash_attention"):
            return stock._flash_attention_impl(
                q, k, v, None, segments(seg), True, False, scale,
                bs.block_b, bs.block_q, bs.block_k_major, bs.block_k, False,
            )

    def dq(q, k, v, seg, l, m, do, di):
        return stock._flash_attention_bwd_dq(
            q, k, v, None, segments(seg), l, m, do, di,
            block_q_major=bs.block_q_dq, block_k_major=bs.block_k_major_dq,
            block_k=bs.block_k_dq, sm_scale=scale, causal=False,
            mask_value=stock.DEFAULT_MASK_VALUE, debug=False,
        )[0]

    def dkv(q, k, v, seg, l, m, do, di):
        return stock._flash_attention_bwd_dkv(
            q, k, v, None, segments(seg), l, m, do, di,
            block_q_major=bs.block_q_major_dkv, block_q=bs.block_q_dkv,
            block_k_major=bs.block_k_major_dkv, block_k=bs.block_k_dkv,
            sm_scale=scale, causal=False,
            mask_value=stock.DEFAULT_MASK_VALUE, debug=False,
        )

    return fwd, dq, dkv


def _jnp_blocks(scale):
    """The same three calls in jnp, the block's logits in float32: wherever
    ``ops/flash.py`` ``flash_takes`` keeps the local block off the kernel."""

    def logits(q, k, seg):
        s = jnp.einsum("bhid,bhjd->bhij", q, k).astype(jnp.float32) * scale
        return s + jnp.where(seg > 0, 0.0, MASK_VALUE)[:, None, None, :]

    def fwd(q, k, v, seg):
        s = logits(q, k, seg)
        m = jnp.max(s, axis=-1)
        p = jnp.exp(s - m[..., None])
        l = jnp.sum(p, axis=-1)
        o = jnp.einsum(
            "bhij,bhjd->bhid", (p / l[..., None]).astype(v.dtype), v)
        return o, l, m

    def probs_and_ds(q, k, v, seg, l, m, do, di):
        p = jnp.exp(logits(q, k, seg) - m[..., None]) / l[..., None]
        dp = jnp.einsum("bhid,bhjd->bhij", do, v).astype(jnp.float32)
        return p, p * (dp - di[..., None]) * scale

    def dq(q, k, v, seg, l, m, do, di):
        _, ds = probs_and_ds(q, k, v, seg, l, m, do, di)
        return jnp.einsum("bhij,bhjd->bhid", ds.astype(k.dtype), k)

    def dkv(q, k, v, seg, l, m, do, di):
        p, ds = probs_and_ds(q, k, v, seg, l, m, do, di)
        dk = jnp.einsum("bhij,bhid->bhjd", ds.astype(q.dtype), q)
        dv = jnp.einsum("bhij,bhid->bhjd", p.astype(do.dtype), do)
        return dk, dv

    return fwd, dq, dkv


@partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _ring(q, k, v, seg, axis_name, blocks):
    return _ring_fwd(q, k, v, seg, axis_name, blocks)[0]


def _hop(x, axis_name):
    sp = lax.axis_size(axis_name)
    return lax.ppermute(x, axis_name, [(i, (i + 1) % sp) for i in range(sp)])


def _visits(k, v, seg, axis_name):
    """This device's K/V/mask block, then each visiting one: ``sp`` blocks,
    ``sp - 1`` hops (nothing waits for a hop but the next step's kernels)."""
    sp = lax.axis_size(axis_name)
    for step in range(sp):
        yield k, v, seg
        if step < sp - 1:
            k, v, seg = (_hop(t, axis_name) for t in (k, v, seg))


def _merge(merged, o_j, l_j, m_j):
    """Fold one step's normalised output into the steps before it, by
    log-sum-exp in float32."""
    o_j = o_j.astype(jnp.float32)
    if merged is None:
        return o_j, l_j, m_j
    o, l, m = merged
    m_new = jnp.maximum(m, m_j)
    w = l * jnp.exp(m - m_new)
    w_j = l_j * jnp.exp(m_j - m_new)
    l_new = w + w_j
    o_new = (o * w[..., None] + o_j * w_j[..., None]) / l_new[..., None]
    return o_new, l_new, m_new


def _ring_fwd(q, k, v, seg, axis_name, blocks):
    """One forward block a step, merged as they come; the residuals hold the
    merged (global) ``l`` and ``m`` and no block's probabilities."""
    fwd, _, _ = blocks
    merged = None
    for k_j, v_j, seg_j in _visits(k, v, seg, axis_name):
        with jax.named_scope("ring_block"):
            step_out = fwd(q, k_j, v_j, seg_j)
        with jax.named_scope("ring_merge"):
            merged = _merge(merged, *step_out)
    o, l, m = merged
    out = o.astype(q.dtype)
    return out, (q, k, v, seg, out, l, m)


def _ring_bwd(axis_name, blocks, residuals, do):
    """dq stays home and sums over the steps; dk and dv travel with their
    block in float32 and are home after ``sp`` hops."""
    _, dq_block, dkv_block = blocks
    q, k, v, seg, out, l, m = residuals
    do = do.astype(q.dtype)
    with jax.named_scope("ring_merge"):
        di = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32), -1)
    dq = dk = dv = 0.0
    for k_j, v_j, seg_j in _visits(k, v, seg, axis_name):
        with jax.named_scope("ring_block"):
            args = (q, k_j, v_j, seg_j, l, m, do, di)
            dq_j = dq_block(*args)
            dk_j, dv_j = dkv_block(*args)
        with jax.named_scope("ring_merge"):
            dq = dq + dq_j.astype(jnp.float32)
            dk = _hop(dk + dk_j.astype(jnp.float32), axis_name)
            dv = _hop(dv + dv_j.astype(jnp.float32), axis_name)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), None


_ring.defvjp(_ring_fwd, _ring_bwd)


def ring_attention(
    q: jnp.ndarray,  # (B, H, nq_local, D) — this device's query block
    k: jnp.ndarray,  # (B, H, nk_local, D) — this device's KV block
    v: jnp.ndarray,
    kmask: jnp.ndarray,  # (B, nk_local) bool: False keys are masked out
    axis_name: str = SEQ_AXIS_NAME,
) -> jnp.ndarray:
    """Exact attention over the ring-sharded sequence axis.

    A ring of flash blocks under one custom VJP: K, V and the key mask visit
    every device by ``ppermute``, one hop a step; see :func:`_ring_fwd` and
    :func:`_ring_bwd`. The block is the stock flash kernel where
    ``ops/flash.py`` ``flash_takes`` the local shape, the jnp triple
    everywhere else. For the kernel both local axes are padded to the 128
    lanes its blocks need, padded keys masked out, as ``ops/flash.py`` does.
    """
    from alphafold2_tpu.ops import flash

    b, h, nq, d = q.shape
    nk = k.shape[2]
    scale = d**-0.5
    seg = kmask.astype(jnp.int32)
    if not flash.flash_takes(nq, nk, d):
        return _ring(q, k, v, seg, axis_name, _jnp_blocks(scale))
    pad_q, pad_k = (-nq) % 128, (-nk) % 128
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        seg = jnp.pad(seg, ((0, 0), (0, pad_k)))
    blocks = _flash_blocks(b, h, nq + pad_q, nk + pad_k, d, q.dtype, scale)
    return _ring(q, k, v, seg, axis_name, blocks)[:, :, :nq]


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
    if mask is None:
        mask = jnp.ones((b, nk), bool)
    bias = jnp.where(mask, 0.0, MASK_VALUE).astype(jnp.float32)
    if mesh is None or SEQ_AXIS_NAME not in mesh.axis_names:
        return _dense(q, k, v, bias)

    if impl == "ring":
        fn, keys = ring_attention, mask
    else:
        fn, keys = ulysses_attention, bias
    qkv_spec = P(DATA_AXIS_NAME, None, SEQ_AXIS_NAME, None)
    keys_spec = P(DATA_AXIS_NAME, SEQ_AXIS_NAME)
    mapped = shard_map(
        partial(fn, axis_name=SEQ_AXIS_NAME),
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, keys_spec),
        out_specs=qkv_spec,
        check_vma=False,
    )
    return mapped(q, k, v, keys)
