"""Pallas TPU kernel: fused dense (flash) attention for the trunk hot paths.

The trunk's real FLOPs live in two dense attention shapes — the axial
row/col passes over the N^2 pair grid and tied-row MSA attention — and both
lowered to stock XLA dense attention (full (.., Nq, Nk) logits in HBM)
everywhere the stock jax kernel was not available. This module is the
in-repo fused answer, same proven idioms as ``block_sparse.py``:

- grid = (batch*heads, q_blocks, kv_blocks); online-softmax (flash)
  accumulation in VMEM scratch across the innermost kv axis, f32
  accumulators, bf16-friendly inputs; the output q-block is revisited and
  finalized on the last kv block. Nothing quadratic ever hits HBM.
- key-padding mask rides as a sublane-replicated (B, _SUB, Nk) f32 additive
  bias streamed per KV block (the Mosaic-tiling idiom block_sparse proved);
  row stats (lse, dsum) are lane-replicated (bh, n, _LANES) tensors.
- fused flash-style backward (custom VJP): dq accumulates over kv blocks,
  dk/dv over q blocks, probabilities recomputed from q/k and the saved
  logsumexp — the standard flash schedule, no quadratic residuals.
- ``interpret`` defaults to on off-TPU, so the same kernels run (slowly
  but exactly) on the CPU mesh and oracle-diff in CI.

The tied-row MSA kernel (``tied_row.py``) reuses these kernels through an
algebraic reduction: the tied logit sum over rows is one contraction over a
fused (row, head_dim) feature axis, so the D dimension here may be R*D.

The model reaches these kernels through ``tied_row.py`` only (its rule is
``tied_row_takes``); the axial passes run the stock kernel of
``ops/flash.py``. Validated against the dense jnp oracle (values and
grads, masked + padded + odd lengths) in tests/test_pallas_kernels.py and
Mosaic-lowered pre-hardware by ``analysis/lowering.py``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from alphafold2_tpu.ops.pallas.block_sparse import (
    NEG_INF,
    _LANES,
    _SUB,
    _rep_rows,
)

# q/kv tile edge: one Mosaic lane width. Arrays pad up to a multiple (the
# padded keys are excluded via the additive bias, padded query rows are
# sliced back off), exactly the policy ops/flash.py applies to the stock
# kernel, so any length — compressed-KV, odd crops — takes the fused path.
BLOCK = 128


def _fwd_core(
    q_ref,  # (1, block_q, d)
    k_ref,  # (1, block_k, d) — the a-th KV block
    v_ref,  # (1, block_k, d)
    bias_ref,  # (1, _SUB, block_k) f32 additive key bias (0 / NEG_INF)
    o_ref,  # (1, block_q, d)
    lse_ref,  # (1, block_q, _LANES) lane-replicated logsumexp, or None
    m_scr,  # (block_q, 1) f32 running max
    l_scr,  # (block_q, 1) f32 running sum
    acc_scr,  # (block_q, d) f32 accumulator
    *,
    scale: float,
):
    a = pl.program_id(2)
    num_a = pl.num_programs(2)

    @pl.when(a == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    dots = (
        jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        * scale
    )  # (block_q, block_k)
    dots = dots + bias_ref[0][:1, :]

    m_prev = m_scr[:]
    m_new = jnp.maximum(m_prev, jnp.max(dots, axis=-1, keepdims=True))
    p = jnp.exp(dots - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[:] = alpha * l_scr[:] + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_scr[:] = m_new

    @pl.when(a == num_a - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[0] = jnp.broadcast_to(
                m_scr[:] + jnp.log(l), lse_ref.shape[1:]
            )


def _kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref, m_scr, l_scr,
            acc_scr, *, scale: float):
    _fwd_core(q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref, m_scr, l_scr,
              acc_scr, scale=scale)


def _kernel_no_lse(q_ref, k_ref, v_ref, bias_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, scale: float):
    # inference/no-grad variant: skips the 128x-replicated lse HBM write
    _fwd_core(q_ref, k_ref, v_ref, bias_ref, o_ref, None, m_scr, l_scr,
              acc_scr, scale=scale)


def _dq_kernel(
    q_ref,  # (1, block_q, d)
    g_ref,  # (1, block_q, d) upstream cotangent dO
    lse_ref,  # (1, block_q, _LANES) lane-replicated
    dsum_ref,  # (1, block_q, _LANES) lane-replicated D = rowsum(dO * O)
    k_ref,  # (1, block_k, d) — the a-th KV block
    v_ref,  # (1, block_k, d)
    bias_ref,  # (1, _SUB, block_k)
    dq_ref,  # (1, block_q, d) out
    dq_scr,  # (block_q, d) f32
    *,
    scale: float,
):
    a = pl.program_id(2)
    num_a = pl.num_programs(2)

    @pl.when(a == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    q, g, k, v = q_ref[0], g_ref[0], k_ref[0], v_ref[0]
    dots = (
        jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        * scale
        + bias_ref[0][:1, :]
    )
    p = jnp.exp(dots - _rep_rows(lse_ref[0], dots.shape[1]))
    dp = jax.lax.dot_general(
        g, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = p * (dp - _rep_rows(dsum_ref[0], dp.shape[1]))
    dq_scr[:] = dq_scr[:] + scale * jax.lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(a == num_a - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(
    k_ref,  # (1, block_k, d) this KV block
    v_ref,  # (1, block_k, d)
    bias_ref,  # (1, _SUB, block_k) additive key bias for this KV block
    q_ref,  # (1, block_q, d) — the a-th attending Q block
    g_ref,  # (1, block_q, d)
    lse_ref,  # (1, block_q, _LANES)
    dsum_ref,  # (1, block_q, _LANES)
    dk_ref,  # (1, block_k, d) out
    dv_ref,  # (1, block_k, d) out
    dk_scr,  # (block_k, d) f32
    dv_scr,  # (block_k, d) f32
    *,
    scale: float,
):
    a = pl.program_id(2)
    num_a = pl.num_programs(2)

    @pl.when(a == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    k, v, q, g = k_ref[0], v_ref[0], q_ref[0], g_ref[0]
    dots = (
        jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        * scale
        + bias_ref[0][:1, :]
    )  # (block_q, block_k)
    p = jnp.exp(dots - _rep_rows(lse_ref[0], dots.shape[1]))
    dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
        p.astype(g.dtype), g, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dp = jax.lax.dot_general(
        g, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = p * (dp - _rep_rows(dsum_ref[0], dp.shape[1]))
    dk_scr[:] = dk_scr[:] + scale * jax.lax.dot_general(
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(a == num_a - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "block_k", "scale", "interpret", "with_lse"),
)
def _run(q, k, v, bias8, block_q, block_k, scale, interpret, with_lse):
    bh, nq, d = q.shape
    nk = k.shape[1]
    b = bias8.shape[0]
    heads = bh // b
    grid = (bh, nq // block_q, nk // block_k)

    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh_, qi, a: (bh_, qi, 0)),
        pl.BlockSpec((1, block_k, d), lambda bh_, qi, a: (bh_, a, 0)),
        pl.BlockSpec((1, block_k, d), lambda bh_, qi, a: (bh_, a, 0)),
        pl.BlockSpec(
            (1, _SUB, block_k),
            lambda bh_, qi, a, h=heads: (bh_ // h, 0, a),
        ),
    ]
    out_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh_, qi, a: (bh_, qi, 0)),
    ] + ([
        pl.BlockSpec((1, block_q, _LANES), lambda bh_, qi, a: (bh_, qi, 0)),
    ] if with_lse else [])
    out_shape = [jax.ShapeDtypeStruct((bh, nq, d), q.dtype)] + (
        [jax.ShapeDtypeStruct((bh, nq, _LANES), jnp.float32)]
        if with_lse else []
    )
    kernel = functools.partial(
        _kernel if with_lse else _kernel_no_lse, scale=scale
    )
    res = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, bias8)
    return (res[0], res[1]) if with_lse else (res[0], None)


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_k", "scale", "interpret")
)
def _run_dq(q, g, lse_l, dsum_l, k, v, bias8, block_q, block_k, scale,
            interpret):
    bh, nq, d = q.shape
    nk = k.shape[1]
    b = bias8.shape[0]
    heads = bh // b
    grid = (bh, nq // block_q, nk // block_k)
    return pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh_, qi, a: (bh_, qi, 0)),
            pl.BlockSpec((1, block_q, d), lambda bh_, qi, a: (bh_, qi, 0)),
            pl.BlockSpec(
                (1, block_q, _LANES), lambda bh_, qi, a: (bh_, qi, 0)
            ),
            pl.BlockSpec(
                (1, block_q, _LANES), lambda bh_, qi, a: (bh_, qi, 0)
            ),
            pl.BlockSpec((1, block_k, d), lambda bh_, qi, a: (bh_, a, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh_, qi, a: (bh_, a, 0)),
            pl.BlockSpec(
                (1, _SUB, block_k),
                lambda bh_, qi, a, h=heads: (bh_ // h, 0, a),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, block_q, d), lambda bh_, qi, a: (bh_, qi, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((bh, nq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(q, g, lse_l, dsum_l, k, v, bias8)


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_k", "scale", "interpret")
)
def _run_dkv(k, v, bias8, q, g, lse_l, dsum_l, block_q, block_k, scale,
             interpret):
    bh, nk, d = k.shape
    nq = q.shape[1]
    b = bias8.shape[0]
    heads = bh // b
    grid = (bh, nk // block_k, nq // block_q)
    return pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh_, kj, a: (bh_, kj, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh_, kj, a: (bh_, kj, 0)),
            pl.BlockSpec(
                (1, _SUB, block_k),
                lambda bh_, kj, a, h=heads: (bh_ // h, 0, kj),
            ),
            pl.BlockSpec((1, block_q, d), lambda bh_, kj, a: (bh_, a, 0)),
            pl.BlockSpec((1, block_q, d), lambda bh_, kj, a: (bh_, a, 0)),
            pl.BlockSpec(
                (1, block_q, _LANES), lambda bh_, kj, a: (bh_, a, 0)
            ),
            pl.BlockSpec(
                (1, block_q, _LANES), lambda bh_, kj, a: (bh_, a, 0)
            ),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh_, kj, a: (bh_, kj, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh_, kj, a: (bh_, kj, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, nk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, nk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
    )(k, v, bias8, q, g, lse_l, dsum_l)


def _pad_seq(t, axis: int, pad: int):
    if pad == 0:
        return t
    widths = [(0, 0)] * t.ndim
    widths[axis] = (0, pad)
    return jnp.pad(t, widths)


def fused_attention(
    q: jnp.ndarray,  # (B, H, Nq, D)
    k: jnp.ndarray,  # (B, H, Nk, D)
    v: jnp.ndarray,
    q_mask: Optional[jnp.ndarray] = None,  # (B, Nq) bool valid-query
    kv_mask: Optional[jnp.ndarray] = None,  # (B, Nk) bool valid-key
    sm_scale: float = 1.0,
    interpret: Optional[bool] = None,
    block_q: int = BLOCK,
    block_k: int = BLOCK,
) -> jnp.ndarray:
    """Fused flash attention, differentiable (fused custom-VJP backward).

    Same contract as ``ops.flash.flash_attention`` / ``ops.chunked``:
    masked keys are excluded exactly (additive NEG_INF bias before the
    online max); masked queries produce zeros (the flash SegmentIds
    convention — padded rows are downstream-masked everywhere this runs).
    Sequence axes pad up to the 128-lane block and the output is sliced
    back. ``interpret=None`` compiles on TPU and interprets elsewhere."""
    b, h, nq, d = q.shape
    nk = k.shape[2]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_q = min(block_q, max(8, -(-nq // 8) * 8)) if interpret else block_q
    block_k = min(block_k, max(8, -(-nk // 8) * 8)) if interpret else block_k
    pad_q = (-nq) % block_q
    pad_k = (-nk) % block_k
    if pad_k and kv_mask is None:
        kv_mask = jnp.ones((b, nk), dtype=bool)

    qp = _pad_seq(q, 2, pad_q)
    kp = _pad_seq(k, 2, pad_k)
    vp = _pad_seq(v, 2, pad_k)
    nqp, nkp = nq + pad_q, nk + pad_k
    if kv_mask is not None:
        kv_pad = _pad_seq(kv_mask, 1, pad_k)  # pads with False = excluded
        bias = jnp.where(kv_pad, 0.0, NEG_INF).astype(jnp.float32)
    else:
        bias = jnp.zeros((b, nkp), dtype=jnp.float32)
    bias8 = jnp.broadcast_to(bias[:, None, :], (b, _SUB, nkp))

    bh = b * h
    qf = qp.reshape(bh, nqp, d)
    kf = kp.reshape(bh, nkp, d)
    vf = vp.reshape(bh, nkp, d)

    @jax.custom_vjp
    def attend(qf, kf, vf, bias8):
        out, _ = _run(
            qf, kf, vf, bias8, block_q, block_k, sm_scale, interpret, False
        )
        return out

    def attend_fwd(qf, kf, vf, bias8):
        out, lse = _run(
            qf, kf, vf, bias8, block_q, block_k, sm_scale, interpret, True
        )
        return out, (qf, kf, vf, bias8, out, lse)

    def attend_bwd(res, g):
        qf, kf, vf, bias8, out, lse = res
        dsum = jnp.sum(
            out.astype(jnp.float32) * g.astype(jnp.float32), axis=-1
        )
        dsum_l = jnp.broadcast_to(dsum[..., None], (bh, nqp, _LANES))
        dq = _run_dq(
            qf, g, lse, dsum_l, kf, vf, bias8, block_q, block_k, sm_scale,
            interpret,
        )
        dk, dv = _run_dkv(
            kf, vf, bias8, qf, g, lse, dsum_l, block_q, block_k, sm_scale,
            interpret,
        )
        return dq, dk, dv, None

    attend.defvjp(attend_fwd, attend_bwd)
    out = attend(qf, kf, vf, bias8).reshape(b, h, nqp, d)[:, :, :nq]
    if q_mask is not None:
        out = jnp.where(q_mask[:, None, :, None], out, 0)
    return out

