"""The attention core of a layer whose every query attends its own set of
keys, picked by a learned indexer (``ops/dsa.py``): ``softmax(q k^T)`` over
the selected keys only, then ``v``, as a forward, a dq and a dk/dv Pallas
kernel in the flash form.

The selection changes every step, so nothing about it is known when the step
is traced: it arrives as device arrays. Two of them:

- the mask, bit-packed along the queries: ``packed`` (B, S / 32, S) int32,
  where the key ``s`` of query ``t = 256 i + 8 j + r`` is bit ``j`` of
  ``packed[i * 8 + r, s]``. A (256 queries x 512 keys) tile is then an
  (8, 512) block of words, one aligned int32 tile of the chip, and unpacks
  into 32 shifted copies of itself stacked along the queries;
- per block of 256 queries the key blocks (of ``block_k``) that hold a
  selected key, ascending, and their count; per key block the query blocks
  that selected one of its keys (:func:`visit_lists`). They ride in as
  scalar-prefetch operands, as ``ops/pallas/block_sparse.py``'s lists do:
  the grid's last axis walks a list, its steps past the count skip their
  work, and the index maps repeat the last listed block there so no new
  block is fetched.

Grouped heads without a broadcast copy: a grid step holds one key/value
head ``g`` and the ``H / G`` query heads that read it (query head ``h``
reads ``h // (H / G)``), and walks them in a loop, so ``k`` and ``v`` are
fetched once for the group and dk, dv sum the group's heads in VMEM.

Every product is formed with the keys along the sublanes and the queries
along the lanes (``s^T = k q^T``, a (block_k, 256) tile): the softmax
statistics are then rows over the queries, the layout the log-sum-exp is
stored in (B, H, S), read back by the two backward kernels without a
transpose. The forward keeps the output transposed in VMEM and turns it
once a block of queries. ``q`` carries the softmax scale, as
``ops/mla.py`` ``causal_core``'s does.

The forward saves its two results, output and log-sum-exp, under the name
``ops/mla.py`` ``CORE_RESIDUALS``: a ``jax.checkpoint`` that keeps that name
(``models/mla_moe_lm.py`` ``remat_layer``) runs no forward kernel again.
"""

from __future__ import annotations

import functools

import jax
from jax.ad_checkpoint import checkpoint_name
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from alphafold2_tpu.ops import mla

F32 = jnp.float32
BLOCK_Q = 256  # queries a grid step; the packing's unit (8 words x 32 bits)
WORDS = BLOCK_Q // 32
NEG = -1e30  # a masked score: finite, so a tile with no key makes no NaN

_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))


def visit_lists(tiles):
    """``tiles`` (B, rows, cols) bool -> (lists (B, rows, cols) int32,
    counts (B, rows) int32): a row's true columns ascending, then its last
    true column repeated (0 for a row with none)."""
    count = tiles.sum(-1).astype(jnp.int32)
    order = jnp.argsort(~tiles, axis=-1, stable=True).astype(jnp.int32)
    last = jnp.take_along_axis(
        order, jnp.maximum(count - 1, 0)[..., None], axis=-1)
    slot = jnp.arange(tiles.shape[-1], dtype=jnp.int32)
    return jnp.where(slot < count[..., None], order, last), count


def tile_visits(packed, block_k: int):
    """(B, S / 32, S) packed mask -> (B, S / 256, S / block_k) bool: the
    tiles that hold a selected key."""
    b, words, s = packed.shape
    tiles = packed.reshape(b, words // WORDS, WORDS, s // block_k, block_k)
    return jnp.any(tiles != 0, axis=(2, 4))


def _keep_t(words):
    """An (8, block_k) block of words -> the tile's mask transposed,
    (block_k, 256) bool: row ``l = 8 j + r`` of the untransposed tile is bit
    ``j`` of word row ``r``."""
    tiled = jnp.concatenate([words] * (BLOCK_Q // WORDS), axis=0)
    shift = jax.lax.broadcasted_iota(jnp.int32, tiled.shape, 0) // WORDS
    bits = jax.lax.shift_right_logical(tiled, shift) & 1
    return bits.T != 0


def _scores_t(k, q):
    """``k q^T``: (block_k, D) x (256, D) -> (block_k, 256) float32."""
    return jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                               preferred_element_type=F32)


def _fwd_kernel(kb_ref, kn_ref, q_ref, k_ref, v_ref, w_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr):
    b, i, a = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    heads = q_ref.shape[0]

    @pl.when(a == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG, F32)
        l_scr[...] = jnp.zeros(l_scr.shape, F32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, F32)

    @pl.when(a < kn_ref[b, i])
    def _visit():
        keep = _keep_t(w_ref[...])
        k, v = k_ref[...], v_ref[...]
        for h in range(heads):
            s = jnp.where(keep, _scores_t(k, q_ref[h]), NEG)
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, s.max(axis=0, keepdims=True))
            p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[h] = alpha * l_scr[h] + p.sum(axis=0, keepdims=True)
            # (D, 256): the output transposed, keys contracted
            acc_scr[h] = alpha * acc_scr[h] + jax.lax.dot_general(
                v, p.astype(v.dtype), (((0,), (0,)), ((), ())),
                preferred_element_type=F32)
            m_scr[h] = m_new

    @pl.when(a == pl.num_programs(3) - 1)
    def _finish():
        for h in range(heads):
            l = l_scr[h]
            o_ref[h] = (acc_scr[h] / l).T.astype(o_ref.dtype)
            lse_ref[pl.ds(h, 1), :] = m_scr[h] + jnp.log(l)


def _dq_kernel(kb_ref, kn_ref, q_ref, k_ref, v_ref, w_ref, do_ref, lse_ref,
               dl_ref, dq_ref, acc_scr):
    b, i, a = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    heads = q_ref.shape[0]

    @pl.when(a == 0)
    def _init():
        acc_scr[...] = jnp.zeros(acc_scr.shape, F32)

    @pl.when(a < kn_ref[b, i])
    def _visit():
        keep = _keep_t(w_ref[...])
        k, v = k_ref[...], v_ref[...]
        for h in range(heads):
            p = jnp.where(keep, jnp.exp(
                _scores_t(k, q_ref[h]) - lse_ref[pl.ds(h, 1), :]), 0.0)
            dp = _scores_t(v, do_ref[h])
            ds = p * (dp - dl_ref[pl.ds(h, 1), :])
            acc_scr[h] = acc_scr[h] + jax.lax.dot_general(
                ds.astype(k.dtype), k, (((0,), (0,)), ((), ())),
                preferred_element_type=F32)

    @pl.when(a == pl.num_programs(3) - 1)
    def _finish():
        dq_ref[...] = acc_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(qb_ref, qn_ref, q_ref, k_ref, v_ref, w_ref, do_ref, lse_ref,
                dl_ref, dk_ref, dv_ref, dk_scr, dv_scr):
    b, j, a = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    heads = q_ref.shape[0]

    @pl.when(a == 0)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, F32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, F32)

    @pl.when(a < qn_ref[b, j])
    def _visit():
        keep = _keep_t(w_ref[...])
        k, v = k_ref[...], v_ref[...]
        for h in range(heads):
            q, do = q_ref[h], do_ref[h]
            p = jnp.where(keep, jnp.exp(
                _scores_t(k, q) - lse_ref[pl.ds(h, 1), :]), 0.0)
            dv_scr[...] += jnp.dot(p.astype(do.dtype), do,
                                   preferred_element_type=F32)
            ds = p * (_scores_t(v, do) - dl_ref[pl.ds(h, 1), :])
            dk_scr[...] += jnp.dot(ds.astype(q.dtype), q,
                                   preferred_element_type=F32)

    @pl.when(a == pl.num_programs(3) - 1)
    def _finish():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _specs(rep, block_k, d, by_query):
    """Block specs of q (and do), k, v, the words, and lse (and delta) for a
    grid (batch, group, walked block, visit) whose lists name the other
    side's blocks."""
    if by_query:  # walk query blocks i, visit key blocks lists[b, i, a]
        def q_at(b, g, i, a, lists, n):
            return b, g, i, 0

        def k_at(b, g, i, a, lists, n):
            return b, g, lists[b, i, a], 0

        def w_at(b, g, i, a, lists, n):
            return b, i, lists[b, i, a]

        def r_at(b, g, i, a, lists, n):
            return b, g, i
    else:  # walk key blocks j, visit query blocks lists[b, j, a]
        def q_at(b, g, j, a, lists, n):
            return b, g, lists[b, j, a], 0

        def k_at(b, g, j, a, lists, n):
            return b, g, j, 0

        def w_at(b, g, j, a, lists, n):
            return b, lists[b, j, a], j

        def r_at(b, g, j, a, lists, n):
            return b, g, lists[b, j, a]

    return {
        "q": pl.BlockSpec((None, rep, BLOCK_Q, d), q_at),
        "k": pl.BlockSpec((None, None, block_k, d), k_at),
        "w": pl.BlockSpec((None, WORDS, block_k), w_at),
        "row": pl.BlockSpec((None, rep, BLOCK_Q), r_at),
    }


def _forward(q, k, v, packed, lists, counts, block_k, interpret):
    b, h, s, d = q.shape
    g = k.shape[1]
    rep = h // g
    sp = _specs(rep, block_k, d, by_query=True)
    return pl.pallas_call(
        _fwd_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, g, s // BLOCK_Q, lists.shape[-1]),
            in_specs=[sp["q"], sp["k"], sp["k"], sp["w"]],
            out_specs=[sp["q"], sp["row"]],
            scratch_shapes=[pltpu.VMEM((rep, 1, BLOCK_Q), F32),
                            pltpu.VMEM((rep, 1, BLOCK_Q), F32),
                            pltpu.VMEM((rep, d, BLOCK_Q), F32)]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, h, s), F32)],
        compiler_params=_PARAMS, interpret=interpret, name="dsa_core_fwd",
    )(lists, counts, q, k, v, packed)


def _backward(q, k, v, packed, out, lse, do, lists, block_k, interpret):
    b, h, s, d = q.shape
    g = k.shape[1]
    rep = h // g
    delta = jnp.sum(do.astype(F32) * out.astype(F32), axis=-1)
    fwd_lists, fwd_counts, bwd_lists, bwd_counts = lists
    sp = _specs(rep, block_k, d, by_query=True)
    dq = pl.pallas_call(
        _dq_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, g, s // BLOCK_Q, fwd_lists.shape[-1]),
            in_specs=[sp["q"], sp["k"], sp["k"], sp["w"], sp["q"],
                      sp["row"], sp["row"]],
            out_specs=sp["q"],
            scratch_shapes=[pltpu.VMEM((rep, BLOCK_Q, d), F32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=_PARAMS, interpret=interpret, name="dsa_core_dq",
    )(fwd_lists, fwd_counts, q, k, v, packed, do, lse, delta)
    sp = _specs(rep, block_k, d, by_query=False)
    dk, dv = pl.pallas_call(
        _dkv_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, g, s // block_k, bwd_lists.shape[-1]),
            in_specs=[sp["q"], sp["k"], sp["k"], sp["w"], sp["q"],
                      sp["row"], sp["row"]],
            out_specs=[sp["k"], sp["k"]],
            scratch_shapes=[pltpu.VMEM((block_k, d), F32),
                            pltpu.VMEM((block_k, d), F32)]),
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        compiler_params=_PARAMS, interpret=interpret, name="dsa_core_dkv",
    )(bwd_lists, bwd_counts, q, k, v, packed, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _core(q, k, v, packed, lists, block_k, interpret):
    out, lse = _forward(q, k, v, packed, lists[0], lists[1], block_k,
                        interpret)
    return out, lse


def _core_fwd(q, k, v, packed, lists, block_k, interpret):
    out, lse = _forward(q, k, v, packed, lists[0], lists[1], block_k,
                        interpret)
    out, lse = checkpoint_name(
        (out, lse), mla.CORE_RESIDUALS)
    return (out, lse), (q, k, v, packed, lists, out, lse)


def _core_bwd(block_k, interpret, res, cotangents):
    q, k, v, packed, lists, out, lse = res
    # the log-sum-exp leaves sparse_core under stop_gradient: no cotangent
    do, _ = cotangents
    dq, dk, dv = _backward(q, k, v, packed, out, lse, do, lists, block_k,
                           interpret)
    return dq, dk, dv, None, None


_core.defvjp(_core_fwd, _core_bwd)


def sparse_core(q, k, v, packed, block_k: int, interpret=None):
    """(``softmax(q k^T)`` over each query's selected keys, times ``v``;
    the log-sum-exp of the scores over them, (B, H, S) float32, under
    ``stop_gradient``). ``q`` (B, H, S, D) carries the softmax scale, ``k``
    and ``v`` (B, G, S, D) with G dividing H, ``packed`` the selection (the
    module's docstring). ``S`` a multiple of 256 and of ``block_k``. Every
    query must have a selected key. ``interpret=None`` compiles on a TPU and
    interprets elsewhere."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    tiles = tile_visits(packed, block_k)
    lists = (*visit_lists(tiles), *visit_lists(tiles.swapaxes(1, 2)))
    out, lse = _core(q, k, v, packed, lists, block_k, bool(interpret))
    return out, jax.lax.stop_gradient(lse)
