"""Learned sparse attention (DeepSeek-V3.2's sparse stage, as Keye-VL-2.0's
``sa_config`` sets it): an indexer scores every earlier key for each query,
the ``topk`` best are selected, and the attention core reads those alone.

With ``qI`` (B, S, J, Di) the indexer's query heads, ``kI`` (B, S, Di) its
one key head and ``w`` (B, S, J) its per-head weights:

- scores ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` for ``s <= t``
  (:func:`index_scores`), float32;
- selection ``S_t``: the ``topk`` largest ``I[t, s]``, ties to the lower
  ``s`` as ``lax.top_k`` breaks them; every ``s <= t`` while ``t < topk``
  (:func:`select`). Exact: a top-k that is approximate picks other keys, and
  is another model;
- the core ``softmax_{s in S_t}(q_h,t . k_s) v_s`` (:func:`sparse_core`):
  on a TPU the Pallas kernels of ``ops/pallas/dsa_core.py``, elsewhere the
  dense masked form :func:`sparse_core_xla`;
- the indexer's loss (:func:`indexer_loss`): the mean over queries of
  ``KL(p_t || softmax_{s in S_t} I[t, s])`` where ``p_t`` is the main
  attention's probability summed over the query heads and renormalised over
  ``S_t``. ``p`` carries no gradient: only the indexer learns from it.

The scores and the selection are made a block of ``chunk`` queries at a time
(a ``lax.map``): a block's (chunk, J, S) products are the largest thing held,
0.5 GB at 512 queries of 16 heads over 16,384 keys, where all of them would
be 17 GB. The selection leaves the block bit-packed (``ops/pallas/
dsa_core.py`` gives the layout: 33.5 MB at 16,384 positions), and the
packed mask is named :data:`SELECTION`, so that a ``jax.checkpoint`` that
keeps it (``models/dsa_moe_lm.py``) does not select again in the backward
pass. The loss is made in blocks of :data:`LOSS_CHUNK` queries, each under
``jax.checkpoint``, in XLA: its (block, H, S) probabilities are formed again
from ``q``, ``k`` and the core's log-sum-exp, and then once more for the
backward pass.
"""

from __future__ import annotations

import jax
from jax.ad_checkpoint import checkpoint_name
import jax.numpy as jnp
import numpy as np

from alphafold2_tpu.ops.pallas import dsa_core

F32 = jnp.float32
SELECTION = "dsa_selection"  # the checkpoint name of the packed mask
LOSS_CHUNK = 256  # queries a block of the loss
BLOCK_K = 512  # keys a block of the Pallas core
SAMPLE_ROWS, SAMPLE_KEYS = 16, 256  # the words of :func:`selection_sample`


def core_kernel_takes(length: int, heads: int, groups: int) -> bool:
    """Whether the Pallas core serves this shape: on a TPU, at a length on
    the kernels' grid (256 queries, ``BLOCK_K`` keys a block) and with the
    query heads of a group on the (8, 128) tile (a multiple of 8, or all of
    them in one group)."""
    rep = heads // groups
    return (jax.default_backend() == "tpu" and length % BLOCK_K == 0
            and length % dsa_core.BLOCK_Q == 0
            and (rep % 8 == 0 or groups == 1))


def index_scores(q_i, k_i, w, start):
    """The indexer's scores of a block of queries against every key:
    ``q_i`` (B, C, J, Di), ``k_i`` (B, S, Di), ``w`` (B, C, J) float32; the
    block's first query is position ``start``. (B, C, S) float32, ``-inf``
    where ``s > t``."""
    logits = jnp.einsum("bcjd,bsd->bcjs", q_i, k_i,
                        preferred_element_type=F32)
    scores = jnp.sum(jax.nn.relu(logits) * w[..., None], axis=2)
    t = start + jnp.arange(q_i.shape[1])
    causal = t[:, None] >= jnp.arange(k_i.shape[1])[None, :]
    return jnp.where(causal, scores, -jnp.inf)


def _total_order(x):
    """float32 -> int32 keys in the order ``lax.top_k`` sorts by: the
    float's order, with -0.0 below +0.0."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def select(scores, topk: int):
    """(B, C, S) scores (``-inf`` off the causal part) -> (B, C, S) bool:
    the ``topk`` largest a row, ties to the lower key; all the causal keys
    of a row that has no more than ``topk``. The value of the ``topk``-th
    largest comes from ``lax.top_k``, the keys from comparisons with it, so
    no scatter is made."""
    if topk >= scores.shape[-1]:
        return scores > -jnp.inf
    kth = jax.lax.top_k(scores, topk)[0][..., -1:]
    # compared as ``lax.top_k`` orders them, -0.0 below +0.0
    key, kth_key = _total_order(scores), _total_order(kth)
    above = key > kth_key
    tied = (key == kth_key) & (kth > -jnp.inf)
    room = topk - above.sum(-1, keepdims=True)

    def by_index(tied):  # the lowest-index ties that fit
        return tied & (jnp.cumsum(tied, axis=-1, dtype=jnp.int32) <= room)

    # a tie at the boundary is rare among float32 sums: the running count
    # over 16,384 keys is made only for a block that has one
    over = jnp.any(tied.sum(-1, keepdims=True) > room)
    return above | jax.lax.cond(over, by_index, lambda t: t, tied)


def pack(keep):
    """(B, C, S) bool, C a multiple of 256 -> (B, C / 32, S) int32 in the
    kernels' layout: query ``256 i + 8 j + r`` is bit ``j`` of word row
    ``8 i + r``."""
    b, c, s = keep.shape
    bits = keep.reshape(b, c // 256, 32, 8, s).astype(jnp.uint32)
    words = jnp.sum(bits << jnp.arange(32, dtype=jnp.uint32)[:, None, None],
                    axis=2, dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(words, jnp.int32).reshape(
        b, c // 32, s)


def unpack(packed):
    """:func:`pack`'s inverse: (B, C / 32, S) int32 -> (B, C, S) bool."""
    b, w, s = packed.shape
    words, shift = jnp.broadcast_arrays(
        packed.reshape(b, w // 8, 1, 8, s),
        jnp.arange(32, dtype=jnp.int32)[:, None, None])
    bits = jax.lax.shift_right_logical(words, shift) & 1
    return bits.reshape(b, w * 32, s) != 0


def _blocks(t, chunk):
    """(B, S, ...) -> (S / chunk, B, chunk, ...)."""
    b, s = t.shape[:2]
    return jnp.moveaxis(t.reshape(b, s // chunk, chunk, *t.shape[2:]), 1, 0)


def _unblocks(t):
    """:func:`_blocks`' inverse."""
    n, b, c = t.shape[:3]
    return jnp.moveaxis(t, 0, 1).reshape(b, n * c, *t.shape[3:])


def selection(q_i, k_i, w, topk: int, chunk: int):
    """The selection of every query, bit-packed: (packed (B, S / 32, S)
    int32, selected keys summed over the queries). ``chunk`` queries a
    block (a multiple of 256 dividing S; the whole length where S is
    shorter). No gradient: the inputs are stopped."""
    q_i, k_i, w = jax.lax.stop_gradient((q_i, k_i, w))
    s = q_i.shape[1]
    chunk = min(chunk, s)

    def one(args):
        qb, wb, start = args
        with jax.named_scope("scores"):
            scores = index_scores(qb, k_i, wb, start)
        with jax.named_scope("select"):
            keep = select(scores, topk)
            return pack(keep), keep.sum(dtype=jnp.int32)

    packed, counts = jax.lax.map(
        one, (_blocks(q_i, chunk), _blocks(w, chunk),
              jnp.arange(0, s, chunk)))
    packed = checkpoint_name(_unblocks(packed), SELECTION)
    return packed, counts.sum()


def tiles_visited_pct(packed):
    """Of the (256 queries x ``BLOCK_K`` keys) tiles that hold a causal
    (query, key) pair, the share, in %, that hold a selected key: the tiles
    the Pallas core visits."""
    b, _, s = packed.shape
    block_k = min(BLOCK_K, s)
    tiles = dsa_core.tile_visits(packed, block_k)
    q_last = (jnp.arange(tiles.shape[1]) + 1) * dsa_core.BLOCK_Q - 1
    causal = jnp.arange(tiles.shape[2])[None, :] * block_k <= q_last[:, None]
    return 100.0 * jnp.sum(tiles, dtype=jnp.float32) / (b * causal.sum())


def sample_at(words: int, keys: int):
    """The word rows and keys of a packed selection (B, ``words``, ``keys``)
    that :func:`selection_sample` takes: :data:`SAMPLE_ROWS` rows and
    :data:`SAMPLE_KEYS` keys, each evenly spaced and ending at the last (all
    of them where there are fewer)."""

    def spaced(n, m):
        m = min(m, n)
        return (np.arange(1, m + 1) * n) // m - 1

    return spaced(words, SAMPLE_ROWS), spaced(keys, SAMPLE_KEYS)


def selection_sample(packed):
    """A fixed sample of the packed selection, (B, rows, keys) int32 at
    :func:`sample_at`'s words: 32 queries a word, 16 word rows by 256 keys
    at 16,384 positions, 16 KB. A step carries it out beside its counters,
    so that the selection it used can be set beside another's."""
    rows, keys = sample_at(*packed.shape[1:])
    return packed[:, rows][:, :, keys]


def sparse_core_xla(q, k, v, keep):
    """The dense masked form of :func:`sparse_core`: ``q`` (B, H, S, D), ``k``
    and ``v`` (B, G, S, D), ``keep`` (B, S, S) bool. Float32 scores and
    softmax. Returns (output, log-sum-exp (B, H, S) float32)."""
    b, h, s, d = q.shape
    g = k.shape[1]
    grouped = q.reshape(b, g, h // g, s, d)
    logits = jnp.einsum("bgrqd,bgkd->bgrqk", grouped, k,
                        preferred_element_type=F32)
    logits = jnp.where(keep[:, None, None], logits, -jnp.inf)
    lse = jax.nn.logsumexp(logits, axis=-1)
    p = jnp.exp(logits - lse[..., None])
    out = jnp.einsum("bgrqk,bgkd->bgrqd", p.astype(v.dtype), v)
    return (out.reshape(b, h, s, v.shape[-1]),
            jax.lax.stop_gradient(lse.reshape(b, h, s)))


def sparse_core(q, k, v, packed):
    """``softmax(q k^T)`` over each query's selected keys, times ``v``; and
    the log-sum-exp of those scores (B, H, S) float32, which carries no
    gradient. ``q`` (B, H, S, D) carries the softmax scale; ``k``, ``v``
    (B, G, S, D), query head h reading key/value head h // (H / G);
    ``packed`` from :func:`selection`. The Pallas kernels where
    :func:`core_kernel_takes`, the dense masked form elsewhere. Returns
    (output, log-sum-exp, whether the kernels ran)."""
    b, h, s, _ = q.shape
    if core_kernel_takes(s, h, k.shape[1]):
        return (*dsa_core.sparse_core(q, k, v, packed, min(BLOCK_K, s)),
                True)
    return (*sparse_core_xla(q, k, v, unpack(packed)), False)


def _loss_block(args, k_i, k):
    """One block of :func:`indexer_loss`: the KL divergences of its queries,
    summed."""
    qb_i, wb, qb, lse_b, words, start = args
    keep = unpack(words)  # (B, C, S)
    scores = index_scores(qb_i, k_i, wb, start)
    masked = jnp.where(keep, scores, -jnp.inf)
    log_q = masked - jax.nn.logsumexp(masked, axis=-1, keepdims=True)
    # the main attention's probabilities, summed over the query heads
    b, g, rep = qb.shape[0], k.shape[1], qb.shape[1] // k.shape[1]
    logits = jnp.einsum(
        "bgrcd,bgsd->bgrcs",
        qb.reshape(b, g, rep, *qb.shape[2:]), k, preferred_element_type=F32)
    lse = lse_b.reshape(b, g, rep, -1)[..., None]
    p = jnp.sum(jnp.where(keep[:, None, None], jnp.exp(logits - lse), 0.0),
                axis=(1, 2))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    terms = jnp.where(keep & (p > 0), p * (jnp.log(
        jnp.where(p > 0, p, 1.0)) - jnp.where(keep, log_q, 0.0)), 0.0)
    return jnp.sum(terms)


def indexer_loss(q_i, k_i, w, q, k, lse, packed):
    """``mean_t KL(p_t || softmax_{s in S_t} I[t, s])`` over the B x S
    queries, float32: ``q_i``, ``k_i``, ``w`` as :func:`index_scores` takes
    them over the whole length, ``q`` (B, H, S, D) and ``k`` (B, G, S, D) the
    core's, ``lse`` its log-sum-exp, ``packed`` the selection. ``q``, ``k``
    and ``lse`` are stopped: the gradient reaches the indexer alone. In
    blocks of :data:`LOSS_CHUNK` queries, each formed again in the backward
    pass."""
    q, k, lse = jax.lax.stop_gradient((q, k, lse))
    b, s = q_i.shape[:2]
    chunk = min(LOSS_CHUNK, s)
    body = jax.checkpoint(lambda args: _loss_block(args, k_i, k))
    sums = jax.lax.map(body, (
        _blocks(q_i, chunk), _blocks(w, chunk),
        _blocks(q.swapaxes(1, 2), chunk).swapaxes(2, 3),
        _blocks(lse.swapaxes(1, 2), chunk).swapaxes(2, 3),
        _blocks(packed, chunk // 32),
        jnp.arange(0, s, chunk)))
    return jnp.sum(sums) / (b * s)
