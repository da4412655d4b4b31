"""The ``topk``-th largest score of each row as one Pallas pass: the
threshold of ``ops/dsa.py`` ``select``, found exactly by bisection over the
scores' order keys, where ``lax.top_k`` sorts the whole row.

A score's order key (:func:`order_key`) is an int32 in the float's order,
-0.0 below +0.0, ``-inf`` below every finite score: the order ``lax.top_k``
sorts by. Read as unsigned, ``key ^ 0x80000000`` keeps that order, so the
row's ``topk``-th largest key is the largest unsigned ``u`` that at least
``topk`` keys reach. It is built most significant bit first:
a trial sets the next bit on the bits kept so far, and the bit stays where
at least ``topk`` of the row's keys are at or above the trial. 32 counts a
row, each one compare-and-add a key from VMEM, and the answer is exact: the
very key of ``lax.top_k(scores, topk)[0][..., -1]``, ties and signed zeros
included, and ``-inf``'s key in a row with fewer than ``topk`` finite
scores. The unsigned order is never formed: ``u >= trial`` unsigned is
``key >= trial ^ 0x80000000`` signed.

A grid step holds :data:`ROWS` rows whole in VMEM (16,384 float32 scores a
row at the fifth language model's cell: 2 MB a step) and their keys in a
scratch of the same size, so the scores are read from HBM once. Each count
compares the whole tile of keys and sums over the lanes. The answer is
written broadcast over a (rows, 128) tile of lanes and sliced outside.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# score rows a grid step: on a v5e a block of 512 x 16,384 took 0.30 ms at
# 32 rows, 0.35 at 16 and 0.62 at 8 (fewer rows, more grid steps)
ROWS = 32
LANES = 128
# the most scores a grid step holds: with their second buffer and the keys'
# scratch 6 MB of VMEM, within the 16 MB a kernel may take
TILE_BYTES = 2 << 20
SIGN = -(1 << 31)  # 0x80000000 as an int32

_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel"))


def order_key(x):
    """float32 -> int32 keys in the order ``lax.top_k`` sorts by: the
    float's order, with -0.0 below +0.0."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def _kernel(x_ref, out_ref, key_ref, *, topk: int):
    key_ref[...] = order_key(x_ref[...])

    def trial(i, kept):
        bit = jax.lax.shift_left(jnp.int32(1), 31 - i)
        tried = kept | bit
        count = jnp.sum((key_ref[...] >= (tried ^ SIGN)).astype(jnp.int32),
                        axis=1, keepdims=True)
        return jnp.where(count >= topk, tried, kept)

    kept = jax.lax.fori_loop(0, 32, trial,
                             jnp.zeros((x_ref.shape[0], 1), jnp.int32))
    out_ref[...] = jnp.broadcast_to(kept ^ SIGN, out_ref.shape)


def kth_key(scores, topk: int, interpret: bool = False):
    """(B, C, S) float32 scores (``-inf`` off the causal part; no NaN), C a
    multiple of :data:`ROWS`, S of 128, ``topk`` below S -> (B, C) int32:
    the order key of each row's ``topk``-th largest score."""
    b, c, s = scores.shape
    out = pl.pallas_call(
        functools.partial(_kernel, topk=topk), grid=(b, c // ROWS),
        in_specs=[pl.BlockSpec((None, ROWS, s), lambda z, r: (z, r, 0))],
        out_specs=pl.BlockSpec((None, ROWS, LANES), lambda z, r: (z, r, 0)),
        out_shape=jax.ShapeDtypeStruct((b, c, LANES), jnp.int32),
        scratch_shapes=[pltpu.VMEM((ROWS, s), jnp.int32)],
        compiler_params=_PARAMS, interpret=interpret, name="dsa_select_kth",
    )(scores)
    return out[..., 0]
