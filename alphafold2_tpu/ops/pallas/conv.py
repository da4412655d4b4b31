"""The causal depthwise convolution of ``ops/ssm.py`` (then SiLU) as a
forward and a backward Pallas kernel: one pass over ``x`` each, with no
padded copy and no float32 copy of it in HBM.

The arithmetic and the casts are ``ops/ssm.py`` ``causal_conv_xla``'s (read
its docstring): ``x`` is read in its own type and taken to float32, the
pre-activation ``bias + sum_j w[:, j] x[t - (K - 1) + j]`` is summed in
float32 in the same order, SiLU follows, and the output is cast to ``x``'s
type. What changes is how often the data crosses HBM. Autodiff through the
XLA form writes the upcast, the padded copy and each product to memory and
reads them back, forward, in the layer's recomputation and backward; here
the forward reads ``x`` once and writes the output once, and the backward
reads ``x`` and the output's cotangent once, forms the pre-activation again
in VMEM, and writes ``dx`` (in ``x``'s type), ``dw`` and ``db`` (float32).

A grid step holds one (batch, block of channel rows) and the WHOLE sequence,
time the minor axis: ``x`` (rows, T). Time minor is how XLA lays out the
state-space layer around the convolution (the projection's output, the
scan's operands, the gated norm), so the operands reach the kernels by
transposes that are changes of layout and no copies. The convolved channels
may be a run of a wider array (the layer's projection holds them between
the gate's and the time steps'): the blocks are read where they lie, so no
sliced copy is made, and ``x``'s cotangent is zero outside them. A tap's
shift is a roll of the block along its lanes with the steps it wraps masked
to zero: ``x[t - s]`` is zero for ``t < s`` (before the sequence's start),
and the backward's ``dpre[t + s]`` zero for ``t >= T - s``. With the
sequence whole in the block no halo crosses grid steps, and each step owns
its rows' ``dw`` and ``db`` (one row a batch, summed in XLA over the batch).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32

# a grid step's channel rows (two bfloat16 tiles) and the most rows x steps
# it holds: a float32 temporary of a block is at most 1 MB, 32 rows of
# 8,192 steps
ROWS = 32
BLOCK_ELEMENTS = 1 << 18


def _behind(v, s: int):
    """``v[:, t - s]``, zero for ``t < s``."""
    if s == 0:
        return v
    t = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    return jnp.where(t >= s, pltpu.roll(v, s, 1), 0.0)


def _ahead(v, s: int):
    """``v[:, t + s]``, zero for ``t >= T - s``."""
    if s == 0:
        return v
    length = v.shape[1]
    t = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    return jnp.where(t < length - s, pltpu.roll(v, length - s, 1), 0.0)


def _pre_activation(x_ref, w_ref, b_ref):
    """(x's taps, ``x[t - (K - 1) + j]`` for each j, and ``bias + sum_j
    w[:, j] x[t - (K - 1) + j]``), float32, summed as the XLA form sums."""
    x = x_ref[...].astype(F32)
    w = w_ref[...]
    taps = w.shape[1]
    shifted = [_behind(x, taps - 1 - j) for j in range(taps)]
    pre = b_ref[...]
    for j in range(taps):
        pre = pre + w[:, j:j + 1] * shifted[j]
    return shifted, pre


def _fwd_kernel(x_ref, w_ref, b_ref, y_ref):
    _, pre = _pre_activation(x_ref, w_ref, b_ref)
    y_ref[...] = jax.nn.silu(pre).astype(y_ref.dtype)


def _bwd_kernel(x_ref, w_ref, b_ref, dy_ref, dx_ref, dw_ref, db_ref):
    shifted, pre = _pre_activation(x_ref, w_ref, b_ref)
    w = w_ref[...]
    taps = w.shape[1]
    sig = jax.nn.sigmoid(pre)
    dpre = dy_ref[...].astype(F32) * (sig + pre * sig * (1.0 - sig))
    dx = w[:, taps - 1:taps] * dpre
    for j in range(taps - 2, -1, -1):
        dx = dx + w[:, j:j + 1] * _ahead(dpre, taps - 1 - j)
    dx_ref[...] = dx.astype(dx_ref.dtype)
    lane = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
    dw = jnp.zeros(w.shape, F32)
    for j in range(taps):
        dw = jnp.where(lane == j, jnp.sum(dpre * shifted[j], axis=1,
                                          keepdims=True), dw)
    dw_ref[...] = dw
    db_ref[...] = jnp.sum(dpre, axis=1, keepdims=True)


def _specs(length, taps, start):
    """(a (batch z, row block c) step's block of x, read ``start`` rows on;
    of y and its cotangent; of the weight, of the bias; and of the
    per-batch dw and db)."""
    offset = start // ROWS
    x = pl.BlockSpec((None, ROWS, length), lambda z, c: (z, c + offset, 0))
    by_time = pl.BlockSpec((None, ROWS, length), lambda z, c: (z, c, 0))
    weight = pl.BlockSpec((ROWS, taps), lambda z, c: (c, 0))
    bias = pl.BlockSpec((ROWS, 1), lambda z, c: (c, 0))
    dweight = pl.BlockSpec((None, ROWS, taps), lambda z, c: (z, c, 0))
    dbias = pl.BlockSpec((None, ROWS, 1), lambda z, c: (z, c, 0))
    return x, by_time, weight, bias, dweight, dbias


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel"))


def _forward(x, w, b, start, interpret):
    batch, length = x.shape[0], x.shape[2]
    channels, taps = w.shape
    x_spec, by_time, weight, bias, _, _ = _specs(length, taps, start)
    return pl.pallas_call(
        _fwd_kernel, grid=(batch, channels // ROWS),
        in_specs=[x_spec, weight, bias], out_specs=by_time,
        out_shape=jax.ShapeDtypeStruct((batch, channels, length), x.dtype),
        compiler_params=_PARAMS, interpret=interpret, name="causal_conv_fwd",
    )(x, w, b)


def _backward(x, w, b, dy, start, interpret):
    batch, length = x.shape[0], x.shape[2]
    channels, taps = w.shape
    x_spec, by_time, weight, bias, dweight, dbias = _specs(length, taps, start)
    dx, dw, db = pl.pallas_call(
        _bwd_kernel, grid=(batch, channels // ROWS),
        in_specs=[x_spec, weight, bias, by_time],
        out_specs=[by_time, dweight, dbias],
        out_shape=[jax.ShapeDtypeStruct(dy.shape, x.dtype),
                   jax.ShapeDtypeStruct((batch, channels, taps), F32),
                   jax.ShapeDtypeStruct((batch, channels, 1), F32)],
        compiler_params=_PARAMS, interpret=interpret, name="causal_conv_bwd",
    )(x, w, b, dy)
    # x's cotangent is zero outside the convolved rows
    dx = jnp.pad(dx, ((0, 0), (start, x.shape[1] - start - channels), (0, 0)))
    return dx, dw.sum(0), db.sum(0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _conv(x, w, b, start, interpret):
    """The kernels' own layouts: ``x`` (B, C', T) of which rows ``start`` ..
    ``start + C`` are convolved, ``w`` (C, K) and ``b`` (C, 1) float32;
    returns (B, C, T) in ``x``'s type."""
    return _forward(x, w, b, start, interpret)


def _conv_fwd(x, w, b, start, interpret):
    return _forward(x, w, b, start, interpret), (x, w, b)


def _conv_bwd(start, interpret, kept, dy):
    return _backward(*kept, dy, start, interpret)


_conv.defvjp(_conv_fwd, _conv_bwd)


def causal_conv(x, weight, bias, start=0, interpret=None):
    """``ops/ssm.py`` ``causal_conv_xla`` through the kernels: ``x`` (B, T,
    C') of which the channels ``start`` .. ``start + C`` are convolved (the
    kernels read them where they lie, so a projection whose output holds
    them among others is not sliced first), ``weight`` (C, K) with C and
    ``start`` multiples of ``ROWS`` and K at most 128, ``bias`` (C,); T a
    multiple of 128. ``x``'s type out, (B, T, C), and the cotangents of ``weight``
    and ``bias`` in their own types. ``interpret=None`` compiles on a TPU
    and interprets elsewhere."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    y = _conv(jnp.swapaxes(x, 1, 2), weight.astype(F32),
              bias.astype(F32)[:, None], start, bool(interpret))
    return jnp.swapaxes(y, 1, 2)
