"""The two pieces of a Mamba-2 state-space mixer that are not projections:
the causal depthwise convolution and the selective state-space scan in its
chunked ("state-space dual") form.

The recurrence, a head with scalar decay ``A < 0``, state ``H`` (N, P)::

    H_t = exp(dt_t A) H_{t-1} + dt_t B_t (x) x_t        H_0 = 0
    y_t = C_t^T H_t + D x_t

is 8,192 sequential rank-1 updates at the benchmark's length and does not
belong on the chip. In chunks of ``chunk`` steps it is four batched matrix
products and one short recurrence (Dao & Gu 2024, section 6), with ``cum_i``
the running sum of ``dt A`` inside a chunk:

- inside a chunk, ``y_i += sum_{j<=i} exp(cum_i - cum_j) (C_i . B_j) dt_j
  x_j``: ``C B^T`` a group (heads share B and C in groups), masked by the
  decay matrix ``L``, times ``dt x`` a head;
- each chunk's outgoing state ``S = sum_j exp(cum_last - cum_j) B_j (x) dt_j
  x_j``;
- the state entering chunk c, ``H_c = exp(cum_last) H_{c-1} + S_{c-1}``: a
  ``lax.scan`` over the chunks, float32. A scan and not
  ``lax.associative_scan``: it reads each chunk state once and writes each
  entering state once (2 x 2 MB a chunk at 64 heads of 128 x 64), where the
  log-depth form passes over all of them (134 MB at 64 chunks) at every
  level, forward and backward, to save 64 steps of a few microseconds; and
  its cost stays linear in the length, where a chunks x chunks decay matrix
  would not;
- the entering state's part, ``y_i += exp(cum_i) C_i^T H_c``.

Cumulative sums, exponentials and the carried state are float32; the
products take ``dtype`` operands and accumulate in float32. ``L`` is built
from differences of the cumulative sums inside the mask, never as a ratio of
exponentials: ``exp(cum_i) / exp(cum_j)`` is 0 / 0 as soon as a chunk's
decay underflows, and the masked-out upper triangle (positive differences)
is never exponentiated.

Two forms compute the chunks, one algorithm and the same casts, chosen by
``scan_kernel_takes`` from the backend and the shapes alone. On a TPU, at
shapes on the (8, 128) tile grid (the hybrid models': chunk 128, 128 state
rows, 8 heads of 64 a group; or chunk 256 and one group of 64 heads, eight
heads a grid step), a forward and a backward Pallas kernel
(``ops/pallas/ssd.py``) that keep a chunk's decay matrix, its scores and the
carried state in VMEM and form them again in the backward pass; there the
cumulative sum alone is XLA's and autodiff's. Everywhere else, and as the
second oracle of the tests, ``ssd_chunks_xla``: the batched products and the
``lax.scan`` below as XLA operations, whose backward pass is autodiff's
through them (each (chunk x chunk) matrix a head goes to HBM and back).

The convolution has two forms in the same way, chosen by
``conv_kernel_takes``: on a TPU, with the channels in whole blocks of 32
rows and the length on the lane grid, a forward and a backward Pallas kernel
(``ops/pallas/conv.py``) that read ``x`` once a pass and shift it on the
chip; everywhere else, and as the tests' oracle, ``causal_conv_xla``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from alphafold2_tpu.ops.pallas import conv, ssd


def conv_kernel_takes(shape, taps: int, start: int = 0) -> bool:
    """Whether the Pallas kernels of ``ops/pallas/conv.py`` serve a
    convolution of ``x`` (B, T, C) with ``taps`` taps (of its channels from
    ``start`` on): on a TPU, when the channels and ``start`` are multiples
    of ``conv.ROWS`` (a grid step's rows), the length a multiple of 128
    (whole lanes) and no longer than a block holds (``conv.BLOCK_ELEMENTS``:
    a grid step holds the whole sequence), and the taps at most 128 (a shift
    within one lane tile). Any other shape, and every backend but the TPU,
    runs ``causal_conv_xla``."""
    _, length, channels = shape
    rows = conv.ROWS
    return (jax.default_backend() == "tpu"
            and channels % rows == 0 and start % rows == 0
            and length % 128 == 0 and rows * length <= conv.BLOCK_ELEMENTS
            and taps <= 128)


def causal_conv(x, weight, bias, start: int = 0):
    """Depthwise causal convolution over time, then SiLU: ``silu(bias +
    sum_j weight[:, j] * x[t - (K - 1) + j])``, zeros before the sequence's
    start (``weight[:, K - 1]`` meets the current step), over the channels
    ``start`` .. ``start + C`` of ``x`` (B, T, C'). ``weight`` (C, K),
    ``bias`` (C,); (B, T, C) in ``x``'s type out. Through the kernels, which
    read those channels where they lie, where ``conv_kernel_takes`` the
    shape; elsewhere through ``causal_conv_xla`` on the slice."""
    channels, taps = weight.shape
    shape = x.shape[:2] + (channels,)
    if conv_kernel_takes(shape, taps, start):
        return conv.causal_conv(x, weight, bias, start)
    return causal_conv_xla(x[..., start:start + channels], weight, bias)


def causal_conv_xla(x, weight, bias):
    """``causal_conv`` as XLA operations: K shifted multiply-adds in float32
    over a padded copy, differentiated by autodiff."""
    taps = weight.shape[1]
    length = x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    out = bias.astype(jnp.float32)
    for j in range(taps):
        out = out + weight[:, j].astype(jnp.float32) \
            * padded[:, j:j + length]
    return jax.nn.silu(out).astype(x.dtype)


def scan_kernel_takes(x_shape, b_shape, chunk: int) -> bool:
    """Whether the Pallas kernels of ``ops/pallas/ssd.py`` serve a scan of
    ``x`` (B, T, H, P) with ``b`` (B, T, G, N): on a TPU, when the chunk and
    the state rows N are multiples of 128, a head's width P is a multiple
    of 16 and a group's heads H / G are a multiple of 8 (or there is one
    group), so that every block and every head's slice inside a grid step
    lies on the (8, 128) tile grid (16 rows a tile in bfloat16). A group of
    more than eight heads in whole eights (the dense hybrid's one group of
    64) is walked eight heads a grid step (``ssd.heads_a_step``). Any other
    shape, and every backend but the TPU, runs ``ssd_chunks_xla``."""
    heads, width = x_shape[2], x_shape[3]
    rep = heads // b_shape[2]
    return (jax.default_backend() == "tpu"
            and chunk % 128 == 0 and b_shape[3] % 128 == 0
            and width % 16 == 0 and (rep % 8 == 0 or rep == heads))


def ssd_scan(x, dt, a, b, c, chunk: int, dtype=jnp.float32, state=None):
    """The recurrence above without its ``D x`` term, in chunks.

    ``x`` (B, T, H, P); ``dt`` (B, T, H) float32, positive; ``a`` (H,)
    float32, negative; ``b``, ``c`` (B, T, G, N) with G dividing H (head h
    reads group h // (H / G)); ``state`` (B, H, N, P) float32 enters the
    first step (None: zeros). Returns (y (B, T, H, P) float32, the state
    after step T (B, H, N, P) float32, the chunks' decays ``exp(sum_chunk dt
    A)`` (B, T / chunk, H) float32).

    A length off the chunk grid is padded at the end with ``dt = 0`` steps,
    which leave the state alone (decay 1, nothing added), and the output is
    sliced. The chunks run through the kernels where ``scan_kernel_takes``
    the shapes and through ``ssd_chunks_xla`` elsewhere."""
    length = x.shape[1]
    pad = (-length) % chunk
    if pad:
        x, dt, b, c = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (x, dt, b, c))
    form = ssd.ssd_chunks if scan_kernel_takes(x.shape, b.shape, chunk) \
        else ssd_chunks_xla
    y, state, chunk_decay = form(x, dt, a, b, c, chunk, dtype, state)
    return y[:, :length] if pad else y, state, chunk_decay


def ssd_chunks_xla(x, dt, a, b, c, chunk: int, dtype=jnp.float32, state=None):
    """``ssd_scan`` at a length on the chunk grid as batched products and a
    ``lax.scan`` over the chunks, differentiated by autodiff."""
    batch, length, heads, width = x.shape
    groups, n = b.shape[2], b.shape[3]
    rep = heads // groups
    chunks = length // chunk

    def grouped(t, *tail):  # (B, T, ...) -> (B, chunks, chunk, G, ...)
        return t.reshape(batch, chunks, chunk, groups, *tail)

    # heads lead and a chunk's steps are the minor axes of everything built
    # elementwise: (..., 128, 128) tiles whole, (..., G, R) minor would not
    dt = dt.astype(jnp.float32)
    cum = jnp.cumsum(jnp.moveaxis(
        grouped(dt * a.astype(jnp.float32), rep), 2, -1), axis=-1)
    last = cum[..., -1]  # (B, chunks, G, R): the whole chunk's log-decay
    dx = grouped(dt[..., None] * x.astype(jnp.float32), rep, width)
    b, c = grouped(b.astype(dtype), n), grouped(c.astype(dtype), n)

    # inside the chunks: (L * C B^T) (dt x)
    steps = jnp.arange(chunk)
    gap = cum[..., :, None] - cum[..., None, :]  # (B, chunks, G, R, i, j)
    decay = jnp.exp(jnp.where(steps[:, None] >= steps[None, :], gap, -jnp.inf))
    cb = jnp.einsum("zkign,zkjgn->zkgij", c, b,
                    preferred_element_type=jnp.float32)
    scores = (decay * cb[:, :, :, None]).astype(dtype)
    y = jnp.einsum("zkgrij,zkjgrp->zkgrip", scores, dx.astype(dtype),
                   preferred_element_type=jnp.float32)

    # each chunk's outgoing state, and the recurrence over the chunks
    to_end = jnp.moveaxis(jnp.exp(last[..., None] - cum), -1, 2)[..., None]
    outgoing = jnp.einsum("zkjgn,zkjgrp->zkgrnp", b,
                          (to_end * dx).astype(dtype),
                          preferred_element_type=jnp.float32)
    chunk_decay = jnp.exp(last)

    def carry(entering, one):
        decay_k, outgoing_k = one
        return decay_k[..., None, None] * entering + outgoing_k, entering

    if state is None:
        state = jnp.zeros((batch, heads, n, width), jnp.float32)
    state, entering = jax.lax.scan(
        carry, state.reshape(batch, groups, rep, n, width),
        (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(outgoing, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)  # (B, chunks, G, R, N, P)

    # what the entering state adds: exp(cum_i) C_i^T H
    carried = jnp.einsum("zkign,zkgrnp->zkgrip", c, entering.astype(dtype),
                         preferred_element_type=jnp.float32)
    y = y + jnp.exp(cum)[..., None] * carried
    return (jnp.moveaxis(y, 4, 2).reshape(batch, length, heads, width),
            state.reshape(batch, heads, n, width),
            chunk_decay.reshape(batch, chunks, heads))
