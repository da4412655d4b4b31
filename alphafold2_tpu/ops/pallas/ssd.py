"""The chunked state-space scan of ``ops/ssm.py`` as a forward and a
backward Pallas kernel: a chunk's decay matrix, its masked scores and the
state carried between chunks stay in VMEM.

The algorithm, its casts and its returns are ``ops/ssm.py``'s (read that
module's docstring first). What changes is where the temporaries live. The
XLA form writes, a layer, a (chunk x chunk) decay and score matrix for every
head of every chunk to HBM (268 MB each in float32 at 8,192 steps of 64
heads) and reads them back for four batched products; here a grid step
holds one (batch, group, chunk), builds those matrices a head at a time in
registers, and carries the group's state (heads x width by state rows,
float32) in a VMEM scratch along the innermost, sequential chunk axis. A
group of more than eight heads (one group of 64 is the dense hybrid's) is
walked eight heads a grid step (``heads_a_step``): the grid's second axis is
then the head blocks, each reading its group's B and C, and the backward
writes one float32 part of ``dB`` and ``dC`` a head block, summed in XLA.

A grid step's blocks, ``rep`` heads sharing one group's B and C, have time
(a chunk's steps) as the minor axis, as the XLA form's arrays have and as
XLA lays out the layer around the scan (the projections' outputs, the
convolution, the gated norm): the operands reach the kernels by transposes
that are changes of layout and no copies.

- ``x`` (rep * width, chunk): a head is ``width`` rows, so a head's slice
  lies on the sublane grid and a sum over a head's width is a sum over rows;
  ``B``, ``C`` (state, chunk);
- ``dt`` and the chunk's cumulative sums of ``dt A`` (rep, chunk), whose row
  r spread over the sublanes is ``cum_i`` at [j, i]; and the cumulative sums
  once more as (chunk, rep), whose column r spread over the lanes is
  ``cum_j``. All are made in XLA (2 MB a layer); the decay is ``exp`` of the
  difference inside the mask;
- every matrix is held transposed: the scores at [j, i], a head's output
  ``(dt x)^T scores`` (width x chunk), the state (rep * width, state).

The forward walks the chunks first to last and writes ``y``, the final
state and, when the backward pass will follow, the state entering every
chunk in the compute type (what ``C H`` reads). The backward walks them last
to first carrying the state's cotangent, forms each chunk's decay and scores
again from the cumulative sums, and writes the cotangents of ``x``, ``B``,
``C``, ``dt`` (its direct part) and the cumulative sums (a column part and a
row part, added in XLA); the cumulative sum's own transpose, and with it
``dA`` and the rest of ``ddt``, is autodiff's in XLA over 2 MB.

Cotangents enter the backward products in the compute type, as the matrix
unit's default precision takes float32 operands of the XLA form's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32


def _mm(lhs, rhs, lhs_axis: int, rhs_axis: int):
    """A product contracting one axis of each operand, float32 out."""
    return jax.lax.dot_general(
        lhs, rhs, (((lhs_axis,), (rhs_axis,)), ((), ())),
        preferred_element_type=F32)


def _over_rows(rows, width: int):
    """(rep, n) -> (rep * width, n): head r's row on each of its rows."""
    return jnp.concatenate(
        [jnp.broadcast_to(rows[r:r + 1], (width, rows.shape[1]))
         for r in range(rows.shape[0])], axis=0)


def _head_sums(values, width: int):
    """(rep * width, n) -> (rep, n): each head's sum over its rows."""
    rep = values.shape[0] // width
    row = jax.lax.broadcasted_iota(jnp.int32, (rep, values.shape[1]), 0)
    out = jnp.zeros((rep, values.shape[1]), F32)
    for r in range(rep):
        head = jnp.sum(
            values[r * width:(r + 1) * width], axis=0, keepdims=True)
        out = jnp.where(row == r, head, out)
    return out


def _chunk_terms(x_ref, dt_ref, cum_ref, width):
    """What both kernels make of a chunk's x, dt and cumulative sums, each
    (rep * width, chunk) with a head's value on its rows: x in float32,
    ``dt x``, ``dt``, ``exp(cum)``, ``exp(cum_last - cum)``; and ``exp(
    cum_last)`` (rep * width, 1)."""
    x = x_ref[...].astype(F32)
    cum = cum_ref[...]
    last = cum[:, -1:]
    dt_e = _over_rows(dt_ref[...], width)
    ecum_e = _over_rows(jnp.exp(cum), width)
    toend_e = _over_rows(jnp.exp(last - cum), width)
    elast_e = _over_rows(jnp.exp(last), width)
    return x, dt_e * x, dt_e, ecum_e, toend_e, elast_e


def _decay_and_scores(cum, cum_t, cb_t, r: int, later):
    """Head r's decay ``exp(cum_i - cum_j)`` at [j, i], from the difference
    inside the mask (never a ratio of exponentials, never the triangle of
    i < j), and the decay times ``(C B^T)^T``, both float32."""
    gap = cum[r:r + 1, :] - cum_t[:, r:r + 1]
    decay = jnp.exp(jnp.where(later, gap, -jnp.inf))
    return decay, decay * cb_t


def _later_or_same(chunk: int):
    """[j, i]: step i (a lane) is step j (a sublane) or a later one."""
    j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    return jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1) >= j


def _fwd_kernel(x_ref, b_ref, c_ref, dt_ref, cum_ref, cum_t_ref, h0_ref,
                y_ref, ht_ref, *rest, width, dtype):
    *entering_ref, h_scr = rest  # the entering states only when kept
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        h_scr[...] = h0_ref[...]

    rep, chunk = dt_ref.shape
    _, dtx, _, ecum_e, toend_e, elast_e = _chunk_terms(
        x_ref, dt_ref, cum_ref, width)
    b, c = b_ref[...], c_ref[...]
    cum, cum_t = cum_ref[...], cum_t_ref[...]
    entering = h_scr[...].astype(dtype)
    if entering_ref:
        entering_ref[0][...] = entering
    cb_t = _mm(b, c, 0, 0)  # [j, i] = C_i . B_j
    carried = ecum_e * _mm(entering, c, 1, 0)
    u = dtx.astype(dtype)
    later = _later_or_same(chunk)
    for r in range(rep):
        rows = slice(r * width, (r + 1) * width)
        _, scores = _decay_and_scores(cum, cum_t, cb_t, r, later)
        y_ref[rows, :] = carried[rows] + _mm(
            u[rows], scores.astype(dtype), 1, 0)
    outgoing = _mm((toend_e * dtx).astype(dtype), b, 1, 1)
    h_scr[...] = elast_e * h_scr[...] + outgoing

    @pl.when(k == pl.num_programs(2) - 1)
    def _():
        ht_ref[...] = h_scr[...]


def _bwd_kernel(x_ref, b_ref, c_ref, dt_ref, cum_ref, cum_t_ref,
                entering_ref, dy_ref, dht_ref,
                dx_ref, db_ref, dc_ref, ddt_ref, dcum_ref, dcum_t_ref,
                dh0_ref, dh_scr, *, width, dtype):
    k = pl.program_id(2)  # chunk chunks - 1 - k: the index maps walk back

    @pl.when(k == 0)
    def _():
        dh_scr[...] = dht_ref[...]

    rep, chunk = dt_ref.shape
    x, dtx, dt_e, ecum_e, toend_e, elast_e = _chunk_terms(
        x_ref, dt_ref, cum_ref, width)
    b, c = b_ref[...], c_ref[...]
    cum, cum_t = cum_ref[...], cum_t_ref[...]
    entering = entering_ref[...]
    dh = dh_scr[...]  # the cotangent of the state leaving this chunk
    dh_c = dh.astype(dtype)
    dy = dy_ref[...]
    dy_c = dy.astype(dtype)
    u = dtx.astype(dtype)
    to_state = (toend_e * dtx).astype(dtype)

    cb_t = _mm(b, c, 0, 0)
    d_to_state = _mm(dh_c, b, 1, 0)  # (rep * width, chunk)
    edy = ecum_e * dy
    edy_c = edy.astype(dtype)
    later = _later_or_same(chunk)
    row = jax.lax.broadcasted_iota(jnp.int32, (rep, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, rep), 1)
    dcb_t = jnp.zeros((chunk, chunk), F32)
    dcum = jnp.zeros((rep, chunk), F32)
    dcum_t = jnp.zeros((chunk, rep), F32)
    du = []
    for r in range(rep):
        rows = slice(r * width, (r + 1) * width)
        decay, scores = _decay_and_scores(cum, cum_t, cb_t, r, later)
        dscores = _mm(u[rows], dy_c[rows], 0, 0)  # [j, i]
        # d/d cum_i of exp(cum_i - cum_j) is the entry, of cum_j minus it
        through_decay = dscores * scores
        dcum = jnp.where(
            row == r, jnp.sum(through_decay, axis=0, keepdims=True), dcum)
        dcum_t = jnp.where(
            col == r, -jnp.sum(through_decay, axis=1, keepdims=True), dcum_t)
        dcb_t = dcb_t + dscores * decay
        du.append(_mm(dy_c[rows], scores.astype(dtype), 1, 1))
    ddtx = toend_e * d_to_state + jnp.concatenate(du, axis=0)
    dx_ref[...] = (dt_e * ddtx).astype(dx_ref.dtype)
    ddt_ref[...] = _head_sums(ddtx * x, width)

    # exp(cum) of the carried term, exp(cum_last - cum) of the outgoing
    # state's, and exp(cum_last) twice: there and on the state carried past
    through_carried = _head_sums(edy * _mm(entering, c, 1, 0), width)
    through_to_end = _head_sums(toend_e * d_to_state * dtx, width)
    kept = jnp.sum(_head_sums(dh * entering.astype(F32), width),
                   axis=1, keepdims=True)
    dlast = jnp.sum(through_to_end, axis=1, keepdims=True) \
        + jnp.exp(cum[:, -1:]) * kept
    step = jax.lax.broadcasted_iota(jnp.int32, (rep, chunk), 1)
    dcum_ref[...] = dcum + through_carried - through_to_end \
        + jnp.where(step == chunk - 1, dlast, 0.0)
    dcum_t_ref[...] = dcum_t

    dcb_c = dcb_t.astype(dtype)
    dc_ref[...] = (_mm(b, dcb_c, 1, 0) + _mm(entering, edy_c, 0, 0)).astype(
        dc_ref.dtype)
    db_ref[...] = (_mm(c, dcb_c, 1, 1) + _mm(dh_c, to_state, 0, 0)).astype(
        db_ref.dtype)
    dh_scr[...] = elast_e * dh + _mm(edy_c, c, 1, 1)

    @pl.when(k == pl.num_programs(2) - 1)
    def _():
        dh0_ref[...] = dh_scr[...]


def _specs(chunk, rep, rows, n, chunk_of, blocks):
    """The block of one (batch z, head block g, grid step k) in each layout;
    ``chunk_of(k)`` is the chunk that step visits. ``shared`` is the block
    of B or C, which the ``blocks`` head blocks of one group all read."""
    def by_time(size):  # (B, G * size, T)
        return pl.BlockSpec(
            (None, size, chunk), lambda z, g, k: (z, g, chunk_of(k)))

    def shared(size):  # (B, G / blocks * size, T)
        if blocks == 1:
            return by_time(size)
        return pl.BlockSpec(
            (None, size, chunk),
            lambda z, g, k: (z, g // blocks, chunk_of(k)))

    cum_t = pl.BlockSpec(  # (B, G, T, rep)
        (None, None, chunk, rep), lambda z, g, k: (z, g, chunk_of(k), 0))
    state = pl.BlockSpec(  # (B, G * rows, n): one block a (z, g)
        (None, rows, n), lambda z, g, k: (z, g, 0))
    entering = pl.BlockSpec(  # (B, chunks, G * rows, n)
        (None, None, rows, n), lambda z, g, k: (z, chunk_of(k), g, 0))
    return by_time, shared, cum_t, state, entering


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _sizes(x, b, dt, spec):
    """(batch, head blocks, chunks, a head block's rows of x, state rows)."""
    chunk, rep, width, _, _, blocks = spec
    groups = dt.shape[1] // rep
    return (x.shape[0], groups, x.shape[2] // chunk, rep * width,
            b.shape[1] * blocks // groups)


def _cum_t(cum, rep):  # (B, H, T) -> (B, G, T, rep)
    batch, heads, length = cum.shape
    return jnp.swapaxes(
        cum.reshape(batch, heads // rep, rep, length), 2, 3)


def _forward(x, b, c, dt, cum, state, spec, keep: bool):
    chunk, rep, width, dtype, interpret, blocks = spec
    batch, groups, chunks, rows, n = _sizes(x, b, dt, spec)
    by_time, shared, cum_t, whole, entering = _specs(
        chunk, rep, rows, n, lambda k: k, blocks)
    out_shape = [jax.ShapeDtypeStruct(x.shape, F32),
                 jax.ShapeDtypeStruct(state.shape, F32)]
    out_specs = [by_time(rows), whole]
    if keep:
        out_shape.append(jax.ShapeDtypeStruct(
            (batch, chunks) + state.shape[1:], dtype))
        out_specs.append(entering)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, width=width, dtype=dtype),
        grid=(batch, groups, chunks),
        in_specs=[by_time(rows), shared(n), shared(n), by_time(rep),
                  by_time(rep), cum_t, whole],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((rows, n), F32)],
        compiler_params=_PARAMS, interpret=interpret, name="ssd_chunk_fwd",
    )(x, b, c, dt, cum, _cum_t(cum, rep), state)


def _backward(x, b, c, dt, cum, entering, dy, dstate, spec):
    chunk, rep, width, dtype, interpret, blocks = spec
    batch, groups, chunks, rows, n = _sizes(x, b, dt, spec)
    by_time, shared, cum_t, whole, entering_spec = _specs(
        chunk, rep, rows, n, lambda k: chunks - 1 - k, blocks)
    cum_t_shape = (batch, groups, x.shape[2], rep)
    # one head block a group writes dB and dC themselves; several each write
    # a float32 part, summed below
    if blocks == 1:
        db_dc = [jax.ShapeDtypeStruct(t.shape, t.dtype) for t in (b, c)]
    else:
        db_dc = [jax.ShapeDtypeStruct(
            (batch, groups * n, x.shape[2]), F32)] * 2
    dx, db, dc, ddt, dcum, dcum_t, dentering = pl.pallas_call(
        functools.partial(_bwd_kernel, width=width, dtype=dtype),
        grid=(batch, groups, chunks),
        in_specs=[by_time(rows), shared(n), shared(n), by_time(rep),
                  by_time(rep), cum_t, entering_spec, by_time(rows), whole],
        out_specs=[by_time(rows), by_time(n), by_time(n), by_time(rep),
                   by_time(rep), cum_t, whole],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   *db_dc,
                   jax.ShapeDtypeStruct(dt.shape, F32),
                   jax.ShapeDtypeStruct(cum.shape, F32),
                   jax.ShapeDtypeStruct(cum_t_shape, F32),
                   jax.ShapeDtypeStruct(dstate.shape, F32)],
        scratch_shapes=[pltpu.VMEM((rows, n), F32)],
        compiler_params=_PARAMS, interpret=interpret, name="ssd_chunk_bwd",
    )(x, b, c, dt, cum, _cum_t(cum, rep), entering, dy, dstate)
    dcum = dcum + jnp.swapaxes(dcum_t, 2, 3).reshape(cum.shape)
    if blocks > 1:
        db, dc = (
            t.reshape(batch, groups // blocks, blocks, n, -1).sum(2).reshape(
                b.shape).astype(b.dtype) for t in (db, dc))
    return dx, db, dc, ddt, dcum, dentering


HEADS_A_STEP = 8


def heads_a_step(rep: int) -> tuple:
    """(the heads one grid step holds, the grid steps a group's ``rep``
    heads are spread over). A group of more than ``HEADS_A_STEP`` heads in
    whole eights is walked eight heads a grid step: at one group of 64 heads
    of 64 and a chunk of 256 a step would otherwise hold 4,096 rows of x,
    y and their cotangents (21 MB of blocks against 16 MB of VMEM) and
    unroll 64 heads. The blocks of a group read the same B and C."""
    if rep > HEADS_A_STEP and rep % HEADS_A_STEP == 0:
        return HEADS_A_STEP, rep // HEADS_A_STEP
    return rep, 1


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _chunks(x, b, c, dt, cum, state, spec):
    """The kernels' own layouts, time the minor axis of all but the state:
    ``x`` (B, H * P, T); ``b``, ``c`` (B, G * N, T) in the compute type;
    ``dt`` and ``cum`` (B, H, T) float32; ``state`` (B, H * P, N) float32.
    Returns (y (B, H * P, T) float32, the final state in ``state``'s
    layout). ``spec`` is (chunk, the heads of a grid step, width, compute
    type, interpret, the grid steps a group's heads are spread over)."""
    return tuple(_forward(x, b, c, dt, cum, state, spec, keep=False))


def _chunks_fwd(x, b, c, dt, cum, state, spec):
    y, final, entering = _forward(x, b, c, dt, cum, state, spec, keep=True)
    return (y, final), (x, b, c, dt, cum, entering)


def _chunks_bwd(spec, kept, cotangents):
    return _backward(*kept, *cotangents, spec)


_chunks.defvjp(_chunks_fwd, _chunks_bwd)


def ssd_chunks(x, dt, a, b, c, chunk: int, dtype=F32, state=None,
               interpret=None):
    """``ops/ssm.py`` ``ssd_chunks_xla`` through the kernels: the same
    arguments (a length on the chunk grid) and returns. ``interpret=None``
    compiles on a TPU and interprets elsewhere."""
    batch, length, heads, width = x.shape
    groups, n = b.shape[2], b.shape[3]
    chunks = length // chunk
    rep, blocks = heads_a_step(heads // groups)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    def time_last(t):  # (B, T, ...) -> (B, the rest flattened, T)
        return jnp.swapaxes(t.reshape(batch, length, -1), 1, 2)

    dt = time_last(dt.astype(F32))
    cum = jnp.cumsum(
        (dt * a.astype(F32)[:, None]).reshape(batch, heads, chunks, chunk),
        axis=-1)
    if state is None:
        state = jnp.zeros((batch, heads, n, width), F32)
    # (B, H, N, P) <-> (B, H * P, N)
    state = jnp.swapaxes(state.astype(F32), 2, 3)
    y, state = _chunks(
        time_last(x), time_last(b.astype(dtype)), time_last(c.astype(dtype)),
        dt, cum.reshape(batch, heads, length),
        state.reshape(batch, heads * width, n),
        (chunk, rep, width, jnp.dtype(dtype), bool(interpret), blocks))
    state = jnp.swapaxes(state.reshape(batch, heads, width, n), 2, 3)
    return (jnp.swapaxes(y, 1, 2).reshape(batch, length, heads, width), state,
            jnp.moveaxis(jnp.exp(cum[..., -1]), 1, 2))
