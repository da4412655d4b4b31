"""Dense attention primitives: FeedForward (GEGLU), Attention, AxialAttention.

TPU-native re-design of reference ``alphafold2_pytorch/alphafold2.py``:

- :class:`FeedForward`   <- alphafold2.py:53-74 (GEGLU + projections)
- :class:`Attention`     <- alphafold2.py:78-182 (self/cross, tied-row,
  memory-compressed KV)
- :class:`AxialAttention`<- alphafold2.py:241-287

Design (not a port):
- The reference flattens the pair map to an N^2 token stream and re-views it
  inside every axial block (alphafold2.py:472,259). Here the pair rep is a
  (B, H, W, D) grid end-to-end; the axial passes are plain batched attention
  with the non-attended axis folded into batch — static reshapes XLA removes.
- Row/column attention passes use one shared q/k/v projection applied to the
  whole grid once (the reference projects separately inside each of the two
  Attention submodules; two projections are kept for parameter parity of the
  two axes, but each is applied to a (B*, n, d) view with no copies).
- Tied-row attention (MSA-Transformer style) is a single einsum contracting
  the row axis with the extra r^-0.5 scale (alphafold2.py:151) — XLA fuses it;
  under a mesh the row axis can be sharded and the logits psum'd
  (see parallel/).
- Memory-compressed cross-attention KV downsampling (alphafold2.py:100-137)
  uses a strided grouped conv (lax.conv via nn.Conv, feature_group_count =
  heads) with sum-pooled masks.
- Masking is additive (large negative) with mask combination OR-free:
  ``mask[..., :, None] & context_mask[..., None, :]``; the tied-row path
  additionally zeroes padded q/k/v entries so they abstain from the shared
  (row-summed) logits exactly.
- Compute dtype is configurable (bfloat16 on TPU); params stay float32.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from alphafold2_tpu.ops import flash

MASK_VALUE = -1e9


def _kernel_hook(kernel_fn, sharded: bool):
    """An ``attn_fn`` hook around a Pallas kernel. ``sharded=True`` means the
    hook already runs per device inside the grid shard_map; otherwise it is
    called on global arrays, where an active mesh needs the kernel wrapped
    (parallel.sharding.per_device)."""
    if sharded:
        return kernel_fn
    from alphafold2_tpu.parallel.sharding import per_device

    def attn_fn(q2, k2, v2, m2):
        return per_device(kernel_fn, q2, k2, v2, m2)

    return attn_fn


def grid_axial_project_attend(
    to_q, to_kv, to_out, heads, dim_head, x, mask, attend_axis, attn_fn,
    sharded,
):
    """Shared grid_axial body for Attention and SparseAttention: pointwise
    q/kv projections on the (possibly sharded) grid, one axial pass with
    the module's fused per-device kernel, output projection.

    ``sharded=True`` runs the pass as an explicit shard_map over an active
    (dp, spr, spc) mesh — correct ONLY for arrays laid out P(dp, spr, spc)
    (the pair stream under grid_parallel). ``sharded=False`` runs the
    meshless grid-native formulation; under jit, GSPMD handles whatever
    sharding the array actually has (e.g. the MSA stream)."""
    from alphafold2_tpu.parallel.grid_parallel import grid_axial_attention
    from alphafold2_tpu.parallel.sharding import active_mesh

    b, gh, gw, _ = x.shape
    q = to_q(x).reshape(b, gh, gw, heads, dim_head)
    k, v = jnp.split(to_kv(x), 2, axis=-1)
    k = k.reshape(b, gh, gw, heads, dim_head)
    v = v.reshape(b, gh, gw, heads, dim_head)
    out = grid_axial_attention(
        q, k, v, mask=mask, mesh=active_mesh() if sharded else None,
        attend_axis=attend_axis, attn_fn=attn_fn,
    )
    return to_out(out.reshape(b, gh, gw, heads * dim_head))


class FeedForward(nn.Module):
    """GEGLU feedforward: Linear(d -> 2*mult*d) -> gated GELU -> Linear(mult*d -> d).

    ``gelu_exact``: the reference's torch ``F.gelu`` is the exact erf form
    (alphafold2.py:57); jax defaults to the tanh approximation, which is
    the faster choice on TPU and stays the default here — the flag exists
    so matched head-to-heads can eliminate the one remaining systematic
    functional divergence from the reference block.
    """

    dim: int
    mult: int = 4
    dropout: float = 0.0
    gelu_exact: bool = False
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        inner = self.dim * self.mult
        h = nn.Dense(inner * 2, dtype=self.dtype, name="wi")(x)
        h, gates = jnp.split(h, 2, axis=-1)
        h = h * jax.nn.gelu(gates, approximate=not self.gelu_exact)
        h = nn.Dropout(self.dropout)(h, deterministic=deterministic)
        return nn.Dense(self.dim, dtype=self.dtype, name="wo")(h)


class Attention(nn.Module):
    """Multi-head attention with cross-attention, tied-row, and KV-compression.

    Feature parity with reference alphafold2.py:78-182:
    - ``context``/``context_mask`` for cross-attention
    - ``tie_dim``: fold a leading row axis (input (B*R, N, D)) into one shared
      attention matrix with r^-0.5 scaling. Unlike the reference (which
      forbids padding under tied rows, alphafold2.py:147-149), masks are
      handled here: padded (row, position) entries abstain from the shared
      logits and the row-count scale counts only voting rows. This equals
      attention on the cropped array when rows agree on masked positions
      (column padding — what MSA length padding is — and fully-masked
      rows); genuinely ragged per-row masks degrade gracefully (masked
      entries abstain) but have no cropped-array equivalent
    - ``compress_ratio`` > 1: strided grouped-conv KV compression (cross only)
    """

    dim: int
    heads: int = 8
    dim_head: int = 64
    dropout: float = 0.0
    compress_ratio: int = 1
    context_parallel: Optional[str] = None  # None | "ring" | "ulysses"
    dtype: jnp.dtype = jnp.float32

    def setup(self):
        inner = self.heads * self.dim_head
        self.to_q = nn.Dense(inner, use_bias=False, dtype=self.dtype)
        self.to_kv = nn.Dense(inner * 2, use_bias=False, dtype=self.dtype)
        self.to_out = nn.Dense(self.dim, dtype=self.dtype)
        self.attn_dropout = nn.Dropout(self.dropout)
        if self.compress_ratio > 1:
            self.kv_compress = nn.Conv(
                inner,
                kernel_size=(self.compress_ratio,),
                strides=(self.compress_ratio,),
                feature_group_count=self.heads,
                padding="VALID",
                dtype=self.dtype,
            )

    def grid_axial(self, x, mask=None, attend_axis: int = 2,
                   sharded: bool = True):
        """Self-attention along ONE axis of a (B, H, W, D) grid. With
        ``sharded=True`` and an active (dp, spr, spc) mesh the grid is
        2D-sharded (parallel/grid_parallel.py): projections are pointwise
        and run on the local shard; the attended axis is gathered by an
        all-to-all inside the primitive. The per-device attended-axis
        pass runs the stock flash kernel where ``ops/flash.py`` takes the
        shape (the whole attended axis is local there) and exact streamed
        or dense attention elsewhere; no tied rows / compression /
        broadcast context here."""
        dh = self.dim_head
        n = x.shape[attend_axis]
        if flash.flash_takes(n, n, dh):

            def flash_fn(q2, k2, v2, m2):
                return flash.flash_attention(
                    q2, k2, v2, q_mask=m2, kv_mask=m2, sm_scale=dh**-0.5
                )

            attn_fn = _kernel_hook(flash_fn, sharded)
        else:
            # exact streamed attention once the per-device logits would
            # cross the chunk threshold; declines (returns None) below it
            # so small shapes stay dense
            from alphafold2_tpu.ops.chunked import chunked_attn_fn

            attn_fn = chunked_attn_fn(dh**-0.5)

        return grid_axial_project_attend(
            self.to_q, self.to_kv, self.to_out, self.heads, dh,
            x, mask, attend_axis, attn_fn, sharded,
        )

    def __call__(
        self,
        x,
        context=None,
        mask=None,
        context_mask=None,
        tie_dim: Optional[int] = None,
        deterministic: bool = True,
    ):
        h, dh = self.heads, self.dim_head
        inner = h * dh
        has_context = context is not None
        ctx = context if has_context else x

        q = self.to_q(x)
        k, v = jnp.split(self.to_kv(ctx), 2, axis=-1)

        if self.compress_ratio > 1:
            if not has_context:
                raise ValueError(
                    "KV compression is for cross-attention only"
                )
            ratio = self.compress_ratio
            j = k.shape[-2]
            pad = (-j) % ratio
            if pad:
                k = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
                v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
            k = self.kv_compress(k)
            v = self.kv_compress(v)
            if context_mask is not None:
                cm = context_mask
                if pad:
                    cm = jnp.pad(cm, ((0, 0), (0, pad)))
                cm = cm.reshape(cm.shape[0], -1, ratio).sum(-1) > 0
                context_mask = cm
            elif pad:
                cm = jnp.pad(
                    jnp.ones((ctx.shape[0], j), dtype=bool), ((0, 0), (0, pad))
                )
                context_mask = cm.reshape(cm.shape[0], -1, ratio).sum(-1) > 0

        def split_heads(t):
            return t.reshape(*t.shape[:-1], h, dh)

        q, k, v = split_heads(q), split_heads(k), split_heads(v)  # (B, n, h, dh)
        scale = dh**-0.5

        # Fused-kernel gate for the paths below: tied rows keep their
        # bespoke dense contraction, and attention-weight dropout needs
        # materialized probabilities. KV compression composes with the
        # fused kernels — by this point k/v/context_mask are already the
        # compressed versions, and at large crops the fused path is what
        # keeps the (N^2 queries x compressed keys) logits out of HBM.
        needs_probs = self.dropout != 0.0 and not deterministic
        fused_ok = tie_dim is None and not needs_probs
        kv_mask = context_mask
        if kv_mask is None and not has_context:
            kv_mask = mask

        def heads_first(t):
            return jnp.moveaxis(t, -2, 1)

        def project_out(out):  # (B, H, n, dh) -> (B, n, dim)
            out = jnp.moveaxis(out, 1, -2).reshape(*x.shape[:-1], inner)
            return self.to_out(out)

        # context-parallel path: exact attention with the sequence axis
        # sharded over the mesh's sp axis (ring ppermute or Ulysses
        # all-to-all — parallel/seq_parallel.py), when a mesh is active.
        # (compression is excluded here: the compressed KV length no longer
        # matches the sequence-parallel shard layout)
        if (
            self.context_parallel is not None
            and fused_ok
            and self.compress_ratio == 1
        ):
            from alphafold2_tpu.parallel.seq_parallel import (
                SEQ_AXIS_NAME,
                sequence_parallel_attention,
            )
            from alphafold2_tpu.parallel.sharding import active_mesh

            mesh = active_mesh()
            if mesh is not None and SEQ_AXIS_NAME in mesh.axis_names:
                out = sequence_parallel_attention(
                    heads_first(q),
                    heads_first(k),
                    heads_first(v),
                    mask=kv_mask,
                    mesh=mesh,
                    impl=self.context_parallel,
                )  # (B, H, n, dh)
                return project_out(out)

        # fused flash-attention path (TPU): the (n, n) attention matrix stays
        # in VMEM instead of HBM. Asked before the call is wrapped for a
        # mesh, so a shape the kernel declines leaves nothing in the program.
        if fused_ok and flash.flash_takes(q.shape[1], k.shape[1], dh):
            from alphafold2_tpu.parallel.sharding import per_device

            out = per_device(
                lambda q, k, v, qm, km: flash.flash_attention(
                    q, k, v, q_mask=qm, kv_mask=km, sm_scale=scale
                ),
                heads_first(q), heads_first(k), heads_first(v),
                mask, kv_mask,
            )
            return project_out(out)

        # exact streamed attention off-TPU once the dense logits would
        # cross the chunk threshold (ops/chunked.py): the long-chain serve
        # buckets' N^2-query cross-attention would otherwise materialize
        # tens of GB. Below the threshold the dense path (and its
        # committed graph fingerprints) is untouched.
        if fused_ok:
            from alphafold2_tpu.ops.chunked import (
                chunked_attention,
                should_chunk,
            )

            if should_chunk(q.shape[0] * h, q.shape[1], k.shape[1]):
                out = chunked_attention(
                    heads_first(q),
                    heads_first(k),
                    heads_first(v),
                    q_mask=mask,
                    kv_mask=kv_mask,
                    sm_scale=scale,
                )
                return project_out(out)

        if tie_dim is not None:
            # (B*R, n, h, d) -> (B, R, n, h, d); one attention matrix per (B, h)
            r = tie_dim
            q, k, v = (t.reshape(-1, r, *t.shape[1:]) for t in (q, k, v))
            tie_scale = r**-0.5
            kv_side = context_mask if has_context else mask
            if mask is not None or kv_side is not None:
                # The reference hard-asserts tied rows never see padding
                # (alphafold2.py:147-149). Here padding is exact instead:
                # each padded (row, position) ABSTAINS from the shared
                # logits (its q/k zeroed) and from the per-row output (its
                # v zeroed), the row-count scale uses the number of rows
                # that actually vote, and the softmax sees the shared
                # column mask. For column padding (every row masks the same
                # positions — what MSA length padding is) this equals
                # attention on the cropped array; fully-masked rows are
                # likewise exact (they abstain entirely). Query and kv
                # sides are masked independently so tied cross-attention
                # (broadcast context) works too.
                bt, n, j = q.shape[0], q.shape[2], k.shape[2]
                qr = (
                    mask.reshape(bt, r, n)
                    if mask is not None
                    else jnp.ones((bt, r, n), dtype=bool)
                )
                kr = (
                    kv_side.reshape(bt, r, j)
                    if kv_side is not None
                    else jnp.ones((bt, r, j), dtype=bool)
                )
                q = jnp.where(qr[..., None, None], q, 0)
                k = jnp.where(kr[..., None, None], k, 0)
                v = jnp.where(kr[..., None, None], v, 0)
                # a row votes in the logit sum iff it has both a valid
                # query and a valid key position
                n_rows = jnp.maximum((qr.any(-1) & kr.any(-1)).sum(-1), 1)
                tie_scale = (
                    n_rows.astype(jnp.float32) ** -0.5
                )[:, None, None, None].astype(self.dtype)
                # shared masks for the softmax below (batch dim B, not B*R)
                mask = qr.any(1)
                context_mask = kr.any(1) if has_context else None

            # fused tied-row kernel (ops/pallas/tied_row.py, where its
            # own rule takes the call): the shared (B, H, n, j) logits
            # stay in VMEM via the fused (row, head_dim) contraction; the
            # abstention masking and voting-row tie scale above are already
            # applied, so the kernel sees exactly the dense inputs. Active
            # attention-weight dropout keeps the dense path (it needs
            # materialized probabilities).
            from alphafold2_tpu.ops.pallas import tied_row

            if tied_row.tied_row_takes(needs_probs):
                from alphafold2_tpu.parallel.sharding import per_device

                km = context_mask if has_context else mask
                out = per_device(
                    lambda q, k, v, qm, km, ts: tied_row.tied_row_attention(
                        q, k, v, q_mask=qm, kv_mask=km, sm_scale=scale,
                        tie_scale=ts,
                    ),
                    q, k, v, mask, km, tie_scale,
                )  # (B, R, n, h, dh)
                out = out.reshape(-1, *out.shape[2:])
                out = out.reshape(*out.shape[:-2], inner)
                return self.to_out(out)
            dots = jnp.einsum("brihd,brjhd->bhij", q, k) * scale * tie_scale
        else:
            dots = jnp.einsum("bihd,bjhd->bhij", q, k) * scale

        if mask is not None or context_mask is not None:
            i, j = dots.shape[-2], dots.shape[-1]
            b = dots.shape[0]
            qm = mask if mask is not None else jnp.ones((1, i), dtype=bool)
            if context_mask is not None:
                km = context_mask
            elif not has_context and mask is not None:
                km = mask
            else:
                km = jnp.ones((1, j), dtype=bool)
            pair = qm[:, None, :, None] & km[:, None, None, :]
            dots = jnp.where(pair, dots, MASK_VALUE)

        attn = jax.nn.softmax(dots.astype(jnp.float32), axis=-1).astype(self.dtype)
        attn = self.attn_dropout(attn, deterministic=deterministic)

        if tie_dim is not None:
            out = jnp.einsum("bhij,brjhd->brihd", attn, v)
            out = out.reshape(-1, *out.shape[2:])
        else:
            out = jnp.einsum("bhij,bjhd->bihd", attn, v)

        out = out.reshape(*out.shape[:-2], inner)
        return self.to_out(out)


class AxialAttention(nn.Module):
    """Factorized attention over a 2D grid: column pass + row pass, summed.

    Operates directly on (B, H, W, D) (+ optional (B, H, W) mask), unlike the
    reference which round-trips through a flat (B, H*W, D) stream
    (alphafold2.py:256-287). An optional cross-attention ``context``
    (B, Nc, D) is broadcast to every row/column. ``tie_row_attn`` ties the row
    (height) pass across rows — used for the MSA grid where H = num
    alignments. ``sparse_attn`` swaps the column/row attention for
    block-sparse attention (ops/sparse.py).
    """

    dim: int
    heads: int = 8
    dim_head: int = 64
    dropout: float = 0.0
    tie_row_attn: bool = False
    sparse_attn: bool = False
    seq_len: Optional[int] = None  # static max length for sparse block layout
    sparse_config: Optional[object] = None  # ops.sparse.BlockSparseConfig
    sparse_use_pallas: Optional[bool] = None  # None -> auto (Pallas on TPU)
    grid_parallel: bool = False  # 2D-sharded passes over a (dp, spr, spc) mesh
    grid_native: bool = True  # grid-layout self-attn passes (no pair-map
    # transpose materialization); False forces the flat (B*, n, d) route
    dtype: jnp.dtype = jnp.float32

    def _attn_cls(self, name):
        if self.sparse_attn:
            from alphafold2_tpu.ops.sparse import BlockSparseConfig, SparseAttention

            return SparseAttention(
                dim=self.dim,
                heads=self.heads,
                dim_head=self.dim_head,
                dropout=self.dropout,
                seq_len=self.seq_len,
                config=self.sparse_config or BlockSparseConfig(),
                use_pallas=self.sparse_use_pallas,
                dtype=self.dtype,
                name=name,
            )
        return Attention(
            dim=self.dim,
            heads=self.heads,
            dim_head=self.dim_head,
            dropout=self.dropout,
            dtype=self.dtype,
            name=name,
        )

    @nn.compact
    def __call__(
        self,
        x,
        mask=None,
        context=None,
        context_mask=None,
        deterministic: bool = True,
    ):
        b, height, w, d = x.shape
        attn_width = self._attn_cls("attn_width")
        attn_height = self._attn_cls("attn_height")

        grid_mesh_active = False
        if self.grid_parallel:
            from alphafold2_tpu.parallel.grid_parallel import ROW_AXIS_NAME
            from alphafold2_tpu.parallel.sharding import active_mesh

            mesh = active_mesh()
            if mesh is not None and ROW_AXIS_NAME in mesh.axis_names:
                if context is not None or self.tie_row_attn:
                    raise ValueError(
                        "grid_parallel axial attention is self-attention "
                        "only (no broadcast context, no tied rows — "
                        "neither occurs on the pair stream)"
                    )
                grid_mesh_active = True

        # Grid route: q/kv/out projections stay pointwise on the
        # (B, H, W, D) grid — the flat route instead materializes a
        # transpose of the whole pair map for the column pass, a full extra
        # HBM round-trip per axial block. Each pass runs the module's fused
        # per-device kernel (flash / block-sparse); with grid_parallel and
        # an active (dp, spr, spc) mesh it is the explicit 2D-sharded
        # shard_map pass. Constraints: self-attention only, untied, no
        # active attention-weight dropout (the fused kernels never
        # materialize probabilities), and block-aligned axes for sparse
        # layouts. grid_native=False is a debug escape back to the flat
        # route — but never under an active grid mesh, where the flat
        # route's transpose of the 2D-sharded pair map would be a silent
        # memory/perf cliff.
        grid_ok = (
            (self.grid_native or grid_mesh_active)
            and context is None
            and not self.tie_row_attn
            and (self.dropout == 0.0 or deterministic)
        )
        if grid_ok and self.sparse_attn:
            from alphafold2_tpu.ops.sparse import BlockSparseConfig

            bs = (self.sparse_config or BlockSparseConfig()).block_size
            aligned = height % bs == 0 and w % bs == 0
            if grid_mesh_active and not aligned:
                # meshless flat sparse pads unaligned crops, but there is
                # no sharded flat route — refuse rather than silently
                # running unsharded at the crop sizes grid_parallel targets
                raise ValueError(
                    f"grid_parallel sparse attention needs block-aligned "
                    f"grid axes: ({height}, {w}) vs block_size {bs}; pad "
                    "the crop or change sparse_config.block_size"
                )
            grid_ok = aligned
        if grid_ok:
            # attn_width attends within columns (over rows, axis 1),
            # attn_height within rows (over columns, axis 2). Only the
            # grid_parallel pair stream is laid out P(dp, spr, spc) —
            # everything else (e.g. the MSA grid) must NOT enter the
            # explicit shard_map and relies on GSPMD instead.
            sharded = grid_mesh_active
            w_out = attn_width.grid_axial(
                x, mask=mask, attend_axis=1, sharded=sharded
            )
            h_out = attn_height.grid_axial(
                x, mask=mask, attend_axis=2, sharded=sharded
            )
            return w_out + h_out

        def broadcast_ctx(n_batch):
            if context is None:
                return {}
            nc = context.shape[1]
            c = jnp.broadcast_to(
                context[:, None], (b, n_batch // b, nc, context.shape[-1])
            ).reshape(n_batch, nc, context.shape[-1])
            cm = None
            if context_mask is not None:
                cm = jnp.broadcast_to(
                    context_mask[:, None], (b, n_batch // b, nc)
                ).reshape(n_batch, nc)
            return {"context": c, "context_mask": cm}

        # column pass: attend over the height axis within each column
        w_x = jnp.swapaxes(x, 1, 2).reshape(b * w, height, d)
        w_mask = (
            jnp.swapaxes(mask, 1, 2).reshape(b * w, height) if mask is not None else None
        )
        w_out = attn_width(
            w_x, mask=w_mask, deterministic=deterministic, **broadcast_ctx(b * w)
        )
        w_out = jnp.swapaxes(w_out.reshape(b, w, height, d), 1, 2)

        # row pass: attend over the width axis within each row (optionally tied)
        h_x = x.reshape(b * height, w, d)
        h_mask = mask.reshape(b * height, w) if mask is not None else None
        tie = {"tie_dim": height} if self.tie_row_attn else {}
        h_out = attn_height(
            h_x, mask=h_mask, deterministic=deterministic, **broadcast_ctx(b * height), **tie
        )
        h_out = h_out.reshape(b, height, w, d)

        return w_out + h_out
