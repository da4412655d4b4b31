"""Block-sparse attention: sparsity config, layout, gather-based jnp impl.

Replaces the reference's DeepSpeed ``SparseSelfAttention`` +
``VariableSparsityConfig`` Triton/CUDA path (reference alphafold2.py:184-239;
built by install_deepspeed.sh) with a TPU-native design:

- :class:`BlockSparseConfig` — variable sparsity layout abstraction: local
  sliding-window blocks, global blocks (first rows+columns dense), and
  seeded random blocks per row — the same layout family as DeepSpeed's
  VariableSparsityConfig (block=16, num_random_blocks=seq_len/block/4 default,
  bidirectional; reference alphafold2.py:198-206).
- :func:`block_sparse_attention` — gather-based jnp implementation: for each
  query block, gather its active KV blocks (static layout -> static gather
  indices baked at trace time) and attend only over those. Compute is
  O(N * active_blocks * block) rather than O(N^2); runs on any backend and
  is the oracle for the Pallas kernel.
- :class:`SparseAttention` — drop-in module matching :class:`Attention`'s
  call surface for the self-attention case (the reference's sparse path is
  self-attn only and incompatible with tied rows, alphafold2.py:193).
- the Pallas TPU kernel lives in ops/pallas/block_sparse.py; it is selected
  with ``use_pallas=True`` (or on TPU backends) and validated against the
  jnp implementation — including the dense-layout == dense-attention
  differential test (tests/test_sparse.py).

Unlike the reference, a caller-supplied mask composes with padding instead of
being overwritten (alphafold2.py:222 clobbers it — SURVEY.md S2.5), and
there is no dead dense-dots compute (alphafold2.py:228).
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from alphafold2_tpu.ops.attention import MASK_VALUE, grid_axial_project_attend
from alphafold2_tpu.parallel.sharding import per_device


@dataclasses.dataclass(frozen=True)
class BlockSparseConfig:
    """Variable block-sparsity layout (bidirectional).

    block_size: attention block edge (reference default 16; use 128 on TPU
    for lane alignment). num_local_blocks: sliding window width in blocks.
    num_global_blocks: leading blocks attending/attended densely.
    num_random_blocks: extra random blocks per query row; None -> the
    reference's default seq_len/block/4 (alphafold2.py:198).
    """

    block_size: int = 16
    num_local_blocks: int = 4
    num_global_blocks: int = 1
    num_random_blocks: Optional[int] = None
    seed: int = 0
    # kernel backend: "auto" = in-repo Pallas kernels on TPU / jnp gather
    # elsewhere (the long-standing behavior); "pallas" / "jnp" force those;
    # "splash" = the stock jax splash-attention kernel over the same layout
    # (schedules only the layout's active blocks; fused custom-VJP backward)
    backend: str = "auto"

    def resolve_random(self, seq_len: int) -> int:
        if self.num_random_blocks is not None:
            return self.num_random_blocks
        return max(seq_len // self.block_size // 4, 0)

    def layout(self, seq_len: int) -> np.ndarray:
        """(num_blocks, num_blocks) bool — True where a block attends."""
        if seq_len % self.block_size != 0:
            raise ValueError(
                f"seq_len {seq_len} must be a multiple of block_size "
                f"{self.block_size}"
            )
        nb = seq_len // self.block_size
        lay = np.zeros((nb, nb), dtype=bool)
        # local sliding window
        half = self.num_local_blocks // 2
        for i in range(nb):
            lo = max(0, i - half)
            hi = min(nb, i + max(self.num_local_blocks - half, 1))
            lay[i, lo:hi] = True
        # global blocks: first G rows and columns fully dense
        g = min(self.num_global_blocks, nb)
        lay[:g, :] = True
        lay[:, :g] = True
        # seeded random blocks per row
        r = min(self.resolve_random(seq_len), nb)
        if r > 0:
            rng = np.random.default_rng(self.seed)
            for i in range(nb):
                lay[i, rng.choice(nb, size=r, replace=False)] = True
        return lay


def active_indices(layout: np.ndarray) -> tuple[np.ndarray, np.ndarray, int]:
    """Pack the layout into per-row active-block index lists.

    Returns (indices (nb, max_active) int32, valid (nb, max_active) bool,
    max_active). Rows with fewer active blocks are padded with index 0 and
    valid=False — static shapes for the gather.
    """
    nb = layout.shape[0]
    counts = layout.sum(-1)
    max_active = int(counts.max()) if nb else 0
    idx = np.zeros((nb, max_active), dtype=np.int32)
    valid = np.zeros((nb, max_active), dtype=bool)
    for i in range(nb):
        a = np.nonzero(layout[i])[0]
        idx[i, : len(a)] = a
        valid[i, : len(a)] = True
    return idx, valid, max_active


def block_sparse_attention(
    q: jnp.ndarray,  # (B, H, N, D)
    k: jnp.ndarray,
    v: jnp.ndarray,
    layout: np.ndarray,  # (nb, nb) bool, static
    block_size: int,
    mask: Optional[jnp.ndarray] = None,  # (B, N) bool key-side padding mask
) -> jnp.ndarray:
    """Gather-based block-sparse attention, numerically == dense attention
    restricted to the layout's blocks. Scale is applied inside."""
    b, h, n, d = q.shape
    nb = n // block_size
    idx, valid, max_active = active_indices(layout)
    idx_j = jnp.asarray(idx)  # (nb, A)
    valid_j = jnp.asarray(valid)

    scale = d**-0.5
    qb = q.reshape(b, h, nb, block_size, d)
    kb = k.reshape(b, h, nb, block_size, d)
    vb = v.reshape(b, h, nb, block_size, d)

    # gather active KV blocks per query block: (B, H, nb, A, block, d)
    kg = jnp.take(kb, idx_j.reshape(-1), axis=2).reshape(
        b, h, nb, max_active, block_size, d
    )
    vg = jnp.take(vb, idx_j.reshape(-1), axis=2).reshape(
        b, h, nb, max_active, block_size, d
    )

    dots = jnp.einsum("bhnqd,bhnakd->bhnqak", qb, kg) * scale

    # mask: invalid (padding) active slots + key padding mask
    am = valid_j[None, None, :, None, :, None]
    if mask is not None:
        mb = mask.reshape(b, nb, block_size)  # (B, nb, block)
        mg = jnp.take(mb, idx_j.reshape(-1), axis=1).reshape(
            b, nb, max_active, block_size
        )
        am = am & mg[:, None, :, None, :, :]
    dots = jnp.where(am, dots, MASK_VALUE)

    flat = dots.reshape(b, h, nb, block_size, max_active * block_size)
    attn = jax.nn.softmax(flat.astype(jnp.float32), axis=-1).astype(q.dtype)
    attn = attn.reshape(b, h, nb, block_size, max_active, block_size)
    out = jnp.einsum("bhnqak,bhnakd->bhnqd", attn, vg)
    return out.reshape(b, h, n, d)


def block_sparse_attention_pallas(
    q, k, v, layout: np.ndarray, block_size: int, mask=None, interpret=None
):
    """Pallas forward + fused Pallas backward.

    ``pallas_call`` kernels carry no autodiff rule, so this wrapper supplies
    one: the forward kernel additionally emits the per-row logsumexp, and
    the backward runs two flash-style kernels — dq over the row-wise active
    lists, dk/dv over the transposed (column-wise) lists — recomputing
    probabilities from q/k and the saved logsumexp. Nothing quadratic is
    saved or materialized in either direction. Gradient parity with the
    gather-based jnp oracle is proven in tests/test_sparse.py.

    ``interpret``: None = compiled on TPU, interpret elsewhere (the kernel
    default); the lowering gate (scripts/check_tpu_lowering.py) forces
    False to exercise the Mosaic pipeline off-hardware.
    """

    @jax.custom_vjp
    def f(q, k, v, mask):
        from alphafold2_tpu.ops.pallas.block_sparse import (
            pallas_block_sparse_attention,
        )

        return pallas_block_sparse_attention(
            q, k, v, layout, block_size, mask=mask, interpret=interpret
        )

    def fwd(q, k, v, mask):
        from alphafold2_tpu.ops.pallas.block_sparse import (
            pallas_block_sparse_attention,
        )

        out, lse = pallas_block_sparse_attention(
            q, k, v, layout, block_size, mask=mask, return_lse=True,
            interpret=interpret,
        )
        return out, (q, k, v, out, lse, mask)

    def bwd(res, g):
        q, k, v, out, lse, mask = res
        from alphafold2_tpu.ops.pallas.block_sparse import (
            pallas_block_sparse_attention_bwd,
        )

        dq, dk, dv = pallas_block_sparse_attention_bwd(
            q, k, v, out, lse, g, layout, block_size, mask=mask,
            interpret=interpret,
        )
        return dq, dk, dv, None

    f.defvjp(fwd, bwd)
    return f(q, k, v, mask)


@functools.lru_cache(maxsize=1)
def _block_layout_mask_cls():
    """The splash Mask subclass, built once (its base class lives inside
    the lazily-imported splash module). Module-level caching keeps mask
    equality/hashing stable across _splash_kernel calls — a per-call class
    would break __eq__'s isinstance against previously built masks."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as sm,
    )

    class _BlockLayoutMask(sm.Mask):
        """Element-level view of a block-level layout, evaluated lazily:
        __getitem__ maps the requested element indices to layout blocks,
        touching only the requested chunk — nothing O(n^2) is ever
        materialized, at any sequence length."""

        def __init__(self, layout: np.ndarray, block_size: int):
            self._layout = layout
            self._bs = block_size

        @property
        def shape(self):
            return (
                self._layout.shape[0] * self._bs,
                self._layout.shape[1] * self._bs,
            )

        def __getitem__(self, idx) -> np.ndarray:
            if not isinstance(idx, tuple) or len(idx) != 2:
                raise NotImplementedError(f"unsupported mask index {idx!r}")
            r = np.arange(self.shape[0])[idx[0]] // self._bs
            c = np.arange(self.shape[1])[idx[1]] // self._bs
            # dispatch on the ORIGINAL index types, not the resolved
            # arrays: numpy gives slice-involved indexing
            # outer-product semantics but array+array element-wise
            # *paired/broadcast* semantics, and a dense ndarray mask would
            # honor both — np.ix_ on a resolved integer-array pair would
            # silently return an outer-product block of the wrong shape
            # and values.
            if not isinstance(idx[0], slice) and not isinstance(idx[1], slice):
                return self._layout[r, c]  # paired/broadcast
            if r.ndim == 1 and c.ndim == 1:
                return self._layout[np.ix_(r, c)]  # outer product
            return self._layout[r, c]  # scalar-involved: broadcast

        def __eq__(self, other):
            if not isinstance(other, _BlockLayoutMask):
                return NotImplemented
            return self._bs == other._bs and np.array_equal(
                self._layout, other._layout
            )

        def __hash__(self):
            return hash(
                (type(self).__name__, self._bs, self._layout.tobytes())
            )

    return _BlockLayoutMask


@functools.lru_cache(maxsize=32)
def _splash_kernel(layout_bytes: bytes, nb: int, block_size: int, heads: int,
                   interpret: bool):
    """Build (and cache) a splash MHA kernel for a static block layout —
    mask preprocessing (MaskInfo construction) is trace-time work worth
    doing once per (layout, heads) rather than per call. The mask is
    served lazily from the (nb, nb) block layout via _block_layout_mask_cls
    (no dense element-level materialization)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
        splash_attention_mask as sm,
    )

    layout = np.frombuffer(layout_bytes, dtype=bool).reshape(nb, nb)
    mask_cls = _block_layout_mask_cls()
    mh = sm.MultiHeadMask([mask_cls(layout, block_size)] * heads)
    return sk.make_splash_mha(
        mh, head_shards=1, q_seq_shards=1, interpret=interpret
    )


def block_sparse_attention_splash(
    q, k, v, layout: np.ndarray, block_size: int, mask=None
):
    """The stock jax splash-attention kernel over the same static layout —
    an alternative TPU backend to the in-repo Pallas kernels (fused
    forward + custom-VJP backward, schedules only the layout's active
    blocks). Padding composes via segment ids (valid=1, pad=0). Output at
    PADDED query rows is unspecified and differs from the jnp oracle —
    downstream masking makes those rows irrelevant (the loss excludes
    masked pairs), and valid-region parity (values and grads) is proven in
    interpret mode in tests/test_sparse.py."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
    )

    b, h, n, d = q.shape
    if n % 128 != 0:
        # the splash kernel's q/kv block size is 128. A backend chosen by
        # name is never swapped for the gather oracle behind the caller's
        # back: on a chip that turns a shape bug into a slower run
        raise ValueError(
            f"splash backend needs seq_len % 128 == 0, got {n}; pad the "
            "sequence or pick backend=\"pallas\"/\"jnp\""
        )
    if jax.default_backend() != "tpu":
        warnings.warn(  # python shows a warning once per call site
            "splash backend off-TPU runs the kernel in Pallas interpret "
            "mode (orders of magnitude slower) — fine for tests, wrong "
            "for real runs; use backend=\"auto\" or \"jnp\" off-TPU",
        )
    nb = layout.shape[0]
    kernel = _splash_kernel(
        np.ascontiguousarray(layout).tobytes(), nb, block_size, h,
        jax.default_backend() != "tpu",
    )
    seg = None
    if mask is not None:
        m = mask.astype(jnp.int32)
        seg = sk.SegmentIds(q=m, kv=m)
    out = jax.vmap(kernel)(q * (d**-0.5), k, v, segment_ids=seg)
    return out.astype(q.dtype)


class SparseAttention(nn.Module):
    """Block-sparse multi-head self-attention (drop-in for Attention).

    Pads the sequence to a block multiple (composing with, not clobbering,
    any caller mask) and slices the padding back off. ``seq_len`` bounds the
    allowed input length (reference alphafold2.py:194,215).
    """

    dim: int
    heads: int = 8
    dim_head: int = 64
    dropout: float = 0.0
    seq_len: Optional[int] = None
    config: BlockSparseConfig = BlockSparseConfig()
    use_pallas: Optional[bool] = None  # None -> Pallas kernel on TPU backends
    dtype: jnp.dtype = jnp.float32

    def setup(self):
        inner = self.heads * self.dim_head
        self.to_q = nn.Dense(inner, use_bias=False, dtype=self.dtype)
        self.to_kv = nn.Dense(inner * 2, use_bias=False, dtype=self.dtype)
        self.to_out = nn.Dense(self.dim, dtype=self.dtype)
        self.out_dropout = nn.Dropout(self.dropout)

    def _impl(self):
        backend = getattr(self.config, "backend", "auto")
        # precedence: the explicit use_pallas bool (predates config.backend,
        # wins for back-compat) > a non-"auto" config.backend (a reviewed
        # per-module choice) > the Pallas kernel on a TPU, jnp elsewhere
        impls = {
            "jnp": block_sparse_attention,
            "pallas": block_sparse_attention_pallas,
            "splash": block_sparse_attention_splash,
        }
        if backend != "auto" and backend not in impls:
            raise ValueError(
                f"unknown sparse backend {backend!r}; have "
                f"{['auto', *impls]}"
            )
        if self.use_pallas is not None:
            return (
                block_sparse_attention_pallas
                if self.use_pallas
                else block_sparse_attention
            )
        if backend != "auto":
            return impls[backend]
        return impls["pallas" if jax.default_backend() == "tpu" else "jnp"]

    def _attend(self, q, k, v, mask, layout, wrap: bool):
        """The selected backend on (B, H, N, D) arrays. A kernel called on
        global arrays (``wrap``) runs per device under an active mesh
        (parallel.sharding.per_device); the jnp oracle is left to GSPMD."""
        impl = self._impl()
        bs = self.config.block_size

        def call(q, k, v, m):
            return impl(q, k, v, layout, bs, mask=m)

        if wrap and impl is not block_sparse_attention:
            return per_device(call, q, k, v, mask)
        return call(q, k, v, mask)

    def grid_axial(self, x, mask=None, attend_axis: int = 2,
                   sharded: bool = True):
        """Block-sparse self-attention along ONE axis of a (B, H, W, D) grid
        2D-sharded over a (dp, spr, spc) mesh: after the all-to-all gathers
        the full attended axis per device, the local pass runs this module's
        block-sparse kernel instead of dense attention — O(N * active_blocks
        * block) logits per device, which is what makes 768+-crop grids fit
        (parallel/grid_parallel.py)."""
        h, dh = self.heads, self.dim_head
        n_att = x.shape[attend_axis]
        bs = self.config.block_size
        if n_att % bs != 0:
            raise ValueError(
                f"grid-sharded sparse attention needs the attended axis "
                f"({n_att}) to be a multiple of block_size ({bs})"
            )
        if self.seq_len is not None and n_att > self.seq_len:
            raise ValueError(
                f"attended axis {n_att} exceeds max_seq_len {self.seq_len}"
            )
        layout = self.config.layout(n_att)

        def attn_fn(q2, k2, v2, m2):
            # inside the grid shard_map the call is per device already
            return self._attend(q2, k2, v2, m2, layout, wrap=not sharded)

        return grid_axial_project_attend(
            self.to_q, self.to_kv, self.to_out, h, dh,
            x, mask, attend_axis, attn_fn, sharded,
        )

    def __call__(
        self,
        x,
        context=None,
        mask=None,
        context_mask=None,
        tie_dim=None,
        deterministic: bool = True,
    ):
        if context is not None:
            raise ValueError("sparse attention is self-attention only")
        if tie_dim is not None:
            raise ValueError(
                "sparse attention is not compatible with tying of row "
                "attention"
            )
        b, n, _ = x.shape
        if self.seq_len is not None and n > self.seq_len:
            raise ValueError(
                f"sequence length {n} exceeds max_seq_len {self.seq_len}"
            )
        h, dh = self.heads, self.dim_head
        inner = h * dh
        bs = self.config.block_size
        pad = (-n) % bs
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        padded_n = n + pad
        if mask is None:
            mask = jnp.ones((b, n), dtype=bool)
        if pad:
            mask = jnp.pad(mask, ((0, 0), (0, pad)))

        q = self.to_q(x)
        k, v = jnp.split(self.to_kv(x), 2, axis=-1)

        def heads_first(t):
            return jnp.moveaxis(t.reshape(b, padded_n, h, dh), 2, 1)

        q, k, v = heads_first(q), heads_first(k), heads_first(v)
        layout = self.config.layout(padded_n)
        out = self._attend(q, k, v, mask, layout, wrap=True)

        out = jnp.moveaxis(out, 1, 2).reshape(b, padded_n, inner)
        out = self.to_out(out)
        out = self.out_dropout(out, deterministic=deterministic)
        return out[:, :n]
