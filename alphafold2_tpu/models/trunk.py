"""Trunk execution engines: interleaved [self-attn, cross-attn] layer pairs.

Covers both reference engines (alphafold2.py:291-327 SequentialSequence,
reversible.py ReversibleSequence) with three TPU-native options:

- default: python loop over :class:`TrunkLayer` (the SequentialSequence
  equivalent); ``scan_layers=True`` rolls it into one ``lax.scan`` with
  stacked params (depth-independent compile, no reference analogue).
- ``remat=True``: O(1)-in-depth activation memory via XLA rematerialization
  (``jax.checkpoint``) — recompute in backward, dropout replayed exactly by
  stateless PRNG keys (no ``Deterministic`` RNG capture machinery,
  reference reversible.py:26-56). Parameter-isomorphic with the default
  engine (the reference's two engines are NOT isomorphic — it drops each
  self-block's MSA feedforward in the sequential engine, alphafold2.py:
  427-428; SURVEY.md S2.5 flags this defect and we do not replicate it).
  Gradient parity proven in tests/test_remat.py.
- ``reversible=True``: the direct equivalent of the reference's reversible
  engine — inversion-based O(1) memory coupling (models/reversible.py).
  A DIFFERENT network from the other two engines (halved two-stream state,
  twice the feedforwards per depth step, its own stacked parameter tree):
  checkpoints are not interchangeable across this flag, exactly as
  reference reversible/sequential configs differ. Takes precedence over
  ``remat``/``scan_layers`` (it already scans stacked params and needs no
  remat). Gradient parity of its custom backward: tests/test_reversible.py.

Streams stay in grid form throughout: pair (B, N, N, D), MSA (B, M, Nm, D).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from alphafold2_tpu.observe.numerics import tag
from alphafold2_tpu.ops.attention import Attention, AxialAttention, FeedForward
from alphafold2_tpu.parallel.sharding import shard_pair, shard_msa


class TrunkLayer(nn.Module):
    """One depth step: axial self-attn on both streams, bidirectional
    cross-attn between them, then feedforwards. All residual, all pre-LN."""

    dim: int
    heads: int = 8
    dim_head: int = 64
    attn_dropout: float = 0.0
    ff_dropout: float = 0.0
    gelu_exact: bool = False  # erf GELU (the reference's torch F.gelu)
    sparse_attn: bool = False
    seq_len: Optional[int] = None
    sparse_config: Optional[object] = None  # ops.sparse.BlockSparseConfig
    sparse_use_pallas: Optional[bool] = None
    cross_attn_compress_ratio: int = 1
    msa_tie_row_attn: bool = False
    msa_row_shard: bool = False  # shard MSA rows over sp (tied psum via GSPMD)
    context_parallel: Optional[str] = None  # None | "ring" | "ulysses"
    grid_parallel: bool = False  # 2D-sharded pair axial passes (spr x spc)
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(
        self,
        x: jnp.ndarray,  # (B, N, N, D) pair grid
        m: Optional[jnp.ndarray],  # (B, M, Nm, D) MSA grid or None
        pair_mask: Optional[jnp.ndarray] = None,  # (B, N, N)
        msa_mask: Optional[jnp.ndarray] = None,  # (B, M, Nm)
        deterministic: bool = True,
    ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
        dt = self.dtype
        ln = lambda name: nn.LayerNorm(dtype=dt, name=name)

        # pair self-attention (axial over the N x N grid)
        x = x + AxialAttention(
            dim=self.dim,
            heads=self.heads,
            dim_head=self.dim_head,
            dropout=self.attn_dropout,
            sparse_attn=self.sparse_attn,
            seq_len=self.seq_len,
            sparse_config=self.sparse_config,
            sparse_use_pallas=self.sparse_use_pallas,
            grid_parallel=self.grid_parallel,
            dtype=dt,
            name="pair_axial",
        )(ln("pair_axial_norm")(x), mask=pair_mask, deterministic=deterministic)
        x = shard_pair(x)

        if m is not None:
            # MSA self-attention (axial over the M x Nm grid, rows optionally tied)
            m = m + AxialAttention(
                dim=self.dim,
                heads=self.heads,
                dim_head=self.dim_head,
                dropout=self.attn_dropout,
                tie_row_attn=self.msa_tie_row_attn,
                dtype=dt,
                name="msa_axial",
            )(ln("msa_axial_norm")(m), mask=msa_mask, deterministic=deterministic)
            m = shard_msa(m, rows=self.msa_row_shard)

            # cross-attention: pair tokens query the MSA stream and vice versa
            b, n, n2, d = x.shape
            bm, mm, nm, _ = m.shape
            x_flat = x.reshape(b, n * n2, d)
            m_flat = m.reshape(bm, mm * nm, d)
            x_mask_flat = (
                pair_mask.reshape(b, n * n2) if pair_mask is not None else None
            )
            m_mask_flat = (
                msa_mask.reshape(bm, mm * nm) if msa_mask is not None else None
            )

            x_flat = x_flat + Attention(
                dim=self.dim,
                heads=self.heads,
                dim_head=self.dim_head,
                dropout=self.attn_dropout,
                compress_ratio=self.cross_attn_compress_ratio,
                context_parallel=self.context_parallel,
                dtype=dt,
                name="pair_from_msa",
            )(
                ln("pair_cross_norm")(x_flat),
                context=ln("pair_cross_ctx_norm")(m_flat),
                mask=x_mask_flat,
                context_mask=m_mask_flat,
                deterministic=deterministic,
            )
            m_flat = m_flat + Attention(
                dim=self.dim,
                heads=self.heads,
                dim_head=self.dim_head,
                dropout=self.attn_dropout,
                context_parallel=self.context_parallel,
                dtype=dt,
                name="msa_from_pair",
            )(
                ln("msa_cross_norm")(m_flat),
                context=ln("msa_cross_ctx_norm")(x_flat),
                mask=m_mask_flat,
                context_mask=x_mask_flat,
                deterministic=deterministic,
            )
            x = shard_pair(x_flat.reshape(b, n, n2, d))
            m = shard_msa(m_flat.reshape(bm, mm, nm, d), rows=self.msa_row_shard)

        # feedforwards
        x = x + FeedForward(
            dim=self.dim, dropout=self.ff_dropout,
            gelu_exact=self.gelu_exact, dtype=dt, name="pair_ff"
        )(ln("pair_ff_norm")(x), deterministic=deterministic)
        x = shard_pair(x)
        if m is not None:
            m = m + FeedForward(
                dim=self.dim, dropout=self.ff_dropout,
                gelu_exact=self.gelu_exact, dtype=dt, name="msa_ff"
            )(ln("msa_ff_norm")(m), deterministic=deterministic)
            m = shard_msa(m, rows=self.msa_row_shard)

        return x, m


def resolve_remat_policy(name):
    """Map a config-level policy name to a jax.checkpoint policy.

    None/"nothing" = save nothing (full recompute — max memory savings,
    the long-standing behavior). "dots" / "dots_no_batch" save matmul
    outputs ("no_batch" excludes batched dots): the backward pass skips
    recomputing the MXU-heavy ops at the cost of keeping their outputs —
    the standard memory/MFU trade on TPU.
    """
    if name is None or name == "nothing":
        return None
    policies = {
        "dots": jax.checkpoint_policies.checkpoint_dots,
        "dots_no_batch": (
            jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        ),
    }
    if name not in policies:
        raise ValueError(
            f"unknown remat_policy {name!r}; have "
            f"{[None, 'nothing', *policies]}"
        )
    return policies[name]


class _ScanBody(nn.Module):
    """nn.scan body: carries (x, m) through one TrunkLayer; masks ride in
    as broadcast (loop-invariant) scan inputs."""

    layer_kwargs: dict
    deterministic: bool
    remat: bool
    remat_policy: Optional[str] = None

    @nn.compact
    def __call__(self, carry, pair_mask, msa_mask):
        x, m = carry
        layer_cls = TrunkLayer
        if self.remat:
            # prevent_cse=False: the CSE-prevention barriers jax.checkpoint
            # inserts by default are unnecessary (and costly) inside scan
            layer_cls = nn.remat(
                TrunkLayer, static_argnums=(5,), prevent_cse=False,
                policy=resolve_remat_policy(self.remat_policy),
            )
        x, m = layer_cls(**self.layer_kwargs, name="layer")(
            x, m, pair_mask, msa_mask, self.deterministic
        )
        return (x, m), ()


class Trunk(nn.Module):
    """Stack of TrunkLayers; ``remat=True`` checkpoints each layer, and
    ``reversible=True`` dispatches to the inversion-based engine (see the
    module docstring for the three-engine map; reversible takes precedence
    over remat/scan_layers and has its own parameter layout).

    ``scan_layers=True`` rolls the depth loop into one ``lax.scan`` over a
    single layer with stacked parameters: the trunk is traced/compiled ONCE
    regardless of depth (compile time and program size stop growing with
    depth — the TPU-first answer to deep trunks). Requires homogeneous
    layers (a per-layer ``sparse_self_attn`` tuple needs the python loop).
    Parameter trees differ between the two modes (stacked vs layer_i), so
    checkpoints are not interchangeable across the flag.
    """

    dim: int
    depth: int = 6
    heads: int = 8
    dim_head: int = 64
    attn_dropout: float = 0.0
    ff_dropout: float = 0.0
    gelu_exact: bool = False  # erf GELU (the reference's torch F.gelu)
    sparse_self_attn: tuple | bool = False
    seq_len: Optional[int] = None
    sparse_config: Optional[object] = None  # ops.sparse.BlockSparseConfig
    sparse_use_pallas: Optional[bool] = None
    cross_attn_compress_ratio: int = 1
    msa_tie_row_attn: bool = False
    msa_row_shard: bool = False  # shard MSA rows over sp (tied psum via GSPMD)
    context_parallel: Optional[str] = None  # None | "ring" | "ulysses"
    grid_parallel: bool = False  # 2D-sharded pair axial passes (spr x spc)
    remat: bool = False
    remat_policy: Optional[str] = None  # None/"nothing" | "dots" | "dots_no_batch"
    reversible: bool = False  # inversion-based O(1)-memory engine
    scan_layers: bool = False
    dtype: jnp.dtype = jnp.float32

    def _layer_kwargs(self, sparse: bool) -> dict:
        return dict(
            dim=self.dim,
            heads=self.heads,
            dim_head=self.dim_head,
            attn_dropout=self.attn_dropout,
            ff_dropout=self.ff_dropout,
            gelu_exact=self.gelu_exact,
            sparse_attn=sparse,
            seq_len=self.seq_len,
            sparse_config=self.sparse_config,
            sparse_use_pallas=self.sparse_use_pallas,
            cross_attn_compress_ratio=self.cross_attn_compress_ratio,
            msa_tie_row_attn=self.msa_tie_row_attn,
            msa_row_shard=self.msa_row_shard,
            context_parallel=self.context_parallel,
            grid_parallel=self.grid_parallel,
            dtype=self.dtype,
        )

    @nn.compact
    def __call__(
        self, x, m, pair_mask=None, msa_mask=None, deterministic: bool = True
    ):
        sparse_flags = self.sparse_self_attn
        if not isinstance(sparse_flags, (tuple, list)):
            sparse_flags = (sparse_flags,) * self.depth
        if len(sparse_flags) != self.depth:
            raise ValueError(
                f"sparse_self_attn tuple has {len(sparse_flags)} entries "
                f"for depth {self.depth}"
            )

        # validate eagerly: a policy name (even a typo) with remat off, or
        # with the reversible engine (which never applies it), would
        # otherwise be a silent no-op — the config asked for a memory/MFU
        # trade that is not happening. "nothing" is the explicit spelling
        # of the default and is always allowed.
        if resolve_remat_policy(self.remat_policy) is not None and (
            not self.remat or self.reversible
        ):
            raise ValueError(
                f"remat_policy={self.remat_policy!r} has no effect "
                + ("with the reversible engine (it has its own O(1)-memory "
                   "schedule and never applies checkpoint policies)"
                   if self.reversible else "without remat=True")
            )

        if self.reversible:
            # true reversible coupling engine (reference reversible.py);
            # already scans over stacked per-depth params, so scan_layers
            # is implied and remat is redundant
            from alphafold2_tpu.models.reversible import ReversibleTrunk

            if len(set(sparse_flags)) > 1:
                raise ValueError(
                    "the reversible engine scans one stacked layer; "
                    f"per-layer sparse_self_attn={sparse_flags} needs the "
                    "python loop"
                )
            if self.context_parallel is not None:
                raise ValueError(
                    "context_parallel is not supported by the reversible "
                    "engine (its cross-attention runs dense per device); "
                    "use remat=True with context_parallel, or reversible "
                    "without it"
                )
            if self.msa_row_shard:
                raise ValueError(
                    "msa_row_shard is not supported by the reversible "
                    "engine (its MSA streams are replicated); use "
                    "remat=True to combine MSA-row sharding with O(1) "
                    "activation memory"
                )
            if self.grid_parallel:
                raise ValueError(
                    "grid_parallel is not supported by the reversible "
                    "engine (its axial passes run dense, so the 2D-sharded "
                    "pair state would be all-gathered and the memory "
                    "benefit silently lost); use remat=True with "
                    "grid_parallel"
                )
            x, m = ReversibleTrunk(
                dim=self.dim,
                depth=self.depth,
                heads=self.heads,
                dim_head=self.dim_head,
                attn_dropout=self.attn_dropout,
                ff_dropout=self.ff_dropout,
                gelu_exact=self.gelu_exact,
                sparse_attn=sparse_flags[0],
                seq_len=self.seq_len,
                sparse_config=self.sparse_config,
                sparse_use_pallas=self.sparse_use_pallas,
                cross_attn_compress_ratio=self.cross_attn_compress_ratio,
                msa_tie_row_attn=self.msa_tie_row_attn,
                dtype=self.dtype,
                name="reversible",
            )(x, m, pair_mask=pair_mask, msa_mask=msa_mask,
              deterministic=deterministic)
            # numerics tags only at the engine boundary: tagging inside the
            # scanned/custom-backward body would capture inner-trace tracers
            x = tag("trunk.out.pair", x)
            if m is not None:
                m = tag("trunk.out.msa", m)
            return x, m

        if self.scan_layers:
            if len(set(sparse_flags)) > 1:
                raise ValueError(
                    "scan_layers needs homogeneous layers; per-layer "
                    f"sparse_self_attn={sparse_flags} requires the python "
                    "loop"
                )
            scanned = nn.scan(
                _ScanBody,
                variable_axes={"params": 0},
                split_rngs={"params": True, "dropout": True},
                in_axes=(nn.broadcast, nn.broadcast),
                length=self.depth,
            )(
                layer_kwargs=self._layer_kwargs(sparse_flags[0]),
                deterministic=deterministic,
                remat=self.remat,
                remat_policy=self.remat_policy,
                name="scan",
            )
            (x, m), _ = scanned((x, m), pair_mask, msa_mask)
            # per-layer tags would sit inside the scan body (inner tracers);
            # the scanned engine tags at the trunk boundary only
            x = tag("trunk.out.pair", x)
            if m is not None:
                m = tag("trunk.out.msa", m)
            return x, m

        layer_cls = TrunkLayer
        if self.remat:
            layer_cls = nn.remat(
                TrunkLayer, static_argnums=(5,),
                policy=resolve_remat_policy(self.remat_policy),
            )

        for i, sparse in enumerate(sparse_flags):
            x, m = layer_cls(
                **self._layer_kwargs(sparse), name=f"layer_{i}"
            )(x, m, pair_mask, msa_mask, deterministic)
            # layer-boundary numerics tags: OUTSIDE the (possibly remat'ed)
            # layer body, so the stats are outer-trace values in every
            # engine mode; tag order == depth order == topological order
            x = tag(f"trunk.layer_{i}.pair", x)
            if m is not None:
                m = tag(f"trunk.layer_{i}.msa", m)
        return x, m
