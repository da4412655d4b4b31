"""2D sequence sharding of the pair grid: rows x cols with all-to-all.

SURVEY.md S7 "hard parts": axial attention needs all of a row (or column)
local to one device for the attended axis; the clean mesh layout for the
(B, N, N, D) pair representation is therefore TWO sequence axes — rows
sharded over ``spr`` and columns over ``spc`` — with an all-to-all transpose
before/after each axial pass. Per pass, each device temporarily trades a
factor of the *non-attended* axis for the full *attended* axis:

    at rest:   (B, N/spr, N/spc, ...)           P(dp, spr, spc)
    row pass:  all_to_all over spc ->  (B, N/(spr*spc), N, ...)   attend cols
    col pass:  all_to_all over spr ->  (B, N, N/(spr*spc), ...)   attend rows

Peak per-device memory for the pair grid is O(N^2 / (spr*spc)) — square in
the mesh size rather than linear as with the 1D ``sp`` layout
(parallel/sharding.py), which is what lets crop 768+ fit a pod slice. The
collectives are ``lax.all_to_all`` over one mesh axis each, riding ICI.

The reference has no analogue (single device, SURVEY.md S2.3); this and
ring/Ulysses (parallel/seq_parallel.py) are the green-field long-context
layer. Everything is jnp-only and differentiable; exactness vs the dense
oracle (values and grads) is proven on the 8-virtual-device CPU mesh in
tests/test_grid_parallel.py.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from alphafold2_tpu.ops.attention import MASK_VALUE

DATA_AXIS_NAME = "dp"
ROW_AXIS_NAME = "spr"  # shards grid axis 1 (rows / height)
COL_AXIS_NAME = "spc"  # shards grid axis 2 (cols / width)


def make_grid_mesh(
    n_data: int = 1, n_row: int = 1, n_col: int = 1, devices=None
) -> Mesh:
    """A (dp, spr, spc) mesh for 2D pair-grid sharding.

    Device order comes from ``mesh_utils.create_device_mesh`` so the spr/spc
    axes land on physically-adjacent chips (their per-layer all_to_all
    transposes then ride ICI, with dp crossing DCN — same placement policy
    as distributed.pod_mesh). Only the host CPU's virtual devices take raw
    order; on an accelerator a layout the helper refuses is an error."""
    import numpy as np

    devices = devices if devices is not None else jax.devices()
    n = n_data * n_row * n_col
    if n != len(devices):
        raise ValueError(
            f"mesh {n_data}x{n_row}x{n_col} != {len(devices)} devices"
        )
    if devices[0].platform == "cpu":
        # virtual host devices: any order works, nothing to optimize
        arr = np.asarray(devices).reshape(n_data, n_row, n_col)
    else:
        from jax.experimental import mesh_utils

        arr = mesh_utils.create_device_mesh(
            (n_data, n_row, n_col), devices=devices
        )
    return Mesh(arr, (DATA_AXIS_NAME, ROW_AXIS_NAME, COL_AXIS_NAME))


def grid_spec() -> P:
    """At-rest spec for (B, H, W, ...) grid arrays on a grid mesh."""
    return P(DATA_AXIS_NAME, ROW_AXIS_NAME, COL_AXIS_NAME)


def _attend_last_grid_axis(q, k, v, mask, attn_fn=None):
    """Attention over grid axis 2. q/k/v: (B, R, N, H, D); mask: (B, R, N)
    bool key validity. Rows R are independent batch entries.

    ``attn_fn`` is an optional fused kernel taking row-flattened
    ``(B*R, H, N, D)`` q/k/v and a ``(B*R, N)`` mask (or None), returning
    the attended values in the same layout — or None to decline the shape
    (trace-time), falling back to the dense jnp path. This is how flash /
    block-sparse attention run INSIDE the 2D-sharded axial passes.

    ``mask=None`` stays None all the way down so fused kernels keep their
    unmasked fast paths (e.g. flash without SegmentIds)."""
    b, r, n, h, d = q.shape
    if attn_fn is not None:
        # shape-only pre-probe: a hook exposing ``accepts`` can decline
        # from the static shape alone, BEFORE the row-flattening ops are
        # traced — a declined call must leave zero footprint in the jaxpr
        # (the graph contracts fingerprint dead eqns too)
        accepts = getattr(attn_fn, "accepts", None)
        if accepts is None or accepts(b * r, h, n):
            def flat(t):  # (B, R, N, H, D) -> (B*R, H, N, D)
                return jnp.moveaxis(t.reshape(b * r, n, h, d), 2, 1)

            m2 = mask.reshape(b * r, n) if mask is not None else None
            out = attn_fn(flat(q), flat(k), flat(v), m2)
            if out is not None:
                return jnp.moveaxis(out, 1, 2).reshape(b, r, n, h, d)
    scale = d**-0.5
    dots = jnp.einsum("brihd,brjhd->brhij", q, k).astype(jnp.float32) * scale
    if mask is not None:
        bias = jnp.where(mask, 0.0, MASK_VALUE)
        dots = dots + bias[:, :, None, None, :].astype(jnp.float32)
    attn = jax.nn.softmax(dots, axis=-1).astype(q.dtype)
    return jnp.einsum("brhij,brjhd->brihd", attn, v)


def _sharded_pass(q, k, v, mask, attend_axis: int, attn_fn=None):
    """Runs inside shard_map over (dp, spr, spc). Local blocks:
    q/k/v (b, hl, wl, heads, d), mask (b, hl, wl) or None."""
    if attend_axis == 2:
        gather_name, split_axis = COL_AXIS_NAME, 1
    elif attend_axis == 1:
        gather_name, split_axis = ROW_AXIS_NAME, 2
    else:
        raise ValueError(f"attend_axis must be 1 or 2, got {attend_axis}")
    size = lax.axis_size(gather_name)
    if q.shape[split_axis] % size:
        raise ValueError(
            f"non-attended local axis {q.shape[split_axis]} must divide by "
            f"mesh axis {gather_name}={size} for the all-to-all transpose"
        )

    def gather(t):  # trade non-attended axis for the full attended axis
        return lax.all_to_all(
            t, gather_name, split_axis=split_axis, concat_axis=attend_axis,
            tiled=True,
        )

    def scatter(t):  # inverse transpose
        return lax.all_to_all(
            t, gather_name, split_axis=attend_axis, concat_axis=split_axis,
            tiled=True,
        )

    q, k, v = gather(q), gather(k), gather(v)
    if mask is not None:
        mask = gather(mask)
    if attend_axis == 1:  # put the attended axis last for the shared kernel
        q, k, v = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
        mask = jnp.swapaxes(mask, 1, 2) if mask is not None else None
    out = _attend_last_grid_axis(q, k, v, mask, attn_fn=attn_fn)
    if attend_axis == 1:
        out = jnp.swapaxes(out, 1, 2)
    return scatter(out)


def grid_axial_attention(
    q: jnp.ndarray,  # (B, H, W, heads, dh) global grid
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,  # (B, H, W) bool key-validity
    mesh: Optional[Mesh] = None,
    attend_axis: int = 2,
    attn_fn=None,  # fused kernel hook, see _attend_last_grid_axis
) -> jnp.ndarray:
    """One axial attention pass over a 2D-sharded grid.

    ``attend_axis=2`` attends within rows (over columns), ``attend_axis=1``
    within columns (over rows) — call twice and sum for the full axial
    block (ops/attention.py AxialAttention semantics). Exact dense
    attention in both the sharded and meshless paths; ``attn_fn`` swaps the
    per-device attended-axis computation for a fused kernel (flash /
    block-sparse) after the all-to-all gather.
    """
    if mesh is None or ROW_AXIS_NAME not in mesh.axis_names:
        if attend_axis == 1:
            qt, kt, vt = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
            mt = jnp.swapaxes(mask, 1, 2) if mask is not None else None
            out = _attend_last_grid_axis(qt, kt, vt, mt, attn_fn=attn_fn)
            return jnp.swapaxes(out, 1, 2)
        return _attend_last_grid_axis(q, k, v, mask, attn_fn=attn_fn)

    qkv_spec = P(DATA_AXIS_NAME, ROW_AXIS_NAME, COL_AXIS_NAME, None, None)
    mask_spec = P(DATA_AXIS_NAME, ROW_AXIS_NAME, COL_AXIS_NAME)
    if mask is None:
        # mask stays None down to the per-device kernels (their unmasked
        # fast paths) — shard_map over the three tensor inputs only
        mapped = shard_map(
            partial(
                _sharded_pass, mask=None, attend_axis=attend_axis,
                attn_fn=attn_fn,
            ),
            mesh=mesh,
            in_specs=(qkv_spec, qkv_spec, qkv_spec),
            out_specs=qkv_spec,
            check_vma=False,
        )
        return mapped(q, k, v)
    mapped = shard_map(
        partial(_sharded_pass, attend_axis=attend_axis, attn_fn=attn_fn),
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, mask_spec),
        out_specs=qkv_spec,
        check_vma=False,
    )
    return mapped(q, k, v, mask)
