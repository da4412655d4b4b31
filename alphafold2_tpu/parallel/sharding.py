"""Sharding constraints for the pair/MSA streams over a device mesh.

The reference has no multi-device parallelism of any kind (SURVEY.md S2.3);
this module is the green-field capability layer. Design (scaling-book recipe):

- Mesh axes: ``dp`` (data parallel over batch) x ``sp`` (sequence parallel
  over pair-map rows). The pair grid (B, N, N, D) is sharded
  P(dp, sp, None, None): each device holds a contiguous band of rows i with
  all columns j — so the *row* attention pass (attend over j) is fully local.
  The *column* pass needs all i per column; annotating the layer-boundary
  constraint and leaving the interior unconstrained lets XLA insert the
  all-to-all transposes between the two passes (the ring/Ulysses-adjacent
  design SURVEY.md S7 calls for) over ICI.
- The MSA grid (B, M, Nm, D) is tiny next to the N^2 pair grid (M <= 20);
  it is replicated across ``sp`` and sharded only over ``dp``.
- Cross-attention (N^2 queries vs M*Nm keys) keeps pair tokens row-sharded;
  the MSA context is replicated so no gather is needed on the KV side.

Blocks call :func:`shard_pair`/:func:`shard_msa` at their boundaries; outside
an active mesh context these are identity, so the same model code runs
single-chip, under tests, and on a pod.
"""

from __future__ import annotations

import os
from contextlib import contextmanager
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "dp"
SEQ_AXIS = "sp"

_active: dict = {"mesh": None}


def make_mesh(
    n_data: Optional[int] = None, n_seq: int = 1, devices=None
) -> Mesh:
    """Create a (dp, sp) mesh. Defaults to all devices on the dp axis."""
    import numpy as np

    devices = devices if devices is not None else jax.devices()
    if n_data is None:
        n_data = len(devices) // n_seq
    if n_data * n_seq != len(devices):
        raise ValueError(
            f"mesh {n_data}x{n_seq} != {len(devices)} devices"
        )
    arr = np.asarray(devices).reshape(n_data, n_seq)
    return Mesh(arr, (DATA_AXIS, SEQ_AXIS))


@contextmanager
def use_mesh(mesh: Mesh):
    """Activate sharding constraints for model code traced inside."""
    prev = _active["mesh"]
    _active["mesh"] = mesh
    try:
        with mesh:
            yield mesh
    finally:
        _active["mesh"] = prev


def active_mesh() -> Optional[Mesh]:
    return _active["mesh"]


def per_device(fn, *args):
    """``fn(*args)`` where ``fn`` holds a Pallas kernel, which GSPMD cannot
    partition ("Mosaic kernels cannot be automatically partitioned"): under
    an active mesh the call runs inside a ``shard_map``, each device on its
    own slice. Without a mesh it is the plain call.

    Every array in ``args`` and the result carry the same leading axis, and
    work along it is independent (batch, or batch x rows folded); None and
    scalar entries pass through as they are. The axis is split over the
    mesh axes that divide it, taken in mesh order — 512 folded rows on
    (dp=2, sp=2) become P((dp, sp)), the pair stream's own layout; a batch
    of 2 becomes P(dp). A mesh axis that does not divide what is left
    repeats the work on its devices: correct and wasteful, never wrong."""
    mesh = _active["mesh"]
    if mesh is None:
        return fn(*args)
    present = [i for i, a in enumerate(args) if getattr(a, "ndim", 0)]
    left = args[present[0]].shape[0]
    names = []
    for name, size in mesh.shape.items():
        if left % size == 0:
            names.append(name)
            left //= size
    spec = P(tuple(names)) if names else P()

    def local(*arrays):
        full = list(args)
        for i, a in zip(present, arrays):
            full[i] = a
        return fn(*full)

    return jax.shard_map(
        local, mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False
    )(*(args[i] for i in present))


def _constrain(x, spec: P):
    mesh = _active["mesh"]
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def pair_spec() -> P:
    mesh = _active["mesh"]
    if mesh is not None:
        from alphafold2_tpu.parallel.grid_parallel import (
            COL_AXIS_NAME,
            ROW_AXIS_NAME,
        )

        if ROW_AXIS_NAME in mesh.axis_names:
            # 2D grid mesh (parallel/grid_parallel.py): rows x cols sharding
            return P(DATA_AXIS, ROW_AXIS_NAME, COL_AXIS_NAME)
    return P(DATA_AXIS, SEQ_AXIS)


def msa_spec(rows: bool = False) -> P:
    """MSA grid (B, M, Nm, D) layout: replicated over sp by default (M is
    tiny next to N^2); ``rows=True`` shards the row axis over sp — the
    tied-row logit contraction then completes with an XLA-inserted psum
    (SURVEY.md S7: "tied-rows becomes a collective"), scaling MSA depth.
    On a 2D grid mesh (no sp axis) the row axis shards over spr instead,
    so tied-row psum composes with the pair-grid layout."""
    if rows:
        mesh = _active["mesh"]
        if mesh is not None and SEQ_AXIS in mesh.axis_names:
            return P(DATA_AXIS, SEQ_AXIS)
        if mesh is not None:
            from alphafold2_tpu.parallel.grid_parallel import ROW_AXIS_NAME

            if ROW_AXIS_NAME in mesh.axis_names:
                return P(DATA_AXIS, ROW_AXIS_NAME)
    return P(DATA_AXIS)


def batch_spec() -> P:
    return P(DATA_AXIS)


def shard_pair(x):
    """Constrain a (B, N, N, D) or (B, N, N) pair array: batch x row sharded."""
    if os.environ.get("AF2TPU_AUDIT_DROP_SHARD_PAIR"):
        # Seeded-defect hook for the HLO audit's negative control (analysis/
        # hlo_audit.py, CI static-analysis job): deliberately drop the pair
        # constraint so the resharding detector must catch the resulting
        # implicit all-gathers / per-device footprint blowup statically.
        # Never set in production; trace-time only, so no runtime cost.
        return x
    return _constrain(x, pair_spec())


def shard_msa(m, rows: bool = False):
    """Constrain a (B, M, Nm, D) MSA array: batch sharded; ``rows=True``
    additionally shards the MSA-row axis over sp (see :func:`msa_spec`)."""
    return _constrain(m, msa_spec(rows))


def shard_batch(t):
    """Constrain any batch-leading array to data-parallel sharding."""
    return _constrain(t, batch_spec())


def replicated(t):
    return _constrain(t, P())


def describe_mesh(mesh: Optional[Mesh]) -> Optional[str]:
    """Stable mesh-identity string, e.g. ``"dp1.spr2.spc4"`` — the key the
    serve executable cache, result cache, bench records and regression gate
    all share, so a CPU-mesh number can never silently compare against a
    differently-sharded (or unsharded) one. None for no mesh."""
    if mesh is None:
        return None
    return ".".join(
        f"{name}{size}" for name, size in zip(mesh.axis_names, mesh.devices.shape)
    )


def parse_mesh_spec(spec: Optional[str]) -> Optional[Mesh]:
    """Build a mesh from a compact CLI/env spec: ``"DPxSPRxSPC"`` (three
    ints — a 2D pair-grid mesh, parallel/grid_parallel.py) or ``"DPxSP"``
    (two ints — the 1D (dp, sp) mesh). Empty/None -> no mesh."""
    if not spec:
        return None
    parts = [int(p) for p in spec.lower().replace("x", " ").split()]
    if len(parts) == 3:
        from alphafold2_tpu.parallel.grid_parallel import make_grid_mesh

        return make_grid_mesh(*parts)
    if len(parts) == 2:
        return make_mesh(parts[0], parts[1])
    raise ValueError(
        f"mesh spec {spec!r} must be 'dpxsprxspc' (grid) or 'dpxsp' (1D)"
    )
