"""Mosaic TPU lowering gate — the auditor's pre-hardware rule set.

``scripts/check_tpu_lowering.py`` is a thin shim over this module, and
``python -m alphafold2_tpu.analysis.jaxpr_audit --rules lowering`` folds
these cases into the same findings stream as the jaxpr rules — one
lowering-gate entry point.

The first compiled-mode Pallas attempt on a real chip died in Mosaic's
``_check_block_mappings`` — an error class interpret-mode tests can never
surface, because interpret mode skips the Mosaic lowering entirely. This
gate runs the FULL Mosaic lowering pipeline on a CPU-only host via JAX's
cross-platform AOT path::

    jax.jit(f).trace(*args).lower(lowering_platforms=("tpu",))

which executes ``jax._src.pallas.mosaic.lowering.lower_jaxpr_to_module`` —
block-mapping tiling checks, scratch allocation, op lowering, the works —
without any TPU backend. Every kernel entry point is lowered, plus the
stock flash-attention kernel at the shapes ``ops/flash.py`` feeds it from
the axial/cross attention paths. (Lowering stops before the chip's
compiler; ``tests/test_chip_compile.py`` compiles the same kernels for a
described v5e, which also refuses what does not fit its fast memory.)

A NEGATIVE CONTROL lowers a deliberately mis-tiled kernel (a (1, block)
row-stat block, the bug class that died on the chip) and requires the gate
to reject it — proving the gate actually detects what it claims to.

Prints one JSON line per case; exit 0 iff every positive case lowers AND
the negative control is rejected.
"""

from __future__ import annotations

import json
import math
import os
import sys
import time

import jax
import jax.numpy as jnp


def lower_for_tpu(fn, *args) -> None:
    """Run the full Mosaic TPU lowering of ``fn(*args)`` on this (CPU)
    host; raises exactly what a real-chip compile's lowering phase would."""
    jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))


def _sparse_inputs(n: int, block_size: int):
    """Block-sparse inputs at the serving geometry: 4 heads, head dim 64, 17
    padded tail keys."""
    from alphafold2_tpu.ops.sparse import BlockSparseConfig

    cfg = BlockSparseConfig(
        block_size=block_size, num_local_blocks=4, num_global_blocks=1,
        num_random_blocks=None,
    )
    layout = cfg.layout(n)
    k1, k2, k3 = jax.random.split(jax.random.key(0), 3)
    shape = (1, 4, n, 64)
    q = jax.random.normal(k1, shape, jnp.float32)
    k = jax.random.normal(k2, shape, jnp.float32)
    v = jax.random.normal(k3, shape, jnp.float32)
    mask = jnp.ones((1, n), bool).at[:, -17:].set(False)
    return q, k, v, layout, mask


def case_block_sparse_fwd(n=512, block_size=128, with_lse=True):
    from alphafold2_tpu.ops.pallas.block_sparse import (
        pallas_block_sparse_attention,
    )

    q, k, v, layout, mask = _sparse_inputs(n, block_size)

    def f(q, k, v):
        return pallas_block_sparse_attention(
            q, k, v, layout, block_size, mask=mask, interpret=False,
            return_lse=with_lse,
        )

    lower_for_tpu(f, q, k, v)


def case_block_sparse_bwd(n=512, block_size=128):
    from alphafold2_tpu.ops.pallas.block_sparse import (
        pallas_block_sparse_attention,
        pallas_block_sparse_attention_bwd,
    )

    q, k, v, layout, mask = _sparse_inputs(n, block_size)

    def f(q, k, v):
        out, lse = pallas_block_sparse_attention(
            q, k, v, layout, block_size, mask=mask, interpret=False,
            return_lse=True,
        )
        return pallas_block_sparse_attention_bwd(
            q, k, v, out, lse, jnp.ones_like(out), layout, block_size,
            mask=mask, interpret=False,
        )

    lower_for_tpu(f, q, k, v)


def case_block_sparse_custom_vjp(n=512, block_size=128):
    """The composed custom_vjp wrapper the model actually calls — grads
    through it exercise fwd+dq+dkv inside one traced program."""
    from alphafold2_tpu.ops import pallas as _p  # noqa: F401
    import alphafold2_tpu.ops.sparse as sparse

    q, k, v, layout, mask = _sparse_inputs(n, block_size)

    def loss(q, k, v):
        o = sparse.block_sparse_attention_pallas(
            q, k, v, layout, block_size, mask=mask, interpret=False,
        )
        return jnp.sum(o * o)

    lower_for_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)


def _stock_flash(q_shape, kv_shape):
    """The stock jax flash kernel at the (pre-padded, segment-id-masked)
    shapes ops/flash.py produces for the axial and compressed-cross paths."""
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        SegmentIds,
        flash_attention as _fa,
    )

    b, h, nq, d = q_shape
    nk = kv_shape[2]
    q = jnp.ones(q_shape, jnp.float32)
    k = jnp.ones(kv_shape, jnp.float32)
    v = jnp.ones(kv_shape, jnp.float32)
    qs = jnp.ones((b, nq), jnp.int32)
    ks = jnp.ones((b, nk), jnp.int32)

    def f(q, k, v):
        return _fa(
            q, k, v, segment_ids=SegmentIds(q=qs, kv=ks), sm_scale=0.125
        )

    lower_for_tpu(f, q, k, v)


def case_flash_axial_256():
    # axial row/col pass at crop 256: (B*N, H, N, D) with B*N folded small
    _stock_flash((4, 8, 256, 64), (4, 8, 256, 64))


def case_flash_compressed_cross():
    # pair-stream queries (crop 64 -> 4096 tokens) against a 128-padded
    # compressed MSA context — the ops/flash.py wrapper's padded geometry
    _stock_flash((1, 8, 4096, 64), (1, 8, 128, 64))


def case_flash_bwd_256():
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        SegmentIds,
        flash_attention as _fa,
    )

    shape = (2, 8, 256, 64)
    q = jnp.ones(shape, jnp.float32)
    k = jnp.ones(shape, jnp.float32)
    v = jnp.ones(shape, jnp.float32)
    qs = jnp.ones((2, 256), jnp.int32)

    def loss(q, k, v):
        o = _fa(q, k, v, segment_ids=SegmentIds(q=qs, kv=qs), sm_scale=0.125)
        return jnp.sum(o * o)

    lower_for_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)


def case_ring_flash_step(nq_local=32768, nk_local=2048):
    """The ring of flash blocks (parallel/seq_parallel.py) at a chip's share
    of the mesh cell's cross-attention, sp = 2, bf16, forward and backward:
    the stock kernel's three calls lower at the blocks ``block_sizes_for``
    gives the local shape, and no visiting block's float32 logits
    (8 x 32,768 x 2,048) exist anywhere in the module."""
    import re
    from unittest import mock

    from alphafold2_tpu.ops import flash
    from alphafold2_tpu.parallel.seq_parallel import (
        sequence_parallel_attention,
    )
    from alphafold2_tpu.parallel.sharding import make_mesh

    if len(jax.devices()) < 2:
        raise RuntimeError(
            "the ring needs two devices: set XLA_FLAGS="
            "--xla_force_host_platform_device_count=2 (main() does)")
    mesh = make_mesh(1, 2, devices=jax.devices()[:2])
    q = jnp.ones((1, 8, 2 * nq_local, 64), jnp.bfloat16)
    k = jnp.ones((1, 8, 2 * nk_local, 64), jnp.bfloat16)
    mask = jnp.ones((1, 2 * nk_local), bool).at[:, -9:].set(False)

    def loss(q, k, v):
        o = sequence_parallel_attention(
            q, k, v, mask=mask, mesh=mesh, impl="ring")
        return jnp.sum(o.astype(jnp.float32) ** 2)

    # the ring asks ops/flash.py whether a TPU is there; this process is
    # pinned to the CPU, so the case answers for the chip
    with mock.patch.object(flash, "flash_available", lambda: True):
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(
            q, k, k).lower(lowering_platforms=("tpu",)).as_text()
    kernels = text.count("tpu_custom_call")
    if kernels != 6:  # forward, dq, dkv for each of the two ring steps
        raise RuntimeError(f"expected 6 Mosaic kernels, lowered {kernels}")
    logits = 8 * nq_local * nk_local
    dense = sorted({
        shape for shape in re.findall(r"tensor<([0-9x]+)xf32>", text)
        if math.prod(int(n) for n in shape.split("x")) >= logits
    })
    if dense:
        raise RuntimeError(f"float32 arrays the size of a block's logits: "
                           f"{dense}")


def case_grouped_causal_core(window=None, heads=28, groups=4, n=16384,
                             width=128, fused=False):
    """The language models' causal core (ops/mla.py) as the grouped-query
    model calls it at its cell's shape: 28 query heads over 4 key/value
    heads of 128 at 16,384 positions in bf16, under the causal mask or a
    window, forward and the two-kernel backward: three splash kernels lower
    (forward, dq, dkv), and nothing the size of a head's logits or of keys
    broadcast to the query heads exists in the module. The hybrid model's
    attention layer calls it at 32 query heads over 2 key/value heads and
    8,192 positions, where the fused backward fits (``fused``): two kernels."""
    import re
    from unittest import mock

    from alphafold2_tpu.ops import mla

    q = jnp.ones((1, heads, n, width), jnp.bfloat16)
    k = jnp.ones((1, groups, n, width), jnp.bfloat16)

    def loss(q, k, v):
        o = mla.causal_core(q, k, v, window=window)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    # the core asks JAX whether a TPU is there; this process is pinned to
    # the CPU, so the case answers for the chip
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(
            q, k, k).lower(lowering_platforms=("tpu",)).as_text()
    kernels, want_kernels = text.count("tpu_custom_call"), 2 if fused else 3
    if kernels != want_kernels:
        raise RuntimeError(
            f"expected {want_kernels} Mosaic kernels, lowered {kernels}")
    # the fused backward's partial dq, one a key block, is the one array
    # over the queries' size that it is entitled to
    partial = f"{heads}x{n}x{width}"
    big = sorted({
        shape for shape in re.findall(r"tensor<([0-9x]+)x(?:f32|bf16)>", text)
        if math.prod(int(d) for d in shape.split("x")) > heads * n * width
        and not (fused and shape.endswith(partial))
    })
    if big:
        raise RuntimeError(f"arrays larger than the queries: {big}")


def case_fused_axial_fwd(n=256):
    """The in-repo fused dense attention kernel (ops/pallas/axial.py) at
    the axial-pass shape, compiled-mode Mosaic lowering with a padding
    mask (the bias-streaming layout is what tiling checks bite on)."""
    from alphafold2_tpu.ops.pallas.axial import fused_attention

    b, h, d = 2, 4, 64
    k1, k2, k3 = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(k1, (b, h, n, d), jnp.float32)
    k = jax.random.normal(k2, (b, h, n, d), jnp.float32)
    v = jax.random.normal(k3, (b, h, n, d), jnp.float32)
    mask = jnp.ones((b, n), bool).at[:, -17:].set(False)

    def f(q, k, v):
        return fused_attention(
            q, k, v, q_mask=mask, kv_mask=mask, sm_scale=d**-0.5,
            interpret=False,
        )

    lower_for_tpu(f, q, k, v)


def case_fused_axial_bwd(n=256):
    """Gradients through the fused kernel's custom VJP: lowers the dq and
    dk/dv kernels inside one traced program."""
    from alphafold2_tpu.ops.pallas.axial import fused_attention

    b, h, d = 2, 4, 64
    q = jnp.ones((b, h, n, d), jnp.float32)
    mask = jnp.ones((b, n), bool).at[:, -17:].set(False)

    def loss(q, k, v):
        o = fused_attention(
            q, k, v, kv_mask=mask, sm_scale=d**-0.5, interpret=False
        )
        return jnp.sum(o * o)

    lower_for_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)


def case_tied_row_fwd(n=256):
    """The fused tied-row MSA kernel at trunk shape: fused feature axis
    R*D = 512 exercises the wide-accumulator tiling."""
    from alphafold2_tpu.ops.pallas.tied_row import tied_row_attention

    b, r, h, d = 1, 8, 4, 64
    q = jnp.ones((b, r, n, h, d), jnp.float32)
    mask = jnp.ones((b, n), bool).at[:, -9:].set(False)

    def f(q, k, v):
        return tied_row_attention(
            q, k, v, q_mask=mask, kv_mask=mask, sm_scale=d**-0.5,
            interpret=False,
        )

    lower_for_tpu(f, q, q, q)


def case_tied_row_bwd(n=256):
    from alphafold2_tpu.ops.pallas.tied_row import tied_row_attention

    b, r, h, d = 1, 8, 4, 64
    q = jnp.ones((b, r, n, h, d), jnp.float32)
    mask = jnp.ones((b, n), bool).at[:, -9:].set(False)

    def loss(q, k, v):
        o = tied_row_attention(
            q, k, v, kv_mask=mask, sm_scale=d**-0.5, interpret=False
        )
        return jnp.sum(o * o)

    lower_for_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)


def case_negative_control():
    """The bug class that died on the chip, reconstructed: a (1, block) row-stat output
    block on a (rows, n) array. The gate MUST reject it — if this lowers,
    the gate is not checking what it claims and the run fails."""
    from jax.experimental import pallas as pl

    def kern(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    def f(x):
        return pl.pallas_call(
            kern,
            out_shape=jax.ShapeDtypeStruct((4, 512), jnp.float32),
            grid=(4,),
            in_specs=[pl.BlockSpec((1, 512), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((1, 512), lambda i: (i, 0)),
        )(x)

    x = jnp.ones((4, 512), jnp.float32)
    try:
        lower_for_tpu(f, x)
    except Exception as e:
        if _is_mosaic_tiling_rejection(e):
            return  # gate correctly rejects the bug class
        raise
    raise AssertionError(
        "negative control LOWERED: the gate is not exercising Mosaic's "
        "tiling checks (jax behavior change?) — do not trust green results"
    )


def _is_mosaic_tiling_rejection(e: BaseException) -> bool:
    """Does this exception look like Mosaic's lowering rejecting the
    mis-tiled kernel? The old exact-substring match ('divisible by 8 and
    128') turned into a false RED whenever JAX reworded the message; accept
    any error that (a) mentions tiling/block-shape vocabulary, or (b) was
    raised from inside the Pallas/Mosaic lowering code, chained causes
    included. The hard failure stays only for the case that matters: the
    bad kernel lowering CLEANLY."""
    seen = set()
    while e is not None and id(e) not in seen:
        seen.add(id(e))
        msg = str(e).lower()
        if any(
            s in msg
            for s in (
                "divisible by",
                "tiling",
                "tile",
                "block shape",
                "block_shape",
                "layout",
            )
        ):
            return True
        tb = e.__traceback__
        while tb is not None:
            fname = tb.tb_frame.f_code.co_filename.lower()
            if "pallas" in fname or "mosaic" in fname:
                return True
            tb = tb.tb_next
        e = e.__cause__ or e.__context__
    return False


CASES = [
    ("block_sparse_fwd_n512", lambda: case_block_sparse_fwd(512)),
    ("block_sparse_fwd_nolse_n512",
     lambda: case_block_sparse_fwd(512, with_lse=False)),
    ("block_sparse_fwd_n1024", lambda: case_block_sparse_fwd(1024)),
    ("block_sparse_bwd_n512", lambda: case_block_sparse_bwd(512)),
    ("block_sparse_bwd_n1024", lambda: case_block_sparse_bwd(1024)),
    ("block_sparse_custom_vjp_n512", case_block_sparse_custom_vjp),
    ("flash_axial_256", case_flash_axial_256),
    ("flash_compressed_cross", case_flash_compressed_cross),
    ("flash_bwd_256", case_flash_bwd_256),
    ("ring_flash_pair_from_msa", case_ring_flash_step),
    ("ring_flash_msa_from_pair", lambda: case_ring_flash_step(2048, 32768)),
    ("grouped_causal_core_16k", case_grouped_causal_core),
    ("grouped_window_core_16k", lambda: case_grouped_causal_core(4096)),
    ("grouped_causal_core_32_2_8k", lambda: case_grouped_causal_core(
        heads=32, groups=2, n=8192, fused=True)),
    ("fused_axial_fwd_256", case_fused_axial_fwd),
    ("fused_axial_bwd_256", case_fused_axial_bwd),
    ("tied_row_fwd_256", case_tied_row_fwd),
    ("tied_row_bwd_256", case_tied_row_bwd),
    ("negative_control_rejects_bad_tiling", case_negative_control),
]


def run_gate(names=()) -> tuple:
    """Run the named cases (all when empty). Returns (records, failed)."""
    run = [(n, f) for n, f in CASES if not names or n in names]
    records = []
    failed = []
    for name, fn in run:
        t0 = time.monotonic()
        try:
            fn()
            rec = {"case": name, "ok": True}
        except Exception as e:
            failed.append(name)
            rec = {
                "case": name, "ok": False,
                "error": f"{type(e).__name__}: {str(e)[:500]}",
            }
        rec["seconds"] = round(time.monotonic() - t0, 1)
        records.append(rec)
    return records, failed


def main(argv=None) -> int:
    # the ring cases shard over two devices; the CPU backend makes them up
    # (read when the backend starts, which nothing has done yet)
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=2").strip()
    names = (argv or sys.argv)[1:]
    unknown = sorted(set(names) - {n for n, _ in CASES})
    if unknown:
        # a typo'd case name must be a loud red, not a zero-case run that
        # exits green having certified nothing
        print(json.dumps({
            "gate": "tpu_lowering",
            "error": f"unknown case name(s): {unknown}",
            "known": [n for n, _ in CASES],
        }), flush=True)
        return 2
    records, failed = run_gate(names)
    for rec in records:
        print(json.dumps(rec), flush=True)
    print(json.dumps({
        "gate": "tpu_lowering", "cases": len(records), "failed": failed,
    }), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
