#!/usr/bin/env python3
"""Time the stock Pallas flash kernel's forward, dq and dkv over block sizes.

The measurement behind ``ops/flash.py``'s ``block_sizes_for``: each of the
stock kernel's three ``pallas_call``s is jitted alone (with the l/m/di
broadcasts its wrapper makes, which are part of what a block choice costs),
warmed once and timed over a few repetitions with ``block_until_ready``.
Inputs are bf16 with segment ids, as ``Attention.__call__`` sends them.

    chiprun -- python scripts/sweep_flash_blocks.py            # time, on a TPU
    JAX_PLATFORMS=cpu python scripts/sweep_flash_blocks.py --compile-only
                                 # which candidates fit: a described v5e, no time

One JSON line a candidate on stdout, all of them in
``chiprun_out/flash_sweep.jsonl``. Not part of the library: nothing imports
it, and no switch for block sizes exists anywhere else.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import flash_attention as stock

# (batch, heads, nq, nk, head_dim): the shape classes the benchmark's cells run
SHAPES = {
    "pair_from_msa": (1, 8, 65536, 4096, 64),
    "msa_from_pair": (1, 8, 4096, 65536, 64),
    "pair_axial": (256, 8, 256, 256, 64),
    "pair_axial_mesh": (128, 8, 256, 256, 64),
    # outside the cells: lengths that only 128 (or the whole axis) divides
    "compressed_1408": (1, 8, 65536, 1408, 64),
    "pair_axial_crop384": (384, 8, 384, 384, 64),
    # the longest key axis the rule takes whole, against 1,024 keys at a time
    "cross_keys_2048": (1, 8, 65536, 2048, 64),
}
SM_SCALE = 0.125


ODD = {
    "compressed_1408": {
        "fwd": [(1, 128, 128, 128), (1, 512, 1408, 128), (1, 512, 1408, 1408),
                (1, 1024, 1408, 1408)],
        "dkv": [(128, 128, 128, 128), (1024, 256, 128, 128),
                (1024, 256, 1408, 1408), (1024, 128, 1408, 1408)],
        "dq": [(128, 128, 128), (1024, 128, 128), (2048, 128, 128)],
    },
    "cross_keys_2048": {
        "fwd": [(1, 128, 128, 128), (1, 512, 2048, 2048), (1, 512, 2048, 1024),
                (1, 512, 1024, 1024), (1, 1024, 2048, 1024),
                (1, 256, 2048, 2048)],
        "dkv": [(128, 128, 128, 128), (1024, 256, 2048, 2048),
                (1024, 256, 2048, 1024), (1024, 256, 1024, 1024),
                (512, 256, 2048, 2048), (1024, 128, 2048, 2048)],
        "dq": [(128, 128, 128), (1024, 512, 512), (1024, 256, 256)],
    },
    "pair_axial_crop384": {
        "fwd": [(1, 128, 128, 128), (1, 384, 384, 128), (1, 384, 384, 384),
                (2, 384, 384, 384)],
        "dkv": [(128, 128, 128, 128), (384, 128, 384, 128),
                (384, 128, 384, 384), (384, 384, 384, 384)],
        "dq": [(128, 128, 128), (384, 128, 128), (384, 384, 384)],
    },
}


def candidates(shape_name: str) -> dict:
    """kernel -> list of block tuples. fwd: (block_b, block_q, block_k_major,
    block_k); dkv: (block_q_major, block_q, block_k_major, block_k); dq:
    (block_q, block_k_major, block_k)."""
    if shape_name in ODD:
        return ODD[shape_name]
    b, h, nq, nk, d = SHAPES[shape_name]
    if shape_name.startswith("pair_axial"):
        fwd = [(1, 128, 128, 128), (1, 256, 256, 128), (1, 256, 256, 256),
               (2, 256, 256, 256), (4, 256, 256, 256), (8, 256, 256, 256),
               (16, 256, 256, 256), (4, 128, 256, 256)]
        dkv = [(128, 128, 128, 128), (256, 128, 256, 128),
               (256, 256, 256, 128), (256, 128, 256, 256),
               (256, 256, 256, 256)]
        dq = [(128, 128, 128), (256, 128, 128), (256, 256, 128),
              (256, 256, 256), (128, 256, 256)]
        if shape_name == "pair_axial_mesh":  # the same rule must win at half
            fwd = [fwd[0], fwd[2], fwd[4], fwd[5]]
            dkv = [dkv[0], dkv[-1]]
            dq = [dq[0], dq[3]]
        return {"fwd": fwd, "dkv": dkv, "dq": dq}
    fwd = [(1, bq, bkm, bk)
           for bq in (256, 512, 1024, 2048)
           for bkm in (512, 1024, 2048, 4096)
           for bk in (256, 512, 1024)
           if bk <= bkm and bkm // bk <= 8 and bq * bk <= 1024 * 1024
           # (1024, 4096, *) and (2048, 2048, *) run out of scoped VMEM
           and bq * bkm < 2048 * 2048]
    if nk <= 4096:  # the single-step body: block_k == nk
        fwd += [(1, bq, nk, nk) for bq in (128, 256, 512)]
    dkv = [(bqm, bq, bkm, bk)
           for bqm in (512, 1024, 2048)
           for bq in (256, 512, 1024)
           for bkm in (512, 1024, 2048, 4096)
           for bk in (256, 512, 1024)
           if bq <= bqm and bk <= bkm and bq * bk <= 512 * 1024
           and (bqm // bq) * (bkm // bk) <= 8]
    dq = [(bq, bkm, bk)
          for bq in (256, 512, 1024, 2048)
          for bkm in (256, 512, 1024, 2048)
          for bk in (256, 512, 1024)
          if bk <= bkm and bkm // bk <= 4 and bq * bk <= 1024 * 512
          # the stock dq wrapper materialises di at (b, h, nq, bkm) f32
          and b * h * nq * bkm * 4 <= 2.5 * 2**30]
    return {"fwd": [(1, 128, 128, 128)] + fwd,
            "dkv": [(128, 128, 128, 128)] + dkv,
            "dq": [(128, 128, 128)] + dq}


def make_inputs(shape, key):
    b, h, nq, nk, d = shape
    kq, kk, kv, kd = jax.random.split(key, 4)
    q = jax.random.normal(kq, (b, h, nq, d), jnp.float32).astype(jnp.bfloat16)
    k = jax.random.normal(kk, (b, h, nk, d), jnp.float32).astype(jnp.bfloat16)
    v = jax.random.normal(kv, (b, h, nk, d), jnp.float32).astype(jnp.bfloat16)
    do = jax.random.normal(kd, (b, h, nq, d), jnp.float32).astype(jnp.bfloat16)
    seg = stock.SegmentIds(q=jnp.ones((b, nq), jnp.int32),
                           kv=jnp.ones((b, nk), jnp.int32))
    return q, k, v, do, seg


def kernel_fn(kernel: str, blocks: tuple):
    """One jitted function of (q, k, v, seg, l, m, do, di) that runs one of
    the stock kernel's three pallas_calls at ``blocks``."""
    if kernel == "fwd":
        bb, bq, bkm, bk = blocks

        def fn(q, k, v, seg, l, m, do, di):
            return stock._flash_attention_impl(
                q, k, v, None, seg, True, False, SM_SCALE, bb, bq, bkm, bk,
                False)
    elif kernel == "dkv":
        bqm, bq, bkm, bk = blocks

        def fn(q, k, v, seg, l, m, do, di):
            return stock._flash_attention_bwd_dkv(
                q, k, v, None, seg, l, m, do, di, block_q_major=bqm,
                block_q=bq, block_k_major=bkm, block_k=bk, sm_scale=SM_SCALE)
    else:
        bq, bkm, bk = blocks

        def fn(q, k, v, seg, l, m, do, di):
            return stock._flash_attention_bwd_dq(
                q, k, v, None, seg, l, m, do, di, block_q_major=bq,
                block_k_major=bkm, block_k=bk, sm_scale=SM_SCALE,
                causal=False, mask_value=stock.DEFAULT_MASK_VALUE,
                debug=False)[0]
    return jax.jit(fn)


def arg_shapes(shape, sharding):
    b, h, nq, nk, d = shape

    def s(shp, dt):
        return jax.ShapeDtypeStruct(shp, jnp.dtype(dt), sharding=sharding)

    q, kv = s((b, h, nq, d), "bfloat16"), s((b, h, nk, d), "bfloat16")
    seg = stock.SegmentIds(q=s((b, nq), "int32"), kv=s((b, nk), "int32"))
    lm = s((b, h, nq), "float32")
    return q, kv, kv, seg, lm, lm, q, lm


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--compile-only", action="store_true",
                    help="compile for a described v5e; nothing is timed")
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--kernels", default="fwd,dkv,dq")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--out", default="chiprun_out/flash_sweep.jsonl")
    args = ap.parse_args(argv)

    sharding = None  # --compile-only: one chip of a described v5e
    if args.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        sharding = SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", False)
    elif jax.default_backend() != "tpu":
        print("needs a TPU (or --compile-only)", file=sys.stderr)
        return 1
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "a") as out:
        sweep(args, sharding, out)
    return 0


def sweep(args, sharding, out) -> None:
    """Every candidate of every chosen shape and kernel: one record each,
    printed and appended to ``out``."""
    kernels = args.kernels.split(",")
    for shape_name in args.shapes.split(","):
        shape = SHAPES[shape_name]
        if not args.compile_only:
            q, k, v, do, seg = make_inputs(shape, jax.random.key(0))
            o, l, m = jax.jit(lambda q, k, v, seg: stock._flash_attention_impl(
                q, k, v, None, seg, True, False, SM_SCALE, 1, 128, 128, 128,
                False))(q, k, v, seg)
            di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), -1)
            operands = (q, k, v, seg, l, m, do, di)
        for kernel, cands in candidates(shape_name).items():
            if kernel not in kernels:
                continue
            for blocks in cands:
                rec = {"shape": shape_name, "kernel": kernel,
                       "blocks": list(blocks)}
                t0 = time.perf_counter()
                try:
                    fn = kernel_fn(kernel, blocks)
                    if args.compile_only:
                        fn.lower(*arg_shapes(shape, sharding)).compile()
                        rec["compile_s"] = round(time.perf_counter() - t0, 2)
                    else:
                        jax.block_until_ready(fn(*operands))
                        rec["compile_s"] = round(time.perf_counter() - t0, 2)
                        times = []
                        for _ in range(args.reps):
                            t1 = time.perf_counter()
                            jax.block_until_ready(fn(*operands))
                            times.append(time.perf_counter() - t1)
                        rec["ms"] = round(min(times) * 1e3, 3)
                        rec["ms_all"] = [round(t * 1e3, 3) for t in times]
                except Exception as e:  # a block set the compiler refuses
                    msg = str(e)
                    at = msg.find("exceed")
                    rec["error"] = (msg[max(0, at - 120): at + 160]
                                    if at >= 0 else msg[:300])
                line = json.dumps(rec)
                print(line, flush=True)
                out.write(line + "\n")
                out.flush()


if __name__ == "__main__":
    sys.exit(main())
