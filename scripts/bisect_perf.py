"""Microbenchmark the hot modules at flagship bench shapes on the attached
accelerator: per-module fwd+bwd time and achieved FLOPs/s, to locate where
the step's time goes without reducing a full profiler trace.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import alphafold2_tpu

alphafold2_tpu.enable_compile_cache()

import jax
import jax.numpy as jnp

from alphafold2_tpu.observe.flops import step_flops
from alphafold2_tpu.ops.attention import Attention, AxialAttention, FeedForward

CROP = int(os.environ.get("AF2TPU_BENCH_CROP", 256))
MSA_D = int(os.environ.get("AF2TPU_BENCH_MSA_DEPTH", 16))
MSA_L = int(os.environ.get("AF2TPU_BENCH_MSA_LEN", 256))
DIM = 256
ITERS = 10


def timed(name, module, *args, **kwargs):
    params = module.init(jax.random.key(0), *args, **kwargs)

    def loss(p):
        out = module.apply(p, *args, **kwargs)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    step = jax.jit(jax.value_and_grad(loss))
    compiled = step.lower(params).compile()
    flops = step_flops(compiled) or 0.0  # observe.flops: the one parser

    compiled(params)[0].block_until_ready()
    t0 = time.perf_counter()
    for _ in range(ITERS):
        l, _ = compiled(params)
    l.block_until_ready()
    dt = (time.perf_counter() - t0) / ITERS
    print(f"{name:42s} {dt*1e3:8.2f} ms  {flops/dt/1e12:6.1f} TF/s  "
          f"({flops/1e9:.1f} GFLOP)")
    return dt


def main():
    dt = jnp.bfloat16
    k = jax.random.key(1)
    pair = jax.random.normal(k, (1, CROP, CROP, DIM), dt)
    msa = jax.random.normal(k, (1, MSA_D, MSA_L, DIM), dt)
    pair_flat = pair.reshape(1, CROP * CROP, DIM)
    msa_flat = msa.reshape(1, MSA_D * MSA_L, DIM)

    print(f"crop={CROP} msa={MSA_D}x{MSA_L} dim={DIM} device="
          f"{jax.devices()[0].device_kind}\n")

    total = 0.0
    total += timed(
        "pair AxialAttention (grid-native, flash)",
        AxialAttention(dim=DIM, heads=8, dim_head=64, dtype=dt), pair,
    )
    # the A/B for the grid-native default: the flat route materializes a
    # transpose of the whole pair map for the column pass. 3 extra compiles
    # of the hottest module — AF2TPU_BENCH_AB=0 skips once the question is
    # settled on real hardware.
    if os.environ.get("AF2TPU_BENCH_AB", "1") == "1":
        timed(
            "pair AxialAttention (flat route, flash)",
            AxialAttention(dim=DIM, heads=8, dim_head=64, grid_native=False,
                           dtype=dt),
            pair,
        )
        timed(
            "pair AxialAttention (grid-native, no flash)",
            AxialAttention(dim=DIM, heads=8, dim_head=64, use_flash=False,
                           dtype=dt),
            pair,
        )
        timed(
            "pair AxialAttention (flat route, no flash)",
            AxialAttention(dim=DIM, heads=8, dim_head=64, use_flash=False,
                           grid_native=False, dtype=dt),
            pair,
        )
    total += timed(
        "msa AxialAttention tied",
        AxialAttention(dim=DIM, heads=8, dim_head=64, tie_row_attn=True, dtype=dt),
        msa,
    )
    total += timed(
        "cross pair<-msa (flash)",
        Attention(dim=DIM, heads=8, dim_head=64, dtype=dt),
        pair_flat, context=msa_flat,
    )
    total += timed(
        "cross msa<-pair (flash)",
        Attention(dim=DIM, heads=8, dim_head=64, dtype=dt),
        msa_flat, context=pair_flat,
    )
    total += timed(
        "pair FeedForward",
        FeedForward(dim=DIM, dtype=dt), pair,
    )
    total += timed(
        "msa FeedForward",
        FeedForward(dim=DIM, dtype=dt), msa,
    )
    # per trunk layer = pair axial + msa axial + 2 cross + 2 FF (one config of
    # the two axial baselines applies)
    print(f"\nsum of micro-times (one of each): {total*1e3:.2f} ms")


if __name__ == "__main__":
    main()
