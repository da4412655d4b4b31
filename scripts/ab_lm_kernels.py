#!/usr/bin/env python3
"""A/B of the language model's two kernels at the benchmark cell's shapes.

    chiprun -- python scripts/ab_lm_kernels.py        # times, on a TPU

(a) The stock flash kernel, causal, 2 x 32 heads x 8,192 x 8,192 at head 256
    (q/k 192 and v 128 zero-padded, as ``ops/flash.py`` sends them): forward
    and forward + backward over square blocks of several sides and over the
    non-causal rule's blocks, which are long along the keys.
(b) The grouped matrix product over 16 held experts, 98,304 sorted rows of
    which an eighth is live: ``jax.lax.ragged_dot`` against the stock
    megablox ``gmm`` at three tilings, forward + backward of the expert
    SwiGLU (gate/up as one product, then down).

Each candidate is jitted alone, warmed once, timed best of ``--reps`` with
``block_until_ready``. One JSON line a candidate on stdout and in
``chiprun_out/ab_lm_kernels.jsonl``. Not part of the library: the winner is
written into ``ops/flash.py`` / ``ops/moe.py`` by hand, with PERF.md's record.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

import chip_smoke


def best_ms(fn, args, reps):
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t)
    return 1e3 * min(times)


def flash_candidates(reps):
    from jax.experimental.pallas.ops.tpu import flash_attention as stock

    from alphafold2_tpu.ops import flash

    b, h, n, d = 2, 32, 8192, 256
    keys = jax.random.split(jax.random.key(0), 3)
    q, k, v = (jax.random.normal(kk, (b, h, n, d), jnp.bfloat16)
               for kk in keys)
    sets = {"rule_noncausal": flash.block_sizes_for(
        b, h, n, n, d, jnp.bfloat16)}
    chosen = flash.CAUSAL_BLOCK
    for side in (256, 512, 1024, 2048):
        flash.CAUSAL_BLOCK = side
        sets[f"square_{side}"] = flash.block_sizes_for(
            b, h, n, n, d, jnp.bfloat16, causal=True)
    flash.CAUSAL_BLOCK = chosen
    for name, blocks in sets.items():
        def fwd(q, k, v, blocks=blocks):
            return stock.flash_attention(
                q, k, v, causal=True, sm_scale=192 ** -0.5,
                block_sizes=blocks)

        def both(q, k, v, fwd=fwd):
            return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                            argnums=(0, 1, 2))(q, k, v)

        rec = {"kernel": "flash_causal", "blocks": name}
        try:
            rec["fwd_ms"] = best_ms(jax.jit(fwd), (q, k, v), reps)
            rec["fwd_bwd_ms"] = best_ms(jax.jit(both), (q, k, v), reps)
        except Exception as e:  # a set the compiler refuses
            rec["error"] = repr(e)[:300]
        yield rec


def gmm_candidates(reps):
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    from alphafold2_tpu.ops import moe

    rows, d, f, held = 98304, 2048, 768, 16
    sizes = chip_smoke._group_sizes(rows, held)  # an eighth live, uneven
    group_sizes = jnp.asarray(sizes, jnp.int32)
    keys = jax.random.split(jax.random.key(1), 4)
    x = jax.random.normal(keys[0], (rows, d), jnp.bfloat16)
    w_gate, w_up = (jax.random.normal(kk, (held, d, f), jnp.float32)
                    * d ** -0.5 for kk in keys[1:3])
    w_down = jax.random.normal(keys[3], (held, f, d), jnp.float32) * f ** -0.5
    live = jnp.arange(rows) < int(sizes.sum())

    def ffn(grouped):
        def loss(x, w_gate, w_up, w_down):
            real, moe.grouped_matmul = moe.grouped_matmul, grouped
            try:
                y = moe.expert_ffn(x, group_sizes, w_gate, w_up, w_down,
                                   jnp.bfloat16)
            finally:
                moe.grouped_matmul = real
            return jnp.where(live[:, None], y, 0).astype(jnp.float32).sum()

        return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))

    def megablox_at(tiling):
        # one group more than the weights hold: the rest of the rows, which
        # megablox then leaves out and zeroes
        padded = jnp.concatenate(
            [group_sizes, (rows - group_sizes.sum())[None]])
        return lambda r, w, _: megablox.gmm(
            r, w, padded, r.dtype, tiling)

    candidates = {"ragged_dot": moe.grouped_matmul}
    for tiling in ((128, 128, 128), (512, 512, 512), (512, 1024, 1024)):
        candidates["megablox_" + "x".join(map(str, tiling))] = \
            megablox_at(tiling)
    for name, grouped in candidates.items():
        rec = {"kernel": "expert_ffn_fwd_bwd", "impl": name,
               "live_rows": int(sizes.sum()), "largest_group": int(sizes.max())}
        try:
            rec["ms"] = best_ms(ffn(grouped), (x, w_gate, w_up, w_down), reps)
        except Exception as e:
            rec["error"] = repr(e)[:300]
        yield rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--out", default="chiprun_out/ab_lm_kernels.jsonl")
    args = ap.parse_args(argv)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "a") as out:
        for gen in (flash_candidates, gmm_candidates):
            for rec in gen(args.reps):
                rec["device"] = jax.devices()[0].device_kind
                line = json.dumps(rec)
                print(line, flush=True)
                out.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
