#!/usr/bin/env python3
"""A/B of the language models' kernels at the benchmark cells' shapes.

    JAX_PLATFORMS=cpu python scripts/ab_lm_kernels.py --compile-only
                                  # which attention candidates Mosaic takes
    chiprun -- python scripts/ab_lm_kernels.py        # times, on a TPU
    chiprun -- python scripts/ab_lm_kernels.py --kernels attention
    chiprun -- python scripts/ab_lm_kernels.py --kernels scan
    python scripts/ab_lm_kernels.py --kernels conv    # on a TPU

(a) The causal core, 2 x 32 heads x 8,192 x 8,192, forward and forward +
    backward: the stock flash kernel at head 256 (q/k 192 and v 128
    zero-padded, as the tree ran it until PR 31) in square blocks, against
    the splash kernel at 192/128 under a causal mask over its block sizes,
    with the two-kernel backward and the fused one, and at what
    ``ops/mla.py`` ``splash_block_sizes`` gives.
(b) The grouped matrix product over 16 held experts, 98,304 sorted rows of
    which an eighth is live: ``jax.lax.ragged_dot`` against the stock
    megablox ``gmm`` at three tilings, forward + backward of the expert
    SwiGLU (gate/up as one product, then down).
(c) The chunked state-space scan of the hybrid model's cell, 1 x 8,192 steps
    of 64 heads x 64 over 8 groups of 128 state rows in chunks of 128,
    bfloat16 products: the XLA form (``ops/ssm.py`` ``ssd_chunks_xla``)
    beside the Pallas kernels (``ops/pallas/ssd.py``), forward alone and
    forward + backward towards x, dt, A, B and C.
(d) The causal depthwise convolution of the two state-space cells, 1 x
    8,192 steps of 6,144 and of 4,352 bfloat16 channels, 4 taps: the XLA
    form (``ops/ssm.py`` ``causal_conv_xla``) beside the Pallas kernels
    (``ops/pallas/conv.py``), forward alone and forward + backward.

Each candidate is jitted alone, warmed once, timed best of ``--reps`` with
``block_until_ready``. One JSON line a candidate on stdout and in
``chiprun_out/ab_lm_kernels.jsonl``. Not part of the library: the winner is
written into ``ops/mla.py`` / ``ops/moe.py`` by hand, with PERF.md's record.
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


B, H, N, D_QK, D_V = 2, 32, 8192, 192, 128  # the cell's causal core


def _measure(rec, fwd, shapes, reps, sharding, backward=True):
    """Times of ``fwd`` and (``backward``) its gradient at ``shapes``; under
    ``--compile-only`` (``sharding`` a described chip) the compile's verdict
    and the bytes of its temporaries."""
    def both(q, k, v):
        return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    fwd, both = jax.jit(fwd), jax.jit(both)
    try:
        if sharding is not None:
            args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=sharding)
                    for s in shapes]
            fwd.lower(*args).compile()
            if backward:
                rec["fwd_bwd_temp_bytes"] = both.lower(
                    *args).compile().memory_analysis().temp_size_in_bytes
        else:
            keys = jax.random.split(jax.random.key(0), len(shapes))
            args = [jax.random.normal(kk, s, jnp.bfloat16)
                    for kk, s in zip(keys, shapes)]
            rec["fwd_ms"] = best_ms(fwd, args, reps)
            if backward:
                rec["fwd_bwd_ms"] = best_ms(both, args, reps)
    except Exception as e:  # a set the compiler refuses
        rec["error"] = repr(e)[:300]
    return rec


def flash_candidates(reps, sharding):
    """The stock flash kernel at head 256 (q/k 192 and v 128 zero-padded, as
    the tree sent them until PR 31), causal, in square blocks."""
    from jax.experimental.pallas.ops.tpu import flash_attention as stock

    shapes = [(B, H, N, 256)] * 3
    for side, inner in ((512, 512), (1024, 512)):
        blocks = stock.BlockSizes(
            block_q=side, block_k_major=side, block_k=inner, block_b=1,
            block_q_major_dkv=side, block_k_major_dkv=side,
            block_k_dkv=inner, block_q_dkv=256,
            # di (b, h, n, block_k_major_dq) float32 within 1 GiB
            block_k_major_dq=512, block_k_dq=512, block_q_dq=side)

        def fwd(q, k, v, blocks=blocks):
            return stock.flash_attention(
                q, k, v, causal=True, sm_scale=D_QK ** -0.5,
                block_sizes=blocks)

        yield _measure(
            {"kernel": "flash_causal_256", "blocks": f"square_{side}"},
            fwd, shapes, reps, sharding)


def splash_candidates(reps, sharding):
    """The splash kernel at 192/128 under a causal mask: forward blocks
    (block_q, block_kv, block_kv_compute) alone, then the two backward forms
    at (block_q_dkv, block_kv_dkv, block_kv_dkv_compute) behind one forward
    (the two-kernel form's dq at the dkv's block_q, block_kv). The last
    candidate is what ``ops/mla.py`` ``splash_block_sizes`` gives."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
        splash_attention_mask as sm,
    )

    from alphafold2_tpu.ops import mla

    shapes = [(B, H, N, D_QK), (B, H, N, D_QK), (B, H, N, D_V)]
    mask = sm.MultiHeadMask([sm.CausalMask((N, N))] * H)
    # a block of 2,048 on either axis runs out of scoped VMEM in every
    # kernel (compile for a described v5e, PR 31), so 512 and 1,024 it is
    sets = {}
    grid = ((512, 512, 512), (512, 1024, 512), (1024, 512, 512),
            (1024, 1024, 256), (1024, 1024, 512), (1024, 1024, 1024))
    for bq, bkv, comp in grid:
        sets[f"fwd_{bq}_{bkv}_{comp}"] = sk.BlockSizes(
            block_q=bq, block_kv=bkv, block_kv_compute=comp)
    forward = dict(block_q=1024, block_kv=1024, block_kv_compute=512)
    for bq, bkv, comp in grid:
        back = dict(block_q_dkv=bq, block_kv_dkv=bkv,
                    block_kv_dkv_compute=comp)
        sets[f"unfused_{bq}_{bkv}_{comp}"] = sk.BlockSizes(
            **forward, **back, block_q_dq=bq, block_kv_dq=bkv)
        sets[f"fused_{bq}_{bkv}_{comp}"] = sk.BlockSizes(
            **forward, **back, use_fused_bwd_kernel=True)
    # the layouts: keys or values handed over positions-minor (a transpose
    # by XLA before the call for one inside the kernel a block)
    seq_minor = sk.QKVLayout.SEQ_MINOR
    for name, layouts in (("k", dict(k_layout=seq_minor)),
                          ("v", dict(v_layout=seq_minor)),
                          ("kv", dict(k_layout=seq_minor,
                                      v_layout=seq_minor))):
        sets[f"fwd_1024_1024_256_{name}_seq_minor"] = sk.BlockSizes(
            block_q=1024, block_kv=1024, block_kv_compute=256, **layouts)
    sets["fused_1024_1024_512_k_seq_minor"] = sk.BlockSizes(
        **forward, block_q_dkv=1024, block_kv_dkv=1024,
        block_kv_dkv_compute=512, use_fused_bwd_kernel=True,
        k_layout=seq_minor)
    sets["rule"] = mla.splash_block_sizes(H, N, D_QK, D_V, jnp.bfloat16)
    for name, blocks in sets.items():
        with jax.ensure_compile_time_eval():
            kernel = sk.make_splash_mha(
                mask, head_shards=1, q_seq_shards=1, block_sizes=blocks)

        def fwd(q, k, v, kernel=kernel):
            return jax.vmap(kernel)(q * D_QK ** -0.5, k, v)

        yield _measure({"kernel": "splash_causal_192_128", "blocks": name},
                       fwd, shapes, reps, sharding,
                       backward=blocks.has_backward_blocks)


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


def scan_candidates(reps):
    from alphafold2_tpu.ops import ssm
    from alphafold2_tpu.ops.pallas import ssd

    heads, width, groups, n, length, chunk = 64, 64, 8, 128, 8192, 128
    x, b, c, dt, a, weight = chip_smoke._scan_inputs(
        heads, width, groups, n, length)
    x, b, c = (t.astype(jnp.bfloat16) for t in (x, b, c))
    for name, form in (("xla", ssm.ssd_chunks_xla),
                       ("pallas", ssd.ssd_chunks)):
        def fwd(x, dt, a, b, c, form=form):
            return form(x, dt, a, b, c, chunk, jnp.bfloat16)[0]

        def both(*args, fwd=fwd):
            return jax.grad(lambda *v: (fwd(*v) * weight).sum(),
                            argnums=(0, 1, 2, 3, 4))(*args)

        rec = {"kernel": "ssd_scan_8k", "impl": name}
        try:
            rec["fwd_ms"] = best_ms(jax.jit(fwd), (x, dt, a, b, c), reps)
            rec["fwd_bwd_ms"] = best_ms(jax.jit(both), (x, dt, a, b, c), reps)
        except Exception as e:
            rec["error"] = repr(e)[:300]
        yield rec


def conv_candidates(reps):
    """The state-space layers' causal depthwise convolution at the two
    cells' shapes, bfloat16 x of 8,192 steps and 4 taps: the XLA form beside
    the kernels, forward and forward + backward towards x, the taps and the
    bias. x and the output are handed over time-minor, (B, C, T), as the
    layer lays them out, so neither form pays a transpose the step does
    not."""
    from alphafold2_tpu.ops import ssm
    from alphafold2_tpu.ops.pallas import conv

    length, taps = 8192, 4
    for channels in chip_smoke.CELL_CONVS:
        keys = jax.random.split(jax.random.key(channels), 4)
        x = jax.random.normal(keys[0], (1, channels, length), jnp.bfloat16)
        w = jax.random.uniform(keys[1], (channels, taps), jnp.float32, -.5, .5)
        b = jax.random.uniform(keys[2], (channels,), jnp.float32, -.5, .5)
        weight = jax.random.normal(keys[3], x.shape, jnp.bfloat16)
        for name, by_time in (("xla", ssm.causal_conv_xla),
                              ("pallas", conv.causal_conv)):
            def form(x, w, b, by_time=by_time):
                return jnp.swapaxes(by_time(jnp.swapaxes(x, 1, 2), w, b), 1, 2)

            def both(x, w, b, form=form):  # the output and its pull-back
                y, pull = jax.vjp(form, x, w, b)
                return y, pull(weight)

            rec = {"kernel": "causal_conv_8k", "impl": name,
                   "channels": channels}
            try:
                rec["fwd_ms"] = best_ms(jax.jit(form), (x, w, b), reps)
                rec["fwd_bwd_ms"] = best_ms(jax.jit(both), (x, w, b), reps)
            except Exception as e:
                rec["error"] = repr(e)[:300]
            yield rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--out", default="chiprun_out/ab_lm_kernels.jsonl")
    ap.add_argument("--kernels", default="all",
                    choices=("attention", "experts", "scan", "conv", "all"))
    ap.add_argument("--compile-only", action="store_true",
                    help="compile the attention candidates for one chip of a "
                         "described v5e (JAX_PLATFORMS=cpu, no TPU attached): "
                         "which sets Mosaic takes, and their temporaries")
    args = ap.parse_args(argv)
    sharding = None
    if args.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        jax.config.update("jax_enable_compilation_cache", False)
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        sharding = SingleDeviceSharding(topo.devices[0])
        args.kernels = "attention"
    elif jax.default_backend() != "tpu":
        print("needs a TPU (or --compile-only)", file=sys.stderr)
        return 1
    gens = []
    if args.kernels in ("attention", "all"):
        gens += [lambda: flash_candidates(args.reps, sharding),
                 lambda: splash_candidates(args.reps, sharding)]
    if args.kernels in ("experts", "all"):
        gens.append(lambda: gmm_candidates(args.reps))
    if args.kernels in ("scan", "all"):
        gens.append(lambda: scan_candidates(args.reps))
    if args.kernels in ("conv", "all"):
        gens.append(lambda: conv_candidates(args.reps))
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "a") as out:
        for gen in gens:
            for rec in gen():
                rec["device"] = ("described v5e, compile only" if sharding
                                 else jax.devices()[0].device_kind)
                line = json.dumps(rec)
                print(line, flush=True)
                out.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
