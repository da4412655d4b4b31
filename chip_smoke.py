#!/usr/bin/env python
"""First light on the attached TPU: drive the main path once, check it, say so.

    python chip_smoke.py                # one chip: train, serve, kernels, scan,
                                        # convolution, sparse core, selection
    python chip_smoke.py --four-chips   # four chips: the (dp, sp) mesh, and
                                        # the ring's blocks against references

Needs a TPU. Where ``jax.devices()[0].platform`` is anything else the script
says so and exits non-zero: there is no platform override and no CPU mode
(the CPU rehearsal of the same phase functions is tests/test_chip_smoke.py).
One process, the only one that touches JAX; nothing here starts another.

Default phases, on one chip, at the width of the flagship (bench.py):

1. train  — ``alphafold2_tpu.train.loop.train(cfg)``, what train_pre.py
   calls, for a few optimizer steps on synthetic data from a seed.
2. serve  — a ``ServeEngine`` behind ``AsyncServeFrontend`` with the
   dispatch pipeline on, answering requests over three buckets, twice.
3. kernels — the three in-repo Pallas kernels, and the stock flash kernel
   through ``ops/flash.py`` at the blocks it picks for the flagship's
   shapes, forward and gradient, against plain jnp references at float32 /
   highest matmul precision.
4. ssd_scan_8k — the chunked state-space scan (``ops/ssm.py``) in bfloat16
   at the hybrid language model's shape, output and five gradients against
   the float32 per-step recurrence; then ssd_scan_8k_one_group_c256, the
   same at the dense hybrid's (one group read by all 64 heads, chunks of
   256).
5. causal_conv_8k — the state-space layers' causal depthwise convolution
   (``ops/ssm.py``) on bfloat16 inputs at both state-space cells' channel
   counts, output and three gradients against the float32 XLA form.
6. sparse_core_16k — the learned-sparse-attention core (``ops/dsa.py``) at
   the fifth language model's shape, every query reading the 2,048 keys an
   indexer of random weights picks: output, log-sum-exp and three
   gradients in bfloat16 against a float32 form in blocks of queries.
7. select_16k — the same model's selection of 2,048 of 16,384 keys for a
   block of 512 queries (``ops/dsa.py`` ``select``): the packed selection
   through the Pallas threshold against the ``lax.top_k`` form, bit for
   bit, and both forms' times.

Every phase prints one JSON object on a line of its own. The LAST line of
stdout is ``{"ok": true, "device": {"platform", "kind", "count"}}`` and
carries nothing else; on any failure it is ``{"ok": false, ...}`` and the
exit code is 1.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time

# what the error of a kernel against its reference may be, relative to the
# reference's largest entry; set from the dtype before the first chip run
# (bfloat16 keeps 8 mantissa bits; float32 leaves room for a matmul that
# the MXU takes in bfloat16 passes on one side of the comparison)
KERNEL_TOL = {"bfloat16": 4e-2, "float32": 2e-2}
# one-chip and dp2 x sp2 losses: the same bfloat16 step, summed in another
# order across chips
MESH_LOSS_TOL = 5e-2

FLAGSHIP = dict(
    dim=256, heads=8, dim_head=64, depth=2, crop=256, msa_depth=16,
    msa_len=256, batch=1,
)
# about eight requests over three rungs of the default ladder
# (64, 96, 128, 192, 256), the top one included
SERVE_LENGTHS = (40, 64, 100, 128, 120, 200, 256, 230)

# What 16 GB forced, found by compiling for a described v5e before any chip
# run (tests/test_chip_compile.py keeps the train-step compiles). Widths are
# never cut; every phase prints its entry under "cut".
CUTS = {
    "serve": {
        "max_batch": "4 -> 2: the bucket-256 executable elongates to 768 "
        "tokens, and at batch 4 its program needs 22.6 GB of the 15.75 GB "
        "a v5e chip offers; at batch 2 it needs 11.95 GB",
    },
    "mesh": {
        "one_device_remat": "off -> on, one-device twin only: at global "
        "batch 2 its program needs 14.56 GB without remat and 7.94 GB with "
        "it; the dp2 x sp2 program (4.33 GB per device) runs as the "
        "flagship does, and remat changes no value",
    },
}
SERVE_MAX_BATCH = 2


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def device_record() -> dict:
    import jax

    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def peak_bytes() -> list:
    """``peak_bytes_in_use`` of every device (None where the backend keeps
    no allocator statistics, as the host CPU does)."""
    import jax

    return [
        (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.devices()
    ]


def program_bytes(compiled) -> int:
    """Arguments + outputs + temporaries of a compiled program, per device."""
    ma = compiled.memory_analysis()
    return int(ma.argument_size_in_bytes + ma.output_size_in_bytes
               + ma.temp_size_in_bytes)


# ------------------------------------------------------------------ train ---


def train_config(sizes: dict, *, steps: int, seed: int = 0, dp: int = 1,
                 sp: int = 1, bfloat16: bool = True):
    """The flagship train config of bench.py at ``sizes``; ``dp``/``sp``
    lay it over a (dp, sp) mesh with ring context parallelism."""
    from alphafold2_tpu.config import (
        Config, DataConfig, MeshConfig, ModelConfig, TrainConfig,
    )

    return Config(
        model=ModelConfig(
            dim=sizes["dim"], depth=sizes["depth"], heads=sizes["heads"],
            dim_head=sizes["dim_head"], max_seq_len=sizes["crop"] * 2,
            msa_tie_row_attn=True, bfloat16=bfloat16,
            context_parallel="ring" if sp > 1 else None,
        ),
        mesh=MeshConfig(data_parallel=dp, seq_parallel=sp),
        data=DataConfig(
            crop_len=sizes["crop"], msa_depth=sizes["msa_depth"],
            msa_len=sizes["msa_len"], batch_size=sizes["batch"],
            min_len_filter=sizes["crop"],  # full-length crops
        ),
        train=TrainConfig(
            gradient_accumulate_every=1, warmup_steps=2, num_steps=steps,
            seed=seed,
        ),
    )


def compile_train_step(cfg):
    """Lower and compile the step ``train(cfg)`` builds — same model, state,
    first batch, mesh and numerics mode — to time the compile and read the
    program. ``train()`` then finds it in the compile cache. Returns
    (compiled, seconds)."""
    import jax

    from alphafold2_tpu.data.pipeline import make_dataset
    from alphafold2_tpu.train.loop import (
        apply_features, build_model, device_put_batch, make_train_step,
        tiny_init_state,
    )

    dataset = make_dataset(cfg.data, seed=cfg.train.seed)
    sample = next(apply_features(iter(dataset), cfg))
    model = build_model(cfg)
    state = tiny_init_state(cfg, model, sample)
    mesh = None
    if cfg.mesh.data_parallel * cfg.mesh.seq_parallel > 1:
        from jax.sharding import NamedSharding, PartitionSpec

        from alphafold2_tpu.parallel.distributed import pod_mesh

        mesh = pod_mesh(cfg.mesh.data_parallel, cfg.mesh.seq_parallel)
        state = jax.device_put(state, NamedSharding(mesh, PartitionSpec()))
    step = make_train_step(model, mesh, numerics_mode="norms")
    t0 = time.perf_counter()
    compiled = step.lower(
        state, device_put_batch(sample, mesh),
        jax.random.key(cfg.train.seed + 1),
    ).compile()
    return compiled, time.perf_counter() - t0


def run_train(cfg, steps: int) -> dict:
    """``train(cfg)`` for ``steps`` steps; loss VALUES fetched each step, so
    a step's seconds end when the device has finished it."""
    from alphafold2_tpu.train.loop import train

    losses, skipped, stamps = [], [], []

    def on_step(i, state, metrics):
        losses.append(float(metrics["loss"]))
        skipped.append(int(metrics["skipped"]))
        stamps.append(time.perf_counter())

    t0 = time.perf_counter()
    train(cfg, num_steps=steps, callbacks=[on_step])
    return {
        "losses": losses,
        "skipped": skipped[-1] if skipped else None,
        # set-up, the (cached) compile and step 0
        "first_step_s": round(stamps[0] - t0, 3),
        "step_s": [round(b - a, 4) for a, b in zip(stamps, stamps[1:])],
    }


def check_losses(losses: list, skipped, steps: int) -> None:
    import math

    if len(losses) != steps:
        raise RuntimeError(f"{len(losses)} losses for {steps} steps")
    if not all(math.isfinite(x) for x in losses):
        raise RuntimeError(f"non-finite loss: {losses}")
    if skipped:
        raise RuntimeError(f"{skipped} step(s) skipped as non-finite")
    if any(a == b for a, b in zip(losses, losses[1:])):
        raise RuntimeError(f"loss did not change between steps: {losses}")


def phase_train(sizes: dict = FLAGSHIP, steps: int = 4, seed: int = 0,
                bfloat16: bool = True) -> dict:
    cfg = train_config(sizes, steps=steps, seed=seed, bfloat16=bfloat16)
    compiled, compile_s = compile_train_step(cfg)
    has_kernel = "tpu_custom_call" in compiled.as_text()
    step_bytes = program_bytes(compiled)
    del compiled
    run = run_train(cfg, steps)
    check_losses(run["losses"], run["skipped"], steps)
    return {
        "phase": "train", "sizes": sizes, "steps": steps, "seed": seed,
        "cut": None,
        "compile_s": round(compile_s, 2),
        "has_tpu_custom_call": has_kernel,
        "program_bytes": step_bytes,
        **run,
        "peak_bytes_in_use": peak_bytes()[0],
    }


# ------------------------------------------------------------------ serve ---


def serve_config(sizes: dict, buckets=None, seed: int = 0,
                 bfloat16: bool = True):
    """Model at the flagship width behind the default ServeConfig (ladder,
    mds_iters, pipeline depth 2) with ``max_batch`` as CUTS says;
    ``buckets`` only shrinks the ladder for the CPU rehearsal."""
    from alphafold2_tpu.config import (
        Config, DataConfig, ModelConfig, ServeConfig, TrainConfig,
    )

    serve = (
        ServeConfig(max_batch=SERVE_MAX_BATCH) if buckets is None
        else ServeConfig(buckets=tuple(buckets), mds_iters=10,
                         max_batch=SERVE_MAX_BATCH)
    )
    return Config(
        model=ModelConfig(
            dim=sizes["dim"], depth=sizes["depth"], heads=sizes["heads"],
            dim_head=sizes["dim_head"], max_seq_len=3 * max(serve.buckets),
            bfloat16=bfloat16,
        ),
        data=DataConfig(msa_depth=sizes["serve_msa_depth"]),
        train=TrainConfig(seed=seed),
        serve=serve,
    )


def _sequences(lengths, seed: int) -> list:
    import numpy as np

    rng = np.random.default_rng(seed)
    alphabet = "ACDEFGHIKLMNPQRSTVWY"
    return ["".join(rng.choice(list(alphabet), n)) for n in lengths]


def phase_serve(sizes: dict = {**FLAGSHIP, "serve_msa_depth": 5},
                lengths=SERVE_LENGTHS, buckets=None, seed: int = 0,
                bfloat16: bool = True, timeout_s: float = 900.0) -> dict:
    import numpy as np

    from alphafold2_tpu.serve import (
        AsyncServeFrontend, ServeEngine, ServeRequest,
    )

    cfg = serve_config(sizes, buckets, seed, bfloat16)
    engine = ServeEngine(cfg)
    if engine.pipeline is None:
        raise RuntimeError("dispatch pipeline is off")
    frontend = AsyncServeFrontend(engine)
    passes = []
    try:
        for n_pass in range(2):
            # other sequences and seeds on the second pass: same lengths,
            # but nothing the result cache could answer
            seqs = _sequences(lengths, seed + 1000 * n_pass)
            handles = [
                frontend.submit(ServeRequest(s, seed=seed + n_pass))
                for s in seqs
            ]
            results = [h.result(timeout=timeout_s) for h in handles]
            for s, r in zip(seqs, results):
                if r.status != "ok" or r.retried or r.cache_hit:
                    raise RuntimeError(
                        f"request len {len(s)}: status={r.status} "
                        f"retried={r.retried} cache_hit={r.cache_hit} "
                        f"error={r.error}"
                    )
                if r.atom14.shape != (len(s), 14, 3):
                    raise RuntimeError(
                        f"request len {len(s)}: coords {r.atom14.shape}"
                    )
                if not np.all(np.isfinite(r.atom14)):
                    raise RuntimeError(
                        f"request len {len(s)}: non-finite coordinates"
                    )
            passes.append({
                "latency_s": [round(r.latency_s, 3) for r in results],
                "buckets": [r.bucket for r in results],
                "compiles": len(engine.compile_records),
            })
    finally:
        frontend.close()
        engine.close()
    if passes[1]["compiles"] != passes[0]["compiles"]:
        raise RuntimeError(
            f"second pass compiled: {passes[0]['compiles']} -> "
            f"{passes[1]['compiles']} executables"
        )
    if len(set(passes[0]["buckets"])) < 3:
        raise RuntimeError(f"fewer than 3 buckets: {passes[0]['buckets']}")
    stats = engine.stats()
    if stats.get("serve.dispatch_errors") or stats.get("sched.retries"):
        raise RuntimeError(f"dispatch errors or retries: {stats}")
    return {
        "phase": "serve", "sizes": sizes, "lengths": list(lengths),
        "ladder": list(engine.buckets), "max_batch": engine.max_batch,
        "mds_iters": cfg.serve.mds_iters, "pipeline": engine.pipeline_desc,
        "cut": CUTS["serve"],
        "compile_s": {
            str(r["bucket"]): r["seconds"] for r in engine.compile_records
        },
        "passes": passes,
        "peak_bytes_in_use": peak_bytes()[0],
    }


# ---------------------------------------------------------------- kernels ---


def _ref_attention(q, k, v, q_mask, kv_mask, scale):
    """Plain softmax attention, (B, H, N, D) layout, float32."""
    import jax
    import jax.numpy as jnp

    q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    dots = jnp.einsum("bhid,bhjd->bhij", q, k) * scale
    if kv_mask is not None:
        dots = jnp.where(kv_mask[:, None, None, :], dots, -1e30)
    out = jnp.einsum("bhij,bhjd->bhid", jax.nn.softmax(dots, axis=-1), v)
    if q_mask is not None:  # the kernels zero masked queries
        out = jnp.where(q_mask[:, None, :, None], out, 0)
    return out


def _ref_attention_by_head(q, k, v, kv_mask, scale):
    """``_ref_attention`` one head at a time, recomputed in the backward:
    the flagship cross-attention's float32 logits are 1 GB a head."""
    import jax
    import jax.numpy as jnp

    def one_head(qkv):
        q1, k1, v1 = (t[:, None] for t in qkv)
        return _ref_attention(q1, k1, v1, None, kv_mask, scale)[:, 0]

    heads_first = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v))
    return jnp.moveaxis(
        jax.lax.map(jax.checkpoint(one_head), heads_first), 0, 1)


def _ref_causal_by_head(q, k, v, window=None):
    """Causal softmax attention in float32, one head at a time, the scale
    on q already: q/k heads may be wider than v heads (latent attention's
    192 against 128), there may be fewer key/value heads than query heads
    (query head h reads h // (H / G): repeated to H here), and a query may
    see only the last ``window`` keys."""
    import jax
    import jax.numpy as jnp

    n = q.shape[2]
    ahead = jnp.arange(n)[:, None] - jnp.arange(n)[None, :]
    keep = ahead >= 0 if window is None else (ahead >= 0) & (ahead < window)
    k, v = (jnp.repeat(t, q.shape[1] // t.shape[1], axis=1) for t in (k, v))

    def one_head(qkv):
        q1, k1, v1 = (t.astype(jnp.float32) for t in qkv)
        dots = jnp.einsum("bid,bjd->bij", q1, k1)
        probs = jax.nn.softmax(jnp.where(keep, dots, -1e30), axis=-1)
        return jnp.einsum("bij,bjd->bid", probs, v1)

    heads_first = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v))
    return jnp.moveaxis(
        jax.lax.map(jax.checkpoint(one_head), heads_first), 0, 1)


def _group_sizes(rows: int, held: int, seed: int = 0):
    """Uneven group sizes over ``held`` experts that fill an eighth of the
    rows (what 16 of 128 experts are sent on average)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    return rng.multinomial(rows // 8, rng.dirichlet(np.full(held, 2.0)))


def _ref_expert_ffn(sizes):
    """The expert SwiGLU an expert at a time in float32: rows of group g
    through expert g's matrices, rows past the groups zero."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ends = np.cumsum(sizes)

    def ref(rows, w_in, w_down):
        out = jnp.zeros((rows.shape[0], w_down.shape[-1]), jnp.float32)
        for g, (start, end) in enumerate(zip(ends - sizes, ends)):
            h = rows[start:end].astype(jnp.float32) @ w_in[g].astype(
                jnp.float32)
            gate, up = jnp.split(h, 2, axis=-1)
            out = out.at[start:end].set(
                (jax.nn.silu(gate) * up) @ w_down[g].astype(jnp.float32))
        return out

    return ref


def _ref_tied(q, k, v, q_mask, kv_mask, scale):
    """Tied-row attention, (B, R, N, H, D) layout: one attention matrix per
    (batch, head), logits summed over the R rows and scaled by R**-0.5."""
    import jax
    import jax.numpy as jnp

    q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    dots = jnp.einsum("brihd,brjhd->bhij", q, k) * scale * q.shape[1] ** -0.5
    if kv_mask is not None:
        dots = jnp.where(kv_mask[:, None, None, :], dots, -1e30)
    out = jnp.einsum("bhij,brjhd->brihd", jax.nn.softmax(dots, axis=-1), v)
    if q_mask is not None:
        out = jnp.where(q_mask[:, None, :, None, None], out, 0)
    return out


def _tail_mask(b: int, n: int, pad: int):
    import jax.numpy as jnp

    return jnp.ones((b, n), bool).at[:, n - pad:].set(False) if pad else None


def kernel_cases(small: bool = False) -> list:
    """(name, kernel fn, reference fn, input shapes, dtype) for the three
    in-repo kernels at the shapes the flagship reaches, plus one masked
    odd-length case each, and the stock flash kernel at every shape class
    the flagship sends it. ``small`` is the CPU rehearsal's size (no stock
    flash there: off the TPU its wrapper declines)."""
    import jax
    import jax.numpy as jnp

    from alphafold2_tpu.ops.pallas.axial import fused_attention
    from alphafold2_tpu.ops.pallas.tied_row import tied_row_attention
    from alphafold2_tpu.ops.sparse import (
        BlockSparseConfig, block_sparse_attention,
        block_sparse_attention_pallas,
    )

    cases = []

    def axial(name, shape, dtype, pad):
        b, h, n, d = shape
        m = _tail_mask(b, n, pad)
        cases.append((
            name,
            lambda q, k, v: fused_attention(
                q, k, v, q_mask=m, kv_mask=m, sm_scale=d ** -0.5),
            lambda q, k, v: _ref_attention(q, k, v, m, m, d ** -0.5),
            (shape,) * 3, dtype,
        ))

    def tied(name, shape, dtype, pad):
        b, r, n, h, d = shape
        m = _tail_mask(b, n, pad)

        def zeroed(t):  # padded positions abstain: the caller zeroes them
            return t if m is None else jnp.where(
                m[:, None, :, None, None], t, 0)

        cases.append((
            name,
            lambda q, k, v: tied_row_attention(
                zeroed(q), zeroed(k), zeroed(v), q_mask=m, kv_mask=m,
                sm_scale=d ** -0.5),
            lambda q, k, v: _ref_tied(
                zeroed(q), zeroed(k), zeroed(v), m, m, d ** -0.5),
            (shape,) * 3, dtype,
        ))

    def sparse(name, n, block, pad):
        shape = (1, 4, n, 64 if not small else 16)
        layout = BlockSparseConfig(
            block_size=block, num_local_blocks=4, num_global_blocks=1,
            num_random_blocks=None,
        ).layout(n)
        m = _tail_mask(1, n, pad)
        cases.append((
            name,
            lambda q, k, v: block_sparse_attention_pallas(
                q, k, v, layout, block, mask=m),
            # the repo's gather-based jnp implementation of the same layout
            lambda q, k, v: block_sparse_attention(
                q, k, v, layout, block, mask=m),
            (shape,) * 3, "float32",
        ))

    def flash(name, q_shape, kv_shape, pad):
        from alphafold2_tpu.ops.flash import flash_attention

        d = q_shape[-1]
        m = _tail_mask(kv_shape[0], kv_shape[2], pad)
        cases.append((
            name,
            lambda q, k, v: flash_attention(
                q, k, v, kv_mask=m, sm_scale=d ** -0.5),
            lambda q, k, v: _ref_attention_by_head(q, k, v, m, d ** -0.5),
            (q_shape, kv_shape, kv_shape), "bfloat16",
        ))

    def ring(name, q_shape, kv_shape, pad):
        """Ring context parallelism over sp = 2 (two of the devices, batch
        1): each device's half of the keys visits the other. On the chip the
        blocks are the stock flash kernel's, elsewhere jnp."""
        from alphafold2_tpu.parallel.seq_parallel import (
            sequence_parallel_attention,
        )
        from alphafold2_tpu.parallel.sharding import make_mesh

        mesh = make_mesh(1, 2, devices=jax.devices()[:2])
        d = q_shape[-1]
        m = _tail_mask(kv_shape[0], kv_shape[2], pad)
        cases.append((
            name,
            lambda q, k, v: sequence_parallel_attention(
                q, k, v, mask=m, mesh=mesh, impl="ring"),
            lambda q, k, v: _ref_attention_by_head(q, k, v, m, d ** -0.5),
            (q_shape, kv_shape, kv_shape), "bfloat16",
        ))

    def causal(name, b, h, n, qk, dv, groups=None, window=None):
        """The language models' causal core. It takes q with the softmax
        scale on it (the models' query projections put it there): both sides
        get the same scaled, rounded q. ``groups``: key/value heads where
        they are fewer than the ``h`` query heads; ``window``: the keys a
        query sees (None: all before it). A sixth entry: the core under
        ``jax.checkpoint`` as the models' layers are, keeping what the core
        names (its recomputation runs no forward kernel) and keeping nothing
        (it runs it again): the same kernels on the same operands, so the
        two are to agree outright."""
        from alphafold2_tpu.ops.mla import CORE_RESIDUALS, causal_core

        g = groups or h

        def scaled(q):
            return (q * qk ** -0.5).astype(q.dtype)

        def core(q, k, v):
            return causal_core(scaled(q), k, v, window=window)

        cases.append((
            name, core,
            lambda q, k, v: _ref_causal_by_head(scaled(q), k, v, window),
            ((b, h, n, qk), (b, g, n, qk), (b, g, n, dv)), "bfloat16",
            (jax.checkpoint(
                core, policy=jax.checkpoint_policies.save_only_these_names(
                    CORE_RESIDUALS)), jax.checkpoint(core)),
        ))

    def grouped(name, rows, d, f, held):
        """The expert SwiGLU over sorted rows (``ops/moe.py``); its three
        arguments stand where q, k, v do: rows, gate/up matrices stacked
        (held, d, 2f), down matrices (held, f, d)."""
        from alphafold2_tpu.ops import moe

        sizes = _group_sizes(rows, held)
        live = jnp.arange(rows) < int(sizes.sum())

        def kernel(x, w_in, w_down):
            w_gate, w_up = jnp.split(w_in * d ** -0.5, 2, axis=-1)
            y = moe.expert_ffn(x, jnp.asarray(sizes, jnp.int32), w_gate, w_up,
                               w_down * f ** -0.5, x.dtype)
            return jnp.where(live[:, None], y, 0)

        ref = _ref_expert_ffn(sizes)
        cases.append((
            name, kernel,
            lambda x, w_in, w_down: ref(x, w_in * d ** -0.5,
                                        w_down * f ** -0.5),
            ((rows, d), (held, d, 2 * f), (held, f, d)), "bfloat16",
        ))

    if small:
        causal("mla_causal_core_small", 1, 2, 160, 24, 16)
        causal("swa_core_small_global", 1, 6, 160, 16, 16, groups=2)
        causal("swa_core_small_window", 1, 6, 160, 16, 16, groups=2, window=50)
        causal("gqa_core_small_heads_of_64", 1, 8, 160, 64, 64, groups=2)
        grouped("moe_grouped_matmul_small", 256, 32, 16, 4)
        axial("fused_axial_f32", (2, 2, 32, 16), "float32", 0)
        axial("fused_axial_masked_odd", (1, 2, 40, 16), "float32", 7)
        tied("tied_row_f32", (1, 3, 32, 2, 16), "float32", 0)
        tied("tied_row_masked_odd", (1, 3, 40, 2, 16), "float32", 5)
        sparse("block_sparse_n64", 64, 16, 0)
        sparse("block_sparse_masked", 64, 16, 5)
        ring("ring_flash_small", (1, 2, 64, 16), (1, 2, 32, 16), 0)
        ring("ring_flash_small_masked", (1, 2, 32, 16), (1, 2, 64, 16), 5)
        return cases
    for dt in ("bfloat16", "float32"):
        axial(f"fused_axial_{dt}", (256, 8, 256, 64), dt, 0)
        tied(f"tied_row_{dt}", (1, 16, 256, 8, 64), dt, 0)
    axial("fused_axial_masked_odd", (4, 8, 200, 64), "float32", 17)
    tied("tied_row_masked_odd", (1, 5, 200, 8, 64), "float32", 9)
    sparse("block_sparse_n512", 512, 128, 0)
    sparse("block_sparse_n1024_masked", 1024, 128, 17)
    pair, msa = (1, 8, 256 * 256, 64), (1, 8, 16 * 256, 64)
    flash("stock_flash_pair_axial", (256, 8, 256, 64), (256, 8, 256, 64), 0)
    flash("stock_flash_pair_from_msa", pair, msa, 0)
    flash("stock_flash_msa_from_pair", msa, pair, 0)
    # 4,096 keys pooled by 3: 1,366, padded to 1,408 = 11 x 128, tail masked
    flash("stock_flash_compressed_masked_odd", pair, (1, 8, 1366, 64), 9)
    # 2,048 keys: the longest axis the block rule takes whole, against many
    # query blocks and against one (MSA 4 x 128 queries)
    flash("stock_flash_keys_2048_masked", pair, (1, 8, 2048, 64), 9)
    flash("stock_flash_one_q_block_keys_2048", (1, 8, 512, 64),
          (1, 8, 2048, 64), 0)
    # the language-model cell's two kernels at its shapes: causal, q/k heads
    # of 192 against v heads of 128 (the splash kernel, nothing padded), and
    # the grouped product over 16 held experts, an eighth of the rows live
    causal("mla_causal_core_8k", 2, 32, 8192, 192, 128)
    grouped("moe_grouped_matmul_16_experts", 6 * 16384, 2048, 768, 16)
    # the second language model's attention at its cell's shape: 28 query
    # heads over 4 key/value heads of 128 at 16,384 positions, a global
    # layer's causal mask and a window layer's 4,096 keys (the two-kernel
    # backward: the fused one's partial dq would be 1.9 GB)
    causal("swa_core_16k_global", 1, 28, 16384, 128, 128, groups=4)
    causal("swa_core_16k_window", 1, 28, 16384, 128, 128, groups=4,
           window=4096)
    # the dense hybrid's attention layer at its cell's shape: 32 query heads
    # over 8 key/value heads of 64, half the width of any other cell's, at
    # 8,192 positions (the fused backward)
    causal("gqa_core_8k_heads_of_64", 1, 32, 8192, 64, 64, groups=8)
    # the mesh cell's cross-attentions: a chip's blocks are 32,768 x 2,048
    # and 2,048 x 32,768 (only where there is a second chip for the ring)
    if len(jax.devices()) >= 2:
        ring("ring_flash_pair_from_msa_masked", pair, msa, 9)
        ring("ring_flash_msa_from_pair", msa, pair, 0)
    return cases


def phase_kernels(small: bool = False, seed: int = 0, only: str = "") -> dict:
    """Forward and gradient of every case (whose name holds ``only``) against
    its reference. The reference runs in float32 at the highest matmul
    precision on the same (dtype-rounded) inputs; the error is the largest
    absolute difference over the reference's largest entry. The causal
    core's cases read one more, ``kept_vs_recomputed``: the largest such
    error, over output and gradients, between the core under a
    ``jax.checkpoint`` that keeps its named results and under one that
    recomputes them."""
    import jax
    import jax.numpy as jnp

    def rel_err(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-6))

    def fwd_and_grad(fn):
        def loss(q, k, v):
            out = fn(q, k, v)
            return jnp.sum(jnp.sin(out.astype(jnp.float32))), out

        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                          has_aux=True))

    results, failed = [], []
    for i, (name, kernel, ref, shapes, dtype, *under_remat) in enumerate(
            kernel_cases(small)):
        if only not in name:
            continue
        keys = jax.random.split(jax.random.key(seed + i), len(shapes))
        args = [
            jax.random.normal(kk, s, jnp.float32).astype(dtype)
            for kk, s in zip(keys, shapes)
        ]
        (_, out), grads = fwd_and_grad(kernel)(*args)
        with jax.default_matmul_precision("highest"):
            (_, out_r), grads_r = fwd_and_grad(ref)(*args)
        errs = {
            "fwd": rel_err(out, out_r),
            **{f"d{n}": rel_err(g, gr)
               for n, g, gr in zip("qkv", grads, grads_r)},
        }
        for kept, recomputed in under_remat:
            ((_, out), grads), ((_, out_r), grads_r) = (
                fwd_and_grad(fn)(*args) for fn in (kept, recomputed))
            errs["kept_vs_recomputed"] = max(
                rel_err(a, b)
                for a, b in zip((out, *grads), (out_r, *grads_r)))
        tol = KERNEL_TOL[dtype]
        ok = all(e == e and e <= tol for e in errs.values())  # e==e: no NaN
        if not ok:
            failed.append(name)
        results.append({
            "case": name, "dtype": dtype, "shape": list(shapes[0]),
            "tol": tol, "ok": ok,
            **{k: float(f"{v:.3g}") for k, v in errs.items()},
        })
    if failed:
        raise RuntimeError(
            f"kernels disagree with their references: {failed}: "
            + json.dumps([r for r in results if not r["ok"]])
        )
    return {"phase": "kernels", "cases": results}


# ---------------------------------------------------- the chunked scan ---

# Readings on the chip (my chip run, PR 34; PERF.md section 6): output 0.0054,
# gradients towards x / B / C / dt / A 0.0040 / 0.0030 / 0.0038 / 0.0029 /
# 0.0036 of the reference's largest entry. The products take bfloat16
# operands (2**-8 each) and a step's output sums a chunk's 128 terms and the
# carried state's; the reference is the float32 recurrence. 3.7 times the
# largest reading; a chunk's state lost or a decay misplaced reads 0.1 to 1.
SSD_SCAN_TOL = 2e-2


# (heads, width, groups, state rows, steps, chunk) of the cells' scans: the
# hybrid expert model's, and the dense hybrid's (every head reads the one
# group; chunks of 256: my chip run, PR 38, is in PERF.md section 6)
CELL_SCANS = {(64, 64, 8, 128, 8192, 128): "ssd_scan_8k",
              (64, 64, 1, 128, 8192, 256): "ssd_scan_8k_one_group_c256"}


def _scan_inputs(heads, width, groups, n, length, seed=0):
    """(x, B, C, dt, A, a weight of x's shape) of one sequence under the
    hybrid model's published initialisation (a uniform on [1, 16], dt =
    softplus(N(0, 1) + softplus^-1 of a log-uniform [0.001, 0.1])); x, B and
    C are float32 holding bfloat16-rounded values."""
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.key(seed), 7)
    x = jax.random.normal(keys[0], (1, length, heads, width))
    b = jax.random.normal(keys[1], (1, length, groups, n))
    c = jax.random.normal(keys[2], (1, length, groups, n))
    a = -jax.random.uniform(keys[3], (heads,), jnp.float32, 1.0, 16.0)
    step = jnp.exp(jax.random.uniform(
        keys[4], (heads,), jnp.float32, math.log(1e-3), math.log(0.1)))
    dt = jax.nn.softplus(
        jax.random.normal(keys[5], (1, length, heads))
        + step + jnp.log(-jnp.expm1(-step)))
    x, b, c = (t.astype(jnp.bfloat16).astype(jnp.float32) for t in (x, b, c))
    return x, b, c, dt, a, jax.random.normal(keys[6], x.shape)


def phase_ssd_scan(heads=64, width=64, groups=8, n=128, length=8192,
                   chunk=128, seed=0, tol=SSD_SCAN_TOL) -> dict:
    """``ops/ssm.py`` ``ssd_scan`` in bfloat16 at the hybrid model's cell's
    shape (64 heads of 64 over 8 groups of 128 state rows, 8,192 steps,
    chunks of 128; on a TPU that is the Pallas kernels of
    ``ops/pallas/ssd.py``, and the record's ``implementation`` says which
    form ran: a fall-back to the XLA form there fails the phase; ``groups=1,
    chunk=256`` is the dense hybrid's cell, the other of ``CELL_SCANS``)
    under the
    published initialisation (``_scan_inputs``): the output and its
    gradients towards x, B, C, dt and A against the float32 per-step
    recurrence (a ``lax.scan`` over the steps, walked in segments under
    ``jax.checkpoint``), each error the largest absolute difference over the
    reference's largest entry."""
    import jax
    import jax.numpy as jnp

    from alphafold2_tpu.ops import ssm

    x, b, c, dt, a, weight = _scan_inputs(
        heads, width, groups, n, length, seed)

    def recurrence(x, b, c, dt, a):
        rep = heads // groups
        b, c = (jnp.repeat(t, rep, axis=2) for t in (b, c))

        def one(h, at):
            x_t, dt_t, b_t, c_t = at
            h = jnp.exp(dt_t * a)[..., None, None] * h \
                + (dt_t[..., None] * b_t)[..., :, None] * x_t[..., None, :]
            return h, jnp.sum(c_t[..., :, None] * h, axis=-2)

        walk = jax.checkpoint(lambda h, seg: jax.lax.scan(one, h, seg))
        seg = chunk if length % chunk == 0 else length

        def segments(t):
            t = jnp.moveaxis(t, 1, 0)
            return t.reshape(length // seg, seg, *t.shape[1:])

        _, y = jax.lax.scan(
            walk, jnp.zeros((1, heads, n, width)),
            tuple(segments(t) for t in (x, dt, b, c)))
        return jnp.moveaxis(y.reshape(length, 1, heads, width), 0, 1)

    def chunked(x, b, c, dt, a):
        return ssm.ssd_scan(x, dt, a, b, c, chunk, jnp.bfloat16)[0]

    def both(fn):
        return jax.jit(jax.value_and_grad(
            lambda *args: (jnp.sum(fn(*args) * weight), fn(*args)),
            argnums=(0, 1, 2, 3, 4), has_aux=True))

    def rel_err(got, want):
        return float(jnp.max(jnp.abs(got - want))
                     / (jnp.max(jnp.abs(want)) + 1e-30))

    in_kernel = ssm.scan_kernel_takes(x.shape, b.shape, chunk)
    (_, out), grads = both(chunked)(x, b, c, dt, a)
    with jax.default_matmul_precision("highest"):
        (_, out_r), grads_r = both(recurrence)(x, b, c, dt, a)
    errs = {"fwd": rel_err(out, out_r),
            **{f"d{name}": rel_err(g, gr) for name, g, gr in zip(
                ("x", "B", "C", "dt", "A"), grads, grads_r)}}
    ok = all(e == e and e <= tol for e in errs.values())
    at_cell = CELL_SCANS.get((heads, width, groups, n, length, chunk))
    record = {"phase": at_cell or "ssd_scan",
              "shape": [1, length, heads, width], "groups": groups,
              "state": n, "chunk": chunk, "tol": tol, "ok": ok,
              "implementation": "pallas" if in_kernel else "xla",
              **{k: float(f"{v:.3g}") for k, v in errs.items()}}
    if jax.default_backend() == "tpu" and at_cell and not in_kernel:
        raise RuntimeError(
            "the scan fell back to the XLA form at the cell's shape on a "
            "TPU: " + json.dumps(record))
    if not ok:
        raise RuntimeError(
            "the chunked scan disagrees with the recurrence: "
            + json.dumps(record))
    return record


# ------------------------------------------------ the causal convolution ---

# The output is cast to bfloat16 (2**-9 of an entry) and dx likewise; dw and
# db sum 8,192 steps of float32 products of a bfloat16-rounded cotangent.
# Set before the first chip run at five times bfloat16's rounding of the
# largest entry. On the chip the kernels read 0.0013-0.0041 (PERF.md section
# 6); on the CPU the output with its taps reversed reads 1.01 against the
# reference, shifted one step further 1.07, without its bias 0.17.
CONV_TOL = 1e-2

# the conv channels (x, B and C) of the two state-space cells at 8,192 steps
# and 4 taps: the hybrid expert model's and the dense hybrid's
CELL_CONVS = (6144, 4352)


def phase_causal_conv(channels=CELL_CONVS, length=8192, taps=4, seed=0,
                      tol=CONV_TOL) -> dict:
    """``ops/ssm.py`` ``causal_conv`` on bfloat16 inputs at the state-space
    cells' shapes (on a TPU the Pallas kernels of ``ops/pallas/conv.py``; a
    fall-back to the XLA form there fails the phase) under the published
    initialisation (taps and bias uniform on +-1/sqrt(taps)): the output and
    its gradients towards x, the taps and the bias against
    ``causal_conv_xla`` in float32 on the same bfloat16-rounded inputs, each
    error the largest absolute difference over the reference's largest
    entry. One case a channel count."""
    import jax
    import jax.numpy as jnp

    from alphafold2_tpu.ops import ssm

    def both(fn, weight):
        return jax.jit(jax.value_and_grad(
            lambda *args: (jnp.sum(fn(*args).astype(jnp.float32) * weight),
                           fn(*args)),
            argnums=(0, 1, 2), has_aux=True))

    def rel_err(got, want):
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        return float(jnp.max(jnp.abs(got - want))
                     / (jnp.max(jnp.abs(want)) + 1e-30))

    cases, failed = [], []
    for i, width in enumerate(channels):
        keys = jax.random.split(jax.random.key(seed + i), 4)
        bound = taps ** -0.5
        x = jax.random.normal(keys[0], (1, length, width), jnp.bfloat16)
        w = jax.random.uniform(keys[1], (width, taps), jnp.float32,
                               -bound, bound)
        b = jax.random.uniform(keys[2], (width,), jnp.float32, -bound, bound)
        weight = jax.random.normal(keys[3], x.shape)
        (_, out), grads = both(ssm.causal_conv, weight)(x, w, b)
        (_, out_r), grads_r = both(ssm.causal_conv_xla, weight)(
            x.astype(jnp.float32), w, b)
        errs = {"fwd": rel_err(out, out_r),
                **{f"d{name}": rel_err(g, gr) for name, g, gr in zip(
                    ("x", "w", "b"), grads, grads_r)}}
        in_kernel = ssm.conv_kernel_takes(x.shape, taps)
        ok = all(e == e and e <= tol for e in errs.values())
        case = {"shape": [1, length, width], "taps": taps, "ok": ok,
                "implementation": "pallas" if in_kernel else "xla",
                **{k: float(f"{v:.3g}") for k, v in errs.items()}}
        cases.append(case)
        if not ok or (jax.default_backend() == "tpu" and not in_kernel):
            failed.append(case)
    record = {"phase": "causal_conv_8k" if length == 8192 else "causal_conv",
              "tol": tol, "cases": cases}
    if failed:
        raise RuntimeError(
            "the convolution disagrees with its float32 reference or fell "
            "back to the XLA form on a TPU: " + json.dumps(record))
    return record


SPARSE_CORE_TOL = 2e-2


def phase_sparse_core(heads=32, groups=4, width=128, length=16384, topk=2048,
                      seed=0, tol=SPARSE_CORE_TOL) -> dict:
    """``ops/dsa.py`` ``sparse_core`` on bfloat16 q, k and v at the sixth
    language model's shape (on a TPU the Pallas kernels of
    ``ops/pallas/dsa_core.py``; a fall-back to the dense form there fails
    the phase), each query reading the ``topk`` keys that ``selection``
    picks from an indexer of random weights: the output, the log-sum-exp
    and the gradients towards q, k and v against the same mathematics in
    float32 at the highest matmul precision, 256 queries at a time (a
    whole 16,384 x 16,384 float32 score matrix a head would not fit), each
    error the largest absolute difference over the reference's largest
    entry."""
    import jax
    import jax.numpy as jnp

    from alphafold2_tpu.ops import dsa

    keys = jax.random.split(jax.random.key(seed), 8)
    q = (jax.random.normal(keys[0], (1, heads, length, width))
         * width ** -0.5).astype(jnp.bfloat16)
    k, v = (jax.random.normal(kk, (1, groups, length, width)).astype(
        jnp.bfloat16) for kk in keys[1:3])
    weight = jax.random.normal(keys[3], q.shape)
    packed = jax.jit(lambda a, b, c: dsa.selection(a, b, c, topk, 512)[0])(
        jax.random.normal(keys[4], (1, length, 16, 64), jnp.bfloat16),
        jax.random.normal(keys[5], (1, length, 64), jnp.bfloat16),
        jax.random.normal(keys[6], (1, length, 16)))

    def blocked(q, k, v):
        """The core in float32, a block of 256 queries at a time."""
        rep = heads // groups
        k32, v32 = (jnp.repeat(t.astype(jnp.float32), rep, 1) for t in (k, v))

        @jax.checkpoint
        def one(args):
            qb, words = args
            keep = dsa.unpack(words)[:, None]
            logits = jnp.einsum("bhqd,bhkd->bhqk", qb.astype(jnp.float32),
                                k32, precision="highest")
            logits = jnp.where(keep, logits, -jnp.inf)
            lse = jax.nn.logsumexp(logits, axis=-1)
            out = jnp.einsum("bhqk,bhkd->bhqd", jnp.exp(logits - lse[..., None]),
                             v32, precision="highest")
            return out, lse

        qs = q.reshape(1, heads, length // 256, 256, width).transpose(
            2, 0, 1, 3, 4)
        words = packed.reshape(1, length // 256, 8, length).transpose(
            1, 0, 2, 3)
        out, lse = jax.lax.map(one, (qs, words))
        return (out.transpose(1, 2, 0, 3, 4).reshape(q.shape),
                lse.transpose(1, 2, 0, 3).reshape(1, heads, length))

    def kernel(q, k, v):
        out, lse, _ = dsa.sparse_core(q, k, v, packed)
        return out, lse

    def both(fn):
        def weighted(*a):
            out, lse = fn(*a)
            return jnp.sum(out.astype(jnp.float32) * weight), (out, lse)

        return jax.jit(jax.value_and_grad(weighted, argnums=(0, 1, 2),
                                          has_aux=True))

    def rel_err(got, want):
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        return float(jnp.max(jnp.abs(got - want))
                     / (jnp.max(jnp.abs(want)) + 1e-30))

    (_, (out, lse)), grads = both(kernel)(q, k, v)
    (_, (out_r, lse_r)), grads_r = both(blocked)(q, k, v)
    errs = {"fwd": rel_err(out, out_r), "lse": rel_err(lse, lse_r),
            **{f"d{name}": rel_err(g, gr) for name, g, gr in zip(
                ("q", "k", "v"), grads, grads_r)}}
    in_kernel = dsa.core_kernel_takes(length, heads, groups)
    ok = all(e == e and e <= tol for e in errs.values())
    record = {"phase": "sparse_core_16k" if length == 16384
              else "sparse_core", "shape": [1, heads, length, width],
              "groups": groups, "topk": topk, "tol": tol, "ok": ok,
              "implementation": "pallas" if in_kernel else "xla",
              "tiles_visited_pct": float(dsa.tiles_visited_pct(packed)),
              **{k_: float(f"{v_:.3g}") for k_, v_ in errs.items()}}
    if not ok or (jax.default_backend() == "tpu" and not in_kernel):
        raise RuntimeError(
            "the sparse core disagrees with its float32 reference or fell "
            "back to the dense form on a TPU: " + json.dumps(record))
    return record


def phase_select(length=16384, topk=2048, chunk=512, heads=16, width=64,
                 seed=0, reps=5) -> dict:
    """``ops/dsa.py`` ``select`` at the fifth language model's block: the
    scores of ``chunk`` queries against ``length`` keys from an indexer of
    random weights (``heads`` heads of ``width``), for the block whose rows
    straddle ``topk`` causal keys (the first half of its rows keep every
    causal key) and for the last block. The packed selection through the
    Pallas threshold (``ops/pallas/dsa_select.py``; a fall-back to
    ``lax.top_k`` on a TPU fails the phase) must equal the ``lax.top_k``
    form's bit for bit; both forms' ms a block, best of ``reps``, on the
    host's clock."""
    import jax
    import jax.numpy as jnp

    from alphafold2_tpu.ops import dsa

    keys = jax.random.split(jax.random.key(seed), 3)
    q_i = jax.random.normal(keys[0], (1, chunk, heads, width))
    k_i = jax.random.normal(keys[1], (1, length, width))
    w = jax.random.normal(keys[2], (1, chunk, heads)) * (heads * width) ** -.5
    starts = (max(topk - chunk // 2, 0), length - chunk)
    scores = [jax.jit(dsa.index_scores, static_argnums=3)(q_i, k_i, w, start)
              for start in starts]
    in_kernel = dsa.select_kernel_takes(scores[0].shape)

    def best_ms(fn, x):
        jax.block_until_ready(fn(x))
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(x))
            times.append(time.perf_counter() - t0)
        return 1e3 * min(times)

    kernel_form = jax.jit(lambda s: dsa.pack(dsa.select(s, topk)))
    packed = [kernel_form(s) for s in scores]
    kernel_ms = best_ms(kernel_form, scores[-1])
    takes = dsa.select_kernel_takes
    dsa.select_kernel_takes = lambda shape: False  # the lax.top_k form
    try:
        top_k_form = jax.jit(lambda s: dsa.pack(dsa.select(s, topk)))
        want = [top_k_form(s) for s in scores]
        top_k_ms = best_ms(top_k_form, scores[-1])
    finally:
        dsa.select_kernel_takes = takes
    equal = all(bool(jnp.array_equal(a, b)) for a, b in zip(packed, want))
    record = {"phase": "select_16k" if length == 16384 else "select",
              "shape": [1, chunk, length], "topk": topk,
              "block_starts": list(starts), "equal": equal,
              "implementation": "pallas" if in_kernel else "top_k",
              "keys_selected": [int(jnp.sum(jax.lax.population_count(p)))
                                for p in packed],
              "ms": {"pallas" if in_kernel else "top_k": round(kernel_ms, 3),
                     "top_k": round(top_k_ms, 3)}}
    if not equal or (jax.default_backend() == "tpu" and not in_kernel):
        raise RuntimeError(
            "the selection through the Pallas threshold differs from the "
            "lax.top_k form or fell back to it on a TPU: "
            + json.dumps(record))
    return record


# ------------------------------------------------------------- four chips ---


def phase_mesh(sizes: dict = {**FLAGSHIP, "batch": 2}, steps: int = 3,
               seed: int = 0, bfloat16: bool = True,
               tol: float = MESH_LOSS_TOL) -> dict:
    """The train step on a dp2 x sp2 mesh (ring context parallelism, global
    batch 2) through ``train(cfg)``, against the same steps from the same
    seed on one device."""
    import jax

    if len(jax.devices()) < 4:
        raise RuntimeError(f"need 4 devices, have {len(jax.devices())}")
    cfg_mesh = train_config(sizes, steps=steps, seed=seed, dp=2, sp=2,
                            bfloat16=bfloat16)
    cfg_one = train_config(sizes, steps=steps, seed=seed,
                           bfloat16=bfloat16)
    cfg_one.model.remat = True  # CUTS["mesh"]

    compiled, compile_s = compile_train_step(cfg_mesh)
    text = compiled.as_text()
    ring_kernels = sum(
        "tpu_custom_call" in line and "/ring_block/" in line
        for line in text.splitlines())
    collectives = {
        name: text.count(f" {name}(") + text.count(f" {name}-start(")
        for name in ("all-reduce", "collective-permute", "all-gather",
                     "all-to-all")
    }
    per_device_program_bytes = program_bytes(compiled)
    argument_bytes = int(compiled.memory_analysis().argument_size_in_bytes)
    del compiled, text
    for need in ("all-reduce", "collective-permute"):
        if not collectives[need]:
            raise RuntimeError(f"no {need} in the mesh program: {collectives}")
    # off the TPU the ring's blocks are jnp by design; on it, a ring that
    # fell back to them would write every block's logits to HBM in silence
    if jax.devices()[0].platform == "tpu" and not ring_kernels:
        raise RuntimeError(
            "no Mosaic kernel under a ring_block scope in the mesh program: "
            "the ring's cross-attention blocks did not take the flash kernel")

    mesh_run = run_train(cfg_mesh, steps)
    check_losses(mesh_run["losses"], mesh_run["skipped"], steps)
    in_use = [
        (d.memory_stats() or {}).get("bytes_in_use") for d in jax.devices()
    ]
    peaks = peak_bytes()
    one_run = run_train(cfg_one, steps)
    check_losses(one_run["losses"], one_run["skipped"], steps)

    diffs = [abs(a - b) for a, b in zip(mesh_run["losses"],
                                        one_run["losses"])]
    record = {
        "phase": "mesh", "sizes": sizes, "layout": "dp2 x sp2",
        "context_parallel": "ring", "steps": steps, "seed": seed,
        "cut": CUTS["mesh"],
        "compile_s": round(compile_s, 2), "collectives": collectives,
        "ring_block_kernels": ring_kernels,
        "per_device_program_bytes": per_device_program_bytes,
        "per_device_argument_bytes": argument_bytes,
        "mesh_run": mesh_run, "one_device_run": one_run,
        "loss_abs_diff": [float(f"{d:.3g}") for d in diffs], "tol": tol,
        "bytes_in_use_after_mesh_run": in_use,
        "peak_bytes_in_use_after_mesh_run": peaks,
    }
    if max(diffs) > tol:
        raise RuntimeError(
            f"mesh and one-device losses differ by {max(diffs):.3g} > "
            f"{tol}: {json.dumps(record)}"
        )
    # the CPU keeps no allocator statistics: only an accelerator can show
    # that every device held bytes, and at its peak at least the program's
    # arguments (the replicated state and its slice of the batch)
    if jax.devices()[0].platform != "cpu" and not all(
        use and peak >= argument_bytes for use, peak in zip(in_use, peaks)
    ):
        raise RuntimeError(
            f"a device held less than the program's {argument_bytes} "
            f"argument bytes: in use {in_use}, peak {peaks}"
        )
    return record


# ------------------------------------------------------------------- main ---


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--four-chips", action="store_true",
        help="run only the (dp2, sp2) mesh phase and its one-chip twin",
    )
    args = ap.parse_args(argv)
    try:
        import jax

        device = device_record()
        if device["platform"] != "tpu":
            raise RuntimeError(
                f"chip_smoke needs a TPU; JAX found {device} — run it "
                "through the chip tool, there is no CPU mode"
            )
        want = 4 if args.four_chips else 1
        if device["count"] != want:
            raise RuntimeError(
                ("--four-chips needs 4 chips" if args.four_chips
                 else "the default phases need exactly 1 chip")
                + f", JAX found {device['count']}"
            )
        import alphafold2_tpu

        alphafold2_tpu.enable_compile_cache()
        emit({"phase": "start", "device": device,
              "jax": jax.__version__,
              "compile_cache": alphafold2_tpu.compile_cache_dir()})
        t0 = time.perf_counter()
        if args.four_chips:
            emit(phase_mesh())
            emit(phase_kernels(only="ring_flash"))
        else:
            train_rec = phase_train()
            emit(train_rec)
            if not train_rec["has_tpu_custom_call"]:
                raise RuntimeError(
                    "the compiled train step holds no tpu_custom_call: no "
                    "Pallas kernel ran (the dense pair<->MSA cross-attention "
                    "is 65,536 x 4,096 x 8 logits at this size)"
                )
            emit(phase_serve())
            emit(phase_kernels())
            emit(phase_ssd_scan())
            emit(phase_ssd_scan(groups=1, chunk=256))
            emit(phase_causal_conv())
            emit(phase_sparse_core())
            emit(phase_select())
        emit({"phase": "done", "seconds": round(time.perf_counter() - t0, 1)})
    except Exception as e:  # the boundary: report, then fail
        import traceback

        traceback.print_exc()
        emit({"ok": False, "error": f"{type(e).__name__}: {e}"[:2000]})
        return 1
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
