"""Run the BASELINE.json benchmark-config suite on the attached chip and
write BENCH_SUITE.json.

The five configs come from BASELINE.json "configs" (mirrored in BASELINE.md),
scaled to ONE chip where the original calls for a pod (config 5). Each entry
reports residue-pairs/sec/chip for a full train step (fwd+bwd+opt) and the
step time; config 1 is the reference README functional config (forward+
backward only, the "CPU sanity" anchor — here timed on the accelerator).

Usage:
    python scripts/bench_suite.py            # all configs (slow: ~5 compiles)
    python scripts/bench_suite.py 2 4        # a subset by number
    AF2TPU_SUITE_SMOKE=1 python scripts/bench_suite.py   # tiny shapes (CI)
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import alphafold2_tpu

alphafold2_tpu.enable_compile_cache()

import jax
import jax.numpy as jnp

SMOKE = os.environ.get("AF2TPU_SUITE_SMOKE") == "1"
ITERS = 3 if SMOKE else 8


def _timed_loop(run, warmup: int = 2) -> float:
    """Shared timing protocol: warmup calls, then ITERS timed calls.
    ``run()`` performs one step and returns an array to block on."""
    out = None
    for _ in range(warmup):
        out = run()
    if out is not None:
        jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(ITERS):
        out = run()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / ITERS


def _train_throughput(cfg_kw, data_kw, label):
    from alphafold2_tpu.config import Config, DataConfig, ModelConfig, TrainConfig
    from alphafold2_tpu.data.pipeline import SyntheticDataset
    from alphafold2_tpu.train.loop import (
        build_model, device_put_batch, make_train_step, tiny_init_state,
    )

    cfg = Config(
        model=ModelConfig(**cfg_kw),
        data=DataConfig(**data_kw),
        train=TrainConfig(gradient_accumulate_every=1, warmup_steps=10),
    )
    batch = next(iter(SyntheticDataset(cfg.data, seed=0)))
    model = build_model(cfg)
    state = tiny_init_state(cfg, model, batch)
    step = make_train_step(model, mesh=None)
    dev_batch = device_put_batch(batch)
    rng = jax.random.key(0)
    compiled = step.lower(state, dev_batch, rng).compile()
    box = {"state": state, "rng": rng}

    def run():
        box["rng"], r = jax.random.split(box["rng"])
        box["state"], metrics = compiled(box["state"], dev_batch, r)
        return metrics["loss"]

    dt = _timed_loop(run)
    crop = data_kw["crop_len"]
    bsz = data_kw["batch_size"]
    return {
        "config": label,
        "step_ms": round(dt * 1e3, 2),
        "pairs_per_sec": round(bsz * crop * crop / dt, 1),
    }


def config_1():
    """Reference README functional config: fwd+bwd on 128-seq + 5x64 MSA."""
    from alphafold2_tpu.models import Alphafold2

    n, m, nm = (16, 2, 16) if SMOKE else (128, 5, 64)
    model = Alphafold2(dim=256, depth=2, heads=8, dim_head=64,
                      max_seq_len=2 * n, dtype=jnp.bfloat16)
    k = jax.random.key(0)
    seq = jax.random.randint(jax.random.fold_in(k, 1), (1, n), 0, 21)
    msa = jax.random.randint(jax.random.fold_in(k, 2), (1, m, nm), 0, 21)
    mask = jnp.ones((1, n), bool)
    msa_mask = jnp.ones((1, m, nm), bool)
    params = model.init(k, seq, msa, mask=mask, msa_mask=msa_mask)

    def loss(p):
        out = model.apply(p, seq, msa, mask=mask, msa_mask=msa_mask)
        return jnp.mean(out.astype(jnp.float32) ** 2)

    step = jax.jit(jax.value_and_grad(loss))
    compiled = step.lower(params).compile()
    dt = _timed_loop(lambda: compiled(params)[0])
    return {
        "config": f"1: README functional config fwd+bwd ({n} seq, {m}x{nm} MSA)",
        "step_ms": round(dt * 1e3, 2),
        "pairs_per_sec": round(n * n / dt, 1),
    }


def config_2():
    crop, msa = (16, 8) if SMOKE else (256, 64)
    depth = 2 if SMOKE else 12
    return _train_throughput(
        dict(dim=256 if not SMOKE else 64, depth=depth, heads=8,
             dim_head=64 if not SMOKE else 16, max_seq_len=2 * crop,
             remat=True, bfloat16=True),
        dict(crop_len=crop, msa_depth=1 if SMOKE else 8, msa_len=msa,
             batch_size=1, min_len_filter=crop),
        f"2: depth={depth} dense trunk, crop {crop}, {msa}-seq MSA pretraining",
    )


def config_3():
    crop = 16 if SMOKE else 512
    depth = 2 if SMOKE else 12
    sparse = (True, False) * (depth // 2)
    return _train_throughput(
        dict(dim=64 if SMOKE else 256, depth=depth, heads=8,
             dim_head=16 if SMOKE else 64, max_seq_len=crop,
             sparse_self_attn=sparse, cross_attn_compress_ratio=3,
             remat=True, bfloat16=True),
        dict(crop_len=crop, msa_depth=2 if SMOKE else 8,
             msa_len=16 if SMOKE else 128, batch_size=1,
             min_len_filter=crop),
        f"3: depth={depth} interleaved block-sparse + compress=3, crop {crop}",
    )


def config_4():
    crop, msa_d, msa_l = (16, 2, 16) if SMOKE else (384, 16, 128)
    from alphafold2_tpu.models import Alphafold2

    model = Alphafold2(
        dim=64 if SMOKE else 256, depth=1 if SMOKE else 2, heads=8,
        dim_head=16 if SMOKE else 64, max_seq_len=2 * crop,
        msa_tie_row_attn=True, template_attn_depth=1 if SMOKE else 2,
        use_se3_template_embedder=False, dtype=jnp.bfloat16,
    )
    T = 2 if SMOKE else 4
    k = jax.random.key(1)
    seq = jax.random.randint(jax.random.fold_in(k, 1), (1, crop), 0, 21)
    msa = jax.random.randint(jax.random.fold_in(k, 2), (1, msa_d, msa_l), 0, 21)
    t_seq = jax.random.randint(jax.random.fold_in(k, 3), (1, T, crop), 0, 21)
    t_coors = jax.random.normal(jax.random.fold_in(k, 4), (1, T, crop, 3)) * 10
    kw = dict(
        mask=jnp.ones((1, crop), bool),
        msa_mask=jnp.ones((1, msa_d, msa_l), bool),
        templates_seq=t_seq, templates_coors=t_coors,
        templates_mask=jnp.ones((1, T, crop), bool),
    )
    # init at tiny shapes (params depend only on the model config; the
    # template tables are sized by max_num_templates/max_seq_len) — skips
    # the full-size init compile, which at crop 384 + templates dominates
    tn, tm, tT = min(16, crop), min(2, msa_d), min(2, T)
    params = model.init(
        k, seq[:, :tn], msa[:, :tm, :tn],
        mask=kw["mask"][:, :tn],
        msa_mask=kw["msa_mask"][:, :tm, :tn],
        templates_seq=t_seq[:, :tT, :tn],
        templates_coors=t_coors[:, :tT, :tn],
        templates_mask=kw["templates_mask"][:, :tT, :tn],
    )

    def loss(p):
        out = model.apply(p, seq, msa, **kw)
        return jnp.mean(out.astype(jnp.float32) ** 2)

    step = jax.jit(jax.value_and_grad(loss))
    compiled = step.lower(params).compile()
    dt = _timed_loop(lambda: compiled(params)[0])
    return {
        "config": f"4: tied-row MSA + templates ({T}), crop {crop}, "
                  f"{msa_d}x{msa_l} MSA fwd+bwd",
        "step_ms": round(dt * 1e3, 2),
        "pairs_per_sec": round(crop * crop / dt, 1),
    }


def config_5():
    """End-to-end pipeline step (distogram -> MDS -> refine -> RMSD loss),
    reversible trunk. Pod config scaled to one chip."""
    crop = 16 if SMOKE else 128  # elongated x3 -> 384 trunk tokens
    depth = 2 if SMOKE else 8  # depth 24 of the pod config scaled to 1 chip
    from alphafold2_tpu.config import Config, DataConfig, ModelConfig, TrainConfig
    from alphafold2_tpu.data.pipeline import SyntheticDataset
    from alphafold2_tpu.train.end2end import (
        End2EndModel,
        init_end2end_state,
        make_end2end_step,
    )

    cfg = Config(
        model=ModelConfig(dim=64 if SMOKE else 128, depth=depth, heads=4,
                          dim_head=16 if SMOKE else 32, max_seq_len=6 * crop,
                          reversible=True, bfloat16=False),
        data=DataConfig(crop_len=crop, msa_depth=2, msa_len=crop,
                        batch_size=1, min_len_filter=crop),
        train=TrainConfig(gradient_accumulate_every=1, warmup_steps=10),
    )
    batch = next(iter(SyntheticDataset(cfg.data, seed=0)))
    model = End2EndModel(
        dim=cfg.model.dim, depth=cfg.model.depth, heads=cfg.model.heads,
        dim_head=cfg.model.dim_head, max_seq_len=cfg.model.max_seq_len,
        reversible=True, mds_iters=8 if SMOKE else 50,
    )
    from alphafold2_tpu.train.loop import device_put_batch

    from alphafold2_tpu.train.loop import tiny_batch_like

    state = init_end2end_state(cfg, model, tiny_batch_like(batch))
    step = make_end2end_step(model, mesh=None)
    dev_batch = device_put_batch(batch)
    rng = jax.random.key(0)
    compiled = step.lower(state, dev_batch, rng).compile()
    box = {"state": state, "rng": rng}

    def run():
        box["rng"], r = jax.random.split(box["rng"])
        box["state"], metrics = compiled(box["state"], dev_batch, r)
        return metrics["loss"]

    dt = _timed_loop(run)
    return {
        "config": f"5: end-to-end (distogram->MDS->SE3->RMSD), "
                  f"reversible depth={depth}, crop {crop}",
        "step_ms": round(dt * 1e3, 2),
        "pairs_per_sec": round(crop * crop / dt, 1),
    }


def config_6():
    """Bucketed batched serving: mixed-length request stream through
    serve.ServeEngine (one executable per ladder rung, batch 4)."""
    import numpy as np

    from alphafold2_tpu.config import Config, DataConfig, ModelConfig, ServeConfig
    from alphafold2_tpu.serve import ServeEngine, ServeRequest

    buckets = (8, 16) if SMOKE else (32, 48, 64)
    n_req = 6 if SMOKE else 24
    cfg = Config(
        model=ModelConfig(
            dim=32 if SMOKE else 64, depth=1 if SMOKE else 2, heads=4,
            dim_head=8 if SMOKE else 16, max_seq_len=3 * buckets[-1],
            bfloat16=jax.devices()[0].platform != "cpu",
        ),
        data=DataConfig(msa_depth=2 if SMOKE else 4),
        serve=ServeConfig(
            buckets=buckets, max_batch=4, mds_iters=8 if SMOKE else 50
        ),
    )
    engine = ServeEngine(cfg)
    rng = np.random.default_rng(0)
    alpha = "ACDEFGHIKLMNPQRSTVWY"
    reqs = [
        ServeRequest("".join(rng.choice(list(alpha), size=int(n))), seed=i)
        for i, n in enumerate(
            rng.integers(4, buckets[-1] + 1, size=n_req)
        )
    ]
    engine.warmup()
    t0 = time.perf_counter()
    results = engine.predict_many(reqs)
    wall = time.perf_counter() - t0
    lat = sorted(r.latency_s for r in results)
    stats = engine.stats()
    return {
        "config": f"6: bucketed serve engine, buckets {list(buckets)}, "
                  f"batch 4, {n_req} mixed-length requests",
        "step_ms": round(1e3 * wall / max(1, stats.get("serve.batches", 1)), 2),
        "pairs_per_sec": round(
            sum(len(r.seq) ** 2 for r in reqs) / wall, 1
        ),
        "residues_per_sec": round(sum(len(r.seq) for r in reqs) / wall, 1),
        "p50_ms": round(1e3 * lat[len(lat) // 2], 1),
        "p95_ms": round(1e3 * lat[min(len(lat) - 1, int(0.95 * len(lat)))], 1),
        "compiles": stats.get("serve.compiles", 0),
    }


CONFIGS = {"1": config_1, "2": config_2, "3": config_3, "4": config_4,
           "5": config_5, "6": config_6}


def main():
    args = sys.argv[1:]
    unknown = [a for a in args if a not in CONFIGS]
    if unknown:
        raise SystemExit(
            f"unknown config(s) {unknown}; choose from {sorted(CONFIGS)}"
        )
    which = args or list(CONFIGS)
    results = []
    for key in which:
        print(f"running config {key}...", flush=True)
        try:
            r = CONFIGS[key]()
        except Exception as e:  # report partial suites rather than nothing
            r = {"config": key, "error": f"{type(e).__name__}: {e}"[:300]}
        results.append(r)
        print(json.dumps(r), flush=True)
    device = jax.devices()[0].device_kind
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_SUITE.json")
    write_results(path, results, device, SMOKE,
                  partial=which != list(CONFIGS))
    print(f"wrote {path}")


def write_results(path, results, device, smoke, partial):
    """Write the suite file. A subset (``partial``) run MERGES into the
    existing rows instead of clobbering the configs it did not run — but
    only when the rows are comparable (same device, same smoke setting);
    a first TPU run replaces CPU smoke rows wholesale."""
    merged = results
    if partial and os.path.exists(path):
        try:
            with open(path) as f:
                prior = json.load(f)
            if prior.get("device") == device and prior.get("smoke") == smoke:
                by_key = {
                    r["config"].split(":", 1)[0]: r
                    for r in prior.get("results", [])
                }
                for r in results:
                    by_key[r["config"].split(":", 1)[0]] = r
                merged = [by_key[k] for k in sorted(by_key)]
        except (OSError, ValueError, KeyError):
            pass  # unreadable prior file: write this run's rows alone
    with open(path, "w") as f:
        json.dump(
            {"device": device, "smoke": smoke, "results": merged}, f, indent=2
        )


if __name__ == "__main__":
    main()
