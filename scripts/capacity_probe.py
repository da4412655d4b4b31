"""Probe the largest training configuration that fits one chip.

BASELINE.md's second metric is "peak MSA x seq_len per chip: measure &
maximize". This driver binary-searches the largest crop that completes a
full training step (fwd+bwd+opt) on the attached accelerator for each of a
few engine configs (dense+remat, reversible, block-sparse, dense+remat-dots), at fixed MSA
16 x crop, and writes CAPACITY.json.

Each probe costs a compile, so the search is bounded (MAX_PROBES per
config). OOM is detected by catching RESOURCE_EXHAUSTED from compile or
execute.

Usage: python scripts/capacity_probe.py [--smoke]
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import alphafold2_tpu

alphafold2_tpu.enable_compile_cache()

import jax

SMOKE = "--smoke" in sys.argv or os.environ.get("AF2TPU_SUITE_SMOKE") == "1"
MAX_PROBES = 3 if SMOKE else 6


def step_fits(crop: int, model_kw: dict) -> bool:
    """One full train step at this crop; False on device OOM."""
    import jax.numpy as jnp  # noqa: F401

    from alphafold2_tpu.config import Config, DataConfig, ModelConfig, TrainConfig
    from alphafold2_tpu.data.pipeline import SyntheticDataset
    from alphafold2_tpu.train.loop import (
        build_model, device_put_batch, make_train_step, tiny_init_state,
    )

    cfg = Config(
        model=ModelConfig(max_seq_len=2 * crop, **model_kw),
        data=DataConfig(crop_len=crop, msa_depth=2 if SMOKE else 16,
                        msa_len=crop, batch_size=1, min_len_filter=crop),
        train=TrainConfig(gradient_accumulate_every=1, warmup_steps=10),
    )
    try:
        batch = next(iter(SyntheticDataset(cfg.data, seed=0)))
        model = build_model(cfg)
        state = tiny_init_state(cfg, model, batch)
        step = make_train_step(model, mesh=None)
        state, metrics = step(state, device_put_batch(batch), jax.random.key(0))
        jax.block_until_ready(metrics["loss"])
        return bool(jax.numpy.isfinite(metrics["loss"]))
    except Exception as e:
        msg = str(e)
        if "RESOURCE_EXHAUSTED" in msg or "Out of memory" in msg or "OOM" in msg:
            return False
        raise


def probe(name: str, model_kw: dict, lo: int, hi: int) -> dict:
    """Largest crop in [lo, hi] that fits, by bounded bisection on
    multiples of 64 (128-lane friendly)."""
    quantum = 16 if SMOKE else 64
    results = {}

    def fits(crop):
        if crop not in results:
            print(f"  {name}: probing crop={crop}...", flush=True)
            results[crop] = step_fits(crop, model_kw)
            print(f"  {name}: crop={crop} -> "
                  f"{'fits' if results[crop] else 'OOM'}", flush=True)
        return results[crop]

    if not fits(lo):
        return {"engine": name, "max_crop": 0, "probes": results}
    best = lo
    for _ in range(MAX_PROBES - 1):
        if lo >= hi:
            break
        mid = ((lo + hi + quantum) // (2 * quantum)) * quantum
        mid = max(lo + quantum, min(mid, hi))
        if fits(mid):
            best, lo = mid, mid
        else:
            hi = mid - quantum
    return {"engine": name, "max_crop": best, "probes": {
        str(c): ok for c, ok in sorted(results.items())}}


def main():
    lo, hi = (16, 64) if SMOKE else (256, 1024)
    dim = 64 if SMOKE else 256
    dh = 16 if SMOKE else 64
    depth = 1 if SMOKE else 4
    engines = [
        ("dense+remat", dict(dim=dim, depth=depth, heads=8, dim_head=dh,
                             remat=True, msa_tie_row_attn=True,
                             bfloat16=True)),
        ("reversible", dict(dim=dim, depth=depth, heads=8, dim_head=dh,
                            reversible=True, msa_tie_row_attn=True,
                            bfloat16=True)),
        ("block-sparse+remat", dict(dim=dim, depth=depth, heads=8, dim_head=dh,
                                    remat=True, sparse_self_attn=True,
                                    msa_tie_row_attn=True, bfloat16=True)),
        # remat_policy="dots" keeps matmul outputs (backward skips their
        # recompute): how much peak crop does the MFU trade cost?
        ("dense+remat-dots", dict(dim=dim, depth=depth, heads=8, dim_head=dh,
                                  remat=True, remat_policy="dots",
                                  msa_tie_row_attn=True, bfloat16=True)),
    ]
    out = {"device": jax.devices()[0].device_kind, "smoke": SMOKE,
           "msa": "16 x crop", "results": []}
    for name, kw in engines:
        out["results"].append(probe(name, kw, lo, hi))
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "CAPACITY.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps(out))
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
