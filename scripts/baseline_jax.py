"""The JAX twin of baseline_torch.py: identical protocol, this framework.

One half of the matched head-to-head pair (BASELINE.md quality bar:
"distogram lDDT within 1% of the PyTorch baseline"). Every knob mirrors
scripts/baseline_torch.py exactly — same NpzShardDataset stream (same
seeds -> bit-identical numpy batches), same bucketed-distance labels, same
plain Adam(3e-4) with no warmup/clip/accum (the reference's optimizer,
train_pre.py:63), same eval protocol (held-out crop/MSA draws at
--eval-seed, optional --holdout-dir of never-trained chains), same JSON
record shape. The only intentional difference is the framework under test.

    python scripts/baseline_jax.py --data-dir shards/_h2h_train \
        --holdout-dir shards/_h2h_holdout --steps 600 --dim 256 --depth 2 \
        --heads 8 --dim-head 64 --crop 64 --msa-depth 16 --msa-len 64 \
        --tie-rows --eval-batches 16 --seed 0
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

os.environ["JAX_PLATFORMS"] = "cpu"  # matched-pair runs are host-side


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--data-dir", required=True)
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--depth", type=int, default=2)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--dim-head", type=int, default=16)
    ap.add_argument("--crop", type=int, default=128)
    ap.add_argument("--msa-depth", type=int, default=1)
    ap.add_argument("--msa-len", type=int, default=0)  # 0 = crop
    ap.add_argument("--tie-rows", action="store_true")
    # inversion-based O(1)-activation-memory trunk engine (beyond-reference
    # at this scale: the reference's reversible mode exists but its repo
    # never trained it on real data)
    ap.add_argument("--reversible", action="store_true")
    # re-draw params under the reference's torch module defaults
    # (models/init.py) — isolates init distributions in the head-to-head
    ap.add_argument("--torch-init", action="store_true")
    # exact erf GELU (the reference's torch F.gelu) instead of the tanh
    # approximation — the remaining known systematic functional divergence
    ap.add_argument("--exact-gelu", action="store_true")
    ap.add_argument("--bf16", action="store_true")  # default f32 = torch CPU
    ap.add_argument("--holdout-dir", default=None)
    ap.add_argument("--batch-size", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--eval-batches", type=int, default=8)
    ap.add_argument("--eval-seed", type=int, default=1234)
    ap.add_argument("--log-every", type=int, default=25)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import optax
    from flax.training.train_state import TrainState

    from alphafold2_tpu.config import Config, DataConfig, ModelConfig
    from alphafold2_tpu.data.pipeline import NpzShardDataset
    from alphafold2_tpu.train.loop import (
        build_model,
        distogram_cross_entropy,
        tiny_batch_like,
    )
    from alphafold2_tpu.utils import distogram_lddt
    from alphafold2_tpu.utils.structure import get_bucketed_distance_matrix

    if args.torch_init and args.reversible:
        ap.error(
            "--torch-init is incompatible with --reversible: the reversible "
            "trunk's depth-stacked params would corrupt the fan_in "
            "computation (models/init.py)"
        )

    msa_len = args.msa_len or args.crop
    use_msa = args.msa_depth > 1

    def make_data_cfg(data_dir):
        return DataConfig(
            source="npz", data_dir=data_dir, crop_len=args.crop,
            batch_size=args.batch_size, msa_depth=args.msa_depth,
            msa_len=msa_len, min_len_filter=16, max_len_filter=10_000,
        )

    data_cfg = make_data_cfg(args.data_dir)
    cfg = Config(
        model=ModelConfig(
            dim=args.dim, depth=args.depth, heads=args.heads,
            dim_head=args.dim_head, max_seq_len=args.crop * 2,
            msa_tie_row_attn=args.tie_rows, bfloat16=args.bf16,
            reversible=args.reversible, gelu_exact=args.exact_gelu,
        ),
        data=data_cfg,
    )
    model = build_model(cfg)

    def model_kwargs(batch):
        kw = {"mask": jnp.asarray(batch["mask"])}
        if use_msa:
            kw["msa"] = jnp.asarray(batch["msa"])
            kw["msa_mask"] = jnp.asarray(batch["msa_mask"])
        return kw

    stream = iter(NpzShardDataset(data_cfg, seed=args.seed))
    sample = next(stream)
    # tiny-shape init (bit-identical params, none of the full-size compile)
    tiny = tiny_batch_like(sample if use_msa else
                           {k: v for k, v in sample.items()
                            if k in ("seq", "mask")})
    params = model.init(
        jax.random.key(args.seed), jnp.asarray(tiny["seq"]),
        jnp.asarray(tiny["msa"]) if use_msa else None,
        mask=jnp.asarray(tiny["mask"]),
        msa_mask=jnp.asarray(tiny["msa_mask"]) if use_msa else None,
    )
    if args.torch_init:
        from alphafold2_tpu.models.init import torch_match_reinit

        params = torch_match_reinit(params, jax.random.key(args.seed))
    # plain Adam, exactly torch.optim.Adam's defaults (betas 0.9/0.999,
    # eps 1e-8) — NOT the production warmup-cosine/clip/adamw of
    # train.loop.build_optimizer, which torch's side doesn't have
    state = TrainState.create(
        apply_fn=model.apply, params=params, tx=optax.adam(args.lr)
    )

    @jax.jit
    def train_step(state, batch):
        labels = get_bucketed_distance_matrix(batch["coords"], batch["mask"])

        def loss_fn(p):
            logits = state.apply_fn(
                p, batch["seq"], batch.get("msa"),
                mask=batch["mask"], msa_mask=batch.get("msa_mask"),
            )
            return distogram_cross_entropy(logits, labels)

        ce, grads = jax.value_and_grad(loss_fn)(state.params)
        return state.apply_gradients(grads=grads), ce

    @jax.jit
    def eval_logits(params, batch):
        return model.apply(
            params, batch["seq"], batch.get("msa"),
            mask=batch["mask"], msa_mask=batch.get("msa_mask"),
        )

    def device_batch(b):
        out = {"seq": jnp.asarray(b["seq"]), "mask": jnp.asarray(b["mask"]),
               "coords": jnp.asarray(b["coords"])}
        if use_msa:
            out["msa"] = jnp.asarray(b["msa"])
            out["msa_mask"] = jnp.asarray(b["msa_mask"])
        return out

    t0 = time.time()
    batch_np = sample
    step_ce = float("nan")
    for step in range(args.steps):
        state, ce = train_step(state, device_batch(batch_np))
        step_ce = float(ce)
        batch_np = next(stream)
        if step % args.log_every == 0:
            print(
                f"[jax baseline step {step}] ce={step_ce:.4f} "
                f"({time.time() - t0:.0f}s)",
                flush=True,
            )

    def eval_stream_metrics(dcfg, seed):
        lddts, ces = [], []
        es = iter(NpzShardDataset(dcfg, seed=seed))
        for _ in range(args.eval_batches):
            b = next(es)
            db = device_batch(b)
            logits = eval_logits(state.params, db)
            labels = get_bucketed_distance_matrix(db["coords"], db["mask"])
            ces.append(float(distogram_cross_entropy(logits, labels)))
            dl = distogram_lddt(logits, db["coords"], mask=db["mask"])
            lddts.append(float(np.mean(np.asarray(dl))))
        return float(np.mean(ces)), float(np.mean(lddts))

    eval_ce, eval_lddt = eval_stream_metrics(data_cfg, args.eval_seed)
    record = {
        "baseline": "alphafold2_tpu",
        "steps": args.steps,
        "config": {
            "dim": args.dim, "depth": args.depth, "heads": args.heads,
            "dim_head": args.dim_head, "crop": args.crop,
            "batch": args.batch_size, "lr": args.lr, "accum": 1,
            "msa_depth": args.msa_depth, "msa_len": msa_len,
            "tie_rows": args.tie_rows, "seed": args.seed,
            "dtype": "bf16" if args.bf16 else "f32",
            "engine": "reversible" if args.reversible else "default",
            "init": "torch" if args.torch_init else "flax",
            "gelu": "exact" if args.exact_gelu else "tanh",
        },
        "final_train_ce": round(step_ce, 4),
        "eval_ce": round(eval_ce, 4),
        "distogram_lddt": round(eval_lddt, 4),
        "seconds": round(time.time() - t0, 1),
    }
    if args.holdout_dir:
        hce, hdl = eval_stream_metrics(
            make_data_cfg(args.holdout_dir), args.eval_seed
        )
        record["holdout_eval_ce"] = round(hce, 4)
        record["holdout_distogram_lddt"] = round(hdl, 4)
    print(json.dumps(record))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
