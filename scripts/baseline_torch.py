"""PyTorch-baseline distogram training on the SAME data pipeline.

BASELINE.md's quality bar is "distogram lDDT within 1% of the PyTorch
baseline", but the reference publishes no numbers and its own training
driver needs the sidechainnet package (absent here). This script produces
the missing baseline number: it imports the reference package itself
(``--reference``, default /root/reference, read-only) and trains its
``Alphafold2`` on the same npz shards, batching, labels, optimizer
settings, and lDDT metric as this framework's ``train_pre.py`` — an
apples-to-apples pair of runs.

    python scripts/import_pdbs.py pdb_dir/ shards/
    python scripts/baseline_torch.py --data-dir shards/ --steps 300 \
        --dim 64 --depth 2 --crop 128

Two reference dependencies that this baseline never exercises are stubbed
so the import succeeds: ``mdtraj`` (PDB I/O helpers — we load npz shards
instead) and ``se3_transformer_pytorch`` (template sidechain encoder — the
distogram pretraining path never calls it, reference train_pre.py:79).
Prints one JSON line with the final cross-entropy and distogram lDDT.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

os.environ["JAX_PLATFORMS"] = "cpu"  # jax side (labels/metrics) stays on host


def _install_stubs():
    import torch

    if "mdtraj" not in sys.modules:
        sys.modules["mdtraj"] = types.ModuleType("mdtraj")
    if "se3_transformer_pytorch" not in sys.modules:
        se3 = types.ModuleType("se3_transformer_pytorch")

        class SE3Transformer(torch.nn.Module):  # constructed, never called
            def __init__(self, **kwargs):
                super().__init__()

            def forward(self, *args, **kwargs):
                raise NotImplementedError(
                    "SE3 stub: the distogram baseline never runs templates"
                )

        se3.SE3Transformer = SE3Transformer
        sys.modules["se3_transformer_pytorch"] = se3


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--reference", default="/root/reference")
    ap.add_argument("--data-dir", required=True)
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--depth", type=int, default=2)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--dim-head", type=int, default=16)
    ap.add_argument("--crop", type=int, default=128)
    # MSA stream (default off = the round-2 seq-only protocol): depth > 1
    # feeds the reference model a real MSA stream; shards without stored
    # alignments get the same seeded mutation-synthesized MSA as the jax
    # side (data/pipeline.py _fill_msa), so both frameworks see identical
    # arrays. --tie-rows enables the reference's tied-row attention
    # (alphafold2.py:141-151); crop must then not exceed the shortest
    # chain (its tied path forbids padded positions).
    ap.add_argument("--msa-depth", type=int, default=1)
    ap.add_argument("--msa-len", type=int, default=0)  # 0 = crop
    ap.add_argument("--tie-rows", action="store_true")
    # evaluate on a second shard dir of chains NEVER seen in training
    ap.add_argument("--holdout-dir", default=None)
    ap.add_argument("--batch-size", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)  # train_pre.py:18
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--eval-batches", type=int, default=8)
    ap.add_argument("--eval-seed", type=int, default=1234)  # held-out stream
    ap.add_argument("--log-every", type=int, default=25)
    args = ap.parse_args()

    import torch
    import torch.nn.functional as F

    sys.path.insert(0, args.reference)
    _install_stubs()
    from alphafold2_pytorch import Alphafold2  # the reference model itself

    from alphafold2_tpu.config import DataConfig
    from alphafold2_tpu.data.pipeline import NpzShardDataset
    from alphafold2_tpu.utils import distogram_lddt
    from alphafold2_tpu.utils.structure import get_bucketed_distance_matrix

    torch.manual_seed(args.seed)
    msa_len = args.msa_len or args.crop
    use_msa = args.msa_depth > 1

    def make_data_cfg(data_dir):
        return DataConfig(
            source="npz", data_dir=data_dir, crop_len=args.crop,
            batch_size=args.batch_size, msa_depth=args.msa_depth,
            msa_len=msa_len, min_len_filter=16, max_len_filter=10_000,
        )

    data_cfg = make_data_cfg(args.data_dir)

    model = Alphafold2(
        dim=args.dim, depth=args.depth, heads=args.heads,
        dim_head=args.dim_head, max_seq_len=args.crop * 2,
        msa_tie_row_attn=args.tie_rows,
    )
    optim = torch.optim.Adam(model.parameters(), lr=args.lr)

    def batches(seed, cfg=None):
        for batch in NpzShardDataset(cfg or data_cfg, seed=seed):
            seq = torch.from_numpy(batch["seq"]).long()
            mask = torch.from_numpy(batch["mask"]).bool()
            kw = {"mask": mask}
            if use_msa:
                kw["msa"] = torch.from_numpy(batch["msa"]).long()
                kw["msa_mask"] = torch.from_numpy(batch["msa_mask"]).bool()
            # identical labels to train_pre.py: jnp bucketing, -100 ignore
            labels_np = np.asarray(
                get_bucketed_distance_matrix(batch["coords"], batch["mask"])
            )
            yield seq, kw, torch.from_numpy(labels_np).long(), batch

    t0 = time.time()
    stream = batches(args.seed)
    model.train()
    step_ce = float("nan")
    for step in range(args.steps):
        optim.zero_grad()
        for _ in range(args.accum):
            seq, kw, labels, _ = next(stream)
            logits = model(seq, **kw)
            ce = F.cross_entropy(
                logits.reshape(-1, logits.shape[-1]), labels.reshape(-1),
                ignore_index=-100,
            )
            (ce / args.accum).backward()
        optim.step()
        step_ce = float(ce.detach())
        if step % args.log_every == 0:
            print(
                f"[torch baseline step {step}] ce={step_ce:.4f} "
                f"({time.time() - t0:.0f}s)",
                flush=True,
            )

    model.eval()

    def eval_stream_metrics(cfg, seed):
        lddts, ces = [], []
        stream = batches(seed, cfg)
        with torch.no_grad():
            for _ in range(args.eval_batches):
                seq, kw, labels, batch = next(stream)
                logits = model(seq, **kw)
                ces.append(float(F.cross_entropy(
                    logits.reshape(-1, logits.shape[-1]), labels.reshape(-1),
                    ignore_index=-100,
                )))
                dl = distogram_lddt(
                    logits.numpy(), batch["coords"], mask=batch["mask"]
                )
                lddts.append(float(np.mean(np.asarray(dl))))
        return float(np.mean(ces)), float(np.mean(lddts))

    eval_ce, eval_lddt = eval_stream_metrics(data_cfg, args.eval_seed)

    record = {
        "baseline": "pytorch-reference",
        "steps": args.steps,
        "config": {
            "dim": args.dim, "depth": args.depth, "heads": args.heads,
            "dim_head": args.dim_head, "crop": args.crop,
            "batch": args.batch_size, "lr": args.lr, "accum": args.accum,
            "msa_depth": args.msa_depth, "msa_len": msa_len,
            "tie_rows": args.tie_rows, "seed": args.seed,
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
