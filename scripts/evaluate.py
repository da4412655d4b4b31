"""Evaluate a (checkpointed) model: distogram quality + realized-structure
metrics over held-out batches.

    python scripts/evaluate.py [--checkpoint dir] [--batches 8] [overrides...]

Reports the BASELINE.md quality bar (distogram lDDT) plus distogram
cross-entropy/accuracy and, with --realize, full-pipeline structure metrics
(MDS -> Kabsch -> RMSD/GDT/TM/lDDT vs the true CA trace). One JSON line at
the end for automation.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import alphafold2_tpu
from alphafold2_tpu.config import Config, parse_cli


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--batches", type=int, default=4)
    ap.add_argument("--seed", type=int, default=1234)  # held-out stream
    ap.add_argument("--realize", action="store_true",
                    help="also run MDS realization + structure metrics")
    ap.add_argument("overrides", nargs="*")
    args = ap.parse_args()

    alphafold2_tpu.enable_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from alphafold2_tpu.data.pipeline import make_dataset
    from alphafold2_tpu.train.loop import (
        apply_features, build_model, device_put_batch,
        distogram_cross_entropy, tiny_init_state,
    )
    from alphafold2_tpu.utils import Kabsch, RMSD, TMscore, distogram_lddt, lddt
    from alphafold2_tpu.utils.structure import get_bucketed_distance_matrix

    cfg = parse_cli(args.overrides, Config())
    # same feature adaptation as training: PLM-trained checkpoints need the
    # embedds stream to restore and to be evaluated on what they were fed
    ds = apply_features(iter(make_dataset(cfg.data, seed=args.seed)), cfg)
    model = build_model(cfg)
    sample = next(ds)
    # params only (for the checkpoint restore target): tiny-sliced init
    # skips the full-size forward compile
    state = tiny_init_state(cfg, model, sample)
    params = state.params
    if args.checkpoint:
        from alphafold2_tpu.train.checkpoint import CheckpointManager

        mgr = CheckpointManager(args.checkpoint)
        try:
            params, step = mgr.restore_params(state.params)
            print(f"restored checkpoint step {step}")
        finally:
            mgr.close()

    @jax.jit
    def forward(params, batch):
        logits = model.apply(
            params, batch["seq"], batch.get("msa"), mask=batch["mask"],
            msa_mask=batch.get("msa_mask"), embedds=batch.get("embedds"),
        )
        labels = get_bucketed_distance_matrix(batch["coords"], batch["mask"])
        ce = distogram_cross_entropy(logits, labels)
        pred_bins = jnp.argmax(logits, -1)
        valid = labels != -100
        acc = jnp.sum((pred_bins == labels) & valid) / jnp.maximum(
            jnp.sum(valid), 1
        )
        dl = distogram_lddt(logits, batch["coords"], mask=batch["mask"])
        return ce, acc, jnp.mean(dl), logits

    ces, accs, dls, struct = [], [], [], []
    batch = sample
    for b in range(args.batches):
        dev = device_put_batch(batch)
        ce, acc, dl, logits = forward(params, dev)
        ces.append(float(ce)); accs.append(float(acc)); dls.append(float(dl))
        print(f"[batch {b}] ce={float(ce):.4f} bin_acc={float(acc):.4f} "
              f"distogram_lddt={float(dl):.4f}")
        if args.realize:
            from alphafold2_tpu.predict import realize_structure

            # CA-level distogram: no (N,CA,C) triplets, so the phi-based
            # chirality fix does not apply. Padding weights zeroed via mask.
            coords, _, _ = realize_structure(
                logits, iters=100, fix_mirror=False,
                mask=jnp.asarray(batch["mask"]),
            )
            for k in range(coords.shape[0]):
                # select valid residues by index — masks from real data can
                # have interior holes, a prefix slice would be wrong
                valid = np.where(np.asarray(batch["mask"][k]))[0]
                true = np.asarray(batch["coords"][k])[valid].T  # (3, V)
                pred = np.asarray(coords[k])[:, valid]
                a, t = Kabsch(pred, true)
                struct.append({
                    "rmsd": float(RMSD(np.asarray(a), np.asarray(t))[0]),
                    "tm": float(TMscore(np.asarray(a), np.asarray(t))[0]),
                    "lddt": float(lddt(np.asarray(a).T[None],
                                       np.asarray(t).T[None])[0]),
                })
        batch = next(ds)

    result = {
        "distogram_ce": sum(ces) / len(ces),
        "distogram_bin_accuracy": sum(accs) / len(accs),
        "distogram_lddt": sum(dls) / len(dls),
        "batches": args.batches,
    }
    if struct:
        for key in ("rmsd", "tm", "lddt"):
            result[f"structure_{key}"] = sum(s[key] for s in struct) / len(struct)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
