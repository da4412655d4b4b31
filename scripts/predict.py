"""Predict a 3D structure from a sequence and write it as a PDB file.

    python scripts/predict.py --seq MKTAYIAKQRQISFVKSHFSRQLEERLGLIEVQ \
        [--checkpoint ckpt_dir] [--out pred.pdb] [model.dim=256 ...]

Runs the full pipeline (trunk -> distogram -> MDS -> sidechains -> SE(3)
refine — the flow the reference only sketches) and exports N/CA/C backbone
records via the dependency-free PDB writer. Without --checkpoint the model
is randomly initialized: the geometry is meaningless but the pipeline is
real, which is exactly what an integration smoke needs.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import alphafold2_tpu
from alphafold2_tpu.config import Config, parse_cli


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seq", required=True, help="one-letter AA sequence")
    ap.add_argument("--checkpoint", default=None, help="training checkpoint dir")
    ap.add_argument("--out", default="prediction.pdb")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("overrides", nargs="*", help="config overrides key=value")
    args = ap.parse_args()

    alphafold2_tpu.enable_compile_cache()
    from alphafold2_tpu.predict import predict
    from alphafold2_tpu.utils import pdb as pdbio

    cfg = parse_cli(args.overrides, Config())
    pred = predict(cfg, args.seq, checkpoint_dir=args.checkpoint, seed=args.seed)
    pdbio.save_pdb(pred.to_pdb(args.seq), args.out)
    ca = pred.backbone[:, 1]
    import numpy as np

    d = np.linalg.norm(ca[1:] - ca[:-1], axis=-1)
    print(
        f"wrote {args.out}: {len(args.seq)} residues, "
        f"mean consecutive CA-CA distance {d.mean():.2f} A, "
        f"mean confidence weight {pred.weights.mean():.3f}"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
