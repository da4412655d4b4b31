#!/usr/bin/env python
"""End-to-end structure training driver — the runnable realization of the
reference's train_end2end.py design sketch (which crashes as written;
SURVEY.md S2.5). Full pipeline: trunk -> distogram -> MDS -> sidechains ->
SE(3) refine -> Kabsch/RMSD loss, compiled as one program.

Usage:
  python train_end2end.py data.crop_len=64 model.depth=1 train.num_steps=1000
"""

import sys

import alphafold2_tpu
from alphafold2_tpu.config import Config, DataConfig, ModelConfig, parse_cli


def main(argv):
    alphafold2_tpu.enable_compile_cache()
    from alphafold2_tpu.parallel.distributed import initialize

    initialize()  # multi-host process group (no-op single-process)
    base = Config(
        model=ModelConfig(dim=256, depth=1),
        data=DataConfig(crop_len=64),  # distogram runs over 3L atom tokens
    )
    cfg = parse_cli(argv, base)
    print("config:", cfg.to_json())
    from alphafold2_tpu.train.end2end import train_end2end

    train_end2end(cfg)


if __name__ == "__main__":
    main(sys.argv[1:])
