#!/usr/bin/env python
"""Distogram pretraining driver — CLI equivalent of reference train_pre.py,
with a real config system instead of in-source constants (SURVEY.md S5.6).

Usage:
  python train_pre.py                               # reference defaults
  python train_pre.py model.depth=12 data.crop_len=256 mesh.data_parallel=4
"""

import sys

import alphafold2_tpu
from alphafold2_tpu.config import Config, ModelConfig, parse_cli


def main(argv):
    alphafold2_tpu.enable_compile_cache()
    from alphafold2_tpu.parallel.distributed import initialize

    initialize()  # multi-host process group (no-op single-process)
    base = Config(model=ModelConfig(dim=256, depth=1))  # train_pre.py:52-57
    cfg = parse_cli(argv, base)
    print("config:", cfg.to_json())
    from alphafold2_tpu.train.loop import train

    train(cfg)


if __name__ == "__main__":
    main(sys.argv[1:])
