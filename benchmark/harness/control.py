#!/usr/bin/env python3
"""Readings that the limits of a training cell's comparison are set from.

    python3 benchmark/harness/control.py --workload train_flagship --seeds 11 12 13

No measured window and no program: for each seed the plain reference follows
the first steps in float32, and beside it, put in the program's place,

- ``fp8``: the same reference with every matmul's operands rounded to
  float8_e4m3 first, the nearest precision below the configuration's
  bfloat16. This is the control: it has to come out as not correct;
- ``bf16``: the reference in the configuration's own precision (what a sound
  program is expected to read, a diagnostic);
- ``half_batch``: the fault "half of the batch left out, the mean taken over
  the rest", planted in the float32 reference: the second half of the crop's
  residues masked out of the loss and of the attention.

Each prints the numbers ``harness/correct.py`` compares. (A state left
unchanged needs no run: the change's worst leaf reads 1.)
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def readings(resolved: dict, seed: int, which) -> dict:
    import itertools

    from benchmark.harness import correct, traffic, train
    from benchmark.reference import model as ref_model

    config = resolved["config"]
    s31 = traffic.seed31(seed)
    params = ref_model.init_params(train.model_sizes(config), s31)
    batches = list(itertools.islice(traffic.train_batches(
        resolved["traffic"], train.data_sizes(config), s31),
        train.CHECK_STEPS))
    ref = train.reference_readings(config, params, batches)
    out = {}
    for name in which:
        if name == "half_batch":
            half = [dict(b, mask=b["mask"].copy()) for b in batches]
            for b in half:
                b["mask"][:, b["mask"].shape[1] // 2:] = False
            other = train.reference_readings(config, params, half)
        else:
            other = train.reference_readings(
                config, params, batches, ref_model.Precision(name))
        out[name] = {k: {"value": v, "at": at} for k, (v, at) in
                     correct.training_numbers(other, ref).items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--which", nargs="+",
                    default=["fp8", "bf16", "half_batch"])
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmark.harness import common

    resolved = common.resolve(args.workload)
    import jax

    common.enable_cache()
    for seed in args.seeds:
        print(json.dumps({"seed": seed, "device": jax.devices()[0].device_kind,
                          **readings(resolved, seed, args.which)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
