#!/usr/bin/env python3
"""Readings that the limits of a ``train_swa_lm`` cell's comparison are set
from (``harness/control_lm.py``'s method, for this kind's reference).

    python3 benchmark/harness/control_swa_lm.py \\
        --workload train_smallthinker_ep8_seq16k --seeds 11 12

No measured window and no program: for each seed the plain reference follows
the first steps in float32, and beside it, put in the program's place,

- ``fp8``: the same reference with every matmul's operands rounded to
  float8_e4m3 first, the nearest precision below the configuration's
  bfloat16. This is the control: it has to come out as not correct;
- ``bf16``: the reference in the configuration's own precision (what a sound
  program is expected to read, a diagnostic);
- ``window_off``: the window layers run full causal; ``rope_on_global``: the
  global layer turned by rotary positions too; ``route_from_y``: the router
  reads the normed post-attention stream. Each planted in the float32
  reference; each has to fail at least one limit.

Each prints the numbers ``harness/train_swa_lm.py`` compares, and which of
the configuration's limits they break, as ``correct.judge`` judges them.
"""

import argparse
import itertools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
WHICH = ("fp8", "bf16", "window_off", "rope_on_global", "route_from_y")


def readings(resolved: dict, seed: int, which) -> dict:
    from benchmark.harness import correct, traffic_lm, train_swa_lm
    from benchmark.reference.lm_model import Precision

    config = resolved["config"]
    s31 = traffic_lm.seed31(seed)
    batches = list(itertools.islice(traffic_lm.lm_batches(
        resolved["traffic"], config["vocab_size"], s31),
        train_swa_lm.CHECK_STEPS))
    ref = train_swa_lm.reference_readings(config, s31, batches)
    limits = config["correct"]["limits"]
    out = {}
    for name in which:
        if name in ("fp8", "bf16"):
            other = train_swa_lm.reference_readings(
                config, s31, batches, prec=Precision(name))
        else:
            other = train_swa_lm.reference_readings(
                config, s31, batches, fault=name)
        compared, _ = correct.judge(
            train_swa_lm.training_numbers(other, ref), limits)
        out[name] = {k: {"value": c["value"], "at": c["at"]}
                     for k, c in compared.items()}
        out[name]["breaks"] = sorted(
            k for k, c in compared.items() if not c["ok"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--which", nargs="+", default=list(WHICH))
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
