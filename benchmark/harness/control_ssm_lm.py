#!/usr/bin/env python3
"""Readings that the limits of a ``train_ssm_lm`` cell's comparison are set
from (``harness/control_lm.py``'s method, as ``harness/control_swa_lm.py`` has
it, for this kind's reference).

    python3 benchmark/harness/control_ssm_lm.py \\
        --workload train_nemotron3_nano_ep16_seq8k --seeds 11 12

No measured window and no step of the program: the start is the cell's own
(``train_ssm_lm.balanced_router_bias``: the program's forward sets the
router's correction bias, as in a run's set-up); from it, for each seed, the
plain reference follows the first steps in float32, and beside it, put in
the program's place,

- ``fp8``: the same reference with every matmul's operands rounded to
  float8_e4m3 first, the nearest precision below the configuration's
  bfloat16. This is the control: it has to come out as not correct;
- ``bf16``: the reference in the configuration's own precision (what a sound
  program is expected to read, a diagnostic);
- ``state_dropped``: the state-space layers' state set to zero at every
  multiple of ``chunk_size`` steps (a chunked scan that forgets to carry);
  ``conv_reversed``: the convolution's taps applied in reverse order;
  ``relu``: ``relu`` in place of ``relu^2`` in every expert. Each planted in
  the float32 reference; each has to fail at least one limit.

Each prints the numbers ``harness/train_ssm_lm.py`` compares, and which of
the configuration's limits they break, as ``correct.judge`` judges them.
"""

import argparse
import itertools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
WHICH = ("fp8", "bf16", "state_dropped", "conv_reversed", "relu")


def readings(resolved: dict, seed: int, which) -> dict:
    from benchmark.harness import correct, traffic_lm, train_ssm_lm
    from benchmark.reference.lm_model import Precision

    config = resolved["config"]
    s31 = traffic_lm.seed31(seed)
    batches = list(itertools.islice(traffic_lm.lm_batches(
        resolved["traffic"], config["vocab_size"], s31),
        train_ssm_lm.CHECK_STEPS))
    bias = train_ssm_lm.balanced_router_bias(
        config, resolved["traffic"], s31,
        train_ssm_lm.start_params(config, s31, None))
    ref = train_ssm_lm.reference_readings(config, s31, batches,
                                          router_bias=bias)
    limits = config["correct"]["limits"]
    out = {}
    for name in which:
        if name in ("fp8", "bf16"):
            other = train_ssm_lm.reference_readings(
                config, s31, batches, prec=Precision(name), router_bias=bias)
        else:
            other = train_ssm_lm.reference_readings(
                config, s31, batches, fault=name, router_bias=bias)
        compared, _ = correct.judge(
            train_ssm_lm.training_numbers(other, ref), limits)
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
