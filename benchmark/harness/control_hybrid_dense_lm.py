#!/usr/bin/env python3
"""Readings that the limits of a ``train_hybrid_dense_lm`` cell's comparison
are set from (``harness/control_ssm_lm.py``'s method, for this kind's
reference).

    python3 benchmark/harness/control_hybrid_dense_lm.py \\
        --workload train_granite4_h_micro_pp4_seq8k --seeds 11 12

No measured window and no step of the program: for each seed the plain
reference follows the first steps in float32 from the cell's own start, and
beside it, put in the program's place,

- ``fp8``: the same reference with every matmul's operands rounded to
  float8_e4m3 first, the nearest precision below the configuration's
  bfloat16. This is the control: it has to come out as not correct;
- ``bf16``: the reference in the configuration's own precision (what a sound
  program is expected to read, a diagnostic);
- the planted faults of ``reference/hybrid_dense_lm_model.py``:
  ``residual_one`` (``residual_multiplier`` taken as 1), ``scale_sqrt`` (the
  softmax scale 64^-0.5 for 1/64), ``state_dropped`` (the carried state
  dropped at every chunk boundary), ``head_untied`` (a second table from
  another key), ``no_logits_scaling``. Each planted in the float32
  reference; each has to fail at least one limit.

Each prints the numbers ``harness/train_hybrid_dense_lm.py`` compares, which
of the configuration's limits they break, as ``correct.judge`` judges them,
and the stream's two root mean squares at step 0 beside the reference's.
"""

import argparse
import itertools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
WHICH = ("fp8", "bf16", "residual_one", "scale_sqrt", "state_dropped",
         "head_untied", "no_logits_scaling")


def readings(resolved: dict, seed: int, which) -> dict:
    import jax

    from benchmark.harness import correct, traffic_lm
    from benchmark.harness import train_hybrid_dense_lm as driver
    from benchmark.reference import hybrid_dense_lm_model as ref_model
    from benchmark.reference.lm_model import Precision

    config = resolved["config"]
    s31 = traffic_lm.seed31(seed)
    batches = list(itertools.islice(traffic_lm.lm_batches(
        resolved["traffic"], config["vocab_size"], s31), driver.CHECK_STEPS))
    start = jax.device_get(
        ref_model.init_params(driver.model_sizes(config), s31))
    ref = driver.reference_readings(config, start, batches)
    limits = config["correct"]["limits"]
    out = {"stream_rms": [float(v) for v in ref["stream_rms"]]}
    for name in which:
        if name in ("fp8", "bf16"):
            other = driver.reference_readings(
                config, start, batches, prec=Precision(name))
        else:
            other = driver.reference_readings(
                config, start, batches, fault=name)
        compared, _ = correct.judge(
            driver.training_numbers(other, ref, 0), limits)
        out[name] = {k: {"value": c["value"], "at": c["at"]}
                     for k, c in compared.items()}
        out[name]["breaks"] = sorted(
            k for k, c in compared.items() if not c["ok"])
        out[name]["stream_rms"] = [float(v) for v in other["stream_rms"]]
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
