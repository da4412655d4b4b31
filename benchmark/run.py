#!/usr/bin/env python3
"""The benchmark's command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Resolves the cell in BENCHMARK.json to its configuration, traffic mix and
per-layer metrics (each a file found by name), checks that JAX holds a TPU
with the chips the cell asks for, and hands over to the driver of the
configuration's ``kind``. The last line of standard output is the result.
"""

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmark.harness import common

    resolved = common.resolve(args.workload)
    import jax

    devices = jax.devices()
    chips = resolved["cell"]["chips"]
    if devices[0].platform != "tpu" or len(devices) != chips:
        print(f"benchmark: {args.workload} needs {chips} TPU chip(s); JAX "
              f"found {len(devices)} device(s) of platform "
              f"{devices[0].platform!r}. There is no CPU mode.",
              file=sys.stderr)
        return 2
    common.enable_cache()
    driver = importlib.import_module(
        f"benchmark.harness.{resolved['config']['kind']}")
    run = driver.run(resolved, args.seed, args.seconds, bool(args.trace),
                     T_START)
    common.emit(common.result_line(resolved, run, bool(args.trace)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
