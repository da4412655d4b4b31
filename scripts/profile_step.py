"""Profile the benchmark training step on the attached accelerator and print
the top ops by self time, aggregated from the trace's XLA-op events.

Usage: python scripts/profile_step.py [overrides like AF2TPU_BENCH_* env]
Writes the raw jax.profiler trace under <checkout>/chiprun_out/profile (the
directory a chip run brings back; inspect with tensorboard if available) and prints a text summary so no external viewer
is needed.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import alphafold2_tpu

alphafold2_tpu.enable_compile_cache()

import jax
import jax.numpy as jnp


def run_profiled_steps(trace_dir: str, n_steps: int = 3):
    from alphafold2_tpu.config import Config, DataConfig, ModelConfig, TrainConfig
    from alphafold2_tpu.data.pipeline import SyntheticDataset
    from alphafold2_tpu.train.loop import (
        build_model, device_put_batch, make_train_step, tiny_init_state,
    )

    e = lambda k, d: int(os.environ.get(k, d))
    cfg = Config(
        model=ModelConfig(
            dim=e("AF2TPU_BENCH_DIM", 256), depth=e("AF2TPU_BENCH_DEPTH", 2),
            heads=8, dim_head=64,
            max_seq_len=e("AF2TPU_BENCH_CROP", 256) * 2,
            msa_tie_row_attn=True, bfloat16=True,
        ),
        data=DataConfig(
            crop_len=e("AF2TPU_BENCH_CROP", 256),
            msa_depth=e("AF2TPU_BENCH_MSA_DEPTH", 16),
            msa_len=e("AF2TPU_BENCH_MSA_LEN", 256),
            batch_size=e("AF2TPU_BENCH_BATCH", 1),
            min_len_filter=e("AF2TPU_BENCH_CROP", 256),
        ),
        train=TrainConfig(gradient_accumulate_every=1, warmup_steps=10),
    )
    batch = next(iter(SyntheticDataset(cfg.data, seed=0)))
    model = build_model(cfg)
    state = tiny_init_state(cfg, model, batch)
    step = make_train_step(model, mesh=None)
    dev_batch = device_put_batch(batch)
    rng = jax.random.key(0)
    compiled = step.lower(state, dev_batch, rng).compile()

    for _ in range(3):  # warmup
        rng, r = jax.random.split(rng)
        state, metrics = compiled(state, dev_batch, r)
    jax.block_until_ready(state.params)

    with jax.profiler.trace(trace_dir):
        for _ in range(n_steps):
            rng, r = jax.random.split(rng)
            state, metrics = compiled(state, dev_batch, r)
        jax.block_until_ready(metrics["loss"])


def summarize(trace_dir: str, n_steps: int, top: int = 30):
    paths = glob.glob(
        os.path.join(trace_dir, "**", "*.trace.json.gz"), recursive=True
    )
    assert paths, f"no trace found under {trace_dir}"
    path = max(paths, key=os.path.getmtime)
    with gzip.open(path, "rt") as f:
        trace = json.load(f)

    # device traces emit several lanes per device pid (XLA Modules / Steps /
    # XLA Ops); only the per-op lane is summed — the others span the same
    # wall time and would double-count it
    by_name = defaultdict(float)
    total = 0.0
    device_pids = set()
    op_lanes = set()  # (pid, tid) of "XLA Ops" thread lanes
    for ev in trace.get("traceEvents", []):
        if ev.get("ph") != "M":
            continue
        if ev.get("name") == "process_name":
            pname = ev.get("args", {}).get("name", "")
            if "TPU" in pname or "GPU" in pname or "/device:" in pname:
                device_pids.add(ev["pid"])
        elif ev.get("name") == "thread_name":
            tname = ev.get("args", {}).get("name", "")
            if "XLA Ops" in tname:
                op_lanes.add((ev["pid"], ev.get("tid")))
    if not op_lanes:
        print(
            "WARNING: no 'XLA Ops' lane in trace — summing ALL device lanes; "
            "totals include module/step spans and overcount wall time 2-3x",
            file=sys.stderr,
        )
    for ev in trace.get("traceEvents", []):
        if ev.get("ph") != "X" or ev.get("pid") not in device_pids:
            continue
        if op_lanes and (ev["pid"], ev.get("tid")) not in op_lanes:
            continue
        name = ev.get("name", "?")
        dur = float(ev.get("dur", 0.0))  # microseconds
        by_name[name] += dur
        total += dur

    print(f"\ntrace: {path}")
    print(f"device op time total: {total/1e3:.2f} ms over {n_steps} steps "
          f"({total/1e3/max(n_steps,1):.2f} ms/step)\n")
    print(f"{'us/step':>10}  {'%':>5}  op")
    for name, dur in sorted(by_name.items(), key=lambda kv: -kv[1])[:top]:
        print(f"{dur/max(n_steps,1):10.0f}  {100*dur/total:5.1f}  {name[:110]}")


if __name__ == "__main__":
    trace_dir = os.environ.get(
        "AF2TPU_TRACE_DIR",
        os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "chiprun_out", "profile",
        ),
    )
    n = int(os.environ.get("AF2TPU_PROFILE_STEPS", 3))
    run_profiled_steps(trace_dir, n)
    summarize(trace_dir, n)
