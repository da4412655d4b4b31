"""Driver of ``kind: "train_swa_lm"`` configurations: the second language
model (grouped-query attention, one global layer among window layers, a
softmax router that reads the layer's input, ReGLU experts: one chip's share
of an expert-parallel job) through the same ``train()``.

``harness/train_lm.py``'s clockwork, step for step, and its ``Clock``, its
counters and its comparison as they are: ONE call ``train(cfg, dataset=...,
callbacks=[clock], init_params=...)``; steps 0..2 are the checked steps, two
warm-up steps, then the window, one step always in flight; a ``--trace 1``
run traces two steps before the window; the routing counters of every step
are fetched once after the window; the program's state is released before
the reference runs. What differs is what the two functions at the top say:
the sizes the reference reads (``reference/swa_lm_model.py``) and the
program's ``Config`` (``model.arch`` ``swa_moe_lm``, the ``swa`` section).
``drive_program`` and ``run`` are ``train_lm``'s with those two and the
reference swapped: that file names its own, and this PR may not edit it.
"""

from __future__ import annotations

import gc
import shutil
import sys
import tempfile
import time

from benchmark.harness import correct, trace_reduce, traffic_lm
from benchmark.harness.train import (
    CHECK_STEPS, TRACE_FIRST, TRACE_STEPS, WindowClosed, memory_peak_bytes,
    window_step,
)
from benchmark.harness.train_lm import Clock, steps_of, training_numbers
from benchmark.reference import swa_lm_model as ref_model

KIND = "train_swa_lm"


def model_sizes(config: dict) -> dict:
    """The sizes the reference reads (the two layouts as tuples: the
    reference's jitted steps take them as static arguments)."""
    return {k: tuple(config[k]) if isinstance(config[k], list) else config[k]
            for k in ref_model.SIZE_KEYS}


def global_every(config: dict) -> int:
    """The period of the layer pattern: the program takes layer ``i`` for a
    global one (full causal, no positions) where ``i % period == 0`` and for
    a window layer under rotary positions otherwise, which is what the
    source's two layouts say. A file whose layouts say anything else is
    refused."""
    window, rope = config["sliding_window_layout"], config["rope_layout"]
    period = window.index(0, 1) if 0 in window[1:] else len(window)
    if window != rope or any(
            flag != int(i % period != 0) for i, flag in enumerate(window)):
        raise SystemExit(
            "sliding_window_layout and rope_layout must be one pattern, a "
            "global layer without positions then window layers with them, "
            f"repeated: got {window} and {rope}")
    return period


def program_config(config: dict, traffic_params: dict, seed: int,
                   profile_dir=None):
    """The program's Config for this configuration file and traffic mix."""
    from alphafold2_tpu.config import (
        Config, DataConfig, ModelConfig, SwaLMConfig, TrainConfig,
    )

    opt = config["optimizer"]
    return Config(
        model=ModelConfig(arch="swa_moe_lm"),
        swa=SwaLMConfig(
            vocab_size=config["vocab_size"],
            hidden_size=config["hidden_size"],
            num_layers=config["num_hidden_layers"],
            num_heads=config["num_attention_heads"],
            num_kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            sliding_window=config["sliding_window_size"],
            global_every=global_every(config),
            moe_intermediate_size=config["moe_ffn_hidden_size"],
            n_routed_experts=config["router_width"],
            num_experts_per_tok=config["moe_num_active_primary_experts"],
            rope_theta=float(config["rope_theta"]),
            rms_norm_eps=config["rms_norm_eps"],
            experts_held=config["moe_num_primary_experts"],
            first_expert=config["first_expert"],
            bfloat16=config["compute_dtype"] == "bfloat16",
        ),
        data=DataConfig(source="tokens",
                        batch_size=traffic_params["sequences"],
                        seq_len=traffic_params["seq_len"],
                        zipf_exponent=traffic_params["zipf_exponent"]),
        train=TrainConfig(
            learning_rate=opt["learning_rate"],
            gradient_accumulate_every=1, warmup_steps=opt["warmup_steps"],
            num_steps=opt["num_steps"], weight_decay=0.0, seed=seed,
            profile_dir=profile_dir,
            profile_steps=(TRACE_FIRST, TRACE_FIRST + TRACE_STEPS),
        ),
    )


def drive_program(config: dict, traffic_params: dict, seed: int,
                  seconds: float, trace_dir=None, break_step=None) -> dict:
    """Set-up and window. ``break_step`` (tests only) wraps the jitted step to
    plant a fault underneath the timed path."""
    import jax

    from alphafold2_tpu.train import loop

    tokens = traffic_params["sequences"] * traffic_params["seq_len"]
    if tokens != config["pairs_per_step"]:
        raise SystemExit(
            f"the traffic mix sends {tokens} tokens a step, the configuration"
            f" states pairs_per_step {config['pairs_per_step']}")
    marks = {"jax_ready": time.perf_counter()}
    s31 = traffic_lm.seed31(seed)
    sizes = model_sizes(config)
    batches = traffic_lm.lm_batches(traffic_params, config["vocab_size"], s31)
    first = []

    def feed():
        for batch in batches:
            if len(first) < CHECK_STEPS:
                first.append(batch)
            yield batch

    cfg = program_config(config, traffic_params, s31, profile_dir=trace_dir)
    clock = Clock(seconds, lambda: ref_model.init_params(sizes, s31),
                  window_step(trace_dir is not None))
    params = ref_model.init_params(sizes, s31)
    jax.block_until_ready(params)
    marks["weights"] = time.perf_counter()
    real_make = loop.make_train_step
    if break_step is not None:
        loop.make_train_step = lambda *a, **k: break_step(real_make(*a, **k))
    try:
        loop.train(cfg, dataset=feed(), callbacks=[clock], init_params=params)
        raise RuntimeError("train() returned before the window closed")
    except WindowClosed:
        pass
    finally:
        loop.make_train_step = real_make
        del params
    jax.block_until_ready(clock.prev)  # the step still in flight
    peak = memory_peak_bytes()
    counters = jax.device_get(clock.counters)
    dropped = sum(int(c["moe/dropped"].sum()) for c in counters)
    if dropped:
        raise RuntimeError(f"{dropped} assignments to held experts dropped")
    steps = len(clock.stamps)
    span = clock.stamps[-1] - clock.t0
    clock.prev = None
    return {
        "sizes": sizes, "seed31": s31, "batches": first,
        "program": clock.program,
        "t0": clock.t0, "stamps": clock.stamps, "steps": steps,
        "window_s": span,
        "pairs_per_s": steps * config["pairs_per_step"] / span,
        "skipped": int(clock.skipped), "memory_peak_bytes": peak,
        # a number a step, its index the step's: the mean over the layers of
        # the largest held expert's rows over the mean, and the rows the
        # held experts got, summed over the layers
        "counters": {
            "moe/load_max_over_mean": [
                float(c["moe/load_max_over_mean"].mean()) for c in counters],
            "moe/assignments_here": [
                int(c["moe/assignments_here"].sum()) for c in counters]},
        "window_first": clock.first,
        "marks": {**marks, "checked_steps": clock.t_checked},
    }


def reference_readings(config: dict, seed31: int, batches,
                       prec=ref_model.F32, fault=None):
    import jax

    sizes = model_sizes(config)
    return ref_model.train_steps(
        ref_model.init_params(sizes, seed31),
        [jax.numpy.asarray(b["tokens"]) for b in batches],
        sizes, config["optimizer"], prec, fault)


def run(resolved: dict, seed: int, seconds: float, trace: bool,
        t_start: float, break_step=None) -> dict:
    """One run of a cell of this kind; returns what ``common.result_line``
    reads."""
    import jax

    # a program without this model fails here, at once
    from alphafold2_tpu.config import SwaLMConfig  # noqa: F401

    config, cell = resolved["config"], resolved["cell"]
    if config["mesh"]["dp"] * config["mesh"]["sp"] != cell["chips"] \
            or cell["chips"] != 1:
        raise SystemExit(
            f"mesh {config['mesh']} on {cell['chips']} chip(s): this driver "
            "runs one chip's share on one chip")
    trace_dir = tempfile.mkdtemp(prefix="af2bench_trace_") if trace else None
    try:
        out = drive_program(config, resolved["traffic"], seed, seconds,
                            trace_dir, break_step=break_step)
        summary = trace_reduce.summarize_dir(trace_dir) if trace else None
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference_readings(config, out["seed31"], out["batches"])
    reference_s = time.perf_counter() - t_ref
    numbers = training_numbers(out["program"], ref)
    compared, ok = correct.judge(numbers, config["correct"]["limits"])
    device_kind = jax.devices()[0].device_kind
    window = steps_of(out["counters"], out["window_first"])
    rows = out["counters"]["moe/assignments_here"]
    print(f"routed rows a step: step 0 {rows[0]}, the window's first "
          f"{window['moe/assignments_here'][0]} and last {rows[-1]}",
          file=sys.stderr)
    return {
        "correct": ok, "compared": compared, "reference_s": reference_s,
        "attempted": out["steps"], "failed": out["skipped"],
        "memory_peak_bytes": out["memory_peak_bytes"],
        "end_to_end": {"pairs_per_s": out["pairs_per_s"],
                       "setup_s": out["t0"] - t_start},
        "trace": summary,
        # what the per-layer readers read
        "kind": KIND, "config": config, "traffic": resolved["traffic"],
        "chips": cell["chips"],
        "device_kind": device_kind, "peaks": resolved["peaks"],
        "steps": out["steps"], "window_s": out["window_s"],
        "stamps": [out["t0"], *out["stamps"]],
        # the program's counters, a number a step: of the window's steps,
        # and of the steps a traced run traced before it
        "counters": window,
        "traced_counters": steps_of(
            out["counters"], TRACE_FIRST, out["window_first"])
        if trace else None,
        # where set-up went: seconds from process start to each mark
        "setup_parts_s": {k: v - t_start for k, v in out["marks"].items()},
    }
