"""Driver of ``kind: "train_lm"`` configurations: a language model (latent
attention, routed experts: one chip's share of an expert-parallel job)
through the same ``train()`` the flagship's driver drives.

As ``harness/train.py``, whose clockwork it follows step for step: ONE call
``train(cfg, dataset=..., callbacks=[clock], init_params=...)``; steps 0..2
are the checked steps, two warm-up steps, then the window, one step always in
flight; a ``--trace 1`` run traces two steps before the window. What differs:

- the weights go in through ``train()``'s own ``init_params`` (the program
  offers that since this driver exists; nothing of ``train.loop`` is
  replaced). The step donates them, so whoever needs the start again (the
  change's norms after step 2, the reference after the window) makes it anew
  from the seed: a third copy of 2.3 GB held through the window would take
  the room the activations need;
- ``pairs_per_s`` = steps finished in the window x ``pairs_per_step`` over
  the time to the last finished step. One pair is one trained token here
  (the configuration's file says so);
- ``route_hist_l1_step0`` joins the compared numbers: the program's counts of
  assignments over all the router's experts at step 0, carried in the step's
  metrics, against the reference's;
- the routing counters of every step are kept as device arrays and fetched
  once after the window (no fetch inside it): ``moe/load_max_over_mean`` for
  the reader of ``expert_load_max_over_mean``, and ``moe/assignments_here``,
  the rows the held experts were sent, for the readers that count the routed
  work (MFU over the window's steps, the grouped product's roofline over the
  traced steps). A dropped assignment is an error.

The program's state is released before the reference runs: the reference's
three steps hold 9.2 GB themselves.
"""

from __future__ import annotations

import gc
import shutil
import sys
import tempfile
import time

from benchmark.harness import correct, trace_reduce, traffic_lm, train
from benchmark.harness.train import (
    CHECK_STEPS, TRACE_FIRST, TRACE_STEPS, WindowClosed, memory_peak_bytes,
    window_step,
)
from benchmark.reference import lm_model as ref_model


COUNTERS = ("moe/load_max_over_mean", "moe/assignments_here", "moe/dropped")


def model_sizes(config: dict) -> dict:
    """The sizes the reference reads."""
    return {k: config[k] for k in ref_model.SIZE_KEYS}


def program_config(config: dict, traffic_params: dict, seed: int,
                   profile_dir=None):
    """The program's Config for this configuration file and traffic mix."""
    from alphafold2_tpu.config import (
        Config, DataConfig, LMConfig, ModelConfig, TrainConfig,
    )

    opt = config["optimizer"]
    return Config(
        model=ModelConfig(arch="mla_moe_lm"),
        lm=LMConfig(
            vocab_size=config["vocab_size"],
            hidden_size=config["hidden_size"],
            num_layers=config["num_hidden_layers"],
            first_k_dense=config["first_k_dense_replace"],
            num_heads=config["num_attention_heads"],
            qk_nope_head_dim=config["qk_nope_head_dim"],
            qk_rope_head_dim=config["qk_rope_head_dim"],
            v_head_dim=config["v_head_dim"],
            kv_lora_rank=config["kv_lora_rank"],
            intermediate_size=config["intermediate_size"],
            moe_intermediate_size=config["moe_intermediate_size"],
            n_routed_experts=config["router_width"],
            n_shared_experts=config["n_shared_experts"],
            num_experts_per_tok=config["num_experts_per_tok"],
            routed_scaling_factor=config["routed_scaling_factor"],
            rope_theta=float(config["rope_theta"]),
            rms_norm_eps=config["rms_norm_eps"],
            experts_held=config["n_routed_experts"],
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


class Clock(train.Clock):
    """``harness/train.py``'s clock, with the routing counters and a start
    that is made anew when the change is read."""

    def __init__(self, seconds: float, make_start, first: int):
        super().__init__(seconds, None, first)
        self.make_start = make_start
        self.counters = []  # routing counters of every step, by its index

    def __call__(self, i, state, metrics):
        import jax

        if i == 0:
            self.program["route_hist"] = jax.device_get(metrics["moe/hist"])
        if i == CHECK_STEPS - 1:
            self.start_params = self.make_start()
        if i:  # step i - 1, dispatched before: device arrays, no fetch
            self.counters.append({k: self.prev[k] for k in COUNTERS})
        super().__call__(i, state, metrics)


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
        # a number a step, its index the step's: the mean over the expert
        # layers of the largest held expert's rows over the mean, and the
        # rows the held experts got, summed over the expert layers
        "counters": {
            "moe/load_max_over_mean": [
                float(c["moe/load_max_over_mean"].mean()) for c in counters],
            "moe/assignments_here": [
                int(c["moe/assignments_here"].sum()) for c in counters]},
        "window_first": clock.first,
        "marks": {**marks, "checked_steps": clock.t_checked},
    }


def steps_of(counters: dict, first: int, end=None) -> dict:
    return {k: v[first:end] for k, v in counters.items()}


def reference_readings(config: dict, seed31: int, batches,
                       prec=ref_model.F32, fault=None):
    import jax

    sizes = model_sizes(config)
    return ref_model.train_steps(
        ref_model.init_params(sizes, seed31),
        [jax.numpy.asarray(b["tokens"]) for b in batches],
        sizes, config["optimizer"], prec, fault)


def training_numbers(prog: dict, ref: dict) -> dict:
    """``correct.training_numbers`` and the routing: the L1 distance between
    the two sides' assignment counts at step 0 over every expert of every
    expert layer, over the total of assignments."""
    import numpy as np

    out = correct.training_numbers(prog, ref)
    p = np.asarray(prog["route_hist"], np.int64)
    r = np.asarray(ref["route_hist"], np.int64)
    if p.shape != r.shape:
        raise ValueError(f"route counts {p.shape} against {r.shape}")
    out["route_hist_l1_step0"] = (
        float(np.abs(p - r).sum()) / float(r.sum()),
        f"program total {int(p.sum())} reference total {int(r.sum())}")
    return out


def run(resolved: dict, seed: int, seconds: float, trace: bool,
        t_start: float, break_step=None) -> dict:
    """One run of a language-model training cell; returns what
    ``common.result_line`` reads."""
    import jax

    # a program without this model fails here, at once
    from alphafold2_tpu.config import LMConfig  # noqa: F401

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
        "kind": "train_lm", "config": config, "traffic": resolved["traffic"],
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
