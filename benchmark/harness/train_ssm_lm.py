"""Driver of ``kind: "train_ssm_lm"`` configurations: the third language
model (a layer is one mixer: Mamba-2 state-space, ungated relu^2 experts
beside a shared one, or positionless grouped-query attention: one chip's
share of an expert-parallel job) through the same ``train()``.

``harness/train_lm.py``'s clockwork, step for step, as
``harness/train_swa_lm.py`` follows it, with that file's counters and
comparison as they are: ONE call ``train(cfg, dataset=..., callbacks=[clock],
init_params=...)``; steps 0..2 are the checked steps, two warm-up steps, then
the window, one step always in flight; a ``--trace 1`` run traces two steps
before the window; the counters of every step are fetched once after the
window; the program's state is released before the reference runs. What
differs is what the two functions at the top say (the sizes the reference
reads, ``reference/ssm_lm_model.py``, and the program's ``Config``:
``model.arch`` ``ssm_moe_lm``, the ``ssm`` section), and that the clock also
keeps the scan's counters a state-space layer (``ssm/chunk_decay_min``,
``ssm/chunk_decay_mean``, ``ssm/dt_mean``). ``drive_program`` and ``run`` are
``train_lm``'s with those swapped: that file names its own, and this PR may
not edit it.

One more thing differs, the start. The router's correction bias is part of
the weights made from the seed: ``balanced_router_bias`` sets it in set-up by
the family's own rule (after every batch, ``b_e`` goes up by the speed where
expert ``e`` got fewer assignments than the mean and down where it got more)
over ``router_bias_calibration.steps`` batches of the run's own stream taken
from far behind what the window reaches, forward passes only, the speed
falling by ``decay`` a batch. Then it is frozen: the program and the
reference both start from it. At a bias of 0 the rows a seed's routers send
the 8 held experts differ by a tenth between seeds (a component common to
all positions favours some experts for every token), the step's time follows
them, and ``pairs_per_s`` spread by 1.0-1.9% between seeds against a bound of
1%; under the calibrated bias every seed's held experts get a balanced
router's rows, as a deployment's do.
"""

from __future__ import annotations

import gc
import itertools
import shutil
import sys
import tempfile
import time

from benchmark.harness import correct, trace_reduce, traffic_lm, train_lm
from benchmark.harness.train import (
    CHECK_STEPS, TRACE_FIRST, TRACE_STEPS, WindowClosed, memory_peak_bytes,
    window_step,
)
from benchmark.harness.train_lm import steps_of, training_numbers
from benchmark.reference import ssm_lm_model as ref_model

KIND = "train_ssm_lm"
SCAN_COUNTERS = ("ssm/chunk_decay_min", "ssm/chunk_decay_mean",
                 "ssm/dt_mean")


def model_sizes(config: dict) -> dict:
    """The sizes the reference reads."""
    return {k: config[k] for k in ref_model.SIZE_KEYS}


def program_config(config: dict, traffic_params: dict, seed: int,
                   profile_dir=None):
    """The program's Config for this configuration file and traffic mix."""
    from alphafold2_tpu.config import (
        Config, DataConfig, ModelConfig, SsmLMConfig, TrainConfig,
    )

    opt = config["optimizer"]
    return Config(
        model=ModelConfig(arch="ssm_moe_lm"),
        ssm=SsmLMConfig(
            vocab_size=config["vocab_size"],
            hidden_size=config["hidden_size"],
            num_layers=config["num_hidden_layers"],
            layer_pattern=config["hybrid_override_pattern"],
            mamba_num_heads=config["mamba_num_heads"],
            mamba_head_dim=config["mamba_head_dim"],
            ssm_groups=config["n_groups"],
            ssm_state_size=config["ssm_state_size"],
            conv_kernel=config["conv_kernel"],
            chunk_size=config["chunk_size"],
            time_step_min=config["time_step_min"],
            time_step_max=config["time_step_max"],
            time_step_floor=config["time_step_floor"],
            num_heads=config["num_attention_heads"],
            num_kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            moe_intermediate_size=config["moe_intermediate_size"],
            moe_shared_expert_intermediate_size=config[
                "moe_shared_expert_intermediate_size"],
            n_routed_experts=config["router_width"],
            num_experts_per_tok=config["num_experts_per_tok"],
            routed_scaling_factor=config["routed_scaling_factor"],
            rms_norm_eps=config["layer_norm_epsilon"],
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


def expert_layers(config: dict) -> list:
    """The names of the layers whose mixer is the experts'."""
    kinds = config["hybrid_override_pattern"][:config["num_hidden_layers"]]
    return [f"layer_{i}" for i, kind in enumerate(kinds) if kind == "E"]


def with_router_bias(params: dict, config: dict, bias) -> dict:
    """``params`` with row ``i`` of ``bias`` as the correction bias of the
    ``i``-th expert layer (``None``: as they are)."""
    if bias is None:
        return params
    import jax.numpy as jnp

    tree = dict(params["params"])
    for name, row in zip(expert_layers(config), bias, strict=True):
        tree[name] = {**tree[name], "moe": {
            **tree[name]["moe"], "router_bias": jnp.asarray(row)}}
    return {"params": tree}


def balanced_router_bias(config: dict, traffic_params: dict, seed31: int,
                         params: dict):
    """The correction bias of every expert layer, (expert layers,
    ``router_width``) float32 on the host: from 0, one update of the
    family's rule a batch, ``b += speed * sign(mean load - load)`` over all
    the router's experts in every expert layer at once, through the
    program's forward at ``params``. The batches are the seed's own stream
    (the same hot ids) from ``first_batch`` on, which no window reaches."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from alphafold2_tpu.train import loop

    cal = config["router_bias_calibration"]
    model = loop.build_task(
        program_config(config, traffic_params, seed31)).model
    stream = traffic_lm.lm_batches(
        traffic_params, config["vocab_size"], seed31)
    tokens = np.stack([b["tokens"] for b in itertools.islice(
        stream, cal["first_batch"], cal["first_batch"] + cal["steps"])])
    speeds = (cal["speed"] * cal["decay"] ** np.arange(cal["steps"])).astype(
        np.float32)

    @jax.jit
    def calibrate(params, tokens, speeds):
        def update(bias, xs):
            batch, speed = xs
            load = model.apply(with_router_bias(params, config, bias),
                               batch)["moe"]["hist"].astype(jnp.float32)
            mean = load.mean(axis=-1, keepdims=True)
            return bias + speed * jnp.sign(mean - load), None

        start = jnp.zeros(
            (len(expert_layers(config)), config["router_width"]),
            jnp.float32)
        return jax.lax.scan(update, start, (tokens, speeds))[0]

    return np.asarray(calibrate(params, tokens, speeds))


def start_params(config: dict, seed31: int, router_bias) -> dict:
    """The weights both sides start from: the reference's, from the seed,
    under the calibrated correction bias."""
    return with_router_bias(
        ref_model.init_params(model_sizes(config), seed31), config,
        router_bias)


class Clock(train_lm.Clock):
    """``train_lm``'s clock, and the scan's counters of every step beside
    the routing's (device arrays, no fetch inside the window)."""

    def __init__(self, *args):
        super().__init__(*args)
        self.scan_counters = []

    def __call__(self, i, state, metrics):
        if i:  # step i - 1, dispatched before
            self.scan_counters.append(
                {k: self.prev[k] for k in SCAN_COUNTERS})
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
    params = ref_model.init_params(sizes, s31)
    router_bias = balanced_router_bias(config, traffic_params, s31, params)
    marks["router_bias"] = time.perf_counter()
    clock = Clock(seconds, lambda: start_params(config, s31, router_bias),
                  window_step(trace_dir is not None))
    params = with_router_bias(params, config, router_bias)
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
    counters, scanned = jax.device_get((clock.counters, clock.scan_counters))
    dropped = sum(int(c["moe/dropped"].sum()) for c in counters)
    if dropped:
        raise RuntimeError(f"{dropped} assignments to held experts dropped")
    steps = len(clock.stamps)
    span = clock.stamps[-1] - clock.t0
    clock.prev = None
    return {
        "sizes": sizes, "seed31": s31, "batches": first,
        "router_bias": router_bias,
        "program": clock.program,
        "t0": clock.t0, "stamps": clock.stamps, "steps": steps,
        "window_s": span,
        "pairs_per_s": steps * config["pairs_per_step"] / span,
        "skipped": int(clock.skipped), "memory_peak_bytes": peak,
        # a number a step, its index the step's: the mean over the expert
        # layers of the largest held expert's rows over the mean, the rows
        # the held experts got summed over the expert layers, and over the
        # state-space layers the smallest and the mean decay of a whole
        # chunk and the mean time step
        "counters": {
            "moe/load_max_over_mean": [
                float(c["moe/load_max_over_mean"].mean()) for c in counters],
            "moe/assignments_here": [
                int(c["moe/assignments_here"].sum()) for c in counters],
            "ssm/chunk_decay_min": [
                float(c["ssm/chunk_decay_min"].min()) for c in scanned],
            "ssm/chunk_decay_mean": [
                float(c["ssm/chunk_decay_mean"].mean()) for c in scanned],
            "ssm/dt_mean": [float(c["ssm/dt_mean"].mean()) for c in scanned]},
        "window_first": clock.first,
        "marks": {**marks, "checked_steps": clock.t_checked},
    }


def reference_readings(config: dict, seed31: int, batches,
                       prec=ref_model.F32, fault=None, router_bias=None):
    """``router_bias``: what ``balanced_router_bias`` gave the program
    (``None``: the reference's own start, a bias of 0)."""
    import jax

    return ref_model.train_steps(
        start_params(config, seed31, router_bias),
        [jax.numpy.asarray(b["tokens"]) for b in batches],
        model_sizes(config), config["optimizer"], prec, fault)


def run(resolved: dict, seed: int, seconds: float, trace: bool,
        t_start: float, break_step=None) -> dict:
    """One run of a cell of this kind; returns what ``common.result_line``
    reads."""
    import jax

    # a program without this model fails here, at once
    from alphafold2_tpu.config import SsmLMConfig  # noqa: F401

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
    ref = reference_readings(config, out["seed31"], out["batches"],
                             router_bias=out["router_bias"])
    reference_s = time.perf_counter() - t_ref
    numbers = training_numbers(out["program"], ref)
    compared, ok = correct.judge(numbers, config["correct"]["limits"])
    device_kind = jax.devices()[0].device_kind
    window = steps_of(out["counters"], out["window_first"])
    rows = out["counters"]["moe/assignments_here"]
    took = sorted(b - a for a, b in zip(
        [out["t0"], *out["stamps"]], out["stamps"]))
    print(f"the window's steps: median {took[len(took) // 2]:.4f} s, "
          f"longest {took[-1]:.4f} s; largest held expert's rows over the "
          f"mean {max(window['moe/load_max_over_mean']):.3f}",
          file=sys.stderr)
    print(f"routed rows a step: step 0 {rows[0]}, the window's first "
          f"{window['moe/assignments_here'][0]} and last {rows[-1]}; "
          "decay of a whole chunk over the window: smallest "
          f"{min(window['ssm/chunk_decay_min'])!r}, mean "
          f"{window['ssm/chunk_decay_mean'][-1]!r}; mean time step "
          f"{window['ssm/dt_mean'][-1]!r}", file=sys.stderr)
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
