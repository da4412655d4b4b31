"""Driver of ``kind: "train_hybrid_dense_lm"`` configurations: the fifth model
(a dense hybrid decoder: every layer a Mamba-2 state-space mixer or
positionless grouped-query attention, then a gated MLP, under four scalar
multipliers and a tied head: one pipeline stage's share of the model)
through the same ``train()``.

``harness/train_lm.py``'s clockwork, step for step: ONE call ``train(cfg,
dataset=..., callbacks=[clock], init_params=...)``; steps 0..2 are the
checked steps, two warm-up steps, then the window, one step always in flight;
a ``--trace 1`` run traces two steps before the window; the counters of every
step are fetched once after the window; the program's state is released
before the reference runs. What differs:

- **the start waits on the host.** 772 M parameters are 12.35 GB of state
  (float32 weights, gradients as they are made, Adam's two moments) and the
  step's temporaries bring it to 14-15 GB of the 16.9 the compiler leaves: a
  second copy of the weights (3.09 GB) does not fit beside the step, where
  the other three language-model drivers make one on the device at step 2.
  The weights are made on the device from the seed, copied to the host, and
  handed to ``train(init_params=...)``, whose step donates them; the change
  after step 2 is read a leaf at a time against the host's copy; the
  reference gets the host's copy once the program's state is gone;
- there is no router: no calibration, no ``moe/*`` counter and no
  ``route_hist_l1_step0`` in the comparison;
- the clock keeps, a step, the scan's counters a state-space layer
  (``ssm/chunk_decay_min``, ``ssm/chunk_decay_mean``, ``ssm/dt_mean``,
  ``ssm/scan_in_kernel``) and the stream's two (``stream/rms_in``,
  ``stream/rms_out``). ``scan_fallback_layers`` joins the compared numbers:
  the (layer, step) readings of ``ssm/scan_in_kernel`` under 1 over the whole
  run, against a limit of 0, so a run in which any state-space layer's scan
  fell back to the XLA form is reported ``correct: false`` and says so on
  its line.
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
from benchmark.harness.train_lm import steps_of
from benchmark.reference import hybrid_dense_lm_model as ref_model

KIND = "train_hybrid_dense_lm"
COUNTERS = ("ssm/chunk_decay_min", "ssm/chunk_decay_mean", "ssm/dt_mean",
            "ssm/scan_in_kernel", "stream/rms_in", "stream/rms_out")
MIXER_OF = {"mamba": "M", "attention": "*"}
SCAN_LEAVES = ("A_log", "dt_bias", "D")  # a number a state-space head

model_sizes = ref_model.model_sizes  # the sizes the reference reads


def program_config(config: dict, traffic_params: dict, seed: int,
                   profile_dir=None):
    """The program's Config for this configuration file and traffic mix."""
    from alphafold2_tpu.config import (
        Config, DataConfig, HybridDenseLMConfig, ModelConfig, TrainConfig,
    )

    opt = config["optimizer"]
    return Config(
        model=ModelConfig(arch="hybrid_dense_lm"),
        hybrid=HybridDenseLMConfig(
            vocab_size=config["vocab_size"],
            hidden_size=config["hidden_size"],
            num_layers=config["num_hidden_layers"],
            layer_pattern="".join(
                MIXER_OF[kind] for kind in config["layer_types"]),
            intermediate_size=config["intermediate_size"],
            mamba_num_heads=config["mamba_n_heads"],
            mamba_head_dim=config["mamba_d_head"],
            ssm_groups=config["mamba_n_groups"],
            ssm_state_size=config["mamba_d_state"],
            conv_kernel=config["mamba_d_conv"],
            chunk_size=config["mamba_chunk_size"],
            time_step_min=config["time_step_min"],
            time_step_max=config["time_step_max"],
            time_step_floor=config["time_step_floor"],
            num_heads=config["num_attention_heads"],
            num_kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            embedding_multiplier=config["embedding_multiplier"],
            residual_multiplier=config["residual_multiplier"],
            attention_multiplier=config["attention_multiplier"],
            logits_scaling=config["logits_scaling"],
            rms_norm_eps=config["rms_norm_eps"],
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


def change_norms(params, host_start) -> dict:
    """``reference/model.py`` ``leaf_norms`` of ``params - host_start``, a
    leaf at a time: each of the host's leaves comes up alone (the largest is
    the table's 103 MB), never a second whole copy beside the state."""
    import jax
    import jax.numpy as jnp

    gap = jax.jit(lambda new, old: jnp.sqrt(jnp.sum(jnp.square(new - old))))
    out = {}
    for (path, new), old in zip(
            jax.tree.flatten_with_path(params)[0],
            jax.tree.leaves(host_start), strict=True):
        name = "/".join(  # leaf_norms' names
            str(getattr(k, "key", getattr(k, "name", k))) for k in path)
        out[name] = gap(new, old)
    return {k: float(v) for k, v in jax.device_get(out).items()}


class Clock(train.Clock):
    """``harness/train.py``'s clock with the start on the host and the
    model's counters of every step (device arrays, no fetch inside the
    window)."""

    def __init__(self, seconds: float, host_start, first: int):
        super().__init__(seconds, None, first)
        self.host_start = host_start
        self.counters = []  # of every step, by its index

    def __call__(self, i, state, metrics):
        if i:  # step i - 1, dispatched before
            self.counters.append({k: self.prev[k] for k in COUNTERS})
        if i != CHECK_STEPS - 1:
            return super().__call__(i, state, metrics)
        # the last checked step: the base would read the change against a
        # second copy of the start on the device
        self.program["losses"].append(float(metrics["loss"]))
        self.program["change_norms"] = change_norms(
            state.params, self.host_start)
        self.t_checked = time.perf_counter()
        self.prev = metrics


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
    host_start = jax.device_get(params)
    clock = Clock(seconds, host_start, window_step(trace_dir is not None))
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
    steps = len(clock.stamps)
    span = clock.stamps[-1] - clock.t0
    clock.prev = None
    return {
        "sizes": sizes, "seed31": s31, "batches": first,
        "host_start": host_start, "program": clock.program,
        "t0": clock.t0, "stamps": clock.stamps, "steps": steps,
        "window_s": span,
        "pairs_per_s": steps * config["pairs_per_step"] / span,
        "skipped": int(clock.skipped), "memory_peak_bytes": peak,
        # a number a step, its index the step's: over the state-space layers
        # the smallest and the mean decay of a whole chunk, the mean time
        # step and the smallest ``scan_in_kernel`` (1.0 only where every
        # layer's scan ran in the kernels); the stream's two root mean squares
        "counters": {
            "ssm/chunk_decay_min": [
                float(c["ssm/chunk_decay_min"].min()) for c in counters],
            "ssm/chunk_decay_mean": [
                float(c["ssm/chunk_decay_mean"].mean()) for c in counters],
            "ssm/dt_mean": [float(c["ssm/dt_mean"].mean()) for c in counters],
            "ssm/scan_in_kernel": [
                float(c["ssm/scan_in_kernel"].min()) for c in counters],
            "stream/rms_in": [float(c["stream/rms_in"]) for c in counters],
            "stream/rms_out": [float(c["stream/rms_out"]) for c in counters]},
        "scan_fallback_layers": sum(
            int((c["ssm/scan_in_kernel"] < 1.0).sum()) for c in counters),
        "window_first": clock.first,
        "marks": {**marks, "checked_steps": clock.t_checked},
    }


def reference_readings(config: dict, host_start, batches,
                       prec=ref_model.F32, fault=None):
    """The reference's readings from ``host_start`` (the host's copy: it goes
    up to the device here and ``train_steps`` consumes it)."""
    import jax

    return ref_model.train_steps(
        jax.device_put(host_start),
        [jax.numpy.asarray(b["tokens"]) for b in batches],
        model_sizes(config), config["optimizer"], prec, fault)


def training_numbers(prog: dict, ref: dict, fallback_layers: int) -> dict:
    """``correct.training_numbers``; the first gradient's worst leaf among
    the scan's own leaves alone (``SCAN_LEAVES`` of every state-space layer,
    64 numbers each: what a scan that forgets its state between chunks
    moves, where among all leaves the projections' rounding sets the worst);
    and the scan's fall-backs: how many (state-space layer, step) readings
    of ``ssm/scan_in_kernel`` were under 1 over the run, held to 0."""
    out = correct.training_numbers(prog, ref)
    out["grad_norm_worst_scan_leaf"] = correct.worst_leaf(
        prog["grad_norms"], ref["grad_norms"],
        [n for n in ref["grad_norms"] if n.rsplit("/", 1)[-1] in SCAN_LEAVES])
    out["scan_fallback_layers"] = (
        float(fallback_layers),
        "readings of ssm/scan_in_kernel under 1, over every state-space "
        "layer and step of the run")
    return out


def run(resolved: dict, seed: int, seconds: float, trace: bool,
        t_start: float, break_step=None) -> dict:
    """One run of a cell of this kind; returns what ``common.result_line``
    reads."""
    import jax

    # a program without this model fails here, at once
    from alphafold2_tpu.config import HybridDenseLMConfig  # noqa: F401

    config, cell = resolved["config"], resolved["cell"]
    if config["mesh"]["dp"] * config["mesh"]["sp"] != cell["chips"] \
            or cell["chips"] != 1:
        raise SystemExit(
            f"mesh {config['mesh']} on {cell['chips']} chip(s): this driver "
            "runs one stage's share on one chip")
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
    ref = reference_readings(config, out.pop("host_start"), out["batches"])
    reference_s = time.perf_counter() - t_ref
    numbers = training_numbers(out["program"], ref,
                               out["scan_fallback_layers"])
    compared, ok = correct.judge(numbers, config["correct"]["limits"])
    device_kind = jax.devices()[0].device_kind
    window = steps_of(out["counters"], out["window_first"])
    took = sorted(b - a for a, b in zip(
        [out["t0"], *out["stamps"]], out["stamps"]))
    print(f"the window's steps: median {took[len(took) // 2]:.4f} s, "
          f"longest {took[-1]:.4f} s; scans in the kernels: "
          f"{min(out['counters']['ssm/scan_in_kernel'])!r} (1.0: every "
          "state-space layer of every step)", file=sys.stderr)
    print("the stream's root mean square at step 0: entering "
          f"{out['counters']['stream/rms_in'][0]!r} (reference "
          f"{float(ref['stream_rms'][0])!r}), leaving "
          f"{out['counters']['stream/rms_out'][0]!r} (reference "
          f"{float(ref['stream_rms'][1])!r}); over the window, leaving: "
          f"{window['stream/rms_out'][0]!r} to {window['stream/rms_out'][-1]!r}"
          "; decay of a whole chunk: smallest "
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
