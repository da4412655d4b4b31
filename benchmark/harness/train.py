"""Driver of ``kind: "train"`` configurations.

The window drives ``alphafold2_tpu.train.loop.train(cfg, dataset=...,
callbacks=[clock])``, what ``train_pre.py`` calls: ONE call, so one compiled
step with its state serves the checked steps, the warm-up and the window.

- steps 0..2 are the checked steps: the clock reads each one's loss, the
  per-leaf norms of Adam's first moment after step 0 (the first gradient as
  Adam gets it) and of the parameters' change after step 2;
- two warm-up steps, then the window. The clock blocks on the PREVIOUS
  step's loss, so one step is always in flight and a step's stamp is when
  the device finished it. When the clock passes ``--seconds`` it ends the
  run from inside with a private exception;
- ``pairs_per_s`` = steps finished in the window x batch x crop^2 over the
  time from the window's start to the last finished step.

``train()`` takes no initial state, so for this call the driver puts its own
``tiny_init_state`` into ``train.loop``: the benchmark's weights (from the
seed, ``reference/model.py`` ``init_params``) inside the program's own
``TrainState`` and optimizer. PERF.md lists it as something the program should offer itself.

In a ``--trace 1`` run ``train.profile_dir`` / ``train.profile_steps`` trace
two steps right after the warm-up and BEFORE the window: stopping a trace
stalls the host for about two seconds, which inside the window would read as
a slower step (PR 24's first traced run: 0.91 s a step for 0.69). The window
then starts one settling step later, in the same steady state.
"""

from __future__ import annotations

import gc
import shutil
import tempfile
import time

from benchmark.harness import correct, trace_reduce, traffic
from benchmark.reference import model as ref_model

CHECK_STEPS = 3
WARM_STEPS = 2
TRACE_FIRST, TRACE_STEPS = CHECK_STEPS + WARM_STEPS, 2
ADAM_B1 = 0.9  # optax.adamw's default, which build_optimizer leaves alone


def window_step(trace: bool) -> int:
    """The index of the first step of the window: after the warm-up, and in
    a traced run after the traced steps and one more that settles the loop
    again once the trace has been written."""
    first = CHECK_STEPS + WARM_STEPS
    return first + TRACE_STEPS + 2 if trace else first


def model_sizes(config: dict) -> dict:
    """The sizes the reference and the operation count read."""
    return {k: config[k] for k in (
        "dim", "heads", "dim_head", "depth", "max_seq_len",
        "msa_tie_row_attn")}


def data_sizes(config: dict) -> dict:
    return {"crop": config["crop"], "msa_depth": config["msa_depth"],
            "msa_len": config["msa_len"],
            "batch": config["batch"] * config["mesh"]["dp"]}


def program_config(config: dict, seed: int, profile_dir=None):
    """The program's Config for this configuration file (after
    ``chip_smoke.py`` ``train_config``)."""
    from alphafold2_tpu.config import (
        Config, DataConfig, MeshConfig, ModelConfig, TrainConfig,
    )

    dp, sp = config["mesh"]["dp"], config["mesh"]["sp"]
    opt = config["optimizer"]
    return Config(
        model=ModelConfig(
            dim=config["dim"], depth=config["depth"], heads=config["heads"],
            dim_head=config["dim_head"], max_seq_len=config["max_seq_len"],
            msa_tie_row_attn=config["msa_tie_row_attn"],
            bfloat16=config["compute_dtype"] == "bfloat16",
            context_parallel="ring" if sp > 1 else None,
        ),
        mesh=MeshConfig(data_parallel=dp, seq_parallel=sp),
        data=DataConfig(
            crop_len=config["crop"], msa_depth=config["msa_depth"],
            msa_len=config["msa_len"], batch_size=config["batch"] * dp,
            min_len_filter=config["crop"],
        ),
        train=TrainConfig(
            learning_rate=opt["learning_rate"],
            gradient_accumulate_every=1, warmup_steps=opt["warmup_steps"],
            num_steps=opt["num_steps"], weight_decay=0.0, seed=seed,
            profile_dir=profile_dir,
            profile_steps=(TRACE_FIRST, TRACE_FIRST + TRACE_STEPS),
        ),
    )


class WindowClosed(Exception):
    """Raised by the clock to end ``train()`` from inside."""


class Clock:
    """The callback ``train()`` calls after it has dispatched step ``i``."""

    def __init__(self, seconds: float, start_params, first: int):
        self.seconds, self.first = seconds, first
        self.start_params = start_params
        self.program = {"losses": []}
        self.prev = None  # the last dispatched step's metrics
        self.t0 = None
        self.stamps = []  # finish time of every step of the window
        self.skipped = 0
        self.t_checked = None

    def __call__(self, i, state, metrics):
        import jax

        if i < CHECK_STEPS:
            self.program["losses"].append(float(metrics["loss"]))
            if i == 0:
                mu = _adam_mu(state.opt_state)
                self.program["grad_norms"] = {
                    k: float(v) / (1.0 - ADAM_B1) for k, v in jax.device_get(
                        _leaf_norms(mu)).items()}
            if i == CHECK_STEPS - 1:
                self.program["change_norms"] = {
                    k: float(v) for k, v in jax.device_get(_change_norms(
                        state.params, self.start_params)).items()}
                self.start_params = None
                self.t_checked = time.perf_counter()
            self.prev = metrics
            return
        jax.block_until_ready(self.prev["loss"])
        now = time.perf_counter()
        self.skipped = self.prev["skipped"]
        self.prev = metrics
        if i == self.first:
            self.t0 = now
        elif i > self.first:
            self.stamps.append(now)
            if now - self.t0 >= self.seconds:
                raise WindowClosed


def _leaf_norms(tree):
    import jax

    return jax.jit(ref_model.leaf_norms)(tree)


def _change_norms(new, old):
    import jax

    return jax.jit(lambda a, b: ref_model.leaf_norms(
        jax.tree.map(lambda x, y: x - y, a, b)))(new, old)


def _adam_mu(opt_state):
    """Adam's first moment, wherever the optimizer chain keeps it."""
    import jax

    found = [s for s in jax.tree.leaves(
        opt_state, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu")]
    if len(found) != 1:
        raise RuntimeError(f"{len(found)} Adam states in the optimizer")
    return found[0].mu


def memory_peak_bytes() -> int:
    """The fullest chip's peak. ``peak_bytes_in_use`` leaves out a program's
    temporaries on this runtime (0.77 GB beside an 8.3 GB step);
    ``peak_bytes_reserved`` has them, so the larger of the two is taken."""
    import jax

    return max(
        max((d.memory_stats() or {}).get(k, 0)
            for k in ("peak_bytes_in_use", "peak_bytes_reserved"))
        for d in jax.local_devices())


def drive_program(config: dict, traffic_params: dict, seed: int,
                  seconds: float, trace_dir=None, break_step=None) -> dict:
    """Set-up and window. ``break_step`` (tests only) wraps the jitted step to
    plant a fault underneath the timed path."""
    import jax

    from alphafold2_tpu.train import loop

    marks = {"jax_ready": time.perf_counter()}
    s31 = traffic.seed31(seed)
    sizes = model_sizes(config)
    params = ref_model.init_params(sizes, s31)
    batches = traffic.train_batches(traffic_params, data_sizes(config), s31)
    first = []

    def feed():
        for batch in batches:
            if len(first) < CHECK_STEPS:
                first.append(batch)
            yield batch

    cfg = program_config(config, s31, profile_dir=trace_dir)
    clock = Clock(seconds, params, window_step(trace_dir is not None))
    real_init, real_make = loop.tiny_init_state, loop.make_train_step
    marks["weights"] = time.perf_counter()

    def init_from_benchmark(cfg_, model, sample_batch=None):
        # a copy: the step donates its state, the reference needs the weights
        state = loop.TrainState.create(
            apply_fn=model.apply, params=jax.tree.map(jax.numpy.copy, params),
            tx=loop.build_optimizer(cfg_),
            skipped=jax.numpy.zeros((), jax.numpy.int32))
        return state.replace(step=jax.numpy.zeros((), jax.numpy.int32))

    def make_broken(*args, **kwargs):
        return break_step(real_make(*args, **kwargs))

    loop.tiny_init_state = init_from_benchmark
    if break_step is not None:
        loop.make_train_step = make_broken
    try:
        loop.train(cfg, dataset=feed(), callbacks=[clock])
        raise RuntimeError("train() returned before the window closed")
    except WindowClosed:
        pass
    finally:
        loop.tiny_init_state, loop.make_train_step = real_init, real_make
    jax.block_until_ready(clock.prev)  # the step still in flight
    peak = memory_peak_bytes()
    steps = len(clock.stamps)
    span = clock.stamps[-1] - clock.t0
    pairs = steps * data_sizes(config)["batch"] * config["crop"] ** 2
    return {
        "params": params, "batches": first, "program": clock.program,
        "t0": clock.t0, "stamps": clock.stamps, "steps": steps,
        "window_s": span, "pairs_per_s": pairs / span,
        "skipped": int(clock.skipped), "memory_peak_bytes": peak,
        "marks": {**marks, "checked_steps": clock.t_checked},
    }


def reference_readings(config: dict, params, batches, prec=ref_model.F32):
    import jax

    return ref_model.train_steps(
        params, [{k: jax.numpy.asarray(v) for k, v in b.items()
                  if k != "msa_mask"} for b in batches],
        model_sizes(config), config["optimizer"], prec)


def run(resolved: dict, seed: int, seconds: float, trace: bool,
        t_start: float, break_step=None) -> dict:
    """One run of a training cell; returns what ``common.result_line``
    reads."""
    import jax

    config, cell = resolved["config"], resolved["cell"]
    if config["mesh"]["dp"] * config["mesh"]["sp"] != cell["chips"]:
        raise SystemExit(
            f"mesh {config['mesh']} does not cover {cell['chips']} chip(s)")
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
    ref = reference_readings(config, out["params"], out["batches"])
    reference_s = time.perf_counter() - t_ref
    numbers = correct.training_numbers(out["program"], ref)
    compared, ok = correct.judge(numbers, config["correct"]["limits"])
    device_kind = jax.devices()[0].device_kind
    return {
        "correct": ok, "compared": compared, "reference_s": reference_s,
        "attempted": out["steps"], "failed": out["skipped"],
        "memory_peak_bytes": out["memory_peak_bytes"],
        "end_to_end": {"pairs_per_s": out["pairs_per_s"],
                       "setup_s": out["t0"] - t_start},
        "trace": summary,
        # what the per-layer readers read
        "kind": "train", "config": config, "chips": cell["chips"],
        "device_kind": device_kind, "peaks": resolved["peaks"],
        "steps": out["steps"], "window_s": out["window_s"],
        "stamps": [out["t0"], *out["stamps"]],
        # where set-up went: seconds from process start to each mark
        "setup_parts_s": {k: v - t_start for k, v in out["marks"].items()},
    }
