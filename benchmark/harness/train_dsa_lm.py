"""Driver of ``kind: "train_dsa_lm"`` configurations: the sixth model
(grouped-query attention in which every query reads the 2,048 keys a learned
indexer picks, the indexer's own loss, softmax-routed SwiGLU experts: one
chip's share of an expert-parallel job) through the same ``train()``.

``harness/train_lm.py``'s clockwork, step for step: ONE call ``train(cfg,
dataset=..., callbacks=[clock], init_params=...)``; steps 0..2 are the
checked steps, two warm-up steps, then the window, one step always in
flight; a ``--trace 1`` run traces two steps before the window; the counters
of every step are fetched once after the window; the program's state is
released before the reference runs. The start waits on the host and the
change after step 2 is read a leaf at a time, as
``harness/train_hybrid_dense_lm.py`` does it (its ``change_norms``). What
differs:

- the clock keeps, a step, the routing counters and the sparse attention's
  (``dsa/keys_selected_mean``, ``dsa/indexer_kl``, ``dsa/core_in_kernel``,
  ``dsa/tiles_visited_pct``), a number a layer, and fetches step 0's
  ``dsa/selection_sample`` once, as it fetches ``moe/hist``;
- ``select_missed_step0`` joins the compared numbers: of the (query, key)
  pairs the timed step selected at step 0 in its sample of the packed
  selection (``ops/dsa.py`` ``selection_sample``: 16 word rows of 32
  queries by 256 keys a layer at 16,384 positions), over the layers, the
  share the reference did not select at the same words;
- ``core_fallback_layers`` joins them too: the (layer, step) readings of
  ``dsa/core_in_kernel`` under 1 over the run, held to 0, so a run in
  which a layer's core left the Pallas kernels is reported ``correct:
  false``.
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
from benchmark.harness.train_hybrid_dense_lm import change_norms
from benchmark.harness.train_lm import steps_of
from benchmark.harness.train_lm import training_numbers as routed_numbers
from benchmark.reference import dsa_lm_model as ref_model

KIND = "train_dsa_lm"
COUNTERS = ("moe/load_max_over_mean", "moe/assignments_here", "moe/dropped",
            "dsa/keys_selected_mean", "dsa/indexer_kl", "dsa/core_in_kernel",
            "dsa/tiles_visited_pct")

model_sizes = ref_model.model_sizes  # the sizes the reference reads


def program_config(config: dict, traffic_params: dict, seed: int,
                   profile_dir=None):
    """The program's Config for this configuration file and traffic mix."""
    from alphafold2_tpu.config import (
        Config, DataConfig, DsaLMConfig, ModelConfig, TrainConfig,
    )

    opt, sa = config["optimizer"], config["sa_config"]
    sizes = model_sizes(config)
    return Config(
        model=ModelConfig(arch="dsa_moe_lm"),
        dsa=DsaLMConfig(
            vocab_size=config["vocab_size"],
            hidden_size=config["hidden_size"],
            num_layers=config["num_hidden_layers"],
            num_heads=config["num_attention_heads"],
            num_kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            index_num_heads=sizes["index_num_heads"],
            index_head_dim=sizes["index_head_dim"],
            index_topk=sizes["index_topk"],
            index_chunk=sa["q_chunk_size"],
            index_loss_weight=config["index_loss_weight"],
            moe_intermediate_size=config["moe_intermediate_size"],
            n_routed_experts=config["router_width"],
            num_experts_per_tok=config["num_experts_per_tok"],
            rope_theta=float(config["rope_theta"]),
            rms_norm_eps=config["rms_norm_eps"],
            experts_held=config["num_experts"],
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
    """``harness/train.py``'s clock with the start on the host, step 0's
    routing counts and sample of its selection, and the model's counters of
    every step (device arrays, no fetch inside the window)."""

    def __init__(self, seconds: float, host_start, first: int):
        super().__init__(seconds, None, first)
        self.host_start = host_start
        self.counters = []  # of every step, by its index

    def __call__(self, i, state, metrics):
        import jax

        if i == 0:
            self.program["route_hist"] = jax.device_get(metrics["moe/hist"])
            self.program["selection"] = jax.device_get(
                metrics["dsa/selection_sample"])
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
    dropped = sum(int(c["moe/dropped"].sum()) for c in counters)
    if dropped:
        raise RuntimeError(f"{dropped} assignments to held experts dropped")
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
        # a number a step, its index the step's: over the layers, the mean
        # of the largest held expert's rows over the mean, the rows the held
        # experts got summed, the mean keys a query read, the indexer's loss
        # summed, the smallest core_in_kernel (1.0 only where every layer's
        # core ran in the Pallas kernels) and the mean share of causal tiles
        # the core visited
        "counters": {
            "moe/load_max_over_mean": [
                float(c["moe/load_max_over_mean"].mean()) for c in counters],
            "moe/assignments_here": [
                int(c["moe/assignments_here"].sum()) for c in counters],
            "dsa/keys_selected_mean": [
                float(c["dsa/keys_selected_mean"].mean()) for c in counters],
            "dsa/indexer_kl": [
                float(c["dsa/indexer_kl"].sum()) for c in counters],
            "dsa/core_in_kernel": [
                float(c["dsa/core_in_kernel"].min()) for c in counters],
            "dsa/tiles_visited_pct": [
                float(c["dsa/tiles_visited_pct"].mean()) for c in counters]},
        "core_fallback_layers": sum(
            int((c["dsa/core_in_kernel"] < 1.0).sum()) for c in counters),
        "window_first": clock.first,
        "marks": {**marks, "checked_steps": clock.t_checked},
    }


def sampled(packed):
    """Packed selections (layers, B, S / 32, S) at the words a step carries
    out (``ops/dsa.py`` ``sample_at``)."""
    import numpy as np

    from alphafold2_tpu.ops import dsa

    rows, keys = dsa.sample_at(*packed.shape[-2:])
    return np.asarray(packed)[..., rows, :][..., keys]


def overlap_numbers(prog_packed, ref_packed) -> dict:
    """``select_missed_step0``: of the program's selected (query, key) pairs
    over the layers, the share the reference did not select (bit counts of
    the two packed samples, ANDed)."""
    import numpy as np

    prog = np.asarray(prog_packed).view(np.uint32)
    ref = np.asarray(ref_packed).view(np.uint32)
    if prog.shape != ref.shape:
        raise ValueError(f"selections {prog.shape} against {ref.shape}")
    picked = int(np.bitwise_count(prog).sum(dtype=np.int64))
    both = int(np.bitwise_count(prog & ref).sum(dtype=np.int64))
    return {"select_missed_step0": (
        1.0 - both / picked,
        f"the step's sample of its selection holds {picked} pairs, the "
        f"reference selects {both} of them")}


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
    """``harness/train_lm.py``'s numbers (losses, the worst leaves, the
    routing at step 0), the selection's (``prog["selection"]``, a sample,
    against the same words of ``ref["selection"]``) and the core's
    fall-backs, held to 0."""
    out = routed_numbers(prog, ref)
    out.update(overlap_numbers(prog["selection"], sampled(ref["selection"])))
    out["core_fallback_layers"] = (
        float(fallback_layers),
        "readings of dsa/core_in_kernel under 1, over every layer and step "
        "of the run")
    return out


def run(resolved: dict, seed: int, seconds: float, trace: bool,
        t_start: float, break_step=None) -> dict:
    """One run of a cell of this kind; returns what ``common.result_line``
    reads."""
    import jax

    # a program without this model fails here, at once
    from alphafold2_tpu.config import DsaLMConfig  # noqa: F401

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
    host_start = out.pop("host_start")
    program = out["program"]
    t_ref = time.perf_counter()
    ref = reference_readings(config, host_start, out["batches"])
    reference_s = time.perf_counter() - t_ref
    numbers = training_numbers(program, ref, out["core_fallback_layers"])
    compared, ok = correct.judge(numbers, config["correct"]["limits"])
    device_kind = jax.devices()[0].device_kind
    window = steps_of(out["counters"], out["window_first"])
    rows = out["counters"]["moe/assignments_here"]
    print(f"routed rows a step: step 0 {rows[0]}, the window's first "
          f"{window['moe/assignments_here'][0]} and last {rows[-1]}; keys a "
          f"query {window['dsa/keys_selected_mean'][-1]!r}, causal tiles "
          f"visited {window['dsa/tiles_visited_pct'][-1]!r}%, cores in the "
          f"kernels {min(out['counters']['dsa/core_in_kernel'])!r}; the "
          "indexer's loss over the layers at step 0 "
          f"{out['counters']['dsa/indexer_kl'][0]!r} (reference "
          f"{float(sum(ref['indexer_kl']))!r}), over the window "
          f"{window['dsa/indexer_kl'][0]!r} to {window['dsa/indexer_kl'][-1]!r}",
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
