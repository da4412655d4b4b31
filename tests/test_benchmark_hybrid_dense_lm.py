"""The benchmark's fifth model's cell, ``train_granite4_h_micro_pp4_seq8k``
(kind ``train_hybrid_dense_lm``), on the CPU: the files it resolves to, the
published widths its configuration keeps, the counts from shapes by hand,
the driver end to end at a tiny size (the scan's kernels interpreted), the
control and the planted faults against the limits, and the new readers on a
hand-written record. No time, rate or share here is a device number.
"""

import itertools
import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, ROOT)

from benchmark.harness import common, correct, traffic_lm  # noqa: E402
from benchmark.harness import ops_from_shapes_hybrid_dense_lm as ops  # noqa: E402

CELL = "train_granite4_h_micro_pp4_seq8k"
CONFIG = "granite4_h_micro_train_pp4"
KIND = "train_hybrid_dense_lm"
EARLIER_CELLS = [
    "train_flagship", "train_mesh_dp2sp2", "train_kanana2_ep8_seq8k",
    "train_smallthinker_ep8_seq16k", "train_nemotron3_nano_ep16_seq8k"]
# the catalog row's ``config`` (model-configs guide, granite-4.0-h-micro),
# every key: the file holds each under the same name, two of them reduced
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192,
    "layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 8192,
    "tie_word_embeddings": True, "vocab_size": 100352,
}
REDUCED = {"num_hidden_layers": 10, "vocab_size": 12544}
TINY = dict(
    vocab_size=64, hidden_size=64, intermediate_size=96, mamba_n_heads=4,
    mamba_d_head=16, mamba_d_state=8, num_attention_heads=4,
    num_key_value_heads=1, head_dim=16, pairs_per_step=80,
    # five chunks a sequence, and time steps short enough that a state
    # outlives its chunk: dropping it has to show at this size too
    mamba_chunk_size=8, time_step_max=0.01,
    # float32 compute: at hidden 64 bfloat16's own noise is larger than what
    # the weaker faults move
    compute_dtype="float32",
)
# at the tiny size in float32 the program reads 1e-7 on a loss and under
# 1e-3 on a leaf; the control is the reference in bfloat16
TINY_LIMITS = {"loss_step0": 2e-5, "loss_step1": 2e-5, "loss_step2": 2e-5,
               "grad_norm_worst_leaf": 0.005, "change_norm_worst_leaf": 0.01,
               "grad_norm_worst_scan_leaf": 0.005, "scan_fallback_layers": 0}
SEED = 2_500_000_011


def manifest():
    return common.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def the_config():
    return common.load_json(os.path.join(BENCH, "configs", CONFIG + ".json"))


def tiny_resolved():
    resolved = common.resolve(CELL)
    resolved["config"].update(TINY)
    resolved["config"]["correct"]["limits"] = dict(TINY_LIMITS)
    resolved["traffic"].update(sequences=2, seq_len=40)
    return resolved


@pytest.fixture(scope="module")
def tiny_run():
    """The driver end to end, the scan through the interpreted kernels (on
    the CPU ``scan_kernel_takes`` nothing, and a run whose scans fell back is
    not correct: the next test)."""
    from alphafold2_tpu.ops import ssm
    from benchmark.harness import train_hybrid_dense_lm as driver

    resolved = tiny_resolved()
    with pytest.MonkeyPatch.context() as patched:
        patched.setattr(ssm, "scan_kernel_takes", lambda *shapes: True)
        run = driver.run(resolved, SEED, 0.5, False, time.perf_counter())
    return resolved, run


def test_the_cell_resolves_to_files_of_its_own_kind():
    resolved = common.resolve(CELL)
    assert resolved["config"]["kind"] == KIND
    assert resolved["cell"]["chips"] == 1
    assert resolved["cell"]["traffic"] == "lm_zipf_seq8k_x1"
    assert resolved["traffic"] == {
        **resolved["traffic"], "kind": "lm_zipf", "sequences": 1,
        "seq_len": 8192, "zipf_exponent": 1.0}
    names = {m["name"] for m in resolved["per_layer"]}
    assert {m for m in names if m.endswith("." + KIND)} == {
        f"{stem}.{KIND}" for stem in (
            "mfu_pct", "ssd_scan_roofline_pct", "attn_core_roofline_pct",
            "lm_rest_device_ms", "unscoped_device_pct", "ssm_scan_in_kernel")}
    # the other models' files that read this record unchanged
    assert names - {m for m in names if m.endswith("." + KIND)} == {
        "step_ms_p50.train", "device_idle_pct.train", "step_device_ms.train",
        "idle_attributed_pct.train", "setup_lower_s.train",
        "setup_compile_s.train", "compiles_after_warmup.train",
        "inferred_scope_device_pct.train",
        "ssm_scan_device_ms.train_ssm_lm", "ssm_other_device_ms.train_ssm_lm",
        "attn_global_device_ms.train_swa_lm",
        "dense_shared_ffn_device_ms.train_lm",
        "embed_head_loss_device_ms.train_lm", "update_device_ms.train_lm"}
    assert [m["name"] for m in resolved["end_to_end"]] == [
        "pairs_per_s", "setup_s"]
    # every reader and metric file the cell names is there and answers
    for spec in resolved["per_layer"]:
        assert os.path.exists(
            os.path.join(BENCH, "readers", spec["reader"] + ".py"))
        assert callable(__import__(
            f"benchmark.readers.{spec['reader']}", fromlist=["read"]).read)
    assert os.path.exists(os.path.join(BENCH, "harness", KIND + ".py"))


def test_the_manifest_gained_one_configuration_and_one_cell():
    m = manifest()  # (a later PR's cells and configurations come after)
    assert [w["name"] for w in m["workloads"]][:6] == EARLIER_CELLS + [CELL]
    assert m["configs"][5]["name"] == CONFIG
    assert m["configs"][5]["reduced"] == sorted(REDUCED)
    assert m["configs"][5]["source"] == the_config()["source"]
    assert m["run_seconds"] == 45
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    for entry in m["configs"] + m["workloads"]:
        assert len(entry["why"]) <= 200
    # an earlier metric changed only by this cell's name, last in its list
    for metric in m["per_layer"] + m["end_to_end"]:
        cells = metric.get("workloads", [])
        if CELL in cells and not metric["name"].endswith(KIND):
            assert cells[0] in EARLIER_CELLS
            assert set(cells[:cells.index(CELL)]) <= set(EARLIER_CELLS)
    assert len(json.dumps(m, indent=2)) < 64 * 1024


def test_the_configuration_keeps_every_published_width():
    config = the_config()
    for key, value in PUBLISHED.items():
        assert config[key] == REDUCED.get(key, value), key
    assert config["reduced"] == sorted(REDUCED)
    assert config["published"]["num_hidden_layers"] == 40
    assert config["published"]["vocab_size"] == 100352
    # one whole period, the published 9 : 1, and an eighth of the vocabulary
    assert config["layer_types"][:10].count("mamba") == 9
    assert config["layer_types"][5] == "attention"
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert config["head_dim"] * config["num_attention_heads"] \
        == config["hidden_size"]
    assert config["attention_multiplier"] == 1 / 64 \
        != config["head_dim"] ** -0.5
    assert "deployment" in config and "initial_weights" in config["assumed"]
    assert sorted(config["correct"]["limits"]) == sorted(
        config["correct"]["reasons"])


def test_the_program_is_configured_from_the_files_keys():
    from benchmark.harness import train_hybrid_dense_lm as driver

    resolved = common.resolve(CELL)
    cfg = driver.program_config(resolved["config"], resolved["traffic"], 7)
    h = cfg.hybrid
    assert cfg.model.arch == "hybrid_dense_lm" and cfg.language_model() is h
    assert (h.vocab_size, h.hidden_size, h.num_layers) == (12544, 2048, 10)
    assert h.layer_pattern[:10] == "MMMMM*MMMM" and len(h.layer_pattern) == 40
    assert (h.mamba_num_heads, h.mamba_head_dim, h.ssm_groups,
            h.ssm_state_size, h.conv_kernel, h.chunk_size) == (
        64, 64, 1, 128, 4, 256)
    assert (h.num_heads, h.num_kv_heads, h.head_dim) == (32, 8, 64)
    assert (h.embedding_multiplier, h.residual_multiplier,
            h.attention_multiplier, h.logits_scaling) == (
        12, 0.22, 0.015625, 8)
    assert h.intermediate_size == 8192 and h.bfloat16
    assert (cfg.data.batch_size, cfg.data.seq_len) == (1, 8192)
    assert cfg.train.learning_rate == 1e-5 and cfg.train.warmup_steps == 2000
    # the defaults of the section are the published model, whole
    from alphafold2_tpu.config import HybridDenseLMConfig

    whole = HybridDenseLMConfig()
    assert (whole.vocab_size, whole.num_layers) == (100352, 40)
    assert {f: getattr(whole, f) for f in (
        "hidden_size", "intermediate_size", "chunk_size", "head_dim")} == {
        f: getattr(h, f) for f in (
            "hidden_size", "intermediate_size", "chunk_size", "head_dim")}


def test_operation_counts_by_hand():
    config = the_config()
    parts = ops.layer_forward_flops(config, 8192)
    assert parts["ssm_projections"] == 2 * 2048 * 8512 + 2 * 4096 * 2048
    # C B^T over the one group, scores x (dt x) a head, two state products
    assert parts["ssm_scan"] == 2 * (256 * 128 + 256 * 4096
                                     + 2 * 128 * 4096) == 4_259_840
    assert parts["mlp"] == 2 * 3 * 2048 * 8192
    assert parts["attn_projections"] == 2 * 2048 * 64 * (2 * 32 + 2 * 8)
    assert parts["attention"] == 2 * 32 * 2 * 64 * 8193 / 2
    assert ops.layers_of(config, "mamba") == 9
    assert ops.layers_of(config, "attention") == 1
    step = ops.train_step_flops(config, 8192)
    a_token = (9 * (parts["ssm_projections"] + parts["ssm_scan"])
               + 10 * parts["mlp"] + parts["attn_projections"]
               + parts["attention"] + 2 * 2048 * 12544)
    assert step["total"] == 3 * 8192 * a_token
    assert step["total"] == pytest.approx(39.71e12, rel=1e-3)
    # the ten MLPs are over three fifths of the arithmetic, the nine
    # state-space layers' projections and scans under a third
    assert 0.6 < 3 * 8192 * 10 * parts["mlp"] / step["total"] < 0.65
    assert step["scan"] == 3 * 8192 * 9 * 4_259_840
    # the scan's least time: operations bind, barely (4.78 ms against 4.63
    # of bytes)
    assert ops.scan_bytes(config) == 3 * 8192 * 9 * (
        (2 * 4096 + 2 * 128) * 2 + 64 * 4)
    assert ops.scan_bytes(config) / 819e9 == pytest.approx(4.63e-3, rel=0.01)
    assert step["scan"] / 197e12 == pytest.approx(4.78e-3, rel=0.01)
    # the attention core: operations bind (4.2 ms against 0.04 of bytes)
    assert ops.attention_bytes(config) == 3 * 8192 * 2 * 64 * 40 * 2
    assert step["attention"] / 197e12 == pytest.approx(4.19e-3, rel=0.01)


def test_parameter_count_and_state_bytes_of_the_cut():
    import jax

    from benchmark.reference import hybrid_dense_lm_model as ref_model

    sizes = ref_model.model_sizes(the_config())
    shapes = ref_model.param_shapes(sizes)
    count = sum(
        int(jax.numpy.prod(jax.numpy.array(s))) for s in jax.tree.leaves(
            shapes, is_leaf=lambda x: isinstance(x, tuple)))
    assert count == 772_160_448
    assert count * 16 == 12_354_567_168  # 73% of 16,911,433,728
    layer = shapes["params"]["layer_0"]
    assert layer["ssm"]["in_proj"]["kernel"] == (2048, 8512)
    assert layer["ssm"]["conv"]["kernel"] == (4352, 4)
    assert shapes["params"]["layer_5"]["attn_global"]["k_proj"][
        "kernel"] == (2048, 512)
    assert "head" not in shapes["params"]


def test_the_traffic_is_one_sequence_of_8k_over_the_held_slice():
    resolved = common.resolve(CELL)
    config = resolved["config"]
    batch = next(traffic_lm.lm_batches(
        resolved["traffic"], config["vocab_size"], 5))
    assert batch["tokens"].shape == (1, 8192)
    assert 0 <= batch["tokens"].min() and batch["tokens"].max() < 12544
    assert config["pairs_per_step"] == 8192


def test_the_driver_runs_a_cell_given_only_as_files(tiny_run):
    resolved, run = tiny_run
    line = common.result_line(resolved, run, trace=False)
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == {"pairs_per_s", "setup_s"}
    assert line["attempted"] == run["steps"] >= 2 and line["failed"] == 0
    assert run["end_to_end"]["pairs_per_s"] == pytest.approx(
        run["steps"] * 80 / run["window_s"])
    assert sorted(line["compared"]) == sorted(TINY_LIMITS)
    assert "route_hist_l1_step0" not in line["compared"]
    assert line["compared"]["scan_fallback_layers"]["value"] == 0
    assert len(run["stamps"]) == run["steps"] + 1
    # the model's counters of every step of the window
    for name in ("ssm/chunk_decay_min", "ssm/chunk_decay_mean",
                 "ssm/dt_mean", "ssm/scan_in_kernel", "stream/rms_in",
                 "stream/rms_out"):
        assert len(run["counters"][name]) == run["steps"], name
    assert set(run["counters"]["ssm/scan_in_kernel"]) == {1.0}
    assert all(0 < low <= mean < 1 for low, mean in zip(
        run["counters"]["ssm/chunk_decay_min"],
        run["counters"]["ssm/chunk_decay_mean"]))
    # 12 x a row of length 1 over hidden 64
    assert run["counters"]["stream/rms_in"][0] == pytest.approx(
        12 / 64 ** 0.5, rel=0.1)
    assert run["traced_counters"] is None and run["kind"] == KIND
    json.dumps(line)
    # the readers that take the record as it is: the untraced ones answer,
    # the traced ones find no trace
    traced = common.result_line(
        resolved, dict(run, trace=None, device_kind="TPU v5 lite"),
        trace=True)
    assert set(traced["metrics"]) == {
        "step_ms_p50.train", f"mfu_pct.{KIND}", f"ssm_scan_in_kernel.{KIND}"}
    assert traced["metrics"][f"mfu_pct.{KIND}"]["value"] > 0
    assert traced["metrics"][f"ssm_scan_in_kernel.{KIND}"]["value"] == 1.0


def test_a_run_whose_scans_fell_back_is_not_correct():
    """On the CPU no shape is the kernels': every state-space layer reads
    ``ssm/scan_in_kernel`` 0 and the run says so, whatever its numbers."""
    from benchmark.harness import train_hybrid_dense_lm as driver

    run = driver.run(tiny_resolved(), 5, 0.3, False, time.perf_counter())
    assert run["correct"] is False
    broke = [k for k, c in run["compared"].items() if not c["ok"]]
    assert broke == ["scan_fallback_layers"], run["compared"]
    # nine layers' readings a step, the three checked steps and the rest
    assert run["compared"]["scan_fallback_layers"]["value"] >= 9 * 5
    assert set(run["counters"]["ssm/scan_in_kernel"]) == {0.0}


def test_the_start_waits_on_the_host_and_the_change_is_read_a_leaf_at_a_time(
        tiny_run):
    import jax
    import numpy as np

    from benchmark.harness import train_hybrid_dense_lm as driver
    from benchmark.reference import hybrid_dense_lm_model as ref_model

    resolved, run = tiny_run
    sizes = driver.model_sizes(resolved["config"])
    start = jax.device_get(ref_model.init_params(sizes, 3))
    assert all(isinstance(x, np.ndarray) for x in jax.tree.leaves(start))
    moved = jax.tree.map(lambda x: x + 0.5, ref_model.init_params(sizes, 3))
    got = driver.change_norms(moved, start)
    want = ref_model.leaf_norms(jax.tree.map(lambda a, b: a - b, moved, start))
    assert sorted(got) == sorted(want)
    for name, norm in want.items():
        assert got[name] == pytest.approx(float(norm), rel=1e-6), name
    assert "host_start" not in run  # released to the reference, not kept


@pytest.mark.parametrize("fault", [
    "bf16", "fp8", "residual_one", "scale_sqrt", "state_dropped",
    "head_untied", "no_logits_scaling"])
def test_control_and_faults_come_out_not_correct(tiny_run, fault):
    """The reference in the nearest precision below the stated one, and each
    planted fault, put in the program's place against the float32 reference:
    at least one limit catches each."""
    import jax

    from benchmark.harness import train_hybrid_dense_lm as driver
    from benchmark.reference import hybrid_dense_lm_model as ref_model
    from benchmark.reference.lm_model import Precision

    resolved, _ = tiny_run
    config = resolved["config"]
    s31 = traffic_lm.seed31(SEED)
    batches = list(itertools.islice(traffic_lm.lm_batches(
        resolved["traffic"], config["vocab_size"], s31), 3))
    start = jax.device_get(
        ref_model.init_params(driver.model_sizes(config), s31))
    good = driver.reference_readings(config, start, batches)
    if fault in ("bf16", "fp8"):
        other = driver.reference_readings(
            config, start, batches, prec=Precision(fault))
    else:
        other = driver.reference_readings(config, start, batches, fault=fault)
    compared, ok = correct.judge(
        driver.training_numbers(other, good, 0), config["correct"]["limits"])
    assert not ok, compared


def test_a_fault_under_the_timed_path_comes_out_not_correct():
    """A step that leaves the state unchanged, planted underneath
    ``train()``: the change's worst leaf reads about 1."""
    import jax

    from alphafold2_tpu.ops import ssm
    from benchmark.harness import train_hybrid_dense_lm as driver

    def unchanged(step):
        return jax.jit(lambda s, b, r: (s, step(s, b, r)[1]))

    with pytest.MonkeyPatch.context() as patched:
        patched.setattr(ssm, "scan_kernel_takes", lambda *shapes: True)
        run = driver.run(tiny_resolved(), 5, 0.3, False,
                         time.perf_counter(), break_step=unchanged)
    assert run["correct"] is False
    assert run["compared"]["change_norm_worst_leaf"]["value"] > 0.9
    assert run["compared"]["scan_fallback_layers"]["ok"]


def test_control_script_reads_which_limits_each_fault_breaks():
    from benchmark.harness import control_hybrid_dense_lm as control

    out = control.readings(
        tiny_resolved(), 1, ("fp8", "state_dropped", "residual_one"))
    assert out["fp8"]["breaks"] and out["residual_one"]["breaks"]
    assert "grad_norm_worst_scan_leaf" in out["state_dropped"]["breaks"]
    assert set(out["fp8"]) == set(TINY_LIMITS) | {"breaks", "stream_rms"}
    # the residual multiplier holds the stream down: taken as 1 it leaves
    # several times larger
    assert out["residual_one"]["stream_rms"][1] > 2 * out["stream_rms"][1]
    assert out["residual_one"]["stream_rms"][0] == pytest.approx(
        out["stream_rms"][0])


# ------------------------------------------------------------- the readers ---

STEP = "jit_step"


@pytest.fixture
def record(monkeypatch):
    """Two executions of a step, each: a state-space layer's scan 10 (the two
    kernels 3 + 5, the D term 2) and its projections, convolution and gate 9,
    the attention layer 7 (kernels 2 + 4, a projection 1), a layer's MLP 8,
    head 3 + loss 2, optimizer 5, a layer's two norms and a residual sum 3,
    an unscoped copy 1."""
    from benchmark.harness import scope_reduce

    fwd = "jit(step)/jvp(HybridDenseLM)"
    bwd = "jit(step)/transpose(jvp(HybridDenseLM))/jvp(HybridDenseLM)/" \
        "checkpoint/rematted_computation"
    ops_, t = [], 0

    def add(name, scope, ns):
        nonlocal t
        ops_.append((name, scope, t, t + ns))
        t += ns

    for _ in range(2):
        add("ssd_chunk_fwd.1",
            f"{fwd}/layer_0/ssm/scan/ssd_chunk_fwd/pallas_call", 3)
        add("ssd_chunk_bwd.1",
            f"{bwd}/layer_0/ssm/scan/ssd_chunk_bwd/pallas_call", 5)
        add("fusion.3", f"{bwd}/layer_0/ssm/scan/mul", 2)
        add("fusion.4", f"{fwd}/layer_0/ssm/in_proj/dot_general", 4)
        add("fusion.5", f"{fwd}/layer_0/ssm/conv/mul", 2)
        add("fusion.6", f"{bwd}/layer_0/ssm/gate_norm/mul", 1)
        add("fusion.7", f"{bwd}/layer_0/ssm/out_proj/dot_general", 2)
        add("splash_mha_fwd_residuals.1",
            f"{fwd}/layer_5/attn_global/core/pallas_call", 2)
        add("splash_mha_dkv_no_residuals.1",
            f"{bwd}/layer_5/attn_global/core/pallas_call", 4)
        add("fusion.8", f"{fwd}/layer_5/attn_global/q_proj/dot_general", 1)
        add("fusion.9", f"{fwd}/layer_0/dense_ffn/up_proj/dot_general", 8)
        add("fusion.10", f"{fwd}/head/dot_general", 3)
        add("fusion.11", "jit(step)/jvp(loss)/reduce_sum", 2)
        add("fusion.12", "jit(step)/optimizer/grad_clip/mul", 5)
        add("fusion.13", f"{fwd}/layer_0/mixer_norm/mul", 1)
        add("fusion.14", f"{fwd}/layer_0/ffn_norm/mul", 1)
        add("fusion.15", f"{fwd}/layer_0/add", 1)
        add("copy.5", "", 1)
    rec = {
        "devices": {"/device:TPU:0": {
            "ops": ops_,
            "modules": [(f"{STEP}(123)", 0, 48), (f"{STEP}(123)", 48, 96)],
            "steps": []}},
        "host": [], "spans": [], "step_module": STEP,
    }
    monkeypatch.setattr(scope_reduce, "program_record", lambda: rec)
    planes = {"/device:TPU:0": [(n, s, e) for n, _, s, e in ops_]}
    return {"trace": {"planes": planes, "busy_s": 1.0, "window_s": 1.0},
            "kind": KIND, "config": the_config(),
            "device_kind": "TPU v5 lite", "chips": 1,
            "traffic": {"seq_len": 8192},
            "peaks": common.load_json(
                os.path.join(BENCH, "harness", "peaks.json"))}


def metric(name):
    return common.load_json(os.path.join(BENCH, "metrics", name + ".json"))


BLOCKS = ("ssm_scan_device_ms.train_ssm_lm", "ssm_other_device_ms.train_ssm_lm",
          "attn_global_device_ms.train_swa_lm",
          "dense_shared_ffn_device_ms.train_lm",
          "embed_head_loss_device_ms.train_lm",
          f"lm_rest_device_ms.{KIND}")


def test_block_metrics_partition_the_step(record):
    from benchmark.readers import scope_paths_device_ms as reader

    read = {n: reader.read(record, metric(n)["params"]) for n in BLOCKS}
    ms = 1e-6
    assert read == {
        "ssm_scan_device_ms.train_ssm_lm": pytest.approx(10 * ms),
        "ssm_other_device_ms.train_ssm_lm": pytest.approx(9 * ms),
        "attn_global_device_ms.train_swa_lm": pytest.approx(7 * ms),
        "dense_shared_ffn_device_ms.train_lm": pytest.approx(8 * ms),
        "embed_head_loss_device_ms.train_lm": pytest.approx(5 * ms),
        f"lm_rest_device_ms.{KIND}": pytest.approx(9 * ms),
    }
    assert sum(read.values()) == pytest.approx(48 * ms)  # one step's time
    # the remainder names every scope the five others read
    rest = set(metric(f"lm_rest_device_ms.{KIND}")["params"]["all_but"])
    for name in BLOCKS[:-1]:
        assert set(metric(name)["params"]["scopes"]) <= rest, name
    # the update is a part of the remainder, not a block beside it
    assert reader.read(record, metric("update_device_ms.train_lm")[
        "params"]) == pytest.approx(5 * ms)


def test_scan_roofline_divides_the_least_time_by_the_scopes_time(record):
    from benchmark.readers import scope_roofline_hybrid_dense_lm_pct as reader

    spec = metric(f"ssd_scan_roofline_pct.{KIND}")["params"]
    config = record["config"]
    least = max(ops.train_step_flops(config, 8192)["scan"] / 197e12,
                ops.scan_bytes(config) / 819e9)
    assert least == pytest.approx(4.78e-3, rel=0.01)  # operations bind
    # 10 ns a step under ssm/scan
    assert reader.read(record, spec) == pytest.approx(100.0 * least / 10e-9)
    # nothing where no operation carries the scope, or the kind is another's,
    # or the program kept no record: never 0
    assert reader.read(record, {"scopes": ["ssm/kernel"]}) is None
    assert reader.read(dict(record, kind="train_ssm_lm"), spec) is None
    assert reader.read(dict(record, trace=None), spec) is None
    with pytest.raises(SystemExit, match="no peaks"):
        reader.read(dict(record, device_kind="TPU v9"), spec)
    # the third model's reader leaves this kind alone
    from benchmark.readers import scope_roofline_ssm_lm_pct

    assert scope_roofline_ssm_lm_pct.read(record, spec) is None


def test_core_roofline_counts_the_published_head_width(record):
    from benchmark.readers import kernel_roofline_hybrid_dense_lm_pct as reader

    spec = metric(f"attn_core_roofline_pct.{KIND}")["params"]
    config = record["config"]
    least = max(ops.train_step_flops(config, 8192)["attention"] / 197e12,
                ops.attention_bytes(config) / 819e9)
    assert least == pytest.approx(4.19e-3, rel=0.01)  # operations bind
    # 2 steps in the trace, 12 ns of splash kernels
    assert reader.read(record, spec) == pytest.approx(
        100.0 * least * 2 / 12e-9)
    assert reader.read(record, {**spec, "prefixes": ["nothing"]}) is None
    assert reader.read(dict(record, kind="train_swa_lm"), spec) is None
    assert reader.read(dict(record, trace=None), spec) is None
    from benchmark.readers import kernel_roofline_swa_lm_pct

    assert kernel_roofline_swa_lm_pct.read(
        record, {**spec, "work": "attention"}) is None


def test_mfu_is_the_shapes_count_over_the_windows_time(record):
    from benchmark.readers import mfu_from_shapes_hybrid_dense_lm as reader

    run = dict(record, steps=4, window_s=2.0)
    want = ops.train_step_flops(record["config"], 8192)["total"]
    assert reader.read(run, {}) == pytest.approx(
        100.0 * want * 4 / 2.0 / 197e12)
    assert reader.read(dict(run, kind="train_ssm_lm"), {}) is None
    assert reader.read(dict(run, steps=0), {}) is None
    from benchmark.readers import mfu_from_shapes_ssm_lm

    assert mfu_from_shapes_ssm_lm.read(run, {}) is None


def test_unscoped_share_names_this_models_class(record):
    from benchmark.readers import unscoped_model_device_pct as reader

    spec = metric(f"unscoped_device_pct.{KIND}")["params"]
    assert spec["model"] == "HybridDenseLM"
    # of a step's 48: the copy's 1
    assert reader.read(record, spec) == pytest.approx(100.0 * 1 / 48)
    from alphafold2_tpu.models.hybrid_dense_lm import HybridDenseLM

    assert HybridDenseLM.__name__ == spec["model"]


def test_scan_in_kernel_is_the_windows_mean_of_the_smallest_layer(record):
    from benchmark.readers import run_counter_mean as reader

    spec = metric(f"ssm_scan_in_kernel.{KIND}")["params"]
    assert reader.read(dict(record, counters={
        "ssm/scan_in_kernel": [1.0, 1.0, 1.0]}), spec) == 1.0
    assert reader.read(dict(record, counters={
        "ssm/scan_in_kernel": [1.0, 0.0]}), spec) == 0.5
    assert reader.read(record, spec) is None  # a run that collected none
