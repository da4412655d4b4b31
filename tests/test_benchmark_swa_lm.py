"""The second language-model cell's benchmark pieces, tiny on the CPU: the
driver ``harness/train_swa_lm.py`` given a cell only as files, what decides
``correct`` (the float8 control and three planted faults come out not
correct), the operation counts, the traffic, the new readers on a
hand-written record.

(The tier-1 command collects ``tests/`` only.)
"""

import itertools
import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, ROOT)

from benchmark.harness import (  # noqa: E402
    common, correct, ops_from_shapes_swa_lm as ops, traffic_lm,
)

CELL = "train_smallthinker_ep8_seq16k"
CONFIG = "smallthinker_21b_a3b_train_ep8"
TINY = dict(
    vocab_size=64, hidden_size=64, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    sliding_window_size=8, moe_ffn_hidden_size=32, router_width=8,
    moe_num_primary_experts=4, first_expert=2,
    moe_num_active_primary_experts=2, pairs_per_step=64,
)
# readings at the tiny size (seeds 1-3): the reference in bfloat16 reads up
# to 0.002 / 0.06 / 0.01 / 0.02 (loss, gradient, change, routing); the float8
# control 0.25-0.33 on the worst leaf's gradient and 0.29-0.46 on its change
TINY_LIMITS = {"loss_step0": 0.01, "loss_step1": 0.01, "loss_step2": 0.01,
               "grad_norm_worst_leaf": 0.15, "change_norm_worst_leaf": 0.1,
               "route_hist_l1_step0": 0.08}


def manifest():
    return common.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def tiny_resolved():
    resolved = common.resolve(CELL)
    resolved["config"].update(TINY)
    resolved["config"]["correct"]["limits"] = dict(TINY_LIMITS)
    resolved["traffic"].update(sequences=2, seq_len=32)
    return resolved


@pytest.fixture(scope="module")
def tiny_run():
    from benchmark.harness import train_swa_lm

    resolved = tiny_resolved()
    run = train_swa_lm.run(resolved, 2_500_000_011, 0.5, False,
                           time.perf_counter())
    return resolved, run


def test_the_cell_resolves_to_files_of_its_own_kind():
    resolved = common.resolve(CELL)
    assert resolved["config"]["kind"] == "train_swa_lm"
    assert resolved["cell"]["chips"] == 1
    assert resolved["traffic"] == {
        **resolved["traffic"], "kind": "lm_zipf", "sequences": 1,
        "seq_len": 16384, "zipf_exponent": 1.0}
    names = {m["name"] for m in resolved["per_layer"]}
    assert {m for m in names if m.endswith(".train_swa_lm")} == {
        f"{stem}.train_swa_lm" for stem in (
            "mfu_pct", "attn_global_device_ms", "attn_window_device_ms",
            "swa_attn_kernels_roofline_pct",
            "moe_grouped_matmul_roofline_pct", "lm_rest_device_ms",
            "unscoped_device_pct")}
    # the first language model's readers that read this record unchanged
    assert {m for m in names if m.endswith(".train_lm")} == {
        f"{stem}.train_lm" for stem in (
            "moe_dispatch_device_ms", "moe_experts_device_ms",
            "embed_head_loss_device_ms", "expert_load_max_over_mean",
            "update_device_ms")}
    assert {"setup_lower_s.train", "setup_compile_s.train",
            "step_device_ms.train", "compiles_after_warmup.train",
            "inferred_scope_device_pct.train"} <= names
    assert [m["name"] for m in resolved["end_to_end"]] == [
        "pairs_per_s", "setup_s"]
    # every reader and metric file the cell names is there
    for spec in resolved["per_layer"]:
        assert os.path.exists(
            os.path.join(BENCH, "readers", spec["reader"] + ".py"))


# the cells later PRs appended, in their order
LATER_CELLS = ["train_nemotron3_nano_ep16_seq8k",
               "train_granite4_h_micro_pp4_seq8k",
               "train_keye_vl2_ep8_seq16k"]


def test_the_manifest_keeps_what_it_had():
    """The three cells and their metrics as before; every list only gained
    the new cell at its end (and, since, the cells of later PRs after it)."""
    m = manifest()
    assert [w["name"] for w in m["workloads"]][:4] == [
        "train_flagship", "train_mesh_dp2sp2", "train_kanana2_ep8_seq8k",
        CELL]
    assert [c["name"] for c in m["configs"]][3] == CONFIG
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    for metric in m["end_to_end"] + m["per_layer"]:
        cells = metric.get("workloads", [])
        if CELL in cells:  # nothing after it but what later PRs appended
            after = cells[cells.index(CELL) + 1:]
            assert after == [c for c in LATER_CELLS if c in after]
    assert m["run_seconds"] == 45


def test_the_configuration_keeps_every_published_width():
    """Every number of the source's config.json under its own key, but for
    the three cuts ``reduced`` names, with the published counts beside."""
    published = {
        "head_dim": 128, "hidden_size": 2560,
        "max_position_embeddings": 16384, "moe_ffn_hidden_size": 768,
        "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
        "num_attention_heads": 28, "num_hidden_layers": 52,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_theta": 1500000, "sliding_window_size": 4096,
        "vocab_size": 151936,
    }
    config = common.load_json(os.path.join(BENCH, "configs", CONFIG + ".json"))
    differ = sorted(k for k, v in published.items() if config[k] != v)
    assert differ == sorted(config["reduced"]) == [
        "moe_num_primary_experts", "num_hidden_layers", "vocab_size"]
    assert {k: config["published"][k] for k in differ} == {
        k: published[k] for k in differ}
    assert (config["num_hidden_layers"], config["moe_num_primary_experts"],
            config["vocab_size"]) == (4, 8, 18992)
    # the nested groups whole: 13 periods of global, window, window, window
    assert config["sliding_window_layout"] == config["rope_layout"] \
        == [0, 1, 1, 1] * 13
    assert config["moe_primary_router_apply_softmax"] is True
    assert config["norm_topk_prob"] is True
    assert config["tie_word_embeddings"] is False
    # the floors: a whole period and 4 expert layers, 8 experts, 1/8 vocab
    assert config["num_hidden_layers"] >= 4
    assert config["moe_num_primary_experts"] >= 8
    assert config["vocab_size"] * 8 >= published["vocab_size"]
    assert config["router_width"] == 64 and config["pairs_per_step"] == 16384
    assert "8 chips" in config["deployment"]
    assert "experts 0-7" in config["deployment"]
    assert "0-18,991" in config["deployment"]
    assert {"expert_activation", "router_input", "rotary", "attention",
            "window", "balancing_loss", "packing"} <= set(config["assumed"])
    entry = next(c for c in manifest()["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    for text in (entry["why"], *(w["why"] for w in manifest()["workloads"])):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_the_layouts_must_be_one_periodic_pattern():
    from benchmark.harness import train_swa_lm

    config = common.load_json(os.path.join(BENCH, "configs", CONFIG + ".json"))
    assert train_swa_lm.global_every(config) == 4
    cfg = train_swa_lm.program_config(
        config, {"sequences": 1, "seq_len": 16384, "zipf_exponent": 1.0}, 3)
    assert cfg.model.arch == "swa_moe_lm"
    assert (cfg.swa.num_heads, cfg.swa.num_kv_heads, cfg.swa.head_dim,
            cfg.swa.sliding_window, cfg.swa.global_every,
            cfg.swa.experts_held, cfg.swa.n_routed_experts,
            cfg.swa.num_experts_per_tok, cfg.swa.rope_theta) == (
        28, 4, 128, 4096, 4, 8, 64, 6, 1.5e6)
    assert cfg.train.warmup_steps == 2000
    for bad in ({"rope_layout": [0, 1, 1, 0] * 13},
                {"sliding_window_layout": [0, 1, 1, 1, 1] + [0, 1, 1, 1] * 12,
                 "rope_layout": [0, 1, 1, 1, 1] + [0, 1, 1, 1] * 12}):
        with pytest.raises(SystemExit, match="one pattern"):
            train_swa_lm.global_every({**config, **bad})
    sizes = train_swa_lm.model_sizes(config)
    hash(tuple(sorted(sizes.items())))  # the reference's static argument


def test_operation_counts_by_hand():
    config = common.load_json(os.path.join(BENCH, "configs", CONFIG + ".json"))
    parts = ops.layer_forward_flops(config, 16384)
    assert parts["projections"] == 2 * (
        2560 * 3584 + 2 * 2560 * 512 + 3584 * 2560)
    assert parts["attention_global"] == 2 * 28 * 8192.5 * 256
    # a window query sees min(i + 1, 4096) keys: 3,584.125 on average
    assert ops.mean_keys(16384, 4096) == (
        4096 * 4097 / 2 + 12288 * 4096) / 16384 == 3584.125
    assert ops.mean_keys(4096, 4096) == ops.mean_keys(4096) == 2048.5
    assert parts["attention_window"] == 2 * 28 * 3584.125 * 256
    assert parts["routed_row"] == 2 * 3 * 2560 * 768
    assert ops.window_layers(config) == 3
    # a balanced router sends the held experts 6 x 8/64 of the tokens
    assert ops.formula_routed_rows(config) == 4 * 12288
    step = ops.train_step_flops(config, 16384)
    assert step["total"] == pytest.approx(28.18e12, rel=0.001)  # ISSUE 32's
    assert step["attention"] == pytest.approx(13.35e12, rel=0.001)
    assert step["attention"] / step["total"] == pytest.approx(0.474, abs=0.002)
    assert step["routed"] == pytest.approx(1.74e12, rel=0.002)
    # a window layer does 44% of a global layer's attention work at 16k
    assert parts["attention_window"] / parts["attention_global"] \
        == pytest.approx(0.4375, abs=0.001)
    # the attention kernels are bound by operations, not bytes
    assert ops.attention_bytes(config) == 3.0 * 16384 * 4 * 2 * 128 * 32 * 2
    assert ops.attention_bytes(config) / 819e9 \
        < 0.1 * step["attention"] / 197e12
    assert ops.routed_bytes(config) == 3.0 * 2 * (
        4 * 8 * 3 * 2560 * 768 + 4 * 12288 * (2560 + 3 * 768 + 2560))
    half = ops.train_step_flops(config, 16384, 2 * 12288)
    assert half["routed"] == step["routed"] / 2
    assert half["attention"] == step["attention"]


def test_parameter_count_and_state_bytes_of_the_cut():
    from benchmark.harness import train_swa_lm
    from benchmark.reference import swa_lm_model

    config = common.load_json(os.path.join(BENCH, "configs", CONFIG + ".json"))
    shapes = swa_lm_model.param_shapes(train_swa_lm.model_sizes(config))
    leaves = []

    def walk(node):
        for v in node.values():
            leaves.append(int(np.prod(v))) if isinstance(v, tuple) else walk(v)

    walk(shapes)
    assert sum(leaves) == 370_547_200  # ISSUE 32's count
    assert 5.9e9 < 16 * sum(leaves) < 5.95e9  # weights, gradients, mu, nu


def test_the_traffic_is_one_sequence_of_the_models_context():
    params = common.load_json(
        os.path.join(BENCH, "traffic", "lm_zipf_seq16k.json"))
    a = traffic_lm.lm_batches(params, 18992, traffic_lm.seed31(2_500_000_011))
    b = traffic_lm.lm_batches(params, 18992, traffic_lm.seed31(2_500_000_011))
    first, again = next(a)["tokens"], next(b)["tokens"]
    assert first.shape == (1, 16384) and first.dtype == np.int32
    assert 0 <= first.min() and first.max() < 18992
    np.testing.assert_array_equal(first, again)
    assert (first != next(a)["tokens"]).any()


def test_train_swa_lm_driver_runs_a_cell_given_only_as_files(tiny_run):
    resolved, run = tiny_run
    line = common.result_line(resolved, run, trace=False)
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == {"pairs_per_s", "setup_s"}
    assert line["attempted"] == run["steps"] >= 2 and line["failed"] == 0
    assert run["end_to_end"]["pairs_per_s"] == pytest.approx(
        run["steps"] * 64 / run["window_s"])
    assert sorted(line["compared"]) == sorted(TINY_LIMITS)
    assert len(run["stamps"]) == run["steps"] + 1
    # 64 tokens x top-2 in each of the four layers, those sent here
    rows = run["counters"]["moe/assignments_here"]
    assert len(rows) == run["steps"] and all(0 < r <= 512 for r in rows)
    assert run["traced_counters"] is None and run["kind"] == "train_swa_lm"
    json.dumps(line)
    # the readers that take the record as it is: the untraced ones answer,
    # the traced ones find no trace
    traced = common.result_line(
        resolved, dict(run, trace=None, device_kind="TPU v5 lite"),
        trace=True)
    assert set(traced["metrics"]) == {
        "step_ms_p50.train", "mfu_pct.train_swa_lm",
        "expert_load_max_over_mean.train_lm"}
    assert traced["metrics"]["mfu_pct.train_swa_lm"]["value"] > 0


@pytest.mark.parametrize(
    "fault", ["fp8", "window_off", "rope_on_global", "route_from_y"])
def test_control_and_faults_come_out_not_correct(tiny_run, fault):
    """The reference in the nearest precision below the stated one, and each
    planted fault, put in the program's place against the float32 reference:
    at least one limit catches each."""
    from benchmark.harness import train_swa_lm
    from benchmark.reference.lm_model import Precision

    resolved, _ = tiny_run
    config = resolved["config"]
    s31 = traffic_lm.seed31(2_500_000_011)
    batches = list(itertools.islice(traffic_lm.lm_batches(
        resolved["traffic"], config["vocab_size"], s31), 3))
    good = train_swa_lm.reference_readings(config, s31, batches)
    if fault == "fp8":
        other = train_swa_lm.reference_readings(
            config, s31, batches, prec=Precision("fp8"))
    else:
        other = train_swa_lm.reference_readings(
            config, s31, batches, fault=fault)
    compared, ok = correct.judge(
        train_swa_lm.training_numbers(other, good),
        config["correct"]["limits"])
    assert not ok, compared
    out = [k for k, c in compared.items() if not c["ok"]]
    if fault == "route_from_y":  # other experts chosen in every layer
        assert "route_hist_l1_step0" in out


def test_a_fault_under_the_timed_path_comes_out_not_correct():
    """A step that leaves the state unchanged, planted underneath
    ``train()``: the change's worst leaf reads about 1."""
    import jax

    from benchmark.harness import train_swa_lm

    def unchanged(step):
        return jax.jit(lambda s, b, r: (s, step(s, b, r)[1]))

    run = train_swa_lm.run(tiny_resolved(), 5, 0.3, False,
                           time.perf_counter(), break_step=unchanged)
    assert run["correct"] is False
    assert run["compared"]["change_norm_worst_leaf"]["value"] > 0.9


def test_control_script_reads_which_limits_each_fault_breaks():
    from benchmark.harness import control_swa_lm

    out = control_swa_lm.readings(
        tiny_resolved(), 1, ("fp8", "bf16", "route_from_y"))
    assert out["bf16"]["breaks"] == []  # the stated precision is inside
    assert out["fp8"]["breaks"]
    assert "route_hist_l1_step0" in out["route_from_y"]["breaks"]
    assert set(out["fp8"]) == set(TINY_LIMITS) | {"breaks"}


# ------------------------------------------------------------- the readers ---

STEP = "jit_step"


@pytest.fixture
def record(monkeypatch):
    """Two executions of a step, each: a global layer's attention 12 (4
    forward + 8 backward), a window layer's 6, a ragged product 6 whose
    scope is XLA's own name, dispatch 3 + router 1, loss 2, optimizer 5, a
    norm 2, an unscoped copy 1."""
    from benchmark.harness import scope_reduce

    fwd = "jit(step)/jvp(SwaMoeLM)"
    bwd = "jit(step)/transpose(jvp(SwaMoeLM))/jvp(SwaMoeLM)/checkpoint/" \
        "rematted_computation"
    ops_, t = [], 0

    def add(name, scope, ns):
        nonlocal t
        ops_.append((name, scope, t, t + ns))
        t += ns

    for _ in range(2):
        add("splash_mha_fwd_residuals.1",
            f"{fwd}/layer_0/attn_global/core/pallas_call", 4)
        add("splash_mha_dkv_no_residuals.1",
            f"{bwd}/layer_0/attn_global/core/pallas_call", 5)
        add("splash_mha_dq_no_residuals.1",
            f"{bwd}/layer_0/attn_global/core/pallas_call", 3)
        add("splash_mha_fwd_residuals.2",
            f"{fwd}/layer_1/attn_window/core/pallas_call", 2)
        add("fusion.7", f"{bwd}/layer_1/attn_window/rope/mul", 4)
        add("ragged-dot-none.3", "ragged-dot-none", 6)
        add("sort.1", f"{fwd}/layer_1/moe/dispatch/sort", 3)
        add("fusion.9", f"{fwd}/layer_1/moe/router/dot_general", 1)
        add("fusion.3", "jit(step)/jvp(loss)/reduce_sum", 2)
        add("fusion.4", "jit(step)/optimizer/grad_clip/mul", 5)
        add("fusion.5", f"{fwd}/layer_1/ffn_norm/mul", 2)
        add("copy.5", "", 1)
    rec = {
        "devices": {"/device:TPU:0": {
            "ops": ops_,
            "modules": [(f"{STEP}(123)", 0, 38), (f"{STEP}(123)", 38, 76)],
            "steps": []}},
        "host": [], "spans": [], "step_module": STEP,
    }
    monkeypatch.setattr(scope_reduce, "program_record", lambda: rec)
    planes = {"/device:TPU:0": [(n, s, e) for n, _, s, e in ops_]}
    return {"trace": {"planes": planes, "busy_s": 1.0, "window_s": 1.0},
            "kind": "train_swa_lm",
            "traced_counters": {"moe/assignments_here": [
                30000, 20000, 7, 7]}}


def metric(name):
    return common.load_json(os.path.join(BENCH, "metrics", name + ".json"))


def test_block_metrics_partition_the_step(record):
    from benchmark.readers import scope_paths_device_ms as reader

    names = ("attn_global_device_ms.train_swa_lm",
             "attn_window_device_ms.train_swa_lm",
             "moe_dispatch_device_ms.train_lm", "moe_experts_device_ms.train_lm",
             "embed_head_loss_device_ms.train_lm",
             "lm_rest_device_ms.train_swa_lm")
    read = {n: reader.read(record, metric(n)["params"]) for n in names}
    ms = 1e-6
    assert read == {
        "attn_global_device_ms.train_swa_lm": pytest.approx(12 * ms),
        "attn_window_device_ms.train_swa_lm": pytest.approx(6 * ms),
        "moe_dispatch_device_ms.train_lm": pytest.approx(4 * ms),
        "moe_experts_device_ms.train_lm": pytest.approx(6 * ms),
        "embed_head_loss_device_ms.train_lm": pytest.approx(2 * ms),
        "lm_rest_device_ms.train_swa_lm": pytest.approx(8 * ms),
    }
    assert sum(read.values()) == pytest.approx(38 * ms)  # one step's time


def test_kernel_roofline_reader_counts_needed_work_once_a_step(record):
    from benchmark.readers import kernel_roofline_swa_lm_pct as reader

    config = common.load_json(os.path.join(BENCH, "configs", CONFIG + ".json"))
    run = dict(record, config=config, device_kind="TPU v5 lite", chips=1,
               traffic={"seq_len": 16384},
               peaks=common.load_json(
                   os.path.join(BENCH, "harness", "peaks.json")))
    spec = metric("swa_attn_kernels_roofline_pct.train_swa_lm")["params"]
    need_s = ops.train_step_flops(config, 16384)["attention"] / 197e12
    assert need_s == pytest.approx(0.0678, rel=0.002)  # ISSUE 32's 67.8 ms
    # 2 steps in the trace, 28 ns of splash kernel events
    assert reader.read(run, spec) == pytest.approx(
        100.0 * need_s * 2 / 28e-9)
    assert reader.read(run, {**spec, "prefixes": ["nothing"]}) is None
    assert reader.read(dict(run, kind="train_lm"), spec) is None
    with pytest.raises(SystemExit, match="no peaks"):
        reader.read(dict(run, device_kind="TPU v9"), spec)
    routed = metric("moe_grouped_matmul_roofline_pct.train_swa_lm")["params"]
    least = max(
        ops.train_step_flops(config, 16384, 25000)["routed"] / 197e12,
        ops.routed_bytes(config, 25000) / 819e9)
    assert reader.read(run, routed) == pytest.approx(
        100.0 * least * 2 / 12e-9)
    # the first language model's reader leaves this kind alone, and back
    from benchmark.readers import kernel_roofline_lm_pct, mfu_from_shapes_lm

    assert kernel_roofline_lm_pct.read(run, routed) is None
    assert mfu_from_shapes_lm.read(dict(run, steps=3), {}) is None


def test_mfu_counts_the_rows_the_window_sent_the_held_experts():
    from benchmark.readers import mfu_from_shapes_swa_lm as reader

    config = common.load_json(os.path.join(BENCH, "configs", CONFIG + ".json"))
    run = {"kind": "train_swa_lm", "config": config, "chips": 1, "steps": 4,
           "window_s": 4.0, "traffic": {"seq_len": 16384},
           "device_kind": "TPU v5 lite",
           "counters": {"moe/assignments_here": [40000, 30000, 20000, 10000]},
           "peaks": common.load_json(
               os.path.join(BENCH, "harness", "peaks.json"))}
    want = ops.train_step_flops(config, 16384, 25000)["total"]
    assert reader.read(run, {}) == pytest.approx(100.0 * want / 197e12)
    assert reader.read(dict(run, kind="train_lm"), {}) is None


def test_unscoped_share_names_this_models_class(record):
    from benchmark.readers import unscoped_model_device_pct as reader

    spec = metric("unscoped_device_pct.train_swa_lm")["params"]
    assert spec["model"] == "SwaMoeLM"
    # of a step's 38: the ragged product's 6 and the copy's 1
    assert reader.read(record, spec) == pytest.approx(100.0 * 7 / 38)
    from alphafold2_tpu.models.swa_moe_lm import SwaMoeLM

    assert SwaMoeLM.__name__ == spec["model"]
