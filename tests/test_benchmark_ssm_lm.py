"""The third language-model cell's benchmark pieces, tiny on the CPU: the
driver ``harness/train_ssm_lm.py`` given a cell only as files, what decides
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
    common, correct, ops_from_shapes_ssm_lm as ops, traffic_lm,
)

CELL = "train_nemotron3_nano_ep16_seq8k"
CONFIG = "nemotron3_nano_30b_a3b_train_ep16"
TINY = dict(
    vocab_size=64, hidden_size=64, mamba_num_heads=4, mamba_head_dim=16,
    n_groups=2, ssm_state_size=8, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, moe_intermediate_size=32,
    moe_shared_expert_intermediate_size=48, router_width=8,
    n_routed_experts=4, first_expert=2, num_experts_per_tok=2,
    pairs_per_step=80,
    # five chunks a sequence, and time steps short enough that a state
    # outlives its chunk as the slow quarter of the published heads' does at
    # the cell's 128 (a dt 8 <= 1.3): dropping it has to show at this size too
    chunk_size=8, time_step_max=0.01,
    # and float32 compute: at hidden 64 bfloat16's own noise (0.005 on a
    # loss, 0.1 on a leaf) is larger than what a dropped state moves
    compute_dtype="float32",
)
# readings at the tiny size in float32 (seeds 1-3 and the run's): the program
# reads 2e-7 / 6e-7 / 1.3e-4 / 0 (loss, gradient, change, routing); the
# control, here the reference in bfloat16 (the nearest precision below the
# tiny configuration's float32), 0.0015-0.005 on a loss,
# 0.01-0.1 on the worst leaf's gradient, 0.02-0.05 on its change; the
# dropped state, the weakest fault, 0.03-0.07 / 0.03-0.05 / 0.009-0.04 on
# gradient, change and routing
TINY_LIMITS = {"loss_step0": 5e-4, "loss_step1": 5e-4, "loss_step2": 5e-4,
               "grad_norm_worst_leaf": 0.005, "change_norm_worst_leaf": 0.01,
               "route_hist_l1_step0": 0.004}
PUBLISHED = {
    "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128,
    "hidden_size": 2688, "intermediate_size": 1856,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_num_heads": 64, "max_position_embeddings": 262144,
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
    "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
    "norm_eps": 1e-05, "num_attention_heads": 32, "num_experts_per_tok": 6,
    "num_hidden_layers": 52, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "partial_rotary_factor": 1,
    "rope_theta": 10000, "routed_scaling_factor": 2.5,
    "ssm_state_size": 128, "time_step_floor": 0.0001, "time_step_max": 0.1,
    "time_step_min": 0.001, "topk_group": 1, "vocab_size": 131072,
}


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
    from benchmark.harness import train_ssm_lm

    resolved = tiny_resolved()
    run = train_ssm_lm.run(resolved, 2_500_000_011, 0.5, False,
                           time.perf_counter())
    return resolved, run


def test_the_cell_resolves_to_files_of_its_own_kind():
    resolved = common.resolve(CELL)
    assert resolved["config"]["kind"] == "train_ssm_lm"
    assert resolved["cell"]["chips"] == 1
    assert resolved["traffic"] == {
        **resolved["traffic"], "kind": "lm_zipf", "sequences": 1,
        "seq_len": 8192, "zipf_exponent": 1.0}
    names = {m["name"] for m in resolved["per_layer"]}
    assert {m for m in names if m.endswith(".train_ssm_lm")} == {
        f"{stem}.train_ssm_lm" for stem in (
            "mfu_pct", "ssm_scan_device_ms", "ssm_other_device_ms",
            "ssd_scan_roofline_pct", "moe_grouped_matmul_roofline_pct",
            "lm_rest_device_ms", "unscoped_device_pct")}
    # the other language models' readers that read this record unchanged
    assert {m for m in names if m.endswith((".train_lm", ".train_swa_lm"))} \
        == {"moe_dispatch_device_ms.train_lm",
            "moe_experts_device_ms.train_lm",
            "dense_shared_ffn_device_ms.train_lm",
            "embed_head_loss_device_ms.train_lm",
            "expert_load_max_over_mean.train_lm",
            "update_device_ms.train_lm",
            "attn_global_device_ms.train_swa_lm"}
    assert {"setup_lower_s.train", "setup_compile_s.train",
            "step_device_ms.train", "compiles_after_warmup.train",
            "step_ms_p50.train", "device_idle_pct.train",
            "idle_attributed_pct.train",
            "inferred_scope_device_pct.train"} <= names
    assert [m["name"] for m in resolved["end_to_end"]] == [
        "pairs_per_s", "setup_s"]
    # every reader and metric file the cell names is there
    for spec in resolved["per_layer"]:
        assert os.path.exists(
            os.path.join(BENCH, "readers", spec["reader"] + ".py"))


def test_the_manifest_keeps_what_it_had():
    """The four cells and their metrics as before; every list only gained
    the new cell at its end (and, since, the cells of later PRs after it)."""
    later = ["train_granite4_h_micro_pp4_seq8k"]
    m = manifest()
    assert [w["name"] for w in m["workloads"]][:5] == [
        "train_flagship", "train_mesh_dp2sp2", "train_kanana2_ep8_seq8k",
        "train_smallthinker_ep8_seq16k", CELL]
    assert [c["name"] for c in m["configs"]][4] == CONFIG
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    for metric in m["end_to_end"] + m["per_layer"]:
        cells = metric.get("workloads", [])
        if CELL in cells:  # nothing after it but what later PRs appended
            assert cells[cells.index(CELL) + 1:] in ([], later)
    # PR 36 appended two metrics of the record's inferred scopes after them
    # (and PR 38 its own cell's after those)
    names = [p["name"] for p in m["per_layer"]]
    last = names.index("update_device_ms.train_lm")
    assert names[last - 1] == "inferred_scope_device_pct.train"
    assert all(CELL in p["workloads"]
               for p in m["per_layer"][last - 1:last + 1])
    new = m["per_layer"][last - 8:last - 1]
    assert all(p["name"].endswith(".train_ssm_lm") for p in new)
    assert all(p["workloads"][0] == CELL and p["workloads"][1:] in ([], later)
               and p["moves"] == "pairs_per_s" for p in new)
    assert all(p["workloads"] == later for p in m["per_layer"][last + 1:])
    assert m["run_seconds"] == 45
    assert len(json.dumps(m, indent=2)) < 64 * 1024


def test_the_configuration_keeps_every_published_width():
    """Every number of the source's config.json under its own key, but for
    the three cuts ``reduced`` names, with the published counts beside."""
    config = the_config()
    differ = sorted(k for k, v in PUBLISHED.items() if config[k] != v)
    assert differ == sorted(config["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert {k: config["published"][k] for k in differ} == {
        k: PUBLISHED[k] for k in differ}
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (9, 8, 16384)
    # the pattern whole, its first nine characters run: 4 M, 4 E, 1 *
    pattern = config["hybrid_override_pattern"]
    assert len(pattern) == 52 and [pattern.count(k) for k in "ME*"] == [
        23, 23, 6]
    assert pattern[:9] == "MEMEM*EME"
    assert [ops.layers_of(config, k) for k in "ME*"] == [4, 4, 1]
    assert config["mlp_hidden_act"] == "relu2"
    assert config["mamba_hidden_act"] == "silu"
    assert config["norm_topk_prob"] is True and config["use_conv_bias"] is True
    assert config["mamba_proj_bias"] is False
    assert config["attention_bias"] is False
    assert config["tie_word_embeddings"] is False
    # the floors: 4 layers of each repeating kind and the attention layer
    # with them, 8 experts, 1/8 of the vocabulary
    assert config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    assert config["router_width"] == 128 and config["pairs_per_step"] == 8192
    for said in ("16 chips", "experts 0-7", "0-16,383", "MEMEM*EME"):
        assert said in config["deployment"], said
    assert {"attention_positions", "gate_before_norm", "dt_unclamped",
            "initial_weights", "router_bias", "balancing_loss", "packing",
            "positions", "optimizer"} <= set(config["assumed"])
    assert sorted(config["correct"]["limits"]) == sorted(
        config["correct"]["reasons"]) == sorted(TINY_LIMITS)
    entry = next(c for c in manifest()["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    for text in (entry["why"], *(w["why"] for w in manifest()["workloads"])):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_the_program_is_configured_from_the_files_keys():
    from benchmark.harness import train_ssm_lm

    config = the_config()
    cfg = train_ssm_lm.program_config(
        config, {"sequences": 1, "seq_len": 8192, "zipf_exponent": 1.0}, 3)
    assert cfg.model.arch == "ssm_moe_lm" and cfg.language_model() is cfg.ssm
    s = cfg.ssm
    assert (s.hidden_size, s.num_layers, s.mamba_num_heads, s.mamba_head_dim,
            s.ssm_groups, s.ssm_state_size, s.conv_kernel, s.chunk_size) == (
        2688, 9, 64, 64, 8, 128, 4, 128)
    assert (s.num_heads, s.num_kv_heads, s.head_dim) == (32, 2, 128)
    assert (s.moe_intermediate_size, s.moe_shared_expert_intermediate_size,
            s.n_routed_experts, s.experts_held, s.first_expert,
            s.num_experts_per_tok, s.routed_scaling_factor) == (
        1856, 3712, 128, 8, 0, 6, 2.5)
    assert s.layer_pattern[:9] == "MEMEM*EME" and s.rms_norm_eps == 1e-5
    assert (s.time_step_min, s.time_step_max, s.time_step_floor) == (
        0.001, 0.1, 1e-4)
    assert cfg.train.warmup_steps == 2000 and cfg.train.learning_rate == 1e-5
    sizes = train_ssm_lm.model_sizes(config)
    hash(tuple(sorted(sizes.items())))  # the reference's static argument


def test_operation_counts_by_hand():
    config = the_config()
    parts = ops.layer_forward_flops(config, 8192)
    assert parts["ssm_projections"] == 2 * (2688 * 10304 + 4096 * 2688)
    # C B^T a group, scores x (dt x) a head, chunk states out and in
    assert parts["ssm_scan"] == 2 * (
        128 * 8 * 128 + 128 * 64 * 64 + 2 * 128 * 64 * 64) == 3_407_872
    assert parts["attn_projections"] == 2 * 2688 * 128 * (2 * 32 + 2 * 2)
    assert parts["attention"] == 2 * 32 * 4096.5 * 256
    assert parts["shared"] == 2 * 2 * 2688 * 3712
    assert parts["routed_row"] == 2 * 2 * 2688 * 1856  # two products
    # a balanced router sends the held experts 6 x 8/128 of the tokens a
    # layer: 384 rows an expert
    assert ops.formula_routed_rows(config) == 4 * 3072
    assert ops.formula_routed_rows(config) / (4 * 8) == 384
    step = ops.train_step_flops(config, 8192)
    assert step["total"] / (3 * 8192) == pytest.approx(717e6, rel=0.005)
    assert step["total"] == pytest.approx(17.6e12, rel=0.005)  # ISSUE 34's
    a_token = step["total"] / (3 * 8192)
    m_share = 4 * (parts["ssm_projections"] + parts["ssm_scan"]) / a_token
    assert m_share == pytest.approx(0.45, abs=0.005)
    assert 2 * 2688 * 16384 / a_token == pytest.approx(0.123, abs=0.003)
    assert step["scan"] == 3 * 8192 * 4 * parts["ssm_scan"]
    assert step["scan"] / step["total"] == pytest.approx(0.019, abs=0.001)
    # the scan's least time is bound by bytes: 2.5 ms against 1.7 ms
    assert ops.scan_bytes(config) == 3.0 * 8192 * 4 * (
        (2 * 4096 + 2 * 1024) * 2 + 64 * 4)
    assert ops.scan_bytes(config) / 819e9 == pytest.approx(2.49e-3, rel=0.01)
    assert step["scan"] / 197e12 == pytest.approx(1.70e-3, rel=0.01)
    assert ops.routed_bytes(config) == 3.0 * 2 * (
        4 * 8 * 2 * 2688 * 1856 + 4 * 3072 * 2 * (2688 + 1856))
    half = ops.train_step_flops(config, 8192, 2 * 3072)
    assert half["routed"] == step["routed"] / 2
    assert half["scan"] == step["scan"]


def test_parameter_count_and_state_bytes_of_the_cut():
    from benchmark.harness import train_ssm_lm
    from benchmark.reference import ssm_lm_model

    shapes = ssm_lm_model.param_shapes(train_ssm_lm.model_sizes(the_config()))
    by_layer = {}

    def walk(node, top=None):
        for k, v in node.items():
            if isinstance(v, tuple):
                by_layer[top] = by_layer.get(top, 0) + int(np.prod(v))
            else:
                walk(v, top or k)

    walk(shapes["params"])
    # ISSUE 34's count: a state-space layer, an expert layer, the attention
    # layer with their norms; embedding, head and final norm
    assert by_layer["layer_0"] == 38_744_896
    assert by_layer["layer_1"] == 100_125_440  # the router's bias with it
    assert by_layer["layer_5"] == 23_399_040
    total = sum(by_layer.values())
    assert total == 666_963_456
    assert 10.6e9 < 16 * total < 10.7e9  # weights, gradients, mu, nu


def test_the_traffic_is_one_sequence_of_the_pretraining_length():
    params = common.load_json(
        os.path.join(BENCH, "traffic", "lm_zipf_seq8k_x1.json"))
    a = traffic_lm.lm_batches(params, 16384, traffic_lm.seed31(2_500_000_011))
    b = traffic_lm.lm_batches(params, 16384, traffic_lm.seed31(2_500_000_011))
    first, again = next(a)["tokens"], next(b)["tokens"]
    assert first.shape == (1, 8192) and first.dtype == np.int32
    assert 0 <= first.min() and first.max() < 16384
    np.testing.assert_array_equal(first, again)
    assert (first != next(a)["tokens"]).any()


def test_train_ssm_lm_driver_runs_a_cell_given_only_as_files(tiny_run):
    resolved, run = tiny_run
    line = common.result_line(resolved, run, trace=False)
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == {"pairs_per_s", "setup_s"}
    assert line["attempted"] == run["steps"] >= 2 and line["failed"] == 0
    assert run["end_to_end"]["pairs_per_s"] == pytest.approx(
        run["steps"] * 80 / run["window_s"])
    assert sorted(line["compared"]) == sorted(TINY_LIMITS)
    assert len(run["stamps"]) == run["steps"] + 1
    # 80 tokens x top-2 in each of the four expert layers, those sent here
    rows = run["counters"]["moe/assignments_here"]
    assert len(rows) == run["steps"] and all(0 < r <= 640 for r in rows)
    # the scan's counters of every step of the window
    for name in ("ssm/chunk_decay_min", "ssm/chunk_decay_mean",
                 "ssm/dt_mean"):
        assert len(run["counters"][name]) == run["steps"]
    assert all(0 < low <= mean < 1 for low, mean in zip(
        run["counters"]["ssm/chunk_decay_min"],
        run["counters"]["ssm/chunk_decay_mean"]))
    assert run["traced_counters"] is None and run["kind"] == "train_ssm_lm"
    json.dumps(line)
    # the readers that take the record as it is: the untraced ones answer,
    # the traced ones find no trace
    traced = common.result_line(
        resolved, dict(run, trace=None, device_kind="TPU v5 lite"),
        trace=True)
    assert set(traced["metrics"]) == {
        "step_ms_p50.train", "mfu_pct.train_ssm_lm",
        "expert_load_max_over_mean.train_lm"}
    assert traced["metrics"]["mfu_pct.train_ssm_lm"]["value"] > 0


BALANCE = dict(
    vocab_size=256, hidden_size=64, mamba_num_heads=4, mamba_head_dim=16,
    n_groups=2, ssm_state_size=8, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, moe_intermediate_size=32,
    moe_shared_expert_intermediate_size=48, router_width=32,
    n_routed_experts=4, first_expert=0, num_experts_per_tok=4,
    pairs_per_step=512, chunk_size=32, compute_dtype="float32",
)


def balance_resolved():
    """A size at which a load can be told from noise: 512 tokens x top-4
    over a router of 32 in each of the four expert layers, 64 a balanced
    expert, 1,024 rows a step the four held experts."""
    resolved = common.resolve(CELL)
    resolved["config"].update(BALANCE)
    resolved["traffic"].update(sequences=1, seq_len=512)
    return resolved


@pytest.fixture(scope="module")
def balanced():
    """seed -> (bias, rows the held experts get and the largest expert's
    load over the mean, at a bias of 0 and under the calibrated one, each a
    mean over eight of the window's batches)."""
    import jax

    from alphafold2_tpu.train import loop
    from benchmark.harness import train_ssm_lm

    resolved = balance_resolved()
    config, traffic = resolved["config"], resolved["traffic"]
    model = loop.build_task(
        train_ssm_lm.program_config(config, traffic, 1)).model
    counters = jax.jit(lambda p, t: model.apply(p, t)["moe"])
    out = {}
    for seed in (11, 12, 13, 14):
        s31 = traffic_lm.seed31(seed)
        batches = [b["tokens"] for b in itertools.islice(
            traffic_lm.lm_batches(traffic, config["vocab_size"], s31), 8)]
        params = train_ssm_lm.start_params(config, s31, None)
        bias = train_ssm_lm.balanced_router_bias(config, traffic, s31, params)
        read = []
        for start in (params,
                      train_ssm_lm.with_router_bias(params, config, bias)):
            got = [jax.device_get(counters(start, b)) for b in batches]
            hist = np.mean([g["hist"] for g in got], axis=0)
            read.append((
                float(np.mean([g["assignments_here"].sum() for g in got])),
                float((hist.max(-1) / hist.mean(-1)).mean())))
        out[seed] = (bias, *read)
    return out


def test_the_calibrated_bias_gives_every_seed_a_balanced_routers_rows(
        balanced):
    """What the cell's spread hung on: at a bias of 0 the held experts' rows
    lie up to a tenth off a balanced router's 1,024 (924-1,076 over four seeds);
    under the bias set in set-up every seed's lie within 4% of it, and no
    expert's mean load is far over the mean."""
    zero = np.array([v[1][0] for v in balanced.values()])
    calibrated = np.array([v[2][0] for v in balanced.values()])
    assert np.abs(zero - 1024).max() > 60
    assert np.abs(calibrated - 1024).max() < 40
    assert calibrated.std() < zero.std() / 4
    for _, at_zero, under_bias in balanced.values():
        assert under_bias[1] < 1.35 < 1.8 < at_zero[1]


def test_the_calibrated_bias_is_the_seeds_and_bounded(balanced):
    """The same seed gives the same bias, another seed another; no entry can
    pass the sum of the speeds; every expert layer has its row."""
    from benchmark.harness import train_ssm_lm

    resolved = balance_resolved()
    config, traffic = resolved["config"], resolved["traffic"]
    cal = config["router_bias_calibration"]
    s31 = traffic_lm.seed31(11)
    again = train_ssm_lm.balanced_router_bias(
        config, traffic, s31, train_ssm_lm.start_params(config, s31, None))
    np.testing.assert_array_equal(again, balanced[11][0])
    assert np.abs(balanced[11][0] - balanced[12][0]).max() > 0.01
    assert train_ssm_lm.expert_layers(config) == [
        "layer_1", "layer_3", "layer_6", "layer_8"]
    for bias, *_ in balanced.values():
        assert bias.shape == (4, 32) and bias.dtype == np.float32
        assert 0.01 < np.abs(bias).max() \
            <= cal["speed"] / (1 - cal["decay"]) + 1e-6


@pytest.mark.parametrize("bias", [None, "calibrated"])
def test_the_bias_goes_into_the_expert_layers_and_nothing_else(
        balanced, bias):
    import jax

    from benchmark.harness import train_ssm_lm

    config = balance_resolved()["config"]
    s31 = traffic_lm.seed31(11)
    plain = train_ssm_lm.start_params(config, s31, None)
    rows = None if bias is None else balanced[11][0]
    start = train_ssm_lm.start_params(config, s31, rows)
    assert jax.tree.structure(start) == jax.tree.structure(plain)
    for (path, a), b in zip(jax.tree.leaves_with_path(start),
                            jax.tree.leaves(plain)):
        if path[-1].key == "router_bias" and bias is not None:
            layer = train_ssm_lm.expert_layers(config).index(path[1].key)
            np.testing.assert_array_equal(a, rows[layer])
            assert np.abs(np.asarray(b)).max() == 0
        else:
            np.testing.assert_array_equal(a, b)


def test_program_and_reference_start_from_the_same_bias(tiny_run):
    """The run hands the reference the bias it gave the program: with
    another start the reference's routing at step 0 is another."""
    from benchmark.harness import train_ssm_lm

    resolved, run = tiny_run
    config = resolved["config"]
    assert run["correct"] is True
    s31 = traffic_lm.seed31(2_500_000_011)
    bias = train_ssm_lm.balanced_router_bias(
        config, resolved["traffic"], s31,
        train_ssm_lm.start_params(config, s31, None))
    assert np.abs(bias).max() > 0
    batches = list(itertools.islice(traffic_lm.lm_batches(
        resolved["traffic"], config["vocab_size"], s31), 1))
    with_bias = train_ssm_lm.reference_readings(
        config, s31, batches, router_bias=bias)
    without = train_ssm_lm.reference_readings(config, s31, batches)
    assert np.abs(np.asarray(with_bias["route_hist"])
                  - np.asarray(without["route_hist"])).sum() > 0
    assert run["setup_parts_s"]["jax_ready"] \
        < run["setup_parts_s"]["router_bias"] \
        < run["setup_parts_s"]["weights"]


def test_the_worst_leaf_can_be_a_state_space_leaf(tiny_run):
    """The compared norms cover ``A_log``, ``dt_bias``, ``D`` and the
    convolution of every state-space layer, on both sides."""
    _, run = tiny_run
    at = run["compared"]["grad_norm_worst_leaf"]["at"]
    assert at.startswith("params/")
    from benchmark.harness import train_ssm_lm

    resolved = tiny_resolved()
    config = resolved["config"]
    s31 = traffic_lm.seed31(3)
    batches = list(itertools.islice(traffic_lm.lm_batches(
        resolved["traffic"], config["vocab_size"], s31), 1))
    ref = train_ssm_lm.reference_readings(config, s31, batches)
    for i in (0, 2, 4, 7):
        for leaf in ("A_log", "dt_bias", "D", "conv/kernel", "conv/bias"):
            name = f"params/layer_{i}/ssm/{leaf}"
            assert ref["grad_norms"][name] > 0 and name in ref["change_norms"]
    # and a gap on one of them alone is the worst leaf
    bent = {k: float(v) for k, v in ref["grad_norms"].items()}
    bent["params/layer_2/ssm/A_log"] *= 3.0
    assert correct.worst_leaf(bent, ref["grad_norms"])[1] \
        == "params/layer_2/ssm/A_log"


@pytest.mark.parametrize(
    "fault", ["bf16", "fp8", "state_dropped", "conv_reversed", "relu"])
def test_control_and_faults_come_out_not_correct(tiny_run, fault):
    """The reference in the nearest precision below the stated one, and each
    planted fault, put in the program's place against the float32 reference:
    at least one limit catches each."""
    from benchmark.harness import train_ssm_lm
    from benchmark.reference.lm_model import Precision

    resolved, _ = tiny_run
    config = resolved["config"]
    s31 = traffic_lm.seed31(2_500_000_011)
    batches = list(itertools.islice(traffic_lm.lm_batches(
        resolved["traffic"], config["vocab_size"], s31), 3))
    good = train_ssm_lm.reference_readings(config, s31, batches)
    if fault in ("bf16", "fp8"):
        other = train_ssm_lm.reference_readings(
            config, s31, batches, prec=Precision(fault))
    else:
        other = train_ssm_lm.reference_readings(
            config, s31, batches, fault=fault)
    compared, ok = correct.judge(
        train_ssm_lm.training_numbers(other, good),
        config["correct"]["limits"])
    assert not ok, compared


def test_a_fault_under_the_timed_path_comes_out_not_correct():
    """A step that leaves the state unchanged, planted underneath
    ``train()``: the change's worst leaf reads about 1."""
    import jax

    from benchmark.harness import train_ssm_lm

    def unchanged(step):
        return jax.jit(lambda s, b, r: (s, step(s, b, r)[1]))

    run = train_ssm_lm.run(tiny_resolved(), 5, 0.3, False,
                           time.perf_counter(), break_step=unchanged)
    assert run["correct"] is False
    assert run["compared"]["change_norm_worst_leaf"]["value"] > 0.9


def test_control_script_reads_which_limits_each_fault_breaks():
    from benchmark.harness import control_ssm_lm

    out = control_ssm_lm.readings(
        tiny_resolved(), 1, ("fp8", "state_dropped", "relu"))
    assert out["fp8"]["breaks"] and out["relu"]["breaks"]
    assert "grad_norm_worst_leaf" in out["state_dropped"]["breaks"]
    assert set(out["fp8"]) == set(TINY_LIMITS) | {"breaks"}


# ------------------------------------------------------------- the readers ---

STEP = "jit_step"


@pytest.fixture
def record(monkeypatch):
    """Two executions of a step, each: a state-space layer's scan 10 (3
    forward, 2 of them inside the chunk recurrence's while body, + 7
    backward) and its projections, convolution and gate 9, the attention
    layer 6, a ragged product 4 whose scope is XLA's own name, the shared
    expert 3, dispatch 2 + router 1, loss 2, optimizer 5, a layer's norm 2,
    an unscoped copy 1."""
    from benchmark.harness import scope_reduce

    fwd = "jit(step)/jvp(SsmMoeLM)"
    bwd = "jit(step)/transpose(jvp(SsmMoeLM))/jvp(SsmMoeLM)/checkpoint/" \
        "rematted_computation"
    ops_, t = [], 0

    def add(name, scope, ns):
        nonlocal t
        ops_.append((name, scope, t, t + ns))
        t += ns

    for _ in range(2):
        add("fusion.1", f"{fwd}/layer_0/ssm/scan/dot_general", 1)
        add("fusion.2",
            f"{fwd}/layer_0/ssm/scan/closed_call/while/body/mul", 2)
        add("fusion.3", f"{bwd}/layer_0/ssm/scan/transpose/dot_general", 7)
        add("fusion.4", f"{fwd}/layer_0/ssm/in_proj/dot_general", 4)
        add("fusion.5", f"{fwd}/layer_0/ssm/conv/mul", 2)
        add("fusion.6", f"{bwd}/layer_0/ssm/gate_norm/mul", 1)
        add("fusion.7", f"{bwd}/layer_0/ssm/out_proj/dot_general", 2)
        add("splash_mha_fwd_residuals.1",
            f"{fwd}/layer_5/attn_global/core/pallas_call", 2)
        add("splash_mha_dkv_no_residuals.1",
            f"{bwd}/layer_5/attn_global/core/pallas_call", 4)
        add("ragged-dot-none.3", "ragged-dot-none", 4)
        add("fusion.8", f"{fwd}/layer_1/moe/shared/up_proj/dot_general", 3)
        add("sort.1", f"{fwd}/layer_1/moe/dispatch/sort", 2)
        add("fusion.9", f"{fwd}/layer_1/moe/router/dot_general", 1)
        add("fusion.10", "jit(step)/jvp(loss)/reduce_sum", 2)
        add("fusion.11", "jit(step)/optimizer/grad_clip/mul", 5)
        add("fusion.12", f"{fwd}/layer_1/norm/mul", 2)
        add("copy.5", "", 1)
    rec = {
        "devices": {"/device:TPU:0": {
            "ops": ops_,
            "modules": [(f"{STEP}(123)", 0, 45), (f"{STEP}(123)", 45, 90)],
            "steps": []}},
        "host": [], "spans": [], "step_module": STEP,
    }
    monkeypatch.setattr(scope_reduce, "program_record", lambda: rec)
    planes = {"/device:TPU:0": [(n, s, e) for n, _, s, e in ops_]}
    return {"trace": {"planes": planes, "busy_s": 1.0, "window_s": 1.0},
            "kind": "train_ssm_lm", "config": the_config(),
            "device_kind": "TPU v5 lite", "chips": 1,
            "traffic": {"seq_len": 8192},
            "peaks": common.load_json(
                os.path.join(BENCH, "harness", "peaks.json")),
            "traced_counters": {"moe/assignments_here": [
                14000, 10000, 7, 7]}}


def metric(name):
    return common.load_json(os.path.join(BENCH, "metrics", name + ".json"))


BLOCKS = ("ssm_scan_device_ms.train_ssm_lm", "ssm_other_device_ms.train_ssm_lm",
          "attn_global_device_ms.train_swa_lm",
          "moe_dispatch_device_ms.train_lm", "moe_experts_device_ms.train_lm",
          "dense_shared_ffn_device_ms.train_lm",
          "embed_head_loss_device_ms.train_lm",
          "lm_rest_device_ms.train_ssm_lm")


def test_block_metrics_partition_the_step(record):
    from benchmark.readers import scope_paths_device_ms as reader

    read = {n: reader.read(record, metric(n)["params"]) for n in BLOCKS}
    ms = 1e-6
    assert read == {
        # the while body's operations carry the scan's scope and count
        "ssm_scan_device_ms.train_ssm_lm": pytest.approx(10 * ms),
        "ssm_other_device_ms.train_ssm_lm": pytest.approx(9 * ms),
        "attn_global_device_ms.train_swa_lm": pytest.approx(6 * ms),
        "moe_dispatch_device_ms.train_lm": pytest.approx(3 * ms),
        "moe_experts_device_ms.train_lm": pytest.approx(4 * ms),
        "dense_shared_ffn_device_ms.train_lm": pytest.approx(3 * ms),
        "embed_head_loss_device_ms.train_lm": pytest.approx(2 * ms),
        "lm_rest_device_ms.train_ssm_lm": pytest.approx(8 * ms),
    }
    assert sum(read.values()) == pytest.approx(45 * ms)  # one step's time
    # the remainder names every scope the seven others read
    rest = set(metric("lm_rest_device_ms.train_ssm_lm")["params"]["all_but"])
    for name in BLOCKS[:-1]:
        assert set(metric(name)["params"]["scopes"]) <= rest, name


def test_scan_roofline_divides_the_least_time_by_the_scopes_time(record):
    from benchmark.readers import scope_roofline_ssm_lm_pct as reader

    spec = metric("ssd_scan_roofline_pct.train_ssm_lm")["params"]
    config = record["config"]
    least = max(ops.train_step_flops(config, 8192)["scan"] / 197e12,
                ops.scan_bytes(config) / 819e9)
    assert least == pytest.approx(2.49e-3, rel=0.01)  # bytes bind
    # 10 ns a step under ssm/scan
    assert reader.read(record, spec) == pytest.approx(100.0 * least / 10e-9)
    # nothing where no operation carries the scope, or the kind is another's,
    # or the program kept no record: never 0
    assert reader.read(record, {"scopes": ["ssm/kernel"]}) is None
    assert reader.read(dict(record, kind="train_lm"), spec) is None
    assert reader.read(dict(record, trace=None), spec) is None
    with pytest.raises(SystemExit, match="no peaks"):
        reader.read(dict(record, device_kind="TPU v9"), spec)


def test_routed_roofline_counts_two_products_an_expert(record):
    from benchmark.readers import kernel_roofline_ssm_lm_pct as reader

    spec = metric("moe_grouped_matmul_roofline_pct.train_ssm_lm")["params"]
    config = record["config"]
    least = max(
        ops.train_step_flops(config, 8192, 12000)["routed"] / 197e12,
        ops.routed_bytes(config, 12000) / 819e9)
    # 2 steps in the trace, 8 ns of ragged products, 12,000 rows a step
    assert reader.read(record, spec) == pytest.approx(
        100.0 * least * 2 / 8e-9)
    assert reader.read(record, {**spec, "prefixes": ["nothing"]}) is None
    assert reader.read(dict(record, kind="train_swa_lm"), spec) is None
    # the other models' readers leave this kind alone
    from benchmark.readers import (
        kernel_roofline_lm_pct, kernel_roofline_swa_lm_pct,
        mfu_from_shapes_lm, mfu_from_shapes_swa_lm,
    )

    other = {**spec, "work": "routed"}
    assert kernel_roofline_lm_pct.read(record, other) is None
    assert kernel_roofline_swa_lm_pct.read(record, other) is None
    assert mfu_from_shapes_lm.read(dict(record, steps=3), {}) is None
    assert mfu_from_shapes_swa_lm.read(dict(record, steps=3), {}) is None


def test_mfu_counts_the_rows_the_window_sent_the_held_experts(record):
    from benchmark.readers import mfu_from_shapes_ssm_lm as reader

    run = dict(record, steps=4, window_s=4.0, counters={
        "moe/assignments_here": [16000, 14000, 10000, 8000]})
    want = ops.train_step_flops(record["config"], 8192, 12000)["total"]
    assert reader.read(run, {}) == pytest.approx(100.0 * want / 197e12)
    assert reader.read(dict(run, kind="train_lm"), {}) is None
    assert reader.read(dict(run, steps=0), {}) is None


def test_unscoped_share_names_this_models_class(record):
    from benchmark.readers import unscoped_model_device_pct as reader

    spec = metric("unscoped_device_pct.train_ssm_lm")["params"]
    assert spec["model"] == "SsmMoeLM"
    # of a step's 45: the ragged product's 4 and the copy's 1
    assert reader.read(record, spec) == pytest.approx(100.0 * 5 / 45)
    from alphafold2_tpu.models.ssm_moe_lm import SsmMoeLM

    assert SsmMoeLM.__name__ == spec["model"]
