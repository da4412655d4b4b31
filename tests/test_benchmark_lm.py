"""The language-model cell's benchmark pieces, tiny on the CPU: the driver
``harness/train_lm.py`` given a cell only as files, what decides ``correct``
(the float8 control and three planted faults come out not correct), the
operation counts, the traffic, the new readers on a hand-written record.

(The tier-1 command collects ``tests/`` only; the flagship's benchmark tests
live under ``benchmark/tests/``, which this PR may not edit.)
"""

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
    common, correct, ops_from_shapes_lm, traffic_lm,
)

CELL = "train_kanana2_ep8_seq8k"
CONFIG = "kanana2_30b_a3b_train_ep8"
TINY = dict(
    vocab_size=64, hidden_size=64, num_hidden_layers=3,
    num_attention_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, kv_lora_rank=32, intermediate_size=128,
    moe_intermediate_size=32, router_width=8, n_routed_experts=4,
    first_expert=2, num_experts_per_tok=2, pairs_per_step=64,
)
# readings at the tiny size (seeds 1-3): the program in bfloat16 reads up to
# 0.004 / 0.04 / 0.02 / 0.03 (loss, gradient, change, routing); the float8
# control 0.02-0.05 on the losses and over 0.3 on the worst leaf's gradient
TINY_LIMITS = {"loss_step0": 0.01, "loss_step1": 0.01, "loss_step2": 0.01,
               "grad_norm_worst_leaf": 0.15, "change_norm_worst_leaf": 0.08,
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
    from benchmark.harness import train_lm

    resolved = tiny_resolved()
    run = train_lm.run(resolved, 2_500_000_011, 0.5, False,
                       time.perf_counter())
    return resolved, run


def test_the_cell_resolves_to_files_of_its_own_kind():
    resolved = common.resolve(CELL)
    assert resolved["config"]["kind"] == "train_lm"
    assert resolved["cell"]["chips"] == 1
    assert resolved["traffic"] == {
        **resolved["traffic"], "kind": "lm_zipf", "sequences": 2,
        "seq_len": 8192, "zipf_exponent": 1.0}
    names = {m["name"] for m in resolved["per_layer"]}
    assert {m for m in names if m.endswith(".train_lm")} == {
        f"{stem}.train_lm" for stem in (
            "mfu_pct", "mla_attn_device_ms", "moe_dispatch_device_ms",
            "moe_experts_device_ms", "dense_shared_ffn_device_ms",
            "embed_head_loss_device_ms", "lm_rest_device_ms",
            "mla_attn_kernels_roofline_pct",
            "moe_grouped_matmul_roofline_pct", "expert_load_max_over_mean",
            "unscoped_device_pct", "update_device_ms")}
    assert "inferred_scope_device_pct.train" in names
    # every reader and metric file the cell names is there
    for spec in resolved["per_layer"]:
        assert os.path.exists(
            os.path.join(BENCH, "readers", spec["reader"] + ".py"))
    # the set-up spans' reader reads this cell's one-chip AOT compile too
    assert {"setup_lower_s.train", "setup_compile_s.train"} <= names
    # the trunk's own readers are not asked to read this cell
    assert not names & {"mfu_pct.train", "attn_kernels_roofline_pct.train",
                        "cross_attn_device_ms.train"}
    assert [m["name"] for m in resolved["end_to_end"]] == [
        "pairs_per_s", "setup_s"]


def test_the_configuration_keeps_every_published_width():
    """Every number of the source's config.json under its own key, but for
    the three cuts ``reduced`` names, with the published counts beside."""
    published = {
        "first_k_dense_replace": 1, "head_dim": 64, "hidden_size": 2048,
        "intermediate_size": 6144, "kv_lora_rank": 512,
        "max_position_embeddings": 32768, "moe_intermediate_size": 768,
        "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
        "n_shared_experts": 2, "num_attention_heads": 32,
        "num_experts_per_tok": 6, "num_hidden_layers": 48,
        "num_key_value_heads": 32, "qk_head_dim": 192,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 1000000,
        "routed_scaling_factor": 2.448, "topk_group": 1, "v_head_dim": 128,
        "vocab_size": 128256,
    }
    config = common.load_json(os.path.join(BENCH, "configs", CONFIG + ".json"))
    differ = sorted(k for k, v in published.items() if config[k] != v)
    assert differ == sorted(config["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert {k: config["published"][k] for k in differ} == {
        k: published[k] for k in differ}
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 16, 16032)
    # the floors: 4 expert layers after the dense one, 8 experts, 1/8 vocab
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4
    assert config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 >= published["vocab_size"]
    assert config["router_width"] == 128 and config["pairs_per_step"] == 16384
    assert "8 chips" in config["deployment"]
    entry = next(c for c in manifest()["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    for text in (entry["why"], *(w["why"] for w in manifest()["workloads"])):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_operation_counts_by_hand():
    config = common.load_json(os.path.join(BENCH, "configs", CONFIG + ".json"))
    parts = ops_from_shapes_lm.layer_forward_flops(config, 8192)
    assert parts["projections"] == 2 * (
        2048 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048)
    assert parts["attention"] == 2 * 32 * 4096.5 * 320
    assert parts["shared"] == 2 * 2 * 3 * 2048 * 768
    assert parts["routed_row"] == 2 * 3 * 2048 * 768
    # a balanced router sends the held experts 6 x 16/128 of the tokens
    assert ops_from_shapes_lm.formula_routed_rows(config) == 4 * 12288
    assert parts["dense_ffn"] == 2 * 3 * 2048 * 6144
    step = ops_from_shapes_lm.train_step_flops(config, 8192)
    a_token = step["total"] / 3 / 16384
    assert a_token == pytest.approx(930e6, rel=0.002)  # ISSUE 27's reckoning
    assert step["total"] == pytest.approx(45.7e12, rel=0.002)
    assert step["attention"] / step["total"] == pytest.approx(0.451, abs=0.002)
    assert step["routed"] / step["total"] == pytest.approx(0.0305, abs=0.001)
    # the attention kernels are bound by operations: their least bytes
    # (10.5 GB a step, 13 ms) take an eighth of their operations' time
    assert ops_from_shapes_lm.attention_bytes(config) == 3.0 * 16384 * 5 \
        * 32 * (2 * 192 + 2 * 128) * 2
    assert ops_from_shapes_lm.attention_bytes(config) / 819e9 \
        < 0.15 * step["attention"] / 197e12
    assert ops_from_shapes_lm.routed_bytes(config) == 3.0 * 4 * 2 * (
        16 * 3 * 2048 * 768 + 12288 * (2048 + 3 * 768 + 2048))
    # the readers give the rows the run's steps counted: half the formula's
    # rows are half the routed operations, and the weights' bytes stay
    half = ops_from_shapes_lm.train_step_flops(config, 8192, 2 * 12288)
    assert half["routed"] == step["routed"] / 2
    assert step["total"] - half["total"] == step["routed"] / 2
    assert half["attention"] == step["attention"]
    assert ops_from_shapes_lm.routed_bytes(config, 2 * 12288) == 3.0 * 2 * (
        4 * 16 * 3 * 2048 * 768 + 2 * 12288 * (2048 + 3 * 768 + 2048))


def test_parameter_count_and_state_bytes_of_the_cut():
    from benchmark.harness import train_lm
    from benchmark.reference import lm_model

    config = common.load_json(os.path.join(BENCH, "configs", CONFIG + ".json"))
    shapes = lm_model.param_shapes(train_lm.model_sizes(config))
    leaves = []

    def walk(node):
        for v in node.values():
            leaves.append(int(np.prod(v))) if isinstance(v, tuple) else walk(v)

    walk(shapes)
    assert sum(leaves) == 575_955_968
    assert 9.2e9 < 16 * sum(leaves) < 9.25e9  # weights, gradients, mu, nu


def test_same_seed_same_tokens_and_every_seed_the_same_shapes():
    params = common.load_json(
        os.path.join(BENCH, "traffic", "lm_zipf_seq8k.json"))
    a = traffic_lm.lm_batches(params, 16032, traffic_lm.seed31(2_500_000_011))
    b = traffic_lm.lm_batches(params, 16032, traffic_lm.seed31(2_500_000_011))
    c = traffic_lm.lm_batches(params, 16032, traffic_lm.seed31(7))
    first, again, other = next(a)["tokens"], next(b)["tokens"], \
        next(c)["tokens"]
    assert first.shape == other.shape == (2, 8192)
    assert first.dtype == np.int32 and 0 <= first.min() \
        and first.max() < 16032
    np.testing.assert_array_equal(first, again)
    assert (first != other).any() and (first != next(a)["tokens"]).any()
    counts = np.sort(np.bincount(first.ravel(), minlength=16032))[::-1]
    # Zipf(1.0) over 16,032 ids: the hottest takes 1 / H(16032) = 9.7%
    assert 0.08 < counts[0] / first.size < 0.12
    assert counts[0] > 1.5 * counts[1]
    with pytest.raises(ValueError, match="not a token mix"):
        next(traffic_lm.lm_batches({"kind": "train_crops"}, 10, 1))


def test_train_lm_driver_runs_a_cell_given_only_as_files(tiny_run):
    resolved, run = tiny_run
    line = common.result_line(resolved, run, trace=False)
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == {"pairs_per_s", "setup_s"}
    assert line["attempted"] == run["steps"] >= 2 and line["failed"] == 0
    assert run["end_to_end"]["pairs_per_s"] == pytest.approx(
        run["steps"] * 64 / run["window_s"])
    assert sorted(line["compared"]) == sorted(TINY_LIMITS)
    assert len(run["stamps"]) == run["steps"] + 1
    assert len(run["counters"]["moe/load_max_over_mean"]) == run["steps"]
    # 64 tokens x top-2 in each of the two expert layers, those sent here
    rows = run["counters"]["moe/assignments_here"]
    assert len(rows) == run["steps"] and all(0 < r <= 256 for r in rows)
    assert run["traced_counters"] is None
    json.dumps(line)
    # the readers that take the record as it is: the untraced ones answer,
    # the traced ones find no trace
    traced = common.result_line(
        resolved, dict(run, trace=None, device_kind="TPU v5 lite"),
        trace=True)
    assert set(traced["metrics"]) == {
        "step_ms_p50.train", "mfu_pct.train_lm",
        "expert_load_max_over_mean.train_lm"}
    assert 1.0 <= traced["metrics"]["expert_load_max_over_mean.train_lm"][
        "value"] <= 4.0
    assert traced["metrics"]["mfu_pct.train_lm"]["value"] > 0


def test_the_program_is_compared_through_train_own_initial_parameters(
        tiny_run, monkeypatch):
    """The driver replaces nothing of ``train.loop`` (the flagship's still
    swaps ``tiny_init_state``): afterwards the module is as it was."""
    from alphafold2_tpu.train import loop

    assert loop.tiny_init_state.__module__ == loop.__name__
    assert loop.make_train_step.__module__ == loop.__name__


@pytest.mark.parametrize("fault", ["fp8", "top5", "no_routed", "no_causal"])
def test_control_and_faults_come_out_not_correct(tiny_run, fault):
    """The reference in the nearest precision below the stated one, and each
    planted fault, put in the program's place against the float32 reference:
    at least one limit catches each."""
    from benchmark.harness import train_lm
    from benchmark.reference import lm_model

    resolved, _ = tiny_run
    config = resolved["config"]
    s31 = traffic_lm.seed31(2_500_000_011)
    import itertools

    batches = list(itertools.islice(traffic_lm.lm_batches(
        resolved["traffic"], config["vocab_size"], s31), 3))
    good = train_lm.reference_readings(config, s31, batches)
    if fault == "fp8":
        other = train_lm.reference_readings(
            config, s31, batches, prec=lm_model.Precision("fp8"))
    else:
        other = train_lm.reference_readings(config, s31, batches, fault=fault)
    compared, ok = correct.judge(
        train_lm.training_numbers(other, good), config["correct"]["limits"])
    assert not ok, compared
    out = [k for k, c in compared.items() if not c["ok"]]
    if fault == "top5":
        assert "route_hist_l1_step0" in out  # a sixth of the assignments gone
    if fault == "no_causal":
        assert "loss_step0" in out


def test_a_fault_under_the_timed_path_comes_out_not_correct():
    """A step that leaves the state unchanged, planted underneath
    ``train()``: the change's worst leaf reads about 1."""
    import jax

    from benchmark.harness import train_lm

    def unchanged(step):
        return jax.jit(lambda s, b, r: (s, step(s, b, r)[1]))

    run = train_lm.run(tiny_resolved(), 5, 0.3, False, time.perf_counter(),
                       break_step=unchanged)
    assert run["correct"] is False
    assert run["compared"]["change_norm_worst_leaf"]["value"] > 0.9


# ------------------------------------------------------------- the readers ---

STEP = "jit_step"


def op(name, scope, start, end):
    return (name, scope, start, end)


@pytest.fixture
def record(monkeypatch):
    """Two executions of a step, each: attention 10 (forward) + 20 (backward,
    recomputed), a ragged product 6 whose scope is XLA's own name, dispatch
    4, shared 3, loss 2, optimizer 5, an unscoped copy 1."""
    from benchmark.harness import scope_reduce

    fwd = "jit(step)/jvp(MlaMoeLM)"
    bwd = "jit(step)/transpose(jvp(MlaMoeLM))/jvp(MlaMoeLM)/checkpoint/" \
        "rematted_computation"
    ops, t = [], 0

    def add(name, scope, ns):
        nonlocal t
        ops.append(op(name, scope, t, t + ns))
        t += ns

    for _ in range(2):
        add("flash_attention.1", f"{fwd}/layer_1/mla_attn/core/pallas_call", 10)
        add("flash_mha_bwd_dq.1", f"{bwd}/layer_1/mla_attn/core/pallas_call",
            20)
        add("ragged-dot-none.3", "ragged-dot-none", 6)
        add("sort.1", f"{fwd}/layer_1/moe/dispatch/sort", 3)
        add("fusion.9", f"{fwd}/layer_1/moe/router/dot_general", 1)
        add("fusion.2", f"{bwd}/layer_1/moe/shared/up_proj/dot_general", 3)
        add("fusion.3", "jit(step)/jvp(loss)/reduce_sum", 2)
        add("fusion.4", "jit(step)/optimizer/grad_clip/mul", 5)
        add("copy.5", "", 1)
    rec = {
        "devices": {"/device:TPU:0": {
            "ops": ops,
            "modules": [(f"{STEP}(123)", 0, 51), (f"{STEP}(123)", 51, 102)],
            "steps": []}},
        "host": [], "spans": [], "step_module": STEP,
    }
    monkeypatch.setattr(scope_reduce, "program_record", lambda: rec)
    planes = {"/device:TPU:0": [(n, s, e) for n, _, s, e in ops]}
    return {"trace": {"planes": planes, "busy_s": 1.0, "window_s": 1.0},
            "kind": "train_lm",
            # the held experts' rows in the traced steps and two more
            "traced_counters": {"moe/assignments_here": [
                30000, 20000, 7, 7]}}


def metric(name):
    return common.load_json(os.path.join(BENCH, "metrics", name + ".json"))


def test_block_metrics_partition_the_step(record):
    from benchmark.readers import scope_paths_device_ms as reader

    read = {stem: reader.read(record, metric(stem + ".train_lm")["params"])
            for stem in ("mla_attn_device_ms", "moe_dispatch_device_ms",
                         "moe_experts_device_ms", "dense_shared_ffn_device_ms",
                         "embed_head_loss_device_ms", "lm_rest_device_ms")}
    ms = 1e-6
    assert read == {
        "mla_attn_device_ms": pytest.approx(30 * ms),
        "moe_dispatch_device_ms": pytest.approx(4 * ms),
        "moe_experts_device_ms": pytest.approx(6 * ms),  # by instruction name
        "dense_shared_ffn_device_ms": pytest.approx(3 * ms),
        "embed_head_loss_device_ms": pytest.approx(2 * ms),
        "lm_rest_device_ms": pytest.approx(6 * ms),
    }
    assert sum(read.values()) == pytest.approx(51 * ms)  # one step's time


def test_scope_paths_need_their_names_side_by_side():
    from benchmark.readers.scope_paths_device_ms import holds, names

    found = names("jit(step)/transpose(jvp(M))/layer_2/moe/experts/mul")
    assert found == ["jit(step)", "M", "layer_2", "moe", "experts", "mul"]
    assert holds(found, "moe/experts") and holds(found, "moe")
    assert not holds(found, "moe/shared") and not holds(found, "experts/moe")
    assert not holds(names("jit(step)/jvp(M)/header/x"), "head")


def test_block_reader_finds_nothing_without_a_record(monkeypatch):
    from benchmark.harness import scope_reduce
    from benchmark.readers import scope_paths_device_ms as reader

    monkeypatch.setattr(scope_reduce, "program_record", lambda: None)
    run = {"trace": {"planes": {}}, "kind": "train_lm"}
    assert reader.read(run, {"scopes": ["mla_attn"]}) is None
    assert reader.read({"trace": None}, {"scopes": ["mla_attn"]}) is None


def test_kernel_roofline_reader_counts_needed_work_once_a_step(record):
    from benchmark.readers import kernel_roofline_lm_pct as reader

    config = common.load_json(os.path.join(BENCH, "configs", CONFIG + ".json"))
    run = dict(record, config=config, device_kind="TPU v5 lite", chips=1,
               traffic={"seq_len": 8192},
               peaks=common.load_json(
                   os.path.join(BENCH, "harness", "peaks.json")))
    spec = metric("mla_attn_kernels_roofline_pct.train_lm")["params"]
    need_s = ops_from_shapes_lm.train_step_flops(
        config, 8192)["attention"] / 197e12
    # 2 steps in the trace, 60 ns of kernel events
    assert reader.read(run, spec) == pytest.approx(
        100.0 * need_s * 2 / 60e-9)
    assert reader.read(run, {**spec, "prefixes": ["nothing"]}) is None
    assert reader.read(dict(run, kind="train"), spec) is None
    with pytest.raises(SystemExit, match="no peaks"):
        reader.read(dict(run, device_kind="TPU v9"), spec)
    # the routed work is counted at the rows the two traced steps sent the
    # held experts (their mean: both counts are linear in the rows)
    routed = metric("moe_grouped_matmul_roofline_pct.train_lm")["params"]
    least = max(
        ops_from_shapes_lm.train_step_flops(config, 8192, 25000)["routed"]
        / 197e12, ops_from_shapes_lm.routed_bytes(config, 25000) / 819e9)
    assert reader.read(run, routed) == pytest.approx(
        100.0 * least * 2 / 12e-9)
    short = dict(run, traced_counters={"moe/assignments_here": [30000]})
    with pytest.raises(RuntimeError, match="2 steps in the trace"):
        reader.read(short, routed)


def test_mfu_counts_the_rows_the_window_sent_the_held_experts():
    from benchmark.readers import mfu_from_shapes_lm as reader

    config = common.load_json(os.path.join(BENCH, "configs", CONFIG + ".json"))
    run = {"kind": "train_lm", "config": config, "chips": 1, "steps": 4,
           "window_s": 4.0, "traffic": {"seq_len": 8192},
           "device_kind": "TPU v5 lite",
           "counters": {"moe/assignments_here": [40000, 30000, 20000, 10000]},
           "peaks": common.load_json(
               os.path.join(BENCH, "harness", "peaks.json"))}
    want = ops_from_shapes_lm.train_step_flops(config, 8192, 25000)["total"]
    assert reader.read(run, {}) == pytest.approx(100.0 * want / 197e12)
    formula = ops_from_shapes_lm.train_step_flops(config, 8192)["total"]
    assert formula - want == pytest.approx(3 * 24152 * 2 * 3 * 2048 * 768)


@pytest.mark.parametrize("scope, named", [
    ("jit(step)/transpose(jvp(MlaMoeLM))/layer_2/moe/experts/mul", True),
    ("jit(step)/jvp(loss)/reduce_sum", True),
    ("jit(step)/optimizer/grad_clip/mul", True),
    ("jit(_threefry_split)/threefry2x32", True),  # another program's name
    ("ragged-dot-none", False),  # XLA's own name in place of the scope
    ("jit(step)/mul", False), ("", False),
    ("jit(step)/jvp(Alphafold2)/trunk/layer_0/pair_ff", False),
])
def test_unscoped_reader_takes_the_model_name_as_a_parameter(scope, named):
    from benchmark.readers import unscoped_model_device_pct as reader

    assert reader.named(scope, "MlaMoeLM") is named


def test_unscoped_share_of_a_language_model_step(record, monkeypatch):
    from benchmark.harness import scope_reduce
    from benchmark.readers import unscoped_model_device_pct as reader

    spec = metric("unscoped_device_pct.train_lm")["params"]
    # of a step's 51: the ragged product's 6 and the copy's 1
    assert reader.read(record, spec) == pytest.approx(100.0 * 7 / 51)
    # the trunk's reader, asked the same, would call the whole model unscoped
    # (all but the loss's 2 and the optimizer's 5)
    assert reader.read(record, {"model": "Alphafold2"}) == pytest.approx(
        100.0 * 44 / 51)
    monkeypatch.setattr(scope_reduce, "program_record", lambda: None)
    assert reader.read(record, spec) is None


def test_update_passes_are_a_part_of_the_rest_not_a_block_beside_it(record):
    """``update_device_ms.train_lm``: ``grads_ok``, ``optimizer`` (the clip
    inside it) and ``metrics``, all of which ``lm_rest`` already holds."""
    from benchmark.readers import scope_paths_device_ms as reader

    ms = 1e-6
    spec = metric("update_device_ms.train_lm")
    assert spec["reader"] == "scope_paths_device_ms"
    assert reader.read(record, spec["params"]) == pytest.approx(5 * ms)
    rest = metric("lm_rest_device_ms.train_lm")["params"]
    assert not set(spec["params"]["scopes"]) & set(rest["all_but"])
    assert reader.read(record, rest) == pytest.approx(6 * ms)  # + the copy


def test_inferred_share_reads_the_records_own_account(record, monkeypatch):
    """``inferred_scope_device_pct.train``: own time of the operations whose
    instruction the record lists as inferred, over all own time; an
    instruction of that name in another program is not the step's; nothing
    where the record has no such key (a program from before it)."""
    from benchmark.harness import scope_reduce
    from benchmark.readers import inferred_scope_device_pct as reader

    spec = metric("inferred_scope_device_pct.train")
    assert spec == {"reader": "inferred_scope_device_pct", "params": {}}
    assert reader.read(record, {}) is None  # the record has no account
    rec = scope_reduce.program_record()
    plane = rec["devices"]["/device:TPU:0"]
    path = "jit(step)/jvp(MlaMoeLM)/layer_1"
    named = {"ragged-dot-none.3": f"{path}/moe/experts/ragged-dot-none",
             "copy.5": f"{path}/mla_attn/core/pallas_call"}
    plane["ops"] = [(n, named.get(n, scope), s, e)
                    for n, scope, s, e in plane["ops"]]
    end = plane["ops"][-1][3]
    plane["ops"].append(("copy.5", "jit(_threefry_split)", end, end + 2))
    rec["inferred"] = {"ragged-dot-none.3": "kin", "copy.5": "user",
                       "fusion.77": "body"}
    # of 2 x 51 + 2: the ragged product's 6 and the copy's 1, twice
    assert reader.read(record, {}) == pytest.approx(100.0 * 14 / 104)
    rec["inferred"] = {}
    assert reader.read(record, {}) == 0.0
    monkeypatch.setattr(scope_reduce, "program_record", lambda: None)
    assert reader.read(record, {}) is None
    assert reader.read({"trace": None}, {}) is None


def test_counter_reader_means_over_the_window():
    from benchmark.readers import run_counter_mean as reader

    run = {"counters": {"moe/load_max_over_mean": [1.5, 2.5]}}
    assert reader.read(run, {"counter": "moe/load_max_over_mean"}) == 2.0
    assert reader.read({}, {"counter": "moe/load_max_over_mean"}) is None


def test_reference_steps_read_the_clipped_and_the_raw_first_gradient():
    """``train_steps`` folds the clip into Adam's update and parks the
    moments on the host (memory); what it reports of step 0 is the batch's
    loss and gradient, raw and clipped to global norm 1."""
    import jax
    import jax.numpy as jnp

    from benchmark.harness import train_lm
    from benchmark.reference import lm_model

    sizes = train_lm.model_sizes(tiny_resolved()["config"])
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 64, (3, 32)),
                         jnp.int32)
    opt = {"learning_rate": 3e-4, "warmup_steps": 2, "num_steps": 10}
    got = lm_model.train_steps(
        lm_model.init_params(sizes, 1), [tokens], sizes, opt)
    (loss, hists), grads = jax.value_and_grad(lm_model.loss_fn, has_aux=True)(
        lm_model.init_params(sizes, 1), tokens, sizes)
    assert float(got["losses"][0]) == pytest.approx(float(loss), rel=1e-6)
    np.testing.assert_array_equal(got["route_hist"], hists)
    whole = jax.device_get(lm_model.leaf_norms(grads))
    for name, norm in whole.items():
        assert float(got["raw_grad_norms"][name]) == pytest.approx(
            float(norm), rel=1e-4, abs=1e-7), name
    total = np.sqrt(sum(float(v) ** 2 for v in whole.values()))
    assert total > 1.0  # so the clip is at work
    for name, norm in whole.items():
        assert float(got["grad_norms"][name]) == pytest.approx(
            float(norm) / total, rel=1e-4, abs=1e-7), name
    # step 0 runs at rate 0: nothing has changed
    assert max(float(v) for v in got["change_norms"].values()) == 0.0


def test_control_script_reads_which_limits_each_fault_breaks():
    from benchmark.harness import control_lm

    out = control_lm.readings(tiny_resolved(), 5, ("fp8", "bf16", "top5"))
    assert out["bf16"]["breaks"] == []  # the stated precision is inside
    assert out["fp8"]["breaks"] and "route_hist_l1_step0" in out["top5"][
        "breaks"]
    assert set(out["fp8"]) == set(TINY_LIMITS) | {"breaks"}
