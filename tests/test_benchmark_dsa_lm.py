"""The fifth language-model cell's benchmark pieces, tiny on the CPU: the
driver ``harness/train_dsa_lm.py`` given a cell only as files, what decides
``correct`` (the float8 control and four planted faults come out not
correct, and so does a core that left the kernels), the selection's
comparison, the operation counts, the configuration against the catalog,
the new readers on a hand-written record.

(The tier-1 command collects ``tests/`` only.)
"""

import copy
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
    common, ops_from_shapes_dsa_lm as ops,
)

CELL = "train_keye_vl2_ep8_seq16k"
CONFIG = "keye_vl2_30b_a3b_train_ep8"
KIND = "train_dsa_lm"
EARLIER_CELLS = [
    "train_flagship", "train_mesh_dp2sp2", "train_kanana2_ep8_seq8k",
    "train_smallthinker_ep8_seq16k", "train_nemotron3_nano_ep16_seq8k",
    "train_granite4_h_micro_pp4_seq8k"]
# the catalog row's ``config`` (model-configs guide, Keye-VL-2.0-30B-A3B),
# every key: the file holds each under the same name, three of them reduced
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}
REDUCED = {"num_hidden_layers": 4, "num_experts": 16, "vocab_size": 18992}
TINY = dict(
    hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
    num_key_value_heads=2, head_dim=32, moe_intermediate_size=32,
    num_experts=4, router_width=8, num_experts_per_tok=2, vocab_size=96,
    first_expert=2, pairs_per_step=512)
TINY_SA = dict(indexer_num_heads=4, indexer_head_dim=16, topk=32,
               q_chunk_size=256)
# readings at the tiny size (seeds 1-3; the program through the interpreted
# kernels, seeds 5-6): the program and the reference in bfloat16 read up to
# 0.0015 / 0.058 / 0.028 / 0.016 / 0.018 (loss, gradient, change, routing,
# selection); the float8 control 1.0 on the worst leaf's gradient and change
# and 0.092-0.098 on the selection; the faults, at least, 0.15 on the
# gradient (the indexer reading the stream unstopped; q and k unnormed),
# 0.45 on the loss (its softmax over every causal key), everything (dense)
TINY_LIMITS = {"loss_step0": 0.005, "loss_step1": 0.005, "loss_step2": 0.005,
               "grad_norm_worst_leaf": 0.1, "change_norm_worst_leaf": 0.05,
               "route_hist_l1_step0": 0.03, "select_missed_step0": 0.04,
               "core_fallback_layers": 0}
SEED = 2_500_000_011


def manifest():
    return common.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def the_config():
    return common.load_json(os.path.join(BENCH, "configs", CONFIG + ".json"))


def tiny_resolved():
    resolved = common.resolve(CELL)
    config = resolved["config"]
    config.update(copy.deepcopy(TINY))
    config["sa_config"] = {**config["sa_config"], **TINY_SA}
    config["correct"]["limits"] = dict(TINY_LIMITS)
    resolved["traffic"].update(seq_len=512)
    return resolved


@pytest.fixture(scope="module")
def tiny_run():
    """The driver end to end, the core through the interpreted kernels (on
    the CPU ``core_kernel_takes`` nothing, and a run whose cores fell back
    is not correct: a test below)."""
    from alphafold2_tpu.ops import dsa
    from benchmark.harness import train_dsa_lm

    resolved = tiny_resolved()
    with pytest.MonkeyPatch.context() as patched:
        patched.setattr(dsa, "core_kernel_takes", lambda *shape: True)
        run = train_dsa_lm.run(resolved, SEED, 0.5, False,
                               time.perf_counter())
    return resolved, run


@pytest.fixture(scope="module")
def control():
    from benchmark.harness import control_dsa_lm

    return control_dsa_lm.readings(tiny_resolved(), 1, control_dsa_lm.WHICH)


def test_the_cell_resolves_to_files_of_its_own_kind():
    resolved = common.resolve(CELL)
    assert resolved["config"]["kind"] == KIND
    assert resolved["cell"]["chips"] == 1
    assert resolved["cell"]["traffic"] == "lm_zipf_seq16k"
    assert resolved["traffic"] == {
        **resolved["traffic"], "kind": "lm_zipf", "sequences": 1,
        "seq_len": 16384, "zipf_exponent": 1.0}
    names = {m["name"] for m in resolved["per_layer"]}
    assert {m for m in names if m.endswith("." + KIND)} == {
        f"{stem}.{KIND}" for stem in (
            "mfu_pct", "dsa_indexer_device_ms", "dsa_core_device_ms",
            "dsa_indexer_loss_device_ms", "dsa_core_roofline_pct",
            "dsa_indexer_roofline_pct", "dsa_tiles_visited_pct",
            "dsa_core_in_kernel", "unscoped_device_pct",
            "moe_grouped_matmul_roofline_pct", "lm_rest_device_ms")}
    # the first language model's readers that read this record unchanged
    assert {m for m in names if m.endswith(".train_lm")} == {
        f"{stem}.train_lm" for stem in (
            "moe_dispatch_device_ms", "moe_experts_device_ms",
            "embed_head_loss_device_ms", "expert_load_max_over_mean",
            "update_device_ms")}
    assert {m for m in names if m.endswith(".train")} == {
        f"{stem}.train" for stem in (
            "step_ms_p50", "device_idle_pct", "step_device_ms",
            "idle_attributed_pct", "setup_lower_s", "setup_compile_s",
            "compiles_after_warmup", "inferred_scope_device_pct")}
    assert [m["name"] for m in resolved["end_to_end"]] == [
        "pairs_per_s", "setup_s"]
    for spec in resolved["per_layer"]:
        assert os.path.exists(
            os.path.join(BENCH, "readers", spec["reader"] + ".py"))


def test_the_manifest_gained_one_configuration_and_one_cell():
    m = manifest()  # (a later PR's cells and configurations come after)
    assert [w["name"] for w in m["workloads"]][:7] == EARLIER_CELLS + [CELL]
    assert m["configs"][6]["name"] == CONFIG
    assert sorted(m["configs"][6]["reduced"]) == sorted(REDUCED)
    assert m["configs"][6]["source"] == the_config()["source"]
    assert m["run_seconds"] == 45
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    for entry in m["configs"] + m["workloads"]:
        assert len(entry["why"]) <= 200
    # an earlier metric changed only by this cell's name, last in its list
    for metric in m["per_layer"] + m["end_to_end"]:
        cells = metric.get("workloads", [])
        if CELL in cells and not metric["name"].endswith(KIND):
            assert set(cells[:cells.index(CELL)]) <= set(EARLIER_CELLS)
            assert cells[0] in EARLIER_CELLS
    ours = [p for p in m["per_layer"] if p["name"].endswith(KIND)]
    assert len(ours) == 11 and all(p["workloads"] == [CELL] for p in ours)
    assert len(json.dumps(m, indent=2)) < 64 * 1024


def test_the_configuration_keeps_every_published_width():
    config = the_config()
    for key, value in PUBLISHED.items():
        assert config[key] == REDUCED.get(key, value), key
    assert sorted(config["reduced"]) == sorted(REDUCED)
    for key in REDUCED:
        assert config["published"][key] == PUBLISHED[key]
    # every layer an expert layer, at least 8 experts held, an eighth of
    # the vocabulary, the router at its published width
    assert config["num_experts"] * 8 == config["router_width"] == 128
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert config["num_hidden_layers"] >= 4
    assert "deployment" in config and "indexer" in config["assumed"]
    assert sorted(config["correct"]["limits"]) == sorted(
        config["correct"]["reasons"])


def test_operation_counts_by_hand():
    config = the_config()
    parts = ops.layer_forward_flops(config, 16384)
    assert parts["projections"] == 2 * 2048 * 128 * (64 + 8)
    assert parts["indexer_projections"] == 2 * 2048 * (1024 + 64 + 16)
    # a query reads min(t + 1, 2,048) keys: 1,920.06 on average at 16k
    assert ops.mean_selected(16384, 2048) == (
        2048 * 2049 / 2 + 14336 * 2048) / 16384 == pytest.approx(1920.06,
                                                                 abs=0.01)
    assert parts["core"] == 2 * 32 * 256 * parts["selected_keys"]
    assert parts["routed_row"] == 2 * 3 * 2048 * 768
    # a balanced router sends the held experts 8 x 16/128 of the tokens
    assert ops.formula_routed_rows(config) == 4 * 16384
    step = ops.train_step_flops(config, 16384)
    # a layer's forward at 16k, as PERF.md section 4 sizes the cell: scores
    # 0.28 TF, core 0.52 TF, indexer projections 0.07 TF, q/k/v/o 0.62 TF,
    # held experts 0.16 TF
    tokens = 16384
    assert parts["indexer_key"] * parts["causal_keys"] * tokens \
        == pytest.approx(0.275e12, rel=0.01)
    assert parts["core"] * tokens == pytest.approx(0.516e12, rel=0.01)
    assert parts["indexer_projections"] * tokens == pytest.approx(
        0.074e12, rel=0.01)
    assert parts["projections"] * tokens == pytest.approx(0.618e12, rel=0.01)
    assert step["routed"] / 3 / 4 == pytest.approx(0.155e12, rel=0.01)
    assert step["core"] == 3 * 4 * parts["core"] * tokens
    # the core is bound by operations, not bytes
    assert ops.core_bytes(config, 16384) / 819e9 < step["core"] / 197e12


def test_parameter_count_and_state_bytes_of_the_cut():
    from benchmark.harness import train_dsa_lm
    from benchmark.reference import dsa_lm_model

    shapes = dsa_lm_model.param_shapes(train_dsa_lm.model_sizes(the_config()))
    leaves = []

    def walk(node):
        for v in node.values():
            leaves.append(int(np.prod(v))) if isinstance(v, tuple) else walk(v)

    walk(shapes)
    assert sum(leaves) == 465_391_104  # 465 M, as PERF.md section 4 counts
    assert 7.44e9 < 16 * sum(leaves) < 7.45e9  # weights, gradients, mu, nu


def test_train_dsa_lm_driver_runs_a_cell_given_only_as_files(tiny_run):
    resolved, run = tiny_run
    line = common.result_line(resolved, run, trace=False)
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == {"pairs_per_s", "setup_s"}
    assert line["attempted"] == run["steps"] >= 2 and line["failed"] == 0
    assert sorted(line["compared"]) == sorted(TINY_LIMITS)
    assert line["compared"]["core_fallback_layers"]["value"] == 0
    detail = line["compared"]["select_missed_step0"]["at"]
    assert "the step's sample of its selection holds" in detail
    counters = run["counters"]
    assert counters["dsa/core_in_kernel"] == [1.0] * run["steps"]
    want = (32 * 33 / 2 + 480 * 32) / 512
    assert counters["dsa/keys_selected_mean"][0] == pytest.approx(want)
    assert all(0 < v <= 100 for v in counters["dsa/tiles_visited_pct"])
    assert run["traced_counters"] is None and run["kind"] == KIND
    json.dumps(line)
    # the readers that take the record as it is: the untraced ones answer,
    # the traced ones find no trace
    traced = common.result_line(
        resolved, dict(run, trace=None, device_kind="TPU v5 lite"),
        trace=True)
    assert set(traced["metrics"]) == {
        "step_ms_p50.train", "mfu_pct.train_dsa_lm",
        "expert_load_max_over_mean.train_lm",
        "dsa_tiles_visited_pct.train_dsa_lm",
        "dsa_core_in_kernel.train_dsa_lm"}
    assert traced["metrics"]["dsa_core_in_kernel.train_dsa_lm"][
        "value"] == 1.0


def test_a_core_that_leaves_the_kernels_comes_out_not_correct():
    """On the CPU the core takes the dense XLA form: every layer of every
    step is counted, and the run is not correct by that number alone."""
    from benchmark.harness import train_dsa_lm

    run = train_dsa_lm.run(tiny_resolved(), SEED, 0.3, False,
                           time.perf_counter())
    broke = [k for k, c in run["compared"].items() if not c["ok"]]
    assert broke == ["core_fallback_layers"], run["compared"]
    assert run["compared"]["core_fallback_layers"]["value"] >= 2 * 5


def _unchanged(step):
    """A step that hands back the state it was given."""
    import jax

    return jax.jit(lambda s, b, r: (s, step(s, b, r)[1]))


@pytest.mark.parametrize("fault, breaks", [
    ("unchanged", "change_norm_worst_leaf"),
    ("select_lowest", "select_missed_step0")])
def test_a_fault_under_the_timed_path_comes_out_not_correct(fault, breaks):
    """Faults planted in the program underneath ``train()``: a step that
    leaves the state unchanged (the change's worst leaf reads about 1); a
    selection of the lowest-scored causal keys in place of the highest, in
    the step's own selection, which the sample it carries out shows."""
    import jax.numpy as jnp

    from alphafold2_tpu.ops import dsa
    from benchmark.harness import train_dsa_lm

    select = dsa.select
    with pytest.MonkeyPatch.context() as patched:
        if fault == "select_lowest":
            patched.setattr(dsa, "select", lambda scores, topk: select(
                jnp.where(scores > -jnp.inf, -scores, -jnp.inf), topk))
        run = train_dsa_lm.run(
            tiny_resolved(), SEED, 0.3, False, time.perf_counter(),
            break_step=_unchanged if fault == "unchanged" else None)
    assert run["correct"] is False
    assert not run["compared"][breaks]["ok"], run["compared"]
    if fault == "unchanged":
        assert run["compared"][breaks]["value"] > 0.9


@pytest.mark.parametrize("which", [
    "fp8", "dense_causal", "indexer_no_stop", "kl_all_causal", "no_qk_norm"])
def test_control_and_faults_come_out_not_correct(control, which):
    """The reference in the nearest precision below the stated one, and each
    planted fault, put in the program's place against the float32
    reference: at least one limit catches each; the stated precision
    passes every one."""
    assert control["bf16"]["breaks"] == []
    assert control[which]["breaks"], control[which]
    if which == "dense_causal":
        assert "select_missed_step0" in control[which]["breaks"]
    if which in ("indexer_no_stop", "no_qk_norm"):
        assert "grad_norm_worst_leaf" in control[which]["breaks"]
    assert set(control[which]) == set(TINY_LIMITS) | {"breaks", "indexer_kl"}


def test_the_selection_comparison_counts_pairs():
    from benchmark.harness import train_dsa_lm

    prog = np.zeros((2, 1, 8, 16), np.int32)
    ref = np.zeros_like(prog)
    prog[0, 0, 0, :4] = 0b1011  # 12 pairs
    ref[0, 0, 0, :4] = 0b0011  # of which 8 selected by the reference too
    prog[1, 0, 3, 0] = -1  # all 32 bits: 32 pairs, none in the reference
    number = train_dsa_lm.overlap_numbers(prog, ref)
    value, detail = number["select_missed_step0"]
    assert value == pytest.approx(1 - 8 / 44)
    assert "holds 44 pairs" in detail and "8 of them" in detail
    with pytest.raises(ValueError):
        train_dsa_lm.overlap_numbers(prog, ref[:1])


def test_the_reference_is_read_at_the_words_of_the_steps_sample():
    """``sampled`` takes from the reference's whole packed selection the
    very words ``ops/dsa.py`` ``selection_sample`` takes in the step."""
    import jax.numpy as jnp

    from alphafold2_tpu.ops import dsa
    from benchmark.harness import train_dsa_lm

    packed = np.arange(4 * 1 * 64 * 2048, dtype=np.int32).reshape(
        4, 1, 64, 2048)
    want = np.stack([dsa.selection_sample(jnp.asarray(p)) for p in packed])
    got = train_dsa_lm.sampled(packed)
    assert got.shape == (4, 1, 16, 256)
    np.testing.assert_array_equal(got, want)


# ------------------------------------------------------------- the readers ---

STEP = "jit_step"


@pytest.fixture
def record(monkeypatch):
    """Two executions of a step, each: the core's three kernels 9 (3
    forward, 2 dq, 4 dkv) and its visit lists 1; the indexer's projection 2
    and selection 5 (scores 1, the sort 4); the indexer's loss 6; a ragged
    product 4 under XLA's own name; dispatch 2 + router 1; the head 2;
    optimizer 3; a norm 1; an unscoped copy 1."""
    from benchmark.harness import scope_reduce

    fwd = "jit(step)/jvp(DsaMoeLM)"
    bwd = "jit(step)/transpose(jvp(DsaMoeLM))/jvp(DsaMoeLM)/checkpoint/" \
        "rematted_computation"
    ops_, t = [], 0

    def add(name, scope, ns):
        nonlocal t
        ops_.append((name, scope, t, t + ns))
        t += ns

    for _ in range(2):
        add("dsa_core_fwd.1", f"{fwd}/layer_0/attn/core/dsa_core_fwd", 3)
        add("dsa_core_dq.1", f"{bwd}/layer_0/attn/core/dsa_core_dq", 2)
        add("dsa_core_dkv.1", f"{bwd}/layer_0/attn/core/dsa_core_dkv", 4)
        add("sort.3", f"{fwd}/layer_0/attn/core/jit(argsort)/sort", 1)
        add("fusion.2", f"{bwd}/layer_0/attn/indexer/proj/q_proj/dot", 2)
        add("fusion.3",
            f"{fwd}/layer_0/attn/indexer/closed_call/while/body/scores", 1)
        add("sort.4",
            f"{fwd}/layer_0/attn/indexer/closed_call/while/body/select", 4)
        add("fusion.5", f"{bwd}/layer_0/attn/indexer_loss/while/body/exp", 6)
        add("ragged-dot-none.3", "ragged-dot-none", 4)
        add("sort.1", f"{fwd}/layer_0/moe/dispatch/sort", 2)
        add("fusion.9", f"{fwd}/layer_0/moe/router/dot_general", 1)
        add("fusion.6", f"{fwd}/head/dot_general", 2)
        add("fusion.4", "jit(step)/optimizer/grad_clip/mul", 3)
        add("fusion.7", f"{fwd}/layer_0/ffn_norm/mul", 1)
        add("copy.5", "", 1)
    rec = {
        "devices": {"/device:TPU:0": {
            "ops": ops_,
            "modules": [(f"{STEP}(123)", 0, 37), (f"{STEP}(123)", 37, 74)],
            "steps": []}},
        "host": [], "spans": [], "step_module": STEP,
    }
    monkeypatch.setattr(scope_reduce, "program_record", lambda: rec)
    planes = {"/device:TPU:0": [(n, s, e) for n, _, s, e in ops_]}
    return {"trace": {"planes": planes, "busy_s": 1.0, "window_s": 1.0},
            "kind": KIND, "config": the_config(), "device_kind": "TPU v5 lite",
            "chips": 1, "traffic": {"seq_len": 16384},
            "peaks": common.load_json(
                os.path.join(BENCH, "harness", "peaks.json"))}


def metric(name):
    return common.load_json(os.path.join(BENCH, "metrics", name + ".json"))


def test_block_metrics_read_their_scopes(record):
    from benchmark.readers import scope_paths_device_ms as reader

    names = ("dsa_indexer_device_ms.train_dsa_lm",
             "dsa_core_device_ms.train_dsa_lm",
             "dsa_indexer_loss_device_ms.train_dsa_lm",
             "moe_dispatch_device_ms.train_lm", "moe_experts_device_ms.train_lm",
             "embed_head_loss_device_ms.train_lm",
             "lm_rest_device_ms.train_dsa_lm", "update_device_ms.train_lm")
    read = {n: reader.read(record, metric(n)["params"]) for n in names}
    ms = 1e-6
    assert read == {
        "dsa_indexer_device_ms.train_dsa_lm": pytest.approx(7 * ms),
        "dsa_core_device_ms.train_dsa_lm": pytest.approx(10 * ms),
        "dsa_indexer_loss_device_ms.train_dsa_lm": pytest.approx(6 * ms),
        "moe_dispatch_device_ms.train_lm": pytest.approx(3 * ms),
        "moe_experts_device_ms.train_lm": pytest.approx(4 * ms),
        "embed_head_loss_device_ms.train_lm": pytest.approx(2 * ms),
        # the optimizer, the norm and the unscoped copy
        "lm_rest_device_ms.train_dsa_lm": pytest.approx(5 * ms),
        "update_device_ms.train_lm": pytest.approx(3 * ms),
    }
    # the seven blocks partition a step's 37; the update is a part of the rest
    del read["update_device_ms.train_lm"]
    assert sum(read.values()) == pytest.approx(37 * ms)


def test_core_roofline_counts_the_selected_keys_once_a_step(record):
    from benchmark.readers import kernel_roofline_dsa_lm_pct as reader

    config = the_config()
    spec = metric("dsa_core_roofline_pct.train_dsa_lm")["params"]
    need_s = ops.train_step_flops(config, 16384)["core"] / 197e12
    assert need_s == pytest.approx(0.0314, rel=0.01)  # 6.19 TF a step
    # 2 steps in the trace, 18 ns of the three kernels' events
    assert reader.read(record, spec) == pytest.approx(
        100.0 * need_s * 2 / 18e-9)
    assert reader.read(record, {**spec, "prefixes": ["nothing"]}) is None
    assert reader.read(dict(record, kind="train_swa_lm"), spec) is None
    with pytest.raises(SystemExit, match="no peaks"):
        reader.read(dict(record, device_kind="TPU v9"), spec)


def test_grouped_matmul_roofline_counts_the_traced_rows(record):
    from benchmark.readers import kernel_roofline_dsa_lm_pct as reader

    config = the_config()
    spec = metric("moe_grouped_matmul_roofline_pct.train_dsa_lm")["params"]
    run = dict(record, traced_counters={
        "moe/assignments_here": [70000, 60000, 50000]})
    flops = ops.train_step_flops(config, 16384, 65000)["routed"]
    least_bytes = ops.routed_bytes(config, 65000)
    # three products of 2 x 2,048 x 768 a row, three passes: at 65,000 rows
    # the operations bind (9.3 ms at the peak), not the held weights and
    # the rows (4.3 GB over the three passes, 5.3 ms)
    assert flops == 3 * 65000 * 2 * 3 * 2048 * 768
    assert least_bytes / 819e9 < flops / 197e12
    # 2 steps in the trace (the first two steps' rows), 8 ns of ragged-dot
    assert reader.read(run, spec) == pytest.approx(
        100.0 * flops / 197e12 * 2 / 8e-9)
    with pytest.raises(RuntimeError, match="routing counters of 0"):
        reader.read(dict(record, traced_counters=None), spec)


def test_indexer_roofline_reads_its_scope(record):
    from benchmark.readers import scope_roofline_dsa_lm_pct as reader

    spec = metric("dsa_indexer_roofline_pct.train_dsa_lm")["params"]
    need_s = ops.train_step_flops(the_config(), 16384)["indexer"] / 197e12
    assert reader.read(record, spec) == pytest.approx(
        100.0 * need_s / 7e-9)
    assert reader.read(dict(record, kind="train_lm"), spec) is None


def test_mfu_counts_the_rows_the_window_sent_the_held_experts():
    from benchmark.readers import mfu_from_shapes_dsa_lm as reader

    config = the_config()
    run = {"kind": KIND, "config": config, "chips": 1, "steps": 4,
           "window_s": 4.0, "traffic": {"seq_len": 16384},
           "device_kind": "TPU v5 lite",
           "counters": {"moe/assignments_here": [40000, 30000, 20000, 10000]},
           "peaks": common.load_json(
               os.path.join(BENCH, "harness", "peaks.json"))}
    want = ops.train_step_flops(config, 16384, 25000)["total"]
    assert reader.read(run, {}) == pytest.approx(100.0 * want / 197e12)
    assert reader.read(dict(run, kind="train_swa_lm"), {}) is None


def test_unscoped_share_names_this_models_class(record):
    from alphafold2_tpu.models.dsa_moe_lm import DsaMoeLM
    from benchmark.readers import unscoped_model_device_pct as reader

    spec = metric("unscoped_device_pct.train_dsa_lm")["params"]
    assert spec["model"] == DsaMoeLM.__name__
    # of a step's 37: the ragged product's 4 and the copy's 1
    assert reader.read(record, spec) == pytest.approx(100.0 * 5 / 37)


@pytest.mark.parametrize("name, counter", [
    ("dsa_tiles_visited_pct.train_dsa_lm", "dsa/tiles_visited_pct"),
    ("dsa_core_in_kernel.train_dsa_lm", "dsa/core_in_kernel")])
def test_counter_metrics_mean_over_the_window(name, counter):
    from benchmark.readers import run_counter_mean as reader

    spec = metric(name)
    assert spec["reader"] == "run_counter_mean"
    assert spec["params"]["counter"] == counter
    run = {"counters": {counter: [1.0, 0.5, 0.0, 0.5]}}
    assert reader.read(run, spec["params"]) == 0.5
    assert reader.read({"counters": {}}, spec["params"]) is None
