"""Tests of the benchmark itself, all on the CPU at tiny sizes.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

They stand under ``benchmark/`` because the benchmark's PR may add files
nowhere else; the repository's tier-1 command collects ``tests/`` only.
"""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark.harness import (  # noqa: E402
    common, correct, ops_from_shapes, trace_reduce, traffic,
)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TINY = dict(dim=32, heads=2, dim_head=16, depth=1, crop=16, msa_depth=3,
            msa_len=16, max_seq_len=32)
# at the tiny size the program's bfloat16 reads up to 0.06 on the worst
# leaf's gradient and the fp8 control 0.6 (seeds 1, 2)
TINY_LIMITS = {"loss_step0": 0.01, "loss_step1": 0.01, "loss_step2": 0.01,
               "grad_norm_worst_leaf": 0.2, "change_norm_worst_leaf": 0.05}
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def manifest():
    return common.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def stand_in(tmp_path, mesh=None, chips=1):
    """A directory standing in for ``benchmark/``: a tiny configuration, a
    traffic mix and the per-layer metrics as files, found by name through a
    manifest of its own. Nothing under the real ``benchmark/`` is edited."""
    bench = tmp_path / "benchmark"
    for sub in ("configs", "traffic", "metrics", "harness"):
        (bench / sub).mkdir(parents=True)
    config = common.load_json(
        os.path.join(BENCH, "configs", "flagship_train.json"))
    config.update(TINY)
    config["correct"]["limits"] = dict(TINY_LIMITS)
    if mesh:
        config["mesh"] = mesh
    (bench / "configs" / "tiny_train.json").write_text(json.dumps(config))
    (bench / "traffic" / "tiny_crops.json").write_text(json.dumps(
        {"kind": "train_crops", "fill": 1.0, "msa_mutation_rate": 0.15}))
    shutil.copy(os.path.join(BENCH, "harness", "peaks.json"),
                bench / "harness" / "peaks.json")
    per_layer = []
    for m in manifest()["per_layer"]:
        if "train_flagship" in m["workloads"]:
            shutil.copy(os.path.join(BENCH, "metrics", m["name"] + ".json"),
                        bench / "metrics" / (m["name"] + ".json"))
            per_layer.append({**m, "workloads": ["tiny_cell"]})
    man = {
        "configs": [{"name": "tiny_train",
                     "file": "benchmark/configs/tiny_train.json"}],
        "workloads": [{"name": "tiny_cell", "config": "tiny_train",
                       "traffic": "tiny_crops", "chips": chips}],
        "end_to_end": [
            {"name": "pairs_per_s", "unit": "pairs/s",
             "workloads": ["tiny_cell"]},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": per_layer,
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    return common.resolve("tiny_cell", bench_dir=str(bench))


def tiny_run(tmp_path, trace=False, break_step=None, **kw):
    import time

    from benchmark.harness import train

    resolved = stand_in(tmp_path, **kw)
    run = train.run(resolved, 2_500_000_011, 1.0, trace,
                    time.perf_counter(), break_step=break_step)
    return resolved, run


def test_manifest_resolves_every_name_to_a_file_and_back():
    man = manifest()
    assert man["paths"] == ["benchmark"]
    for cell in man["workloads"]:
        resolved = common.resolve(cell["name"])
        assert resolved["config"]["kind"] in ("train", "serve")
        assert resolved["per_layer"], cell["name"]
        assert any(m["name"] == "setup_s" for m in resolved["end_to_end"])
        assert len(resolved["end_to_end"]) >= 2

    def stems(sub):
        return {f[:-5] for f in os.listdir(os.path.join(BENCH, sub))}

    assert stems("configs") == {c["name"] for c in man["configs"]}
    assert stems("traffic") == {w["traffic"] for w in man["workloads"]}
    assert stems("metrics") == {m["name"] for m in man["per_layer"]}
    for c in man["configs"]:
        on_file = common.load_json(os.path.join(ROOT, c["file"]))
        assert on_file["reduced"] == c["reduced"]
        assert on_file["source"] == c["source"]
    cells = {w["name"] for w in man["workloads"]}
    e2e = {m["name"] for m in man["end_to_end"]}
    for m in man["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= cells


def test_names_and_units_use_only_the_allowed_characters():
    man = manifest()
    groups = {k: [x["name"] for x in man[k]] for k in (
        "configs", "workloads", "end_to_end", "per_layer")}
    metrics = groups["end_to_end"] + groups["per_layer"]
    assert len(set(metrics)) == len(metrics)
    assert all(len(set(g)) == len(g) for g in groups.values())
    names = sum(groups.values(), [w["traffic"] for w in man["workloads"]])
    names += [k for c in man["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    for m in man["end_to_end"] + man["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in man["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
    assert 1 <= man["run_seconds"] <= 51


def test_same_seed_same_inputs_and_every_seed_the_same_shapes():
    sizes = {"crop": 16, "msa_depth": 3, "msa_len": 16, "batch": 2}
    mix = common.load_json(os.path.join(BENCH, "traffic", "full_crops.json"))
    big = 2**31 + 12345
    a = next(traffic.train_batches(mix, sizes, traffic.seed31(big)))
    b = next(traffic.train_batches(mix, sizes, traffic.seed31(big)))
    c = next(traffic.train_batches(mix, sizes, traffic.seed31(big + 1)))
    assert all((a[k] == b[k]).all() for k in a)
    assert (a["seq"] != c["seq"]).any()
    assert {k: v.shape for k, v in a.items()} == {
        k: v.shape for k, v in c.items()}
    assert a["mask"].all() and a["msa_mask"].all()
    assert (a["seq"][0] != a["seq"][1]).any()  # rows that all differ


def test_reference_weights_fit_the_programs_parameter_tree():
    import jax

    from alphafold2_tpu.train import loop
    from benchmark.harness import train
    from benchmark.reference import model as ref_model

    config = dict(common.load_json(
        os.path.join(BENCH, "configs", "flagship_train.json")), **TINY)
    cfg = train.program_config(config, 0)
    state = jax.eval_shape(
        lambda: loop.tiny_init_state(cfg, loop.build_model(cfg)))
    theirs = jax.tree.map(lambda x: tuple(x.shape), state.params)
    assert theirs == ref_model.param_shapes(train.model_sizes(config))


def test_train_driver_runs_a_cell_given_only_as_files(tmp_path, capsys):
    resolved, run = tiny_run(tmp_path)
    line = common.result_line(resolved, run, trace=False)
    common.emit(line)
    captured = capsys.readouterr()
    last = json.loads(captured.out.strip().splitlines()[-1])
    assert list(last)[:5] == KEYS and list(last)[-1] == "compared"
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 1
    assert set(last["metrics"]) == {"pairs_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in last["metrics"].values())
    assert set(last["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert captured.err.strip().splitlines()[-1].startswith("compared ")
    assert set(last["compared"]) == set(TINY_LIMITS)
    # the per-layer readers on the same run: the trace's reader finds
    # nothing to read and is left out, it never reads 0
    traced = common.result_line(
        resolved, dict(run, trace=None, device_kind="TPU v5 lite"),
        trace=True)
    assert set(traced["metrics"]) == {"step_ms_p50.train", "mfu_pct.train"}


def test_mfu_reader_refuses_a_device_without_published_peaks(tmp_path):
    resolved = stand_in(tmp_path)
    spec = next(m for m in resolved["per_layer"]
                if m["name"] == "mfu_pct.train")
    run = {"kind": "train", "config": resolved["config"], "chips": 1,
           "steps": 10, "window_s": 5.0, "peaks": resolved["peaks"]}
    with pytest.raises(SystemExit, match="no peaks"):
        common.read_metric(spec, dict(run, device_kind="cpu"))
    value = common.read_metric(spec, dict(run, device_kind="TPU v5 lite"))
    flops = ops_from_shapes.train_step_flops(resolved["config"])["total"]
    assert value == pytest.approx(100 * flops * 2 / 197e12)


def _unchanged_state(step):
    import jax

    return jax.jit(lambda s, b, r: (s, step(s, b, r)[1]))


def _half_the_batch(step):
    import jax

    def broken(s, b, r):
        n = b["mask"].shape[1]
        return step(s, {**b, "mask": b["mask"].at[:, n // 2:].set(False)}, r)

    return jax.jit(broken)


@pytest.mark.parametrize("fault,fails", [
    (_unchanged_state, {"grad_norm_worst_leaf", "change_norm_worst_leaf"}),
    (_half_the_batch, {"grad_norm_worst_leaf"}),
])
def test_a_fault_under_the_timed_path_comes_out_not_correct(
        tmp_path, fault, fails):
    _, run = tiny_run(tmp_path, break_step=fault)
    assert run["correct"] is False
    out = {k for k, c in run["compared"].items() if not c["ok"]}
    assert fails <= out, run["compared"]


def test_the_fp8_control_comes_out_not_correct(tmp_path):
    from benchmark.harness import control

    resolved = stand_in(tmp_path)
    for seed in (1, 2, 3):
        got = control.readings(resolved, seed, ["fp8", "bf16"])
        as_numbers = lambda side: {k: (v["value"], v["at"])
                                   for k, v in got[side].items()}
        _, ok = correct.judge(as_numbers("fp8"), TINY_LIMITS)
        assert ok is False, got["fp8"]
        _, ok = correct.judge(as_numbers("bf16"), TINY_LIMITS)
        assert ok is True, got["bf16"]


def test_worst_leaf_is_a_gap_of_norms_against_the_larger_of_leaf_and_median():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-6}
    worst, where = correct.worst_leaf({"a": 1.0, "b": 2.2, "c": 1e-3}, ref)
    assert where == "b" and worst == pytest.approx(0.1)
    worst, where = correct.worst_leaf({"a": 1.0, "b": 2.0, "c": 0.5}, ref)
    assert where == "c" and worst == pytest.approx(0.5 - 1e-6)  # median 1
    worst, where = correct.worst_leaf(
        {"a": float("nan"), "b": 2.0, "c": 1e-6}, ref)
    assert where == "a" and worst != worst
    compared, ok = correct.judge({"x": (float("nan"), "")}, {"x": 1.0})
    assert ok is False


def test_trace_reduce_on_the_recorded_trace():
    from jax.profiler import ProfileData

    with open(os.path.join(BENCH, "tests", "data",
                           "small_trace.textproto")) as f:
        profile = ProfileData.from_text_proto(f.read())
    planes = trace_reduce.device_ops(profile)
    assert list(planes) == ["/device:TPU:0"]
    events = planes["/device:TPU:0"]
    assert trace_reduce.busy_ns(events) == 19000.0
    assert trace_reduce.window_ns(events) == 30000.0
    assert trace_reduce.idle_gaps(events) == [
        ("fusion.1", 2000.0), ("copy.3", 9000.0)]
    assert trace_reduce.kernel_ns(events, ["flash_attention"]) == 5500.0
    own = trace_reduce.self_times(events)
    assert own["while.2"] == 3500.0 and own["flash_attention_fwd"] == 3000.0
    assert sum(own.values()) == 19000.0
    summary = trace_reduce.summarize(profile)
    assert summary["busy_s"] == 19000e-9 and summary["window_s"] == 30000e-9
    assert summary["breakdown"]["idle_gaps"][0] == ["after copy.3", 9000e-9]
    assert trace_reduce.executions(events, ["flash_attention"]) == 1
    assert trace_reduce.short_name(
        "%flash_attention.13 = (bf16[1,8,65536,64]{3,2,1,0:T(8,128)(2,1)}, "
        "f32[1,8,65536,128]{3,2,1,0}) custom-call(bf16[1,8,65536,64] %x)"
    ) == "flash_attention.13 bf16[1,8,65536,64]"
    from benchmark.readers import device_idle_pct

    assert device_idle_pct.read({"trace": summary}, {}) == pytest.approx(
        100 * 11000 / 30000)
    assert device_idle_pct.read({"trace": None}, {}) is None
    # the kernels' roofline: 5.37e12 attention operations a flagship step
    # (the last layer's MSA<-pair is dead and not counted) are 27.3 ms at
    # the peak, here against 5,500 ns of kernel events
    resolved = common.resolve("train_flagship")
    spec = next(m for m in resolved["per_layer"]
                if m["name"] == "attn_kernels_roofline_pct.train")
    run = {"trace": summary, "kind": "train", "config": resolved["config"],
           "chips": 1, "device_kind": "TPU v5 lite",
           "peaks": resolved["peaks"]}
    cross = 4 * 65536 * 4096 * 512
    by_hand = 3 * (2 * (2 * 4 * 65536 * 256 * 512 + 4 * 4096 * 256 * 512
                        + cross) + cross)
    assert common.read_metric(spec, run) == pytest.approx(
        100 * (by_hand / 197e12) / 5500e-9)
    spec["params"] = dict(spec["params"], prefixes=["no_such_kernel"])
    assert common.read_metric(spec, run) is None


def test_ops_from_shapes_by_hand_and_against_xla():
    config = common.load_json(
        os.path.join(BENCH, "configs", "flagship_train.json"))
    blocks = ops_from_shapes.forward_blocks(config, 256, 16, 256)
    # the flagship's cross-attention, one direction: QK^T and PV, two
    # operations a multiply-add, 65,536 x 4,096 pairs, 8 heads x 64
    by_hand = 2 * 2 * 65536 * 4096 * 64 * 8
    assert blocks["pair_from_msa"]["attention"] == by_hand
    assert blocks["msa_from_pair"]["attention"] == by_hand
    assert by_hand == pytest.approx(5.5e11, rel=0.01)
    # against XLA's own count where XLA sees everything: the program's
    # forward pass, dense attention, on the CPU. XLA also counts the
    # elementwise work the shape count leaves out (norms, softmax, GELU),
    # which at dim 64 is 15-19% (a share that falls as 1 / dim), so it reads
    # higher, never lower. At depth 1 the dead MSA update is a fifth of the
    # layer: counting it would put the shape count ABOVE XLA's
    import jax

    from alphafold2_tpu.train import loop
    from benchmark.harness import train
    from benchmark.reference import model as ref_model

    for depth in (1, 2):
        tiny = dict(config, dim=64, heads=2, dim_head=32, depth=depth,
                    crop=32, msa_depth=4, msa_len=32, max_seq_len=64)
        cfg = train.program_config(tiny, 0)
        model = loop.build_model(cfg)
        params = ref_model.init_params(train.model_sizes(tiny), 0)
        batch = next(traffic.train_batches(
            {"kind": "train_crops", "fill": 1.0, "msa_mutation_rate": 0.15},
            train.data_sizes(tiny), 0))
        compiled = jax.jit(lambda p, s, m, k, mk: model.apply(
            p, s, m, mask=k, msa_mask=mk)).lower(
            params, batch["seq"], batch["msa"], batch["mask"],
            batch["msa_mask"]).compile()
        cost = compiled.cost_analysis()
        cost = cost[0] if isinstance(cost, (list, tuple)) else cost
        counted = ops_from_shapes.forward_flops(tiny, 32, 4, 32)["total"]
        assert counted <= cost["flops"] <= 1.20 * counted, (
            depth, counted, cost["flops"])


def test_run_py_refuses_a_cpu_and_names_it():
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "train_flagship", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=ROOT,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr and proc.stdout.strip() == ""


def test_run_py_fails_where_only_the_benchmark_is(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "train_flagship",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ""},
        cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


MESH_CHILD = """
import json, sys, time
sys.path.insert(0, {root!r}); sys.path.insert(0, {tests!r})
import test_benchmark as t
from pathlib import Path
resolved, run = t.tiny_run(Path({tmp!r}), mesh={{"dp": 2, "sp": 2}}, chips=4)
import jax
print(json.dumps({{"correct": run["correct"], "devices": len(jax.devices()),
                  "steps": run["steps"], "compared": run["compared"]}}))
"""


def test_mesh_dp2_sp2_is_a_data_only_addition(tmp_path):
    """The train driver honours ``mesh: {dp, sp}`` of a configuration file:
    four virtual CPU devices, in a child so this process keeps its one."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    proc = subprocess.run(
        [sys.executable, "-c", MESH_CHILD.format(
            root=ROOT, tests=os.path.join(BENCH, "tests"),
            tmp=str(tmp_path))],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["devices"] == 4 and last["steps"] >= 1
    assert last["correct"] is True, last["compared"]
