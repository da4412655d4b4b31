"""The CPU rehearsal of chip_smoke.py, kept as tests, and the rules its chip
run relies on: it fails without a TPU, its last line is the contract's, the
compile cache is placed from outside, nothing on its path falls back behind
the caller's back, and no module starts a child process that needs the device.

The phase functions run here at a tiny size with the Pallas kernels in
interpret mode; the widths, times and kernel agreement that matter come only
from ``python chip_smoke.py`` on the chip.
"""

import ast
import json
import os
import subprocess
import sys

import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = dict(dim=32, heads=2, dim_head=16, depth=1, crop=16, msa_depth=4,
            msa_len=16, batch=1)


def _run(cmd, env=None, cwd=REPO, timeout=600):
    proc = subprocess.run(
        cmd, capture_output=True, text=True, timeout=timeout, cwd=cwd,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})},
    )
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    return proc, lines


# ------------------------------------------------------------- the phases ---


def test_train_phase_takes_steps_and_the_loss_moves(monkeypatch):
    from alphafold2_tpu.ops.pallas import tied_row

    # the tied-row kernel as on the chip, in interpret mode off it
    monkeypatch.setattr(tied_row, "tied_row_available", lambda: True)
    rec = chip_smoke.phase_train(TINY, steps=3, bfloat16=False)
    assert rec["phase"] == "train" and len(rec["losses"]) == 3
    assert len(set(rec["losses"])) == 3 and rec["skipped"] == 0
    assert len(rec["step_s"]) == 2 and rec["compile_s"] > 0
    # interpret mode leaves no Mosaic call: only the chip can say True here,
    # and main() fails the run where it does not
    assert rec["has_tpu_custom_call"] is False
    assert rec["peak_bytes_in_use"] is None  # the CPU keeps no such count


def test_serve_phase_answers_and_reuses_its_executables():
    rec = chip_smoke.phase_serve(
        {**TINY, "serve_msa_depth": 2},
        lengths=(5, 8, 12, 16, 20, 30, 32, 25), buckets=(8, 16, 32),
        bfloat16=False,
    )
    first, second = rec["passes"]
    assert sorted(set(first["buckets"])) == [8, 16, 32]
    assert first["compiles"] == second["compiles"] == 3
    assert len(first["latency_s"]) == len(second["latency_s"]) == 8
    assert rec["pipeline"] == "depth2"
    assert rec["max_batch"] == chip_smoke.SERVE_MAX_BATCH and rec["cut"]


def test_kernel_phase_agrees_with_the_references(monkeypatch):
    from alphafold2_tpu.ops import mla

    # the causal core's splash kernel as on the chip, interpreted off it
    monkeypatch.setattr(mla, "causal_kernel_takes", lambda n: n >= 128)
    rec = chip_smoke.phase_kernels(small=True)
    names = [c["case"] for c in rec["cases"]]
    assert len(names) == 13 and all(c["ok"] for c in rec["cases"])
    for kernel in ("fused_axial", "tied_row", "block_sparse"):
        assert any(n.startswith(kernel) for n in names)
        assert any(n.startswith(kernel) and "masked" in n for n in names)
    # the language model's two: bfloat16 against float32 references
    assert {"mla_causal_core_small", "moe_grouped_matmul_small"} <= set(names)
    # the grouped-query core under both of its masks
    assert {"swa_core_small_global", "swa_core_small_window"} <= set(names)
    # and at the dense hybrid's head width of 64, four query heads a key head
    assert "gqa_core_small_heads_of_64" in names
    # the four cores under a remat that keeps their named results and under
    # one that runs the forward kernel again: the same numbers
    kept = [c["kept_vs_recomputed"] for c in rec["cases"]
            if "kept_vs_recomputed" in c]
    assert kept == [0.0, 0.0, 0.0, 0.0]
    # the ring over two devices (jnp blocks here), one case masked
    assert {"ring_flash_small", "ring_flash_small_masked"} <= set(names)
    # float32 in interpret mode: far inside the chip's tolerance
    assert max(c[k] for c in rec["cases"] if c["dtype"] == "float32"
               for k in ("fwd", "dq", "dk", "dv")) < 1e-5


def test_scan_phase_agrees_with_the_recurrence_at_a_small_size():
    """The chunked scan's phase, small and off the chunk grid's end: every
    error of its bfloat16 products under the chip's tolerance, and a
    tolerance nothing meets fails the phase."""
    small = dict(heads=4, width=8, groups=2, n=16, length=96, chunk=16)
    rec = chip_smoke.phase_ssd_scan(**small)
    assert rec["ok"] and rec["phase"] == "ssd_scan"
    # off the TPU (and off the tile grid) the XLA form ran, and the record
    # says so; on a TPU a fall-back at the cell's shape fails the phase
    assert rec["implementation"] == "xla"
    assert set(rec) >= {"fwd", "dx", "dB", "dC", "ddt", "dA"}
    assert 0 < max(rec[k] for k in ("fwd", "dx", "dB", "dC", "ddt", "dA")) \
        <= chip_smoke.SSD_SCAN_TOL
    with pytest.raises(RuntimeError, match="disagrees with the recurrence"):
        chip_smoke.phase_ssd_scan(**small, tol=1e-9)
    # the two cells' shapes are named, and nothing else is
    assert sorted(chip_smoke.CELL_SCANS.values()) == [
        "ssd_scan_8k", "ssd_scan_8k_one_group_c256"]
    one = chip_smoke.phase_ssd_scan(
        heads=16, width=8, groups=1, n=16, length=96, chunk=32)
    assert one["ok"] and one["phase"] == "ssd_scan" and one["groups"] == 1


def test_kernel_phase_takes_a_filter_by_name():
    rec = chip_smoke.phase_kernels(small=True, only="moe_grouped")
    assert [c["case"] for c in rec["cases"]] == ["moe_grouped_matmul_small"]


def test_kernel_phase_fails_on_disagreement(monkeypatch):
    monkeypatch.setattr(
        chip_smoke, "_ref_attention",
        lambda q, k, v, qm, km, scale: 1.5 * q.astype("float32"),
    )
    with pytest.raises(RuntimeError, match="fused_axial"):
        chip_smoke.phase_kernels(small=True)


def test_four_chip_phase_agrees_with_one_device_on_four_virtual_devices():
    """Rehearsal 2: dp2 x sp2 with ring context parallelism on exactly four
    virtual CPU devices (this process has eight, and ``pod_mesh`` takes all
    there are, so the rehearsal gets a process of its own), with the tied-row
    Pallas kernel on (the child steers its platform predicate; interpret
    mode) — under the mesh it must run inside a shard_map."""
    code = (
        "import json, chip_smoke; "
        "from alphafold2_tpu.ops.pallas import tied_row; "
        "tied_row.tied_row_available = lambda: True; "
        "print(json.dumps(chip_smoke.phase_mesh("
        f"{ {**TINY, 'batch': 2}!r}, steps=3, bfloat16=False, tol=1e-4)))"
    )
    proc, lines = _run(
        [sys.executable, "-c", code],
        env={"XLA_FLAGS": "--xla_force_host_platform_device_count=4"},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(lines[-1])
    assert rec["layout"] == "dp2 x sp2" and rec["context_parallel"] == "ring"
    assert rec["collectives"]["all-reduce"] > 0
    assert rec["collectives"]["collective-permute"] > 0
    assert rec["ring_block_kernels"] == 0  # jnp blocks off the TPU
    assert max(rec["loss_abs_diff"]) <= 1e-4
    assert len(rec["mesh_run"]["losses"]) == 3
    # one compile on the mesh path: step 1 is no slower than a compile
    # would make it (train() places the state on the mesh before step 0)
    assert rec["mesh_run"]["step_s"][0] < rec["mesh_run"]["first_step_s"]


# ------------------------------------------------------------- the script ---


def test_script_fails_where_there_is_no_tpu():
    proc, lines = _run([sys.executable, "chip_smoke.py"])
    assert proc.returncode != 0
    last = json.loads(lines[-1])
    assert last["ok"] is False and "TPU" in last["error"]
    assert not any('"ok": true' in ln for ln in lines)


def test_script_fails_alone_in_a_directory(tmp_path):
    (tmp_path / "chip_smoke.py").write_text(
        open(os.path.join(REPO, "chip_smoke.py")).read()
    )
    proc, lines = _run([sys.executable, "chip_smoke.py"], cwd=str(tmp_path),
                       env={"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert not any('"ok": true' in ln for ln in lines)


def _fake_chip(monkeypatch, count=1, **phases):
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": count}
    monkeypatch.setattr(chip_smoke, "device_record", lambda: device)
    monkeypatch.setattr("alphafold2_tpu.enable_compile_cache", lambda: None)
    calls = []
    for name in ("phase_train", "phase_serve", "phase_kernels",
                 "phase_ssd_scan", "phase_mesh"):
        def phase(name=name, **kwargs):
            calls.append((name, kwargs) if kwargs else name)
            if name in phases:
                return phases[name]()
            return {"phase": name, "has_tpu_custom_call": True}
        monkeypatch.setattr(chip_smoke, name, phase)
    return device, calls


def test_success_line_has_exactly_the_contract_keys(monkeypatch, capsys):
    device, calls = _fake_chip(monkeypatch)
    assert chip_smoke.main([]) == 0
    lines = capsys.readouterr().out.splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": device}
    assert sorted(device) == ["count", "kind", "platform"]
    assert calls == ["phase_train", "phase_serve", "phase_kernels",
                     "phase_ssd_scan",
                     ("phase_ssd_scan", {"groups": 1, "chunk": 256})]
    for ln in lines:  # one JSON object per earlier line
        assert isinstance(json.loads(ln), dict)


def test_four_chip_option_runs_that_phase_and_no_other(monkeypatch, capsys):
    device, calls = _fake_chip(monkeypatch, count=4)
    assert chip_smoke.main(["--four-chips"]) == 0
    assert calls == ["phase_mesh", ("phase_kernels", {"only": "ring_flash"})]
    last = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert last == {"ok": True, "device": device} and device["count"] == 4


@pytest.mark.parametrize("argv,count", [([], 4), (["--four-chips"], 1)])
def test_wrong_chip_count_fails(monkeypatch, capsys, argv, count):
    _, calls = _fake_chip(monkeypatch, count=count)
    assert chip_smoke.main(argv) == 1 and calls == []
    assert json.loads(capsys.readouterr().out.splitlines()[-1])["ok"] is False


def test_a_failed_phase_fails_the_run(monkeypatch, capsys):
    def boom():
        raise RuntimeError("serve blew up")

    _, calls = _fake_chip(monkeypatch, phase_serve=boom)
    assert chip_smoke.main([]) == 1
    last = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert last["ok"] is False and "serve blew up" in last["error"]
    assert "phase_kernels" not in calls


def test_a_train_step_without_a_kernel_fails_the_run(monkeypatch, capsys):
    _fake_chip(monkeypatch, phase_train=lambda: {
        "phase": "train", "has_tpu_custom_call": False})
    assert chip_smoke.main([]) == 1
    last = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert last["ok"] is False and "tpu_custom_call" in last["error"]


# ------------------------------------------------- E: the compile cache ---


def test_cache_dir_from_the_environment_is_left_to_jax(monkeypatch):
    import jax

    import alphafold2_tpu

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    alphafold2_tpu.enable_compile_cache()
    assert updates == []  # no code sets the directory
    assert alphafold2_tpu.compile_cache_dir() == "/some/dir"


def test_cache_dir_defaults_to_the_checkout(monkeypatch, tmp_path):
    import jax

    import alphafold2_tpu

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("HOME", str(tmp_path))  # must not matter
    assert alphafold2_tpu.compile_cache_dir() == os.path.join(
        REPO, ".jax_cache")
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    monkeypatch.setattr(os, "makedirs", lambda *a, **k: None)
    alphafold2_tpu.enable_compile_cache()
    assert updates == [
        ("jax_compilation_cache_dir", os.path.join(REPO, ".jax_cache"))]
    assert os.listdir(tmp_path) == []


def test_cache_path_is_fixed():
    """The path is part of the cache's key: nothing in it may come from the
    user, a temporary name, the process or the clock."""
    src = open(os.path.join(REPO, "alphafold2_tpu", "__init__.py")).read()
    for word in ("tempfile", "getpid", "time", "expanduser", "getuid",
                 "AF2TPU_COMPILE_CACHE"):
        assert word not in src, word
    ignored = open(os.path.join(REPO, ".gitignore")).read().split()
    assert ".jax_cache/" in ignored and "chiprun_out/" in ignored


# ------------------------------------------------------- C: no fallbacks ---


def test_flash_refusal_raises_on_the_tpu_branch(monkeypatch):
    import jax.numpy as jnp

    from alphafold2_tpu.ops import flash

    q = jnp.ones((1, 2, 128, 64))
    k = jnp.ones((1, 4, 128, 64))  # a head count the kernel refuses
    assert flash.flash_attention(q, k, k) is None  # off the chip: by design
    monkeypatch.setattr(flash, "flash_available", lambda: True)
    with pytest.raises((ValueError, NotImplementedError)):
        flash.flash_attention(q, k, k)  # on it: an error, never dense


class _Device:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


@pytest.mark.parametrize("platform,kind,want", [
    ("tpu", "TPU v5 lite", 197e12),
    ("cpu", "cpu", None),  # the field is absent, not estimated
    ("tpu", "TPU v9 mystery", ValueError),
])
def test_peak_flops_is_looked_up_never_estimated(platform, kind, want):
    from alphafold2_tpu.observe import flops

    assert not hasattr(flops, "calibrated_peak_flops")
    if want is ValueError:
        with pytest.raises(ValueError, match="unknown device_kind"):
            flops.device_peak_flops(_Device(platform, kind))
        with pytest.raises(ValueError):
            flops.mfu(1e12, 1.0, device=_Device(platform, kind))
    else:
        assert flops.device_peak_flops(_Device(platform, kind)) == want


def test_native_source_without_the_library_raises(monkeypatch):
    from alphafold2_tpu.config import DataConfig
    from alphafold2_tpu.data import native, pipeline

    monkeypatch.setattr(native, "available", lambda: False)
    with pytest.raises(RuntimeError, match="make -C native"):
        pipeline.make_dataset(DataConfig(source="native"))


def test_grid_mesh_takes_raw_order_only_on_the_cpu(monkeypatch):
    from jax.experimental import mesh_utils

    from alphafold2_tpu.parallel.grid_parallel import make_grid_mesh

    def refuse(*a, **k):
        raise AssertionError("create_device_mesh refused this layout")

    monkeypatch.setattr(mesh_utils, "create_device_mesh", refuse)
    import jax

    make_grid_mesh(1, 2, 2, devices=jax.devices()[:4])  # cpu: raw order
    chips = [_Device("tpu", "TPU v5 lite") for _ in range(4)]
    with pytest.raises(AssertionError, match="refused"):
        make_grid_mesh(1, 2, 2, devices=chips)  # a chip: the error stands


@pytest.mark.parametrize("env,argv,needle", [
    ({}, ["--mode", "no-such-mode"], "no-such-mode"),
    # the one overall deadline: the flagship cannot finish in a second here
    ({"AF2TPU_BENCH_DEADLINE": "1"}, [], "deadline 1s exceeded"),
])
def test_bench_exits_non_zero_on_failure(env, argv, needle):
    proc, lines = _run([sys.executable, "bench.py", *argv], env=env)
    assert proc.returncode == 1, (proc.returncode, proc.stderr[-800:])
    (line,) = lines  # exactly one record
    rec = json.loads(line)
    assert rec["value"] == 0.0 and needle in rec["error"]
    assert "fallback" not in rec and "first_light" not in rec


# ----------------------------------------- D: one process for each chip ---


def _python_files():
    yield os.path.join(REPO, "bench.py")
    yield os.path.join(REPO, "chip_smoke.py")
    for root, _, files in os.walk(os.path.join(REPO, "alphafold2_tpu")):
        for f in files:
            if f.endswith(".py"):
                yield os.path.join(root, f)


def test_no_module_starts_a_child_that_needs_the_device():
    """Static: a parent that has touched JAX holds the chip, so nothing under
    alphafold2_tpu/, bench.py or chip_smoke.py may start a Python child that
    imports JAX. The one place that starts children at all is the auditor's
    lowering/HLO gates, which pin the child to the CPU backend."""
    starters = {}
    for path in _python_files():
        tree = ast.parse(open(path).read())
        for node in ast.walk(tree):
            names = []
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                names = [node.module or ""]
            elif isinstance(node, ast.Attribute) and isinstance(
                    node.value, ast.Name) and node.value.id == "os":
                if node.attr.startswith(("exec", "spawn", "fork", "popen",
                                         "system")):
                    names = ["os." + node.attr]
            for n in names:
                if n.split(".")[0] in ("subprocess", "multiprocessing") \
                        or n.startswith("os."):
                    starters.setdefault(os.path.relpath(path, REPO),
                                        set()).add(n)
    assert starters == {
        "alphafold2_tpu/analysis/jaxpr_audit.py": {"subprocess"}}, starters
    src = open(os.path.join(
        REPO, "alphafold2_tpu", "analysis", "jaxpr_audit.py")).read()
    # every child it starts is pinned to the CPU backend
    assert src.count("subprocess.run(") == src.count(
        'env = dict(os.environ, JAX_PLATFORMS="cpu")') == 2
