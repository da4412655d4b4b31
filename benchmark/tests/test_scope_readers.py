"""The readers of the program's own trace record, on the hand-written record
``data/small_record.json`` (CPU, no trace taken).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_scope_readers.py -q
"""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark.harness import common, scope_reduce  # noqa: E402

CELLS = ["train_flagship", "train_mesh_dp2sp2"]
# metric -> (its cells, what it reads on the written record)
NEW = {
    "cross_attn_device_ms.train": (CELLS, 0.5),
    "pair_axial_device_ms.train": (CELLS, 0.25),
    "msa_axial_device_ms.train": (CELLS, 0.0),
    "feedforward_device_ms.train": (CELLS, 0.05),
    "model_rest_device_ms.train": (CELLS, 0.1),
    "outside_model_device_ms.train": (CELLS, 0.11),
    "unscoped_device_pct.train": (CELLS, 100 * 50 / 1010),
    "step_device_ms.train": (CELLS, 1.0),
    "idle_attributed_pct.train": (CELLS, 100 * 60 / 90),
    "setup_lower_s.train": (["train_flagship"], 8.0),
    "setup_compile_s.train": (["train_flagship"], 2.0),
    "compiles_after_warmup.train": (CELLS, 1),
    "collective_exposed_ms.train": (["train_mesh_dp2sp2"], 0.05),
}


def written_record() -> dict:
    """``data/small_record.json`` with its microseconds as nanoseconds."""
    record = common.load_json(
        os.path.join(BENCH, "tests", "data", "small_record.json"))
    for plane in record["devices"].values():
        for key in ("ops", "modules", "steps"):
            plane[key] = [(*row[:-2], 1e3 * row[-2], 1e3 * row[-1])
                          for row in plane[key]]
    record["host"] = [(n, 1e3 * a, 1e3 * b, t, args)
                      for n, a, b, t, args in record["host"]]
    return record


def run_with(record) -> dict:
    """The part of the driver's record of a traced run the readers use."""
    return {"trace": {"planes": {
        name: [(o[0], o[2], o[3]) for o in plane["ops"]]
        for name, plane in record["devices"].items()}}}


def specs() -> dict:
    man = common.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    return {m["name"]: {**m, **common.load_json(os.path.join(
        BENCH, "metrics", m["name"] + ".json"))} for m in man["per_layer"]}


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_on_the_written_record(name, monkeypatch):
    spec = specs()[name]
    assert spec["workloads"] == NEW[name][0]
    record = written_record()
    monkeypatch.setattr(scope_reduce, "program_record", lambda: record)
    value = common.read_metric(spec, run_with(record))
    assert value == pytest.approx(NEW[name][1])


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_finds_nothing_where_the_program_keeps_no_record(
        name, monkeypatch):
    """The parent of the PR that added the record has no ``last_record``;
    a run without ``--trace 1`` has no trace: ``None``, never a raise."""
    from alphafold2_tpu.observe import profiler

    spec = specs()[name]
    record = written_record()
    monkeypatch.setattr(profiler, "_LAST", None, raising=False)
    assert common.read_metric(spec, run_with(record)) is None
    monkeypatch.delattr(profiler, "last_record", raising=False)
    assert scope_reduce.program_record() is None
    assert common.read_metric(spec, run_with(record)) is None
    monkeypatch.setattr(scope_reduce, "program_record", lambda: record)
    assert common.read_metric(spec, {"trace": None}) is None


def test_the_six_blocks_partition_the_plane_and_sum_to_the_step():
    plane = scope_reduce.first_plane(written_record())
    sums = scope_reduce.by_block(plane)
    assert sum(sums["groups"].values()) == sums["total"] == 1010e3
    assert set(sums["groups"]) == set(scope_reduce.ALL_GROUPS)
    assert sum(sums["blocks"].values()) == sums["total"]


def test_block_reader_raises_when_record_and_planes_disagree(monkeypatch):
    record = written_record()
    monkeypatch.setattr(scope_reduce, "program_record", lambda: record)
    run = run_with(record)
    next(iter(run["trace"]["planes"].values())).pop(4)  # 200 of 1010 us
    with pytest.raises(RuntimeError, match="not the same trace"):
        common.read_metric(specs()["cross_attn_device_ms.train"], run)


def test_set_up_readers_find_nothing_on_a_path_without_aot(monkeypatch):
    record = written_record()
    record["spans"] = [e for e in record["spans"]
                       if e["name"] == "train.step"]
    monkeypatch.setattr(scope_reduce, "program_record", lambda: record)
    assert common.read_metric(
        specs()["setup_lower_s.train"], run_with(record)) is None


def test_collective_reader_finds_nothing_on_one_chip(monkeypatch):
    record = written_record()
    plane = scope_reduce.first_plane(record)
    plane["ops"] = [o for o in plane["ops"]
                    if not o[0].startswith("all-reduce")]
    monkeypatch.setattr(scope_reduce, "program_record", lambda: record)
    assert common.read_metric(
        specs()["collective_exposed_ms.train"], run_with(record)) is None


def test_mesh_cell_is_the_flagship_on_four_chips():
    flag = common.resolve("train_flagship")
    mesh = common.resolve("train_mesh_dp2sp2")
    assert mesh["cell"]["chips"] == 4
    assert mesh["config"]["mesh"] == {"dp": 2, "sp": 2}
    assert mesh["traffic"] == flag["traffic"]
    same = [k for k in flag["config"]
            if k not in ("mesh", "source", "assumed", "deployment", "correct")]
    assert all(mesh["config"][k] == flag["config"][k] for k in same)
    names = {m["name"] for m in mesh["per_layer"]}
    assert "collective_exposed_ms.train" in names
    assert "attn_kernels_roofline_pct.train" not in names
    assert not {"setup_lower_s.train", "setup_compile_s.train"} & names
    assert {m["name"] for m in mesh["end_to_end"]} == {
        "pairs_per_s", "setup_s"}
    assert json.dumps(mesh["config"]["correct"]["limits"])
