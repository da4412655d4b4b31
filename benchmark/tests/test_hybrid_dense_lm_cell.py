"""The fifth model's cell (``train_granite4_h_micro_pp4_seq8k``, kind
``train_hybrid_dense_lm``) from its files alone, and its new readers on the
hand-written record ``data/small_record.json`` (CPU, no trace taken): the
trunk's record carries none of this model's scopes or kernels, so the
readers of a scope or a kernel find nothing there and say so (``None``, never
0), which is also what they find on a program that lacks the model. The
readers on a record that has the scopes: ``tests/test_benchmark_hybrid_dense_lm.py``.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_hybrid_dense_lm_cell.py -q
"""

import importlib
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark.harness import common, scope_reduce  # noqa: E402
from benchmark.tests.test_scope_readers import (  # noqa: E402
    run_with, written_record,
)

CELL = "train_granite4_h_micro_pp4_seq8k"
KIND = "train_hybrid_dense_lm"
NEW = ("mfu_pct", "ssd_scan_roofline_pct", "attn_core_roofline_pct",
       "lm_rest_device_ms", "unscoped_device_pct", "ssm_scan_in_kernel")


def test_the_cell_resolves_from_files_alone():
    resolved = common.resolve(CELL)
    assert resolved["config"]["kind"] == KIND
    assert resolved["cell"] == {
        **resolved["cell"], "config": "granite4_h_micro_train_pp4",
        "traffic": "lm_zipf_seq8k_x1", "chips": 1}
    assert os.path.exists(os.path.join(BENCH, "harness", KIND + ".py"))
    assert [m["name"] for m in resolved["end_to_end"]] == [
        "pairs_per_s", "setup_s"]
    names = [m["name"] for m in resolved["per_layer"]]
    assert len(names) == 20 and len(set(names)) == 20
    assert {f"{stem}.{KIND}" for stem in NEW} <= set(names)


def test_every_metric_the_cell_lists_finds_its_file_and_reader():
    for spec in common.resolve(CELL)["per_layer"]:
        assert os.path.exists(os.path.join(
            BENCH, "metrics", spec["name"] + ".json")), spec["name"]
        reader = importlib.import_module(
            f"benchmark.readers.{spec['reader']}")
        assert callable(reader.read), spec["reader"]
        assert CELL in spec["workloads"]


@pytest.mark.parametrize("stem", NEW)
def test_a_new_reader_finds_nothing_on_a_record_without_the_model(
        stem, monkeypatch):
    record = written_record()
    monkeypatch.setattr(scope_reduce, "program_record", lambda: record)
    resolved = common.resolve(CELL)
    spec = next(m for m in resolved["per_layer"]
                if m["name"] == f"{stem}.{KIND}")
    run = {**run_with(record), "kind": KIND, "config": resolved["config"],
           "traffic": resolved["traffic"], "peaks": resolved["peaks"],
           "device_kind": "TPU v5 lite", "chips": 1, "steps": 0,
           "window_s": 1.0, "counters": {}}
    value = common.read_metric(spec, run)
    if stem in ("lm_rest_device_ms", "unscoped_device_pct"):
        # a remainder and a share of what has no scope of this model: on the
        # trunk's record that is all of the step
        assert value is not None and value > 0
    else:
        assert value is None
