"""Numerics telemetry tests: tagged stats under jit match an unjitted
reference, disabled tags add zero ops, NaN triage names the poisoned trunk
block, and the train loop emits the triage report + first_step_s /
per-group-norm / flops metrics end to end."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from alphafold2_tpu.config import Config, DataConfig, ModelConfig, TrainConfig
from alphafold2_tpu.observe import numerics


def tiny_config(depth=1, **train_kw):
    return Config(
        model=ModelConfig(dim=32, depth=depth, heads=2, dim_head=16,
                          max_seq_len=64, bfloat16=False),
        data=DataConfig(crop_len=16, msa_depth=2, msa_len=16, batch_size=1,
                        min_len_filter=8),
        train=TrainConfig(gradient_accumulate_every=1, warmup_steps=1,
                          **train_kw),
    )


# ------------------------------------------------------------ tag mechanics


def test_tag_without_collection_is_identity_and_free():
    x = jnp.arange(6.0).reshape(2, 3)
    assert numerics.tag("t", x) is x
    # zero overhead when disabled: the jaxpr is IDENTICAL to untagged code
    tagged = jax.make_jaxpr(lambda a: numerics.tag("a", a) * 2.0)(x)
    plain = jax.make_jaxpr(lambda a: a * 2.0)(x)
    assert str(tagged) == str(plain)


def test_stats_match_unjitted_reference():
    arr = np.array([[1.0, -2.0, np.nan], [np.inf, 3.0, 0.5]], np.float32)

    def f(a):
        with numerics.collect() as col:
            numerics.tag("x", a)
            return col.stats()

    finite = arr[np.isfinite(arr)]
    for fn in (f, jax.jit(f)):  # eager and jitted agree with numpy
        s = jax.device_get(fn(jnp.asarray(arr)))["x"]
        np.testing.assert_allclose(s["l2"], np.linalg.norm(finite), rtol=1e-6)
        assert s["max_abs"] == 3.0
        assert s["nan_count"] == 1 and s["inf_count"] == 1


def test_tag_order_survives_jit_and_dedupes():
    def f(a):
        with numerics.collect() as col:
            numerics.tag("zz", a)
            numerics.tag("aa", a + 1)
            numerics.tag("zz", a * jnp.nan)
            return col.stats()

    stats = jax.device_get(jax.jit(f)(jnp.ones(3)))
    # jit sorts dict keys in its output pytree; the recorded index is what
    # restores topological (tag) order
    assert [n for n, _ in numerics._ordered(stats)] == ["zz", "aa", "zz#2"]
    assert numerics.first_nonfinite(stats) == "zz#2"


def test_flatten_and_report_helpers():
    with numerics.collect() as col:
        numerics.tag("good", jnp.ones(4))
        numerics.tag("bad", jnp.array([1.0, jnp.inf]))
    stats = col.stats()
    flat = numerics.flatten_stats(stats)
    assert flat["numerics/bad/inf_count"] == 1.0
    assert not any(k.endswith("/index") for k in flat)
    report = numerics.triage_report(stats, step=3)
    assert report["event"] == "nan_triage"
    assert report["step"] == 3
    assert report["first_nonfinite"] == "bad"
    assert report["nonfinite"] == ["bad"]
    assert report["tensors"]["good"]["nan_count"] == 0


def test_collect_disabled_and_tree_stats():
    with numerics.collect(enabled=False) as col:
        numerics.tag("x", jnp.ones(3))
    assert col.stats() == {}
    s = numerics.tree_stats({"a": jnp.ones(4), "b": jnp.full(2, jnp.nan)})
    assert float(s["l2"]) == 2.0 and float(s["nan_count"]) == 2


# ------------------------------------------------------- train-step wiring


def _batch_and_model(cfg):
    from alphafold2_tpu.data.pipeline import SyntheticDataset
    from alphafold2_tpu.train.loop import build_model, init_state

    batch = next(iter(SyntheticDataset(cfg.data, seed=0)))
    model = build_model(cfg)
    return batch, model, init_state(cfg, model, batch)


def _poison(params, key_name):
    """NaN every leaf under the named module subtree."""
    import jax.tree_util as jtu

    flat, _ = jtu.tree_flatten_with_path(params)
    leaves = [
        np.full_like(v, np.nan)
        if any(getattr(k, "key", None) == key_name for k in path) else v
        for path, v in flat
    ]
    return jax.tree.unflatten(jax.tree.structure(params), leaves)


def test_full_mode_step_carries_numerics_and_group_norms():
    from alphafold2_tpu.train.loop import device_put_batch, make_train_step

    cfg = tiny_config()
    batch, model, state = _batch_and_model(cfg)
    step = make_train_step(model, numerics_mode="full")
    _, metrics = step(state, device_put_batch(batch), jax.random.key(0))
    stats = metrics["numerics"]
    assert {"embed.pair", "trunk.layer_0.pair", "distogram.logits",
            "loss.distogram_nll"} <= set(stats)
    assert numerics.first_nonfinite(stats) is None
    assert any(k.startswith("grad_norm/") for k in metrics)
    assert any(k.startswith("param_norm/") for k in metrics)
    assert any(k.startswith("update_norm/") for k in metrics)


def test_triage_names_poisoned_trunk_layer():
    """The ISSUE's acceptance demo: poison one trunk block's weights; the
    triage report names that block as the first non-finite tensor."""
    from alphafold2_tpu.train.loop import device_put_batch, make_triage_step

    cfg = tiny_config(depth=2)
    batch, model, state = _batch_and_model(cfg)
    poisoned = _poison(state.params, "layer_1")
    triage = make_triage_step(model)
    stats = triage(poisoned, device_put_batch(batch), jax.random.key(1))
    report = numerics.triage_report(stats)
    assert report["first_nonfinite"] == "trunk.layer_1.pair"
    assert float(stats["trunk.layer_0.pair"]["nan_count"]) == 0
    assert "grad/trunk" in stats  # per-group gradient stats follow the loss
    # clean params through the same compiled triage: everything finite
    clean = triage(state.params, device_put_batch(batch), jax.random.key(1))
    assert numerics.first_nonfinite(clean) is None


def test_train_loop_triage_and_first_step_metrics(tmp_path):
    """End to end: a poisoned restored checkpoint makes every step skip; the
    loop AOT-compiles (compile_s its own record, with no count of XLA's
    beside it: that leaves out every custom call), logs first_step_s
    instead of the old steps_per_sec=0.0 placeholder, records per-group
    norms, and emits a nan_triage report naming the poisoned block."""
    from alphafold2_tpu.train.checkpoint import CheckpointManager
    from alphafold2_tpu.train.loop import train

    cfg = tiny_config(num_steps=3, log_every=1,
                      checkpoint_dir=str(tmp_path), checkpoint_every=1000)
    _, _, state = _batch_and_model(cfg)
    state = state.replace(params=_poison(state.params, "pair_ff"))
    mgr = CheckpointManager(str(tmp_path), keep=2)
    mgr.save(1, state)
    mgr.wait()
    mgr.close()

    final = train(cfg)  # restores at step 1, runs steps 1 and 2
    assert int(final.skipped) == 2

    with open(os.path.join(str(tmp_path), "metrics.jsonl")) as f:
        records = [json.loads(line) for line in f if line.strip()]
    assert [set(r) - {"step", "time"} for r in records if "compile_s" in r] \
        == [{"compile_s"}]
    assert any("first_step_s" in r for r in records)
    assert not any(r.get("steps_per_sec") == 0.0 for r in records)
    step_recs = [r for r in records if "loss" in r]
    assert any("grad_norm/trunk" in r for r in step_recs)
    triages = [r for r in records if r.get("event") == "nan_triage"]
    assert triages, records
    assert triages[0]["first_nonfinite"].startswith("trunk.layer_0")
    assert triages[0]["numerics/trunk.layer_0.pair/nan_count"] > 0
