"""Training-step tests: loss correctness, jitted step runs and learns,
checkpoint round-trip, config overrides. All single-compile, tiny shapes."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alphafold2_tpu.config import Config, DataConfig, MeshConfig, ModelConfig, TrainConfig
from alphafold2_tpu.data.pipeline import SyntheticDataset
from alphafold2_tpu.train.loop import (
    build_model,
    device_put_batch,
    distogram_cross_entropy,
    init_state,
    make_train_step,
)


def tiny_config(**model_kw):
    return Config(
        model=ModelConfig(
            dim=32, depth=1, heads=2, dim_head=16, max_seq_len=64,
            bfloat16=False, **model_kw,
        ),
        data=DataConfig(crop_len=16, msa_depth=2, msa_len=16, batch_size=2,
                        min_len_filter=8),
        train=TrainConfig(gradient_accumulate_every=1, warmup_steps=2),
    )


def test_distogram_cross_entropy_ignore_index():
    logits = jnp.zeros((1, 2, 2, 37))
    labels = jnp.array([[[0, -100], [-100, 5]]])
    loss = distogram_cross_entropy(logits, labels)
    # uniform logits -> CE = log(37) over the 2 valid entries
    assert np.isclose(float(loss), np.log(37), atol=1e-5)
    # all-ignored -> 0, not NaN
    assert float(distogram_cross_entropy(logits, jnp.full((1, 2, 2), -100))) == 0.0


def test_train_step_runs_and_learns():
    cfg = tiny_config()
    ds = iter(SyntheticDataset(cfg.data, seed=0))
    batch = next(ds)
    model = build_model(cfg)
    state = init_state(cfg, model, batch)
    step = make_train_step(model)
    dev = device_put_batch(batch)
    rng = jax.random.key(0)

    losses = []
    for i in range(8):
        rng, r = jax.random.split(rng)
        state, metrics = step(state, dev, r)
        losses.append(float(metrics["loss"]))
        assert bool(metrics["grads_ok"])
    # same batch repeated: loss must drop
    assert losses[-1] < losses[0], losses
    assert int(state.skipped) == 0


@pytest.fixture
def tiny_step_setup():
    """Everything the guarded tests below must NOT do inside the guard:
    data synthesis, param init (jax.random.key transfers its seed scalar),
    step construction and the explicit device_put of the batch."""
    cfg = tiny_config()
    batch = next(iter(SyntheticDataset(cfg.data, seed=0)))
    model = build_model(cfg)
    state = init_state(cfg, model, batch)
    step = make_train_step(model)
    return model, state, step, device_put_batch(batch), jax.random.key(0)


def test_train_step_transfer_guard_clean(
    tiny_step_setup, no_implicit_transfers
):
    """Compile + execute the train step under jax.transfer_guard
    ("disallow"): the jitted step must not depend on any implicit
    host->device transfer (flax's python-int TrainState.step was exactly
    such a leak until init_state pinned it on device)."""
    _, state, step, dev, rng = tiny_step_setup
    state, metrics = step(state, dev, rng)
    state, metrics = step(state, dev, rng)
    assert bool(metrics["grads_ok"])
    assert np.isfinite(float(metrics["loss"]))


@pytest.mark.slow
def test_train_grad_strict_promotion(tiny_step_setup, strict_promotion):
    """Forward + distogram loss + backward trace cleanly under strict
    dtype promotion — the first-party surface of the train step (the optax
    update is waived upstream: see analysis/targets.py train_step
    allow_reasons)."""
    from alphafold2_tpu.train.loop import distogram_cross_entropy
    from alphafold2_tpu.utils.structure import get_bucketed_distance_matrix

    model, state, _, dev, rng = tiny_step_setup

    def loss_fn(params):
        logits = model.apply(
            params, dev["seq"], dev.get("msa"), mask=dev["mask"],
            msa_mask=dev.get("msa_mask"), deterministic=False,
            rngs={"dropout": rng},
        )
        labels = get_bucketed_distance_matrix(dev["coords"], dev["mask"])
        return distogram_cross_entropy(logits, labels)

    loss, grads = jax.value_and_grad(loss_fn)(state.params)
    assert np.isfinite(float(loss))
    assert all(
        bool(jnp.all(jnp.isfinite(g))) for g in jax.tree.leaves(grads)
    )


def test_train_step_skips_nonfinite():
    cfg = tiny_config()
    ds = iter(SyntheticDataset(cfg.data, seed=0))
    batch = next(ds)
    model = build_model(cfg)
    state = init_state(cfg, model, batch)
    step = make_train_step(model)
    # poison one parameter leaf -> non-finite forward -> non-finite grads
    flat = jax.tree.leaves(state.params)
    poisoned = jax.tree.unflatten(
        jax.tree.structure(state.params),
        [l.at[(0,) * l.ndim].set(np.nan) if i == 0 else l
         for i, l in enumerate(flat)],
    )
    # snapshot before the step: the step donates its input state, so the
    # poisoned device buffers are deleted after the call
    before = [np.asarray(l) for l in jax.tree.leaves(poisoned)]
    bad_state = state.replace(params=poisoned)
    state2, metrics = step(bad_state, device_put_batch(batch), jax.random.key(1))
    assert not bool(metrics["grads_ok"])
    assert int(state2.skipped) == 1
    # params unchanged on skip (grads zeroed; only opt-state counters move)
    for a, b in zip(before, jax.tree.leaves(state2.params)):
        assert np.allclose(a, b, equal_nan=True)


def test_checkpoint_roundtrip(tmp_path):
    from alphafold2_tpu.train.checkpoint import CheckpointManager

    cfg = tiny_config()
    ds = iter(SyntheticDataset(cfg.data, seed=0))
    batch = next(ds)
    model = build_model(cfg)
    state = init_state(cfg, model, batch)
    mgr = CheckpointManager(str(tmp_path / "ckpt"), keep=2)
    mgr.save(3, state)
    mgr.wait()
    restored, step = mgr.maybe_restore(state)
    assert step == 3
    for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(restored.params)):
        assert np.allclose(a, b)
    mgr.close()


def test_config_overrides_and_roundtrip():
    cfg = Config()
    cfg2 = cfg.apply_overrides(
        ["model.depth=12", "train.learning_rate=1e-4", "model.remat=true",
         "data.source=synthetic"]
    )
    assert cfg2.model.depth == 12
    assert cfg2.model.remat is True
    assert np.isclose(cfg2.train.learning_rate, 1e-4)
    cfg3 = Config.from_json(cfg2.to_json())
    assert cfg3.model.depth == 12


@pytest.mark.slow
def test_ingraph_multistep_matches_sequential():
    """bench.py's lax.scan-chained stepping == the same steps dispatched
    one jit call at a time (same rng schedule, same params)."""
    cfg = tiny_config()
    batch = next(iter(SyntheticDataset(cfg.data, seed=3)))
    model = build_model(cfg)
    raw_step = make_train_step(model, mesh=None, jit=False)
    dev_batch = device_put_batch(batch)
    rng = jax.random.key(11)
    keys = jax.random.split(rng, 3)

    state_a = init_state(cfg, model, batch)
    seq_step = jax.jit(raw_step)
    for r in keys:
        state_a, _ = seq_step(state_a, dev_batch, r)

    state_b = init_state(cfg, model, batch)

    def multi(state, batch, ks):
        def body(st, r):
            st, metrics = raw_step(st, batch, r)
            return st, metrics["loss"]

        return jax.lax.scan(body, state, ks)

    state_b, losses = jax.jit(multi)(state_b, dev_batch, keys)
    assert losses.shape == (3,)
    for a, b in zip(jax.tree.leaves(state_a.params), jax.tree.leaves(state_b.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_device_prefetch_order_and_exhaustion():
    from alphafold2_tpu.train.loop import device_prefetch

    batches = [{"seq": np.full((1, 4), i)} for i in range(5)]
    got = [int(b["seq"][0, 0]) for b in device_prefetch(iter(batches), size=2)]
    assert got == [0, 1, 2, 3, 4]
    # shorter than the prefetch depth
    got = [int(b["seq"][0, 0]) for b in device_prefetch(iter(batches[:1]), size=3)]
    assert got == [0]
    assert list(device_prefetch(iter([]), size=2)) == []
