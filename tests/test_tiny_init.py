"""tiny_init_state invariant: initializing at tiny data shapes produces the
BIT-IDENTICAL TrainState to full-size init.

Param shapes (and flax's shape-driven initializer values + rng consumption
order) depend only on the model config, never on crop/MSA batch shapes —
this is what lets every driver skip the full-size init compile (measured
1348s at crop 256 on CPU, vs 49s for the training-step compile itself).
"""

import jax
import numpy as np
import pytest

from alphafold2_tpu.config import Config, DataConfig, ModelConfig, TrainConfig
from alphafold2_tpu.data.pipeline import SyntheticDataset
from alphafold2_tpu.train.loop import (
    build_model,
    init_state,
    tiny_batch_like,
    tiny_init_state,
)


def _cfg(**data_kw):
    return Config(
        model=ModelConfig(
            dim=32, depth=1, heads=2, dim_head=16, max_seq_len=128,
            msa_tie_row_attn=True,
        ),
        data=DataConfig(**data_kw),
        train=TrainConfig(),
    )


def _assert_identical(a, b):
    la, lb = jax.tree.leaves(a.params), jax.tree.leaves(b.params)
    assert len(la) == len(lb)
    assert all(np.array_equal(x, y) for x, y in zip(la, lb))


def test_tiny_init_matches_full_init():
    cfg = _cfg(crop_len=48, msa_depth=4, msa_len=48, batch_size=2,
               min_len_filter=48)
    batch = next(iter(SyntheticDataset(cfg.data, seed=0)))
    model = build_model(cfg)
    full = init_state(cfg, model, batch)
    _assert_identical(full, tiny_init_state(cfg, model, batch))  # sliced
    _assert_identical(full, tiny_init_state(cfg, model))  # synthetic


def test_tiny_init_preserves_plm_feature_structure():
    # the embedds width sizes embedd_project's kernel: the sliced batch must
    # carry it through (a synthetic rebuild could use the wrong provider dim)
    cfg = _cfg(crop_len=32, msa_depth=2, msa_len=32, batch_size=1,
               min_len_filter=32, features="plm")
    from alphafold2_tpu.train.loop import apply_features

    batch = next(apply_features(iter(SyntheticDataset(cfg.data, seed=0)), cfg))
    assert "embedds" in batch and batch.get("msa") is None
    model = build_model(cfg)
    full = init_state(cfg, model, batch)
    _assert_identical(full, tiny_init_state(cfg, model, batch))
    tiny = tiny_batch_like(batch)
    assert tiny["embedds"].shape[-1] == batch["embedds"].shape[-1]


@pytest.mark.slow
def test_tiny_init_matches_full_init_end2end():
    # the end2end drivers init from tiny_batch_like too: the structure half
    # (MDS realization, sidechain lift, SE3 refiner) must also be free of
    # input-shape-dependent params / rng draws
    from alphafold2_tpu.train.end2end import End2EndModel, init_end2end_state

    cfg = _cfg(crop_len=24, msa_depth=2, msa_len=24, batch_size=1,
               min_len_filter=24)
    batch = next(iter(SyntheticDataset(cfg.data, seed=0)))
    model = End2EndModel(
        dim=32, depth=1, heads=2, dim_head=16, max_seq_len=128, mds_iters=4,
    )
    full = init_end2end_state(cfg, model, batch)
    tiny = init_end2end_state(cfg, model, tiny_batch_like(batch))
    _assert_identical(full, tiny)


@pytest.mark.slow
def test_tiny_init_matches_full_init_templates():
    # bench_suite config_4 inits at tiny template shapes inline; this pins
    # the invariant that run relies on: the template embedder (with and
    # without the SE(3) sidechain colorer) has no input-shape-dependent
    # params or rng draws, so tiny-shape init is bit-identical
    import jax.numpy as jnp

    from alphafold2_tpu.models import Alphafold2

    crop, msa_d, T, tn, tT = 24, 3, 3, 12, 2
    for use_se3 in (False, True):
        model = Alphafold2(
            dim=32, depth=1, heads=2, dim_head=16, max_seq_len=64,
            msa_tie_row_attn=True, template_attn_depth=1,
            use_se3_template_embedder=use_se3,
        )
        k = jax.random.key(7)
        seq = jax.random.randint(jax.random.fold_in(k, 1), (1, crop), 0, 21)
        msa = jax.random.randint(
            jax.random.fold_in(k, 2), (1, msa_d, crop), 0, 21
        )
        t_seq = jax.random.randint(
            jax.random.fold_in(k, 3), (1, T, crop), 0, 21
        )
        t_coors = jax.random.normal(
            jax.random.fold_in(k, 4), (1, T, crop, 3)
        ) * 10
        full = model.init(
            k, seq, msa,
            mask=jnp.ones((1, crop), bool),
            msa_mask=jnp.ones((1, msa_d, crop), bool),
            templates_seq=t_seq, templates_coors=t_coors,
            templates_mask=jnp.ones((1, T, crop), bool),
        )
        tiny = model.init(
            k, seq[:, :tn], msa[:, :2, :tn],
            mask=jnp.ones((1, tn), bool),
            msa_mask=jnp.ones((1, 2, tn), bool),
            templates_seq=t_seq[:, :tT, :tn],
            templates_coors=t_coors[:, :tT, :tn],
            templates_mask=jnp.ones((1, tT, tn), bool),
        )
        lf, lt = jax.tree.leaves(full), jax.tree.leaves(tiny)
        assert len(lf) == len(lt), f"use_se3={use_se3}"
        assert all(np.array_equal(a, b) for a, b in zip(lf, lt)), (
            f"use_se3={use_se3}"
        )


def test_tiny_batch_like_shapes():
    batch = {
        "seq": np.zeros((2, 64), np.int32),
        "mask": np.ones((2, 64), bool),
        "msa": np.zeros((2, 8, 64), np.int32),
        "msa_mask": np.ones((2, 8, 64), bool),
        "coords": np.zeros((2, 64, 3)),  # non-feature keys are dropped
    }
    tiny = tiny_batch_like(batch)
    assert tiny["seq"].shape == (1, 16)
    assert tiny["msa"].shape == (1, 2, 16)
    assert "coords" not in tiny
