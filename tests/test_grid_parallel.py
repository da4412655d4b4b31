"""2D (rows x cols) pair-grid sharding: exactness of each axial pass and its
gradients against the dense oracle, on the 8-virtual-device CPU mesh."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alphafold2_tpu.parallel.grid_parallel import (
    grid_axial_attention,
    make_grid_mesh,
)

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices"
)

B, N, HEADS, D = 2, 8, 2, 4


def _qkv(key):
    ks = jax.random.split(key, 3)
    shape = (B, N, N, HEADS, D)
    return tuple(jax.random.normal(k, shape) for k in ks)


def _mask():
    m = jnp.ones((B, N, N), bool)
    return m.at[:, -2:, :].set(False).at[:, :, -1].set(False)


@pytest.mark.parametrize("attend_axis", [1, 2])
def test_sharded_matches_dense(attend_axis):
    q, k, v = _qkv(jax.random.key(0))
    mask = _mask()
    mesh = make_grid_mesh(2, 2, 2)
    dense = grid_axial_attention(q, k, v, mask, mesh=None, attend_axis=attend_axis)
    sharded = jax.jit(
        lambda q, k, v: grid_axial_attention(
            q, k, v, mask, mesh=mesh, attend_axis=attend_axis
        )
    )(q, k, v)
    # compare only at valid *query* positions: fully-masked key rows produce
    # uniform-softmax garbage at padded queries in both paths, but the
    # accumulation order differs
    valid = np.asarray(mask)[..., None, None]
    np.testing.assert_allclose(
        np.asarray(sharded) * valid, np.asarray(dense) * valid, atol=2e-5
    )


@pytest.mark.parametrize("attend_axis", [1, 2])
def test_grads_match_dense(attend_axis):
    q, k, v = _qkv(jax.random.key(1))
    mask = _mask()
    mesh = make_grid_mesh(2, 2, 2)
    w = jax.random.normal(jax.random.key(2), q.shape)  # fixed cotangent probe
    valid = _mask()[..., None, None]

    def loss(mesh_arg):
        def f(q, k, v):
            out = grid_axial_attention(
                q, k, v, mask, mesh=mesh_arg, attend_axis=attend_axis
            )
            return jnp.sum(jnp.where(valid, out * w, 0.0))

        return f

    gd = jax.grad(loss(None), argnums=(0, 1, 2))(q, k, v)
    gs = jax.jit(jax.grad(loss(mesh), argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(gs, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_spr_only_grid():
    """Degenerate 1D layouts of the same mesh type still work (spc=1)."""
    q, k, v = _qkv(jax.random.key(3))
    mesh = make_grid_mesh(2, 4, 1)
    dense = grid_axial_attention(q, k, v, attend_axis=1)
    sharded = jax.jit(
        lambda q, k, v: grid_axial_attention(q, k, v, mesh=mesh, attend_axis=1)
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(sharded), np.asarray(dense), atol=2e-5)


@pytest.mark.slow
def test_grid_train_step_matches_single_device():
    """Full training step with model.grid_parallel=True over a (2, 2, 2)
    grid mesh == the single-device step (same params, same loss)."""
    from alphafold2_tpu.config import Config, DataConfig, MeshConfig, ModelConfig, TrainConfig
    from alphafold2_tpu.data.pipeline import SyntheticDataset
    from alphafold2_tpu.train.loop import (
        build_model, device_put_batch, init_state, make_train_step,
    )

    cfg = Config(
        model=ModelConfig(dim=32, depth=1, heads=2, dim_head=16,
                          max_seq_len=64, bfloat16=False, grid_parallel=True),
        mesh=MeshConfig(data_parallel=2, grid_rows=2, grid_cols=2),
        data=DataConfig(crop_len=16, msa_depth=2, msa_len=16, batch_size=2,
                        min_len_filter=8),
        train=TrainConfig(gradient_accumulate_every=1, warmup_steps=2),
    )
    batch = next(iter(SyntheticDataset(cfg.data, seed=4)))
    model = build_model(cfg)

    state1 = init_state(cfg, model, batch)
    step1 = make_train_step(model, mesh=None)
    s1, m1 = step1(state1, device_put_batch(batch), jax.random.key(9))

    mesh = make_grid_mesh(2, 2, 2)
    state2 = init_state(cfg, model, batch)
    step2 = make_train_step(model, mesh=mesh)
    s2, m2 = step2(state2, device_put_batch(batch, mesh), jax.random.key(9))

    assert np.isclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-4), (
        float(m1["loss"]), float(m2["loss"]),
    )
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.parametrize("attend_axis", [1, 2])
def test_attn_fn_hook_runs_inside_sharded_pass(attend_axis):
    """The fused-kernel hook must actually execute per device after the
    all-to-all gather: an exact jnp reimplementation fed through the hook
    reproduces the dense path, and a sentinel (zeros) proves it ran."""
    q, k, v = _qkv(jax.random.key(5))
    mask = _mask()
    mesh = make_grid_mesh(2, 2, 2)

    def exact(q2, k2, v2, m2):  # (B2, H, N, D) + (B2, N), like flash/sparse
        dots = jnp.einsum("bhid,bhjd->bhij", q2, k2) * q2.shape[-1] ** -0.5
        dots = jnp.where(m2[:, None, None, :], dots, -1e9)
        return jnp.einsum("bhij,bhjd->bhid", jax.nn.softmax(dots, -1), v2)

    dense = grid_axial_attention(q, k, v, mask, mesh=None,
                                 attend_axis=attend_axis)
    hooked = jax.jit(
        lambda q, k, v: grid_axial_attention(
            q, k, v, mask, mesh=mesh, attend_axis=attend_axis, attn_fn=exact
        )
    )(q, k, v)
    valid = np.asarray(mask)[..., None, None]
    np.testing.assert_allclose(
        np.asarray(hooked) * valid, np.asarray(dense) * valid, atol=2e-5
    )

    sentinel = jax.jit(
        lambda q, k, v: grid_axial_attention(
            q, k, v, mask, mesh=mesh, attend_axis=attend_axis,
            attn_fn=lambda q2, k2, v2, m2: jnp.zeros_like(q2),
        )
    )(q, k, v)
    np.testing.assert_array_equal(np.asarray(sentinel), 0.0)


def test_attn_fn_decline_falls_back_dense():
    # a hook returning None (flash declining the shape) must leave the
    # dense result untouched
    q, k, v = _qkv(jax.random.key(6))
    mesh = make_grid_mesh(2, 2, 2)
    dense = jax.jit(
        lambda q, k, v: grid_axial_attention(q, k, v, mesh=mesh)
    )(q, k, v)
    declined = jax.jit(
        lambda q, k, v: grid_axial_attention(
            q, k, v, mesh=mesh, attn_fn=lambda *a: None
        )
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(declined), np.asarray(dense))


def test_sparse_axial_in_grid_matches_meshless():
    """AxialAttention(sparse_attn=True, grid_parallel=True): the 2D-sharded
    passes run the block-sparse kernel per device after the gather, and the
    values match the same module without a mesh."""
    from alphafold2_tpu.ops.attention import AxialAttention
    from alphafold2_tpu.ops.sparse import BlockSparseConfig
    from alphafold2_tpu.parallel.sharding import use_mesh

    n = 16  # grid 16x16, block 4 -> 4 blocks per attended axis
    cfg = BlockSparseConfig(
        block_size=4, num_local_blocks=2, num_global_blocks=1,
        num_random_blocks=1,
    )
    mod = AxialAttention(
        dim=16, heads=2, dim_head=8, sparse_attn=True, seq_len=n,
        sparse_config=cfg, sparse_use_pallas=False, grid_parallel=True,
    )
    x = jax.random.normal(jax.random.key(7), (2, n, n, 16))
    mask = jnp.ones((2, n, n), bool).at[:, :, -2:].set(False)
    params = mod.init(jax.random.key(8), x, mask=mask)

    meshless = mod.apply(params, x, mask=mask)
    mesh = make_grid_mesh(2, 2, 2)
    with use_mesh(mesh):
        sharded = jax.jit(lambda x: mod.apply(params, x, mask=mask))(x)
    valid = np.asarray(mask)[..., None]
    np.testing.assert_allclose(
        np.asarray(sharded) * valid, np.asarray(meshless) * valid, atol=2e-5
    )


@pytest.mark.slow
def test_sparse_grid_768_crop_step():
    """The 768-crop story (grid_parallel.py module docstring): one sparse
    axial pass over a (1, 768, 768) grid on the 8-virtual-device mesh.
    Dense logits for one pass would be 768^2 * 768 * 4B ~ 1.7TB — only the
    block-sparse per-device path makes this executable at all here."""
    from alphafold2_tpu.ops.attention import AxialAttention
    from alphafold2_tpu.ops.sparse import BlockSparseConfig
    from alphafold2_tpu.parallel.sharding import use_mesh

    n = 768
    cfg = BlockSparseConfig(
        block_size=128, num_local_blocks=2, num_global_blocks=1,
        num_random_blocks=0,
    )
    mod = AxialAttention(
        dim=8, heads=1, dim_head=8, sparse_attn=True, seq_len=n,
        sparse_config=cfg, sparse_use_pallas=False, grid_parallel=True,
    )
    x = jax.random.normal(jax.random.key(9), (1, n, n, 8), jnp.float32)
    mesh = make_grid_mesh(1, 2, 4)
    with use_mesh(mesh):
        params = jax.eval_shape(lambda: mod.init(jax.random.key(10), x))
        params = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), params
        )
        out = jax.jit(lambda x: mod.apply(params, x))(x)
    assert out.shape == (1, n, n, 8)
    assert np.all(np.isfinite(np.asarray(out)))


@pytest.mark.parametrize("sparse", [False, True])
def test_grid_native_matches_flat_route(sparse):
    """The default grid-native axial route (pointwise projections on the
    grid, no pair-map transpose materialization) computes the same values
    as the flat (B*, n, d) route on the valid region, dense and sparse."""
    from alphafold2_tpu.ops.attention import AxialAttention
    from alphafold2_tpu.ops.sparse import BlockSparseConfig

    n = 8
    kw = dict(dim=16, heads=2, dim_head=8)
    if sparse:
        kw.update(
            sparse_attn=True, seq_len=n, sparse_use_pallas=False,
            sparse_config=BlockSparseConfig(
                block_size=4, num_local_blocks=2, num_global_blocks=1,
                num_random_blocks=0,
            ),
        )
    a = AxialAttention(**kw, grid_native=True)
    b_mod = AxialAttention(**kw, grid_native=False)
    x = jax.random.normal(jax.random.key(11), (2, n, n, 16))
    mask = jnp.ones((2, n, n), bool).at[:, :, -2:].set(False)
    params = a.init(jax.random.key(12), x, mask=mask)

    out_grid = a.apply(params, x, mask=mask)
    out_flat = b_mod.apply(params, x, mask=mask)
    valid = np.asarray(mask)[..., None]
    np.testing.assert_allclose(
        np.asarray(out_grid) * valid, np.asarray(out_flat) * valid,
        atol=2e-5,
    )


def test_grid_mesh_overrides_grid_native_escape():
    """grid_native=False is a flat-route debug escape, but under an active
    grid mesh the sharded pass must still run (the flat route would
    transpose the 2D-sharded pair map — a silent memory cliff)."""
    from alphafold2_tpu.ops.attention import AxialAttention
    from alphafold2_tpu.parallel.sharding import use_mesh

    n = 8
    mod = AxialAttention(dim=16, heads=2, dim_head=8, grid_parallel=True,
                         grid_native=False)
    x = jax.random.normal(jax.random.key(13), (2, n, n, 16))
    params = mod.init(jax.random.key(14), x)
    ref = mod.apply(params, x)
    mesh = make_grid_mesh(2, 2, 2)
    with use_mesh(mesh):
        out = jax.jit(lambda x: mod.apply(params, x))(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_grid_sparse_unaligned_fails_loudly():
    from alphafold2_tpu.ops.attention import AxialAttention
    from alphafold2_tpu.ops.sparse import BlockSparseConfig
    from alphafold2_tpu.parallel.sharding import use_mesh

    n = 12  # not a multiple of block_size 8
    mod = AxialAttention(
        dim=16, heads=2, dim_head=8, sparse_attn=True, seq_len=16,
        sparse_use_pallas=False, grid_parallel=True,
        sparse_config=BlockSparseConfig(block_size=8),
    )
    x = jax.random.normal(jax.random.key(15), (2, n, n, 16))
    mesh = make_grid_mesh(2, 2, 2)
    with use_mesh(mesh):
        with pytest.raises(ValueError, match="block-aligned"):
            mod.init(jax.random.key(16), x)


@pytest.mark.skipif(
    os.environ.get("AF2TPU_HEAVY") != "1",
    reason="~7 min on CPU; set AF2TPU_HEAVY=1 (verified run: compile 396s, "
    "then 23s/step, finite loss — 2026-07-30)",
)
def test_grid_sparse_768_full_train_step():
    """The 'done' criterion: a FULL 768-crop training step
    (grid_parallel + block-sparse + remat) executes on the 8-virtual-device
    mesh. Dense logits for one axial pass would be ~1.7TB; the sparse
    per-device kernels inside the 2D-sharded passes make this fit."""
    from alphafold2_tpu.config import (
        Config, DataConfig, MeshConfig, ModelConfig, TrainConfig,
    )
    from alphafold2_tpu.data.pipeline import SyntheticDataset
    from alphafold2_tpu.train.loop import (
        build_model, device_put_batch, init_state, make_train_step,
    )

    cfg = Config(
        model=ModelConfig(
            dim=16, depth=1, heads=2, dim_head=8, max_seq_len=1536,
            grid_parallel=True, sparse_self_attn=True, remat=True,
            bfloat16=False,
        ),
        mesh=MeshConfig(data_parallel=1, grid_rows=2, grid_cols=4),
        data=DataConfig(crop_len=768, msa_depth=2, msa_len=32, batch_size=1,
                        min_len_filter=768),
        train=TrainConfig(gradient_accumulate_every=1, warmup_steps=1),
    )
    batch = next(iter(SyntheticDataset(cfg.data, seed=0)))
    model = build_model(cfg)
    state = init_state(cfg, model, batch)
    mesh = make_grid_mesh(1, 2, 4)
    step = make_train_step(model, mesh)
    state, metrics = step(state, device_put_batch(batch, mesh),
                          jax.random.key(1))
    assert np.isfinite(float(metrics["loss"]))


def test_indivisible_axis_raises():
    # N/spr = 4 rows per device, spc = 2 -> fine; but N=6 local rows 3 is
    # not divisible by spc=2 for the transpose
    n = 6
    shape = (B, n, n, HEADS, D)
    q = k = v = jnp.zeros(shape)
    mesh = make_grid_mesh(2, 2, 2)
    with pytest.raises(ValueError, match="must divide"):
        jax.jit(
            lambda q, k, v: grid_axial_attention(q, k, v, mesh=mesh, attend_axis=2)
        )(q, k, v)
