"""Chunked (online-softmax) attention tests: exactness vs the dense path,
mask semantics, gradients, the size-gated routing, and the SE(3) refiner's
streamed edge attention — the long-chain enablement layer (ops/chunked.py)
that keeps 512+ serve buckets out of dense-logits memory off-TPU."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import alphafold2_tpu.ops.chunked as chunked_mod
from alphafold2_tpu.ops.chunked import (
    chunked_attention,
    chunked_attn_fn,
    should_chunk,
)


def _dense(q, k, v, kv_mask, scale):
    dots = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if kv_mask is not None:
        dots = jnp.where(kv_mask[:, None, None, :], dots, -1e9)
    attn = jax.nn.softmax(dots, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", attn, v.astype(jnp.float32)).astype(
        q.dtype
    )


@pytest.fixture()
def qkv():
    rng = np.random.default_rng(0)
    b, h, nq, nk, d = 2, 3, 37, 53, 8
    q = jnp.asarray(rng.normal(size=(b, h, nq, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, nk, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, nk, d)), jnp.float32)
    kv_mask = jnp.asarray(rng.random((b, nk)) > 0.3)
    return q, k, v, kv_mask


@pytest.mark.parametrize("qc,kc", [(8, 16), (37, 53), (5, 7), (None, None)])
def test_chunked_matches_dense(qkv, qc, kc):
    """Exact to float reassociation across chunk geometries, including
    ragged final chunks and the auto-sized default."""
    q, k, v, kv_mask = qkv
    scale = q.shape[-1] ** -0.5
    ref = _dense(q, k, v, kv_mask, scale)
    out = chunked_attention(
        q, k, v, kv_mask=kv_mask, sm_scale=scale, q_chunk=qc, kv_chunk=kc
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)


def test_chunked_unmasked_and_query_mask(qkv):
    q, k, v, kv_mask = qkv
    scale = q.shape[-1] ** -0.5
    # no masks at all
    out = chunked_attention(q, k, v, sm_scale=scale, q_chunk=16, kv_chunk=8)
    ref = _dense(q, k, v, None, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)
    # masked queries emit zeros (the flash SegmentIds convention); valid
    # queries are untouched by the q_mask
    rng = np.random.default_rng(1)
    q_mask = jnp.asarray(rng.random(q.shape[0::2][:1] + (q.shape[2],)) > 0.4)
    q_mask = jnp.asarray(rng.random((q.shape[0], q.shape[2])) > 0.4)
    out = chunked_attention(
        q, k, v, q_mask=q_mask, kv_mask=kv_mask, sm_scale=scale,
        q_chunk=8, kv_chunk=8,
    )
    ref = _dense(q, k, v, kv_mask, scale)
    qm = np.asarray(q_mask)
    assert np.all(np.asarray(out)[~qm[:, None, :].repeat(q.shape[1], 1)] == 0)
    valid = np.broadcast_to(qm[:, None, :, None], ref.shape)
    np.testing.assert_allclose(
        np.asarray(out)[valid], np.asarray(ref)[valid], atol=2e-6
    )


def test_chunked_gradients_match_dense(qkv):
    q, k, v, kv_mask = qkv
    scale = q.shape[-1] ** -0.5

    g1 = jax.grad(
        lambda q: chunked_attention(
            q, k, v, kv_mask=kv_mask, sm_scale=scale, q_chunk=8, kv_chunk=8
        ).sum()
    )(q)
    g2 = jax.grad(lambda q: _dense(q, k, v, kv_mask, scale).sum())(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=5e-6)


def test_should_chunk_threshold_and_grid_hook(qkv, monkeypatch):
    """Routing: small shapes stay dense (the committed graph fingerprints
    depend on it); the grid attn_fn declines below threshold and computes
    above it."""
    q, k, v, kv_mask = qkv
    assert not should_chunk(4, 192, 192)  # single-device serve shapes
    assert should_chunk(1, 2_359_296, 1024)  # bucket-512 cross-attention
    fn = chunked_attn_fn(q.shape[-1] ** -0.5)
    assert fn(q, k[:, :, : q.shape[2]], v[:, :, : q.shape[2]], None) is None
    monkeypatch.setattr(chunked_mod, "CHUNK_THRESHOLD", 1)
    out = fn(q, q, q, kv_mask[:, : q.shape[2]])
    assert out is not None and out.shape == q.shape


def test_attention_module_chunked_branch_matches_dense(monkeypatch):
    """ops.attention.Attention routes through the chunked path above the
    threshold with identical results (same params, same inputs)."""
    from alphafold2_tpu.ops.attention import Attention

    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(2, 40, 16)), jnp.float32)
    ctx = jnp.asarray(rng.normal(size=(2, 23, 16)), jnp.float32)
    cmask = jnp.asarray(rng.random((2, 23)) > 0.3)
    mod = Attention(dim=16, heads=2, dim_head=8)
    params = mod.init(jax.random.key(0), x, context=ctx, context_mask=cmask)
    dense = mod.apply(params, x, context=ctx, context_mask=cmask)
    monkeypatch.setattr(chunked_mod, "CHUNK_THRESHOLD", 1)
    streamed = mod.apply(params, x, context=ctx, context_mask=cmask)
    np.testing.assert_allclose(
        np.asarray(streamed), np.asarray(dense), atol=2e-5
    )


def test_grid_axial_chunked_matches_dense(monkeypatch):
    """The sharded axial passes' attn_fn hook: chunked per-device kernels
    inside grid_axial_attention equal the dense meshless result."""
    from alphafold2_tpu.ops.attention import AxialAttention

    rng = np.random.default_rng(3)
    n = 8
    x = jnp.asarray(rng.normal(size=(2, n, n, 16)), jnp.float32)
    mask = jnp.ones((2, n, n), bool).at[:, :, -2:].set(False)
    mod = AxialAttention(
        dim=16, heads=2, dim_head=8, grid_parallel=True
    )
    params = mod.init(jax.random.key(1), x, mask=mask)
    dense = mod.apply(params, x, mask=mask)
    monkeypatch.setattr(chunked_mod, "CHUNK_THRESHOLD", 1)
    from alphafold2_tpu.parallel.grid_parallel import make_grid_mesh
    from alphafold2_tpu.parallel.sharding import use_mesh

    mesh = make_grid_mesh(2, 2, 2)
    with use_mesh(mesh):
        sharded = jax.jit(lambda x: mod.apply(params, x, mask=mask))(x)
    valid = np.asarray(mask)[..., None]
    np.testing.assert_allclose(
        np.asarray(sharded) * valid, np.asarray(dense) * valid, atol=2e-5
    )


def test_se3_streamed_matches_dense(monkeypatch):
    """The SE(3) refiner's streamed edge attention (rel/RBF/logits tiles +
    shared online softmax across all three aggregations) is exact vs the
    dense layer, with ragged edge blocks, and owns the identical parameter
    tree."""
    from alphafold2_tpu.models.se3 import EquivariantLayer

    rng = np.random.default_rng(4)
    b, n, ds, dv = 2, 50, 24, 4
    s = jnp.asarray(rng.normal(size=(b, n, ds)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, n, dv, 3)), jnp.float32)
    coords = jnp.asarray(rng.normal(size=(b, n, 3)), jnp.float32)
    mask = jnp.asarray(rng.random((b, n)) > 0.2)

    dense_mod = EquivariantLayer(dim=16, vec_dim=dv, heads=2)
    params = dense_mod.init(jax.random.key(2), s, v, coords, mask=mask)
    s_ref, v_ref = dense_mod.apply(params, s, v, coords, mask=mask)

    monkeypatch.setattr(chunked_mod, "CHUNK_THRESHOLD", 1)
    # edge_block 16 with n=50: ragged final tiles on both loop axes
    stream_mod = EquivariantLayer(dim=16, vec_dim=dv, heads=2, edge_block=16)
    p2 = stream_mod.init(jax.random.key(2), s, v, coords, mask=mask)
    assert jax.tree_util.tree_structure(params) == (
        jax.tree_util.tree_structure(p2)
    )
    s_out, v_out = stream_mod.apply(params, s, v, coords, mask=mask)
    # valid region exact; masked-query rows are garbage-by-contract in
    # BOTH paths (dense attends them uniformly over real keys, streamed
    # over padded keys) and every downstream read masks them out
    m = np.asarray(mask)
    sm = np.broadcast_to(m[:, :, None], s_ref.shape)
    vm = np.broadcast_to(m[:, :, None, None], v_ref.shape)
    np.testing.assert_allclose(
        np.asarray(s_out)[sm], np.asarray(s_ref)[sm], atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(v_out)[vm], np.asarray(v_ref)[vm], atol=1e-5
    )
    # unmasked: every row is valid -> full-tensor equality (ragged
    # padding rows are sliced off and padded keys masked internally)
    s_ref2, v_ref2 = dense_mod.apply(params, s, v, coords)
    s_out2, v_out2 = stream_mod.apply(params, s, v, coords)
    np.testing.assert_allclose(np.asarray(s_out2), np.asarray(s_ref2),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(v_out2), np.asarray(v_ref2),
                               atol=1e-5)
