"""Sequence-parallel attention tests on the 8-virtual-device CPU mesh:
ring and Ulysses implementations must equal the dense oracle exactly
(they are exact algorithms, not approximations), with masking, under jit,
and through gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from alphafold2_tpu.parallel.seq_parallel import (
    sequence_parallel_attention,
)
from alphafold2_tpu.parallel.sharding import make_mesh

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 (virtual) devices"
)


def _qkv(key, b=2, h=4, n=32, d=8):
    ks = jax.random.split(key, 3)
    return tuple(jax.random.normal(kk, (b, h, n, d)) for kk in ks)


def _dense_oracle(q, k, v, mask=None):
    scale = q.shape[-1] ** -0.5
    dots = jnp.einsum("bhid,bhjd->bhij", q, k) * scale
    if mask is not None:
        dots = jnp.where(mask[:, None, None, :], dots, -1e9)
    return jnp.einsum(
        "bhij,bhjd->bhid", jax.nn.softmax(dots, axis=-1), v
    )


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_matches_dense_oracle(impl):
    q, k, v = _qkv(jax.random.key(0))
    mesh = make_mesh(2, 4)
    out = sequence_parallel_attention(q, k, v, mesh=mesh, impl=impl)
    ref = _dense_oracle(q, k, v)
    assert np.allclose(out, ref, atol=1e-5), np.abs(np.asarray(out - ref)).max()


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_masked_matches_dense_oracle(impl):
    q, k, v = _qkv(jax.random.key(1))
    mask = jnp.ones((2, 32), bool).at[:, 27:].set(False)
    mesh = make_mesh(2, 4)
    out = sequence_parallel_attention(q, k, v, mask=mask, mesh=mesh, impl=impl)
    ref = _dense_oracle(q, k, v, mask=mask)
    # only unmasked queries are meaningful
    assert np.allclose(out[:, :, :27], ref[:, :, :27], atol=1e-5)


def test_ring_under_jit_and_grads():
    q, k, v = _qkv(jax.random.key(2), h=2, n=16)
    mesh = make_mesh(1, 8)

    def loss_sp(q, k, v):
        return jnp.sum(
            sequence_parallel_attention(q, k, v, mesh=mesh, impl="ring") ** 2
        )

    def loss_dense(q, k, v):
        return jnp.sum(_dense_oracle(q, k, v) ** 2)

    g_sp = jax.jit(jax.grad(loss_sp))(q, k, v)
    g_dense = jax.grad(loss_dense)(q, k, v)
    assert np.allclose(g_sp, g_dense, atol=1e-4), (
        np.abs(np.asarray(g_sp - g_dense)).max()
    )


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_cross_attention_different_lengths_no_mask(impl):
    # cross-attention: Nq != Nk, mask=None — the default key bias must be
    # built with the KEY length (regression: it used the query length)
    kq, kk, kv = jax.random.split(jax.random.key(5), 3)
    q = jax.random.normal(kq, (2, 4, 32, 8))
    k = jax.random.normal(kk, (2, 4, 64, 8))
    v = jax.random.normal(kv, (2, 4, 64, 8))
    mesh = make_mesh(2, 4)
    out = sequence_parallel_attention(q, k, v, mesh=mesh, impl=impl)
    ref = _dense_oracle(q, k, v)
    assert np.allclose(out, ref, atol=1e-5)


def test_unknown_impl_rejected():
    q, k, v = _qkv(jax.random.key(6))
    with pytest.raises(ValueError, match="impl"):
        sequence_parallel_attention(q, k, v, mesh=make_mesh(1, 8), impl="Ring")


def test_tied_row_attention_sharded_matches_dense():
    # MSA rows sharded over sp: psum of per-shard logits must equal the
    # dense tied contraction exactly (SURVEY.md S7 "tied-rows becomes a
    # collective")
    from alphafold2_tpu.parallel.seq_parallel import tied_row_attention

    ks = jax.random.split(jax.random.key(7), 3)
    q, k, v = (jax.random.normal(kk, (2, 8, 2, 16, 8)) for kk in ks)
    mesh = make_mesh(2, 4)  # 8 rows / 4-way sharding
    out = tied_row_attention(q, k, v, mesh=mesh)
    ref = tied_row_attention(q, k, v, mesh=None)
    assert np.allclose(out, ref, atol=1e-5), np.abs(np.asarray(out - ref)).max()

    # gradients flow through the psum identically to the dense contraction
    g = jax.grad(lambda q: jnp.sum(tied_row_attention(q, k, v, mesh=mesh) ** 2))(q)
    gd = jax.grad(lambda q: jnp.sum(tied_row_attention(q, k, v, mesh=None) ** 2))(q)
    assert np.allclose(g, gd, atol=1e-4)


def test_dense_fallback_without_mesh():
    q, k, v = _qkv(jax.random.key(3))
    out = sequence_parallel_attention(q, k, v, mesh=None)
    assert np.allclose(out, _dense_oracle(q, k, v), atol=1e-5)


def test_ulysses_rejects_indivisible_heads():
    q, k, v = _qkv(jax.random.key(4), h=3)
    mesh = make_mesh(1, 8)
    with pytest.raises(ValueError, match="heads"):
        sequence_parallel_attention(q, k, v, mesh=mesh, impl="ulysses")


# ------------------------------------------------- the ring of flash blocks ---
#
# Off the TPU the ring runs its jnp block triple, so what these cases run is
# the skeleton itself: the log-sum-exp merge, the rotation of K/V/mask and of
# the dk/dv accumulators, and the custom VJP.


def _ring_inputs(kind, masking, sp, b=4, h=2, d=8):
    nq, nk = (32, 32) if kind == "self" else (48, 32)
    ks = jax.random.split(jax.random.key(11), 3)
    q = jax.random.normal(ks[0], (b, h, nq, d))
    k = jax.random.normal(ks[1], (b, h, nk, d))
    v = jax.random.normal(ks[2], (b, h, nk, d))
    block = nk // sp
    mask = np.ones((b, nk), bool)
    if masking == "whole_block":  # one visiting block contributes nothing
        mask[:, block:2 * block] = False
    elif masking == "part_of_each":
        for j in range(sp):
            mask[:, j * block + block // 2 + j % 2:(j + 1) * block] = False
    return q, k, v, jnp.asarray(mask)


@pytest.mark.parametrize("masking", ["whole_block", "part_of_each"])
@pytest.mark.parametrize("kind", ["self", "cross_unequal"])
@pytest.mark.parametrize("sp", [2, 4])
def test_ring_output_and_gradients_match_dense_oracle(sp, kind, masking):
    q, k, v, mask = _ring_inputs(kind, masking, sp)
    mesh = make_mesh(8 // sp, sp)
    weights = jax.random.normal(jax.random.key(12), q.shape)

    def run(attend):
        def loss(q, k, v):
            out = attend(q, k, v)
            return jnp.sum(out * weights), out

        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                          has_aux=True))(q, k, v)

    (_, out), grads = run(lambda q, k, v: sequence_parallel_attention(
        q, k, v, mask=mask, mesh=mesh, impl="ring"))
    (_, out_ref), grads_ref = run(
        lambda q, k, v: _dense_oracle(q, k, v, mask=mask))
    assert np.allclose(out, out_ref, atol=1e-5)
    for name, g, g_ref in zip("qkv", grads, grads_ref):
        assert np.allclose(g, g_ref, atol=1e-5), (
            name, np.abs(np.asarray(g - g_ref)).max())
    # masked keys get no gradient at all, wherever their block travelled
    masked_out = ~np.asarray(mask)[:, None, :, None]
    assert not (np.asarray(grads[1]) * masked_out).any()
    assert not (np.asarray(grads[2]) * masked_out).any()


@pytest.mark.parametrize("on_tpu,n_local,kernel", [
    (True, 200, True),   # 200 x 170, padded to the kernel's 128 lanes
    (False, 200, False),  # off the TPU
    (True, 64, False),  # both local axes under one 128 block
])
def test_ring_block_choice_follows_platform_and_shape(
        monkeypatch, on_tpu, n_local, kernel):
    """On a TPU (steered here) the ring takes the kernel's blocks unless the
    local block is under one lane tile: ``ops/flash.py``'s own rule. The
    kernel itself cannot run on the CPU: a stand-in records the (padded)
    shape it was asked for and hands back the jnp triple."""
    from alphafold2_tpu.ops import flash
    from alphafold2_tpu.parallel import seq_parallel as sp_mod

    asked = []

    def stand_in(b, h, nq, nk, d, dtype, scale):
        asked.append((nq, nk))
        return sp_mod._jnp_blocks(scale)

    monkeypatch.setattr(flash, "flash_available", lambda: on_tpu)
    monkeypatch.setattr(sp_mod, "_flash_blocks", stand_in)
    ks = jax.random.split(jax.random.key(13), 3)
    q = jax.random.normal(ks[0], (4, 2, 2 * n_local, 8))
    k = jax.random.normal(ks[1], (4, 2, 2 * (n_local - 30), 8))
    v = jax.random.normal(ks[2], (4, 2, 2 * (n_local - 30), 8))
    mask = jnp.ones(k.shape[::2], bool).at[:, -9:].set(False)

    def loss(q, k, v):
        return jnp.sum(sequence_parallel_attention(
            q, k, v, mask=mask, mesh=make_mesh(4, 2)) ** 2)

    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    grads_ref = jax.grad(
        lambda q, k, v: jnp.sum(_dense_oracle(q, k, v, mask=mask) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    assert asked == ([(256, 256)] if kernel else [])
    for g, g_ref in zip(grads, grads_ref):
        assert g.shape == g_ref.shape
        assert np.allclose(g, g_ref, atol=1e-4)
