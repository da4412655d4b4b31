"""Reversible trunk engine tests.

The reference validates its hand-written reversible backward against plain
autograd with a gradient-equality oracle (reference tests/test_reversible.py:
identical inputs through reverse=True/False, allclose on input grads).
Same protocol here: ``use_custom_vjp=False`` runs the identical coupling
under plain autodiff and must produce the same values and gradients as the
inversion-based custom backward. Plus what the reference never tests:
inversion exactness, dropout-replay exactness, and model-level integration.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alphafold2_tpu.models.reversible import ReversibleTrunk, RevLayerPair

B, N, M, NM, D = 2, 6, 3, 5, 16


def _inputs(key):
    kx, km = jax.random.split(key)
    x = jax.random.normal(kx, (B, N, N, D))
    m = jax.random.normal(km, (B, M, NM, D))
    pair_mask = jnp.ones((B, N, N), bool).at[:, -1].set(False)
    msa_mask = jnp.ones((B, M, NM), bool).at[:, :, -1].set(False)
    return x, m, pair_mask, msa_mask


def _trunk(**kw):
    base = dict(dim=D, depth=3, heads=2, dim_head=8)
    base.update(kw)
    return ReversibleTrunk(**base)


def test_forward_matches_plain_autodiff_path():
    x, m, pm, mm = _inputs(jax.random.key(0))
    rev = _trunk(use_custom_vjp=True)
    ref = _trunk(use_custom_vjp=False)
    params = rev.init(jax.random.key(1), x, m, pm, mm)
    out_rev = rev.apply(params, x, m, pm, mm)
    out_ref = ref.apply(params, x, m, pm, mm)
    for a, b in zip(jax.tree.leaves(out_rev), jax.tree.leaves(out_ref)):
        np.testing.assert_allclose(a, b, atol=1e-5)


@pytest.mark.slow
def test_reversible_grad_parity():
    """The custom (inversion-based) backward == plain autodiff, for both
    parameter and input gradients — the reference's own oracle standard
    (tests/test_reversible.py:48-52, atol 1e-3; tighter here)."""
    x, m, pm, mm = _inputs(jax.random.key(2))
    rev = _trunk(use_custom_vjp=True)
    ref = _trunk(use_custom_vjp=False)
    params = rev.init(jax.random.key(3), x, m, pm, mm)

    def loss(mod):
        def f(p, x, m):
            xo, mo = mod.apply(p, x, m, pm, mm)
            return jnp.sum(xo**2) + jnp.sum(mo**2)

        return f

    (gp_rev, gx_rev, gm_rev) = jax.grad(loss(rev), argnums=(0, 1, 2))(params, x, m)
    (gp_ref, gx_ref, gm_ref) = jax.grad(loss(ref), argnums=(0, 1, 2))(params, x, m)

    np.testing.assert_allclose(gx_rev, gx_ref, atol=2e-4, rtol=1e-3)
    np.testing.assert_allclose(gm_rev, gm_ref, atol=2e-4, rtol=1e-3)
    flat_rev = jax.tree.leaves(gp_rev)
    flat_ref = jax.tree.leaves(gp_ref)
    assert len(flat_rev) == len(flat_ref)
    for a, b in zip(flat_rev, flat_ref):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=1e-3)


def test_layer_inversion_exact():
    """invert(forward(h)) == h to float32 roundoff."""
    x, m, pm, mm = _inputs(jax.random.key(4))
    layer = RevLayerPair(dim=D, heads=2, dim_head=8)
    h = (x, x * 0.5, m, m * 0.5)
    params = layer.init(jax.random.key(5), h, pm, mm, True)
    h_out = layer.apply(params, h, pm, mm, True)
    h_back = layer.apply(params, h_out, pm, mm, True, method=RevLayerPair.invert)
    for a, b in zip(h, h_back):
        np.testing.assert_allclose(a, b, atol=1e-4)


@pytest.mark.slow
def test_grad_parity_with_dropout():
    """Dropout replay by PRNG key: the custom backward re-runs blocks with
    the same per-layer keys, so gradients still match plain autodiff (the
    capability the reference needs CUDA RNG capture for, reversible.py:26-56)."""
    x, m, pm, mm = _inputs(jax.random.key(6))
    rev = _trunk(use_custom_vjp=True, attn_dropout=0.1, ff_dropout=0.1)
    ref = _trunk(use_custom_vjp=False, attn_dropout=0.1, ff_dropout=0.1)
    params = rev.init(jax.random.key(7), x, m, pm, mm)
    dk = jax.random.key(8)

    def loss(mod):
        def f(p):
            xo, mo = mod.apply(
                p, x, m, pm, mm, False, rngs={"dropout": dk}
            )
            return jnp.sum(xo**2) + jnp.sum(mo**2)

        return f

    gp_rev = jax.grad(loss(rev))(params)
    gp_ref = jax.grad(loss(ref))(params)
    for a, b in zip(jax.tree.leaves(gp_rev), jax.tree.leaves(gp_ref)):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=1e-3)


@pytest.mark.slow
def test_bf16_compute_keeps_f32_carry_and_grad_parity():
    """Under bf16 compute the carried state stays float32 (inversion error
    must not compound in the low-precision carry), and the custom backward
    still matches plain autodiff."""
    x, m, pm, mm = _inputs(jax.random.key(12))
    rev = _trunk(use_custom_vjp=True, dtype=jnp.bfloat16, depth=2)
    ref = _trunk(use_custom_vjp=False, dtype=jnp.bfloat16, depth=2)
    params = rev.init(jax.random.key(13), x, m, pm, mm)
    xo, mo = rev.apply(params, x, m, pm, mm)
    assert xo.dtype == jnp.float32 and mo.dtype == jnp.float32

    def loss(mod):
        def f(p):
            xo, mo = mod.apply(p, x, m, pm, mm)
            return jnp.sum(xo.astype(jnp.float32) ** 2) + jnp.sum(
                mo.astype(jnp.float32) ** 2
            )

        return f

    gp_rev = jax.grad(loss(rev))(params)
    gp_ref = jax.grad(loss(ref))(params)
    for a, b in zip(jax.tree.leaves(gp_rev), jax.tree.leaves(gp_ref)):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        # bf16 compute carries ~3 significant digits, and the reversible
        # path recomputes activations by inversion, so the two backward
        # graphs round differently in the low bits: bound the error
        # against the leaf's own gradient SCALE — per-leaf relative L2
        # plus a coarse elementwise cap. (An elementwise rtol demands
        # bf16-impossible precision wherever a near-zero grad sits next
        # to O(10) ones; a wrong backward FORMULA errs at O(scale) and
        # still trips both bounds.)
        scale = max(np.abs(b).max(), 1.0)
        rel_l2 = np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-6)
        assert rel_l2 < 2e-2, rel_l2
        np.testing.assert_allclose(a, b, atol=0.1 * scale, rtol=0)


def test_no_masks_path():
    x, m, _, _ = _inputs(jax.random.key(9))
    rev = _trunk(depth=2)
    params = rev.init(jax.random.key(10), x, m)
    xo, mo = jax.jit(lambda p: rev.apply(p, x, m))(params)
    assert xo.shape == x.shape and mo.shape == m.shape
    assert np.isfinite(np.asarray(xo)).all()


@pytest.mark.slow
def test_model_reversible_trains():
    """Alphafold2(reversible=True): forward + one grad step, finite, and the
    distogram head shape is unchanged."""
    from alphafold2_tpu.models import Alphafold2

    model = Alphafold2(
        dim=32, depth=2, heads=2, dim_head=16, max_seq_len=32,
        reversible=True, msa_tie_row_attn=True,
    )
    k = jax.random.key(11)
    seq = jax.random.randint(jax.random.fold_in(k, 1), (1, 8), 0, 21)
    msa = jax.random.randint(jax.random.fold_in(k, 2), (1, 3, 8), 0, 21)
    mask = jnp.ones((1, 8), bool)
    msa_mask = jnp.ones((1, 3, 8), bool)
    params = model.init(k, seq, msa, mask=mask, msa_mask=msa_mask)

    def loss(p):
        out = model.apply(p, seq, msa, mask=mask, msa_mask=msa_mask)
        return jnp.mean(out**2)

    l, g = jax.jit(jax.value_and_grad(loss))(params)
    assert np.isfinite(float(l))
    gn = float(jnp.sqrt(sum(jnp.sum(x**2) for x in jax.tree.leaves(g))))
    assert np.isfinite(gn) and gn > 0


def test_reversible_requires_msa():
    from alphafold2_tpu.models.trunk import Trunk

    x = jnp.zeros((1, 4, 4, D))
    t = Trunk(dim=D, depth=1, heads=2, dim_head=8, reversible=True)
    with pytest.raises(ValueError):
        t.init(jax.random.key(0), x, None)


def test_reversible_rejects_grid_parallel():
    # the reversible engine's axial passes run dense: combining it with the
    # 2D pair-grid sharding would silently all-gather the pair state and
    # lose the memory benefit — must refuse, like context_parallel does
    from alphafold2_tpu.models.trunk import Trunk

    x = jnp.zeros((1, 4, 4, D))
    m = jnp.zeros((1, 2, 4, D))
    t = Trunk(dim=D, depth=1, heads=2, dim_head=8, reversible=True,
              grid_parallel=True)
    with pytest.raises(ValueError, match="grid_parallel"):
        t.init(jax.random.key(0), x, m)


@pytest.mark.slow
def test_reversible_with_sparse_attention():
    """Composition: block-sparse pair attention (its own custom-vjp Pallas
    path) inside the reversible engine's hand-scheduled backward. Values and
    grads must match the plain-autodiff reversible path."""
    from alphafold2_tpu.ops.sparse import BlockSparseConfig

    _, m, _, mm = _inputs(jax.random.key(20))
    # sparse layouts need block-size-aligned grids: 8x8 with block 4
    x = jax.random.normal(jax.random.key(21), (B, 8, 8, D))
    pm = jnp.ones((B, 8, 8), bool)
    kw = dict(
        dim=D, depth=2, heads=2, dim_head=8,
        sparse_attn=True, seq_len=8,
        sparse_config=BlockSparseConfig(block_size=4, num_random_blocks=0),
    )
    rev = ReversibleTrunk(use_custom_vjp=True, **kw)
    ref = ReversibleTrunk(use_custom_vjp=False, **kw)
    params = rev.init(jax.random.key(22), x, m, pm, mm)

    def loss(mod):
        def f(p):
            xo, mo = mod.apply(p, x, m, pm, mm)
            return jnp.sum(xo**2) + jnp.sum(mo**2)

        return f

    l_rev = float(loss(rev)(params))
    l_ref = float(loss(ref)(params))
    assert np.isclose(l_rev, l_ref, rtol=1e-5)
    gp_rev = jax.grad(loss(rev))(params)
    gp_ref = jax.grad(loss(ref))(params)
    for a, b in zip(jax.tree.leaves(gp_rev), jax.tree.leaves(gp_ref)):
        np.testing.assert_allclose(a, b, atol=3e-4, rtol=1e-3)
