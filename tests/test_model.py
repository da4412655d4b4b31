"""Model-level tests: coverage model is reference tests/test_attention.py
(test_main, test_msa_tie_row_attn, test_templates, test_reversible), upgraded
with finite-ness and gradient checks; small dims for CPU speed."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alphafold2_tpu.models import Alphafold2


def _inputs(key, b=1, n=16, m=3, nm=16):
    k1, k2 = jax.random.split(key)
    seq = jax.random.randint(k1, (b, n), 0, 21)
    msa = jax.random.randint(k2, (b, m, nm), 0, 21)
    mask = jnp.ones((b, n), dtype=bool)
    msa_mask = jnp.ones((b, m, nm), dtype=bool)
    return seq, msa, mask, msa_mask


def test_main():
    model = Alphafold2(dim=32, depth=2, heads=2, dim_head=16, max_seq_len=64)
    seq, msa, mask, msa_mask = _inputs(jax.random.key(0))
    params = model.init(jax.random.key(1), seq, msa, mask=mask, msa_mask=msa_mask)
    out = model.apply(params, seq, msa, mask=mask, msa_mask=msa_mask)
    assert out.shape == (1, 16, 16, 37)
    assert np.all(np.isfinite(out))


def test_no_msa():
    # reference train_pre.py path: model(seq, mask=mask) with no MSA at all
    model = Alphafold2(dim=32, depth=1, heads=2, dim_head=16, max_seq_len=64)
    seq = jax.random.randint(jax.random.key(0), (2, 12), 0, 21)
    mask = jnp.ones((2, 12), dtype=bool)
    params = model.init(jax.random.key(1), seq, mask=mask)
    out = model.apply(params, seq, mask=mask)
    assert out.shape == (2, 12, 12, 37)


def test_msa_tie_row_attn():
    model = Alphafold2(
        dim=32, depth=2, heads=2, dim_head=16, max_seq_len=64, msa_tie_row_attn=True
    )
    seq, msa, mask, msa_mask = _inputs(jax.random.key(2))
    params = model.init(jax.random.key(3), seq, msa, mask=mask, msa_mask=msa_mask)
    out = model.apply(params, seq, msa, mask=mask, msa_mask=msa_mask)
    assert out.shape == (1, 16, 16, 37)
    assert np.all(np.isfinite(out))


def test_embedds_path():
    # the ESM/PLM path — broken in the reference (SURVEY.md S2.5), works here
    model = Alphafold2(dim=32, depth=1, heads=2, dim_head=16, max_seq_len=64, num_embedds=64)
    seq = jax.random.randint(jax.random.key(0), (1, 12), 0, 21)
    embedds = jax.random.normal(jax.random.key(1), (1, 12, 64))
    mask = jnp.ones((1, 12), dtype=bool)
    params = model.init(jax.random.key(2), seq, mask=mask, embedds=embedds)
    out = model.apply(params, seq, mask=mask, embedds=embedds)
    assert out.shape == (1, 12, 12, 37)
    assert np.all(np.isfinite(out))


def test_templates():
    b, n, T = 1, 12, 2
    model = Alphafold2(dim=32, depth=1, heads=2, dim_head=16, max_seq_len=64)
    key = jax.random.key(4)
    seq, msa, mask, msa_mask = _inputs(key, b=b, n=n, m=2, nm=n)
    templates_seq = jax.random.randint(jax.random.key(5), (b, T, n), 0, 21)
    templates_coors = jax.random.normal(jax.random.key(6), (b, T, n, 3)) * 5
    templates_mask = jnp.ones((b, T, n), dtype=bool)
    kwargs = dict(
        mask=mask,
        msa_mask=msa_mask,
        templates_seq=templates_seq,
        templates_coors=templates_coors,
        templates_mask=templates_mask,
    )
    params = model.init(jax.random.key(7), seq, msa, **kwargs)
    out = model.apply(params, seq, msa, **kwargs)
    assert out.shape == (b, n, n, 37)
    assert np.all(np.isfinite(out))


def test_templates_with_sidechains():
    b, n, T = 1, 8, 2
    model = Alphafold2(dim=32, depth=1, heads=2, dim_head=16, max_seq_len=64)
    seq, msa, mask, msa_mask = _inputs(jax.random.key(8), b=b, n=n, m=2, nm=n)
    kwargs = dict(
        mask=mask,
        msa_mask=msa_mask,
        templates_seq=jax.random.randint(jax.random.key(9), (b, T, n), 0, 21),
        templates_coors=jax.random.normal(jax.random.key(10), (b, T, n, 3)) * 5,
        templates_mask=jnp.ones((b, T, n), dtype=bool),
        templates_sidechains=jax.random.normal(jax.random.key(11), (b, T, n, 3)),
    )
    params = model.init(jax.random.key(12), seq, msa, **kwargs)
    out = model.apply(params, seq, msa, **kwargs)
    assert out.shape == (b, n, n, 37)
    assert np.all(np.isfinite(out))


def test_grad_flows():
    model = Alphafold2(dim=32, depth=1, heads=2, dim_head=16, max_seq_len=64)
    seq, msa, mask, msa_mask = _inputs(jax.random.key(13), n=8, nm=8)
    params = model.init(jax.random.key(14), seq, msa, mask=mask, msa_mask=msa_mask)

    def loss(p):
        return jnp.sum(model.apply(p, seq, msa, mask=mask, msa_mask=msa_mask))

    g = jax.grad(loss)(params)
    leaves = jax.tree.leaves(g)
    assert all(np.all(np.isfinite(l)) for l in leaves)
    assert any(np.any(l != 0) for l in leaves)


def test_cross_attn_compression():
    model = Alphafold2(
        dim=32, depth=1, heads=2, dim_head=16, max_seq_len=64,
        cross_attn_compress_ratio=2,
    )
    seq, msa, mask, msa_mask = _inputs(jax.random.key(15), n=10, m=3, nm=10)
    params = model.init(jax.random.key(16), seq, msa, mask=mask, msa_mask=msa_mask)
    out = model.apply(params, seq, msa, mask=mask, msa_mask=msa_mask)
    assert out.shape == (1, 10, 10, 37)
    assert np.all(np.isfinite(out))


def test_distogram_symmetric_under_symmetric_mask():
    model = Alphafold2(dim=32, depth=1, heads=2, dim_head=16, max_seq_len=64)
    seq, msa, mask, msa_mask = _inputs(jax.random.key(17), n=8, nm=8)
    params = model.init(jax.random.key(18), seq, msa, mask=mask, msa_mask=msa_mask)
    out = model.apply(params, seq, msa, mask=mask, msa_mask=msa_mask)
    assert np.allclose(out, np.swapaxes(out, 1, 2), atol=1e-4)


def test_axial_attention_broadcast_context():
    """AxialAttention's optional cross-attention context is broadcast to
    every row/column pass (reference alphafold2.py:270-276): runs, is
    finite, differentiable, and masked context changes nothing where the
    context is fully masked out vs absent-key baseline shapes."""
    from alphafold2_tpu.ops.attention import AxialAttention

    k = jax.random.key(31)
    x = jax.random.normal(jax.random.fold_in(k, 0), (2, 6, 6, 16))
    ctx = jax.random.normal(jax.random.fold_in(k, 1), (2, 5, 16))
    ctx_mask = jnp.ones((2, 5), bool).at[:, 3:].set(False)
    mod = AxialAttention(dim=16, heads=2, dim_head=8)
    params = mod.init(jax.random.fold_in(k, 2), x, context=ctx,
                      context_mask=ctx_mask)
    out = mod.apply(params, x, context=ctx, context_mask=ctx_mask)
    assert out.shape == x.shape
    assert np.isfinite(np.asarray(out)).all()

    # masked-out context columns must not influence the output
    ctx2 = ctx.at[:, 3:].set(123.0)
    out2 = mod.apply(params, x, context=ctx2, context_mask=ctx_mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2), atol=1e-5)

    g = jax.grad(
        lambda c: jnp.sum(
            mod.apply(params, x, context=c, context_mask=ctx_mask) ** 2
        )
    )(ctx)
    assert np.isfinite(np.asarray(g)).all()


def test_templates_explicit_distogram():
    """User-supplied template distance buckets skip auto-binning (reference
    alphafold2.py:508-509) and produce the same result as pre-bucketing the
    coordinates manually."""
    from alphafold2_tpu.utils.structure import get_bucketed_distance_matrix

    b, n, T = 1, 8, 2
    model = Alphafold2(dim=32, depth=1, heads=2, dim_head=16, max_seq_len=32,
                       template_attn_depth=1, use_se3_template_embedder=False)
    k = jax.random.key(41)
    seq = jax.random.randint(jax.random.fold_in(k, 0), (b, n), 0, 21)
    msa = jax.random.randint(jax.random.fold_in(k, 1), (b, 2, n), 0, 21)
    t_seq = jax.random.randint(jax.random.fold_in(k, 2), (b, T, n), 0, 21)
    t_coors = jax.random.normal(jax.random.fold_in(k, 3), (b, T, n, 3)) * 5
    masks = dict(
        mask=jnp.ones((b, n), bool), msa_mask=jnp.ones((b, 2, n), bool),
        templates_mask=jnp.ones((b, T, n), bool),
    )
    params = model.init(k, seq, msa, templates_seq=t_seq,
                        templates_coors=t_coors, **masks)
    out_auto = model.apply(params, seq, msa, templates_seq=t_seq,
                           templates_coors=t_coors, **masks)
    t_dist = jnp.maximum(
        get_bucketed_distance_matrix(t_coors, masks["templates_mask"]), 0
    )
    out_explicit = model.apply(params, seq, msa, templates_seq=t_seq,
                               templates_coors=t_coors, templates_dist=t_dist,
                               **masks)
    np.testing.assert_allclose(np.asarray(out_auto), np.asarray(out_explicit),
                               atol=1e-5)
