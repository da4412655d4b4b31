"""Tied-row attention under padding: exact mask semantics.

The reference FORBIDS padding under tied rows (alphafold2.py:147-149,
hard assert). This framework is exact instead: padded (row, position)
entries abstain from the shared logits, the r^-0.5 scale counts only
voting rows, and the softmax sees the shared column mask. These tests
prove the exactness property the reference can't offer: tied attention
on a padded batch equals tied attention on the cropped batch.
"""

import jax
import jax.numpy as jnp
import numpy as np

from alphafold2_tpu.ops.attention import Attention


def _attn(key, dim=16, heads=2, dim_head=8):
    mod = Attention(dim=dim, heads=heads, dim_head=dim_head)
    x0 = jnp.zeros((2, 4, dim))
    params = mod.init(key, x0)
    return mod, params


def test_tied_column_padding_matches_cropped():
    # column padding (every row masks the same tail positions) — what MSA
    # length padding is. Padded entries are filled with huge garbage: if
    # anything leaks into the valid region, the comparison fails.
    b, r, n_valid, n_pad, dim = 2, 3, 8, 12, 16
    mod, params = _attn(jax.random.key(0), dim=dim)
    k1, k2 = jax.random.split(jax.random.key(1))
    x_valid = jax.random.normal(k1, (b, r, n_valid, dim))
    garbage = 1e3 * jax.random.normal(k2, (b, r, n_pad - n_valid, dim))
    x_pad = jnp.concatenate([x_valid, garbage], axis=2)
    mask = jnp.concatenate(
        [
            jnp.ones((b, r, n_valid), dtype=bool),
            jnp.zeros((b, r, n_pad - n_valid), dtype=bool),
        ],
        axis=2,
    )

    out_pad = mod.apply(
        params,
        x_pad.reshape(b * r, n_pad, dim),
        mask=mask.reshape(b * r, n_pad),
        tie_dim=r,
    ).reshape(b, r, n_pad, dim)
    # cropped oracle runs the unmasked branch (static r**-0.5 scale):
    # also proves the two branches agree when padding vanishes
    out_crop = mod.apply(
        params, x_valid.reshape(b * r, n_valid, dim), tie_dim=r
    ).reshape(b, r, n_valid, dim)

    np.testing.assert_allclose(
        out_pad[:, :, :n_valid], out_crop, rtol=1e-5, atol=1e-5
    )


def test_tied_fully_masked_rows_abstain():
    # depth padding: extra fully-masked MSA rows must not change the valid
    # rows' outputs (they abstain from the shared logits AND from the
    # row-count scale).
    b, r_valid, r_pad, n, dim = 2, 2, 4, 8, 16
    mod, params = _attn(jax.random.key(2), dim=dim)
    k1, k2 = jax.random.split(jax.random.key(3))
    x_valid = jax.random.normal(k1, (b, r_valid, n, dim))
    garbage = 1e3 * jax.random.normal(k2, (b, r_pad - r_valid, n, dim))
    x_pad = jnp.concatenate([x_valid, garbage], axis=1)
    mask = jnp.concatenate(
        [
            jnp.ones((b, r_valid, n), dtype=bool),
            jnp.zeros((b, r_pad - r_valid, n), dtype=bool),
        ],
        axis=1,
    )

    out_pad = mod.apply(
        params,
        x_pad.reshape(b * r_pad, n, dim),
        mask=mask.reshape(b * r_pad, n),
        tie_dim=r_pad,
    ).reshape(b, r_pad, n, dim)
    out_crop = mod.apply(
        params, x_valid.reshape(b * r_valid, n, dim), tie_dim=r_valid
    ).reshape(b, r_valid, n, dim)

    np.testing.assert_allclose(
        out_pad[:, :r_valid], out_crop, rtol=1e-5, atol=1e-5
    )


def test_tied_masked_grads_finite_and_padding_blind():
    # gradients flow through the masked tied path, and the grads w.r.t.
    # padded inputs are exactly zero (nothing downstream reads them)
    b, r, n_valid, n_pad, dim = 1, 2, 6, 8, 16
    mod, params = _attn(jax.random.key(4), dim=dim)
    x = jax.random.normal(jax.random.key(5), (b * r, n_pad, dim))
    mask = jnp.concatenate(
        [
            jnp.ones((b * r, n_valid), dtype=bool),
            jnp.zeros((b * r, n_pad - n_valid), dtype=bool),
        ],
        axis=1,
    )

    def loss(x):
        out = mod.apply(params, x, mask=mask, tie_dim=r)
        return jnp.sum(jnp.where(mask[..., None], out, 0.0) ** 2)

    g = jax.grad(loss)(x)
    assert np.all(np.isfinite(g))
    np.testing.assert_array_equal(np.asarray(g[:, n_valid:]), 0.0)


def test_tied_cross_attention_padding_matches_cropped():
    # tie_dim + broadcast context + masks on BOTH sides (the AxialAttention
    # tie_row_attn + context combination): query and kv sides are masked
    # independently, so context padding must also be exact
    b, r, n, nc_valid, nc_pad, dim = 2, 3, 6, 5, 8, 16
    mod, params = _attn(jax.random.key(10), dim=dim)
    kx, kc, kg = jax.random.split(jax.random.key(11), 3)
    x = jax.random.normal(kx, (b * r, n, dim))
    ctx_valid = jax.random.normal(kc, (b, nc_valid, dim))
    garbage = 1e3 * jax.random.normal(kg, (b, nc_pad - nc_valid, dim))
    ctx_pad = jnp.concatenate([ctx_valid, garbage], axis=1)
    # broadcast the per-sample context to every row, like AxialAttention does
    ctx_rows = jnp.repeat(ctx_pad, r, axis=0)
    cm = jnp.concatenate(
        [
            jnp.ones((b * r, nc_valid), dtype=bool),
            jnp.zeros((b * r, nc_pad - nc_valid), dtype=bool),
        ],
        axis=1,
    )
    mask = jnp.ones((b * r, n), dtype=bool)

    out_pad = mod.apply(
        params, x, context=ctx_rows, mask=mask, context_mask=cm, tie_dim=r
    )
    out_crop = mod.apply(
        params, x, context=jnp.repeat(ctx_valid, r, axis=0), tie_dim=r
    )
    np.testing.assert_allclose(out_pad, out_crop, rtol=1e-5, atol=1e-5)


def test_model_tied_rows_with_padded_msa_finite():
    # the flagship-bench combination: msa_tie_row_attn=True with a genuinely
    # padded MSA — previously the mask was silently dropped here
    from alphafold2_tpu.models import Alphafold2

    model = Alphafold2(
        dim=32, depth=2, heads=2, dim_head=16, max_seq_len=64,
        msa_tie_row_attn=True,
    )
    b, n, m, nm = 1, 16, 4, 16
    seq = jax.random.randint(jax.random.key(6), (b, n), 0, 21)
    msa = jax.random.randint(jax.random.key(7), (b, m, nm), 0, 21)
    mask = jnp.ones((b, n), dtype=bool)
    msa_mask = jnp.zeros((b, m, nm), dtype=bool)
    msa_mask = msa_mask.at[:, :3, :12].set(True)  # depth AND length padding
    params = model.init(
        jax.random.key(8), seq, msa, mask=mask, msa_mask=msa_mask
    )
    out = model.apply(params, seq, msa, mask=mask, msa_mask=msa_mask)
    assert out.shape == (b, n, n, 37)
    assert np.all(np.isfinite(out))
