"""Oracle-diff + dispatch tests for the fused Pallas trunk kernels.

The fused dense (axial) and tied-row MSA attention kernels
(ops/pallas/axial.py, ops/pallas/tied_row.py) run here in interpret mode
on the CPU suite — exact, slow — and are diffed against the dense jnp
formulations they replace, values AND grads, across masked / padded /
odd-length shapes (the acceptance bound is 1e-4; measured ~1e-6). The
compiled-mode Mosaic lowering of the same kernels is certified separately
by analysis/lowering.py (test_pallas_lowering.py).

The dispatch sites are pinned too: Attention.__call__'s tied path takes the
kernel exactly when ``tied_row.tied_row_takes`` says so, and SparseAttention's
backend follows its two module options, then the platform.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alphafold2_tpu.ops.pallas.axial import fused_attention
from alphafold2_tpu.ops.pallas.tied_row import tied_row_attention

ATOL = 1e-4  # the acceptance bound; measured errors sit near 1e-6


# ------------------------------------------------------------ dense oracles


def dense_attention(q, k, v, q_mask=None, kv_mask=None, scale=1.0):
    dots = jnp.einsum("bhid,bhjd->bhij", q, k).astype(jnp.float32) * scale
    if kv_mask is not None:
        dots = jnp.where(kv_mask[:, None, None, :], dots, -1e30)
    p = jax.nn.softmax(dots, axis=-1)
    out = jnp.einsum("bhij,bhjd->bhid", p, v.astype(jnp.float32))
    out = out.astype(q.dtype)
    if q_mask is not None:  # the kernels' flash convention
        out = jnp.where(q_mask[:, None, :, None], out, 0)
    return out


def dense_tied(q, k, v, qm, km, tie_scale, scale):
    """The dense tied contraction of ops/attention.py (inputs pre-zeroed,
    shared masks, voting-row tie scale)."""
    dots = jnp.einsum("brihd,brjhd->bhij", q, k) * scale * tie_scale
    if qm is not None:
        pair = qm[:, None, :, None] & km[:, None, None, :]
        dots = jnp.where(pair, dots, -1e9)
    p = jax.nn.softmax(dots.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhij,brjhd->brihd", p, v)


def tied_inputs(shape, ragged=False, masked=True, seed=0):
    b, r, n, h, d = shape
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], shape)
    k = jax.random.normal(ks[1], shape)
    v = jax.random.normal(ks[2], shape)
    if not masked:
        return q, k, v, None, None, float(r) ** -0.5
    # column padding (every row agrees — what MSA length padding is),
    # optionally one fully-masked row (abstains entirely)
    rows = jnp.ones((b, r, n), bool).at[:, :, max(1, n - 5):].set(False)
    if ragged:
        rows = rows.at[:, 1].set(False)
    q = jnp.where(rows[..., None, None], q, 0)
    k = jnp.where(rows[..., None, None], k, 0)
    v = jnp.where(rows[..., None, None], v, 0)
    n_rows = jnp.maximum((rows.any(-1) & rows.any(-1)).sum(-1), 1)
    tie_scale = (n_rows.astype(jnp.float32) ** -0.5)[:, None, None, None]
    return q, k, v, rows.any(1), rows.any(1), tie_scale


# ---------------------------------------------------- axial kernel oracle


@pytest.mark.parametrize(
    "shape",
    [
        (2, 2, 128, 128, 32),  # exact one-block tiles
        (1, 2, 200, 200, 16),  # odd length: padded keys + sliced queries
        (2, 1, 37, 91, 8),  # rectangular (cross-shape), tiny blocks
    ],
)
def test_fused_attention_matches_dense(shape):
    b, h, nq, nk, d = shape
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (b, h, nq, d))
    k = jax.random.normal(ks[1], (b, h, nk, d))
    v = jax.random.normal(ks[2], (b, h, nk, d))
    q_mask = jnp.ones((b, nq), bool).at[:, nq - 3:].set(False)
    kv_mask = jnp.ones((b, nk), bool).at[:, max(1, nk - 7):].set(False)
    out = fused_attention(
        q, k, v, q_mask=q_mask, kv_mask=kv_mask, sm_scale=d**-0.5
    )
    ref = dense_attention(
        q, k, v, q_mask=q_mask, kv_mask=kv_mask, scale=d**-0.5
    )
    assert out.shape == ref.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=ATOL)


def test_fused_attention_unmasked_and_inside_jit():
    b, h, n, d = 1, 2, 160, 32  # non-block length, no masks at all
    ks = jax.random.split(jax.random.key(2), 3)
    q, k, v = (jax.random.normal(x, (b, h, n, d)) for x in ks)
    out = jax.jit(
        lambda q, k, v: fused_attention(q, k, v, sm_scale=d**-0.5)
    )(q, k, v)
    ref = dense_attention(q, k, v, scale=d**-0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=ATOL)


def test_fused_attention_grad_matches_dense():
    b, h, n, d = 1, 2, 200, 16  # odd length: grads flow through padding
    ks = jax.random.split(jax.random.key(3), 3)
    q, k, v = (jax.random.normal(x, (b, h, n, d)) for x in ks)
    mask = jnp.ones((b, n), bool).at[:, 180:].set(False)

    def loss(fn):
        return jax.grad(
            lambda q, k, v: jnp.sum(
                jnp.sin(fn(q, k, v, kv_mask=mask, sm_scale=d**-0.5))
            ),
            argnums=(0, 1, 2),
        )(q, k, v)

    grads_f = loss(lambda *a, **kw: fused_attention(*a, **kw))
    grads_d = loss(
        lambda q, k, v, kv_mask, sm_scale: dense_attention(
            q, k, v, kv_mask=kv_mask, scale=sm_scale
        )
    )
    for gf, gd in zip(grads_f, grads_d):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd), atol=ATOL)


def test_fused_attention_fully_masked_batch_row_is_finite():
    # one batch entry with EVERY key masked: the l >= 1e-30 guard must
    # yield finite (zero-ish) output, not NaN
    b, h, n, d = 2, 1, 64, 8
    q = jax.random.normal(jax.random.key(4), (b, h, n, d))
    mask = jnp.ones((b, n), bool).at[0].set(False)
    out = fused_attention(q, q, q, kv_mask=mask, sm_scale=d**-0.5)
    assert bool(jnp.all(jnp.isfinite(out)))


# ------------------------------------------------- tied-row kernel oracle


@pytest.mark.parametrize(
    "shape,ragged,masked",
    [
        ((2, 3, 24, 2, 16), False, True),  # column padding
        ((1, 5, 140, 2, 8), False, True),  # odd length, padded blocks
        ((2, 4, 33, 1, 8), True, True),  # a fully-masked row abstains
        ((1, 4, 48, 2, 8), False, False),  # no masks at all
    ],
)
def test_tied_row_matches_dense(shape, ragged, masked):
    q, k, v, qm, km, tie_scale = tied_inputs(shape, ragged, masked)
    d = shape[-1]
    out = tied_row_attention(
        q, k, v, q_mask=qm, kv_mask=km, sm_scale=d**-0.5,
        tie_scale=tie_scale,
    )
    ref = dense_tied(q, k, v, qm, km, tie_scale, d**-0.5)
    valid = (
        jnp.broadcast_to(qm[:, None, :, None, None], ref.shape)
        if qm is not None else jnp.ones_like(ref, bool)
    )
    err = jnp.max(jnp.abs(jnp.where(valid, out - ref, 0)))
    assert float(err) < ATOL


def test_tied_row_grad_matches_dense():
    shape = (1, 4, 60, 2, 8)
    q, k, v, qm, km, tie_scale = tied_inputs(shape, ragged=True)
    d = shape[-1]
    valid = jnp.broadcast_to(
        qm[:, None, :, None, None],
        (shape[0], shape[1], shape[2], shape[3], shape[4]),
    )

    def grads(fn):
        def inner(q_, k_, v_):
            return jnp.sum(jnp.sin(fn(q_, k_, v_)) * valid)

        return jax.grad(inner, argnums=(0, 1, 2))(q, k, v)

    gf = grads(
        lambda a, b, c: tied_row_attention(
            a, b, c, q_mask=qm, kv_mask=km, sm_scale=d**-0.5,
            tie_scale=tie_scale,
        )
    )
    gd = grads(
        lambda a, b, c: dense_tied(a, b, c, qm, km, tie_scale, d**-0.5)
    )
    for x, y in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=ATOL)


# ----------------------------------------------------------- dispatch sites


def test_attention_tied_path_dispatch(monkeypatch):
    """The tied branch must route through the fused kernel exactly when its
    rule (ops/pallas/tied_row.py ``tied_row_takes``) says so — on a TPU,
    steered here, with dropout inactive — and produce the dense numbers
    (valid region) when it does."""
    from alphafold2_tpu.ops.attention import Attention
    from alphafold2_tpu.ops.pallas import tied_row as tied_mod

    x = jax.random.normal(jax.random.key(5), (4, 24, 32))  # (B*R, n, d), R=2
    mask = jnp.ones((4, 24), bool).at[:, 20:].set(False)
    attn = Attention(dim=32, heads=2, dim_head=16)
    params = attn.init(jax.random.key(6), x, mask=mask, tie_dim=2)
    dense_out = attn.apply(params, x, mask=mask, tie_dim=2)

    calls = {"n": 0}
    real = tied_mod.tied_row_attention

    def spy(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(tied_mod, "tied_row_attention", spy)
    drop = Attention(dim=32, heads=2, dim_head=16, dropout=0.5)
    params_d = drop.init(jax.random.key(7), x, tie_dim=2)
    # off the TPU (this suite): the rule says no, the kernel is never touched
    assert not tied_mod.tied_row_takes(False)
    attn.apply(params, x, mask=mask, tie_dim=2)
    assert calls["n"] == 0

    monkeypatch.setattr(tied_mod, "tied_row_available", lambda: True)
    assert tied_mod.tied_row_takes(False) and not tied_mod.tied_row_takes(True)
    fused_out = attn.apply(params, x, mask=mask, tie_dim=2)
    assert calls["n"] == 1
    valid = np.asarray(mask)[:, :, None]
    assert np.max(np.abs(np.asarray(fused_out - dense_out)) * valid) < ATOL

    # active attention-weight dropout needs materialized probabilities:
    # the kernel must NOT be taken even on a TPU
    out = drop.apply(
        params_d, x, tie_dim=2, deterministic=False,
        rngs={"dropout": jax.random.key(8)},
    )
    assert calls["n"] == 1 and bool(jnp.all(jnp.isfinite(out)))
    # ...and with the same module deterministic it is taken again
    drop.apply(params_d, x, tie_dim=2, deterministic=True)
    assert calls["n"] == 2


def test_sparse_backend_policy_registration(monkeypatch):
    """SparseAttention's backend: explicit use_pallas > config.backend >
    the Pallas kernel on a TPU, jnp elsewhere."""
    from alphafold2_tpu.ops.sparse import BlockSparseConfig, SparseAttention

    def impl_name(module):
        # bound via a parent-less setup: _impl only reads config/attrs
        return module._impl().__name__

    base = dict(dim=32, heads=2, dim_head=16, seq_len=64)
    assert impl_name(SparseAttention(**base)) == "block_sparse_attention"
    assert (
        impl_name(SparseAttention(**base, use_pallas=True))
        == "block_sparse_attention_pallas"
    )
    splash = BlockSparseConfig(backend="splash")
    assert (
        impl_name(SparseAttention(**base, config=splash))
        == "block_sparse_attention_splash"
    )
    # the explicit bool wins over the config's backend
    assert (
        impl_name(SparseAttention(**base, config=splash, use_pallas=False))
        == "block_sparse_attention"
    )
    with pytest.raises(ValueError, match="unknown sparse backend"):
        impl_name(SparseAttention(
            **base, config=BlockSparseConfig(backend="warp")))
    # on a TPU the auto case is the Pallas kernel; module choices still win
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert (
        impl_name(SparseAttention(**base)) == "block_sparse_attention_pallas"
    )
    assert (
        impl_name(
            SparseAttention(**base, config=BlockSparseConfig(backend="jnp"))
        )
        == "block_sparse_attention"
    )
