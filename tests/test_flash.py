"""Flash-attention wrapper gating tests. The fused kernel itself is the
stock JAX Pallas TPU op (compiled only on TPU backends; AF2TPU_TEST_TPU=1
runs these paths on hardware) — what is tested hermetically is the
gating/fallback contract the model relies on."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alphafold2_tpu.ops.attention import Attention
from alphafold2_tpu.ops.flash import (
    block_sizes_for, flash_attention, flash_available,
)


def test_unavailable_off_tpu_returns_none():
    assert not flash_available()  # suite runs on the CPU backend
    q = jnp.ones((1, 2, 16, 8))
    assert flash_attention(q, q, q) is None


def test_flash_skipped_for_tied_rows_and_dropout(monkeypatch):
    # tied rows and attn dropout are dense-path features: the flash path
    # must not be taken for them even where the kernel takes the shape
    from alphafold2_tpu.ops import flash as flash_mod

    x = jax.random.normal(jax.random.key(2), (4, 128, 32))  # (B*R, n, d)
    plain = Attention(dim=32, heads=2, dim_head=16)
    drop = Attention(dim=32, heads=2, dim_head=16, dropout=0.5)
    # before the mock: init is deterministic
    params = plain.init(jax.random.key(3), x, tie_dim=2)
    params_d = drop.init(jax.random.key(4), x)
    tied = plain.apply(params, x, tie_dim=2)

    def boom(*a, **kw):  # pragma: no cover - must not be reached
        raise AssertionError("flash path taken for tied rows or dropout")

    monkeypatch.setattr(flash_mod, "flash_available", lambda: True)
    monkeypatch.setattr(flash_mod, "flash_attention", boom)
    assert flash_mod.flash_takes(128, 128, 16)
    assert np.allclose(plain.apply(params, x, tie_dim=2), tied, atol=1e-6)

    # dropout gate: with attn dropout active (deterministic=False), the flash
    # path must NOT be taken even when the kernel is "available" — attention-
    # weight dropout needs materialized probabilities
    out = drop.apply(
        params_d, x, deterministic=False, rngs={"dropout": jax.random.key(5)}
    )
    assert np.all(np.isfinite(out))
    # ...and with deterministic=True the (mocked) flash path IS selected
    with np.testing.assert_raises(AssertionError):
        drop.apply(params_d, x, deterministic=True)


def test_compressed_cross_attention_routes_through_flash(monkeypatch):
    """KV-compressed cross-attention composes with the fused kernel: the
    flash branch sees the already-compressed k/v and the pooled mask. At
    large crops this is what keeps the (N^2 queries x compressed keys)
    logits out of HBM (bench config 3)."""
    from alphafold2_tpu.ops import flash as flash_mod

    b, n, nc, d = 2, 130, 30, 32  # one long axis: the kernel takes the call
    x = jax.random.normal(jax.random.key(6), (b, n, d))
    ctx = jax.random.normal(jax.random.key(7), (b, nc, d))
    cmask = jnp.ones((b, nc), bool).at[:, 25:].set(False)

    attn = Attention(dim=d, heads=2, dim_head=16, compress_ratio=3)
    params = attn.init(jax.random.key(8), x, context=ctx, context_mask=cmask)
    out_d = attn.apply(params, x, context=ctx, context_mask=cmask)

    seen = {}

    def spy(q, k, v, q_mask=None, kv_mask=None, sm_scale=1.0):
        seen["kv_len"] = k.shape[2]
        seen["kv_mask"] = kv_mask
        # the kernel's answer, in jnp: the module must project this output
        dots = jnp.einsum("bhid,bhjd->bhij", q, k) * sm_scale
        dots = jnp.where(kv_mask[:, None, None, :], dots, -1e9)
        return jnp.einsum("bhij,bhjd->bhid", jax.nn.softmax(dots, -1), v)

    monkeypatch.setattr(flash_mod, "flash_available", lambda: True)
    monkeypatch.setattr(flash_mod, "flash_attention", spy)
    out_f = attn.apply(params, x, context=ctx, context_mask=cmask)

    assert seen["kv_len"] == nc // 3  # kernel sees compressed KV
    assert seen["kv_mask"].shape == (b, nc // 3)  # ...and the pooled mask
    # pooled mask: windows [24..26] contain a valid position -> True;
    # windows [27..29] all padded -> False
    assert bool(seen["kv_mask"][0, 8]) and not bool(seen["kv_mask"][0, 9])
    assert np.allclose(out_f, out_d, atol=1e-5)


def test_context_parallel_excludes_compression(monkeypatch):
    # the compressed KV length no longer matches the sp shard layout, so the
    # context-parallel fused path must not engage when compress_ratio > 1 —
    # even with an active sp mesh (faked here so the gate itself is what is
    # under test, not the mesh lookup)
    import types

    from alphafold2_tpu.parallel import seq_parallel as sp_mod
    from alphafold2_tpu.parallel import sharding as sharding_mod

    calls = {"n": 0}

    def boom(*a, **kw):
        calls["n"] += 1
        raise AssertionError("context-parallel path taken with compressed KV")

    fake_mesh = types.SimpleNamespace(axis_names=(sp_mod.SEQ_AXIS_NAME,))
    monkeypatch.setattr(sp_mod, "sequence_parallel_attention", boom)
    monkeypatch.setattr(sharding_mod, "active_mesh", lambda: fake_mesh)

    x = jax.random.normal(jax.random.key(9), (1, 8, 32))
    ctx = jax.random.normal(jax.random.key(10), (1, 12, 32))
    a = Attention(dim=32, heads=2, dim_head=16, compress_ratio=2,
                  context_parallel="ring")
    params = a.init(jax.random.key(11), x, context=ctx)
    out = a.apply(params, x, context=ctx)  # compressed: gate skips the path
    assert np.all(np.isfinite(out)) and calls["n"] == 0

    # sanity that the fake-mesh plumbing reaches the path when uncompressed:
    # the same call without compression must enter it (and hit the mock)
    b = Attention(dim=32, heads=2, dim_head=16, context_parallel="ring")
    plain = Attention(dim=32, heads=2, dim_head=16)
    params_b = plain.init(jax.random.key(12), x, context=ctx)  # same params
    with np.testing.assert_raises(AssertionError):
        b.apply(params_b, x, context=ctx)
    assert calls["n"] == 1


def test_flash_pads_to_block_multiples(monkeypatch):
    """The stock kernel hard-requires both sequence axes divisible by 128;
    the wrapper must pad (mask-excluding the padding) and slice the output
    — otherwise e.g. compressed-KV lengths silently fall back to dense."""
    import jax.experimental.pallas.ops.tpu.flash_attention as stock

    from alphafold2_tpu.ops import flash as flash_mod

    seen = {}

    def fake_kernel(q, k, v, *, segment_ids=None, sm_scale=1.0, **kw):
        seen["nq"], seen["nk"] = q.shape[2], k.shape[2]
        seen["seg"] = segment_ids
        return jnp.zeros(q.shape, q.dtype)

    monkeypatch.setattr(flash_mod, "flash_available", lambda: True)
    monkeypatch.setattr(stock, "flash_attention", fake_kernel)

    b, h, nq, nk, d = 1, 2, 200, 342, 16
    q = jnp.ones((b, h, nq, d))
    k = jnp.ones((b, h, nk, d))
    v = jnp.ones((b, h, nk, d))
    out = flash_mod.flash_attention(q, k, v)
    assert out.shape == (b, h, nq, d)  # sliced back to the caller's nq
    assert seen["nq"] == 256 and seen["nk"] == 384  # padded to 128 multiples
    qs, ks = seen["seg"].q, seen["seg"].kv
    # padding positions are mask-excluded (segment id 0 vs valid 1)
    assert qs.shape == (b, 256) and ks.shape == (b, 384)
    assert bool(qs[0, nq - 1]) and not bool(qs[0, nq])
    assert bool(ks[0, nk - 1]) and not bool(ks[0, nk])

    # aligned shapes with no masks still skip segment-id construction
    q2 = jnp.ones((b, h, 128, d))
    flash_mod.flash_attention(q2, q2, q2)
    assert seen["seg"] is None


def test_flash_engages_with_one_short_axis(monkeypatch):
    # nq huge / nk sub-block (compressed context): the short axis is padded
    # to one block instead of silently falling back to the dense path
    import jax.experimental.pallas.ops.tpu.flash_attention as stock

    from alphafold2_tpu.ops import flash as flash_mod

    seen = {}

    def fake_kernel(q, k, v, *, segment_ids=None, sm_scale=1.0, **kw):
        seen["nk"] = k.shape[2]
        return jnp.zeros(q.shape, q.dtype)

    monkeypatch.setattr(flash_mod, "flash_available", lambda: True)
    monkeypatch.setattr(stock, "flash_attention", fake_kernel)

    q = jnp.ones((1, 2, 256, 16))
    k = jnp.ones((1, 2, 86, 16))
    out = flash_mod.flash_attention(q, k, k)
    assert out.shape == (1, 2, 256, 16)
    assert seen["nk"] == 128  # padded up to one block

    # both axes sub-block: dense stays preferred
    tiny = jnp.ones((1, 2, 64, 16))
    assert flash_mod.flash_attention(tiny, tiny, tiny) is None


# name: (nq, nk, head size, on a TPU, the kernel takes it: the flat path and
# the ring, for a local block of this shape, alike)
ONE_RULE_CASES = {
    # the flagship cells' cross-attentions, whole and as one ring step's block
    "pair_from_msa": (65536, 4096, 64, True, True),
    "msa_from_pair": (4096, 65536, 64, True, True),
    "ring_local_pair_from_msa": (32768, 2048, 64, True, True),
    "ring_local_msa_from_pair": (2048, 32768, 64, True, True),
    "pair_axial": (256, 256, 64, True, True),
    "one_short_axis": (65536, 86, 64, True, True),
    # the kernel takes one head size, a multiple of 128 once it is over 128,
    # and nothing pads heads: 192 stays off it (the language model's causal
    # core at 192/128 has a kernel of its own, ops/mla.py)
    "head_192": (8192, 8192, 192, True, False),
    "head_256": (512, 512, 256, True, True),
    "under_one_block_64": (64, 64, 64, True, False),
    "under_one_block_100": (100, 100, 64, True, False),
    "off_the_tpu": (65536, 4096, 64, False, False),
}


@pytest.mark.parametrize("name", sorted(ONE_RULE_CASES))
def test_flat_path_and_ring_ask_one_rule(monkeypatch, name):
    """``Attention``'s flat path and the ring both ask ``ops/flash.py``
    ``flash_takes`` whether the stock kernel serves the shape, and each takes
    the kernel exactly where it says so. Shapes only (``jax.eval_shape``):
    the kernel and the ring's kernel blocks are stand-ins that record that
    they were asked for."""
    import jax.experimental.pallas.ops.tpu.flash_attention as stock
    from jax.sharding import Mesh, PartitionSpec as P

    from alphafold2_tpu.ops import flash as flash_mod
    from alphafold2_tpu.parallel import seq_parallel as sp_mod

    nq, nk, d, on_tpu, takes = ONE_RULE_CASES[name]
    answers = []
    rule = flash_mod.flash_takes

    def recording(nq, nk, head_dim):
        answers.append(rule(nq, nk, head_dim))
        return answers[-1]

    kernel_calls, ring_blocks = [], []

    def fake_kernel(q, k, v, **kw):
        kernel_calls.append((q.shape[2], k.shape[2], q.shape[3]))
        return jnp.zeros(q.shape, q.dtype)

    def fake_blocks(b, h, nq, nk, d, dtype, scale):
        ring_blocks.append((nq, nk, d))
        return sp_mod._jnp_blocks(scale)

    monkeypatch.setattr(flash_mod, "flash_available", lambda: on_tpu)
    monkeypatch.setattr(flash_mod, "flash_takes", recording)
    monkeypatch.setattr(stock, "flash_attention", fake_kernel)
    monkeypatch.setattr(sp_mod, "_flash_blocks", fake_blocks)

    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype)

    attn = Attention(dim=8, heads=1, dim_head=d)
    out = jax.eval_shape(
        lambda x, c: attn.init_with_output(
            jax.random.key(0), x, context=c)[0],
        shape(1, nq, 8), shape(1, nk, 8))
    assert out.shape == (1, nq, 8)
    assert bool(kernel_calls) == takes
    assert answers and set(answers) == {takes}
    if takes:  # both axes padded to the kernel's lanes, heads as they are
        assert all(q % 128 == 0 and k % 128 == 0 and w == d
                   for q, k, w in kernel_calls)
    del answers[:]

    mesh = Mesh(np.array(jax.devices()[:2]), ("sp",))
    spec = P(None, None, "sp", None)
    out = jax.eval_shape(
        jax.shard_map(
            lambda q, k, v, m: sp_mod.ring_attention(q, k, v, m),
            mesh=mesh, in_specs=(spec, spec, spec, P(None, "sp")),
            out_specs=spec, check_vma=False),
        shape(1, 1, 2 * nq, d), shape(1, 1, 2 * nk, d),
        shape(1, 1, 2 * nk, d), shape(1, 2 * nk, dtype=bool))
    assert out.shape == (1, 1, 2 * nq, d)
    assert bool(ring_blocks) == takes
    assert answers == [takes]


# (batch, heads, nq, nk, head_dim, dtype) as the wrapper hands them on: padded
# to 128
BLOCK_RULE_SHAPES = {
    "pair_from_msa": (1, 8, 256 * 256, 16 * 256, 64, "bfloat16"),
    "msa_from_pair": (1, 8, 16 * 256, 256 * 256, 64, "bfloat16"),
    "pair_axial": (256, 8, 256, 256, 64, "bfloat16"),
    "pair_axial_mesh_half": (128, 8, 256, 256, 64, "bfloat16"),
    "pair_axial_crop384": (384, 8, 384, 384, 64, "bfloat16"),
    "compressed_keys_1408": (1, 8, 256 * 256, 1408, 64, "bfloat16"),
    "served_3L_stream": (2, 8, 768 * 768, 5 * 768, 64, "bfloat16"),
    "short_query_axis": (1, 8, 128, 256 * 256, 64, "bfloat16"),  # nq 64 -> 128
    "short_key_axis": (1, 8, 256 * 256, 128, 64, "bfloat16"),
    "odd_multiple_queries": (1, 8, 17 * 128, 4096, 64, "bfloat16"),
    "odd_multiple_keys": (1, 8, 4096, 17 * 128, 64, "bfloat16"),
    "odd_multiples_both": (3, 4, 5 * 128, 9 * 128, 64, "float32"),
    "one_block_odd_batch": (3, 4, 128, 128, 64, "float32"),
    "wide_head_float32": (1, 8, 256 * 256, 4096, 256, "float32"),
    # one query block against a whole key axis too large for block_b 2 or 4
    "msa_queries_pair_keys_2048": (1, 8, 512, 2048, 64, "bfloat16"),
    "one_q_block_keys_1408": (1, 8, 384, 1408, 64, "bfloat16"),
    "one_q_block_even_batch": (2, 8, 512, 1152, 64, "bfloat16"),
    "one_q_block_at_area_cap": (4, 8, 256, 512, 64, "bfloat16"),
    "one_q_block_past_area_cap": (4, 8, 256, 640, 64, "bfloat16"),
    # the dq wrapper's di, (b, h, nq, block_k_major_dq) float32, above 1 GiB
    # at 512 keys
    "pair_from_msa_crop384": (1, 8, 384 * 384, 16 * 384, 64, "bfloat16"),
    "pair_from_msa_batch2": (2, 8, 256 * 256, 16 * 256, 64, "bfloat16"),
    "pair_from_msa_16_heads": (1, 16, 256 * 256, 16 * 256, 64, "bfloat16"),
    "whole_key_axis_2048": (1, 8, 256 * 256, 2048, 64, "bfloat16"),
}


@pytest.mark.parametrize("name", sorted(BLOCK_RULE_SHAPES))
def test_block_rule_gives_blocks_the_kernel_accepts(name):
    """What the stock kernel's ``_verify_block`` and ``BlockSizes`` ask of a
    block set, for every length the wrapper can hand it: multiples of 128
    that divide the padded axis, inner blocks dividing their major blocks,
    the backward's query blocks dividing ``nq`` (the forward's need not, and
    still do)."""
    batch, heads, nq, nk, head_dim, dtype = BLOCK_RULE_SHAPES[name]
    bs = block_sizes_for(batch, heads, nq, nk, head_dim, dtype)  # constructs
    assert bs.has_backward_blocks
    axis = {"q": nq, "k": nk}
    blocks = dataclasses.asdict(bs)
    assert batch % blocks.pop("block_b") == 0
    for field, size in blocks.items():
        n = axis[field.split("_")[1]]
        assert size % 128 == 0 and 128 <= size <= n and n % size == 0, field
    for major, minor in [
        ("block_k_major", "block_k"),
        ("block_q_major_dkv", "block_q_dkv"),
        ("block_k_major_dkv", "block_k_dkv"),
        ("block_k_major_dq", "block_k_dq"),
    ]:
        assert blocks[major] % blocks[minor] == 0, (major, minor)
    # one K or V tile stays within 1 MiB whatever the head width and dtype
    row = head_dim * jnp.dtype(dtype).itemsize
    assert max(bs.block_k_major, bs.block_k_major_dkv) * row <= max(
        2**20, 128 * row)
    # the dq wrapper's di stays within 1 GiB, or within the default's
    di_row = batch * heads * nq * 4
    assert bs.block_k_major_dq * di_row <= max(2**30, 128 * di_row)
    if name in ("pair_from_msa", "msa_from_pair"):
        # the flagship's cross-attentions leave the 128 x 128 default
        assert min(blocks.values()) >= 256
        assert bs.block_k >= 1024 and bs.block_q_major_dkv >= 1024
        assert bs.block_k_major_dq == 512  # di exactly 1 GiB: measured
    if name.startswith("pair_from_msa_"):
        assert bs.block_k_major_dq < 512 and bs.block_k == 1024
    if "one_q_block" in name or name == "msa_queries_pair_keys_2048":
        assert bs.block_q == nq and bs.block_k == nk
        assert bs.block_b == {"one_q_block_at_area_cap": 4,
                              "one_q_block_past_area_cap": 2}.get(name, 1)
    if name.startswith("pair_axial"):
        assert bs.block_k == nk  # the kernel's single-step body
        assert bs.block_b == {256: 4, 384: 2}[nq]  # sequences a grid step


# the eleven blocks the rule gave the two flagship cells' shape classes when
# PR 26 set it (fields in BlockSizes' order)
FLAGSHIP_BLOCKS = {
    "pair_from_msa": (512, 4096, 1024, 1, 1024, 2048, 1024, 256, 512, 512,
                      1024),
    "msa_from_pair": (512, 4096, 1024, 1, 1024, 2048, 1024, 256, 512, 512,
                      1024),
    "pair_axial": (256, 256, 256, 4, 256, 256, 256, 256, 256, 256, 256),
    "pair_axial_mesh_half": (256, 256, 256, 4, 256, 256, 256, 256, 256, 256,
                             256),
}


@pytest.mark.parametrize("name", sorted(FLAGSHIP_BLOCKS))
def test_flagship_blocks_are_the_ones_pr26_measured(name):
    bs = block_sizes_for(*BLOCK_RULE_SHAPES[name])
    assert dataclasses.astuple(bs) == FLAGSHIP_BLOCKS[name]


