"""The second language model (grouped-query attention, one global layer
among window layers, a softmax router that reads the layer's input, ReGLU
experts as one chip's share) against the benchmark's plain reference, tiny
on the CPU.

The reference (``benchmark/reference/swa_lm_model.py``) imports nothing of
the program; weights are the reference's seeded ones, which the program's
parameter tree takes as they are.
"""

import dataclasses
import itertools
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from alphafold2_tpu.config import (  # noqa: E402
    Config, DataConfig, ModelConfig, SwaLMConfig, TrainConfig,
)
from alphafold2_tpu.models import mla_moe_lm as lm  # noqa: E402
from alphafold2_tpu.models import swa_moe_lm as swa  # noqa: E402
from alphafold2_tpu.ops import mla, moe  # noqa: E402
from benchmark.reference import swa_lm_model as ref  # noqa: E402

# one period (global, window, window, window), hidden 64, 4 query heads over
# 2 key/value heads of 16, a window of 16, 8 experts top-2, 4 of them held
SIZES = dict(
    vocab_size=48, hidden_size=64, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    sliding_window_size=16, sliding_window_layout=(0, 1, 1, 1) * 13,
    rope_layout=(0, 1, 1, 1) * 13, moe_ffn_hidden_size=32,
    moe_num_primary_experts=4, router_width=8, first_expert=2,
    moe_num_active_primary_experts=2, rope_theta=1.5e6, rms_norm_eps=1e-6,
)
SEQ, BATCH = 40, 2


def swa_config(sizes=SIZES, **kw) -> SwaLMConfig:
    return SwaLMConfig(**{**dict(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        num_layers=sizes["num_hidden_layers"],
        num_heads=sizes["num_attention_heads"],
        num_kv_heads=sizes["num_key_value_heads"],
        head_dim=sizes["head_dim"],
        sliding_window=sizes["sliding_window_size"], global_every=4,
        moe_intermediate_size=sizes["moe_ffn_hidden_size"],
        n_routed_experts=sizes["router_width"],
        num_experts_per_tok=sizes["moe_num_active_primary_experts"],
        rope_theta=sizes["rope_theta"], rms_norm_eps=sizes["rms_norm_eps"],
        experts_held=sizes["moe_num_primary_experts"],
        first_expert=sizes["first_expert"], bfloat16=False),
        **kw})


def tokens(seed=0, batch=BATCH, seq=SEQ, vocab=SIZES["vocab_size"]):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, vocab, (batch, seq)), jnp.int32)


# as tests/test_mla_moe_lm.py holds the other model: float32 sums in another
# order; bfloat16 2**-8 a product, four layers and a head deep
TOL = {
    "float32": dict(logits=2e-5, loss=1e-5, grad=5e-4),
    # a flipped token moves its two experts' gradients: 0.15 on one leaf of
    # an expert layer that sees 80 tokens
    "bfloat16": dict(logits=5e-2, loss=5e-3, grad=2e-1),
}


@pytest.fixture(scope="module")
def params():
    return ref.init_params(SIZES, 7)


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def both_sides(request, params):
    """(dtype, program's outputs/loss/grads, reference's) on seeded
    weights: the reference is float32 in both cases."""
    model = swa.SwaMoeLM(swa_config(bfloat16=request.param == "bfloat16"))
    toks = tokens()

    def program_loss(p):
        out = model.apply(p, toks)
        return lm.next_token_cross_entropy(out["logits"], toks), out

    (loss_p, out_p), grads_p = jax.value_and_grad(
        program_loss, has_aux=True)(params)
    (loss_r, hist_r), grads_r = jax.value_and_grad(
        ref.loss_fn, has_aux=True)(params, toks, SIZES)
    logits_r, _ = ref.forward(params, toks, SIZES)
    return request.param, (out_p, loss_p, grads_p), (
        logits_r, hist_r, loss_r, grads_r)


def test_the_programs_parameter_tree_is_the_references(params):
    model = swa.SwaMoeLM(swa_config())
    made = jax.eval_shape(model.init, jax.random.key(0), tokens())
    assert jax.tree.map(lambda x: x.shape, made) == jax.tree.map(
        lambda x: x.shape, params)
    assert "attn_global" in params["params"]["layer_0"]
    assert all("attn_window" in params["params"][f"layer_{i}"]
               for i in (1, 2, 3))


def test_logits_agree_with_the_reference(both_sides):
    dtype, (out, _, _), (logits_r, _, _, _) = both_sides
    gap = np.abs(np.asarray(out["logits"] - logits_r)).max(-1) / float(
        jnp.max(jnp.abs(logits_r)))
    assert out["logits"].dtype == jnp.float32
    # in bfloat16 a near-tie of the top-2 flips in a few of the 80 tokens x 4
    # layers (the reference run in bfloat16 flips the same number): such a
    # token gets another expert's output, 0.14 of the largest logit. The
    # others are held to the tolerance
    flipped = np.sort(gap.ravel())[::-1][:0 if dtype == "float32" else 4]
    assert (np.sort(gap.ravel())[::-1][len(flipped):]
            < TOL[dtype]["logits"]).all(), np.sort(gap.ravel())[-8:]


def test_loss_agrees_with_the_reference(both_sides):
    dtype, (_, loss_p, _), (_, _, loss_r, _) = both_sides
    assert abs(float(loss_p) - float(loss_r)) / float(loss_r) \
        < TOL[dtype]["loss"]


def test_every_leafs_gradient_agrees_with_the_reference(both_sides):
    dtype, (_, _, grads_p), (_, _, _, grads_r) = both_sides
    flat_p = ref.leaf_norms(grads_p)
    flat_r = ref.leaf_norms(grads_r)
    assert sorted(flat_p) == sorted(flat_r)
    diff = ref.leaf_norms(jax.tree.map(lambda a, b: a - b, grads_p, grads_r))
    median = float(np.median([float(v) for v in flat_r.values()]))
    for name in flat_r:
        scale = max(float(flat_r[name]), median)
        assert float(diff[name]) / scale < TOL[dtype]["grad"], name
    # the router learns through the combine weights, in every layer
    for i in range(4):
        assert float(flat_p[f"params/layer_{i}/moe/router"]) > 0.0


def test_routing_counts_agree_with_the_reference(both_sides):
    dtype, (out, _, _), (_, hist_r, _, _) = both_sides
    hist_p = np.asarray(out["moe"]["hist"])
    assert hist_p.shape == (4, SIZES["router_width"])
    assert (hist_p.sum(1) == BATCH * SEQ * 2).all()
    flips = np.abs(hist_p - np.asarray(hist_r)).sum()
    # a flip moves two counts; 6 flips of 640 assignments in bfloat16
    assert flips <= (0 if dtype == "float32" else 16)
    held = slice(SIZES["first_expert"],
                 SIZES["first_expert"] + SIZES["moe_num_primary_experts"])
    np.testing.assert_array_equal(
        out["moe"]["assignments_here"], hist_p[:, held].sum(1))
    assert int(out["moe"]["dropped"].sum()) == 0


# ------------------------------------------------ (b) the sum of the shares ---


@pytest.mark.parametrize("shares", [8, 4, 2, 1])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    """Every share's routed part (there is no shared expert to count once)
    adds up to what the uncut reference gives for the whole expert layer:
    the cut to one chip's experts leaves out exactly the other chips'
    parts."""
    uncut = {**SIZES, "moe_num_primary_experts": 8, "first_expert": 0}
    p = ref.init_params(uncut, 11)["params"]["layer_1"]["moe"]
    keys = jax.random.split(jax.random.key(3))
    x = jax.random.normal(keys[0], (BATCH, SEQ, 64), jnp.float32)
    y = jax.random.normal(keys[1], (BATCH, SEQ, 64), jnp.float32)
    whole, hist = ref.expert_layer(p, y, x, uncut, ref.F32)
    held = 8 // shares
    total = jnp.zeros_like(whole)
    for s in range(shares):
        cut = swa_config({**uncut, "moe_num_primary_experts": held,
                          "first_expert": s * held})
        mine = {k: (v[s * held:(s + 1) * held] if k.startswith("w_") else v)
                for k, v in p.items()}
        out, counters = swa.RoutedExperts(cut).apply(
            {"params": mine}, y, route_from=x)
        total = total + out
        np.testing.assert_array_equal(counters["hist"], hist)  # all route alike
    np.testing.assert_allclose(total, whole, rtol=2e-4, atol=2e-5)


# ------------------------------------------------- (c) the router's place ---


def test_the_router_reads_the_layers_input_and_not_the_attention(
        monkeypatch, params):
    """Perturb the attention's weights: the stream after attention changes,
    and so does the block's output, but the experts chosen and their weights
    stay bit for bit, because the router reads ``x``."""
    routed = []
    real = moe.route_softmax
    monkeypatch.setattr(
        moe, "route_softmax",
        lambda *a: routed.append(real(*a)) or routed[-1])
    layer = params["params"]["layer_1"]
    shaken = jax.tree.map(lambda t: t, layer)
    shaken["attn_window"] = jax.tree.map(
        lambda t: t * 1.5, layer["attn_window"])
    x = jax.random.normal(jax.random.key(4), (BATCH, SEQ, 64), jnp.float32)
    block = swa.Block(swa_config(), SIZES["sliding_window_size"])
    out_a, _ = block.apply({"params": layer}, x)
    out_b, _ = block.apply({"params": shaken}, x)
    (experts_a, weights_a), (experts_b, weights_b) = routed
    assert float(jnp.abs(out_a - out_b).max()) > 1e-3
    np.testing.assert_array_equal(experts_a, experts_b)
    np.testing.assert_array_equal(weights_a, weights_b)
    # and the choice is the one the input gives
    want_e, want_w = ref.route(layer["moe"], x.reshape(-1, 64), 2)
    np.testing.assert_array_equal(experts_a, want_e)
    np.testing.assert_allclose(weights_a, want_w, rtol=1e-6)


def test_softmax_over_the_selected_is_softmax_over_all_renormalised():
    x = jax.random.normal(jax.random.key(0), (50, 16))
    w = jax.random.normal(jax.random.key(1), (16, 8))
    experts, weights = moe.route_softmax(x, w, 3)
    probs = jax.nn.softmax(x @ w, axis=-1)
    top, want = jax.lax.top_k(probs, 3)
    np.testing.assert_array_equal(experts, want)
    np.testing.assert_allclose(
        weights, top / top.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-6)


def test_reglu_experts_take_their_activation_as_an_argument():
    sizes = jnp.asarray([3, 0, 5], jnp.int32)
    keys = jax.random.split(jax.random.key(0), 4)
    rows = jax.random.normal(keys[0], (12, 8))
    w_gate, w_up = (jax.random.normal(k, (3, 8, 4)) for k in keys[1:3])
    w_down = jax.random.normal(keys[3], (3, 4, 8))
    out = moe.expert_ffn(rows, sizes, w_gate, w_up, w_down, jnp.float32,
                         jax.nn.relu)
    group = np.repeat([0, 2], [3, 5])
    for r in range(8):
        e = group[r]
        want = (jax.nn.relu(rows[r] @ w_gate[e]) * (rows[r] @ w_up[e])) \
            @ w_down[e]
        np.testing.assert_allclose(out[r], want, rtol=1e-5, atol=1e-6)
    assert bool(jnp.all(out[8:] == 0))
    silu = moe.expert_ffn(rows, sizes, w_gate, w_up, w_down, jnp.float32)
    assert float(jnp.abs(silu[:8] - out[:8]).max()) > 1e-3


# ------------------------------------------- (d) the grouped, windowed core ---


def _dense_masked(q, k, v, window=None):
    """Query by query, in float64 numpy; query head h reads key/value head
    h // (H / G); query i sees keys max(0, i - window + 1) .. i."""
    q, k, v = (np.asarray(t, np.float64) for t in (q, k, v))
    rep = q.shape[1] // k.shape[1]
    out = np.zeros(q.shape[:-1] + (v.shape[-1],))
    for h in range(q.shape[1]):
        g = h // rep
        for i in range(q.shape[2]):
            lo = 0 if window is None else max(0, i - window + 1)
            logits = np.einsum("bd,bjd->bj", q[:, h, i], k[:, g, lo:i + 1])
            p = np.exp(logits - logits.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            out[:, h, i] = np.einsum("bj,bjd->bd", p, v[:, g, lo:i + 1])
    return out


def _dense_masked_jnp(q, k, v, window=None):
    """The same in jnp with the keys repeated to H heads, for gradients."""
    s, rep = q.shape[2], q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(t, rep, axis=1) for t in (k, v))
    ahead = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    keep = ahead >= 0 if window is None else (ahead >= 0) & (ahead < window)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k)
    p = jax.nn.softmax(jnp.where(keep, logits, -jnp.inf), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _qkv(length, b=2, heads=6, groups=2, width=16, seed=0):
    keys = jax.random.split(jax.random.key(length + seed), 3)
    q = jax.random.normal(keys[0], (b, heads, length, width)) * width ** -0.5
    k = jax.random.normal(keys[1], (b, groups, length, width))
    v = jax.random.normal(keys[2], (b, groups, length, width))
    return q, k, v


@pytest.mark.parametrize("window", [None, 1, 16, 33])
@pytest.mark.parametrize("length", [40, 130])
def test_window_core_matches_dense_masked_attention(length, window):
    """The dense path (every length off the TPU): 6 query heads over 2
    key/value heads, a window shorter than the sequence, off the 128 grid."""
    q, k, v = _qkv(length)
    out = mla.causal_core(q, k, v, window=window)
    assert out.shape == q.shape
    np.testing.assert_allclose(
        out, _dense_masked(q, k, v, window), rtol=2e-4, atol=2e-5)
    if window is not None:
        # the window is at work: full causal attention gives something else
        assert float(jnp.abs(out - mla.causal_core(q, k, v)).max()) > 1e-3
        # and one that covers the sequence is no window
        np.testing.assert_array_equal(
            mla.causal_core(q, k, v, window=length), mla.causal_core(q, k, v))


def test_query_heads_read_their_groups_keys_without_a_broadcast():
    """28 query heads over 4 key/value heads: head h reads h // 7, and no
    array with 28 heads of keys or values is made on the way."""
    q, k, v = _qkv(24, b=1, heads=28, groups=4, width=8)
    out = mla.causal_core(q, k, v, window=10)
    np.testing.assert_allclose(
        out, _dense_masked(q, k, v, 10), rtol=2e-4, atol=2e-5)
    # heads 7..13 read group 1 and nothing else
    moved = mla.causal_core(q, k.at[:, 1, 20].add(1.0), v, window=10)
    changed = np.abs(np.asarray(moved - out)).max(axis=(0, 2, 3)) > 1e-6
    np.testing.assert_array_equal(changed, np.arange(28) // 7 == 1)
    jaxpr = jax.make_jaxpr(
        lambda q, k, v: mla.causal_core(q, k, v, window=10))(q, k, v)
    key_shaped = [e.outvars[0].aval.shape for e in jaxpr.eqns
                  if e.primitive.name in ("broadcast_in_dim", "concatenate",
                                          "gather")]
    assert not [s for s in key_shaped if 28 in s and s[-1] == 8 and
                s[-2] == 24 and len(s) == 4], key_shaped


@pytest.mark.parametrize("kind", ["attn_global", "attn_window"])
def test_only_a_window_layer_sees_positions(params, kind):
    """A global layer has no positional encoding: shifting every position by
    the same amount, or by any amount, changes nothing. A window layer's
    rotary positions are relative: a common shift changes nothing, a
    stretch does."""
    layer = params["params"]["layer_0" if kind == "attn_global" else "layer_1"]
    window = None if kind == "attn_global" else SIZES["sliding_window_size"]
    attn = swa.GroupedAttention(swa_config(), window)
    x = jax.random.normal(jax.random.key(2), (BATCH, SEQ, 64), jnp.float32)
    base = attn.apply({"params": layer[kind]}, x)
    shifted = attn.apply({"params": layer[kind]}, x,
                         positions=jnp.arange(SEQ) + 1000)
    stretched = attn.apply({"params": layer[kind]}, x,
                           positions=jnp.arange(SEQ) * 3)
    np.testing.assert_allclose(shifted, base, rtol=1e-3, atol=2e-4)
    gap = float(jnp.abs(stretched - base).max())
    if kind == "attn_global":
        assert gap == 0.0
    else:
        assert gap > 1e-3
    want = ref.attention(layer[kind], x, SIZES, ref.F32, window,
                         rope=window is not None)
    np.testing.assert_allclose(base, want, rtol=2e-4, atol=2e-5)


def test_rotary_turns_the_two_halves_by_position():
    x = jax.random.normal(jax.random.key(0), (2, 9, 3, 8))
    out = np.asarray(mla.rotary_half_split(x, jnp.arange(9), 1.5e6))
    x64 = np.asarray(x, np.float64)
    z = x64[..., :4] + 1j * x64[..., 4:]
    angle = np.arange(9)[:, None, None] * 1.5e6 ** (-np.arange(0, 8, 2) / 8)
    turned = z * np.exp(1j * angle)
    want = np.concatenate([turned.real, turned.imag], -1)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(out[:, 0], np.asarray(x)[:, 0])
    np.testing.assert_allclose(ref.rotary(x, 1.5e6), out, rtol=1e-6,
                               atol=1e-6)


@pytest.mark.parametrize("backward", ["fused", "two_kernels"])
@pytest.mark.parametrize("length, window", [
    (256, None), (384, None), (384, 128), (384, 200), (256, 1)])
def test_splash_path_agrees_with_dense_attention_under_both_masks(
        monkeypatch, kernel_path_on_the_cpu, length, window, backward):
    """The kernel ``causal_core`` takes on a TPU, interpreted here: 6 query
    heads over 2 key/value heads, the causal mask and windows that end on
    and off the 128 grid (384 with 128: blocks on, under and outside the
    band), the output and all three gradients against dense attention with
    the keys repeated, with the fused backward and with the two-kernel one
    (which the block rule takes at the cell's 28 x 16,384 x 128)."""
    if backward == "two_kernels":
        monkeypatch.setattr(mla, "PARTIAL_DQ_BYTES", 0)
    q, k, v = _qkv(length, b=1, seed=window or 0)
    weight = jax.random.normal(jax.random.key(9), q.shape)

    def both(core):
        return jax.value_and_grad(
            lambda q, k, v: (core(q, k, v, window=window) * weight).sum(),
            argnums=(0, 1, 2))

    (loss, grads), (want_loss, want) = (
        both(c)(q, k, v) for c in (mla.causal_core, _dense_masked_jnp))
    np.testing.assert_allclose(
        mla.causal_core(q, k, v, window=window),
        _dense_masked(q, k, v, window), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-4, atol=1e-4)
    for name, got, ref_grad in zip("qkv", grads, want):
        assert got.shape == ref_grad.shape
        np.testing.assert_allclose(
            got, ref_grad, rtol=2e-3, atol=2e-4, err_msg=f"d{name}")


def test_splash_window_path_pads_a_length_off_the_128_grid(
        kernel_path_on_the_cpu):
    q, k, v = _qkv(200, b=1)

    def grads(core):
        return jax.grad(
            lambda q, k, v: (core(q, k, v, window=70) ** 2).sum(),
            argnums=(0, 1, 2))(q, k, v)

    out = mla.causal_core(q, k, v, window=70)
    np.testing.assert_allclose(
        out, _dense_masked(q, k, v, 70), rtol=2e-4, atol=2e-5)
    for got, want in zip(grads(mla.causal_core), grads(_dense_masked_jnp)):
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-4)


def test_a_kernel_is_built_once_for_each_kind_of_layer(
        kernel_path_on_the_cpu):
    """Two periods (2 global, 6 window layers) and their recomputation ask
    for two kernels: one a mask kind, whatever the number of layers."""
    model = swa.SwaMoeLM(swa_config(num_layers=8, sliding_window=64))
    toks = jnp.zeros((1, 128), jnp.int32)
    shapes = jax.eval_shape(model.init, jax.random.key(0), toks)

    def loss(p):
        return model.apply(p, toks)["logits"].sum()

    jax.jit(jax.grad(loss)).lower(shapes)
    info = mla._causal_kernel.cache_info()
    assert info.misses == 2 and info.hits >= 14


# a global layer and a window layer, whose band the 128 positions exceed
TWO_LAYERS = dict(num_layers=2, sliding_window=64)


def _logits_sum_grad(model, toks):
    return jax.grad(lambda p: model.apply(p, toks)["logits"].sum())


@pytest.mark.parametrize("remat, forwards", [("kept", 2), ("bare", 4)])
def test_the_forward_kernel_runs_once_a_layer(
        monkeypatch, kernel_path_on_the_cpu, named_eqns, remat, forwards):
    """A global and a window layer, the two-kernel backward as in the cell:
    the gradient calls every kernel once a layer, the forward too, because
    each layer's remat keeps what the core names. Under a bare ``nn.remat``
    the recomputation calls the forward again: were that count 2 as well,
    the name or the policy would have gone inert."""
    monkeypatch.setattr(mla, "PARTIAL_DQ_BYTES", 0)
    if remat == "bare":
        monkeypatch.setattr(swa, "remat_layer", nn.remat)
    model = swa.SwaMoeLM(swa_config(**TWO_LAYERS))
    toks = tokens(batch=1, seq=128)
    shapes = jax.eval_shape(model.init, jax.random.key(0), toks)
    assert named_eqns(
            "pallas_call", _logits_sum_grad(model, toks), shapes) == {
        "splash_mha_fwd_residuals": forwards,
        "splash_mha_dkv_no_residuals": 2, "splash_mha_dq_no_residuals": 2}


@pytest.mark.parametrize("remat", ["kept", "bare"])
@pytest.mark.parametrize("window", [None, 64])
def test_a_layer_keeps_the_cores_two_results_and_nothing_else(
        kernel_path_on_the_cpu, kept_across_remat, window, remat):
    """Across the recomputation of a layer of either kind: the core's output
    (B, H, S, D) and log-sum-exp (B, H, S) float32 and no other
    intermediate, the layer's own arguments (parameters, stream) and
    constants (the mask's block schedule) aside; a bare ``nn.remat`` keeps
    nothing."""
    wrap = lm.remat_layer if remat == "kept" else nn.remat
    layer = wrap(swa.Block)(swa_config(), window)
    kept = kept_across_remat(layer, jnp.ones((1, 128, 64)))
    assert kept == (["f32[1,4,128,16]", "f32[1,4,128]"]
                    if remat == "kept" else [])


def test_keeping_the_cores_results_leaves_the_gradients_as_they_were(
        monkeypatch, kernel_path_on_the_cpu):
    """The same kernels on the same operands, one call fewer: every leaf's
    gradient is bitwise the bare ``nn.remat``'s (no tolerance)."""
    toks = tokens(batch=1, seq=128)
    seeded = ref.init_params({**SIZES, "num_hidden_layers": 2}, 7)

    def grads():
        model = swa.SwaMoeLM(swa_config(**TWO_LAYERS))
        return jax.jit(_logits_sum_grad(model, toks))(seeded)

    kept = grads()
    monkeypatch.setattr(swa, "remat_layer", nn.remat)
    jax.tree.map(np.testing.assert_array_equal, kept, grads())


@pytest.mark.parametrize("length, window, named", [
    (256, None, 2), (256, 128, 2), (40, None, 0), (40, 16, 0)])
def test_only_the_kernel_path_names_its_results(
        kernel_path_on_the_cpu, named_eqns, length, window, named):
    """Under a gradient the kernel's forward names its output and
    log-sum-exp ``mla.CORE_RESIDUALS``, under either mask; the dense path
    (here: under 128 positions) has no log-sum-exp to keep and names
    nothing."""
    grad = jax.grad(
        lambda q, k, v: mla.causal_core(q, k, v, window=window).sum())
    names = named_eqns("name", grad, *_qkv(length, b=1))
    assert names == ({mla.CORE_RESIDUALS: named} if named else {})


def test_window_schedules_fewer_blocks_than_the_causal_mask():
    """At the cell's 16,384 positions, blocks of 1,024: the causal mask
    keeps 136 block pairs, the window of 4,096 keeps 70 (the band's whole
    squares and the ones it cuts): the kernel's grid shrinks with them."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as sm,
        splash_attention_mask_info as mi,
    )

    def blocks(mask):
        info, _ = mi.process_mask(
            sm.MultiHeadMask([mask]), (1024, 1024), head_shards=1,
            q_seq_shards=1)
        return int((np.asarray(info.block_mask) > 0).sum()), \
            np.asarray(info.data_next).shape[-1]

    shape = (16384, 16384)
    assert blocks(sm.CausalMask(shape)) == (136, 16)
    assert blocks(sm.LocalMask(shape, (4095, 0), 0)) == (70, 5)


# --------------------------------------------- (e) through train(), 4 steps ---


def train_config(steps=3, **kw) -> Config:
    return Config(
        model=ModelConfig(arch="swa_moe_lm"), swa=swa_config(**kw),
        data=DataConfig(source="tokens", batch_size=BATCH, seq_len=SEQ),
        train=TrainConfig(num_steps=steps, log_every=1, warmup_steps=1,
                          gradient_accumulate_every=1, learning_rate=3e-3))


def test_train_runs_the_model_and_the_loss_falls_on_one_batch():
    from alphafold2_tpu.train.loop import train

    seen = []
    state = train(
        train_config(steps=4),
        dataset=itertools.repeat({"tokens": np.asarray(tokens(1))}),
        callbacks=[lambda i, s, m: seen.append(m)])
    losses = [float(m["loss"]) for m in seen]
    assert all(np.isfinite(losses)) and len(losses) == 4
    assert losses[-1] < losses[1] <= losses[0] + 1e-6  # step 0 has rate 0
    assert int(state.step) == 4 and int(seen[-1]["skipped"]) == 0
    for m in seen:  # the counters ride beside the loss, a row a layer
        assert m["moe/hist"].shape == (4, 8)
        assert int(m["moe/dropped"].sum()) == 0
        assert m["moe/load_max_over_mean"].shape == (4,)


def test_train_takes_the_references_parameters_and_reads_its_loss():
    from alphafold2_tpu.train.loop import train

    start = ref.init_params(SIZES, 3)
    kept = jax.tree.map(np.asarray, start)
    seen = []
    train(train_config(steps=1),
          dataset=itertools.repeat({"tokens": np.asarray(tokens(2))}),
          callbacks=[lambda i, s, m: seen.append(m)], init_params=start)
    want, _ = ref.loss_fn(jax.tree.map(jnp.asarray, kept), tokens(2), SIZES)
    assert float(seen[0]["loss"]) == pytest.approx(float(want), rel=1e-5)


def test_train_pre_entry_trains_the_model(capsys):
    import train_pre

    small = swa_config()
    fields = {f.name: getattr(small, f.name)
              for f in dataclasses.fields(small)}
    train_pre.main(
        ["model.arch=swa_moe_lm", "data.source=tokens", "data.batch_size=2",
         f"data.seq_len={SEQ}",
         "train.num_steps=3", "train.log_every=1", "train.warmup_steps=1",
         "train.gradient_accumulate_every=1"]
        + [f"swa.{k}={v}" for k, v in fields.items()])
    out = capsys.readouterr().out
    assert "[step 2]" in out and "moe/assignments_here" in out
    assert '"arch": "swa_moe_lm"' in out


def test_the_token_stream_draws_over_the_models_own_vocabulary():
    cfg = train_config()
    assert cfg.language_model() is cfg.swa
    assert Config().language_model().vocab_size == Config().lm.vocab_size


def test_the_first_language_model_does_not_import_the_second():
    import subprocess

    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from alphafold2_tpu.config import Config, ModelConfig\n"
        "from alphafold2_tpu.train import loop\n"
        "loop.build_task(Config(model=ModelConfig(arch='mla_moe_lm')))\n"
        "bad = [m for m in sys.modules if m.endswith('swa_moe_lm')]\n"
        "assert not bad, bad\n" % ROOT)
    subprocess.run([sys.executable, "-c", code], check=True,
                   env={**os.environ, "JAX_PLATFORMS": "cpu"})
