"""The language model (latent attention + routed experts as one chip's share)
against the benchmark's plain reference, tiny on the CPU.

The reference (``benchmark/reference/lm_model.py``) imports nothing of the
program; weights are the reference's seeded ones, which the program's
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
    Config, DataConfig, LMConfig, ModelConfig, TrainConfig,
)
from alphafold2_tpu.models import mla_moe_lm as lm  # noqa: E402
from alphafold2_tpu.ops import mla, moe  # noqa: E402
from benchmark.reference import lm_model as ref  # noqa: E402

# 2 layers (1 dense + 1 expert), hidden 64, 8 experts top-2, 4 of them held
SIZES = dict(
    vocab_size=48, hidden_size=64, num_hidden_layers=2,
    first_k_dense_replace=1, num_attention_heads=4, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32, intermediate_size=96,
    moe_intermediate_size=32, n_routed_experts=4, router_width=8,
    first_expert=2, n_shared_experts=2, num_experts_per_tok=2,
    routed_scaling_factor=2.448, rope_theta=1e6, rms_norm_eps=1e-6,
)
SEQ, BATCH = 40, 2


def lm_config(sizes=SIZES, **kw) -> LMConfig:
    return LMConfig(**{**dict(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        num_layers=sizes["num_hidden_layers"],
        first_k_dense=sizes["first_k_dense_replace"],
        num_heads=sizes["num_attention_heads"],
        qk_nope_head_dim=sizes["qk_nope_head_dim"],
        qk_rope_head_dim=sizes["qk_rope_head_dim"],
        v_head_dim=sizes["v_head_dim"], kv_lora_rank=sizes["kv_lora_rank"],
        intermediate_size=sizes["intermediate_size"],
        moe_intermediate_size=sizes["moe_intermediate_size"],
        n_routed_experts=sizes["router_width"],
        n_shared_experts=sizes["n_shared_experts"],
        num_experts_per_tok=sizes["num_experts_per_tok"],
        routed_scaling_factor=sizes["routed_scaling_factor"],
        rope_theta=sizes["rope_theta"], rms_norm_eps=sizes["rms_norm_eps"],
        experts_held=sizes["n_routed_experts"],
        first_expert=sizes["first_expert"], bfloat16=False),
        **kw})


def tokens(seed=0, batch=BATCH, seq=SEQ, vocab=SIZES["vocab_size"]):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, vocab, (batch, seq)), jnp.int32)


# what each compute type is held to. float32: both sides are float32 sums in
# different orders. bfloat16: 8 bits of mantissa an operand, so 2**-8 = 0.4%
# a product and a few of them deep (two layers, a head): the logits read 1-2%
# of their largest entry, the loss (a mean over 78 positions) a tenth of
# that, a leaf's gradient norm up to a few percent; a top-2 choice that flips
# on a near-tie moves one token's output, not the norms.
TOL = {
    "float32": dict(logits=2e-5, loss=1e-5, grad=5e-4),
    "bfloat16": dict(logits=4e-2, loss=5e-3, grad=8e-2),
}


@pytest.fixture(scope="module")
def params():
    return ref.init_params(SIZES, 7)


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def both_sides(request, params):
    """(dtype, program's outputs/loss/grads, reference's) on seeded
    weights: the reference is float32 in both cases."""
    model = lm.MlaMoeLM(lm_config(bfloat16=request.param == "bfloat16"))
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


def test_logits_agree_with_the_reference(both_sides):
    dtype, (out, _, _), (logits_r, _, _, _) = both_sides
    gap = jnp.max(jnp.abs(out["logits"] - logits_r)) / jnp.max(
        jnp.abs(logits_r))
    assert out["logits"].dtype == jnp.float32
    assert float(gap) < TOL[dtype]["logits"]


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
    # the router's bias is a buffer: no gradient on either side
    assert float(flat_p["params/layer_1/moe/router_bias"]) == 0.0
    assert float(flat_r["params/layer_1/moe/router_bias"]) == 0.0
    # the router's weights learn through the combine weights
    assert float(flat_p["params/layer_1/moe/router"]) > 0.0


def test_routing_counts_agree_with_the_reference(both_sides):
    dtype, (out, _, _), (_, hist_r, _, _) = both_sides
    hist_p = np.asarray(out["moe"]["hist"])
    assert hist_p.shape == (1, SIZES["router_width"])
    assert hist_p.sum() == BATCH * SEQ * SIZES["num_experts_per_tok"]
    flips = np.abs(hist_p - np.asarray(hist_r)).sum()
    assert flips <= (0 if dtype == "float32" else 4)
    held = slice(SIZES["first_expert"],
                 SIZES["first_expert"] + SIZES["n_routed_experts"])
    assert int(out["moe"]["assignments_here"][0]) == hist_p[0, held].sum()
    assert int(out["moe"]["dropped"][0]) == 0


# ------------------------------------------------ (b) the sum of the shares ---


@pytest.mark.parametrize("shares", [8, 4, 2, 1])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    """Every share's routed part, with the shared expert counted once, is
    what the uncut reference gives for the whole expert layer: the cut to one
    chip's experts leaves out exactly the other chips' parts."""
    uncut = {**SIZES, "n_routed_experts": 8, "first_expert": 0}
    p = ref.init_params(uncut, 11)["params"]["layer_1"]["moe"]
    x = jax.random.normal(jax.random.key(3), (BATCH, SEQ, 64), jnp.float32)
    whole, hist = ref.expert_layer(p, x, uncut, ref.F32)
    shared = ref.swiglu(p["shared"], x, ref.F32)
    held = 8 // shares
    total = shared
    for s in range(shares):
        cut = lm_config({**uncut, "n_routed_experts": held,
                         "first_expert": s * held})
        mine = {k: (v[s * held:(s + 1) * held] if k.startswith("w_") else v)
                for k, v in p.items()}
        out, counters = lm.ExpertLayer(cut).apply({"params": mine}, x)
        total = total + (out - shared)
        np.testing.assert_array_equal(counters["hist"], hist)  # all route alike
    np.testing.assert_allclose(total, whole, rtol=2e-4, atol=2e-5)


# ------------------------------------------------- (c) dropless under skew ---


@pytest.mark.parametrize("target", [2, 5])
def test_a_router_that_sends_every_token_to_one_held_expert_loses_none(
        target):
    """Experts 2..5 are held. A bias that makes ``target`` every token's
    first choice (and absent expert 7 its second) gives that expert all T
    rows: all are computed, none is dropped, and the result is the dense
    product of every token through that one expert."""
    cfg = lm_config()
    x = jax.random.normal(jax.random.key(5), (BATCH, SEQ, 64), jnp.float32)
    layer = lm.ExpertLayer(cfg)
    p = layer.init(jax.random.key(6), x)["params"]
    p["router_bias"] = jnp.zeros(8).at[target].set(10.0).at[7].set(5.0)
    out, counters = layer.apply({"params": p}, x)
    n = BATCH * SEQ
    local = target - cfg.first_expert
    assert int(counters["assignments_here"]) == n
    assert int(counters["dropped"]) == 0
    assert float(counters["load_max_over_mean"]) == pytest.approx(4.0)
    np.testing.assert_array_equal(
        counters["hist"], np.eye(8, dtype=np.int32)[target] * n
        + np.eye(8, dtype=np.int32)[7] * n)
    flat = x.reshape(n, 64)
    scores = jax.nn.sigmoid(flat @ p["router"])
    weight = 2.448 * scores[:, target] / (scores[:, target] + scores[:, 7])
    dense = (jax.nn.silu(flat @ p["w_gate"][local]) * (flat @ p["w_up"][local])
             ) @ p["w_down"][local]
    shared = lm.SwiGLU(64).apply({"params": p["shared"]}, x)
    np.testing.assert_allclose(
        out, shared + (weight[:, None] * dense).reshape(x.shape),
        rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("top_k,held,first", [(1, 3, 0), (2, 4, 2), (3, 8, 0),
                                             (6, 2, 6)])
def test_dispatch_and_combine_are_a_weighted_sum_over_held_experts(
        top_k, held, first):
    """gather -> per-group work -> combine, against a scatter-add written
    out; gradients too (the backward passes are gathers of their own)."""
    t, d, n = 50, 8, 8
    rng = np.random.default_rng(top_k)
    experts = jnp.asarray(np.stack(
        [rng.permutation(n)[:top_k] for _ in range(t)]), jnp.int32)
    weights = jnp.asarray(rng.random((t, top_k)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    gain = jnp.arange(1.0, held + 1.0)  # expert g multiplies by g + 1

    def program(x, weights):
        plan = moe.dispatch(experts, first, held, n)
        rows = moe.gather_rows(x, plan, top_k)
        group = jnp.searchsorted(
            jnp.cumsum(plan["group_sizes"]), jnp.arange(t * top_k),
            side="right")
        # zero past the groups, as expert_ffn leaves them
        rows = rows * jnp.append(gain, 0.0)[group][:, None]
        return moe.combine(rows, weights, plan, top_k), plan

    def written_out(x, weights):
        local = experts - first
        here = (local >= 0) & (local < held)
        g = jnp.where(here, gain[jnp.clip(local, 0, held - 1)], 0.0)
        return jnp.einsum("tk,td->td", weights * g, x)

    out, plan = program(x, weights)
    np.testing.assert_allclose(out, written_out(x, weights), rtol=1e-5)
    assert int(moe.load_counters(plan)["dropped"]) == 0
    assert int(plan["group_sizes"].sum()) == int(
        ((experts >= first) & (experts < first + held)).sum())
    for arg in (0, 1):
        g_p = jax.grad(lambda *a: jnp.sum(jnp.sin(program(*a)[0])), arg)(
            x, weights)
        g_w = jax.grad(lambda *a: jnp.sum(jnp.sin(written_out(*a))), arg)(
            x, weights)
        np.testing.assert_allclose(g_p, g_w, rtol=1e-4, atol=1e-4)


def test_expert_ffn_hides_what_the_kernel_leaves_past_the_groups(monkeypatch):
    """The grouped product computes the groups' rows only, forward and
    towards the rows; on the chip the rest holds whatever the buffer held. A
    stand-in that leaves NaN there: zeros come out, zeros go back."""
    sizes = jnp.asarray([3, 0, 5], jnp.int32)
    real = moe.grouped_matmul

    @jax.custom_vjp
    def leaky(rows, w, group_sizes):
        past = (jnp.arange(rows.shape[0]) >= group_sizes.sum())[:, None]
        return jnp.where(past, jnp.nan, real(rows, w, group_sizes))

    def fwd(rows, w, group_sizes):
        return leaky(rows, w, group_sizes), (rows, w, group_sizes)

    def bwd(res, g):
        rows, w, group_sizes = res
        _, pull = jax.vjp(lambda r, m: real(r, m, group_sizes), rows, w)
        past = (jnp.arange(rows.shape[0]) >= group_sizes.sum())[:, None]
        d_rows, d_w = pull(jnp.where(past, 0, g))
        return jnp.where(past, jnp.nan, d_rows), d_w, None

    leaky.defvjp(fwd, bwd)
    keys = jax.random.split(jax.random.key(0), 4)
    rows = jax.random.normal(keys[0], (12, 8))
    w_gate, w_up = (jax.random.normal(k, (3, 8, 4)) for k in keys[1:3])
    w_down = jax.random.normal(keys[3], (3, 4, 8))

    def run(rows, w_gate, w_up, w_down):
        return moe.expert_ffn(rows, sizes, w_gate, w_up, w_down, jnp.float32)

    want = run(rows, w_gate, w_up, w_down)
    want_grads = jax.grad(lambda *a: jnp.sum(jnp.sin(run(*a))), (0, 1, 2, 3))(
        rows, w_gate, w_up, w_down)
    monkeypatch.setattr(moe, "grouped_matmul", leaky)
    out = run(rows, w_gate, w_up, w_down)
    grads = jax.grad(lambda *a: jnp.sum(jnp.sin(run(*a))), (0, 1, 2, 3))(
        rows, w_gate, w_up, w_down)
    assert bool(jnp.all(out[8:] == 0)) and bool(jnp.all(grads[0][8:] == 0))
    np.testing.assert_allclose(out, want, rtol=1e-6)
    for g, w in zip(grads, want_grads):
        assert bool(jnp.all(jnp.isfinite(g)))
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(grads[1][1]).max()) == 0.0  # the empty group


# ----------------------------------------------------- (d) the MLA core ---


def _dense_causal(q, k, v, scale):
    """Query by query, in float64 numpy."""
    q, k, v = (np.asarray(t, np.float64) for t in (q, k, v))
    out = np.zeros(q.shape[:-1] + (v.shape[-1],))
    for i in range(q.shape[2]):
        logits = np.einsum("bhd,bhjd->bhj", q[:, :, i], k[:, :, :i + 1]) * scale
        p = np.exp(logits - logits.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[:, :, i] = np.einsum("bhj,bhjd->bhd", p, v[:, :, :i + 1])
    return out


def _rotated_qkv(length, b=2, h=3, nope=16, rope=8, dv=16):
    """q, k, v (B, H, S, .) as the model hands them to the core: rotary on
    the last ``rope`` of q/k's ``nope + rope``, one rotary key head shared,
    the softmax scale on q."""
    keys = jax.random.split(jax.random.key(length), 4)
    q = jax.random.normal(keys[0], (b, length, h, nope + rope))
    k_nope = jax.random.normal(keys[1], (b, length, h, nope))
    k_rope = jax.random.normal(keys[2], (b, length, 1, rope))
    v = jax.random.normal(keys[3], (b, length, h, dv))
    pos = jnp.arange(length)
    q = jnp.concatenate(
        [q[..., :nope], mla.rotary_interleaved(q[..., nope:], pos, 1e4)], -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(
            mla.rotary_interleaved(k_rope, pos, 1e4), (b, length, h, rope))],
        -1)
    q = q * (nope + rope) ** -0.5
    return tuple(t.transpose(0, 2, 1, 3) for t in (q, k, v))


@pytest.mark.parametrize("length", [40, 128, 200, 384])
def test_causal_core_with_wider_query_key_heads(length):
    """q/k heads of 24 (16 + 8 rotary) against v heads of 16, causal,
    rotary on interleaved pairs, against attention written out query by
    query: the dense path, which serves every length off the TPU."""
    q, k, v = _rotated_qkv(length)
    out = mla.causal_core(q, k, v)
    assert out.shape == v.shape
    np.testing.assert_allclose(
        out, _dense_causal(q, k, v, 1.0), rtol=2e-4, atol=2e-5)


def _dense_causal_jnp(q, k, v):
    """The same in jnp, for gradients (float32)."""
    s = q.shape[2]
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k)
    p = jax.nn.softmax(
        jnp.where(jnp.tril(jnp.ones((s, s), bool)), logits, -jnp.inf), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


@pytest.mark.parametrize("backward", ["fused", "two_kernels"])
@pytest.mark.parametrize("length", [128, 256, 384])
@pytest.mark.parametrize("heads", ["24_16", "192_128"])
def test_splash_path_agrees_with_dense_attention(
        monkeypatch, kernel_path_on_the_cpu, heads, length, backward):
    """The kernel ``causal_core`` takes on a TPU, interpreted here: q/k heads
    wider than v heads at a small size and at the published 192/128, one to
    three blocks of 128 (384: blocks on, under and over the diagonal), the
    output and all three gradients against dense attention, with the fused
    backward and with the two-kernel one (which the block rule takes where
    the fused one's partial dq would pass its byte limit)."""
    d_qk, d_v = map(int, heads.split("_"))
    if backward == "two_kernels":
        monkeypatch.setattr(mla, "PARTIAL_DQ_BYTES", 0)
    blocks = mla.splash_block_sizes(2, length, d_qk, d_v, jnp.float32)
    assert blocks.use_fused_bwd_kernel == (backward == "fused")
    keys = jax.random.split(jax.random.key(length + d_qk), 4)
    q = jax.random.normal(keys[0], (1, 2, length, d_qk)) * d_qk ** -0.5
    k = jax.random.normal(keys[1], (1, 2, length, d_qk))
    v = jax.random.normal(keys[2], (1, 2, length, d_v))
    weight = jax.random.normal(keys[3], (1, 2, length, d_v))

    def both(core):
        return jax.value_and_grad(
            lambda q, k, v: (core(q, k, v) * weight).sum(), argnums=(0, 1, 2))

    (loss, grads), (want_loss, want) = (
        both(c)(q, k, v) for c in (mla.causal_core, _dense_causal_jnp))
    np.testing.assert_allclose(
        mla.causal_core(q, k, v), _dense_causal(q, k, v, 1.0),
        rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-4, atol=1e-4)
    for name, got, ref_grad in zip("qkv", grads, want):
        np.testing.assert_allclose(
            got, ref_grad, rtol=2e-3, atol=2e-4, err_msg=f"d{name}")


@pytest.mark.parametrize("length", [130, 200])
def test_splash_path_pads_a_length_off_the_128_grid(
        monkeypatch, kernel_path_on_the_cpu, length):
    """No segment ids: under the causal mask the zero rows appended to q, k
    and v reach no kept row, forward or backward."""
    q, k, v = _rotated_qkv(length)
    asked = []
    real = mla._causal_kernel
    monkeypatch.setattr(
        mla, "_causal_kernel",
        lambda *key, **kw: asked.append(key) or real(*key, **kw))

    def grads(core):
        return jax.grad(lambda q, k, v: (core(q, k, v) ** 2).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    out = mla.causal_core(q, k, v)
    assert out.shape == v.shape and asked[0][:2] == (3, 256)  # heads, padded
    np.testing.assert_allclose(
        out, _dense_causal(q, k, v, 1.0), rtol=2e-4, atol=2e-5)
    for got, want in zip(grads(mla.causal_core), grads(_dense_causal_jnp)):
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-4)


def test_the_kernel_is_built_once_for_a_models_layers(kernel_path_on_the_cpu):
    """Five layers, and their recomputation under ``nn.remat``, ask for one
    (heads, length, head sizes, dtype): the causal mask's block schedule is
    made once and found in the cache after that, across traces too."""
    model = lm.MlaMoeLM(lm_config(num_layers=5))
    tokens = jnp.zeros((1, 128), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.key(0), tokens)

    def loss(p):
        return model.apply(p, tokens)["logits"].sum()

    jax.jit(jax.grad(loss)).lower(params)
    info = mla._causal_kernel.cache_info()
    # asked for by init's trace, the forward's and the recomputation's
    assert info.misses == 1 and info.hits >= 9


def _logits_sum_grad(model, toks):
    return jax.grad(lambda p: model.apply(p, toks)["logits"].sum())


@pytest.mark.parametrize("remat, forwards", [("kept", 2), ("bare", 4)])
def test_the_forward_kernel_runs_once_a_layer(
        monkeypatch, kernel_path_on_the_cpu, named_eqns, remat, forwards):
    """Two layers: the gradient calls the forward kernel twice and the
    backward kernel twice, because each layer's remat keeps what the core
    names and its recomputation's forward call is dead code. Under a bare
    ``nn.remat`` the recomputation calls it again: were that count 2 as
    well, the name or the policy would have gone inert."""
    if remat == "bare":
        monkeypatch.setattr(lm, "remat_layer", nn.remat)
    model = lm.MlaMoeLM(lm_config())
    toks = tokens(batch=1, seq=128)
    shapes = jax.eval_shape(model.init, jax.random.key(0), toks)
    assert named_eqns(
            "pallas_call", _logits_sum_grad(model, toks), shapes) == {
        "splash_mha_fwd_residuals": forwards,
        "splash_mha_dkv_no_residuals": 2}


@pytest.mark.parametrize("remat", ["kept", "bare"])
def test_a_layer_keeps_the_cores_two_results_and_nothing_else(
        kernel_path_on_the_cpu, kept_across_remat, remat):
    """Across a layer's recomputation: the core's output (B, H, S, Dv) and
    log-sum-exp (B, H, S) float32, no projection, norm or expert
    intermediate; a bare ``nn.remat`` keeps nothing."""
    wrap = lm.remat_layer if remat == "kept" else nn.remat
    layer = wrap(lm.Block)(lm_config(), False)
    kept = kept_across_remat(layer, jnp.ones((1, 128, 64)))
    assert kept == (["f32[1,4,128,16]", "f32[1,4,128]"]
                    if remat == "kept" else [])


def test_keeping_the_cores_results_leaves_the_gradients_as_they_were(
        monkeypatch, kernel_path_on_the_cpu, params):
    """The same kernels on the same operands, one call fewer: every leaf's
    gradient is bitwise the bare ``nn.remat``'s (no tolerance)."""
    toks = tokens(batch=1, seq=128)

    def grads():
        return jax.jit(_logits_sum_grad(lm.MlaMoeLM(lm_config()), toks))(
            params)

    kept = grads()
    monkeypatch.setattr(lm, "remat_layer", nn.remat)
    jax.tree.map(np.testing.assert_array_equal, kept, grads())


@pytest.mark.parametrize("path, named", [("kernel", 2), ("dense", 0)])
def test_only_the_kernel_path_names_its_results(
        kernel_path_on_the_cpu, named_eqns, path, named):
    """Under a gradient the kernel's forward names its output and
    log-sum-exp ``mla.CORE_RESIDUALS``; the dense path (here: under 128
    positions) has no log-sum-exp to keep and names nothing."""
    grad = jax.grad(lambda q, k, v: mla.causal_core(q, k, v).sum())
    names = named_eqns(
        "name", grad, *_rotated_qkv(128 if path == "kernel" else 40))
    assert names == ({mla.CORE_RESIDUALS: named} if named else {})


# (heads, length, q/k head, v head, dtype): causal calls
CAUSAL_SHAPES = {
    "lm_cell": (32, 8192, 192, 128, "bfloat16"),
    "swa_cell": (28, 16384, 128, 128, "bfloat16"),  # 28 query heads over 4
    "equal_heads_256": (32, 8192, 256, 256, "bfloat16"),
    "odd_multiple": (4, 11 * 128, 128, 128, "bfloat16"),
    "one_block": (2, 128, 64, 64, "float32"),
    "long_float32": (8, 32768, 256, 256, "float32"),
    "many_heads": (64, 8192, 128, 128, "bfloat16"),
    "many_heads_float32": (64, 8192, 128, 128, "float32"),
}


@pytest.mark.parametrize("name", sorted(CAUSAL_SHAPES))
def test_splash_block_rule_gives_blocks_the_kernel_accepts(name):
    """What the kernels' own checks ask (multiples of 128 dividing the
    length, the compute block dividing the key block), square grid steps of
    at most 1,024 (past it nothing compiles for a v5e), a tile of a block's
    rows within 512 KiB, and the fused backward exactly where its partial dq
    stays within ``PARTIAL_DQ_BYTES`` a sequence."""
    heads, n, d_qk, d_v, dtype = CAUSAL_SHAPES[name]
    bs = mla.splash_block_sizes(heads, n, d_qk, d_v, dtype)
    assert bs.has_backward_blocks
    itemsize = jnp.dtype(dtype).itemsize
    sides = {bs.block_q, bs.block_kv, bs.block_q_dkv, bs.block_kv_dkv}
    assert len(sides) == 1
    side = sides.pop()
    assert side % 128 == 0 and 128 <= side <= 1024 and n % side == 0
    row = max(d_qk, d_v) * itemsize
    assert side * row <= max(2**19, 128 * row)
    for compute in (bs.block_kv_compute, bs.block_kv_dkv_compute):
        assert compute % 128 == 0 and side % compute == 0
    partial_dq = n // side * heads * n * d_qk * itemsize
    assert bs.use_fused_bwd_kernel == (partial_dq <= mla.PARTIAL_DQ_BYTES)
    if bs.use_fused_bwd_kernel:
        assert bs.block_q_dq is None and bs.block_kv_dq is None
    else:
        assert bs.block_q_dq == bs.block_kv_dq == side
    if name == "lm_cell":  # what the on-chip sweep chose (PERF.md, PR 31)
        assert (side, bs.block_kv_compute, bs.block_kv_dkv_compute,
                bs.use_fused_bwd_kernel) == (1024, 256, 512, True)
        assert partial_dq == 805_306_368
    if name == "swa_cell":  # 1.88 GB of partial dq: the two-kernel backward
        assert (side, bs.use_fused_bwd_kernel, bs.block_q_dq) == (
            1024, False, 1024)
        assert partial_dq == 1_879_048_192


def test_rotary_turns_interleaved_pairs_by_position():
    x = jax.random.normal(jax.random.key(0), (2, 9, 3, 8))
    out = np.asarray(mla.rotary_interleaved(x, jnp.arange(9), 1e6))
    pairs = np.asarray(x, np.float64).reshape(2, 9, 3, 4, 2)
    z = pairs[..., 0] + 1j * pairs[..., 1]
    angle = np.arange(9)[:, None, None] * 1e6 ** (-np.arange(0, 8, 2) / 8)
    turned = z * np.exp(1j * angle)
    want = np.stack([turned.real, turned.imag], -1).reshape(2, 9, 3, 8)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)
    # position 0 is left as it is; the reference turns the same way
    np.testing.assert_array_equal(out[:, 0], np.asarray(x)[:, 0])
    np.testing.assert_allclose(ref.rotary(x, 1e6), out, rtol=1e-6, atol=1e-6)


# --------------------------------------------- (e) through train(), 3 steps ---


def train_config(steps=3, **lm_kw) -> Config:
    return Config(
        model=ModelConfig(arch="mla_moe_lm"), lm=lm_config(**lm_kw),
        data=DataConfig(source="tokens", batch_size=BATCH, seq_len=SEQ),
        train=TrainConfig(num_steps=steps, log_every=1, warmup_steps=1,
                          gradient_accumulate_every=1, learning_rate=3e-3))


def test_train_runs_the_language_model_and_the_loss_falls_on_one_batch():
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
        assert m["moe/hist"].shape == (1, 8)
        assert int(m["moe/dropped"].sum()) == 0
        assert m["moe/load_max_over_mean"].shape == (1,)
        assert "distogram_entropy" not in m


def test_train_takes_initial_parameters():
    from alphafold2_tpu.train.loop import train

    start = ref.init_params(SIZES, 3)
    kept = jax.tree.map(np.asarray, start)
    seen = []
    cfg = train_config(steps=1)
    train(cfg, dataset=itertools.repeat({"tokens": np.asarray(tokens(2))}),
          callbacks=[lambda i, s, m: seen.append((s, m))],
          init_params=start)
    state, metrics = seen[0]
    # warm-up: step 0 runs at rate 0, so the parameters are the ones given
    for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(kept)):
        np.testing.assert_array_equal(a, b)
    want, _ = ref.loss_fn(jax.tree.map(jnp.asarray, kept), tokens(2), SIZES)
    assert float(metrics["loss"]) == pytest.approx(float(want), rel=1e-5)


def test_train_pre_entry_trains_the_language_model(capsys):
    import train_pre

    small = lm_config()
    fields = {f.name: getattr(small, f.name)
              for f in dataclasses.fields(small)}
    train_pre.main(
        ["model.arch=mla_moe_lm", "data.source=tokens", "data.batch_size=2",
         f"data.seq_len={SEQ}",
         "train.num_steps=3", "train.log_every=1", "train.warmup_steps=1",
         "train.gradient_accumulate_every=1"]
        + [f"lm.{k}={v}" for k, v in fields.items()])
    out = capsys.readouterr().out
    assert "[step 2]" in out and "moe/assignments_here" in out
    assert '"arch": "mla_moe_lm"' in out


def test_an_unknown_architecture_is_refused():
    from alphafold2_tpu.train.loop import build_task

    with pytest.raises(ValueError, match="model.arch"):
        build_task(Config(model=ModelConfig(arch="nope")))


def test_the_trunk_does_not_import_the_language_model():
    """``train.imports`` must not grow for the flagship: the new model's
    modules come in only when the configuration asks for them."""
    import subprocess

    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from alphafold2_tpu.config import Config\n"
        "from alphafold2_tpu.train import loop\n"
        "loop.build_task(Config())\n"
        "bad = [m for m in sys.modules if m.endswith(('mla_moe_lm', "
        "'ops.moe', 'ops.mla', 'data.tokens'))]\n"
        "assert not bad, bad\n" % ROOT)
    subprocess.run([sys.executable, "-c", code], check=True,
                   env={**os.environ, "JAX_PLATFORMS": "cpu"})


def test_token_stream_is_zipf_over_the_held_vocabulary():
    from alphafold2_tpu.data.pipeline import make_dataset

    cfg = DataConfig(source="tokens", batch_size=4, seq_len=4096)
    with pytest.raises(ValueError, match="vocabulary"):
        make_dataset(cfg, seed=1)
    a = next(iter(make_dataset(cfg, seed=1, vocab_size=1000)))["tokens"]
    b = next(iter(make_dataset(cfg, seed=1, vocab_size=1000)))["tokens"]
    c = next(iter(make_dataset(cfg, seed=2, vocab_size=1000)))["tokens"]
    assert a.shape == (4, 4096) and a.dtype == np.int32
    assert a.min() >= 0 and a.max() < 1000
    np.testing.assert_array_equal(a, b)
    assert (a != c).any()
    counts = np.sort(np.bincount(a.ravel(), minlength=1000))[::-1]
    # Zipf(1.0) over 1,000 ids: the hottest id takes 1 / H(1000) = 13.4%
    assert 0.11 < counts[0] / a.size < 0.16
    assert counts[0] > 1.6 * counts[1] > 1.6 * counts[3]
