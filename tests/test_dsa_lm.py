"""The fifth language model (every layer's queries read the keys a learned
indexer picks, the indexer's own loss, softmax-routed SwiGLU experts as one
chip's share) against the benchmark's plain reference, tiny on the CPU; and
the sparse attention's pieces (``ops/dsa.py``, the Pallas core of
``ops/pallas/dsa_core.py`` interpreted) against plain forms.

The reference (``benchmark/reference/dsa_lm_model.py``) imports nothing of
the program; weights are the reference's seeded ones, which the program's
parameter tree takes as they are.
"""

import dataclasses
import itertools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from alphafold2_tpu.config import (  # noqa: E402
    Config, DataConfig, DsaLMConfig, ModelConfig, TrainConfig,
)
from alphafold2_tpu.models import dsa_moe_lm as dsa_lm  # noqa: E402
from alphafold2_tpu.ops import dsa, mla  # noqa: E402
from alphafold2_tpu.ops.pallas import dsa_core, dsa_select  # noqa: E402
from benchmark.reference import dsa_lm_model as ref  # noqa: E402

# hidden 64, 4 query heads over 2 key/value heads of 32, an indexer of 4
# heads of 16 picking 32 keys a query, 2 layers, 8 experts top-2, 4 held
SIZES = dict(
    vocab_size=96, hidden_size=64, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, head_dim=32,
    moe_intermediate_size=32, num_experts=4, router_width=8, first_expert=2,
    num_experts_per_tok=2, rope_theta=1e4, rms_norm_eps=1e-6,
    index_num_heads=4, index_head_dim=16, index_topk=32,
    index_loss_weight=1.0,
)
SEQ = 256


def dsa_config(sizes=SIZES, **kw) -> DsaLMConfig:
    return DsaLMConfig(**{**dict(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        num_layers=sizes["num_hidden_layers"],
        num_heads=sizes["num_attention_heads"],
        num_kv_heads=sizes["num_key_value_heads"],
        head_dim=sizes["head_dim"],
        index_num_heads=sizes["index_num_heads"],
        index_head_dim=sizes["index_head_dim"],
        index_topk=sizes["index_topk"], index_chunk=256,
        index_loss_weight=sizes["index_loss_weight"],
        moe_intermediate_size=sizes["moe_intermediate_size"],
        n_routed_experts=sizes["router_width"],
        num_experts_per_tok=sizes["num_experts_per_tok"],
        rope_theta=sizes["rope_theta"], rms_norm_eps=sizes["rms_norm_eps"],
        experts_held=sizes["num_experts"],
        first_expert=sizes["first_expert"], bfloat16=False), **kw})


def tokens(seed=1, length=SEQ):
    return jax.random.randint(jax.random.key(seed), (1, length), 0,
                              SIZES["vocab_size"])


@pytest.fixture(scope="module")
def params():
    return ref.init_params(SIZES, 3)


def program_loss(params, toks, cfg):
    out = dsa_lm.DsaMoeLM(cfg).apply(params, toks)
    return dsa_lm.loss(out, {"tokens": toks}, cfg.index_loss_weight), out


@pytest.fixture(scope="module")
def both_sides(params):
    """The program in float32 and the reference at "highest": loss, every
    gradient, the outputs (the sample of each layer's packed selection
    among the counters), and the reference's aux (routing counts, indexer
    losses, packed selections)."""
    with jax.default_matmul_precision("highest"):
        toks = tokens()
        cfg = dsa_config()
        (loss, out), grads = jax.value_and_grad(
            program_loss, has_aux=True)(params, toks, cfg)
        (ref_loss, aux), ref_grads = jax.value_and_grad(
            ref.loss_fn, has_aux=True)(params, toks, SIZES)
        ref_logits = ref.forward(params, toks, SIZES)
    return dict(loss=loss, out=out, grads=grads, ref_loss=ref_loss, ref_aux=aux, ref_grads=ref_grads,
                ref_logits=ref_logits)


# ------------------------------------------------- (a) the reference ---


def test_the_programs_parameter_tree_is_the_references(params):
    shapes = jax.eval_shape(lambda: dsa_lm.DsaMoeLM(dsa_config()).init(
        jax.random.key(0), tokens()))
    assert jax.tree.structure(shapes) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(shapes), jax.tree.leaves(params)):
        assert a.shape == b.shape


def test_logits_agree_with_the_reference(both_sides):
    np.testing.assert_allclose(both_sides["out"]["logits"],
                               both_sides["ref_logits"], rtol=1e-4,
                               atol=1e-4)


def test_loss_and_indexer_losses_agree_with_the_reference(both_sides):
    assert float(both_sides["loss"]) == pytest.approx(
        float(both_sides["ref_loss"]), rel=1e-5)
    np.testing.assert_allclose(both_sides["out"]["dsa"]["indexer_kl"],
                               both_sides["ref_aux"][1], rtol=1e-4)
    assert float(both_sides["ref_aux"][1].min()) > 0.0


def test_every_leafs_gradient_agrees_with_the_reference(both_sides):
    prog = jax.tree.flatten_with_path(both_sides["grads"])[0]
    want = jax.tree.leaves(both_sides["ref_grads"])
    for (path, got), exp in zip(prog, want, strict=True):
        scale = float(jnp.abs(exp).max())
        assert scale > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(got, exp, rtol=0, atol=2e-4 * scale,
                                   err_msg=jax.tree_util.keystr(path))


def test_the_selection_is_the_references(both_sides):
    """Bit for bit, both packed 32 queries a word: the program's threshold
    and tie rule pick the very keys the reference's ``lax.top_k`` indices
    name. At 256 positions the sample the step carries out is the whole
    selection (8 word rows by 256 keys)."""
    sample = both_sides["out"]["dsa"]["selection_sample"]
    assert sample.shape == (2, 1, SEQ // 32, SEQ)
    np.testing.assert_array_equal(sample, both_sides["ref_aux"][2])


def test_the_sample_spans_the_selection():
    """At the cell's 16,384 positions: 16 word rows, one in each 32 (queries
    of blocks 3, 7, ..., 63 of 256), by every 64th key, the last of each
    ending the sample; the words taken are the packed array's own."""
    rows, keys = dsa.sample_at(512, 16384)
    np.testing.assert_array_equal(rows, np.arange(31, 512, 32))
    np.testing.assert_array_equal(keys, np.arange(63, 16384, 64))
    packed = jax.random.randint(jax.random.key(2), (1, 16, 1024), -2**31,
                                2**31 - 1, dtype=jnp.int32)
    rows, keys = dsa.sample_at(16, 1024)
    np.testing.assert_array_equal(rows, np.arange(16))
    np.testing.assert_array_equal(keys, np.arange(3, 1024, 4))
    np.testing.assert_array_equal(dsa.selection_sample(packed),
                                  packed[:, :, 3::4])


def test_routing_counts_and_counters(both_sides):
    dsa_counters = both_sides["out"]["dsa"]
    np.testing.assert_array_equal(both_sides["out"]["moe"]["hist"],
                                  both_sides["ref_aux"][0])
    topk = SIZES["index_topk"]
    want = (topk * (topk + 1) / 2 + (SEQ - topk) * topk) / SEQ
    np.testing.assert_allclose(dsa_counters["keys_selected_mean"], want)
    np.testing.assert_array_equal(dsa_counters["core_in_kernel"], 0.0)
    np.testing.assert_array_equal(dsa_counters["select_in_kernel"], 0.0)
    assert dsa_counters["tiles_visited_pct"].shape == (2,)


# ------------------------------- (b) two disjoint paths of the gradient ---


def test_the_loss_weight_scales_the_indexers_gradient_alone(params):
    """The indexer reads a stopped stream and the attention's probabilities
    are stopped in its loss: at weight 0 the main model's gradients are the
    weight-1 ones and the indexer's are zero; at weight 2 the indexer's are
    doubled."""
    toks = tokens(4)
    grads = {w: jax.grad(lambda p, w=w: program_loss(
        p, toks, dsa_config(index_loss_weight=w))[0])(params)
        for w in (0.0, 1.0, 2.0)}
    flat = {w: jax.tree.flatten_with_path(g)[0] for w, g in grads.items()}
    indexer = 0
    for (path, g0), (_, g1), (_, g2) in zip(flat[0.0], flat[1.0], flat[2.0]):
        name = jax.tree_util.keystr(path)
        if "'indexer'" in name:
            indexer += 1
            np.testing.assert_array_equal(g0, 0.0)
            assert float(jnp.abs(g1).max()) > 0, name
            np.testing.assert_allclose(g2, 2 * g1, rtol=1e-5, atol=1e-9)
        else:
            np.testing.assert_allclose(g0, g1, rtol=1e-5, atol=1e-9)
    assert indexer == 2 * 5  # q_proj, k_proj, k_norm's two, weights_proj


# -------------------------------------------------- (c) the selection ---


def _select_scores(case):
    """(2, 32, 256) scores of 32 queries at positions 0, 8, .., 248 against
    256 keys (``-inf`` past each), and the ``topk`` to pick: random normal;
    rounded to halves (ties at the boundary); drawn from {1, +0.0, -0.0,
    -1} (the signed zeros straddle the boundary); every score negative;
    ``topk`` over most rows' causal keys (the k-th is ``-inf``); ``topk``
    1; ``topk`` one short of the keys."""
    rng = np.random.default_rng(7)
    scores = rng.standard_normal((2, 32, 256)).astype(np.float32)
    topk = {"short_rows": 200, "topk_1": 1, "topk_s_minus_1": 255}.get(case, 16)
    if case == "ties":
        scores = np.round(scores * 2) / 2
    elif case == "signed_zeros":
        scores = rng.choice(np.array([1.0, 0.0, -0.0, -1.0], np.float32),
                            p=[0.03, 0.05, 0.05, 0.87], size=scores.shape)
    elif case == "all_negative":
        scores = -np.abs(scores) - 1.0
    t = 8 * np.arange(32)
    causal = t[:, None] >= np.arange(256)[None, :]
    return jnp.asarray(np.where(causal, scores, -np.inf)), topk, t


@pytest.fixture(params=["kernel", "top_k"])
def form(request, monkeypatch):
    """``select`` with its threshold from the Pallas kernel (interpreted
    here) or from ``lax.top_k``."""
    monkeypatch.setattr(dsa, "select_kernel_takes",
                        lambda shape: request.param == "kernel")
    return request.param


@pytest.mark.parametrize("case", ["distinct", "ties", "signed_zeros",
                                  "all_negative", "short_rows", "topk_1",
                                  "topk_s_minus_1"])
def test_select_keeps_the_keys_top_k_names(form, case):
    """The threshold and the lower-key rule pick exactly ``lax.top_k``'s
    indices, causal keys only, every causal key of a short row; with ties
    at the boundary, -0.0 and +0.0 on either side of it, negative rows,
    ``topk`` 1 and one short of the keys; the threshold from the kernel or
    from ``lax.top_k`` alike."""
    scores, topk, t = _select_scores(case)
    keep = jax.jit(lambda s: dsa.select(s, topk))(scores)  # traced anew
    _, idx = jax.lax.top_k(scores, topk)
    causal = scores > -jnp.inf
    want = jnp.zeros(scores.shape, bool).at[
        jnp.arange(2)[:, None, None], jnp.arange(32)[None, :, None],
        idx].set(True) & causal
    np.testing.assert_array_equal(keep, want)
    np.testing.assert_array_equal(keep.sum(-1), jnp.broadcast_to(
        jnp.minimum(t + 1, topk), (2, 32)))


def test_kth_key_is_the_key_top_k_ends_at():
    """The kernel alone, interpreted: on rows of random 32-bit patterns
    read as float32 (NaNs made 1.0), so that every bit of the keys is
    decided, the key of ``lax.top_k``'s last value, for ``topk`` low, in
    the middle and high."""
    rng = np.random.default_rng(3)
    bits = rng.integers(-2 ** 31, 2 ** 31, (1, 32, 512)).astype(np.int32)
    x = bits.view(np.float32)
    x = jnp.asarray(np.where(np.isnan(x), np.float32(1.0), x))
    for topk in (3, 256, 509):
        got = jax.jit(dsa_select.kth_key, static_argnums=(1, 2))(
            x, topk, True)
        np.testing.assert_array_equal(got, dsa_select.order_key(
            jax.lax.top_k(x, topk)[0][..., -1]))


def test_selection_says_where_its_threshold_came_from(monkeypatch):
    """``selection``'s flag reads the rule, and the packed selection is the
    same bits through the kernel as through ``lax.top_k``."""
    keys = jax.random.split(jax.random.key(4), 3)
    args = (jax.random.normal(keys[0], (1, 256, 2, 8)),
            jax.random.normal(keys[1], (1, 256, 8)),
            jax.random.normal(keys[2], (1, 256, 2)))
    made = {}
    for takes in (False, True):
        monkeypatch.setattr(dsa, "select_kernel_takes", lambda shape: takes)
        made[takes] = dsa.selection(*args, 24, 256)
        assert made[takes][2] is takes
    np.testing.assert_array_equal(made[True][0], made[False][0])
    assert int(made[True][1]) == int(made[False][1])


def test_pack_lays_queries_out_as_the_kernels_read_them():
    keep = jax.random.bernoulli(jax.random.key(2), 0.3, (2, 512, 64))
    packed = dsa.pack(keep)
    assert packed.shape == (2, 16, 64) and packed.dtype == jnp.int32
    np.testing.assert_array_equal(dsa.unpack(packed), keep)
    words = np.asarray(packed).view(np.uint32)
    b, i, j, r, s = 1, 1, 31, 5, 9  # query 256 i + 8 j + r, bit j
    assert ((words[b, 8 * i + r, s] >> j) & 1) == bool(
        keep[b, 256 * i + 8 * j + r, s])
    np.testing.assert_array_equal(ref.pack(keep), packed)


def test_the_selection_is_made_once_a_step():
    """The layer's recomputation keeps the packed selection (its name is
    among ``remat_layer``'s): the compiled gradient sorts the scores over
    the keys once a layer, in the forward pass, and never in the backward
    pass's recomputation."""
    cfg = dsa_config()
    toks = tokens()
    params_ = ref.init_params(SIZES, 3)
    text = jax.jit(jax.grad(lambda p: program_loss(p, toks, cfg)[0])).lower(
        params_).compile().as_text()
    over_keys = [line for line in text.split("\n")
                 if " sort(" in line and f"[1,{SEQ},{SEQ}]" in line]
    assert len(over_keys) == SIZES["num_hidden_layers"], over_keys
    for line in over_keys:
        assert "/attn/indexer/" in line and "transpose(" not in line


# ------------------------------------------------------- (d) the core ---


def _qkv(length, heads=8, groups=2, width=128, seed=0):
    keys = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(keys[0], (1, heads, length, width)) * width ** -0.5
    k = jax.random.normal(keys[1], (1, groups, length, width))
    v = jax.random.normal(keys[2], (1, groups, length, width))
    do = jax.random.normal(keys[3], (1, heads, length, width))
    return q, k, v, do


def _selection(case, length):
    causal = jnp.arange(length)[:, None] >= jnp.arange(length)[None, :]
    if case == "top_k":
        scores = jax.random.normal(jax.random.key(9), (1, length, length))
        return dsa.select(jnp.where(causal, scores, -jnp.inf), 48)
    if case == "one_key_a_tile":
        # every query reads itself and key 0: the tile (block 1, key block
        # 0) holds one selected key a row, and key block 0's keys but one
        # are read by nobody past the first block
        keep = jnp.eye(length, dtype=bool) | (jnp.arange(length) == 0)
        return keep[None]
    # "behind_the_diagonal": the second block of queries reads the first
    # block's keys only, so its diagonal tile is not visited and the second
    # key block is read by the first block's diagonal alone
    first = jnp.arange(length) < 256
    keep = jnp.where(first[:, None], causal, first[None, :])
    return keep[None]


@pytest.mark.parametrize("case", ["top_k", "one_key_a_tile",
                                  "behind_the_diagonal"])
def test_pallas_core_matches_the_dense_form(case):
    """The three kernels, interpreted, against ``sparse_core_xla`` in
    float32: output, log-sum-exp and the three gradients, 8 query heads
    over 2 key/value heads, 512 positions in 2 x 2 tiles."""
    length = 512
    q, k, v, do = _qkv(length)
    keep = _selection(case, length)
    packed = dsa.pack(keep)
    (got, lse), pull = jax.vjp(
        lambda q, k, v: dsa_core.sparse_core(q, k, v, packed, 256, True),
        q, k, v)
    (want, want_lse), pull_want = jax.vjp(
        lambda q, k, v: dsa.sparse_core_xla(q, k, v, keep), q, k, v)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(lse, want_lse, rtol=1e-5, atol=1e-5)
    zero = jnp.zeros_like(lse)
    for a, b, name in zip(pull((do, zero)), pull_want((do, zero)),
                          ("dq", "dk", "dv")):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4, err_msg=name)
    tiles = dsa_core.tile_visits(packed, 256)
    if case == "behind_the_diagonal":
        np.testing.assert_array_equal(tiles[0], [[True, False],
                                                 [True, False]])


def test_visit_lists_repeat_the_last_block_past_the_count():
    tiles = jnp.array([[[True, False, True, False],
                        [False, False, False, False],
                        [False, True, True, True]]])
    lists, counts = dsa_core.visit_lists(tiles)
    np.testing.assert_array_equal(counts, [[2, 0, 3]])
    np.testing.assert_array_equal(lists, [[[0, 2, 2, 2], [0, 0, 0, 0],
                                           [1, 2, 3, 3]]])


def test_with_top_k_over_the_length_the_core_is_the_causal_core():
    """A selection of every earlier key is the causal mask: the sparse
    core, dense form and kernels both, equals ``mla.causal_core``."""
    length = 256
    q, k, v, _ = _qkv(length, heads=4, width=64, seed=3)
    scores = jax.random.normal(jax.random.key(5), (1, length, length))
    causal = jnp.arange(length)[:, None] >= jnp.arange(length)[None, :]
    keep = dsa.select(jnp.where(causal, scores, -jnp.inf), length)
    np.testing.assert_array_equal(keep[0], causal)
    want = mla.causal_core(q, k, v)
    np.testing.assert_allclose(dsa.sparse_core_xla(q, k, v, keep)[0], want,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        dsa_core.sparse_core(q, k, v, dsa.pack(keep), 256, True)[0], want,
        rtol=1e-5, atol=1e-5)


def test_the_indexer_loss_is_the_kl_written_out():
    """``indexer_loss`` against the divergence written with numpy: the
    heads' probabilities over each query's selected keys, summed and
    renormalised, against the softmax of the scores over the same keys."""
    length, heads, groups, width = 256, 4, 2, 16
    keys = jax.random.split(jax.random.key(8), 6)
    q = jax.random.normal(keys[0], (1, heads, length, width)) * 0.5
    k = jax.random.normal(keys[1], (1, groups, length, width))
    q_i = jax.random.normal(keys[2], (1, length, 3, 8))
    k_i = jax.random.normal(keys[3], (1, length, 8))
    w = jax.random.normal(keys[4], (1, length, 3))
    scores = dsa.index_scores(q_i, k_i, w, 0)
    keep = dsa.select(scores, 24)
    _, lse = dsa.sparse_core_xla(q, k, jnp.zeros_like(k), keep)
    got = dsa.indexer_loss(q_i, k_i, w, q, k, lse, dsa.pack(keep))
    s = np.asarray(scores[0], np.float64)
    m = np.asarray(keep[0])
    logits = np.einsum("hqd,hkd->hqk", np.asarray(q[0], np.float64),
                       np.repeat(np.asarray(k[0], np.float64), 2, 0))
    probs = np.where(m, np.exp(logits - logits.max(-1, keepdims=True)), 0)
    probs /= probs.sum(-1, keepdims=True)
    p = probs.sum(0)
    p /= p.sum(-1, keepdims=True)
    top = np.where(m, s, -np.inf).max(-1, keepdims=True)
    log_q = s - top - np.log(np.where(m, np.exp(s - top), 0).sum(
        -1, keepdims=True))
    kl = np.where(m & (p > 0), p * (np.log(np.where(p > 0, p, 1))
                                    - np.where(m, log_q, 0)), 0).sum()
    assert float(got) == pytest.approx(kl / length, rel=1e-4)


# ----------------------------------------------- (e) the sum of the shares ---


@pytest.mark.parametrize("shares", [8, 2])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    """A layer whose 8 experts are spread over ``shares`` chips: each
    share's block gives the replicated attention's stream ``h`` plus its
    experts' part, so the shares' outputs less ``shares - 1`` copies of
    ``h`` are the uncut layer's output. ``h`` is the block with every
    expert's down projection zeroed."""
    uncut = {**SIZES, "num_experts": 8, "first_expert": 0}
    p = ref.init_params(uncut, 11)["params"]["layer_1"]
    x = jax.random.normal(jax.random.key(3), (1, SEQ, 64)) * 4.0
    with jax.default_matmul_precision("highest"):
        whole, _ = dsa_lm.Block(dsa_config(uncut)).apply({"params": p}, x)
        silent = {**p, "moe": {**p["moe"],
                               "w_down": jnp.zeros_like(p["moe"]["w_down"])}}
        h, _ = dsa_lm.Block(dsa_config(uncut)).apply({"params": silent}, x)
        held = 8 // shares
        total = -(shares - 1) * h
        for s in range(shares):
            cut = dsa_config({**uncut, "num_experts": held,
                              "first_expert": s * held})
            mine = {**p, "moe": {
                k: (v[s * held:(s + 1) * held] if k.startswith("w_") else v)
                for k, v in p["moe"].items()}}
            out, _ = dsa_lm.Block(cut).apply({"params": mine}, x)
            total = total + out
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-4)


# --------------------------------------------------- (f) through train() ---


def train_config(steps=3, **kw) -> Config:
    return Config(
        model=ModelConfig(arch="dsa_moe_lm"), dsa=dsa_config(**kw),
        data=DataConfig(source="tokens", batch_size=1, seq_len=SEQ),
        train=TrainConfig(num_steps=steps, log_every=1, warmup_steps=1,
                          gradient_accumulate_every=1, learning_rate=3e-3))


def test_train_takes_the_references_parameters_and_reads_its_loss():
    from alphafold2_tpu.train.loop import train

    start = ref.init_params(SIZES, 3)
    kept = jax.tree.map(np.asarray, start)
    seen = []
    train(train_config(steps=2),
          dataset=itertools.repeat({"tokens": np.asarray(tokens(2))}),
          callbacks=[lambda i, s, m: seen.append(m)], init_params=start)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.loss_fn(jax.tree.map(jnp.asarray, kept), tokens(2),
                              SIZES)
    assert float(seen[0]["loss"]) == pytest.approx(float(want), rel=1e-5)
    for m in seen:  # the counters ride beside the loss, a row a layer
        assert m["dsa/indexer_kl"].shape == (2,)
        assert m["moe/hist"].shape == (2, 8)
        assert int(m["moe/dropped"].sum()) == 0
    assert np.isfinite(float(seen[-1]["loss"]))


def test_train_pre_entry_trains_the_model(capsys):
    import train_pre

    small = dsa_config()
    fields = {f.name: getattr(small, f.name)
              for f in dataclasses.fields(small)}
    train_pre.main(
        ["model.arch=dsa_moe_lm", "data.source=tokens", "data.batch_size=1",
         f"data.seq_len={SEQ}", "train.num_steps=2", "train.log_every=1",
         "train.warmup_steps=1", "train.gradient_accumulate_every=1"]
        + [f"dsa.{k}={v}" for k, v in fields.items()])
    out = capsys.readouterr().out
    assert "[step 1]" in out and "dsa/keys_selected_mean" in out
    assert '"arch": "dsa_moe_lm"' in out


def test_a_length_off_the_packing_grid_is_refused():
    with pytest.raises(ValueError, match="whole blocks of 256"):
        dsa_lm.DsaMoeLM(dsa_config()).init(jax.random.key(0),
                                           tokens(length=200))
    cfg = train_config()
    assert cfg.language_model() is cfg.dsa
