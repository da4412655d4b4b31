"""The third language model (a layer is one norm and one mixer: a Mamba-2
state-space mixer, sigmoid-routed ungated relu^2 experts beside a shared one,
or positionless grouped-query attention, as one chip's share) against the
benchmark's plain reference, tiny on the CPU (the chunked scan of
``ops/ssm.py`` against the per-step recurrence: ``tests/test_ssm_scan.py``).

The reference (``benchmark/reference/ssm_lm_model.py``) imports nothing of
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
    Config, DataConfig, ModelConfig, SsmLMConfig, TrainConfig,
)
from alphafold2_tpu.models import mla_moe_lm as lm  # noqa: E402
from alphafold2_tpu.models import ssm_moe_lm as hybrid  # noqa: E402
from alphafold2_tpu.models import swa_moe_lm as swa  # noqa: E402
from alphafold2_tpu.ops import mla, moe  # noqa: E402
from benchmark.reference import ssm_lm_model as ref  # noqa: E402

PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
# the cell's nine layers, hidden 64, 4 state-space heads of 16 over 2 groups
# of 8 state rows, chunks of 16; 4 query heads over 2 key/value heads of 16;
# 8 experts top-2, 4 of them held, one shared expert
SIZES = dict(
    vocab_size=48, hidden_size=64, num_hidden_layers=9,
    hybrid_override_pattern=PATTERN, mamba_num_heads=4, mamba_head_dim=16,
    n_groups=2, ssm_state_size=8, conv_kernel=4, chunk_size=16,
    time_step_min=0.001, time_step_max=0.1, time_step_floor=1e-4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    moe_intermediate_size=32, moe_shared_expert_intermediate_size=48,
    n_routed_experts=4, router_width=8, first_expert=2,
    num_experts_per_tok=2, routed_scaling_factor=2.5,
    layer_norm_epsilon=1e-5,
)
SEQ, BATCH = 40, 2
SSM_LEAVES = ("A_log", "D", "dt_bias", "conv/bias", "conv/kernel",
              "gate_norm/scale", "in_proj/kernel", "out_proj/kernel")


def ssm_config(sizes=SIZES, **kw) -> SsmLMConfig:
    return SsmLMConfig(**{**dict(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        num_layers=sizes["num_hidden_layers"],
        layer_pattern=sizes["hybrid_override_pattern"],
        mamba_num_heads=sizes["mamba_num_heads"],
        mamba_head_dim=sizes["mamba_head_dim"],
        ssm_groups=sizes["n_groups"],
        ssm_state_size=sizes["ssm_state_size"],
        conv_kernel=sizes["conv_kernel"], chunk_size=sizes["chunk_size"],
        num_heads=sizes["num_attention_heads"],
        num_kv_heads=sizes["num_key_value_heads"],
        head_dim=sizes["head_dim"],
        moe_intermediate_size=sizes["moe_intermediate_size"],
        moe_shared_expert_intermediate_size=sizes[
            "moe_shared_expert_intermediate_size"],
        n_routed_experts=sizes["router_width"],
        num_experts_per_tok=sizes["num_experts_per_tok"],
        routed_scaling_factor=sizes["routed_scaling_factor"],
        rms_norm_eps=sizes["layer_norm_epsilon"],
        experts_held=sizes["n_routed_experts"],
        first_expert=sizes["first_expert"], bfloat16=False),
        **kw})


def tokens(seed=0, batch=BATCH, seq=SEQ, vocab=SIZES["vocab_size"]):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, vocab, (batch, seq)), jnp.int32)


# as tests/test_swa_moe_lm.py holds the second model: float32 sums in
# another order; bfloat16 2**-8 a product, nine layers and a head deep
TOL = {
    "float32": dict(logits=2e-5, loss=1e-5, grad=5e-4),
    # a flipped token moves its two experts' gradients, and the router's of
    # that layer with them (0.26 of the leaf on the last expert layer, whose
    # weights are scaled 2.5 and whose experts square)
    "bfloat16": dict(logits=7e-2, loss=5e-3, grad=4e-1),
}


@pytest.fixture(scope="module")
def params():
    return ref.init_params(SIZES, 7)


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def both_sides(request, params):
    """(dtype, program's outputs/loss/grads, reference's) on seeded
    weights: the reference is float32 in both cases."""
    model = hybrid.SsmMoeLM(ssm_config(bfloat16=request.param == "bfloat16"))
    toks = tokens()

    def program_loss(p):
        out = model.apply(p, toks)
        return lm.next_token_cross_entropy(out["logits"], toks), out

    (loss_p, out_p), grads_p = jax.jit(jax.value_and_grad(
        program_loss, has_aux=True))(params)

    @jax.jit
    def reference(p):
        (loss, hist), grads = jax.value_and_grad(
            ref.loss_fn, has_aux=True)(p, toks, SIZES)
        return ref.forward(p, toks, SIZES)[0], hist, loss, grads

    logits_r, hist_r, loss_r, grads_r = reference(params)
    return request.param, (out_p, loss_p, grads_p), (
        logits_r, hist_r, loss_r, grads_r)


def test_the_programs_parameter_tree_is_the_references(params):
    model = hybrid.SsmMoeLM(ssm_config())
    made = jax.eval_shape(model.init, jax.random.key(0), tokens())
    assert jax.tree.map(lambda x: x.shape, made) == jax.tree.map(
        lambda x: x.shape, params)


def test_the_pattern_string_decides_each_layers_one_mixer(params):
    """``MEMEM*EME``: 4 state-space, 4 expert and 1 attention layer, each
    one norm and one mixer and nothing else."""
    assert hybrid.layer_kinds(ssm_config()) == "MEMEM*EME"
    assert [hybrid.layer_kinds(ssm_config()).count(k) for k in "ME*"] == [
        4, 4, 1]
    for i, kind in enumerate("MEMEM*EME"):
        assert sorted(params["params"][f"layer_{i}"]) == sorted(
            ["norm", hybrid.MIXERS[kind]])
    # another pattern, other mixers: the same code, no option
    other = hybrid.SsmMoeLM(ssm_config(num_layers=3, layer_pattern="*ME"))
    made = jax.eval_shape(other.init, jax.random.key(0), tokens())["params"]
    assert [sorted(made[f"layer_{i}"]) for i in range(3)] == [
        ["attn_global", "norm"], ["norm", "ssm"], ["moe", "norm"]]
    for bad in (dict(num_layers=60), dict(layer_pattern="MEM-M*EME")):
        with pytest.raises(ValueError, match="layer_pattern"):
            hybrid.layer_kinds(ssm_config(**bad))


def test_the_published_initialisation_of_the_state_space_leaves():
    """Both sides draw ``A_log``, ``dt_bias``, ``D`` and the convolution from
    the configuration's own keys: a in [1, 16], dt in [0.001, 0.1]."""
    wide = {**SIZES, "mamba_num_heads": 64, "n_groups": 8}
    made = ref.init_params(wide, 3)["params"]["layer_0"]["ssm"]
    own = hybrid.SsmMoeLM(ssm_config(wide)).init(
        jax.random.key(3), tokens())["params"]["layer_0"]["ssm"]
    for leaves in (made, own):
        a, dt = np.exp(leaves["A_log"]), jax.nn.softplus(leaves["dt_bias"])
        assert 1.0 <= a.min() and a.max() <= 16.0 and a.max() > 8.0
        assert 0.001 <= dt.min() * 1.001 and dt.max() <= 0.1001
        assert dt.min() < 0.004 and dt.max() > 0.03  # log-uniform, not flat
        np.testing.assert_array_equal(leaves["D"], 1.0)
        for leaf in ("kernel", "bias"):
            assert np.abs(leaves["conv"][leaf]).max() <= 0.5
            assert np.abs(leaves["conv"][leaf]).max() > 0.4


def test_logits_agree_with_the_reference(both_sides):
    dtype, (out, _, _), (logits_r, _, _, _) = both_sides
    gap = np.abs(np.asarray(out["logits"] - logits_r)).max(-1) / float(
        jnp.max(jnp.abs(logits_r)))
    assert out["logits"].dtype == jnp.float32
    # in bfloat16 a near-tie of the top-2 flips in a few of the 80 tokens x 4
    # expert layers (5 under jit): such a token gets another expert's
    # output, 0.26-0.40 of the largest logit. The others are held to the
    # tolerance
    flipped = 0 if dtype == "float32" else 8
    assert (np.sort(gap.ravel())[::-1][flipped:]
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
    # the state-space leaves by name, each against its own norm (the median
    # would hide A_log's and dt_bias's small gradients), in every M layer
    for i in (0, 2, 4, 7):
        for leaf in SSM_LEAVES:
            name = f"params/layer_{i}/ssm/{leaf}"
            assert float(flat_r[name]) > 0.0, name
            if dtype == "float32":
                assert float(diff[name]) / float(flat_r[name]) < 2e-4, name
    # the router learns through the combine weights, in every expert layer
    for i in (1, 3, 6, 8):
        assert float(flat_p[f"params/layer_{i}/moe/router"]) > 0.0


def test_routing_counts_agree_with_the_reference(both_sides):
    dtype, (out, _, _), (_, hist_r, _, _) = both_sides
    hist_p = np.asarray(out["moe"]["hist"])
    assert hist_p.shape == (4, SIZES["router_width"])
    assert (hist_p.sum(1) == BATCH * SEQ * 2).all()
    flips = np.abs(hist_p - np.asarray(hist_r)).sum()
    assert flips <= (0 if dtype == "float32" else 16)
    held = slice(SIZES["first_expert"],
                 SIZES["first_expert"] + SIZES["n_routed_experts"])
    np.testing.assert_array_equal(
        out["moe"]["assignments_here"], hist_p[:, held].sum(1))
    assert int(out["moe"]["dropped"].sum()) == 0


def test_the_scans_counters_ride_beside_the_routings(both_sides):
    _, (out, _, _), _ = both_sides
    metrics = lm.step_metrics(out)
    assert sorted(metrics) == [
        "moe/assignments_here", "moe/dropped", "moe/hist",
        "moe/load_max_over_mean", "moe/rows_computed",
        "ssm/chunk_decay_mean", "ssm/chunk_decay_min", "ssm/conv_in_kernel",
        "ssm/dt_mean", "ssm/scan_in_kernel"]
    # every rung's rows hold what the layer was sent (at this size: one rung)
    assert (metrics["moe/rows_computed"]
            >= metrics["moe/assignments_here"]).all()
    for name in ("ssm/chunk_decay_min", "ssm/chunk_decay_mean",
                 "ssm/dt_mean"):
        assert metrics[name].shape == (4,)  # a row a state-space layer
    low, mean = metrics["ssm/chunk_decay_min"], metrics["ssm/chunk_decay_mean"]
    assert (0.0 <= low).all() and (low <= mean).all() and (mean < 1.0).all()
    assert (metrics["ssm/dt_mean"] > 0.0).all()
    # off the TPU (and off the tile grid) every layer's scan and convolution
    # is the XLA form
    np.testing.assert_array_equal(metrics["ssm/scan_in_kernel"], 0.0)
    assert metrics["ssm/conv_in_kernel"].shape == (4,)
    np.testing.assert_array_equal(metrics["ssm/conv_in_kernel"], 0.0)


# ------------------------------------- (b) the state-space mixer, whole ---
# (the scan and the convolution alone: tests/test_ssm_scan.py)


def test_changing_a_token_leaves_every_earlier_output_alone(params):
    """Causality through the convolution and the scan (and the attention
    layer and the experts): token t moves logits t.. and none before."""
    apply = jax.jit(hybrid.SsmMoeLM(ssm_config()).apply)
    toks = tokens(batch=1)
    at = 23
    base = apply(params, toks)["logits"]
    moved = apply(params, toks.at[0, at].set((toks[0, at] + 1) % 48))["logits"]
    changed = np.abs(np.asarray(moved - base)).max(-1)[0]
    np.testing.assert_array_equal(changed[:at], 0.0)
    assert (changed[at:] > 0).all()


@pytest.mark.parametrize("cut", [16, 21])
def test_a_mixer_split_in_two_with_its_state_carried_is_the_whole(
        params, cut):
    """The reference's mixer on a sequence's halves, the state and the last
    three convolution inputs carried between them, is its mixer on the
    whole, which is the program's."""
    p = params["params"]["layer_0"]["ssm"]
    u = jax.random.normal(jax.random.key(4), (BATCH, SEQ, 64))
    want = ref.mamba_mixer(p, u, SIZES, ref.F32)
    zeros = (jnp.zeros((BATCH, 4, 8, 16)), jnp.zeros((BATCH, 3, 96)))
    out1, carried = ref.mamba_mixer(p, u[:, :cut], SIZES, ref.F32,
                                    carried=zeros)
    out2, _ = ref.mamba_mixer(p, u[:, cut:], SIZES, ref.F32, carried=carried)
    np.testing.assert_allclose(
        jnp.concatenate([out1, out2], 1), want, rtol=1e-4, atol=1e-5)
    got, _ = hybrid.Mamba2Mixer(ssm_config()).apply({"params": p}, u)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("fault", ["state_dropped", "conv_reversed"])
def test_the_references_planted_faults_change_the_mixer(params, fault):
    p = params["params"]["layer_0"]["ssm"]
    u = jax.random.normal(jax.random.key(4), (BATCH, SEQ, 64))
    good = ref.mamba_mixer(p, u, SIZES, ref.F32)
    bad = ref.mamba_mixer(p, u, SIZES, ref.F32, fault=fault)
    gap = np.abs(np.asarray(bad - good)).max((0, 2))
    if fault == "state_dropped":  # the first chunk has nothing to drop
        np.testing.assert_array_equal(gap[:16], 0.0)
        assert (gap[16:] > 1e-4).all()
    else:
        assert (gap > 1e-3).all()


# ------------------------------------------------ (c) the sum of the shares ---


@pytest.mark.parametrize("shares", [16, 8, 4, 2, 1])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    """Every share's routed part, with the shared expert (which every chip
    computes alike) counted once, adds up to what the uncut reference gives
    for the whole expert layer: the cut to one chip's experts leaves out
    exactly the other chips' parts."""
    uncut = {**SIZES, "num_hidden_layers": 2, "router_width": 16,
             "n_routed_experts": 16, "first_expert": 0}
    p = ref.init_params(uncut, 11)["params"]["layer_1"]["moe"]
    y = jax.random.normal(jax.random.key(3), (BATCH, SEQ, 64), jnp.float32)
    whole, hist = ref.expert_layer(p, y, uncut, ref.F32)
    shared = ref.relu2_mlp(p["shared"], y, ref.F32)
    held = 16 // shares
    total = jnp.zeros_like(whole)
    for s in range(shares):
        cut = ssm_config({**uncut, "n_routed_experts": held,
                          "first_expert": s * held})
        mine = {k: (v[s * held:(s + 1) * held] if k.startswith("w_") else v)
                for k, v in p.items()}
        out, counters = hybrid.UngatedExperts(cut).apply({"params": mine}, y)
        total = total + (out - shared)
        np.testing.assert_array_equal(counters["hist"], hist)  # all route alike
    np.testing.assert_allclose(total + shared, whole, rtol=2e-4, atol=2e-5)
    assert float(jnp.abs(shared).max()) > 1e-2  # there is one to count once


def test_the_router_is_the_first_language_models_at_another_scaling(params):
    layer = params["params"]["layer_1"]["moe"]
    y = jax.random.normal(jax.random.key(5), (BATCH * SEQ, 64))
    experts, weights = moe.route(y, layer["router"], layer["router_bias"],
                                 2, 2.5)
    want_e, want_w = ref.route(layer, y, SIZES, 2)
    np.testing.assert_array_equal(experts, want_e)
    np.testing.assert_allclose(weights, want_w, rtol=1e-6)
    np.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-5)


# ------------------------------------------------ (d) the attention layer ---


def test_the_attention_layer_sees_no_positions(params):
    """The model's ``*`` layer is the second model's global layer at this
    model's heads: shifting or stretching the positions changes nothing, and
    the output is the reference's."""
    layer = params["params"]["layer_5"]["attn_global"]
    attn = swa.GroupedAttention(ssm_config(), None)
    x = jax.random.normal(jax.random.key(2), (BATCH, SEQ, 64), jnp.float32)
    base = attn.apply({"params": layer}, x)
    for positions in (jnp.arange(SEQ) + 1000, jnp.arange(SEQ) * 3):
        moved = attn.apply({"params": layer}, x, positions=positions)
        assert float(jnp.abs(moved - base).max()) == 0.0
    want = ref.swa_lm_model.attention(layer, x, SIZES, ref.F32, None, False)
    np.testing.assert_allclose(base, want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("remat", ["kept", "bare"])
def test_a_layer_that_is_attention_alone_keeps_the_cores_two_results(
        kernel_path_on_the_cpu, kept_across_remat, remat):
    """PR 33's kept output and log-sum-exp hold for a layer with no
    feed-forward half: across its recomputation the core's output (B, H, S,
    D) and log-sum-exp (B, H, S) and nothing else; a bare ``nn.remat`` keeps
    nothing."""
    wrap = lm.remat_layer if remat == "kept" else nn.remat
    layer = wrap(hybrid.Block)(ssm_config(), "*")
    kept = kept_across_remat(layer, jnp.ones((1, 128, 64)))
    assert kept == (["f32[1,4,128,16]", "f32[1,4,128]"]
                    if remat == "kept" else [])


@pytest.mark.parametrize("kind", ["M", "E"])
def test_the_other_mixers_keep_nothing_across_their_recomputation(
        kept_across_remat, kind):
    layer = lm.remat_layer(hybrid.Block)(ssm_config(), kind)
    assert kept_across_remat(layer, jnp.ones((1, 32, 64))) == []


def test_the_forward_kernel_runs_once_in_the_attention_layer(
        kernel_path_on_the_cpu, named_eqns):
    """The cell's shape class: the fused backward over grouped heads (one
    backward kernel, no dq kernel), the forward kernel once though the layer
    is recomputed."""
    model = hybrid.SsmMoeLM(ssm_config(num_layers=6))  # MEMEM*
    toks = tokens(batch=1, seq=128)
    shapes = jax.eval_shape(model.init, jax.random.key(0), toks)
    grad = jax.grad(lambda p: model.apply(p, toks)["logits"].sum())
    assert named_eqns("pallas_call", grad, shapes) == {
        "splash_mha_fwd_residuals": 1, "splash_mha_dkv_no_residuals": 1}


def test_the_cells_core_takes_the_fused_backward():
    """32 query heads x 8,192 x 128 in bfloat16: the fused backward's partial
    dq is 8 x 32 x 8,192 x 128 x 2 B = 0.54 GB a sequence, under
    ``PARTIAL_DQ_BYTES`` (the second model's 28 x 16,384 is not)."""
    sizes = mla.splash_block_sizes(32, 8192, 128, 128, jnp.bfloat16)
    assert sizes.use_fused_bwd_kernel and sizes.block_q == 1024
    assert 8 * 32 * 8192 * 128 * 2 < mla.PARTIAL_DQ_BYTES
    assert not mla.splash_block_sizes(
        28, 16384, 128, 128, jnp.bfloat16).use_fused_bwd_kernel


# --------------------------------------------- (e) through train(), 4 steps ---


# the pattern's first six layers (MEMEM*: every kind of mixer) compile in
# half the time of the nine
SIX = {**SIZES, "num_hidden_layers": 6}


def train_config(steps=3, **kw) -> Config:
    return Config(
        model=ModelConfig(arch="ssm_moe_lm"), ssm=ssm_config(SIX, **kw),
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
    for m in seen:  # the counters ride beside the loss
        assert m["moe/hist"].shape == (2, 8)  # a row an expert layer
        assert int(m["moe/dropped"].sum()) == 0
        assert m["ssm/chunk_decay_min"].shape == (3,)
        assert m["ssm/dt_mean"].shape == (3,)


def test_train_takes_the_references_parameters_and_reads_its_loss():
    from alphafold2_tpu.train.loop import train

    start = ref.init_params(SIX, 3)
    kept = jax.tree.map(np.asarray, start)
    seen = []
    train(train_config(steps=1),
          dataset=itertools.repeat({"tokens": np.asarray(tokens(2))}),
          callbacks=[lambda i, s, m: seen.append(m)], init_params=start)
    want, _ = jax.jit(lambda p: ref.loss_fn(p, tokens(2), SIX))(
        jax.tree.map(jnp.asarray, kept))
    assert float(seen[0]["loss"]) == pytest.approx(float(want), rel=1e-5)


def test_train_pre_entry_trains_the_model(capsys):
    import train_pre

    small = ssm_config(SIX)
    fields = {f.name: getattr(small, f.name)
              for f in dataclasses.fields(small)}
    train_pre.main(
        ["model.arch=ssm_moe_lm", "data.source=tokens", "data.batch_size=2",
         f"data.seq_len={SEQ}",
         "train.num_steps=3", "train.log_every=1", "train.warmup_steps=1",
         "train.gradient_accumulate_every=1"]
        + [f"ssm.{k}={v}" for k, v in fields.items()])
    out = capsys.readouterr().out
    assert "[step 2]" in out and "ssm/chunk_decay_min" in out
    assert "moe/assignments_here" in out
    assert '"arch": "ssm_moe_lm"' in out


def test_the_token_stream_draws_over_the_models_own_vocabulary():
    cfg = train_config()
    assert cfg.language_model() is cfg.ssm
    assert Config.from_json(cfg.to_json()).ssm == cfg.ssm


def test_an_unknown_arch_names_the_four():
    from alphafold2_tpu.train import loop

    with pytest.raises(ValueError, match="'ssm_moe_lm'"):
        loop.build_task(Config(model=ModelConfig(arch="rwkv")))


def test_the_other_language_models_do_not_import_this_one():
    import subprocess

    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from alphafold2_tpu.config import Config, ModelConfig\n"
        "from alphafold2_tpu.train import loop\n"
        "for arch in ('mla_moe_lm', 'swa_moe_lm'):\n"
        "    loop.build_task(Config(model=ModelConfig(arch=arch)))\n"
        "bad = [m for m in sys.modules if m.endswith(('ssm_moe_lm', "
        "'ops.ssm'))]\n"
        "assert not bad, bad\n" % ROOT)
    subprocess.run([sys.executable, "-c", code], check=True,
                   env={**os.environ, "JAX_PLATFORMS": "cpu"})
