"""The dense hybrid language model (models/hybrid_dense_lm.py) against its
plain reference (benchmark/reference/hybrid_dense_lm_model.py) on seeded
weights, small on the CPU: hidden 128, the published ten-layer period
(``MMMMM*MMMM``), one group of B and C read by every state-space head,
chunks of 256 at 512 tokens (so a state is carried), 4 query heads over one
key/value head of 64, the four multipliers as published, the head tied.

The reference walks the recurrence a time step at a time and attends densely
in float32; the program runs the chunked scan and the causal core. They
share no code.
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
    Config, DataConfig, HybridDenseLMConfig, ModelConfig, TrainConfig,
)
from alphafold2_tpu.models import hybrid_dense_lm as hybrid  # noqa: E402
from alphafold2_tpu.models import mla_moe_lm as lm  # noqa: E402
from benchmark.reference import hybrid_dense_lm_model as ref  # noqa: E402

LAYER_TYPES = tuple((["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4)
SIZES = dict(
    vocab_size=64, hidden_size=128, num_hidden_layers=10,
    layer_types=LAYER_TYPES, intermediate_size=192,
    mamba_n_heads=8, mamba_d_head=16, mamba_n_groups=1, mamba_d_state=16,
    mamba_d_conv=4, mamba_chunk_size=256,
    time_step_min=0.001, time_step_max=0.1, time_step_floor=1e-4,
    num_attention_heads=4, num_key_value_heads=1, head_dim=64,
    embedding_multiplier=12, residual_multiplier=0.22,
    attention_multiplier=0.015625, logits_scaling=8, rms_norm_eps=1e-5,
)
SEQ, BATCH = 512, 1
FAULTS = [f for f in ref.FAULTS if f]


def pattern(sizes) -> str:
    return "".join("M" if kind == "mamba" else "*"
                   for kind in sizes["layer_types"])


def program_config(sizes=SIZES, **kw) -> HybridDenseLMConfig:
    return HybridDenseLMConfig(**{**dict(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        num_layers=sizes["num_hidden_layers"], layer_pattern=pattern(sizes),
        intermediate_size=sizes["intermediate_size"],
        mamba_num_heads=sizes["mamba_n_heads"],
        mamba_head_dim=sizes["mamba_d_head"],
        ssm_groups=sizes["mamba_n_groups"],
        ssm_state_size=sizes["mamba_d_state"],
        conv_kernel=sizes["mamba_d_conv"],
        chunk_size=sizes["mamba_chunk_size"],
        num_heads=sizes["num_attention_heads"],
        num_kv_heads=sizes["num_key_value_heads"],
        head_dim=sizes["head_dim"],
        embedding_multiplier=sizes["embedding_multiplier"],
        residual_multiplier=sizes["residual_multiplier"],
        attention_multiplier=sizes["attention_multiplier"],
        logits_scaling=sizes["logits_scaling"],
        rms_norm_eps=sizes["rms_norm_eps"], bfloat16=False), **kw})


def tokens(seed=0, batch=BATCH, seq=SEQ, low=0, high=SIZES["vocab_size"]):
    return jnp.asarray(np.random.default_rng(seed).integers(
        low, high, (batch, seq)), jnp.int32)


# float32: sums in another order, twenty residual adds and a head deep;
# bfloat16: 2**-8 a product and a stream rounded after each add
TOL = {
    # (read: the same loss to the last bit, logits 2.5e-7 of the largest)
    "float32": dict(logits=5e-6, loss=1e-6, grad=1e-3),
    "bfloat16": dict(logits=1.5e-1, loss=1e-2, grad=4e-1),
}


@pytest.fixture(scope="module")
def params():
    return ref.init_params(SIZES, 7)


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def both_sides(request, params):
    """(dtype, program's outputs/loss/grads, reference's) on seeded
    weights: the reference is float32 in both cases."""
    model = hybrid.HybridDenseLM(
        program_config(bfloat16=request.param == "bfloat16"))
    toks = tokens()

    def program_loss(p):
        out = model.apply(p, toks)
        return lm.next_token_cross_entropy(out["logits"], toks), out

    (loss_p, out_p), grads_p = jax.jit(jax.value_and_grad(
        program_loss, has_aux=True))(params)

    @jax.jit
    def reference(p):
        (loss, rms), grads = jax.value_and_grad(
            ref.loss_fn, has_aux=True)(p, toks, SIZES)
        return ref.forward(p, toks, SIZES)[0], rms, loss, grads

    return request.param, (out_p, loss_p, grads_p), reference(params)


def test_the_programs_parameter_tree_is_the_references(params):
    model = hybrid.HybridDenseLM(program_config())
    made = jax.eval_shape(
        lambda: model.init(jax.random.key(0), tokens(seq=16)))
    assert jax.tree.map(lambda x: x.shape, made) == jax.tree.map(
        lambda x: x.shape, params)
    assert "head" not in params["params"]  # the tie: one table


def test_layer_types_decide_each_layers_mixer_and_every_layer_has_an_mlp(
        params):
    for i, kind in enumerate(LAYER_TYPES[:10]):
        layer = params["params"][f"layer_{i}"]
        mixer = "attn_global" if i == 5 else "ssm"
        assert kind == ("attention" if i == 5 else "mamba")
        assert sorted(layer) == sorted(
            ["mixer_norm", mixer, "ffn_norm", "dense_ffn"])
    with pytest.raises(ValueError, match="layer_pattern"):
        hybrid.layer_kinds(program_config(layer_pattern="MME"))
    assert hybrid.layer_kinds(HybridDenseLMConfig()) == "MMMMM*MMMM" * 4


def test_logits_agree_with_the_reference(both_sides):
    dtype, (out_p, _, _), (logits_r, _, _, _) = both_sides
    scale = float(jnp.abs(logits_r).max())
    np.testing.assert_allclose(out_p["logits"], logits_r, rtol=0,
                               atol=TOL[dtype]["logits"] * scale)


def test_loss_agrees_with_the_reference(both_sides):
    dtype, (_, loss_p, _), (_, _, loss_r, _) = both_sides
    assert float(loss_p) == pytest.approx(float(loss_r),
                                          rel=TOL[dtype]["loss"])


def test_every_leafs_gradient_agrees_with_the_reference(both_sides):
    dtype, (_, _, grads_p), (_, _, _, grads_r) = both_sides
    flat_p = dict(jax.tree.flatten_with_path(grads_p)[0])
    worst = 0.0
    for path, want in jax.tree.flatten_with_path(grads_r)[0]:
        got = np.asarray(flat_p[path], np.float32)
        gap = np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-12)
        assert gap <= TOL[dtype]["grad"], (jax.tree_util.keystr(path), gap)
        worst = max(worst, gap)
    assert worst > 0.0  # two computations, not one


def test_the_steps_counters_have_no_router_and_a_row_a_state_space_layer(
        both_sides):
    dtype, (out_p, _, _), (_, rms_r, _, _) = both_sides
    assert out_p["moe"] == {}
    metrics = lm.step_metrics(out_p)
    assert sorted(metrics) == [
        "ssm/chunk_decay_mean", "ssm/chunk_decay_min", "ssm/dt_mean",
        "ssm/scan_in_kernel", "stream/rms_in", "stream/rms_out"]
    for name in ("ssm/chunk_decay_min", "ssm/dt_mean", "ssm/scan_in_kernel"):
        assert metrics[name].shape == (9,)
    assert float(metrics["ssm/scan_in_kernel"].max()) == 0.0  # the CPU
    # the entering stream is 12 x a row of length 1 (root mean square 12 /
    # sqrt(hidden)); the 0.22 on each of twenty adds holds the leaving one
    rel = 1e-5 if dtype == "float32" else 1e-2
    assert float(metrics["stream/rms_in"]) == pytest.approx(
        float(rms_r[0]), rel=rel)
    assert float(metrics["stream/rms_out"]) == pytest.approx(
        float(rms_r[1]), rel=rel)
    assert float(rms_r[0]) == pytest.approx(12 / 128 ** 0.5, rel=0.05)
    assert float(rms_r[0]) < float(rms_r[1]) < 3.0


@pytest.mark.parametrize("fault", FAULTS)
def test_each_planted_fault_moves_the_loss_past_the_tolerance(params, fault):
    """A multiplier taken as 1, the other softmax scale, a state that is
    not carried, an untied head, unscaled logits: the reference with the
    fault planted reads another loss than the program, by more than twice
    what the float32 comparison allows (the weakest, the softmax scale of
    one layer in ten under a loss near ln V, by 2.3e-6 of it), and other
    logits by a thousand times their tolerance."""
    model = hybrid.HybridDenseLM(program_config())
    toks = tokens()
    logits = jax.jit(lambda p: model.apply(p, toks)["logits"])(params)
    got = float(lm.next_token_cross_entropy(logits, toks))
    (broken, _), (broken_logits, _) = jax.jit(lambda p: (
        ref.loss_fn(p, toks, SIZES, fault=fault),
        ref.forward(p, toks, SIZES, fault=fault)))(params)
    assert abs(float(broken) - got) > 2 * TOL["float32"]["loss"] * got, (
        fault, float(broken), got)
    assert float(jnp.abs(broken_logits - logits).max()) > 1000 * TOL[
        "float32"]["logits"] * float(jnp.abs(logits).max()), fault
    with pytest.raises(ValueError, match="unknown fault"):
        ref.hidden(params, toks, SIZES, fault="relu")


def test_the_tied_tables_gradient_is_the_embeddings_plus_the_heads(params):
    """The program's gradient of the one table against the reference's
    untied form, where the head reads a second leaf holding the same
    numbers: what arrives at the embedding and what arrives at the head,
    summed."""
    model = hybrid.HybridDenseLM(program_config())
    toks = tokens()
    tied = jax.jit(jax.grad(lambda p: lm.next_token_cross_entropy(
        model.apply(p, toks)["logits"], toks)))(params)
    table = params["params"]["embed"]["embedding"]
    at_embedding, at_head = jax.jit(jax.grad(
        lambda p, t: ref.loss_fn(p, toks, SIZES, table=t)[0],
        argnums=(0, 1)))(params, table)
    at_embedding = at_embedding["params"]["embed"]["embedding"]
    want = at_embedding + at_head
    got = tied["params"]["embed"]["embedding"]
    assert float(jnp.linalg.norm(at_head)) > 0.1 * float(
        jnp.linalg.norm(want))  # neither end is negligible
    assert float(jnp.linalg.norm(at_embedding)) > 0.1 * float(
        jnp.linalg.norm(want))
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-3 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("share", range(8))
def test_a_vocabulary_share_gives_its_columns_of_the_uncut_logits(share):
    """The deployment splits the tied table eight ways over the vocabulary.
    A share holds rows ``share * V/8 ..`` as its ids 0..V/8-1: on a sequence
    drawn from its own slice (a sliced vocabulary is a smaller vocabulary:
    the traffic draws from the slice) its logits are the uncut reference's
    columns of that slice, column for column; the eight side by side are
    the uncut model's whole vocabulary."""
    whole = {**SIZES, "vocab_size": 8 * SIZES["vocab_size"]}
    uncut = ref.init_params(whole, 11)
    each = SIZES["vocab_size"]
    rows = slice(share * each, (share + 1) * each)
    toks = tokens(share, seq=256, low=rows.start, high=rows.stop)
    want = jax.jit(lambda p: ref.forward(p, toks, whole)[0])(uncut)
    held = {"params": {**uncut["params"], "embed": {
        "embedding": uncut["params"]["embed"]["embedding"][rows]}}}
    got = jax.jit(lambda p: hybrid.HybridDenseLM(program_config()).apply(
        p, toks - rows.start)["logits"])(held)
    assert got.shape == (1, 256, each)
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(got, want[..., rows], rtol=0,
                               atol=TOL["float32"]["logits"] * scale)


def test_changing_a_token_leaves_every_earlier_output_alone(params):
    model = hybrid.HybridDenseLM(program_config())
    toks = tokens(seq=300)
    other = toks.at[0, 200].set((toks[0, 200] + 1) % SIZES["vocab_size"])
    run = jax.jit(lambda t: model.apply(params, t)["logits"])
    a, b = run(toks), run(other)
    np.testing.assert_array_equal(a[:, :200], b[:, :200])
    assert float(jnp.abs(a[:, 200:] - b[:, 200:]).max()) > 0


def test_the_attention_layer_is_handed_the_published_scale(params):
    """``GroupedAttention`` takes its softmax scale from the caller: the
    dense hybrid hands it ``attention_multiplier``, the other two models
    nothing, which is ``head_dim ** -0.5``."""
    from alphafold2_tpu.models.swa_moe_lm import GroupedAttention

    c = program_config()
    x = jax.random.normal(jax.random.key(3), (1, 64, c.hidden_size))
    layer = params["params"]["layer_5"]["attn_global"]
    default = GroupedAttention(c, None).apply({"params": layer}, x)
    eighth = GroupedAttention(c, None, scale=c.head_dim ** -0.5).apply(
        {"params": layer}, x)
    published = GroupedAttention(
        c, None, scale=c.attention_multiplier).apply({"params": layer}, x)
    np.testing.assert_array_equal(default, eighth)
    assert float(jnp.abs(published - default).max()) > 1e-3
    want = ref.attention(layer, x, SIZES, ref.F32, 1 / 64)
    np.testing.assert_allclose(published, want, rtol=0, atol=2e-5)


# ------------------------------------------------------------ through train ---


def train_config(steps=3, **kw) -> Config:
    return Config(
        model=ModelConfig(arch="hybrid_dense_lm"),
        hybrid=program_config(**kw),
        data=DataConfig(source="tokens", batch_size=BATCH, seq_len=SEQ),
        train=TrainConfig(num_steps=steps, log_every=1, warmup_steps=1,
                          gradient_accumulate_every=1, learning_rate=3e-3))


OPT = {"learning_rate": 3e-3, "warmup_steps": 1, "num_steps": 3}


def test_three_adam_steps_through_train_are_the_references():
    """``train()`` from the reference's start, three steps of Adam under
    the clip: each step's loss and every leaf's change against
    ``ref.train_steps``."""
    from alphafold2_tpu.train.loop import train

    start = ref.init_params(SIZES, 3)
    kept = jax.tree.map(np.asarray, start)
    batches = [np.asarray(tokens(s)) for s in (2, 3, 4)]
    seen = []
    state = train(train_config(steps=3),
                  dataset=itertools.chain(  # a fourth is prefetched
                      ({"tokens": b} for b in batches),
                      itertools.repeat({"tokens": batches[-1]})),
                  callbacks=[lambda i, s, m: seen.append(m)],
                  init_params=start)
    want = ref.train_steps(jax.tree.map(jnp.asarray, kept),
                           [jnp.asarray(b) for b in batches], SIZES, OPT)
    for m, loss in zip(seen, want["losses"]):
        assert float(m["loss"]) == pytest.approx(float(loss), rel=2e-5)
        assert "moe/hist" not in m and m["ssm/dt_mean"].shape == (9,)
        assert float(m["stream/rms_in"]) == pytest.approx(
            12 / 128 ** 0.5, rel=0.05)
    change = ref.leaf_norms(jax.tree.map(
        lambda a, b: a - b, state.params, kept))
    for name, norm in want["change_norms"].items():
        assert float(change[name]) == pytest.approx(
            float(norm), rel=2e-2, abs=1e-7), name
    assert int(state.step) == 3 and int(seen[-1]["skipped"]) == 0


def test_train_pre_entry_trains_the_model(capsys):
    import train_pre

    small = program_config(num_layers=6)  # MMMMM*: both kinds of mixer
    fields = {f.name: getattr(small, f.name)
              for f in dataclasses.fields(small)}
    train_pre.main(
        ["model.arch=hybrid_dense_lm", "data.source=tokens",
         "data.batch_size=1", "data.seq_len=256",
         "train.num_steps=3", "train.log_every=1", "train.warmup_steps=1",
         "train.gradient_accumulate_every=1"]
        + [f"hybrid.{k}={v}" for k, v in fields.items()])
    out = capsys.readouterr().out
    assert "[step 2]" in out and "ssm/scan_in_kernel" in out
    assert "stream/rms_out" in out and "moe/" not in out
    assert '"arch": "hybrid_dense_lm"' in out


def test_the_loss_falls_on_one_batch_under_every_numerics_mode(monkeypatch):
    from alphafold2_tpu.train.loop import train

    for mode in ("off", "triage", "full"):
        monkeypatch.setenv("AF2TPU_NUMERICS", mode)
        seen = []
        train(train_config(steps=4, num_layers=6),
              dataset=itertools.repeat({"tokens": np.asarray(tokens(1))}),
              callbacks=[lambda i, s, m: seen.append(m)])
        losses = [float(m["loss"]) for m in seen]
        assert all(np.isfinite(losses)) and losses[-1] < losses[1], mode


def test_the_token_stream_draws_over_the_models_own_vocabulary():
    cfg = train_config()
    assert cfg.language_model() is cfg.hybrid
    assert Config.from_json(cfg.to_json()).hybrid == cfg.hybrid


def test_an_unknown_arch_names_the_five():
    from alphafold2_tpu.train import loop

    with pytest.raises(ValueError, match="'hybrid_dense_lm'"):
        loop.build_task(Config(model=ModelConfig(arch="rwkv")))


def test_the_other_language_models_do_not_import_this_one():
    import subprocess

    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from alphafold2_tpu.config import Config, ModelConfig\n"
        "from alphafold2_tpu.train import loop\n"
        "for arch in ('mla_moe_lm', 'swa_moe_lm', 'ssm_moe_lm'):\n"
        "    loop.build_task(Config(model=ModelConfig(arch=arch)))\n"
        "bad = [m for m in sys.modules if m.endswith('hybrid_dense_lm')]\n"
        "assert not bad, bad\n" % ROOT)
    subprocess.run([sys.executable, "-c", code], check=True,
                   env={**os.environ, "JAX_PLATFORMS": "cpu"})
