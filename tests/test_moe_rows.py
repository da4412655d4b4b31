"""The expert layer's rows chosen from a static ladder by the live count
(``ops/moe.py`` ``row_ladder``, ``held_experts_sum``): every rung gives the
last rung's sum and gradients, none drops a row, the counter says which rung
ran, a layer that holds every expert has no switch, and the scopes the
benchmark's readers look for survive inside the switch's branches.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from alphafold2_tpu.config import (  # noqa: E402
    Config, DataConfig, LMConfig, ModelConfig, SsmLMConfig, SwaLMConfig,
    TrainConfig,
)
from alphafold2_tpu.ops import moe  # noqa: E402


@pytest.mark.parametrize("n_rows,held,n_experts,want", [
    # the three cells: 8-way, 8-way, 16-way
    (2 * 8192 * 6, 16, 128, (24576, 49152, 98304)),
    (16384 * 6, 8, 64, (24576, 49152, 98304)),
    (8192 * 6, 8, 128, (6144, 12288, 24576, 49152)),
    # rungs are whole tiles of 512 but for the last, which is all the rows
    (3000, 1, 16, (512, 1024, 1536, 3000)),
    (2048, 4, 32, (512, 1024, 2048)),
    # half the experts or more, or every one: one rung
    (98304, 64, 128, (98304,)),
    (98304, 128, 128, (98304,)),
    # too few rows for two tiles: still all of them
    (80, 4, 8, (80,)),
    (128, 2, 16, (128,)),
    (600, 1, 64, (512, 600)),
])
def test_the_ladder_doubles_from_twice_the_share_to_all_rows(
        n_rows, held, n_experts, want):
    ladder = moe.row_ladder(n_rows, held, n_experts)
    assert ladder == want
    assert ladder[-1] == n_rows and list(ladder) == sorted(set(ladder))


# 512 tokens, 4 experts a token, experts 5..8 of 32 held: the balanced share
# is 256 of the 2,048 assignments and the ladder 512 / 1,024 / 2,048
T, K, D, F, E, HELD, FIRST = 512, 4, 16, 8, 32, 4, 5
LADDER = (512, 1024, 2048)


def routing(live: int, seed: int = 0):
    """(T, K) experts, distinct in a token, of which exactly ``live`` are
    held ones: the first tokens (in a shuffled order) get four, one the
    remainder, the rest none."""
    rng = np.random.default_rng(seed)
    absent = np.r_[0:FIRST, FIRST + HELD:E]
    experts = np.stack([rng.permutation(absent)[:K] for _ in range(T)])
    for i, t in enumerate(rng.permutation(T)):
        n = min(K, live - K * i)
        if n <= 0:
            break
        experts[t, rng.permutation(K)[:n]] = FIRST + rng.permutation(HELD)[:n]
    assert ((experts >= FIRST) & (experts < FIRST + HELD)).sum() == live
    return jnp.asarray(experts, jnp.int32)


def operands(gated: bool):
    keys = jax.random.split(jax.random.key(1), 5)
    w_gate = jax.random.normal(keys[2], (HELD, D, F)) if gated else None
    return (jax.random.normal(keys[0], (T, D)),
            jax.random.uniform(keys[1], (T, K)), w_gate,
            jax.random.normal(keys[3], (HELD, D, F)),
            jax.random.normal(keys[4], (HELD, F, D)))


def last_rung(experts, x, weights, w_gate, w_up, w_down):
    """The path at all T x K rows, step by step."""
    plan = moe.dispatch(experts, FIRST, HELD, E)
    rows = moe.gather_rows(x, plan, K)
    assert rows.shape[0] == T * K
    rows = moe.expert_ffn(
        rows, plan["group_sizes"], w_gate, w_up, w_down, jnp.float32)
    return moe.combine(rows, weights, plan, K)


def laddered(experts, x, weights, w_gate, w_up, w_down):
    out, plan = moe.held_experts_sum(
        x, experts, weights, w_gate, w_up, w_down, FIRST, E, jnp.float32)
    return out, moe.load_counters(plan)


@pytest.mark.parametrize("gated", [True, False], ids=["gated", "ungated"])
@pytest.mark.parametrize("live,rung", [
    (0, 512), (300, 512), (512, 512),  # under the first rung, and on its edge
    (513, 1024), (700, 1024), (1024, 1024),  # between two rungs
    (1025, 2048), (1500, 2048), (2048, 2048),  # over all but the last
])
def test_every_rung_gives_the_last_rungs_sum_and_gradients(live, rung, gated):
    experts, args = routing(live, seed=live), operands(gated)
    out, counters = jax.jit(laddered)(experts, *args)
    assert int(counters["rows_computed"]) == rung
    assert int(counters["assignments_here"]) == live
    assert int(counters["dropped"]) == 0
    want = last_rung(experts, *args)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-4)
    wrt = tuple(i for i, a in enumerate(args) if a is not None)

    def grads(fn):
        return jax.jit(jax.grad(
            lambda *a: jnp.sum(jnp.sin(fn(experts, *a))), wrt))(*args)

    for got, ref in zip(grads(lambda *a: laddered(*a)[0]), grads(last_rung)):
        scale = float(jnp.abs(ref).max()) + 1e-30
        np.testing.assert_allclose(got / scale, ref / scale, atol=2e-5)


def test_a_rung_too_short_would_show_as_dropped():
    """The counter is honest: it counts the held experts' assignments whose
    sorted row lies inside the groups and inside the rung that ran."""
    plan = moe.dispatch(routing(700), FIRST, HELD, E)
    assert int(moe.load_counters(plan)["dropped"]) == 0
    short = {**plan, "rows_computed": jnp.int32(512)}
    assert int(moe.load_counters(short)["dropped"]) == 700 - 512


@pytest.mark.parametrize("held,first,conds", [(E, 0, 0), (E // 2, 3, 0),
                                              (HELD, FIRST, 1)])
def test_no_switch_where_one_rung_holds_all(held, first, conds):
    """A layer that holds every expert (or half of them) has one rung: its
    jaxpr has no ``cond`` and its rows are all T x K. Under that, one
    ``cond`` forward (and one more in the gradient)."""
    x, weights, w_gate, _, _ = operands(True)
    keys = jax.random.split(jax.random.key(2), 2)
    w_up = jax.random.normal(keys[0], (held, D, F))
    w_down = jax.random.normal(keys[1], (held, F, D))
    w_gate = jnp.zeros((held, D, F))

    def run(x, weights, w_gate, w_up, w_down):
        out, plan = moe.held_experts_sum(
            x, routing(300), weights, w_gate, w_up, w_down, first, E,
            jnp.float32)
        return out.sum(), moe.load_counters(plan)["rows_computed"]

    args = (x, weights, w_gate, w_up, w_down)
    assert moe.row_ladder(T * K, held, E) == (LADDER if conds else (T * K,))
    assert str(jax.make_jaxpr(run)(*args)).count(" cond[") == conds
    grad = jax.make_jaxpr(jax.grad(run, (0, 1, 2, 3, 4), has_aux=True))(*args)
    assert str(grad).count(" cond[") == 2 * conds
    if not conds:
        assert int(run(*args)[1]) == T * K


def test_sizes_too_small_for_a_tile_run_all_their_rows():
    """40 tokens x 2: one rung of 80 rows, as before the ladder."""
    rng = np.random.default_rng(0)
    experts = jnp.asarray(np.stack(
        [rng.permutation(8)[:2] for _ in range(40)]), jnp.int32)
    keys = jax.random.split(jax.random.key(3), 5)
    x = jax.random.normal(keys[0], (40, D))
    out, plan = moe.held_experts_sum(
        x, experts, jax.random.uniform(keys[1], (40, 2)),
        jax.random.normal(keys[2], (2, D, F)),
        jax.random.normal(keys[3], (2, D, F)),
        jax.random.normal(keys[4], (2, F, D)), 3, 8, jnp.float32)
    counters = moe.load_counters(plan)
    assert int(counters["rows_computed"]) == 80
    assert int(counters["dropped"]) == 0 and out.shape == x.shape


# --------------------------------------------------- scopes in the switch ---

TOYS = {
    "mla_moe_lm": dict(lm=LMConfig(
        vocab_size=512, hidden_size=64, num_layers=2, first_k_dense=1,
        num_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        kv_lora_rank=32, intermediate_size=96, moe_intermediate_size=32,
        n_routed_experts=32, n_shared_experts=1, experts_held=4,
        first_expert=2, bfloat16=True)),
    "swa_moe_lm": dict(swa=SwaLMConfig(
        vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=16, sliding_window=64,
        moe_intermediate_size=32, n_routed_experts=32, experts_held=4,
        first_expert=2, bfloat16=True)),
    "ssm_moe_lm": dict(ssm=SsmLMConfig(
        vocab_size=512, hidden_size=64, num_layers=2, mamba_num_heads=4,
        mamba_head_dim=16, ssm_groups=2, ssm_state_size=16, num_heads=4,
        num_kv_heads=2, head_dim=16, moe_intermediate_size=32,
        moe_shared_expert_intermediate_size=64, n_routed_experts=32,
        experts_held=4, first_expert=2, bfloat16=True)),
}
BLOCKS = ("moe/dispatch", "moe/experts", "moe/combine")


@pytest.mark.parametrize("arch", sorted(TOYS))
def test_operations_in_the_switch_keep_the_scopes_the_readers_find(arch):
    """One training step of a toy of each model, compiled here: every
    operation inside a branch of the expert layers' switches, forward and
    backward, holds one and only one of ``moe/dispatch``, ``moe/experts`` and
    ``moe/combine`` as the benchmark's reader of the three device-time
    metrics looks for them (names side by side), so no block's time moves to
    ``lm_rest`` and none is counted twice."""
    from alphafold2_tpu.data.pipeline import make_dataset
    from alphafold2_tpu.observe.profiler import instruction_scopes
    from alphafold2_tpu.train import loop
    from benchmark.readers.scope_paths_device_ms import holds, names

    cfg = Config(
        model=ModelConfig(arch=arch), **TOYS[arch],
        data=DataConfig(source="tokens", batch_size=1, seq_len=512),
        train=TrainConfig(gradient_accumulate_every=1, num_steps=3))
    lm = cfg.language_model()
    assert len(moe.row_ladder(512 * lm.num_experts_per_tok, lm.experts_held,
                              lm.n_routed_experts)) > 1
    task = loop.build_task(cfg)
    sample = next(iter(make_dataset(cfg.data, vocab_size=lm.vocab_size)))
    state = loop.tiny_init_state(cfg, task, sample)
    text = loop.make_train_step(task, None, numerics_mode="norms").lower(
        state, {k: jnp.asarray(v) for k, v in sample.items()},
        jax.random.key(1)).compile().as_text()
    scopes = set(instruction_scopes(text)[1].values())
    inside = [s for s in scopes if "/cond/branch_" in s]
    assert all("/moe/" in s for s in inside)
    # both switches of a layer: the sum, and its gradient's
    assert any("transpose(" in s for s in inside)
    assert any("transpose(" not in s for s in inside)
    for block in BLOCKS:
        assert any(holds(names(s), block) for s in inside), block
    # one block each: none lost to lm_rest, none counted under two metrics
    astray = [s for s in inside
              if sum(holds(names(s), block) for block in BLOCKS) != 1]
    assert not astray, astray[:5]
