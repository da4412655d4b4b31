"""bf16 serving mode: per-layer drift vs f32 pinned against stated bounds.

The bf16 serving mode (ServeConfig.dtype="bfloat16") is numerically GATED,
not asserted: the in-graph numerics tags (observe/numerics.py — embeddings,
every trunk layer boundary, the distogram logits) are collected for the
same tiny trunk at f32 and bf16, and the per-tensor drift must stay inside
the bounds below. The bounds are the contract README documents; measured
drift on this config sits ~10x under them (per-layer norm drift <= 7e-4,
logits relative error ~0.9%), so a violation means the bf16 path changed,
not that the tolerance was tight.

Coordinate-level parity is deliberately NOT asserted: structure realization
chaotically amplifies trunk-level perturbations (pinned by the attribution
test in tests/test_serve_mesh.py), so the honest bf16 contract is at the
trunk/logits level plus end-to-end finiteness and serving health.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alphafold2_tpu.models.alphafold2 import Alphafold2
from alphafold2_tpu.observe import numerics

# The stated bf16 drift bounds (README "Pallas kernels & low-precision
# serving"): relative drift of each tagged tensor's L2 norm, and relative
# L2 error of the distogram logits vs the f32 run. Re-baselining policy:
# loosen ONLY with a PR that explains the numerical change.
PER_LAYER_L2_DRIFT_BOUND = 0.01
LOGITS_REL_ERR_BOUND = 0.05


def _tiny_trunk(dtype):
    return Alphafold2(
        dim=32, depth=2, heads=2, dim_head=16, max_seq_len=64,
        msa_tie_row_attn=True, dtype=dtype,
    )


def _inputs():
    rng = np.random.default_rng(0)
    b, n, m, nm = 1, 24, 4, 24
    seq = jnp.asarray(rng.integers(0, 20, (b, n)), jnp.int32)
    msa = jnp.asarray(rng.integers(0, 20, (b, m, nm)), jnp.int32)
    mask = jnp.ones((b, n), bool).at[:, 20:].set(False)
    msa_mask = jnp.ones((b, m, nm), bool).at[:, :, 20:].set(False)
    return seq, msa, mask, msa_mask


def _cast_bf16(params):
    return jax.tree.map(
        lambda x: x.astype(jnp.bfloat16)
        if getattr(x, "dtype", None) == jnp.float32 else x,
        params,
    )


def _run_tagged(model, params, seq, msa, mask, msa_mask):
    with numerics.collect() as col:
        logits = model.apply(
            params, seq, msa, mask=mask, msa_mask=msa_mask,
            deterministic=True,
        )
    return np.asarray(logits, np.float32), numerics.stats_to_host(
        col.stats()
    )


@pytest.fixture(scope="module")
def drift():
    seq, msa, mask, msa_mask = _inputs()
    f32 = _tiny_trunk(jnp.float32)
    params = f32.init(jax.random.key(0), seq, msa, mask=mask,
                      msa_mask=msa_mask)
    logits_f, stats_f = _run_tagged(f32, params, seq, msa, mask, msa_mask)
    bf16 = _tiny_trunk(jnp.bfloat16)
    logits_b, stats_b = _run_tagged(
        bf16, _cast_bf16(params), seq, msa, mask, msa_mask
    )
    return logits_f, stats_f, logits_b, stats_b


def test_bf16_per_layer_drift_inside_bounds(drift):
    _, stats_f, _, stats_b = drift
    shared = set(stats_f) & set(stats_b)
    # the tag vocabulary itself must not silently shrink: every layer
    # boundary the f32 trunk tags must exist in the bf16 run too
    assert shared == set(stats_f), (set(stats_f) ^ set(stats_b))
    assert any(name.startswith("trunk.layer_") for name in shared)
    for name in sorted(shared):
        a, b = stats_f[name], stats_b[name]
        rel = abs(b["l2"] - a["l2"]) / max(a["l2"], 1e-9)
        assert rel <= PER_LAYER_L2_DRIFT_BOUND, (
            f"{name}: bf16 L2 drift {rel:.4f} exceeds the stated bound "
            f"{PER_LAYER_L2_DRIFT_BOUND}"
        )


def test_bf16_introduces_no_nonfinites(drift):
    _, _, _, stats_b = drift
    for name, s in stats_b.items():
        assert s["nan_count"] == 0 and s["inf_count"] == 0, (name, s)
    assert numerics.first_nonfinite(stats_b) is None


def test_bf16_logits_error_inside_bounds(drift):
    logits_f, _, logits_b, _ = drift
    rel = np.linalg.norm(logits_b - logits_f) / max(
        np.linalg.norm(logits_f), 1e-9
    )
    assert rel <= LOGITS_REL_ERR_BOUND, (
        f"distogram logits rel L2 error {rel:.4f} exceeds the stated "
        f"bound {LOGITS_REL_ERR_BOUND}"
    )
    # and the drift is REAL (the two runs are not accidentally identical,
    # which would mean the bf16 cast silently did not happen)
    assert rel > 0


def test_bf16_serve_engine_end_to_end(monkeypatch):
    """ServeEngine in the bf16 mode with the fused tied-row kernel (its
    platform predicate steered, so it runs in interpret mode here): params
    actually cast, requests served ok with finite coords, the kernel is in
    every executable, and the executable identity (compile records)
    carries the dtype key the regression gate refuses to cross-compare."""
    from alphafold2_tpu.config import (
        Config, DataConfig, ModelConfig, ServeConfig,
    )
    from alphafold2_tpu.ops.pallas import tied_row
    from alphafold2_tpu.serve import ServeEngine

    traced = []
    kernel = tied_row.tied_row_attention

    def recording(q, *args, **kwargs):
        traced.append(q.dtype)
        return kernel(q, *args, **kwargs)

    monkeypatch.setattr(tied_row, "tied_row_available", lambda: True)
    monkeypatch.setattr(tied_row, "tied_row_attention", recording)

    cfg = Config(
        model=ModelConfig(
            dim=32, depth=1, heads=2, dim_head=16, max_seq_len=48,
            bfloat16=False, msa_tie_row_attn=True,
        ),
        data=DataConfig(msa_depth=2),
        serve=ServeConfig(
            buckets=(8, 16), max_batch=2, mds_iters=8,
            dtype="bfloat16",
        ),
    )
    engine = ServeEngine(cfg)
    assert engine.serve_dtype == "bfloat16"
    float_leaves = [
        x for x in jax.tree.leaves(engine.params)
        if jnp.issubdtype(x.dtype, jnp.floating)
    ]
    assert float_leaves and all(
        x.dtype == jnp.bfloat16 for x in float_leaves
    )
    results = engine.predict_many(["ACDEFGH", "MKVLAWGACDEF"])
    for r in results:
        assert r.ok, r
        assert np.all(np.isfinite(r.atom14))
    for rec in engine.compile_records:
        assert rec["dtype"] == "bfloat16"
        assert "kernels" not in rec
        assert rec["flops_breakdown"]["tied_row"] > 0
    # one tied row pass a layer in each executable, in the serving dtype
    assert len(traced) >= len(engine.compile_records) > 0
    assert set(traced) == {jnp.dtype("bfloat16")}


def test_serve_dtype_validation():
    from alphafold2_tpu.config import Config, ServeConfig
    from alphafold2_tpu.serve import ServeEngine

    cfg = Config(serve=ServeConfig(buckets=(8,), dtype="float16"))
    with pytest.raises(ValueError, match="serve.dtype"):
        ServeEngine(cfg)
