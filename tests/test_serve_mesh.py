"""Mesh-sharded serving tests: the mesh-gated long-chain ladder, the
(bucket, batch, mesh) executable cache key, explicit-sharding dispatch, and
cross-mesh parity.

Parity contract (and why it is stated the way it is): the sharded trunk is
the SAME model function — its outputs (distogram logits, confidence
weights) match the single-device executable to ~1e-7, far inside the 1e-4
acceptance bound, for every shared bucket including padded batch slots.
The realized COORDINATES are a different matter: MDS + dihedral-based atom
placement on an untrained model's random distogram enlarges whatever
disturbance reaches it, the float-reassociation noise between two XLA
programs of the same computation included (pinned by
test_realization_chaos_attribution: coordinates move further than the model
outputs under the same parameter perturbation).
So coordinates are asserted finite/valid, model outputs are asserted at
1e-4, and the chaos is pinned by an attribution test rather than papered
over with a giant tolerance."""

import numpy as np
import pytest

import jax

from alphafold2_tpu.config import (
    Config,
    DataConfig,
    ModelConfig,
    ServeConfig,
)
from alphafold2_tpu.parallel.grid_parallel import make_grid_mesh
from alphafold2_tpu.serve import ServeEngine, ServeRequest, result_key


def _cfg(buckets=(8, 16), max_batch=2, grid=False, **serve_kw):
    serve_kw.setdefault("mds_iters", 20)
    serve_kw.setdefault("return_distogram", True)
    return Config(
        model=ModelConfig(dim=32, depth=1, heads=2, dim_head=16,
                          max_seq_len=3 * 64, bfloat16=False,
                          grid_parallel=grid),
        data=DataConfig(msa_depth=2),
        serve=ServeConfig(buckets=buckets, max_batch=max_batch, **serve_kw),
    )


@pytest.fixture(scope="module")
def single():
    return ServeEngine(_cfg())


@pytest.fixture(scope="module")
def mesh():
    return make_grid_mesh(1, 2, 2, devices=jax.devices()[:4])


@pytest.fixture(scope="module")
def sharded(single, mesh):
    return ServeEngine(
        _cfg(grid=True, long_buckets=(24,), long_max_batch=1),
        params=single.params, mesh=mesh,
    )


# ------------------------------------------------------------- ladder gate


def test_long_buckets_rejected_without_mesh():
    with pytest.raises(ValueError, match="require a device mesh"):
        ServeEngine(_cfg(long_buckets=(24,)))


def test_long_buckets_admitted_with_mesh(sharded):
    assert sharded.buckets == (8, 16, 24)
    assert sharded.long_buckets == (24,)
    assert sharded.batch_for(8) == 2 and sharded.batch_for(24) == 1
    assert sharded.mesh_desc == "dp1.spr2.spc2"


def test_long_request_rejected_single_device(single):
    with pytest.raises(ValueError, match="exceeds the largest bucket"):
        single.predict_many(["A" * 20])


def test_grid_mesh_requires_grid_parallel_model(mesh):
    with pytest.raises(ValueError, match="grid_parallel"):
        ServeEngine(_cfg(grid=False), mesh=mesh)


def test_mesh_batch_divisibility_validated():
    mesh = make_grid_mesh(2, 1, 2, devices=jax.devices()[:4])
    with pytest.raises(ValueError, match="divide by the mesh's dp axis"):
        ServeEngine(_cfg(grid=True, max_batch=3), mesh=mesh)


# -------------------------------------------------------- cache / identity


def test_executable_cache_keyed_by_mesh(sharded):
    sharded.predict_many([ServeRequest("ACDEFG", seed=0)])
    keys = list(sharded._executables)
    assert all(k[2] == "dp1.spr2.spc2" for k in keys), keys
    # compile records carry the mesh identity + per-device memory analysis
    rec = sharded.compile_records[0]
    assert rec["mesh"] == "dp1.spr2.spc2"
    assert rec.get("program_bytes", 0) > 0


def test_result_cache_key_carries_mesh():
    assert result_key("ACD", 1, None) != result_key("ACD", 1, "dp1.spr2.spc2")


# ------------------------------------------------------- cross-mesh parity


def test_cross_mesh_model_output_parity(single, sharded):
    """Sharded predict_many output matches single-device output within
    1e-4 for every shared bucket: the model outputs (distogram logits and
    confidence weights) are the parity surface — measured margin is ~1e-7.
    """
    for seed, seq in enumerate(["ACDEFG", "MKVLITDSW", "ACDEFGHKLMNPQR"]):
        a = single.predict_many([ServeRequest(seq, seed=seed)])[0]
        b = sharded.predict_many([ServeRequest(seq, seed=seed)])[0]
        assert a.bucket == b.bucket  # shared rung
        np.testing.assert_allclose(b.weights, a.weights, atol=1e-4)
        np.testing.assert_allclose(b.distogram, a.distogram, atol=1e-4)
        # realized coordinates: finite and correctly shaped on both (their
        # pointwise comparison is chaos-bound — see module docstring and
        # test_realization_chaos_attribution)
        assert np.all(np.isfinite(b.atom14))
        assert b.atom14.shape == a.atom14.shape


def test_cross_mesh_parity_includes_padded_batch_slots(single, sharded):
    """The same request co-batched beside a partner (and beside the
    fully-masked dummy slot padding creates) must produce the same model
    outputs as solo, on the mesh, and match single-device at 1e-4."""
    req = ServeRequest("ACDEFG", seed=11)
    solo = sharded.predict_many([req])[0]
    batched = sharded.predict_many(
        [ServeRequest("MKVLIT", seed=5), req]
    )[1]
    # same sharded executable shape -> padding exactness is bitwise-level
    np.testing.assert_allclose(batched.weights, solo.weights, atol=1e-6)
    np.testing.assert_allclose(batched.atom14, solo.atom14, atol=1e-5)
    ref = single.predict_many([req])[0]
    np.testing.assert_allclose(batched.weights, ref.weights, atol=1e-4)
    np.testing.assert_allclose(batched.distogram, ref.distogram, atol=1e-4)


def test_long_rung_serves_end_to_end(sharded):
    """A request only the mesh ladder admits (20 residues > top regular
    rung 16) dispatches on the long rung and returns a valid structure."""
    r = sharded.predict_many([ServeRequest("ACDEFGHKLMNPQRSTVWYA", seed=3)])[0]
    assert r.bucket == 24 and r.status == "ok"
    assert r.atom14.shape == (20, 14, 3)
    assert np.all(np.isfinite(r.atom14))


def test_realization_chaos_attribution(single):
    """Why coordinates are not pointwise-compared across meshes: the
    distogram->MDS->dihedral pipeline does not damp a disturbance of the
    trunk, it passes it on enlarged — a parameter perturbation moves the
    realized coordinates further than it moves the model outputs. So
    coordinates get a finite/shape check and the model outputs get the
    tight bound. The perturbation (1e-3 on one parameter vector) is chosen
    so that both deltas stand two orders above float32 reassociation noise
    (~1e-7 here): the comparison is signal against signal, never a ratio
    of two rounding errors."""
    req = [ServeRequest("ACDEFG", seed=3)]
    base = single.predict_many(req)[0]
    leaves, treedef = jax.tree_util.tree_flatten(single.params)
    leaves = [leaves[0] + 1e-3] + leaves[1:]
    eng2 = ServeEngine(_cfg(), params=jax.tree_util.tree_unflatten(
        treedef, leaves
    ))
    moved = eng2.predict_many(req)[0]
    w_delta = float(np.abs(moved.weights - base.weights).max())
    c_delta = float(np.abs(moved.atom14 - base.atom14).max())
    # the trunk moves measurably, but barely (measured 3.9e-5)...
    assert 5e-6 < w_delta < 1e-3
    # ...and the realized coordinates move further still (measured 7.7e-5)
    assert c_delta > w_delta


# ------------------------------------------------------ scheduler on mesh


def test_frontend_over_sharded_engine(sharded):
    """The async frontend threads mesh identity through its dispatch and
    result-cache keys, and forms long-rung batches at long_max_batch."""
    from alphafold2_tpu.serve import AsyncServeFrontend

    fe = AsyncServeFrontend(sharded, start=False)
    h_long = fe.submit(ServeRequest("ACDEFGHKLMNPQRSTVWYA", seed=9))
    h_dup = fe.submit(ServeRequest("ACDEFGHKLMNPQRSTVWYA", seed=9))
    fe.pump()  # long rung fills at long_max_batch=1 -> dispatches alone
    r1, r2 = h_long.result(timeout=120), h_dup.result(timeout=120)
    assert r1.status == "ok" and r1.bucket == 24
    assert r2.status == "ok" and r2.cache_hit  # in-flight dedup, mesh key
    assert fe.cache.peek(
        result_key("ACDEFGHKLMNPQRSTVWYA", 9, sharded.mesh_desc)
    ) is not None
    fe.close()
