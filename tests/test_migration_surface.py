"""MIGRATION.md anti-rot test: every symbol and calling pattern the
migration guide promises to reference users must exist and run. Mirrors the
reference's README usage (README.md:15-49) through this framework's API."""

import jax
import jax.numpy as jnp
import numpy as np


def test_reference_readme_usage_pattern():
    """The reference's front-page snippet, translated per MIGRATION.md:
    model -> distogram -> center_distogram -> MDScaling -> 3D coords."""
    from alphafold2_tpu.models import Alphafold2
    from alphafold2_tpu.utils.mds import MDScaling
    from alphafold2_tpu.utils.structure import center_distogram

    model = Alphafold2(dim=32, depth=1, heads=2, dim_head=16, max_seq_len=64)
    k = jax.random.key(0)
    seq = jax.random.randint(jax.random.fold_in(k, 1), (1, 16), 0, 21)
    msa = jax.random.randint(jax.random.fold_in(k, 2), (1, 3, 16), 0, 21)
    mask = jnp.ones((1, 16), bool)
    msa_mask = jnp.ones((1, 3, 16), bool)
    params = model.init(k, seq, msa, mask=mask, msa_mask=msa_mask)
    distogram = model.apply(params, seq, msa, mask=mask, msa_mask=msa_mask)
    assert distogram.shape == (1, 16, 16, 37)  # reference output spec

    probs = jax.nn.softmax(distogram, -1)
    distances, weights = center_distogram(probs)
    coords_3d, _ = MDScaling(distances, weights=weights, iters=10,
                             fix_mirror=0)
    assert coords_3d.shape == (1, 3, 16)
    assert np.isfinite(np.asarray(coords_3d)).all()


def test_migration_symbols_exist():
    """Every API name the migration table maps must import."""
    from alphafold2_tpu.models import Alphafold2  # noqa: F401
    from alphafold2_tpu.ops.sparse import BlockSparseConfig  # noqa: F401
    from alphafold2_tpu.parallel.seq_parallel import (  # noqa: F401
        tied_row_attention,
    )
    from alphafold2_tpu.utils.mds import MDScaling, mds  # noqa: F401
    from alphafold2_tpu.utils.metrics import (  # noqa: F401
        GDT, RMSD, Kabsch, TMscore, calc_phis, get_dihedral,
    )
    from alphafold2_tpu.utils.pdb import (  # noqa: F401
        backbone_to_pdb, clean_pdb, custom2pdb, download_pdb,
    )
    from alphafold2_tpu.utils.structure import (  # noqa: F401
        center_distogram, get_bucketed_distance_matrix, scn_backbone_mask,
        scn_cloud_mask, sidechain_container,
    )

    # ctor kwargs promised to carry over from the reference
    import inspect

    fields = set(inspect.signature(Alphafold2).parameters)
    for kw in ("dim", "depth", "heads", "dim_head", "max_seq_len",
               "reversible", "sparse_self_attn", "cross_attn_compress_ratio",
               "msa_tie_row_attn", "template_attn_depth", "attn_dropout",
               "ff_dropout"):
        assert kw in fields, kw
