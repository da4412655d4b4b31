"""Structure refinement via PyRosetta FastRelax — optional-dependency stub.

Keeps the same optional-stub shape as the reference (scripts/refinement.py:
import is warning-guarded :8-14, pdb<->pose conversion :22-54, and
``run_fast_relax`` loads a JSON config then raises NotImplementedError
:56-74). PyRosetta is licensed/closed and out of scope (SURVEY.md S2.4);
what IS implemented here is everything around the rosetta call so a user
with PyRosetta installed only fills in the marked section.
"""

from __future__ import annotations

import json
import warnings
from pathlib import Path

try:
    import pyrosetta  # type: ignore

    HAS_PYROSETTA = True
    pyrosetta.init(silent=True)
except ImportError:
    HAS_PYROSETTA = False
    warnings.warn(
        "pyrosetta not installed: FastRelax refinement unavailable. "
        "Install from https://www.pyrosetta.org/ (license required)."
    )

DEFAULT_CONFIG = {
    "scorefxn": "ref2015",
    "max_iter": 100,
    "constrain_relax_to_start_coords": True,
}


def pdb_to_pose(path: str):
    """Load a .pdb into a rosetta Pose (reference scripts/refinement.py:22-37)."""
    if not HAS_PYROSETTA:
        raise ImportError("pyrosetta required for pdb_to_pose")
    return pyrosetta.pose_from_pdb(path)


def pose_to_pdb(pose, path: str) -> str:
    """Write a rosetta Pose to .pdb (reference scripts/refinement.py:39-54)."""
    if not HAS_PYROSETTA:
        raise ImportError("pyrosetta required for pose_to_pdb")
    pose.dump_pdb(path)
    return path


def load_config(path: str | None = None) -> dict:
    cfg = dict(DEFAULT_CONFIG)
    if path is not None:
        cfg.update(json.loads(Path(path).read_text()))
    return cfg


def run_native_relax(pdb_in: str, pdb_out: str, iters: int = 200) -> str:
    """Dependency-free relaxation on the backbone (utils/relax.py): Adam on
    a bond-geometry + clash + restraint energy, jit-compiled — works on TPU
    with no external license. Beyond-reference: the reference's FastRelax
    was never implemented."""
    import sys

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    import alphafold2_tpu

    alphafold2_tpu.enable_compile_cache()
    import jax
    import numpy as np

    from alphafold2_tpu.utils.pdb import load_pdb, replace_coords, to_pdb_string
    from alphafold2_tpu.utils.relax import fast_relax

    s = load_pdb(pdb_in)
    seq, bb, rows = s.backbone_trace(return_indices=True)  # (L, 3, 3)
    if len(seq) == 0:
        raise SystemExit(
            f"no complete N/CA/C backbone residues found in {pdb_in} "
            "(CA-only traces cannot be relaxed)"
        )
    flat = bb.reshape(1, -1, 3)
    result = jax.jit(lambda c: fast_relax(c, iters=iters))(flat)
    e0 = float(result.energy_history[0, 0])
    e1 = float(result.energy[0])
    print(f"native relax: energy {e0:.2f} -> {e1:.2f} over {iters} iters")
    # scatter relaxed backbone back into the original structure: chains,
    # numbering, sidechains, and non-backbone atoms are preserved verbatim
    new_coords = s.coords.copy()
    new_coords[rows.reshape(-1)] = np.asarray(result.coords[0])
    Path(pdb_out).write_text(to_pdb_string(replace_coords(s, new_coords)))
    return pdb_out


def run_fast_relax(pdb_in: str, pdb_out: str, config_path: str | None = None) -> str:
    """FastRelax a structure (reference scripts/refinement.py:56-74 raises
    NotImplementedError after loading its config; same contract here when
    pyrosetta is absent — use ``--native`` / :func:`run_native_relax` for
    the dependency-free path)."""
    config = load_config(config_path)
    if not HAS_PYROSETTA:
        raise NotImplementedError(
            f"FastRelax needs pyrosetta (config loaded: {config}); "
            "run with --native for the dependency-free jnp relaxation"
        )
    pose = pdb_to_pose(pdb_in)
    scorefxn = pyrosetta.create_score_function(config["scorefxn"])
    relax = pyrosetta.rosetta.protocols.relax.FastRelax(scorefxn)
    relax.max_iter(int(config["max_iter"]))
    relax.constrain_relax_to_start_coords(
        bool(config["constrain_relax_to_start_coords"])
    )
    relax.apply(pose)
    return pose_to_pdb(pose, pdb_out)


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("pdb_in")
    ap.add_argument("pdb_out")
    ap.add_argument("--config", default=None)
    ap.add_argument("--native", action="store_true",
                    help="dependency-free jnp relaxation (utils/relax.py)")
    ap.add_argument("--iters", type=int, default=200)
    args = ap.parse_args()
    if args.native:
        if args.config is not None:
            ap.error("--config applies to the pyrosetta path, not --native")
        run_native_relax(args.pdb_in, args.pdb_out, iters=args.iters)
    else:
        if args.iters != 200:
            ap.error("--iters applies to --native; use --config for pyrosetta")
        run_fast_relax(args.pdb_in, args.pdb_out, config_path=args.config)
