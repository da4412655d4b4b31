"""Structure-math validation on real(istic) structures — the notebook, as a CLI.

The reference validates its structure utilities interactively against real PDB
entries (notebooks/structure_utils_tests.ipynb: load 1h22/4k77, perturb,
check Kabsch/RMSD/GDT/TMscore behavior, MDS round-trip a true distance
matrix). Same checks here, runnable and assertable:

    python scripts/validate_structure_math.py [--pdb path/to/file.pdb]

Without ``--pdb`` a protein-like synthetic chain is used (this image has no
network to fetch RCSB entries); with it, any real structure's CA trace drives
the exact notebook protocol. Exits non-zero if any check fails.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

# Host-side validation: run on CPU.
if not os.environ.get("AF2TPU_TEST_TPU"):
    os.environ["JAX_PLATFORMS"] = "cpu"

from alphafold2_tpu.utils import GDT, Kabsch, MDScaling, RMSD, TMscore, cdist
from alphafold2_tpu.utils import pdb as pdbio


def load_ca(pdb_path: str | None, length: int = 96) -> np.ndarray:
    if pdb_path is not None:
        seq, ca = pdbio.load_pdb(pdb_path).ca_trace()
        if len(seq) < 4:
            raise SystemExit(
                f"{pdb_path}: found {len(seq)} CA atoms — not a usable "
                "protein structure (need >= 4 residues)"
            )
        print(f"loaded {pdb_path}: {len(seq)} residues")
        return ca.T.astype(np.float32)  # (3, N)
    from alphafold2_tpu.data.pipeline import _smooth_walk

    ca = _smooth_walk(np.random.default_rng(7), length)
    print(f"synthetic chain: {length} residues")
    return ca.T.astype(np.float32)


def check(name: str, ok: bool, detail: str) -> bool:
    print(f"  [{'ok' if ok else 'FAIL'}] {name}: {detail}")
    return ok


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--pdb", default=None, help="optional .pdb file to validate on")
    args = ap.parse_args()

    rng = np.random.default_rng(0)
    ca = load_ca(args.pdb)  # (3, N)
    n = ca.shape[1]
    ok = True

    # --- Kabsch recovers an arbitrary rigid transform exactly (notebook cells
    # 8-13: rotate+translate, align, expect RMSD ~ 0, TM ~ 1) ---
    print("rigid-transform recovery:")
    theta = 0.9
    rot = np.array(
        [[np.cos(theta), -np.sin(theta), 0],
         [np.sin(theta), np.cos(theta), 0],
         [0, 0, 1.0]], np.float32,
    )
    moved = rot @ ca + np.asarray([[5.0], [-3.0], [2.0]], np.float32)
    a, b = Kabsch(moved, ca)
    r0 = float(RMSD(np.asarray(a), np.asarray(b))[0])
    tm0 = float(TMscore(np.asarray(a), np.asarray(b))[0])
    ok &= check("kabsch rmsd", r0 < 1e-3, f"rmsd={r0:.2e}")
    ok &= check("kabsch tmscore", tm0 > 0.9999, f"tm={tm0:.6f}")

    # --- perturbation monotonicity (notebook cells 14-22: metrics degrade
    # with noise scale; GDT_HA <= GDT_TS always) ---
    print("noise-scale monotonicity:")
    scales = [0.1, 0.5, 1.0, 2.0]
    rmsds, tms, gts, ghs = [], [], [], []
    for s in scales:
        noisy = ca + rng.normal(scale=s, size=ca.shape).astype(np.float32)
        a, b = Kabsch(noisy, ca)
        a, b = np.asarray(a), np.asarray(b)
        rmsds.append(float(RMSD(a, b)[0]))
        tms.append(float(TMscore(a, b)[0]))
        gts.append(float(GDT(a, b, mode="TS")[0]))
        ghs.append(float(GDT(a, b, mode="HA")[0]))
    for s, r, t, g, h in zip(scales, rmsds, tms, gts, ghs):
        print(f"    noise={s:>4}: rmsd={r:6.3f} tm={t:.3f} gdt_ts={g:.3f} gdt_ha={h:.3f}")
    ok &= check("rmsd increases", all(np.diff(rmsds) > 0), f"{rmsds}")
    ok &= check("tm decreases", all(np.diff(tms) < 0), f"{tms}")
    ok &= check("gdt_ts decreases", all(np.diff(gts) <= 0), f"{gts}")
    ok &= check("gdt_ha <= gdt_ts", all(h <= g for h, g in zip(ghs, gts)), "")

    # --- MDS round-trip: true distance matrix -> 3D -> align -> high TM
    # (notebook cells 23-27) ---
    print("MDS round-trip from the true distance matrix:")
    dist = np.asarray(cdist(ca.T[None], ca.T[None]))[0]  # (N, N)
    coords3d, stress = MDScaling(dist, iters=200, tol=1e-7, fix_mirror=False)
    rec = np.asarray(coords3d)[0]  # (3, N)
    best_tm, best_rmsd = -1.0, np.inf
    for cand in (rec, rec * np.asarray([[1.0], [1.0], [-1.0]], np.float32)):
        a, b = Kabsch(cand, ca)
        t = float(TMscore(np.asarray(a), np.asarray(b))[0])
        if t > best_tm:
            best_tm = t
            best_rmsd = float(RMSD(np.asarray(a), np.asarray(b))[0])
    final_stress = float(np.asarray(stress)[-1, 0])
    print(f"    final stress={final_stress:.4f} rmsd={best_rmsd:.3f} tm={best_tm:.3f}")
    ok &= check("mds tmscore", best_tm > 0.8, f"tm={best_tm:.3f}")
    ok &= check("mds rmsd", best_rmsd < 0.25 * n ** 0.5, f"rmsd={best_rmsd:.3f}")

    # --- PDB export round-trip of the reconstruction ---
    print("PDB export round-trip:")
    s = pdbio.backbone_to_pdb("A" * n, rec.T)
    back = pdbio.parse_pdb(pdbio.to_pdb_string(s))
    _, ca2 = back.ca_trace()
    ok &= check(
        "pdb roundtrip", bool(np.allclose(ca2.T, rec, atol=1e-3)),
        f"max err={np.abs(ca2.T - rec).max():.2e}",
    )

    print("ALL OK" if ok else "FAILURES PRESENT")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
