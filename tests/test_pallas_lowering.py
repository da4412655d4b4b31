"""Pre-hardware Mosaic lowering gate.

The distinction between interpret-mode parity and compiled lowering bites
for real: the first on-chip Pallas attempt failed in Mosaic's block-mapping
check, an error no interpret-mode test can see. This test runs the full
Mosaic TPU lowering of every Pallas kernel entry point on the CPU host
(scripts/check_tpu_lowering.py: `.lower(lowering_platforms=("tpu",))` in a
subprocess pinned to the CPU backend — it only lowers, so it never loads the
TPU's library), so the NEXT tiling/layout violation is caught in CI, not on
a live chip. tests/test_chip_compile.py goes one step further and compiles
the same kernels for a described v5e.

The script includes its own negative control: a deliberately mis-tiled
(1, block) kernel — the exact bug class that died on the chip — must FAIL
to lower, or the gate reports failure. A green run therefore certifies both
that the kernels lower and that the gate can detect when they don't.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "scripts", "check_tpu_lowering.py")


def test_all_pallas_kernels_lower_for_tpu():
    proc = subprocess.run(
        [sys.executable, SCRIPT],
        capture_output=True, text=True, timeout=1200,
    )
    lines = [
        json.loads(l) for l in proc.stdout.splitlines() if l.startswith("{")
    ]
    summary = next((l for l in lines if l.get("gate")), None)
    assert proc.returncode == 0, (
        f"TPU lowering gate failed (rc={proc.returncode}):\n"
        + "\n".join(
            f"  {l['case']}: {l.get('error', 'ok')}"
            for l in lines if "case" in l and not l.get("ok")
        )
        + f"\nstderr tail: {proc.stderr[-1000:]}"
    )
    assert summary is not None and summary["failed"] == []
    cases = {l["case"] for l in lines if "case" in l}
    # the negative control must have actually run — a gate that silently
    # dropped it could go green without detecting anything
    assert "negative_control_rejects_bad_tiling" in cases
    assert {
        "block_sparse_fwd_n512", "block_sparse_bwd_n1024",
        "block_sparse_custom_vjp_n512", "flash_axial_256",
        "ring_flash_pair_from_msa", "ring_flash_msa_from_pair",
        "grouped_causal_core_16k", "grouped_window_core_16k",
        "grouped_causal_core_32_2_8k",
    } <= cases
