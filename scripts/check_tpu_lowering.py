"""Pre-hardware TPU lowering gate — thin shim over analysis/lowering.py.

The gate's substance (every Pallas kernel entry point lowered through the
full Mosaic pipeline on a CPU host, plus the mis-tiled negative control)
lives in :mod:`alphafold2_tpu.analysis.lowering`, where the jaxpr auditor
folds it into the same findings stream (``python -m
alphafold2_tpu.analysis.jaxpr_audit --rules lowering``). Lowering needs no
backend: a CPU process lowers for the tpu platform purely in Python.

Run directly or via tests/test_pallas_lowering.py:

    python scripts/check_tpu_lowering.py          # exit 0 = gate green

Prints one JSON line per case; exit 0 iff every positive case lowers AND
the negative control is rejected.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    # lowering only: keep this process off any attached accelerator
    os.environ["JAX_PLATFORMS"] = "cpu"
    from alphafold2_tpu.analysis.lowering import main

    sys.exit(main())
