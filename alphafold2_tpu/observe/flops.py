"""Unified FLOPs/bytes accounting and MFU: the tree's ONE cost_analysis parser.

``compiled.cost_analysis()`` parsing used to be duplicated ad hoc in
``bench.py`` and a bisect script; every consumer (the train
bench, the serve engine's ``compile_records``, the train loop's metrics and
the microbenchmarks) now sources flops/bytes/MFU from here, so the peak
tables and the plausibility ceiling cannot drift apart between call sites.

jax is imported lazily (only where a device is actually consulted) so the
module rides along with ``alphafold2_tpu.observe`` imports in host-side
tools without touching a backend.
"""

from __future__ import annotations

from typing import Optional

# published peak dense bf16 FLOPs/s per chip (v5e's oft-quoted 394 is int8)
PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6e": 918e12,
}

def cost_analysis(compiled) -> dict:
    """Normalized XLA cost-analysis properties of a compiled executable.

    Returns ``{}`` when the backend exposes nothing (cost analysis is
    best-effort and must never break a measurement)."""
    try:
        cost = compiled.cost_analysis()
        return dict(cost) if cost else {}
    except Exception:
        return {}


def executable_costs(compiled) -> dict:
    """``{"flops": float|None, "bytes_accessed": float|None}`` for one
    compiled executable (None = the backend exposes no such count)."""
    cost = cost_analysis(compiled)
    flops = float(cost.get("flops", 0.0))
    bytes_accessed = float(cost.get("bytes accessed", 0.0))
    return {
        "flops": flops if flops > 0 else None,
        "bytes_accessed": bytes_accessed if bytes_accessed > 0 else None,
    }


def step_flops(compiled) -> Optional[float]:
    """The compiled program's own FLOP count from XLA cost analysis; None
    when the backend exposes none."""
    return executable_costs(compiled)["flops"]


def executable_memory(compiled) -> dict:
    """Per-device memory footprint of one compiled executable from XLA's
    ``memory_analysis()``: ``argument_bytes`` / ``output_bytes`` /
    ``temp_bytes`` (+ their sum ``program_bytes``). For SPMD programs these
    are PER-DEVICE numbers — exactly the quantity the pair-grid sharding
    exists to shrink, and what the serve compile records and the mesh
    regression gate key on. ``{}`` when the backend exposes nothing (the
    accounting must never break a measurement)."""
    try:
        ma = compiled.memory_analysis()
        out = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
        }
        out["program_bytes"] = sum(out.values())
        return out
    except Exception:
        return {}


def device_peak_flops(device=None) -> Optional[float]:
    """Published peak dense bf16 FLOPs/s of ``device`` (default: the first
    jax device). None on the host CPU, where no utilization is reported at
    all; an accelerator whose ``device_kind`` is not in ``PEAK_FLOPS`` is an
    error — a peak is looked up, never estimated."""
    if device is None:
        import jax

        device = jax.devices()[0]
    if device.platform == "cpu":
        return None
    kind = device.device_kind
    for k, v in PEAK_FLOPS.items():
        if k.lower() in kind.lower():
            return v
    raise ValueError(
        f"unknown device_kind {kind!r}: add its published peak (with the "
        "source) to observe.flops.PEAK_FLOPS before measuring on it"
    )


def mfu(
    flops: Optional[float],
    seconds: float,
    device=None,
    peak: Optional[float] = None,
    n_devices: int = 1,
) -> Optional[float]:
    """Model FLOPs utilization: ``flops / seconds / (peak * n_devices)``.
    None when the flop count is unknown or the run is on the host CPU."""
    if not flops or not seconds or seconds <= 0:
        return None
    peak = peak if peak is not None else device_peak_flops(device)
    if not peak:
        return None
    return flops / seconds / (peak * max(1, n_devices))


def estimate_mfu(compiled, step_seconds: float) -> Optional[float]:
    """MFU of one executed step of ``compiled`` taking ``step_seconds``."""
    return mfu(step_flops(compiled), step_seconds)


def attention_flops_attribution(
    *,
    batch: int,
    pair_len: int,
    msa_depth: int,
    msa_len: int,
    depth: int,
    heads: int,
    dim_head: int,
    tie_rows: bool = False,
    total_flops: Optional[float] = None,
) -> dict:
    """Per-kernel attribution of one trunk forward's attention FLOPs.

    XLA's ``cost_analysis`` reports one number for the whole executable;
    when MFU moves, nothing says WHICH attention shape is responsible. This
    is the analytical split (matmul FLOPs only, 2 flops per MAC, QK^T + AV
    per pass) over the trunk's attention families at the engine's static
    shapes — the same quantities the fused kernels target:

    - ``axial``: the two axial passes per layer over the (pair_len,
      pair_len) pair grid — 2 * 4 * B * N^3 * inner per layer, the N^2
      hot path.
    - ``tied_row``: the MSA row pass when rows are tied (the tied-row
      kernel's shape) — 4 * B * M * Nm^2 * inner per layer; attributed to
      ``msa_axial_untied`` instead when ``tie_rows`` is False.
    - ``msa_axial_untied``: the remaining MSA axial work (column pass, and
      the row pass when untied).
    - ``other``: ``total_flops`` minus the attention families (cross-attn,
      feedforwards, embeddings, realization) when a total is given.

    Shapes follow the serve engine's geometry: ``pair_len`` is the
    elongated token length (3 * bucket), ``msa_len`` the unelongated
    bucket. Purely analytical — never touches a backend."""
    inner = heads * dim_head
    axial = depth * 2 * 4.0 * batch * float(pair_len) ** 3 * inner
    msa_row = depth * 4.0 * batch * msa_depth * float(msa_len) ** 2 * inner
    msa_col = depth * 4.0 * batch * msa_len * float(msa_depth) ** 2 * inner
    out = {
        "axial": axial,
        "tied_row": msa_row if tie_rows else 0.0,
        "msa_axial_untied": (0.0 if tie_rows else msa_row) + msa_col,
    }
    if total_flops:
        out["other"] = max(0.0, float(total_flops) - sum(out.values()))
    return out
