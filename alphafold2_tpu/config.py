"""Dataclass config tree + CLI parsing.

The reference has no config system at all — hyperparameters are module-level
constants edited in-source (train_pre.py:13-24, train_end2end.py:22-28,
constants.py:5-14) and model config is ctor kwargs (alphafold2.py:330-350).
SURVEY.md S5.6 calls for a real config system; this is it: typed dataclasses,
flat ``--section.field=value`` CLI overrides, JSON round-trip for
checkpointing reproducibility.
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Optional, Tuple


@dataclass
class ModelConfig:
    # which (model, loss, batch spec) train() builds: "alphafold2" (the axial
    # trunk, the fields below) | "mla_moe_lm" (the ``lm`` section) |
    # "swa_moe_lm" (the ``swa`` section) | "ssm_moe_lm" (the ``ssm`` section)
    # | "hybrid_dense_lm" (the ``hybrid`` section)
    arch: str = "alphafold2"
    dim: int = 256  # trunk embedding width (single-repr channels)
    max_seq_len: int = 2048  # positional-embedding table size (max residues)
    depth: int = 6  # trunk layers (MSA+pair block repeats)
    heads: int = 8  # attention heads per layer
    dim_head: int = 64  # per-head channel width
    attn_dropout: float = 0.0  # attention-prob dropout rate (train only)
    ff_dropout: float = 0.0  # feedforward dropout rate (train only)
    # exact erf GELU in the GEGLU feedforwards (the reference's torch
    # F.gelu); default False = tanh approximation, the faster form on TPU
    gelu_exact: bool = False
    remat: bool = False  # rematerialize trunk layers (memory for recompute)
    # remat checkpoint policy: None/"nothing" (save nothing — max memory
    # savings) | "dots" | "dots_no_batch" (save matmul outputs: backward
    # skips recomputing MXU-heavy ops — the memory/MFU trade)
    remat_policy: Optional[str] = None
    reversible: bool = False  # inversion-based O(1)-memory trunk engine
    sparse_self_attn: bool = False  # block-sparse axial self-attention
    cross_attn_compress_ratio: int = 1  # pair-token pooling for cross-attn
    msa_tie_row_attn: bool = False  # tie row-attention logits across MSA rows
    # shard the MSA-row axis over sp: the tied-row logit sum completes via
    # an XLA-inserted psum, scaling MSA depth across the mesh
    msa_row_shard: bool = False
    # sequence/context parallelism for the cross-attention over the N^2 pair
    # tokens: None | "ring" | "ulysses" (parallel/seq_parallel.py)
    context_parallel: Optional[str] = None
    # 2D-sharded pair axial attention over a (dp, spr, spc) grid mesh
    grid_parallel: bool = False
    # compile the trunk as ONE scanned layer with stacked params (compile
    # time independent of depth); needs homogeneous layers
    scan_layers: bool = False
    template_attn_depth: int = 2  # template pointwise-attention layers
    bfloat16: bool = True  # compute dtype on TPU
    # parameter init distributions: "flax" (lecun-normal Dense, N(0,1/dim)
    # embeddings) | "torch" (the reference's module defaults — see
    # models/init.py; incompatible with scan_layers' stacked params)
    init_scheme: str = "flax"


@dataclass
class LMConfig:
    """Decoder-only language model with latent attention (MLA) and
    sigmoid-routed experts (models/mla_moe_lm.py), read when ``model.arch``
    is ``mla_moe_lm``. The defaults are the published sizes of a
    DeepSeek-V3-shaped 30B-A3B model, whole; a chip's share of an
    expert-parallel layer (``experts_held``, ``first_expert``, a slice of the
    vocabulary) or fewer layers are for the caller to set."""

    vocab_size: int = 128256  # vocabulary rows held here (ids 0..vocab_size-1)
    hidden_size: int = 2048  # residual stream width
    num_layers: int = 48  # blocks, the leading dense ones included
    first_k_dense: int = 1  # leading blocks with a dense SwiGLU
    num_heads: int = 32  # attention heads
    qk_nope_head_dim: int = 128  # query/key head part without positions
    qk_rope_head_dim: int = 64  # query/key head part under rotary positions
    v_head_dim: int = 128  # value head width
    kv_lora_rank: int = 512  # width of the key/value latent
    intermediate_size: int = 6144  # dense SwiGLU width
    moe_intermediate_size: int = 768  # one expert's SwiGLU width
    n_routed_experts: int = 128  # the router's width, held or not
    n_shared_experts: int = 2  # run as one SwiGLU of n_shared x the width
    num_experts_per_tok: int = 6  # experts a token is routed to
    routed_scaling_factor: float = 2.448  # on the normalised routing weights
    rope_theta: float = 1e6  # rotary base
    rms_norm_eps: float = 1e-6  # inside every RMSNorm's rsqrt
    # the share: experts first_expert .. first_expert + experts_held - 1
    experts_held: int = 128  # routed experts this chip holds a layer
    first_expert: int = 0  # id of the first expert held
    bfloat16: bool = True  # compute dtype (weights stay float32)


@dataclass
class SwaLMConfig:
    """Decoder-only language model with grouped-query attention, one global
    layer without positions among window layers under rotary ones, and
    softmax-routed ReGLU experts chosen from the stream that enters the
    layer (models/swa_moe_lm.py), read when ``model.arch`` is
    ``swa_moe_lm``. The defaults are the published sizes of a
    SmallThinker-shaped 21B-A3B model, whole; the share (``experts_held``,
    ``first_expert``, a slice of the vocabulary) and fewer layers are for the
    caller to set, as in ``LMConfig``."""

    vocab_size: int = 151936  # vocabulary rows held here (ids 0..vocab_size-1)
    hidden_size: int = 2560  # residual stream width
    num_layers: int = 52  # blocks, every one an expert layer
    num_heads: int = 28  # query heads
    num_kv_heads: int = 4  # key/value heads: query head h reads h // (28 / 4)
    head_dim: int = 128  # width of every head
    sliding_window: int = 4096  # keys a window layer's query sees, its own too
    global_every: int = 4  # layer i is global (full causal, no positions)
    # where i % global_every == 0, a window layer under rotary ones otherwise
    moe_intermediate_size: int = 768  # one expert's ReGLU width
    n_routed_experts: int = 64  # the router's width, held or not
    num_experts_per_tok: int = 6  # experts a token is routed to
    rope_theta: float = 1.5e6  # rotary base
    rms_norm_eps: float = 1e-6  # inside every RMSNorm's rsqrt
    # the share: experts first_expert .. first_expert + experts_held - 1
    experts_held: int = 64  # routed experts this chip holds a layer
    first_expert: int = 0  # id of the first expert held
    bfloat16: bool = True  # compute dtype (weights stay float32)


@dataclass
class SsmLMConfig:
    """Decoder-only hybrid language model whose layer is one norm and one
    mixer, the mixer's kind read from ``layer_pattern``: a Mamba-2
    state-space mixer (``M``), sigmoid-routed ungated relu^2 experts beside
    one shared expert (``E``), or full causal grouped-query attention without
    positions (``*``) (models/ssm_moe_lm.py), read when ``model.arch`` is
    ``ssm_moe_lm``. The defaults are the published sizes of a
    Nemotron-H-shaped 30B-A3B model, whole; the share (``experts_held``,
    ``first_expert``, a slice of the vocabulary) and fewer layers are for the
    caller to set, as in ``LMConfig``. The attention layer's softmax scale
    is ``head_dim ** -0.5`` (``GroupedAttention``'s default: this section
    has no multiplier) and the head is a table of its own, untied; the
    dense hybrid's section, ``HybridDenseLMConfig``, is where a scale and a
    tie come from the configuration."""

    vocab_size: int = 131072  # vocabulary rows held here (ids 0..vocab_size-1)
    hidden_size: int = 2688  # residual stream width
    num_layers: int = 52  # layers run: the pattern's first num_layers entries
    # one character a layer: M state-space, E experts, * attention
    layer_pattern: str = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    mamba_num_heads: int = 64  # state-space heads
    mamba_head_dim: int = 64  # width of a state-space head (inner 64 x 64)
    ssm_groups: int = 8  # groups sharing B and C: head h reads h // (64 / 8)
    ssm_state_size: int = 128  # state rows a head
    conv_kernel: int = 4  # taps of the causal depthwise convolution
    chunk_size: int = 128  # steps a chunk of the chunked scan (ops/ssm.py)
    time_step_min: float = 0.001  # dt_bias starts at softplus^-1 of a time
    time_step_max: float = 0.1  # step drawn log-uniform between these two
    time_step_floor: float = 1e-4  # and floored at this
    num_heads: int = 32  # attention query heads
    num_kv_heads: int = 2  # key/value heads: query head h reads h // (32 / 2)
    head_dim: int = 128  # width of every attention head
    moe_intermediate_size: int = 1856  # one routed expert's width
    moe_shared_expert_intermediate_size: int = 3712  # the shared expert's
    n_routed_experts: int = 128  # the router's width, held or not
    num_experts_per_tok: int = 6  # experts a token is routed to
    routed_scaling_factor: float = 2.5  # on the normalised routing weights
    rms_norm_eps: float = 1e-5  # inside every norm's rsqrt
    # the share: experts first_expert .. first_expert + experts_held - 1
    experts_held: int = 128  # routed experts this chip holds a layer
    first_expert: int = 0  # id of the first expert held
    bfloat16: bool = True  # compute dtype (weights stay float32)


@dataclass
class HybridDenseLMConfig:
    """Decoder-only dense hybrid language model: every layer is a mixer and
    then a gated MLP, each behind its own RMSNorm and each added to the
    stream times ``residual_multiplier``; the mixer is a Mamba-2 state-space
    mixer (``M``) or full causal grouped-query attention without positions
    (``*``), read from ``layer_pattern`` (models/hybrid_dense_lm.py), read
    when ``model.arch`` is ``hybrid_dense_lm``. The defaults are the
    published sizes of a Granite-4.0-H-shaped 3B model
    (``granitemoehybrid`` with no experts), whole. There is no share of
    experts to hold: a chip's cut is fewer layers (a pipeline stage) and a
    slice of the vocabulary, for the caller to set. The softmax scale is
    ``attention_multiplier`` and not ``head_dim ** -0.5`` (the model hands
    it to ``GroupedAttention``), and the output head is the embedding table
    itself (``logits = h E^T / logits_scaling``): there is no switch for
    either, the family publishes no untied or unscaled member."""

    vocab_size: int = 100352  # vocabulary rows held here (ids 0..vocab_size-1)
    hidden_size: int = 2048  # residual stream width
    num_layers: int = 40  # layers run: the pattern's first num_layers entries
    # one character a layer: M state-space, * attention (layer_types)
    layer_pattern: str = "MMMMM*MMMM" * 4
    intermediate_size: int = 8192  # the gated MLP's width, every layer
    mamba_num_heads: int = 64  # state-space heads
    mamba_head_dim: int = 64  # width of a state-space head (inner 64 x 64)
    ssm_groups: int = 1  # groups sharing B and C: one, read by all 64 heads
    ssm_state_size: int = 128  # state rows a head
    conv_kernel: int = 4  # taps of the causal depthwise convolution
    chunk_size: int = 256  # steps a chunk of the chunked scan (ops/ssm.py)
    time_step_min: float = 0.001  # dt_bias starts at softplus^-1 of a time
    time_step_max: float = 0.1  # step drawn log-uniform between these two
    time_step_floor: float = 1e-4  # and floored at this
    num_heads: int = 32  # attention query heads
    num_kv_heads: int = 8  # key/value heads: query head h reads h // (32 / 8)
    head_dim: int = 64  # width of every attention head
    embedding_multiplier: float = 12.0  # on the embedding row, entering
    residual_multiplier: float = 0.22  # on every mixer's and MLP's output
    attention_multiplier: float = 0.015625  # the softmax scale, 1 / 64
    logits_scaling: float = 8.0  # the logits are divided by this
    rms_norm_eps: float = 1e-5  # inside every norm's rsqrt
    bfloat16: bool = True  # compute dtype (weights stay float32)


@dataclass
class MeshConfig:
    data_parallel: int = 1  # dp axis size; -1 = fill with all devices
    seq_parallel: int = 1  # sp axis size (pair-map row sharding)
    # 2D pair-grid sharding (parallel/grid_parallel.py); both > 1 builds a
    # (dp, spr, spc) mesh instead of (dp, sp)
    grid_rows: int = 1  # spr axis (pair-row shards)
    grid_cols: int = 1  # spc axis (pair-col shards)


@dataclass
class DataConfig:
    crop_len: int = 128  # residues per crop (static shape)
    msa_depth: int = 5  # MSA rows per example
    msa_len: int = 64  # MSA row length (columns)
    batch_size: int = 1  # examples per training batch
    max_len_filter: int = 250  # drop chains longer than this (train_pre.py:47)
    min_len_filter: int = 16  # drop chains shorter than this
    # "synthetic" | "native" | "npz" | "sidechainnet" | "tokens" (data/tokens.py)
    source: str = "synthetic"
    casp_version: int = 12  # sidechainnet CASP release to load
    thinning: int = 30  # sidechainnet thinning percentage
    # source "tokens": batch_size sequences of seq_len ids, Zipf over the
    # vocabulary rows the model holds (lm.vocab_size)
    seq_len: int = 8192  # positions a sequence
    zipf_exponent: float = 1.0  # P(rank r) ~ r ** -zipf_exponent
    data_dir: Optional[str] = None  # on-disk dataset root for "npz"/"native"
    # feature stream fed beside the sequence (reference train_end2end.py:22-28
    # FEATURES): "msa" | "plm" (frozen PLM embeddings via data/plm.py) | "none"
    features: str = "msa"
    plm_provider: str = "hash"  # "hash" | "precomputed" | "esm"
    plm_path: Optional[str] = None  # .npz archive for "precomputed"


@dataclass
class ServeConfig:
    """Shape-bucketed batched inference (alphafold2_tpu/serve).

    Sequence lengths are padded up a geometric bucket ladder so the number
    of distinct compiled executables is bounded by ``len(buckets)`` instead
    of the number of distinct request lengths; requests sharing a bucket are
    batched up to ``max_batch`` with batch-dim padding (masked dummy slots)
    so each bucket compiles exactly one (bucket, max_batch) executable."""

    buckets: Tuple[int, ...] = (64, 96, 128, 192, 256)  # residues, ascending
    # mesh-gated long-chain rungs (e.g. 512,768,1024 — the crop-free
    # ladder): their O(N^2) pair state only fits per-device memory when
    # sharded, so ServeEngine REJECTS them without a device mesh and admits
    # them (appended above ``buckets``) when constructed with one
    long_buckets: Tuple[int, ...] = ()
    # requests fused per dispatch on the long-chain rungs (their per-request
    # memory is what the mesh exists to shard; batch multiplies it back)
    long_max_batch: int = 1
    max_batch: int = 4  # requests fused per dispatch (batch-dim padded)
    # pad partial chunks up to max_batch: one executable per bucket (the
    # serving default); False compiles one executable per seen chunk size
    pad_batches: bool = True
    msa_depth: int = 0  # synthesized MSA rows per request; 0 -> data.msa_depth
    mds_iters: int = 200  # structure-realization Guttman iterations
    # serving precision: "float32" (default — model.bfloat16 still governs
    # the TPU compute dtype exactly as before) | "bfloat16" (params cast to
    # bf16 at engine build + bf16 compute; numerically gated by the drift
    # bounds tests/test_precision.py pins, and fingerprinted as distinct
    # graph-contract targets so precision changes are explicit diffs)
    dtype: str = "float32"
    donate_buffers: bool = True  # donate per-request feature buffers to XLA
    return_distogram: bool = False  # ship (3L,3L,K) logits back per request
    # --- pipelined dispatch (serve/pipeline.py: PipelinedDispatcher) ---
    # batches in flight at once: the host stage featurizes + device_puts
    # batch N+1 while batch N computes and batch N-1's results fetch, so
    # the executable stays fed. 2 = classic double buffering; 0 disables
    # the pipeline (every dispatch runs the serial featurize->compute->
    # fetch path in the calling thread, pre-pipeline behavior)
    pipeline_depth: int = 2
    # admit a request arriving while its bucket's next formation is still
    # in the host stage into that in-flight batch (continuous batching)
    # instead of making it wait a full fill-or-dwell window
    inflight_admission: bool = True
    # --- async frontend (serve/scheduler.py: AsyncServeFrontend) ---
    queue_depth: int = 64  # bounded admission queue; full -> structured reject
    dwell_ms: float = 25.0  # max wait for batch fill before partial dispatch
    default_deadline_s: float = 0.0  # per-request deadline; 0 = none
    cache_size: int = 256  # (seq, seed)-keyed LRU result entries; 0 disables
    shed_watermark: float = 0.75  # queue fraction where low-priority sheds
    retry_failed: bool = True  # retry a failed dispatch on another executable
    # --- variant-scan fast lane (serve/cache.py FeatureCache + affinity) ---
    # featurized input trees kept in the content-addressed FeatureCache
    # (leaf-interned LRU over derivation keys); 0 disables the layer
    feature_cache_size: int = 128
    # featurize a point mutant of a cached parent by patching only the
    # columns its mutation touches (data.pipeline.featurize_delta) instead
    # of recomputing the whole tree — byte-identical to cold featurization
    delta_featurize: bool = True
    # pack same-parent mutants (edit-distance-1 family, or an explicit
    # ServeRequest.parent_id hint) into the same bucket formation so scan
    # traffic rides full near-zero-padding batches
    affinity_batching: bool = True


@dataclass
class TrainConfig:
    learning_rate: float = 3e-4  # train_pre.py:18
    num_steps: int = 100000  # train_pre.py:14 NUM_BATCHES
    gradient_accumulate_every: int = 16  # train_pre.py:16
    warmup_steps: int = 1000  # linear LR warmup steps before cosine decay
    weight_decay: float = 0.0  # AdamW decoupled weight decay
    seed: int = 0  # PRNG seed for params + data order
    log_every: int = 50  # steps between train-metric log lines
    checkpoint_every: int = 1000  # steps between checkpoint writes
    checkpoint_dir: Optional[str] = None  # checkpoint root; None disables
    keep_checkpoints: int = 3  # newest checkpoints retained (older pruned)
    profile_dir: Optional[str] = None  # jax.profiler trace output
    profile_steps: Tuple[int, int] = (10, 13)  # [start, end) profiled steps
    # observe.Tracer span output (Chrome trace-event JSONL, Perfetto-
    # loadable): per-step host-side spans beside the XLA profile above
    trace_events: Optional[str] = None
    # in-graph numerics telemetry (observe.numerics): "off" | "triage"
    # (per-parameter-group norms every step; on a non-finite-grad skip,
    # rerun the step fully tagged and report the first bad tensor) |
    # "full" (tagged activation stats on every step). AF2TPU_NUMERICS
    # env var overrides per run.
    numerics: str = "triage"


def _tuplify(section, name):
    """JSON round-trips tuples as lists; restore the tuple type so configs
    hash/compare consistently (executable-cache keys include buckets)."""
    value = getattr(section, name)
    if isinstance(value, list):
        setattr(section, name, tuple(value))
    return section


@dataclass
class Config:
    model: ModelConfig = field(default_factory=ModelConfig)  # architecture
    lm: LMConfig = field(default_factory=LMConfig)  # model.arch "mla_moe_lm"
    swa: SwaLMConfig = field(default_factory=SwaLMConfig)  # "swa_moe_lm"
    ssm: SsmLMConfig = field(default_factory=SsmLMConfig)  # "ssm_moe_lm"
    # model.arch "hybrid_dense_lm"
    hybrid: HybridDenseLMConfig = field(default_factory=HybridDenseLMConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)  # device mesh axes
    data: DataConfig = field(default_factory=DataConfig)  # dataset + features
    train: TrainConfig = field(default_factory=TrainConfig)  # optimizer loop
    serve: ServeConfig = field(default_factory=ServeConfig)  # inference plane

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), indent=2)

    def language_model(self):
        """The section of the language model that ``model.arch`` names: its
        ``vocab_size`` is what ``data.source`` "tokens" draws over."""
        return {"swa_moe_lm": self.swa, "ssm_moe_lm": self.ssm,
                "hybrid_dense_lm": self.hybrid}.get(self.model.arch, self.lm)

    @classmethod
    def from_json(cls, s: str) -> "Config":
        raw = json.loads(s)
        return cls(
            model=ModelConfig(**raw.get("model", {})),
            lm=LMConfig(**raw.get("lm", {})),
            swa=SwaLMConfig(**raw.get("swa", {})),
            ssm=SsmLMConfig(**raw.get("ssm", {})),
            hybrid=HybridDenseLMConfig(**raw.get("hybrid", {})),
            mesh=MeshConfig(**raw.get("mesh", {})),
            data=DataConfig(**raw.get("data", {})),
            train=_tuplify(TrainConfig(**raw.get("train", {})), "profile_steps"),
            serve=_tuplify(
                _tuplify(ServeConfig(**raw.get("serve", {})), "buckets"),
                "long_buckets",
            ),
        )

    def apply_overrides(self, overrides: list[str]) -> "Config":
        """Apply ``section.field=value`` strings (CLI) onto a copy."""
        cfg = dataclasses.replace(self)
        for item in overrides:
            key, _, value = item.partition("=")
            key = key.lstrip("-")
            section_name, _, field_name = key.partition(".")
            section = getattr(cfg, section_name)
            if not hasattr(section, field_name):
                raise KeyError(f"unknown config field {key!r}")
            current = getattr(section, field_name)
            if isinstance(current, bool):
                parsed = value.lower() in ("1", "true", "yes")
            elif isinstance(current, int):
                parsed = int(value)
            elif isinstance(current, float):
                parsed = float(value)
            elif isinstance(current, tuple):
                # comma-separated ints, e.g. --serve.buckets=64,128,256
                parsed = tuple(int(v) for v in value.split(",") if v)
            else:
                parsed = value
            setattr(section, field_name, parsed)
        return cfg


def parse_cli(argv: list[str], base: Optional[Config] = None) -> Config:
    cfg = base or Config()
    return cfg.apply_overrides([a for a in argv if "=" in a])
