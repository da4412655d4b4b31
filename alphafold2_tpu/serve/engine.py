"""Shape-bucketed, batched inference engine over the end-to-end predict path.

``predict.predict()`` traces and compiles a fresh XLA program per distinct
sequence length and serves one request at a time. This engine is the serving
layer the ROADMAP north star needs instead:

- **Bucketing** — request lengths pad up a geometric ladder
  (``serve.buckets``), so at most ``len(buckets)`` executables ever exist.
- **Batching** — requests sharing a bucket are fused to ``serve.max_batch``
  per dispatch; partial chunks are batch-dim padded with fully-masked dummy
  slots (``serve.pad_batches``), keeping one executable per bucket.
- **Masked padding end to end** — the token-validity mask flows through the
  trunk attention, the distogram realization (zero MDS weight on pairs
  touching padding + padding-blind chirality statistic, utils/mds.py) and
  the SE(3) refiner, so padded positions cannot distort valid coordinates;
  the position-keyed MDS init makes the valid-region solve independent of
  bucket shape and batch slot.
- **Compile accounting** — an in-process executable cache (fronting the
  persistent XLA compilation cache wired in ``alphafold2_tpu/__init__``)
  counts traces/compiles/cache-hits through an ``observe.EventCounters``
  hook, so tests can assert "N mixed-length requests in one bucket ==
  exactly 1 compile" instead of trusting it.
- **Observability** — every request rides through nested ``observe.Tracer``
  spans (featurize → get_executable/compile → dispatch → device_get →
  unpad) emitted as Chrome-trace-event JSONL; per-request queue-wait and
  dispatch latency, batch occupancy and pad ratio stream into
  ``observe.Histogram`` distributions (p50/p95/p99 in ``bench.py --mode
  serve`` records); compile durations are recorded per (bucket, batch)
  shape in ``compile_records``.
"""

from __future__ import annotations

import dataclasses
import re
import threading
import time
import warnings
from contextlib import nullcontext
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from alphafold2_tpu import constants
from alphafold2_tpu.config import Config
from alphafold2_tpu.data.pipeline import (
    featurize_bucketed_with_plan,
    featurize_delta,
)
from alphafold2_tpu.observe import (
    EventCounters,
    Histogram,
    MemorySampler,
    TraceContext,
    Tracer,
)
from alphafold2_tpu.observe import flightrec
from alphafold2_tpu.observe.flops import (
    attention_flops_attribution,
    executable_costs,
    executable_memory,
)
from alphafold2_tpu.parallel.sharding import (
    DATA_AXIS,
    describe_mesh,
    use_mesh,
)
from alphafold2_tpu.predict import encode_sequence
from alphafold2_tpu.serve.bucketing import bucket_for, validate_ladder
from alphafold2_tpu.serve.cache import FeatureCache, feature_key
from alphafold2_tpu.train.end2end import End2EndModel


@dataclasses.dataclass
class ServeRequest:
    """One inference request. ``seed`` drives the synthesized-MSA sampling
    (and nothing else), so identical (seq, seed) requests are reproducible
    whatever bucket or batch slot they land in.

    ``arrival_s`` is the request's own arrival timestamp on the
    ``time.perf_counter`` timebase: when present, queue-wait accounting is
    per request instead of per stream (requests dispatched in a later
    bucket no longer accrue earlier buckets' dispatch time as "queue
    wait"). The async frontend (serve/scheduler.py) stamps it at submit;
    ``priority`` and ``deadline_s`` (relative seconds, 0/None = none) are
    likewise scheduler inputs that ride with the request.

    ``trace`` is the request's :class:`~alphafold2_tpu.observe.tracectx.
    TraceContext`, minted at construction when the caller doesn't hand one
    in (an external frontend propagating a W3C traceparent would) — so
    every request owns a trace_id from birth and every lifecycle event the
    scheduler/engine emit is attributable to it."""

    seq: str
    seed: int = 0
    arrival_s: Optional[float] = None
    priority: int = 0
    deadline_s: Optional[float] = None
    # variant-scan hint: requests carrying the same parent_id belong to one
    # mutant family — the scheduler packs them into the same bucket
    # formation (parent-affinity batching) without having to rediscover the
    # family by edit distance. Optional: edit-distance-1 detection against
    # recent traffic covers unhinted scans.
    parent_id: Optional[str] = None
    trace: Optional[TraceContext] = dataclasses.field(
        default=None, compare=False, repr=False
    )

    def __post_init__(self):
        if self.trace is None:
            self.trace = TraceContext.new()


@dataclasses.dataclass
class ServeResult:
    """One request's outcome. ``status`` is one of the structured failure
    classes: ``"ok"`` (arrays populated), ``"error"`` (dispatch raised —
    converted, never propagated, so a batch partner's poison pill cannot
    crash the caller), ``"rejected"`` (admission control turned the request
    away; ``retry_after_s`` hints when to come back), or
    ``"deadline_exceeded"`` (the request's deadline passed while queued).
    Non-``ok`` results carry ``None`` arrays and an ``error`` message."""

    seq: str
    bucket: int
    atom14: Optional[np.ndarray] = None  # (L, 14, 3) refined all-atom coords
    backbone: Optional[np.ndarray] = None  # (L, 3, 3) N/CA/C
    weights: Optional[np.ndarray] = None  # (3L, 3L) distogram confidence
    distogram: Optional[np.ndarray] = None  # (3L, 3L, K) logits if requested
    latency_s: float = 0.0  # queue wait + dispatch: what a caller observes
    queue_wait_s: float = 0.0  # time between arrival and dispatch start
    dispatch_s: float = 0.0  # device execution + result fetch of the batch
    status: str = "ok"  # "ok" | "error" | "rejected" | "deadline_exceeded"
    error: Optional[str] = None  # failure detail for non-"ok" statuses
    retry_after_s: Optional[float] = None  # backoff hint on "rejected"
    cache_hit: bool = False  # served from the result cache / in-flight dedup
    retried: bool = False  # produced by the scheduler's retry dispatch
    trace_id: Optional[str] = None  # the owning request's trace identity
    # featurization-reuse ledger entry: how this request's input tree was
    # produced — "miss" (cold featurize), "hit" (FeatureCache), "delta"
    # (column-patched from a cached parent). None on non-dispatched
    # results (rejected / deadline / result-cache hits).
    feat_reuse: Optional[str] = None
    # per-request cost ledger: the request's even share of the batch it
    # rode in — queue_wait_s, device_share_s (dispatch wall over real
    # members), compile_share_s (executable compile seconds amortized
    # over that executable's dispatches so far, then split), flops_share
    # (analytic executable flops over real members), pad_fraction (the
    # batch rectangle's padded slots+residues fraction). None on
    # non-dispatched results.
    cost: Optional[dict] = None

    @property
    def ok(self) -> bool:
        return self.status == "ok"


def _as_request(r: Union[str, ServeRequest]) -> ServeRequest:
    return r if isinstance(r, ServeRequest) else ServeRequest(seq=r)


class ServeEngine:
    """Synchronous bucketed/batched inference engine.

    >>> engine = ServeEngine(cfg)
    >>> results = engine.predict_many(["ACDEFGH...", "MKV..."])

    ``counters`` (observe.EventCounters) accumulates:
    ``serve.requests``, ``serve.batches``, ``serve.traces`` (python trace
    executions), ``serve.compiles`` (XLA executable builds),
    ``serve.cache_hits`` (dispatches served by an already-built
    executable), ``serve.padded_slots`` / ``serve.padded_residues``
    (batch-dim / length-dim padding waste).

    ``tracer`` (observe.Tracer) receives the request-lifecycle spans; the
    default is a disabled tracer (near-zero overhead). ``histograms``
    (name -> observe.Histogram) streams ``latency_s`` / ``queue_wait_s`` /
    ``dispatch_s`` (seconds) and ``batch_occupancy`` / ``pad_ratio``
    (fractions); ``compile_records`` lists every XLA build as
    ``{"bucket", "batch", "seconds"}``.
    """

    def __init__(
        self,
        cfg: Config,
        params=None,
        checkpoint_dir: Optional[str] = None,
        counters: Optional[EventCounters] = None,
        tracer: Optional[Tracer] = None,
        faults=None,
        mesh: Optional[Mesh] = None,
    ):
        # faults: an optional serve.faults.FaultPlan consulted at the top of
        # every dispatch — the injection point that makes the scheduler's
        # retry and graceful-degradation paths testable
        self.faults = faults
        self.cfg = cfg
        # mesh: an optional jax device mesh ((dp, sp) from
        # parallel.sharding.make_mesh or (dp, spr, spc) from
        # parallel.grid_parallel.make_grid_mesh). With one, every
        # executable is AOT-compiled sharded (batch over dp, the pair grid
        # over the sequence axes via the model's shard_pair constraints)
        # and dispatch device_puts with explicit shardings; without one the
        # engine is the unchanged single-device path. The mesh identity is
        # part of the executable cache key, so one engine could in
        # principle be rebuilt against a different mesh without stale hits.
        self.mesh = mesh
        self.mesh_desc = describe_mesh(mesh)
        self.buckets = validate_ladder(cfg.serve.buckets)
        self.long_buckets: tuple = ()
        if cfg.serve.long_buckets:
            long = validate_ladder(cfg.serve.long_buckets)
            if mesh is None:
                # the mesh gate: long-chain rungs' O(N^2) pair state is
                # exactly what a single device cannot hold — refuse them
                # loudly instead of OOMing mid-dispatch
                raise ValueError(
                    f"serve.long_buckets={long} require a device mesh: "
                    "the long-chain rungs are mesh-gated (construct "
                    "ServeEngine with mesh=..., e.g. "
                    "parallel.grid_parallel.make_grid_mesh)"
                )
            if long[0] <= self.buckets[-1]:
                raise ValueError(
                    f"serve.long_buckets {long} must all exceed the top "
                    f"regular rung {self.buckets[-1]}"
                )
            self.long_buckets = long
            self.buckets = self.buckets + long
        self.max_batch = int(cfg.serve.max_batch)
        self.long_max_batch = int(cfg.serve.long_max_batch)
        if self.max_batch < 1:
            raise ValueError(f"serve.max_batch must be >= 1, got {self.max_batch}")
        if self.long_buckets and self.long_max_batch < 1:
            raise ValueError(
                f"serve.long_max_batch must be >= 1, got {self.long_max_batch}"
            )
        if 3 * self.buckets[-1] > cfg.model.max_seq_len:
            raise ValueError(
                f"largest bucket {self.buckets[-1]} elongates to "
                f"{3 * self.buckets[-1]} tokens > model.max_seq_len="
                f"{cfg.model.max_seq_len}; raise it or trim serve.buckets"
            )
        if mesh is not None:
            self._validate_mesh(mesh, cfg)
        self.msa_depth = int(cfg.serve.msa_depth or cfg.data.msa_depth)
        if self.msa_depth > constants.MAX_NUM_MSA:
            raise ValueError(
                f"serve msa_depth={self.msa_depth} exceeds MAX_NUM_MSA="
                f"{constants.MAX_NUM_MSA}"
            )
        # serving precision mode: "bfloat16" casts params at build (below)
        # and switches the compute dtype; proven against stated per-layer
        # drift bounds in tests/test_precision.py, fingerprinted as its own
        # graph-contract target (analysis/targets.py serve_fwd_bf16)
        self.serve_dtype = str(cfg.serve.dtype or "float32")
        if self.serve_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"serve.dtype must be 'float32' or 'bfloat16', got "
                f"{self.serve_dtype!r}"
            )
        self.counters = counters if counters is not None else EventCounters()
        self.tracer = tracer if tracer is not None else Tracer(enabled=False)
        self.memory = MemorySampler()
        self.histograms = {
            "latency_s": Histogram(),
            "queue_wait_s": Histogram(),
            "dispatch_s": Histogram(),
            "batch_occupancy": Histogram(),
            "pad_ratio": Histogram(),
        }
        self.compile_records: list = []
        # flops of every executed dispatch (observe.flops cost analysis of
        # the executable that carried it): the serve bench's MFU numerator.
        # The breakdown accumulates the analytical per-kernel attribution
        # (tied-row vs axial vs rest) so MFU deltas name the kernel.
        self.executed_flops: float = 0.0
        self.executed_flops_breakdown: dict = {}
        self._exe_flops: dict = {}
        self._exe_breakdown: dict = {}
        # per-executable compile seconds + dispatch counts: the cost
        # ledger's amortized-compile denominator (compile_s / dispatches,
        # so early dispatches carry more of the build than late ones)
        self._exe_compile_s: dict = {}
        self._exe_dispatches: dict = {}
        if self.serve_dtype == "bfloat16":
            compute_dtype = jnp.bfloat16
        else:
            compute_dtype = (
                jnp.bfloat16 if cfg.model.bfloat16 else jnp.float32
            )
        self.model = End2EndModel(
            dim=cfg.model.dim, depth=cfg.model.depth, heads=cfg.model.heads,
            dim_head=cfg.model.dim_head, max_seq_len=cfg.model.max_seq_len,
            mds_iters=cfg.serve.mds_iters,
            mds_per_position_init=True,
            remat=cfg.model.remat, msa_tie_row_attn=cfg.model.msa_tie_row_attn,
            context_parallel=cfg.model.context_parallel,
            grid_parallel=cfg.model.grid_parallel,
            dtype=compute_dtype,
        )
        self.params = self._init_params(params, checkpoint_dir)
        if self.serve_dtype == "bfloat16":
            # cast float params ONCE at build: weight memory halves and the
            # matmuls run bf16-in without per-dispatch casting. Checkpoints
            # stay f32 on disk; the cast is a serving-time decision whose
            # numerical safety observe/numerics drift bounds prove, not a
            # training-state change.
            self.params = jax.tree.map(
                lambda x: x.astype(jnp.bfloat16)
                if getattr(x, "dtype", None) == jnp.float32 else x,
                self.params,
            )
        self._mds_key = jax.random.key(cfg.train.seed)
        self._executables: dict = {}
        # the compile path and the flops accumulators are shared with the
        # pipeline's worker threads: double-checked locking on the
        # executable cache, a dedicated lock for executed-flops accounting
        self._compile_lock = threading.Lock()
        self._account_lock = threading.Lock()
        # params replicated onto the mesh once, reused by every sharded
        # dispatch (a sharded executable rejects differently-placed inputs)
        self._mesh_params = None
        # pipelined dispatch (serve/pipeline.py): depth batches in flight,
        # host featurize/device_put overlapping device compute overlapping
        # result fetch. 0 disables it (pure serial dispatch).
        self.pipeline_depth = int(cfg.serve.pipeline_depth)
        if self.pipeline_depth < 0:
            raise ValueError(
                f"serve.pipeline_depth must be >= 0, got {self.pipeline_depth}"
            )
        # variant-scan fast lane: content-addressed featurization reuse.
        # The FeatureCache holds featurized input trees keyed by their
        # derivation (seq, bucket, msa_depth, seed) with leaves interned by
        # content hash; delta featurization patches a point mutant's
        # columns out of a cached parent instead of recomputing the tree.
        fcap = int(cfg.serve.feature_cache_size)
        self.feature_cache = FeatureCache(fcap) if fcap > 0 else None
        self.delta_featurize = bool(cfg.serve.delta_featurize)
        self.pipeline = None
        if self.pipeline_depth > 0:
            from alphafold2_tpu.serve.pipeline import PipelinedDispatcher

            self.pipeline = PipelinedDispatcher(
                self, depth=self.pipeline_depth
            )

    @property
    def pipeline_desc(self) -> str:
        """The dispatch-path identity serve records carry (``"depth2"`` /
        ``"off"``) — regress.py refuses to compare across it, the same way
        mesh/dtype variants are fenced."""
        return (
            f"depth{self.pipeline_depth}" if self.pipeline is not None
            else "off"
        )

    def close(self) -> None:
        """Stop the pipeline stage workers (in-flight batches drain first)."""
        if self.pipeline is not None:
            self.pipeline.shutdown(wait=True)

    def _validate_mesh(self, mesh: Mesh, cfg: Config) -> None:
        from alphafold2_tpu.parallel.grid_parallel import (
            COL_AXIS_NAME,
            ROW_AXIS_NAME,
        )

        axes = dict(zip(mesh.axis_names, mesh.devices.shape))
        n_dp = axes.get(DATA_AXIS, 1)
        if self.max_batch % n_dp or (
            self.long_buckets and self.long_max_batch % n_dp
        ):
            raise ValueError(
                f"serve batch sizes (max_batch={self.max_batch}, "
                f"long_max_batch={self.long_max_batch}) must divide by the "
                f"mesh's dp axis ({n_dp}) for even batch sharding"
            )
        if ROW_AXIS_NAME in axes:
            if not cfg.model.grid_parallel:
                # same refusal as train/loop.py: without the sharded axial
                # primitive GSPMD all-gathers the attended axis and the
                # per-device memory win silently evaporates
                raise ValueError(
                    "a (dp, spr, spc) grid mesh requires "
                    "model.grid_parallel=true — without it the axial "
                    "passes run dense and the long-chain rungs lose their "
                    "O(N^2/(spr*spc)) per-device memory"
                )
            tile = axes[ROW_AXIS_NAME] * axes.get(COL_AXIS_NAME, 1)
            for b in self.buckets:
                if (3 * b) % tile:
                    raise ValueError(
                        f"bucket {b} elongates to {3 * b} pair rows, not "
                        f"divisible by the spr*spc tile ({tile}) the "
                        "all-to-all transposes need; adjust serve.buckets "
                        "or the mesh"
                    )

    def batch_for(self, bucket: int) -> int:
        """Dispatch batch size for one rung: long-chain rungs batch
        ``serve.long_max_batch`` (their per-request memory is what the mesh
        shards), everything else ``serve.max_batch``."""
        return (
            self.long_max_batch
            if bucket in self.long_buckets else self.max_batch
        )

    # ---------------------------------------------------------------- params

    def _init_params(self, params, checkpoint_dir):
        if params is not None:
            return params
        # params depend only on the model config, not the request length:
        # init at a tiny fixed shape (no bucket-sized init compile)
        n, m = 4, max(1, min(2, self.msa_depth))
        tiny = {
            "seq": np.zeros((1, n), np.int32),
            "mask": np.ones((1, n), bool),
            "msa": np.zeros((1, m, n), np.int32),
            "msa_mask": np.ones((1, m, n), bool),
        }
        if checkpoint_dir:
            from alphafold2_tpu.train.checkpoint import CheckpointManager

            def init_fn():
                return self.model.init(
                    jax.random.key(self.cfg.train.seed),
                    jnp.asarray(tiny["seq"]), jnp.asarray(tiny["msa"]),
                    mask=jnp.asarray(tiny["mask"]),
                    msa_mask=jnp.asarray(tiny["msa_mask"]),
                )

            template = jax.eval_shape(init_fn)
            mgr = CheckpointManager(checkpoint_dir)
            try:
                restored, _ = mgr.restore_params(template)
            finally:
                mgr.close()
            return restored
        return self.model.init(
            jax.random.key(self.cfg.train.seed),
            jnp.asarray(tiny["seq"]), jnp.asarray(tiny["msa"]),
            mask=jnp.asarray(tiny["mask"]),
            msa_mask=jnp.asarray(tiny["msa_mask"]),
        )

    # ----------------------------------------------------------- executables

    def _fwd(self, params, seq, msa, mask, msa_mask):
        # python side effect: runs once per TRACE, never per dispatch — the
        # compile-count tests pin the executable cache's behavior on it,
        # so the per-trace firing is the point, not a bug
        self.counters.bump("serve.traces")  # af2: noqa[AF2L009]
        out = self.model.apply(
            params, seq, msa, mask=mask, msa_mask=msa_mask,
            mds_key=self._mds_key, deterministic=True,
        )
        picked = {"refined": out["refined"], "weights": out["weights"]}
        if self.cfg.serve.return_distogram:
            picked["distogram"] = out["distogram"]
        return picked

    def _get_executable(self, bucket: int, batch: int):
        """One compiled executable per (bucket, batch, mesh) shape, AOT-
        built. The mesh identity in the key is what lets sharded and
        single-device executables (and their compile records) coexist.

        The in-process dict makes reuse O(1); the persistent XLA compilation
        cache behind it (enable_compile_cache) makes even the first build of
        a known HLO a deserialization instead of a compile."""
        key = self._exe_key(bucket, batch)
        hit = self._executables.get(key)
        if hit is not None:
            self.counters.bump("serve.cache_hits")
            return hit
        with self._compile_lock:
            return self._compile_executable(key, bucket, batch)

    def _compile_executable(self, key, bucket: int, batch: int):
        """Build + record one executable; caller holds ``_compile_lock``
        (the pipeline's device worker, the sync path and warmup can race
        to the same rung — exactly one of them compiles)."""
        hit = self._executables.get(key)
        if hit is not None:  # lost the race: the build already happened
            self.counters.bump("serve.cache_hits")
            return hit
        donate = (1, 2, 3, 4) if self.cfg.serve.donate_buffers else ()
        abstract = self._abstract_batch(bucket, batch)
        jit_kwargs: dict = {"donate_argnums": donate}
        if self.mesh is not None:
            # explicit input shardings: params replicated, every request
            # buffer batch-sharded over dp; the pair grid's sequence-axis
            # sharding comes from the model's shard_pair constraints traced
            # under the active mesh (parallel/sharding.py)
            rep = NamedSharding(self.mesh, P())
            dp = NamedSharding(self.mesh, P(DATA_AXIS))
            jit_kwargs["in_shardings"] = (rep, dp, dp, dp, dp)
        ctx = use_mesh(self.mesh) if self.mesh is not None else nullcontext()
        t0 = time.perf_counter()
        with self.tracer.span(
            "serve.compile", bucket=bucket, batch=batch,
            **({"mesh": self.mesh_desc} if self.mesh_desc else {}),
        ):
            # capture the compile's warnings instead of suppressing them
            # blind: the "Some donated buffers were not usable" notice is
            # expected (feature buffers are int/bool, outputs f32 coords —
            # XLA cannot ALIAS the donation; donating still lets the
            # runtime release the request buffers during execution, the
            # point on HBM-tight serving) and is STRUCTURED into the
            # compile record below so tests can assert the donation intent
            # actually reached XLA; everything else is re-emitted.
            with warnings.catch_warnings(record=True) as caught:
                warnings.simplefilter("always")
                with ctx:
                    compiled = (
                        jax.jit(self._fwd, **jit_kwargs)
                        .lower(self.params, *abstract)
                        .compile()
                    )
        donation_notes = [
            w for w in caught
            if "donated buffers were not usable" in str(w.message)
        ]
        for w in caught:
            if w not in donation_notes:
                warnings.warn_explicit(
                    w.message, w.category, w.filename, w.lineno
                )
        self.counters.bump("serve.compiles")
        costs = executable_costs(compiled)  # flops/bytes via observe.flops
        self._exe_flops[key] = costs["flops"] or 0.0
        memory = executable_memory(compiled)  # per-device, via observe.flops
        # analytical per-kernel attribution at this executable's static
        # shapes (observe.flops): names the kernel responsible for an MFU
        # delta — pair-axial vs tied-row MSA vs everything else
        breakdown = attention_flops_attribution(
            batch=batch, pair_len=3 * bucket, msa_depth=self.msa_depth,
            msa_len=bucket, depth=self.cfg.model.depth,
            heads=self.cfg.model.heads, dim_head=self.cfg.model.dim_head,
            tie_rows=self.model.msa_tie_row_attn,
            total_flops=costs["flops"],
        )
        self._exe_breakdown[key] = breakdown
        collectives: dict = {}
        if self.mesh is not None:
            # census of the post-SPMD collectives XLA actually emitted for
            # this rung (analysis/hlo_audit.py) — the runtime counterpart of
            # the committed hlo_contracts.json; a rung whose census is empty
            # here is paying for a mesh it does not use
            try:
                from alphafold2_tpu.analysis.hlo_audit import (
                    collective_census,
                )

                collectives = collective_census(compiled.as_text())
            except Exception:  # census is diagnostics, never a serve fault
                collectives = {}
        self._exe_compile_s[key] = round(time.perf_counter() - t0, 4)
        self.compile_records.append({
            "bucket": bucket, "batch": batch,
            "seconds": self._exe_compile_s[key],
            # donation audit: how many argument buffers we asked XLA to
            # donate, and how many shapes XLA reported back as unaliasable
            # (counted off the warning text) — a silently-dropped donation
            # would show up as donated_args without any unusable report
            # AND without aliasing, which tests/test_serve_pipeline.py pins
            **({"donated_args": len(donate)} if donate else {}),
            # jax lists them as "int32[2,8], bool[2,8], ..."
            **({"donation_unusable": len(re.findall(
                r"\w+\[[^\]]*\]", str(donation_notes[0].message)))}
               if donate and donation_notes else {}),
            **({"mesh": self.mesh_desc} if self.mesh_desc else {}),
            # the precision key rides only when non-default so records
            # (and the committed baselines) predating it stay comparable
            **({"dtype": self.serve_dtype}
               if self.serve_dtype != "float32" else {}),
            **({"flops": costs["flops"]} if costs["flops"] else {}),
            **({"flops_breakdown": breakdown} if costs["flops"] else {}),
            **({"bytes_accessed": costs["bytes_accessed"]}
               if costs["bytes_accessed"] else {}),
            **({"collectives": collectives} if collectives else {}),
            **memory,
        })
        self._executables[key] = compiled
        return compiled

    def _sharded_params(self):
        """The replicated-on-mesh copy of ``self.params`` every sharded
        executable consumes (built once, cached)."""
        if self._mesh_params is None:
            self._mesh_params = jax.device_put(
                self.params, NamedSharding(self.mesh, P())
            )
        return self._mesh_params

    def _abstract_batch(self, bucket: int, batch: int):
        f32 = jax.ShapeDtypeStruct
        return (
            f32((batch, bucket), jnp.int32),  # seq
            f32((batch, self.msa_depth, bucket), jnp.int32),  # msa
            f32((batch, bucket), jnp.bool_),  # mask
            f32((batch, self.msa_depth, bucket), jnp.bool_),  # msa_mask
        )

    # --------------------------------------------------- dispatch stages
    # Shared by the serial path (_dispatch_inner) and the pipelined path
    # (serve/pipeline.py stage workers), so the two produce byte-identical
    # results by construction — same featurize, same stacking, same
    # executable, same fetch.

    def _padded_batch(self, bucket: int, n_real: int) -> int:
        """Batch-dim size a chunk of ``n_real`` requests dispatches at:
        padded to the bucket's batch target (serve.pad_batches) and rounded
        up to the mesh's dp multiple for even batch sharding."""
        batch = (
            self.batch_for(bucket) if self.cfg.serve.pad_batches else n_real
        )
        if self.mesh is not None:
            n_dp = dict(
                zip(self.mesh.axis_names, self.mesh.devices.shape)
            ).get(DATA_AXIS, 1)
            batch += (-batch) % n_dp
        return batch

    # hamming-distance ceiling for the delta path: column patching is
    # exact at ANY same-length edit count (each touched column is O(M)),
    # but past a handful of edits the request is no longer "a mutant of"
    # the parent in any traffic sense, so treat it as cold
    DELTA_MAX_EDITS = 8

    def _featurize_one(self, bucket: int, req: ServeRequest) -> tuple:
        """Featurize one request, via the content-addressed fast lane when
        possible. Returns ``(item, reuse)`` with ``reuse`` the per-request
        ledger entry: ``"hit"`` (exact derivation-key cache hit),
        ``"delta"`` (column-patched from a cached same-shape parent —
        byte-identical to cold, pinned by tests), or ``"miss"`` (cold
        featurize). Every dispatched request bumps exactly one of
        ``serve.feat_hits`` / ``serve.feat_delta`` / ``serve.feat_misses``,
        so the ledger always sums to the dispatched-request count."""
        tokens = encode_sequence(req.seq)[0]
        pad = bucket - len(req.seq)
        self.counters.bump("serve.padded_residues", pad)
        self.histograms["pad_ratio"].observe(pad / bucket)
        fc = self.feature_cache
        if fc is None:
            item, _ = featurize_bucketed_with_plan(
                tokens, bucket, self.msa_depth, seed=req.seed
            )
            self.counters.bump("serve.feat_misses")
            return item, "miss"
        key = feature_key(req.seq, bucket, self.msa_depth, req.seed)
        found = fc.lookup(key)
        if found is not None:
            self.counters.bump("serve.feat_hits")
            return found[0], "hit"
        if self.delta_featurize:
            for p_item, p_plan in fc.delta_parent(
                bucket, self.msa_depth, req.seed, len(req.seq)
            ):
                edits = int((p_plan["tokens"] != tokens).sum())
                if 0 < edits <= self.DELTA_MAX_EDITS:
                    item = featurize_delta(p_item, p_plan, tokens)
                    # the mutant inherits the parent's plan verbatim apart
                    # from its own tokens: the MSA mutation mask depends
                    # only on (seed, msa_len, depth), never on sequence
                    # content, so the mutant is itself a valid delta parent
                    # (scan chains stay warm even after the original parent
                    # ages out of the LRU)
                    plan = dict(p_plan)
                    plan["tokens"] = tokens.copy()
                    item = fc.put(key, item, plan)
                    self.counters.bump("serve.feat_delta")
                    return item, "delta"
        item, plan = featurize_bucketed_with_plan(
            tokens, bucket, self.msa_depth, seed=req.seed
        )
        item = fc.put(key, item, plan)
        self.counters.bump("serve.feat_misses")
        return item, "miss"

    def _dummy_item(self, bucket: int) -> dict:
        """A fully-masked batch-padding slot."""
        return {
            "seq": np.full(bucket, constants.AA_PAD_INDEX, np.int32),
            "mask": np.zeros(bucket, bool),
            "msa": np.full(
                (self.msa_depth, bucket), constants.AA_PAD_INDEX, np.int32
            ),
            "msa_mask": np.zeros((self.msa_depth, bucket), bool),
        }

    def _stack_host(self, bucket: int, items: list, batch: int) -> dict:
        full = items + [
            self._dummy_item(bucket) for _ in range(batch - len(items))
        ]
        return {k: np.stack([it[k] for it in full]) for k in full[0]}

    def _transfer(self, host: dict, dispatch_index: int, bucket: int):
        """Explicit host->device transfer: handing raw numpy to the
        executable would be an implicit transfer, which the transfer-guard
        test fixtures (tests/conftest.py) and
        ``jax.transfer_guard("disallow")`` deployments reject. Under a mesh
        the transfer carries its sharding explicitly — batch split over dp
        at the host boundary, never an all-replicated copy that GSPMD
        reshards later."""
        if self.faults is not None:
            self.faults.on_stage("transfer", dispatch_index, bucket)
        if self.mesh is not None:
            dp = NamedSharding(self.mesh, P(DATA_AXIS))
            return {k: jax.device_put(a, dp) for k, a in host.items()}
        return jax.device_put(host)

    def _execute_batch(self, compiled, stacked, dispatch_index, bucket):
        """Invoke the executable; under async dispatch (CPU and TPU alike)
        the call returns while XLA executes in the background — blocking
        is the fetch stage's job."""
        if self.faults is not None:
            self.faults.on_stage("compute", dispatch_index, bucket)
        params = (
            self._sharded_params() if self.mesh is not None else self.params
        )
        return compiled(
            params, stacked["seq"], stacked["msa"],
            stacked["mask"], stacked["msa_mask"],
        )

    def _fetch(self, out, dispatch_index, bucket):
        """ONE blocking device_get of the whole output tree (one transfer
        issued, not three serial ones), closing on device completion."""
        if self.faults is not None:
            self.faults.on_stage("fetch", dispatch_index, bucket)
        fetched = jax.device_get(out)
        refined = np.asarray(fetched["refined"])
        weights = np.asarray(fetched["weights"])
        disto = (
            np.asarray(fetched["distogram"])
            if "distogram" in fetched else None
        )
        return refined, weights, disto

    def _exe_key(self, bucket: int, batch: int) -> tuple:
        return (bucket, batch, self.mesh_desc, self.serve_dtype)

    def _account_flops(self, exe_key) -> None:
        # executed-flops accumulators are shared with the pipeline's
        # completion worker, hence the lock
        with self._account_lock:
            self.executed_flops += self._exe_flops.get(exe_key, 0.0)
            self._exe_dispatches[exe_key] = (
                self._exe_dispatches.get(exe_key, 0) + 1
            )
            for kernel, flops in self._exe_breakdown.get(
                exe_key, {}
            ).items():
                self.executed_flops_breakdown[kernel] = (
                    self.executed_flops_breakdown.get(kernel, 0.0) + flops
                )

    def _request_cost(
        self, bucket: int, batch: int, n_real: int, real_residues: int,
        wait: float, dispatch_s: float,
    ) -> dict:
        """One request's even share of its batch — the per-request cost
        ledger (``ServeResult.cost``). Amortized compile uses this
        executable's compile seconds over its dispatch count SO FAR
        (``_account_flops`` runs first, so the divisor is >= 1): the first
        dispatch carries the whole build, the Nth carries 1/N of it."""
        exe_key = self._exe_key(bucket, batch)
        with self._account_lock:
            dispatches = max(1, self._exe_dispatches.get(exe_key, 1))
        compile_s = self._exe_compile_s.get(exe_key, 0.0)
        flops = self._exe_flops.get(exe_key, 0.0)
        rect = max(1, batch * bucket)
        return {
            "queue_wait_s": round(wait, 6),
            "device_share_s": round(dispatch_s / n_real, 6),
            "compile_share_s": round(compile_s / dispatches / n_real, 6),
            "flops_share": round(flops / n_real, 3),
            "pad_fraction": round(
                max(0, rect - real_residues) / rect, 4
            ),
        }

    def _build_results(
        self, bucket, reqs, waits, dispatch_s, refined, weights, disto,
        feat=None, batch=None,
    ) -> list:
        """Unpad/realize one batch's outputs into per-request results.
        ``feat`` (optional, slot-aligned) carries each request's
        featurization-reuse ledger entry onto its result; ``batch`` (the
        padded batch dimension) enables the per-request cost ledger."""
        built = []
        real_residues = sum(len(r.seq) for r in reqs)
        for slot, req in enumerate(reqs):
            L = len(req.seq)
            atom14 = refined[slot, :L]
            wait = max(0.0, waits[slot])
            latency = wait + dispatch_s
            self.histograms["latency_s"].observe(latency)
            built.append(ServeResult(
                seq=req.seq,
                bucket=bucket,
                atom14=atom14,
                backbone=atom14[:, :3],
                weights=weights[slot, : 3 * L, : 3 * L],
                distogram=(
                    disto[slot, : 3 * L, : 3 * L]
                    if disto is not None else None
                ),
                latency_s=latency,
                queue_wait_s=wait,
                dispatch_s=dispatch_s,
                trace_id=req.trace.trace_id if req.trace else None,
                feat_reuse=feat[slot] if feat is not None else None,
                cost=(
                    self._request_cost(
                        bucket, batch, len(reqs), real_residues,
                        wait, dispatch_s,
                    )
                    if batch else None
                ),
            ))
        return built

    def _error_results(self, bucket, reqs, waits, msg, dispatch_s) -> list:
        """Structured per-request error results for a failed batch (the
        scheduler retries them against a different executable)."""
        self.counters.bump("serve.dispatch_errors")
        rec = flightrec.active()
        if rec is not None:  # preserve the telemetry leading up to it
            rec.note(
                "dispatch_error", bucket=int(bucket), error=msg,
                n_real=len(reqs),
                trace_ids=[r.trace.trace_id for r in reqs if r.trace],
            )
            rec.dump("dispatch_error")  # once per process (deduped)
        return [
            ServeResult(
                seq=req.seq,
                bucket=bucket,
                status="error",
                error=msg,
                latency_s=max(0.0, waits[slot]) + dispatch_s,
                queue_wait_s=max(0.0, waits[slot]),
                dispatch_s=dispatch_s,
                trace_id=req.trace.trace_id if req.trace else None,
            )
            for slot, req in enumerate(reqs)
        ]

    # -------------------------------------------------------------- serving

    def predict_many(
        self, requests: Sequence[Union[str, ServeRequest]]
    ) -> list:
        """Serve a request list: group by bucket, batch, dispatch, unpad.

        Results come back in input order. Latency per request is the wall
        time of the dispatch that carried it (what a caller of a batched
        service observes)."""
        reqs = [_as_request(r) for r in requests]
        self.counters.bump("serve.requests", len(reqs))
        by_bucket: dict = {}
        for i, r in enumerate(reqs):
            if not r.seq:
                raise ValueError(f"request {i} has an empty sequence")
            b = bucket_for(len(r.seq), self.buckets)
            by_bucket.setdefault(b, []).append(i)

        results: list = [None] * len(reqs)
        arrival = time.perf_counter()  # queue-wait origin for this stream
        if self.pipeline is not None:
            # pipelined path: every chunk is submitted up front, so the
            # host stage featurizes/transfers batch N+1 while batch N
            # computes and batch N-1's results fetch; submit() blocks at
            # pipeline_depth in flight (backpressure), result() drains in
            # submission order
            handles = []
            for bucket in sorted(by_bucket):
                order = by_bucket[bucket]
                step = self.batch_for(bucket)
                for lo in range(0, len(order), step):
                    chunk = order[lo : lo + step]
                    handles.append((chunk, self.pipeline.submit(
                        bucket, [reqs[i] for i in chunk], arrival=arrival
                    )))
            for chunk, handle in handles:
                for idx, res in zip(chunk, handle.result()):
                    results[idx] = res
            return results
        for bucket in sorted(by_bucket):
            order = by_bucket[bucket]
            step = self.batch_for(bucket)
            for lo in range(0, len(order), step):
                chunk = order[lo : lo + step]
                self._dispatch(
                    bucket, [reqs[i] for i in chunk], chunk, results, arrival
                )
        return results

    def dispatch_batch(
        self, bucket: int, requests: Sequence[Union[str, ServeRequest]]
    ) -> list:
        """Dispatch one pre-formed batch at ``bucket`` and return its
        results in order. The async frontend (serve/scheduler.py) forms its
        own batches and calls this; per-request ``arrival_s`` stamps drive
        the queue-wait accounting. A dispatch failure yields structured
        ``status="error"`` results, never an exception."""
        reqs = [_as_request(r) for r in requests]
        results: list = [None] * len(reqs)
        self._dispatch(bucket, reqs, list(range(len(reqs))), results)
        return results

    def dispatch_batch_async(
        self,
        bucket: int,
        requests: Sequence[Union[str, ServeRequest]],
        joinable: bool = False,
    ):
        """Pipelined dispatch of one pre-formed batch: returns a
        :class:`~alphafold2_tpu.serve.pipeline.DispatchHandle` future over
        the ordered result list instead of blocking through featurize /
        compute / fetch. With ``joinable=True`` the batch stays open to
        ``handle.try_join(req)`` while its host stage runs — the
        scheduler's in-flight admission (continuous batching). Requires
        ``serve.pipeline_depth > 0``."""
        if self.pipeline is None:
            raise RuntimeError(
                "pipelined dispatch requires serve.pipeline_depth > 0"
            )
        return self.pipeline.submit(
            bucket, [_as_request(r) for r in requests], joinable=joinable
        )

    def retry_bucket(self, bucket: int) -> Optional[int]:
        """The next rung up the ladder — a *different* (bucket, batch)
        executable for the scheduler's retry-with-exclusion path — or None
        when ``bucket`` is already the largest rung."""
        i = self.buckets.index(bucket)
        return self.buckets[i + 1] if i + 1 < len(self.buckets) else None

    def _dispatch(self, bucket, chunk_reqs, chunk_idx, results, arrival=None):
        n_real = len(chunk_reqs)
        batch = self._padded_batch(bucket, n_real)
        dispatch_index = self.counters.bump("serve.batches")
        self.counters.bump("serve.padded_slots", batch - n_real)
        t_start = time.perf_counter()
        # per-request queue wait when the request carries its own arrival
        # stamp (the scheduler sets it at submit); the stream-level arrival
        # is the fallback for the synchronous predict_many path
        waits = []
        for r in chunk_reqs:
            origin = r.arrival_s if r.arrival_s is not None else arrival
            waits.append(t_start - origin if origin is not None else 0.0)
            self.histograms["queue_wait_s"].observe(max(0.0, waits[-1]))
        self.histograms["batch_occupancy"].observe(n_real / batch)

        try:
            self._dispatch_inner(
                bucket, batch, dispatch_index, chunk_reqs, chunk_idx,
                results, waits,
            )
        except Exception as e:  # noqa: BLE001 — converted, never swallowed
            # an exception mid-dispatch (device fault, injected fault, OOM)
            # must not leave the whole chunk's result slots as None with
            # counters already bumped: every request gets a structured
            # per-request error result the scheduler can retry against a
            # different (bucket, batch) executable
            msg = f"{type(e).__name__}: {e}"
            dispatch_s = time.perf_counter() - t_start
            errs = self._error_results(
                bucket, chunk_reqs, waits, msg, dispatch_s
            )
            for idx, res in zip(chunk_idx, errs):
                results[idx] = res

    def _dispatch_inner(
        self, bucket, batch, dispatch_index, chunk_reqs, chunk_idx, results,
        waits,
    ):
        n_real = len(chunk_reqs)
        if self.faults is not None:
            # fault-injection hook: may delay (simulating a slow device) or
            # raise (converted to structured error results by the caller)
            self.faults.on_dispatch(dispatch_index, bucket)
        member_traces = [r.trace.trace_id for r in chunk_reqs if r.trace]
        with self.tracer.span(
            "serve.batch", bucket=bucket, batch=batch, n_real=n_real,
            dispatch_index=dispatch_index,
            **({"trace_ids": member_traces} if member_traces else {}),
        ) as batch_span:
            with self.tracer.span(
                "serve.featurize", bucket=bucket,
                dispatch_index=dispatch_index,
            ):
                items, feat = [], []
                for r in chunk_reqs:
                    item, reuse = self._featurize_one(bucket, r)
                    items.append(item)
                    feat.append(reuse)
                host = self._stack_host(bucket, items, batch)
                stacked = self._transfer(host, dispatch_index, bucket)

            with self.tracer.span(
                "serve.get_executable", bucket=bucket, batch=batch
            ) as exe_span:
                before = self.counters.get("serve.compiles")
                compiled = self._get_executable(bucket, batch)
                exe_span.set(
                    compiled_now=self.counters.get("serve.compiles") > before
                )

            t0 = time.perf_counter()
            with self.tracer.span(
                "serve.dispatch", bucket=bucket,
                dispatch_index=dispatch_index,
                **({"mesh": self.mesh_desc} if self.mesh_desc else {}),
            ):
                out = self._execute_batch(
                    compiled, stacked, dispatch_index, bucket
                )
            # fetch the values, not just readiness: the timed region must
            # close on device completion (the bench's validity contract)
            with self.tracer.span(
                "serve.device_get", bucket=bucket,
                dispatch_index=dispatch_index,
            ):
                refined, weights, disto = self._fetch(
                    out, dispatch_index, bucket
                )
            dispatch_s = time.perf_counter() - t0
            batch_span.set(dispatch_s=round(dispatch_s, 4))
            self.histograms["dispatch_s"].observe(dispatch_s)
            self._account_flops(self._exe_key(bucket, batch))
            self.memory.counter_to(self.tracer)  # HBM beside the spans

            with self.tracer.span(
                "serve.unpad", bucket=bucket, dispatch_index=dispatch_index
            ):
                built = self._build_results(
                    bucket, chunk_reqs, waits, dispatch_s,
                    refined, weights, disto, feat=feat, batch=batch,
                )
            for idx, res in zip(chunk_idx, built):
                results[idx] = res

    # ------------------------------------------------- pipelined completion

    def _complete_pipelined(self, job) -> list:
        """Completion stage of the pipelined dispatch (runs on the fetch
        worker): accounting + unpad/realize into ordered ServeResults.
        Always returns one result per member — an error carried from any
        stage becomes structured per-request error results, so a poisoned
        batch cannot wedge the completion thread."""
        t_end = time.perf_counter()
        reqs = job.members
        t0 = job.t_device0 if job.t_device0 is not None else t_end
        dispatch_s = max(0.0, t_end - t0)
        # queue wait runs from arrival to DEVICE dispatch: under the
        # pipeline, host featurize/transfer is pre-device residency the
        # request observes as waiting, and wait + dispatch_s spans the
        # whole arrival->completion interval
        waits = []
        for r in reqs:
            origin = r.arrival_s if r.arrival_s is not None else job.arrival
            waits.append(t0 - origin if origin is not None else 0.0)
            self.histograms["queue_wait_s"].observe(max(0.0, waits[-1]))
        if job.error is not None:
            msg = f"{type(job.error).__name__}: {job.error}"
            return self._error_results(
                job.bucket, reqs, waits, msg, dispatch_s
            )
        self.histograms["batch_occupancy"].observe(
            job.n_real / job.batch_size
        )
        self.histograms["dispatch_s"].observe(dispatch_s)
        self._account_flops(self._exe_key(job.bucket, job.batch_size))
        self.memory.counter_to(self.tracer)
        refined, weights, disto = job.fetched
        with self.tracer.span(
            "serve.unpad", bucket=job.bucket, dispatch_index=job.index
        ):
            built = self._build_results(
                job.bucket, reqs, waits, dispatch_s, refined, weights,
                disto, feat=job.feat, batch=job.batch_size,
            )
        member_traces = [r.trace.trace_id for r in reqs if r.trace]
        # the batch span is retroactive (its start predates this thread's
        # involvement); explicit bounds keep the Chrome timeline honest
        self.tracer.span_event(
            "serve.batch",
            job.t_host0 if job.t_host0 is not None else t0, t_end,
            bucket=job.bucket, batch=job.batch_size, n_real=job.n_real,
            dispatch_index=job.index, dispatch_s=round(dispatch_s, 4),
            pipelined=True,
            **({"trace_ids": member_traces} if member_traces else {}),
        )
        return built

    def _completion_fallback(self, job) -> list:
        """Last-resort error results if completion itself raised — the
        future always resolves with one result per member."""
        msg = f"{type(job.error).__name__}: {job.error}"
        return [
            ServeResult(
                seq=req.seq, bucket=job.bucket, status="error", error=msg,
                trace_id=req.trace.trace_id if req.trace else None,
            )
            for req in job.members
        ]

    def warmup(self) -> dict:
        """Compile every ladder rung ahead of traffic (one dummy dispatch
        per bucket). Returns the counter snapshot afterwards."""
        for bucket in self.buckets:
            self._get_executable(bucket, self._padded_batch(bucket, 1))
        return self.counters.snapshot()

    def stats(self) -> dict:
        return self.counters.snapshot()

    def histogram_snapshots(self, unit_scale: float = 1.0) -> dict:
        """One summary dict per latency/occupancy distribution; the time
        histograms (``*_s``) are scaled by ``unit_scale`` (1e3 → ms)."""
        return {
            name: h.snapshot(
                unit_scale=unit_scale if name.endswith("_s") else 1.0,
                digits=4,
            )
            for name, h in self.histograms.items()
        }
