"""Training: losses, state, pjit-sharded train step, distogram pretraining.

Capability target: reference ``train_pre.py`` (distogram pretraining loop:
cross-entropy vs bucketed CA distances with ignore_index -100, Adam 3e-4,
gradient accumulation 16 — train_pre.py:13-24, 66-95) re-designed TPU-first:

- the whole step (forward, loss, backward, optimizer) is ONE jitted program
  laid out over a (dp, sp) mesh; batch enters data-parallel-sharded, params
  and optimizer state are replicated, pair activations are row-sharded via
  the constraints in parallel/sharding.py — XLA inserts the psum for the
  gradient all-reduce (the reference is strictly single-device, SURVEY.md
  S2.3)
- gradient accumulation uses optax.MultiSteps (single compiled step instead
  of a python accumulation loop)
- bfloat16 compute / float32 params + optimizer
- failure handling the reference lacks (SURVEY.md S5.3): NaN/Inf gradients
  are detected in-graph and the step is skipped (state update suppressed).
"""

from __future__ import annotations

import dataclasses
from contextlib import nullcontext
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import optax
from flax.training import train_state
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from alphafold2_tpu.config import Config
from alphafold2_tpu.models.alphafold2 import Alphafold2
from alphafold2_tpu.observe import numerics
from alphafold2_tpu.parallel.sharding import DATA_AXIS, use_mesh
from alphafold2_tpu.utils.structure import get_bucketed_distance_matrix


class TrainState(train_state.TrainState):
    """Adds a monotone count of skipped (non-finite-gradient) steps."""

    skipped: jnp.ndarray = None  # scalar int32


def distogram_cross_entropy(
    logits: jnp.ndarray, labels: jnp.ndarray, ignore_index: int = -100
) -> jnp.ndarray:
    """Mean CE over non-ignored pairs (reference train_pre.py:84-87)."""
    valid = labels != ignore_index
    safe_labels = jnp.where(valid, labels, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, safe_labels[..., None], axis=-1)[..., 0]
    # numerics tag (no-op unless collection is active): the loss is the
    # last forward tensor, so a first-NaN here means the loss itself, not
    # the trunk, went bad
    nll = numerics.tag("loss.distogram_nll", nll)
    # explicit bool->float cast: bool*float is an implicit promotion the
    # strict-promotion audit (analysis/jaxpr_audit.py AF2A105) forbids
    validf = valid.astype(nll.dtype)
    return jnp.sum(nll * validf) / jnp.maximum(jnp.sum(validf), 1.0)


def apply_features(data_iter, cfg: Config):
    """Adapt the batch stream to data.features: "msa" (as-is), "plm" (frozen
    PLM embeddings replace the MSA — reference train_end2end.py FEATURES),
    or "none" (sequence only)."""
    if cfg.data.features == "plm":
        from alphafold2_tpu.data.plm import make_provider, wrap_with_embeddings

        provider = make_provider(
            cfg.data.plm_provider, path=cfg.data.plm_path, seed=cfg.train.seed
        )
        return wrap_with_embeddings(data_iter, provider)
    if cfg.data.features == "none":
        return (
            {k: v for k, v in b.items() if k not in ("msa", "msa_mask")}
            for b in data_iter
        )
    if cfg.data.features != "msa":
        raise ValueError(f"unknown data.features {cfg.data.features!r}")
    return data_iter


def build_model(cfg: Config) -> Alphafold2:
    m = cfg.model
    return Alphafold2(
        dim=m.dim,
        max_seq_len=m.max_seq_len,
        depth=m.depth,
        heads=m.heads,
        dim_head=m.dim_head,
        attn_dropout=m.attn_dropout,
        ff_dropout=m.ff_dropout,
        gelu_exact=m.gelu_exact,
        remat=m.remat,
        remat_policy=m.remat_policy,
        reversible=m.reversible,
        sparse_self_attn=m.sparse_self_attn,
        cross_attn_compress_ratio=m.cross_attn_compress_ratio,
        msa_tie_row_attn=m.msa_tie_row_attn,
        msa_row_shard=m.msa_row_shard,
        context_parallel=m.context_parallel,
        grid_parallel=m.grid_parallel,
        scan_layers=m.scan_layers,
        template_attn_depth=m.template_attn_depth,
        dtype=jnp.bfloat16 if m.bfloat16 else jnp.float32,
    )


@dataclasses.dataclass(frozen=True)
class Task:
    """What the loop needs of a model: the model, its loss and its batch
    spec, chosen together by the configuration (:func:`build_task`).
    ``make_train_step``, ``make_triage_step`` and the init functions take a
    Task wherever they take a model; a bare model is the trunk's."""

    model: Any
    # (params, batch, rng) -> outputs, dropout on
    forward: Callable[[Any, dict, jax.Array], Any]
    loss: Callable[[Any, dict], jnp.ndarray]  # (outputs, batch), scope "loss"
    metrics: Callable[[Any], dict]  # outputs -> what rides beside the loss
    init: Callable[[jax.Array, dict], Any]  # (rng, batch) -> params
    tiny_batch: Callable[[dict], dict]  # a real batch cut down for init

    @property
    def apply(self):
        return self.model.apply


def trunk_task(model: Alphafold2) -> Task:
    """Distogram pretraining of the axial trunk: the default triple."""

    def forward(params, batch, rng):
        return model.apply(
            params,
            batch["seq"],
            batch.get("msa"),
            mask=batch["mask"],
            msa_mask=batch.get("msa_mask"),
            embedds=batch.get("embedds"),  # frozen-PLM feature path
            deterministic=False,
            rngs={"dropout": rng},
        )

    def metrics(logits):
        return {
            "distogram_entropy": -jnp.mean(
                jnp.sum(
                    jax.nn.softmax(logits, -1) * jax.nn.log_softmax(logits, -1),
                    -1,
                )
            )
        }

    def init(rng, batch):
        def opt(key):
            v = batch.get(key)
            return jnp.asarray(v) if v is not None else None

        return model.init(
            rng,
            jnp.asarray(batch["seq"]),
            opt("msa"),
            mask=jnp.asarray(batch["mask"]),
            msa_mask=opt("msa_mask"),
            embedds=opt("embedds"),
        )

    return Task(model, forward, _scoped_loss, metrics, init, tiny_batch_like)


def as_task(model) -> Task:
    return model if isinstance(model, Task) else trunk_task(model)


def build_task(cfg: Config) -> Task:
    """The (model, loss, batch spec) that ``model.arch`` names. Another
    architecture's modules are imported only when asked for."""
    if cfg.model.arch == "alphafold2":
        return trunk_task(build_model(cfg))
    if cfg.model.arch == "dsa_moe_lm":
        from alphafold2_tpu.models import dsa_moe_lm as dsa_lm
        from alphafold2_tpu.models import mla_moe_lm as lm

        model = dsa_lm.DsaMoeLM(cfg.dsa)
        return Task(model, partial(lm.forward, model),
                    partial(dsa_lm.loss, weight=cfg.dsa.index_loss_weight),
                    lm.step_metrics, partial(lm.init, model),
                    dsa_lm.tiny_batch)
    if cfg.model.arch in ("mla_moe_lm", "swa_moe_lm", "ssm_moe_lm",
                          "hybrid_dense_lm"):
        from alphafold2_tpu.models import mla_moe_lm as lm

        if cfg.model.arch == "mla_moe_lm":
            model = lm.MlaMoeLM(cfg.lm)
        elif cfg.model.arch == "swa_moe_lm":
            from alphafold2_tpu.models.swa_moe_lm import SwaMoeLM

            model = SwaMoeLM(cfg.swa)
        elif cfg.model.arch == "ssm_moe_lm":
            from alphafold2_tpu.models.ssm_moe_lm import SsmMoeLM

            model = SsmMoeLM(cfg.ssm)
        else:
            from alphafold2_tpu.models.hybrid_dense_lm import HybridDenseLM

            model = HybridDenseLM(cfg.hybrid)
        return Task(model, partial(lm.forward, model), lm.loss,
                    lm.step_metrics, partial(lm.init, model), lm.tiny_batch)
    raise ValueError(
        f"unknown model.arch {cfg.model.arch!r}; expected 'alphafold2', "
        "'mla_moe_lm', 'swa_moe_lm', 'ssm_moe_lm', 'hybrid_dense_lm' or "
        "'dsa_moe_lm'")


def build_optimizer(cfg: Config) -> optax.GradientTransformation:
    t = cfg.train
    schedule = optax.warmup_cosine_decay_schedule(
        init_value=0.0,
        peak_value=t.learning_rate,
        warmup_steps=t.warmup_steps,
        decay_steps=max(t.num_steps, t.warmup_steps + 1),
        end_value=t.learning_rate * 0.1,
    )
    clip = optax.clip_by_global_norm(1.0)

    def clip_update(updates, state, params=None):
        with jax.named_scope("grad_clip"):  # a name in the trace, no more
            return clip.update(updates, state, params)

    tx = optax.chain(
        optax.GradientTransformation(clip.init, clip_update),
        optax.adamw(schedule, weight_decay=t.weight_decay),
    )
    if t.gradient_accumulate_every > 1:
        tx = optax.MultiSteps(tx, every_k_schedule=t.gradient_accumulate_every)
    return tx


def init_state(cfg: Config, model, sample_batch: dict) -> TrainState:
    task = as_task(model)
    # validate the init scheme BEFORE the (expensive) model.init trace
    if cfg.model.init_scheme == "torch":
        if cfg.model.scan_layers or cfg.model.reversible:
            raise ValueError(
                "init_scheme='torch' is incompatible with scan_layers and "
                "the reversible engine: their depth-stacked params would "
                "corrupt the fan_in computation (models/init.py)"
            )
    elif cfg.model.init_scheme != "flax":
        raise ValueError(
            f"unknown init_scheme {cfg.model.init_scheme!r}; "
            "expected 'flax' or 'torch'"
        )
    rng = jax.random.key(cfg.train.seed)
    params = task.init(rng, sample_batch)
    if cfg.model.init_scheme == "torch":
        # re-draw under the reference's torch module defaults (models/init.py)
        from alphafold2_tpu.models.init import torch_match_reinit

        params = torch_match_reinit(params, rng)
    return state_from_params(cfg, task, params)


def state_from_params(cfg: Config, model, params) -> TrainState:
    """A fresh TrainState (step 0, new optimizer state) around ``params``.
    The train step donates its state, ``params`` with it."""
    state = TrainState.create(
        apply_fn=as_task(model).apply,
        params=params,
        tx=build_optimizer(cfg),
        skipped=jnp.zeros((), jnp.int32),
    )
    # flax's create() sets step to the python int 0; keep every state leaf
    # on device so the first jitted step performs no implicit host->device
    # transfer (jax.transfer_guard("disallow") clean — tests/conftest.py)
    return state.replace(step=jnp.zeros((), jnp.int32))


def tiny_batch_like(sample_batch: dict, n: int = 16, m: int = 2) -> dict:
    """Slice a real batch's feature arrays to tiny shapes for init tracing.

    Preserves the feature STRUCTURE (msa vs embedds vs none, embedds
    width) while shrinking the shapes that param construction never
    depends on (batch, crop, MSA depth/length)."""
    import numpy as np

    tiny = {}
    for key in ("seq", "mask"):
        if key in sample_batch:
            tiny[key] = np.asarray(sample_batch[key])[:1, :n]
    for key in ("msa", "msa_mask"):
        if sample_batch.get(key) is not None:
            tiny[key] = np.asarray(sample_batch[key])[:1, :m, :n]
    if sample_batch.get("embedds") is not None:
        tiny["embedds"] = np.asarray(sample_batch["embedds"])[:1, :n, :]
    return tiny


def tiny_init_state(
    cfg: Config, model, sample_batch: Optional[dict] = None
) -> TrainState:
    """init_state at minimal data shapes with cfg's feature structure.

    Param shapes (and init values) depend only on the model config — the
    positional tables are sized by max_seq_len / max_num_msas, every other
    layer by dim, and ``embedd_project`` by the embedds feature width —
    not on crop/MSA batch shapes. Initializing with a tiny batch therefore
    produces the identical TrainState while skipping the compile of the
    full-size forward that ``model.init`` would otherwise trigger: at
    crop 256 that init compile costs more than the training-step compile
    itself (measured 1348s vs 49s on CPU).

    When a real ``sample_batch`` is given its arrays are sliced to tiny
    shapes (which preserves the feature structure and the embedds width
    for any PLM provider); otherwise a tiny synthetic batch is built with
    cfg's feature adaptation.
    """
    from dataclasses import replace

    if sample_batch is not None:
        return init_state(
            cfg, model, as_task(model).tiny_batch(sample_batch))

    from alphafold2_tpu.data.pipeline import SyntheticDataset

    d = cfg.data
    tiny_data = replace(
        d,
        crop_len=min(16, d.crop_len),
        msa_depth=min(2, d.msa_depth),
        msa_len=min(16, d.msa_len),
        batch_size=1,
        min_len_filter=min(16, d.crop_len, d.min_len_filter),
        max_len_filter=max(16, d.max_len_filter),
        source="synthetic",
    )
    tiny_cfg = replace(cfg, data=tiny_data)
    batch = next(apply_features(iter(SyntheticDataset(tiny_data, seed=0)), tiny_cfg))
    return init_state(cfg, model, batch)


def _scoped_loss(logits, batch: dict):
    """The distogram loss of one batch, labels included, under the named
    scope ``loss`` (shared by the train step and the triage step)."""
    with jax.named_scope("loss"):
        # native-loader batches carry host-precomputed labels
        # (data/native.py); otherwise bucketize on device
        labels = batch.get("labels")
        if labels is None:
            labels = get_bucketed_distance_matrix(batch["coords"], batch["mask"])
        return distogram_cross_entropy(logits, labels)


def _param_groups(tree) -> dict:
    """Split a param/grad tree into its top-level module groups (``trunk``,
    ``token_emb``, ...), unwrapping the flax ``params`` collection."""
    if hasattr(tree, "keys") and set(tree.keys()) == {"params"}:
        tree = tree["params"]
    if not hasattr(tree, "items"):
        return {"all": tree}
    return dict(tree.items())


def make_train_step(
    model,
    mesh: Optional[Mesh] = None,
    jit: bool = True,
    numerics_mode: str = "off",
):
    """Build the jitted training step of ``model``: a :class:`Task`, or a
    bare trunk model for distogram pretraining.

    Returns step(state, batch, rng) -> (state, metrics). When a mesh is
    given, inputs/outputs carry explicit shardings and the model's internal
    sharding constraints are active. ``jit=False`` returns the raw traceable
    step for embedding in a larger program (e.g. the in-graph multi-step
    scan in bench.py).

    ``numerics_mode`` widens the metrics dict (observe.numerics):

    - ``"off"`` — exactly the historic metrics (loss, grad_norm, grads_ok,
      skipped, and the task's own: distogram_entropy for the trunk).
    - ``"norms"`` — adds per-parameter-group grad/param/update norms
      (``grad_norm/<group>`` etc.) beside the existing global ``grad_norm``.
    - ``"full"`` — norms plus the in-graph activation stats of every
      ``numerics.tag`` in the model under ``metrics["numerics"]``.

    A tagged and an untagged step are DIFFERENT jitted functions (jit
    caches by identity); the mode is fixed at build time on purpose.
    """
    if numerics_mode not in ("off", "norms", "full"):
        raise ValueError(
            f"unknown numerics_mode {numerics_mode!r}; "
            "expected 'off', 'norms' or 'full'"
        )
    task = as_task(model)

    def step(state: TrainState, batch: dict, rng: jax.Array):
        ctx = use_mesh(mesh) if mesh is not None else nullcontext()
        with ctx:
            def loss_fn(params):
                # collection must live inside the differentiated function:
                # the tagged activations are forward-pass tracers, valid
                # only as loss_fn aux outputs (value_and_grad has_aux)
                with numerics.collect(enabled=numerics_mode == "full") as col:
                    outputs = task.forward(params, batch, rng)
                    loss = task.loss(outputs, batch)
                return loss, (outputs, col.stats())

            ((loss, (outputs, act_stats)), grads) = jax.value_and_grad(
                loss_fn, has_aux=True
            )(state.params)
            # The phases around the model carry a named scope each (HLO
            # metadata only; Flax names the model's own modules): a profiler
            # trace is reduced by these names (observe.profiler.block_of).
            # failure detection: skip the update on non-finite gradients
            with jax.named_scope("grads_ok"):
                grads_ok = jnp.all(
                    jnp.asarray(
                        [jnp.all(jnp.isfinite(g)) for g in jax.tree.leaves(grads)]
                    )
                )
                safe_grads = jax.tree.map(
                    lambda g: jnp.where(grads_ok, g, jnp.zeros_like(g)), grads
                )
            # build_optimizer puts the clipping under "grad_clip" inside it
            with jax.named_scope("optimizer"):
                new_state = state.apply_gradients(grads=safe_grads)
            new_state = new_state.replace(
                skipped=state.skipped + jnp.where(grads_ok, 0, 1)
            )
            with jax.named_scope("metrics"):
                gnorm = optax.global_norm(grads)
                metrics = {
                    "loss": loss,
                    "grad_norm": gnorm,
                    "grads_ok": grads_ok,
                    "skipped": new_state.skipped,
                    **task.metrics(outputs),
                }
                if numerics_mode in ("norms", "full"):
                    # per-parameter-group norm trajectories: which part of the
                    # model is drifting/spiking shows up long before the global
                    # grad_norm moves
                    groups_g = _param_groups(grads)
                    groups_new = _param_groups(new_state.params)
                    groups_old = _param_groups(state.params)
                    for k in groups_g:
                        metrics[f"grad_norm/{k}"] = optax.global_norm(groups_g[k])
                        metrics[f"param_norm/{k}"] = optax.global_norm(
                            groups_new[k]
                        )
                        metrics[f"update_norm/{k}"] = optax.global_norm(
                            jax.tree.map(
                                lambda a, b: a - b, groups_new[k], groups_old[k]
                            )
                        )
                    metrics["param_norm"] = optax.global_norm(new_state.params)
                if numerics_mode == "full":
                    metrics["numerics"] = act_stats
            return new_state, metrics

    if not jit:
        return step
    if mesh is None:
        return jax.jit(step, donate_argnums=0)

    repl = NamedSharding(mesh, P())
    data = NamedSharding(mesh, P(DATA_AXIS))
    return jax.jit(
        step,
        in_shardings=(repl, data, repl),
        out_shardings=(repl, repl),
        donate_argnums=0,
    )


def make_triage_step(model, mesh: Optional[Mesh] = None):
    """Fully-tagged diagnostic step for NaN triage.

    Returns triage(params, batch, rng) -> stats, where stats maps every
    tagged tensor — embeddings, per-trunk-layer pair/MSA streams, distogram
    logits, the loss — to its ``numerics.tensor_stats``, followed by
    per-parameter-group gradient stats (``grad/<group>``). Insertion order
    is topological (forward order, then gradients), so
    ``numerics.first_nonfinite(stats)`` names the first tensor that went
    bad. No state update, no donation: the train loop reruns the exact
    (params, batch, rng) of a skipped step through this after the fast
    step's non-finite-grad skip fires.
    """
    task = as_task(model)

    def triage(params, batch: dict, rng: jax.Array):
        ctx = use_mesh(mesh) if mesh is not None else nullcontext()
        with ctx:
            def loss_fn(p):
                with numerics.collect() as col:
                    # rng: the skipped step's exact one
                    loss = task.loss(task.forward(p, batch, rng), batch)
                return loss, col.stats()

            (loss, stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params
            )
            # continue the topological index past the activation tags: the
            # loss follows the forward pass, gradients follow the loss
            stats = dict(stats)
            order = len(stats)
            stats["loss"] = {
                "index": order, **numerics.tensor_stats(loss)
            }
            for name, sub in _param_groups(grads).items():
                order += 1
                stats[f"grad/{name}"] = {
                    "index": order, **numerics.tree_stats(sub)
                }
            return stats

    return jax.jit(triage)


def device_prefetch(data_iter, mesh: Optional[Mesh] = None, size: int = 2):
    """Wrap a host batch iterator with an N-deep on-device prefetch queue.

    ``jax.device_put`` is async: enqueueing the NEXT batch's transfer before
    the current step is consumed overlaps host->device copy with device
    compute (the reference's single-device loop has no such overlap; its
    DataLoader prefetches only into host memory). Python-level, so it works
    for any of the data sources including the native C++ loader."""
    from collections import deque

    queue: deque = deque()
    it = iter(data_iter)
    try:
        for _ in range(size):
            queue.append(device_put_batch(next(it), mesh))
        while queue:
            out = queue.popleft()
            try:
                queue.append(device_put_batch(next(it), mesh))
            except StopIteration:
                pass
            yield out
    except StopIteration:
        while queue:
            yield queue.popleft()


def device_put_batch(batch: dict, mesh: Optional[Mesh] = None) -> dict:
    if mesh is None:
        return {k: jnp.asarray(v) for k, v in batch.items()}
    if jax.process_count() > 1:
        # multi-host: this process holds only its slice of the global batch
        from alphafold2_tpu.parallel.distributed import global_batch

        return global_batch(batch, mesh)
    sh = NamedSharding(mesh, P(DATA_AXIS))
    return {k: jax.device_put(jnp.asarray(v), sh) for k, v in batch.items()}


def train(cfg: Config, num_steps: Optional[int] = None, dataset=None,
          callbacks=(), init_params=None):
    """Training driver (the runnable train_pre.py equivalent): distogram
    pretraining of the trunk, or whatever ``model.arch`` names
    (:func:`build_task`).

    ``init_params``: start from these parameters (the model's own tree) in
    place of a fresh init; step 0, new optimizer state. The step donates its
    state, so the caller's arrays are consumed: pass a copy to keep them.

    Observability (``observe``): host spans go to ``train.trace_events``
    (Chrome trace events) and, while ``train.profile_dir``'s profiler trace
    runs over ``train.profile_steps``, into that trace on the device's clock;
    when it stops, one ``event: profile`` line gives device time by block
    and idle time by host span. Setting ``train.profile_dir`` alone keeps the
    spans in memory. With neither set the tracer is the null object: no
    annotation, no listener, no file read.
    """
    from alphafold2_tpu.observe import MetricsLogger, Profiler, Tracer
    from alphafold2_tpu.observe import profiler as profiler_mod
    from alphafold2_tpu.observe.tracing import compile_counts

    t = cfg.train
    tracer = Tracer(
        t.trace_events,
        enabled=bool(t.trace_events or t.profile_dir),
        # memory only: keep a long run's spans bounded
        max_events=None if t.trace_events else 100_000,
    )
    logger = MetricsLogger(t.checkpoint_dir)
    profiler = Profiler(
        t.profile_dir, t.profile_steps,
        span_names=tracer.annotated_names, log=logger.log,
    )
    try:
        return _train(cfg, num_steps, dataset, callbacks, tracer, logger,
                      profiler, init_params)
    finally:
        # also when a callback ended the run with an exception: whoever
        # holds no handle on these locals still finds spans and record
        profiler.close()
        tracer.close()
        if profiler.enabled:
            profiler_mod.hand_over(tracer.events(), compile_counts())


def _train(cfg: Config, num_steps, dataset, callbacks, tracer, logger, profiler,
           init_params=None):
    import os
    import time

    with tracer.span("train.imports"):  # orbax comes in with the last one
        from alphafold2_tpu.data.pipeline import make_dataset
        from alphafold2_tpu.observe.metrics import flatten_metrics
        from alphafold2_tpu.observe.tracing import compile_counts
        from alphafold2_tpu.train.checkpoint import CheckpointManager

    num_steps = num_steps or cfg.train.num_steps
    owns_dataset = dataset is None
    # fold the process index into the data seed: each host must feed a
    # DIFFERENT slice of the global batch (global_batch() stitches them)
    data_seed = cfg.train.seed + 7919 * jax.process_index()
    with tracer.span("train.dataset"):
        dataset = dataset or make_dataset(
            cfg.data, seed=data_seed,
            vocab_size=cfg.language_model().vocab_size)
        data_iter = apply_features(iter(dataset), cfg)

    mesh = None
    if cfg.mesh.grid_rows * cfg.mesh.grid_cols > 1:
        # 2D pair-grid sharding: (dp, spr, spc) mesh
        from alphafold2_tpu.parallel.grid_parallel import make_grid_mesh

        if cfg.mesh.seq_parallel > 1 or cfg.model.context_parallel:
            raise ValueError(
                "grid_rows/grid_cols builds a (dp, spr, spc) mesh with no "
                "sp axis: mesh.seq_parallel and model.context_parallel "
                "cannot be combined with it"
            )
        if not cfg.model.grid_parallel:
            raise ValueError(
                "mesh.grid_rows/grid_cols requires model.grid_parallel=true "
                "— without it the axial passes run dense and GSPMD "
                "all-gathers the attended axis, losing the memory benefit"
            )
        n_dp = cfg.mesh.data_parallel
        if n_dp == -1:  # fill with all devices, like the 1D path
            n_dp = jax.device_count() // (cfg.mesh.grid_rows * cfg.mesh.grid_cols)
        mesh = make_grid_mesh(n_dp, cfg.mesh.grid_rows, cfg.mesh.grid_cols)
    n_mesh = cfg.mesh.data_parallel * cfg.mesh.seq_parallel
    if mesh is None and (n_mesh > 1 or cfg.mesh.seq_parallel > 1):
        # ICI/DCN-aware device ordering over the whole (multi-host) pod
        from alphafold2_tpu.parallel.distributed import pod_mesh

        mesh = pod_mesh(cfg.mesh.data_parallel, cfg.mesh.seq_parallel)

    with tracer.span("train.build_model"):
        model = build_task(cfg)
    with tracer.span("train.first_batch"):
        sample = next(data_iter)
    # init at tiny slices of the sample: identical params, none of the
    # full-size init compile (see tiny_init_state)
    with tracer.span("train.init_state"):
        if init_params is not None:
            state = state_from_params(cfg, model, init_params)
        else:
            state = tiny_init_state(cfg, model, sample)
    # numerics telemetry mode (observe.numerics): "off" | "triage" (fast
    # step widened with per-parameter-group norms; a fully-tagged rerun
    # fires only when the non-finite-grad skip does) | "full" (every step
    # carries tagged activation stats). AF2TPU_NUMERICS overrides the
    # config for one run.
    numerics_mode = (
        os.environ.get("AF2TPU_NUMERICS") or cfg.train.numerics or "off"
    ).lower()
    if numerics_mode not in ("off", "triage", "full"):
        raise ValueError(
            f"unknown train.numerics {numerics_mode!r}; "
            "expected 'off', 'triage' or 'full'"
        )
    step_fn = make_train_step(
        model,
        mesh,
        numerics_mode={"off": "off", "triage": "norms", "full": "full"}[
            numerics_mode
        ],
    )

    ckpt = (
        CheckpointManager(cfg.train.checkpoint_dir, keep=cfg.train.keep_checkpoints)
        if cfg.train.checkpoint_dir
        else None
    )
    start_step = 0
    if ckpt is not None:
        with tracer.span("train.restore"):
            state, start_step = ckpt.maybe_restore(state)
    if mesh is not None and jax.process_count() == 1:
        # place the state where every later step will find it (replicated
        # over the mesh): handed over on one device, step 0 compiles for
        # that placement and step 1 compiles the same program again
        state = jax.device_put(state, NamedSharding(mesh, P()))

    rng = jax.random.key(cfg.train.seed + 1)

    # preemption safety (SURVEY.md S5.3 — the reference has no failure
    # handling at all): on SIGTERM, finish the in-flight step, checkpoint,
    # and exit cleanly; the next run resumes from maybe_restore above.
    import signal

    stop = {"requested": False}
    prev_handler = None
    if ckpt is not None:
        def _on_sigterm(signum, frame):
            stop["requested"] = True

        try:
            prev_handler = signal.signal(signal.SIGTERM, _on_sigterm)
        except ValueError:  # not on the main thread
            prev_handler = None

    def stop_agreed() -> bool:
        # multi-host: the stop decision must be COLLECTIVE — hosts receive
        # SIGTERM at slightly different times, and a host breaking out
        # early while others run the next step's collectives deadlocks the
        # pod. One tiny bool allgather per step synchronizes the decision.
        if jax.process_count() > 1:
            import numpy as np
            from jax.experimental import multihost_utils

            return bool(
                multihost_utils.process_allgather(
                    np.asarray(stop["requested"])
                ).any()
            )
        return stop["requested"]

    # on-device prefetch: the next batch's host->device transfer overlaps
    # the current step's compute
    from itertools import chain

    prefetched = device_prefetch(chain([sample], data_iter), mesh)
    batch = next(prefetched)

    # AOT-compile the step on the single-mesh path: compile time becomes an
    # explicit metric instead of polluting the first step's rate.
    # The mesh/multi-host path keeps implicit jit compilation: AOT-compiled
    # calls are strict about input shardings the loop does not guarantee.
    step_call = step_fn
    if mesh is None and jax.process_count() == 1:
        t_c = time.perf_counter()
        seen = compile_counts()
        with tracer.span("train.lower") as sp:
            lowered = step_fn.lower(state, batch, rng)
            now = compile_counts()
            sp.set(trace_s=now["trace_s"] - seen["trace_s"],
                   lower_s=now["lower_s"] - seen["lower_s"])
        with tracer.span("train.compile") as sp:
            compiled = lowered.compile()
            done = compile_counts()
            sp.set(backend_s=done["backend_s"] - now["backend_s"],
                   cache_hit=done["cache_hits"] > now["cache_hits"])
        compile_s = time.perf_counter() - t_c
        step_call = compiled
        logger.log(start_step, {"compile_s": round(compile_s, 3)})
        if profiler.enabled:
            profiler.name_operations(compiled.as_text())
    elif profiler.enabled and jax.process_count() == 1:
        # no AOT step here, but the trace is reduced by the names in the
        # compiled step's text. The compile moves here from step 0: jit's
        # own call then finds the executable in jax's in-memory cache
        with tracer.span("train.scope_names"):
            profiler.name_operations(
                step_fn.lower(state, batch, rng).compile().as_text())

    # NaN triage (numerics_mode "triage"/"full"): when a step's non-finite-
    # grad skip fired, rerun it fully tagged and report the first bad
    # tensor in topological order. The check runs one iteration LATE (top
    # of the next loop pass): the skip left params untouched, so the exact
    # (params, batch, rng) triple is still live, and the host only blocks
    # on a step that has had a full iteration to complete.
    triage_fn = None
    pending = None  # (grads_ok, batch, rng, step index) of the last step

    def run_triage(ok, t_batch, t_rng, t_step):
        nonlocal triage_fn
        with tracer.span("train.triage_wait", step=t_step):
            ok = bool(ok)  # a fetch: waits until step t_step has finished
        if ok:
            return
        if triage_fn is None:
            triage_fn = make_triage_step(model, mesh)
        with tracer.span("train.nan_triage", step=t_step):
            stats = triage_fn(state.params, t_batch, t_rng)
        report = numerics.triage_report(stats, step=t_step)
        logger.log(t_step, {
            "event": "nan_triage",
            "first_nonfinite": report["first_nonfinite"],
            "nonfinite": report["nonfinite"],
            **numerics.flatten_stats(stats),
        })
        tracer.instant(
            "numerics.nan_triage", step=t_step,
            first_nonfinite=report["first_nonfinite"],
        )

    t0 = time.perf_counter()
    last_logged = None
    for i in range(start_step, num_steps):
        with tracer.step("train", i):
            if pending is not None:
                run_triage(*pending)
                pending = None
            if profiler.enabled:
                with tracer.span("train.profiler", step=i):
                    profiler.maybe_start(i)
            with tracer.span("train.rng", step=i):
                rng, step_rng = jax.random.split(rng)
            # the running compile count rides on the span: the step at which
            # it rose is the step that compiled
            counted = (
                {"compiles": compile_counts()["compiles"]}
                if tracer.enabled else {}
            )
            with tracer.span("train.step", step=i, **counted):
                state, metrics = step_call(state, batch, step_rng)
            if profiler.enabled:
                with tracer.span("train.profiler", step=i):
                    profiler.maybe_stop(i)
            if numerics_mode in ("triage", "full"):
                pending = (metrics["grads_ok"], batch, step_rng, i)
            if (i + 1) % cfg.train.log_every == 0 or i == start_step:
                with tracer.span("train.log", step=i):
                    m = flatten_metrics(metrics)  # blocks on the step
                    now = time.perf_counter()
                    if last_logged is None:
                        # the session's first step is dispatch- (or, without
                        # AOT, compile-)dominated: record its wall time as
                        # its own metric, not as a rate
                        m["first_step_s"] = round(now - t0, 4)
                    else:
                        m["steps_per_sec"] = (i - last_logged) / max(
                            now - t0, 1e-9)
                    if numerics_mode == "full" and isinstance(
                        metrics.get("numerics"), dict
                    ):
                        # same numerics/<name> vocabulary in the Perfetto
                        # trace
                        numerics.counters_to_tracer(
                            metrics["numerics"], tracer)
                    last_logged = i
                    t0 = now
                    logger.log(i, m)
            if callbacks:
                with tracer.span("train.callbacks", step=i):
                    for cb in callbacks:
                        cb(i, state, metrics)
            if ckpt is not None and (i + 1) % cfg.train.checkpoint_every == 0:
                with tracer.span("train.checkpoint", step=i + 1):
                    ckpt.save(i + 1, state)
            if ckpt is not None:
                with tracer.span("train.stop_agreed", step=i):
                    stopping = stop_agreed()
                if stopping:
                    stop["requested"] = True
                    logger.log(i, {"preempted": 1.0})
                    if ckpt.latest_step() != i + 1:
                        ckpt.save(i + 1, state)
                    break
            with tracer.span("train.next_batch", step=i + 1):
                batch = next(prefetched)
    if pending is not None:  # a skip on the session's final step
        run_triage(*pending)
    if prev_handler is not None:
        signal.signal(signal.SIGTERM, prev_handler)
    if ckpt is not None:
        if not stop["requested"] and ckpt.latest_step() != num_steps:
            ckpt.save(num_steps, state)
        ckpt.wait()
    if owns_dataset and hasattr(dataset, "close"):
        dataset.close()  # shut down native prefetch workers
    return state
