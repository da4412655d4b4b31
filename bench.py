"""Benchmark: distogram-pretraining step throughput on the flagship config.

Primary metric (BASELINE.md): residue-pairs/sec/chip at crop 256. The
reference publishes no numbers (BASELINE.json "published": {}), so
``vs_baseline`` is measured against a recorded run of this bench
(bench_baseline.json, when one has been committed from a chip run; a
missing file means "no baseline" and ``vs_baseline_valid`` is false).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}. Any
failure — an exception, or the one overall deadline — leaves a value-0.0
record with an ``error`` field on stdout and exits non-zero. The bench runs
in one process: nothing here starts a child that needs the device.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp

import alphafold2_tpu


def _env_int(name: str, default: int) -> int:
    return int(os.environ.get(name, default))


# flagship config; AF2TPU_BENCH_* env overrides allow small smoke runs on
# hosts without an accelerator (the driver runs the defaults on TPU)
_T0 = time.monotonic()

CROP = _env_int("AF2TPU_BENCH_CROP", 256)
MSA_DEPTH = _env_int("AF2TPU_BENCH_MSA_DEPTH", 16)
MSA_LEN = _env_int("AF2TPU_BENCH_MSA_LEN", 256)
DIM = _env_int("AF2TPU_BENCH_DIM", 256)
DEPTH = _env_int("AF2TPU_BENCH_DEPTH", 2)
BATCH = _env_int("AF2TPU_BENCH_BATCH", 1)
WARMUP = _env_int("AF2TPU_BENCH_WARMUP", 3)
ITERS = _env_int("AF2TPU_BENCH_ITERS", 10)
# steps chained in-graph per dispatch (lax.scan): isolates device throughput
# from host dispatch latency; compile cost is INGRAPH-independent
INGRAPH = _env_int("AF2TPU_BENCH_INGRAPH", 8)
# the one overall wall-clock budget (s): when it fires the run FAILS — a
# value-0.0 record naming the phase it died in, and a non-zero exit.
# <= 0 disables it.
DEADLINE = _env_int("AF2TPU_BENCH_DEADLINE", 1500)


# DEADLINE / MODE steer the run, not the measured config
_INFRA_KNOBS = {
    "AF2TPU_BENCH_DEADLINE",
    "AF2TPU_BENCH_MODE",  # train vs serve routing, not a config size
}


def config_overridden() -> bool:
    """True when AF2TPU_BENCH_* env overrides change the measured config —
    such runs must be neither compared against nor recorded as the
    flagship baseline."""
    return any(
        k.startswith("AF2TPU_BENCH_") and k not in _INFRA_KNOBS
        for k in os.environ
    )


def _metric() -> str:
    """One label for success and failure records — the driver correlates
    records for the same config by this string."""
    return (
        f"residue-pairs/sec/chip crop={CROP} msa={MSA_DEPTH}x{MSA_LEN} "
        f"dim={DIM} depth={DEPTH} batch={BATCH} fwd+bwd+opt"
    )


# which phase of the measurement the process is in — the deadline's failure
# record reports it, so "backend init never returned" is distinguishable
# from "compile/run exceeded deadline"
_PHASE = {"name": "startup"}

# one clock validation per process
_CLOCK = {"probe": None}


from contextlib import contextmanager

from alphafold2_tpu.observe import (
    MemorySampler,
    MetricsLogger,
    Tracer,
)
from alphafold2_tpu.observe import exposition, flightrec
from alphafold2_tpu.observe.tracing import device_idle_fraction

# the tree's single cost_analysis()/MFU implementation (observe.flops):
# bench, the serve engine and the train loop all share it
from alphafold2_tpu.observe.flops import (
    device_peak_flops as _device_peak_flops,
    estimate_mfu as _estimate_mfu,
    step_flops as _step_flops,
)


def _tracer() -> Tracer:
    """Span tracer for this bench invocation: Chrome trace-event JSONL at
    $AF2TPU_TRACE_EVENTS (Perfetto-loadable), disabled when unset. The
    active flight recorder (if any) rides along as a sink, so its ring
    buffer sees every span the file does."""
    t = Tracer.from_env()
    rec = flightrec.active()
    if rec is not None and t.enabled:
        rec.attach(t)
    return t


def _metrics_logger():
    """Structured JSONL metrics at $AF2TPU_METRICS_DIR/metrics.jsonl
    (compile records, counters, HBM peaks — obs_report.py reads it);
    None when unset. enabled=True: the bench is single-process, and the
    logger must not touch jax.process_index() before backend init."""
    directory = os.environ.get("AF2TPU_METRICS_DIR")
    if not directory:
        return None
    return MetricsLogger(directory, enabled=True, echo=False)


@contextmanager
def _bench_stage(tracer: Tracer, name: str, **args):
    """One bench stage: sets the deadline-visible phase and opens a span."""
    _PHASE["name"] = name
    with tracer.span(f"bench.{name}", **args) as sp:
        yield sp


def _clock_probe(m: int | None = None, size: int = 4096, iters: int = 4):
    """Validate that the timing sync actually tracks device completion.

    Earlier records of this bench were physically impossible (up to 26x the
    chip's peak) because the timed region closed before the device had
    finished. The >peak-FLOPs guard only catches inflation past 100% MFU; a
    partially-async clock inflating 3x at a true 10% MFU passes it
    silently. This probe times the SAME dispatch count at two in-graph work factors — a scan of M vs
    2M chained matmuls. The dispatch/ack path is identical for both, so a
    device-tracking clock shows ~2x elapsed; an early-acking clock shows
    ~1x. No ground-truth step cost is needed.
    """
    m = m or _env_int("AF2TPU_CLOCK_PROBE_CHAIN", 384)
    x = jnp.ones((size, size), jnp.bfloat16)

    def chain(n):
        def body(c, _):
            return (c @ x) * (1.0 / size), ()

        def f(x0):
            out, _ = jax.lax.scan(body, x0, None, length=n)
            return jnp.sum(out[:1, :1].astype(jnp.float32))

        return jax.jit(f)

    times = []
    for f in (chain(m), chain(2 * m)):
        s = f(x)
        jax.device_get(s)  # compile + warm outside the timed region
        t0 = time.perf_counter()
        for _ in range(iters):
            s = f(x)
        jax.device_get(s)
        times.append(time.perf_counter() - t0)
    # The verdict is physics, not a fixed ratio (a constant per-dispatch
    # cost compresses the ratio on an honest clock): the 2x leg runs iters*m extra
    # matmuls of KNOWN cost. An honest clock's elapsed delta must be at
    # least that work at the chip's peak; a delta implying >peak FLOPs/s
    # means the sync acked before the device finished. Constant round-trip
    # cost cancels in the subtraction.
    extra_flops = iters * m * 2 * size**3
    delta = times[1] - times[0]
    implied = extra_flops / max(delta, 1e-9)
    # 1.25x headroom over the published peak absorbs timer jitter (the probe
    # runs only on an accelerator; an unknown device_kind raises)
    ceiling = _device_peak_flops() * 1.25
    return {
        "t_1x": round(times[0], 4),
        "t_2x": round(times[1], 4),
        "extra_work_tflop": round(extra_flops / 1e12, 1),
        "implied_flops_per_s": float(f"{implied:.3g}"),
        "ceiling_flops_per_s": float(f"{ceiling:.3g}"),
        "ok": bool(delta > 0 and implied <= ceiling),
    }


def main(emit: bool = True, tracer: Tracer | None = None):
    owns_tracer = tracer is None
    tracer = tracer if tracer is not None else _tracer()
    from alphafold2_tpu.config import Config, DataConfig, ModelConfig, TrainConfig
    from alphafold2_tpu.data.pipeline import SyntheticDataset
    from alphafold2_tpu.train.loop import (
        build_model,
        device_put_batch,
        make_train_step,
        tiny_init_state,
    )

    cfg = Config(
        model=ModelConfig(
            dim=DIM, depth=DEPTH, heads=8, dim_head=64, max_seq_len=CROP * 2,
            msa_tie_row_attn=True, bfloat16=True,
        ),
        data=DataConfig(
            crop_len=CROP, msa_depth=MSA_DEPTH, msa_len=MSA_LEN,
            batch_size=BATCH,
            min_len_filter=CROP,  # full-length crops for a stable FLOP count
        ),
        train=TrainConfig(gradient_accumulate_every=1, warmup_steps=10),
    )

    with _bench_stage(tracer, "backend_init"):
        data_batch = next(iter(SyntheticDataset(cfg.data, seed=0)))
        model = build_model(cfg)
        # init at tiny slices of the batch: identical params, none of the
        # full-size init compile (train.loop.tiny_init_state)
        state = tiny_init_state(cfg, model, data_batch)
        raw_step = make_train_step(model, mesh=None, jit=False)
        dev_batch = device_put_batch(data_batch)
        rng = jax.random.key(0)

    # chain INGRAPH steps inside one program: per-dispatch host latency
    # is amortized and the timed region is device-bound
    def multi_step(state, batch, rng):
        def body(st, r):
            st, metrics = raw_step(st, batch, r)
            return st, metrics["loss"]

        state, losses = jax.lax.scan(
            body, state, jax.random.split(rng, INGRAPH)
        )
        return state, losses[-1]

    # AOT-compile once: the same executable serves warmup, the timed loop,
    # and the FLOPs count for MFU (no second trace/compile)
    with _bench_stage(tracer, "trace_compile"):
        compiled = jax.jit(multi_step, donate_argnums=0).lower(
            state, dev_batch, rng
        ).compile()

    with _bench_stage(tracer, "warmup_run"):
        for i in range(WARMUP):
            rng, r = jax.random.split(rng)
            state, loss = compiled(state, dev_batch, r)
        if WARMUP:
            # Sync by fetching the VALUE, not just readiness: records timed
            # around block_until_ready alone came out at up to 1084% of the
            # chip's peak and were withdrawn. A device_get of the chained
            # loss cannot resolve early: the bytes don't exist until the
            # whole scan has run.
            jax.device_get(loss)
        else:
            jax.block_until_ready(state.params)

    # validate the clock itself before trusting the timed region with it
    if (
        os.environ.get("AF2TPU_BENCH_CLOCK_CHECK", "1") != "0"
        and jax.devices()[0].platform != "cpu"
        and _CLOCK["probe"] is None
    ):
        with _bench_stage(tracer, "clock_probe"):
            _CLOCK["probe"] = _clock_probe()

    with _bench_stage(tracer, "timed_run"):
        t0 = time.perf_counter()
        for i in range(ITERS):
            rng, r = jax.random.split(rng)
            state, loss = compiled(state, dev_batch, r)
        # one scalar fetch closes the timed region (see warmup comment);
        # its single round-trip amortizes over ITERS*INGRAPH steps and can
        # only make the measurement conservative, never inflate it
        jax.device_get(loss)
        dt = (time.perf_counter() - t0) / (ITERS * INGRAPH)
    _PHASE["name"] = "record"

    pairs_per_sec = BATCH * CROP * CROP / dt
    mfu = _estimate_mfu(compiled, dt * INGRAPH)

    baseline_path = os.path.join(os.path.dirname(__file__), "bench_baseline.json")
    # env-size overrides are non-flagship configs: never compared against
    # the committed baseline
    overridden = config_overridden()
    vs_baseline = 1.0
    compared = False
    if os.path.exists(baseline_path) and not overridden:
        # the committed baseline is the flagship config on TPU; comparing a
        # size-overridden smoke run against it would be meaningless — and so
        # would comparing across timing methodologies (the in-graph step
        # count changes what per-step time includes), hence the ingraph
        # match requirement
        with open(baseline_path) as f:
            base = json.load(f)
        if base.get("value") and base.get("ingraph") == INGRAPH:
            vs_baseline = pairs_per_sec / base["value"]
            compared = True
        elif base.get("value"):
            print(
                f"WARNING: bench_baseline.json was recorded with "
                f"ingraph={base.get('ingraph')} but this run uses "
                f"ingraph={INGRAPH}; regression detection is DISARMED "
                "(vs_baseline=1.0 means 'not compared'). Re-record the "
                "baseline on TPU to re-arm.",
                file=sys.stderr,
            )

    record = {
        "metric": _metric(),
        "value": round(pairs_per_sec, 1),
        "unit": "pairs/sec",
        "vs_baseline": round(vs_baseline, 3),
        "ingraph": INGRAPH,
        # False = no comparable baseline (none committed, size override, or
        # methodology mismatch) — vs_baseline 1.0 then means "not compared",
        # not "at parity"; re-record bench_baseline.json to re-arm
        "vs_baseline_valid": compared,
        # regression-gate comparisons (observe.regress) are device-keyed
        "device": jax.devices()[0].device_kind,
        "platform": jax.devices()[0].platform,
        "device_count": len(jax.devices()),
    }
    if mfu is not None:
        record["mfu"] = round(mfu, 4)
    flops = _step_flops(compiled)
    if flops:
        # the INGRAPH-chained program's flop count (cost analysis covers
        # the whole lax.scan, not one step)
        record["program_flops"] = flops
    # >100% of the chip's published peak: the clock, not the model. Mark
    # the record so nothing downstream can treat it as a valid measurement
    # (a 44.9M pairs/s record was once committed unguarded and had to be
    # withdrawn by hand).
    if mfu is not None and mfu > 1.0:
        record["implausible"] = True
        print(
            f"WARNING: physically impossible measurement (mfu={mfu}) — the "
            "timed region is not syncing with device completion. Record "
            "marked implausible.",
            file=sys.stderr,
        )
    if _CLOCK["probe"] is not None:
        record["clock_probe"] = _CLOCK["probe"]
        if not _CLOCK["probe"]["ok"]:
            # sub-peak inflation the >100%-MFU guard cannot see: the extra
            # in-graph work's elapsed delta implies more than peak FLOPs/s,
            # so the sync is not tracking device completion
            record["clock_suspect"] = True
            print(
                "WARNING: clock probe failed (known extra work implies "
                f"{_CLOCK['probe']['implied_flops_per_s']:.3g} FLOP/s > "
                f"ceiling {_CLOCK['probe']['ceiling_flops_per_s']:.3g}) — "
                "timing does not track device completion. Record marked "
                "clock_suspect.",
                file=sys.stderr,
            )
    if record.get("implausible") or record.get("clock_suspect"):
        # enforce the flag structurally: any consumer that
        # ignores the marker keys must still see "no valid comparison"
        record["vs_baseline"] = 0.0
        record["vs_baseline_valid"] = False
    spans = tracer.span_totals()
    if spans:
        record["spans"] = spans
    hbm_peak = MemorySampler().peak_bytes()
    if hbm_peak is not None:
        record["hbm_peak_bytes"] = hbm_peak
    logger = _metrics_logger()
    if logger is not None:
        logger.log(0, {
            k: v for k, v in record.items()
            if isinstance(v, (int, float, str, bool))
        })
        MemorySampler().log_to(logger)
    if owns_tracer:
        tracer.close()
    if emit:
        _emit(record)
    return record


# ------------------------------------------------------------------ serve ---

# AF2TPU_SERVE_* knobs (NOT AF2TPU_BENCH_*: they must not trip the flagship
# train bench's config_overridden detection). Any of these set -> the serve
# record is a non-flagship config and is never compared to the committed
# serve baseline.
_SERVE_INFRA_KNOBS = {"AF2TPU_SERVE_RECORD_BASELINE"}

# the variant knobs select BETWEEN flagships (single-device vs sharded vs
# bf16 vs tied-row), they do not size-override one: each variant's identity
# rides in the metric label AND its own record key (mesh / dtype / tied
# rows), and the regression gate (observe.regress) refuses any cross-key
# comparison — so records stay self-keyed and safe to compare against their
# own committed baseline (bench_serve_mesh_baseline.json /
# bench_serve_bf16_baseline.json).
_SERVE_MESH_KNOBS = {
    "AF2TPU_SERVE_MESH",
    "AF2TPU_SERVE_LONG_BUCKETS",
    "AF2TPU_SERVE_LONG_REQUESTS",
    "AF2TPU_SERVE_DTYPE",
    "AF2TPU_SERVE_TIE_ROWS",
}


def serve_config_overridden() -> bool:
    return any(
        k.startswith("AF2TPU_SERVE_")
        and k not in _SERVE_INFRA_KNOBS
        and k not in _SERVE_MESH_KNOBS
        for k in os.environ
    )


def _serve_sizes() -> dict:
    """The serve-bench flagship config; CPU-mesh sized so tier-1 hosts give
    real (nonzero, clock-honest) numbers. TPU-scale serving reuses the same
    engine with bigger AF2TPU_SERVE_* values.

    ``AF2TPU_SERVE_MESH`` selects the SECOND flagship — sharded serving
    over the long-chain ladder: its own (smaller-trunk, 512-bucket)
    default sizes, its own metric label and its own mesh-keyed committed
    baseline. Both flagships are fully default-defined; any size env on
    top marks the record overridden exactly as before."""
    mesh_spec = os.environ.get("AF2TPU_SERVE_MESH", "")
    # (single-device flagship default, mesh flagship default)
    dflt = {
        "buckets": ("32,48,64", "32,64"),
        "max_batch": (4, 2),
        "requests": (24, 8),
        "dim": (64, 16),
        "depth": (2, 1),
        "heads": (4, 1),
        "dim_head": (16, 8),
        "msa_depth": (4, 2),
        "mds_iters": (50, 20),
        "long_buckets": ("", "512"),
    }
    pick = 1 if mesh_spec else 0

    buckets = tuple(
        int(v) for v in os.environ.get(
            "AF2TPU_SERVE_BUCKETS", dflt["buckets"][pick]
        ).split(",") if v
    )
    long_buckets = tuple(
        int(v) for v in os.environ.get(
            "AF2TPU_SERVE_LONG_BUCKETS", dflt["long_buckets"][pick]
        ).split(",") if v
    )
    return {
        "buckets": buckets,
        "max_batch": _env_int("AF2TPU_SERVE_MAX_BATCH", dflt["max_batch"][pick]),
        "requests": _env_int("AF2TPU_SERVE_REQUESTS", dflt["requests"][pick]),
        "dim": _env_int("AF2TPU_SERVE_DIM", dflt["dim"][pick]),
        "depth": _env_int("AF2TPU_SERVE_DEPTH", dflt["depth"][pick]),
        "heads": _env_int("AF2TPU_SERVE_HEADS", dflt["heads"][pick]),
        "dim_head": _env_int("AF2TPU_SERVE_DIM_HEAD", dflt["dim_head"][pick]),
        "msa_depth": _env_int("AF2TPU_SERVE_MSA_DEPTH", dflt["msa_depth"][pick]),
        "mds_iters": _env_int("AF2TPU_SERVE_MDS_ITERS", dflt["mds_iters"][pick]),
        "seed": _env_int("AF2TPU_SERVE_SEED", 0),
        # the sharded serve flagship: a mesh spec ("1x2x4" = dp x spr x
        # spc grid) opens the mesh-gated long-chain rungs and routes the
        # record to the mesh-keyed baseline
        "mesh": mesh_spec,
        "long_buckets": long_buckets,
        "long_requests": _env_int("AF2TPU_SERVE_LONG_REQUESTS", 1),
        # precision/workload variants (not size overrides): bf16 serving
        # routes to its own dtype-keyed baseline; tied rows turn on the
        # MSA tied-row attention path (the tied-row kernel's shape)
        "dtype": os.environ.get("AF2TPU_SERVE_DTYPE", "float32"),
        "tie_rows": _env_int("AF2TPU_SERVE_TIE_ROWS", 0) != 0,
    }


def _serve_metric(s: dict) -> str:
    label = (
        f"serve residues/sec buckets={','.join(map(str, s['buckets']))} "
        f"max_batch={s['max_batch']} requests={s['requests']} "
        f"dim={s['dim']} depth={s['depth']} msa_depth={s['msa_depth']} "
        f"mds_iters={s['mds_iters']}"
    )
    if s.get("mesh"):
        # the sharded flagship is a DIFFERENT metric (and baseline): the
        # mesh and long-chain workload are part of what is measured
        label += (
            f" mesh={s['mesh']} "
            f"long={','.join(map(str, s['long_buckets'])) or '-'}"
            f"x{s['long_requests']}"
        )
    if s.get("dtype", "float32") != "float32":
        # the precision variant is likewise its own metric (and baseline)
        label += f" dtype={s['dtype']}"
    if s.get("tie_rows"):
        label += " tied_rows"
    return label


def bench_serve(emit: bool = True, tracer: Tracer | None = None) -> dict:
    """Serving throughput/latency on the bucketed batched engine.

    Measures a mixed-length request stream end to end: residues/sec over
    the whole stream plus p50/p95/p99 per-request latency from the
    engine's streaming Histogram (queue wait + dispatch — what a caller
    observes), with queue-wait/dispatch/batch-occupancy/pad-ratio
    distributions and per-stage span timings alongside. Compiles happen
    in an explicit warmup and are reported separately (per-(bucket,batch)
    durations in ``compile_records``); the timed region closes on
    jax.device_get of the output coordinates, so the numbers are real
    completions, not dispatch acks (clock-probe-checked on non-CPU
    backends like the main bench)."""
    import numpy as np

    from alphafold2_tpu.config import (
        Config, DataConfig, ModelConfig, ServeConfig,
    )
    from alphafold2_tpu.serve import ServeEngine, ServeRequest, padding_fraction

    owns_tracer = tracer is None
    tracer = tracer if tracer is not None else _tracer()
    if not tracer.enabled:
        # device_idle_frac is computed from live serve.dispatch /
        # serve.device_get spans, so the headline run always traces (a
        # memory-only tracer when no trace file was requested)
        tracer = Tracer(enabled=True)
        owns_tracer = True
    s = _serve_sizes()
    with _bench_stage(tracer, "serve:backend_init"):
        from alphafold2_tpu.parallel.sharding import parse_mesh_spec

        mesh = parse_mesh_spec(s["mesh"])
        top = (s["long_buckets"] or s["buckets"])[-1]
        cfg = Config(
            model=ModelConfig(
                dim=s["dim"], depth=s["depth"], heads=s["heads"],
                dim_head=s["dim_head"], max_seq_len=3 * top,
                bfloat16=jax.devices()[0].platform != "cpu",
                # the tied-rows variant exercises the tied-row MSA
                # attention path (the tied-row kernel's shape)
                msa_tie_row_attn=s["tie_rows"],
                # a grid mesh needs the sharded axial primitive (the
                # engine refuses the combination otherwise)
                grid_parallel=bool(
                    mesh is not None and "spr" in mesh.axis_names
                ),
            ),
            data=DataConfig(msa_depth=s["msa_depth"]),
            serve=ServeConfig(
                buckets=s["buckets"], max_batch=s["max_batch"],
                mds_iters=s["mds_iters"],
                long_buckets=s["long_buckets"] if mesh is not None else (),
                dtype=s["dtype"],
            ),
        )
        engine = ServeEngine(cfg, tracer=tracer, mesh=mesh)

    # deterministic mixed-length request stream spanning the ladder
    rng = np.random.default_rng(s["seed"])
    lo = max(4, s["buckets"][0] // 2)
    lengths = rng.integers(lo, s["buckets"][-1] + 1, size=s["requests"])
    alpha = "ACDEFGHIKLMNPQRSTVWY"
    reqs = [
        ServeRequest(
            seq="".join(rng.choice(list(alpha), size=int(n))), seed=i
        )
        for i, n in enumerate(lengths)
    ]
    if mesh is not None and s["long_buckets"]:
        # the crop-free long-chain workload: requests near the top rung —
        # lengths a single device REJECTS (the mesh-gated ladder), served
        # here because the pair grid is sharded O(N^2/(spr*spc)) per device
        for i in range(s["long_requests"]):
            n = int(s["long_buckets"][-1] * 0.92) + i
            reqs.append(ServeRequest(
                seq="".join(rng.choice(list(alpha), size=n)),
                seed=len(reqs),
            ))

    with _bench_stage(tracer, "serve:trace_compile"):
        t0 = time.perf_counter()
        engine.warmup()  # one executable per ladder rung, counted
        compile_s = time.perf_counter() - t0

    if (
        os.environ.get("AF2TPU_BENCH_CLOCK_CHECK", "1") != "0"
        and jax.devices()[0].platform != "cpu"
        and _CLOCK["probe"] is None
    ):
        with _bench_stage(tracer, "serve:clock_probe"):
            _CLOCK["probe"] = _clock_probe()

    with _bench_stage(tracer, "serve:timed_run"):
        flops_before = engine.executed_flops
        t0 = time.perf_counter()
        results = engine.predict_many(reqs)
        wall = time.perf_counter() - t0
        executed_flops = engine.executed_flops - flops_before
    _PHASE["name"] = "serve:record"
    # host/device overlap over the timed stream, measured from the spans
    # the dispatch path just emitted (warmup compiles emit none of the
    # device-span names, so the window covers exactly the stream)
    idle = device_idle_fraction(tracer.events())

    total_residues = int(sum(len(r.seq) for r in reqs))
    assert all(r is not None for r in results)
    stats = engine.stats()
    hists = {  # time histograms scaled seconds -> ms, renamed to match
        (n[:-2] + "_ms" if n.endswith("_s") else n): snap
        for n, snap in engine.histogram_snapshots(unit_scale=1e3).items()
    }
    lat = hists["latency_ms"]

    record = {
        "metric": _serve_metric(s),
        "value": round(total_residues / wall, 1),
        "unit": "residues/sec",
        "mode": "serve",
        # per-request latency percentiles from the streaming Histogram
        # (queue wait + dispatch, ms)
        "p50_ms": round(lat["p50"], 1),
        "p95_ms": round(lat["p95"], 1),
        "p99_ms": round(lat["p99"], 1),
        "compile_s": round(compile_s, 1),
        "compiles": stats.get("serve.compiles", 0),
        "cache_hits": stats.get("serve.cache_hits", 0),
        "requests": stats.get("serve.requests", 0),
        "batches": stats.get("serve.batches", 0),
        "padding_fraction": round(
            padding_fraction(
                # the engine's effective ladder includes the admitted
                # long-chain rungs
                [len(r.seq) for r in reqs], engine.buckets,
            ), 3,
        ),
        # queue-wait/dispatch breakdown + occupancy/pad distributions
        "histograms": hists,
        # XLA build durations keyed by executable shape
        "compile_records": engine.compile_records,
        "device": jax.devices()[0].device_kind,
        # dispatch-path variant key: pipelined ("depthN") vs serial
        # ("off") numbers are different measurements — the regression
        # gate refuses any cross-key comparison (observe.regress)
        "pipeline": engine.pipeline_desc,
        # precision variant key, present only when non-default so
        # pre-existing baselines stay comparable; the regression gate
        # refuses any cross-key comparison (observe.regress)
        **({"dtype": engine.serve_dtype}
           if engine.serve_dtype != "float32" else {}),
    }
    if idle is not None:
        # fraction of the dispatch window the device spent NOT inside a
        # serve.dispatch/serve.device_get span — the overlap the pipeline
        # buys, gated as an absolute ceiling by observe/regress.py
        record["device_idle_frac"] = round(idle["device_idle_frac"], 4)
        record["device_idle"] = {
            "busy_s": round(idle["busy_s"], 3),
            "window_s": round(idle["window_s"], 3),
            "dispatches": idle["dispatches"],
        }
    if mesh is not None:
        # mesh-keyed record: the identity string keys the executable
        # cache, the result cache, the baseline file and the regression
        # gate's comparability check all at once
        record["mesh"] = engine.mesh_desc
        record["mesh_devices"] = int(mesh.devices.size)
        per_dev = [
            c["program_bytes"] for c in engine.compile_records
            if c.get("program_bytes")
        ]
        if per_dev:
            # XLA memory analysis is per device for SPMD programs — the
            # quantity the pair-grid sharding shrinks, gated vs baseline
            record["per_device_program_bytes"] = max(per_dev)
    if executed_flops:
        # dispatched model flops over the timed stream (observe.flops)
        record["flops_total"] = executed_flops
        if engine.executed_flops_breakdown:
            # analytical per-kernel attribution (tied-row vs axial vs
            # rest): an MFU delta names the attention family responsible
            record["flops_by_kernel"] = {
                k: round(v, 1)
                for k, v in engine.executed_flops_breakdown.items()
            }
        from alphafold2_tpu.observe.flops import mfu as _mfu

        # against the published peak of every chip the program spans;
        # absent on the host CPU (observe.flops.device_peak_flops)
        serve_mfu = _mfu(
            executed_flops, wall,
            n_devices=int(mesh.devices.size) if mesh is not None else 1,
        )
        if serve_mfu is not None:
            record["mfu"] = round(serve_mfu, 4)
    spans = tracer.span_totals()
    if spans:
        record["spans"] = spans
    hbm_peak = engine.memory.peak_bytes()
    if hbm_peak is not None:
        record["hbm_peak_bytes"] = hbm_peak
    if _CLOCK["probe"] is not None:
        record["clock_probe"] = _CLOCK["probe"]
        if not _CLOCK["probe"]["ok"]:
            record["clock_suspect"] = True

    # the serve trajectory competes against its own committed first record,
    # like the train bench; comparisons require the identical metric label
    # AND device AND mesh (a CPU-mesh number vs a TPU number is not a
    # comparison, nor is a sharded number vs a single-device one) — the
    # sharded flagship gets its own mesh-keyed baseline file
    baseline_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "bench_serve_mesh_baseline.json" if mesh is not None
        else "bench_serve_bf16_baseline.json"
        if engine.serve_dtype == "bfloat16"
        else "bench_serve_baseline.json",
    )
    vs, compared = 1.0, False
    if (
        os.path.exists(baseline_path)
        and not serve_config_overridden()
        and not record.get("clock_suspect")
    ):
        with open(baseline_path) as f:
            base = json.load(f)
        if (
            base.get("value")
            and base.get("metric") == record["metric"]
            and base.get("device") == record["device"]
            # dispatch-path pipelining is a variant key the metric label
            # does not encode: a different selection is a different
            # measurement
            and base.get("pipeline") == record.get("pipeline")
        ):
            vs = record["value"] / base["value"]
            compared = True
    record["vs_baseline"] = round(vs, 3)
    record["vs_baseline_valid"] = compared and not record.get("clock_suspect")
    if record.get("clock_suspect"):
        record["vs_baseline"] = 0.0

    if (
        os.environ.get("AF2TPU_SERVE_RECORD_BASELINE") == "1"
        and not serve_config_overridden()
        and not record.get("clock_suspect")
    ):
        with open(baseline_path, "w") as f:
            json.dump(record, f, indent=2)
        print(f"recorded serve baseline -> {baseline_path}", file=sys.stderr)

    logger = _metrics_logger()
    if logger is not None:
        logger.log(0, stats)
        logger.log(0, {
            k: v for k, v in record.items()
            if isinstance(v, (int, float, str, bool))
        })
        # mesh runs log per-device HBM peaks (obs_report's mesh section)
        MemorySampler().log_to(logger, per_device=mesh is not None)
    if owns_tracer:
        tracer.close()
    if emit:
        _emit(record)
    return record


# ------------------------------------------------------------ serve-async ---


def _serve_async_sizes() -> dict:
    """The open-loop serve-async flagship; CPU-mesh sized (tiny trunk,
    short buckets) so CI runners and tier-1 hosts produce comparable
    records against the committed ``bench_serve_async_baseline.json``.
    AF2TPU_SERVE_ASYNC_* env knobs rescale it for TPU sessions — any of
    them set marks the record non-flagship (never baseline-compared)."""
    buckets = tuple(
        int(v) for v in os.environ.get(
            "AF2TPU_SERVE_ASYNC_BUCKETS", "12,16,24"
        ).split(",") if v
    )
    return {
        "buckets": buckets,
        "max_batch": _env_int("AF2TPU_SERVE_ASYNC_MAX_BATCH", 4),
        "requests": _env_int("AF2TPU_SERVE_ASYNC_REQUESTS", 50),
        "rate": float(os.environ.get("AF2TPU_SERVE_ASYNC_RATE", 8.0)),
        "dup_fraction": 0.2,  # workload definition: repeat-sequence share
        "dim": _env_int("AF2TPU_SERVE_ASYNC_DIM", 32),
        "depth": _env_int("AF2TPU_SERVE_ASYNC_DEPTH", 1),
        "heads": _env_int("AF2TPU_SERVE_ASYNC_HEADS", 2),
        "dim_head": _env_int("AF2TPU_SERVE_ASYNC_DIM_HEAD", 16),
        "msa_depth": _env_int("AF2TPU_SERVE_ASYNC_MSA_DEPTH", 2),
        "mds_iters": _env_int("AF2TPU_SERVE_ASYNC_MDS_ITERS", 20),
        "dwell_ms": float(os.environ.get("AF2TPU_SERVE_ASYNC_DWELL_MS", 30.0)),
        "queue_depth": _env_int("AF2TPU_SERVE_ASYNC_QUEUE_DEPTH", 16),
        "deadline_s": float(
            os.environ.get("AF2TPU_SERVE_ASYNC_DEADLINE_S", 30.0)
        ),
        "cache_size": _env_int("AF2TPU_SERVE_ASYNC_CACHE", 64),
        "seed": _env_int("AF2TPU_SERVE_ASYNC_SEED", 0),
        # workload definition like dup_fraction: the priority-class mix
        # (high/normal/low shares) the per-class latency breakdowns and
        # per-class SLO specs are evaluated over
        "class_mix": (0.2, 0.6, 0.2),
    }


def _serve_async_metric(s: dict) -> str:
    mix = "/".join(f"{v:g}" for v in s["class_mix"])
    return (
        f"serve-async residues/sec buckets={','.join(map(str, s['buckets']))} "
        f"max_batch={s['max_batch']} requests={s['requests']} "
        f"rate={s['rate']:g}/s dup={s['dup_fraction']:g} classes={mix} "
        f"dim={s['dim']} "
        f"depth={s['depth']} msa_depth={s['msa_depth']} "
        f"mds_iters={s['mds_iters']} dwell_ms={s['dwell_ms']:g} "
        f"queue={s['queue_depth']} deadline_s={s['deadline_s']:g}"
    )


def _telemetry_overhead_probe(engine, s: dict, arms: int = 2,
                              n_requests: int = 12) -> dict:
    """The telemetry plane's cost, measured: identical closed-loop bursts
    through fresh frontends against the ALREADY-WARM engine, alternating
    telemetry off (disabled tracer, no observers) and on (memory tracer +
    SLO monitor + registry feed), best-of-``arms`` per arm so a one-off
    scheduler hiccup doesn't fake an overhead. The burst stays under the
    queue depth at high priority, so admission control never varies
    between arms."""
    import numpy as np

    from alphafold2_tpu.observe.registry import MetricsRegistry
    from alphafold2_tpu.observe.slo import SLOMonitor, default_serve_slos
    from alphafold2_tpu.serve import AsyncServeFrontend, ServeRequest

    rng = np.random.default_rng(s["seed"] + 1)
    lo = max(4, s["buckets"][0] // 2)
    alpha = "ACDEFGHIKLMNPQRSTVWY"
    n = max(1, min(n_requests, s["queue_depth"] - 2))
    seqs = [
        "".join(rng.choice(
            list(alpha), size=int(rng.integers(lo, s["buckets"][-1] + 1))
        ))
        for _ in range(n)
    ]

    def run(telemetry: bool) -> float:
        tr = Tracer(enabled=telemetry)  # memory-only when on
        old_engine_tracer = engine.tracer
        engine.tracer = tr  # the engine's serve.* spans are part of the cost
        try:
            fe = AsyncServeFrontend(engine, tracer=tr)
            if telemetry:
                mon = SLOMonitor(
                    default_serve_slos(s["deadline_s"]),
                    registry=MetricsRegistry(), tracer=tr,
                )
                fe.add_observer(mon.observe)
            t0 = time.perf_counter()
            handles = [
                fe.submit(ServeRequest(seq=q, seed=j, priority=1))
                for j, q in enumerate(seqs)
            ]
            n_ok = sum(
                1 for h in handles if h.result(timeout=600).status == "ok"
            )
            wall = time.perf_counter() - t0
            fe.close()
            return n_ok / wall if wall > 0 else 0.0
        finally:
            engine.tracer = old_engine_tracer

    best = {"off": 0.0, "on": 0.0}
    for _ in range(max(1, arms)):
        for name, tel in (("off", False), ("on", True)):
            best[name] = max(best[name], run(tel))
    frac = (
        max(0.0, 1.0 - best["on"] / best["off"]) if best["off"] else 0.0
    )
    return {
        "goodput_rps_off": round(best["off"], 3),
        "goodput_rps_on": round(best["on"], 3),
        "requests_per_arm": n,
        "arms": arms,
        "overhead_frac": round(frac, 4),
    }


def bench_serve_async(emit: bool = True, tracer: Tracer | None = None) -> dict:
    """Open-loop latency/goodput bench on the async serving frontend.

    A seeded Poisson arrival process (exponential inter-arrival gaps at
    ``rate`` req/s, ~20% repeat sequences) submits requests to an
    ``AsyncServeFrontend`` on their own schedule — the caller does NOT
    wait for one request before offering the next, so queueing, admission
    control, dwell-vs-fill batching, dedup and deadlines are all actually
    exercised. The record carries p50/p95/p99 end-to-end latency over
    successful requests, goodput (ok residues/sec and ok requests/sec over
    the whole open-loop window), the rejection rate, and the structured
    failure counts (deadline misses, cache hits, in-flight dedups,
    retries, dispatch errors). ``AF2TPU_SERVE_ASYNC_FAULT`` (e.g.
    ``"dispatch=2,times=1"``) injects a FaultPlan for degradation drills —
    like every AF2TPU_SERVE_* knob it marks the record non-flagship.

    The telemetry plane is ALWAYS on for the headline run (a memory-only
    tracer when $AF2TPU_TRACE_EVENTS is unset): the record carries the
    trace-reconstruction completeness fraction over non-rejected requests,
    per-priority-class latency/goodput breakdowns, SLO burn-rate verdicts
    (``AF2TPU_SLO_SPECS`` overrides the default specs), and a measured
    telemetry-on-vs-off overhead fraction — the last two gated by
    ``observe/regress.py``'s absolute thresholds."""
    import numpy as np

    from alphafold2_tpu.config import (
        Config, DataConfig, ModelConfig, ServeConfig,
    )
    from alphafold2_tpu.observe import Histogram
    from alphafold2_tpu.observe.registry import MetricsRegistry
    from alphafold2_tpu.observe.slo import (
        SLOMonitor, default_serve_slos, parse_slo_specs, priority_class,
    )
    from alphafold2_tpu.observe.tracectx import trace_completeness
    from alphafold2_tpu.observe.workload import WorkloadRecorder
    from alphafold2_tpu.serve import (
        AsyncServeFrontend, FaultPlan, ServeEngine, ServeRequest,
    )

    owns_tracer = tracer is None
    tracer = tracer if tracer is not None else _tracer()
    if not tracer.enabled:
        # the telemetry contract (trace completeness, SLO ingestion) needs
        # live events even when no trace file was requested
        tracer = Tracer(enabled=True)
        owns_tracer = True
    rec_fr = flightrec.maybe_install_from_env()
    if rec_fr is not None:
        rec_fr.attach(tracer)
    s = _serve_async_sizes()
    with _bench_stage(tracer, "serve_async:backend_init"):
        cfg = Config(
            model=ModelConfig(
                dim=s["dim"], depth=s["depth"], heads=s["heads"],
                dim_head=s["dim_head"], max_seq_len=3 * s["buckets"][-1],
                bfloat16=jax.devices()[0].platform != "cpu",
            ),
            data=DataConfig(msa_depth=s["msa_depth"]),
            serve=ServeConfig(
                buckets=s["buckets"], max_batch=s["max_batch"],
                mds_iters=s["mds_iters"], dwell_ms=s["dwell_ms"],
                queue_depth=s["queue_depth"],
                default_deadline_s=s["deadline_s"],
                cache_size=s["cache_size"],
            ),
        )
        faults = FaultPlan.from_spec(
            os.environ.get("AF2TPU_SERVE_ASYNC_FAULT")
        )
        engine = ServeEngine(cfg, tracer=tracer, faults=faults)

    # deterministic open-loop workload: Poisson arrivals, mixed lengths,
    # ~dup_fraction repeats of earlier (seq, seed) pairs (cache/dedup
    # food), priorities drawn from class_mix. A repeat is a FRESH request
    # object with the same (seq, seed): its own arrival, priority, and
    # trace identity — two users submitting the same sequence are two
    # lifecycles that happen to share one dispatch
    rng = np.random.default_rng(s["seed"])
    lo = max(4, s["buckets"][0] // 2)
    alpha = "ACDEFGHIKLMNPQRSTVWY"
    pri_levels = np.array([1, 0, -1])
    reqs: list = []
    for i in range(s["requests"]):
        priority = int(rng.choice(pri_levels, p=np.array(s["class_mix"])))
        if reqs and rng.random() < s["dup_fraction"]:
            src = reqs[int(rng.integers(0, len(reqs)))]
            reqs.append(ServeRequest(
                seq=src.seq, seed=src.seed, priority=priority
            ))
        else:
            n = int(rng.integers(lo, s["buckets"][-1] + 1))
            reqs.append(ServeRequest(
                seq="".join(rng.choice(list(alpha), size=n)), seed=i,
                priority=priority,
            ))
    gaps = rng.exponential(1.0 / s["rate"], size=s["requests"])

    with _bench_stage(tracer, "serve_async:trace_compile"):
        t0 = time.perf_counter()
        engine.warmup()  # one executable per ladder rung, counted
        compile_s = time.perf_counter() - t0

    if (
        os.environ.get("AF2TPU_BENCH_CLOCK_CHECK", "1") != "0"
        and jax.devices()[0].platform != "cpu"
        and _CLOCK["probe"] is None
    ):
        with _bench_stage(tracer, "serve_async:clock_probe"):
            _CLOCK["probe"] = _clock_probe()

    # telemetry plane around the timed run: SLO monitor + rolling-window
    # registry fed from every resolution, periodic snapshots to the JSONL
    # channel (and the flight recorder), optional Prometheus exposition
    logger = _metrics_logger()
    registry = MetricsRegistry()
    slo_specs = parse_slo_specs(
        os.environ.get("AF2TPU_SLO_SPECS", "")
    ) or default_serve_slos(s["deadline_s"])
    slo_monitor = SLOMonitor(slo_specs, registry=registry, tracer=tracer)

    def _feed_registry(result, priority):
        registry.windowed_counter(f"serve.resolved.{result.status}").add()
        if result.status == "ok":
            registry.windowed_values(
                f"serve.latency_ms.{priority_class(priority)}"
            ).observe(result.latency_s * 1e3)

    frontend = AsyncServeFrontend(engine, tracer=tracer)
    frontend.add_observer(slo_monitor.observe)
    frontend.add_observer(_feed_registry)
    # workload capture (observe/workload.py): every submit + resolution as
    # a scrubbed event — ring-only by default (the flight recorder's
    # workload tail), a replayable JSONL artifact when AF2TPU_WORKLOAD_LOG
    # is set (raw sequences only with AF2TPU_WORKLOAD_RAW=1; the bench's
    # own traffic is synthetic, so the CI smoke opts in)
    workload_rec = WorkloadRecorder(
        path=os.environ.get("AF2TPU_WORKLOAD_LOG"),
        record_raw=os.environ.get("AF2TPU_WORKLOAD_RAW") == "1",
        buckets=s["buckets"], msa_depth=s["msa_depth"],
    )
    frontend.add_submit_observer(workload_rec.on_submit)
    frontend.add_observer(workload_rec.observe)
    if rec_fr is not None:
        rec_fr.attach_workload(workload_rec.tail)
    # zero-seed the variant-scan counters so the fleet scrape sees the
    # gauges (as 0) even before the first family/feature-cache event —
    # EventCounters.snapshot() omits never-bumped keys, and an absent
    # series is indistinguishable from a dead exporter to a scraper
    _scan_counter_zeros = {
        "serve.feat_hits": 0, "serve.feat_delta": 0,
        "serve.feat_misses": 0, "sched.family_members": 0,
        "sched.affinity_batches": 0, "sched.family_inflight_joins": 0,
    }
    metrics_server = exposition.serve_from_env(
        lambda: {
            **_scan_counter_zeros,
            **engine.counters.snapshot(),
            **registry.snapshot(),
        }
    )
    registry.start_snapshotter(
        logger, period_s=0.5,
        also=(
            (lambda snap: rec_fr.snapshot("registry", snap))
            if rec_fr is not None else None
        ),
    )
    with _bench_stage(tracer, "serve_async:timed_run"):
        t0 = time.perf_counter()
        handles = []
        due = t0
        for req, gap in zip(reqs, gaps):
            due += gap
            delay = due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            handles.append(frontend.submit(req))
        results = [h.result(timeout=600) for h in handles]
        wall = time.perf_counter() - t0
    frontend.close()
    registry.stop_snapshotter()
    slo_verdicts = slo_monitor.evaluate()
    _PHASE["name"] = "serve_async:record"

    ok = [r for r in results if r.status == "ok"]
    rejected = sum(1 for r in results if r.status == "rejected")
    deadline_missed = sum(
        1 for r in results if r.status == "deadline_exceeded"
    )
    errors = sum(1 for r in results if r.status == "error")
    lat = Histogram()
    for r in ok:
        lat.observe(r.latency_s)
    lat_ms = lat.snapshot(unit_scale=1e3, digits=4) if ok else {"count": 0}
    stats = frontend.stats()

    # per-priority-class breakdown: what the per-class SLO specs promise,
    # and what the per-class regression thresholds gate
    class_acc: dict = {}
    for req, r in zip(reqs, results):
        b = class_acc.setdefault(
            priority_class(req.priority),
            {"requests": 0, "completed": 0, "rejected": 0,
             "hist": Histogram()},
        )
        b["requests"] += 1
        if r.status == "ok":
            b["completed"] += 1
            b["hist"].observe(r.latency_s)
        elif r.status == "rejected":
            b["rejected"] += 1
    by_class = {}
    for cls, b in sorted(class_acc.items()):
        snap = (
            b["hist"].snapshot(unit_scale=1e3, digits=4)
            if b["completed"] else {"count": 0}
        )
        by_class[cls] = {
            "requests": b["requests"],
            "completed": b["completed"],
            "rejected": b["rejected"],
            "goodput_rps": round(b["completed"] / wall, 3),
            "p50_ms": round(snap.get("p50", 0.0), 1),
            "p95_ms": round(snap.get("p95", 0.0), 1),
            "p99_ms": round(snap.get("p99", 0.0), 1),
        }

    # per-request cost ledger (ServeResult.cost) rolled up per priority
    # class and per (hashed) family: where the device-seconds, amortized
    # compile and padding actually went — the substrate the cost-aware
    # tiering item needs per-tier
    _cost_keys = ("queue_wait_s", "device_share_s", "compile_share_s",
                  "flops_share", "pad_fraction")

    def _cost_add(acc: dict, cost: dict) -> None:
        acc["n"] += 1
        for k in _cost_keys:
            acc[k] += cost.get(k, 0.0)

    def _cost_round(acc: dict) -> dict:
        out = {"n": acc["n"]}
        for k in _cost_keys:
            total = acc[k]
            # padding is only meaningful as a mean; the rest as totals
            out[k] = round(
                total / max(1, acc["n"]) if k == "pad_fraction" else total,
                6,
            )
        return out

    fam_map = workload_rec.family_by_trace()
    cost_by_class: dict = {}
    cost_by_family: dict = {}
    for req, r in zip(reqs, results):
        if not r.cost:
            continue
        acc = cost_by_class.setdefault(
            priority_class(req.priority), {"n": 0, **dict.fromkeys(_cost_keys, 0.0)}
        )
        _cost_add(acc, r.cost)
        fam = fam_map.get(r.trace_id)
        if fam:
            _cost_add(cost_by_family.setdefault(
                fam, {"n": 0, **dict.fromkeys(_cost_keys, 0.0)}
            ), r.cost)
    cost_by_class = {
        cls: _cost_round(acc) for cls, acc in sorted(cost_by_class.items())
    }
    # bounded: the largest families only (a scan-heavy stream could mint
    # hundreds of one-off labels and bloat the record)
    cost_by_family = {
        fam: _cost_round(acc)
        for fam, acc in sorted(
            cost_by_family.items(), key=lambda kv: -kv[1]["n"]
        )[:8]
    }

    # trace reconstruction: every non-rejected request's lifecycle must
    # rebuild from the emitted events as an unbroken span chain
    completeness = trace_completeness(
        tracer.events(),
        [r.trace_id for r in results
         if r.status != "rejected" and r.trace_id],
    )
    # host/device overlap snapshot BEFORE the overhead probe below: the
    # probe issues extra dispatches that would pollute the idle window
    idle = device_idle_fraction(tracer.events())

    with _bench_stage(tracer, "serve_async:overhead_probe"):
        overhead = _telemetry_overhead_probe(engine, s)
    hists = {
        (n[:-2] + "_ms" if n.endswith("_s") else n): snap
        for n, snap in {
            **engine.histogram_snapshots(unit_scale=1e3),
            **frontend.histogram_snapshots(unit_scale=1e3),
        }.items()
    }
    hists["latency_e2e_ms"] = lat_ms

    record = {
        "metric": _serve_async_metric(s),
        "value": round(sum(len(r.seq) for r in ok) / wall, 1),
        "unit": "residues/sec",
        "mode": "serve-async",
        # end-to-end (submit -> resolve) latency over successful requests
        "p50_ms": round(lat_ms.get("p50", 0.0), 1),
        "p95_ms": round(lat_ms.get("p95", 0.0), 1),
        "p99_ms": round(lat_ms.get("p99", 0.0), 1),
        "goodput_rps": round(len(ok) / wall, 3),
        "rejection_rate": round(rejected / max(1, len(results)), 4),
        "requests": len(results),
        "completed": len(ok),
        "rejected": rejected,
        "deadline_misses": deadline_missed,
        "dispatch_error_results": errors,
        "cache_hits": stats.get("sched.cache_hits", 0),
        "inflight_dedup": stats.get("sched.inflight_dedup", 0),
        "retries": stats.get("sched.retries", 0),
        "dispatches": stats.get("sched.dispatches", 0),
        "compiles": stats.get("serve.compiles", 0),
        "compile_s": round(compile_s, 1),
        "histograms": hists,
        "compile_records": engine.compile_records,
        "device": jax.devices()[0].device_kind,
        # dispatch-path variant key (see bench_serve): "depthN" or "off"
        "pipeline": engine.pipeline_desc,
        "by_class": by_class,
        "cost_by_class": cost_by_class,
        **({"cost_by_family": cost_by_family} if cost_by_family else {}),
        "trace": completeness,
        "trace_complete_fraction": completeness["fraction"],
        "slo": slo_verdicts,
        "slo_alerts": sum(1 for v in slo_verdicts if v["alert"]),
        "telemetry_overhead": overhead,
        "telemetry_overhead_frac": overhead["overhead_frac"],
    }
    if idle is not None:
        # open-loop idleness is dominated by the arrival process, so its
        # absolute ceiling (observe/regress.py) is far looser than the
        # closed-loop serve bench's
        record["device_idle_frac"] = round(idle["device_idle_frac"], 4)
        record["device_idle"] = {
            "busy_s": round(idle["busy_s"], 3),
            "window_s": round(idle["window_s"], 3),
            "dispatches": idle["dispatches"],
        }
    # flat per-class keys beside the nested breakdown: the regression
    # gate's threshold table addresses record keys by name
    for cls, b in by_class.items():
        record[f"p95_ms_{cls}"] = b["p95_ms"]
        record[f"goodput_rps_{cls}"] = b["goodput_rps"]
    if metrics_server is not None:
        record["metrics_port"] = metrics_server.port
    if rec_fr is not None and (
        os.environ.get("AF2TPU_FLIGHTREC_FORCE_DUMP") == "1"
    ):
        dump_path = rec_fr.dump("forced", force=True)
        if dump_path:
            record["flightrec_dump"] = dump_path
    if engine.executed_flops:
        record["flops_total"] = engine.executed_flops
        from alphafold2_tpu.observe.flops import mfu as _mfu

        async_mfu = _mfu(engine.executed_flops, wall)
        if async_mfu is not None:
            record["mfu"] = round(async_mfu, 4)
    spans = tracer.span_totals()
    if spans:
        record["spans"] = spans
    hbm_peak = engine.memory.peak_bytes()
    if hbm_peak is not None:
        record["hbm_peak_bytes"] = hbm_peak
    if _CLOCK["probe"] is not None:
        record["clock_probe"] = _CLOCK["probe"]
        if not _CLOCK["probe"]["ok"]:
            record["clock_suspect"] = True

    baseline_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "bench_serve_async_baseline.json",
    )
    vs, compared = 1.0, False
    if (
        os.path.exists(baseline_path)
        and not serve_config_overridden()
        and not record.get("clock_suspect")
    ):
        with open(baseline_path) as f:
            base = json.load(f)
        if (
            base.get("value")
            and base.get("metric") == record["metric"]
            and base.get("device") == record["device"]
            # pipelined vs serial dispatch are different measurements
            and base.get("pipeline") == record.get("pipeline")
        ):
            vs = record["value"] / base["value"]
            compared = True
    record["vs_baseline"] = round(vs, 3)
    record["vs_baseline_valid"] = compared and not record.get("clock_suspect")
    if record.get("clock_suspect"):
        record["vs_baseline"] = 0.0

    if (
        os.environ.get("AF2TPU_SERVE_RECORD_BASELINE") == "1"
        and not serve_config_overridden()
        and not record.get("clock_suspect")
    ):
        with open(baseline_path, "w") as f:
            json.dump(record, f, indent=2)
        print(
            f"recorded serve-async baseline -> {baseline_path}",
            file=sys.stderr,
        )

    if logger is not None:
        logger.log(0, stats)
        logger.log(0, {
            k: v for k, v in record.items()
            if isinstance(v, (int, float, str, bool))
        })
        for v in slo_verdicts:  # slo/<spec>/<field> keys for obs_report
            logger.log(0, {
                f"slo/{v['spec']}/{k}": val for k, val in v.items()
                if isinstance(val, (int, float, bool))
            })
        MemorySampler().log_to(logger)
    # the recording's closing summary: the reference half of the
    # record→replay diff (--mode serve-replay loads it via load_workload)
    workload_rec.write_summary({
        "requests": len(results),
        "completed": len(ok),
        "goodput_rps": record["goodput_rps"],
        "p50_ms": record["p50_ms"],
        "p95_ms": record["p95_ms"],
        "trace_complete_fraction": record["trace_complete_fraction"],
        "ledger": {
            "feat_hits": stats.get("serve.feat_hits", 0),
            "feat_delta": stats.get("serve.feat_delta", 0),
            "feat_misses": stats.get("serve.feat_misses", 0),
            "cache_hits": stats.get("sched.cache_hits", 0),
            "inflight_dedup": stats.get("sched.inflight_dedup", 0),
        },
    })
    workload_rec.close()
    if workload_rec.path:
        record["workload_log"] = workload_rec.path
        record["workload_events"] = workload_rec.events_recorded
    if metrics_server is not None:
        metrics_server.stop()
    if owns_tracer:
        tracer.close()
    if emit:
        _emit(record)
    return record


# ------------------------------------------------------------- serve-scan ---


def _serve_scan_sizes() -> dict:
    """The variant-scan flagship: one parent sequence plus its full
    single-point deep-mutational-scan (19 substitutions x parent_len
    positions ~= 20*L variants, every mutant distinct so the result cache
    never short-circuits featurization accounting). CPU-mesh sized like
    the other serve flagships; AF2TPU_SERVE_SCAN_* knobs rescale it and
    mark the record non-flagship (never baseline-compared)."""
    parent_len = _env_int("AF2TPU_SERVE_SCAN_PARENT_LEN", 24)
    full_scan = parent_len * 19  # every (position, substitution) once
    return {
        "parent_len": parent_len,
        "variants": _env_int("AF2TPU_SERVE_SCAN_VARIANTS", full_scan),
        "max_batch": _env_int("AF2TPU_SERVE_SCAN_MAX_BATCH", 8),
        # cold arm: this many variants dispatched one at a time through an
        # identical engine with the fast lane off — the denominator of the
        # amortized-speedup claim, same machine, same compile
        "cold_sample": _env_int("AF2TPU_SERVE_SCAN_COLD_SAMPLE", 16),
        "dim": _env_int("AF2TPU_SERVE_SCAN_DIM", 32),
        "depth": _env_int("AF2TPU_SERVE_SCAN_DEPTH", 1),
        "heads": _env_int("AF2TPU_SERVE_SCAN_HEADS", 2),
        "dim_head": _env_int("AF2TPU_SERVE_SCAN_DIM_HEAD", 16),
        "msa_depth": _env_int("AF2TPU_SERVE_SCAN_MSA_DEPTH", 2),
        "mds_iters": _env_int("AF2TPU_SERVE_SCAN_MDS_ITERS", 20),
        "dwell_ms": float(os.environ.get("AF2TPU_SERVE_SCAN_DWELL_MS", 10.0)),
        "seed": _env_int("AF2TPU_SERVE_SCAN_SEED", 0),
    }


def scan_config_overridden() -> bool:
    return any(k.startswith("AF2TPU_SERVE_SCAN_") for k in os.environ)


def _serve_scan_metric(s: dict) -> str:
    return (
        f"serve-scan variants/sec parent_len={s['parent_len']} "
        f"variants={s['variants']} max_batch={s['max_batch']} "
        f"cold_sample={s['cold_sample']} dim={s['dim']} depth={s['depth']} "
        f"msa_depth={s['msa_depth']} mds_iters={s['mds_iters']} "
        f"dwell_ms={s['dwell_ms']:g}"
    )


def _scan_mutants(parent: str, n: int, rng) -> list:
    """``n`` DISTINCT single-point mutants of ``parent`` in a seeded
    shuffled order — a deep mutational scan submits position-sweeps, but
    shuffling makes the affinity former's job honest (siblings are found
    by family, not by accidental adjacency)."""
    alpha = "ACDEFGHIKLMNPQRSTVWY"
    all_muts = [
        parent[:i] + aa + parent[i + 1:]
        for i in range(len(parent))
        for aa in alpha
        if aa != parent[i]
    ]
    rng.shuffle(all_muts)
    return all_muts[:n]


def bench_serve_scan(emit: bool = True, tracer: Tracer | None = None) -> dict:
    """Variant-scan fast-lane bench: amortized per-variant latency of a
    deep mutational scan through the scan lane vs the cold path.

    Two arms on the same machine in one process:

    - **scan lane** — parent + ``variants`` distinct point mutants (one
      seed: delta featurization requires seed equality) submitted as a
      burst to an ``AsyncServeFrontend`` with the content-addressed
      FeatureCache, delta featurization and parent-affinity batching on.
      Amortized per-variant latency = wall / requests.
    - **cold path** — ``cold_sample`` of the same variants dispatched ONE
      AT A TIME through an identical engine with the fast lane disabled:
      each pays featurization, batch padding and a whole dispatch alone,
      which is exactly what today's cache-miss mutant traffic pays.

    The record's ``speedup_vs_cold`` (cold per-variant / scan per-variant)
    is the tentpole's >=5x acceptance bar, gated absolutely in
    observe/regress.py SERVE_SCAN_THRESHOLDS. The featurization-reuse
    ledger must fully account the scan arm: ``feat_hits + feat_misses +
    feat_delta == requests`` (every dispatched request bumps exactly one),
    recorded as ``ledger_accounted_frac``. The record carries
    ``"scan": true`` — a comparability variant key, so scan records never
    ratio against plain serve records."""
    import numpy as np

    from alphafold2_tpu.config import (
        Config, DataConfig, ModelConfig, ServeConfig,
    )
    from alphafold2_tpu.observe import Histogram
    from alphafold2_tpu.serve import (
        AsyncServeFrontend, ServeEngine, ServeRequest,
    )

    owns_tracer = tracer is None
    tracer = tracer if tracer is not None else _tracer()
    s = _serve_scan_sizes()
    bucket = s["parent_len"]  # one rung: a scan is single-length traffic
    rng = np.random.default_rng(s["seed"])
    alpha = "ACDEFGHIKLMNPQRSTVWY"
    parent = "".join(rng.choice(list(alpha), size=s["parent_len"]))
    mutants = _scan_mutants(parent, s["variants"], rng)
    n_requests = 1 + len(mutants)  # parent + mutants

    def _cfg(fast_lane: bool) -> Config:
        return Config(
            model=ModelConfig(
                dim=s["dim"], depth=s["depth"], heads=s["heads"],
                dim_head=s["dim_head"], max_seq_len=3 * bucket,
                bfloat16=jax.devices()[0].platform != "cpu",
            ),
            data=DataConfig(msa_depth=s["msa_depth"]),
            serve=ServeConfig(
                buckets=(bucket,), max_batch=s["max_batch"],
                mds_iters=s["mds_iters"], dwell_ms=s["dwell_ms"],
                # the whole scan queues as one burst: deep queue, no
                # shedding (0 disables the watermark), no deadline —
                # admission control is not what this bench measures
                queue_depth=n_requests + 64,
                shed_watermark=0.0,
                default_deadline_s=0.0,
                feature_cache_size=(n_requests + 16) if fast_lane else 0,
                delta_featurize=fast_lane,
                affinity_batching=fast_lane,
            ),
        )

    with _bench_stage(tracer, "serve_scan:backend_init"):
        engine = ServeEngine(_cfg(fast_lane=True), tracer=tracer)
    with _bench_stage(tracer, "serve_scan:trace_compile"):
        t0 = time.perf_counter()
        engine.warmup()  # compiles only; featurizes nothing (clean ledger)
        compile_s = time.perf_counter() - t0

    # ---- scan-lane arm: the whole scan as one burst ----
    frontend = AsyncServeFrontend(engine, tracer=tracer)
    with _bench_stage(tracer, "serve_scan:timed_scan"):
        t0 = time.perf_counter()
        handles = [frontend.submit(ServeRequest(parent, seed=s["seed"]))]
        handles += [
            frontend.submit(ServeRequest(
                m, seed=s["seed"], parent_id="scan-parent-0"
            ))
            for m in mutants
        ]
        results = [h.result(timeout=600) for h in handles]
        scan_wall = time.perf_counter() - t0
    frontend.close()
    stats = engine.counters.snapshot()
    ok = [r for r in results if r.status == "ok"]
    lat = Histogram()
    for r in ok:
        lat.observe(r.latency_s)
    lat_ms = lat.snapshot(unit_scale=1e3, digits=4) if ok else {"count": 0}

    # featurization-reuse ledger: every dispatched request bumped exactly
    # one of the three counters, and every result carries its entry
    feat_hits = stats.get("serve.feat_hits", 0)
    feat_misses = stats.get("serve.feat_misses", 0)
    feat_delta = stats.get("serve.feat_delta", 0)
    featurized = feat_hits + feat_misses + feat_delta
    by_reuse: dict = {}
    for r in results:
        by_reuse[r.feat_reuse] = by_reuse.get(r.feat_reuse, 0) + 1
    ledger = {
        "feat_hits": feat_hits,
        "feat_misses": feat_misses,
        "feat_delta": feat_delta,
        "featurized": featurized,
        "requests": n_requests,
        "results_by_reuse": {str(k): v for k, v in by_reuse.items()},
    }

    # ---- cold arm: one variant per dispatch, fast lane off ----
    with _bench_stage(tracer, "serve_scan:cold_arm"):
        cold_engine = ServeEngine(
            _cfg(fast_lane=False), params=engine.params, tracer=tracer
        )
        cold_engine.warmup()
        sample = mutants[: max(1, s["cold_sample"])]
        t0 = time.perf_counter()
        for m in sample:
            cold_engine.predict_many([ServeRequest(m, seed=s["seed"])])
        cold_wall = time.perf_counter() - t0
        cold_engine.close()
    _PHASE["name"] = "serve_scan:record"

    scan_per_variant = scan_wall / max(1, len(ok))
    cold_per_variant = cold_wall / len(sample)
    speedup = (
        cold_per_variant / scan_per_variant if scan_per_variant > 0 else 0.0
    )
    fc_stats = (
        engine.feature_cache.stats()
        if engine.feature_cache is not None else {}
    )
    engine.close()
    hists = {
        (n[:-2] + "_ms" if n.endswith("_s") else n): snap
        for n, snap in {
            **engine.histogram_snapshots(unit_scale=1e3),
            **frontend.histogram_snapshots(unit_scale=1e3),
        }.items()
    }
    hists["latency_e2e_ms"] = lat_ms
    # flat padding-fraction scalars beside the nested histograms: the
    # obs_report variant-scan section reads metrics.jsonl, which only
    # carries scalars
    pad_flat = {}
    for hname, key in (("affinity_pad_fraction", "affinity_pad_p50"),
                       ("regular_pad_fraction", "regular_pad_p50")):
        snap = hists.get(hname) or {}
        if snap.get("count"):
            pad_flat[key] = round(snap.get("p50", 0.0), 4)

    record = {
        "metric": _serve_scan_metric(s),
        "value": round(len(ok) / scan_wall, 1) if scan_wall > 0 else 0.0,
        "unit": "variants/sec",
        "mode": "serve-scan",
        # comparability variant key: scan records only ever ratio against
        # scan records (observe/regress.py comparable_reason)
        "scan": True,
        "speedup_vs_cold": round(speedup, 2),
        "scan_ms_per_variant": round(scan_per_variant * 1e3, 2),
        "cold_ms_per_variant": round(cold_per_variant * 1e3, 2),
        "cold_sampled": len(sample),
        "reuse_ledger": ledger,
        "ledger_accounted_frac": (
            round(featurized / n_requests, 4) if n_requests else 0.0
        ),
        "reuse_fraction": (
            round((feat_hits + feat_delta) / featurized, 4)
            if featurized else 0.0
        ),
        "feature_cache": fc_stats,
        "p50_ms": round(lat_ms.get("p50", 0.0), 1),
        "p95_ms": round(lat_ms.get("p95", 0.0), 1),
        "requests": n_requests,
        "completed": len(ok),
        "affinity_batches": stats.get("sched.affinity_batches", 0),
        "family_members": stats.get("sched.family_members", 0),
        "family_inflight_joins": stats.get(
            "sched.family_inflight_joins", 0
        ),
        "inflight_admitted": stats.get("sched.inflight_admitted", 0),
        "dispatches": stats.get("sched.dispatches", 0),
        "compiles": stats.get("serve.compiles", 0),
        "compile_s": round(compile_s, 1),
        "histograms": hists,
        **pad_flat,
        "device": jax.devices()[0].device_kind,
        "pipeline": engine.pipeline_desc,
    }
    if _CLOCK["probe"] is not None:
        record["clock_probe"] = _CLOCK["probe"]
        if not _CLOCK["probe"]["ok"]:
            record["clock_suspect"] = True

    baseline_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "bench_serve_scan_baseline.json",
    )
    vs, compared = 1.0, False
    if (
        os.path.exists(baseline_path)
        and not scan_config_overridden()
        and not record.get("clock_suspect")
    ):
        with open(baseline_path) as f:
            base = json.load(f)
        if (
            base.get("value")
            and base.get("metric") == record["metric"]
            and base.get("device") == record["device"]
            and base.get("pipeline") == record.get("pipeline")
            and bool(base.get("scan")) == bool(record.get("scan"))
        ):
            vs = record["value"] / base["value"]
            compared = True
    record["vs_baseline"] = round(vs, 3)
    record["vs_baseline_valid"] = compared and not record.get("clock_suspect")
    if record.get("clock_suspect"):
        record["vs_baseline"] = 0.0

    if (
        os.environ.get("AF2TPU_SERVE_RECORD_BASELINE") == "1"
        and not scan_config_overridden()
        and not record.get("clock_suspect")
    ):
        with open(baseline_path, "w") as f:
            json.dump(record, f, indent=2)
        print(
            f"recorded serve-scan baseline -> {baseline_path}",
            file=sys.stderr,
        )

    logger = _metrics_logger()
    if logger is not None:
        logger.log(0, stats)
        logger.log(0, {
            k: v for k, v in record.items()
            if isinstance(v, (int, float, str, bool))
        })
    if owns_tracer:
        tracer.close()
    if emit:
        _emit(record)
    return record


# ----------------------------------------------------------- serve-replay ---


def _serve_replay_sizes() -> dict:
    """The record→replay flagship: a seeded synthetic diurnal stream
    through the full fast-lane frontend, recorded and replayed in one
    process. AF2TPU_SERVE_REPLAY_* knobs rescale it (CI smoke) and mark
    the record non-flagship."""
    return {
        "requests": _env_int("AF2TPU_SERVE_REPLAY_REQUESTS", 40),
        "mean_rate": float(os.environ.get("AF2TPU_SERVE_REPLAY_RATE", 8.0)),
        "period_s": float(
            os.environ.get("AF2TPU_SERVE_REPLAY_PERIOD_S", 4.0)
        ),
        "amplitude": float(
            os.environ.get("AF2TPU_SERVE_REPLAY_AMPLITUDE", 0.8)
        ),
        "buckets": tuple(
            int(x) for x in os.environ.get(
                "AF2TPU_SERVE_REPLAY_BUCKETS", "12,16"
            ).split(",")
        ),
        "max_batch": _env_int("AF2TPU_SERVE_REPLAY_MAX_BATCH", 4),
        "dim": _env_int("AF2TPU_SERVE_REPLAY_DIM", 32),
        "depth": _env_int("AF2TPU_SERVE_REPLAY_DEPTH", 1),
        "heads": _env_int("AF2TPU_SERVE_REPLAY_HEADS", 2),
        "dim_head": _env_int("AF2TPU_SERVE_REPLAY_DIM_HEAD", 16),
        "msa_depth": _env_int("AF2TPU_SERVE_REPLAY_MSA_DEPTH", 2),
        "mds_iters": _env_int("AF2TPU_SERVE_REPLAY_MDS_ITERS", 20),
        "dwell_ms": float(
            os.environ.get("AF2TPU_SERVE_REPLAY_DWELL_MS", 10.0)
        ),
        "deadline_s": float(
            os.environ.get("AF2TPU_SERVE_REPLAY_DEADLINE_S", 60.0)
        ),
        "seed": _env_int("AF2TPU_SERVE_REPLAY_SEED", 0),
    }


def _replay_args(argv=None) -> dict:
    """The replay driver's knobs, bench_mode-style: ``--time-warp`` /
    ``--load-scale`` / ``--replay-log`` (``--flag value`` or
    ``--flag=value``), with AF2TPU_SERVE_REPLAY_{WARP,SCALE,LOG} env
    fallbacks."""
    args = sys.argv[1:] if argv is None else argv

    def flag(name: str, env: str, default: str) -> str:
        for i, a in enumerate(args):
            if a == name and i + 1 < len(args):
                return args[i + 1]
            if a.startswith(name + "="):
                return a.split("=", 1)[1]
        return os.environ.get(env, default)

    return {
        "time_warp": float(
            flag("--time-warp", "AF2TPU_SERVE_REPLAY_WARP", "1.0")
        ),
        "load_scale": int(
            flag("--load-scale", "AF2TPU_SERVE_REPLAY_SCALE", "1")
        ),
        "log": flag("--replay-log", "AF2TPU_SERVE_REPLAY_LOG", "") or None,
    }


def replay_config_overridden(ra: dict | None = None) -> bool:
    """Any env resize, an external log, or non-default warp/scale marks
    the record non-flagship: never baseline-compared, never re-recorded."""
    if any(k.startswith("AF2TPU_SERVE_REPLAY_") for k in os.environ):
        return True
    if ra is None:
        return False
    return bool(
        ra["log"] or ra["time_warp"] != 1.0 or ra["load_scale"] != 1
    )


def _serve_replay_metric(s: dict, ra: dict) -> str:
    source = "log" if ra["log"] else "synthetic-diurnal"
    return (
        f"serve-replay residues/sec source={source} "
        f"requests={s['requests']} rate={s['mean_rate']:g}/s "
        f"period_s={s['period_s']:g} amp={s['amplitude']:g} "
        f"warp={ra['time_warp']:g} scale={ra['load_scale']} "
        f"buckets={','.join(map(str, s['buckets']))} "
        f"max_batch={s['max_batch']} dim={s['dim']} depth={s['depth']} "
        f"msa_depth={s['msa_depth']} mds_iters={s['mds_iters']} "
        f"dwell_ms={s['dwell_ms']:g}"
    )


def _drive_stream(frontend, pairs) -> tuple:
    """Open-loop submission of a timed (offset, request) stream: each
    request goes in at its offset from stream start whether or not earlier
    ones resolved. Returns (results, wall_s) aligned with ``pairs``."""
    t0 = time.perf_counter()
    handles = []
    for off, req in pairs:
        delay = t0 + off - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        handles.append(frontend.submit(req))
    results = [h.result(timeout=600) for h in handles]
    return results, time.perf_counter() - t0


def _recorder_overhead_probe(engine, s: dict, arms: int = 2,
                             n_requests: int = 12) -> dict:
    """The workload recorder's cost, measured exactly like
    ``_telemetry_overhead_probe``: identical closed-loop bursts through
    fresh frontends on the ALREADY-WARM engine, alternating recorder off
    and on (both hooks + a real JSONL append per event), best-of-arms."""
    import tempfile

    import numpy as np

    from alphafold2_tpu.observe.workload import WorkloadRecorder
    from alphafold2_tpu.serve import AsyncServeFrontend, ServeRequest

    rng = np.random.default_rng(s["seed"] + 1)
    lo = max(4, s["buckets"][0] // 2)
    alpha = "ACDEFGHIKLMNPQRSTVWY"
    n = max(1, n_requests)
    seqs = [
        "".join(rng.choice(
            list(alpha), size=int(rng.integers(lo, s["buckets"][-1] + 1))
        ))
        for _ in range(n)
    ]

    def run(recording: bool) -> float:
        fe = AsyncServeFrontend(engine)
        rec = None
        path = None
        if recording:
            fd, path = tempfile.mkstemp(suffix=".jsonl",
                                        prefix="af2tpu_wkld_probe_")
            os.close(fd)
            rec = WorkloadRecorder(
                path=path, record_raw=True,
                buckets=s["buckets"], msa_depth=s["msa_depth"],
            )
            fe.add_submit_observer(rec.on_submit)
            fe.add_observer(rec.observe)
        try:
            t0 = time.perf_counter()
            handles = [
                fe.submit(ServeRequest(seq=q, seed=j, priority=1))
                for j, q in enumerate(seqs)
            ]
            n_ok = sum(
                1 for h in handles if h.result(timeout=600).status == "ok"
            )
            wall = time.perf_counter() - t0
            fe.close()
            return n_ok / wall if wall > 0 else 0.0
        finally:
            if rec is not None:
                rec.close()
            if path is not None:
                try:
                    os.unlink(path)
                except OSError:
                    pass

    best = {"off": 0.0, "on": 0.0}
    for _ in range(max(1, arms)):
        for name, on in (("off", False), ("on", True)):
            best[name] = max(best[name], run(on))
    frac = (
        max(0.0, 1.0 - best["on"] / best["off"]) if best["off"] else 0.0
    )
    return {
        "goodput_rps_off": round(best["off"], 3),
        "goodput_rps_on": round(best["on"], 3),
        "requests_per_arm": n,
        "arms": arms,
        "overhead_frac": round(frac, 4),
    }


def bench_serve_replay(emit: bool = True,
                       tracer: Tracer | None = None) -> dict:
    """Workload record→replay bench: the deterministic replay driver and
    the loop's own gate, in one process.

    - **record arm** (skipped when ``--replay-log`` points at an existing
      recording): a seeded synthetic diurnal stream
      (:func:`observe.workload.synthetic_diurnal` — inhomogeneous Poisson
      arrivals riding a sinusoidal load curve, with duplicate and
      single-point-mutant traffic) runs open-loop through a fast-lane
      ``AsyncServeFrontend`` with a raw-opt-in :class:`WorkloadRecorder`
      attached, producing a replayable JSONL recording plus its closing
      summary (the reuse ledger, goodput, latency tails).
    - **replay arm**: the recording is loaded and re-issued with original
      timing against a FRESH engine (fresh feature cache, fresh counters)
      — ``--time-warp`` divides every arrival offset, ``--load-scale``
      multiplies each request into distinct-seed copies. The record
      carries the replay-vs-record diff: ``ledger_match`` (the replay
      reproduced the recording's feature-reuse ledger EXACTLY),
      ``replay_bytes_identical`` (same (seq, seed) → byte-identical
      atom14 outputs across arms), goodput/latency ratios, the replay
      arm's trace completeness, and ``recorder_overhead_frac`` measured
      on/off on the warm engine — all gated by REPLAY_THRESHOLDS
      (observe/regress.py). Non-default warp/scale/log marks the record
      non-flagship (its own ``replay`` comparability key)."""
    import hashlib
    import tempfile

    from alphafold2_tpu.config import (
        Config, DataConfig, ModelConfig, ServeConfig,
    )
    from alphafold2_tpu.observe import Histogram
    from alphafold2_tpu.observe.tracectx import trace_completeness
    from alphafold2_tpu.observe.workload import (
        WorkloadRecorder, build_replay, load_workload, replayable_reason,
        synthetic_diurnal,
    )
    from alphafold2_tpu.serve import AsyncServeFrontend, ServeEngine

    owns_tracer = tracer is None
    tracer = tracer if tracer is not None else _tracer()
    if not tracer.enabled:
        # trace completeness over the replay arm needs live events even
        # when no trace file was requested
        tracer = Tracer(enabled=True)
        owns_tracer = True
    s = _serve_replay_sizes()
    ra = _replay_args()
    n_expected = s["requests"]

    def _cfg() -> Config:
        return Config(
            model=ModelConfig(
                dim=s["dim"], depth=s["depth"], heads=s["heads"],
                dim_head=s["dim_head"], max_seq_len=3 * s["buckets"][-1],
                bfloat16=jax.devices()[0].platform != "cpu",
            ),
            data=DataConfig(msa_depth=s["msa_depth"]),
            serve=ServeConfig(
                buckets=s["buckets"], max_batch=s["max_batch"],
                mds_iters=s["mds_iters"], dwell_ms=s["dwell_ms"],
                # replay determinism needs admission control out of the
                # way: deep queue, no shedding, per-request deadlines only
                queue_depth=max(256, 4 * n_expected * ra["load_scale"]),
                shed_watermark=0.0,
                default_deadline_s=s["deadline_s"],
                feature_cache_size=4 * n_expected * ra["load_scale"] + 16,
                delta_featurize=True,
                affinity_batching=True,
            ),
        )

    with _bench_stage(tracer, "serve_replay:backend_init"):
        engine = ServeEngine(_cfg(), tracer=tracer)
    with _bench_stage(tracer, "serve_replay:trace_compile"):
        t0 = time.perf_counter()
        engine.warmup()
        compile_s = time.perf_counter() - t0

    # ---- record arm (or load an external recording) ----
    ref_hashes: dict = {}
    if ra["log"]:
        log_path = ra["log"]
        source = "log"
    else:
        fd, log_path = tempfile.mkstemp(suffix=".jsonl",
                                        prefix="af2tpu_workload_")
        os.close(fd)
        source = "synthetic-diurnal"
        stream = synthetic_diurnal(
            seed=s["seed"], requests=s["requests"],
            mean_rate=s["mean_rate"], period_s=s["period_s"],
            amplitude=s["amplitude"], buckets=s["buckets"],
            msa_depth=s["msa_depth"], deadline_s=s["deadline_s"],
        )
        recorder = WorkloadRecorder(
            path=log_path, record_raw=True,  # synthetic: raw is safe
            buckets=s["buckets"], msa_depth=s["msa_depth"],
        )
        fe = AsyncServeFrontend(engine, tracer=tracer)
        fe.add_submit_observer(recorder.on_submit)
        fe.add_observer(recorder.observe)
        with _bench_stage(tracer, "serve_replay:timed_record"):
            rec_pairs = build_replay(stream)  # original timing, 1x
            rec_results, rec_wall = _drive_stream(fe, rec_pairs)
        fe.close()
        rec_stats = engine.counters.snapshot()
        rec_ok = [r for r in rec_results if r.status == "ok"]
        rec_lat = Histogram()
        for r in rec_ok:
            rec_lat.observe(r.latency_s)
        rec_snap = (
            rec_lat.snapshot(unit_scale=1e3, digits=4)
            if rec_ok else {"count": 0}
        )
        rec_completeness = trace_completeness(
            tracer.events(),
            [r.trace_id for r in rec_results
             if r.status != "rejected" and r.trace_id],
        )
        recorder.write_summary({
            "requests": len(rec_results),
            "completed": len(rec_ok),
            "goodput_rps": round(len(rec_ok) / rec_wall, 3),
            "p50_ms": round(rec_snap.get("p50", 0.0), 1),
            "p95_ms": round(rec_snap.get("p95", 0.0), 1),
            "trace_complete_fraction": rec_completeness["fraction"],
            "ledger": {
                "feat_hits": rec_stats.get("serve.feat_hits", 0),
                "feat_delta": rec_stats.get("serve.feat_delta", 0),
                "feat_misses": rec_stats.get("serve.feat_misses", 0),
            },
        })
        recorder.close()
        # the byte-determinism reference: (seq, seed) -> atom14 digest
        for (_, req), r in zip(rec_pairs, rec_results):
            if r.status == "ok":
                ref_hashes[(req.seq, req.seed)] = hashlib.sha256(
                    r.atom14.tobytes()
                ).hexdigest()

    recording = load_workload(log_path)
    submits, ref_summary = recording["submits"], recording["summary"]
    reason = replayable_reason(submits)
    if reason is not None:
        raise RuntimeError(f"recording not replayable: {reason}")

    # ---- replay arm: fresh engine(s), fresh caches, fresh counters.
    # AF2TPU_SERVE_REPLAY_FLEET=N replays through an N-replica
    # FleetFrontend instead of a single cell — the per-cell contract
    # (byte determinism per (seq, seed), trace completeness across the
    # hop) must survive fleet routing; the reuse ledger is summed across
    # cells but its EXACT reproduction is only claimable single-cell
    # (load-balanced placement legitimately re-splits the feature
    # caches), so ledger_match stays a 1-replica gate ----
    fleet_n = max(1, _env_int("AF2TPU_SERVE_REPLAY_FLEET", 1))
    with _bench_stage(tracer, "serve_replay:replay_init"):
        replay_engines = [
            ServeEngine(_cfg(), params=engine.params, tracer=tracer)
            for _ in range(fleet_n)
        ]
        replay_engine = replay_engines[0]
        for eng in replay_engines:
            eng.warmup()
    if fleet_n > 1:
        from alphafold2_tpu.serve import FleetFrontend

        frontend = FleetFrontend(replay_engines, tracer=tracer)
    else:
        frontend = AsyncServeFrontend(replay_engine, tracer=tracer)
    with _bench_stage(tracer, "serve_replay:timed_run"):
        pairs = build_replay(
            submits, time_warp=ra["time_warp"],
            load_scale=ra["load_scale"],
        )
        results, wall = _drive_stream(frontend, pairs)
    frontend.close()
    stats: dict = {}
    for eng in replay_engines:
        for k, v in eng.counters.snapshot().items():
            stats[k] = stats.get(k, 0) + v
    _PHASE["name"] = "serve_replay:record"

    ok = [r for r in results if r.status == "ok"]
    lat = Histogram()
    for r in ok:
        lat.observe(r.latency_s)
    lat_ms = lat.snapshot(unit_scale=1e3, digits=4) if ok else {"count": 0}
    completeness = trace_completeness(
        tracer.events(),
        [r.trace_id for r in results
         if r.status != "rejected" and r.trace_id],
    )
    replay_ledger = {
        "feat_hits": stats.get("serve.feat_hits", 0),
        "feat_delta": stats.get("serve.feat_delta", 0),
        "feat_misses": stats.get("serve.feat_misses", 0),
    }
    # compare on the featurize-reuse keys only: recording summaries may
    # carry extra ledger entries (serve-async adds cache_hits/dedup),
    # but exact replay is claimed over the deterministic feat_* classes
    ref_ledger = (ref_summary or {}).get("ledger")
    if ref_ledger is not None:
        ref_ledger = {k: ref_ledger.get(k, 0) for k in replay_ledger}

    # byte determinism: every replayed (seq, seed) the record arm also
    # completed must produce byte-identical atom14 (checked on a bounded
    # sample; only meaningful with in-process reference hashes)
    bytes_identical = None
    if ref_hashes:
        compared = matched = 0
        for (_, req), r in zip(pairs, results):
            if r.status != "ok" or compared >= 32:
                continue
            ref = ref_hashes.get((req.seq, req.seed))
            if ref is None:
                continue
            compared += 1
            if hashlib.sha256(r.atom14.tobytes()).hexdigest() == ref:
                matched += 1
        if compared:
            bytes_identical = 1.0 if matched == compared else round(
                matched / compared, 4
            )

    with _bench_stage(tracer, "serve_replay:overhead_probe"):
        overhead = _recorder_overhead_probe(replay_engine, s)

    hists = {
        (n[:-2] + "_ms" if n.endswith("_s") else n): snap
        for n, snap in {
            **replay_engine.histogram_snapshots(unit_scale=1e3),
            **frontend.histogram_snapshots(unit_scale=1e3),
        }.items()
    }
    hists["latency_e2e_ms"] = lat_ms

    record = {
        "metric": _serve_replay_metric(s, ra),
        "value": (
            round(sum(len(r.seq) for r in ok) / wall, 1)
            if wall > 0 else 0.0
        ),
        "unit": "residues/sec",
        "mode": "serve-replay",
        "source": source,
        "time_warp": ra["time_warp"],
        "load_scale": ra["load_scale"],
        "workload_log": log_path,
        "p50_ms": round(lat_ms.get("p50", 0.0), 1),
        "p95_ms": round(lat_ms.get("p95", 0.0), 1),
        "goodput_rps": round(len(ok) / wall, 3) if wall > 0 else 0.0,
        "requests": len(results),
        "completed": len(ok),
        "rejected": sum(1 for r in results if r.status == "rejected"),
        "deadline_misses": sum(
            1 for r in results if r.status == "deadline_exceeded"
        ),
        "reuse_ledger": {
            "replay": replay_ledger,
            **({"record": ref_ledger} if ref_ledger else {}),
        },
        "trace": completeness,
        "trace_complete_fraction": completeness["fraction"],
        "recorder_overhead": overhead,
        "recorder_overhead_frac": overhead["overhead_frac"],
        "histograms": hists,
        "dispatches": stats.get("sched.dispatches", 0),
        "compiles": engine.counters.snapshot().get("serve.compiles", 0),
        "compile_s": round(compile_s, 1),
        "device": jax.devices()[0].device_kind,
        "pipeline": replay_engine.pipeline_desc,
    }
    # comparability variant key, carried only when non-default (an
    # external log or warped/scaled stream measures a different offered
    # workload than the flagship synthetic roundtrip)
    if ra["log"] or ra["time_warp"] != 1.0 or ra["load_scale"] != 1:
        record["replay"] = (
            f"warp{ra['time_warp']:g}-scale{ra['load_scale']}"
            + ("-log" if ra["log"] else "")
        )
    if fleet_n > 1:
        # comparability variant key, like the serve-fleet records: an
        # N-cell replay measures a different serving topology
        record["replicas"] = fleet_n
    # the loop's structural gates: exact reuse-ledger reproduction is
    # only claimable at 1x load (scaled copies are new work by design)
    # through one cell (fleet placement re-splits the feature caches)
    if ref_ledger is not None and ra["load_scale"] == 1 and fleet_n == 1:
        record["ledger_match"] = (
            1.0 if replay_ledger == ref_ledger else 0.0
        )
    if bytes_identical is not None:
        record["replay_bytes_identical"] = bytes_identical
    if ref_summary:
        for k in ("goodput_rps", "p50_ms", "p95_ms"):
            if ref_summary.get(k):
                record[f"record_{k}"] = ref_summary[k]
        if ref_summary.get("goodput_rps") and record["goodput_rps"]:
            record["replay_vs_record_goodput"] = round(
                record["goodput_rps"] / ref_summary["goodput_rps"], 3
            )
    if _CLOCK["probe"] is not None:
        record["clock_probe"] = _CLOCK["probe"]
        if not _CLOCK["probe"]["ok"]:
            record["clock_suspect"] = True

    baseline_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "bench_serve_replay_baseline.json",
    )
    vs, compared = 1.0, False
    if (
        os.path.exists(baseline_path)
        and not replay_config_overridden(ra)
        and not record.get("clock_suspect")
    ):
        with open(baseline_path) as f:
            base = json.load(f)
        if (
            base.get("value")
            and base.get("metric") == record["metric"]
            and base.get("device") == record["device"]
            and base.get("pipeline") == record.get("pipeline")
            and base.get("replay") == record.get("replay")
        ):
            vs = record["value"] / base["value"]
            compared = True
    record["vs_baseline"] = round(vs, 3)
    record["vs_baseline_valid"] = compared and not record.get("clock_suspect")
    if record.get("clock_suspect"):
        record["vs_baseline"] = 0.0

    if (
        os.environ.get("AF2TPU_SERVE_RECORD_BASELINE") == "1"
        and not replay_config_overridden(ra)
        and not record.get("clock_suspect")
    ):
        with open(baseline_path, "w") as f:
            json.dump(record, f, indent=2)
        print(
            f"recorded serve-replay baseline -> {baseline_path}",
            file=sys.stderr,
        )

    logger = _metrics_logger()
    if logger is not None:
        logger.log(0, stats)
        logger.log(0, {
            k: v for k, v in record.items()
            if isinstance(v, (int, float, str, bool))
        })
    engine.close()
    for eng in replay_engines:
        eng.close()
    if owns_tracer:
        tracer.close()
    if emit:
        _emit(record)
    return record


# ------------------------------------------------------------ serve-fleet ---


def _serve_fleet_sizes() -> dict:
    """The fleet-serving flagship: one open-loop offered stream through N
    replica cells behind the health-aware router, CPU-mesh sized like the
    other serve flagships. The arrival rate deliberately exceeds a single
    replica's capacity so the reference arm saturates and the N-replica
    goodput ratio measures real horizontal scaling, not idle slack.
    AF2TPU_SERVE_FLEET_* knobs rescale it — any of them set marks the
    record non-flagship (never baseline-compared)."""
    buckets = tuple(
        int(v) for v in os.environ.get(
            "AF2TPU_SERVE_FLEET_BUCKETS", "12,16"
        ).split(",") if v
    )
    return {
        "replicas": _env_int("AF2TPU_SERVE_FLEET_REPLICAS", 2),
        "buckets": buckets,
        "max_batch": _env_int("AF2TPU_SERVE_FLEET_MAX_BATCH", 2),
        "requests": _env_int("AF2TPU_SERVE_FLEET_REQUESTS", 48),
        "rate": float(os.environ.get("AF2TPU_SERVE_FLEET_RATE", 200.0)),
        "dup_fraction": 0.1,  # workload definition: repeat-sequence share
        "dim": _env_int("AF2TPU_SERVE_FLEET_DIM", 32),
        "depth": _env_int("AF2TPU_SERVE_FLEET_DEPTH", 1),
        "heads": _env_int("AF2TPU_SERVE_FLEET_HEADS", 2),
        "dim_head": _env_int("AF2TPU_SERVE_FLEET_DIM_HEAD", 16),
        "msa_depth": _env_int("AF2TPU_SERVE_FLEET_MSA_DEPTH", 2),
        "mds_iters": _env_int("AF2TPU_SERVE_FLEET_MDS_ITERS", 20),
        "dwell_ms": float(
            os.environ.get("AF2TPU_SERVE_FLEET_DWELL_MS", 10.0)
        ),
        # deep enough that the saturating backlog is queued, not shed:
        # admission rejections would pollute the goodput ratio
        "queue_depth": _env_int("AF2TPU_SERVE_FLEET_QUEUE_DEPTH", 96),
        "deadline_s": float(
            os.environ.get("AF2TPU_SERVE_FLEET_DEADLINE_S", 120.0)
        ),
        "seed": _env_int("AF2TPU_SERVE_FLEET_SEED", 0),
        # replica fault spec for the drill arm ("replica=1,at_s=2" kill /
        # "degrade=0.05" latency); empty = the built-in mid-run kill
        "fault": os.environ.get("AF2TPU_SERVE_FLEET_FAULT", ""),
    }


def fleet_config_overridden() -> bool:
    return any(k.startswith("AF2TPU_SERVE_FLEET_") for k in os.environ)


def _serve_fleet_metric(s: dict) -> str:
    return (
        f"serve-fleet residues/sec replicas={s['replicas']} "
        f"buckets={','.join(map(str, s['buckets']))} "
        f"max_batch={s['max_batch']} requests={s['requests']} "
        f"rate={s['rate']:g}/s dup={s['dup_fraction']:g} dim={s['dim']} "
        f"depth={s['depth']} msa_depth={s['msa_depth']} "
        f"mds_iters={s['mds_iters']} dwell_ms={s['dwell_ms']:g} "
        f"queue={s['queue_depth']}"
    )


def _drive_fleet_stream(frontend, pairs, timeout: float = 240.0) -> tuple:
    """Open-loop submission like :func:`_drive_stream`, but an unresolved
    handle is COUNTED instead of raising — the zero-silent-drops claim is
    the measurement, so a dropped request must surface as a number, not a
    bench crash. Returns (results-with-None-for-unresolved, wall_s,
    unresolved_count)."""
    t0 = time.perf_counter()
    handles = []
    for off, req in pairs:
        delay = t0 + off - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        handles.append(frontend.submit(req))
    results: list = []
    unresolved = 0
    for h in handles:
        try:
            results.append(h.result(timeout=timeout))
        except TimeoutError:
            unresolved += 1
            results.append(None)
    return results, time.perf_counter() - t0, unresolved


def bench_serve_fleet(emit: bool = True, tracer: Tracer | None = None) -> dict:
    """Multi-replica fleet bench: horizontal goodput scaling and the
    replica-death drill, in one record.

    Three arms over the SAME deterministic offered stream (seeded request
    list + Poisson arrival offsets, re-minted per arm so every arm owns
    fresh trace identities), all on engines sharing ONE parameter set:

    - **reference arm**: a 1-replica ``FleetFrontend`` — router overhead
      included, so the speedup ratio isolates horizontal scaling.
    - **fleet arm**: the N-replica fleet. ``fleet_speedup`` = fleet
      goodput / reference goodput, gated >= 1.6 at 2 replicas
      (FLEET_THRESHOLDS, observe/regress.py).
    - **drill arm**: the N-replica fleet with a mid-run replica kill
      (``AF2TPU_SERVE_FLEET_FAULT`` spec, or a built-in kill of the last
      replica at 40% of the fleet arm's wall). The claim is structural:
      every accepted request resolves to a terminal ServeResult
      (``accepted_unresolved`` == 0 — queued work on the dead replica
      re-routes to survivors, dispatched work completes), and trace
      reconstruction stays >= 99% ACROSS the router→replica traceparent
      hop, kill included.

    The record carries ``replicas`` always — it is a comparability
    variant key, so a 2-replica number never ratios a 4-replica
    baseline."""
    import numpy as np

    from alphafold2_tpu.config import (
        Config, DataConfig, ModelConfig, ServeConfig,
    )
    from alphafold2_tpu.observe import Histogram
    from alphafold2_tpu.observe.slo import (
        default_serve_slos, parse_slo_specs,
    )
    from alphafold2_tpu.observe.tracectx import trace_completeness
    from alphafold2_tpu.serve import FleetFaultPlan, ServeEngine, ServeRequest
    from alphafold2_tpu.serve.fleet import FleetFrontend, fleet_counter_zeros

    owns_tracer = tracer is None
    tracer = tracer if tracer is not None else _tracer()
    if not tracer.enabled:
        # the cross-hop trace-reconstruction gate needs live events even
        # when no trace file was requested
        tracer = Tracer(enabled=True)
        owns_tracer = True
    s = _serve_fleet_sizes()
    n_replicas = max(1, s["replicas"])

    with _bench_stage(tracer, "serve_fleet:backend_init"):
        cfg = Config(
            model=ModelConfig(
                dim=s["dim"], depth=s["depth"], heads=s["heads"],
                dim_head=s["dim_head"], max_seq_len=3 * s["buckets"][-1],
                bfloat16=jax.devices()[0].platform != "cpu",
            ),
            data=DataConfig(msa_depth=s["msa_depth"]),
            serve=ServeConfig(
                buckets=s["buckets"], max_batch=s["max_batch"],
                mds_iters=s["mds_iters"], dwell_ms=s["dwell_ms"],
                queue_depth=s["queue_depth"], shed_watermark=0.0,
                default_deadline_s=s["deadline_s"],
            ),
        )
        # one parameter set across the whole fleet: replica 0 initializes,
        # the rest alias (N replicas never re-initialize N times)
        engines = []
        for _ in range(n_replicas):
            engines.append(ServeEngine(
                cfg,
                params=engines[0].params if engines else None,
                tracer=tracer,
            ))
    with _bench_stage(tracer, "serve_fleet:trace_compile"):
        t0 = time.perf_counter()
        for eng in engines:
            eng.warmup()
        compile_s = time.perf_counter() - t0

    # the deterministic offered stream, shared by every arm: same (seq,
    # seed) list, same Poisson arrival offsets; each arm re-mints fresh
    # ServeRequest objects so its lifecycles own their trace identities
    rng = np.random.default_rng(s["seed"])
    lo = max(4, s["buckets"][0] // 2)
    alpha = "ACDEFGHIKLMNPQRSTVWY"
    spec: list = []  # [(seq, seed)]
    for i in range(s["requests"]):
        if spec and rng.random() < s["dup_fraction"]:
            spec.append(spec[int(rng.integers(0, len(spec)))])
        else:
            n = int(rng.integers(lo, s["buckets"][-1] + 1))
            spec.append((
                "".join(rng.choice(list(alpha), size=n)), i,
            ))
    offsets = np.cumsum(rng.exponential(1.0 / s["rate"], size=s["requests"]))

    def make_pairs() -> list:
        return [
            (float(off), ServeRequest(seq=q, seed=sd))
            for off, (q, sd) in zip(offsets, spec)
        ]

    slo_specs = parse_slo_specs(
        os.environ.get("AF2TPU_SLO_SPECS", "")
    ) or default_serve_slos(s["deadline_s"])

    # Prometheus exposition with every fleet counter zero-seeded from the
    # first scrape (the PR-13 absent-at-zero fix, fleet edition): the
    # collect closure reads whichever arm's fleet is live right now
    current: dict = {"fleet": None}
    metrics_server = exposition.serve_from_env(
        lambda: {
            **fleet_counter_zeros(n_replicas),
            **(
                current["fleet"].snapshot()
                if current["fleet"] is not None else {}
            ),
        }
    )

    def run_arm(arm_engines, fault=None, specs=None, stage="timed_run"):
        fleet = FleetFrontend(
            arm_engines, tracer=tracer, fault=fault, slo_specs=specs,
        )
        current["fleet"] = fleet
        try:
            with _bench_stage(tracer, f"serve_fleet:{stage}"):
                results, wall, unresolved = _drive_fleet_stream(
                    fleet, make_pairs()
                )
            snap = fleet.snapshot()
            slo = fleet.slo_summary()
        finally:
            fleet.close()
        resolved = [r for r in results if r is not None]
        ok = [r for r in resolved if r.status == "ok"]
        lat = Histogram()
        for r in ok:
            lat.observe(r.latency_s)
        lat_ms = lat.snapshot(unit_scale=1e3, digits=4) if ok else {"count": 0}
        completeness = trace_completeness(
            tracer.events(),
            [r.trace_id for r in resolved
             if r.status != "rejected" and r.trace_id],
        )
        return {
            "results": results,
            "ok": ok,
            "wall": wall,
            "unresolved": unresolved,
            "rejected": sum(
                1 for r in resolved if r.status == "rejected"
            ),
            "errors": sum(1 for r in resolved if r.status == "error"),
            "deadline_misses": sum(
                1 for r in resolved if r.status == "deadline_exceeded"
            ),
            "goodput_rps": round(len(ok) / wall, 3) if wall > 0 else 0.0,
            "residues_per_s": (
                round(sum(len(r.seq) for r in ok) / wall, 1)
                if wall > 0 else 0.0
            ),
            "lat_ms": lat_ms,
            "counters": snap,
            "slo": slo,
            "trace": completeness,
        }

    # reference arm: ONE replica behind the same router (overhead-equal)
    ref = run_arm(engines[:1], stage="timed_ref")
    # fleet arm: all N replicas, same offered stream
    fleet_arm = run_arm(
        engines, specs=slo_specs, stage="timed_fleet"
    )
    # drill arm: the same fleet with a mid-run replica kill. The built-in
    # default kills the LAST replica at 40% of the fleet arm's wall —
    # mid-backlog by construction, whatever this host's speed
    fault = FleetFaultPlan.from_spec(s["fault"]) or FleetFaultPlan(
        replica=n_replicas - 1,
        at_s=max(0.2, 0.4 * fleet_arm["wall"]),
    )
    drill = run_arm(engines, fault=fault, stage="timed_drill")
    _PHASE["name"] = "serve_fleet:record"

    speedup = (
        fleet_arm["goodput_rps"] / ref["goodput_rps"]
        if ref["goodput_rps"] else 0.0
    )
    # the cross-hop reconstruction claim covers the drill too: a kill must
    # not orphan lifecycles
    trace_fraction = min(
        fleet_arm["trace"]["fraction"], drill["trace"]["fraction"]
    )
    unresolved_total = (
        ref["unresolved"] + fleet_arm["unresolved"] + drill["unresolved"]
    )
    fleet_counters = fleet_arm["counters"]
    drill_counters = drill["counters"]

    record = {
        "metric": _serve_fleet_metric(s),
        "value": fleet_arm["residues_per_s"],
        "unit": "residues/sec",
        "mode": "serve-fleet",
        # ALWAYS carried: the comparability variant key fencing records
        # with different fleet widths from each other
        "replicas": n_replicas,
        "p50_ms": round(fleet_arm["lat_ms"].get("p50", 0.0), 1),
        "p95_ms": round(fleet_arm["lat_ms"].get("p95", 0.0), 1),
        "p99_ms": round(fleet_arm["lat_ms"].get("p99", 0.0), 1),
        "goodput_rps": fleet_arm["goodput_rps"],
        "ref_goodput_rps": ref["goodput_rps"],
        "fleet_speedup": round(speedup, 3),
        # replica dispatchers are OS threads: a single-core host cannot
        # express N-replica parallelism, so the regression gate applies
        # the fleet_speedup floor only where host_cpus >= 2
        "host_cpus": os.cpu_count() or 1,
        "requests": s["requests"],
        "completed": len(fleet_arm["ok"]),
        "rejected": fleet_arm["rejected"],
        "deadline_misses": fleet_arm["deadline_misses"],
        "dispatch_error_results": fleet_arm["errors"],
        # the structural gates: every accepted request reaches a terminal
        # result, in every arm, kill included
        "accepted_unresolved": drill["unresolved"],
        "dropped_requests": unresolved_total,
        "trace_complete_fraction": trace_fraction,
        "trace": {
            "fleet": fleet_arm["trace"],
            "drill": drill["trace"],
        },
        "fleet_counters": {
            k: v for k, v in sorted(fleet_counters.items())
            if k.startswith("fleet.")
        },
        "drill": {
            "fault": {
                "replica": fault.replica,
                "kind": fault.kind,
                "at_s": round(fault.at_s, 3),
                "fired": fault.fired,
            },
            "requests": s["requests"],
            "completed": len(drill["ok"]),
            "rejected": drill["rejected"],
            "unresolved": drill["unresolved"],
            "goodput_rps": drill["goodput_rps"],
            "rerouted": drill_counters.get("fleet.rerouted", 0),
            "steals": drill_counters.get("fleet.steals", 0),
            "drains": drill_counters.get("fleet.drains", 0),
            "replica_deaths": drill_counters.get(
                "fleet.replica_deaths", 0
            ),
        },
        "steals": fleet_counters.get("fleet.steals", 0),
        "rerouted": fleet_counters.get("fleet.rerouted", 0),
        "slo": fleet_arm["slo"],
        "compiles": sum(
            eng.counters.snapshot().get("serve.compiles", 0)
            for eng in engines
        ),
        "compile_s": round(compile_s, 1),
        "device": jax.devices()[0].device_kind,
        "pipeline": engines[0].pipeline_desc,
    }
    # per-replica goodput, flat beside the nested counters: the scrape
    # and obs_report's occupancy table address these by name
    for i in range(n_replicas):
        record[f"goodput_requests_replica{i}"] = fleet_counters.get(
            f"fleet.replica{i}.resolved_ok", 0
        )
    if _CLOCK["probe"] is not None:
        record["clock_probe"] = _CLOCK["probe"]
        if not _CLOCK["probe"]["ok"]:
            record["clock_suspect"] = True

    baseline_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "bench_serve_fleet_baseline.json",
    )
    vs, compared = 1.0, False
    if (
        os.path.exists(baseline_path)
        and not fleet_config_overridden()
        and not record.get("clock_suspect")
    ):
        with open(baseline_path) as f:
            base = json.load(f)
        if (
            base.get("value")
            and base.get("metric") == record["metric"]
            and base.get("device") == record["device"]
            and base.get("pipeline") == record.get("pipeline")
            # different fleet widths are different measurements
            and base.get("replicas") == record.get("replicas")
        ):
            vs = record["value"] / base["value"]
            compared = True
    record["vs_baseline"] = round(vs, 3)
    record["vs_baseline_valid"] = compared and not record.get("clock_suspect")
    if record.get("clock_suspect"):
        record["vs_baseline"] = 0.0

    if (
        os.environ.get("AF2TPU_SERVE_RECORD_BASELINE") == "1"
        and not fleet_config_overridden()
        and not record.get("clock_suspect")
    ):
        with open(baseline_path, "w") as f:
            json.dump(record, f, indent=2)
        print(
            f"recorded serve-fleet baseline -> {baseline_path}",
            file=sys.stderr,
        )

    logger = _metrics_logger()
    if logger is not None:
        logger.log(0, record["fleet_counters"])
        logger.log(0, {
            k: v for k, v in record.items()
            if isinstance(v, (int, float, str, bool))
        })
    for eng in engines:
        closer = getattr(eng, "close", None)
        if closer is not None:
            try:
                closer()
            except Exception:
                pass
    if metrics_server is not None:
        metrics_server.stop()
    if owns_tracer:
        tracer.close()
    if emit:
        _emit(record)
    return record


# ---------------------------------------------------------------- kernels ---


def _kernels_sizes() -> dict:
    """The kernels-microbench flagship: three tied-row and three axial
    attention shapes, sized so the fused kernels' interpret-mode grids stay
    small on CPU hosts (the committed CPU baseline is an interpret-mode
    record; TPU sessions re-record compiled numbers under the same metric
    machinery, keyed by device). AF2TPU_KERNEL_BENCH_* overrides mark the
    record non-flagship (never baseline-compared)."""
    return {
        "iters": _env_int("AF2TPU_KERNEL_BENCH_ITERS", 5),
        # (B, H, N, D) — the axial per-device pass after row-flattening
        "axial": ((2, 4, 128, 64), (1, 4, 256, 64), (1, 2, 384, 64)),
        # (B, R, N, H, D) — tied-row MSA attention
        "tied": ((1, 4, 128, 4, 32), (1, 8, 128, 4, 64),
                 (2, 16, 64, 2, 32)),
    }


def kernels_config_overridden() -> bool:
    return any(k.startswith("AF2TPU_KERNEL_BENCH_") for k in os.environ)


def _kernels_metric(s: dict) -> str:
    fmt = lambda shapes: ",".join("x".join(map(str, sh)) for sh in shapes)
    return (
        f"kernels fused-vs-stock speedup axial={fmt(s['axial'])} "
        f"tied={fmt(s['tied'])} iters={s['iters']}"
    )


def bench_kernels(emit: bool = True, tracer: Tracer | None = None) -> dict:
    """Microbench: fused Pallas kernels vs stock XLA dense attention.

    Times the in-repo fused kernels (ops/pallas/axial.py, tied_row.py)
    against the jnp dense formulation at three shapes each, forward only
    (the serving hot path). On CPU the fused side runs in Pallas interpret
    mode — the committed CPU record is a regression canary for the
    interpret path and the dispatch plumbing, not a speed claim; on TPU the
    same driver times the compiled kernels and the speedup is the real
    number. One JSON line, device/kernel-keyed, gated by
    scripts/bench_compare.py against bench_kernels_baseline.json."""
    import numpy as np

    from alphafold2_tpu.ops.pallas.axial import fused_attention
    from alphafold2_tpu.ops.pallas.tied_row import tied_row_attention

    owns_tracer = tracer is None
    tracer = tracer if tracer is not None else _tracer()
    s = _kernels_sizes()
    iters = s["iters"]

    def dense_axial(q, k, v, mask, scale):
        dots = jnp.einsum("bhid,bhjd->bhij", q, k).astype(jnp.float32) * scale
        dots = jnp.where(mask[:, None, None, :], dots, -1e9)
        p = jax.nn.softmax(dots, axis=-1).astype(q.dtype)
        out = jnp.einsum("bhij,bhjd->bhid", p, v)
        return jnp.where(mask[:, None, :, None], out, 0)

    def dense_tied(q, k, v, mask, shared, scale, tie_scale):
        qz = jnp.where(mask[..., None, None], q, 0)
        kz = jnp.where(mask[..., None, None], k, 0)
        vz = jnp.where(mask[..., None, None], v, 0)
        dots = (
            jnp.einsum("brihd,brjhd->bhij", qz, kz).astype(jnp.float32)
            * scale * tie_scale
        )
        dots = jnp.where(shared[:, None, None, :], dots, -1e9)
        p = jax.nn.softmax(dots, axis=-1).astype(q.dtype)
        return jnp.einsum("bhij,brjhd->brihd", p, vz)

    def timed(fn, args):
        out = fn(*args)
        jax.block_until_ready(out)  # compile + warm outside the timing
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters * 1e3  # ms

    rng = np.random.default_rng(0)
    shapes: list = []
    with _bench_stage(tracer, "kernels:backend_init"):
        jax.devices()

    with _bench_stage(tracer, "kernels:timed_run"):
        for b, h, n, d in s["axial"]:
            q, k, v = (
                jnp.asarray(rng.standard_normal((b, h, n, d)), jnp.float32)
                for _ in range(3)
            )
            mask = jnp.ones((b, n), bool).at[:, -max(1, n // 10):].set(False)
            scale = d**-0.5
            fused = jax.jit(lambda q, k, v, m, sc=scale: fused_attention(
                q, k, v, q_mask=m, kv_mask=m, sm_scale=sc))
            stock = jax.jit(lambda q, k, v, m, sc=scale: dense_axial(
                q, k, v, m, sc))
            fused_ms = timed(fused, (q, k, v, mask))
            stock_ms = timed(stock, (q, k, v, mask))
            shapes.append({
                "name": f"axial_{b}x{h}x{n}x{d}",
                "fused_ms": round(fused_ms, 3),
                "stock_ms": round(stock_ms, 3),
                "speedup": round(stock_ms / max(fused_ms, 1e-9), 4),
            })
        for b, r, n, h, d in s["tied"]:
            q, k, v = (
                jnp.asarray(
                    rng.standard_normal((b, r, n, h, d)), jnp.float32
                )
                for _ in range(3)
            )
            mask = jnp.ones((b, r, n), bool).at[
                :, :, -max(1, n // 10):
            ].set(False)
            shared = mask.any(1)  # (B, N) shared column mask
            scale = d**-0.5
            tie = float(r) ** -0.5
            fused = jax.jit(
                lambda q, k, v, m, sm, sc=scale, t=tie: tied_row_attention(
                    jnp.where(m[..., None, None], q, 0),
                    jnp.where(m[..., None, None], k, 0),
                    jnp.where(m[..., None, None], v, 0),
                    q_mask=sm, kv_mask=sm, sm_scale=sc, tie_scale=t,
                )
            )
            stock = jax.jit(lambda q, k, v, m, sm, sc=scale, t=tie:
                            dense_tied(q, k, v, m, sm, sc, t))
            fused_ms = timed(fused, (q, k, v, mask, shared))
            stock_ms = timed(stock, (q, k, v, mask, shared))
            shapes.append({
                "name": f"tied_{b}x{r}x{n}x{h}x{d}",
                "fused_ms": round(fused_ms, 3),
                "stock_ms": round(stock_ms, 3),
                "speedup": round(stock_ms / max(fused_ms, 1e-9), 4),
            })
    _PHASE["name"] = "kernels:record"

    speedups = [sh["speedup"] for sh in shapes]
    geomean = float(np.exp(np.mean(np.log(np.maximum(speedups, 1e-9)))))
    interpret = jax.default_backend() != "tpu"
    record = {
        "metric": _kernels_metric(s),
        "value": round(geomean, 4),
        "unit": "x-speedup",
        "mode": "kernels",
        "fused_ms_total": round(sum(sh["fused_ms"] for sh in shapes), 3),
        "stock_ms_total": round(sum(sh["stock_ms"] for sh in shapes), 3),
        "shapes": shapes,
        # interpret-mode fused timings are a canary, not a speed claim —
        # the flag keeps that explicit in the committed record
        "interpret": interpret,
        "device": jax.devices()[0].device_kind,
    }

    baseline_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "bench_kernels_baseline.json",
    )
    vs, compared = 1.0, False
    if os.path.exists(baseline_path) and not kernels_config_overridden():
        with open(baseline_path) as f:
            base = json.load(f)
        if (
            base.get("value")
            and base.get("metric") == record["metric"]
            and base.get("device") == record["device"]
        ):
            vs = record["value"] / base["value"]
            compared = True
    record["vs_baseline"] = round(vs, 3)
    record["vs_baseline_valid"] = compared

    if (
        os.environ.get("AF2TPU_KERNEL_RECORD_BASELINE") == "1"
        and not kernels_config_overridden()
    ):
        with open(baseline_path, "w") as f:
            json.dump(record, f, indent=2)
        print(
            f"recorded kernels baseline -> {baseline_path}", file=sys.stderr
        )

    logger = _metrics_logger()
    if logger is not None:
        logger.log(0, {
            k: v for k, v in record.items()
            if isinstance(v, (int, float, str, bool))
        })
    if owns_tracer:
        tracer.close()
    if emit:
        _emit(record)
    return record


def bench_mode(argv=None) -> str:
    """The bench mode: 'train' (default flagship step bench), 'serve'
    (closed-loop batched engine), 'serve-async' (open-loop frontend),
    'serve-scan' (variant-scan fast lane vs cold path), 'serve-replay'
    (workload record→replay roundtrip; also takes ``--time-warp``,
    ``--load-scale`` and ``--replay-log``; ``AF2TPU_SERVE_REPLAY_FLEET=N``
    replays against an N-replica fleet), 'serve-fleet' (N replica cells
    behind the health-aware router: scaling + replica-death drill) or
    'kernels' (fused-vs-stock attention microbench).
    Spelled ``--mode serve`` / ``--mode=serve-async`` or AF2TPU_BENCH_MODE."""
    args = sys.argv[1:] if argv is None else argv
    for i, a in enumerate(args):
        if a == "--mode" and i + 1 < len(args):
            return args[i + 1]
        if a.startswith("--mode="):
            return a.split("=", 1)[1]
    return os.environ.get("AF2TPU_BENCH_MODE", "train")


def _failure_record(msg: str) -> dict:
    """Diagnostic record: value 0.0 + an ``error`` field is unambiguous
    ("no measurement"), but stays parseable for the driver."""
    return {
        "metric": _metric(),
        "value": 0.0,
        "unit": "pairs/sec",
        "vs_baseline": 0.0,
        "vs_baseline_valid": False,
        "error": msg,
        "phase": _PHASE["name"],
    }


def _phase_failure_msg() -> str:
    """Deadline message that says WHICH phase died — 'backend_init' is a
    device that never came up, 'trace_compile' is a too-slow/hung compile,
    'warmup/timed' is a run that is genuinely too slow for the budget."""
    phase = _PHASE["name"]
    if "backend_init" in phase:
        detail = "backend init never returned"
    elif "trace_compile" in phase:
        detail = "compile exceeded the remaining budget"
    elif "run" in phase:
        detail = "compiled run too slow for the remaining budget"
    else:
        detail = "died before touching the backend"
    return (
        f"deadline {DEADLINE}s exceeded during phase '{phase}': {detail}; "
        "raise AF2TPU_BENCH_DEADLINE for bigger configs"
    )


import threading

_EMIT_LOCK = threading.Lock()
_emitted = False


def _emit(record: dict) -> None:
    """Write the one JSON result line. First writer wins: the deadline
    thread and the main thread can race near the deadline, and the driver
    must never see two records."""
    global _emitted
    with _EMIT_LOCK:
        if _emitted:
            return
        _emitted = True
        sys.stdout.write(json.dumps(record) + "\n")
        sys.stdout.flush()


_MODES = {
    "train": main,
    "serve": bench_serve,
    "serve-async": bench_serve_async,
    "serve-scan": bench_serve_scan,
    "serve-replay": bench_serve_replay,
    "serve-fleet": bench_serve_fleet,
    "kernels": bench_kernels,
}


def _deadline_thread() -> None:
    """The one overall deadline. A backend call can hang inside C++ where
    no Python exception reaches it; a daemon thread + os._exit is the only
    escape that still gets the failure record onto stdout. When it fires
    the run has FAILED: exit code 1."""
    time.sleep(max(0.0, DEADLINE - (time.monotonic() - _T0)))
    _emit(_failure_record(_phase_failure_msg()))
    os._exit(1)


def run(argv=None) -> int:
    """Run one bench mode in this process; 0 on a measurement, 1 on any
    failure (after leaving the failure record on stdout)."""
    alphafold2_tpu.enable_compile_cache()
    # crash flight recorder (observe/flightrec.py): opt-in via
    # AF2TPU_FLIGHTREC_DIR — rings of recent telemetry dumped as a
    # scrubbed incident file on dispatch error / SIGTERM
    rec = flightrec.maybe_install_from_env()
    if rec is not None:
        flightrec.install_signal_handler(rec)
    if DEADLINE > 0:
        threading.Thread(target=_deadline_thread, daemon=True).start()
    mode = bench_mode(argv)
    try:
        _MODES[mode]()
    except Exception as e:
        import traceback

        traceback.print_exc()
        _emit(_failure_record(f"{type(e).__name__}: {e}"))
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(run())
