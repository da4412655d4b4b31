"""Compile the main path's kernels for a described TPU v5e, nothing attached.

Interpret mode (every other kernel test here) skips Mosaic, and the lowering
gate (test_pallas_lowering.py) stops before the chip's compiler. These cases
go all the way: ``jit(f).lower(shapes on a described v5e chip).compile()``
raises what the chip's compiler would raise — a slice not aligned to the
tiling, more fast memory than a kernel may use, a program that does not fit
16 GB. Shapes are the flagship's (bench.py / chip_smoke.py): dim_head 64,
8 heads, crop 256, MSA 16 x 256. A compile that passes here is not a chip
run; chip_smoke.py is.

The topology is described inside a module-scoped fixture and nowhere else:
only one process may load the TPU's library, every xdist worker imports this
file, and a file that asked at import time would give the workers different
tests to collect. Keep these cases in this one file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off around the
    compiles: an executable for a described chip is written to the cache but
    cannot be read back without one, and the next run would warn."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    """Compile ``fn`` for the described chip at ``shapes`` — (shape, dtype)
    pairs — and return the compiled program's text."""
    args = [
        jax.ShapeDtypeStruct(s, jnp.dtype(d), sharding=sharding)
        for s, d in shapes
    ]
    return jax.jit(fn).lower(*args).compile().as_text()


def _grad_of(fn):
    """Gradient w.r.t. q, k, v of a scalar of ``fn(q, k, v, *rest)``."""

    def loss(q, k, v, *rest):
        out = fn(q, k, v, *rest)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    return jax.grad(loss, argnums=(0, 1, 2))


# ------------------------------------------------------------ the kernels ---


def _fused_axial(q, k, v, mask=None):
    from alphafold2_tpu.ops.pallas.axial import fused_attention

    return fused_attention(
        q, k, v, q_mask=mask, kv_mask=mask, sm_scale=q.shape[-1] ** -0.5,
        interpret=False,
    )


def _tied_row(q, k, v, mask=None):
    from alphafold2_tpu.ops.pallas.tied_row import tied_row_attention

    return tied_row_attention(
        q, k, v, q_mask=mask, kv_mask=mask, sm_scale=q.shape[-1] ** -0.5,
        interpret=False,
    )


def _stock_flash(q, k, v, kv_mask=None):
    """The stock kernel through the repo's wrapper (padding to 128, segment
    ids and the blocks ``block_sizes_for`` picks for the shape included), as
    Attention.__call__ reaches it. The scoped-VMEM limit is the fence on
    those blocks, and only this compile sees it."""
    from alphafold2_tpu.ops.flash import flash_attention

    return flash_attention(q, k, v, kv_mask=kv_mask, sm_scale=0.125)


def _block_sparse(n, block=128):
    from alphafold2_tpu.ops.sparse import (
        BlockSparseConfig, block_sparse_attention_pallas,
    )

    layout = BlockSparseConfig(
        block_size=block, num_local_blocks=4, num_global_blocks=1,
        num_random_blocks=None,
    ).layout(n)

    def fn(q, k, v, mask=None):
        return block_sparse_attention_pallas(
            q, k, v, layout, block, mask=mask, interpret=False
        )

    return fn


AXIAL = (256, 8, 256, 64)  # (B*N rows, heads, N, dim_head) at crop 256
AXIAL_MESH_HALF = (128, 8, 256, 64)  # per device under dp2 x sp2
AXIAL_CROP384 = (384, 8, 384, 64)  # 384 = 3 x 128: one block of 384
TIED = (1, 16, 256, 8, 64)  # (B, MSA rows, N, heads, dim_head)
PAIR_Q = (1, 8, 256 * 256, 64)  # the flat pair stream as queries
MSA_KV = (1, 8, 16 * 256, 64)  # the flat MSA stream as keys/values
# cross_attn_compress_ratio=3 pools 4096 keys to 1366; the wrapper pads
# them to 1408 with mask-excluded positions
MSA_KV_COMPRESSED = (1, 8, -(-16 * 256 // 3), 64)

CASES = {
    "fused_axial_bf16": (_fused_axial, [(AXIAL, "bfloat16")] * 3),
    "fused_axial_f32": (_fused_axial, [(AXIAL, "float32")] * 3),
    "tied_row_bf16": (_tied_row, [(TIED, "bfloat16")] * 3),
    "tied_row_f32": (_tied_row, [(TIED, "float32")] * 3),
    "stock_flash_axial": (_stock_flash, [(AXIAL, "bfloat16")] * 3),
    "stock_flash_axial_mesh_half": (
        _stock_flash, [(AXIAL_MESH_HALF, "bfloat16")] * 3,
    ),
    "stock_flash_axial_crop384": (
        _stock_flash, [(AXIAL_CROP384, "bfloat16")] * 3,
    ),
    "stock_flash_cross": (  # pair_from_msa
        _stock_flash,
        [(PAIR_Q, "bfloat16"), (MSA_KV, "bfloat16"), (MSA_KV, "bfloat16")],
    ),
    "stock_flash_cross_msa_from_pair": (
        _stock_flash,
        [(MSA_KV, "bfloat16"), (PAIR_Q, "bfloat16"), (PAIR_Q, "bfloat16")],
    ),
    "stock_flash_cross_f32": (  # float32 operands: K/V tiles twice the bytes
        _stock_flash,
        [(PAIR_Q, "float32"), (MSA_KV, "float32"), (MSA_KV, "float32")],
    ),
    # a key axis of 2,048 is the largest the rule takes whole: forward and
    # dkv hold a (·, 2048) key tile, against many query blocks and against one
    "stock_flash_cross_keys_2048": (
        _stock_flash,
        [(PAIR_Q, "bfloat16")] + [((1, 8, 2048, 64), "bfloat16")] * 2,
    ),
    "stock_flash_one_q_block_keys_2048": (  # MSA 4 x 128 against 2,048 keys
        _stock_flash,
        [((1, 8, 512, 64), "bfloat16")] + [((1, 8, 2048, 64), "bfloat16")] * 2,
    ),
    "stock_flash_compressed_cross": (
        _stock_flash,
        [(PAIR_Q, "bfloat16"), (MSA_KV_COMPRESSED, "bfloat16"),
         (MSA_KV_COMPRESSED, "bfloat16"),
         ((1, MSA_KV_COMPRESSED[2]), "bool")],  # (B, Nk) pooled key mask
    ),
    "block_sparse_n512": (
        lambda *a: _block_sparse(512)(*a), [((1, 4, 512, 64), "float32")] * 3,
    ),
    "block_sparse_n1024": (
        lambda *a: _block_sparse(1024)(*a),
        [((1, 4, 1024, 64), "float32")] * 3,
    ),
}

# one masked, odd-length case per in-repo kernel (block-sparse lengths are
# block multiples by construction: its odd part is the masked tail)
MASKED = {
    "fused_axial_masked_odd": (
        _fused_axial,
        [((4, 8, 200, 64), "float32")] * 3 + [((4, 200), "bool")],
    ),
    "tied_row_masked_odd": (
        _tied_row,
        [((1, 5, 200, 8, 64), "float32")] * 3 + [((1, 200), "bool")],
    ),
    "block_sparse_masked": (
        lambda *a: _block_sparse(512)(*a),
        [((1, 4, 512, 64), "float32")] * 3 + [((1, 512), "bool")],
    ),
}


@pytest.fixture
def on_tpu_branch(monkeypatch):
    """ops/flash.py asks ``jax.default_backend()``, which says ``cpu`` here;
    the test steers it to the branch the chip takes."""
    from alphafold2_tpu.ops import flash

    monkeypatch.setattr(flash, "flash_available", lambda: True)


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, on_tpu_branch, name, direction):
    fn, shapes = CASES[name]
    text = _compile(fn if direction == "fwd" else _grad_of(fn), one_chip,
                    *shapes)
    assert "tpu_custom_call" in text  # the kernel is in the program


@pytest.mark.parametrize("name", sorted(MASKED))
def test_masked_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = MASKED[name]
    text = _compile(_grad_of(fn), one_chip, *shapes)  # fwd + dq + dk/dv
    assert "tpu_custom_call" in text


def test_mis_tiled_kernel_is_refused(one_chip):
    """The negative control of analysis/lowering.py, taken to the chip's
    compiler: a (1, block) row block on a (rows, n) array — the bug class
    that killed the first on-chip attempt — must not compile. If it does,
    the cases above prove nothing."""
    from jax.experimental import pallas as pl

    from alphafold2_tpu.analysis.lowering import _is_mosaic_tiling_rejection

    def kern(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    def f(x):
        return pl.pallas_call(
            kern,
            out_shape=jax.ShapeDtypeStruct((4, 512), jnp.float32),
            grid=(4,),
            in_specs=[pl.BlockSpec((1, 512), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((1, 512), lambda i: (i, 0)),
        )(x)

    with pytest.raises(Exception) as err:
        _compile(f, one_chip, ((4, 512), "float32"))
    assert _is_mosaic_tiling_rejection(err.value), err.value


# ------------------------------------------------------- the whole program ---


@pytest.mark.parametrize("layout", ["one_chip", "dp2_sp2"])
def test_flagship_train_step_compiles_for_v5e(topo, one_chip, monkeypatch,
                                              layout):
    """The whole jitted train step of chip_smoke.py at the flagship's sizes,
    for one described chip and for the dp2 x sp2 mesh with ring context
    parallelism (global batch 2): the TPU branch's kernels are in it, it fits
    16 GB, and on the mesh the kernels sit inside a shard_map (GSPMD refuses
    to partition a Mosaic kernel) beside the all-reduce and the ring's
    collective-permute."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import chip_smoke
    from alphafold2_tpu.data.pipeline import make_dataset
    from alphafold2_tpu.train.loop import (
        build_model, make_train_step, tiny_init_state,
    )

    # the program asks jax.default_backend() which branch to take; it says
    # "cpu" here, so the test steers it to what the chip would answer
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    if layout == "one_chip":
        cfg = chip_smoke.train_config(chip_smoke.FLAGSHIP, steps=4)
        mesh, repl, data = None, one_chip, one_chip
    else:
        cfg = chip_smoke.train_config(
            {**chip_smoke.FLAGSHIP, "batch": 2}, steps=4, dp=2, sp=2
        )
        mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("dp", "sp"))
        repl = NamedSharding(mesh, P())
        data = NamedSharding(mesh, P("dp"))

    def shapes(tree, sharding):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=sharding), tree)

    sample = next(iter(make_dataset(cfg.data, seed=0)))
    model = build_model(cfg)
    state = jax.eval_shape(lambda: tiny_init_state(cfg, model, sample))
    rng = jax.eval_shape(lambda: jax.random.key(1))
    compiled = make_train_step(model, mesh, numerics_mode="norms").lower(
        shapes(state, repl),
        shapes({k: jnp.asarray(v) for k, v in sample.items()}, data),
        jax.ShapeDtypeStruct(rng.shape, rng.dtype, sharding=repl),
    ).compile()

    text = compiled.as_text()
    assert "tpu_custom_call" in text
    ma = compiled.memory_analysis()
    per_device = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                  + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert per_device < 15.75 * 2**30  # what the compiler leaves of 16 GB
    if mesh is not None:
        assert " all-reduce(" in text or " all-reduce-start(" in text
        assert (" collective-permute(" in text
                or " collective-permute-start(" in text)
        # the ring's blocks are the flash kernel's, not dense jnp blocks: no
        # visiting block's logits (float32 or bf16) are in the program
        assert any("tpu_custom_call" in line and "/ring_block/" in line
                   for line in text.splitlines())
        assert "[1,8,32768,2048]" not in text
        assert "[1,8,2048,32768]" not in text


@pytest.mark.parametrize("direction", ["pair_from_msa", "msa_from_pair"])
def test_ring_of_flash_blocks_compiles_for_v5e(topo, one_chip, on_tpu_branch,
                                               direction):
    """The mesh cell's cross-attention alone, forward and backward, on the
    described dp2 x sp2 mesh: a chip's blocks are 32,768 x 2,048 and 2,048 x
    32,768, and each of the two ring steps is the stock kernel's three calls
    at the blocks ``block_sizes_for`` gives (the scoped-VMEM limit is the
    fence on them), the key mask travelling as segment ids."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from alphafold2_tpu.parallel.seq_parallel import (
        sequence_parallel_attention,
    )

    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("dp", "sp"))
    nq, nk = PAIR_Q[2], MSA_KV[2]
    if direction == "msa_from_pair":
        nq, nk = nk, nq
    rows = NamedSharding(mesh, P("dp", None, "sp", None))
    q = jax.ShapeDtypeStruct((2, 8, nq, 64), jnp.bfloat16, sharding=rows)
    kv = jax.ShapeDtypeStruct((2, 8, nk, 64), jnp.bfloat16, sharding=rows)
    mask = jax.ShapeDtypeStruct(
        (2, nk), jnp.bool_, sharding=NamedSharding(mesh, P("dp", "sp")))

    def loss(q, k, v, mask):
        out = sequence_parallel_attention(
            q, k, v, mask=mask, mesh=mesh, impl="ring")
        return jnp.sum(out.astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv, mask).compile().as_text()
    kernels = [line for line in text.splitlines()
               if "tpu_custom_call" in line and "/ring_block/" in line]
    assert len(kernels) == 6  # forward, dq, dkv for each of the two steps
    assert sum("flash_mha_bwd_dq" in line for line in kernels) == 2
    assert sum("flash_mha_bwd_dkv" in line for line in kernels) == 2
    assert f"[1,8,{nq // 2},{nk // 2}]" not in text  # no block's logits
    assert " collective-permute-start(" in text or (
        " collective-permute(" in text)


# --------------------------------------------------- the language model ---


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("length", [8192, 8192 - 40])
def test_causal_latent_core_compiles_for_v5e(one_chip, monkeypatch, length,
                                             direction):
    """The language-model cell's attention: causal, 2 x 32 heads, q/k heads
    of 192 against v heads of 128 as they are, through the splash kernel at
    the blocks ``ops/mla.py`` ``splash_block_sizes`` gives (Mosaic takes a
    192-deep score product; the scoped-VMEM limit is the fence on the
    blocks), forward and the fused backward; also at a length that is no
    multiple of 128."""
    from alphafold2_tpu.ops import mla

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _compile(
        mla.causal_core if direction == "fwd" else _grad_of(mla.causal_core),
        one_chip, ((2, 32, length, 192), "bfloat16"),
        ((2, 32, length, 192), "bfloat16"), ((2, 32, length, 128), "bfloat16"))
    assert "tpu_custom_call" in text and "splash_mha_fwd" in text
    if direction == "bwd":  # one backward kernel: dq comes with dk and dv
        assert "splash_mha_dkv" in text and "splash_mha_dq" not in text
    assert "f32[2,32,8192,8192]" not in text  # no dense logits anywhere
    assert "256]" not in text  # no head padded to 256


def _kernel_scopes(text, kernel):
    """The scope of every call of the Pallas kernel ``kernel`` in a compiled
    program's text: XLA names the custom call after the kernel
    (``kernel.N``), as a trace's device operations are named."""
    from alphafold2_tpu.observe.profiler import instruction_scopes

    return [scope for name, scope in instruction_scopes(text)[1].items()
            if name.split(".")[0] == kernel]


@pytest.fixture(scope="module")
def lm_step(one_chip):
    """The whole jitted train step of the benchmark's language-model cell
    (576 M parameters, 2 x 8,192 tokens) compiled once for one described
    chip: its text, its parameter count and its bytes on the device."""
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from alphafold2_tpu.data.pipeline import make_dataset
    from alphafold2_tpu.train import loop
    from benchmark.harness import common, train_lm

    with pytest.MonkeyPatch.context() as patched:
        patched.setattr(jax, "default_backend", lambda: "tpu")
        resolved = common.resolve("train_kanana2_ep8_seq8k")
        cfg = train_lm.program_config(
            resolved["config"], resolved["traffic"], 1)
        task = loop.build_task(cfg)
        sample = next(iter(make_dataset(
            cfg.data, vocab_size=cfg.lm.vocab_size)))

        def shapes(tree):
            return jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=one_chip), tree)

        state = jax.eval_shape(
            lambda: loop.tiny_init_state(cfg, task, sample))
        rng = jax.eval_shape(lambda: jax.random.key(1))
        compiled = loop.make_train_step(
            task, None, numerics_mode="norms").lower(
            shapes(state),
            shapes({k: jnp.asarray(v) for k, v in sample.items()}),
            jax.ShapeDtypeStruct(rng.shape, rng.dtype, sharding=one_chip),
        ).compile()
    ma = compiled.memory_analysis()
    return {
        "text": compiled.as_text(),
        "parameters": sum(x.size for x in jax.tree.leaves(state.params)),
        "bytes": (ma.argument_size_in_bytes + ma.output_size_in_bytes
                  + ma.temp_size_in_bytes - ma.alias_size_in_bytes),
    }


def test_lm_train_step_compiles_for_v5e(lm_step):
    """The whole jitted train step of the benchmark's language-model cell
    for one described chip: the splash kernels and XLA's ragged-product
    kernels are in it, the stock flash kernel and dense 8,192^2 logits are
    not, the forward kernel runs once a layer (the layers' recomputation
    finds its output and log-sum-exp kept), and weights + Adam + activations
    fit the 15.75 GiB the compiler leaves."""
    text = lm_step["text"]
    assert lm_step["parameters"] == 575_955_968
    assert len(_kernel_scopes(text, "splash_mha_fwd_residuals")) == 5
    assert len(_kernel_scopes(text, "splash_mha_dkv_no_residuals")) == 5
    assert "flash_attention" not in text and "flash_mha_bwd" not in text
    assert "ragged-dot" in text  # the grouped product is a kernel, not dense
    assert "32,8192,8192]" not in text and "64,8192,8192]" not in text
    print(f"compiled step, bytes on the device: {lm_step['bytes']}")
    assert 8e9 < lm_step["bytes"] < 15.75 * 2**30


def _inferred_scopes_hold(text, attention):
    """What ``observe.profiler.infer_scopes`` makes of a compiled step's
    text: a path the text gives is never replaced; at most 0.5% of what
    executes (the entry, ``while`` bodies and conditions, a switch's
    branches; parameters, tuples and their elements, constants and bitcasts
    left out) stays without one; every kernel XLA names itself
    (``ragged-dot*``) lands under the experts and under no other block of
    the expert layer; every layout ``copy`` without a name that a splash
    kernel consumes, at most two instructions between them, lands under one
    of ``attention``.
    Returns (executing, read from the text, those copies)."""
    import re

    from alphafold2_tpu.observe import profiler
    from benchmark.readers.scope_paths_device_ms import holds, names

    graph = profiler.instruction_graph(text)[1]
    scopes, inferred = profiler.infer_scopes(graph)
    for name, at in graph.items():
        if profiler.read_scope(at.op_name):
            assert scopes[name] == at.op_name and name not in inferred
    runs = set(re.findall(
        r"(?:^ENTRY |\b(?:body|condition|true_computation|"
        r"false_computation)=)%([\w.\-]+)", text, re.M))
    for branches in re.findall(r"branch_computations=\{([^}]*)\}", text):
        runs.update(re.findall(r"%([\w.\-]+)", branches))
    executing = {
        name: at for name, at in graph.items() if at.computation in runs
        and at.opcode not in ("parameter", "tuple", "get-tuple-element",
                              "constant", "bitcast")}
    read = [n for n, at in executing.items()
            if profiler.read_scope(at.op_name)]
    left = [n for n in executing
            if not profiler.read_scope(scopes.get(n, ""))]
    assert len(left) <= 0.005 * len(executing), left
    kernels = [n for n in graph if n.startswith("ragged-dot")]
    assert kernels and all(n in executing for n in kernels)
    for name in kernels:
        found = names(scopes[name])
        assert inferred[name] == "kin" and holds(found, "moe/experts"), name
        assert not any(holds(found, f"moe/{other}") for other in
                       ("router", "dispatch", "combine", "shared")), name
    users = {}
    for name, at in graph.items():
        for operand in at.operands:
            users.setdefault(operand, []).append(name)
    copies = []
    for name, at in executing.items():
        if at.opcode != "copy" or at.op_name:
            continue
        level = [name]
        for _ in range(3):  # the copy, a pad or a bitcast or two, the kernel
            level = [u for n in level for u in users.get(n, ())]
            if any(u.startswith("splash_mha_") for u in level):
                copies.append(name)
                assert any(holds(names(scopes[name]), path)
                           for path in attention), name
                break
    return executing, read, copies


def test_lm_step_instructions_find_a_scope(lm_step):
    """Two thirds of what the compiled step executes carry no ``op_name``
    (the compiler's moves between memory spaces, layout copies, the kernels
    XLA writes itself); the program's record takes those scopes from the
    instructions around them."""
    executing, read, copies = _inferred_scopes_hold(
        lm_step["text"], ["mla_attn"])
    assert len(executing) > 5000 and len(read) < 0.4 * len(executing)
    # q, k and v heads-first for the splash kernels, forward and backward
    assert len(copies) >= 20


# --------------------------------------- the grouped-query language model ---


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("window", [None, 4096])
def test_grouped_window_core_compiles_for_v5e(one_chip, monkeypatch, window,
                                              direction):
    """The second language-model cell's attention: 28 query heads over 4
    key/value heads of 128 at 16,384 positions, under the causal mask and
    under the window of 4,096, through the splash kernel at the blocks
    ``splash_block_sizes`` gives: forward, and the two-kernel backward (the
    fused one's partial dq would be 1.9 GB). No dense logits, and no keys
    broadcast to the query heads."""
    import functools
    import re

    from alphafold2_tpu.ops import mla

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    core = functools.partial(mla.causal_core, window=window)
    text = _compile(
        core if direction == "fwd" else _grad_of(core), one_chip,
        ((1, 28, 16384, 128), "bfloat16"), ((1, 4, 16384, 128), "bfloat16"),
        ((1, 4, 16384, 128), "bfloat16"))
    assert "tpu_custom_call" in text and "splash_mha_fwd" in text
    if direction == "bwd":
        assert "splash_mha_dkv" in text and "splash_mha_dq" in text
    assert "16384,16384]" not in text  # no dense logits anywhere
    # nothing of k's or v's is broadcast to the 28 query heads
    assert not re.search(r"= bf16\[1,28,16384,128\]\S* broadcast\(", text)


def test_swa_lm_train_step_compiles_for_v5e(one_chip, monkeypatch):
    """The whole jitted train step of the benchmark's second language-model
    cell (371 M parameters, 1 x 16,384 tokens) for one described chip: splash
    kernels under both kinds of layer and XLA's ragged-product kernels are
    in it, each kernel once a layer (the forward too: the layers'
    recomputation finds its output and log-sum-exp kept), dense 16,384^2
    logits are not, and weights + Adam + activations fit the chip with room
    (under 14.5 GB of the 15.75 GiB the compiler leaves)."""
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from alphafold2_tpu.data.pipeline import make_dataset
    from alphafold2_tpu.train import loop
    from benchmark.harness import common, train_swa_lm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    resolved = common.resolve("train_smallthinker_ep8_seq16k")
    cfg = train_swa_lm.program_config(
        resolved["config"], resolved["traffic"], 1)
    task = loop.build_task(cfg)
    sample = next(iter(make_dataset(
        cfg.data, vocab_size=cfg.language_model().vocab_size)))
    assert sample["tokens"].shape == (1, 16384)

    def shapes(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), tree)

    state = jax.eval_shape(lambda: loop.tiny_init_state(cfg, task, sample))
    assert sum(x.size for x in jax.tree.leaves(state.params)) == 370_547_200
    rng = jax.eval_shape(lambda: jax.random.key(1))
    compiled = loop.make_train_step(task, None, numerics_mode="norms").lower(
        shapes(state), shapes({k: jnp.asarray(v) for k, v in sample.items()}),
        jax.ShapeDtypeStruct(rng.shape, rng.dtype, sharding=one_chip),
    ).compile()
    text = compiled.as_text()
    # the three kernels, each once a layer: one global layer's, three window
    # layers'
    for kernel in ("splash_mha_fwd_residuals", "splash_mha_dkv_no_residuals",
                   "splash_mha_dq_no_residuals"):
        scopes = _kernel_scopes(text, kernel)
        assert len(scopes) == 4, kernel
        assert sum("attn_global/core" in scope for scope in scopes) == 1
        assert sum("attn_window/core" in scope for scope in scopes) == 3
    assert "flash_attention" not in text
    assert "ragged-dot" in text  # the grouped product is a kernel, not dense
    assert "16384,16384]" not in text
    _inferred_scopes_hold(text, ["attn_global", "attn_window"])
    ma = compiled.memory_analysis()
    per_device = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                  + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    print(f"compiled step, bytes on the device: {per_device}")
    assert 6e9 < per_device < 14.5e9


# ------------------------------------------------ the hybrid language model ---


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_grouped_core_32_over_2_compiles_for_v5e(one_chip, monkeypatch,
                                                 direction):
    """The third language-model cell's attention layer: 32 query heads over
    2 key/value heads of 128 at 8,192 positions under the causal mask: a
    shape no other cell runs. The fused backward (its partial dq is 0.54 GB,
    under ``PARTIAL_DQ_BYTES``) over grouped heads: one backward kernel, no
    dq kernel; no dense logits, no keys broadcast to the query heads."""
    import re

    from alphafold2_tpu.ops import mla

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _compile(
        mla.causal_core if direction == "fwd" else _grad_of(mla.causal_core),
        one_chip, ((1, 32, 8192, 128), "bfloat16"),
        ((1, 2, 8192, 128), "bfloat16"), ((1, 2, 8192, 128), "bfloat16"))
    assert "tpu_custom_call" in text and "splash_mha_fwd" in text
    if direction == "bwd":
        assert "splash_mha_dkv" in text and "splash_mha_dq" not in text
    assert "8192,8192]" not in text
    assert not re.search(r"= bf16\[1,32,8192,128\]\S* broadcast\(", text)


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("length", [8192, 8192 - 40])
def test_state_space_scan_compiles_for_v5e(one_chip, monkeypatch, length,
                                           direction):
    """The third language-model cell's scan, 64 heads of 64 over 8 groups of
    128 state rows in chunks of 128, through ``ssm.ssd_scan`` on the TPU
    branch: the two kernels of ``ops/pallas/ssd.py`` (a length off the chunk
    grid padded first), and no decay matrix a head in HBM."""
    from alphafold2_tpu.ops import ssm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def scan(x, b, c, dt, a):
        return ssm.ssd_scan(x, dt, a, b, c, 128, jnp.bfloat16)[0]

    text = _compile(
        scan if direction == "fwd" else _grad_of(scan), one_chip,
        ((1, length, 64, 64), "bfloat16"), ((1, length, 8, 128), "bfloat16"),
        ((1, length, 8, 128), "bfloat16"), ((1, length, 64), "float32"),
        ((64,), "float32"))
    assert "ssd_chunk_fwd" in text
    assert ("ssd_chunk_bwd" in text) == (direction == "bwd")
    assert "8,8,128,128]" not in text and " while(" not in text


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("channels", [6144, 4352])
def test_causal_conv_compiles_for_v5e(one_chip, monkeypatch, channels,
                                      direction):
    """The two state-space cells' convolutions, 8,192 bfloat16 steps of
    6,144 and of 4,352 channels, 4 taps, through ``ssm.causal_conv`` on the
    TPU branch: the two kernels of ``ops/pallas/conv.py`` (a block of 32
    rows and the whole sequence within the scoped VMEM), and no padded
    copy of x."""
    from alphafold2_tpu.ops import ssm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _compile(
        ssm.causal_conv if direction == "fwd" else _grad_of(ssm.causal_conv),
        one_chip, ((1, 8192, channels), "bfloat16"),
        ((channels, 4), "float32"), ((channels,), "float32"))
    assert "causal_conv_fwd" in text
    assert ("causal_conv_bwd" in text) == (direction == "bwd")
    assert f",8195,{channels}]" not in text


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_sparse_core_compiles_for_v5e(one_chip, monkeypatch, direction):
    """The fifth language-model cell's sparse core, 32 query heads over 4
    key/value heads of 128 at 16,384 positions, every query's selection
    bit-packed, through ``dsa.sparse_core`` on the TPU branch: the three
    kernels of ``ops/pallas/dsa_core.py`` (a group's eight heads a grid
    step within the scoped VMEM), no key or value broadcast to the query
    heads and no length x length array."""
    import re

    from alphafold2_tpu.ops import dsa

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def core(q, k, v, packed):
        out, _, in_kernel = dsa.sparse_core(q, k, v, packed)
        assert in_kernel
        return out

    text = _compile(
        core if direction == "fwd" else _grad_of(core), one_chip,
        ((1, 32, 16384, 128), "bfloat16"), ((1, 4, 16384, 128), "bfloat16"),
        ((1, 4, 16384, 128), "bfloat16"), ((1, 512, 16384), "int32"))
    assert "dsa_core_fwd" in text
    assert ("dsa_core_dq" in text) == ("dsa_core_dkv" in text) \
        == (direction == "bwd")
    assert "16384,16384]" not in text
    assert not re.search(r"= bf16\[1,32,16384,128\]\S* broadcast\(", text)


def test_selection_compiles_for_v5e(one_chip):
    """The same cell's selection of one layer: the indexer's scores of 512
    queries at a time against the 16,384 keys, the exact top 2,048, the
    packing; no length x length array of scores."""
    from alphafold2_tpu.ops import dsa

    text = _compile(
        lambda q_i, k_i, w: dsa.selection(q_i, k_i, w, 2048, 512),
        one_chip, ((1, 16384, 16, 64), "bfloat16"),
        ((1, 16384, 64), "bfloat16"), ((1, 16384, 16), "float32"))
    assert "s32[1,512,16384]" in text
    assert "16384,16384]" not in text and "approx" not in text.lower()


def test_ssm_lm_train_step_compiles_for_v5e(one_chip, monkeypatch):
    """The whole jitted train step of the benchmark's third language-model
    cell (667 M parameters, 1 x 8,192 tokens) for one described chip: the
    splash kernels under the attention layer's core, each once (the forward
    too: the layer's recomputation finds its output and log-sum-exp kept;
    the backward fused), XLA's ragged-product kernels for the held experts,
    the scan's two kernels under the scan's scope, no array of length x
    length anywhere and no decay matrix either (chunks x heads x 128 x 128:
    it stays in the kernels' registers), and weights + Adam + activations
    under 15.0e9 B of the 15.75 GiB the compiler leaves."""
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from alphafold2_tpu.data.pipeline import make_dataset
    from alphafold2_tpu.observe.profiler import instruction_scopes
    from alphafold2_tpu.train import loop
    from benchmark.harness import common, train_ssm_lm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    resolved = common.resolve("train_nemotron3_nano_ep16_seq8k")
    cfg = train_ssm_lm.program_config(
        resolved["config"], resolved["traffic"], 1)
    task = loop.build_task(cfg)
    sample = next(iter(make_dataset(
        cfg.data, vocab_size=cfg.language_model().vocab_size)))
    assert sample["tokens"].shape == (1, 8192)

    def shapes(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), tree)

    state = jax.eval_shape(lambda: loop.tiny_init_state(cfg, task, sample))
    assert sum(x.size for x in jax.tree.leaves(state.params)) == 666_963_456
    rng = jax.eval_shape(lambda: jax.random.key(1))
    compiled = loop.make_train_step(task, None, numerics_mode="norms").lower(
        shapes(state), shapes({k: jnp.asarray(v) for k, v in sample.items()}),
        jax.ShapeDtypeStruct(rng.shape, rng.dtype, sharding=one_chip),
    ).compile()
    text = compiled.as_text()
    for kernel in ("splash_mha_fwd_residuals", "splash_mha_dkv_no_residuals"):
        scopes = _kernel_scopes(text, kernel)
        assert len(scopes) == 1 and "layer_5/attn_global/core" in scopes[0]
    assert not _kernel_scopes(text, "splash_mha_dq_no_residuals")
    assert "flash_attention" not in text
    assert "ragged-dot" in text  # the grouped product is a kernel, not dense
    assert "8192,8192]" not in text  # no T x T array, in any layer
    assert "8,8,128,128]" not in text  # nor a chunk's decay matrix a head
    scopes = instruction_scopes(text)[1].values()
    # the scan is the two kernels of ops/pallas/ssd.py under the scan's scope
    # (a trace reduced by names finds them): a layer's forward twice (the
    # layer's recomputation keeps nothing of it) and its backward once; no
    # chunk recurrence is left as a while loop
    forward = _kernel_scopes(text, "ssd_chunk_fwd")
    backward = _kernel_scopes(text, "ssd_chunk_bwd")
    for layer in (0, 2, 4, 7):
        here = f"layer_{layer}/ssm/scan/"
        assert sum(here in s for s in forward) == 2, (layer, forward)
        assert sum(here in s for s in backward) == 1, (layer, backward)
    assert len(forward) == 8 and len(backward) == 4
    # the convolution likewise: the two kernels of ops/pallas/conv.py
    conv_forward = _kernel_scopes(text, "causal_conv_fwd")
    conv_backward = _kernel_scopes(text, "causal_conv_bwd")
    for layer in (0, 2, 4, 7):
        here = f"layer_{layer}/ssm/conv/"
        assert sum(here in s for s in conv_forward) == 2, layer
        assert sum(here in s for s in conv_backward) == 1, layer
    assert len(conv_forward) == 8 and len(conv_backward) == 4
    assert not any("/while/body/" in s for s in scopes)
    for layer in (0, 2, 4, 7):
        for part in ("in_proj", "conv", "scan", "gate_norm", "out_proj"):
            assert any(f"layer_{layer}/ssm/{part}/" in s for s in scopes), \
                (layer, part)
    # as the benchmark's reader finds them (names side by side): what follows
    # the sort sits in a branch of the rung's switch, which opens moe again
    from benchmark.readers.scope_paths_device_ms import holds, names
    for part in ("router", "dispatch", "experts", "combine", "shared"):
        assert any("/layer_1/" in s and holds(names(s), f"moe/{part}")
                   for s in scopes), part
    # one switch a layer forward and one in its gradient, over four rungs
    assert text.count(" conditional(") == 8
    in_switch = [s for s in scopes if "/cond/branch_" in s]
    for layer in (1, 3, 6, 8):  # each under its own layer's name
        assert any(f"/layer_{layer}/moe/" in s for s in in_switch), layer
    assert all(
        sum(holds(names(s), f"moe/{part}")
            for part in ("dispatch", "experts", "combine")) == 1
        for s in in_switch)
    _inferred_scopes_hold(text, ["attn_global"])
    ma = compiled.memory_analysis()
    per_device = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                  + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    print(f"compiled step, bytes on the device: {per_device}")
    assert 11e9 < per_device < 15.0e9


# --------------------------------------------- the dense hybrid language model ---


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_grouped_core_32_over_8_of_64_compiles_for_v5e(one_chip, monkeypatch,
                                                       direction):
    """The fifth model's attention layer: 32 query heads over 8 key/value
    heads of 64 at 8,192 positions under the causal mask: half the head
    width any other cell runs. The splash kernel takes it as it is (no head
    padded to 128: a 64-wide array of the call's shape is in the program and
    a 128-wide one is not), the fused backward (its partial dq is 0.27 GB);
    no dense logits, no keys broadcast to the query heads."""
    import re

    from alphafold2_tpu.ops import mla

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _compile(
        mla.causal_core if direction == "fwd" else _grad_of(mla.causal_core),
        one_chip, ((1, 32, 8192, 64), "bfloat16"),
        ((1, 8, 8192, 64), "bfloat16"), ((1, 8, 8192, 64), "bfloat16"))
    assert "tpu_custom_call" in text and "splash_mha_fwd" in text
    if direction == "bwd":
        assert "splash_mha_dkv" in text and "splash_mha_dq" not in text
    assert "8192,8192]" not in text
    # (the kernel's own softmax statistics are float32 (1, 32, 8192, 128))
    assert "bf16[1,32,8192,64]" in text
    assert "bf16[1,32,8192,128]" not in text
    assert not re.search(r"= bf16\[1,32,8192,64\]\S* broadcast\(", text)


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_one_group_scan_in_chunks_of_256_compiles_for_v5e(
        one_chip, monkeypatch, direction):
    """The fifth model's scan, 64 heads of 64 all reading ONE group of 128
    state rows, in chunks of 256, through ``ssm.ssd_scan`` on the TPU branch:
    the two kernels of ``ops/pallas/ssd.py`` walking the group eight heads a
    grid step (whole, a step's blocks pass the chip's VMEM), and no decay
    matrix a head in HBM."""
    from alphafold2_tpu.ops import ssm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ssm.scan_kernel_takes((1, 8192, 64, 64), (1, 8192, 1, 128), 256)

    def scan(x, b, c, dt, a):
        return ssm.ssd_scan(x, dt, a, b, c, 256, jnp.bfloat16)[0]

    text = _compile(
        scan if direction == "fwd" else _grad_of(scan), one_chip,
        ((1, 8192, 64, 64), "bfloat16"), ((1, 8192, 1, 128), "bfloat16"),
        ((1, 8192, 1, 128), "bfloat16"), ((1, 8192, 64), "float32"),
        ((64,), "float32"))
    assert "ssd_chunk_fwd" in text
    assert ("ssd_chunk_bwd" in text) == (direction == "bwd")
    assert "64,256,256]" not in text and " while(" not in text


def test_hybrid_dense_lm_train_step_compiles_for_v5e(one_chip, monkeypatch):
    """The whole jitted train step of the benchmark's fifth model's cell
    (772,160,448 parameters, 1 x 8,192 tokens) for one described chip: the
    splash kernels under the one attention layer's core, each once; the
    scan's two kernels under each of the nine state-space layers' scan scope
    (the forward twice: the layer's recomputation keeps nothing of it); every
    block's scope as the benchmark's readers look for it; no array of length
    x length and no decay matrix; and weights + gradients + Adam +
    activations under the 16,911,433,728 B the compiler leaves, with the
    start NOT on the device (``harness/train_hybrid_dense_lm.py`` keeps it on
    the host: beside it the sum would pass the chip)."""
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from alphafold2_tpu.data.pipeline import make_dataset
    from alphafold2_tpu.observe.profiler import instruction_scopes
    from alphafold2_tpu.train import loop
    from benchmark.harness import common, train_hybrid_dense_lm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    resolved = common.resolve("train_granite4_h_micro_pp4_seq8k")
    cfg = train_hybrid_dense_lm.program_config(
        resolved["config"], resolved["traffic"], 1)
    task = loop.build_task(cfg)
    sample = next(iter(make_dataset(
        cfg.data, vocab_size=cfg.language_model().vocab_size)))
    assert sample["tokens"].shape == (1, 8192)

    def shapes(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), tree)

    state = jax.eval_shape(lambda: loop.tiny_init_state(cfg, task, sample))
    assert sum(x.size for x in jax.tree.leaves(state.params)) == 772_160_448
    rng = jax.eval_shape(lambda: jax.random.key(1))
    compiled = loop.make_train_step(task, None, numerics_mode="norms").lower(
        shapes(state), shapes({k: jnp.asarray(v) for k, v in sample.items()}),
        jax.ShapeDtypeStruct(rng.shape, rng.dtype, sharding=one_chip),
    ).compile()
    text = compiled.as_text()
    for kernel in ("splash_mha_fwd_residuals", "splash_mha_dkv_no_residuals"):
        scopes = _kernel_scopes(text, kernel)
        assert len(scopes) == 1 and "layer_5/attn_global/core" in scopes[0]
    assert not _kernel_scopes(text, "splash_mha_dq_no_residuals")
    assert "ragged-dot" not in text and " conditional(" not in text
    # no T x T logits a head (tokens x MLP width is 8,192 x 8,192 too)
    assert ",8192,8192]" not in text
    assert "64,256,256]" not in text  # nor a chunk's decay matrix a head
    forward = _kernel_scopes(text, "ssd_chunk_fwd")
    backward = _kernel_scopes(text, "ssd_chunk_bwd")
    state_space = [i for i in range(10) if i != 5]
    for layer in state_space:
        here = f"layer_{layer}/ssm/scan/"
        assert sum(here in s for s in forward) == 2, (layer, forward)
        assert sum(here in s for s in backward) == 1, (layer, backward)
    assert len(forward) == 18 and len(backward) == 9
    conv_forward = _kernel_scopes(text, "causal_conv_fwd")
    conv_backward = _kernel_scopes(text, "causal_conv_bwd")
    for layer in state_space:
        here = f"layer_{layer}/ssm/conv/"
        assert sum(here in s for s in conv_forward) == 2, layer
        assert sum(here in s for s in conv_backward) == 1, layer
    assert len(conv_forward) == 18 and len(conv_backward) == 9
    scopes = instruction_scopes(text)[1].values()
    assert not any("/while/body/" in s for s in scopes)
    for layer in state_space:
        for part in ("in_proj", "conv", "scan", "gate_norm", "out_proj"):
            assert any(f"layer_{layer}/ssm/{part}/" in s for s in scopes), \
                (layer, part)
    for layer in range(10):
        for part in ("mixer_norm", "ffn_norm", "dense_ffn"):
            assert any(f"layer_{layer}/{part}/" in s for s in scopes), \
                (layer, part)
    # as the benchmark's reader finds them (names side by side, wrappers off)
    from benchmark.readers.scope_paths_device_ms import holds, names
    for part in ("embed", "final_norm", "head", "loss", "optimizer"):
        assert any(holds(names(s), part) for s in scopes), part
    ma = compiled.memory_analysis()
    per_device = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                  + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    print(f"compiled step, bytes on the device: {per_device} (temporaries "
          f"{ma.temp_size_in_bytes})")
    # 12,195,462,144 B: the state, weights and both Adam moments (12 B a
    # parameter, 9.27 GB) and 2.93 GB of temporaries. The gradients are not
    # 4 B a parameter among them: the buffer assignment holds each product's
    # weight gradient in bfloat16 (the cotangent of the weight's bf16 copy),
    # made float32 only inside that leaf's update fusion, so 1.62 GB of
    # gradients are live where the backward pass ends. The floor sits 95 MB
    # (0.8%) under the reading
    assert 12_100_000_000 < per_device < 16_911_433_728
