#!/usr/bin/env python3
"""sha256[:16] of each benchmark cell's lowered train step, TPU branch, for a
described v5e (no chip): what a PR prints for its parent and for itself to
show that a cell's program did not change.

    JAX_PLATFORMS=cpu python scripts/hash_lowered_steps.py [cell ...]

Run it from the root of each checkout (it imports the checkout it is run
from). The lowered text holds every Mosaic kernel as base64 MLIR bytecode
WITH the Python locations of the whole call stack: a line added anywhere in
a file of that stack (``train/loop.py``, a model, a kernel's own file)
changes the bytes of a kernel that did not change, and so does the
checkout's path. Each body is therefore parsed and printed without debug
information before the text is hashed; the rest of the text carries no
locations. The last number of a line is how many kernel bodies were read.
"""

import base64
import hashlib
import importlib
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.getcwd())

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import (  # noqa: E402
    Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding,
)


def without_locations(text: str) -> tuple:
    """(``text`` with every kernel body replaced by the hash of its printed
    form without debug information, how many bodies)."""
    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    ctx = mlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True  # serialised as stable_mosaic

    def one(match):
        with ctx:
            module = ir.Module.parse(base64.b64decode(match.group(1)))
            asm = module.operation.get_asm(enable_debug_info=False)
        return '\\22body\\22: \\22' + hashlib.sha256(
            asm.encode()).hexdigest() + '\\22'

    return re.subn(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', one, text)


def lowered(cell: str, topo):
    """The cell's jitted train step lowered for the described chips."""
    from alphafold2_tpu.data.pipeline import make_dataset
    from alphafold2_tpu.train import loop
    from benchmark.harness import common

    resolved = common.resolve(cell)
    config = resolved["config"]
    driver = importlib.import_module(f"benchmark.harness.{config['kind']}")
    if config["kind"] == "train":
        cfg = driver.program_config(config, 1)
    else:
        cfg = driver.program_config(config, resolved["traffic"], 1)
    dp, sp = config["mesh"]["dp"], config["mesh"]["sp"]
    if dp * sp > 1:
        mesh = Mesh(np.array(topo.devices).reshape(dp, sp), ("dp", "sp"))
        repl, data = NamedSharding(mesh, P()), NamedSharding(mesh, P("dp"))
    else:
        mesh = None
        repl = data = SingleDeviceSharding(topo.devices[0])
    task = loop.build_task(cfg)
    if cfg.model.arch == "alphafold2":
        sample = next(iter(make_dataset(cfg.data, seed=0)))
    else:
        sample = next(iter(make_dataset(
            cfg.data, vocab_size=cfg.language_model().vocab_size)))

    def shapes(tree, sharding):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding), tree)

    state = jax.eval_shape(lambda: loop.tiny_init_state(cfg, task, sample))
    rng = jax.eval_shape(lambda: jax.random.key(1))
    return loop.make_train_step(task, mesh, numerics_mode="norms").lower(
        shapes(state, repl),
        shapes({k: jnp.asarray(v) for k, v in sample.items()}, data),
        jax.ShapeDtypeStruct(rng.shape, rng.dtype, sharding=repl))


def main(argv) -> int:
    from jax.experimental import topologies

    from benchmark.harness import common

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")
    jax.default_backend = lambda: "tpu"  # the program asks which branch
    manifest = common.load_json("BENCHMARK.json")
    for cell in argv or [w["name"] for w in manifest["workloads"]]:
        text, kernels = without_locations(lowered(cell, topo).as_text())
        print(cell, hashlib.sha256(text.encode()).hexdigest()[:16], kernels,
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
