"""Torch-matched parameter re-initialization (init-distribution A/B).

The reference model never customizes initialization — every layer uses the
torch module defaults (`/root/reference/alphafold2_pytorch/alphafold2.py:354-361`
constructs plain ``nn.Embedding``/``nn.Linear``/``nn.LayerNorm``;
`/root/reference/train_pre.py:52-57` trains them as-is):

- ``nn.Linear``: weight = kaiming_uniform(a=sqrt(5)) which reduces to
  U(-1/sqrt(fan_in), +1/sqrt(fan_in)); bias = U(-1/sqrt(fan_in), ...)
  (torch ``Linear.reset_parameters``)
- ``nn.Conv1d``: same rule with fan_in = in_channels/groups * kernel_size
- ``nn.Embedding``: N(0, 1)
- ``nn.LayerNorm``: ones/zeros

Flax defaults differ materially: Dense kernels are lecun-normal
(std 1/sqrt(fan_in), vs torch's uniform with std 1/sqrt(3*fan_in)), biases
are zeros (vs torch's uniform), and ``nn.Embed`` draws N(0, 1/features) —
at dim 256 the reference's token embeddings are 16x larger in scale.
A review named this distribution mismatch the prime suspect for the
flagship-width in-distribution quality gap; re-drawing an initialized tree
under the torch rules isolates init alone while keeping data, optimizer,
and architecture bit-identical.

Scope note: ``scan_layers=True`` and the reversible engine both stack a
leading depth axis onto their trunk kernels (lax.scan params /
ReversibleTrunk's vmap-initialized ``layers``), which would corrupt the
fan_in computation here. Stackedness cannot be inferred from shapes alone,
so those configs are rejected at the callers: ``train.loop.init_state``
and ``scripts/baseline_jax.py`` raise before any init work.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np


def _path_key(rng, path: tuple) -> jax.Array:
    # crc32 is stable across processes (unlike str hash under hash
    # randomization): same tree + same rng => bit-identical params
    return jax.random.fold_in(rng, zlib.crc32("/".join(path).encode()))


def torch_match_reinit(params, rng: jax.Array):
    """Re-draw every parameter of an initialized tree per torch defaults.

    Walks the nested param dict; any module dict holding a ``kernel``
    (Dense / DenseGeneral / Conv) gets the kaiming-uniform(a=sqrt(5)) rule
    on kernel AND bias with fan_in = prod(kernel.shape[:-1]); ``embedding``
    leaves become N(0,1); LayerNorm (``scale``) modules keep flax's
    ones/zeros, which already equal torch's. Leaf dtypes are preserved.
    Deterministic in (params, rng).
    """

    def rec(tree, path):
        # flax puts a module's own params and its child-module dicts in ONE
        # mapping — after handling this level's params, always recurse into
        # the remaining (dict-valued) siblings so children of a
        # param-holding scope are never silently left at flax init
        if not isinstance(tree, dict):
            return tree
        if "kernel" in tree:
            k = tree["kernel"]
            fan_in = int(np.prod(k.shape[:-1]))
            bound = 1.0 / math.sqrt(fan_in)
            kk, kb = jax.random.split(_path_key(rng, path))
            out = dict(tree)
            out["kernel"] = jax.random.uniform(
                kk, k.shape, k.dtype, -bound, bound
            )
            if "bias" in tree:
                b = tree["bias"]
                out["bias"] = jax.random.uniform(
                    kb, b.shape, b.dtype, -bound, bound
                )
            for key, v in tree.items():
                if key not in ("kernel", "bias"):
                    out[key] = rec(v, path + (key,))
            return out
        if "embedding" in tree:
            out = dict(tree)
            out["embedding"] = jax.random.normal(
                _path_key(rng, path), tree["embedding"].shape,
                tree["embedding"].dtype,
            )
            for key, v in tree.items():
                if key != "embedding":
                    out[key] = rec(v, path + (key,))
            return out
        if "scale" in tree:
            # LayerNorm: flax ones/zeros == torch ones/zeros — keep the
            # params, still visit any sibling children
            return {
                key: (v if key in ("scale", "bias") else rec(v, path + (key,)))
                for key, v in tree.items()
            }
        return {k: rec(v, path + (k,)) for k, v in tree.items()}

    return rec(params, ())
