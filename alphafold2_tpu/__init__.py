"""alphafold2_tpu: a TPU-native (JAX/XLA/pjit/Pallas) protein structure framework.

Re-designed from scratch with the capabilities of the reference
alphafold2-pytorch (lucidrains v0.0.33): axial-attention trunk over a pairwise
residue representation cross-attending an MSA stream, distogram prediction,
and structure realization (distogram -> MDS -> sidechain lift -> refinement)
with alignment/quality metrics — built TPU-first: static shapes, scan/remat
trunks, mesh-sharded pair maps, Pallas kernels for the sparse paths.
"""

__version__ = "0.1.0"

import os as _os

from alphafold2_tpu import constants

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def compile_cache_dir() -> str:
    """Where the persistent XLA compile cache lives:
    ``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache`` (git-ignored). The path is part
    of the cache's key, so it is fixed — derived from this package's
    location, never from the user, the process or the clock."""
    return _os.environ.get(CACHE_ENV) or _os.path.join(
        _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
        ".jax_cache",
    )


def enable_compile_cache() -> None:
    """Turn on XLA's persistent compilation cache; every driver calls this
    before its first compile, so a later process compiling the same HLO
    reuses the serialized executable.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX's own handling of it
    stands and nothing here touches the directory. Where it is not, the
    cache goes to ``compile_cache_dir()``; a path that cannot be created
    raises (``OSError``) instead of running uncached."""
    if not _os.environ.get(CACHE_ENV):
        import jax

        cache_dir = compile_cache_dir()
        _os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
