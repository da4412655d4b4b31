"""Seeded token traffic for the language-model cells.

``kind: "lm_zipf"``: an endless stream of training batches, every one drawn
afresh: ``sequences`` sequences of ``seq_len`` token ids, full length, no
padding, one document a sequence. Ids are drawn Zipf (``zipf_exponent``) over
the vocabulary slice the configuration holds; which id has which rank is a
permutation drawn from the seed, so the hot ids differ from seed to seed and
the shapes never do. Zipf because routing is uneven on text: a hot token is
the same vector at the first layer wherever it occurs, so it loads the same
experts every time.
"""

from __future__ import annotations

import numpy as np

from benchmark.harness.traffic import seed31  # noqa: F401  (one seed rule)


def lm_batches(params: dict, vocab: int, seed: int):
    """Endless iterator of {"tokens": (sequences, seq_len) int32}."""
    if params.get("kind") != "lm_zipf":
        raise ValueError(f"not a token mix: {params.get('kind')!r}")
    rng = np.random.default_rng(int(seed))
    shape = (int(params["sequences"]), int(params["seq_len"]))
    cdf = np.cumsum(
        np.arange(1, vocab + 1, dtype=np.float64)
        ** -float(params["zipf_exponent"]))
    cdf /= cdf[-1]
    id_of_rank = rng.permutation(vocab).astype(np.int32)
    while True:
        ranks = np.searchsorted(cdf, rng.random(shape), side="right")
        yield {"tokens": id_of_rank[np.minimum(ranks, vocab - 1)]}
