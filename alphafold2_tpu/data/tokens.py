"""A synthetic token stream for the language model (``data.source``
``tokens``): full-length sequences, one document each, ids drawn Zipf over
the vocabulary rows held here so that some ids (and so some experts) are hot
as they are on text. Packing several documents into a sequence with segment
masks is not implemented."""

from __future__ import annotations

import dataclasses
from typing import Iterator

import numpy as np

from alphafold2_tpu.config import DataConfig


def zipf_sampler(vocab: int, exponent: float, rng: np.random.Generator):
    """draw(shape) -> int32 ids with P(rank r) ~ r**-exponent over ``vocab``
    ids; which id has which rank is a permutation drawn from ``rng``."""
    cdf = np.cumsum(np.arange(1, vocab + 1, dtype=np.float64) ** -exponent)
    cdf /= cdf[-1]
    id_of_rank = rng.permutation(vocab).astype(np.int32)

    def draw(shape):
        ranks = np.searchsorted(cdf, rng.random(shape), side="right")
        return id_of_rank[np.minimum(ranks, vocab - 1)]

    return draw


@dataclasses.dataclass
class SyntheticTokens:
    """Infinite iterator of {"tokens": (batch, seq_len) int32}."""

    config: DataConfig
    vocab_size: int  # ids 0..vocab_size-1: the rows the model holds
    seed: int = 0

    def __iter__(self) -> Iterator[dict]:
        c = self.config
        draw = zipf_sampler(self.vocab_size, c.zipf_exponent,
                            np.random.default_rng(self.seed))
        while True:
            yield {"tokens": draw((c.batch_size, c.seq_len))}
