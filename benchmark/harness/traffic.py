"""Seeded traffic: the one generator every traffic file's parameters go to.

A traffic mix is a JSON file of parameters under ``benchmark/traffic/``; this
module turns (parameters, configuration sizes, seed) into inputs. The same
seed gives the same inputs, and every seed gives the same shapes: only the
contents differ, so no seed changes the work.

``kind: "train_crops"``: an endless stream of training batches, every one
drawn afresh (rows that all differ). Copied from the program's synthetic
source (``alphafold2_tpu/data/pipeline.py`` ``SyntheticDataset``): a smoothed
random walk of 3.8 A steps for the CA trace, an MSA made by mutating the
primary sequence. ``fill`` is the share of the crop a chain fills; 1.0 is
full-length crops (no padding), which is what the plain reference's tied
rows take.
"""

from __future__ import annotations

import numpy as np

PAD = 20


def seed31(seed: int) -> int:
    """Any whole number -> a 31-bit seed for code that keeps seeds in int32."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0] >> 1)


def smooth_walk(rng: np.random.Generator, n: int) -> np.ndarray:
    steps = rng.normal(size=(n, 3))
    steps /= np.linalg.norm(steps, axis=-1, keepdims=True) + 1e-9
    for i in range(1, n):
        steps[i] = 0.6 * steps[i - 1] + 0.4 * steps[i]
        steps[i] /= np.linalg.norm(steps[i]) + 1e-9
    coords = np.cumsum(3.8 * steps, axis=0)
    return (coords - coords.mean(0)).astype(np.float32)


def train_batches(params: dict, sizes: dict, seed: int):
    """Endless iterator of {"seq", "msa", "mask", "msa_mask", "coords"}."""
    if params.get("kind") != "train_crops":
        raise ValueError(f"not a training mix: {params.get('kind')!r}")
    rng = np.random.default_rng(int(seed))
    crop, rows, nm, batch = (sizes["crop"], sizes["msa_depth"],
                             sizes["msa_len"], sizes["batch"])
    fill = float(params["fill"])
    rate = float(params["msa_mutation_rate"])
    n = max(1, int(round(fill * crop)))
    while True:
        out = {
            "seq": np.full((batch, crop), PAD, np.int32),
            "msa": np.full((batch, rows, nm), PAD, np.int32),
            "mask": np.zeros((batch, crop), bool),
            "msa_mask": np.zeros((batch, rows, nm), bool),
            "coords": np.zeros((batch, crop, 3), np.float32),
        }
        for b in range(batch):
            seq = rng.integers(0, 20, size=n)
            out["seq"][b, :n] = seq
            out["mask"][b, :n] = True
            out["coords"][b, :n] = smooth_walk(rng, n)
            width = min(nm, n)
            for r in range(rows):
                mut = rng.random(width) < rate
                row = seq[:width].copy()
                row[mut] = rng.integers(0, 20, size=int(mut.sum()))
                out["msa"][b, r, :width] = row
                out["msa_mask"][b, r, :width] = True
        yield out
