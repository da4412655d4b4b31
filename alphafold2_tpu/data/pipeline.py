"""Data pipeline: fixed-shape protein batches for TPU training.

Replaces the reference's sidechainnet DataLoader usage (train_pre.py:37-48:
``scn.load(casp_version=12, thinning=30)`` + a python length filter < 250 and
``cycle``). TPU-first differences:

- **Static shapes.** The reference feeds variable-length chains (anything
  < 250) straight into the model, retracing shapes every batch on a compiler
  backend. Here every batch is cropped/padded to ``crop_len`` with masks —
  one compiled program for the whole run.
- Sources: ``sidechainnet`` when the package is installed (same CASP12 /
  thinning-30 default), else a deterministic synthetic sampler with
  realistic marginals (sequence/MSA agreement, compact 3D coords from a
  smoothed random walk) so every part of the framework is exercisable in
  this hermetic environment.
- MSA synthesis: sidechainnet has no MSAs; the reference trains distogram-only
  without them (train_pre.py:79). We synthesize MSA rows by mutating the
  primary sequence (rate ~0.15) so the MSA stream trains end-to-end.

Batches are dicts of numpy arrays:
  seq (B, L) int32 | msa (B, M, L) int32 | mask (B, L) bool |
  msa_mask (B, M, L) bool | coords (B, L, 3) float32 CA positions |
  backbone (B, L*3, 3) float32 N/CA/C positions (end-to-end target)
"""

from __future__ import annotations

import dataclasses
from typing import Iterator

import numpy as np

from alphafold2_tpu import constants
from alphafold2_tpu.config import DataConfig


def _smooth_walk(rng: np.random.Generator, n: int) -> np.ndarray:
    """Compact protein-like CA trace: random walk with ~3.8A steps, smoothed."""
    steps = rng.normal(size=(n, 3))
    steps /= np.linalg.norm(steps, axis=-1, keepdims=True) + 1e-9
    # correlate consecutive steps for secondary-structure-like persistence
    for i in range(1, n):
        steps[i] = 0.6 * steps[i - 1] + 0.4 * steps[i]
        steps[i] /= np.linalg.norm(steps[i]) + 1e-9
    coords = np.cumsum(3.8 * steps, axis=0)
    return (coords - coords.mean(0)).astype(np.float32)


def _fill_msa(rng, seq_crop, msa_out, msa_mask_out, mutation_rate=0.15,
              mut_rows=None):
    """Fill (M, NM) MSA rows by mutating the cropped primary sequence —
    the one MSA-synthesis implementation shared by every data source.

    The rng stream consumed here depends only on (seed state, msa_len, M),
    never on the sequence CONTENT: the mutation mask is drawn first and the
    replacement residues are drawn for the masked positions regardless of
    what they replace. ``featurize_delta`` builds on exactly that property.
    ``mut_rows`` (a list) collects the per-row mutation masks when the
    caller wants the delta-featurization plan."""
    M, NM = msa_out.shape
    msa_len = min(NM, len(seq_crop))
    for m in range(M):
        mut = rng.random(msa_len) < mutation_rate
        row = np.asarray(seq_crop[:msa_len]).copy()
        row[mut] = rng.integers(0, 20, size=int(mut.sum()))
        msa_out[m, :msa_len] = row
        msa_mask_out[m, :msa_len] = True
        if mut_rows is not None:
            mut_rows.append(mut)


def _synthesize_backbone(rng: np.random.Generator, ca: np.ndarray) -> np.ndarray:
    """Place N and C pseudo-atoms ~1.5A off each CA along the chain direction."""
    n = ca.shape[0]
    d = np.diff(ca, axis=0, prepend=ca[:1] - (ca[1:2] - ca[:1]))
    d /= np.linalg.norm(d, axis=-1, keepdims=True) + 1e-9
    jitter = rng.normal(scale=0.1, size=(n, 3)).astype(np.float32)
    n_atom = ca - 1.46 * d + jitter
    c_atom = ca + 1.52 * d - jitter
    bb = np.stack([n_atom, ca, c_atom], axis=1)  # (L, 3, 3)
    return bb.reshape(n * 3, 3).astype(np.float32)


def featurize_bucketed(
    seq_tokens: np.ndarray,  # (L,) int32 AA tokens
    bucket_len: int,
    msa_depth: int,
    seed: int = 0,
    msa_len: int | None = None,
) -> dict:
    """One inference request -> fixed-shape features at a bucket length.

    The serve engine's featurizer: the sequence is padded up to
    ``bucket_len`` with ``AA_PAD_INDEX`` + a validity mask, and an MSA is
    synthesized by mutating the primary sequence (the same ``_fill_msa``
    every training source uses) into ``(msa_depth, msa_len or bucket_len)``
    padded rows. Returns an UNBATCHED item dict (``seq`` (bucket,), ``mask``,
    ``msa``, ``msa_mask``) — the engine stacks items into its batch dim.
    """
    item, _ = featurize_bucketed_with_plan(
        seq_tokens, bucket_len, msa_depth, seed=seed, msa_len=msa_len
    )
    return item


def featurize_bucketed_with_plan(
    seq_tokens: np.ndarray,
    bucket_len: int,
    msa_depth: int,
    seed: int = 0,
    msa_len: int | None = None,
) -> tuple:
    """:func:`featurize_bucketed` plus the delta-featurization *plan*.

    The plan records what :func:`featurize_delta` needs to featurize a
    point mutant of this sequence without re-running the MSA synthesis:
    the parent's tokens, the derivation coordinates (bucket/msa_depth/
    seed), and the per-row mutation masks ``_fill_msa`` drew — at a given
    (seed, length, msa_depth) those masks and the replacement residues are
    sequence-content-independent, which is the whole trick. The item dict
    is byte-identical to a plain ``featurize_bucketed`` call (same rng
    consumption order)."""
    seq_tokens = np.asarray(seq_tokens, np.int32).reshape(-1)
    L = len(seq_tokens)
    if L > bucket_len:
        raise ValueError(
            f"sequence of {L} residues does not fit bucket {bucket_len}"
        )
    NM = msa_len or bucket_len
    rng = np.random.default_rng(seed)
    item = {
        "seq": np.full(bucket_len, constants.AA_PAD_INDEX, np.int32),
        "mask": np.zeros(bucket_len, bool),
        "msa": np.full((msa_depth, NM), constants.AA_PAD_INDEX, np.int32),
        "msa_mask": np.zeros((msa_depth, NM), bool),
    }
    item["seq"][:L] = seq_tokens
    item["mask"][:L] = True
    mut_rows: list = []
    _fill_msa(rng, seq_tokens, item["msa"], item["msa_mask"],
              mut_rows=mut_rows)
    eff_len = min(NM, L)
    plan = {
        "tokens": seq_tokens.copy(),
        "bucket_len": int(bucket_len),
        "msa_depth": int(msa_depth),
        "msa_len": int(NM),
        "seed": int(seed),
        # (M, min(NM, L)) bool: True where _fill_msa replaced the primary
        # residue with a content-independent random one
        "mut": (
            np.stack(mut_rows) if mut_rows
            else np.zeros((0, eff_len), bool)
        ),
    }
    return item, plan


def featurize_delta(
    parent_item: dict,
    plan: dict,
    mutant_tokens: np.ndarray,
) -> dict:
    """Featurize a mutant of ``plan``'s parent by patching only the
    touched columns — byte-identical to cold featurization.

    For a mutant at the parent's length, the same (bucket, msa_depth,
    seed) cold featurization differs from the parent's only at the mutated
    positions: the primary-sequence slot, and per MSA row the positions
    the row's mutation mask did NOT replace (masked positions hold random
    residues whose draw never saw the sequence content). So the mutant's
    feature tree is the parent's with those columns patched — an O(M ·
    n_mutations) copy-and-patch instead of an O(M · L) re-synthesis. The
    parity test (tests/test_variant_scan.py) pins byte-level equality
    against :func:`featurize_bucketed`, tolerance zero.

    Masks are returned as the PARENT'S arrays (they are content-independent
    at equal length); callers must treat items as immutable, which the
    serve engine does (stacking copies). Raises ValueError when the mutant
    is not delta-eligible (different length)."""
    mutant_tokens = np.asarray(mutant_tokens, np.int32).reshape(-1)
    parent_tokens = plan["tokens"]
    if len(mutant_tokens) != len(parent_tokens):
        raise ValueError(
            f"delta featurization needs equal lengths: mutant "
            f"{len(mutant_tokens)} vs parent {len(parent_tokens)}"
        )
    positions = np.nonzero(mutant_tokens != parent_tokens)[0]
    seq = parent_item["seq"].copy()
    msa = parent_item["msa"].copy()
    mut = plan["mut"]  # (M, eff_len) bool
    eff_len = mut.shape[1] if mut.size else min(
        plan["msa_len"], len(parent_tokens)
    )
    for p in positions:
        seq[p] = mutant_tokens[p]
        if p < eff_len:
            msa[~mut[:, p], p] = mutant_tokens[p]
    return {
        "seq": seq,
        "mask": parent_item["mask"],
        "msa": msa,
        "msa_mask": parent_item["msa_mask"],
    }


@dataclasses.dataclass
class SyntheticDataset:
    """Deterministic synthetic chains; infinite iterator of fixed-shape batches."""

    config: DataConfig
    seed: int = 0

    def __iter__(self) -> Iterator[dict]:
        cfg = self.config
        rng = np.random.default_rng(self.seed)
        L, M, NM, B = cfg.crop_len, cfg.msa_depth, cfg.msa_len, cfg.batch_size
        while True:
            batch = {
                "seq": np.zeros((B, L), np.int32),
                "msa": np.zeros((B, M, NM), np.int32),
                "mask": np.zeros((B, L), bool),
                "msa_mask": np.zeros((B, M, NM), bool),
                "coords": np.zeros((B, L, 3), np.float32),
                "backbone": np.zeros((B, L * 3, 3), np.float32),
            }
            min_len = min(cfg.min_len_filter, L)  # crop shorter than the
            # filter floor: full-length chains, not a crash
            for b in range(B):
                true_len = int(rng.integers(min_len, L + 1))
                seq = rng.integers(0, 20, size=true_len)
                ca = _smooth_walk(rng, true_len)
                batch["seq"][b, :true_len] = seq
                batch["seq"][b, true_len:] = constants.AA_PAD_INDEX
                batch["mask"][b, :true_len] = True
                batch["coords"][b, :true_len] = ca
                batch["backbone"][b, : true_len * 3] = _synthesize_backbone(rng, ca)
                batch["msa"][b, :, :] = constants.AA_PAD_INDEX
                _fill_msa(rng, seq, batch["msa"][b], batch["msa_mask"][b])
            yield batch


class SidechainnetDataset:
    """CASP data via the sidechainnet package (reference train_pre.py:37-48),
    cropped/padded to static shapes. Import-gated: raises a clear error when
    the package is absent (it is not in this image)."""

    def __init__(self, config: DataConfig, seed: int = 0):
        try:
            import sidechainnet as scn
        except ImportError as e:
            raise ImportError(
                "sidechainnet is not installed; use source='synthetic'"
            ) from e
        self.config = config
        self.seed = seed
        self._data = scn.load(
            casp_version=config.casp_version,
            thinning=config.thinning,
            with_pytorch="dataloaders",
            batch_size=config.batch_size,
            dynamic_batching=False,
        )

    def __iter__(self):
        cfg = self.config
        rng = np.random.default_rng(self.seed)
        L, M, NM, B = cfg.crop_len, cfg.msa_depth, cfg.msa_len, cfg.batch_size
        while True:
            for batch in self._data["train"]:
                seqs = batch.int_seqs.numpy()
                masks = batch.msks.numpy().astype(bool)
                coords = batch.crds.numpy().reshape(
                    seqs.shape[0], -1, constants.NUM_COORDS_PER_RES, 3
                )
                lengths = masks.sum(-1)
                keep = (lengths >= cfg.min_len_filter) & (
                    lengths <= cfg.max_len_filter
                )
                if not keep.any():
                    continue
                out = {
                    "seq": np.full((B, L), constants.AA_PAD_INDEX, np.int32),
                    "msa": np.full((B, M, NM), constants.AA_PAD_INDEX, np.int32),
                    "mask": np.zeros((B, L), bool),
                    "msa_mask": np.zeros((B, M, NM), bool),
                    "coords": np.zeros((B, L, 3), np.float32),
                    "backbone": np.zeros((B, L * 3, 3), np.float32),
                }
                rows = np.nonzero(keep)[0][:B]
                for i, r in enumerate(rows):
                    n = int(lengths[r])
                    start = 0 if n <= L else int(rng.integers(0, n - L + 1))
                    end = min(start + L, n)
                    sl = slice(start, end)
                    w = end - start
                    out["seq"][i, :w] = seqs[r, sl]
                    out["mask"][i, :w] = masks[r, sl]
                    out["coords"][i, :w] = coords[r, sl, 1]  # CA slot
                    bb = coords[r, sl, :3].reshape(w * 3, 3)
                    out["backbone"][i, : w * 3] = bb
                    _fill_msa(rng, seqs[r, sl], out["msa"][i], out["msa_mask"][i])
                    msa_len = min(NM, w)
                    out["msa_mask"][i, :, :msa_len] &= masks[r, sl][:msa_len]
                yield out


def _npz_paths(data_dir: str) -> list:
    import glob
    import os

    if not data_dir:
        raise ValueError("npz shards need data.data_dir")
    paths = sorted(glob.glob(os.path.join(data_dir, "*.npz")))
    if not paths:
        raise FileNotFoundError(f"no .npz shards under {data_dir!r}")
    return paths


def _read_shard(path: str):
    """One shard -> (seq (L,) int32, coords float32, msa (M, L) int32 or
    None), shape-validated so malformed shards fail loudly here rather than
    corrupting downstream consumers (the native loader trusts lengths)."""
    with np.load(path) as z:
        seq = np.ascontiguousarray(z["seq"], np.int32)
        coords = np.asarray(z["coords"], np.float32)
        msa = np.asarray(z["msa"], np.int32) if "msa" in z else None
    n = len(seq)
    ok = (coords.ndim == 2 and coords.shape == (n, 3)) or (
        coords.ndim == 3
        and coords.shape[0] == n
        and coords.shape[1] >= 3
        and coords.shape[2] == 3
    )
    if not ok:
        raise ValueError(
            f"shard {path!r}: coords shape {coords.shape} does not match "
            f"seq length {n} (want (L, 3) CA or (L, k>=3, 3) atomic)"
        )
    if msa is not None and (msa.ndim != 2 or msa.shape[1] != n):
        raise ValueError(
            f"shard {path!r}: msa shape "
            f"{msa.shape} does not match seq length {n} (want (M, L))"
        )
    return seq, coords, msa


def _length_ok(n: int, config: DataConfig) -> bool:
    return max(4, config.min_len_filter) <= n <= config.max_len_filter


def _shard_backbone(coords: np.ndarray, rng) -> tuple:
    """coords -> (ca (L, 3), backbone_atoms (L*3, 3)); CA-only shards get
    synthesized N/C pseudo-atoms so structure losses have a real target."""
    if coords.ndim == 3:  # (L, k, 3) atomic: slots 0..2 = N/CA/C
        return coords[:, 1], coords[:, :3].reshape(-1, 3)
    return coords, _synthesize_backbone(rng, coords)


# one message for the one policy, whichever entry point detects it
MSA_FALLBACK_WARNING = (
    "shards carry stored MSAs, which the native loader would replace with "
    "mutation-synthesized ones; use the numpy npz pipeline "
    "(data.source='npz') to train on the stored alignments"
)


def shards_carry_msa(config: DataConfig) -> bool:
    """Cheap pre-scan: does any length-passing shard store an MSA? Reads
    only zip directories and the small ``seq`` arrays — no coords — so
    routing decisions don't pay a full dataset load."""
    for p in _npz_paths(config.data_dir):
        with np.load(p) as z:
            if "msa" in z.files and _length_ok(len(z["seq"]), config):
                return True
    return False


def load_npz_chains(config: DataConfig, seed: int = 0) -> tuple:
    """Load every length-filtered chain from the ``.npz`` shard directory as
    ``(seq (L,) int32, backbone (L, 3, 3) float32)`` pairs — the registry
    format the native real-data loader copies once at startup. Returns
    ``(chains, any_msa)``; ``any_msa`` is True when any length-passing
    shard carries a stored MSA (which this registry format cannot hold).

    ``seed`` drives the N/C pseudo-atom jitter for CA-only shards. The
    registry is built once, so that jitter is fixed for the run (the numpy
    pipeline re-draws per epoch) but varies across training seeds."""
    rng = np.random.default_rng(seed)
    chains = []
    any_msa = False
    for p in _npz_paths(config.data_dir):
        seq, coords, msa = _read_shard(p)
        if not _length_ok(len(seq), config):
            continue
        any_msa = any_msa or msa is not None
        _, backbone_atoms = _shard_backbone(coords, rng)
        chains.append((
            seq,
            np.ascontiguousarray(backbone_atoms.reshape(len(seq), 3, 3)),
        ))
    if not chains:
        raise ValueError(
            f"no shard in {config.data_dir!r} passes the length filter "
            f"[{config.min_len_filter}, {config.max_len_filter}]"
        )
    return chains, any_msa


class NpzShardDataset:
    """Local real-data ingestion: a directory of ``.npz`` shards.

    Each shard holds one chain: ``seq`` (L,) int tokens (AA_ALPHABET
    order), ``coords`` (L, 3) CA positions (or (L, k>=3, 3) atom14-style,
    slot 1 = CA, slots 0..2 = N/CA/C), optional ``msa`` (M, L) int. Chains
    are length-filtered, cropped/padded to static shapes, cycled forever
    with a seeded shuffle; MSAs absent from a shard are synthesized by
    mutation like the other sources. ``scripts/import_pdbs.py`` converts a
    directory of PDB files into this format using the built-in PDB codec.
    """

    def __init__(self, config: DataConfig, seed: int = 0):
        self.config = config
        self.seed = seed
        self.paths = _npz_paths(config.data_dir)

    def __iter__(self) -> Iterator[dict]:
        cfg = self.config
        rng = np.random.default_rng(self.seed)
        L, M, NM, B = cfg.crop_len, cfg.msa_depth, cfg.msa_len, cfg.batch_size
        order = np.arange(len(self.paths))
        buf = []
        while True:
            rng.shuffle(order)
            accepted = 0
            for idx in order:
                seq, coords, msa_full = _read_shard(self.paths[idx])
                n = len(seq)
                if not _length_ok(n, cfg):
                    continue
                accepted += 1
                ca, backbone_atoms = _shard_backbone(coords, rng)
                start = 0 if n <= L else int(rng.integers(0, n - L + 1))
                end = min(start + L, n)
                w = end - start
                item = {
                    "seq": np.full(L, constants.AA_PAD_INDEX, np.int32),
                    "msa": np.full((M, NM), constants.AA_PAD_INDEX, np.int32),
                    "mask": np.zeros(L, bool),
                    "msa_mask": np.zeros((M, NM), bool),
                    "coords": np.zeros((L, 3), np.float32),
                    "backbone": np.zeros((L * 3, 3), np.float32),
                }
                item["seq"][:w] = seq[start:end]
                item["mask"][:w] = True
                item["coords"][:w] = ca[start:end]
                item["backbone"][: w * 3] = backbone_atoms[start * 3 : end * 3]
                if msa_full is not None:
                    msa_len = min(NM, w)
                    rows = min(M, len(msa_full))
                    item["msa"][:rows, :msa_len] = msa_full[
                        :rows, start : start + msa_len
                    ]
                    item["msa_mask"][:rows, :msa_len] = True
                    if rows < M:
                        _fill_msa(rng, seq[start:end], item["msa"][rows:],
                                  item["msa_mask"][rows:])
                else:
                    _fill_msa(rng, seq[start:end], item["msa"], item["msa_mask"])
                buf.append(item)
                if len(buf) == B:
                    yield {
                        k: np.stack([it[k] for it in buf]) for k in buf[0]
                    }
                    buf = []
            if accepted == 0:
                raise ValueError(
                    f"no shard in {cfg.data_dir!r} passes the length filter "
                    f"[{cfg.min_len_filter}, {cfg.max_len_filter}]"
                )


def make_dataset(config: DataConfig, seed: int = 0, vocab_size=None):
    """The dataset ``config.source`` names. ``vocab_size`` is the language
    model's (``lm.vocab_size``); only the token stream reads it."""
    if config.source == "synthetic":
        return SyntheticDataset(config, seed=seed)
    if config.source == "tokens":
        from alphafold2_tpu.data.tokens import SyntheticTokens

        if vocab_size is None:
            raise ValueError("data.source='tokens' needs the vocabulary size")
        return SyntheticTokens(config, vocab_size, seed=seed)
    if config.source == "native":
        from alphafold2_tpu.data import native

        if not native.available():
            # same error native.py's own entry points raise: a requested
            # loader is never swapped for the numpy pipeline
            raise RuntimeError(
                "data.source='native' but libaf2data.so is not built "
                "(make -C native)"
            )
        # data_dir set -> real npz shards through the native prefetch
        # ring; otherwise the native synthetic stream
        if config.data_dir:
            if shards_carry_msa(config):
                import warnings

                warnings.warn(MSA_FALLBACK_WARNING)
                return NpzShardDataset(config, seed=seed)
            return native.NativeShardLoader(config, seed=seed)
        return native.NativeSyntheticLoader(config, seed=seed)
    if config.source == "npz":
        return NpzShardDataset(config, seed=seed)
    if config.source == "sidechainnet":
        return SidechainnetDataset(config, seed=seed)
    raise ValueError(f"unknown data source {config.source!r}")
