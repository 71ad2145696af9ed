"""The one general generator of a cell's inputs.

A traffic mix is a data file (``workloads/<cell>.json``); its ``data``
block names a ``kind`` below and that kind's parameters.  Everything is
drawn from ``--seed`` with numpy's ``default_rng``, in bulk, on the host:
the same seed gives the same bytes.  The program receives only what is
generated here.
"""

from __future__ import annotations

import numpy as np

__all__ = ["KINDS", "generate", "fold_seed", "epoch_order"]


def fold_seed(seed: int) -> int:
    """``--seed`` folded under 2**31 - 1 for the places that take a
    32-bit signed seed (``jax.random.key`` without x64, the program's
    samplers).  numpy takes the whole number."""
    return int(seed) % (2**31 - 1)


def epoch_order(n: int, seed: int, epoch: int) -> np.ndarray:
    """The order in which one epoch reads ``n`` rows, as the program's
    sampler documents it (``data/sampler.py``: a permutation seeded by
    ``(seed, epoch)``, numpy's ``default_rng``).  The benchmark's own copy:
    the batches that the plain reference follows come from here and from
    ``generate``, never from the program's feed, so a fault in the feed's
    indexing shows as a gap and is not shared by both sides."""
    return np.random.default_rng((int(seed), int(epoch))).permutation(int(n))


def zipf_tokens(data: dict, seed: int, *, vocab_size: int, seq_len: int) -> np.ndarray:
    """``windows * seq_len + 1`` token ids, unigram Zipf over the whole
    vocabulary (p(rank r) ~ 1 / (r + shift) ** exponent), ranks scattered
    over ids by a seeded permutation.  A loss that can fall (the unigram
    statistics are learnable) and rows that all differ."""
    rng = np.random.default_rng([int(seed), 0x7E4])
    n = int(data["windows"]) * seq_len + 1
    ranks = np.arange(vocab_size, dtype=np.float64)
    p = 1.0 / (ranks + float(data.get("shift", 8.0))) ** float(data.get("exponent", 1.0))
    cdf = np.cumsum(p / p.sum())
    draws = np.searchsorted(cdf, rng.random(n), side="right")
    draws = np.minimum(draws, vocab_size - 1)
    perm = rng.permutation(vocab_size)
    dtype = np.uint16 if vocab_size <= 65536 else np.int32
    return perm[draws].astype(dtype)


def blob_images(data: dict, seed: int, *, split: str = "train"):
    """``(images uint8 (N, S, S, 3), labels int64 (N,))``: a class-placed
    bright blob over uniform noise, made in bulk (no per-image Python).
    The position of the blob is the only thing that tells the class, as
    in the program's own synthetic APTOS stand-in."""
    n = int(data["num_train" if split == "train" else "num_test"])
    size = int(data["image_size"])
    classes = int(data["num_classes"])
    noise = int(data.get("noise", 48))
    rng = np.random.default_rng([int(seed), 0x1A6E, 0 if split == "train" else 1])
    labels = rng.integers(0, classes, size=n).astype(np.int64)
    angles = 2 * np.pi * np.arange(classes) / classes
    r = size * 0.25
    cy = size / 2 + r * np.sin(angles)
    cx = size / 2 + r * np.cos(angles)
    yy, xx = np.mgrid[0:size, 0:size]
    sigma = size * 0.08
    blobs = np.exp(
        -(((yy[None] - cy[:, None, None]) ** 2 + (xx[None] - cx[:, None, None]) ** 2)
          / (2 * sigma**2))
    )
    templates = (70.0 + 130.0 * blobs).astype(np.uint8)  # (classes, S, S)
    images = rng.integers(0, noise, size=(n, size, size, 3), dtype=np.uint8)
    images += templates[labels][..., None]
    return images, labels


KINDS = {"zipf_tokens": zipf_tokens, "blob_images": blob_images}


def generate(data: dict, seed: int, **shape):
    kind = data["kind"]
    if kind not in KINDS:
        raise KeyError(f"unknown traffic kind {kind!r}; have {sorted(KINDS)}")
    return KINDS[kind](data, seed, **shape)
