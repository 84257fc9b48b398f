"""The benchmark's inputs, made from the run seed.

The five UCI datasets of the paper (§V-A) are not shipped, so each is a
seeded Gaussian mixture with its UCI signature (features, classes, rows,
difficulty), the same generator as the system's ``repro.data.tabular``,
kept here so that the benchmark's inputs cannot change with the system.
The training split has a fixed number of rows per dataset, whatever the
seed, so that every seed compiles the same shapes.
"""
from __future__ import annotations

import zlib

import numpy as np

# name → (features, classes, samples, class separation, ordinal)
SPECS = {
    "breast_cancer": (10, 2, 699, 1.05, False),
    "cardio": (21, 3, 2126, 0.55, False),
    "pendigits": (16, 10, 10992, 1.6, False),
    "redwine": (11, 6, 1599, 1.3, True),
    "whitewine": (11, 7, 4898, 1.2, True),
}


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generator ``stream`` of run seed ``seed``."""
    return np.random.default_rng([int(seed) % 2**64, stream])


def _mixture(n, d, c, sep, rng, ordinal):
    if ordinal:
        u = rng.normal(0.0, 1.0, (1, d))
        u /= np.linalg.norm(u)
        centers = (np.arange(c)[:, None] - c / 2) * sep * u
        centers += rng.normal(0.0, 0.15 * sep, (c, d))
    else:
        centers = rng.normal(0.0, 1.0, (c, d))
        centers *= sep / np.maximum(
            np.linalg.norm(centers, axis=1, keepdims=True) / np.sqrt(d), 1e-9)
    y = rng.integers(0, c, n)
    scales = 0.6 + 0.8 * rng.random((c, d))
    x = centers[y] + rng.normal(0.0, 1.0, (n, d)) * scales[y]
    x = (x - x.min(0)) / np.maximum(x.max(0) - x.min(0), 1e-9)
    return x.astype(np.float32), y.astype(np.int32)


def training_split(name: str, seed: int, rows: int):
    """(x in [0, 1], labels) of ``rows`` training rows of ``name``.

    A stratified 70/30 split of the seeded mixture; the training rows are
    cut to, or topped up from the held-out rows to, exactly ``rows``."""
    d, c, n, sep, ordinal = SPECS[name]
    rng = np.random.default_rng([int(seed) % 2**64, zlib.crc32(name.encode())])
    x, y = _mixture(n, d, c, sep, rng, ordinal)
    tr, te = [], []
    for cls in range(c):
        idx = np.where(y == cls)[0]
        rng.shuffle(idx)
        k = int(round(0.7 * len(idx)))
        tr.append(idx[:k])
        te.append(idx[k:])
    tr, te = np.concatenate(tr), np.concatenate(te)
    rng.shuffle(tr)
    rng.shuffle(te)
    order = np.concatenate([tr, te])[:rows]
    return x[order], y[order]


def doping_genomes(lay, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` nearly non-approximate chromosomes (paper §IV-A): every mask
    keeps all its bits, no shift drops a bit, and signs, exponents and
    biases are drawn at random within their bounds."""
    low, high = lay["low"], lay["high"]
    g = rng.integers(low, high, size=(n, low.shape[0])).astype(np.int32)
    g[:, lay["is_mask"]] = high[lay["is_mask"]] - 1
    for start, _, _, _ in lay["layers"]:
        g[:, start["bshift"]] = 0
        g[:, start["rshift"]] = 0
    return g
