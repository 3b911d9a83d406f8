"""Higgs-like synthetic binary data (no files)."""

import numpy as np

from . import columns, normals, rng_of


def make(rows: int, features: int, data_seed: int, seed: int) -> dict:
    rng = rng_of(data_seed)
    X = normals(rng, rows, features)
    w = normals(rng, features) / np.float32(np.sqrt(features))
    logits = X @ w + 0.5 * np.sin(X[:, 0] * 2) * X[:, 1]
    p = 1 / (1 + np.exp(-logits))
    y = (rng.random(rows) < p).astype(np.float64)
    return {"X": columns(X, seed), "label": y}
