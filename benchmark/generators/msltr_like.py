"""MS-LTR-like synthetic ranking data: fixed-size query groups, graded
relevance 0-4 skewed to low grades (60/20/10/7/3 %)."""

import numpy as np

from . import columns, normals, rng_of


def make(rows: int, features: int, group: int, data_seed: int,
         seed: int) -> dict:
    rng = rng_of(data_seed)
    X = normals(rng, rows, features)
    w = normals(rng, features) / np.float32(np.sqrt(features))
    util = X @ w + np.float32(0.3) * normals(rng, rows)
    cuts = np.quantile(util, [0.60, 0.80, 0.90, 0.97])
    y = np.searchsorted(cuts, util).astype(np.float64)
    groups = np.full(rows // group, group, np.int64)
    rem = rows - groups.sum()
    if rem:
        groups = np.concatenate([groups, [rem]])
    return {"X": columns(X, seed), "label": y, "group": groups}
