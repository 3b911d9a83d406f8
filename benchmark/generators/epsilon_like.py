"""Epsilon-like synthetic binary data (no files): dense, wide, every row of
unit length, as the published set is (PASCAL Large Scale Learning Challenge
2008: 2 000 features, rows scaled to unit length).

    X     = N(0, 1) float32, each row divided by its Euclidean length
    score = sqrt(features) * X @ w / |w|,  w ~ N(0, 1)      (about N(0, 1))
    label = score + NOISE * N(0, 1) > 0

``NOISE`` = 0.43 is the signal-to-noise at which the linear score itself
reads an AUC of 0.950 against the labels (0.9503 on 2 M draws of the
formula, 0.9502 on 100 000 x 2 000 rows of this generator), beside the
published 0.950243 of LightGBM on the real set (docs/GPU-Performance.rst,
BASELINE.md).  The formula is ``bench.make_epsilon_like``'s with the rows
scaled and the noise set from that AUC.

800 M normals and a 3.2 GB column gather are most of a run's set-up in one
thread (15 s + 18 s), so the rows are drawn in ``BLOCKS`` row blocks, each
from a generator of its own (``rng_of(data_seed * (BLOCKS + 1) + block)``;
stream 0 draws ``w`` and the label noise) and each permuted by
``columns``: the data depend on ``data_seed`` and ``BLOCKS``, never on how
many threads drew them (numpy's generators and ``take`` release the
interpreter lock).
"""

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import columns, normals, rng_of

NOISE = 0.43
BLOCKS = 16


def make(rows: int, features: int, data_seed: int, seed: int) -> dict:
    stream = int(data_seed) * (BLOCKS + 1)
    head = rng_of(stream)
    w = normals(head, features)
    w *= np.float32(np.sqrt(features) / np.linalg.norm(w))
    noise = normals(head, rows)
    X = np.empty((rows, features), np.float32)
    score = np.empty(rows, np.float32)
    edges = [rows * k // BLOCKS for k in range(BLOCKS + 1)]

    def block(k: int) -> None:
        a, b = edges[k], edges[k + 1]
        x = normals(rng_of(stream + 1 + k), b - a, features)
        x /= np.sqrt(np.einsum("ij,ij->i", x, x))[:, None]
        score[a:b] = x @ w
        X[a:b] = columns(x, seed)

    with ThreadPoolExecutor(min(BLOCKS, os.cpu_count() or 1)) as pool:
        list(pool.map(block, range(BLOCKS)))
    y = score + np.float32(NOISE) * noise > 0
    return {"X": X, "label": y.astype(np.float64)}
