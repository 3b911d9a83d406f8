"""Seeded data generators, one file each, found by the ``generator`` name in
a configuration file: ``generators/<name>.py`` holds
``make(seed, **data)`` for the configuration's ``data`` arguments and
returns ``{"X": (N, F) float32, "label": (N,) float64[, "group": (Q,)
int64]}``.  A later PR adds a data shape by adding a file.

The formulas are those of ``bench.make_higgs_like`` / ``bench.make_msltr_like``
as of PR 21, kept here so the yardstick's data does not move when a later PR
edits ``bench.py`` — but drawn by numpy's ``Generator`` straight into float32
(311 M normals for the ranking shape: 43 s through ``RandomState.randn`` +
``astype``, about 2 s this way; data is made anew in every run and counts as
set-up).

THE ROWS ARE FIXED PER CONFIGURATION: they come from the configuration's
``data_seed``, and the run's ``--seed`` decides only the ORDER OF THE FEATURE
COLUMNS (and the comparison's samples).  Why: trees grown on freshly drawn
data differ in shape, and an iteration's time with them — across six seeds
``train_s_per_iter`` spread 5-6 % while two runs of one seed agreed to
0.007 %; with the same rows in another ROW order the binning sample and the
float32 sums still moved the trees (1.7 %, two modes).  Another column order
leaves every per-feature histogram bit for bit what it was, so every seed
does the same work on inputs that differ (PERF.md, Findings PR 24).  The
price: a cell sees one data set, and since the program bakes the labels into
its compiled step, only a checkout's first run compiles.
"""

from __future__ import annotations

import importlib

import numpy as np


def rng_of(seed: int) -> np.random.Generator:
    return np.random.Generator(np.random.SFC64(int(seed)))


def normals(rng, *shape) -> np.ndarray:
    return rng.standard_normal(shape, dtype=np.float32)


def columns(X: np.ndarray, seed: int) -> np.ndarray:
    """``X`` with its feature columns in the order the run's seed draws."""
    return np.take(X, rng_of(seed).permutation(X.shape[1]), axis=1)


def make(name: str, seed: int, **data_args) -> dict:
    return importlib.import_module(f"{__name__}.{name}").make(
        seed=seed, **data_args)
