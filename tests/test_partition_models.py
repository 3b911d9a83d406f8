"""The partition pass changes no model.

A stable partition writes the same ``perm`` whatever computes it, so the
kernels are handed the same rows in the same order and every tree is the
parent's.  ``fixtures/partition_parent_models.json`` holds
``model_to_string()`` of a handful of compositions as the commit BEFORE
the one-pass partition (PR 29, ``fcf13ae``) wrote them — each a different
column read or layout of the pass: plain bins in a wave of four, a
categorical column and NaNs, EFB-decoded bundle columns, packed4 nibbles
under quantised gradients, the data mesh (``nl`` per slot feeds the
global smaller side) and the feature mesh (the go-left vector arrives by
row id).  Recorded with::

    PYTHONPATH=<checkout of the parent> python tests/test_partition_models.py

(8 virtual CPU devices, as ``conftest.py`` forces for the tests).

The ``ragged_*`` compositions were recorded the same way from the commit
BEFORE the unfused wave's one ragged histogram launch (PR 33,
``ad9f38f``), whose per-leaf ``histogram_flat`` loop they ran: the Pallas
kernel (interpreted here) on the unfused wave — a wave of sixteen and of
one, quantised int8 sums, packed4 nibbles, the histogram pool (parents
recomputed on a miss by the per-leaf call that stays), EFB's bundle
columns, the data mesh (a shard's smaller side may hold all its rows) and
one sampled tree grown on its in-bag rows.  Every slot accumulates its
segment in the blocks the per-leaf call used, so the float32 sums — and
the models — are the parent's bit for bit.
"""

import json
import os
import sys

import numpy as np
import pytest

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "partition_parent_models.json")
BASE = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
        "verbosity": -1, "metric": "none", "seed": 3}
CASES = {
    "plain_wave4": (6000, {"tpu_leaf_batch": 4}),
    "categorical_nan": (6000, {"categorical_feature": [3]}),
    "efb": (6000, {"enable_bundle": True, "tpu_leaf_batch": 4}),
    "packed4_quantised": (6000, {"max_bin": 15, "use_quantized_grad": True,
                                 "tpu_leaf_batch": 4}),
    "data_mesh": (20000, {"tree_learner": "data", "tpu_leaf_batch": 4}),
    "feature_mesh": (6000, {"tree_learner": "feature"}),
}
RAGGED = {"tpu_histogram_impl": "pallas", "tpu_wave_kernel": "unfused",
          "tpu_leaf_batch": 4}
CASES.update({name: (n, dict(RAGGED, **extra)) for name, (n, extra) in {
    "ragged_wave16": (6000, {"tpu_leaf_batch": 16, "num_leaves": 31}),
    "ragged_wave1": (6000, {"tpu_leaf_batch": 1}),
    "ragged_quantised": (6000, {"use_quantized_grad": True}),
    "ragged_packed4": (6000, {"max_bin": 15}),
    "ragged_pool": (6000, {"histogram_pool_size": 0.2}),
    "ragged_efb": (6000, {"enable_bundle": True}),
    "ragged_data_mesh": (20000, {"tree_learner": "data"}),
    "ragged_goss": (6000, {"data_sample_strategy": "goss", "top_rate": 0.2,
                           "other_rate": 0.1, "learning_rate": 0.5}),
}.items()})
ROUNDS = 3


def _data(n, sparse):
    rng = np.random.RandomState(11)
    X = rng.randn(n, 10)
    X[:, 3] = rng.randint(0, 7, n)              # the categorical column
    X[rng.rand(n) < 0.1, 1] = np.nan
    if sparse:                                  # exclusive columns: bundled
        which = rng.randint(5, 10, n)
        for j in range(5, 10):
            X[:, j] = np.where(which == j, np.abs(X[:, j]) + 0.5, 0.0)
    z = (X[:, 0] + 0.6 * np.nan_to_num(X[:, 1]) * X[:, 2]
         + 0.5 * (X[:, 3] % 2) + 0.4 * X[:, 6] + 0.3 * rng.randn(n))
    return X, (z > 0).astype(np.float64)


def _model(name):
    import lightgbm_tpu as lgb

    n, extra = CASES[name]
    X, y = _data(n, sparse="efb" in name)
    bst = lgb.train(dict(BASE, **extra), lgb.Dataset(X, label=y), ROUNDS)
    g = bst._gbdt
    assert g.plan.body == "wave", str(g.plan)   # the pass under test ran
    assert g.grower_cfg.bundled is ("efb" in name)
    assert g.plan.packed4 is ("packed4" in name)
    assert g.plan.layout == ("data" if "data_mesh" in name else "feature"
                             if "feature_mesh" in name else "single")
    if name.startswith("ragged"):               # the branch under test ran
        assert g.plan.hist_impl == "pallas" and not g.plan.fused, str(g.plan)
        assert g.plan.pool is (name == "ragged_pool")
        assert (g.plan.sampling == "subset") is (name == "ragged_goss")
    return bst.model_to_string()


@pytest.mark.parametrize("name", list(CASES))
def test_trees_are_the_parents(name):
    with open(FIXTURE) as f:
        want = json.load(f)[name]
    assert _model(name) == want


if __name__ == "__main__":
    sys.path.append(os.path.dirname(os.path.dirname(      # after PYTHONPATH
        os.path.abspath(__file__))))
    import _hermetic
    _hermetic.force_cpu(8)
    out = {name: _model(name) for name in CASES}
    with open(FIXTURE, "w") as f:
        json.dump(out, f, indent=0, sort_keys=True)
    print({k: len(v) for k, v in out.items()})
