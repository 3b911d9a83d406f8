"""Forced splits (reference ``ForceSplits``,
``serial_tree_learner.cpp:620`` + ``forcedsplits_filename``): a JSON tree of
(feature, threshold) is applied from the root before gain-driven growth."""

import json

import numpy as np
import pytest
from sklearn.datasets import make_classification

import lightgbm_tpu as lgb


@pytest.mark.parametrize("n_samples,extra,body", [
    # 2 000 rows: the mask body
    pytest.param(2000, {}, "mask", id="mask"),
    # above the perm layouts' floor: the wave of one; a 3-slot histogram
    # pool evicts the root's left child before its own forced split comes
    # up, so _apply_forced reads it through the wave's pooled parent
    # lookup (recompute on a miss)
    pytest.param(6000, {"histogram_pool_size": 0.001}, "wave",
                 id="wave-of-one-pooled"),
])
def test_forced_root_and_nested_child(tmp_path, n_samples, extra, body):
    X, y = make_classification(n_samples=n_samples, n_features=8,
                               n_informative=4, random_state=0)
    spec = {
        "feature": 5, "threshold": 0.25,
        "left": {"feature": 3, "threshold": -0.5,
                 "left": {"feature": 1, "threshold": 0.0}},
        "right": {"feature": 0, "threshold": 0.1},
    }
    path = tmp_path / "forced.json"
    path.write_text(json.dumps(spec))
    bst = lgb.train(dict({"objective": "binary", "num_leaves": 15,
                          "min_data_in_leaf": 5, "verbosity": -1,
                          "forcedsplits_filename": str(path)}, **extra),
                    lgb.Dataset(X, label=y), 4)
    plan = bst._gbdt.plan
    assert plan.body == body and plan.pool is bool(extra), str(plan)
    if extra:
        assert bst._gbdt.grow.pool_slots(8) == 3
    for tree in bst._gbdt.models[0]:
        # BFS order: root, its left, its right, the left's left
        assert list(tree.split_feature[:4]) == [5, 3, 0, 1]
        assert tree.left_child[1] == 3
        # node 0 = forced root; node 1 = forced split of its LEFT child
        assert tree.split_feature[0] == 5
        assert tree.split_feature[1] == 3
        # node 1 must actually be the left child of node 0
        assert tree.left_child[0] == 1
        # forced thresholds bin-quantized around the requested value
        td = bst._gbdt.train_data
        thr0 = td.binned.mappers[5].bin_to_threshold(tree.split_bin[0])
        assert abs(thr0 - 0.25) < 0.2
    # training still learns: accuracy beyond chance
    acc = ((bst.predict(X) > 0.5) == (y > 0.5)).mean()
    assert acc > 0.8


def test_forced_splits_survive_model_roundtrip(tmp_path):
    X, y = make_classification(n_samples=1200, n_features=6, random_state=1)
    path = tmp_path / "forced.json"
    path.write_text(json.dumps({"feature": 2, "threshold": 0.0}))
    params = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
              "forcedsplits_filename": str(path)}
    bst = lgb.train(params, lgb.Dataset(X, label=y), 3)
    s = bst.model_to_string()
    reloaded = lgb.Booster(model_str=s)
    np.testing.assert_allclose(reloaded.predict(X[:50]), bst.predict(X[:50]),
                               rtol=1e-6)


def test_forced_splits_reject_wave_config(tmp_path):
    X, y = make_classification(n_samples=4000 + 2100, n_features=6,
                               random_state=2)
    path = tmp_path / "forced.json"
    path.write_text(json.dumps({"feature": 0, "threshold": 0.0}))
    # leaf_batch>1 downgrades with a warning rather than erroring
    bst = lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1,
                     "tpu_leaf_batch": 8,
                     "forcedsplits_filename": str(path)},
                    lgb.Dataset(X, label=y), 2)
    assert bst._gbdt.models[0][0].split_feature[0] == 0


def test_forced_splits_survive_intermediate_monotone(tmp_path):
    """_inter_refresh overwrites best_* for all leaves at the end of each
    growth step, but _apply_forced re-pins the pending forced directive at
    the START of every step (grower.py body), so forced splits must still
    land under monotone_constraints_method=intermediate."""
    rng = np.random.RandomState(0)
    n = 4000
    X = rng.rand(n, 4).astype(np.float32)
    y = 2 * X[:, 0] + np.sin(5 * X[:, 1]) + 0.5 * X[:, 2] \
        + 0.1 * rng.randn(n)
    path = tmp_path / "forced.json"
    path.write_text(json.dumps({
        "feature": 3, "threshold": 0.5,
        "left": {"feature": 3, "threshold": 0.25}}))
    for method in ("basic", "intermediate"):
        params = {"objective": "regression", "num_leaves": 15,
                  "monotone_constraints": [1, 0, 0, 0],
                  "monotone_constraints_method": method,
                  "forcedsplits_filename": str(path),
                  "min_data_in_leaf": 5, "verbosity": -1}
        bst = lgb.train(params, lgb.Dataset(X, label=y), 2)
        for tree in bst._gbdt.models[0]:
            assert tree.split_feature[0] == 3
            assert tree.left_child[0] == 1
            assert tree.split_feature[1] == 3
