"""Every formerly-dead parameter now has behavior (or an explicit rejection).

VERDICT round-2 ask #7: reg_sqrt, monotone_penalty + method rejection,
pred_early_stop*, interaction_constraints per-branch semantics, dataset
binary save/load (save_binary), inert-layout-param warnings.
"""

import io
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config


def _reg_data(n=2000, f=6, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = X[:, 0] * 2 + np.abs(X[:, 1]) + 0.1 * rng.randn(n)
    return X, y


P = {"objective": "regression", "num_leaves": 15, "min_data_in_leaf": 5,
     "verbosity": -1, "deterministic": True}


def test_reg_sqrt():
    rng = np.random.RandomState(1)
    X = rng.randn(1500, 4)
    y = (X[:, 0] + 0.05 * rng.randn(1500)) ** 2 * 10  # heavy right tail
    plain = lgb.train(P, lgb.Dataset(X, label=y), 30)
    sq = lgb.train(dict(P, reg_sqrt=True), lgb.Dataset(X, label=y), 30)
    p_plain, p_sq = plain.predict(X), sq.predict(X)
    assert not np.allclose(p_plain, p_sq)
    # sqrt transform fits the transformed scale; predictions square back to
    # the label scale and remain non-negative-ish for this target
    assert np.mean((p_sq - y) ** 2) < np.var(y)
    # raw scores live on the sqrt scale: predictions = sign(s)*s^2
    raw = sq.predict(X, raw_score=True)
    np.testing.assert_allclose(np.sign(raw) * raw * raw, p_sq, rtol=1e-6)
    # the back-transform survives save/load ("objective=regression sqrt")
    re = lgb.Booster(model_str=sq.model_to_string())
    np.testing.assert_allclose(re.predict(X), p_sq, rtol=1e-5, atol=1e-6)


def test_monotone_penalty_changes_trees():
    X, y = _reg_data()
    mono = [1, 0, 0, 0, 0, 0]
    base = lgb.train(dict(P, monotone_constraints=mono),
                     lgb.Dataset(X, label=y), 10)
    pen = lgb.train(dict(P, monotone_constraints=mono, monotone_penalty=2.0),
                    lgb.Dataset(X, label=y), 10)

    def root_feats(bst):
        return [r["split_feature"] for r in bst.trees_to_dataframe()
                if r["node_depth"] == 0 and r["split_feature"] is not None]
    # monotone_penalty=2 multiplies depth-0/1 monotone gains by ~0
    # (reference: penalization >= depth+1 -> kEpsilon), so the constrained
    # feature cannot win the root split anymore
    assert "Column_0" in root_feats(base)
    assert "Column_0" not in root_feats(pen)
    assert not np.allclose(base.predict(X), pen.predict(X))


def test_monotone_bounds_enforced():
    """Basic-mode bounds: model predictions must be monotone in the
    constrained feature (reference BasicLeafConstraints midpoint caps)."""
    rng = np.random.RandomState(3)
    n = 4000
    x0 = rng.uniform(-2, 2, n)
    y = 1.5 * x0 + np.sin(x0 * 4) + 0.2 * rng.randn(n)  # locally non-monotone
    X = np.column_stack([x0, rng.randn(n)])
    bst = lgb.train(dict(P, monotone_constraints=[1, 0], num_leaves=31),
                    lgb.Dataset(X, label=y), 30)
    grid = np.linspace(-2, 2, 200)
    pred = bst.predict(np.column_stack([grid, np.zeros(200)]))
    assert np.all(np.diff(pred) >= -1e-6), "violation of monotone increase"


def test_monotone_method_unknown_rejected():
    X, y = _reg_data(n=300)
    with pytest.raises(ValueError, match="monotone_constraints_method"):
        lgb.train(dict(P, monotone_constraints=[1, 0, 0, 0, 0, 0],
                       monotone_constraints_method="bogus"),
                  lgb.Dataset(X, label=y), 2)


def test_pred_early_stop_binary():
    rng = np.random.RandomState(5)
    X = rng.randn(2000, 5)
    y = (X[:, 0] * 3 > 0).astype(float)  # strong signal, huge margins
    p = dict(P, objective="binary")
    bst = lgb.train(p, lgb.Dataset(X, label=y), 40)
    full = bst.predict(X, raw_score=True)
    es = bst.predict(X, raw_score=True, pred_early_stop=True,
                     pred_early_stop_freq=5, pred_early_stop_margin=2.0)
    # early-stopped scores stop accumulating once |score| > margin: same
    # sign everywhere, smaller magnitude where stopped, identical where not
    assert np.all(np.sign(es) == np.sign(full))
    assert np.any(np.abs(es) < np.abs(full) - 1e-9)
    assert np.all(np.abs(es) <= np.abs(full) + 1e-9)
    # a loose margin never triggers -> exact equality
    noop = bst.predict(X, raw_score=True, pred_early_stop=True,
                       pred_early_stop_margin=1e9)
    np.testing.assert_allclose(noop, full, rtol=1e-6, atol=1e-7)


def test_interaction_constraints_per_branch():
    """Trees may not mix features from different groups on one path
    (reference ColSampler::GetByNode)."""
    rng = np.random.RandomState(7)
    n = 4000
    X = rng.randn(n, 4)
    # joint signal across the group boundary: unconstrained trees would mix
    y = (X[:, 0] * X[:, 2] + 0.5 * X[:, 1] + 0.5 * X[:, 3]
         + 0.1 * rng.randn(n))
    p = dict(P, num_leaves=15,
             interaction_constraints=[[0, 1], [2, 3]])
    bst = lgb.train(p, lgb.Dataset(X, label=y), 10)
    groups = [{0, 1}, {2, 3}]

    def walk_paths(node, path):
        if "leaf_index" in node:
            return [path]
        f = node["split_feature"]
        return (walk_paths(node["left_child"], path | {f})
                + walk_paths(node["right_child"], path | {f}))

    mixed = 0
    for t in bst.dump_model()["tree_info"]:
        for path in walk_paths(t["tree_structure"], set()):
            ok = any(path <= g for g in groups)
            mixed += 0 if ok else 1
    assert mixed == 0, f"{mixed} branch(es) mix interaction groups"
    # unconstrained comparison: mixing must actually happen on this data
    un = lgb.train(dict(P, num_leaves=15), lgb.Dataset(X, label=y), 10)
    un_mixed = 0
    for t in un.dump_model()["tree_info"]:
        for path in walk_paths(t["tree_structure"], set()):
            if not any(path <= g for g in groups):
                un_mixed += 1
    assert un_mixed > 0


def test_binary_dataset_round_trip(tmp_path):
    X, y = _reg_data(n=1500)
    w = np.random.RandomState(0).rand(1500)
    ds = lgb.Dataset(X, label=y, weight=w)
    bst1 = lgb.train(P, ds, 10)
    path = str(tmp_path / "train.bin")
    ds.save_binary(path)
    ds2 = lgb.Dataset(path)
    bst2 = lgb.train(P, ds2, 10)
    np.testing.assert_allclose(bst1.predict(X), bst2.predict(X),
                               rtol=1e-5, atol=1e-6)


def test_cli_save_binary_and_train_from_bin(tmp_path):
    X, y = _reg_data(n=400, f=3)
    data_path = str(tmp_path / "t.csv")
    np.savetxt(data_path, np.column_stack([y, X]), delimiter=",", fmt="%.8g")
    from lightgbm_tpu.cli import run
    rc = run(["task=save_binary", f"data={data_path}", "verbosity=-1"])
    assert rc == 0 and os.path.exists(data_path + ".bin")
    out = str(tmp_path / "m.txt")
    rc = run(["task=train", f"data={data_path}.bin", "num_iterations=5",
              "objective=regression", f"output_model={out}", "verbosity=-1"])
    assert rc == 0 and os.path.exists(out)


def test_inert_layout_params_warn(capsys):
    X, y = _reg_data(n=300)
    lgb.train(dict(P, is_enable_sparse=False), lgb.Dataset(X, label=y), 1)
    err = capsys.readouterr()
    text = err.out + err.err
    assert "is_enable_sparse" in text


def test_max_bin_by_feature_caps_per_feature():
    rng = np.random.RandomState(0)
    X = rng.randn(2000, 3)
    y = (X[:, 0] > 0).astype(float)
    ds = lgb.Dataset(X, label=y)
    td = ds.construct({"objective": "binary", "max_bin": 100,
                       "max_bin_by_feature": [5, 100, 100],
                       "verbosity": -1})
    assert td.binned.num_bins_per_feature[0] <= 6   # 5 value bins (+nan)
    assert td.binned.num_bins_per_feature[1] > 20


def test_feature_contri_scales_gains():
    rng = np.random.RandomState(1)
    X = rng.randn(3000, 3)
    # feature 0 and 1 both informative; crushing 0's contribution must
    # steer the root split to feature 1
    y = (X[:, 0] + 0.95 * X[:, 1] > 0).astype(float)
    base = {"objective": "binary", "num_leaves": 7, "verbosity": -1}
    b0 = lgb.train(base, lgb.Dataset(X, label=y), 1)
    assert b0._gbdt.models[0][0].split_feature[0] == 0
    b1 = lgb.train(dict(base, feature_contri=[0.01, 1.0, 1.0]),
                   lgb.Dataset(X, label=y), 1)
    assert b1._gbdt.models[0][0].split_feature[0] == 1


def test_early_stopping_min_delta():
    rng = np.random.RandomState(2)
    X = rng.randn(1500, 5)
    y = (X[:, 0] > 0).astype(float)
    ds = lgb.Dataset(X[:1000], label=y[:1000])
    vs = lgb.Dataset(X[1000:], label=y[1000:], reference=ds)
    params = {"objective": "binary", "num_leaves": 7, "metric": "auc",
              "verbosity": -1, "early_stopping_round": 3}
    full = lgb.train(params, ds, 60, valid_sets=[vs])
    strict = lgb.train(dict(params, early_stopping_min_delta=0.05), ds, 60,
                       valid_sets=[vs])
    # demanding 0.05 AUC improvement per round stops much earlier
    assert strict.best_iteration <= full.best_iteration
    assert strict.num_trees() < 60


def test_xgboost_dart_mode_changes_scaling():
    rng = np.random.RandomState(3)
    X = rng.randn(1200, 4)
    y = (X[:, 0] > 0).astype(float)
    base = {"objective": "binary", "boosting": "dart", "num_leaves": 7,
            "verbosity": -1, "drop_rate": 0.5, "skip_drop": 0.0,
            "drop_seed": 7}
    b_norm = lgb.train(base, lgb.Dataset(X, label=y), 8)
    b_xgb = lgb.train(dict(base, xgboost_dart_mode=True),
                      lgb.Dataset(X, label=y), 8)
    p_norm = b_norm.predict(X[:50], raw_score=True)
    p_xgb = b_xgb.predict(X[:50], raw_score=True)
    assert not np.allclose(p_norm, p_xgb)


def test_predict_shape_check_and_start_iteration_predict():
    rng = np.random.RandomState(4)
    X = rng.randn(800, 4)
    y = (X[:, 0] > 0).astype(float)
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "verbosity": -1}, lgb.Dataset(X, label=y), 6)
    with pytest.raises(ValueError, match="features"):
        bst.predict(X[:5, :2])
    p = bst.predict(X[:5, :2], predict_disable_shape_check=True)
    assert p.shape == (5,)
    # start_iteration_predict kwarg == start_iteration argument
    a = bst.predict(X[:20], raw_score=True, start_iteration=3)
    b = bst.predict(X[:20], raw_score=True, start_iteration_predict=3)
    np.testing.assert_allclose(a, b)


def test_two_round_loading_matches_direct(tmp_path):
    """two_round=true streams the text file in chunks (pass 1: sample +
    labels; pass 2: bin chunk-by-chunk) and must produce the same model as
    the direct in-memory load (reference dataset_loader.cpp:203,1022)."""
    import subprocess
    import sys

    rng = np.random.RandomState(6)
    n, f = 9000, 8
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
    data = str(tmp_path / "tr.csv")
    np.savetxt(data, np.column_stack([y, X]), delimiter=",", fmt="%.7g")

    # loader-level equality: bins identical to the one-shot path
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.dataset import TrainData, load_train_data_two_round
    from lightgbm_tpu.io.parser import load_data_file

    cfg = Config({"objective": "binary", "verbosity": -1, "max_bin": 63})
    td2 = load_train_data_two_round(data, cfg, block_lines=1000)
    Xd, yd, _w, _g = load_data_file(data)
    td1 = TrainData.build(Xd, yd, cfg)
    np.testing.assert_array_equal(td1.binned.bins, td2.binned.bins)
    np.testing.assert_allclose(td1.label, td2.label)

    # CLI end-to-end with two_round=true
    model = str(tmp_path / "m2r.txt")
    r = subprocess.run(
        [sys.executable, "-u", "-m", "lightgbm_tpu", "task=train",
         f"data={data}", "objective=binary", "num_leaves=15",
         "num_iterations=5", "two_round=true", "verbosity=-1",
         "max_bin=63", f"output_model={model}"],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": os.pathsep.join(
                 [os.path.dirname(os.path.dirname(os.path.abspath(
                     __file__)))] + os.environ.get(
                     "PYTHONPATH", "").split(os.pathsep))})
    assert r.returncode == 0, r.stdout + r.stderr
    loaded = lgb.Booster(model_file=model)
    direct = lgb.train({"objective": "binary", "num_leaves": 15,
                        "verbosity": -1, "max_bin": 63},
                       lgb.Dataset(Xd, label=yd), 5)
    np.testing.assert_allclose(loaded.predict(Xd), direct.predict(Xd),
                               rtol=1e-5, atol=1e-6)
