"""End-to-end training tests (modelled on the reference's
tests/python_package_test/test_engine.py strategy: synthetic sklearn data,
metric thresholds, model round-trips, param interactions)."""

import numpy as np
import pytest
from sklearn.datasets import make_classification, make_regression

import lightgbm_tpu as lgb


def _cls_data(n=3000, seed=7, **kw):
    X, y = make_classification(n_samples=n, n_features=20, n_informative=10,
                               random_state=seed, **kw)
    cut = int(n * 0.8)
    return X[:cut], y[:cut], X[cut:], y[cut:]


def test_regression_learns(rng):
    X, y = make_regression(n_samples=2000, n_features=10, noise=0.1,
                           random_state=42)
    bst = lgb.train({"objective": "regression", "num_leaves": 31,
                     "min_data_in_leaf": 5, "verbosity": -1},
                    lgb.Dataset(X, label=y), num_boost_round=50)
    mse = np.mean((y - bst.predict(X)) ** 2)
    assert mse < 0.05 * y.var()


def test_binary_auc_threshold():
    Xtr, ytr, Xva, yva = _cls_data()
    ds = lgb.Dataset(Xtr, label=ytr)
    ev = {}
    lgb.train({"objective": "binary", "metric": "auc", "verbosity": -1},
              ds, 50, valid_sets=[lgb.Dataset(Xva, label=yva, reference=ds)],
              callbacks=[lgb.record_evaluation(ev)])
    assert ev["valid_0"]["auc"][-1] > 0.95


def test_early_stopping_triggers():
    Xtr, ytr, Xva, yva = _cls_data(n=1500)
    ds = lgb.Dataset(Xtr, label=ytr)
    va = lgb.Dataset(Xva, label=yva, reference=ds)
    bst = lgb.train({"objective": "binary", "metric": "binary_logloss",
                     "learning_rate": 0.3, "verbosity": -1},
                    ds, 500, valid_sets=[va],
                    callbacks=[lgb.early_stopping(10, verbose=False)])
    assert bst.best_iteration > 0
    assert bst.current_iteration < 500


def test_multiclass_accuracy():
    X, y = make_classification(n_samples=3000, n_features=15, n_informative=10,
                               n_classes=4, random_state=3)
    bst = lgb.train({"objective": "multiclass", "num_class": 4,
                     "verbosity": -1}, lgb.Dataset(X, label=y), 30)
    pred = bst.predict(X)
    assert pred.shape == (3000, 4)
    np.testing.assert_allclose(pred.sum(axis=1), 1.0, rtol=1e-5)
    assert (pred.argmax(1) == y).mean() > 0.9


@pytest.mark.parametrize("objective", [
    "regression_l1", "huber", "fair", "quantile", "mape"])
def test_robust_regression_objectives(objective):
    X, y = make_regression(n_samples=1500, n_features=8, noise=0.2,
                           random_state=0)
    # Moderate label scale: fair/huber Newton steps assume O(1) residuals
    # (their default c/alpha are O(1)); keep MAPE away from zero labels.
    y = 10.0 * y / y.std() + 100
    bst = lgb.train({"objective": objective, "alpha": 0.5,
                     "min_data_in_leaf": 5, "verbosity": -1},
                    lgb.Dataset(X, label=y), 60)
    mae = np.mean(np.abs(y - bst.predict(X)))
    assert mae < 0.5 * np.abs(y - y.mean()).mean()


@pytest.mark.parametrize("objective", ["poisson", "gamma", "tweedie"])
def test_positive_regression_objectives(objective):
    rng = np.random.RandomState(1)
    X = rng.randn(1500, 6)
    rate = np.exp(0.5 * X[:, 0] - 0.4 * X[:, 1])
    if objective == "gamma":
        y = rng.gamma(2.0, rate / 2.0) + 1e-3  # strictly positive, mean=rate
    else:
        y = rng.poisson(rate).astype(np.float64)
    bst = lgb.train({"objective": objective, "min_data_in_leaf": 5,
                     "verbosity": -1}, lgb.Dataset(X, label=y), 40)
    pred = bst.predict(X)
    assert (pred > 0).all()
    corr = np.corrcoef(pred, rate)[0, 1]
    assert corr > 0.7


def test_bagging_and_feature_fraction():
    Xtr, ytr, Xva, yva = _cls_data()
    ds = lgb.Dataset(Xtr, label=ytr)
    ev = {}
    lgb.train({"objective": "binary", "metric": "auc",
               "bagging_fraction": 0.6, "bagging_freq": 1,
               "feature_fraction": 0.7, "verbosity": -1},
              ds, 40, valid_sets=[lgb.Dataset(Xva, label=yva, reference=ds)],
              callbacks=[lgb.record_evaluation(ev)])
    assert ev["valid_0"]["auc"][-1] > 0.93


def test_goss_sampling():
    Xtr, ytr, Xva, yva = _cls_data()
    ds = lgb.Dataset(Xtr, label=ytr)
    ev = {}
    lgb.train({"objective": "binary", "metric": "auc",
               "data_sample_strategy": "goss", "verbosity": -1},
              ds, 40, valid_sets=[lgb.Dataset(Xva, label=yva, reference=ds)],
              callbacks=[lgb.record_evaluation(ev)])
    assert ev["valid_0"]["auc"][-1] > 0.93


def test_dart_boosting():
    Xtr, ytr, Xva, yva = _cls_data(n=1500)
    ds = lgb.Dataset(Xtr, label=ytr)
    ev = {}
    lgb.train({"objective": "binary", "boosting": "dart", "metric": "auc",
               "drop_rate": 0.2, "verbosity": -1},
              ds, 40, valid_sets=[lgb.Dataset(Xva, label=yva, reference=ds)],
              callbacks=[lgb.record_evaluation(ev)])
    assert ev["valid_0"]["auc"][-1] > 0.9


def test_rf_boosting():
    Xtr, ytr, Xva, yva = _cls_data(n=1500)
    ds = lgb.Dataset(Xtr, label=ytr)
    ev = {}
    lgb.train({"objective": "binary", "boosting": "rf", "metric": "auc",
               "bagging_fraction": 0.7, "bagging_freq": 1, "verbosity": -1},
              ds, 30, valid_sets=[lgb.Dataset(Xva, label=yva, reference=ds)],
              callbacks=[lgb.record_evaluation(ev)])
    assert ev["valid_0"]["auc"][-1] > 0.9


def test_custom_objective():
    X, y = make_regression(n_samples=1000, n_features=8, noise=0.1,
                           random_state=5)
    ds = lgb.Dataset(X, label=y)
    # custom gradients cross the API boundary per iteration
    # (reference LGBM_BoosterUpdateOneIterCustom, c_api.cpp:2073)
    bst = lgb.Booster(params={"objective": "custom", "min_data_in_leaf": 5,
                              "verbosity": -1}, train_set=ds)
    for _ in range(40):
        bst.update(fobj=lambda score, ts: (score - y, np.ones_like(score)))
    mse = np.mean((y - bst.predict(X, raw_score=True)) ** 2)
    assert mse < 0.1 * y.var()


def test_callable_objective_in_params():
    X, y = make_regression(n_samples=800, n_features=6, noise=0.1,
                           random_state=8)

    def l2_obj(score, train_data):
        return score - y, np.ones_like(score)

    bst = lgb.train({"objective": l2_obj, "min_data_in_leaf": 5,
                     "verbosity": -1}, lgb.Dataset(X, label=y), 40)
    mse = np.mean((y - bst.predict(X, raw_score=True)) ** 2)
    assert mse < 0.1 * y.var()


def test_custom_objective_without_fobj_raises():
    X, y = make_regression(n_samples=100, n_features=3, random_state=9)
    bst = lgb.Booster(params={"objective": "custom", "verbosity": -1},
                      train_set=lgb.Dataset(X, label=y))
    import pytest as _pytest
    with _pytest.raises(ValueError, match="custom"):
        bst.update()


def test_bagging_child_counts_consistent():
    """Out-of-bag rows must not leak into child histogram counts (they would
    corrupt min_data_in_leaf and histogram subtraction)."""
    rng = np.random.RandomState(17)
    X = rng.randn(1000, 4)
    y = (X[:, 0] > 0).astype(float)
    # min_data_in_leaf > bagged rows per leaf forces the count constraint to
    # actually bind; success = training still learns and never produces
    # impossible splits (which would show up as NaN/garbage predictions).
    bst = lgb.train({"objective": "binary", "bagging_fraction": 0.5,
                     "bagging_freq": 1, "min_data_in_leaf": 30,
                     "verbosity": -1}, lgb.Dataset(X, label=y), 20)
    pred = bst.predict(X)
    assert np.isfinite(pred).all()
    assert ((pred > 0.5) == y).mean() > 0.9
    # every leaf count recorded must respect min_data_in_leaf on bagged data
    for tree in bst._gbdt.models[0]:
        if tree.num_leaves > 1:
            assert (tree.leaf_count[: tree.num_leaves] >= 30).all()


def test_missing_values_learned():
    rng = np.random.RandomState(9)
    X = rng.randn(2000, 5)
    # Signal: feature 0 missing  <=>  positive class (pure missing signal).
    y = (rng.rand(2000) < 0.5).astype(int)
    X[y == 1, 0] = np.nan
    bst = lgb.train({"objective": "binary", "verbosity": -1},
                    lgb.Dataset(X, label=y), 10)
    pred = bst.predict(X)
    assert ((pred > 0.5) == y).mean() > 0.99


def test_categorical_feature_learned():
    rng = np.random.RandomState(11)
    n = 2000
    cat = rng.randint(0, 10, n)
    X = np.column_stack([cat.astype(float), rng.randn(n)])
    y = (np.isin(cat, [2, 5, 7])).astype(int)
    bst = lgb.train({"objective": "binary", "verbosity": -1,
                     "min_data_in_leaf": 5},
                    lgb.Dataset(X, label=y, categorical_feature=[0]), 20)
    pred = bst.predict(X)
    assert ((pred > 0.5) == y).mean() > 0.99


def test_monotone_constraints():
    rng = np.random.RandomState(13)
    X = rng.rand(2000, 2)
    y = 2 * X[:, 0] + 0.3 * rng.randn(2000)
    bst = lgb.train({"objective": "regression", "monotone_constraints": [1, 0],
                     "min_data_in_leaf": 5, "verbosity": -1},
                    lgb.Dataset(X, label=y), 30)
    grid = np.linspace(0.05, 0.95, 20)
    Xg = np.column_stack([grid, np.full(20, 0.5)])
    pred = bst.predict(Xg)
    # predictions non-decreasing in the constrained feature
    assert (np.diff(pred) >= -1e-6).all()


def test_weights_affect_training():
    X, y = make_regression(n_samples=1000, n_features=5, noise=0.1,
                           random_state=2)
    w = np.ones(1000)
    w[:500] = 100.0
    bst = lgb.train({"objective": "regression", "min_data_in_leaf": 5,
                     "verbosity": -1},
                    lgb.Dataset(X, label=y, weight=w), 30)
    pred = bst.predict(X)
    mse_heavy = np.mean((y[:500] - pred[:500]) ** 2)
    mse_light = np.mean((y[500:] - pred[500:]) ** 2)
    assert mse_heavy < mse_light


def test_cv_runs():
    X, y = make_regression(n_samples=600, n_features=5, noise=0.1,
                           random_state=4)
    res = lgb.cv({"objective": "regression", "min_data_in_leaf": 5,
                  "verbosity": -1}, lgb.Dataset(X, label=y),
                 num_boost_round=10, nfold=3)
    assert "valid l2-mean" in res
    assert len(res["valid l2-mean"]) == 10
    assert res["valid l2-mean"][-1] < res["valid l2-mean"][0]


def test_feature_importance():
    rng = np.random.RandomState(21)
    X = rng.randn(1500, 5)
    y = 3 * X[:, 2] + 0.1 * rng.randn(1500)
    bst = lgb.train({"objective": "regression", "min_data_in_leaf": 5,
                     "verbosity": -1}, lgb.Dataset(X, label=y), 20)
    imp = bst.feature_importance()
    assert imp.argmax() == 2


def test_rollback_one_iter():
    X, y = make_regression(n_samples=500, n_features=5, random_state=6)
    ds = lgb.Dataset(X, label=y)
    bst = lgb.Booster(params={"objective": "regression",
                              "min_data_in_leaf": 5, "verbosity": -1},
                      train_set=ds)
    for _ in range(5):
        bst.update()
    p5 = bst.predict(X)
    bst.update()
    bst.rollback_one_iter()
    p5b = bst.predict(X)
    np.testing.assert_allclose(p5, p5b, rtol=1e-5)


def test_max_depth_one_gives_stumps():
    from sklearn.datasets import make_classification

    X, y = make_classification(n_samples=500, n_features=6, random_state=0)
    bst = lgb.train({"objective": "binary", "max_depth": 1, "num_leaves": 31,
                     "verbosity": -1}, lgb.Dataset(X, label=y), 5)
    for tree in bst._gbdt.models[0]:
        assert tree.num_leaves == 2  # stumps, not empty trees


def test_goss_other_rate_zero():
    from sklearn.datasets import make_classification

    X, y = make_classification(n_samples=500, n_features=6, random_state=0)
    bst = lgb.train({"objective": "binary", "data_sample_strategy": "goss",
                     "other_rate": 0.0, "top_rate": 0.3, "num_leaves": 7,
                     "verbosity": -1}, lgb.Dataset(X, label=y), 5)
    assert bst.num_trees() >= 1


def test_goss_device_mask_semantics():
    """Device GOSS keeps exactly top_k rows at weight 1, ~other_k rows
    amplified, rest zero (reference goss.hpp:30-60)."""
    import jax
    import numpy as np
    from lightgbm_tpu.sampling import goss_mask_device

    rng = np.random.RandomState(0)
    n = 5000
    g = rng.randn(n).astype(np.float32)
    h = np.full(n, 0.25, np.float32)
    top_k, other_k = 500, 750
    amp = (1.0 - 0.1) / 0.15
    mask = np.asarray(goss_mask_device(g, h, jax.random.PRNGKey(0),
                                       top_k, other_k, amp))
    assert (mask == 1.0).sum() == top_k
    assert abs((np.isclose(mask, amp)).sum() - other_k) <= 1
    # top set really is the top |g*h|
    score = np.abs(g * h)
    thr = np.sort(score)[-top_k]
    assert score[mask == 1.0].min() >= thr - 1e-7
    assert (mask == 0.0).sum() == n - top_k - np.isclose(mask, amp).sum()


def test_degenerate_stop_deferred_exactly_one_extra():
    """The per-round deterministic fused path defers the degenerate-stop
    fetch by one iteration (pipelining): driving update() directly, a
    constant target stops exactly one iteration after the first degenerate
    tree — two stored trees, which pins that the deferral is active on the
    per-round path.  (engine.train now routes this config through the
    iteration-packed path, whose pack-boundary check stores no stumps at
    all — pinned in tests/test_iter_pack.py.)"""
    X = np.random.RandomState(0).randn(500, 4)
    y = np.zeros(500)
    bst = lgb.Booster(params={"objective": "regression", "verbosity": -1,
                              "num_leaves": 7},
                      train_set=lgb.Dataset(X, label=y))
    for _ in range(10):
        if bst.update():
            break
    assert bst.num_trees() == 2


def test_degenerate_stop_immediate_with_dart():
    """DART mutates scores between iterations, so its stop check must stay
    immediate: a constant target stops after the first degenerate tree."""
    X = np.random.RandomState(0).randn(500, 4)
    y = np.zeros(500)
    bst = lgb.train({"objective": "regression", "boosting": "dart",
                     "verbosity": -1, "num_leaves": 7},
                    lgb.Dataset(X, label=y), 10)
    assert bst.num_trees() == 1


def test_mosaic_compile_failure_raises_and_names_optouts(monkeypatch):
    """A Pallas/Mosaic kernel compile failure must reach the user — with
    the compiler's own first line and the explicit opt-outs named — and
    never be retried on a slower implementation (ISSUE-21: the silent
    degrade to the XLA one-hot hid that the default TPU path did not
    compile at all)."""
    from lightgbm_tpu.ops import pallas_histogram

    def boom(*a, **k):
        raise RuntimeError(
            "Mosaic failed to compile TPU kernel: infer-vector-layout: "
            "unsupported shape cast (simulated)")

    monkeypatch.setattr(pallas_histogram, "histogram_flat", boom)
    X, y = make_regression(n_samples=600, n_features=6, noise=0.1,
                           random_state=3)
    for impl in ("pallas",):
        with pytest.raises(RuntimeError) as ei:
            lgb.train({"objective": "regression", "verbosity": -1,
                       "num_leaves": 15, "tpu_histogram_impl": impl},
                      lgb.Dataset(X, label=y), 8)
        msg = str(ei.value)
        assert "unsupported shape cast (simulated)" in msg
        assert "tpu_histogram_impl=onehot" in msg
        assert "tpu_wave_kernel=unfused" in msg
        assert isinstance(ei.value.__cause__, RuntimeError)


def test_explicit_impl_failure_raises(monkeypatch):
    """An explicit non-pallas impl choice must fail loudly, not degrade."""
    from lightgbm_tpu.ops import histogram

    def boom(*a, **k):
        raise RuntimeError("Mosaic failed to compile TPU kernel (simulated)")

    monkeypatch.setattr(histogram, "histogram_segment", boom)
    X, y = make_regression(n_samples=300, n_features=4, noise=0.1,
                           random_state=3)
    with pytest.raises(Exception, match="[Mm]osaic"):
        lgb.train({"objective": "regression", "verbosity": -1,
                   "tpu_histogram_impl": "segment"},
                  lgb.Dataset(X, label=y), 3)
