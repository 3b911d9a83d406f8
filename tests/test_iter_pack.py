"""Iteration-packed training (docs/ITER_PACK.md): ``tpu_iter_pack=K`` scans
K boosting rounds into ONE jitted dispatch.  Pack size is a scheduling
knob, never a modeling knob — these tests pin bitwise-identical models
between K=1 and K=4 across the supported mask configurations, identical
early-stopping behavior, the exact pack-boundary degenerate stop, and the
auto-degrade contract."""

import numpy as np
import pytest

import lightgbm_tpu as lgb


def _data(n=600, f=8, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.5 * rng.randn(n) > 0).astype(np.float64)
    return X, y


BASE = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
        "verbosity": -1}


def _train(extra, pack, num_round=8, label=None, X=None):
    Xd, y = _data()
    if X is not None:
        Xd = X
    if label is not None:
        y = label
    params = dict(BASE, tpu_iter_pack=pack)
    params.update(extra)
    return lgb.train(params, lgb.Dataset(Xd, label=y), num_round)


def _assert_identical(b1, b4, scores_exact=True):
    """Bitwise model identity: tree structure, leaf values, final scores.
    ``scores_exact=False`` allows float dust in the resident train scores
    (mid-pack early stop recovers them by predict-and-subtract); the MODEL
    stays bitwise identical either way."""
    assert b1.num_trees() == b4.num_trees()
    for c1, c4 in zip(b1._gbdt.models, b4._gbdt.models):
        for t1, t4 in zip(c1, c4):
            assert t1.num_leaves == t4.num_leaves
            k = max(t1.num_leaves - 1, 0)
            assert np.array_equal(t1.split_feature[:k], t4.split_feature[:k])
            assert np.array_equal(t1.split_bin[:k], t4.split_bin[:k])
            assert np.array_equal(t1.leaf_value, t4.leaf_value)
    s1 = np.asarray(b1._gbdt.scores)
    s4 = np.asarray(b4._gbdt.scores)
    if scores_exact:
        assert np.array_equal(s1, s4)
    else:
        np.testing.assert_allclose(s1, s4, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("extra", [
    {},                                                    # binary, static
    {"bagging_fraction": 0.7, "bagging_freq": 2},          # device bagging
    {"feature_fraction": 0.6},                             # device col mask
    {"bagging_fraction": 0.8, "bagging_freq": 1,
     "feature_fraction": 0.7},                             # both dynamic
    {"data_sample_strategy": "goss",
     "learning_rate": 0.5},           # in-trace GOSS, sampled from round 2
    {"cegb_tradeoff": 0.5, "cegb_penalty_split": 0.02,
     "cegb_penalty_feature_coupled": [2.0] * 8},           # in-trace CEGB
], ids=["binary", "bagging", "feature_fraction", "bagging+ff", "goss",
        "cegb"])
def test_pack_bitwise_identical_binary(extra):
    _assert_identical(_train(extra, 1), _train(extra, 4))


def test_pack_bitwise_identical_multiclass():
    rng = np.random.RandomState(1)
    y = rng.randint(0, 3, 600).astype(np.float64)
    extra = {"objective": "multiclass", "num_class": 3}
    _assert_identical(_train(extra, 1, label=y), _train(extra, 4, label=y))


def test_pack_bitwise_identical_quantized():
    extra = {"use_quantized_grad": True}
    _assert_identical(_train(extra, 1), _train(extra, 4))


def test_pack_remainder_rounds():
    """num_boost_round not divisible by K: the trailing smaller pack trains
    the exact remaining rounds."""
    b = _train({}, 4, num_round=10)
    assert b.num_trees() == 10
    _assert_identical(_train({}, 1, num_round=10), b)


def test_auto_pack_matches_explicit_on_static_masks():
    """tpu_iter_pack=0 (auto) packs static-mask configs and must produce
    the same model as the explicit pack path AND the per-round semantics."""
    auto = _train({"tpu_iter_pack": 0}, 0)
    _assert_identical(auto, _train({}, 1))


def test_early_stopping_fires_same_iteration():
    """Early stopping must fire at the SAME iteration for K=1 and K=4: the
    engine commits pack rounds one by one and replays callbacks per round
    (valid scores update per committed tree), then discards the mid-pack
    tail — per-iteration semantics survive packing exactly."""
    X, y = _data()
    Xv, yv = _data(n=300, seed=7)
    results = []
    for pack in (1, 4):
        params = dict(BASE, tpu_iter_pack=pack, metric="binary_logloss",
                      early_stopping_round=3)
        bst = lgb.train(params, lgb.Dataset(X, label=y), 60,
                        valid_sets=[lgb.Dataset(Xv, label=yv)],
                        valid_names=["v"])
        results.append(bst)
    b1, b4 = results
    assert b1.best_iteration == b4.best_iteration
    assert b1.num_trees() == b4.num_trees()
    _assert_identical(b1, b4, scores_exact=False)


def test_pack_boundary_degenerate_stop_is_exact():
    """A constant target grows no tree; the pack path trims the degenerate
    rounds at the pack boundary, storing NO stump trees (the per-round
    deferred check stores up to two — see
    test_degenerate_stop_deferred_exactly_one_extra)."""
    X, _ = _data()
    y = np.zeros(X.shape[0])
    bst = lgb.train({"objective": "regression", "verbosity": -1,
                     "num_leaves": 7, "tpu_iter_pack": 4},
                    lgb.Dataset(X, label=y), 10)
    assert bst.num_trees() == 0
    # predictions are still exact: init score only
    np.testing.assert_allclose(bst.predict(X[:16]), np.zeros(16), atol=1e-7)


def test_pack_degrades_for_host_paths():
    """Configs that need the host every round must degrade to the per-round
    path (with a warning), not crash or silently change semantics."""
    X, y = _data()
    # GOSS packs by default (the tpu_device_goss auto/on in-trace mask);
    # only the host-RNG sampler (off) pins the per-round loop.
    gdev = lgb.train(dict(BASE, tpu_iter_pack=4,
                          data_sample_strategy="goss"),
                     lgb.Dataset(X, label=y), 5)._gbdt
    assert gdev.iter_pack_degrade_reason() is None
    # CEGB packs too: the first-use used vector is device state carried
    # through the scan
    gcegb = lgb.train(dict(BASE, tpu_iter_pack=4, cegb_tradeoff=0.5,
                           cegb_penalty_split=0.02,
                           cegb_penalty_feature_coupled=[2.0] * 8),
                      lgb.Dataset(X, label=y), 5)._gbdt
    assert gcegb.iter_pack_degrade_reason() is None
    assert gcegb.iter_pack_plan(4) == (4, True)
    gbdt = lgb.train(dict(BASE, tpu_iter_pack=4,
                          data_sample_strategy="goss",
                          tpu_device_goss="off"),
                     lgb.Dataset(X, label=y), 5)._gbdt
    assert gbdt.iter_pack_degrade_reason() is not None
    assert gbdt.iter_pack_plan(5) == (1, False)
    # linear trees: host leaf solves
    greg = lgb.train({"objective": "regression", "verbosity": -1,
                      "num_leaves": 7, "linear_tree": True,
                      "tpu_iter_pack": 4},
                     lgb.Dataset(X, label=X[:, 0] * 2.0), 3)._gbdt
    assert greg.iter_pack_degrade_reason() is not None
    # l1 regression renews leaf outputs on the host
    gl1 = lgb.train({"objective": "regression_l1", "verbosity": -1,
                     "num_leaves": 7, "tpu_iter_pack": 4},
                    lgb.Dataset(X, label=X[:, 0]), 3)._gbdt
    assert gl1.iter_pack_degrade_reason() is not None


def test_auto_pack_preserves_host_rng_sampling():
    """Auto mode must not silently swap the host bagging RNG for device
    sampling: with bagging active, auto resolves to the per-round path and
    the model matches the seed's host-RNG behavior."""
    extra = {"bagging_fraction": 0.7, "bagging_freq": 2}
    auto = _train(dict(extra, tpu_iter_pack=0), 0)
    assert auto._gbdt.iter_pack_plan(8) == (1, False)
    # explicit pack (device sampling) is allowed to differ from auto here;
    # it must still be self-consistent (covered by the bitwise test above)


def test_update_pack_booster_api():
    """Booster.update_pack trains K rounds in one dispatch and reports the
    rounds actually kept."""
    X, y = _data()
    bst = lgb.Booster(params=dict(BASE, tpu_iter_pack=6),
                      train_set=lgb.Dataset(X, label=y))
    done, finished = bst.update_pack(6)
    assert (done, finished) == (6, False)
    assert bst.num_trees() == 6
    ref = _train({}, 1, num_round=6)
    _assert_identical(ref, bst)
