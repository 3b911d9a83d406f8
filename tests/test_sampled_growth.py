"""Growth over the sampled rows (PR 33): with the device GOSS selection on
the single-device wave body a tree is grown over the in-bag row ids alone
(``plan.sampling == "subset"``) — never over a mask of all rows — and every
row still reaches its leaf.

Pins: the program's samples against the benchmark's plain reference
(``benchmark/compare_sampled.py``: sizes, top set, amplification, the
unsampled first ``int(1 / learning_rate)`` iterations); subset growth grows
the trees the mask path grows when handed the SAME in-bag set; every row's
leaf from the dense per-wave routing equals the finished tree's walk,
out-of-bag rows included; the plan names the sampling form and the
sentence behind a refusal; the packed scan picks the program by iteration.
"""

import os
import sys

import jax
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.models.capabilities import plan_growth
from lightgbm_tpu.models.grower import GrowerConfig
from lightgbm_tpu.sampling import goss_sample_device
from lightgbm_tpu.telemetry import registry

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark import compare_sampled, objectives  # noqa: E402

N, F = 6000, 8
GOSS = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
        "metric": "none", "data_sample_strategy": "goss",
        "top_rate": 0.2, "other_rate": 0.1, "learning_rate": 0.25,
        "min_data_in_leaf": 5, "tpu_leaf_batch": 4,
        # few bins: none is empty of in-bag rows, so no two thresholds tie
        "max_bin": 31}


def _data(n=N, f=F, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.6 * X[:, 1] * X[:, 2] + 0.3 * rng.randn(n) > 0)
    return X, y.astype(np.float64)


def _booster(extra=None, n=N):
    X, y = _data(n)
    return X, y, lgb.Booster(params=dict(GOSS, **(extra or {})),
                             train_set=lgb.Dataset(X, label=y))


# ------------------------------------------------- the sample, by the reference
def test_program_samples_against_the_plain_reference():
    X, y, bst = _booster()
    g = bst._gbdt
    assert g.plan.sampling == "subset", str(g.plan)
    sizes = compare_sampled.goss_sizes(N, GOSS)
    unsampled, top_k, other_k, amplify = sizes
    assert (unsampled, top_k, other_k, float(amplify)) == (4, 1200, 600, 8.0)
    assert g.sample_strategy.goss_constants() == (top_k, other_k, 8.0)
    obj = objectives.load(GOSS, y)
    before = registry().counter("sample.unsampled_iters").value
    earlier = None
    for it in range(unsampled + 3):
        score = np.asarray(g.scores, np.float64)
        grad, hess = obj.gradients(score)
        bst.update()
        sample = g.last_sample()
        got = compare_sampled.sample_readings(
            sample, N, it >= unsampled, sizes, np.abs(grad * hess), 1e-3,
            earlier)
        assert got["sample_size_gap"] == 0, (it, got)
        if it < unsampled:
            assert sample is None
            continue
        rows, w = sample
        assert np.all(np.diff(rows) > 0) and len(rows) == top_k + other_k
        assert got["sample_top_gap"] == 0 and got["sample_draw_gap"] < 5, got
        # a dump's leaf counts are in-bag counts
        tree = bst.dump_model()["tree_info"][it]
        assert compare_sampled.ref.flatten_tree(tree)["leaf_count"].sum() \
            == top_k + other_k
        earlier = rows[w != 1]
    assert registry().counter("sample.unsampled_iters").value - before \
        == unsampled
    snap = registry().snapshot()["gauges"]
    assert (snap["sample.top_k"], snap["sample.other_k"],
            snap["sample.in_bag_rows"], snap["sample.amplify"]) == \
        (top_k, other_k, top_k + other_k, 8.0)


def test_unsampled_first_iterations_are_plain_gbdt():
    """GOSS leaves the first int(1 / learning_rate) iterations unsampled:
    they are the trees of the same Booster without GOSS, bit for bit."""
    X, y, goss = _booster()
    plain = lgb.Booster(params={k: v for k, v in GOSS.items() if k not in (
        "data_sample_strategy", "top_rate", "other_rate")},
        train_set=lgb.Dataset(X, label=y))
    for _ in range(5):
        goss.update()
        plain.update()
    tg, tp = goss._gbdt.models[0], plain._gbdt.models[0]
    for k in range(4):
        np.testing.assert_array_equal(tg[k].leaf_value, tp[k].leaf_value)
        np.testing.assert_array_equal(tg[k].leaf_count, tp[k].leaf_count)
    assert tg[4].leaf_count.sum() == 1800 and tp[4].leaf_count.sum() == N


# ------------------------------------- subset growth against the mask path
@pytest.mark.parametrize("extra", [
    {},
    {"tpu_leaf_batch": 1},
    {"tpu_leaf_batch": 16, "num_leaves": 31},
    {"use_quantized_grad": True},
    {"histogram_pool_size": 0.01},
    {"tpu_wave_kernel": "fused", "tpu_histogram_impl": "pallas"},
], ids=["wave4", "wave1", "wave16", "quantized", "pool", "fused_kernel"])
def test_subset_grows_the_mask_paths_trees(extra):
    """Handed the SAME in-bag set, growth over the subset and growth over
    all rows under the mask grow the same tree: structure and every row's
    leaf equal, sums to float32 (a leaf's rows are summed in other blocks)."""
    _, _, bst = _booster(extra)
    g = bst._gbdt
    assert g.plan.sampling == "subset" and g.plan.body == "wave", str(g.plan)
    if "tpu_wave_kernel" in extra:
        assert g.plan.fused, str(g.plan)
    for _ in range(2):
        bst.update()
    grad, hess = g._grad_fn(g.scores)
    top_k, other_k, amp = g.sample_strategy.goss_constants()
    mask, rows = goss_sample_device(grad, hess, jax.random.PRNGKey(1), top_k,
                                    other_k, amp)
    key = jax.random.PRNGKey(2) if extra.get("use_quantized_grad") else None
    sub, leaf_sub = g._raw_grow(grad, hess, mask, g._tree_fmask(), key, None,
                                rows)
    msk, leaf_msk = g._raw_grow(grad, hess, mask, g._tree_fmask(), key, None)
    assert int(sub.num_leaves) == int(msk.num_leaves) > 4
    for name in ("split_feature", "split_bin", "left_child", "right_child",
                 "leaf_count", "internal_count"):
        np.testing.assert_array_equal(np.asarray(getattr(sub, name)),
                                      np.asarray(getattr(msk, name)), name)
    np.testing.assert_array_equal(np.asarray(leaf_sub), np.asarray(leaf_msk))
    assert float(np.asarray(sub.leaf_count).sum()) == top_k + other_k
    for name in ("leaf_value", "leaf_weight", "split_gain"):
        np.testing.assert_allclose(np.asarray(getattr(sub, name)),
                                   np.asarray(getattr(msk, name)),
                                   rtol=1e-4, atol=1e-5, err_msg=name)


# ------------------------------------------- every row's leaf, by the walk
@pytest.mark.parametrize("extra", [{}, {"tpu_leaf_batch": 16},
                                   {"objective": "regression_l1"}],
                         ids=["fused_iter", "wave16", "renew_objective"])
def test_every_rows_leaf_is_the_trees_walk(extra, monkeypatch):
    """The dense per-wave routing gives EVERY row — the four in five a
    sampled tree was not grown on among them — the leaf the finished tree's
    walk gives it, and the score update reaches every row."""
    X, y, bst = _booster(dict({"tpu_device_goss": "on"}, **extra))
    g = bst._gbdt
    assert g.plan.sampling == "subset", str(g.plan)
    handed = []
    store = g._store_tree
    monkeypatch.setattr(g, "_store_tree", lambda k, arrays, row_leaf: (
        handed.append(np.asarray(row_leaf)), store(k, arrays, row_leaf))[1])
    for _ in range(7):
        bst.update()
    walked = bst.predict(X, pred_leaf=True)
    assert walked.shape == (N, 7)
    for k, row_leaf in enumerate(handed):
        np.testing.assert_array_equal(row_leaf, walked[:, k], str(k))
    sample = g.last_sample()
    assert sample is not None and len(sample[0]) == 1800
    np.testing.assert_allclose(np.asarray(g.scores),
                               bst.predict(X, raw_score=True),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------------ the plan
def test_plan_names_the_sampling_form_and_the_refusal():
    _, _, dev = _booster()
    assert dev._gbdt.plan.sampling == "subset"
    assert "sampling=subset" in str(dev._gbdt.plan)
    assert "subset" not in dev._gbdt.plan.why
    _, _, host = _booster({"tpu_device_goss": "off"})
    assert host._gbdt.plan.sampling == "mask"
    assert "tpu_device_goss=off" in host._gbdt.plan.why["subset"]
    assert "sampling=mask; " in str(host._gbdt.plan) + "; "
    _, _, small = _booster(n=1500)
    assert small._gbdt.plan.body == "mask"
    assert small._gbdt.plan.sampling == "mask"
    assert "permutation layout" in small._gbdt.plan.why["subset"]
    _, _, bag = _booster({"data_sample_strategy": "bagging",
                          "bagging_fraction": 0.5, "bagging_freq": 1})
    assert bag._gbdt.plan.sampling == "mask"
    assert "bagging" in bag._gbdt.plan.why["subset"]
    X, y = _data()
    plain = lgb.Booster(params={"objective": "binary", "verbosity": -1},
                        train_set=lgb.Dataset(X, label=y))._gbdt.plan
    assert plain.sampling == "none" and "sampling" not in str(plain)


@pytest.mark.parametrize("learner", ["data", "feature"])
def test_plan_keeps_the_mask_on_a_mesh(learner):
    _, _, bst = _booster({"tree_learner": learner})
    plan = bst._gbdt.plan
    assert plan.layout != "single", str(plan)
    assert plan.sampling == "mask" and "device mesh" in plan.why["subset"]
    for _ in range(6):                      # and it trains, sampled, there
        bst.update()
    assert bst._gbdt.last_sample() is not None


def test_plan_growth_answers_for_a_tpu_from_the_cpu():
    cfg = GrowerConfig(num_leaves=255, num_bins=256, leaf_batch=16,
                       sampling="goss_device")
    plan = plan_growth(cfg, None, rows=2_270_000, features=137,
                       platform="tpu")
    assert (plan.body, plan.layout, plan.sampling) == \
        ("wave", "single", "subset")
    import dataclasses
    plain = plan_growth(dataclasses.replace(cfg, sampling="none"), None,
                        rows=2_270_000, features=137, platform="tpu")
    assert plain.sampling == "none"
    assert dataclasses.replace(plan, sampling="none", why=plain.why) == plain


# ------------------------------------------------------------ the packed scan
def test_packed_scan_picks_the_program_by_iteration():
    """``tpu_iter_pack``: the scanned body holds the plain program for the
    unsampled iterations and the sampled one for the rest; the model is
    the per-round model."""
    X, y = _data()
    boosters = []
    for pack in (1, 3):
        boosters.append(lgb.train(
            dict(GOSS, learning_rate=0.5, tpu_iter_pack=pack),
            lgb.Dataset(X, label=y), 5))
    one, packed = boosters
    assert packed._gbdt.plan.sampling == "subset"
    assert one.num_trees() == packed.num_trees() == 5
    for t1, t3 in zip(one._gbdt.models[0], packed._gbdt.models[0]):
        np.testing.assert_array_equal(t1.split_feature, t3.split_feature)
        np.testing.assert_array_equal(t1.leaf_count, t3.leaf_count)
        np.testing.assert_allclose(t1.leaf_value, t3.leaf_value, rtol=1e-5,
                                   atol=1e-7)
    assert one._gbdt.models[0][1].leaf_count.sum() == N      # unsampled
    assert one._gbdt.models[0][2].leaf_count.sum() == 1800   # sampled
    with pytest.raises(RuntimeError, match="packed"):
        packed._gbdt.last_sample()
