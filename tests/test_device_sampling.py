"""ISSUE-5 device-resident sampling/penalty paths.

Device GOSS (``tpu_device_goss``): the in-trace mask's top set must match
the host sampler's bit-for-bit under distinct scores and carry the exact
``(N - top_k) / other_k`` amplification; the random rest-sample is a
different (seed-keyed device) stream than the host ``np.random`` one, so
end-to-end quality is pinned by AUC parity, not bitwise equality.

Fused CEGB: deterministic, so routing it through the one-dispatch fused
iteration must leave trees BITWISE identical to the per-tree
``_grow_apply`` fallback (fp32 x quantized x EFB).

Linear trees: the batched device solve must match the reference-style
host f64 solve (``LIGHTGBM_TPU_HOST_LINEAR=1`` facade) to float tolerance.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.sampling import SampleStrategy, goss_mask_device


def _data(n=3000, f=8, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.6 * X[:, 1] * X[:, 2] + 0.3 * rng.randn(n) > 0)
    return X, y.astype(np.float64)


def _auc(y, s):
    order = np.argsort(s)
    ranks = np.empty(len(s))
    ranks[order] = np.arange(1, len(s) + 1)
    pos = y == 1
    npos, nneg = pos.sum(), (~pos).sum()
    return (ranks[pos].sum() - npos * (npos + 1) / 2) / (npos * nneg)


def _unfuse(bst):
    """Force the per-round non-fused branch (the pre-ISSUE-5 path shape):
    gradients in their own dispatch, per-tree _grow_apply."""
    bst._gbdt._fused_iter = None
    return bst


class TestDeviceGoss:
    def test_top_set_matches_host_under_distinct_scores(self):
        rng = np.random.RandomState(3)
        n = 5000
        grad = rng.randn(n).astype(np.float32)
        hess = (0.1 + rng.rand(n)).astype(np.float32)
        cfg = Config({"data_sample_strategy": "goss",
                      "top_rate": 0.2, "other_rate": 0.1,
                      "verbosity": -1})
        strat = SampleStrategy(cfg, n)
        top_k, other_k, amp = strat.goss_constants()
        # GOSS leaves the first int(1 / learning_rate) iterations unsampled
        assert strat.mask(strat.goss_unsampled_iters - 1, grad, hess) is None
        host = strat.mask(strat.goss_unsampled_iters, grad, hess)
        dev = np.asarray(goss_mask_device(
            jnp.asarray(grad), jnp.asarray(hess), jax.random.PRNGKey(9),
            top_k, other_k, amp))
        # the deterministic top set (mask == 1.0) is identical
        np.testing.assert_array_equal(host == 1.0, dev == 1.0)
        assert int((dev == 1.0).sum()) == top_k
        # rest-sample: exact count, exact amplification weight, disjoint
        # from the top set
        amp32 = np.float32(amp)
        assert int((dev == amp32).sum()) == other_k
        assert not np.any((dev == amp32) & (host == 1.0))
        assert set(np.unique(dev)) <= {np.float32(0.0), np.float32(1.0),
                                       amp32}
        # host path carries the same amplification value
        assert int((host == amp32).sum()) == other_k

    def test_fused_goss_identical_to_standalone_device_mask(self):
        """auto (in-trace mask inside the fused dispatch) and the
        non-fused standalone-mask branch (tpu_device_goss=on with the
        fused program disabled) share one key stream and must produce
        bitwise-identical trees."""
        X, y = _data()
        # learning_rate 0.5: GOSS samples from iteration int(1 / 0.5) = 2
        params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
                  "data_sample_strategy": "goss", "metric": "none",
                  "learning_rate": 0.5}
        fused = lgb.Booster(params=params, train_set=lgb.Dataset(X, label=y))
        standalone = _unfuse(lgb.Booster(
            params=dict(params, tpu_device_goss="on"),
            train_set=lgb.Dataset(X, label=y)))
        for _ in range(6):
            fused.update()
            standalone.update()
        assert fused._gbdt.fused_path_active is True
        assert standalone._gbdt.fused_path_active is False
        assert fused._gbdt.last_sample() is not None
        for a, b in zip(fused._gbdt.last_sample(),
                        standalone._gbdt.last_sample()):
            np.testing.assert_array_equal(a, b)
        for tf, ts in zip(fused._gbdt.models[0], standalone._gbdt.models[0]):
            assert tf.num_leaves == ts.num_leaves
            k = max(tf.num_leaves - 1, 0)
            np.testing.assert_array_equal(tf.split_feature[:k],
                                          ts.split_feature[:k])
            np.testing.assert_array_equal(tf.leaf_value, ts.leaf_value)

    def test_device_vs_host_goss_auc_parity(self):
        """The device rest-sample is a different RNG stream than the host
        np.random one — statistically equivalent: both land the same
        quality on a held-out split."""
        X, y = _data(n=6000, seed=1)
        nt = 4500
        aucs = {}
        for name, dg in (("device", "auto"), ("host", "off")):
            bst = lgb.train({"objective": "binary", "num_leaves": 31,
                             "verbosity": -1, "metric": "none",
                             "data_sample_strategy": "goss",
                             "tpu_device_goss": dg},
                            lgb.Dataset(X[:nt], label=y[:nt]), 30)
            aucs[name] = _auc(y[nt:], bst.predict(X[nt:], raw_score=True))
        assert aucs["device"] > 0.85 and aucs["host"] > 0.85, aucs
        assert abs(aucs["device"] - aucs["host"]) < 0.02, aucs

    def test_bad_knob_value_rejected(self):
        X, y = _data(n=400)
        with pytest.raises(ValueError, match="tpu_device_goss"):
            lgb.train({"objective": "binary", "verbosity": -1,
                       "data_sample_strategy": "goss",
                       "tpu_device_goss": "maybe"},
                      lgb.Dataset(X, label=y), 1)


CEGB = {"cegb_tradeoff": 0.5, "cegb_penalty_split": 0.02,
        "cegb_penalty_feature_coupled": [2.0] * 8,
        "cegb_penalty_feature_lazy": [0.5] * 8}


class TestFusedCegb:
    @pytest.mark.parametrize("extra", [
        {},
        {"use_quantized_grad": True},
        {"enable_bundle": True},
    ], ids=["fp32", "quantized", "efb"])
    def test_fused_bitwise_identical_to_nonfused(self, extra):
        """CEGB is deterministic: carrying the first-use ``used`` vector
        in-trace (fused one-dispatch path) must not move a single split
        vs the per-tree fallback."""
        X, y = _data()
        if extra.get("enable_bundle"):
            # sparsify some columns so EFB actually bundles
            X = X.copy()
            X[:, 5][X[:, 5] < 1.0] = 0.0
            X[:, 6][X[:, 6] > -1.0] = 0.0
        params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
                  "metric": "none", **CEGB, **extra}
        fused = lgb.Booster(params=params, train_set=lgb.Dataset(X, label=y))
        plain = _unfuse(lgb.Booster(params=params,
                                    train_set=lgb.Dataset(X, label=y)))
        for _ in range(8):
            fused.update()
            plain.update()
        assert fused._gbdt.fused_path_active is True
        for tf, tp in zip(fused._gbdt.models[0], plain._gbdt.models[0]):
            assert tf.num_leaves == tp.num_leaves
            k = max(tf.num_leaves - 1, 0)
            np.testing.assert_array_equal(tf.split_feature[:k],
                                          tp.split_feature[:k])
            np.testing.assert_array_equal(tf.split_bin[:k], tp.split_bin[:k])
            np.testing.assert_array_equal(tf.leaf_value, tp.leaf_value)
        # the penalty actually bit: coupled first-use marks accumulated
        assert bool(np.asarray(
            jax.device_get(fused._gbdt._cegb_used_dev)).any())

    def test_discard_rounds_rolls_back_used_vector(self):
        """A discarded pack tail must not leak first-use marks: the
        resident used vector only advances through committed rounds."""
        X, y = _data(n=1200)
        params = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
                  "metric": "none", "tpu_iter_pack": 4, **CEGB}
        bst = lgb.Booster(params=params, train_set=lgb.Dataset(X, label=y))
        g = bst._gbdt
        rounds, _fin = g.train_pack(4)
        used_before = np.asarray(jax.device_get(g._cegb_used_dev))
        assert not used_before.any()        # fresh booster: nothing marked
        g.commit_round(rounds[0])
        used_commit1 = np.asarray(jax.device_get(g._cegb_used_dev))
        # the committed snapshot is EXACTLY round 0's live split features
        expect = np.zeros_like(used_before)
        for arrays in rounds[0]:
            sf, nl = jax.device_get((arrays.split_feature,
                                     arrays.num_leaves))
            expect[np.asarray(sf)[: max(int(nl) - 1, 0)]] = True
        np.testing.assert_array_equal(used_commit1, expect)
        assert expect.any()                 # the penalty actually bit
        g.discard_rounds(rounds[1:])
        used_after = np.asarray(jax.device_get(g._cegb_used_dev))
        # discarding the tail advances nothing further
        np.testing.assert_array_equal(used_commit1, used_after)


class TestDeviceLinearSolve:
    def test_device_solve_matches_host_facade(self, monkeypatch):
        rng = np.random.RandomState(5)
        X = rng.randn(2500, 6)
        X[::17, 3] = np.nan            # NaN rows fall back per leaf
        y = 2.0 * X[:, 0] - 1.5 * X[:, 1] + 0.05 * rng.randn(2500)
        params = {"objective": "regression", "num_leaves": 15,
                  "verbosity": -1, "linear_tree": True,
                  "linear_lambda": 0.1, "metric": "none"}
        preds = {}
        for name, env in (("device", "0"), ("host", "1")):
            monkeypatch.setenv("LIGHTGBM_TPU_HOST_LINEAR", env)
            bst = lgb.train(params, lgb.Dataset(X, label=y), 8)
            preds[name] = bst.predict(X)
        np.testing.assert_allclose(preds["device"], preds["host"],
                                   rtol=2e-3, atol=2e-3)
