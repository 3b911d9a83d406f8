"""Bounded histogram pool (ISSUE-4 tentpole; reference ``HistogramPool``,
``serial_tree_learner.h``: LRU slots + recompute-on-miss driven by
``histogram_pool_size`` MB).

Bitwise discipline mirrors docs/PERF.md: pool slots hold exactly the values
the unpooled (L, G, B, 3) carry held, sibling subtraction lands in the
parent's slot, and a miss recomputes the leaf's histogram from its
contiguous perm segment in creation-time row order — exact under quantized
training (integer histograms are order-independent) and under fp32 whenever
the gradient sums are exactly representable (these tests use the
first-iteration binary gradients +-0.5 / hess 0.25, like the parallel
parity suite) — so pooled trees pin BITWISE-identical to the unpooled path
across serial/wave/sharded layouts x fp32/quantized x EFB x packed4 x
``tpu_hist_comm=reduce_scatter``.
"""

import dataclasses
import io
import json
import os
import tempfile

import numpy as np
import pytest

import jax.numpy as jnp

import lightgbm_tpu as lgb
import lightgbm_tpu.models.grower as G
from lightgbm_tpu.config import Config
from lightgbm_tpu.dataset import TrainData
from lightgbm_tpu.models.gbdt import _split_config
from lightgbm_tpu.parallel.mesh import DATA_AXIS, make_mesh

_TREE_FIELDS = ("split_feature", "split_bin", "default_left", "left_child",
                "right_child", "split_gain", "leaf_value", "leaf_count")


def _assert_same_tree(t0, t1, rl0=None, rl1=None):
    for field in _TREE_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(t0, field)), np.asarray(getattr(t1, field)),
            err_msg=field)
    assert int(t0.num_leaves) == int(t1.num_leaves)
    if rl0 is not None:
        np.testing.assert_array_equal(np.asarray(rl0), np.asarray(rl1))


@pytest.fixture(scope="module")
def grow_args():
    """Exact-sum fp32 inputs (grads +-0.5, hess 0.25) at > _MIN_BUCKET rows
    per 4-way shard, with NaNs for default-direction coverage."""
    n, f = 4 * 2560, 12
    rng = np.random.RandomState(7)
    X = rng.randn(n, f)
    X[rng.rand(n) < 0.05, 3] = np.nan
    y = (X[:, 0] + 0.7 * X[:, 1] * X[:, 2] + 0.3 * rng.randn(n) > 0)
    cfg = Config({"objective": "binary", "num_leaves": 31, "verbosity": -1})
    td = TrainData.build(X, y.astype(np.float64), cfg)
    meta = td.feature_meta_device()
    args = (jnp.asarray(td.binned.bins),
            jnp.asarray((0.5 - y).astype(np.float32)),
            jnp.full(n, 0.25, jnp.float32), jnp.ones(n, jnp.float32),
            jnp.ones(f, bool), meta["num_bins_per_feature"],
            meta["nan_bins"], meta["is_categorical"], meta["monotone"])
    base = G.GrowerConfig(num_leaves=31, num_bins=td.binned.max_num_bins,
                          split=_split_config(cfg))
    slot_mb = f * td.binned.max_num_bins * 3 * 4 / (1 << 20)
    return args, base, slot_mb


@pytest.mark.parametrize("leaf_batch,slots", [(1, 5), (4, 9)])
def test_pool_bitwise_serial_and_wave(grow_args, leaf_batch, slots):
    """Perm (W=1) and wave (W=4) layouts under a pool far smaller than the
    leaf count (heavy LRU eviction + recompute-on-miss) grow BITWISE the
    same trees and row partitions as the unpooled carry."""
    args, base, slot_mb = grow_args
    base = dataclasses.replace(base, leaf_batch=leaf_batch)
    g0 = G.make_grower(base)
    g1 = G.make_grower(dataclasses.replace(
        base, histogram_pool_size=slots * slot_mb))
    assert not g0.plan.pool and g1.plan.pool
    assert g1.pool_slots(12) < base.num_leaves
    t0, rl0 = g0(*args)
    t1, rl1 = g1(*args)
    assert int(t1.num_leaves) == base.num_leaves
    _assert_same_tree(t0, t1, rl0, rl1)


@pytest.mark.parametrize("quantized", [False, True])
def test_pool_bitwise_sharded_reduce_scatter(grow_args, quantized):
    """Data-parallel sharded-perm wave growth with the feature-sliced
    reduce-scatter: pool slots then hold only the owned ceil(G/K) feature
    block (the wins multiply), misses re-reduce through the identical
    scatter, and trees stay bitwise-identical to the unpooled rs path —
    fp32 and quantized (int16 wire + int32 fallback intact)."""
    args, base, slot_mb = grow_args
    base = dataclasses.replace(base, leaf_batch=4, quantized=quantized,
                               hist_comm="reduce_scatter")
    mesh = make_mesh(4, 1)
    g0 = G.make_grower(base, mesh=mesh, data_axis=DATA_AXIS)
    g1 = G.make_grower(
        dataclasses.replace(base, histogram_pool_size=10 * slot_mb),
        mesh=mesh, data_axis=DATA_AXIS)
    assert (g0.plan.reduce == g1.plan.reduce == "scatter"
            and g1.plan.pool)
    t0, rl0 = g0(*args)
    t1, rl1 = g1(*args)
    _assert_same_tree(t0, t1, rl0, rl1)


def _xy(n=6000, f=10, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(float)
    return X, y


def test_pool_bitwise_booster_packed4_and_efb_quantized():
    """Full Booster path over several boosting rounds with a TINY pool
    (guaranteed evictions + misses) under quantized training — integer
    histograms make the recompute unconditionally exact — composed with
    4-bit packed bins and with EFB bundling."""
    X, y = _xy()
    base = {"objective": "binary", "num_leaves": 31, "verbosity": -1,
            "use_quantized_grad": True}
    # packed4 (max_bin <= 15 auto-packs)
    p4 = dict(base, max_bin=15)
    b0 = lgb.train(p4, lgb.Dataset(X, label=y), 3)
    b1 = lgb.train(dict(p4, histogram_pool_size=0.005),
                   lgb.Dataset(X, label=y), 3)
    assert b0._gbdt.grower_cfg.packed4
    assert b1._gbdt.plan.pool
    np.testing.assert_array_equal(b0.predict(X, raw_score=True),
                                  b1.predict(X, raw_score=True))
    # EFB
    from tests.test_efb import _onehot_data
    Xe, ye = _onehot_data(n=6000)
    e0 = lgb.train(dict(base, enable_bundle=True),
                   lgb.Dataset(Xe, label=ye), 3)
    e1 = lgb.train(dict(base, enable_bundle=True, histogram_pool_size=0.02),
                   lgb.Dataset(Xe, label=ye), 3)
    assert e0._gbdt.bundles is not None and e1._gbdt.plan.pool
    np.testing.assert_array_equal(e0.predict(Xe, raw_score=True),
                                  e1.predict(Xe, raw_score=True))


def test_pool_forced_splits_recompute_on_miss():
    """Forced splits read an arbitrary (possibly long-evicted) leaf's
    histogram at split time — the reference's recompute-on-miss case.  A
    3-node forced tree under a near-minimal pool must reproduce the
    unpooled model exactly (quantized => integer-exact recompute)."""
    X, y = _xy()
    spec = {"feature": 0, "threshold": 0.0,
            "left": {"feature": 1, "threshold": 0.0},
            "right": {"feature": 2, "threshold": 0.0}}
    fd, path = tempfile.mkstemp(suffix=".json")
    with os.fdopen(fd, "w") as fh:
        json.dump(spec, fh)
    try:
        p = {"objective": "binary", "num_leaves": 31, "verbosity": -1,
             "use_quantized_grad": True, "forcedsplits_filename": path}
        f0 = lgb.train(p, lgb.Dataset(X, label=y), 3)
        f1 = lgb.train(dict(p, histogram_pool_size=0.004),
                       lgb.Dataset(X, label=y), 3)
        assert f1._gbdt.plan.pool
        np.testing.assert_array_equal(f0.predict(X, raw_score=True),
                                      f1.predict(X, raw_score=True))
    finally:
        os.unlink(path)


def test_pool_slots_clamp_and_predicate():
    """MB -> slot arithmetic and the composition predicate: the frontier
    floor (2W+1) and the L cap clamp the user knob; -1 and the excluded
    compositions (mask layout, voting, monotone refresh) keep the full
    carry; the growth plan's ``pool`` is the ONE shared gate."""
    from lightgbm_tpu.models.capabilities import plan_growth

    def pool(c):
        return plan_growth(c, None, rows=None, features=None).pool
    split = G.SplitConfig()
    base = G.GrowerConfig(num_leaves=255, num_bins=256, split=split,
                          leaf_batch=16, histogram_pool_size=1.0)
    g = G.make_grower(base)
    # 1 MB / (28*256*3*4 B/slot) = 12 slots, below the 2*16+1 frontier floor
    assert g.pool_slots(28) == 2 * 16 + 1
    big = G.make_grower(dataclasses.replace(base,
                                            histogram_pool_size=1e6))
    assert big.pool_slots(28) == 255          # cap at L == unpooled carry
    off = G.make_grower(dataclasses.replace(base,
                                            histogram_pool_size=-1.0))
    assert not off.plan.pool
    # excluded compositions keep full residency
    assert not pool(dataclasses.replace(base, gather_rows=False))
    assert not pool(dataclasses.replace(base, voting=True))
    assert not pool(dataclasses.replace(
        base, mono_intermediate=True,
        split=dataclasses.replace(split, has_monotone=True)))
    assert pool(base)


def test_pool_knob_warns_only_when_inert(capsys):
    """histogram_pool_size is a REAL knob now: accepting it must not emit
    the dead-param warning; requesting it on a full-residency composition
    (intermediate monotone) warns once, naming the fallback (repo rule:
    no silent dead params)."""
    X, y = _xy(n=3000)
    lgb.train({"objective": "binary", "num_leaves": 15, "verbosity": 1,
               "histogram_pool_size": 2.0}, lgb.Dataset(X, label=y), 2)
    out = capsys.readouterr()
    txt = out.out + out.err
    assert "histogram_pool_size" not in txt, txt
    lgb.train({"objective": "binary", "num_leaves": 15, "verbosity": 1,
               "histogram_pool_size": 2.0,
               "monotone_constraints": [1] + [0] * 9,
               "monotone_constraints_method": "intermediate"},
              lgb.Dataset(X, label=y), 2)
    out = capsys.readouterr()
    assert "histogram_pool_size is ignored" in out.out + out.err
