"""Fused wave kernel (ISSUE-7 tentpole, ``ops/pallas_wave.py``,
``tpu_wave_kernel``): one pallas_call per wave builds the smaller-sibling
histograms, derives the larger siblings by parent subtraction and runs the
split scan in VMEM.

Bitwise discipline mirrors tests/test_hist_pool.py: with exact-sum inputs
(first-iteration binary gradients +-0.5 / hess 0.25) every histogram value
is exact regardless of accumulation order, the kernel's scan is the SAME
refactored arithmetic (``ops/split.scan_tables``) the unfused path runs,
and the Mosaic-safe one-hot selection replays the unfused argmax's
tie-break exactly — so fused trees pin BITWISE-identical to unfused across
fp32 x quantized x packed4 x pooled (and EFB, where the capability gate
degrades fused to the unfused path).  Quantized histograms are integer,
so those pins are unconditionally exact.  All of this runs the kernel
body in interpret mode on CPU — the tier-1 coverage the gate's
``fused``-forces-interpret semantics exist for."""

import dataclasses

import numpy as np
import pytest

import jax.numpy as jnp

import lightgbm_tpu as lgb
import lightgbm_tpu.models.grower as G
from lightgbm_tpu.config import Config
from lightgbm_tpu.dataset import TrainData
from lightgbm_tpu.models.gbdt import _split_config

_TREE_FIELDS = ("split_feature", "split_bin", "default_left", "is_cat",
                "left_child", "right_child", "split_gain", "leaf_value",
                "leaf_count")


def _assert_same_tree(t0, t1, rl0=None, rl1=None):
    for field in _TREE_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(t0, field)), np.asarray(getattr(t1, field)),
            err_msg=field)
    assert int(t0.num_leaves) == int(t1.num_leaves)
    if rl0 is not None:
        np.testing.assert_array_equal(np.asarray(rl0), np.asarray(rl1))


def _exact_grow_args(td, n, f):
    """Exact-sum fp32 inputs (grads +-0.5, hess 0.25) — histogram sums are
    exactly representable, so accumulation order cannot perturb them."""
    rng = np.random.RandomState(3)
    sign = (rng.rand(n) > 0.5).astype(np.float32)
    meta = td.feature_meta_device()
    return (jnp.asarray(td.binned.bins),
            jnp.asarray(sign - 0.5), jnp.full(n, 0.25, jnp.float32),
            jnp.ones(n, jnp.float32), jnp.ones(f, bool),
            meta["num_bins_per_feature"], meta["nan_bins"],
            meta["is_categorical"], meta["monotone"])


@pytest.fixture(scope="module")
def grown():
    """Shared dataset: > _MIN_BUCKET rows, NaNs for default-direction
    coverage, one low-cardinality int column kept NUMERICAL."""
    n, f = 3 * 2560, 12
    rng = np.random.RandomState(7)
    X = rng.randn(n, f)
    X[rng.rand(n) < 0.05, 3] = np.nan
    X[:, 5] = rng.randint(0, 6, n)
    y = (X[:, 0] + 0.7 * X[:, 1] * X[:, 2] + 0.3 * rng.randn(n) > 0)
    cfg = Config({"objective": "binary", "num_leaves": 31, "verbosity": -1})
    td = TrainData.build(X, y.astype(np.float64), cfg)
    base = G.GrowerConfig(num_leaves=31, num_bins=td.binned.max_num_bins,
                          split=_split_config(cfg, td))
    return _exact_grow_args(td, n, f), base


def _pair(base, args, **kw):
    gu = G.make_grower(dataclasses.replace(base, wave_kernel="unfused",
                                           **kw))
    gf = G.make_grower(dataclasses.replace(base, wave_kernel="fused", **kw))
    assert not gu.plan.fused and gf.plan.fused
    return gu(*args), gf(*args)


@pytest.mark.parametrize("leaf_batch", [1, 4])
def test_fused_bitwise_fp32(grown, leaf_batch):
    """Fused trees == unfused trees bitwise, W=1 (a wave of one — the
    fused grower routes through _grow_wave even at leaf_batch=1) and
    W=4."""
    args, base = grown
    (t0, rl0), (t1, rl1) = _pair(base, args, leaf_batch=leaf_batch)
    _assert_same_tree(t0, t1, rl0, rl1)
    assert int(t0.num_leaves) > 8      # the pin actually grew a tree


def test_fused_bitwise_quantized(grown):
    """int8 wire / int32 accumulation: integer histograms are exact
    unconditionally, and the in-kernel scale-to-f32 mirrors _scale_hist
    elementwise — bitwise without any exact-sum caveat."""
    args, base = grown
    (t0, rl0), (t1, rl1) = _pair(base, args, leaf_batch=4, quantized=True)
    _assert_same_tree(t0, t1, rl0, rl1)


@pytest.mark.parametrize("quantized", [False, True])
def test_fused_bitwise_pooled(grown, quantized):
    """Bounded histogram pool x fused kernel: the kernel writes into
    claimed slots, parents recompute-on-miss through the UNFUSED branch
    and feed the kernel — trees stay bitwise across heavy eviction."""
    args, base = grown
    f = args[0].shape[1]
    slot_mb = f * base.num_bins * 3 * 4 / (1 << 20)
    (t0, rl0), (t1, rl1) = _pair(
        base, args, leaf_batch=4, quantized=quantized,
        histogram_pool_size=10.5 * slot_mb)   # ~10 slots for 31 leaves
    gf = G.make_grower(dataclasses.replace(
        base, wave_kernel="fused", leaf_batch=4,
        histogram_pool_size=10.5 * slot_mb))
    assert gf.plan.pool and gf.pool_slots(f) < base.num_leaves
    _assert_same_tree(t0, t1, rl0, rl1)


def test_fused_bitwise_packed4():
    """4-bit nibble packing: the kernel unpacks planes in VMEM and scans
    in plane order with ORIGINAL-feature-order tie-break keys — bitwise
    vs the unfused packed4 path (odd F exercises the phantom column)."""
    n, f = 3 * 2560, 9
    rng = np.random.RandomState(11)
    X = np.round(rng.randn(n, f) * 2)      # few distinct values -> <=16 bins
    y = (X[:, 0] + X[:, 1] > 0)
    cfg = Config({"objective": "binary", "num_leaves": 31, "max_bin": 15,
                  "verbosity": -1})
    td = TrainData.build(X, y.astype(np.float64), cfg)
    assert td.binned.max_num_bins <= 16
    from lightgbm_tpu.ops.histogram import pack_bins4
    args = list(_exact_grow_args(td, n, f))
    args[0] = pack_bins4(args[0])
    base = G.GrowerConfig(num_leaves=31, num_bins=td.binned.max_num_bins,
                          split=_split_config(cfg, td), packed4=True)
    (t0, rl0), (t1, rl1) = _pair(base, tuple(args), leaf_batch=4)
    _assert_same_tree(t0, t1, rl0, rl1)
    assert int(t0.num_leaves) > 8


def test_fused_bitwise_onehot_categorical():
    """One-hot categorical splits INSIDE the kernel (cat_stats gather,
    bis_cat selection, the cat_mask payload lanes): a low-cardinality
    categorical feature engineered to win splits must produce bitwise
    trees — including the (L, B) cat_mask routing — on the fused path.
    max_cat_to_onehot is raised so no feature is sorted-eligible (the
    sorted scan is the one categorical path the kernel excludes)."""
    n, f = 3 * 2560, 4
    rng = np.random.RandomState(13)
    cat = rng.randint(0, 6, n).astype(np.float64)
    X = np.column_stack([cat, rng.randn(n, f - 1)])
    y = ((cat == 2.0) | (cat == 5.0)) ^ (X[:, 1] > 1.0)
    cfg = Config({"objective": "binary", "num_leaves": 31,
                  "max_cat_to_onehot": 16, "verbosity": -1})
    td = TrainData.build(X, y.astype(np.float64), cfg,
                         categorical_features=[0])
    scfg = _split_config(cfg, td)
    assert scfg.has_categorical and not scfg.use_sorted_categorical
    base = G.GrowerConfig(num_leaves=31, num_bins=td.binned.max_num_bins,
                          split=scfg)
    (t0, rl0), (t1, rl1) = _pair(base, _exact_grow_args(td, n, f),
                                 leaf_batch=4)
    _assert_same_tree(t0, t1, rl0, rl1)
    np.testing.assert_array_equal(np.asarray(t0.cat_mask),
                                  np.asarray(t1.cat_mask))
    assert bool(np.any(np.asarray(t0.is_cat)[
        :int(t0.num_leaves) - 1])), "no categorical split won — dead pin"


def _ragged_case(dtype, packed4):
    """Uneven segments through ``fused_wave_call`` itself: returns what the
    kernel gave and, per slot, what the per-leaf kernel + the unfused scan
    give on the same rows."""
    from lightgbm_tpu.ops.histogram import pack_bins4
    from lightgbm_tpu.ops.pallas_common import C_PAD
    from lightgbm_tpu.ops.pallas_histogram import histogram_flat
    from lightgbm_tpu.ops.pallas_wave import (
        STAT_LANES, fused_wave_call, hist_from_flat, hist_to_flat,
        payload_to_best, plane_order, wave_block_map, wave_block_slots,
        wave_layout, wave_meta)
    from lightgbm_tpu.ops.split import SplitConfig, best_split

    f, nbins = (9, 16) if packed4 else (6, 64)
    lay = wave_layout(f, nbins, dtype, packed4=packed4)
    blk = lay["rows_block"]
    # one slot with almost all rows, a slot of 1 row, an exact multiple of
    # blk, an inactive (empty) slot, a slot one row over a block
    cnts = np.array([5 * blk + 37, 1, 2 * blk, 0, blk + 1], np.int32)
    active = cnts > 0
    w = len(cnts)
    rng = np.random.RandomState(17)
    n = int(cnts.sum()) * 2 + 50
    bins = rng.randint(0, nbins, (n, f)).astype(np.uint8)
    if dtype == "int8":
        vals = np.stack([rng.randint(-100, 100, n), rng.randint(1, 100, n),
                         np.ones(n)], 1).astype(np.int8)
        scale = np.array([0.013, 0.007, 1.0], np.float32)
    else:
        vals = np.stack([rng.randn(n), rng.rand(n) + 0.1, np.ones(n)],
                        1).astype(np.float32)
        scale = None
    sbins = np.asarray(pack_bins4(jnp.asarray(bins))) if packed4 else bins
    hist_kw = dict(num_bins=nbins, dtype=dtype, packed4=packed4, features=f,
                   interpret=True)

    # disjoint leaves: slot j's parent is its rows plus as many again
    order = rng.permutation(n)
    small_rows, parent_rows, at = [], [], 0
    for c in cnts:
        small_rows.append(order[at:at + c])
        parent_rows.append(order[at:at + 2 * c + 3])
        at += 2 * c + 3
    parent = jnp.stack([histogram_flat(jnp.asarray(sbins[r]),
                                       jnp.asarray(vals[r]), **hist_kw)
                        for r in parent_rows])            # (W, F, B, 3)
    small_left = np.array([True, False, True, True, False])

    # the packing under test, from the pure block map
    _, off, nb_total = wave_block_map(jnp.asarray(cnts), blk)
    total = 4 * int(nb_total) * blk      # nb_total well under the step
    slot, k = (np.asarray(a) for a in wave_block_slots(off, total // blk))
    seg = np.full((total // blk, blk), n, np.int64)       # phantom row n
    for b in range(int(nb_total)):
        rows = small_rows[slot[b]][k[b] * blk:(k[b] + 1) * blk]
        seg[b, :len(rows)] = rows
    seg = seg.reshape(-1)
    bins_pad = np.concatenate([sbins, np.zeros((1, sbins.shape[1]),
                                               np.uint8)])
    vals_pad = np.concatenate([vals, np.zeros((1, 3), vals.dtype)])
    gvT = np.pad(vals_pad[seg], ((0, 0), (0, C_PAD - 3))).T

    scfg = SplitConfig(min_data_in_leaf=1, has_nan=False,
                       has_categorical=False, use_sorted_categorical=False,
                       has_monotone=False)
    meta_kw = dict(num_bins_per_feature=jnp.full(f, nbins, jnp.int32),
                   nan_bins=jnp.full(f, nbins, jnp.int32),
                   is_categorical=jnp.zeros(f, bool),
                   feature_mask=jnp.ones(f, bool))

    def scaled(h):
        return h if scale is None else h.astype(jnp.float32) * scale

    # per-leaf: the flat kernel on the slot's rows, XLA subtract, scan
    want = []
    for j in range(w):
        sm = (histogram_flat(jnp.asarray(sbins[small_rows[j]]),
                             jnp.asarray(vals[small_rows[j]]), **hist_kw)
              if cnts[j] else jnp.zeros_like(parent[j]))
        big = parent[j] - sm
        lr = (sm, big) if small_left[j] else (big, sm)
        stats_j, bests = [], []
        for h in lr:
            g, hs, c = (jnp.asarray(v, jnp.float32)
                        for v in np.asarray(scaled(h))[0].sum(axis=0))
            stats_j.append((g, hs, c))
            bests.append(best_split(scaled(h), g, hs, c, monotone=None,
                                    cfg=scfg, parent_output=jnp.float32(0),
                                    **meta_kw))
        want.append((lr, stats_j, bests))

    stats = np.zeros((w, 2, STAT_LANES), np.float32)
    for j in range(w):
        for ci in range(2):
            stats[j, ci, :3] = [float(v) for v in want[j][1][ci]]
        stats[j, :, 4] = small_left[j]
        stats[j, :, 5] = active[j]
    order_p, inv_p = plane_order(f, packed4)
    hist2, payload = fused_wave_call(
        jnp.asarray(bins_pad[seg]), jnp.asarray(gvT),
        hist_to_flat(parent, lay["ftile"], lay["b_pad"], order_p),
        jnp.asarray(stats),
        wave_meta(meta_kw["num_bins_per_feature"], meta_kw["nan_bins"],
                  meta_kw["is_categorical"], meta_kw["feature_mask"],
                  features=f, num_bins=nbins, packed4=packed4),
        jnp.asarray(slot), jnp.asarray(nb_total)[None],
        None if scale is None else jnp.asarray(np.pad(scale, (0, 1))[None]),
        num_bins=nbins, features=f, rows_block=0, dtype=dtype,
        packed4=packed4, scfg=scfg, interpret=True)
    child = hist_from_flat(hist2, f, nbins, lay["b_pad"], inv_p)
    got = payload_to_best(jnp.concatenate([payload[:, 0], payload[:, 1]]))
    return child, got, want, active


@pytest.mark.parametrize("dtype, packed4", [("f32", False), ("int8", False),
                                            ("f32", True)],
                         ids=["f32", "int8", "packed4"])
def test_ragged_kernel_matches_per_leaf(dtype, packed4):
    """The ragged launch against the per-leaf path, slot by slot: (left,
    right) histograms bitwise those of ``histogram_flat`` on the slot's
    rows -/+ the parent (same row blocks from the segment's start, so the
    f32 sums group identically), the payload the unfused ``best_split``'s
    — with one slot holding almost all rows, a slot of one row, an exact
    multiple of the row block, an inactive slot, and ``nb_total`` a
    quarter of the launch's blocks."""
    child, got, want, active = _ragged_case(dtype, packed4)
    w = len(want)
    for j, (lr, _stats, bests) in enumerate(want):
        for ci in range(2):
            np.testing.assert_array_equal(
                np.asarray(child[j, ci]), np.asarray(lr[ci]),
                err_msg=f"slot {j} child {ci}")
            i, ref = ci * w + j, bests[ci]
            if not active[j]:
                assert float(got.gain[i]) == -np.inf
                continue
            assert float(got.gain[i]) == float(ref.gain), (j, ci)
            assert int(got.feature[i]) == int(ref.feature)
            assert int(got.bin[i]) == int(ref.bin)
            assert bool(got.default_left[i]) == bool(ref.default_left)
            for name in ("sum_grad_left", "sum_hess_left", "count_left",
                         "sum_grad_right", "sum_hess_right", "count_right"):
                assert (float(getattr(got, name)[i])
                        == float(getattr(ref, name))), (j, ci, name)
    assert any(float(got.gain[i]) > 0 for i in range(2 * w))


@pytest.mark.parametrize("cnts, blk", [
    ([1500, 1, 512, 0, 257], 256),          # uneven, empty, exact multiple
    ([0, 0, 0, 0], 128),                    # nothing to do: W blocks
    ([4096], 1024),                         # W = 1
    ([300] * 16, 256),                      # equal segments
], ids=["uneven", "empty", "w1", "equal"])
def test_wave_block_map_packs_every_row_once(cnts, blk):
    """The block map alone: a slot's blocks are consecutive, every real
    row of every slot appears exactly once, ``nb_total * blk <= sum + W *
    blk``, and blocks past ``nb_total`` mask to nothing."""
    from lightgbm_tpu.ops.pallas_wave import (wave_block_map,
                                              wave_block_slots)

    cnts = np.asarray(cnts, np.int32)
    w = len(cnts)
    nb, off, nb_total = (np.asarray(a) for a in
                         wave_block_map(jnp.asarray(cnts), blk))
    assert np.all(nb >= 1) and nb_total == nb.sum()
    assert nb_total * blk <= cnts.sum() + w * blk
    nblocks = int(nb_total) + 5
    slot, k = (np.asarray(a) for a in wave_block_slots(jnp.asarray(off),
                                                       nblocks))
    real = slot[:nb_total]
    assert np.all(np.diff(real) >= 0)                 # consecutive slots
    assert np.array_equal(np.unique(real), np.arange(w))   # all visited
    assert np.all(slot[nb_total:] == w - 1)           # index maps stay put
    seen = [np.zeros(c, np.int32) for c in cnts]
    for b in range(nblocks):
        rows = k[b] * blk + np.arange(blk)
        rows = rows[rows < cnts[slot[b]]]             # the gather's mask
        assert b < nb_total or rows.size == 0
        seen[slot[b]][rows] += 1
    assert all(np.all(s == 1) for s in seen)


def test_small_n_reports_fused_inactive():
    """n <= _MIN_BUCKET routes to the mask layout (no wave at all):
    wave_fused_active — and everything the census/bench derive from it —
    must say so instead of reporting a kernel that never runs."""
    rng = np.random.RandomState(1)
    X = rng.randn(1500, 4)
    y = (X[:, 0] > 0).astype(np.float64)
    bst = lgb.train({"objective": "binary", "num_leaves": 15,
                     "tpu_wave_kernel": "fused", "tpu_leaf_batch": 4,
                     "verbosity": -1, "metric": "none"},
                    lgb.Dataset(X, label=y), 2)
    assert bst._gbdt.wave_fused_active is False


def test_fused_degrades_under_efb_and_stays_identical():
    """EFB bundling keeps the unfused wave (bundle-offset expansion is not
    Mosaic-expressible): tpu_wave_kernel=fused must DEGRADE — and then
    trivially match the unfused run byte for byte."""
    n = 4000
    rng = np.random.RandomState(2)
    # mutually exclusive one-hot blocks bundle under EFB
    base_col = rng.randint(0, 4, n)
    X = np.zeros((n, 8))
    for j in range(4):
        X[:, j] = (base_col == j) * rng.rand(n)
    X[:, 4:] = rng.randn(n, 4)
    y = (X[:, 4] + base_col > 1.5).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "metric": "none", "deterministic": True, "tpu_leaf_batch": 4}
    b_f = lgb.train(dict(params, tpu_wave_kernel="fused"),
                    lgb.Dataset(X, label=y), 3)
    b_u = lgb.train(dict(params, tpu_wave_kernel="unfused"),
                    lgb.Dataset(X, label=y), 3)
    assert b_f._gbdt.bundles is not None          # EFB actually engaged
    assert b_f._gbdt.wave_fused_active is False   # ... and fused degraded
    # byte-identical trees; only the echoed parameter block may differ
    tree_f = b_f.model_to_string().split("end of parameters")[1]
    tree_u = b_u.model_to_string().split("end of parameters")[1]
    assert tree_f == tree_u


def test_fused_iter_pack_k1_eq_k4():
    """tpu_wave_kernel=fused composes with iteration packing: K=4 packed
    rounds (the pallas kernel traced inside the lax.scan body) produce the
    byte-identical model of 4 per-round updates."""
    n = 3 * 2560
    rng = np.random.RandomState(5)
    X = rng.randn(n, 8)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "metric": "none", "deterministic": True, "tpu_leaf_batch": 4,
              "tpu_wave_kernel": "fused"}
    b1 = lgb.Booster(params=params, train_set=lgb.Dataset(X, label=y))
    for _ in range(4):
        b1.update()
    b4 = lgb.Booster(params=params, train_set=lgb.Dataset(X, label=y))
    assert b4._gbdt.iter_pack_plan(4)[1], "config must be pack-capable"
    b4.update_pack(4)
    assert b1.model_to_string() == b4.model_to_string()


def test_selection_parity_onehot_vs_argmax(rng):
    """ops/split.select_payload (the Mosaic-safe one-hot selection the
    kernel uses) must pick the SAME winner as _select_from_tables' argmax
    — including on exact gain ties and the all--inf no-split case."""
    from lightgbm_tpu.ops.split import (SplitConfig, _select_from_tables,
                                        scan_tables, select_payload)

    F, B = 5, 16
    cfg = SplitConfig(min_data_in_leaf=1, has_nan=True,
                      has_categorical=False, use_sorted_categorical=False,
                      has_monotone=False)
    hist = np.zeros((F, B, 3), np.float32)
    hist[:, :, 0] = rng.randn(F, B)
    hist[:, :, 1] = rng.rand(F, B) + 0.1
    hist[:, :, 2] = rng.randint(1, 20, (F, B))
    hist[2] = hist[1]                      # exact duplicate -> gain ties
    tot = hist[0].sum(axis=0)
    for variant in ("normal", "no_split"):
        cfgv = cfg if variant == "normal" else dataclasses.replace(
            cfg, min_data_in_leaf=10**6)
        t = scan_tables(
            jnp.asarray(hist[..., 0]), jnp.asarray(hist[..., 1]),
            jnp.asarray(hist[..., 2]), *(jnp.asarray(v) for v in tot),
            num_bins_per_feature=jnp.full(F, B, jnp.int32),
            nan_bins=jnp.full(F, B, jnp.int32),
            is_categorical=jnp.zeros(F, bool),
            feature_mask=jnp.ones(F, bool), cfg=cfgv)
        ref = _select_from_tables(t, jnp.zeros(F, bool), cfgv)
        got = select_payload(t, jnp.zeros(F, bool), cfgv)
        gain, bf, bb, dl, ic, GL, HL, CL, GR, HR, CR = got
        assert float(gain) == float(ref.gain)
        assert int(bf) == int(ref.feature) and int(bb) == int(ref.bin)
        assert bool(dl) == bool(ref.default_left)
        for a, b in ((GL, ref.sum_grad_left), (HL, ref.sum_hess_left),
                     (CL, ref.count_left), (GR, ref.sum_grad_right),
                     (HR, ref.sum_hess_right), (CR, ref.count_right)):
            assert float(a) == float(b)


def test_wave_layout_legal_and_budgeted():
    """Hermetic kernel_layout-style pin for the fused kernel's VMEM plan:
    every BlockSpec-relevant dimension Mosaic-legal (128-multiple lane
    dims, nibble-pair-even feature tiles), the working set — three
    resident histograms + scan scratch + streamed blocks; the one-hot is
    never stored since PR 28 — under budget wherever the layout claims to
    fit, and the shapes that must (bench Higgs) / must not (MS-LTR,
    Epsilon-wide) fuse: the widths admitted are the ones admitted before
    the one-hot left the working set."""
    from lightgbm_tpu.ops.pallas_wave import (WAVE_VMEM_BUDGET,
                                              wave_layout)

    for dtype in ("f32", "bf16", "int8"):
        for nb in (16, 64, 255, 256):
            for f in (1, 28, 137):
                lay = wave_layout(f, nb, dtype)
                assert lay["b_pad"] % 128 == 0 and lay["b_pad"] >= nb
                assert (lay["ftile"] * lay["b_pad"]) % 128 == 0
                assert lay["rows_block"] % 128 == 0
                assert lay["total_bytes"] == (
                    lay["hist_block_bytes"] + lay["scan_scratch_bytes"]
                    + lay["stream_bytes"])
                if lay["fits"]:
                    assert lay["single_chunk"]
                    assert lay["total_bytes"] <= WAVE_VMEM_BUDGET
        lay4 = wave_layout(13, 16, dtype, packed4=True)
        assert lay4["ftile"] % 2 == 0
    # the bench Higgs shape fuses (fp32 AND the quantized int8 wire) ...
    assert wave_layout(28, 256, "f32")["fits"]
    assert wave_layout(28, 256, "int8")["fits"]
    assert wave_layout(28, 255, "f32")["rows_block"] == 256
    # ... Epsilon-wide does not (keeps the unfused + pool + tiled scan),
    # and neither does MS-LTR's 137 columns, though kernel_layout now hands
    # them to one launch and the bytes would fit: the admitted widths end
    # where they ended before PR 28 (63 columns at 255 bins f32)
    assert not wave_layout(2000, 256, "f32")["fits"]
    wide = wave_layout(137, 255, "f32")
    assert wide["ftile"] == 137 and wide["total_bytes"] <= WAVE_VMEM_BUDGET
    assert not wide["single_chunk"] and not wide["fits"]
    assert wave_layout(63, 255, "f32")["fits"]
    assert not wave_layout(64, 255, "f32")["fits"]
    assert wave_layout(240, 255, "int8")["fits"]
    assert not wave_layout(241, 255, "int8")["fits"]


def test_capability_predicate_and_knob():
    """The growth plan's ``fused`` at open shape gates: the composition
    gate — excluded axes degrade, explicit fused forces on CPU, auto
    engages only where the pallas histogram is live."""
    from lightgbm_tpu.models.capabilities import plan_growth
    from lightgbm_tpu.ops.split import SplitConfig

    def fused(c):
        return plan_growth(c, None, rows=None, features=None).fused

    plain = SplitConfig(has_nan=True, has_categorical=False,
                        use_sorted_categorical=False, has_monotone=False)
    base = G.GrowerConfig(num_leaves=15, num_bins=64, split=plain,
                          leaf_batch=4)
    rep = dataclasses.replace
    assert fused(rep(base, wave_kernel="fused"))
    # auto on a CPU backend (resolve_impl -> segment): stays unfused
    assert not fused(rep(base, wave_kernel="auto"))
    # ... but auto with the pallas impl engages
    assert fused(rep(base, wave_kernel="auto", histogram_impl="pallas"))
    assert not fused(rep(base, wave_kernel="unfused"))
    for bad in (
        rep(base, wave_kernel="fused", voting=True),
        rep(base, wave_kernel="fused", bundled=True),
        rep(base, wave_kernel="fused", gather_rows=False),
        rep(base, wave_kernel="fused",
            forced_splits=((0, 1, -1, -1),)),
        rep(base, wave_kernel="fused",
            split=rep(plain, has_monotone=True)),
        rep(base, wave_kernel="fused", split=rep(plain, use_cegb=True)),
        rep(base, wave_kernel="fused",
            split=rep(plain, extra_trees=True)),
        rep(base, wave_kernel="fused", feature_fraction_bynode=0.5),
        rep(base, wave_kernel="fused", interaction_groups=((0, 1),)),
        rep(base, wave_kernel="fused",
            split=rep(plain, feature_contri=(0.5, 1.0))),
        rep(base, wave_kernel="fused",
            split=rep(plain, has_categorical=True,
                      use_sorted_categorical=True)),
    ):
        assert not fused(bad), bad
    with pytest.raises(ValueError, match="wave_kernel"):
        fused(rep(base, wave_kernel="bogus"))
    with pytest.raises(ValueError, match="tpu_wave_kernel"):
        lgb.train({"objective": "binary", "tpu_wave_kernel": "bogus",
                   "verbosity": -1},
                  lgb.Dataset(np.random.rand(100, 3),
                              label=np.zeros(100)), 1)


def test_explicit_fused_downgrades_through_matrix(capsys):
    """The capability matrix owns the composition downgrades: an explicit
    fused request against monotone constraints warns and keeps the
    unfused wave (same message discipline as every other rule)."""
    rng = np.random.RandomState(0)
    X = rng.rand(1500, 4)
    y = 2 * X[:, 0] + 0.1 * rng.randn(1500)
    bst = lgb.train({"objective": "regression", "num_leaves": 15,
                     "monotone_constraints": [1, 0, 0, 0],
                     "tpu_wave_kernel": "fused", "tpu_leaf_batch": 4,
                     "verbosity": 1},
                    lgb.Dataset(X, label=y), 2)
    out = capsys.readouterr()
    assert "tpu_wave_kernel=fused" in out.out + out.err
    assert bst._gbdt.wave_fused_active is False
    assert bst._gbdt.grower_cfg.wave_kernel == "unfused"
