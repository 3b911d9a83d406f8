"""The wide dense shape (ISSUE-31: Epsilon, 400 000 x 2 000 at 255 bins) at
CPU sizes: what a histogram wider than one kernel launch and a scan wider
than one block select, and that the models they grow are the narrow path's.

Past 256 columns at 255 bins ``histogram_flat`` takes several launches
(balanced column chunks, ``kernel_layout``) and ``ops/split.best_split``
scans 128-column blocks under ``lax.map``.  On the CPU ``auto`` picks the
``segment`` histogram and the chunk loop never runs, so the models here ask
for ``tpu_histogram_impl="pallas"`` (the kernel body interpreted, as
tests/test_phase_scopes.py does) at 260 columns: 2 chunks of 130, 3 scan
blocks.  The references are the mask body (``_grow_mask``: one leaf at a
time over every row) for a wave of one, and the same wave on the narrow
path's code (``segment`` histograms, one untiled scan) for a wave of 16.
On quantised gradients the sums are integers and the models must be the
same bytes; on float32 (read in the wave of 16, the benchmark cell's own
setting) the chunked kernel adds in another order than ``segment``, so the
splits must be the same and the leaf values agree to float32 rounding of
sums of a few thousand terms (2e-5 relative).  An interpreted launch of
2 048 x 260 x 255 bins costs 0.07-0.2 s and a compile 5-8 s: the cases
share their models and the file takes about 50 s on an idle core.
"""

import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
import lightgbm_tpu.models.capabilities as capabilities
from benchmark import compare, generators
from lightgbm_tpu.ops.histogram import histogram_from_vals
from lightgbm_tpu.ops.pallas_histogram import histogram_flat, kernel_layout
from lightgbm_tpu.ops.split import _resolve_tile
from lightgbm_tpu.telemetry import registry

ROWS, COLS = 2560, 260          # > PERM_MIN_ROWS rows; 2 chunks, 3 blocks
PARAMS = {"objective": "binary", "num_leaves": 15, "learning_rate": 0.1,
          "max_bin": 255, "min_data_in_leaf": 1,
          "min_sum_hessian_in_leaf": 1.0, "metric": "none", "verbosity": -1,
          "tpu_histogram_impl": "pallas"}
NARROW = {"tpu_histogram_impl": "segment", "tpu_split_tile": 1}
QUANT = {"use_quantized_grad": True}
with open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "epsilon.json")) as _f:
    EPSILON = json.load(_f)


@pytest.fixture(scope="module")
def data():
    return generators.make("epsilon_like", 2 ** 31 + 77, rows=ROWS,
                           features=COLS, data_seed=3)


_models = {}


def _model(data, iters=1, mask_body=False, **extra):
    """A Booster trained through ``lgb.train`` (one a parameter set a
    module: a pallas-interpreted compile is most of a case's seconds).
    ``mask_body`` lifts the permutation layout's row floor over the data,
    which is how a single device reaches ``_grow_mask``."""
    key = (iters, mask_body, tuple(sorted(extra.items())))
    if key not in _models:
        floor = capabilities.PERM_MIN_ROWS
        if mask_body:
            capabilities.PERM_MIN_ROWS = 10 ** 9
        try:
            bst = lgb.train(dict(PARAMS, **extra),
                            lgb.Dataset(data["X"], label=data["label"]),
                            iters)
            assert bst._gbdt.plan.body == ("mask" if mask_body else "wave")
            bst._gbdt.scores.block_until_ready()
            bst.gauges = registry().snapshot()["gauges"]   # of ITS trace
        finally:
            capabilities.PERM_MIN_ROWS = floor
        _models[key] = bst
    return _models[key]


def _trees(bst) -> str:
    """``model_to_string()`` without its parameter block (the models under
    comparison differ in ``tpu_*`` parameters, which it prints)."""
    return bst.model_to_string().split("\nparameters:")[0]


def _walk(tree):
    """(splits, leaves) of one ``dump_model`` tree: per split its index,
    feature, NaN side and rows; per leaf its index, rows and value."""
    splits, leaves, stack = [], [], [tree["tree_structure"]]
    while stack:
        n = stack.pop()
        if "split_index" in n:
            splits.append((n["split_index"], n["split_feature"],
                           n["default_left"], n["internal_count"]))
            stack += [n["right_child"], n["left_child"]]
        else:
            leaves.append((n["leaf_index"], n["leaf_count"],
                           n["leaf_value"]))
    return sorted(splits), sorted(leaves)


def _same_splits_close_leaves(a, b, rtol=2e-5):
    """Every split on the same feature with the same rows on each side (a
    threshold may sit in a neighbouring EMPTY bin: the same partition, two
    gains that differ by float32 rounding only), every leaf value close."""
    ta, tb = (m.dump_model()["tree_info"] for m in (a, b))
    assert len(ta) == len(tb)
    for x, y in zip(ta, tb):
        (sa, la), (sb, lb) = _walk(x), _walk(y)
        assert sa == sb
        assert [l[:2] for l in la] == [l[:2] for l in lb]
        np.testing.assert_allclose([l[2] for l in la], [l[2] for l in lb],
                                   rtol=rtol, atol=1e-9)


def _verdict(bst, data, **params):
    """The benchmark's own comparison under ``epsilon``'s limits."""
    config = {"params": {k: v for k, v in dict(PARAMS, **params).items()
                         if not k.startswith("tpu_")},
              "correct": dict(EPSILON["correct"], score_sample_rows=ROWS)}
    readings = compare.compare(
        config, data, bst.dump_model()["tree_info"],
        np.asarray(bst._gbdt.scores), 2 ** 31 + 77, 3)
    ok, compared = compare.verdict(readings["program"],
                                   EPSILON["correct"]["limits"])
    assert ok, compared


# ------------------------------------------------------------ (a) the kernel

def test_chunked_histogram_is_the_sum_and_the_one_chunk_bits():
    """512 x 260 at 255 bins is 2 launches of 130 columns: the result is
    the float64 histogram to float32 rounding, and BITWISE what one launch
    over each half's columns gives (a chunk is a launch of its own: the
    slices, the concatenate and the transpose move no sum)."""
    assert kernel_layout(COLS, 255, "f32") == (128, 130, 130, 256)
    rng = np.random.RandomState(5)
    bins = rng.randint(0, 255, (512, COLS)).astype(np.uint8)
    vals = np.stack([rng.randn(512), rng.rand(512), np.ones(512)],
                    axis=1).astype(np.float32)
    got = np.asarray(histogram_flat(jnp.asarray(bins), jnp.asarray(vals),
                                    num_bins=255, interpret=True))
    assert got.shape == (COLS, 255, 3)
    want = np.zeros((COLS, 255, 3))
    np.add.at(want, (np.arange(COLS)[None, :], bins), vals[:, None, :])
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)
    assert np.array_equal(got[..., 2], want[..., 2])        # counts: exact
    for lo in (0, 130):
        one = histogram_flat(jnp.asarray(bins[:, lo:lo + 130]),
                             jnp.asarray(vals), num_bins=255,
                             interpret=True)
        assert np.array_equal(got[lo:lo + 130], np.asarray(one))


# ------------------------------------------- (b) what the width selects

def test_what_2000_columns_select():
    blk, ftile, cols_tile, b_pad = kernel_layout(2000, 255, "f32")
    assert (blk, ftile, cols_tile, b_pad) == (128, 250, 250, 256)
    assert -(-2000 // ftile) == 8
    assert _resolve_tile(0, 2000) == 128
    # one launch, one block: the accepted cells' shapes select neither
    assert kernel_layout(137, 255, "f32")[1] == 137
    assert _resolve_tile(0, 137) == 0 and _resolve_tile(0, 28) == 0


def test_plan_at_2000_columns():
    """The plan a Booster prints at 2 000 columns: the unfused wave, with
    the reason it gives today."""
    rng = np.random.RandomState(1)
    X = rng.randn(2100, 2000).astype(np.float32)
    params = dict(PARAMS, num_leaves=4, tpu_leaf_batch=16)
    ds = lgb.Dataset(X, label=(X[:, 0] > 0).astype(np.float32))
    ds.construct(params)
    assert str(lgb.Booster(params=params, train_set=ds)._gbdt.plan) == (
        "body=wave layout=single fused=False hist_impl=pallas packed4=False "
        "reduce=none pool=False; no fused: 2000 features: wave_layout admits "
        "up to 63 at 255 bins f32")


def test_chunks_segment_only_where_a_histogram_takes_several_launches():
    """``chunks<K>/cols<C>/rows<R>`` at 260 columns; ``cols<C>/rows<R>`` and
    nothing before it at 6 (the accepted cells' paths stay byte for byte)."""
    def paths(cols):
        fn = lambda b, v: histogram_from_vals(b, v, num_bins=255,
                                              impl="pallas")
        text = jax.jit(fn).lower(
            jax.ShapeDtypeStruct((256, cols), jnp.uint8),
            jax.ShapeDtypeStruct((256, 3), jnp.float32)).as_text(
                debug_info=True)
        return set(re.findall(r"((?:chunks\d+/)?cols\d+/rows\d+)", text))

    assert paths(COLS) == {"chunks2/cols130/rows256"}
    assert paths(6) == {"cols6/rows256"}


# ---------------------------------- (c) whole models, chunked and tiled

def test_wave_of_one_quantised_is_the_mask_bodys_model(data):
    a = _model(data, tpu_leaf_batch=1, **QUANT)
    b = _model(data, mask_body=True, tpu_leaf_batch=1, **QUANT, **NARROW)
    assert _trees(a) == _trees(b)


# a wave of 16 launches 16 kernels whatever it splits (an idle slot takes
# the smallest bucket), 0.07-0.2 s each interpreted: 8 leaves are 3 waves
W16 = {"tpu_leaf_batch": 16, "num_leaves": 8}


def test_wave_of_16_quantised_is_the_narrow_paths_model(data):
    a = _model(data, **W16, **QUANT)
    b = _model(data, **W16, **QUANT, **NARROW)
    assert _trees(a) == _trees(b)


def test_wave_of_16_float32_is_the_narrow_paths_splits(data):
    a = _model(data, **W16)
    b = _model(data, **W16, **NARROW)
    _same_splits_close_leaves(a, b)
    _verdict(a, data, num_leaves=8)
    # what each traced width selected, as the registry held it
    want = {"hist.col_chunks": 2, "hist.cols_tile": 130, "scan.tile": 128,
            "grow.leaf_hist_bytes": 8 * COLS * 255 * 3 * 4}
    assert {k: a.gauges[k] for k in want} == want
    assert (b.gauges["hist.col_chunks"], b.gauges["hist.cols_tile"],
            b.gauges["scan.tile"]) == (1, COLS, 0)


# ------------------------------------------------- (d) the bounded pool

@pytest.mark.parametrize("leaf_batch,slots", [(1, 4)])
def test_evicting_pool_grows_the_unpooled_model(data, leaf_batch, slots):
    """A pool of fewer slots than leaves (evictions, and recomputes from
    the leaf's rows through the chunked kernel) is the unpooled model on
    exact gradients, as tests/test_hist_pool.py pins at 12 columns."""
    slot_mb = COLS * 255 * 3 * 4 / (1 << 20)
    a = _model(data, tpu_leaf_batch=leaf_batch, **QUANT)
    b = _model(data, tpu_leaf_batch=leaf_batch, **QUANT,
               histogram_pool_size=slots * slot_mb)
    assert not a._gbdt.plan.pool and b._gbdt.plan.pool
    assert _trees(a) == _trees(b)


# ------------------------------------- the set-up of a wide dense matrix

def test_bundle_search_refuses_dense_columns_without_reading_rows(
        monkeypatch):
    """Dense columns whose zero bin is their first (non-negative data) are
    all eligible for EFB and no two fit one bundle: the search must say so
    from the columns' non-zero COUNTS (a + b - rows conflicts at least),
    not from a row-wise AND per pair — 2 M pairs at 2 000 columns."""
    from lightgbm_tpu import binning

    rng = np.random.RandomState(2)
    binned = binning.bin_dataset(
        np.abs(rng.randn(3000, 64)).astype(np.float32), max_bin=63)
    assert all(m.default_bin == 0 for m in binned.mappers)
    looked = []
    real = np.count_nonzero
    monkeypatch.setattr(np, "count_nonzero",
                        lambda *a, **k: looked.append(1) or real(*a, **k))
    assert binning.build_bundles(binned) is None
    assert not looked


def test_boundaries_on_threads_are_the_serial_boundaries(monkeypatch):
    """``bin_dataset`` finds a wide sample's boundaries column by column on
    a few threads; a column's mapper depends on that column alone."""
    from lightgbm_tpu import binning

    rng = np.random.RandomState(4)
    X = rng.randn(4000, 48).astype(np.float32)
    X[rng.rand(4000) < 0.02, 7] = np.nan
    X[:, 9] = 1.0
    monkeypatch.setattr(binning, "_PARALLEL_FIND_BIN_VALUES", 0)
    par = binning.bin_dataset(X, max_bin=63)
    monkeypatch.setattr(binning, "_PARALLEL_FIND_BIN_VALUES", 1 << 62)
    ser = binning.bin_dataset(X, max_bin=63)
    assert np.array_equal(par.bins, ser.bins)
    for m, k in zip(par.mappers, ser.mappers):
        assert (m.num_bins, m.missing_type, m.default_bin, m.is_trivial) == \
            (k.num_bins, k.missing_type, k.default_bin, k.is_trivial)
        assert np.array_equal(m.upper_bounds, k.upper_bounds)
