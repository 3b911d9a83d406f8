"""Native C++ runtime vs pure-Python parity.

The native library (lightgbm_tpu/native/csrc/native.cpp) re-implements the
reference's host-side C++ components (parser.cpp, bin.cpp, tree.cpp traversal);
these tests pin it to the Python implementations bit-for-bit.
"""

import os

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import native
from lightgbm_tpu.binning import _greedy_find_boundaries, bin_dataset, find_bin

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native library unavailable")


def test_parse_csv(tmp_path):
    p = tmp_path / "d.csv"
    p.write_text("1,2.5,3\n0,na,4.5\n1,7,8\n")
    X, y = native.parse_file(str(p))
    np.testing.assert_array_equal(y, [1, 0, 1])
    assert np.isnan(X[1, 0]) and X[2, 1] == 8.0


def test_parse_csv_header_name_label(tmp_path):
    p = tmp_path / "d.csv"
    p.write_text("a,target,b\n1.5,1,3\n2.5,0,4\n")
    X, y = native.parse_file(str(p), header=True, label_column="name:target")
    np.testing.assert_array_equal(y, [1, 0])
    np.testing.assert_array_equal(X, [[1.5, 3], [2.5, 4]])


def test_parse_tsv_label_index(tmp_path):
    p = tmp_path / "d.tsv"
    p.write_text("1\t2\t0\n3\t4\t1\n")
    X, y = native.parse_file(str(p), label_column="2")
    np.testing.assert_array_equal(y, [0, 1])
    np.testing.assert_array_equal(X, [[1, 2], [3, 4]])


def test_parse_libsvm(tmp_path):
    p = tmp_path / "d.svm"
    p.write_text("1 0:1.5 3:2\n0 1:4\n")
    X, y = native.parse_file(str(p))
    np.testing.assert_array_equal(y, [1, 0])
    assert X.shape == (2, 4)
    assert X[0, 0] == 1.5 and X[0, 3] == 2 and X[1, 1] == 4 and X[1, 0] == 0


def test_parse_error(tmp_path):
    with pytest.raises(ValueError):
        native.parse_file(str(tmp_path / "missing.csv"))
    p = tmp_path / "bad.csv"
    p.write_text("1,2,3\n1,2\n")
    with pytest.raises(ValueError, match="inconsistent"):
        native.parse_file(str(p))


@pytest.mark.parametrize("max_bins", [4, 63, 255])
def test_find_boundaries_parity(rng, max_bins):
    v = np.round(rng.randn(20000), 2)
    d, c = np.unique(v, return_counts=True)
    py = _greedy_find_boundaries(d, c, max_bins, len(v), 3)
    nat = native.find_boundaries(d, c.astype(np.int64), max_bins, len(v), 3)
    np.testing.assert_allclose(py, nat)


def test_unique_counts_parity(rng):
    v = np.round(rng.randn(5000), 1)
    v[::31] = np.nan
    d, c = np.unique(v[~np.isnan(v)], return_counts=True)
    nd, nc = native.unique_counts(v)
    np.testing.assert_array_equal(d, nd)
    np.testing.assert_array_equal(c, nc)


def test_value_to_bin_parity(rng):
    v = rng.randn(5000)
    v[::13] = np.nan
    v[::7] = 0.0
    m = find_bin(v, 63)
    os.environ["LIGHTGBM_TPU_NO_NATIVE"] = "1"
    try:
        # force the numpy branch by calling internals directly
        vv = np.where(np.isnan(v), np.nan, v)
        n_value_bins = m.num_bins - (1 if m.has_nan_bin else 0)
        ref = np.searchsorted(m.upper_bounds[: n_value_bins - 1], vv,
                              side="left").astype(np.int32)
        ref = np.where(np.isnan(vv), m.nan_bin if m.has_nan_bin else 0, ref)
    finally:
        del os.environ["LIGHTGBM_TPU_NO_NATIVE"]
    nat = native.value_to_bin(v, m.upper_bounds, n_value_bins, m.nan_bin,
                              False)
    np.testing.assert_array_equal(ref, nat)


def test_predict_bins_parity(rng):
    from sklearn.datasets import make_classification

    X, y = make_classification(n_samples=800, n_features=12, random_state=3)
    X[::11, 2] = np.nan
    X[:, 11] = np.abs(X[:, 11] * 4).astype(int) % 9
    ds = lgb.Dataset(X, label=y, categorical_feature=[11])
    bst = lgb.train({"objective": "binary", "num_leaves": 31,
                     "verbosity": -1}, ds, 12)
    gbdt = bst._gbdt
    bins = gbdt.train_data.binned.apply(X)
    nan_bins = gbdt.train_data.binned.nan_bins
    trees = gbdt.models[0]
    ref = np.zeros(len(X))
    for t in trees:
        ref += t.predict_bins(bins, nan_bins)
    nat = native.predict_bins(bins, nan_bins, trees)
    np.testing.assert_allclose(ref, nat, rtol=1e-12, atol=1e-12)


def test_predict_leaf_index_parity(rng):
    from sklearn.datasets import make_regression

    X, y = make_regression(n_samples=500, n_features=8, random_state=0)
    bst = lgb.train({"objective": "regression", "num_leaves": 15,
                     "verbosity": -1}, lgb.Dataset(X, label=y), 5)
    gbdt = bst._gbdt
    bins = gbdt.train_data.binned.apply(X)
    nan_bins = gbdt.train_data.binned.nan_bins
    for t in gbdt.models[0]:
        nat = native.predict_leaf_index(bins, nan_bins, t)
        # leaves partition rows; leaf values looked up via native indices must
        # reproduce the tree's predictions exactly
        np.testing.assert_allclose(t.leaf_value[nat],
                                   t.predict_bins(bins, nan_bins))


def test_dataset_from_file_uses_native(tmp_path):
    rng = np.random.RandomState(0)
    X = rng.randn(200, 5)
    y = (X[:, 0] > 0).astype(int)
    rows = "\n".join(",".join([str(y[i])] + ["%.6f" % v for v in X[i]])
                     for i in range(200))
    p = tmp_path / "train.csv"
    p.write_text(rows + "\n")
    from lightgbm_tpu.io.parser import load_data_file
    Xf, yf, w, g = load_data_file(str(p))
    np.testing.assert_array_equal(yf, y)
    np.testing.assert_allclose(Xf, X, atol=1e-6)


def test_native_predict_multiclass():
    from sklearn.datasets import make_classification

    X, y = make_classification(n_samples=600, n_features=8, n_classes=3,
                               n_informative=6, random_state=1)
    bst = lgb.train({"objective": "multiclass", "num_class": 3,
                     "num_leaves": 7, "verbosity": -1},
                    lgb.Dataset(X, label=y), 5)
    p = bst.predict(X)
    assert p.shape == (600, 3)
    np.testing.assert_allclose(p.sum(axis=1), 1.0, rtol=1e-5)
    assert (p.argmax(axis=1) == y).mean() > 0.7


def test_cached_library_is_keyed_by_source_not_mtime(tmp_path):
    """utils/sobuild: the cached .so is named by a hash of its source (and
    whatever the build bakes in), so a binary built from other source — or
    for another checkout path — cannot load, whatever the file times say
    (a copy or a checkout makes them arbitrary)."""
    import shutil

    from lightgbm_tpu.utils.sobuild import cached_library

    if shutil.which("g++") is None:
        pytest.skip("no g++")
    src = tmp_path / "csrc" / "lib.cpp"
    src.parent.mkdir()
    src.write_text('extern "C" int answer() { return 1; }\n')

    def cmd(out):
        return ["g++", "-shared", "-fPIC", "-o", out, str(src)]

    first = cached_library(str(src), "_lib", cmd, baked="/checkout/a")
    assert first and os.path.dirname(first) == str(tmp_path)
    assert cached_library(str(src), "_lib", cmd, baked="/checkout/a") == first
    # an OLDER source file with different text still rebuilds
    src.write_text('extern "C" int answer() { return 2; }\n')
    os.utime(src, (0, 0))
    second = cached_library(str(src), "_lib", cmd, baked="/checkout/a")
    assert second and second != first
    assert not os.path.exists(first)          # the stale binary is gone
    import ctypes
    assert ctypes.CDLL(second).answer() == 2
    # same source, another baked-in path: another binary
    third = cached_library(str(src), "_lib", cmd, baked="/checkout/b")
    assert third not in (first, second)
    # a failing toolchain reports None and leaves no partial file behind
    assert cached_library(str(src), "_lib",
                          lambda out: ["g++", "--no-such-flag", str(src)],
                          baked="/checkout/c") is None
    assert sorted(os.listdir(tmp_path)) == ["_lib-" + third.split("_lib-")[1],
                                            "csrc"]
