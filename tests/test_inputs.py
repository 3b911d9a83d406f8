"""Input-format coverage: pandas DataFrames (incl. categorical dtype) and
scipy sparse matrices (reference python-package basic.py _data_from_pandas
and CSR ingestion paths)."""

import numpy as np
import pytest

import lightgbm_tpu as lgb

pd = pytest.importorskip("pandas")


def test_pandas_dataframe_train_predict():
    rng = np.random.RandomState(0)
    n = 800
    df = pd.DataFrame({
        "a": rng.randn(n),
        "b": rng.randn(n),
        "c": pd.Categorical(rng.choice(["x", "y", "z"], n)),
    })
    y = (df["a"].to_numpy() + (df["c"] == "x").to_numpy() > 0).astype(float)
    bst = lgb.train({"objective": "binary", "num_leaves": 15,
                     "verbosity": -1}, lgb.Dataset(df, label=y), 10)
    # auto feature names from columns
    assert bst.feature_name() == ["a", "b", "c"]
    p_df = bst.predict(df)
    assert ((p_df > 0.5) == (y > 0.5)).mean() > 0.9
    # categorical column handled as categorical (codes round-trip)
    ds = lgb.Dataset(df, label=y)
    td = ds.construct({"objective": "binary", "verbosity": -1})
    assert bool(td.binned.is_categorical[2])


def test_pandas_object_column_rejected():
    df = pd.DataFrame({"a": [1.0, 2.0], "b": ["p", "q"]})
    with pytest.raises(ValueError, match="object dtype"):
        lgb.Dataset(df, label=[0, 1]).construct({"objective": "binary"})


def test_scipy_sparse_input():
    sp = pytest.importorskip("scipy.sparse")
    rng = np.random.RandomState(1)
    n, f = 600, 30
    dense = np.zeros((n, f))
    for j in range(f):
        rows = rng.choice(n, size=20, replace=False)
        dense[rows, j] = rng.rand(20) + 0.5
    y = (dense[:, 0] > 0).astype(float)
    X = sp.csr_matrix(dense)
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "min_data_in_leaf": 5, "verbosity": -1},
                    lgb.Dataset(X, label=y), 5)
    p_sparse = bst.predict(sp.csr_matrix(dense[:50]))
    p_dense = bst.predict(dense[:50])
    np.testing.assert_allclose(p_sparse, p_dense, rtol=1e-9)


def test_sparse_bins_match_dense_bins():
    """CSR-direct binning (binning._bin_sparse_matrix — the TPU answer to
    sparse_bin.hpp:73) must produce bit-identical bins to the dense path,
    including NaN entries and training equivalence."""
    sp = pytest.importorskip("scipy.sparse")
    from lightgbm_tpu.binning import bin_dataset

    rng = np.random.RandomState(4)
    n, f = 3000, 40
    dense = np.zeros((n, f))
    for j in range(f):
        rows = rng.choice(n, size=n // 20, replace=False)
        dense[rows, j] = rng.randn(len(rows))
    nanr = rng.choice(n, size=30, replace=False)
    dense[nanr, 3] = np.nan
    X = sp.csr_matrix(dense)
    b_dense = bin_dataset(dense, max_bin=63)
    b_sparse = bin_dataset(X, max_bin=63)
    np.testing.assert_array_equal(b_dense.bins, b_sparse.bins)
    np.testing.assert_array_equal(b_dense.nan_bins, b_sparse.nan_bins)
    # training end-to-end equality
    y = (np.nansum(dense[:, :3], axis=1) > 0).astype(float)
    p = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
         "verbosity": -1, "deterministic": True, "seed": 1}
    bd = lgb.train(p, lgb.Dataset(dense, label=y), 8)
    bs = lgb.train(p, lgb.Dataset(X, label=y), 8)
    np.testing.assert_allclose(bd.predict(dense), bs.predict(X), rtol=1e-9)


def test_sparse_ingestion_memory_bounded():
    """Constructing a Dataset from a 100k x 2000 / ~1% CSR must stay O(nnz)
    + the uint8 bin matrix — never the ~1.6 GB dense f64 copy (VERDICT r3
    missing #4).  Measured as the child process's peak-RSS DELTA across the
    construct call against a same-process baseline taken right before it —
    an absolute bound flaked under concurrent test processes (allocator /
    import-baseline noise moved the ambient floor); the delta is invariant
    to whatever the baseline happens to be (ISSUE-5 satellite).  The
    watermark plumbing is MemoryTracker's (telemetry/memory.py, ISSUE-10)
    — this test asserts on the tracker's host-RSS watermark instead of
    re-implementing the clear_refs bookkeeping it used to duplicate."""
    pytest.importorskip("scipy.sparse")
    import os
    import subprocess
    import sys

    code = r"""
import sys
import numpy as np
import scipy.sparse as sp
import lightgbm_tpu as lgb
from lightgbm_tpu.telemetry.memory import MemoryTracker

n, f, nnz_per_col = 100_000, 2000, 1000
rng = np.random.RandomState(0)
# .copy() matters: choice(replace=False) returns a slice view that pins
# the full n-permutation buffer, which alone would look like ~1.6 GB
rows = np.concatenate([rng.choice(n, nnz_per_col, replace=False).copy()
                       for _ in range(f)])
cols = np.repeat(np.arange(f), nnz_per_col)
vals = rng.randn(f * nnz_per_col)
X = sp.csr_matrix((vals, (rows, cols)), shape=(n, f))
y = (np.asarray(X[:, 0].todense()).ravel() > 0).astype(float)
ds = lgb.Dataset(X, label=y)

# Same-process baseline: imports done, data built, nothing constructed.
# reset_host_peak resets the kernel VmHWM watermark (clear_refs "5") so
# the post-construct read covers only the construct; where /proc is
# unavailable the ru_maxrss fallback's pre/post difference still catches
# any allocation pushing past the prior lifetime peak (the 1.6 GB dense
# copy always does).
_hwm_ok = MemoryTracker.reset_host_peak()
base_mb = MemoryTracker.host_peak_rss_mb(use_hwm=_hwm_ok)

ds.construct({"objective": "binary", "verbosity": -1,
              "enable_bundle": False})
delta_mb = MemoryTracker.host_peak_rss_mb(use_hwm=_hwm_ok) - base_mb
print("BASE_MB", base_mb, "DELTA_MB", delta_mb,
      "(VmHWM)" if _hwm_ok else "(ru_maxrss)")
# Legit construct cost: bins (100k x 2000 uint8) = 200 MB plus per-column
# working buffers; 900 MB of headroom still sits far below the ~1.6 GB
# the dense-f64 copy would add on top.
sys.exit(0 if delta_mb < 900 else 1)
"""
    r = subprocess.run([sys.executable, "-u", "-c", code],
                       capture_output=True, text=True, timeout=600,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stdout + r.stderr


def test_pandas_series_label_and_weight():
    rng = np.random.RandomState(2)
    X = rng.randn(300, 4)
    y = pd.Series((X[:, 0] > 0).astype(float))
    w = pd.Series(np.ones(300))
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "verbosity": -1},
                    lgb.Dataset(X, label=y, weight=w), 3)
    assert bst.num_trees() == 3


def test_pyarrow_table_input():
    pa = pytest.importorskip("pyarrow")
    rng = np.random.RandomState(3)
    n = 500
    codes = rng.randint(0, 4, n)
    tbl = pa.table({
        "f0": rng.randn(n),
        "f1": rng.randn(n),
        "cat": pa.array(np.array(["a", "b", "c", "d"])[codes]).dictionary_encode(),
    })
    y = (tbl.column("f0").to_numpy() + (codes == 1) > 0.3).astype(float)
    bst = lgb.train({"objective": "binary", "num_leaves": 15,
                     "verbosity": -1}, lgb.Dataset(tbl, label=y), 8)
    assert bst.feature_name() == ["f0", "f1", "cat"]
    td = lgb.Dataset(tbl, label=y).construct({"objective": "binary",
                                              "verbosity": -1})
    assert bool(td.binned.is_categorical[2])
    acc = ((bst.predict(tbl) > 0.5) == (y > 0.5)).mean()
    assert acc > 0.85


def test_chunked_and_sequence_input():
    rng = np.random.RandomState(4)
    chunks = [rng.randn(200, 4) for _ in range(5)]
    X = np.concatenate(chunks, axis=0)
    y = (X[:, 0] > 0).astype(float)

    class _Seq(lgb.Sequence):
        def __init__(self, arr):
            self.arr = arr

        def __len__(self):
            return len(self.arr)

        def __getitem__(self, idx):
            return self.arr[idx]

    for data in (chunks, _Seq(X), [_Seq(chunks[0]), _Seq(chunks[1]),
                                   np.concatenate(chunks[2:], axis=0)]):
        bst = lgb.train({"objective": "binary", "num_leaves": 7,
                         "verbosity": -1}, lgb.Dataset(data, label=y), 3)
        p_chunks = bst.predict(X[:50])
        assert p_chunks.shape == (50,)


def test_dataset_subset_and_add_features():
    rng = np.random.RandomState(5)
    X = rng.randn(400, 4)
    y = (X[:, 0] > 0).astype(float)
    ds = lgb.Dataset(X, label=y, weight=np.ones(400))
    sub = ds.subset(np.arange(0, 400, 2))
    assert sub.num_data() == 200
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "verbosity": -1}, sub, 3)
    assert bst.num_trees() == 3

    extra = lgb.Dataset(rng.randn(400, 2))
    ds2 = lgb.Dataset(X.copy(), label=y, feature_name=[f"f{i}" for i in range(4)])
    ds2.add_features_from(extra)
    assert ds2.num_feature() == 6
    td = ds2.construct({"objective": "binary", "verbosity": -1})
    assert td.num_features == 6


def test_in_data_column_specs(tmp_path):
    """weight_column / group_column / ignore_column reference semantics:
    int indices don't count the label column; name: uses the header; the
    query column holds per-row ids; ignored columns leave the matrix."""
    import numpy as np
    from lightgbm_tpu.io.parser import load_data_file

    rng = np.random.RandomState(0)
    n = 40
    y = rng.randint(0, 2, n).astype(float)
    f0 = rng.randn(n)
    w = rng.rand(n) + 0.5
    qid = np.repeat(np.arange(8), 5).astype(float)
    junk = np.full(n, 9.9)
    f1 = rng.randn(n)
    # file columns: label, f0, weight, qid, junk, f1
    mat = np.column_stack([y, f0, w, qid, junk, f1])
    path = tmp_path / "d.csv"
    np.savetxt(path, mat, delimiter=",", fmt="%.10g",
               header="lab,f0,wt,q,junk,f1", comments="")

    X, yy, ww, gg = load_data_file(
        str(path), label_column="0", header=True,
        weight_column="1",     # X-space: w is file col 2 -> X col 1
        group_column="2",      # X-space: qid is file col 3 -> X col 2
        ignore_column="3")     # X-space: junk is file col 4 -> X col 3
    np.testing.assert_allclose(yy, y)
    np.testing.assert_allclose(ww, w, rtol=1e-9)
    np.testing.assert_array_equal(gg, np.full(8, 5))
    assert X.shape == (n, 2)
    np.testing.assert_allclose(X[:, 0], f0, rtol=1e-9)
    np.testing.assert_allclose(X[:, 1], f1, rtol=1e-9)

    # name: form resolves through the header identically
    X2, _, ww2, gg2 = load_data_file(
        str(path), label_column="name:lab", header=True,
        weight_column="name:wt", group_column="name:q",
        ignore_column="name:junk")
    np.testing.assert_allclose(ww2, w, rtol=1e-9)
    np.testing.assert_array_equal(gg2, np.full(8, 5))
    np.testing.assert_allclose(X2, X, rtol=1e-9)


def test_column_specs_tsv_and_sidefile_independence(tmp_path):
    """name: specs must work on TSV headers, and a .query side file loads
    even when weight comes from an in-data column (independent fields,
    reference metadata.cpp)."""
    import numpy as np
    from lightgbm_tpu.io.parser import load_data_file

    rng = np.random.RandomState(1)
    n = 20
    y = rng.randint(0, 2, n).astype(float)
    f0 = rng.randn(n)
    w = rng.rand(n) + 0.5
    mat = np.column_stack([y, f0, w])
    path = tmp_path / "d.tsv"
    np.savetxt(path, mat, delimiter="\t", fmt="%.10g",
               header="lab\tf0\twt", comments="")
    np.savetxt(str(path) + ".query", np.array([5, 5, 10]), fmt="%d")

    X, yy, ww, gg = load_data_file(str(path), label_column="name:lab",
                                   header=True, weight_column="name:wt")
    np.testing.assert_allclose(ww, w, rtol=1e-9)
    np.testing.assert_array_equal(gg, [5, 5, 10])   # side file still loads
    assert X.shape == (n, 1)
    np.testing.assert_allclose(X[:, 0], f0, rtol=1e-9)


def test_header_names_propagate_to_model(tmp_path):
    """CSV header names must survive into the saved model's feature_names
    (reference DatasetLoader reads them from the header), accounting for
    extracted weight columns."""
    import subprocess, sys, os
    import numpy as np
    rng = np.random.RandomState(0)
    n = 200
    mat = np.column_stack([rng.randint(0, 2, n), rng.rand(n) + 0.5,
                           rng.randn(n), rng.randn(n)])
    path = tmp_path / "d.csv"
    np.savetxt(path, mat, delimiter=",", fmt="%.8g",
               header="lab,wt,alpha,beta", comments="")
    out = tmp_path / "m.txt"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "lightgbm_tpu", "task=train",
         "objective=binary", "header=true", f"data={path}",
         "weight_column=0", "num_iterations=2", "num_leaves=4",
         f"output_model={out}"], capture_output=True, text=True, env=env)
    assert r.returncode == 0, r.stderr[-1500:]
    model = out.read_text()
    assert "feature_names=alpha beta" in model


def test_dataset_accepts_text_file_path(tmp_path):
    """lgb.Dataset('train.csv') must load text files like the reference
    python package (binary caches remain the fast path), honoring header
    names and params column specs."""
    import numpy as np
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(0)
    n = 500
    Xf = rng.randn(n, 3)
    y = (Xf[:, 0] > 0).astype(float)
    path = tmp_path / "tr.csv"
    np.savetxt(path, np.column_stack([y, Xf]), delimiter=",", fmt="%.8g",
               header="lab,a,b,c", comments="")
    ds = lgb.Dataset(str(path), params={"header": True})
    bst = lgb.train({"objective": "binary", "verbosity": -1,
                     "num_leaves": 7, "header": True}, ds, 5)
    assert bst.feature_name() == ["a", "b", "c"]
    acc = ((bst.predict(Xf) > 0.5) == (y > 0.5)).mean()
    assert acc > 0.95


def test_categorical_feature_name_prefix(tmp_path):
    """categorical_feature='name:c1,c2' (reference form: one prefix for
    the whole list) resolves through feature names."""
    import numpy as np
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(0)
    n = 600
    cat = rng.randint(0, 6, n).astype(float)
    num = rng.randn(n)
    y = (np.isin(cat, [1, 4]) ^ (num > 0)).astype(float)
    X = np.column_stack([cat, num])
    ds = lgb.Dataset(X, label=y, feature_name=["kind", "score"],
                     categorical_feature="name:kind")
    bst = lgb.train({"objective": "binary", "verbosity": -1,
                     "num_leaves": 7}, ds, 15)
    model = bst.model_to_string()
    # trees record categorical split counts in num_cat (reference
    # gbdt_model_text format)
    assert any(line.startswith("num_cat=") and set(line[8:].split()) != {"0"}
               for line in model.splitlines())
    acc = ((bst.predict(X) > 0.5) == (y > 0.5)).mean()
    assert acc > 0.9
