"""C API shim tests — the reference's ctypes-driven pattern
(``tests/c_api_test/test_.py``): load the C-ABI library, run the full
Dataset -> Booster -> train -> eval -> predict -> save/load workflow through
the C surface, and check parity with the Python API.
"""

import ctypes

import numpy as np
import pytest
from sklearn.datasets import make_classification

import lightgbm_tpu as lgb
from lightgbm_tpu import capi

_LIB = capi.lib_path()
pytestmark = pytest.mark.skipif(_LIB is None,
                                reason="C API shim failed to build")


def _load():
    lib = ctypes.CDLL(_LIB)
    lib.LGBM_GetLastError.restype = ctypes.c_char_p
    return lib


def _check(lib, rc):
    assert rc == 0, lib.LGBM_GetLastError().decode()


def _dataset_from_mat(lib, X, y=None, params=b"", reference=None):
    X32 = np.ascontiguousarray(X, np.float32)
    handle = ctypes.c_void_p()
    _check(lib, lib.LGBM_DatasetCreateFromMat(
        X32.ctypes.data_as(ctypes.c_void_p), 0,  # C_API_DTYPE_FLOAT32
        ctypes.c_int32(X32.shape[0]), ctypes.c_int32(X32.shape[1]),
        ctypes.c_int(1), params, reference or ctypes.c_void_p(),
        ctypes.byref(handle)))
    if y is not None:
        y32 = np.ascontiguousarray(y, np.float32)
        _check(lib, lib.LGBM_DatasetSetField(
            handle, b"label", y32.ctypes.data_as(ctypes.c_void_p),
            ctypes.c_int(len(y32)), 0))
    return handle


def test_capi_full_workflow(tmp_path):
    lib = _load()
    assert lib.LGBM_CAPIVersion() == 1

    X, y = make_classification(n_samples=800, n_features=6, n_informative=4,
                               random_state=0)
    train = _dataset_from_mat(lib, X[:600], y[:600])
    valid = _dataset_from_mat(lib, X[600:], y[600:])

    nd, nf = ctypes.c_int32(), ctypes.c_int32()
    _check(lib, lib.LGBM_DatasetGetNumData(train, ctypes.byref(nd)))
    _check(lib, lib.LGBM_DatasetGetNumFeature(train, ctypes.byref(nf)))
    assert (nd.value, nf.value) == (600, 6)

    params = b"objective=binary metric=auc num_leaves=15 verbosity=-1"
    bst = ctypes.c_void_p()
    _check(lib, lib.LGBM_BoosterCreate(train, params, ctypes.byref(bst)))
    _check(lib, lib.LGBM_BoosterAddValidData(bst, valid))

    finished = ctypes.c_int()
    for _ in range(10):
        _check(lib, lib.LGBM_BoosterUpdateOneIter(bst, ctypes.byref(finished)))

    it = ctypes.c_int()
    _check(lib, lib.LGBM_BoosterGetCurrentIteration(bst, ctypes.byref(it)))
    assert it.value == 10
    nc = ctypes.c_int()
    _check(lib, lib.LGBM_BoosterGetNumClasses(bst, ctypes.byref(nc)))
    assert nc.value == 1

    # eval on the valid set: AUC should be sane
    n_eval = ctypes.c_int()
    _check(lib, lib.LGBM_BoosterGetEvalCounts(bst, ctypes.byref(n_eval)))
    assert n_eval.value >= 1
    res = (ctypes.c_double * 8)()
    out_len = ctypes.c_int()
    _check(lib, lib.LGBM_BoosterGetEval(bst, 1, ctypes.byref(out_len), res))
    assert out_len.value >= 1
    assert 0.7 < res[0] <= 1.0

    # predict through the C API and compare with the Python API
    Xp = np.ascontiguousarray(X[600:], np.float64)
    out = (ctypes.c_double * Xp.shape[0])()
    out_n = ctypes.c_int64()
    _check(lib, lib.LGBM_BoosterPredictForMat(
        bst, Xp.ctypes.data_as(ctypes.c_void_p), 1,
        ctypes.c_int32(Xp.shape[0]), ctypes.c_int32(Xp.shape[1]),
        ctypes.c_int(1), ctypes.c_int(1),  # RAW_SCORE
        ctypes.c_int(0), ctypes.c_int(-1), b"", ctypes.byref(out_n), out))
    assert out_n.value == Xp.shape[0]
    c_pred = np.array(out[:])

    # "pred_early_stop=false" must be parsed as bool false (reference
    # Config::GetBool), not as a truthy non-empty string — predictions with
    # the flag explicitly disabled must match the default exactly
    out_es = (ctypes.c_double * Xp.shape[0])()
    _check(lib, lib.LGBM_BoosterPredictForMat(
        bst, Xp.ctypes.data_as(ctypes.c_void_p), 1,
        ctypes.c_int32(Xp.shape[0]), ctypes.c_int32(Xp.shape[1]),
        ctypes.c_int(1), ctypes.c_int(1), ctypes.c_int(0), ctypes.c_int(-1),
        b"pred_early_stop=false", ctypes.byref(out_n), out_es))
    np.testing.assert_array_equal(np.array(out_es[:]), c_pred)

    # save -> reload via string round trip
    buf_len = ctypes.c_int64(1 << 22)
    buf = ctypes.create_string_buffer(buf_len.value)
    str_len = ctypes.c_int64()
    _check(lib, lib.LGBM_BoosterSaveModelToString(
        bst, ctypes.c_int(0), ctypes.c_int(-1), ctypes.c_int(0), buf_len,
        ctypes.byref(str_len), buf))
    model_str = buf.value.decode()
    assert "tree" in model_str

    bst2 = ctypes.c_void_p()
    out_it = ctypes.c_int()
    _check(lib, lib.LGBM_BoosterLoadModelFromString(
        buf.value, ctypes.byref(out_it), ctypes.byref(bst2)))
    assert out_it.value == 10
    out2 = (ctypes.c_double * Xp.shape[0])()
    _check(lib, lib.LGBM_BoosterPredictForMat(
        bst2, Xp.ctypes.data_as(ctypes.c_void_p), 1,
        ctypes.c_int32(Xp.shape[0]), ctypes.c_int32(Xp.shape[1]),
        ctypes.c_int(1), ctypes.c_int(1), ctypes.c_int(0), ctypes.c_int(-1),
        b"", ctypes.byref(out_n), out2))
    np.testing.assert_allclose(np.array(out2[:]), c_pred, rtol=1e-6,
                               atol=1e-6)

    # parity with the Python surface (same params, same data)
    py = lgb.train({"objective": "binary", "metric": "auc", "num_leaves": 15,
                    "verbosity": -1},
                   lgb.Dataset(X[:600], label=y[:600]), 10)
    py_pred = py.predict(X[600:], raw_score=True)
    np.testing.assert_allclose(c_pred, py_pred, rtol=1e-4, atol=1e-4)

    # model file save + load
    path = str(tmp_path / "capi_model.txt")
    _check(lib, lib.LGBM_BoosterSaveModel(
        bst, ctypes.c_int(0), ctypes.c_int(-1), ctypes.c_int(0),
        path.encode()))
    bst3 = ctypes.c_void_p()
    _check(lib, lib.LGBM_BoosterCreateFromModelfile(
        path.encode(), ctypes.byref(out_it), ctypes.byref(bst3)))
    assert out_it.value == 10

    # feature importance
    imp = (ctypes.c_double * 6)()
    _check(lib, lib.LGBM_BoosterFeatureImportance(
        bst, ctypes.c_int(-1), ctypes.c_int(0), imp))
    assert sum(imp[:]) > 0

    for h in (bst, bst2, bst3):
        _check(lib, lib.LGBM_BoosterFree(h))
    for h in (train, valid):
        _check(lib, lib.LGBM_DatasetFree(h))


def test_capi_error_reporting():
    lib = _load()
    bad = ctypes.c_void_p()
    rc = lib.LGBM_DatasetCreateFromFile(b"/nonexistent/file.csv", b"",
                                        ctypes.c_void_p(), ctypes.byref(bad))
    assert rc == -1
    msg = lib.LGBM_GetLastError().decode()
    assert "nonexistent" in msg or "No such file" in msg


def test_capi_rollback_and_dump():
    lib = _load()
    X, y = make_classification(n_samples=400, n_features=5, random_state=1)
    train = _dataset_from_mat(lib, X, y)
    bst = ctypes.c_void_p()
    _check(lib, lib.LGBM_BoosterCreate(
        train, b"objective=binary num_leaves=7 verbosity=-1",
        ctypes.byref(bst)))
    fin = ctypes.c_int()
    for _ in range(3):
        _check(lib, lib.LGBM_BoosterUpdateOneIter(bst, ctypes.byref(fin)))
    _check(lib, lib.LGBM_BoosterRollbackOneIter(bst))
    it = ctypes.c_int()
    _check(lib, lib.LGBM_BoosterGetCurrentIteration(bst, ctypes.byref(it)))
    assert it.value == 2

    buf_len = ctypes.c_int64(1 << 22)
    buf = ctypes.create_string_buffer(buf_len.value)
    out_len = ctypes.c_int64()
    _check(lib, lib.LGBM_BoosterDumpModel(
        bst, ctypes.c_int(0), ctypes.c_int(-1), ctypes.c_int(0), buf_len,
        ctypes.byref(out_len), buf))
    import json
    model = json.loads(buf.value.decode())
    assert model["num_tree_per_iteration"] >= 1
    _check(lib, lib.LGBM_BoosterFree(bst))
    _check(lib, lib.LGBM_DatasetFree(train))


def test_capi_standalone_c_program(tmp_path):
    """Compile a plain C program against the shim and run it OUTSIDE any
    Python process — proves the embedded-interpreter mode (the reference's
    c_api is likewise consumable from bare C)."""
    import os
    import shutil
    import subprocess
    import sys

    if shutil.which("gcc") is None:
        pytest.skip("no C compiler")
    import lightgbm_tpu
    pkg_root = os.path.dirname(os.path.dirname(
        os.path.abspath(lightgbm_tpu.__file__)))
    src = tmp_path / "demo.c"
    src.write_text(r'''
#include <stdio.h>
#include "lightgbm_tpu_c_api.h"
int main(void) {
  float X[200 * 3]; float y[200];
  for (int i = 0; i < 200; ++i) {
    for (int j = 0; j < 3; ++j) X[i*3+j] = (float)((i*37+j*11) % 100) / 100.0f - 0.5f;
    y[i] = X[i*3] > 0 ? 1.0f : 0.0f;
  }
  DatasetHandle ds; BoosterHandle bst; int fin;
  if (LGBM_DatasetCreateFromMat(X, C_API_DTYPE_FLOAT32, 200, 3, 1, "", NULL, &ds)) { fprintf(stderr, "%s\n", LGBM_GetLastError()); return 1; }
  if (LGBM_DatasetSetField(ds, "label", y, 200, C_API_DTYPE_FLOAT32)) return 1;
  if (LGBM_BoosterCreate(ds, "objective=binary num_leaves=7 min_data_in_leaf=5 verbosity=-1", &bst)) { fprintf(stderr, "%s\n", LGBM_GetLastError()); return 1; }
  for (int i = 0; i < 3; ++i) if (LGBM_BoosterUpdateOneIter(bst, &fin)) { fprintf(stderr, "%s\n", LGBM_GetLastError()); return 1; }
  int it; LGBM_BoosterGetCurrentIteration(bst, &it);
  printf("iters=%d\n", it);
  return it == 3 ? 0 : 1;
}
''')
    exe = tmp_path / "demo"
    subprocess.run(
        ["gcc", "-O1", str(src),
         f"-I{os.path.join(pkg_root, 'lightgbm_tpu', 'capi', 'include')}",
         _LIB, "-o", str(exe),
         f"-Wl,-rpath,{os.path.dirname(_LIB)}"],
        check=True, capture_output=True)
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               LIGHTGBM_TPU_PKG_DIR=pkg_root,
               PYTHONPATH=pkg_root + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    res = subprocess.run([str(exe)], env=env, capture_output=True,
                         text=True, timeout=240)
    assert res.returncode == 0, (res.stdout, res.stderr)
    assert "iters=3" in res.stdout


def test_capi_csr_and_feature_names():
    sp = pytest.importorskip("scipy.sparse")
    lib = _load()
    rng = np.random.RandomState(2)
    dense = np.zeros((500, 12))
    for j in range(12):
        rows = rng.choice(500, size=40, replace=False)
        dense[rows, j] = rng.rand(40) + 0.2
    y = (dense[:, 0] > 0).astype(np.float32)
    csr = sp.csr_matrix(dense)
    indptr = np.ascontiguousarray(csr.indptr, np.int32)
    indices = np.ascontiguousarray(csr.indices, np.int32)
    data = np.ascontiguousarray(csr.data, np.float64)
    ds = ctypes.c_void_p()
    _check(lib, lib.LGBM_DatasetCreateFromCSR(
        indptr.ctypes.data_as(ctypes.c_void_p), ctypes.c_int(2),  # INT32
        indices.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        data.ctypes.data_as(ctypes.c_void_p), ctypes.c_int(1),  # FLOAT64
        ctypes.c_int64(len(indptr)), ctypes.c_int64(len(data)),
        ctypes.c_int64(12), b"", ctypes.c_void_p(), ctypes.byref(ds)))
    _check(lib, lib.LGBM_DatasetSetField(
        ds, b"label", y.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_int(len(y)), 0))

    names = [f"feat_{i}".encode() for i in range(12)]
    arr = (ctypes.c_char_p * 12)(*names)
    _check(lib, lib.LGBM_DatasetSetFeatureNames(
        ds, ctypes.cast(arr, ctypes.POINTER(ctypes.c_char_p)),
        ctypes.c_int(12)))
    bufs = [ctypes.create_string_buffer(64) for _ in range(12)]
    ptrs = (ctypes.c_char_p * 12)(*[ctypes.addressof(b) for b in bufs])
    nn, blen = ctypes.c_int(), ctypes.c_size_t()
    _check(lib, lib.LGBM_DatasetGetFeatureNames(
        ds, ctypes.c_int(12), ctypes.byref(nn), ctypes.c_size_t(64),
        ctypes.byref(blen), ctypes.cast(ptrs,
                                        ctypes.POINTER(ctypes.c_char_p))))
    assert nn.value == 12 and bufs[3].value == b"feat_3"

    bst = ctypes.c_void_p()
    _check(lib, lib.LGBM_BoosterCreate(
        ds, b"objective=binary num_leaves=7 min_data_in_leaf=5 verbosity=-1",
        ctypes.byref(bst)))
    fin = ctypes.c_int()
    for _ in range(5):
        _check(lib, lib.LGBM_BoosterUpdateOneIter(bst, ctypes.byref(fin)))
    _check(lib, lib.LGBM_BoosterResetParameter(bst, b"learning_rate=0.05"))

    out = (ctypes.c_double * 500)()
    out_n = ctypes.c_int64()
    _check(lib, lib.LGBM_BoosterPredictForCSR(
        bst, indptr.ctypes.data_as(ctypes.c_void_p), ctypes.c_int(2),
        indices.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        data.ctypes.data_as(ctypes.c_void_p), ctypes.c_int(1),
        ctypes.c_int64(len(indptr)), ctypes.c_int64(len(data)),
        ctypes.c_int64(12), ctypes.c_int(0), ctypes.c_int(0),
        ctypes.c_int(-1), b"", ctypes.byref(out_n), out))
    assert out_n.value == 500
    preds = np.array(out[:])
    from lightgbm_tpu.metrics import _auc
    auc = _auc(y.astype(np.float64), preds, None, None)
    assert auc > 0.9, auc
    assert preds.std() > 1e-6  # actually discriminates
    _check(lib, lib.LGBM_BoosterFree(bst))
    _check(lib, lib.LGBM_DatasetFree(ds))


def test_capi_streaming_push():
    """CreateByReference + PushRows chunks + WithMetadata (reference
    streaming protocol, c_api.h:162-323): a dataset streamed in 4 chunks
    must train identically to the one-shot matrix dataset."""
    lib = _load()
    rng = np.random.RandomState(8)
    n, f = 800, 6
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)

    ref = _dataset_from_mat(lib, X, y, params=b"max_bin=63")
    stream = ctypes.c_void_p()
    _check(lib, lib.LGBM_DatasetCreateByReference(
        ref, ctypes.c_int64(n), ctypes.byref(stream)))
    _check(lib, lib.LGBM_DatasetSetWaitForManualFinish(stream, 1))
    chunk = n // 4
    for i in range(4):
        blk = np.ascontiguousarray(X[i * chunk:(i + 1) * chunk], np.float64)
        lab = np.ascontiguousarray(y[i * chunk:(i + 1) * chunk], np.float32)
        _check(lib, lib.LGBM_DatasetPushRowsWithMetadata(
            stream, blk.ctypes.data_as(ctypes.c_void_p), 1,
            ctypes.c_int32(chunk), ctypes.c_int32(f),
            ctypes.c_int32(i * chunk),
            lab.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            None, None, None, ctypes.c_int32(0)))
    _check(lib, lib.LGBM_DatasetMarkFinished(stream))
    nd = ctypes.c_int32()
    _check(lib, lib.LGBM_DatasetGetNumData(stream, ctypes.byref(nd)))
    assert nd.value == n

    def _train(ds):
        bst = ctypes.c_void_p()
        _check(lib, lib.LGBM_BoosterCreate(
            ds, b"objective=binary num_leaves=15 min_data_in_leaf=5 "
                b"verbosity=-1 max_bin=63 deterministic=true seed=3",
            ctypes.byref(bst)))
        fin = ctypes.c_int()
        for _ in range(8):
            _check(lib, lib.LGBM_BoosterUpdateOneIter(bst, ctypes.byref(fin)))
        return bst

    b_stream = _train(stream)
    b_mat = _train(_dataset_from_mat(lib, X, y, params=b"max_bin=63"))
    Xp = np.ascontiguousarray(X[:100], np.float64)
    outs = []
    for bst in (b_stream, b_mat):
        out = (ctypes.c_double * 100)()
        out_n = ctypes.c_int64()
        _check(lib, lib.LGBM_BoosterPredictForMat(
            bst, Xp.ctypes.data_as(ctypes.c_void_p), 1,
            ctypes.c_int32(100), ctypes.c_int32(f), ctypes.c_int(1),
            ctypes.c_int(0), ctypes.c_int(0), ctypes.c_int(-1), b"",
            ctypes.byref(out_n), out))
        outs.append(np.array(out[:]))
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-9)


def test_capi_csr_push_and_csc_create():
    sp = pytest.importorskip("scipy.sparse")
    lib = _load()
    rng = np.random.RandomState(9)
    n, f = 600, 8
    dense = np.where(rng.rand(n, f) < 0.3, rng.randn(n, f), 0.0)
    y = (dense[:, 0] > 0).astype(np.float64)

    # CSC create routes through the sparse-direct binning path
    csc = sp.csc_matrix(dense)
    indptr = np.ascontiguousarray(csc.indptr, np.int32)
    indices = np.ascontiguousarray(csc.indices, np.int32)
    vals = np.ascontiguousarray(csc.data, np.float64)
    h_csc = ctypes.c_void_p()
    _check(lib, lib.LGBM_DatasetCreateFromCSC(
        indptr.ctypes.data_as(ctypes.c_void_p), 2,
        indices.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        vals.ctypes.data_as(ctypes.c_void_p), 1,
        ctypes.c_int64(len(indptr)), ctypes.c_int64(csc.nnz),
        ctypes.c_int64(n), b"max_bin=63", ctypes.c_void_p(),
        ctypes.byref(h_csc)))
    nd = ctypes.c_int32()
    nf = ctypes.c_int32()
    _check(lib, lib.LGBM_DatasetGetNumData(h_csc, ctypes.byref(nd)))
    _check(lib, lib.LGBM_DatasetGetNumFeature(h_csc, ctypes.byref(nf)))
    assert (nd.value, nf.value) == (n, f)

    # CSR streaming push against it
    stream = ctypes.c_void_p()
    _check(lib, lib.LGBM_DatasetCreateByReference(
        h_csc, ctypes.c_int64(n), ctypes.byref(stream)))
    csr = sp.csr_matrix(dense)
    half = n // 2
    for i, (lo, hi) in enumerate(((0, half), (half, n))):
        blk = csr[lo:hi]
        bi = np.ascontiguousarray(blk.indptr, np.int32)
        bj = np.ascontiguousarray(blk.indices, np.int32)
        bv = np.ascontiguousarray(blk.data, np.float64)
        lab = np.ascontiguousarray(y[lo:hi], np.float32)
        _check(lib, lib.LGBM_DatasetPushRowsByCSRWithMetadata(
            stream, bi.ctypes.data_as(ctypes.c_void_p), 2,
            bj.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            bv.ctypes.data_as(ctypes.c_void_p), 1,
            ctypes.c_int64(len(bi)), ctypes.c_int64(blk.nnz),
            ctypes.c_int64(lo),
            lab.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            None, None, None, ctypes.c_int32(0)))
    _check(lib, lib.LGBM_DatasetMarkFinished(stream))
    bst = ctypes.c_void_p()
    _check(lib, lib.LGBM_BoosterCreate(
        stream, b"objective=binary num_leaves=7 verbosity=-1 max_bin=63",
        ctypes.byref(bst)))
    fin = ctypes.c_int()
    for _ in range(3):
        _check(lib, lib.LGBM_BoosterUpdateOneIter(bst, ctypes.byref(fin)))
    it = ctypes.c_int()
    _check(lib, lib.LGBM_BoosterGetCurrentIteration(bst, ctypes.byref(it)))
    assert it.value == 3


def test_capi_single_row_fast_predict():
    """FastConfig single-row serving (reference c_api.h:1332): parity with
    the batch path and a sub-millisecond per-call budget."""
    import time

    lib = _load()
    rng = np.random.RandomState(10)
    n, f = 1200, 10
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.3 * X[:, 1] > 0).astype(np.float64)
    ds = _dataset_from_mat(lib, X, y)
    bst = ctypes.c_void_p()
    _check(lib, lib.LGBM_BoosterCreate(
        ds, b"objective=binary num_leaves=31 verbosity=-1",
        ctypes.byref(bst)))
    fin = ctypes.c_int()
    for _ in range(20):
        _check(lib, lib.LGBM_BoosterUpdateOneIter(bst, ctypes.byref(fin)))

    fast = ctypes.c_void_p()
    _check(lib, lib.LGBM_BoosterPredictForMatSingleRowFastInit(
        bst, ctypes.c_int(0), ctypes.c_int(0), ctypes.c_int(-1),
        ctypes.c_int(1), ctypes.c_int32(f), b"", ctypes.byref(fast)))

    # parity vs batch predict
    rows = np.ascontiguousarray(X[:50], np.float64)
    batch = (ctypes.c_double * 50)()
    out_n = ctypes.c_int64()
    _check(lib, lib.LGBM_BoosterPredictForMat(
        bst, rows.ctypes.data_as(ctypes.c_void_p), 1, ctypes.c_int32(50),
        ctypes.c_int32(f), ctypes.c_int(1), ctypes.c_int(0),
        ctypes.c_int(0), ctypes.c_int(-1), b"", ctypes.byref(out_n), batch))
    one = ctypes.c_double()
    for i in range(50):
        row = np.ascontiguousarray(rows[i], np.float64)
        _check(lib, lib.LGBM_BoosterPredictForMatSingleRowFast(
            fast, row.ctypes.data_as(ctypes.c_void_p),
            ctypes.byref(out_n), ctypes.byref(one)))
        assert out_n.value == 1
        # batch path converts outputs through jax f32; the fast path's
        # host-numpy sigmoid is f64 — identical rounding is not expected
        np.testing.assert_allclose(one.value, batch[i], rtol=1e-6,
                                   atol=1e-7)

    # latency budget: <= 1 ms/call averaged over 200 calls (after warmup)
    row = np.ascontiguousarray(rows[0], np.float64)
    t0 = time.perf_counter()
    for _ in range(200):
        lib.LGBM_BoosterPredictForMatSingleRowFast(
            fast, row.ctypes.data_as(ctypes.c_void_p),
            ctypes.byref(out_n), ctypes.byref(one))
    per_call_ms = (time.perf_counter() - t0) / 200 * 1e3
    assert per_call_ms < 1.0, f"{per_call_ms:.3f} ms/call"
    _check(lib, lib.LGBM_FastConfigFree(fast))


def test_capi_extended_surface(tmp_path):
    """Round-4 parity batch: metadata getters, leaf get/set, bounds, merge,
    shuffle, refit, custom objective, subset, param aliases, sampling,
    log callback (reference c_api.h declarations of the same names)."""
    lib = _load()
    rng = np.random.RandomState(11)
    n, f = 900, 6
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.4 * X[:, 1] > 0).astype(np.float64)
    ds = _dataset_from_mat(lib, X, y)
    bst = ctypes.c_void_p()
    _check(lib, lib.LGBM_BoosterCreate(
        ds, b"objective=binary num_leaves=15 verbosity=-1",
        ctypes.byref(bst)))
    fin = ctypes.c_int()
    for _ in range(6):
        _check(lib, lib.LGBM_BoosterUpdateOneIter(bst, ctypes.byref(fin)))

    # CalcNumPredict / NumberOfTotalModel / GetLinear
    n_pred = ctypes.c_int64()
    _check(lib, lib.LGBM_BoosterCalcNumPredict(
        bst, ctypes.c_int(50), ctypes.c_int(0), ctypes.c_int(0),
        ctypes.c_int(-1), ctypes.byref(n_pred)))
    assert n_pred.value == 50
    _check(lib, lib.LGBM_BoosterCalcNumPredict(
        bst, ctypes.c_int(50), ctypes.c_int(3), ctypes.c_int(0),
        ctypes.c_int(-1), ctypes.byref(n_pred)))
    assert n_pred.value == 50 * (f + 1)
    total = ctypes.c_int()
    _check(lib, lib.LGBM_BoosterNumberOfTotalModel(bst, ctypes.byref(total)))
    assert total.value == 6
    lin = ctypes.c_int()
    _check(lib, lib.LGBM_BoosterGetLinear(bst, ctypes.byref(lin)))
    assert lin.value == 0

    # bounds bracket every prediction
    lo, hi = ctypes.c_double(), ctypes.c_double()
    _check(lib, lib.LGBM_BoosterGetLowerBoundValue(bst, ctypes.byref(lo)))
    _check(lib, lib.LGBM_BoosterGetUpperBoundValue(bst, ctypes.byref(hi)))
    Xp = np.ascontiguousarray(X[:100], np.float64)
    out = (ctypes.c_double * 100)()
    out_n = ctypes.c_int64()
    _check(lib, lib.LGBM_BoosterPredictForMat(
        bst, Xp.ctypes.data_as(ctypes.c_void_p), 1, ctypes.c_int32(100),
        ctypes.c_int32(f), ctypes.c_int(1), ctypes.c_int(1), ctypes.c_int(0),
        ctypes.c_int(-1), b"", ctypes.byref(out_n), out))
    preds = np.array(out[:])
    assert lo.value <= preds.min() + 1e-9
    assert hi.value >= preds.max() - 1e-9

    # leaf get/set round trip changes predictions
    v = ctypes.c_double()
    _check(lib, lib.LGBM_BoosterGetLeafValue(
        bst, ctypes.c_int(0), ctypes.c_int(0), ctypes.byref(v)))
    _check(lib, lib.LGBM_BoosterSetLeafValue(
        bst, ctypes.c_int(0), ctypes.c_int(0),
        ctypes.c_double(v.value + 1.0)))
    v2 = ctypes.c_double()
    _check(lib, lib.LGBM_BoosterGetLeafValue(
        bst, ctypes.c_int(0), ctypes.c_int(0), ctypes.byref(v2)))
    assert abs(v2.value - v.value - 1.0) < 1e-12
    _check(lib, lib.LGBM_BoosterSetLeafValue(
        bst, ctypes.c_int(0), ctypes.c_int(0), v))

    # GetPredict over the training data matches batch predict
    npred = ctypes.c_int64()
    _check(lib, lib.LGBM_BoosterGetNumPredict(bst, ctypes.c_int(0),
                                              ctypes.byref(npred)))
    assert npred.value == n
    trainp = (ctypes.c_double * n)()
    _check(lib, lib.LGBM_BoosterGetPredict(bst, ctypes.c_int(0),
                                           ctypes.byref(npred), trainp))
    full = (ctypes.c_double * n)()
    Xa = np.ascontiguousarray(X, np.float64)
    _check(lib, lib.LGBM_BoosterPredictForMat(
        bst, Xa.ctypes.data_as(ctypes.c_void_p), 1, ctypes.c_int32(n),
        ctypes.c_int32(f), ctypes.c_int(1), ctypes.c_int(0), ctypes.c_int(0),
        ctypes.c_int(-1), b"", ctypes.byref(out_n), full))
    np.testing.assert_allclose(np.array(trainp[:]), np.array(full[:]),
                               rtol=2e-3, atol=2e-3)

    # refit with the model's own leaf assignments at decay 1 is a no-op
    nleaf = (ctypes.c_double * (n * 6))()
    _check(lib, lib.LGBM_BoosterPredictForMat(
        bst, Xa.ctypes.data_as(ctypes.c_void_p), 1, ctypes.c_int32(n),
        ctypes.c_int32(f), ctypes.c_int(1), ctypes.c_int(2), ctypes.c_int(0),
        ctypes.c_int(-1), b"", ctypes.byref(out_n), nleaf))
    leaf_preds = np.ascontiguousarray(
        np.array(nleaf[: n * 6]).reshape(n, 6), np.int32)
    _check(lib, lib.LGBM_BoosterRefit(
        bst, leaf_preds.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        ctypes.c_int32(n), ctypes.c_int32(6)))

    # shuffle + merge keep model count consistent
    _check(lib, lib.LGBM_BoosterShuffleModels(bst, ctypes.c_int(0),
                                              ctypes.c_int(-1)))
    bst2 = ctypes.c_void_p()
    _check(lib, lib.LGBM_BoosterCreate(
        ds, b"objective=binary num_leaves=7 verbosity=-1",
        ctypes.byref(bst2)))
    for _ in range(2):
        _check(lib, lib.LGBM_BoosterUpdateOneIter(bst2, ctypes.byref(fin)))
    _check(lib, lib.LGBM_BoosterMerge(bst, bst2))
    _check(lib, lib.LGBM_BoosterNumberOfTotalModel(bst, ctypes.byref(total)))
    assert total.value == 8

    # custom objective iteration
    bst3 = ctypes.c_void_p()
    _check(lib, lib.LGBM_BoosterCreate(
        ds, b"objective=custom num_leaves=7 verbosity=-1",
        ctypes.byref(bst3)))
    grad = np.ascontiguousarray(rng.randn(n), np.float32)
    hess = np.ascontiguousarray(np.ones(n), np.float32)
    _check(lib, lib.LGBM_BoosterUpdateOneIterCustom(
        bst3, grad.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        hess.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        ctypes.byref(fin)))
    it = ctypes.c_int()
    _check(lib, lib.LGBM_BoosterGetCurrentIteration(bst3, ctypes.byref(it)))
    assert it.value == 1

    # dataset helpers
    nb = ctypes.c_int()
    _check(lib, lib.LGBM_DatasetGetFeatureNumBin(ds, ctypes.c_int(0),
                                                 ctypes.byref(nb)))
    assert nb.value > 1
    fl = ctypes.c_int()
    ptr = ctypes.c_void_p()
    ftype = ctypes.c_int()
    _check(lib, lib.LGBM_DatasetGetField(
        ds, b"label", ctypes.byref(fl), ctypes.byref(ptr),
        ctypes.byref(ftype)))
    assert fl.value == n and ftype.value == 0
    # a second GetField must not invalidate the first pointer
    w32 = np.ascontiguousarray(np.ones(n), np.float32)
    _check(lib, lib.LGBM_DatasetSetField(
        ds, b"weight", w32.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_int(n), 0))
    wl = ctypes.c_int()
    wptr = ctypes.c_void_p()
    _check(lib, lib.LGBM_DatasetGetField(
        ds, b"weight", ctypes.byref(wl), ctypes.byref(wptr),
        ctypes.byref(ftype)))
    lab = np.ctypeslib.as_array(
        ctypes.cast(ptr, ctypes.POINTER(ctypes.c_float)), shape=(n,))
    np.testing.assert_allclose(lab, y.astype(np.float32))
    idx = np.ascontiguousarray(np.arange(0, n, 2), np.int32)
    sub = ctypes.c_void_p()
    _check(lib, lib.LGBM_DatasetGetSubset(
        ds, idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        ctypes.c_int32(len(idx)), b"", ctypes.byref(sub)))
    nd = ctypes.c_int32()
    _check(lib, lib.LGBM_DatasetGetNumData(sub, ctypes.byref(nd)))
    assert nd.value == len(idx)
    rc = lib.LGBM_DatasetUpdateParamChecking(b"max_bin=255", b"max_bin=63")
    assert rc == -1
    _check(lib, lib.LGBM_DatasetUpdateParamChecking(
        b"max_bin=255", b"learning_rate=0.2"))
    txt = str(tmp_path / "dump.tsv")
    _check(lib, lib.LGBM_DatasetDumpText(ds, txt.encode()))
    assert len(open(txt).readlines()) == n

    # param aliases / threads / sampling
    buf = ctypes.create_string_buffer(1 << 20)
    blen = ctypes.c_int64()
    _check(lib, lib.LGBM_DumpParamAliases(
        ctypes.c_int64(1 << 20), ctypes.byref(blen), buf))
    import json
    aliases = json.loads(buf.value.decode())
    assert "num_leaves" in aliases
    _check(lib, lib.LGBM_SetMaxThreads(4))
    mt = ctypes.c_int()
    _check(lib, lib.LGBM_GetMaxThreads(ctypes.byref(mt)))
    assert mt.value == 4
    sc = ctypes.c_int()
    _check(lib, lib.LGBM_GetSampleCount(
        ctypes.c_int32(10 ** 7), b"bin_construct_sample_cnt=5000",
        ctypes.byref(sc)))
    assert sc.value == 5000
    sidx = (ctypes.c_int32 * 5000)()
    slen = ctypes.c_int32()
    _check(lib, lib.LGBM_SampleIndices(
        ctypes.c_int32(10 ** 7), b"bin_construct_sample_cnt=5000",
        sidx, ctypes.byref(slen)))
    assert slen.value == 5000
    arr = np.array(sidx[:])
    assert (np.diff(arr) > 0).all() and arr.max() < 10 ** 7

    # feature names + validation + loaded params
    name_bufs = [ctypes.create_string_buffer(64) for _ in range(f)]
    names = (ctypes.c_char_p * f)(*[
        ctypes.cast(b, ctypes.c_char_p) for b in name_bufs])
    nn = ctypes.c_int()
    bl = ctypes.c_size_t()
    _check(lib, lib.LGBM_BoosterGetFeatureNames(
        bst, ctypes.c_int(f), ctypes.byref(nn), ctypes.c_size_t(64),
        ctypes.byref(bl), names))
    assert nn.value == f
    _check(lib, lib.LGBM_BoosterValidateFeatureNames(bst, names,
                                                     ctypes.c_int(f)))
    rc = lib.LGBM_BoosterValidateFeatureNames(
        bst, (ctypes.c_char_p * 1)(b"bogus"), ctypes.c_int(1))
    assert rc == -1
    pbuf = ctypes.create_string_buffer(1 << 16)
    plen = ctypes.c_int64()
    _check(lib, lib.LGBM_BoosterGetLoadedParam(
        bst, ctypes.c_int64(1 << 16), ctypes.byref(plen), pbuf))
    assert "num_leaves" in pbuf.value.decode()

    # error report helpers + log callback
    _check(lib, lib.LGBM_SetLastError(b"custom error"))
    assert lib.LGBM_GetLastError().decode() == "custom error"
    seen = []
    CB = ctypes.CFUNCTYPE(None, ctypes.c_char_p)
    cb = CB(lambda m: seen.append(m))
    _check(lib, lib.LGBM_RegisterLogCallback(cb))
    bst4 = ctypes.c_void_p()
    # num_threads triggers a deterministic warning through Log
    _check(lib, lib.LGBM_BoosterCreate(
        ds, b"objective=binary num_leaves=7 verbosity=2 num_threads=4",
        ctypes.byref(bst4)))
    _check(lib, lib.LGBM_BoosterUpdateOneIter(bst4, ctypes.byref(fin)))
    assert seen, "log callback never fired"
    CB0 = ctypes.CFUNCTYPE(None, ctypes.c_char_p)
    lib.LGBM_RegisterLogCallback(ctypes.cast(None, CB0))

    # network facade: single-machine init is a no-op success
    _check(lib, lib.LGBM_NetworkInit(b"", ctypes.c_int(0), ctypes.c_int(0),
                                     ctypes.c_int(1)))
    _check(lib, lib.LGBM_NetworkFree())


def test_capi_predict_csc_and_single_row():
    sp = pytest.importorskip("scipy.sparse")
    lib = _load()
    rng = np.random.RandomState(12)
    n, f = 700, 7
    X = rng.randn(n, f)
    y = (X[:, 0] > 0).astype(np.float64)
    ds = _dataset_from_mat(lib, X, y)
    bst = ctypes.c_void_p()
    _check(lib, lib.LGBM_BoosterCreate(
        ds, b"objective=binary num_leaves=15 verbosity=-1",
        ctypes.byref(bst)))
    fin = ctypes.c_int()
    for _ in range(5):
        _check(lib, lib.LGBM_BoosterUpdateOneIter(bst, ctypes.byref(fin)))
    Xp = np.ascontiguousarray(X[:40], np.float64)
    ref = (ctypes.c_double * 40)()
    out_n = ctypes.c_int64()
    _check(lib, lib.LGBM_BoosterPredictForMat(
        bst, Xp.ctypes.data_as(ctypes.c_void_p), 1, ctypes.c_int32(40),
        ctypes.c_int32(f), ctypes.c_int(1), ctypes.c_int(0), ctypes.c_int(0),
        ctypes.c_int(-1), b"", ctypes.byref(out_n), ref))

    # CSC batch predict
    csc = sp.csc_matrix(Xp)
    ip = np.ascontiguousarray(csc.indptr, np.int32)
    ind = np.ascontiguousarray(csc.indices, np.int32)
    vals = np.ascontiguousarray(csc.data, np.float64)
    out = (ctypes.c_double * 40)()
    _check(lib, lib.LGBM_BoosterPredictForCSC(
        bst, ip.ctypes.data_as(ctypes.c_void_p), 2,
        ind.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        vals.ctypes.data_as(ctypes.c_void_p), 1,
        ctypes.c_int64(len(ip)), ctypes.c_int64(csc.nnz),
        ctypes.c_int64(40), ctypes.c_int(0), ctypes.c_int(0),
        ctypes.c_int(-1), b"", ctypes.byref(out_n), out))
    np.testing.assert_allclose(np.array(out[:]), np.array(ref[:]),
                               rtol=1e-9)

    # single-row variants
    one = ctypes.c_double()
    row = np.ascontiguousarray(Xp[3], np.float64)
    _check(lib, lib.LGBM_BoosterPredictForMatSingleRow(
        bst, row.ctypes.data_as(ctypes.c_void_p), 1, ctypes.c_int(f),
        ctypes.c_int(1), ctypes.c_int(0), ctypes.c_int(0), ctypes.c_int(-1),
        b"", ctypes.byref(out_n), ctypes.byref(one)))
    np.testing.assert_allclose(one.value, ref[3], rtol=1e-9)
    csr = sp.csr_matrix(Xp[3:4])
    rip = np.ascontiguousarray(csr.indptr, np.int32)
    rind = np.ascontiguousarray(csr.indices, np.int32)
    rval = np.ascontiguousarray(csr.data, np.float64)
    _check(lib, lib.LGBM_BoosterPredictForCSRSingleRow(
        bst, rip.ctypes.data_as(ctypes.c_void_p), 2,
        rind.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        rval.ctypes.data_as(ctypes.c_void_p), 1,
        ctypes.c_int64(len(rip)), ctypes.c_int64(csr.nnz),
        ctypes.c_int64(f), ctypes.c_int(0), ctypes.c_int(0),
        ctypes.c_int(-1), b"", ctypes.byref(out_n), ctypes.byref(one)))
    np.testing.assert_allclose(one.value, ref[3], rtol=1e-9)


def test_capi_multiclass_tree_index_convention():
    """tree_idx is iteration-major (it*num_class + k, reference c_api):
    a get/set round trip must address the SAME tree."""
    lib = _load()
    rng = np.random.RandomState(13)
    n, f = 600, 5
    X = rng.randn(n, f)
    y = rng.randint(0, 3, n).astype(np.float64)
    ds = _dataset_from_mat(lib, X, y)
    bst = ctypes.c_void_p()
    _check(lib, lib.LGBM_BoosterCreate(
        ds, b"objective=multiclass num_class=3 num_leaves=7 verbosity=-1",
        ctypes.byref(bst)))
    fin = ctypes.c_int()
    for _ in range(2):
        _check(lib, lib.LGBM_BoosterUpdateOneIter(bst, ctypes.byref(fin)))
    for tree_idx in range(6):
        v = ctypes.c_double()
        _check(lib, lib.LGBM_BoosterGetLeafValue(
            bst, ctypes.c_int(tree_idx), ctypes.c_int(0), ctypes.byref(v)))
        _check(lib, lib.LGBM_BoosterSetLeafValue(
            bst, ctypes.c_int(tree_idx), ctypes.c_int(0),
            ctypes.c_double(v.value + 0.125)))
        v2 = ctypes.c_double()
        _check(lib, lib.LGBM_BoosterGetLeafValue(
            bst, ctypes.c_int(tree_idx), ctypes.c_int(0), ctypes.byref(v2)))
        assert abs(v2.value - v.value - 0.125) < 1e-12, tree_idx


def test_capi_arrow_interface():
    """Arrow C data interface (reference arrow.h + the three LGBM_*Arrow
    entry points): export pyarrow batches to C structs, create a dataset,
    set a field, train, and predict — all through raw Arrow pointers.
    Caller keeps struct ownership (shallow copies with no-op release)."""
    pa = pytest.importorskip("pyarrow")
    lib = _load()
    rng = np.random.RandomState(14)
    n, f = 800, 5
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
    table = pa.table({f"f{j}": X[:, j] for j in range(f)})
    batches = table.to_batches(max_chunksize=300)

    class ArrowArray(ctypes.Structure):
        _fields_ = [("length", ctypes.c_int64),
                    ("null_count", ctypes.c_int64),
                    ("offset", ctypes.c_int64),
                    ("n_buffers", ctypes.c_int64),
                    ("n_children", ctypes.c_int64),
                    ("buffers", ctypes.c_void_p),
                    ("children", ctypes.c_void_p),
                    ("dictionary", ctypes.c_void_p),
                    ("release", ctypes.c_void_p),
                    ("private_data", ctypes.c_void_p)]

    class ArrowSchema(ctypes.Structure):
        _fields_ = [("format", ctypes.c_char_p),
                    ("name", ctypes.c_char_p),
                    ("metadata", ctypes.c_char_p),
                    ("flags", ctypes.c_int64),
                    ("n_children", ctypes.c_int64),
                    ("children", ctypes.c_void_p),
                    ("dictionary", ctypes.c_void_p),
                    ("release", ctypes.c_void_p),
                    ("private_data", ctypes.c_void_p)]

    n_chunks = len(batches)
    chunk_arr = (ArrowArray * n_chunks)()
    schema = ArrowSchema()
    # export schema once and every batch
    batches[0]._export_to_c(ctypes.addressof(chunk_arr[0]),
                            ctypes.addressof(schema))
    for i in range(1, n_chunks):
        batches[i]._export_to_c(ctypes.addressof(chunk_arr[i]))

    ds = ctypes.c_void_p()
    _check(lib, lib.LGBM_DatasetCreateFromArrow(
        ctypes.c_int64(n_chunks), chunk_arr, ctypes.byref(schema),
        b"max_bin=63", ctypes.c_void_p(), ctypes.byref(ds)))
    nd = ctypes.c_int32()
    _check(lib, lib.LGBM_DatasetGetNumData(ds, ctypes.byref(nd)))
    assert nd.value == n

    # label via Arrow
    lab = pa.array(y.astype(np.float32))
    lab_arr = ArrowArray()
    lab_schema = ArrowSchema()
    lab._export_to_c(ctypes.addressof(lab_arr),
                     ctypes.addressof(lab_schema))
    _check(lib, lib.LGBM_DatasetSetFieldFromArrow(
        ds, b"label", ctypes.c_int64(1), ctypes.byref(lab_arr),
        ctypes.byref(lab_schema)))

    bst = ctypes.c_void_p()
    _check(lib, lib.LGBM_BoosterCreate(
        ds, b"objective=binary num_leaves=15 verbosity=-1 max_bin=63",
        ctypes.byref(bst)))
    fin = ctypes.c_int()
    for _ in range(5):
        _check(lib, lib.LGBM_BoosterUpdateOneIter(bst, ctypes.byref(fin)))

    # predict through Arrow, compare against the Mat path
    p_arrow = (ctypes.c_double * n)()
    out_n = ctypes.c_int64()
    chunk_arr2 = (ArrowArray * n_chunks)()
    schema2 = ArrowSchema()
    batches[0]._export_to_c(ctypes.addressof(chunk_arr2[0]),
                            ctypes.addressof(schema2))
    for i in range(1, n_chunks):
        batches[i]._export_to_c(ctypes.addressof(chunk_arr2[i]))
    _check(lib, lib.LGBM_BoosterPredictForArrow(
        bst, ctypes.c_int64(n_chunks), chunk_arr2, ctypes.byref(schema2),
        ctypes.c_int(0), ctypes.c_int(0), ctypes.c_int(-1), b"",
        ctypes.byref(out_n), p_arrow))
    assert out_n.value == n
    Xa = np.ascontiguousarray(X, np.float64)
    p_mat = (ctypes.c_double * n)()
    _check(lib, lib.LGBM_BoosterPredictForMat(
        bst, Xa.ctypes.data_as(ctypes.c_void_p), 1, ctypes.c_int32(n),
        ctypes.c_int32(f), ctypes.c_int(1), ctypes.c_int(0), ctypes.c_int(0),
        ctypes.c_int(-1), b"", ctypes.byref(out_n), p_mat))
    np.testing.assert_allclose(np.array(p_arrow[:]), np.array(p_mat[:]),
                               rtol=1e-9)


def test_capi_serialized_reference_and_mats():
    """ByteBuffer reference serialization (c_api.h:162-215): serialize a
    dataset's bin mappers, rebuild an aligned streaming dataset from the
    buffer in a 'fresh worker', push rows, train — bins align with the
    original.  Plus CreateFromMats and PredictForMats."""
    lib = _load()
    rng = np.random.RandomState(15)
    n, f = 700, 6
    X = rng.randn(n, f)
    y = (X[:, 0] > 0).astype(np.float64)
    ds = _dataset_from_mat(lib, X, y, params=b"max_bin=31")

    buf = ctypes.c_void_p()
    blen = ctypes.c_int32()
    _check(lib, lib.LGBM_DatasetSerializeReferenceToBinary(
        ds, ctypes.byref(buf), ctypes.byref(blen)))
    assert blen.value > 64
    # spot-check GetAt, then read the full buffer byte-by-byte (the
    # reference's consumption pattern for shipping the buffer elsewhere)
    one = ctypes.c_uint8()
    full = bytearray(blen.value)
    for i in range(blen.value):
        _check(lib, lib.LGBM_ByteBufferGetAt(buf, ctypes.c_int32(i),
                                             ctypes.byref(one)))
        full[i] = one.value
    full = bytes(full)
    rc = lib.LGBM_ByteBufferGetAt(buf, ctypes.c_int32(blen.value),
                                  ctypes.byref(one))
    assert rc == -1                      # out-of-range errors, not crashes

    stream = ctypes.c_void_p()
    cbuf = (ctypes.c_char * len(full)).from_buffer_copy(full)
    _check(lib, lib.LGBM_DatasetCreateFromSerializedReference(
        cbuf, ctypes.c_int32(len(full)), ctypes.c_int64(n),
        ctypes.c_int32(1), b"max_bin=31", ctypes.byref(stream)))
    _check(lib, lib.LGBM_DatasetInitStreaming(
        stream, 0, 0, 0, ctypes.c_int32(1), ctypes.c_int32(1),
        ctypes.c_int32(1)))
    Xa = np.ascontiguousarray(X, np.float64)
    lab = np.ascontiguousarray(y, np.float32)
    _check(lib, lib.LGBM_DatasetPushRowsWithMetadata(
        stream, Xa.ctypes.data_as(ctypes.c_void_p), 1, ctypes.c_int32(n),
        ctypes.c_int32(f), ctypes.c_int32(0),
        lab.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), None, None,
        None, ctypes.c_int32(0)))
    _check(lib, lib.LGBM_DatasetMarkFinished(stream))
    nb1 = ctypes.c_int()
    nb2 = ctypes.c_int()
    _check(lib, lib.LGBM_DatasetGetFeatureNumBin(ds, 0, ctypes.byref(nb1)))
    _check(lib, lib.LGBM_DatasetGetFeatureNumBin(stream, 0,
                                                 ctypes.byref(nb2)))
    assert nb1.value == nb2.value
    _check(lib, lib.LGBM_ByteBufferFree(buf))

    # CreateFromMats: two blocks == one matrix
    half = n // 2
    b1 = np.ascontiguousarray(X[:half], np.float64)
    b2 = np.ascontiguousarray(X[half:], np.float64)
    ptrs = (ctypes.c_void_p * 2)(b1.ctypes.data_as(ctypes.c_void_p),
                                 b2.ctypes.data_as(ctypes.c_void_p))
    nrows = (ctypes.c_int32 * 2)(half, n - half)
    majors = (ctypes.c_int * 2)(1, 1)
    dmats = ctypes.c_void_p()
    _check(lib, lib.LGBM_DatasetCreateFromMats(
        ctypes.c_int32(2), ptrs, 1, nrows, ctypes.c_int32(f), majors,
        b"max_bin=31", ctypes.c_void_p(), ctypes.byref(dmats)))
    ndm = ctypes.c_int32()
    _check(lib, lib.LGBM_DatasetGetNumData(dmats, ctypes.byref(ndm)))
    assert ndm.value == n

    # PredictForMats row-pointer batch == contiguous batch
    bst = ctypes.c_void_p()
    _check(lib, lib.LGBM_BoosterCreate(
        ds, b"objective=binary num_leaves=7 verbosity=-1 max_bin=31",
        ctypes.byref(bst)))
    fin = ctypes.c_int()
    for _ in range(3):
        _check(lib, lib.LGBM_BoosterUpdateOneIter(bst, ctypes.byref(fin)))
    rows = np.ascontiguousarray(X[:20], np.float64)
    rptrs = (ctypes.c_void_p * 20)(*[
        rows[i:i + 1].ctypes.data_as(ctypes.c_void_p) for i in range(20)])
    outm = (ctypes.c_double * 20)()
    out_n = ctypes.c_int64()
    _check(lib, lib.LGBM_BoosterPredictForMats(
        bst, rptrs, 1, ctypes.c_int32(20), ctypes.c_int32(f),
        ctypes.c_int(0), ctypes.c_int(0), ctypes.c_int(-1), b"",
        ctypes.byref(out_n), outm))
    ref = (ctypes.c_double * 20)()
    _check(lib, lib.LGBM_BoosterPredictForMat(
        bst, rows.ctypes.data_as(ctypes.c_void_p), 1, ctypes.c_int32(20),
        ctypes.c_int32(f), ctypes.c_int(1), ctypes.c_int(0), ctypes.c_int(0),
        ctypes.c_int(-1), b"", ctypes.byref(out_n), ref))
    np.testing.assert_allclose(np.array(outm[:]), np.array(ref[:]),
                               rtol=1e-9)


def test_capi_multiclass_custom_objective_layout():
    """LGBM_BoosterUpdateOneIterCustom and LGBM_BoosterGetPredict use the
    reference's CLASS-MAJOR buffers (grad[class*num_data+row], c_api.h;
    GBDT::GetPredictAt gbdt.cpp:665).  Feeding class-major softmax
    gradients through the C API must reproduce the built-in multiclass
    objective — a row-major mixup scrambles classes and diverges wildly
    (ADVICE r4 medium #1)."""
    lib = _load()
    rng = np.random.RandomState(7)
    n, f, k = 600, 5, 3
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.7 * X[:, 1] > 0).astype(int) + (X[:, 2] > 0.5)

    params = (b"objective=multiclass num_class=3 num_leaves=7 "
             b"verbosity=-1 boost_from_average=false")
    ds_a = _dataset_from_mat(lib, X, y)
    bst_a = ctypes.c_void_p()
    _check(lib, lib.LGBM_BoosterCreate(ds_a, params, ctypes.byref(bst_a)))
    fin = ctypes.c_int()
    iters = 4
    for _ in range(iters):
        _check(lib, lib.LGBM_BoosterUpdateOneIter(bst_a, ctypes.byref(fin)))

    ds_b = _dataset_from_mat(lib, X, y)
    bst_b = ctypes.c_void_p()
    _check(lib, lib.LGBM_BoosterCreate(
        ds_b, b"objective=custom num_class=3 num_leaves=7 verbosity=-1 "
        b"boost_from_average=false",
        ctypes.byref(bst_b)))
    onehot = np.eye(k, dtype=np.float64)[y]
    out_len = ctypes.c_int64()
    scores = (ctypes.c_double * (n * k))()
    for _ in range(iters):
        # class-major raw scores of the CURRENT model state
        _check(lib, lib.LGBM_BoosterGetPredict(
            bst_b, ctypes.c_int(0), ctypes.byref(out_len), scores))
        assert out_len.value == n * k
        s = np.array(scores[:]).reshape(k, n).T          # back to (n, k)
        e = np.exp(s - s.max(axis=1, keepdims=True))
        p = e / e.sum(axis=1, keepdims=True)
        grad = np.ascontiguousarray((p - onehot).T, np.float32)  # (k, n)
        # reference softmax hessian factor k/(k-1) (multiclass_objective.hpp:31)
        hess = np.ascontiguousarray(
            (k / (k - 1.0) * p * (1.0 - p)).T, np.float32)
        _check(lib, lib.LGBM_BoosterUpdateOneIterCustom(
            bst_b,
            grad.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            hess.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            ctypes.byref(fin)))

    def _raw_predict(bst):
        out = (ctypes.c_double * (n * k))()
        m = ctypes.c_int64()
        _check(lib, lib.LGBM_BoosterPredictForMat(
            bst, np.ascontiguousarray(X, np.float32).ctypes.data_as(
                ctypes.c_void_p), 0, ctypes.c_int32(n), ctypes.c_int32(f),
            ctypes.c_int(1), ctypes.c_int(1), ctypes.c_int(0),
            ctypes.c_int(-1), b"", ctypes.byref(m), out))
        return np.array(out[: n * k]).reshape(n, k)

    a, b = _raw_predict(bst_a), _raw_predict(bst_b)
    np.testing.assert_allclose(b, a, rtol=2e-3, atol=2e-3)


def test_capi_sparse_predict_output():
    """LGBM_BoosterPredictSparseOutput returns num_class stacked CSR
    matrices of non-zero SHAP contributions with one shared data buffer
    (reference Booster::PredictSparseCSR, c_api.cpp); parity against the
    dense contrib path, then LGBM_BoosterFreePredictSparse releases it."""
    import scipy.sparse as sp

    lib = _load()
    rng = np.random.RandomState(3)
    n, f = 300, 6
    X = rng.randn(n, f)
    X[rng.rand(n, f) < 0.3] = 0.0
    y = (X[:, 0] + X[:, 1] > 0).astype(float)
    ds = _dataset_from_mat(lib, X, y)
    bst = ctypes.c_void_p()
    _check(lib, lib.LGBM_BoosterCreate(
        ds, b"objective=binary num_leaves=15 verbosity=-1",
        ctypes.byref(bst)))
    fin = ctypes.c_int()
    for _ in range(5):
        _check(lib, lib.LGBM_BoosterUpdateOneIter(bst, ctypes.byref(fin)))

    Xcsr = sp.csr_matrix(X)
    indptr = np.ascontiguousarray(Xcsr.indptr, np.int32)
    indices = np.ascontiguousarray(Xcsr.indices, np.int32)
    data = np.ascontiguousarray(Xcsr.data, np.float64)

    out_len = (ctypes.c_int64 * 2)()
    out_indptr = ctypes.c_void_p()
    out_indices = ctypes.POINTER(ctypes.c_int32)()
    out_data = ctypes.c_void_p()
    _check(lib, lib.LGBM_BoosterPredictSparseOutput(
        bst, indptr.ctypes.data_as(ctypes.c_void_p), ctypes.c_int(2),
        indices.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        data.ctypes.data_as(ctypes.c_void_p), ctypes.c_int(1),
        ctypes.c_int64(len(indptr)), ctypes.c_int64(len(data)),
        ctypes.c_int64(f), ctypes.c_int(3),  # C_API_PREDICT_CONTRIB
        ctypes.c_int(0), ctypes.c_int(-1), b"", ctypes.c_int(0),  # CSR
        out_len, ctypes.byref(out_indptr), ctypes.byref(out_indices),
        ctypes.byref(out_data)))
    nnz, ip_len = out_len[0], out_len[1]
    assert ip_len == n + 1          # one class -> one stacked matrix
    got_ip = np.ctypeslib.as_array(
        ctypes.cast(out_indptr, ctypes.POINTER(ctypes.c_int32)),
        shape=(ip_len,)).copy()
    got_ix = np.ctypeslib.as_array(out_indices, shape=(max(nnz, 1),))[
        :nnz].copy()
    got_dt = np.ctypeslib.as_array(
        ctypes.cast(out_data, ctypes.POINTER(ctypes.c_double)),
        shape=(max(nnz, 1),))[:nnz].copy()
    sparse_contrib = sp.csr_matrix((got_dt, got_ix, got_ip),
                                   shape=(n, f + 1)).toarray()

    dense = (ctypes.c_double * (n * (f + 1)))()
    m = ctypes.c_int64()
    _check(lib, lib.LGBM_BoosterPredictForCSR(
        bst, indptr.ctypes.data_as(ctypes.c_void_p), ctypes.c_int(2),
        indices.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        data.ctypes.data_as(ctypes.c_void_p), ctypes.c_int(1),
        ctypes.c_int64(len(indptr)), ctypes.c_int64(len(data)),
        ctypes.c_int64(f), ctypes.c_int(3), ctypes.c_int(0),
        ctypes.c_int(-1), b"", ctypes.byref(m), dense))
    np.testing.assert_allclose(
        sparse_contrib, np.array(dense[:]).reshape(n, f + 1), rtol=1e-9)
    _check(lib, lib.LGBM_BoosterFreePredictSparse(
        out_indptr, out_indices, out_data, ctypes.c_int(2),
        ctypes.c_int(1)))


def test_capi_csr_single_row_fast():
    """FastConfig pair for CSR rows (reference c_api.h:1162-1202): per-row
    predictions must match the batch CSR path."""
    import scipy.sparse as sp

    lib = _load()
    rng = np.random.RandomState(5)
    n, f = 400, 5
    X = rng.randn(n, f)
    X[rng.rand(n, f) < 0.4] = 0.0
    y = (X[:, 0] - X[:, 2] > 0).astype(float)
    ds = _dataset_from_mat(lib, X, y)
    bst = ctypes.c_void_p()
    _check(lib, lib.LGBM_BoosterCreate(
        ds, b"objective=binary num_leaves=15 verbosity=-1",
        ctypes.byref(bst)))
    fin = ctypes.c_int()
    for _ in range(5):
        _check(lib, lib.LGBM_BoosterUpdateOneIter(bst, ctypes.byref(fin)))

    fast = ctypes.c_void_p()
    _check(lib, lib.LGBM_BoosterPredictForCSRSingleRowFastInit(
        bst, ctypes.c_int(0), ctypes.c_int(0), ctypes.c_int(-1),
        ctypes.c_int(1), ctypes.c_int64(f), b"", ctypes.byref(fast)))

    batch = np.zeros(n)
    outv = ctypes.c_double()
    out_n = ctypes.c_int64()
    full = (ctypes.c_double * n)()
    Xcsr = sp.csr_matrix(X)
    _check(lib, lib.LGBM_BoosterPredictForCSR(
        bst,
        np.ascontiguousarray(Xcsr.indptr, np.int32).ctypes.data_as(
            ctypes.c_void_p), ctypes.c_int(2),
        np.ascontiguousarray(Xcsr.indices, np.int32).ctypes.data_as(
            ctypes.POINTER(ctypes.c_int32)),
        np.ascontiguousarray(Xcsr.data, np.float64).ctypes.data_as(
            ctypes.c_void_p), ctypes.c_int(1),
        ctypes.c_int64(n + 1), ctypes.c_int64(Xcsr.nnz), ctypes.c_int64(f),
        ctypes.c_int(0), ctypes.c_int(0), ctypes.c_int(-1), b"",
        ctypes.byref(out_n), full))
    for i in range(0, n, 37):
        row = sp.csr_matrix(X[i:i + 1])
        rp = np.ascontiguousarray(row.indptr, np.int32)
        ri = np.ascontiguousarray(row.indices, np.int32)
        rd = np.ascontiguousarray(row.data, np.float64)
        if row.nnz == 0:
            ri = np.zeros(1, np.int32)
            rd = np.zeros(1, np.float64)
        _check(lib, lib.LGBM_BoosterPredictForCSRSingleRowFast(
            fast, rp.ctypes.data_as(ctypes.c_void_p), ctypes.c_int(2),
            ri.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            rd.ctypes.data_as(ctypes.c_void_p), ctypes.c_int64(2),
            ctypes.c_int64(row.nnz), ctypes.byref(out_n),
            ctypes.byref(outv)))
        batch[i] = outv.value
        # fast path bins through baked f32 LUTs; 1e-6 covers the rounding
        np.testing.assert_allclose(outv.value, full[i], rtol=1e-6)
    _check(lib, lib.LGBM_FastConfigFree(fast))


def test_capi_dataset_create_from_csr_func(tmp_path):
    """LGBM_DatasetCreateFromCSRFunc consumes a C++ row callback
    (std::function pointer, the SynapseML seam — reference c_api.h:363);
    driven here through a small compiled helper."""
    import subprocess
    import sys
    import sysconfig

    helper_src = tmp_path / "rowfn.cpp"
    helper_src.write_text(r"""
    #include <functional>
    #include <utility>
    #include <vector>
    #include <cmath>
    using RowFn = std::function<void(int, std::vector<std::pair<int, double>>&)>;
    static RowFn g_fn = [](int i, std::vector<std::pair<int, double>>& ret) {
      ret.clear();
      ret.emplace_back(i % 4, std::sin(i * 0.7) + 1.5);
      if (i % 3 == 0) ret.emplace_back(4, 1.0);
    };
    extern "C" void* make_row_fn() { return &g_fn; }
    """)
    so = tmp_path / "rowfn.so"
    subprocess.run(["g++", "-O1", "-shared", "-fPIC", str(helper_src),
                    "-o", str(so)], check=True)
    helper = ctypes.CDLL(str(so))
    helper.make_row_fn.restype = ctypes.c_void_p

    lib = _load()
    n, f = 600, 5
    ds = ctypes.c_void_p()
    _check(lib, lib.LGBM_DatasetCreateFromCSRFunc(
        ctypes.c_void_p(helper.make_row_fn()), ctypes.c_int(n),
        ctypes.c_int64(f), b"min_data_in_bin=1", ctypes.c_void_p(),
        ctypes.byref(ds)))
    nd, nf = ctypes.c_int32(), ctypes.c_int32()
    _check(lib, lib.LGBM_DatasetGetNumData(ds, ctypes.byref(nd)))
    _check(lib, lib.LGBM_DatasetGetNumFeature(ds, ctypes.byref(nf)))
    assert (nd.value, nf.value) == (n, f)
    # label + one boosting iteration proves the dataset is usable
    y = np.ascontiguousarray((np.arange(n) % 4 < 2).astype(np.float32))
    _check(lib, lib.LGBM_DatasetSetField(
        ds, b"label", y.ctypes.data_as(ctypes.c_void_p), ctypes.c_int(n),
        0))
    bst = ctypes.c_void_p()
    _check(lib, lib.LGBM_BoosterCreate(
        ds, b"objective=binary num_leaves=7 verbosity=-1",
        ctypes.byref(bst)))
    fin = ctypes.c_int()
    _check(lib, lib.LGBM_BoosterUpdateOneIter(bst, ctypes.byref(fin)))


def test_capi_network_init_with_functions():
    """LGBM_NetworkInitWithFunctions (reference c_api.cpp:2773, the
    SynapseML injection seam) installs external reduce-scatter/allgather
    C functions as the collectives-facade transport; a training run with
    the backend installed keeps working, and the facade routes through
    the injected functions until LGBM_NetworkFree."""
    import jax.numpy as jnp

    import lightgbm_tpu as lgb
    import lightgbm_tpu.parallel.collectives as C
    from lightgbm_tpu.parallel.mesh import make_mesh

    lib = _load()
    calls = []
    world = 2

    AG_T = ctypes.CFUNCTYPE(
        None, ctypes.c_void_p, ctypes.c_int32,
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ctypes.c_int, ctypes.c_void_p, ctypes.c_int32)
    RS_T = ctypes.CFUNCTYPE(
        None, ctypes.c_void_p, ctypes.c_int32, ctypes.c_int,
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ctypes.c_int, ctypes.c_void_p, ctypes.c_int32, ctypes.c_void_p)

    def fake_allgather(inp, in_size, starts, lens, nblock, out, out_size):
        # single-process fake: every "rank" contributes the same block
        calls.append("allgather")
        blk = ctypes.string_at(inp, in_size)
        buf = (ctypes.c_char * out_size).from_address(out)
        for b in range(nblock):
            buf[starts[b]:starts[b] + lens[b]] = blk[:lens[b]]

    def fake_reduce_scatter(inp, in_size, type_size, starts, lens, nblock,
                            out, out_size, reducer):
        # world identical contributions -> own block times world
        calls.append("reduce_scatter")
        own = np.frombuffer(ctypes.string_at(inp, lens[0]), np.float32)
        res = (own * world).astype(np.float32).tobytes()
        ctypes.memmove(out, res, min(out_size, len(res)))

    ag = AG_T(fake_allgather)
    rs = RS_T(fake_reduce_scatter)
    _check(lib, lib.LGBM_NetworkInitWithFunctions(
        ctypes.c_int(world), ctypes.c_int(0),
        ctypes.cast(rs, ctypes.c_void_p), ctypes.cast(ag, ctypes.c_void_p)))
    try:
        mesh = make_mesh()
        v = jnp.ones(4)
        s = np.asarray(C.global_sum(v, mesh))
        # fake allgather replicates this rank's contribution world times,
        # so the backend's sum over ranks doubles each element
        np.testing.assert_allclose(s, world * np.ones(4))
        hist = jnp.arange(8 * 4 * 3, dtype=jnp.float32).reshape(8, 4, 3)
        red = np.asarray(C.histogram_reduce_scatter(hist, mesh))
        # the single-process fakes: reduce_scatter returns own block * world,
        # allgather replicates this rank's block into every slot
        expect = np.tile(np.asarray(hist[:4]) * world, (world, 1, 1))
        np.testing.assert_allclose(red, expect)
        assert "allgather" in calls and "reduce_scatter" in calls
        # training still works with the backend installed (the in-jit
        # grower collectives are XLA's and unaffected by design)
        rng = np.random.RandomState(0)
        X = rng.randn(500, 4)
        y = (X[:, 0] > 0).astype(float)
        bst = lgb.train({"objective": "binary", "num_leaves": 7,
                         "verbosity": -1}, lgb.Dataset(X, label=y), 3)
        assert bst.num_trees() == 3
    finally:
        _check(lib, lib.LGBM_NetworkFree())
    assert C._comm_backend is None


def test_capi_sparse_predict_output_csc():
    """CSC matrix_type: input is column-compressed and the output is a CSC
    matrix over the (num_data, num_feature+1) contribution block — col_ptr
    of length ncols_out+1 per class (reference Booster::PredictSparseCSC)."""
    import scipy.sparse as sp

    lib = _load()
    rng = np.random.RandomState(11)
    n, f = 250, 5
    X = rng.randn(n, f)
    X[rng.rand(n, f) < 0.35] = 0.0
    y = (X[:, 0] - X[:, 1] > 0).astype(float)
    ds = _dataset_from_mat(lib, X, y)
    bst = ctypes.c_void_p()
    _check(lib, lib.LGBM_BoosterCreate(
        ds, b"objective=binary num_leaves=7 verbosity=-1", ctypes.byref(bst)))
    fin = ctypes.c_int()
    for _ in range(4):
        _check(lib, lib.LGBM_BoosterUpdateOneIter(bst, ctypes.byref(fin)))

    Xcsc = sp.csc_matrix(X)
    col_ptr = np.ascontiguousarray(Xcsc.indptr, np.int32)
    indices = np.ascontiguousarray(Xcsc.indices, np.int32)
    data = np.ascontiguousarray(Xcsc.data, np.float64)
    out_len = (ctypes.c_int64 * 2)()
    oip = ctypes.c_void_p()
    oix = ctypes.POINTER(ctypes.c_int32)()
    odt = ctypes.c_void_p()
    _check(lib, lib.LGBM_BoosterPredictSparseOutput(
        bst, col_ptr.ctypes.data_as(ctypes.c_void_p), ctypes.c_int(2),
        indices.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        data.ctypes.data_as(ctypes.c_void_p), ctypes.c_int(1),
        ctypes.c_int64(len(col_ptr)), ctypes.c_int64(len(data)),
        ctypes.c_int64(n),               # CSC: num rows
        ctypes.c_int(3), ctypes.c_int(0), ctypes.c_int(-1), b"",
        ctypes.c_int(1),                 # C_API_MATRIX_TYPE_CSC
        out_len, ctypes.byref(oip), ctypes.byref(oix), ctypes.byref(odt)))
    nnz, ip_len = out_len[0], out_len[1]
    assert ip_len == f + 2               # (ncols_out + 1) per class
    got_ip = np.ctypeslib.as_array(
        ctypes.cast(oip, ctypes.POINTER(ctypes.c_int32)),
        shape=(ip_len,)).copy()
    got_ix = np.ctypeslib.as_array(oix, shape=(max(nnz, 1),))[:nnz].copy()
    got_dt = np.ctypeslib.as_array(
        ctypes.cast(odt, ctypes.POINTER(ctypes.c_double)),
        shape=(max(nnz, 1),))[:nnz].copy()
    contrib_csc = sp.csc_matrix((got_dt, got_ix, got_ip),
                                shape=(n, f + 1)).toarray()
    _check(lib, lib.LGBM_BoosterFreePredictSparse(
        oip, oix, odt, ctypes.c_int(2), ctypes.c_int(1)))

    # parity vs the dense contrib path on the same rows
    dense = (ctypes.c_double * (n * (f + 1)))()
    m = ctypes.c_int64()
    X32 = np.ascontiguousarray(X, np.float32)
    _check(lib, lib.LGBM_BoosterPredictForMat(
        bst, X32.ctypes.data_as(ctypes.c_void_p), 0, ctypes.c_int32(n),
        ctypes.c_int32(f), ctypes.c_int(1), ctypes.c_int(3), ctypes.c_int(0),
        ctypes.c_int(-1), b"", ctypes.byref(m), dense))
    np.testing.assert_allclose(
        contrib_csc, np.array(dense[:]).reshape(n, f + 1), rtol=1e-9)
