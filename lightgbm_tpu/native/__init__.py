"""Native host runtime: ctypes bindings for the C++ library.

The C++ side (``csrc/native.cpp``) provides the host components that are C++
in the reference — text data loading (``src/io/parser.cpp``), binning
(``src/io/bin.cpp``), and batch tree traversal (``src/io/tree.cpp``).  The
library is compiled on first use with ``g++`` and cached next to the sources
under a name that carries the source's hash;
every entry point has a pure-NumPy fallback so the package works without a
toolchain (``available()`` reports which path is active).
"""

from __future__ import annotations

import ctypes
import os
import threading
from typing import Optional, Tuple

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "csrc", "native.cpp")

_lock = threading.Lock()
_lib = None
_lib_tried = False

_i64 = ctypes.c_int64
_i32 = ctypes.c_int32
_f64 = ctypes.c_double


def _build() -> Optional[str]:
    """Compile csrc/native.cpp -> _native-<source hash>.so (utils/sobuild)."""
    from ..utils.sobuild import cached_library
    return cached_library(
        _SRC, "_native",
        lambda out: ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-o",
                     out, _SRC])


def _load():
    global _lib, _lib_tried
    with _lock:
        if _lib_tried:
            return _lib
        _lib_tried = True
        if os.environ.get("LIGHTGBM_TPU_NO_NATIVE"):
            return None
        path = _build()
        if path is None:
            return None
        try:
            lib = ctypes.CDLL(path)
        except OSError:
            return None
        u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
        u16p = np.ctypeslib.ndpointer(np.uint16, flags="C_CONTIGUOUS")
        u32p = np.ctypeslib.ndpointer(np.uint32, flags="C_CONTIGUOUS")
        i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
        i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
        f64p = np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS")

        lib.ltpu_version.restype = ctypes.c_int
        if lib.ltpu_version() != 2:
            return None
        lib.ltpu_parse_file.restype = ctypes.c_void_p
        lib.ltpu_parse_file.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_char_p, ctypes.c_int,
            ctypes.POINTER(_i64), ctypes.POINTER(_i64), ctypes.c_char_p,
            ctypes.c_int]
        lib.ltpu_parse_get.argtypes = [ctypes.c_void_p, f64p, f64p]
        lib.ltpu_parse_free.argtypes = [ctypes.c_void_p]
        lib.ltpu_find_boundaries.restype = ctypes.c_int
        lib.ltpu_find_boundaries.argtypes = [
            f64p, i64p, _i64, ctypes.c_int, _i64, ctypes.c_int, f64p]
        lib.ltpu_unique_counts.restype = _i64
        lib.ltpu_unique_counts.argtypes = [f64p, _i64, f64p, i64p]
        lib.ltpu_value_to_bin.argtypes = [
            f64p, _i64, f64p, ctypes.c_int, ctypes.c_int, ctypes.c_int, i32p]
        lib.ltpu_bin_matrix.argtypes = [
            f64p, _i64, _i64, f64p, _i64, i32p, i32p, u8p, u16p]
        lib.ltpu_predict_bins.argtypes = [
            u16p, _i64, _i64, i32p, ctypes.c_int, i64p, i64p, i32p, i32p,
            u8p, u8p, u32p, ctypes.c_int, i32p, i32p, f64p, f64p]
        lib.ltpu_predict_leaf_index.argtypes = [
            u16p, _i64, _i64, i32p, _i64, i32p, i32p, u8p, u8p, u32p,
            ctypes.c_int, i32p, i32p, i32p]
        lib.ltpu_tree_shap.argtypes = [
            u16p, _i64, _i64, i32p, ctypes.c_int, i64p, i64p, i32p, i32p,
            u8p, u8p, u32p, ctypes.c_int, i32p, i32p, f64p, f64p, f64p,
            f64p]
        _lib = lib
        return _lib


def available() -> bool:
    """True when the compiled native library is loaded (vs NumPy fallback)."""
    return _load() is not None


# ------------------------------------------------------------------ data loader

def parse_file(path: str, header: bool = False, label_column: str = "",
               num_features: int = 0
               ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Parse CSV/TSV/LibSVM -> (X float64 (n,f), y float64 (n,)).

    Returns None when the native library is unavailable (caller falls back to
    the Python parser).  Raises ValueError on malformed files.
    """
    lib = _load()
    if lib is None:
        return None
    nrows = _i64()
    ncols = _i64()
    err = ctypes.create_string_buffer(512)
    h = lib.ltpu_parse_file(path.encode(), int(header),
                            (label_column or "").encode(), int(num_features),
                            ctypes.byref(nrows), ctypes.byref(ncols), err, 512)
    if not h:
        raise ValueError(err.value.decode() or "native parse failed")
    try:
        X = np.empty((nrows.value, ncols.value), np.float64)
        y = np.empty(nrows.value, np.float64)
        lib.ltpu_parse_get(ctypes.c_void_p(h), X, y)
    finally:
        lib.ltpu_parse_free(ctypes.c_void_p(h))
    return X, y


# ---------------------------------------------------------------------- binning

def find_boundaries(distinct: np.ndarray, counts: np.ndarray, max_bins: int,
                    total_cnt: int, min_data_in_bin: int
                    ) -> Optional[np.ndarray]:
    lib = _load()
    if lib is None:
        return None
    distinct = np.ascontiguousarray(distinct, np.float64)
    counts = np.ascontiguousarray(counts, np.int64)
    out = np.empty(max(max_bins, 1), np.float64)
    n = lib.ltpu_find_boundaries(distinct, counts, len(distinct), max_bins,
                                 int(total_cnt), int(min_data_in_bin), out)
    return out[:n].copy()


def unique_counts(values: np.ndarray) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    lib = _load()
    if lib is None:
        return None
    v = np.ascontiguousarray(values, np.float64)
    dist = np.empty(len(v) if len(v) else 1, np.float64)
    cnt = np.empty(len(v) if len(v) else 1, np.int64)
    m = lib.ltpu_unique_counts(v, len(v), dist, cnt)
    return dist[:m].copy(), cnt[:m].copy()


def value_to_bin(values: np.ndarray, upper_bounds: np.ndarray,
                 n_value_bins: int, nan_bin: int,
                 zero_as_missing: bool) -> Optional[np.ndarray]:
    lib = _load()
    if lib is None:
        return None
    v = np.ascontiguousarray(values, np.float64)
    ub = np.ascontiguousarray(upper_bounds, np.float64)
    out = np.empty(len(v), np.int32)
    lib.ltpu_value_to_bin(v, len(v), ub, int(n_value_bins), int(nan_bin),
                          int(zero_as_missing), out)
    return out


def bin_matrix(X: np.ndarray, upper_bounds: np.ndarray,
               n_value_bins: np.ndarray, nan_bins: np.ndarray,
               zero_as_missing: np.ndarray) -> Optional[np.ndarray]:
    """Bin all (numerical) columns of X at once. Shapes:
    X (n,f) f64; upper_bounds (f,maxb) f64; rest (f,)."""
    lib = _load()
    if lib is None:
        return None
    X = np.ascontiguousarray(X, np.float64)
    n, f = X.shape
    ub = np.ascontiguousarray(upper_bounds, np.float64)
    out = np.empty((n, f), np.uint16)
    lib.ltpu_bin_matrix(X, n, f, ub, ub.shape[1],
                        np.ascontiguousarray(n_value_bins, np.int32),
                        np.ascontiguousarray(nan_bins, np.int32),
                        np.ascontiguousarray(zero_as_missing, np.uint8), out)
    return out


# ------------------------------------------------------------------- prediction

def pack_cat_masks(cat_mask: np.ndarray) -> np.ndarray:
    """(M, B) bool -> (M, ceil(B/32)) u32 bitset."""
    m, b = cat_mask.shape
    words = max((b + 31) // 32, 1)
    padded = np.zeros((m, words * 32), bool)
    padded[:, :b] = cat_mask
    bits = padded.reshape(m, words, 32)
    weights = (1 << np.arange(32, dtype=np.uint64)).astype(np.uint64)
    return (bits.astype(np.uint64) * weights).sum(axis=2).astype(np.uint32)


def _flatten_trees(trees, with_counts=False):
    """Concatenated-tree layout shared by ltpu_predict_bins/ltpu_tree_shap:
    node_offsets/leaf_offsets delimit each tree's node/leaf ranges; cat masks
    are packed to a common word width.  ``with_counts`` adds the
    leaf_count/internal_count arrays only TreeSHAP needs."""
    node_off, leaf_off = [0], [0]
    sf, sb, dl, ic, lc, rc, lv, lcnt, icnt, masks = \
        [], [], [], [], [], [], [], [], [], []
    max_b = 1
    for t in trees:
        max_b = max(max_b, t.cat_mask.shape[1] if t.cat_mask.size else 1)
    words = max((max_b + 31) // 32, 1)
    for t in trees:
        m = t.num_splits()
        node_off.append(node_off[-1] + m)
        nl = max(t.num_leaves, 1)
        leaf_off.append(leaf_off[-1] + nl)
        sf.append(t.split_feature[:m])
        sb.append(t.split_bin[:m])
        dl.append(t.default_left[:m])
        ic.append(t.is_cat[:m])
        lc.append(t.left_child[:m])
        rc.append(t.right_child[:m])
        lv.append(t.leaf_value[:nl] if len(t.leaf_value) else np.zeros(1))
        if with_counts:
            lcnt.append(t.leaf_count[:nl] if len(t.leaf_count)
                        else np.zeros(1))
            icnt.append(t.internal_count[:m])
        if m:
            cm = np.zeros((m, max_b), bool)
            cm[:, :t.cat_mask.shape[1]] = t.cat_mask[:m]
            masks.append(pack_cat_masks(cm))
        else:
            masks.append(np.zeros((0, words), np.uint32))
    cat = (np.concatenate(masks, axis=0) if masks
           else np.zeros((0, words), np.uint32))

    def _f64cat(parts):
        return np.ascontiguousarray(
            np.concatenate(parts) if parts else np.zeros(1), np.float64)

    out = {
        "node_off": np.asarray(node_off, np.int64),
        "leaf_off": np.asarray(leaf_off, np.int64),
        "sf": _cat_i32(sf), "sb": _cat_i32(sb),
        "dl": _cat_u8(dl), "ic": _cat_u8(ic),
        "cat": np.ascontiguousarray(cat), "words": words,
        "lc": _cat_i32(lc), "rc": _cat_i32(rc),
        "lv": _f64cat(lv),
    }
    if with_counts:
        out["lcnt"] = _f64cat(lcnt)
        out["icnt"] = _f64cat(icnt)
    return out


def make_bins_predictor(trees, nan_bins: np.ndarray):
    """Bind a tree list ONCE and return ``run(bins, out) -> out``.

    The serving fast path (C API FastConfig, reference c_api.h:1332): the
    per-call cost of :func:`predict_bins` is dominated by re-flattening the
    tree pack; this pre-marshals it so a single-row call is just the native
    traversal.  Returns None when the native library is unavailable."""
    lib = _load()
    if lib is None:
        return None
    t = _flatten_trees(trees)
    nan_bins = np.ascontiguousarray(nan_bins, np.int32)
    ntrees = len(trees)

    def run(bins: np.ndarray, out: np.ndarray) -> np.ndarray:
        bins = np.ascontiguousarray(bins, np.uint16)
        n, f = bins.shape
        lib.ltpu_predict_bins(
            bins, n, f, nan_bins, ntrees,
            t["node_off"], t["leaf_off"], t["sf"], t["sb"], t["dl"],
            t["ic"], t["cat"], t["words"], t["lc"], t["rc"], t["lv"], out)
        return out

    return run


def predict_bins(bins: np.ndarray, nan_bins: np.ndarray, trees,
                 out: Optional[np.ndarray] = None) -> Optional[np.ndarray]:
    """Sum of tree outputs over binned rows. ``trees``: list of Tree
    (models.tree.Tree) objects. Accumulates into ``out`` (zeros if None)."""
    lib = _load()
    if lib is None:
        return None
    bins = np.ascontiguousarray(bins, np.uint16)
    n, f = bins.shape
    t = _flatten_trees(trees)
    if out is None:
        out = np.zeros(n, np.float64)
    lib.ltpu_predict_bins(
        bins, n, f, np.ascontiguousarray(nan_bins, np.int32), len(trees),
        t["node_off"], t["leaf_off"], t["sf"], t["sb"], t["dl"], t["ic"],
        t["cat"], t["words"], t["lc"], t["rc"], t["lv"], out)
    return out


def tree_shap(bins: np.ndarray, nan_bins: np.ndarray,
              trees) -> Optional[np.ndarray]:
    """Path-dependent TreeSHAP over binned rows for a tree list; returns
    (n, f+1) f64 contributions (expected-value column left zero — the caller
    adds per-tree expected values).  Reference ``Tree::PredictContrib``
    (``src/io/tree.cpp``)."""
    lib = _load()
    if lib is None:
        return None
    bins = np.ascontiguousarray(bins, np.uint16)
    n, f = bins.shape
    t = _flatten_trees(trees, with_counts=True)
    out = np.zeros((n, f + 1), np.float64)
    lib.ltpu_tree_shap(
        bins, n, f, np.ascontiguousarray(nan_bins, np.int32), len(trees),
        t["node_off"], t["leaf_off"], t["sf"], t["sb"], t["dl"], t["ic"],
        t["cat"], t["words"], t["lc"], t["rc"], t["lv"], t["lcnt"],
        t["icnt"], out)
    return out


def predict_leaf_index(bins: np.ndarray, nan_bins: np.ndarray,
                       tree) -> Optional[np.ndarray]:
    lib = _load()
    if lib is None:
        return None
    bins = np.ascontiguousarray(bins, np.uint16)
    n, f = bins.shape
    m = tree.num_splits()
    out = np.empty(n, np.int32)
    cm = pack_cat_masks(tree.cat_mask[:m] if m else np.zeros((0, 1), bool))
    lib.ltpu_predict_leaf_index(
        bins, n, f, np.ascontiguousarray(nan_bins, np.int32), m,
        np.ascontiguousarray(tree.split_feature[:m], np.int32),
        np.ascontiguousarray(tree.split_bin[:m], np.int32),
        np.ascontiguousarray(tree.default_left[:m], np.uint8),
        np.ascontiguousarray(tree.is_cat[:m], np.uint8),
        np.ascontiguousarray(cm), cm.shape[1] if cm.size else 1,
        np.ascontiguousarray(tree.left_child[:m], np.int32),
        np.ascontiguousarray(tree.right_child[:m], np.int32), out)
    return out


def _cat_i32(parts):
    return np.ascontiguousarray(
        np.concatenate(parts) if parts else np.zeros(0), np.int32)


def _cat_u8(parts):
    return np.ascontiguousarray(
        np.concatenate(parts) if parts else np.zeros(0), np.uint8)
