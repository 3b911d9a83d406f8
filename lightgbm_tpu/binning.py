"""Feature discretization: value -> bin mapping.

TPU-native re-design of the reference ``BinMapper`` (``include/LightGBM/bin.h:85``,
``src/io/bin.cpp:1072`` — greedy equal-count bin finding with ``min_data_in_bin``,
categorical vocabularies, ``MissingType`` None/Zero/NaN).  Differences from the
reference, chosen for the TPU storage model:

- Bins are stored **dense** per feature as ``uint8``/``uint16`` device arrays; there is
  no most-frequent-bin elision (``GetMostFreqBin``/``FixHistogram``) because dense HBM
  histograms do not need it.
- The NaN bin, when present, is always the **last** bin of a feature, so the split
  scan can peel it off with a static slice instead of per-feature bin bookkeeping.
- Categorical bins are ordered by descending category frequency (rare categories
  beyond ``max_bin`` collapse into the last bin).

On the reference's ``SparseBin`` (``src/io/sparse_bin.hpp:73``, delta-encoded
sparse column storage): that structure exists to serve the CPU's pointer-chasing
scan; on TPU the histogram is a dense MXU contraction over gathered row blocks,
so a sparse post-binning layout would force serialized scatters.  The roles
SparseBin plays are covered TPU-natively instead: sparse INGESTION bins straight
from CSC without densifying (``_bin_sparse_matrix``, O(nnz) peak), EFB bundles
mutually-exclusive sparse columns into shared histogram columns (the compaction
win), and 4-bit nibble packing (``ops/histogram.pack_bins4``) halves the dense
matrix whenever every feature fits 16 bins — the reference's own ``IS_4BIT``
dense arm, which is what LightGBM itself uses once sparse columns are bundled.
"""

from __future__ import annotations

import dataclasses
import os
from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional, Sequence

import numpy as np

from . import native

MISSING_NONE = 0
MISSING_ZERO = 1
MISSING_NAN = 2

_KZERO_LO, _KZERO_HI = -1e-35, 1e-35  # reference uses kZeroThreshold = 1e-35
# Sampled values (columns x sampled rows) from which bin_dataset finds the
# columns' boundaries on several threads: below it a pool costs more than
# the sorts it spreads (4 M values are 0.4 s in one thread).
_PARALLEL_FIND_BIN_VALUES = 1 << 22


@dataclasses.dataclass
class BinMapper:
    """Per-feature value->bin discretizer (reference ``bin.h:85``)."""

    num_bins: int
    missing_type: int
    is_categorical: bool
    # Numerical: inclusive upper bound of each *value* bin (excludes the NaN bin).
    upper_bounds: Optional[np.ndarray] = None
    # Categorical: category integer value per bin index.
    categories: Optional[np.ndarray] = None
    is_trivial: bool = False  # single-bin feature; carries no signal
    default_bin: int = 0      # bin of value 0.0 (used by sparse paths later)

    @property
    def has_nan_bin(self) -> bool:
        return self.missing_type != MISSING_NONE

    @property
    def nan_bin(self) -> int:
        return self.num_bins - 1 if self.has_nan_bin else -1

    def value_to_bin(self, values: np.ndarray) -> np.ndarray:
        """Vectorized ValueToBin (reference ``bin.h:173``)."""
        v = np.asarray(values, dtype=np.float64)
        if self.is_categorical:
            cats = self.categories
            # Map category value -> bin by table lookup; unseen/negative -> last bin.
            out = np.full(v.shape, self.num_bins - 1, dtype=np.int32)
            vi = np.where(np.isfinite(v), v, -1).astype(np.int64)
            lut_size = int(cats.max()) + 1 if cats.size else 1
            lut = np.full(lut_size, self.num_bins - 1, dtype=np.int32)
            lut[cats] = np.arange(len(cats), dtype=np.int32)
            in_range = (vi >= 0) & (vi < lut_size)
            out[in_range] = lut[vi[in_range]]
            return out
        n_value_bins = self.num_bins - (1 if self.has_nan_bin else 0)
        nb = native.value_to_bin(
            v.ravel(), self.upper_bounds, n_value_bins,
            self.nan_bin, self.missing_type == MISSING_ZERO)
        if nb is not None:
            return nb.reshape(v.shape)
        if self.missing_type == MISSING_ZERO:
            v = np.where((v > _KZERO_LO) & (v < _KZERO_HI), np.nan, v)
        # bin b holds values <= upper_bounds[b]; clip overflow into last value bin.
        bins = np.searchsorted(self.upper_bounds[: n_value_bins - 1], v, side="left")
        bins = bins.astype(np.int32)
        if self.has_nan_bin:
            bins = np.where(np.isnan(v), self.nan_bin, bins)
        else:
            bins = np.where(np.isnan(v), 0, bins)
        return bins

    def bin_to_threshold(self, bin_idx: int) -> float:
        """Real-valued split threshold for ``bin <= bin_idx`` (go-left) decisions."""
        if self.is_categorical:
            return float(bin_idx)
        n_value_bins = self.num_bins - (1 if self.has_nan_bin else 0)
        b = min(int(bin_idx), n_value_bins - 1)
        return float(self.upper_bounds[b])


def _greedy_find_boundaries(
    distinct: np.ndarray,
    counts: np.ndarray,
    max_bins: int,
    total_cnt: int,
    min_data_in_bin: int,
) -> List[float]:
    """Greedy equal-count boundary search (reference ``bin.cpp`` GreedyFindBin).

    Walks distinct values accumulating counts; closes a bin once it holds at least
    ``max(mean_size, min_data_in_bin)`` samples, re-estimating the mean from the
    remainder.  Heavy hitters (count >= mean) always get their own bin.
    """
    n = len(distinct)
    if n == 0:
        return [np.inf]
    if n <= max_bins:
        # Every distinct value gets a bin; boundary = midpoint to next value.
        bounds = [(distinct[i] + distinct[i + 1]) / 2.0 for i in range(n - 1)]
        bounds.append(np.inf)
        return bounds
    bounds: List[float] = []
    rest_cnt = total_cnt
    rest_bins = max_bins
    cur = 0
    i = 0
    while i < n:
        mean_size = rest_cnt / max(rest_bins, 1)
        target = max(mean_size, float(min_data_in_bin))
        cur += counts[i]
        rest_cnt -= counts[i]
        # Close the bin if full, or if the remaining values just fit remaining bins.
        if cur >= target or (n - i - 1) <= (rest_bins - 1 - len(bounds) - 1):
            if i + 1 < n:
                bounds.append((distinct[i] + distinct[i + 1]) / 2.0)
            cur = 0
            rest_bins -= 1
            if len(bounds) >= max_bins - 1:
                break
        i += 1
    bounds.append(np.inf)
    return bounds


def load_forced_bins(path: str, num_features: int,
                     categorical: Sequence[int] = ()) -> Optional[dict]:
    """Parse a forcedbins_filename JSON file into {feature: [bounds]}
    (reference ``DatasetLoader::GetForcedBins``, dataset_loader.cpp:1493:
    array of {"feature": i, "bin_upper_bound": [...]}; categorical
    features are warned and skipped; missing file warns and is ignored)."""
    if not path:
        return None
    import json
    from .utils.log import Log
    try:
        with open(path) as fh:
            spec = json.load(fh)
    except OSError:
        Log.warning(f"Could not open {path}. Will ignore.")
        return None
    cats = set(int(c) for c in categorical)
    out: dict = {}
    for entry in spec:
        fi = int(entry["feature"])
        if fi >= num_features:
            raise ValueError(
                f"forced bins feature {fi} out of range ({num_features})")
        if fi in cats:
            Log.warning(f"Feature {fi} is categorical. Will ignore forced "
                        "bins for this feature.")
            continue
        out[fi] = [float(b) for b in entry["bin_upper_bound"]]
    return out or None


def _bounds_with_forced(distinct, counts, max_bins, total_cnt,
                        min_data_in_bin, forced) -> List[float]:
    """Bin boundaries honoring user-forced upper bounds (reference
    ``FindBinWithPredefinedBin``, bin.cpp:157): the forced bounds become
    boundaries first, then each segment between them gets a greedy-
    equal-count refill proportional to its sample mass, the last segment
    absorbing the remaining budget.

    Forced bounds within ``kZeroThreshold`` (1e-35) of zero are dropped,
    as the reference skips any ``|bound| <= kZeroThreshold`` — it reserves
    that band for its own ±kZeroThreshold boundaries so value 0.0 always
    gets a dedicated bin.  Deviation note: this repo omits those implicit
    zero boundaries REPO-WIDE (``_greedy_find_boundaries`` too, not just
    here) — dense HBM histograms have no most-frequent-bin elision, so
    zero earns a bin only when the data's own mass puts one there; what
    must not differ is the forced-bound filter, else a user bound at/near
    0.0 would create a sliver bin the reference refuses."""
    forced = sorted({float(b) for b in forced
                     if np.isfinite(b) and not (_KZERO_LO <= b <= _KZERO_HI)})
    bounds = forced[: max(max_bins - 1, 0)] + [np.inf]
    free_bins = max_bins - len(bounds)
    to_add: List[float] = []
    vi = 0
    for i, ub in enumerate(bounds):
        seg_start = vi
        cnt_in_bin = 0
        while vi < len(distinct) and distinct[vi] < ub:
            cnt_in_bin += int(counts[vi])
            vi += 1
        remaining = free_bins - len(to_add)
        if i == len(bounds) - 1:
            num_sub = remaining + 1
        else:
            num_sub = min(int(round(cnt_in_bin * free_bins
                                    / max(total_cnt, 1))), remaining) + 1
        if num_sub > 1 and vi > seg_start:
            sub = _greedy_find_boundaries(
                distinct[seg_start:vi], counts[seg_start:vi], num_sub,
                cnt_in_bin, min_data_in_bin)
            to_add.extend(sub[:-1])   # last sub-bound is +inf
    return sorted(bounds[:-1] + to_add) + [np.inf]


def find_bin(
    sample_values: np.ndarray,
    max_bin: int,
    min_data_in_bin: int = 3,
    *,
    is_categorical: bool = False,
    use_missing: bool = True,
    zero_as_missing: bool = False,
    min_data_per_category: int = 1,
    forced_upper_bounds: Optional[Sequence[float]] = None,
) -> BinMapper:
    """Construct a :class:`BinMapper` from sampled values (reference ``FindBin``,
    ``bin.cpp:~150``)."""
    v = np.asarray(sample_values, dtype=np.float64).ravel()
    na_mask = np.isnan(v)
    if zero_as_missing:
        zmask = (v > _KZERO_LO) & (v < _KZERO_HI)
        na_mask = na_mask | zmask
    num_na = int(na_mask.sum())
    vv = v[~na_mask]

    if is_categorical:
        cats_f = vv[vv >= 0]
        cats, counts = np.unique(cats_f.astype(np.int64), return_counts=True)
        order = np.argsort(-counts, kind="stable")
        cats, counts = cats[order], counts[order]
        keep = counts >= min_data_per_category
        if keep.any():
            cats, counts = cats[keep], counts[keep]
        cats = cats[: max_bin - 1] if len(cats) >= max_bin else cats
        num_bins = len(cats) + 1  # final bin: rare/unseen/missing
        if num_bins < 2:
            return BinMapper(num_bins=1, missing_type=MISSING_NONE,
                             is_categorical=True, categories=cats.astype(np.int64),
                             is_trivial=True)
        return BinMapper(
            num_bins=num_bins,
            missing_type=MISSING_NAN if (use_missing and num_na > 0) else MISSING_NONE,
            is_categorical=True,
            categories=cats.astype(np.int64),
        )

    missing_type = MISSING_NONE
    if use_missing and zero_as_missing and num_na > 0:
        missing_type = MISSING_ZERO
    elif use_missing and num_na > 0:
        missing_type = MISSING_NAN

    has_nan_bin = missing_type != MISSING_NONE
    max_value_bins = max_bin - (1 if has_nan_bin else 0)
    uc = native.unique_counts(vv)
    if uc is not None:
        distinct, counts = uc
    else:
        distinct, counts = np.unique(vv, return_counts=True)
    if forced_upper_bounds:
        bounds = _bounds_with_forced(distinct, counts, max_value_bins,
                                     len(vv), min_data_in_bin,
                                     forced_upper_bounds)
    else:
        nb = native.find_boundaries(distinct, counts, max_value_bins,
                                    len(vv), min_data_in_bin)
        if nb is not None:
            bounds = list(nb)
        else:
            bounds = _greedy_find_boundaries(
                distinct, counts, max_value_bins, len(vv), min_data_in_bin
            )
    num_bins = len(bounds) + (1 if has_nan_bin else 0)
    trivial = num_bins <= 1 or (len(distinct) <= 1 and not has_nan_bin)
    ub = np.asarray(bounds, dtype=np.float64)
    default_bin = int(np.searchsorted(ub[:-1], 0.0, side="left")) if len(ub) else 0
    return BinMapper(
        num_bins=max(num_bins, 1),
        missing_type=missing_type,
        is_categorical=False,
        upper_bounds=ub,
        is_trivial=trivial,
        default_bin=default_bin,
    )


def _is_sparse(X) -> bool:
    return hasattr(X, "tocsc") and hasattr(X, "tocsr")


def bin_dataset(
    X: np.ndarray,
    max_bin: int = 255,
    min_data_in_bin: int = 3,
    categorical_features: Sequence[int] = (),
    *,
    use_missing: bool = True,
    zero_as_missing: bool = False,
    sample_cnt: int = 200000,
    random_state: int = 1,
    max_bin_by_feature: Optional[Sequence[int]] = None,
    forced_bins: Optional[dict] = None,
) -> "BinnedData":
    """Bin a full feature matrix. Sampling mirrors the reference's
    ``DatasetLoader::SampleTextDataFromFile`` (``dataset_loader.cpp:1022``): bin
    boundaries come from a row subsample, then the full matrix is discretized.

    scipy sparse inputs are binned column-wise straight from CSC — peak
    memory stays O(nnz) + the (N, F) uint8/16 bin matrix, never a dense f64
    copy (the reference's sparse answer is ``SparseBin``,
    ``src/io/sparse_bin.hpp:73``; here post-binning storage is dense-narrow
    + EFB, so only INGESTION needs the sparse-aware path)."""
    sparse = _is_sparse(X)
    if not sparse:
        X = np.asarray(X)
    n, f = X.shape
    if n > sample_cnt:
        rng = np.random.RandomState(random_state)
        idx = rng.choice(n, size=sample_cnt, replace=False)
        sample = X[idx] if not sparse else X.tocsr()[np.sort(idx)]
    else:
        sample = X
    if sparse:
        sample = sample.tocsc()
    cat_set = set(int(c) for c in categorical_features)
    if max_bin_by_feature is not None:
        # reference CHECKs length == num features and every value > 1
        if len(max_bin_by_feature) != f:
            raise ValueError(
                f"max_bin_by_feature has {len(max_bin_by_feature)} entries "
                f"for {f} features (reference requires an exact match)")
        if any(int(v) <= 1 for v in max_bin_by_feature):
            raise ValueError("max_bin_by_feature values must be > 1")
    s = sample.shape[0]

    def one_feature(j: int):
        """Column ``j``'s ``(mapper, all its sampled values are NaN)``."""
        mb = max_bin
        if max_bin_by_feature is not None:
            mb = int(max_bin_by_feature[j])
        if sparse:
            nz = np.asarray(sample.data[sample.indptr[j]:
                                        sample.indptr[j + 1]], np.float64)
            col = np.zeros(s, np.float64)
            col[: len(nz)] = nz       # find_bin is order-invariant
        else:
            col = sample[:, j]
        all_nan = (j not in cat_set and s > 0
                   and bool(np.isnan(np.asarray(col, np.float64)).all()))
        return find_bin(
            col, mb, min_data_in_bin,
            is_categorical=(j in cat_set),
            use_missing=use_missing, zero_as_missing=zero_as_missing,
            forced_upper_bounds=(forced_bins or {}).get(j)), all_nan

    # A column's boundaries depend on that column alone, and nearly all of
    # the work is the native sort (ctypes releases the interpreter lock):
    # wide samples go column by column over a few threads, as the
    # reference's loader does under OpenMP (2000 columns x 200 000 sampled
    # rows: 43 s in one thread).
    workers = min(os.cpu_count() or 1, 16, f)
    if workers > 1 and f * s >= _PARALLEL_FIND_BIN_VALUES:
        with ThreadPoolExecutor(workers) as pool:
            found = list(pool.map(one_feature, range(f)))
    else:
        found = [one_feature(j) for j in range(f)]
    mappers: List[BinMapper] = [m for m, _ in found]
    all_nan_cols: List[int] = [j for j, (_, nan) in enumerate(found) if nan]
    # Ingestion health (docs/ROBUSTNESS.md; reference DatasetLoader
    # feature_pre_filter warnings): a column that is entirely NaN in the
    # binning sample, or binned trivially (constant), can never split —
    # usually an upstream join/pipeline bug worth one loud line.
    const_cols = [j for j, m in enumerate(mappers)
                  if m.is_trivial and j not in all_nan_cols]
    if all_nan_cols or const_cols:
        from .utils.log import Log
        if all_nan_cols:
            Log.warning(
                f"{len(all_nan_cols)} feature column(s) are entirely NaN "
                f"in the binning sample (e.g. {all_nan_cols[:8]}); they "
                "can never split")
        if const_cols:
            Log.warning(
                f"{len(const_cols)} feature column(s) are constant "
                f"(e.g. {const_cols[:8]}); they can never split")
    return BinnedData.from_mappers(X, mappers)


def _bin_sparse_matrix(X, mappers: List["BinMapper"], dtype) -> np.ndarray:
    """Bin a scipy sparse matrix column-wise without densifying: every
    column starts at its zero-value bin, then only the nonzeros are
    discretized and scattered.  Peak extra memory is O(nnz)."""
    csc = X.tocsc()
    n, f = csc.shape
    out = np.empty((n, f), dtype=dtype)
    zero = np.zeros(1, np.float64)
    for j, m in enumerate(mappers):
        out[:, j] = m.value_to_bin(zero)[0]
        lo, hi = csc.indptr[j], csc.indptr[j + 1]
        if hi > lo:
            out[csc.indices[lo:hi], j] = m.value_to_bin(
                np.asarray(csc.data[lo:hi], np.float64)).astype(dtype)
    return out


def predict_dense_chunks(predict_fn, X, chunk: int = 65536) -> np.ndarray:
    """Run a dense-only predict over a sparse matrix in row chunks: peak
    extra memory stays O(chunk * F) instead of the full dense copy (used
    where raw-value tree traversal genuinely needs dense rows — loaded
    models, linear trees)."""
    outs = [np.asarray(predict_fn(
                np.asarray(X[lo:lo + chunk].todense(), np.float64)),
                np.float64)
            for lo in range(0, X.shape[0], chunk)]
    return np.concatenate(outs, axis=0)


def bake_bin_luts(mappers: List["BinMapper"]):
    """Flatten the numerical mappers into the (ubm, nvb, nnb, zam) arrays
    ``native.bin_matrix`` consumes.  Single source of the bin-encoding
    convention — shared by batch binning here and the C API's single-row
    fast path (capi/bridge.py FastConfig)."""
    f = len(mappers)
    max_b = max((len(m.upper_bounds) for m in mappers
                 if m.upper_bounds is not None), default=1)
    ubm = np.full((f, max_b), np.inf, np.float64)
    nvb = np.ones(f, np.int32)
    nnb = np.full(f, -1, np.int32)
    zam = np.zeros(f, np.uint8)
    for j, m in enumerate(mappers):
        if m.is_categorical or m.upper_bounds is None:
            continue
        k = len(m.upper_bounds)
        ubm[j, :k] = m.upper_bounds
        nvb[j] = m.num_bins - (1 if m.has_nan_bin else 0) + 1
        nnb[j] = m.nan_bin if m.has_nan_bin else -1
        zam[j] = 1 if m.missing_type == MISSING_ZERO else 0
    return ubm, nvb, nnb, zam


def _bin_full_matrix(X, mappers: List["BinMapper"], dtype) -> np.ndarray:
    """Bin every column in one threaded native pass (numerical features);
    categorical columns fall back to the per-feature LUT path."""
    if _is_sparse(X):
        return _bin_sparse_matrix(X, mappers, dtype)
    X = np.asarray(X)
    n, f = X.shape
    any_num = any(not m.is_categorical for m in mappers)
    out = None
    if any_num:
        nb = native.bin_matrix(X, *bake_bin_luts(mappers))
        if nb is not None:
            out = nb.astype(dtype, copy=False)
    if out is None:
        out = np.empty((n, f), dtype=dtype)
        for j, m in enumerate(mappers):
            out[:, j] = m.value_to_bin(X[:, j]).astype(dtype)
        return out
    for j, m in enumerate(mappers):
        if m.is_categorical:
            out[:, j] = m.value_to_bin(X[:, j]).astype(dtype)
    return out


@dataclasses.dataclass
class BinnedData:
    """Dense binned matrix + per-feature metadata, ready for device upload."""

    bins: np.ndarray                 # (N, F) uint8/uint16
    mappers: List[BinMapper]
    max_num_bins: int                # B: padded bin axis for device histograms
    upper_bounds_padded: np.ndarray  # (F, B) f32: threshold per (feature, bin)
    nan_bins: np.ndarray             # (F,) int32: NaN bin index or B (none)
    num_bins_per_feature: np.ndarray  # (F,) int32
    is_categorical: np.ndarray       # (F,) bool

    @classmethod
    def from_mappers(cls, X: np.ndarray, mappers: List[BinMapper]) -> "BinnedData":
        max_b = max(max(m.num_bins for m in mappers), 2)
        dtype = np.uint8 if max_b <= 256 else np.uint16
        return cls.from_prebinned(_bin_full_matrix(X, mappers, dtype),
                                  mappers)

    @classmethod
    def from_prebinned(cls, bins: np.ndarray,
                       mappers: List[BinMapper]) -> "BinnedData":
        """Wrap an ALREADY-binned matrix (two-round streaming load bins
        chunk-by-chunk; binary-cache reload stores bins directly)."""
        f = len(mappers)
        max_b = max(max(m.num_bins for m in mappers), 2)
        ub = np.full((f, max_b), np.inf, dtype=np.float32)
        nan_bins = np.full(f, max_b, dtype=np.int32)
        nbpf = np.empty(f, dtype=np.int32)
        is_cat = np.zeros(f, dtype=bool)
        for j, m in enumerate(mappers):
            nbpf[j] = m.num_bins
            is_cat[j] = m.is_categorical
            if m.is_categorical:
                ub[j, : m.num_bins] = np.arange(m.num_bins, dtype=np.float32)
            elif m.upper_bounds is not None:
                k = len(m.upper_bounds)
                ub[j, :k] = m.upper_bounds.astype(np.float32)
            if m.has_nan_bin:
                nan_bins[j] = m.nan_bin
        return cls(
            bins=bins, mappers=mappers, max_num_bins=max_b,
            upper_bounds_padded=ub, nan_bins=nan_bins,
            num_bins_per_feature=nbpf, is_categorical=is_cat,
        )

    @property
    def num_data(self) -> int:
        return self.bins.shape[0]

    @property
    def num_features(self) -> int:
        return self.bins.shape[1]

    def apply(self, X) -> np.ndarray:
        """Bin new data (e.g. a validation set) with the training mappers —
        reference ``LoadFromFileAlignWithOtherDataset`` (``dataset_loader.cpp:299``).
        Accepts dense arrays or scipy sparse (binned straight from CSC)."""
        if _is_sparse(X):
            return _bin_sparse_matrix(X, self.mappers, self.bins.dtype)
        return _bin_full_matrix(np.asarray(X), self.mappers,
                                self.bins.dtype)


# ---------------------------------------------------------------- binary cache
def mappers_to_arrays(mappers: List[BinMapper]) -> dict:
    """Flatten per-feature mappers into fixed arrays for the binary dataset
    cache (reference ``Dataset::SaveBinaryFile``, ``dataset_loader.cpp:417``
    reload path)."""
    f = len(mappers)
    num_bins = np.array([m.num_bins for m in mappers], np.int32)
    missing = np.array([m.missing_type for m in mappers], np.int32)
    is_cat = np.array([m.is_categorical for m in mappers], bool)
    trivial = np.array([m.is_trivial for m in mappers], bool)
    default_bin = np.array([m.default_bin for m in mappers], np.int32)
    ub_flat, ub_off = [], [0]
    cat_flat, cat_off = [], [0]
    for m in mappers:
        ub = m.upper_bounds if m.upper_bounds is not None else np.zeros(0)
        ub_flat.append(np.asarray(ub, np.float64))
        ub_off.append(ub_off[-1] + len(ub))
        cats = m.categories if m.categories is not None else np.zeros(0, np.int64)
        cat_flat.append(np.asarray(cats, np.int64))
        cat_off.append(cat_off[-1] + len(cats))
    return {
        "mapper_num_bins": num_bins, "mapper_missing": missing,
        "mapper_is_cat": is_cat, "mapper_trivial": trivial,
        "mapper_default_bin": default_bin,
        "mapper_ub": np.concatenate(ub_flat) if f else np.zeros(0),
        "mapper_ub_off": np.array(ub_off, np.int64),
        "mapper_cats": np.concatenate(cat_flat) if f else np.zeros(0, np.int64),
        "mapper_cat_off": np.array(cat_off, np.int64),
    }


def mappers_from_arrays(d: dict) -> List[BinMapper]:
    # Materialize members once: NpzFile.__getitem__ decompresses the whole
    # array on every access, which would make this loop O(F^2).
    d = {k: np.asarray(d[k]) for k in (
        "mapper_num_bins", "mapper_missing", "mapper_is_cat",
        "mapper_trivial", "mapper_default_bin", "mapper_ub",
        "mapper_ub_off", "mapper_cats", "mapper_cat_off")}
    f = len(d["mapper_num_bins"])
    out: List[BinMapper] = []
    for j in range(f):
        is_cat = bool(d["mapper_is_cat"][j])
        lo, hi = int(d["mapper_ub_off"][j]), int(d["mapper_ub_off"][j + 1])
        clo, chi = int(d["mapper_cat_off"][j]), int(d["mapper_cat_off"][j + 1])
        out.append(BinMapper(
            num_bins=int(d["mapper_num_bins"][j]),
            missing_type=int(d["mapper_missing"][j]),
            is_categorical=is_cat,
            upper_bounds=None if is_cat else d["mapper_ub"][lo:hi],
            categories=d["mapper_cats"][clo:chi] if is_cat else None,
            is_trivial=bool(d["mapper_trivial"][j]),
            default_bin=int(d["mapper_default_bin"][j]),
        ))
    return out


# ------------------------------------------------------------------------ EFB
@dataclasses.dataclass
class FeatureBundles:
    """Exclusive feature bundling (reference EFB: ``DatasetLoader::FindGroups``
    / ``FeatureGroup``, ``src/io/dataset_loader.cpp`` + ``feature_group.h:26``).

    Mutually (near-)exclusive sparse features share ONE histogram column:
    bundle bin 0 means "every member at its default"; member ``f``'s
    non-default bins ``1..nb_f-1`` occupy ``[offset_f, offset_f + nb_f - 2]``.
    Dense/categorical/non-zero-default features ride along as singleton
    groups with identity bin mapping (``feat_offset == -1``).

    The grower consumes the bundled (N, G) matrix for histograms and row
    partitions, then reconstructs per-ORIGINAL-feature histogram views at
    split-scan time — trees, serialization, and prediction stay entirely in
    original feature space.
    """

    feat_group: np.ndarray    # (F,) int32 — bundle column of each feature
    feat_offset: np.ndarray   # (F,) int32 — non-default-bin offset; -1 = identity
    group_bins: np.ndarray    # (G,) int32 — bins per bundle column
    bins: np.ndarray          # (N, G) bundled matrix

    @property
    def num_groups(self) -> int:
        return len(self.group_bins)

    @property
    def max_group_bins(self) -> int:
        return int(self.group_bins.max()) if len(self.group_bins) else 1

    def bundle_row_matrix(self, bins: np.ndarray) -> np.ndarray:
        """Re-bundle an (N, F) original-bin matrix (e.g. after binary-cache
        reload)."""
        n = bins.shape[0]
        out = np.zeros((n, self.num_groups), dtype=self.bins.dtype)
        for f in range(len(self.feat_group)):
            g, off = int(self.feat_group[f]), int(self.feat_offset[f])
            col = bins[:, f]
            if off < 0:
                out[:, g] = col
            else:
                nz = col > 0
                out[nz, g] = (off + col[nz].astype(np.int32) - 1).astype(
                    out.dtype)
        return out


def build_bundles(binned: "BinnedData", *, max_conflict_rate: float = 0.0,
                  sample_cnt: int = 20000, max_bundle_bins: int = 4096,
                  min_gain_cols: float = 0.75,
                  random_state: int = 3) -> Optional[FeatureBundles]:
    """Greedy conflict-bounded bundling (the EFB paper's Greedy Bundling,
    reference ``FindGroups``).  Returns None when bundling would not shrink
    the column count below ``min_gain_cols * F`` (dense data)."""
    bins = binned.bins
    n, f = bins.shape
    if f < 8:
        return None
    mappers = binned.mappers
    eligible = np.array(
        [(not m.is_categorical) and m.default_bin == 0 and m.num_bins >= 2
         and m.num_bins - 1 <= max_bundle_bins - 1
         for m in mappers])
    if n > sample_cnt:
        rng = np.random.RandomState(random_state)
        sample = bins[rng.choice(n, size=sample_cnt, replace=False)]
    else:
        sample = bins
    s = sample.shape[0]
    nz = sample != 0                                   # (S, F)
    nz_cnt = nz.sum(axis=0)
    budget = int(max_conflict_rate * s)
    nbpf = binned.num_bins_per_feature

    # Greedy: sparsest-first so dense features don't eat bundle capacity.
    order = [int(j) for j in np.argsort(nz_cnt) if eligible[j]]
    bundles: List[List[int]] = []
    bundle_nz: List[np.ndarray] = []
    bundle_cnt: List[int] = []
    bundle_bins: List[int] = []
    for j in order:
        extra = int(nbpf[j]) - 1
        cnt_j = int(nz_cnt[j])
        placed = False
        for bi in range(len(bundles)):
            if bundle_bins[bi] + extra > max_bundle_bins:
                continue
            # two sets of a + b members among s rows share at least
            # a + b - s: dense columns (2000 of them are 2 M pairs, 115 s
            # of row-wise ANDs) are refused without looking at the rows
            if bundle_cnt[bi] + cnt_j - s > budget:
                continue
            conflict = int(np.count_nonzero(bundle_nz[bi] & nz[:, j]))
            if conflict <= budget:
                bundles[bi].append(j)
                bundle_nz[bi] |= nz[:, j]
                bundle_cnt[bi] += cnt_j - conflict
                bundle_bins[bi] += extra
                placed = True
                break
        if not placed:
            bundles.append([j])
            bundle_nz.append(nz[:, j].copy())
            bundle_cnt.append(cnt_j)
            bundle_bins.append(1 + extra)

    # The greedy pass enforced the budget on a sample only; re-check each
    # multi-member bundle on the FULL matrix with the SAME accumulated
    # criterion the greedy pass used (each of the m-1 additions was allowed
    # <= budget conflicts, so a bundle may hold up to (m-1)*budget total)
    # and evict the worst offender into a singleton until it fits —
    # otherwise out-of-sample conflicts silently lose values last-writer-
    # wins in bundle_row_matrix.  When the sample was the full matrix the
    # greedy pass already enforced this exactly.
    full_budget = int(max_conflict_rate * n)
    n_evicted = 0
    if n > s:
        for bi in range(len(bundles)):
            members = bundles[bi]
            while len(members) > 1:
                nz_cols = bins[:, members] != 0          # (N, m)
                row_nnz = nz_cols.sum(axis=1)
                conflicts = int(np.maximum(row_nnz - 1, 0).sum())
                if conflicts <= (len(members) - 1) * full_budget:
                    break
                overlap = ((row_nnz > 1)[:, None] & nz_cols).sum(axis=0)
                bundles.append([members.pop(int(np.argmax(overlap)))])
                n_evicted += 1
    if n_evicted:
        from .utils.log import Log
        Log.debug(f"EFB: evicted {n_evicted} feature(s) whose full-data "
                  f"conflict count exceeded the sampled budget")

    n_single = f - sum(len(b) for b in bundles)
    n_groups = len(bundles) + n_single
    if n_groups > min_gain_cols * f:
        return None

    feat_group = np.empty(f, np.int32)
    feat_offset = np.full(f, -1, np.int32)
    group_bins = []
    for bi, members in enumerate(bundles):
        off = 1
        for j in members:
            feat_group[j] = bi
            feat_offset[j] = off
            off += int(nbpf[j]) - 1
        group_bins.append(off)
    g = len(bundles)
    for j in range(f):
        if eligible[j]:
            continue
        feat_group[j] = g
        group_bins.append(int(nbpf[j]))
        g += 1

    dtype = np.uint8 if max(group_bins) <= 256 else np.uint16
    assert max(group_bins) <= 65535
    fb = FeatureBundles(
        feat_group=feat_group, feat_offset=feat_offset,
        group_bins=np.asarray(group_bins, np.int32),
        bins=np.zeros((0, len(group_bins)), dtype))
    # conflicts outside the sample resolve last-writer-wins (the reference
    # likewise tolerates bounded conflicts)
    fb.bins = fb.bundle_row_matrix(bins)
    return fb
