"""User-facing ``Dataset`` and ``Booster`` (lightgbm-compatible surface).

Reference: ``python-package/lightgbm/basic.py`` (``Dataset:1764``, ``Booster:3586``).
There is no ctypes boundary here — the "C API" equivalent is the in-process
:class:`~lightgbm_tpu.models.gbdt.GBDT` driver whose compute runs as XLA
programs.
"""

from __future__ import annotations

import copy
import os
from typing import Any, Dict, List, Optional, Tuple, Union
from typing import Sequence as TypingSequence

import numpy as np

from .config import Config
from .dataset import TrainData
from .models.gbdt import GBDT
from .models.dart import DART
from .models.rf import RandomForest
from .telemetry import span


class Sequence:
    """Generic data access interface for two-pass/chunked loading
    (reference ``lightgbm.Sequence``): subclasses implement ``__len__`` and
    ``__getitem__`` (row or slice).  A list of Sequences/arrays passed as
    ``Dataset(data=...)`` is concatenated row-wise."""

    batch_size = 4096

    def __len__(self) -> int:
        raise NotImplementedError

    def __getitem__(self, idx):
        raise NotImplementedError

    def _materialize(self) -> np.ndarray:
        out = []
        for start in range(0, len(self), self.batch_size):
            out.append(np.asarray(
                self[slice(start, min(start + self.batch_size, len(self)))],
                np.float64))
        return np.concatenate(out, axis=0) if out else np.zeros((0, 0))


def _as_2d(data) -> np.ndarray:
    """Accept ndarray / list / pandas DataFrame / scipy sparse / pyarrow
    Table / Sequence(s) (reference ``basic.py`` ``_data_from_pandas``,
    CSR/CSC and Arrow ingestion, ``include/LightGBM/arrow.h``).  Sparse
    inputs densify: the TPU build stores one dense (N, F) bin matrix and EFB
    (enable_bundle) recovers the sparse-column win after binning."""
    df = _pandas_df(data)
    if df is not None:
        return _pandas_to_mat(df)
    if _is_scipy_sparse(data):
        return np.asarray(data.todense(), dtype=np.float64)
    arrow = _arrow_to_mat(data)
    if arrow is not None:
        return arrow
    if isinstance(data, Sequence):
        return _as_2d(data._materialize())
    if (isinstance(data, (list, tuple)) and data
            and all(isinstance(c, Sequence)
                    or (isinstance(c, np.ndarray) and c.ndim == 2)
                    or _pandas_df(c) is not None for c in data)):
        # chunked push: list of 2-D row blocks (reference
        # LGBM_DatasetPushRows / Sequence lists).  Lists of 1-D rows keep
        # the plain "matrix from list of rows" meaning.
        return np.concatenate([_as_2d(c) for c in data], axis=0)
    arr = np.asarray(data, dtype=np.float64)
    if arr.ndim == 1:
        arr = arr.reshape(-1, 1)
    return arr


def _arrow_to_mat(data):
    """pyarrow Table / RecordBatch -> (N, F) f64; dictionary columns ->
    category codes (reference Arrow ingestion, include/LightGBM/arrow.h)."""
    try:
        import pyarrow as pa
    except ImportError:
        return None
    if isinstance(data, pa.RecordBatch):
        data = pa.Table.from_batches([data])
    if not isinstance(data, pa.Table):
        return None
    cols = []
    for name in data.column_names:
        col = data.column(name)
        if pa.types.is_dictionary(col.type):
            codes = col.combine_chunks().indices.to_numpy(
                zero_copy_only=False).astype(np.float64)
            cols.append(codes)
        else:
            cols.append(col.to_numpy(zero_copy_only=False).astype(
                np.float64))
    return np.column_stack(cols) if cols else np.zeros((len(data), 0))


def _pandas_df(data):
    try:
        import pandas as pd
    except ImportError:
        return None
    if isinstance(data, pd.DataFrame):
        return data
    if isinstance(data, pd.Series):
        return data.to_frame()
    return None


def _is_scipy_sparse(data) -> bool:
    return hasattr(data, "tocsr") and hasattr(data, "todense")


def _pandas_to_mat(df) -> np.ndarray:
    """Categorical columns -> their integer codes (NaN for missing), object
    columns rejected (reference ``_data_from_pandas`` semantics)."""
    import pandas as pd

    cols = []
    for c in df.columns:
        col = df[c]
        if isinstance(col.dtype, pd.CategoricalDtype):
            codes = col.cat.codes.to_numpy(np.float64)
            cols.append(np.where(codes < 0, np.nan, codes))
        elif not (pd.api.types.is_numeric_dtype(col)
                  or pd.api.types.is_bool_dtype(col)):
            raise ValueError(
                f"DataFrame column {c!r} has object dtype; convert it to "
                "numeric or categorical first (reference basic.py "
                "bad_indices error)")
        else:
            cols.append(col.to_numpy(np.float64))
    return np.column_stack(cols) if cols else np.zeros((len(df), 0))


def _pandas_meta(data):
    """(feature_names, categorical_columns) from a DataFrame, for the
    'auto' resolution path."""
    import pandas as pd

    names = [str(c) for c in data.columns]
    cats = [i for i, c in enumerate(data.columns)
            if isinstance(data[c].dtype, pd.CategoricalDtype)]
    return names, cats


class Dataset:
    """Lazily-constructed training dataset (reference ``basic.py:1764``)."""

    def __init__(
        self,
        data,
        label=None,
        reference: Optional["Dataset"] = None,
        weight=None,
        group=None,
        position=None,
        init_score=None,
        feature_name: Union[str, List[str]] = "auto",
        categorical_feature: Union[str, List[int], List[str]] = "auto",
        params: Optional[Dict[str, Any]] = None,
        free_raw_data: bool = False,
    ):
        self._binary_path = None
        self._text_path = None
        if isinstance(data, str):
            # Binary cache fast path (reference Dataset(path) +
            # CheckCanLoadFromBin, dataset_loader.cpp:1466); any other
            # path is a CSV/TSV/LibSVM text file, loaded with the params'
            # column specs like the reference python package delegates to
            # DatasetLoader.
            from .dataset import is_binary_dataset_file
            if not os.path.exists(data):
                raise FileNotFoundError(f"no such data file: {data!r}")
            if is_binary_dataset_file(data):
                self._binary_path = data
            else:
                import zipfile
                if zipfile.is_zipfile(data):
                    # a real zip container that failed binary validation
                    # is a truncated/corrupt cache, not a text file
                    raise ValueError(
                        f"{data!r} looks like a corrupt lightgbm_tpu "
                        "binary dataset file")
                # Text file: defer the parse to construct() so params
                # passed to train() (header, label/column specs) apply,
                # like the binary path and the reference's lazy loader.
                self._text_path = data
            data = np.zeros((0, 0))
        df = _pandas_df(data)
        if df is not None:
            # reference _data_from_pandas: auto feature names + auto
            # categorical columns from pandas category dtypes
            names, pd_cats = _pandas_meta(df)
            if feature_name == "auto":
                feature_name = names
            if categorical_feature == "auto" and pd_cats:
                categorical_feature = pd_cats
        else:
            try:
                import pyarrow as pa
                if isinstance(data, (pa.Table, pa.RecordBatch)):
                    if feature_name == "auto":
                        feature_name = list(data.schema.names)
                    if categorical_feature == "auto":
                        cats = [i for i, t in enumerate(data.schema.types)
                                if pa.types.is_dictionary(t)]
                        if cats:
                            categorical_feature = cats
            except ImportError:
                pass
        # scipy sparse stays sparse all the way into binning (binned
        # column-wise from CSC, binning._bin_sparse_matrix) — a Bosch-class
        # 1.2M x 968 CSR must never materialize as ~9 GB of dense f64.
        # ``data/init``: the conversion of the caller's matrix (a dense
        # input becomes a float64 copy: 6.4 GB and seconds at 400 K x 2000)
        with span("data/init"):
            self.data = (data.tocsr() if _is_scipy_sparse(data)
                         else _as_2d(data))
        self.label = None if label is None else np.asarray(label)
        self.reference = reference
        self.weight = None if weight is None else np.asarray(weight, np.float64)
        self.group = None if group is None else np.asarray(group, np.int64)
        self.position = None if position is None else np.asarray(position)
        self.init_score = None if init_score is None else np.asarray(init_score)
        self.params = dict(params or {})
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature
        self.free_raw_data = free_raw_data
        self._train_data: Optional[TrainData] = None

    def _merged_params(self, params: Optional[Dict[str, Any]]) -> Dict[str, Any]:
        merged = dict(self.params)
        merged.update(params or {})
        return merged

    def construct(self, params: Optional[Dict[str, Any]] = None) -> "TrainData":
        if self._train_data is None and self._binary_path is not None:
            self._train_data = TrainData.load_binary(self._binary_path)
            self.label = self._train_data.label
            self.weight = self._train_data.weight
            self.group = self._train_data.group
        if self._train_data is None and self._text_path is not None:
            from .io.parser import load_data_file, position_side_file
            cfg0 = Config(self._merged_params(params))
            X, fy, fw, fg, names = load_data_file(
                self._text_path, cfg0.label_column, cfg0.header,
                weight_column=cfg0.weight_column,
                group_column=cfg0.group_column,
                ignore_column=cfg0.ignore_column,
                with_feature_names=True)
            if self.position is None:
                self.position = position_side_file(self._text_path,
                                                   expected_rows=len(X))
            self.data = X
            self._text_path = None
            if self.label is None:
                self.label = fy
            if self.weight is None:
                self.weight = fw
            if self.group is None:
                self.group = fg
            if self.feature_name == "auto" and names:
                self.feature_name = names
        if self._train_data is None:
            merged = self._merged_params(params)
            cat_param = None
            for key in ("categorical_feature", "cat_feature",
                        "categorical_column", "cat_column",
                        "categorical_features"):
                if key in merged:
                    cat_param = merged.pop(key)
            cfg = Config(merged)
            cats: TypingSequence[int] = ()
            # The constructor arg wins whenever actually given (list OR
            # string — a bare/name: string used to be silently dropped);
            # "auto"/None/empty defer to the params key.
            given = self.categorical_feature
            deferred = (given is None
                        or (isinstance(given, str) and given in ("auto", ""))
                        or (isinstance(given, (list, tuple))
                            and len(given) == 0))
            cat_spec = cat_param if deferred else given
            if cat_spec == "auto":
                cat_spec = None
            force_names = False
            if isinstance(cat_spec, str) and cat_spec:
                if cat_spec.startswith("name:"):
                    # reference form: the prefix applies once to the whole
                    # comma-separated name list, and declares every token
                    # a NAME even if it looks numeric
                    cat_spec = cat_spec[5:]
                    force_names = True
                cat_spec = [t.strip() for t in cat_spec.split(",")
                            if t.strip()]
            if isinstance(cat_spec, (list, tuple)):
                names = self._feature_names()

                def cat_idx(c):
                    if not force_names and (not isinstance(c, str)
                                            or c.lstrip("-").isdigit()):
                        return int(c)
                    return names.index(c)

                cats = [cat_idx(c) for c in cat_spec]
            elif cfg.categorical_feature:
                cats = [int(c) for c in cfg.categorical_feature.split(",")]
            ref_td = (self.reference.construct(params)
                      if self.reference is not None else None)
            # Tracked telemetry span (telemetry/memory.py): dataset
            # construction is where the binned matrix — usually the
            # largest single resident buffer — lands on the device, so a
            # memory.watermark event brackets it when accounting is armed.
            # Arm from THIS construct's own params first (explicit-params
            # rule): construction runs before the GBDT constructor or
            # engine session ever sees the config, so without this the
            # run's own training set would always bin under mode "off".
            from .telemetry.memory import set_memory_mode
            if "tpu_telemetry_memory" in cfg.raw_params \
                    or "telemetry_memory" in cfg.raw_params:
                set_memory_mode(cfg.tpu_telemetry_memory)
            with span("data/construct", track_memory=True):
                self._train_data = TrainData.build(
                    self.data, self.label if self.label is not None
                    else np.zeros(self.data.shape[0]), cfg,
                    weight=self.weight, group=self.group,
                    position=self.position,
                    init_score=self.init_score,
                    categorical_features=cats,
                    feature_names=self._feature_names(),
                    reference=ref_td,
                )
        return self._train_data

    def _feature_names(self) -> List[str]:
        if isinstance(self.feature_name, list):
            return list(self.feature_name)
        return [f"Column_{i}" for i in range(self.data.shape[1])]

    def num_data(self) -> int:
        return self.data.shape[0]

    def num_feature(self) -> int:
        return self.data.shape[1]

    def get_label(self):
        return self.label

    def get_weight(self):
        return self.weight

    def get_group(self):
        return self.group

    def set_label(self, label):
        self.label = np.asarray(label)
        self._train_data = None
        return self

    def set_weight(self, weight):
        self.weight = None if weight is None else np.asarray(weight, np.float64)
        self._train_data = None
        return self

    def subset(self, used_indices, params=None):
        """Row-subset Dataset (reference ``Dataset.subset`` /
        ``CopySubrow`` — used by cv folds and bagging-style workflows).
        Bins with THIS dataset as reference so mappers stay identical."""
        if self.group is not None:
            raise ValueError(
                "subset() cannot slice a Dataset with query groups; "
                "slice whole queries and rebuild the Dataset instead")
        idx = np.asarray(used_indices, np.int64)
        return Dataset(
            self.data[idx],
            label=None if self.label is None else self.label[idx],
            reference=self,
            weight=None if self.weight is None else self.weight[idx],
            position=None if self.position is None else self.position[idx],
            init_score=(None if self.init_score is None
                        else np.asarray(self.init_score)[idx]),
            feature_name=self.feature_name,
            categorical_feature=self.categorical_feature,
            params=dict(self.params, **(params or {})),
        )

    def add_features_from(self, other: "Dataset"):
        """Horizontally stack another Dataset's features (reference
        ``Dataset.add_features_from`` / ``AddFeaturesFrom``)."""
        if self.num_data() != other.num_data():
            raise ValueError("add_features_from needs equal row counts")
        f0 = self.num_feature()
        if _is_scipy_sparse(self.data) or _is_scipy_sparse(other.data):
            import scipy.sparse as sp
            self.data = sp.hstack([self.data, other.data], format="csr")
        else:
            self.data = np.concatenate([self.data, other.data], axis=1)
        if isinstance(self.feature_name, list) \
                or isinstance(other.feature_name, list):
            def _names(ds, base):
                if isinstance(ds.feature_name, list):
                    return list(ds.feature_name)
                return [f"Column_{base + i}" for i in range(ds.num_feature())]
            self.feature_name = _names(self, 0) + _names(other, f0)

        def _cats_as_ints(ds, base):
            spec = ds.categorical_feature
            if not isinstance(spec, (list, tuple)):
                return []
            names = (ds.feature_name if isinstance(ds.feature_name, list)
                     else [])
            out = []
            for c in spec:
                if isinstance(c, int):
                    out.append(c + base)
                elif c in names:
                    out.append(names.index(c) + base)
                else:
                    raise ValueError(
                        f"categorical feature {c!r} not resolvable during "
                        "add_features_from; use integer indices")
            return out

        cats = _cats_as_ints(self, 0) + _cats_as_ints(other, f0)
        if cats:
            self.categorical_feature = cats
        self._train_data = None
        return self

    def set_position(self, position):
        """Per-row positions for unbiased LTR (reference
        ``Dataset.set_position`` / Metadata positions)."""
        self.position = None if position is None else np.asarray(position)
        self._train_data = None
        return self

    def set_group(self, group):
        self.group = None if group is None else np.asarray(group, np.int64)
        self._train_data = None
        return self

    def save_binary(self, filename: str) -> "Dataset":
        """Save the constructed dataset to a binary cache file (reference
        ``Dataset.save_binary`` -> ``LGBM_DatasetSaveBinary``)."""
        self.construct().save_binary(filename)
        return self

    def to_shards(self, path: str, rows_per_shard: Optional[int] = None,
                  params: Optional[Dict[str, Any]] = None,
                  resume: bool = False):
        """Partition the constructed (binned) dataset into a sharded
        streaming store at ``path`` (lightgbm_tpu/stream/,
        docs/STREAMING.md): fixed-row-count checksummed shard frames plus
        a manifest carrying the bin-mapper identity.  Honors
        ``free_raw_data``: the raw host matrix is released once the
        binned representation exists, so the store build's host RSS is
        bounded by binned + one shard instead of raw + binned.  Returns
        the opened :class:`~.stream.store.ShardedDataset`."""
        from .config import Config
        from .stream.store import dataset_to_shards
        if rows_per_shard is None:
            rows_per_shard = Config(
                self._merged_params(params)).tpu_stream_rows_per_shard
        return dataset_to_shards(self, path, rows_per_shard,
                                 params=params, resume=resume)

    def create_valid(self, data, label=None, weight=None, group=None,
                     init_score=None, params=None) -> "Dataset":
        return Dataset(data, label=label, reference=self, weight=weight,
                       group=group, init_score=init_score,
                       params=params or self.params)


class Booster:
    """Gradient-boosting model handle (reference ``basic.py:3586``)."""

    def __init__(
        self,
        params: Optional[Dict[str, Any]] = None,
        train_set: Optional[Dataset] = None,
        model_file: Optional[str] = None,
        model_str: Optional[str] = None,
        valid_sets: TypingSequence[Tuple[str, Dataset]] = (),
        base_model=None,
    ):
        self.params = dict(params or {})
        self.best_iteration = -1
        self.best_score: Dict = {}
        if model_file is not None or model_str is not None:
            from .serialization import load_model_string
            if model_file is not None:
                with open(model_file) as fh:
                    model_str = fh.read()
            self._gbdt = load_model_string(model_str)
            self.cfg = self._gbdt.cfg
            return
        if train_set is None:
            raise ValueError("either train_set or a model must be provided")
        self.cfg = Config(self.params)
        td = train_set.construct(self.params)
        valid_td = [(nm, ds.construct(self.params)) for nm, ds in valid_sets]
        if self.cfg.boosting == "dart":
            cls = DART
        elif self.cfg.boosting == "rf":
            cls = RandomForest
        else:
            cls = GBDT
        # ``train/booster_init``: objective init (lambdarank's query
        # tables), the initial score, the device copies, the growth plan
        with span("train/booster_init"):
            self._gbdt = cls(self.cfg, td, valid_td, base_model=base_model)
        self.train_set = train_set

    # ------------------------------------------------------------------- train
    def update(self, train_set=None, fobj=None) -> bool:
        """One boosting iteration; returns True if training should stop
        (reference ``Booster.update`` -> ``LGBM_BoosterUpdateOneIter``)."""
        if fobj is not None:
            score = self._gbdt.scores
            import jax
            grad, hess = fobj(np.asarray(jax.device_get(score)),
                              self.train_set)
            return self._gbdt.train_one_iter(np.asarray(grad), np.asarray(hess))
        return self._gbdt.train_one_iter()

    def update_pack(self, num_rounds: int = 1):
        """Train up to ``num_rounds`` boosting rounds in ONE scanned device
        dispatch (the iteration-packed path, docs/ITER_PACK.md).  Returns
        ``(rounds_done, finished)``.  Falls back to per-round :meth:`update`
        when the config cannot pack (the plan's auto-degrade list)."""
        k, use_pack = self._gbdt.iter_pack_plan(num_rounds)
        if not use_pack:
            done, finished = 0, False
            for _ in range(num_rounds):
                finished = self.update()
                done += 1
                if finished:
                    break
            return done, finished
        rounds, finished = self._gbdt.train_pack(min(k, num_rounds))
        for rnd in rounds:
            self._gbdt.commit_round(rnd)
        return len(rounds), finished

    def rollback_one_iter(self) -> "Booster":
        self._gbdt.rollback_one_iter()
        return self

    def reset_parameter(self, params: Dict[str, Any]) -> "Booster":
        self.params.update(params)
        self._gbdt.cfg.update(params)
        return self

    def _evals(self, feval=None) -> List[Tuple[str, str, float, bool]]:
        res = self._gbdt.eval_set()
        if feval is not None:
            import jax
            for i, (name, data) in enumerate([("training", self._gbdt.train_data)]
                                             + list(self._gbdt.valids)):
                scores = (self._gbdt.scores if name == "training"
                          else self._gbdt.valid_scores[i - 1])
                sc = np.asarray(jax.device_get(scores))
                out = feval(sc, data)
                if out is not None:
                    if not isinstance(out, list):
                        out = [out]
                    for metric, value, hb in out:
                        res.append((name, metric, value, hb))
        return res

    # ----------------------------------------------------------------- predict
    def predict(self, data, start_iteration: int = 0,
                num_iteration: Optional[int] = None, raw_score: bool = False,
                pred_leaf: bool = False, pred_contrib: bool = False,
                **kwargs) -> np.ndarray:
        if num_iteration is None and self.best_iteration > 0:
            num_iteration = self.best_iteration
        if start_iteration == 0:
            start_iteration = int(kwargs.pop("start_iteration_predict", 0))
        # Sparse predict batches stay sparse into host binning (a Bosch-
        # class CSR must not densify at predict either); only pred_leaf/
        # pred_contrib and the NaN shape-pad need a dense copy.
        sparse_in = _is_scipy_sparse(data)
        data2 = data.tocsr() if sparse_in else _as_2d(data)
        nf = self.num_feature()
        if data2.shape[1] != nf:
            # reference predict_disable_shape_check semantics: extra columns
            # are sliced, missing ones are an error unless disabled (padded
            # with NaN -> routed by missing handling).
            if not kwargs.pop("predict_disable_shape_check", False):
                raise ValueError(
                    f"data has {data2.shape[1]} features, model expects "
                    f"{nf}; pass predict_disable_shape_check=True to "
                    "override (reference LGBM_BoosterPredictForMat check)")
            if data2.shape[1] > nf:
                data2 = data2[:, :nf]      # CSR column slice stays sparse
            elif sparse_in:
                # only the NaN pad needs a dense copy
                data2 = np.asarray(data2.todense(), np.float64)
                sparse_in = False
                pad = np.full((data2.shape[0], nf - data2.shape[1]), np.nan)
                data2 = np.concatenate([data2, pad], axis=1)
            else:
                pad = np.full((data2.shape[0], nf - data2.shape[1]), np.nan)
                data2 = np.concatenate([data2, pad], axis=1)
        data = data2
        if pred_leaf or pred_contrib:
            if getattr(self._gbdt, "base_model", None) is not None:
                raise ValueError(
                    "pred_leaf/pred_contrib on a continuation booster is not "
                    "supported yet; save_model() and reload, then predict")
            from .explain import predict_leaf_index, predict_contrib
            fn = predict_leaf_index if pred_leaf else predict_contrib
            dense = (np.asarray(data.todense(), np.float64) if sparse_in
                     else _as_2d(data))
            return fn(self._gbdt, dense, start_iteration, num_iteration)
        es_kwargs = {kk: vv for kk, vv in kwargs.items()
                     if kk.startswith("pred_early_stop")}
        return self._gbdt.predict(data if sparse_in else _as_2d(data),
                                  raw_score=raw_score,
                                  num_iteration=num_iteration,
                                  start_iteration=start_iteration,
                                  **es_kwargs)

    def serving_predictor(self, **kwargs):
        """A long-lived compiled :class:`~lightgbm_tpu.serve.Predictor` for
        this booster (frozen slice, device-resident tree pack, shape-
        bucketed batching, serving metrics — docs/SERVING.md).  Keyword
        arguments are forwarded (``raw_score``, ``num_iteration``,
        ``start_iteration``, ``ladder``, ``max_compiles``)."""
        from .serve import Predictor
        return Predictor(self, **kwargs)

    # -------------------------------------------------------------------- misc
    @property
    def current_iteration(self) -> int:
        base = getattr(self._gbdt, "base_model", None)
        return self._gbdt.iter_ + (base.iter_ if base is not None else 0)

    def num_trees(self) -> int:
        return self._gbdt.num_trees

    def num_model_per_iteration(self) -> int:
        return self._gbdt.num_class

    def num_feature(self) -> int:
        td = getattr(self._gbdt, "train_data", None)
        if td is not None:
            return td.num_features
        return int(self._gbdt.num_features)  # LoadedModel

    def feature_name(self) -> List[str]:
        names = self._gbdt.train_data.feature_names
        return names or [f"Column_{i}"
                         for i in range(self._gbdt.train_data.num_features)]

    def feature_importance(self, importance_type: str = "split",
                           iteration=None) -> np.ndarray:
        return self._gbdt.feature_importance(importance_type)

    def model_to_string(self, num_iteration: Optional[int] = None,
                        start_iteration: int = 0) -> str:
        from .serialization import LoadedModel, model_to_string
        if isinstance(self._gbdt, LoadedModel):
            return self._gbdt.to_string(num_iteration=num_iteration,
                                        start_iteration=start_iteration)
        return model_to_string(self._gbdt, num_iteration=num_iteration,
                               start_iteration=start_iteration)

    def save_model(self, filename: str, num_iteration: Optional[int] = None,
                   start_iteration: int = 0) -> "Booster":
        with open(filename, "w") as fh:
            fh.write(self.model_to_string(num_iteration, start_iteration))
        return self

    def dump_model(self, num_iteration: Optional[int] = None,
                   start_iteration: int = 0) -> dict:
        """JSON-style model dict (reference ``LGBM_BoosterDumpModel`` /
        Python ``Booster.dump_model``)."""
        from .serialization import model_to_dict
        return model_to_dict(self._gbdt, num_iteration=num_iteration,
                             start_iteration=start_iteration)

    def trees_to_dataframe(self):
        """Flat per-node table (reference ``Booster.trees_to_dataframe``);
        returns a list of dicts (pandas-free)."""
        rows = []
        dump = self.dump_model()
        names = dump["feature_names"]

        def walk(tree_idx, node, parent=None, depth=0):
            if "leaf_index" in node:
                rows.append({
                    "tree_index": tree_idx, "node_depth": depth,
                    "node_index": f"{tree_idx}-L{node['leaf_index']}",
                    "parent_index": parent, "split_feature": None,
                    "threshold": None, "value": node["leaf_value"],
                    "count": node.get("leaf_count"),
                })
                return
            ni = f"{tree_idx}-S{node['split_index']}"
            rows.append({
                "tree_index": tree_idx, "node_depth": depth,
                "node_index": ni, "parent_index": parent,
                "split_feature": names[node["split_feature"]],
                "threshold": node["threshold"],
                "split_gain": node["split_gain"],
                "value": node["internal_value"],
                "count": node["internal_count"],
            })
            walk(tree_idx, node["left_child"], ni, depth + 1)
            walk(tree_idx, node["right_child"], ni, depth + 1)

        for info in dump["tree_info"]:
            walk(info["tree_index"], info["tree_structure"])
        return rows

    def eval(self, data: Dataset, name: str, feval=None):
        """Evaluate the current model on an arbitrary dataset (reference
        ``Booster.eval`` -> ``LGBM_BoosterGetEval`` on an added valid set).
        Unlike training valid_sets the scores are recomputed per call."""
        label = data.label
        weight = data.weight
        group = data.group
        raw = self._gbdt.predict_raw(data.data)
        raw = np.asarray(raw, np.float64)
        metrics = getattr(self._gbdt, "metrics", None)
        if metrics is None:  # loaded (prediction-only) booster
            from .metrics import metrics_for_config
            metrics = metrics_for_config(self._gbdt.cfg)
        out = []
        for m in metrics:
            out.append((name, m.name,
                        m(label, raw, weight, group),
                        m.higher_better))
        if feval is not None:
            res = feval(raw, data)
            if res is not None:
                if not isinstance(res, list):
                    res = [res]
                for metric, value, hb in res:
                    out.append((name, metric, value, hb))
        return out

    def refit(self, data, label, decay_rate: float = 0.9, weight=None,
              group=None, **kwargs) -> "Booster":
        """Refit leaf values on new data keeping all tree structures
        (reference ``GBDT::RefitTree``, ``gbdt.cpp:258``; new leaf output =
        decay_rate * old + (1 - decay_rate) * refit).  ``weight``/``group``
        feed the objective's gradients like the reference's Metadata."""
        from .refit import refit_booster, refit_loaded
        from .serialization import LoadedModel
        if isinstance(self._gbdt, LoadedModel):
            new_model = refit_loaded(self._gbdt, _as_2d(data),
                                     np.asarray(label), decay_rate,
                                     weight=weight, group=group)
            out = copy.copy(self)
            out._gbdt = new_model
            return out
        return refit_booster(self, _as_2d(data), np.asarray(label),
                             decay_rate, self.params,
                             weight=weight, group=group)

    def eval_train(self, feval=None):
        return [e for e in self._evals(feval) if e[0] == "training"]

    def eval_valid(self, feval=None):
        return [e for e in self._evals(feval) if e[0] != "training"]
