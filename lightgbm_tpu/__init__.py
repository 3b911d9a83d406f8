"""lightgbm_tpu — a TPU-native gradient boosting framework.

A from-scratch re-design of LightGBM's capabilities (reference: h2oai/LightGBM)
for TPU hardware: histogram GBDT/DART/RF with a fully device-resident training
loop expressed as XLA programs (one-hot MXU histogram contractions, vectorized
split scans, static-shape leaf-wise growth), data/feature-parallel scaling via
``jax.sharding`` meshes, and a lightgbm-compatible Python API.
"""

from .basic import Booster, Dataset, Sequence
from .callback import EarlyStopException, early_stopping, log_evaluation, \
    record_evaluation, reset_parameter
from .config import Config
from .engine import cv, train

__version__ = "0.1.0"

__all__ = [
    "Booster", "Dataset", "Config", "train", "cv",
    "early_stopping", "log_evaluation", "record_evaluation",
    "reset_parameter", "EarlyStopException",
    "LGBMModel", "LGBMClassifier", "LGBMRegressor", "LGBMRanker",
    "plot_importance", "plot_metric", "plot_split_value_histogram",
    "plot_tree", "create_tree_digraph",
    "Sequence",
]

_PLOT_FNS = ("plot_importance", "plot_metric", "plot_split_value_histogram",
             "plot_tree", "create_tree_digraph")


def __getattr__(name):
    # sklearn wrappers / plotting / serving are imported lazily to keep the
    # base import light.
    if name in ("serve", "stream"):
        # importlib (not ``from . import``): the fromlist machinery would
        # re-enter this __getattr__ and recurse.
        import importlib
        return importlib.import_module(f".{name}", __name__)
    if name in ("LGBMModel", "LGBMClassifier", "LGBMRegressor", "LGBMRanker"):
        from . import sklearn as _sk
        return getattr(_sk, name)
    if name in _PLOT_FNS:
        from . import plotting as _pl
        return getattr(_pl, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
