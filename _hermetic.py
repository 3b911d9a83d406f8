"""Repo-root shim: the canonical implementation lives in the package
(``lightgbm_tpu/utils/hermetic.py``) so library code — e.g. the
multi-process launcher — can use it when installed.  Loaded here by FILE
PATH, not package import: ``force_cpu`` must run before jax's backend
initializes, and importing the package imports jax."""

import importlib.util as _ilu
import os as _os

_spec = _ilu.spec_from_file_location(
    "lightgbm_tpu_hermetic_impl",
    _os.path.join(_os.path.dirname(_os.path.abspath(__file__)),
                  "lightgbm_tpu", "utils", "hermetic.py"))
_mod = _ilu.module_from_spec(_spec)
_spec.loader.exec_module(_mod)

cpu_env = _mod.cpu_env
force_cpu = _mod.force_cpu
