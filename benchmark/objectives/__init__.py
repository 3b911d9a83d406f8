"""The objectives' plain forms, one file each, found by the ``objective`` in a
configuration's ``params``: ``objectives/<name>.py`` holds
``make(params, label, group)`` returning an object with ``init_score()`` and
``gradients(score) -> (grad, hess)``, in numpy float64 and importing nothing
of the program.  A later PR adds an objective by adding a file.
"""

import importlib

import numpy as np


def load(params: dict, label, group=None):
    module = importlib.import_module(f"{__name__}.{params['objective']}")
    return module.make(params, np.asarray(label, np.float64),
                       None if group is None else np.asarray(group, np.int64))
