"""One reader per per-layer metric, found by the metric's name:
``layer_metrics/<name>.py`` holds ``read(trace, facts)``.

``trace`` is a ``benchmark.trace.Trace``; ``facts`` holds what the run
knows: ``window`` (start and end of the traced window, trace clock, ns),
``iters`` (boosting iterations inside it), ``needed`` (mean needed ops and
bytes per traced iteration, ``benchmark.work.needed``) and ``peak`` (the
chip's peaks).  A reader that finds nothing to read returns ``None`` and the
harness leaves the metric out of the line; it never returns 0 for a share.
"""

import importlib


def reader(name: str):
    return importlib.import_module(f"{__name__}.{name}").read
