"""Layer ``grower``: device seconds per boosting iteration of everything else the grower does outside the kernels: root set-up, leaf selection, layout conversions around the fused kernel, subtract, XLA scan, collectives, tree and per-leaf state writes, the finish.
Union of the operations' intervals in the traced window; which scopes
count is ``scope_names.json``."""

from .. import scopes


def read(trace, facts):
    return scopes.metric_seconds(trace, facts, "grow_state_s_per_iter")
