"""Layer ``boosting_loop``: device seconds per boosting iteration of gradients and hessians (with the in-trace GOSS mask) and the score update (``boost/*``).
Union of the operations' intervals in the traced window; which scopes
count is ``scope_names.json``."""

from .. import scopes


def read(trace, facts):
    return scopes.metric_seconds(trace, facts, "boost_s_per_iter")
