"""Layer ``grower``: device seconds per boosting iteration of the go-left decision and the permutation scatter of every split leaf (``grow/partition``).
Union of the operations' intervals in the traced window; which scopes
count is ``scope_names.json``."""

from .. import scopes


def read(trace, facts):
    return scopes.metric_seconds(trace, facts, "partition_s_per_iter")
