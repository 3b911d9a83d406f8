"""Layer ``grower``: device seconds per boosting iteration of the row gathers that feed the histogram kernels: non-kernel operations under ``grow/wave_gather`` (the fused wave's ``dynamic_slice`` of the permutation, ``bins_pad[seg]``, ``vals_pad[seg]``, pad and transpose) and under ``grow/hist`` (the unfused per-leaf gather and the layout work around ``histogram_flat``).
Union of the operations' intervals in the traced window; which scopes
count is ``scope_names.json``."""

from .. import scopes


def read(trace, facts):
    return scopes.metric_seconds(trace, facts, "gather_s_per_iter")
