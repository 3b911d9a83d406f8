"""Layer ``pallas_kernels``: 100 x the rows an iteration NEEDS histogrammed
(``work.rows_hist``: the root and every split's smaller child) over the
rows its histogram kernels were handed (``rows<R>`` of each kernel
event's scope, summed over the launches in the traced window)."""

from .. import scopes


def read(trace, facts):
    if facts["peak"] is None:
        return None
    fed = scopes.rows_fed(trace, facts)
    if not fed or not facts["needed"]["rows_hist"]:
        return None
    return 100.0 * facts["needed"]["rows_hist"] / fed
