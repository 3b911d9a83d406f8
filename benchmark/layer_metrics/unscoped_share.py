"""Layer ``device``: 100 x the non-kernel busy time in which no operation
of any phase ran (operations with no ``PHASES`` scope in their path, and
time only an enclosing ``while`` covers) over all non-kernel busy time —
the phase scopes' own health check."""

from .. import scopes


def read(trace, facts):
    if facts["peak"] is None:
        return None
    split = scopes.split_ns(trace, facts)
    if not split or not split["non_kernel"] or not split["unscoped"]:
        return None
    return 100.0 * split["unscoped"] / split["non_kernel"]
