"""Layer ``device``: 100 x (1 - union of the intervals in which any
operation ran on the device / the traced window)."""


def read(trace, facts):
    busy = trace.busy_ns(facts["window"])
    if busy is None:
        return None
    lo, hi = facts["window"]
    return 100.0 * (1.0 - busy / (hi - lo))
