"""Layer ``entry``: the host's critical path from ``Booster.update()`` to
the iteration's program being enqueued — the mean ``dispatched_ns -
enter_ns`` over the window's records (``benchmark/host_timeline.py``).
Also says, on standard error, how the records lie against the trace's
``train/iter`` spans."""

from .. import host_timeline


def read(trace, facts):
    if facts["peak"] is None:
        return None
    win = host_timeline.window_records(trace, facts)
    if win is None:
        return None
    al = host_timeline.alignment(trace, facts)
    if al is not None:
        host_timeline.log(
            f"{al[0]} train/iter spans in the traced window, {al[1]} with a "
            f"record of the same iter, largest difference {al[2] / 1e6:.6f} "
            f"ms; {len(win)} records in the window")
    return sum(r["dispatched_ns"] - r["enter_ns"] for r in win) / len(win) / 1e9
