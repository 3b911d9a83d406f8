"""Layer ``entry``: the window's slowest iteration against its neighbours
— the largest ``period / median(period of the 8 nearest records)`` over the
window's records (``benchmark/host_timeline.py``).  1.00-1.05 in a sound
run; a stalled iteration reads 2 and more, and its whole record (CPU,
run-queue wait, switches, faults, compiles) goes to standard error."""

import json

from .. import host_timeline


def read(trace, facts):
    if facts["peak"] is None:
        return None
    win = host_timeline.window_records(trace, facts)
    if win is None:
        return None
    ratio, rec = host_timeline.slowest(win)
    host_timeline.log(f"slowest iteration of {len(win)}: {ratio:.4f} x its "
                      f"neighbours: {json.dumps(rec)}")
    return ratio
