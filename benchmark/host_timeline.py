"""The host's side of the window, from the program's own records.

Since PR 35 the program keeps one record per boosting iteration
(``lightgbm_tpu.telemetry.iter_records()``: ``enter_ns`` / ``dispatched_ns``
on ``time.time_ns()``, ``period_ns`` = this ``update()`` to the next, the
programs it dispatched, CPU, switches, faults, compiles) and shows every
iteration in the profiler's trace as a host span ``train/iter`` whose stats
carry ``iter`` and ``t_ns`` — that span's own ``time.time_ns()`` at entry,
the anchor that converts the trace's relative clock to the records'.

``window_records`` picks THE WINDOW'S RECORDS: the trailing run of closed
records that dispatched the same programs with no compile, less its first,
and less the records whose period holds the harness's ``start_trace`` or
``stop_trace`` (the per-layer metrics are read in the traced run, where the
profiler holds the host for seconds between one fence and the next
``update()``): the edges of ``facts["window"]``, converted by the anchor,
say which those are.  Fewer than ``MIN_RECORDS`` is nothing to read.

Everything returns ``None`` on a program that keeps no such records.
"""

from __future__ import annotations

import statistics
import sys

from . import scopes
from . import trace as tracemod

MIN_RECORDS = 10
SPAN = "train/iter"


def records():
    """The program's iteration records, oldest first; ``None`` where the
    program keeps none."""
    from lightgbm_tpu import telemetry
    fn = getattr(telemetry, "iter_records", None)
    return fn() if fn is not None else None


def iter_spans(path: str = None) -> list:
    """``[{"iter", "t_ns", "start_ns", "duration_ns"}]``: the ``train/iter``
    spans of the run's newest ``.xplane.pb`` with their stats (which
    ``trace.load_xplane`` does not keep); ``[]`` where there are none."""
    path = path or scopes._newest_xplane()
    if path is None:
        return []
    import jax.profiler
    host = tracemod.names()["host_plane_prefix"]
    out = []
    for pl in jax.profiler.ProfileData.from_file(path).planes:
        if not pl.name.startswith(host):
            continue
        for ln in pl.lines:
            for e in ln.events:
                if e.name != SPAN:
                    continue
                stats = dict(e.stats)
                if "iter" in stats and "t_ns" in stats:
                    out.append({"iter": int(stats["iter"]),
                                "t_ns": int(stats["t_ns"]),
                                "start_ns": float(e.start_ns),
                                "duration_ns": float(e.duration_ns)})
    return out


def anchor_ns(spans: list):
    """Records' clock minus trace clock: the median of ``t_ns - start_ns``
    over the spans; ``None`` without a span."""
    return (statistics.median(s["t_ns"] - s["start_ns"] for s in spans)
            if spans else None)


def _spans_of(trace) -> list:
    cached = getattr(trace, "_iter_spans", None)
    if cached is None:
        cached = iter_spans()
        try:
            trace._iter_spans = cached       # two readers, one pass
        except AttributeError:
            pass
    return cached


def window_records(trace, facts: dict, recs: list = None):
    """The window's records (the module's docstring), or ``None``."""
    recs = records() if recs is None else recs
    if not recs:
        return None
    closed = [r for r in recs if r.get("period_ns") is not None]
    run = []
    for r in reversed(closed):
        if r["programs"] != closed[-1]["programs"] or r.get("compiles"):
            break
        run.append(r)
    run = run[::-1][1:]
    if facts.get("window") is not None:
        off = anchor_ns(_spans_of(trace))
        if off is None:
            return None
        edges = [e + off for e in facts["window"]]
        run = [r for r in run
               if not any(r["enter_ns"] <= e <= r["enter_ns"] + r["period_ns"]
                          for e in edges)]
    return run if len(run) >= MIN_RECORDS else None


def alignment(trace, facts: dict, recs: list = None):
    """``(spans in the traced window, of them with a record of the same
    iter, largest difference in ns)``: a span's start against its record's
    ``enter_ns`` and its end against ``dispatched_ns`` (which must lie
    inside it), both through the one anchor.  ``None`` without spans."""
    recs = records() if recs is None else recs
    if not recs or facts.get("window") is None:
        return None
    spans = _spans_of(trace)
    off = anchor_ns(spans)
    if off is None:
        return None
    by_iter = {r["iter"]: r for r in recs}
    lo, hi = facts["window"]
    n = matched = 0
    worst = 0.0
    for s in spans:
        if not lo <= s["start_ns"] < hi:
            continue
        n += 1
        r = by_iter.get(s["iter"])
        if r is None:
            continue
        matched += 1
        start, end = s["start_ns"] + off, s["start_ns"] + s["duration_ns"] + off
        worst = max(worst, abs(r["enter_ns"] - start),
                    (r["dispatched_ns"] or start) - end)
    return n, matched, worst


def slowest(win: list, neighbours: int = 8):
    """``(ratio, record)``: the largest ``period / median(period of the
    nearest neighbours)`` over the window's records — detrended, because
    trees lengthen with their index."""
    periods = [r["period_ns"] for r in win]
    best = (0.0, None)
    for j, p in enumerate(periods):
        lo = min(max(j - neighbours // 2, 0),
                 max(len(periods) - neighbours - 1, 0))
        near = periods[lo:j] + periods[j + 1:lo + neighbours + 1]
        ratio = p / statistics.median(near)
        if ratio > best[0]:
            best = (ratio, win[j])
    return best


def log(msg: str) -> None:
    sys.stderr.write(f"[host_timeline] {msg}\n")
    sys.stderr.flush()
