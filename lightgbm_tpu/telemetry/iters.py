"""The host's side of a boosting iteration: ONE record per iteration, made
where the work happens (``GBDT.train_one_iter`` / ``train_pack``, the
streamed ``train_round``) and kept in a bounded ring, so every caller feeds
it — a raw ``Booster.update()`` loop, ``engine.train``, the benchmark
(docs/OBSERVABILITY.md has the field table).

    with iter_record(i) as rec:        # one clock read, one TraceAnnotation
        ...host work, dispatch...      # _dispatch names the programs
        dispatched(rec)                # the counters, AFTER the enqueue

Record ``i`` describes iteration ``i``: ``enter_ns`` / ``dispatched_ns`` on
``time.time_ns()`` (the profiler's host clock is the realtime clock;
``perf_counter`` has another epoch), ``period_ns`` = the next record's
``enter_ns`` minus this one's — the iteration as the program sees it, its
device time and the caller's work between two calls included — and the
differences of the process's counters between the read after THIS
iteration's dispatch and the read after the NEXT one's.  A record is
therefore closed by the next iteration; a run's last record stays open
(``period_ns`` ``None``) and readers use closed records.

Nothing here enters a traced function: the compiled programs are byte for
byte what they are without it.  ``tpu_telemetry=off`` costs one flag read
and leaves no record.
"""

from __future__ import annotations

import gc
import json
import os
import statistics
import time
from collections import deque
from typing import Dict, List, Optional

try:
    import resource
except ImportError:          # Windows: those fields stay None
    resource = None

from . import memory, spans

RING = 4096                  # records kept
STALL_RATIO = 3.0            # a period this many times the median ...
STALL_MIN_S = 0.1            # ... and this much over it is reported
STALL_HISTORY = 16           # periods of the same programs the median is of
STALL_MIN_HISTORY = 4        # fewer say nothing about "usual"
STALL_LINES = 8              # warning lines a process writes, at most

# The counters a record holds as differences, in the order _read() gives
# them.  Each source fails soft ONCE: a source that raises is dropped and its
# fields read None from then on.
DELTA_FIELDS = ("cpu_ns", "thread_cpu_ns", "voluntary_switches",
                "involuntary_switches", "major_faults", "minor_faults",
                "runq_wait_ns", "steal_ticks", "gc_collections",
                "compiles", "compile_s")

_ring: deque = deque(maxlen=RING)
_base: Optional[tuple] = None        # _read() after the newest dispatch
_read_for: Optional[dict] = None     # the record that read was made for
_history: Dict[tuple, deque] = {}    # programs -> its last closed periods
_stall_lines = 0
_proc_fds: Dict[str, int] = {}


def _pread(path: str) -> bytes:
    fd = _proc_fds.get(path)
    if fd is None:
        fd = _proc_fds[path] = os.open(path, os.O_RDONLY)
    return os.pread(fd, 256, 0)


def _rusage():
    r = resource.getrusage(resource.RUSAGE_SELF)
    return r.ru_nvcsw, r.ru_nivcsw, r.ru_majflt, r.ru_minflt


# [reader, number of fields]: the main thread's run-queue wait is the second
# field of /proc/self/schedstat (the thread group's leader), the machine's
# steal ticks the eighth number of /proc/stat's first line.
_SOURCES = [
    [lambda: (time.process_time_ns(), time.thread_time_ns()), 2],
    [_rusage, 4],
    [lambda: (int(_pread("/proc/self/schedstat").split(None, 2)[1]),), 1],
    [lambda: (int(_pread("/proc/stat").split(None, 9)[8]),), 1],
    [lambda: (gc.get_stats()[2]["collections"],), 1],
    [memory.jit_totals, 2],
]


def _read() -> tuple:
    out = ()
    for src in _SOURCES:
        try:
            out += src[0]()
        except Exception:  # noqa: BLE001 — observation never raises: the
            n = src[1]     # source is dropped, its fields read None
            src[0] = lambda n=n: (None,) * n
            out += (None,) * n
    return out


_BLANK = dict.fromkeys(("iter", "count", "enter_ns", "dispatched_ns",
                        "period_ns", "programs") + DELTA_FIELDS)


class iter_record:
    """``with iter_record(i, count) as rec:`` around one boosting iteration
    (``count`` > 1: a pack of iterations ``i .. i + count - 1`` in one
    dispatch).  ONE clock read on entry; the whole body shows in a profiler
    trace as the host span ``train/iter`` carrying ``iter`` and ``t_ns`` —
    that read, the anchor that converts the trace's relative clock to the
    records'.  ``rec`` is ``None`` with telemetry off."""

    __slots__ = ("_iter", "_count", "_rec", "_trace")

    def __init__(self, iteration: int, count: int = 1):
        self._iter = int(iteration)
        self._count = int(count)
        self._rec = None
        self._trace = None

    def __enter__(self):
        if not spans.enabled():
            return None
        t = time.time_ns()
        if _ring and _ring[-1]["period_ns"] is None:
            _ring[-1]["period_ns"] = t - _ring[-1]["enter_ns"]
        rec = self._rec = dict(_BLANK, iter=self._iter, count=self._count,
                               enter_ns=t, programs=[])
        _ring.append(rec)
        try:
            import jax.profiler
            self._trace = jax.profiler.TraceAnnotation(
                "train/iter", iter=self._iter, t_ns=t)
            self._trace.__enter__()
        except Exception:  # noqa: BLE001 — the profiler is garnish
            self._trace = None
        return rec

    def __exit__(self, *exc):
        if self._rec is None:
            return False
        if self._trace is not None:
            try:
                self._trace.__exit__(*exc)
            except Exception:  # noqa: BLE001
                pass
        dispatched(self._rec)            # for a path that never said so
        return False


def dispatched(rec) -> None:
    """The iteration's programs are enqueued: stamp ``dispatched_ns`` (where
    ``note_program`` has not), read the counters — after the dispatch, so
    the device never waits for the record — and close the record before."""
    global _base, _read_for
    if rec is None or rec is _read_for:
        return
    if rec["dispatched_ns"] is None:
        rec["dispatched_ns"] = time.time_ns()
    now = _read()
    prev = _ring[-2] if len(_ring) > 1 and _ring[-1] is rec else None
    if prev is not None and _base is not None:
        for name, a, b in zip(DELTA_FIELDS, _base, now):
            prev[name] = None if a is None or b is None else b - a
        _judge(prev)
    _base, _read_for = now, rec


def note_program(rec, name: str) -> None:
    """One compiled program of this iteration was handed to the device."""
    if rec is not None:
        rec["programs"].append(name)
        rec["dispatched_ns"] = time.time_ns()


def _judge(rec) -> None:
    """The stall rule: a closed period over ``STALL_RATIO`` x the median of
    the last ``STALL_HISTORY`` periods of the same programs, and at least
    ``STALL_MIN_S`` over it, writes ONE warning line with the whole record
    (at most ``STALL_LINES`` a process)."""
    global _stall_lines
    if rec["period_ns"] is None:
        return
    period = rec["period_ns"] / 1e9
    key = tuple(rec["programs"])
    hist = _history.get(key)
    if hist is None:
        hist = _history[key] = deque(maxlen=STALL_HISTORY)
    if len(hist) >= STALL_MIN_HISTORY and _stall_lines < STALL_LINES:
        median = statistics.median(hist)
        if (period > STALL_RATIO * median
                and period - median >= STALL_MIN_S):
            _stall_lines += 1
            from ..utils.log import Log
            Log.warning(
                f"iteration {rec['iter']} took {period:.3f} s from one "
                f"update() to the next, {period / median:.1f}x the median "
                f"{median:.3f} s of the last {len(hist)} (the caller's work "
                f"between the two calls is part of it): {json.dumps(rec)}")
    hist.append(period)


def iter_records() -> List[dict]:
    """The ring's records, oldest first (copies)."""
    return [dict(r) for r in _ring]


def last_iter_record() -> Optional[dict]:
    """The newest record itself (open until the next iteration)."""
    return _ring[-1] if _ring else None


def reset_iter_records() -> None:
    global _base, _read_for, _stall_lines
    _ring.clear()
    _history.clear()
    _base = _read_for = None
    _stall_lines = 0
