"""Hierarchical wall-clock timer: per-name totals and counts.

Reference: ``Common::Timer`` (``utils/common.h:973``).  The one span system
built on it is ``telemetry.spans`` (``span`` opens the profiler annotation
and adds the duration here); nothing else times spans.

Thread-safety: concurrent serve threads (MicroBatcher worker + caller
threads) time spans on the SAME instance, so every mutation is
lock-guarded and in-flight starts are tracked per ``(thread, name)`` as a
STACK — nested same-name spans on one thread are re-entrancy-safe (each
``stop`` closes the innermost matching ``start``)."""

from __future__ import annotations

import collections
import threading
import time
from typing import Dict, List, Tuple


class Timer:
    def __init__(self):
        self._lock = threading.Lock()
        self.durations: Dict[str, float] = collections.defaultdict(float)
        self.counts: Dict[str, int] = collections.defaultdict(int)
        # (thread ident, name) -> stack of perf_counter starts
        self._starts: Dict[Tuple[int, str], List[float]] = {}

    def start(self, name: str) -> None:
        t = time.perf_counter()
        key = (threading.get_ident(), name)
        with self._lock:
            self._starts.setdefault(key, []).append(t)

    def stop(self, name: str) -> None:
        t = time.perf_counter()
        key = (threading.get_ident(), name)
        with self._lock:
            stack = self._starts.get(key)
            if not stack:
                return   # unmatched stop (or a different thread's start)
            t0 = stack.pop()
            if not stack:
                del self._starts[key]
            self.durations[name] += t - t0
            self.counts[name] += 1

    def add(self, name: str, seconds: float) -> None:
        """Aggregate an externally-measured duration (telemetry spans)."""
        with self._lock:
            self.durations[name] += float(seconds)
            self.counts[name] += 1

    def snapshot(self) -> List[Tuple[str, float, int]]:
        """``(name, total_seconds, count)`` rows, longest first."""
        with self._lock:
            return sorted(((n, self.durations[n], self.counts[n])
                           for n in self.durations),
                          key=lambda row: -row[1])

    def reset(self) -> None:
        with self._lock:
            self.durations.clear()
            self.counts.clear()
            self._starts.clear()

    def summary(self) -> str:
        lines = ["LightGBM-TPU timer summary:"]
        for name, secs, cnt in self.snapshot():
            lines.append(f"  {name}: {secs:.3f}s (x{cnt})")
        return "\n".join(lines)
