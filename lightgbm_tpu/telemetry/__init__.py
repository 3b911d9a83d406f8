"""lightgbm_tpu.telemetry — unified observability layer.

One process-wide home for the three signal families every subsystem
publishes (docs/OBSERVABILITY.md):

- **Metrics registry** (:mod:`.registry`): counters / gauges / histograms
  with bounded reservoirs.  Training, resilience (health sentinel,
  watchdog, checkpoints) and serving all publish here;
  :func:`render_prometheus` turns any snapshot into a scrape answer.
- **Spans** (:mod:`.spans`): ``with span("train/grow")`` wraps
  ``jax.profiler.TraceAnnotation`` + the lock-guarded hierarchical host
  timer behind one context manager.  Host-side, at dispatch boundaries
  only — ``tpu_telemetry=off`` compiles bitwise-identical programs.
  Inside the compiled iteration, ``phase(name)`` (a ``jax.named_scope``
  from the one list ``PHASES``) names each phase in the HLO metadata, so
  a device trace splits by the program's own names.
- **JSONL events** (:mod:`.events`): ``tpu_telemetry_log=<path>`` streams
  schema-versioned, monotonic-clocked events (``train.iter`` per committed
  round with dispatch-wait vs host-bookkeeping wall split, checkpoint
  durations, health verdicts, serve snapshots) that
  ``tools/telemetry_report.py`` replays into a triage table.

Knobs: ``tpu_telemetry=on|off`` (off is bitwise-inert),
``tpu_telemetry_log=<path>``, ``tpu_profile_iters=N`` (+
``tpu_profile_dir``) for a first-N-iterations ``jax.profiler`` trace.
"""

from __future__ import annotations

import time
from typing import Dict

from .events import (SCHEMA_VERSION, JsonlSink, active_sink, close_log,
                     configure_log, emit)
from .iters import (dispatched, iter_record, iter_records,
                    last_iter_record, note_program)
from .memory import (MEMORY_MODES, MemoryTracker, arm_memory_from_config,
                     device_memory_stats, host_peak_rss_mb, listen_to_jit,
                     live_buffer_census, memory_analysis_summary,
                     memory_block, memory_mode, note_compile,
                     set_memory_mode)
from .prometheus import render_prometheus
from .registry import (Counter, Gauge, Histogram, MetricsRegistry, registry)
from .spans import (PHASES, SEGMENTS, enabled, instrument, kernel_rows,
                    phase, reset_spans, segment, set_enabled, span,
                    span_totals, watch_compiles)

__all__ = [
    "MEMORY_MODES", "PHASES", "SEGMENTS", "SCHEMA_VERSION", "Counter",
    "Gauge",
    "Histogram",
    "JsonlSink", "MemoryTracker", "MetricsRegistry", "TrainTelemetry",
    "active_sink", "arm_from_config", "arm_memory_from_config",
    "close_log", "configure_log", "device_memory_stats", "dispatched",
    "emit", "enabled", "host_peak_rss_mb", "instrument", "iter_record",
    "iter_records", "kernel_rows", "last_iter_record", "listen_to_jit",
    "live_buffer_census", "memory_analysis_summary", "memory_block",
    "memory_mode", "note_compile", "note_program", "phase", "registry",
    "render_prometheus", "reset_spans", "segment", "set_enabled",
    "set_memory_mode", "span", "span_totals", "telemetry_block",
    "train_session", "watch_compiles",
]

listen_to_jit()     # every compile of the process, from its first (jit.*)


def arm_from_config(cfg) -> bool:
    """Set the process-wide enable flag from a resolved Config
    (``tpu_telemetry``).  Called by every GBDT construction so raw
    ``Booster.update`` loops honor the knob too; returns the armed state."""
    on = getattr(cfg, "tpu_telemetry", "on") != "off"
    set_enabled(on)
    return on


def iter_event(rec, iteration: int, pack_size: int = 1, **fields) -> Dict:
    """The fields of a ``train.iter`` event from an iteration's record:
    ``wall_s`` is the record's period (update() to the next update(); for a
    run's last, open record: to now), ``dispatch_wait_s`` the part of it
    before the program was enqueued, ``host_s`` the rest; ``period_s`` and
    ``cpu_s`` are ``None`` while the record is open.  A pack's record is
    one dispatch of ``pack_size`` rounds: its seconds are amortised per
    round, its counts stay the pack's."""
    n = max(int(pack_size), 1)

    def secs(ns):
        return None if ns is None else round(ns / 1e9 / n, 6)

    rec = rec or {}
    enter, period = rec.get("enter_ns"), rec.get("period_ns")
    if enter is not None:
        wall = secs(time.time_ns() - enter if period is None else period)
        disp = secs((rec["dispatched_ns"] or enter) - enter)
        host = round(max(wall - disp, 0.0), 6)
    else:
        wall = disp = host = None
    return dict(
        iteration=iteration, wall_s=wall, dispatch_wait_s=disp, host_s=host,
        pack_size=pack_size, **fields, period_s=secs(period),
        cpu_s=secs(rec.get("cpu_ns")),
        involuntary_switches=rec.get("involuntary_switches"),
        major_faults=rec.get("major_faults"), compiles=rec.get("compiles"))


def telemetry_block() -> Dict:
    """The ``detail.telemetry`` block every BENCH blob (primary + rungs)
    carries: schema version, armed state, per-kind event counts, span
    totals and the registry snapshot — the whole observability state of
    the process in one JSON-safe dict."""
    snap = registry().snapshot()
    events = {name[len("event."):]: count
              for name, count in snap["counters"].items()
              if name.startswith("event.")}
    return {
        "schema": SCHEMA_VERSION,
        "enabled": enabled(),
        "events": events,
        "spans": span_totals(),
        "registry": snap,
    }


class TrainTelemetry:
    """Per-``engine.train`` telemetry session: arms the enable flag and the
    JSONL sink from the config, tracks span deltas, and closes the sink it
    opened on :meth:`close` (the leak the conftest guard warns about)."""

    def __init__(self, cfg):
        self.enabled = arm_from_config(cfg)
        # Device-memory accounting mode (telemetry/memory.py): armed per
        # run from tpu_telemetry_memory, exactly like the master switch.
        self.memory_mode = arm_memory_from_config(cfg)
        self.log_path = getattr(cfg, "tpu_telemetry_log", "") or None
        self.profile_iters = int(getattr(cfg, "tpu_profile_iters", 0) or 0)
        self.profile_dir = getattr(cfg, "tpu_profile_dir", "") or (
            f"{self.log_path}.trace" if self.log_path
            else "/tmp/lightgbm_tpu_profile")
        self._opened_sink = False
        if self.enabled and self.log_path:
            configure_log(self.log_path)
            self._opened_sink = True
        self._span_base = {n: d["seconds"]
                          for n, d in span_totals().items()}
        self._profiling = False
        self._held = []       # train.iter events whose record is still open

    # ------------------------------------------------------------ events
    def emit(self, kind: str, **fields) -> None:
        if self.enabled:
            emit(kind, **fields)

    def emit_iter(self, iteration: int, pack_size: int = 1,
                  **fields) -> None:
        """One ``train.iter`` event of a committed round: a view of the
        iteration's record (:func:`iter_event`), so it is HELD until the
        next iteration has closed that record; :meth:`flush_iters` writes
        what a run's end leaves open."""
        if not self.enabled:
            return
        self.flush_iters(closed_only=True)
        self._held.append((last_iter_record(), dict(
            iteration=iteration, pack_size=pack_size, **fields)))

    def flush_iters(self, closed_only: bool = False) -> None:
        held, self._held = self._held, []
        for rec, fields in held:
            if closed_only and rec is not None and rec["period_ns"] is None:
                self._held.append((rec, fields))
            else:
                self.emit("train.iter", **iter_event(rec, **fields))

    def span_delta(self) -> Dict[str, float]:
        """Per-span seconds accumulated since this session started."""
        out = {}
        for name, d in span_totals().items():
            dt = d["seconds"] - self._span_base.get(name, 0.0)
            if dt > 0:
                out[name] = round(dt, 6)
        return out

    # --------------------------------------------------------- profiling
    def maybe_start_profile(self) -> None:
        """Arm the ``jax.profiler`` trace for the first
        ``tpu_profile_iters`` committed rounds (ROADMAP 3: a live-TPU
        round lands with Mosaic kernel traces in hand)."""
        if not self.enabled or self.profile_iters <= 0 or self._profiling:
            return
        try:
            import jax
            jax.profiler.start_trace(self.profile_dir)
            self._profiling = True
            self.emit("profile.start", trace_dir=self.profile_dir,
                      iters=self.profile_iters)
        except Exception as e:  # noqa: BLE001 — profiling is best-effort
            from ..utils.log import Log
            Log.warning(f"telemetry: jax.profiler trace failed to start "
                        f"({e}); training continues unprofiled")
            self.profile_iters = 0

    def maybe_stop_profile(self, committed_rounds: int) -> None:
        if not self._profiling or committed_rounds < self.profile_iters:
            return
        self._stop_profile()

    def _stop_profile(self) -> None:
        if not self._profiling:
            return
        self._profiling = False
        try:
            import jax
            jax.profiler.stop_trace()
            self.emit("profile.stop", trace_dir=self.profile_dir)
            from ..utils.log import Log
            Log.info(f"telemetry: profiler trace written to "
                     f"{self.profile_dir} (tensorboard --logdir "
                     f"{self.profile_dir})")
        except Exception:  # noqa: BLE001 — stop must never fail training
            pass

    # ------------------------------------------------------------- close
    def close(self) -> None:
        self._stop_profile()
        if self._opened_sink:
            close_log()
            self._opened_sink = False


def train_session(cfg) -> TrainTelemetry:
    return TrainTelemetry(cfg)
