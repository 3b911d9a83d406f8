"""Device-memory and compile telemetry (``tpu_telemetry_memory``,
docs/OBSERVABILITY.md memory section).

Three signal families, all publishing through the PR-9 registry/event
sink so one scrape (or one JSONL artifact) answers "where did the bytes
and compiles go":

- **Device-memory accounting** — :func:`device_memory_stats` snapshots
  ``device.memory_stats()`` (``bytes_in_use`` / ``peak_bytes_in_use``;
  gracefully ``None`` on backends that do not account, e.g. CPU) and
  :func:`live_buffer_census` groups ``jax.live_arrays()`` by
  (shape, dtype) with byte totals.  Any telemetry span opened with
  ``track_memory=True`` records its HBM delta + watermark into
  ``memory.*`` registry gauges and a ``memory.watermark`` JSONL event.
- **Host-side RSS** — :class:`MemoryTracker` owns the peak-RSS watermark
  (``VmHWM`` with a ``clear_refs`` reset where /proc allows, else
  ``ru_maxrss``); the engine publishes it as the
  ``memory.host_peak_rss_mb`` gauge.
- **Compile telemetry** — :func:`note_compile` (driven by the
  ``instrument()``/``watch_compiles()`` seam in spans.py) emits one
  ``compile.end`` event per XLA compile (program label, compile wall
  seconds, plus the ``compiled.memory_analysis()`` byte summary where the
  caller has the AOT object) and bumps the ``compile.count`` counter /
  ``compile.seconds`` histogram.

Arming: ``tpu_telemetry_memory=off|watermark|census``; ``off`` (the
default) is bitwise-inert — memory accounting is host-side observation at
span boundaries, never traced into a device program, so the lowered-HLO
equality pin from PR 9 extends to this knob
(tests/test_memory_telemetry.py).  ``watermark`` snapshots device memory
stats per tracked span; ``census`` additionally walks ``jax.live_arrays``
per tracked span — O(live buffers) host work, cheap next to a dispatch
but not free (the cost caveat in docs/OBSERVABILITY.md).  Compile
telemetry rides the master ``tpu_telemetry`` switch, not this knob: a
compile is a rare, expensive event worth counting whenever telemetry is
on at all.
"""

from __future__ import annotations

import sys
import threading
from typing import Any, Dict, Optional

try:
    import resource
except ImportError:          # Windows: no resource module — the VmHWM
    resource = None          # path is absent there too; report 0.0

from .registry import registry

MEMORY_MODES = ("off", "watermark", "census")

# How many (shape, dtype) groups a census keeps, and how many of those a
# memory.watermark EVENT carries (events are per-span — the log must not
# grow by a full census table per dispatch).
CENSUS_TOP_GROUPS = 12
EVENT_TOP_GROUPS = 4

_mode = "off"


def set_memory_mode(mode: str) -> str:
    """Set the process-wide accounting mode; returns the armed mode."""
    global _mode
    if mode not in MEMORY_MODES:
        raise ValueError(
            f"tpu_telemetry_memory={mode!r}: expected one of "
            f"{', '.join(MEMORY_MODES)}")
    _mode = mode
    return _mode


def memory_mode() -> str:
    return _mode


def arm_memory_from_config(cfg) -> str:
    """Arm the accounting mode from a resolved Config
    (``tpu_telemetry_memory``); engine.train calls this for every run."""
    return set_memory_mode(
        getattr(cfg, "tpu_telemetry_memory", "off") or "off")


def tracking_enabled() -> bool:
    """Memory accounting is live: mode is not ``off`` AND the master
    telemetry switch (``tpu_telemetry``) is on."""
    if _mode == "off":
        return False
    from . import spans
    return spans.enabled()


# ------------------------------------------------------------ device side
def device_memory_stats(device=None) -> Optional[Dict[str, int]]:
    """``device.memory_stats()`` snapshot (``bytes_in_use`` always,
    ``peak_bytes_in_use``/``bytes_limit`` where the allocator reports
    them) — or ``None``, gracefully, on backends without memory
    accounting (CPU jax returns None) or before jax is importable.

    ``peak_bytes_in_use`` counts the buffers the process held (bins,
    scores, trees); the arena the runtime reserves for the loaded
    programs' temporaries is ``peak_bytes_reserved`` (on a v5e 6.38 of the
    6.54 GB of a Higgs iteration, PERF.md Findings PR 24).  Where the
    runtime gives both, ``peak_bytes_total`` is their sum: the peak a
    capacity decision wants."""
    try:
        if device is None:
            import jax
            device = jax.devices()[0]
        stats = device.memory_stats()
    except Exception:  # noqa: BLE001 — accounting must never raise
        return None
    if not stats or "bytes_in_use" not in stats:
        return None
    out = {"bytes_in_use": int(stats["bytes_in_use"])}
    for key in ("peak_bytes_in_use", "peak_bytes_reserved", "bytes_limit",
                "largest_alloc_size"):
        if key in stats:
            out[key] = int(stats[key])
    if "peak_bytes_in_use" in out and "peak_bytes_reserved" in out:
        out["peak_bytes_total"] = (out["peak_bytes_in_use"]
                                   + out["peak_bytes_reserved"])
    return out


def live_buffer_census(arrays=None, top: int = CENSUS_TOP_GROUPS) -> Dict:
    """Group live device arrays by (shape, dtype) with byte totals.

    ``arrays`` defaults to ``jax.live_arrays()`` — the process-wide live
    set (pass an explicit list to census a known working set, as the
    tests do).  Returns ``{"total_bytes", "total_arrays",
    "distinct_shapes", "groups": [{shape, dtype, count, bytes}, ...
    largest first, top N], "truncated"}``."""
    if arrays is None:
        try:
            import jax
            arrays = jax.live_arrays()
        except Exception:  # noqa: BLE001 — census is observation only
            arrays = []
    groups: Dict[tuple, Dict[str, Any]] = {}
    total = 0
    count = 0
    for a in arrays:
        try:
            shape = tuple(int(d) for d in a.shape)
            dtype = str(a.dtype)
            nbytes = int(a.nbytes)
        except Exception:  # noqa: BLE001 — deleted/donated buffers raise
            continue
        count += 1
        total += nbytes
        g = groups.get((shape, dtype))
        if g is None:
            g = groups[(shape, dtype)] = {
                "shape": list(shape), "dtype": dtype, "count": 0, "bytes": 0}
        g["count"] += 1
        g["bytes"] += nbytes
    ordered = sorted(groups.values(), key=lambda g: (-g["bytes"],
                                                    g["dtype"],
                                                    g["shape"]))
    return {
        "total_bytes": total,
        "total_arrays": count,
        "distinct_shapes": len(ordered),
        "groups": ordered[:top] if top else [],
        "truncated": max(len(ordered) - top, 0) if top else len(ordered),
    }


# -------------------------------------------------------------- host side
class MemoryTracker:
    """Host + device memory snapshotter.

    The host half owns the peak-RSS watermark the sparse-ingestion bound
    test asserts on (tests/test_inputs.py): :meth:`reset_host_peak`
    resets the kernel's VmHWM watermark (``/proc/self/clear_refs`` "5")
    so a subsequent :meth:`host_peak_rss_mb` reads only what happened
    AFTER the reset point; where /proc is unavailable the fallback is
    ``ru_maxrss`` (a lifetime peak — deltas across it still catch any
    allocation pushing past the prior high-water mark)."""

    @staticmethod
    def reset_host_peak() -> bool:
        """Reset the kernel peak-RSS watermark; returns True when VmHWM
        tracking is live (clear_refs written), False on the ru_maxrss
        fallback."""
        try:
            with open("/proc/self/clear_refs", "w") as fh:
                fh.write("5")
            return True
        except OSError:
            return False

    @staticmethod
    def host_peak_rss_mb(use_hwm: bool = True) -> float:
        """Peak resident-set MB: ``VmHWM`` (honors :meth:`reset_host_peak`)
        when readable and ``use_hwm``, else ``ru_maxrss``."""
        if use_hwm:
            try:
                with open("/proc/self/status") as fh:
                    for line in fh:
                        if line.startswith("VmHWM:"):
                            return int(line.split()[1]) / 1024.0
            except OSError:
                pass
        if resource is None:
            return 0.0
        peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        # ru_maxrss unit is kilobytes on Linux but BYTES on Darwin
        return peak / (2**20 if sys.platform == "darwin" else 1024.0)

    def __init__(self, device=None):
        self._device = device

    def device_stats(self) -> Optional[Dict[str, int]]:
        return device_memory_stats(self._device)

    def census(self, top: int = CENSUS_TOP_GROUPS) -> Dict:
        return live_buffer_census(top=top)

    def publish(self) -> Dict:
        """One combined snapshot, pushed into the ``memory.*`` gauges."""
        reg = registry()
        stats = self.device_stats()
        if stats is not None:
            reg.gauge("memory.bytes_in_use").set(stats["bytes_in_use"])
            if "peak_bytes_in_use" in stats:
                reg.gauge("memory.peak_bytes").set(
                    stats["peak_bytes_in_use"])
        rss = self.host_peak_rss_mb()
        reg.gauge("memory.host_peak_rss_mb").set(rss)
        return {"device": stats, "host_peak_rss_mb": rss}


def host_peak_rss_mb() -> float:
    """Module-level convenience: read the host watermark AND publish the
    ``memory.host_peak_rss_mb`` gauge (the engine's train.end hook)."""
    v = MemoryTracker.host_peak_rss_mb()
    registry().gauge("memory.host_peak_rss_mb").set(v)
    return v


# ------------------------------------------------------------- span hooks
def _live_total_bytes() -> int:
    """Just the live-array byte total — the span-entry baseline needs no
    shape/dtype grouping, so this costs one walk, not a census build."""
    try:
        import jax
        arrays = jax.live_arrays()
    except Exception:  # noqa: BLE001 — observation only
        return 0
    total = 0
    for a in arrays:
        try:
            total += int(a.nbytes)
        except Exception:  # noqa: BLE001 — deleted/donated buffers raise
            pass
    return total


def span_begin():
    """Token for a ``track_memory=True`` span; ``None`` when accounting is
    disarmed (the common case — one mode check)."""
    if not tracking_enabled():
        return None
    stats = device_memory_stats()
    live = _live_total_bytes() if _mode == "census" else None
    return (None if stats is None else stats["bytes_in_use"], live)


def span_end(path: str, token) -> None:
    """Close a tracked span: HBM delta + watermark into ``memory.*``
    gauges and one ``memory.watermark`` JSONL event.  Host-side
    observation only — never touches a compiled program."""
    if token is None or not tracking_enabled():
        return
    base_dev, base_live = token
    reg = registry()
    fields: Dict[str, Any] = {"span": path}
    stats = device_memory_stats()
    if stats is not None:
        fields["bytes_in_use"] = stats["bytes_in_use"]
        fields["peak_bytes"] = stats.get("peak_bytes_in_use")
        if base_dev is not None:
            fields["delta_bytes"] = stats["bytes_in_use"] - base_dev
        reg.gauge("memory.bytes_in_use").set(stats["bytes_in_use"])
        if stats.get("peak_bytes_in_use") is not None:
            reg.gauge("memory.peak_bytes").set(stats["peak_bytes_in_use"])
        if "peak_bytes_reserved" in stats:
            # the loaded programs' temporaries (see device_memory_stats)
            fields["peak_bytes_reserved"] = stats["peak_bytes_reserved"]
            reg.gauge("memory.peak_bytes_reserved").set(
                stats["peak_bytes_reserved"])
    else:
        # graceful-None contract: the event still lands (a CPU run's log
        # shows WHICH spans were tracked), just with no device numbers
        fields["bytes_in_use"] = None
        fields["peak_bytes"] = None
    if _mode == "census":
        census = live_buffer_census()
        fields["live_bytes"] = census["total_bytes"]
        fields["live_arrays"] = census["total_arrays"]
        if base_live is not None:
            fields["live_delta_bytes"] = census["total_bytes"] - base_live
        fields["census"] = census["groups"][:EVENT_TOP_GROUPS]
        reg.gauge("memory.live_bytes").set(census["total_bytes"])
    rss = MemoryTracker.host_peak_rss_mb()
    fields["host_peak_rss_mb"] = round(rss, 1)
    reg.gauge("memory.host_peak_rss_mb").set(rss)
    from . import events
    events.emit("memory.watermark", **fields)


# -------------------------------------------------------- compile telemetry
def memory_analysis_summary(compiled) -> Optional[Dict[str, int]]:
    """Byte summary from ``compiled.memory_analysis()`` (XLA
    CompiledMemoryStats): temp / generated-code / argument / output /
    donated-alias sizes.  ``None`` where the backend has no analysis."""
    try:
        ma = compiled.memory_analysis()
    except Exception:  # noqa: BLE001 — optional on some backends
        return None
    if ma is None:
        return None
    if isinstance(ma, list):
        if not ma:
            return None
        ma = ma[0]
    out = {}
    for key in ("temp_size_in_bytes", "argument_size_in_bytes",
                "output_size_in_bytes", "alias_size_in_bytes",
                "generated_code_size_in_bytes"):
        v = getattr(ma, key, None)
        if v is not None:
            out[key] = int(v)
    return out or None


def note_compile(label: str, seconds: float, compiled=None) -> None:
    """Record one XLA compile: bump ``compile.count``, observe
    ``compile.seconds``, emit a ``compile.end`` event (with the
    memory-analysis byte summary when the caller holds the AOT compiled
    object — the jit seam only knows the wall time)."""
    reg = registry()
    reg.counter("compile.count").inc()
    reg.histogram("compile.seconds").observe(seconds)
    # the same seconds per program, to lay beside jax's own account of it
    # (jit.*{fun_name=...}): this one is the wall time of the whole call
    reg.histogram("compile.seconds", labels={"label": label}).observe(seconds)
    fields: Dict[str, Any] = {"label": label, "seconds": round(seconds, 6)}
    if compiled is not None:
        summary = memory_analysis_summary(compiled)
        if summary:
            fields["memory_analysis"] = summary
    from . import events
    events.emit("compile.end", **fields)


# ------------------------------------------- every compile, by jax's account
# ``note_compile`` sees the programs behind ``instrument`` / ``watch_compiles``
# from OUTSIDE the call.  jax itself reports every trace, lowering and backend
# compile of the process through ``jax.monitoring``; one listener turns those
# into ``jit.*`` instruments, each also under ``{fun_name="..."}``:
#   jit.trace_seconds, jit.lower_seconds, jit.backend_seconds (histograms),
#   jit.cache_load_seconds (the backend step of a program the persistent cache
#   held: retrieval + deserialisation, kept OUT of backend_seconds so the four
#   add up), jit.compiles (programs made ready), jit.cache_hits /
#   jit.cache_misses (of the requests that used the persistent cache).
# A trace inside a trace (a jitted helper traced while the outer program is)
# is part of the outer one's seconds and is not observed again.
_JIT_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit.trace_seconds",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower_seconds",
    "/jax/core/compile/backend_compile_duration": "jit.backend_seconds",
}
_CACHE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_jit_local = threading.local()     # trace depth, cache request / hit pending
_jit_totals = [0, 0.0]             # compiles, seconds: the iteration records'
_jit_listening = False


def jit_totals():
    """``(compiles, seconds)`` of the process so far by jax's account — what
    an iteration record's ``compiles`` / ``compile_s`` are differences of."""
    return _jit_totals[0], _jit_totals[1]


def _fun(fun_name) -> str:
    name = str(fun_name or "?")
    return name[4:-1] if name.startswith("jit(") and name.endswith(")") \
        else name


def _on_jit_start(event: str, _value, **_kw) -> None:
    if event == "/jax/core/compile/jaxpr_trace_duration":
        _jit_local.depth = getattr(_jit_local, "depth", 0) + 1


def _on_jit_event(event: str, **_kw) -> None:
    if event == _CACHE_REQUEST:
        _jit_local.cache = "miss"
    elif event == _CACHE_HIT:
        _jit_local.cache = "hit"


def _on_jit_duration(event: str, seconds: float, fun_name=None,
                     **_kw) -> None:
    name = _JIT_EVENTS.get(event)
    if name is None:
        return
    if name == "jit.trace_seconds":
        depth = _jit_local.depth = max(
            getattr(_jit_local, "depth", 1) - 1, 0)
        if depth:
            return                 # part of the enclosing trace's seconds
    from . import spans
    if not spans.enabled():
        _jit_local.cache = None
        return
    reg, labels = registry(), {"fun_name": _fun(fun_name)}
    counts = []
    if name == "jit.backend_seconds":
        cache, _jit_local.cache = getattr(_jit_local, "cache", None), None
        counts.append("jit.compiles")
        _jit_totals[0] += 1
        if cache == "hit":
            name = "jit.cache_load_seconds"
        if cache:
            counts.append("jit.cache_hits" if cache == "hit"
                          else "jit.cache_misses")
    _jit_totals[1] += seconds
    for lb in (None, labels):
        reg.histogram(name, labels=lb).observe(seconds)
        for c in counts:
            reg.counter(c, labels=lb).inc()


def listen_to_jit() -> bool:
    """Register the one ``jax.monitoring`` listener of the process
    (idempotent; called when the telemetry package is imported)."""
    global _jit_listening
    if _jit_listening:
        return True
    try:
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_on_jit_duration)
        monitoring.register_event_listener(_on_jit_event)
    except Exception:  # noqa: BLE001 — a jax without it: no jit.* series
        return False
    _jit_listening = True
    try:       # without it nested traces are observed once more each
        monitoring.register_scalar_listener(_on_jit_start)
    except Exception:  # noqa: BLE001
        pass
    return True


# ----------------------------------------------------------- bench block
def memory_block() -> Dict:
    """The ``detail.memory`` block every BENCH blob (primary + rungs)
    carries: device watermark (None on CPU), the live-buffer census,
    compile count/seconds so far, and the host peak RSS.  bench.py adds
    the per-program ``memory_analysis`` byte summary beside it."""
    reg = registry()
    compile_hist = reg.histogram("compile.seconds")
    return {
        "mode": _mode,
        "device": device_memory_stats(),
        "live_buffers": live_buffer_census(),
        "compile": {
            "count": reg.counter("compile.count").value,
            "seconds": round(compile_hist.sum, 6),
        },
        "host_peak_rss_mb": round(MemoryTracker.host_peak_rss_mb(), 1),
    }
