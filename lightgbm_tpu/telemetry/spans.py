"""Trace spans: ONE context manager that opens a ``jax.profiler.
TraceAnnotation`` region (so the span shows up in device profiler traces)
AND aggregates host wall time into the hierarchical timer + the process
registry (docs/OBSERVABILITY.md).

Span names are ``area/phase`` (``train/iter_dispatch``, ``grower/grow``,
``serve/predict``); nested spans join with ``/`` through a thread-local
stack, so a ``grow`` span opened inside ``train/iter_dispatch`` aggregates
as ``train/iter_dispatch/grow``.

HOST-SIDE ONLY, at dispatch boundaries: a span wraps the *launch* of a
compiled program (and any blocking fetch), never code inside a trace —
``tpu_telemetry=off`` therefore compiles bitwise-identical programs and
the dispatch census stays pinned (tests/test_telemetry.py).  Disabled
spans cost one flag read.

INSIDE a compiled program the names are phase scopes: ``with
phase("grow/partition")`` is ``jax.named_scope`` — HLO metadata only, no
operation, no knob, the same binary — so a device trace can say which
phase an operation belongs to.  ``PHASES`` is the one list of those names.
"""

from __future__ import annotations

import threading
import time
from typing import Dict

from ..utils.timer import Timer
from .registry import registry

# Process-wide arm switch (tpu_telemetry).  Set per-run by the engine /
# GBDT constructor from the config; raw Booster.update loops (bench rungs)
# keep whatever the last constructed booster asked for (default: on).
_enabled = True

# Span totals are read programmatically (span_totals / the bench
# telemetry block).
_span_timer = Timer()

# The phases of one boosting iteration INSIDE the compiled program, in the
# order the work runs (docs/OBSERVABILITY.md says what each holds).  The
# program's scopes, the docs and benchmark/scope_names.json all name these.
PHASES = (
    "boost/gradients", "boost/score_update",
    "grow/setup", "grow/select", "grow/partition", "grow/wave_gather",
    "grow/wave_unpack", "grow/hist", "grow/subtract", "grow/scan",
    "grow/reduce", "grow/update", "grow/finish",
)

_local = threading.local()


def set_enabled(on: bool) -> None:
    global _enabled
    _enabled = bool(on)


def enabled() -> bool:
    return _enabled


def phase(name: str):
    """``with phase("grow/scan"):`` (or ``@phase("grow/scan")`` on a
    function) around code that is being TRACED: the operations it emits
    carry ``name`` in their HLO ``op_name``.  Refuses a name that is not
    in ``PHASES``."""
    if name not in PHASES:
        raise ValueError(f"phase {name!r} is not in telemetry.PHASES")
    import jax
    return jax.named_scope(name)


def kernel_rows(rows: int):
    """The last scope segment of a histogram-kernel launch: ``rows<R>``,
    ``R`` the static number of rows that launch is handed."""
    import jax
    return jax.named_scope(f"rows{int(rows)}")


def _stack():
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


class span:
    """``with span("train/grow"): ...`` — host timer + profiler
    annotation + registry histogram, one context manager.  Re-entrant and
    thread-safe (per-thread name stacks; the timer is lock-guarded).

    ``track_memory=True`` additionally records the span's device-memory
    delta + watermark (telemetry/memory.py) when
    ``tpu_telemetry_memory`` is armed — a no-op (one mode check) when it
    is ``off``, host-side observation either way."""

    __slots__ = ("name", "_path", "_t0", "_trace", "_track_memory",
                 "_mem_token")

    def __init__(self, name: str, track_memory: bool = False):
        self.name = name
        self._path = None
        self._t0 = 0.0
        self._trace = None
        self._track_memory = track_memory
        self._mem_token = None

    def __enter__(self):
        if not _enabled:
            return self
        stack = _stack()
        self._path = (f"{stack[-1]}/{self.name}" if stack else self.name)
        stack.append(self._path)
        try:
            import jax.profiler
            self._trace = jax.profiler.TraceAnnotation(self._path)
            self._trace.__enter__()
        except Exception:  # noqa: BLE001 — profiler is garnish on the timer
            self._trace = None
        if self._track_memory:
            from . import memory
            self._mem_token = memory.span_begin()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._path is None:   # entered disabled
            return False
        dt = time.perf_counter() - self._t0
        if self._trace is not None:
            try:
                self._trace.__exit__(*exc)
            except Exception:  # noqa: BLE001 — a torn-down profiler must
                pass           # not break training or strand the stack
        stack = _stack()
        if stack and stack[-1] == self._path:
            stack.pop()
        if self._mem_token is not None:
            from . import memory
            try:
                memory.span_end(self._path, self._mem_token)
            except Exception:  # noqa: BLE001 — accounting must never
                pass           # break training or mask the real exception
            self._mem_token = None
        _span_timer.add(self._path, dt)
        registry().histogram(f"span.{self._path}").observe(dt)
        self._path = None
        return False


def instrument(fn, name: str, track_memory: bool = False):
    """Wrap a compiled callable so every launch runs under ``span(name)``,
    delegating attribute access (``.lower``, ``.raw``, the grower's static
    capability facts) to the wrapped function — callers and the dispatch
    census see the same surface.  The wrapper is ALSO the compile seam:
    a call that grows the jit executable cache emits a ``compile.end``
    event (telemetry/memory.py note_compile) with the call's wall seconds
    — a first call to a new shape is dominated by the XLA compile."""
    return _Instrumented(fn, name, track_memory=track_memory)


def watch_compiles(fn, name: str):
    """Compile telemetry WITHOUT a span: for jitted programs whose
    launches already run under a caller-side span (the fused iteration
    under ``train/fused_iter``, the pack program under
    ``train/pack_dispatch``) — wrapping them in ``instrument`` would
    double-count the span."""
    return _Instrumented(fn, name, use_span=False)


def _compile_cache_size(fn):
    """jit executable-cache size, or None where jax doesn't expose it."""
    try:
        return int(fn._cache_size())
    except Exception:  # noqa: BLE001 — older jax / non-jit callables
        return None


class _Instrumented:
    def __init__(self, fn, name: str, track_memory: bool = False,
                 use_span: bool = True):
        self._fn = fn
        self._span_name = name
        self._track_memory = track_memory
        self._use_span = use_span

    def __call__(self, *args, **kwargs):
        if not _enabled:
            return self._fn(*args, **kwargs)
        n0 = _compile_cache_size(self._fn)
        t0 = time.perf_counter()
        if self._use_span:
            with span(self._span_name, track_memory=self._track_memory):
                out = self._fn(*args, **kwargs)
        else:
            out = self._fn(*args, **kwargs)
        if n0 is not None and _compile_cache_size(self._fn) > n0:
            from . import memory
            memory.note_compile(self._span_name,
                                time.perf_counter() - t0)
        return out

    def __getattr__(self, item):
        return getattr(self._fn, item)


def span_totals() -> Dict[str, Dict[str, float]]:
    """``{span_path: {"seconds": s, "count": n}}`` aggregated since process
    start (or the last :func:`reset_spans`)."""
    return {name: {"seconds": secs, "count": cnt}
            for name, secs, cnt in _span_timer.snapshot()}


def reset_spans() -> None:
    _span_timer.reset()
