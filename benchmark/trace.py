"""From a profiler trace (xplane) to device intervals and host spans.

``load_xplane`` reads the ``.xplane.pb`` jax's profiler wrote with nothing
but jax and keeps only what the reducers need: for each plane its lines, for
each line ``[name, start_ns, duration_ns]`` events.  The same structure
is kept as JSON (``from_json``) for the tiny recorded trace beside the
tests.  Which planes are devices, which line holds the operations, which
names are Pallas/Mosaic kernels and which host events are spans is data:
``trace_names.json``.
"""

from __future__ import annotations

import glob
import json
import os
import re

_HERE = os.path.dirname(os.path.abspath(__file__))


def names() -> dict:
    with open(os.path.join(_HERE, "trace_names.json")) as f:
        return json.load(f)


class Trace:
    def __init__(self, planes: list, name_rules: dict = None):
        self.planes = planes
        self.rules = name_rules or names()

    # ---- selection
    def device_planes(self) -> list:
        p = self.rules["device_plane_prefix"]
        return [pl for pl in self.planes
                if pl["name"].startswith(p)
                and pl["name"][len(p):].isdigit()]

    def device_ops(self, plane: dict) -> list:
        return [e for ln in plane["lines"]
                if ln["name"] in self.rules["op_lines"]
                for e in ln["events"]]

    def is_kernel(self, name: str) -> bool:
        return any(p in name for p in self.rules["kernel_patterns"])

    def host_spans(self) -> list:
        """Host events that are spans of the program or of the harness."""
        pre = tuple(self.rules["host_span_prefixes"])
        return [e for pl in self.planes
                if pl["name"].startswith(self.rules["host_plane_prefix"])
                for ln in pl["lines"] for e in ln["events"]
                if e[0].startswith(pre)]

    def window(self) -> tuple:
        """``(start_ns, end_ns)`` of the harness's traced window."""
        w = [e for e in self.host_spans()
             if e[0] == self.rules["window_span"]]
        if not w:
            return None
        return (min(e[1] for e in w), max(e[1] + e[2] for e in w))

    # ---- reductions
    def busy_ns(self, window: tuple, kernels_only: bool = False) -> float:
        """Nanoseconds in which an operation (or, ``kernels_only``, a
        Pallas/Mosaic kernel) ran, as the union of the operations'
        intervals clipped to the window, averaged over the device planes.
        ``None`` where the trace has no device plane."""
        planes = self.device_planes()
        if not planes:
            return None
        total = 0.0
        for pl in planes:
            ev = [e for e in self.device_ops(pl)
                  if not kernels_only or self.is_kernel(e[0])]
            total += union_ns(clip(ev, window))
        return total / len(planes)

    def top_ops(self, window: tuple, n: int = 10) -> list:
        """``[[name, seconds], ...]``: the operations with most summed
        duration in the window, as ``<instruction> <opcode>``; enclosing
        control flow (``while``, ``conditional``, ``call``) holds its body's
        time too and is left out."""
        acc = {}
        for pl in self.device_planes():
            for name, s, d in clip(self.device_ops(pl), window):
                op = short_name(name)
                if op.split(" ")[-1] not in self.rules["container_opcodes"]:
                    acc[op] = acc.get(op, 0.0) + d
        k = max(len(self.device_planes()), 1)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / k / 1e9] for name, ns in top]

    def idle_gaps(self, window: tuple, n: int = 10) -> list:
        """``[[host span, seconds], ...]``: the device's idle time in the
        window, each gap given to the innermost host span open at its
        middle (``"(no span)"`` where none is), summed by span."""
        planes = self.device_planes()
        if not planes:
            return []
        spans = self.host_spans()
        acc = {}
        for lo, hi in gaps(clip(self.device_ops(planes[0]), window), window):
            mid = (lo + hi) / 2
            open_ = [e for e in spans if e[1] <= mid <= e[1] + e[2]
                     and e[0] != self.rules["window_span"]]
            name = (min(open_, key=lambda e: e[2])[0] if open_
                    else "(no span)")
            acc[name] = acc.get(name, 0.0) + (hi - lo)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / 1e9] for name, ns in top]


_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")


def short_name(name: str) -> str:
    """The TPU trace names an operation by its whole HLO text
    (``%fusion.4 = f32[...] fusion(...), kind=...``): keep the
    instruction's name and its opcode."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:120]
    m = _OPCODE.search(rest)
    return f"{head.lstrip('%')} {m.group(1)}" if m else head.lstrip("%")


def clip(events: list, window: tuple) -> list:
    lo, hi = window
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append([name, a, b - a])
    return out


def _merged(events: list) -> list:
    iv = sorted((s, s + d) for _, s, d in events)
    out = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def union_ns(events: list) -> float:
    return float(sum(b - a for a, b in _merged(events)))


def gaps(events: list, window: tuple) -> list:
    """The intervals of ``window`` that no event covers."""
    out, at = [], window[0]
    for a, b in _merged(events):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if window[1] > at:
        out.append((at, window[1]))
    return out


# ---- reading and keeping

def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def load_xplane(path: str) -> Trace:
    import jax.profiler
    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for pl in data.planes:
        lines = [{"name": ln.name,
                  "events": [[e.name, float(e.start_ns), float(e.duration_ns)]
                             for e in ln.events]}
                 for ln in pl.lines]
        planes.append({"name": pl.name, "lines": lines})
    return Trace(planes)


def from_json(obj: dict) -> Trace:
    return Trace(obj["planes"])
