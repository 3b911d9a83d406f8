"""Replay a telemetry JSONL log (``tpu_telemetry_log=<path>``) into
per-iteration and per-phase triage tables (docs/OBSERVABILITY.md).

Usage::

    python tools/telemetry_report.py LOG.jsonl [more logs ...]

Three tables per log:

- **iterations** — one row per ``train.iter`` event: wall seconds split
  into dispatch wait vs host bookkeeping, pack size, checkpoint write
  duration and the health verdict at that round;
- **phases** — the span totals the run's ``train.end`` event carries
  (``train/pack_dispatch``, ``grower/grow``, ``train/eval``, ...), i.e.
  where the wall clock went by phase;
- **events** — per-kind counts plus any health trips / rollbacks /
  checkpoint restores, verbatim.

``--memory`` adds two more tables replayed from the same artifact
(ISSUE-10, ``tpu_telemetry_memory``):

- **memory watermarks** — ``memory.watermark`` events aggregated per
  span: peak HBM / live-buffer bytes high-water marks and the largest
  single-span delta, so "where did the bytes go" reads per phase;
- **compiles** — ``compile.end`` events per program label: count, total
  and max compile seconds.

``--serve`` adds two more (ISSUE-14, ``tpu_serve_request_log``):

- **serve request phases** — sampled ``serve.request`` events decomposed
  into queue-wait / bin+assemble / device-dispatch / post-process
  latency (count, mean, p50/p99/max ms per phase);
- **serve tenants** — per-model-label traffic: sampled request count,
  rows, event-window QPS, mean/p99 latency and slow-request count.

Unknown schema versions and unparseable lines are reported, not fatal —
a triage tool must read partial/torn logs.  Plain stdlib; safe anywhere
the repo checks out.
"""

from __future__ import annotations

import argparse
import collections
import json
import math
import os
import statistics
import sys
from typing import Dict, List, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

KNOWN_SCHEMAS = (1,)


def _fmt_row(cols, widths):
    return "  ".join(str(c).ljust(w) for c, w in zip(cols, widths))


def _table(title, header, rows):
    print(f"\n== {title} ==")
    if not rows:
        print("(none)")
        return
    widths = [max(len(str(h)), *(len(str(r[i])) for r in rows))
              for i, h in enumerate(header)]
    print(_fmt_row(header, widths))
    print(_fmt_row(["-" * w for w in widths], widths))
    for r in rows:
        print(_fmt_row(r, widths))


def load_events(path: str) -> Tuple[List[dict], List[str]]:
    """``(events, problems)``: every parseable schema-known event line, in
    file order, plus human-readable notes for anything skipped."""
    events, problems = [], []
    with open(path) as fh:
        for lineno, line in enumerate(fh, 1):
            line = line.strip()
            if not line:
                continue
            try:
                obj = json.loads(line)
            except json.JSONDecodeError as e:
                problems.append(f"line {lineno}: unparseable ({e})")
                continue
            if not isinstance(obj, dict) or "kind" not in obj:
                problems.append(f"line {lineno}: not a telemetry event")
                continue
            if obj.get("schema") not in KNOWN_SCHEMAS:
                problems.append(
                    f"line {lineno}: unknown schema {obj.get('schema')!r} "
                    f"(kind={obj.get('kind')!r}; this tool knows "
                    f"{list(KNOWN_SCHEMAS)})")
                continue
            events.append(obj)
    return events, problems


# The program's stall rule (lightgbm_tpu/telemetry/iters.py; this tool
# imports nothing of the program, tests/test_iter_records.py holds the
# two to the same numbers).
STALL_RATIO, STALL_MIN_S, STALL_HISTORY, STALL_MIN_HISTORY = 3.0, 0.1, 16, 4


def _f(v, digits=4):
    return "-" if v is None else f"{float(v):.{digits}f}"


def _n(v):
    return "-" if v is None else int(v)


def iteration_rows(events: List[dict]) -> List[tuple]:
    """One row per ``train.iter`` event.  ``stall`` marks the rows the
    program's stall rule would have flagged (the constants above: the
    period against the median of the 16 periods before it); a log does
    not say which programs a round dispatched, so all rounds are one
    history here."""
    rows, hist = [], collections.deque(maxlen=STALL_HISTORY)
    for e in events:
        if e["kind"] != "train.iter":
            continue
        period = e.get("period_s")
        mark = "-"
        if period is not None:
            if len(hist) >= STALL_MIN_HISTORY:
                median = statistics.median(hist)
                if (period > STALL_RATIO * median
                        and period - median >= STALL_MIN_S):
                    mark = "STALL"
            hist.append(period)
        rows.append((e.get("iteration", "?"), _f(e.get("wall_s")),
                     _f(e.get("dispatch_wait_s")), _f(e.get("host_s")),
                     e.get("pack_size", 1), _f(e.get("checkpoint_s")),
                     e.get("health") or "-", _f(period), _f(e.get("cpu_s")),
                     _n(e.get("involuntary_switches")),
                     _n(e.get("compiles")), mark))
    return rows


def phase_rows(events: List[dict]) -> List[tuple]:
    """Span totals, summed over every ``train.end`` in the log (a file can
    hold several runs — cv folds, retries), longest first."""
    totals: Dict[str, float] = collections.defaultdict(float)
    for e in events:
        if e["kind"] == "train.end":
            for name, secs in (e.get("spans") or {}).items():
                totals[name] += float(secs)
    return sorted(((n, f"{s:.4f}") for n, s in totals.items()),
                  key=lambda r: -float(r[1]))


def incident_rows(events: List[dict]) -> List[tuple]:
    rows = []
    for e in events:
        if e["kind"] in ("health.trip", "health.overflow", "train.rollback",
                         "checkpoint.restore", "watchdog.probe"):
            detail = {k: v for k, v in e.items()
                      if k not in ("schema", "kind", "ts", "wall", "pid")}
            rows.append((e["kind"], e.get("iteration", "-"),
                         json.dumps(detail, default=str)[:100]))
    return rows


def _mb(v) -> str:
    return "-" if v is None else f"{float(v) / 2**20:.2f}"


def memory_rows(events: List[dict]) -> List[tuple]:
    """Per-span aggregation of ``memory.watermark`` events: event count,
    max device peak / bytes-in-use, max live-buffer bytes, and the
    largest single-span HBM delta (all MB; '-' where the backend reported
    no stats — the CPU graceful-None path)."""
    per: Dict[str, Dict[str, object]] = {}
    for e in events:
        if e["kind"] != "memory.watermark":
            continue
        agg = per.setdefault(e.get("span", "?"),
                             {"n": 0, "peak": None, "in_use": None,
                              "live": None, "delta": None})
        agg["n"] += 1
        for field, key in (("peak_bytes", "peak"),
                           ("bytes_in_use", "in_use"),
                           ("live_bytes", "live"),
                           ("delta_bytes", "delta")):
            v = e.get(field)
            if v is None:
                continue
            cur = agg[key]
            agg[key] = v if cur is None else max(cur, v)
    return [(span, a["n"], _mb(a["peak"]), _mb(a["in_use"]),
             _mb(a["live"]), _mb(a["delta"]))
            for span, a in sorted(per.items())]


def stream_rows(events: List[dict]) -> List[tuple]:
    """Aggregation of ``stream.chunk`` events (ISSUE-13,
    lightgbm_tpu/stream/residency.py): per-chunk-slot upload count,
    total uploaded MB, prefetch hit/stall split and total/max wait
    seconds — the streaming pipeline's health at a glance (a pipeline
    that stopped overlapping shows up as stalls ~= uploads)."""
    per: Dict[int, Dict[str, float]] = {}
    for e in events:
        if e["kind"] != "stream.chunk":
            continue
        agg = per.setdefault(int(e.get("chunk", -1)),
                             {"n": 0, "bytes": 0, "hits": 0, "stalls": 0,
                              "wait": 0.0, "max_wait": 0.0})
        agg["n"] += 1
        agg["bytes"] += int(e.get("bytes", 0))
        if e.get("prefetch_hit"):
            agg["hits"] += 1
        else:
            agg["stalls"] += 1
        w = float(e.get("wait_s", 0.0))
        agg["wait"] += w
        agg["max_wait"] = max(agg["max_wait"], w)
    return [(ci, a["n"], _mb(a["bytes"]), a["hits"], a["stalls"],
             f"{a['wait']:.4f}", f"{a['max_wait']:.4f}")
            for ci, a in sorted(per.items())]


_SERVE_PHASES = ("queue_wait", "assemble", "dispatch", "post", "total")


def _pctl(sorted_vals: List[float], q: float):
    """Nearest-rank percentile over a pre-sorted list (stdlib-only):
    rank ceil(q/100 * n), converted to a 0-based index."""
    if not sorted_vals:
        return None
    k = max(math.ceil(q / 100.0 * len(sorted_vals)) - 1, 0)
    return sorted_vals[min(k, len(sorted_vals) - 1)]


def serve_phase_rows(events: List[dict]) -> List[tuple]:
    """Per-phase latency breakdown replayed from ``serve.request`` events
    (ISSUE-14): where a request's wall time went — queue wait vs
    bin/assemble vs device dispatch vs post-process — as count / mean /
    p50 / p99 / max milliseconds.  Only SAMPLED requests are in the log
    (rate knob + always-sampled slow requests), so the distribution skews
    toward the tail by design — the triage-relevant end."""
    per: Dict[str, List[float]] = {p: [] for p in _SERVE_PHASES}
    for e in events:
        if e["kind"] != "serve.request":
            continue
        for p in _SERVE_PHASES:
            v = e.get(f"{p}_s" if p != "total" else "total_s")
            if v is not None:
                per[p].append(float(v) * 1e3)
    rows = []
    for p in _SERVE_PHASES:
        vals = sorted(per[p])
        if not vals:
            continue
        rows.append((p, len(vals), _f(sum(vals) / len(vals)),
                     _f(_pctl(vals, 50)), _f(_pctl(vals, 99)),
                     _f(vals[-1])))
    return rows


def serve_tenant_rows(events: List[dict]) -> List[tuple]:
    """Per-tenant traffic table from the same ``serve.request`` events:
    sampled-request count, served rows, event-window QPS (count over the
    first->last event timespan — a LOWER bound on real traffic when the
    sample rate is < 1), mean/p99 total latency and slow-request count,
    keyed by the model label (``-`` for unnamed predictors)."""
    per: Dict[str, Dict] = {}
    for e in events:
        if e["kind"] != "serve.request":
            continue
        name = str(e.get("model") or "-")
        agg = per.setdefault(name, {"n": 0, "rows": 0, "slow": 0,
                                    "lat": [], "t0": None, "t1": None})
        agg["n"] += 1
        agg["rows"] += int(e.get("rows", 0))
        if e.get("slow"):
            agg["slow"] += 1
        if e.get("total_s") is not None:
            agg["lat"].append(float(e["total_s"]) * 1e3)
        ts = e.get("ts")
        if ts is not None:
            agg["t0"] = ts if agg["t0"] is None else min(agg["t0"], ts)
            agg["t1"] = ts if agg["t1"] is None else max(agg["t1"], ts)
    rows = []
    for name, a in sorted(per.items()):
        span_s = (a["t1"] - a["t0"]) if a["t0"] is not None else None
        qps = (a["n"] / span_s) if span_s else None
        lat = sorted(a["lat"])
        rows.append((name, a["n"], a["rows"],
                     "-" if qps is None else f"{qps:.1f}",
                     _f(sum(lat) / len(lat)) if lat else "-",
                     _f(_pctl(lat, 99)), a["slow"]))
    return rows


def compile_rows(events: List[dict]) -> List[tuple]:
    """Per-label aggregation of ``compile.end`` events."""
    per: Dict[str, List[float]] = collections.defaultdict(list)
    for e in events:
        if e["kind"] == "compile.end":
            per[e.get("label", "?")].append(float(e.get("seconds", 0.0)))
    return [(label, len(secs), f"{sum(secs):.4f}", f"{max(secs):.4f}")
            for label, secs in sorted(per.items())]


def report(path: str, memory: bool = False, serve: bool = False) -> int:
    """Print the triage tables for one log; returns 0 when the log held at
    least one valid event."""
    events, problems = load_events(path)
    print(f"\n#### {path}: {len(events)} events"
          + (f", {len(problems)} skipped lines" if problems else ""))
    for p in problems[:8]:
        print(f"  ! {p}")
    if not events:
        return 1
    counts = collections.Counter(e["kind"] for e in events)
    starts = [e for e in events if e["kind"] == "train.start"]
    for s in starts:
        print(f"  run: {s.get('objective')}/{s.get('boosting')} "
              f"rows={s.get('rows')} features={s.get('features')} "
              f"rounds={s.get('num_boost_round')} "
              f"pack={s.get('pack_size')} (packed={s.get('packed')}"
              + (f", degrade: {s['pack_degrade_reason']}"
                 if s.get("pack_degrade_reason") else "") + ")")
    _table("iterations",
           ("iter", "wall_s", "dispatch_s", "host_s", "pack", "ckpt_s",
            "health", "period_s", "cpu_s", "invol_sw", "compiles",
            "stall"), iteration_rows(events))
    _table("phases (span totals, seconds)", ("span", "seconds"),
           phase_rows(events))
    _table("event counts", ("kind", "count"),
           sorted(counts.items()))
    inc = incident_rows(events)
    if inc:
        _table("incidents", ("kind", "iter", "detail"), inc)
    stream = stream_rows(events)
    if stream:
        _table("stream chunks (residency pipeline)",
               ("chunk", "uploads", "MB_total", "hits", "stalls",
                "wait_s", "max_wait_s"), stream)
    if memory:
        _table("memory watermarks (MB, per span)",
               ("span", "events", "peak_hbm", "hbm_in_use", "live_bufs",
                "max_delta"), memory_rows(events))
        _table("compiles", ("label", "count", "total_s", "max_s"),
               compile_rows(events))
    if serve:
        _table("serve request phases (ms, sampled serve.request events)",
               ("phase", "count", "mean", "p50", "p99", "max"),
               serve_phase_rows(events))
        _table("serve tenants (sampled serve.request events)",
               ("model", "events", "rows", "qps", "mean_ms", "p99_ms",
                "slow"), serve_tenant_rows(events))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("logs", nargs="+", help="telemetry JSONL log file(s)")
    ap.add_argument("--memory", action="store_true",
                    help="add the per-span memory-watermark and "
                         "per-label compile tables (ISSUE-10)")
    ap.add_argument("--serve", action="store_true",
                    help="add the serve request-phase breakdown and "
                         "per-tenant traffic tables replayed from "
                         "serve.request events (ISSUE-14)")
    args = ap.parse_args(argv)
    rc = 0
    for path in args.logs:
        if not os.path.exists(path):
            print(f"{path}: no such file", file=sys.stderr)
            rc = 1
            continue
        rc = max(rc, report(path, memory=args.memory, serve=args.serve))
    return rc


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BrokenPipeError:
        # piped into head/less and the reader closed — normal for a
        # triage tool, not an error
        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
        sys.exit(0)
