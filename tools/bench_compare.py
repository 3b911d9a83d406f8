"""Bench-trajectory regression gate (ISSUE-10): compare BENCH metric
blobs and FAIL when a watched metric regresses.

Until now the ``BENCH_r*.json`` trajectory was write-only — blobs
accumulated but nothing compared them, so a PR that halved predict QPS or
doubled peak HBM sailed through.  This tool is the gate::

    python tools/bench_compare.py OLD.json NEW.json [--max-regress 0.10]
    python tools/bench_compare.py --trajectory DIR_or_files...

**Pair mode** compares two blobs metric by metric and exits non-zero on a
regression past the threshold.  **Trajectory mode** walks a committed
``BENCH_r*.json`` sequence (a directory or explicit files, sorted by
name), compares each consecutive pair of metric-bearing rounds, and
reports rounds with no salvageable metric (wedged attempts) instead of
dying on them.

**Platform honesty** (the ``detail.device`` block): a CPU blob is NEVER
comparable to an accelerator blob — a TPU round followed by a CPU round
is a ~30x throughput cliff that is a backend event, not a code
regression.  Pair mode REFUSES such a comparison (exit 3); trajectory
mode flags the pair ``probe-mismatch`` and skips it.

Watched metrics (missing on either side -> ``n/a``, skipped):

==================  ======  =============================================
metric              better  source
==================  ======  =============================================
train_s_per_iter    lower   detail.train_time_s / detail.iters
predict_qps         higher  detail.predict.warm_qps
hlo_flops           lower   detail.hlo_cost.flops
hlo_bytes           lower   detail.hlo_cost.bytes_accessed
peak_hbm_bytes      lower   detail.memory.device.peak_bytes_in_use
compile_s           lower   detail.memory.compile.seconds
dispatches_per_iter lower   detail.dispatches_per_iter
==================  ======  =============================================

Thresholds: ``--max-regress 0.10`` is the default fractional regression
allowed on every watched metric; ``--metric-max name=frac`` (repeatable)
overrides per metric (e.g. ``--metric-max compile_s=0.5`` — compile time
is noisier than throughput).

Exit codes: 0 = no regression; 1 = at least one watched metric regressed
past its threshold; 2 = usage / unreadable input; 3 = refused (pair mode,
CPU-fallback vs live-accelerator).

Plain stdlib — safe in any CI image the repo checks out in.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

# (name, higher_is_better)
WATCHED: List[Tuple[str, bool]] = [
    ("train_s_per_iter", False),
    ("predict_qps", True),
    ("hlo_flops", False),
    ("hlo_bytes", False),
    ("peak_hbm_bytes", False),
    ("compile_s", False),
    ("dispatches_per_iter", False),
    # BENCH_serve blobs (tools/serve_bench.py, ISSUE-12): the serving
    # trajectory gates on the same machinery — warm QPS, tail latency,
    # fresh-compile count, resident pack bytes and the zero-cold-start
    # restart compile count.  n/a on training blobs (and vice versa), so
    # the two blob families coexist in one trajectory.
    ("serve_warm_qps", True),
    ("serve_p50_ms", False),
    ("serve_p99_ms", False),
    ("serve_compiles", False),
    ("serve_plan_bytes", False),
    ("serve_restart_compiles", False),
    # tools/serve_load.py (ISSUE-14): the open-loop load-generator blob —
    # p999 tail, achieved throughput under the offered schedule, and the
    # saturation-search headline (max QPS meeting the p99 SLO).  n/a on
    # closed-loop serve_bench blobs and training blobs.
    ("serve_p999_ms", False),
    ("serve_achieved_qps", True),
    ("serve_slo_qps", True),
    # detail.stream rung (ISSUE-13, lightgbm_tpu/stream/): the streaming
    # trajectory — per-iteration wall cost under the budget, prefetch
    # stall seconds (a pipeline that stops overlapping regresses here
    # before s/iter moves), and the peak resident streaming bytes (which
    # leaving its budget is an unconditional regression the rung itself
    # also refuses to publish).
    ("stream_s_per_iter", False),
    ("stream_stall_s", False),
    ("stream_peak_bytes", False),
]


def _num(v) -> Optional[float]:
    return float(v) if isinstance(v, (int, float)) \
        and not isinstance(v, bool) else None


def _dig(d, *path):
    for key in path:
        if not isinstance(d, dict):
            return None
        d = d.get(key)
    return d


def load_blob(path: str) -> Optional[dict]:
    """Load one metric blob.  Accepts two shapes: a raw bench.py metric
    line (``{"metric": ..., "detail": ...}``) and a driver wrapper
    (``BENCH_r*.json``: the metric blob under ``"parsed"`` — ``null`` for
    rounds that produced no metric line).  Returns None for a wrapper
    whose round has no metric."""
    with open(path) as fh:
        obj = json.load(fh)
    if not isinstance(obj, dict):
        raise ValueError(f"{path}: not a JSON object")
    if "metric" in obj:
        return obj
    if "parsed" in obj:
        parsed = obj["parsed"]
        if parsed is not None and "metric" not in parsed:
            raise ValueError(f"{path}: 'parsed' is not a metric blob")
        return parsed
    raise ValueError(f"{path}: no metric blob (expected a bench.py line "
                     f"or a BENCH_r*.json wrapper)")


def blob_platform(blob: dict) -> str:
    """The backend a blob was measured on: the ``detail.device`` identity
    block bench.py writes (jax's own report), else an older blob's probe
    verdict, else its self-reported platform tag."""
    d = blob.get("detail") or {}
    device = d.get("device") or {}
    probe = d.get("probe") or {}
    return str(device.get("platform") or probe.get("backend")
               or d.get("platform") or "unknown")


def is_cpu_fallback(blob: dict) -> bool:
    d = blob.get("detail") or {}
    if d.get("cpu_fallback"):
        return True
    return blob_platform(blob) == "cpu"


def extract_metrics(blob: dict) -> Dict[str, Optional[float]]:
    d = blob.get("detail") or {}
    train_s = _num(d.get("train_time_s"))
    iters = _num(d.get("iters"))
    out: Dict[str, Optional[float]] = {
        "train_s_per_iter": (train_s / iters if train_s is not None
                             and iters else None),
        "predict_qps": _num(_dig(d, "predict", "warm_qps")),
        "hlo_flops": _num(_dig(d, "hlo_cost", "flops")),
        "hlo_bytes": _num(_dig(d, "hlo_cost", "bytes_accessed")),
        "peak_hbm_bytes": _num(_dig(d, "memory", "device",
                                    "peak_bytes_in_use")),
        "compile_s": _num(_dig(d, "memory", "compile", "seconds")),
        "dispatches_per_iter": _num(d.get("dispatches_per_iter")),
        "serve_warm_qps": None, "serve_p50_ms": None,
        "serve_p99_ms": None, "serve_compiles": None,
        "serve_plan_bytes": None, "serve_restart_compiles": None,
        "serve_p999_ms": None, "serve_achieved_qps": None,
        "serve_slo_qps": None,
        "stream_s_per_iter": _num(_dig(d, "stream", "s_per_iter")),
        "stream_stall_s": _num(_dig(d, "stream", "stall_s")),
        "stream_peak_bytes": _num(_dig(d, "stream",
                                       "peak_stream_bytes")),
    }
    if blob.get("metric") == "BENCH_serve":
        # serve blobs carry their watched fields top-level
        # (tools/serve_bench.py); the serve gate only ever compares serve
        # blobs against serve blobs — everything else stays n/a.
        out["serve_warm_qps"] = _num(blob.get("warm_qps"))
        out["serve_p50_ms"] = _num(blob.get("p50_ms"))
        out["serve_p99_ms"] = _num(blob.get("p99_ms"))
        out["serve_compiles"] = _num(blob.get("compiles"))
        out["serve_plan_bytes"] = _num(blob.get("plan_bytes"))
        out["serve_restart_compiles"] = _num(blob.get("restart_compiles"))
        out["serve_p999_ms"] = _num(blob.get("p999_ms"))
        out["serve_achieved_qps"] = _num(blob.get("achieved_qps"))
        out["serve_slo_qps"] = _num(blob.get("slo_qps"))
    return out


def compare_pair(old: dict, new: dict, max_regress: float,
                 overrides: Dict[str, float],
                 label_old: str = "old", label_new: str = "new"
                 ) -> Tuple[List[tuple], List[str]]:
    """Per-metric comparison rows ``(metric, old, new, delta%, verdict)``
    plus the list of metric names that REGRESSED past their threshold."""
    mo, mn = extract_metrics(old), extract_metrics(new)
    rows, regressed = [], []
    for name, higher_better in WATCHED:
        vo, vn = mo.get(name), mn.get(name)
        if vo is None or vn is None:
            rows.append((name, _fmt(vo), _fmt(vn), "-", "n/a"))
            continue
        if vo == 0:
            if not higher_better and vn > 0:
                # a lower-is-better metric leaving zero is an infinite-
                # fraction regression (e.g. restart_compiles 0 -> 3 means
                # the zero-cold-start guarantee broke) — never skippable.
                rows.append((name, _fmt(vo), _fmt(vn), "+inf",
                             "REGRESS (was zero)"))
                regressed.append(name)
            else:
                rows.append((name, _fmt(vo), _fmt(vn), "-",
                             "n/a (old is zero)" if vn != 0 else "ok"))
            continue
        delta = (vn - vo) / abs(vo)
        # regression = the bad direction: slower / fewer QPS / more bytes
        bad = -delta if higher_better else delta
        thr = overrides.get(name, max_regress)
        if bad > thr:
            verdict = f"REGRESS (>{thr:.0%})"
            regressed.append(name)
        elif bad < 0:
            verdict = "improved"
        else:
            verdict = "ok"
        rows.append((name, _fmt(vo), _fmt(vn), f"{delta:+.1%}", verdict))
    return rows, regressed


def _fmt(v) -> str:
    if v is None:
        return "-"
    if abs(v) >= 1e6:
        return f"{v:.4g}"
    return f"{v:.4f}".rstrip("0").rstrip(".") or "0"


def _table(header, rows):
    widths = [max(len(str(h)), *(len(str(r[i])) for r in rows))
              if rows else len(str(h)) for i, h in enumerate(header)]
    def fmt(cols):
        return "  ".join(str(c).ljust(w) for c, w in zip(cols, widths))
    print(fmt(header))
    print(fmt(["-" * w for w in widths]))
    for r in rows:
        print(fmt(r))


def _parse_overrides(items) -> Dict[str, float]:
    out = {}
    known = {name for name, _ in WATCHED}
    for item in items or ():
        name, _, frac = item.partition("=")
        if name not in known or not frac:
            raise SystemExit(
                f"bench_compare: bad --metric-max {item!r} "
                f"(expected one of {sorted(known)} = fraction)")
        out[name] = float(frac)
    return out


def run_pair(path_old: str, path_new: str, max_regress: float,
             overrides: Dict[str, float]) -> int:
    old, new = load_blob(path_old), load_blob(path_new)
    for path, blob in ((path_old, old), (path_new, new)):
        if blob is None:
            print(f"bench_compare: {path} carries no metric blob "
                  f"(wedged round?)", file=sys.stderr)
            return 2
    cpu_old, cpu_new = is_cpu_fallback(old), is_cpu_fallback(new)
    if cpu_old != cpu_new:
        print(f"bench_compare: REFUSED — probe-mismatch: "
              f"{path_old} ran on {blob_platform(old)!r} but {path_new} "
              f"ran on {blob_platform(new)!r}; a CPU-fallback blob is "
              f"never comparable to a live-accelerator blob "
              f"(backend event, not a code regression)", file=sys.stderr)
        return 3
    print(f"# {path_old} ({blob_platform(old)}) -> "
          f"{path_new} ({blob_platform(new)})")
    rows, regressed = compare_pair(old, new, max_regress, overrides)
    _table(("metric", "old", "new", "delta", "verdict"), rows)
    if regressed:
        print(f"\nbench_compare: FAIL — regressed past threshold: "
              f"{', '.join(regressed)}")
        return 1
    print("\nbench_compare: OK")
    return 0


def trajectory_files(paths: List[str]) -> List[str]:
    """Explicit files in the given order, or a directory expanded to its
    sorted ``BENCH_r*.json`` training sequence PLUS the sorted
    ``BENCH_serve_r*.json`` serving sequence (ISSUE-14: the serve
    trajectory gates beside the training one; the two families are
    compared within themselves, never against each other)."""
    if len(paths) == 1 and os.path.isdir(paths[0]):
        found = sorted(glob.glob(os.path.join(paths[0], "BENCH_r*.json")))
        found += sorted(glob.glob(os.path.join(paths[0],
                                               "BENCH_serve_r*.json")))
        if not found:
            raise SystemExit(
                f"bench_compare: no BENCH_r*.json or BENCH_serve_r*.json "
                f"under {paths[0]}")
        return found
    return paths


def _blob_family(blob: dict) -> str:
    return "serve" if blob.get("metric") == "BENCH_serve" else "train"


def run_trajectory(paths: List[str], max_regress: float,
                   overrides: Dict[str, float]) -> int:
    files = trajectory_files(paths)
    loaded: List[Tuple[str, Optional[dict]]] = []
    for path in files:
        blob = load_blob(path)   # raises on unreadable -> exit 2 via main
        loaded.append((path, blob))
        if blob is None:
            print(f"{os.path.basename(path)}: no metric blob "
                  f"(wedged/failed round — skipped)")
        else:
            cpu = " cpu-fallback" if is_cpu_fallback(blob) else ""
            print(f"{os.path.basename(path)}: value={blob.get('value')} "
                  f"platform={blob_platform(blob)}{cpu}")
    metric_rounds = [(p, b) for p, b in loaded if b is not None]
    any_regress = False
    mismatches = 0
    # consecutive pairs WITHIN each blob family: a serving round never
    # compares against a training round (every metric would be n/a)
    pairs = []
    for family in ("train", "serve"):
        fam = [(p, b) for p, b in metric_rounds
               if _blob_family(b) == family]
        pairs.extend(zip(fam, fam[1:]))
    for (p_old, b_old), (p_new, b_new) in pairs:
        name_old = os.path.basename(p_old)
        name_new = os.path.basename(p_new)
        if is_cpu_fallback(b_old) != is_cpu_fallback(b_new):
            mismatches += 1
            print(f"\n{name_old} -> {name_new}: probe-mismatch "
                  f"({blob_platform(b_old)} vs {blob_platform(b_new)}) — "
                  f"backend discontinuity, not compared")
            continue
        print(f"\n{name_old} -> {name_new}:")
        rows, regressed = compare_pair(b_old, b_new, max_regress,
                                       overrides)
        _table(("metric", "old", "new", "delta", "verdict"), rows)
        if regressed:
            any_regress = True
            print(f"REGRESSED: {', '.join(regressed)}")
    n_cmp = max(len(pairs) - mismatches, 0)
    print(f"\nbench_compare: {len(files)} rounds, "
          f"{len(metric_rounds)} with metrics, {n_cmp} compared, "
          f"{mismatches} probe-mismatch pair(s) skipped — "
          f"{'FAIL' if any_regress else 'OK'}")
    return 1 if any_regress else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("paths", nargs="+",
                    help="two blobs (pair mode) or a trajectory "
                         "directory / file list (--trajectory)")
    ap.add_argument("--trajectory", action="store_true",
                    help="walk a BENCH_r*.json sequence instead of "
                         "comparing exactly two blobs")
    ap.add_argument("--max-regress", type=float, default=0.10,
                    help="allowed fractional regression per watched "
                         "metric (default 0.10)")
    ap.add_argument("--metric-max", action="append", metavar="NAME=FRAC",
                    help="per-metric threshold override (repeatable)")
    args = ap.parse_args(argv)
    overrides = _parse_overrides(args.metric_max)
    try:
        if args.trajectory:
            return run_trajectory(args.paths, args.max_regress, overrides)
        if len(args.paths) != 2:
            ap.error("pair mode takes exactly two blob paths "
                     "(or pass --trajectory)")
        return run_pair(args.paths[0], args.paths[1], args.max_regress,
                        overrides)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"bench_compare: {e}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())
