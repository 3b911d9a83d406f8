"""tools/bench_compare.py — the bench regression gate (ISSUE-10):

- pair mode passes on identical/improved blobs, fails (rc 1) on an
  injected >= 10% regression, honors per-metric threshold overrides, and
  REFUSES (rc 3) to compare a CPU-fallback blob against a live-TPU one;
- trajectory mode walks a BENCH_r*.json wrapper sequence: parses every
  file, reports rounds with no metric blob without dying, and flags a
  TPU -> CPU discontinuity as probe-mismatch rather than a regression.
"""

import copy
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOL = os.path.join(REPO, "tools", "bench_compare.py")

sys.path.insert(0, REPO)

from tools.bench_compare import (blob_platform, compare_pair,  # noqa: E402
                                 extract_metrics, is_cpu_fallback,
                                 load_blob)

BASE = {
    "metric": "binary_255leaves_row_iters_per_sec",
    "value": 1_000_000.0,
    "detail": {
        "platform": "tpu",
        "probe": {"verdict": "live", "backend": "tpu"},
        "train_time_s": 10.0, "iters": 20,
        "dispatches_per_iter": 1.0,
        "predict": {"warm_qps": 500.0},
        "hlo_cost": {"flops": 1e9, "bytes_accessed": 2e9},
        "memory": {"device": {"bytes_in_use": 9e5,
                              "peak_bytes_in_use": 1e6},
                   "compile": {"count": 3, "seconds": 5.0}},
    },
}


def _blob(**mods):
    b = copy.deepcopy(BASE)
    d = b["detail"]
    for key, val in mods.items():
        if key == "cpu":
            d["platform"] = "cpu"
            d["probe"]["backend"] = "cpu"
            d["cpu_fallback"] = True
        elif key in ("train_time_s", "iters", "dispatches_per_iter"):
            d[key] = val
        elif key == "qps":
            d["predict"]["warm_qps"] = val
        elif key == "peak_hbm":
            d["memory"]["device"]["peak_bytes_in_use"] = val
        elif key == "compile_s":
            d["memory"]["compile"]["seconds"] = val
        else:
            raise KeyError(key)
    return b


def _write(tmp_path, name, blob):
    path = str(tmp_path / name)
    with open(path, "w") as fh:
        json.dump(blob, fh)
    return path


def _run(*argv):
    return subprocess.run([sys.executable, TOOL, *argv],
                          capture_output=True, text=True, timeout=120)


# -------------------------------------------------------------- extraction
def test_extract_metrics_covers_watched_set():
    m = extract_metrics(BASE)
    assert m["train_s_per_iter"] == 0.5
    assert m["predict_qps"] == 500.0
    assert m["hlo_flops"] == 1e9 and m["hlo_bytes"] == 2e9
    assert m["peak_hbm_bytes"] == 1e6
    assert m["compile_s"] == 5.0
    assert m["dispatches_per_iter"] == 1.0


def test_platform_prefers_probe_block():
    b = _blob()
    b["detail"]["platform"] = "cpu"        # stale self-report
    assert blob_platform(b) == "tpu"       # probe verdict wins
    assert not is_cpu_fallback(b)
    assert is_cpu_fallback(_blob(cpu=True))
    # bench.py's device identity block (jax's own report) outranks both
    b["detail"]["device"] = {"platform": "cpu", "kind": "cpu", "count": 1}
    assert blob_platform(b) == "cpu" and is_cpu_fallback(b)


def test_load_blob_accepts_both_shapes(tmp_path):
    raw = _write(tmp_path, "raw.json", BASE)
    wrapper = _write(tmp_path, "wrap.json",
                     {"n": 2, "rc": 0, "tail": "...", "parsed": BASE})
    wedged = _write(tmp_path, "wedged.json",
                    {"n": 3, "rc": 1, "tail": "...", "parsed": None})
    assert load_blob(raw)["value"] == BASE["value"]
    assert load_blob(wrapper)["value"] == BASE["value"]
    assert load_blob(wedged) is None
    bad = _write(tmp_path, "bad.json", {"hello": 1})
    with pytest.raises(ValueError):
        load_blob(bad)


def test_compare_pair_missing_metrics_are_na():
    lean = {"metric": "m", "value": 1.0,
            "detail": {"train_time_s": 10.0, "iters": 20,
                       "platform": "cpu"}}
    rows, regressed = compare_pair(lean, lean, 0.10, {})
    verdicts = {r[0]: r[4] for r in rows}
    assert verdicts["train_s_per_iter"] == "ok"
    assert verdicts["predict_qps"] == "n/a"
    assert verdicts["peak_hbm_bytes"] == "n/a"
    assert not regressed


# --------------------------------------------------------------- pair CLI
def test_pair_identical_passes(tmp_path):
    a = _write(tmp_path, "a.json", _blob())
    b = _write(tmp_path, "b.json", _blob())
    r = _run(a, b)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout


def test_pair_injected_regression_fails(tmp_path):
    a = _write(tmp_path, "a.json", _blob())
    b = _write(tmp_path, "b.json", _blob(train_time_s=11.5))  # +15%
    r = _run(a, b)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "REGRESS" in r.stdout and "train_s_per_iter" in r.stdout


def test_pair_higher_better_direction(tmp_path):
    a = _write(tmp_path, "a.json", _blob())
    worse = _write(tmp_path, "b.json", _blob(qps=400.0))   # -20% QPS
    better = _write(tmp_path, "c.json", _blob(qps=600.0))
    assert _run(a, worse).returncode == 1
    r = _run(a, better)
    assert r.returncode == 0
    assert "improved" in r.stdout


def test_pair_memory_metrics_gated(tmp_path):
    a = _write(tmp_path, "a.json", _blob())
    b = _write(tmp_path, "b.json", _blob(peak_hbm=1.3e6))   # +30% HBM
    assert _run(a, b).returncode == 1
    c = _write(tmp_path, "c.json", _blob(compile_s=20.0))
    assert _run(a, c).returncode == 1
    # per-metric override loosens just that metric
    assert _run(a, c, "--metric-max", "compile_s=4.0").returncode == 0


def test_pair_threshold_flag(tmp_path):
    a = _write(tmp_path, "a.json", _blob())
    b = _write(tmp_path, "b.json", _blob(train_time_s=11.5))  # +15%
    assert _run(a, b, "--max-regress", "0.25").returncode == 0


def test_pair_probe_mismatch_refused(tmp_path):
    tpu = _write(tmp_path, "tpu.json", _blob())
    cpu = _write(tmp_path, "cpu.json", _blob(cpu=True))
    r = _run(tpu, cpu)
    assert r.returncode == 3, r.stdout + r.stderr
    assert "probe-mismatch" in r.stderr
    # same-platform CPU blobs DO compare (the PR-6 honesty block rule:
    # CPU-fallback compares only against CPU-fallback)
    cpu2 = _write(tmp_path, "cpu2.json", _blob(cpu=True))
    assert _run(cpu, cpu2).returncode == 0


def test_unreadable_input_is_usage_error(tmp_path):
    a = _write(tmp_path, "a.json", _blob())
    r = _run(a, str(tmp_path / "missing.json"))
    assert r.returncode == 2


# --------------------------------------------------- committed trajectory
def test_trajectory_walks_wrappers_nulls_and_platform_cliff(tmp_path):
    """Trajectory mode over a synthetic round sequence (a TPU blob, a round
    whose metric line was lost — ``parsed: null`` — a second TPU blob and
    a CPU round): every round is parsed and listed, the null round is
    reported as having no metric blob instead of killing the walk, the
    TPU -> CPU cliff is flagged probe-mismatch and skipped — exit 0,
    because a backend discontinuity is not a code regression."""
    rounds = {
        "BENCH_r01.json": {"n": 1, "rc": 0, "tail": "", "parsed": _blob()},
        "BENCH_r02.json": {"n": 2, "rc": 1, "tail": "lost", "parsed": None},
        "BENCH_r03.json": {"n": 3, "rc": 0, "tail": "",
                           "parsed": _blob(train_time_s=10.2)},
        "BENCH_r04.json": {"n": 4, "rc": 0, "tail": "",
                           "parsed": _blob(cpu=True, train_time_s=300.0)},
    }
    for name, blob in rounds.items():
        _write(tmp_path, name, blob)
    r = _run("--trajectory", str(tmp_path))
    assert r.returncode == 0, r.stdout + r.stderr
    for name in rounds:
        assert name in r.stdout        # every round parsed and listed
    assert "probe-mismatch" in r.stdout
    assert "no metric blob" in r.stdout
    assert "OK" in r.stdout.splitlines()[-1]


def test_trajectory_synthetic_regression_fails(tmp_path):
    _write(tmp_path, "BENCH_r01.json",
           {"n": 1, "rc": 0, "tail": "", "parsed": _blob()})
    _write(tmp_path, "BENCH_r02.json",
           {"n": 2, "rc": 0, "tail": "", "parsed": _blob(train_time_s=13.0)})
    r = _run("--trajectory", str(tmp_path))
    assert r.returncode == 1, r.stdout + r.stderr
    assert "REGRESSED" in r.stdout


# --------------------------------------------------- serve trajectory (I-14)
def _serve_blob(**top):
    b = {
        "metric": "BENCH_serve", "mode": "load",
        "offered_qps": 100.0, "achieved_qps": 99.0,
        "p50_ms": 2.0, "p99_ms": 9.0, "p999_ms": 14.0,
        "slo_qps": 120.0,
        "detail": {"platform": "cpu", "cpu_fallback": True},
    }
    b.update(top)
    return b


def test_serve_trajectory_committed_fixture():
    """The committed serve-trajectory smoke (ISSUE-14 satellite): two
    BENCH_serve_r*.json wrapper files walk through trajectory mode, the
    load-gate metrics (achieved QPS / p999 / slo_qps) compare, rc 0."""
    fix = os.path.join(REPO, "tests", "fixtures", "serve_traj")
    files = sorted(os.listdir(fix))
    assert files == ["BENCH_serve_r01.json", "BENCH_serve_r02.json"]
    r = _run("--trajectory", fix)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "BENCH_serve_r01.json -> BENCH_serve_r02.json" in r.stdout
    for metric in ("serve_achieved_qps", "serve_p999_ms", "serve_p99_ms"):
        assert metric in r.stdout
    assert "1 compared" in r.stdout
    assert r.stdout.splitlines()[-1].endswith("OK")


def test_serve_trajectory_families_never_cross_compare(tmp_path):
    """A directory holding BOTH families compares train rounds against
    train rounds and serve rounds against serve rounds — never across
    (every cross metric would be n/a and the pair count would lie)."""
    _write(tmp_path, "BENCH_r01.json",
           {"n": 1, "rc": 0, "tail": "", "parsed": _blob()})
    _write(tmp_path, "BENCH_r02.json",
           {"n": 2, "rc": 0, "tail": "", "parsed": _blob()})
    _write(tmp_path, "BENCH_serve_r01.json",
           {"n": 3, "rc": 0, "tail": "", "parsed": _serve_blob()})
    _write(tmp_path, "BENCH_serve_r02.json",
           {"n": 4, "rc": 0, "tail": "", "parsed": _serve_blob()})
    r = _run("--trajectory", str(tmp_path))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "BENCH_r01.json -> BENCH_r02.json" in r.stdout
    assert "BENCH_serve_r01.json -> BENCH_serve_r02.json" in r.stdout
    assert "BENCH_r02.json -> BENCH_serve_r01.json" not in r.stdout
    assert "2 compared" in r.stdout


def test_serve_trajectory_regression_and_probe_refusal(tmp_path):
    """The serve gate fails on a load-metric regression and keeps the
    probe-honesty refusal: a CPU-fallback serve blob never compares
    against a live-accelerator one."""
    _write(tmp_path, "BENCH_serve_r01.json",
           {"n": 1, "rc": 0, "tail": "", "parsed": _serve_blob()})
    _write(tmp_path, "BENCH_serve_r02.json",
           {"n": 2, "rc": 0, "tail": "",
            "parsed": _serve_blob(p999_ms=28.0, achieved_qps=60.0)})
    r = _run("--trajectory", str(tmp_path))
    assert r.returncode == 1, r.stdout + r.stderr
    assert "serve_p999_ms" in r.stdout and "REGRESSED" in r.stdout
    # pair-mode refusal on a platform cliff (same rule as training blobs)
    tpu = _serve_blob()
    tpu["detail"] = {"platform": "tpu", "cpu_fallback": False,
                     "probe": {"verdict": "live", "backend": "tpu"}}
    a = _write(tmp_path, "serve_tpu.json", tpu)
    b = _write(tmp_path, "serve_cpu.json", _serve_blob())
    r = _run(a, b)
    assert r.returncode == 3, r.stdout + r.stderr
