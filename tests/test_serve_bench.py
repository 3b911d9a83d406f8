"""Smoke test for tools/serve_bench.py: the BENCH_serve blob must be
emittable hermetically (JAX_PLATFORMS=cpu) carrying every field the
``bench_compare.py`` serve gate watches (ISSUE-12: warm QPS, p50/p99,
compile count, plan bytes + shrink ratio, post-restart compile count,
platform honesty)."""

import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.serve


def test_serve_bench_smoke():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        SERVE_BENCH_ROWS="1500",
        SERVE_BENCH_ITERS="3",
        SERVE_BENCH_CALLS="12",
        SERVE_BENCH_MAX_BATCH="128",
        PYTHONPATH=os.pathsep.join(
            [root] + os.environ.get("PYTHONPATH", "").split(os.pathsep)),
    )
    r = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "serve_bench.py")],
        capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    blob = None
    for line in r.stdout.splitlines():
        line = line.strip()
        if line.startswith("{"):
            obj = json.loads(line)
            if obj.get("metric") == "BENCH_serve":
                blob = obj
    assert blob is not None, r.stdout
    assert blob["warm_qps"] > 0
    assert blob["p50_ms"] is not None and blob["p50_ms"] >= 0
    assert blob["p99_ms"] >= blob["p50_ms"]
    # ladder: 128-row cap with base 32 / ratio 2 -> at most 3 rungs
    assert blob["compiles"] <= 3
    assert blob["detail"]["served_rows"] > 0
    # the serve-gate fields (tools/bench_compare.py WATCHED serve_*)
    assert blob["quantize"] == "int8"           # SERVE_BENCH_QUANTIZE default
    assert 0 < blob["plan_bytes"] < blob["plan_bytes_fp32"]
    # the tree pack itself shrinks >= 3x even at this tiny 3-tree
    # geometry; the whole-plan ratio needs the bench-default ensemble
    # (tables are exactness-bound f64 keys, same bytes every mode)
    assert blob["detail"]["pack_shrink"] >= 3.0
    assert blob["detail"]["plan_shrink"] > 1.0
    # zero cold-start: the simulated restart paid no XLA compiles
    assert blob["restart_compiles"] == 0
    assert blob["restart_aot_hits"] >= 1
    assert blob["detail"]["restart"]["cold_compiles"] >= 1
    # platform honesty rides the blob (probe machinery input)
    assert blob["detail"]["platform"] == "cpu"
    assert blob["detail"]["cpu_fallback"] is True
    assert blob["detail"]["quantize_error_bound"] > 0


def test_bench_compare_gates_serve_blobs(tmp_path):
    """The serve gate end-to-end: a QPS collapse or a restart-compile
    appearance FAILS pair mode; an identical pair passes."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from tools.bench_compare import main as bc_main

    good = {"metric": "BENCH_serve", "warm_qps": 100.0, "p50_ms": 1.0,
            "p99_ms": 5.0, "compiles": 3, "plan_bytes": 50000,
            "restart_compiles": 0,
            "detail": {"platform": "cpu", "cpu_fallback": True}}
    bad = dict(good, warm_qps=40.0, restart_compiles=3)
    pa, pb, pc = (str(tmp_path / f"{n}.json") for n in "abc")
    for path, blob in ((pa, good), (pb, bad), (pc, dict(good))):
        with open(path, "w") as fh:
            json.dump(blob, fh)
    assert bc_main([pa, pb]) == 1            # regressed: qps + restart
    assert bc_main([pa, pc]) == 0            # identical: ok
    # probe honesty: serve blobs refuse CPU-vs-accelerator comparisons
    tpu = dict(good, detail={"platform": "tpu", "cpu_fallback": False})
    pt = str(tmp_path / "t.json")
    with open(pt, "w") as fh:
        json.dump(tpu, fh)
    assert bc_main([pa, pt]) == 3
