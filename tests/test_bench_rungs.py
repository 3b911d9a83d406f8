"""bench.py shape-matrix rungs (ISSUE-4 satellite, GOSS rung ISSUE-5): the
lambdarank (MS-LTR-like), wide (Epsilon-like) and GOSS (Higgs-shape
sampled) rungs must emit their detail blobs — each naming the device it
ran on — the wide rung must actually engage the bounded histogram pool it
exists to exercise, and the GOSS rung must witness the device-resident
sampler's ONE compiled dispatch per boosting round.  Scaled-down
geometries here; bench.py's env knobs carry the full sizes.

And the run's honesty rules (ISSUE-21): no chip => non-zero exit and no
metric line; a raising rung => non-zero exit; a CPU rehearsal's blob says
so and carries no value under the device metric's name."""

import json
import os
import subprocess
import sys

import jax

import bench
from bench import (run_fused_rung, run_goss_rung, run_ltr_rung,
                   run_serve_fused_rung, run_stream_rung, run_wide_rung)


def _assert_hlo_cost(blob):
    """Every rung blob carries the XLA cost-model block (ISSUE-7
    satellite: detail.hlo_cost — the compile-time number kernel PRs land
    with even when no chip answers)."""
    dev = jax.devices()
    assert blob["device"] == {"platform": "cpu", "kind": dev[0].device_kind,
                              "count": len(dev)}
    cost = blob["hlo_cost"]
    assert cost.get("flops", 0) > 0, cost
    assert cost.get("bytes_accessed", 0) > 0, cost
    # ISSUE-8 satellite: every rung blob also carries the post-hoc health
    # audit — a rung that trained on NaN can't publish a clean rate.
    h = blob["health"]
    assert h["verdict"] == "healthy", h
    assert h["rounds_checked"] == blob["iters"]
    assert h["last_health"]["grad_nonfinite"] == 0.0
    # ISSUE-9 satellite: and the schema-valid unified-telemetry block —
    # span totals at dispatch boundaries, per-kind event counts, the
    # process registry snapshot (docs/OBSERVABILITY.md BENCH section).
    t = blob["telemetry"]
    assert t.get("schema") == 1 and t["enabled"] is True, t
    assert isinstance(t["events"], dict)
    assert isinstance(t["registry"], dict) and "counters" in t["registry"]
    assert t["spans"], t
    assert all(d["seconds"] >= 0.0 and d["count"] >= 1
               for d in t["spans"].values()), t["spans"]
    json.dumps(t)   # JSON-serializable end to end (it rides the blob)
    # ISSUE-10: every rung blob also carries the schema-valid
    # detail.memory block — device watermark (None on CPU), live-buffer
    # census, compile count/seconds, host RSS, and the grower program's
    # compiled memory plan beside hlo_cost.
    m = blob["memory"]
    assert "error" not in m, m
    assert set(m) >= {"mode", "device", "live_buffers", "compile",
                      "host_peak_rss_mb", "memory_analysis"}, sorted(m)
    lb = m["live_buffers"]
    assert lb["total_bytes"] > 0 and lb["total_arrays"] > 0, lb
    assert lb["groups"] and lb["groups"][0]["bytes"] >= lb["groups"][-1]["bytes"]
    assert m["compile"]["count"] >= 0 and m["compile"]["seconds"] >= 0.0
    assert m["host_peak_rss_mb"] > 0
    ma = m["memory_analysis"]
    assert "error" not in ma, ma
    json.dumps(m)   # rides the blob too


def test_ltr_rung_blob():
    blob = run_ltr_rung(4200, 2, "cpu", jax, features=24, group=60,
                        num_leaves=15)
    assert blob["rows"] == 4200 and blob["features"] == 24
    assert blob["queries"] == 70
    assert blob["row_iters_per_sec"] > 0
    assert 0.0 <= blob["ndcg5_train_sample"] <= 1.0
    _assert_hlo_cost(blob)


def test_fused_rung_blob_one_dispatch_per_wave():
    """The quantized-fused rung (ISSUE-7): tpu_wave_kernel=fused engages
    (interpret mode on CPU — the kernel body actually runs), the census
    fact says one histogram dispatch per wave, and the blob carries the
    compile-time cost block."""
    blob = run_fused_rung(4096, 2, "cpu", jax, features=10, num_leaves=15)
    assert blob["rows"] == 4096 and blob["quantized"] is True
    assert blob["wave_kernel"] == "fused"
    assert blob["wave_fused_active"] is True
    assert blob["hist_dispatches_per_wave"] == 1
    assert blob["interpret_mode"] is True
    assert blob["row_iters_per_sec"] > 0
    _assert_hlo_cost(blob)


def test_wide_rung_blob_pool_engaged():
    # features > 256 also auto-engages the tiled split scan; rows must
    # exceed _MIN_BUCKET so the pooled perm layout (not the mask
    # fallback) runs.
    blob = run_wide_rung(2600, 2, "cpu", jax, features=320, num_leaves=31,
                         max_bin=31, pool_mb=1.0)
    assert blob["rows"] == 2600 and blob["features"] == 320
    assert blob["row_iters_per_sec"] > 0
    assert blob["pool_engaged"] is True
    assert blob["pool_slots"] < 31
    assert blob["leaf_hist_mb_pooled"] < blob["leaf_hist_mb_unpooled"]
    _assert_hlo_cost(blob)


def test_goss_rung_blob_one_dispatch():
    blob = run_goss_rung(4096, 2, "cpu", jax, features=12, num_leaves=15)
    assert blob["rows"] == 4096 and blob["features"] == 12
    assert blob["data_sample_strategy"] == "goss"
    assert blob["row_iters_per_sec"] > 0
    # device GOSS (tpu_device_goss auto) keeps the round fused: the mask
    # is derived in-trace, so the census sees exactly one program launch
    assert blob["used_fused"] is True
    assert blob["dispatches_per_iter"] == 1.0
    assert blob["host_syncs_per_iter"] <= 2.0
    _assert_hlo_cost(blob)


def test_serve_fused_rung_blob():
    """The quantized-traversal serving rung (ISSUE-12): int8 pack + fused
    Pallas traversal (interpret mode on CPU — the kernel body runs), the
    fused-vs-unfused integer identity asserted in-rung, >= 3x pack
    shrink, fp32 parity inside the analytic bound, and the zero-cold-
    start restart paying no compiles."""
    blob = run_serve_fused_rung(2600, 2, "cpu", jax, features=10,
                                num_leaves=15, calls=4, max_batch=64)
    assert blob["rows"] == 2600 and blob["quantize"] == "int8"
    assert blob["traverse"] == "fused"
    assert blob["interpret_mode"] is True
    assert blob["fused_bitwise_unfused"] is True
    assert blob["warm_qps"] > 0
    assert blob["p99_ms"] >= blob["p50_ms"] >= 0
    assert blob["pack_shrink"] >= 3.0
    assert 0 < blob["plan_bytes"] < blob["plan_bytes_fp32"]
    assert blob["parity_ok"] is True
    assert blob["parity_err"] <= blob["parity_bound"] + 1e-12
    r = blob["restart"]
    assert r["cold_compiles"] >= 1
    assert r["restart_compiles"] == 0
    assert r["restart_aot_hits"] >= 1


def test_stream_rung_blob_budget_witnessed():
    """The out-of-core streaming rung (ISSUE-13): trains through the
    budget-bounded residency pipeline, WITNESSES peak streaming bytes <=
    the budget (asserted in-rung too — a violating blob never publishes),
    reports the prefetch ledger, and on CPU asserts the streamed trees
    bitwise-equal the in-core run's."""
    blob = run_stream_rung(4096, 2, "cpu", jax, features=10, num_leaves=7,
                           budget_mb=0.25)
    assert blob["rows"] == 4096 and blob["budget_ok"] is True
    assert blob["bitwise_identical"] is True
    assert 0 < blob["peak_stream_bytes"] <= blob["budget_bytes"]
    assert blob["peak_stream_bytes"] < blob["full_bins_bytes"] \
        or blob["chunks"] == 1
    assert blob["prefetch_hits"] + blob["prefetch_stalls"] >= blob["chunks"]
    assert blob["s_per_iter"] > 0 and blob["incore_s_per_iter"] > 0
    assert blob["shards"] >= 1 and blob["train_time_s"] > 0


# ------------------------------- the run's honesty rules (ISSUE-21) ----
def test_no_chip_exits_nonzero_and_prints_no_metric():
    """``python bench.py`` where jax resolves no tpu: the one child refuses
    before measuring, the parent passes its code through, and nothing is
    written under the device metric's name."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "bench.py")],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", BENCH_ROWS="4096",
                 BENCH_ITERS="2"))
    assert proc.returncode != 0, proc.stdout
    assert "metric" not in proc.stdout and "{" not in proc.stdout
    assert "only taken on a tpu" in proc.stderr


def test_raising_rung_fails_the_run_and_rehearsal_blob_says_so(
        monkeypatch, capsys):
    """The child in rehearsal mode (what ``JAX_PLATFORMS=cpu python
    bench.py --dry-run`` runs): every emitted blob names the device, says
    ``rehearsal``, and carries neither ``value`` nor ``vs_baseline``; a
    rung that raises lands its error in its slot, is listed in
    ``failed_rungs``, and turns the exit code non-zero — the finished
    rungs still print."""
    for flag in ("PREDICT_CHECK", "LTR_CHECK", "WIDE_CHECK", "FUSED_CHECK",
                 "SERVE_FUSED_CHECK", "STREAM_CHECK", "QUANT_CHECK"):
        monkeypatch.setattr(bench, flag, False)
    monkeypatch.setattr(bench, "GOSS_CHECK", True)
    monkeypatch.setattr(bench, "NUM_LEAVES", 15)
    monkeypatch.setattr(bench, "LEAF_BATCH", 4)

    def boom(*a, **k):
        raise RuntimeError("rung exploded (simulated)")

    monkeypatch.setattr(bench, "run_goss_rung", boom)
    rc = bench.run_bench(4096, 2, rehearsal=True, cache_dir="<unset>")
    assert rc == 1
    blobs = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    assert len(blobs) == 2            # the primary, then the failed rung
    dev = jax.devices()
    for blob in blobs:
        d = blob["detail"]
        assert d["device"] == {"platform": "cpu",
                               "kind": dev[0].device_kind,
                               "count": len(dev)}
        assert d["rehearsal"] is True
        assert blob["value"] is None and blob["vs_baseline"] is None
        assert d["train_time_s"] > 0          # the plumbing did run
    assert blobs[0]["detail"]["failed_rungs"] == []
    last = blobs[-1]["detail"]
    assert last["failed_rungs"] == ["goss"]
    assert "rung exploded (simulated)" in last["goss"]["error"]


def test_non_tpu_without_rehearsal_refuses_before_measuring(capsys):
    """Without --dry-run a cpu backend is refused (exit 2), even in the
    child itself, and no blob is printed."""
    assert bench.run_bench(4096, 2) == 2
    out = capsys.readouterr()
    assert "{" not in out.out and "only taken on a tpu" in out.err
