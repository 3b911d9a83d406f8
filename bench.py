"""Benchmark: Higgs-style binary classification training throughput.

Mirrors the reference's headline config (docs/Experiments.rst:82-91 — 255 leaves,
lr=0.1, max_bin=255, binary objective on Higgs 10.5M x 28).  Data is synthetic
Higgs-scale-per-feature (28 features); rows are scaled to fit the bench budget
and throughput is normalized to row-iterations/second so it is comparable to the
reference's published wall-clock:

    reference CPU (16 threads): 10.5M rows x 500 iters / 130.094 s = 40.4M row-iters/s
    (BASELINE.md; docs/Experiments.rst:113)

Prints JSON metric lines (cumulative; the last is the fullest) with
vs_baseline = ours / reference.

The numbers are device numbers, so the run needs the device: the child
exits non-zero — before measuring anything — when jax resolves any
platform but ``tpu``, and non-zero after measuring when any rung raised
(the partial blob is still printed, with the error in the rung's slot).
There is no retry and no fallback to another backend or histogram
implementation.  ``JAX_PLATFORMS=cpu python bench.py --dry-run`` (with
small ``BENCH_ROWS``/``BENCH_ITERS``) rehearses the plumbing on the CPU;
its blob says ``rehearsal``, carries no ``value`` and no ``vs_baseline``.

One process per chip: this parent process never imports jax (a parent that
has touched jax holds the chip); it runs ONE child under a hard timeout,
streams its output through, and exits with its code.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np

ROWS = int(os.environ.get("BENCH_ROWS", 10_500_000))
FEATURES = 28
ITERS = int(os.environ.get("BENCH_ITERS", 60))
NUM_LEAVES = 255
REFERENCE_ROW_ITERS_PER_SEC = 10_500_000 * 500 / 130.094
ATTEMPT_TIMEOUT = int(os.environ.get("BENCH_ATTEMPT_TIMEOUT", 2400))
# Wave growth width for the bench config (quality-equivalent best-first
# set; see models/grower.py GrowerConfig.leaf_batch).
LEAF_BATCH = int(os.environ.get("BENCH_LEAF_BATCH", 16))
QUANTIZED = os.environ.get("BENCH_QUANTIZED", "0") == "1"
# Also measure the int8 quantized-training path (reference quantized
# training headline) and record it inside detail.* — the primary metric
# line stays the fp32 config.
QUANT_CHECK = os.environ.get("BENCH_QUANT_CHECK", "1") == "1"
QUANT_ITERS = int(os.environ.get("BENCH_QUANT_ITERS", 20))
# Iteration packing (docs/ITER_PACK.md): boosting rounds scanned into one
# XLA dispatch.  0 disables (per-round update()); the effective size is
# clamped to a divisor of the timed iteration count so the measured window
# never recompiles a remainder pack.
ITER_PACK = int(os.environ.get("BENCH_ITER_PACK", 12))
# Serving phase (docs/SERVING.md): warm QPS / p50 latency / compile census
# for the compiled predict plan, reported inside detail.predict.
PREDICT_CHECK = os.environ.get("BENCH_PREDICT", "1") == "1"
PREDICT_CALLS = int(os.environ.get("BENCH_PREDICT_CALLS", 40))
PREDICT_MAX_BATCH = int(os.environ.get("BENCH_PREDICT_MAX_BATCH", 8192))
# Shape-matrix rungs (ISSUE-4 / BASELINE.md table beyond Higgs): a
# lambdarank rung at the MS-LTR geometry (137 features, query groups,
# NDCG@5 reported) and a wide rung at the Epsilon geometry (dense F=2000,
# where the bounded histogram pool + tiled split scan are what make the
# shape fit).  Each emits its own blob inside detail.* and never disturbs
# the primary Higgs metric (emitted first; rung failures record an error
# string).  Both rungs shrink with the primary row budget.
LTR_CHECK = os.environ.get("BENCH_LTR", "1") == "1"
LTR_ROWS = int(os.environ.get("BENCH_LTR_ROWS", 2_270_000))   # MS-LTR scale
LTR_FEATURES = int(os.environ.get("BENCH_LTR_FEATURES", 137))
LTR_ITERS = int(os.environ.get("BENCH_LTR_ITERS", 15))
LTR_GROUP = int(os.environ.get("BENCH_LTR_GROUP", 120))       # docs/query
WIDE_CHECK = os.environ.get("BENCH_WIDE", "1") == "1"
WIDE_ROWS = int(os.environ.get("BENCH_WIDE_ROWS", 400_000))   # Epsilon scale
WIDE_FEATURES = int(os.environ.get("BENCH_WIDE_FEATURES", 2000))
WIDE_ITERS = int(os.environ.get("BENCH_WIDE_ITERS", 10))
WIDE_POOL_MB = float(os.environ.get("BENCH_WIDE_POOL_MB", 256.0))
# GOSS rung (ISSUE-5): Higgs shape under data_sample_strategy=goss — the
# device-resident sampler keeps the boosting round ONE compiled dispatch
# (tpu_device_goss auto), witnessed as dispatches_per_iter in the blob.
GOSS_CHECK = os.environ.get("BENCH_GOSS", "1") == "1"
GOSS_ITERS = int(os.environ.get("BENCH_GOSS_ITERS", 15))
# Quantized-fused rung (ISSUE-7): Higgs shape, tpu_wave_kernel=fused + the
# int8 quantized wire — one pallas dispatch per wave builds, subtracts and
# scans in VMEM.  On non-TPU platforms the kernel runs in interpret mode
# (a correctness vehicle, not a speed number; the blob says so).
FUSED_CHECK = os.environ.get("BENCH_FUSED", "1") == "1"
FUSED_ITERS = int(os.environ.get("BENCH_FUSED_ITERS", 12))
# Quantized-traversal serving rung (ISSUE-12): the int8 serving pack +
# fused Pallas traversal + AOT restart simulation, emitting
# detail.serve_fused beside the training rungs — warm QPS, pack shrink
# ratio, fp32-parity gap vs its bound, and the zero-cold-start restart
# compile count.  Interpret-mode kernel on non-TPU platforms (the blob
# says so).
SERVE_FUSED_CHECK = os.environ.get("BENCH_SERVE_FUSED", "1") == "1"
SERVE_FUSED_ITERS = int(os.environ.get("BENCH_SERVE_FUSED_ITERS", 12))
SERVE_FUSED_CALLS = int(os.environ.get("BENCH_SERVE_FUSED_CALLS", 20))
# Out-of-core streaming rung (ISSUE-13, lightgbm_tpu/stream/): the Higgs
# shape sharded to disk and trained at a DELIBERATELY tiny
# tpu_stream_budget_mb, witnessing peak streaming-buffer bytes <= budget
# (asserted in-rung against the residency accounting), prefetch
# hit/stall seconds, and s/iter vs the same config in-core.
STREAM_CHECK = os.environ.get("BENCH_STREAM", "1") == "1"
STREAM_ITERS = int(os.environ.get("BENCH_STREAM_ITERS", 6))
STREAM_BUDGET_MB = float(os.environ.get("BENCH_STREAM_BUDGET_MB", 8.0))
STREAM_LEAVES = int(os.environ.get("BENCH_STREAM_LEAVES", 31))


def _pack_eff(iters, pack):
    """Largest divisor of ``iters`` that is <= ``pack`` (1 = per-round)."""
    if pack <= 1 or iters <= 0:
        return 1
    return max(d for d in range(1, min(pack, iters) + 1) if iters % d == 0)


def bench_params():
    """The headline training config (docs/Experiments.rst:82-91) with the
    env knobs applied — shared with tools/profile_iter.py so a profiler
    trace always compiles the SAME program the bench measured."""
    params = {
        "objective": "binary",
        "num_leaves": NUM_LEAVES,
        "learning_rate": 0.1,
        "max_bin": 255,
        "min_data_in_leaf": 0,
        "min_sum_hessian_in_leaf": 100.0,
        "metric": "none",
        "verbosity": -1,
        "tpu_leaf_batch": LEAF_BATCH,
    }
    if QUANTIZED:
        params["use_quantized_grad"] = True
    return params


def make_higgs_like(n, f, seed=0):
    """Higgs-like synthetic binary data from a seed (no files)."""
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    w = rng.randn(f) / np.sqrt(f)
    logits = X @ w + 0.5 * np.sin(X[:, 0] * 2) * X[:, 1]
    p = 1 / (1 + np.exp(-logits))
    y = (rng.rand(n) < p).astype(np.float64)
    return X, y


def make_msltr_like(n, f, group, seed=0):
    """MS-LTR-like synthetic ranking data: fixed-size query groups, graded
    relevance 0-4 skewed to low grades (the reference's LTR benchmark
    shape, docs/Experiments.rst:115)."""
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    w = rng.randn(f) / np.sqrt(f)
    util = X @ w + 0.3 * rng.randn(n)
    # per-row grade from global utility quantiles (60/20/10/7/3%)
    cuts = np.quantile(util, [0.60, 0.80, 0.90, 0.97])
    y = np.searchsorted(cuts, util).astype(np.float64)
    groups = np.full(n // group, group, np.int64)
    rem = n - groups.sum()
    if rem:
        groups = np.concatenate([groups, [rem]])
    return X, y, groups


def make_epsilon_like(n, f, seed=0):
    """Epsilon-like dense wide binary data (f ~ 2000 gaussian features)."""
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    w = rng.randn(f) / np.sqrt(f)
    y = (X @ w + 0.5 * rng.randn(n) > 0).astype(np.float64)
    return X, y


def _interpret_mode():
    from lightgbm_tpu.ops.pallas_common import interpret_mode
    return interpret_mode()


def _health_block(bst, rounds):
    """The ``detail.health`` block every BENCH/rung blob carries (ISSUE-8):
    one post-hoc sentinel audit (the same isfinite/max-abs reductions the
    in-dispatch health vector runs, outside the timed window) plus the
    process-level int16-wire overflow tally — so a rung that silently
    trained on NaN can never publish a clean-looking rate."""
    try:
        from lightgbm_tpu.resilience.health import bench_health_block
        return bench_health_block(bst, rounds)
    except Exception as e:  # noqa: BLE001 — audit is garnish on the rate
        return {"error": f"{e!r}"[:160]}


def _telemetry_block():
    """The ``detail.telemetry`` block every BENCH/rung blob carries
    (ISSUE-9): schema version, armed state, per-kind event counts, span
    totals (where the wall clock went, by phase, at dispatch boundaries)
    and the process registry snapshot — so every bench round lands with
    its observability state attached."""
    try:
        from lightgbm_tpu import telemetry
        return telemetry.telemetry_block()
    except Exception as e:  # noqa: BLE001 — telemetry is garnish on the rate
        return {"error": f"{e!r}"[:160]}


def _memory_block(bst):
    """The ``detail.memory`` block every BENCH/rung blob carries
    (ISSUE-10): device HBM watermark (null where the backend reports none),
    the live-buffer census grouped by shape/dtype, the process compile
    count/seconds, host peak RSS, and XLA's compiled memory plan
    (temp/generated-code/argument/output bytes) for the rung's grower
    program — the byte-side twin of ``hlo_cost``, sharing its one AOT
    compile.  Re-built at every cumulative emit, so the primary blob's
    census reflects the last finished rung."""
    try:
        from lightgbm_tpu.telemetry.memory import memory_block
        blk = memory_block()
    except Exception as e:  # noqa: BLE001 — accounting is garnish on the rate
        return {"error": f"{e!r}"[:160]}
    try:
        from tools.profile_iter import train_step_memory_analysis
        blk["memory_analysis"] = train_step_memory_analysis(bst)
    except Exception as e:  # noqa: BLE001
        blk["memory_analysis"] = {"error": f"{e!r}"[:160]}
    return blk


def _hlo_cost_block(bst):
    """The per-rung HLO cost block (ROADMAP 3b, ISSUE-7 satellite): XLA's
    own cost model (FLOPs / bytes accessed) for the rung's compiled grower
    program, so every kernel PR lands with a compile-time cost number even
    when the TPU probe verdict is not live.  Deltas across BENCH rounds =
    the kernel's cost trajectory."""
    try:
        from tools.profile_iter import train_step_hlo_cost
        return train_step_hlo_cost(bst)
    except Exception as e:  # noqa: BLE001 — cost is garnish on the rate
        return {"error": f"{e!r}"[:200]}


def _rung_train(params, ds_kw, iters, jax):
    """Train one side-rung booster and return (booster, elapsed_s)."""
    import lightgbm_tpu as lgb

    ds = lgb.Dataset(ds_kw.pop("X"), **ds_kw)
    ds.construct(params)
    bst = lgb.Booster(params=params, train_set=ds)
    bst.update()                                    # warmup compile
    np.array(jax.device_get(bst._gbdt.scores[:8]))
    t0 = time.time()
    for _ in range(iters):
        bst.update()
    np.array(jax.device_get(bst._gbdt.scores[:8]))
    return bst, time.time() - t0


def run_ltr_rung(rows, iters, platform, jax, features=None, group=None,
                 num_leaves=None):
    """lambdarank throughput + NDCG@5 sample at the MS-LTR geometry;
    returns the detail blob."""
    features = features or LTR_FEATURES
    group = group or LTR_GROUP
    num_leaves = num_leaves or NUM_LEAVES
    X, y, groups = make_msltr_like(rows, features, group)
    params = {"objective": "lambdarank", "num_leaves": num_leaves,
              "learning_rate": 0.1, "max_bin": 255, "min_data_in_leaf": 0,
              "min_sum_hessian_in_leaf": 100.0, "metric": "none",
              "verbosity": -1, "tpu_leaf_batch": LEAF_BATCH}
    bst, elapsed = _rung_train(
        params, dict(X=X, label=y, group=groups), iters, jax)
    from lightgbm_tpu.metrics import _ndcg_multi
    nq = min(len(groups), 500)
    ns = int(groups[:nq].sum())
    pred = bst.predict(X[:ns], raw_score=True)
    gains = np.array([2.0 ** i - 1.0 for i in range(32)])
    ndcg = _ndcg_multi(y[:ns], pred, groups[:nq], [5], gains)[0]
    return {
        "rows": rows, "features": features, "iters": iters,
        "num_leaves": num_leaves, "queries": int(len(groups)),
        "docs_per_query": group, "platform": platform,
        "device": device_block(jax),
        "train_time_s": round(elapsed, 3),
        "row_iters_per_sec": round(rows * iters / elapsed, 1),
        "ndcg5_train_sample": round(ndcg, 6),
        "hlo_cost": _hlo_cost_block(bst),
        "health": _health_block(bst, iters),
        "telemetry": _telemetry_block(),
        "memory": _memory_block(bst),
    }


def run_wide_rung(rows, iters, platform, jax, features=None,
                  num_leaves=None, max_bin=None, pool_mb=None):
    """Dense-wide (Epsilon-like) rung: the (L, F, B, 3) leaf-histogram
    carry that motivates the bounded pool (~1.5 GB f32 unpooled at
    F=2000/B=256/L=255).  Trains with histogram_pool_size set so the blob
    also witnesses the pooled carry; returns the detail blob.  The chip
    figure for this shape, unpooled, lives in the root PERF.md under the
    benchmark cell ``epsilon.train`` (PR 31); this rung is the CPU one."""
    features = features or WIDE_FEATURES
    # CPU rehearsal: XLA-on-host cannot afford B=256 x F=2000 histograms —
    # shrink depth/bins, keep the WIDTH (the shape under test).
    cpu = platform == "cpu"
    num_leaves = num_leaves or (63 if cpu else NUM_LEAVES)
    max_bin = max_bin or (63 if cpu else 255)
    pool_mb = WIDE_POOL_MB if pool_mb is None else pool_mb
    X, y = make_epsilon_like(rows, features)
    params = {"objective": "binary", "num_leaves": num_leaves,
              "learning_rate": 0.1, "max_bin": max_bin,
              "min_data_in_leaf": 0, "min_sum_hessian_in_leaf": 100.0,
              "metric": "none", "verbosity": -1,
              "tpu_leaf_batch": min(LEAF_BATCH, 8),
              "histogram_pool_size": pool_mb}
    bst, elapsed = _rung_train(params, dict(X=X, label=y), iters, jax)
    g = bst._gbdt
    bins = g.train_data.binned.max_num_bins
    slots = g.grow.pool_slots(features)
    return {
        "rows": rows, "features": features, "iters": iters,
        "num_leaves": num_leaves, "max_bin": max_bin, "platform": platform,
        "device": device_block(jax),
        "train_time_s": round(elapsed, 3),
        "row_iters_per_sec": round(rows * iters / elapsed, 1),
        "histogram_pool_mb": pool_mb,
        "pool_slots": int(slots),
        "pool_engaged": bool(g.plan.pool and slots < num_leaves),
        "leaf_hist_mb_unpooled": round(
            num_leaves * features * bins * 3 * 4 / 2**20, 1),
        "leaf_hist_mb_pooled": round(
            slots * features * bins * 3 * 4 / 2**20, 1),
        "hlo_cost": _hlo_cost_block(bst),
        "health": _health_block(bst, iters),
        "telemetry": _telemetry_block(),
        "memory": _memory_block(bst),
    }


def run_goss_rung(rows, iters, platform, jax, features=None,
                  num_leaves=None):
    """GOSS rung at the Higgs shape (``data_sample_strategy=goss``): the
    device-resident sampler (ISSUE-5, ``tpu_device_goss`` auto) derives
    the top-set + amplified rest-sample mask IN-TRACE from the fused
    iteration's own gradients, so a GOSS boosting round stays ONE compiled
    dispatch — ``dispatches_per_iter`` in the blob is measured the census
    way (tools/profile_iter.py) on top of the timed window."""
    features = features or FEATURES
    num_leaves = num_leaves or NUM_LEAVES
    X, y = make_higgs_like(rows, features)
    params = {"objective": "binary", "num_leaves": num_leaves,
              "learning_rate": 0.1, "max_bin": 255, "min_data_in_leaf": 0,
              "min_sum_hessian_in_leaf": 100.0, "metric": "none",
              "verbosity": -1, "tpu_leaf_batch": LEAF_BATCH,
              "data_sample_strategy": "goss"}
    bst, elapsed = _rung_train(params, dict(X=X, label=y), iters, jax)
    blob = {
        "rows": rows, "features": features, "iters": iters,
        "num_leaves": num_leaves, "platform": platform,
        "device": device_block(jax),
        "data_sample_strategy": "goss",
        "top_rate": bst._gbdt.cfg.top_rate,
        "other_rate": bst._gbdt.cfg.other_rate,
        "used_fused": bool(bst._gbdt.fused_path_active),
        "train_time_s": round(elapsed, 3),
        "row_iters_per_sec": round(rows * iters / elapsed, 1),
    }
    try:
        from tools.profile_iter import _count_dispatches_and_syncs
        d, s = _count_dispatches_and_syncs(bst, 2)
        blob["dispatches_per_iter"] = round(d / 2, 2)
        blob["host_syncs_per_iter"] = round(s / 2, 2)
    except Exception as e:  # noqa: BLE001 — census is garnish on the rate
        blob["dispatches_per_iter"] = f"failed: {e!r}"[:120]
    blob["hlo_cost"] = _hlo_cost_block(bst)
    blob["health"] = _health_block(bst, iters)
    blob["telemetry"] = _telemetry_block()
    blob["memory"] = _memory_block(bst)
    return blob


def run_fused_rung(rows, iters, platform, jax, features=None,
                   num_leaves=None):
    """Quantized-fused rung (ISSUE-7): Higgs shape trained with
    ``tpu_wave_kernel=fused`` on the int8 quantized wire — ONE pallas
    dispatch per wave builds the smaller-sibling histograms, derives the
    larger siblings by parent subtraction and runs the split scan without
    the (W, G, B, 3) tensors leaving VMEM.  On non-TPU platforms the
    kernel runs in interpret mode (correctness vehicle, not a speed
    number — ``interpret_mode`` in the blob says so); the blob's
    ``hlo_cost`` is the compile-time number that travels across rounds."""
    features = features or FEATURES
    cpu = platform == "cpu"
    num_leaves = num_leaves or (63 if cpu else NUM_LEAVES)
    X, y = make_higgs_like(rows, features)
    params = {"objective": "binary", "num_leaves": num_leaves,
              "learning_rate": 0.1, "max_bin": 255, "min_data_in_leaf": 0,
              "min_sum_hessian_in_leaf": 100.0, "metric": "none",
              "verbosity": -1, "tpu_leaf_batch": min(LEAF_BATCH, 8),
              "use_quantized_grad": True, "tpu_wave_kernel": "fused"}
    bst, elapsed = _rung_train(params, dict(X=X, label=y), iters, jax)
    g = bst._gbdt
    return {
        "rows": rows, "features": features, "iters": iters,
        "num_leaves": num_leaves, "platform": platform,
        "device": device_block(jax),
        "quantized": True, "wave_kernel": "fused",
        "wave_fused_active": bool(g.wave_fused_active),
        "hist_dispatches_per_wave": (
            1 if g.wave_fused_active else int(g.grower_cfg.leaf_batch)),
        "interpret_mode": _interpret_mode(),
        "train_time_s": round(elapsed, 3),
        "row_iters_per_sec": round(rows * iters / elapsed, 1),
        "hlo_cost": _hlo_cost_block(bst),
        "health": _health_block(bst, iters),
        "telemetry": _telemetry_block(),
        "memory": _memory_block(bst),
    }


def run_stream_rung(rows, iters, platform, jax, features=None,
                    num_leaves=None, budget_mb=None):
    """Out-of-core streaming rung (ISSUE-13): the Higgs shape sharded to a
    disk store and trained through the budget-bounded residency pipeline
    (``lightgbm_tpu/stream/``, docs/STREAMING.md).  The blob WITNESSES the
    budget: peak streaming-buffer bytes (residency accounting, the same
    buffers the live-buffer census sees) must sit under
    ``tpu_stream_budget_mb`` or the rung refuses to publish.  On CPU the
    rung also asserts the streamed trees bitwise-equal the in-core run's
    (on TPU the fp32 guarantee needs rows_block-aligned chunks, so there
    it reports the flag without asserting); ``s_per_iter`` lands beside
    the in-core number so the streaming tax is a tracked trajectory
    metric (tools/bench_compare.py)."""
    import shutil
    import tempfile

    import lightgbm_tpu as lgb
    from lightgbm_tpu.stream import dataset_to_shards, train_streamed

    features = features or FEATURES
    num_leaves = num_leaves or STREAM_LEAVES
    budget_mb = budget_mb or STREAM_BUDGET_MB
    X, y = make_higgs_like(rows, features)
    params = {"objective": "binary", "num_leaves": num_leaves,
              "learning_rate": 0.1, "max_bin": 255, "min_data_in_leaf": 0,
              "min_sum_hessian_in_leaf": 100.0, "metric": "none",
              "verbosity": -1}
    tmp = tempfile.mkdtemp(prefix="lgbm_stream_bench_")
    try:
        rows_per_shard = max(min(rows // 8, 262144), 4096)
        ds = lgb.Dataset(X, label=y, params=params, free_raw_data=True)
        t0 = time.time()
        store = dataset_to_shards(ds, os.path.join(tmp, "store"),
                                  rows_per_shard, params=params)
        build_s = time.time() - t0
        sp = dict(params, tpu_stream_budget_mb=budget_mb)
        t0 = time.time()
        bst = train_streamed(sp, store, num_boost_round=iters)
        stream_s = time.time() - t0
        stats = dict(bst._stream_stats)
        budget_bytes = int(budget_mb * (1 << 20))
        peak = max(stats["peak_bytes"], stats["goss_resident_bytes"])
        # the witness: a blob that violated its own budget would be worse
        # than no blob
        assert peak <= budget_bytes, (
            f"stream residency exceeded its budget: {peak} > "
            f"{budget_bytes} bytes ({stats})")
        bst2, incore_s = _rung_train(params, dict(X=X, label=y), iters, jax)
        # _rung_train warms up with ONE extra round before the timed
        # window — compare the first `iters` trees of both models
        identical = (
            bst.model_to_string(num_iteration=iters)
            .split("\nfeature_importances")[0]
            == bst2.model_to_string(num_iteration=iters)
            .split("\nfeature_importances")[0])
        if platform == "cpu":
            assert identical, \
                "streamed trees diverged from in-core on the CPU backend"
        full_bins_bytes = rows * ((features + 1) // 2
                                  if stats.get("packed4") else features)
        return {
            "rows": rows, "features": features, "iters": iters,
            "num_leaves": num_leaves, "platform": platform,
            "device": device_block(jax),
            "budget_mb": budget_mb, "rows_per_shard": rows_per_shard,
            "shards": store.num_shards,
            "shard_build_s": round(build_s, 3),
            "residency": stats["residency"],
            "chunks": stats["chunks"],
            "chunk_bytes": stats["chunk_bytes"],
            "peak_stream_bytes": int(peak),
            "budget_bytes": budget_bytes,
            "budget_ok": True,
            "full_bins_bytes": int(full_bins_bytes),
            "prefetch_hits": stats["prefetch_hits"],
            "prefetch_stalls": stats["prefetch_stalls"],
            "stall_s": stats["stall_s"],
            "upload_bytes": stats["upload_bytes"],
            "train_time_s": round(stream_s, 3),
            "s_per_iter": round(stream_s / iters, 4),
            "incore_s_per_iter": round(incore_s / iters, 4),
            "stream_slowdown": round(stream_s / max(incore_s, 1e-9), 2),
            "row_iters_per_sec": round(rows * iters / stream_s, 1),
            "bitwise_identical": bool(identical),
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def run_serve_fused_rung(rows, iters, platform, jax, features=None,
                         num_leaves=31, calls=None, max_batch=1024):
    """Quantized-traversal serving rung (ISSUE-12): trains a small model,
    serves it through the int8 quantized pack with the fused Pallas
    traversal (interpret mode off-TPU — correctness vehicle, the blob
    says so), and reports warm QPS / p99 / pack shrink / fp32 parity /
    the zero-cold-start restart compile count.  The fused-vs-unfused
    integer identity is asserted IN the rung — a blob that publishes a
    QPS from a kernel that diverged would be worse than no blob."""
    import tempfile

    import numpy as np

    import lightgbm_tpu as lgb
    from lightgbm_tpu import serve
    from tools.serve_bench import restart_sim, run_request_stream

    features = features or FEATURES
    calls = calls or SERVE_FUSED_CALLS
    X, y = make_higgs_like(rows, features)
    bst = lgb.train({"objective": "binary", "num_leaves": num_leaves,
                     "learning_rate": 0.1, "max_bin": 255,
                     "metric": "none", "verbosity": -1},
                    lgb.Dataset(X, label=y), iters)
    pred_fp = serve.Predictor(bst, raw_score=True, quantize="off")
    pred_q = serve.Predictor(bst, raw_score=True, quantize="int8",
                             traverse="fused")
    sample = X[:min(rows, 4096)]
    ref = pred_fp.predict(sample)
    got = pred_q.predict(sample)
    unfused = serve.Predictor(bst, raw_score=True, quantize="int8",
                              traverse="unfused").predict(sample)
    if not np.array_equal(got, unfused):
        raise RuntimeError("fused traversal diverged from unfused "
                           "(integer identity broken)")
    bound = pred_q.plan.quantize_error_bound()
    parity_err = float(np.abs(got - ref).max())
    pred_q.warmup(max_batch)
    elapsed, served, per_call = run_request_stream(pred_q, X, calls,
                                                   max_batch)
    cache_dir = tempfile.mkdtemp(prefix="lgbm_bench_serve_aot_")
    try:
        restart = restart_sim(bst, serve, cache_dir, max_batch, "int8")
    except Exception as e:  # noqa: BLE001 — restart sim is garnish
        restart = {"error": f"{e!r}"[:200]}
    finally:
        import shutil
        shutil.rmtree(cache_dir, ignore_errors=True)
    snap = pred_q.metrics_snapshot()
    fp_plan_bytes = int(pred_fp.plan.plan_bytes)
    fp_pack_bytes = int(pred_fp.plan.pack_bytes)
    q_pack_bytes = int(pred_q.plan.pack_bytes)
    # The rung's plans (device-resident packs) must not stay live past
    # it: later rungs/tests census the process-wide buffer set.  A
    # PredictPlan is a reference CYCLE (its jitted closures capture the
    # plan), so clearing the cache alone leaves the packs to linger as
    # uncollected garbage until a gen-2 GC — collect deterministically.
    import gc
    pred_fp = pred_q = unfused = None
    serve.clear_plan_cache()
    gc.collect()
    return {
        "rows": rows, "features": features, "iters": iters,
        "num_leaves": num_leaves, "platform": platform,
        "device": device_block(jax),
        "quantize": snap["quantize"], "traverse": snap["traverse"],
        "interpret_mode": _interpret_mode(),
        "warm_qps": round(calls / elapsed, 2),
        "warm_rows_per_sec": round(served / elapsed, 1),
        # full per-call array percentiles (not the metrics reservoir)
        "p50_ms": round(float(np.percentile(per_call, 50) * 1e3), 4),
        "p99_ms": round(float(np.percentile(per_call, 99) * 1e3), 4),
        "compiles": snap["compiles"],
        "plan_bytes": snap["plan_bytes"],
        "plan_bytes_fp32": fp_plan_bytes,
        "plan_shrink": round(fp_plan_bytes
                             / max(snap["plan_bytes"], 1), 3),
        "pack_shrink": round(fp_pack_bytes / max(q_pack_bytes, 1), 3),
        "fused_bitwise_unfused": True,
        "parity_err": parity_err,
        "parity_bound": bound,
        "parity_ok": parity_err <= bound + 1e-12,
        "restart": restart,
    }


def device_block(jax):
    """The device identity every blob carries, as jax reports it."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _timed_train(bst, iters, pack, jax):
    """Warmup-compile one step, then time ``iters`` boosting rounds —
    packed (Booster.update_pack) when the booster's own plan allows, else
    per-round.  Returns ``(elapsed_s, dispatches, pack_eff)`` so callers
    report the pack size that actually ran, never the one requested."""
    if pack > 1 and not bst._gbdt.iter_pack_plan(pack)[1]:
        pack = 1   # config cannot pack — report per-round honestly
    # Warmup: compile the training step (excluded from timing, like the
    # reference excludes data loading).  The pack warmup compiles the SAME
    # scan length the timed window uses, so timing never pays a compile.
    if pack > 1:
        bst.update_pack(pack)
    else:
        bst.update()
    # Fence: dispatch is asynchronous, so the clock starts and stops on a
    # host readback of a score slice (it cannot return before the work).
    np.array(jax.device_get(bst._gbdt.scores[:8]))
    dispatches = 0
    t0 = time.time()
    if pack > 1:
        for _ in range(iters // pack):
            bst.update_pack(pack)
            dispatches += 1
    else:
        for _ in range(iters):
            bst.update()
            dispatches += 1
    np.array(jax.device_get(bst._gbdt.scores[:8]))
    return time.time() - t0, dispatches, pack


def _bench_predict(bst, X):
    """Warm serving stats from the compiled predict plan: warm QPS, p50
    latency and the compile count over a mixed-size request stream (the
    serve subsystem's whole point is that this stays O(log n) compiles
    and re-stacks nothing)."""
    from lightgbm_tpu import serve
    from tools.serve_bench import run_request_stream

    pred = serve.Predictor(bst, raw_score=True)
    t0 = time.time()
    warmed = pred.warmup(PREDICT_MAX_BATCH)
    warm_s = time.time() - t0
    elapsed, served, per_call = run_request_stream(pred, X, PREDICT_CALLS,
                                                   PREDICT_MAX_BATCH)
    snap = pred.metrics_snapshot()
    return {
        "warm_qps": round(PREDICT_CALLS / elapsed, 2),
        "warm_rows_per_sec": round(served / elapsed, 1),
        # full per-call array (not the metrics reservoir window)
        "p50_ms": round(float(np.percentile(per_call, 50) * 1e3), 4),
        "compiles": snap["compiles"],
        "warmed_rungs": warmed,
        "warmup_s": round(warm_s, 3),
        "plan_cache_hits": snap["plan_cache"]["hits"],
    }


def run_bench(rows, iters, rehearsal=False, cache_dir=None):
    """The child: measure on the device jax resolves, or refuse.  Returns
    the process exit code (0 only when every rung ran)."""
    import jax

    t_init = time.time()
    device = device_block(jax)
    init_s = time.time() - t_init
    platform = device["platform"]
    if platform != "tpu" and not (rehearsal and platform == "cpu"):
        print(f"bench: jax resolved {device} — the benchmark's numbers are "
              "device numbers and are only taken on a tpu (a CPU rehearsal "
              "of the plumbing: JAX_PLATFORMS=cpu python bench.py --dry-run)",
              file=sys.stderr)
        return 2
    rehearsal = platform != "tpu"     # on a tpu the flag changes nothing

    import lightgbm_tpu as lgb
    from lightgbm_tpu.ops.histogram import resolve_impl as _resolve_impl

    X, y = make_higgs_like(rows, FEATURES)
    params = bench_params()
    t_bin0 = time.time()
    ds = lgb.Dataset(X, label=y)
    ds.construct(params)
    bin_time = time.time() - t_bin0

    bst = lgb.Booster(params=params, train_set=ds)
    elapsed, dispatches, pack = _timed_train(
        bst, iters, _pack_eff(iters, ITER_PACK), jax)

    iters_per_sec = iters / elapsed
    row_iters_per_sec = rows * iters_per_sec

    from lightgbm_tpu.metrics import _auc
    sample = np.random.RandomState(1).choice(
        rows, size=min(rows, 200_000), replace=False)
    auc = _auc(y[sample], bst.predict(X[sample], raw_score=True), None, None)

    # Per-rung HLO cost (ISSUE-7): the primary config's compile-time
    # FLOPs / bytes-accessed ride every emitted line.
    hlo_cost = _hlo_cost_block(bst)
    # Post-hoc sentinel audit (ISSUE-8): the rate above is only publishable
    # when the final gradients/scores are finite — detail.health says so.
    health_block = _health_block(bst, iters)

    # rung name -> blob, or {"error": ...} when the rung raised
    rungs = {"quantized_row_iters_per_sec": None, "predict": None,
             "lambdarank": None, "wide": None, "goss": None,
             "fused_wave": None, "serve_fused": None, "stream": None}
    failed = []

    def emit():
        print(json.dumps({
            "metric": "binary_255leaves_row_iters_per_sec",
            # a CPU rehearsal exercises the plumbing; its rate is not a
            # device number and is never written under the metric's name
            "value": None if rehearsal else round(row_iters_per_sec, 1),
            "unit": "rows*iters/s",
            "vs_baseline": None if rehearsal else round(
                row_iters_per_sec / REFERENCE_ROW_ITERS_PER_SEC, 4),
            "detail": {
                "rows": rows, "features": FEATURES, "iters": iters,
                "num_leaves": NUM_LEAVES, "leaf_batch": LEAF_BATCH,
                "quantized": QUANTIZED,
                # what ran, not what was asked
                "histogram_impl": _resolve_impl(
                    bst._gbdt.grower_cfg.histogram_impl, platform),
                "wave_fused_active": bool(bst._gbdt.wave_fused_active),
                "device": device, "platform": platform,
                "backend_init_s": round(init_s, 3),
                "rehearsal": rehearsal,
                "compile_cache_dir": cache_dir,
                "failed_rungs": list(failed),
                # XLA cost-model block for the compiled grower program
                # (tools/profile_iter.train_step_hlo_cost): flops /
                # bytes_accessed — per-rung deltas across BENCH rounds.
                "hlo_cost": hlo_cost,
                # Training-health audit (resilience/health.py): sentinel
                # verdict over the final gradients/scores, rounds checked,
                # rollbacks and int16-wire overflow escalations.
                "health": health_block,
                # Unified telemetry block (ISSUE-9, telemetry/): schema,
                # per-kind event counts, span totals at dispatch
                # boundaries, registry snapshot — rebuilt at every emit so
                # late rungs' spans ride the cumulative re-emits too.
                "telemetry": _telemetry_block(),
                # Memory block (ISSUE-10, telemetry/memory.py): peak HBM,
                # live-buffer census at this emit, compile count/seconds,
                # host peak RSS, and the grower program's compiled memory
                # plan beside hlo_cost.
                "memory": _memory_block(bst),
                # Iteration packing: training dispatches per boosting round
                # (1.0 = per-round loop; 1/K with K-round packs — the
                # host-sync elimination the pack path is for).
                "iter_pack": pack,
                "dispatches_per_iter": round(dispatches / iters, 4),
                "train_time_s": round(elapsed, 3),
                "iters_per_sec": round(iters_per_sec, 3),
                "bin_time_s": round(bin_time, 3),
                "train_auc_sample": round(auc, 6),
                **rungs,
                "reference": "LightGBM CPU 16t Higgs 10.5Mx28 500it in "
                             "130.094s (docs/Experiments.rst:113)",
            },
        }))
        sys.stdout.flush()

    def rung(name, enabled, fn):
        """Run one side rung and re-emit cumulatively, so a child killed
        at the parent's timeout has still printed every finished rung.  A
        raising rung lands its error in its slot AND fails the run."""
        if not enabled:
            return
        try:
            rungs[name] = fn()
        except Exception as e:  # noqa: BLE001 — recorded, and exit != 0
            rungs[name] = {"error": f"{e!r}"[:400]}
            failed.append(name)
        emit()

    # Primary result FIRST, then the side rungs.  Row/iter budgets derive
    # from the primary budget (sizes per rung are the next PR's cells).
    emit()
    rung("predict", PREDICT_CHECK, lambda: _bench_predict(bst, X))
    rung("lambdarank", LTR_CHECK, lambda: run_ltr_rung(
        max(min(LTR_ROWS, rows // 4), 4096),
        max(min(LTR_ITERS, iters), 2), platform, jax))
    rung("wide", WIDE_CHECK, lambda: run_wide_rung(
        max(min(WIDE_ROWS, rows // 8), 4096),
        max(min(WIDE_ITERS, iters // 2), 2), platform, jax))
    rung("goss", GOSS_CHECK, lambda: run_goss_rung(
        max(rows // 4, 4096), max(min(GOSS_ITERS, iters), 2), platform, jax))
    rung("fused_wave", FUSED_CHECK, lambda: run_fused_rung(
        max(min(rows // 16, 65536), 4096),
        max(min(FUSED_ITERS, iters // 2), 2), platform, jax))
    rung("serve_fused", SERVE_FUSED_CHECK, lambda: run_serve_fused_rung(
        max(min(rows // 16, 65536), 4096),
        max(min(SERVE_FUSED_ITERS, iters), 2), platform, jax))
    # per-split full-matrix sweeps make streaming O(num_leaves) passes per
    # tree — the rung stays small
    rung("stream", STREAM_CHECK, lambda: run_stream_rung(
        max(min(rows // 16, 131072), 8192),
        max(min(STREAM_ITERS, iters), 2), platform, jax))

    def quantized_rate():
        qbst = lgb.Booster(params=dict(params, use_quantized_grad=True),
                           train_set=ds)
        q_elapsed, _qd, _qp = _timed_train(
            qbst, QUANT_ITERS, _pack_eff(QUANT_ITERS, ITER_PACK), jax)
        return round(rows * QUANT_ITERS / q_elapsed, 1)

    rung("quantized_row_iters_per_sec", QUANT_CHECK and not QUANTIZED,
         quantized_rate)
    if failed:
        print(f"bench: rung(s) raised: {failed}", file=sys.stderr)
    return 1 if failed else 0


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    rehearsal = "--dry-run" in argv
    if os.environ.get("_BENCH_INNER") == "1":
        from lightgbm_tpu.utils.jax_cache import enable_compile_cache
        return run_bench(ROWS, ITERS, rehearsal=rehearsal,
                         cache_dir=enable_compile_cache())
    # The parent: never imports jax, runs ONE child under a hard timeout
    # (a hung device call cannot be interrupted from inside its process)
    # and passes its output and exit code through.
    try:
        return subprocess.run(
            [sys.executable, os.path.abspath(__file__)] + list(argv),
            env=dict(os.environ, _BENCH_INNER="1"),
            timeout=ATTEMPT_TIMEOUT).returncode
    except subprocess.TimeoutExpired:
        print(f"bench: child exceeded its {ATTEMPT_TIMEOUT}s limit and was "
              "killed; the last JSON line above is what it finished",
              file=sys.stderr)
        return 124


if __name__ == "__main__":
    sys.exit(main())
