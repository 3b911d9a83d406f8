"""Memory & compile observability invariants (ISSUE-10,
docs/OBSERVABILITY.md memory section):

- ``tpu_telemetry_memory=off`` is bitwise-inert — the lowered fused-
  iteration HLO is equal TEXT with accounting off vs census (the PR-9
  inertness pin extended to the new knob) and the fused dispatch census
  stays 1.0 dispatches/iter WITH memory tracking armed;
- live-buffer census math on a synthetic array set (grouping, byte
  totals, largest-first ordering);
- the CPU graceful-None path of ``device_memory_stats``;
- tracked spans: ``memory.watermark`` events with a positive live-buffer
  delta when a span allocates, silence when the mode is off;
- compile telemetry: a first-time jit launch bumps ``compile.count`` and
  emits ``compile.end``;
- the bench ``detail.memory`` block schema (the per-rung assertions live
  in tests/test_bench_rungs.py);
- serve plan-pack byte gauges (``plan_bytes``, plan-cache ``bytes``) and
  their Prometheus exposition.
"""

import json
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import telemetry
from lightgbm_tpu.telemetry import memory

pytestmark = pytest.mark.telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _data(n=800, f=5, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(np.float64)
    return X, y


@pytest.fixture(autouse=True)
def _rearm():
    """Every test starts armed with accounting OFF (the process default)
    and leaves no sink or armed mode behind."""
    telemetry.set_enabled(True)
    telemetry.set_memory_mode("off")
    yield
    telemetry.close_log()
    telemetry.set_enabled(True)
    telemetry.set_memory_mode("off")


# ----------------------------------------------------------------- knob
def test_memory_knob_validated():
    X, y = _data(300)
    with pytest.raises(ValueError, match="tpu_telemetry_memory"):
        lgb.Booster(params={"objective": "binary", "verbosity": -1,
                            "tpu_telemetry_memory": "sometimes"},
                    train_set=lgb.Dataset(X, label=y))
    with pytest.raises(ValueError, match="tpu_telemetry_memory"):
        memory.set_memory_mode("maybe")


def test_memory_mode_armed_only_when_explicit():
    """A default-params booster must not flip the mode under an armed
    session (the tpu_telemetry explicit-params rule, extended)."""
    X, y = _data(300)
    telemetry.set_memory_mode("census")
    lgb.Booster(params={"objective": "binary", "verbosity": -1,
                        "metric": "none"},
                train_set=lgb.Dataset(X, label=y))
    assert memory.memory_mode() == "census"
    lgb.Booster(params={"objective": "binary", "verbosity": -1,
                        "metric": "none", "tpu_telemetry_memory": "off"},
                train_set=lgb.Dataset(X, label=y))
    assert memory.memory_mode() == "off"


# ------------------------------------------------------ inertness contract
def _fused_lowered_text(memory_mode):
    X, y = _data(600)
    ds = lgb.Dataset(X, label=y)
    bst = lgb.Booster(params={"objective": "binary", "num_leaves": 7,
                              "verbosity": -1, "metric": "none",
                              "tpu_telemetry_memory": memory_mode},
                      train_set=ds)
    g = bst._gbdt
    assert g._fused_iter is not None
    lowered = g._fused_iter.lower(g.bins_dev, g.scores, g._full_mask,
                                  g._fmask_static, 0.1, None, None, None,
                                  None, None)
    return lowered.as_text()


def test_off_mode_bitwise_program_identity():
    """tpu_telemetry_memory=off vs census: equal lowered-HLO text — memory
    accounting is host-side observation at span boundaries, never part of
    a traced program (the PR-9 pin, extended to the new knob)."""
    off = _fused_lowered_text("off")
    census = _fused_lowered_text("census")
    assert off == census


def test_census_one_dispatch_with_memory_armed(tmp_path):
    """The fused census stays 1.0 dispatches/iter WITH memory tracking
    armed (census mode + a live JSONL sink): watermark reads are never
    dispatches (acceptance criterion)."""
    from tools.profile_iter import nonfused_dispatch_census
    telemetry.set_memory_mode("census")
    telemetry.configure_log(str(tmp_path / "census.jsonl"))
    try:
        blobs = nonfused_dispatch_census(rows=2048, iters=2, num_leaves=7,
                                         paths=("fused",))
    finally:
        telemetry.close_log()
    assert blobs[0]["used_fused"] is True
    assert blobs[0]["dispatches_per_iter"] == 1.0, blobs[0]


# ------------------------------------------------------------------ census
def test_census_math_on_synthetic_arrays():
    arrays = [jnp.zeros((4, 4), jnp.float32) for _ in range(3)]
    arrays.append(jnp.zeros((256,), jnp.int8))
    c = memory.live_buffer_census(arrays=arrays)
    assert c["total_arrays"] == 4
    assert c["total_bytes"] == 3 * 64 + 256
    assert c["distinct_shapes"] == 2
    g0, g1 = c["groups"]
    # largest group first
    assert g0 == {"shape": [256], "dtype": "int8", "count": 1,
                  "bytes": 256}
    assert g1 == {"shape": [4, 4], "dtype": "float32", "count": 3,
                  "bytes": 192}
    assert c["truncated"] == 0
    json.dumps(c)


def test_census_top_truncation():
    arrays = [jnp.zeros((i + 1,), jnp.float32) for i in range(6)]
    c = memory.live_buffer_census(arrays=arrays, top=2)
    assert len(c["groups"]) == 2 and c["truncated"] == 4
    assert c["distinct_shapes"] == 6
    # totals cover EVERYTHING, not just the kept groups
    assert c["total_bytes"] == 4 * sum(range(1, 7))


def test_process_census_sees_live_arrays():
    # collect first: cyclic garbage from earlier tests (e.g. serve plans,
    # whose jitted closures capture the plan) still shows in
    # jax.live_arrays() until a gen-2 GC and can crowd the truncated
    # top-groups list — the pin is about arrays actually HELD live.
    import gc
    gc.collect()
    keep = jnp.zeros((128, 128), jnp.float32)      # 64 KiB, held live
    c = memory.live_buffer_census()
    assert c["total_bytes"] >= keep.nbytes
    assert any(g["shape"] == [128, 128] and g["dtype"] == "float32"
               for g in c["groups"]), c["groups"][:4]


# ------------------------------------------------------- device stats path
def test_device_stats_graceful_none_on_cpu():
    """CPU jax reports no allocator stats — the snapshot must be None,
    never an exception (the graceful-None contract; on a real TPU the
    same call returns bytes_in_use/peak_bytes_in_use)."""
    stats = memory.device_memory_stats()
    if jax.default_backend() == "cpu":
        assert stats is None
    else:   # live accelerator: the dict contract
        assert stats is not None and stats["bytes_in_use"] >= 0


class _FakeDevice:
    """A device whose runtime reports what a v5e's does (PERF.md, Findings
    PR 24: most of an iteration's peak is the reserved arena)."""

    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


@pytest.mark.parametrize("stats, want", [
    ({"bytes_in_use": 100, "peak_bytes_in_use": 160,
      "peak_bytes_reserved": 6380, "bytes_limit": 16000},
     {"bytes_in_use": 100, "peak_bytes_in_use": 160,
      "peak_bytes_reserved": 6380, "peak_bytes_total": 6540,
      "bytes_limit": 16000}),
    # a runtime without the reserved arena: no sum is made up
    ({"bytes_in_use": 100, "peak_bytes_in_use": 160},
     {"bytes_in_use": 100, "peak_bytes_in_use": 160}),
    ({}, None),
])
def test_device_stats_report_reserved_peak(stats, want):
    assert memory.device_memory_stats(_FakeDevice(stats)) == want


def test_host_rss_watermark_positive_and_resettable():
    ok = memory.MemoryTracker.reset_host_peak()
    v = memory.MemoryTracker.host_peak_rss_mb(use_hwm=ok)
    assert v > 0
    # module-level helper publishes the gauge
    assert telemetry.host_peak_rss_mb() > 0
    assert telemetry.registry().gauge(
        "memory.host_peak_rss_mb").value > 0


# -------------------------------------------------------------- span hook
def test_tracked_span_emits_watermark_with_positive_delta(tmp_path):
    log = str(tmp_path / "mem.jsonl")
    telemetry.set_memory_mode("census")
    telemetry.configure_log(log)
    big = None
    try:
        with telemetry.span("memtest/alloc", track_memory=True):
            big = jnp.zeros((512, 512), jnp.float32)   # 1 MiB, kept live
            big.block_until_ready()
    finally:
        telemetry.close_log()
    events = [json.loads(line) for line in open(log)]
    wm = [e for e in events if e["kind"] == "memory.watermark"]
    assert len(wm) == 1
    e = wm[0]
    assert e["span"] == "memtest/alloc"
    # census mode: live-buffer accounting works even where device stats
    # are None (CPU) — the allocation's bytes must show in the delta
    assert e["live_delta_bytes"] >= big.nbytes
    assert e["live_bytes"] >= big.nbytes
    assert e["host_peak_rss_mb"] > 0
    assert isinstance(e["census"], list) and e["census"]
    if jax.default_backend() == "cpu":
        assert e["bytes_in_use"] is None and e["peak_bytes"] is None
    # gauges landed too
    assert telemetry.registry().gauge("memory.live_bytes").value \
        >= big.nbytes


def test_off_mode_tracked_span_emits_nothing(tmp_path):
    log = str(tmp_path / "off.jsonl")
    telemetry.configure_log(log)      # mode stays "off" (fixture default)
    try:
        with telemetry.span("memtest/off", track_memory=True):
            jnp.zeros((64,), jnp.float32).block_until_ready()
    finally:
        telemetry.close_log()
    kinds = [json.loads(line)["kind"] for line in open(log)]
    assert "memory.watermark" not in kinds


def test_train_sites_tracked_and_train_end_rss(tmp_path):
    """An armed training run brackets its span sites (pack dispatch /
    fused iter / checkpoint capture) with watermark events, dataset
    construction is tracked, and train.end carries host_peak_rss_mb."""
    log = str(tmp_path / "run.jsonl")
    X, y = _data(1200)
    lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1,
               "metric": "none", "tpu_telemetry_log": log,
               "tpu_telemetry_memory": "watermark",
               "checkpoint_interval": 2,
               "checkpoint_dir": str(tmp_path / "ckpt")},
              lgb.Dataset(X, label=y), 4)
    events = [json.loads(line) for line in open(log)]
    spans = {e["span"] for e in events if e["kind"] == "memory.watermark"}
    assert "checkpoint/capture" in spans, spans
    assert any(s.startswith("train/") for s in spans), spans
    end = [e for e in events if e["kind"] == "train.end"][-1]
    assert end["host_peak_rss_mb"] > 0


def test_construct_arms_from_its_own_params(tmp_path):
    """Dataset construction runs BEFORE the GBDT constructor or the
    engine session ever sees the config, so construct() arms the mode
    from its own merged params (explicit-params rule) — no caller-side
    set_memory_mode needed for the run's own training set to be
    tracked."""
    log = str(tmp_path / "construct.jsonl")
    X, y = _data(900)
    telemetry.configure_log(log)
    try:
        lgb.Dataset(X, label=y).construct(
            {"objective": "binary", "verbosity": -1,
             "tpu_telemetry_memory": "census"})
    finally:
        telemetry.close_log()
    assert memory.memory_mode() == "census"   # armed by construct itself
    events = [json.loads(line) for line in open(log)]
    spans = {e["span"] for e in events if e["kind"] == "memory.watermark"}
    assert "data/construct" in spans, spans


# ------------------------------------------------------- compile telemetry
def test_compile_emits_event_and_counters(tmp_path):
    log = str(tmp_path / "compile.jsonl")
    reg = telemetry.registry()
    before = reg.counter("compile.count").value
    telemetry.configure_log(log)
    try:
        fn = telemetry.watch_compiles(jax.jit(lambda a: a * 2 + 1),
                                      "test/prog")
        fn(jnp.ones((16,), jnp.float32))            # compiles
        fn(jnp.ones((16,), jnp.float32))            # cache hit
        fn(jnp.ones((32,), jnp.float32))            # new shape: compiles
    finally:
        telemetry.close_log()
    assert reg.counter("compile.count").value == before + 2
    events = [json.loads(line) for line in open(log)]
    ce = [e for e in events if e["kind"] == "compile.end"]
    assert len(ce) == 2
    assert all(e["label"] == "test/prog" and e["seconds"] > 0
               for e in ce)
    # the report tool aggregates them
    from tools.telemetry_report import compile_rows
    rows = compile_rows(events)
    assert rows and rows[0][0] == "test/prog" and rows[0][1] == 2


def test_memory_analysis_summary_from_compiled():
    compiled = jax.jit(lambda a: a @ a).lower(
        jnp.ones((8, 8), jnp.float32)).compile()
    summary = memory.memory_analysis_summary(compiled)
    assert summary is not None
    assert summary.get("argument_size_in_bytes", 0) > 0
    assert all(isinstance(v, int) for v in summary.values())


def test_aot_compile_event_carries_memory_analysis(tmp_path):
    """The profile/train_step AOT path holds the compiled object, so its
    compile.end event is the one that carries the memory_analysis byte
    summary the jit seam cannot produce."""
    from tools.profile_iter import train_step_memory_analysis
    log = str(tmp_path / "aot.jsonl")
    X, y = _data(600)
    bst = lgb.Booster(params={"objective": "binary", "num_leaves": 7,
                              "verbosity": -1, "metric": "none"},
                      train_set=lgb.Dataset(X, label=y))
    bst.update()
    telemetry.configure_log(log)
    try:
        ma = train_step_memory_analysis(bst)
    finally:
        telemetry.close_log()
    assert "error" not in ma and "unavailable" not in ma, ma
    events = [json.loads(line) for line in open(log)]
    ce = [e for e in events if e["kind"] == "compile.end"
          and e["label"] == "profile/train_step"]
    assert len(ce) == 1
    assert ce[0]["memory_analysis"] == ma


# ------------------------------------------------------------ bench block
def test_bench_memory_block_schema():
    import bench
    X, y = _data(600)
    bst = lgb.Booster(params={"objective": "binary", "num_leaves": 7,
                              "verbosity": -1, "metric": "none"},
                      train_set=lgb.Dataset(X, label=y))
    bst.update()
    blk = bench._memory_block(bst)
    assert "error" not in blk, blk
    assert set(blk) >= {"mode", "device", "live_buffers", "compile",
                        "host_peak_rss_mb", "memory_analysis"}
    if jax.default_backend() == "cpu":
        assert blk["device"] is None
    lb = blk["live_buffers"]
    assert lb["total_bytes"] > 0 and lb["groups"]
    assert blk["compile"]["count"] >= 0
    assert blk["compile"]["seconds"] >= 0.0
    assert blk["host_peak_rss_mb"] > 0
    ma = blk["memory_analysis"]
    assert "error" not in ma, ma
    json.dumps(blk)


def test_memory_report_tool_section(tmp_path):
    """CLI smoke: --memory renders the watermark and compile tables from
    a real training artifact (subprocess, like the other tools)."""
    import subprocess
    import sys
    log = str(tmp_path / "run.jsonl")
    X, y = _data(900)
    lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1,
               "metric": "none", "tpu_telemetry_log": log,
               "tpu_telemetry_memory": "census"},
              lgb.Dataset(X, label=y), 3)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "telemetry_report.py"),
         "--memory", log], capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert "memory watermarks" in proc.stdout
    assert "compiles" in proc.stdout
    assert "memory.watermark" in proc.stdout   # event counts table


# ------------------------------------------------------- serve plan bytes
def test_serve_plan_bytes_and_cache_byte_gauges():
    from lightgbm_tpu import serve
    from lightgbm_tpu.serve.plan import cache_stats, clear_plan_cache
    X, y = _data(600)
    bst = lgb.train({"objective": "binary", "num_leaves": 15,
                     "verbosity": -1, "metric": "none"},
                    lgb.Dataset(X, label=y), 3)
    clear_plan_cache()
    pred = serve.Predictor(bst, raw_score=True)
    out = pred.predict(X[:32])
    assert out.shape[0] == 32
    plan = pred.plan
    assert plan.plan_bytes > 0
    snap = pred.metrics_snapshot()
    assert snap["plan_bytes"] == plan.plan_bytes
    stats = cache_stats()
    assert stats["bytes"] >= plan.plan_bytes and stats["size"] >= 1
    assert snap["plan_cache"]["bytes"] == stats["bytes"]
    reg = telemetry.registry()
    assert reg.gauge("serve.plan_bytes").value == plan.plan_bytes
    assert reg.gauge("serve.plan_cache_bytes").value == stats["bytes"]
    text = pred.metrics.render_prometheus(plan=plan)
    assert "lgbm_tpu_serve_plan_bytes " in text
    assert "lgbm_tpu_serve_plan_cache_bytes " in text
    clear_plan_cache()
    assert reg.gauge("serve.plan_cache_bytes").value == 0
    # the per-plan gauge tracks the MRU cached plan — an evicted/cleared
    # pack's bytes never linger
    assert reg.gauge("serve.plan_bytes").value == 0


def test_serve_planless_snapshot_keeps_bytes_keys():
    from lightgbm_tpu.serve.metrics import ServeMetrics
    m = ServeMetrics()
    snap = m.snapshot()
    assert snap["plan_bytes"] is None
    text = m.render_prometheus()
    assert "lgbm_tpu_serve_plan_bytes NaN" in text
    assert "lgbm_tpu_serve_plan_cache_bytes NaN" in text
