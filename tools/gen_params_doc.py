"""Generate docs/PARAMETERS.md from the config spec table (the reference
generates docs/Parameters.rst from config.h the same way,
.ci/parameter-generator.py)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lightgbm_tpu.config import _PARAMS  # noqa: E402

# Descriptions for parameters whose behavior is TPU-build-specific or
# otherwise non-obvious from the name; everything else inherits the
# reference's semantics (docs/Parameters.rst).
_DESCRIPTIONS = {
    "histogram_pool_size": (
        "max MB of device memory for the per-tree leaf-histogram pool "
        "(reference HistogramPool semantics): the growth loop carries only "
        "`floor(MB / slot_bytes)` histograms (LRU slots + "
        "recompute-on-miss) instead of one per leaf — the knob that makes "
        "wide-feature shapes (F=700/F=2000) fit HBM; -1 = unbounded (full "
        "residency); auto-clamped so one growth wave always fits; under "
        "`tpu_hist_comm=reduce_scatter` a slot holds only the shard's "
        "owned feature slice, so the savings multiply; voting, "
        "intermediate/advanced monotone and the GSPMD mask layout keep "
        "full residency (a warning names the fallback)"),
    "tree_learner": (
        "serial, or data/feature/voting — which device-mesh sharding the "
        "tree learner uses (parallel/mesh.py)"),
    "device_type": (
        "tpu (any jax backend; cpu runs the identical programs) — but an "
        "EXPLICIT tpu|gpu|cuda that resolves to the cpu backend is an "
        "error"),
    "tpu_histogram_impl": (
        "histogram kernel: auto|pallas|onehot|segment (auto = "
        "pallas on TPU, segment elsewhere; a kernel that fails to compile "
        "raises — onehot is the explicit XLA opt-out, never a fallback)"),
    "tpu_4bit_bins": (
        "auto 4-bit bin packing when every feature fits 16 bins "
        "(reference DenseBin IS_4BIT): resident bin matrix and per-leaf "
        "gathers halve"),
    "tpu_leaf_batch": (
        "leaves split per growth step (wave growth); 1 = strict "
        "best-first, >1 divides sequential steps per tree"),
    "tpu_wave_kernel": (
        "fused wave kernel (ops/pallas_wave.py): auto|fused|unfused — one "
        "pallas dispatch per leaf-batch wave runs histogram build -> "
        "sibling subtraction -> split scan while the accumulators stay "
        "VMEM-resident (vs one histogram dispatch per leaf plus two more "
        "HBM passes unfused); quantized trees are bitwise-identical "
        "either way, fp32 trees are identical whenever histogram sums "
        "are exactly representable (otherwise ULP-level — the per-leaf "
        "path may pick another row block at a small bucket and regroup "
        "f32 partial sums, the histogram "
        "pool's recompute caveat; tests/test_wave_fused.py, "
        "docs/PERF.md round 9).  auto = fused only where the "
        "capability checks pass (no mesh/voting/EFB/monotone/"
        "sorted-categorical/CEGB/per-node randomness, feature space fits "
        "one VMEM block) AND the pallas histogram is the live impl "
        "(TPU); fused = force the kernel (interpret mode on CPU — the "
        "tier-1 coverage vehicle, slow); unfused = always the per-leaf "
        "path"),
    "tpu_hist_comm": (
        "cross-shard histogram reduction on data meshes: auto|allreduce|"
        "reduce_scatter (auto = feature-sliced psum_scatter + slice-local "
        "scan + SplitInfo payload broadcast, ~2x less comm per wave)"),
    "tpu_split_tile": (
        "feature-block width for the split scan's (F, B) cumsum/gain "
        "buffers: 0 = auto (128-wide blocks once the scan width exceeds "
        "256 columns), 1 = untiled, >= 2 explicit; winner selection "
        "replays the untiled tie-break order exactly, so tiling never "
        "changes the chosen split"),
    "tpu_iter_pack": (
        "boosting rounds fused into one scanned XLA dispatch "
        "(docs/ITER_PACK.md); 0 = auto-pack when results cannot change"),
    "tpu_device_goss": (
        "GOSS sampling residency: auto|on|off — auto/on select the sample "
        "in-trace from the device gradients (exact lax.top_k top set with "
        "the host sampler's tie-break, key-folded rest-sample with "
        "LightGBM's (N - top_k) / other_k amplification), keeping a GOSS "
        "round ONE compiled dispatch and pack-capable; on the "
        "single-device wave body the selection hands the grower the "
        "in-bag row ids and the tree is grown over those rows alone "
        "(plan.sampling = subset), elsewhere over a mask of all rows; the "
        "rest-sample RNG stream differs from the host np.random one "
        "(statistically equivalent, AUC-parity pinned); off = reference "
        "host sampler (np argsort + np.random), pulling gradients each "
        "round, always a mask. Either way the first int(1 / "
        "learning_rate) iterations take every row (goss.hpp)"),
    "tpu_native_predict_max_rows": (
        "predict batches up to this many rows take the native C++ host "
        "traversal; larger batches go through the compiled serve plan "
        "(docs/SERVING.md); 0 routes everything to the device"),
    "tpu_serve_quantize": (
        "quantized serving packs (serve/plan.py + models/tree.py, "
        "docs/SERVING.md): off|int16|int8 — int16/int8 leaf-value quanta "
        "+ i16 node arrays + bit-packed categorical masks, ~4x smaller "
        "resident tree packs (more tenants per chip; serve.plan_bytes "
        "shrinks accordingly).  Routing decisions stay EXACT (bins and "
        "thresholds remain integers through the bit-key transform); leaf "
        "values round within `num_trees * scale / 2` "
        "(PredictPlan.quantize_error_bound, parity pinned in "
        "tests/test_serve_quantize.py).  Governs serve.Predictor packs "
        "ONLY — Booster.predict's internal plan routing pins "
        "quantize=off, so the training-API predict stays exact fp32 "
        "regardless of this knob; shapes past the narrow encodings "
        "(num_leaves/bins/features > 32767) degrade to off with a "
        "warning"),
    "tpu_traverse_kernel": (
        "serving traversal kernel (ops/pallas_traverse.py): "
        "auto|fused|unfused — fused keeps the whole quantized tree pack "
        "VMEM-resident and pipelines row blocks through the pallas grid "
        "(one streamed pass over binned rows vs per-depth XLA gathers); "
        "int32 quanta accumulation makes fused bitwise-identical to "
        "unfused UNCONDITIONALLY.  auto = fused on TPU when a quantized "
        "pack is active and the VMEM fit gate "
        "(pallas_traverse.traverse_layout) passes; fused = force "
        "(interpret mode on CPU — tier-1 coverage vehicle, slow; needs "
        "tpu_serve_quantize != off or it degrades with a warning); "
        "unfused = always the XLA while-loop walk"),
    "tpu_serve_compile_cache": (
        "persistent AOT compile cache for serving programs "
        "(serve/compile_cache.py): a directory of serialized compiled "
        "executables in checksummed frames, keyed by plan identity + "
        "padded batch shape + jax/jaxlib version + backend, so a process "
        "restart or hot model swap pays ZERO predict compiles "
        "(BENCH_serve's restart_compiles); corrupt/version-stale entries "
        "are detected, warned about and rebuilt; '' disables; the "
        "LIGHTGBM_TPU_SERVE_CACHE_DIR env var overrides"),
    "tpu_serve_request_log": (
        "per-request serve tracing (ISSUE-14, docs/OBSERVABILITY.md): on "
        "= every Predictor.predict / MicroBatcher request gets a request "
        "id and a host-side phase breakdown (queue-wait / bin+assemble / "
        "device dispatch / post-process, marked at dispatch boundaries "
        "only), sampled serve.request JSONL events and a bounded top-K "
        "slow-request exemplar ring in ServeMetrics.snapshot(); off "
        "(default) is bitwise-inert — identical lowered predict HLO, "
        "and armed tracing still adds ZERO device dispatches (pinned in "
        "tests/test_serve_tracing.py)"),
    "tpu_serve_request_sample": (
        "fraction of traced requests emitting a serve.request event — "
        "DETERMINISTIC pacing over the request sequence (no RNG: a fixed "
        "stream samples the same set every run); requests past "
        "tpu_serve_slow_ms always sample regardless of the rate"),
    "tpu_serve_slow_ms": (
        "slow-request threshold (ms): traced requests at/above it bypass "
        "the sample rate and enter the top-K exemplar ring surfaced by "
        "ServeMetrics.snapshot()['slow_requests']; 0 disables the slow "
        "override"),
    "tpu_serve_slo_p99_ms": (
        "p99 latency SLO target (ms): arms rolling-window SLO-attainment "
        "and error-budget-burn gauges (serve.slo_attainment / "
        "serve.slo_budget_burn; burn = violation fraction over the 1% "
        "budget a p99 target grants) with per-cause violation "
        "attribution (latency/shed/deadline/fault); also the target "
        "tools/serve_load.py --saturate searches against; 0 disables"),
    "checkpoint_interval": (
        "atomic training snapshots (resilience/checkpoint.py, "
        "docs/ROBUSTNESS.md) every N committed boosting rounds, emitted at "
        "iter-pack commit boundaries (with packing the interval is a "
        "floor); resume via `engine.train(..., resume_from=)` is "
        "bitwise-identical to the uninterrupted run; 0 = disabled"),
    "checkpoint_dir": (
        "snapshot directory; '' derives `<output_model>.ckpt`"),
    "checkpoint_keep": (
        "snapshot generations retained — the older ones are the fallback "
        "chain when the newest fails its checksum (torn write/bitrot)"),
    "tpu_probe_timeout": (
        "hard wall-clock budget (seconds) for the backend watchdog's "
        "subprocess probe (resilience/watchdog.py, armed via "
        "LIGHTGBM_TPU_WATCHDOG=1): compile + tiny dispatch must answer "
        "within it or the backend is classified wedged and training "
        "refuses to start instead of hanging"),
    "serve_max_queue": (
        "serve admission control (serve/predictor.py MicroBatcher): "
        "requests queued past this many are shed with ServeOverloadError "
        "(counted in ServeMetrics.shed); 0 = unbounded"),
    "serve_deadline_ms": (
        "per-request serving deadline: requests still QUEUED past it are "
        "failed with ServeDeadlineError instead of dispatched late "
        "(counted in ServeMetrics.deadline_misses); an in-flight dispatch "
        "is never interrupted; 0 = none"),
    "tpu_health_policy": (
        "training-health sentinel (resilience/health.py, "
        "docs/ROBUSTNESS.md): off = no guards (training is "
        "bitwise-identical to a sentinel-less build), warn = fold "
        "isfinite/max-abs health reductions into the training dispatch, "
        "watch the per-round loss history and log trips, halt = raise "
        "HealthHaltError on a trip, rollback = restore the last good "
        "checkpoint in-process (needs checkpoint_interval > 0), back off "
        "the learning rate, re-fold the device sampling keys and resume "
        "— the recovered trees are bitwise-identical to a fresh run "
        "resumed from that checkpoint with the same "
        "tpu_health_recovery_salt"),
    "tpu_health_spike_factor": (
        "divergence detector: trip when a lower-is-better eval loss "
        "exceeds this factor times the best value in the trailing "
        "tpu_health_window rounds"),
    "tpu_health_window": (
        "trailing per-round loss window for the spike and "
        "bitwise-stagnation checks"),
    "tpu_health_score_limit": (
        "max-abs train score above which the sentinel trips "
        "score_overflow (pre-NaN saturation); 0 disables the magnitude "
        "check"),
    "tpu_health_max_rollbacks": (
        "in-process recovery attempts allowed under "
        "tpu_health_policy=rollback before escalating to HealthHaltError"),
    "tpu_health_lr_backoff": (
        "learning_rate multiplier applied per recovery generation: the "
        "Nth rollback resumes at snapshot_lr * backoff**N"),
    "tpu_health_recovery_salt": (
        "recovery generation for a MANUAL resume: > 0 applies the same "
        "lr backoff and device sampling-key re-fold the Nth in-process "
        "rollback applies, so train(resume_from=ckpt, "
        "tpu_health_recovery_salt=N) reproduces the recovered run's "
        "trees bitwise (docs/ROBUSTNESS.md)"),
    "tpu_telemetry": (
        "unified telemetry (telemetry/, docs/OBSERVABILITY.md): on = "
        "host-side spans at dispatch boundaries (jax.profiler."
        "TraceAnnotation + the lock-guarded hierarchical timer), the "
        "process-wide metrics registry and JSONL events; off is "
        "bitwise-inert — telemetry never enters a traced program, so the "
        "compiled training programs are identical and the dispatch "
        "census stays pinned either way (tests/test_telemetry.py)"),
    "tpu_telemetry_log": (
        "structured JSONL event log path (docs/OBSERVABILITY.md event "
        "taxonomy): schema-versioned, monotonic-clocked train.start/"
        "train.iter (dispatch-wait vs host-bookkeeping wall split, pack "
        "size, checkpoint write duration, health verdict)/train.end "
        "events plus health/checkpoint/watchdog incidents; replay with "
        "tools/telemetry_report.py — the same file feeds tools/"
        "health_report.py and tools/profile_iter.py --from-log; '' = no "
        "event file (registry counters and spans still aggregate)"),
    "tpu_profile_iters": (
        "capture a jax.profiler trace directory covering the FIRST N "
        "committed boosting rounds (Mosaic/XLA kernel timelines for "
        "tensorboard/xprof; ROADMAP 3's live-TPU rounds land with traces "
        "in hand); 0 = off"),
    "tpu_profile_dir": (
        "destination for the tpu_profile_iters trace; '' derives "
        "\"<tpu_telemetry_log>.trace\" when a telemetry log is set, else "
        "/tmp/lightgbm_tpu_profile"),
    "tpu_telemetry_memory": (
        "device-memory accounting (telemetry/memory.py, "
        "docs/OBSERVABILITY.md memory section): off (default) is "
        "bitwise-inert — accounting is host-side observation at span "
        "boundaries, never traced into a device program, and the "
        "lowered-HLO equality pin covers this knob "
        "(tests/test_memory_telemetry.py); watermark makes every "
        "tracked span (fused_iter / pack_dispatch / valid_scores / "
        "grower grow / dataset construct / checkpoint capture) snapshot "
        "device.memory_stats() — bytes_in_use / peak_bytes_in_use, "
        "gracefully null on CPU backends — emitting memory.watermark "
        "events and memory.* gauges; census additionally walks "
        "jax.live_arrays() grouped by shape/dtype with byte totals "
        "(O(live buffers) host work per tracked span — triage runs, "
        "not steady-state serving).  Replay with "
        "tools/telemetry_report.py --memory; every BENCH blob carries "
        "the detail.memory block tools/bench_compare.py gates on"),
    "tpu_stream_budget_mb": (
        "device-byte budget for the out-of-core streaming residency "
        "pipeline (lightgbm_tpu/stream/, docs/STREAMING.md): the "
        "host->device chunk double buffer (and the goss-residency "
        "compact slice) must fit inside it — dataset size becomes a "
        "disk/host problem instead of an HBM problem.  Per-row training "
        "state (scores/gradients/partition, O(N) bytes, ~F*itemsize "
        "smaller than the bins matrix) is deliberately outside the "
        "budget; the detail.stream bench rung witnesses live "
        "streaming-buffer bytes <= budget"),
    "tpu_stream_residency": (
        "streaming residency mode: chunks (default via auto) sweeps "
        "budget-bounded chunks through every bins pass — streamed trees "
        "are BITWISE-identical to in-core training (seeded chunk "
        "histogram accumulation replays the in-core add order; pinned "
        "in tests/test_stream.py); goss keeps only the device-GOSS "
        "sampled slice resident per iteration (compact gather + one "
        "routing sweep; needs data_sample_strategy=goss with device "
        "GOSS; stochastically-rounded quantized gradients degrade back "
        "to chunks with a warning)"),
    "tpu_stream_rows_per_shard": (
        "rows per shard file for Dataset.to_shards (stream/store.py): "
        "smaller shards give the residency pipeline finer chunking "
        "under tight budgets at the cost of more checksummed frames"),
    "tpu_stream_prefetch": (
        "double-buffered async prefetch: assemble + upload the next "
        "chunk while the current one's dispatches run (upload time "
        "hides behind compute; stream.prefetch_hits/stalls count the "
        "overlap).  Disable to debug — every chunk then uploads "
        "synchronously as a counted stall"),
}


def main():
    stale = set(_DESCRIPTIONS) - {name for name, *_ in _PARAMS}
    if stale:
        raise SystemExit(
            f"gen_params_doc: _DESCRIPTIONS keys not in config._PARAMS "
            f"(renamed or removed parameter?): {sorted(stale)}")
    out = ["# Parameters",
           "",
           "Generated from `lightgbm_tpu/config.py` by "
           "`tools/gen_params_doc.py` — the single source of truth for the "
           "parameter surface (reference: `docs/Parameters.rst` generated "
           "from `config.h`).  Parameters without a description follow the "
           "reference's semantics unchanged.",
           "",
           "| parameter | type | default | aliases | constraints |"
           " description |",
           "|---|---|---|---|---|---|"]
    for name, typ, default, aliases, bounds in _PARAMS:
        tname = typ if isinstance(typ, str) else typ.__name__
        alias_s = ", ".join(aliases) if aliases else ""
        if bounds is None:
            bound_s = ""
        else:
            lo, hi = bounds
            bound_s = f"{'' if lo is None else lo} .. {'' if hi is None else hi}"
        d = "" if default is None else repr(default)
        desc = _DESCRIPTIONS.get(name, "")
        out.append(f"| `{name}` | {tname} | {d} | {alias_s} | {bound_s} |"
                   f" {desc} |")
    out.append("")
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "PARAMETERS.md")
    with open(path, "w") as fh:
        fh.write("\n".join(out))
    print(f"wrote {path}: {len(_PARAMS)} parameters")


if __name__ == "__main__":
    main()
