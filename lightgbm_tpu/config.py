"""Typed training configuration with LightGBM-compatible parameter names and aliases.

The reference defines ~180 parameters as annotated comments in
``include/LightGBM/config.h:39-1322`` and generates the alias table / setters into
``src/io/config_auto.cpp``.  Here the single source of truth is the ``_PARAMS`` spec
table below; :class:`Config` is generated from it at import time.  Alias resolution
follows ``ParameterAlias::KeyAliasTransform`` semantics (first write wins, aliases
mapped onto the canonical name).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, List, Optional, Tuple

# (name, type, default, aliases, check)
#   type is one of: bool, int, float, str, "list_int", "list_float", "list_str"
#   check is an optional (lo, hi) inclusive bound for numeric params.
_PARAMS: List[Tuple[str, Any, Any, Tuple[str, ...], Optional[Tuple[Any, Any]]]] = [
    # ---- Core parameters (config.h "Core Parameters" block) ----
    ("objective", str, "regression",
     ("objective_type", "app", "application", "loss"), None),
    ("boosting", str, "gbdt", ("boosting_type", "boost"), None),
    ("data_sample_strategy", str, "bagging", (), None),
    ("num_iterations", int, 100,
     ("num_iteration", "n_iter", "num_tree", "num_trees", "num_round", "num_rounds",
      "nrounds", "num_boost_round", "n_estimators", "max_iter"), (0, None)),
    ("learning_rate", float, 0.1, ("shrinkage_rate", "eta"), (0.0, None)),
    ("num_leaves", int, 31, ("num_leaf", "max_leaves", "max_leaf", "max_leaf_nodes"), (2, 131072)),
    ("tree_learner", str, "serial",
     ("tree", "tree_type", "tree_learner_type"), None),
    ("num_threads", int, 0,
     ("num_thread", "nthread", "nthreads", "n_jobs"), None),
    ("device_type", str, "tpu", ("device",), None),
    ("seed", int, 0, ("random_seed", "random_state"), None),
    ("deterministic", bool, False, (), None),
    # ---- Learning control ----
    ("force_col_wise", bool, False, (), None),
    ("force_row_wise", bool, False, (), None),
    ("histogram_pool_size", float, -1.0, ("hist_pool_size",), None),
    ("max_depth", int, -1, (), None),
    ("min_data_in_leaf", int, 20,
     ("min_data_per_leaf", "min_data", "min_child_samples", "min_samples_leaf"), (0, None)),
    ("min_sum_hessian_in_leaf", float, 1e-3,
     ("min_sum_hessian_per_leaf", "min_sum_hessian", "min_hessian", "min_child_weight"),
     (0.0, None)),
    ("bagging_fraction", float, 1.0,
     ("sub_row", "subsample", "bagging"), (0.0, 1.0)),
    ("pos_bagging_fraction", float, 1.0,
     ("pos_sub_row", "pos_subsample", "pos_bagging"), (0.0, 1.0)),
    ("neg_bagging_fraction", float, 1.0,
     ("neg_sub_row", "neg_subsample", "neg_bagging"), (0.0, 1.0)),
    ("bagging_freq", int, 0, ("subsample_freq",), None),
    ("bagging_seed", int, 3, ("bagging_fraction_seed",), None),
    ("bagging_by_query", bool, False, (), None),
    ("feature_fraction", float, 1.0,
     ("sub_feature", "colsample_bytree"), (0.0, 1.0)),
    ("feature_fraction_bynode", float, 1.0,
     ("sub_feature_bynode", "colsample_bynode"), (0.0, 1.0)),
    ("feature_fraction_seed", int, 2, (), None),
    ("extra_trees", bool, False, ("extra_tree",), None),
    ("extra_seed", int, 6, (), None),
    ("early_stopping_round", int, 0,
     ("early_stopping_rounds", "early_stopping", "n_iter_no_change"), None),
    ("early_stopping_min_delta", float, 0.0, (), (0.0, None)),
    ("first_metric_only", bool, False, (), None),
    ("max_delta_step", float, 0.0, ("max_tree_output", "max_leaf_output"), None),
    ("lambda_l1", float, 0.0, ("reg_alpha", "l1_regularization"), (0.0, None)),
    ("lambda_l2", float, 0.0, ("reg_lambda", "lambda", "l2_regularization"), (0.0, None)),
    ("linear_lambda", float, 0.0, (), (0.0, None)),
    ("min_gain_to_split", float, 0.0, ("min_split_gain",), (0.0, None)),
    ("drop_rate", float, 0.1, ("rate_drop",), (0.0, 1.0)),
    ("max_drop", int, 50, (), None),
    ("skip_drop", float, 0.5, (), (0.0, 1.0)),
    ("xgboost_dart_mode", bool, False, (), None),
    ("uniform_drop", bool, False, (), None),
    ("drop_seed", int, 4, (), None),
    ("top_rate", float, 0.2, (), (0.0, 1.0)),
    ("other_rate", float, 0.1, (), (0.0, 1.0)),
    ("min_data_per_group", int, 100, (), (1, None)),
    ("max_cat_threshold", int, 32, (), (1, None)),
    ("cat_l2", float, 10.0, (), (0.0, None)),
    ("cat_smooth", float, 10.0, (), (0.0, None)),
    ("max_cat_to_onehot", int, 4, (), (1, None)),
    ("top_k", int, 20, ("topk",), (1, None)),
    ("monotone_constraints", "list_int", None, ("mc", "monotone_constraint", "monotonic_cst"), None),
    ("monotone_constraints_method", str, "basic", ("monotone_constraining_method", "mc_method"), None),
    ("monotone_penalty", float, 0.0, ("monotone_splits_penalty", "ms_penalty", "mc_penalty"), (0.0, None)),
    ("feature_contri", "list_float", None, ("feature_contrib", "fc", "fp", "feature_penalty"), None),
    ("forcedsplits_filename", str, "", ("fs", "forced_splits_filename", "forced_splits_file", "forced_splits"), None),
    ("refit_decay_rate", float, 0.9, (), (0.0, 1.0)),
    # IO / continuation (reference config.h "IO Parameters" block).
    ("input_model", str, "", ("model_input", "model_in"), None),
    ("output_model", str, "LightGBM_model.txt", ("model_output", "model_out"), None),
    ("snapshot_freq", int, -1, ("save_period",), None),
    ("cegb_tradeoff", float, 1.0, (), (0.0, None)),
    ("cegb_penalty_split", float, 0.0, (), (0.0, None)),
    ("cegb_penalty_feature_lazy", "list_float", None, (), None),
    ("cegb_penalty_feature_coupled", "list_float", None, (), None),
    ("path_smooth", float, 0.0, (), (0.0, None)),
    ("interaction_constraints", "list_str", None, (), None),
    ("verbosity", int, 1, ("verbose",), None),
    ("use_quantized_grad", bool, False, (), None),
    # Bounded so hessian levels (num_bins - 1) fit int8 (ops/quantize.py).
    ("num_grad_quant_bins", int, 4, (), (2, 128)),
    ("quant_train_renew_leaf", bool, False, (), None),
    ("stochastic_rounding", bool, True, (), None),
    # ---- Dataset parameters ----
    ("linear_tree", bool, False, ("linear_trees",), None),
    ("max_bin", int, 255, ("max_bins",), (2, None)),
    ("max_bin_by_feature", "list_int", None, (), None),
    ("min_data_in_bin", int, 3, (), (1, None)),
    ("bin_construct_sample_cnt", int, 200000, ("subsample_for_bin",), (1, None)),
    ("data_random_seed", int, 1, ("data_seed",), None),
    ("is_enable_sparse", bool, True, ("is_sparse", "enable_sparse", "sparse"), None),
    ("enable_bundle", bool, True, ("is_enable_bundle", "bundle"), None),
    # EFB conflict budget (the reference hard-codes 0 in FindGroups; the EFB
    # paper's gamma) — fraction of sampled rows where bundle members may
    # both be non-default.
    ("max_conflict_rate", float, 0.0, (), (0.0, 1.0)),
    ("use_missing", bool, True, (), None),
    ("zero_as_missing", bool, False, (), None),
    ("feature_pre_filter", bool, True, (), None),
    ("pre_partition", bool, False, ("is_pre_partition",), None),
    ("two_round", bool, False, ("two_round_loading", "use_two_round_loading"), None),
    ("header", bool, False, ("has_header",), None),
    ("label_column", str, "", ("label",), None),
    ("weight_column", str, "", ("weight",), None),
    ("group_column", str, "", ("group", "group_id", "query_column", "query", "query_id"), None),
    ("ignore_column", str, "", ("ignore_feature", "blacklist"), None),
    ("categorical_feature", str, "", ("cat_feature", "categorical_column", "cat_column", "categorical_features"), None),
    ("forcedbins_filename", str, "", (), None),
    ("save_binary", bool, False, ("is_save_binary", "is_save_binary_file"), None),
    ("saved_feature_importance_type", int, 0, (), (0, 1)),
    ("precise_float_parser", bool, False, (), None),
    ("parser_config_file", str, "", (), None),
    # ---- Predict parameters ----
    ("start_iteration_predict", int, 0, (), None),
    ("num_iteration_predict", int, -1, (), None),
    ("predict_raw_score", bool, False, ("is_predict_raw_score", "predict_rawscore", "raw_score"), None),
    ("predict_leaf_index", bool, False, ("is_predict_leaf_index", "leaf_index"), None),
    ("predict_contrib", bool, False, ("is_predict_contrib", "contrib"), None),
    ("predict_disable_shape_check", bool, False, (), None),
    ("pred_early_stop", bool, False, (), None),
    ("pred_early_stop_freq", int, 10, (), None),
    ("pred_early_stop_margin", float, 10.0, (), None),
    # ---- Objective parameters ----
    ("objective_seed", int, 5, (), None),
    ("num_class", int, 1, ("num_classes",), (1, None)),
    ("is_unbalance", bool, False, ("unbalance", "unbalanced_sets"), None),
    ("scale_pos_weight", float, 1.0, (), (0.0, None)),
    ("sigmoid", float, 1.0, (), (0.0, None)),
    ("boost_from_average", bool, True, (), None),
    ("reg_sqrt", bool, False, (), None),
    ("alpha", float, 0.9, (), (0.0, None)),
    ("fair_c", float, 1.0, (), (0.0, None)),
    ("poisson_max_delta_step", float, 0.7, (), (0.0, None)),
    ("tweedie_variance_power", float, 1.5, (), (1.0, 2.0)),
    ("lambdarank_truncation_level", int, 30, (), (1, None)),
    ("lambdarank_norm", bool, True, (), None),
    ("label_gain", "list_float", None, (), None),
    ("lambdarank_position_bias_regularization", float, 0.0, (), (0.0, None)),
    # ---- Metric parameters ----
    ("metric", "list_str", None, ("metrics", "metric_types"), None),
    ("metric_freq", int, 1, ("output_freq",), (1, None)),
    ("is_provide_training_metric", bool, False, ("training_metric", "is_training_metric", "train_metric"), None),
    ("eval_at", "list_int", None, ("ndcg_eval_at", "ndcg_at", "map_eval_at", "map_at"), None),
    ("multi_error_top_k", int, 1, (), (1, None)),
    ("auc_mu_weights", "list_float", None, (), None),
    # ---- Network parameters (mesh-level in the TPU build) ----
    ("num_machines", int, 1, ("num_machine",), (1, None)),
    ("local_listen_port", int, 12400, ("local_port", "port"), None),
    ("time_out", int, 120, (), (1, None)),
    ("machine_list_filename", str, "", ("machine_list_file", "machine_list", "mlist"), None),
    ("machines", str, "", ("workers", "nodes"), None),
    # ---- Device / TPU parameters ----
    ("gpu_platform_id", int, -1, (), None),
    ("gpu_device_id", int, -1, (), None),
    ("gpu_use_dp", bool, False, (), None),
    ("num_gpu", int, 1, (), (1, None)),
    # TPU-specific knobs (no reference analog).
    ("tpu_histogram_impl", str, "auto", (), None),  # auto|pallas|onehot|segment
    # auto 4-bit bin packing when all features fit 16 bins (reference
    # DenseBin IS_4BIT); set false to force byte-per-bin storage
    ("tpu_4bit_bins", bool, True, (), None),
    # Leaves split per growth step (wave growth); 1 = strict best-first.
    ("tpu_leaf_batch", int, 1, (), (1, 128)),
    # Fused wave kernel (ops/pallas_wave.py): one pallas_call per leaf-
    # batch wave runs histogram build -> sibling subtraction -> split scan
    # while the accumulators stay VMEM-resident, vs one histogram dispatch
    # per leaf plus two more HBM passes (subtract + scan) unfused.  auto =
    # fused only where the capability checks pass and the flat pallas
    # histogram is the live impl (TPU); fused = force (interpret-mode on
    # CPU — slow, test vehicle); unfused = always the per-leaf path.
    # Identity: quantized trees are bitwise-identical either way (integer
    # histograms); fp32 trees are identical whenever histogram sums are
    # exactly representable, ULP-level otherwise — the wave's shared row
    # bucket may regroup f32 partial sums vs the per-leaf buckets, the
    # same caveat as the histogram pool's recompute-on-miss
    # (tests/test_wave_fused.py, docs/PERF.md round 9).
    ("tpu_wave_kernel", str, "auto", (), None),  # auto|fused|unfused
    # Cross-shard histogram reduction on data-parallel meshes
    # (tree_learner=data): reduce_scatter = feature-sliced psum_scatter +
    # per-shard split scan + SplitInfo payload broadcast (~2x less comm
    # per wave than allreduce, the reference data_parallel_tree_learner's
    # ReduceScatter layout); allreduce = full-histogram psum + replicated
    # scan.  auto picks reduce_scatter whenever the composition allows
    # (voting, intermediate/advanced monotone and forced splits keep
    # allreduce; the mask layout keeps its own reductions).
    ("tpu_hist_comm", str, "auto", (), None),  # auto|allreduce|reduce_scatter
    # Feature-block width for the split scan's (F, B) cumsum/gain buffers:
    # wide feature spaces evaluate candidates per G-block through a
    # sequential map so peak scan scratch stops scaling with full F.
    # 0 = auto (128-wide blocks once the scan width exceeds 256 columns),
    # 1 = untiled, >= 2 = explicit block width.  The winner is selected
    # with the untiled argmax's exact tie-break order, so tiling never
    # changes the chosen split (ops/split.py best_split).
    ("tpu_split_tile", int, 0, (), (0, None)),
    # Boosting rounds fused into ONE scanned XLA dispatch (iteration
    # packing, docs/ITER_PACK.md).  0 = auto: pack whenever the config is
    # pack-capable with static row/feature masks; explicit K >= 1 forces
    # the pack path (bagging/feature-fraction masks move to key-folded
    # device sampling there).
    ("tpu_iter_pack", int, 0, (), (0, 4096)),
    # Device-resident GOSS (data_sample_strategy=goss): select the
    # sample in-trace from the just-computed device gradients —
    # exact lax.top_k top set (same stable descending tie-break as the
    # host argsort), key-folded jax.random rest-sample with LightGBM's
    # (N - top_k) / other_k amplification; on the single-device wave body
    # the tree is then grown over the in-bag row ids alone
    # (plan.sampling = subset; sampling.py).  The top set matches the
    # host sampler bit-for-bit under distinct scores; the random rest
    # sample is a DIFFERENT (seed-keyed device) stream than the host
    # np.random one — statistically equivalent, AUC-parity tested.
    # auto = in-trace when the fused one-dispatch iteration applies,
    # host sampler otherwise; on = device sampling even on non-fused
    # paths (standalone mask dispatch); off = always the host sampler.
    ("tpu_device_goss", str, "auto", (), None),  # auto|on|off
    # Predict batches up to this many rows take the native C++ host
    # traversal (no device round-trip); larger batches go through the
    # compiled serve plan (docs/SERVING.md).  0 routes everything to the
    # device.  The LIGHTGBM_TPU_NATIVE_PREDICT_MAX_ROWS env var, where
    # set, overrides this knob.
    ("tpu_native_predict_max_rows", int, 262144, (), (0, None)),
    # Quantized serving packs (serve/plan.py + models/tree.py, ISSUE-12):
    # int16/int8 leaf-value quanta + narrow node arrays + bit-packed
    # categorical masks — ~4x smaller device-resident packs (more tenants
    # per chip; serve.plan_bytes shrinks accordingly).  Traversal decisions
    # stay EXACT (bins and thresholds remain integers through the bit-key
    # transform); only the leaf values quantize, with per-class scale, so
    # raw scores differ from fp32 by at most num_trees * scale / 2
    # (PredictPlan.quantize_error_bound; parity pinned in
    # tests/test_serve_quantize.py).  off = fp32 packs (the bitwise-vs-
    # Booster.predict default); models whose shape exceeds the narrow
    # encodings (num_leaves/bins/features > 32767) degrade to off with a
    # warning.
    ("tpu_serve_quantize", str, "off", (), None),  # off|int16|int8
    # Serving traversal kernel (ops/pallas_traverse.py): fused keeps the
    # whole quantized tree pack VMEM-resident and pipelines row blocks
    # through the pallas grid — one streamed pass over binned rows instead
    # of per-depth XLA gathers.  Integer accumulation makes fused
    # bitwise-identical to unfused unconditionally (the quantized-pack
    # twin of tpu_wave_kernel's identity story).  auto = fused on TPU
    # when a quantized pack is active and the VMEM fit gate passes;
    # fused = force (interpret mode on CPU — the tier-1 coverage vehicle,
    # slow; requires tpu_serve_quantize != off, else degrades with a
    # warning); unfused = always the XLA while-loop walk.
    ("tpu_traverse_kernel", str, "auto", (), None),  # auto|fused|unfused
    # Persistent AOT compile cache for serving programs
    # (serve/compile_cache.py): directory holding serialized compiled
    # executables keyed by plan identity + padded batch shape + jax/jaxlib
    # version + backend, so a process restart or hot model swap never
    # re-pays the predict compiles (zero cold-start).  "" disables; the
    # LIGHTGBM_TPU_SERVE_CACHE_DIR env var, where set, overrides.
    # Corrupt or version-stale entries are detected (checksummed frames),
    # warned about and rebuilt.
    ("tpu_serve_compile_cache", str, "", ("serve_compile_cache",), None),
    # ---- Serve request-path observability (ISSUE-14,
    # docs/OBSERVABILITY.md serve section) ----
    # Per-request tracing: on = every Predictor.predict / MicroBatcher
    # request gets a host-side phase breakdown (queue-wait, bin/assemble,
    # device dispatch, post-process — recorded at dispatch boundaries
    # only), sampled serve.request JSONL events and a bounded
    # slow-request exemplar ring in ServeMetrics.snapshot().  off
    # (default) is bitwise-inert: the compiled predict programs and the
    # 1-dispatch census are identical (tests/test_serve_tracing.py) —
    # and armed tracing still adds ZERO device dispatches.
    ("tpu_serve_request_log", str, "off", (), None),  # off|on
    # Fraction of traced requests emitting a serve.request event
    # (deterministic pacing over the request sequence, not random);
    # requests past tpu_serve_slow_ms are ALWAYS sampled.
    ("tpu_serve_request_sample", float, 0.01, (), (0.0, 1.0)),
    # Slow-request threshold (ms): traced requests at/above it bypass
    # sampling and enter the top-K exemplar ring; 0 disables the
    # slow override (pure rate sampling, no ring entries).
    ("tpu_serve_slow_ms", float, 100.0, (), (0.0, None)),
    # p99 latency SLO target (ms) driving rolling-window SLO-attainment
    # and error-budget-burn gauges (serve.slo_attainment /
    # serve.slo_budget_burn) with shed/deadline/fault attribution;
    # 0 disables SLO accounting.
    ("tpu_serve_slo_p99_ms", float, 0.0, (), (0.0, None)),
    # ---- Resilience / fault tolerance (docs/ROBUSTNESS.md) ----
    # Atomic training snapshots (resilience/checkpoint.py) every N
    # committed boosting rounds, emitted at iter-pack commit boundaries;
    # 0 disables.  Resume via engine.train(..., resume_from=...) is
    # bitwise-identical to the uninterrupted run.
    ("checkpoint_interval", int, 0, ("ckpt_interval",), (0, None)),
    # Snapshot directory; "" derives "<output_model>.ckpt".
    ("checkpoint_dir", str, "", ("ckpt_dir",), None),
    # Snapshot generations retained (older ones are the corruption
    # fallback chain).
    ("checkpoint_keep", int, 2, (), (1, None)),
    # Hard wall-clock budget (seconds) for the backend watchdog's
    # subprocess probe (resilience/watchdog.py): compile + tiny dispatch
    # must answer within it or the backend is classified wedged.
    ("tpu_probe_timeout", float, 60.0, (), (0.0, None)),
    # Serve admission control (serve/predictor.py MicroBatcher): queued
    # requests beyond this are shed with ServeOverloadError; 0 = unbounded.
    ("serve_max_queue", int, 0, (), (0, None)),
    # Per-request serving deadline: requests still queued past it are
    # failed with ServeDeadlineError instead of dispatched late; 0 = none.
    ("serve_deadline_ms", float, 0.0, (), (0.0, None)),
    # ---- Training-health sentinel (resilience/health.py) ----
    # What to do when the sentinel trips (non-finite gradients/hessians/
    # leaf values/scores in the in-dispatch health vector, a non-finite or
    # spiking eval loss, or a stagnant-to-saturation loss window):
    # off = no guards at all (training is bitwise-identical to a build
    # without the sentinel), warn = log and continue, halt = raise
    # HealthHaltError, rollback = restore the last good checkpoint
    # in-process, back off the learning rate and re-fold the device
    # sampling keys, then resume.
    ("tpu_health_policy", str, "off", ("health_policy",), None),
    # Divergence detector: trip when a lower-is-better eval loss exceeds
    # spike_factor x the best value inside the trailing window.
    ("tpu_health_spike_factor", float, 10.0, (), (1.0, None)),
    # Trailing per-round loss window for spike/stagnation detection.
    ("tpu_health_window", int, 5, (), (2, None)),
    # Max-abs train score above which the sentinel reports overflow
    # (pre-NaN saturation); 0 disables the magnitude check.
    ("tpu_health_score_limit", float, 1e30, (), (0.0, None)),
    # In-process rollbacks allowed before escalating to HealthHaltError.
    ("tpu_health_max_rollbacks", int, 2, (), (0, None)),
    # learning_rate multiplier applied per recovery generation (salt).
    ("tpu_health_lr_backoff", float, 0.5, (), (0.0, 1.0)),
    # Recovery generation: >0 re-folds the device sampling keys and backs
    # off the learning rate exactly as the Nth in-process rollback does —
    # a fresh run resumed from the same checkpoint with the same salt
    # reproduces the recovered run's trees bitwise (docs/ROBUSTNESS.md).
    ("tpu_health_recovery_salt", int, 0, (), (0, None)),
    # ---- Telemetry / observability (telemetry/, docs/OBSERVABILITY.md) ----
    # Unified telemetry: on = host-side spans at dispatch boundaries, the
    # process metrics registry and JSONL events; off is bitwise-inert —
    # compiled programs identical, dispatch census unchanged (telemetry is
    # never traced into a device program either way).
    ("tpu_telemetry", str, "on", (), None),  # on|off
    # Structured JSONL event log path ("" = no event file; registry
    # counters and spans still aggregate in-process).  Replay with
    # tools/telemetry_report.py; also feeds tools/health_report.py and
    # tools/profile_iter.py --from-log.
    ("tpu_telemetry_log", str, "", ("telemetry_log",), None),
    # Capture a jax.profiler trace directory for the FIRST N committed
    # boosting rounds (0 = off).  Directory: tpu_profile_dir, else
    # "<tpu_telemetry_log>.trace", else /tmp/lightgbm_tpu_profile.
    ("tpu_profile_iters", int, 0, (), (0, None)),
    ("tpu_profile_dir", str, "", (), None),
    # Device-memory accounting (telemetry/memory.py): off (default,
    # bitwise-inert — pure host-side observation, the lowered-HLO
    # equality pin covers this knob too) | watermark (tracked spans
    # snapshot device.memory_stats() bytes-in-use/peak and emit
    # memory.watermark events + memory.* gauges) | census (watermark
    # plus a jax.live_arrays() shape/dtype census per tracked span —
    # O(live buffers) host work per dispatch boundary).
    ("tpu_telemetry_memory", str, "off", ("telemetry_memory",), None),
    # ---- Out-of-core streaming training (lightgbm_tpu/stream/,
    # docs/STREAMING.md) ----
    # Device-byte budget for the streaming residency pipeline: the
    # host->device chunk double buffer (and the goss-residency compact
    # slice) must fit inside it; the detail.stream bench rung witnesses
    # live streaming-buffer bytes <= this budget.  Per-row training state
    # (scores/gradients/partition, O(N) bytes) is deliberately outside
    # the budget — it is ~F*itemsize times smaller than the bins matrix
    # the budget exists to keep off the device.
    ("tpu_stream_budget_mb", float, 256.0, ("stream_budget_mb",),
     (0.01, None)),
    # Residency mode: chunks = every bins pass sweeps budget-bounded
    # chunks (bitwise-identical trees to in-core training); goss = only
    # the device-GOSS sampled slice is resident per iteration (compact
    # gather + one routing sweep; needs data_sample_strategy=goss with
    # device GOSS, and stochastically-rounded quantized gradients degrade
    # back to chunks).  auto = chunks.
    ("tpu_stream_residency", str, "auto", (), None),  # auto|chunks|goss
    # Default row count per shard file for Dataset.to_shards; smaller
    # shards give the residency pipeline finer chunking under tight
    # budgets at the cost of more frames.
    ("tpu_stream_rows_per_shard", int, 65536, (), (256, None)),
    # Double-buffered async prefetch: assemble + upload the next chunk
    # while the current one's dispatches run.  Disable to debug (every
    # chunk then uploads synchronously, counted as a prefetch stall).
    ("tpu_stream_prefetch", bool, True, (), None),
]

_CANONICAL: Dict[str, Tuple[str, Any, Any, Optional[Tuple[Any, Any]]]] = {}
_ALIASES: Dict[str, str] = {}
for _name, _typ, _default, _aliases, _check in _PARAMS:
    _CANONICAL[_name] = (_name, _typ, _default, _check)
    for _a in _aliases:
        _ALIASES[_a] = _name

_OBJECTIVE_ALIASES = {
    "regression": "regression", "regression_l2": "regression", "l2": "regression",
    "mean_squared_error": "regression", "mse": "regression", "l2_root": "regression",
    "root_mean_squared_error": "regression", "rmse": "regression",
    "regression_l1": "regression_l1", "l1": "regression_l1",
    "mean_absolute_error": "regression_l1", "mae": "regression_l1",
    "huber": "huber", "fair": "fair", "poisson": "poisson", "quantile": "quantile",
    "mape": "mape", "mean_absolute_percentage_error": "mape",
    "gamma": "gamma", "tweedie": "tweedie",
    "binary": "binary",
    "multiclass": "multiclass", "softmax": "multiclass",
    "multiclassova": "multiclassova", "multiclass_ova": "multiclassova",
    "ova": "multiclassova", "ovr": "multiclassova",
    "cross_entropy": "cross_entropy", "xentropy": "cross_entropy",
    "cross_entropy_lambda": "cross_entropy_lambda", "xentlambda": "cross_entropy_lambda",
    "lambdarank": "lambdarank", "rank_xendcg": "rank_xendcg",
    "xendcg": "rank_xendcg", "xe_ndcg": "rank_xendcg", "xe_ndcg_mart": "rank_xendcg",
    "xendcg_mart": "rank_xendcg",
    "custom": "custom", "none": "custom", "null": "custom", "na": "custom",
}


def _coerce(name: str, typ: Any, value: Any) -> Any:
    if typ is bool:
        if isinstance(value, str):
            return value.strip().lower() in ("true", "1", "yes", "+")
        return bool(value)
    if typ is int:
        return int(value)
    if typ is float:
        return float(value)
    if typ is str:
        return str(value).strip().lower() if name in ("objective", "boosting", "tree_learner",
                                                      "device_type", "monotone_constraints_method",
                                                      "data_sample_strategy", "tpu_histogram_impl",
                                                      "tpu_hist_comm", "tpu_wave_kernel",
                                                      "tpu_serve_quantize",
                                                      "tpu_serve_request_log",
                                                      "tpu_traverse_kernel",
                                                      "tpu_health_policy",
                                                      "tpu_telemetry",
                                                      "tpu_telemetry_memory",
                                                      "tpu_stream_residency") \
            else str(value)
    if typ in ("list_int", "list_float", "list_str"):
        if value is None:
            return None
        if isinstance(value, str):
            if "[" in value:
                # Bracket-grouped form (reference Config::Str2FeatureVec,
                # e.g. interaction_constraints="[0,1],[2,3]"): each
                # bracketed group is ONE list element — a bare comma split
                # would shred the groups into singletons.
                parts = re.findall(r"\[([^\]]*)\]", value)
            else:
                parts = [p for p in value.replace(";", ",").split(",")
                         if p != ""]
        elif isinstance(value, (list, tuple)):
            parts = list(value)
        else:
            parts = [value]
        if typ == "list_int":
            return [int(p) for p in parts]
        if typ == "list_float":
            return [float(p) for p in parts]
        return [str(p) for p in parts]
    raise TypeError(f"unknown param type for {name}")


@dataclasses.dataclass
class Config:
    """Resolved training configuration (all canonical parameter names)."""

    # Populated dynamically below from _PARAMS.

    def __init__(self, params: Optional[Dict[str, Any]] = None, **kwargs: Any):
        merged = dict(params or {})
        merged.update(kwargs)
        for name, (_, typ, default, _) in _CANONICAL.items():
            object.__setattr__(self, name, default)
        self.raw_params: Dict[str, Any] = {}
        self.update(merged)

    def update(self, params: Dict[str, Any]) -> None:
        """Apply a param dict; aliases resolve to canonical names (first write wins
        per reference ``ParameterAlias::KeyAliasTransform``: an explicit canonical
        key beats its aliases)."""
        resolved: Dict[str, Any] = {}
        for key, value in params.items():
            canon = _ALIASES.get(key, key)
            if canon in resolved and key in _ALIASES:
                continue  # canonical (or earlier alias) already set
            resolved[canon] = value
        for key, value in resolved.items():
            if value is None and key not in _CANONICAL:
                continue
            if key not in _CANONICAL:
                # Unknown params are kept (callers may carry app-specific keys).
                self.raw_params[key] = value
                continue
            _, typ, _, check = _CANONICAL[key]
            coerced = _coerce(key, typ, value)
            if check is not None and coerced is not None and not isinstance(coerced, list):
                lo, hi = check
                if lo is not None and coerced < lo:
                    raise ValueError(f"{key}={coerced} < minimum {lo}")
                if hi is not None and coerced > hi:
                    raise ValueError(f"{key}={coerced} > maximum {hi}")
            object.__setattr__(self, key, coerced)
            self.raw_params[key] = value
        self._post_process()

    def _post_process(self) -> None:
        # Objective aliases (reference: config.cpp ParseObjectiveAlias).
        obj = self.objective
        if obj in _OBJECTIVE_ALIASES:
            object.__setattr__(self, "objective", _OBJECTIVE_ALIASES[obj])
        elif obj.startswith("quantile:") or obj.startswith("alpha:"):
            object.__setattr__(self, "alpha", float(obj.split(":")[1]))
            object.__setattr__(self, "objective", "quantile")
        if self.boosting in ("gbrt", "gbdt"):
            object.__setattr__(self, "boosting", "gbdt")
        elif self.boosting in ("rf", "random_forest"):
            object.__setattr__(self, "boosting", "rf")
        if self.data_sample_strategy == "goss" or self.boosting == "goss":
            object.__setattr__(self, "data_sample_strategy", "goss")
            if self.boosting == "goss":
                object.__setattr__(self, "boosting", "gbdt")
        # Multiclass must know K (reference: config.cpp check).
        if self.objective in ("multiclass", "multiclassova") and self.num_class <= 1:
            raise ValueError("num_class must be >1 for multiclass objectives")
        if self.is_unbalance and self.scale_pos_weight != 1.0:
            raise ValueError("is_unbalance and scale_pos_weight cannot both be set")

    def to_dict(self) -> Dict[str, Any]:
        return {name: getattr(self, name) for name in _CANONICAL}

    @property
    def num_model_per_iteration(self) -> int:
        # "custom" matches reference GBDT::Init: with a null objective the
        # boosting order is num_class trees per iteration (gbdt.cpp), so a
        # custom multiclass objective trains k trees from class-major grads.
        if self.objective in ("multiclass", "multiclassova", "custom"):
            return self.num_class
        return 1


def canonical_name(key: str) -> str:
    return _ALIASES.get(key, key)


def aliases_of(name: str) -> List[str]:
    """All alias spellings of a canonical parameter (excluding itself)."""
    return [a for a, c in _ALIASES.items() if c == name]


def param_names() -> List[str]:
    return list(_CANONICAL.keys())
