"""GBDT boosting driver.

Reference: ``GBDT`` (``src/boosting/gbdt.cpp`` — ``Train:237``, ``TrainOneIter:344``
boost-from-average -> gradients -> bagging -> one tree per class -> RenewTreeOutput
-> Shrinkage -> UpdateScore; ``gbdt_model_text.cpp`` for serialization).

TPU layout: scores, gradients, binned rows and the whole tree-growth loop live in
HBM; one boosting iteration is a handful of fused XLA programs (objective grads ->
grow_tree -> score gather).  Host work per iteration is O(1) scalars plus the
optional percentile leaf renewal (branchy, host-friendly — kept on CPU exactly as
the reference keeps SHAP/categorical logic host-side).
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..dataset import TrainData
from ..metrics import Metric
from ..telemetry import (dispatched, iter_record, note_program, phase,
                         registry, segment, span, watch_compiles)
from ..objectives import ObjectiveFunction, create_objective
from ..sampling import FeatureSampler, SampleStrategy
from ..ops.split import SplitConfig
from .grower import GrowerConfig, TreeArrays, make_grower, \
    slice_tree_arrays
from .tree import Tree, predict_tree_bins_device, stack_trees, \
    predict_ensemble_bins_device


def _split_config(cfg: Config, train: Optional[TrainData] = None) -> SplitConfig:
    facts = {}
    if train is not None:
        binned = train.binned
        mono = train.monotone_constraints
        is_cat = np.asarray(binned.is_categorical)
        nbpf = np.asarray(binned.num_bins_per_feature)
        facts = dict(
            has_nan=bool(np.any(np.asarray(binned.nan_bins)
                                < binned.max_num_bins)),
            has_categorical=bool(np.any(is_cat)),
            use_sorted_categorical=bool(
                np.any(is_cat & (nbpf > cfg.max_cat_to_onehot))),
            has_monotone=mono is not None and bool(np.any(mono != 0)),
        )
    return SplitConfig(
        lambda_l1=cfg.lambda_l1,
        lambda_l2=cfg.lambda_l2,
        min_data_in_leaf=cfg.min_data_in_leaf,
        min_sum_hessian_in_leaf=cfg.min_sum_hessian_in_leaf,
        min_gain_to_split=cfg.min_gain_to_split,
        max_delta_step=cfg.max_delta_step,
        cat_l2=cfg.cat_l2,
        cat_smooth=cfg.cat_smooth,
        max_cat_threshold=cfg.max_cat_threshold,
        max_cat_to_onehot=cfg.max_cat_to_onehot,
        min_data_per_group=cfg.min_data_per_group,
        path_smooth=cfg.path_smooth,
        monotone_penalty=cfg.monotone_penalty,
        feature_contri=(tuple(float(v) for v in cfg.feature_contri)
                        if cfg.feature_contri else None),
        extra_trees=cfg.extra_trees,
        use_cegb=bool(cfg.cegb_penalty_split > 0.0
                      or cfg.cegb_penalty_feature_coupled
                      or cfg.cegb_penalty_feature_lazy
                      or cfg.cegb_tradeoff < 1.0),
        cegb_tradeoff=cfg.cegb_tradeoff,
        cegb_penalty_split=cfg.cegb_penalty_split,
        scan_tile=cfg.tpu_split_tile,
        **facts,
    )


@jax.jit
def _add_leaf_outputs(scores, row_leaf, leaf_values):
    return scores + leaf_values[row_leaf]


def _tree_dict(arrays: TreeArrays) -> dict:
    """Zero-copy view of device TreeArrays in the dict layout the traversal
    kernels consume (same keys as ``stack_trees``)."""
    return {
        "split_feature": arrays.split_feature,
        "split_bin": arrays.split_bin,
        "default_left": arrays.default_left,
        "is_cat": arrays.is_cat,
        "cat_mask": arrays.cat_mask,
        "left_child": arrays.left_child,
        "right_child": arrays.right_child,
        "leaf_value": arrays.leaf_value,
        "num_leaves": arrays.num_leaves,
    }


@jax.jit
def _scale_tree_arrays(arrays: TreeArrays, factor) -> TreeArrays:
    return arrays._replace(leaf_value=arrays.leaf_value * factor,
                           internal_value=arrays.internal_value * factor)


@contextlib.contextmanager
def _kernel_compile_errors():
    """Around a dispatch that may compile a Pallas kernel.  A Mosaic
    compile failure must reach the user as itself: the north star is
    "never silently routed to a slow path", so nothing is retried on
    another implementation.  It is re-raised with the compiler's first
    line and the two explicit opt-outs; any other exception passes
    through untouched."""
    try:
        yield
    except Exception as err:  # noqa: BLE001 — annotate, never swallow
        msg = str(err)
        if "mosaic" not in msg.lower() and "pallas" not in msg.lower():
            raise
        raise RuntimeError(
            f"TPU kernel failed to compile: {msg.strip().splitlines()[0][:300]}"
            " — no fallback is taken; to train without the kernels set "
            "tpu_histogram_impl=onehot (XLA one-hot histogram) and/or "
            "tpu_wave_kernel=unfused explicitly") from err


def _mark_features_used_trace(used, split_feature, num_leaves):
    """``used |= features split by this tree`` — the in-trace CEGB
    first-use update (reference ``CostEfficientGradientBoosting::
    UpdateUsedFeatures``): only the tree's ``num_leaves - 1`` live split
    slots mark, stale tail entries scatter out of range and drop."""
    m = split_feature.shape[0]
    f = used.shape[0]
    live = jnp.arange(m, dtype=jnp.int32) < (num_leaves - 1)
    idx = jnp.where(live, split_feature, f)
    return used.at[idx].set(True, mode="drop")


_mark_features_used = jax.jit(_mark_features_used_trace)


class GBDT:
    """Boosting driver (reference ``GBDT``, ``gbdt.h:630``)."""

    # Subclasses that mutate scores between iterations (DART's drop/renorm)
    # clear this so the stop check never defers (see train_one_iter).
    _deterministic_iters = True
    # Subclasses that do host work between rounds (DART drop/renorm, RF
    # per-round re-bagging) clear this; the iteration-packed path
    # (train_pack) is only offered when the plain GBDT round loop applies.
    _supports_iter_pack = True
    # Auto pack-size ceiling: bounds the (K, ...)-stacked TreeArrays a
    # single scan emits (explicit tpu_iter_pack may exceed it).
    _PACK_AUTO_CAP = 256
    # The running iteration's record (telemetry/iters.py; None with
    # telemetry off) and what marks its programs' names under GOSS.
    _iter_rec = None
    _iter_tag = ""

    def __init__(self, cfg: Config, train: TrainData,
                 valids: Sequence[Tuple[str, TrainData]] = (),
                 base_model=None):
        self.cfg = cfg
        self.train_data = train
        self.valids = list(valids)
        self.num_class = cfg.num_model_per_iteration
        # Training continuation (reference boosting.cpp:34-59 input_model):
        # ``base_model`` is a LoadedModel whose raw-score predictions were
        # folded into every dataset's init_score by the caller; its trees are
        # re-emitted on save and summed into predictions.
        self.base_model = base_model
        self.objective: Optional[ObjectiveFunction] = create_objective(cfg)
        if self.objective is not None:
            self.objective.init(train.label, train.weight, train.group,
                                cfg, position=train.position)
        self.metrics = self._create_metrics()
        # Device-resident ensemble: dev_models holds TreeArrays in HBM (the
        # reference's CUDATree); host Tree mirrors are materialized lazily in
        # one batched transfer (every host<->device round trip stalls the
        # dispatch queue).
        self.dev_models: List[List[TreeArrays]] = [
            [] for _ in range(self.num_class)]
        self._host_cache: List[List[Optional[Tree]]] = [
            [] for _ in range(self.num_class)]
        self.iter_ = 0
        self.best_iteration = -1
        # Bumped by IN-PLACE leaf mutations that change predictions without
        # touching iter_/num_trees (C-API SetLeafValue / Refit) — part of
        # the serve PredictPlan cache key, so a mutated model can never be
        # served from a stale device tree pack.
        self._pred_version = 0

        # Distributed layout: sharding the inputs IS the parallel tree learner
        # (see parallel/mesh.py; reference §2.9 data/feature/voting learners).
        from ..parallel.mesh import mesh_for_tree_learner, shard_arrays
        self.mesh = mesh_for_tree_learner(cfg.tree_learner)
        self.feature_sampler = FeatureSampler(cfg, train.num_features)
        has_mono = (train.monotone_constraints is not None
                    and np.any(train.monotone_constraints != 0))
        mono_method = cfg.monotone_constraints_method
        if has_mono and mono_method not in ("basic", "intermediate",
                                            "advanced"):
            raise ValueError(
                f"unknown monotone_constraints_method={mono_method}; "
                "expected basic, intermediate or advanced")
        self._mono_advanced = has_mono and mono_method == "advanced"
        self._mono_intermediate = has_mono and mono_method == "intermediate"
        # is_enable_sparse is subsumed by EFB (enable_bundle), which covers
        # the sparse-column win here — say so loudly instead of silently
        # ignoring it.
        from ..utils.log import Log
        for pname in ("is_enable_sparse",):
            if pname in cfg.raw_params:
                Log.warning(
                    f"{pname} has no effect on the TPU build: bins are "
                    "stored as one dense (rows, features) device array and "
                    "sparse columns are handled by EFB (enable_bundle)")
        if cfg.parser_config_file:
            Log.warning(
                "parser_config_file (pluggable custom parsers) is not "
                "supported; the built-in CSV/TSV/LibSVM parsers are used")
        if (cfg.two_round
                and not getattr(train, "_two_round_loaded", False)):
            Log.warning(
                "two_round streaming applies to FILE input (CLI "
                "data=<file> or dataset.load_train_data_two_round); this "
                "dataset came from in-memory arrays, which are already "
                "materialized")
        # Host-threading / GPU-device knobs have no TPU analog (XLA owns
        # threading and fusion; the device is the jax backend) — warn
        # instead of silently accepting (round-2 verdict: no silent dead
        # params).  histogram_pool_size is NOT on this list: it bounds the
        # growth loop's device-resident leaf-histogram carry (grower
        # P-slot pool, reference HistogramPool).
        for pname in ("num_threads", "force_col_wise", "force_row_wise",
                      "gpu_platform_id",
                      "gpu_device_id", "gpu_use_dp", "num_gpu"):
            if pname in cfg.raw_params:
                Log.warning(
                    f"{pname} has no effect on the TPU build (XLA/the jax "
                    "backend owns threading, histogram memory and device "
                    "selection)")
        # An EXPLICITLY requested accelerator that resolved to the cpu
        # backend is an error, not a CPU run under an accelerator's name.
        # Checked here because the uploads below initialize the backend
        # either way.  (The default device_type is not a request: CPU runs
        # of the identical programs stay how the tests work.)
        requested = str(cfg.raw_params.get(
            "device_type", cfg.raw_params.get("device", ""))).lower()
        if requested in ("tpu", "gpu", "cuda") \
                and jax.default_backend() == "cpu":
            raise RuntimeError(
                f"device_type={requested} was requested but the live jax "
                "backend is 'cpu' (JAX_PLATFORMS="
                f"{os.environ.get('JAX_PLATFORMS', '<unset>')!r}); drop "
                "device_type or set it to cpu to train on the CPU backend")
        from ..parallel.mesh import DATA_AXIS, FEATURE_AXIS
        # Data-only meshes use the sharded permutation layout (shard_map:
        # per-shard pallas histograms + one reduction per wave); what a
        # feature-only or hybrid mesh runs is the growth plan's to say
        # (self.plan, below).
        data_only_mesh = (self.mesh is not None
                          and int(self.mesh.shape[FEATURE_AXIS]) == 1)
        voting = cfg.tree_learner == "voting" and data_only_mesh
        # EFB (reference FindGroups/FeatureGroup): histogram/partition run
        # on the bundled column matrix; split scans see reconstructed
        # per-feature views (models/grower.py _expand_hist).
        self.bundles = train.build_bundles(cfg)
        # Forced splits (reference ForceSplits JSON,
        # serial_tree_learner.cpp:620): BFS-flatten the nested
        # {feature, threshold, left, right} tree, thresholds -> bins.
        forced = None
        leaf_batch = cfg.tpu_leaf_batch
        if cfg.forcedsplits_filename:
            import json as _json
            with open(cfg.forcedsplits_filename) as fh:
                root_spec = _json.load(fh)
            nodes = []
            queue = [(root_spec, -1, True)]
            while queue:
                spec, parent, is_left = queue.pop(0)
                fi = int(spec["feature"])
                if train.binned.mappers[fi].is_categorical:
                    raise ValueError(
                        f"forced split on categorical feature {fi} is not "
                        "supported (numerical thresholds only)")
                thr = float(spec["threshold"])
                sbin = int(train.binned.mappers[fi].value_to_bin(
                    np.asarray([thr]))[0])
                idx = len(nodes)
                nodes.append([fi, sbin, -1, -1])
                if parent >= 0:
                    nodes[parent][2 if is_left else 3] = idx
                if "left" in spec and spec["left"]:
                    queue.append((spec["left"], idx, True))
                if "right" in spec and spec["right"]:
                    queue.append((spec["right"], idx, False))
            forced = tuple(tuple(nd) for nd in nodes)
        if self.bundles is not None:
            Log.info(f"EFB: bundled {train.num_features} features into "
                     f"{self.bundles.num_groups} columns")
        # Every learner-composition downgrade/rejection goes through the
        # declarative capability matrix (models/capabilities.py) — ONE
        # enumerable table instead of scattered warn-and-fallback branches.
        from .capabilities import Composition, plan_growth, resolve
        comp, _ = resolve(Composition(
            voting=voting,
            leaf_batch=leaf_batch,
            mono_method=mono_method if has_mono else "none",
            forced_splits=forced is not None,
            extra_trees=cfg.extra_trees,
            feature_fraction_bynode=cfg.feature_fraction_bynode < 1.0,
            wave_kernel=cfg.tpu_wave_kernel),
            warn=Log.warning)
        voting, leaf_batch = comp.voting, comp.leaf_batch
        wave_kernel = comp.wave_kernel
        if cfg.tpu_device_goss not in ("auto", "on", "off"):
            raise ValueError(
                f"tpu_device_goss={cfg.tpu_device_goss!r}: expected auto, "
                "on or off")
        from ..resilience.health import POLICIES
        if cfg.tpu_health_policy not in POLICIES:
            raise ValueError(
                f"tpu_health_policy={cfg.tpu_health_policy!r}: expected "
                f"one of {', '.join(POLICIES)}")
        if cfg.tpu_telemetry not in ("on", "off"):
            raise ValueError(
                f"tpu_telemetry={cfg.tpu_telemetry!r}: expected on or off")
        # Arm/disarm the process-wide telemetry switch — but only when the
        # caller SAID something (tpu_telemetry in this booster's params):
        # constructing a default-params booster (a serve mirror, a second
        # model load, a callback building a helper) must not flip the
        # switch under an in-flight training session.  engine.train arms
        # unconditionally from its own run's config.  Spans/events are
        # host-side only, so the knob never changes a compiled program —
        # "off" just silences the host instrumentation (bitwise-inert).
        if "tpu_telemetry" in cfg.raw_params:
            from .. import telemetry
            telemetry.arm_from_config(cfg)
        # Device-memory accounting mode (telemetry/memory.py) — same
        # explicit-params rule as the master switch above; engine.train
        # arms unconditionally from its own run's config.  An invalid
        # value can only arrive explicitly (the default "off" is valid),
        # so set_memory_mode is the single validator.
        if "tpu_telemetry_memory" in cfg.raw_params \
                or "telemetry_memory" in cfg.raw_params:
            from ..telemetry.memory import set_memory_mode
            set_memory_mode(cfg.tpu_telemetry_memory)
        # Training-health sentinel (resilience/health.py): with any policy
        # but "off", the iteration/pack programs fold the isfinite/max-abs
        # health vector into their dispatch and the quantized int16-wire
        # overflow guard reports its escalations.  "off" compiles the
        # EXACT pre-sentinel programs (bitwise-identity contract).
        self._health_active = cfg.tpu_health_policy != "off"
        self._health_pending = None
        self._trailing_health = None
        self._health_eval = None
        self._pack_health_pending: List = []
        self.sample_strategy = SampleStrategy(
            cfg, train.num_data, train.label, train.query_boundaries())
        # Device-resident GOSS (tpu_device_goss): "on"/"auto" select the
        # sample from the just-computed DEVICE gradients — in-trace inside
        # the fused iteration when it applies, via a standalone device
        # dispatch under "on" otherwise; "off" (and "auto" on
        # non-fused-capable configs) replays the reference's host sampler
        # (np argsort + np.random), pulling gradients to the host.  Which
        # FORM the sample takes — in-bag row ids the tree is grown over, or
        # a mask over all rows — is the growth plan's word (plan.sampling).
        self._device_goss = cfg.tpu_device_goss
        # (mask, in-bag row ids or None) of the last iteration's sample;
        # None = it took every row (last_sample())
        self._last_sample = None
        # per class (gq, hq, g_scale, h_scale) the last fused iteration
        # grew its trees on under quantized training (last_levels())
        self._last_levels = None
        if self.sample_strategy.is_goss:
            top_k, other_k, amp = self.sample_strategy.goss_constants()
            registry().gauge("sample.top_k").set(top_k)
            registry().gauge("sample.other_k").set(other_k)
            registry().gauge("sample.in_bag_rows").set(top_k + other_k)
            registry().gauge("sample.amplify").set(amp)
        self.grower_cfg = GrowerConfig(
            num_leaves=cfg.num_leaves,
            max_depth=cfg.max_depth,
            num_bins=train.binned.max_num_bins,
            hist_bins=(self.bundles.max_group_bins
                       if self.bundles is not None else 0),
            split=_split_config(cfg, train),
            histogram_impl=cfg.tpu_histogram_impl,
            gather_rows=self.mesh is None or data_only_mesh,
            leaf_batch=leaf_batch,
            forced_splits=forced,
            feature_fraction_bynode=cfg.feature_fraction_bynode,
            interaction_groups=self.feature_sampler.interaction_groups,
            quantized=cfg.use_quantized_grad,
            num_grad_quant_bins=cfg.num_grad_quant_bins,
            stochastic_rounding=cfg.stochastic_rounding,
            quant_renew_leaf=cfg.quant_train_renew_leaf,
            voting=voting,
            vote_top_k=cfg.top_k,
            bundled=self.bundles is not None,
            mono_intermediate=self._mono_intermediate,
            mono_advanced=self._mono_advanced,
            mono_static=(tuple(int(m) for m in train.monotone_constraints)
                         if self._mono_advanced else None),
            hist_comm=cfg.tpu_hist_comm,
            histogram_pool_size=cfg.histogram_pool_size,
            wave_kernel=wave_kernel,
            health_signal=self._health_active,
            # 4-bit bin packing (reference DenseBin IS_4BIT
            # auto-selection): with every feature at <= 16 bins, store
            # nibble pairs — the resident bin matrix and per-leaf gathers
            # halve.  Asked for here; the plan refuses it under EFB and
            # the feature-parallel layout.
            packed4=cfg.tpu_4bit_bins and train.binned.max_num_bins <= 16,
            sampling=self._row_sampler(),
        )
        # What this configuration runs on this mesh at this shape — body,
        # layout, kernel, reduction, pool — decided in ONE place
        # (capabilities.plan_growth) and read everywhere else, the grower
        # included.  Invalid tpu_histogram_impl / tpu_wave_kernel /
        # tpu_hist_comm values raise here.
        self.plan = plan_growth(self.grower_cfg, self.mesh, DATA_AXIS,
                                rows=train.num_data,
                                features=train.num_features)
        Log.debug(f"growth plan: {self.plan}")
        # the config states the form the bins travel in: the plan's
        self.grower_cfg = dataclasses.replace(self.grower_cfg,
                                              packed4=self.plan.packed4)
        for asked, key, said in (
                (cfg.tpu_hist_comm == "reduce_scatter", "scatter",
                 "tpu_hist_comm=reduce_scatter cannot engage ({}); keeping "
                 "the full-histogram allreduce"),
                (cfg.histogram_pool_size >= 0, "pool",
                 "histogram_pool_size is ignored for this composition "
                 "({}); keeping the full (num_leaves, ...) carry"),
                (wave_kernel == "fused", "fused",
                 "tpu_wave_kernel=fused cannot engage ({}); keeping the "
                 "unfused path")):
            if asked and key in self.plan.why:
                Log.warning(said.format(self.plan.why[key]))
        self._quant_key = (jax.random.PRNGKey(cfg.seed)
                           if cfg.use_quantized_grad else None)
        # PRNG for per-node randomness (extra_trees thresholds / bynode
        # feature sampling; reference extra_seed / feature_fraction_seed).
        self._goss_key = jax.random.PRNGKey(cfg.bagging_seed)
        # Pack-path device sampling keys (docs/ITER_PACK.md): bagging shares
        # the bagging_seed key above; feature_fraction gets its own stream.
        self._ff_key = jax.random.PRNGKey(cfg.feature_fraction_seed)
        self._split_key = None
        if cfg.extra_trees or cfg.feature_fraction_bynode < 1.0:
            self._split_key = jax.random.PRNGKey(
                cfg.extra_seed * 92821 + cfg.feature_fraction_seed)
        self.grow = make_grower(self.grower_cfg, mesh=self.mesh,
                                data_axis=DATA_AXIS)
        if self.bundles is not None:
            self.bins_dev = train.bundled_bins_device()
            self._fg_dev = jnp.asarray(self.bundles.feat_group, jnp.int32)
            self._fo_dev = jnp.asarray(self.bundles.feat_offset, jnp.int32)
        else:
            self.bins_dev = train.bins_device()
            self._fg_dev = self._fo_dev = None
        if self.grower_cfg.packed4:
            from ..ops.histogram import pack_bins4
            self.bins_dev = pack_bins4(self.bins_dev)
            # Drop the Dataset's cached byte-per-bin device matrix — the
            # packed copy is now the resident one (the halving is the
            # feature's point).  DART/rollback re-materialize the unpacked
            # view through score_bins_dev, which warns about the cost.
            train._bins_dev = None
        self.meta_dev = train.feature_meta_device()
        if self.mesh is not None:
            if data_only_mesh:
                # Pre-pad rows once so the sharded grower's shard_map sees
                # even shards without re-copying bins every iteration (pad
                # rows carry zero values — see grower.grow).
                pad = (-self.bins_dev.shape[0]) % int(
                    self.mesh.shape[DATA_AXIS])
                if pad:
                    self.bins_dev = jnp.pad(self.bins_dev,
                                            ((0, pad), (0, 0)))
            elif self.plan.layout == "feature":
                # Feature-sharded perm layout: pad feature columns so the
                # (data, feature) placement shards evenly; the grower pads
                # its per-feature metadata to match (grower._grow_fp).
                padf = (-self.bins_dev.shape[1]) % int(
                    self.mesh.shape[FEATURE_AXIS])
                if padf:
                    self.bins_dev = jnp.pad(self.bins_dev,
                                            ((0, 0), (0, padf)))
            self.bins_dev = shard_arrays(self.mesh, self.bins_dev)

        # CEGB (reference cost_effective_gradient_boosting.hpp): coupled
        # penalties apply on a feature's FIRST use in the model.  The
        # cross-iteration ``used`` feature vector is a device-resident (F,)
        # bool carried in the training state and updated IN-TRACE from each
        # tree's split_feature/num_leaves, so the fused iteration (and the
        # iter-pack scan) never round-trips it through the host.
        self._use_cegb = self.grower_cfg.split.use_cegb
        if self._use_cegb:
            nf = train.num_features
            def _vec(lst):
                v = np.zeros(nf, np.float32)
                if lst:
                    v[: len(lst)] = np.asarray(lst, np.float32)[:nf]
                return v
            self._cegb_coupled_raw = _vec(cfg.cegb_penalty_feature_coupled)
            self._cegb_coupled_dev = jnp.asarray(self._cegb_coupled_raw)
            self._cegb_lazy_dev = jnp.asarray(
                _vec(cfg.cegb_penalty_feature_lazy))
            self._cegb_used_dev = jnp.zeros(nf, bool)
        # Uncommitted per-round CEGB used-vector snapshots from the last
        # train_pack (commit_round advances _cegb_used_dev through them).
        self._pack_used_pending: List[jnp.ndarray] = []

        self._linear_nls: List[int] = []
        # Degenerate-tree stop check runs one iteration BEHIND: the pending
        # num_leaves handles are fetched only after the NEXT iteration has
        # been dispatched, so the host sync never drains the device queue
        # (each fetch targets an iteration that has already finished).
        self._nls_pending = None
        self.init_scores = np.zeros(self.num_class, np.float64)
        # Reference gbdt.cpp:319 BoostFromAverage applies only when the data
        # carries no init score (continuation replays the base model there).
        if (cfg.boost_from_average and self.objective is not None
                and train.init_score is None):
            for k in range(self.num_class):
                self.init_scores[k] = self.objective.boost_from_score(k)
        self.scores = self._init_scores_array(train)
        self.valid_bins = [v.bins_device() for _, v in self.valids]
        self.valid_scores = [self._init_scores_array(v) for _, v in self.valids]
        self._shape_k = self.num_class > 1 or self.cfg.objective in (
            "multiclass", "multiclassova")
        # Per-iteration device state cached once: uploading an (N,) mask every
        # iteration costs a host->device transfer that dwarfs the tree growth.
        self._full_mask = jnp.ones(train.num_data, jnp.float32)
        self._bag_mask_dev = None
        self._fmask_static = None
        if cfg.feature_fraction >= 1.0:
            self._fmask_static = jnp.asarray(self.feature_sampler.tree_mask(0))
        if self.objective is None:
            self._grad_fn = None
        elif self.objective.stochastic_gradients:
            self._grad_fn = self.objective.get_gradients
        else:
            self._grad_fn = jax.jit(
                phase("boost/gradients")(self.objective.get_gradients))
        self._build_iter_fns()

    def _row_sampler(self) -> str:
        """The row sampler this configuration runs, as ``GrowerConfig``
        names it: the device GOSS selection is the one that hands the
        grower in-bag row ids (an iteration given custom gradients still
        samples on the host, as a mask)."""
        cfg, strategy, obj = self.cfg, self.sample_strategy, self.objective
        if cfg.boosting == "rf":
            return "rf_bagging" if (strategy.is_goss
                                    or strategy.is_bagging) else "none"
        if strategy.is_goss:
            fusable = (obj is not None and not obj.need_renew_tree_output
                       and not obj.stochastic_gradients
                       and not cfg.linear_tree)
            device = (cfg.tpu_device_goss == "on"
                      or (cfg.tpu_device_goss == "auto" and fusable))
            return "goss_device" if device else "goss_host"
        return "bagging" if strategy.is_bagging else "none"

    def _build_iter_fns(self) -> None:
        """Compile the per-iteration programs.  The fused program runs
        objective gradients -> tree growth -> shrinkage -> score update as ONE
        XLA dispatch (reference: the CUDA learner's device-resident iteration,
        ``cuda_single_gpu_tree_learner.cpp:158`` — host sees only scalars)."""
        grow = getattr(self.grow, "raw", self.grow)
        meta = self.meta_dev
        obj = self.objective
        num_class = self.num_class
        shape_k = self._shape_k

        def grow_apply(bins, scores_k, grad_k, hess_k, mask, fmask, shrink,
                       cegb_coupled=None, cegb_lazy=None, quant_key=None,
                       split_key=None, sample_rows=None, levels_out=False):
            # bins rides as an ARGUMENT (not a closure): multi-process jit
            # rejects closing over arrays spanning non-addressable devices
            grown = grow(
                bins, grad_k, hess_k, mask, fmask,
                meta["num_bins_per_feature"], meta["nan_bins"],
                meta["is_categorical"], meta["monotone"],
                cegb_coupled, cegb_lazy, quant_key, split_key,
                self._fg_dev, self._fo_dev, sample_rows,
                levels_out=levels_out)
            arrays, row_leaf = grown[:2]
            with phase("boost/score_update"):
                grew = arrays.num_leaves > 1
                lv = jnp.where(grew, arrays.leaf_value * shrink, 0.0)
                # Defined rounding for the score update
                # (docs/STREAMING.md): without the barrier XLA may (or may
                # not, per surrounding graph) refuse to materialize lv and
                # instead fuse the shrink multiply into the gather+add as
                # an FMA — a per-program 1-ULP coin flip.  The barrier pins
                # the semantics to "materialized lv, then one exact add per
                # row", the ONE arithmetic every path
                # (fused/unfused/pack/streamed) reproduces, which is what
                # makes streamed==in-core bitwise provable instead of
                # fusion-heuristic-dependent.
                lv = jax.lax.optimization_barrier(lv)
                arrays = arrays._replace(
                    leaf_value=lv,
                    internal_value=arrays.internal_value * shrink)
                return (scores_k + lv[row_leaf], arrays, row_leaf) \
                    + tuple(grown[2:])

        self._grow_apply = jax.jit(grow_apply)

        self._fused_iter = None
        self._fused_core = None
        self._pack_fns: Dict[int, object] = {}
        # In-trace sampling/penalty state (docs/PERF.md round 8): GOSS
        # derives its mask from the in-trace gradients (tpu_device_goss)
        # and CEGB carries its first-use feature vector on device, so both
        # paths keep the ONE-dispatch iteration and stay pack-capable.
        strategy = self.sample_strategy
        goss_in_trace = (strategy.is_goss
                         and self._device_goss in ("auto", "on"))
        use_cegb = self._use_cegb
        track_used = use_cegb and bool(self._cegb_coupled_raw.any())
        n_rows = self.train_data.num_data
        subset = self.plan.sampling == "subset"
        if strategy.is_goss:
            goss_top_k, goss_other_k, goss_amp = strategy.goss_constants()

            def goss_sample(grad, hess, key, it):
                """This iteration's sample from its gradients: ``(mask,
                rows)``, ``rows`` the in-bag ids where the plan grows over
                the subset and None where it keeps the mask.  |g*h| summed
                across classes, key folded by the absolute iteration — ONE
                stream for the fused, the standalone and the packed
                iteration."""
                from ..sampling import goss_sample_device
                with phase("boost/gradients"), segment("sample"):
                    return goss_sample_device(
                        grad.reshape(n_rows, -1).sum(axis=1),
                        hess.reshape(n_rows, -1).sum(axis=1),
                        jax.random.fold_in(key, it), goss_top_k,
                        goss_other_k, goss_amp, with_rows=subset)

            self._goss_sample = jax.jit(goss_sample)
        cegb_lazy = self._cegb_lazy_dev if use_cegb else None
        cegb_coupled_raw = self._cegb_coupled_dev if use_cegb else None
        health_active = self._health_active
        # quantized training hands each class's (gq, hq, g_scale, h_scale)
        # out of the fused program, LAST (what last_levels() reads)
        levels_out = self._quant_key is not None
        if (obj is not None and not obj.need_renew_tree_output
                and not obj.stochastic_gradients):
            def fused(bins, scores, mask, fmask, shrink, quant_key=None,
                      split_key=None, it=None, goss_key=None,
                      cegb_used=None):
                with phase("boost/gradients"):
                    grad, hess = obj.get_gradients(scores)
                rows = sample = None
                if goss_in_trace and it is not None:
                    # ``it`` is None in the iterations GOSS leaves
                    # unsampled (the first int(1 / learning_rate)): the
                    # caller's full mask stands and this is the plain
                    # program
                    mask, rows = sample = goss_sample(grad, hess, goss_key,
                                                      it)
                coupled = lazy = None
                if use_cegb:
                    coupled = cegb_coupled_raw * (~cegb_used)
                    lazy = cegb_lazy
                outs, levels = [], []
                if shape_k:
                    new_scores = scores
                    for k in range(num_class):
                        qk = (None if quant_key is None
                              else jax.random.fold_in(quant_key, k))
                        sk = (None if split_key is None
                              else jax.random.fold_in(split_key, k))
                        ns_k, arrays, row_leaf, *lv = grow_apply(
                            bins, new_scores[:, k], grad[:, k], hess[:, k],
                            mask, fmask, shrink, coupled, lazy,
                            quant_key=qk, split_key=sk, sample_rows=rows,
                            levels_out=levels_out)
                        new_scores = new_scores.at[:, k].set(ns_k)
                        outs.append((arrays, row_leaf))
                        levels += lv
                else:
                    new_scores, arrays, row_leaf, *levels = grow_apply(
                        bins, scores, grad, hess, mask, fmask, shrink,
                        coupled, lazy, quant_key=quant_key,
                        split_key=split_key, sample_rows=rows,
                        levels_out=levels_out)
                    outs = [(arrays, row_leaf)]
                hv = None
                if health_active:
                    # in-dispatch health vector (resilience/health.py):
                    # folded into this same program, so the guard adds no
                    # extra dispatch (profile-census invariant)
                    from ..resilience.health import health_vector
                    with phase("boost/score_update"):
                        hv = health_vector(
                            grad, hess,
                            tuple(a.leaf_value for a, _rl in outs),
                            new_scores)
                ret = [new_scores, outs]
                if use_cegb:
                    new_used = cegb_used
                    if track_used:
                        for arrays, _rl in outs:
                            new_used = _mark_features_used_trace(
                                new_used, arrays.split_feature,
                                arrays.num_leaves)
                    ret.append(new_used)
                if health_active:
                    ret.append(hv)
                if sample is not None:
                    ret.append(sample)     # what last_sample() reads
                if levels_out:
                    ret.append(tuple(levels))   # LAST: last_levels()
                return tuple(ret)
            self._fused_core = fused      # scanned by the pack path
            # watch_compiles (telemetry/spans.py): launches already run
            # under the train/fused_iter span; the wrapper only notices
            # executable-cache growth and emits compile.end events.
            self._fused_iter = watch_compiles(jax.jit(fused),
                                              "train/fused_iter")

    # ------------------------------------------------------------------ helpers
    def _init_scores_array(self, data: TrainData) -> jnp.ndarray:
        n = data.num_data
        k = self.num_class
        base = np.tile(self.init_scores[None, :], (n, 1)).astype(np.float32)
        if data.init_score is not None:
            ins = np.asarray(data.init_score, np.float32).reshape(n, -1)
            base = base + ins
        if k == 1:
            return jnp.asarray(base[:, 0])
        return jnp.asarray(base)

    def _create_metrics(self) -> List[Metric]:
        from ..metrics import metrics_for_config
        return metrics_for_config(self.cfg)

    # ----------------------------------------------------------------- training
    def _iter_masks(self, grad=None, hess=None):
        """Device row/feature masks for this iteration (cached when static).
        Returns ``(mask, fmask, grads)`` where ``grads`` is the (g, h) device
        pair when it had to be computed anyway (GOSS), else None; leaves
        the sample — the mask and, from the device selection, the in-bag
        row ids — in ``_last_sample``."""
        strategy = self.sample_strategy
        n = self.train_data.num_data
        grads = rows = None
        if strategy.is_goss and not strategy.goss_samples_at(self.iter_):
            mask_dev = self._full_mask     # goss.hpp: not sampled yet
        elif strategy.is_goss:
            if grad is None and self._device_goss == "on":
                # Standalone device GOSS sample (reference goss.hpp):
                # gradients never leave HBM even though this config could
                # not fuse the selection into the iteration dispatch.
                g_dev, h_dev = self._grad_fn(self.scores)
                grads = (g_dev, h_dev)
                mask_dev, rows = self._goss_sample(
                    g_dev, h_dev, self._goss_key, np.int32(self.iter_))
            elif grad is None:
                # Host sampler (tpu_device_goss=off, or auto on a config
                # whose objective already needs per-round host access):
                # pull the gradients and replay the reference's np argsort
                # + np.random rest-sample exactly.
                g_dev, h_dev = self._grad_fn(self.scores)
                grads = (g_dev, h_dev)
                gm = np.asarray(jax.device_get(g_dev)).reshape(n, -1)
                hm = np.asarray(jax.device_get(h_dev)).reshape(n, -1)
                mask_dev = jnp.asarray(strategy.mask(
                    self.iter_, gm.sum(axis=1), hm.sum(axis=1)))
            else:
                gm = np.asarray(grad).reshape(n, -1)
                hm = np.asarray(hess).reshape(n, -1)
                mask_dev = jnp.asarray(strategy.mask(
                    self.iter_, gm.sum(axis=1), hm.sum(axis=1)))
        elif strategy.is_bagging:
            if strategy.needs_resample(self.iter_) or self._bag_mask_dev is None:
                self._bag_mask_dev = jnp.asarray(strategy.mask(self.iter_))
            mask_dev = self._bag_mask_dev
        else:
            mask_dev = self._full_mask
        self._last_sample = (None if mask_dev is self._full_mask
                             else (mask_dev, rows))
        return mask_dev, self._tree_fmask(), grads

    def last_sample(self):
        """The rows the last ``train_one_iter`` grew its trees on:
        ``None`` when it took every row at weight 1, else ``(rows,
        weights)`` as numpy arrays — the in-bag row ids ascending and each
        one's multiplier of its gradient and hessian (1, or GOSS's
        amplification for a drawn row).  What a comparison that recomputes
        a sampled tree is handed (``benchmark/compare_sampled.py``)."""
        if self._last_sample == "packed":
            raise RuntimeError("the packed iterations keep no sample; "
                               "run the one to be read with update()")
        if self._last_sample is None:
            return None
        mask, rows = jax.device_get(self._last_sample)
        mask = np.asarray(mask)
        # a slot of the device selection no row filled holds N
        rows = (np.flatnonzero(mask > 0) if rows is None
                else rows[rows < mask.shape[0]])
        return rows.astype(np.int64), mask[rows]

    def last_levels(self):
        """What the last ``train_one_iter`` grew its trees on under
        quantized training (``use_quantized_grad``): ``None`` when that is
        off, else ``(gq, hq, g_scale, h_scale)`` as numpy arrays — each
        row's int8 gradient and hessian levels and the two float32 scales
        the int32 histograms are rescaled by (a list of them, one per
        class, for ``num_class > 1``).  Reading it is a host read: what a
        comparison that recomputes a quantized tree is handed
        (``benchmark/compare_quantized.py``), never called by update()."""
        if self._quant_key is None:
            return None
        if self._last_levels in ("packed", "unfused"):
            raise RuntimeError(
                f"the {self._last_levels} iterations keep no levels; run the "
                "one to be read with update() on the fused path")
        if self._last_levels is None:
            raise RuntimeError("no iteration has run yet")
        got = [tuple(np.asarray(a) for a in lv)
               for lv in jax.device_get(self._last_levels)]
        return got if self._shape_k else got[0]

    def _tree_fmask(self) -> jnp.ndarray:
        """This iteration's feature mask — the ONE derivation shared by
        ``_iter_masks`` and the fused-GOSS branch of ``train_one_iter``
        (static mask when feature_fraction == 1, per-tree host sample
        otherwise)."""
        return (self._fmask_static if self._fmask_static is not None
                else jnp.asarray(self.feature_sampler.tree_mask(self.iter_)))

    def _store_tree(self, k: int, arrays: TreeArrays,
                    row_leaf: jnp.ndarray) -> None:
        self.dev_models[k].append(arrays)
        self._host_cache[k].append(None)
        if not self.valid_bins:
            return
        with span("train/valid_scores", track_memory=True,
                  iter=self.iter_ + 1):
            for i, vbins in enumerate(self.valid_bins):
                pred = predict_tree_bins_device(
                    _tree_dict(arrays), vbins, self.meta_dev["nan_bins"])
                if self._shape_k:
                    self.valid_scores[i] = \
                        self.valid_scores[i].at[:, k].add(pred)
                else:
                    self.valid_scores[i] = self.valid_scores[i] + pred

    @property
    def wave_fused_active(self) -> bool:
        """Read-only alias of ``self.plan.fused`` (the fused wave kernel
        runs), kept for the tools and tests that read it."""
        return self.plan.fused

    @property
    def fused_path_active(self) -> bool:
        """Does ``train_one_iter`` (without explicit gradients) take the
        fused one-dispatch path?  The ONE predicate shared with
        ``tools/profile_iter.py``'s dispatch census so the census label can
        never disagree with the branch actually taken.  GOSS rides the
        fused dispatch whenever device GOSS is allowed (tpu_device_goss
        auto/on) and CEGB always does (its used-feature vector is device
        state); linear trees still solve leaf models outside it."""
        return (self._fused_iter is not None
                and not (self.sample_strategy.is_goss
                         and self._device_goss == "off")
                and not self.cfg.linear_tree)

    def train_one_iter(self, grad: Optional[np.ndarray] = None,
                       hess: Optional[np.ndarray] = None) -> bool:
        """One boosting iteration (reference ``GBDT::TrainOneIter``).  Returns
        True when no tree could be grown (training should stop).  The host's
        side of it is one iteration record and one ``train/iter`` span
        (telemetry/iters.py); DART and RF override ``_train_one_iter``."""
        with iter_record(self.iter_ + 1) as self._iter_rec:
            return self._train_one_iter(grad, hess)

    def _train_one_iter(self, grad, hess) -> bool:
        cfg = self.cfg
        if grad is None and self.objective is None:
            raise ValueError(
                "objective='custom' requires gradients: pass a callable "
                "objective in params or call update(fobj=...) "
                "(reference LGBM_BoosterUpdateOneIterCustom)")
        from ..resilience import faults
        if faults.nan_grads_due(self.iter_ + 1):
            # fault seam (resilience/faults.py): one NaN score entering
            # this round makes the in-trace gradients non-finite — the
            # exact poison the health sentinel exists to catch
            self._poison_scores()
        used_fused = grad is None and self.fused_path_active
        sampled = self.sample_strategy.goss_samples_at(self.iter_)
        if self.sample_strategy.is_goss and not sampled:
            registry().counter("sample.unsampled_iters").inc()
        goss_in_fused = used_fused and sampled
        self._iter_tag = "[sampled]" if sampled else ""
        if goss_in_fused:
            # The GOSS mask is derived IN-TRACE from the fused iteration's
            # own gradients — no standalone mask dispatch, no host pull.
            mask_dev, goss_grads = self._full_mask, None
            fmask = self._tree_fmask()
            rows = None
        else:
            mask_dev, fmask, goss_grads = self._iter_masks(grad, hess)
            rows = self._last_sample and self._last_sample[1]
        shrink = cfg.learning_rate if cfg.boosting != "rf" else 1.0
        qkey = (jax.random.fold_in(self._quant_key, self.iter_)
                if self._quant_key is not None else None)
        skey = (jax.random.fold_in(self._split_key, self.iter_)
                if self._split_key is not None else None)

        results = []
        if used_fused:
            # Hot path: ONE device dispatch for gradients + all class trees +
            # score updates (+ the in-trace GOSS mask / CEGB used-vector).
            it_arg = np.int32(self.iter_) if goss_in_fused else None
            gkey = self._goss_key if goss_in_fused else None
            used0 = self._cegb_used_dev if self._use_cegb else None
            out = self._dispatch(
                "_fused_iter", self.bins_dev, self.scores, mask_dev,
                fmask, shrink, qkey, skey, it_arg, gkey, used0)
            self._last_sample = None
            if qkey is not None:
                *out, self._last_levels = out
            if goss_in_fused:
                *out, self._last_sample = out
            if self._health_active:
                *out, self._health_pending = out
            if self._use_cegb:
                self.scores, outs, self._cegb_used_dev = out
            else:
                self.scores, outs = out
            results = [(k, a, rl) for k, (a, rl) in enumerate(outs)]
        else:
            if qkey is not None:
                self._last_levels = "unfused"
            if goss_grads is not None:
                g_dev, h_dev = goss_grads
            elif grad is None:
                g_dev, h_dev = self._grad_fn(self.scores)
            else:
                g_dev = jnp.asarray(grad, jnp.float32).reshape(self.scores.shape)
                h_dev = jnp.asarray(hess, jnp.float32).reshape(self.scores.shape)
            for k in range(self.num_class):
                gk = g_dev[:, k] if self._shape_k else g_dev
                hk = h_dev[:, k] if self._shape_k else h_dev
                sk = self.scores[:, k] if self._shape_k else self.scores
                # Key derivation mirrors the fused trace exactly (fold by
                # class only in the multiclass shape), so fused-vs-unfused
                # trees stay bitwise identical under quantized rounding
                # and split smearing.
                qk = (qkey if qkey is None or not self._shape_k
                      else jax.random.fold_in(qkey, k))
                nk = (skey if skey is None or not self._shape_k
                      else jax.random.fold_in(skey, k))
                if cfg.linear_tree:
                    arrays, row_leaf = self._dispatch(
                        "_raw_grow", gk, hk, mask_dev, fmask, qk, nk, rows)
                    new_sk = self._fit_and_store_linear(
                        k, arrays, row_leaf, gk, hk, mask_dev, sk, shrink)
                    if self._shape_k:
                        self.scores = self.scores.at[:, k].set(new_sk)
                    else:
                        self.scores = new_sk
                    continue
                if (self.objective is not None
                        and self.objective.need_renew_tree_output):
                    arrays, row_leaf = self._dispatch(
                        "_raw_grow", gk, hk, mask_dev, fmask, qk, nk, rows)
                    arrays = self._renew_and_shrink(arrays, row_leaf, sk,
                                                    shrink)
                    new_sk = _add_leaf_outputs(sk, row_leaf,
                                               arrays.leaf_value)
                elif self._use_cegb:
                    coupled = self._cegb_coupled_dev * (~self._cegb_used_dev)
                    new_sk, arrays, row_leaf = self._dispatch(
                        "_grow_apply", self.bins_dev, sk, gk, hk, mask_dev,
                        fmask, shrink, coupled, self._cegb_lazy_dev, qk, nk,
                        rows)
                else:
                    new_sk, arrays, row_leaf = self._dispatch(
                        "_grow_apply", self.bins_dev, sk, gk, hk, mask_dev,
                        fmask, shrink, quant_key=qk, split_key=nk,
                        sample_rows=rows)
                if self._shape_k:
                    self.scores = self.scores.at[:, k].set(new_sk)
                else:
                    self.scores = new_sk
                results.append((k, arrays, row_leaf))
            if self._health_active:
                # non-fused fallback (custom grads / renew objectives /
                # linear trees): the same reductions, one small extra
                # dispatch on a path that is already multi-dispatch.
                # Linear trees attach leaf models host-side, so only the
                # scores (which any NaN leaf poisons) are checked there.
                if self._health_eval is None:
                    from ..resilience.health import health_vector
                    self._health_eval = jax.jit(health_vector)
                self._health_pending = self._health_eval(
                    g_dev, h_dev,
                    tuple(a.leaf_value for _k, a, _rl in results),
                    self.scores)
        dispatched(self._iter_rec)     # the counters, after the enqueue
        for k, arrays, row_leaf in results:
            self._store_tree(k, arrays, row_leaf)
        self.iter_ += 1
        if (self._use_cegb and not used_fused
                and self._cegb_coupled_raw.any()):
            # Coupled penalties, non-fused fallback (custom gradients /
            # renew objectives): mark this iteration's split features used
            # with the SAME in-trace update the fused path runs, so the
            # device vector stays the one source of truth.
            for _, arrays, _rl in results:
                self._cegb_used_dev = _mark_features_used(
                    self._cegb_used_dev, arrays.split_feature,
                    arrays.num_leaves)
        nls = [a.num_leaves for _, a, _rl in results] + self._linear_nls
        self._linear_nls = []
        # Deferring the degenerate-stop fetch by one iteration keeps the
        # device queue full (the fetch targets an iteration that finished
        # while the next was dispatched above).  Only sound when iteration
        # t+1 replays t exactly if scores did not change: the fused
        # deterministic path with static row/feature masks and no
        # per-iteration RNG (bagging/GOSS resample, quantize or smearing
        # keys, DART score mutation all break that, as does any path that
        # already syncs the host each iteration).
        # goss_in_fused passes the full mask only as a placeholder — the
        # real mask is recomputed in-trace each iteration, so a stump round
        # would NOT replay identically and the check cannot defer.  Fused
        # CEGB CAN defer: a stump leaves scores AND the used vector
        # unchanged, so iteration t+1 replays t exactly.
        defer = (used_fused and self._deterministic_iters
                 and not goss_in_fused
                 and mask_dev is self._full_mask
                 and self._fmask_static is not None
                 and qkey is None and skey is None)
        if not defer:
            if self._nls_pending is not None:   # drain a deferred backlog
                nls = list(self._nls_pending) + nls
                self._nls_pending = None
            return all(int(x) <= 1 for x in jax.device_get(nls))
        prev, self._nls_pending = self._nls_pending, nls
        if prev is None:
            return False
        # Stopping one iteration late stores at most one extra tree, trained
        # on the stump-shifted scores — a legitimate boosting step, where
        # reference GBDT::TrainOneIter's immediate check stores none.
        return all(int(x) <= 1 for x in jax.device_get(prev))

    # ------------------------------------------------------ iteration packing
    def iter_pack_degrade_reason(self) -> Optional[str]:
        """Why this configuration cannot run the iteration-packed path
        (None = pack-capable).  One enumerable list, mirrored by
        docs/ITER_PACK.md's auto-degrade table."""
        cfg = self.cfg
        if not self._supports_iter_pack:
            return "boosting mode does host work between rounds (dart/rf)"
        if not self._deterministic_iters:
            return "scores are mutated between iterations"
        if self.objective is None:
            return "custom-objective gradients arrive from the host each round"
        if self._fused_iter is None:
            return ("objective needs per-round host access (tree-output "
                    "renewal or host-stochastic gradients)")
        if cfg.linear_tree:
            return ("linear trees read tree structure back each round "
                    "(batched device solve, but per-round host attach)")
        if (self.sample_strategy.is_goss
                and self._device_goss == "off"):
            return ("GOSS uses the host sampler (tpu_device_goss=off); "
                    "device GOSS (auto/on) is pack-capable")
        if self.sample_strategy.is_balanced or cfg.bagging_by_query:
            return "balanced / by-query bagging samples on the host"
        return None

    def iter_pack_plan(self, remaining: int,
                       eval_period: Optional[int] = None):
        """Resolve ``tpu_iter_pack`` into ``(pack_size, use_pack)`` for the
        next ``remaining`` rounds.

        ``eval_period`` is the cadence at which the caller needs per-round
        host evaluation (None = never).  Auto mode (``tpu_iter_pack=0``)
        packs only when it cannot change results: pack-capable configs with
        STATIC row/feature masks (the host-RNG bagging / feature_fraction
        streams are preserved by degrading to the per-round path) and no
        per-round eval consumer.  An explicit ``tpu_iter_pack=K`` forces
        the pack path — bagging / feature_fraction masks then move to
        key-folded device sampling (sampling.bagging_mask_device)."""
        remaining = max(int(remaining), 1)
        requested = int(getattr(self.cfg, "tpu_iter_pack", 0) or 0)
        reason = self.iter_pack_degrade_reason()
        k, use = 1, False
        if reason is not None:
            if requested > 1:
                from ..utils.log import Log
                Log.warning(f"tpu_iter_pack={requested} ignored: {reason}")
        elif requested >= 1:
            k, use = min(requested, remaining), True
        elif (self.sample_strategy.is_bagging
                or self.cfg.feature_fraction < 1.0):
            pass   # auto never swaps the host-RNG sampling streams
        elif eval_period is not None and eval_period <= 1:
            pass   # a per-round eval consumer pins the per-round path
        else:
            k = min(remaining, self._PACK_AUTO_CAP)
            if eval_period is not None:
                k = min(k, eval_period)
            use = k > 1
            if not use:
                k = 1
        # EVERY resolution passes the lockstep gate: a pack-vs-no-pack
        # divergence across processes must fail fast at the allgather, not
        # hang the packing processes inside it.  The payload also carries
        # the in-trace sampling/penalty capabilities — a device-GOSS or
        # fused-CEGB divergence would change the scanned program's
        # collective layout just like a hist_comm divergence would.
        from ..parallel.distributed import assert_pack_lockstep
        return assert_pack_lockstep(
            k, use, hist_comm=self.grower_cfg.hist_comm,
            device_goss=bool(self.sample_strategy.is_goss
                             and self._device_goss != "off"),
            cegb_fused=bool(self._use_cegb
                            and self._fused_iter is not None)), use

    def _pack_fn(self, k: int):
        """Compiled K-round program: ONE ``lax.scan`` over the fused
        iteration (objective gradients -> grow -> shrinkage -> score
        update), emitting (K, ...)-stacked TreeArrays — the whole boosting
        LOOP stays device-resident (arXiv:1806.11248 / arXiv:2005.09148:
        the next throughput factor lives in the loop, not the tree
        build)."""
        fn = self._pack_fns.get(k)
        if fn is not None:
            return fn
        core = self._fused_core
        cfg = self.cfg
        strategy = self.sample_strategy
        n = self.train_data.num_data
        use_bag = strategy.is_bagging
        bag_k = int(n * cfg.bagging_fraction)
        bag_freq = max(cfg.bagging_freq, 1)
        use_ff = cfg.feature_fraction < 1.0
        ff_k = 0
        if use_ff:
            nvalid = int(np.count_nonzero(self.feature_sampler.used))
            ff_k = max(int(np.ceil(nvalid * cfg.feature_fraction)), 1)
        use_quant = self._quant_key is not None
        use_split = self._split_key is not None
        use_goss = strategy.is_goss          # pack-capable => device GOSS
        goss_unsampled = strategy.goss_unsampled_iters
        use_cegb = self._use_cegb
        health_active = self._health_active
        from ..sampling import bagging_mask_device, feature_mask_device

        def packed(bins, scores, iter0, shrink, row_mask, base_fmask,
                   bag_key, ff_key, quant_key, split_key, cegb_used=None):
            def body(carry, it):
                sc, used = carry if use_cegb else (carry, None)
                mask = (bagging_mask_device(bag_key, it // bag_freq, n,
                                            bag_k)
                        if use_bag else row_mask)
                fmask = (feature_mask_device(ff_key, it, base_fmask, ff_k)
                         if use_ff else base_fmask)
                qk = (jax.random.fold_in(quant_key, it) if use_quant
                      else None)
                sk = (jax.random.fold_in(split_key, it) if use_split
                      else None)
                # bag_key IS the GOSS key (PRNGKey(bagging_seed), folded
                # by the absolute iteration in-trace — the same stream the
                # per-round fused iteration uses, so K is scheduling-only).
                def run(goss_it):
                    out = core(bins, sc, mask, fmask, shrink, qk, sk,
                               it=goss_it,
                               goss_key=(None if goss_it is None
                                         else bag_key),
                               cegb_used=used)
                    if use_quant:       # the levels: no pack keeps them
                        out = out[:-1]
                    # a sampled iteration's last output is its sample
                    return out if goss_it is None else out[:-1]

                # the iterations GOSS leaves unsampled run the plain
                # program, the later ones the sampled one: both live in
                # the scanned body, the iteration number picks
                out = (jax.lax.cond(it < goss_unsampled,
                                    lambda: run(None), lambda: run(it))
                       if use_goss else run(None))
                hv = None
                if health_active:
                    *out, hv = out
                if use_cegb:
                    new_sc, outs, new_used = out
                    ys = [tuple(a for a, _rl in outs), new_used]
                    if health_active:
                        ys.append(hv)
                    return (new_sc, new_used), tuple(ys)
                new_sc, outs = out
                if health_active:
                    # the per-round health vectors stack alongside the
                    # trees; commit_round surfaces each at its commit
                    # boundary (docs/ROBUSTNESS.md)
                    return new_sc, (tuple(a for a, _rl in outs), hv)
                return new_sc, tuple(a for a, _rl in outs)

            iters = iter0 + jnp.arange(k, dtype=jnp.int32)
            health_stack = None
            if use_cegb:
                (scores2, _used2), ys = jax.lax.scan(
                    body, (scores, cegb_used), iters)
                if health_active:
                    stacked, used_stack, health_stack = ys
                else:
                    stacked, used_stack = ys
            else:
                scores2, ys = jax.lax.scan(body, scores, iters)
                used_stack = None
                if health_active:
                    stacked, health_stack = ys
                else:
                    stacked = ys
            nls = jnp.stack([t.num_leaves for t in stacked], axis=1)
            return scores2, stacked, nls, used_stack, health_stack

        fn = watch_compiles(jax.jit(packed), f"train/pack_k{k}")
        self._pack_fns[k] = fn
        return fn

    def train_pack(self, k: int):
        """Run up to ``k`` boosting rounds in ONE scanned dispatch.

        Returns ``(rounds, finished)``: ``rounds`` is a list (one entry per
        KEPT round) of per-class TreeArrays, NOT yet stored — the caller
        commits each via :meth:`commit_round`, which lets the engine fire
        callbacks between commits so per-iteration semantics survive
        packing.  The degenerate-stop check runs ONCE per pack from the
        scanned ``num_leaves`` matrix; the stopping round's constant trees
        (and everything after) are trimmed — the exact stop that the
        deferred per-round check in train_one_iter approximates one
        iteration late."""
        with iter_record(self.iter_ + 1, k) as self._iter_rec:
            return self._train_pack(k)

    def _train_pack(self, k: int):
        # a previous pack's trailing vector that nothing consumed (e.g. a
        # callback early-stop at the last committed round) must not be
        # misattributed to this pack's rounds
        self._trailing_health = None
        if self._nls_pending is not None:   # drain a deferred legacy check
            pend = jax.device_get(self._nls_pending)
            self._nls_pending = None
            if all(int(x) <= 1 for x in pend):
                return [], True
        cfg = self.cfg
        from ..resilience import faults
        if faults.nan_grads_due(self.iter_ + 1, self.iter_ + k):
            # fault seam: scores are pack INPUTS, so a target round inside
            # this pack poisons from the pack's first round (faults.py)
            self._poison_scores()
        shrink = cfg.learning_rate if cfg.boosting != "rf" else 1.0
        self._last_sample = None
        self._last_levels = "packed" if self._quant_key is not None else None
        if self.sample_strategy.is_goss:
            self._last_sample = "packed"
            registry().counter("sample.unsampled_iters").inc(max(0, min(
                self.iter_ + k, self.sample_strategy.goss_unsampled_iters)
                - self.iter_))
        base_fmask = (self._fmask_static if self._fmask_static is not None
                      else jnp.asarray(self.feature_sampler.used))
        args = (self.bins_dev, self.scores, np.int32(self.iter_), shrink,
                self._full_mask, base_fmask, self._goss_key, self._ff_key,
                self._quant_key, self._split_key,
                self._cegb_used_dev if self._use_cegb else None)
        with span("train/pack_dispatch", track_memory=True,
                  iter=self.iter_ + 1), _kernel_compile_errors():
            scores2, stacked, nls, used_stack, health_stack = \
                self._pack_fn(k)(*args)
        note_program(self._iter_rec, f"pack_k{k}")
        dispatched(self._iter_rec)
        self.scores = scores2
        with span("train/pack_sync"):
            if health_stack is not None:
                # rides the pack's one host sync; per-round vectors are
                # surfaced by commit_round at each commit boundary
                nls_host, health_host = jax.device_get((nls, health_stack))
                nls_host = np.asarray(nls_host)
            else:
                nls_host = np.asarray(jax.device_get(nls))  # ONE sync/pack
                health_host = None
        dead = np.all(nls_host <= 1, axis=1)
        j0 = int(np.argmax(dead)) if dead.any() else k
        finished = bool(dead.any())
        rounds = [[slice_tree_arrays(stacked[c], j)
                   for c in range(self.num_class)] for j in range(j0)]
        # CEGB: per-round used-vector snapshots; commit_round advances the
        # resident vector through them so an uncommitted tail (mid-pack
        # early stop) never leaks its first-use marks.
        self._pack_used_pending = (
            [used_stack[j] for j in range(j0)] if self._use_cegb else [])
        self._pack_health_pending = (
            [np.asarray(health_host[j], np.float64) for j in range(j0)]
            if health_host is not None else [])
        # Degenerate stop: the stopping round is trimmed (never
        # committed), but its health vector is exactly the evidence a
        # NaN-poisoned round leaves behind — a poisoned gradient grows no
        # tree, so without this the sentinel would see a clean "finished"
        # instead of the divergence.  Kept in its own slot (NOT
        # _health_pending: the committed rounds' vectors pop over that
        # slot first) and consumed by the engine's post-pack check after
        # the last commit's own check has drained.
        self._trailing_health = (
            np.asarray(health_host[j0], np.float64)
            if health_host is not None and j0 < k else None)
        # Rounds at/after the stop are dropped; any that still grew (a
        # later bagging epoch can revive growth after a degenerate round —
        # the reference stops at the FIRST degenerate round regardless)
        # must surrender their in-scan score contributions.
        for j in range(j0, k):
            for c in range(self.num_class):
                if nls_host[j, c] > 1:
                    self._subtract_tree_scores(
                        c, slice_tree_arrays(stacked[c], j))
        return rounds, finished

    def commit_round(self, round_arrays) -> None:
        """Store one pack round's trees (device appends + valid-score
        updates, no host sync) and advance the iteration counter."""
        for c, arrays in enumerate(round_arrays):
            self._store_tree(c, arrays, None)
        if self._pack_used_pending:
            self._cegb_used_dev = self._pack_used_pending.pop(0)
        if self._pack_health_pending:
            self._health_pending = self._pack_health_pending.pop(0)
        self.iter_ += 1

    # ------------------------------------------------------- health sentinel
    def consume_health(self):
        """The last committed round's health vector as a host float64
        array (resilience/health.py HEALTH_SLOTS layout), or None when no
        round produced one since the last call.  Pack rounds surface
        theirs at commit (already host-side, riding the pack's one sync);
        per-round vectors cost one small device transfer here.  After the
        committed vectors drain, the pack's TRAILING vector (the trimmed
        degenerate-stop round, if any) surfaces exactly once."""
        h, self._health_pending = self._health_pending, None
        if h is None:
            h, self._trailing_health = self._trailing_health, None
        if h is None:
            return None
        with span("train/health_fetch"):
            return np.asarray(jax.device_get(h), np.float64)

    def apply_health_recovery(self, salt: int) -> None:
        """Re-fold every device sampling-key stream for recovery
        generation ``salt`` (resilience/health.py apply_recovery): the
        rolled-back run must not replay the exact random draws that
        accompanied the divergence.  Deterministic in (config seeds,
        salt) and derived from the INITIAL keys, so the Nth in-process
        rollback and a fresh ``tpu_health_recovery_salt=N`` resume land
        on identical streams (the bitwise-recovery contract)."""
        salt = int(salt)
        if salt <= 0:
            return
        cfg = self.cfg
        fold = 0x48EA17 + salt          # disjoint from iteration folds
        self._goss_key = jax.random.fold_in(
            jax.random.PRNGKey(cfg.bagging_seed), fold)
        self._ff_key = jax.random.fold_in(
            jax.random.PRNGKey(cfg.feature_fraction_seed), fold)
        if self._quant_key is not None:
            self._quant_key = jax.random.fold_in(
                jax.random.PRNGKey(cfg.seed), fold)
        if self._split_key is not None:
            self._split_key = jax.random.fold_in(
                jax.random.PRNGKey(
                    cfg.extra_seed * 92821 + cfg.feature_fraction_seed),
                fold)
        # pack programs close over nothing key-related (keys are args),
        # but any deferred stop handle refers to pre-rollback trees
        self._nls_pending = None

    def _poison_scores(self) -> None:
        """NaN-poison one train score (the ``nan_grads`` fault seam)."""
        from ..utils.log import Log
        Log.warning(f"fault injection: NaN-poisoning train scores before "
                    f"iteration {self.iter_ + 1} (nan_grads)")
        if self._shape_k:
            self.scores = self.scores.at[0, 0].set(jnp.nan)
        else:
            self.scores = self.scores.at[0].set(jnp.nan)

    # ------------------------------------------------------------ checkpointing
    # DART (host drop/renorm bookkeeping) and RF (averaged scores) carry
    # per-round host state outside the captured set; they opt out until a
    # subclass capture exists (docs/ROBUSTNESS.md).
    _supports_checkpoint = True

    def capture_train_state(self) -> dict:
        """Everything the boosting loop mutates, pulled to the host in ONE
        batched transfer — the payload resilience/checkpoint.py frames and
        publishes atomically.  Only valid at an iter-pack commit boundary:
        mid-pack, ``scores`` already include uncommitted rounds and a
        snapshot would resume into a diverged stream."""
        if not self._supports_checkpoint:
            raise NotImplementedError(
                f"checkpoint/resume is not supported for "
                f"boosting={self.cfg.boosting} (per-round host state is "
                "not captured); train without checkpoint_interval")
        if self._pack_used_pending or self._pack_health_pending:
            raise RuntimeError(
                "capture_train_state called mid-pack (uncommitted rounds "
                "pending); snapshots are only sound at iter-pack commit "
                "boundaries")
        dev = {
            "scores": self.scores,
            "valid_scores": list(self.valid_scores),
            "models": [list(cls) for cls in self.dev_models],
        }
        if self._use_cegb:
            dev["cegb_used"] = self._cegb_used_dev
        host = jax.device_get(dev)
        host.setdefault("cegb_used", None)
        return {
            "iter_": int(self.iter_),
            **host,
            # linear trees live in HOST mirrors (leaf models never go to
            # the device); everything else re-materializes lazily.
            "host_cache": (self._host_cache if self.cfg.linear_tree
                           else None),
            "sample_rng": self.sample_strategy.rng.get_state(),
            "bag_cached": (None if self.sample_strategy._cached is None
                           else np.asarray(self.sample_strategy._cached)),
            "feature_rng": self.feature_sampler.rng.get_state(),
            "linear_nls": [int(x) for x in jax.device_get(self._linear_nls)],
            "nls_pending": (None if self._nls_pending is None else
                            [int(x)
                             for x in jax.device_get(self._nls_pending)]),
            "pred_version": int(self._pred_version),
            "objective": (self.objective.mutable_state()
                          if self.objective is not None else None),
        }

    def restore_train_state(self, state: dict) -> None:
        """Inverse of :meth:`capture_train_state` onto a freshly-built
        booster over the SAME dataset and config — the device RNG keys are
        seed-derived and key-folded by absolute iteration, so restoring
        the host-side state here is sufficient for bitwise continuation."""
        if not self._supports_checkpoint:
            raise NotImplementedError(
                f"checkpoint/resume is not supported for "
                f"boosting={self.cfg.boosting}")
        if len(state["models"]) != self.num_class:
            raise ValueError(
                f"checkpoint has {len(state['models'])} model classes, "
                f"booster has {self.num_class}")
        if tuple(state["scores"].shape) != tuple(self.scores.shape):
            raise ValueError(
                f"checkpoint scores shape {state['scores'].shape} != "
                f"{self.scores.shape}: the snapshot was taken on a "
                "different dataset")
        if len(state["valid_scores"]) != len(self.valid_scores):
            raise ValueError(
                f"checkpoint carries {len(state['valid_scores'])} valid "
                f"sets, booster has {len(self.valid_scores)}")
        self.scores = jnp.asarray(state["scores"])
        self.valid_scores = [jnp.asarray(v) for v in state["valid_scores"]]
        self.dev_models = [[jax.tree.map(jnp.asarray, a) for a in cls]
                           for cls in state["models"]]
        if state.get("host_cache") is not None:
            self._host_cache = [list(c) for c in state["host_cache"]]
        else:
            self._host_cache = [[None] * len(cls) for cls in self.dev_models]
        if self._use_cegb and state.get("cegb_used") is not None:
            self._cegb_used_dev = jnp.asarray(state["cegb_used"])
        self._pack_used_pending = []
        self._pack_health_pending = []
        self._health_pending = None
        self._trailing_health = None
        self.iter_ = int(state["iter_"])
        self.sample_strategy.rng.set_state(state["sample_rng"])
        self.sample_strategy._cached = state["bag_cached"]
        self._bag_mask_dev = (None if state["bag_cached"] is None
                              else jnp.asarray(state["bag_cached"]))
        self.feature_sampler.rng.set_state(state["feature_rng"])
        self._linear_nls = list(state["linear_nls"])
        self._nls_pending = state["nls_pending"]
        self._pred_version = int(state["pred_version"])
        if self.objective is not None and state.get("objective"):
            self.objective.set_mutable_state(state["objective"])

    def discard_rounds(self, rounds) -> None:
        """Drop uncommitted pack rounds (mid-pack early stop): their trees
        were trained inside the same dispatch but must vanish as if
        training had halted per-round.  Stumps carry zero leaf values, so
        subtracting every tree's prediction is exact."""
        self._pack_used_pending = []
        self._pack_health_pending = []
        self._trailing_health = None
        for rnd in rounds:
            for c, arrays in enumerate(rnd):
                self._subtract_tree_scores(c, arrays)

    def _subtract_tree_scores(self, k: int, arrays: TreeArrays) -> None:
        """Remove one uncommitted tree's contribution from the train scores
        (same predict-and-subtract scheme as rollback_one_iter)."""
        pred = predict_tree_bins_device(
            _tree_dict(arrays), self.score_bins_dev,
            self.meta_dev["nan_bins"])
        pred = pred[: self.scores.shape[0]]
        if self._shape_k:
            self.scores = self.scores.at[:, k].add(-pred)
        else:
            self.scores = self.scores - pred

    @property
    def score_bins_dev(self):
        """ORIGINAL-feature-space train bins for on-device tree prediction
        (rollback, DART drop/renorm).  Equals ``bins_dev`` unless EFB is
        active, in which case the original (N, F) matrix is ALSO kept on
        device — an F/G x memory overhead paid only when a consumer (DART,
        rollback) actually needs it."""
        if self.bundles is None:
            if self.grower_cfg.packed4:
                # Tree prediction indexes ORIGINAL feature columns, so the
                # packed matrix cannot be used directly.  Return the cached
                # unpacked matrix (train_data caches it, keeping the object
                # identity DART's pad-trim check relies on) and warn about
                # the extra residency, mirroring the EFB branch below.
                if self.train_data._bins_dev is None:
                    from ..utils.log import Log
                    Log.warning(
                        "4-bit bins + DART/rollback keeps both the packed "
                        "and the byte-per-bin matrices on device; set "
                        "tpu_4bit_bins=false if HBM is tight")
                return self.train_data.bins_device()
            return self.bins_dev
        if self.train_data._bins_dev is None:
            from ..utils.log import Log
            Log.warning(
                "EFB + DART/rollback keeps both the bundled and the "
                "original bin matrices on device; set enable_bundle=false "
                "if HBM is tight")
        return self.train_data.bins_device()

    def _dispatch(self, name, *args, **kw):
        """Dispatch a compiled program by attribute name under a telemetry
        span named for it (host-side instrumentation at the dispatch
        boundary only).  A kernel compile failure is re-raised with the
        explicit opt-outs named (:func:`_kernel_compile_errors`) — never
        retried on another implementation."""
        program = name.lstrip("_")
        with span("train/" + program, track_memory=True,
                  iter=self.iter_ + 1), _kernel_compile_errors():
            out = getattr(self, name)(*args, **kw)
        note_program(self._iter_rec, program + self._iter_tag)
        return out

    def _raw_grow(self, gk, hk, mask_dev, fmask, quant_key=None,
                  split_key=None, sample_rows=None):
        return self.grow(
            self.bins_dev, gk, hk, mask_dev, fmask,
            self.meta_dev["num_bins_per_feature"], self.meta_dev["nan_bins"],
            self.meta_dev["is_categorical"], self.meta_dev["monotone"],
            None, None, quant_key, split_key,
            self._fg_dev, self._fo_dev, sample_rows)

    def _renew_and_shrink(self, arrays: TreeArrays, row_leaf, scores_k,
                          shrink: float) -> TreeArrays:
        """Host percentile leaf renewal (reference ``RenewTreeOutput``,
        L1/Huber/Quantile/MAPE) then shrinkage — branchy host work by design."""
        nl = int(arrays.num_leaves)
        if nl <= 1:
            return arrays._replace(leaf_value=jnp.zeros_like(arrays.leaf_value))
        rl = np.asarray(jax.device_get(row_leaf))
        sc = np.asarray(jax.device_get(scores_k))
        renewed = self.objective.renew_leaf_values(sc, rl, nl)
        L = arrays.leaf_value.shape[0]
        if renewed is not None:
            lv = np.zeros(L, np.float32)
            lv[:nl] = renewed * shrink
            return arrays._replace(
                leaf_value=jnp.asarray(lv),
                internal_value=arrays.internal_value * shrink)
        return _scale_tree_arrays(arrays, shrink)

    def _fit_and_store_linear(self, k: int, arrays: TreeArrays, row_leaf,
                              gk, hk, mask_dev, sk, shrink: float):
        """Linear-tree path (reference ``LinearTreeLearner``): the per-leaf
        weighted normal equations are built by segment-sums over the
        row->leaf assignment and solved in ONE batched device dispatch
        (ops/linear.py) — the per-leaf host Python loop and its six
        gradient/hessian/mask/row pulls are gone; the host touches only
        the tree structure (one batched transfer, as every path does) and
        one (L,)-shaped coefficient readback.  The reference's f64 host
        solve stays behind the models/linear.py facade
        (LIGHTGBM_TPU_HOST_LINEAR=1) for parity debugging and platforms
        where the batched f32 solve is unavailable."""
        from .linear import fit_leaf_linear_models, leaf_path_features, \
            predict_linear

        ub = self.train_data.binned.upper_bounds_padded
        tree = Tree.from_arrays(arrays, ub)  # unshrunk
        arrays = _scale_tree_arrays(arrays, shrink)
        raw = self.train_data.raw
        nan_bins_np = np.asarray(self.train_data.binned.nan_bins)
        if tree.num_leaves <= 1 or raw is None:
            arrays = arrays._replace(
                leaf_value=jnp.zeros_like(arrays.leaf_value))
            tree.leaf_value = np.zeros_like(tree.leaf_value)
            tree.is_linear = True
            tree.leaf_const = np.zeros(max(tree.num_leaves, 1))
            tree.leaf_features = [np.zeros(0, np.int64)] * max(tree.num_leaves, 1)
            tree.leaf_coeff = [np.zeros(0)] * max(tree.num_leaves, 1)
            self.dev_models[k].append(arrays)
            self._host_cache[k].append(tree)
            self._linear_nls.append(tree.num_leaves)
            return sk
        if os.environ.get("LIGHTGBM_TPU_HOST_LINEAR", "0") == "1":
            rl = np.asarray(jax.device_get(row_leaf))
            m = np.asarray(jax.device_get(mask_dev), np.float64)
            g = np.asarray(jax.device_get(gk), np.float64) * m
            h = np.asarray(jax.device_get(hk), np.float64) * m
            # Solve with unshrunk stats, then one Tree::Shrinkage covers
            # leaf values, constants and coefficients (tree.h:201-213).
            fit_leaf_linear_models(
                tree, raw, rl, g, h, self.cfg.linear_lambda,
                np.asarray(self.train_data.binned.is_categorical))
            tree.shrink(shrink)
            pred = predict_linear(tree, rl, raw)
            new_sk = sk + jnp.asarray(pred, jnp.float32)
        else:
            from ..ops.linear import attach_leaf_models, \
                fit_linear_leaves_device, pad_leaf_features
            if getattr(self, "_raw_dev", None) is None:
                self._raw_dev = jnp.asarray(raw, jnp.float32)
            feats = leaf_path_features(
                tree, raw.shape[1],
                np.asarray(self.train_data.binned.is_categorical))
            lf_np, fok_np = pad_leaf_features(feats, arrays.max_leaves)
            lv_np = np.zeros(arrays.max_leaves, np.float32)
            lv_np[: tree.num_leaves] = np.asarray(
                tree.leaf_value[: tree.num_leaves], np.float32)
            coeffs, const, good, pred = fit_linear_leaves_device(
                self._raw_dev, row_leaf, gk, hk, mask_dev,
                jnp.asarray(lf_np), jnp.asarray(fok_np),
                jnp.asarray(lv_np), self.cfg.linear_lambda, shrink)
            new_sk = sk + pred
            co, cs, gd = jax.device_get((coeffs, const, good))
            attach_leaf_models(tree, feats, np.asarray(co),
                               np.asarray(cs), np.asarray(gd))
            tree.shrink(shrink)
        self.dev_models[k].append(arrays)
        self._host_cache[k].append(tree)
        self._linear_nls.append(tree.num_leaves)
        for i, (_name, vdata) in enumerate(self.valids):
            li = tree.predict_leaf_bins(vdata.binned.bins, nan_bins_np)
            vp = jnp.asarray(predict_linear(tree, li, vdata.raw), jnp.float32)
            if self._shape_k:
                self.valid_scores[i] = self.valid_scores[i].at[:, k].add(vp)
            else:
                self.valid_scores[i] = self.valid_scores[i] + vp
        return new_sk

    # ------------------------------------------------- host model materialization
    def host_trees(self, start: int = 0,
                   end: Optional[int] = None) -> List[List[Tree]]:
        """Host Tree mirrors for iterations ``[start, end)`` of every class,
        materializing ONLY that range in one batched transfer — a serve
        plan freezing a 10-iteration slice of a 5000-iteration booster
        must not pull the whole ensemble off the device."""
        n = len(self.dev_models[0]) if self.dev_models else 0
        start = max(int(start), 0)
        end = n if end is None else min(int(end), n)
        pending = [(k, i)
                   for k in range(self.num_class)
                   for i in range(start, end)
                   if self._host_cache[k][i] is None]
        if pending:
            host = jax.device_get([self.dev_models[k][i] for k, i in pending])
            ub = self.train_data.binned.upper_bounds_padded
            for (k, i), a in zip(pending, host):
                self._host_cache[k][i] = Tree.from_arrays(a, ub)
        return [self._host_cache[k][start:end]
                for k in range(self.num_class)]

    @property
    def models(self) -> List[List[Tree]]:
        """Host Tree mirrors of the device ensemble (lazy, batched transfer).
        Returns the LIVE per-class lists (callers index/extend them)."""
        self.host_trees()
        return self._host_cache

    # --------------------------------------------------------------- evaluation
    def eval_set(self, feval=None) -> List[Tuple[str, str, float, bool]]:
        """[(dataset_name, metric_name, value, higher_better)] for all datasets
        (reference ``GBDT::OutputMetric``)."""
        out = []
        datasets = [("training", self.train_data, self.scores)]
        datasets += [
            (name, data, self.valid_scores[i])
            for i, (name, data) in enumerate(self.valids)
        ]
        for name, data, scores in datasets:
            if name == "training" and not self.cfg.is_provide_training_metric \
                    and feval is None and not self._force_train_metric():
                continue
            with span("train/eval"):
                sc = np.asarray(jax.device_get(scores), np.float64)
                for m in self.metrics:
                    out.append((name, m.name,
                                m(data.label, sc, data.weight, data.group),
                                m.higher_better))
        return out

    def _force_train_metric(self) -> bool:
        return False

    def eval_valid(self) -> List[Tuple[str, str, float, bool]]:
        return [e for e in self.eval_set() if e[0] != "training"]

    # --------------------------------------------------------------- prediction
    def predict_raw(self, X: np.ndarray, num_iteration: Optional[int] = None,
                    start_iteration: int = 0) -> np.ndarray:
        """Raw scores for new data.  Iterations are indexed over the COMBINED
        model: a continuation base model's trees come first (reference
        ``GBDT::GetPredictAt`` over the full ensemble), then this booster's."""
        # Negative starts would mean Python wraparound slicing on some paths
        # and a clamp on others (serve plan) — normalize once, here.
        start_iteration = max(int(start_iteration), 0)
        if self.base_model is not None:
            from ..binning import _is_sparse
            nb = self.base_model.iter_
            end = (None if num_iteration is None
                   else start_iteration + num_iteration)
            b_start = min(start_iteration, nb)
            b_num = (nb if end is None else max(min(end, nb), b_start)) - b_start
            if _is_sparse(X):
                from ..binning import predict_dense_chunks
                base = predict_dense_chunks(
                    lambda Xd: self.base_model.predict_raw(
                        Xd, num_iteration=b_num, start_iteration=b_start),
                    X)
            else:
                base = self.base_model.predict_raw(
                    np.asarray(X, np.float64), num_iteration=b_num,
                    start_iteration=b_start)
            own_start = max(start_iteration - nb, 0)
            own_num = (None if end is None
                       else max(end - nb - own_start, 0))
            return base + self._predict_raw_own(X, own_num, own_start)
        return self._predict_raw_own(X, num_iteration, start_iteration)

    def _native_predict_cutoff(self) -> int:
        """Row count at/below which prediction takes the native C++ host
        traversal.  ``tpu_native_predict_max_rows`` is the config knob; the
        LIGHTGBM_TPU_NATIVE_PREDICT_MAX_ROWS env var stays as an override
        (deploy-time tuning without touching model params)."""
        env = os.environ.get("LIGHTGBM_TPU_NATIVE_PREDICT_MAX_ROWS")
        if env is not None:
            return int(env)
        return self.cfg.tpu_native_predict_max_rows

    def _predict_raw_own(self, X: np.ndarray,
                         num_iteration: Optional[int] = None,
                         start_iteration: int = 0) -> np.ndarray:
        """This booster's own trees: the native C++ batch traversal for
        small batches (host binning, no device round-trip), the compiled
        serve plan for large ones (device binning + resident tree pack,
        docs/SERVING.md), and the legacy per-call device scan as fallback."""
        from .. import native
        from ..binning import _is_sparse, predict_dense_chunks

        if _is_sparse(X):
            if self.cfg.linear_tree:
                # linear leaves need raw values; densify in row chunks
                return predict_dense_chunks(
                    lambda Xd: self._predict_raw_linear(
                        Xd, num_iteration, start_iteration), X)
        else:
            X = np.asarray(X)
        if self.cfg.linear_tree:
            return self._predict_raw_linear(X, num_iteration, start_iteration)
        n = X.shape[0]
        k = self.num_class
        use_native = native.available() and n <= self._native_predict_cutoff()
        if not use_native and os.environ.get("LIGHTGBM_TPU_SERVE",
                                             "1") != "0":
            # Device path -> compiled serve plan: the stacked tree pack and
            # binning tables are built once and cached (PredictPlan), so
            # repeat predicts skip re-stacking, re-upload AND host binning.
            # quantize is pinned OFF here: the training-API predict must
            # stay exact fp32 regardless of tpu_serve_quantize — the knob
            # governs serve.Predictor packs, and routing it through this
            # path would make Booster.predict's answers depend on batch
            # size (native cutoff) and knob state (docs/SERVING.md).
            from ..serve import plan_for_model
            plan = plan_for_model(self, num_iteration, start_iteration,
                                  quantize="off")
            if plan is not None:
                if _is_sparse(X):
                    raw = plan.raw_scores_binned(
                        self.train_data.binned.apply(X))
                else:
                    raw = plan.raw_scores(X)
                return raw[:, 0] if k == 1 else raw
        host_bins = self.train_data.binned.apply(X)
        nan_bins_np = self.train_data.binned.nan_bins
        bins = None if use_native else jnp.asarray(host_bins)
        nan_bins = None if use_native else self.meta_dev["nan_bins"]
        out = np.zeros((n, k), np.float64)
        for kk in range(k):
            trees = self.models[kk]
            end = len(trees) if num_iteration is None else min(
                len(trees), start_iteration + num_iteration)
            trees = trees[start_iteration:end]
            if trees and use_native:
                buf = np.zeros(n, np.float64)
                native.predict_bins(host_bins, nan_bins_np, trees, out=buf)
                out[:, kk] += buf
            elif trees:
                stacked = stack_trees(trees, self.cfg.num_leaves,
                                      self.train_data.binned.max_num_bins)
                pred = predict_ensemble_bins_device(stacked, bins, nan_bins)
                out[:, kk] = np.asarray(jax.device_get(pred), np.float64)
            out[:, kk] += self.init_scores[kk]
        return out[:, 0] if k == 1 else out

    def _predict_raw_linear(self, X, num_iteration, start_iteration):
        """Host prediction for linear-leaf models (leaf routing in bin space,
        linear output on raw values)."""
        from .linear import predict_linear

        host_bins = self.train_data.binned.apply(X)
        nan_bins_np = np.asarray(self.train_data.binned.nan_bins)
        X64 = np.asarray(X, np.float64)
        n, k = X.shape[0], self.num_class
        out = np.zeros((n, k), np.float64)
        for kk in range(k):
            trees = self.models[kk]
            end = len(trees) if num_iteration is None else min(
                len(trees), start_iteration + num_iteration)
            for tree in trees[start_iteration:end]:
                if tree.num_leaves <= 1:
                    continue
                li = tree.predict_leaf_bins(host_bins, nan_bins_np)
                if tree.is_linear:
                    out[:, kk] += predict_linear(tree, li, X64)
                else:
                    out[:, kk] += np.asarray(tree.leaf_value, np.float64)[li]
            out[:, kk] += self.init_scores[kk]
        return out[:, 0] if k == 1 else out

    def predict(self, X: np.ndarray, raw_score: bool = False,
                num_iteration: Optional[int] = None,
                start_iteration: int = 0, **kwargs) -> np.ndarray:
        if kwargs.get("pred_early_stop"):
            # Margin-based early exit runs on the host raw-threshold trees
            # (reference Predictor + prediction_early_stop.cpp); the
            # serialized mirror is cached and rebuilt only when trees were
            # added/removed — or rewritten in place (_pred_version) — since.
            from ..binning import _is_sparse
            from ..serialization import load_model_string, model_to_string
            if _is_sparse(X):
                X = np.asarray(X.todense(), np.float64)
            mirror_key = (self.num_trees, self._pred_version)
            cache = getattr(self, "_loaded_mirror", None)
            if cache is None or cache[0] != mirror_key:
                cache = (mirror_key,
                         load_model_string(
                             model_to_string(self, fold_bias=False)))
                self._loaded_mirror = cache
            return cache[1].predict(X, raw_score=raw_score,
                                    num_iteration=num_iteration,
                                    start_iteration=start_iteration, **kwargs)
        raw = self.predict_raw(X, num_iteration, start_iteration)
        if raw_score or self.objective is None:
            return raw
        return np.asarray(jax.device_get(
            self.objective.convert_output(jnp.asarray(raw))))

    def rollback_one_iter(self) -> None:
        """reference ``GBDT::RollbackOneIter`` — drop the last iteration's trees
        and subtract their score contributions."""
        if self.iter_ == 0:
            return
        self._nls_pending = None   # handles refer to the dropped trees
        # Rollback then retraining restores an earlier (iter_, num_trees)
        # pair with DIFFERENT trees — the monotone version bump keeps every
        # post-rollback state uniquely keyed for the serve plan cache.
        self._pred_version += 1
        from .linear import predict_linear
        nan_bins_np = np.asarray(self.train_data.binned.nan_bins)
        for k in range(self.num_class):
            arrays = self.dev_models[k].pop()
            tree = self._host_cache[k].pop()
            if (tree is not None and tree.is_linear
                    and self.train_data.raw is not None):
                li = tree.predict_leaf_bins(self.train_data.binned.bins,
                                            nan_bins_np)
                pred = jnp.asarray(
                    predict_linear(tree, li, self.train_data.raw), jnp.float32)
                if self._shape_k:
                    self.scores = self.scores.at[:, k].add(-pred)
                else:
                    self.scores = self.scores - pred
                for i, (_nm, vdata) in enumerate(self.valids):
                    vli = tree.predict_leaf_bins(vdata.binned.bins,
                                                 nan_bins_np)
                    vp = jnp.asarray(predict_linear(tree, vli, vdata.raw),
                                     jnp.float32)
                    if self._shape_k:
                        self.valid_scores[i] = \
                            self.valid_scores[i].at[:, k].add(-vp)
                    else:
                        self.valid_scores[i] = self.valid_scores[i] - vp
                continue
            dev_tree = _tree_dict(arrays)
            pred = predict_tree_bins_device(
                dev_tree, self.score_bins_dev, self.meta_dev["nan_bins"])
            # bins may carry shard-padding rows (data meshes); scores do not.
            pred = pred[:self.scores.shape[0]]
            if self._shape_k:
                self.scores = self.scores.at[:, k].add(-pred)
            else:
                self.scores = self.scores - pred
            for i, vbins in enumerate(self.valid_bins):
                vp = predict_tree_bins_device(
                    dev_tree, vbins, self.meta_dev["nan_bins"])
                if self._shape_k:
                    self.valid_scores[i] = self.valid_scores[i].at[:, k].add(-vp)
                else:
                    self.valid_scores[i] = self.valid_scores[i] - vp
        self.iter_ -= 1

    @property
    def num_trees(self) -> int:
        own = sum(len(m) for m in self.dev_models)
        if self.base_model is not None:
            own += self.base_model.num_trees
        return own

    def feature_importance(self, importance_type: str = "split") -> np.ndarray:
        """reference ``GBDT::FeatureImportance`` (``gbdt.cpp``)."""
        imp = np.zeros(self.train_data.num_features, np.float64)
        if self.base_model is not None:
            base_imp = self.base_model.feature_importance(importance_type)
            imp[: len(base_imp)] += base_imp
        for cls_models in self.models:
            for tree in cls_models:
                k = tree.num_splits()
                if importance_type == "split":
                    np.add.at(imp, tree.split_feature[:k], 1.0)
                else:
                    np.add.at(imp, tree.split_feature[:k],
                              tree.split_gain[:k].astype(np.float64))
        return imp
