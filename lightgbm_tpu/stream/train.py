"""Streamed training: boost over a :class:`~.store.ShardedDataset` whose
binned matrix NEVER materializes on the device (docs/STREAMING.md).

The driver is a host-driven twin of the in-core round loop: per-row
training state (scores, gradients, the row->leaf partition) stays
device-resident — O(N) bytes, tiny next to the O(N*F) bins — while every
pass over the bins matrix (root histogram, per-split partition + smaller
-sibling histogram) sweeps budget-bounded chunks through the
:class:`~.residency.ResidencyManager`.  The split decisions themselves
run through the grower's stream kit (``models/grower.py``), which reuses
the EXACT state/scan/update functions the in-core layouts trace, and the
chunked histogram accumulation seeds each chunk's pass with the previous
chunk's accumulator (``histogram_from_vals(init=...)``) so the add
sequence replays the in-core one — streamed trees are bitwise-identical
to in-core trees (pinned across fp32/quantized/packed4 x iter-pack x
GOSS in tests/test_stream.py; on TPU's blockwise pallas histogram the
fp32 guarantee needs chunk rows aligned to the kernel's row block, while
quantized integer histograms are unconditionally exact).

Gradient-based residency (``tpu_stream_residency=goss``, the
arXiv:2005.09148 sampling design riding the PR-5 device-GOSS machinery):
the per-iteration device GOSS mask selects the sampled slice, ONLY those
rows' bins are gathered host-side and uploaded compact, and the in-core
grower trains on the compact slice; one routing sweep then updates every
row's partition/scores.  Iteration packing degrades to per-round
dispatches here (reason "streamed residency") — pack size is
scheduling-only (K pinned bitwise == K=1 since PR 1), so streamed trees
still match in-core ``tpu_iter_pack=K`` training bitwise.
"""

from __future__ import annotations

import copy
import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np

from ..telemetry import iter_record, note_program
from ..utils.log import Log
from .residency import ResidencyManager, pack_bins4_host
from .store import ShardedDataset

RESIDENCY_MODES = ("auto", "chunks", "goss")


def _stream_train_data_cls():
    from ..dataset import TrainData

    @dataclasses.dataclass
    class _StreamTrainData(TrainData):
        """``TrainData`` over a zero-row bins placeholder that still
        reports the store's row count — the GBDT constructor sizes
        scores/masks from ``num_data`` while ``bins_device()`` uploads
        the empty placeholder (the real bins stream through the
        residency manager).  Valid sets referencing this dataset bin
        through the ordinary mapper ``apply`` path unchanged."""

        stream_rows: int = 0

        @property
        def num_data(self) -> int:  # type: ignore[override]
            return self.stream_rows

        def build_bundles(self, cfg):  # noqa: ARG002
            # EFB bundle discovery would run over the zero-row
            # placeholder; streaming shapes never bundle
            self.bundles = None
            return None

    return _StreamTrainData


def stream_train_data(store: ShardedDataset, cfg):
    """A ``TrainData`` over the store's metadata with a zero-row bins
    placeholder; ``save_binary`` and raw-data consumers are unsupported
    by construction (the matrix lives in the store)."""
    mono = store.monotone
    if mono is None and cfg.monotone_constraints:
        mono = np.zeros(store.num_features, np.int32)
        mc = np.asarray(cfg.monotone_constraints, np.int32)
        mono[: len(mc)] = mc
    init = store.init_score
    return _stream_train_data_cls()(
        binned=store.binned_meta(),
        stream_rows=store.num_data,
        label=np.asarray(store.label),
        weight=(None if store.weight is None
                else np.asarray(store.weight, np.float32)),
        group=(None if store.group is None
               else np.asarray(store.group, np.int64)),
        position=store.position,
        init_score=None if init is None else np.asarray(init),
        feature_names=store.feature_names,
        monotone_constraints=(None if mono is None
                              else np.asarray(mono, np.int32)),
        raw=None)


class StreamDataset:
    """Duck-typed ``Dataset`` over a shard store: ``construct()`` yields
    the placeholder-bins TrainData; everything raw-data-dependent
    (subset, add_features_from, save_binary) is absent by design."""

    def __init__(self, store: Union[str, ShardedDataset],
                 params: Optional[Dict[str, Any]] = None,
                 init_score: Optional[np.ndarray] = None):
        self.store = (store if isinstance(store, ShardedDataset)
                      else ShardedDataset.open(store))
        self.params = dict(params or {})
        # EFB bundle discovery needs the full matrix; it must never run
        # over the zero-row placeholder (train_streamed warns on an
        # explicit request)
        self.params["enable_bundle"] = False
        self.label = np.asarray(self.store.label)
        self.weight = self.store.weight
        self.group = self.store.group
        self.position = self.store.position
        self.init_score = init_score            # overrides the store's
        self.reference = None
        self.free_raw_data = False
        self.data = np.zeros((0, self.store.num_features))
        self._train_data = None

    def construct(self, params: Optional[Dict[str, Any]] = None):
        if self._train_data is None:
            from ..config import Config
            merged = dict(self.params)
            merged.update(params or {})
            td = stream_train_data(self.store, Config(merged))
            if self.init_score is not None:
                td.init_score = np.asarray(self.init_score)
            self._train_data = td
        return self._train_data

    def num_data(self) -> int:
        return self.store.num_data

    def num_feature(self) -> int:
        return self.store.num_features

    def get_label(self):
        return self.label


def stream_degrade_reason(gbdt) -> Optional[str]:
    """Why this booster cannot train streamed (None = capable) — the
    stream twin of ``iter_pack_degrade_reason``, one enumerable list."""
    reason = gbdt.plan.stream_reason
    if reason is not None:
        return reason
    if gbdt.cfg.boosting != "gbdt":
        return ("boosting mode does host work between rounds "
                f"({gbdt.cfg.boosting})")
    if gbdt.cfg.linear_tree:
        return "linear trees need the raw matrix for leaf solves"
    if gbdt.objective is None:
        return "custom objectives feed gradients from the host"
    if gbdt.objective.need_renew_tree_output:
        return "objective renews tree outputs from host state per round"
    if gbdt.objective.stochastic_gradients:
        return "objective draws host-stochastic gradients per round"
    return None


class StreamTrainer:
    """Per-round streamed boosting over one booster + store."""

    def __init__(self, booster, store: ShardedDataset,
                 budget_bytes: Optional[int] = None):
        import jax
        import jax.numpy as jnp
        self._jax, self._jnp = jax, jnp
        g = booster._gbdt
        reason = stream_degrade_reason(g)
        if reason is not None:
            raise ValueError(f"streamed training unsupported: {reason}")
        self.booster = booster
        self.g = g
        self.store = store
        cfg = g.cfg
        if budget_bytes is None:
            budget_bytes = int(cfg.tpu_stream_budget_mb * (1 << 20))
        self.budget_bytes = budget_bytes
        mode = str(cfg.tpu_stream_residency).lower()
        if mode not in RESIDENCY_MODES:
            raise ValueError(
                f"tpu_stream_residency={cfg.tpu_stream_residency!r}: "
                f"expected one of {', '.join(RESIDENCY_MODES)}")
        strategy = g.sample_strategy
        # device-GOSS stream parity: the in-core run derives its mask
        # in-trace (auto, fused-capable) or via the standalone device
        # dispatch (on) — both key-fold PRNGKey(bagging_seed) by the
        # absolute iteration, which is the stream we replay here.
        self._device_goss = (strategy.is_goss
                             and g._device_goss != "off"
                             and (g.fused_path_active
                                  or g._device_goss == "on"))
        self.residency = "chunks"
        if mode == "goss":
            if not (strategy.is_goss and self._device_goss):
                Log.warning(
                    "tpu_stream_residency=goss needs "
                    "data_sample_strategy=goss with device GOSS "
                    "(tpu_device_goss auto/on); using chunks residency")
            elif cfg.use_quantized_grad and cfg.stochastic_rounding:
                Log.warning(
                    "tpu_stream_residency=goss with stochastically-"
                    "rounded quantized gradients cannot reproduce in-core "
                    "trees (per-row rounding keys are position-dependent "
                    "on the compact slice); using chunks residency")
            else:
                self.residency = "goss"
        packed4 = bool(g.grower_cfg.packed4)
        # goss residency gathers/routes UNPACKED rows (the compact grow
        # re-packs host-side when the grower wants nibbles)
        self.rm = ResidencyManager(
            store, budget_bytes,
            packed4=packed4 and self.residency == "chunks",
            prefetch=bool(cfg.tpu_stream_prefetch))
        self.kit = g.grow.stream_kit(store.num_features)
        self._C = self.rm.plan.chunk_rows
        meta = g.meta_dev
        self._meta4 = (meta["num_bins_per_feature"], meta["nan_bins"],
                       meta["is_categorical"], meta["monotone"])
        C = self._C
        self._slice_vals = jax.jit(
            lambda v, lo: jax.lax.dynamic_slice(v, (lo, 0), (C, 3)))
        self._pad_vals = jax.jit(
            lambda v: jnp.pad(v, ((0, C), (0, 0))))
        self._init_rl = jax.jit(
            lambda count: jnp.where(jnp.arange(C, dtype=jnp.int32) < count,
                                    0, -1).astype(jnp.int32))
        self._route = jax.jit(self._route_impl)
        self._goss_grow = jax.jit(getattr(g.grow, "raw", g.grow))

        def _epilogue(scores_k, arrays, row_leaf, shrink):
            # the exact grow_apply epilogue graph — including its
            # optimization_barrier, which pins the score update to
            # "materialized shrunk leaf values, one exact add per row"
            # in EVERY program (models/gbdt.py grow_apply documents why)
            grew = arrays.num_leaves > 1
            lv = jnp.where(grew, arrays.leaf_value * shrink, 0.0)
            lv = jax.lax.optimization_barrier(lv)
            arrays = arrays._replace(
                leaf_value=lv,
                internal_value=arrays.internal_value * shrink)
            return scores_k + lv[row_leaf], arrays

        self._epilogue = jax.jit(_epilogue)
        self._renew = jax.jit(self._renew_impl) \
            if (g.grower_cfg.quantized and g.grower_cfg.quant_renew_leaf) \
            else None
        if self.residency == "goss":
            top_k, other_k, _amp = strategy.goss_constants()
            self._goss_smax = top_k + other_k
            cols = store.num_features
            if packed4:
                cols = (cols + 1) // 2
            compact = self._goss_smax * cols * store.bins_dtype.itemsize
            if compact > budget_bytes:
                raise ValueError(
                    f"tpu_stream_budget_mb too small for goss residency: "
                    f"the sampled slice is {compact / 1e6:.1f}MB "
                    f"(top_rate+other_rate of {store.num_data} rows)")
            self.goss_resident_bytes = compact
        else:
            self.goss_resident_bytes = 0

    # -------------------------------------------------------------- helpers
    def _route_impl(self, tree, bins_c, nan_bins):
        """Leaf index per chunk row by bin-space traversal — the same
        predicate sequence the partition applies, so routed row_leaf
        matches the grower's partition exactly."""
        jnp = self._jnp
        import jax
        C = bins_c.shape[0]
        rows = jnp.arange(C, dtype=jnp.int32)
        start = jnp.where(tree.num_leaves > 1, 0, -1)
        cur = jnp.full(C, start, jnp.int32)

        def step(_, cur):
            nd = jnp.maximum(cur, 0)
            feat = tree.split_feature[nd]
            col = bins_c[rows, feat].astype(jnp.int32)
            is_nan = col == nan_bins[feat]
            is_cat = tree.is_cat[nd]
            go_left = jnp.where(is_cat, tree.cat_mask[nd, col],
                                col <= tree.split_bin[nd])
            go_left = jnp.where(is_nan & ~is_cat, tree.default_left[nd],
                                go_left)
            nxt = jnp.where(go_left, tree.left_child[nd],
                            tree.right_child[nd])
            return jnp.where(cur < 0, cur, nxt)

        depth = max(int(tree.split_feature.shape[0]), 1)
        cur = jax.lax.fori_loop(0, depth, step, cur)
        return ~jnp.minimum(cur, -1)           # ~cur for leaves; stump -> 0

    def _renew_impl(self, arrays, row_leaf, gk, hk, mask):
        """quant_train_renew_leaf over the FULL row partition — the exact
        _grow_impl epilogue (reference RenewIntGradTreeOutput)."""
        import jax
        jnp = self._jnp
        from ..ops.split import leaf_output
        L = self.kit.max_leaves
        g = gk * mask
        h = hk * mask
        g_leaf = jax.ops.segment_sum(g, row_leaf, num_segments=L)
        h_leaf = jax.ops.segment_sum(h, row_leaf, num_segments=L)
        renewed = leaf_output(g_leaf, h_leaf, self.g.grower_cfg.split)
        active = jnp.arange(L) < arrays.num_leaves
        return arrays._replace(
            leaf_value=jnp.where(active, renewed, 0.0),
            leaf_weight=jnp.where(active, h_leaf, 0.0))

    def _iter_inputs(self):
        """(mask, fmask, (g, h) or None) for this round, replaying the
        in-core derivations/key streams exactly."""
        import jax
        g = self.g
        strategy = g.sample_strategy
        if (self._device_goss
                and strategy.goss_samples_at(g.iter_)):
            from ..sampling import goss_mask_device
            n = g.train_data.num_data
            g_dev, h_dev = g._grad_fn(g.scores)
            gs = g_dev.reshape(n, -1).sum(axis=1)
            hs = h_dev.reshape(n, -1).sum(axis=1)
            top_k, other_k, amp = strategy.goss_constants()
            key = jax.random.fold_in(g._goss_key, g.iter_)
            mask = goss_mask_device(gs, hs, key, top_k, other_k, amp)
            return mask, g._tree_fmask(), (g_dev, h_dev)
        mask, fmask, grads = g._iter_masks()
        return mask, fmask, grads

    # --------------------------------------------------------- chunked grow
    def _grow_chunked(self, gk, hk, mask, fmask, qk, nk):
        import jax
        jnp = self._jnp
        kit, rm = self.kit, self.rm
        g = self.g
        vals, scale3 = kit.prep(gk, hk, mask, qk)
        vals_big = self._pad_vals(vals)
        meta4 = self._meta4
        acc = jnp.zeros(kit.hist_shape, kit.hist_dtype)
        counts = []
        for ci, lo, hi, bins_c in rm.sweep():
            acc = kit.chunk_root(acc, bins_c,
                                 self._slice_vals(vals_big, lo), hi - lo)
            counts.append((lo, hi))
        st = kit.init(acc, jnp.asarray(g.train_data.num_data, jnp.int32),
                      scale3, meta4, fmask, nk)
        rl = [self._init_rl(hi - lo) for lo, hi in counts]
        nl, mg = jax.device_get(kit.probe(st))
        L = kit.max_leaves
        while int(nl) < L and float(mg) > -np.inf:
            sel = kit.select(st)
            h = jnp.zeros(kit.hist_shape, kit.hist_dtype)
            for ci, lo, hi, bins_c in rm.sweep():
                h, rl[ci] = kit.chunk_step(
                    h, bins_c, self._slice_vals(vals_big, lo), rl[ci],
                    sel, meta4[1])
            st = kit.apply(st, sel, h, scale3, meta4, fmask)
            nl, mg = jax.device_get(kit.probe(st))
        arrays = kit.finish(st)
        row_leaf = jnp.concatenate(
            [rl[ci][: hi - lo] for ci, (lo, hi) in enumerate(counts)])
        if self._renew is not None:
            arrays = self._renew(arrays, row_leaf, gk, hk, mask)
        return arrays, row_leaf

    # ------------------------------------------------------------ goss grow
    def _grow_goss(self, gk, hk, mask, fmask, qk, nk):
        """Gradient-based residency: only the GOSS-sampled slice's bins go
        to the device; the in-core grower trains on the compact slice and
        one routing sweep rebuilds every row's partition."""
        import jax
        jnp = self._jnp
        g, rm = self.g, self.rm
        S = self._goss_smax
        mask_np = np.asarray(jax.device_get(mask))
        idx = np.nonzero(mask_np > 0.0)[0][:S]
        bins_host = rm.gather_rows(idx)
        if g.grower_cfg.packed4:
            bins_host = pack_bins4_host(bins_host)
        pad = S - bins_host.shape[0]
        if pad:
            bins_host = np.pad(bins_host, ((0, pad), (0, 0)))
        bins_dev = jax.device_put(bins_host)
        idx_dev = jnp.asarray(
            np.pad(idx, (0, pad)).astype(np.int32))
        valid = jnp.arange(S) < len(idx)
        gk_c = jnp.where(valid, gk[idx_dev], 0.0)
        hk_c = jnp.where(valid, hk[idx_dev], 0.0)
        mask_c = jnp.where(valid, mask[idx_dev], 0.0)
        meta4 = self._meta4
        try:
            arrays, _rl_comp = self._goss_grow(
                bins_dev, gk_c, hk_c, mask_c, fmask, *meta4,
                None, None, qk, nk, None, None)
        finally:
            # drop the compact slice deterministically even when the
            # grow dispatch raises — the budget accounting's buffer
            try:
                bins_dev.delete()
            except Exception:  # noqa: BLE001
                pass
        # routing sweep: full-partition row_leaf chunk-by-chunk (the
        # same per-node predicates the partition applies)
        rls = []
        for ci, lo, hi, bins_c in rm.sweep():
            rls.append(self._route(arrays, bins_c, meta4[1])[: hi - lo])
        row_leaf = jnp.concatenate(rls)
        return arrays, row_leaf

    # ---------------------------------------------------------------- round
    def train_round(self) -> bool:
        """One streamed boosting round; True = degenerate stop (no tree
        grew) — the reference ``TrainOneIter`` contract, checked per
        round (the in-core fused path may defer this check by one
        iteration; streamed never defers)."""
        with iter_record(self.g.iter_ + 1) as rec:
            finished = self._train_round()
            note_program(rec, "stream_round")   # its last fetch is back
            return finished

    def _train_round(self) -> bool:
        import jax
        jnp = self._jnp
        g = self.g
        cfg = g.cfg
        mask, fmask, grads = self._iter_inputs()
        if grads is None:
            g_dev, h_dev = g._grad_fn(g.scores)
        else:
            g_dev, h_dev = grads
        shrink = cfg.learning_rate if cfg.boosting != "rf" else 1.0
        qkey = (jax.random.fold_in(g._quant_key, g.iter_)
                if g._quant_key is not None else None)
        skey = (jax.random.fold_in(g._split_key, g.iter_)
                if g._split_key is not None else None)
        # goss residency holds the SAMPLED slice: the iterations GOSS
        # leaves unsampled stream every chunk like any unsampled round
        grow = (self._grow_goss if self.residency == "goss"
                and g.sample_strategy.goss_samples_at(g.iter_)
                else self._grow_chunked)
        results = []
        for k in range(g.num_class):
            gk = g_dev[:, k] if g._shape_k else g_dev
            hk = h_dev[:, k] if g._shape_k else h_dev
            sk = g.scores[:, k] if g._shape_k else g.scores
            qk = (qkey if qkey is None or not g._shape_k
                  else jax.random.fold_in(qkey, k))
            nk = (skey if skey is None or not g._shape_k
                  else jax.random.fold_in(skey, k))
            arrays, row_leaf = grow(gk, hk, mask, fmask, qk, nk)
            new_sk, arrays = self._epilogue(sk, arrays, row_leaf,
                                            np.float32(shrink))
            if g._shape_k:
                g.scores = g.scores.at[:, k].set(new_sk)
            else:
                g.scores = new_sk
            results.append((k, arrays, row_leaf))
        for k, arrays, row_leaf in results:
            g._store_tree(k, arrays, row_leaf)
        g.iter_ += 1
        nls = [a.num_leaves for _k, a, _rl in results]
        return all(int(x) <= 1 for x in jax.device_get(nls))

    def stats(self) -> dict:
        out = self.rm.stats()
        out["residency"] = self.residency
        out["goss_resident_bytes"] = self.goss_resident_bytes
        return out

    def close(self) -> None:
        self.rm.close()


def base_scores_over_store(booster, store: ShardedDataset) -> np.ndarray:
    """f64 raw scores of a dataset-backed booster over every store row,
    by bin-space routing of its host tree mirrors — accumulated in the
    same (init + per-tree, iteration-major-per-class) f64 order as
    ``LoadedModel.predict_raw``, so a streamed continuation's init fold
    is bitwise the in-core ``engine.train(init_model=...)`` fold."""
    g = booster._gbdt
    if getattr(g, "base_model", None) is not None:
        raise ValueError(
            "base_scores_over_store cannot route a chained continuation "
            "booster (its base model carries raw-value trees only); pass "
            "init_model_scores computed at ingest "
            "(stream.ContinualSession maintains them incrementally)")
    g.train_data.binned.mappers  # noqa: B018 — dataset-backed check
    k = g.num_class
    n = store.num_data
    out = np.tile(np.asarray(g.init_scores, np.float64)[None, :k], (n, 1))
    nan_bins = np.asarray(g.train_data.binned.nan_bins)
    models = g.models
    iters = min(len(m) for m in models) if models else 0
    for lo, hi, bins in store.iter_shards():
        bins = np.asarray(bins)
        for kk in range(k):
            for t in range(iters):
                tree = models[kk][t]
                leaf = tree.predict_leaf_bins(bins, nan_bins)
                out[lo:hi, kk] += np.asarray(tree.leaf_value,
                                             np.float64)[leaf]
    return out[:, 0] if k == 1 else out


def train_streamed(
    params: Dict[str, Any],
    store: Union[str, ShardedDataset],
    num_boost_round: int = 100,
    valid_sets: Optional[Sequence] = None,
    valid_names: Optional[Sequence[str]] = None,
    feval=None,
    callbacks: Optional[List] = None,
    init_model=None,
    init_model_scores: Optional[np.ndarray] = None,
    resume_from: Optional[str] = None,
):
    """Train a booster out-of-core over a shard store (the streaming twin
    of ``engine.train``).  Supports valid sets (in-core), after-callbacks
    (early stopping, eval recording), ``checkpoint_interval`` snapshots
    at round boundaries, ``resume_from`` bitwise continuation, and
    ``init_model`` continuation (the base model's raw scores over the
    store fold into the init score — supplied via ``init_model_scores``
    or computed by :func:`base_scores_over_store`).  Returns the Booster
    with ``booster._stream_stats`` carrying the residency counters."""
    from .. import callback as callback_mod
    from .. import telemetry as telemetry_mod
    from ..basic import Booster
    from ..callback import CallbackEnv, EarlyStopException
    from ..resilience import faults

    if isinstance(store, str):
        store = ShardedDataset.open(store)
    params = copy.deepcopy(params)
    # Early composition gate — BEFORE any booster construction touches
    # the placeholder dataset (e.g. linear trees would reach for the raw
    # matrix inside the GBDT constructor).
    from ..config import Config
    cfg0 = Config(dict(params))
    if cfg0.linear_tree:
        raise ValueError("streamed training unsupported: linear trees "
                         "need the raw matrix for leaf solves")
    if cfg0.boosting != "gbdt":
        raise ValueError("streamed training unsupported: boosting="
                         f"{cfg0.boosting} does host work between rounds")
    if cfg0.enable_bundle and "enable_bundle" in params:
        Log.warning("streamed training disables EFB bundling (bundle "
                    "discovery needs the full matrix at build time)")
    # EFB off by construction: bundle discovery would run over the
    # zero-row placeholder and is meaningless for the dense streaming
    # shapes; in-core comparisons on dense data never bundle either.
    params["enable_bundle"] = False
    if "num_iterations" in params or "num_boost_round" in params:
        num_boost_round = int(params.pop(
            "num_boost_round", params.pop("num_iterations",
                                          num_boost_round)))
    early_stopping_rounds = None
    for alias in ("early_stopping_round", "early_stopping_rounds",
                  "early_stopping", "n_iter_no_change"):
        if params.get(alias):
            early_stopping_rounds = int(params[alias])
    first_metric_only = bool(params.get("first_metric_only", False))
    es_min_delta = float(params.get("early_stopping_min_delta", 0.0))

    valid_sets = list(valid_sets or [])
    names = list(valid_names or [])
    valid_pairs = [(names[i] if i < len(names) else f"valid_{i}", vs)
                   for i, vs in enumerate(valid_sets)]

    base = None
    train_init = None
    if init_model is not None:
        from ..serialization import LoadedModel, load_model_string
        if isinstance(init_model, Booster):
            base = load_model_string(init_model.model_to_string())
        elif isinstance(init_model, LoadedModel):
            base = init_model
        else:
            with open(init_model) as fh:
                base = load_model_string(fh.read())
        if init_model_scores is not None:
            train_init = np.asarray(init_model_scores, np.float64)
        elif isinstance(init_model, Booster):
            train_init = base_scores_over_store(init_model, store)
        else:
            raise ValueError(
                "streamed continuation from a serialized model needs "
                "init_model_scores (raw base scores over the store rows) "
                "— a text model carries raw-value trees the store's "
                "binned rows cannot route")
        if store.init_score is not None:
            train_init = (train_init.reshape(store.num_data, -1)
                          + np.asarray(store.init_score,
                                       np.float64).reshape(
                              store.num_data, -1))
        # valid sets hold raw data: fold exactly as engine.train does
        folded = []
        for nm, vs in valid_pairs:
            td_ok = getattr(vs, "data", np.zeros(0))
            if not getattr(td_ok, "size", 0):
                raise ValueError(
                    "init_model continuation needs raw feature data in "
                    f"valid set {nm!r} to fold base predictions")
            out = copy.copy(vs)
            pred = np.asarray(base.predict_raw(np.asarray(vs.data,
                                                          np.float64)),
                              np.float64)
            if vs.init_score is not None:
                pred = pred + np.asarray(vs.init_score,
                                         np.float64).reshape(pred.shape)
            out.init_score = pred
            out._train_data = None
            folded.append((nm, out))
        valid_pairs = folded

    sds = StreamDataset(store, params=params, init_score=train_init)
    # every valid set must bin through the STORE's frozen mappers —
    # re-point references at the stream dataset (shallow copies keep the
    # caller's Datasets untouched)
    repointed = []
    for nm, vs in valid_pairs:
        if vs.reference is not sds:
            vs = copy.copy(vs)
            vs.reference = sds
            vs._train_data = None
        repointed.append((nm, vs))
    valid_pairs = repointed
    booster = Booster(params=params, train_set=sds,
                      valid_sets=valid_pairs, base_model=base)
    trainer = StreamTrainer(booster, store)
    cfg = booster.cfg
    if cfg.tpu_health_policy not in ("off", "warn"):
        Log.warning(
            f"tpu_health_policy={cfg.tpu_health_policy} is not enforced "
            "on the streamed path (no in-dispatch health vector); "
            "training continues unguarded")

    cbs = list(callbacks or [])
    if early_stopping_rounds is not None and valid_pairs:
        # the same kwargs engine.train resolves from these params — a
        # config moved between the two surfaces must stop identically
        cbs.append(callback_mod.early_stopping(
            early_stopping_rounds, first_metric_only=first_metric_only,
            verbose=params.get("verbosity", 1) > 0,
            min_delta=es_min_delta))
    if any(getattr(cb, "before_iteration", False) for cb in cbs):
        Log.warning("streamed training ignores before-iteration "
                    "callbacks (reset_parameter schedules)")
    cbs_after = sorted(
        (cb for cb in cbs if not getattr(cb, "before_iteration", False)),
        key=lambda cb: getattr(cb, "order", 0))
    cb_periods = [p for p in (int(getattr(cb, "eval_period", 1))
                              for cb in cbs_after) if p > 0]
    if feval is not None:
        cb_periods.append(1)

    def _needs_eval(it: int) -> bool:
        return any((it + 1) % p == 0 for p in cb_periods)

    tel = telemetry_mod.train_session(cfg)
    booster._ckpt_eval_history = []
    start_it = 0
    n_base = base.iter_ if base is not None else 0
    if resume_from is not None:
        from ..resilience import checkpoint as checkpoint_mod
        try:
            start_it = checkpoint_mod.restore(booster, resume_from)
            for it_h, evals_h in booster._ckpt_eval_history:
                if it_h >= start_it:
                    continue
                for cb in cbs_after:
                    cb(CallbackEnv(booster, params, it_h, 0,
                                   num_boost_round, evals_h))
        except EarlyStopException as e:
            booster.best_iteration = e.best_iteration + 1 + n_base
            booster.best_score = e.best_score
            tel.close()
            trainer.close()
            booster._stream_stats = trainer.stats()
            return booster
        except BaseException:
            tel.close()
            trainer.close()
            raise
    ckpt_interval = cfg.checkpoint_interval
    ckpt_dir = cfg.checkpoint_dir or \
        f"{cfg.output_model or 'LightGBM_model.txt'}.ckpt"
    last_ckpt = start_it

    def _fire_after(it: int) -> bool:
        if not _needs_eval(it):
            return False
        evals = booster._evals(feval)
        if ckpt_interval > 0 and cbs_after:
            booster._ckpt_eval_history.append((it, evals))
        try:
            for cb in cbs_after:
                cb(CallbackEnv(booster, params, it, 0,
                               num_boost_round, evals))
        except EarlyStopException as e:
            booster.best_iteration = e.best_iteration + 1 + n_base
            booster.best_score = e.best_score
            return True
        return False

    it = start_it
    t0 = time.perf_counter()
    tel.emit("train.start", num_boost_round=num_boost_round,
             start_iteration=it, objective=cfg.objective,
             boosting=cfg.boosting, num_class=booster._gbdt.num_class,
             rows=store.num_data, features=store.num_features,
             packed=False, pack_size=1,
             pack_degrade_reason="streamed residency",
             health_policy=cfg.tpu_health_policy,
             checkpoint_interval=ckpt_interval,
             valid_sets=[nm for nm, _ in valid_pairs],
             stream=trainer.stats())
    try:
        while it < num_boost_round:
            finished = trainer.train_round()
            faults.maybe_kill(it + 1)
            stopped = _fire_after(it)
            it += 1
            ckpt_s = None
            if (not (stopped or finished) and ckpt_interval > 0
                    and it // ckpt_interval > last_ckpt // ckpt_interval):
                from ..resilience import checkpoint as checkpoint_mod
                t_c0 = time.perf_counter()
                checkpoint_mod.save_snapshot(booster, ckpt_dir,
                                             keep=cfg.checkpoint_keep)
                ckpt_s = time.perf_counter() - t_c0
                last_ckpt = it
                tel.emit("train.checkpoint", iteration=it, dir=ckpt_dir,
                         seconds=round(ckpt_s, 6))
            tel.emit_iter(it, 1, health=None,
                          checkpoint_s=(None if ckpt_s is None
                                        else round(ckpt_s, 6)))
            if stopped or finished:
                break
    finally:
        booster._stream_stats = trainer.stats()
        tel.flush_iters()
        tel.emit("train.end", iterations=int(booster._gbdt.iter_),
                 elapsed_s=round(time.perf_counter() - t0, 6),
                 best_iteration=int(booster.best_iteration),
                 health=None,
                 host_peak_rss_mb=round(
                     telemetry_mod.host_peak_rss_mb(), 1),
                 spans=tel.span_delta(), stream=booster._stream_stats)
        tel.close()
        trainer.close()
    return booster
